"""Pallas TPU kernels for the MalStone/MalGen compute hot spots.

The paper's performance-critical loops are (a) the Reducer's group-by-site
aggregation (the whole point of the middleware comparison) and (b) MalGen's
power-law site sampling. Each kernel ships:

- ``<name>/<name>.py`` — ``pl.pallas_call`` + explicit BlockSpec VMEM tiling
  (TPU is the *target*; other backends run the same body in interpret mode),
- ``<name>/ops.py``    — the jit'd public wrapper (padding, reshapes,
  interpret-mode switch),
- ``<name>/ref.py``    — the pure-jnp oracle the tests sweep against.

TPU adaptation notes (vs the GPU idiom): TPU has no atomics, so the GPU
"atomicAdd histogram" becomes tile-local dense accumulation — scatter-add is
re-expressed as a one-hot matmul that runs on the MXU, with the histogram
tile resident in VMEM across the whole record stream (see
``segment_hist/``). Binary search with per-lane gathers is not
vector-friendly on TPU, so the power-law sampler uses sorted-CDF
comparison-counting on the VPU (see ``powerlaw_sample/``).

Mosaic's block rule shapes every kernel: the last two dimensions of a block
must be multiples of (8, 128) or equal the array's. Record streams are
therefore laid out as ``[n_tiles, 1, record_tile]`` and each grid step sees
one lane-dense ``[1, record_tile]`` row; one-hot matrices put the record
axis on lanes and contract it on the MXU (``A @ B^T``), so no kernel ever
moves records from lanes to sublanes.
"""

from __future__ import annotations

from typing import Optional

import jax


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """The one place that decides interpret vs compiled: an explicit
    ``interpret`` wins (tests force the interpreter); otherwise kernels run
    compiled on a TPU and interpreted on every other backend."""
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"
