"""Public jit'd dispatch for the count_scatter counting sort.

``impl="auto"`` runs the Pallas kernels on TPU and the jnp oracle
(``ref.py`` — itself the measured CPU fast path) everywhere else; the
kernel path is validated bit-exactly against the oracle in interpret mode
by ``tests/test_counting_exchange.py`` and compiled for a v5e by
``tests/test_tpu_compile.py``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret
from repro.kernels.count_scatter.count_scatter import (
    DEST_LANES,
    RECORD_TILE,
    _round_up,
    count_tiles_pallas,
    scatter_tiles_pallas,
)
from repro.kernels.count_scatter.ref import count_scatter_ref


@functools.partial(
    jax.jit,
    static_argnames=("num_partitions", "impl", "record_tile", "interpret"))
def count_scatter(words: jnp.ndarray, dest: jnp.ndarray, num_partitions: int,
                  *, impl: str = "auto", record_tile: int = RECORD_TILE,
                  interpret: Optional[bool] = None):
    """Stable counting sort of packed uint32 ``words`` by ``dest``.

    ``dest`` is int32 in ``[0, num_partitions]`` (destination ``P`` = the
    invalid-row pseudo-destination). Returns ``(words_sorted, starts)``,
    bit-identical to ``jnp.argsort(dest, stable=True)`` + gather +
    ``searchsorted`` — see ``ref.py``.

    ``impl``: ``"jnp"`` = the oracle, ``"pallas"`` = the TPU kernels,
    ``"auto"`` = pallas on TPU else jnp.
    """
    if impl == "auto":  # the kernels where they compile, else the oracle
        impl = "jnp" if resolve_interpret() else "pallas"
    if impl == "jnp":
        return count_scatter_ref(words, dest, num_partitions)
    if impl != "pallas":
        raise ValueError(f"impl must be 'auto', 'jnp' or 'pallas', got {impl!r}")
    interpret = resolve_interpret(interpret)

    n = words.shape[0]
    p1 = num_partitions + 1
    p_pad = _round_up(p1, DEST_LANES)
    n_pad = _round_up(max(n, 1), record_tile)
    # padding rows get a sentinel past every counted column
    dest_t = jnp.pad(dest.astype(jnp.int32), (0, n_pad - n),
                     constant_values=p_pad).reshape(-1, 1, record_tile)
    words_t = jax.lax.bitcast_convert_type(
        jnp.pad(words, (0, n_pad - n)), jnp.int32).reshape(-1, 1, record_tile)

    counts_t = count_tiles_pallas(dest_t, p_pad=p_pad,
                                  interpret=interpret)[:, 0, :p1]  # [T, P+1]
    counts = jnp.sum(counts_t, axis=0)                    # [P+1]
    starts = jnp.cumsum(counts) - counts                  # exclusive over d
    tile_excl = jnp.cumsum(counts_t, axis=0) - counts_t   # exclusive over t
    base = (starts[None, :] + tile_excl).astype(jnp.int32)

    out = scatter_tiles_pallas(
        dest_t, words_t, base[:, None, :], counts_t[:, None, :],
        num_dests=p1, interpret=interpret)                # [rows, 128]
    words_sorted = jax.lax.bitcast_convert_type(out.reshape(-1)[:n],
                                                jnp.uint32)
    return words_sorted, starts.astype(jnp.int32)


def analysis_cases():
    """Static-analysis cases (see segment_hist.ops.analysis_cases)."""
    n, parts = 1 << 16, 4
    sds = jax.ShapeDtypeStruct

    def stage():
        fn = functools.partial(count_scatter.__wrapped__,
                               num_partitions=parts, impl="pallas",
                               interpret=True)
        return jax.eval_shape(fn, sds((n,), jnp.uint32),
                              sds((n,), jnp.int32))

    # the scatter output lives in HBM and is written by DMA (no blocked
    # window), so only the streamed record/table blocks are audited
    return [{"name": "count_scatter/shard_exchange", "stage": stage,
             "accumulate": {}}]
