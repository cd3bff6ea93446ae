"""MalGen's power-law site sampler — Pallas TPU kernel.

Inverse-CDF sampling: ``site = searchsorted(cdf, u, side='right')``. The GPU
idiom is a per-thread binary search (data-dependent gathers). TPU vector
units have no per-lane gather, so the kernel uses the sorted-CDF
**comparison-count** identity instead:

    searchsorted_right(cdf, u) == sum_s 1{cdf[s] <= u}

which is a broadcast-compare + reduction — pure VPU work with fully regular
memory access. The CDF streams through VMEM in sublane-major column tiles
(``[TC, 1]``: each 8-entry slice broadcasts along lanes for free) while a
record row ``[1, TR]`` (lane-dense, from a ``[n_tiles, 1, TR]`` array)
broadcasts along sublanes; an ``[8, TR]`` int32 accumulator collects the
compares and is reduced over sublanes once per CDF tile. Cost is
O(N * S / lanes) compares but zero irregular access, which wins on TPU
whenever S fits the VMEM budget (the paper's default is ~120k sites —
0.5 MB of f32 CDF).

Grid: (record_tiles, cdf_tiles), CDF innermost so the per-record count
accumulates in the output block while CDF tiles stream through VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

RECORD_TILE = 512   # u's per block (one lane-dense row)
CDF_TILE = 2048     # CDF entries per streamed block
_SUBLANES = 8


def _kernel(u_ref, cdf_ref, out_ref, *, cdf_tile: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    u = u_ref[...]           # [1, TR] f32

    def body(k, acc):
        start = pl.multiple_of(k * _SUBLANES, _SUBLANES)
        cdf = cdf_ref[pl.ds(start, _SUBLANES), :]   # [8, 1] (pad = +2.0)
        return acc + jnp.where(cdf <= u, 1, 0)

    acc = jax.lax.fori_loop(0, cdf_tile // _SUBLANES, body,
                            jnp.zeros((_SUBLANES, u.shape[1]), jnp.int32))
    out_ref[...] += jnp.sum(acc, axis=0, keepdims=True)


def powerlaw_sample_pallas(u: jnp.ndarray, cdf: jnp.ndarray, *,
                           record_tile: int = RECORD_TILE,
                           cdf_tile: int = CDF_TILE,
                           interpret: bool) -> jnp.ndarray:
    """Raw entry. u: [n_rec_tiles, 1, record_tile] f32 in [0,1);
    cdf: [n_cdf_tiles * cdf_tile, 1] f32 padded with +2.0 beyond the real
    sites. Returns int32 [n_rec_tiles, 1, record_tile] counts == site
    indices (clamped by ops.py)."""
    n_rec_tiles, one, tr = u.shape
    s_pad, cdf_cols = cdf.shape
    if (one != 1 or tr != record_tile or cdf_cols != 1
            or s_pad % cdf_tile != 0 or cdf_tile % _SUBLANES != 0):
        raise ValueError(
            f"u is laid out {tuple(u.shape)} and cdf {tuple(cdf.shape)} but "
            f"the kernel reads u as [n_tiles, 1, record_tile={record_tile}] "
            f"and cdf as a [k * cdf_tile={cdf_tile}, 1] column (cdf_tile a "
            f"multiple of {_SUBLANES}); retile both streams")

    u_spec = pl.BlockSpec((None, 1, record_tile), lambda i, j: (i, 0, 0))
    return pl.pallas_call(
        functools.partial(_kernel, cdf_tile=cdf_tile),
        grid=(n_rec_tiles, s_pad // cdf_tile),
        in_specs=[u_spec, pl.BlockSpec((cdf_tile, 1), lambda i, j: (j, 0))],
        out_specs=u_spec,
        out_shape=jax.ShapeDtypeStruct((n_rec_tiles, 1, record_tile),
                                       jnp.int32),
        interpret=interpret,
    )(u, cdf)
