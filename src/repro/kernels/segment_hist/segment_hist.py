"""Fused (site, week, mark) -> (total, marked) histogram — Pallas TPU kernel.

This is the MalStone Reducer's inner loop (paper §6.1): for every record,
``hist[site, week, 0] += 1`` and ``hist[site, week, 1] += mark``. On GPU one
would scatter with atomics; TPU has no atomics, so the kernel re-expresses
scatter-add as a **one-hot matmul** that runs on the MXU. Records sit on
lanes, so both one-hot operands are built by comparing a record row against
a sublane iota and the record axis is contracted as ``A @ B^T``:

    oh_site_t[s, r] = (site[r] == tile_start + s)              [TS, TR]
    rhs_t[c, r]     = week one-hot (c < W_pad) | mark one-hot   [2W, TR]
    tile_out       += oh_site_t @ rhs_t^T                       [TS, 2W]

Memory-hierarchy plan (HBM -> VMEM -> MXU):
  * grid = (site_tiles, record_tiles); record dim is innermost so the
    [TS, 2W] histogram tile stays resident in VMEM for the entire record
    stream (initialized at record-tile 0, flushed once).
  * records stream through VMEM as lane-dense ``[1, TR]`` rows of a
    ``[n_tiles, 1, TR]`` array (a (1, TR) block of a 2-D array would break
    Mosaic's (8, 128) block rule); each row is read once per site tile.
  * the matmul is TS x TR x 2W_pad with every dim a multiple of the MXU's
    128 systolic width (2W padded to 128 for W=52).

Exactness: both one-hot operands are 0/1, exact in bf16, and each
per-record-tile partial count is <= TR < 2^24, so the f32-accumulated MXU
product is exact; cross-tile accumulation happens in int32 in VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# MXU/VPU-aligned defaults (multiples of 128 lanes / 8 sublanes).
SITE_TILE = 256     # TS: sites per histogram tile
RECORD_TILE = 1024  # TR: records per stream block


def _accumulate(local, week, mark, in_tile, out_ref, *,
                mark_col_offset: int, w2_pad: int, site_tile: int):
    """Shared accumulate body: fold one record row's (tile-local site,
    week, mark, membership) — each ``[1, TR]`` — into the VMEM-resident
    histogram tile via the one-hot MXU matmul described in the module
    docstring."""
    tr = local.shape[1]
    site_iota = jax.lax.broadcasted_iota(jnp.int32, (site_tile, tr), 0)
    oh_site_t = jnp.where(local == site_iota, 1.0, 0.0).astype(jnp.bfloat16)

    # rhs_t [2W_pad, TR]: event-count block at rows [0, W), mark-count
    # block at [mark_col_offset, mark_col_offset + W)
    col_iota = jax.lax.broadcasted_iota(jnp.int32, (w2_pad, tr), 0)
    hit = (week == col_iota) | (((week + mark_col_offset) == col_iota)
                                & (mark > 0))
    rhs_t = jnp.where(hit & in_tile, 1.0, 0.0).astype(jnp.bfloat16)

    # MXU: [TS, TR] @ [2W_pad, TR]^T — per-tile partials are exact in f32
    partial = jax.lax.dot_general(
        oh_site_t, rhs_t, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    out_ref[...] += partial.astype(jnp.int32)


def _kernel(site_ref, week_ref, mark_ref, valid_ref, out_ref, *,
            mark_col_offset: int, w2_pad: int, site_tile: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    site = site_ref[...]                       # [1, TR] int32
    tile_start = pl.program_id(0) * site_tile
    local = site - tile_start
    in_tile = (local >= 0) & (local < site_tile) & (valid_ref[...] > 0)
    _accumulate(local, week_ref[...], mark_ref[...], in_tile, out_ref,
                mark_col_offset=mark_col_offset, w2_pad=w2_pad,
                site_tile=site_tile)


def _packed_kernel(my_ref, word_ref, out_ref, *,
                   mark_col_offset: int, w2_pad: int, site_tile: int,
                   num_partitions: int):
    """Fused unpack + histogram over packed shuffle words.

    The MapReduce reducer's input is the stream of packed uint32 words the
    exchange delivered (``repro.common.types`` layout: site<<8 | week<<2 |
    mark<<1 | valid). Unpacking in-kernel — bit shifts on the VPU while the
    words stream through VMEM — means the four int32 columns are never
    materialized in HBM. The kernel also applies the reducer's ownership
    filter (``site % P == my``) and re-bases strided site ids to the local
    dense rows (``site // P``), so its output is directly the device's
    owned histogram block. Words are int32 *bit patterns* (bitcast by
    ops.py); masking after the arithmetic shift makes every field
    extraction sign-safe. ``my`` is one scalar in SMEM.
    """
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    word = word_ref[...]                       # [1, TR] int32 bit pattern
    my = my_ref[0, 0]

    valid = (word & 1) > 0
    mark = (word >> 1) & 1
    week = (word >> 2) & 0x3F
    site = (word >> 8) & 0xFFFFFF
    ok = valid & ((site % num_partitions) == my)
    local = site // num_partitions - pl.program_id(0) * site_tile
    in_tile = ok & (local >= 0) & (local < site_tile)
    _accumulate(local, week, mark, in_tile, out_ref,
                mark_col_offset=mark_col_offset, w2_pad=w2_pad,
                site_tile=site_tile)


def _check_tiling(stream, record_tile: int, num_sites_padded: int,
                  site_tile: int) -> int:
    n_rec_tiles, one, tr = stream.shape
    if one != 1 or tr != record_tile:
        raise ValueError(
            f"record stream is laid out {tuple(stream.shape)} but the kernel"
            f" reads [n_tiles, 1, record_tile={record_tile}] rows; retile "
            f"before calling the kernel")
    if num_sites_padded % site_tile != 0:
        raise ValueError(
            f"num_sites_padded={num_sites_padded} must be a multiple of "
            f"site_tile={site_tile} (the VMEM histogram tile height)")
    return n_rec_tiles


def segment_hist_pallas(site: jnp.ndarray, week: jnp.ndarray,
                        mark: jnp.ndarray, valid: jnp.ndarray,
                        num_sites_padded: int, num_weeks: int,
                        *, site_tile: int = SITE_TILE,
                        record_tile: int = RECORD_TILE,
                        interpret: bool) -> jnp.ndarray:
    """Raw kernel entry. Preconditions (ops.py enforces):

    - record arrays are [n_rec_tiles, 1, record_tile] int32,
    - ``num_sites_padded % site_tile == 0``,
    - out-of-range site ids already have valid == 0.

    Returns int32 ``[num_sites_padded, 2 * W_pad]`` with the event-count
    block in columns [0, W) and the mark-count block in [W_pad, W_pad + W)
    — ops.py slices/stacks back to [S, W, 2].
    """
    n_rec_tiles = _check_tiling(site, record_tile, num_sites_padded,
                                site_tile)
    w_pad = max(64, _round_up(num_weeks, 64))
    w2_pad = 2 * w_pad

    rec_spec = pl.BlockSpec((None, 1, record_tile), lambda i, j: (j, 0, 0))
    kernel = functools.partial(
        _kernel, mark_col_offset=w_pad, w2_pad=w2_pad, site_tile=site_tile)
    return pl.pallas_call(
        kernel,
        grid=(num_sites_padded // site_tile, n_rec_tiles),
        in_specs=[rec_spec, rec_spec, rec_spec, rec_spec],
        out_specs=pl.BlockSpec((site_tile, w2_pad), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((num_sites_padded, w2_pad), jnp.int32),
        interpret=interpret,
    )(site, week, mark, valid)


def segment_hist_packed_pallas(words: jnp.ndarray, my_index: jnp.ndarray,
                               num_sites_padded: int, num_weeks: int,
                               num_partitions: int,
                               *, site_tile: int = SITE_TILE,
                               record_tile: int = RECORD_TILE,
                               interpret: bool) -> jnp.ndarray:
    """Raw fused-reducer entry (see ``_packed_kernel``). Preconditions
    (ops.py enforces): ``words`` is [n_rec_tiles, 1, record_tile] int32 bit
    patterns with zero-word padding, ``my_index`` is [1, 1] int32, and
    ``num_sites_padded % site_tile == 0`` counts *local* (per-device)
    sites. Same output layout as ``segment_hist_pallas``.
    """
    n_rec_tiles = _check_tiling(words, record_tile, num_sites_padded,
                                site_tile)
    w_pad = max(64, _round_up(num_weeks, 64))
    w2_pad = 2 * w_pad

    kernel = functools.partial(
        _packed_kernel, mark_col_offset=w_pad, w2_pad=w2_pad,
        site_tile=site_tile, num_partitions=num_partitions)
    return pl.pallas_call(
        kernel,
        grid=(num_sites_padded // site_tile, n_rec_tiles),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  pl.BlockSpec((None, 1, record_tile),
                               lambda i, j: (j, 0, 0))],
        out_specs=pl.BlockSpec((site_tile, w2_pad), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((num_sites_padded, w2_pad), jnp.int32),
        interpret=interpret,
    )(my_index, words)


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m
