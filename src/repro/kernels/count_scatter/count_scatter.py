"""Counting-sort scatter of packed shuffle words — Pallas TPU kernels.

The MapReduce exchange needs its packed uint32 words in destination-
contiguous stable order before the round loop (see ``ref.py`` for why
stability makes this bit-identical to the argsort path). The destination
key space is tiny — ``P`` devices plus one invalid pseudo-destination — so
a counting sort does it in two O(n) record passes, each a Pallas kernel.
Records stream as lane-dense ``[1, TR]`` rows of ``[n_tiles, 1, TR]``
arrays (Mosaic's (8, 128) block rule forbids a (1, TR) block of a 2-D
array).

1. ``_count_kernel``: per record tile, the ``[P+1]`` destination histogram
   (one-hot compare against a sublane iota on the VPU, record axis
   contracted on the MXU), written as one ``[1, p_pad]`` row. A cheap jnp
   glue pass turns the ``[n_tiles, P+1]`` table into exclusive prefix sums
   over destinations (segment starts) and over tiles (each tile's write
   base per destination) — O(tiles x P) work, negligible next to the
   record passes.
2. ``_scatter_kernel``: per record tile and destination ``d``, place the
   tile's ``d`` records at ``base[tile, d] + rank-within-tile``. TPU has no
   per-lane scatter, so the permutation is re-expressed as MXU matmuls:
   the within-tile stable rank is a triangular comparison-count matmul,
   and the destination window (rank ``k`` -> its word) is a one-hot
   matmul. Words travel as four byte planes, exact in bf16, so each
   window entry is a single product <= 255 (exact in the f32 accumulator)
   and the planes recombine bitwise. The window is built as ``[rows, 128]``
   (word ``k`` at row ``k // 128``, lane ``k % 128``) and shifted to its
   unaligned global offset with two dynamic rotates (lanes, then sublanes
   below an 8-row-aligned base); the output stays in HBM and each window
   is OR-ed into it with a DMA read-modify-write of one aligned row block.
   The grid is sequential, positions are unique, and the output starts
   zeroed (aliased to a zero input), so OR-accumulation is exact.

Base offsets and per-tile counts reach each grid step as SMEM scalars. On
the CPU both kernels run in interpret mode and are tested against
``ref.py``; TPU is the target.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Lane-aligned defaults (multiples of 128).
RECORD_TILE = 1024   # TR: records per stream block
DEST_LANES = 128     # the [P+1] histogram padded up to one lane group
LANES = 128
SUBLANES = 8


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def window_block_rows(record_tile: int) -> int:
    """Rows of the aligned block one window's read-modify-write touches: the
    window's ``record_tile / 128`` rows, one row of lane carry, and up to
    7 rows of shift below the 8-row-aligned base."""
    return _round_up(record_tile // LANES + SUBLANES, SUBLANES)


def scatter_out_rows(n_pad: int, record_tile: int) -> int:
    """Rows of the ``[rows, 128]`` scatter output for ``n_pad`` words: the
    last window's block may start at the final word's row."""
    return _round_up(n_pad // LANES + window_block_rows(record_tile),
                     SUBLANES)


def _count_kernel(dest_ref, out_ref, *, p_pad: int):
    """out[0, d] = #{i in tile : dest[i] == d} for d in [0, p_pad).

    The one-hot puts destinations on sublanes (records stay on lanes); an
    all-ones MXU operand contracts the record axis and lands the counts on
    lanes, as the output row needs (0/1 operands: exact in bf16, counts
    <= TR exact in f32)."""
    dest = dest_ref[...]                                         # [1, TR]
    tr = dest.shape[1]
    d_iota = jax.lax.broadcasted_iota(jnp.int32, (p_pad, tr), 0)
    oh_t = jnp.where(dest == d_iota, 1.0, 0.0).astype(jnp.bfloat16)
    ones = jnp.ones((SUBLANES, tr), jnp.bfloat16)
    counts = jax.lax.dot_general(ones, oh_t, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    out_ref[...] = counts[0:1].astype(jnp.int32)


def count_tiles_pallas(dest: jnp.ndarray, *, p_pad: int,
                       interpret: bool) -> jnp.ndarray:
    """Per-tile destination histograms: int32 [n_tiles, 1, p_pad].

    ``dest`` is [n_tiles, 1, record_tile] int32; padding rows must carry a
    sentinel >= p_pad so they count nowhere.
    """
    n_tiles, _, record_tile = dest.shape
    return pl.pallas_call(
        functools.partial(_count_kernel, p_pad=p_pad),
        grid=(n_tiles,),
        in_specs=[pl.BlockSpec((None, 1, record_tile), lambda t: (t, 0, 0))],
        out_specs=pl.BlockSpec((None, 1, p_pad), lambda t: (t, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_tiles, 1, p_pad), jnp.int32),
        interpret=interpret,
        name="count_tiles",
    )(dest)


def _scatter_kernel(base_ref, cnt_ref, dest_ref, word_ref, zero_ref,
                    out_ref, tri_ref, buf_ref, *, num_dests: int,
                    record_tile: int):
    del zero_ref  # aliased to out_ref: only supplies the zeroed output
    t = pl.program_id(0)
    tr = record_tile
    rows = tr // LANES
    block_rows = buf_ref.shape[0]

    @pl.when(t == 0)
    def _init():
        # strict upper-triangular counting matrix: tri[j, i] = 1 iff j < i
        row_i = jax.lax.broadcasted_iota(jnp.int32, (tr, tr), 0)
        col_i = jax.lax.broadcasted_iota(jnp.int32, (tr, tr), 1)
        tri_ref[...] = jnp.where(row_i < col_i, 1.0, 0.0).astype(
            jnp.bfloat16)

    dest = dest_ref[...]                                         # [1, TR]
    word = word_ref[...]                                         # [1, TR]
    # byte planes in rows 0..3 of an 8-row MXU operand
    plane = jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, tr), 0)
    planes = jnp.where(plane < 4,
                       (word >> (8 * jnp.minimum(plane, 3))) & 0xFF, 0)
    planes = planes.astype(jnp.float32).astype(jnp.bfloat16)    # exact
    k_iota = jax.lax.broadcasted_iota(jnp.int32, (LANES, tr), 0)
    win_row = jax.lax.broadcasted_iota(jnp.int32, (block_rows, LANES), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (block_rows, LANES), 1)
    shifts = 8 * jnp.minimum(
        jax.lax.broadcasted_iota(jnp.int32, (SUBLANES, LANES), 0), 3)

    def one_dest(d, carry):
        @pl.when(cnt_ref[0, d] > 0)
        def _write():
            m = dest == d                                        # [1, TR]
            mf = jnp.broadcast_to(jnp.where(m, 1.0, 0.0), (SUBLANES, tr))
            # within-tile stable rank r[i] = #{j < i : dest[j] == d}
            rank = jax.lax.dot_general(
                mf.astype(jnp.bfloat16), tri_ref[...],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)[0:1]         # [1, TR]
            rank = jnp.where(m, rank.astype(jnp.int32), -1)

            def one_row(q, win):
                # window words [128q, 128q + 128): one-hot over ranks
                oh_t = jnp.where(rank == k_iota + q * LANES, 1.0, 0.0)
                got = jax.lax.dot_general(
                    planes, oh_t.astype(jnp.bfloat16),
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)          # [8, 128]
                got = got.astype(jnp.int32) << shifts
                row = got[0:1] | got[1:2] | got[2:3] | got[3:4]  # [1, 128]
                return jnp.where(win_row == q, row, win)

            win = jax.lax.fori_loop(
                0, rows, one_row, jnp.zeros((block_rows, LANES), jnp.int32))
            g = base_ref[0, d]
            row0 = g // LANES
            lane0 = g - row0 * LANES
            rot = pltpu.roll(win, lane0, 1)
            # lanes >= lane0 stay on their row; the rest carry to the next
            carried = pltpu.roll(jnp.where(lane < lane0, rot, 0), 1, 0)
            shifted = jnp.where(lane >= lane0, rot, 0) | carried
            sub = row0 % SUBLANES
            block = pltpu.roll(shifted, sub, 0)
            start = pl.multiple_of(row0 - sub, SUBLANES)
            dst = out_ref.at[pl.ds(start, block_rows)]
            pltpu.sync_copy(dst, buf_ref)
            buf_ref[...] = buf_ref[...] | block
            pltpu.sync_copy(buf_ref, dst)

        return carry

    jax.lax.fori_loop(0, num_dests, one_dest, 0)


def scatter_tiles_pallas(dest: jnp.ndarray, words: jnp.ndarray,
                         base: jnp.ndarray, counts: jnp.ndarray, *,
                         num_dests: int, interpret: bool) -> jnp.ndarray:
    """Scatter words into destination-contiguous stable order.

    ``dest``/``words`` are [n_tiles, 1, record_tile] int32 (words as bit
    patterns); ``base`` and ``counts`` are [n_tiles, 1, num_dests] int32
    with ``base[t, 0, d]`` = the global output offset of tile ``t``'s first
    record for destination ``d`` and ``counts[t, 0, d]`` its record count.
    Returns int32 ``[scatter_out_rows(n_tiles * record_tile), 128]``: word
    ``k`` of the sorted order at row ``k // 128``, lane ``k % 128`` (the
    tail rows are slack for the last window); callers flatten, slice and
    bitcast.
    """
    n_tiles, _, record_tile = dest.shape
    if record_tile % LANES:
        raise ValueError(
            f"record_tile={record_tile} must be a multiple of {LANES}")
    block_rows = window_block_rows(record_tile)
    out_rows = scatter_out_rows(n_tiles * record_tile, record_tile)
    rec_spec = pl.BlockSpec((None, 1, record_tile), lambda t: (t, 0, 0))
    tab_spec = pl.BlockSpec((None, 1, num_dests), lambda t: (t, 0, 0),
                            memory_space=pltpu.SMEM)
    return pl.pallas_call(
        functools.partial(_scatter_kernel, num_dests=num_dests,
                          record_tile=record_tile),
        grid=(n_tiles,),
        in_specs=[tab_spec, tab_spec, rec_spec, rec_spec,
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        out_shape=jax.ShapeDtypeStruct((out_rows, LANES), jnp.int32),
        scratch_shapes=[pltpu.VMEM((record_tile, record_tile), jnp.bfloat16),
                        pltpu.VMEM((block_rows, LANES), jnp.int32)],
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="scatter_tiles",
    )(base, counts, dest, words, jnp.zeros((out_rows, LANES), jnp.int32))
