"""Streaming chunked MalStone execution — paper scale at bounded memory.

The one-shot drivers in ``runner.py`` materialize the whole ``EventLog`` on
device before any backend runs, which caps the benchmark far below the
paper's classes (B-10 = 10 billion 100-byte records). This module runs the
same statistic as a ``jax.lax.scan`` over fixed-size record chunks with a
histogram carry: per scan step the device either *regenerates* its next
chunk from the MalGen seed (generate-as-you-go — the log is never
materialized) or slices it from a pre-generated shard, folds the chunk into
the carry with the chosen backend's dataflow, and moves on. Peak memory is
O(chunk + sites x weeks), independent of the global record count; the scan
carry is buffer-donated by XLA, so the histogram is accumulated in place.

Exactness: the site x week histogram is a commutative monoid (integer
segment sums), so chunk-wise accumulation is *bit-identical* to the one-shot
path for every backend — tests assert exact integer equality, not allclose.
This holds **unconditionally**, at any ``capacity_factor``: the
``mapreduce`` per-chunk shuffle is the same multi-round residual loop as the
one-shot path (see ``backends/mapreduce.py``), which re-exchanges bucket
overflow until every record reaches its reducer instead of dropping it.

Backend dataflows inside the scan (all run INSIDE ``shard_map``):

- ``streams`` / ``sphere``: local combine per chunk into a full-site carry;
  ONE collective after the scan (psum, resp. psum_scatter + all_gather) —
  the local-combine-first structure is exactly why these stacks won the
  paper's Tables 4/5, and it streams for free.
- ``mapreduce`` / ``mapreduce_combiner``: the shuffle happens *per chunk*
  inside the scan body (multi-round bucketed all_to_all, resp. combiner
  block exchange), accumulating each device's owned strided site block; one
  all_gather + unstride after the scan. This keeps the defining
  every-record-crosses-the-network (resp. histogram-slices-cross) cost while
  bounding the in-flight buffer to one chunk. Small chunks see relatively
  more power-law skew than a whole shard, so per-chunk shuffles simply run
  more rounds — ``ShuffleStats`` (accumulated across chunks; ``rounds`` is
  the max any chunk needed) makes that cost observable.

The carry is also exposed as a first-class :class:`HistogramState` pytree
with explicit ``fold_chunk(state, chunk) -> state`` / ``snapshot(state)``
operations — the shared substrate behind the batch scan above, the
resumable runner's checkpoints (``repro.core.resume``), and the resident
serving engine (``repro.serve``).
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.common.compat import axis_size
from repro.common.types import (
    EventLog,
    ExchangePlan,
    WEEKS_PER_YEAR,
    resolve_exchange_plan,
)
from repro.core import spm as spm_lib
from repro.core.backends import (
    ShuffleStats,
    mapreduce_histogram,
    shuffle_stats,
    sphere_histogram,  # noqa: F401  (re-exported for symmetry)
    streams_histogram,  # noqa: F401
)
from repro.core.backends.mapreduce import mapreduce_combiner_histogram
from repro.core.plan import resolve_histogram_fns
from repro.malgen.generator import generate_chunk
from repro.malgen.seeding import MalGenConfig, SeedInfo

STREAM_BACKENDS = ("streams", "sphere", "mapreduce", "mapreduce_combiner")


def _zero_stats() -> ShuffleStats:
    return ShuffleStats(sent=jnp.int32(0), overflow=jnp.int32(0),
                        capacity=jnp.int32(0), rounds=jnp.int32(0),
                        residual=jnp.int32(0), bytes_exchanged=jnp.int32(0))


def merge_stats(acc: ShuffleStats, chunk: ShuffleStats) -> ShuffleStats:
    """Fold one chunk's shuffle stats into the scan carry: counters add,
    ``rounds`` keeps the worst chunk, ``capacity`` is chunk-constant.

    Segment-splitting-invariant: splitting a chunk sequence into segments
    and folding segment-wise produces the same totals (sums commute, max
    is associative), which is what makes the carry checkpointable without
    perturbing the reported accounting."""
    return ShuffleStats(
        sent=acc.sent + chunk.sent,
        overflow=acc.overflow + chunk.overflow,
        capacity=jnp.int32(chunk.capacity),
        rounds=jnp.maximum(acc.rounds, jnp.int32(chunk.rounds)),
        residual=acc.residual + chunk.residual,
        bytes_exchanged=acc.bytes_exchanged + chunk.bytes_exchanged,
    )


_merge_stats = merge_stats  # back-compat alias


def carry_init(backend: str, s_pad: int, num_weeks: int, axis_name):
    """Zero carry in the backend's accumulation layout; the ``mapreduce``
    carry also threads accumulated ShuffleStats. Runs INSIDE ``shard_map``
    (the mapreduce row count depends on the axis size)."""
    with jax.named_scope("malstone.combine"):
        if backend in ("streams", "sphere"):
            return jnp.zeros((s_pad, num_weeks, 2), jnp.int32)
        p = axis_size(axis_name)
        owned = jnp.zeros((s_pad // p, num_weeks, 2), jnp.int32)
        if backend == "mapreduce":
            return (owned, _zero_stats())
        if backend == "mapreduce_combiner":
            return owned
    raise ValueError(f"unknown streaming backend {backend!r}")


_carry_init = carry_init  # back-compat alias


def carry_zeros_host(backend: str, parts: int, s_pad: int,
                     num_weeks: int):
    """Host-side zero carry in the *global* layout the resumable driver
    checkpoints: every per-device leaf gains a leading ``parts`` axis, so
    the whole carry is one pytree of numpy arrays that round-trips through
    ``repro.checkpoint.store`` (and elastically reshards along that axis).
    """
    def z(shape):
        return np.zeros(shape, np.int32)

    if backend in ("streams", "sphere"):
        return z((parts, s_pad, num_weeks, 2))
    owned = z((parts, s_pad // parts, num_weeks, 2))
    if backend == "mapreduce":
        stats = ShuffleStats(*(z((parts,)) for _ in ShuffleStats._fields))
        return (owned, stats)
    if backend == "mapreduce_combiner":
        return owned
    raise ValueError(f"unknown streaming backend {backend!r}")


def carry_partition_spec(backend: str, axis_name):
    """PartitionSpecs matching ``carry_zeros_host``'s layout: every leaf is
    sharded over its leading device axis."""
    spec = P(axis_name)
    if backend == "mapreduce":
        return (spec, ShuffleStats(*(spec for _ in ShuffleStats._fields)))
    return spec


def _accumulate_chunk(carry, chunk: EventLog, backend: str,
                      s_pad: int, num_weeks: int, axis_name,
                      histogram_fn, plan: ExchangePlan,
                      word_histogram_fn=None):
    """Fold one chunk into the carry using the backend's dataflow.

    The local combine and the carry add run under the ``malstone.combine``
    scope; the MapReduce exchanges name their own (``malstone.exchange``)."""
    if backend in ("streams", "sphere"):
        # local combine only; the cross-device collective runs post-scan
        with jax.named_scope("malstone.combine"):
            return carry + histogram_fn(chunk, s_pad, num_weeks)
    if backend == "mapreduce":
        hist, stats = carry
        owned, chunk_stats = mapreduce_histogram(
            chunk, s_pad, num_weeks, axis_name,
            capacity_factor=plan.capacity_factor, histogram_fn=histogram_fn,
            max_rounds=plan.max_shuffle_rounds, impl=plan.impl,
            word_histogram_fn=word_histogram_fn)
        with jax.named_scope("malstone.combine"):
            hist = hist + owned
        with jax.named_scope("malstone.exchange"):
            return (hist, _merge_stats(stats, chunk_stats))
    if backend == "mapreduce_combiner":
        owned = mapreduce_combiner_histogram(
            chunk, s_pad, num_weeks, axis_name, histogram_fn=histogram_fn)
        with jax.named_scope("malstone.combine"):
            return carry + owned
    raise ValueError(f"unknown streaming backend {backend!r}")


def scan_chunk_range(carry, seed: SeedInfo, cfg: MalGenConfig,
                     first_chunk, num_chunks: int, chunk_records: int,
                     *, s_pad: int, num_weeks: int = WEEKS_PER_YEAR,
                     axis_name="data", backend: str = "streams",
                     histogram_fn=None, plan: Optional[ExchangePlan] = None,
                     capacity_factor: Optional[float] = None,
                     max_rounds: Optional[int] = None,
                     packed: Optional[bool] = None):
    """Fold chunks ``[first_chunk, first_chunk + num_chunks)`` into
    ``carry`` with one ``lax.scan``. Runs INSIDE ``shard_map``.

    This is the checkpointable unit the resumable driver
    (``repro.core.resume``) is built on: because the site x week histogram
    is a commutative monoid and ``merge_stats`` is segment-splitting-
    invariant, running the full chunk range as several consecutive
    ``scan_chunk_range`` calls (saving the carry in between) is
    *bit-identical* to one uninterrupted scan. ``first_chunk`` may be a
    traced int32 (``generate_chunk`` is a pure function of
    ``(seed, chunk_id)``).

    ``plan`` is the unified :class:`~repro.common.types.ExchangePlan`;
    ``capacity_factor`` / ``max_rounds`` / ``packed`` are deprecated aliases
    that build one (and warn).
    """
    plan = resolve_exchange_plan(
        plan, capacity_factor=capacity_factor, max_shuffle_rounds=max_rounds,
        packed_shuffle=packed, _caller="scan_chunk_range")
    hist_fn, word_fn = resolve_histogram_fns(plan, histogram_fn)
    hist_fn = hist_fn or spm_lib.site_week_histogram

    def step(c, i):
        chunk = generate_chunk(seed, cfg, first_chunk + i, chunk_records)
        return _accumulate_chunk(c, chunk, backend, s_pad, num_weeks,
                                 axis_name, hist_fn, plan, word_fn), None

    carry, _ = jax.lax.scan(step, carry,
                            jnp.arange(num_chunks, dtype=jnp.int32))
    return carry


def post_scan_collective(carry, backend: str, s_pad: int,
                         num_weeks: int, axis_name):
    """Turn the per-device carry into the replicated full-site histogram
    (matching ``malstone_run``'s layout exactly) plus, for ``mapreduce``,
    the globally accumulated ShuffleStats (``None`` otherwise)."""
    with jax.named_scope("malstone.exchange"):
        if backend == "streams":
            return jax.lax.psum(carry, axis_name), None
        if backend == "sphere":
            owned = jax.lax.psum_scatter(carry, axis_name,
                                         scatter_dimension=0, tiled=True)
            return jax.lax.all_gather(owned, axis_name, axis=0,
                                      tiled=True), None
        # mapreduce*: carry rows are strided (site = row * P + d):
        # gather + unstride
        stats = None
        if backend == "mapreduce":
            carry, stats = carry
            stats = shuffle_stats(stats, axis_name)
        # [P, S/P, W, 2]
        gathered = jax.lax.all_gather(carry, axis_name, axis=0)
        hist = jnp.transpose(gathered, (1, 0, 2, 3)).reshape(
            s_pad, num_weeks, 2)
        return hist, stats


_post_scan_collective = post_scan_collective  # back-compat alias


# ---------------------------------------------------------------------------
# HistogramState: the scan carry as a first-class, resident pytree.
#
# The batch scan, the resumable runner's checkpoints, and the serving engine
# (``repro.serve``) all advance the SAME object: a backend carry plus an
# int32 chunk cursor. Because the site x week histogram is a commutative
# monoid and ``merge_stats`` is segment-splitting-invariant, any interleaving
# of ``fold_chunk`` / ``fold_chunk_range`` calls that covers the same chunks
# produces a bit-identical ``snapshot`` — that invariant is what makes the
# state checkpointable AND incrementally updatable without approximation.
#
# Two layouts, mirroring the carry helpers above:
#
# - *local*  — per-device leaves as seen INSIDE ``shard_map`` (no leading
#   device axis); ``fold_chunk`` / ``fold_chunk_range`` / ``snapshot``
#   operate on this layout.
# - *global* — every carry leaf gains a leading ``parts`` axis
#   (``carry_zeros_host``'s layout), which is what checkpoints store and
#   what a resident service keeps on device between calls.
#   ``state_to_local`` / ``state_to_global`` convert at the shard_map
#   boundary.
#
# ``chunks_folded`` is the number of chunks EACH device has folded (the
# devices advance in lockstep), kept replicated (``P()``) so the global
# layout matches the resumable checkpoint's scalar ``chunks_done`` cursor
# exactly. It is a strongly-typed int32 scalar — never a bare Python int —
# so the state survives the JX002 weak-type carry check.
# ---------------------------------------------------------------------------

class HistogramState(NamedTuple):
    """Resident MalStone accumulator: backend carry + chunk cursor."""

    carry: object          # backend carry pytree (see ``carry_init``)
    chunks_folded: object  # int32 scalar: chunks folded per device


def state_init(backend: str, s_pad: int, num_weeks: int,
               axis_name) -> HistogramState:
    """Zero local state. Runs INSIDE ``shard_map`` (like ``carry_init``)."""
    return HistogramState(carry_init(backend, s_pad, num_weeks, axis_name),
                          jnp.int32(0))


def state_zeros_host(backend: str, parts: int, s_pad: int,
                     num_weeks: int) -> HistogramState:
    """Host-side zero state in the *global* layout (numpy leaves; carry
    leaves carry a leading ``parts`` axis — the checkpoint/resident
    layout)."""
    return HistogramState(carry_zeros_host(backend, parts, s_pad, num_weeks),
                          np.zeros((), np.int32))


def state_partition_spec(backend: str, axis_name) -> HistogramState:
    """PartitionSpecs for the global layout: carry leaves shard over their
    leading device axis, the chunk cursor is replicated."""
    return HistogramState(carry_partition_spec(backend, axis_name), P())


def state_to_local(state: HistogramState) -> HistogramState:
    """Drop the size-1 leading device axis a global-layout state presents
    INSIDE ``shard_map`` (the cursor is replicated — no axis to drop)."""
    return HistogramState(jax.tree.map(lambda x: x[0], state.carry),
                          state.chunks_folded)


def state_to_global(state: HistogramState) -> HistogramState:
    """Inverse of ``state_to_local``: re-add the leading device axis so the
    shard_map output reassembles into the global layout."""
    return HistogramState(jax.tree.map(lambda x: x[None], state.carry),
                          state.chunks_folded)


def fold_chunk(state: HistogramState, chunk: EventLog, *,
               backend: str, s_pad: int,
               num_weeks: int = WEEKS_PER_YEAR, axis_name="data",
               histogram_fn=None,
               plan: Optional[ExchangePlan] = None) -> HistogramState:
    """Fold one materialized record chunk into a local state. Runs INSIDE
    ``shard_map``; the chunk is this device's slice (``chunk_records``
    rows). One ingest = one ``fold_chunk`` per device, so the per-chunk
    ``mapreduce`` shuffle (and its ShuffleStats accounting) is exactly the
    streaming scan's."""
    plan = resolve_exchange_plan(plan, _caller="fold_chunk")
    hist_fn, word_fn = resolve_histogram_fns(plan, histogram_fn)
    hist_fn = hist_fn or spm_lib.site_week_histogram
    carry = _accumulate_chunk(state.carry, chunk, backend, s_pad, num_weeks,
                              axis_name, hist_fn, plan, word_fn)
    return HistogramState(carry, state.chunks_folded + jnp.int32(1))


def fold_chunk_range(state: HistogramState, seed: SeedInfo,
                     cfg: MalGenConfig, first_chunk, num_chunks: int,
                     chunk_records: int, *, s_pad: int,
                     num_weeks: int = WEEKS_PER_YEAR, axis_name="data",
                     backend: str = "streams", histogram_fn=None,
                     plan: Optional[ExchangePlan] = None) -> HistogramState:
    """Regenerate-and-fold chunks ``[first_chunk, first_chunk+num_chunks)``
    into a local state (``scan_chunk_range`` over the carry, cursor
    advanced by ``num_chunks``). Runs INSIDE ``shard_map``; ``first_chunk``
    may be traced."""
    carry = scan_chunk_range(
        state.carry, seed, cfg, first_chunk, num_chunks, chunk_records,
        s_pad=s_pad, num_weeks=num_weeks, axis_name=axis_name,
        backend=backend, histogram_fn=histogram_fn, plan=plan)
    return HistogramState(carry,
                          state.chunks_folded + jnp.int32(num_chunks))


def snapshot(state: HistogramState, *, backend: str, s_pad: int,
             num_weeks: int = WEEKS_PER_YEAR, axis_name="data"):
    """Materialize the replicated full-site histogram (and, for
    ``mapreduce``, the global ShuffleStats) from a local state — the
    read-side half of the fold/snapshot contract. Runs INSIDE
    ``shard_map``; does NOT consume the state, so a resident service can
    keep folding afterwards."""
    return post_scan_collective(state.carry, backend, s_pad, num_weeks,
                                axis_name)


def streaming_histogram_from_log(log_shard: EventLog, s_pad: int,
                                 chunk_records: int,
                                 num_weeks: int = WEEKS_PER_YEAR,
                                 axis_name="data",
                                 backend: str = "streams",
                                 histogram_fn=None,
                                 plan: Optional[ExchangePlan] = None,
                                 capacity_factor: Optional[float] = None,
                                 max_rounds: Optional[int] = None,
                                 packed: Optional[bool] = None):
    """Chunked histogram over a materialized (per-device) log shard.

    Runs INSIDE ``shard_map``. The shard's record dim must be divisible by
    ``chunk_records`` (the runner pads with invalid rows). Returns
    ``(histogram, shuffle_stats)``: the replicated ``[s_pad, num_weeks, 2]``
    histogram and, for the ``mapreduce`` backend, the chunk-accumulated
    global ``ShuffleStats`` (``None`` for every other backend).

    ``plan`` is the unified :class:`~repro.common.types.ExchangePlan`;
    ``capacity_factor`` / ``max_rounds`` / ``packed`` are deprecated aliases
    that build one (and warn).
    """
    plan = resolve_exchange_plan(
        plan, capacity_factor=capacity_factor, max_shuffle_rounds=max_rounds,
        packed_shuffle=packed, _caller="streaming_histogram_from_log")
    hist_fn, word_fn = resolve_histogram_fns(plan, histogram_fn)
    hist_fn = hist_fn or spm_lib.site_week_histogram
    n = log_shard.num_records
    if n % chunk_records != 0:
        raise ValueError(
            f"per-device record count ({n}) must be divisible by "
            f"chunk_records ({chunk_records}); pad the log with invalid "
            f"rows first (see repro.core.pad_log_to)")
    num_chunks = n // chunk_records

    def to_chunks(col):
        return None if col is None else col.reshape(num_chunks, chunk_records)

    def step(carry, chunk):
        return _accumulate_chunk(carry, chunk, backend, s_pad, num_weeks,
                                 axis_name, hist_fn, plan, word_fn), None

    # reading the log: the reshape into chunks and the scan's per-chunk
    # slice (the fold inside the scan names its own scopes)
    with jax.named_scope("malstone.read"):
        chunks = EventLog(*(to_chunks(col) for col in log_shard))
        carry, _ = jax.lax.scan(
            step, _carry_init(backend, s_pad, num_weeks, axis_name), chunks)
    return _post_scan_collective(carry, backend, s_pad, num_weeks, axis_name)


def streaming_histogram_generate(seed: SeedInfo, cfg: MalGenConfig,
                                 s_pad: int,
                                 chunks_per_device: int,
                                 chunk_records: int,
                                 num_weeks: int = WEEKS_PER_YEAR,
                                 axis_name="data",
                                 backend: str = "streams",
                                 histogram_fn=None,
                                 plan: Optional[ExchangePlan] = None,
                                 capacity_factor: Optional[float] = None,
                                 max_rounds: Optional[int] = None,
                                 packed: Optional[bool] = None):
    """Generate-as-you-go chunked histogram: each scan step regenerates its
    chunk from the seed (``generate_chunk`` is a pure function of
    (seed, chunk_id)) — the log never exists in memory.

    Runs INSIDE ``shard_map``. Device ``d`` owns the contiguous chunk block
    ``[d * chunks_per_device, (d+1) * chunks_per_device)`` — the same layout
    ``generate_chunked_log`` materializes, so results are bit-identical to
    running the one-shot path over that log. Returns
    ``(histogram, shuffle_stats)`` exactly like
    ``streaming_histogram_from_log``.
    """
    plan = resolve_exchange_plan(
        plan, capacity_factor=capacity_factor, max_shuffle_rounds=max_rounds,
        packed_shuffle=packed, _caller="streaming_histogram_generate")
    first_chunk = jax.lax.axis_index(axis_name) * chunks_per_device
    carry = scan_chunk_range(
        carry_init(backend, s_pad, num_weeks, axis_name), seed, cfg,
        first_chunk, chunks_per_device, chunk_records, s_pad=s_pad,
        num_weeks=num_weeks, axis_name=axis_name, backend=backend,
        histogram_fn=histogram_fn, plan=plan)
    return post_scan_collective(carry, backend, s_pad, num_weeks, axis_name)
