"""Hadoop-MapReduce-analogue backend: a true record shuffle.

Paper Section 6.1: the Mapper emits ``(site_id, (timestamp, mark))``, the
Partitioner routes by ``site_id % num_reducers``, and each Reducer aggregates
the records for its sites. The defining cost is that *every record* crosses
the network (plus, on 2010 Hadoop, spills to disk twice) — this is why
MapReduce lost to Streams by ~5x and to Sphere by ~13-20x in Tables 4/5.

TPU adaptation: the shuffle is a **multi-round** fixed-capacity bucketed
``lax.all_to_all``. TPU collectives need static shapes, so each device packs
its records into ``[P, capacity]`` buckets (dest = site_id % P, the paper's
Partitioner) and exchanges them; records that do not fit their bucket are
*not dropped* — they stay behind and a ``lax.while_loop`` re-exchanges them
until the psum'd global leftover count reaches zero. The shuffle is
therefore exact at **any** ``capacity_factor``: the paper's MapReduce ships
every record to its reducer, and so do we — a small capacity just pays for
it in extra rounds (the measured rounds-vs-capacity tradeoff is the
``mapreduce_lossless_*`` / ``mapreduce_packed_*`` bench scenarios). Rounds
are bounded statically: a device holds at most ``n`` records for any one
destination and each round drains ``capacity`` of them, so
``ceil(n / capacity)`` rounds always suffice; ``max_rounds=None`` uses
exactly that bound, making the loop provably lossless. An explicit smaller
``max_rounds`` is an escape hatch for bounding worst-case latency — the
runner raises ``ShuffleExhaustedError`` if it is exhausted with records
still undelivered (never a silent drop).

Three exchange implementations share that loop (``ExchangePlan.impl``):

- **packed counting-sort** (``"counting"`` — what ``"auto"`` picks
  whenever the fields fit: ``num_sites <= 2^24`` and ``num_weeks <= 64``):
  the Reducer only ever needs ``(site, week, mark, valid)``, so the mapper
  projects each record into ONE uint32 word
  (``repro.common.types.pack_site_week_mark``) and orders the words by
  destination with a **stable counting sort** — per-destination histogram,
  exclusive prefix sum over the ``P+1``-entry table, scatter
  (``repro.kernels.count_scatter``: Pallas kernels on TPU, a jnp
  counting-scatter elsewhere). Two O(n) record passes; the destination
  key space is only ``P`` devices, so an O(n log n) comparison sort is
  pure waste. Each round then gathers the next ``capacity``-wide window
  per destination from the ordered array (the residual stays ordered by
  construction) and the ``all_to_all`` carries 4 bytes per bucket slot
  instead of 17.
- **packed sort-once** (``"sort"``): identical except the ordering pass
  is a stable ``argsort``. A stable counting sort produces the *same
  permutation* as a stable comparison sort, so the two packed paths are
  bit-identical arrays-in, arrays-out — histograms AND every ShuffleStats
  field — and "sort" is kept as the counting path's oracle and its bench
  comparison row (``mapreduce_packed_*`` vs ``mapreduce_counting_*``).
- **4-column fallback** (``"columns"``): the original path — per-round
  stable argsort + scatter of all four record columns plus validity
  (``_pack_buckets``), kept for field ranges the packed word cannot
  represent and as the packed paths' cross-representation oracle (tests
  assert all paths produce identical histograms AND identical
  ``sent``/``rounds``/``residual``/``overflow`` accounting).

``ShuffleStats.bytes_exchanged`` makes the paper's defining cost — bytes
crossing the network — a first-class measured quantity: per-device bucket
bytes shipped through ``all_to_all`` summed over rounds (int32 with x64
off — saturating at the 2 GB horizon with a warning, never wrapping;
enable ``jax_enable_x64`` for exact int64 accounting at paper-scale
classes).
"""

from __future__ import annotations

import warnings
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.compat import axis_size
from repro.common.types import (
    EXCHANGE_IMPLS,
    EventLog,
    PACK_MAX_SITES,
    PACK_MAX_WEEKS,
    SECONDS_PER_WEEK,
    WEEKS_PER_YEAR,
    pack_site_week_mark,
    unpack_site_week_mark,
)
from repro.core.spm import site_week_histogram
from repro.kernels.count_scatter import count_scatter

# Bytes one bucket slot occupies on the wire per shuffle round.
PACKED_SLOT_BYTES = 4        # one uint32 word
UNPACKED_SLOT_BYTES = 17     # four int32 columns + one bool validity column


class ShuffleExhaustedError(RuntimeError):
    """``max_rounds`` shuffle rounds ran and records remain undelivered."""


class ShuffleStats(NamedTuple):
    """Shuffle accounting. From ``mapreduce_histogram`` the fields cover the
    whole multi-round loop (per device; ``shuffle_stats`` psums them):

    - ``sent``: records delivered to their reducer, summed over rounds;
    - ``overflow``: records still undelivered when the loop stopped —
      **0 means the shuffle was lossless** (always, unless an explicit
      ``max_rounds`` cut the loop short);
    - ``capacity``: per-destination bucket capacity of each round;
    - ``rounds``: shuffle rounds executed (identical on every device; the
      streaming engine reports the max over chunks);
    - ``residual``: total deferred-record re-packs — the sum over rounds of
      records pushed to the next round (a record deferred k times counts k
      times), i.e. how much re-shuffle pressure the capacity caused;
    - ``bytes_exchanged``: bucket-buffer bytes this device shipped through
      ``all_to_all``, summed over rounds (``rounds x P x capacity x
      bytes-per-slot`` — the fixed-capacity buffers cross the network
      whole, empty slots included). The paper's defining MapReduce cost
      (§6.1) as a measured number; the packed word is 4 bytes/slot vs 17
      for the 4-column fallback. int32 with x64 off (saturates with a
      warning past 2 GB/device instead of wrapping), int64 with x64 on.

    ``_pack_buckets`` fills the same tuple for its single round
    (``rounds=1``, ``residual == overflow`` = this round's leftover,
    ``bytes_exchanged = 0`` — the exchange, and thus byte accounting,
    happens in ``mapreduce_histogram``).

    The trailing-field defaults are ``np.int32`` scalars, NOT Python ints:
    a Python int default is weakly typed inside jit, so ``shuffle_stats``'s
    psums would rely on implicit weak-type promotion (and a uint32 consumer
    would see the value silently change dtype). numpy scalars carry a
    concrete int32 dtype without initializing a jax backend at import time
    (``tests/test_packed_shuffle.py`` regression-tests this contract).
    """

    sent: jnp.ndarray
    overflow: jnp.ndarray
    capacity: jnp.ndarray
    rounds: jnp.ndarray = np.int32(1)
    residual: jnp.ndarray = np.int32(0)
    bytes_exchanged: jnp.ndarray = np.int32(0)


def _pack_buckets(log: EventLog, num_partitions: int, capacity: int):
    """Scatter records into a [P, C, fields] bucket buffer by site % P.

    Returns ``(bucket_columns, residual_log, stats)``: records beyond
    ``capacity`` for their destination are kept (not dropped) in
    ``residual_log`` — an ``EventLog`` of the same record count whose
    ``valid`` mask marks exactly the leftover records, ready to be packed
    again by the next shuffle round.
    """
    n = log.num_records
    dest = (log.site_id % num_partitions).astype(jnp.int32)
    valid = log.valid_mask()
    dest = jnp.where(valid, dest, num_partitions)  # invalid -> overflow row

    # Stable position of each record within its destination bucket.
    order = jnp.argsort(dest, stable=True)
    dest_sorted = dest[order]
    # start offset of each destination in the sorted order
    starts = jnp.searchsorted(dest_sorted, jnp.arange(num_partitions + 1))
    pos_sorted = jnp.arange(n) - starts[dest_sorted]
    keep = (pos_sorted < capacity) & (dest_sorted < num_partitions)

    bucket_row = jnp.where(keep, dest_sorted, num_partitions)
    bucket_pos = jnp.where(keep, pos_sorted, 0)

    def scatter(col, fill):
        buf = jnp.full((num_partitions + 1, capacity), fill, col.dtype)
        return buf.at[bucket_row, bucket_pos].set(col[order])[:num_partitions]

    site = scatter(log.site_id, -1)
    entity = scatter(log.entity_id, 0)
    ts = scatter(log.timestamp, 0)
    mark = scatter(log.mark, 0)
    vmask = site >= 0

    leftover = (~keep) & (dest_sorted < num_partitions)
    residual = EventLog(
        site_id=log.site_id[order], entity_id=log.entity_id[order],
        timestamp=log.timestamp[order], mark=log.mark[order],
        valid=leftover)
    overflow = jnp.sum(leftover)
    sent = jnp.sum(keep)
    stats = ShuffleStats(sent=sent, overflow=overflow,
                         capacity=jnp.int32(capacity),
                         rounds=np.int32(1), residual=overflow)
    return (site, entity, ts, mark, vmask), residual, stats


def static_capacity(num_records: int, parts: int,
                    capacity_factor: float) -> int:
    """Per-destination bucket capacity for a per-device record count —
    the single formula both the shuffle and its callers' static checks
    use (keeping them from drifting apart)."""
    return int(max(1, round(num_records / parts * capacity_factor)))


def shuffle_round_bound(num_records: int, capacity: int) -> int:
    """Static round count that provably drains any skew: a device holds at
    most ``num_records`` records for one destination and each round moves
    ``capacity`` of them."""
    return max(1, -(-num_records // capacity))


def packed_shuffle_supported(num_sites: int, num_weeks: int) -> bool:
    """Whether the one-word record projection can represent this workload
    (site in 24 bits, week in 6 — see ``repro.common.types``)."""
    return num_sites <= PACK_MAX_SITES and num_weeks <= PACK_MAX_WEEKS


def resolve_packed_shuffle(packed: Optional[bool], num_sites: int,
                           num_weeks: int) -> bool:
    """Static pack-vs-fallback decision. ``None`` = auto (pack whenever the
    fields fit); an explicit ``True`` for an unrepresentable workload is an
    error, never a silent fallback."""
    supported = packed_shuffle_supported(num_sites, num_weeks)
    if packed is None:
        return supported
    if packed and not supported:
        raise ValueError(
            f"packed shuffle requested but the one-word projection cannot "
            f"represent num_sites={num_sites} (max {PACK_MAX_SITES}) / "
            f"num_weeks={num_weeks} (max {PACK_MAX_WEEKS}); pass "
            f"packed=None for the automatic 4-column fallback")
    return bool(packed)


def resolve_exchange_impl(impl: Optional[str], num_sites: int,
                          num_weeks: int,
                          packed: Optional[bool] = None) -> str:
    """Static exchange-implementation decision (module docstring).

    ``impl=None`` defers to the legacy ``packed`` tri-state
    (``True -> "sort"``, ``False -> "columns"``, ``None -> "auto"``);
    ``"auto"`` picks the counting exchange whenever the one-word projection
    can represent the workload, else the 4-column fallback. Forcing a
    word-based impl (``"sort"``/``"counting"``) on an unrepresentable
    workload raises — never a silent fallback.
    """
    if impl is None:
        impl = "auto" if packed is None else ("sort" if packed else "columns")
    if impl not in EXCHANGE_IMPLS:
        raise ValueError(
            f"exchange impl must be one of {EXCHANGE_IMPLS}, got {impl!r}")
    supported = packed_shuffle_supported(num_sites, num_weeks)
    if impl == "auto":
        return "counting" if supported else "columns"
    if impl in ("sort", "counting") and not supported:
        raise ValueError(
            f"exchange impl {impl!r} requested but the one-word projection "
            f"cannot represent num_sites={num_sites} (max {PACK_MAX_SITES}) "
            f"/ num_weeks={num_weeks} (max {PACK_MAX_WEEKS}); use "
            f"impl='auto' for the automatic 4-column fallback")
    return impl


def _sort_words(words: jnp.ndarray, dest: jnp.ndarray, num_partitions: int):
    """Order words by destination via stable argsort (the "sort" impl).
    Returns ``(words_sorted, starts)`` — the counting path's oracle."""
    order = jnp.argsort(dest, stable=True)
    starts = jnp.searchsorted(dest[order], jnp.arange(num_partitions + 1))
    return words[order], starts


def _counting_words(words: jnp.ndarray, dest: jnp.ndarray,
                    num_partitions: int):
    """Order words by destination via stable counting sort (the "counting"
    impl) — bit-identical output to ``_sort_words``, two O(n) passes."""
    return count_scatter(words, dest, num_partitions)


def mapreduce_histogram(log: EventLog,
                        num_sites: int,
                        num_weeks: int = WEEKS_PER_YEAR,
                        axis_name: str = "data",
                        capacity_factor: float = 2.0,
                        histogram_fn=site_week_histogram,
                        max_rounds: Optional[int] = None,
                        packed: Optional[bool] = None,
                        impl: Optional[str] = None,
                        word_histogram_fn=None,
                        ) -> tuple[jnp.ndarray, ShuffleStats]:
    """Multi-round lossless shuffle + reduce. Returns (owned hist, stats).

    Device ``d`` owns the strided site set ``{j : j % P == d}`` (paper's
    Partitioner); the returned histogram is ``[num_sites // P, W, 2]`` with
    local row ``i`` = global site ``i * P + d``. ``num_sites % P == 0``
    required (runner pads).

    The shuffle loop re-exchanges residual (bucket-overflow) records until
    the global leftover count is zero, so the histogram is exact at any
    ``capacity_factor`` — including under MalGen's power-law site skew with
    every record on one site. ``max_rounds=None`` uses the static bound
    ``ceil(n / capacity)`` (provably sufficient); an explicit smaller value
    bounds latency but may stop with ``stats.overflow > 0`` — callers that
    thread it must check (``repro.core.runner`` raises
    ``ShuffleExhaustedError``).

    ``impl`` selects the exchange implementation (module docstring):
    ``"counting"`` / ``"sort"`` / ``"columns"`` / ``"auto"``; ``None``
    defers to the legacy ``packed`` tri-state (``True -> "sort"``,
    ``False -> "columns"``, ``None -> "auto"``). ``"auto"`` is the
    counting exchange whenever ``num_sites <= 2^24`` and
    ``num_weeks <= 64``, else the 4-column fallback; forcing a word-based
    impl on an unrepresentable workload raises ``ValueError``. All paths
    produce bit-identical histograms and identical stats; only
    ``bytes_exchanged`` (4 vs 17 B/slot) and wall time differ.

    ``word_histogram_fn`` (optional) is the fused reducer hook for the
    word-based impls: called as ``(shipped_words, my_index, s_local,
    num_weeks, p)`` instead of unpack + ``histogram_fn`` — the Pallas
    ``segment_hist_packed_words`` kernel reduces the shuffled words
    without materializing the unpacked columns. Ignored by ``"columns"``.

    The pack, the ordering and the rounds run under the ``malstone.exchange``
    scope; the reducer's fold of each round nests ``malstone.combine``.
    """
    p = axis_size(axis_name)
    n = log.num_records
    capacity = static_capacity(n, p, capacity_factor)
    if max_rounds is None:
        max_rounds = shuffle_round_bound(n, capacity)
    if max_rounds < 1:
        raise ValueError(f"max_rounds must be >= 1, got {max_rounds}")
    impl = resolve_exchange_impl(impl, num_sites, num_weeks, packed=packed)
    with jax.named_scope("malstone.exchange"):
        if impl == "columns":
            return _unpacked_shuffle_histogram(
                log, num_sites, num_weeks, axis_name, capacity,
                histogram_fn, max_rounds)
        return _word_shuffle_histogram(
            log, num_sites, num_weeks, axis_name, capacity, histogram_fn,
            max_rounds,
            order_words=_sort_words if impl == "sort" else _counting_words,
            word_histogram_fn=word_histogram_fn)


def _shuffle_loop(body, carry0, *, capacity: int,
                  num_partitions: int, slot_bytes: int, max_rounds: int):
    """Shared while-loop skeleton: both exchange implementations carry
    ``(rounds, global_left, hist, <impl state...>, sent, deferred)`` and
    stop when the psum'd global leftover reaches zero or ``max_rounds`` is
    exhausted. Returns the final carry plus the per-device
    ``bytes_exchanged`` total (one full ``[P, capacity]`` buffer per slot
    column per round)."""

    def cond(carry):
        rounds, global_left = carry[0], carry[1]
        return (global_left > 0) & (rounds < max_rounds)

    out = jax.lax.while_loop(cond, body, carry0)
    rounds = out[0]
    # Byte accounting in the widest integer the session allows: with x64
    # off the counter is int32, whose per-device horizon (2 GB shipped) is
    # reachable at paper-scale classes — saturate the static per-round term
    # (never crash the trace or wrap silently) and tell the caller how to
    # get exact numbers. The psum across devices can still wrap int32 at
    # extreme scale; enabling x64 widens the whole chain.
    dtype = jnp.int64 if jax.config.jax_enable_x64 else jnp.int32
    limit = int(jnp.iinfo(dtype).max)
    per_round = num_partitions * capacity * slot_bytes
    if per_round * max_rounds > limit:
        warnings.warn(
            f"ShuffleStats.bytes_exchanged may exceed {dtype.__name__} "
            f"({per_round} B/round x up to {max_rounds} rounds); the value "
            f"saturates instead of wrapping — enable jax_enable_x64 for "
            f"exact byte accounting at this scale", stacklevel=2)
    per_round_c = min(per_round, limit)
    # first round count whose exact byte total would exceed the dtype —
    # select the saturation value there so the (wrapping) product below
    # it is only ever used where it is exact
    sat_from = limit // per_round_c + 1
    bytes_exchanged = jnp.where(
        rounds >= sat_from, jnp.asarray(limit, dtype),
        rounds.astype(dtype) * jnp.asarray(per_round_c, dtype))
    return out, bytes_exchanged


def _unpacked_shuffle_histogram(log: EventLog, num_sites: int,
                                num_weeks: int, axis_name: str,
                                capacity: int, histogram_fn,
                                max_rounds: int):
    """The 4-column fallback: per-round stable argsort + bucket scatter of
    all record columns (``_pack_buckets``), residual records re-packed as a
    same-shape ``EventLog`` each round. Kept as the oracle for the packed
    path and for field ranges the packed word cannot represent."""
    p = axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    s_local = num_sites // p

    def exch(x):
        return jax.lax.all_to_all(x, axis_name, split_axis=0, concat_axis=0,
                                  tiled=True)

    def one_round(pending: EventLog):
        """Pack -> all_to_all -> local reduce. Returns the histogram
        increment of the received records plus the residual for the next
        round."""
        cols, residual, rstats = _pack_buckets(pending, p, capacity)
        site, entity, ts, mark, vmask = (exch(c) for c in cols)
        shuffled = EventLog(
            site_id=site.reshape(-1),
            entity_id=entity.reshape(-1),
            timestamp=ts.reshape(-1),
            mark=mark.reshape(-1),
            valid=vmask.reshape(-1),
        )
        # Re-base strided site ids to local dense rows: local = site // P.
        # All received records satisfy site % P == my by construction;
        # guard anyway.
        with jax.named_scope("malstone.combine"):
            ok = shuffled.valid & ((shuffled.site_id % p) == my)
            rebased = shuffled._replace(site_id=shuffled.site_id // p,
                                        valid=ok)
            inc = histogram_fn(rebased, s_local, num_weeks)
        return inc, residual, rstats

    # Normalize the pending-record pytree so the while carry has a fixed
    # structure (the shuffle only moves the four record columns + validity).
    pending0 = EventLog(site_id=log.site_id, entity_id=log.entity_id,
                        timestamp=log.timestamp, mark=log.mark,
                        valid=log.valid_mask())

    def body(carry):
        rounds, _, hist, pending, sent, deferred = carry
        inc, residual, rstats = one_round(pending)
        rounds = rounds + 1
        left = jax.lax.psum(rstats.overflow, axis_name)
        with jax.named_scope("malstone.combine"):
            hist = hist + inc
        return (rounds, left, hist, residual,
                sent + rstats.sent,
                deferred + rstats.overflow)

    carry0 = (jnp.int32(0),
              jax.lax.psum(jnp.sum(pending0.valid), axis_name),
              jnp.zeros((s_local, num_weeks, 2), jnp.int32),
              pending0,
              jnp.int32(0),
              jnp.int32(0))
    carry, bytes_exchanged = _shuffle_loop(
        body, carry0, capacity=capacity, num_partitions=p,
        slot_bytes=UNPACKED_SLOT_BYTES, max_rounds=max_rounds)
    rounds, _, hist, pending, sent, deferred = carry

    stats = ShuffleStats(
        sent=sent,
        overflow=jnp.sum(pending.valid_mask()),  # undelivered after loop
        capacity=jnp.int32(capacity),
        rounds=rounds,
        residual=deferred,
        bytes_exchanged=bytes_exchanged,
    )
    return hist, stats


def _word_shuffle_histogram(log: EventLog, num_sites: int,
                            num_weeks: int, axis_name: str,
                            capacity: int, histogram_fn,
                            max_rounds: int, *, order_words,
                            word_histogram_fn=None):
    """Packed word exchange (module docstring): project every record to
    one uint32 word, order the words by destination ONCE before the loop
    (``order_words`` — stable argsort for the "sort" impl, counting sort
    for "counting"; bit-identical permutations), then each round gathers
    the next ``capacity``-wide window per destination from the ordered
    array. The residual of round ``r`` is exactly the ordered suffix past
    offset ``(r+1) * capacity`` of each destination segment — ordered by
    construction, so no per-round re-ordering and no residual buffer at
    all; the loop carries only scalar counters and the histogram."""
    p = axis_size(axis_name)
    my = jax.lax.axis_index(axis_name)
    s_local = num_sites // p

    valid = log.valid_mask()
    # Mapper-side projection: week is bucketed BEFORE the exchange (the
    # Reducer's own bucketing function, so the round-trip is exact) and the
    # four reducer-relevant fields become one word. Invalid rows order to a
    # trailing pseudo-destination and pack to the all-zero word.
    dest = jnp.where(valid, (log.site_id % p).astype(jnp.int32), p)
    words = pack_site_week_mark(log.site_id, log.week(num_weeks=num_weeks),
                                log.mark, valid)

    words_sorted, starts = order_words(words, dest, p)  # THE ordering — once
    counts = starts[1:] - starts[:-1]               # valid records per dest
    lane = jnp.arange(capacity, dtype=jnp.int32)[None, :]

    def reduce_words(shipped_words):
        """Fold one round's received words into an owned-histogram
        increment. The fused path hands the words straight to the Pallas
        unpack+histogram kernel; the default path unpacks and rebuilds a
        minimal EventLog so any histogram_fn reduces it unchanged —
        ``week * SECONDS_PER_WEEK`` re-buckets to exactly ``week``."""
        if word_histogram_fn is not None:
            return word_histogram_fn(shipped_words, my, s_local, num_weeks, p)
        site, week, mark, ok = unpack_site_week_mark(shipped_words)
        # Re-base strided site ids to local dense rows (site % P == my by
        # construction; guard anyway).
        ok = ok & ((site % p) == my)
        rebased = EventLog(site_id=site // p, entity_id=jnp.zeros_like(site),
                           timestamp=week * SECONDS_PER_WEEK, mark=mark,
                           valid=ok)
        return histogram_fn(rebased, s_local, num_weeks)

    def body(carry):
        r, _, hist, sent, deferred = carry
        # Round r ships window [r*C, (r+1)*C) of every destination segment.
        idx = (starts[:-1] + r * capacity)[:, None] + lane       # [P, C]
        live = idx < starts[1:][:, None]
        buf = jnp.where(live, jnp.take(words_sorted, idx, mode="clip"),
                        jnp.uint32(0))
        shipped = jax.lax.all_to_all(buf, axis_name, split_axis=0,
                                     concat_axis=0, tiled=True)
        left = jnp.sum(jnp.maximum(counts - (r + 1) * capacity, 0))
        r_next = r + 1
        global_left = jax.lax.psum(left, axis_name)
        with jax.named_scope("malstone.combine"):
            hist = hist + reduce_words(shipped.reshape(-1))
        return (r_next, global_left, hist,
                sent + jnp.sum(live),
                deferred + left)

    carry0 = (jnp.int32(0),
              jax.lax.psum(starts[p], axis_name),   # global valid count
              jnp.zeros((s_local, num_weeks, 2), jnp.int32),
              jnp.int32(0),
              jnp.int32(0))
    carry, bytes_exchanged = _shuffle_loop(
        body, carry0, capacity=capacity, num_partitions=p,
        slot_bytes=PACKED_SLOT_BYTES, max_rounds=max_rounds)
    rounds, _, hist, sent, deferred = carry

    stats = ShuffleStats(
        sent=sent,
        # undelivered after the loop: the sorted suffix past rounds*C
        overflow=jnp.sum(jnp.maximum(counts - rounds * capacity, 0)),
        capacity=jnp.int32(capacity),
        rounds=rounds,
        residual=deferred,
        bytes_exchanged=bytes_exchanged,
    )
    return hist, stats


def shuffle_stats(stats: ShuffleStats, axis_name: str = "data") -> ShuffleStats:
    """Global shuffle accounting: psum the per-device counters (``rounds``
    and ``capacity`` are device-uniform and pass through unchanged)."""
    return ShuffleStats(
        sent=jax.lax.psum(stats.sent, axis_name),
        overflow=jax.lax.psum(stats.overflow, axis_name),
        capacity=stats.capacity,
        rounds=stats.rounds,
        residual=jax.lax.psum(stats.residual, axis_name),
        bytes_exchanged=jax.lax.psum(stats.bytes_exchanged, axis_name),
    )


def mapreduce_combiner_histogram(log: EventLog,
                                 num_sites: int,
                                 num_weeks: int = WEEKS_PER_YEAR,
                                 axis_name: str = "data",
                                 histogram_fn=site_week_histogram,
                                 ) -> jnp.ndarray:
    """MapReduce WITH a combiner — the §Perf hillclimb of the paper's
    slowest stack (EXPERIMENTS.md §Perf cell 3).

    Hadoop's classic fix for shuffle-bound jobs: aggregate map output
    locally before the shuffle. The paper's MapReduce implementation ships
    every record to its reducer; but the site x week histogram is a
    commutative monoid, so each mapper can pre-reduce its records into
    partial (site, week) counts and the shuffle only moves histogram
    *slices*: bytes drop from O(records x 16 B) to O(sites x weeks x 8 B),
    independent of record count. Functionally identical output to
    ``mapreduce_histogram`` (tests assert exact equality); the dataflow is
    an all-to-all of pre-reduced strided site blocks + a local sum — i.e.
    the combiner turns MapReduce into Sphere's dataflow, which is exactly
    why Sphere won Tables 4/5.
    """
    p = axis_size(axis_name)
    with jax.named_scope("malstone.exchange"):
        with jax.named_scope("malstone.combine"):
            local = histogram_fn(log, num_sites, num_weeks)   # [S, W, 2]
        # regroup rows so destination d's strided sites (j % P == d) form a
        # contiguous block: row (d, i) = site i * P + d
        s_local = num_sites // p
        blocks = local.reshape(s_local, p, num_weeks, 2).transpose(
            1, 0, 2, 3)
        # shuffle: block d of every device -> device d; then sum the P
        # partials
        exch = jax.lax.all_to_all(blocks, axis_name, split_axis=0,
                                  concat_axis=0, tiled=True)
        return jnp.sum(exch.reshape(p, s_local, num_weeks, 2), axis=0)
