"""MalStone A & B drivers over a device mesh.

``malstone_run`` is the public entry point: give it an event log sharded over
the record dimension, a mesh, and a backend name; it returns the SpmResult
with identical values regardless of backend (tests assert exact equality of
the integer histograms across backends — the paper's three stacks compute the
same statistic, only the dataflow differs).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.common.compat import shard_map

from repro.common.types import (
    EventLog,
    ExchangePlan,
    PAD_SHARD_HASH,
    SpmResult,
    WEEKS_PER_YEAR,
    resolve_exchange_plan,
)
from repro.core import spm as spm_lib
from repro.core.backends import (
    ShuffleExhaustedError,
    ShuffleStats,
    mapreduce_histogram,
    shuffle_stats,
    sphere_histogram,
    streams_histogram,
)
from repro.core.backends.mapreduce import mapreduce_combiner_histogram
from repro.core.plan import resolve_histogram_fns

_STATS_SPEC = ShuffleStats(P(), P(), P(), P(), P(), P())


def _raise_if_exhausted(stats: Optional[ShuffleStats]) -> None:
    """Host-side escape-hatch check: an explicit ``max_shuffle_rounds`` may
    stop the shuffle loop with records undelivered — that must be an error,
    never a silent drop. Only runs eagerly; the under-trace case is closed
    by ``_check_round_cap_under_trace`` below."""
    if stats is None or isinstance(stats.overflow, jax.core.Tracer):
        return
    undelivered = int(stats.overflow)
    if undelivered > 0:
        raise ShuffleExhaustedError(
            f"mapreduce shuffle stopped after {int(stats.rounds)} rounds "
            f"with {undelivered} records undelivered (bucket capacity "
            f"{int(stats.capacity)}); raise max_shuffle_rounds (None = "
            f"the provably sufficient ceil(records/capacity) bound) or "
            f"capacity_factor")


def _refuse_under_bound_cap(max_shuffle_rounds: Optional[int],
                            return_shuffle_stats: bool,
                            shard_records: int, parts: int,
                            capacity_factor: float) -> None:
    """Refuse a traced call whose explicit round cap is below the provable
    lossless bound (all bound math is static Python ints): the post-run
    overflow check cannot raise under a trace, so such a cap could drop
    records with no error — unless the caller takes responsibility for
    checking the returned stats (``return_shuffle_stats=True``)."""
    from repro.core.backends.mapreduce import (
        shuffle_round_bound,
        static_capacity,
    )
    if max_shuffle_rounds is None or return_shuffle_stats:
        return
    bound = shuffle_round_bound(
        shard_records, static_capacity(shard_records, parts, capacity_factor))
    if max_shuffle_rounds < bound:
        raise ValueError(
            f"max_shuffle_rounds={max_shuffle_rounds} is below the provable "
            f"lossless bound ({bound}) and the call is being traced, so the "
            f"post-run overflow check cannot raise — records could be "
            f"silently dropped. Pass return_shuffle_stats=True and check "
            f"stats.overflow yourself, or raise max_shuffle_rounds")


def _check_round_cap_under_trace(inputs, max_shuffle_rounds: Optional[int],
                                 return_shuffle_stats: bool,
                                 shard_records: int, parts: int,
                                 capacity_factor: float) -> None:
    """Close the silent-drop hole for traced callers whose *inputs* carry
    tracers (the materialized/seed-mode drivers). The generated drivers
    have no traced inputs — their seed is concrete by contract — so they
    detect an outer trace on the *output* instead (see
    ``_check_stats_or_refuse``)."""
    if not any(isinstance(x, jax.core.Tracer)
               for x in jax.tree_util.tree_leaves(inputs)):
        return  # eager call: _raise_if_exhausted will see concrete stats
    _refuse_under_bound_cap(max_shuffle_rounds, return_shuffle_stats,
                            shard_records, parts, capacity_factor)


def _check_stats_or_refuse(stats: Optional[ShuffleStats],
                           max_shuffle_rounds: Optional[int],
                           return_shuffle_stats: bool,
                           shard_records: int, parts: int,
                           capacity_factor: float) -> None:
    """Post-run lossless check for the generated drivers. Their seed input
    is always concrete (closed over), so input sniffing cannot detect an
    outer ``jax.jit`` — but the returned stats can: traced stats mean the
    overflow check below cannot fire, so an under-bound explicit cap must
    be refused statically instead."""
    if stats is not None and isinstance(stats.overflow, jax.core.Tracer):
        _refuse_under_bound_cap(max_shuffle_rounds, return_shuffle_stats,
                                shard_records, parts, capacity_factor)
        return
    _raise_if_exhausted(stats)


def _pad_sites(num_sites: int, parts: int) -> int:
    return ((num_sites + parts - 1) // parts) * parts


def _finalize(hist: jnp.ndarray, statistic: str) -> SpmResult:
    with jax.named_scope("malstone.finalize"):
        if statistic == "A":
            return spm_lib.malstone_a(hist)
        if statistic == "B":
            return spm_lib.malstone_b(hist)
        if statistic == "B-fixed":
            return spm_lib.malstone_b_fixed_denominator(hist)
    raise ValueError(f"unknown statistic {statistic!r}")


def _axis_size(mesh: Mesh, axis_name) -> int:
    if isinstance(axis_name, str):
        return mesh.shape[axis_name]
    size = 1
    for a in axis_name:
        size *= mesh.shape[a]
    return size


def _local_backend_histogram(log_shard: EventLog, backend: str, s_pad: int,
                             num_weeks: int, axis_name, hist_fn,
                             plan: ExchangePlan, word_histogram_fn=None):
    """One device's backend dataflow -> (replicated full-site histogram,
    ShuffleStats or None). Runs INSIDE ``shard_map``; shared by the
    materialized (``malstone_run``), fused-generation
    (``malstone_run_generated``) and partitioned drivers. The ``mapreduce``
    exchange is configured by ``plan`` (impl / capacity / round cap)."""
    if backend == "streams":
        return streams_histogram(log_shard, s_pad, num_weeks, axis_name,
                                 histogram_fn=hist_fn), None
    if backend == "sphere":
        owned = sphere_histogram(log_shard, s_pad, num_weeks, axis_name,
                                 histogram_fn=hist_fn)
        # Gather owned contiguous blocks back to full (tests / API parity;
        # production would keep the partitioned result — see
        # ``malstone_run_partitioned``).
        return jax.lax.all_gather(owned, axis_name, axis=0, tiled=True), None
    if backend in ("mapreduce", "mapreduce_combiner"):
        stats = None
        if backend == "mapreduce":
            owned, stats = mapreduce_histogram(
                log_shard, s_pad, num_weeks, axis_name,
                capacity_factor=plan.capacity_factor, histogram_fn=hist_fn,
                max_rounds=plan.max_shuffle_rounds, impl=plan.impl,
                word_histogram_fn=word_histogram_fn)
            stats = shuffle_stats(stats, axis_name)
        else:
            owned = mapreduce_combiner_histogram(
                log_shard, s_pad, num_weeks, axis_name,
                histogram_fn=hist_fn)
        # owned rows are strided (site = row * P + d): gather + unstride.
        gathered = jax.lax.all_gather(owned, axis_name, axis=0)  # [P,S/P,W,2]
        full = jnp.transpose(gathered, (1, 0, 2, 3)).reshape(
            s_pad, num_weeks, 2)
        return full, stats
    raise ValueError(f"unknown backend {backend!r}")


def _log_pspec(log: EventLog, axis_name) -> EventLog:
    """Record-dim PartitionSpecs for a log's present columns."""
    return EventLog(
        site_id=P(axis_name), entity_id=P(axis_name), timestamp=P(axis_name),
        mark=P(axis_name),
        event_seq=None if log.event_seq is None else P(axis_name),
        shard_hash=None if log.shard_hash is None else P(axis_name),
        valid=None if log.valid is None else P(axis_name),
    )


def malstone_run(log: EventLog,
                 num_sites: int,
                 *,
                 mesh: Mesh,
                 statistic: str = "B",
                 backend: str = "streams",
                 num_weeks: int = WEEKS_PER_YEAR,
                 axis_name="data",
                 plan: Optional[ExchangePlan] = None,
                 capacity_factor: Optional[float] = None,
                 max_shuffle_rounds: Optional[int] = None,
                 packed_shuffle: Optional[bool] = None,
                 histogram_fn=None,
                 donate_log: bool = False,
                 return_shuffle_stats: bool = False):
    """Run MalStone over the mesh. Returns a replicated, full-site SpmResult.

    ``axis_name`` may be a single mesh axis or a tuple (the production
    meshes treat every chip as a data-cloud node: ("pod","data","model")).
    The log must be shardable over the record dimension by the total size of
    ``axis_name`` (caller pads with ``valid=False`` rows if needed).

    The shuffle/reducer configuration is one ``plan``
    (:class:`~repro.common.types.ExchangePlan`): ``plan.impl`` selects the
    ``mapreduce`` exchange implementation (``"auto"`` — the default — uses
    the one-word packed *counting-sort* path whenever the padded site count
    fits in 24 bits and ``num_weeks <= 64``, falling back to the 4-column
    exchange; ``"counting"`` / ``"sort"`` / ``"columns"`` force one),
    ``plan.capacity_factor`` sizes the per-round buckets,
    ``plan.max_shuffle_rounds`` caps the residual loop and
    ``plan.histogram_impl`` picks the reducer (``"pallas"`` fuses
    unpack+histogram over the shuffled words). All impls are bit-identical;
    only ``stats.bytes_exchanged`` and wall time differ (see
    ``backends/mapreduce.py``). The ``capacity_factor`` /
    ``max_shuffle_rounds`` / ``packed_shuffle`` keyword arguments are
    deprecated aliases that build a plan (and warn).

    The ``mapreduce`` backend's shuffle is lossless at any
    ``capacity_factor`` (multi-round residual exchange).
    ``max_shuffle_rounds=None`` uses the provably sufficient round bound;
    an explicit smaller cap raises ``ShuffleExhaustedError`` if records
    remain undelivered (and when the call is traced under an outer
    ``jax.jit`` — where that post-run check cannot fire — an under-bound
    cap is refused at trace time unless ``return_shuffle_stats=True`` puts
    the overflow counter in the caller's hands). With ``donate_log=True``
    the log's buffers are donated to the computation
    (``jax.jit(..., donate_argnums=0)``) — the caller must not reuse the
    log afterwards on backends that honor donation (CPU ignores it with a
    warning). ``return_shuffle_stats=True`` returns
    ``(SpmResult, ShuffleStats)`` — the globally psum'd shuffle accounting
    for ``mapreduce``, ``None`` for the other backends (no record shuffle).
    """
    plan = resolve_exchange_plan(
        plan, capacity_factor=capacity_factor,
        max_shuffle_rounds=max_shuffle_rounds, packed_shuffle=packed_shuffle,
        _caller="malstone_run")
    parts = _axis_size(mesh, axis_name)
    s_pad = _pad_sites(num_sites, parts)
    hist_fn, word_fn = resolve_histogram_fns(plan, histogram_fn)
    hist_fn = hist_fn or spm_lib.site_week_histogram

    def local(log_shard: EventLog):
        hist, stats = _local_backend_histogram(
            log_shard, backend, s_pad, num_weeks, axis_name, hist_fn,
            plan, word_fn)
        return (hist, stats) if backend == "mapreduce" else hist

    spec = _log_pspec(log, axis_name)
    out_specs = (P(), _STATS_SPEC) if backend == "mapreduce" else P()
    fn = shard_map(local, mesh=mesh, in_specs=(spec,), out_specs=out_specs,
                   check_vma=False)
    jit_fn = jax.jit(fn, donate_argnums=(0,) if donate_log else ())
    stats = None
    if backend == "mapreduce":
        _check_round_cap_under_trace(
            log, plan.max_shuffle_rounds, return_shuffle_stats,
            log.num_records // parts, parts, plan.capacity_factor)
        hist, stats = jit_fn(log)
        _raise_if_exhausted(stats)
    else:
        hist = jit_fn(log)
    result = _finalize(hist[:num_sites], statistic)
    return (result, stats) if return_shuffle_stats else result


def malstone_run_streaming(seed_or_log, num_sites: int, *,
                           mesh: Mesh,
                           backend: str = "streams",
                           chunk_records: int = 65_536,
                           statistic: str = "B",
                           cfg=None,
                           num_chunks: Optional[int] = None,
                           num_weeks: int = WEEKS_PER_YEAR,
                           axis_name="data",
                           plan: Optional[ExchangePlan] = None,
                           capacity_factor: Optional[float] = None,
                           max_shuffle_rounds: Optional[int] = None,
                           packed_shuffle: Optional[bool] = None,
                           histogram_fn=None,
                           return_shuffle_stats: bool = False,
                           overlap: Optional[bool] = None):
    """Streaming chunked MalStone: ``lax.scan`` over fixed-size record
    chunks with a histogram carry — peak memory O(chunk + sites x weeks)
    instead of O(records). Bit-identical integer histograms to
    ``malstone_run`` for **all four backends at any** ``capacity_factor``
    (the site x week histogram is a commutative monoid, so chunk
    accumulation is exact, and the ``mapreduce`` per-chunk shuffle is the
    same lossless multi-round residual loop as the one-shot path).
    ``plan`` / ``return_shuffle_stats`` behave exactly as in
    ``malstone_run`` (legacy shuffle kwargs are deprecated aliases);
    streaming ``ShuffleStats`` counters accumulate over
    chunks and ``rounds`` is the max any single chunk needed.

    ``overlap`` selects the execution strategy for seed mode: ``None``
    (default) runs the single-jit ``lax.scan``; ``True`` / ``False`` route
    through the double-buffered per-chunk driver
    (:class:`~repro.core.overlap.OverlapStreamingRunner` — overlap on
    pipelines chunk k+1's generation behind chunk k's exchange+reduce, off
    serializes the identical programs). All three are bit-identical,
    including ShuffleStats. Perf loops should hold their own runner
    instance (this convenience path builds — and so re-jits — one per
    call).

    Two modes, selected by the first argument:

    - ``SeedInfo`` (from ``make_seed_streaming``): generate-as-you-go — each
      scan step regenerates its chunk from the seed; requires ``cfg`` (the
      ``MalGenConfig``) and ``num_chunks`` (must divide evenly over the
      mesh). Equivalent one-shot oracle: ``malstone_run`` over
      ``generate_chunked_log(seed, cfg, num_chunks, chunk_records)``.
    - ``EventLog``: chunked pass over a pre-generated log; the log is padded
      with invalid rows so every device scans whole chunks (uneven final
      chunks are handled exactly).
    """
    from repro.core.streaming import (
        streaming_histogram_from_log,
        streaming_histogram_generate,
    )
    from repro.malgen.seeding import SeedInfo

    plan = resolve_exchange_plan(
        plan, capacity_factor=capacity_factor,
        max_shuffle_rounds=max_shuffle_rounds, packed_shuffle=packed_shuffle,
        _caller="malstone_run_streaming")
    parts = _axis_size(mesh, axis_name)
    s_pad = _pad_sites(num_sites, parts)
    if backend == "mapreduce":
        # per-chunk shuffle: the capacity/round bound is set by chunk size
        _check_round_cap_under_trace(
            seed_or_log, plan.max_shuffle_rounds, return_shuffle_stats,
            chunk_records, parts, plan.capacity_factor)

    if overlap is not None and not isinstance(seed_or_log, SeedInfo):
        raise ValueError(
            "overlap= requires seed-mode streaming (a SeedInfo source);"
            " the log path has no generation stage to pipeline")

    if isinstance(seed_or_log, SeedInfo):
        if cfg is None or num_chunks is None:
            raise ValueError("seed mode requires cfg= and num_chunks=")
        if num_chunks % parts != 0:
            raise ValueError(
                f"num_chunks ({num_chunks}) must divide over the mesh "
                f"({parts} devices)")
        if overlap is not None:
            from repro.core.overlap import OverlapStreamingRunner

            runner = OverlapStreamingRunner(
                seed_or_log, cfg, mesh=mesh, num_chunks=num_chunks,
                chunk_records=chunk_records, num_sites=num_sites,
                backend=backend, num_weeks=num_weeks, axis_name=axis_name,
                plan=plan, histogram_fn=histogram_fn)
            result, stats = runner.run_result(statistic, overlap=overlap)
            return (result, stats) if return_shuffle_stats else result
        seed = seed_or_log
        cpd = num_chunks // parts
        out_specs = (P(), _STATS_SPEC if backend == "mapreduce" else None)

        def run_gen():
            return streaming_histogram_generate(
                seed, cfg, s_pad, chunks_per_device=cpd,
                chunk_records=chunk_records, num_weeks=num_weeks,
                axis_name=axis_name, backend=backend,
                histogram_fn=histogram_fn, plan=plan)

        fn = shard_map(run_gen, mesh=mesh, in_specs=(), out_specs=out_specs,
                       check_vma=False)
        hist, stats = jax.jit(fn)()
    else:
        log = seed_or_log
        per_dev = -(-log.num_records // (parts * chunk_records)) * chunk_records
        log = pad_log_to(log, per_dev * parts)
        out_specs = (P(), _STATS_SPEC if backend == "mapreduce" else None)

        def run_log(log_shard: EventLog):
            return streaming_histogram_from_log(
                log_shard, s_pad, chunk_records=chunk_records,
                num_weeks=num_weeks, axis_name=axis_name, backend=backend,
                histogram_fn=histogram_fn, plan=plan)

        spec = _log_pspec(log, axis_name)
        fn = shard_map(run_log, mesh=mesh, in_specs=(spec,),
                       out_specs=out_specs, check_vma=False)
        hist, stats = jax.jit(fn)(log)

    if backend == "mapreduce":
        _raise_if_exhausted(stats)
    result = _finalize(hist[:num_sites], statistic)
    return (result, stats) if return_shuffle_stats else result


def malstone_run_generated(seed, cfg, *,
                           mesh: Mesh,
                           records_per_shard: int,
                           num_sites: Optional[int] = None,
                           statistic: str = "B",
                           backend: str = "streams",
                           num_weeks: int = WEEKS_PER_YEAR,
                           axis_name="data",
                           plan: Optional[ExchangePlan] = None,
                           capacity_factor: Optional[float] = None,
                           max_shuffle_rounds: Optional[int] = None,
                           packed_shuffle: Optional[bool] = None,
                           histogram_fn=None,
                           return_shuffle_stats: bool = False):
    """Fused MalGen phase 3 + MalStone: each device *generates* the shard
    "its node" owns (``generate_shard_device``) and feeds it straight into
    the backend dataflow — the global log is never materialized, on host or
    device. Bit-identical to ``malstone_run`` over
    ``generate_sharded_log(key, cfg, P, records_per_shard)`` when ``seed``
    is that log's ``SeedInfo`` and the mesh has P devices on ``axis_name``.

    ``seed`` comes from ``make_seed(key, cfg, P * records_per_shard)`` and
    is closed over (its ``num_marked_events`` must stay a Python int —
    don't pass it through ``jax.jit`` arguments). ``num_sites`` defaults to
    ``cfg.num_sites``; ``plan`` (and the deprecated shuffle kwarg aliases)
    behaves exactly as in ``malstone_run``.
    """
    from repro.malgen.generator import generate_shard_device

    plan = resolve_exchange_plan(
        plan, capacity_factor=capacity_factor,
        max_shuffle_rounds=max_shuffle_rounds, packed_shuffle=packed_shuffle,
        _caller="malstone_run_generated")
    parts = _axis_size(mesh, axis_name)
    num_sites = num_sites or cfg.num_sites
    s_pad = _pad_sites(num_sites, parts)
    hist_fn, word_fn = resolve_histogram_fns(plan, histogram_fn)
    hist_fn = hist_fn or spm_lib.site_week_histogram

    def local():
        sid = jax.lax.axis_index(axis_name)
        shard = generate_shard_device(seed, cfg, sid, parts,
                                      records_per_shard)
        return _local_backend_histogram(
            shard, backend, s_pad, num_weeks, axis_name, hist_fn,
            plan, word_fn)

    out_specs = (P(), _STATS_SPEC if backend == "mapreduce" else None)
    fn = shard_map(local, mesh=mesh, in_specs=(), out_specs=out_specs,
                   check_vma=False)
    hist, stats = jax.jit(fn)()
    if backend == "mapreduce":
        _check_stats_or_refuse(stats, plan.max_shuffle_rounds,
                               return_shuffle_stats, records_per_shard,
                               parts, plan.capacity_factor)
    result = _finalize(hist[:num_sites], statistic)
    return (result, stats) if return_shuffle_stats else result


def malstone_run_generated_streaming(seed, cfg, *,
                                     mesh: Mesh,
                                     records_per_shard: int,
                                     chunk_records: int = 65_536,
                                     num_sites: Optional[int] = None,
                                     statistic: str = "B",
                                     backend: str = "streams",
                                     num_weeks: int = WEEKS_PER_YEAR,
                                     axis_name="data",
                                     plan: Optional[ExchangePlan] = None,
                                     capacity_factor: Optional[float] = None,
                                     max_shuffle_rounds: Optional[int] = None,
                                     packed_shuffle: Optional[bool] = None,
                                     histogram_fn=None,
                                     return_shuffle_stats: bool = False):
    """Streaming twin of ``malstone_run_generated``: each device generates
    its shard in place, then folds it through the chunked ``lax.scan``
    engine (per-chunk backend dataflow, histogram carry). Bit-identical to
    ``malstone_run_streaming`` over the materialized
    ``generate_sharded_log`` log at the same ``chunk_records``.

    ``records_per_shard`` must divide by ``chunk_records`` (the shard-
    layout marked stream cannot be regenerated per chunk, so unlike seed-
    mode streaming the shard is generated once per device — peak memory
    O(records_per_shard + marked stream), the win over the host path being
    that generation happens on the mesh and the global log never exists).
    """
    from repro.core.streaming import streaming_histogram_from_log
    from repro.malgen.generator import generate_shard_device

    plan = resolve_exchange_plan(
        plan, capacity_factor=capacity_factor,
        max_shuffle_rounds=max_shuffle_rounds, packed_shuffle=packed_shuffle,
        _caller="malstone_run_generated_streaming")
    parts = _axis_size(mesh, axis_name)
    num_sites = num_sites or cfg.num_sites
    s_pad = _pad_sites(num_sites, parts)
    if records_per_shard % chunk_records != 0:
        raise ValueError(
            f"records_per_shard ({records_per_shard}) must be divisible by "
            f"chunk_records ({chunk_records}) on the fused generated path "
            f"(no padding rows are generated)")

    def local():
        sid = jax.lax.axis_index(axis_name)
        shard = generate_shard_device(seed, cfg, sid, parts,
                                      records_per_shard)
        return streaming_histogram_from_log(
            shard, s_pad, chunk_records=chunk_records, num_weeks=num_weeks,
            axis_name=axis_name, backend=backend, histogram_fn=histogram_fn,
            plan=plan)

    out_specs = (P(), _STATS_SPEC if backend == "mapreduce" else None)
    fn = shard_map(local, mesh=mesh, in_specs=(), out_specs=out_specs,
                   check_vma=False)
    hist, stats = jax.jit(fn)()
    if backend == "mapreduce":
        # per-chunk shuffle: the capacity/round bound is set by chunk size
        _check_stats_or_refuse(stats, plan.max_shuffle_rounds,
                               return_shuffle_stats, chunk_records, parts,
                               plan.capacity_factor)
    result = _finalize(hist[:num_sites], statistic)
    return (result, stats) if return_shuffle_stats else result


def malstone_run_partitioned(log: EventLog,
                             num_sites: int,
                             *,
                             mesh: Mesh,
                             statistic: str = "B",
                             backend: str = "sphere",
                             num_weeks: int = WEEKS_PER_YEAR,
                             axis_name="data",
                             plan: Optional[ExchangePlan] = None,
                             capacity_factor: Optional[float] = None,
                             max_shuffle_rounds: Optional[int] = None,
                             packed_shuffle: Optional[bool] = None,
                             histogram_fn=None,
                             return_shuffle_stats: bool = False):
    """Production path: the result stays partitioned by site block (device
    d owns sites [d*S/P, (d+1)*S/P)); the finalized statistic is never
    re-broadcast. Returns an SpmResult whose arrays are sharded over
    ``axis_name`` on the site dimension.

    Any backend works (``sphere``, the default, is the only one that also
    avoids gathering the *histogram* — its ``psum_scatter`` dataflow is
    already block-partitioned; the others compute the replicated histogram
    and finalize only the owned block). ``plan`` and the lossless-shuffle
    guards behave exactly as in ``malstone_run``:
    ``return_shuffle_stats=True`` returns ``(SpmResult, ShuffleStats)``
    and an under-bound explicit round cap is refused under a trace.
    """
    plan = resolve_exchange_plan(
        plan, capacity_factor=capacity_factor,
        max_shuffle_rounds=max_shuffle_rounds, packed_shuffle=packed_shuffle,
        _caller="malstone_run_partitioned")
    parts = _axis_size(mesh, axis_name)
    s_pad = _pad_sites(num_sites, parts)
    hist_fn, word_fn = resolve_histogram_fns(plan, histogram_fn)
    hist_fn = hist_fn or spm_lib.site_week_histogram
    block = s_pad // parts

    def local(log_shard: EventLog):
        if backend == "sphere":
            owned, stats = sphere_histogram(
                log_shard, s_pad, num_weeks, axis_name,
                histogram_fn=hist_fn), None
        else:
            hist, stats = _local_backend_histogram(
                log_shard, backend, s_pad, num_weeks, axis_name, hist_fn,
                plan, word_fn)
            my = jax.lax.axis_index(axis_name)
            owned = jax.lax.dynamic_slice_in_dim(hist, my * block, block)
        result = _finalize(owned, statistic)
        return (result, stats) if backend == "mapreduce" else result

    spec = _log_pspec(log, axis_name)
    out_spec = SpmResult(rho=P(axis_name), total=P(axis_name),
                         marked=P(axis_name))
    out_specs = ((out_spec, _STATS_SPEC) if backend == "mapreduce"
                 else out_spec)
    fn = shard_map(local, mesh=mesh, in_specs=(spec,), out_specs=out_specs,
                   check_vma=False)
    jit_fn = jax.jit(fn)
    stats = None
    if backend == "mapreduce":
        _check_round_cap_under_trace(
            log, plan.max_shuffle_rounds, return_shuffle_stats,
            log.num_records // parts, parts, plan.capacity_factor)
        result, stats = jit_fn(log)
        _raise_if_exhausted(stats)
    else:
        result = jit_fn(log)
    return (result, stats) if return_shuffle_stats else result


def malstone_lowerable(num_records_global: int, num_sites: int, *,
                       mesh: Mesh, backend: str = "sphere",
                       statistic: str = "B",
                       num_weeks: int = WEEKS_PER_YEAR,
                       axis_name=("data", "model"),
                       plan: Optional[ExchangePlan] = None,
                       capacity_factor: Optional[float] = None,
                       max_shuffle_rounds: Optional[int] = None,
                       packed_shuffle: Optional[bool] = None):
    """(fn, example_log_SDS) for dry-run lowering of the paper's workload.

    The log is a ShapeDtypeStruct stand-in (no allocation): the paper's
    benchmark classes are huge (B-10 = 10 billion records = 1 TB), exactly
    what ``.lower().compile()`` is for. Every chip acts as one data-cloud
    node (records sharded over all mesh axes).

    Note for HLO byte accounting: the ``mapreduce`` shuffle is now a
    multi-round ``while`` loop, and the trip-count-aware analyzer reports
    its *static worst-case* rounds. Pass ``max_shuffle_rounds=1`` to
    recover the expected-case single-round collective bytes — but treat
    that compiled artifact as **analysis-only**: a cap below the provable
    bound truncates the shuffle loop in the compiled program itself, and
    this path discards ``ShuffleStats``, so executing it on real skewed
    data would drop residual records with no error (use ``malstone_run``
    for anything that actually runs; it enforces the lossless contract)."""
    if (plan is None and capacity_factor is None
            and max_shuffle_rounds is None and packed_shuffle is None):
        # dry-run analysis default: tighter buckets than the run drivers
        plan = ExchangePlan(capacity_factor=1.5)
    else:
        plan = resolve_exchange_plan(
            plan, capacity_factor=capacity_factor,
            max_shuffle_rounds=max_shuffle_rounds,
            packed_shuffle=packed_shuffle, _caller="malstone_lowerable")
    parts = _axis_size(mesh, axis_name)
    n = (num_records_global // parts) * parts
    s_pad = _pad_sites(num_sites, parts)

    def fn(log: EventLog):
        def local(log_shard: EventLog) -> jnp.ndarray:
            if backend == "streams":
                hist = streams_histogram(log_shard, s_pad, num_weeks,
                                         axis_name)
            elif backend == "sphere":
                hist = sphere_histogram(log_shard, s_pad, num_weeks,
                                        axis_name)
            elif backend == "mapreduce":
                hist, _ = mapreduce_histogram(
                    log_shard, s_pad, num_weeks, axis_name,
                    capacity_factor=plan.capacity_factor,
                    max_rounds=plan.max_shuffle_rounds, impl=plan.impl)
            elif backend == "mapreduce_combiner":
                hist = mapreduce_combiner_histogram(
                    log_shard, s_pad, num_weeks, axis_name)
            else:
                raise ValueError(backend)
            return _finalize(hist, statistic).rho

        spec = EventLog(site_id=P(axis_name), entity_id=P(axis_name),
                        timestamp=P(axis_name), mark=P(axis_name))
        # streams output is replicated; sphere/mapreduce stay partitioned
        # by site (the production layout — nothing is re-broadcast)
        out_spec = P() if backend == "streams" else P(axis_name)
        return shard_map(local, mesh=mesh, in_specs=(spec,),
                         out_specs=out_spec, check_vma=False)(log)

    import jax as _jax
    sds = lambda: _jax.ShapeDtypeStruct((n,), jnp.int32)
    log_sds = EventLog(site_id=sds(), entity_id=sds(), timestamp=sds(),
                       mark=sds())
    return fn, log_sds


def malstone_single_device(log: EventLog, num_sites: int,
                           statistic: str = "B",
                           num_weeks: int = WEEKS_PER_YEAR,
                           histogram_fn=None) -> SpmResult:
    """Reference single-device path (the "fits in a database" case of §1)."""
    hist_fn = histogram_fn or spm_lib.site_week_histogram
    hist = hist_fn(log, num_sites, num_weeks)
    return _finalize(hist, statistic)


def pad_log_to(log: EventLog, target: int) -> EventLog:
    """Pad a log with invalid rows so the record dim divides the mesh."""
    with jax.named_scope("malstone.read"):
        n = log.num_records
        if n == target:
            if log.valid is None:
                return log._replace(valid=jnp.ones((n,), bool))
            return log
        pad = target - n
        if pad < 0:
            raise ValueError(
                f"pad_log_to target ({target}) is smaller than the log's "
                f"record count ({n}); pass a target >= num_records (it "
                f"should be the record count rounded up to a multiple of "
                f"mesh size x chunk)")

        def padcol(x, fill=0):
            return jnp.concatenate([x, jnp.full((pad,), fill, x.dtype)])

        valid = log.valid if log.valid is not None else jnp.ones((n,), bool)
        return EventLog(
            site_id=padcol(log.site_id),
            entity_id=padcol(log.entity_id),
            timestamp=padcol(log.timestamp),
            mark=padcol(log.mark),
            event_seq=None if log.event_seq is None else padcol(log.event_seq),
            # sentinel, not 0: a zero fill gave padding rows the Event IDs
            # (0, 0..pad) which collided with any real shard hashing to 0
            shard_hash=None if log.shard_hash is None
            else padcol(log.shard_hash, fill=PAD_SHARD_HASH),
            valid=jnp.concatenate([valid, jnp.zeros((pad,), bool)]),
        )
