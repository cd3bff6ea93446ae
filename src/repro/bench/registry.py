"""Scenario registry: every timed unit in the repo, named and enumerable.

The registry covers:

- the **full MalStone grid** — backend {streams, sphere, mapreduce,
  mapreduce_combiner} x statistic {A, B, B-fixed} x engine {one-shot,
  streaming}: ``malstone_{a|b|bfixed}_{backend}_{oneshot|streaming}``;
- the **kernel path pairs** — Pallas kernel (interpret mode on CPU) vs
  its pure-jnp reference: ``kernel_{segment_hist,windowed_ratio,
  powerlaw_sample}_{pallas,jnp}``;
- the **lossless shuffle sweep** — MalStone B over the ``mapreduce``
  backend at capacity factors {0.25, 0.5, 1.0, 2.0} plus one streaming
  point: ``mapreduce_lossless_cf{0p25,0p5,1,2}`` /
  ``mapreduce_lossless_streaming_cf0p5``, each recording the executed
  shuffle round count in its ``derived`` extras — and its paired
  **word-exchange sweeps**: ``mapreduce_packed_cf{0p5,1}`` (stable
  sort-once ordering) and ``mapreduce_counting_cf{0p5,1}`` (counting
  sort, the ``exchange_impl="auto"`` default), bit-identical histograms
  and stats to the 4-column rows at the same factor;
- the **MalGen phases** (paper Table 3): ``malgen_seed``,
  ``malgen_generate``, ``malgen_encode``;
- **scaling sweeps** — ``sweep_records_x{1,2,4}`` (records-per-node
  multipliers over the preset base), ``sweep_mesh_p{1,2,4}`` (mesh
  size; skipped when the host exposes fewer devices), and
  ``sweep_multiproc_p{1,2,4}`` (real ``jax.distributed`` localhost gangs
  of P one-device processes through ``repro.launch.malstone``, wall-clock
  + shuffle accounting adopted from the gang's own BENCH json);
- the **overlap pipeline pair** — ``streaming_overlap_{on,off}``: the
  double-buffered per-chunk driver (``repro.core.overlap``) vs the same
  compiled programs strictly serialized, over many small chunks;
- **resumable runs** — ``resume_overhead_{nockpt,ckpt,resume}`` (the
  checkpoint tax: segmented run without checkpoints, with a fresh
  checkpoint dir per call, and a pure restore-from-complete-checkpoint)
  and ``faulty_run_{transient,badhost}`` (seeded chaos schedules through
  the retry + NodeDoctor-rerouting recovery loop), each carrying its
  ``RecoveryReport`` accounting in ``derived``;
- **serving** — the resident query engine
  (``repro.serve.MalStoneService``): ``serving_ingest_latency`` (one
  chunk-per-device fold into the live state), ``serving_query_batch``
  (one jitted mixed-batch dispatch, p50/p95/p99 in ``derived``) and
  ``serving_sustained_qps`` (async submit/drain pipeline, queries/s).

Each scenario is a named, individually runnable unit:
``SCENARIOS[name].run(scale, ctx)`` times it under the shared protocol
(``repro.bench.timing``) and returns a ``ScenarioResult`` ready for
``repro.bench.schema.add_result``. A ``BenchContext`` caches generated
logs/seeds so a sweep over 24 grid points generates data once per shape.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Iterable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.bench.timing import TimingResult, time_callable

BACKENDS = ("streams", "sphere", "mapreduce", "mapreduce_combiner")
STATISTICS = ("A", "B", "B-fixed")
ENGINES = ("oneshot", "streaming")
KERNELS = ("segment_hist", "windowed_ratio", "powerlaw_sample")
KERNEL_PATHS = ("pallas", "jnp")

_STAT_SLUG = {"A": "a", "B": "b", "B-fixed": "bfixed"}


@dataclasses.dataclass(frozen=True)
class Scale:
    """One preset's knob settings; every scenario builder takes one."""

    records_per_node: int
    num_sites: int
    num_entities: int
    chunk_records: int        # streaming-engine chunk size
    warmup: int
    iters: int
    marked_event_fraction: float = 0.2

    def as_params(self) -> dict:
        return dataclasses.asdict(self)


PRESETS: Dict[str, Scale] = {
    # CI / acceptance preset: small enough for shared runners, still
    # compiles and runs every backend and both engines.
    "smoke": Scale(records_per_node=8_192, num_sites=512,
                   num_entities=4_096, chunk_records=2_048,
                   warmup=1, iters=3),
    # the historical benchmarks/run.py scale (paper-table CSV snapshot)
    "full": Scale(records_per_node=262_144, num_sites=2_048,
                  num_entities=16_384, chunk_records=65_536,
                  warmup=2, iters=3),
}


@dataclasses.dataclass
class ScenarioResult:
    timing: TimingResult
    records: Optional[int] = None
    derived: Optional[dict] = None
    # actual run parameters where they differ from the Scale defaults
    # (sweeps override nodes / records_per_node); merged last into the
    # emitted params so BENCH json provenance matches what actually ran
    effective: Optional[dict] = None


class BenchContext:
    """Per-process cache of meshes, logs, and seeds keyed by shape."""

    def __init__(self, nodes: Optional[int] = None):
        self.nodes = nodes or jax.device_count()
        if self.nodes > jax.device_count():
            raise ValueError(
                f"nodes={self.nodes} > visible devices ({jax.device_count()};"
                " set --nodes before jax initializes)")
        self._meshes: dict = {}
        self._logs: dict = {}
        self._seeds: dict = {}

    def cfg(self, scale: Scale):
        from repro.malgen import MalGenConfig
        return MalGenConfig(
            num_sites=scale.num_sites, num_entities=scale.num_entities,
            marked_event_fraction=scale.marked_event_fraction)

    def mesh(self, nodes: Optional[int] = None):
        nodes = nodes or self.nodes
        if nodes not in self._meshes:
            from repro.launch.mesh import make_mesh
            self._meshes[nodes] = make_mesh((nodes,), ("data",))
        return self._meshes[nodes]

    def log(self, scale: Scale, nodes: Optional[int] = None,
            records_per_node: Optional[int] = None):
        from repro.malgen import generate_sharded_log
        nodes = nodes or self.nodes
        rpn = records_per_node or scale.records_per_node
        key = (nodes, rpn, scale.num_sites, scale.num_entities,
               scale.marked_event_fraction)
        if key not in self._logs:
            log, _ = generate_sharded_log(
                jax.random.key(1), self.cfg(scale), nodes, rpn)
            jax.block_until_ready(log.site_id)
            self._logs[key] = log
        return self._logs[key]

    def seed(self, scale: Scale, nodes: Optional[int] = None):
        from repro.malgen import make_seed_streaming
        nodes = nodes or self.nodes
        num_chunks = nodes * max(
            1, scale.records_per_node // scale.chunk_records)
        key = (num_chunks, scale.chunk_records, scale.num_sites,
               scale.num_entities, scale.marked_event_fraction)
        if key not in self._seeds:
            seed = make_seed_streaming(
                jax.random.key(4), self.cfg(scale), num_chunks,
                scale.chunk_records)
            jax.block_until_ready(seed.entity_mark_time)
            self._seeds[key] = (seed, num_chunks)
        return self._seeds[key]


@dataclasses.dataclass(frozen=True)
class Scenario:
    """A named, individually runnable benchmark unit."""

    name: str
    group: str                # malstone | kernel | malgen | sweep
    params: dict              # the grid point (static descriptors)
    runner: Callable[[Scale, BenchContext], ScenarioResult]

    def run(self, scale: Scale, ctx: BenchContext) -> ScenarioResult:
        return self.runner(scale, ctx)


SCENARIOS: Dict[str, Scenario] = {}


def _register(name: str, group: str, params: dict):
    def deco(fn):
        if name in SCENARIOS:
            raise ValueError(f"duplicate scenario {name!r}")
        SCENARIOS[name] = Scenario(name=name, group=group, params=params,
                                   runner=fn)
        return fn
    return deco


# --------------------------------------------------------------- MalStone grid
def _run_malstone(scale: Scale, ctx: BenchContext, *, backend: str,
                  statistic: str, engine: str,
                  nodes: Optional[int] = None,
                  records_per_node: Optional[int] = None,
                  capacity_factor: float = 2.0,
                  packed: Optional[bool] = None,
                  impl: Optional[str] = None,
                  collect_shuffle_stats: bool = False) -> ScenarioResult:
    """One timed grid point, routed through the unified ``repro.core.run``
    front door. With ``collect_shuffle_stats`` the jitted fn returns
    (rho, ShuffleStats) so ``time_callable``'s output carries the
    shuffle accounting into ``derived`` — used by the lossless sweep.
    ``impl`` names the exchange implementation directly; the legacy
    ``packed`` tri-state maps onto it (True -> sort, False -> columns,
    None -> auto). The per-chunk mapreduce shuffle is lossless at any
    capacity factor (multi-round residual exchange), so the streaming
    grid uses the same default factor as the one-shot grid."""
    from repro.core import ExchangePlan
    from repro.core import run as malstone
    nodes = nodes or ctx.nodes
    rpn = records_per_node or scale.records_per_node
    mesh = ctx.mesh(nodes)
    cfg = ctx.cfg(scale)
    total = nodes * rpn
    if impl is None:
        impl = {True: "sort", False: "columns", None: "auto"}[packed]
    plan = ExchangePlan(impl=impl, capacity_factor=capacity_factor)

    def shape_out(out):
        return (out[0].rho, out[1]) if collect_shuffle_stats else out.rho

    if engine == "oneshot":
        args = (ctx.log(scale, nodes, rpn),)
        fn = jax.jit(lambda l: shape_out(malstone(
            l, cfg.num_sites, mesh=mesh, statistic=statistic,
            backend=backend, plan=plan,
            return_shuffle_stats=collect_shuffle_stats)))
    elif engine == "streaming":
        seed, num_chunks = ctx.seed(scale, nodes)
        args = (seed,)
        fn = jax.jit(lambda s: shape_out(malstone(
            s, cfg.num_sites, mesh=mesh, engine="streaming",
            statistic=statistic, backend=backend,
            chunk_records=scale.chunk_records, cfg=cfg,
            num_chunks=num_chunks, plan=plan,
            return_shuffle_stats=collect_shuffle_stats)))
        total = num_chunks * scale.chunk_records
    else:
        raise ValueError(f"unknown engine {engine!r}")

    timing, out = time_callable(fn, *args, warmup=scale.warmup,
                                iters=scale.iters)
    derived = None
    if collect_shuffle_stats:
        stats = out[1]
        derived = {"capacity_factor": capacity_factor,
                   "shuffle_rounds": int(stats.rounds),
                   "shuffle_capacity": int(stats.capacity),
                   "shuffle_deferred": int(stats.residual),
                   "shuffle_overflow": int(stats.overflow),
                   "shuffle_bytes_exchanged": int(stats.bytes_exchanged)}
    return ScenarioResult(timing=timing, records=total, derived=derived,
                          effective={"nodes": nodes,
                                     "records_per_node": rpn})


for _stat in STATISTICS:
    for _backend in BACKENDS:
        for _engine in ENGINES:
            _name = (f"malstone_{_STAT_SLUG[_stat]}_{_backend}_{_engine}")

            @_register(_name, "malstone",
                       {"backend": _backend, "statistic": _stat,
                        "engine": _engine, "kernel_path": "jnp"})
            def _scenario(scale, ctx, *, _b=_backend, _s=_stat, _e=_engine):
                return _run_malstone(scale, ctx, backend=_b, statistic=_s,
                                     engine=_e)


# ------------------------------------------------- lossless shuffle sweep
# The mapreduce shuffle delivers every record at ANY capacity factor by
# re-exchanging bucket overflow in extra rounds (backends/mapreduce.py's
# multi-round residual loop). This sweep turns the capacity-vs-rounds
# tradeoff into a measured curve: each point times MalStone B at one
# capacity factor and records the executed round count (plus deferred
# and overflow counters — overflow is asserted 0, i.e. lossless) in the
# BENCH json ``derived`` extras.
LOSSLESS_CAPACITY_FACTORS = (0.25, 0.5, 1.0, 2.0)


def _cf_slug(cf: float) -> str:
    return f"cf{cf:g}".replace(".", "p")     # 0.25 -> cf0p25, 2.0 -> cf2


def _run_mapreduce_lossless(scale: Scale, ctx: BenchContext, *, cf: float,
                            engine: str = "oneshot", packed: bool = False,
                            impl: Optional[str] = None) -> ScenarioResult:
    """One shuffle-sweep point. The exchange impl is explicit (never auto)
    so the ``mapreduce_lossless_*`` rows stay the 4-column baseline the
    ``mapreduce_packed_*`` / ``mapreduce_counting_*`` rows are compared
    against."""
    from repro.core import ShuffleExhaustedError
    res = _run_malstone(scale, ctx, backend="mapreduce", statistic="B",
                        engine=engine, capacity_factor=cf, packed=packed,
                        impl=impl, collect_shuffle_stats=True)
    res.derived["shuffle_impl"] = impl or ("sort" if packed else "columns")
    res.derived["shuffle_packed"] = res.derived["shuffle_impl"] != "columns"
    overflow = res.derived["shuffle_overflow"]
    if overflow != 0:
        # the sweep's whole claim is losslessness — never record timings
        # for a shuffle that dropped records (explicit raise, not assert:
        # this must survive python -O)
        raise ShuffleExhaustedError(
            f"mapreduce_lossless cf={cf} ({engine}) finished with "
            f"{overflow} undelivered records — the round bound has "
            f"regressed")
    return res


for _cf in LOSSLESS_CAPACITY_FACTORS:
    @_register(f"mapreduce_lossless_{_cf_slug(_cf)}", "lossless",
               {"backend": "mapreduce", "statistic": "B",
                "engine": "oneshot", "capacity_factor": _cf,
                "packed": False})
    def _scenario_lossless(scale, ctx, *, _c=_cf):
        return _run_mapreduce_lossless(scale, ctx, cf=_c)


@_register("mapreduce_lossless_streaming_cf0p5", "lossless",
           {"backend": "mapreduce", "statistic": "B",
            "engine": "streaming", "capacity_factor": 0.5,
            "packed": False})
def _scenario_lossless_streaming(scale, ctx):
    return _run_mapreduce_lossless(scale, ctx, cf=0.5, engine="streaming")


# Packed sort-once twins of the lossless sweep: same statistic, same
# losslessness assertion, but the mapper projects each record to one
# uint32 word and sorts once before the round loop. The paired
# ``mapreduce_lossless_cf{0p5,1}`` rows (4-column exchange, explicit
# ``packed=False``) are the baseline: the delta IS the tentpole claim —
# ~4x fewer shuffled bytes (``shuffle_bytes_exchanged`` in derived) and
# the per-round argsort hoisted out of the loop.
PACKED_CAPACITY_FACTORS = (0.5, 1.0)

for _cf in PACKED_CAPACITY_FACTORS:
    @_register(f"mapreduce_packed_{_cf_slug(_cf)}", "lossless",
               {"backend": "mapreduce", "statistic": "B",
                "engine": "oneshot", "capacity_factor": _cf,
                "packed": True})
    def _scenario_packed(scale, ctx, *, _c=_cf):
        return _run_mapreduce_lossless(scale, ctx, cf=_c, packed=True)


# Counting-sort twins of the packed rows: same one-word projection and
# byte accounting, but the mapper orders the words with a per-destination
# histogram + exclusive prefix sum + scatter (two O(n) passes,
# ``kernels/count_scatter``) instead of a stable argsort. The paired
# ``mapreduce_packed_cf{0p5,1}`` rows are the baseline: the delta IS this
# tentpole's claim — identical ``shuffle_bytes_exchanged`` and rounds,
# lower mapper-side ordering time.
COUNTING_CAPACITY_FACTORS = (0.5, 1.0)

for _cf in COUNTING_CAPACITY_FACTORS:
    @_register(f"mapreduce_counting_{_cf_slug(_cf)}", "lossless",
               {"backend": "mapreduce", "statistic": "B",
                "engine": "oneshot", "capacity_factor": _cf,
                "packed": True, "exchange_impl": "counting"})
    def _scenario_counting(scale, ctx, *, _c=_cf):
        return _run_mapreduce_lossless(scale, ctx, cf=_c, impl="counting")


# ------------------------------------------------------------- kernel paths
def _kernel_inputs(scale: Scale, kernel: str):
    rng = np.random.default_rng(0)
    n = scale.records_per_node
    s = scale.num_sites
    if kernel == "segment_hist":
        return (jnp.asarray(rng.integers(0, s, n), jnp.int32),
                jnp.asarray(rng.integers(0, 52, n), jnp.int32),
                jnp.asarray(rng.integers(0, 2, n), jnp.int32),
                jnp.ones(n, jnp.int32))
    if kernel == "windowed_ratio":
        hist = np.stack([rng.integers(0, 50, (s, 52))] * 2, -1)
        return (jnp.asarray(hist.astype(np.int32)),)
    if kernel == "powerlaw_sample":
        from repro.malgen import power_law_cdf, power_law_weights
        cdf = power_law_cdf(power_law_weights(s))
        u = jax.random.uniform(jax.random.key(2), (n,))
        return u, cdf
    raise ValueError(f"unknown kernel {kernel!r}")


def _run_kernel(scale: Scale, ctx: BenchContext, *, kernel: str,
                path: str) -> ScenarioResult:
    from repro.kernels.powerlaw_sample.ops import powerlaw_sample
    from repro.kernels.powerlaw_sample.ref import powerlaw_sample_ref
    from repro.kernels.segment_hist.ops import segment_hist
    from repro.kernels.segment_hist.ref import segment_hist_ref
    from repro.kernels.windowed_ratio.ops import windowed_ratio
    from repro.kernels.windowed_ratio.ref import windowed_ratio_ref

    args = _kernel_inputs(scale, kernel)
    if kernel == "segment_hist":
        work = scale.records_per_node
        fn = (jax.jit(lambda *a: segment_hist(*a, num_sites=scale.num_sites))
              if path == "pallas" else
              jax.jit(lambda *a: segment_hist_ref(
                  *a, num_sites=scale.num_sites, num_weeks=52)))
    elif kernel == "windowed_ratio":
        work = scale.num_sites
        fn = (jax.jit(windowed_ratio)
              if path == "pallas" else jax.jit(windowed_ratio_ref))
    else:  # powerlaw_sample
        work = scale.records_per_node
        fn = (jax.jit(powerlaw_sample)
              if path == "pallas" else jax.jit(powerlaw_sample_ref))
    timing, _ = time_callable(fn, *args, warmup=scale.warmup,
                              iters=scale.iters)
    return ScenarioResult(timing=timing, records=work)


for _kernel in KERNELS:
    for _path in KERNEL_PATHS:
        @_register(f"kernel_{_kernel}_{_path}", "kernel",
                   {"kernel": _kernel, "kernel_path": _path})
        def _scenario_k(scale, ctx, *, _k=_kernel, _p=_path):
            return _run_kernel(scale, ctx, kernel=_k, path=_p)


# ------------------------------------------------------------ MalGen phases
@_register("malgen_seed", "malgen", {"phase": "seed"})
def _malgen_seed(scale: Scale, ctx: BenchContext) -> ScenarioResult:
    from repro.malgen import make_seed
    cfg = ctx.cfg(scale)
    timing, seed = time_callable(
        lambda: make_seed(jax.random.key(0), cfg, scale.records_per_node),
        warmup=scale.warmup, iters=scale.iters)
    # phase 1's work unit is entities, not records — keep the derived
    # unit honest instead of reporting an entities/s number as records/s
    eps = scale.num_entities / (timing.us_per_call / 1e6)
    return ScenarioResult(
        timing=timing,
        derived={"entities_per_s": round(eps, 1),
                 "seed_bytes": int(seed.seed_bytes)})


@_register("malgen_generate", "malgen", {"phase": "generate"})
def _malgen_generate(scale: Scale, ctx: BenchContext) -> ScenarioResult:
    from repro.malgen import generate_shard, make_seed
    cfg = ctx.cfg(scale)
    seed = make_seed(jax.random.key(0), cfg, scale.records_per_node)
    shard_records = max(1, scale.records_per_node // 8)
    fn = jax.jit(lambda: generate_shard(seed, cfg, 0, 8, shard_records))
    timing, _ = time_callable(fn, warmup=scale.warmup, iters=scale.iters)
    return ScenarioResult(timing=timing, records=shard_records)


@_register("malgen_encode", "malgen", {"phase": "encode"})
def _malgen_encode(scale: Scale, ctx: BenchContext) -> ScenarioResult:
    from repro.malgen import encode_records
    log = ctx.log(scale)
    n = min(16_384, scale.records_per_node)
    sl = jax.tree.map(lambda x: np.asarray(x[:n]), log)
    timing, blob = time_callable(
        lambda: encode_records(sl.event_seq, sl.shard_hash, sl.timestamp,
                               sl.site_id, sl.entity_id, sl.mark),
        warmup=1, iters=max(1, scale.iters - 1))
    return ScenarioResult(timing=timing, records=n,
                          derived={"blob_bytes": len(blob)})


# ------------------------------------------- device-parallel MalGen (phase 3)
# Paper §5 generates each node's records *on* the node; the repo's host path
# (``generate_sharded_log``) regenerates the global marked stream once per
# shard and concatenates in host memory. These scenarios measure that gap:
# the same total record budget generated by the host loop vs in place on the
# mesh (``generate_shard_device`` under ``shard_map``), plus fused
# generate+run end-to-end vs materialize-then-run.

def _malgen_oneshot_seed(scale: Scale, ctx: BenchContext, nodes: int):
    from repro.malgen import make_seed
    return make_seed(jax.random.key(3), ctx.cfg(scale),
                     nodes * scale.records_per_node)


@_register("malgen_generate_host_sharded", "malgen",
           {"phase": "generate", "malgen_path": "host"})
def _malgen_generate_host_sharded(scale: Scale,
                                  ctx: BenchContext) -> ScenarioResult:
    """The host loop: every shard regenerates the global marked stream,
    full log concatenated in host memory (seeding excluded — both paths
    time phase 3 only)."""
    from repro.malgen import generate_shard
    from repro.malgen.generator import _concat_logs
    cfg = ctx.cfg(scale)
    nodes = ctx.nodes
    seed = _malgen_oneshot_seed(scale, ctx, nodes)

    def gen():
        return _concat_logs(
            [generate_shard(seed, cfg, s, nodes, scale.records_per_node)
             for s in range(nodes)])

    timing, _ = time_callable(gen, warmup=1, iters=scale.iters, max_warmup=1)
    return ScenarioResult(timing=timing,
                          records=nodes * scale.records_per_node,
                          effective={"nodes": nodes})


@_register("malgen_generate_device", "malgen",
           {"phase": "generate", "malgen_path": "device"})
def _malgen_generate_device(scale: Scale,
                            ctx: BenchContext) -> ScenarioResult:
    """Device-parallel phase 3: each device of the data mesh generates its
    own shard in place (one jitted shard_map, nothing on host)."""
    from jax.sharding import PartitionSpec as P
    from repro.common.compat import shard_map
    from repro.common.types import EventLog
    from repro.malgen import generate_shard_device
    cfg = ctx.cfg(scale)
    nodes = ctx.nodes
    rpn = scale.records_per_node
    seed = _malgen_oneshot_seed(scale, ctx, nodes)
    mesh = ctx.mesh(nodes)

    def local():
        sid = jax.lax.axis_index("data")
        return generate_shard_device(seed, cfg, sid, nodes, rpn)

    spec = EventLog(site_id=P("data"), entity_id=P("data"),
                    timestamp=P("data"), mark=P("data"),
                    event_seq=P("data"), shard_hash=P("data"))
    fn = jax.jit(shard_map(local, mesh=mesh, in_specs=(), out_specs=spec,
                           check_vma=False))
    timing, _ = time_callable(fn, warmup=scale.warmup, iters=scale.iters)
    return ScenarioResult(timing=timing, records=nodes * rpn,
                          effective={"nodes": nodes})


def _run_e2e(scale: Scale, ctx: BenchContext, *, generation: str,
             engine: str = "oneshot",
             nodes: Optional[int] = None) -> ScenarioResult:
    """End-to-end MalStone B (sphere): phase-3 generation + statistic per
    call, seeding (phases 1-2) prebuilt outside timing for BOTH paths so
    the comparison isolates where generation happens.

    ``generation='fused'`` runs the device-parallel fused path (the log
    never exists); ``'materialized'`` is the host shard loop + concat +
    malstone_run — the generate-then-load anti-pattern."""
    from repro.core import (
        malstone_run,
        malstone_run_generated,
        malstone_run_generated_streaming,
    )
    from repro.malgen import generate_shard
    from repro.malgen.generator import _concat_logs
    cfg = ctx.cfg(scale)
    nodes = nodes or ctx.nodes
    rpn = scale.records_per_node
    mesh = ctx.mesh(nodes)
    total = nodes * rpn
    # seed is closed over: its num_marked_events must stay static
    seed = _malgen_oneshot_seed(scale, ctx, nodes)

    if generation == "fused":
        if engine == "oneshot":
            fn = jax.jit(lambda: malstone_run_generated(
                seed, cfg, mesh=mesh, records_per_shard=rpn,
                statistic="B", backend="sphere").rho)
        else:
            fn = jax.jit(lambda: malstone_run_generated_streaming(
                seed, cfg, mesh=mesh, records_per_shard=rpn,
                chunk_records=scale.chunk_records,
                statistic="B", backend="sphere").rho)
        timing, _ = time_callable(fn, warmup=scale.warmup,
                                  iters=scale.iters)
    else:
        def run():
            log = _concat_logs(
                [generate_shard(seed, cfg, s, nodes, rpn)
                 for s in range(nodes)])
            return malstone_run(log, cfg.num_sites, mesh=mesh,
                                statistic="B", backend="sphere").rho

        timing, _ = time_callable(run, warmup=1, iters=scale.iters,
                                  max_warmup=1)
    return ScenarioResult(timing=timing, records=total,
                          effective={"nodes": nodes})


@_register("e2e_fused_oneshot", "e2e",
           {"backend": "sphere", "statistic": "B", "engine": "oneshot",
            "generation": "fused"})
def _e2e_fused_oneshot(scale, ctx):
    return _run_e2e(scale, ctx, generation="fused", engine="oneshot")


@_register("e2e_fused_streaming", "e2e",
           {"backend": "sphere", "statistic": "B", "engine": "streaming",
            "generation": "fused"})
def _e2e_fused_streaming(scale, ctx):
    return _run_e2e(scale, ctx, generation="fused", engine="streaming")


@_register("e2e_materialized_oneshot", "e2e",
           {"backend": "sphere", "statistic": "B", "engine": "oneshot",
            "generation": "materialized"})
def _e2e_materialized_oneshot(scale, ctx):
    return _run_e2e(scale, ctx, generation="materialized")


# ----------------------------------------------------------- scaling sweeps
class ScenarioSkip(RuntimeError):
    """Raised by a scenario that cannot run in this environment."""


SWEEP_RECORD_MULTIPLIERS = (1, 2, 4)
SWEEP_MESH_SIZES = (1, 2, 4)

for _mult in SWEEP_RECORD_MULTIPLIERS:
    @_register(f"sweep_records_x{_mult}", "sweep",
               {"sweep": "records_per_node", "multiplier": _mult,
                "backend": "sphere", "statistic": "B", "engine": "oneshot"})
    def _sweep_records(scale, ctx, *, _m=_mult):
        return _run_malstone(
            scale, ctx, backend="sphere", statistic="B", engine="oneshot",
            records_per_node=scale.records_per_node * _m)

for _p in SWEEP_MESH_SIZES:
    @_register(f"sweep_mesh_p{_p}", "sweep",
               {"sweep": "mesh_size", "nodes": _p, "backend": "sphere",
                "statistic": "B", "engine": "oneshot"})
    def _sweep_mesh(scale, ctx, *, _p=_p):
        if _p > jax.device_count():
            raise ScenarioSkip(
                f"needs {_p} devices, host exposes {jax.device_count()}")
        return _run_malstone(scale, ctx, backend="sphere", statistic="B",
                             engine="oneshot", nodes=_p)

for _p in SWEEP_MESH_SIZES:
    @_register(f"sweep_gen_device_p{_p}", "sweep",
               {"sweep": "gen_device_mesh", "nodes": _p,
                "backend": "sphere", "statistic": "B", "engine": "oneshot",
                "generation": "fused"})
    def _sweep_gen_device(scale, ctx, *, _p=_p):
        # fused generate+run at growing mesh size: generation parallelizes
        # with the mesh (the host loop it replaces got *slower* per node)
        if _p > jax.device_count():
            raise ScenarioSkip(
                f"needs {_p} devices, host exposes {jax.device_count()}")
        return _run_e2e(scale, ctx, generation="fused", nodes=_p)


# --------------------------------------------------------- overlap pipeline
# The double-buffered per-chunk driver (repro.core.overlap) vs the SAME
# programs strictly serialized. The pair uses many SMALL chunks (overriding
# the preset chunk size down to OVERLAP_CHUNK_RECORDS): overlap hides
# per-chunk dispatch/launch latency behind in-flight device work, so the
# win scales with chunk COUNT, not chunk size — at the presets' big-chunk
# shapes compute dominates and the two rows converge (expected on 1-core
# CI hosts; accelerators also overlap the generation compute itself).
# Bit-identity between the rows is asserted by tests, not here. On a
# single-core CPU host the only physically available win is the halved
# host-synchronization count (overlap blocks once per chunk, serialized
# blocks twice), so the pair uses chunks small enough for dispatch/sync
# overhead to be a measurable share of each step; accelerator hosts
# additionally overlap the generation compute itself with the exchange.
OVERLAP_CHUNK_RECORDS = 64


def _overlap_runner(scale: Scale, ctx: BenchContext):
    from repro.core.overlap import OverlapStreamingRunner
    from repro.malgen import make_seed_streaming
    cache = ctx.__dict__.setdefault("_overlap", {})
    chunk = min(OVERLAP_CHUNK_RECORDS, scale.chunk_records)
    cpd = max(1, scale.records_per_node // chunk)
    num_chunks = ctx.nodes * cpd
    key = (ctx.nodes, num_chunks, chunk, scale.num_sites,
           scale.num_entities, scale.marked_event_fraction)
    if key not in cache:
        seed = make_seed_streaming(jax.random.key(6), ctx.cfg(scale),
                                   num_chunks, chunk)
        jax.block_until_ready(seed.entity_mark_time)
        runner = OverlapStreamingRunner(
            seed, ctx.cfg(scale), mesh=ctx.mesh(), num_chunks=num_chunks,
            chunk_records=chunk, backend="mapreduce")
        cache[key] = (runner, chunk, num_chunks)
    return cache[key]


def _overlap_timings(scale: Scale, ctx: BenchContext):
    """One interleaved A/B measurement shared by both rows: alternating
    on/off per iteration makes the pair immune to the machine-load drift
    that separate measurement windows pick up (the pair's DELTA is the
    claim, so the two timings must come from the same window)."""
    import time as _time

    from repro.bench.timing import timing_from_samples
    cache = ctx.__dict__.setdefault("_overlap_timings", {})
    key = (ctx.nodes, scale.records_per_node, scale.num_entities,
           scale.marked_event_fraction)
    if key not in cache:
        runner, chunk, num_chunks = _overlap_runner(scale, ctx)
        samples = {True: [], False: []}
        stats = {}
        for ov in (True, False):            # compile + warm both paths
            runner.run_result("B", overlap=ov)
        for i in range(max(scale.iters * 5, 15)):
            # alternate which path goes first so per-round ordering bias
            # cancels over the sample set
            for ov in (True, False) if i % 2 == 0 else (False, True):
                t0 = _time.perf_counter()
                _, stats[ov] = runner.run_result("B", overlap=ov)
                samples[ov].append((_time.perf_counter() - t0) * 1e6)
        cache[key] = {
            ov: (timing_from_samples(samples[ov], warmup_iters=1,
                                     steady=True),
                 stats[ov], chunk, num_chunks)
            for ov in (True, False)}
    return cache[key]


for _ov in (True, False):
    @_register(f"streaming_overlap_{'on' if _ov else 'off'}", "overlap",
               {"backend": "mapreduce", "statistic": "B",
                "engine": "streaming", "overlap": "on" if _ov else "off"})
    def _scenario_overlap(scale, ctx, *, _o=_ov):
        # both rows share ONE cached runner and ONE interleaved measurement:
        # identical compiled programs, the host-side scheduling policy is
        # the only variable
        timing, stats, chunk, num_chunks = _overlap_timings(scale, ctx)[_o]
        return ScenarioResult(
            timing=timing, records=num_chunks * chunk,
            derived={"overlap": _o, "num_chunks": num_chunks,
                     "shuffle_rounds": int(stats.rounds),
                     "shuffle_overflow": int(stats.overflow),
                     "shuffle_bytes_exchanged": int(stats.bytes_exchanged)},
            effective={"nodes": ctx.nodes, "chunk_records": chunk})


# ------------------------------------------------------- multi-process sweep
# Real jax.distributed scaling: each point shells out to the launcher as a
# P-process localhost gang (1 device per process, streaming mapreduce) and
# adopts the wall-clock samples + shuffle accounting from the BENCH json
# the gang's rank 0 writes. P=1 is the same launcher single-process — the
# curve's baseline. On one-core CI hosts the curve measures coordination
# overhead, not speedup (the paper's Tables 4/5 machines had a core per
# node); the scenarios exist so the scaling data is collected wherever the
# bench runs.
SWEEP_MULTIPROC_SIZES = (1, 2, 4)


def _run_multiproc(scale: Scale, ctx: BenchContext, *,
                   procs: int) -> ScenarioResult:
    import os
    import pathlib
    import subprocess
    import sys
    import tempfile

    from repro.bench import schema
    from repro.bench.timing import timing_from_samples
    from repro.common.env import refuse_gang_off_cpu

    refuse_gang_off_cpu(f"sweep_multiproc_p{procs}")
    src_root = str(pathlib.Path(__file__).resolve().parents[2])
    chunks = max(1, scale.records_per_node // scale.chunk_records)
    sub_env = dict(os.environ)
    sub_env["PYTHONPATH"] = (src_root + os.pathsep
                             + sub_env.get("PYTHONPATH", ""))
    # the gang decides its own device count (1 per process) — the bench
    # parent's forced-device XLA_FLAGS must not leak into the workers
    sub_env.pop("XLA_FLAGS", None)
    with tempfile.TemporaryDirectory(prefix="bench_multiproc_") as tmp:
        out = os.path.join(tmp, f"BENCH_multiproc_p{procs}.json")
        cmd = [sys.executable, "-m", "repro.launch.malstone",
               "--nodes", str(procs), "--num-processes", str(procs),
               "--records-per-node", str(scale.records_per_node),
               "--sites", str(scale.num_sites),
               "--entities", str(scale.num_entities),
               "--stream-chunks", str(chunks),
               "--backend", "mapreduce", "--statistic", "B",
               "--runs", str(scale.iters), "--bench-json", out]
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              env=sub_env, timeout=1800)
        if proc.returncode != 0:
            raise RuntimeError(
                f"sweep_multiproc_p{procs} gang failed "
                f"({proc.returncode}):\n{proc.stdout[-2000:]}\n"
                f"{proc.stderr[-2000:]}")
        res = schema.load_document(out)["results"][0]
    timing = timing_from_samples(res["samples_us"], warmup_iters=1)
    derived = dict(res.get("derived") or {})
    derived["num_processes"] = procs
    return ScenarioResult(
        timing=timing, records=procs * scale.records_per_node,
        derived=derived,
        effective={"nodes": procs, "num_processes": procs})


for _p in SWEEP_MULTIPROC_SIZES:
    @_register(f"sweep_multiproc_p{_p}", "sweep",
               {"sweep": "multiproc", "nodes": _p, "num_processes": _p,
                "backend": "mapreduce", "statistic": "B",
                "engine": "streaming"})
    def _sweep_multiproc(scale, ctx, *, _p=_p):
        return _run_multiproc(scale, ctx, procs=_p)


# ------------------------------------------------------------------ resume
# Checkpoint-tax and chaos-recovery scenarios over repro.core.resume. One
# runner per scenario (built once — the jitted segment fns cache on the
# instance, so warmup pays compilation and the samples measure the loop).
def _resume_runner(scale: Scale, ctx: BenchContext, *,
                   backend: str = "streams", segment_chunks: int = 1):
    from repro.core.resume import ResumableRunner
    seed, num_chunks = ctx.seed(scale)
    runner = ResumableRunner(
        seed, ctx.cfg(scale), mesh=ctx.mesh(), num_chunks=num_chunks,
        chunk_records=scale.chunk_records, segment_chunks=segment_chunks,
        backend=backend, statistic="B")
    return runner, num_chunks * scale.chunk_records


def _resume_scenario_result(scale: Scale, timing, out,
                            records: int) -> ScenarioResult:
    return ScenarioResult(timing=timing, records=records,
                          derived=out.report.to_derived())


@_register("resume_overhead_nockpt", "resume",
           {"backend": "streams", "engine": "resumable",
            "checkpoint": "off", "segment_chunks": 1})
def _resume_overhead_nockpt(scale: Scale, ctx: BenchContext):
    # segmented host loop, no checkpoint IO: the pure segmentation tax
    # over malstone_b_streams_streaming (one uninterrupted scan)
    runner, records = _resume_runner(scale, ctx)

    def fn():
        out = runner.run()
        fn.last = out
        return out.result.rho

    timing, _ = time_callable(fn, warmup=scale.warmup, iters=scale.iters)
    return _resume_scenario_result(scale, timing, fn.last, records)


@_register("resume_overhead_ckpt", "resume",
           {"backend": "streams", "engine": "resumable",
            "checkpoint": "fresh", "segment_chunks": 1})
def _resume_overhead_ckpt(scale: Scale, ctx: BenchContext):
    # + checkpoint write per segment (fresh dir per call so every sample
    # actually computes and saves instead of resuming the previous one)
    import itertools
    import pathlib
    import shutil
    import tempfile

    runner, records = _resume_runner(scale, ctx)
    root = tempfile.mkdtemp(prefix="bench_resume_ckpt_")
    counter = itertools.count()

    def fn():
        d = pathlib.Path(root) / f"call{next(counter)}"
        out = runner.run(checkpoint_dir=str(d), resume=False)
        fn.last = out
        return out.result.rho

    try:
        timing, _ = time_callable(fn, warmup=scale.warmup, iters=scale.iters)
    finally:
        shutil.rmtree(root, ignore_errors=True)
    return _resume_scenario_result(scale, timing, fn.last, records)


@_register("resume_overhead_resume", "resume",
           {"backend": "streams", "engine": "resumable",
            "checkpoint": "restore", "segment_chunks": 1})
def _resume_overhead_resume(scale: Scale, ctx: BenchContext):
    # recovery cost floor: restore a COMPLETE checkpoint and finalize —
    # zero chunks regenerated (the recovery-time-vs-segment-size curve's
    # y-intercept; see EXPERIMENTS.md)
    import shutil
    import tempfile

    runner, records = _resume_runner(scale, ctx)
    root = tempfile.mkdtemp(prefix="bench_resume_restore_")

    def fn():
        out = runner.run(checkpoint_dir=root, resume=True)
        fn.last = out
        return out.result.rho

    try:
        runner.run(checkpoint_dir=root, resume=False)  # populate
        timing, _ = time_callable(fn, warmup=scale.warmup, iters=scale.iters)
    finally:
        shutil.rmtree(root, ignore_errors=True)
    return _resume_scenario_result(scale, timing, fn.last, records)


def _run_faulty(scale: Scale, ctx: BenchContext, *, plan,
                num_hosts: int = 4) -> ScenarioResult:
    from repro.faults import RetryPolicy
    runner, records = _resume_runner(scale, ctx)
    retry = RetryPolicy(max_attempts=6, backoff_s=0.0)

    def fn():
        # fault schedules are pure functions of (plan.seed, segment,
        # shard, host, attempt): every timed call replays the same chaos
        out = runner.run(faults=plan, retry=retry, num_hosts=num_hosts)
        fn.last = out
        return out.result.rho

    timing, _ = time_callable(fn, warmup=scale.warmup, iters=scale.iters)
    return _resume_scenario_result(scale, timing, fn.last, records)


@_register("faulty_run_transient", "resume",
           {"backend": "streams", "engine": "resumable", "faults":
            "transient_rate=0.25,seed=11", "num_hosts": 4})
def _faulty_run_transient(scale: Scale, ctx: BenchContext):
    from repro.faults import FaultPlan
    return _run_faulty(scale, ctx,
                       plan=FaultPlan(seed=11, transient_rate=0.25,
                                      kill_mode="raise"))


@_register("faulty_run_badhost", "resume",
           {"backend": "streams", "engine": "resumable",
            "faults": "bad_hosts=0", "num_hosts": 4})
def _faulty_run_badhost(scale: Scale, ctx: BenchContext):
    from repro.faults import FaultPlan
    return _run_faulty(scale, ctx,
                       plan=FaultPlan(bad_hosts=(0,), kill_mode="raise"))


# ----------------------------------------------------------------- serving
# The resident query engine (repro.serve.MalStoneService): ingest-step
# latency, batched-query latency (with p50/p95/p99 in ``derived``), and
# sustained async query throughput. One fully-ingested service per
# (scale, backend) is cached on the context so the three scenarios share
# compilation and device state.


def _serving_service(scale: Scale, ctx: BenchContext, *,
                     backend: str = "streams", ingested: bool = True):
    from repro.serve import MalStoneService
    cache = ctx.__dict__.setdefault("_serving", {})
    key = (backend, ingested, scale.num_sites, scale.num_entities,
           scale.chunk_records, scale.records_per_node)
    if key not in cache:
        seed, num_chunks = ctx.seed(scale)
        svc = MalStoneService(
            mesh=ctx.mesh(), num_sites=scale.num_sites,
            chunk_records=scale.chunk_records, backend=backend,
            seed=seed, cfg=ctx.cfg(scale), num_chunks=num_chunks)
        if ingested:
            svc.ingest_chunks(svc.cpd)
        cache[key] = svc
    return cache[key]


def _serving_mix(scale: Scale):
    from repro.serve import default_query_mix
    return default_query_mix(num_sites=scale.num_sites,
                             top_k=min(8, scale.num_sites))


@_register("serving_ingest_latency", "serving",
           {"backend": "streams", "engine": "serving", "phase": "ingest"})
def _serving_ingest(scale: Scale, ctx: BenchContext) -> ScenarioResult:
    """Per-ingest latency of the resident engine: each call folds one
    chunk per device into the live HistogramState and blocks on the
    device cursor. The service resets once its stream is exhausted, so
    the iteration count is not bounded by chunks-per-device."""
    from repro.bench import schema
    svc = _serving_service(scale, ctx, ingested=False)
    svc.reset()

    def fn():
        if svc.chunks_folded >= svc.cpd:
            svc.reset()
        svc.ingest_chunks(1)
        return svc.chunks_folded      # numpy conversion blocks on device

    timing, _ = time_callable(fn, warmup=scale.warmup, iters=scale.iters)
    return ScenarioResult(
        timing=timing, records=ctx.nodes * scale.chunk_records,
        derived={"latency_percentiles":
                 schema.latency_percentiles(timing.samples_us)})


@_register("serving_query_batch", "serving",
           {"backend": "streams", "engine": "serving", "phase": "query",
            "query_mix": "default", "kernel_path": "pallas"})
def _serving_query_batch(scale: Scale, ctx: BenchContext) -> ScenarioResult:
    """Latency of one mixed query batch (A/B/B-fixed windows, top-k,
    drill-down) answered in a single jitted dispatch over the resident
    snapshot; p50/p95/p99 land in ``derived`` for the CI gate."""
    from repro.bench import schema
    svc = _serving_service(scale, ctx)
    specs = _serving_mix(scale)
    timing, _ = time_callable(lambda: svc.query(specs),
                              warmup=scale.warmup, iters=scale.iters)
    return ScenarioResult(
        timing=timing, records=len(specs),
        derived={"latency_percentiles":
                 schema.latency_percentiles(timing.samples_us),
                 "batch_queries": len(specs)})


@_register("serving_sustained_qps", "serving",
           {"backend": "streams", "engine": "serving", "phase": "sustained",
            "query_mix": "default", "kernel_path": "pallas"})
def _serving_sustained(scale: Scale, ctx: BenchContext) -> ScenarioResult:
    """Sustained throughput: submit a pipeline of async batches (tickets
    returned while device work is in flight), then drain — queries/s over
    the whole pipeline, the serving twin of records/s."""
    svc = _serving_service(scale, ctx)
    specs = _serving_mix(scale)
    batches = max(4, scale.iters)

    def fn():
        tickets = [svc.submit(specs) for _ in range(batches)]
        for t in tickets:
            svc.wait(t)
        return batches * len(specs)

    timing, queries = time_callable(fn, warmup=scale.warmup,
                                    iters=scale.iters)
    qps = queries / (timing.us_per_call / 1e6)
    return ScenarioResult(
        timing=timing, records=queries,
        derived={"queries_per_s": round(qps, 1), "batches": batches,
                 "batch_queries": len(specs)})


# ------------------------------------------------------------------ selection
# Preset -> which scenarios run by default. ``smoke`` must cover all four
# backends and both engines (acceptance criterion) but trims the statistic
# axis to keep shared-runner wall clock bounded; ``full`` runs everything.
def preset_scenario_names(preset: str) -> list:
    if preset not in PRESETS:
        raise ValueError(f"unknown preset {preset!r}; have {list(PRESETS)}")
    names = []
    for name, sc in SCENARIOS.items():
        if preset == "smoke":
            if sc.group == "malstone" and sc.params["statistic"] != "B":
                # keep one non-B point per statistic so the finalize paths
                # stay covered without tripling the grid
                if not (sc.params["backend"] == "streams"
                        and sc.params["engine"] == "oneshot"):
                    continue
            if sc.group == "sweep" and sc.params.get("multiplier") == 4:
                continue
            if (sc.params.get("sweep") == "multiproc"
                    and sc.params["num_processes"] > 2):
                # p4 forks four jax processes — full-preset only
                continue
            if (sc.group == "lossless"
                    and name not in ("mapreduce_lossless_cf0p25",
                                     "mapreduce_packed_cf0p5",
                                     "mapreduce_counting_cf0p5")):
                # one multi-round unpacked point + one packed-sort point +
                # one counting point keep the perf gate on all three
                # shuffle code paths without running the full sweep
                continue
        names.append(name)
    return names


def iter_scenarios(names: Optional[Iterable[str]] = None):
    for name in (names if names is not None else SCENARIOS):
        if name not in SCENARIOS:
            raise KeyError(
                f"unknown scenario {name!r}; run with --list to enumerate")
        yield SCENARIOS[name]
