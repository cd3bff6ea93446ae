"""Jaxpr-level passes: trace the real drivers, execute nothing.

``jax.make_jaxpr`` stages every driver end-to-end — inner ``jax.jit``
calls become ``pjit`` equations under the outer dynamic trace, ``shard_map``
and the exchange ``lax.while_loop`` appear as sub-jaxprs — so the checks
here see exactly the program the benchmark runs, at tiny static shapes,
without ever dispatching a kernel.

Rules:

- **JX001** — a driver fails to trace at all (concretization of a traced
  value: the classic ``if traced:`` / ``int(traced)`` hazard).
- **JX002** — a ``while``/``scan`` carry component carries a weak-typed
  dtype. This is the PR 5 bug class: a Python-int default in
  ``ShuffleStats`` made one carry leg weak, and a later ``+`` silently
  promoted the whole counter chain.
- **JX003** — a driver donated its log but the compiled executable has no
  ``input_output_alias``: the donation bought nothing and the caller gave
  up its buffers for free.
- **JX004** — a host callback / transfer op staged inside a jitted hot
  path (sync point per dispatch).
"""

from __future__ import annotations

import warnings

import jax
import numpy as np
from jax._src import core as _jcore

from repro.analysis.findings import Finding
from repro.analysis.registry import AnalysisContext, register_pass

# Trace geometry: tiny but structurally faithful (multi-round shuffle,
# multi-chunk scan, padded sites). Everything divides any device count.
TINY_SITES = 8
TINY_ENTITIES = 16
TINY_RECORDS_PER_SHARD = 16
TINY_CHUNK_RECORDS = 8
TINY_NUM_WEEKS = 8

_LOG_ENGINES = ("oneshot", "streaming")
_SEED_ENGINES = ("generated", "generated_streaming")

# Primitives that force a device<->host round trip when staged inside a
# jitted computation. ``debug_print`` (what ``jax.debug.print`` stages) and
# ``debug_callback`` are deliberately included: a forgotten
# jax.debug.print in a driver is exactly the hazard JX004 exists to catch.
_HOST_OPS = ("infeed", "outfeed", "outside_call", "debug_print")


def _is_host_op(prim_name: str) -> bool:
    return "callback" in prim_name or prim_name in _HOST_OPS


def _sub_jaxprs(eqn):
    """Yield every sub-jaxpr hanging off an equation's params.

    Explicit isinstance checks, NOT ``tree_leaves``: ``ClosedJaxpr`` is a
    registered pytree and flattens through, which silently yields nothing.
    """
    for val in eqn.params.values():
        if isinstance(val, _jcore.ClosedJaxpr):
            yield val.jaxpr
        elif isinstance(val, _jcore.Jaxpr):
            yield val
        elif isinstance(val, (tuple, list)):
            for item in val:
                if isinstance(item, _jcore.ClosedJaxpr):
                    yield item.jaxpr
                elif isinstance(item, _jcore.Jaxpr):
                    yield item


def _walk_eqns(jaxpr, visit):
    for eqn in jaxpr.eqns:
        visit(eqn)
        for sub in _sub_jaxprs(eqn):
            _walk_eqns(sub, visit)


def _loop_carries(eqn):
    """(kind, [carry outvars]) for while/scan equations, else None."""
    name = eqn.primitive.name
    if name == "while":
        return "while", list(eqn.params["body_jaxpr"].jaxpr.outvars)
    if name == "scan":
        num_carry = eqn.params["num_carry"]
        return "scan", list(eqn.params["jaxpr"].jaxpr.outvars[:num_carry])
    return None


def analyze_jaxpr(closed, target: str) -> list:
    """Run the JX002/JX004 structural checks over one closed jaxpr.

    Exposed separately from the registered pass so tests can feed a
    seeded-bug jaxpr straight in (no driver required).
    """
    findings = []
    counters = {"while": 0, "scan": 0, "host": 0}

    def visit(eqn):
        loop = _loop_carries(eqn)
        if loop is not None:
            kind, outvars = loop
            idx = counters[kind]
            counters[kind] += 1
            for i, var in enumerate(outvars):
                aval = var.aval
                if getattr(aval, "weak_type", False):
                    findings.append(Finding(
                        rule="JX002", severity="error", target=target,
                        location=f"{kind}#{idx}/carry[{i}]",
                        message=(f"loop carry component {i} is weak-typed "
                                 f"{aval.dtype}: a mixed-dtype op on it "
                                 f"will silently promote the whole carry"),
                        fix_hint=("give the carry's initial value an "
                                  "explicit dtype (np.int32(0) / "
                                  "jnp.asarray(x, dtype)), never a bare "
                                  "Python scalar")))
            return
        name = eqn.primitive.name
        if _is_host_op(name):
            idx = counters["host"]
            counters["host"] += 1
            findings.append(Finding(
                rule="JX004", severity="warning", target=target,
                location=f"{name}#{idx}",
                message=(f"host op '{name}' staged inside the jitted "
                         f"program: every dispatch synchronizes with "
                         f"the host"),
                fix_hint=("move the callback out of the hot path, or "
                          "accumulate on device and read back once")))

    _walk_eqns(closed.jaxpr, visit)
    return findings


def _tiny_world(ctx: AnalysisContext):
    """Mesh + tiny seed/log avals, cached on the context."""
    if "tiny_world" in ctx.cache:
        return ctx.cache["tiny_world"]
    from jax.sharding import Mesh
    from repro.common.types import EventLog
    from repro.malgen.seeding import MalGenConfig, make_seed

    devices = np.array(jax.devices())
    mesh = Mesh(devices, ("data",))
    parts = devices.size
    cfg = MalGenConfig(num_sites=TINY_SITES, num_entities=TINY_ENTITIES)
    seed = make_seed(jax.random.PRNGKey(0), cfg,
                     parts * TINY_RECORDS_PER_SHARD)
    n = parts * TINY_RECORDS_PER_SHARD
    sds = lambda: jax.ShapeDtypeStruct((n,), np.int32)  # noqa: E731
    log_sds = EventLog(site_id=sds(), entity_id=sds(), timestamp=sds(),
                       mark=sds())
    world = {"mesh": mesh, "parts": parts, "cfg": cfg, "seed": seed,
             "log_sds": log_sds}
    ctx.cache["tiny_world"] = world
    ctx.note(f"jaxpr: tracing on {parts} device(s)")
    return world


def driver_trace_targets(ctx: AnalysisContext):
    """name -> zero-arg thunk returning the driver's ClosedJaxpr.

    Covers every engine x backend combination that is a pure staged
    computation. ``resumable`` is deliberately absent: it is a host-side
    segment orchestrator (checkpoint I/O between device calls), not one
    traceable program — its device work is the streaming engine, which is
    covered.
    """
    from repro.core.api import run
    from repro.core.backends import BACKENDS

    world = _tiny_world(ctx)
    mesh, cfg, seed = world["mesh"], world["cfg"], world["seed"]
    log_sds, parts = world["log_sds"], world["parts"]

    targets = {}
    for engine in _LOG_ENGINES:
        for backend in BACKENDS:
            kwargs = {}
            if engine == "streaming":
                kwargs["chunk_records"] = TINY_CHUNK_RECORDS

            def thunk(engine=engine, backend=backend, kwargs=kwargs):
                def fn(log):
                    return run(log, TINY_SITES, mesh=mesh, engine=engine,
                               backend=backend, num_weeks=TINY_NUM_WEEKS,
                               **kwargs)
                return jax.make_jaxpr(fn)(log_sds)

            targets[f"jaxpr:{engine}/{backend}"] = thunk
    for engine in _SEED_ENGINES:
        for backend in BACKENDS:
            kwargs = {"records_per_shard": TINY_RECORDS_PER_SHARD}
            if engine == "generated_streaming":
                kwargs["chunk_records"] = TINY_CHUNK_RECORDS

            def thunk(engine=engine, backend=backend, kwargs=kwargs):
                def fn():
                    return run(seed, None, mesh=mesh, engine=engine,
                               backend=backend, cfg=cfg,
                               num_weeks=TINY_NUM_WEEKS, **kwargs)
                return jax.make_jaxpr(fn)()

            targets[f"jaxpr:{engine}/{backend}"] = thunk
    targets.update(_serve_trace_targets(ctx))
    # record parts so HLO passes can reuse the same geometry
    targets = dict(sorted(targets.items()))
    ctx.cache.setdefault("trace_parts", parts)
    return targets


def _serve_trace_targets(ctx: AnalysisContext):
    """The resident query engine's staged programs: the seed-mode ingest
    fold (state -> state, per backend), the snapshot collective, and the
    batched query dispatch — via the service's public ``*_program`` hooks,
    so the passes audit exactly what ``MalStoneService`` runs.
    """
    from repro.core.backends import BACKENDS
    from repro.malgen.seeding import make_seed_streaming
    from repro.serve import MalStoneService, batched_query

    world = _tiny_world(ctx)
    mesh, cfg, parts = world["mesh"], world["cfg"], world["parts"]
    num_chunks = parts * 2
    stream_seed = make_seed_streaming(jax.random.PRNGKey(1), cfg,
                                      num_chunks, TINY_CHUNK_RECORDS)

    def service(backend):
        return MalStoneService(
            mesh=mesh, num_sites=TINY_SITES,
            chunk_records=TINY_CHUNK_RECORDS, backend=backend,
            num_weeks=TINY_NUM_WEEKS, seed=stream_seed, cfg=cfg,
            num_chunks=num_chunks)

    targets = {}
    for backend in BACKENDS:
        def ingest_thunk(backend=backend):
            svc = service(backend)
            return jax.make_jaxpr(svc.ingest_program(1))(
                svc.zero_state_host())

        targets[f"jaxpr:serve_ingest/{backend}"] = ingest_thunk

    def snapshot_thunk():
        svc = service("mapreduce")   # the stats-carrying snapshot variant
        return jax.make_jaxpr(svc.snapshot_program())(svc.zero_state_host())

    def query_thunk():
        n = 3
        hist = jax.ShapeDtypeStruct((TINY_SITES, TINY_NUM_WEEKS, 2),
                                    np.int32)
        mask = jax.ShapeDtypeStruct((n, TINY_NUM_WEEKS), np.bool_)
        sites = jax.ShapeDtypeStruct((n,), np.int32)

        def fn(h, nm, dm, s):
            return batched_query(h, nm, dm, s, max_top_k=2,
                                 kernel_path="ref", interpret=True)

        return jax.make_jaxpr(fn)(hist, mask, mask, sites)

    targets["jaxpr:serve_snapshot/mapreduce"] = snapshot_thunk
    targets["jaxpr:serve_query/ref"] = query_thunk
    return targets


def _traced(ctx: AnalysisContext):
    """Trace every target once; cache {name: ClosedJaxpr | Exception}."""
    if "traced_drivers" in ctx.cache:
        return ctx.cache["traced_drivers"]
    out = {}
    for name, thunk in driver_trace_targets(ctx).items():
        try:
            out[name] = thunk()
        except jax.errors.JAXTypeError as exc:
            out[name] = exc
    ctx.cache["traced_drivers"] = out
    return out


@register_pass("jaxpr-trace", "jaxpr", ("JX001",),
               doc="every engine x backend driver traces without "
                   "concretizing a traced value")
def trace_pass(ctx: AnalysisContext) -> list:
    findings = []
    for name, traced in _traced(ctx).items():
        if isinstance(traced, Exception):
            first_line = str(traced).strip().splitlines()[0]
            findings.append(Finding(
                rule="JX001", severity="error", target=name,
                location="trace",
                message=f"driver failed to trace: {first_line}",
                fix_hint=("replace data-dependent Python control flow "
                          "with lax.cond/lax.select, or hoist the value "
                          "to a static argument")))
    return findings


@register_pass("jaxpr-structure", "jaxpr", ("JX002", "JX004"),
               doc="weak-typed loop carries and host callbacks in the "
                   "staged driver programs")
def structure_pass(ctx: AnalysisContext) -> list:
    findings = []
    for name, traced in _traced(ctx).items():
        if isinstance(traced, Exception):
            continue  # reported by jaxpr-trace
        findings.extend(analyze_jaxpr(traced, name))
    return findings


@register_pass("jaxpr-donation", "jaxpr", ("JX003",),
               doc="donated log buffers are actually aliased by the "
                   "compiled executable")
def donation_pass(ctx: AnalysisContext) -> list:
    from repro.core.runner import malstone_lowerable

    world = _tiny_world(ctx)
    mesh, parts = world["mesh"], world["parts"]
    findings = []
    for backend in ("sphere", "mapreduce"):
        fn, log_sds = malstone_lowerable(
            parts * TINY_RECORDS_PER_SHARD, TINY_SITES, mesh=mesh,
            backend=backend, num_weeks=TINY_NUM_WEEKS, axis_name="data")
        with warnings.catch_warnings():
            # CPU XLA warns that donation is unimplemented; that exact
            # condition is what this pass turns into a finding.
            warnings.simplefilter("ignore")
            compiled = jax.jit(fn, donate_argnums=0).lower(log_sds).compile()
        if "input_output_alias" not in compiled.as_text():
            findings.append(Finding(
                rule="JX003", severity="warning",
                target=f"jaxpr:donate/{backend}",
                location="arg0(log)",
                message=("donate_argnums=0 produced no input_output_alias "
                         "in the compiled executable: the platform ignored "
                         "the donation and the caller's buffers were "
                         "surrendered for nothing"),
                fix_hint=("expected on CPU (XLA:CPU has no donation); on "
                          "TPU this is a real regression — check that the "
                          "donated aval matches an output aval exactly")))
    return findings
