"""MalStone query-service launcher — the resident engine as a CLI.

    PYTHONPATH=src python -m repro.launch.serve_malstone \
        --nodes 2 --sites 512 --entities 4096 \
        --ingest-chunks 4 --chunk-records 2048 --query-mix default

Three phases against one :class:`repro.serve.MalStoneService`:

1. **seed** — a MalGen streaming seed for ``--nodes * --ingest-chunks``
   chunks of ``--chunk-records`` records each (the log is never
   materialized; each ingest regenerates its chunks on the mesh).
2. **ingest** — fold the stream one chunk-per-device at a time into the
   resident ``HistogramState``, reporting per-ingest latency (each sample
   blocks on the device cursor, so it includes the fold's device time).
3. **query** — answer a batch of ``--query-mix`` specs per call over the
   resident snapshot: first a latency loop (``--query-batches`` timed
   calls -> p50/p95/p99), then a sustained-throughput loop (all batches
   submitted asynchronously, optionally paced to ``--qps``, then drained).

``--check`` recomputes the identical stream through the one-shot
streaming engine (``repro.core.run``) and asserts bit-identical rho for
all three statistics — plus field-by-field ``ShuffleStats`` equality on
the mapreduce backends. This is the CI serve-smoke gate.

``--bench-json PATH`` emits the three phases as one BENCH_*.json document
(``latency_percentiles`` in each row's derived block) for
``repro.bench.compare``.
"""

from __future__ import annotations

import argparse
import sys

from repro.bench import force_host_devices, preparse_nodes

if __name__ == "__main__":
    force_host_devices(preparse_nodes(1))

import pathlib  # noqa: E402
import time  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.bench import schema  # noqa: E402
from repro.bench.timing import time_callable, timing_from_samples  # noqa: E402
from repro.common.env import enable_compile_cache  # noqa: E402
from repro.common.types import ExchangePlan  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.malgen import MalGenConfig, make_seed_streaming  # noqa: E402
from repro.serve import (  # noqa: E402
    MalStoneService,
    default_query_mix,
    growing_window_specs,
)

BACKENDS = ("streams", "sphere", "mapreduce", "mapreduce_combiner")


def build_query_mix(name: str, *, num_sites: int, top_k: int) -> list:
    """The named query workloads: ``default`` is the 5-spec mixed batch
    (A/B/B-fixed, partial windows, top-k, drill-down), ``growing`` is the
    paper's 52 nested B windows, ``mixed`` is both (default + every 13th
    growing window)."""
    if name == "default":
        return list(default_query_mix(num_sites=num_sites, top_k=top_k))
    if name == "growing":
        return list(growing_window_specs("B"))
    if name == "mixed":
        return (list(default_query_mix(num_sites=num_sites, top_k=top_k))
                + list(growing_window_specs("B"))[12::13])
    raise ValueError(f"unknown query mix {name!r}")


def _check_bit_identity(service, seed, cfg, mesh, args, num_chunks) -> None:
    """Assert the resident snapshot matches the one-shot streaming engine
    bit-for-bit: rho for every statistic, and (mapreduce) every
    ShuffleStats field. SystemExit on any mismatch."""
    from repro.core import run as malstone

    plan = service.plan
    want_stats = args.backend in ("mapreduce", "mapreduce_combiner")
    for stat in ("A", "B", "B-fixed"):
        out = malstone(
            seed, cfg.num_sites, mesh=mesh, engine="streaming",
            statistic=stat, backend=args.backend,
            chunk_records=args.chunk_records, cfg=cfg,
            num_chunks=num_chunks, plan=plan,
            return_shuffle_stats=want_stats)
        if want_stats:
            out, ref_stats = out
        got = np.asarray(service.result(stat).rho)
        want = np.asarray(out.rho)
        if not np.array_equal(got, want):
            raise SystemExit(
                f"check FAILED: MalStone {stat} diverges from the one-shot "
                f"streaming run (max |delta| = "
                f"{np.max(np.abs(got - want))})")
    if want_stats:
        _, svc_stats = service.snapshot()
        for field, ref_val in zip(type(ref_stats)._fields, ref_stats):
            got_val = getattr(svc_stats, field)
            if int(np.asarray(got_val)) != int(np.asarray(ref_val)):
                raise SystemExit(
                    f"check FAILED: ShuffleStats.{field} = "
                    f"{int(np.asarray(got_val))} != one-shot "
                    f"{int(np.asarray(ref_val))}")
    print("check OK: resident snapshot bit-identical to the one-shot "
          "streaming run (A, B, B-fixed"
          + (", ShuffleStats)" if want_stats else ")"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="repro.launch.serve_malstone",
                                 description=__doc__)
    ap.add_argument("--nodes", type=int, default=1)
    ap.add_argument("--sites", type=int, default=512)
    ap.add_argument("--entities", type=int, default=4_096)
    ap.add_argument("--chunk-records", type=int, default=2_048)
    ap.add_argument("--ingest-chunks", type=int, default=4, metavar="N",
                    help="chunks per node to stream into the resident state"
                         " (total records = nodes * N * chunk-records)")
    ap.add_argument("--backend", default="streams", choices=BACKENDS)
    ap.add_argument("--exchange-impl", default="auto",
                    choices=("auto", "sort", "columns", "counting"),
                    help="mapreduce per-chunk shuffle exchange (see"
                         " repro.launch.malstone)")
    ap.add_argument("--capacity-factor", type=float, default=2.0)
    ap.add_argument("--query-mix", default="default",
                    choices=("default", "growing", "mixed"),
                    help="specs per batch: the 5-spec mixed workload, the"
                         " 52 nested growing-B windows, or both")
    ap.add_argument("--top-k", type=int, default=8,
                    help="top-k sites in the default mix's ranking query")
    ap.add_argument("--query-batches", type=int, default=8, metavar="B",
                    help="timed batch calls in the latency loop AND"
                         " batches submitted in the sustained loop")
    ap.add_argument("--qps", type=float, default=0.0,
                    help="target query rate for the sustained loop"
                         " (queries/s; 0 = open throttle)")
    ap.add_argument("--kernel-path", default="auto",
                    choices=("auto", "pallas", "ref"),
                    help="query kernel: the masked Pallas windowed-ratio"
                         " tile kernel (interpret mode off-TPU) or the"
                         " pure-jnp reference")
    ap.add_argument("--check", action="store_true",
                    help="assert bit-identity of the resident snapshot vs"
                         " the one-shot streaming engine (CI serve-smoke)")
    ap.add_argument("--bench-json", default=None, metavar="PATH",
                    help="also write the three phases as a BENCH_*.json"
                         " document (repro/bench/schema.py)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.ingest_chunks < 1:
        ap.error("--ingest-chunks must be >= 1")
    if args.query_batches < 1:
        ap.error("--query-batches must be >= 1")

    mesh = make_mesh((args.nodes,), ("data",))
    cfg = MalGenConfig(num_sites=args.sites, num_entities=args.entities)
    num_chunks = args.nodes * args.ingest_chunks
    total = num_chunks * args.chunk_records
    plan = ExchangePlan(impl=args.exchange_impl,
                        capacity_factor=args.capacity_factor)

    print(f"MalGen (service seed): {total:,} records over {args.nodes} "
          f"nodes x {args.ingest_chunks} chunks of {args.chunk_records:,}"
          f" — log never materialized")
    t0 = time.perf_counter()
    seed = make_seed_streaming(jax.random.key(0), cfg, num_chunks,
                               args.chunk_records)
    jax.block_until_ready(seed.entity_mark_time)
    print(f"  seeded in {time.perf_counter() - t0:.1f}s "
          f"(scatter payload {seed.seed_bytes / 1e6:.1f} MB)")

    service = MalStoneService(
        mesh=mesh, num_sites=args.sites, chunk_records=args.chunk_records,
        backend=args.backend, seed=seed, cfg=cfg, num_chunks=num_chunks,
        plan=plan, kernel_path=args.kernel_path)

    # ---------------------------------------------------------- ingest loop
    # one chunk per device per call: each sample blocks on the device-side
    # cursor, so it measures dispatch + the fold's device time (first call
    # also pays compilation — reported separately, excluded from stats)
    per_ingest = args.nodes * args.chunk_records
    ingest_samples = []
    for step in range(args.ingest_chunks):
        t0 = time.perf_counter()
        service.ingest_chunks(1)
        folded = service.chunks_folded  # blocks
        us = (time.perf_counter() - t0) * 1e6
        ingest_samples.append(us)
        print(f"  ingest {folded}/{args.ingest_chunks}: {us / 1e3:.1f} ms "
              f"({per_ingest / (us / 1e6) / 1e6:.2f}M records/s)"
              + (" [compile]" if step == 0 else ""), flush=True)
    steady = ingest_samples[1:] or ingest_samples
    ingest_timing = timing_from_samples(steady)
    median_ms = ingest_timing.us_per_call / 1e3
    print(f"ingest [{args.backend}] median {median_ms:.1f} ms/chunk-step "
          f"over {len(steady)} steady steps "
          f"(compile step {ingest_samples[0] / 1e3:.1f} ms)")

    if args.check:
        _check_bit_identity(service, seed, cfg, mesh, args, num_chunks)

    # ------------------------------------------------- query latency loop
    specs = build_query_mix(args.query_mix, num_sites=args.sites,
                            top_k=min(args.top_k, args.sites))
    nq = len(specs)

    query_timing, answers = time_callable(
        lambda: service.query(specs), warmup=1,
        iters=args.query_batches, max_warmup=1)
    pcts = schema.latency_percentiles(query_timing.samples_us)
    print(f"query [{args.query_mix}, {nq} specs/batch, "
          f"{service.kernel_path}] p50 {pcts['p50'] / 1e3:.1f} ms  "
          f"p95 {pcts['p95'] / 1e3:.1f} ms  p99 {pcts['p99'] / 1e3:.1f} ms"
          f" per batch over {args.query_batches} calls")
    top = next((a for a in answers if a.top_sites is not None), None)
    if top is not None:
        print(f"  top-{len(top.top_sites)} sites by rho: "
              f"{top.top_sites.tolist()}")

    # --------------------------------------------- sustained throughput
    # submit every batch (async dispatch), optionally paced to --qps,
    # then drain: wall clock over the whole pipeline -> achieved rate
    interval = nq / args.qps if args.qps > 0 else 0.0
    t0 = time.perf_counter()
    tickets = []
    for b in range(args.query_batches):
        if interval:
            lag = t0 + b * interval - time.perf_counter()
            if lag > 0:
                time.sleep(lag)
        tickets.append(service.submit(specs))
    for ticket in tickets:
        service.wait(ticket)
    wall_s = time.perf_counter() - t0
    queries = nq * args.query_batches
    achieved = queries / wall_s
    sustained_timing = timing_from_samples([wall_s * 1e6])
    print(f"sustained: {queries} queries ({args.query_batches} batches) in "
          f"{wall_s * 1e3:.1f} ms -> {achieved:.0f} queries/s"
          + (f" (target {args.qps:.0f})" if args.qps > 0 else ""))

    st = service.stats()
    print(f"service: {st.chunks_folded} chunks/device folded, "
          f"{st.records_ingested:,} records, {st.batches_answered} batches /"
          f" {st.queries_answered} queries answered, {st.pending} pending")

    if args.bench_json:
        base_params = {
            "backend": args.backend, "nodes": args.nodes,
            "sites": args.sites, "entities": args.entities,
            "chunk_records": args.chunk_records,
            "ingest_chunks": args.ingest_chunks,
            "query_mix": args.query_mix,
            "kernel_path": service.kernel_path,
            "exchange_impl": args.exchange_impl,
        }
        doc = schema.new_document(
            pathlib.Path(args.bench_json).stem.removeprefix("BENCH_"),
            env={"source": "repro.launch.serve_malstone"})
        schema.add_result(
            doc, f"launch_serve_ingest_{args.backend}",
            dict(base_params, phase="ingest"), ingest_timing,
            records=per_ingest,
            derived={"latency_percentiles":
                     schema.latency_percentiles(steady),
                     "compile_us": ingest_samples[0]})
        schema.add_result(
            doc, f"launch_serve_query_{args.backend}",
            dict(base_params, phase="query", batch_queries=nq),
            query_timing, records=nq,
            derived={"latency_percentiles": pcts})
        schema.add_result(
            doc, f"launch_serve_sustained_{args.backend}",
            dict(base_params, phase="sustained", batch_queries=nq,
                 qps_target=args.qps),
            sustained_timing, records=queries,
            derived={"queries_per_s": round(achieved, 1),
                     "batches": args.query_batches})
        path = schema.write_document(doc, path=args.bench_json)
        print(f"wrote {path}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
