"""MalStone benchmark launcher — the paper's experiment as a CLI.

    PYTHONPATH=src python -m repro.launch.malstone \
        --nodes 8 --records-per-node 262144 --sites 10000 \
        --backend sphere --statistic B

``--stream-chunks N`` switches to the streaming chunked engine: each node
regenerates its records N chunks at a time from the MalGen seed inside a
``lax.scan`` (the log is never materialized), so ``--records-per-node`` can
exceed device memory. N must divide ``--records-per-node``.

``--checkpoint-dir DIR`` makes the streaming run resumable: the scan runs
in segments of ``--segment-chunks`` chunks, saving the carry after each
(``repro.core.resume``); ``--resume`` continues a preempted run from the
latest committed checkpoint, regenerating only unprocessed chunks —
bit-identical to an uninterrupted run. ``--inject-faults`` executes a
seeded chaos schedule (``repro.faults.FaultPlan.parse`` spec) under the
bounded-retry + NodeDoctor-rerouting recovery loop.

Multi-node on one host uses forced host devices; set ``--nodes`` BEFORE any
other jax usage (this module configures the runtime at import, through
``repro.common.env`` / ``repro.launch.coordinator``, like dryrun).

Real multi-process runs: add ``--num-processes N`` and the launcher forks N
localhost workers joined through ``jax.distributed`` (each owning
``nodes/N`` devices; the mesh — and the mapreduce ``all_to_all`` — spans
all of them). ``--coordinator HOST:PORT --process-id K`` instead joins an
externally-launched gang (one process per host). ``--overlap {on,off}``
drives the streaming engine through the double-buffered per-chunk pipeline
(``repro.core.overlap``); ``--check`` asserts the run's rho bit-equals the
single-device host oracle.
"""

import argparse

from repro.common.env import enable_compile_cache, preparse_nodes
from repro.launch import coordinator

_N = preparse_nodes(default=1)
# spawn-parent invocations exit inside bootstrap (before jax ever loads);
# workers join the coordinator here, then import jax normally below
_DIST = coordinator.bootstrap(local_devices_for=_N)

import pathlib
import time

import jax
import numpy as np

from repro.bench import schema
from repro.bench.timing import time_callable
from repro.common.types import ExchangePlan
from repro.core import run as malstone
from repro.launch.mesh import (
    make_global_mesh,
    make_mesh,
    replicate_to_mesh,
    shard_log_to_mesh,
)
from repro.malgen import MalGenConfig, generate_sharded_log, make_seed_streaming


def main():
    ap = argparse.ArgumentParser()
    coordinator.add_arguments(ap)
    ap.add_argument("--nodes", type=int, default=1)
    ap.add_argument("--records-per-node", type=int, default=262_144)
    ap.add_argument("--sites", type=int, default=10_000)
    ap.add_argument("--entities", type=int, default=100_000)
    ap.add_argument("--backend", default="sphere",
                    choices=("streams", "sphere", "mapreduce",
                             "mapreduce_combiner"))
    ap.add_argument("--statistic", default="B",
                    choices=("A", "B", "B-fixed"))
    ap.add_argument("--runs", type=int, default=3)
    ap.add_argument("--capacity-factor", type=float, default=2.0,
                    help="mapreduce shuffle bucket capacity as a multiple of"
                         " records/nodes; ANY value is lossless (smaller ="
                         " less memory, more shuffle rounds)")
    ap.add_argument("--max-shuffle-rounds", type=int, default=None,
                    metavar="R",
                    help="cap mapreduce shuffle rounds (default: the"
                         " provably sufficient ceil(records/capacity) bound;"
                         " an explicit cap errors out rather than dropping"
                         " records if exhausted)")
    ap.add_argument("--exchange-impl", default="auto",
                    choices=("auto", "sort", "columns", "counting"),
                    help="mapreduce shuffle exchange: 'counting' packs each"
                         " record into one uint32 and orders it with a"
                         " per-destination counting scatter (no sort at"
                         " all); 'sort' packs and stable-argsorts once;"
                         " 'columns' ships the four int32 columns; 'auto'"
                         " uses counting whenever sites fit in 24 bits"
                         " (bit-identical results either way)")
    ap.add_argument("--packed-shuffle", default="auto",
                    choices=("auto", "on", "off"),
                    help="DEPRECATED alias of --exchange-impl: 'on' ="
                         " --exchange-impl sort, 'off' = columns")
    ap.add_argument("--histogram-impl", default="segment_sum",
                    choices=("segment_sum", "pallas"),
                    help="local-combine histogram implementation: the"
                         " fused jnp segment-sum (default) or the Pallas"
                         " segment_hist kernel (interpret mode off-TPU),"
                         " plugged into every backend's histogram_fn hook")
    ap.add_argument("--stream-chunks", type=int, default=0, metavar="N",
                    help="stream each node's records in N regenerated chunks"
                         " (0 = one-shot materialized log)")
    ap.add_argument("--overlap", default="auto",
                    choices=("auto", "on", "off"),
                    help="streaming execution strategy: 'auto' runs the"
                         " single-jit lax.scan; 'on' double-buffers per"
                         " chunk (chunk k+1's MalGen generation dispatched"
                         " behind chunk k's exchange+reduce —"
                         " repro.core.overlap); 'off' runs the identical"
                         " per-chunk programs strictly serialized (the"
                         " control for measuring the overlap win). All"
                         " three are bit-identical; requires"
                         " --stream-chunks")
    ap.add_argument("--check", action="store_true",
                    help="after the timed runs, assert the computed rho"
                         " bit-equals the single-device host oracle"
                         " (exit 1 on mismatch) — the multi-process"
                         " acceptance gate")
    ap.add_argument("--checkpoint-dir", default=None, metavar="DIR",
                    help="make the streaming run resumable: run the scan in"
                         " segments, checkpointing the carry after each into"
                         " DIR (requires --stream-chunks; incompatible with"
                         " --gen-device)")
    ap.add_argument("--resume", action="store_true",
                    help="resume from the latest committed checkpoint in"
                         " --checkpoint-dir (default: start fresh)")
    ap.add_argument("--segment-chunks", type=int, default=0, metavar="K",
                    help="chunks per checkpointed segment (default: "
                         "--stream-chunks, i.e. one segment)")
    ap.add_argument("--inject-faults", default=None, metavar="SPEC",
                    help="seeded chaos schedule, e.g. 'transient_rate=0.2,"
                         "seed=5,bad_hosts=1+3,kill_at_segment=2' (see"
                         " repro.faults.FaultPlan.parse)")
    ap.add_argument("--retry-attempts", type=int, default=3,
                    help="total tries per segment before"
                         " SegmentRetriesExhausted (resumable runs)")
    ap.add_argument("--gen-device", action="store_true",
                    help="device-parallel MalGen: each node generates its "
                         "own shard on its device (generate_shard_device) "
                         "and the statistic runs fused on the generated "
                         "records — the global log is never materialized "
                         "on host. The timed run includes generation. "
                         "Default (host) path stays the bit-exact oracle")
    ap.add_argument("--bench-json", default=None, metavar="PATH",
                    help="also write this run as a BENCH_*.json document "
                         "(schema: repro/bench/schema.py) for "
                         "repro.bench.compare")
    args = ap.parse_args()
    enable_compile_cache()

    if _DIST.is_distributed:
        if jax.device_count() != args.nodes:
            ap.error(f"--nodes ({args.nodes}) must equal the global device"
                     f" count ({jax.device_count()} across"
                     f" {_DIST.num_processes} processes)")
        mesh = make_global_mesh()
        print(f"[{coordinator.process_banner(_DIST)}] "
              f"{jax.local_device_count()} local of "
              f"{jax.device_count()} global devices", flush=True)
    else:
        mesh = make_mesh((args.nodes,), ("data",))
    cfg = MalGenConfig(num_sites=args.sites, num_entities=args.entities)
    total = args.nodes * args.records_per_node

    # the mapreduce shuffle is lossless at any capacity factor (multi-round
    # residual exchange); surface its round/overflow accounting alongside
    # the timing so the capacity/rounds tradeoff is visible per run
    want_stats = args.backend == "mapreduce"
    impl = args.exchange_impl
    if args.packed_shuffle != "auto":
        if impl != "auto":
            ap.error("--packed-shuffle is a deprecated alias of"
                     " --exchange-impl; pass only one of them")
        impl = {"on": "sort", "off": "columns"}[args.packed_shuffle]
        print(f"--packed-shuffle {args.packed_shuffle} is deprecated; "
              f"use --exchange-impl {impl}")
    plan = ExchangePlan(impl=impl, capacity_factor=args.capacity_factor,
                        max_shuffle_rounds=args.max_shuffle_rounds,
                        histogram_impl=args.histogram_impl)
    if args.histogram_impl == "pallas":
        from repro.kernels import resolve_interpret
        print("histogram: Pallas segment_hist kernel"
              + (" (interpret mode)" if resolve_interpret() else ""))

    if args.stream_chunks:
        if args.records_per_node % args.stream_chunks:
            ap.error("--stream-chunks must divide --records-per-node")
        chunk = args.records_per_node // args.stream_chunks
    if args.overlap != "auto" and not args.stream_chunks:
        ap.error("--overlap on/off pipelines the streaming engine;"
                 " pass --stream-chunks")
    if args.check and args.gen_device:
        ap.error("--check compares against the host oracle; it does not"
                 " cover --gen-device (see tests/md_scripts/"
                 "gen_device_check.py for that path's oracle)")

    resumable = args.checkpoint_dir is not None or args.inject_faults
    if resumable:
        if _DIST.is_distributed:
            ap.error("--checkpoint-dir/--inject-faults are single-process"
                     " for now (multi-writer checkpoints over"
                     " jax.distributed are a separate work item)")
        if not args.stream_chunks:
            ap.error("--checkpoint-dir/--inject-faults need --stream-chunks"
                     " (resumable runs segment the streaming scan)")
        if args.gen_device:
            ap.error("--checkpoint-dir/--inject-faults are incompatible"
                     " with --gen-device")
        return _run_resumable(ap, args, mesh, cfg, chunk, plan)

    if args.gen_device:
        if _DIST.is_distributed:
            ap.error("--gen-device is single-process for now (its seed"
                     " closes over per-shard static layout; use the"
                     " streaming engine for multi-process runs)")
        from repro.malgen import make_seed

        mode = (f"fused + stream x{args.stream_chunks}" if args.stream_chunks
                else "fused")
        print(f"MalGen (device, {mode}): {total:,} records "
              f"({total * 100 / 1e6:.0f} MB logical) generated in place on "
              f"{args.nodes} nodes — global log never materialized on host")
        t0 = time.perf_counter()
        seed = make_seed(jax.random.key(0), cfg, total)
        jax.block_until_ready(seed.entity_mark_time)
        print(f"  seeded in {time.perf_counter() - t0:.1f}s "
              f"(scatter payload {seed.seed_bytes / 1e6:.1f} MB)")

        def run_generated():
            # seed is closed over, not a jit argument: its static
            # num_marked_events defines the per-shard layout
            kw = dict(mesh=mesh, cfg=cfg, plan=plan,
                      records_per_shard=args.records_per_node,
                      statistic=args.statistic, backend=args.backend,
                      return_shuffle_stats=want_stats)
            if args.stream_chunks:
                out = malstone(seed, engine="generated_streaming",
                               chunk_records=chunk, **kw)
            else:
                out = malstone(seed, engine="generated", **kw)
            return (out[0].rho, out[1]) if want_stats else out.rho

        fn = jax.jit(run_generated)
        run_args = ()
    elif args.stream_chunks:
        num_chunks = args.nodes * args.stream_chunks
        mode_extra = {"on": " [overlap pipeline]",
                      "off": " [per-chunk serialized]"}.get(args.overlap, "")
        print(f"MalGen (streaming{mode_extra}): {total:,} records "
              f"({total * 100 / 1e6:.0f} MB logical) over {args.nodes} nodes"
              f" x {args.stream_chunks} chunks of {chunk:,} — "
              f"log never materialized")
        t0 = time.perf_counter()
        seed = make_seed_streaming(jax.random.key(0), cfg, num_chunks, chunk)
        jax.block_until_ready(seed.entity_mark_time)
        print(f"  seeded in {time.perf_counter() - t0:.1f}s "
              f"(scatter payload {seed.seed_bytes / 1e6:.1f} MB)")
        host_seed = seed
        if _DIST.is_distributed:
            seed = replicate_to_mesh(seed, mesh)

        if args.overlap != "auto":
            from repro.core.overlap import OverlapStreamingRunner

            # one runner for all timed iterations: the gen/fold/snapshot
            # programs compile once and are driven per chunk from the host
            runner = OverlapStreamingRunner(
                seed, cfg, mesh=mesh, num_chunks=num_chunks,
                chunk_records=chunk, num_sites=cfg.num_sites,
                backend=args.backend, plan=plan)
            overlap_on = args.overlap == "on"

            def run_overlap():
                res, stats = runner.run_result(args.statistic,
                                               overlap=overlap_on)
                return (res.rho, stats) if want_stats else res.rho

            fn = run_overlap  # eager driver: it jits per-chunk internally
            run_args = ()
        else:
            def run_stream(s):
                out = malstone(
                    s, cfg.num_sites, mesh=mesh, engine="streaming",
                    plan=plan, backend=args.backend, chunk_records=chunk,
                    statistic=args.statistic, cfg=cfg,
                    num_chunks=num_chunks,
                    return_shuffle_stats=want_stats)
                return (out[0].rho, out[1]) if want_stats else out.rho

            fn = jax.jit(run_stream)
            run_args = (seed,)
    else:
        print(f"MalGen: {total:,} records ({total * 100 / 1e6:.0f} MB "
              f"logical) over {args.nodes} nodes")
        t0 = time.perf_counter()
        log, _ = generate_sharded_log(jax.random.key(0), cfg, args.nodes,
                                      args.records_per_node)
        jax.block_until_ready(log.site_id)
        print(f"  generated in {time.perf_counter() - t0:.1f}s")
        host_log = log
        if _DIST.is_distributed:
            log = shard_log_to_mesh(log, mesh)

        def run_oneshot(l):
            out = malstone(
                l, cfg.num_sites, mesh=mesh, plan=plan,
                statistic=args.statistic, backend=args.backend,
                return_shuffle_stats=want_stats)
            return (out[0].rho, out[1]) if want_stats else out.rho

        fn = jax.jit(run_oneshot)
        run_args = (log,)

    # shared timing protocol (repro.bench.timing), with exactly ONE warmup
    # execution (max_warmup=1 opts out of steady-state probing): launcher
    # runs can be minutes each, so the adaptive warmup loop is not worth
    # up-to-8 extra executions here
    timing, out = time_callable(
        fn, *run_args, warmup=1, iters=args.runs, max_warmup=1,
        on_sample=lambda r, us: print(
            f"  run {r + 1}: {us / 1e3:.1f} ms "
            f"({total / (us / 1e6) / 1e6:.1f}M records/s)", flush=True))
    mode = f"stream x{args.stream_chunks}" if args.stream_chunks else "one-shot"
    if args.stream_chunks and args.overlap != "auto":
        mode += f" overlap={args.overlap}"
    if args.gen_device:
        mode = f"gen-device {mode}" if args.stream_chunks else "gen-device"
    print(f"MalStone {args.statistic} [{args.backend}, {mode}] "
          f"median {timing.us_per_call / 1e3:.1f} ms over {args.runs} runs")

    if args.check:
        from repro.core import malstone_single_device
        from repro.malgen import generate_chunked_log

        # every process checks against its own host-side oracle — in a
        # multi-process gang a mismatch anywhere exits that worker nonzero,
        # which the spawn parent propagates
        olog = (generate_chunked_log(host_seed, cfg, num_chunks, chunk)
                if args.stream_chunks else host_log)
        oracle = malstone_single_device(olog, cfg.num_sites, args.statistic)
        got = np.asarray(out[0] if want_stats else out)
        ref = np.asarray(oracle.rho)
        if not np.array_equal(got, ref):
            raise SystemExit(
                f"--check FAILED: rho {got!r} != single-device oracle "
                f"{ref!r}")
        print(f"--check: rho[{ref.size}] bit-equals the single-device "
              f"oracle (mean {float(ref.mean()):.6f})")

    shuffle_derived = None
    if want_stats:
        stats = out[1]
        if int(stats.overflow) != 0:
            raise SystemExit(
                f"shuffle exhausted --max-shuffle-rounds with "
                f"{int(stats.overflow)} records undelivered")
        from repro.common.types import WEEKS_PER_YEAR
        from repro.core.backends import resolve_exchange_impl
        from repro.core.runner import _pad_sites
        # same static decision the shuffle itself makes: runner-padded
        # sites, the default week bucketing the drivers run at
        impl_used = resolve_exchange_impl(
            plan.impl, _pad_sites(args.sites, args.nodes), WEEKS_PER_YEAR)
        packed_used = impl_used != "columns"
        shuffle_derived = {
            "capacity_factor": args.capacity_factor,
            "shuffle_impl": impl_used,
            "shuffle_packed": packed_used,
            "shuffle_rounds": int(stats.rounds),
            "shuffle_capacity": int(stats.capacity),
            "shuffle_sent": int(stats.sent),
            "shuffle_deferred": int(stats.residual),
            "shuffle_overflow": int(stats.overflow),
            "shuffle_bytes_exchanged": int(stats.bytes_exchanged),
        }
        print(f"  shuffle: {'packed' if packed_used else 'unpacked'} "
              f"impl={impl_used} "
              f"rounds={shuffle_derived['shuffle_rounds']} "
              f"capacity={shuffle_derived['shuffle_capacity']}/dest "
              f"deferred={shuffle_derived['shuffle_deferred']} "
              f"bytes={shuffle_derived['shuffle_bytes_exchanged']:,} "
              f"overflow=0 (lossless)")

    # only rank 0 writes the document in a multi-process gang (every worker
    # would otherwise race on the same path with identical content)
    if args.bench_json and _DIST.process_id in (None, 0):
        engine = "streaming" if args.stream_chunks else "oneshot"
        stat_slug = args.statistic.lower().replace("-", "")
        scenario = f"launch_malstone_{stat_slug}_{args.backend}_{engine}"
        if args.gen_device:
            scenario += "_gendev"
        doc = schema.new_document(
            pathlib.Path(args.bench_json).stem.removeprefix("BENCH_"),
            env={"source": "repro.launch.malstone"})
        schema.add_result(
            doc, scenario,
            {"backend": args.backend, "statistic": args.statistic,
             "engine": engine, "gen_device": args.gen_device,
             "nodes": args.nodes,
             "records_per_node": args.records_per_node,
             "sites": args.sites, "entities": args.entities,
             "stream_chunks": args.stream_chunks,
             "overlap": args.overlap,
             "num_processes": _DIST.num_processes,
             "capacity_factor": args.capacity_factor,
             "exchange_impl": args.exchange_impl,
             "packed_shuffle": args.packed_shuffle,
             "histogram_impl": args.histogram_impl},
            timing, records=total, derived=shuffle_derived)
        out = schema.write_document(doc, path=args.bench_json)
        print(f"wrote {out}")


def _run_resumable(ap, args, mesh, cfg, chunk, exchange_plan):
    """The --checkpoint-dir / --inject-faults path: one segment-at-a-time
    run through ``repro.core.resume`` (bit-identical to the uninterrupted
    streaming engine), wall-clocked once — re-running it under the shared
    timing loop would resume instead of compute, so the single sample goes
    through ``timing_from_samples`` into the same BENCH json shape."""
    from repro.bench.timing import timing_from_samples
    from repro.core.resume import ResumableRunner
    from repro.faults import FaultPlan, RetryPolicy

    total = args.nodes * args.records_per_node
    num_chunks = args.nodes * args.stream_chunks
    seg = args.segment_chunks or args.stream_chunks
    plan = FaultPlan.parse(args.inject_faults) if args.inject_faults else None

    print(f"MalGen (streaming, resumable): {total:,} records "
          f"({total * 100 / 1e6:.0f} MB logical) over {args.nodes} nodes "
          f"x {args.stream_chunks} chunks of {chunk:,}; checkpoint every "
          f"{seg} chunks"
          + (f" -> {args.checkpoint_dir}" if args.checkpoint_dir else
             " (no checkpoint dir — faults only)"))
    t0 = time.perf_counter()
    seed = make_seed_streaming(jax.random.key(0), cfg, num_chunks, chunk)
    jax.block_until_ready(seed.entity_mark_time)
    print(f"  seeded in {time.perf_counter() - t0:.1f}s "
          f"(scatter payload {seed.seed_bytes / 1e6:.1f} MB)")
    if plan is not None:
        print(f"  fault schedule: {plan}")

    runner = ResumableRunner(
        seed, cfg, mesh=mesh, num_chunks=num_chunks, chunk_records=chunk,
        segment_chunks=seg, backend=args.backend, statistic=args.statistic,
        plan=exchange_plan)
    t0 = time.perf_counter()
    out = runner.run(checkpoint_dir=args.checkpoint_dir, resume=args.resume,
                     faults=plan,
                     retry=RetryPolicy(max_attempts=args.retry_attempts))
    wall_us = (time.perf_counter() - t0) * 1e6
    timing = timing_from_samples([wall_us])
    rep = out.report

    print(f"MalStone {args.statistic} [{args.backend}, resumable "
          f"x{args.stream_chunks}/seg{seg}] {wall_us / 1e3:.1f} ms "
          f"({rep.segments_run}/{rep.segments_total} segments run, "
          f"{rep.chunks_skipped} chunks restored)")
    if rep.resumed_from_step is not None:
        print(f"  resumed from checkpoint step {rep.resumed_from_step}")
    print(f"  checkpoint: save {rep.checkpoint_save_ms:.1f} ms total, "
          f"restore {rep.checkpoint_restore_ms:.1f} ms")
    if plan is not None:
        print(f"  recovery: {rep.fault_events} injected faults, "
              f"{rep.segments_retried} segment retries, alarmed hosts "
              f"{rep.alarmed_hosts}, {rep.rerouted_shards} shards rerouted")

    derived = rep.to_derived()
    derived["segment_chunks"] = seg
    if out.shuffle_stats is not None:
        stats = out.shuffle_stats
        derived.update(
            capacity_factor=args.capacity_factor,
            shuffle_rounds=int(stats.rounds),
            shuffle_sent=int(stats.sent),
            shuffle_overflow=int(stats.overflow),
            shuffle_bytes_exchanged=int(stats.bytes_exchanged))
        print(f"  shuffle: rounds={derived['shuffle_rounds']} "
              f"sent={derived['shuffle_sent']} overflow=0 (lossless)")

    if args.bench_json:
        stat_slug = args.statistic.lower().replace("-", "")
        scenario = f"launch_malstone_{stat_slug}_{args.backend}_resume"
        doc = schema.new_document(
            pathlib.Path(args.bench_json).stem.removeprefix("BENCH_"),
            env={"source": "repro.launch.malstone"})
        schema.add_result(
            doc, scenario,
            {"backend": args.backend, "statistic": args.statistic,
             "engine": "resumable", "nodes": args.nodes,
             "records_per_node": args.records_per_node,
             "sites": args.sites, "entities": args.entities,
             "stream_chunks": args.stream_chunks, "segment_chunks": seg,
             "resume": args.resume,
             "inject_faults": args.inject_faults or "",
             "capacity_factor": args.capacity_factor,
             "exchange_impl": args.exchange_impl},
            timing, records=rep.chunks_processed * chunk, derived=derived)
        path = schema.write_document(doc, path=args.bench_json)
        print(f"wrote {path}")


if __name__ == "__main__":
    main()
