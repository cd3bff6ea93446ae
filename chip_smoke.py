"""Smoke run of MalStone's main path on a TPU, checked against the oracle.

    python chip_smoke.py               # one chip: every phase below
    python chip_smoke.py --four-chips  # one process, 2x2 mesh: the four
                                       # streaming backends and their oracle

The deployment is MalGen's default (``repro.malgen.MalGenConfig``): 100,000
sites, 1,000,000 entities, alpha = 1.2, 10% marked sites, 52 weeks, so the
device state is the real 100k x 52 x 2 int32 histogram. Phases, each driven
through the entry points a user calls and each printing one line:

1. streaming engine (``repro.core.run``, ``engine="streaming"``), MalStone
   B, all four backends at 2^26 records per chip in chunks of 2^20;
2. one-shot engine, sphere and mapreduce, over a 2^24-record log;
3. ``histogram_impl="pallas"`` (the Pallas reducer) on sphere and
   mapreduce over the same log, against the default reducer;
4. serving: a ``MalStoneService`` ingests 16 chunks of 2^20 records and
   answers batches of the ``default`` and ``growing`` query mixes with the
   Pallas query kernel.

Every ``rho`` and histogram must be bit-equal to the single-device oracle in
``repro.core.spm``. For the mapreduce and serving programs the line says
whether the compiled HLO holds ``tpu_custom_call`` (the Pallas kernels ran
compiled, not interpreted or replaced by their jnp references). Times are
the median of a few runs after a compile and a checked run, and are not a
benchmark.

The script exits non-zero, printing no result, when JAX finds no TPU or
when it is not run from a checkout holding ``src/repro``. On success the
last line of standard output is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import statistics
import sys
import time
from typing import Callable, Optional

REPO = pathlib.Path(__file__).resolve().parent
SRC = REPO / "src"
BACKENDS = ("streams", "sphere", "mapreduce", "mapreduce_combiner")


@dataclasses.dataclass(frozen=True)
class Geometry:
    """Sizes of one smoke run. ``FULL`` is the deployment; tests pass a
    tiny one to rehearse the control flow on the CPU."""

    sites: int
    entities: int
    stream_records: int   # per chip, streaming engine
    chunk_records: int
    oneshot_records: int  # per chip, one-shot engine and Pallas reducer
    serve_chunks: int     # chunks the service ingests, one per call
    runs: int             # timed runs per program, after one checked run


FULL = Geometry(sites=100_000, entities=1_000_000, stream_records=1 << 26,
                chunk_records=1 << 20, oneshot_records=1 << 24,
                serve_chunks=16, runs=3)


@dataclasses.dataclass
class PhaseResult:
    name: str
    mismatches: list
    median_s: float
    compile_s: Optional[float] = None
    kernels: Optional[bool] = None   # "tpu_custom_call" in compiled HLO
    note: str = ""

    @property
    def ok(self) -> bool:
        return not self.mismatches

    def line(self) -> str:
        status = "OK" if self.ok else "FAIL " + "; ".join(self.mismatches)
        parts = [f"[phase] {self.name}: {status}"]
        if self.note:
            parts.append(self.note)
        if self.compile_s is not None:
            parts.append(f"compile {self.compile_s:.3f} s")
        parts.append(f"median {self.median_s:.6f} s per run "
                     f"(not a benchmark)")
        if self.kernels is not None:
            parts.append("tpu_custom_call in compiled HLO: "
                         + ("yes" if self.kernels else "no"))
        return " | ".join(parts)


# ------------------------------------------------------------------ checks
def bit_diff(name: str, got, want) -> list:
    """[] when ``got`` and ``want`` are bit-for-bit equal, else one line."""
    import numpy as np

    got = np.ascontiguousarray(np.atleast_1d(got))
    want = np.ascontiguousarray(np.atleast_1d(want))
    if got.shape != want.shape or got.dtype != want.dtype:
        return [f"{name}: {got.dtype}{list(got.shape)} != "
                f"{want.dtype}{list(want.shape)}"]
    bits = f"u{got.dtype.itemsize}"
    bad = int((got.view(bits) != want.view(bits)).sum())
    if not bad:
        return []
    delta = np.abs(got.astype(np.float64) - want.astype(np.float64)).max()
    return [f"{name}: {bad} of {got.size} elements differ "
            f"(max |diff| {delta!r})"]


def result_diff(name: str, got, want) -> list:
    """Bit-compare two ``SpmResult``s field by field."""
    return (bit_diff(f"{name} rho", got.rho, want.rho)
            + bit_diff(f"{name} total", got.total, want.total)
            + bit_diff(f"{name} marked", got.marked, want.marked))


def oracle_hist(seed, cfg, num_chunks: int, chunk_records: int):
    """``spm.site_week_histogram`` of the chunk-keyed log, summed chunk by
    chunk on the default device. The histogram is a sum over records, so
    this is exactly the single-device oracle's one-pass histogram, at
    O(chunk) memory: a 4-chip stream of 2^28 records would not fit one chip
    as a materialized log."""
    import jax
    import jax.numpy as jnp

    from repro.core import site_week_histogram
    from repro.malgen import generate_chunk

    @jax.jit
    def chunk_hist(s, i):
        log = generate_chunk(s, cfg, i, chunk_records)
        return site_week_histogram(log, cfg.num_sites)

    hist = chunk_hist(seed, jnp.int32(0))
    for i in range(1, num_chunks):
        hist = hist + chunk_hist(seed, jnp.int32(i))
    return hist


# ------------------------------------------------------------------ timing
def compile_program(fn: Callable, *args):
    """(compiled, seconds, has_tpu_custom_call) for ``jax.jit(fn)``."""
    import jax

    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    seconds = time.perf_counter() - t0
    return compiled, seconds, "tpu_custom_call" in compiled.as_text()


def run_and_time(fn: Callable, *args, runs: int):
    """(output of one run, median seconds of ``runs`` more)."""
    import jax

    out = jax.block_until_ready(fn(*args))
    samples = []
    for _ in range(runs):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        samples.append(time.perf_counter() - t0)
    return out, statistics.median(samples)


# ------------------------------------------------------------------ phases
def streaming_phase(geo: Geometry, mesh, report) -> None:
    """MalStone B through the streaming engine on every backend, each
    bit-equal to the oracle over the same chunk-keyed log."""
    import jax

    from repro.core import malstone_b, run
    from repro.malgen import MalGenConfig, make_seed_streaming

    parts = mesh.devices.size
    cfg = MalGenConfig(num_sites=geo.sites, num_entities=geo.entities)
    chunk = geo.chunk_records
    num_chunks = parts * (geo.stream_records // chunk)
    seed = jax.block_until_ready(
        make_seed_streaming(jax.random.key(0), cfg, num_chunks, chunk))
    want = malstone_b(oracle_hist(seed, cfg, num_chunks, chunk))
    records = num_chunks * chunk

    for backend in BACKENDS:
        def stream(s, backend=backend):
            return run(s, cfg.num_sites, mesh=mesh, engine="streaming",
                       cfg=cfg, num_chunks=num_chunks, chunk_records=chunk,
                       backend=backend, statistic="B")

        compiled, compile_s, kernels = compile_program(stream, seed)
        got, median_s = run_and_time(compiled, seed, runs=geo.runs)
        report(PhaseResult(
            f"streaming/B/{backend}",
            result_diff("vs spm oracle", got, want), median_s, compile_s,
            kernels if backend == "mapreduce" else None,
            note=f"{records:,} records on {parts} device(s), chunks of "
                 f"{chunk:,}, state {cfg.num_sites:,} x 52 x 2 int32"))


def oneshot_phases(geo: Geometry, mesh, report) -> None:
    """The one-shot engine on sphere and mapreduce against
    ``malstone_single_device``, then the Pallas reducer on both against the
    default reducer's result."""
    import jax

    from repro.common.types import ExchangePlan
    from repro.core import malstone_single_device, run
    from repro.malgen import MalGenConfig, generate_sharded_log

    parts = mesh.devices.size
    cfg = MalGenConfig(num_sites=geo.sites, num_entities=geo.entities)
    log, _ = generate_sharded_log(jax.random.key(1), cfg, parts,
                                  geo.oneshot_records)
    log = jax.block_until_ready(log)
    want = malstone_single_device(log, cfg.num_sites, "B")
    note = f"{log.num_records:,} records on {parts} device(s)"

    default = {}
    for impl in ("default", "pallas"):
        plan = ExchangePlan(histogram_impl="pallas") if impl == "pallas" \
            else ExchangePlan()
        for backend in ("sphere", "mapreduce"):
            def oneshot(lg, backend=backend, plan=plan):
                return run(lg, cfg.num_sites, mesh=mesh, engine="oneshot",
                           backend=backend, plan=plan, statistic="B")

            compiled, compile_s, kernels = compile_program(oneshot, log)
            got, median_s = run_and_time(compiled, log, runs=geo.runs)
            if impl == "default":
                default[backend] = got
                name = f"oneshot/B/{backend}"
                mismatches = result_diff("vs malstone_single_device", got,
                                         want)
            else:
                name = f"histogram_impl=pallas/B/{backend}"
                mismatches = (
                    result_diff("vs default reducer", got, default[backend])
                    + result_diff("vs malstone_single_device", got, want))
            report(PhaseResult(
                name, mismatches, median_s, compile_s,
                kernels if backend == "mapreduce" or impl == "pallas"
                else None, note=note))


def _answer_diff(mix: str, answers, hist, num_masks, den_masks) -> list:
    """Check decoded query answers against counts contracted on the host
    from the snapshot histogram, and ratios from ``safe_ratio``."""
    import jax.numpy as jnp
    import numpy as np

    from repro.common.types import safe_ratio

    num = np.asarray(num_masks, np.int64) @ hist[..., 1].T.astype(np.int64)
    den = np.asarray(den_masks, np.int64) @ hist[..., 0].T.astype(np.int64)
    rho = np.asarray(safe_ratio(jnp.asarray(num, jnp.int32),
                                jnp.asarray(den, jnp.int32)))
    out = []
    for i, ans in enumerate(answers):
        tag = f"{mix}[{i}]"
        out += bit_diff(f"{tag} num", ans.num, num[i].astype(np.int32))
        out += bit_diff(f"{tag} den", ans.den, den[i].astype(np.int32))
        out += bit_diff(f"{tag} rho", ans.rho, rho[i])
        if ans.top_sites is not None:
            k = len(ans.top_sites)
            out += bit_diff(f"{tag} top_rho", ans.top_rho,
                            np.sort(rho[i])[::-1][:k])
            out += bit_diff(f"{tag} top_sites' rho", rho[i][ans.top_sites],
                            ans.top_rho)
        if ans.site_total is not None:
            site = ans.spec.site
            out += bit_diff(f"{tag} site_total", ans.site_total,
                            hist[site, :, 0])
            out += bit_diff(f"{tag} site_marked", ans.site_marked,
                            hist[site, :, 1])
            out += bit_diff(f"{tag} site_rho", np.float32(ans.site_rho),
                            rho[i][site])
    return out


def serving_phase(geo: Geometry, mesh, report) -> None:
    """A mapreduce ``MalStoneService`` ingests ``serve_chunks`` chunks; its
    snapshot must equal the oracle histogram and the streaming engine over
    the same chunks, and its answers the oracle's counts and ratios."""
    import jax
    import numpy as np

    from repro.core import malstone_b, run
    from repro.malgen import MalGenConfig, make_seed_streaming
    from repro.serve import (
        MalStoneService,
        batched_query,
        default_query_mix,
        encode_query_batch,
        growing_window_specs,
    )

    parts = mesh.devices.size
    cfg = MalGenConfig(num_sites=geo.sites, num_entities=geo.entities)
    chunk = geo.chunk_records
    num_chunks = parts * geo.serve_chunks
    seed = jax.block_until_ready(
        make_seed_streaming(jax.random.key(2), cfg, num_chunks, chunk))
    service = MalStoneService(
        mesh=mesh, num_sites=cfg.num_sites, chunk_records=chunk,
        backend="mapreduce", seed=seed, cfg=cfg, num_chunks=num_chunks,
        kernel_path="pallas")

    samples = []
    for _ in range(geo.serve_chunks):
        t0 = time.perf_counter()
        service.ingest_chunks(1)
        service.chunks_folded  # blocks on the device cursor
        samples.append(time.perf_counter() - t0)
    hist, _ = service.snapshot()
    want_hist = np.asarray(oracle_hist(seed, cfg, num_chunks, chunk))
    streamed = run(seed, cfg.num_sites, mesh=mesh, engine="streaming",
                   cfg=cfg, num_chunks=num_chunks, chunk_records=chunk,
                   backend="mapreduce", statistic="B")
    got = service.result("B")
    report(PhaseResult(
        "serving/ingest/mapreduce",
        bit_diff("snapshot vs spm oracle histogram", hist, want_hist)
        + result_diff("result(B) vs streaming engine", got, streamed)
        + result_diff("result(B) vs spm oracle", got,
                      malstone_b(want_hist)),
        statistics.median(samples[1:] or samples),
        note=f"{geo.serve_chunks} ingests of {parts * chunk:,} records "
             f"(median excludes the compiling first ingest)"))

    mixes = {
        "default": default_query_mix(num_sites=cfg.num_sites, top_k=8),
        "growing": growing_window_specs("B"),
    }
    for mix, specs in mixes.items():
        batch = encode_query_batch(specs, num_sites=cfg.num_sites)
        shapes = [jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype)
                  for a in (hist, batch.num_masks, batch.den_masks,
                            batch.sites)]
        _, compile_s, kernels = compile_program(
            lambda h, n, d, s, k=batch.max_top_k: batched_query(
                h, n, d, s, max_top_k=k, kernel_path="pallas"), *shapes)
        answers = service.query(specs)
        qs = []
        for _ in range(geo.runs):
            t0 = time.perf_counter()
            service.query(specs)
            qs.append(time.perf_counter() - t0)
        report(PhaseResult(
            f"serving/query/{mix}",
            _answer_diff(mix, answers, hist, batch.num_masks,
                         batch.den_masks),
            statistics.median(qs), compile_s, kernels,
            note=f"{len(specs)} queries per batch, kernel_path=pallas"))


def one_chip(geo: Geometry, mesh, report) -> None:
    streaming_phase(geo, mesh, report)
    oneshot_phases(geo, mesh, report)
    serving_phase(geo, mesh, report)


# -------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="drive a 4-chip mesh from this one process and run "
                         "only the four streaming backends against the "
                         "oracle (4 x 2^26 records)")
    args = ap.parse_args(argv)

    if not (SRC / "repro").is_dir():
        print(f"chip_smoke.py: no src/repro next to {__file__}; run it from "
              f"a checkout of the repository", file=sys.stderr)
        return 2
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke.py: JAX found no TPU (platform {dev.platform!r});"
              f" this smoke run needs the chip", file=sys.stderr)
        return 1
    chips = 4 if args.four_chips else 1
    if len(devices) < chips:
        print(f"chip_smoke.py: {chips} chips needed, JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 1

    from repro.common.env import enable_compile_cache
    from repro.launch.mesh import make_mesh

    print(f"compile cache: {enable_compile_cache()}", flush=True)
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"mesh of {chips}", flush=True)
    mesh = make_mesh((chips,), ("data",), devices=devices[:chips])

    results = []

    def report(res: PhaseResult) -> None:
        results.append(res)
        print(res.line(), flush=True)

    t0 = time.perf_counter()
    if args.four_chips:
        streaming_phase(FULL, mesh, report)
    else:
        one_chip(FULL, mesh, report)
    failed = [r.name for r in results if not r.ok]
    # the exchange and the serving query must run as compiled kernels
    failed += [f"{r.name} (no tpu_custom_call)" for r in results
               if r.kernels is False]
    print(f"{len(results)} phases in {time.perf_counter() - t0:.1f} s",
          flush=True)
    if failed:
        print("chip_smoke.py: FAILED: " + ", ".join(failed), file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
