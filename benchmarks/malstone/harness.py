"""One run of one cell: set up, measure whole MalStone-B jobs, check them.

Everything particular to a cell is data: ``BENCHMARK.json`` names the
cell's configuration, traffic and chips, the configuration file gives the
deployment, the traffic file the source of records (``sources.py``), and
each per-layer metric is a reader ``metrics/<name>.py`` with
``read(ctx) -> float | None``. Nothing here branches on a cell.

A run:

1. Set-up: build the source, compile the job (``repro.core.run``, streaming
   engine, the configuration's middleware, MalStone B), all before the
   first timed dispatch. ``setup_s`` runs from process start to there.
2. Window: jobs back to back, each waited for, until the first job boundary
   after ``seconds``. ``records_per_s`` is the records of all those jobs,
   on all chips, over the window's wall time. With ``trace``, the profiler
   records the first ``TRACE_JOBS`` jobs, and the window's end-to-end
   numbers are not reported.
3. After the window: the peak device memory, then the per-layer readers
   (traced runs), then the reference over the same records, against which
   every job's ``rho``, ``total`` and ``marked`` are compared.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import statistics
import tempfile
import time
from typing import Callable, Optional

import numpy as np

import reference
import sources

TRACE_JOBS = 2
# The numbers compared and their limits (PERF.md gives the readings).
LIMITS = {"total_mismatch": 0, "marked_mismatch": 0, "rho_ulp": 1024}


@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    traffic: dict
    chips: int
    end_to_end: list        # BENCHMARK.json entries that apply to the cell
    per_layer: list
    bench_dir: pathlib.Path


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: pathlib.Path, name: str) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json``, with its configuration
    and traffic files read."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r}; have {sorted(by_name)}")
    w = by_name[name]
    configs = {c["name"]: c for c in bench["configs"]}
    bench_dir = root / bench["paths"][0]
    config = json.loads((root / configs[w["config"]]["file"]).read_text())
    traffic = json.loads(
        (bench_dir / "traffic" / f"{w['traffic']}.json").read_text())
    return Cell(name, config, traffic, int(w["chips"]),
                [m for m in bench["end_to_end"] if _applies(m, name)],
                [m for m in bench["per_layer"] if _applies(m, name)],
                bench_dir)


def load_reader(bench_dir: pathlib.Path, metric: str) -> Callable:
    """``read`` of ``metrics/<metric>.py``."""
    path = bench_dir / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"malstone_metric_{metric.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


def seconds_per_call(fn: Callable, calls: int, groups: int = 5) -> float:
    """Host-clock seconds per call: ``fn(i)`` for ``i`` in ``range(calls)``
    dispatched back to back and waited for as one group, after one group of
    warm-up; the median over ``groups`` groups."""
    import jax

    def group():
        t0 = time.perf_counter()
        jax.block_until_ready([fn(i) for i in range(calls)])
        return (time.perf_counter() - t0) / calls

    group()
    return statistics.median(group() for _ in range(groups))


@dataclasses.dataclass
class Context:
    """What a per-layer reader may read."""

    cell: Cell
    mesh: object
    source: sources.Source
    plan: object
    device_kind: str
    summary: Optional[object] = None   # trace_reduce.Summary
    traced_jobs: int = 0
    counters: dict = dataclasses.field(default_factory=dict)

    @property
    def middleware(self) -> str:
        return self.cell.config["middleware"]


def exchange_plan(config: dict):
    from repro.common.types import ExchangePlan

    return ExchangePlan(**config["exchange"])


def compile_job(cell: Cell, mesh, source: sources.Source, plan):
    """The job the window drives, compiled for the source it reads."""
    import jax

    from repro.core import run

    cfg = cell.config

    def malstone_job(program_input):
        return run(program_input, cfg["num_sites"], mesh=mesh,
                   engine="streaming", backend=cfg["middleware"],
                   statistic=cfg["statistic"],
                   chunk_records=source.chunk_records, plan=plan,
                   return_shuffle_stats=True, **source.run_kwargs)

    return jax.jit(malstone_job).lower(source.program_input).compile()


def _annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def memory_peak_bytes(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks, default=0)


def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             devices, started: float, log=print) -> dict:
    """One run of ``cell`` on ``devices[:cell.chips]``; returns the result
    line's object. ``started`` is the process's start on
    ``time.perf_counter``'s clock."""
    import jax

    from repro.launch.mesh import make_mesh

    t_in = time.perf_counter()
    use = list(devices)[:cell.chips]
    mesh = make_mesh((cell.chips,), (sources.AXIS,), devices=use)
    source = sources.make_source(cell.traffic, cell.config, mesh, seed)
    t_source = time.perf_counter()
    plan = exchange_plan(cell.config)
    job = compile_job(cell, mesh, source, plan)
    hlo_text = job.as_text() if trace else ""
    t_compiled = time.perf_counter()
    log(f"set-up: {t_in - started:.3f} s to the chips and the cache, "
        f"{t_source - t_in:.3f} s making the source, "
        f"{t_compiled - t_source:.3f} s loading or compiling the job")

    trace_dir = tempfile.TemporaryDirectory(prefix="malstone-trace-")
    outs, traced_jobs = [], 0
    t_first = time.perf_counter()
    setup_s = t_first - started
    log(f"set-up {setup_s:.3f} s; window of {seconds} s starts")
    if trace:
        jax.profiler.start_trace(trace_dir.name)
    deadline = t_first + seconds
    while True:
        t_job = time.perf_counter()
        with _annotate("bench.job"):
            with _annotate("bench.dispatch"):
                out = job(source.program_input)
            with _annotate("bench.wait"):
                jax.block_until_ready(out)
        outs.append(out)
        log(f"job {len(outs)}: {time.perf_counter() - t_job:.6f} s")
        if trace and len(outs) == TRACE_JOBS:
            jax.profiler.stop_trace()
            traced_jobs = len(outs)
        if time.perf_counter() >= deadline:
            break
    t_end = time.perf_counter()
    if trace and not traced_jobs:
        jax.profiler.stop_trace()
        traced_jobs = len(outs)
    window_s = t_end - t_first
    peak = memory_peak_bytes(use)

    answers = [reference.Answer(*(np.asarray(x) for x in
                                  (r.rho, r.total, r.marked)))
               for r, _ in outs]
    stats = outs[0][1]
    counters = {} if stats is None else {
        k: int(v) for k, v in stats._asdict().items()}
    del outs, out

    dev = use[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(use), "memory_peak_bytes": peak}
    metrics, breakdown = {}, None
    if trace:
        summary = _summarize(trace_dir.name, hlo_text)
        trace_dir.cleanup()
        ctx = Context(cell, mesh, source, plan, dev.device_kind, summary,
                      traced_jobs, counters)
        for m in cell.per_layer:
            value = load_reader(cell.bench_dir, m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        if summary is not None:
            device["busy_s"] = statistics.fmean(summary.busy_s.values())
            device["window_s"] = summary.window_s
            breakdown = {"device_ops": [list(x) for x in summary.top_ops],
                         "idle_gaps": [list(x) for x in summary.gaps]}
    else:
        trace_dir.cleanup()
        e2e = {"records_per_s": len(answers) * source.records_per_job
               / window_s,
               "setup_s": setup_s}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}

    t0 = time.perf_counter()
    counts = reference.count_keys(
        source.reference_key_blocks(), cell.config["num_sites"],
        cell.config["num_weeks"])
    want = reference.malstone_b(counts)
    log(f"reference {time.perf_counter() - t0:.3f} s")
    worst = dict.fromkeys(LIMITS, 0)
    failed = 0
    for got in answers:
        numbers = reference.compare(got, want)
        failed += any(numbers[k] > LIMITS[k] for k in LIMITS)
        worst = {k: max(worst[k], numbers[k]) for k in LIMITS}

    result = {"correct": bool(answers) and failed == 0,
              "attempted": len(answers), "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = {k: {"value": worst[k], "limit": LIMITS[k]}
                        for k in LIMITS}
    return result


def _summarize(trace_dir: str, hlo_text: str):
    import trace_reduce

    files = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        return None
    return trace_reduce.summarize(trace_reduce.load(files[-1]),
                                  trace_reduce.parse_hlo(hlo_text))


def check_lines(result: dict) -> list:
    """The compared numbers beside their limits, one per line."""
    return [f"check {k} {v['value']} limit {v['limit']}"
            for k, v in result["checks"].items()]

