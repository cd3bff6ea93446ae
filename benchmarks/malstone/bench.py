"""MalStone benchmark: one run of one cell, on the chips of this machine.

    python3 benchmarks/malstone/bench.py --workload streams.seed \\
        --seed 7 --seconds 30 --trace 0

Run it from the root of a checkout: the program is imported from
``src/``, the cell is looked up by name in ``BENCHMARK.json``. The run sets
up, measures whole MalStone-B jobs for ``--seconds`` (``--trace 1``: the
per-layer metrics instead of the end-to-end ones), checks every job against
the plain reference, and prints one JSON object as the last line of
standard output; the numbers compared, beside their limits, are the last
lines of standard error. Without a TPU, or with fewer chips than the cell
asks for, it prints no result and exits 1.
"""

from __future__ import annotations

import time

STARTED = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parents[1]


def runtime_env() -> None:
    """Keep the TPU runtime's log files off the fixed ``/tmp/tpu_logs``
    (unless a log directory is already set): a run writes only inside its
    checkout and its own home and temporary directories."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def enable_compile_cache() -> str:
    """The program's persistent compile cache (``$JAX_COMPILATION_CACHE_DIR``
    when set, else the checkout's fixed ``.jax_cache``), keeping every
    program however quick its compile, so a warm run compiles nothing."""
    import jax

    from repro.common.env import enable_compile_cache as program_cache

    path = program_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def chips_or_none(need: int):
    """The devices to run on, or ``None`` (after saying why) when JAX finds
    no TPU or fewer than ``need`` chips."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        log(f"JAX found no TPU (platform {devices[0].platform!r}); "
            f"this benchmark measures the chip and never falls back")
        return None
    if len(devices) < need:
        log(f"the cell needs {need} chips, JAX sees {len(devices)}")
        return None
    return devices


def main(argv=None) -> int:
    args = parse(argv)
    if not (ROOT / "src" / "repro").is_dir():
        log(f"no src/repro under {ROOT}: run from a checkout of the program")
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    runtime_env()
    import harness

    cell = harness.load_cell(ROOT, args.workload)
    t_imported = time.perf_counter()
    devices = chips_or_none(cell.chips)
    if devices is None:
        return 1
    log(f"set-up: {t_imported - STARTED:.3f} s importing, "
        f"{time.perf_counter() - t_imported:.3f} s starting the chips")
    log(f"compile cache: {enable_compile_cache()}")
    result = harness.run_cell(cell, seed=args.seed, seconds=args.seconds,
                              trace=bool(args.trace), devices=devices,
                              started=STARTED, log=log)
    for line in harness.check_lines(result):
        print(line, file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
