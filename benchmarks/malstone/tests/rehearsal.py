"""A cell's run at a tiny geometry on the CPU, through the harness's own
functions: the rehearsal of a chip run that the tests make.

Run as a script in a fresh process to get four virtual devices:

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python benchmarks/malstone/tests/rehearsal.py <cell> \\
        --root <checkout> --trace 1 --fault exchange_left_out

It prints the run's result object as one JSON line. ``--fault`` breaks the
program underneath the harness, as a faulty change to it would.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import pathlib
import sys
import time

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]
for p in (ROOT / "src", BENCH_DIR):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

import harness  # noqa: E402
import peaks  # noqa: E402

TINY = {"num_sites": 512, "num_entities": 4096,
        "records_per_node": 1 << 14, "chunk_records": 1 << 12}
FAULTS = ("none", "state_unchanged", "half_batch", "exchange_left_out",
          "answer_altered")


def tiny_cell(name: str, root: pathlib.Path = ROOT) -> harness.Cell:
    cell = harness.load_cell(root, name)
    cell.config.update(TINY)
    return cell


@contextlib.contextmanager
def _patched(obj, attr, value):
    old = getattr(obj, attr)
    setattr(obj, attr, value)
    try:
        yield
    finally:
        setattr(obj, attr, old)


@contextlib.contextmanager
def fault(kind: str):
    """Break the program's timed path in one of the ways a change could."""
    import jax
    import jax.numpy as jnp

    from repro.common.types import SpmResult
    from repro.core import spm, streaming

    if kind == "none":
        yield
        return
    if kind == "state_unchanged":   # a fold that returns its state as is
        with _patched(streaming, "_accumulate_chunk",
                      lambda carry, *a, **k: carry):
            yield
        return
    if kind == "half_batch":        # half of each chunk, counted twice
        fold = streaming._accumulate_chunk

        def halved(carry, chunk, *a, **k):
            n = chunk.num_records // 2
            chunk = type(chunk)(*(None if c is None else
                                  jnp.concatenate([c[:n], c[:n]])
                                  for c in chunk))
            return fold(carry, chunk, *a, **k)

        with _patched(streaming, "_accumulate_chunk", halved):
            yield
        return
    if kind == "exchange_left_out":  # records stay on the chip they are on
        with _patched(jax.lax, "all_to_all", lambda x, *a, **k: x):
            yield
        return
    if kind == "answer_altered":    # one count off by one where it is made
        finalize = spm.malstone_b

        def altered(hist):
            r = finalize(hist)
            return SpmResult(r.rho, r.total, r.marked.at[1, 1].add(1))

        with _patched(spm, "malstone_b", altered):
            yield
        return
    raise ValueError(f"unknown fault {kind!r}; have {FAULTS}")


def rehearse(name: str, *, trace: bool = False, fault_kind: str = "none",
             seed: int = 2**31 + 11, seconds: float = 0.3,
             root: pathlib.Path = ROOT) -> dict:
    """One tiny run of cell ``name`` on this process's devices. A made-up
    peak stands in for the CPU's, so the roofline reader has a number to
    divide by; nothing of it is a device measurement."""
    import jax

    with _patched(peaks, "PEAKS", {**peaks.PEAKS,
                                   "cpu": {"hbm_bytes_per_s": 1e11}}):
        with fault(fault_kind):
            return harness.run_cell(
                tiny_cell(name, root), seed=seed, seconds=seconds,
                trace=trace, devices=jax.devices(),
                started=time.perf_counter(), log=lambda msg: None)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("workload")
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--fault", default="none", choices=FAULTS)
    ap.add_argument("--root", type=pathlib.Path, default=ROOT)
    args = ap.parse_args()
    print(json.dumps(rehearse(args.workload, trace=bool(args.trace),
                              fault_kind=args.fault, root=args.root)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
