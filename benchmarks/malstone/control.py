"""The controls of the comparison that decides ``correct``: the plain
reference put in the program's place at a lower precision than the
configuration states, once with its ratio in bfloat16 (below float32) and
once with its counts summed in float32 (below int32). Each has to come out
as not correct on every seed.

    python3 benchmarks/malstone/control.py --workload streams.seed \\
        --seed 1 --seed 2 --seed 3

Builds the cell's records at the cell's own size on this machine's chips,
counts them with the reference, and prints one JSON line per seed with,
for each control, the numbers compared, and whether it passed the limits.
The benchmark's own runs never run it.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parents[1]


def readings(cell, seed: int, devices) -> dict:
    """The controls' numbers for one seed, beside the limits, and the
    largest cumulative count."""
    import harness
    import reference
    import sources
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((cell.chips,), (sources.AXIS,),
                     devices=list(devices)[:cell.chips])
    source = sources.make_source(cell.traffic, cell.config, mesh, seed)
    counts = reference.count_keys(source.reference_key_blocks(),
                                  cell.config["num_sites"],
                                  cell.config["num_weeks"])
    want = reference.malstone_b(counts)
    out = {"seed": seed, "limits": harness.LIMITS,
           "largest_total": int(want.total.max())}
    for name, control in reference.CONTROLS.items():
        numbers = reference.compare(control(counts), want)
        out[name] = {"numbers": numbers, "passed": all(
            numbers[k] <= v for k, v in harness.LIMITS.items())}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, action="append", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    import bench
    import harness

    cell = harness.load_cell(ROOT, args.workload)
    devices = bench.chips_or_none(cell.chips)
    if devices is None:
        return 1
    bench.enable_compile_cache()
    for seed in args.seed:
        print(json.dumps(readings(cell, seed, devices)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
