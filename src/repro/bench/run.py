"""Bench runner CLI — sweep the scenario registry, emit BENCH_<name>.json.

    PYTHONPATH=src python -m repro.bench.run --preset smoke
    PYTHONPATH=src python -m repro.bench.run --scenario malstone_b_sphere_oneshot
    PYTHONPATH=src python -m repro.bench.run --list

Output: ``BENCH_<name>.json`` (default name = preset) at the repo root,
conforming to ``repro.bench.schema``; plus the historical
``name,us_per_call,derived`` CSV rows on stdout so existing tooling keeps
parsing. Compare two runs with ``python -m repro.bench.compare``.

``--nodes N`` forces N host devices for the mesh sweeps (must be set
before jax initializes — this module preparses it like
``repro.launch.malstone``). Default 2 so ``sweep_mesh_p2`` and both
engines exercise real collectives even on a single-CPU container.
"""

from __future__ import annotations

import argparse
import sys

from repro.bench import force_host_devices, preparse_nodes

if __name__ == "__main__":
    force_host_devices(preparse_nodes())

import time  # noqa: E402

import jax  # noqa: E402

from repro.bench import registry, schema  # noqa: E402
from repro.common.env import enable_compile_cache  # noqa: E402


def _csv_row(entry: dict) -> str:
    derived = ""
    if "records_per_s" in entry:
        derived = f"{entry['records_per_s']:.4g}_records_per_s"
    elif entry.get("derived"):
        k, v = next(iter(entry["derived"].items()))
        derived = f"{v:.4g}_{k}" if isinstance(v, float) else f"{v}_{k}"
    return f"{entry['scenario']},{entry['us_per_call']:.1f},{derived}"


def run_scenarios(names, scale, ctx, doc, *, verbose=True):
    """Run each named scenario, append to ``doc``; return skipped names."""
    skipped = []
    for sc in registry.iter_scenarios(names):
        t0 = time.perf_counter()
        try:
            res = sc.run(scale, ctx)
        except registry.ScenarioSkip as e:
            skipped.append(sc.name)
            if verbose:
                print(f"# skip {sc.name}: {e}", flush=True)
            continue
        # provenance: scale defaults, then the grid point, then whatever
        # the scenario actually ran with (sweeps override nodes/records)
        params = scale.as_params()
        params["nodes"] = ctx.nodes
        params.update(sc.params)
        params.update(res.effective or {})
        entry = schema.add_result(doc, sc.name, params, res.timing,
                                  records=res.records, derived=res.derived)
        if verbose:
            wall = time.perf_counter() - t0
            print(f"{_csv_row(entry)}  # wall {wall:.1f}s "
                  f"steady={res.timing.steady}", flush=True)
    return skipped


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="repro.bench.run", description=__doc__)
    ap.add_argument("--preset", default="smoke",
                    choices=sorted(registry.PRESETS))
    ap.add_argument("--scenario", action="append", metavar="NAME",
                    help="run only these scenarios (repeatable); default = "
                         "the preset's selection")
    ap.add_argument("--name", default=None,
                    help="document name -> BENCH_<name>.json (default: "
                         "the preset name)")
    ap.add_argument("--out", default=None,
                    help="explicit output path (overrides --name placement)")
    ap.add_argument("--nodes", type=int, default=2,
                    help="forced host device count for the data mesh")
    ap.add_argument("--list", action="store_true",
                    help="list scenario names (with the preset's selection "
                         "marked) and exit")
    args = ap.parse_args(argv)
    enable_compile_cache()

    selected = set(registry.preset_scenario_names(args.preset))
    if args.list:
        for name, sc in registry.SCENARIOS.items():
            mark = "*" if name in selected else " "
            print(f"{mark} {name:42s} [{sc.group}]")
        print(f"\n* = in --preset {args.preset} selection "
              f"({len(selected)}/{len(registry.SCENARIOS)})")
        return 0

    names = args.scenario if args.scenario else sorted(selected)
    scale = registry.PRESETS[args.preset]
    ctx = registry.BenchContext(nodes=min(args.nodes, jax.device_count()))
    doc = schema.new_document(args.name or args.preset, preset=args.preset)

    print("name,us_per_call,derived")
    skipped = run_scenarios(names, scale, ctx, doc)
    if not doc["results"]:
        print("error: no scenario produced a result", file=sys.stderr)
        return 2
    path = schema.write_document(
        doc, path=args.out if args.out else None)
    print(f"# wrote {path} ({len(doc['results'])} scenarios, "
          f"{len(skipped)} skipped)", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
