from repro.common.env import force_host_devices
force_host_devices(512)

# ^ MUST run first: XLA locks the device count when the backend first
# initializes. Everything below (including jax-touching imports) is safe —
# the backend only comes up at the first device query.

"""Multi-pod dry-run (deliverable e).

For every (architecture x input shape) cell, on BOTH production meshes
(single-pod 16x16 and multi-pod 2x16x16):

    lowered  = jax.jit(step, in_shardings=...).lower(**input_specs(...))
    compiled = lowered.compile()
    print(compiled.memory_analysis())   # proves it fits
    print(compiled.cost_analysis())     # FLOPs/bytes for the roofline

plus the paper's own workload (malstone_step over the same meshes).
Results (memory, flops, collective-bytes parsed from HLO) are persisted to
results/dryrun/<cell>.json.

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3-8b \
        --shape train_4k [--multi-pod] [--all] [--out results/dryrun]
"""

import argparse
import json
import pathlib
import sys
import time
import traceback

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ALIASES, get_config
from repro.launch.mesh import batch_axes, make_production_mesh
from repro.models import steps as S
from repro.models.sharding import param_shardings, sharding_ctx
from repro.models.steps import SHAPES, input_specs, shape_applicable
from repro.optim import AdamWConfig

# grok's optimizer state only fits a single 256-chip pod with bf16 moments
# (DESIGN.md §6); everything else uses fp32 moments.
MOMENT_DTYPE = {"grok-1-314b": "bfloat16"}


def _opt_cfg(cfg):
    return AdamWConfig(moment_dtype=MOMENT_DTYPE.get(cfg.name, "float32"))


def batch_shardings(spec_tree, mesh, global_batch: int, baxes=None):
    """Shard the leading dim equal to global_batch over (pod, data) — or
    the explicitly supplied axes (e.g. full-DP hillclimbs put small models'
    batch over (pod, data, model)); replicate everything else."""
    baxes = tuple(a for a in (baxes or batch_axes(mesh)) if a in mesh.shape)
    bsize = 1
    for a in baxes:
        bsize *= mesh.shape[a]

    def one(leaf):
        shape = leaf.shape
        if (global_batch > 1 and shape and shape[0] == global_batch
                and global_batch % bsize == 0):
            return NamedSharding(mesh, P(baxes))
        if (global_batch > 1 and len(shape) >= 2
                and shape[0] != global_batch and shape[1] == global_batch
                and global_batch % bsize == 0):
            # stacked-layer cache leaves: [R, B, ...]
            return NamedSharding(mesh, P(None, baxes))
        return NamedSharding(mesh, P())

    return jax.tree.map(one, spec_tree)


def state_shardings(cfg, mesh, with_opt: bool):
    """NamedShardings for TrainState (params + optimizer moments share the
    param layout; the step counter is replicated)."""
    axes = S.params_axes(cfg)
    pspecs = S.params_specs(cfg, with_opt=with_opt,
                            opt_cfg=_opt_cfg(cfg) if with_opt else None)
    if not with_opt:
        return param_shardings(pspecs, axes, mesh)
    params_sh = param_shardings(pspecs.params, axes, mesh)
    mu_sh = param_shardings(pspecs.opt.mu, axes, mesh)
    nu_sh = param_shardings(pspecs.opt.nu, axes, mesh)
    from repro.models.steps import TrainState
    from repro.optim import OptState
    return TrainState(
        params=params_sh,
        opt=OptState(step=NamedSharding(mesh, P()), mu=mu_sh, nu=nu_sh))


from repro.analysis.hlo import analyze as analyze_hlo


def build_lowerable(cfg, shape_name: str, mesh, baxes=None):
    """Returns (fn, example_args, in_shardings) for the cell's step."""
    sh = SHAPES[shape_name]
    ispec = input_specs(cfg, shape_name)
    global batch_shardings
    if baxes:
        _orig = batch_shardings

    if sh.kind == "train":
        opt_cfg = _opt_cfg(cfg)
        st_spec = S.params_specs(cfg, with_opt=True, opt_cfg=opt_cfg)
        st_sh = state_shardings(cfg, mesh, with_opt=True)
        b_sh = batch_shardings(ispec, mesh, sh.global_batch, baxes)
        step = S.make_train_step(cfg, opt_cfg)
        return step, (st_spec, ispec), (st_sh, b_sh)

    if sh.kind == "prefill":
        p_spec = S.params_specs(cfg, with_opt=False)
        p_sh = state_shardings(cfg, mesh, with_opt=False)
        b_sh = batch_shardings(ispec, mesh, sh.global_batch)
        prefix = cfg.num_patches if cfg.family == "vlm" else 0
        step = S.make_prefill_step(cfg, max_len=sh.seq_len + prefix + 8)
        return step, (p_spec, ispec), (p_sh, b_sh)

    # decode
    p_spec = S.params_specs(cfg, with_opt=False)
    p_sh = state_shardings(cfg, mesh, with_opt=False)
    tok_spec, cache_spec = ispec["token"], ispec["cache"]
    tok_sh = batch_shardings(tok_spec, mesh, sh.global_batch)
    cache_sh = batch_shardings(cache_spec, mesh, sh.global_batch)
    dstep = S.make_decode_step(cfg)
    if cfg.is_encoder_decoder:
        enc_spec = ispec["enc_out"]
        enc_sh = batch_shardings(enc_spec, mesh, sh.global_batch)
        return (dstep, (p_spec, tok_spec, cache_spec, enc_spec),
                (p_sh, tok_sh, cache_sh, enc_sh))
    return dstep, (p_spec, tok_spec, cache_spec), (p_sh, tok_sh, cache_sh)


def _parse_overrides(items):
    out = []
    for it in items or ():
        name, _, ax = it.partition("=")
        if ax.lower() in ("none", ""):
            val = None
        elif "," in ax:
            val = tuple(a for a in ax.split(",") if a)
        else:
            val = ax
        out.append((name, val))
    return tuple(out)


def run_cell(arch_id: str, shape_name: str, multi_pod: bool,
             out_dir: pathlib.Path, verbose: bool = True,
             param_overrides=(), act_overrides=(), q_chunk: int = 0) -> dict:
    cell = f"{arch_id}__{shape_name}__{'pod2' if multi_pod else 'pod1'}"
    out_path = out_dir / f"{cell}.json"
    cfg = get_config(arch_id)
    if q_chunk:
        import dataclasses as _dc
        cfg = _dc.replace(cfg, attn_q_chunk=q_chunk)

    ok, reason = shape_applicable(cfg, shape_name)
    if not ok:
        result = {"cell": cell, "status": "skipped", "reason": reason}
        out_path.write_text(json.dumps(result, indent=2))
        return result

    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    try:
        with sharding_ctx(
                mesh,
                param_overrides=tuple(cfg.sharding_rules) + tuple(
                    param_overrides),
                act_overrides=tuple(cfg.act_sharding_rules) + tuple(
                    act_overrides)):
            bx = None
            for nm, val in act_overrides:
                if nm == "batch":
                    bx = (val,) if isinstance(val, str) else val
            fn, args, shardings = build_lowerable(cfg, shape_name, mesh,
                                                  baxes=bx)
            with mesh:
                lowered = jax.jit(fn, in_shardings=shardings).lower(*args)
                t_lower = time.time() - t0
                t1 = time.time()
                compiled = lowered.compile()
                t_compile = time.time() - t1
                print(compiled.memory_analysis(), flush=True)
                ma = compiled.memory_analysis()
                mem = {k: int(getattr(ma, k)) for k in (
                    "argument_size_in_bytes", "output_size_in_bytes",
                    "temp_size_in_bytes", "generated_code_size_in_bytes",
                    "alias_size_in_bytes") if hasattr(ma, k)}
                cost = compiled.cost_analysis()
                if isinstance(cost, list):
                    cost = cost[0] if cost else {}
                cost = {k: float(v) for k, v in dict(cost).items()
                        if isinstance(v, (int, float))}
                print({k: cost.get(k) for k in ("flops", "bytes accessed")},
                      flush=True)
                # trip-count-aware per-device analysis of the post-SPMD HLO
                # (cost_analysis counts scan bodies once — see hlo_analysis)
                hlo_summary = analyze_hlo(compiled.as_text())
                coll = hlo_summary["collectives"]
    except Exception as e:
        result = {"cell": cell, "status": "error",
                  "error": f"{type(e).__name__}: {e}",
                  "traceback": traceback.format_exc()[-3000:]}
        out_path.write_text(json.dumps(result, indent=2))
        if verbose:
            print(f"[FAIL] {cell}: {e}", flush=True)
        return result

    result = {
        "cell": cell,
        "arch": arch_id,
        "shape": shape_name,
        "multi_pod": multi_pod,
        "status": "ok",
        "num_devices": int(mesh.size),
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory_analysis": mem,
        "cost_analysis": {k: cost.get(k) for k in
                          ("flops", "bytes accessed", "transcendentals")
                          if k in cost},
        # per-device, trip-count-aware (primary roofline inputs):
        "hlo_flops_per_device": hlo_summary["flops"],
        "hlo_hbm_bytes_per_device": hlo_summary["hbm_bytes"],
        "collectives": coll,
        "model_params_total": cfg.num_params_total,
        "model_params_active": cfg.num_params_active,
    }
    out_path.write_text(json.dumps(result, indent=2))
    if verbose:
        print(f"[OK] {cell}: compile={t_compile:.1f}s "
              f"hlo_flops/dev={hlo_summary['flops']:.3g} "
              f"coll={coll.get('total_bytes', 0):.3g}B "
              f"temp={mem.get('temp_size_in_bytes', 0):.3g}B", flush=True)
    return result


MALSTONE_CLASSES = {
    # paper Table 2: B-10 = 10 billion 100-byte records (1 TB)
    "B10": dict(num_records=10_000_000_000, num_sites=120_000,
                statistic="B"),
    "A10": dict(num_records=10_000_000_000, num_sites=120_000,
                statistic="A"),
}


def run_malstone_cell(backend: str, klass: str, multi_pod: bool,
                      out_dir: pathlib.Path) -> dict:
    """Dry-run the paper's own workload on the production mesh."""
    from repro.core.runner import malstone_lowerable
    cell = f"malstone-{klass}-{backend}__{'pod2' if multi_pod else 'pod1'}"
    out_path = out_dir / f"{cell}.json"
    mesh = make_production_mesh(multi_pod=multi_pod)
    axes = tuple(a for a in ("pod", "data", "model") if a in mesh.shape)
    spec = MALSTONE_CLASSES[klass]
    t0 = time.time()
    try:
        fn, log_sds = malstone_lowerable(
            spec["num_records"], spec["num_sites"], mesh=mesh,
            backend=backend, statistic=spec["statistic"], axis_name=axes)
        with mesh:
            lowered = jax.jit(fn).lower(log_sds)
            t1 = time.time()
            compiled = lowered.compile()
            t_compile = time.time() - t1
            print(compiled.memory_analysis(), flush=True)
            ma = compiled.memory_analysis()
            mem = {k: int(getattr(ma, k)) for k in (
                "argument_size_in_bytes", "output_size_in_bytes",
                "temp_size_in_bytes") if hasattr(ma, k)}
            hlo_summary = analyze_hlo(compiled.as_text())
    except Exception as e:
        result = {"cell": cell, "status": "error",
                  "error": f"{type(e).__name__}: {e}",
                  "traceback": traceback.format_exc()[-3000:]}
        out_path.write_text(json.dumps(result, indent=2))
        print(f"[FAIL] {cell}: {e}", flush=True)
        return result
    result = {
        "cell": cell, "status": "ok", "arch": "malstone",
        "backend": backend, "klass": klass, "multi_pod": multi_pod,
        "num_devices": int(mesh.size),
        "records_global": spec["num_records"],
        "num_sites": spec["num_sites"],
        "compile_s": round(t_compile, 2),
        "memory_analysis": mem,
        "hlo_flops_per_device": hlo_summary["flops"],
        "hlo_hbm_bytes_per_device": hlo_summary["hbm_bytes"],
        "collectives": hlo_summary["collectives"],
    }
    out_path.write_text(json.dumps(result, indent=2))
    coll = hlo_summary["collectives"]
    print(f"[OK] {cell}: compile={t_compile:.1f}s "
          f"coll={coll.get('total_bytes', 0):.3g}B "
          f"hbm={hlo_summary['hbm_bytes']:.3g}B", flush=True)
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None,
                    help="arch id (dashed or underscored); default: all")
    ap.add_argument("--shape", default=None, choices=list(SHAPES),
                    help="input shape; default: all four")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--malstone", action="store_true",
                    help="also dry-run the paper's workload (3 backends)")
    ap.add_argument("--malstone-only", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--param-override", action="append", default=[],
                    help="logical=axis rule override (axis 'none' to drop)")
    ap.add_argument("--act-override", action="append", default=[])
    ap.add_argument("--q-chunk", type=int, default=0,
                    help="override attention q_chunk (seq-parallel align)")
    args = ap.parse_args()
    p_over = _parse_overrides(args.param_override)
    a_over = _parse_overrides(args.act_override)

    out_dir = pathlib.Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)

    archs = [args.arch] if args.arch else list(ALIASES)
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    failures = 0
    if not args.malstone_only:
        for arch in archs:
            for shape in shapes:
                for mp in meshes:
                    cell = (f"{arch}__{shape}__{'pod2' if mp else 'pod1'}")
                    path = out_dir / f"{cell}.json"
                    if args.skip_existing and path.exists():
                        prev = json.loads(path.read_text())
                        if prev.get("status") in ("ok", "skipped"):
                            print(f"[SKIP-CACHED] {cell}", flush=True)
                            continue
                    res = run_cell(arch, shape, mp, out_dir,
                                   param_overrides=p_over,
                                   act_overrides=a_over,
                                   q_chunk=args.q_chunk)
                    if res["status"] == "error":
                        failures += 1
    if args.malstone or args.malstone_only:
        for backend in ("streams", "sphere", "mapreduce",
                        "mapreduce_combiner"):
            for mp in meshes:
                cell = (f"malstone-B10-{backend}__"
                        f"{'pod2' if mp else 'pod1'}")
                path = out_dir / f"{cell}.json"
                if args.skip_existing and path.exists():
                    prev = json.loads(path.read_text())
                    if prev.get("status") == "ok":
                        print(f"[SKIP-CACHED] {cell}", flush=True)
                        continue
                res = run_malstone_cell(backend, "B10", mp, out_dir)
                if res["status"] == "error":
                    failures += 1
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
