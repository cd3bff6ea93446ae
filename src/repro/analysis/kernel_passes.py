"""Pallas kernel passes: grid/BlockSpec invariants, derived statically.

Each kernel package exports ``analysis_cases()`` — thunks that stage the
public entry points under ``jax.eval_shape`` at production-preset
geometry. Before staging, this module swaps ``pl.pallas_call`` for a
recorder that captures the grid, the Block Specs, and the argument/output
shapes, then returns zeros of ``out_shape`` so tracing continues — no
kernel body ever runs, no array is ever materialized.

From those records four invariants are checked:

- **PK001** — every block shape evenly tiles its array dimension (the
  kernels assume exact tiling; a ragged edge means the wrapper's padding
  contract broke).
- **PK002** — the index map stays in bounds at every grid point: the
  block starting at ``index_map(idx) * block_shape`` must lie entirely
  inside the array.
- **PK003** — the per-grid-step VMEM footprint (sum of all in/out blocks)
  fits the budget. TPU cores have ~16 MB of VMEM; the default budget of
  12 MB leaves room for the compiler's double buffering and kernel
  temporaries, which this block-level bound cannot see.
- **PK004** — no two grid steps write the same output window unless the
  case declares that output as accumulating (init at first visit, then
  read-modify-write — ``segment_hist``'s resident histogram tile and
  ``count_scatter``'s OR-scatter are the declared cases). In blocked
  mode, windows at distinct block indices are disjoint, so a race is
  exactly an undeclared revisit of one window.
"""

from __future__ import annotations

import contextlib
import dataclasses
import itertools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.analysis.findings import Finding
from repro.analysis.registry import AnalysisContext, register_pass

# Above this many grid points the full PK002/PK004 sweep is downgraded to
# a corners-only PK002 check (and noted); all live cases are far below it.
GRID_SWEEP_LIMIT = 500_000

_KERNEL_PACKAGES = ("segment_hist", "count_scatter", "powerlaw_sample",
                    "windowed_ratio")


@dataclasses.dataclass
class PallasCallRecord:
    """One captured ``pl.pallas_call`` invocation."""

    kernel_name: str
    grid: tuple            # normalized to a tuple of ints
    in_specs: list         # BlockSpec per input
    out_specs: list        # BlockSpec per output
    in_shapes: list        # (shape, dtype) per input array
    out_shapes: list       # (shape, dtype) per output array


def _as_list(x):
    if x is None:
        return []
    return list(x) if isinstance(x, (list, tuple)) else [x]


def _kernel_fn_name(kernel) -> str:
    inner = getattr(kernel, "func", kernel)  # unwrap functools.partial
    return getattr(inner, "__name__", repr(kernel))


@contextlib.contextmanager
def record_pallas_calls():
    """Swap ``pl.pallas_call`` for a shape-level recorder.

    Yields the list the records land in. The kernels resolve
    ``pl.pallas_call`` at call time (module attribute lookup), so the
    patch reaches them without touching their code.
    """
    records: list[PallasCallRecord] = []
    real = pl.pallas_call

    def fake_pallas_call(kernel, *, grid=None, in_specs=None, out_specs=None,
                         out_shape=None, **_unused):
        grid_t = (grid,) if isinstance(grid, int) else tuple(grid or ())
        outs = _as_list(out_shape)

        def staged(*args):
            records.append(PallasCallRecord(
                kernel_name=_kernel_fn_name(kernel),
                grid=grid_t,
                in_specs=_as_list(in_specs),
                out_specs=_as_list(out_specs),
                in_shapes=[(tuple(a.shape), a.dtype) for a in args],
                out_shapes=[(tuple(s.shape), jnp.dtype(s.dtype))
                            for s in outs],
            ))
            zeros = [jnp.zeros(s.shape, s.dtype) for s in outs]
            return zeros if isinstance(out_shape, (list, tuple)) else zeros[0]

        return staged

    pl.pallas_call = fake_pallas_call
    try:
        yield records
    finally:
        pl.pallas_call = real


def _block_index(spec, grid_idx):
    """Evaluate a BlockSpec's index map at a Python-int grid point."""
    out = spec.index_map(*grid_idx)
    return tuple(out) if isinstance(out, (tuple, list)) else (out,)


def _grid_corners(grid):
    axes = [(0,) if g <= 1 else (0, g - 1) for g in grid]
    return list(itertools.product(*axes))


def _grid_points(grid):
    return itertools.product(*[range(g) for g in grid])


def _check_record(rec: PallasCallRecord, case_name: str,
                  accumulate: dict, ctx: AnalysisContext) -> list:
    findings = []
    target = f"kernels:{case_name}"
    kname = rec.kernel_name
    acc_outs = set(accumulate.get(kname, ()))

    roles = ([("in", i, spec, shape, dt)
              for i, (spec, (shape, dt))
              in enumerate(zip(rec.in_specs, rec.in_shapes))]
             + [("out", i, spec, shape, dt)
                for i, (spec, (shape, dt))
                in enumerate(zip(rec.out_specs, rec.out_shapes))])

    # whole-array operands outside the blocked VMEM pipeline (SMEM
    # scalars, HBM refs a kernel addresses by DMA) have no block to check
    roles = [r for r in roles if r[2].block_shape is not None]

    # PK001: exact tiling, and PK003: per-step block bytes
    step_bytes = 0
    for role, i, spec, shape, dt in roles:
        block = tuple(spec.block_shape)
        if len(block) != len(shape):
            findings.append(Finding(
                rule="PK001", severity="error", target=target,
                location=f"{kname}/{role}{i}",
                message=(f"block shape {block} has rank {len(block)} but "
                         f"the array is rank {len(shape)} ({shape})"),
                fix_hint="make the BlockSpec rank match the operand"))
            continue
        elems = 1
        for d, (b, s) in enumerate(zip(block, shape)):
            if b is None:  # a squeezed dim: one row per block
                b = 1
            elems *= b
            if b <= 0 or s % b != 0:
                findings.append(Finding(
                    rule="PK001", severity="error", target=target,
                    location=f"{kname}/{role}{i}[dim{d}]",
                    message=(f"block dim {b} does not evenly tile array "
                             f"dim {s} (shape {shape}, block {block}): "
                             f"the ragged last block would read/write "
                             f"padding the wrapper never laid down"),
                    fix_hint="pad the operand to a block multiple in the "
                             "ops.py wrapper"))
        step_bytes += elems * jnp.dtype(dt).itemsize

    if step_bytes > ctx.vmem_budget:
        findings.append(Finding(
            rule="PK003", severity="error", target=target,
            location=f"{kname}/step",
            message=(f"per-grid-step blocks need {step_bytes} bytes of "
                     f"VMEM, over the {ctx.vmem_budget}-byte budget"),
            fix_hint="shrink a tile size or stream the resident operand "
                     "in blocks"))

    # PK002 + PK004 need index-map evaluation over the grid
    n_points = 1
    for g in rec.grid:
        n_points *= max(g, 1)
    full_sweep = n_points <= GRID_SWEEP_LIMIT
    points = list(_grid_points(rec.grid)) if full_sweep \
        else _grid_corners(rec.grid)
    if not full_sweep:
        ctx.note(f"kernels: {target}/{kname} grid has {n_points} points; "
                 f"PK002 checked at corners only, PK004 skipped")

    writers: dict[int, dict[tuple, int]] = {}
    for role, i, spec, shape, _dt in roles:
        block = tuple(1 if b is None else b for b in spec.block_shape)
        if len(block) != len(shape):
            continue  # already a PK001 rank error
        seen_oob = False
        for idx in points:
            bidx = _block_index(spec, idx)
            oob = any(b * w < 0 or b * (w + 1) > s
                      for w, b, s in zip(bidx, block, shape))
            if oob and not seen_oob:
                seen_oob = True
                findings.append(Finding(
                    rule="PK002", severity="error", target=target,
                    location=f"{kname}/{role}{i}",
                    message=(f"index map sends grid point {idx} to block "
                             f"index {bidx}: window "
                             f"{[w * b for w, b in zip(bidx, block)]}+"
                             f"{list(block)} leaves the array bounds "
                             f"{shape}"),
                    fix_hint="clamp or fix the index map; the grid extreme "
                             "is where off-by-one block math surfaces"))
            if role == "out" and full_sweep:
                writers.setdefault(i, {})[bidx] = \
                    writers.setdefault(i, {}).get(bidx, 0) + 1

    if full_sweep:
        for i, windows in writers.items():
            revisits = {w: c for w, c in windows.items() if c > 1}
            if revisits and i not in acc_outs:
                worst = max(revisits.items(), key=lambda kv: kv[1])
                findings.append(Finding(
                    rule="PK004", severity="error", target=target,
                    location=f"{kname}/out{i}",
                    message=(f"{len(revisits)} output window(s) are "
                             f"written by multiple grid steps (e.g. block "
                             f"index {worst[0]} by {worst[1]} steps) and "
                             f"the case does not declare out{i} as "
                             f"accumulating: on a sequential grid later "
                             f"steps silently overwrite earlier ones, and "
                             f"on a parallel grid this is a data race"),
                    fix_hint="either make the revisit an accumulation "
                             "(init at the first visit, read-modify-write "
                             "after) and declare it in analysis_cases(), "
                             "or give each grid step its own window"))
    return findings


def kernel_analysis_cases():
    """All ``analysis_cases()`` from the four kernel packages."""
    import importlib

    cases = []
    for pkg in _KERNEL_PACKAGES:
        mod = importlib.import_module(f"repro.kernels.{pkg}")
        cases.extend(mod.analysis_cases())
    return cases


def check_case(case: dict, ctx: AnalysisContext) -> list:
    """Record one case's pallas calls and run all PK checks on them.

    Exposed separately so tests can feed seeded-bug cases (an OOB index
    map, an undeclared overlapping window) straight in.
    """
    with record_pallas_calls() as records:
        case["stage"]()
    findings = []
    if not records:
        findings.append(Finding(
            rule="PK001", severity="error", target=f"kernels:{case['name']}",
            location="stage",
            message="staging the case recorded no pallas_call at all — "
                    "the entry point routed around the kernel",
            fix_hint="force the pallas impl in the case thunk"))
    for rec in records:
        findings.extend(
            _check_record(rec, case["name"], case.get("accumulate", {}), ctx))
    return findings


@register_pass("pallas-kernels", "kernels",
               ("PK001", "PK002", "PK003", "PK004"),
               doc="grid/BlockSpec invariants for all four kernel packages "
                   "at production-preset geometry")
def kernels_pass(ctx: AnalysisContext) -> list:
    findings = []
    for case in kernel_analysis_cases():
        findings.extend(check_case(case, ctx))
    return findings
