"""The reader of ``sort_roofline``: the counting sort's share of the HBM
roofline, timed by the ops that the compiled job shows to be its two named
Pallas kernels.

The job is the MapReduce cell's, compiled for a described ``v5e:2x2`` mesh
(nothing runs), with the word ordering steered to the kernels as on a TPU.
The topology is described inside a module-scoped fixture, as
``tests/test_tpu_compile.py`` explains. A CPU rehearsal of
``mapreduce.log.x4`` on four virtual devices runs the jnp oracle instead,
and the reader finds nothing there.
"""

import json
import os
import pathlib
import subprocess
import sys
import types

import pytest

TESTS = pathlib.Path(__file__).resolve().parent
BENCH_DIR = TESTS.parent
ROOT = BENCH_DIR.parents[1]
sys.path.insert(0, str(BENCH_DIR))
sys.path.insert(0, str(BENCH_DIR / "metrics"))

import harness  # noqa: E402
import peaks  # noqa: E402
import scopes  # noqa: E402
import sort_roofline  # noqa: E402
import trace_reduce as tr  # noqa: E402

SORT = sort_roofline.read
CELL = "mapreduce.log.x4"
CHUNK, CHUNKS = 4096, 2         # records per chunk and chip; chunks per chip


@pytest.fixture(scope="module")
def described():
    """The cell's job at a small size, compiled for a described v5e:2x2,
    and the context the reader would be given for it."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import NamedSharding, PartitionSpec as P

    import repro.kernels.count_scatter.ops as count_scatter_ops
    from repro.common.types import EventLog
    from repro.launch.mesh import make_mesh

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cell = harness.load_cell(ROOT, CELL)
    cell.config.update(num_sites=1024, chunk_records=CHUNK,
                       records_per_node=CHUNKS * CHUNK)
    mesh = make_mesh((4,), ("data",), devices=topo.devices)
    sharding = NamedSharding(mesh, P("data"))
    n = 4 * CHUNKS * CHUNK
    source = types.SimpleNamespace(
        program_input=EventLog(*(
            jax.ShapeDtypeStruct((n,), dtype, sharding=sharding)
            for dtype in (jnp.int32,) * 4 + (jnp.uint32,) * 2)),
        chunk_records=CHUNK, chunks_per_chip=CHUNKS, run_kwargs={})
    plan = harness.exchange_plan(cell.config)

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    jax.clear_caches()
    try:
        with pytest.MonkeyPatch.context() as mp:
            mp.setattr(count_scatter_ops, "resolve_interpret",
                       lambda interpret=None: bool(interpret))
            job = harness.compile_job(cell, mesh, source, plan)
    finally:
        jax.clear_caches()
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    ctx = types.SimpleNamespace(cell=cell, mesh=mesh, source=source,
                                plan=plan, device_kind="TPU v5 lite",
                                summary=None, traced_jobs=2)
    return job, ctx


def _summary(top_ops):
    return tr.Summary(window_s=1.0, busy_s={0: 1.0}, collective_s={},
                      scatter_s={}, top_ops=top_ops, gaps=[])


def _label(name, hlo):
    """A ``top_ops`` label, as the trace reduction writes it."""
    path = hlo[name].op_name.split("/")[-3:]
    return f"{name} {'/'.join(path)}"


@pytest.fixture
def job_in_reader(described, monkeypatch):
    job, ctx = described
    asked = []
    monkeypatch.setattr(harness, "compile_job",
                        lambda *a: asked.append(a) or job)
    return job, ctx, asked


def test_kernels_are_the_exchanges_named_custom_calls(described):
    job, _ = described
    text = job.as_text()
    kernels = sort_roofline.kernel_instructions(text)
    hlo, owner = tr.parse_hlo(text), scopes.instruction_scopes(text)
    assert {hlo[k].op_name.split("/")[-2] for k in kernels} == \
        {"count_tiles", "scatter_tiles"}
    assert {owner[k] for k in kernels} == {"malstone.exchange"}
    assert all(hlo[k].opcode == "custom-call" for k in kernels)


def test_reader_takes_exactly_the_kernels_time(job_in_reader):
    job, ctx, asked = job_in_reader
    text = job.as_text()
    hlo = tr.parse_hlo(text)
    kernels = sorted(sort_roofline.kernel_instructions(text))
    owner = scopes.instruction_scopes(text)
    # the exchange's other ops, a collective among them, and the reducer's
    others = [n for n in hlo
              if n not in kernels and hlo[n].opcode not in tr.CONTAINERS
              and owner[n] in ("malstone.exchange", "malstone.combine")]
    assert any(tr.is_collective(hlo[n]) for n in others)
    top = [(_label(n, hlo), 0.25) for n in others[:6]]
    top += [(_label(kernels[0], hlo), 0.003),
            (_label(kernels[1], hlo), 0.009)]
    ctx.summary = _summary(top)
    slots = 2 * CHUNKS * CHUNK
    want = 100 * (12 * slots / peaks.PEAKS["TPU v5 lite"]["hbm_bytes_per_s"]
                  ) / 0.012
    assert SORT(ctx) == pytest.approx(want)
    assert asked == [(ctx.cell, ctx.mesh, ctx.source, ctx.plan)]


def test_bytes_count_the_chunks_slots():
    min_bytes = sort_roofline.min_bytes
    assert min_bytes(1) == 12
    assert min_bytes(3000) == 36000       # not the 3 x 1024 padded slots


def test_reader_reads_nothing_without_the_kernels(job_in_reader):
    job, ctx, _ = job_in_reader
    hlo = tr.parse_hlo(job.as_text())
    kernels = sort_roofline.kernel_instructions(job.as_text())
    others = [n for n in hlo if n not in kernels][:5]
    ctx.summary = _summary([(_label(n, hlo), 0.5) for n in others])
    assert SORT(ctx) is None
    traced = _summary([(_label(k, hlo), 0.5) for k in kernels])
    assert SORT(types.SimpleNamespace(**{**vars(ctx), "summary": None})) \
        is None
    assert SORT(types.SimpleNamespace(**{**vars(ctx), "summary": traced,
                                         "traced_jobs": 0})) is None


def test_cpu_rehearsal_of_the_cell_reads_nothing():
    """On four virtual CPU devices the exchange crosses devices and the
    trace has its collectives, but the words are ordered by the jnp oracle:
    no named kernel ran, so the line leaves the share out."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = os.pathsep.join(
        [str(ROOT / "src")] + [p for p in [env.get("PYTHONPATH")] if p])
    out = subprocess.run(
        [sys.executable, str(TESTS / "rehearsal.py"), CELL, "--trace", "1"],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    result = json.loads(out.stdout.strip().splitlines()[-1])
    assert result["correct"] is True
    assert result["device"]["count"] == 4
    assert result["metrics"]["collective_share"]["value"] > 0
    assert "sort_roofline" not in result["metrics"]
