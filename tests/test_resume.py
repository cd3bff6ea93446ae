"""Crash-recovery tests for the resumable streaming driver.

The contract under test: a run interrupted at ANY point — a segment
boundary, or mid-checkpoint-write with shard files on disk and no commit
marker — and then resumed is **bit-identical** to an uninterrupted run:
same histogram, same statistic, same accumulated ShuffleStats. Every
assertion is assert_array_equal, never allclose.

In-process tests use ``kill_mode="raise"`` (``SimulatedKill``) so the whole
backend x segment-size matrix runs without process death; the real
``os._exit`` crash windows run in subprocesses via
tests/md_scripts/resume_crash_check.py (2 forced host devices), which also
cross-checks the resumable result against BOTH engines (one-shot and
streaming).
"""

import os
import pathlib
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.core import malstone_run_streaming
from repro.core.resume import ResumableRunner
from repro.faults import FaultPlan, SimulatedKill
from repro.launch.mesh import make_mesh
from repro.malgen import MalGenConfig, make_seed_streaming

HERE = pathlib.Path(__file__).parent
SRC = str(HERE.parent / "src")

BACKENDS = ("streams", "sphere", "mapreduce", "mapreduce_combiner")

CFG = MalGenConfig(num_sites=301, num_entities=1000,
                   marked_site_fraction=0.2, marked_event_fraction=0.3)
NUM_CHUNKS, CHUNK = 8, 512


@pytest.fixture(scope="module")
def mesh():
    return make_mesh((1,), ("data",))


@pytest.fixture(scope="module")
def seed():
    return make_seed_streaming(jax.random.key(7), CFG, NUM_CHUNKS, CHUNK)


def _runner(seed, mesh, backend, segment_chunks, **kw):
    return ResumableRunner(
        seed, CFG, mesh=mesh, num_chunks=NUM_CHUNKS, chunk_records=CHUNK,
        segment_chunks=segment_chunks, backend=backend, statistic="B", **kw)


def _reference(seed, mesh, backend):
    return malstone_run_streaming(
        seed, CFG.num_sites, mesh=mesh, backend=backend, chunk_records=CHUNK,
        statistic="B", cfg=CFG, num_chunks=NUM_CHUNKS,
        return_shuffle_stats=True)


def assert_outcome_equal(out, ref, ref_stats, msg=""):
    np.testing.assert_array_equal(np.asarray(out.result.total),
                                  np.asarray(ref.total), err_msg=msg)
    np.testing.assert_array_equal(np.asarray(out.result.marked),
                                  np.asarray(ref.marked), err_msg=msg)
    np.testing.assert_array_equal(np.asarray(out.result.rho),
                                  np.asarray(ref.rho), err_msg=msg)
    if ref_stats is not None:
        assert out.shuffle_stats is not None, msg
        for f in ref_stats._fields:
            np.testing.assert_array_equal(
                np.asarray(getattr(out.shuffle_stats, f)),
                np.asarray(getattr(ref_stats, f)),
                err_msg=f"{msg}: ShuffleStats.{f}")


# ------------------------------------------------------------- bit identity
@pytest.mark.parametrize("segment_chunks", [1, 3, 8])
@pytest.mark.parametrize("backend", BACKENDS)
def test_segmented_run_bit_identical(mesh, seed, backend, segment_chunks):
    # K=3 over 8 chunks/device exercises the uneven final segment (3+3+2)
    ref, ref_stats = _reference(seed, mesh, backend)
    out = _runner(seed, mesh, backend, segment_chunks).run()
    assert_outcome_equal(out, ref, ref_stats,
                         msg=f"{backend} K={segment_chunks}")
    rep = out.report
    assert rep.segments_run == rep.segments_total
    assert rep.chunks_processed == NUM_CHUNKS
    assert rep.chunks_skipped == 0 and rep.resumed_from_step is None


@pytest.mark.parametrize("backend", ("streams", "mapreduce"))
def test_checkpointed_then_fully_resumed(mesh, seed, backend, tmp_path):
    ref, ref_stats = _reference(seed, mesh, backend)
    runner = _runner(seed, mesh, backend, 2)
    first = runner.run(checkpoint_dir=str(tmp_path))
    assert_outcome_equal(first, ref, ref_stats, msg=f"{backend} checkpointed")
    # a second run over a complete checkpoint regenerates NOTHING
    again = runner.run(checkpoint_dir=str(tmp_path))
    assert_outcome_equal(again, ref, ref_stats, msg=f"{backend} resumed")
    assert again.report.segments_run == 0
    assert again.report.chunks_processed == 0
    assert again.report.chunks_skipped == NUM_CHUNKS
    assert again.report.resumed_from_step == first.report.segments_total


@pytest.mark.parametrize("backend", ("streams", "mapreduce"))
def test_simulated_kill_at_boundary_then_resume(mesh, seed, backend,
                                                tmp_path):
    ref, ref_stats = _reference(seed, mesh, backend)
    runner = _runner(seed, mesh, backend, 2)
    with pytest.raises(SimulatedKill):
        runner.run(checkpoint_dir=str(tmp_path),
                   faults=FaultPlan(kill_at_segment=2, kill_mode="raise"))
    out = runner.run(checkpoint_dir=str(tmp_path))
    assert_outcome_equal(out, ref, ref_stats, msg=f"{backend} kill+resume")
    rep = out.report
    assert rep.resumed_from_step == 2
    assert rep.chunks_skipped == 4 and rep.chunks_processed == 4


@pytest.mark.parametrize("backend", ("streams", "mapreduce"))
def test_simulated_midckpt_kill_then_resume(mesh, seed, backend, tmp_path):
    # the crash window: shard files written into the tmp dir, commit
    # marker never placed — the torn step must be invisible to resume
    ref, ref_stats = _reference(seed, mesh, backend)
    runner = _runner(seed, mesh, backend, 2)
    with pytest.raises(SimulatedKill):
        runner.run(checkpoint_dir=str(tmp_path),
                   faults=FaultPlan(kill_mid_checkpoint_step=2,
                                    kill_mode="raise"))
    names = sorted(p.name for p in tmp_path.iterdir())
    assert any(n.startswith(".tmp_step_2_") for n in names), names
    assert "step_00000001.COMMITTED" in names
    assert "step_00000002.COMMITTED" not in names

    out = runner.run(checkpoint_dir=str(tmp_path))
    assert_outcome_equal(out, ref, ref_stats, msg=f"{backend} midckpt")
    assert out.report.resumed_from_step == 1
    assert out.report.chunks_skipped == 2
    # the torn tmp dir was swept on manager init
    left = sorted(p.name for p in tmp_path.iterdir())
    assert not any(n.startswith(".tmp_") for n in left), left


def test_resume_refuses_other_runs_checkpoint(mesh, seed, tmp_path):
    _runner(seed, mesh, "streams", 2).run(checkpoint_dir=str(tmp_path))
    other = _runner(seed, mesh, "sphere", 2)
    with pytest.raises(ValueError, match="different run configuration"):
        other.run(checkpoint_dir=str(tmp_path))


def test_resume_false_recomputes(mesh, seed, tmp_path):
    runner = _runner(seed, mesh, "streams", 2)
    runner.run(checkpoint_dir=str(tmp_path))
    out = runner.run(checkpoint_dir=str(tmp_path), resume=False)
    assert out.report.resumed_from_step is None
    assert out.report.chunks_processed == NUM_CHUNKS


def test_constructor_validation(mesh, seed):
    with pytest.raises(ValueError, match="unknown streaming backend"):
        _runner(seed, mesh, "nope", 1)
    with pytest.raises(ValueError, match="segment_chunks"):
        _runner(seed, mesh, "streams", 0)
    with pytest.raises(ValueError, match="segment_chunks"):
        _runner(seed, mesh, "streams", NUM_CHUNKS + 1)


def test_recovery_report_derived_keys(mesh, seed):
    out = _runner(seed, mesh, "streams", 4).run()
    d = out.report.to_derived()
    for key in ("segments_total", "segments_run", "segments_retried",
                "resumed_from_step", "chunks_processed", "chunks_skipped",
                "checkpoint_save_ms", "checkpoint_restore_ms",
                "fault_events", "alarmed_hosts", "rerouted_shards"):
        assert key in d, key
    assert d["resumed_from_step"] == -1  # json-friendly sentinel


# ----------------------------------------------------- subprocess crashes
def _run_crash_script(args, expect_rc, timeout=600):
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, str(HERE / "md_scripts" / "resume_crash_check.py"),
         *map(str, args)],
        capture_output=True, text=True, timeout=timeout, env=env)
    assert proc.returncode == expect_rc, (
        f"rc={proc.returncode}, wanted {expect_rc}\n"
        f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr[-4000:]}")
    return proc.stdout


@pytest.fixture(scope="module")
def crash_reference(tmp_path_factory):
    """Per-backend uninterrupted reference npz (computed once; the
    reference phase itself cross-checks vs both engines)."""
    root = tmp_path_factory.mktemp("crash_ref")
    cache = {}

    def get(backend):
        if backend not in cache:
            npz = root / f"ref_{backend}.npz"
            out = _run_crash_script([backend, "reference", "-", npz], 0)
            assert "REFERENCE_OK" in out
            cache[backend] = npz
        return cache[backend]

    return get


@pytest.mark.slow
@pytest.mark.parametrize("kill_phase", ("kill_boundary", "kill_midckpt"))
@pytest.mark.parametrize("backend", ("streams", "mapreduce"))
def test_crash_and_resume_subprocess(crash_reference, backend, kill_phase,
                                     tmp_path):
    ref = np.load(crash_reference(backend))
    ckpt = tmp_path / "ckpt"

    # the kill fires: hard os._exit(17), no cleanup
    _run_crash_script([backend, kill_phase, ckpt, "-"], 17)
    committed = sorted(p.name for p in ckpt.iterdir()
                       if p.name.endswith(".COMMITTED"))
    assert committed, "kill fired before any checkpoint committed"
    if kill_phase == "kill_midckpt":
        # torn write: tmp dir on disk, step 2 never committed
        names = sorted(p.name for p in ckpt.iterdir())
        assert any(n.startswith(".tmp_step_2_") for n in names), names
        assert "step_00000002.COMMITTED" not in names

    out_npz = tmp_path / "resumed.npz"
    stdout = _run_crash_script([backend, "resume", ckpt, out_npz], 0)
    assert "RESUMED_FROM=" in stdout
    got = np.load(out_npz)
    assert set(got.files) == set(ref.files)
    for name in ref.files:
        np.testing.assert_array_equal(
            got[name], ref[name],
            err_msg=f"{backend}/{kill_phase}: {name} not bit-identical "
                    f"after crash+resume")
