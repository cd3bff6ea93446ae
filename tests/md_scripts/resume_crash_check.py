"""Crash/resume worker for the fault-tolerance tests — run as a subprocess
with 2 forced host devices so a hard ``os._exit`` kill never takes the
pytest process down (tests/test_resume.py drives this).

    resume_crash_check.py BACKEND PHASE CKPT_DIR OUT_NPZ

Phases:

- ``reference``  — uninterrupted resumable run; cross-checks it bit-exactly
  against BOTH engines (one-shot ``malstone_run`` and streaming
  ``malstone_run_streaming``) and writes the result arrays to OUT_NPZ.
- ``kill_boundary`` — run with a checkpoint dir and a hard kill (exit 17)
  at the segment-2 boundary: steps 1..2 are committed, the process dies.
- ``kill_midckpt``  — hard kill inside the checkpoint writer's crash
  window while saving step 2: shard files written into the tmp dir, no
  commit marker — step 1 is the last committed state.
- ``resume``     — resume from the latest committed checkpoint, assert it
  actually resumed (regenerating only unprocessed chunks), write OUT_NPZ.
"""

import os

from repro.common.env import force_host_devices

force_host_devices(2, extra=os.environ.get("XLA_FLAGS_EXTRA", ""))

import sys

import jax
import numpy as np

from repro.core import malstone_run, malstone_run_streaming
from repro.core.resume import ResumableRunner
from repro.faults import FaultPlan
from repro.launch.mesh import make_mesh
from repro.malgen import MalGenConfig, generate_chunked_log, make_seed_streaming

CFG = MalGenConfig(num_sites=301, num_entities=1000,
                   marked_site_fraction=0.2, marked_event_fraction=0.3)
NUM_CHUNKS, CHUNK, SEG = 8, 512, 1   # 4 chunks/device -> 4 segments
KILL_STEP = 2
EXIT_CODE = 17


def _save(out_npz, out):
    arrs = {"total": np.asarray(out.result.total),
            "marked": np.asarray(out.result.marked),
            "rho": np.asarray(out.result.rho)}
    if out.shuffle_stats is not None:
        for f in out.shuffle_stats._fields:
            arrs[f"stats_{f}"] = np.asarray(getattr(out.shuffle_stats, f))
    np.savez(out_npz, **arrs)


def main():
    backend, phase, ckpt_dir, out_npz = sys.argv[1:5]
    assert jax.device_count() == 2, jax.devices()
    mesh = make_mesh((2,), ("data",))
    seed = make_seed_streaming(jax.random.key(13), CFG, NUM_CHUNKS, CHUNK)
    runner = ResumableRunner(
        seed, CFG, mesh=mesh, num_chunks=NUM_CHUNKS, chunk_records=CHUNK,
        segment_chunks=SEG, backend=backend, statistic="B")

    if phase == "reference":
        out = runner.run()
        log = generate_chunked_log(seed, CFG, NUM_CHUNKS, CHUNK)
        ref_one = malstone_run(log, CFG.num_sites, mesh=mesh, statistic="B",
                               backend=backend)
        ref_stream = malstone_run_streaming(
            seed, CFG.num_sites, mesh=mesh, backend=backend,
            chunk_records=CHUNK, statistic="B", cfg=CFG,
            num_chunks=NUM_CHUNKS)
        for ref, engine in ((ref_one, "oneshot"), (ref_stream, "streaming")):
            np.testing.assert_array_equal(
                np.asarray(out.result.total), np.asarray(ref.total),
                err_msg=f"{backend} vs {engine}: totals differ")
            np.testing.assert_array_equal(
                np.asarray(out.result.marked), np.asarray(ref.marked),
                err_msg=f"{backend} vs {engine}: marked differ")
        _save(out_npz, out)
        print("REFERENCE_OK")
    elif phase in ("kill_boundary", "kill_midckpt"):
        plan = (FaultPlan(kill_at_segment=KILL_STEP, kill_exit_code=EXIT_CODE)
                if phase == "kill_boundary" else
                FaultPlan(kill_mid_checkpoint_step=KILL_STEP,
                          kill_exit_code=EXIT_CODE))
        runner.run(checkpoint_dir=ckpt_dir, resume=False, faults=plan)
        print("UNREACHABLE: the injected kill never fired")
        sys.exit(3)
    elif phase == "resume":
        out = runner.run(checkpoint_dir=ckpt_dir, resume=True)
        rep = out.report
        assert rep.resumed_from_step is not None, "did not resume"
        assert rep.resumed_from_step >= 1, rep
        assert rep.chunks_skipped > 0, rep
        assert (rep.chunks_skipped + rep.chunks_processed
                == NUM_CHUNKS), rep
        _save(out_npz, out)
        print(f"RESUMED_FROM={rep.resumed_from_step}")
    else:
        sys.exit(f"unknown phase {phase!r}")


if __name__ == "__main__":
    main()
