"""``chip_smoke.py`` rehearsed on the CPU.

The script's phases run in-process at a tiny geometry (512 sites, 2^12
records, Pallas kernels interpreted) so its control flow and its oracle
checks are guarded without the chip; the refusals (no TPU, no checkout
around the script) are checked as a user would hit them.
"""

import importlib.util
import pathlib
import shutil
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.launch.mesh import make_mesh

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module  # dataclasses look their module up
    spec.loader.exec_module(module)
    return module


chip_smoke = _load_chip_smoke()
TINY = chip_smoke.Geometry(sites=512, entities=4096, stream_records=1 << 12,
                           chunk_records=1 << 10, oneshot_records=1 << 12,
                           serve_chunks=4, runs=1)
BACKENDS = chip_smoke.BACKENDS
PHASES = {
    "streaming_phase": [f"streaming/B/{b}" for b in BACKENDS],
    "oneshot_phases": ["oneshot/B/sphere", "oneshot/B/mapreduce",
                       "histogram_impl=pallas/B/sphere",
                       "histogram_impl=pallas/B/mapreduce"],
    "serving_phase": ["serving/ingest/mapreduce", "serving/query/default",
                      "serving/query/growing"],
}


@pytest.fixture(scope="module")
def mesh():
    return make_mesh((1,), ("data",), devices=jax.devices()[:1])


@pytest.mark.parametrize("phase", sorted(PHASES))
def test_phase_bit_equal_to_oracle_at_tiny_size(phase, mesh):
    results = []
    getattr(chip_smoke, phase)(TINY, mesh, results.append)
    assert [r.name for r in results] == PHASES[phase]
    bad = [r.line() for r in results if not r.ok]
    assert not bad, bad
    for r in results:
        assert "(not a benchmark)" in r.line()
        # interpreted off the chip: the compiled HLO holds no TPU kernel
        assert r.kernels is not True


def test_bit_diff_sees_one_ulp_and_signed_zero():
    one = np.float32([1.0, 0.0])
    assert chip_smoke.bit_diff("x", one, one.copy()) == []
    ulp = one.copy()
    ulp[0] = np.nextafter(np.float32(1.0), np.float32(2.0))
    assert chip_smoke.bit_diff("x", ulp, one)
    assert chip_smoke.bit_diff("x", np.float32([1.0, -0.0]), one)
    assert chip_smoke.bit_diff("x", one.astype(np.int32), one)
    assert chip_smoke.bit_diff("x", np.int32(3), np.int32(3)) == []


def test_main_refuses_without_a_tpu(capsys):
    assert chip_smoke.main([]) == 1
    out, err = capsys.readouterr()
    assert out == ""
    assert "no TPU" in err


def test_script_alone_fails_without_a_result(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "checkout" in proc.stderr
