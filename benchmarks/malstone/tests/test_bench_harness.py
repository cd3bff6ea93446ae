"""The harness rehearsed on the CPU at a tiny geometry (512 sites, 2^14
records per chip, Pallas kernels interpreted): each cell's job loop, the
result line, the refusals, finding a cell by name, and ``correct`` coming
out false when the program is broken underneath.

A MapReduce cell over the resident log also runs across four virtual
devices, in a fresh process, so that its exchange crosses devices.
"""

import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

TESTS = pathlib.Path(__file__).resolve().parent
BENCH_DIR = TESTS.parent
ROOT = BENCH_DIR.parents[1]
sys.path.insert(0, str(BENCH_DIR))
sys.path.insert(0, str(TESTS))

import harness  # noqa: E402
import rehearsal  # noqa: E402

KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


def _metric_names(kind, cell):
    return {m["name"] for m in BENCH[kind]
            if "workloads" not in m or cell in m["workloads"]}


def _env(devices=1):
    """A child's environment: this one's, with the CPU platform and its
    own device count (other tests of the same process may leave flags in
    ``XLA_FLAGS``)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    env["PYTHONPATH"] = os.pathsep.join(
        [str(ROOT / "src")] + [p for p in [env.get("PYTHONPATH")] if p])
    return env


def _mapreduce_root(root, chips):
    """A checkout whose one cell, ``mr.log``, is MapReduce over the
    resident log on ``chips`` chips, with every per-layer metric."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["paths"] = [str(BENCH_DIR)]
    bench["configs"] = [{
        "name": "mr", "source": "https://arxiv.org/abs/1007.1261",
        "file": str(BENCH_DIR / "configs" / "malstone-b10-mapreduce.json"),
        "reduced": [], "why": "MapReduce"}]
    bench["workloads"] = [{"name": "mr.log", "config": "mr",
                           "traffic": "log", "chips": chips,
                           "why": "rehearsal"}]
    bench["per_layer"] = [m for m in bench["per_layer"]
                          if m["name"] != "gen_ms_per_chunk"] + [{
        "name": name, "unit": unit, "better": "lower",
        "source": source, "layer": "exchange", "moves": "records_per_s"}
        for name, unit, source in (
            ("collective_share", "%", "device_trace"),
            ("exchange_rounds", "rounds", "program_counter"))]
    for m in bench["per_layer"]:
        m.pop("workloads", None)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


@pytest.fixture
def four_chip_root(tmp_path):
    return _mapreduce_root(tmp_path, 4)


def _rehearse_in_subprocess(root, trace, fault="none"):
    out = subprocess.run(
        [sys.executable, str(TESTS / "rehearsal.py"), "mr.log",
         "--root", str(root), "--trace", str(trace), "--fault", fault],
        env=_env(4), capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def _check_line(result, trace, metric_names):
    assert list(result) == (KEYS[:5] + ["breakdown", "checks"] if trace
                            else KEYS)
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    assert set(result["metrics"]) == metric_names
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert list(result["checks"]) == list(harness.LIMITS)
    if trace:
        assert 0 < result["device"]["busy_s"] <= result["device"]["window_s"]
        assert result["breakdown"]["device_ops"]


ONE_CHIP = [w["name"] for w in BENCH["workloads"] if w["chips"] == 1]


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", ONE_CHIP)
def test_one_chip_cell_rehearsed(cell, trace):
    result = rehearsal.rehearse(cell, trace=bool(trace))
    kind = "per_layer" if trace else "end_to_end"
    _check_line(result, trace, _metric_names(kind, cell))


@pytest.mark.parametrize("trace", [0, 1])
def test_mapreduce_log_rehearsed_on_four_virtual_devices(trace,
                                                         four_chip_root):
    result = _rehearse_in_subprocess(four_chip_root, trace)
    names = ({"fold_ms_per_chunk", "hist_roofline", "collective_share",
              "exchange_rounds", "device_idle_share"} if trace
             else {"records_per_s", "setup_s"})
    _check_line(result, trace, names)
    assert result["device"]["count"] == 4
    if trace:
        assert result["metrics"]["exchange_rounds"]["value"] >= 1


def test_mapreduce_log_rehearsed_on_one_device(tmp_path):
    """The MapReduce job on one chip: its exchange sends every record back
    to the chip it is on, so ``collective_share`` may read nothing."""
    root = _mapreduce_root(tmp_path, 1)
    result = rehearsal.rehearse("mr.log", trace=True, root=root)
    names = {"fold_ms_per_chunk", "hist_roofline", "exchange_rounds",
             "device_idle_share"}
    assert names <= set(result["metrics"]) <= names | {"collective_share"}
    _check_line(result, 1, set(result["metrics"]))
    assert result["device"]["count"] == 1
    assert result["metrics"]["exchange_rounds"]["value"] == 1


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered"])
def test_broken_program_is_not_correct(fault):
    result = rehearsal.rehearse("streams.seed", fault_kind=fault)
    assert result["correct"] is False
    assert result["failed"] == result["attempted"] >= 1


def test_exchange_left_out_is_not_correct(four_chip_root):
    result = _rehearse_in_subprocess(four_chip_root, 0, "exchange_left_out")
    assert result["correct"] is False
    assert result["checks"]["total_mismatch"]["value"] > 0


def test_main_refuses_without_a_tpu():
    out = subprocess.run(
        [sys.executable, str(BENCH_DIR / "bench.py"), "--workload",
         "streams.seed", "--seed", str(2**31 + 3), "--seconds", "1"],
        cwd=ROOT, env=_env(), capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no TPU" in out.stderr


def test_main_refuses_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH_DIR, tmp_path / "benchmarks" / "malstone",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = subprocess.run(
        [sys.executable, "benchmarks/malstone/bench.py", "--workload",
         "streams.seed", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=_env(), capture_output=True, text=True,
        timeout=300)
    assert out.returncode != 0
    assert out.stdout.strip() == ""


def test_new_workload_file_is_found_by_name(tmp_path):
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["paths"] = ["probe"]
    bench["configs"] = [dict(bench["configs"][0], name="probe-cfg",
                             file="probe/configs/probe-cfg.json")]
    bench["workloads"] = [{"name": "probe.seed", "config": "probe-cfg",
                           "traffic": "probe-traffic", "chips": 1,
                           "why": "a throwaway cell"}]
    bench["per_layer"] = []
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    for sub in ("configs", "traffic"):
        (tmp_path / "probe" / sub).mkdir(parents=True)
    config = json.loads((BENCH_DIR / "configs" /
                         "malstone-b10-streams.json").read_text())
    (tmp_path / "probe/configs/probe-cfg.json").write_text(json.dumps(config))
    (tmp_path / "probe/traffic/probe-traffic.json").write_text(
        json.dumps({"source": "seed"}))

    cell = harness.load_cell(tmp_path, "probe.seed")
    assert cell.traffic == {"source": "seed"}
    assert cell.bench_dir == tmp_path / "probe"
    result = rehearsal.rehearse("probe.seed", root=tmp_path)
    assert result["correct"] is True
    assert set(result["metrics"]) == {"records_per_s", "setup_s"}
    with pytest.raises(KeyError):
        harness.load_cell(tmp_path, "no.such.cell")
