"""Lossless multi-round MapReduce shuffle under adversarial skew.

The paper's MapReduce stack ships *every* record to its reducer (§6.1);
the TPU adaptation must therefore be exact at ANY ``capacity_factor`` —
a small capacity buys extra shuffle rounds, never dropped records. These
tests drive the worst case the power-law site distribution can produce
(every record on one site) through all four backends and both engines and
assert bit-identical integer histograms plus ``overflow == 0`` after the
final round. Multi-device coverage (8 forced host devices) lives in
tests/md_scripts/{backends,streaming}_check.py; here the mesh is the main
process's single device — the round loop is independent of mesh size
(capacity scales as records/P, so P=1 still forces multi-round draining).

Also covers the satellite fixes that ride along with the shuffle rewrite:
``donate_log`` round-trip, ``max_shuffle_rounds`` exhaustion raising
instead of dropping, and the chunk-divisibility / padding guards raising
``ValueError`` (not bare ``assert``, which vanishes under ``python -O``).
"""

import json
import os
import pathlib
import subprocess
import sys
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    ShuffleExhaustedError,
    malstone_run,
    malstone_run_streaming,
    pad_log_to,
)
from repro.core.streaming import streaming_histogram_from_log
from repro.launch.mesh import make_mesh
from repro.malgen import MalGenConfig, generate_full_log

BACKENDS = ("streams", "sphere", "mapreduce", "mapreduce_combiner")
CAPACITY_FACTORS = (0.1, 0.25, 1.0, 2.0)

CFG = MalGenConfig(num_sites=257, num_entities=700,
                   marked_site_fraction=0.2, marked_event_fraction=0.3)
N, CHUNK = 2048, 512


@pytest.fixture(scope="module")
def mesh():
    return make_mesh((1,), ("data",))


@pytest.fixture(scope="module")
def logs():
    """(power-law log, adversarial all-records-on-one-site log)."""
    log, _ = generate_full_log(jax.random.key(13), CFG, N)
    adversarial = log._replace(site_id=jnp.zeros_like(log.site_id))
    return log, adversarial


@pytest.fixture(scope="module")
def reference(mesh, logs):
    """The streams backend is the equality oracle (no shuffle capacity)."""
    log, adversarial = logs
    return (malstone_run(log, CFG.num_sites, mesh=mesh, backend="streams"),
            malstone_run(adversarial, CFG.num_sites, mesh=mesh,
                         backend="streams"))


def assert_exact(got, ref, msg=""):
    np.testing.assert_array_equal(np.asarray(got.total),
                                  np.asarray(ref.total), err_msg=msg)
    np.testing.assert_array_equal(np.asarray(got.marked),
                                  np.asarray(ref.marked), err_msg=msg)


@pytest.mark.parametrize("cf", CAPACITY_FACTORS)
@pytest.mark.parametrize("backend", BACKENDS)
def test_adversarial_oneshot_exact(mesh, logs, reference, backend, cf):
    """All records on one site, capacity down to 0.1x: every backend's
    one-shot histogram equals the streams oracle bit-for-bit."""
    _, adversarial = logs
    _, ref = reference
    if backend == "mapreduce":
        got, stats = malstone_run(
            adversarial, CFG.num_sites, mesh=mesh, backend=backend,
            capacity_factor=cf, return_shuffle_stats=True)
        assert int(stats.overflow) == 0
        assert int(stats.sent) == N
        # worst case drains exactly capacity records per round
        assert int(stats.rounds) == -(-N // int(stats.capacity))
    else:
        got = malstone_run(adversarial, CFG.num_sites, mesh=mesh,
                           backend=backend, capacity_factor=cf)
    assert_exact(got, ref, f"{backend}/cf={cf}")


@pytest.mark.parametrize("cf", CAPACITY_FACTORS)
@pytest.mark.parametrize("backend", BACKENDS)
def test_adversarial_streaming_exact(mesh, logs, reference, backend, cf):
    """Same adversarial log through the chunked streaming engine: the
    per-chunk multi-round shuffle stays exact at any capacity factor."""
    _, adversarial = logs
    _, ref = reference
    if backend == "mapreduce":
        got, stats = malstone_run_streaming(
            adversarial, CFG.num_sites, mesh=mesh, backend=backend,
            chunk_records=CHUNK, capacity_factor=cf,
            return_shuffle_stats=True)
        assert int(stats.overflow) == 0
        assert int(stats.sent) == N
        # rounds = the worst chunk's rounds; every chunk is all-one-site
        assert int(stats.rounds) == -(-CHUNK // int(stats.capacity))
    else:
        got = malstone_run_streaming(
            adversarial, CFG.num_sites, mesh=mesh, backend=backend,
            chunk_records=CHUNK, capacity_factor=cf)
    assert_exact(got, ref, f"streaming {backend}/cf={cf}")


def test_powerlaw_small_capacity_exact(mesh, logs, reference):
    """Ordinary power-law skew at sub-1.0 capacity (the regime the old
    pack-and-drop shuffle silently lost records in)."""
    log, _ = logs
    ref, _ = reference
    got, stats = malstone_run(log, CFG.num_sites, mesh=mesh,
                              backend="mapreduce", capacity_factor=0.25,
                              return_shuffle_stats=True)
    assert_exact(got, ref)
    assert int(stats.overflow) == 0
    assert int(stats.rounds) >= 2          # capacity 0.25x forces re-rounds
    assert int(stats.residual) > 0         # deferred work was measured


def test_shuffle_stats_reported_fields(mesh, logs):
    """ShuffleStats surfaces rounds/residual alongside the old counters."""
    log, _ = logs
    _, stats = malstone_run(log, CFG.num_sites, mesh=mesh,
                            backend="mapreduce", capacity_factor=2.0,
                            return_shuffle_stats=True)
    for field in ("sent", "overflow", "capacity", "rounds", "residual"):
        assert int(getattr(stats, field)) >= 0
    # non-shuffle backends have no stats to report
    _, none_stats = malstone_run(log, CFG.num_sites, mesh=mesh,
                                 backend="streams",
                                 return_shuffle_stats=True)
    assert none_stats is None


def test_max_rounds_exhaustion_raises(mesh, logs):
    """An explicit round cap that cannot drain the skew must raise — the
    escape hatch bounds latency but never silently drops records."""
    _, adversarial = logs
    with pytest.raises(ShuffleExhaustedError, match="undelivered"):
        malstone_run(adversarial, CFG.num_sites, mesh=mesh,
                     backend="mapreduce", capacity_factor=0.1,
                     max_shuffle_rounds=1)
    with pytest.raises(ShuffleExhaustedError, match="undelivered"):
        malstone_run_streaming(adversarial, CFG.num_sites, mesh=mesh,
                               backend="mapreduce", chunk_records=CHUNK,
                               capacity_factor=0.1, max_shuffle_rounds=1)


def test_under_trace_round_cap_refused(mesh, logs):
    """Under an outer jit the post-run overflow check cannot fire, so an
    under-bound round cap without return_shuffle_stats is refused at trace
    time — the silent-drop hole stays closed for traced callers too."""
    _, adversarial = logs
    fn = jax.jit(lambda l: malstone_run(
        l, CFG.num_sites, mesh=mesh, backend="mapreduce",
        capacity_factor=0.1, max_shuffle_rounds=1).rho)
    with pytest.raises(ValueError, match="being traced"):
        fn(adversarial)
    fn_s = jax.jit(lambda l: malstone_run_streaming(
        l, CFG.num_sites, mesh=mesh, backend="mapreduce",
        chunk_records=CHUNK, capacity_factor=0.1, max_shuffle_rounds=1).rho)
    with pytest.raises(ValueError, match="being traced"):
        fn_s(adversarial)
    # return_shuffle_stats=True hands the overflow counter to the caller,
    # which makes the capped traced call legal (and observably lossy here)
    fn_ok = jax.jit(lambda l: malstone_run(
        l, CFG.num_sites, mesh=mesh, backend="mapreduce",
        capacity_factor=0.1, max_shuffle_rounds=1,
        return_shuffle_stats=True)[1].overflow)
    assert int(fn_ok(adversarial)) > 0


def test_max_rounds_sufficient_cap_ok(mesh, logs, reference):
    """A cap at (or above) the provable bound behaves like the default."""
    _, adversarial = logs
    _, ref = reference
    got, stats = malstone_run(
        adversarial, CFG.num_sites, mesh=mesh, backend="mapreduce",
        capacity_factor=1.0, max_shuffle_rounds=4,
        return_shuffle_stats=True)
    assert_exact(got, ref)
    assert int(stats.overflow) == 0


def test_donate_log_round_trips(mesh, logs, reference):
    """donate_log=True must produce identical results (on CPU, donation is
    ignored with a warning; the flag wires jit donate_argnums either way)."""
    log, _ = logs
    ref, _ = reference
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")   # CPU: "donated buffers not usable"
        got = malstone_run(log, CFG.num_sites, mesh=mesh, backend="streams",
                           donate_log=True)
        got_mr = malstone_run(log, CFG.num_sites, mesh=mesh,
                              backend="mapreduce", donate_log=True)
    assert_exact(got, ref)
    assert_exact(got_mr, ref)


def test_chunk_divisibility_raises_value_error(logs):
    """The chunk-divisibility guard must survive ``python -O`` (it used to
    be a bare assert)."""
    log, _ = logs
    odd = jax.tree.map(lambda x: x[:100], log)
    with pytest.raises(ValueError, match="divisible by"):
        streaming_histogram_from_log(odd, s_pad=CFG.num_sites,
                                     chunk_records=64)


def test_pad_log_to_raises_value_error(logs):
    log, _ = logs
    with pytest.raises(ValueError, match="smaller than"):
        pad_log_to(log, N - 1)


@pytest.mark.slow
def test_launcher_bfixed_and_shuffle_flags(tmp_path):
    """repro.launch.malstone accepts --statistic B-fixed and the new
    --capacity-factor / --max-shuffle-rounds flags, and reports the shuffle
    rounds in the BENCH json extras."""
    out = tmp_path / "BENCH_launch.json"
    env = dict(os.environ)
    env["PYTHONPATH"] = (str(pathlib.Path(__file__).parent.parent / "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.malstone",
         "--nodes", "1", "--records-per-node", "1024",
         "--sites", "64", "--entities", "256",
         "--backend", "mapreduce", "--statistic", "B-fixed",
         "--capacity-factor", "0.25", "--max-shuffle-rounds", "8",
         "--runs", "1", "--bench-json", str(out)],
        capture_output=True, text=True, timeout=600, env=env)
    assert proc.returncode == 0, proc.stderr[-4000:]
    assert "MalStone B-fixed [mapreduce" in proc.stdout
    assert "overflow=0 (lossless)" in proc.stdout
    doc = json.loads(out.read_text())
    (entry,) = doc["results"]
    assert entry["scenario"] == "launch_malstone_bfixed_mapreduce_oneshot"
    assert entry["params"]["capacity_factor"] == 0.25
    assert entry["derived"]["shuffle_rounds"] >= 2
    assert entry["derived"]["shuffle_overflow"] == 0
