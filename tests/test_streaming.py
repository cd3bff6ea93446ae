"""Streaming chunked engine: exact-equality tests vs the one-shot path.

The engine's contract is *bit-identical* integer histograms (the site x week
histogram is a commutative monoid, so chunk accumulation commutes exactly) —
every assertion here is assert_array_equal on the integer counts, never
allclose. Multi-device coverage (8 forced host devices) runs in a subprocess
(tests/md_scripts/streaming_check.py) because device count is locked at
first jax init.
"""

import os
import pathlib
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.core import malstone_run, malstone_run_streaming
from repro.launch.mesh import make_mesh
from repro.malgen import (
    MalGenConfig,
    chunk_marked_records,
    generate_chunk,
    generate_chunked_log,
    generate_full_log,
    make_seed_streaming,
)

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
def seed_and_log():
    seed = make_seed_streaming(jax.random.key(7), CFG, NUM_CHUNKS, CHUNK)
    log = generate_chunked_log(seed, CFG, NUM_CHUNKS, CHUNK)
    return seed, log


def assert_exact(got, ref, msg=""):
    np.testing.assert_array_equal(np.asarray(got.total),
                                  np.asarray(ref.total), err_msg=msg)
    np.testing.assert_array_equal(np.asarray(got.marked),
                                  np.asarray(ref.marked), err_msg=msg)


@pytest.mark.parametrize("statistic", ["A", "B"])
@pytest.mark.parametrize("backend", BACKENDS)
def test_seed_mode_bit_identical(mesh, seed_and_log, backend, statistic):
    """Generate-as-you-go streaming == one-shot over the materialized log."""
    seed, log = seed_and_log
    ref = malstone_run(log, CFG.num_sites, mesh=mesh, statistic=statistic,
                       backend=backend)
    got = malstone_run_streaming(seed, CFG.num_sites, mesh=mesh,
                                 backend=backend, chunk_records=CHUNK,
                                 statistic=statistic, cfg=CFG,
                                 num_chunks=NUM_CHUNKS)
    assert_exact(got, ref, f"{backend}/{statistic}")


@pytest.mark.parametrize("backend", BACKENDS)
def test_log_mode_uneven_final_chunk(mesh, seed_and_log, backend):
    """A record count that does not divide the chunk size is padded with
    invalid rows and still agrees exactly."""
    _, log = seed_and_log
    odd = jax.tree.map(lambda x: x[:3000], log)  # 3000 = 5*512 + 440
    ref = malstone_run(odd, CFG.num_sites, mesh=mesh, statistic="B",
                       backend=backend)
    got = malstone_run_streaming(odd, CFG.num_sites, mesh=mesh,
                                 backend=backend, chunk_records=512,
                                 statistic="B")
    assert_exact(got, ref, backend)


def test_log_mode_accepts_any_generated_log(mesh):
    """The chunked variant works on generate_shard-layout logs too (the
    pre-generated-data path) — chunking is exactness-preserving regardless
    of how the log was produced."""
    log, _ = generate_full_log(jax.random.key(5), CFG, 4096)
    ref = malstone_run(log, CFG.num_sites, mesh=mesh, statistic="B",
                       backend="streams")
    got = malstone_run_streaming(log, CFG.num_sites, mesh=mesh,
                                 backend="streams", chunk_records=1024,
                                 statistic="B")
    assert_exact(got, ref)


def test_chunk_regeneration_is_pure(seed_and_log):
    """generate_chunk is a pure function of (seed, chunk_id): traced and
    eager invocations produce identical records."""
    seed, log = seed_and_log
    import jax.numpy as jnp
    eager = generate_chunk(seed, CFG, 3, CHUNK)
    traced = jax.jit(lambda i: generate_chunk(seed, CFG, i, CHUNK))(
        jnp.int32(3))
    for a, b, name in zip(traced, eager, eager._fields):
        if a is not None:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                          err_msg=name)
    # chunk 3 of the materialized log is exactly this chunk
    sl = slice(3 * CHUNK, 4 * CHUNK)
    np.testing.assert_array_equal(np.asarray(eager.site_id),
                                  np.asarray(log.site_id[sl]))


def test_marked_fraction_layout():
    """Every chunk devotes the same static row budget to marked-site
    traffic (what makes chunk generation scan-traceable)."""
    n = chunk_marked_records(CFG, CHUNK)
    assert n == round(CHUNK * CFG.marked_event_fraction)
    assert 0 <= n <= CHUNK


def test_seed_mode_requires_cfg_and_chunks(mesh, seed_and_log):
    seed, _ = seed_and_log
    with pytest.raises(ValueError, match="seed mode requires"):
        malstone_run_streaming(seed, CFG.num_sites, mesh=mesh,
                               chunk_records=CHUNK)


def _run_md_script(name: str, timeout=600) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, str(HERE / "md_scripts" / name)],
        capture_output=True, text=True, timeout=timeout, env=env)
    assert proc.returncode == 0, (
        f"{name} failed\nSTDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr[-4000:]}")
    return proc.stdout


@pytest.mark.slow
def test_streaming_equivalent_on_8_devices():
    out = _run_md_script("streaming_check.py")
    assert "ALL_OK" in out
