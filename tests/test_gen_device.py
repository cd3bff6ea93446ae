"""Device-parallel MalGen: bit-identity with the host oracle + Event IDs.

``generate_shard_device`` must reproduce ``generate_shard`` *bit for bit*
for every shard — including ragged layouts where the marked stream does not
divide evenly over shards (the per-shard marked-row count differs by one) —
while keeping every shape static so it traces under ``shard_map``. The
fused drivers (``malstone_run_generated`` and its streaming twin) must then
match ``malstone_run`` over the materialized ``generate_sharded_log`` log
exactly, for all four backends and both engines. Multi-device coverage
(8 forced host devices) runs in a subprocess
(tests/md_scripts/gen_device_check.py).
"""

import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.common.types import PAD_SHARD_HASH
from repro.core import (
    malstone_run,
    malstone_run_generated,
    malstone_run_generated_streaming,
    malstone_run_streaming,
    pad_log_to,
)
from repro.launch.mesh import make_mesh
from repro.malgen import (
    MalGenConfig,
    chunk_shard_hash,
    generate_shard,
    generate_shard_device,
    generate_sharded_log,
    generate_streaming_log,
    make_seed,
    shard_marked_budget,
)

HERE = pathlib.Path(__file__).parent
SRC = str(HERE.parent / "src")

BACKENDS = ("streams", "sphere", "mapreduce", "mapreduce_combiner")

CFG = MalGenConfig(num_sites=200, num_entities=500,
                   marked_site_fraction=0.2, marked_event_fraction=0.3)

# (num_shards, records_per_shard) covering uniform (r == 0) and ragged
# (r != 0) marked-stream layouts at this config
SHAPES = ((1, 512), (2, 384), (4, 96), (5, 64))


def assert_logs_equal(got, ref, msg=""):
    for a, b, name in zip(got, ref, ref._fields):
        if b is None:
            continue
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"{msg}: {name}")


class TestBitIdentity:
    @pytest.mark.parametrize("num_shards, rps", SHAPES)
    def test_every_shard_matches_host(self, num_shards, rps):
        _, seed = generate_sharded_log(jax.random.key(0), CFG,
                                       num_shards, rps)
        for s in range(num_shards):
            host = generate_shard(seed, CFG, s, num_shards, rps)
            dev = generate_shard_device(seed, CFG, s, num_shards, rps)
            assert_logs_equal(dev, host, f"shard {s}/{num_shards}")

    def test_traced_shard_id_matches_eager(self):
        num_shards, rps = 4, 96   # ragged: NM % 4 != 0 at this config
        _, seed = generate_sharded_log(jax.random.key(1), CFG,
                                       num_shards, rps)
        assert seed.num_marked_events % num_shards != 0
        fn = jax.jit(lambda i: generate_shard_device(seed, CFG, i,
                                                     num_shards, rps))
        for s in range(num_shards):
            assert_logs_equal(fn(jnp.int32(s)),
                              generate_shard(seed, CFG, s, num_shards, rps),
                              f"traced shard {s}")

    def test_overflow_raises_like_host(self):
        seed = make_seed(jax.random.key(2), CFG, total_records=20_000)
        with pytest.raises(ValueError, match="marked"):
            generate_shard_device(seed, CFG, 0, 2, 256)
        with pytest.raises(ValueError, match="marked"):
            shard_marked_budget(seed.num_marked_events, 2, 256)

    def test_traced_seed_budget_is_refused(self):
        _, seed = generate_sharded_log(jax.random.key(3), CFG, 2, 128)
        with pytest.raises(ValueError, match="num_marked_events"):
            jax.jit(lambda sd: generate_shard_device(sd, CFG, 0, 2, 128))(
                seed)


class TestEventIds:
    def test_chunk_zero_hash_is_not_zero(self):
        """Regression: _mix32(0) == 0 gave chunk 0 an all-zero shard_hash,
        colliding with pad_log_to's zero-filled padding rows."""
        assert int(chunk_shard_hash(0)) != 0
        assert int(chunk_shard_hash(jnp.int32(0))) != 0

    def test_padding_never_collides_with_chunk_ids(self):
        log, _ = generate_streaming_log(jax.random.key(4), CFG, 4, 256)
        padded = pad_log_to(log, 1536)
        hsh = np.asarray(padded.shard_hash)
        seq = np.asarray(padded.event_seq)
        valid = np.asarray(padded.valid)
        assert np.all(hsh[~valid] == PAD_SHARD_HASH)
        real = set(zip(hsh[valid].tolist(), seq[valid].tolist()))
        padded_ids = set(zip(hsh[~valid].tolist(), seq[~valid].tolist()))
        assert len(real) == int(valid.sum())      # unique across chunks
        assert not (real & padded_ids)            # and disjoint from padding


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 2**31 - 1), st.integers(1, 6))
def test_property_event_ids_unique_host_and_device(seed_int, num_shards):
    """(shard_hash, event_seq) is globally unique for the host shard path,
    the device shard path, and the chunk-keyed path."""
    rps = 190  # NM = round(47.5 * num_shards): ragged for most shard counts
    cfg = MalGenConfig(num_sites=64, num_entities=256,
                       marked_event_fraction=0.25)
    key = jax.random.key(seed_int)

    host, seed = generate_sharded_log(key, cfg, num_shards, rps)
    ids = set(zip(np.asarray(host.shard_hash).tolist(),
                  np.asarray(host.event_seq).tolist()))
    assert len(ids) == host.num_records

    dev_parts = [generate_shard_device(seed, cfg, s, num_shards, rps)
                 for s in range(num_shards)]
    dev_ids = set()
    for p in dev_parts:
        dev_ids |= set(zip(np.asarray(p.shard_hash).tolist(),
                           np.asarray(p.event_seq).tolist()))
    assert dev_ids == ids                          # device == host, as sets

    chunked, _ = generate_streaming_log(key, cfg, num_shards, rps)
    cids = set(zip(np.asarray(chunked.shard_hash).tolist(),
                   np.asarray(chunked.event_seq).tolist()))
    assert len(cids) == chunked.num_records
    assert 0 not in np.asarray(chunked.shard_hash)  # salted chunk hashes


@pytest.fixture(scope="module")
def mesh():
    return make_mesh((1,), ("data",))


@pytest.fixture(scope="module")
def log_and_seed():
    return generate_sharded_log(jax.random.key(5), CFG, 1, 2048)


def assert_exact(got, ref, msg=""):
    np.testing.assert_array_equal(np.asarray(got.total),
                                  np.asarray(ref.total), err_msg=msg)
    np.testing.assert_array_equal(np.asarray(got.marked),
                                  np.asarray(ref.marked), err_msg=msg)


@pytest.mark.parametrize("statistic", ["A", "B"])
@pytest.mark.parametrize("backend", BACKENDS)
def test_fused_oneshot_bit_identical(mesh, log_and_seed, backend, statistic):
    """malstone_run_generated == malstone_run over the materialized log."""
    log, seed = log_and_seed
    ref = malstone_run(log, CFG.num_sites, mesh=mesh, statistic=statistic,
                       backend=backend)
    got = malstone_run_generated(seed, CFG, mesh=mesh,
                                 records_per_shard=2048,
                                 statistic=statistic, backend=backend)
    assert_exact(got, ref, f"fused {backend}/{statistic}")


@pytest.mark.parametrize("backend", BACKENDS)
def test_fused_streaming_bit_identical(mesh, log_and_seed, backend):
    """The streaming twin matches chunked malstone_run_streaming exactly."""
    log, seed = log_and_seed
    ref = malstone_run_streaming(log, CFG.num_sites, mesh=mesh,
                                 backend=backend, chunk_records=512,
                                 statistic="B")
    got = malstone_run_generated_streaming(
        seed, CFG, mesh=mesh, records_per_shard=2048, chunk_records=512,
        statistic="B", backend=backend)
    assert_exact(got, ref, f"fused-streaming {backend}")


def test_fused_streaming_requires_divisible_chunks(mesh, log_and_seed):
    _, seed = log_and_seed
    with pytest.raises(ValueError, match="divisible"):
        malstone_run_generated_streaming(seed, CFG, mesh=mesh,
                                         records_per_shard=2048,
                                         chunk_records=600)


def test_fused_shuffle_stats_round_trip(mesh, log_and_seed):
    """The fused mapreduce path reports the same lossless shuffle
    accounting contract as the materialized one."""
    _, seed = log_and_seed
    got, stats = malstone_run_generated(
        seed, CFG, mesh=mesh, records_per_shard=2048, backend="mapreduce",
        capacity_factor=0.25, return_shuffle_stats=True)
    assert int(stats.overflow) == 0
    assert int(stats.rounds) >= 1
    assert np.all(np.isfinite(np.asarray(got.rho)))


@pytest.mark.parametrize("streaming", [False, True])
def test_fused_under_bound_cap_refused_under_outer_jit(mesh, log_and_seed,
                                                       streaming):
    """Regression: the generated drivers' seed is concrete (closed over),
    so the input-sniffing trace guard of malstone_run never fired for them
    — an outer jax.jit plus an under-bound max_shuffle_rounds could drop
    shuffle records silently. The post-run stats-tracedness check must
    refuse that combination at trace time (and still allow it when the
    caller takes the stats)."""
    _, seed = log_and_seed

    def call(**kw):
        fn = (malstone_run_generated_streaming if streaming
              else malstone_run_generated)
        extra = {"chunk_records": 512} if streaming else {}
        out = fn(seed, CFG, mesh=mesh, records_per_shard=2048,
                 backend="mapreduce", capacity_factor=0.25,
                 max_shuffle_rounds=1, **extra, **kw)
        return out[0].rho if kw.get("return_shuffle_stats") else out.rho

    with pytest.raises(ValueError, match="lossless bound"):
        jax.jit(call)()
    # the documented escape hatch: caller owns the overflow check
    jax.block_until_ready(
        jax.jit(lambda: call(return_shuffle_stats=True))())


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
def test_gen_device_equivalent_on_8_devices():
    out = _run_md_script("gen_device_check.py")
    assert "ALL_OK" in out
