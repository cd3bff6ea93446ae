"""Packed sort-once shuffle: word round-trip, guarded fallback, and
bit-identity against the 4-column oracle.

The tentpole claim of the packed exchange (``backends/mapreduce.py``) is
that projecting each record to one uint32 word and sorting once before the
round loop changes NOTHING observable except bytes moved and wall time:
histograms, ``sent``/``rounds``/``residual``/``overflow`` accounting, the
``ShuffleExhaustedError`` contract — all bit-identical to the 4-column
fallback, for both engines, at any capacity factor, under adversarial
skew, and with padded (invalid) rows present. These tests pin that down,
plus the ``ShuffleStats`` trailing-default dtype contract (numpy int32
scalars, not weakly-typed Python ints) and the ``bytes_exchanged``
accounting formula.
"""

import json
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import example, given, settings, strategies as st

from repro.common.types import (
    PACK_MAX_SITES,
    PACK_MAX_WEEKS,
    pack_site_week_mark,
    unpack_site_week_mark,
)
from repro.core import malstone_run, malstone_run_streaming, pad_log_to
from repro.core.backends.mapreduce import (
    PACKED_SLOT_BYTES,
    UNPACKED_SLOT_BYTES,
    ShuffleStats,
    packed_shuffle_supported,
    resolve_packed_shuffle,
)
from repro.launch.mesh import make_mesh
from repro.malgen import MalGenConfig, generate_full_log

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


def assert_exact(got, ref, msg=""):
    np.testing.assert_array_equal(np.asarray(got.total),
                                  np.asarray(ref.total), err_msg=msg)
    np.testing.assert_array_equal(np.asarray(got.marked),
                                  np.asarray(ref.marked), err_msg=msg)


# ------------------------------------------------------- word round-trip
@settings(max_examples=50, deadline=None)
@given(st.integers(0, PACK_MAX_SITES - 1),
       st.integers(0, PACK_MAX_WEEKS - 1),
       st.integers(0, 1))
@example(0, 0, 0)
@example(PACK_MAX_SITES - 1, PACK_MAX_WEEKS - 1, 1)
def test_pack_roundtrip_full_field_ranges(site, week, mark):
    """Property: every representable (site, week, mark) survives the word
    round-trip, endpoints included (the explicit examples pin the
    field-range endpoints — site = 2^24 - 1, week = 63)."""
    word = pack_site_week_mark(jnp.int32(site), jnp.int32(week),
                               jnp.int32(mark), jnp.bool_(True))
    s, w, m, v = unpack_site_week_mark(word)
    assert (int(s), int(w), int(m), bool(v)) == (site, week, mark, True)


class TestPackRoundTrip:
    def test_invalid_rows_pack_to_zero_word(self):
        """Invalid rows must pack to 0 regardless of field garbage — the
        shuffle uses zero-filled buffer slots as self-describing padding."""
        word = pack_site_week_mark(jnp.int32(-1), jnp.int32(63),
                                   jnp.int32(1), jnp.bool_(False))
        assert int(word) == 0
        _, _, _, v = unpack_site_week_mark(word)
        assert not bool(v)

    def test_vectorized_roundtrip_endpoints(self):
        site = jnp.array([0, PACK_MAX_SITES - 1, 12345], jnp.int32)
        week = jnp.array([0, PACK_MAX_WEEKS - 1, 51], jnp.int32)
        mark = jnp.array([1, 0, 1], jnp.int32)
        valid = jnp.array([True, True, True])
        s, w, m, v = unpack_site_week_mark(
            pack_site_week_mark(site, week, mark, valid))
        np.testing.assert_array_equal(np.asarray(s), np.asarray(site))
        np.testing.assert_array_equal(np.asarray(w), np.asarray(week))
        np.testing.assert_array_equal(np.asarray(m), np.asarray(mark))
        assert bool(v.all())


# ----------------------------------------------------- guarded fallback
class TestGuardedFallback:
    def test_supported_bounds(self):
        assert packed_shuffle_supported(PACK_MAX_SITES, PACK_MAX_WEEKS)
        assert not packed_shuffle_supported(PACK_MAX_SITES + 1, 52)
        assert not packed_shuffle_supported(512, PACK_MAX_WEEKS + 1)

    def test_resolve_auto_falls_back(self):
        assert resolve_packed_shuffle(None, 512, 52) is True
        assert resolve_packed_shuffle(None, PACK_MAX_SITES + 1, 52) is False
        assert resolve_packed_shuffle(False, 512, 52) is False

    def test_resolve_forced_packed_raises(self):
        with pytest.raises(ValueError, match="cannot represent"):
            resolve_packed_shuffle(True, PACK_MAX_SITES + 1, 52)

    def test_auto_fallback_end_to_end_num_weeks(self, mesh, logs):
        """num_weeks > 64 trips the auto fallback on a real run: auto and
        explicit off agree exactly; forcing packed raises."""
        log, _ = logs
        auto = malstone_run(log, CFG.num_sites, mesh=mesh,
                            backend="mapreduce", num_weeks=65)
        off = malstone_run(log, CFG.num_sites, mesh=mesh,
                           backend="mapreduce", num_weeks=65,
                           packed_shuffle=False)
        assert_exact(auto, off, "auto fallback vs explicit off")
        with pytest.raises(ValueError, match="cannot represent"):
            malstone_run(log, CFG.num_sites, mesh=mesh, backend="mapreduce",
                         num_weeks=65, packed_shuffle=True)


# ------------------------------------------- packed-vs-unpacked identity
class TestPackedBitIdentity:
    @pytest.mark.parametrize("cf", (0.1, 0.5, 2.0))
    @pytest.mark.parametrize("engine", ("oneshot", "streaming"))
    def test_adversarial_packed_equals_unpacked(self, mesh, logs, engine,
                                                cf):
        """All records on one site, capacity down to 0.1x, both engines:
        packed and unpacked paths agree on the histogram AND on every
        accounting counter; only bytes_exchanged differs (17/4 = 4.25x)."""
        _, adversarial = logs

        def run(packed):
            if engine == "oneshot":
                return malstone_run(
                    adversarial, CFG.num_sites, mesh=mesh,
                    backend="mapreduce", capacity_factor=cf,
                    packed_shuffle=packed, return_shuffle_stats=True)
            return malstone_run_streaming(
                adversarial, CFG.num_sites, mesh=mesh, backend="mapreduce",
                chunk_records=CHUNK, capacity_factor=cf,
                packed_shuffle=packed, return_shuffle_stats=True)

        got_p, stats_p = run(True)
        got_u, stats_u = run(False)
        assert_exact(got_p, got_u, f"{engine}/cf={cf}")
        for field in ("sent", "overflow", "capacity", "rounds", "residual"):
            assert int(getattr(stats_p, field)) == \
                int(getattr(stats_u, field)), f"{field} ({engine}/cf={cf})"
        assert int(stats_p.overflow) == 0
        assert int(stats_u.bytes_exchanged) == (
            int(stats_p.bytes_exchanged)
            * UNPACKED_SLOT_BYTES // PACKED_SLOT_BYTES)

    def test_powerlaw_with_padding_rows(self, mesh, logs):
        """Padded (valid=False, PAD_SHARD_HASH) rows ride through the
        packed exchange without polluting the histogram."""
        log, _ = logs
        odd = jax.tree.map(lambda x: x[: N - 100], log)
        padded = pad_log_to(odd, N)
        ref = malstone_run(odd, CFG.num_sites, mesh=mesh, backend="streams")
        got, stats = malstone_run(
            padded, CFG.num_sites, mesh=mesh, backend="mapreduce",
            capacity_factor=0.5, packed_shuffle=True,
            return_shuffle_stats=True)
        assert_exact(got, ref, "packed shuffle over padded log")
        assert int(stats.sent) == N - 100      # padding rows never shipped
        assert int(stats.overflow) == 0

    def test_packed_histogram_fn_hook_pallas(self, mesh, logs):
        """The packed reducer reconstructs a week-faithful EventLog
        (``timestamp = week * SECONDS_PER_WEEK`` re-buckets to exactly
        ``week``), so an arbitrary histogram_fn — here the real Pallas
        segment_hist kernel, the --histogram-impl pallas production hook —
        reduces it to the same counts as the streams oracle."""
        import functools

        from repro.kernels.segment_hist.ops import segment_hist_eventlog

        log, _ = logs
        hist_fn = functools.partial(segment_hist_eventlog, interpret=True)
        ref = malstone_run(log, CFG.num_sites, mesh=mesh, backend="streams")
        got = malstone_run(log, CFG.num_sites, mesh=mesh,
                           backend="mapreduce", packed_shuffle=True,
                           histogram_fn=hist_fn)
        assert_exact(got, ref, "packed shuffle + Pallas histogram_fn")


# ------------------------------------------------- ShuffleStats contract
class TestShuffleStatsDefaults:
    def test_trailing_defaults_are_typed_int32_scalars(self):
        """Regression (satellite): the defaults used to be Python ints
        annotated as jnp.ndarray — weakly typed inside jit, so psums and
        uint32 consumers relied on implicit promotion. They must be numpy
        int32 scalars: concrete dtype, no jax backend init at import."""
        for field in ("rounds", "residual", "bytes_exchanged"):
            default = ShuffleStats._field_defaults[field]
            assert isinstance(default, np.int32), (field, type(default))
            assert not jnp.asarray(default).weak_type, field

    def test_default_constructed_stats_leaves_all_typed(self):
        stats = ShuffleStats(sent=jnp.int32(5), overflow=jnp.int32(0),
                             capacity=jnp.int32(8))
        for leaf in jax.tree_util.tree_leaves(stats):
            assert jnp.asarray(leaf).dtype == jnp.int32
            assert not jnp.asarray(leaf).weak_type

    def test_bytes_exchanged_formula(self, mesh, logs):
        """bytes = rounds x P x capacity x slot-bytes, psum'd (P=1 here):
        the fixed-capacity buffers cross the network whole every round."""
        _, adversarial = logs
        for packed, slot in ((True, PACKED_SLOT_BYTES),
                             (False, UNPACKED_SLOT_BYTES)):
            _, stats = malstone_run(
                adversarial, CFG.num_sites, mesh=mesh, backend="mapreduce",
                capacity_factor=0.5, packed_shuffle=packed,
                return_shuffle_stats=True)
            assert int(stats.bytes_exchanged) == (
                int(stats.rounds) * int(stats.capacity) * slot), packed


# ------------------------------------------------------ launcher plumbing
def _run_launcher(tmp_path, *extra):
    out = tmp_path / "BENCH_launch.json"
    env = dict(os.environ)
    env["PYTHONPATH"] = (str(pathlib.Path(__file__).parent.parent / "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.malstone",
         "--nodes", "1", "--records-per-node", "1024",
         "--sites", "64", "--entities", "256", "--runs", "1",
         "--bench-json", str(out), *extra],
        capture_output=True, text=True, timeout=600, env=env)
    assert proc.returncode == 0, proc.stderr[-4000:]
    doc = json.loads(out.read_text())
    (entry,) = doc["results"]
    return proc.stdout, entry


@pytest.mark.slow
def test_launcher_packed_shuffle_flag(tmp_path):
    """--packed-shuffle on/off both run losslessly, report the path and
    bytes in stdout + BENCH derived, and the on/off byte ratio is 17/4."""
    out_on, on = _run_launcher(
        tmp_path, "--backend", "mapreduce", "--capacity-factor", "0.5",
        "--packed-shuffle", "on")
    assert "shuffle: packed" in out_on
    out_off, off = _run_launcher(
        tmp_path, "--backend", "mapreduce", "--capacity-factor", "0.5",
        "--packed-shuffle", "off")
    assert "shuffle: unpacked" in out_off
    assert on["params"]["packed_shuffle"] == "on"
    assert on["derived"]["shuffle_packed"] is True
    assert off["derived"]["shuffle_packed"] is False
    assert on["derived"]["shuffle_overflow"] == 0
    assert off["derived"]["shuffle_bytes_exchanged"] == (
        on["derived"]["shuffle_bytes_exchanged"] * 17 // 4)


@pytest.mark.slow
def test_launcher_histogram_impl_pallas(tmp_path):
    """--histogram-impl pallas reaches the Pallas segment_hist kernel from
    the production launcher (interpret mode on CPU) and the statistic still
    matches the shuffle's lossless accounting."""
    stdout, entry = _run_launcher(
        tmp_path, "--backend", "mapreduce", "--histogram-impl", "pallas",
        "--packed-shuffle", "on")
    assert "histogram: Pallas segment_hist kernel" in stdout
    assert "overflow=0 (lossless)" in stdout
    assert entry["params"]["histogram_impl"] == "pallas"
