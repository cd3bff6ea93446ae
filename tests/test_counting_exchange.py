"""Counting-sort exchange + the ExchangePlan run-plan API.

The tentpole claim of the counting exchange (``exchange_impl="counting"``)
is that replacing the per-exchange stable argsort with a stable counting
sort — per-destination histogram, exclusive prefix sum, scatter; two O(n)
passes, ``repro.kernels.count_scatter`` — changes NOTHING observable:
a stable counting sort produces the *same permutation* as a stable
argsort, so ``words_sorted`` and ``starts`` are bit-identical and the
shared round loop yields identical histograms and identical ShuffleStats
on every field *including* ``bytes_exchanged`` (both paths move 4-byte
words). These tests pin that down at three layers: the kernel against its
jnp oracle and the argsort oracle (property tests incl. all-one-destination
skew), the drivers across all four backends x both engines x capacity
factors down to 0.1, and the plan-level API contract
(``ExchangePlan`` validation, deprecated kwarg aliases, the ``core.run``
dispatcher). The real multi-destination exchange runs on 8 forced host
devices in tests/md_scripts/counting_exchange_check.py.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.common.types import (
    EXCHANGE_IMPLS,
    ExchangePlan,
    PACK_MAX_SITES,
    PACK_MAX_WEEKS,
    resolve_exchange_plan,
)
from repro.core import (
    ENGINES,
    malstone_run,
    malstone_run_partitioned,
    malstone_run_resumable,
    malstone_run_streaming,
    pad_log_to,
    run,
)
from repro.core.backends.mapreduce import (
    PACKED_SLOT_BYTES,
    UNPACKED_SLOT_BYTES,
    resolve_exchange_impl,
)
from repro.kernels.count_scatter import count_scatter
from repro.kernels.count_scatter.ref import count_scatter_ref
from repro.launch.mesh import make_mesh
from repro.malgen import (
    MalGenConfig,
    generate_full_log,
    generate_sharded_log,
    make_seed_streaming,
)
from tests.test_backends import _run_md_script

CFG = MalGenConfig(num_sites=257, num_entities=700,
                   marked_site_fraction=0.2, marked_event_fraction=0.3)
N, CHUNK = 2048, 512
BACKENDS = ("streams", "sphere", "mapreduce", "mapreduce_combiner")
STAT_FIELDS = ("sent", "overflow", "capacity", "rounds", "residual",
               "bytes_exchanged")


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


def assert_stats_identical(a, b, msg=""):
    """EVERY ShuffleStats field, bytes_exchanged included: both word paths
    ship 4-byte slots, so even the wire accounting must agree exactly."""
    for field in STAT_FIELDS:
        assert int(getattr(a, field)) == int(getattr(b, field)), \
            f"{field} ({msg})"


def _mr(log, engine, mesh, plan, **kw):
    if engine == "oneshot":
        return malstone_run(log, CFG.num_sites, mesh=mesh,
                            backend="mapreduce", plan=plan,
                            return_shuffle_stats=True, **kw)
    return malstone_run_streaming(log, CFG.num_sites, mesh=mesh,
                                  backend="mapreduce", chunk_records=CHUNK,
                                  plan=plan, return_shuffle_stats=True, **kw)


# --------------------------------------------------- ExchangePlan contract
class TestExchangePlan:
    def test_defaults(self):
        plan = ExchangePlan()
        assert plan.impl == "auto"
        assert plan.capacity_factor == 2.0
        assert plan.max_shuffle_rounds is None
        assert plan.histogram_impl == "segment_sum"

    def test_frozen(self):
        with pytest.raises(dataclasses.FrozenInstanceError):
            ExchangePlan().impl = "sort"

    @pytest.mark.parametrize("bad", [
        dict(impl="radix"),
        dict(histogram_impl="triton"),
        dict(capacity_factor=0.0),
        dict(capacity_factor=-1.0),
        dict(max_shuffle_rounds=0),
    ])
    def test_validation_rejects(self, bad):
        with pytest.raises(ValueError):
            ExchangePlan(**bad)

    def test_plan_passthrough_is_silent(self, recwarn):
        plan = ExchangePlan(impl="counting", capacity_factor=0.5)
        assert resolve_exchange_plan(plan) is plan
        assert resolve_exchange_plan(None) == ExchangePlan()
        assert not [w for w in recwarn.list
                    if issubclass(w.category, DeprecationWarning)]

    @pytest.mark.parametrize("packed,impl", [(True, "sort"),
                                             (False, "columns"),
                                             (None, "auto")])
    def test_legacy_aliases_warn_and_map(self, packed, impl):
        with pytest.warns(DeprecationWarning, match="deprecated aliases"):
            plan = resolve_exchange_plan(
                None, capacity_factor=0.25, max_shuffle_rounds=9,
                packed_shuffle=packed, histogram_impl="pallas")
        assert plan == ExchangePlan(impl=impl, capacity_factor=0.25,
                                    max_shuffle_rounds=9,
                                    histogram_impl="pallas")

    def test_plan_plus_legacy_is_ambiguous(self):
        with pytest.raises(ValueError, match="not both"):
            resolve_exchange_plan(ExchangePlan(), capacity_factor=0.5)

    def test_driver_alias_matches_plan(self, mesh, logs):
        """The deprecated per-kwarg spelling and the plan spelling reach
        the exact same exchange: bit-identical result AND stats."""
        log, _ = logs
        with pytest.warns(DeprecationWarning, match="malstone_run"):
            got_legacy, stats_legacy = malstone_run(
                log, CFG.num_sites, mesh=mesh, backend="mapreduce",
                capacity_factor=0.5, packed_shuffle=True,
                return_shuffle_stats=True)
        got_plan, stats_plan = _mr(
            log, "oneshot", mesh,
            ExchangePlan(impl="sort", capacity_factor=0.5))
        assert_exact(got_legacy, got_plan, "legacy alias vs plan")
        assert_stats_identical(stats_legacy, stats_plan, "legacy vs plan")


class TestResolveExchangeImpl:
    def test_auto_prefers_counting(self):
        assert resolve_exchange_impl("auto", 512, 52) == "counting"
        assert resolve_exchange_impl(None, 512, 52) == "counting"

    def test_auto_falls_back_to_columns(self):
        assert resolve_exchange_impl("auto", PACK_MAX_SITES + 1,
                                     52) == "columns"
        assert resolve_exchange_impl("auto", 512,
                                     PACK_MAX_WEEKS + 1) == "columns"

    def test_legacy_packed_tristate(self):
        assert resolve_exchange_impl(None, 512, 52, packed=True) == "sort"
        assert resolve_exchange_impl(None, 512, 52, packed=False) == "columns"

    @pytest.mark.parametrize("impl", ("sort", "counting"))
    def test_forced_word_impl_unrepresentable_raises(self, impl):
        with pytest.raises(ValueError, match="cannot represent"):
            resolve_exchange_impl(impl, PACK_MAX_SITES + 1, 52)

    def test_unknown_impl_raises(self):
        with pytest.raises(ValueError, match="exchange impl"):
            resolve_exchange_impl("radix", 512, 52)

    def test_counting_auto_fallback_end_to_end(self, mesh, logs):
        """num_weeks > 64 on a real run: auto (-> columns) agrees with
        explicit columns exactly; forcing counting raises."""
        log, _ = logs
        auto = malstone_run(log, CFG.num_sites, mesh=mesh,
                            backend="mapreduce", num_weeks=65,
                            plan=ExchangePlan(impl="auto"))
        cols = malstone_run(log, CFG.num_sites, mesh=mesh,
                            backend="mapreduce", num_weeks=65,
                            plan=ExchangePlan(impl="columns"))
        assert_exact(auto, cols, "auto fallback vs explicit columns")
        with pytest.raises(ValueError, match="cannot represent"):
            malstone_run(log, CFG.num_sites, mesh=mesh, backend="mapreduce",
                         num_weeks=65, plan=ExchangePlan(impl="counting"))


# --------------------------------------------- count_scatter kernel vs ref
def _argsort_oracle(words, dest, num_partitions):
    order = jnp.argsort(dest, stable=True)
    starts = jnp.searchsorted(dest[order],
                              jnp.arange(num_partitions + 1)).astype(jnp.int32)
    return words[order], starts


def _random_case(seed, n, p):
    kd, kw = jax.random.split(jax.random.key(seed))
    # dest covers [0, p] — p is the exchange's invalid-row pseudo-destination
    dest = jax.random.randint(kd, (n,), 0, p + 1, dtype=jnp.int32)
    # random words are almost surely distinct, so words_sorted equality
    # checks the *permutation*, not just the multiset
    words = jax.random.bits(kw, (n,), dtype=jnp.uint32)
    return words, dest


def assert_scatter_equal(got, ref, msg=""):
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(ref[0]),
                                  err_msg=f"words_sorted ({msg})")
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(ref[1]),
                                  err_msg=f"starts ({msg})")


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 12), st.integers(1, 3000), st.integers(0, 10_000))
def test_ref_is_the_stable_argsort_property(p, n, seed):
    """Property: the jnp oracle == stable argsort + gather + searchsorted
    for any (P, n, data) — the exact equivalence the exchange relies on."""
    words, dest = _random_case(seed, n, p)
    assert_scatter_equal(count_scatter_ref(words, dest, p),
                         _argsort_oracle(words, dest, p),
                         f"p={p} n={n} seed={seed}")


class TestCountScatterKernel:
    """Pallas kernels (interpret mode on CPU) vs the jnp oracle."""

    @pytest.mark.parametrize("n,p,tile", [
        (1024, 4, 256),    # multi-tile, tiny dest space
        (1000, 7, 256),    # n not a multiple of the record tile
        (100, 3, 256),     # n smaller than one tile
        (2048, 16, 512),   # more destinations than a pod axis
    ])
    def test_kernel_matches_ref_random(self, n, p, tile):
        words, dest = _random_case(17, n, p)
        got = count_scatter(words, dest, p, impl="pallas", record_tile=tile,
                            interpret=True)
        assert_scatter_equal(got, count_scatter_ref(words, dest, p),
                             f"n={n} p={p} tile={tile}")

    @pytest.mark.parametrize("d0", (0, 3, 8))
    def test_all_one_destination_skew(self, d0):
        """Adversarial skew: every record lands on ONE destination (d0=8 is
        the invalid pseudo-destination). The rank pass must produce the
        identity permutation within the single segment."""
        n, p = 1500, 8
        words = jax.random.bits(jax.random.key(d0), (n,), dtype=jnp.uint32)
        dest = jnp.full((n,), d0, jnp.int32)
        got = count_scatter(words, dest, p, impl="pallas", record_tile=256,
                            interpret=True)
        ref = count_scatter_ref(words, dest, p)
        assert_scatter_equal(got, ref, f"one-destination d0={d0}")
        np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(words))

    def test_zero_words_invalid_rows(self):
        """The exchange's actual payload shape: invalid rows pack to word 0
        and route to the trailing pseudo-destination."""
        n, p = 800, 4
        words, dest = _random_case(23, n, p - 1)  # valid dests only
        invalid = jax.random.bernoulli(jax.random.key(5), 0.3, (n,))
        words = jnp.where(invalid, jnp.uint32(0), words)
        dest = jnp.where(invalid, p, dest).astype(jnp.int32)
        got = count_scatter(words, dest, p, impl="pallas", record_tile=256,
                            interpret=True)
        assert_scatter_equal(got, count_scatter_ref(words, dest, p),
                             "invalid rows")

    def test_dispatch_validates_impl(self):
        words, dest = _random_case(1, 64, 2)
        with pytest.raises(ValueError, match="impl must be"):
            count_scatter(words, dest, 2, impl="bogus")


# ------------------------------------------- counting-vs-sort bit identity
class TestCountingBitIdentity:
    @pytest.mark.parametrize("cf", (0.1, 0.5, 2.0))
    @pytest.mark.parametrize("engine", ("oneshot", "streaming"))
    def test_adversarial_counting_equals_sort(self, mesh, logs, engine, cf):
        """All records on one site, capacity down to 0.1x, both engines:
        counting and sort agree on the histogram AND on every ShuffleStats
        field — bytes_exchanged included (same 4-byte packed slots)."""
        _, adversarial = logs
        got_c, stats_c = _mr(adversarial, engine, mesh,
                             ExchangePlan(impl="counting",
                                          capacity_factor=cf))
        got_s, stats_s = _mr(adversarial, engine, mesh,
                             ExchangePlan(impl="sort", capacity_factor=cf))
        assert_exact(got_c, got_s, f"{engine}/cf={cf}")
        assert_stats_identical(stats_c, stats_s, f"{engine}/cf={cf}")
        assert int(stats_c.overflow) == 0

    @pytest.mark.parametrize("backend", BACKENDS)
    @pytest.mark.parametrize("engine", ("oneshot", "streaming"))
    def test_counting_plan_across_backends(self, mesh, logs, backend,
                                           engine):
        """One counting plan drives a full backend x engine sweep: every
        combination reproduces the streams oracle exactly (non-mapreduce
        backends ignore the exchange fields by contract)."""
        log, _ = logs
        ref = malstone_run(log, CFG.num_sites, mesh=mesh, backend="streams")
        plan = ExchangePlan(impl="counting", capacity_factor=0.5)
        if engine == "oneshot":
            got = malstone_run(log, CFG.num_sites, mesh=mesh,
                               backend=backend, plan=plan)
        else:
            got = malstone_run_streaming(log, CFG.num_sites, mesh=mesh,
                                         backend=backend,
                                         chunk_records=CHUNK, plan=plan)
        assert_exact(got, ref, f"{backend}/{engine} vs streams oracle")

    def test_counting_with_padding_rows(self, mesh, logs):
        """Padded (valid=False) rows ride through the counting exchange to
        the pseudo-destination without polluting the histogram."""
        log, _ = logs
        odd = jax.tree.map(lambda x: x[: N - 100], log)
        padded = pad_log_to(odd, N)
        ref = malstone_run(odd, CFG.num_sites, mesh=mesh, backend="streams")
        got, stats = malstone_run(
            padded, CFG.num_sites, mesh=mesh, backend="mapreduce",
            plan=ExchangePlan(impl="counting", capacity_factor=0.5),
            return_shuffle_stats=True)
        assert_exact(got, ref, "counting exchange over padded log")
        assert int(stats.sent) == N - 100     # padding rows never shipped
        assert int(stats.overflow) == 0

    def test_counting_vs_columns_byte_ratio(self, mesh, logs):
        """Counting ships 4-byte words, the column fallback 17-byte slots;
        all other accounting is identical."""
        _, adversarial = logs
        got_c, stats_c = _mr(adversarial, "oneshot", mesh,
                             ExchangePlan(impl="counting",
                                          capacity_factor=0.5))
        got_u, stats_u = _mr(adversarial, "oneshot", mesh,
                             ExchangePlan(impl="columns",
                                          capacity_factor=0.5))
        assert_exact(got_c, got_u, "counting vs columns")
        for field in ("sent", "overflow", "capacity", "rounds", "residual"):
            assert int(getattr(stats_c, field)) == \
                int(getattr(stats_u, field)), field
        assert int(stats_u.bytes_exchanged) == (
            int(stats_c.bytes_exchanged)
            * UNPACKED_SLOT_BYTES // PACKED_SLOT_BYTES)

    @pytest.mark.parametrize("engine", ("oneshot", "streaming"))
    def test_fused_pallas_reducer_bit_identical(self, mesh, logs, engine):
        """histogram_impl="pallas" on the counting exchange reduces the
        shuffled *words* directly (fused unpack+segment_hist kernel) — the
        unpacked columns are never materialized, and the result + stats
        still match the segment_sum reducer bit-for-bit."""
        log, _ = logs
        got_p, stats_p = _mr(log, engine, mesh,
                             ExchangePlan(impl="counting",
                                          capacity_factor=0.5,
                                          histogram_impl="pallas"))
        got_s, stats_s = _mr(log, engine, mesh,
                             ExchangePlan(impl="counting",
                                          capacity_factor=0.5))
        assert_exact(got_p, got_s, f"fused pallas reducer ({engine})")
        assert_stats_identical(stats_p, stats_s, f"pallas reducer {engine}")


# ------------------------------------------------- core.run dispatcher
class TestRunDispatcher:
    PLAN = ExchangePlan(impl="counting", capacity_factor=0.5)

    def test_oneshot_log_routes_to_malstone_run(self, mesh, logs):
        log, _ = logs
        got, stats = run(log, CFG.num_sites, mesh=mesh, backend="mapreduce",
                         plan=self.PLAN, return_shuffle_stats=True)
        ref, ref_stats = _mr(log, "oneshot", mesh, self.PLAN)
        assert_exact(got, ref, "run() oneshot")
        assert_stats_identical(stats, ref_stats, "run() oneshot")

    def test_streaming_log_routes_to_streaming(self, mesh, logs):
        log, _ = logs
        got, stats = run(log, CFG.num_sites, mesh=mesh, engine="streaming",
                         backend="mapreduce", chunk_records=CHUNK,
                         plan=self.PLAN, return_shuffle_stats=True)
        ref, ref_stats = _mr(log, "streaming", mesh, self.PLAN)
        assert_exact(got, ref, "run() streaming")
        assert_stats_identical(stats, ref_stats, "run() streaming")

    def test_generated_seed_matches_materialized(self, mesh):
        """A seed source through engine="generated" equals the one-shot
        run over the materialized sharded log (num_sites from cfg)."""
        log, seed = generate_sharded_log(jax.random.key(3), CFG,
                                         num_shards=1, records_per_shard=N)
        got = run(seed, mesh=mesh, engine="generated", cfg=CFG,
                  records_per_shard=N, backend="mapreduce", plan=self.PLAN)
        ref = malstone_run(log, CFG.num_sites, mesh=mesh,
                           backend="mapreduce", plan=self.PLAN)
        assert_exact(got, ref, "run() generated seed vs materialized")

    def test_partitioned_oneshot_log(self, mesh, logs):
        log, _ = logs
        got, stats = run(log, CFG.num_sites, mesh=mesh, partitioned=True,
                         backend="mapreduce", plan=self.PLAN,
                         return_shuffle_stats=True)
        ref, ref_stats = malstone_run_partitioned(
            log, CFG.num_sites, mesh=mesh, backend="mapreduce",
            plan=self.PLAN, return_shuffle_stats=True)
        assert_exact(got, ref, "run() partitioned")
        assert_stats_identical(stats, ref_stats, "run() partitioned")

    def test_engines_constant_is_exhaustive(self):
        assert ENGINES == ("oneshot", "streaming", "generated",
                           "generated_streaming", "resumable")
        assert set(EXCHANGE_IMPLS) == {"auto", "sort", "columns", "counting"}

    def test_error_cases(self, mesh, logs):
        log, _ = logs
        with pytest.raises(ValueError, match="unknown engine"):
            run(log, CFG.num_sites, mesh=mesh, engine="batch")
        with pytest.raises(ValueError, match="requires num_sites"):
            run(log, mesh=mesh)
        with pytest.raises(ValueError, match="requires cfg"):
            run(object(), mesh=mesh, engine="generated")
        with pytest.raises(ValueError, match="SeedInfo source"):
            run(log, CFG.num_sites, mesh=mesh, engine="generated")
        with pytest.raises(ValueError, match="partitioned"):
            run(log, CFG.num_sites, mesh=mesh, engine="streaming",
                partitioned=True)


# ---------------------------------------------- resume-path plan threading
def test_resumable_counting_bit_identical(mesh, tmp_path):
    """The counting plan survives the checkpointed segment loop: resumable
    == plain streaming (histogram AND accumulated stats), and the plan is
    part of the run fingerprint so the checkpoint round-trips."""
    seed = make_seed_streaming(jax.random.key(7), CFG, 8, CHUNK)
    plan = ExchangePlan(impl="counting", capacity_factor=0.5)
    ref, ref_stats = malstone_run_streaming(
        seed, CFG.num_sites, mesh=mesh, backend="mapreduce",
        chunk_records=CHUNK, cfg=CFG, num_chunks=8, plan=plan,
        return_shuffle_stats=True)
    out = malstone_run_resumable(
        seed, CFG, mesh=mesh, num_chunks=8, chunk_records=CHUNK,
        segment_chunks=2, backend="mapreduce", plan=plan,
        checkpoint_dir=str(tmp_path))
    assert_exact(out.result, ref, "resumable counting")
    assert_stats_identical(out.shuffle_stats, ref_stats,
                           "resumable counting")


# ------------------------------------------------ real multi-device mesh
@pytest.mark.slow
def test_counting_exchange_on_8_devices():
    out = _run_md_script("counting_exchange_check.py")
    assert "ALL_OK" in out
