"""MalStone-as-a-service: bit-identity, batched queries, client surface.

The engine's contract mirrors the streaming scan's: chunk-by-chunk ingest
followed by ``snapshot`` must be *bit-identical* to ``malstone_run_streaming``
over the same chunks — integer histograms AND per-field ``ShuffleStats``
(``rounds`` depends on the exact (device, chunk) grouping, which is why
``ingest_slices`` exists). Every assertion on counts is assert_array_equal,
never allclose. Multi-device coverage (8 forced host devices) runs in a
subprocess (tests/md_scripts/serve_check.py).
"""

import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.types import (
    ExchangePlan,
    SECONDS_PER_WEEK,
    SECONDS_PER_YEAR,
    WindowSpec,
)
from repro.core import malstone_run_streaming
from repro.core.spm import (
    malstone_a,
    malstone_b,
    malstone_b_fixed_denominator,
)
from repro.launch.mesh import make_mesh
from repro.malgen import (
    MalGenConfig,
    generate_chunked_log,
    make_seed_streaming,
)
from repro.serve import (
    MalStoneService,
    QuerySpec,
    default_query_mix,
    encode_query_batch,
    growing_window_specs,
    ingest_slices,
    query_masks,
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


@pytest.fixture(scope="module")
def resident(mesh, seed_and_log):
    """A fully-ingested streams service shared by the query tests."""
    seed, _ = seed_and_log
    svc = MalStoneService(mesh=mesh, num_sites=CFG.num_sites,
                          chunk_records=CHUNK, backend="streams",
                          seed=seed, cfg=CFG, num_chunks=NUM_CHUNKS)
    svc.ingest_chunks(NUM_CHUNKS)
    return svc


def assert_stats_equal(got, ref, msg=""):
    for f in ref._fields:
        assert int(getattr(got, f)) == int(getattr(ref, f)), (
            f"{msg}: ShuffleStats.{f} differs "
            f"({int(getattr(got, f))} != {int(getattr(ref, f))})")


# --------------------------------------------------------------------------
# incremental-ingest bit-identity
# --------------------------------------------------------------------------

@pytest.mark.parametrize("backend", BACKENDS)
def test_seed_ingest_bit_identical(mesh, seed_and_log, backend):
    """Chunk-by-chunk seed-mode ingest (uneven schedule) == one streaming
    run: integer histograms and, for mapreduce, every ShuffleStats field."""
    seed, _ = seed_and_log
    ref, ref_stats = malstone_run_streaming(
        seed, CFG.num_sites, mesh=mesh, backend=backend, cfg=CFG,
        num_chunks=NUM_CHUNKS, chunk_records=CHUNK,
        return_shuffle_stats=True)
    svc = MalStoneService(mesh=mesh, num_sites=CFG.num_sites,
                          chunk_records=CHUNK, backend=backend,
                          seed=seed, cfg=CFG, num_chunks=NUM_CHUNKS)
    for k in (1, 3, 2, 2):  # deliberately uneven ingest schedule
        svc.ingest_chunks(k)
    res = svc.result("B")
    _, stats = svc.snapshot()
    np.testing.assert_array_equal(np.asarray(res.total),
                                  np.asarray(ref.total), err_msg=backend)
    np.testing.assert_array_equal(np.asarray(res.marked),
                                  np.asarray(ref.marked), err_msg=backend)
    if backend == "mapreduce":
        assert_stats_equal(stats, ref_stats, backend)
    assert svc.chunks_folded == NUM_CHUNKS


@pytest.mark.parametrize("backend", BACKENDS)
def test_log_ingest_bit_identical(mesh, seed_and_log, backend):
    """ingest_slices cuts the log into the streaming scan's exact (device,
    chunk) grouping, so log-mode ingest reproduces the one-shot streaming
    run bit-for-bit — including an uneven final chunk (padding path)."""
    _, log = seed_and_log
    odd = jax.tree.map(lambda x: x[:3000], log)  # 3000 = 5*512 + 440
    ref, ref_stats = malstone_run_streaming(
        odd, CFG.num_sites, mesh=mesh, backend=backend, chunk_records=CHUNK,
        return_shuffle_stats=True)
    svc = MalStoneService(mesh=mesh, num_sites=CFG.num_sites,
                          chunk_records=CHUNK, backend=backend)
    n = svc.ingest_log(odd)
    assert n == 6  # ceil(3000 / 512)
    res = svc.result("B")
    _, stats = svc.snapshot()
    np.testing.assert_array_equal(np.asarray(res.total),
                                  np.asarray(ref.total), err_msg=backend)
    np.testing.assert_array_equal(np.asarray(res.marked),
                                  np.asarray(ref.marked), err_msg=backend)
    if backend == "mapreduce":
        assert_stats_equal(stats, ref_stats, backend)


def test_counting_exchange_plan_variant(mesh, seed_and_log):
    """The service threads its ExchangePlan into every per-ingest shuffle:
    a forced counting-sort exchange stays bit-identical to the batch run
    under the same plan."""
    seed, _ = seed_and_log
    plan = ExchangePlan(impl="counting")
    ref, ref_stats = malstone_run_streaming(
        seed, CFG.num_sites, mesh=mesh, backend="mapreduce", cfg=CFG,
        num_chunks=NUM_CHUNKS, chunk_records=CHUNK, plan=plan,
        return_shuffle_stats=True)
    svc = MalStoneService(mesh=mesh, num_sites=CFG.num_sites,
                          chunk_records=CHUNK, backend="mapreduce",
                          seed=seed, cfg=CFG, num_chunks=NUM_CHUNKS,
                          plan=plan)
    svc.ingest_chunks(NUM_CHUNKS)
    res = svc.result("B")
    _, stats = svc.snapshot()
    np.testing.assert_array_equal(np.asarray(res.total),
                                  np.asarray(ref.total))
    assert_stats_equal(stats, ref_stats, "counting")


def test_ingest_slices_regroup_roundtrip(seed_and_log):
    """Sharding each yielded chunk over P devices and re-concatenating each
    device's blocks reconstructs that device's contiguous shard — the
    grouping invariant the ShuffleStats bit-identity rests on."""
    _, log = seed_and_log
    parts = 2
    chunks = list(ingest_slices(log, parts, CHUNK))
    per_dev = NUM_CHUNKS * CHUNK // parts
    full = np.asarray(log.site_id)
    for d in range(parts):
        got = np.concatenate([
            np.asarray(c.site_id)[d * CHUNK:(d + 1) * CHUNK] for c in chunks])
        np.testing.assert_array_equal(got,
                                      full[d * per_dev:(d + 1) * per_dev])


def test_reset_and_reingest(mesh, seed_and_log):
    seed, _ = seed_and_log
    svc = MalStoneService(mesh=mesh, num_sites=CFG.num_sites,
                          chunk_records=CHUNK, backend="streams",
                          seed=seed, cfg=CFG, num_chunks=NUM_CHUNKS)
    svc.ingest_chunks(NUM_CHUNKS)
    want = np.asarray(svc.result("B").total)
    svc.reset()
    hist, _ = svc.snapshot()
    assert svc.chunks_folded == 0
    assert not np.any(hist)
    svc.ingest_chunks(NUM_CHUNKS)
    np.testing.assert_array_equal(np.asarray(svc.result("B").total), want)


# --------------------------------------------------------------------------
# batched queries vs the per-statistic oracles
# --------------------------------------------------------------------------

def test_batched_b_matches_malstone_b_columns(resident):
    """The growing-window B batch IS malstone_b: query t's rho equals the
    oracle's column t for every site."""
    hist, _ = resident.snapshot()
    oracle = malstone_b(jnp.asarray(hist))
    answers = resident.query(growing_window_specs("B"))
    for t, ans in enumerate(answers):
        np.testing.assert_allclose(ans.rho, np.asarray(oracle.rho[:, t]),
                                   rtol=0, atol=0)
        np.testing.assert_array_equal(ans.num,
                                      np.asarray(oracle.marked[:, t]))
        np.testing.assert_array_equal(ans.den,
                                      np.asarray(oracle.total[:, t]))


def test_batched_a_and_bfixed_match_oracles(resident):
    hist, _ = resident.snapshot()
    year = WindowSpec.full_year()
    answers = resident.query([QuerySpec(statistic="A", window=year),
                              QuerySpec(statistic="B-fixed", window=year)])
    a = malstone_a(jnp.asarray(hist))
    np.testing.assert_array_equal(answers[0].num, np.asarray(a.marked))
    np.testing.assert_array_equal(answers[0].den, np.asarray(a.total))
    np.testing.assert_allclose(answers[0].rho, np.asarray(a.rho),
                               rtol=0, atol=0)
    bf = malstone_b_fixed_denominator(jnp.asarray(hist))
    np.testing.assert_array_equal(answers[1].num,
                                  np.asarray(bf.marked[:, -1]))
    np.testing.assert_array_equal(answers[1].den,
                                  np.asarray(bf.total[:, -1]))
    np.testing.assert_allclose(answers[1].rho, np.asarray(bf.rho[:, -1]),
                               rtol=0, atol=0)


def test_partial_window_a_matches_mask_einsum(resident):
    """An interior monitor window reduces to the masked integer einsum."""
    hist, _ = resident.snapshot()
    win = WindowSpec(0, SECONDS_PER_YEAR,
                     10 * SECONDS_PER_WEEK, 30 * SECONDS_PER_WEEK)
    spec = QuerySpec(statistic="A", window=win)
    [ans] = resident.query([spec])
    nm, dm = (np.asarray(m) for m in query_masks(spec))
    np.testing.assert_array_equal(ans.num,
                                  hist[..., 1] @ nm.astype(np.int32))
    np.testing.assert_array_equal(ans.den,
                                  hist[..., 0] @ dm.astype(np.int32))


def test_batch_equals_independent_queries(resident):
    """Stacking N specs into one dispatch returns exactly what N separate
    single-spec dispatches return."""
    specs = default_query_mix(num_sites=CFG.num_sites)
    batched = resident.query(specs)
    for spec, got in zip(specs, batched):
        [alone] = resident.query([spec])
        np.testing.assert_array_equal(got.rho, alone.rho)
        np.testing.assert_array_equal(got.num, alone.num)
        np.testing.assert_array_equal(got.den, alone.den)
        if spec.top_k:
            np.testing.assert_array_equal(got.top_rho, alone.top_rho)
        if spec.site is not None:
            assert got.site_rho == alone.site_rho


def test_topk_and_drilldown_oracles(resident):
    hist, _ = resident.snapshot()
    k = 7
    [ans] = resident.query([QuerySpec(statistic="B", top_k=k, site=13)])
    oracle = malstone_b(jnp.asarray(hist))
    rho = np.asarray(oracle.rho[:, -1])
    np.testing.assert_allclose(np.sort(ans.top_rho)[::-1],
                               np.sort(rho)[::-1][:k], rtol=0, atol=0)
    np.testing.assert_allclose(ans.top_rho, rho[ans.top_sites],
                               rtol=0, atol=0)
    np.testing.assert_array_equal(ans.site_total, hist[13, :, 0])
    np.testing.assert_array_equal(ans.site_marked, hist[13, :, 1])
    assert ans.site_rho == pytest.approx(float(rho[13]), abs=0)


def test_pallas_and_ref_kernel_paths_agree(mesh, seed_and_log):
    seed, _ = seed_and_log
    results = {}
    for path in ("pallas", "ref"):
        svc = MalStoneService(mesh=mesh, num_sites=CFG.num_sites,
                              chunk_records=CHUNK, backend="streams",
                              seed=seed, cfg=CFG, num_chunks=NUM_CHUNKS,
                              kernel_path=path)
        svc.ingest_chunks(NUM_CHUNKS)
        results[path] = svc.query(default_query_mix(
            num_sites=CFG.num_sites))
    for a, b in zip(results["pallas"], results["ref"]):
        np.testing.assert_array_equal(a.num, b.num)
        np.testing.assert_array_equal(a.den, b.den)
        np.testing.assert_allclose(a.rho, b.rho, rtol=1e-6, atol=1e-7)


def test_queries_interleave_with_ingest(mesh, seed_and_log):
    """Snapshot isolation: answers reflect exactly the chunks folded at
    submit time, and the next ingest refreshes the resident snapshot."""
    seed, _ = seed_and_log
    svc = MalStoneService(mesh=mesh, num_sites=CFG.num_sites,
                          chunk_records=CHUNK, backend="streams",
                          seed=seed, cfg=CFG, num_chunks=NUM_CHUNKS)
    spec = QuerySpec(statistic="B")
    svc.ingest_chunks(3)
    [early] = svc.query([spec])
    assert int(early.den.sum()) == 3 * CHUNK
    svc.ingest_chunks(5)
    [late] = svc.query([spec])
    assert int(late.den.sum()) == 8 * CHUNK


# --------------------------------------------------------------------------
# async submit / wait / stats surface
# --------------------------------------------------------------------------

def test_submit_wait_out_of_order(resident):
    t1 = resident.submit([QuerySpec(statistic="A")])
    t2 = resident.submit([QuerySpec(statistic="B"), QuerySpec("B-fixed")])
    assert t1 != t2
    assert resident.stats().pending == 2
    ans2 = resident.wait(t2)
    ans1 = resident.wait(t1)
    assert len(ans2) == 2 and len(ans1) == 1
    assert resident.stats().pending == 0
    with pytest.raises(KeyError, match="already-collected"):
        resident.wait(t1)


def test_wait_all_drains(resident):
    tickets = [resident.submit([QuerySpec(statistic="B")])
               for _ in range(3)]
    out = resident.wait_all()
    assert sorted(out) == sorted(tickets)
    assert resident.stats().pending == 0


def test_stats_accounting(mesh, seed_and_log):
    seed, _ = seed_and_log
    svc = MalStoneService(mesh=mesh, num_sites=CFG.num_sites,
                          chunk_records=CHUNK, backend="streams",
                          seed=seed, cfg=CFG, num_chunks=NUM_CHUNKS)
    svc.ingest_chunks(2)
    svc.ingest_chunks(1)
    svc.query(default_query_mix(num_sites=CFG.num_sites))
    s = svc.stats()
    assert s.chunks_folded == 3
    assert s.records_ingested == 3 * CHUNK
    assert s.ingest_calls == 2
    assert (s.batches_submitted, s.batches_answered) == (1, 1)
    assert (s.queries_submitted, s.queries_answered) == (5, 5)
    assert s.pending == 0


# --------------------------------------------------------------------------
# validation / error surface
# --------------------------------------------------------------------------

def test_ingest_rejects_wrong_chunk_size(mesh, seed_and_log):
    _, log = seed_and_log
    svc = MalStoneService(mesh=mesh, num_sites=CFG.num_sites,
                          chunk_records=CHUNK, backend="streams")
    with pytest.raises(ValueError, match="folds 512 per ingest"):
        svc.ingest(jax.tree.map(lambda x: x[:100], log))


def test_ingest_chunks_requires_seed_mode(mesh):
    svc = MalStoneService(mesh=mesh, num_sites=CFG.num_sites,
                          chunk_records=CHUNK, backend="streams")
    with pytest.raises(ValueError, match="seed-mode ingest is unavailable"):
        svc.ingest_chunks(1)


def test_ingest_chunks_overrun(mesh, seed_and_log):
    seed, _ = seed_and_log
    svc = MalStoneService(mesh=mesh, num_sites=CFG.num_sites,
                          chunk_records=CHUNK, backend="streams",
                          seed=seed, cfg=CFG, num_chunks=NUM_CHUNKS)
    svc.ingest_chunks(NUM_CHUNKS)
    with pytest.raises(ValueError, match="overruns the configured stream"):
        svc.ingest_chunks(1)


def test_constructor_validation(mesh, seed_and_log):
    seed, _ = seed_and_log
    with pytest.raises(ValueError, match="unknown streaming backend"):
        MalStoneService(mesh=mesh, num_sites=8, chunk_records=CHUNK,
                        backend="spark")
    with pytest.raises(ValueError, match="needs all of seed="):
        MalStoneService(mesh=mesh, num_sites=8, chunk_records=CHUNK,
                        seed=seed)
    with pytest.raises(ValueError, match="unknown kernel_path"):
        MalStoneService(mesh=mesh, num_sites=8, chunk_records=CHUNK,
                        kernel_path="triton")


def test_query_spec_validation():
    with pytest.raises(ValueError, match="unknown statistic"):
        QuerySpec(statistic="C")
    with pytest.raises(ValueError, match="top_k"):
        QuerySpec(top_k=-1)
    with pytest.raises(ValueError, match="site"):
        QuerySpec(site=-2)
    with pytest.raises(ValueError, match="at least one"):
        encode_query_batch([])
    with pytest.raises(ValueError, match="out of range"):
        encode_query_batch([QuerySpec(site=50)], num_sites=10)
    with pytest.raises(ValueError, match="exceeds num_sites"):
        encode_query_batch([QuerySpec(top_k=20)], num_sites=10)


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
def test_serve_bit_identical_on_8_devices():
    out = _run_md_script("serve_check.py")
    assert "ALL_OK" in out
