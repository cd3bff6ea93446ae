"""The benchmark's frozen MalGen copy and plain reference, on the CPU.

The copy must give records bit-equal to the program's ``repro.malgen``; the
reference must give the same MalStone B as ``repro.core.spm`` on a seeded
log, and its controls (the ratio in bfloat16; the counts summed in float32,
once a total passes 2^24) must fail the comparison.
"""

import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH_DIR))

import malgen_frozen  # noqa: E402
import reference  # noqa: E402
import sources  # noqa: E402

from repro.core import spm  # noqa: E402
from repro.malgen import generate_chunk, make_seed_streaming  # noqa: E402

CONFIG = {"num_sites": 512, "num_entities": 4096, "alpha": 1.2,
          "marked_site_fraction": 0.1, "marked_event_fraction": 0.1,
          "p_mark": 0.7, "mark_delay_s": 604800, "span_s": 31536000,
          "num_weeks": 52}
CHUNKS, C = 4, 1 << 12
DEP = sources.deployment(CONFIG)
CFG = sources.program_config(CONFIG)


def _bits_equal(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return a.shape == b.shape and a.dtype == b.dtype and \
        a.tobytes() == b.tobytes()


@pytest.mark.parametrize("jit", [False, True], ids=["eager", "jit"])
@pytest.mark.parametrize("key", [0, 2**31 + 7])
def test_frozen_seed_bit_equal_to_program(jit, key):
    def make(fn, *args):
        return (jax.jit(fn, static_argnums=(1, 2, 3)) if jit else fn)(*args)

    k = jax.random.key(key)
    got = make(malgen_frozen.make_seed, k, DEP, CHUNKS, C)
    want = make(make_seed_streaming, k, CFG, CHUNKS, C)
    for field in ("marked_mask", "entity_mark_time", "marked_cdf",
                  "unmarked_cdf"):
        assert _bits_equal(getattr(got, field), getattr(want, field)), field


@pytest.mark.parametrize("chunk_id", [0, 3, 1000])
def test_frozen_chunk_bit_equal_to_program(chunk_id):
    k = jax.random.key(2**31 + 99)
    seed = make_seed_streaming(k, CFG, CHUNKS, C)
    frozen_seed = malgen_frozen.make_seed(k, DEP, CHUNKS, C)
    got = malgen_frozen.generate_chunk(frozen_seed, DEP, jnp.int32(chunk_id),
                                       C)
    want = generate_chunk(seed, CFG, jnp.int32(chunk_id), C)
    for field in malgen_frozen.Records._fields:
        assert _bits_equal(getattr(got, field), getattr(want, field)), field


@pytest.fixture(scope="module")
def seeded_log():
    seed = make_seed_streaming(jax.random.key(5), CFG, CHUNKS, C)
    return [generate_chunk(seed, CFG, i, C) for i in range(CHUNKS)]


def _reference_counts(chunks):
    keys = (reference.record_keys(c.site_id, c.timestamp, c.mark, 52)
            for c in chunks)
    return reference.count_keys(keys, CONFIG["num_sites"], 52)


def test_reference_equals_program_oracle(seeded_log):
    want = reference.malstone_b(_reference_counts(seeded_log))
    hist = sum(spm.site_week_histogram(c, CONFIG["num_sites"])
               for c in seeded_log)
    got = spm.malstone_b(hist)
    numbers = reference.compare(
        reference.Answer(*(np.asarray(x) for x in got)), want)
    assert numbers == {"total_mismatch": 0, "marked_mismatch": 0,
                       "rho_ulp": 0}
    assert want.total[:, -1].sum() == CHUNKS * C


def test_reference_keys_same_on_host_and_device(seeded_log):
    c = seeded_log[0]
    on_device = reference.record_keys(c.site_id, c.timestamp, c.mark, 52)
    on_host = reference.record_keys(np.asarray(c.site_id),
                                    np.asarray(c.timestamp),
                                    np.asarray(c.mark), 52)
    assert _bits_equal(on_device, on_host)


def test_control_fails_the_comparison(seeded_log):
    import harness

    counts = _reference_counts(seeded_log)
    numbers = reference.compare(reference.control(counts),
                                reference.malstone_b(counts))
    assert numbers["rho_ulp"] > harness.LIMITS["rho_ulp"]


@pytest.mark.parametrize("weekly, fails", [(300_001, False),
                                           (600_001, True)])
def test_count_control_fails_once_a_total_passes_2_24(weekly, fails):
    """Counts summed in float32 are exact below 2^24 and lose records above:
    a site with ``weekly`` records a week passes 2^24 within 52 weeks only
    at 600,001, and then the exact comparison of ``total`` fails."""
    import harness

    counts = np.zeros((2, 52, 2), np.int64)
    counts[0, :] = (weekly - 1, 1)
    counts[1, :] = (3, 0)
    want = reference.malstone_b(counts)
    assert (want.total[0, -1] >= 2**24) == fails
    numbers = reference.compare(reference.count_control(counts), want)
    passed = all(numbers[k] <= v for k, v in harness.LIMITS.items())
    assert passed is not fails
    assert (numbers["total_mismatch"] > 0) == fails


def test_compare_counts_each_kind_of_difference():
    counts = np.zeros((3, 4, 2), np.int64)
    counts[0, 0] = (2, 1)
    counts[2, 3] = (5, 5)
    want = reference.malstone_b(counts)
    assert reference.compare(want, want) == {
        "total_mismatch": 0, "marked_mismatch": 0, "rho_ulp": 0}
    bad = reference.Answer(np.nextafter(want.rho, 2, dtype=np.float32),
                           want.total + 1, want.marked)
    assert reference.compare(bad, want) == {
        "total_mismatch": want.total.size, "marked_mismatch": 0,
        "rho_ulp": 1}
    nan = reference.Answer(want.rho * np.nan, want.total, want.marked)
    assert reference.compare(nan, want)["rho_ulp"] == 2**31 - 1


def test_count_keys_refuses_keys_off_the_grid():
    with pytest.raises(ValueError):
        reference.count_keys([np.array([3 * 4 * 2])], 3, 4)
