"""MalGen's site table: one lookup per draw gives the site the inverse-CDF
search gives, bit for bit.

``powerlaw.site_table`` evaluates ``sample_sites``' search on every value a
float32 uniform draw can take (``k * 2^-23``, ``k < 2^23``); ``draw_sites``
looks each draw up there. The table is checked against the search on the
whole grid, built here the way ``jax.random.uniform`` builds its floats;
the records generated through it against records generated by searching;
and the compiled streaming job for the search's loop, which must not come
back into generation.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.types import EventLog, ExchangePlan
from repro.core import run
from repro.launch.mesh import make_mesh
from repro.malgen import (
    MalGenConfig,
    generate_chunk,
    generate_shard,
    generate_shard_device,
    make_seed,
    make_seed_streaming,
)
from repro.malgen import seeding
from repro.malgen.powerlaw import (
    SITE_TABLE_SIZE,
    UNIFORM_BITS,
    draw_sites,
    sample_sites,
    site_table,
)

CFG = MalGenConfig()                       # 100k sites, alpha 1.2, 10% marked
CHUNKS, CHUNK = 160, 1 << 16               # 10.5M records: the seed has tables


def _bits_equal(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return a.shape == b.shape and a.dtype == b.dtype and \
        a.tobytes() == b.tobytes()


@jax.jit
def _searched_on_grid(cdf):
    """The search and clip of ``sample_sites`` on every float32 uniform
    value, each made as ``jax.random.uniform`` makes it: the mantissa of a
    float in [1, 2), minus 1."""
    k = jnp.arange(SITE_TABLE_SIZE, dtype=jnp.uint32)
    u = jax.lax.bitcast_convert_type(k | jnp.uint32(0x3F800000),
                                     jnp.float32) - jnp.float32(1.0)
    idx = jnp.searchsorted(cdf, u, side="right")
    return jnp.clip(idx, 0, cdf.shape[0] - 1).astype(jnp.int32)


def _counted_table(cdf):
    """The table a count of grid points under each CDF entry would give:
    right for a monotone CDF only."""
    grid = np.arange(SITE_TABLE_SIZE, dtype=np.float32) \
        * np.float32(2.0 ** -UNIFORM_BITS)
    counts = (np.asarray(cdf)[None, :] <= grid[:, None]).sum(axis=1)
    return np.minimum(counts, cdf.shape[0] - 1).astype(np.int32)


@pytest.fixture(scope="module")
def malgen_cdfs():
    _, _, mask, marked, unmarked, _, _ = seeding._site_tables(
        jax.random.key(2**31 + 11), CFG, 0)
    assert int(mask.sum()) == CFG.num_marked_sites
    return {"marked": marked, "unmarked": unmarked}


@pytest.mark.parametrize("which", ["marked", "unmarked"])
def test_table_equals_search_on_every_draw(malgen_cdfs, which):
    cdf = malgen_cdfs[which]
    assert cdf.shape == (CFG.num_sites,)
    assert _bits_equal(site_table(cdf), _searched_on_grid(cdf))


# Plateaus, steps down (as a float32 cumsum can take), an entry on a grid
# point (0.25 = 2^21 * 2^-23, where side="right" matters) and a last entry
# under 1 (where the clip matters).
HAND_CDFS = {
    "non_monotone": [0.1, 0.1, 0.25, 0.5, 0.2, 0.5, 0.5, 0.75, 0.7, 0.9],
    "plateaus_short_of_one": [0.0, 0.0, 0.25, 0.25, 0.25, 0.6, 0.6, 0.6],
    "all_zero": [0.0, 0.0, 0.0, 0.0],
}


@pytest.mark.parametrize("name", sorted(HAND_CDFS))
def test_table_equals_search_on_hand_made_cdfs(name):
    cdf = jnp.asarray(HAND_CDFS[name], jnp.float32)
    table = site_table(cdf)
    assert _bits_equal(table, _searched_on_grid(cdf))
    if name == "non_monotone":
        # the case a table built by counting gets wrong
        assert not _bits_equal(table, _counted_table(cdf))


@pytest.mark.parametrize("key", [0, 1, 2**31 + 3, 2**32 - 1])
def test_uniform_draws_lie_on_the_grid(key):
    u = np.asarray(jax.random.uniform(jax.random.key(key), (1 << 20,),
                                      dtype=jnp.float32))
    scaled = u.astype(np.float64) * SITE_TABLE_SIZE
    assert np.array_equal(scaled, np.floor(scaled))
    assert scaled.min() >= 0 and scaled.max() < SITE_TABLE_SIZE


@pytest.mark.parametrize("key", [5, 2**31 + 17])
def test_draws_through_the_table_equal_the_search(malgen_cdfs, key):
    for cdf in malgen_cdfs.values():
        k = jax.random.key(key)
        assert _bits_equal(draw_sites(k, cdf, site_table(cdf), 1 << 20),
                           sample_sites(k, cdf, 1 << 20))


def _searching(seed):
    """The same seed without its site tables: generation searches."""
    return seed._replace(marked_table=None, unmarked_table=None)


@pytest.fixture(scope="module")
def streaming_seed():
    seed = make_seed_streaming(jax.random.key(2**31 + 29), CFG, CHUNKS, CHUNK)
    assert seed.marked_table is not None and seed.unmarked_table is not None
    return seed


@pytest.mark.parametrize("chunk_id", [0, 1, CHUNKS - 1])
def test_chunk_through_the_table_is_bit_equal(streaming_seed, chunk_id):
    gen = jax.jit(lambda s, i: generate_chunk(s, CFG, i, CHUNK))
    got = gen(streaming_seed, jnp.int32(chunk_id))
    want = gen(_searching(streaming_seed), jnp.int32(chunk_id))
    for field in EventLog._fields[:6]:
        assert _bits_equal(getattr(got, field), getattr(want, field)), field


SHARDS, SHARD_RECORDS = 64, 1 << 17        # 2^23 records: the seed has tables


@pytest.fixture(scope="module")
def one_shot_seed():
    seed = make_seed(jax.random.key(41), CFG, SHARDS * SHARD_RECORDS)
    assert seed.marked_table is not None
    return seed


def test_one_shot_mark_table_is_bit_equal(one_shot_seed):
    """``make_seed`` draws every marked event's site through the table."""
    k_events = jax.random.split(one_shot_seed.key, 3)[2]
    searched = seeding._derive_mark_table(
        k_events, CFG, one_shot_seed.marked_cdf, None,
        one_shot_seed.num_marked_events)
    assert _bits_equal(one_shot_seed.entity_mark_time, searched)


@pytest.mark.parametrize("generate", [generate_shard, generate_shard_device],
                         ids=["host", "device"])
def test_shard_through_the_table_is_bit_equal(one_shot_seed, generate):
    got = generate(one_shot_seed, CFG, 3, SHARDS, SHARD_RECORDS)
    want = generate(_searching(one_shot_seed), CFG, 3, SHARDS, SHARD_RECORDS)
    for field in EventLog._fields[:6]:
        assert _bits_equal(getattr(got, field), getattr(want, field)), field


def test_small_seed_keeps_no_table():
    """A log smaller than a table searches: building the table would cost
    more searches than generation saves."""
    seed = make_seed_streaming(jax.random.key(1), CFG, 4, 1 << 10)
    assert seed.marked_table is None and seed.unmarked_table is None


# ------------------------------------------------- the compiled streaming job
GUARD_CFG = MalGenConfig(num_sites=301, num_entities=1000,
                         marked_site_fraction=0.2, marked_event_fraction=0.3)
GUARD_CHUNKS = 128                         # 128 x 2^16 = 2^23 records


def _generate_ops(seed):
    """(opcode, op path) of every instruction of the compiled streaming
    seed job (streams, MalStone B, one device) whose path is under
    ``malstone.generate``."""
    mesh = make_mesh((1,), ("data",), devices=jax.devices()[:1])

    def malstone_job(x):
        return run(x, GUARD_CFG.num_sites, mesh=mesh, engine="streaming",
                   backend="streams", statistic="B", chunk_records=CHUNK,
                   plan=ExchangePlan(), num_chunks=GUARD_CHUNKS,
                   cfg=GUARD_CFG)

    ops = []
    for line in jax.jit(malstone_job).lower(seed).compile().as_text() \
            .splitlines():
        path = re.search(r'op_name="([^"]*)"', line)
        if " = " in line and path and "malstone.generate" in path.group(1):
            rest = line.split(" = ", 1)[1]
            opcode = re.search(r"(?:^|[\s)])([a-z][\w\-]*)\(", rest)
            ops.append((opcode.group(1), path.group(1)))
    return ops


@pytest.fixture(scope="module")
def guard_seed():
    seed = make_seed_streaming(jax.random.key(3), GUARD_CFG, GUARD_CHUNKS,
                               CHUNK)
    assert seed.marked_table is not None
    return seed


# On the CPU, threefry lowers to rolled loops: a ``while`` inside one of
# JAX's random functions is the PRNG's own.
PRNG_CALLS = ("jit(_uniform)", "jit(_randint)", "jit(_threefry")


def _is_search(op, path):
    inner = path.split("malstone.generate/", 1)[-1]
    return "searchsorted" in path or \
        (op == "while" and not inner.startswith(PRNG_CALLS))


@pytest.mark.parametrize("tables", [True, False], ids=["table", "searched"])
def test_generation_runs_no_search_loop(guard_seed, tables):
    """No ``while`` but the PRNG's and no ``searchsorted`` op under
    ``malstone.generate`` once the seed has its tables; the same seed
    without them shows the search, so the check would see it come back."""
    ops = _generate_ops(guard_seed if tables else _searching(guard_seed))
    assert any(op == "gather" for op, _ in ops)
    search = [(op, path) for op, path in ops if _is_search(op, path)]
    assert (not search) if tables else search
