"""A frozen copy of MalGen's chunk-keyed generator (paper section 5).

The benchmark makes its inputs and checks its outputs with this copy, never
with ``repro.malgen``: a later change to the program's generator then moves
neither the resident log a cell reads nor the records the reference counts.
It follows ``repro.malgen`` as it stood when the benchmark was defined
(``powerlaw``, ``seeding.make_seed_streaming``, ``generator.generate_chunk``)
and must stay bit-equal to it; ``tests/test_bench_gen.py`` checks that.

Sites are drawn from a power law over a random permutation of site ids, by
inverse-CDF search of two seed-time CDFs (marked and unmarked sites). Each
chunk's randomness is keyed by ``fold_in(key, chunk_id)``, so the log is a
pure function of (key, chunk_id). An entity is marked ``mark_delay`` after
its earliest marking visit (a marked-site visit marks it with probability
``p_mark``); a record's ``mark`` is 1 when the entity was already marked at
the visit.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

SECONDS_PER_WEEK = 7 * 86_400
SECONDS_PER_YEAR = 365 * 86_400
NEVER_MARKED = 2**31 - 1


class Deployment(NamedTuple):
    """MalGen's parameters, named as in a configuration file."""

    num_sites: int
    num_entities: int
    alpha: float
    marked_site_fraction: float
    marked_event_fraction: float
    p_mark: float
    mark_delay_s: int
    span_s: int

    @property
    def num_marked_sites(self) -> int:
        return max(1, int(self.num_sites * self.marked_site_fraction))


class Seed(NamedTuple):
    key: jax.Array
    marked_mask: jax.Array       # bool [num_sites]
    entity_mark_time: jax.Array  # int32 [num_entities]
    marked_cdf: jax.Array        # float32 [num_sites]
    unmarked_cdf: jax.Array      # float32 [num_sites]


class Records(NamedTuple):
    """One block of records, in the field order of the program's log."""

    site_id: jax.Array
    entity_id: jax.Array
    timestamp: jax.Array
    mark: jax.Array
    event_seq: jax.Array
    shard_hash: jax.Array


def _masked_cdf(weights, mask):
    w = jnp.where(mask, weights, 0.0)
    cdf = jnp.cumsum(w)
    return cdf / jnp.maximum(cdf[-1], 1e-30)


def _sample_sites(key, cdf, num):
    u = jax.random.uniform(key, (num,), dtype=jnp.float32)
    idx = jnp.searchsorted(cdf, u, side="right")
    return jnp.clip(idx, 0, cdf.shape[0] - 1).astype(jnp.int32)


def marked_rows_per_chunk(dep: Deployment, chunk_records: int) -> int:
    n = int(round(chunk_records * dep.marked_event_fraction))
    return max(0, min(chunk_records, n))


def chunk_keys(key, chunk_id):
    """(marked site, marked entity, marked ts, bernoulli, unmarked site,
    unmarked entity, unmarked ts) keys of one chunk."""
    return tuple(jax.random.split(jax.random.fold_in(key, chunk_id), 7))


def make_seed(key, dep: Deployment, num_chunks: int,
              chunk_records: int) -> Seed:
    """The marked-site set, the two sampling CDFs and the entity mark
    table of the log ``generate_chunk`` makes over ``[0, num_chunks)``."""
    k_perm, k_marked, _ = jax.random.split(key, 3)
    perm = jax.random.permutation(k_perm, dep.num_sites)
    ranks = jnp.arange(1, dep.num_sites + 1, dtype=jnp.float32)
    weights = ranks ** (-dep.alpha)
    weights = (weights / jnp.sum(weights))[perm]
    marked_ids = jax.random.choice(k_marked, dep.num_sites,
                                   shape=(dep.num_marked_sites,),
                                   replace=False)
    marked_mask = jnp.zeros((dep.num_sites,), bool).at[marked_ids].set(True)
    marked_cdf = _masked_cdf(weights, marked_mask)
    unmarked_cdf = _masked_cdf(weights, ~marked_mask)

    n_marked = marked_rows_per_chunk(dep, chunk_records)

    def step(earliest, chunk_id):
        _, k_ent, k_ts, k_bern, _, _, _ = chunk_keys(key, chunk_id)
        entity = jax.random.randint(k_ent, (n_marked,), 0, dep.num_entities,
                                    dtype=jnp.int32)
        ts = jax.random.randint(k_ts, (n_marked,), 0, dep.span_s,
                                dtype=jnp.int32)
        marks = jax.random.bernoulli(k_bern, dep.p_mark, (n_marked,))
        return earliest.at[entity].min(jnp.where(marks, ts, NEVER_MARKED)), None

    init = jnp.full((dep.num_entities,), NEVER_MARKED, jnp.int32)
    earliest, _ = jax.lax.scan(step, init,
                               jnp.arange(num_chunks, dtype=jnp.int32))
    mark_time = jnp.where(earliest >= NEVER_MARKED - dep.mark_delay_s,
                          NEVER_MARKED,
                          earliest + dep.mark_delay_s).astype(jnp.int32)
    return Seed(key, marked_mask, mark_time, marked_cdf, unmarked_cdf)


def _mix32(x):
    x = jnp.asarray(x).astype(jnp.uint32)
    x ^= x >> 16
    x *= jnp.uint32(0x85EBCA6B)
    x ^= x >> 13
    x *= jnp.uint32(0xC2B2AE35)
    x ^= x >> 16
    return x


def generate_chunk(seed: Seed, dep: Deployment, chunk_id,
                   chunk_records: int) -> Records:
    """Chunk ``chunk_id`` (may be traced) of the log ``seed`` describes."""
    c = chunk_records
    n_marked = marked_rows_per_chunk(dep, c)
    k_msite, k_ment, k_mts, _, k_usite, k_uent, k_uts = chunk_keys(
        seed.key, chunk_id)
    n_unmarked = c - n_marked

    def draw(k_site, cdf, k_ent, k_ts, n):
        site = _sample_sites(k_site, cdf, n)
        entity = jax.random.randint(k_ent, (n,), 0, dep.num_entities,
                                    dtype=jnp.int32)
        ts = jax.random.randint(k_ts, (n,), 0, dep.span_s, dtype=jnp.int32)
        return site, entity, ts

    m = draw(k_msite, seed.marked_cdf, k_ment, k_mts, n_marked)
    u = draw(k_usite, seed.unmarked_cdf, k_uent, k_uts, n_unmarked)
    site, entity, ts = (jnp.concatenate([a, b]) for a, b in zip(m, u))
    mark = (seed.entity_mark_time[entity] <= ts).astype(jnp.int32)
    shard_hash = jnp.full((c,), 1, jnp.uint32) * _mix32(
        jnp.asarray(chunk_id) + 1)
    return Records(site, entity, ts, mark, jnp.arange(c, dtype=jnp.uint32),
                   shard_hash)
