"""Phase 1 of MalGen: head-node seeding (paper §5, Table 3 "seed" phase).

The head node decides which sites are marked, generates *all* marked-site
events for the year, and derives the entity mark table:

- a marked-site visit marks the entity with probability ``p_mark`` (paper
  example: 70%),
- the mark lands ``mark_delay`` after the visit (paper example: one week),
- a later marking visit never delays an existing mark; an earlier one moves
  it earlier ("the date-time of the mark is updated accordingly" — §5). Net:
  ``mark_time[e] = min over marking visits (ts) + delay``.

The scatterable seed is tiny relative to the data: the PRNG key, the marked
site set, and the int32 per-entity mark-time table — this is the "seed
information ... kept in memory" whose footprint Table 3/Figure 3 track.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.common.types import NEVER_MARKED, SECONDS_PER_WEEK, SECONDS_PER_YEAR
from repro.malgen.powerlaw import (
    SITE_TABLE_SIZE,
    draw_sites,
    masked_site_cdf,
    power_law_weights,
    site_table,
)


class MalGenConfig(NamedTuple):
    num_sites: int = 100_000
    num_entities: int = 1_000_000
    marked_site_fraction: float = 0.10   # "The Ghost in the Browser": ~10%
    alpha: float = 1.2                   # power-law exponent
    p_mark: float = 0.70                 # paper §5 example
    mark_delay: int = SECONDS_PER_WEEK   # paper §5 example: one week
    span_seconds: int = SECONDS_PER_YEAR  # default: one year of data
    # Fraction of all events that land on marked sites. The paper routes all
    # marked-site traffic through phase 1; we keep the fraction explicit so
    # record budgets stay static-shaped.
    marked_event_fraction: float = 0.10

    @property
    def num_marked_sites(self) -> int:
        return max(1, int(self.num_sites * self.marked_site_fraction))


class SeedInfo(NamedTuple):
    """Everything phase 2 scatters to the worker nodes.

    The two sampling CDFs are precomputed here — at seed time, once —
    because their float32 cumsum/normalize is not bitwise-stable across
    XLA fusion contexts (see ``masked_site_cdf``). Storing the values
    makes every generation program (eager oracle, outer-jitted scan,
    standalone overlap programs, multi-process SPMD) sample against the
    *same* table, so the log is bit-identical everywhere.

    The two site tables (``powerlaw.site_table``) are seed data for the same
    reason: each is the CDF's search evaluated once over every float32
    uniform draw, so generation looks a draw up instead of searching for it.
    Both are None for a log of fewer records than a table has entries,
    which generation then searches.
    """
    key: jax.Array                 # the root PRNG key (regeneration handle)
    marked_mask: jnp.ndarray       # bool [num_sites]
    entity_mark_time: jnp.ndarray  # int32 [num_entities]; NEVER_MARKED if not
    site_weights: jnp.ndarray      # float32 [num_sites] popularity
    num_marked_events: int         # length of the global marked-event stream
    marked_cdf: jnp.ndarray        # float32 [num_sites] CDF over marked sites
    unmarked_cdf: jnp.ndarray      # float32 [num_sites] CDF over the rest
    marked_table: jnp.ndarray | None    # int32 [2^23] site of each draw
    unmarked_table: jnp.ndarray | None  # int32 [2^23] site of each draw

    @property
    def seed_bytes(self) -> int:
        """Scatter payload size — the paper's Table 3 memory concern.

        The site tables are not counted: each node derives them from the
        CDFs, which are."""
        return (self.marked_mask.size * 1 + self.entity_mark_time.size * 4
                + self.site_weights.size * 4
                + self.marked_cdf.size * 4 + self.unmarked_cdf.size * 4 + 32)


def _site_tables(key: jax.Array, cfg: MalGenConfig, records: int):
    """(k_events, site_weights, marked_mask, marked_cdf, unmarked_cdf,
    marked_table, unmarked_table) — shared by both seeding paths so a given
    root key yields identical site popularity / marked-site sets whether the
    log is later generated shard-wise or chunk-wise. ``records`` is the
    log's size, which decides whether the site tables are built."""
    k_perm, k_marked, k_events = jax.random.split(key, 3)

    # Popularity decoupled from site id ordering.
    perm = jax.random.permutation(k_perm, cfg.num_sites)
    weights = power_law_weights(cfg.num_sites, cfg.alpha, permutation=perm)

    # Marked sites: a uniform random subset (drive-by exploit sites are not
    # systematically the most/least popular).
    marked_ids = jax.random.choice(
        k_marked, cfg.num_sites, shape=(cfg.num_marked_sites,), replace=False)
    marked_mask = jnp.zeros((cfg.num_sites,), bool).at[marked_ids].set(True)

    # Sampling CDFs, fixed here so every later program reads values, not
    # recomputed float reductions (fusion-context instability — see
    # masked_site_cdf).
    marked_cdf = masked_site_cdf(weights, marked_mask)
    unmarked_cdf = masked_site_cdf(weights, ~marked_mask)
    # A table costs a search of each of its entries: a log of fewer records
    # than that draws fewer sites than building the table would search.
    if records < SITE_TABLE_SIZE:
        return (k_events, weights, marked_mask, marked_cdf, unmarked_cdf,
                None, None)
    return (k_events, weights, marked_mask, marked_cdf, unmarked_cdf,
            site_table(marked_cdf), site_table(unmarked_cdf))


def make_seed(key: jax.Array, cfg: MalGenConfig,
              total_records: int) -> SeedInfo:
    """Phase 1. ``total_records`` is the global record budget; the marked
    stream gets ``round(total * marked_event_fraction)`` events."""
    (k_events, weights, marked_mask, marked_cdf, unmarked_cdf,
     marked_table, unmarked_table) = _site_tables(key, cfg, total_records)

    num_marked_events = max(1, int(round(total_records * cfg.marked_event_fraction)))
    entity_mark_time = _derive_mark_table(
        k_events, cfg, marked_cdf, marked_table, num_marked_events)

    return SeedInfo(key=key, marked_mask=marked_mask,
                    entity_mark_time=entity_mark_time,
                    site_weights=weights,
                    num_marked_events=num_marked_events,
                    marked_cdf=marked_cdf, unmarked_cdf=unmarked_cdf,
                    marked_table=marked_table, unmarked_table=unmarked_table)


def marked_event_stream(seed: SeedInfo, cfg: MalGenConfig):
    """Deterministically (re)generate the full global marked-event stream.

    Returns (site, entity, ts) int32 arrays of length num_marked_events.
    Any node holding the seed can call this — that is the phase-2 scatter
    trick: bytes moved = seed, not events.
    """
    k_events = jax.random.split(seed.key, 3)[2]
    return _marked_events(k_events, cfg, seed.marked_cdf, seed.marked_table,
                          seed.num_marked_events)


def _marked_events(k_events, cfg, marked_cdf, marked_table, num_events):
    k_site, k_ent, k_ts, _ = jax.random.split(k_events, 4)
    site = draw_sites(k_site, marked_cdf, marked_table, num_events)
    entity = jax.random.randint(k_ent, (num_events,), 0, cfg.num_entities,
                                dtype=jnp.int32)
    ts = jax.random.randint(k_ts, (num_events,), 0, cfg.span_seconds,
                            dtype=jnp.int32)
    return site, entity, ts


# ----------------------------------------------------------------------------
# Streaming (chunk-keyed) seeding — the generate-as-you-go engine's phase 1.
#
# The one-shot path above materializes the full global marked-event stream to
# derive the mark table. At paper scale (B-10 = 10 billion records) even the
# head node cannot hold that stream, so the streaming path re-keys ALL
# randomness per fixed-size chunk (``fold_in(key, chunk_id)``) and derives the
# entity mark table with a min-accumulating ``lax.scan`` over chunks: memory
# is O(num_entities + chunk), never O(records). ``generate_chunk`` (see
# generator.py) regenerates any chunk from the same per-chunk keys, so the
# log is a pure function of (seed, chunk_id) — phase 2's scatter stays a
# seed, exactly as the paper prescribes.
# ----------------------------------------------------------------------------

def chunk_marked_records(cfg: MalGenConfig, records_per_chunk: int) -> int:
    """Marked-site rows per chunk (static — every chunk gets the same)."""
    n = int(round(records_per_chunk * cfg.marked_event_fraction))
    return max(0, min(records_per_chunk, n))


def chunk_keys(root_key: jax.Array, chunk_id):
    """Per-chunk PRNG keys; ``chunk_id`` may be a traced int32.

    Single source of truth for the split layout — ``make_seed_streaming``
    (mark-table derivation) and ``generate_chunk`` (record generation) must
    draw the marked rows from the same keys or the joined mark flags would
    not correspond to the marking visits.
    Returns (k_marked_site, k_marked_entity, k_marked_ts, k_bernoulli,
    k_unmarked_site, k_unmarked_entity, k_unmarked_ts).
    """
    k = jax.random.fold_in(root_key, chunk_id)
    return tuple(jax.random.split(k, 7))


def make_seed_streaming(key: jax.Array, cfg: MalGenConfig,
                        num_chunks: int, records_per_chunk: int) -> SeedInfo:
    """Phase 1 for the streaming engine: bounded-memory mark-table derivation.

    Scans the chunk index space, regenerating only each chunk's marked rows
    and folding the earliest marking visit per entity into a carry — the
    chunk records themselves are never stored. The returned ``SeedInfo`` is
    layout-bound: it corresponds to the log produced by ``generate_chunk``
    over ``chunk_id in [0, num_chunks)`` at this ``records_per_chunk``.
    """
    (_, weights, marked_mask, marked_cdf, unmarked_cdf,
     marked_table, unmarked_table) = _site_tables(
        key, cfg, num_chunks * records_per_chunk)
    n_marked = chunk_marked_records(cfg, records_per_chunk)

    def step(earliest, chunk_id):
        _, k_ent, k_ts, k_bern, _, _, _ = chunk_keys(key, chunk_id)
        entity = jax.random.randint(k_ent, (n_marked,), 0, cfg.num_entities,
                                    dtype=jnp.int32)
        ts = jax.random.randint(k_ts, (n_marked,), 0, cfg.span_seconds,
                                dtype=jnp.int32)
        marks_entity = jax.random.bernoulli(k_bern, cfg.p_mark, (n_marked,))
        visit_ts = jnp.where(marks_entity, ts, NEVER_MARKED)
        return earliest.at[entity].min(visit_ts), None

    init = jnp.full((cfg.num_entities,), NEVER_MARKED, jnp.int32)
    earliest, _ = jax.lax.scan(step, init,
                               jnp.arange(num_chunks, dtype=jnp.int32))
    mark_time = _apply_mark_delay(earliest, cfg)

    return SeedInfo(key=key, marked_mask=marked_mask,
                    entity_mark_time=mark_time, site_weights=weights,
                    num_marked_events=num_chunks * n_marked,
                    marked_cdf=marked_cdf, unmarked_cdf=unmarked_cdf,
                    marked_table=marked_table, unmarked_table=unmarked_table)


def _apply_mark_delay(earliest: jnp.ndarray, cfg: MalGenConfig) -> jnp.ndarray:
    """earliest marking visit -> mark time, guarding int32 overflow of
    ``earliest + mark_delay`` for never-marked entities (dtype-max fill)."""
    return jnp.where(
        earliest >= NEVER_MARKED - cfg.mark_delay, NEVER_MARKED,
        earliest + cfg.mark_delay).astype(jnp.int32)


def _derive_mark_table(k_events, cfg, marked_cdf, marked_table, num_events):
    site, entity, ts = _marked_events(k_events, cfg, marked_cdf, marked_table,
                                      num_events)
    _, _, _, k_bern = jax.random.split(k_events, 4)
    marks_entity = jax.random.bernoulli(k_bern, cfg.p_mark, (num_events,))

    # earliest marking visit wins; delay applied after the min
    visit_ts = jnp.where(marks_entity, ts, NEVER_MARKED)
    earliest = jax.ops.segment_min(visit_ts, entity,
                                   num_segments=cfg.num_entities)
    # segment_min fills empty segments with +inf equivalent (dtype max)
    return _apply_mark_delay(earliest, cfg)
