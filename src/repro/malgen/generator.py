"""Phases 2-3 of MalGen: scatter + per-shard local generation (paper §5).

Each shard produces ``records_per_shard`` events:

- its strided slice of the global marked-event stream (regenerated from the
  seed — phase 2's scatter is the seed, not the events), and
- locally generated unmarked-site traffic under ``fold_in(key, shard_id)``.

Every record carries the *joined* mark flag of paper §4: 1 iff the entity's
mark time is <= the visit timestamp — "the fact that the mark is 1 does not
indicate that the site with Site ID is responsible for the mark".
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.common.types import EventLog
from repro.malgen.powerlaw import draw_sites
from repro.malgen.seeding import (
    MalGenConfig,
    SeedInfo,
    chunk_keys,
    chunk_marked_records,
    marked_event_stream,
)


def _fnv1a32(text: str) -> int:
    """FNV-1a — the "hash of the hostname" in the paper's Event ID scheme."""
    h = 0x811C9DC5
    for b in text.encode():
        h ^= b
        h = (h * 0x01000193) & 0xFFFFFFFF
    return h


def generate_shard(seed: SeedInfo, cfg: MalGenConfig,
                   shard_id: int, num_shards: int,
                   records_per_shard: int,
                   hostname: str | None = None) -> EventLog:
    """Phase 3 on one shard. Pure function of (seed, shard_id)."""
    n_marked_global = seed.num_marked_events
    # strided slice of the marked stream owned by this shard
    n_marked_local = len(range(shard_id, n_marked_global, num_shards))
    if n_marked_local > records_per_shard:
        # A shard whose strided slice of the marked stream exceeds its
        # record budget cannot emit every marked event it owns — that is
        # data loss, never a clamp (the seed was built for a bigger log
        # than (num_shards, records_per_shard) describes).
        raise ValueError(
            f"shard {shard_id}: {n_marked_local} marked events exceed "
            f"records_per_shard={records_per_shard} (global marked stream "
            f"{n_marked_global} over {num_shards} shards); the seed's "
            f"record budget does not match this shard layout — regenerate "
            f"the seed with total_records = num_shards * records_per_shard")
    n_unmarked = records_per_shard - n_marked_local

    m_site, m_entity, m_ts = marked_event_stream(seed, cfg)
    sl = slice(shard_id, shard_id + n_marked_local * num_shards, num_shards)
    m_site, m_entity, m_ts = m_site[sl], m_entity[sl], m_ts[sl]

    k = jax.random.fold_in(seed.key, shard_id)
    k_site, k_ent, k_ts = jax.random.split(k, 3)
    u_site = draw_sites(k_site, seed.unmarked_cdf, seed.unmarked_table,
                        n_unmarked)
    u_entity = jax.random.randint(k_ent, (n_unmarked,), 0, cfg.num_entities,
                                  dtype=jnp.int32)
    u_ts = jax.random.randint(k_ts, (n_unmarked,), 0, cfg.span_seconds,
                              dtype=jnp.int32)

    site = jnp.concatenate([m_site, u_site])
    entity = jnp.concatenate([m_entity, u_entity])
    ts = jnp.concatenate([m_ts, u_ts])

    # joined mark flag (paper §4)
    mark = (seed.entity_mark_time[entity] <= ts).astype(jnp.int32)

    host = hostname or f"node{shard_id:04d}"
    shard_hash = jnp.full((records_per_shard,), _fnv1a32(host),
                          dtype=jnp.uint32)
    event_seq = jnp.arange(records_per_shard, dtype=jnp.uint32)

    return EventLog(site_id=site, entity_id=entity, timestamp=ts, mark=mark,
                    event_seq=event_seq, shard_hash=shard_hash)


def _concat_logs(parts: list[EventLog]) -> EventLog:
    """Column-wise concat of per-shard/per-chunk logs (None columns stay
    None)."""
    return EventLog(*[
        None if parts[0][i] is None
        else jnp.concatenate([p[i] for p in parts])
        for i in range(len(parts[0]))
    ])


def generate_sharded_log(key: jax.Array, cfg: MalGenConfig,
                         num_shards: int, records_per_shard: int
                         ) -> tuple[EventLog, SeedInfo]:
    """All shards concatenated in shard order (record dim = shards * rps).

    This is the layout ``malstone_run`` expects: sharding the leading dim
    over the data axis gives each device exactly the records "its node"
    generated — matching the paper's disk-local layout.
    """
    from repro.malgen.seeding import make_seed
    total = num_shards * records_per_shard
    seed = make_seed(key, cfg, total)
    return _concat_logs(
        [generate_shard(seed, cfg, s, num_shards, records_per_shard)
         for s in range(num_shards)]), seed


def generate_full_log(key: jax.Array, cfg: MalGenConfig,
                      total_records: int) -> tuple[EventLog, SeedInfo]:
    """Single-shard convenience wrapper (tests, quickstart)."""
    return generate_sharded_log(key, cfg, 1, total_records)


# ----------------------------------------------------------------------------
# Chunk-keyed generation — the streaming engine's phase 3.
#
# ``generate_shard`` above computes shard-dependent *shapes* in Python (its
# strided slice of the marked stream varies per shard), so it cannot be traced
# with a dynamic shard id inside ``lax.scan``. ``generate_chunk`` is the
# scan-friendly counterpart: every chunk has the same static layout (the first
# ``chunk_marked_records(cfg, C)`` rows are marked-site traffic, the rest
# unmarked), and ALL randomness comes from ``chunk_keys(seed.key, chunk_id)``
# — a pure, traceable function of the chunk index. The pairing
# ``make_seed_streaming``/``generate_chunk`` replaces
# ``make_seed``/``generate_shard`` when the log must never be materialized.
# ----------------------------------------------------------------------------

def _mix32(x) -> jnp.ndarray:
    """Murmur3 finalizer — a traceable stand-in for the hostname hash of the
    paper's Event ID scheme when the shard id is a traced chunk index."""
    x = jnp.asarray(x).astype(jnp.uint32)
    x ^= x >> 16
    x *= jnp.uint32(0x85EBCA6B)
    x ^= x >> 13
    x *= jnp.uint32(0xC2B2AE35)
    x ^= x >> 16
    return x


def chunk_shard_hash(chunk_id) -> jnp.ndarray:
    """uint32 Event-ID namespace of one chunk; ``chunk_id`` may be traced.

    The mix input is salted (``chunk_id + 1``): the finalizer is a bijection
    on uint32 with ``_mix32(0) == 0``, so unsalted chunk 0 hashed to 0 and
    its Event IDs ``(0, seq)`` collided with ``pad_log_to``'s padding rows
    (``shard_hash=0, event_seq=0..``). With the salt no reachable chunk id
    maps to 0 (only ``chunk_id == 2**32 - 1`` would).
    """
    return _mix32(jnp.asarray(chunk_id) + 1)


def generate_chunk(seed: SeedInfo, cfg: MalGenConfig,
                   chunk_id, records_per_chunk: int) -> EventLog:
    """One fixed-size chunk; ``chunk_id`` may be a traced int32.

    ``seed`` must come from ``make_seed_streaming`` with the same
    ``records_per_chunk`` (the mark table is derived from the same per-chunk
    keys). Memory is O(records_per_chunk) regardless of the global log size.
    """
    with jax.named_scope("malstone.generate"):
        c = records_per_chunk
        n_marked = chunk_marked_records(cfg, c)
        (k_msite, k_ment, k_mts, _bern,
         k_usite, k_uent, k_uts) = chunk_keys(seed.key, chunk_id)

        m_site = draw_sites(k_msite, seed.marked_cdf, seed.marked_table,
                            n_marked)
        m_entity = jax.random.randint(k_ment, (n_marked,), 0,
                                      cfg.num_entities, dtype=jnp.int32)
        m_ts = jax.random.randint(k_mts, (n_marked,), 0, cfg.span_seconds,
                                  dtype=jnp.int32)

        n_unmarked = c - n_marked
        u_site = draw_sites(k_usite, seed.unmarked_cdf,
                            seed.unmarked_table, n_unmarked)
        u_entity = jax.random.randint(k_uent, (n_unmarked,), 0,
                                      cfg.num_entities, dtype=jnp.int32)
        u_ts = jax.random.randint(k_uts, (n_unmarked,), 0, cfg.span_seconds,
                                  dtype=jnp.int32)

        site = jnp.concatenate([m_site, u_site])
        entity = jnp.concatenate([m_entity, u_entity])
        ts = jnp.concatenate([m_ts, u_ts])

        # joined mark flag (paper §4)
        mark = (seed.entity_mark_time[entity] <= ts).astype(jnp.int32)

        shard_hash = jnp.full((c,), 1, jnp.uint32) * chunk_shard_hash(chunk_id)
        event_seq = jnp.arange(c, dtype=jnp.uint32)
        return EventLog(site_id=site, entity_id=entity, timestamp=ts,
                        mark=mark, event_seq=event_seq, shard_hash=shard_hash)


def generate_chunked_log(seed: SeedInfo, cfg: MalGenConfig,
                         num_chunks: int, records_per_chunk: int) -> EventLog:
    """Materialize the chunk-keyed log (chunks concatenated in chunk order).

    This is the oracle for the streaming engine's bit-identity tests: running
    ``malstone_run`` over this log must agree exactly with
    ``malstone_run_streaming`` over the bare seed, because both observe the
    same per-chunk pure function — here eagerly, there inside a scan.
    """
    return _concat_logs([generate_chunk(seed, cfg, i, records_per_chunk)
                         for i in range(num_chunks)])


def generate_streaming_log(key: jax.Array, cfg: MalGenConfig,
                           num_chunks: int, records_per_chunk: int
                           ) -> tuple[EventLog, SeedInfo]:
    """Convenience: streaming seed + materialized chunk-keyed log."""
    from repro.malgen.seeding import make_seed_streaming
    seed = make_seed_streaming(key, cfg, num_chunks, records_per_chunk)
    return generate_chunked_log(seed, cfg, num_chunks, records_per_chunk), seed


# ----------------------------------------------------------------------------
# Device-parallel generation — phase 3 *on* the data mesh (paper §5: "each
# node generating its own records locally").
#
# ``generate_shard`` computes shard-dependent Python shapes, so
# ``generate_sharded_log`` is a host loop that regenerates the whole global
# marked-event stream once per shard and concatenates the full log in host
# memory — O(num_shards x marked-stream) redundant host work, the exact
# anti-pattern the paper's scatter trick avoids. ``generate_shard_device``
# is the trace-friendly twin: every shape is a static function of the
# *global* layout (num_shards, records_per_shard, seed.num_marked_events),
# the shard id may be a traced ``lax.axis_index``, and the output is
# bit-identical to ``generate_shard`` for every shard. Under ``shard_map``
# each device generates exactly the records "its node" owns, in place; the
# host never materializes (or even touches) the global log.
#
# Static-layout construction, given q, r = divmod(num_marked, num_shards):
# shard s owns q + (s < r) marked rows. The two possible unmarked row
# counts differ by one, and threefry draws depend on their shape, so both
# candidate unmarked streams are drawn at their exact static shapes and the
# right one is selected per device — that is what keeps the ragged
# (r != 0) layout bit-identical under a single SPMD trace. The marked
# slice is a strided gather from the deterministically regenerated stream
# (per-device work O(num_marked + records_per_shard); the O(chunk)
# alternative is the chunk-keyed streaming path above).
# ----------------------------------------------------------------------------

def shard_marked_budget(num_marked: int, num_shards: int,
                        records_per_shard: int) -> tuple[int, int]:
    """(q, r) of the static per-shard marked-row layout; raises the same
    truncation error as ``generate_shard`` if any shard's slice would
    exceed its record budget (all quantities are Python ints, so this is
    a trace-time check)."""
    q, r = divmod(num_marked, num_shards)
    worst = q + (1 if r else 0)
    if worst > records_per_shard:
        raise ValueError(
            f"shard layout ({num_shards} x {records_per_shard}) cannot hold "
            f"the marked stream: shard 0 owns {worst} of {num_marked} "
            f"marked events > records_per_shard={records_per_shard}; "
            f"regenerate the seed with total_records = num_shards * "
            f"records_per_shard")
    return q, r


def _fnv1a32_digits(h0: int, value, width: int) -> jnp.ndarray:
    """Continue an FNV-1a fold over the zero-padded decimal digits of a
    (possibly traced) int32 — the traceable tail of ``_fnv1a32(f"node"
    f"{value:0{width}d}")``."""
    h = jnp.uint32(h0)
    value = jnp.asarray(value, jnp.int32)
    for d in range(width - 1, -1, -1):
        digit = (value // (10 ** d)) % 10
        h = (h ^ (jnp.uint32(ord("0")) + digit.astype(jnp.uint32))) \
            * jnp.uint32(0x01000193)
    return h


def generate_shard_device(seed: SeedInfo, cfg: MalGenConfig,
                          shard_id, num_shards: int,
                          records_per_shard: int) -> EventLog:
    """Trace-friendly ``generate_shard``: ``shard_id`` may be a traced int32
    (``lax.axis_index`` under ``shard_map``); bit-identical output.

    All shapes are static; the per-shard marked-row count (which varies by
    one across shards when the marked stream does not divide evenly) is
    handled with a traced row select, never a Python shape.
    """
    n_marked_global = seed.num_marked_events
    if isinstance(n_marked_global, jax.core.Tracer):
        raise ValueError(
            "seed.num_marked_events is traced — the static per-shard layout "
            "needs it as a Python int. Close over the seed instead of "
            "passing it through jax.jit arguments")
    q, r = shard_marked_budget(n_marked_global, num_shards,
                               records_per_shard)
    nm_max = q + (1 if r else 0)
    if num_shards > 10_000:
        raise ValueError(
            f"num_shards={num_shards}: hostnames beyond node9999 change "
            f"digit width per shard, which has no static layout; use "
            f"generate_shard (host path) for >10k shards")

    sid = jnp.asarray(shard_id, jnp.int32)
    nm_local = jnp.int32(q) + (sid < r).astype(jnp.int32) \
        if r else jnp.int32(q)

    # marked rows: strided gather from the deterministically regenerated
    # global stream (the phase-2 scatter trick: the seed, not the events,
    # is what this function closes over)
    m_site_g, m_entity_g, m_ts_g = marked_event_stream(seed, cfg)
    pos = sid + jnp.arange(nm_max, dtype=jnp.int32) * num_shards
    take = jnp.minimum(pos, n_marked_global - 1)  # tail row unused when
    m_site = m_site_g[take]                       # pos >= n_marked_global
    m_entity = m_entity_g[take]
    m_ts = m_ts_g[take]

    # unmarked rows: the host path draws exactly records_per_shard -
    # nm_local values, and threefry output depends on that shape — so draw
    # both static candidates and select per device
    k = jax.random.fold_in(seed.key, sid)
    k_site, k_ent, k_ts = jax.random.split(k, 3)

    def draw_unmarked(n: int):
        return (draw_sites(k_site, seed.unmarked_cdf, seed.unmarked_table,
                           n),
                jax.random.randint(k_ent, (n,), 0, cfg.num_entities,
                                   dtype=jnp.int32),
                jax.random.randint(k_ts, (n,), 0, cfg.span_seconds,
                                   dtype=jnp.int32))

    n_unmarked_max = records_per_shard - q   # shards s >= r
    if n_unmarked_max > 0:
        hi = draw_unmarked(n_unmarked_max)
        if r:
            lo = tuple(jnp.pad(x, (0, 1))
                       for x in draw_unmarked(n_unmarked_max - 1))
            u_site, u_entity, u_ts = (jnp.where(sid < r, a, b)
                                      for a, b in zip(lo, hi))
        else:
            u_site, u_entity, u_ts = hi

    # assemble: row i is marked for i < nm_local, else unmarked row
    # (i - nm_local) — the concat of the host path as a static gather
    i = jnp.arange(records_per_shard, dtype=jnp.int32)
    is_marked_row = i < nm_local
    mi = jnp.minimum(i, nm_max - 1)
    if n_unmarked_max > 0:
        ui = jnp.clip(i - nm_local, 0, n_unmarked_max - 1)
        site = jnp.where(is_marked_row, m_site[mi], u_site[ui])
        entity = jnp.where(is_marked_row, m_entity[mi], u_entity[ui])
        ts = jnp.where(is_marked_row, m_ts[mi], u_ts[ui])
    else:                                    # every row marked (q == rps)
        site, entity, ts = m_site[mi], m_entity[mi], m_ts[mi]

    # joined mark flag (paper §4)
    mark = (seed.entity_mark_time[entity] <= ts).astype(jnp.int32)

    # same Event-ID namespace as the host path: FNV-1a of f"node{sid:04d}"
    shard_hash = jnp.full((records_per_shard,), 1, jnp.uint32) \
        * _fnv1a32_digits(_fnv1a32("node"), sid, 4)
    event_seq = jnp.arange(records_per_shard, dtype=jnp.uint32)
    return EventLog(site_id=site, entity_id=entity, timestamp=ts, mark=mark,
                    event_seq=event_seq, shard_hash=shard_hash)
