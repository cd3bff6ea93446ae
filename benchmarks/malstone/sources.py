"""The one traffic generator: what a cell's jobs read, made from ``--seed``.

A traffic file names its ``source``:

- ``seed``: the program's MalGen streaming seed (``make_seed_streaming``);
  every job regenerates its records from it as the scan runs. The
  reference regenerates the same records with the frozen MalGen copy.
- ``log``: a resident chunk-keyed log that the frozen MalGen copy makes
  once in set-up, each chip its own shard of ``records_per_node`` records
  on the device; every job scans it. The reference counts that same log.

Every job reads the same records, so a run's work is fixed by its seed.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

import malgen_frozen
import reference

AXIS = "data"
REFERENCE_BLOCK_CHUNKS = 8


def deployment(config: dict) -> malgen_frozen.Deployment:
    return malgen_frozen.Deployment(**{
        f: config[f] for f in malgen_frozen.Deployment._fields})


def program_config(config: dict):
    """The program's ``MalGenConfig`` for a configuration file."""
    from repro.malgen import MalGenConfig

    return MalGenConfig(
        num_sites=config["num_sites"], num_entities=config["num_entities"],
        marked_site_fraction=config["marked_site_fraction"],
        alpha=config["alpha"], p_mark=config["p_mark"],
        mark_delay=config["mark_delay_s"], span_seconds=config["span_s"],
        marked_event_fraction=config["marked_event_fraction"])


class Source:
    """What the jobs of one cell read; built in set-up."""

    def __init__(self, config: dict, mesh, seed: int):
        self.config = config
        self.mesh = mesh
        self.chips = mesh.devices.size
        self.chunk_records = config["chunk_records"]
        if config["records_per_node"] % self.chunk_records:
            raise ValueError("records_per_node must be a whole number of "
                             "chunks")
        self.chunks_per_chip = config["records_per_node"] // self.chunk_records
        self.num_chunks = self.chips * self.chunks_per_chip
        self.records_per_job = self.num_chunks * self.chunk_records
        self.key = jax.random.key(seed)
        self.dep = deployment(config)

    def _shard_map(self, fn, in_specs, out_specs):
        return jax.shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)


class SeedSource(Source):
    def __init__(self, config, mesh, seed):
        super().__init__(config, mesh, seed)
        from repro.malgen import make_seed_streaming

        self.cfg = program_config(config)
        self.program_input = jax.block_until_ready(
            jax.jit(make_seed_streaming, static_argnums=(1, 2, 3))(
                self.key, self.cfg, self.num_chunks, self.chunk_records))
        self.run_kwargs = {"num_chunks": self.num_chunks, "cfg": self.cfg}

    def chunk_per_device(self):
        """One chunk per chip, made by the program's generator."""
        from repro.common.types import EventLog
        from repro.malgen import generate_chunk

        cpd, c = self.chunks_per_chip, self.chunk_records

        def one(seed):
            first = jax.lax.axis_index(AXIS) * cpd
            return generate_chunk(seed, self.cfg, first, c)

        spec = EventLog(*(P(AXIS) for _ in EventLog._fields[:6]))
        return jax.jit(self._shard_map(one, (P(),), spec))(self.program_input)

    def reference_key_blocks(self):
        dep, c, w = self.dep, self.chunk_records, self.config["num_weeks"]
        seed = jax.jit(malgen_frozen.make_seed, static_argnums=(1, 2, 3))(
            self.key, dep, self.num_chunks, c)

        @jax.jit
        def keys(seed, ids):
            def one(i):
                r = malgen_frozen.generate_chunk(seed, dep, i, c)
                return reference.record_keys(r.site_id, r.timestamp, r.mark, w)
            return jax.lax.map(one, ids)

        for first in range(0, self.num_chunks, REFERENCE_BLOCK_CHUNKS):
            ids = jnp.arange(first, min(first + REFERENCE_BLOCK_CHUNKS,
                                        self.num_chunks), dtype=jnp.int32)
            yield np.asarray(keys(seed, ids))


class LogSource(Source):
    def __init__(self, config, mesh, seed):
        super().__init__(config, mesh, seed)
        from repro.common.types import EventLog

        dep, c, cpd = self.dep, self.chunk_records, self.chunks_per_chip
        fields = malgen_frozen.Records._fields

        def shard(seed):
            first = jax.lax.axis_index(AXIS) * cpd
            blocks = jax.lax.map(
                lambda i: malgen_frozen.generate_chunk(seed, dep, first + i, c),
                jnp.arange(cpd, dtype=jnp.int32))
            return malgen_frozen.Records(*(b.reshape(-1) for b in blocks))

        def make(key):
            seed = malgen_frozen.make_seed(key, dep, self.num_chunks, c)
            return self._shard_map(shard, (P(),), malgen_frozen.Records(
                *(P(AXIS) for _ in fields)))(seed)

        self.records = jax.block_until_ready(jax.jit(make)(self.key))
        self.program_input = EventLog(**self.records._asdict())
        self.run_kwargs = {}

    def chunk_per_device(self):
        """The first chunk of every chip's shard."""
        from repro.common.types import EventLog

        c = self.chunk_records
        spec = EventLog(*(P(AXIS) for _ in EventLog._fields[:6]))
        cut = self._shard_map(
            lambda lg: EventLog(*(x[:c] for x in lg[:6])), (spec,), spec)
        return jax.jit(cut)(self.program_input)

    def reference_key_blocks(self):
        w = self.config["num_weeks"]
        sharding = NamedSharding(self.mesh, P(AXIS))
        keys = jax.jit(lambda r: reference.record_keys(
            r.site_id, r.timestamp, r.mark, w), out_shardings=sharding)(
                self.records)
        for shard in keys.addressable_shards:
            yield np.asarray(shard.data)


SOURCES = {"seed": SeedSource, "log": LogSource}


def make_source(traffic: dict, config: dict, mesh, seed: int) -> Source:
    kind = traffic.get("source")
    if kind not in SOURCES:
        raise ValueError(f"unknown traffic source {kind!r}; "
                         f"have {sorted(SOURCES)}")
    return SOURCES[kind](config, mesh, seed)
