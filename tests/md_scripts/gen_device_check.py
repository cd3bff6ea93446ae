"""Multi-device device-parallel MalGen check — run as a subprocess with 8
forced host devices (tests/test_gen_device.py drives this; the main pytest
process must stay single-device).

Covers, on a real 8-way data mesh with a *ragged* marked-stream layout
(num_marked_events % 8 != 0, so per-shard marked counts differ):

- generate_shard_device under shard_map == generate_sharded_log, bit for
  bit, every column;
- malstone_run_generated == malstone_run over the materialized log for all
  four backends (fused path never materializes the global log);
- the streaming twin == chunked malstone_run_streaming;
- fused mapreduce at sub-1.0 capacity stays lossless (overflow == 0).
"""

import os

from repro.common.env import force_host_devices

force_host_devices(8, extra=os.environ.get("XLA_FLAGS_EXTRA", ""))

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.common.compat import shard_map
from repro.common.types import EventLog
from repro.core import (
    malstone_run,
    malstone_run_generated,
    malstone_run_generated_streaming,
    malstone_run_streaming,
)
from repro.launch.mesh import make_mesh
from repro.malgen import MalGenConfig, generate_shard_device, generate_sharded_log

BACKENDS = ("streams", "sphere", "mapreduce", "mapreduce_combiner")


def main():
    assert jax.device_count() == 8, jax.devices()
    mesh = make_mesh((8,), ("data",))
    parts, rps = 8, 1024

    cfg = MalGenConfig(num_sites=301, num_entities=1000,
                       marked_site_fraction=0.2, marked_event_fraction=0.3)
    log, seed = generate_sharded_log(jax.random.key(11), cfg, parts, rps)
    r = seed.num_marked_events % parts
    assert r != 0, "want a ragged layout to exercise the traced row select"

    # device generation under shard_map is the host log, bit for bit
    def local():
        sid = jax.lax.axis_index("data")
        return generate_shard_device(seed, cfg, sid, parts, rps)

    spec = EventLog(site_id=P("data"), entity_id=P("data"),
                    timestamp=P("data"), mark=P("data"),
                    event_seq=P("data"), shard_hash=P("data"))
    got = jax.jit(shard_map(local, mesh=mesh, in_specs=(), out_specs=spec,
                            check_vma=False))()
    for a, b, name in zip(got, log, log._fields):
        if b is None:
            continue
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=f"shard_map column {name}")
    print(f"OK shard_map generation == host log "
          f"(NM={seed.num_marked_events}, r={r})")

    for backend in BACKENDS:
        for stat in ("A", "B"):
            ref = malstone_run(log, cfg.num_sites, mesh=mesh,
                               statistic=stat, backend=backend)
            fused = malstone_run_generated(
                seed, cfg, mesh=mesh, records_per_shard=rps,
                statistic=stat, backend=backend)
            np.testing.assert_array_equal(
                np.asarray(fused.total), np.asarray(ref.total),
                err_msg=f"fused {backend}/{stat}: totals differ")
            np.testing.assert_array_equal(
                np.asarray(fused.marked), np.asarray(ref.marked),
                err_msg=f"fused {backend}/{stat}: marked differ")
        sref = malstone_run_streaming(log, cfg.num_sites, mesh=mesh,
                                      backend=backend, chunk_records=256,
                                      statistic="B")
        sgot = malstone_run_generated_streaming(
            seed, cfg, mesh=mesh, records_per_shard=rps,
            chunk_records=256, statistic="B", backend=backend)
        np.testing.assert_array_equal(
            np.asarray(sgot.total), np.asarray(sref.total),
            err_msg=f"fused-streaming {backend}: totals differ")
        np.testing.assert_array_equal(
            np.asarray(sgot.marked), np.asarray(sref.marked),
            err_msg=f"fused-streaming {backend}: marked differ")
        print(f"OK fused oneshot+streaming backend={backend}")

    # lossless shuffle through the fused path at adversarial capacity
    got, stats = malstone_run_generated(
        seed, cfg, mesh=mesh, records_per_shard=rps, backend="mapreduce",
        statistic="B", capacity_factor=0.25, return_shuffle_stats=True)
    ref = malstone_run(log, cfg.num_sites, mesh=mesh, statistic="B",
                       backend="mapreduce", capacity_factor=0.25)
    np.testing.assert_array_equal(np.asarray(got.total),
                                  np.asarray(ref.total))
    assert int(stats.overflow) == 0, int(stats.overflow)
    assert int(stats.rounds) >= 1
    print(f"OK fused lossless shuffle (rounds={int(stats.rounds)}, "
          f"overflow=0)")

    print("ALL_OK")


if __name__ == "__main__":
    main()
