"""Multi-device counting-exchange check — run as a subprocess with 8 host
devices (tests/test_counting_exchange.py drives this; the main pytest
process must keep a single device).

With P=8 the destination key space is real (the single-device tests only
ever route to one partition + the invalid pseudo-destination): this is the
configuration where a wrong permutation out of the counting sort would
actually misdeliver records. Checks counting == sort bit-identity on
histograms AND every ShuffleStats field, the 4-vs-17-byte column ratio,
adversarial one-site skew through multiple residual rounds, the streaming
engine, the partitioned production layout, and the ``core.run`` dispatcher
— all against the single-device oracle.
"""

import os

from repro.common.env import force_host_devices

force_host_devices(8, extra=os.environ.get("XLA_FLAGS_EXTRA", ""))

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.types import ExchangePlan
from repro.core import (
    malstone_run,
    malstone_single_device,
    run,
)
from repro.launch.mesh import make_mesh
from repro.malgen import MalGenConfig, generate_sharded_log

STAT_FIELDS = ("sent", "overflow", "capacity", "rounds", "residual",
               "bytes_exchanged")


def assert_exact(got, ref, msg):
    np.testing.assert_array_equal(np.asarray(got.total),
                                  np.asarray(ref.total), err_msg=msg)
    np.testing.assert_array_equal(np.asarray(got.marked),
                                  np.asarray(ref.marked), err_msg=msg)


def main():
    assert jax.device_count() == 8, jax.devices()
    mesh = make_mesh((8,), ("data",))

    cfg = MalGenConfig(num_sites=301, num_entities=1000,
                       marked_site_fraction=0.2, marked_event_fraction=0.3)
    log, seed = generate_sharded_log(jax.random.key(7), cfg, num_shards=8,
                                     records_per_shard=4096)
    ref = malstone_single_device(log, cfg.num_sites, statistic="B")

    def plan(impl, cf=0.5):
        return ExchangePlan(impl=impl, capacity_factor=cf)

    # counting == sort == columns on the real 8-way exchange: identical
    # histograms, identical accounting; counting/sort also agree on the
    # wire bytes (both 4 B/slot), columns ships 17/4 = 4.25x more.
    stats = {}
    for impl in ("counting", "sort", "columns"):
        got, st = malstone_run(log, cfg.num_sites, mesh=mesh,
                               backend="mapreduce", plan=plan(impl),
                               return_shuffle_stats=True)
        assert_exact(got, ref, f"{impl} vs single-device oracle")
        assert int(st.overflow) == 0, impl
        stats[impl] = st
    for field in STAT_FIELDS:
        assert int(getattr(stats["counting"], field)) == \
            int(getattr(stats["sort"], field)), field
    for field in STAT_FIELDS[:-1]:
        assert int(getattr(stats["counting"], field)) == \
            int(getattr(stats["columns"], field)), field
    assert int(stats["columns"].bytes_exchanged) == \
        int(stats["counting"].bytes_exchanged) * 17 // 4
    print(f"OK counting==sort==columns x8 devices "
          f"(rounds={int(stats['counting'].rounds)}, "
          f"bytes {int(stats['counting'].bytes_exchanged):,} vs "
          f"{int(stats['columns'].bytes_exchanged):,})")

    # Adversarial skew: EVERY record routes to the device owning site 0 —
    # the counting sort's per-destination table is maximally unbalanced and
    # the shuffle needs multiple residual rounds. Still exact, still equal
    # to the sort path on every counter.
    adv = log._replace(site_id=jnp.zeros_like(log.site_id))
    ref_adv = malstone_single_device(adv, cfg.num_sites, statistic="B")
    got_c, st_c = malstone_run(adv, cfg.num_sites, mesh=mesh,
                               backend="mapreduce", plan=plan("counting", 0.25),
                               return_shuffle_stats=True)
    got_s, st_s = malstone_run(adv, cfg.num_sites, mesh=mesh,
                               backend="mapreduce", plan=plan("sort", 0.25),
                               return_shuffle_stats=True)
    assert_exact(got_c, ref_adv, "adversarial counting vs oracle")
    assert_exact(got_c, got_s, "adversarial counting vs sort")
    for field in STAT_FIELDS:
        assert int(getattr(st_c, field)) == int(getattr(st_s, field)), field
    assert int(st_c.overflow) == 0
    assert int(st_c.rounds) > 1
    assert int(st_c.sent) == adv.num_records
    print(f"OK adversarial one-site counting exchange "
          f"(rounds={int(st_c.rounds)}, overflow=0)")

    # Streaming engine through the dispatcher: per-chunk counting shuffle,
    # accumulated stats identical to the sort path.
    run_kw = dict(mesh=mesh, engine="streaming", backend="mapreduce",
                  chunk_records=4096, return_shuffle_stats=True)
    got_c, st_c = run(log, cfg.num_sites, plan=plan("counting"), **run_kw)
    got_s, st_s = run(log, cfg.num_sites, plan=plan("sort"), **run_kw)
    assert_exact(got_c, ref, "streaming counting vs oracle")
    for field in STAT_FIELDS:
        assert int(getattr(st_c, field)) == int(getattr(st_s, field)), field
    print("OK streaming engine counting==sort")

    # Partitioned production layout: device d owns sites [d*S/P, (d+1)*S/P);
    # concatenating the blocks reconstructs the oracle.
    part, st_p = run(log, cfg.num_sites, mesh=mesh, partitioned=True,
                     backend="mapreduce", plan=plan("counting"),
                     return_shuffle_stats=True)
    np.testing.assert_allclose(np.asarray(part.rho)[:cfg.num_sites],
                               np.asarray(ref.rho), rtol=1e-6,
                               err_msg="partitioned counting rho")
    np.testing.assert_array_equal(np.asarray(part.total)[:cfg.num_sites],
                                  np.asarray(ref.total),
                                  err_msg="partitioned counting total")
    assert int(st_p.overflow) == 0
    print("OK partitioned counting path")

    # Fused Pallas word reducer on the real mesh (interpret mode off-TPU):
    # the reducer consumes the shuffled words directly, never unpacking.
    got_f, st_f = malstone_run(
        log, cfg.num_sites, mesh=mesh, backend="mapreduce",
        plan=ExchangePlan(impl="counting", capacity_factor=0.5,
                          histogram_impl="pallas"),
        return_shuffle_stats=True)
    assert_exact(got_f, ref, "fused pallas reducer vs oracle")
    for field in STAT_FIELDS:
        assert int(getattr(st_f, field)) == \
            int(getattr(stats["counting"], field)), field
    print("OK fused pallas word reducer x8 devices")
    print("ALL_OK")


if __name__ == "__main__":
    main()
