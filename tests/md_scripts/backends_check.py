"""Multi-device backend equivalence check — run as a subprocess with 8 host
devices (tests/test_backends.py drives this; the main pytest process must
keep a single device)."""

import os

from repro.common.env import force_host_devices

force_host_devices(8, extra=os.environ.get("XLA_FLAGS_EXTRA", ""))

import jax
import numpy as np

from repro.common.types import WEEKS_PER_YEAR
from repro.core import (
    malstone_run,
    malstone_run_partitioned,
    malstone_single_device,
    pad_log_to,
)
from repro.launch.mesh import make_mesh
from repro.malgen import MalGenConfig, generate_sharded_log


def main():
    assert jax.device_count() == 8, jax.devices()
    mesh = make_mesh((8,), ("data",))

    cfg = MalGenConfig(num_sites=301, num_entities=1000,
                       marked_site_fraction=0.2, marked_event_fraction=0.3)
    key = jax.random.key(7)
    log, seed = generate_sharded_log(key, cfg, num_shards=8,
                                     records_per_shard=4096)

    ref = malstone_single_device(log, cfg.num_sites, statistic="B")

    results = {}
    for backend in ("streams", "sphere", "mapreduce",
                    "mapreduce_combiner"):
        # capacity_factor 0.5 forces the mapreduce shuffle into multiple
        # residual rounds under the real power-law skew — the result must
        # still be exact (the shuffle is lossless at any capacity factor)
        res = malstone_run(log, cfg.num_sites, mesh=mesh, statistic="B",
                           backend=backend, capacity_factor=0.5)
        results[backend] = res
        np.testing.assert_array_equal(
            np.asarray(res.total), np.asarray(ref.total),
            err_msg=f"{backend}: total counts differ from single-device")
        np.testing.assert_array_equal(
            np.asarray(res.marked), np.asarray(ref.marked),
            err_msg=f"{backend}: marked counts differ")
        np.testing.assert_allclose(
            np.asarray(res.rho), np.asarray(ref.rho), rtol=1e-6,
            err_msg=f"{backend}: rho differs")
        print(f"OK backend={backend}")

    # MalStone A equivalence too
    for backend in ("streams", "sphere", "mapreduce",
                    "mapreduce_combiner"):
        res = malstone_run(log, cfg.num_sites, mesh=mesh, statistic="A",
                           backend=backend, capacity_factor=0.5)
        ref_a = malstone_single_device(log, cfg.num_sites, statistic="A")
        np.testing.assert_allclose(np.asarray(res.rho), np.asarray(ref_a.rho),
                                   rtol=1e-6)
    print("OK malstone A x4 backends")

    # Adversarial skew: EVERY record on one site — the worst case a
    # power-law can produce. The multi-round shuffle must deliver all of
    # them (overflow 0) and agree with the single-device oracle exactly.
    adv = log._replace(site_id=jax.numpy.zeros_like(log.site_id))
    ref_adv = malstone_single_device(adv, cfg.num_sites, statistic="B")
    res, stats = malstone_run(adv, cfg.num_sites, mesh=mesh, statistic="B",
                              backend="mapreduce", capacity_factor=0.25,
                              return_shuffle_stats=True)
    np.testing.assert_array_equal(np.asarray(res.total),
                                  np.asarray(ref_adv.total))
    np.testing.assert_array_equal(np.asarray(res.marked),
                                  np.asarray(ref_adv.marked))
    assert int(stats.overflow) == 0, int(stats.overflow)
    assert int(stats.rounds) > 1, int(stats.rounds)
    assert int(stats.sent) == adv.num_records
    print(f"OK adversarial single-site shuffle "
          f"(rounds={int(stats.rounds)}, overflow=0)")

    # Packed sort-once vs 4-column fallback on the real 8-device mesh:
    # identical histograms AND identical round/residual accounting; the
    # packed exchange moves 17/4 = 4.25x fewer bytes.
    res_u, stats_u = malstone_run(adv, cfg.num_sites, mesh=mesh,
                                  statistic="B", backend="mapreduce",
                                  capacity_factor=0.25,
                                  packed_shuffle=False,
                                  return_shuffle_stats=True)
    np.testing.assert_array_equal(np.asarray(res.total),
                                  np.asarray(res_u.total))
    np.testing.assert_array_equal(np.asarray(res.marked),
                                  np.asarray(res_u.marked))
    for field in ("sent", "overflow", "rounds", "residual"):
        assert int(getattr(stats, field)) == int(getattr(stats_u, field)), \
            field
    assert int(stats_u.bytes_exchanged) == \
        int(stats.bytes_exchanged) * 17 // 4
    print(f"OK packed vs unpacked exchange "
          f"(bytes {int(stats.bytes_exchanged):,} vs "
          f"{int(stats_u.bytes_exchanged):,})")

    # Partitioned (production sphere) path: concatenating owned blocks
    # reconstructs the padded full result.
    part = malstone_run_partitioned(log, cfg.num_sites, mesh=mesh,
                                    statistic="B")
    s_pad = ((cfg.num_sites + 7) // 8) * 8
    assert part.rho.shape == (s_pad, WEEKS_PER_YEAR), part.rho.shape
    np.testing.assert_allclose(np.asarray(part.rho)[:cfg.num_sites],
                               np.asarray(ref.rho), rtol=1e-6)
    print("OK partitioned sphere path")

    # Padded (non-divisible) record counts
    odd = jax.tree.map(lambda x: x[:30_001], log)
    padded = pad_log_to(odd, 30_008)
    ref_odd = malstone_single_device(odd, cfg.num_sites)
    got = malstone_run(padded, cfg.num_sites, mesh=mesh, backend="streams")
    np.testing.assert_array_equal(np.asarray(got.total),
                                  np.asarray(ref_odd.total))
    print("OK padded logs")
    print("ALL_OK")


if __name__ == "__main__":
    main()
