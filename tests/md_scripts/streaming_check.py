"""Multi-device streaming-engine equivalence check — run as a subprocess
with 8 forced host devices (tests/test_streaming.py drives this; the main
pytest process must stay single-device)."""

import os

from repro.common.env import force_host_devices

force_host_devices(8, extra=os.environ.get("XLA_FLAGS_EXTRA", ""))

import jax
import numpy as np

from repro.core import malstone_run, malstone_run_streaming
from repro.launch.mesh import make_mesh
from repro.malgen import (
    MalGenConfig,
    generate_chunked_log,
    generate_sharded_log,
    make_seed_streaming,
)

BACKENDS = ("streams", "sphere", "mapreduce", "mapreduce_combiner")


def main():
    assert jax.device_count() == 8, jax.devices()
    mesh = make_mesh((8,), ("data",))

    cfg = MalGenConfig(num_sites=301, num_entities=1000,
                       marked_site_fraction=0.2, marked_event_fraction=0.3)
    key = jax.random.key(11)
    num_chunks, chunk = 32, 512  # 4 chunks per device
    seed = make_seed_streaming(key, cfg, num_chunks, chunk)
    log = generate_chunked_log(seed, cfg, num_chunks, chunk)

    # Seed mode (generate-as-you-go) vs one-shot over the materialized log.
    # Default capacity factor everywhere: the mapreduce shuffle is lossless
    # at any value (multi-round residual exchange), so streaming no longer
    # needs the old capacity_factor >= P crutch.
    for backend in BACKENDS:
        for stat in ("A", "B"):
            ref = malstone_run(log, cfg.num_sites, mesh=mesh, statistic=stat,
                               backend=backend)
            got = malstone_run_streaming(
                seed, cfg.num_sites, mesh=mesh, backend=backend,
                chunk_records=chunk, statistic=stat, cfg=cfg,
                num_chunks=num_chunks)
            np.testing.assert_array_equal(
                np.asarray(got.total), np.asarray(ref.total),
                err_msg=f"seed-mode {backend}/{stat}: totals differ")
            np.testing.assert_array_equal(
                np.asarray(got.marked), np.asarray(ref.marked),
                err_msg=f"seed-mode {backend}/{stat}: marked differ")
        print(f"OK seed-mode backend={backend}")

    # Log mode over a generate_shard-layout log (the pre-generated-data
    # variant), including a record count that does not divide chunk size.
    slog, _ = generate_sharded_log(jax.random.key(3), cfg, 8, 2048)
    odd = jax.tree.map(lambda x: x[:10_000], slog)
    for backend in BACKENDS:
        ref = malstone_run(odd, cfg.num_sites, mesh=mesh, statistic="B",
                           backend=backend)
        got = malstone_run_streaming(
            odd, cfg.num_sites, mesh=mesh, backend=backend,
            chunk_records=512, statistic="B")
        np.testing.assert_array_equal(
            np.asarray(got.total), np.asarray(ref.total),
            err_msg=f"log-mode {backend}: totals differ")
        np.testing.assert_array_equal(
            np.asarray(got.marked), np.asarray(ref.marked),
            err_msg=f"log-mode {backend}: marked differ")
        print(f"OK log-mode backend={backend}")

    # Adversarial skew through the streaming engine: every record on one
    # site, sub-1.0 capacity — each per-chunk shuffle must run multiple
    # residual rounds and still deliver everything.
    adv = odd._replace(site_id=jax.numpy.zeros_like(odd.site_id))
    ref = malstone_run(adv, cfg.num_sites, mesh=mesh, statistic="B",
                       backend="streams")
    got, stats = malstone_run_streaming(
        adv, cfg.num_sites, mesh=mesh, backend="mapreduce",
        chunk_records=512, statistic="B", capacity_factor=0.25,
        return_shuffle_stats=True)
    np.testing.assert_array_equal(np.asarray(got.total),
                                  np.asarray(ref.total))
    np.testing.assert_array_equal(np.asarray(got.marked),
                                  np.asarray(ref.marked))
    assert int(stats.overflow) == 0, int(stats.overflow)
    assert int(stats.rounds) > 1, int(stats.rounds)
    print(f"OK adversarial streaming shuffle "
          f"(max rounds/chunk={int(stats.rounds)}, overflow=0)")

    print("ALL_OK")


if __name__ == "__main__":
    main()
