"""Multi-device serving-engine equivalence check — run as a subprocess with
8 forced host devices (tests/test_serve.py drives this; the main pytest
process must stay single-device).

Asserts the resident service's incremental ingest is bit-identical to
``malstone_run_streaming`` on all four backends in both ingest modes
(histograms AND per-field ShuffleStats), and that a mixed query batch
matches the integer mask-einsum oracle, on a real 8-way mesh.
"""

import os

from repro.common.env import force_host_devices

force_host_devices(8, extra=os.environ.get("XLA_FLAGS_EXTRA", ""))

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import malstone_run_streaming
from repro.kernels.windowed_ratio.ref import masked_window_ratio_ref
from repro.launch.mesh import make_mesh
from repro.malgen import (
    MalGenConfig,
    generate_chunked_log,
    make_seed_streaming,
)
from repro.serve import (
    MalStoneService,
    default_query_mix,
    encode_query_batch,
)

BACKENDS = ("streams", "sphere", "mapreduce", "mapreduce_combiner")


def check_stats(got, ref, msg):
    if ref is None:
        return
    for f in ref._fields:
        assert int(getattr(got, f)) == int(getattr(ref, f)), (
            f"{msg}: ShuffleStats.{f} differs "
            f"({int(getattr(got, f))} != {int(getattr(ref, f))})")


def main():
    assert jax.device_count() == 8, jax.devices()
    mesh = make_mesh((8,), ("data",))

    cfg = MalGenConfig(num_sites=301, num_entities=1000,
                       marked_site_fraction=0.2, marked_event_fraction=0.3)
    num_chunks, chunk = 32, 512  # 4 chunks per device
    seed = make_seed_streaming(jax.random.key(11), cfg, num_chunks, chunk)
    log = generate_chunked_log(seed, cfg, num_chunks, chunk)

    for backend in BACKENDS:
        ref, ref_stats = malstone_run_streaming(
            seed, cfg.num_sites, mesh=mesh, backend=backend, cfg=cfg,
            num_chunks=num_chunks, chunk_records=chunk,
            return_shuffle_stats=True)

        # seed mode: uneven incremental schedule covering all 4 per-device
        # chunks
        svc = MalStoneService(mesh=mesh, num_sites=cfg.num_sites,
                              chunk_records=chunk, backend=backend,
                              seed=seed, cfg=cfg, num_chunks=num_chunks)
        for k in (1, 2, 1):
            svc.ingest_chunks(k)
        res = svc.result("B")
        _, stats = svc.snapshot()
        np.testing.assert_array_equal(
            np.asarray(res.total), np.asarray(ref.total),
            err_msg=f"seed-mode {backend}: totals differ")
        np.testing.assert_array_equal(
            np.asarray(res.marked), np.asarray(ref.marked),
            err_msg=f"seed-mode {backend}: marked differ")
        check_stats(stats, ref_stats, f"seed-mode {backend}")
        print(f"OK seed-mode backend={backend}")

        # log mode via ingest_slices (same grouping as the streaming scan)
        svc = MalStoneService(mesh=mesh, num_sites=cfg.num_sites,
                              chunk_records=chunk, backend=backend)
        svc.ingest_log(log)
        res = svc.result("B")
        _, stats = svc.snapshot()
        np.testing.assert_array_equal(
            np.asarray(res.total), np.asarray(ref.total),
            err_msg=f"log-mode {backend}: totals differ")
        np.testing.assert_array_equal(
            np.asarray(res.marked), np.asarray(ref.marked),
            err_msg=f"log-mode {backend}: marked differ")
        check_stats(stats, ref_stats, f"log-mode {backend}")
        print(f"OK log-mode backend={backend}")

    # mixed query batch on the last resident service vs the einsum oracle
    hist, _ = svc.snapshot()
    specs = default_query_mix(num_sites=cfg.num_sites)
    batch = encode_query_batch(specs, num_sites=cfg.num_sites)
    rho, num, den = masked_window_ratio_ref(
        jnp.asarray(hist), jnp.asarray(batch.num_masks),
        jnp.asarray(batch.den_masks))
    answers = svc.query(specs)
    for i, ans in enumerate(answers):
        np.testing.assert_array_equal(ans.num, np.asarray(num[i]))
        np.testing.assert_array_equal(ans.den, np.asarray(den[i]))
        np.testing.assert_allclose(ans.rho, np.asarray(rho[i]),
                                   rtol=1e-6, atol=1e-7)
    print("OK query-batch")

    print("ALL_OK")


if __name__ == "__main__":
    main()
