"""The paper's experiment at cloud shape: 8 "nodes" (host devices), the
three middleware backends side by side, MalStone A and B (Tables 4 & 5).

    PYTHONPATH=src python examples/malstone_cloud.py
"""

import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import time

import jax
import numpy as np

from repro.core import malstone_run, malstone_single_device
from repro.launch.mesh import make_mesh
from repro.malgen import MalGenConfig, generate_sharded_log


def main():
    nodes = jax.device_count()
    mesh = make_mesh((nodes,), ("data",))
    cfg = MalGenConfig(num_sites=10_000, num_entities=100_000)
    rps = 262_144
    print(f"MalGen: {nodes} nodes x {rps} records "
          f"({nodes * rps * 100 / 1e6:.0f} MB at 100 B/record)")
    log, _ = generate_sharded_log(jax.random.key(0), cfg, nodes, rps)

    ref = malstone_single_device(log, cfg.num_sites, statistic="B")

    print(f"\n{'backend':<12} {'stat':<5} {'time':>9}  matches-reference")
    for stat in ("A", "B"):
        for backend in ("streams", "sphere", "mapreduce"):
            fn = jax.jit(lambda l, b=backend, s=stat: malstone_run(
                l, cfg.num_sites, mesh=mesh, statistic=s, backend=b).rho)
            fn(log).block_until_ready()          # compile
            t0 = time.perf_counter()
            rho = fn(log)
            rho.block_until_ready()
            dt = time.perf_counter() - t0
            if stat == "B":
                ok = np.allclose(np.asarray(rho), np.asarray(ref.rho),
                                 rtol=1e-6)
            else:
                ok = True
            print(f"{backend:<12} {stat:<5} {dt * 1e3:8.1f}ms  {ok}")

    print("\nNote: on one CPU host the collectives are memcpys; the real"
          "\nmiddleware gap (paper's ~20x) shows up in bytes-on-interconnect —"
          "\nsee EXPERIMENTS.md §Roofline for the 256/512-chip dry-run "
          "numbers.")


if __name__ == "__main__":
    main()
