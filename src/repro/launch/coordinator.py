"""Multi-process launch bootstrap: ``jax.distributed`` over localhost or a
cluster.

The paper's numbers are wall-clock times on a real 20-node cloud; everything
this repo measured before ran inside ONE process on forced host devices.
This module is the missing rung: it stands up genuine multi-process JAX —
N processes, each owning a slice of the global device set, joined by a
coordinator so ``jax.devices()`` spans all of them and the packed exchange's
``all_to_all`` / psum'd round termination run across real process
boundaries.

Three launch modes, selected by the shared ``--num-processes`` /
``--process-id`` / ``--coordinator`` flags (``add_arguments``):

- **single-process** (default): ``initialize`` just forces local host
  devices and returns — nothing distributed.
- **worker**: ``--process-id K --coordinator HOST:PORT`` — this process is
  rank K of an externally-launched gang (SLURM, mpirun, or the spawn parent
  below). ``initialize`` configures CPU collectives (gloo) and calls
  ``jax.distributed.initialize``.
- **spawn parent**: ``--num-processes N`` *without* ``--process-id`` —
  auto-spawn localhost mode for CI/CPU. ``spawn_local`` re-invokes this
  very command N times with ``--coordinator 127.0.0.1:<port>
  --process-id k`` appended, waits for all workers, and returns the first
  nonzero exit status. The parent never initializes jax.

The preparse half is **jax-import-free** on purpose: device forcing and
``XLA_FLAGS`` must be decided before the caller's first jax import, so entry
points call ``preparse()`` / ``initialize()`` at module import time, before
``import jax`` (see ``repro.launch.malstone``).
"""

from __future__ import annotations

import dataclasses
import socket
import subprocess
import sys
from typing import Optional, Sequence

from repro.common import env


@dataclasses.dataclass(frozen=True)
class DistConfig:
    """Parsed distributed-launch configuration (jax-free)."""

    num_processes: int = 1
    process_id: Optional[int] = None
    coordinator: Optional[str] = None

    @property
    def is_distributed(self) -> bool:
        return self.num_processes > 1

    @property
    def is_spawn_parent(self) -> bool:
        """``--num-processes N`` given without a rank: this invocation's job
        is to fork the N localhost workers, not to compute."""
        return self.num_processes > 1 and self.process_id is None

    @property
    def is_worker(self) -> bool:
        return self.num_processes > 1 and self.process_id is not None


def add_arguments(ap) -> None:
    """Attach the distributed flags to an ``argparse`` parser (display/
    validation only — the values that *matter* are preparsed before jax
    loads; keep both in sync via ``preparse``)."""
    g = ap.add_argument_group(
        "distributed launch (repro.launch.coordinator)")
    g.add_argument("--num-processes", type=int, default=1, metavar="N",
                   help="run as N cooperating jax processes; without"
                        " --process-id this invocation becomes a spawn"
                        " parent that forks N localhost workers")
    g.add_argument("--process-id", type=int, default=None, metavar="K",
                   help="this process's rank in [0, N) — set by the spawn"
                        " parent, or by an external launcher (SLURM/mpirun)")
    g.add_argument("--coordinator", default=None, metavar="HOST:PORT",
                   help="jax.distributed coordinator address (spawn parent"
                        " picks a free localhost port automatically)")


def preparse(argv: Optional[Sequence[str]] = None) -> DistConfig:
    """Build a :class:`DistConfig` from raw argv, before argparse and
    before jax import (mirrors ``add_arguments``)."""
    return DistConfig(
        num_processes=env.preparse_int_flag("--num-processes", 1, argv) or 1,
        process_id=env.preparse_int_flag("--process-id", None, argv),
        coordinator=env.preparse_flag("--coordinator", None, argv),
    )


def pick_port() -> int:
    """A free localhost TCP port for the coordinator (bind-to-0 probe)."""
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def spawn_local(cfg: DistConfig, argv: Optional[Sequence[str]] = None, *,
                timeout: Optional[float] = None) -> int:
    """Fork ``cfg.num_processes`` localhost workers of this very command.

    Each worker is ``sys.executable + argv`` with ``--coordinator
    127.0.0.1:<fresh port> --process-id k`` appended (preparse reads the
    LAST occurrence of a flag, so the appended rank wins even if argv
    already mentions one). Workers inherit the environment and stream their
    output directly; returns the first nonzero worker exit status (0 when
    all succeed). On timeout every worker is killed and 124 is returned.
    Refuses (``RuntimeError``) unless ``JAX_PLATFORMS=cpu``: one process
    per chip (``env.refuse_gang_off_cpu``).
    """
    env.refuse_gang_off_cpu("spawn_local")
    argv = list(sys.argv if argv is None else argv)
    address = cfg.coordinator or f"127.0.0.1:{pick_port()}"
    procs = []
    for rank in range(cfg.num_processes):
        cmd = [sys.executable] + argv + [
            "--coordinator", address, "--process-id", str(rank)]
        procs.append(subprocess.Popen(cmd))
    status = 0
    try:
        for p in procs:
            rc = p.wait(timeout=timeout)
            if rc != 0 and status == 0:
                status = rc
    except subprocess.TimeoutExpired:
        status = 124
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
    return status


def initialize(cfg: DistConfig, *, local_devices: int = 1,
               collectives: str = "gloo") -> bool:
    """Configure the process runtime and (if distributed) join the gang.

    Must run before any *other* jax usage: forces ``local_devices`` host
    devices per process (so a worker gang presents ``num_processes x
    local_devices`` global devices), selects the CPU collectives layer, and
    calls ``jax.distributed.initialize`` with this process's rank. Returns
    True iff distributed initialization actually happened; the
    single-process case just forces devices. A spawn parent must not call
    this — run :func:`spawn_local` instead (this raises to catch that
    mix-up).
    """
    if cfg.is_spawn_parent:
        raise ValueError(
            "spawn parent must not initialize jax — call spawn_local() and"
            " exit with its status; only workers (--process-id) initialize")
    if local_devices > 1:
        env.force_host_devices(local_devices)
    if not cfg.is_distributed:
        return False
    if cfg.coordinator is None:
        raise ValueError(
            "worker needs --coordinator HOST:PORT (the spawn parent appends"
            " it automatically)")
    if not 0 <= cfg.process_id < cfg.num_processes:
        raise ValueError(
            f"--process-id {cfg.process_id} out of range for"
            f" --num-processes {cfg.num_processes}")
    env.enable_cpu_collectives(collectives)
    import jax

    jax.distributed.initialize(
        coordinator_address=cfg.coordinator,
        num_processes=cfg.num_processes,
        process_id=cfg.process_id)
    return True


def process_banner(cfg: DistConfig) -> str:
    """One-line launch description for run logs."""
    if not cfg.is_distributed:
        return "single-process"
    return (f"process {cfg.process_id}/{cfg.num_processes} "
            f"via {cfg.coordinator}")


def bootstrap(argv: Optional[Sequence[str]] = None, *,
              local_devices_for: Optional[int] = None) -> DistConfig:
    """The one-call entry-point preamble: preparse, spawn-and-exit if this
    is a spawn parent, otherwise initialize and return the config.

    ``local_devices_for`` is the TOTAL logical node count the run wants
    (e.g. ``--nodes``); each process forces its even share
    (``total // num_processes``). Call this at module scope, before
    ``import jax``::

        _DIST = coordinator.bootstrap(local_devices_for=_N)
    """
    cfg = preparse(argv)
    if cfg.is_spawn_parent:
        raise SystemExit(spawn_local(cfg, argv))
    local = 1
    if local_devices_for is not None:
        if local_devices_for % cfg.num_processes != 0:
            raise SystemExit(
                f"--nodes ({local_devices_for}) must divide evenly over"
                f" --num-processes ({cfg.num_processes})")
        local = local_devices_for // cfg.num_processes
    initialize(cfg, local_devices=local)
    return cfg
