"""Process/XLA environment configuration — one implementation, every entry
point.

Before this module existed, ~10 call sites (launchers, the bench CLI, the
analysis entry point, every ``tests/md_scripts/*`` subprocess) each hand-rolled
their own ``--xla_force_host_platform_device_count`` string surgery. They all
route through here now, so device forcing, latency-hiding flags, and the
cross-process collective configuration have exactly one implementation.

Everything in this module except :func:`enable_cpu_collectives` and
:func:`enable_compile_cache` is **jax-import-free**: XLA reads
``XLA_FLAGS`` once, when the backend first initializes (the first device
query — NOT ``import jax``), so these helpers must run before that. They
refuse — returning ``False`` — once a backend is already up, rather than
silently setting flags that will never be read.
(Merely having ``import jax`` executed is fine: importing this module pulls
in ``repro.common``, whose ``compat`` module imports jax, so the old
``"jax" in sys.modules`` test would always trip.)

Flag hygiene: XLA aborts at startup on an *unknown* flag, so
:func:`latency_hiding_flags` only ever emits flags known to exist on the named
platform (the GPU async-collective set per SNIPPETS 1). On CPU there is no
safe latency-hiding flag — overlap there comes from async dispatch (see
``repro.core.overlap``) — so the CPU set is empty by design.
"""

from __future__ import annotations

import os
import pathlib
import sys
from typing import Optional, Sequence

FORCE_DEVICES_FLAG = "--xla_force_host_platform_device_count"
COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = pathlib.Path(__file__).resolve().parents[3]

# Known-good latency-hiding / async-collective flags per platform. Unknown
# XLA flags are *fatal* at startup, so nothing speculative goes in here.
_LATENCY_HIDING_FLAGS = {
    "gpu": (
        "--xla_gpu_enable_async_collectives=true",
        "--xla_gpu_enable_latency_hiding_scheduler=true",
        "--xla_gpu_enable_highest_priority_async_stream=true",
    ),
    # CPU/TPU: no stable flag — CPU overlap is done by async dispatch
    # (repro.core.overlap); TPU enables the latency-hiding scheduler by
    # default on current toolchains.
    "cpu": (),
    "tpu": (),
}


def preparse_flag(name: str, default: Optional[str] = None,
                  argv: Optional[Sequence[str]] = None) -> Optional[str]:
    """Pull ``--name VALUE`` / ``--name=VALUE`` out of ``argv`` (default
    ``sys.argv``) before argparse — and therefore before jax — runs. Like
    argparse, the LAST occurrence wins (the spawn parent relies on this:
    it appends rank flags to a re-invoked command line)."""
    argv = sys.argv if argv is None else list(argv)
    value = default
    for i, a in enumerate(argv):
        if a == name and i + 1 < len(argv):
            value = argv[i + 1]
        elif a.startswith(name + "="):
            value = a.split("=", 1)[1]
    return value


def preparse_int_flag(name: str, default: Optional[int] = None,
                      argv: Optional[Sequence[str]] = None) -> Optional[int]:
    """Integer-valued :func:`preparse_flag`."""
    raw = preparse_flag(name, None, argv)
    return default if raw is None else int(raw)


def preparse_nodes(default: int = 2,
                   argv: Optional[Sequence[str]] = None) -> int:
    """The shared ``--nodes`` preparse every CLI front-end uses."""
    return preparse_int_flag("--nodes", default, argv)


def xla_backend_initialized() -> bool:
    """True once any XLA backend is up — the point after which ``XLA_FLAGS``
    edits are dead letters. Deliberately inspects ``sys.modules`` instead of
    importing jax (this module must never trigger the import itself); if the
    private registry moves in a future jax, assume initialized (refusing to
    set flags is always safe — setting unread ones is not)."""
    if "jax" not in sys.modules:
        return False
    bridge = sys.modules.get("jax._src.xla_bridge")
    if bridge is None:
        return False
    try:
        return bool(bridge._backends)
    except AttributeError:
        return True


def add_xla_flags(flags: Sequence[str]) -> bool:
    """Prepend ``flags`` to ``XLA_FLAGS``; must run before the XLA backend
    initializes.

    Returns False (doing nothing) if a backend is already up, ``flags`` is
    empty, or every flag is already present.
    """
    if xla_backend_initialized():
        return False
    current = os.environ.get("XLA_FLAGS", "")
    fresh = [f for f in flags if f.split("=", 1)[0] not in current]
    if not fresh:
        return False
    os.environ["XLA_FLAGS"] = " ".join([*fresh, current]).strip()
    return True


def force_host_devices(n: int, *, extra: str = "",
                       respect_existing: bool = False) -> bool:
    """Force ``n`` XLA host devices; must run before the XLA backend
    initializes.

    Returns False (doing nothing) if a backend is already up, ``n <= 1``,
    or a device-count flag is already present in ``XLA_FLAGS`` (an explicit
    caller setting wins — never silently double-force). With
    ``respect_existing=True`` any pre-set ``XLA_FLAGS`` at all defers to the
    caller (the old ``os.environ.setdefault`` idiom of the analysis entry
    point). ``extra`` appends caller-supplied flags in the same write (the
    ``XLA_FLAGS_EXTRA`` convention of the md_scripts subprocesses).
    """
    if n <= 1 or xla_backend_initialized():
        return False
    current = os.environ.get("XLA_FLAGS", "")
    if FORCE_DEVICES_FLAG in current:
        return False
    if respect_existing and current:
        return False
    os.environ["XLA_FLAGS"] = " ".join(
        part for part in (f"{FORCE_DEVICES_FLAG}={n}", extra, current)
        if part).strip()
    return True


def clear_forced_devices() -> bool:
    """Remove any device-count force from ``XLA_FLAGS``.

    The CURRENT process's backend already read the flag (clearing changes
    nothing locally) — but child processes inherit the environment and must
    decide their own device count, so a spawn parent that forced devices
    for its own oracle computation calls this before forking one-device
    workers. Returns True iff a force flag was removed.
    """
    current = os.environ.get("XLA_FLAGS", "")
    if FORCE_DEVICES_FLAG not in current:
        return False
    os.environ["XLA_FLAGS"] = " ".join(
        p for p in current.split() if not p.startswith(FORCE_DEVICES_FLAG))
    return True


def latency_hiding_flags(platform: str = "cpu") -> tuple:
    """The known latency-hiding XLA flags for ``platform`` (may be empty —
    see module docstring; an unknown platform gets no flags rather than a
    fatal XLA startup error)."""
    return _LATENCY_HIDING_FLAGS.get(platform, ())


def configure_latency_hiding(platform: str = "cpu") -> bool:
    """Prepend the platform's latency-hiding flags to ``XLA_FLAGS`` (no-op
    on platforms with none; must run before jax first imports)."""
    return add_xla_flags(latency_hiding_flags(platform))


def enable_cpu_collectives(impl: str = "gloo") -> bool:
    """Select the CPU cross-process collective implementation.

    ``jax.distributed`` on the CPU backend needs a real collectives layer
    (gloo/mpi) — without it, a multi-process ``psum``/``all_to_all`` fails
    at dispatch. This is a jax *config*, not an env var, so it imports jax
    (safe after flags are set). Returns False when the running jax has no
    such knob (older releases); the distributed init that follows will
    produce its own error if collectives are actually required.
    """
    import jax

    try:
        jax.config.update("jax_cpu_collectives_implementation", impl)
    except (AttributeError, ValueError):
        return False
    return True


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point and
    return its directory.

    ``$JAX_COMPILATION_CACHE_DIR`` wins when it is set; otherwise the cache
    lives at the fixed ``<repo>/.jax_cache`` (git-ignored). The directory
    is never built from temporary names, process ids or the time: a cache
    whose path moves never hits. Only entry points (the launchers,
    ``repro.bench.run``, ``chip_smoke.py``) call this — importing the
    library sets no cache.

    The cache key includes each instruction's metadata: the job names its
    layers with ``jax.named_scope``, and an executable loaded under a key
    without them would carry another program's op paths into a profile.
    Source files enter that metadata by base name, so the key does not
    depend on where the checkout lives.
    """
    import jax

    path = os.environ.get(COMPILE_CACHE_ENV) or str(REPO_ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_hlo_source_file_canonicalization_regex", ".*/")
    return path


def refuse_gang_off_cpu(what: str) -> None:
    """Refuse to fork JAX worker processes unless the platform is forced
    to ``cpu``.

    A multi-process gang on one host is the CPU rehearsal of a multi-host
    run. On a host with a chip, the first process that touches JAX holds
    the chips and every other one fails or hangs, so the chips are driven
    from ONE process through one mesh over all local devices.
    """
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() != "cpu":
        raise RuntimeError(
            f"{what} forks one JAX process per rank, which only works as a "
            f"CPU rehearsal: set JAX_PLATFORMS=cpu. On a host with chips, "
            f"one process drives every local chip through one mesh "
            f"(repro.launch.malstone --nodes N without --num-processes; "
            f"chip_smoke.py --four-chips)")
