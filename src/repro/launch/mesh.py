"""Mesh construction: the repo's one mesh constructor and its layouts.

Functions (not module-level constants) so importing this module never
touches jax device state — device count is locked at first jax init, and
only launch/dryrun.py (which sets XLA_FLAGS before any import) should see
512 devices.

Every mesh is built by :func:`make_mesh` with ``AxisType.Auto`` axes.
``jax.make_mesh`` defaults to Explicit axes on current jax; arrays then
carry their mesh axes in their types, and the Pallas lowering of the
serving query kernel rejects them (``ShardingTypeError``). The drivers'
``shard_map`` programs are written for Auto axes.
"""

from __future__ import annotations

import math

import jax
import numpy as np
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with every axis ``AxisType.Auto`` — the only place
    in ``src/`` that calls it. ``devices`` defaults to ``jax.devices()``
    (pass described devices to compile for a chip that is not attached)."""
    axes = tuple(axes)
    return jax.make_mesh(tuple(shape), axes,
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """Single-pod: (16, 16) = (data, model), 256 chips.
    Multi-pod:  (2, 16, 16) = (pod, data, model), 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def batch_axes(mesh) -> tuple:
    """Mesh axes the global batch shards over."""
    return tuple(a for a in ("pod", "data") if a in mesh.shape)


def make_host_mesh(n: int = 8, axes=("data",), shape=None):
    """Small host-device mesh for functional multi-device tests.

    Multi-axis meshes need an explicit ``shape`` whose product is ``n``
    (the old implementation built an empty shape for ``len(axes) > 1``,
    which can never match the axis names)."""
    if shape is None:
        if len(axes) != 1:
            raise ValueError(
                f"make_host_mesh needs an explicit shape= for multi-axis"
                f" axes {tuple(axes)!r} (whose product must be n={n})")
        shape = (n,)
    shape = tuple(shape)
    if math.prod(shape) != n:
        raise ValueError(
            f"mesh shape {shape} places {math.prod(shape)} devices,"
            f" not n={n}")
    return make_mesh(shape, axes)


def make_global_mesh(axes=("data",)):
    """1-D mesh over ALL devices — across every process once
    ``jax.distributed.initialize`` has run (``jax.devices()`` is the global
    device list), degenerating to the usual host mesh single-process.

    Built from the explicit device array (not :func:`make_mesh`'s
    reordering heuristics) so the device→rank order is the deterministic
    process-major one the multi-process bit-identity tests assume. Note
    ``np.array``, never ``jnp.array``: Device objects aren't JAX types.
    """
    if len(axes) != 1:
        raise ValueError(f"make_global_mesh is 1-D; got axes {tuple(axes)!r}")
    return jax.sharding.Mesh(np.array(jax.devices()), tuple(axes))


def replicate_to_mesh(tree, mesh):
    """Commit a host pytree to a fully-replicated sharding over the global
    (possibly multi-process) mesh.

    Every process holds identical values by construction (same root PRNG
    key), so each just donates its local copy to its addressable shards via
    ``make_array_from_callback`` — nothing crosses the network. ``device_put``
    cannot do this: it refuses shardings with non-addressable devices.
    Typed PRNG keys round-trip through ``key_data``/``wrap_key_data``
    (``make_array_from_callback`` only takes raw dtypes). Static Python
    leaves (e.g. ``SeedInfo.num_marked_events``) pass through untouched.
    """
    from jax.sharding import NamedSharding, PartitionSpec

    sharding = NamedSharding(mesh, PartitionSpec())

    def globalize(a):
        return jax.make_array_from_callback(a.shape, sharding,
                                            lambda idx, arr=a: arr[idx])

    def put(x):
        if not hasattr(x, "dtype"):
            return x
        if jax.dtypes.issubdtype(x.dtype, jax.dtypes.prng_key):
            data = globalize(np.asarray(jax.random.key_data(x)))
            return jax.random.wrap_key_data(data)
        return globalize(np.asarray(x))

    return jax.tree.map(put, tree)


def shard_log_to_mesh(log, mesh, axis_name: str = "data"):
    """Commit a host-materialized log to the record-sharded global layout
    (device d takes contiguous block d) — the multi-process twin of handing
    ``malstone_run`` a local log. Every process generated the identical
    full log (same key), so each donates its addressable blocks."""
    from jax.sharding import NamedSharding, PartitionSpec

    from repro.common.types import EventLog

    sharding = NamedSharding(mesh, PartitionSpec(axis_name))

    def put(x):
        if x is None:
            return None
        a = np.asarray(x)
        return jax.make_array_from_callback(a.shape, sharding,
                                            lambda idx, arr=a: arr[idx])

    return EventLog(*(put(col) for col in log))
