"""The sharding entry points every module imports from one place.

``shard_map`` (with the ``check_vma`` keyword) and ``axis_size`` are the
installed jax's own (``jax.shard_map``, ``jax.lax.axis_size``); keeping one
import site means an upstream move is a one-line change here.
"""

from __future__ import annotations

from jax import shard_map
from jax.lax import axis_size

__all__ = ["axis_size", "shard_map"]
