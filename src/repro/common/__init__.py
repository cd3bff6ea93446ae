"""Shared substrate: array types, pytree helpers, numerics config, the
sharding entry points."""

from repro.common.compat import shard_map
from repro.common.types import (
    EventLog,
    SpmResult,
    WindowSpec,
    SECONDS_PER_WEEK,
    SECONDS_PER_YEAR,
    WEEKS_PER_YEAR,
)
from repro.common import tree

__all__ = [
    "shard_map",
    "EventLog",
    "SpmResult",
    "WindowSpec",
    "SECONDS_PER_WEEK",
    "SECONDS_PER_YEAR",
    "WEEKS_PER_YEAR",
    "tree",
]
