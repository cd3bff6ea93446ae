"""The plain reference of MalStone B and the comparison that decides
``correct``.

MalStone B (arXiv:1007.1261, section 3): for every site ``j`` and week
``t``, ``total[j, t]`` counts the records at ``j`` in weeks ``<= t``,
``marked[j, t]`` those of them whose entity was already marked, and
``rho[j, t] = marked / total`` (0 where ``total`` is 0). A record's week is
``timestamp // 604800``, clipped to ``[0, num_weeks)``.

The reference counts on the host with ``numpy.bincount`` over one int32 key
per record, ``(site * num_weeks + week) * 2 + mark``, which plain ``jnp``
computes on the device block by block. It imports nothing of the program.
Its ratio is numpy's correctly rounded float32 quotient of the float32
counts. A TPU's float32 divide is not correctly rounded, so ``rho`` is
compared in units in the last place; ``total`` and ``marked`` must be equal.
"""

from __future__ import annotations

from typing import Iterable, NamedTuple

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

SECONDS_PER_WEEK = 7 * 86_400


class Answer(NamedTuple):
    rho: np.ndarray     # float32 [sites, weeks]
    total: np.ndarray   # int32 [sites, weeks]
    marked: np.ndarray  # int32 [sites, weeks]


def record_keys(site_id, timestamp, mark, num_weeks: int):
    """One int32 key per record (works on numpy or jnp arrays)."""
    xp = jnp if isinstance(site_id, jax.Array) else np
    week = xp.clip(timestamp // SECONDS_PER_WEEK, 0, num_weeks - 1)
    return ((site_id * num_weeks + week) * 2 + (mark > 0)).astype(xp.int32)


def count_keys(key_blocks: Iterable[np.ndarray], num_sites: int,
               num_weeks: int) -> np.ndarray:
    """int64 ``[sites, weeks, 2]``: records per (site, week) with mark 0
    and with mark 1, summed over the blocks."""
    bins = num_sites * num_weeks * 2
    counts = np.zeros(bins, np.int64)
    for keys in key_blocks:
        keys = np.asarray(keys).ravel()
        if keys.size and (keys.min() < 0 or keys.max() >= bins):
            raise ValueError("a record key lies outside the site x week grid")
        counts += np.bincount(keys, minlength=bins)
    return counts.reshape(num_sites, num_weeks, 2)


def _ratio(marked: np.ndarray, total: np.ndarray, dtype) -> np.ndarray:
    num = marked.astype(np.float32).astype(dtype)
    den = np.maximum(total, 1).astype(np.float32).astype(dtype)
    return np.where(total > 0, num / den, 0).astype(np.float32)


def malstone_b(counts: np.ndarray, ratio_dtype=np.float32,
               count_dtype=np.int64) -> Answer:
    """MalStone B from ``count_keys``' counts. A ``ratio_dtype`` below
    float32, or float32 as ``count_dtype``, gives a control."""
    weekly = counts.astype(count_dtype)
    total = np.cumsum(weekly.sum(axis=-1), axis=-1, dtype=count_dtype)
    marked = np.cumsum(weekly[..., 1], axis=-1, dtype=count_dtype)
    if total.size and total.max() >= 2**31:
        raise ValueError("a cumulative count does not fit int32")
    return Answer(_ratio(marked, total, ratio_dtype),
                  total.astype(np.int32), marked.astype(np.int32))


def control(counts: np.ndarray) -> Answer:
    """The reference with its ratio in bfloat16, the precision below the
    configuration's float32: what a cheaper finalize would return."""
    return malstone_b(counts, ml_dtypes.bfloat16)


def count_control(counts: np.ndarray) -> Answer:
    """The reference with its counts summed in float32 instead of the
    configuration's int32: exact only while every count stays below 2^24,
    what a histogram or a running total kept in float32 would return."""
    return malstone_b(counts, count_dtype=np.float32)


CONTROLS = {"ratio_bfloat16": control, "counts_float32": count_control}


def ulp_distance(a: np.ndarray, b: np.ndarray) -> int:
    """Largest distance, in float32 units in the last place, between two
    arrays of non-negative float32 (NaN counts as the largest distance)."""
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    if a.shape != b.shape:
        return 2**31 - 1
    if np.isnan(a).any() or np.isnan(b).any() or (a < 0).any() or (b < 0).any():
        return 2**31 - 1
    diff = np.abs(a.view(np.int32).astype(np.int64)
                  - b.view(np.int32).astype(np.int64))
    return int(diff.max()) if diff.size else 0


def compare(got: Answer, want: Answer) -> dict:
    """The numbers compared: elements of ``total`` and ``marked`` that
    differ, and the largest ``rho`` distance in float32 ulps."""
    def differ(x, y):
        x, y = np.asarray(x), np.asarray(y)
        if x.shape != y.shape:
            return int(y.size) or 1
        return int(np.count_nonzero(x != y))

    return {"total_mismatch": differ(got.total, want.total),
            "marked_mismatch": differ(got.marked, want.marked),
            "rho_ulp": ulp_distance(got.rho, want.rho)}
