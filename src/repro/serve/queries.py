"""Batched MalStone queries over a resident site x week histogram.

A query asks for one statistic — A, B, or B-fixed — over an arbitrary
week-aligned monitor window (``repro.core.windows``), optionally with the
top-k worst sites and a per-site drill-down. N queries are *stacked*: each
spec becomes one numerator mask row and one denominator mask row ([N, W]),
and the whole batch is answered by ONE jitted device dispatch — the masked
``windowed_ratio`` kernel contracts every mask against every site tile on
the MXU, ``lax.top_k`` ranks the sites, and the drill-down rows are one
gather. Mask semantics per statistic (``hist[..., 0]`` = total events,
``hist[..., 1]`` = marked):

- **A** — ratio within the monitor window: numerator and denominator both
  mask ``week_mask_for_window(mon)``.
- **B** — the running ratio (paper §6's "running totals in date order")
  read out at the window's end: both masks are the prefix
  ``[year start, mon_end)``, so a growing-window sequence reproduces
  ``malstone_b``'s rho columns exactly.
- **B-fixed** — Definition 1's literal reading: prefix numerator over a
  denominator fixed by the *exposure* window mask.

Masks are 0/1, so every count is an exact integer sum — the decoded
answers are bit-identical to computing each statistic independently.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.types import (
    SECONDS_PER_YEAR,
    WEEKS_PER_YEAR,
    WindowSpec,
    safe_ratio,
)
from repro.core.windows import week_mask_for_window

STATISTICS = ("A", "B", "B-fixed")


@dataclasses.dataclass(frozen=True)
class QuerySpec:
    """One query against the resident histogram.

    ``window.mon_*`` bounds the monitor window (week-aligned seconds);
    ``window.exp_*`` only matters for ``B-fixed`` (the fixed denominator).
    ``top_k > 0`` additionally returns the k worst sites by rho;
    ``site`` additionally returns that site's rho and raw week rows.
    """

    statistic: str = "B"
    window: WindowSpec = WindowSpec.full_year()
    top_k: int = 0
    site: Optional[int] = None

    def __post_init__(self):
        if self.statistic not in STATISTICS:
            raise ValueError(
                f"unknown statistic {self.statistic!r}; have {STATISTICS}")
        if self.top_k < 0:
            raise ValueError(f"top_k must be >= 0, got {self.top_k}")
        if self.site is not None and self.site < 0:
            raise ValueError(f"site must be >= 0, got {self.site}")


@dataclasses.dataclass(frozen=True)
class QueryBatch:
    """N stacked query specs encoded as device-ready mask arrays."""

    specs: tuple                # the original QuerySpecs, in order
    num_masks: np.ndarray       # bool [N, W] numerator week mask per query
    den_masks: np.ndarray       # bool [N, W] denominator week mask
    sites: np.ndarray           # int32 [N] drilldown site (0 when unused)
    max_top_k: int              # k for the shared lax.top_k (0 = skip)


@dataclasses.dataclass(frozen=True)
class QueryAnswer:
    """Decoded answer for one QuerySpec (numpy, host-side)."""

    spec: QuerySpec
    rho: np.ndarray                       # f32 [num_sites] per-site ratio
    num: np.ndarray                       # i32 [num_sites] numerator counts
    den: np.ndarray                       # i32 [num_sites] denominator
    top_sites: Optional[np.ndarray] = None    # i32 [top_k] worst sites
    top_rho: Optional[np.ndarray] = None      # f32 [top_k]
    site_rho: Optional[float] = None          # drilldown ratio
    site_total: Optional[np.ndarray] = None   # i32 [W] raw week row
    site_marked: Optional[np.ndarray] = None  # i32 [W]


def query_masks(spec: QuerySpec, num_weeks: int = WEEKS_PER_YEAR):
    """(numerator, denominator) week masks for one spec (bool [W] each)."""
    win = spec.window
    mon = week_mask_for_window(win, num_weeks)
    prefix = week_mask_for_window(
        WindowSpec(win.exp_start, win.exp_end, 0, win.mon_end), num_weeks)
    if spec.statistic == "A":
        return mon, mon
    if spec.statistic == "B":
        return prefix, prefix
    # B-fixed: running numerator over the exposure-window denominator
    exposure = week_mask_for_window(
        WindowSpec(win.exp_start, win.exp_end, win.exp_start, win.exp_end),
        num_weeks)
    return prefix, exposure


def encode_query_batch(specs: Sequence[QuerySpec],
                       num_weeks: int = WEEKS_PER_YEAR,
                       num_sites: Optional[int] = None) -> QueryBatch:
    """Stack N specs into one device-ready mask batch (host-side)."""
    specs = tuple(specs)
    if not specs:
        raise ValueError("encode_query_batch needs at least one QuerySpec")
    num_masks = np.zeros((len(specs), num_weeks), bool)
    den_masks = np.zeros((len(specs), num_weeks), bool)
    sites = np.zeros((len(specs),), np.int32)
    for i, spec in enumerate(specs):
        nm, dm = query_masks(spec, num_weeks)
        num_masks[i] = np.asarray(nm)
        den_masks[i] = np.asarray(dm)
        if spec.site is not None:
            if num_sites is not None and spec.site >= num_sites:
                raise ValueError(
                    f"spec[{i}].site={spec.site} out of range "
                    f"(num_sites={num_sites})")
            sites[i] = spec.site
    max_top_k = max((s.top_k for s in specs), default=0)
    if num_sites is not None and max_top_k > num_sites:
        raise ValueError(
            f"top_k={max_top_k} exceeds num_sites={num_sites}")
    return QueryBatch(specs=specs, num_masks=num_masks, den_masks=den_masks,
                      sites=sites, max_top_k=max_top_k)


@functools.partial(jax.jit,
                   static_argnames=("max_top_k", "kernel_path", "interpret"))
def batched_query(hist: jnp.ndarray, num_masks: jnp.ndarray,
                  den_masks: jnp.ndarray, sites: jnp.ndarray, *,
                  max_top_k: int = 0, kernel_path: str = "pallas",
                  interpret: Optional[bool] = None):
    """ONE device dispatch answering every stacked query.

    hist int32 [S, W, 2] (the resident snapshot), masks bool [N, W],
    sites int32 [N]. Returns ``(rho [N, S], num [N, S], den [N, S],
    top_rho [N, k], top_sites [N, k], site_rows [N, W, 2])`` — every
    answer block for the whole batch, computed together.
    """
    if kernel_path == "pallas":
        from repro.kernels.windowed_ratio.ops import masked_window_ratio
        rho, num, den = masked_window_ratio(
            hist, num_masks, den_masks, interpret=interpret)
    elif kernel_path == "ref":
        num = jnp.einsum("nw,sw->ns", num_masks.astype(jnp.int32),
                         hist[..., 1])
        den = jnp.einsum("nw,sw->ns", den_masks.astype(jnp.int32),
                         hist[..., 0])
        rho = safe_ratio(num, den)
    else:
        raise ValueError(f"unknown kernel_path {kernel_path!r}")
    if max_top_k > 0:
        top_rho, top_sites = jax.lax.top_k(rho, max_top_k)
    else:
        n = rho.shape[0]
        top_rho = jnp.zeros((n, 0), rho.dtype)
        top_sites = jnp.zeros((n, 0), jnp.int32)
    site_rows = hist[sites]  # [N, W, 2] drilldown gather
    return rho, num, den, top_rho, top_sites, site_rows


def decode_answers(batch: QueryBatch, outputs) -> list:
    """Split the batched device outputs back into per-spec QueryAnswers."""
    rho, num, den, top_rho, top_sites, site_rows = (
        np.asarray(x) for x in outputs)
    answers = []
    for i, spec in enumerate(batch.specs):
        ans = QueryAnswer(spec=spec, rho=rho[i], num=num[i], den=den[i])
        if spec.top_k > 0:
            ans = dataclasses.replace(
                ans, top_sites=top_sites[i, :spec.top_k],
                top_rho=top_rho[i, :spec.top_k])
        if spec.site is not None:
            ans = dataclasses.replace(
                ans, site_rho=float(rho[i, spec.site]),
                site_total=site_rows[i, :, 0],
                site_marked=site_rows[i, :, 1])
        answers.append(ans)
    return answers


def growing_window_specs(statistic: str = "B",
                         num_weeks: int = WEEKS_PER_YEAR) -> list:
    """The paper's MalStone B window sequence as query specs (week 1..W)."""
    from repro.core.windows import growing_monitor_windows
    return [QuerySpec(statistic=statistic, window=w)
            for w in growing_monitor_windows(num_weeks)]


def default_query_mix(num_weeks: int = WEEKS_PER_YEAR,
                      num_sites: int = 1, top_k: int = 8) -> list:
    """A small mixed batch touching every answer block (bench/CI unit)."""
    from repro.common.types import SECONDS_PER_WEEK
    mid = (num_weeks // 2) * SECONDS_PER_WEEK
    year = WindowSpec(0, SECONDS_PER_YEAR, 0, SECONDS_PER_YEAR)
    half = WindowSpec(0, SECONDS_PER_YEAR, mid, SECONDS_PER_YEAR)
    return [
        QuerySpec(statistic="A", window=year),
        QuerySpec(statistic="A", window=half),
        QuerySpec(statistic="B", window=WindowSpec(
            0, SECONDS_PER_YEAR, 0, mid or SECONDS_PER_YEAR)),
        QuerySpec(statistic="B", window=year, top_k=min(top_k, num_sites)),
        QuerySpec(statistic="B-fixed", window=year,
                  site=max(0, num_sites - 1)),
    ]
