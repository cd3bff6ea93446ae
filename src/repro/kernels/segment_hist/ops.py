"""Public jit'd wrapper for the segment_hist Pallas kernel.

Handles padding (records to a tile multiple, sites to the site-tile
multiple), the ``[n_tiles, 1, record_tile]`` record layout, the
[S, 2*W_pad] -> [S, W, 2] relayout, and the interpret-mode switch
(``repro.kernels.resolve_interpret``: compiled on TPU, interpreted
elsewhere unless a caller forces it).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.common.types import EventLog, WEEKS_PER_YEAR
from repro.kernels import resolve_interpret
from repro.kernels.segment_hist.segment_hist import (
    RECORD_TILE,
    SITE_TILE,
    segment_hist_packed_pallas,
    segment_hist_pallas,
    _round_up,
)


@functools.partial(
    jax.jit,
    static_argnames=("num_sites", "num_weeks", "site_tile", "record_tile",
                     "interpret"))
def segment_hist(site: jnp.ndarray, week: jnp.ndarray, mark: jnp.ndarray,
                 valid: jnp.ndarray, *, num_sites: int,
                 num_weeks: int = WEEKS_PER_YEAR,
                 site_tile: int = SITE_TILE,
                 record_tile: int = RECORD_TILE,
                 interpret: Optional[bool] = None) -> jnp.ndarray:
    """int32 [num_sites, num_weeks, 2] histogram via the Pallas kernel."""
    n = site.shape[0]
    n_pad = _round_up(max(n, 1), record_tile)
    s_pad = _round_up(max(num_sites, 1), site_tile)
    w_pad = max(64, _round_up(num_weeks, 64))

    def prep(x, fill=0):
        x = x.astype(jnp.int32).reshape(-1)
        x = jnp.pad(x, (0, n_pad - n), constant_values=fill)
        return x.reshape(n_pad // record_tile, 1, record_tile)

    ok = (valid.astype(jnp.int32) > 0) & (site >= 0) & (site < num_sites) \
        & (week >= 0) & (week < num_weeks)
    out = segment_hist_pallas(
        prep(site), prep(week), prep(mark), prep(ok.astype(jnp.int32)),
        num_sites_padded=s_pad, num_weeks=num_weeks,
        site_tile=site_tile, record_tile=record_tile,
        interpret=resolve_interpret(interpret))

    total = out[:num_sites, :num_weeks]
    marked = out[:num_sites, w_pad:w_pad + num_weeks]
    return jnp.stack([total, marked], axis=-1)


def segment_hist_eventlog(log: EventLog, num_sites: int,
                          num_weeks: int = WEEKS_PER_YEAR,
                          site_offset: int = 0,
                          interpret: Optional[bool] = None) -> jnp.ndarray:
    """Drop-in replacement for ``repro.core.spm.site_week_histogram`` backed
    by the Pallas kernel (same signature contract as ``histogram_fn`` in the
    backends)."""
    valid = log.valid_mask()
    return segment_hist(
        log.site_id - site_offset, log.week(num_weeks=num_weeks), log.mark,
        valid, num_sites=num_sites, num_weeks=num_weeks, interpret=interpret)


@functools.partial(
    jax.jit,
    static_argnames=("num_sites_local", "num_partitions", "num_weeks",
                     "site_tile", "record_tile", "interpret"))
def segment_hist_packed_words(words: jnp.ndarray, my_index: jnp.ndarray, *,
                              num_sites_local: int, num_partitions: int,
                              num_weeks: int = WEEKS_PER_YEAR,
                              site_tile: int = SITE_TILE,
                              record_tile: int = RECORD_TILE,
                              interpret: Optional[bool] = None
                              ) -> jnp.ndarray:
    """The MapReduce reducer's fused unpack+histogram over packed words.

    ``words`` is the flat uint32 stream the exchange delivered (invalid
    slots are zero words) and ``my_index`` this device's mesh position
    (``jax.lax.axis_index``); the kernel unpacks, ownership-filters
    (``site % P == my``) and re-bases in one pass, so the unpacked columns
    never exist. Returns the owned int32 ``[num_sites_local, num_weeks, 2]``
    histogram block — bit-identical to unpack + ``segment_hist``.
    """
    n = words.shape[0]
    n_pad = _round_up(max(n, 1), record_tile)
    s_pad = _round_up(max(num_sites_local, 1), site_tile)
    w_pad = max(64, _round_up(num_weeks, 64))

    words_t = jax.lax.bitcast_convert_type(
        jnp.pad(words.reshape(-1), (0, n_pad - n)), jnp.int32
    ).reshape(n_pad // record_tile, 1, record_tile)
    my = jnp.asarray(my_index, jnp.int32).reshape(1, 1)

    out = segment_hist_packed_pallas(
        words_t, my, num_sites_padded=s_pad, num_weeks=num_weeks,
        num_partitions=num_partitions, site_tile=site_tile,
        record_tile=record_tile, interpret=resolve_interpret(interpret))

    total = out[:num_sites_local, :num_weeks]
    marked = out[:num_sites_local, w_pad:w_pad + num_weeks]
    return jnp.stack([total, marked], axis=-1)


def analysis_cases():
    """Static-analysis cases for ``repro.analysis`` (kernel passes).

    Each case stages a public entry under ``jax.eval_shape`` — the analysis
    framework patches ``pl.pallas_call`` to a recorder first, so grids and
    BlockSpecs are captured at production-preset geometry with zero
    execution. ``accumulate`` declares, per kernel function, which output
    indices legally revisit the same window across grid steps
    (init-at-step-0 + accumulate); undeclared revisits are PK004 races.
    """
    n, s = 1 << 16, 100_000
    sds = jax.ShapeDtypeStruct
    rec = sds((n,), jnp.int32)

    def stage_hist():
        fn = functools.partial(segment_hist.__wrapped__, num_sites=s,
                               num_weeks=WEEKS_PER_YEAR)
        return jax.eval_shape(fn, rec, rec, rec, rec)

    def stage_packed():
        fn = functools.partial(segment_hist_packed_words.__wrapped__,
                               num_sites_local=s // 4, num_partitions=4,
                               num_weeks=WEEKS_PER_YEAR)
        return jax.eval_shape(fn, sds((n,), jnp.uint32), sds((), jnp.int32))

    return [
        {"name": "segment_hist/b_preset", "stage": stage_hist,
         "accumulate": {"_kernel": {0}}},
        {"name": "segment_hist/packed_words", "stage": stage_packed,
         "accumulate": {"_packed_kernel": {0}}},
    ]
