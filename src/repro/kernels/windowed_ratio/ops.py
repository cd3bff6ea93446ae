"""Public jit'd wrapper for the windowed_ratio Pallas kernel."""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret
from repro.kernels.windowed_ratio.windowed_ratio import (
    SITE_TILE,
    masked_window_ratio_pallas,
    windowed_ratio_pallas,
)


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@functools.partial(jax.jit, static_argnames=("site_tile", "interpret"))
def windowed_ratio(hist: jnp.ndarray, *, site_tile: int = SITE_TILE,
                   interpret: Optional[bool] = None):
    """MalStone B finalize: hist int32 [S, W, 2] ->
    (rho f32 [S, W], cum_total i32, cum_marked i32)."""
    s, w, _ = hist.shape
    s_pad = _round_up(max(s, 1), site_tile)
    w_pad = max(128, _round_up(w, 128))

    def pad(x):
        return jnp.pad(x.astype(jnp.int32), ((0, s_pad - s), (0, w_pad - w)))

    rho, cum_t, cum_m = windowed_ratio_pallas(
        pad(hist[..., 0]), pad(hist[..., 1]),
        site_tile=site_tile, interpret=resolve_interpret(interpret))
    return rho[:s, :w], cum_t[:s, :w], cum_m[:s, :w]


@functools.partial(jax.jit, static_argnames=("site_tile", "interpret"))
def masked_window_ratio(hist: jnp.ndarray, num_masks: jnp.ndarray,
                        den_masks: jnp.ndarray, *,
                        site_tile: int = SITE_TILE,
                        interpret: Optional[bool] = None):
    """Batched query reducer for the serving engine: hist int32 [S, W, 2]
    plus N numerator/denominator week masks (bool/int [N, W]) ->
    (rho f32 [N, S], num i32 [N, S], den i32 [N, S]) in one kernel
    dispatch — row i answers query i over every site."""
    s, w, _ = hist.shape
    n = num_masks.shape[0]
    if den_masks.shape != num_masks.shape:
        raise ValueError(
            f"num/den mask shapes differ: {num_masks.shape} vs "
            f"{den_masks.shape}")
    s_pad = _round_up(max(s, 1), site_tile)
    w_pad = max(128, _round_up(w, 128))
    n_pad = max(8, _round_up(n, 8))

    def pad_hist(x):
        return jnp.pad(x.astype(jnp.int32), ((0, s_pad - s), (0, w_pad - w)))

    def pad_mask(m):
        return jnp.pad(m.astype(jnp.float32),
                       ((0, n_pad - n), (0, w_pad - w)))

    rho, num, den = masked_window_ratio_pallas(
        pad_hist(hist[..., 0]), pad_hist(hist[..., 1]),
        pad_mask(num_masks), pad_mask(den_masks),
        site_tile=site_tile, interpret=resolve_interpret(interpret))
    return rho[:n, :s], num[:n, :s], den[:n, :s]


def analysis_cases():
    """Static-analysis cases (see segment_hist.ops.analysis_cases)."""
    s, w, n = 100_000, 52, 64

    def stage():
        return jax.eval_shape(windowed_ratio.__wrapped__,
                              jax.ShapeDtypeStruct((s, w, 2), jnp.int32))

    def stage_masked():
        masks = jax.ShapeDtypeStruct((n, w), jnp.bool_)
        return jax.eval_shape(masked_window_ratio.__wrapped__,
                              jax.ShapeDtypeStruct((s, w, 2), jnp.int32),
                              masks, masks)

    # single pass over site tiles: no window is ever revisited
    return [{"name": "windowed_ratio/b_preset", "stage": stage,
             "accumulate": {}},
            {"name": "windowed_ratio/masked_batch64", "stage": stage_masked,
             "accumulate": {}}]
