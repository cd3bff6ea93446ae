"""MalStone B finalizer — Pallas TPU kernel.

Fuses the Reducer's "running totals computed in date order" (paper §6.1)
with the ratio: given the (site, week) histogram, produce

    rho[s, t] = cumsum_w(marked)[s, t] / cumsum_w(total)[s, t]   (0/0 -> 0)

in one VMEM pass — the unfused path materializes two cumsum arrays and a
divide in HBM. Layout: sites on sublanes (tile rows), weeks on lanes; the
week-axis prefix sum is a matmul against a constant lower-triangular ones
matrix, so even the scan maps onto the MXU:

    cum[TS, W] = hist[TS, W] @ L^T,   L[t, w] = 1{w <= t}

(W = 52 -> one 64/128-padded matmul; exact in f32 since counts < 2^24.)
Counts exceed bf16's 8-bit mantissa, so every matmul asks for full f32
contraction (``Precision.HIGHEST``) rather than the MXU's default pass.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

SITE_TILE = 512
_EXACT = jax.lax.Precision.HIGHEST


def _kernel(total_ref, marked_ref, rho_ref, cum_total_ref, cum_marked_ref, *,
            w_pad: int):
    total = total_ref[...].astype(jnp.float32)    # [TS, W_pad]
    marked = marked_ref[...].astype(jnp.float32)  # [TS, W_pad]

    # lower-triangular ones: cum[:, t] = sum_{w<=t} x[:, w]
    row = jax.lax.broadcasted_iota(jnp.int32, (w_pad, w_pad), 0)  # w index
    col = jax.lax.broadcasted_iota(jnp.int32, (w_pad, w_pad), 1)  # t index
    tri = jnp.where(row <= col, 1.0, 0.0).astype(jnp.float32)

    cum_total = jax.lax.dot_general(
        total, tri, (((1,), (0,)), ((), ())), precision=_EXACT,
        preferred_element_type=jnp.float32)
    cum_marked = jax.lax.dot_general(
        marked, tri, (((1,), (0,)), ((), ())), precision=_EXACT,
        preferred_element_type=jnp.float32)

    rho = jnp.where(cum_total > 0.0,
                    cum_marked / jnp.maximum(cum_total, 1.0), 0.0)
    rho_ref[...] = rho
    cum_total_ref[...] = cum_total.astype(jnp.int32)
    cum_marked_ref[...] = cum_marked.astype(jnp.int32)


def _masked_kernel(total_ref, marked_ref, nmask_ref, dmask_ref,
                   rho_ref, num_ref, den_ref):
    """Batched masked-window reduction for the serving engine: N query
    masks against one site tile. The week axis contracts on the MXU —
    ``num[N, TS] = nmask[N, W] @ marked[TS, W]^T`` — so a whole query
    batch is one matmul pair per tile (exact in f32: counts < 2^24)."""
    total = total_ref[...].astype(jnp.float32)    # [TS, W_pad]
    marked = marked_ref[...].astype(jnp.float32)  # [TS, W_pad]
    nmask = nmask_ref[...]                        # [N_pad, W_pad] f32 0/1
    dmask = dmask_ref[...]

    num = jax.lax.dot_general(
        nmask, marked, (((1,), (1,)), ((), ())), precision=_EXACT,
        preferred_element_type=jnp.float32)       # [N_pad, TS]
    den = jax.lax.dot_general(
        dmask, total, (((1,), (1,)), ((), ())), precision=_EXACT,
        preferred_element_type=jnp.float32)

    rho = jnp.where(den > 0.0, num / jnp.maximum(den, 1.0), 0.0)
    rho_ref[...] = rho
    num_ref[...] = num.astype(jnp.int32)
    den_ref[...] = den.astype(jnp.int32)


def masked_window_ratio_pallas(total: jnp.ndarray, marked: jnp.ndarray,
                               num_masks: jnp.ndarray,
                               den_masks: jnp.ndarray,
                               *, site_tile: int = SITE_TILE,
                               interpret: bool):
    """Raw entry: total/marked int32 [S_pad, W_pad]; num/den masks f32
    [N_pad, W_pad] (N_pad a sublane multiple). Returns
    (rho f32, num i32, den i32), all [N_pad, S_pad] — one row per query,
    one column per site."""
    s_pad, w_pad = total.shape
    n_pad = num_masks.shape[0]
    if s_pad % site_tile != 0:
        raise ValueError(
            f"padded site dim {s_pad} must be a multiple of "
            f"site_tile={site_tile} (total/marked are [{s_pad}, {w_pad}])")
    grid = (s_pad // site_tile,)
    hist_spec = pl.BlockSpec((site_tile, w_pad), lambda i: (i, 0))
    mask_spec = pl.BlockSpec((n_pad, w_pad), lambda i: (0, 0))
    out_spec = pl.BlockSpec((n_pad, site_tile), lambda i: (0, i))

    return pl.pallas_call(
        _masked_kernel,
        grid=grid,
        in_specs=[hist_spec, hist_spec, mask_spec, mask_spec],
        out_specs=[out_spec, out_spec, out_spec],
        out_shape=[
            jax.ShapeDtypeStruct((n_pad, s_pad), jnp.float32),
            jax.ShapeDtypeStruct((n_pad, s_pad), jnp.int32),
            jax.ShapeDtypeStruct((n_pad, s_pad), jnp.int32),
        ],
        interpret=interpret,
    )(total, marked, num_masks, den_masks)


def windowed_ratio_pallas(total: jnp.ndarray, marked: jnp.ndarray,
                          *, site_tile: int = SITE_TILE,
                          interpret: bool):
    """Raw entry: total/marked int32 [S_pad, W_pad]; S_pad % site_tile == 0,
    W_pad a lane multiple. Returns (rho f32, cum_total i32, cum_marked i32),
    all [S_pad, W_pad]."""
    s_pad, w_pad = total.shape
    if s_pad % site_tile != 0:
        raise ValueError(
            f"padded site dim {s_pad} must be a multiple of "
            f"site_tile={site_tile} (total/marked are [{s_pad}, {w_pad}])")
    grid = (s_pad // site_tile,)
    spec = pl.BlockSpec((site_tile, w_pad), lambda i: (i, 0))

    return pl.pallas_call(
        functools.partial(_kernel, w_pad=w_pad),
        grid=grid,
        in_specs=[spec, spec],
        out_specs=[spec, spec, spec],
        out_shape=[
            jax.ShapeDtypeStruct((s_pad, w_pad), jnp.float32),
            jax.ShapeDtypeStruct((s_pad, w_pad), jnp.int32),
            jax.ShapeDtypeStruct((s_pad, w_pad), jnp.int32),
        ],
        interpret=interpret,
    )(total, marked)
