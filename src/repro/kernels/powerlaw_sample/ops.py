"""Public jit'd wrapper for the powerlaw_sample Pallas kernel."""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret
from repro.kernels.powerlaw_sample.powerlaw_sample import (
    CDF_TILE,
    RECORD_TILE,
    powerlaw_sample_pallas,
)


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


@functools.partial(
    jax.jit, static_argnames=("record_tile", "cdf_tile", "interpret"))
def powerlaw_sample(u: jnp.ndarray, cdf: jnp.ndarray, *,
                    record_tile: int = RECORD_TILE,
                    cdf_tile: int = CDF_TILE,
                    interpret: Optional[bool] = None) -> jnp.ndarray:
    """Inverse-CDF sampling: int32 site indices, same leading shape as ``u``.

    ``cdf`` must be the inclusive normalized cumulative weights (sorted
    ascending, last element 1.0).
    """
    n = u.shape[0]
    s = cdf.shape[0]
    n_pad = _round_up(max(n, 1), record_tile)
    s_pad = _round_up(max(s, 1), cdf_tile)

    u_p = jnp.pad(u.astype(jnp.float32), (0, n_pad - n))
    u_p = u_p.reshape(n_pad // record_tile, 1, record_tile)
    # pad with +2.0: strictly greater than any u, never counted
    cdf_p = jnp.pad(cdf.astype(jnp.float32), (0, s_pad - s),
                    constant_values=2.0)
    cdf_p = cdf_p.reshape(s_pad, 1)

    counts = powerlaw_sample_pallas(
        u_p, cdf_p, record_tile=record_tile, cdf_tile=cdf_tile,
        interpret=resolve_interpret(interpret))
    idx = counts.reshape(-1)[:n]
    return jnp.clip(idx, 0, s - 1).astype(jnp.int32)


def analysis_cases():
    """Static-analysis cases (see segment_hist.ops.analysis_cases)."""
    n, s = 1 << 15, 100_000
    sds = jax.ShapeDtypeStruct

    def stage():
        return jax.eval_shape(powerlaw_sample.__wrapped__,
                              sds((n,), jnp.float32), sds((s,), jnp.float32))

    # the per-record count accumulates over streamed CDF tiles: the output
    # window for record tile i is revisited at every cdf step j
    return [{"name": "powerlaw_sample/b_preset", "stage": stage,
             "accumulate": {"_kernel": {0}}}]
