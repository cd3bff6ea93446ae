"""Power-law site popularity (paper §5: "MalGen uses a power law distribution
to model the number of entities associated with a site").

Site ``i`` (after a random permutation, so popularity is not correlated with
the id ordering) gets weight ``(rank+1)^-alpha``. Sampling is inverse-CDF.
``sample_sites`` binary-searches each float32 uniform draw into the
cumulative weight table; it is the oracle. Generation instead looks each
draw up in a table over every value such a draw can take (``site_table``):
a float32 uniform from ``jax.random.uniform`` is ``k * 2^-23`` for an
integer ``k < 2^23``, so the search's answer is a function of ``k`` alone,
computed once per seed by the same search over that grid. One gather per
draw then gives the site the search would give, bit for bit. A seed whose
log has fewer records than the table has entries keeps no table and
searches (``seeding._site_tables``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def power_law_weights(num_sites: int, alpha: float = 1.2,
                      permutation: jnp.ndarray | None = None) -> jnp.ndarray:
    """Normalized float32 weights [num_sites]; heavy head, long tail."""
    ranks = jnp.arange(1, num_sites + 1, dtype=jnp.float32)
    w = ranks ** (-alpha)
    w = w / jnp.sum(w)
    if permutation is not None:
        w = w[permutation]
    return w


def power_law_cdf(weights: jnp.ndarray) -> jnp.ndarray:
    """Inclusive cumulative sum; last element == 1 (renormalized)."""
    cdf = jnp.cumsum(weights.astype(jnp.float32))
    return cdf / cdf[-1]


# A float32 uniform draw is k * 2^-UNIFORM_BITS, k < 2^UNIFORM_BITS: JAX
# fills the 23 mantissa bits of a float in [1, 2) and subtracts 1.
UNIFORM_BITS = 23
SITE_TABLE_SIZE = 1 << UNIFORM_BITS


def _search(cdf: jnp.ndarray, u: jnp.ndarray) -> jnp.ndarray:
    """The inverse-CDF search: int32 site of each draw ``u``."""
    idx = jnp.searchsorted(cdf, u, side="right")
    return jnp.clip(idx, 0, cdf.shape[0] - 1).astype(jnp.int32)


def sample_sites(key: jax.Array, cdf: jnp.ndarray, num: int) -> jnp.ndarray:
    """Inverse-CDF sampling: int32 site indices [num]."""
    u = jax.random.uniform(key, (num,), dtype=jnp.float32)
    return _search(cdf, u)


def site_table(cdf: jnp.ndarray) -> jnp.ndarray:
    """int32 [SITE_TABLE_SIZE]: the site ``sample_sites`` gives for each
    draw ``k * 2^-23``, by the same search over that grid.

    The search itself builds the table, not a count of grid points under
    each CDF entry: the stored float32 CDF need not be monotone (its cumsum
    can step down by an ulp), and only the search gives the search's answer
    there.
    """
    grid = jnp.arange(SITE_TABLE_SIZE, dtype=jnp.int32).astype(jnp.float32) \
        * jnp.float32(2.0 ** -UNIFORM_BITS)
    return _search(cdf, grid)


def draw_sites(key: jax.Array, cdf: jnp.ndarray, table: jnp.ndarray | None,
               num: int) -> jnp.ndarray:
    """``sample_sites(key, cdf, num)``, one gather per draw through
    ``table = site_table(cdf)``; searched where ``table`` is None.
    int32 site indices [num]."""
    if table is None:
        return sample_sites(key, cdf, num)
    u = jax.random.uniform(key, (num,), dtype=jnp.float32)
    k = (u * jnp.float32(SITE_TABLE_SIZE)).astype(jnp.int32)
    return table[k]


def masked_site_cdf(weights: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """Normalized inclusive CDF of ``weights`` restricted to ``mask``.

    This is *seed data*, not per-chunk compute: the cumsum + normalize are
    float32 reductions whose compiled values depend on the XLA fusion
    context they land in (CPU rewrites the normalize divide differently in
    different programs, which flips boundary-adjacent uniform draws to the
    neighboring site). Compute the CDF once at seed time and store it in
    ``SeedInfo``; generation then only draws uniforms (elementwise) and
    looks them up in the site table searched from it at seed time, or
    searches it (exact comparisons) — bitwise deterministic in every
    compilation context.
    """
    w = jnp.where(mask, weights, 0.0)
    cdf = jnp.cumsum(w)
    return cdf / jnp.maximum(cdf[-1], 1e-30)


def sample_sites_masked(key: jax.Array, weights: jnp.ndarray,
                        mask: jnp.ndarray, num: int) -> jnp.ndarray:
    """Sample sites restricted to ``mask`` (True = eligible).

    Used to split generation into the marked-site stream (phase 1) and the
    unmarked-site stream (phase 3) while preserving each site's relative
    popularity.

    Recomputes the masked CDF per call — fine eagerly or inside a single
    program, but NOT stable across different jitted programs (see
    ``masked_site_cdf``). Generation paths must sample against the CDFs
    stored in ``SeedInfo`` instead.
    """
    return sample_sites(key, masked_site_cdf(weights, mask), num)
