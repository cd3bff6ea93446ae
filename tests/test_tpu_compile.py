"""Compile every Pallas kernel for a TPU v5e at production geometry.

Interpret mode runs a kernel's body but not Mosaic's rules (block tiling,
VMEM budget, supported ops), so these tests lower and compile each kernel
against a described ``v5e:2x2`` topology: 100k sites (MalGen's default
deployment), a 2^20-record chunk, and 4 partitions for the exchange and the
packed reducer. Nothing runs; a compile that Mosaic refuses fails here.

The topology is described inside a module-scoped fixture (never at import
time) so every pytest-xdist worker collects the same tests and only the
worker running this file loads the TPU compiler.
"""

import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.common.types import WEEKS_PER_YEAR
from repro.kernels.count_scatter import count_scatter
from repro.kernels.powerlaw_sample.ops import powerlaw_sample
from repro.kernels.segment_hist.ops import (
    segment_hist,
    segment_hist_packed_words,
)
from repro.kernels.windowed_ratio.ops import (
    masked_window_ratio,
    windowed_ratio,
)

SITES = 100_000
CHUNK = 1 << 20
PARTS = 4
QUERIES = 64


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """One described v5e chip, with the persistent compilation cache off:
    a program compiled for a described device is written to the cache but
    can never be read back without the chip."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def test_segment_hist_plain(one_chip):
    rec = jax.ShapeDtypeStruct((CHUNK,), jnp.int32, sharding=one_chip)
    _compile(functools.partial(segment_hist, num_sites=SITES,
                               num_weeks=WEEKS_PER_YEAR, interpret=False),
             rec, rec, rec, rec)


def test_segment_hist_packed(one_chip):
    words = jax.ShapeDtypeStruct((CHUNK,), jnp.uint32, sharding=one_chip)
    my = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    _compile(functools.partial(segment_hist_packed_words,
                               num_sites_local=SITES // PARTS,
                               num_partitions=PARTS,
                               num_weeks=WEEKS_PER_YEAR, interpret=False),
             words, my)


def test_count_scatter(one_chip):
    words = jax.ShapeDtypeStruct((CHUNK,), jnp.uint32, sharding=one_chip)
    dest = jax.ShapeDtypeStruct((CHUNK,), jnp.int32, sharding=one_chip)
    compiled = _compile(functools.partial(
        count_scatter, num_partitions=PARTS, impl="pallas", interpret=False),
        words, dest)
    # two kernels: the per-tile count and the scatter
    assert compiled.as_text().count("tpu_custom_call") >= 2


def test_windowed_ratio_plain(one_chip):
    hist = jax.ShapeDtypeStruct((SITES, WEEKS_PER_YEAR, 2), jnp.int32,
                                sharding=one_chip)
    _compile(functools.partial(windowed_ratio, interpret=False), hist)


def test_windowed_ratio_masked(one_chip):
    hist = jax.ShapeDtypeStruct((SITES, WEEKS_PER_YEAR, 2), jnp.int32,
                                sharding=one_chip)
    masks = jax.ShapeDtypeStruct((QUERIES, WEEKS_PER_YEAR), jnp.bool_,
                                 sharding=one_chip)
    _compile(functools.partial(masked_window_ratio, interpret=False),
             hist, masks, masks)


def test_powerlaw_sample(one_chip):
    u = jax.ShapeDtypeStruct((CHUNK,), jnp.float32, sharding=one_chip)
    cdf = jax.ShapeDtypeStruct((SITES,), jnp.float32, sharding=one_chip)
    _compile(functools.partial(powerlaw_sample, interpret=False), u, cdf)
