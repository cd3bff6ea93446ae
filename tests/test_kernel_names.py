"""The counting sort's Pallas kernels carry stable names into the compiled
MapReduce job: ``count_tiles`` and ``scatter_tiles``, under the exchange's
``malstone.exchange`` scope, so that a profile of the job tells them apart
from every other custom call. The names change nothing the kernels compute.

The streaming MapReduce job (``repro.core.run``) is compiled for a described
``v5e:2x2`` mesh: nothing runs. Off the TPU the program orders the words
with the jnp oracle (``resolve_interpret``), so the test steers the dispatch
to the kernels as a TPU backend would. The topology is described inside a
module-scoped fixture, as ``tests/test_tpu_compile.py`` explains.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

import repro.kernels.count_scatter.ops as count_scatter_ops
from repro.common.types import EventLog, ExchangePlan
from repro.core import run
from repro.kernels.count_scatter import count_scatter
from repro.kernels.count_scatter.ref import count_scatter_ref
from repro.launch.mesh import make_mesh

SITES = 1024
CHUNK = 4096            # records per chunk and chip
CHUNKS = 2              # chunks per chip
KERNELS = {"count_tiles", "scatter_tiles"}


@pytest.fixture(scope="module")
def mesh():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "no compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return make_mesh((4,), ("data",), devices=topo.devices)


@pytest.fixture
def kernels_dispatched(monkeypatch):
    """``impl="auto"`` resolves to the Pallas kernels, as on a TPU, with the
    persistent compile cache off (a program compiled for a described device
    can never be read back) and no trace cached across the switch."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(count_scatter_ops, "resolve_interpret",
                        lambda interpret=None: bool(interpret))
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    jax.clear_caches()
    yield
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def compiled_mapreduce_job(mesh) -> str:
    """The HLO text of the streaming MapReduce job over a resident log of
    ``CHUNKS`` chunks per chip."""
    sharding = NamedSharding(mesh, P("data"))
    n = mesh.devices.size * CHUNKS * CHUNK
    log = EventLog(*(jax.ShapeDtypeStruct((n,), dtype, sharding=sharding)
                     for dtype in (jnp.int32,) * 4 + (jnp.uint32,) * 2))

    def malstone_job(x):
        return run(x, SITES, mesh=mesh, engine="streaming",
                   backend="mapreduce", statistic="B", chunk_records=CHUNK,
                   plan=ExchangePlan(), return_shuffle_stats=True)

    return jax.jit(malstone_job).lower(log).compile().as_text()


def tpu_custom_calls(hlo_text: str) -> list:
    """The op path of every Mosaic custom call of an HLO module."""
    return [m.group(1) for line in hlo_text.splitlines()
            if 'custom_call_target="tpu_custom_call"' in line
            for m in [re.search(r'op_name="([^"]*)"', line)] if m]


def test_job_names_both_kernels_under_the_exchange(mesh, kernels_dispatched):
    paths = tpu_custom_calls(compiled_mapreduce_job(mesh))
    names = set()
    for path in paths:
        parts = path.split("/")
        assert parts[-1] == "pallas_call", path
        names.add(parts[-2])
        scopes = [p for p in parts if p.startswith("malstone.")]
        assert scopes and scopes[-1] == "malstone.exchange", path
    assert names == KERNELS


def _case(seed, n, p):
    kw, kd = jax.random.split(jax.random.key(seed))
    words = jax.random.bits(kw, (n,), dtype=jnp.uint32)
    dest = jax.random.randint(kd, (n,), 0, p + 1, dtype=jnp.int32)
    return words, dest


@pytest.mark.parametrize("n,p,tile", [(3000, 4, 1024), (700, 1, 256)])
def test_named_kernels_stay_bit_equal_to_the_oracle(n, p, tile):
    words, dest = _case(n + p, n, p)
    got = count_scatter(words, dest, p, impl="pallas", record_tile=tile,
                        interpret=True)
    want = count_scatter_ref(words, dest, p)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
