"""Distributed substrate: pipeline parallelism (subprocess, 4 devices),
gradient compression, sharding-rule engine."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.mesh import make_mesh
from repro.optim.compression import (
    compress_int8,
    decompress_int8,
    ef_compress_update,
    tree_ef_compress,
)
from tests.test_backends import _run_md_script


@pytest.mark.slow
def test_pipeline_parallel_on_4_devices():
    out = _run_md_script("pipeline_check.py")
    assert "ALL_OK" in out


class TestCompression:
    def test_roundtrip_error_bounded(self):
        x = jax.random.normal(jax.random.key(0), (512,)) * 3
        q, s = compress_int8(x)
        err = np.abs(np.asarray(decompress_int8(q, s) - x))
        assert err.max() <= float(s) / 2 + 1e-6

    def test_error_feedback_accumulates_to_zero_bias(self):
        """EF: the *sum* of compressed estimates tracks the sum of grads."""
        key = jax.random.key(1)
        err = jnp.zeros((256,))
        total_est = jnp.zeros((256,))
        total_g = jnp.zeros((256,))
        for i in range(50):
            g = jax.random.normal(jax.random.fold_in(key, i), (256,))
            est, err = ef_compress_update(g, err)
            total_est += est
            total_g += g
        # residual bias is exactly the leftover error buffer
        np.testing.assert_allclose(np.asarray(total_g - total_est),
                                   np.asarray(err), rtol=1e-4, atol=1e-4)

    def test_tree_compress_structure(self):
        grads = {"a": jnp.ones((8, 8)), "b": jnp.full((4,), 2.0)}
        errors = jax.tree.map(jnp.zeros_like, grads)
        est, new_err = tree_ef_compress(grads, errors)
        assert set(est) == {"a", "b"}
        np.testing.assert_allclose(np.asarray(est["a"]), 1.0, rtol=1e-2)


class TestShardingRules:
    def test_divisibility_fallback(self):
        from jax.sharding import PartitionSpec as P
        from repro.models.sharding import spec_for
        mesh = make_mesh((1,), ("data",))
        # dim 7 not divisible by data=1? divisible; use rules with data
        spec = spec_for((8, 7), ("embed", None), {"embed": "data"}, mesh)
        assert spec == P("data")

    def test_missing_axis_filtered_not_dropped(self):
        """The (pod, data) binding must keep data on a pod-less mesh."""
        from jax.sharding import PartitionSpec as P
        from repro.models.sharding import spec_for
        mesh = make_mesh((1,), ("data",))
        spec = spec_for((4, 4), ("batch", None),
                        {"batch": ("pod", "data")}, mesh)
        assert spec == P("data")

    def test_no_axis_reuse_within_tensor(self):
        from jax.sharding import PartitionSpec as P
        from repro.models.sharding import spec_for
        mesh = make_mesh((1,), ("data",))
        spec = spec_for((4, 4), ("a", "b"),
                        {"a": "data", "b": "data"}, mesh)
        assert spec == P("data")  # second binding blocked (axis used)
