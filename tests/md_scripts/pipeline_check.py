"""Pipeline-parallelism functional check (4 host devices)."""

from repro.common.env import force_host_devices

force_host_devices(4)

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed import PipelineConfig, pipeline_apply
from repro.launch.mesh import make_mesh


def main():
    mesh = make_mesh((4,), ("pipe",))
    s, m, mb, d = 4, 4, 2, 8
    w = jax.random.normal(jax.random.key(0), (s, d, d)) * 0.3

    def fn(params, x, stage):
        return jnp.tanh(x @ params)

    cfg = PipelineConfig(num_stages=s, num_microbatches=m, axis_name="pipe")
    x = jax.random.normal(jax.random.key(1), (m * mb, d))
    got = pipeline_apply(fn, w, x, cfg, mesh)
    want = x
    for i in range(s):
        want = jnp.tanh(want @ w[i])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    print("OK pipeline 4-stage x 4-microbatch")

    # different microbatch count
    cfg2 = PipelineConfig(num_stages=s, num_microbatches=8, axis_name="pipe")
    x2 = jax.random.normal(jax.random.key(2), (8 * mb, d))
    got2 = pipeline_apply(fn, w, x2, cfg2, mesh)
    want2 = x2
    for i in range(s):
        want2 = jnp.tanh(want2 @ w[i])
    np.testing.assert_allclose(np.asarray(got2), np.asarray(want2),
                               rtol=1e-5, atol=1e-5)
    print("OK pipeline 4-stage x 8-microbatch")
    print("ALL_OK")


if __name__ == "__main__":
    main()
