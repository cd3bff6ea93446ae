"""Record ``data/cpu_scoped_trace.*``: a profiler trace, on the CPU, of two
jobs of a small jitted scan whose steps run under the program's layer scopes
(``malstone.read`` / ``generate`` / ``combine`` / ``finalize``; generation is
an inverse-CDF ``searchsorted``, as in MalGen), each job wrapped in the
benchmark's ``bench.job`` / ``bench.dispatch`` / ``bench.wait`` spans, and
the program's compiled HLO.

    JAX_PLATFORMS=cpu python benchmarks/malstone/tests/record_trace.py
"""

import pathlib
import shutil
import tempfile

import jax
import jax.numpy as jnp

DATA = pathlib.Path(__file__).resolve().parent / "data"
NAME = "cpu_scoped_trace"
BINS = 4096


def job(log):
    """A histogram over a resident log, chunk by chunk, then a running
    ratio: one scope per layer, as the MalStone job has them."""
    cdf = jnp.linspace(0.0, 1.0, BINS)

    def step(hist, chunk):
        with jax.named_scope("malstone.generate"):   # inverse-CDF sampling
            keys = jnp.searchsorted(cdf, (chunk % 65521) / 65521.0)
        with jax.named_scope("malstone.combine"):
            hist = hist + jnp.zeros(BINS, jnp.int32).at[keys].add(
                1, mode="drop")
        return hist, None

    with jax.named_scope("malstone.read"):
        chunks = log.reshape(8, -1)
        hist, _ = jax.lax.scan(step, jnp.zeros(BINS, jnp.int32), chunks)
    with jax.named_scope("malstone.finalize"):
        return jnp.cumsum(hist) / jnp.maximum(jnp.sum(hist), 1)


def record(out_dir=DATA):
    # file names in the HLO's metadata without their directories
    jax.config.update("jax_hlo_source_file_canonicalization_regex", ".*/")
    log = jnp.arange(8 * 65536, dtype=jnp.int32)
    compiled = jax.jit(job).lower(log).compile()
    jax.block_until_ready(compiled(log))
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp)
        for _ in range(2):
            with jax.profiler.TraceAnnotation("bench.job"):
                with jax.profiler.TraceAnnotation("bench.dispatch"):
                    out = compiled(log)
                with jax.profiler.TraceAnnotation("bench.wait"):
                    jax.block_until_ready(out)
        jax.profiler.stop_trace()
        trace, = pathlib.Path(tmp).rglob("*.xplane.pb")
        shutil.copy(trace, out_dir / f"{NAME}.xplane.pb")
    (out_dir / f"{NAME}.hlo.txt").write_text(compiled.as_text())


if __name__ == "__main__":
    record()
