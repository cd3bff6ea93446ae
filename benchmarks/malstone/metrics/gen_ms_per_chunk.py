"""gen_ms_per_chunk: data generation (``repro.malgen.generate_chunk``).

Milliseconds the program's generator takes for one chunk of the cell, by the
host clock around ``block_until_ready``: eight chunks dispatched back to back
per sample, the median of five samples. Nothing to read where the cell's
records are not generated as the scan runs.
"""


def read(ctx):
    import harness
    import jax
    import jax.numpy as jnp

    from repro.malgen import SeedInfo, generate_chunk

    seed = ctx.source.program_input
    if not isinstance(seed, SeedInfo):
        return None
    cfg, c = ctx.source.cfg, ctx.source.chunk_records
    gen = jax.jit(lambda s, i: generate_chunk(s, cfg, i, c))
    return 1e3 * harness.seconds_per_call(
        lambda i: gen(seed, jnp.int32(i)), calls=8)
