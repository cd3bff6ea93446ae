"""fold_ms_per_chunk: the engine's fold (``repro.core.streaming.fold_chunk``).

Milliseconds to fold one chunk per chip into the histogram state with the
cell's middleware on the cell's mesh (on MapReduce the exchange included),
by the host clock: eight folds dispatched back to back per sample, the
median of five samples.
"""


def read(ctx):
    import harness
    import jax

    from repro.common.types import EventLog
    from repro.core.streaming import (
        fold_chunk,
        state_init,
        state_partition_spec,
        state_to_global,
        state_to_local,
    )
    from jax.sharding import PartitionSpec as P

    axis, cfg = "data", ctx.cell.config
    backend, parts = ctx.middleware, ctx.mesh.devices.size
    s_pad = -(-cfg["num_sites"] // parts) * parts
    weeks = cfg["num_weeks"]
    spec = state_partition_spec(backend, axis)
    chunk = ctx.source.chunk_per_device()
    chunk_spec = EventLog(*(P(axis) for _ in EventLog._fields[:6]))

    def shard_map(fn, in_specs):
        return jax.jit(jax.shard_map(fn, mesh=ctx.mesh, in_specs=in_specs,
                                     out_specs=spec, check_vma=False))

    init = shard_map(lambda: state_to_global(
        state_init(backend, s_pad, weeks, axis)), ())
    fold = shard_map(lambda st, ch: state_to_global(fold_chunk(
        state_to_local(st), ch, backend=backend, s_pad=s_pad,
        num_weeks=weeks, axis_name=axis, plan=ctx.plan)), (spec, chunk_spec))
    state = [init()]

    def one(_):
        state[0] = fold(state[0], chunk)
        return state[0]

    return 1e3 * harness.seconds_per_call(one, calls=8)
