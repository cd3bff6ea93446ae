"""sort_roofline: the MapReduce exchange's counting sort
(``repro.kernels.count_scatter``: the Pallas kernels ``count_tiles`` and
``scatter_tiles``).

Per cent of the HBM roofline the counting sort reaches in the traced jobs:
the least bytes of a stable partition of the words it orders
(``min_bytes``) at the chip's peak bandwidth, over the device time of the
ops that the compiled job's HLO shows to be those two kernels' custom
calls. Like ``gen_device_ms_per_chunk``, it reads that time from the trace
reduction's longest ops (``top_ops``, seconds per chip), so a kernel outside
the ten longest ops goes uncounted. Nothing to read where neither kernel
runs (off the TPU the program orders the words with its jnp oracle) or
where there is no trace.
"""

KERNELS = frozenset({"count_tiles", "scatter_tiles"})
# per word slot: the word and its destination read once, the word written
SLOT_BYTES = 4 + 4 + 4


def min_bytes(slots: int) -> int:
    """The least HBM traffic of ordering ``slots`` packed words by their
    destinations. It counts the chunk's word slots, not the padded tiles
    the kernels stream, so padding shows as time above this bound."""
    return slots * SLOT_BYTES


def kernel_instructions(hlo_text: str) -> set:
    """Names of the compiled job's custom calls that the two kernels made:
    a Pallas call's ``name`` is a component of its op path."""
    import trace_reduce

    return {name for name, info in trace_reduce.parse_hlo(hlo_text).items()
            if info.opcode == "custom-call"
            and KERNELS & set(info.op_name.split("/"))}


def read(ctx):
    import harness
    import peaks

    s = ctx.summary
    if s is None or not ctx.traced_jobs:
        return None
    job = harness.compile_job(ctx.cell, ctx.mesh, ctx.source, ctx.plan)
    kernels = kernel_instructions(job.as_text())
    seconds = sum(t for label, t in s.top_ops
                  if label.split(" ")[0] in kernels)
    if seconds <= 0:
        return None
    slots = (ctx.traced_jobs * ctx.source.chunks_per_chip
             * ctx.source.chunk_records)
    return peaks.roofline_share(min_bytes(slots), seconds,
                                peaks.peak(ctx.device_kind)["hbm_bytes_per_s"])
