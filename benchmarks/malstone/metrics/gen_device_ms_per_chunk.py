"""gen_device_ms_per_chunk: data generation (``repro.malgen.generate_chunk``),
timed inside the job.

Device milliseconds per chunk and chip that the traced jobs spent in ops of
the program's ``malstone.generate`` scope, from the profiler trace: the
trace reduction's longest ops (``top_ops``, seconds per chip) that belong
to that scope in the compiled job's HLO (``scopes.py``), over the chunks
each chip folded in the traced jobs. Nothing to read where no such op is
among them: a cell whose records are not generated as the scan runs, or a
program without the scope.
"""


def read(ctx):
    import harness
    import scopes

    s = ctx.summary
    if s is None or not ctx.traced_jobs:
        return None
    job = harness.compile_job(ctx.cell, ctx.mesh, ctx.source, ctx.plan)
    owner = scopes.instruction_scopes(job.as_text())
    seconds = sum(t for label, t in s.top_ops
                  if owner.get(label.split(" ")[0]) == "malstone.generate")
    if seconds <= 0:
        return None
    return 1e3 * seconds / (ctx.traced_jobs * ctx.source.chunks_per_chip)
