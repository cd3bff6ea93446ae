"""hist_roofline: the site x week reducer of the default ``histogram_impl``.

Per cent of the HBM roofline the reducer reaches in the traced jobs: the
least bytes for the records reduced (``peaks.reducer_min_bytes``: their input
read once and one read-modify-write of an int32 bin each) at the chip's peak
bandwidth, over the device time of the program's scatter ops, which are that
reducer. Nothing to read where the program runs no scatter.
"""


def read(ctx):
    import peaks

    s = ctx.summary
    if s is None or not ctx.traced_jobs:
        return None
    seconds = sum(s.scatter_s.values())
    if seconds <= 0:
        return None
    records = ctx.traced_jobs * ctx.source.records_per_job
    return peaks.roofline_share(
        peaks.reducer_min_bytes(records, ctx.middleware), seconds,
        peaks.peak(ctx.device_kind)["hbm_bytes_per_s"])
