"""collective_share: the exchange (``repro.core.backends``).

Per cent of the traced window in which a collective (all-to-all,
all-reduce, all-gather, reduce-scatter, collective-permute) ran on the chip
that spent the most time in them, from the profiler trace. Nothing to read
where no collective ran.
"""


def read(ctx):
    s = ctx.summary
    if s is None or not s.collective_s:
        return None
    busiest = max(s.collective_s.values())
    return 100.0 * busiest / s.window_s if busiest > 0 else None
