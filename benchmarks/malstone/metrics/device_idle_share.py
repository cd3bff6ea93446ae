"""device_idle_share: the device.

Per cent of the traced window in which no operation ran on the chip, from
the profiler trace (1 - union of the op intervals / window), averaged over
the cell's chips.
"""


def read(ctx):
    s = ctx.summary
    if s is None or s.window_s <= 0:
        return None
    busy = sum(s.busy_s.values()) / len(s.busy_s)
    return 100.0 * (1.0 - busy / s.window_s)
