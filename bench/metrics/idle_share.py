"""idle_share (%): the share of the traced window in which the device ran
no operation, averaged over the cell's chips.

    1 - (union of the device's operation intervals) / (traced window)
"""


def read(ctx):
    if ctx.trace is None or not ctx.busy_s:
        return None
    return 100.0 * (1.0 - ctx.busy_s / ctx.trace_window_s)
