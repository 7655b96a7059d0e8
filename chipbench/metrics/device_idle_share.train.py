"""``device_idle_share.train``: the share of the traced window in which no
operation ran on a chip, averaged over the cell's chips, in percent.

The window runs from the first to the last of the benchmark's host spans
around the trainer's calls; busy time is the union of the device's
operation intervals inside it (``trace_reduce.busy_s``).
"""
import statistics

import trace_reduce


def read(ctx):
    red = ctx["trace"]
    busy = trace_reduce.busy_s(red)
    if not busy or not red["window"]:
        return None
    win = trace_reduce.window_s(red)
    return 100.0 * (1.0 - statistics.fmean(busy.values()) / win)
