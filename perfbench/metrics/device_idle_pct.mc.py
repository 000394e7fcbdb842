"""Device: share of the traced stretch of a timeline cell, averaged over
its chips, in which no operation ran on the chip (1 - busy union /
traced window)."""

from perfbench import trace


def read(ctx):
    if not ctx.trace.window_s:
        return None
    return 100.0 * (1.0 - trace.busy_s(ctx.trace) / ctx.trace.window_s)
