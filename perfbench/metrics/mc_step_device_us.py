"""Fabric: device microseconds per stream step of the timeline grid
program (the program that takes most device time inside ``run_grid``
spans), each chip's busy time averaged over the chips, divided by the
steps of the calls traced."""

from perfbench import trace


def read(ctx):
    return trace.grid_step_us(ctx.trace, ctx.layer)
