"""Staging: mean milliseconds a traced grid call spends in its
``sweep.readback`` span, copying the per-step arms, rewards, costs and
duals back to the host after the device has finished."""

from perfbench import program_spans


def read(ctx):
    return program_spans.phase_ms(ctx, "sweep.readback")
