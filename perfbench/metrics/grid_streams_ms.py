"""Staging: mean milliseconds a traced grid call spends in its
``sweep.streams`` span, building the seeds' stream tensors and sending
them to the device."""

from perfbench import program_spans


def read(ctx):
    return program_spans.phase_ms(ctx, "sweep.streams")
