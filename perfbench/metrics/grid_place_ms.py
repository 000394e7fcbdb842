"""Staging: mean milliseconds a traced grid call spends in its
``sweep.place`` span, tiling the streams over the conditions, placing
every operand under the grid sharding and copying the states for
donation."""

from perfbench import program_spans


def read(ctx):
    return program_spans.phase_ms(ctx, "sweep.place")
