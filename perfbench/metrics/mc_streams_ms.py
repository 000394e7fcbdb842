"""Staging: mean milliseconds a traced timeline call spends in its
``sweep.streams`` span: the retimed specs, their validation and the
per-element stream stacks built on the host."""

from perfbench import scenario_spans


def read(ctx):
    return scenario_spans.phase_ms(ctx, "sweep.streams")
