"""Staging: mean milliseconds a traced grid call spends in its
``sweep.states`` span, building the stacked initial states (warm
priors, condition edits, tenant ids)."""

from perfbench import program_spans


def read(ctx):
    return program_spans.phase_ms(ctx, "sweep.states")
