"""Staging: mean milliseconds a traced timeline call spends in its
``sweep.states`` span: the stacked initial states (warm priors, the
newcomer's slot inactive), condition edits and payload stacks."""

from perfbench import scenario_spans


def read(ctx):
    return scenario_spans.phase_ms(ctx, "sweep.states")
