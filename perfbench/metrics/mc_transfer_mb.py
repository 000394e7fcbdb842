"""Staging: mean megabytes (1e6 B) a traced timeline call moves over the
host-device link, the ``h2d_bytes`` and ``d2h_bytes`` counters of the
phase spans of its ``sweep.run_scenario_grid`` span, counted by the
program from shapes."""

from perfbench import scenario_spans


def read(ctx):
    return scenario_spans.transfer_mb(ctx)
