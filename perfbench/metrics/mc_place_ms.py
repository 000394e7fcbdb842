"""Staging: mean milliseconds a traced timeline call spends in its
``sweep.place`` span: dispatching the per-element streams, payloads and
event steps under the grid sharding and the copies of the states for
donation. ``device_put`` returns before its copy ends, so this is
dispatch time only; the host-to-device copy itself lands in the
``sweep.wait`` span that follows."""

from perfbench import scenario_spans


def read(ctx):
    return scenario_spans.phase_ms(ctx, "sweep.place")
