"""Selection plane: mean length of the benchmark's span around each
``RouterGateway.route_block`` call in the traced stretch (staging, the
select program, the readbacks, the feedback-store write, telemetry)."""

from perfbench import trace


def read(ctx):
    return trace.span_mean_ms(ctx.trace, "route_block")
