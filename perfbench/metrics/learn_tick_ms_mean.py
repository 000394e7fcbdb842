"""Learner plane: mean length of the benchmark's span around each
learner tick (``enqueue_feedback`` of the due blocks + ``learn_tick``)
in the traced stretch."""

from perfbench import trace


def read(ctx):
    return trace.span_mean_ms(ctx.trace, "learn_tick")
