"""Router programs: device microseconds per execution of the update
program (the program a learner tick launches most), from the trace."""

from perfbench import trace


def read(ctx):
    return trace.per_call_us(ctx.trace, "learn_tick")
