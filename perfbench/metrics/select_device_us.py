"""Router programs: device microseconds per execution of the select
program (the program ``route_block`` launches), from the trace."""

from perfbench import trace


def read(ctx):
    return trace.per_call_us(ctx.trace, "route_block")
