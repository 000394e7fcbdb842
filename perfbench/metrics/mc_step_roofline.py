"""Fabric: the least time one stream step of the timeline grid could
take on a chip (``work.grid_call_work`` over the elements on that chip
and every arm of the portfolio, newcomer included, divided by the
steps), as a share of ``mc_step_device_us``."""

from perfbench import trace, work


def read(ctx):
    us = trace.grid_step_us(ctx.trace, ctx.layer)
    if not us:
        return None
    cfg, L = ctx.cell.config, ctx.layer
    flops, nbytes = work.grid_call_work(
        L["elements_per_chip"], L["steps_per_call"], len(cfg["arms"]),
        cfg["d"])
    least, _ = work.least_s(flops / L["steps_per_call"],
                            nbytes / L["steps_per_call"], ctx.peaks)
    return 100.0 * least / (us * 1e-6)
