"""Router programs: the least time the Eq. 2 scoring of a mean-sized
block could take on this chip (``work.select_work`` at B = the mean
rows per block, K = the active arms, d), as a share of the select
program's device time per call. Bandwidth sets the bound at these
sizes."""

from perfbench import trace, work


def read(ctx):
    us = trace.per_call_us(ctx.trace, "route_block")
    B = ctx.layer.get("block_rows_mean")
    if not us or not B:
        return None
    cfg = ctx.cell.config
    least, _ = work.least_s(*work.select_work(
        B, len(cfg["arms"]), cfg["d"]), ctx.peaks)
    return 100.0 * least / (us * 1e-6)
