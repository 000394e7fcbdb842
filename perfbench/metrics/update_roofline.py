"""Router programs: the least time folding one mean-sized feedback
block could take (``work.update_work``), as a share of the update
program's device time per call."""

from perfbench import trace, work


def read(ctx):
    us = trace.per_call_us(ctx.trace, "learn_tick")
    B = ctx.layer.get("block_rows_mean")
    if not us or not B:
        return None
    cfg = ctx.cell.config
    least, _ = work.least_s(*work.update_work(
        B, len(cfg["arms"]), cfg["d"]), ctx.peaks)
    return 100.0 * least / (us * 1e-6)
