"""Admission (``MicroBatcher``): rows per routed block, mean over the
blocks routed in the window, counted from each ``RouteResult``."""


def read(ctx):
    return ctx.layer.get("block_rows_mean")
