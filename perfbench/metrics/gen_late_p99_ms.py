"""Load generator: how late the front thread submitted requests against
their schedule, 99th percentile over the window (host clock). A late
generator is a starved client, not a fast server."""


def read(ctx):
    return ctx.layer.get("gen_late_p99_ms")
