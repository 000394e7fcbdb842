"""Staging: mean megabytes (1e6 B) a traced grid call moves over the
host-device link, the ``h2d_bytes`` and ``d2h_bytes`` counters of its
``sweep.*`` phase spans, counted by the program from shapes."""

from perfbench import program_spans


def read(ctx):
    return program_spans.transfer_mb(ctx)
