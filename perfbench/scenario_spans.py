"""The program's own spans in a traced timeline run, grouped by call.

``sweep.run_scenario_grid`` records a span around each whole call and,
inside it, the same phase spans as ``sweep.run_grid`` (``sweep.streams``,
``sweep.states``, ``sweep.place``, ``sweep.launch``, ``sweep.wait``,
``sweep.readback``), with the same byte counters as arguments.
``program_spans`` reads them from the trace and groups the phases by
``sweep.run_grid``; this module groups them by
``sweep.run_scenario_grid``.

Every reader returns None where the trace holds no
``sweep.run_scenario_grid`` span: a program that records none, or a
trace recorded before it did.
"""
from __future__ import annotations

from typing import List, Optional

import numpy as np

from perfbench import program_spans

CALL = "sweep.run_scenario_grid"


def calls(ctx) -> Optional[List[program_spans.Call]]:
    """The traced scenario-grid calls, each with the phase spans that
    start inside it on its thread."""
    if not any(s[1] == CALL for s in ctx.trace.spans):
        return None
    sp = program_spans.spans(ctx.cell.trace_dir)
    return [program_spans.Call(c, tuple(
        p for p in sp if p.name != CALL and p.thread == c.thread
        and c.start <= p.start <= c.end)) for c in sp if c.name == CALL]


def phase_ms(ctx, name: str) -> Optional[float]:
    """Mean milliseconds a call spends in its ``name`` spans."""
    cs = calls(ctx)
    if not cs:
        return None
    return float(np.mean([sum(p.end - p.start for p in c.phases
                              if p.name == name) for c in cs])) / 1e6


def transfer_mb(ctx) -> Optional[float]:
    """Mean megabytes (1e6 B) a call moves over the host-device link:
    the ``h2d_bytes`` and ``d2h_bytes`` of its phase spans."""
    cs = calls(ctx)
    if not cs:
        return None
    return float(np.mean([sum(int(p.args.get(k, 0)) for p in c.phases
                              for k in ("h2d_bytes", "d2h_bytes"))
                          for c in cs])) / 1e6
