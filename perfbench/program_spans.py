"""The program's own spans in a traced grid run, with their arguments.

``sweep.run_grid`` records a ``jax.profiler.TraceAnnotation`` span
around each whole call and one around each host phase inside it
(``sweep.streams``, ``sweep.states``, ``sweep.place``, ``sweep.launch``,
``sweep.wait``, ``sweep.readback``); a span's arguments are the
phase's counters (``h2d_bytes``, ``d2h_bytes``). They land in the
run's own profiler session, on the clock of the device operations.
``trace.Trace`` keeps spans without their arguments, so this module
reads the ``sweep.*`` spans again from the ``.xplane.pb`` under the
cell's ``trace_dir``.

Every reader returns None where the trace holds no ``sweep.run_grid``
span: a program that records none, or a trace recorded before it did.
"""
from __future__ import annotations

import dataclasses
import functools
import glob
import os
from typing import Dict, List, Optional, Tuple

import numpy as np

CALL = "sweep.run_grid"
PREFIX = "sweep."


@dataclasses.dataclass(frozen=True)
class Span:
    thread: str
    name: str
    start: int          # ns, on the trace's clock
    end: int
    args: Dict[str, object]


@dataclasses.dataclass(frozen=True)
class Call:
    """One traced ``sweep.run_grid`` call and the phase spans inside it."""

    span: Span
    phases: Tuple[Span, ...]


@functools.lru_cache(maxsize=4)
def _load(path: str, mtime: float) -> Tuple[Span, ...]:
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    s = int(ev.start_ns)
                    out.append(Span(line.name, ev.name, s,
                                    s + int(ev.duration_ns),
                                    {k: v for k, v in ev.stats}))
    return tuple(sorted(out, key=lambda sp: sp.start))


def spans(trace_dir: str) -> Tuple[Span, ...]:
    """The ``sweep.*`` spans of the newest trace under ``trace_dir``
    (the file ``trace.Tracer.load`` reads), in order of start."""
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no trace under {trace_dir}")
    path = max(paths, key=os.path.getmtime)
    return _load(path, os.path.getmtime(path))


def calls(ctx) -> Optional[List[Call]]:
    """The traced grid calls, each with the phase spans that start
    inside it on its thread; None where the reduced trace holds no
    ``sweep.run_grid`` span."""
    if not any(s[1] == CALL for s in ctx.trace.spans):
        return None
    sp = spans(ctx.cell.trace_dir)
    return [Call(c, tuple(p for p in sp if p.name != CALL
                          and p.thread == c.thread
                          and c.start <= p.start <= c.end))
            for c in sp if c.name == CALL]


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
