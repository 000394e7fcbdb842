"""The profiler trace of a ``--trace 1`` run, and its reduction.

``Tracer`` records a short stretch of the window with JAX's profiler
and puts the benchmark's own spans (``TraceAnnotation``) around the
calls into each layer. ``load`` reads the ``.xplane.pb`` it wrote into
plain tuples; everything after that is arithmetic on those tuples, so
the reduction is checked on a small recorded trace
(``perfbench/tests/data``) without a chip.

Device time is the union of the intervals in which an operation ran on
a device. An operation belongs to a program by its ``program_id`` (on a
TPU, by the ``XLA Modules`` execution whose interval holds it); a
program is the select, update or grid program by the host span that
launched it (the ``run_id`` of its execution is launched inside that
span on the host), or, where the trace carries no launch events, by the
span its first operation starts in.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import shutil
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

# Seconds of the window that a traced run records.
TRACE_SECONDS = 2.0
# The benchmark's spans around the calls into each layer.
HOST_SPANS = ("route_block", "learn_tick", "run_grid", "grid_prepare")


class Tracer:
    """Starts and stops the profiler; ``span`` marks a layer call."""

    def __init__(self, directory: str, seconds: float = TRACE_SECONDS):
        self.directory = directory
        self.seconds = seconds
        self.window_s = 0.0

    def span(self, name: str):
        import jax

        return jax.profiler.TraceAnnotation(name)

    def start(self) -> None:
        import jax

        shutil.rmtree(self.directory, ignore_errors=True)
        # The Python tracer would record every interpreter call: it
        # slows the host the trace measures, and no reader uses it.
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.directory, profiler_options=options)
        self._t0 = time.perf_counter()

    def stop(self) -> None:
        import jax

        self.window_s = time.perf_counter() - self._t0
        jax.profiler.stop_trace()

    def load(self) -> "Trace":
        paths = glob.glob(os.path.join(self.directory, "plugins", "profile",
                                       "*", "*.xplane.pb"))
        if not paths:
            raise FileNotFoundError(f"no trace under {self.directory}")
        return load(max(paths, key=os.path.getmtime), self.window_s)


@dataclasses.dataclass
class Trace:
    """A trace reduced to tuples (times in ns on one clock).

    ops:      (device, name, start, dur, program_id, run_id)  the ids
              are -1 and None for an operation outside any execution
    spans:    (thread, name, start, dur)   host spans of every thread
    launches: (thread, run_id, start)      host events that start a run
    """

    ops: List[Tuple[int, str, int, int, int, Optional[int]]]
    spans: List[Tuple[str, str, int, int]]
    launches: List[Tuple[str, int, int]]
    window_s: float

    def devices(self) -> List[int]:
        return sorted({o[0] for o in self.ops})


def _stats(ev) -> Dict[str, object]:
    return {k: v for k, v in ev.stats}


def op_name(hlo: str) -> str:
    """An operation's short name: ``%fusion.7 = f32[..] fusion(..)``
    becomes ``fusion.7``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def _device_ops(dev: int, plane, programs: Dict[str, int]):
    """A TPU plane's operations, each tagged with the program execution
    (``XLA Modules`` event) whose interval holds its start: the module
    name, made an integer by ``programs``, and the execution's run_id."""
    lines = {ln.name: list(ln.events) for ln in plane.lines}
    mods = sorted((int(m.start_ns), int(m.start_ns + m.duration_ns),
                   programs.setdefault(m.name, len(programs)),
                   int(_stats(m).get("run_id", -1)))
                  for m in lines.get("XLA Modules", []))
    starts = [m[0] for m in mods]
    out = []
    for ev in lines.get("XLA Ops", []):
        s = int(ev.start_ns)
        i = int(np.searchsorted(starts, s, side="right")) - 1
        prog, run = (mods[i][2], mods[i][3]) if (
            i >= 0 and s <= mods[i][1]) else (-1, None)
        out.append((dev, op_name(ev.name), s, int(ev.duration_ns), prog,
                    run))
    return out


def load(path: str, window_s: float) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, spans, launches = [], [], []
    programs: Dict[str, int] = {}
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            if plane.name[-1].isdigit():
                ops += _device_ops(int(plane.name.rsplit(":", 1)[-1]),
                                   plane, programs)
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    st = _stats(ev)
                    if "hlo_op" in st:      # a CPU device's operation
                        ops.append((int(st.get("device_ordinal", 0)),
                                    op_name(ev.name), int(ev.start_ns),
                                    int(ev.duration_ns),
                                    int(st.get("program_id", -1)),
                                    int(st.get("run_id", -1))))
                        continue
                    spans.append((line.name, ev.name, int(ev.start_ns),
                                  int(ev.duration_ns)))
                    if "run_id" in st:
                        launches.append((line.name, int(st["run_id"]),
                                         int(ev.start_ns)))
    return Trace(ops=ops, spans=spans, launches=launches, window_s=window_s)


def union_ns(intervals) -> int:
    """Total length of the union of (start, dur) intervals."""
    iv = sorted((s, s + d) for s, d in intervals)
    total, end = 0, None
    cur_s = None
    for s, e in iv:
        if end is None or s > end:
            if end is not None:
                total += end - cur_s
            cur_s, end = s, e
        else:
            end = max(end, e)
    if end is not None:
        total += end - cur_s
    return total


def busy_s(tr: Trace, devices: Optional[List[int]] = None) -> float:
    """Seconds in which an operation ran, averaged over the devices."""
    devs = devices if devices is not None else tr.devices()
    if not devs:
        return 0.0
    return sum(union_ns([(o[2], o[3]) for o in tr.ops if o[0] == d])
               for d in devs) / len(devs) / 1e9


def top_ops(tr: Trace, n: int = 10) -> List[List[object]]:
    """The device operations that took most time (seconds summed over
    devices), largest first."""
    tot: Dict[str, int] = collections.Counter()
    for o in tr.ops:
        tot[o[1]] += o[3]
    return [[k, v / 1e9] for k, v in tot.most_common(n)]


def _busy_intervals(tr: Trace, device: int):
    iv = sorted((o[2], o[2] + o[3]) for o in tr.ops if o[0] == device)
    merged = []
    for s, e in iv:
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def idle_gaps(tr: Trace, names, n: int = 10) -> List[List[object]]:
    """The idle gaps between device operations on the first device,
    summed by what the host was doing: the named span that covers most
    of each gap ("host idle" where none does), largest first."""
    devs = tr.devices()
    if not devs:
        return []
    merged = _busy_intervals(tr, devs[0])
    spans = [(s[1], s[2], s[2] + s[3]) for s in tr.spans if s[1] in names]
    tot: Dict[str, int] = collections.Counter()
    for (_, e0), (s1, _) in zip(merged, merged[1:]):
        best, cover = "host idle", 0
        for name, a, b in spans:
            c = min(b, s1) - max(a, e0)
            if c > cover:
                best, cover = name, c
        tot[best] += s1 - e0
    return [[k, v / 1e9] for k, v in tot.most_common(n)]


def programs_of(tr: Trace, span_name: str) -> Dict[int, int]:
    """Executions (run_ids) per program launched inside ``span_name``
    spans: by the launch events on the spans' threads where the trace
    has them (the CPU's), else by the span in which each execution's
    first operation starts (a TPU's, whose run_id events sit on the
    runtime's own threads)."""
    spans = [(s[0], s[2], s[2] + s[3]) for s in tr.spans
             if s[1] == span_name]
    runs: Dict[int, set] = collections.defaultdict(set)
    prog_of_run = {o[5]: o[4] for o in tr.ops if o[5] is not None}
    threads = {th for th, _, _ in spans}
    launches = [ln for ln in tr.launches if ln[0] in threads]
    if launches:
        for thread, run, t in launches:
            if run in prog_of_run and any(
                    th == thread and a <= t <= b for th, a, b in spans):
                runs[prog_of_run[run]].add(run)
    else:
        first: Dict[int, int] = {}
        for o in tr.ops:
            if o[5] is not None:
                first[o[5]] = min(first.get(o[5], o[2]), o[2])
        for run, t in first.items():
            if any(a <= t <= b for _, a, b in spans):
                runs[prog_of_run[run]].add(run)
    return {p: len(r) for p, r in runs.items()}


def program_device_s(tr: Trace, program: int) -> float:
    """Device seconds (union, summed over devices) of one program."""
    return sum(union_ns([(o[2], o[3]) for o in tr.ops
                         if o[0] == d and o[4] == program])
               for d in tr.devices()) / 1e9


def per_call_us(tr: Trace, span_name: str) -> Optional[float]:
    """Device microseconds per execution of the program launched most
    often inside ``span_name`` spans (None where no such program ran)."""
    progs = programs_of(tr, span_name)
    if not progs:
        return None
    prog = max(progs.items(), key=lambda kv: kv[1])[0]
    runs = {o[5] for o in tr.ops if o[4] == prog}
    n_dev = len({o[0] for o in tr.ops if o[4] == prog})
    return 1e6 * program_device_s(tr, prog) / n_dev / len(runs)


def span_mean_ms(tr: Trace, name: str) -> Optional[float]:
    d = [s[3] for s in tr.spans if s[1] == name]
    return float(np.mean(d)) / 1e6 if d else None


def grid_step_us(tr: Trace, layer: dict) -> Optional[float]:
    """Device microseconds per stream step of the grid program: the
    program with the most device time among those launched inside
    ``run_grid`` spans, its busy time averaged over its chips, divided
    by its executions times the steps of a call."""
    progs = programs_of(tr, "run_grid")
    if not progs:
        return None
    prog = max(progs, key=lambda p: program_device_s(tr, p))
    devs = sorted({o[0] for o in tr.ops if o[4] == prog})
    runs = {o[5] for o in tr.ops if o[4] == prog and o[0] == devs[0]}
    per_device = program_device_s(tr, prog) / len(devs)
    return 1e6 * per_device / (len(runs) * layer["steps_per_call"])
