#!/usr/bin/env python3
"""Run one benchmark cell once.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

Loads the cell named in BENCHMARK.json, warms up every shape its
traffic uses, measures for ``--seconds``, compares what the timed path
produced with the plain reference, and prints one JSON line last on
standard output: ``correct``, ``attempted``, ``failed``, ``metrics``
(the cell's end-to-end metrics with ``--trace 0``, its per-layer metrics
with ``--trace 1``), ``device`` and, last, ``checks``: each number the
comparison read beside its limit. The same numbers are the last lines of
standard error.

It runs only on a TPU with as many chips as the cell asks for, and
exits non-zero, printing no result, anywhere else.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE_DIR = os.path.join(HERE, ".cache", "jax")
TRACE_DIR = os.path.join(HERE, ".cache", "trace")


def _fail(msg: str) -> int:
    print(f"perfbench: {msg}", file=sys.stderr, flush=True)
    return 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    try:
        from perfbench import registry
        bench = registry.benchmark()
        wl = registry.workload(bench, args.workload)
        config = registry.config(bench, wl["config"])
        traffic = registry.traffic(wl["traffic"])
        # The compile cache stays inside the checkout, at a fixed path;
        # the TPU runtime's logs would go to a fixed path under /tmp.
        os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        from repro.compile_cache import enable_compile_cache
    except (OSError, KeyError, ImportError, ValueError) as e:
        return _fail(f"cannot load the cell: {e!r}")
    enable_compile_cache()
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        return _fail(f"needs a TPU; JAX found {len(devices)} "
                     f"{dev.platform!r} device(s) ({dev.device_kind})")
    if len(devices) < int(wl["chips"]):
        return _fail(f"cell {wl['name']} needs {wl['chips']} chips; JAX "
                     f"found {len(devices)} {dev.device_kind}")
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}, "
          f"jax {jax.__version__}", flush=True)

    from perfbench.cell import Cell

    cell = Cell(workload=wl, config=config, traffic=traffic, seed=args.seed,
                seconds=args.seconds, trace=bool(args.trace),
                t_process=T_PROCESS,
                trace_dir=os.path.join(TRACE_DIR, wl["name"]))
    try:
        line, checks = execute(bench, cell, devices)
    except Exception:   # the run failed: no result line
        traceback.print_exc()
        return _fail(f"cell {wl['name']} did not run to its end")
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


def _number(x: float) -> float:
    """A JSON number: a reading that never came is the largest double."""
    x = float(x)
    return x if math.isfinite(x) else sys.float_info.max


def execute(bench, cell, devices):
    """Run the cell on ``devices`` and build the result line; returns
    (line, checks). Needs no TPU: the look for one is ``main``'s."""
    from perfbench import registry, work

    wl = cell.workload
    out = registry.entry(cell.traffic["entry"]).run(cell)
    print(f"memory_peak_bytes: {out.memory_peak_bytes}", flush=True)
    print(f"compiles_in_window: {out.layer['compiles_in_window']}",
          flush=True)
    if "block_rows_range" in out.layer:
        print(f"block_rows_range: {out.layer['block_rows_range']}",
              flush=True)
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": out.memory_peak_bytes}
    extra = {}
    if cell.trace:
        from perfbench import trace as trace_lib

        tr = out.trace.load()
        device["busy_s"] = trace_lib.busy_s(tr)
        device["window_s"] = tr.window_s
        ctx = work.Context(cell=cell, trace=tr, layer=out.layer,
                           peaks=work.peaks(dev.device_kind))
        metrics = {}
        for m in registry.per_layer_of(bench, wl["name"]):
            value = registry.metric(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        extra["breakdown"] = {
            "device_ops": trace_lib.top_ops(tr),
            "idle_gaps": trace_lib.idle_gaps(tr, trace_lib.HOST_SPANS)}
    else:
        values = dict(out.end_to_end, setup_s=out.setup_s)
        metrics = {m["name"]: {"value": _number(values[m["name"]]),
                               "unit": m["unit"]}
                   for m in registry.end_to_end_of(bench, wl["name"])}
    checks = {c.name: {"value": _number(c.value), "limit": c.limit}
              for c in out.checks}
    line = {"correct": all(c.ok for c in out.checks),
            "attempted": out.attempted, "failed": out.failed,
            "metrics": metrics, "device": device}
    line.update(extra)
    line["checks"] = checks
    return line, checks


if __name__ == "__main__":
    sys.exit(main())
