#!/usr/bin/env python3
"""Readings for a cell's limits: the program's and the control's.

    python3 perfbench/calibrate.py --workload paper3_steady \\
        --seeds 101,102,103 --control-seeds 101,102,103 --seconds 20

For each seed, one process on the chip runs the cell's window at its
own load and size, as ``run.py`` does, and prints the numbers its
comparison reads (``"who": "program"``). For each control seed it also
prints the same numbers with the control, the reference computed one
precision step below the configuration (``reference/lowp.py``), in the
program's place (``"who": "control"``). A limit lies between the
largest program reading over a dozen seeds or more and the smallest
control reading (PERF.md gives both for every limit).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
        HERE, ".cache", "jax")
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    if jax.devices()[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 1
    from perfbench import registry
    from perfbench.cell import Cell

    bench = registry.benchmark()
    wl = registry.workload(bench, args.workload)
    cell = Cell(workload=wl, config=registry.config(bench, wl["config"]),
                traffic=registry.traffic(wl["traffic"]), seed=0,
                seconds=args.seconds, trace=False, t_process=0.0,
                trace_dir="")
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    for row in readings(cell, [int(s) for s in args.seeds.split(",")],
                        controls):
        print(json.dumps(row), flush=True)
    return 0


def readings(cell, seeds, controls):
    """Yield the program's (and, for control seeds, the control's)
    readings of the cell, one dict per seed and side."""
    from perfbench.reference.lowp import Bf16x3

    entry = cell.traffic["entry"]
    if entry == "gateway":
        from perfbench.entries import gateway as g

        warm = True
        for seed in seeds:
            cell.seed = seed
            cfg, state0, sched, train, test = g.prepare(cell, warm=warm)
            warm = False
            log, _ = g.play(cell, cfg, state0, sched, test)
            B = [len(b[0]) for b in log.blocks]
            ev = g.evidence(cell, sched, log)
            t0 = time.perf_counter()
            yield dict(seed=seed, who="program", block_rows=[min(B), max(B)],
                       **g.readings(cell, sched, ev, train, test))
            ref_s = time.perf_counter() - t0
            if seed in controls:
                yield dict(seed=seed, who="control", reference_s=ref_s,
                           **g.readings(cell, sched, ev, train, test,
                                        control=Bf16x3()))
    elif entry == "fabric":
        from perfbench.entries import fabric as f

        for seed in seeds:
            cell.seed = seed
            results, train, test, budgets, _ = f.window(cell)
            yield dict(seed=seed, who="program",
                       **f.readings(cell, results, train, test, budgets))
            if seed in controls:
                yield dict(seed=seed, who="control",
                           **f.readings(cell, results, train, test, budgets,
                                        control=Bf16x3()))
    else:
        raise ValueError(f"no calibration for entry {entry!r}")


if __name__ == "__main__":
    sys.exit(main())
