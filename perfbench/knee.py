#!/usr/bin/env python3
"""Find a gateway cell's knee: the highest offered rate its gateway
sustains with route latency p99 within the traffic's limit and no
growing backlog.

    python3 perfbench/knee.py --workload paper3_steady \\
        --rates 10000,20000,40000 --seconds 5 --seed 1

One process, one chip: set-up once, then for each rate a fresh gateway
plays ``judge delay + 1 s`` of warm-up and ``--seconds`` of window.
Prints one JSON line per rate and the knee last. The cell's fixed rate
is 4/5 of the knee, written into its traffic file by hand.
"""
from __future__ import annotations

import argparse
import copy
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(
        HERE, ".cache", "jax")
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax
    import numpy as np

    from perfbench import arrivals, registry
    from perfbench.cell import Cell, percentile
    from perfbench.entries import gateway

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"knee: needs a TPU; JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    bench = registry.benchmark()
    wl = registry.workload(bench, args.workload)
    config = registry.config(bench, wl["config"])
    base = registry.traffic(wl["traffic"])
    limit_ms = float(base["latency_limit_ms"])
    cell = Cell(workload=wl, config=config, traffic=base, seed=args.seed,
                seconds=args.seconds, trace=False, t_process=0.0,
                trace_dir="")
    import time
    t0 = time.perf_counter()
    cfg, state0, _, _, test = gateway.prepare(cell)
    print(f"set-up {time.perf_counter() - t0:.1f}s", file=sys.stderr,
          flush=True)
    knee = None
    for rate in [float(r) for r in args.rates.split(",")]:
        traffic = copy.deepcopy(base)
        traffic["arrivals"]["rate_per_s"] = rate
        traffic["warmup_s"] = float(base["judge_delay_s"]) + 1.0
        cell.traffic = traffic
        sched = arrivals.schedule(traffic, config, args.seed, args.seconds,
                                  test.n)
        log, _ = gateway.play(cell, cfg, state0, sched, test)
        win = sched.in_window()
        lat = log.route_t - sched.due
        end = np.nanmax(log.route_t)
        lat = np.where(np.isnan(lat), end - sched.due, lat)
        half = sched.window_open + args.seconds / 2
        first = win & (sched.due < half)
        second = win & (sched.due >= half)
        p99 = 1e3 * percentile(lat[win], 99)
        p99_a = 1e3 * percentile(lat[first], 99)
        p99_b = 1e3 * percentile(lat[second], 99)
        t_done = np.asarray([b[3] for b in log.blocks])
        B = np.asarray([len(b[0]) for b in log.blocks])
        in_w = (t_done >= sched.window_open) & (t_done < sched.window_close)
        row = {
            "rate": rate, "route_p99_ms": p99, "p99_first_half_ms": p99_a,
            "p99_second_half_ms": p99_b,
            "route_p50_ms": 1e3 * percentile(lat[win], 50),
            "decisions_per_s": float(B[in_w].sum()) / args.seconds,
            "block_rows_mean": float(B[in_w].mean()),
            "block_rows_range": [int(B.min()), int(B.max())],
            "gen_late_p99_ms": 1e3 * percentile(
                (log.submit_t - sched.due)[win], 99),
            "ticks": len(log.ticks),
        }
        sustained = p99 <= limit_ms and p99_b <= max(2 * p99_a, limit_ms / 2)
        row["sustained"] = bool(sustained)
        print(json.dumps(row), flush=True)
        if sustained:
            knee = rate
    print(json.dumps({"workload": wl["name"], "knee": knee,
                      "rate_at_4_5": None if knee is None else 0.8 * knee}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
