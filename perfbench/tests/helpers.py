"""Small cells for the CPU tests: the same code paths as a chip run, at
a size a test run can hold."""
from __future__ import annotations

import copy
import time

from perfbench import registry
from perfbench.cell import Cell

# Gateway traffic scaled down: 300 requests/s, windows of at most 4 rows,
# a 0.2 s judge delay and 0.6 s of warm-up and of window.
SMALL_GATEWAY = {"rate_per_s": 300.0, "max_batch": 4, "judge_delay_s": 0.2,
                 "warmup_s": 0.6}


# The cells the tests drive: (configuration, traffic, chips). Only
# paper3_grid's kind is in BENCHMARK.json; the others wait for the
# program (PERF.md, Open questions) and are driven here at a small size.
CELLS = {
    "paper3_steady": ("paper3", "steady", 1),
    "fleet64_bursty": ("fleet64", "bursty", 1),
    "paper3_trickle": ("paper3", "trickle", 1),
    "paper3_grid_4chip": ("paper3", "grid", 4),
}

# The end-to-end metrics each entry reports: (name, unit, better).
ENTRY_METRICS = {
    "gateway": [("route_p99_ms", "ms", "lower"),
                ("decisions_per_s", "decisions/s", "higher"),
                ("feedback_lag_p99_ms", "ms", "lower")],
    "fabric": [("grid_steps_per_s", "steps/s", "higher")],
}


def bench() -> dict:
    """BENCHMARK.json with the cells above added, each reporting its
    entry's end-to-end metrics and ``setup_s``, for the tests that drive
    cells it does not declare."""
    b = registry.benchmark()
    cells = [{"name": n, "config": c, "traffic": t, "chips": k}
             for n, (c, t, k) in CELLS.items()]
    kind = {w["name"]: registry.traffic(w["traffic"])["entry"]
            for w in b["workloads"] + cells}
    have = {c["name"] for c in b["configs"]}
    b["configs"] = b["configs"] + [
        {"name": c, "file": f"perfbench/configs/{c}.json"}
        for c in sorted({w["config"] for w in cells} - have)]
    b["workloads"] = b["workloads"] + cells
    b["end_to_end"] = [m for m in b["end_to_end"]
                       if m["name"] == "setup_s"] + [
        {"name": name, "unit": unit, "better": better,
         "workloads": [w for w, k in kind.items() if k == entry]}
        for entry, metrics in ENTRY_METRICS.items()
        for name, unit, better in metrics]
    return b


def cell(workload: str, seed: int = 12345, seconds: float = 0.6,
         **traffic_overrides) -> Cell:
    conf, traffic_name, chips = CELLS[workload]
    wl = {"name": workload, "config": conf, "traffic": traffic_name,
          "chips": chips}
    traffic = copy.deepcopy(registry.traffic(traffic_name))
    if traffic["entry"] == "gateway":
        traffic["arrivals"]["rate_per_s"] = SMALL_GATEWAY["rate_per_s"]
        traffic["admission"]["max_batch"] = SMALL_GATEWAY["max_batch"]
        traffic["admission"]["warm_rows"] = [1, SMALL_GATEWAY["max_batch"]]
        traffic["judge_delay_s"] = SMALL_GATEWAY["judge_delay_s"]
        traffic["warmup_s"] = SMALL_GATEWAY["warmup_s"]
    else:
        traffic["budgets"] = [3.0e-4, 1.0]
        traffic["seeds_per_call"] = 2
    traffic.update(traffic_overrides)
    return Cell(workload=wl, config=registry.config_file(conf),
                traffic=traffic, seed=seed, seconds=seconds, trace=False,
                t_process=time.perf_counter(), trace_dir="")
