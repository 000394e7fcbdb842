"""Benchmark harness: one module per paper table/figure.

``python -m benchmarks.run [--quick] [--only NAME ...]``

Prints ``name,value,derived`` CSV rows per benchmark (and saves JSON
under benchmarks/results/).
"""
from __future__ import annotations

import argparse
import sys
import time

BENCHES = [
    ("pareto", "Fig. 1: stationary budget pacing frontier"),
    ("cost_drift", "Table 2 / Fig. 2: budget pacing under cost drift"),
    ("degradation", "Fig. 3: silent quality degradation"),
    ("onboarding", "Figs. 4-5: cold-start model onboarding"),
    ("knee", "Tables 3-4: Pareto knee-point hyperparameters"),
    ("warmup", "Table 5: warmup-prior ablation"),
    ("prior_mismatch", "Fig. 9: prior mismatch sensitivity"),
    ("judges", "App. E: reward-signal robustness across judges"),
    ("cost_heuristic", "App. B: cost heuristic validation"),
    ("recovery_limit", "App. G: recovery limit"),
    ("scenarios", "Scenario engine: new multi-event scenarios, both planes"),
    ("scenario_grid", "Scenario x budget matrices via the sweep fabric"),
    ("scenario_param_grid",
     "Fused (payload x budget x seed) spec families, looped-vs-fused"),
    ("scenario_mc",
     "Scenario Monte Carlo: randomized timelines as one fused call"),
    ("sweep", "Sweep fabric: looped-vs-fabric grid wall clock"),
    ("gateway",
     "Serving gateway: decoupled-plane decisions/sec + select p95"),
    ("tenants",
     "Multi-tenant pacing: per-tenant fold identity + 0.4% compliance"),
    ("latency", "Tables 10-11: routing latency microbenchmark"),
    ("roofline", "Roofline: dry-run roofline table"),
]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="fewer seeds (CI smoke)")
    ap.add_argument("--only", nargs="*", default=None)
    args = ap.parse_args(argv)

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    import importlib
    # Entries whose module or entrypoint differs from bench_{name}.main().
    MODULES = {"scenario_grid": "scenarios",
               "scenario_param_grid": "scenarios",
               "scenario_mc": "scenarios"}
    failures = []
    for name, desc in BENCHES:
        if args.only and name not in args.only:
            continue
        mod = importlib.import_module(
            f"benchmarks.bench_{MODULES.get(name, name)}")
        print(f"# === {name}: {desc} ===", flush=True)
        t0 = time.time()
        try:
            if name == "sweep":
                mod.main(argv=["--smoke"] if args.quick else [])
            elif name == "scenario_grid":
                mod.budget_grid(seeds=tuple(range(5)) if args.quick
                                else tuple(range(20)))
            elif name == "scenario_param_grid":
                mod.param_grid(smoke=args.quick)
            elif name == "scenario_mc":
                mod.mc_grid(smoke=args.quick)
            elif name in ("gateway", "tenants"):
                mod.main(smoke=args.quick)
            elif args.quick and name in ("pareto", "cost_drift",
                                         "degradation", "onboarding",
                                         "warmup", "prior_mismatch",
                                         "judges", "scenarios"):
                mod.main(seeds=tuple(range(5)))
            elif args.quick and name in ("knee", "recovery_limit"):
                mod.main(seeds=tuple(range(3)))
            else:
                mod.main()
        except Exception:  # noqa: BLE001
            import traceback
            traceback.print_exc()
            failures.append(name)
        print(f"# {name} done in {time.time() - t0:.1f}s", flush=True)
    if failures:
        print("FAILED:", failures)
        sys.exit(1)


if __name__ == "__main__":
    main()
