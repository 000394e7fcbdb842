"""Timeline entry: back-to-back ``sweep.run_scenario_grid`` calls over a
Monte Carlo of event timelines, fresh seeds and event steps on every
call.

Each call runs one ceiling x ``seeds_per_call`` fresh stream seeds, and
each element has its own timeline: every event of the configuration at
a step drawn uniformly on the traffic's window, independently per event
and per element. The streams are iid over the test split (the spec's
``stream_seed_base``); the grid runs as one compiled program on the
scalar data plane (the masked timeline fabric), the newcomer's hot swap
and forced pulls inside it. The window runs whole calls until
``--seconds`` have passed; the rate is every completed element-step
over the time from the window's opening to the end of the last call.

After the window the reference (``reference/events.py``) replays a
sample of one call's elements, drawn from the seed, one request at a
time following the program's arms, and compares the realised rewards
and costs, the chosen arms, the dual trace and the final statistics.
"""
from __future__ import annotations

import contextlib
import time
from typing import Dict, List, Optional

import numpy as np

from perfbench import devicemem, flash
from perfbench.cell import Cell, Outcome
from perfbench.entries.fabric import compare
from perfbench.reference import algo1, events

# Grid elements of one call compared with the reference.
SAMPLED = 8
# Whole calls traced in a --trace 1 run: one holds 1,824 device steps
# of every element, and each call's staging phases are recorded once.
TRACED_CALLS = 1
# Call indices of the warm-up calls (their seeds differ from the window's).
WARMUP_CALL_BASE = 2 ** 20


def event_window(traffic):
    """[lo, hi) of every event's step: twelfths of the horizon."""
    T = int(traffic["horizon"])
    a, b = traffic["event_window_twelfths"]
    return T * int(a) // 12, T * int(b) // 12


def call_inputs(cell, call: int):
    """Seeds and per-element event steps of one call, from the run
    seed: (seeds, (S, E) steps)."""
    traffic = cell.traffic
    S, E = int(traffic["seeds_per_call"]), len(cell.config["events"])
    rng = np.random.default_rng([cell.seed, call])
    seeds = rng.integers(0, 2 ** 32, S).tolist()
    lo, hi = event_window(traffic)
    return seeds, rng.integers(lo, hi, size=(S, E))


def scenario_spec(config, traffic):
    """The configuration's events as a ``ScenarioSpec``; each event's
    step is a placeholder that every element's timeline replaces."""
    from repro.core import scenario

    t0, _ = event_window(traffic)
    out = []
    for ev in config["events"]:
        kw = {k: v for k, v in ev.items() if k != "kind"}
        out.append(getattr(scenario, ev["kind"])(t=t0, **kw))
    return scenario.ScenarioSpec(
        horizon=int(traffic["horizon"]), events=tuple(out),
        stream_seed_base=int(traffic["stream_seed_base"]), mode="iid",
        init_active=int(config["init_active"]))


def program_env(config, test):
    """The test split as the program's ``Environment``. An iid stream
    with no traffic-mix event never reads the prompts' task families,
    which the benchmark's data copy does not keep: they are all 0."""
    from repro.core import simulator

    return simulator.Environment(
        contexts=test.contexts, rewards=test.rewards, costs=test.costs,
        families=np.zeros(test.n, np.int64),
        prices_per_req=test.prices_per_req,
        prices_per_1k=test.prices_per_1k, names=tuple(config["arms"]))


def window(cell: Cell, tracer=None):
    """Set up, warm up, and run whole calls for the window. Returns
    (results, train, test, budgets, timing): one (seeds, event steps,
    GridResult, final states) per window call, and the set-up and
    window times with the programs compiled in the window."""
    import jax

    from repro.core import evaluate, scenario, sweep
    from repro.core.types import HyperParams, RouterConfig

    t_entry = time.perf_counter()
    config, traffic = cell.config, cell.traffic
    devices = jax.devices()[:int(cell.workload["chips"])]
    train, test = flash.for_config(config)
    env = program_env(config, test)
    cfg = RouterConfig(d=config["d"], max_arms=config["max_arms"],
                       forced_pulls=config["forced_pulls"],
                       hyper=HyperParams(alpha=config["alpha"],
                                         gamma=config["gamma"]))
    priors = evaluate.fit_warmup_priors(cfg, train)
    priors = list(priors) + [None] * (test.k - len(priors))
    spec = scenario_spec(config, traffic)
    budgets = [float(b) for b in traffic["budgets"]]
    span = tracer.span if tracer else (lambda name: contextlib.nullcontext())

    def call(c: int):
        with span("grid_prepare"):
            seeds, steps = call_inputs(cell, c)
            timelines = [scenario.Timeline(tuple(s)) for s in steps]
        with span("run_grid"):
            grid, finals = sweep.run_scenario_grid(
                cfg, spec, env, budgets, seeds=seeds, priors=priors,
                n_eff=config["n_eff"], devices=devices, return_states=True,
                timelines=timelines)
        return seeds, steps, grid, finals

    t_priors = time.perf_counter()
    for c in range(int(traffic["warmup_calls"])):
        call(WARMUP_CALL_BASE + c)
    t_open = time.perf_counter()
    compiles = devicemem.CompileCounter()
    compiles.start()
    results: List[tuple] = []
    while True:
        if tracer and not results:
            tracer.start()
        results.append(call(len(results)))
        if tracer and len(results) == TRACED_CALLS:
            tracer.stop()
        if time.perf_counter() - t_open >= cell.seconds and (
                not tracer or len(results) >= TRACED_CALLS):
            break
    timing = {"setup_s": t_open - cell.t_process,
              "setup_stages": {"process_to_entry_s": t_entry - cell.t_process,
                               "data_priors_s": t_priors - t_entry,
                               "warmup_calls_s": t_open - t_priors},
              "window_s": time.perf_counter() - t_open,
              "compiles": compiles.stop(),
              "memory_peak_bytes": devicemem.peak_bytes(devices)}
    return results, train, test, budgets, timing


def run(cell: Cell) -> Outcome:
    from perfbench import trace as trace_lib

    tracer = trace_lib.Tracer(cell.trace_dir) if cell.trace else None
    results, train, test, budgets, timing = window(cell, tracer)
    print("setup stages: " + ", ".join(
        f"{k} {v:.3f}" for k, v in timing["setup_stages"].items()),
        flush=True)
    chips = int(cell.workload["chips"])
    E, T = len(budgets) * len(results[0][0]), int(cell.traffic["horizon"])
    layer = {"elements_per_chip": E // chips, "steps_per_call": T,
             "compiles_in_window": timing["compiles"]}
    checks = compare(cell, readings(cell, results, train, test, budgets))
    return Outcome(
        end_to_end={"grid_steps_per_s":
                    len(results) * E * T / timing["window_s"]},
        layer=layer, checks=checks, attempted=len(results) * E, failed=0,
        memory_peak_bytes=timing["memory_peak_bytes"],
        setup_s=timing["setup_s"], trace=tracer)


def _rel_gap(got, want) -> float:
    """The worst row's relative gap; a row of zeros (a slot never
    pulled holds b = theta = 0) gaps by inf unless matched exactly."""
    num = np.linalg.norm(got - want, axis=1)
    den = np.linalg.norm(want, axis=1)
    safe = np.where(den > 0, den, 1.0)
    return float(np.max(np.where(den > 0, num / safe,
                                 np.where(num > 0, np.inf, 0.0))))


def readings(cell, results, train, test, budgets,
             control: Optional[algo1.Arith] = None) -> Dict[str, float]:
    """Every number the comparison reads, over a sample of one call's
    elements drawn from the seed. With ``control``, the reference in
    that arithmetic stands in the program's place."""
    import jax

    rng = np.random.default_rng([cell.seed, 7])
    k = int(rng.integers(len(results)))
    seeds, steps, grid, finals = results[k]
    S = len(seeds)
    slots = np.arange(cell.config["max_arms"]) < test.k
    arm_gap = lam_gap = stats = 0.0
    outcome_faults = 0
    for ci, budget in enumerate(budgets):
        for s in rng.choice(S, min(SAMPLED, S), replace=False):
            arms = grid.arms[ci, s].astype(np.int64)
            element = events.Element(seeds[s], tuple(steps[s]))
            gaps, lams, r, c, st = events.replay(
                cell.config, cell.traffic, train, test, budget, element,
                arms)
            outcome_faults += int(np.sum(grid.rewards[ci, s] != r))
            outcome_faults += int(np.sum(grid.costs[ci, s] != c))
            e = ci * S + s
            got = jax.device_get({"A_inv": finals.A_inv[e],
                                  "theta": finals.theta[e],
                                  "b": finals.b[e]})
            got_lams = grid.lams[ci, s]
            if control:
                gaps, got_lams, _, _, c_st = events.replay(
                    cell.config, cell.traffic, train, test, budget, element,
                    arms, control)
                got = {"A_inv": c_st.Ainv, "theta": c_st.theta,
                       "b": c_st.b}
            arm_gap = max(arm_gap, float(np.max(gaps)))
            lam_gap = max(lam_gap, float(np.max(np.abs(got_lams - lams))))
            for name, w in (("A_inv", st.Ainv), ("theta", st.theta),
                            ("b", st.b)):
                g = np.asarray(got[name], np.float64)[slots].reshape(
                    int(slots.sum()), -1)
                w = w[slots].reshape(len(g), -1)
                stats = max(stats, _rel_gap(g, w))
    return {"outcome_faults": float(outcome_faults), "arm_gap": arm_gap,
            "lam_gap": lam_gap, "stats_rel_gap": stats}
