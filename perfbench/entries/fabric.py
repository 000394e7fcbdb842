"""Fabric entry: back-to-back ``sweep.run_grid`` calls over a (ceiling x
seed) grid, sharded over the cell's chips, fresh seeds on every call.

Each call builds its seeds' shuffled streams on the host (inside the
timed work, as a researcher's loop would) and runs the whole grid as
one compiled program on the scalar data plane. The window runs whole
calls until ``--seconds`` have passed; the rate is every completed
element-step over the time from the window's opening to the end of the
last call.

After the window the reference replays a sample of grid elements, drawn
from the seed, through the plain Algorithm 1 loop and compares the
chosen arms, the realised costs and rewards, the dual trace and the
final statistics.
"""
from __future__ import annotations

import contextlib
import time
from typing import Dict, List, Optional

import numpy as np

from perfbench import data, devicemem
from perfbench.cell import Cell, Check, Outcome
from perfbench.reference import algo1

# Grid elements compared with the reference: one per ceiling.
SAMPLED_PER_CONDITION = 1
# Whole calls traced in a --trace 1 run.
TRACED_CALLS = 2
# Call indices of the warm-up calls (their seeds differ from the window's).
WARMUP_CALL_BASE = 2 ** 20


def _grid_inputs(cell, test, call: int):
    """Seeds and per-seed stream orders of one call, from the run seed."""
    S = int(cell.traffic["seeds_per_call"])
    rng = np.random.default_rng([cell.seed, call])
    seeds = rng.integers(0, 2 ** 32, S).tolist()
    perms = [rng.permutation(test.n) for _ in range(S)]
    return seeds, perms


def window(cell: Cell, tracer=None):
    """Set up, warm up, and run whole grid calls for the window. Returns
    (results, train, test, budgets, timing): one (seeds, stream orders,
    GridResult, final states) per window call, and the set-up and
    window times with the programs compiled in the window."""
    import jax

    from repro.core import evaluate, sweep
    from repro.core.types import HyperParams, RouterConfig

    t_entry = time.perf_counter()
    config, traffic = cell.config, cell.traffic
    devices = jax.devices()[:int(cell.workload["chips"])]
    train, _, test = data.for_config(config)
    cfg = RouterConfig(d=config["d"], max_arms=config["max_arms"],
                       hyper=HyperParams(alpha=config["alpha"],
                                         gamma=config["gamma"]))
    priors = evaluate.fit_warmup_priors(cfg, train)
    budgets = [float(b) for b in traffic["budgets"]]
    span = tracer.span if tracer else (lambda name: contextlib.nullcontext())

    def call(c: int):
        with span("grid_prepare"):
            seeds, perms = _grid_inputs(cell, test, c)
            envs = [test.subset(p) for p in perms]
        with span("run_grid"):
            grid, finals = sweep.run_grid(
                cfg, envs, budgets, seeds=seeds, priors=priors,
                n_eff=config["n_eff"], shuffle=False, devices=devices,
                return_states=True)
        return seeds, perms, grid, finals

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
    E, T = len(budgets) * len(results[0][0]), test.n
    layer = {"elements_per_chip": E // chips, "steps_per_call": T,
             "compiles_in_window": timing["compiles"]}
    checks = compare(cell, readings(cell, results, train, test, budgets))
    return Outcome(
        end_to_end={"grid_steps_per_s":
                    len(results) * E * T / timing["window_s"]},
        layer=layer, checks=checks, attempted=len(results) * E, failed=0,
        memory_peak_bytes=timing["memory_peak_bytes"],
        setup_s=timing["setup_s"], trace=tracer)


def replay_element(config, train, test, budget, perm, arms,
                   control: Optional[algo1.Arith] = None):
    """One grid element through the plain per-request loop, following
    the program's arms. Returns (gaps, lams before each step, final
    router): the gap of each chosen arm below the reference's best.
    With ``control``, a reference in that arithmetic runs in lockstep
    and stands in the program's place: the gaps are of its own choices,
    the lams and the final router its own."""
    hp = algo1.Hyper(alpha=config["alpha"], gamma=config["gamma"])
    ars = [algo1.Arith()] + ([control] if control else [])
    sts = [algo1.warm_router(train.contexts, train.rewards,
                             config["max_arms"], config["n_eff"], [budget],
                             hp, ar) for ar in ars]
    pf = algo1.portfolio(test.prices_per_req, test.prices_per_1k,
                         config["max_arms"], hp)
    X, R, C = test.contexts[perm], test.rewards[perm], test.costs[perm]
    T = len(perm)
    gaps = np.empty(T)
    lams = np.empty(T)
    for i in range(T):
        a = np.asarray([arms[i]])
        lams[i] = sts[-1].pacers.lam[0]
        s, cand = algo1.scores(sts[0], pf, X[i:i + 1], sts[0].pacers.lam[:1],
                               hp)
        chosen = a
        if control:
            sc, cc = algo1.scores(sts[1], pf, X[i:i + 1],
                                  sts[1].pacers.lam[:1], hp, control)
            chosen = np.argmax(np.where(cc, sc, -np.inf), axis=1)
        gaps[i] = algo1.arm_gaps(s, cand, chosen)[0]
        for st, ar in zip(sts, ars):
            algo1.dispatch(st, a)
            algo1.fold_rows(st, st.t, a, X[i:i + 1], R[i:i + 1, arms[i]],
                            hp, ar)
            algo1.fold_costs(st.pacers, C[i:i + 1, arms[i]], None, hp, ar)
    return gaps, lams, sts[-1]


def readings(cell, results, train, test, budgets,
             control: Optional[algo1.Arith] = None) -> Dict[str, float]:
    """Every number the comparison reads, over a sample of one call's
    elements drawn from the seed. With ``control``, the reference in
    that arithmetic stands in the program's place."""
    import jax

    rng = np.random.default_rng([cell.seed, 7])
    k = int(rng.integers(len(results)))
    seeds, perms, grid, finals = results[k]
    S = len(seeds)
    active = np.arange(cell.config["max_arms"]) < test.k
    arm_gap = lam_gap = stats = 0.0
    outcome_faults = 0
    for ci, budget in enumerate(budgets):
        for s in rng.choice(S, SAMPLED_PER_CONDITION, replace=False):
            arms = grid.arms[ci, s].astype(np.int64)
            perm = perms[s]
            rows = np.arange(len(perm))
            outcome_faults += int(np.sum(
                grid.costs[ci, s] != test.costs[perm][rows, arms]))
            outcome_faults += int(np.sum(
                grid.rewards[ci, s] != test.rewards[perm][rows, arms]))
            gaps, lams, st = replay_element(cell.config, train, test,
                                            budget, perm, arms)
            e = ci * S + s
            got = jax.device_get({"A_inv": finals.A_inv[e],
                                  "theta": finals.theta[e],
                                  "b": finals.b[e]})
            got_lams = grid.lams[ci, s]
            if control:
                gaps, got_lams, c_st = replay_element(
                    cell.config, train, test, budget, perm, arms, control)
                got = {"A_inv": c_st.Ainv, "theta": c_st.theta,
                       "b": c_st.b}
            arm_gap = max(arm_gap, float(np.max(gaps)))
            lam_gap = max(lam_gap, float(np.max(np.abs(got_lams - lams))))
            for name, w in (("A_inv", st.Ainv), ("theta", st.theta),
                            ("b", st.b)):
                g = np.asarray(got[name], np.float64)[active].reshape(
                    int(active.sum()), -1)
                w = w[active].reshape(len(g), -1)
                stats = max(stats, float(np.max(
                    np.linalg.norm(g - w, axis=1)
                    / np.linalg.norm(w, axis=1))))
    return {"outcome_faults": float(outcome_faults), "arm_gap": arm_gap,
            "lam_gap": lam_gap, "stats_rel_gap": stats}


def compare(cell, values: Dict[str, float]) -> List[Check]:
    lim = cell.limits()
    return [Check(name, v, 0.0 if name == "outcome_faults" else lim[name])
            for name, v in values.items()]
