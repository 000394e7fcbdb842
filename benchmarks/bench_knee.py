"""Tables 3-4 / Appendix A: T_adapt-constrained Pareto knee-point
hyper-parameter selection — the full grid as ONE fabric call.

Grid over (alpha, gamma) with n_eff derived from the adaptation horizon
(Eq. 13). Objective 1: budget-paced Pareto AUC on the val split;
objective 2: Phase-2 reward under a catastrophic Mistral failure
(reward -> 0.50). Knee-point vs AUC-only selection, for warmup and
tabula-rasa variants, plus the T_adapt in {250, 500, 1000} sensitivity.

Hyper-parameters are state leaves (DESIGN.md §9), so the whole
(alpha x gamma x budget x seed) selection grid stacks on the sweep
fabric's condition axis — ``sweep.TRACE_COUNT`` moves by exactly ONE for
the AUC grid (and once more for the differently-shaped Phase-2 grid)
instead of compiling one program per (alpha, gamma) cell. The cells
enter as per-condition ``HyperParams`` leaves and each cell's
gamma-derived warm start (n_eff via Eq. 13) as a per-condition
``n_eff`` vector, both applied inside ``make_states``' one compiled
program.

``--baseline`` additionally runs the pre-fusion protocol — one fabric
call per cell for the budget frontier plus one ``evaluate.run`` per cell
for Phase 2 — asserts the fused grid reproduces it BIT-IDENTICALLY, and
records the looped-vs-fused wall clock in ``benchmarks/results/knee.json``
(cold = with compile, warm = steady-state). ``--smoke`` shrinks the
environment and grid for the CI ``knee-grid`` job (baseline included).
"""
from __future__ import annotations

import sys

from benchmarks._devices import apply_devices_flag

apply_devices_flag(sys.argv)  # must precede any jax import

import argparse
import time

import numpy as np

from benchmarks.common import benchmark, emit, warmup_priors
from repro.core import evaluate, knee, simulator, sweep, warmup
from tests.trace_guard import assert_traces
from repro.core.types import HyperParams, RouterConfig

ALPHAS = (0.005, 0.01, 0.05, 0.1)
GAMMAS = (0.994, 0.995, 0.996, 0.997, 0.998, 0.999, 1.0)
AUC_BUDGETS = (1.0e-4, 3.0e-4, 6.6e-4, 1.9e-3, 6.0e-3)
PHASE = 595  # half the val split, as in the paper
PHASE2_BUDGET = 6.6e-4
MISTRAL = 1
GRID_SEEDS = tuple(range(10))


def _cells(alphas, gammas):
    return [(a, g) for a in alphas for g in gammas]


def _n_eff(t_adapt, gamma, use_priors):
    return warmup.t_adapt_to_n_eff(t_adapt, gamma) if use_priors else 0.0


def _phase2_envs(env, seeds, phase):
    """Per-seed two-phase streams: stationary, then Mistral reward
    collapses to 0.50 (same draws as the pre-fusion protocol)."""
    envs = []
    for s in seeds:
        rng = np.random.default_rng(5000 + s)
        idx1 = rng.integers(0, env.n, phase)
        idx2 = rng.integers(0, env.n, phase)
        p1 = env.subset(idx1)
        p2 = simulator.with_quality_shift(env, MISTRAL, 0.50).subset(idx2)
        envs.append(simulator.concat_environments((p1, p2)))
    return envs


def _cell_hyper(cells, reps=1):
    """Per-condition (C,) HyperParams stack for ``cells`` repeated
    ``reps`` times each (cell-major condition layout)."""
    return HyperParams(
        alpha=np.asarray([a for a, _ in cells for _ in range(reps)],
                         np.float32),
        gamma=np.asarray([g for _, g in cells for _ in range(reps)],
                         np.float32),
    )


def score_grid_fused(t_adapt, use_priors, seeds, *, env=None, priors=None,
                     alphas=ALPHAS, gammas=GAMMAS, auc_budgets=AUC_BUDGETS,
                     phase=PHASE, return_raw=False, chunk_size=None):
    """The whole (alpha x gamma x budget x seed) selection grid as ONE
    compiled, device-sharded fabric call (plus one more for the Phase-2
    stress grid, whose stream shapes differ).

    The (alpha, gamma) cells ride the condition axis as per-condition
    ``HyperParams`` leaves, and each cell's gamma-derived warm start as a
    per-condition ``n_eff`` — both applied inside ``make_states``' one
    compiled program (DESIGN.md §7/§9), so the host-side setup cost does
    not grow with the number of cells.

    ``chunk_size`` bounds the live per-step working set of each fabric
    call (sweep.run_grid's scan-over-chunks; results bit-identical):
    the full AUC grid is 28 cells x 5 budgets x 10 seeds = 1400 live
    elements, whose combined per-step state spills the CPU last-level
    cache. Non-divisors are fitted per grid via ``sweep.fit_chunk``."""
    if env is None:
        env = benchmark().val
    if use_priors and priors is None:
        priors = list(warmup_priors())
    cfg = RouterConfig()
    cells = _cells(alphas, gammas)
    n_effs = [_n_eff(t_adapt, g, use_priors) for _, g in cells]
    kw = dict(priors=priors) if use_priors else {}

    # Objective 1: every cell's budget frontier, stacked into one grid —
    # C = cells x budgets conditions, cell-major so cell i owns the
    # consecutive conditions [i*nb, (i+1)*nb).
    nb = len(auc_budgets)
    budgets = [b for _ in cells for b in auc_budgets]

    def fit(C):
        if chunk_size is None:
            return None
        return sweep.fit_chunk(C * len(seeds), chunk_size)

    grid = sweep.run_grid(
        cfg, env, budgets, seeds=seeds,
        hyper=_cell_hyper(cells, reps=nb),
        n_eff=np.repeat(n_effs, nb) if use_priors else 0.0,
        chunk_size=fit(len(budgets)), **kw)

    # Objective 2: Phase-2 reward under the Mistral failure, one
    # condition per cell over per-seed two-phase streams.
    envs = _phase2_envs(env, seeds, phase)
    grid2 = sweep.run_grid(
        cfg, envs, (PHASE2_BUDGET,) * len(cells), seeds=seeds,
        hyper=_cell_hyper(cells),
        n_eff=np.asarray(n_effs) if use_priors else 0.0,
        shuffle=False, chunk_size=fit(len(cells)), **kw)

    results = []
    for i, (a, g) in enumerate(cells):
        qualities, costs = [], []
        for j in range(nb):
            res = grid.condition(i * nb + j)
            qualities.append(res.mean_reward)
            costs.append(max(res.mean_cost, 1e-7))
        auc = knee.auc_of_frontier(np.asarray(costs), np.asarray(qualities))
        p2 = grid2.condition(i).phase(phase, 2 * phase).mean_reward
        results.append(dict(alpha=a, gamma=g, n_eff=n_effs[i],
                            auc=auc, p2=p2))
    if return_raw:
        return results, (grid, grid2)
    return results


def score_grid_looped(t_adapt, use_priors, seeds, *, env=None, priors=None,
                      alphas=ALPHAS, gammas=GAMMAS, auc_budgets=AUC_BUDGETS,
                      phase=PHASE, return_raw=False):
    """The pre-fusion protocol: one fabric call per (alpha, gamma) cell
    for the budget frontier + one ``evaluate.run`` per cell for Phase 2.
    Kept as the equivalence gate and the wall-clock baseline."""
    if env is None:
        env = benchmark().val
    if use_priors and priors is None:
        priors = list(warmup_priors())
    envs = _phase2_envs(env, seeds, phase)
    results, raw = [], []
    for alpha in alphas:
        for gamma in gammas:
            n_eff = _n_eff(t_adapt, gamma, use_priors)
            cfg = RouterConfig(hyper=HyperParams(alpha=alpha, gamma=gamma))
            kw = dict(priors=priors if use_priors else None, n_eff=n_eff)
            grid = sweep.run_grid(cfg, env, auc_budgets, seeds=seeds, **kw)
            qualities, costs = [], []
            for _, res in grid.conditions():
                qualities.append(res.mean_reward)
                costs.append(max(res.mean_cost, 1e-7))
            auc = knee.auc_of_frontier(np.asarray(costs),
                                       np.asarray(qualities))
            p2res = evaluate.run(cfg, envs, PHASE2_BUDGET, seeds=seeds,
                                 shuffle=False, **kw)
            p2 = p2res.phase(phase, 2 * phase).mean_reward
            results.append(dict(alpha=alpha, gamma=gamma, n_eff=n_eff,
                                auc=auc, p2=p2))
            raw.append((grid, p2res))
    if return_raw:
        return results, raw
    return results


def _assert_fused_matches_looped(fused_raw, looped_raw, n_cells, nb):
    """The fused grid must reproduce every looped cell BIT-identically."""
    grid, grid2 = fused_raw
    for i in range(n_cells):
        cell_grid, p2res = looped_raw[i]
        for j in range(nb):
            a, b = grid.condition(i * nb + j), cell_grid.condition(j)
            np.testing.assert_array_equal(a.arms, b.arms)
            np.testing.assert_array_equal(a.rewards, b.rewards)
            np.testing.assert_array_equal(a.costs, b.costs)
            np.testing.assert_array_equal(a.lams, b.lams)
        f2 = grid2.condition(i)
        np.testing.assert_array_equal(f2.arms, p2res.arms)
        np.testing.assert_array_equal(f2.rewards, p2res.rewards)
        np.testing.assert_array_equal(f2.costs, p2res.costs)
        np.testing.assert_array_equal(f2.lams, p2res.lams)


def select(results):
    pts = np.asarray([[r["auc"], r["p2"]] for r in results])
    knee_i = knee.knee_point(pts)
    auc_i = int(np.argmax(pts[:, 0]))
    return results[knee_i], results[auc_i]


def _time(fn, repeats):
    """(cold_s, warm_s): first call includes compile; warm is best-of."""
    t0 = time.perf_counter()
    fn()
    cold = time.perf_counter() - t0
    warm = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        warm = min(warm, time.perf_counter() - t0)
    return cold, warm


def _clear_program_caches():
    sweep._cached_grid_fn.cache_clear()
    evaluate._cached_run_fn.cache_clear()


def score_grid_presplit(t_adapt, use_priors, seeds, **grid_kw):
    """Emulate the pre-split protocol, where (alpha, gamma) lived on
    ``RouterConfig`` as trace constants: every cell paid a fresh XLA
    compile. Now that hyper-parameters are state leaves the program
    caches key on ``Statics`` alone, so the only way to reproduce the
    historical cost is to clear them per cell — which is exactly what a
    per-cell config retrace did."""
    alphas, gammas = grid_kw["alphas"], grid_kw["gammas"]
    results = []
    for alpha in alphas:
        for gamma in gammas:
            _clear_program_caches()
            results.extend(score_grid_looped(
                t_adapt, use_priors, seeds,
                **{**grid_kw, "alphas": (alpha,), "gammas": (gamma,)}))
    return results


def run_baseline_gate(seeds, grid_kw, repeats=1, chunk=None):
    """Bit-identity gate + looped-vs-fused wall clock for the headline
    (warmup, T_adapt=500) variant. With ``chunk``, additionally gates
    the chunked fabric (bit-identical to unchunked) and records its
    wall clock — the fix for the wide grid's cache-spilling per-step
    working set. Returns emit rows."""
    rows = []
    n_cells = len(grid_kw["alphas"]) * len(grid_kw["gammas"])
    nb = len(grid_kw["auc_budgets"])

    looped_res, looped_raw = score_grid_looped(
        500.0, True, seeds, return_raw=True, **grid_kw)
    with assert_traces(sweep, 2, what="fused knee grid must compile as "
                       "one program per stream shape (AUC grid + "
                       "Phase-2 grid)"):
        fused_res, fused_raw = score_grid_fused(
            500.0, True, seeds, return_raw=True, **grid_kw)
    _assert_fused_matches_looped(fused_raw, looped_raw, n_cells, nb)
    assert fused_res == looped_res
    # New hyper values and warm starts are data: a whole different grid
    # (different T_adapt => different n_eff per cell) must re-enter the
    # SAME two executables with zero new traces.
    with assert_traces(sweep, 0, what="re-running the fused grid with "
                                      "new hyper values retraced"):
        score_grid_fused(300.0, True, seeds, **grid_kw)
    rows.append(["knee_equivalence", "bit_identical",
                 f"{n_cells}cells x {nb}budgets x {len(seeds)}seeds"])
    rows.append(["knee_fused_traces", "1+1",
                 "one compile for the AUC grid, one for phase2 shapes; "
                 "new (alpha, gamma, n_eff) values re-enter both"])

    # Wall clock. Three protocols:
    #   presplit — compile per (alpha, gamma) cell (the pre-§9 reality:
    #              hypers were trace constants on RouterConfig);
    #   looped   — one fabric call per cell, programs cached across
    #              cells (hypers are data, so cells share executables);
    #   fused    — the whole grid as one fabric call.
    t0 = time.perf_counter()
    score_grid_presplit(500.0, True, seeds, **grid_kw)
    presplit_s = time.perf_counter() - t0
    _clear_program_caches()
    looped_cold, looped_warm = _time(
        lambda: score_grid_looped(500.0, True, seeds, **grid_kw), repeats)
    _clear_program_caches()
    fused_cold, fused_warm = _time(
        lambda: score_grid_fused(500.0, True, seeds, **grid_kw), repeats)
    rows.append(["knee_presplit_s", f"{presplit_s:.3f}",
                 "compile-per-cell: hypers as trace constants (pre-§9)"])
    rows.append(["knee_looped_s", f"{looped_warm:.3f}",
                 f"cold={looped_cold:.3f}"])
    rows.append(["knee_fused_s", f"{fused_warm:.3f}",
                 f"cold={fused_cold:.3f}"])
    rows.append(["knee_speedup_vs_presplit",
                 f"{presplit_s / fused_cold:.2f}x",
                 "fused cold (with its one compile) vs compile-per-cell"])
    rows.append(["knee_speedup", f"{looped_warm / fused_warm:.2f}x",
                 f"cold {looped_cold / fused_cold:.2f}x; warm vs the "
                 "already-cache-sharing looped protocol"])

    if chunk:
        chunked_res = score_grid_fused(500.0, True, seeds,
                                       chunk_size=chunk, **grid_kw)
        assert chunked_res == fused_res, (
            "chunked fabric diverged from the unchunked grid")
        _clear_program_caches()
        ch_cold, ch_warm = _time(
            lambda: score_grid_fused(500.0, True, seeds, chunk_size=chunk,
                                     **grid_kw), repeats)
        rows.append(["knee_chunked_equivalence", "bit_identical",
                     f"chunk_size={chunk} vs whole-grid-live fabric"])
        rows.append(["knee_chunked_s", f"{ch_warm:.3f}",
                     f"cold={ch_cold:.3f};chunk={chunk};"
                     f"warm_vs_unchunked={fused_warm / ch_warm:.2f}x"])
    return rows


def main(seeds=None, argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="reduced environment + grid with the "
                         "compile-once assertion (CI knee-grid job)")
    ap.add_argument("--baseline", action="store_true",
                    help="also run the pre-fusion looped protocol: "
                         "bit-identity gate + wall-clock comparison")
    ap.add_argument("--repeats", type=int, default=1,
                    help="warm-timing repeats for --baseline")
    ap.add_argument("--devices", type=int, default=0,
                    help="force N CPU placeholder devices (before jax init)")
    ap.add_argument("--chunk", type=int, default=0,
                    help="also gate + time the chunk_size=N fabric "
                         "(bounded per-step working set; bit-identical)")
    args = ap.parse_args([] if argv is None else argv)

    if args.smoke:
        b = simulator.make_benchmark(
            seed=0, splits={"train": 256, "val": 128, "test": 64})
        env = b.val
        priors = list(evaluate.fit_warmup_priors(RouterConfig(), b.train))
        grid_kw = dict(env=env, priors=priors, alphas=(0.01, 0.1),
                       gammas=(0.995, 1.0), auc_budgets=AUC_BUDGETS[:3],
                       phase=48)
        seeds = seeds or tuple(range(3))
        variants = (("paretobandit", True), ("tabula_rasa", False))
        tadapts = ()
    else:
        grid_kw = dict(alphas=ALPHAS, gammas=GAMMAS,
                       auc_budgets=AUC_BUDGETS, phase=PHASE)
        seeds = seeds or GRID_SEEDS
        variants = (("paretobandit", True), ("tabula_rasa", False))
        tadapts = (250.0, 1000.0)

    rows = []
    if args.baseline or args.smoke:
        rows.extend(run_baseline_gate(seeds, grid_kw, repeats=args.repeats,
                                      chunk=args.chunk or None))

    for variant, use_priors in variants:
        res = score_grid_fused(500.0, use_priors, seeds, **grid_kw)
        kp, ao = select(res)
        rows.append([
            f"knee_{variant}", f"a={kp['alpha']};g={kp['gamma']}",
            f"n_eff={kp['n_eff']:.0f};auc={kp['auc']:.4f};p2={kp['p2']:.4f}"])
        rows.append([
            f"auconly_{variant}", f"a={ao['alpha']};g={ao['gamma']}",
            f"auc={ao['auc']:.4f};p2={ao['p2']:.4f}"])
    # T_adapt sensitivity (warmup variant)
    for t_adapt in tadapts:
        res = score_grid_fused(t_adapt, True, seeds, **grid_kw)
        kp, _ = select(res)
        rows.append([
            f"tadapt_{int(t_adapt)}", f"a={kp['alpha']};g={kp['gamma']}",
            f"n_eff={kp['n_eff']:.0f};auc={kp['auc']:.4f};p2={kp['p2']:.4f}"])
    # smoke writes its own stub so a CI run never clobbers the full
    # grid's recorded looped-vs-fused wall clock in knee.json
    emit(rows, ["name", "value", "derived"],
         "knee_smoke" if args.smoke else "knee")
    return rows


if __name__ == "__main__":
    main(argv=sys.argv[1:])
