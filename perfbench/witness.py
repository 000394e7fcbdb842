#!/usr/bin/env python3
"""Why the gateway cells do not prove correct: a witness on the CPU.

    JAX_PLATFORMS=cpu python3 perfbench/witness.py --seed 1

Drives the program's own select and update programs over a long stream
in the order a gateway at ~8,000 requests/s takes (80-row blocks, each
block's feedback folded 250 blocks later, as a 2.5 s judge delay gives),
in two precisions in lockstep: the state as the program makes it
(float32) and the same state with every float leaf in float64. Both
take the float32 program's arms and dispatch history, and so does the
plain float64 reference. Every ``--blocks // 12`` blocks it prints, per
precision, the worst arm's relative gap of the cached A^-1 and of the
inverse of the program's own A from the reference's A^-1, the widest gap
of a chosen arm below the reference's best, and the asymmetry of A^-1.
"""
from __future__ import annotations

import argparse
import dataclasses
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(os.path.dirname(HERE), "src"),
                os.path.dirname(HERE)]


def _rel(got, want) -> float:
    return max(float(np.linalg.norm(got[k] - want[k])
                     / np.linalg.norm(want[k])) for k in range(len(want)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rows", type=int, default=80)
    ap.add_argument("--lag", type=int, default=250)
    ap.add_argument("--blocks", type=int, default=3000)
    args = ap.parse_args(argv)
    import jax

    jax.config.update("jax_enable_x64", True)
    import jax.numpy as jnp

    from perfbench import data, registry
    from perfbench.reference import algo1
    from repro.core import evaluate, router
    from repro.core.types import SELECT_LEAVES, HyperParams, RouterConfig

    config = registry.config_file("paper3")
    train, _, test = data.for_config(config)
    cfg = RouterConfig(d=config["d"], max_arms=config["max_arms"],
                       hyper=HyperParams(alpha=config["alpha"],
                                         gamma=config["gamma"]))
    budget, K = config["budget_per_request"], test.k
    priors = evaluate.fit_warmup_priors(cfg, train)
    st32 = jax.tree.map(lambda leaf: leaf[0], evaluate.make_states(
        cfg, test, budget, seeds=(args.seed,), priors=priors,
        n_eff=config["n_eff"]))
    st64 = jax.tree.map(lambda leaf: leaf.astype(jnp.float64)
                        if leaf.dtype == jnp.float32 else leaf, st32)
    states = {"f32": st32, "f64": st64}
    dtypes = {"f32": jnp.float32, "f64": jnp.float64}
    select = router.jit_select_batch(cfg.statics)
    update = router.jit_update_batch(cfg.statics)
    hp = algo1.Hyper(alpha=config["alpha"], gamma=config["gamma"])
    ref = algo1.warm_router(train.contexts, train.rewards,
                            config["max_arms"], config["n_eff"], [budget],
                            hp)
    pf = algo1.portfolio(test.prices_per_req, test.prices_per_1k,
                         config["max_arms"], hp)
    rng = np.random.default_rng(args.seed)
    worst = {"f32": 0.0, "f64": 0.0}
    pending = []
    for j in range(args.blocks):
        p = rng.integers(0, test.n, args.rows)
        X = test.contexts[p]
        s, cand = algo1.scores(ref, pf, X, np.full(args.rows,
                                                   ref.pacers.lam[0]), hp)
        arms = None
        for name in ("f32", "f64"):
            dec, states[name] = select(states[name],
                                       jnp.asarray(X, dtypes[name]))
            chosen = np.asarray(dec.arms)
            worst[name] = max(worst[name], float(np.max(
                algo1.arm_gaps(s, cand, chosen))))
            arms = chosen if arms is None else arms
        # one dispatch history: the float32 program's
        states["f64"] = dataclasses.replace(states["f64"], **{
            n: getattr(states["f32"], n) for n in SELECT_LEAVES})
        algo1.dispatch(ref, arms)
        pending.append((p, arms))
        if len(pending) > args.lag:
            p2, a2 = pending.pop(0)
            t_now = int(states["f32"].t)
            for name, dt in dtypes.items():
                states[name] = update(
                    states[name], jnp.asarray(a2, jnp.int32),
                    jnp.asarray(test.contexts[p2], dt),
                    jnp.asarray(test.rewards[p2, a2], dt),
                    jnp.asarray(test.costs[p2, a2], dt))
            algo1.fold_rows(ref, t_now, a2, test.contexts[p2],
                            test.rewards[p2, a2], hp)
            algo1.fold_costs(ref.pacers, test.costs[p2, a2], None, hp)
        if j % max(args.blocks // 12, 1) == 0 or j == args.blocks - 1:
            want = ref.Ainv[:K]
            out = [f"request {int(states['f32'].t)}"]
            for name, st in states.items():
                Ainv = np.asarray(st.A_inv, np.float64)[:K]
                A = np.asarray(st.A, np.float64)[:K]
                asym = max(float(np.linalg.norm(a - a.T) / np.linalg.norm(a))
                           for a in Ainv)
                out.append(f"{name}: A_inv {_rel(Ainv, want):.3e} "
                           f"inv(A) {_rel(np.linalg.inv(A), want):.3e} "
                           f"arm_gap {worst[name]:.3e} asym {asym:.1e}")
            out.append("reference A_inv vs inv(its A) "
                       f"{_rel(want, np.linalg.inv(ref.A[:K])):.3e}")
            print(" | ".join(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
