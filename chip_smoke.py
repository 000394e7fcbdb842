#!/usr/bin/env python3
"""Bring-up smoke run: the router's main path, compiled, on one TPU.

    python chip_smoke.py            # one chip: serving, fused step, models
    python chip_smoke.py --chips 4  # only the sweep fabric, 4 chips vs 1

One process drives the chip. It fails (non-zero, no result line) when
JAX finds no TPU, and never falls back to the CPU. Phases:

  * router serving: the paper's 3-arm, d=26 portfolio through
    ``RouterGateway`` (route_block -> enqueue_feedback -> learn_tick) at
    B=8 and B=256 on the jnp, pallas and pallas_fused backends, in
    lockstep. Arms must agree, kernel scores must sit within EQUIV_TOL of
    the jnp oracle, and the Pallas select programs must hold a compiled
    kernel (``tpu_custom_call``), not its interpreter.
  * fused step: ``evaluate.run`` over the 1,824-prompt stream at
    batch_size=256, pallas_fused against jnp: arms, realised traces,
    clock and PRNG key bitwise, pacer and statistics within EQUIV_TOL.
  * model serving: the ``launch/serve.py`` portfolio with OLMo-1B and
    Mamba2-370M at their FULL configs (random weights from seeds) and
    deepseek-67b at smoke width. On the same full-width weights at f32,
    greedy tokens from cached prefill + decode must equal the argmax of
    an uncached forward pass over the same sequence; the served bf16
    path's agreement is reported beside it.
  * fabric (``--chips 4`` only): the paper's (7 ceilings + unconstrained)
    x 20 seeds grid through ``sweep.run_grid`` on 4 devices and on 1;
    bit-identical per condition, and the 4-device outputs sharded over 4.

Every check prints its measured difference. The last line is the JSON
result, printed only when every check held.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")

BLOCKS = 3          # gateway blocks per (backend, B)
MODEL_REQUESTS = 16
MODEL_WINDOW = 8


class Checks:
    """Collects check outcomes; a failed check is never forgotten."""

    def __init__(self):
        self.failed = []

    def __call__(self, name: str, ok: bool, detail: str) -> None:
        print(f"[{'PASS' if ok else 'FAIL'}] {name}: {detail}", flush=True)
        if not ok:
            self.failed.append(name)


def phase_router_serving(check: Checks) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import backend as backend_lib
    from repro.core import evaluate, router, simulator
    from repro.core.costs import BUDGET_MODERATE
    from repro.core.types import RouterConfig
    from repro.serving.gateway import RouterGateway

    env = simulator.make_benchmark(seed=0).test
    names = ("jnp", "pallas", "pallas_fused")
    cfgs = {bk: RouterConfig(backend=bk) for bk in names}
    one = jax.tree.map(
        lambda leaf: leaf[0],
        evaluate.make_states(cfgs["jnp"], env, BUDGET_MODERATE, seeds=(0,)))
    gws = {bk: RouterGateway(cfgs[bk], one) for bk in names}
    print(f"router: K={env.k} arms, d={cfgs['jnp'].d}, price spread "
          f"{env.prices_per_req.max() / env.prices_per_req.min():.0f}x")
    row, rid = 0, 0
    for B in (8, 256):
        for bk in ("pallas", "pallas_fused"):
            sel = router.jit_select_batch(cfgs[bk].statics)
            X0 = jnp.asarray(env.contexts[:B], jnp.float32)
            t0 = time.perf_counter()
            text = sel.lower(gws[bk].live_state, X0).compile().as_text()
            check(f"select_kernel_compiled[{bk},B={B}]",
                  "tpu_custom_call" in text,
                  f"tpu_custom_call in select HLO: "
                  f"{'tpu_custom_call' in text}, compile "
                  f"{time.perf_counter() - t0:.2f}s")
        worst = {bk: 0.0 for bk in names[1:]}
        same_arms = True
        first_s = {}
        for blk in range(BLOCKS):
            X = env.contexts[row:row + B].astype(np.float32)
            R, C = env.rewards[row:row + B], env.costs[row:row + B]
            ids = list(range(rid, rid + B))
            row, rid = row + B, rid + B
            ref = None
            for bk in names:
                gw = gws[bk]
                s = gw.live_state
                dt = s.t - jnp.maximum(s.last_upd, s.last_play)
                sc = backend_lib.get_backend(bk).score(
                    cfgs[bk].statics, s.hyper, s.theta, s.A_inv, s.c_tilde,
                    jnp.asarray(X), dt, s.pacer.lam)
                if bk == "jnp":
                    ref = sc
                else:
                    diff = float(jnp.max(jnp.abs(sc - ref)[:, :env.k]))
                    worst[bk] = max(worst[bk], diff)
                t0 = time.perf_counter()
                res = gw.route_block(ids, X)
                arms = res.arms
                gw.enqueue_feedback(ids, arms, R[np.arange(B), arms],
                                    C[np.arange(B), arms])
                gw.learn_tick()
                jax.block_until_ready(gw.live_state.theta)
                if blk == 0:
                    first_s[bk] = time.perf_counter() - t0
                if bk == "jnp":
                    ref_arms = arms
                else:
                    same_arms &= bool(np.array_equal(arms, ref_arms))
        for bk in names[1:]:
            check(f"score_vs_oracle[{bk},B={B}]",
                  worst[bk] <= backend_lib.EQUIV_TOL,
                  f"max |score - jnp| = {worst[bk]:.3e} "
                  f"(tol {backend_lib.EQUIV_TOL:.0e}) over {BLOCKS} blocks")
        check(f"arms_identical[B={B}]", same_arms,
              f"{BLOCKS} blocks x {B} rows, all backends equal: {same_arms}")
        print(f"router B={B}: first route+learn block (compile included) "
              + ", ".join(f"{k} {v:.2f}s" for k, v in first_s.items()))
    m = gws["pallas_fused"].metrics()
    print(f"router: route p50 {m['route_p50_us']:.1f} us/decision "
          f"(pallas_fused gateway, host clock, includes compile blocks)")


def phase_fused_step(check: Checks) -> None:
    import jax
    import numpy as np

    from repro.core import evaluate, simulator
    from repro.core.backend import EQUIV_TOL
    from repro.core.costs import BUDGET_MODERATE
    from repro.core.types import RouterConfig

    env = simulator.make_benchmark(seed=0).test
    out = {}
    for bk in ("jnp", "pallas_fused"):
        t0 = time.perf_counter()
        res, finals = evaluate.run(RouterConfig(backend=bk), env,
                                   BUDGET_MODERATE, batch_size=256,
                                   return_states=True)
        jax.block_until_ready(finals)
        print(f"fused_step[{bk}]: {env.n} prompts x {res.arms.shape[0]} "
              f"seeds, first call (compile included) "
              f"{time.perf_counter() - t0:.2f}s, mean cost "
              f"{res.mean_cost:.4e} = {res.compliance(BUDGET_MODERATE):.4f}x "
              f"ceiling {BUDGET_MODERATE:.1e}")
        out[bk] = (res, finals)
    (rj, fj), (rf, ff) = out["jnp"], out["pallas_fused"]
    n_diff = int((rj.arms != rf.arms).sum())
    check("fused_arms_bitwise", n_diff == 0,
          f"{n_diff} of {rj.arms.size} decisions differ")
    traces = all(np.array_equal(getattr(rj, n), getattr(rf, n))
                 for n in ("rewards", "costs"))
    check("fused_traces_bitwise", traces,
          f"realised reward/cost traces equal: {traces}")
    clock = (np.array_equal(np.asarray(fj.t), np.asarray(ff.t))
             and np.array_equal(np.asarray(fj.key), np.asarray(ff.key)))
    check("fused_clock_bitwise", clock, f"t and PRNG key equal: {clock}")
    lam_gap = float(np.abs(rj.lams - rf.lams).max())
    pacer_gap = max(
        float(np.abs(np.asarray(fj.pacer.lam) - np.asarray(ff.pacer.lam))
              .max()),
        float(np.abs(np.asarray(fj.pacer.c_ema)
                     - np.asarray(ff.pacer.c_ema)).max()))
    check("fused_pacer", max(lam_gap, pacer_gap) <= EQUIV_TOL,
          f"max |lam trace gap| = {lam_gap:.3e}, final (lam, c_ema) gap "
          f"{pacer_gap:.3e}, bitwise: {lam_gap == 0.0 and pacer_gap == 0.0}")
    for n in ("A", "A_inv", "b", "theta"):
        a, b = np.asarray(getattr(fj, n)), np.asarray(getattr(ff, n))
        gap = float(np.abs(a - b).max())
        rel = float((np.abs(a - b) / (np.abs(a) + 1.0)).max())
        ok = bool(np.allclose(b, a, atol=EQUIV_TOL, rtol=EQUIV_TOL))
        check(f"fused_stats[{n}]", ok,
              f"max abs gap {gap:.3e}, max gap/(|x|+1) {rel:.3e}")


def _uncached_logits(params, tokens, *, cfg):
    """(1, S) tokens -> (S, V) f32 logits of one full forward, no cache."""
    import jax.numpy as jnp

    from repro.models import layers, transformer

    dt = cfg.dtype_jnp
    x = params["embed"].astype(dt)[tokens]
    x, _ = transformer.decoder_stack(params, cfg, x,
                                     jnp.arange(tokens.shape[1]))
    x = layers.apply_norm(cfg.norm, params["final_norm"], x)
    return (x[0] @ transformer.head_weight(params, cfg).astype(dt)).astype(
        jnp.float32)


def phase_model_serving(check: Checks) -> None:
    import jax

    from repro.core.costs import BUDGET_MODERATE
    from repro.data import make_request_stream
    from repro.launch.serve import DEFAULT_ARCHS, build_server, serve_stream

    full = ("olmo-1b", "mamba2-370m")
    t0 = time.perf_counter()
    server = build_server(DEFAULT_ARCHS, BUDGET_MODERATE, full_width=full)
    jax.block_until_ready([m.params for m in server.models if m])
    print(f"models: portfolio built in {time.perf_counter() - t0:.2f}s")
    stream = list(make_request_stream(MODEL_REQUESTS, seed=11))
    t0 = time.perf_counter()
    results = serve_stream(server, stream, window=MODEL_WINDOW,
                           publish_every=1)
    print(f"models: served {len(results)} requests in "
          f"{time.perf_counter() - t0:.2f}s (compile included)")
    check("models_all_served", len(results) == len(stream),
          f"{len(results)} of {len(stream)} requests answered")
    tokens = {m.name: 0 for m in server.models if m}
    for r in results:
        tokens[r.model] += r.tokens_out
    print(f"models: tokens served per arm {tokens}")
    m = server.metrics()
    print(f"models: route p50 {m['route_p50_us']:.1f} us/decision "
          f"(host clock, includes the compile block)")
    prompts = {r["id"]: r["prompt"] for r in stream}
    for model in server.models:
        if model is None or model.cfg.name not in full:
            continue
        mine = [prompts[r.request_id] for r in results
                if r.model == model.name]
        routed = len(mine)
        mine = (mine or [s["prompt"] for s in stream])[:4]
        t0 = time.perf_counter()
        n, bad, gap, margin = _greedy_agreement(
            model, mine, server.max_new_tokens)
        print(f"models: served {model.cfg.dtype} path of {model.cfg.name}: "
              f"{n - bad} of {n} greedy tokens equal the uncached argmax; "
              f"largest uncached logit gap at a differing token {gap:.3e}, "
              f"smallest top-2 margin {margin:.3e}")
        # Exact agreement is a property of the cache machinery, not of
        # bf16 arithmetic: bf16-rounded logits tie and near-tie (margins
        # of a few 1e-3), and two bf16 evaluation orders break such ties
        # differently. The contract is checked on the same full-width
        # weights in f32 with f32 contractions.
        m32 = dataclasses.replace(
            model, cfg=dataclasses.replace(model.cfg, dtype="float32"))
        with jax.default_matmul_precision("highest"):
            n, bad, gap, margin = _greedy_agreement(
                m32, mine, server.max_new_tokens)
        check(f"cached_equals_uncached[{model.cfg.name}]", bad == 0,
              f"f32: {bad} of {n} greedy tokens differ over {len(mine)} "
              f"prompts ({routed} routed here); smallest top-2 margin "
              f"{margin:.3e}; both checks took "
              f"{time.perf_counter() - t0:.2f}s")


def _greedy_agreement(model, prompts, max_new: int):
    """Cached greedy decode (``ServedModel.generate``) against the argmax
    of one uncached forward over the same sequence, teacher-forced.
    Returns (tokens, differing tokens, largest uncached logit gap at a
    differing token, smallest uncached top-2 margin)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.serving.tokenizer import HashTokenizer

    fwd = jax.jit(functools.partial(_uncached_logits, cfg=model.cfg))
    tok = HashTokenizer(model.cfg.vocab_size)
    n = bad = 0
    gap, margin = 0.0, float("inf")
    for prompt in prompts:
        ids = tok.encode(prompt)
        gen = model.generate(ids, max_new)
        window = model.prompt_window(ids)
        seq = np.concatenate([window, gen[:-1]])[None, :]
        logits = np.asarray(fwd(model.params, jnp.asarray(seq)))
        logits = logits[len(window) - 1:]
        want = logits.argmax(-1)
        rows = np.arange(len(gen))
        diff = want != gen
        if diff.any():
            gap = max(gap, float((logits[rows, want]
                                  - logits[rows, gen])[diff].max()))
        top2 = np.sort(logits, axis=-1)[:, -2:]
        margin = min(margin, float((top2[:, 1] - top2[:, 0]).min()))
        n += len(gen)
        bad += int(diff.sum())
    return n, bad, gap, margin


def phase_fabric(check: Checks) -> None:
    import jax
    import numpy as np

    from benchmarks.bench_pareto import BUDGET_SWEEP
    from benchmarks.common import SEEDS, benchmark, condition_kwargs
    from repro.core import sweep

    env = benchmark().test
    cfg, kw = condition_kwargs("pareto", env)
    budgets = list(BUDGET_SWEEP) + [1.0]
    runs = {}
    for n in (4, 1):
        t0 = time.perf_counter()
        grid, finals = sweep.run_grid(cfg, env, budgets, seeds=SEEDS,
                                      devices=jax.devices()[:n],
                                      return_states=True, **kw)
        jax.block_until_ready(finals)
        spans = {len(leaf.sharding.device_set)
                 for leaf in jax.tree.leaves(finals)}
        print(f"fabric[{n} chips]: {len(budgets)} conditions x "
              f"{len(SEEDS)} seeds x {env.n} steps, first call (compile "
              f"included) {time.perf_counter() - t0:.2f}s, output leaves "
              f"span {sorted(spans)} devices")
        runs[n] = (grid, finals, spans)
    (g4, f4, s4), (g1, f1, _) = runs[4], runs[1]
    check("fabric_sharded_over_4", s4 == {4},
          f"device_set sizes of the 4-chip outputs: {sorted(s4)}")
    bad = [c for c in range(len(budgets))
           if not all(np.array_equal(getattr(g4, n)[c], getattr(g1, n)[c])
                      for n in ("arms", "rewards", "costs", "lams"))]
    check("fabric_bitwise_4_vs_1", not bad,
          f"{len(budgets) - len(bad)} of {len(budgets)} conditions "
          f"bit-identical (arms, rewards, costs, lams)")
    same = all(np.array_equal(np.asarray(a), np.asarray(b))
               for a, b in zip(jax.tree.leaves(f4), jax.tree.leaves(f1)))
    check("fabric_final_states_bitwise", same,
          f"final router states equal leaf for leaf: {same}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sweep fabric, 4 chips against 1")
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(SRC, "repro")):
        print(f"chip_smoke: no repro package under {SRC}", file=sys.stderr)
        return 2
    sys.path[:0] = [SRC, ROOT]
    from repro.compile_cache import enable_compile_cache
    cache = enable_compile_cache()

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found platform "
              f"{dev.platform!r} ({dev.device_kind})", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 1
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}, "
          f"jax {jax.__version__}, compile cache {cache}", flush=True)

    phases = ([phase_fabric] if args.chips == 4 else
              [phase_router_serving, phase_fused_step, phase_model_serving])
    check = Checks()
    for phase in phases:
        name = phase.__name__.removeprefix("phase_")
        t0 = time.perf_counter()
        try:
            phase(check)
        except Exception:  # recorded as a failure; the run exits non-zero
            traceback.print_exc()
            check.failed.append(name)
        print(f"phase {name}: {time.perf_counter() - t0:.2f}s", flush=True)
    if check.failed:
        print(f"chip_smoke: FAILED {check.failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
