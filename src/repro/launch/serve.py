"""Serving driver: ``python -m repro.launch.serve [--requests N]``.

Stands up a ParetoBandit-routed portfolio of (reduced) assigned
architectures — one budget arm, one SSM arm, one frontier arm — and
streams synthetic requests through the closed loop via the serving
gateway (DESIGN.md §13): requests enter through the micro-batch
admission window (``--window``), feedback is applied by learner ticks
every ``--publish-every`` windows, and the run ends with the gateway's
telemetry (Prometheus text with ``--prom``) plus an optional state
snapshot (``--snapshot PATH``). ``--dry-run`` lowers the FULL decode
configs on the production mesh instead.
"""
from __future__ import annotations

import argparse
import sys


DEFAULT_ARCHS = ("olmo-1b", "mamba2-370m", "deepseek-67b")
TIERS = ("budget", "mid", "frontier")


def build_server(arch_ids, budget: float, *, full_width=()):
    """The routed portfolio: one ``ServedModel`` per architecture, at its
    SMOKE width unless its id is in ``full_width`` (then its FULL config,
    random weights from the arm's seed), always priced from the FULL
    architecture's active parameters."""
    from repro import configs
    from repro.core.costs import price_from_active_params
    from repro.core.features import fit_pca_whitener, hash_encode_batch
    from repro.core.types import RouterConfig
    from repro.data import make_request_stream
    from repro.serving import PortfolioServer, ServedModel

    corpus = [r["prompt"] for r in make_request_stream(400, seed=7)]
    whitener = fit_pca_whitener(hash_encode_batch(corpus))
    models = []
    for i, a in enumerate(arch_ids):
        cfg = (configs.get_config(a) if a in full_width
               else configs.get_smoke(a))
        pricing = price_from_active_params(
            a, configs.get_config(a).active_params(), mean_req_tokens=600)
        tier = TIERS[min(i, 2)]
        models.append(ServedModel.init(cfg, pricing, tier, seed=i))
        print(f"arm {i}: {cfg.name} @ ${pricing.price_per_1k:.2e}/1k tok "
              f"({tier})")
    return PortfolioServer(models, whitener, budget=budget,
                           router_cfg=RouterConfig(max_arms=8),
                           max_new_tokens=4)


def serve_stream(server, stream, *, window: int, publish_every: int):
    """Gateway loop: admission windows of ``window`` requests; feedback
    is deferred to the learner plane and applied by a learn_tick every
    ``publish_every`` windows (cadence 1 == the synchronous fold)."""
    import numpy as np

    def feed(backlog):
        server.feedback_batch(
            [r.request_id for r in backlog],
            np.asarray([r.arm for r in backlog]),
            np.asarray([r.reward for r in backlog]),
            np.asarray([r.cost for r in backlog]))

    results, backlog = [], []
    for windows, i in enumerate(range(0, len(stream), window), start=1):
        served = server.serve_batch(stream[i:i + window], defer_feedback=True)
        results.extend(served)
        backlog.extend(served)
        if windows % publish_every == 0:
            feed(backlog)
            backlog = []
    if backlog:
        feed(backlog)
    return results


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=60)
    ap.add_argument("--budget", type=float, default=6.6e-4)
    ap.add_argument("--arch", action="append", default=None,
                    help="portfolio member (repeatable); default trio")
    ap.add_argument("--window", type=int, default=8,
                    help="micro-batch admission window size")
    ap.add_argument("--publish-every", type=int, default=1,
                    help="learner tick cadence, in routed windows")
    ap.add_argument("--snapshot", default=None,
                    help="save the final router snapshot here (.npz)")
    ap.add_argument("--prom", action="store_true",
                    help="print the Prometheus telemetry scrape")
    ap.add_argument("--dry-run", action="store_true")
    ap.add_argument("--shape", default="decode_32k")
    args = ap.parse_args(argv)

    if args.dry_run:
        import subprocess
        rc = 0
        for arch in args.arch or DEFAULT_ARCHS:
            rc |= subprocess.call([
                sys.executable, "-m", "repro.launch.dryrun",
                "--arch", arch, "--shape", args.shape])
        raise SystemExit(rc)

    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()

    import numpy as np

    from repro.data import make_request_stream

    server = build_server(args.arch or DEFAULT_ARCHS, args.budget)
    models = [m for m in server.models if m is not None]
    results = serve_stream(
        server, list(make_request_stream(args.requests, seed=11)),
        window=args.window, publish_every=args.publish_every)
    reward = np.mean([r.reward for r in results])
    cost = np.mean([r.cost for r in results])
    traffic = {m.name: 0 for m in models}
    for r in results:
        traffic[r.model] += 1
    print(f"\nserved {len(results)} requests: reward {reward:.3f}, "
          f"cost ${cost:.2e}/req ({cost / args.budget:.2f}x ceiling)")
    print("traffic:", traffic)
    m = server.metrics()
    print(f"lambda_t = {m['lam']:.3f}  snapshot v{m['snapshot_version']:.0f}"
          f"  route p50/p95 = {m['route_p50_us']:.1f}/"
          f"{m['route_p95_us']:.1f} µs/dec"
          f"  pulls = {[round(m[f'pull_rate_{k}'], 3) for k in range(3)]}")
    if args.snapshot:
        snap = server.gateway.save(args.snapshot)
        print(f"snapshot v{snap.version} (t={snap.step}) -> {args.snapshot}")
    if args.prom:
        print(server.prometheus_text())


if __name__ == "__main__":
    main()
