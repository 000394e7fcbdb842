"""Timing-based autotune for kernel blocking knobs.

The ``linucb_score`` kernel tiles requests in rows of ``block_r``; the
best tile is a function of problem shape and host (MXU tiling on TPU,
cache lines under interpret mode on CPU), not something a static default
can pin. ``autotune_block_r`` times each candidate on synthetic operands
of the real shape and returns the fastest; ``best_block_r`` memoises the
winner per (R, d, K, interpret) so serving paths pay the sweep once.

Timing-based tuning is inherently host-local: winners are NOT part of
the numerical contract (every ``block_r`` returns identical scores — the
ragged-batch padding in ``linucb_score_blocked`` guarantees it) and the
sweep stays out of jitted code. benchmarks/bench_latency.py records the
candidate table to ``fused_step.json`` so regressions in the blocking
heuristic show up in CI artifacts.
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.linucb_score.ops import linucb_score

BLOCK_R_CANDIDATES = (32, 64, 128, 256)


def _time(fn, repeats: int) -> float:
    """Best-of-``repeats`` wall clock of ``fn()`` (jax-blocking)."""
    fn()  # warm: compile outside the timed region
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def autotune_block_r(
    R: int,
    d: int,
    K: int,
    *,
    interpret: bool | None = None,
    repeats: int = 3,
    candidates=BLOCK_R_CANDIDATES,
):
    """Time the score kernel at each row-tile candidate on synthetic
    operands of shape ((R, d) x K arms); ``interpret=None`` leaves the
    choice to ``kernels.interpret_mode``. Returns (best_block_r,
    {block_r: seconds}). Candidates larger than R collapse to the same
    clamped tile; they are timed anyway so the table stays complete."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(R, d)), jnp.float32)
    theta = jnp.asarray(rng.normal(size=(K, d)), jnp.float32)
    m = rng.normal(size=(K, d, d))
    ainv = jnp.asarray(
        np.einsum("kij,klj->kil", m, m) / d + np.eye(d)[None], jnp.float32)
    pen = jnp.asarray(rng.uniform(size=(K,)), jnp.float32)
    infl = jnp.ones((K,), jnp.float32)
    timings = {}
    for br in candidates:
        timings[int(br)] = _time(
            lambda br=br: linucb_score(
                x, theta, ainv, pen, infl, 0.01,
                block_r=int(br), interpret=interpret),
            repeats,
        )
    best = min(timings, key=timings.get)
    return best, timings


@functools.lru_cache(maxsize=32)
def best_block_r(
    R: int, d: int, K: int, *, interpret: bool | None = None
) -> int:
    """The memoised autotune winner for one problem shape."""
    best, _ = autotune_block_r(R, d, K, interpret=interpret)
    return best
