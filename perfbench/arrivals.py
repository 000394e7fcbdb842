"""The one general traffic generator: a traffic file's parameters and a
seed in, an open-loop request schedule out.

Every seed gets the same multiset of inter-arrival gaps, tenant ranks
and tenant ceilings, drawn once from a fixed generator; the seed decides
their order, which prompts are sent and which tenant is hot when. So the
offered work is the same in every run and the seed only reorders it.
The warm-up segment and the measured window are drawn separately, each
rescaled to its exact length, so the window always holds
``round(rate * seconds)`` requests.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from perfbench import data

# Fixed generator for the shared multisets (gaps, ranks, ceilings).
_SHAPE_SEED = 20260416


@dataclasses.dataclass(frozen=True)
class Schedule:
    """An open-loop schedule: request i is due at ``due[i]`` seconds
    after the schedule starts, carries test prompt ``prompt[i]`` and
    belongs to tenant ``tenant[i]``."""

    due: np.ndarray          # (N,) f64 seconds, non-decreasing
    prompt: np.ndarray       # (N,) i64 index into the test split
    tenant: np.ndarray       # (N,) i32 (all 0 without tenants)
    window_open: float       # seconds: end of warm-up
    window_close: float      # seconds: end of the measured window
    ceilings: Optional[np.ndarray] = None   # (T,) f32 per-tenant $/request

    @property
    def n(self) -> int:
        return int(self.due.shape[0])

    def in_window(self) -> np.ndarray:
        return (self.due >= self.window_open) & (self.due < self.window_close)


def _gaps(arrivals: dict, n: int, length: float, rng) -> np.ndarray:
    """n inter-arrival gaps summing to ``length``: a fixed multiset of
    the named process, in the order ``rng`` gives it."""
    base = np.random.default_rng([_SHAPE_SEED, n])
    process = arrivals["process"]
    if process == "poisson":
        g = base.exponential(1.0, n)
    elif process == "gamma":
        shape = 1.0 / float(arrivals["cv"]) ** 2
        g = base.gamma(shape, 1.0, n)
    else:
        raise ValueError(f"unknown arrival process {process!r}")
    g = g * (length / g.sum())
    return rng.permutation(g)


def _segment(arrivals: dict, start: float, length: float, rng):
    """Arrival times in [start, start + length)."""
    n = int(round(float(arrivals["rate_per_s"]) * length))
    g = _gaps(arrivals, n, length, rng)
    return start + np.cumsum(g) - g[0]


def zipf_weights(T: int, exponent: float) -> np.ndarray:
    return data.normalized_weights(
        1.0 / np.arange(1, T + 1, dtype=np.float64) ** exponent, T)


def ceilings(tenants: dict, rng) -> np.ndarray:
    """T ceilings log-uniform between the configured bounds: the T
    quantiles, assigned to tenants in the order ``rng`` gives."""
    T = int(tenants["count"])
    lo, hi = np.log(tenants["ceiling_min"]), np.log(tenants["ceiling_max"])
    q = np.exp(lo + (hi - lo) * (np.arange(T) + 0.5) / T)
    return rng.permutation(q).astype(np.float32)


def schedule(traffic: dict, config: dict, seed: int, seconds: float,
             n_prompts: int) -> Schedule:
    """The whole run's schedule: ``traffic["warmup_s"]`` of warm-up
    traffic straight into ``seconds`` of measured window."""
    rng = np.random.default_rng(seed)
    warm = float(traffic["warmup_s"])
    arr = traffic["arrivals"]
    due = np.concatenate([_segment(arr, 0.0, warm, rng),
                          _segment(arr, warm, float(seconds), rng)])
    n = due.shape[0]
    reps = -(-n // n_prompts)
    prompt = np.concatenate(
        [rng.permutation(n_prompts) for _ in range(reps)])[:n]
    tenant = np.zeros(n, np.int32)
    ceil = None
    tcfg = config.get("tenants")
    if tcfg:
        T = int(tcfg["count"])
        ceil = ceilings(tcfg, rng)
        mix = traffic["tenant_mix"]
        base = np.random.default_rng([_SHAPE_SEED, n, T])
        ranks = rng.permutation(base.choice(
            T, size=n, p=zipf_weights(T, float(mix["zipf_exponent"]))))
        epoch = (due // float(mix["hot_permute_s"])).astype(np.int64)
        perms = np.stack([rng.permutation(T)
                          for _ in range(int(epoch.max()) + 1)])
        tenant = perms[epoch, ranks].astype(np.int32)
    return Schedule(due=due, prompt=prompt, tenant=tenant,
                    window_open=warm, window_close=warm + float(seconds),
                    ceilings=ceil)
