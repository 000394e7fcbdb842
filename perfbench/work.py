"""The work the router's programs do, counted from shapes, and the
chip's peaks: the yardstick for roofline shares.

FLOPs follow the algorithm (the scoring and update terms of the cost
model that ``benchmarks/bench_roofline.step_cost_model`` wrote down),
not any implementation. Bytes are the least the work needs: the
statistics read once (and, for an update, written once) and the streams
of contexts, feedback and scores. So a share stays valid when a later
change replaces the kernel that does the work. All arrays are float32
(4 bytes); K counts the arms the work is over.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Tuple

F32 = 4

# Peak bfloat16 FLOP/s and HBM bytes/s per chip, by ``device_kind``.
# Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16,
# 819 GB/s HBM). The router's contractions are float32 at HIGHEST, for
# which no peak is published; the bf16 peak makes the compute bound
# lower than the truth, so a share can only read low, never above 100%.
PEAKS: Dict[str, Tuple[float, float]] = {
    "TPU v5 lite": (197e12, 819e9),
}


def peaks(device_kind: str) -> Tuple[float, float]:
    """(FLOP/s, bytes/s) of one chip; an unknown kind is an error."""
    if device_kind not in PEAKS:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add it "
                       f"to perfbench/work.py PEAKS with its source")
    return PEAKS[device_kind]


def stats_bytes(K: int, d: int) -> int:
    """A, A^-1 (K, d, d), b, theta (K, d), per-arm clocks and costs."""
    return F32 * (2 * K * d * d + 2 * K * d + 3 * K)


def select_work(B: int, K: int, d: int) -> Tuple[float, float]:
    """Eq. 2 scoring of a (B, d) block against K arms: (FLOPs, bytes)."""
    flops = 2 * B * K * d * d + 2 * B * K * d + 5 * B * K
    nbytes = F32 * (K * d * d + K * d + 3 * K      # A^-1, theta, costs
                    + B * d + B * K + B)           # X in, scores, arms out
    return float(flops), float(nbytes)


def update_work(B: int, K: int, d: int) -> Tuple[float, float]:
    """B feedback rows folded into K arms' statistics, with theta
    refreshed once per arm: (FLOPs, bytes)."""
    flops = B * (9 * d * d + 5 * d) + K * 2 * d * d
    nbytes = 2 * stats_bytes(K, d) + F32 * (B * d + 3 * B)
    return float(flops), float(nbytes)


def grid_call_work(E: int, T: int, K: int, d: int) -> Tuple[float, float]:
    """One fabric call: E elements, each a T-step closed loop of a B=1
    select and a one-row update. Each element's statistics are read
    once and written once; each step reads a context and one (reward,
    cost) row per arm."""
    fs, _ = select_work(1, K, d)
    fu, _ = update_work(1, K, d)
    flops = E * T * (fs + fu)
    nbytes = E * (2 * stats_bytes(K, d) + F32 * T * (d + 2 * K + 4))
    return float(flops), float(nbytes)


def least_s(flops: float, nbytes: float, pk: Tuple[float, float]):
    """(least seconds, the bound that sets it)."""
    tc, tb = flops / pk[0], nbytes / pk[1]
    return (tc, "compute") if tc >= tb else (tb, "bandwidth")


@dataclasses.dataclass
class Context:
    """What a per-layer metric reader gets: the cell, its reduced trace,
    the harness's own counts, and the chip's peaks."""

    cell: object
    trace: object
    layer: dict
    peaks: Tuple[float, float]
