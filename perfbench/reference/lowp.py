"""The control: the reference computed one precision step below what
the configuration states.

The router states float32 with every contraction at
``Precision.HIGHEST``. One step below is ``Precision.HIGH`` for the
contractions (three bfloat16 passes: a = a_hi + a_lo, and
a_hi b_hi + a_hi b_lo + a_lo b_hi accumulated in float32) and bfloat16
for every other float32 result. Both are emulated here with numpy, so
the control reads the same on any host.
"""
from __future__ import annotations

import numpy as np

from perfbench.reference.algo1 import Arith


def bf16(x) -> np.ndarray:
    """Round float32 values to bfloat16 (nearest, ties to even), kept in
    float32."""
    shape = np.shape(x)
    a = np.ascontiguousarray(np.asarray(x, np.float32)).reshape(-1)
    u = a.view(np.uint32).astype(np.uint64)
    u = (u + 0x7FFF + ((u >> 16) & 1)) & 0xFFFF0000
    return u.astype(np.uint32).view(np.float32).reshape(shape)


class Bf16x3(Arith):
    """``Precision.HIGH`` contractions, bfloat16 elsewhere."""

    dtype = np.float32

    def mm(self, a, b):
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        a_hi, b_hi = bf16(a), bf16(b)
        a_lo, b_lo = bf16(a - a_hi), bf16(b - b_hi)
        f = np.float64
        out = (np.matmul(a_hi.astype(f), b_hi.astype(f))
               + np.matmul(a_hi.astype(f), b_lo.astype(f))
               + np.matmul(a_lo.astype(f), b_hi.astype(f)))
        return out.astype(np.float32)

    def rnd(self, x):
        return bf16(x)

    def scalar(self, x: float) -> float:
        return float(bf16(np.float32(x)))
