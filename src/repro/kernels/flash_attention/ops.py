"""jit'd wrapper: layout handling + padding for the flash-attention kernel.

Pads head_dim to a multiple of 128 (MXU lanes) and sequence lengths to the
block size, then flattens (B, H) for the kernel grid.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.flash_attention.kernel import flash_attention_bh


def _pad_to(x, axis, mult):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x, n
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), n


@functools.partial(
    jax.jit,
    static_argnames=("mode", "window", "block_q", "block_kv", "interpret",
                     "pad_head_dim"),
)
def flash_attention(
    q, k, v, q_pos=None, k_pos=None, *, mode: str = "causal", window: int = 0,
    block_q: int = 128, block_kv: int = 128, interpret: bool | None = None,
    pad_head_dim: int = 128,
):
    """q (B,S,H,hd), k/v (B,T,KV,hd) -> (B,S,H,hd).

    Assumes contiguous positions starting at 0 (prefill); q_pos/k_pos args
    accepted for interface parity with the chunked XLA path.
    """
    if interpret is None:
        interpret = interpret_mode()
    B, S, H, hd = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV

    qp, _ = _pad_to(q, 3, pad_head_dim)
    kp, _ = _pad_to(k, 3, pad_head_dim)
    vp, _ = _pad_to(v, 3, pad_head_dim)
    qp, S0 = _pad_to(qp, 1, block_q)
    kp, T0 = _pad_to(kp, 1, block_kv)
    vp, _ = _pad_to(vp, 1, block_kv)
    # padded key positions must never win: causal mask handles q padding;
    # key padding is masked because padded k_pos > any valid q_pos in
    # causal/sliding mode. For 'full' mode we require no T padding.
    if mode == "full":
        assert kp.shape[1] == T, "full mode requires T % block_kv == 0"

    hdp = qp.shape[-1]
    Sp, Tp = qp.shape[1], kp.shape[1]
    q2 = qp.transpose(0, 2, 1, 3).reshape(B * H, Sp, hdp)
    k2 = kp.transpose(0, 2, 1, 3).reshape(B * KV, Tp, hdp)
    v2 = vp.transpose(0, 2, 1, 3).reshape(B * KV, Tp, hdp)

    out = flash_attention_bh(
        q2, k2, v2, groups=G, num_q_heads=H, mode=mode, window=window,
        block_q=min(block_q, Sp), block_kv=min(block_kv, Tp),
        interpret=interpret, scale=1.0 / float(hd) ** 0.5,
    )
    out = out.reshape(B, H, Sp, hdp).transpose(0, 2, 1, 3)
    return out[:, :S0, :, :hd]
