"""jit'd wrapper for the decode-attention kernel (layout + padding)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.decode_attention.kernel import decode_attention_bkv


@functools.partial(
    jax.jit, static_argnames=("block_kv", "interpret", "pad_head_dim")
)
def decode_attention(
    q, k_cache, v_cache, valid, *, block_kv: int = 512,
    interpret: bool | None = None, pad_head_dim: int = 128,
):
    """q (B,1,H,hd), k/v (B,W,KV,hd), valid (W,) bool -> (B,1,H,hd)."""
    if interpret is None:
        interpret = interpret_mode()
    B, _, H, hd = q.shape
    W, KV = k_cache.shape[1], k_cache.shape[2]
    G = H // KV

    pad_hd = (-hd) % pad_head_dim
    pad_w = (-W) % block_kv
    if pad_hd:
        q = jnp.pad(q, [(0, 0), (0, 0), (0, 0), (0, pad_hd)])
        k_cache = jnp.pad(k_cache, [(0, 0), (0, 0), (0, 0), (0, pad_hd)])
        v_cache = jnp.pad(v_cache, [(0, 0), (0, 0), (0, 0), (0, pad_hd)])
    if pad_w:
        k_cache = jnp.pad(k_cache, [(0, 0), (0, pad_w), (0, 0), (0, 0)])
        v_cache = jnp.pad(v_cache, [(0, 0), (0, pad_w), (0, 0), (0, 0)])
        valid = jnp.concatenate([valid, jnp.zeros((pad_w,), bool)])
    hdp, Wp = hd + pad_hd, W + pad_w

    # (B, 1, H, hd) -> (B*KV, G, hd): group query heads by their kv head
    q2 = q[:, 0].reshape(B, KV, G, hdp).reshape(B * KV, G, hdp)
    k2 = k_cache.transpose(0, 2, 1, 3).reshape(B * KV, Wp, hdp)
    v2 = v_cache.transpose(0, 2, 1, 3).reshape(B * KV, Wp, hdp)
    val2 = jnp.broadcast_to(
        valid.astype(jnp.float32)[None], (B * KV, Wp)
    )

    out = decode_attention_bkv(
        q2, k2, v2, val2, block_kv=min(block_kv, Wp), interpret=interpret,
        scale=1.0 / float(hd) ** 0.5,
    )
    out = out.reshape(B, KV, G, hdp).reshape(B, 1, H, hdp)
    return out[..., :hd]
