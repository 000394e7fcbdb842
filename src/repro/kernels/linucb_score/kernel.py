"""Pallas TPU kernel for batched ParetoBandit UCB scoring (Eq. 2).

The paper's routing hot path: for a batch of request contexts, score every
arm s_a = theta_a.x + alpha*sqrt(x^T A_a^{-1} x / infl_a) - pen_a. At
gateway QPS the request batch is the long axis; the kernel tiles requests
(rows) and keeps all K arms' (d x d) inverses resident in VMEM
(K<=8, d<=128 -> 512 KB f32 worst case). Each arm's quadratic form is one
(br x d) x (d x d) MXU matmul plus an elementwise reduce.

``alpha`` is a (1, 1) scalar *operand*, not a trace constant (DESIGN.md
§9): hyper-parameters are data, so one compiled kernel serves every
exploration coefficient — including a whole (α, γ) grid batched over the
sweep fabric's flattened (condition x seed) vmap axis.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Mosaic runs an unannotated f32 dot below f32 precision (see
# kernels/linucb_step/kernel.py); the scores are held to 1e-4 of jnp.
HIGHEST = jax.lax.Precision.HIGHEST


def _score_kernel(
    x_ref,      # (br, d)
    theta_ref,  # (K, d)
    ainv_ref,   # (K, d, d)
    pen_ref,    # (1, K)  (lambda_c + lam) * c_tilde
    infl_ref,   # (1, K)  max(gamma^dt, 1/V_max)
    alpha_ref,  # (1, 1)  UCB exploration coefficient (traced hyper leaf)
    o_ref,      # (br, K)
    *, num_arms: int,
):
    x = x_ref[...].astype(jnp.float32)                     # (br, d)
    theta = theta_ref[...].astype(jnp.float32)             # (K, d)
    alpha = alpha_ref[0, 0].astype(jnp.float32)
    exploit = jax.lax.dot_general(
        x, theta, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=HIGHEST,
    )                                                      # (br, K)
    cols = []
    for a in range(num_arms):                              # K static, small
        t = jax.lax.dot_general(
            x, ainv_ref[a].astype(jnp.float32),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
            precision=HIGHEST,
        )                                                  # (br, d)
        q = jnp.maximum((t * x).sum(axis=1), 0.0)          # (br,)
        cols.append(q)
    quad = jnp.stack(cols, axis=1)                         # (br, K)
    v = quad / infl_ref[0][None, :]
    scores = exploit + alpha * jnp.sqrt(v) - pen_ref[0][None, :]
    o_ref[...] = scores.astype(o_ref.dtype)


def linucb_score_blocked(
    x: jax.Array,      # (R, d)
    theta: jax.Array,  # (K, d)
    ainv: jax.Array,   # (K, d, d)
    pen: jax.Array,    # (1, K)
    infl: jax.Array,   # (1, K)
    alpha: jax.Array,  # (1, 1)
    *,
    block_r: int = 256,
    interpret: bool = False,
):
    R, d = x.shape
    K = theta.shape[0]
    block_r = max(1, min(block_r, R))
    # Ragged batches (a partial gateway block, R not a block multiple)
    # are padded up to the block boundary and sliced back off: padded
    # rows score garbage in their own lanes only, so the first R rows
    # are untouched.
    pr = (-R) % block_r
    if pr:
        x = jnp.pad(x, [(0, pr), (0, 0)])
    Rp = R + pr
    kernel = functools.partial(_score_kernel, num_arms=K)
    out = pl.pallas_call(
        kernel,
        grid=(Rp // block_r,),
        in_specs=[
            pl.BlockSpec((block_r, d), lambda i: (i, 0)),
            pl.BlockSpec((K, d), lambda i: (0, 0)),
            pl.BlockSpec((K, d, d), lambda i: (0, 0, 0)),
            pl.BlockSpec((1, K), lambda i: (0, 0)),
            pl.BlockSpec((1, K), lambda i: (0, 0)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_r, K), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((Rp, K), jnp.float32),
        interpret=interpret,
    )(x, theta, ainv, pen, infl, alpha)
    return out[:R]
