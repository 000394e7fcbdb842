"""Pure-jnp oracle for the fused step megakernel.

Mirrors ``kernel._step_kernel`` op for op — same ``dot_general``
contractions, same one-hot feedback gather, same ``fori_loop`` update
order, same block-final theta refresh — so the interpret-mode kernel is
BITWISE identical to this reference (pinned in tests/test_kernels.py).
The repo's semantic oracle remains ``router.step_batch`` on the jnp
backend; this file exists to pin the kernel's exact arithmetic.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.linucb_step.kernel import (
    GAMMA_FLOOR, HIGHEST, HYP_AEMA, HYP_ALPHA, HYP_ETA, HYP_GAMMA, HYP_LBAR,
    NEG_INF,
)


def linucb_step_ref(
    A, A_inv, b, theta, last_upd,      # stats: (K,d,d) x2, (K,d) x2, (1,K)
    x, rewards, costs, noise, forced,  # block: (Bp,d), (Bp,K) x3, (Bp,1)
    cand, pen, infl, hypf, ints, pacer,  # (1,K) x3, (1,8), (1,2), (1,4)
    *, num_valid: int, dt_max: int,
):
    """Same operands and returns as ``kernel.linucb_step_blocked``."""
    K, d = b.shape
    x = x.astype(jnp.float32)
    theta = theta.astype(jnp.float32)
    alpha = hypf[0, HYP_ALPHA].astype(jnp.float32)
    exploit = jax.lax.dot_general(
        x, theta, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=HIGHEST,
    )
    cols = []
    for a in range(K):
        t = jax.lax.dot_general(
            x, A_inv[a].astype(jnp.float32),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
            precision=HIGHEST,
        )
        q = jnp.maximum((t * x).sum(axis=1), 0.0)
        cols.append(q)
    quad = jnp.stack(cols, axis=1)
    v = quad / infl[0][None, :]
    scores = exploit + alpha * jnp.sqrt(v) - pen[0][None, :]

    masked = jnp.where(cand[0][None, :] > 0.0, scores + noise, NEG_INF)
    arms = jnp.argmax(masked, axis=1).astype(jnp.int32)
    farm = ints[0, 1]
    arms = jnp.where(forced[..., 0] > 0, farm, arms)

    onehot = (jax.lax.broadcasted_iota(jnp.int32, masked.shape, 1)
              == arms[:, None]).astype(jnp.float32)
    r_all = (rewards.astype(jnp.float32) * onehot).sum(axis=1)
    c_all = (costs.astype(jnp.float32) * onehot).sum(axis=1)
    rc = jnp.stack([r_all, c_all], axis=1)

    t_sel = ints[0, 0]
    gamma = jnp.clip(hypf[:, HYP_GAMMA:HYP_GAMMA + 1].astype(jnp.float32),
                     GAMMA_FLOOR, 1.0)
    eta = hypf[:, HYP_ETA:HYP_ETA + 1].astype(jnp.float32)
    a_ema = hypf[:, HYP_AEMA:HYP_AEMA + 1].astype(jnp.float32)
    lambda_bar = hypf[:, HYP_LBAR:HYP_LBAR + 1].astype(jnp.float32)
    budget = pacer[:, 2:3].astype(jnp.float32)

    def body(i, carry):
        A, A_inv, b, lu, lam, c_ema = carry
        arm = arms[i]
        xi = jax.lax.dynamic_slice_in_dim(x, i, 1)
        rc_i = jax.lax.dynamic_slice_in_dim(rc, i, 1)
        dt = jnp.clip(t_sel - lu[0, arm], 0, dt_max)
        g = jnp.power(gamma, jnp.full((1, 1), dt, jnp.int32)
                      .astype(jnp.float32))
        A_a = A[arm] * g + xi.T * xi
        Ainv_a = A_inv[arm] / g
        Ax = jax.lax.dot_general(
            xi, Ainv_a, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=HIGHEST,
        )
        denom = 1.0 + (xi * Ax).sum(axis=1, keepdims=True)
        Ainv_a = Ainv_a - (Ax.T * Ax) / denom
        b_a = jax.lax.dynamic_slice_in_dim(b, arm, 1) * g + rc_i[:, 0:1] * xi
        A = A.at[arm].set(A_a)
        A_inv = A_inv.at[arm].set(Ainv_a)
        b = jax.lax.dynamic_update_slice_in_dim(b, b_a, arm, 0)
        lu = lu.at[0, arm].set(t_sel)
        c_ema = (1.0 - a_ema) * c_ema + a_ema * rc_i[:, 1:2]
        lam = jnp.clip(lam + eta * (c_ema / budget - 1.0), 0.0, lambda_bar)
        return A, A_inv, b, lu, lam, c_ema

    A, A_inv, b, last_upd, lam, c_ema = jax.lax.fori_loop(
        0, num_valid, body,
        (A.astype(jnp.float32), A_inv.astype(jnp.float32),
         b.astype(jnp.float32), last_upd,
         pacer[:, 0:1].astype(jnp.float32), pacer[:, 1:2].astype(jnp.float32)))
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 4), 1)
    pacer_out = jnp.where(
        lane == 0, lam, jnp.where(lane == 1, c_ema,
                                  jnp.where(lane == 2, budget, 0.0)))

    theta_out = jnp.concatenate([
        jax.lax.dot_general(
            b[a:a + 1], A_inv[a], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=HIGHEST,
        )
        for a in range(K)
    ])

    return (A, A_inv, b, theta_out, last_upd, arms[:, None], rc, pacer_out)
