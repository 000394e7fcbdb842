"""jit'd wrapper for the fused step megakernel: pads (B, d) to
lane-friendly shapes, packs the traced hyper/pacer scalars into the 2-D
operand rows the kernel expects, and slices the padding back off.

Zero-padding is exact for every phase: padded context columns contribute
zero to the quadratic forms, outer products and matvecs (so sliced stats
match the unpadded computation bit-for-bit), and padded request rows are
never entered by the update loop (``num_valid`` is the real B).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.linucb_step.kernel import linucb_step_blocked


@functools.partial(
    jax.jit, static_argnames=("dt_max", "interpret", "pad_d", "pad_b")
)
def linucb_step(
    A, A_inv, b, theta,    # (K,d,d), (K,d,d), (K,d), (K,d)
    last_upd,              # (K,) i32
    X,                     # (B, d) contexts
    rewards, costs,        # (B, K) environment matrices
    noise,                 # (B, K) pre-drawn tiebreak noise
    cand,                  # (K,) bool hard-ceiling candidate mask
    pen, infl,             # (K,) penalty / staleness-inflation vectors
    alpha, gamma, eta, alpha_ema, lambda_bar,  # traced hyper scalars
    lam, c_ema, budget,    # traced pacer scalars
    t_sel,                 # scalar i32: post-select clock (t + B)
    force_arm,             # scalar i32: forced-exploration target (>= 0)
    forced,                # (B,) bool forced-override mask
    *,
    dt_max: int = 4096,
    interpret: bool | None = None,
    pad_d: int = 32,
    pad_b: int = 8,
):
    """One fused step-batch on raw state leaves.

    Returns (A', A_inv', b', theta', last_upd' (K,) i32, arms (B,) i32,
    r (B,), c (B,), lam', c_ema'). Every hyper/pacer scalar is a traced
    operand (DESIGN.md §9): new values — including (alpha, gamma) stacks
    under the fabric's vmap axis — re-enter the same compiled kernel.
    """
    if interpret is None:
        interpret = interpret_mode()
    B, d = X.shape
    K = b.shape[0]
    pd = (-d) % pad_d
    pb = (-B) % pad_b
    if pd:
        A = jnp.pad(A, [(0, 0), (0, pd), (0, pd)])
        A_inv = jnp.pad(A_inv, [(0, 0), (0, pd), (0, pd)])
        b = jnp.pad(b, [(0, 0), (0, pd)])
        theta = jnp.pad(theta, [(0, 0), (0, pd)])
        X = jnp.pad(X, [(0, 0), (0, pd)])
    if pb:
        X = jnp.pad(X, [(0, pb), (0, 0)])
        rewards = jnp.pad(rewards, [(0, pb), (0, 0)])
        costs = jnp.pad(costs, [(0, pb), (0, 0)])
        noise = jnp.pad(noise, [(0, pb), (0, 0)])
        forced = jnp.pad(forced, [(0, pb)])

    f32 = functools.partial(jnp.asarray, dtype=jnp.float32)
    hypf = jnp.stack([
        f32(alpha), f32(gamma), f32(eta), f32(alpha_ema), f32(lambda_bar),
        jnp.float32(0.0), jnp.float32(0.0), jnp.float32(0.0),
    ])[None, :]                                            # (1, 8)
    ints = jnp.stack([
        jnp.asarray(t_sel, jnp.int32), jnp.asarray(force_arm, jnp.int32),
    ])[None, :]                                            # (1, 2)
    pacer = jnp.stack([
        f32(lam), f32(c_ema), f32(budget), jnp.float32(0.0),
    ])[None, :]                                            # (1, 4)

    (A2, Ainv2, b2, theta2, lu2, arms, rc, pacer2) = linucb_step_blocked(
        f32(A), f32(A_inv), f32(b), f32(theta),
        jnp.asarray(last_upd, jnp.int32)[None, :],
        f32(X), f32(rewards), f32(costs), f32(noise),
        forced.astype(jnp.int32)[:, None],
        cand.astype(jnp.float32)[None, :],
        f32(pen)[None, :], f32(infl)[None, :],
        hypf, ints, pacer,
        num_valid=B, dt_max=dt_max, interpret=interpret,
    )
    if pd:
        A2 = A2[:, :d, :d]
        Ainv2 = Ainv2[:, :d, :d]
        b2 = b2[:, :d]
        theta2 = theta2[:, :d]
    return (A2, Ainv2, b2, theta2, lu2[0], arms[:B, 0],
            rc[:B, 0], rc[:B, 1], pacer2[0, 0], pacer2[0, 1])
