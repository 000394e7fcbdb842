"""jit'd wrapper: pads (requests, d, arms) to kernel-friendly shapes and
feeds the Eq. 2 penalty/inflation vectors plus the traced ``alpha``
scalar operand (hyper-parameters are data — DESIGN.md §9)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.linucb_score.kernel import linucb_score_blocked


@functools.partial(
    jax.jit, static_argnames=("block_r", "interpret", "pad_d")
)
def linucb_score(
    x, theta, ainv, pen, infl, alpha, *, block_r: int = 256,
    interpret: bool | None = None, pad_d: int = 32,
):
    """x (R,d), theta (K,d), ainv (K,d,d), pen (K,), infl (K,) -> (R,K).

    ``alpha`` is a traced scalar operand (array or float), so sweeping the
    exploration coefficient re-enters the same compiled kernel. d is
    padded to a lane-friendly multiple (zero-padded contexts leave the
    quadratic form unchanged); R is padded to the row block.
    """
    if interpret is None:
        interpret = interpret_mode()
    R, d = x.shape
    K = theta.shape[0]
    pd = (-d) % pad_d
    pr = (-R) % min(block_r, max(R, 1))
    if pd:
        x = jnp.pad(x, [(0, 0), (0, pd)])
        theta = jnp.pad(theta, [(0, 0), (0, pd)])
        ainv = jnp.pad(ainv, [(0, 0), (0, pd), (0, pd)])
    if pr:
        x = jnp.pad(x, [(0, pr), (0, 0)])
    out = linucb_score_blocked(
        x, theta, ainv, pen[None, :], infl[None, :],
        jnp.asarray(alpha, jnp.float32).reshape(1, 1),
        block_r=block_r, interpret=interpret,
    )
    return out[:R]
