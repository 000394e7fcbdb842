"""Pure-jnp oracle for the batched UCB scoring kernel (= Eq. 2 vmapped)."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.linucb_score.kernel import HIGHEST


def linucb_score_ref(x, theta, ainv, pen, infl, alpha):
    """x (R,d), theta (K,d), ainv (K,d,d), pen/infl (K,) -> (R,K)."""
    exploit = jnp.matmul(x, theta.T, precision=HIGHEST)     # (R, K)
    t = jnp.einsum("rd,kde->rke", x, ainv, precision=HIGHEST)
    quad = jnp.maximum(
        jnp.einsum("rke,re->rk", t, x, precision=HIGHEST), 0.0)
    v = quad / infl[None, :]
    return exploit + alpha * jnp.sqrt(v) - pen[None, :]
