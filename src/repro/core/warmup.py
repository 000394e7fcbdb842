"""Offline-to-online warm-start priors (§3.4, Eqs. 10-12).

Offline sufficient statistics (A_off, b_off) fitted on historical
prompt-reward data are scaled to a target pseudo-observation count n_eff
and regularised with a mean-preserving correction so that
A^{-1} b ~= theta_off at the desired confidence level.
"""
from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp

from repro.core.linucb import HIGHEST
from repro.core.types import ArmPrior, HyperParams, RouterConfig, RouterState

Array = jax.Array


def fit_offline_prior(xs: Array, rs: Array, lambda0: float = 1.0) -> ArmPrior:
    """Ridge sufficient statistics from offline (context, reward) pairs for
    one arm: A_off = lambda0*I + X^T X, b_off = X^T r."""
    d = xs.shape[-1]
    A = lambda0 * jnp.eye(d, dtype=jnp.float32) + jnp.matmul(
        xs.T, xs, precision=HIGHEST)
    b = jnp.matmul(xs.T, rs, precision=HIGHEST)
    return ArmPrior(A_off=A.astype(jnp.float32), b_off=b.astype(jnp.float32))


def scale_prior(cfg: RouterConfig, hp: HyperParams, prior: ArmPrior,
                n_eff: float):
    """Eqs. 10-12.

      s   = n_eff / A_off[d-1, d-1]          (bias-direction precision mass)
      A   = s * A_off + lambda0 * I
      b   = s * b_off + lambda0 * theta_off   (mean-preserving correction)

    ``hp`` supplies lambda0 — a traced hyper leaf, so warm starts compose
    inside jitted programs (sweep condition edits, scenario AddArm).
    """
    d = cfg.d
    assert prior.A_off.shape == (d, d), prior.A_off.shape
    mass = prior.A_off[d - 1, d - 1]
    s = n_eff / jnp.maximum(mass, 1e-12)
    theta_off = jnp.linalg.solve(prior.A_off, prior.b_off)
    A = s * prior.A_off + hp.lambda0 * jnp.eye(d, dtype=jnp.float32)
    b = s * prior.b_off + hp.lambda0 * theta_off
    return A, b


def apply_warmup(
    cfg: RouterConfig,
    state: RouterState,
    priors: Sequence[ArmPrior | None],
    n_eff: float,
) -> RouterState:
    """Load scaled offline priors into every arm slot that has one."""
    A, A_inv, b, theta = state.A, state.A_inv, state.b, state.theta
    for k, prior in enumerate(priors):
        if prior is None:
            continue
        A_k, b_k = scale_prior(cfg, state.hyper, prior, n_eff)
        Ainv_k = jnp.linalg.inv(A_k)
        A = A.at[k].set(A_k)
        A_inv = A_inv.at[k].set(Ainv_k)
        b = b.at[k].set(b_k)
        theta = theta.at[k].set(jnp.matmul(Ainv_k, b_k, precision=HIGHEST))
    import dataclasses

    return dataclasses.replace(state, A=A, A_inv=A_inv, b=b, theta=theta)


def t_adapt_to_n_eff(t_adapt: float, gamma: float) -> float:
    """Appendix A, Eq. 13 inverted: n_eff = (gamma^{-T} - 1) / (1 - gamma),
    -> T as gamma -> 1 (L'Hopital)."""
    if gamma >= 1.0:
        return float(t_adapt)
    return float((gamma ** (-t_adapt) - 1.0) / (1.0 - gamma))


def n_eff_to_t_adapt(n_eff: float, gamma: float) -> float:
    """Appendix A, Eq. 13: T_adapt = -log(n_eff (1-gamma) + 1) / log(gamma)."""
    if gamma >= 1.0:
        return float(n_eff)
    import math

    return -math.log(n_eff * (1.0 - gamma) + 1.0) / math.log(gamma)
