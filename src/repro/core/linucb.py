"""LinUCB sufficient-statistic operations (§3.2-§3.3).

These are the O(d^2) primitives of the paper: geometric forgetting as a
scalar multiply on (A, b) and a scalar divide on the cached inverse,
Sherman-Morrison rank-1 updates, and the staleness-inflated UCB variance.

All functions are pure and shape-stable; the router (router.py) composes
them into Algorithm 1. Every function takes the split configuration
(DESIGN.md §9): ``cfg`` supplies the trace statics (only ``dt_max``
here), ``hp`` the traced ``HyperParams`` leaves — so one compiled program
serves every (α, γ, λ_c, ...) operating point.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.types import HyperParams, RouterConfig

Array = jax.Array

# Runtime floor for the traced forgetting factor: gamma is validated to
# (0, 1] at construction time, but a traced leaf can carry any value, so
# the kernel clamps (identity for every valid gamma).
GAMMA_FLOOR = 1e-6

# Every contraction here states full f32. On a TPU v5e the batched score
# einsum at DEFAULT precision strayed up to 3.0e-3 from the f32 kernels
# (the contract is 1e-4); the CPU computes f32 either way.
HIGHEST = jax.lax.Precision.HIGHEST


def forgetting_factor(cfg: RouterConfig, hp: HyperParams, dt: Array) -> Array:
    """gamma^dt with a numerical clamp on the exponent.

    The paper decays the full sufficient statistics (ridge included). For an
    arm idle for very long, gamma^-dt on the cached inverse overflows f32;
    we clamp dt at cfg.dt_max (gamma^4096 ~= 4.6e-6 at gamma=0.997), which
    is far beyond the point where the V_max selection cap (Eq. 9) saturates,
    so routing behaviour is unchanged. Documented in DESIGN.md §4.
    """
    dt = jnp.clip(dt, 0, cfg.dt_max).astype(jnp.float32)
    g = jnp.clip(jnp.asarray(hp.gamma, jnp.float32), GAMMA_FLOOR, 1.0)
    return jnp.power(g, dt)


def decay_statistics(
    cfg: RouterConfig, hp: HyperParams, A: Array, A_inv: Array, b: Array,
    dt: Array,
):
    """Algorithm 1 lines 18-20: batched exponentiation gamma^dt applied to
    one arm's statistics. A_inv scales by 1/gamma^dt — an O(d^2) scalar op.
    """
    g = forgetting_factor(cfg, hp, dt)
    return A * g, A_inv / g, b * g


def sherman_morrison(A_inv: Array, x: Array) -> Array:
    """Rank-1 inverse update: (A + x x^T)^{-1} from A^{-1} in O(d^2)."""
    Ax = jnp.matmul(A_inv, x, precision=HIGHEST)        # (d,)
    denom = 1.0 + jnp.matmul(x, Ax, precision=HIGHEST)
    return A_inv - jnp.outer(Ax, Ax) / denom


def rank1_update(
    cfg: RouterConfig,
    hp: HyperParams,
    A: Array,
    A_inv: Array,
    b: Array,
    x: Array,
    r: Array,
    dt: Array,
):
    """Decay-then-update for the chosen arm (Algorithm 1 lines 18-23).

    Returns (A, A_inv, b, theta).
    """
    A, A_inv, b = decay_statistics(cfg, hp, A, A_inv, b, dt)
    A = A + jnp.outer(x, x)
    A_inv = sherman_morrison(A_inv, x)
    b = b + r * x
    theta = jnp.matmul(A_inv, b, precision=HIGHEST)
    return A, A_inv, b, theta


def ucb_variance(
    cfg: RouterConfig, hp: HyperParams, A_inv: Array, x: Array, dt: Array
) -> Array:
    """Eq. 9: staleness-inflated posterior variance for one arm.

    v_a = x^T A_a^{-1} x / max(gamma^{dt_a}, 1/V_max)
    """
    q = jnp.matmul(x, jnp.matmul(A_inv, x, precision=HIGHEST),
                   precision=HIGHEST)
    q = jnp.maximum(q, 0.0)  # guard tiny negative from f32 round-off
    return q / staleness_inflation(cfg, hp, dt)


def ucb_scores(
    cfg: RouterConfig,
    hp: HyperParams,
    theta: Array,     # (K, d)
    A_inv: Array,     # (K, d, d)
    c_tilde: Array,   # (K,)
    x: Array,         # (d,)
    dt: Array,        # (K,) staleness per arm
    lam: Array,       # scalar dual variable
) -> Array:
    """Eq. 2 scores for every arm (the Pallas linucb_score kernel mirrors
    this math for batched request streams; this is the jnp oracle)."""
    exploit = jnp.matmul(theta, x, precision=HIGHEST)       # (K,)
    v = jax.vmap(lambda Ai, d_: ucb_variance(cfg, hp, Ai, x, d_))(A_inv, dt)
    explore = hp.alpha * jnp.sqrt(v)
    penalty = (hp.lambda_c + lam) * c_tilde
    return exploit + explore - penalty


def staleness_inflation(
    cfg: RouterConfig, hp: HyperParams, dt: Array
) -> Array:
    """Eq. 9 denominator, vectorised: max(gamma^dt, 1/V_max) per arm."""
    return jnp.maximum(forgetting_factor(cfg, hp, dt), 1.0 / hp.v_max)


def ucb_scores_batch(
    cfg: RouterConfig,
    hp: HyperParams,
    theta: Array,     # (K, d)
    A_inv: Array,     # (K, d, d)
    c_tilde: Array,   # (K,)
    X: Array,         # (B, d) block of request contexts
    dt: Array,        # (K,) staleness per arm, shared by the block
    lam: Array,       # scalar dual variable, or (B,) per-request duals
) -> Array:
    """Eq. 2 scores for a block of B contexts against all arms: (B, K).

    The batched jnp oracle of the routing data plane (DESIGN.md §2); the
    Pallas ``linucb_score`` kernel computes the same quantity on TPU. Each
    arm's quadratic form is one (B, d) x (d, d) matmul, so the whole block
    is scored in O(K B d^2) with no per-request dispatch.

    ``lam`` may be a (B,) vector of per-request duals (the tenant plane
    gathers each request's tenant lambda, §15). Only the cost penalty
    depends on lambda and it is elementwise, so row b of the vector path
    is bit-identical to scoring the whole block under scalar ``lam[b]``.
    """
    exploit = jnp.matmul(X, theta.T, precision=HIGHEST)     # (B, K)
    t = jnp.einsum("bd,kde->bke", X, A_inv, precision=HIGHEST)
    quad = jnp.maximum(
        jnp.einsum("bke,be->bk", t, X, precision=HIGHEST), 0.0)
    v = quad / staleness_inflation(cfg, hp, dt)[None, :]
    if jnp.ndim(lam) == 1:
        penalty = (hp.lambda_c + lam)[:, None] * c_tilde[None, :]   # (B, K)
        return exploit + hp.alpha * jnp.sqrt(v) - penalty
    penalty = (hp.lambda_c + lam) * c_tilde
    return exploit + hp.alpha * jnp.sqrt(v) - penalty[None, :]
