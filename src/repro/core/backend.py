"""Pluggable batched routing backends for the data plane.

``select_batch`` scores a (B, d) block of request contexts against every
arm through a ``RoutingBackend``. Three implementations ship
(DESIGN.md §2/§11):

  * ``jnp``          — the einsum oracle (``linucb.ucb_scores_batch``),
                       portable to any XLA device; the numerical
                       reference.
  * ``pallas``       — the scoring TPU kernel (``kernels/linucb_score``):
                       requests tiled in rows, all K arms' (d x d)
                       inverses resident in VMEM. Compiled on a TPU and
                       run in interpret mode on the CPU
                       (``kernels.interpret_mode``), so CPU tests exercise
                       the exact kernel code that compiles for the chip.
  * ``pallas_fused`` — the full step megakernel (``kernels/linucb_step``):
                       score -> hard-ceiling select -> chosen-arm decay +
                       Sherman-Morrison + theta refresh + pacer dual step
                       as ONE ``pallas_call`` with the stats buffers
                       aliased in/out (VMEM-resident across the whole
                       block). ``router.step_batch`` dispatches to its
                       ``step_block`` hook; select-only serving falls
                       back to the inherited scoring kernel.

The backend is selected statically via ``RouterConfig.backend``, so the
choice is resolved at trace time and never costs a runtime branch. The
hyper-parameters, by contrast, are *traced operands* (DESIGN.md §9):
``alpha`` (and for the fused kernel gamma/eta/alpha_ema/lambda_bar too)
enter the Pallas kernels as scalar inputs, and the penalty / inflation
vectors are computed from the traced ``HyperParams`` leaves — so a sweep
can stack a whole (α, γ) grid on the fabric's flattened (condition x
seed) vmap axis without recompiling any backend.

Numerical-equivalence contract: every backend must agree with the jnp
oracle to ``EQUIV_TOL`` max abs diff — on scores, and for the fused
backend on the post-block sufficient statistics as well (enforced by
tests/test_batched_routing.py and tests/test_kernels.py — including
under the fabric's vmap axis in tests/test_hyperparams.py — and
reported by benchmarks/bench_latency.py).
"""
from __future__ import annotations

from typing import Protocol

import jax
import jax.numpy as jnp

from repro.core import linucb
from repro.core import pacer as pacer_lib
from repro.core.types import HyperParams, RouterConfig, RouterState
from repro.kernels.linucb_score.ops import linucb_score
from repro.kernels.linucb_step.ops import linucb_step

Array = jax.Array

# Max abs score divergence the kernel is allowed vs the jnp oracle.
EQUIV_TOL = 1e-4


class RoutingBackend(Protocol):
    """Batched Eq. 2 scoring: (B, d) contexts -> (B, K) arm scores."""

    name: str

    def score(
        self,
        cfg: RouterConfig,
        hp: HyperParams,  # traced hyper leaves (state-carried)
        theta: Array,     # (K, d)
        A_inv: Array,     # (K, d, d)
        c_tilde: Array,   # (K,)
        X: Array,         # (B, d)
        dt: Array,        # (K,) staleness per arm at block entry
        lam: Array,       # scalar dual variable
    ) -> Array: ...


class JnpBackend:
    name = "jnp"

    def score(self, cfg, hp, theta, A_inv, c_tilde, X, dt, lam) -> Array:
        return linucb.ucb_scores_batch(
            cfg, hp, theta, A_inv, c_tilde, X, dt, lam)


class PallasBackend:
    name = "pallas"

    def score(self, cfg, hp, theta, A_inv, c_tilde, X, dt, lam) -> Array:
        pen = (hp.lambda_c + lam) * c_tilde
        infl = linucb.staleness_inflation(cfg, hp, dt)
        return linucb_score(X, theta, A_inv, pen, infl, hp.alpha)


class FusedPallasBackend(PallasBackend):
    """The step megakernel backend (DESIGN.md §11).

    ``score`` is inherited (select-only serving still runs the scoring
    kernel); closed-loop ``router.step_batch`` detects ``fused_step`` and
    routes the whole block body through ``step_block`` instead — one
    ``pallas_call`` covering score/select/update/pacer with the stats
    buffers aliased in/out.
    """

    name = "pallas_fused"
    fused_step = True

    def step_block(
        self,
        cfg: RouterConfig,
        state: RouterState,
        X: Array,        # (B, d) contexts
        rewards: Array,  # (B, K) environment reward matrix
        costs: Array,    # (B, K) environment cost matrix
        noise: Array,    # (B, K) pre-drawn tiebreak noise
        farm: Array,     # scalar i32 clipped forced-exploration target
        forced: Array,   # (B,) bool forced-override mask
    ):
        """One fused step-batch on the state's raw leaves.

        Computes the same block-entry quantities as ``select_batch``
        (hard-ceiling mask, staleness dt, Eq. 2 penalty / inflation) and
        hands everything to the megakernel. Returns
        (A', A_inv', b', theta', last_upd', arms, r, c, lam', c_ema') —
        the pacer outputs are the UNGATED Eq. 3-4 fold; the router applies
        the ``pacer.enabled`` gate (a frozen pacer changes nothing per
        step, so gating the block result is the same fold).
        """
        hp = state.hyper
        cand = pacer_lib.hard_ceiling_mask(
            state.pacer, state.price, state.active)
        dt = state.t - jnp.maximum(state.last_upd, state.last_play)
        pen = (hp.lambda_c + state.pacer.lam) * state.c_tilde
        infl = linucb.staleness_inflation(cfg, hp, dt)
        t_sel = state.t + X.shape[0]
        return linucb_step(
            state.A, state.A_inv, state.b, state.theta, state.last_upd,
            X, rewards, costs, noise, cand, pen, infl,
            hp.alpha, hp.gamma, hp.eta, hp.alpha_ema, hp.lambda_bar,
            state.pacer.lam, state.pacer.c_ema, state.pacer.budget,
            t_sel, farm, forced,
            dt_max=cfg.dt_max,
        )


_BACKENDS: dict[str, RoutingBackend] = {
    "jnp": JnpBackend(),
    "pallas": PallasBackend(),
    "pallas_fused": FusedPallasBackend(),
}


def get_backend(name: str) -> RoutingBackend:
    try:
        return _BACKENDS[name]
    except KeyError:
        raise KeyError(
            f"unknown routing backend {name!r}; have {sorted(_BACKENDS)}"
        ) from None


def score_divergence(
    cfg: RouterConfig, hp: HyperParams, theta, A_inv, c_tilde, X, dt, lam
) -> float:
    """Max abs score diff between the two backends on one block (the
    equivalence contract, for benchmarks and monitoring)."""
    a = get_backend("jnp").score(cfg, hp, theta, A_inv, c_tilde, X, dt, lam)
    b = get_backend("pallas").score(
        cfg, hp, theta, A_inv, c_tilde, X, dt, lam)
    return float(jnp.max(jnp.abs(a - b)))
