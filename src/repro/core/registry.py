"""Hot-swap model registry (§3.6).

Arms live in fixed-capacity slots of ``RouterState``; adding/removing a
model flips the ``active`` mask and (re)initialises that slot's statistics,
so the jitted routing step never recompiles across portfolio changes.

``add_arm`` supports three initialisations:
  * uninformative    — A = lambda0*I, b = 0 (cold start);
  * heuristic prior  — n_eff pseudo-observations at isotropic uncertainty
                       with a bias-only reward prediction (§3.4);
  * offline prior    — scaled offline sufficient statistics (warmup.py).
The first two make A a scaled identity s*I, so A^-1 = I/s and
theta = b/s are written in closed form; only the offline prior, a full
matrix, is inverted. Under ``jax.vmap`` the scenario engine's
``lax.cond`` around an edit becomes a select that runs the edit on every
step, so an LU here would be paid per step and per element.

A newly added arm can be given a forced-exploration burn-in
(cfg.forced_pulls unconditional routes, §4.5), after which UCB takes over.

``add_arm`` / ``delete_arm`` / ``set_price`` are pure, jnp-only and
vmap-safe (``slot`` and the prior/price parameters are trace constants;
only ``state`` leaves are batched), so control-plane events compose under
``jax.vmap`` over seeds and can be baked into a jitted program — the
scenario engine (scenario.py) applies them between ``lax.scan`` segments
inside one compiled simulation.

Under the serving gateway (DESIGN.md §13) these ops are *control-plane*
writes: they touch both the learner's leaves (slot statistics) and the
selection plane's view (``active``, prices, forced-exploration), so a
live deployment must apply them through
``RouterGateway.apply_control`` — atomically w.r.t. in-flight selection
and published as a new snapshot — never by mutating a state the planes
are already reading. ``free_slot`` is the host-side slot scan for that
path.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.types import ArmPrior, RouterConfig, RouterState, log_normalized_cost
from repro.core import warmup as warmup_lib

Array = jax.Array


def _replace(state: RouterState, **kw) -> RouterState:
    return dataclasses.replace(state, **kw)


def _isotropic(d: int, s):
    """(A, A^-1) of the scaled identity A = s*I, the inverse in closed form."""
    eye = jnp.eye(d, dtype=jnp.float32)
    return eye * s, eye / s


def heuristic_prior(cfg: RouterConfig, hp, n_eff: float, bias_reward: float):
    """§3.4: for models absent from offline data — n_eff pseudo-observations
    at isotropic uncertainty with a bias-only reward prediction. Assumes the
    bias coordinate is the last feature (features.py appends it). ``hp``
    supplies the (traced) ridge weight lambda0. Returns (s, b) with
    A = s*I."""
    d = cfg.d
    s = hp.lambda0 + n_eff / d
    b = jnp.zeros((d,), jnp.float32).at[d - 1].set(bias_reward * n_eff / d)
    return s, b


def add_arm(
    cfg: RouterConfig,
    state: RouterState,
    slot: int,
    price_per_req: float,
    price_per_1k: float,
    *,
    prior: Optional[ArmPrior] = None,
    n_eff: Optional[float] = None,
    bias_reward: float = 0.5,
    forced_exploration: bool = True,
) -> RouterState:
    """Register a model into ``slot`` at runtime. Pure and trace-safe:
    callable from the host (serving gateway), under ``jax.vmap`` over a
    stacked state, or inside a jitted scenario program."""
    d = cfg.d
    hp = state.hyper   # traced leaves: lambda0 / Eq. 6 bounds are data
    # ``n_eff`` may be a traced f32 leaf (a scenario ``Param`` payload,
    # DESIGN.md §10): its truthiness cannot branch, so a traced n_eff
    # always takes the prior branch (heuristic_prior at n_eff == 0 is
    # exactly the cold start, so the semantics agree at the boundary).
    traced_ne = isinstance(n_eff, (jax.Array, jax.core.Tracer))
    if prior is not None:
        ne = n_eff if traced_ne else (n_eff or 1.0)
        A, b = warmup_lib.scale_prior(cfg, hp, prior, ne)
        A_inv = jnp.linalg.inv(A)
        theta = A_inv @ b
    else:
        if n_eff is not None and (traced_ne or n_eff > 0):
            s, b = heuristic_prior(cfg, hp, n_eff, bias_reward)
        else:
            s, b = hp.lambda0, jnp.zeros((d,), jnp.float32)
        A, A_inv = _isotropic(d, s)
        theta = b / s
    c_t = log_normalized_cost(jnp.asarray(price_per_1k, jnp.float32), hp)
    state = _replace(
        state,
        A=state.A.at[slot].set(A),
        A_inv=state.A_inv.at[slot].set(A_inv),
        b=state.b.at[slot].set(b),
        theta=state.theta.at[slot].set(theta),
        last_upd=state.last_upd.at[slot].set(state.t),
        last_play=state.last_play.at[slot].set(state.t),
        active=state.active.at[slot].set(True),
        price=state.price.at[slot].set(price_per_req),
        c_tilde=state.c_tilde.at[slot].set(c_t),
    )
    if forced_exploration:
        state = _replace(
            state,
            force_arm=jnp.asarray(slot, jnp.int32),
            force_left=jnp.asarray(cfg.forced_pulls, jnp.int32),
        )
    return state


def delete_arm(cfg: RouterConfig, state: RouterState, slot: int) -> RouterState:
    """Retire a model. Its statistics are zeroed so a future ``add_arm`` into
    the same slot starts clean; any in-flight forced exploration of the slot
    is cancelled."""
    d = cfg.d
    A, A_inv = _isotropic(d, state.hyper.lambda0)
    cancel = state.force_arm == slot
    return _replace(
        state,
        A=state.A.at[slot].set(A),
        A_inv=state.A_inv.at[slot].set(A_inv),
        b=state.b.at[slot].set(jnp.zeros((d,), jnp.float32)),
        theta=state.theta.at[slot].set(jnp.zeros((d,), jnp.float32)),
        active=state.active.at[slot].set(False),
        force_arm=jnp.where(cancel, jnp.asarray(-1, jnp.int32), state.force_arm),
        force_left=jnp.where(cancel, jnp.asarray(0, jnp.int32), state.force_left),
    )


def set_price(
    cfg: RouterConfig, state: RouterState, slot: int,
    price_per_req: float, price_per_1k: float,
) -> RouterState:
    """Reprice an arm (provider price change). The pacer reacts to realised
    costs automatically; this keeps the hard ceiling and Eq. 6 in sync."""
    c_t = log_normalized_cost(
        jnp.asarray(price_per_1k, jnp.float32), state.hyper)
    return _replace(
        state,
        price=state.price.at[slot].set(price_per_req),
        c_tilde=state.c_tilde.at[slot].set(c_t),
    )


def num_active(state: RouterState):
    """Number of active arms. Host callers get a Python int; under
    ``jit``/``vmap`` tracing the traced i32 scalar is returned instead
    (``int()`` on a tracer would raise ``TracerIntegerConversionError``)."""
    n = jnp.sum(state.active.astype(jnp.int32))
    if isinstance(n, jax.core.Tracer):
        return n
    return int(n)


def free_slot(state: RouterState) -> Optional[int]:
    """Lowest inactive slot, or None when the registry is at capacity.

    Host-side (one device sync) — this is the control-plane slot scan
    for onboarding a model through the gateway publish path; it is NOT
    jit-safe (a traced ``active`` has no concrete free slot)."""
    inactive = np.flatnonzero(~np.asarray(state.active))
    return int(inactive[0]) if inactive.size else None
