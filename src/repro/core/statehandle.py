"""Double-buffered, versioned ``RouterState`` publication (DESIGN.md §13).

The serving gateway decouples the request path from learning: selection
reads an immutable, stale-by-one-tick snapshot while a learner applies
feedback blocks off the request path and *publishes* a fresh snapshot
atomically. This module is the core mechanism, kept in ``core/`` (not
``serving/``) so evaluate/sweep-style drivers can reuse it:

  * ``Snapshot``     — an immutable (state, version) pair. Versions are
    a monotonically increasing publish counter; every routed decision
    carries the version it was scored under, so late feedback can be
    attributed across publish ticks.
  * ``StateHandle``  — the double buffer. ``read()`` is wait-free (one
    attribute load; the GIL makes the swap atomic), ``publish()`` swaps
    the fresh state in under a tiny lock and bumps the version. Readers
    always see a complete snapshot — never a half-written state.
  * ``decay_on_restore`` — §3.3's gamma^Δt forgetting applied eagerly at
    restore time, so a router restarted after Δt offline steps resumes
    with correctly aged sufficient statistics (equivalent, within float
    associativity, to the lazy decay a live router would have applied).
  * ``save_snapshot``/``load_snapshot`` — persistence via
    ``training/checkpoint.py`` (.npz + manifest; the snapshot version
    rides in the manifest's ``step`` field).

The double buffer is conflict-free by construction: ``select_batch``
writes only ``types.SELECT_LEAVES`` and ``update_batch`` writes only
``types.LEARN_LEAVES`` (disjoint partitions), so the learner's output
merges into the live select-side state via ``types.merge_learn_leaves``
without clobbering concurrent dispatch bookkeeping.
"""
from __future__ import annotations

import dataclasses
import json
import threading
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import linucb, tenancy
from repro.core.types import RouterConfig, RouterState
from repro.training import checkpoint

Array = jax.Array


@dataclasses.dataclass(frozen=True)
class Snapshot:
    """An immutable published view of the router state.

    ``version`` is the publish counter (0 = initial state); ``step`` is
    the router's global step ``t`` at publish time, recorded host-side so
    restore can compute elapsed offline steps without a device sync.
    """

    state: RouterState
    version: int
    step: int = 0


class StateHandle:
    """Double-buffered publication point for ``RouterState``.

    One writer (the learner plane / control plane, externally
    serialized), many readers. ``read()`` never blocks on a publish in
    progress: it returns the last fully published ``Snapshot``.
    """

    def __init__(self, state: RouterState, *, version: int = 0,
                 step: Optional[int] = None):
        if step is None:
            step = int(state.t)
        self._lock = threading.Lock()
        self._snap = Snapshot(state=state, version=version, step=step)

    def read(self) -> Snapshot:
        """The current snapshot — wait-free, always complete."""
        return self._snap  # atomic attribute load under the GIL

    @property
    def version(self) -> int:
        return self._snap.version

    def publish(self, state: RouterState, *,
                step: Optional[int] = None) -> Snapshot:
        """Swap ``state`` in as the new snapshot; returns it (with the
        bumped version). The swap is a single reference assignment, so
        concurrent ``read()`` sees either the old or the new snapshot,
        never a mixture."""
        if step is None:
            step = int(state.t)
        with self._lock:
            snap = Snapshot(state=state, version=self._snap.version + 1,
                            step=step)
            self._snap = snap
        return snap


def decay_on_restore(cfg: RouterConfig, state: RouterState,
                     elapsed: int) -> RouterState:
    """Age a restored state by ``elapsed`` offline steps (§3.3).

    Applies gamma^min(elapsed, dt_max) to every arm's (A, A_inv, b)
    eagerly, recomputes theta, and shifts the whole step clock —
    ``t``, ``last_upd``, ``last_play`` — forward by ``elapsed``. Shifting
    the per-arm clocks alongside ``t`` is what keeps the *lazy* decay
    machinery exact: at the next update of arm ``a`` the live path
    applies gamma^(t_now - last_upd[a]) on top, and the composition
    gamma^elapsed * gamma^gap equals the single gamma^(elapsed + gap) a
    never-restarted router would have applied, up to float
    associativity (the 1e-6 round-trip bound asserted in tests; exact
    equality also requires elapsed + gap <= cfg.dt_max, the same clamp
    the live path has).

    The portfolio pacer dual (lam, c_ema) survives restore unchanged:
    Eq. 3-4 track the operator's budget, which does not decay with
    idleness. The *tenant* table, when present, DOES relax — each
    tenant's dual pressure is a live control signal with no requests
    behind it after Δt offline steps, so ``tenancy.decay_table`` applies
    the same gamma^min(Δt, dt_max) clock per tenant (lam toward 0,
    c_ema toward its budget anchor; DESIGN.md §15). Both maps compose
    across repeated restores like the statistics decay does.
    """
    elapsed = int(elapsed)
    if elapsed < 0:
        raise ValueError(f"decay_on_restore: elapsed={elapsed} must be >= 0")
    if elapsed == 0:
        return state
    dt = jnp.asarray(elapsed, jnp.int32)
    A, A_inv, b = jax.vmap(
        lambda a, ai, bb: linucb.decay_statistics(
            cfg.statics, state.hyper, a, ai, bb, dt)
    )(state.A, state.A_inv, state.b)
    theta = jnp.einsum("kij,kj->ki", A_inv, b,
                       precision=linucb.HIGHEST)
    shift = jnp.asarray(elapsed, jnp.int32)
    tenants = state.tenants
    if tenants is not None:
        tenants = tenancy.decay_table(
            cfg.statics, state.hyper, tenants, elapsed)
    return dataclasses.replace(
        state,
        A=A, A_inv=A_inv, b=b, theta=theta,
        last_upd=state.last_upd + shift,
        last_play=state.last_play + shift,
        t=state.t + shift,
        tenants=tenants,
    )


def save_snapshot(path: str, snap: Snapshot) -> None:
    """Persist a snapshot as .npz + manifest (training/checkpoint.py).

    The publish version rides in the manifest ``step`` field; the
    router's global step is already a state leaf (``t``)."""
    checkpoint.save_checkpoint(path, snap.state, step=snap.version)


def load_snapshot(path: str, template: RouterState) -> Snapshot:
    """Restore a snapshot saved by ``save_snapshot``.

    ``template`` supplies the pytree structure and shapes (e.g. a fresh
    ``init_state`` for the same Statics); shape mismatches fail loudly
    in ``load_checkpoint``."""
    state = checkpoint.load_checkpoint(path, template)
    # save_checkpoint writes the manifest at ``path + ".manifest.json"``
    # for the same path string it was given — mirror that here.
    with open(path + ".manifest.json") as f:
        version = int(json.load(f)["step"])
    return Snapshot(state=state, version=version, step=int(state.t))
