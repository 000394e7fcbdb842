"""Multi-seed simulation harness shared by benchmarks and tests.

Runs Algorithm 1 over an offline Environment stream with jax.lax.scan,
vmapped over seeds, and reduces traces to the paper's metrics (mean
reward, mean cost, compliance ratio, per-arm allocation, regret).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import router, scenario as scenario_lib, tenancy, warmup
from repro.core.simulator import Environment
from repro.core.types import (
    HYPER_FIELDS, ArmPrior, HyperParams, RouterConfig, RouterState,
    init_state,
)

Array = jax.Array

# Incremented inside the traced state builder: moves only when XLA
# (re)traces it, so tests can assert that a new grid of the same shape
# re-enters the compiled program (tests/trace_guard.assert_traces).
TRACE_COUNT = [0]


@dataclasses.dataclass(frozen=True)
class RunResult:
    arms: np.ndarray     # (S, T) chosen arm per seed/step
    rewards: np.ndarray  # (S, T)
    costs: np.ndarray    # (S, T)
    lams: np.ndarray     # (S, T) dual variable trace
    # Segment boundaries (0, ..., T) when the run came from a scenario
    # spec or a concat; None for a plain single-segment run.
    bounds: Optional[tuple] = None

    @property
    def mean_reward(self) -> float:
        return float(self.rewards.mean())

    @property
    def mean_cost(self) -> float:
        return float(self.costs.mean())

    def compliance(self, budget: float) -> float:
        """Realised mean cost as a multiple of the ceiling (1.0 = at)."""
        return float(self.costs.mean() / budget)

    def allocation(self, k: int) -> np.ndarray:
        """(K,) fraction of traffic per arm."""
        return np.asarray(
            [(self.arms == a).mean() for a in range(k)], dtype=np.float64
        )

    def phase(self, start: int, stop: int) -> "RunResult":
        arms = self.arms[:, start:stop]
        bounds = None
        if self.bounds is not None:
            # Preserve the segment structure of the slice: boundaries that
            # fall strictly inside [start, stop) survive, re-based to 0.
            L = arms.shape[1]
            inner = sorted({b - start for b in self.bounds
                            if start < b < start + L})
            bounds = (0, *inner, L)
        return RunResult(
            arms=arms,
            rewards=self.rewards[:, start:stop],
            costs=self.costs[:, start:stop],
            lams=self.lams[:, start:stop],
            bounds=bounds,
        )

    @property
    def n_segments(self) -> int:
        return 1 if self.bounds is None else len(self.bounds) - 1

    def segment(self, j: int) -> "RunResult":
        """Slice to scenario segment ``j`` (between event boundaries).
        Bounds are *effective*: a padded timeline run's boundaries stop
        at the element's horizon, so segment slices never read padding
        rows. Out-of-range indices raise ValueError."""
        if self.bounds is None:
            raise ValueError("run has no segment boundaries")
        if not 0 <= j < self.n_segments:
            raise ValueError(
                f"segment index {j} out of range: run has "
                f"{self.n_segments} segments (bounds={self.bounds})")
        return self.phase(self.bounds[j], self.bounds[j + 1])

    @classmethod
    def concat(cls, parts: Sequence["RunResult"]) -> "RunResult":
        """Stitch per-segment results along the time axis; the joins (and
        any internal boundaries of the parts) become segment bounds."""
        parts = list(parts)
        bounds, off = [0], 0
        for p in parts:
            inner = p.bounds if p.bounds is not None else (0, p.arms.shape[1])
            bounds.extend(off + b for b in inner[1:])
            off += p.arms.shape[1]
        return cls(
            arms=np.concatenate([p.arms for p in parts], axis=1),
            rewards=np.concatenate([p.rewards for p in parts], axis=1),
            costs=np.concatenate([p.costs for p in parts], axis=1),
            lams=np.concatenate([p.lams for p in parts], axis=1),
            bounds=tuple(bounds),
        )

    def regret_vs_oracle(self, env_rewards: np.ndarray) -> np.ndarray:
        """(S,) cumulative regret vs the per-prompt oracle."""
        oracle = env_rewards.max(axis=1)  # (T,)
        return (oracle[None, :] - self.rewards).sum(axis=1)


def pad_priors(cfg: RouterConfig, priors: Sequence[ArmPrior | None]):
    """Pad a per-arm prior list out to ``max_arms`` slots (the layout
    ``warmup.apply_warmup`` expects)."""
    pad = cfg.max_arms - len(priors)
    assert pad >= 0, (len(priors), cfg.max_arms)
    return list(priors) + [None] * pad


def _hyper_stack(cfg: RouterConfig, hyper: Optional[HyperParams], n: int):
    """A hyper spec — one shared ``HyperParams`` or one with (n,)-stacked
    leaves (a per-state axis) — as host float32 (n,) stacks. Every stack
    then reaches the state builder in one layout, so a shared value and
    the same value repeated per state build bit-identical states."""
    hp = cfg.hyper if hyper is None else hyper
    if isinstance(hp, HyperParams):
        hp.validate()
    leaves = {}
    for name in HYPER_FIELDS:
        leaf = np.asarray(getattr(hp, name), np.float32)
        if leaf.ndim not in (0, 1) or (leaf.ndim == 1
                                       and leaf.shape[0] != n):
            raise ValueError(
                f"hyper.{name} must be a scalar or a ({n},) stack; got "
                f"shape {leaf.shape}")
        leaves[name] = np.broadcast_to(leaf, (n,))
    return HyperParams(**leaves)


def _tenant_axis(tenants: "tenancy.TenantTable", n: int):
    """The vmap axis of a tenant table that is either one shared (T,)
    table — broadcast to every stacked state (None) — or one with
    (n, T) leaves (0: a per-state axis, the sweep fabric's flattened
    grid). Budgets are positivity-checked here (host boundary, satellite
    of the Eq. 4 division hazard) when concrete."""
    ndim = jnp.ndim(tenants.budget)
    if not isinstance(tenants.budget, jax.core.Tracer):
        b = np.asarray(tenants.budget)
        if not np.all(b > 0.0):
            raise ValueError(
                "tenant budgets must be > 0 ($/request ceilings); got "
                f"min={b.min()!r}")
    if ndim == 1:
        return None
    if ndim == 2 and tenants.budget.shape[0] == n:
        return 0
    raise ValueError(
        f"tenants.budget must be (T,) shared or ({n}, T) per-state; got "
        f"shape {jnp.shape(tenants.budget)}")


@functools.lru_cache(maxsize=64)
def _cached_states_fn(statics, prior_slots, pacer_enabled, tenant_axis):
    """One jitted state builder per stack structure: ``statics``,
    ``prior_slots`` (None for a cold stack, else one bool per arm slot
    saying whether it holds a prior), ``pacer_enabled`` and the vmap
    axis of a tenant table (None shared, 0 per state); jit adds the
    number of states from the operands' shapes. Seeds, budgets, prices,
    the active mask, the priors' statistics and the per-state n_eff,
    hyper leaves and tables are operands, so a new grid of the same
    shape re-enters the same executable."""

    def one(seed, b, preq, p1k, active, h, ne, prior_stats, tb):
        st = init_state(
            statics, preq, p1k, b,
            key=jax.random.PRNGKey(seed), active=active,
            pacer_enabled=pacer_enabled, hyper=h, tenants=tb,
        )
        if prior_slots is not None:
            it = iter(prior_stats)
            padded = [ArmPrior(*next(it)) if has else None
                      for has in prior_slots]
            st = warmup.apply_warmup(statics, st, padded, ne)
        return st

    # Named for its caller, so its XLA module reads ``jit_make_states``.
    def make_states(seeds, budgets, preq, p1k, active, hp, ne, prior_stats,
                    tab):
        TRACE_COUNT[0] += 1       # moves only while tracing
        return jax.vmap(
            one, in_axes=(0, 0, None, None, None, 0, 0, None, tenant_axis),
        )(seeds, budgets, preq, p1k, active, hp, ne, prior_stats, tab)

    return jax.jit(make_states)


def make_states(
    cfg: RouterConfig,
    env: Environment,
    budget: float | Sequence[float],
    seeds: Sequence[int],
    *,
    priors: Optional[Sequence[ArmPrior | None]] = None,
    n_eff: float | Sequence[float] = 0.0,
    pacer_enabled: bool = True,
    active_arms: Optional[int] = None,
    hyper: Optional[HyperParams] = None,
    tenants: Optional["tenancy.TenantTable"] = None,
) -> RouterState:
    """Stacked initial states, one per seed, built by ONE cached compiled
    program (``_cached_states_fn``): ``init_state``, the §3.4 warm start
    and the PRNG keys, vmapped over (seed, budget, hyper, n_eff, tenant
    table) inside one ``jax.jit`` — everything else broadcasts. The
    program is keyed on the stack's structure alone (statics, warm or
    cold and which arm slots hold a prior, pacer on or off, a shared or
    per-state tenant table, and through jit the number of states and
    tenants); every value is an operand,
    so fresh seeds and budgets of the same shape re-enter it with zero
    retraces (``TRACE_COUNT``). Budgets, hyper leaves and n_eff reach it
    as per-state stacks whether given shared or stacked, so one value
    shared and the same value repeated per state build bit-identical
    states through one program.

    ``budget`` is either one ceiling shared by every state or a sequence
    aligned with ``seeds``: the ceiling lives in ``PacerState.budget``, a
    *state leaf*, so a grid sweep stacks one budget per (condition, seed)
    element and the whole grid runs through one compiled program
    (sweep.py) instead of re-entering per ceiling. ``hyper`` follows the
    same rule (DESIGN.md §9): one shared ``HyperParams`` (default:
    ``cfg.hyper``) or one whose leaves are (len(seeds),) stacks — a per-
    state (α, γ, ...) axis for fused hyper grids. ``n_eff`` likewise: a
    scalar, or one pseudo-count per stacked state (the knee grid derives
    n_eff from each cell's gamma via Eq. 13), applied inside the same
    program — all warm or all cold; a mixed stack would need the warmup
    branch to be data-dependent (run the warm and the cold conditions as
    two grids instead).

    ``tenants`` attaches a per-tenant pacer table (DESIGN.md §15): one
    shared (T,) ``tenancy.TenantTable`` copied into every state, or one
    with (len(seeds), T) stacked leaves for a per-state tenant axis.
    """
    k = env.k
    assert k <= cfg.max_arms, (k, cfg.max_arms)
    n = len(seeds)
    b_host = np.asarray(budget, np.float32)
    if not np.all(b_host > 0.0):
        raise ValueError(
            f"budget must be > 0 ($/request ceiling); got {budget!r}")
    pad = cfg.max_arms - k
    preq = np.concatenate([env.prices_per_req, np.full(pad, 1e9)]).astype(np.float32)
    p1k = np.concatenate([env.prices_per_1k, np.full(pad, 1e9)]).astype(np.float32)
    n_active = k if active_arms is None else active_arms
    active = np.zeros(cfg.max_arms, bool)
    active[:n_active] = True
    hp = _hyper_stack(cfg, hyper, n)
    ne = np.asarray(n_eff, np.float32)
    warm = priors is not None and bool(np.any(ne > 0))
    if warm and ne.ndim and not np.all(ne > 0):
        raise ValueError(
            "mixed warm/cold n_eff in one stack: apply_warmup at n_eff=0 "
            "is not a no-op, so warm-vs-cold cannot share the vmapped "
            "branch — run the warm and the cold conditions as two grids")
    if ne.ndim and ne.shape != (n,):
        raise ValueError(
            f"n_eff must be a scalar or one value per state; got shape "
            f"{ne.shape} for {n} states")
    prior_slots, prior_stats = None, ()
    if warm:
        padded = pad_priors(cfg, list(priors))
        prior_slots = tuple(p is not None for p in padded)
        prior_stats = tuple((p.A_off, p.b_off) for p in padded
                            if p is not None)
    tab_axis = None if tenants is None else _tenant_axis(tenants, n)
    build = _cached_states_fn(cfg.statics, prior_slots,
                              bool(pacer_enabled), tab_axis)
    seeds_host = np.asarray([int(s) for s in seeds], np.uint32)
    # Deliberate host->device staging of a few KB of operands.
    with jax.transfer_guard("allow"):
        return build(seeds_host, np.broadcast_to(b_host, (n,)), preq, p1k,
                     active, hp, np.broadcast_to(ne, (n,)), prior_stats,
                     tenants)


def _pad_env_arrays(cfg: RouterConfig, env: Environment, put=jnp.asarray):
    """Pad (T, K) matrices out to max_arms with harmless fillers; ``put``
    sends each to the device."""
    pad = cfg.max_arms - env.k
    rewards = np.concatenate(
        [env.rewards, np.zeros((env.n, pad), np.float32)], axis=1
    )
    costs = np.concatenate(
        [env.costs, np.full((env.n, pad), 1e9, np.float32)], axis=1
    )
    return put(env.contexts), put(rewards), put(costs)


def build_run_streams(
    cfg: RouterConfig,
    env: Environment | Sequence[Environment],
    seeds: Sequence[int],
    shuffle: bool = True,
    put=jnp.asarray,
):
    """Padded per-seed stream tensors for ``run`` and the sweep fabric.

    Returns ``(xs, rmat, cmat, stream_axes, env0)`` where ``stream_axes``
    is 0 for per-seed stacked streams (a sequence of environments, or one
    environment with per-seed shuffles) and None for one shared stream.
    Every host array goes to the device through ``put`` (the sweep
    fabric passes one that counts the bytes).
    """
    if isinstance(env, (list, tuple)):
        assert len(env) == len(seeds), (len(env), len(seeds))
        padded = [_pad_env_arrays(cfg, e, put) for e in env]
        xs = jnp.stack([p[0] for p in padded])
        rmat = jnp.stack([p[1] for p in padded])
        cmat = jnp.stack([p[2] for p in padded])
        return xs, rmat, cmat, 0, env[0]
    xs, rmat, cmat = _pad_env_arrays(cfg, env, put)
    if shuffle:
        perms = np.stack([
            np.random.default_rng(int(s)).permutation(env.n) for s in seeds
        ])
        xs = xs[put(perms)]
        rmat = rmat[put(perms)]
        cmat = cmat[put(perms)]
        return xs, rmat, cmat, 0, env
    return xs, rmat, cmat, None, env


def run(
    cfg: RouterConfig,
    env: Environment | Sequence[Environment],
    budget: float,
    seeds: Sequence[int] = tuple(range(20)),
    *,
    priors: Optional[Sequence[ArmPrior | None]] = None,
    n_eff: float = 0.0,
    pacer_enabled: bool = True,
    states: Optional[RouterState] = None,
    shuffle: bool = True,
    return_states: bool = False,
    batch_size: Optional[int] = None,
    hyper: Optional[HyperParams] = None,
    tenants: Optional["tenancy.TenantTable"] = None,
    tenant_ids: Optional[np.ndarray] = None,
):
    """Vectorised multi-seed run of Algorithm 1 over an environment stream.

    ``env`` is either one Environment (per-seed prompt order is then a
    seed-specific permutation unless ``shuffle=False``) or a sequence of
    per-seed Environments of equal length (phase experiments build one
    ordered stream per seed and pass them here; no further shuffling).

    ``batch_size`` > 1 consumes the stream through the batched data plane
    (``router.run_stream_batched``) in blocks of that size — the same
    select_batch/update_batch path the batch-serving gateway runs — so
    scenario benchmarks can exercise production code. Default (None) is
    the per-request closed loop.

    ``hyper`` overrides ``cfg.hyper`` for the run — a *data* change, so
    sweeping it re-enters the same compiled program (DESIGN.md §9).

    ``tenants`` + ``tenant_ids`` switch the run to the tenant plane
    (DESIGN.md §15): ``tenants`` is a shared (T,) or per-seed (S, T)
    ``tenancy.TenantTable`` and ``tenant_ids`` tags each stream step
    with its tenant — (L,) shared by every seed or (S, L) per seed.
    Requires ``batch_size`` (tenant routing runs on the batched data
    plane). Tables and ids are data: new budgets or a new mix re-enter
    the same compiled program with zero retraces.
    """
    if (tenants is None) != (tenant_ids is None) and states is None:
        raise ValueError("pass tenants and tenant_ids together")
    xs, rmat, cmat, stream_axes, env0 = build_run_streams(
        cfg, env, seeds, shuffle)
    if states is None:
        states = make_states(
            cfg, env0, budget, seeds,
            priors=priors, n_eff=n_eff, pacer_enabled=pacer_enabled,
            hyper=hyper, tenants=tenants,
        )

    if tenant_ids is not None:
        if not batch_size:
            raise ValueError(
                "tenant runs need batch_size: tenant routing is a batched-"
                "data-plane feature (DESIGN.md §15)")
        tids = jnp.asarray(tenant_ids, jnp.int32)
        if tids.ndim == 1:
            tid_axes = None
        elif tids.ndim == 2 and tids.shape[0] == len(seeds):
            tid_axes = 0
        else:
            raise ValueError(
                f"tenant_ids must be (L,) shared or ({len(seeds)}, L) "
                f"per-seed; got shape {tids.shape}")
        run_fn = _cached_run_fn_tenants(
            cfg.statics, stream_axes, batch_size, tid_axes)
        finals, (arms, r, c, lam) = run_fn(states, xs, rmat, cmat, tids)
    else:
        run_fn = _cached_run_fn(cfg.statics, stream_axes, batch_size)
        finals, (arms, r, c, lam) = run_fn(states, xs, rmat, cmat)
    res = RunResult(
        arms=np.asarray(arms), rewards=np.asarray(r),
        costs=np.asarray(c), lams=np.asarray(lam),
    )
    if return_states:
        return res, finals
    return res


def stream_body(cfg: RouterConfig, batch_size=None):
    """The per-seed scan program: one stream through the scalar or
    batched data plane. Shared by the jitted runner below and the
    grid-sweep fabric (sweep.py), which vmaps it over a flattened
    (condition x seed) axis with buffer donation."""

    def one_seed(state, x, rm, cm):
        if batch_size:
            return router.run_stream_batched(cfg, state, x, rm, cm,
                                             batch_size)
        return router.run_stream(cfg, state, x, rm, cm)

    return one_seed


@functools.lru_cache(maxsize=64)
def _cached_run_fn(statics, stream_axes, batch_size=None):
    """One jitted sweep function per (Statics, stream layout). Keyed on
    the *statics projection* only: hyper-parameters live in the state
    (DESIGN.md §9), so an (α, γ) grid — which used to retrace per cell —
    re-enters one cached program."""
    one_seed = stream_body(statics, batch_size)
    return jax.jit(
        jax.vmap(one_seed, in_axes=(0, stream_axes, stream_axes, stream_axes))
    )


def stream_body_tenants(cfg: RouterConfig, batch_size):
    """Tenant-mode per-seed scan program: ``stream_body`` with a
    ``tenant_ids`` (L,) operand threaded to the batched data plane."""

    def one_seed(state, x, rm, cm, tids):
        return router.run_stream_batched(cfg, state, x, rm, cm, batch_size,
                                         tenant_ids=tids)

    return one_seed


@functools.lru_cache(maxsize=64)
def _cached_run_fn_tenants(statics, stream_axes, batch_size, tid_axes):
    """Tenant-mode companion of ``_cached_run_fn``: the extra key is the
    tenant-id layout (None = one mix shared by every seed, 0 = per-seed
    (S, L) mixes). Tables and ids are data — new tenant budgets never
    retrace."""
    one_seed = stream_body_tenants(statics, batch_size)
    return jax.jit(
        jax.vmap(one_seed, in_axes=(0, stream_axes, stream_axes, stream_axes,
                                    tid_axes))
    )


def run_scenario(
    cfg: RouterConfig,
    spec: "scenario_lib.ScenarioSpec",
    env: Environment,
    budget: float,
    seeds: Sequence[int] = tuple(range(20)),
    *,
    priors: Optional[Sequence[ArmPrior | None]] = None,
    n_eff: float = 0.0,
    pacer_enabled: bool = True,
    batch_size: Optional[int] = None,
    return_states: bool = False,
    hyper: Optional[HyperParams] = None,
    scenario_params: Optional["scenario_lib.ScenarioParams"] = None,
    timeline: Optional["scenario_lib.Timeline"] = None,
    tenants: Optional["tenancy.TenantTable"] = None,
    tenant_ids: Optional[np.ndarray] = None,
):
    """Run a declarative ``ScenarioSpec`` over ``env`` as ONE jitted,
    seed-vmapped segmented-scan call (scenario.py).

    The spec's event timeline is compiled to a per-seed stream tensor
    stack plus pure state edits applied between ``lax.scan`` segments;
    ``batch_size`` > 1 consumes every segment through the batched data
    plane instead of the per-request loop. The returned ``RunResult``
    carries the spec's segment ``bounds`` so metrics reduce per segment
    via ``res.segment(j)``.

    ``scenario_params`` resolves any ``Param`` payload references in the
    spec (DESIGN.md §10). Payload values are *data*: re-running the same
    spec with new values re-enters the compiled program with zero
    retraces. Leaves are scalars shared by every seed (or per-seed
    ``(len(seeds),)`` stacks).

    ``timeline`` moves the spec's event *times* (and optionally shrinks
    the effective horizon, padding the scan) through the masked timeline
    runner (DESIGN.md §12): bit-identical to running the concrete
    retimed spec, but every Timeline of one spec shares ONE compiled
    program — new event times re-enter with zero retraces. Traces and
    bounds come back trimmed to the effective horizon.
    """
    params = scenario_lib.resolve_params(spec, scenario_params)
    full = params.updated(**scenario_lib.auto_param_values(spec))
    if (tenants is None) != (tenant_ids is None):
        raise ValueError("pass tenants and tenant_ids together")
    if tenants is not None and timeline is not None:
        raise NotImplementedError(
            "tenant runs are not wired through the masked timeline "
            "runner; use the concrete scenario path (timeline=None)")
    states = make_states(
        cfg, env, budget, seeds,
        priors=priors, n_eff=n_eff, pacer_enabled=pacer_enabled,
        active_arms=spec.init_active, hyper=hyper, tenants=tenants,
    )
    if timeline is not None:
        rspec = scenario_lib.retime(spec, timeline)
        scenario_lib.validate_timeline_alignment(
            rspec, batch_size, spec.horizon)
        xs, rmat, cmat = scenario_lib.build_streams(
            cfg, rspec, env, seeds, params=params, pad_to=spec.horizon)
        run_fn = scenario_lib.compiled_timeline_runner(
            cfg, spec, env, batch_size)
        S, E = len(seeds), len(spec.events)
        ev = jnp.broadcast_to(
            jnp.asarray([e.t for e in rspec.events], jnp.int32), (S, E))
        hz = jnp.full((S,), rspec.horizon, jnp.int32)
        finals, (arms, r, c, lam) = run_fn(
            states, xs, rmat, cmat,
            scenario_lib.broadcast_params(full, S), ev, hz)
        h = rspec.horizon
        res = RunResult(
            arms=np.asarray(arms)[:, :h], rewards=np.asarray(r)[:, :h],
            costs=np.asarray(c)[:, :h], lams=np.asarray(lam)[:, :h],
            bounds=rspec.bounds,
        )
        if return_states:
            return res, finals
        return res
    xs, rmat, cmat = scenario_lib.build_streams(cfg, spec, env, seeds,
                                                params=params)
    run_fn = scenario_lib.compiled_runner(cfg, spec, env, batch_size,
                                          with_tenants=tenants is not None)
    bp = scenario_lib.broadcast_params(full, len(seeds))
    if tenants is not None:
        tids = np.asarray(tenant_ids, np.int32)
        if tids.ndim == 1:
            tids = np.broadcast_to(tids, (len(seeds),) + tids.shape)
        if tids.shape != (len(seeds), spec.horizon):
            raise ValueError(
                f"tenant_ids must be ({spec.horizon},) shared or "
                f"({len(seeds)}, {spec.horizon}) per-seed; got "
                f"{np.asarray(tenant_ids).shape}")
        finals, (arms, r, c, lam) = run_fn(
            states, xs, rmat, cmat, bp,
            jnp.asarray(np.ascontiguousarray(tids)))
    else:
        finals, (arms, r, c, lam) = run_fn(states, xs, rmat, cmat, bp)
    res = RunResult(
        arms=np.asarray(arms), rewards=np.asarray(r),
        costs=np.asarray(c), lams=np.asarray(lam),
        bounds=spec.bounds,
    )
    if return_states:
        return res, finals
    return res


def fit_warmup_priors(
    cfg: RouterConfig, env: Environment, lambda0: float = 1.0
):
    """Fit per-arm offline priors from a train-split environment, emulating
    the paper's offline characterisation (every arm sees every prompt)."""
    priors = []
    for a in range(env.k):
        priors.append(
            warmup.fit_offline_prior(
                jnp.asarray(env.contexts), jnp.asarray(env.rewards[:, a]),
                lambda0=lambda0,
            )
        )
    return priors
