"""Core datatypes for the ParetoBandit router.

Everything the per-step routing loop touches lives in ``RouterState``, a
registered pytree of fixed-capacity arrays (``max_arms`` slots with an
``active`` mask) so that ``add_arm``/``delete_arm`` never change array
shapes and the jitted step functions never recompile on portfolio changes
(the paper's hot-swap registry, §3.6).

Configuration is split in two (DESIGN.md §9):

  * ``Statics``     — shape/trace-affecting knobs (``d``, ``max_arms``,
                      ``backend``, ``dt_max``, ``forced_pulls``). Hashable;
                      the key for every compiled-program cache. Changing a
                      static means a new program.
  * ``HyperParams`` — the continuous knobs of Algorithm 1 (α, γ, λ_c, ...)
                      as a registered pytree. They ride in
                      ``RouterState.hyper`` as traced f32 leaves, so an
                      operator can retune a live router — and a sweep can
                      stack a whole (α, γ) grid on the condition axis —
                      without a single recompile.

``RouterConfig`` remains the user-facing constructor: its static fields
ARE the statics, and ``cfg.hyper`` is the default ``HyperParams`` seeded
into ``init_state``. The pre-split flat hyper kwargs
(``RouterConfig(alpha=...)``) were deprecated for one release and are
now retired: passing one raises a ``TypeError`` naming the migration.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

Array = jax.Array


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class HyperParams:
    """Algorithm 1's continuous hyper-parameters as a pytree (state leaf).

    Defaults are the paper's production configuration (knee-point
    selection, Appendix A Table 3): alpha=0.01, gamma=0.997, n_eff=1164.

    Fields hold Python floats at construction time and f32 scalars (or
    stacked (N,) vectors, in a sweep-fabric grid) once loaded into
    ``RouterState.hyper`` via ``as_leaves``/``init_state``.
    """

    alpha: float | Array = 0.01          # UCB exploration coefficient
    gamma: float | Array = 0.997         # geometric forgetting factor, §3.3
    lambda_c: float | Array = 0.3        # static cost penalty weight, Eq. 2
    lambda0: float | Array = 1.0         # ridge regularisation A_a = lambda0*I
    eta: float | Array = 0.05            # dual ascent step size, Eq. 4
    alpha_ema: float | Array = 0.05      # EMA smoothing of the cost, Eq. 3
    lambda_bar: float | Array = 5.0      # projection cap for lambda_t, Eq. 4
    v_max: float | Array = 200.0         # staleness-inflation cap, Eq. 9
    c_floor: float | Array = 1e-4        # market cost floor ($/1k tok), Eq. 6
    c_ceil: float | Array = 0.1          # market cost ceiling ($/1k tok), Eq. 6
    tiebreak_scale: float | Array = 1e-7  # random tiebreak noise amplitude

    _RANGES = {
        "alpha": (lambda v: v >= 0.0, ">= 0"),
        "gamma": (lambda v: 0.0 < v <= 1.0, "in (0, 1]"),
        "lambda_c": (lambda v: v >= 0.0, ">= 0"),
        "lambda0": (lambda v: v > 0.0, "> 0"),
        "eta": (lambda v: v >= 0.0, ">= 0"),
        "alpha_ema": (lambda v: 0.0 < v <= 1.0, "in (0, 1]"),
        "lambda_bar": (lambda v: v >= 0.0, ">= 0"),
        "v_max": (lambda v: v >= 1.0, ">= 1"),
        "c_floor": (lambda v: v > 0.0, "> 0"),
        "c_ceil": (lambda v: v > 0.0, "> 0"),
        "tiebreak_scale": (lambda v: v >= 0.0, ">= 0"),
    }

    @staticmethod
    def validate_fields(**fields) -> None:
        """Range-check the given *concrete* values, raising ``ValueError``
        (not ``assert``, which vanishes under ``python -O``). Traced or
        stacked leaves cannot be inspected here; ``gamma`` is additionally
        clamp-checked at runtime (linucb.forgetting_factor)."""
        for name, v in fields.items():
            if name not in HYPER_FIELDS:
                raise TypeError(f"unknown hyper-parameter: {name!r}")
            if not isinstance(v, (int, float)):
                continue  # traced / stacked leaf: runtime-clamped instead
            ok, want = HyperParams._RANGES[name]
            if not ok(float(v)):
                raise ValueError(f"HyperParams.{name}={v!r}: must be {want}")
        cf, cc = fields.get("c_floor"), fields.get("c_ceil")
        if (isinstance(cf, (int, float)) and isinstance(cc, (int, float))
                and not float(cc) > float(cf)):
            raise ValueError(
                f"HyperParams.c_ceil={cc!r} must exceed c_floor={cf!r}")

    def validate(self) -> "HyperParams":
        """Range-check every concrete field (see ``validate_fields``)."""
        self.validate_fields(
            **{n: getattr(self, n) for n in HYPER_FIELDS})
        return self

    def as_leaves(self) -> "HyperParams":
        """Every field as an f32 array — the state-leaf representation."""
        return HyperParams(**{
            n: jnp.asarray(getattr(self, n), jnp.float32)
            for n in HYPER_FIELDS
        })

    def updated(self, **overrides) -> "HyperParams":
        """Copy with ``overrides`` applied (validated when concrete)."""
        bad = set(overrides) - set(HYPER_FIELDS)
        if bad:
            raise TypeError(f"unknown hyper-parameters: {sorted(bad)}")
        return dataclasses.replace(self, **overrides).validate()


HYPER_FIELDS = tuple(f.name for f in dataclasses.fields(HyperParams))


def _concrete(v):
    """A hyper leaf as a host float when possible (scalar float or
    concrete 0-d array), else None (tracer or stacked vector)."""
    if isinstance(v, (int, float)):
        return float(v)
    if isinstance(v, jax.core.Tracer):
        return None
    try:
        if jnp.ndim(v) == 0:
            return float(v)
    except TypeError:
        pass
    return None


@dataclasses.dataclass(frozen=True)
class Statics:
    """Shape/trace-affecting router configuration — the compiled-program
    identity. Hashable; every jit/runner cache keys on this (and ONLY
    this: hyper-parameters are data and never force a retrace)."""

    d: int = 26                  # context dim (25 PCA + bias), §2.2
    max_arms: int = 8            # fixed registry capacity (K <= max_arms)
    forced_pulls: int = 20       # burn-in pulls for a hot-swapped arm, §4.5
    dt_max: int = 4096           # numerical clamp on forgetting exponents
    backend: str = "jnp"         # batched routing backend (DESIGN.md §2/§11):
                                 # "jnp" oracle, "pallas" scoring kernel, or
                                 # "pallas_fused" select+update megakernel

    def __post_init__(self):
        if self.d < 2:
            raise ValueError(f"d={self.d}: need >= 2 (features + bias)")
        if self.max_arms < 1:
            raise ValueError(f"max_arms={self.max_arms}: need >= 1")
        if self.forced_pulls < 0:
            raise ValueError(f"forced_pulls={self.forced_pulls}: need >= 0")
        if self.dt_max < 1:
            raise ValueError(f"dt_max={self.dt_max}: need >= 1")
        if self.backend not in ("jnp", "pallas", "pallas_fused"):
            raise ValueError(
                f"backend={self.backend!r}: have "
                "('jnp', 'pallas', 'pallas_fused')")

    @property
    def statics(self) -> "Statics":
        return self


@dataclasses.dataclass(frozen=True, init=False)
class RouterConfig:
    """User-facing router configuration: ``Statics`` fields + the default
    ``HyperParams`` seeded into new states.

    Hyper-parameters are constructed via ``hyper=HyperParams(...)``. The
    pre-split flat kwargs (``RouterConfig(alpha=0.05)``) — deprecated
    since the §9 split — are retired: they raise a ``TypeError`` naming
    the migration, and the old ``cfg.alpha`` read-through attributes
    raise ``AttributeError`` pointing at ``cfg.hyper.alpha``.
    """

    d: int = 26
    max_arms: int = 8
    forced_pulls: int = 20
    dt_max: int = 4096
    backend: str = "jnp"
    hyper: HyperParams = HyperParams()

    def __init__(
        self,
        d: int = 26,
        max_arms: int = 8,
        forced_pulls: int = 20,
        dt_max: int = 4096,
        backend: str = "jnp",
        hyper: Optional[HyperParams] = None,
        **unknown,
    ):
        stale = sorted(set(unknown) & set(HYPER_FIELDS))
        if stale:
            raise TypeError(
                f"RouterConfig no longer accepts flat hyper-parameter "
                f"kwargs ({stale}); pass hyper=HyperParams(...) instead "
                "(DESIGN.md §9)")
        if unknown:
            raise TypeError(
                f"unknown RouterConfig arguments: {sorted(unknown)}")
        object.__setattr__(self, "d", d)
        object.__setattr__(self, "max_arms", max_arms)
        object.__setattr__(self, "forced_pulls", forced_pulls)
        object.__setattr__(self, "dt_max", dt_max)
        object.__setattr__(self, "backend", backend)
        object.__setattr__(self, "hyper", hyper or HyperParams())
        self.__post_init__()

    def __post_init__(self):
        # Field ranges mirror Statics (ValueError, not assert: validation
        # must survive ``python -O``).
        Statics(self.d, self.max_arms, self.forced_pulls, self.dt_max,
                self.backend)
        self.hyper.validate()

    @property
    def statics(self) -> Statics:
        """The trace-identity projection — the cache key for every
        compiled program (evaluate/scenario/sweep runner caches)."""
        return Statics(self.d, self.max_arms, self.forced_pulls,
                       self.dt_max, self.backend)

    def __getattr__(self, name: str):
        # Retired read-through properties (cfg.alpha etc.): fail with the
        # migration spelled out. AttributeError (not TypeError) so the
        # hasattr/getattr-default protocol keeps working for probes.
        if name in HYPER_FIELDS:
            raise AttributeError(
                f"RouterConfig.{name} was removed with the legacy shim; "
                f"read cfg.hyper.{name} instead (DESIGN.md §9)")
        raise AttributeError(name)


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class PacerState:
    """Budget pacer state (Eqs. 3-4). ``budget`` B is state, not config,
    so operators can re-target the ceiling at runtime without recompiling."""

    lam: Array      # scalar f32, dual variable lambda_t >= 0
    c_ema: Array    # scalar f32, EMA-smoothed realised cost  (init: B)
    budget: Array   # scalar f32, per-request ceiling B ($/req)
    enabled: Array  # scalar bool — False recovers the "no pacer" ablations


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class RouterState:
    """Full ParetoBandit state: per-arm sufficient statistics + pacer +
    the live hyper-parameters.

    Shapes use K = cfg.max_arms, d = cfg.d.
    """

    A: Array          # (K, d, d) f32 design matrices (ridge included)
    A_inv: Array      # (K, d, d) f32 cached inverses (Sherman-Morrison)
    b: Array          # (K, d)    f32 reward accumulators
    theta: Array      # (K, d)    f32 ridge solutions A^{-1} b
    last_upd: Array   # (K,) i32  step of last statistics update
    last_play: Array  # (K,) i32  step of last dispatch
    active: Array     # (K,) bool registry mask
    price: Array      # (K,) f32  blended $/request (hard-ceiling + EMA use this)
    c_tilde: Array    # (K,) f32  log-normalised unit cost in [0,1], Eq. 6
    t: Array          # scalar i32 global step
    pacer: PacerState
    force_arm: Array   # scalar i32, -1 when no forced exploration
    force_left: Array  # scalar i32, remaining forced pulls
    key: Array         # PRNG key for random tiebreaks
    hyper: HyperParams  # live (α, γ, λ_c, ...) — f32 leaves, retunable
    # Optional tenant plane (DESIGN.md §15): a ``tenancy.TenantTable``
    # of (..., T) per-tenant pacer leaves sharing this state's LinUCB
    # statistics, or None for the single-tenant paper configuration.
    # Typed ``object`` to keep types.py import-free of tenancy.py
    # (tenancy imports PacerState from here).
    tenants: Optional[object] = None


# Plane ownership of RouterState leaves (gateway double-buffering,
# DESIGN.md §13). ``select_batch`` writes only SELECT_LEAVES (dispatch
# bookkeeping); ``update_batch`` writes only LEARN_LEAVES (sufficient
# statistics + pacer). The partitions are disjoint, so a learner can
# compute on a grabbed state while the select plane advances, and the
# publish step merges LEARN_LEAVES back without clobbering either side.
# Control-plane ops (registry add/delete, set_budget, set_hyperparams)
# write CONTROL_LEAVES (and sometimes force_left) and must serialize
# against both planes — the gateway takes its state lock for those.
LEARN_LEAVES = ("A", "A_inv", "b", "theta", "last_upd", "pacer", "tenants")
SELECT_LEAVES = ("t", "last_play", "key", "force_left")
CONTROL_LEAVES = ("active", "price", "c_tilde", "force_arm", "hyper")


def validate_leaf_partition() -> None:
    """Assert LEARN/SELECT/CONTROL exactly partition RouterState's
    fields: pairwise disjoint, union = every field. A field outside
    every plane would silently lose writes in the gateway publish
    merge; a field in two planes would be written by two planes
    concurrently. Cheap (field-name sets only) — the serving gateway
    calls this at import time so a drifted partition fails fast, before
    any state is published."""
    fields = {f.name for f in dataclasses.fields(RouterState)}
    planes = {"LEARN_LEAVES": LEARN_LEAVES, "SELECT_LEAVES": SELECT_LEAVES,
              "CONTROL_LEAVES": CONTROL_LEAVES}
    union: set = set()
    for name, leaves in planes.items():
        s = set(leaves)
        if len(s) != len(leaves):
            raise ValueError(f"{name} has duplicate entries: {leaves}")
        dup = union & s
        if dup:
            raise ValueError(
                f"leaf plane overlap: {sorted(dup)} claimed by {name} "
                "and an earlier plane — two writer planes on one leaf")
        union |= s
    if union != fields:
        missing = sorted(fields - union)
        unknown = sorted(union - fields)
        raise ValueError(
            "LEARN/SELECT/CONTROL_LEAVES must exactly partition "
            f"RouterState fields; missing={missing} unknown={unknown}")


def merge_learn_leaves(select_side: "RouterState",
                       learn_side: "RouterState") -> "RouterState":
    """The gateway publish merge: LEARN_LEAVES from the learner's output,
    everything else (select bookkeeping + control plane) from the live
    select-side state. Pure; safe under jit."""
    return dataclasses.replace(
        select_side,
        **{n: getattr(learn_side, n) for n in LEARN_LEAVES})


def with_hyperparams(
    state: RouterState,
    hyper: Optional[HyperParams] = None,
    **overrides,
) -> RouterState:
    """Retune a state's hyper-parameters in place (pure; jit/vmap-safe).

    Either a full replacement ``hyper`` or field ``overrides`` on the
    state's current values. The scenario engine's ``HyperShift`` event
    and ``PortfolioServer.set_hyperparams`` lower to this.
    """
    hp = state.hyper if hyper is None else hyper.validate().as_leaves()
    if overrides:
        HyperParams.validate_fields(**overrides)  # before they become arrays
        hp = dataclasses.replace(hp, **{
            k: jnp.asarray(v, jnp.float32) for k, v in overrides.items()
        })
        # Cross-field check against the MERGED values: overriding only
        # c_ceil below the state's current c_floor would silently zero
        # the Eq. 6 cost range. Best effort — traced or stacked leaves
        # cannot be compared here.
        cf, cc = _concrete(hp.c_floor), _concrete(hp.c_ceil)
        if cf is not None and cc is not None and not cc > cf:
            raise ValueError(
                f"HyperParams.c_ceil={cc!r} must exceed c_floor={cf!r} "
                "(merged with the state's current values)")
    return dataclasses.replace(state, hyper=hp)


@dataclasses.dataclass(frozen=True)
class ArmPrior:
    """Offline sufficient statistics for warm start (§3.4)."""

    A_off: jnp.ndarray   # (d, d)
    b_off: jnp.ndarray   # (d,)

    @property
    def theta_off(self) -> jnp.ndarray:
        return jnp.linalg.solve(self.A_off, self.b_off)


def log_normalized_cost(price_per_1k: Array, hp: HyperParams) -> Array:
    """Eq. 6: compress the ~530x price range into [0, 1] on a log scale.

    ``price_per_1k`` is the blended $/1k-token rate. Values at or below the
    market floor map to 0 (the paper: "any model priced at or below the
    floor is treated as zero-cost").
    """
    c_floor = jnp.asarray(hp.c_floor, jnp.float32)
    c_ceil = jnp.asarray(hp.c_ceil, jnp.float32)
    num = jnp.log(jnp.maximum(price_per_1k, c_floor)) - jnp.log(c_floor)
    den = jnp.log(c_ceil) - jnp.log(c_floor)
    return jnp.clip(num / den, 0.0, 1.0)


def init_state(
    cfg: RouterConfig,
    prices_per_req: jnp.ndarray,
    prices_per_1k: jnp.ndarray,
    budget: float,
    *,
    key: Optional[Array] = None,
    active: Optional[jnp.ndarray] = None,
    pacer_enabled: bool = True,
    hyper: Optional[HyperParams] = None,
    tenants: Optional[object] = None,
) -> RouterState:
    """Uninformative (tabula-rasa) initial state; warm start via warmup.py.

    Args:
      prices_per_req: (K,) blended realised $/request per arm (used by the
        hard ceiling and reported compliance).
      prices_per_1k: (K,) blended $/1k-token rate per arm (drives Eq. 6).
      budget: operator ceiling B in $/request.
      hyper: overrides ``cfg.hyper`` as the state's live hyper-parameters.
      tenants: optional ``tenancy.TenantTable`` enabling per-tenant pacing
        (DESIGN.md §15); the scalar pacer stays as the portfolio-wide
        aggregate view but is inert when a table is present.
    """
    K, d = cfg.max_arms, cfg.d
    hp = (cfg.hyper if hyper is None else hyper).as_leaves()
    prices_per_req = jnp.asarray(prices_per_req, jnp.float32)
    prices_per_1k = jnp.asarray(prices_per_1k, jnp.float32)
    assert prices_per_req.shape == (K,), (prices_per_req.shape, K)
    if active is None:
        active = jnp.ones((K,), bool)
    eye = jnp.eye(d, dtype=jnp.float32)
    A = jnp.tile(eye[None], (K, 1, 1)) * hp.lambda0
    A_inv = jnp.tile(eye[None], (K, 1, 1)) / hp.lambda0
    if key is None:
        key = jax.random.PRNGKey(0)
    return RouterState(
        A=A,
        A_inv=A_inv,
        b=jnp.zeros((K, d), jnp.float32),
        theta=jnp.zeros((K, d), jnp.float32),
        last_upd=jnp.zeros((K,), jnp.int32),
        last_play=jnp.zeros((K,), jnp.int32),
        active=jnp.asarray(active, bool),
        price=prices_per_req,
        c_tilde=log_normalized_cost(prices_per_1k, hp),
        t=jnp.zeros((), jnp.int32),
        pacer=PacerState(
            lam=jnp.zeros((), jnp.float32),
            c_ema=jnp.asarray(budget, jnp.float32),  # \bar c_0 <- B (Alg. 1)
            budget=jnp.asarray(budget, jnp.float32),
            enabled=jnp.asarray(pacer_enabled, bool),
        ),
        force_arm=jnp.asarray(-1, jnp.int32),
        force_left=jnp.zeros((), jnp.int32),
        key=key,
        hyper=hp,
        tenants=tenants,
    )
