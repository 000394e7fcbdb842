"""A plain reference of the paper's Algorithm 1, written from the paper
(arXiv:2604.00136, §3, Eqs. 2-4, 6, 9-12) and independent of the
program: it imports nothing of it and takes nothing it made.

It is computed in float64 on the host, one request at a time where the
algorithm is sequential. Every contraction goes through ``Arith.mm`` and
every other arithmetic result through ``Arith.rnd``, so the same code
runs in a lower precision as the control (``perfbench.reference.lowp``).

Semantics, per arm a with context x, reward r, cost c:

* Eq. 2 score: theta_a.x + alpha sqrt(x^T A_a^-1 x / max(g^dt_a, 1/V_max))
  - (lambda_c + lambda) c~_a, where g = gamma and dt_a counts requests
  since the arm's last update or dispatch (clamped to dt_max);
* hard ceiling: with lambda > 0 only arms priced at most
  max_price / (1 + lambda) are candidates (never an empty set);
* update (lines 18-23): decay A, b by g^dt and A^-1 by 1/g^dt, then the
  rank-1 (Sherman-Morrison) step and theta = A^-1 b;
* pacer (Eqs. 3-4): c_ema <- (1 - a) c_ema + a c;
  lambda <- clip(lambda + eta (c_ema / B - 1), 0, lambda_bar);
* warm start (Eqs. 10-12): s = n_eff / A_off[d-1, d-1],
  A = s A_off + lambda0 I, b = s b_off + lambda0 A_off^-1 b_off.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import numpy as np


class Arith:
    """Exact arithmetic: float64, no rounding between operations."""

    dtype = np.float64

    def mm(self, a, b):
        return np.matmul(a, b)

    def rnd(self, x):
        return x

    def scalar(self, x: float) -> float:
        return x


@dataclasses.dataclass(frozen=True)
class Hyper:
    """Algorithm 1's constants (the paper's Appendix A defaults)."""

    alpha: float = 0.01
    gamma: float = 0.997
    lambda_c: float = 0.3
    lambda0: float = 1.0
    eta: float = 0.05
    alpha_ema: float = 0.05
    lambda_bar: float = 5.0
    v_max: float = 200.0
    c_floor: float = 1e-4
    c_ceil: float = 0.1
    dt_max: int = 4096


@dataclasses.dataclass
class Pacers:
    """One or T pacers: (T,) float arrays (T = 1 for the portfolio)."""

    lam: np.ndarray
    c_ema: np.ndarray
    budget: np.ndarray
    pulls: np.ndarray
    spend: np.ndarray

    @classmethod
    def fresh(cls, budgets) -> "Pacers":
        b = np.asarray(budgets, np.float64).ravel()
        return cls(lam=np.zeros_like(b), c_ema=b.copy(), budget=b.copy(),
                   pulls=np.zeros(b.shape, np.int64),
                   spend=np.zeros_like(b))

    def copy(self) -> "Pacers":
        return Pacers(*(np.array(getattr(self, f.name))
                        for f in dataclasses.fields(self)))


@dataclasses.dataclass
class Router:
    """Learner-plane state (statistics, per-arm update clock, pacers)
    plus the select-plane clock and dispatch record."""

    A: np.ndarray         # (K, d, d)
    Ainv: np.ndarray      # (K, d, d)
    b: np.ndarray         # (K, d)
    theta: np.ndarray     # (K, d)
    last_upd: np.ndarray  # (K,) int
    last_play: np.ndarray  # (K,) int
    t: int
    pacers: Pacers

    def learn_copy(self) -> "Router":
        return Router(self.A.copy(), self.Ainv.copy(), self.b.copy(),
                      self.theta.copy(), self.last_upd.copy(),
                      self.last_play.copy(), self.t, self.pacers.copy())


@dataclasses.dataclass(frozen=True)
class Portfolio:
    """Arm slots: ``active`` mask, $/request price, Eq. 6 unit cost."""

    active: np.ndarray    # (K,) bool
    price: np.ndarray     # (K,) $/request
    c_tilde: np.ndarray   # (K,) in [0, 1]


def portfolio(prices_per_req, prices_per_1k, max_arms: int,
              hp: Hyper) -> Portfolio:
    """Slots 0..k-1 hold the arms; the rest are inactive (Eq. 6 costs)."""
    k = len(prices_per_req)
    active = np.arange(max_arms) < k
    price = np.full(max_arms, 1e9)
    price[:k] = np.asarray(prices_per_req, np.float64)
    p1k = np.full(max_arms, 1e9)
    p1k[:k] = np.asarray(prices_per_1k, np.float64)
    lf, lc = math.log(hp.c_floor), math.log(hp.c_ceil)
    c_tilde = np.clip((np.log(np.maximum(p1k, hp.c_floor)) - lf) / (lc - lf),
                      0.0, 1.0)
    return Portfolio(active=active, price=price, c_tilde=c_tilde)


def warm_router(train_X, train_R, max_arms: int, n_eff: float,
                budgets, hp: Hyper, ar: Arith = Arith()) -> Router:
    """Tabula-rasa slots (A = lambda0 I) with the offline priors of
    Eqs. 10-12 loaded into the arms that have train rewards."""
    X = np.asarray(train_X, ar.dtype)
    d = X.shape[1]
    eye = np.eye(d)
    A = np.tile(eye * hp.lambda0, (max_arms, 1, 1)).astype(ar.dtype)
    Ainv = np.tile(eye / hp.lambda0, (max_arms, 1, 1)).astype(ar.dtype)
    b = np.zeros((max_arms, d), ar.dtype)
    theta = np.zeros((max_arms, d), ar.dtype)
    XtX = ar.mm(X.T, X)
    for a in range(np.asarray(train_R).shape[1]):
        A_off = hp.lambda0 * eye + XtX
        b_off = ar.mm(X.T, np.asarray(train_R[:, a], ar.dtype))
        s = n_eff / A_off[d - 1, d - 1]
        A[a] = s * A_off + hp.lambda0 * eye
        b[a] = s * b_off + hp.lambda0 * np.linalg.solve(A_off, b_off)
        Ainv[a] = np.linalg.inv(A[a])
        theta[a] = ar.mm(Ainv[a], b[a])
    return Router(A=A, Ainv=Ainv, b=b, theta=theta,
                  last_upd=np.zeros(max_arms, np.int64),
                  last_play=np.zeros(max_arms, np.int64), t=0,
                  pacers=Pacers.fresh(budgets))


def forgetting(hp: Hyper, dt):
    return hp.gamma ** np.clip(dt, 0, hp.dt_max).astype(np.float64)


def candidates(pf: Portfolio, lam: np.ndarray) -> np.ndarray:
    """(R, K) hard-ceiling candidate sets for R duals (lines 4-8)."""
    c_max = pf.price[pf.active].max()
    lam = np.asarray(lam, np.float64)[:, None]
    mask = np.where(lam > 0.0, pf.price[None, :] <= c_max / (1.0 + lam),
                    True) & pf.active[None, :]
    empty = ~mask.any(axis=1)
    if empty.any():
        cheapest = np.argmin(np.where(pf.active, pf.price, np.inf))
        mask[empty, cheapest] = True
    return mask


def scores(st: Router, pf: Portfolio, X, lam_rows, hp: Hyper,
           ar: Arith = Arith()):
    """(B, K) Eq. 2 scores of a block under the block-entry staleness,
    and the (B, K) candidate sets, for per-row duals ``lam_rows``."""
    X = np.asarray(X, ar.dtype)
    dt = st.t - np.maximum(st.last_upd, st.last_play)
    infl = np.maximum(forgetting(hp, dt), 1.0 / hp.v_max)
    exploit = ar.mm(X, st.theta.T)
    quad = np.zeros_like(exploit)      # inactive slots are never candidates
    for k in np.flatnonzero(pf.active):
        quad[:, k] = np.sum(ar.rnd(ar.mm(X, st.Ainv[k])) * X, axis=1)
    quad = ar.rnd(np.maximum(quad, 0.0))
    explore = ar.rnd(hp.alpha * np.sqrt(ar.rnd(quad / infl[None, :])))
    penalty = ar.rnd((hp.lambda_c + np.asarray(lam_rows)[:, None])
                     * pf.c_tilde[None, :])
    s = ar.rnd(ar.rnd(exploit + explore) - penalty)
    return s, candidates(pf, lam_rows)


def arm_gaps(s: np.ndarray, cand: np.ndarray, arms) -> np.ndarray:
    """How far each chosen arm's reference score lies below the best
    candidate's (inf where the arm is not a candidate)."""
    arms = np.asarray(arms, np.int64)
    best = np.where(cand, s, -np.inf).max(axis=1)
    rows = np.arange(arms.shape[0])
    chosen = s[rows, arms]
    return np.where(cand[rows, arms], best - chosen, np.inf)


def dispatch(st: Router, arms) -> None:
    """Select-plane bookkeeping of a routed block: the clock advances by
    B and each arm records the step of its last dispatch in the block."""
    arms = np.asarray(arms, np.int64)
    played = st.t + 1 + np.arange(arms.shape[0])
    np.maximum.at(st.last_play, arms, played)
    st.t += arms.shape[0]


def fold_rows(st: Router, t_now: int, arms, X, rewards, hp: Hyper,
              ar: Arith = Arith()) -> None:
    """Lines 17-23 for rows in arrival order, all applied at clock
    ``t_now``: an arm decays once by gamma^(t_now - last update), then
    takes its rank-1 steps."""
    X = np.asarray(X, ar.dtype)
    r = np.asarray(rewards, ar.dtype)
    for a in np.unique(arms):
        rows = np.flatnonzero(arms == a)
        g = float(forgetting(hp, np.asarray(t_now - st.last_upd[a])))
        A, Ainv, b = ar.rnd(st.A[a] * g), ar.rnd(st.Ainv[a] / g), \
            ar.rnd(st.b[a] * g)
        for i in rows:
            x = X[i]
            A = ar.rnd(A + np.outer(x, x))
            Ax = ar.mm(Ainv, x)
            denom = ar.rnd(1.0 + ar.mm(x, Ax))
            Ainv = ar.rnd(Ainv - ar.rnd(np.outer(Ax, Ax) / denom))
            b = ar.rnd(b + ar.rnd(r[i] * x))
        st.A[a], st.Ainv[a], st.b[a] = A, Ainv, b
        st.theta[a] = ar.mm(Ainv, b)
        st.last_upd[a] = t_now


def fold_costs(p: Pacers, costs, tenants: Optional[np.ndarray], hp: Hyper,
               ar: Arith = Arith()) -> None:
    """Eqs. 3-4 over costs in arrival order, each into its tenant's
    pacer (tenant 0 when ``tenants`` is None)."""
    q = ar.scalar
    a, eta, lbar = hp.alpha_ema, hp.eta, hp.lambda_bar
    lam, c_ema, budget = p.lam.tolist(), p.c_ema.tolist(), p.budget.tolist()
    spend = p.spend.tolist()
    ten = (np.zeros(len(costs), np.int64) if tenants is None
           else np.asarray(tenants, np.int64))
    for i, c in zip(ten.tolist(), np.asarray(costs, np.float64).tolist()):
        e = q(q(q(1.0 - a) * c_ema[i]) + q(a * c))
        lam[i] = min(max(q(lam[i] + q(eta * q(q(e / budget[i]) - 1.0))),
                         0.0), lbar)
        c_ema[i] = e
        spend[i] = q(spend[i] + c)
    p.lam[:], p.c_ema[:], p.spend[:] = lam, c_ema, spend
    np.add.at(p.pulls, ten, 1)
