"""A plain reference of the paper's non-stationary events around
Algorithm 1 (arXiv:2604.00136 §4.3-4.5): one element of a timeline
Monte Carlo, one request at a time, following the program's arms. It
imports nothing of the program; the algorithm is ``algo1``'s, in
float64 (or in ``algo1.Arith``'s lower precision, as the control).

Semantics, for an element with stream seed ``s`` and one step per event
(the program's DESIGN.md §6):

* the stream is iid over the split: request ``t`` is prompt
  ``default_rng(stream_seed_base + s).integers(0, n, size=T)[t]``;
* an event at step ``t`` takes effect before request ``t`` is routed;
* ``PriceChange(arm, m)``, silent: from its step on, ``arm``'s realised
  cost on every prompt is ``m`` times the base cost; the router's rate
  card (hard ceiling, Eq. 6 cost) keeps the base price;
* ``QualityShift(arm, target)``: from its step on, ``arm``'s reward on
  every prompt moves by one amount, so that its mean over the split is
  ``target``, and is clipped to [0, 1];
* ``AddArm(slot)``, cold with forced exploration: at its step the slot
  becomes a candidate at its base price, with tabula rasa statistics
  (A = lambda0 I, b = 0), and its update and dispatch clocks set to the
  step; the next ``forced_pulls`` requests go to it whatever the scores
  and the ceiling, then UCB decides again.

Departures from that description:

* a shifted or repriced column is computed in float32, as the data are
  stored (the column mean is numpy's float32 mean over the split), so
  the realised outcomes can be compared exactly;
* the program breaks ties between equal scores with noise of scale
  1e-7; the reference has none, and the gap of a chosen arm below the
  reference's best absorbs it.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from perfbench.reference import algo1


@dataclasses.dataclass(frozen=True)
class Element:
    """One element's stream seed and the step of each event (aligned
    with the configuration's ``events``)."""

    seed: int
    steps: tuple


def stream_rows(n: int, T: int, stream_seed_base: int, seed: int):
    """The prompts of an iid stream of ``T`` requests over ``n``."""
    return np.random.default_rng(stream_seed_base + int(seed)).integers(
        0, n, size=T)


def shifted(values: np.ndarray, event: dict) -> np.ndarray:
    """One arm's (N,) float32 column after a stream event."""
    col = np.asarray(values, np.float32)
    if event["kind"] == "PriceChange":
        return col * float(event["multiplier"])
    shift = col.mean() - float(event["target_mean"])
    return np.clip(col - shift, 0.0, 1.0)


def outcomes(config, test, rows, steps):
    """(T, K) rewards and costs of every arm at each request, with each
    stream event in force from its step on."""
    R = test.rewards[rows].astype(np.float32)
    C = test.costs[rows].astype(np.float32)
    t = np.arange(len(rows))
    for ev, t_ev in zip(config["events"], steps):
        if ev["kind"] == "QualityShift":
            col = shifted(test.rewards[:, ev["arm"]], ev)[rows]
            R[:, ev["arm"]] = np.where(t >= t_ev, col, R[:, ev["arm"]])
        elif ev["kind"] == "PriceChange" and not ev["recalibrate"]:
            col = shifted(test.costs[:, ev["arm"]], ev)[rows]
            C[:, ev["arm"]] = np.where(t >= t_ev, col, C[:, ev["arm"]])
    return R, C


def _add_arm(st: algo1.Router, slot: int, hp: algo1.Hyper) -> None:
    d = st.A.shape[1]
    st.A[slot] = hp.lambda0 * np.eye(d)
    st.Ainv[slot] = np.eye(d) / hp.lambda0
    st.b[slot] = 0.0
    st.theta[slot] = 0.0
    st.last_upd[slot] = st.last_play[slot] = st.t


def replay(config, traffic, train, test, budget, element: Element, arms,
           control: Optional[algo1.Arith] = None):
    """One element through the plain per-request loop, following the
    program's ``arms``. Returns (gaps, lams before each step, rewards
    and costs of the chosen arms, final router): the gap of each chosen
    arm below the reference's best candidate, 0 where a forced pull
    chose the newcomer and inf where it did not. With ``control``, a
    reference in that arithmetic runs in lockstep and stands in the
    program's place: the gaps are of its own choices, the lams and the
    final router its own."""
    hp = algo1.Hyper(alpha=config["alpha"], gamma=config["gamma"])
    K = config["max_arms"]
    ars = [algo1.Arith()] + ([control] if control else [])
    sts = [algo1.warm_router(train.contexts, train.rewards, K,
                             config["n_eff"], [budget], hp, ar)
           for ar in ars]
    pf = algo1.portfolio(test.prices_per_req, test.prices_per_1k, K, hp)
    pf = dataclasses.replace(pf, active=np.arange(K) < config["init_active"])
    T = int(traffic["horizon"])
    rows = stream_rows(test.n, T, traffic["stream_seed_base"], element.seed)
    R, C = outcomes(config, test, rows, element.steps)
    X = test.contexts[rows]
    adds = {t: ev for ev, t in zip(config["events"], element.steps)
            if ev["kind"] == "AddArm"}
    arms = np.asarray(arms, np.int64)
    forced_arm, forced_left = -1, 0
    gaps, lams = np.empty(T), np.empty(T)
    for i in range(T):
        if i in adds:
            slot = adds[i]["slot"]
            active = pf.active.copy()
            active[slot] = True
            pf = dataclasses.replace(pf, active=active)
            for st in sts:
                _add_arm(st, slot, hp)
            if adds[i]["forced_exploration"]:
                forced_arm, forced_left = slot, int(config["forced_pulls"])
        a = arms[i:i + 1]
        lams[i] = sts[-1].pacers.lam[0]
        if forced_left > 0:
            gaps[i] = 0.0 if a[0] == forced_arm else np.inf
            forced_left -= 1
        else:
            s, cand = algo1.scores(sts[0], pf, X[i:i + 1],
                                   sts[0].pacers.lam[:1], hp)
            chosen = a
            if control:
                sc, cc = algo1.scores(sts[1], pf, X[i:i + 1],
                                      sts[1].pacers.lam[:1], hp, control)
                chosen = np.argmax(np.where(cc, sc, -np.inf), axis=1)
            gaps[i] = algo1.arm_gaps(s, cand, chosen)[0]
        for st, ar in zip(sts, ars):
            algo1.dispatch(st, a)
            algo1.fold_rows(st, st.t, a, X[i:i + 1], R[i:i + 1, a[0]], hp,
                            ar)
            algo1.fold_costs(st.pacers, C[i:i + 1, a[0]], None, hp, ar)
    return gaps, lams, R[np.arange(T), arms], C[np.arange(T), arms], sts[-1]
