"""Gateway entry: open-loop traffic through ``RouterGateway``.

One front thread plays the schedule: it submits each request when due
(``submit`` routes a window that fills) and flushes expired windows with
``poll``. One learner thread waits out the judge delay of each routed
block, then enqueues its feedback and ticks (``enqueue_feedback`` +
``learn_tick``) whenever rows are pending. Latency is timed from the
schedule, not from ``submit``, so a stalled front thread shows.

After the window the run replays what the gateway did (blocks routed,
each with the snapshot version it was scored under; learner ticks, each
with the clock it folded at) through the plain reference and compares
admission, selection and the published learner state.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import threading
import time
from typing import Dict, List, Optional

import numpy as np

from perfbench import arrivals, data, devicemem
from perfbench.cell import Cell, Check, Outcome, percentile
from perfbench.reference import algo1

# Published snapshots compared with the reference, drawn from the seed
# among those of the window, and the run's last publish.
SAMPLED_PUBLISHES = 256
# How long after the window closes a request may still be routed (and
# a row published) before it counts as failed.
GRACE_S = 30.0
# Threads that compile block shapes during set-up.
WARM_WORKERS = 8


def _hyper(config) -> algo1.Hyper:
    return algo1.Hyper(alpha=config["alpha"], gamma=config["gamma"])


def _program_state(config, sched, seed, train, test):
    """The gateway's initial state, built through the program's public
    entry points (warm priors, ``make_states``, ``tenancy.make_table``)."""
    import jax

    from repro.core import evaluate, tenancy
    from repro.core.types import HyperParams, RouterConfig

    cfg = RouterConfig(d=config["d"], max_arms=config["max_arms"],
                       hyper=HyperParams(alpha=config["alpha"],
                                         gamma=config["gamma"]))
    priors = evaluate.fit_warmup_priors(cfg, train)
    table = (tenancy.make_table(sched.ceilings)
             if sched.ceilings is not None else None)
    states = evaluate.make_states(
        cfg, test, config["budget_per_request"], seeds=(seed % 2 ** 32,),
        priors=priors, n_eff=config["n_eff"], tenants=table)
    return cfg, jax.tree.map(lambda leaf: leaf[0], states)


def _gateway(cfg, state, adm):
    from repro.serving.gateway import MicroBatcher, RouterGateway

    return RouterGateway(cfg, state, batcher=MicroBatcher(
        max_batch=adm["max_batch"], max_wait_s=adm["max_wait_s"]))


def _warm_shapes(cfg, state, adm, test, tenanted):
    """Compile every block shape the cell's traffic can meet (the
    traffic file's ``warm_rows``, routed and folded) on throwaway
    gateways: the compiled programs are shared by every gateway of the
    same configuration. Workers compile in parallel (XLA releases the
    interpreter lock while it compiles)."""
    import concurrent.futures

    import jax

    lo, hi = adm["warm_rows"]
    sizes = list(range(hi, lo - 1, -1))
    workers = max(1, min(WARM_WORKERS, len(sizes)))

    def warm(part):
        gw = _gateway(cfg, state, adm)
        for B in part:
            ids = list(range(B))
            X = test.contexts[:B]
            tids = np.zeros(B, np.int32) if tenanted else None
            res = gw.route_block(ids, X, tenant_ids=tids)
            rows = np.arange(B)
            gw.enqueue_feedback(ids, res.arms, test.rewards[rows, res.arms],
                                test.costs[rows, res.arms])
            jax.block_until_ready(gw.learn_tick().state)

    with concurrent.futures.ThreadPoolExecutor(workers) as pool:
        for f in [pool.submit(warm, sizes[i::workers])
                  for i in range(workers)]:
            f.result()


class _Log:
    """What the two threads record; lists only grow, one writer each."""

    def __init__(self, n):
        self.submit_t = np.zeros(n)         # schedule seconds
        self.route_t = np.full(n, np.nan)   # arm known on the host
        self.block_of = np.full(n, -1, np.int64)
        self.routed_twice = 0
        self.blocks: List[tuple] = []       # (ids, arms, version, t_done)
        self.ticks: List[tuple] = []        # (t0, t_pub, version, blocks,
        #                                      state, rows_kept)
        self.fb_sent = 0


def _front(gw, sched, X, T0, log, feedback, adm, delay, clock, stop):
    due = sched.due.tolist()
    ten = sched.tenant.tolist()
    n = len(due)
    max_wait = adm["max_wait_s"]
    submit, poll = gw.submit, gw.poll

    def record(res):
        t = clock() - T0
        j = len(log.blocks)
        ids = np.asarray(res.request_ids, np.int64)
        if np.any(log.block_of[ids] >= 0):
            log.routed_twice += int(np.sum(log.block_of[ids] >= 0))
        log.block_of[ids] = j
        log.route_t[ids] = t
        log.blocks.append((ids, np.asarray(res.arms), res.version, t))
        feedback.append((t + delay, j))

    i, opened = 0, None
    while not stop.is_set():
        now = clock() - T0
        while i < n and due[i] <= now:
            log.submit_t[i] = clock() - T0
            res = submit(i, X[i], tenant=ten[i])
            if res is not None:
                record(res)
                opened = None
            elif opened is None:
                opened = clock()
            i += 1
        if opened is not None and clock() - opened >= max_wait:
            res = poll()
            if res is not None:
                record(res)
                opened = None
        if i >= n and opened is None:
            return
        wake = T0 + due[i] if i < n else float("inf")
        if opened is not None:
            wake = min(wake, opened + max_wait)
        pause = wake - clock()
        if pause > 0:
            time.sleep(pause)


def _learner(gw, sched, log, feedback, test, T0, until, clock, span, stop):
    import jax

    R, C = test.rewards, test.costs
    prompt = sched.prompt
    while not stop.is_set():
        now = clock() - T0
        take = []
        while feedback and feedback[0][0] <= now:
            take.append(feedback.popleft()[1])
        if not take:
            if now > until:
                return
            nxt = feedback[0][0] if feedback else now + 0.001
            time.sleep(min(max(nxt - now, 0.0), 0.001))
            continue
        t0 = clock() - T0
        kept = 0
        with span("learn_tick"):
            for j in take:
                ids, arms = log.blocks[j][0], log.blocks[j][1]
                p = prompt[ids]
                log.fb_sent += len(ids)
                kept += gw.enqueue_feedback(ids.tolist(), arms, R[p, arms],
                                            C[p, arms])
            snap = gw.learn_tick()
            if snap is not None:
                jax.block_until_ready(snap.state)
        if snap is None:
            continue
        log.ticks.append((t0, clock() - T0, snap.version, take, snap.state,
                          kept))


def prepare(cell: Cell, warm: bool = True):
    """Set-up before traffic: data, schedule, the program's initial
    state, and (``warm``) every block shape compiled."""
    config, traffic = cell.config, cell.traffic
    train, _, test = data.for_config(config)
    sched = arrivals.schedule(traffic, config, cell.seed, cell.seconds,
                              test.n)
    cfg, state0 = _program_state(config, sched, cell.seed, train, test)
    if warm:
        _warm_shapes(cfg, state0, traffic["admission"], test,
                     sched.ceilings is not None)
    return cfg, state0, sched, train, test


def play(cell, cfg, state0, sched, test, tracer=None, on_open=None):
    """Play the schedule through a fresh gateway; returns the log and
    the programs compiled while the window was open. ``on_open`` runs
    when the window opens (it stamps the end of set-up)."""
    adm = cell.traffic["admission"]
    delay = float(cell.traffic["judge_delay_s"])
    gw = _gateway(cfg, state0, adm)
    X = test.contexts[sched.prompt]
    log = _Log(sched.n)
    feedback: collections.deque = collections.deque()
    stop = threading.Event()
    clock = time.perf_counter
    span = tracer.span if tracer else (lambda name: contextlib.nullcontext())
    if tracer:
        route_block = gw.route_block

        def traced_route_block(*a, **k):
            with span("route_block"):
                return route_block(*a, **k)

        gw.route_block = traced_route_block
    compiles = devicemem.CompileCounter()
    errors: List[BaseException] = []

    def guarded(fn, *a):
        def target():
            try:
                fn(*a)
            except BaseException as e:  # re-raised by the main thread
                errors.append(e)
                stop.set()
        return target

    T0 = clock() + 0.05
    front = threading.Thread(target=guarded(
        _front, gw, sched, X, T0, log, feedback, adm, delay, clock, stop),
        name="front")
    learner = threading.Thread(target=guarded(
        _learner, gw, sched, log, feedback, test, T0, sched.window_close,
        clock, span, stop), name="learner")
    front.start()
    learner.start()
    time.sleep(max(0.0, T0 + sched.window_open - clock()))
    if on_open:
        on_open()
    compiles.start()
    if tracer:
        tracer.start()
        time.sleep(max(0.0, T0 + min(sched.window_close, sched.window_open
                                     + tracer.seconds) - clock()))
        tracer.stop()
    time.sleep(max(0.0, T0 + sched.window_close - clock()))
    front.join(GRACE_S)
    learner.join(GRACE_S)
    n_compiles = compiles.stop()
    stop.set()
    front.join()
    learner.join()
    if errors:
        raise errors[0]
    return log, n_compiles


def run(cell: Cell) -> Outcome:
    import jax

    from perfbench import trace as trace_lib

    cfg, state0, sched, train, test = prepare(cell)
    tracer = trace_lib.Tracer(cell.trace_dir) if cell.trace else None
    opened = []
    log, n_compiles = play(
        cell, cfg, state0, sched, test, tracer,
        on_open=lambda: opened.append(time.perf_counter() - cell.t_process))
    memory_peak = devicemem.peak_bytes(jax.devices()[:1])
    e2e, layer = measure(cell, sched, log)
    ev = evidence(cell, sched, log)
    checks = compare(cell, readings(cell, sched, ev, train, test))
    layer["compiles_in_window"] = n_compiles
    return Outcome(
        end_to_end=e2e, layer=layer, checks=checks,
        attempted=int(sched.in_window().sum()),
        failed=int(ev.unrouted.sum()), memory_peak_bytes=memory_peak,
        setup_s=opened[0], trace=tracer)


def measure(cell, sched, log):
    """The end-to-end metrics and the harness's per-layer counts."""
    win = sched.in_window()
    o, c = sched.window_open, sched.window_close
    lat = log.route_t - sched.due
    end = max(c, np.nanmax(log.route_t))
    lat_w = np.where(np.isnan(lat), end - sched.due, lat)[win]
    t_done = np.asarray([b[3] for b in log.blocks])
    B = np.asarray([len(b[0]) for b in log.blocks])
    in_w = (t_done >= o) & (t_done < c)
    # Feedback lag: each routed row is due a judge delay after its block;
    # it is in the state once the tick that folded it has published.
    delay = float(cell.traffic["judge_delay_s"])
    pub_of_block = np.full(len(log.blocks), np.nan)
    for tk in log.ticks:
        pub_of_block[tk[3]] = tk[1]
    fb_due = t_done + delay
    fb_w = (fb_due >= o) & (fb_due < c)
    lag = np.where(np.isnan(pub_of_block), end - fb_due,
                   pub_of_block - fb_due)
    e2e = {
        "route_p99_ms": 1e3 * percentile(lat_w, 99),
        "decisions_per_s": float(B[in_w].sum()) / cell.seconds,
        "feedback_lag_p99_ms": 1e3 * percentile(
            np.repeat(lag[fb_w], B[fb_w]), 99),
    }
    layer = {
        "gen_late_p99_ms": 1e3 * percentile(
            (log.submit_t - sched.due)[win], 99),
        "block_rows_mean": float(B[in_w].mean()) if in_w.any() else None,
        "block_rows_range": [int(B.min()), int(B.max())] if len(B) else [],
    }
    return e2e, layer


@dataclasses.dataclass
class Evidence:
    """What the gateway did, on the host: the routed blocks (ids, arms,
    version, time), the learner ticks (start, publish time, version,
    blocks folded, rows kept), each tick's published per-arm update
    clocks, and a sample of published learner states."""

    blocks: list
    ticks: list
    last_upd: list
    published: dict
    unrouted: np.ndarray
    routed_twice: int
    fb_sent: int


def evidence(cell, sched, log) -> Evidence:
    """Read what the comparison needs off the device, then let the
    program's snapshots go."""
    import jax

    o, c = sched.window_open, sched.window_close
    last_upd = jax.device_get([tk[4].last_upd for tk in log.ticks])
    rng = np.random.default_rng([cell.seed, 7])
    win_ticks = [k for k, tk in enumerate(log.ticks) if o <= tk[1] < c]
    pick = set(rng.choice(win_ticks, size=min(
        SAMPLED_PUBLISHES, len(win_ticks)), replace=False).tolist())
    # The run's last publish holds every row folded, also where a starved
    # learner published nothing inside the window.
    pick |= {len(log.ticks) - 1} if log.ticks else set()
    published = {k: jax.device_get(_learn_leaves(log.ticks[k][4]))
                 for k in sorted(pick)}
    ticks = [(tk[0], tk[1], tk[2], tk[3], tk[5]) for tk in log.ticks]
    log.ticks = None
    return Evidence(
        blocks=log.blocks, ticks=ticks, last_upd=last_upd,
        published=published,
        unrouted=sched.in_window() & np.isnan(log.route_t),
        routed_twice=log.routed_twice, fb_sent=log.fb_sent)


def _learn_leaves(state):
    leaves = {"A_inv": state.A_inv, "theta": state.theta, "b": state.b,
              "lam": state.pacer.lam, "c_ema": state.pacer.c_ema}
    if state.tenants is not None:
        tab = state.tenants
        leaves.update(lam=tab.lam, c_ema=tab.c_ema, pulls=tab.pulls,
                      spend=tab.spend)
    return leaves


def replay(cell, sched, blocks, ticks, t_grab, train, test, want=(),
           control: Optional[algo1.Arith] = None):
    """Run the reference through the gateway's order of events.

    ``blocks``: (ids, arms, version, ...) per routed block in routing
    order; ``ticks``: (version, block indices) per publish, folded at
    clock ``t_grab``. The reference follows the program's arms. Returns
    the per-request gap of the program's arm below the reference's best
    (NaN where unrouted), and the reference's learner state at each
    version in ``want``. With ``control``, a second reference computed
    in that arithmetic runs in lockstep and stands in the program's
    place: the gaps are of its own choices, the states are its own.
    """
    config = cell.config
    hp = _hyper(config)
    tenanted = sched.ceilings is not None
    budgets = (sched.ceilings if tenanted
               else [config["budget_per_request"]])
    ars = [algo1.Arith()] + ([control] if control else [])
    sts = [algo1.warm_router(train.contexts, train.rewards,
                             config["max_arms"], config["n_eff"], budgets,
                             hp, ar) for ar in ars]
    pf = algo1.portfolio(test.prices_per_req, test.prices_per_1k,
                         config["max_arms"], hp)
    gaps = np.full(sched.n, np.nan)
    at_version = {}
    by_version = {tk[0]: (tk[1], tg) for tk, tg in zip(ticks, t_grab)}
    want = set(want)
    version = 0

    def advance(to):
        nonlocal version
        while version < to:
            version += 1
            fold, tg = by_version[version]
            rows = np.concatenate([blocks[j][0] for j in fold])
            rarms = np.concatenate([blocks[j][1] for j in fold])
            p = sched.prompt[rows]
            for st, ar in zip(sts, ars):
                algo1.fold_rows(st, tg, rarms, test.contexts[p],
                                test.rewards[p, rarms], hp, ar)
                algo1.fold_costs(st.pacers, test.costs[p, rarms],
                                 sched.tenant[rows] if tenanted else None,
                                 hp, ar)
            if version in want:
                at_version[version] = sts[-1].learn_copy()

    def lams(st, ids):
        return (st.pacers.lam[sched.tenant[ids]] if tenanted
                else np.full(len(ids), st.pacers.lam[0]))

    for ids, arms, v, *_ in blocks:
        advance(v)
        X = test.contexts[sched.prompt[ids]]
        s, cand = algo1.scores(sts[0], pf, X, lams(sts[0], ids), hp)
        chosen = arms
        if control:
            sc, cc = algo1.scores(sts[1], pf, X, lams(sts[1], ids), hp,
                                  control)
            chosen = np.argmax(np.where(cc, sc, -np.inf), axis=1)
        gaps[ids] = algo1.arm_gaps(s, cand, chosen)
        for st in sts:
            algo1.dispatch(st, arms)
    advance(max(want, default=0))
    return gaps, at_version


def grab_clocks(blocks, ticks, last_upd):
    """The clock each tick folded at, read from its published per-arm
    update clocks (the folded arms carry it, no arm can be ahead of it),
    and how many ticks folded at a clock that is not the start of a
    routed block or lies outside the blocks routed during the tick."""
    starts = np.concatenate([[0], np.cumsum([len(b[0]) for b in blocks])])
    start_set = set(starts.tolist())
    t_done = np.asarray([b[3] for b in blocks])
    bad = 0
    out = []
    for (t0, t_pub, *_), lu in zip(ticks, last_upd):
        tg = int(np.max(lu))
        lo = starts[np.searchsorted(t_done, t0, side="right")]
        hi = starts[min(np.searchsorted(t_done, t_pub, side="right") + 1,
                        len(starts) - 1)]
        if not (lo <= tg <= hi and tg in start_set):
            bad += 1
        out.append(tg)
    return out, bad


def readings(cell, sched, ev: Evidence, train, test,
             control: Optional[algo1.Arith] = None) -> Dict[str, float]:
    """Every number the comparison reads. With ``control``, the
    reference in that arithmetic stands in the program's place for the
    selection and learner numbers."""
    win = sched.in_window()
    t_grab, bad_grabs = grab_clocks(ev.blocks, ev.ticks, ev.last_upd)
    versions = [tk[2] for tk in ev.ticks]
    vers = np.asarray([b[2] for b in ev.blocks])
    order_faults = bad_grabs + int(np.sum(np.diff(vers) < 0)) + int(
        np.any(np.asarray(versions) != np.arange(1, len(versions) + 1)))
    want = {versions[k] for k in ev.published}
    gaps, ref = replay(cell, sched, ev.blocks,
                       [(versions[k], tk[3]) for k, tk in enumerate(ev.ticks)],
                       t_grab, train, test, want=want)
    if control:
        gaps, alt = replay(
            cell, sched, ev.blocks,
            [(versions[k], tk[3]) for k, tk in enumerate(ev.ticks)],
            t_grab, train, test, want=want, control=control)
    out = {
        "unrouted": float(ev.unrouted.sum()),
        "routed_twice": float(ev.routed_twice),
        "feedback_dropped": float(ev.fb_sent - sum(tk[4] for tk in ev.ticks)),
        "event_order_faults": float(order_faults),
        "arm_gap": float(np.nanmax(gaps[win])),
    }
    tenanted = sched.ceilings is not None
    active = np.arange(cell.config["max_arms"]) < test.k
    stats = lam = c_ema = pulls = spend = 0.0
    for k, got in ev.published.items():
        st = ref[versions[k]]
        if control:
            c_st = alt[versions[k]]
            got = {"A_inv": c_st.Ainv, "theta": c_st.theta, "b": c_st.b,
                   "lam": c_st.pacers.lam, "c_ema": c_st.pacers.c_ema,
                   "pulls": c_st.pacers.pulls, "spend": c_st.pacers.spend}
        for name, w in (("A_inv", st.Ainv), ("theta", st.theta),
                        ("b", st.b)):
            g = np.asarray(got[name], np.float64)[active].reshape(
                int(active.sum()), -1)
            w = w[active].reshape(len(g), -1)
            stats = max(stats, float(np.max(np.linalg.norm(g - w, axis=1)
                                            / np.linalg.norm(w, axis=1))))
        p = st.pacers
        lam = max(lam, float(np.max(np.abs(
            np.ravel(got["lam"]).astype(np.float64) - p.lam))))
        c_ema = max(c_ema, float(np.max(np.abs(
            np.ravel(got["c_ema"]).astype(np.float64) - p.c_ema)
            / p.budget)))
        if tenanted:
            pulls = max(pulls, float(np.max(np.abs(
                np.asarray(got["pulls"], np.int64) - p.pulls))))
            spend = max(spend, float(np.max(np.abs(
                np.asarray(got["spend"], np.float64) - p.spend)
                / np.maximum(p.spend, p.budget))))
    out.update(stats_rel_gap=stats, lam_gap=lam, c_ema_rel_gap=c_ema)
    if tenanted:
        out.update(tenant_pulls_gap=pulls, spend_rel_gap=spend)
    return out


# Numbers that must read exactly 0 (counts of faults).
EXACT = ("unrouted", "routed_twice", "feedback_dropped",
         "event_order_faults", "tenant_pulls_gap")


def compare(cell, values: Dict[str, float]) -> List[Check]:
    """Each reading beside its limit (0 for the exact counts)."""
    lim = cell.limits()
    return [Check(name, v, 0.0 if name in EXACT else lim[name])
            for name, v in values.items()]
