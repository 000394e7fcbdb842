"""Timeline-as-data (DESIGN.md §12): the masked timeline runner must be
bit-identical to the concrete retimed spec for every event type and both
data planes, re-enter ONE compiled program across timelines (TRACE_COUNT
contracts), expose effective padded-segment bounds, and compose with the
sweep fabric's payload/hyper/chunk axes. Plus the Monte Carlo layer on
top (sampling validity, metric shapes)."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.core import evaluate, montecarlo, scenario, simulator, sweep
from repro.core.scenario import (
    AddArm, BudgetChange, DeleteArm, HyperShift, Param, PriceChange,
    QualityShift, ScenarioParams, ScenarioSpec, Timeline, TrafficMixShift,
    retime,
)
from repro.core.types import HyperParams, RouterConfig
from tests.trace_guard import assert_traces

CFG = RouterConfig(max_arms=4)
SEEDS = (0, 1, 2)
GEMINI, MISTRAL = 2, 1


@pytest.fixture(scope="module")
def env():
    b = simulator.make_benchmark(
        seed=0, splits={"train": 256, "val": 32, "test": 200})
    return b.test


@pytest.fixture(scope="module")
def priors():
    train = simulator.make_benchmark(
        seed=0, splits={"train": 256, "val": 32, "test": 200}).train
    return evaluate.fit_warmup_priors(CFG, train)


@pytest.fixture(scope="module")
def env4(env):
    return simulator.extend_with_flash(env, "good_cheap")


def _assert_bitwise(a, b):
    np.testing.assert_array_equal(a.arms, b.arms)
    np.testing.assert_array_equal(a.rewards, b.rewards)
    np.testing.assert_array_equal(a.costs, b.costs)
    np.testing.assert_array_equal(a.lams, b.lams)


def _check(spec, env_, budget, tl, seeds=SEEDS, batch_size=None, **kw):
    """Masked timeline run == concrete run of the retimed spec, bitwise,
    with the retimed spec's effective bounds."""
    base = evaluate.run_scenario(CFG, retime(spec, tl), env_, budget,
                                 seeds=seeds, batch_size=batch_size, **kw)
    masked = evaluate.run_scenario(CFG, spec, env_, budget, seeds=seeds,
                                   batch_size=batch_size, timeline=tl, **kw)
    _assert_bitwise(base, masked)
    assert masked.bounds == base.bounds
    return masked


class TestTimelineStructure:
    def test_retime_moves_events_and_horizon(self):
        spec = ScenarioSpec(horizon=200, events=(
            QualityShift(100, 1, 0.7), PriceChange(150, 2, 0.5)))
        r = retime(spec, Timeline((40, 90), horizon=160))
        assert r.horizon == 160
        assert tuple(e.t for e in r.events) == (40, 90)
        assert r.bounds == (0, 40, 90, 160)

    def test_wrong_event_count_rejected(self):
        spec = ScenarioSpec(horizon=100, events=(QualityShift(50, 1, 0.7),))
        with pytest.raises(ValueError, match="event times"):
            retime(spec, Timeline((10, 20)))

    def test_horizon_out_of_range_rejected(self):
        spec = ScenarioSpec(horizon=100, events=())
        with pytest.raises(ValueError, match="horizon"):
            retime(spec, Timeline((), horizon=0))
        with pytest.raises(ValueError, match="horizon"):
            retime(spec, Timeline((), horizon=101))

    def test_invalid_times_fail_spec_validation(self):
        spec = ScenarioSpec(horizon=100, events=(QualityShift(50, 1, 0.7),))
        with pytest.raises(AssertionError):
            retime(spec, Timeline((100,)))  # t >= horizon


class TestBitIdentityPerEventType:
    """Every event type, masked vs concrete, bit for bit."""

    def test_silent_price_and_quality(self, env):
        spec = ScenarioSpec(horizon=120, events=(
            PriceChange(40, GEMINI, 1 / 56),
            QualityShift(80, MISTRAL, 0.72)), stream_seed_base=910)
        _check(spec, env, 6.6e-4, Timeline((25, 70)))

    def test_recalibrated_price(self, env):
        spec = ScenarioSpec(horizon=120, events=(
            PriceChange(40, GEMINI, 0.3, recalibrate=True),),
            stream_seed_base=911)
        _check(spec, env, 6.6e-4, Timeline((65,)))

    def test_budget_change(self, env):
        spec = ScenarioSpec(horizon=120, events=(BudgetChange(40, 3.0e-4),),
                            stream_seed_base=912)
        _check(spec, env, 1.9e-3, Timeline((90,)))

    def test_hyper_shift(self, env):
        spec = ScenarioSpec(horizon=120, events=(HyperShift(60, gamma=0.9),),
                            stream_seed_base=913)
        _check(spec, env, 1.9e-3, Timeline((20,)))

    def test_add_arm(self, env4):
        spec = ScenarioSpec(horizon=120, events=(AddArm(40, 3),),
                            stream_seed_base=914, init_active=3)
        res = _check(spec, env4, 6.6e-4, Timeline((72,)))
        assert (res.segment(1).arms[:, :CFG.forced_pulls] == 3).all()

    def test_delete_arm(self, env):
        spec = ScenarioSpec(horizon=120, events=(DeleteArm(50, MISTRAL),),
                            stream_seed_base=915)
        res = _check(spec, env, 1.0, Timeline((30,)))
        assert not np.any(res.segment(1).arms == MISTRAL)

    def test_traffic_mix_shift(self, env):
        w = tuple(3.0 if f == 1 else 0.25 for f in range(9))
        spec = ScenarioSpec(horizon=200, events=(TrafficMixShift(100, w),),
                            stream_seed_base=916)
        _check(spec, env, 6.6e-4, Timeline((60,)), seeds=(0, 1))

    def test_add_arm_sees_inforce_price(self, env4):
        """The newcomer's registered price must reflect the price event
        in force at its (traced) arrival time — the time-order-dependent
        case the traced in-force fold exists for."""
        spec = ScenarioSpec(horizon=140, events=(
            DeleteArm(10, 3),
            PriceChange(40, 3, 0.1),
            AddArm(80, 3)), stream_seed_base=917)
        # arrival after the reprice: newcomer priced at 0.1x
        _check(spec, env4, 6.6e-4, Timeline((10, 40, 80)))
        # arrival before the reprice: priced at base, repriced later
        _check(spec, env4, 6.6e-4, Timeline((10, 90, 50)))


class TestBitIdentityTimingEdges:
    def test_event_at_t0(self, env):
        spec = ScenarioSpec(horizon=100, events=(
            QualityShift(40, MISTRAL, 0.7),), stream_seed_base=918)
        _check(spec, env, 6.6e-4, Timeline((0,)))

    def test_adjacent_steps(self, env):
        spec = ScenarioSpec(horizon=100, events=(
            PriceChange(30, GEMINI, 0.2),
            BudgetChange(60, 3.0e-4)), stream_seed_base=919)
        _check(spec, env, 1.9e-3, Timeline((50, 51)))

    def test_coincident_events_listed_order(self, env):
        """Two same-arm price events pushed onto one step: the
        later-listed payload must win, exactly as in the concrete path."""
        spec = ScenarioSpec(horizon=100, events=(
            PriceChange(30, GEMINI, 0.5),
            PriceChange(60, GEMINI, 0.05)), stream_seed_base=920)
        _check(spec, env, 6.6e-4, Timeline((45, 45)))

    def test_reordered_times(self, env):
        """Timelines may permute which event lands first."""
        spec = ScenarioSpec(horizon=120, events=(
            PriceChange(40, GEMINI, 0.1),
            QualityShift(80, MISTRAL, 0.7)), stream_seed_base=921)
        _check(spec, env, 6.6e-4, Timeline((80, 30)))

    def test_shrunken_horizon_padding(self, env):
        spec = ScenarioSpec(horizon=160, events=(
            QualityShift(80, MISTRAL, 0.7),), stream_seed_base=922)
        res = _check(spec, env, 6.6e-4, Timeline((40,), horizon=100))
        assert res.arms.shape == (len(SEEDS), 100)
        assert res.bounds == (0, 40, 100)

    def test_no_events_horizon_only(self, env):
        spec = ScenarioSpec(horizon=120, events=(), stream_seed_base=923)
        res = _check(spec, env, 6.6e-4, Timeline((), horizon=90))
        assert res.arms.shape == (len(SEEDS), 90)


class TestRngModes:
    def test_segment_seeds(self, env):
        spec = ScenarioSpec(horizon=120, events=(
            QualityShift(60, MISTRAL, 0.7),), segment_seeds=(300, 400),
            stream_seed_base=0)
        _check(spec, env, 6.6e-4, Timeline((35,)))

    def test_replay_matched_segments(self, env):
        """Replay requires equal segment lengths; a timeline keeping the
        three phases equal must still replay segment 0 into segment 2."""
        spec = ScenarioSpec(horizon=180, events=(
            QualityShift(60, MISTRAL, 0.7),
            QualityShift(120, MISTRAL, None)),
            stream_seed_base=924, replay=((2, 0),))
        tl = Timeline((40, 80), horizon=120)
        _check(spec, env, 6.6e-4, tl)
        idxs = scenario.compile_indices(retime(spec, tl), env, seed=0)
        np.testing.assert_array_equal(idxs[2], idxs[0])


class TestBatchedPlane:
    def test_bit_identity_batched(self, env):
        spec = ScenarioSpec(horizon=128, events=(
            PriceChange(32, GEMINI, 0.1),
            BudgetChange(64, 3.0e-4)), stream_seed_base=925)
        _check(spec, env, 1.9e-3, Timeline((48, 96), horizon=112),
               seeds=(0, 1), batch_size=16)

    def test_misaligned_timeline_rejected(self, env):
        spec = ScenarioSpec(horizon=128, events=(
            PriceChange(32, GEMINI, 0.1),), stream_seed_base=926)
        with pytest.raises(ValueError, match="aligned"):
            evaluate.run_scenario(CFG, spec, env, 6.6e-4, seeds=(0,),
                                  batch_size=16, timeline=Timeline((40,)))
        with pytest.raises(ValueError, match="aligned"):
            evaluate.run_scenario(CFG, spec, env, 6.6e-4, seeds=(0,),
                                  batch_size=16,
                                  timeline=Timeline((32,), horizon=100))


class TestTraceCountContracts:
    def test_single_run_no_retrace_on_new_times(self, env):
        spec = ScenarioSpec(horizon=120, events=(
            PriceChange(40, GEMINI, 0.1),
            QualityShift(80, MISTRAL, 0.7)), stream_seed_base=927)
        evaluate.run_scenario(CFG, spec, env, 6.6e-4, seeds=(0,),
                              timeline=Timeline((40, 80)))
        with assert_traces(scenario, 0, what="event times/horizon must "
                                             "be data, not structure"):
            evaluate.run_scenario(CFG, spec, env, 3.0e-4, seeds=(1,),
                                  timeline=Timeline((70, 15), horizon=100))

    def test_grid_no_retrace_on_new_timelines(self, env):
        spec = ScenarioSpec(horizon=120, events=(
            PriceChange(40, GEMINI, 0.1),), stream_seed_base=928)
        budgets = (1.9e-3, 6.6e-4)
        sweep.run_scenario_grid(CFG, spec, env, budgets, seeds=(0, 1),
                                timelines=[Timeline((30,)),
                                           Timeline((90,))])
        with assert_traces(sweep, 0, what="grid timelines must re-enter "
                                          "one compiled program"):
            sweep.run_scenario_grid(
                CFG, spec, env, budgets, seeds=(0, 1),
                timelines=[Timeline((55,), horizon=80),
                           Timeline((5,), horizon=110)])


class TestGridTimelines:
    SPEC = ScenarioSpec(horizon=120, events=(
        PriceChange(40, GEMINI, 1 / 56),
        BudgetChange(80, 3.0e-4)), stream_seed_base=930)
    BUDGETS = (1.9e-3, 6.6e-4)

    def test_shared_timeline(self, env):
        tl = Timeline((25, 70), horizon=100)
        grid = sweep.run_scenario_grid(CFG, self.SPEC, env, self.BUDGETS,
                                       seeds=SEEDS, timelines=tl)
        for i, b in enumerate(self.BUDGETS):
            ref = evaluate.run_scenario(CFG, retime(self.SPEC, tl), env, b,
                                        seeds=SEEDS)
            _assert_bitwise(ref, grid.condition(i))
            assert grid.condition(i).bounds == ref.bounds

    def test_per_condition_timelines(self, env):
        tls = [Timeline((25, 70)), Timeline((60, 90), horizon=100)]
        grid = sweep.run_scenario_grid(CFG, self.SPEC, env, self.BUDGETS,
                                       seeds=SEEDS, timelines=tls)
        assert grid.horizons == (120, 100)
        for i, (b, tl) in enumerate(zip(self.BUDGETS, tls)):
            ref = evaluate.run_scenario(CFG, retime(self.SPEC, tl), env, b,
                                        seeds=SEEDS)
            res = grid.condition(i)
            _assert_bitwise(ref, res)
            assert res.arms.shape[1] == (tl.horizon or 120)
            assert res.bounds == ref.bounds

    def test_per_element_timelines(self, env):
        seeds = (0, 1)
        tls = [Timeline((25, 70)), Timeline((60, 90), horizon=100),
               Timeline((10, 20)), Timeline((0, 110), horizon=112)]
        grid = sweep.run_scenario_grid(CFG, self.SPEC, env, self.BUDGETS,
                                       seeds=seeds, timelines=tls)
        S = len(seeds)
        for i, tl in enumerate(tls):
            ci, si = divmod(i, S)
            r = retime(self.SPEC, tl)
            ref = evaluate.run_scenario(CFG, r, env, self.BUDGETS[ci],
                                        seeds=(seeds[si],))
            h = r.horizon
            np.testing.assert_array_equal(grid.arms[ci, si, :h],
                                          ref.arms[0])
            np.testing.assert_array_equal(grid.lams[ci, si, :h],
                                          ref.lams[0])

    @pytest.mark.parametrize("stack", ["hyper", "n_eff"])
    def test_per_element_hyper_and_neff_stacks(self, stack, env, priors):
        """(C*S,) per-element hyper and n_eff stacks on the Monte Carlo
        path: each element bit-identical to ``run_scenario`` on its
        retimed spec at its own value."""
        seeds = (0, 1)
        tls = [Timeline((25, 70)), Timeline((60, 90), horizon=100),
               Timeline((10, 20)), Timeline((0, 110), horizon=112)]
        if stack == "hyper":
            values = np.asarray([0.01, 0.3, 0.8, 0.05], np.float32)
            grid_kw = dict(hyper=HyperParams(alpha=values))

            def one(v):
                return dict(hyper=dataclasses.replace(CFG.hyper, alpha=v))
        else:
            values = np.asarray([10.0, 100.0, 400.0, 1164.0], np.float32)
            grid_kw = dict(priors=priors, n_eff=values)

            def one(v):
                return dict(priors=priors, n_eff=v)

        grid = sweep.run_scenario_grid(CFG, self.SPEC, env, self.BUDGETS,
                                       seeds=seeds, timelines=tls, **grid_kw)
        S = len(seeds)
        for i, tl in enumerate(tls):
            ci, si = divmod(i, S)
            r = retime(self.SPEC, tl)
            ref = evaluate.run_scenario(CFG, r, env, self.BUDGETS[ci],
                                        seeds=(seeds[si],),
                                        **one(float(values[i])))
            h = r.horizon
            for name in ("arms", "rewards", "costs", "lams"):
                np.testing.assert_array_equal(
                    getattr(grid, name)[ci, si, :h], getattr(ref, name)[0])

    def test_wrong_timeline_count_rejected(self, env):
        with pytest.raises(ValueError, match="timelines"):
            sweep.run_scenario_grid(CFG, self.SPEC, env, self.BUDGETS,
                                    seeds=SEEDS,
                                    timelines=[Timeline((25, 70))] * 3)

    def test_composes_with_chunk_and_edits(self, env):
        """Timelines x chunked scan x a per-condition (C,) hyper stack:
        the chunked program is bit-identical to the unchunked one."""
        tls = [Timeline((25, 70)), Timeline((60, 90))]
        hyper = HyperParams(
            alpha=np.asarray([0.8, CFG.hyper.alpha], np.float32))
        kw = dict(seeds=(0, 1), timelines=tls, hyper=hyper)
        plain = sweep.run_scenario_grid(CFG, self.SPEC, env, self.BUDGETS,
                                        **kw)
        chunked = sweep.run_scenario_grid(CFG, self.SPEC, env, self.BUDGETS,
                                          chunk_size=2, **kw)
        np.testing.assert_array_equal(plain.arms, chunked.arms)
        np.testing.assert_array_equal(plain.lams, chunked.lams)
        # the edited condition matches a standalone run at its hyper
        ref = evaluate.run_scenario(
            CFG, retime(self.SPEC, tls[0]), env, self.BUDGETS[0],
            seeds=(0, 1),
            hyper=dataclasses.replace(CFG.hyper, alpha=0.8))
        _assert_bitwise(ref, plain.condition(0))

    def test_default_chunk_is_bit_identical(self, env4):
        """The chunk the fabric picks on one TPU v5e for 512 elements at
        the paper's statics, passed here explicitly, gives the bits of
        the whole grid run as one chunk: a silent price cut, a quality
        shift and a cold AddArm with forced pulls, each element on its
        own timeline, every third horizon cut short and padded."""
        cfg = RouterConfig(d=26, max_arms=8, forced_pulls=5)
        spec = ScenarioSpec(horizon=48, events=(
            PriceChange(8, GEMINI, 1 / 56), QualityShift(8, MISTRAL, 0.75),
            AddArm(8, 3)), stream_seed_base=950, init_active=3)
        budgets, seeds = (6.6e-4, 1.9e-3), tuple(range(256))
        n = len(budgets) * len(seeds)
        steps = np.random.default_rng(0).integers(4, 32, size=(n, 3))
        tls = [Timeline(tuple(int(t) for t in s),
                        horizon=40 if i % 3 == 0 else None)
               for i, s in enumerate(steps)]
        one = evaluate.make_states(cfg, env4, budgets[0], (0,),
                                   active_arms=3)
        chunk = sweep.default_chunk(
            n, [jax.ShapeDtypeStruct(l.shape[1:], l.dtype)
                for l in jax.tree.leaves(one)], "TPU v5 lite")
        assert 1 < chunk < n
        kw = dict(seeds=seeds, timelines=tls, return_states=True)
        whole, whole_st = sweep.run_scenario_grid(
            cfg, spec, env4, budgets, chunk_size=n, **kw)
        got, got_st = sweep.run_scenario_grid(
            cfg, spec, env4, budgets, chunk_size=chunk, **kw)
        _assert_bitwise(whole, got)
        assert (whole.arms == 3).any(axis=-1).all()   # forced pulls ran
        for a, b in zip(jax.tree.leaves(whole_st), jax.tree.leaves(got_st)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_composes_with_param_payloads(self, env):
        """A Param payload stack and a timeline axis ride together."""
        spec = ScenarioSpec(horizon=120, events=(
            PriceChange(40, GEMINI, Param("mult")),), stream_seed_base=931)
        tls = [Timeline((25,)), Timeline((80,), horizon=100)]
        mults = np.asarray([0.05, 0.5], np.float32)
        grid = sweep.run_scenario_grid(
            CFG, spec, env, self.BUDGETS, seeds=(0, 1), timelines=tls,
            scenario_params=ScenarioParams(mult=mults))
        for i, (b, tl) in enumerate(zip(self.BUDGETS, tls)):
            ref = evaluate.run_scenario(
                CFG, retime(spec, tl), env, b, seeds=(0, 1),
                scenario_params=ScenarioParams(mult=float(mults[i])))
            _assert_bitwise(ref, grid.condition(i))


class TestVectorizedStreamRebuild:
    """The cross-timeline stream stack (scenario.build_timeline_streams)
    must equal the per-timeline build_streams loop bit for bit — fast
    path for eligible specs, fallback for the rest."""

    SPEC = ScenarioSpec(horizon=160, events=(
        QualityShift(60, MISTRAL, 0.7),
        PriceChange(100, GEMINI, 0.1)), stream_seed_base=940)
    TLS = [Timeline((60, 100)),
           Timeline((100, 20)),              # reordered events
           Timeline((10, 30), horizon=96),   # shorter horizon -> padding
           Timeline((40, 40), horizon=120),  # zero-length segment
           Timeline((0, 150))]               # boundary event times

    def _manual(self, spec, env_, rspecs, seed_groups, pad_to):
        parts = [scenario.build_streams(CFG, r_, env_, tuple(g),
                                        pad_to=pad_to)
                 for r_, g in zip(rspecs, seed_groups)]
        return tuple(np.concatenate([np.asarray(p[j]) for p in parts])
                     for j in range(3))

    def _check_equal(self, spec, env_, tls, seed_groups, pad_to):
        rspecs = [retime(spec, tl) for tl in tls]
        got = scenario.build_timeline_streams(
            CFG, spec, env_, rspecs, seed_groups, pad_to=pad_to)
        want = self._manual(spec, env_, rspecs, seed_groups, pad_to)
        for name, g, w in zip(("contexts", "rewards", "costs"), got, want):
            assert g.shape == w.shape, name
            np.testing.assert_array_equal(np.asarray(g), w, err_msg=name)

    def test_fast_path_shared_seeds(self, env):
        assert scenario.timeline_streams_vectorizable(self.SPEC)
        self._check_equal(self.SPEC, env, self.TLS,
                          [SEEDS] * len(self.TLS), pad_to=160)

    def test_fast_path_per_element_seeds(self, env):
        self._check_equal(self.SPEC, env, self.TLS,
                          [(i + 5,) for i in range(len(self.TLS))],
                          pad_to=160)

    def test_fast_path_with_arm_growth(self, env4):
        """AddArm/DeleteArm are state events (no stream content), and a
        4-arm env exercises the no-arm-padding branch."""
        spec = ScenarioSpec(horizon=140, events=(
            QualityShift(60, MISTRAL, 0.8), AddArm(90, 3)),
            stream_seed_base=941, init_active=3)
        assert scenario.timeline_streams_vectorizable(spec)
        tls = [Timeline((60, 90)), Timeline((100, 120), horizon=130)]
        self._check_equal(spec, env4, tls, [SEEDS, SEEDS], pad_to=140)

    def test_ineligible_specs_detected(self):
        qs = (QualityShift(60, MISTRAL, 0.7),)
        for spec in (
            ScenarioSpec(horizon=180, events=qs + (
                QualityShift(120, MISTRAL, None),),
                replay=((2, 0),), stream_seed_base=942),
            ScenarioSpec(horizon=120, events=qs,
                         segment_seeds=(300, 400), stream_seed_base=0),
            ScenarioSpec(horizon=120, events=qs, mode="permutation",
                         stream_seed_base=943),
            ScenarioSpec(horizon=120, events=(
                TrafficMixShift(60, tuple(
                    3.0 if f == 1 else 0.25 for f in range(9))),),
                stream_seed_base=944),
        ):
            assert not scenario.timeline_streams_vectorizable(spec)

    def test_fallback_still_equal(self, env):
        spec = ScenarioSpec(horizon=160, events=(
            TrafficMixShift(80, tuple(
                3.0 if f == 1 else 0.25 for f in range(9))),),
            stream_seed_base=945)
        tls = [Timeline((80,)), Timeline((30,), horizon=100)]
        self._check_equal(spec, env, tls, [(0, 1), (0, 1)], pad_to=160)

    @pytest.mark.parametrize("mix", [False, True])
    def test_both_paths_return_host_arrays(self, env, mix):
        """One contract on both paths: host arrays, sent and counted by
        the fabric's ``place``."""
        events = ((TrafficMixShift(80, tuple(
            3.0 if f == 1 else 0.25 for f in range(9))),) if mix
            else (QualityShift(80, MISTRAL, 0.7),))
        spec = ScenarioSpec(horizon=160, events=events, stream_seed_base=946)
        assert scenario.timeline_streams_vectorizable(spec) is not mix
        rspecs = [retime(spec, Timeline((80,))), retime(spec, Timeline((30,)))]
        got = scenario.build_timeline_streams(
            CFG, spec, env, rspecs, [(0,), (1,)], pad_to=160)
        for a in got:
            assert isinstance(a, np.ndarray), type(a)


class TestMonteCarlo:
    SPEC = ScenarioSpec(horizon=120, events=(
        PriceChange(40, GEMINI, 1 / 56),
        QualityShift(80, MISTRAL, 0.72)), stream_seed_base=932)

    def test_sample_timelines_valid_and_deterministic(self):
        a = montecarlo.sample_timelines(self.SPEC, 16, seed=7, align=4,
                                        horizons=(80, 120))
        b = montecarlo.sample_timelines(self.SPEC, 16, seed=7, align=4,
                                        horizons=(80, 120))
        assert a == b
        for tl in a:
            retime(self.SPEC, tl)  # all valid
            assert all(t % 4 == 0 for t in tl.event_ts)
            assert tl.horizon % 4 == 0 and 80 <= tl.horizon <= 120

    def test_sample_timelines_impossible_window_raises(self):
        with pytest.raises(ValueError, match="valid timeline"):
            montecarlo.sample_timelines(self.SPEC, 1, t_lo=(100, 100),
                                        t_hi=(119, 119), horizons=(40, 60))

    def test_run_monte_carlo_metrics(self, env):
        tls = montecarlo.sample_timelines(self.SPEC, 6, seed=3)
        mc = montecarlo.run_monte_carlo(CFG, self.SPEC, env, 6.6e-4, tls,
                                        seeds=(0, 1))
        assert mc.lags.shape == (6, 2)
        assert mc.lifts.shape == (6,) and mc.compliance.shape == (6,)
        assert np.all(mc.compliance > 0)
        bands = mc.bands((5, 50, 95))
        assert bands["n_timelines"] == 6
        assert len(bands["adaptation_lag"]["p50"]) == 2
        # each sampled timeline bit-identical to its looped baseline
        for i, tl in enumerate(tls):
            ref = evaluate.run_scenario(CFG, retime(self.SPEC, tl), env,
                                        6.6e-4, seeds=(0, 1))
            _assert_bitwise(ref, mc.grid.condition(i))
