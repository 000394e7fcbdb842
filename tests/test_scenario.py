"""Scenario engine: spec structure, stream compilation, exact equivalence
with the hand-rolled host-loop protocols it replaced, the one-jitted-call
(no retrace) contract, parameterized payloads (Param/ScenarioParams,
DESIGN.md §10), both data planes, and RunResult segment utilities."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.core import evaluate, pacer, registry, scenario, simulator
from repro.core.scenario import (
    AddArm, BudgetChange, DeleteArm, HyperShift, Param, PriceChange,
    QualityShift, ScenarioParams, ScenarioSpec, TrafficMixShift,
)
from repro.core.types import RouterConfig

CFG = RouterConfig(max_arms=4)
SEEDS = (0, 1, 2)
GEMINI, MISTRAL = 2, 1


@pytest.fixture(scope="module")
def env():
    b = simulator.make_benchmark(
        seed=0, splits={"train": 256, "val": 32, "test": 200})
    return b.test


@pytest.fixture(scope="module")
def env4(env):
    return simulator.extend_with_flash(env, "good_cheap")


class TestSpecStructure:
    def test_bounds_and_segments(self):
        spec = ScenarioSpec(horizon=300, events=(
            QualityShift(100, 1, 0.7), PriceChange(200, 2, 0.5)))
        assert spec.bounds == (0, 100, 200, 300)
        assert spec.segments == ((0, 100), (100, 200), (200, 300))

    def test_shared_event_time_single_boundary(self):
        spec = ScenarioSpec(horizon=200, events=(
            PriceChange(100, 1, 0.5), PriceChange(100, 2, 0.5)))
        assert spec.bounds == (0, 100, 200)

    def test_event_beyond_horizon_rejected(self):
        with pytest.raises(AssertionError):
            ScenarioSpec(horizon=100, events=(QualityShift(100, 1, 0.7),))

    def test_bad_replay_rejected(self):
        with pytest.raises(AssertionError):
            ScenarioSpec(horizon=200, events=(QualityShift(100, 1, 0.7),),
                         replay=((0, 1),))

    def test_segment_seeds_length_checked(self):
        with pytest.raises(AssertionError):
            ScenarioSpec(horizon=200, events=(QualityShift(100, 1, 0.7),),
                         segment_seeds=(1,))

    def test_add_arm_on_active_slot_rejected(self, env4):
        # without init_active=3, slot 3 starts active: re-adding it would
        # silently wipe its learned statistics
        spec = ScenarioSpec(horizon=100, events=(AddArm(50, 3),))
        with pytest.raises(AssertionError, match="already active"):
            scenario.build_streams(CFG, spec, env4, (0,))

    def test_delete_then_readd_allowed(self, env4):
        spec = ScenarioSpec(horizon=100, events=(
            DeleteArm(30, 2), AddArm(60, 2)))
        scenario.build_streams(CFG, spec, env4, (0,))

    def test_delete_inactive_slot_rejected(self, env4):
        spec = ScenarioSpec(horizon=100, events=(DeleteArm(50, 3),),
                            init_active=3)
        with pytest.raises(AssertionError, match="not active"):
            scenario.build_streams(CFG, spec, env4, (0,))


class TestStreamCompilation:
    def test_sequential_rng_matches_three_phase_convention(self, env):
        """Segments consume one shared generator in order — the same
        draws ``three_phase_stream`` makes."""
        spec = ScenarioSpec(horizon=180, events=(
            QualityShift(60, MISTRAL, 0.7), QualityShift(120, MISTRAL, None)),
            stream_seed_base=77, replay=((2, 0),))
        idxs = scenario.compile_indices(spec, env, seed=5)
        rng = np.random.default_rng(77 + 5)
        np.testing.assert_array_equal(idxs[0], rng.integers(0, env.n, 60))
        np.testing.assert_array_equal(idxs[1], rng.integers(0, env.n, 60))
        np.testing.assert_array_equal(idxs[2], idxs[0])  # replay, no draw

    def test_segment_seeds_fresh_generators(self, env):
        spec = ScenarioSpec(horizon=100, events=(QualityShift(40, 1, 0.7),),
                            segment_seeds=(300, 400))
        idxs = scenario.compile_indices(spec, env, seed=2)
        np.testing.assert_array_equal(
            idxs[0], np.random.default_rng(302).integers(0, env.n, 40))
        np.testing.assert_array_equal(
            idxs[1], np.random.default_rng(402).integers(0, env.n, 60))

    def test_permutation_mode_is_a_permutation(self, env):
        spec = ScenarioSpec(horizon=env.n, events=(), stream_seed_base=0,
                            mode="permutation")
        (idx,) = scenario.compile_indices(spec, env, seed=1)
        np.testing.assert_array_equal(np.sort(idx), np.arange(env.n))

    def test_traffic_mix_tilts_families(self, env):
        w = tuple(3.0 if f == 1 else 0.2 for f in range(9))
        spec = ScenarioSpec(horizon=400, events=(TrafficMixShift(200, w),),
                            stream_seed_base=11)
        idxs = scenario.compile_indices(spec, env, seed=0)
        base_frac = (env.families[idxs[0]] == 1).mean()
        mix_frac = (env.families[idxs[1]] == 1).mean()
        assert mix_frac > base_frac + 0.2

    def test_build_streams_pads_to_max_arms(self, env):
        spec = ScenarioSpec(horizon=50, events=())
        xs, rmat, cmat = scenario.build_streams(CFG, spec, env, SEEDS)
        assert xs.shape == (3, 50, env.contexts.shape[1])
        assert rmat.shape == (3, 50, CFG.max_arms)
        assert cmat.shape == (3, 50, CFG.max_arms)
        assert np.all(np.asarray(cmat)[..., env.k:] == 1e9)

    def test_price_events_scale_segment_costs(self, env):
        spec = ScenarioSpec(horizon=100, events=(
            PriceChange(50, GEMINI, 0.01),), stream_seed_base=9)
        _, _, cmat = scenario.build_streams(CFG, spec, env, (0,))
        c = np.asarray(cmat)[0]
        assert c[50:, GEMINI].mean() < 0.05 * c[:50, GEMINI].mean()


class TestHandRolledEquivalence:
    """The engine must reproduce the host-loop protocols bit-for-bit:
    same streams, same edits, same scan — one jitted call instead."""

    def test_three_phase_quality_shift(self, env):
        phase = 60
        envs = []
        for s in SEEDS:
            rng = np.random.default_rng(2000 + s)
            envs.append(simulator.three_phase_stream(
                env, lambda e: simulator.with_quality_shift(e, MISTRAL, 0.7),
                rng, phase_len=phase))
        old = evaluate.run(CFG, envs, 6.6e-4, seeds=SEEDS, shuffle=False)
        spec = ScenarioSpec(horizon=3 * phase, events=(
            QualityShift(phase, MISTRAL, 0.7),
            QualityShift(2 * phase, MISTRAL, None)),
            stream_seed_base=2000, replay=((2, 0),))
        new = evaluate.run_scenario(CFG, spec, env, 6.6e-4, seeds=SEEDS)
        np.testing.assert_array_equal(old.arms, new.arms)
        np.testing.assert_allclose(old.rewards, new.rewards, atol=1e-6)
        np.testing.assert_allclose(old.lams, new.lams, atol=1e-6)

    def test_recalibrated_price_drift(self, env):
        """PriceChange(recalibrate=True) == the oracle host loop that
        vmaps ``registry.set_price`` between segments."""
        t1, T, mult = 60, 140, 1 / 56
        seg1, seg2 = [], []
        for s in SEEDS:
            rng = np.random.default_rng(1000 + s)
            seg1.append(env.subset(rng.integers(0, env.n, t1)))
            seg2.append(simulator.with_price_multiplier(env, GEMINI, mult)
                        .subset(rng.integers(0, env.n, T - t1)))
        states = evaluate.make_states(CFG, env, 6.6e-4, SEEDS,
                                      pacer_enabled=False)
        res1, states = evaluate.run(CFG, seg1, 6.6e-4, seeds=SEEDS,
                                    states=states, shuffle=False,
                                    return_states=True)
        preq = float(env.prices_per_req[GEMINI]) * mult
        p1k = float(env.prices_per_1k[GEMINI]) * mult
        states = jax.vmap(
            lambda st: registry.set_price(CFG, st, GEMINI, preq, p1k))(states)
        res2, _ = evaluate.run(CFG, seg2, 6.6e-4, seeds=SEEDS, states=states,
                               shuffle=False, return_states=True)
        old = evaluate.RunResult.concat([res1, res2])
        spec = ScenarioSpec(horizon=T, events=(
            PriceChange(t1, GEMINI, mult, recalibrate=True),),
            stream_seed_base=1000)
        new = evaluate.run_scenario(CFG, spec, env, 6.6e-4, seeds=SEEDS,
                                    pacer_enabled=False)
        np.testing.assert_array_equal(old.arms, new.arms)
        np.testing.assert_allclose(old.costs, new.costs, atol=1e-9)

    def test_onboarding_add_arm(self, env4):
        import functools
        p1, p2 = 50, 90
        s1 = [env4.repeat_to(p1, np.random.default_rng(300 + s))
              for s in SEEDS]
        s2 = [env4.repeat_to(p2, np.random.default_rng(400 + s))
              for s in SEEDS]
        states = evaluate.make_states(CFG, env4, 6.6e-4, SEEDS,
                                      active_arms=3)
        res1, states = evaluate.run(CFG, s1, 6.6e-4, seeds=SEEDS,
                                    states=states, shuffle=False,
                                    return_states=True)
        add = functools.partial(
            registry.add_arm, CFG, slot=3,
            price_per_req=float(env4.prices_per_req[3]),
            price_per_1k=float(env4.prices_per_1k[3]),
            n_eff=None, forced_exploration=True)
        states = jax.vmap(lambda st: add(st))(states)
        res2, _ = evaluate.run(CFG, s2, 6.6e-4, seeds=SEEDS, states=states,
                               shuffle=False, return_states=True)
        old = evaluate.RunResult.concat([res1, res2])
        spec = ScenarioSpec(horizon=p1 + p2, events=(AddArm(p1, 3),),
                            segment_seeds=(300, 400), init_active=3)
        new = evaluate.run_scenario(CFG, spec, env4, 6.6e-4, seeds=SEEDS)
        np.testing.assert_array_equal(old.arms, new.arms)
        np.testing.assert_allclose(old.lams, new.lams, atol=1e-6)
        # forced-exploration burn-in lands on the newcomer
        assert (new.segment(1).arms[:, :CFG.forced_pulls] == 3).all()

    def test_budget_change(self, env):
        t1, T = 60, 140
        seg1, seg2 = [], []
        for s in SEEDS:
            rng = np.random.default_rng(500 + s)
            seg1.append(env.subset(rng.integers(0, env.n, t1)))
            seg2.append(env.subset(rng.integers(0, env.n, T - t1)))
        states = evaluate.make_states(CFG, env, 1.9e-3, SEEDS)
        res1, states = evaluate.run(CFG, seg1, 1.9e-3, seeds=SEEDS,
                                    states=states, shuffle=False,
                                    return_states=True)
        states = jax.vmap(lambda st: dataclasses.replace(
            st, pacer=pacer.set_budget(st.pacer, 3.0e-4)))(states)
        res2, _ = evaluate.run(CFG, seg2, 1.9e-3, seeds=SEEDS, states=states,
                               shuffle=False, return_states=True)
        old = evaluate.RunResult.concat([res1, res2])
        spec = ScenarioSpec(horizon=T, events=(BudgetChange(t1, 3.0e-4),),
                            stream_seed_base=500)
        new = evaluate.run_scenario(CFG, spec, env, 1.9e-3, seeds=SEEDS)
        np.testing.assert_array_equal(old.arms, new.arms)
        np.testing.assert_allclose(old.lams, new.lams, atol=1e-6)

    def test_delete_arm(self, env):
        t1, T = 50, 120
        spec = ScenarioSpec(horizon=T, events=(DeleteArm(t1, MISTRAL),),
                            stream_seed_base=600)
        res = evaluate.run_scenario(CFG, spec, env, 1.0, seeds=SEEDS)
        assert np.any(res.segment(0).arms == MISTRAL)
        assert not np.any(res.segment(1).arms == MISTRAL)


class TestOneJittedCall:
    def test_no_retrace_across_budgets_and_seeds(self, env):
        """A multi-event scenario is one compiled program per (config,
        spec, rate card, batch size): re-running with different budgets
        and different seed values must not retrace."""
        spec = ScenarioSpec(horizon=90, events=(
            PriceChange(30, GEMINI, 0.1, recalibrate=True),
            QualityShift(60, MISTRAL, 0.7)),
            stream_seed_base=42)
        evaluate.run_scenario(CFG, spec, env, 6.6e-4, seeds=(0, 1, 2))
        count = scenario.TRACE_COUNT[0]
        evaluate.run_scenario(CFG, spec, env, 3.0e-4, seeds=(7, 8, 9))
        assert scenario.TRACE_COUNT[0] == count, "scenario runner retraced"

    def test_batched_plane_is_separate_compile(self, env):
        spec = ScenarioSpec(horizon=90, events=(QualityShift(30, 1, 0.8),),
                            stream_seed_base=43)
        a = scenario.compiled_runner(CFG, spec, env, None)
        b = scenario.compiled_runner(CFG, spec, env, 16)
        assert a is not b
        assert scenario.compiled_runner(CFG, spec, env, None) is a


class TestBothDataPlanes:
    @pytest.mark.parametrize("batch_size", [4, 16])
    def test_trace_shapes_match_scalar(self, env4, batch_size):
        spec = ScenarioSpec(horizon=120, events=(
            AddArm(40, 3),
            PriceChange(80, GEMINI, 0.1)),
            stream_seed_base=44, init_active=3)
        scalar = evaluate.run_scenario(CFG, spec, env4, 6.6e-4, seeds=SEEDS)
        batched = evaluate.run_scenario(CFG, spec, env4, 6.6e-4, seeds=SEEDS,
                                        batch_size=batch_size)
        for f in ("arms", "rewards", "costs", "lams"):
            assert getattr(scalar, f).shape == getattr(batched, f).shape
        assert scalar.bounds == batched.bounds
        # burn-in routes to the newcomer on both planes
        assert (scalar.segment(1).arms[:, :CFG.forced_pulls] == 3).all()
        assert (batched.segment(1).arms[:, :CFG.forced_pulls] == 3).all()


def _assert_bitwise(a, b):
    np.testing.assert_array_equal(a.arms, b.arms)
    np.testing.assert_array_equal(a.rewards, b.rewards)
    np.testing.assert_array_equal(a.costs, b.costs)
    np.testing.assert_array_equal(a.lams, b.lams)


class TestParamPayloads:
    """Payloads as data (DESIGN.md §10): a ``Param`` payload resolved to
    value v must reproduce the concrete-payload spec at v bit-for-bit,
    and sweeping payload values must never retrace."""

    def test_stream_payloads_match_concrete_bitwise(self, env):
        """Silent price multiplier + quality target as traced stream
        transforms == the numpy-baked concrete lowering, exactly."""
        mk = lambda m, t: ScenarioSpec(horizon=120, events=(
            PriceChange(40, GEMINI, m),
            QualityShift(80, MISTRAL, t)), stream_seed_base=900)
        concrete = evaluate.run_scenario(
            CFG, mk(1 / 56, 0.72), env, 6.6e-4, seeds=SEEDS)
        param = evaluate.run_scenario(
            CFG, mk(Param("mult"), Param("target")), env, 6.6e-4,
            seeds=SEEDS,
            scenario_params=ScenarioParams(mult=1 / 56, target=0.72))
        _assert_bitwise(concrete, param)

    def test_hypershift_param_matches_concrete_bitwise(self, env):
        mk = lambda g: ScenarioSpec(horizon=120, events=(
            HyperShift(80, gamma=g),), stream_seed_base=901)
        concrete = evaluate.run_scenario(
            CFG, mk(0.9), env, 1.9e-3, seeds=SEEDS)
        param = evaluate.run_scenario(
            CFG, mk(Param("g")), env, 1.9e-3, seeds=SEEDS,
            scenario_params=ScenarioParams(g=0.9))
        _assert_bitwise(concrete, param)

    def test_budget_param_matches_host_loop_bitwise(self, env):
        """A Param ceiling is an *operand*, exactly like the hand-rolled
        host loop's vmapped ``set_budget`` — so the two agree bit-for-bit.
        (Concrete payloads are auto-lifted through the same operand path,
        so this holds for them too — DESIGN.md §10.)"""
        t1, T = 60, 140
        seg1, seg2 = [], []
        for s in SEEDS:
            rng = np.random.default_rng(920 + s)
            seg1.append(env.subset(rng.integers(0, env.n, t1)))
            seg2.append(env.subset(rng.integers(0, env.n, T - t1)))
        states = evaluate.make_states(CFG, env, 1.9e-3, SEEDS)
        res1, states = evaluate.run(CFG, seg1, 1.9e-3, seeds=SEEDS,
                                    states=states, shuffle=False,
                                    return_states=True)
        states = jax.vmap(lambda st: dataclasses.replace(
            st, pacer=pacer.set_budget(st.pacer, 3.0e-4)))(states)
        res2, _ = evaluate.run(CFG, seg2, 1.9e-3, seeds=SEEDS,
                               states=states, shuffle=False,
                               return_states=True)
        old = evaluate.RunResult.concat([res1, res2])
        spec = ScenarioSpec(horizon=T, events=(
            BudgetChange(t1, Param("ceiling")),), stream_seed_base=920)
        new = evaluate.run_scenario(
            CFG, spec, env, 1.9e-3, seeds=SEEDS,
            scenario_params=ScenarioParams(ceiling=3.0e-4))
        _assert_bitwise(old, new)

    def test_budget_param_matches_concrete_bitwise(self, env):
        """Concrete vs Param ceiling: bit-identical everywhere. The
        concrete payload is auto-lifted onto the same ``ScenarioParams``
        operand path (``__auto`` leaves), so XLA can no longer
        constant-fold the pacer's division differently — the 1-ulp
        fine print of the old §10 is gone."""
        mk = lambda b: ScenarioSpec(horizon=120, events=(
            BudgetChange(40, b),), stream_seed_base=921)
        concrete = evaluate.run_scenario(
            CFG, mk(3.0e-4), env, 1.9e-3, seeds=SEEDS)
        param = evaluate.run_scenario(
            CFG, mk(Param("ceiling")), env, 1.9e-3, seeds=SEEDS,
            scenario_params=ScenarioParams(ceiling=3.0e-4))
        _assert_bitwise(concrete, param)

    def test_recalibrate_param_matches_concrete_any_mult(self, env):
        """Concrete recalibrate multipliers share the Param path's f32
        operand lowering (auto-lift), so bits agree at ANY multiplier —
        not just the power-of-two carve-out the old fine print needed."""
        mk = lambda m: ScenarioSpec(horizon=120, events=(
            PriceChange(40, GEMINI, m, recalibrate=True),),
            stream_seed_base=902)
        for mult in (0.25, 1 / 56, 0.3):
            concrete = evaluate.run_scenario(CFG, mk(mult), env, 6.6e-4,
                                             seeds=SEEDS)
            param = evaluate.run_scenario(
                CFG, mk(Param("m")), env, 6.6e-4, seeds=SEEDS,
                scenario_params=ScenarioParams(m=mult))
            _assert_bitwise(concrete, param)

    def test_auto_prefix_reserved(self, env):
        """User params may not squat on the auto-lift namespace."""
        spec = ScenarioSpec(horizon=60, events=(
            BudgetChange(30, Param("__auto0")),), stream_seed_base=922)
        with pytest.raises(ValueError, match="reserved"):
            evaluate.run_scenario(
                CFG, spec, env, 1.9e-3, seeds=(0,),
                scenario_params=ScenarioParams(__auto0=3.0e-4))

    def test_add_arm_param_payloads(self, env4):
        """n_eff / bias_reward as Params (values chosen so the f32 and
        host-float lowerings round identically)."""
        mk = lambda ne, br: ScenarioSpec(
            horizon=120, events=(AddArm(40, 3, n_eff=ne, bias_reward=br),),
            stream_seed_base=903, init_active=3)
        concrete = evaluate.run_scenario(
            CFG, mk(130.0, 0.5), env4, 6.6e-4, seeds=SEEDS)
        param = evaluate.run_scenario(
            CFG, mk(Param("ne"), Param("bias")), env4, 6.6e-4, seeds=SEEDS,
            scenario_params=ScenarioParams(ne=130.0, bias=0.5))
        _assert_bitwise(concrete, param)
        # burn-in still lands on the newcomer through the param path
        assert (param.segment(1).arms[:, :CFG.forced_pulls] == 3).all()

    def test_add_arm_packed_prior_param(self, env4):
        priors = evaluate.fit_warmup_priors(CFG, env4)
        mk = lambda p: ScenarioSpec(
            horizon=120, events=(AddArm(40, 3, prior=p, n_eff=100.0),),
            stream_seed_base=904, init_active=3)
        concrete = evaluate.run_scenario(
            CFG, mk(priors[3]), env4, 6.6e-4, seeds=SEEDS)
        param = evaluate.run_scenario(
            CFG, mk(Param("prior")), env4, 6.6e-4, seeds=SEEDS,
            scenario_params=ScenarioParams(prior=priors[3]))
        _assert_bitwise(concrete, param)

    def test_no_retrace_across_payload_values(self, env):
        spec = ScenarioSpec(horizon=90, events=(
            PriceChange(30, GEMINI, Param("mult")),
            QualityShift(60, MISTRAL, Param("target"))),
            stream_seed_base=905)
        evaluate.run_scenario(
            CFG, spec, env, 6.6e-4, seeds=SEEDS,
            scenario_params=ScenarioParams(mult=0.1, target=0.7))
        count = scenario.TRACE_COUNT[0]
        evaluate.run_scenario(
            CFG, spec, env, 6.6e-4, seeds=SEEDS,
            scenario_params=ScenarioParams(mult=2.0, target=0.95))
        assert scenario.TRACE_COUNT[0] == count, (
            "payload values must be data, not structure")

    def test_missing_and_extra_params_rejected(self, env):
        spec = ScenarioSpec(horizon=60, events=(
            PriceChange(30, GEMINI, Param("mult")),), stream_seed_base=906)
        with pytest.raises(ValueError, match="mult"):
            evaluate.run_scenario(CFG, spec, env, 6.6e-4, seeds=SEEDS)
        with pytest.raises(ValueError, match="typo"):
            evaluate.run_scenario(
                CFG, spec, env, 6.6e-4, seeds=SEEDS,
                scenario_params=ScenarioParams(mult=0.1, typo=1.0))

    def test_param_names_collects_references(self):
        spec = ScenarioSpec(horizon=100, events=(
            PriceChange(20, 2, Param("b")),
            HyperShift(40, alpha=Param("a")),
            BudgetChange(60, Param("c"))))
        assert spec.param_names == ("a", "b", "c")

    def test_mix_weights_resolve_host_side(self, env):
        # weights exactly representable in f32: the param leaf is f32,
        # the concrete tuple is f64, and the draw must not depend on it
        w = tuple(3.0 if f == 1 else 0.25 for f in range(9))
        mk = lambda ws: ScenarioSpec(
            horizon=400, events=(TrafficMixShift(200, ws),),
            stream_seed_base=907)
        concrete = evaluate.run_scenario(CFG, mk(w), env, 6.6e-4,
                                         seeds=(0, 1))
        param = evaluate.run_scenario(
            CFG, mk(Param("mix")), env, 6.6e-4, seeds=(0, 1),
            scenario_params=ScenarioParams(mix=np.asarray(w, np.float32)))
        _assert_bitwise(concrete, param)

    def test_stacked_mix_weights_rejected(self, env):
        """Mix weights are structural (they change the prompt draw):
        a per-condition stack must fail loudly."""
        spec = ScenarioSpec(horizon=100, events=(
            TrafficMixShift(50, Param("mix")),), stream_seed_base=908)
        stacked = np.ones((2, 9), np.float32)
        with pytest.raises(ValueError, match="structural"):
            evaluate.run_scenario(
                CFG, spec, env, 6.6e-4, seeds=(0,),
                scenario_params=ScenarioParams(mix=stacked))

    def test_param_multiplier_is_not_the_restore(self, env):
        """A Param multiplier resolved to 1.0 multiplies by 1.0 (exact)
        rather than popping the modifier — bits match the base run."""
        base = evaluate.run_scenario(
            CFG, ScenarioSpec(horizon=90, events=(
                PriceChange(30, GEMINI, 1.0),), stream_seed_base=909),
            env, 6.6e-4, seeds=SEEDS)
        param = evaluate.run_scenario(
            CFG, ScenarioSpec(horizon=90, events=(
                PriceChange(30, GEMINI, Param("m")),), stream_seed_base=909),
            env, 6.6e-4, seeds=SEEDS,
            scenario_params=ScenarioParams(m=1.0))
        _assert_bitwise(base, param)


class TestRunResultUtils:
    def _mk(self, t0, t, bounds=None):
        shape = (2, t - t0)
        return evaluate.RunResult(
            arms=np.full(shape, t0), rewards=np.zeros(shape),
            costs=np.zeros(shape), lams=np.zeros(shape), bounds=bounds)

    def test_concat_tracks_bounds(self):
        r = evaluate.RunResult.concat([self._mk(0, 10), self._mk(10, 25)])
        assert r.bounds == (0, 10, 25)
        assert r.arms.shape == (2, 25)
        assert r.n_segments == 2
        np.testing.assert_array_equal(r.segment(1).arms,
                                      np.full((2, 15), 10))

    def test_concat_merges_inner_bounds(self):
        a = self._mk(0, 10, bounds=(0, 4, 10))
        r = evaluate.RunResult.concat([a, self._mk(10, 18)])
        assert r.bounds == (0, 4, 10, 18)

    def test_segment_requires_bounds(self):
        with pytest.raises(ValueError, match="no segment boundaries"):
            self._mk(0, 10).segment(0)

    def test_segment_index_out_of_range(self):
        r = evaluate.RunResult.concat([self._mk(0, 10), self._mk(10, 25)])
        with pytest.raises(ValueError, match="out of range"):
            r.segment(2)
        with pytest.raises(ValueError, match="out of range"):
            r.segment(-1)


class TestConcatEnvironmentsRateCard:
    def test_strict_rejects_drifted_phase(self, env):
        drifted = simulator.with_price_multiplier(env, GEMINI, 0.01)
        with pytest.raises(ValueError, match="rate card"):
            simulator.concat_environments((env, drifted))

    def test_explicit_choice_allowed(self, env):
        drifted = simulator.with_price_multiplier(env, GEMINI, 0.01)
        first = simulator.concat_environments((env, drifted), prices="first")
        np.testing.assert_array_equal(first.prices_per_1k, env.prices_per_1k)
        last = simulator.concat_environments((env, drifted), prices="last")
        np.testing.assert_array_equal(last.prices_per_1k,
                                      drifted.prices_per_1k)
        # realised costs keep the per-phase truth either way
        assert first.n == 2 * env.n
        np.testing.assert_array_equal(first.costs, last.costs)

    def test_three_phase_stream_keeps_base_card(self, env):
        stream = simulator.three_phase_stream(
            env, lambda e: simulator.with_price_multiplier(e, GEMINI, 0.01),
            np.random.default_rng(0), phase_len=40)
        np.testing.assert_array_equal(stream.prices_per_1k,
                                      env.prices_per_1k)


class TestMakeStatesVectorized:
    def test_matches_per_seed_loop(self, env):
        """The vmap-over-keys construction equals the old Python loop +
        jnp.stack, including warm-start priors."""
        import jax.numpy as jnp
        from repro.core.types import init_state
        from repro.core import warmup
        priors = evaluate.fit_warmup_priors(CFG, env)
        got = evaluate.make_states(CFG, env, 6.6e-4, SEEDS, priors=priors,
                                   n_eff=100.0, active_arms=2)
        pad = CFG.max_arms - env.k
        preq = np.concatenate([env.prices_per_req,
                               np.full(pad, 1e9)]).astype(np.float32)
        active = np.zeros(CFG.max_arms, bool)
        active[:2] = True

        def one(seed):
            st = init_state(CFG, preq,
                            np.concatenate([env.prices_per_1k,
                                            np.full(pad, 1e9)]
                                           ).astype(np.float32),
                            6.6e-4, key=jax.random.PRNGKey(seed),
                            active=jnp.asarray(active))
            return warmup.apply_warmup(CFG, st, list(priors) + [None] * pad,
                                       100.0)

        want = jax.tree.map(lambda *xs: jnp.stack(xs),
                            *[one(int(s)) for s in SEEDS])
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-7)

    @pytest.mark.parametrize("case", ["cold", "warm", "per_state_vectors",
                                      "active_arms", "tenant_table"])
    def test_compiled_builder_matches_eager_vmap(self, env, case):
        """The one compiled builder equals the construction it replaced:
        ``init_state`` and the warm start vmapped eagerly, one dispatch
        per primitive."""
        import jax.numpy as jnp
        from repro.core import tenancy, warmup
        from repro.core.types import HYPER_FIELDS, HyperParams, init_state
        priors = evaluate.fit_warmup_priors(CFG, env)
        seeds = (7, 2 ** 31 + 5, 4294967295)
        budget = np.asarray([3e-4, 6.6e-4, 1.9e-3], np.float32)
        kw = {"priors": priors, "n_eff": 1164.0}
        if case == "cold":
            kw = {}
        elif case == "per_state_vectors":
            kw = {"priors": priors,
                  "n_eff": np.asarray([100.0, 1164.0, 5000.0], np.float32),
                  "hyper": HyperParams(
                      alpha=np.asarray([0.01, 0.1, 0.5], np.float32),
                      gamma=np.asarray([0.99, 0.997, 1.0], np.float32),
                      lambda0=np.asarray([0.5, 1.0, 2.0], np.float32))}
        elif case == "active_arms":
            kw["active_arms"] = 2
        elif case == "tenant_table":
            kw["tenants"] = tenancy.stack_tables(
                [tenancy.make_table([1e-4 * (i + 1), 5e-4]) for i in range(3)])
        got = evaluate.make_states(CFG, env, budget, seeds, **kw)

        pad = CFG.max_arms - env.k
        preq = np.concatenate([env.prices_per_req,
                               np.full(pad, 1e9)]).astype(np.float32)
        p1k = np.concatenate([env.prices_per_1k,
                              np.full(pad, 1e9)]).astype(np.float32)
        active = np.zeros(CFG.max_arms, bool)
        active[:kw.get("active_arms", env.k)] = True
        hp = kw.get("hyper", CFG.hyper)
        hp = HyperParams(**{n: jnp.asarray(getattr(hp, n), jnp.float32)
                            for n in HYPER_FIELDS})
        hp_axes = HyperParams(**{n: 0 if jnp.ndim(getattr(hp, n)) else None
                                 for n in HYPER_FIELDS})
        ne = jnp.asarray(kw.get("n_eff", 0.0), jnp.float32)
        tab = kw.get("tenants")

        def one(key, b, h, ne_, tb):
            st = init_state(CFG, preq, p1k, b, key=key,
                            active=jnp.asarray(active), hyper=h, tenants=tb)
            if "priors" in kw:
                st = warmup.apply_warmup(CFG, st,
                                         list(priors) + [None] * pad, ne_)
            return st

        keys = jax.vmap(jax.random.PRNGKey)(jnp.asarray(seeds, jnp.uint32))
        want = jax.vmap(one, in_axes=(0, 0, hp_axes, 0 if ne.ndim else None,
                                      None if tab is None else 0))(
            keys, jnp.asarray(budget), hp, ne, tab)
        assert (jax.tree.structure(got) == jax.tree.structure(want))
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            assert a.shape == b.shape and a.dtype == b.dtype
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-7)
