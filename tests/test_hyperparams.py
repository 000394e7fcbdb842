"""Hyper-parameters as data (DESIGN.md §9): the Statics/HyperParams
split, the retired legacy RouterConfig shim, HyperParams as a state leaf
through run/run_scenario/sweep, the HyperShift scenario event, the Pallas
backend under the fabric's flattened vmap axis, and zero-retrace retuning
of a live PortfolioServer."""
import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import backend as backend_lib
from repro.core import evaluate, router, scenario, simulator, sweep
from repro.core.types import (
    HYPER_FIELDS, HyperParams, RouterConfig, Statics, init_state,
    with_hyperparams,
)

SEEDS = (0, 1, 2)


@pytest.fixture(scope="module")
def bench():
    return simulator.make_benchmark(
        seed=0, splits={"train": 256, "val": 32, "test": 200})


@pytest.fixture(scope="module")
def env(bench):
    return bench.test


@pytest.fixture(scope="module")
def priors(bench):
    return evaluate.fit_warmup_priors(RouterConfig(), bench.train)


def _assert_bitwise(a, b):
    np.testing.assert_array_equal(a.arms, b.arms)
    np.testing.assert_array_equal(a.rewards, b.rewards)
    np.testing.assert_array_equal(a.costs, b.costs)
    np.testing.assert_array_equal(a.lams, b.lams)


class TestConfigSplit:
    def test_statics_projection_ignores_hypers(self):
        a = RouterConfig(hyper=HyperParams(alpha=0.005, gamma=0.999))
        b = RouterConfig(hyper=HyperParams(alpha=0.2, gamma=0.994))
        assert a.statics == b.statics        # same compiled-program key
        assert hash(a.statics) == hash(b.statics)
        assert a != b                        # but distinct configs

    def test_statics_capture_trace_knobs(self):
        assert RouterConfig(backend="pallas").statics != \
            RouterConfig().statics
        assert RouterConfig(d=8).statics == Statics(d=8)

    def test_legacy_kwargs_are_retired(self):
        """The pre-split flat kwargs (deprecated since the §9 split) now
        fail loudly with the migration spelled out."""
        with pytest.raises(TypeError, match="hyper=HyperParams"):
            RouterConfig(max_arms=4, alpha=0.05, gamma=0.99)
        with pytest.raises(TypeError, match="hyper=HyperParams"):
            RouterConfig(alpha=0.05, hyper=HyperParams())

    def test_read_through_properties_are_retired(self):
        cfg = RouterConfig(hyper=HyperParams(alpha=0.05))
        # AttributeError (not TypeError): hasattr probes must keep working
        with pytest.raises(AttributeError, match="cfg.hyper.alpha"):
            cfg.alpha
        assert not hasattr(cfg, "alpha")
        assert cfg.hyper.alpha == 0.05

    def test_unknown_kwarg_rejected(self):
        with pytest.raises(TypeError, match="unknown"):
            RouterConfig(alhpa=0.05)

    @pytest.mark.parametrize("bad", [
        dict(gamma=0.0), dict(gamma=1.5), dict(lambda0=0.0),
        dict(alpha=-1.0), dict(alpha_ema=0.0), dict(v_max=0.5),
        dict(c_floor=0.2, c_ceil=0.1),
    ])
    def test_validation_raises_value_error(self, bad):
        # ValueError, not assert: must survive ``python -O``
        with pytest.raises(ValueError):
            HyperParams(**bad).validate()
        with pytest.raises(ValueError):
            RouterConfig(hyper=HyperParams(**bad))

    def test_statics_validation_raises_value_error(self):
        with pytest.raises(ValueError):
            RouterConfig(backend="cuda")
        with pytest.raises(ValueError):
            RouterConfig(d=1)

    def test_runtime_gamma_clamp(self):
        """A traced gamma leaf outside (0, 1] cannot be validated at
        construction time — forgetting_factor clamps it instead."""
        from repro.core import linucb
        cfg = RouterConfig()
        hot = HyperParams(gamma=jnp.float32(7.7)).as_leaves()
        g = linucb.forgetting_factor(cfg, hot, jnp.int32(10))
        assert float(g) == 1.0               # clamped to gamma = 1
        cold = HyperParams(gamma=jnp.float32(-3.0)).as_leaves()
        g = linucb.forgetting_factor(cfg, cold, jnp.int32(1))
        assert 0.0 < float(g) <= linucb.GAMMA_FLOOR


class TestHyperAsStateLeaf:
    def _state(self, cfg, **kw):
        prices = jnp.asarray([1e-4, 1e-3, 1e-2, 1e9], jnp.float32)
        return init_state(cfg, prices, prices, 1.0,
                          active=jnp.asarray([1, 1, 1, 0], bool), **kw)

    def test_init_state_seeds_f32_leaves(self):
        cfg = RouterConfig(max_arms=4,
                           hyper=HyperParams(alpha=0.2, gamma=0.95))
        st = self._state(cfg)
        for n in HYPER_FIELDS:
            leaf = getattr(st.hyper, n)
            assert leaf.dtype == jnp.float32 and leaf.shape == ()
        assert float(st.hyper.alpha) == np.float32(0.2)

    def test_with_hyperparams_overrides(self):
        st = self._state(RouterConfig(max_arms=4))
        st2 = with_hyperparams(st, lambda_c=2.0)
        assert float(st2.hyper.lambda_c) == 2.0
        assert float(st.hyper.lambda_c) == np.float32(0.3)  # pure edit
        with pytest.raises(ValueError):
            with_hyperparams(st, gamma=2.0)
        with pytest.raises(TypeError):
            with_hyperparams(st, not_a_knob=1.0)

    def test_cost_range_cross_check_against_merged_values(self):
        """Overriding only c_ceil below the state's live c_floor must be
        rejected: an inverted Eq. 6 range silently zeroes the cost
        penalty on the next reprice."""
        st = self._state(RouterConfig(max_arms=4))   # c_floor = 1e-4
        with pytest.raises(ValueError, match="exceed c_floor"):
            with_hyperparams(st, c_ceil=5e-5)
        st2 = with_hyperparams(st, c_floor=1e-5)
        with_hyperparams(st2, c_ceil=5e-5)           # now consistent

    def test_hypers_steer_routing(self):
        """A huge traced cost penalty routes to the cheapest arm — the
        hyper leaf, not the config, is what the math reads."""
        cfg = RouterConfig(max_arms=4)
        x = jnp.zeros(cfg.d).at[-1].set(1.0)
        st = self._state(cfg, hyper=HyperParams(alpha=0.0, lambda_c=50.0,
                                                tiebreak_scale=0.0))
        dec, _ = router.select(cfg, st, x)
        assert int(dec.arm) == 0             # cheapest
        st = self._state(cfg, hyper=HyperParams(alpha=0.0, lambda_c=0.0,
                                                tiebreak_scale=0.0))
        dec, _ = router.select(cfg, st, x)   # no penalty: tie on slot 0
        assert int(dec.arm) == 0

    def test_run_hyper_kwarg_matches_legacy_config(self, env):
        """evaluate.run(hyper=...) == the same values baked in the cfg."""
        hp = HyperParams(alpha=0.1, gamma=0.999)
        a = evaluate.run(RouterConfig(), env, 6.6e-4, seeds=SEEDS, hyper=hp)
        b = evaluate.run(RouterConfig(hyper=hp), env, 6.6e-4, seeds=SEEDS)
        _assert_bitwise(a, b)

    def test_make_states_stacked_hyper_axis(self, env):
        """(N,)-stacked hyper leaves: one state per (seed, alpha) pair."""
        hp = HyperParams(alpha=jnp.asarray([0.01, 0.1, 0.5], jnp.float32))
        states = evaluate.make_states(RouterConfig(), env, 6.6e-4, SEEDS,
                                      hyper=hp)
        np.testing.assert_allclose(np.asarray(states.hyper.alpha),
                                   [0.01, 0.1, 0.5])
        np.testing.assert_allclose(np.asarray(states.hyper.gamma),
                                   [0.997] * 3)
        with pytest.raises(ValueError, match="stack"):
            evaluate.make_states(
                RouterConfig(), env, 6.6e-4, SEEDS,
                hyper=HyperParams(alpha=jnp.ones(2)))


class TestOneProgramAcrossHypers:
    def test_run_reuses_program_across_hyper_values(self, env):
        """The pre-split design retraced per (α, γ) cfg; now every cell
        re-enters one cached program (the #1 ROADMAP item)."""
        evaluate.run(RouterConfig(), env, 6.6e-4, seeds=SEEDS)  # warm
        before = router.TRACE_COUNT[0]
        for alpha in (0.005, 0.05, 0.2):
            evaluate.run(RouterConfig(hyper=HyperParams(alpha=alpha)),
                         env, 6.6e-4, seeds=SEEDS)
        assert router.TRACE_COUNT[0] == before, "hyper change retraced"

    @pytest.mark.parametrize("layout", ["per_condition", "per_element"])
    def test_grid_hyper_condition_axis_bitwise(self, layout, env, priors):
        """(α, γ) and a per-cell warm start's n_eff (Eq. 13) stacked on
        the fused condition axis, as (C,) vectors or as (C*S,) stacks
        repeated per seed, expand onto the flattened axis inside
        make_states' one compiled program: one fabric trace, and each
        condition bit-identical to its looped run."""
        from repro.core import warmup
        cfg = RouterConfig()
        cells = ((0.01, 0.997), (0.1, 0.999))
        n_effs = [warmup.t_adapt_to_n_eff(500.0, g) for _, g in cells]
        reps = 1 if layout == "per_condition" else len(SEEDS)

        def stack(values):
            return np.repeat(np.asarray(values, np.float32), reps)

        hyp = HyperParams(alpha=stack([a for a, _ in cells]),
                          gamma=stack([g for _, g in cells]))
        before = sweep.TRACE_COUNT[0]
        grid = sweep.run_grid(cfg, env, (6.6e-4, 1.9e-3), seeds=SEEDS,
                              priors=priors, n_eff=stack(n_effs), hyper=hyp)
        assert sweep.TRACE_COUNT[0] - before <= 1
        for i, ((a, g), b) in enumerate(zip(cells, (6.6e-4, 1.9e-3))):
            res = evaluate.run(cfg, env, b, seeds=SEEDS, priors=priors,
                               n_eff=n_effs[i],
                               hyper=HyperParams(alpha=a, gamma=g))
            _assert_bitwise(grid.condition(i), res)

    def test_mixed_warm_cold_neff_rejected(self, env, priors):
        with pytest.raises(ValueError, match="mixed warm/cold"):
            evaluate.make_states(RouterConfig(), env, 6.6e-4, SEEDS,
                                 priors=priors,
                                 n_eff=np.asarray([0.0, 100.0, 100.0]))

    def test_scenario_runner_shared_across_hypers(self, env):
        """Scenario runners are cached on the statics projection: configs
        differing only in hypers share one compiled runner."""
        spec = scenario.ScenarioSpec(horizon=60)
        evaluate.run_scenario(RouterConfig(max_arms=4), spec, env, 6.6e-4,
                              seeds=SEEDS)
        before = scenario.TRACE_COUNT[0]
        res = evaluate.run_scenario(
            RouterConfig(max_arms=4, hyper=HyperParams(alpha=0.2)),
            spec, env, 6.6e-4, seeds=SEEDS)
        assert scenario.TRACE_COUNT[0] == before, "hyper change retraced"
        assert res.arms.shape == (len(SEEDS), 60)


class TestHyperShift:
    def test_mid_stream_retune_changes_behaviour(self, env):
        """An operator exploration spike (α: 0.01 → 5) mid-stream pulls
        the cold-started router off the cheap arm in segment 2 — one
        compiled program, no retrace at the boundary."""
        cfg = RouterConfig(max_arms=4)
        T = 200
        flat = scenario.ScenarioSpec(horizon=T)
        shifted = scenario.ScenarioSpec(
            horizon=T, events=(
                scenario.HyperShift(T // 2, alpha=5.0, lambda_c=0.0),))
        before = scenario.TRACE_COUNT[0]
        res = evaluate.run_scenario(cfg, shifted, env, 1.0, seeds=SEEDS)
        assert scenario.TRACE_COUNT[0] == before + 1, (
            "HyperShift scenario must stay one compiled program")
        base = evaluate.run_scenario(cfg, flat, env, 1.0, seeds=SEEDS)
        # same stream, same draws before the boundary
        np.testing.assert_array_equal(
            res.segment(0).arms, base.phase(0, T // 2).arms)
        # after the shift, exploration spreads traffic off the cheap arm
        explore = lambda r: float((r.arms != 0).mean())  # noqa: E731
        assert explore(res.segment(1)) > explore(
            base.phase(T // 2, T)) + 0.2

    def test_round_trips_through_final_states(self, env):
        spec = scenario.ScenarioSpec(
            horizon=60, events=(scenario.HyperShift(30, gamma=0.95,
                                                    eta=0.2),))
        _, finals = evaluate.run_scenario(
            RouterConfig(max_arms=4), spec, env, 6.6e-4, seeds=SEEDS,
            return_states=True)
        np.testing.assert_allclose(np.asarray(finals.hyper.gamma),
                                   [np.float32(0.95)] * len(SEEDS))
        np.testing.assert_allclose(np.asarray(finals.hyper.eta),
                                   [np.float32(0.2)] * len(SEEDS))
        # untouched fields keep their initial values
        np.testing.assert_allclose(np.asarray(finals.hyper.alpha),
                                   [np.float32(0.01)] * len(SEEDS))

    def test_bad_payload_rejected_at_spec_build(self):
        with pytest.raises(ValueError):
            scenario.HyperShift(10, gamma=1.5).overrides()

    def test_noop_shift_matches_flat_run(self, env):
        cfg = RouterConfig(max_arms=4)
        spec = scenario.ScenarioSpec(
            horizon=80, events=(scenario.HyperShift(40),))
        res = evaluate.run_scenario(cfg, spec, env, 6.6e-4, seeds=SEEDS)
        flat = evaluate.run_scenario(
            cfg, scenario.ScenarioSpec(horizon=80), env, 6.6e-4,
            seeds=SEEDS)
        np.testing.assert_array_equal(res.arms, flat.arms)


# alpha 0.05 in the first of two conditions, the default in the second.
_ALPHA_PER_CONDITION = HyperParams(
    alpha=np.asarray([0.05, HyperParams().alpha], np.float32))


class TestPallasUnderFabricVmap:
    """ROADMAP item: validate the Pallas ``linucb_score`` backend under
    the fabric's flattened (condition x seed) vmap axis — including
    hyper-parameters stacked on the condition axis."""

    def test_vmapped_scores_match_oracle_with_stacked_hypers(self):
        cfg = RouterConfig(d=8, max_arms=3)
        rng = np.random.default_rng(3)
        theta = jnp.asarray(rng.standard_normal((3, 8)) * 0.1, jnp.float32)
        M = rng.standard_normal((3, 8, 8)) * 0.1
        A = np.einsum("kij,klj->kil", M, M) + np.eye(8)[None]
        ainv = jnp.asarray(np.linalg.inv(A), jnp.float32)
        c_tilde = jnp.asarray([0.0, 0.4, 0.9], jnp.float32)
        X = jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)
        dt = jnp.asarray([0, 7, 500], jnp.int32)
        lam = jnp.float32(0.7)
        base = HyperParams().as_leaves()
        stack = dataclasses.replace(
            base,
            alpha=jnp.asarray([0.005, 0.05, 0.2], jnp.float32),
            gamma=jnp.asarray([0.994, 0.997, 1.0], jnp.float32),
        )
        axes = dataclasses.replace(
            jax.tree.map(lambda _: None, base), alpha=0, gamma=0)

        def score(bk, hp):
            return backend_lib.get_backend(bk).score(
                cfg, hp, theta, ainv, c_tilde, X, dt, lam)

        got = jax.vmap(lambda hp: score("pallas", hp),
                       in_axes=(axes,))(stack)
        want = jax.vmap(lambda hp: score("jnp", hp),
                        in_axes=(axes,))(stack)
        assert got.shape == (3, 16, 3)
        assert float(jnp.max(jnp.abs(got - want))) <= backend_lib.EQUIV_TOL

    def test_run_grid_pallas_bitwise_vs_looped(self, env):
        """The batching rule must not change the kernel's numbers: the
        fabric grid (wide vmap axis) reproduces per-condition looped runs
        of the SAME backend bit-for-bit, with hypers on the grid axis."""
        cfg = RouterConfig(max_arms=4, backend="pallas")
        grid = sweep.run_grid(cfg, env, (6.6e-4, 1.9e-3), seeds=SEEDS,
                              batch_size=16, hyper=_ALPHA_PER_CONDITION)
        a = evaluate.run(cfg, env, 6.6e-4, seeds=SEEDS, batch_size=16,
                         hyper=HyperParams(alpha=0.05))
        b = evaluate.run(cfg, env, 1.9e-3, seeds=SEEDS, batch_size=16)
        _assert_bitwise(grid.condition(0), a)
        _assert_bitwise(grid.condition(1), b)

    def test_run_grid_pallas_tracks_jnp_grid(self, env):
        """Backend equivalence holds inside the fabric: same grid, both
        backends, per-decision agreement within the contract's reach
        (scores differ <= EQUIV_TOL, so argmax flips are rare)."""
        grids = {}
        for bk in ("jnp", "pallas"):
            cfg = RouterConfig(max_arms=4, backend=bk)
            grids[bk] = sweep.run_grid(
                cfg, env, (6.6e-4, 1.9e-3), seeds=SEEDS, batch_size=16,
                hyper=_ALPHA_PER_CONDITION)
        agree = (grids["jnp"].arms == grids["pallas"].arms).mean()
        assert agree > 0.99, f"backends diverged: {agree:.3f} agreement"


class TestLiveServerRetune:
    def _server(self):
        from repro.core.costs import ArmPricing
        from repro.core.features import fit_pca_whitener, hash_encode_batch
        from repro.data import make_request_stream
        from repro.models.config import ModelConfig
        from repro.serving import PortfolioServer, ServedModel, SimulatedJudge

        def tiny(name, d=32):
            return ModelConfig(
                name=name, arch_type="dense", num_layers=1, d_model=d,
                num_heads=2, num_kv_heads=2, d_ff=2 * d, vocab_size=256,
                dtype="float32")

        corpus = [r["prompt"] for r in make_request_stream(120, seed=9)]
        whitener = fit_pca_whitener(hash_encode_batch(corpus))
        models = [
            ServedModel.init(tiny("budget"), ArmPricing("budget", 1e-4, 300),
                             "budget", 0),
            ServedModel.init(tiny("mid"), ArmPricing("mid", 1e-3, 500),
                             "mid", 1),
        ]
        return PortfolioServer(
            models, whitener, budget=6.6e-4,
            router_cfg=RouterConfig(max_arms=4,
                                    hyper=HyperParams(gamma=1.0)),
            judge=SimulatedJudge(0, noise=0.0), max_new_tokens=2, seed=0)

    def test_set_hyperparams_no_retrace(self):
        from repro.data import make_request_stream
        srv = self._server()
        reqs = make_request_stream(8, seed=21)
        srv.serve_batch(reqs[:4])            # warm both block programs
        before = router.TRACE_COUNT[0]
        live = srv.set_hyperparams(alpha=0.5, lambda_c=1.0)
        assert live.alpha == np.float32(0.5)
        assert live.gamma == np.float32(1.0)  # untouched
        srv.serve_batch(reqs[4:8])           # same block shape
        assert router.TRACE_COUNT[0] == before, (
            "set_hyperparams must not retrace the serving programs")
        assert float(np.asarray(srv.state.hyper.alpha)) == np.float32(0.5)

    def test_set_hyperparams_validates(self):
        srv = self._server()
        with pytest.raises(ValueError):
            srv.set_hyperparams(gamma=0.0)
        with pytest.raises(TypeError):
            srv.set_hyperparams(frobnicate=1.0)

    def test_full_replacement_and_view(self):
        srv = self._server()
        srv.set_hyperparams(HyperParams(alpha=0.07, gamma=0.99))
        live = srv.hyperparams()
        assert live.alpha == np.float32(0.07)
        assert live.gamma == np.float32(0.99)


class TestNoLegacyWarningsFromNewApi:
    def test_new_style_construction_is_clean(self):
        with warnings.catch_warnings():
            warnings.simplefilter("error", DeprecationWarning)
            RouterConfig(d=8, max_arms=4, backend="pallas",
                         hyper=HyperParams(alpha=0.1))
