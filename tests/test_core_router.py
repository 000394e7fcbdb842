"""Unit tests for Algorithm 1: selection, updates, forgetting, pacer."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import linucb, pacer, registry, router, warmup
from repro.core.types import (
    HyperParams, RouterConfig, init_state, log_normalized_cost,
)
from tests.trace_guard import staging_ok

CFG = RouterConfig(d=6, max_arms=4)


def mk_state(budget=1.0, prices=(0.1, 1.0, 10.0, 1e9), active=(1, 1, 1, 0),
             cfg=CFG, **kw):
    with staging_ok():  # state init transfers on purpose
        return init_state(
            cfg,
            jnp.asarray(prices, jnp.float32),
            jnp.asarray(prices, jnp.float32),
            budget,
            active=jnp.asarray(active, bool),
            **kw,
        )


def rand_x(seed=0, d=CFG.d):
    with staging_ok():  # PRNG key creation transfers on purpose
        x = jax.random.normal(jax.random.PRNGKey(seed), (d,))
        return x.at[-1].set(1.0)


class TestShermanMorrison:
    def test_matches_dense_inverse(self):
        rng = np.random.default_rng(0)
        A = np.eye(6) + 0.1 * rng.standard_normal((6, 6))
        A = A @ A.T + np.eye(6)
        x = rng.standard_normal(6).astype(np.float32)
        got = linucb.sherman_morrison(jnp.linalg.inv(jnp.asarray(A, jnp.float32)), x)
        want = np.linalg.inv(A + np.outer(x, x))
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)

    def test_repeated_updates_stay_consistent(self):
        cfg = RouterConfig(d=6, max_arms=4, hyper=HyperParams(gamma=0.99))
        A = jnp.eye(6) * cfg.hyper.lambda0
        A_inv = jnp.eye(6) / cfg.hyper.lambda0
        b = jnp.zeros(6)
        for i in range(30):
            x = rand_x(i)
            A, A_inv, b, theta = linucb.rank1_update(
                cfg, cfg.hyper, A, A_inv, b, x, jnp.float32(0.5),
                jnp.int32(1)
            )
        np.testing.assert_allclose(
            A_inv, jnp.linalg.inv(A), rtol=1e-3, atol=1e-4
        )


class TestForgetting:
    def test_decay_is_scalar_multiply(self):
        cfg = RouterConfig(d=6, max_arms=4, hyper=HyperParams(gamma=0.9))
        A = jnp.eye(6) * 2.0
        A_inv = jnp.eye(6) / 2.0
        b = jnp.ones(6)
        A2, Ainv2, b2 = linucb.decay_statistics(
            cfg, cfg.hyper, A, A_inv, b, jnp.int32(3))
        np.testing.assert_allclose(A2, A * 0.9**3, rtol=1e-6)
        np.testing.assert_allclose(b2, b * 0.9**3, rtol=1e-6)
        np.testing.assert_allclose(Ainv2, A_inv / 0.9**3, rtol=1e-6)

    def test_gamma_one_is_standard_linucb(self):
        cfg = RouterConfig(d=6, max_arms=4, hyper=HyperParams(gamma=1.0))
        A = jnp.eye(6)
        A2, _, _ = linucb.decay_statistics(
            cfg, cfg.hyper, A, A, jnp.ones(6), jnp.int32(100))
        np.testing.assert_allclose(A2, A)

    def test_staleness_inflation_capped(self):
        cfg = RouterConfig(d=6, max_arms=4,
                           hyper=HyperParams(gamma=0.9, v_max=50.0))
        A_inv = jnp.eye(6)
        x = rand_x(1)
        v_fresh = linucb.ucb_variance(cfg, cfg.hyper, A_inv, x, jnp.int32(0))
        v_stale = linucb.ucb_variance(
            cfg, cfg.hyper, A_inv, x, jnp.int32(10_000))
        assert v_stale <= 50.0 * v_fresh + 1e-4
        assert v_stale > v_fresh


class TestPacer:
    def test_lambda_rises_when_overspending(self):
        st = mk_state(budget=0.5)
        p = st.pacer
        for _ in range(50):
            p = pacer.pacer_update(CFG.hyper, p, jnp.float32(5.0))
        assert float(p.lam) > 0.5

    def test_lambda_bounded(self):
        st = mk_state(budget=1e-6)
        p = st.pacer
        for _ in range(500):
            p = pacer.pacer_update(CFG.hyper, p, jnp.float32(100.0))
        assert float(p.lam) <= CFG.hyper.lambda_bar + 1e-6

    def test_lambda_decays_when_underspending(self):
        st = mk_state(budget=1.0)
        p = st.pacer
        for _ in range(100):
            p = pacer.pacer_update(CFG.hyper, p, jnp.float32(10.0))
        high = float(p.lam)
        for _ in range(300):
            p = pacer.pacer_update(CFG.hyper, p, jnp.float32(0.0))
        assert float(p.lam) < high
        assert float(p.lam) >= 0.0

    def test_hard_ceiling_excludes_expensive(self):
        st = mk_state()
        p = st.pacer
        import dataclasses
        p = dataclasses.replace(p, lam=jnp.float32(4.0))
        mask = pacer.hard_ceiling_mask(p, st.price, st.active)
        # ceiling = 10 / 5 = 2 -> arm 2 (price 10) excluded
        assert bool(mask[0]) and bool(mask[1]) and not bool(mask[2])
        assert not bool(mask[3])  # inactive stays excluded

    def test_disabled_pacer_freezes_lambda(self):
        st = mk_state(pacer_enabled=False)
        p = st.pacer
        for _ in range(50):
            p = pacer.pacer_update(CFG.hyper, p, jnp.float32(100.0))
        assert float(p.lam) == 0.0


class TestSelect:
    def test_selects_active_arm(self):
        st = mk_state()
        dec, st2 = router.select(CFG, st, rand_x())
        assert 0 <= int(dec.arm) < 3
        assert int(st2.t) == 1
        assert int(st2.last_play[dec.arm]) == 1

    def test_never_selects_inactive(self):
        st = mk_state(active=(1, 0, 0, 0))
        for i in range(10):
            dec, st = router.select(CFG, st, rand_x(i))
            assert int(dec.arm) == 0

    def test_cost_penalty_prefers_cheap_at_equal_quality(self):
        cfg = RouterConfig(d=6, max_arms=4,
                           hyper=HyperParams(alpha=0.0, lambda_c=0.5))
        st = mk_state(cfg=cfg, prices=(1e-4, 0.05, 0.09, 1e9))
        # identical (zero) reward estimates -> cheapest should win
        dec, _ = router.select(cfg, st, rand_x())
        assert int(dec.arm) == 0

    def test_forced_exploration_overrides(self):
        st = mk_state()
        st = registry.add_arm(CFG, st, 3, 0.5, 0.5, n_eff=5.0)
        for _ in range(CFG.forced_pulls):
            dec, st = router.select(CFG, st, rand_x())
            assert int(dec.arm) == 3
            assert bool(dec.forced)
        dec, st = router.select(CFG, st, rand_x())
        assert not bool(dec.forced)


class TestUpdate:
    def test_update_moves_theta_toward_reward(self):
        st = mk_state()
        x = rand_x(3)
        for _ in range(60):
            dec, st = router.select(CFG, st, x)
            st = router.update(CFG, st, jnp.int32(0), x, jnp.float32(0.9),
                               jnp.float32(0.1))
        pred = float(st.theta[0] @ x)
        assert abs(pred - 0.9) < 0.05

    def test_a_inv_consistent_after_mixed_stream(self):
        st = mk_state()
        key = jax.random.PRNGKey(7)
        for i in range(100):
            key, k1, k2 = jax.random.split(key, 3)
            x = jax.random.normal(k1, (CFG.d,)).at[-1].set(1.0)
            dec, st = router.select(CFG, st, x)
            r = jax.random.uniform(k2)
            st = router.update(CFG, st, dec.arm, x, r, jnp.float32(0.01))
        for a in range(3):
            np.testing.assert_allclose(
                st.A_inv[a], jnp.linalg.inv(st.A[a]), rtol=5e-3, atol=1e-4
            )


class TestRegistry:
    def test_add_then_delete_roundtrip(self):
        st = mk_state()
        st = registry.add_arm(CFG, st, 3, 2.0, 2.0, n_eff=10.0)
        assert bool(st.active[3])
        assert registry.num_active(st) == 4
        st = registry.delete_arm(CFG, st, 3)
        assert not bool(st.active[3])
        assert int(st.force_left) == 0
        # a cold re-add writes the very bits the deletion left
        readded = registry.add_arm(CFG, st, 3, 2.0, 2.0)
        for leaf in ("A", "A_inv", "b", "theta"):
            np.testing.assert_array_equal(getattr(readded, leaf)[3],
                                          getattr(st, leaf)[3])

    @pytest.mark.parametrize("init", ["cold", "heuristic", "heuristic_traced",
                                      "offline_prior"])
    def test_add_arm_inverse(self, init):
        """Isotropic initialisations write A^-1 = I/s and theta = b/s in
        closed form (s = lambda0, or lambda0 + n_eff/d); the offline
        prior's full matrix is still inverted."""
        d, lam = CFG.d, np.float32(CFG.hyper.lambda0)
        st = mk_state()
        if init == "cold":
            st = registry.add_arm(CFG, st, 3, 0.5, 0.5)
            s = lam
        elif init == "heuristic":
            st = registry.add_arm(CFG, st, 3, 0.5, 0.5, n_eff=10.0,
                                  bias_reward=0.8)
            s = lam + np.float32(10.0) / np.float32(d)
        elif init == "heuristic_traced":
            n_eff = np.asarray([0.0, 10.0, 1164.0], np.float32)
            with staging_ok():
                stacked = jax.tree.map(lambda l: jnp.stack([l] * 3), st)
                st = jax.jit(jax.vmap(lambda s_, ne: registry.add_arm(
                    CFG, s_, 3, 0.5, 0.5, n_eff=ne, bias_reward=0.8)))(
                        stacked, jnp.asarray(n_eff))
            s = (lam + n_eff / np.float32(d))[:, None, None]
        else:
            rng = np.random.default_rng(0)
            xs = rng.standard_normal((200, d)).astype(np.float32)
            xs[:, -1] = 1.0
            with staging_ok():
                prior = warmup.fit_offline_prior(
                    jnp.asarray(xs), jnp.asarray(xs @ np.linspace(-.3, .6, d),
                                                 jnp.float32))
            st = registry.add_arm(CFG, st, 3, 0.5, 0.5, prior=prior,
                                  n_eff=50.0)
        A = np.asarray(st.A[..., 3, :, :])
        A_inv = np.asarray(st.A_inv[..., 3, :, :])
        if init == "offline_prior":
            assert np.count_nonzero(A - np.diag(np.diag(A))) > 0
            np.testing.assert_allclose(A_inv, jnp.linalg.inv(st.A[3]),
                                       rtol=5e-3, atol=1e-4)
            return
        # jit may round n_eff/d differently from numpy by an ulp: take s
        # from A's diagonal, checked against its formula, then hold
        # A, A^-1 and theta to that s exactly.
        s_prog = A[..., :1, :1]
        np.testing.assert_allclose(s_prog, np.broadcast_to(s, s_prog.shape),
                                   rtol=1e-6)
        eye = np.eye(d, dtype=np.float32)
        np.testing.assert_array_equal(A, eye * s_prog)
        np.testing.assert_array_equal(A_inv, eye / s_prog)
        b = np.asarray(st.b[..., 3, :])
        np.testing.assert_array_equal(np.asarray(st.theta[..., 3, :]),
                                      b / s_prog[..., 0])
        prod = jnp.matmul(A_inv, A, precision=jax.lax.Precision.HIGHEST)
        np.testing.assert_allclose(prod, np.broadcast_to(eye, prod.shape),
                                   rtol=0, atol=1e-6)

    def test_heuristic_prior_biases_prediction(self):
        st = mk_state()
        st = registry.add_arm(CFG, st, 3, 2.0, 2.0, n_eff=100.0,
                              bias_reward=0.8, forced_exploration=False)
        x = jnp.zeros(CFG.d).at[-1].set(1.0)
        pred = float(st.theta[3] @ x)
        assert abs(pred - 0.8) < 0.15

    def test_set_price_updates_ctilde(self):
        st = mk_state()
        st2 = registry.set_price(CFG, st, 2, 0.001, 0.001)
        assert float(st2.c_tilde[2]) < float(st.c_tilde[2])


class TestWarmup:
    def test_scaled_prior_preserves_mean(self):
        cfg = RouterConfig(d=6, max_arms=4)
        rng = np.random.default_rng(0)
        xs = jnp.asarray(rng.standard_normal((500, 6)), jnp.float32)
        xs = xs.at[:, -1].set(1.0)
        theta_true = jnp.asarray([0.1, -0.2, 0.0, 0.3, 0.05, 0.6])
        rs = xs @ theta_true
        prior = warmup.fit_offline_prior(xs, rs)
        A, b = warmup.scale_prior(cfg, cfg.hyper, prior, n_eff=50.0)
        theta = jnp.linalg.solve(A, b)
        np.testing.assert_allclose(theta, prior.theta_off, rtol=0.1, atol=0.02)

    def test_t_adapt_roundtrip(self):
        for gamma in (0.994, 0.997, 0.999):
            n = warmup.t_adapt_to_n_eff(500.0, gamma)
            t = warmup.n_eff_to_t_adapt(n, gamma)
            assert abs(t - 500.0) < 1e-6

    def test_paper_value(self):
        # Appendix A: T_adapt=500, gamma=0.997 -> n_eff ~= 1164
        n = warmup.t_adapt_to_n_eff(500.0, 0.997)
        assert abs(n - 1164) < 15


class TestCostNormalization:
    def test_eq6_floor_and_ceiling(self):
        cfg = RouterConfig(d=6, max_arms=4)
        assert float(log_normalized_cost(jnp.float32(1e-4), cfg.hyper)) == 0.0
        assert float(log_normalized_cost(jnp.float32(2.9e-5), cfg.hyper)) == 0.0
        assert abs(float(log_normalized_cost(jnp.float32(0.1), cfg.hyper)) - 1.0) < 1e-6
        mid = float(log_normalized_cost(jnp.float32(5.3e-4 * 1.0), cfg.hyper))
        assert 0.0 < mid < 1.0
