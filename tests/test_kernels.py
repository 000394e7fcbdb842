"""Per-kernel validation: shape/dtype sweeps against pure-jnp oracles,
run in interpret mode on the CPU (``kernels.interpret_mode``); the same
kernels compile for a described TPU in tests/test_tpu_compile.py."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.ssd_scan.ops import ssd_scan
from repro.kernels.ssd_scan.ref import ssd_sequential
from repro.kernels.linucb_score.ops import linucb_score
from repro.kernels.linucb_score.ref import linucb_score_ref
from repro.kernels.linucb_score.kernel import linucb_score_blocked
from repro.kernels.linucb_step.kernel import linucb_step_blocked
from repro.kernels.linucb_step.ops import linucb_step
from repro.kernels.linucb_step.ref import linucb_step_ref

RNG = np.random.default_rng(42)


def randn(shape, dtype=jnp.float32):
    return jnp.asarray(RNG.standard_normal(shape), dtype)


TOLS = {jnp.float32: dict(rtol=2e-4, atol=2e-5),
        jnp.bfloat16: dict(rtol=5e-2, atol=5e-2)}


class TestFlashAttention:
    @pytest.mark.parametrize("S,H,KV,hd", [
        (64, 4, 2, 16), (128, 8, 8, 32), (96, 6, 3, 48), (130, 4, 1, 24),
    ])
    def test_shapes_causal(self, S, H, KV, hd):
        q = randn((2, S, H, hd))
        k = randn((2, S, KV, hd))
        v = randn((2, S, KV, hd))
        ref = flash_attention_ref(q, k, v)
        got = flash_attention(q, k, v, block_q=32, block_kv=32)
        np.testing.assert_allclose(got, ref, **TOLS[jnp.float32])

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dtypes(self, dtype):
        q = randn((1, 64, 4, 32), dtype)
        k = randn((1, 64, 2, 32), dtype)
        v = randn((1, 64, 2, 32), dtype)
        ref = flash_attention_ref(q, k, v).astype(jnp.float32)
        got = flash_attention(q, k, v, block_q=32, block_kv=32).astype(jnp.float32)
        np.testing.assert_allclose(got, ref, **TOLS[dtype])

    def test_sliding_window(self):
        q = randn((2, 64, 4, 16))
        k = randn((2, 64, 2, 16))
        v = randn((2, 64, 2, 16))
        ref = flash_attention_ref(q, k, v, mode="sliding", window=24)
        got = flash_attention(q, k, v, mode="sliding", window=24,
                              block_q=16, block_kv=16)
        np.testing.assert_allclose(got, ref, **TOLS[jnp.float32])

    def test_cross_attention_full(self):
        q = randn((2, 64, 4, 16))
        k = randn((2, 32, 2, 16))
        v = randn((2, 32, 2, 16))
        ref = flash_attention_ref(q, k, v, mode="full")
        got = flash_attention(q, k, v, mode="full", block_q=16, block_kv=16)
        np.testing.assert_allclose(got, ref, **TOLS[jnp.float32])


class TestDecodeAttention:
    @pytest.mark.parametrize("W,H,KV,hd,nvalid", [
        (64, 4, 2, 16, 64), (128, 8, 1, 32, 100), (256, 4, 4, 64, 7),
    ])
    def test_shapes(self, W, H, KV, hd, nvalid):
        q = randn((2, 1, H, hd))
        k = randn((2, W, KV, hd))
        v = randn((2, W, KV, hd))
        valid = jnp.arange(W) < nvalid
        ref = decode_attention_ref(q, k, v, valid)
        got = decode_attention(q, k, v, valid, block_kv=32)
        np.testing.assert_allclose(got, ref, **TOLS[jnp.float32])

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dtypes(self, dtype):
        q = randn((1, 1, 4, 32), dtype)
        k = randn((1, 64, 2, 32), dtype)
        v = randn((1, 64, 2, 32), dtype)
        valid = jnp.arange(64) < 50
        ref = decode_attention_ref(q, k, v, valid).astype(jnp.float32)
        got = decode_attention(q, k, v, valid, block_kv=32).astype(jnp.float32)
        np.testing.assert_allclose(got, ref, **TOLS[dtype])

    def test_ring_buffer_scattered_validity(self):
        """Non-contiguous valid slots (sliding-window wrap pattern)."""
        W = 64
        q = randn((2, 1, 4, 16))
        k = randn((2, W, 2, 16))
        v = randn((2, W, 2, 16))
        valid = jnp.asarray(RNG.random(W) > 0.5)
        ref = decode_attention_ref(q, k, v, valid)
        got = decode_attention(q, k, v, valid, block_kv=16)
        np.testing.assert_allclose(got, ref, **TOLS[jnp.float32])


class TestSSDScan:
    def _inputs(self, B=2, L=64, H=4, P=8, N=16, dtype=jnp.float32):
        x = randn((B, L, H, P), dtype)
        dt = jnp.asarray(RNG.uniform(0.001, 0.1, (B, L, H)), jnp.float32)
        A = -jnp.asarray(RNG.uniform(0.5, 4.0, (H,)), jnp.float32)
        Bi = randn((B, L, N), dtype)
        Ci = randn((B, L, N), dtype)
        D = jnp.asarray(RNG.standard_normal((H,)), jnp.float32)
        return x, dt, A, Bi, Ci, D

    @pytest.mark.parametrize("chunk", [8, 16, 32, 64])
    def test_chunk_sweep(self, chunk):
        x, dt, A, Bi, Ci, D = self._inputs()
        y_ref, h_ref = ssd_sequential(x, dt, A, Bi, Ci, D)
        y, h = ssd_scan(x, dt, A, Bi, Ci, D, chunk=chunk)
        np.testing.assert_allclose(y, y_ref, rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(h, h_ref, rtol=2e-4, atol=2e-4)

    @pytest.mark.parametrize("P,N", [(8, 8), (16, 32), (64, 16)])
    def test_dim_sweep(self, P, N):
        x, dt, A, Bi, Ci, D = self._inputs(P=P, N=N)
        y_ref, h_ref = ssd_sequential(x, dt, A, Bi, Ci, D)
        y, h = ssd_scan(x, dt, A, Bi, Ci, D, chunk=16)
        np.testing.assert_allclose(y, y_ref, rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(h, h_ref, rtol=2e-4, atol=2e-4)

    def test_bf16_inputs(self):
        x, dt, A, Bi, Ci, D = self._inputs(dtype=jnp.bfloat16)
        y_ref, _ = ssd_sequential(x, dt, A, Bi, Ci, D)
        y, _ = ssd_scan(x, dt, A, Bi, Ci, D, chunk=16)
        np.testing.assert_allclose(
            y.astype(jnp.float32), y_ref.astype(jnp.float32),
            rtol=0.08, atol=0.08)


class TestLinUCBScore:
    @pytest.mark.parametrize("R,K,d", [(32, 3, 26), (100, 4, 26), (256, 8, 13)])
    def test_matches_ref(self, R, K, d):
        x = randn((R, d))
        theta = randn((K, d)) * 0.1
        # SPD inverses
        M = RNG.standard_normal((K, d, d)) * 0.1
        A = np.einsum("kij,klj->kil", M, M) + np.eye(d)[None] * 1.2
        ainv = jnp.asarray(np.linalg.inv(A), jnp.float32)
        pen = jnp.asarray(RNG.uniform(0, 1, (K,)), jnp.float32)
        infl = jnp.asarray(RNG.uniform(0.005, 1.0, (K,)), jnp.float32)
        ref = linucb_score_ref(x, theta, ainv, pen, infl, 0.05)
        got = linucb_score(x, theta, ainv, pen, infl, alpha=0.05, block_r=32)
        np.testing.assert_allclose(got, ref, rtol=2e-4, atol=2e-5)

    @pytest.mark.parametrize("R,block_r", [(100, 32), (7, 256), (65, 64)])
    def test_ragged_rows_blocked(self, R, block_r):
        """Direct kernel-level call with R not a multiple of block_r:
        rows are padded to the block boundary and sliced back (the old
        ``assert R % block_r == 0`` rejected every partial gateway
        block)."""
        K, d = 3, 8
        x = randn((R, d))
        theta = randn((K, d)) * 0.1
        M = RNG.standard_normal((K, d, d)) * 0.1
        A = np.einsum("kij,klj->kil", M, M) + np.eye(d)[None] * 1.2
        ainv = jnp.asarray(np.linalg.inv(A), jnp.float32)
        pen = jnp.asarray(RNG.uniform(0, 1, (K,)), jnp.float32)
        infl = jnp.asarray(RNG.uniform(0.005, 1.0, (K,)), jnp.float32)
        out = linucb_score_blocked(
            x, theta, ainv, pen[None, :], infl[None, :],
            jnp.full((1, 1), 0.05, jnp.float32),
            block_r=block_r, interpret=True)
        assert out.shape == (R, K)
        ref = linucb_score_ref(x, theta, ainv, pen, infl, 0.05)
        np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-5)

    def test_matches_router_scores(self):
        """Kernel == the router's own per-request scoring math (Eq. 2)."""
        from repro.core import linucb
        from repro.core.types import HyperParams, RouterConfig
        cfg = RouterConfig(d=6, max_arms=4, hyper=HyperParams(alpha=0.05))
        theta = randn((4, 6)) * 0.1
        M = RNG.standard_normal((4, 6, 6)) * 0.1
        A = np.einsum("kij,klj->kil", M, M) + np.eye(6)[None]
        ainv = jnp.asarray(np.linalg.inv(A), jnp.float32)
        c_tilde = jnp.asarray([0.0, 0.3, 0.6, 0.9])
        lam = jnp.float32(0.7)
        dt = jnp.zeros((4,), jnp.int32)
        x = randn((6,))
        want = linucb.ucb_scores(
            cfg, cfg.hyper, theta, ainv, c_tilde, x, dt, lam)
        pen = (cfg.hyper.lambda_c + lam) * c_tilde
        infl = jnp.ones((4,))
        got = linucb_score(x[None], theta, ainv, pen, infl,
                           alpha=cfg.hyper.alpha)
        np.testing.assert_allclose(got[0], want, rtol=2e-4, atol=2e-5)


# ---------------------------------------------------------------------------
# Fused step megakernel (kernels/linucb_step, DESIGN.md §11)
# ---------------------------------------------------------------------------


def _step_operands(B=24, K=3, d=10, seed=7):
    """Raw pre-padded operands for the blocked fused kernel / its ref."""
    rng = np.random.default_rng(seed)
    M = rng.standard_normal((K, d, d)) * 0.1
    A = np.einsum("kij,klj->kil", M, M) + np.eye(d)[None] * 1.2
    A_inv = np.linalg.inv(A)
    b = rng.standard_normal((K, d)) * 0.1
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    return dict(
        A=f32(A), A_inv=f32(A_inv), b=f32(b),
        theta=f32(np.einsum("kij,kj->ki", A_inv, b)),
        last_upd=jnp.asarray(rng.integers(0, 50, (1, K)), jnp.int32),
        x=f32(rng.standard_normal((B, d))),
        rewards=f32(rng.uniform(0, 1, (B, K))),
        costs=f32(rng.uniform(0, 1e-3, (B, K))),
        noise=f32(rng.uniform(0, 1e-7, (B, K))),
        forced=jnp.asarray((np.arange(B) < 3)[:, None], jnp.int32),
        cand=f32(np.array([[1.0] * K])),
        pen=f32(rng.uniform(0, 0.5, (1, K))),
        infl=f32(rng.uniform(0.01, 1.0, (1, K))),
        hypf=f32([[0.05, 0.997, 0.05, 0.05, 5.0, 0.0, 0.0, 0.0]]),
        ints=jnp.asarray([[60, 1]], jnp.int32),
        pacer=f32([[0.2, 5e-4, 6.6e-4, 0.0]]),
    )


def _warmed(cfg, blocks=3, B=16, seed=0):
    """A router state warmed with a few jnp-oracle blocks."""
    from repro.core import router
    from repro.core.types import init_state
    rng = np.random.default_rng(seed)
    K, d = cfg.max_arms, cfg.d
    jcfg = RouterConfig(d=d, max_arms=K, backend="jnp", hyper=cfg.hyper)
    prices = jnp.asarray(np.linspace(1e-4, 5.6e-3, K), jnp.float32)
    state = init_state(jcfg, prices, prices, budget=6.6e-4,
                       key=jax.random.PRNGKey(3))
    for _ in range(blocks):
        X, R, C = _rand_env_block(rng, B, d, K)
        state, _ = router.step_batch(jcfg, state, X, R, C)
    return state, rng


def _rand_env_block(rng, B, d, K):
    X = jnp.asarray(rng.standard_normal((B, d)), jnp.float32)
    R = jnp.asarray(rng.uniform(0.5, 1.0, (B, K)), jnp.float32)
    C = jnp.asarray(rng.uniform(1e-5, 1e-3, (B, K)), jnp.float32)
    return X, R, C


from repro.core.types import RouterConfig, HyperParams  # noqa: E402


class TestLinUCBStepFused:
    def test_interpret_bitwise_vs_ref(self):
        """Interpret-mode kernel output is BITWISE equal to ref.py."""
        ops = _step_operands()
        got = linucb_step_blocked(
            ops["A"], ops["A_inv"], ops["b"], ops["theta"],
            ops["last_upd"], ops["x"], ops["rewards"], ops["costs"],
            ops["noise"], ops["forced"], ops["cand"], ops["pen"],
            ops["infl"], ops["hypf"], ops["ints"], ops["pacer"],
            num_valid=20, dt_max=4096, interpret=True)
        # The ref must go through jit: interpret-mode pallas evaluates the
        # kernel as one compiled XLA program, and eager op-by-op dispatch
        # reassociates the final theta matvec by one ulp.
        ref = jax.jit(functools.partial(
            linucb_step_ref, num_valid=20, dt_max=4096))
        want = ref(
            ops["A"], ops["A_inv"], ops["b"], ops["theta"],
            ops["last_upd"], ops["x"], ops["rewards"], ops["costs"],
            ops["noise"], ops["forced"], ops["cand"], ops["pen"],
            ops["infl"], ops["hypf"], ops["ints"], ops["pacer"])
        assert len(got) == len(want) == 8
        for g, w in zip(got, want):
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))

    @pytest.mark.parametrize("B", [1, 13, 64, 256])
    def test_step_batch_matches_oracle(self, B):
        """Closed-loop fused block == jnp oracle: arms bitwise, stats and
        pacer within the 1e-4 contract (odd B exercises pad_b)."""
        from repro.core import router
        cfg_j = RouterConfig(d=12, max_arms=4, backend="jnp",
                             hyper=HyperParams(alpha=0.05))
        cfg_f = RouterConfig(d=12, max_arms=4, backend="pallas_fused",
                             hyper=HyperParams(alpha=0.05))
        state, rng = _warmed(cfg_j)
        X, R, C = _rand_env_block(rng, B, 12, 4)
        sj, tj = router.step_batch(cfg_j, state, X, R, C)
        sf, tf = router.step_batch(cfg_f, state, X, R, C)
        np.testing.assert_array_equal(np.asarray(tj[0]), np.asarray(tf[0]))
        np.testing.assert_array_equal(np.asarray(tj[1]), np.asarray(tf[1]))
        for n in ("A", "A_inv", "b", "theta"):
            np.testing.assert_allclose(
                np.asarray(getattr(sj, n)), np.asarray(getattr(sf, n)),
                atol=1e-4, rtol=1e-4)
        np.testing.assert_array_equal(np.asarray(sj.last_upd),
                                      np.asarray(sf.last_upd))
        np.testing.assert_array_equal(np.asarray(sj.last_play),
                                      np.asarray(sf.last_play))
        assert abs(float(sj.pacer.lam - sf.pacer.lam)) <= 1e-4
        assert abs(float(sj.pacer.c_ema - sf.pacer.c_ema)) <= 1e-4
        assert int(sj.t) == int(sf.t)
        np.testing.assert_array_equal(np.asarray(sj.key),
                                      np.asarray(sf.key))

    def test_pacer_disabled_frozen(self):
        """enabled=False must freeze (lam, c_ema) through the fused path
        exactly as the per-step gate does."""
        import dataclasses
        from repro.core import router
        cfg = RouterConfig(d=8, max_arms=3, backend="pallas_fused")
        state, rng = _warmed(RouterConfig(d=8, max_arms=3))
        state = dataclasses.replace(
            state, pacer=dataclasses.replace(
                state.pacer, enabled=jnp.asarray(False)))
        X, R, C = _rand_env_block(rng, 32, 8, 3)
        s2, _ = router.step_batch(cfg, state, X, R, C)
        assert float(s2.pacer.lam) == float(state.pacer.lam)
        assert float(s2.pacer.c_ema) == float(state.pacer.c_ema)

    def test_forced_exploration_burnin(self):
        """The first force_left requests divert to the forced arm."""
        import dataclasses
        from repro.core import router
        cfg = RouterConfig(d=8, max_arms=3, backend="pallas_fused")
        state, rng = _warmed(RouterConfig(d=8, max_arms=3))
        state = dataclasses.replace(
            state, force_arm=jnp.asarray(2, jnp.int32),
            force_left=jnp.asarray(5, jnp.int32))
        X, R, C = _rand_env_block(rng, 16, 8, 3)
        s2, (arms, _, _, _) = router.step_batch(cfg, state, X, R, C)
        assert np.all(np.asarray(arms[:5]) == 2)
        assert int(s2.force_left) == 0

    def test_end_to_end_evaluate_run(self):
        """evaluate.run on the fused backend tracks the jnp oracle."""
        from repro.core import evaluate, simulator
        b = simulator.make_benchmark(
            seed=0, splits={"train": 64, "val": 16, "test": 96})
        res = {}
        for bk in ("jnp", "pallas_fused"):
            cfg = RouterConfig(backend=bk)
            res[bk] = evaluate.run(cfg, b.test, 6.6e-4, seeds=(0, 1),
                                   batch_size=8)
        agree = float((res["jnp"].arms == res["pallas_fused"].arms).mean())
        assert agree > 0.99, agree
        assert abs(res["jnp"].mean_reward
                   - res["pallas_fused"].mean_reward) < 1e-3

    def test_stacked_hyper_vmap_grid(self):
        """The fused kernel under the fabric's flattened (condition x
        seed) vmap axis with stacked (alpha, gamma) HyperParams."""
        from repro.core import simulator, sweep
        b = simulator.make_benchmark(
            seed=0, splits={"train": 64, "val": 16, "test": 96})
        hyp = HyperParams(alpha=np.asarray([0.01, 0.05, 0.1], np.float32),
                          gamma=np.asarray([0.99, 0.997, 1.0], np.float32))
        budgets = (1.0e-4, 6.6e-4, 1.9e-3)
        grids = {}
        for bk in ("jnp", "pallas_fused"):
            cfg = RouterConfig(backend=bk)
            grids[bk] = sweep.run_grid(cfg, b.test, budgets, seeds=(0, 1),
                                       batch_size=8, hyper=hyp)
        np.testing.assert_array_equal(grids["jnp"].arms,
                                      grids["pallas_fused"].arms)
        np.testing.assert_allclose(grids["jnp"].lams,
                                   grids["pallas_fused"].lams, atol=1e-4)

    def test_zero_retrace_on_new_hypers(self):
        """Retuning every hyper leaf re-enters the compiled fused step."""
        from repro.core import router, types
        cfg = RouterConfig(d=8, max_arms=3, backend="pallas_fused")
        state, rng = _warmed(RouterConfig(d=8, max_arms=3))
        X, R, C = _rand_env_block(rng, 16, 8, 3)
        cycle = jax.jit(
            lambda s, x, r, c: router.step_batch(cfg, s, x, r, c))
        jax.block_until_ready(cycle(state, X, R, C)[0].A)
        before = router.TRACE_COUNT[0]
        retuned = types.with_hyperparams(
            state, alpha=0.2, gamma=0.95, eta=0.1, alpha_ema=0.2,
            lambda_bar=3.0)
        jax.block_until_ready(cycle(retuned, X, R, C)[0].A)
        assert router.TRACE_COUNT[0] == before

    def test_donation_aliasing(self):
        """Donating the state to a jitted fused step releases the input
        stats buffers (the aliasing contract end-to-end)."""
        from repro.core import router
        cfg = RouterConfig(d=8, max_arms=3, backend="pallas_fused")
        state, rng = _warmed(RouterConfig(d=8, max_arms=3))
        X, R, C = _rand_env_block(rng, 16, 8, 3)
        cycle = jax.jit(
            lambda s, x, r, c: router.step_batch(cfg, s, x, r, c),
            donate_argnums=0)
        s2, _ = cycle(state, X, R, C)
        jax.block_until_ready(s2.A)
        assert state.A.is_deleted()
        assert s2.A.shape == (3, 8, 8)


# ---------------------------------------------------------------------------
# Interpret-mode decision (kernels.interpret_mode): one place, no fallback
# ---------------------------------------------------------------------------


class TestInterpretMode:
    def test_cpu_interprets(self):
        from repro.kernels import interpret_mode
        assert jax.default_backend() == "cpu"
        assert interpret_mode() is True

    def test_tpu_compiles(self, monkeypatch):
        from repro.kernels import interpret_mode
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        assert interpret_mode() is False

    @pytest.mark.parametrize("platform", ["gpu", "rocm"])
    def test_other_platform_raises(self, monkeypatch, platform):
        from repro.kernels import interpret_mode
        monkeypatch.setattr(jax, "default_backend", lambda: platform)
        with pytest.raises(RuntimeError, match=platform):
            interpret_mode()

    @pytest.mark.parametrize("wrapper", [
        linucb_score, linucb_step, decode_attention, flash_attention,
        ssd_scan])
    def test_no_wrapper_defaults_to_interpret(self, wrapper):
        """Every kernel wrapper leaves the choice to the platform."""
        import inspect
        param = inspect.signature(wrapper).parameters["interpret"]
        assert param.default is None
