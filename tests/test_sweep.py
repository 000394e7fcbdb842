"""Grid-sweep fabric: bit-for-bit equivalence with the looped
per-condition baseline, the whole-grid-compiles-once contract, budget
stacking in make_states, scenario grids, payload-parameter grids
(ScenarioParams on the condition axis, DESIGN.md §10), grid-argument
guards, device sharding, and the RunResult.phase segment-structure fix
that rides along."""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.core import evaluate, simulator, sweep
from repro.core.scenario import (
    Param, PriceChange, QualityShift, ScenarioParams, ScenarioSpec,
)
from repro.core.types import HyperParams, RouterConfig
from repro.launch import mesh as mesh_lib
from tests.trace_guard import assert_traces

CFG = RouterConfig()
SEEDS = (0, 1, 2)
BUDGETS = (1.0e-4, 6.6e-4, 1.9e-3)


@pytest.fixture(scope="module")
def bench():
    return simulator.make_benchmark(
        seed=0, splits={"train": 256, "val": 32, "test": 200})


@pytest.fixture(scope="module")
def env(bench):
    return bench.test


@pytest.fixture(scope="module")
def priors(bench):
    return evaluate.fit_warmup_priors(CFG, bench.train)


def _assert_bitwise(grid_res, run_res):
    np.testing.assert_array_equal(grid_res.arms, run_res.arms)
    np.testing.assert_array_equal(grid_res.rewards, run_res.rewards)
    np.testing.assert_array_equal(grid_res.costs, run_res.costs)
    np.testing.assert_array_equal(grid_res.lams, run_res.lams)


class TestGridEquivalence:
    def test_grid_matches_looped_run_bitwise(self, env, priors):
        grid = sweep.run_grid(CFG, env, BUDGETS, seeds=SEEDS,
                              priors=priors, n_eff=1164.0)
        for i, b in enumerate(BUDGETS):
            res = evaluate.run(CFG, env, b, seeds=SEEDS,
                               priors=priors, n_eff=1164.0)
            _assert_bitwise(grid.condition(i), res)

    def test_grid_without_priors(self, env):
        grid = sweep.run_grid(CFG, env, BUDGETS[:2], seeds=SEEDS)
        for i, b in enumerate(BUDGETS[:2]):
            _assert_bitwise(grid.condition(i),
                            evaluate.run(CFG, env, b, seeds=SEEDS))

    def test_batched_data_plane_grid(self, env):
        grid = sweep.run_grid(CFG, env, BUDGETS[:2], seeds=SEEDS,
                              batch_size=16)
        for i, b in enumerate(BUDGETS[:2]):
            res = evaluate.run(CFG, env, b, seeds=SEEDS, batch_size=16)
            _assert_bitwise(grid.condition(i), res)


@pytest.mark.usefixtures("no_implicit_transfers", "no_leaked_tracers")
class TestOneCompiledProgram:
    def test_full_pareto_grid_single_trace(self, env, priors):
        """The paper's 7-budget x 20-seed Fig. 1 grid is ONE trace."""
        # bench_pareto.BUDGET_SWEEP (kept inline: tests don't import the
        # benchmarks namespace package)
        BUDGET_SWEEP = (1.0e-4, 2.3e-4, 3.0e-4, 6.6e-4, 1.0e-3, 1.9e-3,
                        4.0e-3)
        seeds = tuple(range(20))
        with assert_traces(sweep, 1, what="7x20 grid must compile as "
                                          "one program"):
            grid = sweep.run_grid(CFG, env, BUDGET_SWEEP, seeds=seeds,
                                  priors=priors, n_eff=1164.0)
        assert grid.arms.shape == (7, 20, env.n)
        # New budget values, same shapes: the program is reused as-is.
        with assert_traces(sweep, 0, what="fabric retraced"):
            sweep.run_grid(CFG, env, [2 * b for b in BUDGET_SWEEP],
                           seeds=seeds, priors=priors, n_eff=1164.0)

    def test_fresh_grid_reenters_the_state_builder(self, env, priors):
        """Fresh seeds and budgets of the same shape re-enter the one
        compiled state builder; another C*S traces it once."""
        seeds = tuple(range(100, 113))       # C*S = 39, met nowhere else
        sweep.run_grid(CFG, env, BUDGETS, seeds=seeds, priors=priors,
                       n_eff=1164.0)
        with assert_traces(evaluate, 0, what="state builder retraced"):
            sweep.run_grid(CFG, env, [3 * b for b in BUDGETS],
                           seeds=tuple(range(200, 213)), priors=priors,
                           n_eff=1164.0)
        with assert_traces(evaluate, 1, what="a new C*S traces once"):
            sweep.run_grid(CFG, env, BUDGETS[:2], seeds=seeds,
                           priors=priors, n_eff=1164.0)

    def test_shared_and_per_state_hyper_share_one_program(self, env,
                                                           priors):
        """A hyper value or n_eff given once and the same value given per
        state reach the builder in one layout: one program, the same
        bits."""
        hp = HyperParams(alpha=0.05, gamma=0.999)
        n = len(SEEDS)
        shared = evaluate.make_states(CFG, env, 6.6e-4, SEEDS,
                                      priors=priors, n_eff=1164.0, hyper=hp)
        with assert_traces(evaluate, 0, what="per-state layout retraced"):
            stacked = evaluate.make_states(
                CFG, env, 6.6e-4, SEEDS, priors=priors,
                n_eff=np.full(n, 1164.0, np.float32),
                hyper=HyperParams(alpha=np.full(n, 0.05, np.float32),
                                  gamma=np.full(n, 0.999, np.float32)))
        for a, b in zip(jax.tree.leaves(shared), jax.tree.leaves(stacked)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_grid_result_accessors(self, env):
        grid = sweep.run_grid(CFG, env, BUDGETS, seeds=SEEDS)
        assert len(grid) == 3
        pairs = list(grid.conditions())
        assert [b for b, _ in pairs] == list(BUDGETS)
        assert pairs[0][1].arms.shape == (len(SEEDS), env.n)


class TestBudgetStacking:
    def test_make_states_budget_vector(self, env):
        states = evaluate.make_states(
            CFG, env, (1e-4, 1e-3, 1e-2), (0, 1, 2))
        np.testing.assert_allclose(
            np.asarray(states.pacer.budget), [1e-4, 1e-3, 1e-2])
        np.testing.assert_allclose(
            np.asarray(states.pacer.c_ema), [1e-4, 1e-3, 1e-2])

    def test_make_states_scalar_budget_unchanged(self, env):
        a = evaluate.make_states(CFG, env, 6.6e-4, SEEDS)
        b = evaluate.make_states(CFG, env, (6.6e-4,) * len(SEEDS), SEEDS)
        for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))


class TestScenarioGrid:
    SPEC = ScenarioSpec(
        horizon=90,
        events=(PriceChange(30, 2, 0.1, recalibrate=True),
                QualityShift(60, 1, 0.7)),
        stream_seed_base=42)

    def test_matches_run_scenario_per_budget(self, env):
        grid = sweep.run_scenario_grid(CFG, self.SPEC, env, BUDGETS,
                                       seeds=SEEDS)
        assert grid.bounds == self.SPEC.bounds
        for i, b in enumerate(BUDGETS):
            res = evaluate.run_scenario(CFG, self.SPEC, env, b, seeds=SEEDS)
            _assert_bitwise(grid.condition(i), res)
            assert grid.condition(i).bounds == res.bounds

    def test_single_trace_and_budget_reuse(self, env):
        sweep.run_scenario_grid(CFG, self.SPEC, env, BUDGETS, seeds=SEEDS)
        with assert_traces(sweep, 0, what="scenario fabric retraced"):
            sweep.run_scenario_grid(CFG, self.SPEC, env,
                                    (2e-4, 5e-4, 2e-3), seeds=SEEDS)

    def test_batched_plane(self, env):
        grid = sweep.run_scenario_grid(CFG, self.SPEC, env, BUDGETS[:2],
                                       seeds=SEEDS, batch_size=16)
        res = evaluate.run_scenario(CFG, self.SPEC, env, BUDGETS[1],
                                    seeds=SEEDS, batch_size=16)
        _assert_bitwise(grid.condition(1), res)


class TestScenarioParamGrid:
    """Whole spec *families* on the condition axis: a (payload x budget
    x seed) grid compiles ONCE and is bit-identical per condition to
    looping ``run_scenario`` over the equivalent concrete-payload specs
    (the ISSUE-5 acceptance grids)."""

    MULTS = (1 / 56, 0.3, 2.0)
    TARGETS = (0.6, 0.75, 0.9)
    BUDGETS2 = (3.0e-4, 6.6e-4)

    @staticmethod
    def _price_spec(mult):
        return ScenarioSpec(horizon=90, events=(
            PriceChange(30, 2, mult), PriceChange(60, 2, 1.0)),
            stream_seed_base=50, replay=((2, 0),))

    @staticmethod
    def _quality_spec(target):
        return ScenarioSpec(horizon=90, events=(
            QualityShift(30, 1, target), QualityShift(60, 1, None)),
            stream_seed_base=51, replay=((2, 0),))

    def _grid_axes(self, payloads):
        b_flat = tuple(np.tile(self.BUDGETS2, len(payloads)))
        p_flat = np.repeat(np.asarray(payloads, np.float32),
                           len(self.BUDGETS2))
        return b_flat, p_flat

    def test_price_multiplier_grid_bitwise_single_trace(self, env):
        b_flat, m_flat = self._grid_axes(self.MULTS)
        with assert_traces(sweep, 1, what="the whole (multiplier x "
                           "budget x seed) family must compile as one "
                           "program"):
            grid = sweep.run_scenario_grid(
                CFG, self._price_spec(Param("mult")), env, b_flat,
                seeds=SEEDS, scenario_params=ScenarioParams(mult=m_flat))
        for i, (m, b) in enumerate(zip(m_flat, b_flat)):
            res = evaluate.run_scenario(
                CFG, self._price_spec(float(m)), env, b, seeds=SEEDS)
            _assert_bitwise(grid.condition(i), res)
        np.testing.assert_allclose(grid.params["mult"], m_flat)

    def test_quality_target_grid_bitwise_single_trace(self, env):
        b_flat, t_flat = self._grid_axes(self.TARGETS)
        with assert_traces(sweep, 1):
            grid = sweep.run_scenario_grid(
                CFG, self._quality_spec(Param("target")), env, b_flat,
                seeds=SEEDS, scenario_params=ScenarioParams(target=t_flat))
        for i, (t, b) in enumerate(zip(t_flat, b_flat)):
            res = evaluate.run_scenario(
                CFG, self._quality_spec(float(t)), env, b, seeds=SEEDS)
            _assert_bitwise(grid.condition(i), res)

    def test_new_payload_values_reenter_same_program(self, env):
        b_flat, m_flat = self._grid_axes(self.MULTS)
        spec = self._price_spec(Param("mult"))
        sweep.run_scenario_grid(CFG, spec, env, b_flat, seeds=SEEDS,
                                scenario_params=ScenarioParams(mult=m_flat))
        with assert_traces(sweep, 0, what="payload values are data; "
                                          "re-running must not retrace"):
            sweep.run_scenario_grid(
                CFG, spec, env, b_flat, seeds=SEEDS,
                scenario_params=ScenarioParams(mult=2.0 * m_flat))

    def test_stacked_hyper_and_payload_axes(self, env):
        """A (C,) ``hyper`` alpha and a (C,) ``scenario_params`` payload
        put an (alpha, payload) pair per condition on one fused grid,
        bit-identical to looping run_scenario with the same knobs."""
        cells = ((0.01, 1 / 56), (0.1, 0.3), (0.2, 2.0))
        spec = self._price_spec(Param("mult"))
        grid = sweep.run_scenario_grid(
            CFG, spec, env, (6.6e-4,) * len(cells), seeds=SEEDS,
            hyper=HyperParams(
                alpha=np.asarray([a for a, _ in cells], np.float32)),
            scenario_params=ScenarioParams(
                mult=np.asarray([m for _, m in cells], np.float32)))
        for i, (a, m) in enumerate(cells):
            res = evaluate.run_scenario(
                CFG, self._price_spec(m), env, 6.6e-4, seeds=SEEDS,
                hyper=HyperParams(alpha=a))
            _assert_bitwise(grid.condition(i), res)


class TestGridGuards:
    """Satellite: degenerate grid arguments fail with explicit
    ValueErrors, not cryptic reshape/vmap/mesh errors."""

    SPEC = ScenarioSpec(horizon=60, events=(QualityShift(30, 1, 0.7),),
                        stream_seed_base=52)

    def test_empty_budgets(self, env):
        with pytest.raises(ValueError, match="budgets is empty"):
            sweep.run_grid(CFG, env, (), seeds=SEEDS)
        with pytest.raises(ValueError, match="budgets is empty"):
            sweep.run_scenario_grid(CFG, self.SPEC, env, (), seeds=SEEDS)

    def test_empty_seeds(self, env):
        with pytest.raises(ValueError, match="seeds is empty"):
            sweep.run_grid(CFG, env, BUDGETS, seeds=())
        with pytest.raises(ValueError, match="seeds is empty"):
            sweep.run_scenario_grid(CFG, self.SPEC, env, BUDGETS, seeds=())

    @pytest.mark.parametrize("stack", ["hyper", "n_eff"])
    @pytest.mark.parametrize("entry", ["run_grid", "run_scenario_grid"])
    def test_stack_of_neither_length_rejected(self, entry, stack, env):
        """A hyper leaf or n_eff of C + 1 values is neither a (C,)
        per-condition nor a (C*S,) per-element stack: refused, not
        broadcast or truncated."""
        bad = np.full(len(BUDGETS) + 1, 0.05, np.float32)
        kw = ({"hyper": HyperParams(alpha=bad)} if stack == "hyper"
              else {"n_eff": bad})
        with pytest.raises(ValueError, match=stack):
            if entry == "run_grid":
                sweep.run_grid(CFG, env, BUDGETS, seeds=SEEDS, **kw)
            else:
                sweep.run_scenario_grid(CFG, self.SPEC, env, BUDGETS,
                                        seeds=SEEDS, **kw)

class TestPriceChangeConcatStrict:
    """Regression (satellite): a PriceChange protocol composes with
    ``concat_environments``' strict rate-card check — the hand-rolled
    three-phase stream must opt out explicitly (prices='first'), while
    the engine's per-segment gather needs no concat at all, and the two
    lowerings agree bit-for-bit."""

    def test_strict_concat_rejects_drifted_phase(self, env):
        drifted = simulator.with_price_multiplier(env, 2, 1 / 56)
        with pytest.raises(ValueError, match="rate card"):
            simulator.concat_environments((env, drifted, env))

    def test_spec_matches_optout_hand_roll(self, env):
        phase = 60
        envs = []
        for s in SEEDS:
            rng = np.random.default_rng(3000 + s)
            envs.append(simulator.three_phase_stream(
                env,
                lambda e: simulator.with_price_multiplier(e, 2, 1 / 56),
                rng, phase_len=phase))   # uses prices='first' internally
        old = evaluate.run(CFG, envs, 6.6e-4, seeds=SEEDS, shuffle=False)
        spec = ScenarioSpec(horizon=3 * phase, events=(
            PriceChange(phase, 2, 1 / 56),
            PriceChange(2 * phase, 2, 1.0)),
            stream_seed_base=3000, replay=((2, 0),))
        new = evaluate.run_scenario(CFG, spec, env, 6.6e-4, seeds=SEEDS)
        _assert_bitwise(old, new)
        # and the same protocol as a *family*: the Param lowering agrees
        pspec = ScenarioSpec(horizon=3 * phase, events=(
            PriceChange(phase, 2, Param("mult")),
            PriceChange(2 * phase, 2, 1.0)),
            stream_seed_base=3000, replay=((2, 0),))
        fam = evaluate.run_scenario(
            CFG, pspec, env, 6.6e-4, seeds=SEEDS,
            scenario_params=ScenarioParams(mult=1 / 56))
        _assert_bitwise(old, fam)


class TestDeviceSharding:
    def test_grid_mesh_divisor_selection(self):
        devs = jax.devices()
        mesh = mesh_lib.make_grid_mesh(6, devs)
        assert 6 % mesh.devices.size == 0
        mesh = mesh_lib.make_grid_mesh(1, devs)
        assert mesh.devices.size == 1

    def test_sharded_run_matches_single_device(self):
        """The fabric must produce identical bits when the grid axis is
        split across many (placeholder host) devices; exercised in a
        subprocess because device count is fixed at jax init."""
        code = (
            "import numpy as np\n"
            "import jax\n"
            "assert len(jax.devices()) == 6, jax.devices()\n"
            "from repro.core import evaluate, simulator, sweep\n"
            "b = simulator.make_benchmark(seed=0, splits={'train': 64, "
            "'val': 16, 'test': 80})\n"
            "from repro.core.types import RouterConfig\n"
            "cfg = RouterConfig()\n"
            "grid = sweep.run_grid(cfg, b.test, (1e-4, 6.6e-4, 1.9e-3), "
            "seeds=(0, 1))\n"
            "for i, bud in enumerate((1e-4, 6.6e-4, 1.9e-3)):\n"
            "    res = evaluate.run(cfg, b.test, bud, seeds=(0, 1))\n"
            "    np.testing.assert_array_equal(grid.condition(i).arms, "
            "res.arms)\n"
            "    np.testing.assert_array_equal(grid.condition(i).lams, "
            "res.lams)\n"
            "print('SHARDED_OK')\n"
        )
        env = dict(os.environ,
                   XLA_FLAGS="--xla_force_host_platform_device_count=6",
                   PYTHONPATH="src")
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=300,
                             cwd=os.path.dirname(os.path.dirname(
                                 os.path.abspath(__file__))))
        assert out.returncode == 0, out.stderr[-2000:]
        assert "SHARDED_OK" in out.stdout


class TestPhaseBounds:
    """RunResult.phase used to silently drop ``bounds`` — slicing a
    scenario result lost its segment structure."""

    def _mk(self, bounds):
        t = bounds[-1]
        return evaluate.RunResult(
            arms=np.zeros((2, t), np.int32), rewards=np.zeros((2, t)),
            costs=np.zeros((2, t)), lams=np.zeros((2, t)), bounds=bounds)

    def test_phase_rebases_overlapping_bounds(self):
        r = self._mk((0, 30, 60, 90))
        p = r.phase(10, 70)
        assert p.bounds == (0, 20, 50, 60)
        assert p.n_segments == 3

    def test_phase_on_boundary_keeps_interior_only(self):
        r = self._mk((0, 30, 60, 90))
        p = r.phase(30, 90)
        assert p.bounds == (0, 30, 60)
        assert p.n_segments == 2

    def test_phase_without_bounds_stays_none(self):
        r = evaluate.RunResult(
            arms=np.zeros((2, 50), np.int32), rewards=np.zeros((2, 50)),
            costs=np.zeros((2, 50)), lams=np.zeros((2, 50)))
        assert r.phase(10, 40).bounds is None

    def test_segment_of_phase(self, env):
        spec = TestScenarioGrid.SPEC
        res = evaluate.run_scenario(CFG, spec, env, 6.6e-4, seeds=(0,))
        sliced = res.phase(0, 75)
        assert sliced.bounds == (0, 30, 60, 75)
        np.testing.assert_array_equal(
            sliced.segment(1).arms, res.segment(1).arms)


class TestChunkedFabric:
    """chunk_size: scan-over-condition-chunks inside the one compiled
    grid program (DESIGN.md §11). Bit-identical to the unchunked fabric
    for plain and scenario grids, single trace, divisor guard."""

    def test_chunked_grid_bitwise(self, env):
        full = sweep.run_grid(CFG, env, BUDGETS, seeds=SEEDS)
        for chunk in (1, 3, 9):
            got = sweep.run_grid(CFG, env, BUDGETS, seeds=SEEDS,
                                 chunk_size=chunk)
            _assert_bitwise(got, full)

    def test_chunked_batched_plane_bitwise(self, env):
        full = sweep.run_grid(CFG, env, BUDGETS, seeds=SEEDS,
                              batch_size=16)
        got = sweep.run_grid(CFG, env, BUDGETS, seeds=SEEDS,
                             batch_size=16, chunk_size=3)
        _assert_bitwise(got, full)

    def test_chunked_fused_backend_bitwise(self, env):
        cfg = RouterConfig(backend="pallas_fused")
        full = sweep.run_grid(cfg, env, BUDGETS, seeds=SEEDS,
                              batch_size=16)
        got = sweep.run_grid(cfg, env, BUDGETS, seeds=SEEDS,
                             batch_size=16, chunk_size=3)
        _assert_bitwise(got, full)

    def test_chunked_scenario_grid_bitwise(self, env):
        spec = TestScenarioGrid.SPEC
        full = sweep.run_scenario_grid(CFG, spec, env, BUDGETS,
                                       seeds=SEEDS)
        got = sweep.run_scenario_grid(CFG, spec, env, BUDGETS,
                                      seeds=SEEDS, chunk_size=3)
        _assert_bitwise(got, full)
        assert got.bounds == spec.bounds

    def test_chunked_single_trace(self, env):
        sweep.run_grid(CFG, env, BUDGETS, seeds=SEEDS, chunk_size=3)
        with assert_traces(sweep, 0, what="chunked fabric retraced"):
            sweep.run_grid(CFG, env, (2e-4, 5e-4, 2e-3), seeds=SEEDS,
                           chunk_size=3)

    def test_non_divisor_chunk_rejected(self, env):
        with pytest.raises(ValueError, match="divisor"):
            sweep.run_grid(CFG, env, BUDGETS, seeds=SEEDS, chunk_size=4)
        with pytest.raises(ValueError, match="divisor"):
            sweep.run_grid(CFG, env, BUDGETS, seeds=SEEDS, chunk_size=0)

    def test_fit_chunk(self):
        assert sweep.fit_chunk(720, 100) == 90
        assert sweep.fit_chunk(9, 4) == 3
        assert sweep.fit_chunk(9, 100) == 9
        assert sweep.fit_chunk(7, 3) == 1
        assert sweep.fit_chunk(12, 12) == 12


class TestDefaultChunk:
    """The fabric's default chunk (``sweep.default_chunk``): on one TPU
    v5e the largest divisor of the grid whose tile-padded state carry
    fits the on-chip budget; elsewhere the whole grid, one chunk."""

    V5E = "TPU v5 lite"

    @pytest.fixture(scope="class")
    def leaves(self, env):
        """Per-element state leaves at the paper's statics (d = 26, 8
        slots, 20 forced pulls), as ``paper3`` and ``paper3_shifts``."""
        cfg = RouterConfig(d=26, max_arms=8, forced_pulls=20)
        states = evaluate.make_states(cfg, env, 6.6e-4, (0,), active_arms=3)
        return [jax.ShapeDtypeStruct(l.shape[1:], l.dtype)
                for l in jax.tree.leaves(states)]

    def test_paper3_grid_stays_one_chunk(self, leaves):
        chunk = sweep.default_chunk(160, leaves, self.V5E)
        assert sweep._n_chunks(160, chunk) == 1

    def test_paper3_mc1024_splits_into_on_chip_chunks(self, leaves):
        # 128 elements carry 35.0 MB tiled, 256 carry 70.0 MB: the
        # compile test confirms 128 keeps A and A^-1 in S(1).
        assert sweep.default_chunk(1024, leaves, self.V5E) == 128

    def test_prime_grid_stays_unchunked(self, leaves):
        assert sweep.default_chunk(1021, leaves, self.V5E) is None

    @pytest.mark.parametrize("kind,n_devices", [
        ("cpu", 1), ("TPU v0 unknown", 1), ("TPU v5 lite", 2)])
    def test_no_default_off_one_known_chip(self, leaves, kind, n_devices):
        assert sweep.default_chunk(1024, leaves, kind, n_devices) is None

    @pytest.mark.parametrize("kind", ["cpu", "TPU v5 lite"])
    def test_explicit_chunk_size_wins(self, leaves, kind):
        assert sweep.default_chunk(1024, leaves, kind, 1, 512) == 512
        assert sweep.default_chunk(1024, leaves, kind, 2, 4) == 4

    def test_placed_cpu_grid_keeps_one_chunk(self, env):
        states = jax.device_put(evaluate.make_states(
            CFG, env, 6.6e-4, tuple(range(4))))
        assert sweep.grid_chunk(states) is None
        assert sweep.grid_chunk(states, 2) == 2

    def test_tiled_bytes(self):
        assert sweep._tiled_nbytes((8, 26, 26), np.float32) == 8 * 32 * 128 * 4
        assert sweep._tiled_nbytes((8,), np.int32) == 128 * 4
        assert sweep._tiled_nbytes((), np.float32) == 4
