"""The plain reference against the program on the CPU, at a tiny size:
the gateway at publish cadence 1 (one learner tick per routed block),
the tenant fold, and two conditions of ``sweep.run_grid``."""
import jax
import numpy as np
import pytest

from perfbench import arrivals, data
from perfbench.entries import fabric, gateway
from perfbench.tests import helpers

B, BLOCKS = 8, 24


def _cadence1(workload):
    """Route BLOCKS blocks of B rows through a gateway, a learner tick
    after each; return what the reference needs and the snapshots."""
    cell = helpers.cell(workload)
    train, _, test = data.for_config(cell.config)
    sched = arrivals.schedule(cell.traffic, cell.config, cell.seed, 1.0,
                              test.n)
    cfg, state0 = gateway._program_state(cell.config, sched, cell.seed,
                                         train, test)
    gw = gateway._gateway(cfg, state0, cell.traffic["admission"])
    tenanted = sched.ceilings is not None
    blocks, ticks, snaps, t_grab = [], [], [], []
    for j in range(BLOCKS):
        ids = np.arange(j * B, (j + 1) * B)
        p = sched.prompt[ids]
        tids = sched.tenant[ids] if tenanted else None
        res = gw.route_block(ids.tolist(), test.contexts[p], tenant_ids=tids)
        blocks.append((ids, np.asarray(res.arms), res.version))
        t_grab.append((j + 1) * B)
        gw.enqueue_feedback(ids.tolist(), res.arms,
                            test.rewards[p, res.arms], test.costs[p, res.arms])
        snap = gw.learn_tick()
        ticks.append((snap.version, [j]))
        snaps.append(jax.device_get(gateway._learn_leaves(snap.state)))
    return cell, sched, train, test, blocks, ticks, t_grab, snaps


@pytest.mark.parametrize("workload", ["paper3_steady", "fleet64_bursty"])
def test_gateway_cadence1_matches_reference(workload):
    cell, sched, train, test, blocks, ticks, t_grab, snaps = _cadence1(
        workload)
    gaps, ref = gateway.replay(cell, sched, blocks, ticks, t_grab, train,
                               test, want=range(1, BLOCKS + 1))
    routed = np.arange(BLOCKS * B)
    # Chosen arms: within float32 rounding and the 1e-7 tiebreak noise of
    # the reference's best candidate.
    assert np.nanmax(gaps[routed]) < 1e-5
    for v, got in enumerate(snaps, start=1):
        st = ref[v]
        for name, want in (("A_inv", st.Ainv[:3]), ("theta", st.theta[:3]),
                           ("b", st.b[:3])):
            np.testing.assert_allclose(got[name][:3], want, rtol=2e-4,
                                       atol=2e-5)
        np.testing.assert_allclose(np.ravel(got["lam"]), st.pacers.lam,
                                   atol=1e-5)
        np.testing.assert_allclose(np.ravel(got["c_ema"]), st.pacers.c_ema,
                                   rtol=1e-5)
        if workload.startswith("fleet"):
            np.testing.assert_array_equal(got["pulls"], st.pacers.pulls)
            np.testing.assert_allclose(got["spend"], st.pacers.spend,
                                       rtol=1e-5, atol=1e-9)


def test_tenant_fold_charges_each_row_to_its_tenant():
    cell, sched, train, test, blocks, ticks, t_grab, snaps = _cadence1(
        "fleet64_bursty")
    _, ref = gateway.replay(cell, sched, blocks, ticks, t_grab, train, test,
                            want=[BLOCKS])
    rows = np.arange(BLOCKS * B)
    counts = np.bincount(sched.tenant[rows], minlength=64)
    np.testing.assert_array_equal(ref[BLOCKS].pacers.pulls, counts)
    np.testing.assert_array_equal(snaps[-1]["pulls"], counts)
    arms = np.concatenate([b[1] for b in blocks])
    spend = np.zeros(64)
    np.add.at(spend, sched.tenant[rows],
              test.costs[sched.prompt[rows], arms])
    np.testing.assert_allclose(snaps[-1]["spend"], spend, rtol=1e-5)


def test_two_grid_conditions_match_reference():
    from repro.core import evaluate, sweep
    from repro.core.types import HyperParams, RouterConfig

    cell = helpers.cell("paper3_grid_4chip")
    config = cell.config
    train, _, test = data.for_config(config)
    cfg = RouterConfig(hyper=HyperParams(alpha=config["alpha"],
                                         gamma=config["gamma"]))
    priors = evaluate.fit_warmup_priors(cfg, train)
    perm = np.random.default_rng(3).permutation(test.n)[:300]
    env = test.subset(perm)
    budgets = [3.0e-4, 1.0]
    grid, finals = sweep.run_grid(cfg, [env], budgets, seeds=[7],
                                  priors=priors, n_eff=config["n_eff"],
                                  shuffle=False, return_states=True)
    for ci, budget in enumerate(budgets):
        arms = grid.arms[ci, 0].astype(np.int64)
        gaps, lams, st = fabric.replay_element(config, train, test, budget,
                                               perm, arms)
        assert gaps.max() < 1e-5
        np.testing.assert_allclose(grid.lams[ci, 0], lams, atol=1e-5)
        np.testing.assert_allclose(np.asarray(finals.theta[ci])[:3],
                                   st.theta[:3], rtol=1e-3, atol=1e-5)


def test_lower_precision_arithmetic():
    from perfbench.reference.lowp import Bf16x3, bf16

    assert bf16(np.float32(1.0009765625)) == 1.0      # 1 + 2^-10 -> 1
    assert bf16(np.float32(1.00390625)) == 1.0         # tie -> even
    assert bf16(np.float32(1.01171875)) == 1.015625    # tie -> even
    assert bf16(np.array([[3.14159]]))[0, 0] == 3.140625
    rng = np.random.default_rng(0)
    a, b = rng.standard_normal((64, 64)), rng.standard_normal((64, 8))
    exact = a @ b
    err3 = np.abs(Bf16x3().mm(a, b) - exact).max() / np.abs(exact).max()
    err32 = np.abs((a.astype(np.float32) @ b.astype(np.float32))
                   - exact).max() / np.abs(exact).max()
    # Three bfloat16 passes lose the product of the two low parts: well
    # above float32 rounding, well below one bfloat16 pass.
    assert err32 < err3 < 1e-4
