"""A run with the timed path broken underneath must come out not
correct. Each test skips the look for a chip and drives the rest of a
run (``run.execute``) at a small size on the CPU, with one fault planted
in the program at run time:

* ``unchanged``: the learner's (or the grid step's) update returns its
  state unchanged;
* ``half``: the update folds only the first half of each feedback block;
* ``altered``: one chosen arm per block is changed where it is produced.

No cell exchanges anything between chips (the grid's elements are
independent and its program holds no collective), so that fault has no
test here.
"""
import contextlib

import jax
import numpy as np
import pytest

from perfbench.run import execute
from perfbench.tests import helpers


@contextlib.contextmanager
def planted(fault: str, tenants: bool):
    """Swap one of the program's compiled entry points for a broken one,
    and drop every cached program built meanwhile."""
    from repro.core import router, sweep

    def clear():
        for fn in (router.jit_select_batch, router.jit_update_batch,
                   router.jit_select_batch_tenants,
                   router.jit_update_batch_tenants, sweep._cached_grid_fn):
            fn.cache_clear()

    saved = {n: getattr(router, n) for n in (
        "jit_select_batch", "jit_update_batch", "jit_select_batch_tenants",
        "jit_update_batch_tenants", "select", "update")}
    upd_name = "jit_update_batch_tenants" if tenants else "jit_update_batch"
    sel_name = "jit_select_batch_tenants" if tenants else "jit_select_batch"

    def broken_update(statics):
        if fault == "unchanged":
            return jax.jit(lambda s, *rest: s)

        def half(s, arms, X, r, c, *tids):
            h = X.shape[0] // 2
            return router.update_batch(statics, s, arms[:h], X[:h], r[:h],
                                       c[:h], *(t[:h] for t in tids))
        return jax.jit(half)

    def broken_select(statics):
        def alter(s, X, *tids):
            dec, s2 = router.select_batch(statics, s, X, *tids)
            arms = dec.arms.at[0].set((dec.arms[0] + 1) % 3)
            return dec._replace(arms=arms), s2
        return jax.jit(alter)

    def step_unchanged(cfg, state, arm, x, reward, cost):
        return state

    def select_altered(cfg, state, x):
        dec, s2 = saved["select"](cfg, state, x)
        return dec._replace(arm=(dec.arm + 1) % 3), s2

    clear()
    try:
        if fault in ("unchanged", "half"):
            setattr(router, upd_name, broken_update)
            router.update = step_unchanged
        else:
            setattr(router, sel_name, broken_select)
            router.select = select_altered
        yield
    finally:
        for n, f in saved.items():
            setattr(router, n, f)
        clear()


@pytest.mark.parametrize("workload,fault", [
    ("paper3_steady", "unchanged"), ("paper3_steady", "half"),
    ("paper3_steady", "altered"), ("fleet64_bursty", "unchanged"),
    ("fleet64_bursty", "half"), ("fleet64_bursty", "altered"),
    ("paper3_grid_4chip", "unchanged"), ("paper3_grid_4chip", "altered"),
])
def test_planted_fault_is_not_correct(workload, fault):
    cell = helpers.cell(workload, seconds=0.6)
    with planted(fault, tenants=workload.startswith("fleet")):
        line, checks = execute(helpers.bench(), cell, jax.devices())
    assert line["correct"] is False
    failed = [n for n, c in checks.items() if not c["value"] <= c["limit"]]
    assert failed, checks


def test_unbroken_small_run_is_correct():
    cell = helpers.cell("paper3_trickle", seconds=0.6)
    line, checks = execute(helpers.bench(), cell, jax.devices())
    assert line["correct"] is True, checks
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device", "checks"}
    assert list(line)[-1] == "checks"
    assert line["attempted"] == round(300 * 0.6) and line["failed"] == 0
    assert set(line["metrics"]) == {"route_p99_ms", "decisions_per_s",
                                    "feedback_lag_p99_ms", "setup_s"}
    assert all(np.isfinite(m["value"]) and m["value"] > 0
               for m in line["metrics"].values())
