"""The timeline cell (``paper3_mc1024``) at a small size on the CPU: 8
elements x 160 steps a call, d = 26, every event's step drawn on
[160/12, 3 x 160/4) = [13, 120). The program's readings sit inside the
cell's limits, the control's do not, and a run with one fault planted in
the program at run time comes out not correct:

* ``late``: every event takes effect one step after its drawn step;
* ``unforced``: the newcomer is added without its forced pulls;
* ``uncut``: the price cut is left out of the realised costs;
* ``unreset``: the newcomer's slot keeps its statistics and clocks.
"""
import copy
import dataclasses
import time

import jax
import numpy as np
import pytest

from perfbench import registry
from perfbench.cell import Cell
from perfbench.entries import timeline
from perfbench.reference.lowp import Bf16x3
from perfbench.run import execute

WORKLOAD = {"name": "paper3_mc1024", "config": "paper3_shifts",
            "traffic": "mc1024", "chips": 1}
SEED = 2 ** 31 + 4242


def small_cell(seed: int = SEED, seconds: float = 0.4) -> Cell:
    traffic = copy.deepcopy(registry.traffic(WORKLOAD["traffic"]))
    traffic.update(seeds_per_call=8, horizon=160)
    return Cell(workload=dict(WORKLOAD),
                config=registry.config_file(WORKLOAD["config"]),
                traffic=traffic, seed=seed, seconds=seconds, trace=False,
                t_process=time.perf_counter(), trace_dir="")


@pytest.fixture
def fresh_programs():
    """Drop the compiled timeline programs before and after a test that
    swaps a function they were traced with."""
    from repro.core import sweep

    sweep._SCEN_CACHE.clear()
    yield
    sweep._SCEN_CACHE.clear()


def plant(fault: str, monkeypatch) -> None:
    from repro.core import registry as reg
    from repro.core import scenario, simulator

    if fault == "late":
        retime = scenario.retime

        def late(spec, tl):
            return retime(spec, scenario.Timeline(
                tuple(t + 1 for t in tl.event_ts), tl.horizon))
        monkeypatch.setattr(scenario, "retime", late)
    elif fault == "uncut":
        monkeypatch.setattr(simulator, "with_price_multiplier",
                            lambda env, arm, multiplier: env)
    else:
        add_arm = reg.add_arm

        def unforced(cfg, state, slot, *a, **kw):
            return add_arm(cfg, state, slot, *a,
                           **dict(kw, forced_exploration=False))

        def unreset(cfg, state, slot, *a, **kw):
            new = add_arm(cfg, state, slot, *a, **kw)
            return dataclasses.replace(new, **{
                n: getattr(state, n) for n in (
                    "A", "A_inv", "b", "theta", "last_upd", "last_play")})
        monkeypatch.setattr(reg, "add_arm",
                            unforced if fault == "unforced" else unreset)


def test_small_run_is_correct():
    line, checks = execute(registry.benchmark(), small_cell(),
                           jax.devices())
    assert line["correct"] is True, checks
    assert set(line["metrics"]) == {"grid_steps_per_s", "setup_s"}
    assert line["attempted"] % 8 == 0 and line["failed"] == 0
    assert checks["outcome_faults"]["value"] == 0.0


def test_control_fails_where_the_program_passes():
    cell = small_cell(seconds=0.0)
    limits = cell.limits()
    results, train, test, budgets, _ = timeline.window(cell)
    prog = timeline.readings(cell, results, train, test, budgets)
    ctrl = timeline.readings(cell, results, train, test, budgets,
                             control=Bf16x3())
    assert all(prog[k] <= limits[k] for k in limits), prog
    assert any(ctrl[k] > limits[k] for k in limits), ctrl


@pytest.mark.parametrize("fault,caught_by", [
    ("late", "arm_gap"), ("unforced", "arm_gap"),
    ("uncut", "outcome_faults"), ("unreset", "stats_rel_gap"),
])
def test_planted_fault_is_not_correct(fault, caught_by, monkeypatch,
                                      fresh_programs):
    plant(fault, monkeypatch)
    line, checks = execute(registry.benchmark(), small_cell(seconds=0.0),
                           jax.devices())
    assert line["correct"] is False
    c = checks[caught_by]
    assert not c["value"] <= c["limit"], checks


def test_reference_stream_is_the_programs_draw():
    """The reference's copy of the iid draw rule and of the stream
    events gives the rows ``build_timeline_streams`` sends."""
    from repro.core import scenario
    from repro.core.types import RouterConfig

    from perfbench import flash
    from perfbench.reference import events

    cell = small_cell()
    T = cell.traffic["horizon"]
    _, test = flash.for_config(cell.config)
    spec = timeline.scenario_spec(cell.config, cell.traffic)
    seeds, steps = timeline.call_inputs(cell, 0)
    rspecs = [scenario.retime(spec, scenario.Timeline(tuple(s)))
              for s in steps]
    xs, rs, cs = scenario.build_timeline_streams(
        RouterConfig(d=26, max_arms=8), spec,
        timeline.program_env(cell.config, test), rspecs,
        [(s,) for s in seeds], pad_to=T)
    for e, (seed, st) in enumerate(zip(seeds, steps)):
        rows = events.stream_rows(test.n, T,
                                  cell.traffic["stream_seed_base"], seed)
        R, C = events.outcomes(cell.config, test, rows, st)
        np.testing.assert_array_equal(xs[e], test.contexts[rows])
        np.testing.assert_array_equal(rs[e, :, :test.k], R)
        np.testing.assert_array_equal(cs[e, :, :test.k], C)
