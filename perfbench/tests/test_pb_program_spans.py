"""The staging readers: the program's ``sweep.*`` spans and their byte
counters reduced to per-call numbers, on a trace recorded here on the
CPU, on hand-built spans, and on the recorded TPU trace, which predates
the spans."""
import json
import os
import types

import numpy as np
import pytest

from perfbench import program_spans, registry, trace, work

STAGING = ["grid_streams_ms", "grid_states_ms", "grid_place_ms",
           "grid_readback_ms", "grid_transfer_mb"]
PHASE_OF = {"grid_streams_ms": "sweep.streams",
            "grid_states_ms": "sweep.states",
            "grid_place_ms": "sweep.place",
            "grid_readback_ms": "sweep.readback"}
CALLS = 2


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Two grid calls (2 ceilings x 2 seeds x 64 steps) under the
    benchmark's tracer; returns (reader context, bytes a call moves)."""
    from repro.core import simulator, sweep
    from repro.core.types import RouterConfig

    cfg = RouterConfig()
    env = simulator.make_benchmark(
        seed=0, splits={"train": 128, "val": 16, "test": 64}).test
    rng = np.random.default_rng(5)
    envs = [env.subset(rng.permutation(env.n)) for _ in range(2)]

    def call():
        return sweep.run_grid(cfg, envs, (3.0e-4, 1.0), seeds=(1, 2),
                              shuffle=False)

    call()
    directory = str(tmp_path_factory.mktemp("trace"))
    tracer = trace.Tracer(directory)
    tracer.start()
    for _ in range(CALLS):
        grid = call()
    tracer.stop()
    ctx = work.Context(cell=types.SimpleNamespace(trace_dir=directory),
                       trace=tracer.load(), layer={}, peaks=None)
    # streams: 2 seeds' rows up; place: them down, and 2 x as many up;
    # readback: the four (2, 2, 64) traces down
    row = 4 * (env.contexts.shape[1] + 2 * cfg.max_arms)
    outs = sum(a.nbytes for a in (grid.arms, grid.rewards, grid.costs,
                                  grid.lams))
    return ctx, 2 * 64 * row * (1 + 1 + 2) + outs


def test_grid_program_is_launched_inside_sweep_launch(traced):
    """On the CPU the trace holds launch events: the grid program's two
    executions start inside the two ``sweep.launch`` spans, on the clock
    of the device operations."""
    ctx, _ = traced
    progs = trace.programs_of(ctx.trace, "sweep.launch")
    assert list(progs.values()) == [CALLS]
    grid, = progs
    in_calls = trace.programs_of(ctx.trace, "sweep.run_grid")
    assert grid == max(in_calls, key=lambda p: trace.program_device_s(
        ctx.trace, p))
    assert trace.programs_of(ctx.trace, "sweep.wait") == {}


@pytest.mark.parametrize("name", STAGING)
def test_readers_reduce_a_cpu_trace_to_consistent_numbers(traced, name):
    ctx, nbytes = traced
    value = registry.metric(name).read(ctx)
    call_ms = trace.span_mean_ms(ctx.trace, "sweep.run_grid")
    assert len(program_spans.calls(ctx)) == CALLS
    if name in PHASE_OF:
        assert value == pytest.approx(
            trace.span_mean_ms(ctx.trace, PHASE_OF[name]), rel=1e-9)
        assert 0 < value <= call_ms
    else:
        assert value == pytest.approx(nbytes / 1e6, rel=1e-12)


def test_readers_on_hand_built_spans(monkeypatch):
    """A call 0..1000 ns on thread ``main`` with two phases, and a
    ``sweep.place`` span on another thread, which is not the call's."""
    S = program_spans.Span
    spans = (S("main", "sweep.run_grid", 0, 1000, {}),
             S("main", "sweep.streams", 0, 300, {"h2d_bytes": 7}),
             S("main", "sweep.wait", 450, 900, {}),
             S("other", "sweep.place", 300, 400, {"h2d_bytes": 5}))
    monkeypatch.setattr(program_spans, "spans", lambda d: spans)
    tr = trace.Trace(ops=[(0, "a", 100, 100, 1, 1)],
                     spans=[("main", "sweep.run_grid", 0, 1000)],
                     launches=[], window_s=1e-6)
    ctx = types.SimpleNamespace(cell=types.SimpleNamespace(trace_dir=""),
                                trace=tr)
    assert program_spans.phase_ms(ctx, "sweep.wait") == pytest.approx(4.5e-4)
    assert program_spans.phase_ms(ctx, "sweep.place") == 0.0
    assert program_spans.transfer_mb(ctx) == pytest.approx(7e-6)


@pytest.mark.parametrize("name", STAGING)
def test_readers_find_nothing_in_the_recorded_tpu_trace(name):
    """The recorded TPU call predates the program's spans: no reader
    opens a trace directory or reports a number there."""
    path = os.path.join(os.path.dirname(__file__), "data",
                        "paper3_grid_share_call.json")
    with open(path) as f:
        d = json.load(f)
    tr = trace.Trace(ops=[tuple(o) for o in d["ops"]],
                     spans=[tuple(s) for s in d["spans"]],
                     launches=[tuple(ln) for ln in d["launches"]],
                     window_s=d["window_s"])

    class Cell:
        config = registry.config_file("paper3")

    ctx = work.Context(cell=Cell, trace=tr, layer={}, peaks=None)
    assert registry.metric(name).read(ctx) is None
