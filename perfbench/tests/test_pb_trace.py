"""The trace reduction on hand-built traces: every number below is
worked out by hand from the intervals."""
import pytest

from perfbench import trace


def _tr(launches=True):
    # Device 0: select program 1 (runs 10, 11) and update program 2
    # (run 20); times in ns.
    ops = [
        (0, "fusion.1", 1_000, 2_000, 1, 10),
        (0, "dot.2", 2_500, 1_000, 1, 10),     # overlaps: union 1000..3500
        (0, "fusion.1", 10_000, 1_000, 1, 11),
        (0, "while.3", 5_000, 3_000, 2, 20),
    ]
    spans = [
        ("front", "route_block", 500, 3_500),
        ("front", "route_block", 9_500, 2_000),
        ("learner", "learn_tick", 4_000, 5_000),
    ]
    ln = [("front", 10, 600), ("front", 11, 9_600), ("learner", 20, 4_100)]
    return trace.Trace(ops=ops, spans=spans, launches=ln if launches else [],
                       window_s=20e-6)


def test_union_and_busy():
    assert trace.union_ns([(0, 10), (5, 10), (20, 5)]) == 20
    assert trace.union_ns([]) == 0
    # 1000..3500, 5000..8000, 10000..11000
    assert trace.busy_s(_tr()) == pytest.approx(6_500e-9)


def test_top_ops_sums_by_name():
    assert trace.top_ops(_tr(), 2) == [["fusion.1", 3e-6],
                                       ["while.3", 3e-6]]


def test_idle_gaps_name_the_host_span():
    # gaps 3500..5000 (route_block covers 0, learn_tick 1000) and
    # 8000..10000 (learn_tick 1000, route_block 500): learn_tick 3500 ns
    assert trace.idle_gaps(_tr(), trace.HOST_SPANS) == [
        ["learn_tick", 3.5e-6]]


@pytest.mark.parametrize("launches", [True, False, "elsewhere"])
def test_programs_by_launching_span(launches):
    tr = _tr(launches)
    if launches == "elsewhere":     # a TPU's: on the runtime's threads
        tr.launches = [("runtime", run, t) for _, run, t in tr.launches]
    assert trace.programs_of(tr, "route_block") == {1: 2}
    assert trace.programs_of(tr, "learn_tick") == {2: 1}
    # select: 2500 + 1000 ns over 2 runs; update: 3000 ns over 1 run
    assert trace.per_call_us(tr, "route_block") == pytest.approx(1.75)
    assert trace.per_call_us(tr, "learn_tick") == pytest.approx(3.0)
    assert trace.per_call_us(tr, "run_grid") is None


def test_span_means_and_grid_step():
    tr = _tr()
    assert trace.span_mean_ms(tr, "route_block") == pytest.approx(2.75e-3)
    grid = trace.Trace(
        ops=[(d, "while", 100 + d, 4_000, 7, 70 + r) for d in range(4)
             for r in range(2)] + [(0, "copy", 50, 10, 8, 80)],
        spans=[("main", "run_grid", 0, 10_000)],
        launches=[("main", 70, 10), ("main", 71, 20), ("main", 80, 5)],
        window_s=1e-5)
    # program 7: 4 devices x union 4000 ns (two runs overlap exactly in
    # this made-up trace), 2 runs a device, 100 steps a call
    assert trace.grid_step_us(grid, {"steps_per_call": 100}) == \
        pytest.approx(4_000e-3 / (2 * 100))


class _Ev:
    def __init__(self, name, start, dur, **stats):
        self.name, self.start_ns, self.duration_ns = name, start, dur
        self.stats = list(stats.items())


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _Plane:
    def __init__(self, lines):
        self.lines = lines


def test_tpu_ops_take_the_program_of_the_module_that_holds_them():
    """On a TPU the operations carry no program: each takes the
    ``XLA Modules`` execution whose interval holds its start."""
    plane = _Plane([
        _Line("XLA Modules", [_Ev("jit_grid(7)", 100, 1_000, run_id=5),
                              _Ev("jit_copy(9)", 2_000, 50, run_id=6)]),
        _Line("XLA Ops", [
            _Ev("%while.6 = (s32[]) while(..)", 110, 900),
            _Ev("%fusion.7 = f32[40] fusion(..)", 120, 10),
            _Ev("%copy.1 = f32[4] copy(..)", 2_010, 20),
            _Ev("%stray = f32[] add(..)", 1_500, 5)]),
    ])
    programs = {}
    ops = trace._device_ops(3, plane, programs)
    assert programs == {"jit_grid(7)": 0, "jit_copy(9)": 1}
    assert ops == [(3, "while.6", 110, 900, 0, 5),
                   (3, "fusion.7", 120, 10, 0, 5),
                   (3, "copy.1", 2_010, 20, 1, 6),
                   (3, "stray", 1_500, 5, -1, None)]


def test_tracer_records_spans_and_device_ops(tmp_path):
    """A real profiler trace, on the CPU: the benchmark's spans and the
    operations of the program each span launched come back as tuples."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    tracer = trace.Tracer(str(tmp_path / "tr"), seconds=1.0)
    tracer.start()
    for _ in range(3):
        with tracer.span("route_block"):
            f(x).block_until_ready()
    tracer.stop()
    tr = tracer.load()
    assert len([s for s in tr.spans if s[1] == "route_block"]) == 3
    assert tr.ops and tr.window_s > 0
    progs = trace.programs_of(tr, "route_block")
    assert sum(progs.values()) == 3
    assert trace.per_call_us(tr, "route_block") > 0
    assert 0 < trace.busy_s(tr) < tr.window_s


def test_recorded_tpu_trace_reduces_to_fixed_numbers():
    """One run_grid call of the one-chip grid cell at an earlier size (8
    ceilings x 5 seeds, 40 elements) recorded on a TPU v5 lite
    (tests/data; operations nested in a longer one were dropped, which
    leaves every union unchanged) gives these numbers, worked out once
    from the recording."""
    import json
    import os

    from perfbench import registry, work

    path = os.path.join(os.path.dirname(__file__), "data",
                        "paper3_grid_share_call.json")
    with open(path) as f:
        d = json.load(f)
    tr = trace.Trace(ops=[tuple(o) for o in d["ops"]],
                     spans=[tuple(s) for s in d["spans"]],
                     launches=[tuple(ln) for ln in d["launches"]],
                     window_s=d["window_s"])
    assert tr.devices() == [0]
    assert trace.busy_s(tr) == pytest.approx(0.050889618, rel=1e-12)
    assert trace.top_ops(tr, 1) == [["while.6", 0.050582744]]
    assert trace.idle_gaps(tr, trace.HOST_SPANS) == [
        ["run_grid", 0.164737839]]
    # the grid program is the one with the most device time: one run
    progs = trace.programs_of(tr, "run_grid")
    grid = max(progs, key=lambda p: trace.program_device_s(tr, p))
    assert progs[grid] == 1
    layer = {"elements_per_chip": 40, "steps_per_call": 1824}
    assert trace.grid_step_us(tr, layer) == pytest.approx(27.779806469298244,
                                                         rel=1e-12)

    class Cell:
        config = registry.config_file("paper3")

    ctx = work.Context(cell=Cell, trace=tr, layer=layer,
                       peaks=work.peaks("TPU v5 lite"))
    # least bytes of a step over 819e9 B/s, over 27.78 us: bandwidth bound
    assert registry.metric("grid_step_roofline").read(ctx) == pytest.approx(
        0.028571655376726676, rel=1e-9)
    assert registry.metric("grid_step_device_us").read(ctx) == pytest.approx(
        27.779806469298244, rel=1e-12)
    assert registry.metric("device_idle_pct.grid").read(ctx) == pytest.approx(
        100 * (1 - 0.050889618 / d["window_s"]), rel=1e-12)
