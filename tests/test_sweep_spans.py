"""The grid fabric's own profiler spans and counters (DESIGN.md §7):
``sweep.run_grid`` and ``sweep.run_scenario_grid`` record one span per
call and one per host phase inside it, each phase's host-device bytes
as span arguments, and stable names for the grid programs. Results do
not depend on whether a profiler is recording."""
import contextlib

import jax
import numpy as np
import pytest

from perfbench import program_spans, trace
from repro.core import evaluate, simulator, sweep, tenancy
from repro.core.scenario import PriceChange, ScenarioSpec, Timeline
from repro.core.types import RouterConfig

CFG = RouterConfig()
BUDGETS = (3.0e-4, 1.0)
SEEDS = (11, 12)
PHASES = ("sweep.streams", "sweep.states", "sweep.place", "sweep.launch",
          "sweep.wait", "sweep.readback")
SPEC = ScenarioSpec(horizon=48, events=(PriceChange(24, 1, 0.1),),
                    stream_seed_base=5)


@pytest.fixture(scope="module")
def env():
    return simulator.make_benchmark(
        seed=0, splits={"train": 128, "val": 16, "test": 64}).test


@pytest.fixture(scope="module")
def priors():
    train = simulator.make_benchmark(
        seed=0, splits={"train": 128, "val": 16, "test": 64}).train
    return evaluate.fit_warmup_priors(CFG, train)


@pytest.fixture(scope="module")
def envs(env):
    """One shuffled stream per seed, as the benchmark's grid calls make."""
    rng = np.random.default_rng(3)
    return [env.subset(rng.permutation(env.n)) for _ in SEEDS]


@contextlib.contextmanager
def profiled(directory):
    """Record a profiler session; yields a list that holds, afterwards,
    every ``sweep.*`` host span (``program_spans.Span``), by start."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    got = []
    jax.profiler.start_trace(str(directory), profiler_options=options)
    try:
        yield got
    finally:
        jax.profiler.stop_trace()
    got.extend(program_spans.spans(str(directory)))


def _by_name(spans):
    out = {}
    for sp in spans:
        out.setdefault(sp.name, []).append(sp)
    return out


def _row_bytes(env):
    """Bytes of one padded stream row: float32 context, rewards, costs."""
    return 4 * (env.contexts.shape[1] + 2 * CFG.max_arms)


def test_run_grid_records_its_phases_nested_and_in_order(envs, tmp_path):
    with profiled(tmp_path) as spans:
        sweep.run_grid(CFG, envs, BUDGETS, seeds=SEEDS, shuffle=False)
    assert [s.name for s in spans] == ["sweep.run_grid", *PHASES]
    call = spans[0]
    ends = [call.start]
    for sp in spans[1:]:
        assert call.start <= sp.start <= sp.end <= call.end, sp.name
        assert sp.start >= ends[-1], f"{sp.name} starts before the last ends"
        ends.append(sp.end)


@pytest.mark.parametrize("shuffle", [False, True])
def test_phases_cover_the_call(shuffle, env, envs, tmp_path):
    """The phases hold the whole call but its argument checks, the
    program lookup and the result's assembly: every idle stretch of the
    device inside a call has a phase that owns it."""
    arg = env if shuffle else envs
    sweep.run_grid(CFG, arg, BUDGETS, seeds=SEEDS, shuffle=shuffle)
    with profiled(tmp_path) as spans:
        sweep.run_grid(CFG, arg, BUDGETS, seeds=SEEDS, shuffle=shuffle)
    call, *phases = spans
    assert call.name == "sweep.run_grid"
    covered = sum(p.end - p.start for p in phases)
    assert covered >= 0.9 * (call.end - call.start)


def test_states_phase_is_one_dispatch(envs, priors, tmp_path):
    """On a warm call the whole ``sweep.states`` phase, warm start
    included, is one execution of one compiled program."""
    def call():
        sweep.run_grid(CFG, envs, BUDGETS, seeds=SEEDS, shuffle=False,
                       priors=priors, n_eff=1164.0)

    call()
    with profiled(tmp_path):
        call()
    progs = trace.programs_of(trace.Tracer(str(tmp_path)).load(),
                              "sweep.states")
    assert list(progs.values()) == [1]


@pytest.mark.parametrize("layout", ["per_seed_envs", "one_env_shuffled"])
def test_byte_counters_equal_the_shapes(layout, env, envs, tmp_path):
    C, S, T = len(BUDGETS), len(SEEDS), env.n
    row = _row_bytes(env)
    if layout == "per_seed_envs":
        arg, streams_h2d = envs, S * T * row
    else:       # one environment sent once, one int32 permutation a gather
        arg, streams_h2d = env, T * row + 3 * S * T * 4
    with profiled(tmp_path) as spans:
        grid = sweep.run_grid(CFG, arg, BUDGETS, seeds=SEEDS,
                              shuffle=layout != "per_seed_envs")
    got = _by_name(spans)
    assert got["sweep.streams"][0].args == {"h2d_bytes": streams_h2d}
    # the (S, T) streams come back once and go out tiled C times
    assert got["sweep.place"][0].args == {"h2d_bytes": C * S * T * row,
                                          "d2h_bytes": S * T * row}
    out = sum(C * S * T * np.dtype(a.dtype).itemsize
              for a in (grid.arms, grid.rewards, grid.costs, grid.lams))
    assert got["sweep.readback"][0].args == {"d2h_bytes": out}


# One timeline per condition: the event at step 16, and at step 30 with
# the horizon cut to 40 of the spec's 48 steps.
TIMELINES = [Timeline((16,)), Timeline((30,), horizon=40)]


def _scenario_grid(env, timelines=TIMELINES):
    return sweep.run_scenario_grid(CFG, SPEC, env, BUDGETS, seeds=SEEDS,
                                   timelines=timelines)


@pytest.mark.parametrize("timelines", [TIMELINES, None],
                         ids=["timeline", "segmented"])
def test_run_scenario_grid_records_its_phases_nested_and_in_order(
        timelines, env, tmp_path):
    with profiled(tmp_path) as spans:
        _scenario_grid(env, timelines)
    assert [s.name for s in spans] == ["sweep.run_scenario_grid", *PHASES]
    call = spans[0]
    ends = [call.start]
    for sp in spans[1:]:
        assert call.start <= sp.start <= sp.end <= call.end, sp.name
        assert sp.start >= ends[-1], f"{sp.name} starts before the last ends"
        ends.append(sp.end)


def test_scenario_grid_phases_cover_the_call(env, tmp_path):
    _scenario_grid(env)
    with profiled(tmp_path) as spans:
        _scenario_grid(env)
    call, *phases = spans
    assert call.name == "sweep.run_scenario_grid"
    covered = sum(p.end - p.start for p in phases)
    assert covered >= 0.9 * (call.end - call.start)


def test_scenario_grid_byte_counters_equal_the_shapes(env, tmp_path):
    """The timeline path builds its per-element streams on the host and
    sends them once, in ``place``, with the payload stack (the
    auto-lifted price multiplier), the event steps and the horizons."""
    n, T, E = len(BUDGETS) * len(SEEDS), SPEC.horizon, len(SPEC.events)
    with profiled(tmp_path) as spans:
        grid = _scenario_grid(env)
    got = _by_name(spans)
    assert "h2d_bytes" not in got["sweep.streams"][0].args
    assert got["sweep.place"][0].args == {
        "h2d_bytes": n * T * _row_bytes(env) + 4 * n + 4 * n * E + 4 * n,
        "d2h_bytes": 0}
    out = sum(n * T * np.dtype(a.dtype).itemsize
              for a in (grid.arms, grid.rewards, grid.costs, grid.lams))
    assert got["sweep.readback"][0].args == {"d2h_bytes": out}


@pytest.mark.parametrize("chunk_size", [None, 2])
def test_launch_records_the_chunk(chunk_size, env, tmp_path):
    """``sweep.launch`` records the elements per chunk the call ran with:
    the whole grid by default on the CPU, else the ``chunk_size``."""
    n = len(BUDGETS) * len(SEEDS)
    with profiled(tmp_path) as spans:
        sweep.run_scenario_grid(CFG, SPEC, env, BUDGETS, seeds=SEEDS,
                                timelines=TIMELINES, chunk_size=chunk_size)
    got = _by_name(spans)
    assert got["sweep.launch"][0].args == {"chunk": chunk_size or n}


def _tenant_args(env):
    tables = tenancy.stack_tables([tenancy.make_table([2e-4, 3e-4]),
                                   tenancy.make_table([4e-4, 5e-4])])
    tids = np.arange(env.n, dtype=np.int32) % 2
    return dict(tenant_tables=tables, tenant_ids=tids, batch_size=8)


def _grid(kind, env, envs):
    """One grid call of each fabric program."""
    if kind == "grid_program":
        return sweep.run_grid(CFG, envs, BUDGETS, seeds=SEEDS,
                              shuffle=False, return_states=True)
    if kind == "grid_program_tenants":
        return sweep.run_grid(CFG, env, BUDGETS, seeds=SEEDS,
                              return_states=True, **_tenant_args(env))
    timelines = ([Timeline((16,)), Timeline((30,), horizon=40)]
                 if kind == "timeline_grid_program" else None)
    return sweep.run_scenario_grid(CFG, SPEC, env, BUDGETS, seeds=SEEDS,
                                   return_states=True, timelines=timelines)


KINDS = ["grid_program", "grid_program_tenants", "scenario_grid_program",
         "timeline_grid_program"]


@pytest.mark.parametrize("kind", KINDS)
def test_results_do_not_depend_on_the_profiler(kind, env, envs, tmp_path):
    off, off_states = _grid(kind, env, envs)
    with profiled(tmp_path) as spans:
        on, on_states = _grid(kind, env, envs)
    # scenario grids share placement, launch, wait and readback
    assert {s.name for s in spans} >= set(PHASES[2:])
    for name in ("arms", "rewards", "costs", "lams"):
        np.testing.assert_array_equal(getattr(on, name), getattr(off, name))
    for a, b in zip(jax.tree.leaves(on_states), jax.tree.leaves(off_states)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_tenant_grid_matches_looped_runs(env):
    """Tenant ids reach each device's shard straight from the host; the
    grid stays bit-identical to one ``evaluate.run`` per condition."""
    kw = _tenant_args(env)
    grid = sweep.run_grid(CFG, env, BUDGETS, seeds=SEEDS, **kw)
    tables = kw.pop("tenant_tables")
    for c, b in enumerate(BUDGETS):
        table = jax.tree.map(lambda leaf: leaf[c], tables)
        res = evaluate.run(CFG, env, b, SEEDS, tenants=table, **kw)
        np.testing.assert_array_equal(grid.condition(c).arms, res.arms)
        np.testing.assert_array_equal(grid.condition(c).lams, res.lams)


@pytest.mark.parametrize("kind", KINDS)
def test_grid_programs_carry_their_names(kind, env, envs, monkeypatch):
    """The jitted function is named for the program, so its module reads
    ``jit_<kind>``, and the per-element scan runs under ``grid_step``."""
    seen = []
    launch = sweep._launch_and_read

    def record(program, operands, C, S, chunk_size=None):
        seen.append((program(1), [jax.ShapeDtypeStruct(a.shape, a.dtype)
                                  for a in jax.tree.leaves(operands)],
                     jax.tree.structure(operands)))
        return launch(program, operands, C, S, chunk_size)

    monkeypatch.setattr(sweep, "_launch_and_read", record)
    _grid(kind, env, envs)
    (fn, leaves, tree), = seen
    text = fn.lower(*jax.tree.unflatten(tree, leaves)).as_text(
        debug_info=True)
    assert f"jit_{kind}" in text
    assert "grid_step" in text
