"""Compile rehearsal: the routing kernels at real widths, compiled with
``interpret=False`` for a described TPU v5e. No chip is needed: the
TPU compiler runs here and refuses what Mosaic cannot lower (interpret
mode cannot). The topology is described inside a fixture, never at
import, and the persistent compile cache is off around these compiles
(a compile for a described chip cannot be read back from it)."""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.linucb_score.ops import linucb_score
from repro.kernels.linucb_step.ops import linucb_step

# (B, K, d): a small gateway block on the paper's 3-arm portfolio, and a
# B=256 block at the registry cap of 8 arms; d = 25 PCA + bias.
SHAPES = [(8, 3, 26), (256, 8, 26)]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "no TPU"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compiled_text(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


def _step_shapes(spec, B, K, d):
    f32, i32 = jnp.float32, jnp.int32
    return (spec((K, d, d)), spec((K, d, d)), spec((K, d)), spec((K, d)),
            spec((K,), i32), spec((B, d)), spec((B, K)), spec((B, K)),
            spec((B, K)), spec((K,), jnp.bool_), spec((K,)), spec((K,)),
            *(spec((), f32) for _ in range(8)), spec((), i32),
            spec((), i32), spec((B,), jnp.bool_))


@pytest.mark.parametrize("B,K,d", SHAPES)
def test_linucb_score_compiles(one_chip, no_persistent_cache, B, K, d):
    spec = lambda s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
    text = _compiled_text(
        lambda *a: linucb_score(*a, interpret=False),
        spec((B, d)), spec((K, d)), spec((K, d, d)), spec((K,)),
        spec((K,)), spec(()))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("B,K,d", SHAPES)
def test_linucb_step_compiles(one_chip, no_persistent_cache, B, K, d):
    spec = lambda s, t=jnp.float32: jax.ShapeDtypeStruct(
        s, t, sharding=one_chip)
    text = _compiled_text(
        lambda *a: linucb_step(*a, interpret=False),
        *_step_shapes(spec, B, K, d))
    assert "tpu_custom_call" in text


def test_linucb_step_vmapped_compiles(one_chip, no_persistent_cache):
    """The fused step under a seed axis, as ``evaluate.run`` and the sweep
    fabric call it: vmap turns the gridless kernel into a gridded one."""
    S, B, K, d = 20, 256, 8, 26
    spec = lambda s, t=jnp.float32: jax.ShapeDtypeStruct(
        (S,) + s, t, sharding=one_chip)
    text = _compiled_text(
        jax.vmap(lambda *a: linucb_step(*a, interpret=False)),
        *_step_shapes(spec, B, K, d))
    assert "tpu_custom_call" in text


def _computations(text):
    """HLO text -> {computation name: its instruction lines}."""
    comps, body = {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%(\S+) .*\{$", line)
        if head:
            body = comps.setdefault(head.group(1), [])
        elif line.startswith("}"):
            body = None
        elif body is not None:
            body.append(line.strip())
    return comps


def _step_scan(text, stack):
    """The ``while`` whose carry holds ``stack`` (an HLO shape such as
    ``f32[128,8,26,26]``): (its carry's ``stack`` types, its body)."""
    comps = _computations(text)
    for line in text.splitlines():
        if " while(" in line and stack + "{" in line:
            carry = re.findall(re.escape(stack) + r"\{[^}]*\}", line)
            body = re.search(r"body=%([^,\s]+)", line).group(1)
            return carry, comps[body]
    raise AssertionError(f"no while carries {stack}")


def _relayout_copies(body, d):
    """Copies in ``body`` that move an f32[..., d, d] stack to another
    layout (the operand's minor-to-major order differs)."""
    types = {}
    for ins in body:
        m = re.match(r"(?:ROOT )?%(\S+) = (\S+\{[^}]*\})", ins)
        if m:
            types[m.group(1)] = m.group(2)

    def order(t):
        return t.split("{", 1)[1].split(":", 1)[0].rstrip("}")

    out = []
    for ins in body:
        m = re.match(r"(?:ROOT )?%(\S+) = (f32\[[^\]]*\]\{[^}]*\}) "
                     r"copy\(%([^)]+)\)", ins)
        if m and m.group(2).split("]")[0].count(str(d)) >= 2:
            src = types.get(m.group(3))
            if src is None or order(src) != order(m.group(2)):
                out.append(ins[:120])
    return out


def _paper3_states(cfg, env, train, sharding, n):
    """The warm-started state stack of ``n`` elements as shapes."""
    from repro.core import evaluate

    priors = evaluate.fit_warmup_priors(cfg, train)
    priors = list(priors) + [None] * (env.k - len(priors))
    states = evaluate.make_states(cfg, env, 6.6e-4, (0,), priors=priors,
                                  n_eff=1164.0, active_arms=3)
    return jax.tree.map(lambda l: jax.ShapeDtypeStruct(
        (n,) + l.shape[1:], l.dtype, sharding=sharding), states)


def test_timeline_grid_program_compiles(one_chip, no_persistent_cache):
    """The masked timeline grid program of the ``paper3_mc1024`` cell at
    its own size: 1,024 elements x 1,824 steps, 8 arm slots, d = 26, a
    silent price cut, a quality shift and a cold ``AddArm`` with forced
    pulls, each event's step an operand of every element. The cold arm's
    inverse is closed form, so the program holds no LU custom call. The
    fabric's default chunk on one v5e (128 elements) keeps each chunk's
    A and A^-1 on chip, in S(1), and the step relays no stack."""
    from repro.core import scenario, simulator, sweep
    from repro.core.scenario import (AddArm, PriceChange, QualityShift,
                                     ScenarioSpec)
    from repro.core.types import RouterConfig

    N, T, d = 1024, 1824, 26
    b = simulator.make_benchmark(
        seed=0, splits={"train": 128, "val": 16, "test": 64})
    env = simulator.extend_with_flash(b.test, "good_cheap")
    cfg = RouterConfig(d=d, max_arms=8, forced_pulls=20)
    spec = ScenarioSpec(horizon=T, events=(
        PriceChange(152, 2, 1 / 56), QualityShift(152, 1, 0.75),
        AddArm(152, 3)), init_active=3)
    states = _paper3_states(cfg, env, b.train, one_chip, N)
    chunk = sweep.grid_chunk(states)
    assert chunk == 128
    fn = sweep._cached_timeline_grid_fn(cfg, spec, env, None,
                                        sweep._n_chunks(N, chunk))
    params = scenario.ScenarioParams(**scenario.auto_param_values(spec))

    def spec_of(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    operands = (
        states, spec_of((N, T, d)), spec_of((N, T, 8)), spec_of((N, T, 8)),
        jax.tree.map(lambda l: spec_of((N,) + l.shape), params),
        spec_of((N, len(spec.events)), jnp.int32), spec_of((N,), jnp.int32))
    compiled = fn.lower(*operands).compile()
    text = compiled.as_text()
    assert "jit_timeline_grid_program" in text
    # The cold AddArm's A^-1 is written in closed form: no batched LU
    # runs inside the scan (the edit's select runs it on every step).
    for target in ("LuDecompositionBlock", "InvertDiagBlocksLowerTriangular",
                   "InvertDiagBlocksUpperTriangular"):
        assert target not in text, target
    # The step scan carries the chunk's A and A^-1 in on-chip memory and
    # moves neither between layouts.
    carry, body = _step_scan(text, f"f32[{chunk},8,{d},{d}]")
    assert len(carry) >= 2 and all(":T(8,128)S(1)}" in c for c in carry), \
        carry
    assert _relayout_copies(body, d) == []
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < 16e9


def test_paper3_grid_default_chunk_is_one(one_chip):
    """The ``paper3_grid`` cell's 160 elements fit on chip whole: its
    grid program stays one chunk on one v5e."""
    from repro.core import simulator, sweep
    from repro.core.types import RouterConfig

    b = simulator.make_benchmark(
        seed=0, splits={"train": 128, "val": 16, "test": 64})
    cfg = RouterConfig(d=26, max_arms=8)
    states = _paper3_states(cfg, b.test, b.train, one_chip, 160)
    assert sweep._n_chunks(160, sweep.grid_chunk(states)) == 1
