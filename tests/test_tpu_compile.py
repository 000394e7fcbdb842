"""Compile rehearsal: the routing kernels at real widths, compiled with
``interpret=False`` for a described TPU v5e. No chip is needed: the
TPU compiler runs here and refuses what Mosaic cannot lower (interpret
mode cannot). The topology is described inside a fixture, never at
import, and the persistent compile cache is off around these compiles
(a compile for a described chip cannot be read back from it)."""
import os

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
