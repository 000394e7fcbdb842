"""Device-sharded grid-sweep fabric: a whole condition grid as ONE
compiled program.

The paper's headline results are *grids* — seven budget ceilings x 20
seeds (Fig. 1), scenario x budget matrices, hyper-parameter AUC sweeps —
yet the benchmarks historically looped over grid conditions in host
Python around a per-condition jitted call, paying a dispatch (and, across
configs, a retrace) per cell. This module evaluates the entire grid in
one jitted, device-sharded call:

  * the condition axis is stacked into *operands* — the budget ceiling
    lives in ``PacerState.budget`` (evaluate.make_states accepts one
    budget per stacked state), and hyper-parameters, warm-start n_eff,
    scenario payloads and tenant tables arrive as scalar, per-condition
    ``(C,)`` or per-element ``(C·S,)`` stacks;

  * the (condition, seed) grid is flattened to one leading axis of size
    N = C x S, ``jax.vmap``-ed over the existing per-seed program —
    ``router.run_stream`` / ``run_stream_batched`` or the scenario
    engine's segmented scan (``scenario.segment_body``) — and sharded
    across available devices with ``jax.sharding`` via the
    ``launch/mesh.py`` grid-mesh helpers (the N axis is embarrassingly
    parallel). On a CPU host, placeholder devices forced with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` shard exactly
    as real accelerators do (dryrun.py's convention);

  * the state stack is donated to the compiled call, so the grid's
    initial states never double-buffer;

  * a ``chunk_size`` knob (DESIGN.md §11) bounds how many grid elements
    are live per stream step: the flat axis is scanned chunk-by-chunk
    *inside* the one compiled program, so wide grids whose per-step
    working set spills the last-level cache (the knee grid's N ~ 720
    elements carry ~30 MB of live state) trade embarrassing parallelism
    for locality without changing a single result bit. Left None on one
    TPU it defaults to the largest chunk whose state carry fits the
    chip's on-chip memory (``default_chunk``), so the step's statistics
    never stream through HBM; elsewhere the whole grid is one chunk.

Hyper-parameters are state leaves too (DESIGN.md §9): ``RouterState``
carries a ``HyperParams`` pytree, so a whole (α, γ) grid stacks on the
condition axis as ``hyper=`` leaves — bench_knee's full (α x γ x
budget x seed) selection grid is ONE fabric call. And scenario event
*payloads* are data as well (DESIGN.md §10): a ``ScenarioSpec`` whose
payloads are ``scenario.Param`` references plus a ``scenario_params=``
stack fuses a whole spec *family* — price cuts at several magnitudes,
regressions to several quality targets — into one
``run_scenario_grid`` call. Knobs that remain *trace constants* — the
``Statics`` (``d``, ``max_arms``, ``backend``, ``dt_max``,
``forced_pulls``), event times/slots and the stream tensors' shapes —
still cost one compile per value. DESIGN.md §1/§7 tabulate which knobs
stack.

Per-condition results are bit-identical to the looped
``evaluate.run``-per-condition baseline (pinned in tests/test_sweep.py):
the fabric reuses the same stream builder, the same state constructor and
the same scan bodies — only the batching axis is wider.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import evaluate, tenancy
from repro.core import scenario as scenario_lib
from repro.core import types as types_lib
from repro.core.simulator import Environment
from repro.core.types import ArmPrior, HyperParams, RouterConfig, RouterState
from repro.launch import mesh as mesh_lib

Array = jax.Array

# Incremented inside the traced grid body: moves only when XLA (re)traces
# a fabric program, so tests can assert the whole-grid-compiles-once
# contract (one trace for 7 budgets x 20 seeds, not one per budget).
TRACE_COUNT = [0]


@dataclasses.dataclass(frozen=True)
class GridResult:
    """Traces for a (condition x seed) grid, shaped (C, S, T)."""

    budgets: tuple       # (C,) condition axis (the stacked ceilings)
    seeds: tuple         # (S,)
    arms: np.ndarray     # (C, S, T)
    rewards: np.ndarray  # (C, S, T)
    costs: np.ndarray    # (C, S, T)
    lams: np.ndarray     # (C, S, T)
    # Segment boundaries shared by every condition (scenario grids).
    bounds: Optional[tuple] = None
    # Per-condition scenario payload values (name -> (C,)+payload_shape),
    # recorded for reporting when a payload axis rides the grid.
    params: Optional[dict] = None
    # Timeline grids: per-condition *effective* bounds / horizons — the
    # (C, S, T) arrays are padded to T_max, and ``condition(i)`` trims to
    # horizons[i] so downstream slicing never reads padding rows.
    cond_bounds: Optional[tuple] = None
    horizons: Optional[tuple] = None

    def __len__(self) -> int:
        return len(self.budgets)

    def condition(self, i: int) -> evaluate.RunResult:
        """Slice one condition to the standard multi-seed ``RunResult``
        (timeline grids: trimmed to the condition's effective horizon,
        with that condition's own segment bounds)."""
        h = None if self.horizons is None else self.horizons[i]
        b = self.bounds if self.cond_bounds is None else self.cond_bounds[i]
        return evaluate.RunResult(
            arms=self.arms[i][:, :h], rewards=self.rewards[i][:, :h],
            costs=self.costs[i][:, :h], lams=self.lams[i][:, :h], bounds=b,
        )

    def conditions(self):
        for i, b in enumerate(self.budgets):
            yield b, self.condition(i)


def _check_grid_args(budgets, seeds):
    """Explicit ValueErrors for degenerate grids — an empty axis would
    otherwise surface as a cryptic reshape / vmap / mesh failure deep
    inside the fabric. Materializes (and returns) the axes exactly once
    so one-shot iterables stay valid."""
    budgets, seeds = tuple(budgets), tuple(seeds)
    if not budgets:
        raise ValueError(
            "budgets is empty: the grid needs at least one condition")
    if not seeds:
        raise ValueError(
            "seeds is empty: the grid needs at least one seed")
    return budgets, seeds


def _flatten_grid(budgets, seeds):
    """(C,) x (S,) -> aligned flat (C*S,) budget / seed vectors, ordered
    condition-major so element c*S + s is (budgets[c], seeds[s])."""
    budgets = tuple(float(b) for b in budgets)
    seeds = tuple(int(s) for s in seeds)
    flat_b = np.repeat(np.asarray(budgets, np.float32), len(seeds))
    flat_s = seeds * len(budgets)
    return budgets, seeds, flat_b, flat_s


def _per_condition_axis(value, C: int, S: int):
    """Expand a per-condition vector to the flattened grid: a (C,) value
    repeats each entry S times to align with the condition-major (C*S,)
    state stack; scalars and already-flat (C*S,) values pass through."""
    arr = np.asarray(value)
    if arr.ndim == 1 and arr.shape[0] == C and C != C * S:
        return np.repeat(arr, S)
    return value


def _expand_hyper(hyper, C: int, S: int):
    """Per-condition (C,) hyper leaves -> flattened (C*S,) stacks."""
    if hyper is None:
        return None
    return HyperParams(**{
        n: _per_condition_axis(getattr(hyper, n), C, S)
        for n in types_lib.HYPER_FIELDS
    })


def _expand_tenants(tables, C: int, S: int):
    """A tenant-table spec for the flattened grid (DESIGN.md §15):
    shared (T,) leaves pass through (every grid element gets a copy),
    per-condition (C, T) leaves repeat S times to (C*S, T), and
    pre-flattened (C*S, T) leaves pass through."""
    if tables is None:
        return None
    ndim = jnp.ndim(tables.budget)
    if ndim == 1:
        return tables
    n0 = tables.budget.shape[0]
    if ndim == 2 and n0 == C and C != C * S:
        return jax.tree.map(
            lambda l: jnp.repeat(jnp.asarray(l), S, axis=0), tables)
    if ndim == 2 and n0 == C * S:
        return tables
    raise ValueError(
        f"tenant_tables.budget must be (T,) shared, ({C}, T) per-"
        f"condition or ({C * S}, T) pre-flattened; got shape "
        f"{jnp.shape(tables.budget)}")


def _tile_conditions(arr: Array, C: int) -> np.ndarray:
    """Stack per-seed stream tensors along a leading condition axis,
    (S, ...) -> (C*S, ...), in host memory: ``_shard_grid`` then places
    the tile directly under the grid sharding, so ``device_put``
    transfers each device only its shard and no single device ever
    holds the C-times tensor (device 0 would OOM first on large
    accelerator grids)."""
    a = np.asarray(arr)
    return np.broadcast_to(a[None], (C,) + a.shape).reshape(
        (C * a.shape[0],) + a.shape[1:])


def _d2h_nbytes(x) -> int:
    """Bytes ``np.asarray(x)`` reads back from the devices: all of a
    device array, nothing of a host one."""
    return int(x.nbytes) if isinstance(x, jax.Array) else 0


def _h2d_nbytes(x, sharding=None) -> int:
    """Bytes ``jax.device_put(x, sharding)`` sends from host memory,
    from shapes alone: every device's shard of a host array (a
    replicated array once per device; all of it to the default device
    where ``sharding`` is None), in the dtype it lands in. An array
    already on a device crosses no host link."""
    if isinstance(x, jax.Array):
        return 0
    itemsize = np.dtype(jax.dtypes.canonicalize_dtype(
        np.result_type(x))).itemsize
    if sharding is None:
        return int(np.prod(np.shape(x))) * itemsize
    shard = sharding.shard_shape(np.shape(x))
    return len(sharding.device_set) * int(np.prod(shard)) * itemsize


def _shard_grid(states: RouterState, streams, stream_axes, C, devices,
                params=None, extras=()):
    """Place the flattened grid on a 1-D device mesh: state leaves,
    condition-tiled streams, per-element scenario-param leaves and any
    ``extras`` (per-element timeline operands) split along the grid
    axis, shared streams replicated. Recorded as the ``sweep.place``
    span, with the bytes it moves over the host link as ``h2d_bytes``
    and ``d2h_bytes``."""
    with jax.profiler.TraceAnnotation("sweep.place") as span:
        n = int(states.t.shape[0])
        mesh = mesh_lib.make_grid_mesh(n, devices)
        sh = mesh_lib.grid_sharding(mesh)
        rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
        h2d = d2h = 0

        def put(a, sharding):
            nonlocal h2d
            h2d += sum(_h2d_nbytes(l, sharding) for l in jax.tree.leaves(a))
            return jax.device_put(a, sharding)

        states = put(states, sh)
        # The state stack is donated to the fabric call; donation
        # requires one buffer per leaf, but identical constant-initialised
        # leaves (zeroed last_upd/last_play, A == A_inv at lambda0 = 1)
        # can share one. Copy to uniquify — a few MB next to the grid
        # compute.
        states = jax.tree.map(lambda l: jnp.array(l, copy=True), states)
        if stream_axes == 0:
            # Pre-stacked per-element streams pass through; per-seed
            # (S,...) streams are condition-tiled.
            placed = []
            for a in streams:
                if a.shape[0] != n:
                    d2h += _d2h_nbytes(a)
                    a = _tile_conditions(a, C)
                placed.append(put(a, sh))
            streams = tuple(placed)
        else:
            streams = tuple(put(a, rep) for a in streams)
        if params is not None:
            params = put(params, sh)
        extras = tuple(put(a, sh) for a in extras)
        span.set_metadata(h2d_bytes=h2d, d2h_bytes=d2h)
    return states, streams, params, extras


def _n_chunks(n: int, chunk_size) -> int:
    """Validate a ``chunk_size`` knob against the flattened grid size."""
    if chunk_size is None:
        return 1
    chunk_size = int(chunk_size)
    if chunk_size < 1 or n % chunk_size:
        raise ValueError(
            f"chunk_size={chunk_size}: must be a positive divisor of the "
            f"flattened grid size C*S = {n} (sweep.fit_chunk picks one)")
    return n // chunk_size


# On-chip budget of one chunk's scan carry, by ``device_kind``. A TPU
# v5e TensorCore has 128 MiB of vector memory (VMEM, XLA's memory space
# S(1)); the carry takes half, the rest holding the step's temporaries.
# Compiled for a described v5e at d = 26 and 8 slots (273,484 B an
# element, tiled): 160 elements (43.8 MB) keep every state leaf in S(1),
# and at 256 (70.0 MB) one stack's copy in the step falls out to HBM.
ONCHIP_CARRY_BYTES = {"TPU v5 lite": 64 * 2 ** 20}


def _tiled_nbytes(shape, dtype) -> int:
    """Bytes of one grid element's leaf in a chunk's state stack, its
    last two dims padded to the (8, 128) tile: a rank-1 leaf pads to
    128 lanes (the element axis is its sublane), a scalar is one word."""
    shape = tuple(int(s) for s in shape)
    if len(shape) >= 2:
        shape = shape[:-2] + (-(-shape[-2] // 8) * 8,
                              -(-shape[-1] // 128) * 128)
    elif shape:
        shape = (-(-shape[0] // 128) * 128,)
    return int(np.prod(shape)) * np.dtype(dtype).itemsize


def default_chunk(n: int, leaves, device_kind: str, n_devices: int = 1,
                  chunk_size: Optional[int] = None) -> Optional[int]:
    """The elements per chunk for an ``n``-element grid whose elements
    carry state ``leaves`` (per-element shapes and dtypes): the largest
    divisor of ``n`` whose tile-padded carry fits the device kind's
    on-chip budget (``ONCHIP_CARRY_BYTES``), so the step's statistics
    stay on chip instead of streaming through HBM every step.

    An explicit ``chunk_size`` wins. None (one chunk, the whole grid
    live) where the grid fits whole, on a kind with no budget (the CPU
    among them), on more than one device (the chunk reshape would cut
    across the sharded axis), and where no divisor reaches a quarter of
    the fitting size (a prime ``n`` never runs one element at a time)."""
    if chunk_size is not None:
        return chunk_size
    budget = ONCHIP_CARRY_BYTES.get(device_kind)
    if budget is None or n_devices != 1:
        return None
    fits = budget // sum(_tiled_nbytes(l.shape, l.dtype) for l in leaves)
    if fits < 1 or n <= fits:
        return None
    c = fit_chunk(n, fits)
    return c if 4 * c >= fits else None


def grid_chunk(states, chunk_size: Optional[int] = None) -> Optional[int]:
    """``default_chunk`` for a placed state stack (arrays, or shapes
    with a sharding): its grid size, per-element leaves and devices."""
    n = int(states.t.shape[0])
    devices = states.t.sharding.device_set
    leaves = [jax.ShapeDtypeStruct(l.shape[1:], l.dtype)
              for l in jax.tree.leaves(states)]
    return default_chunk(n, leaves, next(iter(devices)).device_kind,
                         len(devices), chunk_size)


def fit_chunk(n: int, chunk_size: int) -> int:
    """The largest divisor of ``n`` that is <= ``chunk_size`` (always
    >= 1) — the convenience for callers whose grid size is not known to
    divide evenly (benchmarks sweeping N)."""
    c = max(1, min(int(chunk_size), int(n)))
    while n % c:
        c -= 1
    return c


def _chunk_wrap(vm, n_chunks: int, scan_in):
    """Scan-over-chunks wrapper for a flat-grid-axis vmapped program.

    A wide grid's per-step working set is N x (per-element state), which
    for the knee grid's N ~ 720 elements spills the CPU last-level cache
    (~30 MB live vs ~24 MB L3; see benchmarks/results/knee.json), and
    on a TPU a stack past on-chip memory is streamed through HBM, and
    relaid, every step (``default_chunk``). The wrapper reshapes every
    (N, ...) operand to (n_chunks, N/n_chunks, ...), runs the chunks
    *sequentially* under ``lax.scan`` and flattens the stacked outputs
    back — the live set shrinks by n_chunks while the whole grid stays
    ONE compiled program. vmap is elementwise over the grid axis, so
    per-element math is untouched and results stay bit-identical to the
    unchunked fabric (pinned in tests/test_sweep.py).
    ``scan_in`` flags which trailing operands carry the grid axis
    (chunked with the states) vs being shared across elements (closed
    over, replicated to every chunk).
    """
    if n_chunks <= 1:
        return vm

    def chunked(states, *args):
        def resh(leaf):
            return leaf.reshape((n_chunks, -1) + leaf.shape[1:])

        xs = (jax.tree.map(resh, states),) + tuple(
            jax.tree.map(resh, a) if sc else None
            for a, sc in zip(args, scan_in))
        shared = tuple(a for a, sc in zip(args, scan_in) if not sc)

        def body(carry, inp):
            st, *chunk_args = inp
            it = iter(shared)
            call = [a if sc else next(it)
                    for a, sc in zip(chunk_args, scan_in)]
            return carry, vm(st, *call)

        _, out = jax.lax.scan(body, None, xs)
        return jax.tree.map(lambda l: l.reshape((-1,) + l.shape[2:]), out)

    return chunked


def _grid_program(name: str, body, in_axes, n_chunks: int, scan_in):
    """Jit one fabric program: ``body`` (one grid element's whole
    stream) vmapped over the flat grid axis and chunked, the state stack
    donated. The jitted function is called ``name``, so its XLA module
    reads ``jit_<name>``, and each element's scan, whose body is the
    grid step, runs under ``jax.named_scope("grid_step")``: a trace
    finds the program and its step operations by name."""

    def one(state, *operands):
        TRACE_COUNT[0] += 1       # moves only while tracing
        with jax.named_scope("grid_step"):
            return body(state, *operands)

    step = _chunk_wrap(jax.vmap(one, in_axes=in_axes), n_chunks, scan_in)

    def program(states, *operands):
        return step(states, *operands)

    program.__name__ = program.__qualname__ = name
    return jax.jit(program, donate_argnums=0)


@functools.lru_cache(maxsize=64)
def _cached_grid_fn(statics, stream_axes, batch_size, n_chunks=1):
    """One jitted fabric program per (Statics, stream layout, data
    plane, chunking); budgets, seeds, priors and hyper-parameters are
    data, so every grid with the same shapes re-enters the same
    executable. The state stack is donated."""
    return _grid_program(
        "grid_program", evaluate.stream_body(statics, batch_size),
        (0, stream_axes, stream_axes, stream_axes), n_chunks,
        (stream_axes == 0,) * 3)


@functools.lru_cache(maxsize=64)
def _cached_grid_fn_tenants(statics, stream_axes, batch_size, n_chunks=1):
    """Tenant-mode fabric program (DESIGN.md §15): every grid element
    carries its own (L,) tenant-id stream (expanded host-side to the
    flattened (C*S, L) layout, sharded with the states). Tables and ids
    are data — a new (tenants x budgets) grid with the same shapes
    re-enters this executable with zero retraces."""
    return _grid_program(
        "grid_program_tenants",
        evaluate.stream_body_tenants(statics, batch_size),
        (0, stream_axes, stream_axes, stream_axes, 0), n_chunks,
        (stream_axes == 0,) * 3 + (True,))


def run_grid(
    cfg: RouterConfig,
    env: Environment | Sequence[Environment],
    budgets: Sequence[float],
    seeds: Sequence[int] = tuple(range(20)),
    *,
    priors: Optional[Sequence[ArmPrior | None]] = None,
    n_eff: float | Sequence[float] = 0.0,
    pacer_enabled: bool = True,
    shuffle: bool = True,
    batch_size: Optional[int] = None,
    devices=None,
    return_states: bool = False,
    hyper: Optional[HyperParams] = None,
    chunk_size: Optional[int] = None,
    tenant_tables: Optional["tenancy.TenantTable"] = None,
    tenant_ids=None,
):
    """Evaluate a (budget x seed) grid as one compiled, sharded call.

    Semantics per condition match ``evaluate.run(cfg, env, budgets[c],
    seeds=seeds, ...)`` bit-for-bit: same per-seed shuffles, same initial
    states, same scan bodies.

    ``hyper`` leaves and ``n_eff`` may each be a scalar, a per-condition
    ``(C,)`` vector or a per-element ``(C*S,)`` stack (DESIGN.md §9): a
    (C,) vector is repeated S times onto the flattened stack, and all of
    it is applied inside ``make_states``' one cached compiled program
    (keyed on the stack's structure, every value an operand) — the way
    an (α, γ, n_eff) grid joins the condition axis. ``pacer_enabled``
    and warm versus cold priors are one structure per call: run such
    conditions as separate grids.

    ``devices`` defaults to ``jax.devices()``; the flattened C*S axis is
    sharded over the largest device count dividing it.

    ``chunk_size`` (a divisor of C*S; ``sweep.fit_chunk`` picks one)
    caps how many grid elements are *live* per stream step: the flat
    axis is reshaped to (C*S / chunk_size, chunk_size) and scanned
    chunk-by-chunk inside the same compiled program, shrinking the
    per-step working set so wide grids stop spilling the last-level
    cache (DESIGN.md §11). Results are bit-identical to the unchunked
    fabric. ``None`` (default) takes ``default_chunk``'s: on one TPU the
    largest divisor whose state carry fits on chip, elsewhere (and
    where the whole grid fits) the whole grid live.

    ``tenant_tables`` + ``tenant_ids`` put the tenant plane on the grid
    (DESIGN.md §15): tables with (T,) shared, (C, T) per-condition or
    (C*S, T) pre-flattened leaves, ids shaped (L,) shared, (S, L)
    per-seed or (C*S, L) per-element — so a (tenants x budgets x seeds)
    grid fuses into this one compiled sharded call. Requires
    ``batch_size`` (tenant routing is a batched-data-plane feature).
    """
    with jax.profiler.TraceAnnotation("sweep.run_grid"):
        budgets, seeds = _check_grid_args(budgets, seeds)
        if (tenant_tables is None) != (tenant_ids is None):
            raise ValueError("pass tenant_tables and tenant_ids together")
        if tenant_tables is not None and not batch_size:
            raise ValueError(
                "tenant grids need batch_size: tenant routing is a batched-"
                "data-plane feature (DESIGN.md §15)")
        budgets, seeds, flat_b, flat_s = _flatten_grid(budgets, seeds)
        C, S = len(budgets), len(seeds)
        # Deliberate host->device staging: stream tensors and the stacked
        # state grid are built eagerly once per call. Annotating it keeps
        # jax.transfer_guard("disallow") usable around the compiled
        # dispatch below, where an implicit transfer would be a real bug.
        with jax.transfer_guard("allow"):
            with jax.profiler.TraceAnnotation("sweep.streams") as span:
                sent = 0

                def put(a):
                    nonlocal sent
                    sent += _h2d_nbytes(a)
                    return jnp.asarray(a)

                xs, rmat, cmat, stream_axes, env0 = \
                    evaluate.build_run_streams(cfg, env, seeds, shuffle, put)
                span.set_metadata(h2d_bytes=sent)
            with jax.profiler.TraceAnnotation("sweep.states"):
                states = evaluate.make_states(
                    cfg, env0, flat_b, flat_s,
                    priors=priors, n_eff=_per_condition_axis(n_eff, C, S),
                    pacer_enabled=pacer_enabled,
                    hyper=_expand_hyper(hyper, C, S),
                    tenants=_expand_tenants(tenant_tables, C, S),
                )
                extras = ()
                if tenant_ids is not None:
                    tids = np.asarray(tenant_ids, np.int32)
                    if tids.ndim == 1:
                        tids = np.broadcast_to(tids, (C * S,) + tids.shape)
                    elif tids.ndim == 2 and tids.shape[0] == S and S != C * S:
                        tids = np.broadcast_to(
                            tids[None], (C,) + tids.shape).reshape(C * S, -1)
                    elif not (tids.ndim == 2 and tids.shape[0] == C * S):
                        raise ValueError(
                            f"tenant_ids must be (L,) shared, ({S}, L) "
                            f"per-seed or ({C * S}, L) per-element; got "
                            f"shape {tids.shape}")
                    # Sent by _shard_grid, straight to each device's shard.
                    extras = (np.ascontiguousarray(tids),)
            states, streams, _, extras = _shard_grid(
                states, (xs, rmat, cmat), stream_axes, C, devices,
                extras=extras)

        def program(n_chunks):
            cached = (_cached_grid_fn if tenant_ids is None
                      else _cached_grid_fn_tenants)
            return cached(cfg.statics, stream_axes, batch_size, n_chunks)

        finals, (arms, r, c, lam) = _launch_and_read(
            program, (states, *streams, *extras), C, S, chunk_size)
        res = GridResult(budgets=budgets, seeds=seeds, arms=arms, rewards=r,
                         costs=c, lams=lam)
    if return_states:
        return res, finals
    return res


def _launch_and_read(program, operands, C: int, S: int,
                     chunk_size: Optional[int] = None):
    """Run a compiled grid program and read its per-step traces back as
    (C, S, T) host arrays: ``sweep.launch`` (the chunk, from
    ``chunk_size`` or ``grid_chunk``'s default for the placed states and
    recorded as its ``chunk`` argument; ``program(n_chunks)``, which
    looks the cached program up; its dispatch), ``sweep.wait`` (the
    device running it) and ``sweep.readback`` (the copies, with their
    ``d2h_bytes``). Returns (final states, (arms, rewards, costs,
    lams))."""
    with jax.profiler.TraceAnnotation("sweep.launch") as span:
        n_chunks = _n_chunks(C * S, grid_chunk(operands[0], chunk_size))
        span.set_metadata(chunk=C * S // n_chunks)
        finals, outs = program(n_chunks)(*operands)
    with jax.profiler.TraceAnnotation("sweep.wait"):
        jax.block_until_ready(outs)
    with jax.profiler.TraceAnnotation("sweep.readback") as span:
        host = tuple(np.asarray(o).reshape(C, S, -1) for o in outs)
        span.set_metadata(d2h_bytes=sum(_d2h_nbytes(o) for o in outs))
    return finals, host


# ---------------------------------------------------------------------------
# Scenario grids: (budget x seed) over one ScenarioSpec
# ---------------------------------------------------------------------------

_SCEN_CACHE: collections.OrderedDict = collections.OrderedDict()
_SCEN_CACHE_MAX = 64


def _expand_params(params, C: int, S: int):
    """Stack param leaves onto the flattened condition-major (C*S,)
    axis: (C,)-leading leaves repeat each entry S times (like budgets),
    already-flat (C*S,)-leading leaves pass through, everything else
    broadcasts to all grid elements. The stacks stay in host memory
    until ``_shard_grid`` sends each device its shard."""
    def ex(leaf):
        a = np.asarray(leaf, np.float32)
        if a.ndim and a.shape[0] == C * S:
            return a
        if a.ndim and a.shape[0] == C and C != C * S:
            return np.repeat(a, S, axis=0)
        return np.ascontiguousarray(np.broadcast_to(a, (C * S,) + a.shape))

    return jax.tree.map(ex, params)


def _cached_scenario_grid_fn(
    cfg: RouterConfig,
    spec: "scenario_lib.ScenarioSpec",
    env: Environment,
    batch_size,
    n_chunks: int = 1,
):
    """Fabric program around the scenario engine's segmented-scan body,
    cached like ``scenario.compiled_runner`` (statics, payload-masked
    spec structure, rate card, batch size, chunking) — budgets, seeds,
    hyper-parameters and payload values stay data."""
    key = (cfg.statics, scenario_lib.runner_spec_key(spec),
           scenario_lib._env_sig(env), batch_size, n_chunks)

    def make():
        return _grid_program(
            "scenario_grid_program",
            scenario_lib.spec_body(cfg, spec, env, batch_size), 0,
            n_chunks, (True,) * 4)

    return scenario_lib.lru_get(_SCEN_CACHE, key, make, _SCEN_CACHE_MAX)


def _cached_timeline_grid_fn(
    cfg: RouterConfig,
    spec: "scenario_lib.ScenarioSpec",
    env: Environment,
    batch_size,
    n_chunks: int = 1,
):
    """Fabric program around the masked timeline scan
    (``scenario.timeline_body``): event times and horizons are two more
    per-element operands, so every timeline assignment — every Monte
    Carlo draw — re-enters ONE compiled, device-sharded program."""
    key = (cfg.statics, scenario_lib.runner_spec_key(spec, mask_times=True),
           scenario_lib._env_sig(env), batch_size, n_chunks)

    def make():
        return _grid_program(
            "timeline_grid_program",
            scenario_lib.timeline_body(cfg, spec, env, batch_size), 0,
            n_chunks, (True,) * 6)

    return scenario_lib.lru_get(_SCEN_CACHE, key, make, _SCEN_CACHE_MAX)


def _normalize_timelines(timelines, C: int, S: int):
    """One shared Timeline, a (C,) per-condition sequence, or a (C*S,)
    per-element sequence -> (tuple of timelines, per_condition flag)."""
    if isinstance(timelines, scenario_lib.Timeline):
        return (timelines,) * C, True
    tls = tuple(timelines)
    for tl in tls:
        if not isinstance(tl, scenario_lib.Timeline):
            raise ValueError(f"timelines entries must be Timeline, got "
                             f"{type(tl).__name__}")
    if len(tls) == C:
        return tls, True
    if len(tls) == C * S:
        return tls, False
    raise ValueError(
        f"timelines must be one Timeline, ({C},) per condition or "
        f"({C * S},) per element; got {len(tls)}")


def _timeline_grid_operands(cfg, spec, env, tls, per_cond, seeds, flat_s,
                            params, batch_size):
    """Host-side lowering of a timeline axis: per-timeline retimed specs
    (validated), padded stream stacks concatenated along the flat grid
    axis, and the (N, E) / (N,) traced timing operands."""
    t_max, E = spec.horizon, len(spec.events)
    rspecs = [scenario_lib.retime(spec, tl) for tl in tls]
    for r_ in rspecs:
        scenario_lib.validate_timeline_alignment(r_, batch_size, t_max)
    # Batched cross-timeline rebuild (one rng draw per seed + one gather
    # per block; falls back internally to the per-timeline loop for
    # replay/permutation/mix/per-segment-seed specs). Bit-identical to
    # concatenating per-timeline build_streams calls.
    if per_cond:
        seed_groups = [tuple(int(s) for s in seeds)] * len(rspecs)
        rep = len(seeds)
    else:
        seed_groups = [(int(flat_s[i]),) for i in range(len(rspecs))]
        rep = 1
    streams = scenario_lib.build_timeline_streams(
        cfg, spec, env, rspecs, seed_groups, params=params, pad_to=t_max)
    ev = np.repeat(
        np.asarray([[e.t for e in r_.events] for r_ in rspecs],
                   np.int32).reshape(len(rspecs), E), rep, axis=0)
    hz = np.repeat(
        np.asarray([r_.horizon for r_ in rspecs], np.int32), rep)
    return rspecs, streams, ev, hz


def run_scenario_grid(
    cfg: RouterConfig,
    spec: "scenario_lib.ScenarioSpec",
    env: Environment,
    budgets: Sequence[float],
    seeds: Sequence[int] = tuple(range(20)),
    *,
    priors: Optional[Sequence[ArmPrior | None]] = None,
    n_eff: float | Sequence[float] = 0.0,
    pacer_enabled: bool = True,
    batch_size: Optional[int] = None,
    devices=None,
    return_states: bool = False,
    hyper: Optional[HyperParams] = None,
    scenario_params: Optional["scenario_lib.ScenarioParams"] = None,
    chunk_size: Optional[int] = None,
    timelines=None,
):
    """One multi-event scenario across a budget grid as one compiled,
    sharded call — per condition equivalent to ``evaluate.run_scenario``
    at that budget (same streams, same edits, same segment bounds).

    A ``BudgetChange`` event in the spec overrides the stacked initial
    ceiling from its boundary onward, in every condition — the grid axis
    is the *initial* operating point.

    ``hyper`` leaves and ``n_eff`` stack on the condition axis as in
    ``run_grid``: scalars, ``(C,)`` or ``(C*S,)`` stacks.

    ``scenario_params`` resolves ``Param`` payload references in the
    spec (DESIGN.md §10): leaves may be scalars (shared), ``(C,)``
    stacks aligned with ``budgets`` (a *payload* condition axis — the
    way a whole spec family, e.g. price cuts at several magnitudes,
    fuses into this one compiled grid), or pre-flattened ``(C*S,)``
    stacks.

    ``chunk_size`` scans the flattened grid chunk-by-chunk inside the
    compiled program exactly as in ``run_grid`` (bit-identical results,
    bounded per-step working set), with the same on-chip default.

    ``timelines`` puts the spec's event *times* and effective horizon on
    the condition axis (DESIGN.md §12): one shared
    ``scenario.Timeline``, a ``(C,)`` per-condition sequence, or a
    ``(C*S,)`` per-element sequence. The grid then runs through the
    masked timeline fabric — every element bit-identical to
    ``evaluate.run_scenario`` on its concrete retimed spec, every
    timeline assignment re-entering ONE compiled program (the scenario
    Monte Carlo substrate). Per-condition timelines record effective
    ``cond_bounds``/``horizons`` on the result so ``condition(i)`` trims
    padding; composes with ``hyper``/``n_eff``/``scenario_params``/
    ``chunk_size`` and both data planes unchanged.
    """
    with jax.profiler.TraceAnnotation("sweep.run_scenario_grid"):
        budgets, seeds = _check_grid_args(budgets, seeds)
        budgets, seeds, flat_b, flat_s = _flatten_grid(budgets, seeds)
        C, S = len(budgets), len(seeds)
        cond_bounds = horizons = None
        with jax.profiler.TraceAnnotation("sweep.streams"):
            params = scenario_lib.resolve_params(spec, scenario_params)
            full = params.updated(**scenario_lib.auto_param_values(spec))
            if timelines is None:
                stacks = scenario_lib.build_streams(
                    cfg, spec, env, seeds, params=params)
                extras = ()
            else:
                tls, per_cond = _normalize_timelines(timelines, C, S)
                rspecs, stacks, ev, hz = _timeline_grid_operands(
                    cfg, spec, env, tls, per_cond, seeds, flat_s, params,
                    batch_size)
                extras = (ev, hz)
        with jax.profiler.TraceAnnotation("sweep.states"):
            states = evaluate.make_states(
                cfg, env, flat_b, flat_s,
                priors=priors, n_eff=_per_condition_axis(n_eff, C, S),
                pacer_enabled=pacer_enabled,
                active_arms=spec.init_active,
                hyper=_expand_hyper(hyper, C, S),
            )
            pstack = _expand_params(full, C, S)
        states, streams, pstack, extras = _shard_grid(
            states, stacks, 0, C, devices, pstack, extras=extras)

        def program(n_chunks):
            cached = (_cached_scenario_grid_fn if timelines is None
                      else _cached_timeline_grid_fn)
            return cached(cfg, spec, env, batch_size, n_chunks)

        finals, (arms, r, c, lam) = _launch_and_read(
            program, (states, *streams, pstack, *extras), C, S, chunk_size)
        if timelines is None:
            bounds = spec.bounds
        else:
            bounds = None
            if per_cond:
                cond_bounds = tuple(r_.bounds for r_ in rspecs)
                horizons = tuple(r_.horizon for r_ in rspecs)
        cond_params = {
            n: np.asarray(params.get(n))
            for n in params.names
            if np.ndim(params.get(n)) and np.shape(params.get(n))[0] == C
        } or None
        res = GridResult(
            budgets=budgets, seeds=seeds, arms=arms, rewards=r, costs=c,
            lams=lam, bounds=bounds, params=cond_params,
            cond_bounds=cond_bounds, horizons=horizons,
        )
    if return_states:
        return res, finals
    return res
