"""Fused Pallas TPU megakernel for the full ParetoBandit step-batch body.

The serving hot path runs score -> hard-ceiling select -> chosen-arm
gamma-decay + Sherman-Morrison rank-1 inverse update + b/theta refresh +
primal-dual pacer step. Before this kernel only the *scoring* phase ran
as a Pallas kernel (``kernels/linucb_score``); the update phases were
separate XLA ops that round-tripped every arm's (d x d) statistics
through HBM once per phase. Here the entire per-block bandit body
executes in ONE ``pallas_call`` with all K arms' ``(A, A_inv, b,
theta)`` resident in VMEM (K<=8, d<=128 -> ~1.1 MB f32 worst case, far
under the ~16 MB/core budget) and ``input_output_aliases`` on the five
stats buffers, so the statistics are read from HBM once and written back
once per request block.

Phases inside the kernel:

  1. *Score*  — Eq. 2 for all (Bp, K) pairs, reusing the
     ``linucb_score`` blocking idiom verbatim (per-arm ``dot_general``
     on the VMEM-resident inverse, ``(t * x).sum`` quadratic form) so
     interpret-mode scores are bit-identical to the score kernel's.
  2. *Select* — add the pre-drawn tiebreak noise, mask to the pacer's
     hard-ceiling candidate set, argmax, then apply the
     forced-exploration override mask (both computed outside: they need
     the PRNG chain and force counters, which are bookkeeping, not
     statistics).
  3. *Update* — a ``fori_loop`` over the ``num_valid`` real requests
     (trailing rows are block padding and never enter): dynamic-indexed
     decay of the chosen arm's ``A``/``A_inv``/``b`` slabs in place,
     Sherman-Morrison on the inverse, reward accumulation, and the
     non-associative pacer fold (EMA cost + clipped dual ascent) carried
     through the same loop.
  4. *Refresh* — ``theta_a = A_inv_a b_a`` recomputed once per arm at
     the end. Only the block-final theta is observable downstream
     (theta is read exclusively by scoring), so K small matvecs replace
     ``num_valid`` per-request ones; for untouched arms the recompute
     reproduces the stored solution (same operands, same op).

Hyper-parameters ride as scalar *operands* — a (1, 8) f32 row
[alpha, gamma, eta, alpha_ema, lambda_bar, 0, 0, 0] — exactly like the
score kernel's alpha (DESIGN.md §9), so one compiled kernel serves every
operating point, including a stacked (alpha, gamma) grid under the sweep
fabric's flattened (condition x seed) vmap axis.

``ref.py`` holds the op-for-op jnp mirror (the bitwise interpret-mode
oracle); ``ops.py`` the padding/packing wrapper the backend calls.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Mirrors repro.core.linucb.GAMMA_FLOOR. Importing it would cycle
# (core.__init__ -> router -> backend -> this package), and it must
# be a Python float here anyway: pallas_call rejects captured array
# constants.
GAMMA_FLOOR = 1e-6

# Python float, not a jnp scalar: a module-level array would be captured
# as a kernel constant, which pallas_call rejects. Weak-typed against the
# f32 scores it lands on the same f32 value as router.NEG_INF.
NEG_INF = -1e30

# hypf operand layout (1, 8): one lane-friendly f32 row of hyper scalars.
HYP_ALPHA, HYP_GAMMA, HYP_ETA, HYP_AEMA, HYP_LBAR = range(5)

# Mosaic runs an unannotated f32 dot below f32 precision. On a TPU v5e
# that left theta 4.2e-3 from the jnp oracle after the 1,824-prompt
# stream (the contract is 1e-4); Sherman-Morrison compounds the error.
HIGHEST = jax.lax.Precision.HIGHEST


def _step_kernel(
    # -- stats (aliased in/out: read once, written once) ----------------
    a_ref,       # (K, d, d) design matrices
    ainv_ref,    # (K, d, d) cached inverses
    b_ref,       # (K, d)    reward accumulators
    theta_ref,   # (K, d)    ridge solutions
    lu_ref,      # (1, K) i32 last statistics-update step (SMEM)
    # -- per-request block ----------------------------------------------
    x_ref,       # (Bp, d)  contexts
    rew_ref,     # (Bp, K)  environment reward matrix
    cost_ref,    # (Bp, K)  environment cost matrix
    noise_ref,   # (Bp, K)  pre-drawn tiebreak noise (PRNG chain outside)
    forced_ref,  # (Bp, 1) i32 forced-exploration override mask
    # -- per-block scalars/vectors --------------------------------------
    cand_ref,    # (1, K) f32 hard-ceiling candidate mask (0/1)
    pen_ref,     # (1, K) (lambda_c + lam) * c_tilde
    infl_ref,    # (1, K) max(gamma^dt, 1/V_max) at block entry
    hyp_ref,     # (1, 8) f32 [alpha, gamma, eta, alpha_ema, lambda_bar, ...]
    int_ref,     # (1, 2) i32 [t_sel, force_arm]
    pacer_ref,   # (1, 4) f32 [lam, c_ema, budget, 0]
    # -- outputs ---------------------------------------------------------
    oa_ref, oainv_ref, ob_ref, otheta_ref,
    olu_ref,     # (1, K) i32 (SMEM)
    oarm_ref,    # (Bp, 1) i32 chosen arm per request
    orc_ref,     # (Bp, 2) f32 realised (reward, cost) per request
    opacer_ref,  # (1, 4) f32 [lam', c_ema', budget, 0]
    *, num_arms: int, num_valid: int, dt_max: int,
):
    # Phase 1 — score (the linucb_score idiom, arms resident in VMEM).
    x = x_ref[...].astype(jnp.float32)                     # (Bp, d)
    theta = theta_ref[...].astype(jnp.float32)             # (K, d)
    alpha = hyp_ref[0, HYP_ALPHA].astype(jnp.float32)
    exploit = jax.lax.dot_general(
        x, theta, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32, precision=HIGHEST,
    )                                                      # (Bp, K)
    cols = []
    for a in range(num_arms):                              # K static, small
        t = jax.lax.dot_general(
            x, ainv_ref[a].astype(jnp.float32),
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
            precision=HIGHEST,
        )                                                  # (Bp, d)
        q = jnp.maximum((t * x).sum(axis=1), 0.0)          # (Bp,)
        cols.append(q)
    quad = jnp.stack(cols, axis=1)                         # (Bp, K)
    v = quad / infl_ref[0][None, :]
    scores = exploit + alpha * jnp.sqrt(v) - pen_ref[0][None, :]

    # Phase 2 — select: noise + hard ceiling + forced-exploration mask.
    masked = jnp.where(cand_ref[0][None, :] > 0.0,
                       scores + noise_ref[...], NEG_INF)
    arms = jnp.argmax(masked, axis=1).astype(jnp.int32)    # (Bp,)
    farm = int_ref[0, 1]
    arms = jnp.where(forced_ref[..., 0] > 0, farm, arms)
    oarm_ref[...] = arms[:, None]

    # Bandit feedback gather as a one-hot contraction (TPU-friendly; the
    # sum over K-1 exact zeros reproduces rewards[i, arms[i]] bit-for-bit).
    onehot = (jax.lax.broadcasted_iota(jnp.int32, masked.shape, 1)
              == arms[:, None]).astype(jnp.float32)
    r_all = (rew_ref[...].astype(jnp.float32) * onehot).sum(axis=1)
    c_all = (cost_ref[...].astype(jnp.float32) * onehot).sum(axis=1)
    orc_ref[...] = jnp.stack([r_all, c_all], axis=1)

    # Phase 3 — chosen-arm decay + Sherman-Morrison + pacer fold, all in
    # VMEM. ``t_sel`` is the post-select clock (t + B): the oracle's
    # update_batch runs after select advanced t, and a same-arm second
    # update inside the block sees dt = 0 exactly as the sequential fold.
    #
    # Mosaic indexes refs, not values, by a traced scalar: row i's arm is
    # read back from ``oarm_ref`` (a scalar load), its context and
    # (reward, cost) are (1, .) row loads at a dynamic sublane offset, and
    # the chosen arm's slabs are dynamic leading-axis indexes. The clock
    # ``last_upd`` lives in SMEM, the only space that takes a scalar
    # store. Everything arithmetic stays on (1, 1)/(1, d)/(d, d) vectors.
    oa_ref[...] = a_ref[...]
    oainv_ref[...] = ainv_ref[...]
    ob_ref[...] = b_ref[...]
    for a in range(num_arms):
        olu_ref[0, a] = lu_ref[0, a]
    t_sel = int_ref[0, 0]
    gamma = jnp.clip(hyp_ref[:, HYP_GAMMA:HYP_GAMMA + 1].astype(jnp.float32),
                     GAMMA_FLOOR, 1.0)                     # (1, 1)
    eta = hyp_ref[:, HYP_ETA:HYP_ETA + 1].astype(jnp.float32)
    a_ema = hyp_ref[:, HYP_AEMA:HYP_AEMA + 1].astype(jnp.float32)
    lambda_bar = hyp_ref[:, HYP_LBAR:HYP_LBAR + 1].astype(jnp.float32)
    budget = pacer_ref[:, 2:3].astype(jnp.float32)

    def body(i, pc):
        lam, c_ema = pc                                    # (1, 1) each
        arm = oarm_ref[i, 0]
        xi = x_ref[pl.ds(i, 1), :].astype(jnp.float32)     # (1, d)
        rc = orc_ref[pl.ds(i, 1), :]                       # (1, 2)
        dt = jnp.clip(t_sel - olu_ref[0, arm], 0, dt_max)
        g = jnp.power(gamma, jnp.full((1, 1), dt, jnp.int32)
                      .astype(jnp.float32))                # (1, 1)
        A_a = oa_ref[arm] * g + xi.T * xi
        Ainv_a = oainv_ref[arm] / g
        Ax = jax.lax.dot_general(
            xi, Ainv_a, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=HIGHEST,
        )                                                  # (A^-1 x)^T
        denom = 1.0 + (xi * Ax).sum(axis=1, keepdims=True)
        Ainv_a = Ainv_a - (Ax.T * Ax) / denom
        b_a = ob_ref[pl.ds(arm, 1), :] * g + rc[:, 0:1] * xi
        oa_ref[arm] = A_a
        oainv_ref[arm] = Ainv_a
        ob_ref[pl.ds(arm, 1), :] = b_a
        olu_ref[0, arm] = t_sel
        c_ema = (1.0 - a_ema) * c_ema + a_ema * rc[:, 1:2]  # Eq. 3
        lam = jnp.clip(lam + eta * (c_ema / budget - 1.0),  # Eq. 4
                       0.0, lambda_bar)
        return lam, c_ema

    lam, c_ema = jax.lax.fori_loop(
        0, num_valid, body,
        (pacer_ref[:, 0:1].astype(jnp.float32),
         pacer_ref[:, 1:2].astype(jnp.float32)))
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 4), 1)
    opacer_ref[...] = jnp.where(
        lane == 0, lam, jnp.where(lane == 1, c_ema,
                                  jnp.where(lane == 2, budget, 0.0)))

    # Phase 4 — block-final theta refresh for every arm (K matvecs on the
    # already-updated VMEM statistics instead of num_valid per-request
    # ones; only the final theta is observable by the next score phase).
    for a in range(num_arms):
        otheta_ref[a:a + 1, :] = jax.lax.dot_general(
            ob_ref[a:a + 1, :], oainv_ref[a], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32, precision=HIGHEST,
        )                                                  # (A^-1 b)^T


def linucb_step_blocked(
    A: jax.Array,       # (K, d, d)
    A_inv: jax.Array,   # (K, d, d)
    b: jax.Array,       # (K, d)
    theta: jax.Array,   # (K, d)
    last_upd: jax.Array,  # (1, K) i32
    x: jax.Array,       # (Bp, d)
    rewards: jax.Array,  # (Bp, K)
    costs: jax.Array,   # (Bp, K)
    noise: jax.Array,   # (Bp, K)
    forced: jax.Array,  # (Bp, 1) i32
    cand: jax.Array,    # (1, K) f32
    pen: jax.Array,     # (1, K)
    infl: jax.Array,    # (1, K)
    hypf: jax.Array,    # (1, 8) f32
    ints: jax.Array,    # (1, 2) i32
    pacer: jax.Array,   # (1, 4) f32
    *,
    num_valid: int,
    dt_max: int,
    interpret: bool = False,
):
    """One fused step-batch ``pallas_call``. All shapes pre-padded by
    ``ops.linucb_step``; ``num_valid`` <= Bp is the real request count
    (a trace-time constant — the update loop never touches pad rows).

    Returns (A', A_inv', b', theta', last_upd', arms (Bp,1) i32,
    rc (Bp,2) f32, pacer' (1,4) f32) with the five stats outputs aliased
    onto their inputs (the VMEM-residency contract: one read + one write
    of the statistics per block, never a double materialization).
    """
    K, d = b.shape
    Bp = x.shape[0]
    assert 0 <= num_valid <= Bp, (num_valid, Bp)
    kernel = functools.partial(
        _step_kernel, num_arms=K, num_valid=num_valid, dt_max=dt_max)
    out_shape = (
        jax.ShapeDtypeStruct((K, d, d), jnp.float32),   # A
        jax.ShapeDtypeStruct((K, d, d), jnp.float32),   # A_inv
        jax.ShapeDtypeStruct((K, d), jnp.float32),      # b
        jax.ShapeDtypeStruct((K, d), jnp.float32),      # theta
        jax.ShapeDtypeStruct((1, K), jnp.int32),        # last_upd
        jax.ShapeDtypeStruct((Bp, 1), jnp.int32),       # arms
        jax.ShapeDtypeStruct((Bp, 2), jnp.float32),     # (reward, cost)
        jax.ShapeDtypeStruct((1, 4), jnp.float32),      # pacer
    )
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        kernel,
        out_shape=out_shape,
        in_specs=[vmem] * 4 + [smem] + [vmem] * 11,
        out_specs=[vmem] * 4 + [smem] + [vmem] * 3,
        input_output_aliases={0: 0, 1: 1, 2: 2, 3: 3, 4: 4},
        interpret=interpret,
    )(A, A_inv, b, theta, last_upd, x, rewards, costs, noise, forced,
      cand, pen, infl, hypf, ints, pacer)
