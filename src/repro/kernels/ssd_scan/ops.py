"""jit'd wrapper for the SSD kernel with the model-facing layout."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.ssd_scan.kernel import ssd_scan_bh


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(
    x, dt, A, B_in, C_in, D_skip, *, chunk: int = 128,
    interpret: bool | None = None,
):
    """Same signature as models.ssm.ssd_chunked (h0=0).

    x (B,L,H,P), dt (B,L,H), A (H,), B_in/C_in (B,L,N), D_skip (H,)
    -> (y (B,L,H,P), h_final (B,H,N,P))
    """
    if interpret is None:
        interpret = interpret_mode()
    Bb, L, H, P = x.shape
    N = B_in.shape[-1]
    xf = x.transpose(0, 2, 1, 3).reshape(Bb * H, L, P)
    dtf = dt.transpose(0, 2, 1).reshape(Bb * H, L)
    dtaf = dtf * jnp.tile(A, Bb)[:, None]  # row b*H+h has head h's A
    bf = jnp.repeat(B_in[:, None], H, axis=1).reshape(Bb * H, L, N)
    cf = jnp.repeat(C_in[:, None], H, axis=1).reshape(Bb * H, L, N)
    # The kernel requires L % chunk == 0. Zero-padding the time axis is
    # exact: padded steps have dta = 0 (decay exp(0) = 1 leaves h alone)
    # and dt = x = 0 (no state contribution), so h_final matches the
    # unpadded scan and the padded y rows are sliced back off.
    chunk = min(chunk, L)
    pad_l = (-L) % chunk
    if pad_l:
        xf = jnp.pad(xf, [(0, 0), (0, pad_l), (0, 0)])
        dtaf = jnp.pad(dtaf, [(0, 0), (0, pad_l)])
        dtf = jnp.pad(dtf, [(0, 0), (0, pad_l)])
        bf = jnp.pad(bf, [(0, 0), (0, pad_l), (0, 0)])
        cf = jnp.pad(cf, [(0, 0), (0, pad_l), (0, 0)])
    y, h = ssd_scan_bh(xf, dtaf, dtf, bf, cf, chunk=chunk,
                       interpret=interpret)
    y = y[:, :L].reshape(Bb, H, L, P).transpose(0, 2, 1, 3)
    y = y + x.astype(y.dtype) * D_skip[None, None, :, None].astype(y.dtype)
    h = h.reshape(Bb, H, N, P)
    return y, h
