"""Jit'd SSD scan: Pallas intra-chunk kernel + JAX inter-chunk recurrence."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.ssm_scan.kernel import ssd_chunk_pallas


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, A_log, B, C, *, chunk: int = 256,
             interpret: bool | None = None):
    """x: (b,s,h,p); dt: (b,s,h); A_log: (h,); B,C: (b,s,n) -> (b,s,h,p)."""
    if interpret is None:
        interpret = interpret_mode()
    b, s, h, p = x.shape
    n = B.shape[-1]
    q = min(chunk, s)
    nc = -(-s // q)
    pad = nc * q - s
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))

    xc = x.reshape(b, nc, q, h, p).astype(jnp.float32)
    dtc = dt.reshape(b, nc, q, h).astype(jnp.float32)
    Bc = B.reshape(b, nc, q, n).astype(jnp.float32)
    Cc = C.reshape(b, nc, q, n).astype(jnp.float32)

    # ---- intra-chunk (Pallas) ------------------------------------------
    y_diag, states, chunk_lf = ssd_chunk_pallas(
        xc, dtc, A_log.astype(jnp.float32), Bc, Cc, interpret=interpret)

    # ---- inter-chunk recurrence (JAX scan over nc states) ---------------
    chunk_decay = jnp.exp(chunk_lf)                       # (b,nc,h)

    def step(carry, inp):
        st, dec = inp
        new = carry * dec[..., None, None] + st
        return new, carry                                  # emit previous

    init = jnp.zeros((b, h, n, p), jnp.float32)
    _, prev = jax.lax.scan(step, init,
                           (states.transpose(1, 0, 2, 3, 4),
                            chunk_decay.transpose(1, 0, 2)))
    prev = prev.transpose(1, 0, 2, 3, 4)                   # (b,nc,h,n,p)

    # ---- inter-chunk contribution --------------------------------------
    dA = dtc * (-jnp.exp(A_log))[None, None, None, :]
    dA_cum = jnp.cumsum(dA, axis=2)
    state_decay = jnp.exp(dA_cum)                          # (b,nc,q,h)
    y_off = jnp.einsum("bcqn,bcqh,bchnp->bcqhp", Cc, state_decay, prev)

    y = (y_diag + y_off).reshape(b, nc * q, h, p)
    return y[:, :s]
