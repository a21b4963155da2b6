"""Pallas TPU kernel: SSD (mamba2) intra-chunk scan.

One grid step handles one (batch, chunk, head) tile and computes, entirely
in VMEM:

    dA      = dt * (-exp(A_log))                      (Q,1)
    dA_cum  = cumsum(dA)                              (Q,1)
    L[i,j]  = exp(dA_cum[i] - dA_cum[j]) . tril       (Q,Q)
    y_diag  = ((C B^T) * L * dt_j) @ X                (Q,P)   [MXU]
    decay_e = exp(dA_cum[Q-1] - dA_cum)               (Q,1)
    state   = B^T @ (dt * decay_e * X)                (N,P)   [MXU]
    clf     = dA_cum[Q-1]                             (1,1)

The inter-chunk recurrence (sequential over S/Q chunk states) stays in JAX
— it is O(S/Q) tiny fused multiply-adds and does not benefit from a kernel.

VMEM working set at Q=256, N=64, P=64 fp32: X/B/C/dt ~ 0.3 MiB, the (Q,Q)
decay/score tiles 0.5 MiB — comfortably inside VMEM with double buffering.
Q is a multiple of the 128-lane VREG / MXU tiling.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _ssd_chunk_kernel(x_ref, dt_ref, alog_ref, b_ref, c_ref,
                      y_ref, st_ref, clf_ref):
    x = x_ref[0, 0, 0].astype(jnp.float32)             # (Q, P)
    dt = dt_ref[0, 0, 0].astype(jnp.float32)           # (Q, 1)
    B = b_ref[0, 0].astype(jnp.float32)                # (Q, N)
    C = c_ref[0, 0].astype(jnp.float32)                # (Q, N)
    a = -jnp.exp(alog_ref[0].astype(jnp.float32))      # (1, 1)

    dA = dt * a                                        # (Q, 1)
    q = x.shape[0]
    tri = (jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
           >= jax.lax.broadcasted_iota(jnp.int32, (q, q), 1))
    # cumsum as a masked row sum (Mosaic has no cumsum lowering)
    dA_cum = jnp.sum(jnp.where(tri, dA.reshape(1, q), 0.0), axis=1,
                     keepdims=True)                    # (Q, 1)

    seg = dA_cum - dA_cum.reshape(1, q)                # (Q, Q): cum_i - cum_j
    L = jnp.where(tri, jnp.exp(seg), 0.0)

    scores = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # (Q,Q)
    w = scores * L * dt.reshape(1, q)                  # weight for column j
    y = jax.lax.dot_general(w, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)       # (Q,P)
    y_ref[0, 0, 0] = y.astype(y_ref.dtype)

    decay_e = jnp.exp(dA_cum[q - 1] - dA_cum)          # (Q,1)
    xw = x * (dt * decay_e)                            # (Q,P)
    st = jax.lax.dot_general(B, xw, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)      # (N,P)
    st_ref[0, 0] = st.astype(st_ref.dtype)
    clf_ref[0, 0] = dA_cum[q - 1].reshape(1, 1).astype(clf_ref.dtype)


def ssd_chunk_pallas(x, dt, A_log, B, C, *, interpret: bool = False):
    """Intra-chunk SSD.

    x:  (b, nc, Q, h, p)   chunked per-head inputs
    dt: (b, nc, Q, h)
    A_log: (h,)
    B, C: (b, nc, Q, n)
    returns (y_diag: (b,nc,Q,h,p), states: (b,nc,h,n,p), chunk_lf: (b,nc,h))
    """
    b, nc, q, h, p = x.shape
    n = B.shape[-1]
    # head-major tiles: the TPU lowering needs each block's last two dims
    # to be (8, 128)-aligned or whole, so the head index must not sit in
    # them — x as (b,nc,h,Q,P), dt as (b,nc,h,Q,1)
    xh = x.transpose(0, 1, 3, 2, 4)
    dth = dt.transpose(0, 1, 3, 2)[..., None]
    al = A_log.reshape(h, 1, 1)

    grid = (b, nc, h)
    y, st, clf = pl.pallas_call(
        _ssd_chunk_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, q, p), lambda bi, ci, hi: (bi, ci, hi, 0, 0)),
            pl.BlockSpec((1, 1, 1, q, 1), lambda bi, ci, hi: (bi, ci, hi, 0, 0)),
            pl.BlockSpec((1, 1, 1), lambda bi, ci, hi: (hi, 0, 0)),
            pl.BlockSpec((1, 1, q, n), lambda bi, ci, hi: (bi, ci, 0, 0)),
            pl.BlockSpec((1, 1, q, n), lambda bi, ci, hi: (bi, ci, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, q, p), lambda bi, ci, hi: (bi, ci, hi, 0, 0)),
            pl.BlockSpec((1, 1, n, p), lambda bi, ci, hi: (bi, ci * h + hi, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1),
                         lambda bi, ci, hi: (bi, ci * h + hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, nc, h, q, p), jnp.float32),
            jax.ShapeDtypeStruct((b, nc * h, n, p), jnp.float32),
            jax.ShapeDtypeStruct((b, nc * h, 1, 1), jnp.float32),
        ],
        interpret=interpret,
    )(xh, dth, al, B, C)
    y = y.transpose(0, 1, 3, 2, 4)
    states = st.reshape(b, nc, h, n, p)
    chunk_lf = clf.reshape(b, nc, h)
    return y, states, chunk_lf
