"""Pallas TPU kernel: mLSTM intra-chunk computation (xLSTM matrix memory).

One grid step = one (batch, chunk, head).  Computes in VMEM the
chunk-local quantities the cross-chunk combine needs:

    dmat[i,j] = lf_cum[i] - lf_cum[j] + li[j]   (j<=i)      (Q,Q)
    m_intra   = rowmax(dmat)                                 (Q,1)
    scores    = (q @ k^T) * sm_scale                         (Q,Q)  [MXU]
    y_intra   = (scores * exp(dmat - m_intra)) @ v           (Q,P)  [MXU]
    n_intra   = rowsum(scores * exp(dmat - m_intra))         (Q,1)
    m_state   = max(decay_to_end)                            (1,1)
    state     = k^T @ (exp(decay_to_end - m_state) * v)      (P,P)  [MXU]
    norm      = sum_j exp(decay_to_end - m_state) k_j        (1,P)
    chunk_lf  = lf_cum[Q-1]                                  (1,1)

The sequential cross-chunk recurrence and the stabilised intra/inter
combine stay in JAX (see ops.py) — they are O(S/Q) work.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _mlstm_chunk_kernel(q_ref, k_ref, v_ref, li_ref, lf_ref,
                        y_ref, ni_ref, mi_ref, st_ref, nr_ref,
                        clf_ref, mst_ref, *, sm_scale: float):
    q = q_ref[0, 0, 0].astype(jnp.float32) * sm_scale      # (Q,P)
    k = k_ref[0, 0, 0].astype(jnp.float32)                 # (Q,P)
    v = v_ref[0, 0, 0].astype(jnp.float32)                 # (Q,P)
    li = li_ref[0, 0, 0].astype(jnp.float32)               # (Q,1)
    lf = lf_ref[0, 0, 0].astype(jnp.float32)               # (Q,1)

    qq = q.shape[0]
    tri = (jax.lax.broadcasted_iota(jnp.int32, (qq, qq), 0)
           >= jax.lax.broadcasted_iota(jnp.int32, (qq, qq), 1))
    # cumsum as a masked row sum (Mosaic has no cumsum lowering)
    lf_cum = jnp.sum(jnp.where(tri, lf.reshape(1, qq), 0.0), axis=1,
                     keepdims=True)                        # (Q,1)
    dmat = lf_cum - lf_cum.reshape(1, qq) + li.reshape(1, qq)
    dmat = jnp.where(tri, dmat, -1e30)
    m_intra = jnp.max(dmat, axis=1, keepdims=True)         # (Q,1)
    w = jnp.exp(dmat - m_intra)

    scores = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    sw = scores * w
    y = jax.lax.dot_general(sw, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    y_ref[0, 0, 0] = y.astype(y_ref.dtype)
    ni_ref[0, 0, 0] = jnp.sum(sw, axis=1,
                              keepdims=True).astype(ni_ref.dtype)
    mi_ref[0, 0, 0] = m_intra.astype(mi_ref.dtype)

    decay_end = lf_cum[qq - 1] - lf_cum + li               # (Q,1)
    m_state = jnp.max(decay_end).reshape(1, 1)
    sk = jnp.exp(decay_end - m_state)                      # (Q,1)
    st = jax.lax.dot_general(k, v * sk, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (P,P)
    st_ref[0, 0] = st.astype(st_ref.dtype)
    nr_ref[0, 0] = jnp.sum(k * sk, axis=0,
                           keepdims=True).astype(nr_ref.dtype)
    clf_ref[0, 0] = lf_cum[qq - 1].reshape(1, 1).astype(clf_ref.dtype)
    mst_ref[0, 0] = m_state.astype(mst_ref.dtype)


def mlstm_chunk_pallas(q, k, v, li, lf, *, sm_scale: float,
                       interpret: bool = False):
    """q,k,v: (b,nc,Q,h,p); li,lf: (b,nc,Q,h).

    Returns per-chunk tensors:
      y_intra (b,nc,Q,h,p), n_intra (b,nc,Q,h), m_intra (b,nc,Q,h),
      states (b,nc,h,p,p), norms (b,nc,h,p), chunk_lf (b,nc,h),
      m_state (b,nc,h)
    """
    import functools
    b, nc, qq, h, p = q.shape
    # head-major tiles: the TPU lowering needs each block's last two dims
    # to be (8, 128)-aligned or whole, so the head index must not sit in
    # them — q/k/v as (b,nc,h,Q,P), gates as (b,nc,h,Q,1)
    seq5 = lambda t: t.transpose(0, 1, 3, 2, 4)
    gate5 = lambda t: t.transpose(0, 1, 3, 2)[..., None]
    grid = (b, nc, h)
    kern = functools.partial(_mlstm_chunk_kernel, sm_scale=sm_scale)
    tile = lambda last: pl.BlockSpec((1, 1, 1, qq, last),
                                     lambda bi, ci, hi: (bi, ci, hi, 0, 0))
    per_head = lambda r, c: pl.BlockSpec(
        (1, 1, r, c), lambda bi, ci, hi: (bi, ci * h + hi, 0, 0))
    f32 = jnp.float32
    outs = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[tile(p), tile(p), tile(p), tile(1), tile(1)],
        out_specs=[tile(p), tile(1), tile(1), per_head(p, p),
                   per_head(1, p), per_head(1, 1), per_head(1, 1)],
        out_shape=[
            jax.ShapeDtypeStruct((b, nc, h, qq, p), f32),
            jax.ShapeDtypeStruct((b, nc, h, qq, 1), f32),
            jax.ShapeDtypeStruct((b, nc, h, qq, 1), f32),
            jax.ShapeDtypeStruct((b, nc * h, p, p), f32),
            jax.ShapeDtypeStruct((b, nc * h, 1, p), f32),
            jax.ShapeDtypeStruct((b, nc * h, 1, 1), f32),
            jax.ShapeDtypeStruct((b, nc * h, 1, 1), f32),
        ],
        interpret=interpret,
    )(seq5(q), seq5(k), seq5(v), gate5(li), gate5(lf))
    y, ni, mi, st, nr, clf, mst = outs
    back = lambda t: t[..., 0].transpose(0, 1, 3, 2)
    return (y.transpose(0, 1, 3, 2, 4), back(ni), back(mi),
            st.reshape(b, nc, h, p, p), nr.reshape(b, nc, h, p),
            clf.reshape(b, nc, h), mst.reshape(b, nc, h))
