"""Jit'd mLSTM scan: Pallas intra-chunk kernel + JAX stabilised cross-chunk
recurrence and combine."""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.mlstm_scan.kernel import mlstm_chunk_pallas


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def mlstm_scan(q, k, v, i_gate, f_gate, *, chunk: int = 256,
               interpret: bool | None = None):
    """q,k,v: (b,s,h,p); i_gate,f_gate: (b,s,h) raw logits -> (b,s,h,p)."""
    if interpret is None:
        interpret = interpret_mode()
    b, s, h, p = q.shape
    scale = 1.0 / math.sqrt(p)
    lf = jax.nn.log_sigmoid(f_gate.astype(jnp.float32))
    li = i_gate.astype(jnp.float32)

    qq = min(chunk, s)
    nc = -(-s // qq)
    pad = nc * qq - s
    if pad:
        pad4 = ((0, 0), (0, pad), (0, 0), (0, 0))
        q, k, v = (jnp.pad(t, pad4) for t in (q, k, v))
        lf = jnp.pad(lf, ((0, 0), (0, pad), (0, 0)))
        li = jnp.pad(li, ((0, 0), (0, pad), (0, 0)), constant_values=-1e30)

    r5 = lambda t: t.reshape(b, nc, qq, h, p).astype(jnp.float32)
    r4 = lambda t: t.reshape(b, nc, qq, h)
    y_i, n_i, m_i, states, norms, chunk_lf, m_state = mlstm_chunk_pallas(
        r5(q), r5(k), r5(v), r4(li), r4(lf), sm_scale=scale,
        interpret=interpret)

    # ---- cross-chunk stabilised recurrence --------------------------------
    def step(carry, inp):
        C, n, m = carry
        st, nr, clf, mst = inp
        m_new = jnp.maximum(m + clf, mst)
        alpha = jnp.exp(m + clf - m_new)
        beta = jnp.exp(mst - m_new)
        C_new = C * alpha[..., None, None] + st * beta[..., None, None]
        n_new = n * alpha[..., None] + nr * beta[..., None]
        return (C_new, n_new, m_new), (C, n, m)          # emit previous

    C0 = jnp.zeros((b, h, p, p), jnp.float32)
    n0 = jnp.zeros((b, h, p), jnp.float32)
    m0 = jnp.full((b, h), -1e30, jnp.float32)
    _, (C_prev, n_prev, m_prev) = jax.lax.scan(
        step, (C0, n0, m0),
        (states.transpose(1, 0, 2, 3, 4), norms.transpose(1, 0, 2, 3),
         chunk_lf.transpose(1, 0, 2), m_state.transpose(1, 0, 2)))
    C_prev = C_prev.transpose(1, 0, 2, 3, 4)
    n_prev = n_prev.transpose(1, 0, 2, 3)
    m_prev = m_prev.transpose(1, 0, 2)

    # ---- combine intra + inter --------------------------------------------
    lf_cum = jnp.cumsum(r4(lf), axis=2)
    inter_decay = lf_cum + m_prev[:, :, None, :]         # (b,nc,q,h)
    m_total = jnp.maximum(m_i, inter_decay)
    w_intra = jnp.exp(m_i - m_total)
    w_inter = jnp.exp(inter_decay - m_total)

    qs = r5(q) * scale
    y_inter = jnp.einsum("bcqhp,bchpr->bcqhr",
                         qs * w_inter[..., None], C_prev)
    n_inter = jnp.einsum("bcqhp,bchp->bcqh",
                         qs * w_inter[..., None], n_prev)
    num = y_i * w_intra[..., None] + y_inter
    den = jnp.maximum(jnp.abs(n_i * w_intra + n_inter), jnp.exp(-m_total))
    y = num / den[..., None]
    return y.reshape(b, nc * qq, h, p)[:, :s]
