"""Jit'd public wrapper for the flash-attention kernel.

Differentiable: forward runs the Pallas kernel; backward recomputes via the
blockwise-jnp formulation's VJP (flash-style recompute — no O(S^2) residual
is ever stored, matching the paper's ethos of trading recompute for
memory).  The kernel runs compiled on a TPU and in interpret mode on the
CPU (see :func:`repro.kernels.interpret_mode`).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import interpret_mode
from repro.kernels.flash_attention.kernel import flash_attention_fwd


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, causal, block_q, block_kv):
    return flash_attention_fwd(q, k, v, causal=causal, block_q=block_q,
                               block_kv=block_kv, interpret=interpret_mode())


def _flash_fwd(q, k, v, causal, block_q, block_kv):
    out = _flash(q, k, v, causal, block_q, block_kv)
    return out, (q, k, v)


def _flash_bwd(causal, block_q, block_kv, res, do):
    q, k, v = res
    # recompute-based backward through the memory-efficient reference
    from repro.models.attention import blockwise_attention

    def f(q, k, v):
        # blockwise_attention expects (B, S, H, D)
        o = blockwise_attention(q.transpose(0, 2, 1, 3),
                                k.transpose(0, 2, 1, 3),
                                v.transpose(0, 2, 1, 3),
                                causal=causal, block_q=block_q,
                                block_kv=block_kv)
        return o.transpose(0, 2, 1, 3)

    groups = q.shape[1] // k.shape[1]
    kk = jnp.repeat(k, groups, axis=1) if groups > 1 else k
    vv = jnp.repeat(v, groups, axis=1) if groups > 1 else v
    _, vjp = jax.vjp(f, q, kk, vv)
    dq, dk, dv = vjp(do)
    if groups > 1:
        b, hq, s, d = dk.shape
        dk = dk.reshape(b, k.shape[1], groups, s, d).sum(axis=2)
        dv = dv.reshape(b, v.shape[1], groups, s, d).sum(axis=2)
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, *, causal: bool = True, block_q: int = 512,
                    block_kv: int = 1024):
    """Public API.  q/k/v: (B, S, H, D) layout (matching the model code);
    internally transposed to (B, H, S, D) for the kernel."""
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    out = _flash(qt, kt, vt, causal, block_q, block_kv)
    return out.transpose(0, 2, 1, 3)
