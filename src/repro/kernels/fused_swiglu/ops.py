"""Jit'd wrapper for the fused SwiGLU kernel (interpret mode on the CPU)."""

import functools

import jax

from repro.kernels import interpret_mode
from repro.kernels.fused_swiglu.kernel import fused_swiglu_pallas


@functools.partial(jax.jit,
                   static_argnames=("block_m", "block_f", "block_k",
                                    "interpret"))
def fused_swiglu(x, wg, wu, *, block_m: int = 256, block_f: int = 512,
                 block_k: int = 512, interpret: bool | None = None):
    if interpret is None:
        interpret = interpret_mode()
    return fused_swiglu_pallas(x, wg, wu, block_m=block_m, block_f=block_f,
                               block_k=block_k, interpret=interpret)
