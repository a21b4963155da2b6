"""Pallas TPU kernels for the perf-critical compute layers.

Each kernel package ships kernel.py (pl.pallas_call + BlockSpec),
ops.py (jit'd public wrapper) and ref.py (pure-jnp oracle used by the
allclose test sweeps).
"""

import jax


def interpret_mode() -> bool:
    """Whether the wrappers run their Pallas kernels in interpret mode.

    Compiled on a TPU, interpreted on the CPU (the test backend); any
    other backend raises rather than silently interpreting a kernel that
    was written for the TPU."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(f"Pallas TPU kernels cannot run on backend "
                       f"{backend!r}: only 'tpu' (compiled) and 'cpu' "
                       "(interpret mode) are supported")
