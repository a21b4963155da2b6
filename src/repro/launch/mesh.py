"""Production mesh construction.

Defined as functions (never module-level constants) so importing this
module never touches jax device state — the dry-run sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
initialisation and only then builds meshes.

Mesh shapes:
    single pod:  (16, 16)      axes ("data", "model")   — 256 chips
    multi pod:   (2, 16, 16)   axes ("pod", "data", "model") — 512 chips

``pod`` is the DCN (inter-pod) axis: pure data parallelism with optional
gradient compression; ``data`` is within-pod DP / FSDP / sequence
parallelism; ``model`` is tensor/expert parallelism over ICI.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with ``Auto`` axes.  The sharding rules annotate
    activations with ``with_sharding_constraint``, which JAX's default
    ``Explicit`` axes refuse."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(n_devices: int | None = None, *, model: int = 2):
    """Small mesh over however many (host) devices exist — for tests."""
    n = n_devices or len(jax.devices())
    data = max(n // model, 1)
    return make_mesh((data, model), ("data", "model"))


# TPU v5e hardware constants (the roofline denominators)
PEAK_FLOPS_BF16 = 197e12       # per chip
HBM_BW = 819e9                 # bytes/s per chip
ICI_BW = 50e9                  # bytes/s per link
DCN_BW = 6.25e9                # bytes/s per host (~50 Gbit) for pod axis
HBM_BYTES = 16 * 1024**3       # 16 GiB per chip
