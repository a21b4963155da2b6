"""Compile the main path's kernels and the planned-training reference for a
described TPU v5e (no chip attached): what the TPU compiler refuses —
unaligned tiles, too much VMEM, a program that does not fit — fails here.

The topology is described inside a module fixture, never at import time:
only one process may load the TPU library at once, and the test workers
all import this file.  The compilation cache is off around these tests
(a compile for a described chip is written to the cache but cannot be
read back without one).
"""

import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs import ARCHS

LLAMA = ARCHS["llama3.2-3b"]
ZAMBA = ARCHS["zamba2-7b"]
XLSTM = ARCHS["xlstm-1.3b"]
SEQ, CHUNK = 4096, 256


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    cache_was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        jax.config.update("jax_enable_compilation_cache", cache_was_on)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_was_on)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes, **static):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(lambda *a: fn(*a, **static)).lower(*args).compile()


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
def test_flash_attention_compiles_at_llama_widths(one_chip, dtype):
    from repro.kernels.flash_attention.kernel import flash_attention_fwd
    d = LLAMA.head_dim
    c = _compile(flash_attention_fwd, one_chip,
                 ((1, LLAMA.n_heads, SEQ, d), dtype),
                 ((1, LLAMA.n_kv_heads, SEQ, d), dtype),
                 ((1, LLAMA.n_kv_heads, SEQ, d), dtype),
                 causal=True, interpret=False)
    assert "tpu_custom_call" in c.as_text()


def test_fused_swiglu_compiles_at_llama_widths(one_chip):
    from repro.kernels.fused_swiglu.kernel import fused_swiglu_pallas
    m, d, f = 4096, LLAMA.d_model, LLAMA.d_ff
    c = _compile(fused_swiglu_pallas, one_chip,
                 ((m, d), jnp.bfloat16), ((d, f), jnp.bfloat16),
                 ((d, f), jnp.bfloat16), interpret=False)
    assert "tpu_custom_call" in c.as_text()


def test_ssd_chunk_compiles_at_zamba2_widths(one_chip):
    from repro.kernels.ssm_scan.kernel import ssd_chunk_pallas
    h, n = ZAMBA.n_ssm_heads, ZAMBA.ssm_state
    p = ZAMBA.d_inner // h
    nc = SEQ // CHUNK
    f32 = jnp.float32
    c = _compile(ssd_chunk_pallas, one_chip,
                 ((1, nc, CHUNK, h, p), f32), ((1, nc, CHUNK, h), f32),
                 ((h,), f32), ((1, nc, CHUNK, n), f32),
                 ((1, nc, CHUNK, n), f32), interpret=False)
    assert "tpu_custom_call" in c.as_text()


def test_mlstm_chunk_compiles_at_xlstm_widths(one_chip):
    from repro.kernels.mlstm_scan.kernel import mlstm_chunk_pallas
    h = XLSTM.n_heads
    p = 2 * XLSTM.d_model // h          # mLSTM heads split the 2x up-projection
    nc = SEQ // CHUNK
    f32 = jnp.float32
    c = _compile(mlstm_chunk_pallas, one_chip,
                 ((1, nc, CHUNK, h, p), f32), ((1, nc, CHUNK, h, p), f32),
                 ((1, nc, CHUNK, h, p), f32), ((1, nc, CHUNK, h), f32),
                 ((1, nc, CHUNK, h), f32), sm_scale=p ** -0.5,
                 interpret=False)
    assert "tpu_custom_call" in c.as_text()


def test_vgg16_reference_step_compiles_at_batch_64(one_chip):
    from repro.core.exec.layers import init_params, reference_loss_and_grads
    from repro.core.zoo import ZOO

    g = ZOO["vgg16"]()
    batch = 64
    params = jax.eval_shape(lambda: init_params(g, jax.random.PRNGKey(0)))
    p_shapes = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        params)
    x = jax.ShapeDtypeStruct((batch,) + tuple(g.input_shape), jnp.float32,
                             sharding=one_chip)
    y = jax.ShapeDtypeStruct((batch,) + tuple(g.label_shape), jnp.float32,
                             sharding=one_chip)
    c = jax.jit(lambda p, x, y: reference_loss_and_grads(g, p, x, y)
                ).lower(p_shapes, x, y).compile()
    mem = c.memory_analysis()
    assert mem.argument_size_in_bytes > 0
    # a device program, not a host one: conv fusions for the TPU
    assert "convolution" in c.as_text()
