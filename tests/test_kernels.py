"""Pallas kernel validation: shape/dtype sweeps vs pure-jnp oracles,
executed in interpret mode (CPU container; TPU is the lowering target)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.kernel import flash_attention_fwd
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.fused_swiglu.kernel import fused_swiglu_pallas
from repro.kernels.fused_swiglu.ref import swiglu_ref
from repro.kernels.mlstm_scan.ops import mlstm_scan
from repro.kernels.mlstm_scan.ref import mlstm_ref
from repro.kernels.ssm_scan.ops import ssd_scan
from repro.kernels.ssm_scan.ref import ssd_ref


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

FLASH_CASES = [
    # (b, hq, hkv, sq, skv, d, causal, block_q, block_kv)
    (1, 2, 2, 128, 128, 64, True, 64, 64),
    (2, 4, 2, 256, 256, 64, True, 128, 128),     # GQA 2:1
    (1, 8, 1, 128, 128, 128, True, 64, 64),      # MQA
    (1, 2, 2, 200, 200, 64, True, 64, 64),       # ragged seq (padding)
    (1, 2, 2, 128, 256, 64, False, 64, 128),     # cross attention
    (2, 2, 2, 256, 256, 32, True, 256, 256),     # single block
]


@pytest.mark.parametrize("case", FLASH_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(case, dtype):
    b, hq, hkv, sq, skv, d, causal, bq, bkv = case
    rng = jax.random.PRNGKey(hash(case) % 2**31)
    kq, kk, kv = jax.random.split(rng, 3)
    q = jax.random.normal(kq, (b, hq, sq, d), dtype)
    k = jax.random.normal(kk, (b, hkv, skv, d), dtype)
    v = jax.random.normal(kv, (b, hkv, skv, d), dtype)
    out = flash_attention_fwd(q, k, v, causal=causal, block_q=bq,
                              block_kv=bkv, interpret=True)
    ref = attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


def test_flash_attention_grad_matches_ref():
    rng = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(rng, 3)
    q = jax.random.normal(kq, (1, 2, 128, 64), jnp.float32)
    k = jax.random.normal(kk, (1, 2, 128, 64), jnp.float32)
    v = jax.random.normal(kv, (1, 2, 128, 64), jnp.float32)

    def f_kernel(q, k, v):
        # ops-layer API takes (B, S, H, D)
        return jnp.sum(flash_attention(q.transpose(0, 2, 1, 3),
                                       k.transpose(0, 2, 1, 3),
                                       v.transpose(0, 2, 1, 3),
                                       block_q=64, block_kv=64) ** 2)

    def f_ref(q, k, v):
        return jnp.sum(attention_ref(q, k, v) ** 2)

    g1 = jax.grad(f_kernel, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-3)


# ---------------------------------------------------------------------------
# SSD / mamba2 scan
# ---------------------------------------------------------------------------

SSD_CASES = [
    # (b, s, h, p, n, chunk)
    (1, 64, 2, 16, 16, 32),
    (2, 128, 4, 32, 64, 64),
    (1, 100, 2, 16, 16, 32),      # ragged
    (1, 32, 1, 64, 32, 32),       # single chunk
]


@pytest.mark.parametrize("case", SSD_CASES)
def test_ssd_scan_matches_sequential_ref(case):
    b, s, h, p, n, chunk = case
    rng = jax.random.PRNGKey(hash(case) % 2**31)
    ks = jax.random.split(rng, 5)
    x = jax.random.normal(ks[0], (b, s, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A_log = jax.random.normal(ks[2], (h,)) * 0.5
    B = jax.random.normal(ks[3], (b, s, n), jnp.float32)
    C = jax.random.normal(ks[4], (b, s, n), jnp.float32)
    out = ssd_scan(x, dt, A_log, B, C, chunk=chunk, interpret=True)
    ref = ssd_ref(x, dt, A_log, B, C)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_ssd_models_module_matches_ref():
    """The jnp ssd_chunked inside models/ssm.py agrees with the oracle too."""
    from repro.models.ssm import ssd_chunked
    rng = jax.random.PRNGKey(3)
    ks = jax.random.split(rng, 5)
    b, s, h, p, n = 2, 96, 2, 16, 32
    x = jax.random.normal(ks[0], (b, s, h, p), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A_log = jax.random.normal(ks[2], (h,)) * 0.5
    B = jax.random.normal(ks[3], (b, s, n), jnp.float32)
    C = jax.random.normal(ks[4], (b, s, n), jnp.float32)
    out = ssd_chunked(x, dt, A_log, B, C, chunk=32)
    ref = ssd_ref(x, dt, A_log, B, C)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# mLSTM scan
# ---------------------------------------------------------------------------

MLSTM_CASES = [
    # (b, s, h, p, chunk)
    (1, 64, 2, 16, 32),
    (2, 128, 4, 32, 64),
    (1, 100, 2, 16, 32),          # ragged
    (1, 32, 1, 64, 32),
]


@pytest.mark.parametrize("case", MLSTM_CASES)
def test_mlstm_scan_matches_sequential_ref(case):
    b, s, h, p, chunk = case
    rng = jax.random.PRNGKey(hash(case) % 2**31)
    ks = jax.random.split(rng, 5)
    q = jax.random.normal(ks[0], (b, s, h, p), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, h, p), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, h, p), jnp.float32)
    ig = jax.random.normal(ks[3], (b, s, h)) * 2.0
    fg = jax.random.normal(ks[4], (b, s, h)) * 2.0 + 2.0
    out = mlstm_scan(q, k, v, ig, fg, chunk=chunk, interpret=True)
    ref = mlstm_ref(q, k, v, ig, fg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_mlstm_models_module_matches_ref():
    from repro.models.xlstm import mlstm_chunked
    rng = jax.random.PRNGKey(7)
    ks = jax.random.split(rng, 5)
    b, s, h, p = 1, 96, 2, 16
    q = jax.random.normal(ks[0], (b, s, h, p), jnp.float32)
    k = jax.random.normal(ks[1], (b, s, h, p), jnp.float32)
    v = jax.random.normal(ks[2], (b, s, h, p), jnp.float32)
    ig = jax.random.normal(ks[3], (b, s, h)) * 2.0
    fg = jax.random.normal(ks[4], (b, s, h)) * 2.0 + 2.0
    out = mlstm_chunked(q, k, v, ig, fg, chunk=32)
    ref = mlstm_ref(q, k, v, ig, fg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# fused SwiGLU
# ---------------------------------------------------------------------------

SWIGLU_CASES = [
    # (m, k, f, bm, bf, bk)
    (128, 256, 512, 64, 128, 128),
    (256, 512, 256, 128, 256, 256),
    (100, 200, 300, 64, 128, 128),   # ragged everywhere
    (64, 64, 64, 64, 64, 64),        # single tile
]


@pytest.mark.parametrize("case", SWIGLU_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_swiglu_matches_ref(case, dtype):
    m, k, f, bm, bf, bk = case
    rng = jax.random.PRNGKey(hash(case) % 2**31)
    k1, k2, k3 = jax.random.split(rng, 3)
    x = jax.random.normal(k1, (m, k), dtype) * 0.5
    wg = jax.random.normal(k2, (k, f), dtype) * 0.05
    wu = jax.random.normal(k3, (k, f), dtype) * 0.05
    out = fused_swiglu_pallas(x, wg, wu, block_m=bm, block_f=bf, block_k=bk,
                              interpret=True)
    ref = swiglu_ref(x, wg, wu)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))


# ---------------------------------------------------------------------------
# interpret mode: only on the CPU, never as a silent fallback
# ---------------------------------------------------------------------------

def _kernel_calls():
    # shapes no other test traces, so each wrapper re-traces and asks
    # for the backend again
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.fused_swiglu.ops import fused_swiglu
    k = jax.random.PRNGKey(7)
    x4 = jax.random.normal(k, (1, 24, 2, 8))
    xs = jax.random.normal(k, (1, 24, 3, 8))
    g = jax.random.normal(k, (1, 24, 3))
    return {
        "flash_attention": lambda: flash_attention(
            x4, x4, x4, causal=True, block_q=8, block_kv=8),
        "fused_swiglu": lambda: fused_swiglu(
            jnp.ones((24, 40)), jnp.ones((40, 56)), jnp.ones((40, 56)),
            block_m=8, block_f=8, block_k=8),
        "ssd_scan": lambda: ssd_scan(
            xs, jnp.abs(g), jnp.zeros((3,)), xs[..., 0, :], xs[..., 1, :],
            chunk=8),
        "mlstm_scan": lambda: mlstm_scan(xs, xs, xs, g, g, chunk=8),
    }


@pytest.mark.parametrize("name", ["flash_attention", "fused_swiglu",
                                  "ssd_scan", "mlstm_scan"])
def test_kernel_wrappers_refuse_non_tpu_backends(monkeypatch, name):
    from repro.kernels import interpret_mode
    call = _kernel_calls()[name]
    assert interpret_mode() is True            # the CPU interprets
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu'"):
        call()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert interpret_mode() is False
