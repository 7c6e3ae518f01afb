"""Pallas kernel validation: interpret-mode vs pure-jnp oracles.

Per kernel: sweep shapes + dtypes and assert_allclose against ref.py.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.ops import flash_attention_op
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.flash_attention.flash_attention import \
    flash_attention_pallas
from repro.kernels.rglru_scan.ops import rglru_scan_op
from repro.kernels.rglru_scan.ref import rglru_ref
from repro.kernels.rwkv6_wkv.ops import wkv_op
from repro.kernels.rwkv6_wkv.ref import wkv_ref


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


# --------------------------------------------------------------------- #
# flash attention
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("B,H,S,D", [(1, 2, 128, 32), (2, 1, 256, 64),
                                     (1, 2, 128, 80)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 48),
                                           (False, 0)])
def test_flash_attention_sweep(B, H, S, D, dtype, causal, window):
    rng = np.random.default_rng(0)
    q, k, v = [jnp.asarray(rng.standard_normal((B, H, S, D)), dtype)
               for _ in range(3)]
    out = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                 block_q=64, block_k=64, interpret=True)
    ref = attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        **_tol(dtype))


def test_flash_attention_gqa_wrapper_matches_model_path():
    from repro.models.attention import flash_attention as xla_flash
    rng = np.random.default_rng(1)
    B, S, KV, G, D = 2, 128, 2, 3, 32
    q = jnp.asarray(rng.standard_normal((B, S, KV, G, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, KV, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, KV, D)), jnp.float32)
    out_pl = flash_attention_op(q, k, v, causal=True, block_q=64,
                                block_k=64, interpret=True)
    out_xla = xla_flash(q, k, v, causal=True, q_chunk=64, kv_chunk=64)
    np.testing.assert_allclose(np.asarray(out_pl), np.asarray(out_xla),
                               rtol=2e-5, atol=2e-5)


def test_flash_attention_block_shape_independence():
    rng = np.random.default_rng(2)
    q, k, v = [jnp.asarray(rng.standard_normal((1, 1, 256, 32)), jnp.float32)
               for _ in range(3)]
    outs = [flash_attention_pallas(q, k, v, causal=True, block_q=bq,
                                   block_k=bk, interpret=True)
            for bq, bk in [(64, 64), (128, 64), (64, 128), (256, 256)]]
    for o in outs[1:]:
        np.testing.assert_allclose(np.asarray(outs[0]), np.asarray(o),
                                   rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------------- #
# rg-lru scan
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("B,S,D", [(2, 128, 64), (1, 256, 128), (3, 64, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rglru_scan_sweep(B, S, D, dtype):
    rng = np.random.default_rng(3)
    a = jnp.asarray(rng.uniform(0.5, 0.999, (B, S, D)), dtype)
    b = jnp.asarray(rng.standard_normal((B, S, D)) * 0.1, dtype)
    out, h_last = rglru_scan_op(a, b, block_s=64, block_d=64,
                                interpret=True)
    ref, h_ref = rglru_ref(a, b)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **_tol(dtype))
    np.testing.assert_allclose(np.asarray(h_last), np.asarray(h_ref),
                               rtol=1e-2 if dtype == jnp.bfloat16 else 1e-5,
                               atol=1e-2 if dtype == jnp.bfloat16 else 1e-5)


def test_rglru_matches_model_assoc_scan():
    """Kernel == the model's associative-scan path (same a/b inputs)."""
    from repro.models.rglru import rglru_scan as model_scan
    rng = np.random.default_rng(4)
    B, S, Hr, Dr = 2, 64, 2, 32
    x = jnp.asarray(rng.standard_normal((B, S, Hr, Dr)), jnp.float32)
    p = {"w_a": jnp.asarray(rng.standard_normal((Hr, Dr, Dr)) * 0.3,
                            jnp.float32),
         "b_a": jnp.zeros((Hr, Dr)), "lam": jnp.ones((Hr, Dr)),
         "w_x": jnp.asarray(rng.standard_normal((Hr, Dr, Dr)) * 0.3,
                            jnp.float32),
         "b_x": jnp.zeros((Hr, Dr))}
    y_model, _ = model_scan(x, p)
    # reproduce a/b from the gate math, then run the kernel
    import repro.models.rglru as rg
    i, log_a = rg._gates(x.astype(jnp.float32), p)
    a = jnp.exp(log_a).reshape(B, S, Hr * Dr)
    b = (jnp.sqrt(jnp.maximum(1 - jnp.exp(2 * log_a), 1e-12)) *
         (i * x)).reshape(B, S, Hr * Dr)
    out, _ = rglru_scan_op(a, b, block_s=32, block_d=32, interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(y_model.reshape(B, S, -1)),
                               rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------------- #
# rwkv6 wkv
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("B,H,S,K,V", [(1, 2, 64, 16, 16), (2, 1, 128, 32, 32),
                                       (1, 1, 96, 64, 64)])
@pytest.mark.parametrize("chunk", [16, 32])
def test_wkv_sweep(B, H, S, K, V, chunk):
    rng = np.random.default_rng(5)
    r = jnp.asarray(rng.standard_normal((B, H, S, K)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, H, S, K)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, H, S, V)), jnp.float32)
    w = jnp.asarray(rng.uniform(0.3, 0.99, (B, H, S, K)), jnp.float32)
    u = jnp.asarray(rng.standard_normal((H, K)), jnp.float32)
    if S % chunk:
        pytest.skip("S not divisible")
    out, s_last = wkv_op(r, k, v, w, u, chunk=chunk, interpret=True)
    ref, s_ref = wkv_ref(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(s_last), np.asarray(s_ref),
                               rtol=2e-4, atol=2e-4)


def test_wkv_bf16_inputs():
    rng = np.random.default_rng(6)
    B, H, S, K = 1, 2, 64, 16
    r, k = [jnp.asarray(rng.standard_normal((B, H, S, K)), jnp.bfloat16)
            for _ in range(2)]
    v = jnp.asarray(rng.standard_normal((B, H, S, K)), jnp.bfloat16)
    w = jnp.asarray(rng.uniform(0.5, 0.99, (B, H, S, K)), jnp.bfloat16)
    u = jnp.asarray(rng.standard_normal((H, K)), jnp.bfloat16)
    out, _ = wkv_op(r, k, v, w, u, chunk=16, interpret=True)
    ref, _ = wkv_ref(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=5e-2, atol=5e-2)
