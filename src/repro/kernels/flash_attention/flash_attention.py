"""TPU flash-attention kernel: pl.pallas_call + explicit VMEM BlockSpecs.

Tiling (TARGET: TPU v5e — MXU 128×128, ~16 MB VMEM/core):
  grid = (B·H, S/Bq, S/Bk), kv innermost ('arbitrary' = sequential so the
  online-softmax scratch carries across kv steps; bh and q are 'parallel').
  Per-step VMEM working set with Bq = Bk = 128, D = 128, bf16 in / f32 acc:
    q(128·D·2) + k + v + o + acc(128·D·4) + m/l ≈ 0.2 MB  « VMEM.
  The MXU sees (128, D) @ (D, 128) and (128, 128) @ (128, D) matmuls —
  both hardware-aligned for D ∈ {64, 80, 128, 256}.

Causal/local masking is positional (block offsets from program ids); fully
masked kv blocks are skipped via pl.when so the FLOP count matches the
triangle/band exactly.  Scratch (m, l) kept 2-D — TPU VMEM wants ≥2-D tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention_kernel", "flash_attention_pallas"]

_NEG_INF = -1e30


def flash_attention_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref,
                           l_ref, *, scale: float, causal: bool,
                           window: int, block_q: int, block_k: int,
                           n_k: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = qi * block_q
    k_start = ki * block_k
    # block is live iff it intersects the causal triangle / local band
    live = jnp.asarray(True)
    if causal:
        live = jnp.logical_and(live, k_start <= q_start + block_q - 1)
    if window:
        live = jnp.logical_and(live,
                               k_start + block_k - 1 > q_start - window)

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale          # (Bq, D)
        k = k_ref[0].astype(jnp.float32)                  # (Bk, D)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if causal or window:
            qp = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 0)
            kp = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 1)
            ok = jnp.ones((block_q, block_k), jnp.bool_)
            if causal:
                ok = jnp.logical_and(ok, qp >= kp)
            if window:
                ok = jnp.logical_and(ok, qp - kp < window)
            s = jnp.where(ok, s, _NEG_INF)
        m_prev = m_ref[...]                               # (Bq, 1)
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)                    # (Bq, 1)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(ki == n_k - 1)
    def _finalize():
        o_ref[0] = (acc_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_pallas(q, k, v, *, causal: bool = True, window: int = 0,
                           scale=None, block_q: int = 128,
                           block_k: int = 128, interpret: bool = False):
    """q,k,v: (B, H, S, D) -> (B, H, S, D)."""
    B, H, S, D = q.shape
    scale = scale if scale is not None else D ** -0.5
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    assert S % block_q == 0 and S % block_k == 0
    n_q, n_k = S // block_q, S // block_k
    qf = q.reshape(B * H, S, D)
    kf = k.reshape(B * H, S, D)
    vf = v.reshape(B * H, S, D)

    kernel = functools.partial(
        flash_attention_kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, n_k=n_k)
    out = pl.pallas_call(
        kernel,
        grid=(B * H, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, qi, ki: (b, ki, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, qi, ki: (b, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(B, H, S, D)
