"""Coded decode-reduce kernel: out = Σ_s w_s · g_s in a single HBM pass.

This is the device-local half of the paper's decode (Eq. 3–4): each worker
combines its per-slot coded gradient shards with the runtime-supplied
coefficients before the cross-worker psum.  Memory-bound (one read of g),
so the tile loop streams (n_slots, Bd) panels through VMEM and accumulates
in f32; XLA's unfused alternative reads g once per slot-scale plus once for
the adds.

  grid = (D/Bd,) 'parallel'; weights prefetched whole (n_slots ≤ a few
  hundred) as a (n_slots, 1) VMEM operand.

Arbitrary D is supported: the wrapper zero-pads the feature axis up to the
next block_d multiple before the pallas_call and slices the padding back
off — real gradient payloads (a flattened model pytree) are almost never a
multiple of the tile width, and zero columns contribute nothing to the
weighted sum.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["coded_reduce_kernel", "coded_reduce_pallas"]


def coded_reduce_kernel(g_ref, w_ref, o_ref):
    g = g_ref[...].astype(jnp.float32)          # (n_slots, Bd)
    w = w_ref[...].astype(jnp.float32)          # (n_slots, 1)
    o_ref[...] = jax.lax.dot_general(
        w, g, (((0,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,    # an exact f32 decode
        preferred_element_type=jnp.float32)     # (1, Bd)


def coded_reduce_pallas(g, w, *, block_d: int = 512,
                        interpret: bool = False):
    """g: (n_slots, D); w: (n_slots,) -> (D,) f32."""
    n_slots, D = g.shape
    block_d = min(block_d, D)
    pad = -D % block_d
    if pad:
        g = jnp.pad(g, ((0, 0), (0, pad)))
    Dp = D + pad
    out = pl.pallas_call(
        coded_reduce_kernel,
        grid=(Dp // block_d,),
        in_specs=[
            pl.BlockSpec((n_slots, block_d), lambda di: (0, di)),
            pl.BlockSpec((n_slots, 1), lambda di: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_d), lambda di: (0, di)),
        out_shape=jax.ShapeDtypeStruct((1, Dp), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(g, w.reshape(n_slots, 1))
    return out[0, :D]
