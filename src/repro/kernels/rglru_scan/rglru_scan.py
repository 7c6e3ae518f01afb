"""RG-LRU blocked scan kernel (h_t = a_t ⊙ h_{t-1} + b_t).

TPU mapping: the recurrence is per-channel (embarrassingly parallel over D,
sequential over S).  HBM→VMEM traffic is the bottleneck (element-wise VPU
work), so the kernel streams (Bs, Bd) tiles and keeps the carry h in VMEM:

  grid = (B, D/Bd, S/Bs)  — seq innermost ('arbitrary'), batch/channel
  'parallel'.  Within a tile the scan is a log-depth Hillis–Steele combine:
  step d rolls the running (A, h) pairs down d rows along the sublane axis
  (``pltpu.roll``, an XLU rotate) and folds each row with the one d rows
  above it, masking the rows that wrapped around.  Everything stays in
  full (Bs, Bd) vregs — no strided or zero-length slices, which Mosaic
  refuses.  The carried h is then applied via the tile's cumulative decay
  A_t = Π a and the carry updated:
      h_t(tile) = scan(a, b)_t + A_t ⊙ h_in.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["rglru_scan_kernel", "rglru_scan_pallas"]


def rglru_scan_kernel(a_ref, b_ref, o_ref, hlast_ref, h_ref, *,
                      n_s: int):
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    A = a_ref[0].astype(jnp.float32)          # (Bs, Bd)
    inner = b_ref[0].astype(jnp.float32)
    row = jax.lax.broadcasted_iota(jnp.int32, A.shape, 0)
    d = 1
    while d < A.shape[0]:
        # row t takes (A, h) of row t-d, the identity (1, 0) where t < d
        late = row >= d
        A_up = jnp.where(late, pltpu.roll(A, d, 0), 1.0)
        h_up = jnp.where(late, pltpu.roll(inner, d, 0), 0.0)
        inner = A * h_up + inner
        A = A * A_up
        d *= 2
    h_in = h_ref[...]                          # (1, Bd)
    out = inner + A * h_in
    o_ref[0] = out.astype(o_ref.dtype)
    h_ref[...] = out[-1:]

    @pl.when(si == n_s - 1)
    def _final():
        hlast_ref[0] = out[-1:].astype(hlast_ref.dtype)


def rglru_scan_pallas(a, b, *, block_s: int = 256, block_d: int = 128,
                      interpret: bool = False):
    """a, b: (B, S, D) -> (out (B,S,D), h_last (B,D))."""
    B, S, D = a.shape
    block_s = min(block_s, S)
    block_d = min(block_d, D)
    assert S % block_s == 0 and D % block_d == 0
    n_s = S // block_s
    kernel = functools.partial(rglru_scan_kernel, n_s=n_s)
    out, h_last = pl.pallas_call(
        kernel,
        grid=(B, D // block_d, n_s),
        in_specs=[
            pl.BlockSpec((1, block_s, block_d),
                         lambda bi, di, si: (bi, si, di)),
            pl.BlockSpec((1, block_s, block_d),
                         lambda bi, di, si: (bi, si, di)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_s, block_d),
                         lambda bi, di, si: (bi, si, di)),
            pl.BlockSpec((1, 1, block_d), lambda bi, di, si: (bi, 0, di)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, D), a.dtype),
            jax.ShapeDtypeStruct((B, 1, D), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((1, block_d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(a, b)
    return out, h_last[:, 0]
