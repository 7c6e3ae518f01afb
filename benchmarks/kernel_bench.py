"""Kernel micro-bench: interpret-mode Pallas vs jnp oracle (CPU wall time
is NOT the TPU number — the derived column reports the tile FLOPs/bytes the
kernel schedules, which is what the roofline consumes)."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np


def _time(fn, *args, reps=3):
    fn(*args)  # compile/warm
    t0 = time.time()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.time() - t0) / reps * 1e6


def main(report) -> None:
    from repro.kernels.flash_attention.ops import flash_attention_op
    from repro.kernels.flash_attention.ref import attention_ref
    from repro.kernels.rglru_scan.ops import rglru_scan_op
    from repro.kernels.rwkv6_wkv.ops import wkv_op

    rng = np.random.default_rng(0)
    # flash attention (B,S,KV,G,D) = (1,512,2,2,64)
    q = jnp.asarray(rng.standard_normal((1, 512, 2, 2, 64)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 512, 2, 64)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 512, 2, 64)), jnp.float32)
    us = _time(lambda: flash_attention_op(q, k, v, block_q=128, block_k=128,
                                          interpret=True))
    flops = 2 * 1 * 4 * 512 * 512 * 64 * 2 / 2   # causal triangle
    report("kernel_flash_attention_interpret", us, f"tile_flops={flops:.2e}")

    qh = q.transpose(0, 2, 3, 1, 4).reshape(1, 4, 512, 64)
    us = _time(lambda: attention_ref(qh, qh, qh))
    report("kernel_flash_attention_ref", us, "oracle")

    a = jnp.asarray(rng.uniform(0.5, 0.99, (2, 512, 256)), jnp.float32)
    b = jnp.asarray(rng.standard_normal((2, 512, 256)), jnp.float32)
    us = _time(lambda: rglru_scan_op(a, b, block_s=128, block_d=128,
                                     interpret=True))
    report("kernel_rglru_scan_interpret", us,
           f"bytes={(a.size + b.size) * 2 * 4:.2e}")

    r = jnp.asarray(rng.standard_normal((1, 4, 256, 64)), jnp.float32)
    w = jnp.asarray(rng.uniform(0.5, 0.99, (1, 4, 256, 64)), jnp.float32)
    u = jnp.asarray(rng.standard_normal((4, 64)), jnp.float32)
    us = _time(lambda: wkv_op(r, r, r, w, u, chunk=32, interpret=True))
    report("kernel_rwkv6_wkv_interpret", us,
           f"state_bytes={4 * 64 * 64 * 4}")
