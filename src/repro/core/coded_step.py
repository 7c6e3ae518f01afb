"""Coded gradient train step — the paper's pipeline as ONE standard SPMD step.

TPU-native statement of TSDCFL (DESIGN.md §2):

  encode  = per-example loss weighting   (gradient linearity: a single
            backward pass over coefficient-weighted losses IS the coded
            partial gradient Σ_k B[m,k]·g_k)
  decode  = the existing data-parallel gradient all-reduce, with each
            worker's loss additionally scaled by its decode weight a_m:
            ∇ Σ_m a_m Σ_s c_{m,s} ℓ(slot_{m,s})  =  Σ_m a_m ĝ_m  =  Σ_k g_k

So the coded step costs ZERO extra collectives versus plain data-parallel
SGD, and the straggler pattern enters as runtime data (weights), never as a
recompile.  The host-side TwoStageRuntime (core/runtime.py) builds the slot
assignment + weights each epoch.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["SlotPlan", "build_slot_plan", "slot_weights",
           "make_train_step", "coded_value_and_grad", "make_coded_train_step"]


@dataclasses.dataclass(frozen=True)
class SlotPlan:
    """Static-shape slot layout for one epoch.

    slot_partition[m, s] — global partition id computed in worker m's slot s
    (-1 = unused slot); slot_coeff[m, s] — coding coefficient B[m, k].
    """
    slot_partition: np.ndarray      # (M, n_slots) int
    slot_coeff: np.ndarray          # (M, n_slots) float
    M: int
    n_slots: int


def build_slot_plan(schemes: list, M: int, n_slots: Optional[int] = None
                    ) -> SlotPlan:
    """Pack one or more coding schemes (stage-1 rows + stage-2 rows) into the
    per-worker slot layout.  Rows of each scheme map to global worker ids via
    ``scheme.workers``; columns to global partitions via ``scheme.partitions``.
    """
    assign: list = [[] for _ in range(M)]
    for scheme in schemes:
        B = scheme.B
        for r, w in enumerate(np.asarray(scheme.workers)):
            for c in np.flatnonzero(B[r] != 0.0):
                assign[int(w)].append((int(scheme.partitions[c]),
                                       float(B[r, c])))
    width = max((len(a) for a in assign), default=1)
    n_slots = n_slots or max(width, 1)
    if width > n_slots:
        raise ValueError(f"need {width} slots, layout has {n_slots}")
    part = -np.ones((M, n_slots), np.int64)
    coef = np.zeros((M, n_slots), np.float64)
    for m, a in enumerate(assign):
        for s, (k, b) in enumerate(a):
            part[m, s] = k
            coef[m, s] = b
    return SlotPlan(slot_partition=part, slot_coeff=coef, M=M,
                    n_slots=n_slots)


def slot_weights(plan: SlotPlan, decode_w: np.ndarray) -> np.ndarray:
    """(M, n_slots) per-slot loss weights  a_m · B[m,k]  (0 for unused)."""
    w = plan.slot_coeff * decode_w[:, None]
    w[plan.slot_partition < 0] = 0.0
    return w


# --------------------------------------------------------------------- #
def make_train_step(loss_fn: Callable, optimizer, *,
                    grad_transform: Optional[Callable] = None,
                    clip_norm: float = 0.0) -> Callable:
    """Standard step: (params, opt_state, batch) -> (params, opt_state, aux).

    ``loss_fn(params, batch) -> scalar``.  The coded pipeline reuses this
    step unchanged — coding lives in ``batch['weights']``.
    ``grad_transform(grads) -> grads`` hooks in gradient compression.
    """
    from repro.optim import clip_by_global_norm

    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        gn = jnp.zeros(())
        if clip_norm:
            grads, gn = clip_by_global_norm(grads, clip_norm)
        if grad_transform is not None:
            grads = grad_transform(grads)
        params, opt_state = optimizer.update(grads, opt_state, params)
        return params, opt_state, {"loss": loss, "grad_norm": gn}

    return step


def coded_value_and_grad(per_slot_loss_fn: Callable) -> Callable:
    """(params, slot_batch, weights) -> (weighted loss, decoded gradient).

    ``per_slot_loss_fn(params, slot_batch) -> (M, n_slots)`` per-slot mean
    losses.  Contracting them with the runtime-supplied weight matrix
    (a_m·B[m,k]) makes the gradient, by linearity, the exact decoded full
    gradient Σ_k g_k.
    """
    def fn(params, slot_batch, weights):
        def total_loss(p):
            per_slot = per_slot_loss_fn(p, slot_batch)       # (M, n_slots)
            return jnp.sum(per_slot * weights)
        return jax.value_and_grad(total_loss)(params)

    return fn


def make_coded_train_step(per_slot_loss_fn: Callable, optimizer) -> Callable:
    """Coded step over slotted batches: the :func:`coded_value_and_grad`
    gradient, then one optimizer update."""
    value_and_grad = coded_value_and_grad(per_slot_loss_fn)

    def step(params, opt_state, slot_batch, weights):
        loss, grads = value_and_grad(params, slot_batch, weights)
        params, opt_state = optimizer.update(grads, opt_state, params)
        return params, opt_state, {"loss": loss}

    return step
