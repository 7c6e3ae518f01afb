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
assignment + weights each epoch, and ``slot_batch`` lays the epoch's data
partitions out in that assignment.

Zero-weight slots are skipped when they can be.  Padding slots and every
slot of a worker the decode discards carry weight exactly 0, so they add
exactly 0 to the loss and the gradient.  When the nonzero weights fit in
one row of ``n_slots`` slots (``nnz <= n_slots``), the step gathers those
slots, nonzero first in a stable order over the flattened (M, n_slots)
layout, and runs forward and backward over that one row only.  Otherwise
it runs the whole layout as before: a plan that needs more than one row
costs what it did without the row, not more (no per-row accumulations,
and in bf16 the same whole-batch rounding of the weight gradients).  A
``lax.cond`` on the weights picks the branch: one executable for every
plan.  The gather mixes workers' slots in one row, so it holds only while
all M workers' slots live on one device; a worker axis sharded over a
mesh would turn it into a cross-chip shuffle (ROADMAP R3).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental.layout import Layout, with_layout_constraint

from repro.telemetry.annotation import annotate

__all__ = ["SlotPlan", "build_slot_plan", "slot_weights", "slot_batch",
           "computed_rows", "make_train_step", "coded_value_and_grad",
           "make_coded_train_step"]


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


def slot_batch(ds, plan, step: int) -> dict:
    """Lay the epoch's partitions out in the plan's (M, n_slots, b, S)
    layout; unused slots (partition -1) get zeros, and zero weight.

    Profiler spans: ``coded.batch.data`` (the plan's distinct partitions,
    drawn on the host in one ``ds.host_partitions`` call),
    ``coded.batch.layout`` (one gather per key into the slot layout,
    padding slots reading an appended zero row) and ``coded.batch.h2d``
    (the slot batch to the device, one transfer).
    """
    sp = plan.slot_partition
    ks = np.unique(sp[sp >= 0])
    with annotate("coded.batch.data", partitions=len(ks)):
        parts = ds.host_partitions(step, ks)
    with annotate("coded.batch.layout"):
        idx = np.where(sp >= 0, np.searchsorted(ks, sp), len(ks))
        host = {key: np.concatenate(
            [v, np.zeros((1,) + v.shape[1:], v.dtype)])[idx]
            for key, v in parts.items()}
    with annotate("coded.batch.h2d"):
        return jax.device_put(host)


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


def computed_rows(weights, xp=jnp):
    """Rows of ``n_slots`` slots that the coded step computes for the
    (M, n_slots) ``weights``: 1 when the nonzero weights fit in one row
    (``nnz <= n_slots``), else all M.  ``xp=np`` counts on the host."""
    M, n_slots = weights.shape
    return xp.where(xp.count_nonzero(weights) <= n_slots, 1, M)


def _one_row(slot_batch, weights):
    """The nonzero-weight slots first, in a stable order over the
    flattened layout, cut to one row: a ``(1, n_slots, ...)`` batch and
    its ``(1, n_slots)`` weights."""
    M, n_slots = weights.shape
    flat = weights.reshape(M * n_slots)
    idx = jnp.argsort(flat == 0, stable=True)[:n_slots]   # nonzero first
    batch = jax.tree.map(
        lambda a: a.reshape(M * n_slots, *a.shape[2:])[idx][None],
        slot_batch)
    return batch, flat[idx][None]


def _on_computed_rows(fn, state, slot_batch, weights):
    """``fn(state, batch, w)`` on the :func:`computed_rows` rows: one
    packed row, or the whole layout.  One ``lax.cond``, so every plan
    shares one executable."""
    return lax.cond(computed_rows(weights) == 1,
                    lambda s, b, w: fn(s, *_one_row(b, w)), fn,
                    state, slot_batch, weights)


def _weighted_value_and_grad(per_slot_loss_fn: Callable) -> Callable:
    def fn(params, batch, w):
        return jax.value_and_grad(
            lambda p: jnp.sum(per_slot_loss_fn(p, batch) * w))(params)
    return fn


def coded_value_and_grad(per_slot_loss_fn: Callable) -> Callable:
    """(params, slot_batch, weights) -> (weighted loss, decoded gradient).

    ``per_slot_loss_fn(params, slot_batch) -> (M, n_slots)`` per-slot mean
    losses; it is also called on one row, a ``(1, n_slots, ...)`` batch.
    Contracting them with the runtime-supplied weight matrix (a_m·B[m,k])
    makes the gradient, by linearity, the exact decoded full gradient
    Σ_k g_k.  Only the :func:`computed_rows` rows are computed: the one
    row of nonzero-weight slots, or the whole layout (module docstring).
    """
    weighted = _weighted_value_and_grad(per_slot_loss_fn)
    return functools.partial(_on_computed_rows, weighted)


def make_coded_train_step(per_slot_loss_fn: Callable, optimizer) -> Callable:
    """Coded step over slotted batches: the :func:`coded_value_and_grad`
    gradient, then one optimizer update.  ``aux`` holds the weighted loss
    and the rows computed (:func:`computed_rows`).  The update runs inside
    the same branch as the gradient, so that the compiler can fuse the
    weight gradients into it as it does without the branch."""
    weighted = _weighted_value_and_grad(per_slot_loss_fn)

    def update(state, batch, w):
        params, opt_state = state
        loss, grads = weighted(params, batch, w)
        # row-major gradients: inside a branch the compiler otherwise runs
        # the attention weights' update in a transposed layout, copying
        # params and moments in and out (7.5 ms a step on a v5e)
        grads = jax.tree.map(lambda g: with_layout_constraint(
            g, Layout(major_to_minor=tuple(range(g.ndim)))), grads)
        return optimizer.update(grads, opt_state, params), loss

    def step(params, opt_state, slot_batch, weights):
        (params, opt_state), loss = _on_computed_rows(
            update, (params, opt_state), slot_batch, weights)
        return params, opt_state, {"loss": loss,
                                   "rows": computed_rows(weights)}

    return step
