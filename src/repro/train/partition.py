"""Gradient partitioning for the coded-training bridge (paper §III.1).

The paper codes over K *data* shards: worker m's upload is the coded
combination ĝ_m = Σ_k B[m,k]·g_k of per-shard partial gradients, each the
gradient of the loss over data partition D_k.  This module supplies the
payload side of running a *real* model through that pipeline:

  * :func:`flatten_grads` / :class:`GradPartition` — a gradient pytree
    flattened to one ``(D,)`` f32 payload vector and back; the bridge
    reads its upload size off it (the coded step itself works on
    pytrees, ``repro.core.coded_step``);
  * :func:`shard_assignment` — which data shards each worker computes,
    read off the coding matrix ``B`` (``CodingScheme.support``);
  * :func:`payload_units` — the *measured* per-upload payload, derived
    from the flattened gradient's byte size instead of the synthetic
    ``grad_bytes`` constant the scenarios default to.

Payload calibration: scenario channel rates are in abstract payload
units per slot (e.g. ``bursty-stragglers`` drains ~0.4 units/slot/worker),
not bytes.  ``DEFAULT_BYTES_PER_UNIT`` maps measured bytes onto that
scale — 4 MiB per unit, so a ~2.6 MB reduced-config gradient costs ≈0.6
units, commensurate with the synthetic ``grad_bytes=1.0`` the scenarios
were tuned around, while twice the model is honestly twice the uplink.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, List

import jax
import jax.numpy as jnp
import numpy as np
from jax.flatten_util import ravel_pytree

from repro.core.coding import CodingScheme

__all__ = ["DEFAULT_BYTES_PER_UNIT", "GradPartition", "flatten_grads",
           "shard_assignment", "payload_units"]

#: Bytes of flattened gradient per scenario payload unit (4 MiB).  The
#: registry scenarios' channel rates are tuned for O(1)-unit payloads;
#: this constant anchors real model sizes to that scale.
DEFAULT_BYTES_PER_UNIT = float(4 * 2 ** 20)


def flatten_grads(tree: Any) -> jnp.ndarray:
    """Flatten a gradient pytree into one ``(D,)`` f32 payload vector."""
    flat, _ = ravel_pytree(tree)
    return flat.astype(jnp.float32)


def shard_assignment(scheme: CodingScheme) -> List[np.ndarray]:
    """Per-worker data-shard assignment read off the coding matrix: entry
    ``m`` lists the global partition ids worker ``m`` computes (the
    nonzero columns of ``B[m]``, mapped through ``scheme.partitions``)."""
    parts = np.asarray(scheme.partitions)
    return [parts[np.flatnonzero(scheme.B[r] != 0.0)]
            for r in range(scheme.B.shape[0])]


def payload_units(n_bytes: float,
                  bytes_per_unit: float = DEFAULT_BYTES_PER_UNIT) -> float:
    """Measured payload bytes → scenario payload units (``grad_bytes``)."""
    if n_bytes <= 0 or bytes_per_unit <= 0:
        raise ValueError(f"need positive payload and scale, got "
                         f"n_bytes={n_bytes}, "
                         f"bytes_per_unit={bytes_per_unit}")
    return float(n_bytes) / float(bytes_per_unit)


@dataclasses.dataclass(frozen=True)
class GradPartition:
    """Flattening contract for one model's gradients.

    Captured once from a parameter template; every gradient of the same
    model flattens to the same ``(D,)`` layout, one upload of
    ``payload_bytes``.  ``unflatten`` is the exact inverse.
    """
    D: int                                 # flattened gradient length
    payload_bytes: float                   # one upload's size in bytes
    unflatten: Callable[[jnp.ndarray], Any] = dataclasses.field(
        repr=False, compare=False, default=None)

    @classmethod
    def from_params(cls, params: Any) -> "GradPartition":
        flat, unravel = ravel_pytree(
            jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params))
        return cls(D=int(flat.shape[0]),
                   payload_bytes=float(flat.shape[0] * 4),  # f32 payload
                   unflatten=unravel)

    def grad_bytes(self,
                   bytes_per_unit: float = DEFAULT_BYTES_PER_UNIT) -> float:
        """This model's per-upload payload in scenario units."""
        return payload_units(self.payload_bytes, bytes_per_unit)
