"""CodedTrainer: a real jax model trained through the co-simulated uplink.

Per epoch (DESIGN.md §3.10):

  1. **co-sim epoch** — ``EdgeCluster.run_epoch`` samples the compute
     phase and drains each worker's *measured* payload (the flattened
     gradient's size, not the synthetic constant) through the Lyapunov
     scheduler; decode is gated on byte arrival;
  2. **coded step** — on a successful decode, the epoch's partitions are
     laid out in its slot plan (``slot_batch``) and one jitted
     ``make_coded_train_step`` runs one backward pass over the slot
     losses weighted by the engine's ``(M, n_slots)`` weights
     ``a_m·coeff[m,s]``.  By linearity its gradient is the decoded
     ``Σ_m a_m ĝ_m = Σ_k g_k``, the exact full-batch gradient, and one
     optimizer update follows;
  3. **no-op step** — when decode failed, the paper's no-op: params and
     optimizer state are left untouched (bit-identical), the epoch burned
     wall-clock only.

Wall-clock attribution: *simulated* time comes from the co-sim
(``compute_time``/``comm_time``); *host* time for the bridge's own work
is recorded as telemetry phase spans (``slot_batch`` / ``coded_step``)
on the same recorder the cluster threads its compute/comm/decode spans
through.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, List, Optional

import jax
import jax.numpy as jnp

from repro.core.coded_step import make_coded_train_step, slot_batch
from repro.models.transformer import init_params, per_slot_lm_loss
from repro.sim.spec import ScenarioSpec, build_cluster
from repro.telemetry.recorder import FleetRecorder, phase_span
from repro.train.partition import DEFAULT_BYTES_PER_UNIT, GradPartition

__all__ = ["CodedTrainer", "TrainEpochLog"]


@dataclasses.dataclass
class TrainEpochLog:
    """One bridge epoch: losses are real-model, times are co-simulated."""
    epoch: int
    loss: float                 # pre-step full-batch loss (NaN on no-op)
    time: float                 # simulated epoch wall-clock
    compute_time: float
    comm_time: float
    decode_ok: bool
    n_slots: int                # comm slots this epoch
    grad_bytes: float           # measured payload (scenario units)


@functools.lru_cache(maxsize=8)
def _coded_step(cfg, optimizer):
    """The jitted coded step for ``cfg`` and one optimizer object, shared
    by every trainer given both, so a scheme × seed sweep compiles once
    per slot shape.  Nothing is donated: trainers may share ``params``."""
    return jax.jit(make_coded_train_step(per_slot_lm_loss(cfg), optimizer))


class CodedTrainer:
    """One (model × scenario × scheme) coded-training experiment.

    ``spec`` supplies the cluster physics; its synthetic ``grad_bytes``
    is replaced by the payload measured from the model's flattened
    gradient (``GradPartition``), calibrated through ``bytes_per_unit``
    (see :mod:`repro.train.partition`).  The spec the cluster was
    actually built from — carrying the measured payload — is exposed as
    ``self.spec`` so fleets (``run_fleet(trainer.spec, ...)``) and sweeps
    see the same physics the trainer stepped through.
    """

    def __init__(self, cfg, spec: ScenarioSpec, scheme: str, dataset,
                 optimizer, *, params: Optional[Any] = None, seed: int = 0,
                 bytes_per_unit: float = DEFAULT_BYTES_PER_UNIT,
                 telemetry: Optional[FleetRecorder] = None):
        if dataset.K != spec.K:
            raise ValueError(f"dataset has K={dataset.K} partitions, "
                             f"scenario wants K={spec.K}")
        self.cfg = cfg
        self.scheme = scheme
        self.dataset = dataset
        self.optimizer = optimizer
        self.telemetry = telemetry
        if params is None:
            params = init_params(cfg, jax.random.PRNGKey(seed))
        self.params = params
        self.opt_state = optimizer.init(params)

        # measured payload: the flattened-gradient byte size, in scenario
        # units — the co-sim drains what the model actually uploads
        self.partition = GradPartition.from_params(params)
        self.grad_bytes = self.partition.grad_bytes(bytes_per_unit)
        self.spec = spec.with_overrides(grad_bytes=self.grad_bytes)
        self.cluster = build_cluster(self.spec, scheme, seed)
        if telemetry is not None:
            self.cluster.telemetry = telemetry

        self._step = _coded_step(cfg, optimizer)
        self.logs: List[TrainEpochLog] = []
        self.noop_steps = 0

    def run_epoch(self, epoch: int) -> TrainEpochLog:
        rec = self.telemetry
        # the co-sim epoch always runs (it owns the per-seed RNG stream),
        # whether or not the decode ends up succeeding
        result = self.cluster.run_epoch(epoch)
        if result.decode_ok:
            with phase_span(rec, "slot_batch", epoch=epoch):
                batch = slot_batch(self.dataset, result.plan, epoch)
            with phase_span(rec, "coded_step", epoch=epoch):
                self.params, self.opt_state, aux = self._step(
                    self.params, self.opt_state, batch,
                    jnp.asarray(result.weights, jnp.float32))
                loss = float(aux["loss"])
        else:
            # the paper's no-op step: params and optimizer state are the
            # same objects — bit-identical, nothing was applied.  Loss is
            # NaN so curves show a gap, not a dip (fel.py convention).
            self.noop_steps += 1
            loss = float("nan")
        log = TrainEpochLog(
            epoch=epoch, loss=loss, time=float(result.time),
            compute_time=float(result.compute_time),
            comm_time=float(result.comm_time),
            decode_ok=bool(result.decode_ok),
            n_slots=int(result.comm.n_slots if result.comm else 0),
            grad_bytes=self.grad_bytes)
        self.logs.append(log)
        return log

    def run(self, n_epochs: int) -> List[TrainEpochLog]:
        return [self.run_epoch(e) for e in range(n_epochs)]
