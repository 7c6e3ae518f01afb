"""Coded-training bridge: real-model partial gradients through the co-sim.

The first vertical slice connecting the two halves of the repo (DESIGN.md
§3.10): a real jax model (``repro.models``) trains through the
closed-loop edge co-simulator (``repro.sim``) under the paper's coding
schemes.  Each epoch's decode weights drive the one coded train step
(``repro.core.coded_step``), whose gradient is the decoded full gradient,
and the run yields loss-vs-simulated-wall-clock curves per scheme — the
paper's headline Fig 5e/6e claim, end-to-end.
"""
from repro.train.coded_trainer import CodedTrainer, TrainEpochLog
from repro.train.curves import (curve_dict, loss_curve, running_best,
                                time_to_target)
from repro.train.partition import (DEFAULT_BYTES_PER_UNIT, GradPartition,
                                   flatten_grads, payload_units,
                                   shard_assignment)

__all__ = [
    "CodedTrainer", "TrainEpochLog", "GradPartition", "flatten_grads",
    "shard_assignment", "payload_units", "DEFAULT_BYTES_PER_UNIT",
    "loss_curve", "running_best", "time_to_target", "curve_dict",
]
