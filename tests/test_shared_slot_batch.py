"""The one slot batch, for every dataset and every trainer.

``core.coded_step.slot_batch`` lays a plan's partitions out through the
dataset's ``host_partitions``.  A dataset without its own gets the base
class's: one ``partition`` per key, stacked.  ``FELTrainer`` hands its
step this batch; its bytes must equal the layout the trainer built on its
own before (one ``partition`` per slot, zeros in padding slots, stacked
per worker), restated here as ``stacked_layout``.
"""
import jax
import numpy as np
import pytest

from repro.core.coded_step import SlotPlan, slot_batch
from repro.core.fel import FELTrainer
from repro.data.pipeline import SyntheticClassificationDataset
from repro.models.mlp import init_mlp, per_slot_mlp_loss
from repro.optim import sgd_momentum
from repro.sim.cluster import SCHEMES
from repro.sim.scenarios import scenario_spec
from repro.sim.spec import build_cluster

K, SEED, EPOCHS = 6, 3, 4
#: a layout 4 slots wide: every scheme's plans hold padding slots
SPEC = scenario_spec("bursty-stragglers").with_overrides(n_slots=4)


def _dataset():
    return SyntheticClassificationDataset(K, examples_per_partition=4,
                                          dim=8, n_classes=3, seed=7)


def stacked_layout(ds, epoch, plan) -> dict:
    """(M, n_slots, ...) per key: partition k's arrays in each slot that
    holds k, zeros of partition 0's shapes in each padding slot."""
    zeros = {key: np.zeros_like(np.asarray(v))
             for key, v in ds.partition(epoch, 0).items()}
    return {key: np.stack([np.stack([
        np.asarray(ds.partition(epoch, int(k))[key]) if k >= 0
        else zeros[key] for k in row]) for row in plan.slot_partition])
        for key in zeros}


def _assert_same_bytes(got: dict, want: dict):
    assert got.keys() == want.keys()
    for key, w in want.items():
        g = np.asarray(got[key])
        assert (g.dtype, g.shape) == (w.dtype, w.shape), key
        assert g.tobytes() == w.tobytes(), key


@pytest.mark.parametrize("scheme", SCHEMES)
def test_fel_steps_on_the_stacked_layout(scheme):
    """What FELTrainer hands its step, epoch by epoch, against the stacked
    layout of the same plans, replayed from a cluster built alike."""
    ds = _dataset()
    tr = FELTrainer(scheme, SPEC.M, K, ds, per_slot_mlp_loss,
                    sgd_momentum(lr=0.05),
                    init_mlp(jax.random.PRNGKey(0), dims=(8, 8, 3)),
                    seed=SEED, cluster=SPEC)
    handed, step = [], tr.step_fn
    tr.step_fn = lambda p, o, batch, w: (handed.append(batch)
                                         or step(p, o, batch, w))
    tr.run(EPOCHS)
    assert len(handed) == EPOCHS
    replay = build_cluster(SPEC, scheme, SEED)
    for epoch, batch in enumerate(handed):
        plan = replay.run_epoch(epoch).plan
        assert (plan.slot_partition < 0).any()
        _assert_same_bytes(batch, stacked_layout(ds, epoch, plan))


def test_slot_batch_of_padding_alone_is_zeros_of_partition_shapes():
    ds = _dataset()
    plan = SlotPlan(slot_partition=-np.ones((3, 2), np.int64),
                    slot_coeff=np.zeros((3, 2)), M=3, n_slots=2)
    _assert_same_bytes(slot_batch(ds, plan, 5), stacked_layout(ds, 5, plan))


@pytest.mark.parametrize("ks", [[4, 0, 2], [3], list(range(K))],
                         ids=["unsorted", "single", "all"])
def test_default_host_partitions_stack_partition(ks):
    ds = _dataset()
    want = {key: np.stack([np.asarray(ds.partition(2, k)[key])
                           for k in ks])
            for key in ds.partition(2, 0)}
    _assert_same_bytes(ds.host_partitions(2, np.asarray(ks)), want)
