"""Coded training bridge acceptance tests (DESIGN.md §3.10).

The bridge's contract, pinned per scheme:

  * decode success ⟹ one optimizer step on the uncoded full-batch
    gradient (the sum of the per-shard partial gradients), to allclose;
  * decode failure ⟹ the paper's *no-op step*: params and optimizer
    state are bit-identical to before the epoch;
  * the payload the co-sim drains is *measured* from the flattened
    gradient, not the scenario's synthetic ``grad_bytes`` constant.
"""
import dataclasses
import math

import jax
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.data.pipeline import SyntheticLMDataset
from repro.models import transformer as tfm
from repro.models.transformer import init_params
from repro.optim.optimizers import adamw
from repro.sim.cluster import SCHEMES
from repro.sim.scenarios import scenario_spec
from repro.telemetry.recorder import FleetRecorder
from repro.train import (CodedTrainer, GradPartition, TrainEpochLog,
                         curve_dict, flatten_grads, loss_curve,
                         payload_units, running_best, shard_assignment,
                         time_to_target)

#: One-layer model: big enough to exercise a real pytree (~23k params),
#: small enough that the 4-scheme sweep stays in CI smoke budget.
TINY = ModelConfig(
    name="bridge-test-tiny", family="dense",
    n_layers=1, d_model=32, n_heads=2, n_kv_heads=2, head_dim=16,
    d_ff=64, vocab=64, remat="none", compute_dtype="float32")

SCENARIO = "bursty-stragglers"
#: one optimizer object: the trainers below share one compiled step
OPT = adamw(1e-2)


def _trainer(scheme, *, seed=0, spec=None, telemetry=None):
    spec = spec if spec is not None else scenario_spec(SCENARIO)
    dataset = SyntheticLMDataset(K=spec.K, examples_per_partition=1,
                                 seq_len=16, vocab=TINY.vocab, seed=0)
    return CodedTrainer(TINY, spec, scheme, dataset, OPT,
                        seed=seed, telemetry=telemetry)


# --------------------------------------------------------------------- #
# partition: flatten/unflatten contract and measured payload
# --------------------------------------------------------------------- #
def test_grad_partition_roundtrip():
    params = init_params(TINY, jax.random.PRNGKey(0))
    part = GradPartition.from_params(params)
    flat = flatten_grads(params)
    assert flat.shape == (part.D,) and part.payload_bytes == part.D * 4
    back = part.unflatten(flat)
    leaves_a = jax.tree.leaves(params)
    leaves_b = jax.tree.leaves(back)
    assert len(leaves_a) == len(leaves_b)
    for a, b in zip(leaves_a, leaves_b):
        assert a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b))


def test_payload_units_validation():
    assert payload_units(4 * 2 ** 20) == 1.0
    assert payload_units(2 ** 20, 2 ** 21) == 0.5
    with pytest.raises(ValueError, match="positive"):
        payload_units(0.0)
    with pytest.raises(ValueError, match="positive"):
        payload_units(1.0, -4.0)


def test_shard_assignment_reads_coding_matrix():
    from repro.core.coding import cyclic_repetition
    scheme = cyclic_repetition(6, 2)
    assign = shard_assignment(scheme)
    assert len(assign) == 6
    # CRS(M, s): every worker computes exactly s+1 shards
    assert all(len(a) == 3 for a in assign)
    # and collectively they cover every shard
    assert set(np.concatenate(assign).tolist()) == set(range(6))


def test_measured_grad_bytes_reaches_the_cluster():
    """The spec the cluster is built from carries the *measured* payload
    (flattened-gradient bytes / bytes_per_unit), not the synthetic
    default — and scaling the calibration rescales it exactly."""
    base = scenario_spec(SCENARIO)
    tr = _trainer("two-stage")
    assert tr.grad_bytes == pytest.approx(
        tr.partition.payload_bytes / (4 * 2 ** 20))
    assert tr.spec.comm.grad_bytes == pytest.approx(tr.grad_bytes)
    assert tr.spec.comm.grad_bytes != base.comm.grad_bytes
    dataset = SyntheticLMDataset(K=base.K, examples_per_partition=1,
                                 seq_len=16, vocab=TINY.vocab, seed=0)
    half = CodedTrainer(TINY, base, "two-stage", dataset, adamw(1e-2),
                        bytes_per_unit=2 * 4 * 2 ** 20)
    assert half.grad_bytes == pytest.approx(tr.grad_bytes / 2)


def test_trainer_rejects_mismatched_dataset():
    spec = scenario_spec(SCENARIO)
    bad = SyntheticLMDataset(K=spec.K + 1, examples_per_partition=1,
                             seq_len=16, vocab=TINY.vocab, seed=0)
    with pytest.raises(ValueError, match="partitions"):
        CodedTrainer(TINY, spec, "two-stage", bad, adamw(1e-2))


# --------------------------------------------------------------------- #
# the acceptance pin: decode success ⟹ exact full-batch gradient
# --------------------------------------------------------------------- #
def _recording_cluster(tr, monkeypatch) -> list:
    """The trainer's epoch results, appended as its cluster returns them."""
    results, run = [], tr.cluster.run_epoch
    monkeypatch.setattr(tr.cluster, "run_epoch",
                        lambda epoch: results.append(run(epoch))
                        or results[-1])
    return results


@pytest.mark.parametrize("scheme", SCHEMES)
def test_decoded_gradient_matches_uncoded_full_batch(scheme, monkeypatch):
    tr = _trainer(scheme)
    results = _recording_cluster(tr, monkeypatch)
    params0, opt0 = tr.params, tr.opt_state
    log = tr.run_epoch(0)
    assert log.decode_ok          # bursty-stragglers: slow, never dead
    # the gradient the step applied, off AdamW's first moment m = (1-b1)·g
    assert int(tr.opt_state.step) == 1
    decoded = jax.tree.map(lambda m: m / (1 - 0.9), tr.opt_state.m)
    # the uncoded reference: Σ_k ∇ℓ_k, one backward per partition
    grad = jax.jit(jax.grad(lambda p, batch: tfm.loss_fn(p, batch, TINY)))
    full = jax.tree.map(lambda *g: sum(g), *[
        grad(params0, tr.dataset.partition(0, k))
        for k in range(tr.dataset.K)])
    for got, ref in zip(jax.tree.leaves(decoded), jax.tree.leaves(full)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)
    # and the params are one step from params0 on it.  (Not on `full`:
    # AdamW's first step is lr·g/(|g| + eps), so an element whose
    # gradient is within rounding of 0 may move by up to 2·lr.)
    want, _ = tr.optimizer.update(decoded, opt0, params0)
    for got, ref in zip(jax.tree.leaves(tr.params), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)
    # decode identity on the epoch's own plan, read off the weights the
    # step used: the slots holding partition k weigh 1 together (aᵀB = 1)
    plan, w = results[0].plan, np.asarray(results[0].weights)
    per_partition = [w[plan.slot_partition == k].sum()
                     for k in range(tr.dataset.K)]
    np.testing.assert_allclose(per_partition, np.ones(tr.dataset.K),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("scheme", SCHEMES)
def test_losses_identical_across_schemes_first_epoch(scheme):
    """Exact recovery ⟹ every scheme sees the same loss trajectory; the
    schemes differ only in wall-clock (the paper's Fig 5a vs 5e split)."""
    ref = _trainer("uncoded")
    tr = _trainer(scheme)
    log_ref, log = ref.run_epoch(0), tr.run_epoch(0)
    assert log.loss == pytest.approx(log_ref.loss, rel=1e-5)


# --------------------------------------------------------------------- #
# the acceptance pin: decode failure ⟹ bit-identical no-op step
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("scheme", SCHEMES)
def test_decode_failure_is_bit_identical_noop(scheme):
    spec = scenario_spec(SCENARIO).with_overrides(fault_prob=1.0)
    tr = _trainer(scheme, spec=spec)
    params_before, opt_before = tr.params, tr.opt_state
    flat_before = np.asarray(flatten_grads(tr.params))
    log = tr.run_epoch(0)
    assert not log.decode_ok and math.isnan(log.loss)
    assert tr.noop_steps == 1
    # the very same objects — nothing was applied, not even a copy
    assert tr.params is params_before
    assert tr.opt_state is opt_before
    np.testing.assert_array_equal(np.asarray(flatten_grads(tr.params)),
                                  flat_before)
    # but simulated wall-clock was burned all the same
    assert log.time > 0.0


@pytest.mark.parametrize("scheme", SCHEMES)
def test_successful_epoch_moves_params(scheme):
    tr = _trainer(scheme)
    flat_before = np.asarray(flatten_grads(tr.params))
    log = tr.run_epoch(0)
    assert log.decode_ok
    assert not np.array_equal(np.asarray(flatten_grads(tr.params)),
                              flat_before)


def test_trainers_share_one_compiled_step(monkeypatch):
    """Trainers with an equal config and the same optimizer object share
    one jitted step: it compiles once per slot shape, not per trainer."""
    import repro.train.coded_trainer as ct
    cfg = dataclasses.replace(TINY, name="bridge-test-shared-step")
    spec = scenario_spec(SCENARIO)
    dataset = SyntheticLMDataset(K=spec.K, examples_per_partition=1,
                                 seq_len=16, vocab=cfg.vocab, seed=0)
    shapes, make = set(), ct.slot_batch

    def slot_batch(ds, plan, epoch):
        sb = make(ds, plan, epoch)
        shapes.add(sb["tokens"].shape)
        return sb

    monkeypatch.setattr(ct, "slot_batch", slot_batch)
    opt = adamw(1e-2)
    a, b = (CodedTrainer(cfg, spec, scheme, dataset, opt, seed=seed)
            for scheme, seed in (("two-stage", 0), ("cyclic", 1)))
    assert a._step is b._step
    a.run(2)
    b.run(2)
    assert shapes and a._step._cache_size() == len(shapes)
    other = CodedTrainer(cfg, spec, "two-stage", dataset, adamw(1e-2))
    assert other._step is not a._step


# --------------------------------------------------------------------- #
# telemetry attribution
# --------------------------------------------------------------------- #
def test_bridge_phases_recorded_as_spans():
    rec = FleetRecorder(scenario=SCENARIO, scheme="two-stage")
    tr = _trainer("two-stage", telemetry=rec)
    tr.run(1)
    names = {s.name for s in rec.spans}
    assert {"slot_batch", "coded_step"} <= names
    # the cluster threads its own phase spans through the same recorder
    assert {"compute_phase", "comm", "decode"} <= names


# --------------------------------------------------------------------- #
# curves and time-to-target
# --------------------------------------------------------------------- #
def _log(epoch, loss, t, ok=True):
    return TrainEpochLog(epoch=epoch, loss=loss, time=t, compute_time=t,
                         comm_time=0.0, decode_ok=ok, n_slots=4,
                         grad_bytes=0.1)


def test_loss_curve_and_time_to_target():
    logs = [_log(0, 5.0, 2.0), _log(1, float("nan"), 3.0, ok=False),
            _log(2, 3.0, 1.0)]
    times, losses = loss_curve(logs)
    assert times == [2.0, 5.0, 6.0]
    assert running_best(losses) == [5.0, 5.0, 3.0]   # NaN inherits best
    assert time_to_target(logs, 5.0) == 2.0
    assert time_to_target(logs, 4.0) == 6.0
    assert time_to_target(logs, 1.0) == math.inf
    d = curve_dict(logs)
    assert d["loss"][1] is None and d["noop_epochs"] == 1
    assert d["decode_ok"] == [True, False, True]
    assert d["best_loss"] == [5.0, 5.0, 3.0]


def test_curve_dict_all_noop_is_json_clean():
    import json
    logs = [_log(0, float("nan"), 1.0, ok=False)]
    d = curve_dict(logs)
    assert d["loss"] == [None] and d["best_loss"] == [None]
    json.dumps(d)                    # strict JSON, no NaN/inf leakage


def test_run_returns_per_epoch_logs():
    tr = _trainer("cyclic")
    logs = tr.run(2)
    assert [log.epoch for log in logs] == [0, 1] and tr.logs == logs
    for log in logs:
        assert log.grad_bytes == pytest.approx(tr.grad_bytes)
        assert log.time >= log.comm_time >= 0.0
