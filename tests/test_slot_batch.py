"""The slot batch is drawn on the host in one pass and reaches the device
in one transfer, with the bytes of the per-partition pipeline it replaced.

Two earlier forms are restated here as the references: the dataset's
per-partition generator (one ``default_rng`` per (epoch, partition), the
Markov recurrence over one partition's sequences) and the slot layout
stacked slot by slot.  ``host_partitions`` and ``slot_batch`` must equal
them bit for bit: two workers computing the same partition see the same
bytes, and the chip benchmark's reference restates the same data.
"""
import sys
import types
from pathlib import Path

import jax
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench.references import dense_lm  # noqa: E402
from repro.data.pipeline import SyntheticLMDataset  # noqa: E402
from repro.launch import train as T  # noqa: E402

WORKERS, SLOTS = 6, 15
K = 2 * WORKERS
VOCAB = 100_352
KEYS = ("tokens", "labels", "weights")
DTYPES = {"tokens": np.int32, "labels": np.int32, "weights": np.float32}
KS = {"unsorted": [7, 0, 11, 3, 5], "single": [K - 1], "first": [0],
      "all": list(range(K))}


def one_partition(ds, epoch: int, k: int) -> dict:
    """The per-partition generator, restated: partition ``k`` alone."""
    rng = np.random.default_rng((ds.seed * 1_000_003 + epoch) * 131_071 + k)
    B, S, V = ds.n, ds.seq_len, ds.vocab
    toks = np.empty((B, S), np.int64)
    toks[:, 0] = rng.integers(0, V, size=B)
    noise = rng.random((B, S)) < 0.15
    rand_tok = rng.integers(0, V, size=(B, S))
    for t in range(1, S):
        nxt = ds._trans[toks[:, t - 1]]
        toks[:, t] = np.where(noise[:, t], rand_tok[:, t], nxt)
    labels = np.concatenate([toks[:, 1:], toks[:, :1]], axis=1)
    w = np.ones((B, S), np.float32)
    w[:, -1] = 0.0
    return {"tokens": toks.astype(np.int32),
            "labels": labels.astype(np.int32), "weights": w / w.sum()}


def stacked_layout(ds, plan, step: int) -> dict:
    """The slot layout stacked slot by slot, restated: (M, n_slots, b, S),
    padding slots (partition -1) all zeros."""
    ks = np.unique(plan.slot_partition[plan.slot_partition >= 0])
    parts = {int(k): one_partition(ds, step, int(k)) for k in ks}
    empty = {key: np.zeros((ds.n, ds.seq_len), DTYPES[key]) for key in KEYS}
    return {key: np.stack([
        np.stack([parts[int(k)][key] if k >= 0 else empty[key]
                  for k in row]) for row in plan.slot_partition])
        for key in KEYS}


def dataset(b: int, S: int) -> SyntheticLMDataset:
    return SyntheticLMDataset(K, examples_per_partition=b, seq_len=S,
                              vocab=VOCAB)


@pytest.fixture(scope="module")
def plans():
    """The benchmark cell's runtime (6 workers, 15 slots), epochs 0–11."""
    rt = T.coded_runtime(WORKERS, n_slots=SLOTS)
    return [rt.run_epoch(e).plan for e in range(12)]


@pytest.mark.parametrize("ks", sorted(KS))
@pytest.mark.parametrize("b,S", [(1, 16), (3, 16), (1, 128), (3, 128)])
def test_host_partitions_equal_the_per_partition_generator(b, S, ks):
    ds = dataset(b, S)
    for epoch in (0, 7, 1234):
        got = ds.host_partitions(epoch, KS[ks])
        for key in KEYS:
            assert got[key].shape == (len(KS[ks]), b, S)
            assert got[key].dtype == DTYPES[key]
        for i, k in enumerate(KS[ks]):
            want = one_partition(ds, epoch, k)
            for key in KEYS:
                assert np.array_equal(got[key][i], want[key]), (epoch, k, key)


@pytest.mark.parametrize("b,S", [(1, 16), (3, 128)])
def test_host_partitions_equal_the_benchmark_reference(b, S):
    """``dense_lm.lm_partition`` restates the data for the chip benchmark's
    reference; its weights are the token mask, the dataset's that mask
    over its sum."""
    ds = dataset(b, S)
    for epoch, k in ((0, 0), (5, K - 1), (1234, 4)):
        got = ds.host_partitions(epoch, [k])
        ref = dense_lm.lm_partition(VOCAB, S, b, 0, epoch, k)
        assert np.array_equal(got["tokens"][0], ref["tokens"])
        assert np.array_equal(got["labels"][0], ref["labels"])
        assert np.array_equal(got["weights"][0],
                              ref["weights"] / ref["weights"].sum())


def test_a_partition_does_not_depend_on_its_company():
    ds = dataset(3, 16)
    alone = {k: ds.host_partitions(2, [k]) for k in range(K)}
    together = ds.host_partitions(2, list(range(K))[::-1])
    for i, k in enumerate(range(K)[::-1]):
        for key in KEYS:
            assert np.array_equal(together[key][i], alone[k][key][0])


def test_host_partitions_are_host_arrays():
    got = dataset(1, 16).host_partitions(3, [4, 1])
    assert set(got) == set(KEYS)
    assert all(type(v) is np.ndarray for v in got.values())


def test_no_partitions_give_empty_arrays():
    got = dataset(3, 16).host_partitions(0, [])
    for key in KEYS:
        assert got[key].shape == (0, 3, 16)
        assert got[key].dtype == DTYPES[key]


@pytest.mark.parametrize("b,S", [(1, 16), (3, 128)])
def test_partition_is_device_arrays_with_the_same_bits(b, S):
    ds = dataset(b, S)
    for epoch, k in ((0, 0), (9, K - 1)):
        got = ds.partition(epoch, k)
        want = one_partition(ds, epoch, k)
        assert set(got) == set(KEYS)
        for key in KEYS:
            assert isinstance(got[key], jax.Array)
            assert got[key].shape == (b, S)
            assert got[key].dtype == DTYPES[key]
            assert np.array_equal(np.asarray(got[key]), want[key])


def test_replayed_plans_have_padding_and_shared_partitions(plans):
    """The plans the layout tests replay hold padding slots, and some
    hold a partition in more than one worker's slots."""
    padding = [bool((p.slot_partition < 0).any()) for p in plans]
    shared = []
    for p in plans:
        held = [set(row[row >= 0].tolist()) for row in p.slot_partition]
        shared.append(any(sum(k in h for h in held) > 1
                          for k in set().union(*held)))
    assert all(padding) and sum(shared) >= 2


@pytest.mark.parametrize("b,S", [(1, 16), (3, 16), (1, 128)])
@pytest.mark.parametrize("epoch", [0, 7, 8, 11])
def test_slot_batch_equals_the_stacked_layout(plans, epoch, b, S):
    ds = dataset(b, S)
    plan = plans[epoch]
    got = T.slot_batch(ds, plan, epoch)
    want = stacked_layout(ds, plan, epoch)
    M, n = plan.slot_partition.shape
    assert set(got) == set(KEYS)
    for key in KEYS:
        assert isinstance(got[key], jax.Array)
        assert got[key].shape == (M, n, b, S)
        assert got[key].dtype == DTYPES[key]
        assert np.array_equal(np.asarray(got[key]), want[key]), key


def test_slot_batch_of_padding_alone_is_zeros():
    ds = dataset(1, 16)
    plan = types.SimpleNamespace(slot_partition=np.full((2, 3), -1))
    got = T.slot_batch(ds, plan, 0)
    for key in KEYS:
        assert got[key].shape == (2, 3, 1, 16)
        assert got[key].dtype == DTYPES[key]
        assert not np.asarray(got[key]).any()


def test_slot_batch_is_built_on_the_host_and_sent_once(plans, monkeypatch):
    """The layout is numpy until one ``device_put`` of the whole dict."""
    sent = []
    put = jax.device_put

    def recorded(x, *a, **k):
        sent.append(x)
        return put(x, *a, **k)
    monkeypatch.setattr(jax, "device_put", recorded)
    ds = dataset(1, 16)
    got = T.slot_batch(ds, plans[7], 7)
    assert len(sent) == 1
    assert set(sent[0]) == set(KEYS)
    assert all(type(v) is np.ndarray for v in sent[0].values())
    for key in KEYS:
        assert np.array_equal(np.asarray(got[key]), sent[0][key])
