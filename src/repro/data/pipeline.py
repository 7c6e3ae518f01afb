"""Deterministic sharded data pipeline with K-way partitioning (paper §III.1).

The dataset D is split into K non-overlapping equal-size partitions
D = {D_1..D_K}; the coded step assigns each worker a set of partition
*slots* with coefficients.  The pipeline is:

  * deterministic: (epoch, partition) -> examples, each partition drawn
    from its own numpy ``default_rng`` seeded by (seed, epoch, partition),
    so every worker can materialize any partition without coordination —
    exactly what coded redundancy needs (two workers computing the same
    partition MUST see identical bytes);
  * offline: synthetic token streams (language-model cells) or labeled
    feature vectors (the paper's MNIST/CIFAR-like FEL experiments) — no
    downloads in this container;
  * restart-safe: state is (epoch, step) only.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["PartitionedDataset", "SyntheticLMDataset",
           "SyntheticClassificationDataset"]


@dataclasses.dataclass(frozen=True)
class PartitionSpec_:
    K: int
    examples_per_partition: int


class PartitionedDataset:
    """Base: deterministic partition -> examples mapping."""

    def __init__(self, K: int, examples_per_partition: int, seed: int = 0):
        self.K = K
        self.n = examples_per_partition
        self.seed = seed

    def partition(self, epoch: int, k: int):
        raise NotImplementedError

    def host_partitions(self, epoch: int, ks) -> dict:
        """Partitions ``ks`` of ``epoch`` as host arrays, stacked in the
        order given: each key ``(len(ks), ...)``, one :meth:`partition`
        per key (no ``ks``: empty arrays of partition 0's shapes)."""
        ks = [int(k) for k in ks]
        parts = [self.partition(epoch, k) for k in ks or [0]]
        return {key: np.stack([np.asarray(p[key]) for p in parts])[:len(ks)]
                for key in parts[0]}


class SyntheticLMDataset(PartitionedDataset):
    """Procedural token sequences with learnable structure.

    Tokens follow a noisy Markov chain determined by the seed, giving the
    model something learnable (loss decreases) while being fully offline.
    """

    def __init__(self, K: int, examples_per_partition: int, seq_len: int,
                 vocab: int, seed: int = 0, order: int = 1):
        super().__init__(K, examples_per_partition, seed)
        self.seq_len = seq_len
        self.vocab = vocab
        rng = np.random.default_rng(seed)
        # sparse-ish transition table for structure
        self._trans = rng.integers(0, vocab, size=(vocab,)).astype(np.int64)

    def host_partitions(self, epoch: int, ks) -> dict:
        """Partitions ``ks`` of ``epoch`` as host arrays, stacked in the
        order given: {'tokens', 'labels'} int32 and {'weights'} f32, each
        (len(ks), b, S).

        Each partition draws from its own generator, in the same order
        (first tokens, noise mask, random tokens), so a partition's bytes
        do not depend on which others are drawn with it; only the Markov
        recurrence over positions runs over all of them at once.
        """
        ks = [int(k) for k in ks]
        P, B, S, V = len(ks), self.n, self.seq_len, self.vocab
        toks = np.empty((P, B, S), np.int64)
        noise = np.empty((P, B, S), bool)
        rand_tok = np.empty((P, B, S), np.int64)
        for i, k in enumerate(ks):
            rng = np.random.default_rng(
                (self.seed * 1_000_003 + epoch) * 131_071 + k)
            toks[i, :, 0] = rng.integers(0, V, size=B)
            noise[i] = rng.random((B, S)) < 0.15
            rand_tok[i] = rng.integers(0, V, size=(B, S))
        for t in range(1, S):
            toks[:, :, t] = np.where(noise[:, :, t], rand_tok[:, :, t],
                                     self._trans[toks[:, :, t - 1]])
        labels = np.concatenate([toks[:, :, 1:], toks[:, :, :1]], axis=2)
        w = np.ones((B, S), np.float32)
        w[:, -1] = 0.0                      # no target for last position
        return {"tokens": toks.astype(np.int32),
                "labels": labels.astype(np.int32),
                "weights": np.broadcast_to(w / w.sum(), (P, B, S)).copy()}

    def partition(self, epoch: int, k: int) -> dict:
        """Returns {'tokens','labels','weights'} for partition k, as device
        arrays (:meth:`host_partitions` of the one partition)."""
        return {key: jnp.asarray(v[0])
                for key, v in self.host_partitions(epoch, [k]).items()}


class SyntheticClassificationDataset(PartitionedDataset):
    """MNIST/CIFAR-like: gaussian-cluster images + teacher labels.

    Used by the paper-faithful FEL experiments (benchmarks/paper_*).
    """

    def __init__(self, K: int, examples_per_partition: int, dim: int = 784,
                 n_classes: int = 10, seed: int = 0):
        super().__init__(K, examples_per_partition, seed)
        self.dim = dim
        self.n_classes = n_classes
        rng = np.random.default_rng(seed + 7)
        self._centers = rng.standard_normal((n_classes, dim)).astype(
            np.float32) * 2.0

    def partition(self, epoch: int, k: int) -> dict:
        rng = np.random.default_rng(
            (self.seed * 1_000_003 + epoch) * 131_071 + k)
        B = self.n
        y = rng.integers(0, self.n_classes, size=B)
        x = self._centers[y] + rng.standard_normal(
            (B, self.dim)).astype(np.float32)
        return {"x": jnp.asarray(x), "y": jnp.asarray(y, jnp.int32)}
