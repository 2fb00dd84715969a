"""Synthetic datasets with *controllable non-IIDness*.

The paper's experiments hinge on label-skewed partitions (simple-NIID,
edge-IID, edge-NIID). Offline we cannot load MNIST/CIFAR, so we generate
datasets whose class structure supports exactly the same partition
protocols and whose difficulty is tunable:

* ``clustered_gaussians`` — a C-class Gaussian-mixture classification
  problem (stands in for MNIST/CIFAR in the paper-reproduction benches:
  same 10-class structure, same partition semantics, learnable by the same
  CNN/MLP family in a few hundred steps).
* ``token_corpus`` — a Markov-teacher LM corpus over `vocab` tokens with
  per-class transition kernels, so label-skew partitions induce genuinely
  divergent client gradients (δ, Δ > 0) for the LM architectures.

Everything is generated with numpy RNG (seeded, reproducible) and returned
as plain numpy arrays; the pipeline layer shards/batches them.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import numpy as np


@dataclasses.dataclass(frozen=True)
class ClassificationData:
    x: np.ndarray  # (n, ...) float32
    y: np.ndarray  # (n,) int32

    @property
    def num_samples(self) -> int:
        return int(self.y.shape[0])

    @property
    def num_classes(self) -> int:
        return int(self.y.max()) + 1


def clustered_gaussians(
    rng: np.random.Generator,
    *,
    num_samples: int = 10_000,
    num_classes: int = 10,
    dim: Tuple[int, ...] = (28, 28, 1),
    class_sep: float = 3.0,
    noise: float = 1.0,
) -> ClassificationData:
    """C well-separated Gaussian clusters in a flattened image space.

    class_sep/noise tune difficulty; with the defaults a small CNN reaches
    >95% in a few dozen steps, giving the paper's T_alpha/E_alpha benches a
    fast, deterministic accuracy curve.
    """
    d = int(np.prod(dim))
    centers = rng.normal(0.0, class_sep, size=(num_classes, d))
    y = rng.integers(0, num_classes, size=num_samples).astype(np.int32)
    x = centers[y] + rng.normal(0.0, noise, size=(num_samples, d))
    return ClassificationData(x=x.reshape((num_samples, *dim)).astype(np.float32), y=y)


@dataclasses.dataclass(frozen=True)
class TokenCorpus:
    tokens: np.ndarray  # (n, seq_len+1) int32 — inputs[t], targets shifted
    labels: np.ndarray  # (n,) int32 "topic" class of each sequence

    @property
    def num_samples(self) -> int:
        return int(self.tokens.shape[0])

    @property
    def num_classes(self) -> int:
        return int(self.labels.max()) + 1


# Largest vocab whose dense (classes, vocab, vocab) teacher is drawn whole;
# above it each teacher row keeps SPARSE_SUCCESSORS successor tokens, so a
# 32k vocab costs classes·vocab·K entries instead of classes·vocab² (64 GiB).
DENSE_VOCAB_MAX = 2048
SPARSE_SUCCESSORS = 64


def token_corpus(
    rng: np.random.Generator,
    *,
    num_sequences: int = 2048,
    seq_len: int = 128,
    vocab: int = 256,
    num_classes: int = 10,
    concentration: float = 0.3,
) -> TokenCorpus:
    """Markov-teacher corpus: each class has its own sparse transition kernel.

    Lower `concentration` -> sparser kernels -> more divergent per-class
    gradients (higher δ/Δ under label-skewed partitions). Vocabularies above
    ``DENSE_VOCAB_MAX`` draw each kernel row over ``SPARSE_SUCCESSORS``
    random successor tokens instead of the whole vocab.
    """
    sparse = vocab > DENSE_VOCAB_MAX
    width = SPARSE_SUCCESSORS if sparse else vocab
    # Per-class transition matrices, Dirichlet rows (sparse-ish).
    kernels = rng.dirichlet(np.full(width, concentration), size=(num_classes, vocab))
    successors = (
        rng.integers(0, vocab, size=(num_classes, vocab, width), dtype=np.int32) if sparse else None
    )
    labels = rng.integers(0, num_classes, size=num_sequences).astype(np.int32)
    toks = np.empty((num_sequences, seq_len + 1), np.int32)
    toks[:, 0] = rng.integers(0, vocab, size=num_sequences)
    for t in range(seq_len):
        # vectorized per-class sampling
        p = kernels[labels, toks[:, t]]  # (n, width)
        cdf = np.cumsum(p, axis=1)
        u = rng.random((num_sequences, 1))
        pick = (u < cdf).argmax(axis=1)
        toks[:, t + 1] = successors[labels, toks[:, t], pick] if sparse else pick
    return TokenCorpus(tokens=toks, labels=labels)


def embedding_corpus(
    rng: np.random.Generator,
    *,
    num_sequences: int = 512,
    seq_len: int = 64,
    d_model: int = 64,
    num_classes: int = 10,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Stub-frontend corpus for [vlm]/[audio] archs: precomputed frame/patch
    embeddings (float) + integer targets. Returns (embeds, targets, labels)."""
    centers = rng.normal(0, 1, size=(num_classes, d_model))
    labels = rng.integers(0, num_classes, size=num_sequences).astype(np.int32)
    embeds = centers[labels][:, None, :] + 0.3 * rng.normal(
        0, 1, size=(num_sequences, seq_len, d_model)
    )
    targets = rng.integers(0, num_classes * 8, size=(num_sequences, seq_len)).astype(np.int32)
    return embeds.astype(np.float32), targets, labels
