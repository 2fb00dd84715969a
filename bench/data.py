"""The benchmark's one input generator: a traffic file's parameters and a
seed in, a federated dataset out.

A traffic file (``bench/traffic/<name>.json``) names the topology, the
aggregation schedule and the data each client holds:

    num_edges, clients_per_edge   the two-level tree (N = their product)
    kappas                        [kappa1, kappa2]: local steps per edge sync,
                                  edge syncs per cloud sync
    mesh_devices                  0: one chip; K: the client axis over K chips
    batch_size                    samples per client per local step
    samples_per_client            each client's local dataset size
    partition                     "edge_niid" (one class per client, each edge
                                  covers half its clients' worth of classes)
                                  or "iid"
    num_classes                   classes (image labels, or token "topics")
    dataset                       "tokens" or "images"
    tokens:  seq_len, concentration   Markov-teacher sequences of seq_len + 1
    images:  image_shape, class_sep, noise   clustered Gaussian images

The generators follow ``repro.data.synthetic`` and ``repro.data.partition``
(the Markov teacher, the Gaussian clusters, the edge-NIID dealing) so the
program's own data layer sees the traffic its users configure; they are
copied here so that the yardstick cannot move with the program. Everything
is drawn from ``numpy.random.default_rng(seed)``.
"""
from __future__ import annotations

from typing import List

import numpy as np

# above this vocabulary each teacher row keeps SPARSE_SUCCESSORS successors
DENSE_VOCAB_MAX = 2048
SPARSE_SUCCESSORS = 64


def token_corpus(rng, *, num_sequences, seq_len, vocab, num_classes, concentration):
    """(tokens (n, seq_len + 1) int32, topic labels (n,) int32): each topic
    has its own sparse Markov transition kernel."""
    sparse = vocab > DENSE_VOCAB_MAX
    width = SPARSE_SUCCESSORS if sparse else vocab
    kernels = rng.dirichlet(np.full(width, concentration), size=(num_classes, vocab))
    successors = (
        rng.integers(0, vocab, size=(num_classes, vocab, width), dtype=np.int32) if sparse else None
    )
    labels = rng.integers(0, num_classes, size=num_sequences).astype(np.int32)
    toks = np.empty((num_sequences, seq_len + 1), np.int32)
    toks[:, 0] = rng.integers(0, vocab, size=num_sequences)
    for t in range(seq_len):
        cdf = np.cumsum(kernels[labels, toks[:, t]], axis=1)
        pick = (rng.random((num_sequences, 1)) < cdf).argmax(axis=1)
        toks[:, t + 1] = successors[labels, toks[:, t], pick] if sparse else pick
    return toks, labels


def gaussian_images(rng, *, num_samples, num_classes, shape, class_sep, noise):
    """(images (n, *shape) float32, labels (n,) int32): one Gaussian cluster
    per class in the flattened image space."""
    d = int(np.prod(shape))
    centers = rng.normal(0.0, class_sep, size=(num_classes, d)).astype(np.float32)
    labels = rng.integers(0, num_classes, size=num_samples).astype(np.int32)
    x = rng.standard_normal((num_samples, d), dtype=np.float32)
    x *= np.float32(noise)
    x += centers[labels]
    return x.reshape((num_samples, *shape)), labels


def partition(kind: str, labels, num_edges: int, clients_per_edge: int, rng) -> List[np.ndarray]:
    """Per-client sample indices, every client the same count."""
    n = num_edges * clients_per_edge
    per_client = labels.shape[0] // n
    if kind == "iid":
        perm = rng.permutation(labels.shape[0])
        return [np.sort(perm[i * per_client:(i + 1) * per_client]) for i in range(n)]
    if kind != "edge_niid":
        raise ValueError(f"unknown partition {kind!r}")
    num_classes = int(labels.max()) + 1
    pools = [rng.permutation(np.where(labels == c)[0]) for c in range(num_classes)]
    cursors = [0] * num_classes
    cpe = max(clients_per_edge // 2, 1)
    out = []
    for edge in range(num_edges):
        base = (edge * cpe) % num_classes
        for j in range(clients_per_edge):
            c = (base + j % cpe) % num_classes
            idx = np.arange(cursors[c], cursors[c] + per_client) % pools[c].shape[0]
            cursors[c] = (cursors[c] + per_client) % pools[c].shape[0]
            out.append(np.sort(pools[c][idx]))
    return out


def make_dataset(traffic: dict, seed: int) -> dict:
    """{"arrays": {name: array}, "parts": [indices per client]} for a
    traffic file's parameters and a seed."""
    rng = np.random.default_rng(seed)
    n = traffic["num_edges"] * traffic["clients_per_edge"]
    total = n * traffic["samples_per_client"]
    if traffic["dataset"] == "tokens":
        toks, labels = token_corpus(
            rng, num_sequences=total, seq_len=traffic["seq_len"], vocab=traffic["vocab"],
            num_classes=traffic["num_classes"], concentration=traffic["concentration"],
        )
        arrays = {"tokens": toks}
    elif traffic["dataset"] == "images":
        x, labels = gaussian_images(
            rng, num_samples=total, num_classes=traffic["num_classes"],
            shape=tuple(traffic["image_shape"]), class_sep=traffic["class_sep"],
            noise=traffic["noise"],
        )
        arrays = {"inputs": x, "targets": labels}
    else:
        raise ValueError(f"unknown dataset {traffic['dataset']!r}")
    parts = partition(traffic["partition"], labels, traffic["num_edges"], traffic["clients_per_edge"], rng)
    return {"arrays": arrays, "parts": parts}


class BatchOrder:
    """Which samples each client draws at each local step: a fresh
    permutation of its indices every local epoch, from
    ``default_rng((seed, client, epoch))``, partial batches never emitted.
    The draw order of a restart-safe federated batcher, written out so the
    plain reference can follow the same rows."""

    def __init__(self, parts, batch_size: int, seed: int):
        self.parts = [np.asarray(p) for p in parts]
        self.b = batch_size
        self.seed = seed
        self.epoch = [0] * len(parts)
        self.pos = [0] * len(parts)
        self.order = [self._perm(i) for i in range(len(parts))]

    def _perm(self, i):
        return np.random.default_rng((self.seed, i, self.epoch[i])).permutation(self.parts[i])

    def next_rows(self) -> np.ndarray:
        """(N, b) sample indices of the next local step."""
        rows = []
        for i in range(len(self.parts)):
            if self.pos[i] + self.b > self.order[i].shape[0]:
                self.epoch[i] += 1
                self.pos[i] = 0
                self.order[i] = self._perm(i)
            rows.append(self.order[i][self.pos[i]:self.pos[i] + self.b])
            self.pos[i] += self.b
        return np.stack(rows)
