"""Data pipeline: partition protocols (Section IV-A) + restart-safe batching."""
import numpy as np
import pytest
from repro.testing import given, settings, st

from repro.data import partition as pt
from repro.data.pipeline import FederatedBatcher
from repro.data.synthetic import clustered_gaussians, token_corpus


def labels_10(rng, n=2000):
    return rng.integers(0, 10, size=n).astype(np.int64)


def test_iid_balanced(rng):
    labels = labels_10(rng)
    parts = pt.partition("iid", labels, 5, 10, rng)
    sizes = [len(p) for p in parts]
    assert max(sizes) - min(sizes) <= 1
    assert sum(sizes) == len(labels)


def test_simple_niid_two_classes(rng):
    labels = np.sort(labels_10(rng))
    parts = pt.partition("simple_niid", labels, 5, 10, rng)
    stats = pt.partition_stats(parts, labels)
    classes_per_client = (stats > 0).sum(axis=1)
    # shard edges may split a class boundary: 2 (occasionally 3) classes
    assert classes_per_client.max() <= 3
    assert np.median(classes_per_client) <= 2


def test_edge_iid_structure(rng):
    labels = labels_10(rng)
    parts = pt.partition("edge_iid", labels, 5, 10, rng)
    stats = pt.partition_stats(parts, labels)
    # each client: exactly one class
    assert ((stats > 0).sum(axis=1) == 1).all()
    # each edge: all 10 classes covered (paper: "10 clients with different classes")
    for e in range(5):
        edge = stats[e * 10 : (e + 1) * 10].sum(axis=0)
        assert (edge > 0).all()


def test_edge_niid_structure(rng):
    labels = labels_10(rng)
    parts = pt.partition("edge_niid", labels, 5, 10, rng)
    stats = pt.partition_stats(parts, labels)
    assert ((stats > 0).sum(axis=1) == 1).all()
    for e in range(5):
        edge = stats[e * 10 : (e + 1) * 10].sum(axis=0)
        assert (edge > 0).sum() == 5  # paper: 5 classes per edge


@given(num_edges=st.integers(2, 5), cpe=st.integers(2, 8), seed=st.integers(0, 100))
@settings(max_examples=20, deadline=None)
def test_partition_property_disjoint_cover(num_edges, cpe, seed):
    """Any protocol: client index sets are disjoint (IID/simple split the
    full dataset; class-per-client protocols may subsample evenly)."""
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, 10, size=1200)
    for kind in ("iid", "simple_niid"):
        parts = pt.partition(kind, labels, num_edges, cpe, rng)
        flat = np.concatenate(parts)
        assert len(np.unique(flat)) == len(flat)
        assert len(flat) == len(labels)


def test_synthetic_learnable_structure(rng):
    data = clustered_gaussians(rng, num_samples=500, num_classes=4, dim=(8,), class_sep=4.0)
    # nearest-centroid on the generating structure is >90% accurate
    cents = np.stack([data.x[data.y == c].mean(axis=0) for c in range(4)])
    pred = np.argmin(((data.x[:, None] - cents[None]) ** 2).sum(-1), axis=1)
    assert (pred == data.y).mean() > 0.9


def test_token_corpus_class_structure(rng):
    corp = token_corpus(rng, num_sequences=64, seq_len=32, vocab=50, num_classes=3)
    assert corp.tokens.shape == (64, 33)
    assert corp.tokens.max() < 50 and corp.tokens.min() >= 0


def test_token_corpus_large_vocab_sparse_teacher(rng):
    """Past DENSE_VOCAB_MAX each teacher row keeps SPARSE_SUCCESSORS
    successors: a 32k vocab stays cheap, and every transition lands in its
    (class, token) row's successor set."""
    from repro.data import synthetic

    vocab = 32768
    corp = token_corpus(rng, num_sequences=32, seq_len=16, vocab=vocab, num_classes=2)
    assert corp.tokens.shape == (32, 17)
    assert corp.tokens.max() < vocab and corp.tokens.min() >= 0
    # the same (class, token) state only ever steps to a bounded successor set
    nxt = {}
    for seq, label in zip(corp.tokens, corp.labels):
        for a, b in zip(seq[:-1], seq[1:]):
            nxt.setdefault((label, a), set()).add(b)
    assert max(len(v) for v in nxt.values()) <= synthetic.SPARSE_SUCCESSORS


def test_batcher_restart_safety(rng):
    x = rng.normal(size=(200, 3)).astype(np.float32)
    y = rng.integers(0, 10, size=200).astype(np.int32)
    parts = pt.partition("iid", y, 2, 2, rng)
    mk = lambda: FederatedBatcher({"x": x, "y": y}, parts, batch_size=8, seed=7)

    b1 = mk()
    for _ in range(10):
        b1.next_batch()
    saved = b1.state_dict()
    want = [b1.next_batch() for _ in range(5)]

    b2 = mk()
    b2.load_state_dict(saved)
    got = [b2.next_batch() for _ in range(5)]
    for wb, gb in zip(want, got):
        np.testing.assert_array_equal(wb["x"], gb["x"])


def test_batcher_stacked_shapes(rng):
    x = rng.normal(size=(100, 4)).astype(np.float32)
    y = rng.integers(0, 10, size=100).astype(np.int32)
    parts = pt.partition("iid", y, 2, 3, rng)
    b = FederatedBatcher({"x": x, "y": y}, parts, batch_size=4)
    batch = b.next_batch()
    assert batch["x"].shape == (6, 4, 4)
    multi = b.next_batches(3)
    assert multi["x"].shape == (3, 6, 4, 4)
