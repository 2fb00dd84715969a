"""A whole run with the timed path broken underneath must come out not
correct: a step that hands its state back unchanged, half of every batch
left out (the mean taken over the rest), and on the multi-chip cell the
exchange between chips left out. (Training produces no tokens or answers
that could be altered one by one; that fault does not apply.)"""
import jax
import pytest

import repro.fed.engine as engine


def _wrap_super_round(monkeypatch, transform):
    build = engine.build_super_round

    def broken(*a, **k):
        return transform(build(*a, **k))

    monkeypatch.setattr(engine, "build_super_round", broken)


def test_state_unchanged(monkeypatch, run_small):
    def transform(fn):
        def step(state, batches, masks=None):
            return state, fn(state, batches, masks)[1]
        return step

    _wrap_super_round(monkeypatch, transform)
    rc, out = run_small("lm100m.s128")
    assert rc == 0 and out["correct"] is False, out["check"]
    assert out["check"]["change_gap"]["value"] == pytest.approx(1.0)


# the CNN's one-class clients give every row of a batch the same label, so
# its loss hardly moves with half the rows: the gradient's gaps catch it
@pytest.mark.parametrize("cell", ["lm100m.s128", "cifar_cnn.k5x10"])
def test_half_batch(cell, monkeypatch, run_small):
    def transform(fn):
        def step(state, batches, masks=None):
            half = jax.tree_util.tree_map(lambda x: x[:, :, :, : x.shape[3] // 2], batches)
            return fn(state, half, masks)
        return step

    _wrap_super_round(monkeypatch, transform)
    rc, out = run_small(cell)
    assert rc == 0 and out["correct"] is False, out["check"]


def test_exchange_left_out(monkeypatch, run_small):
    # the one cross-chip collective of the sharded cloud sync becomes a no-op
    monkeypatch.setattr(jax.lax, "psum", lambda x, axis_name, **kw: x)
    rc, out = run_small("lm100m.s128.mesh4")
    assert rc == 0 and out["correct"] is False, out["check"]


def test_sharded_run_is_correct(run_small):
    rc, out = run_small("lm100m.s128.mesh4")
    assert rc == 0 and out["correct"] is True, out["check"]
