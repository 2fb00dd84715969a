"""The numbers that decide ``correct``."""
import math

import pytest

from bench import check


def _readings(loss, first, change, grad0=None):
    out = {"loss": loss, "first": first, "change": change}
    if grad0 is not None:
        out["grad0"] = grad0
    return out


def test_gaps_use_the_median_leaf_as_a_floor():
    ref = _readings([2.0, 1.0], {"a": 10.0, "b": 10.0, "c": 0.01}, {"a": 4.0, "b": 4.0, "c": 4.0},
                    grad0={"a": 1.0, "b": 1.0, "c": 1.0})
    prog = _readings([2.002, 1.0], {"a": 10.0, "b": 10.0, "c": 0.02}, {"a": 4.0, "b": 4.4, "c": 4.0})
    nums = check.numbers(prog, ref)
    assert nums["loss_gap"] == pytest.approx(1e-3)
    # leaf c differs by 0.01 against the median leaf's norm of 10
    assert nums["first_gap"] == pytest.approx(1e-3)
    assert nums["change_gap"] == pytest.approx(0.1)


def test_leaves_with_no_gradient_are_left_out():
    ref = _readings([1.0], {"a": 1.0, "b": 1.0, "bias": 1e-9}, {"a": 1.0, "b": 1.0, "bias": 1e-9},
                    grad0={"a": 1.0, "b": 1.0, "bias": 1e-7})
    prog = _readings([1.0], {"a": 1.0, "b": 1.0, "bias": 5.0}, {"a": 1.0, "b": 1.0, "bias": 5.0})
    assert check.numbers(prog, ref)["change_gap"] == 0.0


def test_verdict_needs_every_number_finite_and_within():
    limits = {"loss_gap": 1e-2, "first_gap": 1e-2, "change_gap": 1e-2}
    ok = {"loss_gap": 1e-3, "first_gap": 1e-3, "change_gap": 1e-3}
    assert check.verdict(ok, limits)
    assert not check.verdict({**ok, "change_gap": 0.5}, limits)
    assert not check.verdict({**ok, "loss_gap": math.nan}, limits)
