"""The comparison that decides ``correct``.

Readings are taken from the program's own first three cloud intervals (the
set-up drives the window's compiled interval through them) and from the
plain reference over the same seed, weights and rows:

    loss    the loss of every edge round (mean over its local steps and the
            clients), 3 * kappa2 values
    first   per parameter leaf, the norm of what the local optimizer made of
            the first interval's gradients: Adam's first moment, or for plain
            SGD the parameters' change (the summed scaled gradients)
    change  per parameter leaf, the norm of the parameters' change over the
            three intervals, taken before the fourth donates them
    grad0   (reference only) per leaf, the norm of the first local step's
            gradient: the rule that leaves out leaves whose gradient is
            nought to rounding

Each number is the worst over rounds or leaves: for a leaf, the gap between
the program's norm and the reference's, over the larger of the reference's
norm of that leaf and of the median leaf.
"""
from __future__ import annotations

import math
from typing import Dict

import numpy as np

# a leaf whose first reference gradient is under this share of the median
# leaf's moves by round-off alone and is not compared
TINY_GRAD = 1e-3
NUMBERS = ("loss_gap", "first_gap", "change_gap")


def leaf_norms(tree, minus=None) -> Dict[str, float]:
    """{leaf path: f32 norm over the whole (stacked) leaf}, or of the leaf
    less ``minus``'s matching unstacked leaf, computed in one program on the
    devices the leaves live on."""
    import jax
    import jax.numpy as jnp

    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    subs = [None] * len(flat) if minus is None else jax.tree_util.tree_leaves(minus)

    def norms(xs, ms):
        return [jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32) - (0.0 if m is None else m[None]))))
                for x, m in zip(xs, ms)]

    out = jax.jit(norms)([x for _, x in flat], subs)
    return {jax.tree_util.keystr(p): float(n) for (p, _), n in zip(flat, out)}


def kept_leaves(grad0: Dict[str, float]):
    med = float(np.median(list(grad0.values())))
    return sorted(k for k, v in grad0.items() if v >= TINY_GRAD * med)


def norm_gap(prog: Dict[str, float], ref: Dict[str, float], keep) -> float:
    med = float(np.median([ref[k] for k in keep]))
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in keep)


def numbers(prog: dict, ref: dict) -> Dict[str, float]:
    lp, lr = np.asarray(prog["loss"], np.float64), np.asarray(ref["loss"], np.float64)
    if lp.shape != lr.shape:
        raise ValueError(f"loss readings differ in count: {lp.shape} vs {lr.shape}")
    keep = kept_leaves(ref["grad0"])
    return {
        "loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
        "first_gap": norm_gap(prog["first"], ref["first"], keep),
        "change_gap": norm_gap(prog["change"], ref["change"], keep),
    }


def verdict(nums: Dict[str, float], limits: Dict[str, float]) -> bool:
    return all(math.isfinite(nums[k]) and nums[k] <= limits[k] for k in NUMBERS)
