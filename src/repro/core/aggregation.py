"""Weighted model-aggregation primitives (the paper's EdgeAggregation /
CloudAggregation, Algorithm 1 lines 25-31) as pytree operators.

Representation
--------------
All federated parameters carry a leading **client axis** of size N, laid
out so that clients of the same aggregation group are contiguous:

    leaf.shape == (N, *param_shape)

Two group encodings are supported:

* **uniform** — ``num_groups`` equal contiguous blocks (the paper's
  num_edges × clients_per_edge tree): ``grouped_weighted_mean`` reduces via
  a (G, C, ...) reshape.
* **ragged**  — an explicit sorted ``segment_ids`` vector mapping each
  client to its group (arbitrary fan-out, any level of a
  ``core.hierarchy.HierarchySpec``): ``segment_weighted_mean`` reduces via
  ``jax.ops.segment_sum`` and gathers the group means back. When the
  segment ids describe equal contiguous blocks it dispatches to the
  uniform reshape path, so the paper topology pays nothing for the
  generality.

Under a mesh sharding of `P(("pod","data"), ...)` these lower to *grouped*
all-reduces over exactly the group's devices (intra-pod ICI) and a global
all-reduce (crossing the pod/DCN axis) respectively — the paper's
two-tier communication pattern, verified in the dry-run HLO.

Fault tolerance: every operator takes an optional survival ``mask`` (N,) and
renormalizes over surviving clients, matching the paper's weighted mean
restricted to the participating set. A group with zero survivors keeps its
members' current parameters (they continue local training and rejoin at the
next aggregation).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import hier_aggregate as _ha
from repro.kernels import ops

PyTree = Any


def _bcast_weights(w: jnp.ndarray, leaf: jnp.ndarray) -> jnp.ndarray:
    """Reshape (…,) weights to broadcast against leaf (…, *param_dims)."""
    return w.reshape(w.shape + (1,) * (leaf.ndim - w.ndim)).astype(jnp.float32)


def weighted_mean(tree: PyTree, weights: jnp.ndarray, mask: Optional[jnp.ndarray] = None) -> PyTree:
    """Cloud aggregation: weighted mean over the full client axis, broadcast back.

    weights: (N,) client dataset sizes |D_i|. mask: optional (N,) in {0,1}.
    """
    w = weights.astype(jnp.float32)
    if mask is not None:
        w = w * mask.astype(jnp.float32)
    denom = jnp.sum(w)

    def leaf_fn(x):
        wb = _bcast_weights(w, x)
        num = jnp.sum(x.astype(jnp.float32) * wb, axis=0, keepdims=True)
        safe = jnp.where(denom > 0, denom, 1.0)
        mean = num / safe
        mean = jnp.broadcast_to(mean, x.shape)
        # zero survivors anywhere -> keep current params
        return jnp.where(denom > 0, mean, x.astype(jnp.float32)).astype(x.dtype)

    return jax.tree_util.tree_map(leaf_fn, tree)


def cloud_model(tree: PyTree, weights: jnp.ndarray, mask: Optional[jnp.ndarray] = None) -> PyTree:
    """The single cloud model (the eval/serving path): the weighted mean over
    the client axis *without* broadcasting back to (N, ...).

    Numerically equal to ``weighted_mean(tree, weights, mask)[0]`` but never
    materializes the N stacked copies of the mean — leaves come back shaped
    (*param_shape,). Zero survivors keeps client 0's current parameters,
    matching the broadcast operator's keep-and-slice behavior.
    """
    w = weights.astype(jnp.float32)
    if mask is not None:
        w = w * mask.astype(jnp.float32)
    denom = jnp.sum(w)

    def leaf_fn(x):
        wb = _bcast_weights(w, x)
        num = jnp.sum(x.astype(jnp.float32) * wb, axis=0)
        safe = jnp.where(denom > 0, denom, 1.0)
        mean = num / safe
        return jnp.where(denom > 0, mean, x[0].astype(jnp.float32)).astype(x.dtype)

    return jax.tree_util.tree_map(leaf_fn, tree)


def grouped_weighted_mean(
    tree: PyTree,
    weights: jnp.ndarray,
    num_groups: int,
    mask: Optional[jnp.ndarray] = None,
) -> PyTree:
    """Edge aggregation: per-edge weighted mean over contiguous client blocks.

    tree leaves: (N, ...); weights/mask: (N,); N must be divisible by num_groups.
    """
    n = weights.shape[0]
    if n % num_groups:
        raise ValueError(f"N={n} not divisible by num_groups={num_groups}")
    group_size = n // num_groups
    w = weights.astype(jnp.float32)
    if mask is not None:
        w = w * mask.astype(jnp.float32)
    wg = w.reshape(num_groups, group_size)
    denom = jnp.sum(wg, axis=1, keepdims=True)  # (G, 1)
    safe = jnp.where(denom > 0, denom, 1.0)

    def leaf_fn(x):
        xg = x.reshape(num_groups, group_size, *x.shape[1:])
        wb = _bcast_weights(wg, xg)
        num = jnp.sum(xg.astype(jnp.float32) * wb, axis=1, keepdims=True)  # (G,1,...)
        mean = num / _bcast_weights(safe, num)
        mean = jnp.broadcast_to(mean, xg.shape)
        alive = _bcast_weights(denom > 0, xg)
        out = jnp.where(alive, mean, xg.astype(jnp.float32))
        return out.reshape(x.shape).astype(x.dtype)

    return jax.tree_util.tree_map(leaf_fn, tree)


def _static_uniform_groups(segment_ids, num_segments: int) -> Optional[int]:
    """If the segment ids are statically known to form equal contiguous
    blocks, return the block count (the uniform fast path); else None."""
    if isinstance(segment_ids, jax.core.Tracer):
        return None
    ids = np.asarray(segment_ids)
    n = ids.shape[0]
    if num_segments <= 0 or n % num_segments:
        return None
    uniform = np.repeat(np.arange(num_segments, dtype=ids.dtype), n // num_segments)
    return num_segments if np.array_equal(ids, uniform) else None


def segment_weighted_mean(
    tree: PyTree,
    weights: jnp.ndarray,
    segment_ids: Union[jnp.ndarray, np.ndarray, Sequence[int]],
    num_segments: int,
    mask: Optional[jnp.ndarray] = None,
) -> PyTree:
    """Ragged edge/region aggregation: per-segment weighted mean, broadcast
    back to the members.

    tree leaves: (N, ...); weights/mask: (N,); segment_ids: (N,) sorted ints
    in [0, num_segments) (a level of ``HierarchySpec.segments``). Equals
    ``grouped_weighted_mean`` exactly when the segments are equal contiguous
    blocks (and dispatches to it, keeping the reshape fast path).
    """
    uniform = _static_uniform_groups(segment_ids, num_segments)
    if uniform is not None:
        return grouped_weighted_mean(tree, weights, uniform, mask)
    seg = jnp.asarray(segment_ids, jnp.int32)
    w = weights.astype(jnp.float32)
    if mask is not None:
        w = w * mask.astype(jnp.float32)
    denom = jax.ops.segment_sum(w, seg, num_segments)  # (G,)
    safe = jnp.where(denom > 0, denom, 1.0)
    alive = denom > 0

    def leaf_fn(x):
        wb = _bcast_weights(w, x)
        sums = jax.ops.segment_sum(x.astype(jnp.float32) * wb, seg, num_segments)  # (G, ...)
        mean = sums / _bcast_weights(safe, sums)
        back = jnp.take(mean, seg, axis=0)  # (N, ...)
        keep = _bcast_weights(jnp.take(alive, seg), back)
        return jnp.where(keep, back, x.astype(jnp.float32)).astype(x.dtype)

    return jax.tree_util.tree_map(leaf_fn, tree)


def segment_weights(
    weights: jnp.ndarray,
    segment_ids: Union[jnp.ndarray, np.ndarray, Sequence[int]],
    num_segments: int,
    mask: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """|D^g| per segment: sum of member dataset sizes (masked)."""
    w = weights.astype(jnp.float32)
    if mask is not None:
        w = w * mask.astype(jnp.float32)
    return jax.ops.segment_sum(w, jnp.asarray(segment_ids, jnp.int32), num_segments)


def hierarchical_segment_mean(
    tree: PyTree,
    weights: jnp.ndarray,
    spec,  # core.hierarchy.HierarchySpec
    level: Optional[int] = None,
    mask: Optional[jnp.ndarray] = None,
) -> PyTree:
    """Level-``level`` aggregation expressed as the staged bottom-up
    composition (edge means, then region means of edge means, ...).

    Numerically equal to the flat ``segment_weighted_mean`` at that level —
    the |D_i| weights compose (each stage's members already hold their
    group's mean, so the next weighted mean over clients equals the mean
    over groups with weights |D^g|) — but kept staged so GSPMD emits the
    hierarchical reduce(ICI) -> reduce(DCN) schedule. ``level=None`` means
    the top (cloud) level.

    On TPU, where every level up to ``level`` is static equal contiguous
    blocks, each leaf that tiles (``kernels.hier_aggregate.group_mean_plan``)
    takes every stage in one in-place Pallas pass (``ops.grouped_mean``):
    XLA would read and write the stack once per reduce, divide and select.
    Elsewhere — the CPU backend, ragged trees, leaves that do not tile —
    the stages run as jnp.
    """
    lvl = spec.depth if level is None else level

    def staged(t):
        out = t
        for s in range(1, lvl + 1):
            out = segment_weighted_mean(out, weights, spec.segments(s), spec.num_nodes(s), mask)
        return out

    fanouts = _uniform_fanouts(spec, lvl) if jax.default_backend() == "tpu" else None
    if fanouts is None:
        return staged(tree)
    w = weights.astype(jnp.float32)
    if mask is not None:
        w = w * mask.astype(jnp.float32)

    def leaf_fn(x):
        if _ha.group_mean_plan(x.shape, x.dtype) is None:
            return staged(x)
        return ops.grouped_mean(x, w, fanouts)

    return jax.tree_util.tree_map(leaf_fn, tree)


def _uniform_fanouts(spec, level: int) -> Optional[Tuple[int, ...]]:
    """Each level's fan-out, bottom-up to ``level``, where every level's
    segment ids are static equal contiguous blocks; else None."""
    fanouts, below = [], spec.num_clients
    for t in range(1, level + 1):
        groups = _static_uniform_groups(spec.segments(t), spec.num_nodes(t))
        if groups is None:
            return None
        fanouts.append(below // groups)
        below = groups
    return tuple(fanouts)


def group_weights(weights: jnp.ndarray, num_groups: int, mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """|D^l| per edge: sum of member dataset sizes (masked)."""
    w = weights.astype(jnp.float32)
    if mask is not None:
        w = w * mask.astype(jnp.float32)
    return w.reshape(num_groups, -1).sum(axis=1)


def delta_weighted_mean(
    tree: PyTree,
    anchor: PyTree,
    weights: jnp.ndarray,
    mask: Optional[jnp.ndarray] = None,
) -> PyTree:
    """Cloud aggregation in *delta* form: anchor + mean(tree - anchor).

    Mathematically identical to ``weighted_mean`` when every client survives
    (the anchor is the last broadcast model, common to all clients), but the
    payload (w - anchor) is small-magnitude and compresses well — this is the
    entry point for the compressed cloud hop (beyond-paper optimization).
    """
    deltas = jax.tree_util.tree_map(lambda x, a: x - a.astype(x.dtype), tree, anchor)
    mean_delta = weighted_mean(deltas, weights, mask)
    return jax.tree_util.tree_map(lambda a, d: (a.astype(jnp.float32) + d.astype(jnp.float32)).astype(a.dtype), anchor, mean_delta)


def psum_weighted_mean(
    tree: PyTree,
    weights: jnp.ndarray,
    axis_name: str,
    mask: Optional[jnp.ndarray] = None,
    *,
    anchor: Optional[PyTree] = None,
) -> Tuple[PyTree, jnp.ndarray]:
    """Global weighted mean over a client axis sharded along ``axis_name``
    with exactly ONE cross-device collective.

    Must be called inside ``shard_map``: every leaf is a shard-local
    (C, ...) slice and ``weights``/``mask`` are the matching (C,) slices.
    Per-leaf partial weighted sums and the masked weight total are raveled
    into a single vector and reduced with one grouped ``lax.psum``; the
    unpacked means broadcast back to every local client. With ``anchor``
    the mean is taken in delta form, anchor + mean(tree − anchor) — the
    ``delta_weighted_mean`` identity. Zero global survivors keeps current
    values, matching ``weighted_mean``.

    Returns ``(tree', alive)`` where ``alive`` is the scalar global
    denominator > 0 predicate — callers reuse it for transport keep-dead
    logic without issuing a second collective.
    """
    w = weights.astype(jnp.float32)
    if mask is not None:
        w = w * mask.astype(jnp.float32)
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    anchors = treedef.flatten_up_to(anchor) if anchor is not None else None
    payload = []
    for i, x in enumerate(leaves):
        xf = x.astype(jnp.float32)
        if anchors is not None:
            xf = xf - anchors[i].astype(jnp.float32)
        payload.append(xf)
    partials = [jnp.sum(p * _bcast_weights(w, p), axis=0).ravel() for p in payload]
    packed = jnp.concatenate(partials + [jnp.sum(w).reshape(1)])
    total = jax.lax.psum(packed, axis_name)
    denom = total[-1]
    alive = denom > 0
    safe = jnp.where(alive, denom, 1.0)
    out = []
    offset = 0
    for i, x in enumerate(leaves):
        param_shape = x.shape[1:]
        size = int(np.prod(param_shape, dtype=np.int64)) if param_shape else 1
        mean = (total[offset : offset + size] / safe).reshape(param_shape)
        offset += size
        full = jnp.broadcast_to(mean, x.shape)
        if anchors is not None:
            full = anchors[i].astype(jnp.float32) + full
        out.append(jnp.where(alive, full, x.astype(jnp.float32)).astype(x.dtype))
    return treedef.unflatten(out), alive


def hierarchical_mean(
    tree: PyTree,
    weights: jnp.ndarray,
    num_groups: int,
    mask: Optional[jnp.ndarray] = None,
) -> PyTree:
    """Cloud aggregation expressed as edge-then-cloud composition.

    Equal to ``weighted_mean`` (weights compose: the cloud's weighted mean of
    edge means with weights |D^l| equals the flat weighted mean with |D_i|) —
    kept as the two-stage form so GSPMD emits the hierarchical
    reduce(ICI) -> reduce(DCN) schedule rather than one flat all-reduce.
    """
    edge = grouped_weighted_mean(tree, weights, num_groups, mask)
    # After the edge stage each member of a group holds the group mean, so a
    # flat weighted mean over clients now equals the mean over edges with
    # weights |D^l|.
    return weighted_mean(edge, weights, mask)


# ---------------------------------------------------------------------------
# Robust per-segment aggregators (the per-level AggregatorSpec axis)
# ---------------------------------------------------------------------------
#
# The paper's protocol aggregates with the |D_i|-weighted mean everywhere.
# Byzantine/outlier-robust FL replaces that statistic per level with a
# coordinate-wise trimmed mean or median (Yin et al., ICML'18) — both are
# *unweighted* order statistics over the surviving members of each segment,
# so they use the survival mask but not the dataset-size weights. A group
# with zero survivors keeps its members' current parameters, matching the
# weighted-mean operators above.


def _segment_members(segment_ids, num_segments: int) -> Tuple[np.ndarray, np.ndarray]:
    """Static (G, Cmax) member-index matrix + validity mask for sorted
    segment ids (host-side; ids come from ``HierarchySpec.segments``)."""
    ids = np.asarray(segment_ids, np.int64)
    sizes = np.bincount(ids, minlength=num_segments)
    cmax = int(sizes.max())
    members = np.zeros((num_segments, cmax), np.int32)
    valid = np.zeros((num_segments, cmax), bool)
    for g in range(num_segments):
        ix = np.where(ids == g)[0]
        members[g, : ix.shape[0]] = ix
        valid[g, : ix.shape[0]] = True
    return members, valid


def _sorted_segment_values(x, members, validb, mask):
    """Gather one (N, ...) leaf into (G, Cmax, ...) f32, masked entries at
    +inf, sorted ascending along the member axis. Returns (sorted, m_g)
    where m_g (G,) counts surviving members per segment."""
    vals = x.astype(jnp.float32)[members]  # (G, Cmax, ...)
    alive = jnp.asarray(validb)
    if mask is not None:
        alive = alive & (mask.astype(jnp.float32)[members] > 0)
    m_g = jnp.sum(alive, axis=1).astype(jnp.int32)  # (G,)
    alive_b = alive.reshape(alive.shape + (1,) * (vals.ndim - 2))
    vals = jnp.where(alive_b, vals, jnp.inf)
    return jnp.sort(vals, axis=1), m_g


def _broadcast_back(per_segment: jnp.ndarray, x: jnp.ndarray, seg, m_g) -> jnp.ndarray:
    """(G, ...) statistic -> (N, ...), zero-survivor groups keep current x."""
    back = jnp.take(per_segment, seg, axis=0)  # (N, ...)
    alive = jnp.take(m_g > 0, seg)
    keep = alive.reshape(alive.shape + (1,) * (back.ndim - 1))
    return jnp.where(keep, back, x.astype(jnp.float32)).astype(x.dtype)


def segment_trimmed_mean(
    tree: PyTree,
    segment_ids: Union[jnp.ndarray, np.ndarray, Sequence[int]],
    num_segments: int,
    mask: Optional[jnp.ndarray] = None,
    *,
    trim: float = 0.1,
) -> PyTree:
    """Coordinate-wise ``trim``-trimmed mean per segment, broadcast back.

    Per segment with m surviving members, each coordinate discards its
    ``floor(trim * m)`` smallest and largest member values and averages the
    rest (unweighted; m small enough that no trimming occurs degrades to the
    plain member mean). ``trim`` must be in [0, 0.5).
    """
    if not 0.0 <= trim < 0.5:
        raise ValueError(f"trim must be in [0, 0.5), got {trim}")
    members, validb = _segment_members(segment_ids, num_segments)
    seg = jnp.asarray(segment_ids, jnp.int32)

    def leaf_fn(x):
        svals, m_g = _sorted_segment_values(x, members, validb, mask)
        k_g = jnp.floor(trim * m_g.astype(jnp.float32)).astype(jnp.int32)  # (G,)
        ranks = jnp.arange(svals.shape[1], dtype=jnp.int32)  # (Cmax,)
        keep = (ranks[None, :] >= k_g[:, None]) & (ranks[None, :] < (m_g - k_g)[:, None])
        count = jnp.maximum(m_g - 2 * k_g, 1).astype(jnp.float32)  # (G,)
        keep_b = keep.reshape(keep.shape + (1,) * (svals.ndim - 2))
        sums = jnp.sum(jnp.where(keep_b, svals, 0.0), axis=1)  # (G, ...)
        mean = sums / count.reshape((-1,) + (1,) * (sums.ndim - 1))
        return _broadcast_back(mean, x, seg, m_g)

    return jax.tree_util.tree_map(leaf_fn, tree)


def segment_coordinate_median(
    tree: PyTree,
    segment_ids: Union[jnp.ndarray, np.ndarray, Sequence[int]],
    num_segments: int,
    mask: Optional[jnp.ndarray] = None,
) -> PyTree:
    """Coordinate-wise median per segment over surviving members, broadcast
    back (the midpoint of the two central order statistics for even m)."""
    members, validb = _segment_members(segment_ids, num_segments)
    seg = jnp.asarray(segment_ids, jnp.int32)

    def leaf_fn(x):
        svals, m_g = _sorted_segment_values(x, members, validb, mask)
        # central order statistics: odd m -> both (m-1)//2; even m -> m//2-1, m//2
        lo = jnp.maximum((m_g - 1) // 2, 0)  # (G,)
        hi = m_g // 2
        idx_shape = (-1, 1) + (1,) * (svals.ndim - 2)
        take = lambda i: jnp.take_along_axis(svals, i.reshape(idx_shape), axis=1)[:, 0]
        med = 0.5 * (take(lo) + take(hi))
        return _broadcast_back(med, x, seg, m_g)

    return jax.tree_util.tree_map(leaf_fn, tree)


# -- aggregator registry ------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class WeightedMeanAggregator:
    """The paper's |D_i|-weighted mean (staged ``hierarchical_segment_mean``).

    The default at every level; ``build_level_sync`` recognizes it and takes
    the exact pre-AggregatorSpec code path, so an all-default spec is
    bitwise-unchanged numerics.
    """

    @property
    def name(self) -> str:
        return "weighted_mean"

    @property
    def is_default(self) -> bool:
        return True

    def __call__(self, tree, weights, spec, level, mask=None):
        return hierarchical_segment_mean(tree, weights, spec, level, mask)


@dataclasses.dataclass(frozen=True)
class TrimmedMeanAggregator:
    """Coordinate-wise trimmed mean over each level-ℓ segment's survivors."""

    trim: float = 0.1

    def __post_init__(self):
        if not 0.0 <= self.trim < 0.5:
            raise ValueError(f"trim must be in [0, 0.5), got {self.trim}")

    @property
    def name(self) -> str:
        return f"trimmed_mean:{self.trim:g}"

    @property
    def is_default(self) -> bool:
        return False

    def __call__(self, tree, weights, spec, level, mask=None):
        return self.segment_call(tree, spec.segments(level), spec.num_nodes(level), mask)

    def segment_call(self, tree, segment_ids, num_segments, mask=None):
        """The statistic over explicit segment ids — the shard-local entry
        point for the mesh-sharded superround (ids must be concrete)."""
        return segment_trimmed_mean(tree, segment_ids, num_segments, mask, trim=self.trim)


@dataclasses.dataclass(frozen=True)
class CoordinateMedianAggregator:
    """Coordinate-wise median over each level-ℓ segment's survivors."""

    @property
    def name(self) -> str:
        return "coordinate_median"

    @property
    def is_default(self) -> bool:
        return False

    def __call__(self, tree, weights, spec, level, mask=None):
        return self.segment_call(tree, spec.segments(level), spec.num_nodes(level), mask)

    def segment_call(self, tree, segment_ids, num_segments, mask=None):
        """The statistic over explicit segment ids — the shard-local entry
        point for the mesh-sharded superround (ids must be concrete)."""
        return segment_coordinate_median(tree, segment_ids, num_segments, mask)


_AGGREGATOR_FACTORIES = {
    "weighted_mean": lambda arg: WeightedMeanAggregator(),
    "mean": lambda arg: WeightedMeanAggregator(),
    "trimmed_mean": lambda arg: TrimmedMeanAggregator(trim=float(arg) if arg else 0.1),
    "coordinate_median": lambda arg: CoordinateMedianAggregator(),
    "median": lambda arg: CoordinateMedianAggregator(),
}


def parse_aggregator(text: str):
    """'weighted_mean' | 'trimmed_mean[:trim]' | 'coordinate_median', e.g.
    'trimmed_mean:0.2'."""
    name, _, arg = text.strip().partition(":")
    if name not in _AGGREGATOR_FACTORIES:
        raise ValueError(
            f"unknown aggregator {name!r}; choose from {sorted(_AGGREGATOR_FACTORIES)}"
        )
    return _AGGREGATOR_FACTORIES[name](arg)


@dataclasses.dataclass(frozen=True)
class AggregatorSpec:
    """One aggregator per tree level, bottom-up — the robustness twin of
    ``fed.transport.TransportSpec``: ``aggregators[0]`` applies at the
    client→edge sync (level 1), ``aggregators[-1]`` at the cloud sync,
    aligned with ``HierFAVGConfig.kappa_vector``."""

    aggregators: Tuple[Any, ...]

    def __post_init__(self):
        object.__setattr__(self, "aggregators", tuple(self.aggregators))
        if not self.aggregators:
            raise ValueError("AggregatorSpec needs at least one level")

    # -- constructors -------------------------------------------------------

    @classmethod
    def default(cls, depth: int) -> "AggregatorSpec":
        return cls(aggregators=tuple(WeightedMeanAggregator() for _ in range(depth)))

    @classmethod
    def uniform(cls, aggregator, depth: int) -> "AggregatorSpec":
        return cls(aggregators=tuple(aggregator for _ in range(depth)))

    @classmethod
    def parse(cls, text: str) -> "AggregatorSpec":
        """'/'-separated aggregator per level, bottom-up:
        'trimmed_mean:0.1/weighted_mean' trims at the edge sync and keeps
        the paper's weighted mean at the cloud."""
        parts = [p for p in text.split("/") if p]
        if not parts:
            raise ValueError(f"empty aggregator spec: {text!r}")
        return cls(aggregators=tuple(parse_aggregator(p) for p in parts))

    # -- queries ------------------------------------------------------------

    @property
    def depth(self) -> int:
        return len(self.aggregators)

    def aggregator(self, level: int):
        if not 1 <= level <= self.depth:
            raise ValueError(f"level must be in 1..{self.depth}, got {level}")
        return self.aggregators[level - 1]

    @property
    def is_trivial(self) -> bool:
        """True iff every level is the default weighted mean — numerics are
        then exactly the pre-AggregatorSpec protocol."""
        return all(a.is_default for a in self.aggregators)

    def describe(self) -> str:
        return "/".join(a.name for a in self.aggregators)
