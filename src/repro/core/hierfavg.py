"""HierFAVG (Algorithm 1) as a composable JAX module.

The production form of the paper's algorithm. Parameters are *stacked* along
a leading client axis (see ``core.aggregation``); a single ``jax.grad`` of
the summed per-client loss yields every client's local gradient at once
(client losses are block-separable in the stacked parameters), so one jitted
``train_step`` advances all N clients one local update and applies the
per-level aggregation schedule. With the paper's κ-vector (κ₁, κ₂):

    k % kappa1 == 0                -> edge aggregation  (grouped, ICI)
    k % (kappa1 * kappa2) == 0     -> cloud aggregation (global, DCN)

and in general, for a depth-L ``HierarchySpec`` with κ = (κ₁, ..., κ_L),
level ℓ aggregates whenever ``k % prod(κ[:ℓ]) == 0`` — the deepest
triggered level wins (its staged mean subsumes all finer levels).

Special cases (paper Remark 1, used as test anchors):
    kappa2 == 1              -> FAVG (two-layer FedAvg)
    kappa1 == kappa2 == 1    -> centralized gradient descent

Two driving modes are exposed:
  * ``build_train_step``  — fused step, aggregation under ``lax.switch``
    (the normal training loop; one compiled executable regardless of k).
  * ``build_local_step`` / ``build_level_sync`` (and the two-level
    ``build_edge_sync`` / ``build_cloud_sync`` wrappers) — the phases as
    separate jittables (used by the dry-run for clean per-phase roofline
    accounting and by the fault-tolerant runner, which injects
    host-detected survival masks at aggregation boundaries).

Topology arguments accept either the seed's two-level ``FedTopology`` or a
ragged ``core.hierarchy.HierarchySpec``; the former is the
``levels=2, uniform`` special case with unchanged numerics.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Callable, NamedTuple, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import aggregation
from repro.core.hierarchy import (
    HierarchySpec,
    ShardPlacement,
    as_hierarchy,
    plan_cohort_placement,
    plan_shard_placement,
)
from repro.optim import GradientTransformation, apply_updates

PyTree = Any
LossFn = Callable[[PyTree, PyTree, jax.Array], jnp.ndarray]  # (params_i, batch_i, rng) -> scalar


@dataclasses.dataclass(frozen=True)
class FedTopology:
    """Client-edge-cloud topology: N = num_edges * clients_per_edge clients.

    The uniform two-level special case; ``hierarchy()`` lifts it into the
    general ragged-tree representation.
    """

    num_edges: int
    clients_per_edge: int

    @property
    def num_clients(self) -> int:
        return self.num_edges * self.clients_per_edge

    def edge_of(self, client: int) -> int:
        return client // self.clients_per_edge

    def hierarchy(self) -> HierarchySpec:
        return HierarchySpec.uniform(self.num_edges, self.clients_per_edge)


Topology = Union[FedTopology, HierarchySpec]


@dataclasses.dataclass(frozen=True)
class PrecisionSpec:
    """Mixed-precision policy for the stacked client state.

    ``param_dtype`` is the storage dtype of the stacked per-client params
    and their stacked optimizer leaves — the N-times-replicated memory that
    dominates device footprint (``"bfloat16"`` halves it). Local-step
    compute runs in the same dtype (batch floating leaves are cast on the
    way into the loss), while every aggregation keeps accumulating in
    float32 (``core.aggregation`` upcasts, reduces, casts back), so the
    per-group / cloud means act as transient fp32 master values re-cast to
    the storage dtype only at the broadcast boundary. Diagnostics (loss /
    grad-norm metrics) are always reduced in float32.

    ``remat`` wraps each per-client loss in ``jax.checkpoint`` so the
    backward pass recomputes activations instead of storing them — the
    knob that trades local-step FLOPs for activation memory when κ₁ steps
    are fused into one executable.
    """

    param_dtype: str = "float32"
    remat: bool = False

    def __post_init__(self):
        dt = jnp.dtype(self.param_dtype)  # raises on unknown names
        if not jnp.issubdtype(dt, jnp.floating):
            raise ValueError(f"param_dtype must be floating, got {self.param_dtype!r}")
        object.__setattr__(self, "param_dtype", dt.name)

    @property
    def dtype(self) -> jnp.dtype:
        return jnp.dtype(self.param_dtype)

    @property
    def is_active(self) -> bool:
        """False for the pure-fp32, no-remat default — every builder then
        takes the exact legacy graph, bitwise unchanged."""
        return self.remat or self.dtype != jnp.dtype(jnp.float32)


@dataclasses.dataclass(frozen=True)
class HierFAVGConfig:
    """Aggregation schedule. kappa1: local steps per edge agg; kappa2: edge
    aggs per cloud agg (paper's κ₁, κ₂). For deeper trees, ``kappas`` holds
    the full per-level vector (κ₁, ..., κ_L): κ_ℓ level-(ℓ-1) intervals per
    level-ℓ aggregation; ``multi_level`` builds a consistent config."""

    kappa1: int
    kappa2: int
    sync_opt_state: bool = False  # also average optimizer state at aggregations
    delta_cloud: bool = False  # cloud agg in delta-vs-anchor form (compressible)
    kappas: Optional[Tuple[int, ...]] = None  # per-level κ vector (None -> (κ₁, κ₂))
    transport: Optional[Any] = None  # fed.transport.TransportSpec: one LinkCodec per level
    aggregators: Optional[Any] = None  # core.aggregation.AggregatorSpec: one per level
    participation: Optional[Any] = None  # fed.participation.ParticipationSpec: sampled cohorts
    precision: Optional[PrecisionSpec] = None  # mixed-precision policy (None == pure fp32)

    def __post_init__(self):
        if self.precision is not None and not isinstance(self.precision, PrecisionSpec):
            raise TypeError(
                f"precision must be a PrecisionSpec, got {type(self.precision).__name__}"
            )
        if self.aggregators is not None:
            if not hasattr(self.aggregators, "aggregator") or not hasattr(
                self.aggregators, "is_trivial"
            ):
                raise TypeError(
                    f"aggregators must be a core.aggregation.AggregatorSpec, got "
                    f"{type(self.aggregators).__name__}"
                )
            n_levels = len(self.kappas) if self.kappas is not None else 2
            if self.aggregators.depth != n_levels:
                raise ValueError(
                    f"aggregators has {self.aggregators.depth} levels but the schedule "
                    f"has {n_levels} (kappas={self.kappas or (self.kappa1, self.kappa2)})"
                )
            if not self.aggregators.is_trivial:
                if self.delta_cloud and not self.aggregators.aggregator(n_levels).is_default:
                    raise ValueError(
                        "delta_cloud requires the default weighted_mean at the top "
                        "level (delta aggregation is a weighted-mean identity)"
                    )
        if self.transport is not None:
            if not hasattr(self.transport, "codec") or not hasattr(self.transport, "is_trivial"):
                raise TypeError(
                    f"transport must be a fed.transport.TransportSpec, got "
                    f"{type(self.transport).__name__}"
                )
            n_levels = len(self.kappas) if self.kappas is not None else 2
            if self.transport.depth != n_levels:
                raise ValueError(
                    f"transport has {self.transport.depth} levels but the schedule has "
                    f"{n_levels} (kappas={self.kappas or (self.kappa1, self.kappa2)})"
                )
            if not self.transport.is_trivial and self.delta_cloud:
                raise ValueError(
                    "a non-identity transport subsumes delta_cloud (both repurpose "
                    "the anchor slot); drop the flag"
                )
        if self.kappas is not None:
            kv = tuple(int(k) for k in self.kappas)
            object.__setattr__(self, "kappas", kv)
            if len(kv) < 1 or any(k < 1 for k in kv):
                raise ValueError(f"kappas must be >= 1 per level, got {kv}")
            if kv[0] != self.kappa1 or (len(kv) > 1 and kv[1] != self.kappa2):
                raise ValueError(
                    f"kappas {kv} inconsistent with kappa1={self.kappa1}, "
                    f"kappa2={self.kappa2}; use HierFAVGConfig.multi_level"
                )
        if self.kappa1 < 1 or self.kappa2 < 1:
            raise ValueError("kappa1/kappa2 must be >= 1")
        if self.participation is not None:
            if not hasattr(self.participation, "cohort_size") or not hasattr(
                self.participation, "is_active"
            ):
                raise TypeError(
                    f"participation must be a fed.participation.ParticipationSpec, got "
                    f"{type(self.participation).__name__}"
                )
            if self.participation.is_active:
                if self.aggregators_active:
                    raise ValueError(
                        "sampled participation requires the default weighted mean at "
                        "every level (a robust statistic over a sampled cohort is not "
                        "the population statistic)"
                    )

    @classmethod
    def multi_level(cls, kappas: Sequence[int], **kwargs) -> "HierFAVGConfig":
        kv = tuple(int(k) for k in kappas)
        if not kv:
            raise ValueError("kappas must have at least one level")
        # a 1-vector is a depth-1 tree (clients -> cloud, classic two-tier
        # FedAvg); kappa2 degrades to 1 for two-level consumers
        return cls(kappa1=kv[0], kappa2=kv[1] if len(kv) > 1 else 1, kappas=kv, **kwargs)

    @property
    def kappa_vector(self) -> Tuple[int, ...]:
        return self.kappas if self.kappas is not None else (self.kappa1, self.kappa2)

    @property
    def num_levels(self) -> int:
        return len(self.kappa_vector)

    def level_interval(self, level: int) -> int:
        """Local steps between level-ℓ aggregations: prod(κ[:ℓ])."""
        return math.prod(self.kappa_vector[:level])

    @property
    def cloud_interval(self) -> int:
        return self.level_interval(self.num_levels)

    @property
    def kappa2_effective(self) -> int:
        """Edge intervals per cloud interval (= κ₂ for two levels) — the
        two-level quantity the paper's cost model consumes."""
        return math.prod(self.kappa_vector[1:])

    def is_level_step(self, level: int, k) -> jnp.ndarray:
        return (k % self.level_interval(level)) == 0

    def is_edge_step(self, k) -> jnp.ndarray:
        return self.is_level_step(1, k)

    def is_cloud_step(self, k) -> jnp.ndarray:
        return self.is_level_step(self.num_levels, k)

    @property
    def transport_active(self) -> bool:
        """True iff some level's uplink actually compresses (an all-identity
        TransportSpec is numerically the uncompressed protocol and allocates
        no anchor/residual state)."""
        return self.transport is not None and not self.transport.is_trivial

    @property
    def aggregators_active(self) -> bool:
        """True iff some level replaces the paper's weighted mean (an
        all-``weighted_mean`` AggregatorSpec is numerically the unchanged
        protocol and takes the exact legacy code path)."""
        return self.aggregators is not None and not self.aggregators.is_trivial

    @property
    def participation_active(self) -> bool:
        """True iff cohort sampling is on (a cohort_size=0 spec is inert and
        every engine keeps its full-population behaviour)."""
        return self.participation is not None and self.participation.is_active

    @property
    def precision_active(self) -> bool:
        """True iff the precision policy changes anything (a pure-fp32,
        no-remat PrecisionSpec keeps the exact legacy graphs)."""
        return self.precision is not None and self.precision.is_active


class FedState(NamedTuple):
    step: jnp.ndarray  # local update counter k
    params: PyTree  # stacked (N, ...) client models
    opt_state: PyTree  # stacked per-client optimizer state
    rng: jax.Array
    anchor: Optional[PyTree] = None  # last broadcast (delta_cloud / compressed transport)
    residual: Optional[PyTree] = None  # per-client error-feedback residual (EF codecs)


def replicate_for_clients(params: PyTree, num_clients: int) -> PyTree:
    """Stack the initial model: every client starts from w0 (Algorithm 1 l.2)."""
    return jax.tree_util.tree_map(
        lambda p: jnp.broadcast_to(p[None], (num_clients,) + p.shape).copy(), params
    )


def init_state(
    rng: jax.Array,
    params: PyTree,
    optimizer: GradientTransformation,
    topology: Topology,
    config: HierFAVGConfig,
    *,
    already_stacked: bool = False,
) -> FedState:
    stacked = params if already_stacked else replicate_for_clients(params, topology.num_clients)
    if config.precision_active:
        # stacked client state is stored (and stepped) in the policy dtype;
        # every aggregation still accumulates in fp32 (core.aggregation)
        dt = config.precision.dtype
        stacked = jax.tree_util.tree_map(
            lambda p: p.astype(dt) if jnp.issubdtype(p.dtype, jnp.floating) else p, stacked
        )
    opt_state = optimizer.init(stacked)
    if config.delta_cloud or config.transport_active:
        # last broadcast each client received: deltas w − anchor are what a
        # compressed uplink carries
        anchor = jax.tree_util.tree_map(jnp.copy, stacked)
    else:
        anchor = None
    residual = None
    if config.transport_active and config.transport.needs_residual:
        residual = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), stacked
        )
    return FedState(
        step=jnp.zeros([], jnp.int32), params=stacked, opt_state=opt_state,
        rng=rng, anchor=anchor, residual=residual,
    )


# ---------------------------------------------------------------------------
# Phase builders
# ---------------------------------------------------------------------------

def _apply_precision(loss_fn: LossFn, precision: Optional[PrecisionSpec]) -> LossFn:
    """Wrap a per-client loss with the ``PrecisionSpec`` policy: optional
    ``jax.checkpoint`` (remat) and casting the batch's floating leaves to
    the compute/storage dtype so the forward/backward genuinely run in it.
    The inert policy (or None) returns ``loss_fn`` unchanged — identical
    graph, identical numerics."""
    if precision is None or not precision.is_active:
        return loss_fn
    inner = jax.checkpoint(loss_fn) if precision.remat else loss_fn
    dt = precision.dtype
    if dt == jnp.dtype(jnp.float32):
        return inner

    def cast_loss(params, batch, rng):
        batch = jax.tree_util.tree_map(
            lambda x: x.astype(dt) if jnp.issubdtype(x.dtype, jnp.floating) else x, batch
        )
        return inner(params, batch, rng)

    return cast_loss


def _build_microbatch_grads(loss_fn: LossFn, grad_accum: int):
    """(params, batch, rngs) -> (summed grads, per-client losses) with the
    microbatch accumulation scan — shared by the single-device and the
    client-sharded local steps (identical graphs, identical numerics)."""

    def total_loss(params, batch, rngs):
        losses = jax.vmap(loss_fn)(params, batch, rngs)
        # Sum (not mean): keeps per-client gradients identical to each client
        # running SGD on its own mean loss.
        return jnp.sum(losses), losses

    grad_fn = jax.grad(total_loss, has_aux=True)

    def microbatch_grads(params, batch, rngs):
        if grad_accum == 1:
            return grad_fn(params, batch, rngs)

        def body(carry, micro):
            acc, _ = carry
            g, losses = grad_fn(params, micro, rngs)
            acc = jax.tree_util.tree_map(lambda a, b: a + b, acc, g)
            return (acc, losses), ()

        first = jax.tree_util.tree_map(lambda x: x[0], batch)
        g0, losses0 = grad_fn(params, first, rngs)
        rest = jax.tree_util.tree_map(lambda x: x[1:], batch)
        (acc, losses), _ = jax.lax.scan(body, (g0, losses0), rest)
        acc = jax.tree_util.tree_map(lambda g: g / grad_accum, acc)
        return acc, losses

    return microbatch_grads


def build_local_step(
    loss_fn: LossFn,
    optimizer: GradientTransformation,
    *,
    grad_accum: int = 1,
    precision: Optional[PrecisionSpec] = None,
):
    """One local SGD update for all clients (Algorithm 1 l.5).

    batch leaves:
        grad_accum == 1 : (N, b, ...)
        grad_accum  > 1 : (grad_accum, N, b, ...)   (scanned microbatches)
    ``precision`` applies the mixed-precision policy (batch cast + remat);
    the loss/grad-norm metrics are reduced in fp32 regardless.
    Returns (state, metrics).
    """
    microbatch_grads = _build_microbatch_grads(_apply_precision(loss_fn, precision), grad_accum)

    def local_step(state: FedState, batch: PyTree) -> Tuple[FedState, dict]:
        rng, step_rng = jax.random.split(state.rng)
        n = jax.tree_util.tree_leaves(state.params)[0].shape[0]
        rngs = jax.random.split(step_rng, n)
        grads, losses = microbatch_grads(state.params, batch, rngs)
        updates, opt_state = optimizer.update(grads, state.opt_state, state.params)
        params = apply_updates(state.params, updates)
        gnorm = jnp.sqrt(
            sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in jax.tree_util.tree_leaves(grads))
        )
        metrics = {"loss": jnp.mean(losses.astype(jnp.float32)), "grad_norm": gnorm}
        return (
            FedState(
                step=state.step + 1, params=params, opt_state=opt_state, rng=rng,
                anchor=state.anchor, residual=state.residual,
            ),
            metrics,
        )

    return local_step


def _maybe_sync_opt_state(opt_state, agg_fn, sync: bool):
    if not sync:
        return opt_state

    def leaf_ok(x):
        return isinstance(x, jnp.ndarray) and x.ndim >= 1

    return jax.tree_util.tree_map(lambda x: agg_fn(x) if leaf_ok(x) else x, opt_state)


def _shard_row(table, axis: str):
    """Select this shard's row of a host-side (num_shards, ...) table at
    trace time inside ``shard_map`` (via ``lax.axis_index``)."""
    idx = jax.lax.axis_index(axis)
    return jax.lax.dynamic_index_in_dim(jnp.asarray(table), idx, axis=0, keepdims=False)


@dataclasses.dataclass(frozen=True)
class ClientSharding:
    """How each shard of the ``axis``-sharded client dimension sees the tree
    inside a ``shard_map`` body.

    Wraps a ``core.hierarchy.ShardPlacement`` plus the global aggregation
    weights; the ``local_*`` accessors must be called at trace time inside
    the body (they select this shard's row of the host tables with
    ``lax.axis_index``). When every shard has the identical local segment
    layout (uniform packing), ``local_segments`` returns the concrete ids so
    ``segment_weighted_mean`` keeps its static uniform reshape fast path.
    """

    axis: str
    placement: ShardPlacement
    weights_table: Any  # np (num_shards, capacity) f32 permuted+padded weights

    @classmethod
    def build(cls, axis: str, placement: ShardPlacement, weights) -> "ClientSharding":
        table = placement.pad_weights(np.asarray(weights)).reshape(
            placement.num_shards, placement.capacity
        )
        return cls(axis=axis, placement=placement, weights_table=table)

    def local_weights(self):
        return _shard_row(self.weights_table, self.axis)

    def static_segments(self, level: int) -> Optional[np.ndarray]:
        """Concrete (capacity,) local ids when identical across shards."""
        tab = self.placement.local_segments(level)
        return tab[0] if bool((tab == tab[0]).all()) else None

    def local_segments(self, level: int):
        static = self.static_segments(level)
        if static is not None:
            return static
        return _shard_row(self.placement.local_segments(level), self.axis)

    def local_num_segments(self, level: int) -> int:
        return self.placement.local_num_segments(level)

    def client_ids_table(self) -> np.ndarray:
        """(num_shards, capacity) original client ids (phantoms read 0)."""
        return self.placement.gather_index().reshape(
            self.placement.num_shards, self.placement.capacity
        )


def sharding_incompatibility(
    config: HierFAVGConfig,
    topology: Topology,
    num_shards: int,
    placement: Optional[ShardPlacement] = None,
) -> Optional[str]:
    """Why this schedule cannot run client-sharded over ``num_shards``
    devices — None when it can. The runner uses this for engine
    eligibility; ``build_sharded_super_round`` raises on a non-None reason.
    Pass ``placement`` to validate the layout that will actually run
    (otherwise the auto-planned one is checked).
    """
    spec = as_hierarchy(topology)
    if config.delta_cloud and config.sync_opt_state:
        return "delta_cloud + sync_opt_state do not compose (the opt tree has no anchor)"
    if placement is None:
        try:
            placement = plan_shard_placement(spec, num_shards)
        except ValueError as e:
            return str(e)
    elif placement.num_shards != num_shards or placement.spec != spec:
        return (
            f"placement was planned for {placement.num_shards} shard(s) over "
            f"{placement.spec.describe()}, not {num_shards} shard(s) over "
            f"{spec.describe()}"
        )
    if config.aggregators_active:
        if config.aggregators.depth != spec.depth:
            # keep the None-or-reason contract even for configs other
            # entry points would reject (direct predicate callers)
            return (
                f"aggregators cover {config.aggregators.depth} level(s) but "
                f"the tree has depth {spec.depth}"
            )
        if not config.aggregators.aggregator(spec.depth).is_default:
            return (
                "a non-default top-level aggregator needs global order "
                "statistics across shards; only weighted_mean lowers to the "
                "cloud psum"
            )
        for lvl in range(1, spec.depth):
            if not config.aggregators.aggregator(lvl).is_default:
                tab = placement.local_segments(lvl)
                if not bool((tab == tab[0]).all()):
                    return (
                        f"the robust aggregator at level {lvl} needs an "
                        f"identical per-shard segment layout (this packing "
                        f"is ragged across shards)"
                    )
    return None


def build_level_sync(
    topology: Topology,
    config: HierFAVGConfig,
    weights: jnp.ndarray,
    level: int,
    *,
    shard: Optional[ClientSharding] = None,
):
    """Aggregation at one hierarchy level (Algorithm 1 l.25-31 generalized)
    with optional survival mask.

    Level 1 is edge aggregation; level ``spec.depth`` is cloud aggregation.
    Expressed as the staged bottom-up composition (edge means first, then
    region means, then global) so GSPMD emits the ICI-then-DCN reduce
    schedule; numerically equal to the flat level-ℓ segment mean because
    the |D_i| weights compose. The top level honors ``delta_cloud``.

    Robust aggregation: when ``config.aggregators`` assigns this level a
    non-default aggregator (``core.aggregation.AggregatorSpec``, e.g.
    ``trimmed_mean`` or ``coordinate_median``), that statistic replaces the
    weighted mean for this level's sync — applied to whatever the transport
    delivered, so robustness composes with compression and survival masks.
    The default ``weighted_mean`` takes this exact legacy path, bitwise
    unchanged.

    Compressed transport: when ``config.transport`` assigns this level a
    non-identity ``LinkCodec``, each client's upload is its model delta
    w − w_anchor (anchor = last broadcast it received) pushed through the
    codec's encode∘decode before aggregating — the aggregator averages what
    the wire actually delivered: mean_g(anchor + decode(encode(w − anchor)))
    = anchor + mean_g(decode(...)) since the anchor is common within a
    group. Error-feedback codecs carry their residual in
    ``FedState.residual``; the anchor re-syncs to the fresh broadcast after
    *every* level sync (identity levels included) so deltas never straddle
    two broadcasts. Identity-only transports take the exact uncompressed
    path — bitwise unchanged numerics.

    Client-sharded lowering: with ``shard`` (a ``ClientSharding``, for use
    inside a ``shard_map`` body over the client axis) sub-top levels lower
    to device-local segment reductions over the shard-local ids — no
    collective; edge groups never straddle shards by placement — and the
    top level to one grouped ``psum`` (params and, when ``sync_opt_state``,
    the opt leaves ride the same packed reduction). Codec round-trips, EF
    residuals, and robust sub-top aggregators are per-client/per-group and
    stay shard-local.
    """
    spec = as_hierarchy(topology)
    if not 1 <= level <= spec.depth:
        raise ValueError(f"level {level} outside 1..{spec.depth}")
    is_top = level == spec.depth
    codec = None
    if config.transport_active:
        codec = config.transport.codec(level)
        if codec.is_identity:
            codec = None
    # per-level robust aggregator (AggregatorSpec axis); the default
    # weighted mean keeps the exact legacy hierarchical_segment_mean path
    robust = None
    if config.aggregators_active:
        robust = config.aggregators.aggregator(level)
        if robust.is_default:
            robust = None
    if shard is not None:
        return _build_sharded_level_sync(spec, config, level, codec, robust, shard)
    seg_ids = jnp.asarray(spec.segments(level), jnp.int32)
    num_segs = spec.num_nodes(level)

    def level_sync(state: FedState, mask: Optional[jnp.ndarray] = None) -> FedState:
        uploaded = state.params
        residual = state.residual
        if codec is not None:
            delta = jax.tree_util.tree_map(
                lambda x, a: x.astype(jnp.float32) - a.astype(jnp.float32),
                state.params, state.anchor,
            )
            delta_hat, residual = codec.roundtrip(delta, residual)
            uploaded = jax.tree_util.tree_map(
                lambda a, d, x: (a.astype(jnp.float32) + d).astype(x.dtype),
                state.anchor, delta_hat, state.params,
            )
        if is_top and config.delta_cloud and state.anchor is not None:
            agg = lambda t: aggregation.delta_weighted_mean(t, state.anchor, weights, mask)
            params = agg(uploaded)
            anchor = jax.tree_util.tree_map(jnp.copy, params)
        else:
            if robust is not None:
                agg = lambda t: robust(t, weights, spec, level, mask)
            else:
                agg = lambda t: aggregation.hierarchical_segment_mean(t, weights, spec, level, mask)
            params = agg(uploaded)
            if config.transport_active:
                anchor = jax.tree_util.tree_map(jnp.copy, params)
            else:
                anchor = state.anchor
        if codec is not None:
            # A client whose whole level-ℓ group died transmitted nothing
            # and received no broadcast: it must keep its EXACT params and
            # anchor, not the codec roundtrip of them (the aggregation's
            # keep path above saw only `uploaded`). Likewise a masked-out
            # client in a surviving group receives the broadcast but never
            # transmitted, so its EF residual must not be consumed.
            w_eff = weights.astype(jnp.float32)
            if mask is not None:
                w_eff = w_eff * mask.astype(jnp.float32)
            received = jnp.take(
                jax.ops.segment_sum(w_eff, seg_ids, num_segs) > 0, seg_ids
            )  # (N,) group had >= 1 survivor

            def keep_dead(new, old):
                r = received.reshape((-1,) + (1,) * (new.ndim - 1))
                return jnp.where(r, new, old.astype(new.dtype))

            params = jax.tree_util.tree_map(keep_dead, params, state.params)
            anchor = jax.tree_util.tree_map(keep_dead, anchor, state.anchor)
            if residual is not None and state.residual is not None:
                sent = w_eff > 0  # (N,) this client actually uploaded

                def keep_residual(new, old):
                    s = sent.reshape((-1,) + (1,) * (new.ndim - 1))
                    return jnp.where(s, new, old)

                residual = jax.tree_util.tree_map(keep_residual, residual, state.residual)
        opt_state = _maybe_sync_opt_state(state.opt_state, agg, config.sync_opt_state)
        return state._replace(params=params, opt_state=opt_state, anchor=anchor, residual=residual)

    return level_sync


def _build_sharded_level_sync(spec, config, level, codec, robust, shard: ClientSharding):
    """The ``shard``-lowered body of ``build_level_sync`` (see its
    docstring): sub-top levels reduce entirely shard-locally (placement
    guarantees their groups never straddle shards); the top level issues
    exactly one grouped psum. Numerics match the single-device sync up to
    cross-shard summation order at the top level (documented ULP tolerance;
    sub-top syncs add members in the single-device order)."""
    depth = spec.depth
    is_top = level == depth
    if robust is not None:
        if is_top:
            raise ValueError(
                "a non-default top-level aggregator cannot run client-sharded "
                "(global order statistics); see sharding_incompatibility"
            )
        if shard.static_segments(level) is None:
            raise ValueError(
                f"robust aggregator at level {level} needs an identical "
                f"per-shard segment layout; see sharding_incompatibility"
            )
    if is_top and config.delta_cloud and config.sync_opt_state:
        raise ValueError("delta_cloud + sync_opt_state cannot run client-sharded")

    def stage_local(tree, w_local, mask, upto):
        out = tree
        for lvl in range(1, upto + 1):
            out = aggregation.segment_weighted_mean(
                out, w_local, shard.local_segments(lvl), shard.local_num_segments(lvl), mask
            )
        return out

    def level_sync(state: FedState, mask: Optional[jnp.ndarray] = None) -> FedState:
        w_local = shard.local_weights()
        uploaded = state.params
        residual = state.residual
        if codec is not None:
            delta = jax.tree_util.tree_map(
                lambda x, a: x.astype(jnp.float32) - a.astype(jnp.float32),
                state.params, state.anchor,
            )
            delta_hat, residual = codec.roundtrip(delta, residual)
            uploaded = jax.tree_util.tree_map(
                lambda a, d, x: (a.astype(jnp.float32) + d).astype(x.dtype),
                state.anchor, delta_hat, state.params,
            )
        agg = None  # per-tree closure (sub-top opt_state sync)
        synced_opt = None  # opt_state that rode the top-level packed psum
        alive_top = None
        if is_top and config.delta_cloud and state.anchor is not None:
            params, alive_top = aggregation.psum_weighted_mean(
                uploaded, w_local, shard.axis, mask, anchor=state.anchor
            )
            anchor = jax.tree_util.tree_map(jnp.copy, params)
        elif is_top:
            # pack params (+ synced opt leaves) so the cloud boundary issues
            # exactly one cross-device collective
            bundle = {"p": uploaded}
            sync_ix: list = []
            if config.sync_opt_state:
                opt_leaves, opt_def = jax.tree_util.tree_flatten(state.opt_state)
                sync_ix = [
                    i for i, x in enumerate(opt_leaves)
                    if isinstance(x, jnp.ndarray) and x.ndim >= 1
                ]
                bundle["o"] = [opt_leaves[i] for i in sync_ix]
            staged = stage_local(bundle, w_local, mask, depth - 1)
            out, alive_top = aggregation.psum_weighted_mean(staged, w_local, shard.axis, mask)
            params = out["p"]
            if config.sync_opt_state:
                for i, new in zip(sync_ix, out["o"]):
                    opt_leaves[i] = new
                synced_opt = jax.tree_util.tree_unflatten(opt_def, opt_leaves)
            if config.transport_active:
                anchor = jax.tree_util.tree_map(jnp.copy, params)
            else:
                anchor = state.anchor
        else:
            if robust is not None:
                ids = shard.static_segments(level)
                nseg = shard.local_num_segments(level)
                agg = lambda t: robust.segment_call(t, ids, nseg, mask)
            else:
                agg = lambda t: stage_local(t, w_local, mask, level)
            params = agg(uploaded)
            if config.transport_active:
                anchor = jax.tree_util.tree_map(jnp.copy, params)
            else:
                anchor = state.anchor
        if codec is not None:
            # mirror of the single-device keep-dead logic (build_level_sync);
            # at the top level the whole tree is one group, so "my group had
            # a survivor" is the alive bit the packed psum already reduced
            w_eff = w_local.astype(jnp.float32)
            if mask is not None:
                w_eff = w_eff * mask.astype(jnp.float32)
            if is_top:
                received = alive_top
            else:
                ids = jnp.asarray(shard.local_segments(level), jnp.int32)
                nseg = shard.local_num_segments(level)
                received = jnp.take(jax.ops.segment_sum(w_eff, ids, nseg) > 0, ids)

            def keep_dead(new, old):
                r = received
                if r.ndim:
                    r = r.reshape((-1,) + (1,) * (new.ndim - 1))
                return jnp.where(r, new, old.astype(new.dtype))

            params = jax.tree_util.tree_map(keep_dead, params, state.params)
            anchor = jax.tree_util.tree_map(keep_dead, anchor, state.anchor)
            if residual is not None and state.residual is not None:
                sent = w_eff > 0

                def keep_residual(new, old):
                    s = sent.reshape((-1,) + (1,) * (new.ndim - 1))
                    return jnp.where(s, new, old)

                residual = jax.tree_util.tree_map(keep_residual, residual, state.residual)
        if synced_opt is not None:
            opt_state = synced_opt
        else:
            opt_state = _maybe_sync_opt_state(state.opt_state, agg, config.sync_opt_state)
        return state._replace(params=params, opt_state=opt_state, anchor=anchor, residual=residual)

    return level_sync


def build_edge_sync(topology: Topology, config: HierFAVGConfig, weights: jnp.ndarray):
    """Edge aggregation (Algorithm 1 l.8, 25-28): level-1 sync."""
    return build_level_sync(topology, config, weights, 1)


def build_cloud_sync(topology: Topology, config: HierFAVGConfig, weights: jnp.ndarray):
    """Cloud aggregation (Algorithm 1 l.18-21, 29-31): top-level sync."""
    return build_level_sync(topology, config, weights, as_hierarchy(topology).depth)


# ---------------------------------------------------------------------------
# Fused train step
# ---------------------------------------------------------------------------

def _check_levels(spec: HierarchySpec, config: HierFAVGConfig) -> int:
    if config.num_levels != spec.depth:
        raise ValueError(
            f"schedule has {config.num_levels} levels (kappas="
            f"{config.kappa_vector}) but the hierarchy has depth {spec.depth}"
        )
    return spec.depth


def build_train_step(
    loss_fn: LossFn,
    optimizer: GradientTransformation,
    topology: Topology,
    config: HierFAVGConfig,
    weights: jnp.ndarray,
    *,
    grad_accum: int = 1,
):
    """Fused HierFAVG step: local update + conditional per-level aggregation.

    train_step(state, batch, mask=None) -> (state, metrics). ``mask`` is the
    (N,) survival vector from the failure detector (None == all alive).

    The level intervals nest (prod(κ[:ℓ]) divides prod(κ[:ℓ+1])), so the set
    of levels triggered at step k is a prefix 1..m; a single ``lax.switch``
    on m picks the deepest triggered level, whose staged mean subsumes the
    finer ones. m=0 (no boundary) is the identity branch.
    """
    spec = as_hierarchy(topology)
    depth = _check_levels(spec, config)
    local_step = build_local_step(loss_fn, optimizer, grad_accum=grad_accum, precision=config.precision)
    level_syncs = [build_level_sync(spec, config, weights, l) for l in range(1, depth + 1)]

    def train_step(state: FedState, batch: PyTree, mask: Optional[jnp.ndarray] = None):
        state, metrics = local_step(state, batch)
        k = state.step
        deepest = sum(
            config.is_level_step(l, k).astype(jnp.int32) for l in range(1, depth + 1)
        )
        branches = [lambda s: s] + [
            (lambda sync: lambda s: sync(s, mask))(sync) for sync in level_syncs
        ]
        state = jax.lax.switch(deepest, branches, state)
        metrics["step"] = k
        return state, metrics

    return train_step


def build_hier_round(
    loss_fn: LossFn,
    optimizer: GradientTransformation,
    topology: Topology,
    config: HierFAVGConfig,
    weights: jnp.ndarray,
    *,
    grad_accum: int = 1,
):
    """One full *edge interval* as a single jittable: kappa1 local steps
    (scanned) + the deepest due aggregation (edge every round, level ℓ
    every prod(κ₂..κ_ℓ) rounds).

    This is the deployable unit the dry-run lowers: batch leaves carry a
    leading (kappa1,) axis; the aggregation level is selected by the round
    index via one ``lax.switch``.
    """
    spec = as_hierarchy(topology)
    depth = _check_levels(spec, config)
    local_step = build_local_step(loss_fn, optimizer, grad_accum=grad_accum, precision=config.precision)
    level_syncs = [build_level_sync(spec, config, weights, l) for l in range(1, depth + 1)]
    kv = config.kappa_vector
    # rounds between level-ℓ aggregations: prod(κ₂..κ_ℓ)  (level 1 = every round)
    round_intervals = [math.prod(kv[1:l]) for l in range(1, depth + 1)]

    def hier_round(state: FedState, batches: PyTree, round_index: jnp.ndarray, mask=None):
        def body(s, b):
            s, m = local_step(s, b)
            return s, (m["loss"], m["grad_norm"])

        state, (losses, gnorms) = jax.lax.scan(body, state, batches)
        rounds_done = round_index + 1
        deepest = sum(
            ((rounds_done % iv) == 0).astype(jnp.int32) for iv in round_intervals
        )
        # every round ends with at least the edge sync -> branch index deepest-1
        branches = [(lambda sync: lambda s: sync(s, mask))(sync) for sync in level_syncs]
        state = jax.lax.switch(deepest - 1, branches, state)
        return state, {"loss": jnp.mean(losses), "grad_norm": jnp.mean(gnorms)}

    return hier_round


def super_round_schedule(config: HierFAVGConfig) -> Tuple[int, ...]:
    """Deepest aggregation level after each of the κ₂ rounds of one cloud
    interval (1 = edge only, depth = cloud). Static — every level interval
    divides the cloud interval, so the pattern repeats each superround."""
    kv = config.kappa_vector
    depth = len(kv)
    round_intervals = [math.prod(kv[1:l]) for l in range(1, depth + 1)]
    k2_eff = config.kappa2_effective
    return tuple(
        sum(1 for iv in round_intervals if (j + 1) % iv == 0) for j in range(k2_eff)
    )


def build_super_round(
    loss_fn: LossFn,
    optimizer: GradientTransformation,
    topology: Topology,
    config: HierFAVGConfig,
    weights: jnp.ndarray,
    *,
    grad_accum: int = 1,
):
    """One full *cloud interval* as a single jittable: κ₂ effective edge
    intervals (each κ₁ scanned local steps + its due aggregation) fused into
    one ``lax.scan`` over rounds, the per-round level switch folded into the
    scan via the static ``super_round_schedule`` vector.

    This is the zero-copy engine's dispatch unit (``fed.engine``): jitted
    with ``donate_argnums=(0,)`` the multi-copy stacked ``FedState`` (params
    + opt_state + anchor + EF residual) is updated in place instead of
    round-tripped through fresh HBM allocations, and the host regains
    control only at the cloud boundary — exactly the paper's natural
    synchronization point.

        super_round(state, batches, masks=None) -> (state, metrics)

    batch leaves carry a leading (κ₂, κ₁) axis pair; ``masks`` is an
    optional (κ₂, N) stack of per-round survival vectors. Metrics come back
    *stacked* — ``{"loss": (κ₂,), "grad_norm": (κ₂,), "step": (κ₂,)}`` —
    and live on device so the caller can defer the host fetch (async
    metrics; ``RoundRecord`` history is reconstructed later).

    Numerically bit-exact to driving ``build_hier_round`` κ₂ times from a
    cloud-aligned round index: the scan body is the same local-step scan +
    ``lax.switch`` subgraph. Callers must start at a cloud boundary
    (round index ≡ 0 mod κ₂ effective) — the folded schedule assumes it.
    """
    spec = as_hierarchy(topology)
    depth = _check_levels(spec, config)
    local_step = build_local_step(loss_fn, optimizer, grad_accum=grad_accum, precision=config.precision)
    level_syncs = [build_level_sync(spec, config, weights, l) for l in range(1, depth + 1)]
    deepest_per_round = jnp.asarray(super_round_schedule(config), jnp.int32)

    def super_round(state: FedState, batches: PyTree, masks: Optional[jnp.ndarray] = None):
        def round_body(s, xs):
            if masks is None:
                deepest, batch_r = xs
                mask_r = None
            else:
                deepest, batch_r, mask_r = xs

            def step_body(ss, b):
                ss, m = local_step(ss, b)
                return ss, (m["loss"], m["grad_norm"])

            s, (losses, gnorms) = jax.lax.scan(step_body, s, batch_r)
            branches = [(lambda sync: lambda st: sync(st, mask_r))(sync) for sync in level_syncs]
            s = jax.lax.switch(deepest - 1, branches, s)
            metrics = {
                "loss": jnp.mean(losses),
                "grad_norm": jnp.mean(gnorms),
                "step": s.step,
            }
            return s, metrics

        xs = (deepest_per_round, batches)
        if masks is not None:
            xs = xs + (masks,)
        return jax.lax.scan(round_body, state, xs)

    return super_round


def deadline_incompatibility(config: HierFAVGConfig, topology: Topology) -> Optional[str]:
    """Why this schedule cannot run under the semi-synchronous deadline
    engine (``build_deadline_super_round``) — None when it can.

    Mirrors ``sharding_incompatibility``: the single predicate both the
    builder (raises) and the runner's engine dispatch (reports) consult.
    The gated cloud sync needs the plain weighted mean at the top level —
    the staleness gate is a per-client weight multiplier, which is only a
    sound reweighting for a linear aggregator — and a broadcast every edge
    actually receives, which anchor-based transports and averaged optimizer
    state do not yet model for partially-received rounds.
    """
    spec = as_hierarchy(topology)
    if config.transport_active:
        return (
            "compressed transports re-sync every client's anchor at each "
            "boundary; a late edge that missed the broadcast would desync "
            "its delta reference"
        )
    if config.delta_cloud:
        return "delta_cloud's anchor rebroadcast assumes every edge receives each round"
    if config.sync_opt_state:
        return (
            "optimizer-state averaging has no per-edge keep path for late "
            "subtrees yet"
        )
    if config.aggregators_active and not config.aggregators.aggregator(spec.depth).is_default:
        return (
            "the staleness gate reweights client columns, which is only a "
            "sound transformation of the default weighted mean at the top level"
        )
    if config.participation_active:
        return "sampled participation runs through the cohort engine"
    return None


def build_deadline_super_round(
    loss_fn: LossFn,
    optimizer: GradientTransformation,
    topology: Topology,
    config: HierFAVGConfig,
    weights: jnp.ndarray,
    *,
    grad_accum: int = 1,
):
    """One *semi-synchronous* cloud interval: ``build_super_round`` with the
    top-level sync gated by a per-client cloud-arrival weight vector.

        deadline_round(state, batches, gate, masks=None) -> (state, metrics)

    ``gate`` is (N,) float32: each client's edge-level arrival × staleness
    multiplier for THIS interval's cloud aggregation (constant within an
    edge; produced by ``fed.deadline.RoundPlan.client_gate``). Semantics at
    the interval's final round:

    * sub-top stages run exactly as the synchronous staged mean — every
      edge performs its own edge sync with the survival mask, late edges
      included (their clients hold the fresh edge model while the upload
      is in flight);
    * the top stage aggregates with ``mask * gate``: folded edges
      contribute at their staleness-decayed weight, late/dropped edges at
      weight 0;
    * clients whose gate is 0 did not receive the broadcast — they keep
      the edge-synced model instead of the new cloud model (the carry that
      turns "late" into "stale next round" rather than "dropped").

    Sub-top rounds of the interval are byte-identical to
    ``build_super_round``'s (same ``build_level_sync`` branches). With an
    all-ones gate the top stage performs the identical op sequence as the
    synchronous staged mean plus an all-true select; the engine still
    dispatches the stock ``build_super_round`` executable for such trivial
    rounds, so the bit-exact parity contract never rides on XLA emitting
    identical code for two different graphs.
    """
    spec = as_hierarchy(topology)
    depth = _check_levels(spec, config)
    reason = deadline_incompatibility(config, topology)
    if reason is not None:
        raise ValueError(f"schedule cannot run the deadline engine: {reason}")
    local_step = build_local_step(loss_fn, optimizer, grad_accum=grad_accum, precision=config.precision)
    # sub-top syncs are the stock branches; the top branch is rebuilt below
    level_syncs = [build_level_sync(spec, config, weights, l) for l in range(1, depth)]
    deepest_per_round = jnp.asarray(super_round_schedule(config), jnp.int32)

    def gated_top_sync(state: FedState, mask_r, gate) -> FedState:
        # staged composition, mirroring hierarchical_segment_mean(..., depth):
        # sub-top stages with the survival mask alone (every edge syncs),
        # the top stage with mask * gate (only folded edges contribute)
        mid = state.params
        for lvl in range(1, depth):
            mid = aggregation.segment_weighted_mean(
                mid, weights, spec.segments(lvl), spec.num_nodes(lvl), mask_r
            )
        top_mask = gate if mask_r is None else mask_r * gate
        top = aggregation.segment_weighted_mean(
            mid, weights, spec.segments(depth), spec.num_nodes(depth), top_mask
        )
        received = gate > 0  # (N,) this client's edge got the broadcast

        def select(new, old):
            r = received.reshape((-1,) + (1,) * (new.ndim - 1))
            return jnp.where(r, new, old)

        params = jax.tree_util.tree_map(select, top, mid)
        return state._replace(params=params)

    def deadline_round(
        state: FedState,
        batches: PyTree,
        gate: jnp.ndarray,
        masks: Optional[jnp.ndarray] = None,
    ):
        def round_body(s, xs):
            if masks is None:
                deepest, batch_r = xs
                mask_r = None
            else:
                deepest, batch_r, mask_r = xs

            def step_body(ss, b):
                ss, m = local_step(ss, b)
                return ss, (m["loss"], m["grad_norm"])

            s, (losses, gnorms) = jax.lax.scan(step_body, s, batch_r)
            branches = [
                (lambda sync: lambda st: sync(st, mask_r))(sync) for sync in level_syncs
            ] + [lambda st: gated_top_sync(st, mask_r, gate)]
            s = jax.lax.switch(deepest - 1, branches, s)
            metrics = {
                "loss": jnp.mean(losses),
                "grad_norm": jnp.mean(gnorms),
                "step": s.step,
            }
            return s, metrics

        xs = (deepest_per_round, batches)
        if masks is not None:
            xs = xs + (masks,)
        return jax.lax.scan(round_body, state, xs)

    return deadline_round


# ---------------------------------------------------------------------------
# Client-blocked megakernel lowering
# ---------------------------------------------------------------------------

def megakernel_incompatibility(
    config: HierFAVGConfig, topology: Topology, *, grad_accum: int = 1
) -> Optional[str]:
    """Why this schedule cannot run through the client-blocked megakernel
    lowering (``build_megakernel_super_round``) — None when it can.

    Mirrors ``sharding_incompatibility``: the single predicate both the
    builder (raises) and the runner's engine dispatch (reports, then falls
    back to the scan-fused superround) consult. The megakernel restricts to
    the paper topology (two uniform levels) and the plain weighted-mean
    protocol: everything it fuses must be expressible as per-client-block
    local steps plus a trailing segment mean.
    """
    spec = as_hierarchy(topology)
    if not spec.is_paper_topology:
        return (
            f"the megakernel lowering is two-level uniform "
            f"(clients/edges/cloud) only, got {spec.describe()}"
        )
    if config.delta_cloud:
        return "delta_cloud's anchor bookkeeping keeps the scan-fused path"
    if config.transport_active:
        return "compressed transports (codec round-trips, EF residuals) keep the scan-fused path"
    if config.aggregators_active:
        return "non-default aggregators need the full client axis at each sync"
    if config.participation_active:
        return "sampled participation runs through the cohort engine"
    if config.sync_opt_state:
        return "optimizer-state averaging keeps the scan-fused path"
    if grad_accum != 1:
        return "microbatch accumulation keeps the scan-fused path"
    return None


def _rng_step_table(rng: jax.Array, steps: int, num_clients: int):
    """Precompute the per-step per-client key table the sequential
    ``build_local_step`` chain would derive: step t does
    ``rng, step_rng = split(rng); split(step_rng, N)``. A scan of splits
    followed by one vmapped N-way split reproduces the exact same keys
    (bit-exact), returning (final rng, (steps, N, 2) table)."""

    def body(c, _):
        c, s = jax.random.split(c)
        return c, s

    rng, step_keys = jax.lax.scan(body, rng, None, length=steps)
    table = jax.vmap(lambda k: jax.random.split(k, num_clients))(step_keys)
    return rng, table


def _megakernel_block_clients(clients_per_edge: int, bytes_per_client: int) -> int:
    """Client-block size: the largest divisor of ``clients_per_edge`` whose
    block of param+opt rows fits the residency budget (a few MB — VMEM-scale
    on TPU, LLC-scale on CPU). Blocks never straddle an edge, so the
    trailing segment mean stays a per-edge reshape."""
    budget = 4 << 20
    best = 1
    for b in range(1, clients_per_edge + 1):
        if clients_per_edge % b == 0 and b * bytes_per_client <= budget:
            best = b
    return best


def build_megakernel_super_round(
    loss_fn: LossFn,
    optimizer: GradientTransformation,
    topology: Topology,
    config: HierFAVGConfig,
    weights: jnp.ndarray,
    *,
    grad_accum: int = 1,
    block_clients: Optional[int] = None,
):
    """``build_super_round`` lowered client-blocked: the fused edge-interval
    megakernel as one executable per cloud interval.

    The scan-fused superround is step-major — every client advances one
    local step before any client takes its next — so each of the κ₁ steps
    streams the whole stacked (N, …) state through the memory hierarchy.
    This lowering is client-major: per edge interval it maps over blocks of
    ``block_clients`` clients, each block running all κ₁ (unrolled) local
    steps while its params/opt rows stay resident (VMEM on TPU, LLC on
    CPU), then applies the trailing edge/cloud weighted mean. Per-step
    memory traffic drops by ~κ₁× once the stacked state exceeds the cache —
    the regime where this path wins (see docs/performance.md); eligibility
    is ``megakernel_incompatibility``.

        super_round(state, batches, masks=None) -> (state, metrics)

    Same contract as ``build_super_round`` — batch leaves (κ₂, κ₁, N, b,
    …), metrics ``{"loss": (κ₂,), "grad_norm": (κ₂,), "step": (κ₂,)}`` —
    except ``masks`` must be None (the eligibility predicate routes failure
    models to the scan-fused engine). Per-client RNG streams, batches, and
    step math are identical to the baseline; only the summation *order* of
    the segment means and metric reductions differs (documented tolerance,
    ``tests/test_megakernel.py``).
    """
    spec = as_hierarchy(topology)
    _check_levels(spec, config)
    reason = megakernel_incompatibility(config, spec, grad_accum=grad_accum)
    if reason is not None:
        raise ValueError(f"schedule cannot run through the megakernel: {reason}")
    n = spec.num_clients
    num_edges = spec.num_nodes(1)
    cpe = n // num_edges
    k1, k2 = config.kappa1, config.kappa2_effective
    deepest_per_round = super_round_schedule(config)  # static: 1 = edge, 2 = cloud
    w = jnp.asarray(weights, jnp.float32)
    wg = w.reshape(num_edges, cpe)
    den_edge = jnp.sum(wg, axis=1)
    den_cloud = jnp.sum(w)

    loss_p = _apply_precision(loss_fn, config.precision)

    def total_loss(params, batch, rngs):
        losses = jax.vmap(loss_p)(params, batch, rngs)
        return jnp.sum(losses), losses

    grad_fn = jax.grad(total_loss, has_aux=True)

    def edge_mean_leaf(x):
        xf = x.astype(jnp.float32).reshape((num_edges, cpe) + x.shape[1:])
        wexp = wg.reshape((num_edges, cpe) + (1,) * (x.ndim - 1))
        m = jnp.sum(xf * wexp, axis=1) / den_edge.reshape((num_edges,) + (1,) * (x.ndim - 1))
        return jnp.broadcast_to(m[:, None], xf.shape).reshape(x.shape).astype(x.dtype)

    def cloud_mean_leaf(x):
        xf = x.astype(jnp.float32)
        wexp = w.reshape((n,) + (1,) * (x.ndim - 1))
        m = jnp.sum(xf * wexp, axis=0) / den_cloud
        return jnp.broadcast_to(m[None], x.shape).astype(x.dtype)

    tmap, tleaves = jax.tree_util.tree_map, jax.tree_util.tree_leaves

    def block_steps(carry):
        """All κ₁ local steps for one client block, params/opt resident.
        carry leaves: params (Bc, …), opt (stacked (Bc, …) or shared
        scalar), batches (κ₁, Bc, …), rngs (κ₁, Bc, 2)."""
        params, opt, batches, rngs = carry
        losses_t, gsq_t = [], []
        for t in range(k1):
            batch_t = tmap(lambda x: x[t], batches)
            grads, losses = grad_fn(params, batch_t, rngs[t])
            updates, opt = optimizer.update(grads, opt, params)
            params = apply_updates(params, updates)
            losses_t.append(losses.astype(jnp.float32))
            gsq_t.append(
                sum(
                    jnp.sum(jnp.square(g.astype(jnp.float32)), axis=tuple(range(1, g.ndim)))
                    for g in tleaves(grads)
                )
            )
        return params, opt, jnp.stack(losses_t), jnp.stack(gsq_t)

    def super_round(state: FedState, batches: PyTree, masks: Optional[jnp.ndarray] = None):
        if masks is not None:
            raise TypeError(
                "the megakernel superround takes no survival masks; failure "
                "models are routed to the scan-fused engine by eligibility"
            )
        params, opt_state = state.params, state.opt_state
        for leaf in tleaves(opt_state):
            if getattr(leaf, "ndim", 0) >= 1 and leaf.shape[0] != n:
                raise ValueError(
                    f"megakernel needs optimizer state leaves that are either "
                    f"scalar (shared) or stacked (N, ...); got shape {leaf.shape}"
                )
        bytes_per_client = sum(x.nbytes // n for x in tleaves(params)) + sum(
            x.nbytes // n for x in tleaves(opt_state) if getattr(x, "ndim", 0) >= 1
        )
        bc = block_clients if block_clients is not None else _megakernel_block_clients(
            cpe, max(1, bytes_per_client)
        )
        if cpe % bc != 0:
            raise ValueError(f"block_clients={bc} does not divide clients_per_edge={cpe}")
        nb = n // bc

        def reblock(x):
            return x.reshape((nb, bc) + x.shape[1:])

        def reblock_steps(x):
            # (κ₁, N, ...) -> (nb, κ₁, Bc, ...): client-major blocks, each
            # carrying its own κ₁-step slice of batches/keys
            return jnp.moveaxis(x, 1, 0).reshape((nb, bc, k1) + x.shape[2:]).swapaxes(1, 2)

        def block_opt(x):
            if getattr(x, "ndim", 0) >= 1 and x.shape[0] == n:
                return reblock(x)
            return jnp.broadcast_to(x[None], (nb,) + jnp.shape(x))

        def unblock_opt(x, ref):
            if getattr(ref, "ndim", 0) >= 1 and ref.shape[0] == n:
                return x.reshape((n,) + x.shape[2:])
            return x[0]  # shared leaf: every block stepped it identically

        rng, table = _rng_step_table(state.rng, k1 * k2, n)
        step0 = state.step
        loss_r, gnorm_r, step_r = [], [], []
        for j in range(k2):
            pb = tmap(reblock, params)
            ob = tmap(block_opt, opt_state)
            bj = tmap(lambda x: reblock_steps(x[j]), batches)
            tb = reblock_steps(table[j * k1 : (j + 1) * k1])
            pb, ob, losses, gsq = jax.lax.map(block_steps, (pb, ob, bj, tb))
            params = tmap(lambda x: x.reshape((n,) + x.shape[2:]), pb)
            opt_state = tmap(unblock_opt, ob, opt_state)
            # (nb, κ₁, Bc) -> (κ₁, N) in canonical client order
            ls = jnp.moveaxis(losses, 0, 1).reshape(k1, n)
            gs = jnp.moveaxis(gsq, 0, 1).reshape(k1, n)
            loss_r.append(jnp.mean(ls))
            gnorm_r.append(jnp.mean(jnp.sqrt(jnp.sum(gs, axis=1))))
            step_r.append(step0 + (j + 1) * k1)
            mean_leaf = cloud_mean_leaf if deepest_per_round[j] == 2 else edge_mean_leaf
            params = tmap(mean_leaf, params)
        new_state = FedState(
            step=step0 + k1 * k2, params=params, opt_state=opt_state, rng=rng,
            anchor=state.anchor, residual=state.residual,
        )
        metrics = {
            "loss": jnp.stack(loss_r),
            "grad_norm": jnp.stack(gnorm_r),
            "step": jnp.stack(step_r),
        }
        return new_state, metrics

    return super_round


# ---------------------------------------------------------------------------
# Sampled-participation (cohort) lowering
# ---------------------------------------------------------------------------

def cohort_incompatibility(
    config: HierFAVGConfig, topology: Topology, cohort_size: int
) -> Optional[str]:
    """None if the schedule can run cohort-sampled, else a human reason.

    Mirrors ``sharding_incompatibility``: the single predicate both the
    builder (raises) and the runner's dispatch (reports) consult.
    """
    spec = as_hierarchy(topology)
    if config.aggregators_active:
        return "a robust statistic over a sampled cohort is not the population statistic"
    if not 1 <= int(cohort_size) <= spec.num_clients:
        return f"cohort_size {cohort_size} outside 1..{spec.num_clients} (population)"
    part = config.participation
    if part is not None and getattr(part, "sampler", None) == "stratified" and spec.depth >= 2:
        num_edges = spec.num_nodes(1)
        if int(cohort_size) < num_edges:
            # the floor-1-per-alive-edge quota would otherwise over-allocate;
            # reject at eligibility time, naming both numbers, instead of
            # surfacing deep inside sampler construction
            return (
                f"stratified sampling needs cohort_size >= num_edges "
                f"({cohort_size} < {num_edges}): every alive edge gets a "
                f"floor-1 quota, so a smaller cohort cannot cover the edges"
            )
    return None


def init_cohort_state(
    rng: jax.Array,
    params: PyTree,
    optimizer: GradientTransformation,
    config: HierFAVGConfig,
    cohort_size: int,
) -> FedState:
    """Cohort-resident ``FedState``: C stacked rows, not N.

    Zero-init opt_state/residual rows equal what ``ClientStateStore`` hands
    back for never-sampled clients, so a fresh state is exactly "every
    cohort member participates for the first time"."""
    stacked = replicate_for_clients(params, int(cohort_size))
    return init_state(rng, stacked, optimizer, None, config, already_stacked=True)


def _build_cohort_level_sync(spec: HierarchySpec, config: HierFAVGConfig, level: int, cohort_size: int):
    """``build_level_sync`` lowered for a sampled cohort.

    The cohort's per-level segment ids and weights arrive as *traced* inputs
    (``cohort = {"segments": (depth-1, C) int32, "weights": (C,) f32}``), so
    one compiled executable serves every sampled cohort. Segment ids are the
    cohort members' ORIGINAL node ids per level; reductions still run over
    the full node count, and nodes with no sampled member contribute nothing
    (their safe-denominator mean is never taken back). Non-participating
    clients thus carry exactly zero weight in every edge/cloud mean — the
    partial-participation HierFAVG semantics.

    The op-for-op body matches ``build_level_sync``. The top stage is
    cohort-independent (every member maps to the single root), so its ids
    stay static and keep the contiguous-reshape fast path — bit-identical
    to the full-population top stage. Sub-top stages use the traced ids'
    ``segment_sum`` path: bit-identical to the static lowering on ragged
    topologies (same op), within 1 ULP on uniform ones (where the static
    path takes the reshape shortcut instead).

    ``mask`` is an optional (C,) survival vector over the *cohort* columns
    (the failure model's population mask gathered at the sampled ids):
    masked members carry zero weight at every staged level — exactly the
    ``hierarchical_segment_mean`` mask semantics, so at C == N the masked
    cohort sync reproduces the masked full-population sync.
    """
    depth = spec.depth
    is_top = level == depth
    codec = None
    if config.transport_active:
        codec = config.transport.codec(level)
        if codec.is_identity:
            codec = None
    top_ids = np.zeros(int(cohort_size), np.int32)

    def seg(cohort, t):
        return top_ids if t == depth else cohort["segments"][t - 1]

    def stage(tree, cohort, upto, mask):
        out = tree
        for t in range(1, upto + 1):
            out = aggregation.segment_weighted_mean(
                out, cohort["weights"], seg(cohort, t), spec.num_nodes(t), mask
            )
        return out

    def level_sync(state: FedState, cohort, mask: Optional[jnp.ndarray] = None) -> FedState:
        uploaded = state.params
        residual = state.residual
        if codec is not None:
            delta = jax.tree_util.tree_map(
                lambda x, a: x.astype(jnp.float32) - a.astype(jnp.float32),
                state.params, state.anchor,
            )
            delta_hat, residual = codec.roundtrip(delta, residual)
            uploaded = jax.tree_util.tree_map(
                lambda a, d, x: (a.astype(jnp.float32) + d).astype(x.dtype),
                state.anchor, delta_hat, state.params,
            )
        if is_top and config.delta_cloud and state.anchor is not None:
            agg = lambda t: aggregation.delta_weighted_mean(t, state.anchor, cohort["weights"], mask)
            params = agg(uploaded)
            anchor = jax.tree_util.tree_map(jnp.copy, params)
        else:
            agg = lambda t: stage(t, cohort, level, mask)
            params = agg(uploaded)
            if config.transport_active:
                anchor = jax.tree_util.tree_map(jnp.copy, params)
            else:
                anchor = state.anchor
        if codec is not None:
            # every unmasked cohort member uploads and receives (weights are
            # > 0 for sampled clients); the keep-dead plumbing is structurally
            # identical to build_level_sync so the graphs only differ in ids
            w_eff = cohort["weights"].astype(jnp.float32)
            if mask is not None:
                w_eff = w_eff * mask.astype(jnp.float32)
            seg_l = jnp.asarray(seg(cohort, level), jnp.int32)
            received = jnp.take(
                jax.ops.segment_sum(w_eff, seg_l, spec.num_nodes(level)) > 0, seg_l
            )

            def keep_dead(new, old):
                r = received.reshape((-1,) + (1,) * (new.ndim - 1))
                return jnp.where(r, new, old.astype(new.dtype))

            params = jax.tree_util.tree_map(keep_dead, params, state.params)
            anchor = jax.tree_util.tree_map(keep_dead, anchor, state.anchor)
            if residual is not None and state.residual is not None:
                sent = w_eff > 0

                def keep_residual(new, old):
                    s = sent.reshape((-1,) + (1,) * (new.ndim - 1))
                    return jnp.where(s, new, old)

                residual = jax.tree_util.tree_map(keep_residual, residual, state.residual)
        opt_state = _maybe_sync_opt_state(state.opt_state, agg, config.sync_opt_state)
        return state._replace(params=params, opt_state=opt_state, anchor=anchor, residual=residual)

    return level_sync


def build_cohort_super_round(
    loss_fn: LossFn,
    optimizer: GradientTransformation,
    topology: Topology,
    config: HierFAVGConfig,
    *,
    cohort_size: int,
    grad_accum: int = 1,
):
    """``build_super_round`` for a sampled cohort of C clients.

        super_round(state, batches, cohort, masks=None) -> (state, metrics)

    ``state`` stacks C rows (``init_cohort_state``); batch leaves carry a
    leading (κ₂, κ₁) axis pair over cohort-shaped per-step batches;
    ``cohort`` is the traced ``{"segments": (depth-1, C), "weights": (C,)}``
    pytree a ``CohortPrefetcher`` assembles per cloud interval; ``masks`` is
    an optional (κ₂, C) stack of survival vectors over the cohort columns
    (failure/straggler draws gathered at the sampled ids — participation
    and survival compose by masking the cohort's weight columns). Because
    the cohort arrays are inputs rather than constants, resampling never
    recompiles — the executable is reused across every interval.

    With the identity cohort (C == N, weights/segments of the full
    population) this reproduces ``build_super_round`` exactly: bit-exact on
    ragged topologies, within the documented 1-ULP summation-order tolerance
    on uniform ones (see ``_build_cohort_level_sync``).
    """
    spec = as_hierarchy(topology)
    depth = _check_levels(spec, config)
    reason = cohort_incompatibility(config, spec, cohort_size)
    if reason is not None:
        raise ValueError(f"schedule cannot run cohort-sampled: {reason}")
    local_step = build_local_step(loss_fn, optimizer, grad_accum=grad_accum, precision=config.precision)
    level_syncs = [
        _build_cohort_level_sync(spec, config, l, cohort_size) for l in range(1, depth + 1)
    ]
    deepest_per_round = jnp.asarray(super_round_schedule(config), jnp.int32)

    def super_round(state: FedState, batches: PyTree, cohort, masks: Optional[jnp.ndarray] = None):
        def round_body(s, xs):
            if masks is None:
                deepest, batch_r = xs
                mask_r = None
            else:
                deepest, batch_r, mask_r = xs

            def step_body(ss, b):
                ss, m = local_step(ss, b)
                return ss, (m["loss"], m["grad_norm"])

            s, (losses, gnorms) = jax.lax.scan(step_body, s, batch_r)
            branches = [
                (lambda sync: lambda st: sync(st, cohort, mask_r))(sync) for sync in level_syncs
            ]
            s = jax.lax.switch(deepest - 1, branches, s)
            metrics = {
                "loss": jnp.mean(losses),
                "grad_norm": jnp.mean(gnorms),
                "step": s.step,
            }
            return s, metrics

        xs = (deepest_per_round, batches)
        if masks is not None:
            xs = xs + (masks,)
        return jax.lax.scan(round_body, state, xs)

    return super_round


def map_stacked_fed_state(state: FedState, stacked_fn, other_fn, stacked_dim: int) -> FedState:
    """Rebuild a ``FedState`` applying ``stacked_fn`` to every params /
    opt_state / anchor / residual leaf carrying the leading ``stacked_dim``
    client axis and ``other_fn`` to everything else (``step``/``rng`` are
    always "other": their shapes may coincidentally equal ``stacked_dim``).
    The single place that knows which FedState fields carry client rows —
    partition specs and the engine's permute/pad both go through it."""

    def leaf(x):
        if getattr(x, "ndim", 0) >= 1 and x.shape[0] == stacked_dim:
            return stacked_fn(x)
        return other_fn(x)

    sub = lambda t: jax.tree_util.tree_map(leaf, t)
    return FedState(
        step=other_fn(state.step),
        params=sub(state.params),
        opt_state=sub(state.opt_state),
        rng=other_fn(state.rng),
        anchor=None if state.anchor is None else sub(state.anchor),
        residual=None if state.residual is None else sub(state.residual),
    )


def fed_state_partition_specs(state: FedState, axis: str, stacked_dim: int):
    """PartitionSpecs for a (padded) stacked ``FedState``: leaves with a
    leading ``stacked_dim`` client axis shard over ``axis``; ``step`` /
    ``rng`` and scalar opt leaves replicate. Shared by ``shard_map`` specs
    and the engine's ``NamedSharding`` placement."""
    from jax.sharding import PartitionSpec as P

    row, rep = P(axis), P()
    return map_stacked_fed_state(state, lambda _: row, lambda _: rep, stacked_dim)


def build_sharded_super_round(
    loss_fn: LossFn,
    optimizer: GradientTransformation,
    topology: Topology,
    config: HierFAVGConfig,
    weights: jnp.ndarray,
    *,
    mesh,
    axis: str = "clients",
    placement: Optional[ShardPlacement] = None,
    grad_accum: int = 1,
):
    """``build_super_round`` with the stacked client axis sharded over
    ``mesh``'s ``axis`` via ``shard_map`` — the hardware topology mirrors
    the client-edge-cloud topology.

    The state/batches/masks must be in *placement order*: permuted by
    ``placement.gather_index()`` and padded to ``placement.padded_clients``
    (phantom positions carry zero weight; ``fed.engine`` owns the
    conversion). Inside the body every sub-top aggregation is a device-local
    segment reduction and each cloud boundary issues exactly one grouped
    ``psum`` (``core.aggregation.psum_weighted_mean``); per-client RNG
    streams are reproduced exactly by replicating the ``split`` of the
    global key and gathering each shard's original client ids, so local
    steps and sub-top syncs match the single-device superround bit-for-bit
    and only the cloud psum reassociates the weighted sum (documented ULP
    tolerance; see docs/performance.md).

        super_round(state, batches, masks=None) -> (state, metrics)

    batch leaves carry (κ₂, κ₁, padded_N, b, ...); ``masks`` is an optional
    (κ₂, padded_N) stack. Metrics stay per-client so no collective is spent
    on diagnostics: ``{"loss": (κ₂, κ₁, padded_N), "gsq": (κ₂, κ₁,
    padded_N), "step": (κ₂,)}`` — the engine reduces them host-side at
    flush time (phantom columns dropped).
    """
    from jax.sharding import PartitionSpec as P

    spec = as_hierarchy(topology)
    depth = _check_levels(spec, config)
    num_shards = int(mesh.shape[axis])
    if placement is None:
        try:
            placement = plan_shard_placement(spec, num_shards)
        except ValueError as e:
            raise ValueError(f"schedule cannot run client-sharded: {e}") from None
    # validate the layout that actually runs, not a freshly planned one
    reason = sharding_incompatibility(config, spec, num_shards, placement=placement)
    if reason is not None:
        raise ValueError(f"schedule cannot run client-sharded: {reason}")
    shard = ClientSharding.build(axis, placement, weights)
    microbatch_grads = _build_microbatch_grads(_apply_precision(loss_fn, config.precision), grad_accum)
    level_syncs = [
        build_level_sync(spec, config, weights, lvl, shard=shard) for lvl in range(1, depth + 1)
    ]
    deepest_per_round = jnp.asarray(super_round_schedule(config), jnp.int32)
    ids_table = shard.client_ids_table()
    n_real = spec.num_clients
    n_padded = placement.padded_clients

    def local_step(s: FedState, batch: PyTree, rngs):
        grads, losses = microbatch_grads(s.params, batch, rngs)
        updates, opt_state = optimizer.update(grads, s.opt_state, s.params)
        params = apply_updates(s.params, updates)
        gsq = sum(
            jnp.sum(jnp.square(g.astype(jnp.float32)), axis=tuple(range(1, g.ndim)))
            for g in jax.tree_util.tree_leaves(grads)
        )
        return (
            FedState(
                step=s.step + 1, params=params, opt_state=opt_state, rng=s.rng,
                anchor=s.anchor, residual=s.residual,
            ),
            losses.astype(jnp.float32),
            gsq,
        )

    def body(state: FedState, batches: PyTree, masks):
        ids = _shard_row(ids_table, axis)
        k1 = config.kappa1
        k2 = len(super_round_schedule(config))
        # Per-step key derivation hoisted out of the step scan: the baseline
        # chain (rng, step_rng = split(rng); split(step_rng, N)) replicated
        # O(N) work inside every sequential scan iteration, which at batch 1
        # dominated the (tiny) per-step math. A scan of bare splits plus one
        # vmapped N-way split + gather of this shard's original client ids
        # reproduces the exact same keys (bit-exact; phantoms reuse client
        # 0's key, their weight is zero) as one batched op per interval.
        def split_body(c, _):
            c, s = jax.random.split(c)
            return c, s

        rng_out, step_keys = jax.lax.scan(split_body, state.rng, None, length=k1 * k2)
        local_keys = jax.vmap(
            lambda k: jnp.take(jax.random.split(k, n_real), ids, axis=0)
        )(step_keys)
        local_keys = local_keys.reshape((k2, k1) + local_keys.shape[1:])
        state = state._replace(rng=rng_out)

        def round_body(s, xs):
            if masks is None:
                deepest, batch_r, keys_r = xs
                mask_r = None
            else:
                deepest, batch_r, keys_r, mask_r = xs

            def step_body(ss, bk):
                b, rngs = bk
                ss, losses, gsq = local_step(ss, b, rngs)
                return ss, (losses, gsq)

            s, (losses, gsqs) = jax.lax.scan(step_body, s, (batch_r, keys_r))
            branches = [(lambda sync: lambda st: sync(st, mask_r))(sync) for sync in level_syncs]
            s = jax.lax.switch(deepest - 1, branches, s)
            return s, {"loss": losses, "gsq": gsqs, "step": s.step}

        xs = (deepest_per_round, batches, local_keys)
        if masks is not None:
            xs = xs + (masks,)
        return jax.lax.scan(round_body, state, xs)

    # batch leaves are (κ₂, κ₁, N, b, ...) — or (κ₂, κ₁, accum, N, b, ...)
    # when microbatch accumulation shifts the client dim right by one
    client_dim = 2 + (1 if grad_accum > 1 else 0)
    batch_spec = P(*([None] * client_dim + [axis]))

    def super_round(state: FedState, batches: PyTree, masks: Optional[jnp.ndarray] = None):
        state_specs = fed_state_partition_specs(state, axis, n_padded)
        batch_specs = jax.tree_util.tree_map(lambda _: batch_spec, batches)
        metric_specs = {"loss": P(None, None, axis), "gsq": P(None, None, axis), "step": P()}
        if masks is None:
            fn = jax.shard_map(
                lambda s, b: body(s, b, None), mesh=mesh,
                in_specs=(state_specs, batch_specs),
                out_specs=(state_specs, metric_specs), check_vma=False,
            )
            return fn(state, batches)
        fn = jax.shard_map(
            body, mesh=mesh,
            in_specs=(state_specs, batch_specs, P(None, axis)),
            out_specs=(state_specs, metric_specs), check_vma=False,
        )
        return fn(state, batches, masks)

    return super_round


# ---------------------------------------------------------------------------
# Sharded cohort lowering (population scale x device scale)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class _CohortSharding(ClientSharding):
    """``ClientSharding`` over a sampled cohort's *slot* axis.

    Placement-stable packing makes the slot layout — and so every local
    segment table — static (a pure function of topology, cohort size, and
    mesh), but the aggregation weights are the sampled cohort's weight
    columns, traced per interval. ``weights_table`` is repurposed as a
    one-slot mutable cell the ``shard_map`` body fills with this shard's
    traced (capacity,) weight slice before any level sync traces.
    """

    def bind_local_weights(self, w_local) -> None:
        self.weights_table[0] = w_local

    def local_weights(self):
        w = self.weights_table[0]
        if w is None:
            raise RuntimeError(
                "cohort shard weights are bound inside the shard_map body; "
                "call bind_local_weights first"
            )
        return w


def _cohort_quotas(spec: HierarchySpec, cohort_size: int) -> np.ndarray:
    """Per-level-1-node stratified slot quotas — the pure function of
    (topology, cohort_size) that placement-stable packing rests on."""
    if spec.depth == 1:
        return np.asarray([int(cohort_size)], np.int64)
    from repro.fed.participation import stratified_quotas

    return stratified_quotas(spec.group_sizes(1), int(cohort_size))


def sharded_cohort_incompatibility(
    config: HierFAVGConfig,
    topology: Topology,
    cohort_size: int,
    num_shards: int,
    placement: Optional[ShardPlacement] = None,
) -> Optional[str]:
    """Why this schedule cannot run cohort-sampled AND client-sharded over
    ``num_shards`` devices — None when it can.

    Mirrors ``sharding_incompatibility``/``cohort_incompatibility``: the
    single predicate both ``build_sharded_cohort_super_round`` (raises) and
    the runner's eligibility dispatch (reports) consult. Pass ``placement``
    to validate the cohort slot placement that will actually run.
    """
    spec = as_hierarchy(topology)
    reason = cohort_incompatibility(config, spec, cohort_size)
    if reason is not None:
        return reason
    part = config.participation
    if part is not None and getattr(part, "sampler", None) != "stratified" and spec.depth >= 2:
        return (
            f"sharded cohorts need the stratified sampler (placement-stable "
            f"per-edge quotas fix the slot->shard layout); got "
            f"{getattr(part, 'sampler', None)!r}"
        )
    if config.delta_cloud and config.sync_opt_state:
        return "delta_cloud + sync_opt_state do not compose (the opt tree has no anchor)"
    try:
        quotas = _cohort_quotas(spec, cohort_size)
    except ValueError as e:
        return str(e)
    if placement is None:
        try:
            plan_cohort_placement(spec, quotas, num_shards)
        except ValueError as e:
            return str(e)
    else:
        from repro.core.hierarchy import cohort_hierarchy

        slot_spec = cohort_hierarchy(spec, quotas)
        if placement.num_shards != num_shards or placement.spec != slot_spec:
            return (
                f"placement was planned for {placement.num_shards} shard(s) over "
                f"{placement.spec.describe()}, not {num_shards} shard(s) over "
                f"the {slot_spec.describe()} cohort slot tree"
            )
    return None


def build_sharded_cohort_super_round(
    loss_fn: LossFn,
    optimizer: GradientTransformation,
    topology: Topology,
    config: HierFAVGConfig,
    *,
    cohort_size: int,
    mesh,
    axis: str = "clients",
    placement: Optional[ShardPlacement] = None,
    grad_accum: int = 1,
):
    """``build_cohort_super_round`` with the cohort's slot axis sharded over
    ``mesh``'s ``axis`` — population scale and device scale multiply.

    **Placement-stable packing.** Under stratified sampling the per-edge
    cohort quotas are a pure function of (topology, cohort_size)
    (``fed.participation.stratified_quotas``), so the cohort's *slot* tree
    (``core.hierarchy.cohort_hierarchy``) — slot j always reports to the
    same edge — and the edge-aligned shard placement planned from it
    (``plan_cohort_placement``) are computed once and reused for every
    sampled cohort. Per-interval sampling only changes which client fills
    each fixed per-edge slot: segment tables stay static (keeping the
    uniform reshape fast paths), and only the (padded_C,) weight vector is
    traced. Phantom slots (LPT packing pad) carry zero weight.

        super_round(state, batches, weights, masks=None) -> (state, metrics)

    Inputs are in *slot placement order*, permuted by
    ``placement.gather_index()`` and padded to ``placement.padded_clients``
    (``fed.engine.CohortEngine`` owns the conversion): state stacks
    padded_C rows, batch leaves carry (κ₂, κ₁, padded_C, b, ...),
    ``weights`` is the (padded_C,) sampled weight vector (phantoms zero),
    ``masks`` an optional (κ₂, padded_C) survival stack. Sub-top syncs are
    device-local segment means adding members in the single-device cohort
    order (bit-exact); each cloud boundary issues exactly one grouped psum
    (documented rtol=3e-6 reassociation tolerance). Per-slot RNG streams
    reproduce the single-device cohort engine's position-keyed streams
    exactly (hoisted split table gathered at slot ids — at C == N these are
    the original client ids, matching ``build_sharded_super_round``).
    Metrics stay per-client: ``{"loss": (κ₂, κ₁, padded_C), "gsq": (κ₂,
    κ₁, padded_C), "step": (κ₂,)}``, reduced host-side at flush.
    """
    from jax.sharding import PartitionSpec as P

    spec = as_hierarchy(topology)
    depth = _check_levels(spec, config)
    num_shards = int(mesh.shape[axis])
    reason = sharded_cohort_incompatibility(
        config, spec, cohort_size, num_shards, placement=placement
    )
    if reason is not None:
        raise ValueError(f"schedule cannot run sharded-cohort: {reason}")
    if placement is None:
        placement = plan_cohort_placement(spec, _cohort_quotas(spec, cohort_size), num_shards)
    shard = _CohortSharding(axis=axis, placement=placement, weights_table=[None])
    microbatch_grads = _build_microbatch_grads(_apply_precision(loss_fn, config.precision), grad_accum)
    level_syncs = []
    for lvl in range(1, depth + 1):
        codec = None
        if config.transport_active:
            codec = config.transport.codec(lvl)
            if codec.is_identity:
                codec = None
        # robust is always None here: cohort_incompatibility rejects
        # non-default aggregators before this point
        level_syncs.append(
            _build_sharded_level_sync(placement.spec, config, lvl, codec, None, shard)
        )
    deepest_per_round = jnp.asarray(super_round_schedule(config), jnp.int32)
    slots_table = shard.client_ids_table()  # (num_shards, capacity) slot ids
    c = int(cohort_size)
    c_padded = placement.padded_clients

    def local_step(s: FedState, batch: PyTree, rngs):
        grads, losses = microbatch_grads(s.params, batch, rngs)
        updates, opt_state = optimizer.update(grads, s.opt_state, s.params)
        params = apply_updates(s.params, updates)
        gsq = sum(
            jnp.sum(jnp.square(g.astype(jnp.float32)), axis=tuple(range(1, g.ndim)))
            for g in jax.tree_util.tree_leaves(grads)
        )
        return (
            FedState(
                step=s.step + 1, params=params, opt_state=opt_state, rng=s.rng,
                anchor=s.anchor, residual=s.residual,
            ),
            losses.astype(jnp.float32),
            gsq,
        )

    def body(state: FedState, batches: PyTree, weights, masks):
        shard.bind_local_weights(weights)
        slots = _shard_row(slots_table, axis)
        k1 = config.kappa1
        k2 = len(super_round_schedule(config))
        # hoisted per-step key table (see build_sharded_super_round): the
        # single-device cohort chain is (rng, step_rng = split(rng);
        # split(step_rng, C)) keyed by cohort POSITION; reproducing it here
        # via a scan of splits + a gather of this shard's slot ids is
        # bit-exact (phantoms reuse slot 0's key, their weight is zero)
        def split_body(cc, _):
            cc, s = jax.random.split(cc)
            return cc, s

        rng_out, step_keys = jax.lax.scan(split_body, state.rng, None, length=k1 * k2)
        local_keys = jax.vmap(
            lambda k: jnp.take(jax.random.split(k, c), slots, axis=0)
        )(step_keys)
        local_keys = local_keys.reshape((k2, k1) + local_keys.shape[1:])
        state = state._replace(rng=rng_out)

        def round_body(s, xs):
            if masks is None:
                deepest, batch_r, keys_r = xs
                mask_r = None
            else:
                deepest, batch_r, keys_r, mask_r = xs

            def step_body(ss, bk):
                b, rngs = bk
                ss, losses, gsq = local_step(ss, b, rngs)
                return ss, (losses, gsq)

            s, (losses, gsqs) = jax.lax.scan(step_body, s, (batch_r, keys_r))
            branches = [(lambda sync: lambda st: sync(st, mask_r))(sync) for sync in level_syncs]
            s = jax.lax.switch(deepest - 1, branches, s)
            return s, {"loss": losses, "gsq": gsqs, "step": s.step}

        xs = (deepest_per_round, batches, local_keys)
        if masks is not None:
            xs = xs + (masks,)
        return jax.lax.scan(round_body, state, xs)

    client_dim = 2 + (1 if grad_accum > 1 else 0)
    batch_spec = P(*([None] * client_dim + [axis]))

    def super_round(state: FedState, batches: PyTree, weights, masks: Optional[jnp.ndarray] = None):
        state_specs = fed_state_partition_specs(state, axis, c_padded)
        batch_specs = jax.tree_util.tree_map(lambda _: batch_spec, batches)
        metric_specs = {"loss": P(None, None, axis), "gsq": P(None, None, axis), "step": P()}
        if masks is None:
            fn = jax.shard_map(
                lambda s, b, w: body(s, b, w, None), mesh=mesh,
                in_specs=(state_specs, batch_specs, P(axis)),
                out_specs=(state_specs, metric_specs), check_vma=False,
            )
            return fn(state, batches, weights)
        fn = jax.shard_map(
            body, mesh=mesh,
            in_specs=(state_specs, batch_specs, P(axis), P(None, axis)),
            out_specs=(state_specs, metric_specs), check_vma=False,
        )
        return fn(state, batches, weights, masks)

    return super_round
