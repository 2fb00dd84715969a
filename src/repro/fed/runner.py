"""Federated round runner: the host-side training orchestrator.

Drives ``core.hierfavg`` edge-interval by edge-interval:

    for round r:                       # r-th edge interval (κ₁ local steps)
        mask  = failure detector + straggler deadline      (host)
        state = hier_round(state, batches_r, r, mask)      (device, jitted)
        if r % kappa2 == kappa2-1: cloud boundary          (inside hier_round)
        eval / checkpoint / cost accounting                (host)

This is the deployable loop: one executable for the whole run, host logic
only at aggregation boundaries (the natural synchronization points of the
paper's protocol). Metrics include the paper's T/E accounting (cost_model)
so experiments read time-to-accuracy directly off the run log.

By default (``RunnerConfig.engine="auto"``) every whole cloud interval is
delegated to the zero-copy superround engine (``fed.engine``): one donated
dispatch per κ₂ edge intervals, device-side batch prefetch, and async
metrics — bit-exact versus this per-round loop, which remains the fallback
whenever ``eval_every``/``checkpoint_every`` demand finer granularity than
a cloud interval. With a device mesh (``mesh=`` or ``RunnerConfig.mesh``)
the engine runs client-sharded over the mesh's ``"clients"`` axis — edge
syncs device-local, one grouped psum per cloud interval — rather than
falling back to the per-round loop; only a schedule the sharded lowering
cannot express (``core.hierfavg.sharding_incompatibility``) or an explicit
``state_shardings`` pytree keeps whole cloud intervals on the per-round
path.

When ``hier_config.transport`` declares per-level link codecs, the cost
accounting automatically switches to the compressed wire: T/E use
``WorkloadCosts.with_bits`` and each round records the cumulative uplink
bytes per client (``wire_mb``) from the ``dist.collectives`` traffic model
at the transport's per-level bits-per-parameter.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import cost_model as cm
from repro.core.hierarchy import as_hierarchy
from repro.core.hierfavg import (
    FedState,
    HierFAVGConfig,
    Topology,
    build_hier_round,
    init_state,
)
from repro.dist import collectives
from repro.fed.failures import FailureSimulator, StragglerModel, compose_masks

PyTree = Any


@dataclasses.dataclass
class RunnerConfig:
    num_rounds: int  # edge intervals to run (= K / kappa1)
    eval_every: int = 0  # rounds between evals (0 = never)
    checkpoint_every: int = 0  # rounds between checkpoints (0 = never)
    target_accuracy: float = 0.0  # stop early when reached (0 = never)
    straggler_deadline_pct: float = 95.0
    # "auto": superround engine (fed.engine) for every whole cloud interval
    # whose boundaries satisfy eval/checkpoint granularity, per-round
    # otherwise; "superround" forces the engine (raises if ineligible);
    # "megakernel" is the opt-in client-blocked fast path (falls back to the
    # scan-fused superround with a named reason when the schedule is not
    # block-separable; see core.hierfavg.megakernel_incompatibility);
    # "per_round" forces the legacy one-dispatch-per-edge-interval loop.
    engine: str = "auto"
    # device mesh for client-sharded execution (jax.sharding.Mesh with a
    # "clients" axis; see dist.sharding.client_mesh). The FederatedRunner
    # constructor's mesh= argument wins when both are given.
    mesh: Any = None

    def __post_init__(self):
        # fail at construction, not on the first run() call
        if self.engine not in ("auto", "superround", "megakernel", "per_round"):
            raise ValueError(
                f"RunnerConfig.engine must be auto|superround|megakernel|per_round, "
                f"got {self.engine!r}"
            )


@dataclasses.dataclass
class RoundRecord:
    round: int
    step: int
    loss: float
    mask_alive: int
    sim_time_s: float
    sim_energy_j: float
    accuracy: Optional[float] = None
    wire_mb: float = 0.0  # cumulative uplink MB/client on the compressed wire
    grad_norm: Optional[float] = None  # mean stacked-gradient norm over the round
    # event-clock seconds at the round's close under the deadline engine
    # (0.0 for the synchronous drivers, which have no event clock)
    wall_clock_s: float = 0.0


class FederatedRunner:
    def __init__(
        self,
        *,
        loss_fn,
        optimizer,
        topology: Topology,  # FedTopology or a ragged HierarchySpec
        hier_config: HierFAVGConfig,
        data_sizes: np.ndarray,
        batcher,  # FederatedBatcher
        runner_config: RunnerConfig,
        eval_fn: Optional[Callable[[PyTree], float]] = None,
        costs: Optional[cm.WorkloadCosts] = None,
        failures: Optional[FailureSimulator] = None,
        stragglers: Optional[StragglerModel] = None,
        deadline=None,  # fed.deadline.SemiSyncScheduler (semi-synchronous cloud)
        checkpointer=None,  # checkpoint.manager.CheckpointManager
        grad_accum: int = 1,
        mesh=None,
        state_shardings=None,
    ):
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.topology = topology
        self.hier_config = hier_config
        self.weights = jnp.asarray(data_sizes, jnp.float32)
        self.batcher = batcher
        self.cfg = runner_config
        self.eval_fn = eval_fn
        self.costs = costs
        self.transport = getattr(hier_config, "transport", None)
        if self.costs is not None and self.transport is not None:
            # T/E accounting on the compressed wire: edge hop = level 1,
            # cloud hop = top level (matches kappa2_effective's 2-level view)
            self.costs = self.costs.with_bits(
                self.transport.bits_per_param(1),
                self.transport.bits_per_param(self.transport.depth),
            )
        self.failures = failures
        self.stragglers = stragglers
        self.deadline = deadline
        # the most recent mask composition's channels (dead vs late) — the
        # deadline engine reads the dead channel to skip-and-reweight outaged
        # edges without force-waiting on them
        self._last_mask_parts = None
        self.checkpointer = checkpointer
        self.grad_accum = grad_accum
        self.mesh = mesh if mesh is not None else runner_config.mesh
        self._state_shardings = state_shardings
        self._mesh_reason: Optional[str] = None
        self._megakernel_reason: Optional[str] = None
        # the edge-aligned placement is a pure function of (topology, mesh):
        # plan it once and share it between eligibility checks and the engine
        self._placement = None
        self._placement_error: Optional[str] = None
        # the cohort *slot* placement (sampled participation + mesh) is
        # likewise pure in (topology, mesh, cohort_size): planned once
        self._cohort_placement = None
        self._cohort_placement_error: Optional[str] = None
        self._engine = None  # lazily built (and cached) SuperRoundEngine / CohortEngine
        # sampled participation: the active ParticipationSpec (or None), the
        # host-side ClientStateStore (built lazily from the first state seen,
        # which fixes the sticky-row template), and the cached cohort sampler
        self.participation = (
            hier_config.participation if getattr(hier_config, "participation_active", False) else None
        )
        self.client_store = None
        self._sampler = None

        if self.participation is not None:
            # the per-round lowering is never driven under sampled
            # participation (no full-population state exists to feed it)
            self._round = None
        else:
            round_fn = build_hier_round(
                loss_fn, optimizer, topology, hier_config, self.weights, grad_accum=grad_accum
            )
            if self.mesh is not None and state_shardings is not None:
                self._round = jax.jit(round_fn, in_shardings=(state_shardings, None, None, None),
                                      out_shardings=(state_shardings, None))
            else:
                self._round = jax.jit(round_fn)
        self.history: List[RoundRecord] = []

    # ------------------------------------------------------------------
    def init(self, rng: jax.Array, params: PyTree) -> FedState:
        if self.participation is not None:
            from repro.core.hierfavg import init_cohort_state

            return init_cohort_state(
                rng, params, self.optimizer, self.hier_config, self.participation.cohort_size
            )
        build = lambda r, p: init_state(r, p, self.optimizer, self.topology, self.hier_config)
        if self.mesh is None or self._state_shardings is not None:
            return build(rng, params)
        from repro.dist.sharding import canonical_state_shardings, client_axis_of

        # a client mesh exists because the stacked state may not fit one
        # device: build it laid out over the mesh, as the engine hands it back
        n = self.topology.num_clients
        shardings = canonical_state_shardings(
            self.mesh, client_axis_of(self.mesh), jax.eval_shape(build, rng, params), n, n
        )
        return jax.jit(build, out_shardings=shardings)(rng, params)

    def restore_or_init(self, rng: jax.Array, params: PyTree) -> tuple:
        """(state, start_round). Resumes from the latest checkpoint if any."""
        state = self.init(rng, params)
        if self.checkpointer is None:
            return state, 0
        if self.participation is not None:
            # cohort checkpoints are the composite {"fed", "store"} pytree,
            # with batcher + cohort-sampler snapshots in the metadata
            store = self._ensure_client_store(state)
            restored = self.checkpointer.restore_latest({"fed": state, "store": store.state()})
            if restored is not None:
                payload, meta = restored
                store.load(payload["store"])
                if "batcher" in meta:
                    self.batcher.load_state_dict(meta["batcher"])
                if "sampler" in meta:
                    self._cohort_sampler().load_state_dict(meta["sampler"])
                if self.failures is not None and "failures" in meta:
                    self.failures.load_state_dict(meta["failures"])
                if self.stragglers is not None and "stragglers" in meta:
                    self.stragglers.load_state_dict(meta["stragglers"])
                return payload["fed"], int(meta.get("round", 0))
            return state, 0
        restored = self.checkpointer.restore_latest(state)
        if restored is not None:
            state, meta = restored
            if "batcher" in meta:
                self.batcher.load_state_dict(meta["batcher"])
            if self.failures is not None and "failures" in meta:
                self.failures.load_state_dict(meta["failures"])
            if self.stragglers is not None and "stragglers" in meta:
                self.stragglers.load_state_dict(meta["stragglers"])
            if self.deadline is not None and "deadline" in meta:
                # the scheduler's event queue + staleness state resume the
                # identical event sequence an uninterrupted run would produce
                self.deadline.load_state_dict(meta["deadline"])
            return state, int(meta.get("round", 0))
        return state, 0

    # -- sampled-participation runtime (shared by engine and resume path) ----
    def _cohort_sampler(self):
        """The run's single cohort sampler (cached: its RNG stream IS the
        cohort sequence, so everyone must share one instance)."""
        if self._sampler is None:
            self._sampler = self.participation.build_sampler(as_hierarchy(self.topology))
        return self._sampler

    def _ensure_client_store(self, state: FedState):
        """Build (once) the host store from the cohort state's sticky-row
        template — stacked opt_state leaves + EF residual rows."""
        if self.client_store is None:
            from repro.fed.client_store import ClientStateStore, sticky_rows

            rows = sticky_rows(state, int(self.participation.cohort_size))
            self.client_store = ClientStateStore.from_rows(
                self.topology.num_clients, jax.device_get(rows)
            )
        return self.client_store

    # ------------------------------------------------------------------
    def _mask_for_round(self) -> Optional[np.ndarray]:
        """Per-round survival mask; the combined mask is bit-identical to the
        historical ``combine_masks`` of every model, but the composition keeps
        the *dead* (outage: no contribution) and *late* (deadline miss: the
        compute happened, the upload is deferred) channels apart on
        ``_last_mask_parts`` for the deadline engine."""
        dead = []
        late = []
        if self.failures is not None:
            dead.append(self.failures.step())
        if self.stragglers is not None:
            m, _ = self.stragglers.survivors(
                self.hier_config.kappa1, None
            )
            late.append(m)
        parts = compose_masks(dead=dead, late=late)
        self._last_mask_parts = parts
        return parts.effective

    def eval_model(self, params: PyTree, mask: Optional[jnp.ndarray]) -> PyTree:
        """The single cloud model the eval/serving path should score: the
        weighted mean of client models — or, when the schedule configures a
        non-default top-level aggregator (``AggregatorSpec``), that robust
        statistic, so robust experiments are judged by the model the cloud
        would actually publish."""
        cfg = self.hier_config
        if getattr(cfg, "aggregators_active", False):
            top = cfg.aggregators.aggregator(cfg.num_levels)
            if not top.is_default:
                spec = as_hierarchy(self.topology)
                agg = top(params, self.weights, spec, spec.depth, mask)
                return jax.tree_util.tree_map(lambda x: x[0], agg)
        from repro.core import aggregation

        # single-model reduction: no (N, ...) broadcast allocation
        return aggregation.cloud_model(params, self.weights, mask)

    def _wire_bytes_per_step(self, state: FedState) -> float:
        """Summed per-level uplink bytes per local step for one client
        (bottleneck link, amortized by each level's interval), at the
        transport's per-level bits-per-parameter."""
        spec = as_hierarchy(self.topology)
        per_client_bytes = sum(
            leaf.size // leaf.shape[0] * leaf.dtype.itemsize
            for leaf in jax.tree_util.tree_leaves(state.params)
        )
        bits = self.transport.bits_vector() if self.transport is not None else None
        traffic = collectives.hierarchy_traffic_per_step(
            float(per_client_bytes), spec, self.hier_config.kappa_vector,
            bits_per_param=bits,
        )
        return float(sum(traffic))

    def _record_round(
        self,
        round_index: int,
        step: int,
        loss: float,
        grad_norm: float,
        mask_alive: int,
        wire_per_step: float,
        accuracy: Optional[float] = None,
        wall_clock_s: float = 0.0,
    ) -> RoundRecord:
        """Assemble and append one round's ``RoundRecord`` — the single
        site both drivers (per-round loop and superround engine) share, so
        cost-model T/E, wire accounting, and any future fields stay
        field-for-field identical between the two histories."""
        sim_t = sim_e = 0.0
        if self.costs is not None:
            k1 = self.hier_config.kappa1
            k2 = self.hier_config.kappa2_effective
            sim_t = cm.time_at_step(self.costs, k1, k2, step)
            sim_e = cm.energy_at_step(self.costs, k1, k2, step)
        record = RoundRecord(
            round=round_index,
            step=step,
            loss=loss,
            mask_alive=mask_alive,
            sim_time_s=sim_t,
            sim_energy_j=sim_e,
            accuracy=accuracy,
            wire_mb=step * wire_per_step / 1e6,
            grad_norm=grad_norm,
            wall_clock_s=wall_clock_s,
        )
        self.history.append(record)
        return record

    def _superround_eligible(self, start_round: int) -> bool:
        """The engine drives whole cloud intervals with host seams at cloud
        boundaries only — eval/checkpoint cadences must land there. A mesh
        no longer forces the per-round loop: whole cloud intervals run
        client-sharded unless the schedule cannot be lowered
        (``core.hierfavg.sharding_incompatibility``) or the caller pinned an
        explicit per-round ``state_shardings`` pytree."""
        self._mesh_reason = None  # never report a stale reason
        k2 = self.hier_config.kappa2_effective
        if start_round % k2 != 0:
            return False
        for every in (self.cfg.eval_every, self.cfg.checkpoint_every):
            if every and every % k2 != 0:
                return False
        if self.mesh is not None:
            if self._state_shardings is not None:
                self._mesh_reason = (
                    "an explicit state_shardings pytree pins the legacy "
                    "per-round mesh path"
                )
                return False
            if self.grad_accum > 1:
                # the prefetcher's block layout carries no microbatch axis,
                # so the engine's client-dim-2 sharding contract breaks
                self._mesh_reason = "grad_accum > 1 has no sharded block layout yet"
                return False
            self._mesh_reason = self._plan_mesh_placement()
            if self._mesh_reason is not None:
                return False
        return True

    def _plan_mesh_placement(self) -> Optional[str]:
        """Plan (once) and validate the edge-aligned placement for the
        mesh; returns the incompatibility reason, or None with
        ``self._placement`` populated for the engine to reuse."""
        from repro.core.hierfavg import sharding_incompatibility
        from repro.dist.sharding import client_axis_of

        axis = client_axis_of(self.mesh)
        num_shards = int(self.mesh.shape[axis])
        if self._placement is None and self._placement_error is None:
            from repro.core.hierarchy import plan_shard_placement

            try:
                self._placement = plan_shard_placement(
                    as_hierarchy(self.topology), num_shards
                )
            except ValueError as e:
                self._placement_error = str(e)
        if self._placement_error is not None:
            return self._placement_error
        return sharding_incompatibility(
            self.hier_config, self.topology, num_shards, placement=self._placement
        )

    def _check_megakernel(self) -> Optional[str]:
        """None if whole cloud intervals can run through the client-blocked
        megakernel lowering, else why the engine falls back to the scan-fused
        superround. Runner-level seams first (mesh routing, masks, overridden
        detectors), then the schedule-level predicate
        (``core.hierfavg.megakernel_incompatibility``). The reason is cached
        on ``_megakernel_reason`` for reporting — the fallback is named, not
        silent, mirroring the ``_mesh_reason`` idiom."""
        from repro.core.hierfavg import megakernel_incompatibility

        if self.mesh is not None:
            reason = "a device mesh routes to the client-sharded superround"
        elif self.grad_accum > 1:
            reason = "microbatch accumulation keeps the scan-fused path"
        elif self.failures is not None or self.stragglers is not None:
            reason = "failure/straggler masks need the scan-fused survival plumbing"
        elif (
            getattr(self._mask_for_round, "__func__", None)
            is not FederatedRunner._mask_for_round
        ):
            reason = "an overridden failure detector is a live per-round mask seam"
        else:
            reason = megakernel_incompatibility(
                self.hier_config, self.topology, grad_accum=self.grad_accum
            )
        self._megakernel_reason = reason
        return reason

    def _plan_cohort_placement(self) -> Optional[str]:
        """Plan (once) and validate the cohort *slot* placement for the
        mesh; returns the incompatibility reason, or None with
        ``self._cohort_placement`` populated for the engine to reuse.
        Placement-stable packing: the slot layout is a pure function of
        (topology, mesh, cohort_size), so one plan serves every interval."""
        from repro.core.hierfavg import (
            _cohort_quotas,
            sharded_cohort_incompatibility,
        )
        from repro.dist.sharding import client_axis_of

        axis = client_axis_of(self.mesh)
        num_shards = int(self.mesh.shape[axis])
        cohort_size = self.participation.cohort_size
        if self._cohort_placement is None and self._cohort_placement_error is None:
            from repro.core.hierarchy import plan_cohort_placement

            spec = as_hierarchy(self.topology)
            try:
                self._cohort_placement = plan_cohort_placement(
                    spec, _cohort_quotas(spec, cohort_size), num_shards
                )
            except ValueError as e:
                self._cohort_placement_error = str(e)
        if self._cohort_placement_error is not None:
            return self._cohort_placement_error
        return sharded_cohort_incompatibility(
            self.hier_config, self.topology, cohort_size, num_shards,
            placement=self._cohort_placement,
        )

    def _cohort_reason(self, start_round: int) -> Optional[str]:
        """None if the run can go cohort-sampled end-to-end, else why not.
        There is no per-round fallback for sampled participation — the
        full-population state the per-round loop needs never exists — so
        every constraint is a hard error, not a silent downgrade.
        Failure/straggler models compose (the engine masks the sampled
        cohort's weight columns); a mesh composes through the sharded
        cohort lowering when ``sharded_cohort_incompatibility`` clears it."""
        from repro.core.hierfavg import cohort_incompatibility

        k2 = self.hier_config.kappa2_effective
        reason = cohort_incompatibility(
            self.hier_config, self.topology, self.participation.cohort_size
        )
        if reason is not None:
            return reason
        if self.cfg.engine == "per_round":
            return "engine='per_round' has no cohort lowering"
        if self._state_shardings is not None:
            return "an explicit state_shardings pytree pins the legacy per-round mesh path"
        if self.mesh is not None:
            if self.grad_accum > 1:
                return "grad_accum > 1 has no sharded block layout yet"
            reason = self._plan_cohort_placement()
            if reason is not None:
                return reason
        if start_round % k2:
            return f"start_round {start_round} is not a cloud boundary (kappa2_eff={k2})"
        if (self.cfg.num_rounds - start_round) % k2:
            return f"num_rounds {self.cfg.num_rounds} is not a whole number of cloud intervals"
        for name, every in (
            ("eval_every", self.cfg.eval_every),
            ("checkpoint_every", self.cfg.checkpoint_every),
        ):
            if every and every % k2 != 0:
                return f"{name}={every} is finer than a cloud interval (kappa2_eff={k2})"
        return None

    def _deadline_reason(self, start_round: int) -> Optional[str]:
        """None if the run can go through the semi-synchronous deadline
        engine, else why not. Like sampled participation there is no
        per-round fallback — a scheduler was configured, so silently running
        synchronous would change the experiment — every constraint is a hard
        error with a named reason."""
        from repro.core.hierfavg import deadline_incompatibility

        if self.participation is not None:
            return "sampled participation runs through the cohort engine"
        reason = deadline_incompatibility(self.hier_config, self.topology)
        if reason is not None:
            return reason
        if self.cfg.engine == "per_round":
            return "engine='per_round' has no deadline lowering"
        if self.cfg.engine == "megakernel":
            return "the deadline engine and the megakernel lowering do not compose"
        if self.mesh is not None:
            return (
                "the deadline engine is single-device (the gated cloud sync "
                "selects per-edge over the whole client axis); drop the mesh"
            )
        if self._state_shardings is not None:
            return "an explicit state_shardings pytree pins the legacy per-round mesh path"
        k2 = self.hier_config.kappa2_effective
        if start_round % k2:
            return f"start_round {start_round} is not a cloud boundary (kappa2_eff={k2})"
        if (self.cfg.num_rounds - start_round) % k2:
            return f"num_rounds {self.cfg.num_rounds} is not a whole number of cloud intervals"
        for name, every in (
            ("eval_every", self.cfg.eval_every),
            ("checkpoint_every", self.cfg.checkpoint_every),
        ):
            if every and every % k2 != 0:
                return f"{name}={every} is finer than a cloud interval (kappa2_eff={k2})"
        return None

    def _run_deadline(self, state: FedState, start_round: int) -> FedState:
        reason = self._deadline_reason(start_round)
        if reason is not None:
            raise ValueError(f"the deadline engine cannot run: {reason}")
        k2 = self.hier_config.kappa2_effective
        intervals = (self.cfg.num_rounds - start_round) // k2
        if intervals <= 0:
            return state
        if self._engine is None:
            from repro.fed.engine import DeadlineEngine

            self._engine = DeadlineEngine(self)
        state, _ = self._engine.run_intervals(
            state, start_round=start_round, num_intervals=intervals
        )
        return state

    def _run_cohort(self, state: FedState, start_round: int) -> FedState:
        reason = self._cohort_reason(start_round)
        if reason is not None:
            raise ValueError(f"sampled participation cannot run: {reason}")
        k2 = self.hier_config.kappa2_effective
        intervals = (self.cfg.num_rounds - start_round) // k2
        if intervals <= 0:
            return state
        if self._engine is None:
            from repro.fed.engine import CohortEngine

            self._engine = CohortEngine(self)
        state, _ = self._engine.run_intervals(
            state, start_round=start_round, num_intervals=intervals
        )
        return state

    def run(self, state: FedState, *, start_round: int = 0) -> FedState:
        mode = self.cfg.engine  # validated by RunnerConfig.__post_init__
        if self.participation is not None:
            return self._run_cohort(state, start_round)
        if self.deadline is not None:
            return self._run_deadline(state, start_round)
        k2 = self.hier_config.kappa2_effective
        if mode != "per_round":
            eligible = self._superround_eligible(start_round)
            full = (self.cfg.num_rounds - start_round) // k2 if eligible else 0
            if mode in ("superround", "megakernel") and full <= 0:
                mesh_note = (
                    f" (mesh: {self._mesh_reason})" if self._mesh_reason else ""
                )
                raise ValueError(
                    f"engine={mode!r} needs a cloud-aligned start_round, "
                    "eval_every/checkpoint_every multiples of "
                    f"kappa2_effective={k2}, a mesh-shardable schedule, and "
                    f"at least one whole cloud interval of rounds{mesh_note}"
                )
            if full > 0:
                if self._engine is None:
                    from repro.fed.engine import SuperRoundEngine

                    self._engine = SuperRoundEngine(self)
                state, stopped = self._engine.run_intervals(
                    state, start_round=start_round, num_intervals=full
                )
                if stopped:
                    return state
                start_round += full * k2
        # per-round path: the remainder (partial trailing interval), or
        # everything when the cadence needs sub-cloud-interval granularity
        k1 = self.hier_config.kappa1
        wire_per_step = self._wire_bytes_per_step(state)
        for r in range(start_round, self.cfg.num_rounds):
            batches = self.batcher.next_batches(k1)
            batches = jax.tree_util.tree_map(jnp.asarray, batches)
            mask = self._mask_for_round()
            mask_dev = None if mask is None else jnp.asarray(mask)
            n_alive = int(mask.sum()) if mask is not None else self.topology.num_clients
            state, metrics = self._round(state, batches, jnp.int32(r), mask_dev)
            step = int(state.step)

            acc = None
            if self.eval_fn is not None and self.cfg.eval_every and (r + 1) % self.cfg.eval_every == 0:
                acc = float(self.eval_fn(self.eval_model(state.params, mask_dev)))

            self._record_round(
                r, step, float(metrics["loss"]), float(metrics["grad_norm"]),
                n_alive, wire_per_step, accuracy=acc,
            )

            if self.checkpointer is not None and self.cfg.checkpoint_every and (
                r + 1
            ) % self.cfg.checkpoint_every == 0:
                meta = {"round": r + 1, "batcher": self.batcher.state_dict()}
                if self.failures is not None:
                    meta["failures"] = self.failures.state_dict()
                if self.stragglers is not None:
                    meta["stragglers"] = self.stragglers.state_dict()
                self.checkpointer.save(int(state.step), state, meta)

            if acc is not None and self.cfg.target_accuracy and acc >= self.cfg.target_accuracy:
                break
        return state

    # ------------------------------------------------------------------
    def records_to_dict(self) -> Dict[str, list]:
        """Column-major history, one key per ``RoundRecord`` field — derived
        from the dataclass so new record fields can't silently drop out."""
        return {
            f.name: [getattr(h, f.name) for h in self.history]
            for f in dataclasses.fields(RoundRecord)
        }
