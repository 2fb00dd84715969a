"""Zero-copy superround execution engine.

The per-round ``FederatedRunner`` loop pays, every edge interval: a Python
dispatch, a full un-donated copy of the stacked (N, ...) ``FedState``
(params + opt_state + anchor + EF residual ≈ 4 model copies per client), a
blocking host sync for ``step``/``loss``, and a synchronous batch upload.
The paper's protocol only *needs* the host at cloud boundaries — failure
masks, eval, checkpointing, and early stopping are all cloud-interval
decisions — so this engine drives one full cloud interval per dispatch and
removes every per-round host cost:

* **Donated state** — ``core.hierfavg.build_super_round`` is jitted with
  ``donate_argnums=(0,)``: XLA reuses the FedState's buffers for the
  output, so the multi-copy stacked state is updated in place instead of
  round-tripped through fresh HBM allocations each interval.
* **Cloud-interval scan fusion** — κ₂ edge intervals (κ₁ local steps +
  the due per-level aggregation each) run as one ``lax.scan`` with the
  level switch folded in: one dispatch and one executable per cloud
  interval instead of κ₂ of each.
* **Async metrics** — per-round loss / grad-norm / step accumulate on
  device inside the scan and come back stacked; the engine stores the
  device arrays and defers the host fetch to eval/checkpoint boundaries
  (or the end of the run), reconstructing the per-round ``RoundRecord``
  history host-side. No per-round blocking transfer.
* **Device-side batch prefetch** — a ``data.pipeline.SuperBatchPrefetcher``
  worker assembles and ``jax.device_put``s interval r+1's
  (κ₂, κ₁, N, b, ...) block while interval r computes.

**Mesh execution** — when the runner carries a device mesh, the engine
swaps in ``core.hierfavg.build_sharded_super_round``: the stacked client
axis is permuted into the edge-aligned ``core.hierarchy.ShardPlacement``
order (each edge subtree wholly on one shard, phantom-padded when the
packing is ragged) and ``shard_map``-sharded over the mesh's ``"clients"``
axis. Edge syncs become device-local segment reductions; each cloud
boundary issues exactly one grouped psum; the prefetcher ``device_put``s
batch blocks with the matching ``NamedSharding`` so every device receives
only its shard's slice; metrics stay per-client on device and are reduced
host-side at flush time. The engine owns the layout conversion: callers
hand in and get back canonical client order.

Protocol state is bit-exact versus the per-round driver (tests enforce
it; see docs/performance.md for the two 1-ULP XLA:CPU codegen caveats and
the cloud-psum reassociation tolerance of the mesh path); the runner
transparently falls back to the per-round path when ``eval_every``/
``checkpoint_every`` demand sub-cloud-interval granularity.
"""
from __future__ import annotations

from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import aggregation
from repro.core.hierarchy import as_hierarchy, plan_shard_placement
from repro.core.hierfavg import (
    FedState,
    build_cohort_super_round,
    build_megakernel_super_round,
    build_sharded_super_round,
    build_super_round,
    map_stacked_fed_state,
)
from repro.data.pipeline import CohortPrefetcher, SuperBatchPrefetcher
from repro.fed.client_store import replace_sticky_rows, sticky_rows

PyTree = Any


def _map_stacked(state: FedState, fn, lead: int) -> FedState:
    """Apply ``fn`` to every state leaf carrying the stacked client dim of
    size ``lead`` (params/opt/anchor/residual rows), pass everything else
    through — the permute/pad twin of ``fed_state_partition_specs``."""
    return map_stacked_fed_state(state, fn, lambda x: x, lead)


def _relayout(state: FedState, index: np.ndarray, lead: int, shardings) -> FedState:
    """Gather the ``lead`` stacked client rows of ``state`` by ``index``
    straight into ``shardings`` in one compiled program, so a sharded state
    never passes whole through one device; an identity index (no permutation,
    no padding) skips the gather."""
    if len(index) == lead and np.array_equal(index, np.arange(lead)):
        return jax.device_put(state, shardings)
    idx = jnp.asarray(index)
    gather = jax.jit(
        lambda s: _map_stacked(s, lambda x: jnp.take(x, idx, axis=0), lead),
        out_shardings=shardings,
    )
    return gather(state)


class SuperRoundEngine:
    """Drives a ``FederatedRunner``'s training loop one cloud interval per
    donated dispatch — client-sharded over the runner's mesh when one is
    configured. Constructed (and cached) by the runner; appends the same
    per-round ``RoundRecord`` history the per-round path would."""

    def __init__(self, runner, *, donate: bool = True, prefetch: bool = True):
        self.runner = runner
        hier = runner.hier_config
        self.k1 = hier.kappa1
        self.k2 = hier.kappa2_effective
        self.prefetch = prefetch
        self.mesh = runner.mesh
        self.placement = None
        # engine="megakernel" is an opt-in fast path: whole cloud intervals
        # through the client-blocked lowering when the schedule is block-
        # separable, otherwise the scan-fused superround with a named reason
        # (queryable here and on runner._megakernel_reason — the same
        # report-don't-raise idiom as the mesh's sharding_incompatibility)
        self.uses_megakernel = False
        self.megakernel_reason: Optional[str] = None
        if getattr(runner.cfg, "engine", "") == "megakernel":
            self.megakernel_reason = runner._check_megakernel()
        if self.mesh is not None:
            from repro.dist import sharding as dist_sharding

            self.axis = dist_sharding.client_axis_of(self.mesh)
            num_shards = int(self.mesh.shape[self.axis])
            # the runner plans (and caches) the placement during eligibility;
            # replan only for directly constructed engines
            self.placement = getattr(runner, "_placement", None)
            if self.placement is None or self.placement.num_shards != num_shards:
                self.placement = plan_shard_placement(as_hierarchy(runner.topology), num_shards)
            fn = build_sharded_super_round(
                runner.loss_fn,
                runner.optimizer,
                runner.topology,
                hier,
                runner.weights,
                mesh=self.mesh,
                axis=self.axis,
                placement=self.placement,
                grad_accum=runner.grad_accum,
            )
            self._gather = self.placement.gather_index()
            self._positions = self.placement.positions()
            self._valid = self.placement.valid()
            self._block_sharding = dist_sharding.batch_block_sharding(self.mesh, self.axis)
            self._mask_sharding = dist_sharding.mask_stack_sharding(self.mesh, self.axis)
        elif getattr(runner.cfg, "engine", "") == "megakernel" and self.megakernel_reason is None:
            fn = build_megakernel_super_round(
                runner.loss_fn,
                runner.optimizer,
                runner.topology,
                hier,
                runner.weights,
                grad_accum=runner.grad_accum,
            )
            self.uses_megakernel = True
        else:
            fn = build_super_round(
                runner.loss_fn,
                runner.optimizer,
                runner.topology,
                hier,
                runner.weights,
                grad_accum=runner.grad_accum,
            )
        self._super = jax.jit(fn, donate_argnums=(0,) if donate else ())
        # [(round_base, [alive...], device metrics)] — single-device metrics
        # are {"loss","grad_norm","step"} (κ₂,) scalars; mesh metrics are
        # per-client {"loss","gsq"} (κ₂, κ₁, padded_N) + "step" (κ₂,)
        self._pending: List[Tuple[int, List[int], dict]] = []

    # -- placement-order layout conversion (mesh path) ----------------------
    def _shard_state(self, state: FedState) -> FedState:
        """Canonical (N, ...) state -> placement-ordered padded state laid
        out with the engine's NamedShardings (one upload per device)."""
        from repro.dist.sharding import fed_state_shardings

        n = self.runner.topology.num_clients
        shardings = fed_state_shardings(self.mesh, self.axis, state, n)
        return _relayout(state, self._gather, n, shardings)

    def _unshard_state(self, state: FedState) -> FedState:
        """Placement-ordered padded state -> canonical client order, still
        spread over the mesh (phantom rows dropped by the inverse gather)."""
        from repro.dist.sharding import canonical_state_shardings

        padded = self.placement.padded_clients
        shardings = canonical_state_shardings(
            self.mesh, self.axis, state, padded, self.runner.topology.num_clients
        )
        return _relayout(state, self._positions, padded, shardings)

    def _canonical_params(self, state: FedState) -> PyTree:
        if self.mesh is None:
            return state.params
        pos = jnp.asarray(self._positions)
        return jax.tree_util.tree_map(lambda x: jnp.take(x, pos, axis=0), state.params)

    def _mask_to_device(self, stack: np.ndarray):
        if self.mesh is None:
            return jnp.asarray(stack)
        padded = stack[:, self._gather] * self._valid[None, :].astype(stack.dtype)
        return jax.device_put(jnp.asarray(padded), self._mask_sharding)

    def _block_transform(self):
        if self.mesh is None:
            return None
        gather = self._gather
        return lambda block: jax.tree_util.tree_map(lambda x: x[:, :, gather], block)

    # ------------------------------------------------------------------
    def _masks_for_interval(self) -> Tuple[Optional[np.ndarray], List[int], Optional[np.ndarray]]:
        """κ₂ host-side survival masks, stacked to a (κ₂, N) numpy block for
        the scan (canonical client order — the engine permutes for the mesh
        at upload time).

        Returns (mask_stack | None, per-round alive counts, last round's
        mask for the boundary eval). Calls the failure detector once per
        round — the same host sequence as the per-round driver.
        """
        r = self.runner
        n = r.topology.num_clients
        masks = [r._mask_for_round() for _ in range(self.k2)]
        if all(m is None for m in masks):
            return None, [n] * self.k2, None
        stack = np.stack(
            [m if m is not None else np.ones(n, np.float32) for m in masks]
        )
        alive = [int(row.sum()) for row in stack]
        return stack, alive, stack[-1]

    def _flush(self, wire_per_step: float) -> None:
        """Materialize pending device metrics into RoundRecords (one
        ``device_get`` per outstanding cloud interval) through the runner's
        shared record-assembly helper — both drivers' histories are built
        by the same code. Mesh metrics arrive per-client (no collective was
        spent on diagnostics): the loss mean and grad-norm reduce here,
        over real clients only (phantom pad columns dropped)."""
        r = self.runner
        for round_base, alive, metrics in self._pending:
            vals = jax.device_get(metrics)
            for j in range(self.k2):
                step = int(vals["step"][j])
                if self.mesh is None:
                    loss = float(vals["loss"][j])
                    gnorm = float(vals["grad_norm"][j])
                else:
                    loss = float(np.mean(vals["loss"][j][:, self._valid]))
                    gsq = vals["gsq"][j][:, self._valid]  # (κ₁, N_real)
                    gnorm = float(np.mean(np.sqrt(np.sum(gsq, axis=1))))
                r._record_round(
                    round_base + j, step, loss, gnorm, alive[j], wire_per_step,
                    wall_clock_s=self._wall_clock_for(round_base + j),
                )
        self._pending.clear()

    # -- engine-variant hooks (overridden by DeadlineEngine) ----------------
    def _dispatch_interval(
        self, state: FedState, block: PyTree, mask_stack: Optional[np.ndarray], round_base: int
    ) -> Tuple[FedState, dict]:
        """Run one cloud interval on device. The stock engine is purely
        synchronous: upload the mask stack (mesh-permuted when sharded) and
        dispatch the fused superround executable."""
        mask_dev = None if mask_stack is None else self._mask_to_device(mask_stack)
        return self._super(state, block, mask_dev)

    def _eval_mask(self, last_mask: Optional[np.ndarray]) -> Optional[np.ndarray]:
        """Mask defining the published cloud model for the boundary eval."""
        return last_mask

    def _wall_clock_for(self, round_index: int) -> float:
        """Simulated wall-clock seconds at a round's close (0.0 for the
        synchronous engine, which has no event clock)."""
        return 0.0

    def _checkpoint_meta(self, end_round: int, batcher_snapshot: dict) -> dict:
        r = self.runner
        meta = {"round": end_round, "batcher": batcher_snapshot}
        if r.failures is not None:
            meta["failures"] = r.failures.state_dict()
        if r.stragglers is not None:
            meta["stragglers"] = r.stragglers.state_dict()
        return meta

    # ------------------------------------------------------------------
    def run_intervals(
        self, state: FedState, *, start_round: int, num_intervals: int
    ) -> Tuple[FedState, bool]:
        """Run ``num_intervals`` cloud intervals from a cloud-aligned
        ``start_round``. Takes and returns canonical client order (the mesh
        path converts to placement order internally). Returns
        (state, stopped_early)."""
        r = self.runner
        if start_round % self.k2:
            raise ValueError(
                f"superround engine must start at a cloud boundary: "
                f"start_round={start_round} is not a multiple of {self.k2}"
            )
        wire_per_step = r._wire_bytes_per_step(state)
        if self.mesh is not None:
            state = self._shard_state(state)
        stopped = False
        # no failure model -> the all-alive mask triple is identical every
        # interval: build it once instead of κ₂ detector calls per interval.
        # An overridden/monkeypatched _mask_for_round is a live seam (the
        # per-round driver polls it unconditionally), so only the stock
        # implementation is hoisted.
        from repro.fed.runner import FederatedRunner

        no_failures = (
            r.failures is None
            and r.stragglers is None
            and getattr(r._mask_for_round, "__func__", None)
            is FederatedRunner._mask_for_round
        )
        static_masks = (None, [r.topology.num_clients] * self.k2, None)
        prefetcher = SuperBatchPrefetcher(
            r.batcher,
            rounds_per_block=self.k2,
            steps_per_round=self.k1,
            num_blocks=num_intervals,
            device=self._block_sharding if self.mesh is not None else None,
            use_thread=self.prefetch,
            transform=self._block_transform(),
        )
        try:
            for q in range(num_intervals):
                round_base = start_round + q * self.k2
                block, batcher_snapshot = prefetcher.get()
                mask_stack, alive, last_mask = (
                    static_masks if no_failures else self._masks_for_interval()
                )
                state, metrics = self._dispatch_interval(state, block, mask_stack, round_base)
                self._pending.append((round_base, alive, metrics))

                end_round = round_base + self.k2  # rounds completed so far
                do_eval = (
                    r.eval_fn is not None
                    and r.cfg.eval_every
                    and end_round % r.cfg.eval_every == 0
                )
                do_ckpt = (
                    r.checkpointer is not None
                    and r.cfg.checkpoint_every
                    and end_round % r.cfg.checkpoint_every == 0
                )
                if do_eval or do_ckpt:
                    self._flush(wire_per_step)
                acc = None
                if do_eval:
                    mask_eval = self._eval_mask(last_mask)
                    mask_last = None if mask_eval is None else jnp.asarray(mask_eval)
                    cloud0 = r.eval_model(self._canonical_params(state), mask_last)
                    acc = float(r.eval_fn(cloud0))
                    r.history[-1].accuracy = acc
                if do_ckpt:
                    # the live batcher has prefetched ahead; the snapshot is
                    # the cursor state as of THIS block's cloud boundary
                    meta = self._checkpoint_meta(end_round, batcher_snapshot)
                    save_state = state if self.mesh is None else self._unshard_state(state)
                    r.checkpointer.save(r.history[-1].step, save_state, meta)
                if acc is not None and r.cfg.target_accuracy and acc >= r.cfg.target_accuracy:
                    stopped = True
                    break
            self._flush(wire_per_step)
        finally:
            prefetcher.stop()
        if self.mesh is not None:
            state = self._unshard_state(state)
        return state, stopped


class DeadlineEngine(SuperRoundEngine):
    """Semi-synchronous cloud rounds: the superround engine driven by a
    ``fed.deadline.SemiSyncScheduler`` event queue.

    Per cloud interval the scheduler advances every edge's upload clock and
    closes the round at the configured deadline/quorum, returning a
    ``RoundPlan``. A *trivial* plan (every edge folded on time at weight 1
    — always the case under uniform cadences with the full-quorum barrier)
    dispatches the stock ``build_super_round`` executable, so the parity
    contract with the synchronous engine is bit-exact *by construction*:
    same jitted function, same inputs. Non-trivial plans dispatch the gated
    ``build_deadline_super_round`` executable: folded edges contribute at
    staleness-decayed weight and receive the broadcast; late edges keep
    their edge-synced model and carry the upload into the next round.

    The ``dead`` channel of the runner's mask composition (outages — see
    ``fed.failures.compose_masks``) feeds the scheduler so a dead edge is
    skip-and-reweighted instead of force-waited: only *late* edges, whose
    upload is actually coming, can hold the cloud past its deadline.

    Wall-clock accounting: each round record gets ``wall_clock_s`` from the
    event clock (rounds inside an interval interpolate linearly to the
    interval's close — the cloud only observes time at its own boundaries).
    Boundary evals aggregate over folded edges only: that is the model the
    cloud actually published. Checkpoints add the scheduler's full event
    state (clock, per-edge finish times, staleness, retry credits, RNG)
    under ``meta["deadline"]`` so interrupted semi-synchronous runs resume
    on the identical event sequence.

    Single-device only for now: the gated top sync wants the whole client
    axis for its per-edge select (the runner's eligibility check reports
    this, mirroring the mesh/cohort predicates).
    """

    def __init__(self, runner, *, donate: bool = True, prefetch: bool = True):
        if runner.mesh is not None:
            raise ValueError(
                "the deadline engine is single-device (the gated cloud sync "
                "selects per-edge over the whole client axis); drop the mesh"
            )
        if getattr(runner.cfg, "engine", "") == "megakernel":
            raise ValueError("the deadline engine and the megakernel lowering do not compose")
        super().__init__(runner, donate=donate, prefetch=prefetch)
        from repro.core.hierfavg import build_deadline_super_round

        self.scheduler = runner.deadline
        if self.scheduler is None:
            raise ValueError("DeadlineEngine needs runner.deadline (a SemiSyncScheduler)")
        spec = as_hierarchy(runner.topology)
        # the unit that talks to the cloud: the top-minus-one tier (edges on
        # two-level trees, regions on deeper ones; the whole client set when
        # clients report straight to the cloud)
        if spec.depth >= 2:
            self._gate_segments = np.asarray(spec.segments(spec.depth - 1))
            num_units = spec.num_nodes(spec.depth - 1)
        else:
            self._gate_segments = np.zeros(spec.num_clients, np.int64)
            num_units = 1
        if self.scheduler.num_edges != num_units:
            raise ValueError(
                f"scheduler models {self.scheduler.num_edges} edge(s) but the "
                f"tree has {num_units} cloud-facing unit(s)"
            )
        fn = build_deadline_super_round(
            runner.loss_fn,
            runner.optimizer,
            runner.topology,
            runner.hier_config,
            runner.weights,
            grad_accum=runner.grad_accum,
        )
        self._gated = jax.jit(fn, donate_argnums=(0,) if donate else ())
        self._wall: dict = {}  # round index -> event-clock seconds at close
        self._last_plan = None

    # ------------------------------------------------------------------
    def _dead_units(self, mask_stack: Optional[np.ndarray]) -> Optional[np.ndarray]:
        """(E,) bool: units with zero surviving clients at the interval's
        cloud boundary, from the outage channel when the runner tracked one
        (late stragglers must NOT count — their upload is still coming)."""
        r = self.runner
        parts = getattr(r, "_last_mask_parts", None)
        dead_clients = None
        if parts is not None and parts.dead is not None:
            dead_clients = parts.dead  # 1 = outage, straggler channel excluded
        elif mask_stack is not None and r.stragglers is None:
            dead_clients = (mask_stack[-1] == 0).astype(np.float32)
        if dead_clients is None:
            return None
        e = self.scheduler.num_edges
        alive_per_unit = np.zeros(e, np.float64)
        np.add.at(alive_per_unit, self._gate_segments, 1.0 - dead_clients)
        return alive_per_unit == 0

    def _dispatch_interval(self, state, block, mask_stack, round_base):
        plan = self.scheduler.next_round(dead=self._dead_units(mask_stack))
        self._last_plan = plan
        start, close = plan.start, plan.close
        for j in range(self.k2):
            # the cloud observes time at its boundaries; interior edge
            # intervals interpolate linearly for plotting/bench purposes
            self._wall[round_base + j] = start + (close - start) * (j + 1) / self.k2
        if plan.is_trivial:
            # stock executable, stock inputs: bit-exact vs SuperRoundEngine
            return super()._dispatch_interval(state, block, mask_stack, round_base)
        gate = jnp.asarray(plan.client_gate(self._gate_segments))
        mask_dev = None if mask_stack is None else jnp.asarray(mask_stack)
        return self._gated(state, block, gate, mask_dev)

    def _eval_mask(self, last_mask):
        plan = self._last_plan
        if plan is None or plan.is_trivial:
            return last_mask
        folded = plan.folded[self._gate_segments].astype(np.float32)
        return folded if last_mask is None else last_mask * folded

    def _wall_clock_for(self, round_index: int) -> float:
        return float(self._wall.get(round_index, 0.0))

    def _checkpoint_meta(self, end_round: int, batcher_snapshot: dict) -> dict:
        meta = super()._checkpoint_meta(end_round, batcher_snapshot)
        meta["deadline"] = self.scheduler.state_dict()
        return meta


class CohortEngine:
    """Superround engine for sampled participation: only the cohort is
    device-resident.

    Per cloud interval the loop is: take the prefetched ``(ids, cohort,
    block)`` triple (cohort arrays + batch block already uploading in the
    worker — see ``CohortPrefetcher``), swap the cohort's sticky rows
    (stacked opt_state leaves + EF residual) in from the host
    ``ClientStateStore``, dispatch the donated cohort superround, and write
    the rows back by original client id. Model params and anchors never
    touch the store: control returns only at cloud boundaries, where every
    stacked row equals the fresh broadcast.

    Device footprint is ∝ cohort size C; the (N, …) population exists only
    as host arrays (store + sampler + batcher cursors). With the identity
    cohort (C == N) the trajectory reproduces ``SuperRoundEngine``'s —
    that's the parity anchor the tests pin.

    **Mesh execution** — with a runner mesh the engine swaps in
    ``core.hierfavg.build_sharded_cohort_super_round``: stratified quotas
    make the cohort's slot→edge layout a pure function of (topology,
    cohort_size), so the slot ``ShardPlacement`` is planned once and every
    sampled cohort reuses one executable and one layout. The prefetcher
    permutes/pads blocks into slot order and ``device_put``s per-device
    slices; store rows ride ``gather_placed``/``scatter_placed``; per-shard
    memory is ∝ C / num_shards. Survival masks compose with sampling on
    both paths by masking the cohort's weight columns.

    History/eval/checkpoint cadences are cloud-interval-granular like the
    superround engine; the per-round fallback does not exist here (the
    runner validates cadences up front). Checkpoints save the composite
    ``{"fed": state, "store": store.state()}`` pytree plus the prefetcher's
    paired batcher+sampler snapshots, so a resumed run replays the exact
    same cohorts and batches.
    """

    def __init__(self, runner, *, donate: bool = True, prefetch: bool = True):
        self.runner = runner
        hier = runner.hier_config
        self.k1 = hier.kappa1
        self.k2 = hier.kappa2_effective
        self.prefetch = prefetch
        self.cohort_size = int(hier.participation.cohort_size)
        self.spec = as_hierarchy(runner.topology)
        self.mesh = runner.mesh
        self.placement = None
        self._weights_np = np.asarray(runner.weights, np.float32)
        if self.mesh is not None:
            from repro.core.hierfavg import (
                _cohort_quotas,
                build_sharded_cohort_super_round,
            )
            from repro.dist import sharding as dist_sharding

            self.axis = dist_sharding.client_axis_of(self.mesh)
            num_shards = int(self.mesh.shape[self.axis])
            # the runner plans (and caches) the cohort slot placement during
            # eligibility; replan only for directly constructed engines
            self.placement = getattr(runner, "_cohort_placement", None)
            if self.placement is None or self.placement.num_shards != num_shards:
                from repro.core.hierarchy import plan_cohort_placement

                self.placement = plan_cohort_placement(
                    self.spec, _cohort_quotas(self.spec, self.cohort_size), num_shards
                )
            fn = build_sharded_cohort_super_round(
                runner.loss_fn,
                runner.optimizer,
                runner.topology,
                hier,
                cohort_size=self.cohort_size,
                mesh=self.mesh,
                axis=self.axis,
                placement=self.placement,
                grad_accum=runner.grad_accum,
            )
            self._gather = self.placement.gather_index()
            self._positions = self.placement.positions()
            self._valid = self.placement.valid()
            self._block_sharding = dist_sharding.batch_block_sharding(self.mesh, self.axis)
            self._mask_sharding = dist_sharding.mask_stack_sharding(self.mesh, self.axis)
            from jax.sharding import NamedSharding, PartitionSpec

            self._row_sharding = NamedSharding(self.mesh, PartitionSpec(self.axis))
        else:
            fn = build_cohort_super_round(
                runner.loss_fn,
                runner.optimizer,
                runner.topology,
                hier,
                cohort_size=self.cohort_size,
                grad_accum=runner.grad_accum,
            )
        self._super = jax.jit(fn, donate_argnums=(0,) if donate else ())
        # [(round_base, [alive...], device metrics)] — single-device metrics
        # are {"loss","grad_norm","step"} (κ₂,) scalars; mesh metrics are
        # per-client {"loss","gsq"} (κ₂, κ₁, padded_C) + "step" (κ₂,)
        self._pending: List[Tuple[int, List[int], dict]] = []

    # -- slot-placement layout conversion (mesh path) -----------------------
    @property
    def _state_rows(self) -> int:
        """Leading stacked dim of the live state: C single-device,
        padded_C on the mesh path."""
        return self.cohort_size if self.mesh is None else self.placement.padded_clients

    def _shard_state(self, state: FedState) -> FedState:
        """Canonical (C, ...) cohort state -> slot-placement-ordered padded
        state laid out with the engine's NamedShardings."""
        from repro.dist.sharding import fed_state_shardings

        shardings = fed_state_shardings(self.mesh, self.axis, state, self.cohort_size)
        return _relayout(state, self._gather, self.cohort_size, shardings)

    def _unshard_state(self, state: FedState) -> FedState:
        """Slot-placement-ordered padded state -> canonical cohort order,
        still spread over the mesh (phantom rows dropped)."""
        from repro.dist.sharding import canonical_state_shardings

        padded = self.placement.padded_clients
        shardings = canonical_state_shardings(self.mesh, self.axis, state, padded, self.cohort_size)
        return _relayout(state, self._positions, padded, shardings)

    def _canonical_params(self, state: FedState) -> PyTree:
        if self.mesh is None:
            return state.params
        pos = jnp.asarray(self._positions)
        return jax.tree_util.tree_map(lambda x: jnp.take(x, pos, axis=0), state.params)

    # ------------------------------------------------------------------
    def _segments_table(self) -> np.ndarray:
        """(depth-1, N) host table of per-client sub-top ancestor ids; the
        prefetcher columns it per cohort."""
        depth = self.spec.depth
        if depth == 1:
            return np.zeros((0, self.spec.num_clients), np.int32)
        return np.stack([np.asarray(self.spec.segments(l), np.int32) for l in range(1, depth)])

    def _masks_for_interval(self, ids: np.ndarray):
        """κ₂ survival draws over the population, columned at the sampled
        ids: participation and failure compose by masking the cohort's
        weight columns. Returns (device mask stack | None, per-round alive
        counts, last round's cohort columns for the boundary eval)."""
        r = self.runner
        masks = [r._mask_for_round() for _ in range(self.k2)]
        if all(m is None for m in masks):
            return None, [self.cohort_size] * self.k2, None
        n = r.topology.num_clients
        stack = np.stack([m if m is not None else np.ones(n, np.float32) for m in masks])
        cols = stack[:, ids]  # (κ₂, C) — the sampled cohort's survival bits
        alive = [int(row.sum()) for row in cols]
        if self.mesh is None:
            return jnp.asarray(cols), alive, cols[-1]
        padded = cols[:, self._gather] * self._valid[None, :].astype(cols.dtype)
        return jax.device_put(jnp.asarray(padded), self._mask_sharding), alive, cols[-1]

    def _load_cohort(self, state: FedState, ids: np.ndarray) -> FedState:
        """Swap the sampled clients' sticky rows in from the host store."""
        store = self.runner.client_store
        if store.is_empty:
            return state
        if self.mesh is None:
            rows = jax.device_put(store.gather(ids))
        else:
            rows = jax.device_put(
                store.gather_placed(ids, self.placement), self._row_sharding
            )
        return replace_sticky_rows(state, rows, self._state_rows)

    def _writeback(self, state: FedState, ids: np.ndarray) -> None:
        """Persist the cohort's post-interval sticky rows by original id.
        The ``device_get`` doubles as this interval's sync point, so the
        store is consistent with ``state`` at every checkpoint boundary."""
        store = self.runner.client_store
        if store.is_empty:
            return
        rows = jax.device_get(sticky_rows(state, self._state_rows))
        if self.mesh is None:
            store.scatter(ids, rows)
        else:
            store.scatter_placed(ids, self.placement, rows)

    def _flush(self, wire_per_step: float) -> None:
        r = self.runner
        for round_base, alive, metrics in self._pending:
            vals = jax.device_get(metrics)
            for j in range(self.k2):
                if self.mesh is None:
                    loss = float(vals["loss"][j])
                    gnorm = float(vals["grad_norm"][j])
                else:
                    loss = float(np.mean(vals["loss"][j][:, self._valid]))
                    gsq = vals["gsq"][j][:, self._valid]  # (κ₁, C)
                    gnorm = float(np.mean(np.sqrt(np.sum(gsq, axis=1))))
                r._record_round(
                    round_base + j,
                    int(vals["step"][j]),
                    loss,
                    gnorm,
                    alive[j],
                    wire_per_step,
                )
        self._pending.clear()

    # ------------------------------------------------------------------
    def run_intervals(
        self, state: FedState, *, start_round: int, num_intervals: int
    ) -> Tuple[FedState, bool]:
        """Run ``num_intervals`` cloud intervals from a cloud-aligned
        ``start_round``. Returns (state, stopped_early)."""
        r = self.runner
        if start_round % self.k2:
            raise ValueError(
                f"cohort engine must start at a cloud boundary: "
                f"start_round={start_round} is not a multiple of {self.k2}"
            )
        r._ensure_client_store(state)
        wire_per_step = r._wire_bytes_per_step(state)
        if self.mesh is not None:
            state = self._shard_state(state)
        stopped = False
        # no failure model -> skip the κ₂ detector calls per interval; an
        # overridden/monkeypatched _mask_for_round is a live seam, so only
        # the stock implementation is hoisted (same idiom as the superround
        # engine above)
        from repro.fed.runner import FederatedRunner

        no_failures = (
            r.failures is None
            and r.stragglers is None
            and getattr(r._mask_for_round, "__func__", None)
            is FederatedRunner._mask_for_round
        )
        static_masks = (None, [self.cohort_size] * self.k2, None)
        prefetcher = CohortPrefetcher(
            r.batcher,
            r._cohort_sampler(),
            segments=self._segments_table(),
            weights=self._weights_np,
            rounds_per_block=self.k2,
            steps_per_round=self.k1,
            num_blocks=num_intervals,
            device=self._block_sharding if self.mesh is not None else None,
            use_thread=self.prefetch,
            placement=self.placement,
            weights_device=self._row_sharding if self.mesh is not None else None,
        )
        try:
            for q in range(num_intervals):
                round_base = start_round + q * self.k2
                (ids, cohort, block), snapshot = prefetcher.get()
                mask_dev, alive, last_mask = (
                    static_masks if no_failures else self._masks_for_interval(ids)
                )
                state = self._load_cohort(state, ids)
                if self.mesh is None:
                    state, metrics = self._super(state, block, cohort, mask_dev)
                else:
                    state, metrics = self._super(state, block, cohort["weights"], mask_dev)
                self._writeback(state, ids)
                self._pending.append((round_base, alive, metrics))

                end_round = round_base + self.k2
                do_eval = (
                    r.eval_fn is not None
                    and r.cfg.eval_every
                    and end_round % r.cfg.eval_every == 0
                )
                do_ckpt = (
                    r.checkpointer is not None
                    and r.cfg.checkpoint_every
                    and end_round % r.cfg.checkpoint_every == 0
                )
                if do_eval or do_ckpt:
                    self._flush(wire_per_step)
                acc = None
                if do_eval:
                    # cohort-weighted cloud model; with C == N this is
                    # bit-identical to the runner's full-population eval
                    mask_last = None if last_mask is None else jnp.asarray(last_mask)
                    cloud0 = aggregation.cloud_model(
                        self._canonical_params(state),
                        jnp.asarray(self._weights_np[ids]),
                        mask_last,
                    )
                    acc = float(r.eval_fn(cloud0))
                    r.history[-1].accuracy = acc
                if do_ckpt:
                    meta = {
                        "round": end_round,
                        "batcher": snapshot["batcher"],
                        "sampler": snapshot["sampler"],
                    }
                    if r.failures is not None:
                        # mask draws for this interval already happened, so
                        # the simulator state resumes at exactly end_round
                        meta["failures"] = r.failures.state_dict()
                    if r.stragglers is not None:
                        meta["stragglers"] = r.stragglers.state_dict()
                    fed = state if self.mesh is None else self._unshard_state(state)
                    save_state = {"fed": fed, "store": r.client_store.state()}
                    r.checkpointer.save(r.history[-1].step, save_state, meta)
                if acc is not None and r.cfg.target_accuracy and acc >= r.cfg.target_accuracy:
                    stopped = True
                    break
            self._flush(wire_per_step)
        finally:
            prefetcher.stop()
        if self.mesh is not None:
            state = self._unshard_state(state)
        return state, stopped
