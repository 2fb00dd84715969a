"""Sharded cohort superrounds: placement-stable packing laws, parity of the
composed lowering against both the single-device cohort engine and the
full-population sharded superround, and the mesh-composed cohort runner.

1-shard cases run everywhere (the full shard_map path over a 1-device
mesh); >=4-shard cases skip unless XLA_FLAGS=--xla_force_host_platform_device_count=4.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import FedTopology, HierFAVGConfig, init_state
from repro.core.hierarchy import (
    HierarchySpec,
    as_hierarchy,
    cohort_hierarchy,
    parse_fanouts,
    plan_cohort_placement,
    plan_shard_placement,
)
from repro.core.hierfavg import (
    _cohort_quotas,
    build_cohort_super_round,
    build_sharded_cohort_super_round,
    build_sharded_super_round,
    build_super_round,
    init_cohort_state,
    map_stacked_fed_state,
    sharded_cohort_incompatibility,
)
from repro.dist.sharding import (
    batch_block_sharding,
    client_mesh,
    fed_state_shardings,
    mask_stack_sharding,
)
from repro.fed import ParticipationSpec, TransportSpec
from repro.fed.participation import (
    StratifiedSampler,
    stratified_quotas,
    stratified_slot_edges,
)
from repro.optim import momentum, sgd
from repro.testing import given, settings, st

needs4 = pytest.mark.skipif(
    jax.device_count() < 4,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=4",
)
DIM = 3


# ---------------------------------------------------------------------------
# placement stability laws (the contract the sharded lowering rests on)
# ---------------------------------------------------------------------------

def _ragged_spec(sizes):
    """A 2-level ragged tree with the given per-edge client counts."""
    e = len(sizes)
    parents0 = tuple(int(x) for x in np.repeat(np.arange(e), sizes))
    return HierarchySpec(parents=(parents0, (0,) * e))


@given(
    sizes=st.lists(st.integers(1, 8), min_size=2, max_size=6),
    extra=st.integers(0, 10),
    shards=st.integers(1, 4),
)
@settings(max_examples=25)
def test_placement_stable_across_intervals_and_resume(sizes, extra, shards):
    """Stratified quotas, the slot->edge layout, and the planned cohort
    ShardPlacement are pure functions of (topology, mesh, cohort_size):
    identical across sampled intervals and across a sampler state_dict
    round-trip — and every shard's quota sum equals its valid slot count."""
    spec = _ragged_spec(sizes)
    n = spec.num_clients
    sizes = np.asarray(sizes, np.int64)
    c = int(min(n, len(sizes) + extra))
    shards = int(min(shards, len(sizes)))

    quotas = stratified_quotas(sizes, c)
    slot_edges = stratified_slot_edges(sizes, c)
    assert int(quotas.sum()) == c
    np.testing.assert_array_equal(
        slot_edges, np.repeat(np.arange(len(sizes)), quotas)
    )

    sampler = StratifiedSampler(n, c, spec.segments(1), seed=7)
    np.testing.assert_array_equal(sampler.quotas, quotas)
    seg1 = np.asarray(spec.segments(1))
    for _ in range(3):
        ids = sampler.sample()
        # every sorted stratified cohort fills the same slot->edge layout
        np.testing.assert_array_equal(seg1[ids], slot_edges)

    # resume: a state_dict round-trip replays the identical cohort stream
    snap = sampler.state_dict()
    twin = StratifiedSampler(n, c, spec.segments(1), seed=0)
    twin.load_state_dict(snap)
    np.testing.assert_array_equal(sampler.sample(), twin.sample())

    # the plan is deterministic: replanning yields the identical placement
    p1 = plan_cohort_placement(spec, quotas, shards)
    p2 = plan_cohort_placement(spec, quotas, shards)
    np.testing.assert_array_equal(p1.perm, p2.perm)
    assert p1.spec == cohort_hierarchy(spec, quotas)

    # per-shard slot accounting: edges never straddle shards, and each
    # shard's valid slot count is exactly the sum of its edges' quotas
    rows = np.asarray(p1.perm).reshape(shards, p1.capacity)
    seen_edges = {}
    for s in range(shards):
        slots = rows[s][rows[s] >= 0]
        edges_here = np.unique(slot_edges[slots])
        for e in edges_here:
            assert e not in seen_edges, "edge straddles shards"
            seen_edges[int(e)] = s
        assert slots.shape[0] == int(quotas[edges_here].sum())
    assert len(seen_edges) == len(sizes)


def test_stratified_rejects_cohort_smaller_than_edges():
    """The floor-1-per-edge quota needs cohort_size >= num_edges; the error
    names both numbers, at the sampler and at cohort eligibility."""
    with pytest.raises(ValueError, match=r"2 < 3"):
        stratified_quotas(np.asarray([4, 4, 4]), 2)
    from repro.core.hierfavg import cohort_incompatibility

    cfg = HierFAVGConfig(
        kappa1=2, kappa2=2,
        participation=ParticipationSpec(cohort_size=2, sampler="stratified"),
    )
    reason = cohort_incompatibility(cfg, parse_fanouts("4,4,4/3"), 2)
    assert reason is not None and "2 < 3" in reason


def test_sharded_cohort_incompatibility_reasons():
    spec = parse_fanouts("5,4,3/3")
    good = HierFAVGConfig(kappa1=2, kappa2=2)
    assert sharded_cohort_incompatibility(good, spec, 8, 2) is None
    # placement-stable packing needs the stratified sampler
    cfg = HierFAVGConfig(
        kappa1=2, kappa2=2,
        participation=ParticipationSpec(cohort_size=8, sampler="uniform"),
    )
    reason = sharded_cohort_incompatibility(cfg, spec, 8, 2)
    assert reason is not None and "stratified" in reason
    # delta_cloud + sync_opt_state has no sharded lowering (no opt anchor)
    cfg = HierFAVGConfig(kappa1=2, kappa2=2, delta_cloud=True, sync_opt_state=True)
    reason = sharded_cohort_incompatibility(cfg, spec, 8, 2)
    assert reason is not None and "sync_opt_state" in reason
    # a placement planned for a different shard count is rejected
    placement = plan_cohort_placement(spec, _cohort_quotas(spec, 8), 1)
    reason = sharded_cohort_incompatibility(good, spec, 8, 2, placement=placement)
    assert reason is not None and "shard" in reason


# ---------------------------------------------------------------------------
# builder parity
# ---------------------------------------------------------------------------

def _quad(rng, n):
    centers = rng.normal(size=(n, DIM))
    sizes = rng.integers(1, 4, size=n).astype(np.float64)

    def loss_fn(params, batch, _rng):
        return 0.5 * jnp.sum((params["w"] - batch["c"]) ** 2)

    batch = {"c": jnp.asarray(centers, jnp.float32)}
    return sizes, loss_fn, batch


def _stratified_ids(spec, c, rng):
    """A sorted stratified-shaped cohort (quota-block slot layout)."""
    edge_sizes = np.bincount(np.asarray(spec.segments(1)))
    quotas = stratified_quotas(edge_sizes, c)
    offsets = np.concatenate([[0], np.cumsum(edge_sizes)])
    return np.sort(
        np.concatenate(
            [
                offsets[e] + rng.choice(int(edge_sizes[e]), size=int(q), replace=False)
                for e, q in enumerate(quotas)
            ]
        )
    ).astype(np.int64)


def _identity_cohort(spec, sizes):
    if spec.depth > 1:
        table = np.stack(
            [np.asarray(spec.segments(l), np.int32) for l in range(1, spec.depth)]
        )
    else:
        table = np.zeros((0, spec.num_clients), np.int32)
    return {"segments": jnp.asarray(table), "weights": jnp.asarray(sizes, jnp.float32)}


def _assert_close(t1, t2, what):
    l1, l2 = jax.tree_util.tree_leaves(t1), jax.tree_util.tree_leaves(t2)
    assert len(l1) == len(l2), what
    for a, b in zip(l1, l2):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=3e-6, atol=2e-7, err_msg=what
        )


def _drive_sharded_cohort(topo, cfg, num_shards, *, c, opt=None, with_masks=False,
                          intervals=2, seed=0):
    """Run `intervals` cloud intervals through (a) the single-device cohort
    superround and (b) the sharded cohort superround over `num_shards`
    devices, with the same stratified-shaped cohort; return both final
    states (sharded one un-permuted to cohort order) and metric views."""
    opt = opt or sgd(0.1)
    spec = as_hierarchy(topo)
    n = spec.num_clients
    rng = np.random.default_rng(seed)
    sizes, loss_fn, batch = _quad(rng, n)
    k1, k2 = cfg.kappa1, cfg.kappa2_effective
    ids = _stratified_ids(spec, c, rng)
    cohort = {
        "segments": jnp.asarray(
            np.stack([np.asarray(spec.segments(l), np.int32)[ids]
                      for l in range(1, spec.depth)])
            if spec.depth > 1 else np.zeros((0, c), np.int32)
        ),
        "weights": jnp.asarray(sizes[ids], jnp.float32),
    }
    batch_c = {"c": batch["c"][ids]}
    block = jax.tree_util.tree_map(
        lambda x: jnp.stack([x] * (k2 * k1)).reshape((k2, k1) + x.shape), batch_c
    )
    masks = (
        None if not with_masks
        else (rng.random((intervals, k2, c)) > 0.3).astype(np.float32)
    )

    s1 = init_cohort_state(jax.random.PRNGKey(0), {"w": jnp.zeros(DIM)}, opt, cfg, c)
    coh = jax.jit(
        build_cohort_super_round(loss_fn, opt, topo, cfg, cohort_size=c),
        donate_argnums=(0,),
    )

    mesh = client_mesh(num_shards)
    placement = plan_cohort_placement(spec, _cohort_quotas(spec, c), num_shards)
    gather, pos = placement.gather_index(), placement.positions()
    valid = placement.valid()
    shc = jax.jit(
        build_sharded_cohort_super_round(
            loss_fn, opt, topo, cfg, cohort_size=c, mesh=mesh, placement=placement
        ),
        donate_argnums=(0,),
    )
    s2 = init_cohort_state(jax.random.PRNGKey(0), {"w": jnp.zeros(DIM)}, opt, cfg, c)
    s2 = map_stacked_fed_state(
        s2, lambda x: jnp.take(x, jnp.asarray(gather), axis=0), lambda x: x, c
    )
    s2 = jax.device_put(
        s2, fed_state_shardings(mesh, "clients", s2, placement.padded_clients)
    )
    block_sh = jax.tree_util.tree_map(
        lambda x: jax.device_put(
            jnp.take(x, jnp.asarray(gather), axis=2),
            batch_block_sharding(mesh, "clients"),
        ),
        block,
    )
    w_pad = jnp.asarray(placement.pad_weights(sizes[ids]))
    m1_all, m2_all = [], []
    for q in range(intervals):
        if masks is None:
            m1 = m2 = None
        else:
            m1 = jnp.asarray(masks[q])
            m2 = jax.device_put(
                jnp.asarray(masks[q][:, gather] * valid[None, :]),
                mask_stack_sharding(mesh, "clients"),
            )
        s1, mt1 = coh(s1, block, cohort, m1)
        s2, mt2 = shc(s2, block_sh, w_pad, m2)
        m1_all.append(jax.device_get(mt1))
        m2_all.append(jax.device_get(mt2))
    s2 = map_stacked_fed_state(
        s2, lambda x: jnp.take(x, jnp.asarray(pos), axis=0), lambda x: x,
        placement.padded_clients,
    )
    return s1, s2, m1_all, m2_all, placement


@pytest.mark.parametrize(
    "opt_name,cfg_kw,with_masks",
    [
        ("sgd", {}, False),
        ("sgd", {}, True),
        ("momentum", {"sync_opt_state": True}, False),
        ("sgd", {"transport": TransportSpec.parse("int8_ef:64/int8_ef:64")}, False),
    ],
    ids=["sgd", "sgd_masked", "momentum_sync_opt", "int8_ef_both"],
)
def test_sharded_cohort_single_shard_everywhere(opt_name, cfg_kw, with_masks):
    """The full sharded-cohort path over a 1-device mesh (C < N, ragged
    tree) — tier-1 always exercises the composed shard_map lowering."""
    topo = parse_fanouts("5,4,3/3")
    cfg = HierFAVGConfig(kappa1=2, kappa2=3, **cfg_kw)
    opt = momentum(0.1, 0.9) if opt_name == "momentum" else sgd(0.1)
    s1, s2, m1, m2, placement = _drive_sharded_cohort(
        topo, cfg, 1, c=8, opt=opt, with_masks=with_masks
    )
    _assert_close(s1.params, s2.params, "params")
    _assert_close(s1.opt_state, s2.opt_state, "opt_state")
    if s1.anchor is not None:
        _assert_close(s1.anchor, s2.anchor, "anchor")
    if s1.residual is not None:
        _assert_close(s1.residual, s2.residual, "residual")
    np.testing.assert_array_equal(np.asarray(s1.rng), np.asarray(s2.rng))
    valid = placement.valid()
    for a, b in zip(m1, m2):
        loss_b = np.asarray(b["loss"])[:, :, valid].mean(axis=(1, 2))
        np.testing.assert_allclose(np.asarray(a["loss"]), loss_b, rtol=1e-5, atol=1e-7)
        np.testing.assert_array_equal(np.asarray(a["step"]), np.asarray(b["step"]))


@needs4
@pytest.mark.parametrize(
    "opt_name,cfg_kw",
    [
        ("sgd", {}),
        ("momentum", {"sync_opt_state": True}),
        ("sgd", {"transport": TransportSpec.parse("int8_ef:64/int8_ef:64")}),
    ],
    ids=["sgd", "momentum_sync_opt", "int8_ef_both"],
)
def test_sharded_cohort_full_population_parity_4shards(opt_name, cfg_kw):
    """C == N over 4 shards: the sharded cohort superround reproduces
    ``build_sharded_super_round`` at the documented cloud-psum tolerance —
    the exit-proof parity anchor (incl. sync_opt_state and int8_ef)."""
    topo = FedTopology(num_edges=4, clients_per_edge=3)
    spec = as_hierarchy(topo)
    n = spec.num_clients
    cfg = HierFAVGConfig(kappa1=2, kappa2=3, **cfg_kw)
    opt = momentum(0.1, 0.9) if opt_name == "momentum" else sgd(0.1)
    rng = np.random.default_rng(0)
    sizes, loss_fn, batch = _quad(rng, n)
    w = jnp.asarray(sizes, jnp.float32)
    k1, k2 = cfg.kappa1, cfg.kappa2_effective
    mesh = client_mesh(4)

    # population path: edge-aligned client placement
    pop_placement = plan_shard_placement(spec, 4)
    # cohort path at C == N: quotas are exactly the edge sizes, so the slot
    # tree equals the client tree and both placements coincide
    coh_placement = plan_cohort_placement(spec, _cohort_quotas(spec, n), 4)
    np.testing.assert_array_equal(pop_placement.perm, coh_placement.perm)
    gather = pop_placement.gather_index()
    pos = pop_placement.positions()

    def shard_in(state, placement):
        out = map_stacked_fed_state(
            state, lambda x: jnp.take(x, jnp.asarray(gather), axis=0), lambda x: x, n
        )
        return jax.device_put(
            out, fed_state_shardings(mesh, "clients", out, placement.padded_clients)
        )

    block = jax.tree_util.tree_map(
        lambda x: jnp.stack([x] * (k2 * k1)).reshape((k2, k1) + x.shape), batch
    )
    block_sh = jax.tree_util.tree_map(
        lambda x: jax.device_put(
            jnp.take(x, jnp.asarray(gather), axis=2),
            batch_block_sharding(mesh, "clients"),
        ),
        block,
    )
    s1 = init_state(jax.random.PRNGKey(0), {"w": jnp.zeros(DIM)}, opt, topo, cfg)
    s1 = shard_in(s1, pop_placement)
    s2 = init_cohort_state(jax.random.PRNGKey(0), {"w": jnp.zeros(DIM)}, opt, cfg, n)
    s2 = shard_in(s2, coh_placement)
    sup = jax.jit(
        build_sharded_super_round(
            loss_fn, opt, topo, cfg, w, mesh=mesh, placement=pop_placement
        ),
        donate_argnums=(0,),
    )
    shc = jax.jit(
        build_sharded_cohort_super_round(
            loss_fn, opt, topo, cfg, cohort_size=n, mesh=mesh, placement=coh_placement
        ),
        donate_argnums=(0,),
    )
    w_pad = jnp.asarray(pop_placement.pad_weights(sizes))
    for _ in range(2):
        s1, mt1 = sup(s1, block_sh, None)
        s2, mt2 = shc(s2, block_sh, w_pad, None)
    unpad = lambda s: map_stacked_fed_state(
        s, lambda x: jnp.take(x, jnp.asarray(pos), axis=0), lambda x: x,
        pop_placement.padded_clients,
    )
    s1, s2 = unpad(s1), unpad(s2)
    _assert_close(s1.params, s2.params, "params")
    _assert_close(s1.opt_state, s2.opt_state, "opt_state")
    if s1.anchor is not None:
        _assert_close(s1.anchor, s2.anchor, "anchor")
    if s1.residual is not None:
        _assert_close(s1.residual, s2.residual, "residual")
    np.testing.assert_array_equal(np.asarray(s1.rng), np.asarray(s2.rng))
    np.testing.assert_allclose(
        np.asarray(mt1["loss"]), np.asarray(mt2["loss"]), rtol=1e-5, atol=1e-7
    )


def test_sharded_cohort_one_collective_per_interval():
    """Exactly one cross-device collective (the grouped cloud psum) in the
    whole sharded-cohort cloud-interval program."""
    topo = FedTopology(num_edges=4, clients_per_edge=4)
    spec = as_hierarchy(topo)
    c = 8
    cfg = HierFAVGConfig(kappa1=2, kappa2=3, sync_opt_state=True)
    rng = np.random.default_rng(0)
    sizes, loss_fn, _ = _quad(rng, spec.num_clients)
    opt = sgd(0.1)
    shards = min(4, jax.device_count())
    mesh = client_mesh(shards)
    placement = plan_cohort_placement(spec, _cohort_quotas(spec, c), shards)
    ids = _stratified_ids(spec, c, rng)
    state = init_cohort_state(jax.random.PRNGKey(0), {"w": jnp.zeros(DIM)}, opt, cfg, c)
    state = map_stacked_fed_state(
        state, lambda x: jnp.take(x, jnp.asarray(placement.gather_index()), axis=0),
        lambda x: x, c,
    )
    block = {
        "c": jnp.zeros((cfg.kappa2_effective, cfg.kappa1, placement.padded_clients, DIM))
    }
    w_pad = jnp.asarray(placement.pad_weights(sizes[ids]))
    fn = build_sharded_cohort_super_round(
        loss_fn, opt, topo, cfg, cohort_size=c, mesh=mesh, placement=placement
    )
    jaxpr = str(jax.make_jaxpr(fn)(state, block, w_pad, None))
    assert jaxpr.count(" psum") == 1, "expected exactly one psum per cloud interval"


# ---------------------------------------------------------------------------
# satellite: masked cohort == masked superround at C == N (same draw)
# ---------------------------------------------------------------------------

def test_cohort_masks_match_superround_full_population():
    """Survival masks compose with participation: at C == N the masked
    cohort superround reproduces the masked full-population superround
    bit-for-bit on a ragged tree (same mask draw, weight-column masking)."""
    spec = parse_fanouts("1,2,3/3")
    n = spec.num_clients
    rng = np.random.default_rng(3)
    sizes, loss_fn, batch = _quad(rng, n)
    cfg = HierFAVGConfig(kappa1=2, kappa2=3)
    opt = sgd(0.1)
    w = jnp.asarray(sizes, jnp.float32)
    k1, k2 = cfg.kappa1, cfg.kappa2_effective
    s1 = init_state(jax.random.PRNGKey(0), {"w": jnp.zeros(DIM)}, opt, spec, cfg)
    s2 = init_cohort_state(jax.random.PRNGKey(0), {"w": jnp.zeros(DIM)}, opt, cfg, n)
    sup = jax.jit(build_super_round(loss_fn, opt, spec, cfg, w), donate_argnums=(0,))
    coh = jax.jit(
        build_cohort_super_round(loss_fn, opt, spec, cfg, cohort_size=n),
        donate_argnums=(0,),
    )
    cohort = _identity_cohort(spec, sizes)
    block = jax.tree_util.tree_map(
        lambda x: jnp.stack([x] * (k2 * k1)).reshape((k2, k1) + x.shape), batch
    )
    for _ in range(2):
        masks = jnp.asarray((rng.random((k2, n)) > 0.3).astype(np.float32))
        s1, mt1 = sup(s1, block, masks)
        s2, mt2 = coh(s2, block, cohort, masks)
        np.testing.assert_array_equal(
            np.asarray(mt1["loss"]), np.asarray(mt2["loss"])
        )
    for t1, t2, what in [(s1.params, s2.params, "params"),
                         (s1.opt_state, s2.opt_state, "opt_state")]:
        for a, b in zip(jax.tree_util.tree_leaves(t1), jax.tree_util.tree_leaves(t2)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=what)
    np.testing.assert_array_equal(np.asarray(s1.rng), np.asarray(s2.rng))


# ---------------------------------------------------------------------------
# runner integration
# ---------------------------------------------------------------------------

def _cohort_spec(extra=()):
    from repro.fed.api import ExperimentSpec

    return ExperimentSpec.parse(
        [
            "topology.num_edges=4", "topology.clients_per_edge=4",
            "schedule.kappas=2,3", "run.num_rounds=12", "run.eval_every=6",
            "data.num_samples=320", "failures.p_fail=0.2",
            "participation.cohort_size=8", "participation.sampler=stratified",
        ]
        + list(extra)
    )


@pytest.mark.parametrize("shards", [1, pytest.param(4, marks=needs4)])
def test_cohort_runner_mesh_state_stays_on_the_mesh(shards):
    """The cohort state a mesh run hands back (canonical slot order) stays
    laid out over the mesh, slot rows split across it."""
    from jax.sharding import NamedSharding, PartitionSpec

    runner, state = _cohort_spec([f"topology.mesh_axes=clients:{shards}"]).run_experiment()
    assert runner._engine is not None and runner._engine.mesh is not None
    c = runner.participation.cohort_size
    for leaf in jax.tree_util.tree_leaves((state.params, state.opt_state)):
        assert isinstance(leaf.sharding, NamedSharding) and leaf.sharding.mesh == runner.mesh
        if leaf.ndim and leaf.shape[0] == c:
            assert leaf.sharding.spec == PartitionSpec("clients")


def test_cohort_runner_mesh_requires_stratified():
    """A mesh + a non-stratified sampler is a named hard error (no silent
    downgrade — sampled participation has no per-round fallback)."""
    spec = _cohort_spec(["participation.sampler=uniform", "topology.mesh_axes=clients:1"])
    with pytest.raises(ValueError, match="stratified"):
        spec.run_experiment()


def test_cohort_runner_with_failures_single_device():
    """Failure/straggler models compose with sampled participation (the old
    hard error is gone): the run completes, records cohort-column alive
    counts, and touches only sampled clients."""
    spec = _cohort_spec()
    runner, state = spec.run_experiment()
    assert runner.mesh is None and runner._engine is not None
    recs = runner.records_to_dict()
    assert recs["round"] == list(range(12))
    assert all(0 <= a <= 8 for a in recs["mask_alive"])
    assert any(a < 8 for a in recs["mask_alive"])  # p_fail=0.2 actually bit
    assert all(np.isfinite(l) for l in recs["loss"])


@needs4
def test_cohort_runner_mesh_parity_end_to_end():
    """The composed path: a mesh-configured cohort spec (stratified, with a
    failure model) runs through the sharded cohort engine and reproduces the
    single-device cohort run — history, masks, store, final params."""
    out = {}
    for tag, extra in [("single", []), ("mesh", ["topology.mesh_axes=clients:4"])]:
        runner, state = _cohort_spec(extra).run_experiment()
        out[tag] = (runner, runner.records_to_dict(), np.asarray(state.params["w1"]))
    runner_m, rec_m, p_m = out["mesh"]
    runner_s, rec_s, p_s = out["single"]
    assert runner_m.mesh is not None
    assert runner_m._engine is not None and runner_m._engine.mesh is not None
    assert runner_m._cohort_placement is not None
    np.testing.assert_allclose(p_s, p_m, rtol=3e-6, atol=2e-7)
    np.testing.assert_allclose(rec_s["loss"], rec_m["loss"], rtol=1e-5)
    assert rec_s["step"] == rec_m["step"]
    assert rec_s["mask_alive"] == rec_m["mask_alive"]
    # sticky rows land in the store by ORIGINAL client id on both paths
    st_s, st_m = runner_s.client_store, runner_m.client_store
    assert st_s.num_touched == st_m.num_touched
    for a, b in zip(st_s.state()["leaves"], st_m.state()["leaves"]):
        np.testing.assert_allclose(a, b, rtol=3e-6, atol=2e-7)
    for a, b in zip(rec_s["accuracy"], rec_m["accuracy"]):
        assert (a is None) == (b is None)
        if a is not None:
            assert abs(a - b) < 0.02


@needs4
def test_cohort_runner_mesh_resume_parity(tmp_path):
    """Interrupted + resumed sharded-cohort run == straight run: the slot
    placement is re-planned identically (placement stability) and the
    checkpoint carries canonical cohort-order state + sampler snapshots."""
    from repro.checkpoint import CheckpointManager

    def run_spec(ckdir, num_rounds):
        spec = _cohort_spec(
            ["topology.mesh_axes=clients:4", f"run.num_rounds={num_rounds}",
             "run.checkpoint_every=6"]
        )
        runner = spec.build()
        runner.checkpointer = CheckpointManager(str(ckdir), keep=4)
        params = spec.init_params(jax.random.PRNGKey(1))
        state, start = runner.restore_or_init(jax.random.PRNGKey(0), params)
        state = runner.run(state, start_round=start)
        return runner, state, start

    ra, sa, _ = run_spec(tmp_path / "straight", 12)
    rb, sb, start_b = run_spec(tmp_path / "resumed", 6)
    assert start_b == 0
    rc, sc, start_c = run_spec(tmp_path / "resumed", 12)  # resumes at round 6
    assert start_c == 6
    np.testing.assert_allclose(
        np.asarray(sa.params["w1"]), np.asarray(sc.params["w1"]),
        rtol=3e-6, atol=2e-7,
    )
    st_a, st_c = ra.client_store.state(), rc.client_store.state()
    for a, b in zip(st_a["leaves"], st_c["leaves"]):
        np.testing.assert_allclose(a, b, rtol=3e-6, atol=2e-7)
    np.testing.assert_array_equal(st_a["touched"], st_c["touched"])
