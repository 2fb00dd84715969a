"""Client-sharded superround execution: edge-aligned shard placement,
shard_map parity against the single-device engine, the one-collective-per-
cloud-interval guarantee, donation, and the mesh-aware runner/API plumbing.

Placement and compatibility logic is pure host code and always runs. The
shard_map cases need a device mesh: the 1-shard cases run everywhere (the
full sharded code path over a 1-device mesh), the >=4-shard cases skip
unless the session exposes 4 devices — CI runs them in a dedicated job
under ``XLA_FLAGS=--xla_force_host_platform_device_count=4``.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import (
    ClientSharding,
    FedTopology,
    HierFAVGConfig,
    build_level_sync,
    build_sharded_super_round,
    build_super_round,
    fed_state_partition_specs,
    init_state,
    plan_shard_placement,
    sharding_incompatibility,
)
from repro.core.aggregation import AggregatorSpec, parse_aggregator
from repro.core.hierarchy import as_hierarchy, parse_fanouts
from repro.dist.sharding import (
    ShardingRules,
    batch_block_sharding,
    client_mesh,
    fed_state_shardings,
    mask_stack_sharding,
)
from repro.fed import TransportSpec
from repro.fed.api import ExperimentSpec
from repro.optim import momentum, sgd

needs4 = pytest.mark.skipif(
    jax.device_count() < 4,
    reason="needs 4 devices (XLA_FLAGS=--xla_force_host_platform_device_count=4)",
)

DIM = 3


# ---------------------------------------------------------------------------
# placement planning (host-side, always runs)
# ---------------------------------------------------------------------------

def test_placement_uniform_packs_exactly():
    spec = as_hierarchy(FedTopology(num_edges=8, clients_per_edge=2))
    p = plan_shard_placement(spec, 4)
    assert p.capacity == 4 and p.num_phantoms == 0
    assert sorted(p.perm) == list(range(16))
    # every edge's clients land inside one shard, in original order
    seg = spec.segments(1)
    for s in range(4):
        row = p.perm[s * p.capacity : (s + 1) * p.capacity]
        for a, b in zip(row, row[1:]):
            assert not (seg[a] == seg[b] and a > b)  # intra-group order kept
        assert len({seg[c] for c in row}) == 2  # whole edges only
    # identical local layout across shards -> static ids, uniform fast path
    tab = p.local_segments(1)
    assert (tab == tab[0]).all()
    np.testing.assert_array_equal(tab[0], [0, 0, 1, 1])


def test_placement_ragged_pads_with_phantoms():
    spec = parse_fanouts("4,2,1/3")
    p = plan_shard_placement(spec, 2)
    assert p.capacity == 4  # LPT: [4] vs [2, 1] + 1 phantom
    assert p.num_phantoms == 1
    assert p.padded_clients == 8
    valid = p.valid()
    assert valid.sum() == 7
    # inverse maps every real client back to its padded position
    pos = p.positions()
    gather = p.gather_index()
    for c in range(7):
        assert gather[pos[c]] == c
    # phantoms get the dedicated trailing local segment
    tab = p.local_segments(1)
    nseg = p.local_num_segments(1)
    phantom_cols = ~valid.reshape(2, p.capacity)
    assert (tab[phantom_cols] == nseg - 1).all()
    # weights: phantoms carry exactly zero
    w = p.pad_weights(np.arange(1, 8, dtype=np.float64))
    assert (w[~valid] == 0).all() and w[valid].sum() == sum(range(1, 8))


def test_placement_rejects_more_shards_than_subtrees():
    spec = as_hierarchy(FedTopology(num_edges=2, clients_per_edge=5))
    with pytest.raises(ValueError, match="subtree"):
        plan_shard_placement(spec, 4)


def test_placement_depth3_aligns_regions():
    # depth-3: alignment groups are level-2 regions, so BOTH edge and
    # region syncs stay shard-local
    spec = parse_fanouts("3,2,3,2/2,2/2")
    p = plan_shard_placement(spec, 2)
    seg2 = spec.segments(2)
    for s in range(2):
        row = [c for c in p.perm[s * p.capacity : (s + 1) * p.capacity] if c >= 0]
        assert len({seg2[c] for c in row}) == 1  # one whole region per shard


def test_sharding_incompatibility_reasons():
    topo = FedTopology(num_edges=4, clients_per_edge=2)
    ok = HierFAVGConfig(kappa1=2, kappa2=2)
    assert sharding_incompatibility(ok, topo, 4) is None
    robust_top = HierFAVGConfig(
        kappa1=2, kappa2=2,
        aggregators=AggregatorSpec(
            aggregators=(parse_aggregator("weighted_mean"), parse_aggregator("median"))
        ),
    )
    assert "top-level" in sharding_incompatibility(robust_top, topo, 4)
    # robust edge sync over a packing that is ragged across shards
    ragged = parse_fanouts("4,2,1/3")
    robust_edge = HierFAVGConfig(
        kappa1=2, kappa2=2,
        aggregators=AggregatorSpec(
            aggregators=(parse_aggregator("trimmed_mean:0.25"), parse_aggregator("weighted_mean"))
        ),
    )
    assert sharding_incompatibility(robust_edge, topo, 4) is None
    assert "segment layout" in sharding_incompatibility(robust_edge, ragged, 2)
    # too many shards surfaces the placement error as the reason
    assert "subtree" in sharding_incompatibility(ok, FedTopology(2, 4), 4)


def test_client_member_rejects_indivisible_counts():
    class _FakeMesh:
        axis_names = ("clients",)
        shape = {"clients": 4}

    rules = ShardingRules(mesh=_FakeMesh(), client_axes=("clients",))
    assert rules._client_member(8) == "clients"
    with pytest.raises(ValueError, match="not divisible"):
        rules._client_member(6)
    # no client axes configured is not an error (serving rules)
    assert ShardingRules(mesh=_FakeMesh(), client_axes=())._client_member(6) is None


# ---------------------------------------------------------------------------
# shard_map parity vs the single-device superround
# ---------------------------------------------------------------------------

def _quad(rng, n):
    centers = rng.normal(size=(n, DIM))
    sizes = rng.integers(1, 4, size=n).astype(np.float64)

    def loss_fn(params, batch, _rng):
        return 0.5 * jnp.sum((params["w"] - batch["c"]) ** 2)

    batch = {"c": jnp.asarray(centers, jnp.float32)}
    return sizes, loss_fn, batch


def _pad_state(state, placement, n):
    gather = jnp.asarray(placement.gather_index())

    def pad_tree(t):
        return jax.tree_util.tree_map(
            lambda x: jnp.take(x, gather, axis=0)
            if getattr(x, "ndim", 0) >= 1 and x.shape[0] == n
            else x,
            t,
        )

    return state._replace(
        params=pad_tree(state.params),
        opt_state=pad_tree(state.opt_state),
        anchor=None if state.anchor is None else pad_tree(state.anchor),
        residual=None if state.residual is None else pad_tree(state.residual),
    )


def _drive_pair(topo, cfg, num_shards, *, opt=None, masks=None, intervals=2, seed=0):
    """Run `intervals` cloud intervals through (a) the single-device
    superround and (b) the client-sharded superround over `num_shards`
    devices; return both final states (sharded one un-permuted back to
    canonical order) plus both metric views."""
    opt = opt or sgd(0.1)
    spec = as_hierarchy(topo)
    n = spec.num_clients
    sizes, loss_fn, batch = _quad(np.random.default_rng(seed), n)
    w = jnp.asarray(sizes, jnp.float32)
    k1, k2 = cfg.kappa1, cfg.kappa2_effective
    mesh = client_mesh(num_shards)
    placement = plan_shard_placement(spec, num_shards)

    s1 = init_state(jax.random.PRNGKey(0), {"w": jnp.zeros(DIM)}, opt, topo, cfg)
    s2 = init_state(jax.random.PRNGKey(0), {"w": jnp.zeros(DIM)}, opt, topo, cfg)
    sup = jax.jit(build_super_round(loss_fn, opt, topo, cfg, w), donate_argnums=(0,))
    shsup = jax.jit(
        build_sharded_super_round(loss_fn, opt, topo, cfg, w, mesh=mesh, placement=placement),
        donate_argnums=(0,),
    )
    block = jax.tree_util.tree_map(
        lambda x: jnp.stack([x] * (k2 * k1)).reshape((k2, k1) + x.shape), batch
    )
    gather = placement.gather_index()
    s2 = _pad_state(s2, placement, n)
    s2 = jax.device_put(
        s2, fed_state_shardings(mesh, "clients", s2, placement.padded_clients)
    )
    block_sh = jax.tree_util.tree_map(
        lambda x: jax.device_put(
            jnp.take(x, jnp.asarray(gather), axis=2), batch_block_sharding(mesh, "clients")
        ),
        block,
    )
    valid = placement.valid()
    m1_all, m2_all = [], []
    for q in range(intervals):
        if masks is None:
            m1 = m2 = None
        else:
            st = np.stack(masks[q * k2 : (q + 1) * k2]).astype(np.float32)
            m1 = jnp.asarray(st)
            m2 = jax.device_put(
                jnp.asarray(st[:, gather] * valid[None, :]),
                mask_stack_sharding(mesh, "clients"),
            )
        s1, mt1 = sup(s1, block, m1)
        s2, mt2 = shsup(s2, block_sh, m2)
        m1_all.append(jax.device_get(mt1))
        m2_all.append(jax.device_get(mt2))
    pos = jnp.asarray(placement.positions())
    unpad = lambda t: jax.tree_util.tree_map(
        lambda x: jnp.take(x, pos, axis=0)
        if getattr(x, "ndim", 0) >= 1 and x.shape[0] == placement.padded_clients
        else x,
        t,
    )
    s2 = s2._replace(
        params=unpad(s2.params),
        opt_state=unpad(s2.opt_state),
        anchor=None if s2.anchor is None else unpad(s2.anchor),
        residual=None if s2.residual is None else unpad(s2.residual),
    )
    return s1, s2, m1_all, m2_all, placement


def _assert_states_close(s1, s2):
    """The documented mesh tolerance: every sub-top reduction and local step
    is order-identical, only the cloud psum reassociates the weighted sum,
    so states agree to ~1 ULP per cloud boundary (rtol 3e-6)."""
    for t1, t2, what in [
        (s1.params, s2.params, "params"),
        (s1.opt_state, s2.opt_state, "opt_state"),
        (s1.anchor, s2.anchor, "anchor"),
        (s1.residual, s2.residual, "residual"),
    ]:
        l1 = jax.tree_util.tree_leaves(t1)
        l2 = jax.tree_util.tree_leaves(t2)
        assert len(l1) == len(l2), what
        for a, b in zip(l1, l2):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=3e-6, atol=2e-7, err_msg=what
            )
    assert int(s1.step) == int(s2.step)


def _assert_metrics_close(m1_all, m2_all, placement):
    valid = placement.valid()
    for mt1, mt2 in zip(m1_all, m2_all):
        loss1 = np.asarray(mt1["loss"])  # (κ₂,)
        loss2 = np.asarray(mt2["loss"])[:, :, valid].mean(axis=(1, 2))
        np.testing.assert_allclose(loss1, loss2, rtol=1e-5, atol=1e-7)
        np.testing.assert_array_equal(np.asarray(mt1["step"]), np.asarray(mt2["step"]))
        gn1 = np.asarray(mt1["grad_norm"])
        gsq = np.asarray(mt2["gsq"])[:, :, valid]
        gn2 = np.sqrt(gsq.sum(axis=2)).mean(axis=1)
        np.testing.assert_allclose(gn1, gn2, rtol=1e-5, atol=1e-7)


def test_sharded_superround_single_shard_everywhere():
    """The full sharded path over a 1-device mesh — runs in every
    environment, so tier-1 always exercises shard_map + psum lowering."""
    topo = FedTopology(num_edges=2, clients_per_edge=3)
    cfg = HierFAVGConfig(kappa1=2, kappa2=3)
    s1, s2, m1, m2, placement = _drive_pair(topo, cfg, 1)
    _assert_states_close(s1, s2)
    _assert_metrics_close(m1, m2, placement)


@needs4
def test_sharded_superround_uniform():
    topo = FedTopology(num_edges=4, clients_per_edge=2)
    cfg = HierFAVGConfig(kappa1=2, kappa2=3)
    s1, s2, m1, m2, placement = _drive_pair(topo, cfg, 4)
    assert placement.num_phantoms == 0
    _assert_states_close(s1, s2)
    _assert_metrics_close(m1, m2, placement)


@needs4
def test_sharded_superround_ragged_padded():
    """Ragged edges force phantom padding; padding must be numerically
    inert (zero weight, dedicated segment)."""
    spec = parse_fanouts("3,2,3,2/4")
    cfg = HierFAVGConfig(kappa1=2, kappa2=2)
    s1, s2, m1, m2, placement = _drive_pair(spec, cfg, 4)
    assert placement.num_phantoms > 0
    _assert_states_close(s1, s2)
    _assert_metrics_close(m1, m2, placement)


@needs4
def test_sharded_superround_masks_with_dead_edge():
    topo = FedTopology(num_edges=4, clients_per_edge=2)
    cfg = HierFAVGConfig(kappa1=2, kappa2=3)
    masks = [np.ones(8, np.float32) for _ in range(6)]
    masks[1][3] = 0.0
    masks[2][:2] = 0.0  # edge 0 entirely dead at a boundary
    masks[5][0] = 0.0  # masked client at the cloud boundary
    s1, s2, m1, m2, placement = _drive_pair(topo, cfg, 4, masks=masks)
    _assert_states_close(s1, s2)
    _assert_metrics_close(m1, m2, placement)


@needs4
def test_sharded_superround_int8_ef_transport():
    """Compressed uplinks: anchor re-sync, EF residual carry, and the
    keep-dead logic all stay shard-local (plus a masked round)."""
    topo = FedTopology(num_edges=4, clients_per_edge=2)
    cfg = HierFAVGConfig(
        kappa1=2, kappa2=2, transport=TransportSpec.parse("int8_ef:64/int8_ef:64")
    )
    masks = [np.ones(8, np.float32) for _ in range(4)]
    masks[1][2] = 0.0
    s1, s2, m1, m2, placement = _drive_pair(topo, cfg, 4, masks=masks)
    assert s2.residual is not None
    _assert_states_close(s1, s2)


@needs4
def test_sharded_superround_trimmed_edge_aggregator():
    topo = FedTopology(num_edges=4, clients_per_edge=3)
    cfg = HierFAVGConfig(
        kappa1=2, kappa2=2,
        aggregators=AggregatorSpec(
            aggregators=(parse_aggregator("trimmed_mean:0.25"), parse_aggregator("weighted_mean"))
        ),
    )
    masks = [np.ones(12, np.float32) for _ in range(4)]
    masks[0][5] = 0.0
    s1, s2, m1, m2, placement = _drive_pair(topo, cfg, 4, masks=masks)
    _assert_states_close(s1, s2)


@needs4
def test_sharded_superround_sync_opt_state():
    """Momentum state rides the same packed cloud psum as the params."""
    topo = FedTopology(num_edges=4, clients_per_edge=2)
    cfg = HierFAVGConfig(kappa1=2, kappa2=2, sync_opt_state=True)
    s1, s2, _, _, _ = _drive_pair(topo, cfg, 4, opt=momentum(0.1, 0.9))
    _assert_states_close(s1, s2)


@needs4
def test_sharded_edge_sync_bitexact():
    """Edge aggregation is collective-free AND bit-exact under sharding:
    placement keeps each edge whole and preserves member order, so the
    shard-local reduction adds the same values in the same order."""
    topo = FedTopology(num_edges=4, clients_per_edge=3)
    cfg = HierFAVGConfig(kappa1=1, kappa2=2)
    spec = as_hierarchy(topo)
    rng = np.random.default_rng(3)
    sizes = rng.integers(1, 4, size=12).astype(np.float64)
    w = jnp.asarray(sizes, jnp.float32)
    opt = sgd(0.1)
    state = init_state(jax.random.PRNGKey(0), {"w": jnp.zeros(DIM)}, opt, topo, cfg)
    state = state._replace(
        params={"w": jnp.asarray(rng.normal(size=(12, DIM)), jnp.float32)}
    )
    ref = build_level_sync(topo, cfg, w, 1)(state).params["w"]

    mesh = client_mesh(4)
    placement = plan_shard_placement(spec, 4)
    shard = ClientSharding.build("clients", placement, w)
    sync = build_level_sync(topo, cfg, w, 1, shard=shard)
    padded = _pad_state(state, placement, 12)
    specs = fed_state_partition_specs(padded, "clients", placement.padded_clients)
    with mesh:
        out = jax.shard_map(
            lambda s: sync(s), mesh=mesh, in_specs=(specs,), out_specs=specs,
            check_vma=False,
        )(padded)
    got = np.asarray(out.params["w"])[placement.positions()]
    np.testing.assert_array_equal(np.asarray(ref), got)


def test_sharded_superround_one_collective_per_interval():
    """The acceptance check: exactly one cross-device collective (psum) in
    the whole cloud-interval program, for a 2-level topology — with and
    without sync_opt_state (opt leaves ride the same packed psum)."""
    topo = FedTopology(num_edges=4, clients_per_edge=2)
    n = 8
    sizes, loss_fn, batch = _quad(np.random.default_rng(0), n)
    w = jnp.asarray(sizes, jnp.float32)
    opt = sgd(0.1)
    shards = min(4, jax.device_count())
    mesh = client_mesh(shards)
    placement = plan_shard_placement(as_hierarchy(topo), shards)
    for cfg in (
        HierFAVGConfig(kappa1=2, kappa2=3),
        HierFAVGConfig(kappa1=2, kappa2=3, sync_opt_state=True),
    ):
        state = init_state(jax.random.PRNGKey(0), {"w": jnp.zeros(DIM)}, opt, topo, cfg)
        state = _pad_state(state, placement, n)
        block = jax.tree_util.tree_map(
            lambda x: jnp.take(
                jnp.stack([x] * 6).reshape((3, 2) + x.shape),
                jnp.asarray(placement.gather_index()), axis=2,
            ),
            batch,
        )
        fn = build_sharded_super_round(
            loss_fn, opt, topo, cfg, w, mesh=mesh, placement=placement
        )
        jaxpr = str(jax.make_jaxpr(fn)(state, block, None))
        assert jaxpr.count(" psum") == 1, "expected exactly one psum per cloud interval"


def test_sharded_superround_donation():
    """donate_argnums must release the sharded input FedState's buffers —
    the zero-copy claim survives shard_map."""
    topo = FedTopology(num_edges=2, clients_per_edge=3)
    cfg = HierFAVGConfig(kappa1=2, kappa2=2)
    n = 6
    sizes, loss_fn, batch = _quad(np.random.default_rng(0), n)
    w = jnp.asarray(sizes, jnp.float32)
    opt = sgd(0.1)
    shards = min(2, jax.device_count())
    mesh = client_mesh(shards)
    placement = plan_shard_placement(as_hierarchy(topo), shards)
    state = init_state(jax.random.PRNGKey(0), {"w": jnp.zeros(DIM)}, opt, topo, cfg)
    state = _pad_state(state, placement, n)
    state = jax.device_put(
        state, fed_state_shardings(mesh, "clients", state, placement.padded_clients)
    )
    donated_leaf = state.params["w"]
    block = jax.tree_util.tree_map(
        lambda x: jax.device_put(
            jnp.take(
                jnp.stack([x] * 4).reshape((2, 2) + x.shape),
                jnp.asarray(placement.gather_index()), axis=2,
            ),
            batch_block_sharding(mesh, "clients"),
        ),
        batch,
    )
    fn = jax.jit(
        build_sharded_super_round(loss_fn, opt, topo, cfg, w, mesh=mesh, placement=placement),
        donate_argnums=(0,),
    )
    out, _ = fn(state, block, None)
    jax.block_until_ready(out.params)
    assert donated_leaf.is_deleted(), "donated sharded input buffer was not released"
    assert not jax.tree_util.tree_leaves(out.params)[0].is_deleted()


# ---------------------------------------------------------------------------
# runner + ExperimentSpec integration
# ---------------------------------------------------------------------------

def _mesh_spec(extra=()):
    return ExperimentSpec.parse(
        [
            "topology.num_edges=4", "topology.clients_per_edge=4",
            "schedule.kappas=2,3", "run.num_rounds=6", "run.eval_every=3",
            "data.num_samples=320", "failures.p_fail=0.2",
        ]
        + list(extra)
    )


@needs4
def test_runner_mesh_parity_end_to_end():
    """A mesh-configured spec runs whole cloud intervals through the
    sharded engine (no per-round fallback) and reproduces the single-device
    history: steps, masks, losses, eval accuracy."""
    out = {}
    for tag, extra in [("single", []), ("mesh", ["topology.mesh_axes=clients:4"])]:
        runner, state = _mesh_spec(extra).run_experiment()
        out[tag] = (runner, runner.records_to_dict(), np.asarray(state.params["w1"]))
    runner_m, rec_m, p_m = out["mesh"]
    _, rec_s, p_s = out["single"]
    assert runner_m.mesh is not None
    assert runner_m._engine is not None and runner_m._engine.mesh is not None
    np.testing.assert_allclose(p_s, p_m, rtol=3e-6, atol=2e-7)
    np.testing.assert_allclose(rec_s["loss"], rec_m["loss"], rtol=1e-5)
    assert rec_s["step"] == rec_m["step"]
    assert rec_s["mask_alive"] == rec_m["mask_alive"]
    for a, b in zip(rec_s["accuracy"], rec_m["accuracy"]):
        assert (a is None) == (b is None)
        if a is not None:
            assert abs(a - b) < 0.02


@needs4
def test_runner_mesh_unshardable_falls_back_per_round():
    """engine='auto' + a schedule the sharded path cannot lower (robust
    cloud aggregator) must still train — via the per-round loop."""
    spec = _mesh_spec(
        ["topology.mesh_axes=clients:4", "aggregators.levels=weighted_mean/median"]
    )
    runner, state = spec.run_experiment()
    assert runner._engine is None  # fell back: no superround engine built
    assert runner._mesh_reason and "top-level" in runner._mesh_reason
    assert [r.round for r in runner.history] == list(range(6))


@pytest.mark.parametrize("shards", [1, pytest.param(4, marks=needs4)])
def test_runner_mesh_state_stays_on_the_mesh(shards):
    """The state a mesh run hands back (canonical client order) is still
    laid out over the mesh, client rows split across it — never committed
    to one device (a state sharded for lack of room must not be gathered)."""
    runner, state = _mesh_spec([f"topology.mesh_axes=clients:{shards}"]).run_experiment()
    assert runner._engine is not None and runner._engine.mesh is not None
    n = runner.topology.num_clients
    for leaf in jax.tree_util.tree_leaves((state.params, state.opt_state)):
        assert isinstance(leaf.sharding, NamedSharding) and leaf.sharding.mesh == runner.mesh
        if leaf.ndim and leaf.shape[0] == n:
            assert leaf.sharding.spec == P("clients")
            assert {sh.data.shape[0] for sh in leaf.addressable_shards} == {n // shards}


def test_topology_spec_mesh_axes_roundtrip_and_errors():
    spec = ExperimentSpec.parse(["topology.mesh_axes=clients:2"])
    assert spec.topology.mesh_axes == "clients:2"
    again = ExperimentSpec.from_dict(spec.to_dict())
    assert again == spec
    assert "mesh=clients:2" in spec.describe()
    # oversubscribing visible devices names the XLA_FLAGS recipe
    with pytest.raises(ValueError, match="xla_force_host_platform_device_count"):
        ExperimentSpec.parse(["topology.mesh_axes=clients:4096"]).build()
    with pytest.raises(ValueError, match="mesh_axes"):
        ExperimentSpec.parse(["topology.mesh_axes=clients:two"]).build()
