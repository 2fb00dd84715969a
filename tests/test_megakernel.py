"""Fused edge-interval megakernel: Pallas kernel vs oracle, the client-
blocked superround lowering vs the scan-fused baseline, and the engine's
opt-in fast path with named-reason fallback."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    FedTopology,
    HierFAVGConfig,
    build_megakernel_super_round,
    build_super_round,
    init_state,
    megakernel_incompatibility,
)
from repro.core.hierarchy import parse_fanouts
from repro.data import FederatedBatcher, clustered_gaussians, make_partition
from repro.fed import FailureSimulator, FederatedRunner, RunnerConfig
from repro.fed.api import ExperimentSpec
from repro.kernels import ops, ref
from repro.models import cnn
from repro.optim import adam, momentum, sgd


# ---------------------------------------------------------------------------
# Pallas kernel vs oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "n,e,k1,b,feat,out,mom",
    [
        (8, 2, 3, 2, 4, 3, 0.0),
        (16, 4, 2, 1, 8, 5, 0.9),
        (8, 2, 4, 2, 6, 2, 0.9),
        (8, 1, 2, 3, 4, 4, 0.0),  # single edge = cloud mean
    ],
)
def test_edge_interval_kernel_matches_ref(rng, n, e, k1, b, feat, out, mom):
    p = feat * out
    params = jnp.asarray(rng.normal(size=(n, p)) * 0.1, jnp.float32)
    x = jnp.asarray(rng.normal(size=(n, k1, b, feat)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(n, k1, b, out)), jnp.float32)
    w = jnp.asarray(rng.uniform(1, 3, n), jnp.float32)
    mu = jnp.asarray(rng.normal(size=(n, p)) * 0.01, jnp.float32) if mom else None
    got = ops.edge_interval(
        params, x, y, w, num_edges=e, feat=feat, lr=0.1, momentum=mom, mu=mu
    )
    want = ref.edge_interval_ref(
        params, x, y, w, e, feat=feat, lr=0.1, momentum=mom, mu=mu
    )
    # shared step body; only the contraction lowering differs -> ULP parity
    for a, b_, name in zip(got, want, ("params", "losses", "mu")):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), rtol=3e-6, atol=5e-7, err_msg=name
        )


def test_edge_interval_kernel_matches_super_round(rng):
    """One fused edge interval == κ₁ local steps + aggregation through the
    reference protocol lowering (E=1, κ₂=1: the cloud mean IS the edge
    mean), documented-ULP tolerance."""
    n, k1, b, feat, out = 8, 3, 2, 4, 3
    topo = FedTopology(num_edges=1, clients_per_edge=n)
    config = HierFAVGConfig(kappa1=k1, kappa2=1)
    w = jnp.asarray(rng.uniform(1, 3, n), jnp.float32)

    def loss_fn(p, batch, _rng):
        return jnp.mean(jnp.square(batch["x"] @ p["w"] - batch["y"]))

    p0 = {"w": jnp.asarray(rng.normal(size=(feat, out)) * 0.1, jnp.float32)}
    st = init_state(jax.random.PRNGKey(0), p0, sgd(0.1), topo, config)
    x = jnp.asarray(rng.normal(size=(1, k1, n, b, feat)), jnp.float32)
    y = jnp.asarray(rng.normal(size=(1, k1, n, b, out)), jnp.float32)
    sb, m = jax.jit(build_super_round(loss_fn, sgd(0.1), topo, config, w))(
        st, {"x": x, "y": y}, None
    )
    gp, gl, _ = ops.edge_interval(
        st.params["w"].reshape(n, feat * out),
        jnp.moveaxis(x[0], 1, 0), jnp.moveaxis(y[0], 1, 0),
        w, num_edges=1, feat=feat, lr=0.1,
    )
    np.testing.assert_allclose(
        np.asarray(sb.params["w"].reshape(n, feat * out)), np.asarray(gp),
        rtol=3e-6, atol=1e-6,
    )
    np.testing.assert_allclose(float(jnp.mean(gl)), float(m["loss"][0]), rtol=1e-6)


def test_edge_interval_kernel_vmem_budget():
    from repro.kernels.megakernel import edge_interval_pallas

    n, feat, out = 8, 512, 1024  # 8 clients x 2 MiB rows, one edge
    params = jnp.zeros((n, feat * out), jnp.float32)
    x = jnp.zeros((n, 4, 1, feat), jnp.float32)
    y = jnp.zeros((n, 4, 1, out), jnp.float32)
    w = jnp.ones((n,), jnp.float32)
    with pytest.raises(ValueError, match="VMEM budget"):
        edge_interval_pallas(
            params, x, y, w, num_edges=1, feat=feat, lr=0.1, interpret=True
        )


# ---------------------------------------------------------------------------
# Client-blocked superround lowering vs the scan-fused baseline
# ---------------------------------------------------------------------------


def _mk_problem(rng, n, feat=5, out=3):
    def loss_fn(p, batch, _rng):
        return jnp.mean(jnp.square(batch["x"] @ p["w"] + p["b"] - batch["y"]))

    p0 = {
        "w": jnp.asarray(rng.normal(size=(feat, out)) * 0.1, jnp.float32),
        "b": jnp.zeros((out,), jnp.float32),
    }
    def batches(k2, k1, b=2):
        return {
            "x": jnp.asarray(rng.normal(size=(k2, k1, n, b, feat)), jnp.float32),
            "y": jnp.asarray(rng.normal(size=(k2, k1, n, b, out)), jnp.float32),
        }
    return loss_fn, p0, batches


@pytest.mark.parametrize("opt_name", ["sgd", "momentum"])
@pytest.mark.parametrize("block_clients", [None, 1, 2, 4])
def test_blocked_super_round_matches_baseline(rng, opt_name, block_clients):
    n, e, k1, k2 = 8, 2, 3, 4
    opt = sgd(0.1) if opt_name == "sgd" else momentum(0.1, 0.9)
    topo = FedTopology(num_edges=e, clients_per_edge=n // e)
    config = HierFAVGConfig(kappa1=k1, kappa2=k2)
    w = jnp.asarray(rng.uniform(1, 3, n), jnp.float32)
    loss_fn, p0, batches = _mk_problem(rng, n)
    blk = batches(k2, k1)
    st = init_state(jax.random.PRNGKey(0), p0, opt, topo, config)
    base = jax.jit(build_super_round(loss_fn, opt, topo, config, w))
    mega = jax.jit(
        build_megakernel_super_round(
            loss_fn, opt, topo, config, w, block_clients=block_clients
        )
    )
    sb, mb = base(jax.tree_util.tree_map(jnp.copy, st), blk, None)
    sm, mm = mega(jax.tree_util.tree_map(jnp.copy, st), blk)
    # same steps, same RNG chain; only the mean/metric summation order
    # differs -> documented reassociation tolerance
    for a, b in zip(
        jax.tree_util.tree_leaves(sb.params), jax.tree_util.tree_leaves(sm.params)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)
    for a, b in zip(
        jax.tree_util.tree_leaves(sb.opt_state), jax.tree_util.tree_leaves(sm.opt_state)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(mb["loss"]), np.asarray(mm["loss"]), rtol=1e-5)
    np.testing.assert_allclose(
        np.asarray(mb["grad_norm"]), np.asarray(mm["grad_norm"]), rtol=1e-5
    )
    np.testing.assert_array_equal(np.asarray(mb["step"]), np.asarray(mm["step"]))
    assert int(sb.step) == int(sm.step)
    # the per-client RNG chain is reproduced exactly, not approximately
    np.testing.assert_array_equal(np.asarray(sb.rng), np.asarray(sm.rng))


def test_blocked_super_round_rejects_masks(rng):
    n, e = 4, 2
    topo = FedTopology(num_edges=e, clients_per_edge=n // e)
    config = HierFAVGConfig(kappa1=2, kappa2=2)
    loss_fn, p0, batches = _mk_problem(rng, n)
    fn = build_megakernel_super_round(
        loss_fn, sgd(0.1), topo, config, jnp.ones((n,), jnp.float32)
    )
    st = init_state(jax.random.PRNGKey(0), p0, sgd(0.1), topo, config)
    with pytest.raises(TypeError, match="survival masks"):
        fn(st, batches(2, 2), jnp.ones((2, n), jnp.float32))


def test_blocked_super_round_rejects_unstackable_opt_state(rng):
    """adam forces f32 (N, ...) mu/nu rows — those stack fine; a synthetic
    optimizer with a wrong-leading-dim leaf must be rejected, not silently
    misblocked."""
    n, e = 4, 2
    topo = FedTopology(num_edges=e, clients_per_edge=n // e)
    config = HierFAVGConfig(kappa1=2, kappa2=2)
    loss_fn, p0, batches = _mk_problem(rng, n)
    fn = build_megakernel_super_round(
        loss_fn, adam(0.01), topo, config, jnp.ones((n,), jnp.float32)
    )
    st = init_state(jax.random.PRNGKey(0), p0, adam(0.01), topo, config)
    # adam's stacked state is fine
    fn(jax.tree_util.tree_map(jnp.copy, st), batches(2, 2))
    bad = st._replace(
        opt_state=jax.tree_util.tree_map(
            lambda x: x[: n - 1] if getattr(x, "ndim", 0) >= 1 and x.shape[0] == n else x,
            st.opt_state,
        )
    )
    with pytest.raises(ValueError, match="optimizer state leaves"):
        fn(bad, batches(2, 2))


# ---------------------------------------------------------------------------
# Eligibility predicate
# ---------------------------------------------------------------------------


def test_megakernel_incompatibility_reasons():
    topo = FedTopology(num_edges=2, clients_per_edge=4)
    ok = HierFAVGConfig(kappa1=2, kappa2=2)
    assert megakernel_incompatibility(ok, topo) is None
    cases = [
        (HierFAVGConfig(kappa1=2, kappa2=2, delta_cloud=True), "delta_cloud"),
        (HierFAVGConfig(kappa1=2, kappa2=2, sync_opt_state=True), "optimizer-state"),
    ]
    for cfg, frag in cases:
        reason = megakernel_incompatibility(cfg, topo)
        assert reason is not None and frag in reason, (cfg, reason)
    assert "microbatch" in megakernel_incompatibility(ok, topo, grad_accum=2)
    # ragged trees stay on the scan-fused path
    ragged = parse_fanouts("5,3/2")
    assert "uniform" in megakernel_incompatibility(ok, ragged)
    # deeper uniform trees too (the lowering is two-level only for now)
    deep = parse_fanouts("2,2,2,2/2,2/2")
    cfg3 = HierFAVGConfig.multi_level((2, 2, 2))
    assert megakernel_incompatibility(cfg3, deep) is not None


def test_megakernel_builder_raises_on_incompatible(rng):
    topo = FedTopology(num_edges=2, clients_per_edge=2)
    cfg = HierFAVGConfig(kappa1=2, kappa2=2, delta_cloud=True)
    loss_fn, _, _ = _mk_problem(rng, 4)
    with pytest.raises(ValueError, match="megakernel"):
        build_megakernel_super_round(
            loss_fn, sgd(0.1), topo, cfg, jnp.ones((4,), jnp.float32)
        )


# ---------------------------------------------------------------------------
# Engine fast path + named-reason fallback
# ---------------------------------------------------------------------------


def _spec(*overrides):
    return ExperimentSpec().with_overrides([
        "topology.num_edges=2", "topology.clients_per_edge=4",
        "schedule.kappas=2,2", "data.num_samples=320", "data.batch_size=4",
        "run.num_rounds=4", "run.eval_every=0", "cost.workload=none",
        *overrides,
    ])


def test_engine_megakernel_matches_superround_trajectory():
    runs = {}
    for eng in ("superround", "megakernel"):
        runner, state = _spec(f"run.engine={eng}").run_experiment()
        runs[eng] = (runner, state)
    rs, ss = runs["superround"]
    rm, sm = runs["megakernel"]
    assert rm._engine.uses_megakernel and rm._engine.megakernel_reason is None
    assert not getattr(rs._engine, "uses_megakernel", False)
    for a, b in zip(
        jax.tree_util.tree_leaves(ss.params), jax.tree_util.tree_leaves(sm.params)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(ss.rng), np.asarray(sm.rng))
    assert len(rs.history) == len(rm.history)
    for h1, h2 in zip(rs.history, rm.history):
        assert h1.step == h2.step
        np.testing.assert_allclose(h1.loss, h2.loss, rtol=1e-5)
        np.testing.assert_allclose(h1.grad_norm, h2.grad_norm, rtol=1e-5)


def test_engine_megakernel_fallback_reasons():
    # schedule-level: delta_cloud keeps the scan-fused path
    runner, _ = _spec("run.engine=megakernel", "schedule.delta_cloud=true").run_experiment()
    eng = runner._engine
    assert not eng.uses_megakernel and "delta_cloud" in eng.megakernel_reason
    assert runner._megakernel_reason == eng.megakernel_reason
    # runner-level: failure models keep the scan-fused survival plumbing
    runner, _ = _spec("run.engine=megakernel", "failures.p_fail=0.3").run_experiment()
    assert not runner._engine.uses_megakernel
    assert "failure" in runner._engine.megakernel_reason


def test_engine_megakernel_fallback_still_correct():
    """A fallen-back megakernel run is exactly a superround run."""
    runs = {}
    for eng in ("superround", "megakernel"):
        runner, state = _spec(
            f"run.engine={eng}", "schedule.delta_cloud=true"
        ).run_experiment()
        runs[eng] = state
    for a, b in zip(
        jax.tree_util.tree_leaves(runs["superround"].params),
        jax.tree_util.tree_leaves(runs["megakernel"].params),
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_engine_megakernel_mesh_routes_to_sharded(rng):
    """With a mesh, engine='megakernel' reports the mesh reason and runs
    the client-sharded superround (single-device mesh keeps it cheap)."""
    mesh = pytest.importorskip("jax.sharding").Mesh(
        np.array(jax.devices()[:1]), ("clients",)
    )
    n, e, k1, k2 = 8, 2, 2, 2
    data = clustered_gaussians(rng, num_samples=160, num_classes=4, dim=(6,), class_sep=2.0)
    parts = make_partition("iid", data.y, e, n // e, rng)
    batcher = FederatedBatcher(
        {"inputs": data.x, "targets": data.y}, parts, batch_size=4, seed=0
    )

    def apply_fn(p, x):
        return x @ p["w"]

    loss_fn = cnn.make_cnn_loss_fn(apply_fn)
    runner = FederatedRunner(
        loss_fn=loss_fn,
        optimizer=sgd(0.1),
        topology=FedTopology(num_edges=e, clients_per_edge=n // e),
        hier_config=HierFAVGConfig(kappa1=k1, kappa2=k2),
        data_sizes=batcher.data_sizes,
        batcher=batcher,
        runner_config=RunnerConfig(num_rounds=k2, engine="megakernel"),
        mesh=mesh,
    )
    p0 = {"w": jnp.asarray(rng.normal(size=(6, 4)) * 0.1, jnp.float32)}
    state = runner.init(jax.random.PRNGKey(0), p0)
    runner.run(state)
    assert not runner._engine.uses_megakernel
    assert "mesh" in runner._engine.megakernel_reason


def test_runner_config_engine_validation():
    RunnerConfig(num_rounds=1, engine="megakernel")
    with pytest.raises(ValueError, match="megakernel"):
        RunnerConfig(num_rounds=1, engine="hyperkernel")


def test_engine_megakernel_raises_without_whole_interval():
    spec = _spec("run.engine=megakernel", "run.num_rounds=1")  # < kappa2
    with pytest.raises(ValueError, match="megakernel"):
        spec.run_experiment()
