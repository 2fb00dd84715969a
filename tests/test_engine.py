"""Superround engine: bit-exactness vs the per-round driver, buffer
donation, device-side prefetch, and runner integration."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    FedTopology,
    HierFAVGConfig,
    aggregation,
    build_cohort_super_round,
    build_hier_round,
    build_super_round,
    init_cohort_state,
    init_state,
    super_round_schedule,
)
from repro.core.hierarchy import as_hierarchy, parse_fanouts
from repro.data import FederatedBatcher, SuperBatchPrefetcher, clustered_gaussians, make_partition
from repro.fed import (
    FailureSimulator,
    FederatedRunner,
    ParticipationSpec,
    RunnerConfig,
    TransportSpec,
)
from repro.models import cnn
from repro.optim import momentum, sgd

DIM = 3


def _quad(rng, n):
    centers = rng.normal(size=(n, DIM))
    sizes = rng.integers(1, 4, size=n).astype(np.float64)

    def loss_fn(params, batch, _rng):
        return 0.5 * jnp.sum((params["w"] - batch["c"]) ** 2)

    batch = {"c": jnp.asarray(centers, jnp.float32)}
    return sizes, loss_fn, batch


def _assert_trees_equal(t1, t2, what, ulp_tol=False):
    leaves1 = jax.tree_util.tree_leaves(t1)
    leaves2 = jax.tree_util.tree_leaves(t2)
    assert len(leaves1) == len(leaves2), what
    for a, b in zip(leaves1, leaves2):
        if ulp_tol:
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=3e-6, atol=2e-7, err_msg=what
            )
        else:
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=what)


def _drive_both(topo, cfg, sizes, loss_fn, batch, opt, *, intervals=2, masks=None):
    """Run `intervals` cloud intervals through (a) the per-round hier_round
    loop and (b) the fused superround, from identical initial state; return
    both final states plus both metric streams."""
    k1, k2 = cfg.kappa1, cfg.kappa2_effective
    w = jnp.asarray(sizes, jnp.float32)
    s1 = init_state(jax.random.PRNGKey(0), {"w": jnp.zeros(DIM)}, opt, topo, cfg)
    s2 = init_state(jax.random.PRNGKey(0), {"w": jnp.zeros(DIM)}, opt, topo, cfg)
    rnd = jax.jit(build_hier_round(loss_fn, opt, topo, cfg, w))
    sup = jax.jit(build_super_round(loss_fn, opt, topo, cfg, w), donate_argnums=(0,))
    per = jax.tree_util.tree_map(lambda x: jnp.stack([x] * k1), batch)
    block = jax.tree_util.tree_map(
        lambda x: jnp.stack([x] * (k2 * k1)).reshape((k2, k1) + x.shape), batch
    )
    metrics1 = {"loss": [], "grad_norm": []}
    for q in range(intervals):
        for j in range(k2):
            m = None if masks is None else jnp.asarray(masks[q * k2 + j])
            s1, mt = rnd(s1, per, jnp.int32(q * k2 + j), m)
            metrics1["loss"].append(float(mt["loss"]))
            metrics1["grad_norm"].append(float(mt["grad_norm"]))
    metrics2 = {"loss": [], "grad_norm": []}
    for q in range(intervals):
        mstack = (
            None
            if masks is None
            else jnp.asarray(np.stack(masks[q * k2 : (q + 1) * k2]))
        )
        s2, mt = sup(s2, block, mstack)
        metrics2["loss"].extend(np.asarray(mt["loss"]).tolist())
        metrics2["grad_norm"].extend(np.asarray(mt["grad_norm"]).tolist())
    return s1, s2, metrics1, metrics2


def _assert_states_equal(s1, s2, ulp_tol=False):
    """ulp_tol=False is the bit-exact claim. Configs whose aggregation graph
    XLA:CPU compiles with different FMA/reassociation choices inside the
    fused scan than in the standalone per-round executable (momentum
    sync_opt_state, depth-3 ragged) are compared at a ~1-ULP tolerance
    instead — the graphs are op-for-op identical; only codegen contraction
    differs between the two executables."""
    _assert_trees_equal(s1.params, s2.params, "params", ulp_tol)
    _assert_trees_equal(s1.opt_state, s2.opt_state, "opt_state", ulp_tol)
    assert int(s1.step) == int(s2.step)
    if s1.anchor is not None or s2.anchor is not None:
        _assert_trees_equal(s1.anchor, s2.anchor, "anchor", ulp_tol)
    if s1.residual is not None or s2.residual is not None:
        _assert_trees_equal(s1.residual, s2.residual, "residual", ulp_tol)


# ---------------------------------------------------------------------------
# bit-exactness vs the per-round loop
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kappa1,kappa2", [(2, 3), (1, 1), (3, 1), (1, 4)])
def test_superround_bitexact_two_level(rng, kappa1, kappa2):
    sizes, loss_fn, batch = _quad(rng, 6)
    topo = FedTopology(num_edges=2, clients_per_edge=3)
    cfg = HierFAVGConfig(kappa1=kappa1, kappa2=kappa2)
    s1, s2, m1, m2 = _drive_both(topo, cfg, sizes, loss_fn, batch, sgd(0.1))
    _assert_states_equal(s1, s2)
    np.testing.assert_array_equal(m1["loss"], m2["loss"])
    # grad_norm is a diagnostic side-output: XLA may reassociate its
    # sum-of-squares reduction differently inside the fused scan (state and
    # loss stay bit-exact), so allow ULP-level drift here only
    np.testing.assert_allclose(m1["grad_norm"], m2["grad_norm"], rtol=1e-6)


@pytest.mark.parametrize("backend,kernels", [("cpu", 0), ("tpu", 2)])
def test_superround_syncs_take_the_kernel_on_tpu_only(monkeypatch, backend, kernels):
    """On the CPU backend the superround's syncs stay jnp (no pallas_call in
    its jaxpr), so the bit-exactness tests here compare the jnp path; on TPU
    the edge and the cloud branch each take the group mean kernel."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    topo = FedTopology(num_edges=2, clients_per_edge=2)
    cfg = HierFAVGConfig(kappa1=2, kappa2=2)
    loss_fn = lambda p, b, _rng: 0.5 * jnp.sum((p["w"] - b["c"]) ** 2)
    state = init_state(jax.random.PRNGKey(0), {"w": jnp.zeros((8, 128))}, sgd(0.1), topo, cfg)
    block = {"c": jnp.zeros((2, 2, 4, 8, 128))}
    fn = build_super_round(loss_fn, sgd(0.1), topo, cfg, jnp.ones(4))
    assert str(jax.make_jaxpr(fn)(state, block, None)).count("pallas_call[") == kernels


def test_superround_bitexact_masks(rng):
    """Per-round survival masks == the (κ₂, N) stacked mask scan, including
    a round where a whole edge dies."""
    sizes, loss_fn, batch = _quad(rng, 6)
    topo = FedTopology(num_edges=2, clients_per_edge=3)
    cfg = HierFAVGConfig(kappa1=2, kappa2=3)
    masks = [np.ones(6, np.float32) for _ in range(6)]
    masks[1][4] = 0.0
    masks[2][:3] = 0.0  # edge 0 entirely dead at a boundary
    masks[5][0] = 0.0  # masked client at the cloud boundary
    s1, s2, m1, m2 = _drive_both(
        topo, cfg, sizes, loss_fn, batch, sgd(0.1), masks=masks
    )
    _assert_states_equal(s1, s2)
    np.testing.assert_array_equal(m1["loss"], m2["loss"])


def test_superround_bitexact_sync_opt_state(rng):
    """Momentum state averaged at boundaries (sync_opt_state) survives the
    fusion bit-exactly."""
    sizes, loss_fn, batch = _quad(rng, 6)
    topo = FedTopology(num_edges=2, clients_per_edge=3)
    cfg = HierFAVGConfig(kappa1=2, kappa2=2, sync_opt_state=True)
    s1, s2, _, _ = _drive_both(topo, cfg, sizes, loss_fn, batch, momentum(0.1, 0.9))
    _assert_states_equal(s1, s2, ulp_tol=True)


def test_superround_bitexact_ragged_multilevel(rng):
    """Depth-3 ragged tree with κ=(2,2,2): the folded level switch must
    reproduce the deepest-wins schedule across both mid and top boundaries."""
    spec = parse_fanouts("3,2,3/2,1/2")
    sizes, loss_fn, batch = _quad(rng, spec.num_clients)
    cfg = HierFAVGConfig.multi_level([2, 2, 2])
    s1, s2, m1, m2 = _drive_both(spec, cfg, sizes, loss_fn, batch, sgd(0.1))
    _assert_states_equal(s1, s2, ulp_tol=True)
    np.testing.assert_allclose(m1["loss"], m2["loss"], rtol=1e-6)
    np.testing.assert_allclose(m1["grad_norm"], m2["grad_norm"], rtol=1e-6)


@pytest.mark.parametrize("transport", ["identity/int8:64", "int8_ef:64/int8_ef:64"])
def test_superround_bitexact_transport(rng, transport):
    """Compressed uplinks (anchor re-sync, EF residual carry) are identical
    under the fused scan — including with a survival mask."""
    sizes, loss_fn, batch = _quad(rng, 6)
    topo = FedTopology(num_edges=2, clients_per_edge=3)
    cfg = HierFAVGConfig(kappa1=2, kappa2=2, transport=TransportSpec.parse(transport))
    masks = [np.ones(6, np.float32) for _ in range(4)]
    masks[1][2] = 0.0
    s1, s2, _, _ = _drive_both(
        topo, cfg, sizes, loss_fn, batch, sgd(0.1), masks=masks
    )
    _assert_states_equal(s1, s2)


def test_super_round_schedule():
    assert super_round_schedule(HierFAVGConfig(kappa1=4, kappa2=4)) == (1, 1, 1, 2)
    assert super_round_schedule(HierFAVGConfig(kappa1=2, kappa2=1)) == (2,)
    assert super_round_schedule(HierFAVGConfig.multi_level([2, 2, 2])) == (1, 2, 1, 3)


def test_superround_donation(rng):
    """donate_argnums must actually release the input FedState's buffers
    (the zero-copy claim): donated leaves are deleted after dispatch."""
    sizes, loss_fn, batch = _quad(rng, 6)
    topo = FedTopology(num_edges=2, clients_per_edge=3)
    cfg = HierFAVGConfig(kappa1=2, kappa2=2)
    opt = sgd(0.1)
    w = jnp.asarray(sizes, jnp.float32)
    sup = jax.jit(build_super_round(loss_fn, opt, topo, cfg, w), donate_argnums=(0,))
    state = init_state(jax.random.PRNGKey(0), {"w": jnp.zeros(DIM)}, opt, topo, cfg)
    donated_leaf = state.params["w"]
    block = jax.tree_util.tree_map(
        lambda x: jnp.stack([x] * 4).reshape((2, 2) + x.shape), batch
    )
    out, _ = sup(state, block, None)
    jax.block_until_ready(out.params)
    if jax.default_backend() in ("cpu", "gpu", "tpu"):
        assert donated_leaf.is_deleted(), "donated input buffer was not released"
    assert not jax.tree_util.tree_leaves(out.params)[0].is_deleted()


# ---------------------------------------------------------------------------
# prefetcher
# ---------------------------------------------------------------------------

def _make_batcher(seed=0, n=6, batch=4):
    rng = np.random.default_rng(seed)
    data = clustered_gaussians(rng, num_samples=240, num_classes=10, dim=(5,), class_sep=2.0)
    parts = make_partition("edge_iid", data.y, 2, n // 2, rng)
    return FederatedBatcher(
        {"inputs": data.x, "targets": data.y}, parts, batch_size=batch, seed=seed
    )


@pytest.mark.parametrize("use_thread", [True, False])
def test_prefetcher_matches_batcher(use_thread):
    """Prefetched blocks reproduce the exact batch sequence (reshaped to a
    (rounds, steps) leading pair) and the snapshots are restart-exact."""
    ref = _make_batcher()
    expect = [ref.next_batches(6) for _ in range(3)]

    pf = SuperBatchPrefetcher(
        _make_batcher(), rounds_per_block=2, steps_per_round=3,
        num_blocks=3, use_thread=use_thread,
    )
    snapshots = []
    with pf:
        for q in range(3):
            block, snap = pf.get()
            snapshots.append(snap)
            for key in ("inputs", "targets"):
                got = np.asarray(block[key]).reshape((-1,) + block[key].shape[2:])
                np.testing.assert_array_equal(got, expect[q][key])
        with pytest.raises(RuntimeError):
            pf.get()  # num_blocks exhausted

    # snapshot q restores a batcher positioned after block q
    resumed = _make_batcher()
    resumed.load_state_dict(snapshots[0])
    np.testing.assert_array_equal(
        resumed.next_batches(6)["inputs"], expect[1]["inputs"]
    )


def test_prefetcher_stop_is_idempotent():
    pf = SuperBatchPrefetcher(
        _make_batcher(), rounds_per_block=2, steps_per_round=2, num_blocks=8
    )
    pf.get()
    pf.stop()
    pf.stop()


# ---------------------------------------------------------------------------
# runner integration
# ---------------------------------------------------------------------------

def _mlp_runner(engine, *, num_rounds, eval_every=0, seed=0, failures=None):
    rng = np.random.default_rng(seed)
    data = clustered_gaussians(rng, num_samples=360, num_classes=10, dim=(8,), class_sep=3.0)
    parts = make_partition("edge_iid", data.y, 2, 3, rng)
    batcher = FederatedBatcher(
        {"inputs": data.x, "targets": data.y}, parts, batch_size=4, seed=seed
    )

    def apply_fn(p, x):
        return jax.nn.relu(x @ p["w1"]) @ p["w2"]

    def eval_fn(p):
        return float(cnn.accuracy(apply_fn(p, jnp.asarray(data.x)), jnp.asarray(data.y)))

    runner = FederatedRunner(
        loss_fn=cnn.make_cnn_loss_fn(apply_fn),
        optimizer=sgd(0.1),
        topology=FedTopology(num_edges=2, clients_per_edge=3),
        hier_config=HierFAVGConfig(kappa1=2, kappa2=3),
        data_sizes=batcher.data_sizes,
        batcher=batcher,
        runner_config=RunnerConfig(num_rounds=num_rounds, eval_every=eval_every, engine=engine),
        eval_fn=eval_fn if eval_every else None,
        failures=failures,
    )
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    params = {
        "w1": jax.random.normal(k1, (8, 16)) * 0.3,
        "w2": jax.random.normal(k2, (16, 10)) * 0.3,
    }
    state = runner.init(jax.random.PRNGKey(seed), params)
    return runner, state


def test_runner_engine_parity(rng):
    """engine='auto' (2 superround intervals + 1 per-round leftover) must
    reproduce the full per-round history: loss, grad_norm, steps, masks,
    eval accuracy, wire bytes."""
    out = {}
    for mode in ("auto", "per_round"):
        runner, state = _mlp_runner(
            mode, num_rounds=7, eval_every=3,
            failures=FailureSimulator(6, p_fail=0.2, p_recover=0.5, seed=3),
        )
        state = runner.run(state)
        out[mode] = (runner.records_to_dict(), np.asarray(state.params["w1"]))
    rec_a, p_a = out["auto"]
    rec_p, p_p = out["per_round"]
    np.testing.assert_array_equal(p_a, p_p)
    gn_a = rec_a.pop("grad_norm")
    gn_p = rec_p.pop("grad_norm")
    np.testing.assert_allclose(gn_a, gn_p, rtol=1e-6)  # diagnostic: ULP drift ok
    assert rec_a == rec_p
    assert rec_a["round"] == list(range(7))  # engine intervals + fallback round


def test_engine_hoists_masks_without_failure_model(monkeypatch):
    """No failure/straggler model -> the all-alive mask triple is built
    once, not by κ₂ detector polls per cloud interval. (Patched on the
    class: the stock implementation is what gets hoisted.)"""
    calls = {"n": 0}

    def counting_mask(self):
        calls["n"] += 1
        return None

    monkeypatch.setattr(FederatedRunner, "_mask_for_round", counting_mask)
    runner, state = _mlp_runner("superround", num_rounds=6)
    runner.run(state)
    assert calls["n"] == 0
    assert [r.round for r in runner.history] == list(range(6))


def test_engine_honors_overridden_mask_seam():
    """An instance-level _mask_for_round override (no failure model set)
    must still be polled per round — the hoist only covers the stock
    implementation, keeping engine/per-round parity for injected masks."""
    calls = {"n": 0}

    def injecting_mask():
        calls["n"] += 1
        m = np.ones(6, np.float32)
        m[5] = 0.0
        return m

    runner, state = _mlp_runner("superround", num_rounds=6)
    runner._mask_for_round = injecting_mask
    runner.run(state)
    assert calls["n"] == 6  # κ₂ polls per interval, 2 intervals
    assert all(r.mask_alive == 5 for r in runner.history)


def test_runner_forced_superround_requires_cloud_granularity():
    runner, state = _mlp_runner("superround", num_rounds=6, eval_every=1)
    with pytest.raises(ValueError, match="superround"):
        runner.run(state)


def test_runner_rejects_unknown_engine():
    # validated at construction (RunnerConfig.__post_init__), not first run()
    with pytest.raises(ValueError, match="engine"):
        RunnerConfig(num_rounds=3, engine="warp")


# ---------------------------------------------------------------------------
# satellites: eval reduction + wire accounting
# ---------------------------------------------------------------------------

def test_cloud_model_matches_weighted_mean(rng):
    x = {"w": jnp.asarray(rng.normal(size=(5, 4, 3)), jnp.float32)}
    w = jnp.asarray([1.0, 2.0, 0.5, 3.0, 1.5])
    mask = jnp.asarray([1.0, 0.0, 1.0, 1.0, 0.0])
    full = aggregation.weighted_mean(x, w, mask)
    single = aggregation.cloud_model(x, w, mask)
    assert single["w"].shape == (4, 3)  # no (N, ...) broadcast
    np.testing.assert_array_equal(np.asarray(full["w"][0]), np.asarray(single["w"]))
    # zero survivors: keeps client 0's params, like weighted_mean[0]
    dead = jnp.zeros(5)
    np.testing.assert_array_equal(
        np.asarray(aggregation.weighted_mean(x, w, dead)["w"][0]),
        np.asarray(aggregation.cloud_model(x, w, dead)["w"]),
    )


# ---------------------------------------------------------------------------
# sampled participation: identity-cohort parity (C == N)
# ---------------------------------------------------------------------------
# The cohort lowering must be the *same algorithm* as the fused superround
# when every client participates. Ragged trees exercise the segment-sum
# aggregation path in both builders, so the comparison there is bit-exact;
# uniform trees are the one place the graphs legitimately differ (the static
# builder takes the contiguous-reshape shortcut, traced cohort ids cannot),
# leaving ~1-ULP contraction differences — same situation as
# `_assert_states_equal`'s documented ulp_tol cases.

def _identity_cohort(spec, sizes):
    """The cohort dict for 'everyone participates': per-level segment ids
    columned from the full tree, weights in original client order."""
    if spec.depth > 1:
        table = np.stack(
            [np.asarray(spec.segments(l), np.int32) for l in range(1, spec.depth)]
        )
    else:
        table = np.zeros((0, spec.num_clients), np.int32)
    return {
        "segments": jnp.asarray(table),
        "weights": jnp.asarray(sizes, jnp.float32),
    }


def _drive_cohort_vs_super(topo, cfg, sizes, loss_fn, batch, opt, *, intervals=2):
    spec = as_hierarchy(topo)
    n = spec.num_clients
    k1, k2 = cfg.kappa1, cfg.kappa2_effective
    w = jnp.asarray(sizes, jnp.float32)
    s1 = init_state(jax.random.PRNGKey(0), {"w": jnp.zeros(DIM)}, opt, topo, cfg)
    s2 = init_cohort_state(jax.random.PRNGKey(0), {"w": jnp.zeros(DIM)}, opt, cfg, n)
    sup = jax.jit(build_super_round(loss_fn, opt, topo, cfg, w), donate_argnums=(0,))
    coh = jax.jit(
        build_cohort_super_round(loss_fn, opt, topo, cfg, cohort_size=n),
        donate_argnums=(0,),
    )
    cohort = _identity_cohort(spec, sizes)
    block = jax.tree_util.tree_map(
        lambda x: jnp.stack([x] * (k2 * k1)).reshape((k2, k1) + x.shape), batch
    )
    m1, m2 = [], []
    for _ in range(intervals):
        s1, mt1 = sup(s1, block, None)
        s2, mt2 = coh(s2, block, cohort)
        m1.append(jax.device_get(mt1))
        m2.append(jax.device_get(mt2))
    return s1, s2, m1, m2


@pytest.mark.parametrize(
    "opt_name,cfg_kw",
    [
        ("sgd", {}),
        ("momentum", {"sync_opt_state": True}),
        ("sgd", {"transport": TransportSpec.parse("int8_ef:64/int8_ef:64")}),
    ],
    ids=["sgd", "momentum_sync_opt", "int8_ef_both"],
)
def test_cohort_identity_bitexact_ragged(rng, opt_name, cfg_kw):
    """Identity cohort == fused superround, bit for bit, on a ragged tree —
    including synced momentum traces and EF residual/anchor carry."""
    spec = parse_fanouts("1,2,3/3")
    sizes, loss_fn, batch = _quad(rng, spec.num_clients)
    cfg = HierFAVGConfig(kappa1=2, kappa2=3, **cfg_kw)
    opt = momentum(0.1, 0.9) if opt_name == "momentum" else sgd(0.1)
    s1, s2, m1, m2 = _drive_cohort_vs_super(spec, cfg, sizes, loss_fn, batch, opt)
    _assert_states_equal(s1, s2)
    _assert_trees_equal(s1.rng, s2.rng, "rng")
    for a, b in zip(m1, m2):
        np.testing.assert_array_equal(a["loss"], b["loss"])
        np.testing.assert_array_equal(a["grad_norm"], b["grad_norm"])
        np.testing.assert_array_equal(a["step"], b["step"])


def test_cohort_identity_uniform_ulp(rng):
    """Uniform trees: the static builder's contiguous-reshape mean vs the
    cohort's segment-sum over traced ids — op-for-op the same reduction, so
    agreement is at the documented ~1-ULP codegen tolerance."""
    sizes, loss_fn, batch = _quad(rng, 6)
    topo = FedTopology(num_edges=2, clients_per_edge=3)
    cfg = HierFAVGConfig(kappa1=2, kappa2=2)
    s1, s2, m1, m2 = _drive_cohort_vs_super(topo, cfg, sizes, loss_fn, batch, sgd(0.1))
    _assert_states_equal(s1, s2, ulp_tol=True)
    for a, b in zip(m1, m2):
        np.testing.assert_allclose(a["loss"], b["loss"], rtol=1e-6)


# ---------------------------------------------------------------------------
# sampled participation: runner-level parity and the cohort engine
# ---------------------------------------------------------------------------

def _ragged_batcher(n, seed=0, batch=4):
    rng = np.random.default_rng(seed)
    data = clustered_gaussians(
        rng, num_samples=40 * n, num_classes=10, dim=(8,), class_sep=3.0
    )
    parts = [np.arange(i, 40 * n, n) for i in range(n)]  # round-robin shards
    batcher = FederatedBatcher(
        {"inputs": data.x, "targets": data.y}, parts, batch_size=batch, seed=seed
    )
    return batcher, data


def _ragged_runner(engine, *, participation=None, opt=None, num_rounds=8,
                   eval_every=4, checkpoint_every=0, seed=0, checkpointer=None,
                   **cfg_kw):
    """A runner on the ragged 5,4,3/3 tree (N=12); `participation` routes it
    through the cohort engine."""
    topo = parse_fanouts("5,4,3/3")
    batcher, data = _ragged_batcher(topo.num_clients, seed)

    def apply_fn(p, x):
        return jax.nn.relu(x @ p["w1"]) @ p["w2"]

    def eval_fn(p):
        return float(cnn.accuracy(apply_fn(p, jnp.asarray(data.x)), jnp.asarray(data.y)))

    runner = FederatedRunner(
        loss_fn=cnn.make_cnn_loss_fn(apply_fn),
        optimizer=opt or sgd(0.1),
        topology=topo,
        hier_config=HierFAVGConfig(
            kappa1=2, kappa2=2, participation=participation, **cfg_kw
        ),
        data_sizes=batcher.data_sizes,
        batcher=batcher,
        runner_config=RunnerConfig(
            num_rounds=num_rounds, eval_every=eval_every,
            checkpoint_every=checkpoint_every, engine=engine,
        ),
        eval_fn=eval_fn if eval_every else None,
        checkpointer=checkpointer,
    )
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    params = {
        "w1": jax.random.normal(k1, (8, 16)) * 0.3,
        "w2": jax.random.normal(k2, (16, 10)) * 0.3,
    }
    state = runner.init(jax.random.PRNGKey(seed), params)
    return runner, state


@pytest.mark.parametrize(
    "opt_name,cfg_kw",
    [
        ("sgd", {"sync_opt_state": True}),
        ("momentum", {}),
        ("sgd", {"transport": TransportSpec.parse("int8_ef:64/int8_ef:64")}),
    ],
    ids=["sgd_sync_opt", "momentum", "int8_ef_both"],
)
def test_cohort_runner_parity_full_population(opt_name, cfg_kw):
    """With C == N (round_robin: every cohort is the whole population, in
    order) the cohort engine — store swap, prefetched cohort arrays, cohort
    eval reduction included — reproduces the superround runner's history and
    final state bit-exactly on the ragged tree."""
    def build(engine, part):
        opt = momentum(0.1, 0.9) if opt_name == "momentum" else sgd(0.1)
        return _ragged_runner(engine, participation=part, opt=opt, **cfg_kw)

    base, bstate = build("superround", None)
    bstate = base.run(bstate)
    part = ParticipationSpec(cohort_size=12, sampler="round_robin")
    coh, cstate = build("auto", part)
    cstate = coh.run(cstate)

    rec_b, rec_c = base.records_to_dict(), coh.records_to_dict()
    gn_b, gn_c = rec_b.pop("grad_norm"), rec_c.pop("grad_norm")
    np.testing.assert_allclose(gn_b, gn_c, rtol=1e-6)  # diagnostic: ULP drift ok
    assert rec_b == rec_c
    _assert_states_equal(bstate, cstate)
    _assert_trees_equal(bstate.rng, cstate.rng, "rng")
    # momentum/EF leave sticky rows behind; the store must have seen them all
    if not coh.client_store.is_empty:
        assert coh.client_store.num_touched == 12


def test_cohort_runner_rejects_incompatible_setups():
    part = ParticipationSpec(cohort_size=6, sampler="uniform")
    runner, state = _ragged_runner("per_round", participation=part)
    with pytest.raises(ValueError, match="per_round"):
        runner.run(state)
    runner, state = _ragged_runner("auto", participation=part, eval_every=3)
    with pytest.raises(ValueError, match="eval_every"):
        runner.run(state)


def test_cohort_config_rejects_aggregators():
    part = ParticipationSpec(cohort_size=4)
    with pytest.raises(ValueError, match="weighted mean"):
        HierFAVGConfig(
            kappa1=2, kappa2=2, participation=part,
            aggregators=aggregation.AggregatorSpec.parse("median/weighted_mean"),
        )


def test_cohort_resume_parity(tmp_path):
    """Interrupted + resumed == straight run, bit for bit. The checkpoint
    carries paired sampler+batcher snapshots (the sampler RNG state IS the
    cohort sequence) and the full store, so the resumed run replays the
    exact same cohorts, batches, and sticky rows."""
    from repro.checkpoint import CheckpointManager

    part = ParticipationSpec(cohort_size=6, sampler="uniform", seed=1)

    def build(ckdir, num_rounds):
        return _ragged_runner(
            "auto", participation=part, opt=momentum(0.1, 0.9),
            num_rounds=num_rounds, eval_every=4, checkpoint_every=4,
            checkpointer=CheckpointManager(str(ckdir), keep=4),
        )

    ra, sa = build(tmp_path / "straight", 8)
    sa = ra.run(sa)

    rb, sb = build(tmp_path / "resumed", 4)
    rb.run(sb)  # stops (and checkpoints) at round 4

    rc, _ = build(tmp_path / "resumed", 8)
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    params = {
        "w1": jax.random.normal(k1, (8, 16)) * 0.3,
        "w2": jax.random.normal(k2, (16, 10)) * 0.3,
    }
    sc, start = rc.restore_or_init(jax.random.PRNGKey(0), params)
    assert start == 4
    sc = rc.run(sc, start_round=start)

    _assert_states_equal(sa, sc)
    _assert_trees_equal(sa.rng, sc.rng, "rng")
    # host store contents (momentum traces by original client id) match
    st_a, st_c = ra.client_store.state(), rc.client_store.state()
    _assert_trees_equal(st_a["leaves"], st_c["leaves"], "store leaves")
    np.testing.assert_array_equal(st_a["touched"], st_c["touched"])
    # the resumed history is the straight run's tail
    tail = ra.history[4:]
    assert len(rc.history) == len(tail)
    for x, y in zip(tail, rc.history):
        assert (x.round, x.step, x.loss, x.accuracy) == (y.round, y.step, y.loss, y.accuracy)
    # and both samplers continue on the identical cohort stream
    np.testing.assert_array_equal(
        ra._cohort_sampler().sample(), rc._cohort_sampler().sample()
    )


def test_wire_bytes_respects_dtype(rng):
    """bf16 models must report half the uplink bytes of fp32 (the hardcoded
    4-byte leaf assumption is gone)."""
    runner, state32 = _mlp_runner("per_round", num_rounds=1)
    params16 = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.bfloat16), {"w1": np.zeros((8, 16)), "w2": np.zeros((16, 10))}
    )
    state16 = runner.init(jax.random.PRNGKey(0), params16)
    b32 = runner._wire_bytes_per_step(state32)
    b16 = runner._wire_bytes_per_step(state16)
    assert b32 > 0
    np.testing.assert_allclose(b16, b32 / 2, rtol=1e-6)
