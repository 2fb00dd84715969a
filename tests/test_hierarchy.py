"""Ragged hierarchies: HierarchySpec validation, segment aggregation laws
(property-based), the multi-level schedule, and the ragged Pallas kernel."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    HierarchySpec, HierFAVGConfig, as_hierarchy, build_train_step, init_state,
    parse_fanouts,
)
from repro.core import aggregation
from repro.core.hierfavg import FedTopology
from repro.kernels import ops, ref
from repro.optim import sgd
from repro.testing import given, settings, st


def random_spec(seed: int, depth: int, max_fan: int = 5) -> HierarchySpec:
    """A random ragged tree: bottom-up fan-outs with 1..max_fan children."""
    r = np.random.default_rng(seed)
    fanouts = []
    nodes = 1
    top_down = []
    for _ in range(depth):
        top_down.append([int(r.integers(1, max_fan + 1)) for _ in range(nodes)])
        nodes = sum(top_down[-1])
    for level in reversed(top_down):
        fanouts.append(level)
    return HierarchySpec.from_fanouts(fanouts)


def numpy_segment_mean(x, w, seg):
    """Literal per-group weighted mean oracle; dead groups keep rows."""
    out = x.astype(np.float64).copy()
    for g in np.unique(seg):
        m = seg == g
        tot = w[m].sum()
        if tot > 0:
            out[m] = (x[m] * w[m, None]).sum(axis=0) / tot
    return out


# ---------------------------------------------------------------------------
# HierarchySpec structure
# ---------------------------------------------------------------------------

def test_uniform_reduces_to_fed_topology():
    spec = HierarchySpec.uniform(5, 10)
    topo = FedTopology(num_edges=5, clients_per_edge=10)
    assert spec == as_hierarchy(topo)
    assert spec.is_paper_topology and spec.depth == 2 and spec.num_clients == 50
    np.testing.assert_array_equal(spec.segments(1), np.repeat(np.arange(5), 10))
    np.testing.assert_array_equal(spec.segments(2), np.zeros(50, np.int32))


def test_from_fanouts_ragged_three_level():
    spec = HierarchySpec.from_fanouts([[3, 5, 2], [2, 1], [2]])
    assert spec.num_clients == 10 and spec.depth == 3
    assert spec.num_nodes(1) == 3 and spec.num_nodes(2) == 2 and spec.num_nodes(3) == 1
    assert not spec.is_uniform(1) and not spec.is_paper_topology
    np.testing.assert_array_equal(spec.group_sizes(1), [3, 5, 2])
    np.testing.assert_array_equal(spec.segments(2), [0] * 8 + [1] * 2)
    assert spec.fanouts() == ((3, 5, 2), (2, 1), (2,))


def test_parse_fanouts_cli_forms():
    assert parse_fanouts("3,5,2/2,1/2") == HierarchySpec.from_fanouts([[3, 5, 2], [2, 1], [2]])
    # trailing singleton root may be omitted
    assert parse_fanouts("10,10,10,10,10/5") == HierarchySpec.uniform(5, 10)


@pytest.mark.parametrize(
    "bad",
    [
        [[2, 0], [2]],  # empty node
        [[2, 2], [3]],  # fan-out/node-count mismatch
        [[2, 2], [1, 1]],  # two roots
    ],
)
def test_invalid_fanouts_rejected(bad):
    with pytest.raises(ValueError):
        HierarchySpec.from_fanouts(bad)


def test_unsorted_parent_ids_rejected():
    with pytest.raises(ValueError):
        HierarchySpec(parents=((0, 1, 0, 1), (0, 0)))


def test_replica_groups_cover_disjointly():
    spec = random_spec(3, depth=3)
    for level in range(1, spec.depth + 1):
        groups = spec.replica_groups(level)
        flat = sorted(i for g in groups for i in g)
        assert flat == list(range(spec.num_clients))


# ---------------------------------------------------------------------------
# segment_weighted_mean laws (property-based over random ragged trees)
# ---------------------------------------------------------------------------

@given(seed=st.integers(0, 50), depth=st.integers(1, 4))
@settings(max_examples=25, deadline=None)
def test_segment_mean_equals_flat_mean_per_group(seed, depth):
    """On any random ragged tree, segment_weighted_mean at any level equals
    the per-group flat weighted mean."""
    spec = random_spec(seed, depth)
    r = np.random.default_rng(seed)
    n = spec.num_clients
    x = r.normal(size=(n, 7)).astype(np.float32)
    w = r.uniform(0.5, 3.0, size=n).astype(np.float32)
    for level in range(1, depth + 1):
        seg = spec.segments(level)
        got = aggregation.segment_weighted_mean(
            jnp.asarray(x), jnp.asarray(w), seg, spec.num_nodes(level)
        )
        np.testing.assert_allclose(
            np.asarray(got), numpy_segment_mean(x, w, seg), atol=1e-5
        )


@given(seed=st.integers(0, 30))
@settings(max_examples=15, deadline=None)
def test_segment_mean_masked_renormalizes(seed):
    """Masked survivors only: the mean renormalizes over the participating
    set; zero-survivor groups keep their members' parameters."""
    spec = random_spec(seed, depth=2)
    r = np.random.default_rng(seed + 1)
    n = spec.num_clients
    seg = spec.segments(1)
    x = r.normal(size=(n, 5)).astype(np.float32)
    w = r.uniform(1.0, 2.0, size=n).astype(np.float32)
    mask = (r.random(n) > 0.4).astype(np.float32)
    got = aggregation.segment_weighted_mean(
        jnp.asarray(x), jnp.asarray(w), seg, spec.num_nodes(1), jnp.asarray(mask)
    )
    want = numpy_segment_mean(x, w * mask, seg)
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-5)
    # explicitly: any group with zero survivors kept its rows bit-for-bit
    for g in range(spec.num_nodes(1)):
        m = seg == g
        if (w * mask)[m].sum() == 0:
            np.testing.assert_array_equal(np.asarray(got)[m], x[m])


def test_zero_survivor_group_keeps_params():
    seg = np.asarray([0, 0, 1, 1, 1], np.int32)
    x = jnp.arange(25, dtype=jnp.float32).reshape(5, 5)
    w = jnp.ones(5)
    mask = jnp.asarray([0.0, 0.0, 1.0, 1.0, 0.0])
    got = aggregation.segment_weighted_mean(x, w, seg, 2, mask)
    np.testing.assert_array_equal(np.asarray(got[:2]), np.asarray(x[:2]))
    want_g1 = np.asarray(x[2:4]).mean(axis=0)
    np.testing.assert_allclose(np.asarray(got[2:]), np.tile(want_g1, (3, 1)), atol=1e-6)


def test_segment_mean_uniform_matches_grouped_exactly():
    """Acceptance anchor: on uniform trees the segment path IS the grouped
    path (static dispatch), so equality is bitwise."""
    r = np.random.default_rng(0)
    x = jnp.asarray(r.normal(size=(12, 33)), jnp.float32)
    w = jnp.asarray(r.uniform(0.5, 2.0, size=12), jnp.float32)
    seg = np.repeat(np.arange(3, dtype=np.int32), 4)
    got = aggregation.segment_weighted_mean(x, w, seg, 3)
    want = aggregation.grouped_weighted_mean(x, w, 3)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_segment_mean_traced_ids_inside_jit():
    """The jnp segment path also works with traced ids (no static dispatch)."""
    r = np.random.default_rng(2)
    x = jnp.asarray(r.normal(size=(6, 4)), jnp.float32)
    w = jnp.ones(6)
    seg = jnp.asarray([0, 0, 0, 1, 1, 1], jnp.int32)

    @jax.jit
    def f(x, w, seg):
        return aggregation.segment_weighted_mean(x, w, seg, 2)

    np.testing.assert_allclose(
        np.asarray(f(x, w, seg)),
        numpy_segment_mean(np.asarray(x), np.asarray(w), np.asarray(seg)),
        atol=1e-6,
    )


def test_hierarchical_segment_mean_equals_flat_top_level():
    """Staged bottom-up composition == flat weighted mean at the root
    (the |D_i| weights compose) on a ragged 3-level tree."""
    spec = HierarchySpec.from_fanouts([[3, 5, 2], [2, 1], [2]])
    r = np.random.default_rng(1)
    x = jnp.asarray(r.normal(size=(10, 6)), jnp.float32)
    w = jnp.asarray(r.uniform(0.5, 3.0, size=10), jnp.float32)
    staged = aggregation.hierarchical_segment_mean(x, w, spec)
    flat = aggregation.weighted_mean(x, w)
    np.testing.assert_allclose(np.asarray(staged), np.asarray(flat), atol=1e-5)


# ---------------------------------------------------------------------------
# Multi-level schedule semantics
# ---------------------------------------------------------------------------

def test_kappa_vector_schedule_intervals():
    cfg = HierFAVGConfig.multi_level([4, 2, 3])
    assert cfg.kappa_vector == (4, 2, 3)
    assert [cfg.level_interval(l) for l in (1, 2, 3)] == [4, 8, 24]
    assert cfg.cloud_interval == 24 and cfg.kappa2_effective == 6
    assert bool(cfg.is_level_step(2, 8)) and not bool(cfg.is_level_step(3, 8))


def test_config_level_mismatch_rejected():
    spec = HierarchySpec.from_fanouts([[2, 2], [2]])
    with pytest.raises(ValueError):
        build_train_step(
            lambda p, b, r: 0.0, sgd(0.1), spec, HierFAVGConfig.multi_level([2, 2, 2]),
            jnp.ones(4),
        )


def test_three_level_train_step_matches_numpy_schedule():
    """Quadratic clients on a ragged 3-level tree: the fused train step
    reproduces the literal per-level numpy schedule."""
    spec = HierarchySpec.from_fanouts([[3, 5, 2], [2, 1], [2]])
    cfg = HierFAVGConfig.multi_level([2, 2, 2])
    r = np.random.default_rng(0)
    centers = r.normal(size=(10, 4))
    sizes = r.integers(1, 5, size=10).astype(np.float64)

    def loss_fn(p, b, _):
        return 0.5 * jnp.sum((p["w"] - b["c"]) ** 2)

    opt = sgd(0.1)
    state = init_state(jax.random.PRNGKey(0), {"w": jnp.zeros(4)}, opt, spec, cfg)
    step = jax.jit(build_train_step(
        loss_fn, opt, spec, cfg, jnp.asarray(sizes, jnp.float32)
    ))
    batch = {"c": jnp.asarray(centers, jnp.float32)}

    w = np.zeros((10, 4))
    for k in range(1, 17):
        w = w - 0.1 * (w - centers)
        for level in (3, 2, 1):
            if k % cfg.level_interval(level) == 0:
                for t in range(1, level + 1):
                    w = numpy_segment_mean(w, sizes, spec.segments(t))
                break
    for _ in range(16):
        state, _ = step(state, batch)
    np.testing.assert_allclose(np.asarray(state.params["w"]), w, atol=1e-5)


def test_two_level_vector_matches_scalar_config():
    """multi_level([k1, k2]) is the seed schedule bit-for-bit."""
    topo = FedTopology(num_edges=2, clients_per_edge=3)
    r = np.random.default_rng(0)
    centers = r.normal(size=(6, 3))
    sizes = r.integers(1, 4, size=6).astype(np.float64)

    def loss_fn(p, b, _):
        return 0.5 * jnp.sum((p["w"] - b["c"]) ** 2)

    batch = {"c": jnp.asarray(centers, jnp.float32)}
    outs = []
    for cfg in (HierFAVGConfig(kappa1=2, kappa2=3), HierFAVGConfig.multi_level([2, 3])):
        opt = sgd(0.1)
        s = init_state(jax.random.PRNGKey(0), {"w": jnp.zeros(3)}, opt, topo, cfg)
        step = jax.jit(build_train_step(loss_fn, opt, topo, cfg, jnp.asarray(sizes, jnp.float32)))
        for _ in range(13):
            s, _ = step(s, batch)
        outs.append(np.asarray(s.params["w"]))
    np.testing.assert_array_equal(outs[0], outs[1])


# ---------------------------------------------------------------------------
# Ragged Pallas kernel (interpret mode) vs jnp reference
# ---------------------------------------------------------------------------

# Bit-exactness is a compiled-vs-compiled claim: the interpret-mode kernel
# runs under jit, so the reference must too (XLA fuses eager-mode
# intermediates differently, which perturbs the last ulp).
_ref_segment_mean = jax.jit(
    ref.segment_mean_ref, static_argnames=("num_segments", "block_d")
)


@pytest.mark.parametrize("d", [64, 300, 513])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_segment_kernel_bitexact_f32(rng, d, seed):
    """Acceptance: the ragged kernel matches the jnp reference bit-for-bit
    in f32 (same one-hot matmul formulation and tiling)."""
    spec = random_spec(seed, depth=2)
    n = spec.num_clients
    seg = spec.segments(1)
    g = spec.num_nodes(1)
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    w = jnp.asarray(rng.uniform(0.5, 4.0, size=n), jnp.float32)
    got = ops.segment_mean(x, w, seg, g, block_d=128)
    want = _ref_segment_mean(x, w, seg, num_segments=g, block_d=128)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_segment_kernel_matches_numpy_on_three_level_tree(rng):
    """Ragged 3-level tree, every level, vs the literal numpy oracle."""
    spec = HierarchySpec.from_fanouts([[6, 4, 5, 3, 2], [3, 2], [2]])
    n = spec.num_clients
    x = jnp.asarray(rng.normal(size=(n, 200)), jnp.float32)
    w = jnp.asarray(rng.uniform(0.5, 2.0, size=n), jnp.float32)
    for level in range(1, spec.depth + 1):
        seg = spec.segments(level)
        got = ops.segment_mean(x, w, seg, spec.num_nodes(level), block_d=128)
        np.testing.assert_allclose(
            np.asarray(got),
            numpy_segment_mean(np.asarray(x), np.asarray(w), seg),
            atol=1e-5,
        )


def test_segment_kernel_masked_dead_group(rng):
    spec = HierarchySpec.from_fanouts([[3, 4, 2], [3]])
    n = spec.num_clients
    seg = spec.segments(1)
    x = jnp.asarray(rng.normal(size=(n, 256)), jnp.float32)
    w = jnp.asarray(rng.uniform(1, 2, size=n), jnp.float32).at[:3].set(0.0)
    got = ops.segment_mean(x, w, seg, 3, block_d=128)
    want = _ref_segment_mean(x, w, seg, num_segments=3, block_d=128)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(got[:3]), np.asarray(x[:3]))  # dead edge


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_segment_kernel_dtypes(rng, dtype):
    spec = random_spec(7, depth=2)
    n = spec.num_clients
    seg = spec.segments(1)
    g = spec.num_nodes(1)
    x = jnp.asarray(rng.normal(size=(n, 384)), dtype)
    w = jnp.asarray(rng.uniform(0.5, 4.0, size=n), jnp.float32)
    got = ops.segment_mean(x, w, seg, g, block_d=128)
    want = _ref_segment_mean(x, w, seg, num_segments=g, block_d=128)
    tol = 0 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=tol
    )


# ---------------------------------------------------------------------------
# Correlated subtree outages
# ---------------------------------------------------------------------------

def test_subtree_outage_masks_whole_edges():
    from repro.fed import SubtreeOutageSimulator

    spec = HierarchySpec.from_fanouts([[3, 5, 2], [2, 1], [2]])
    sim = SubtreeOutageSimulator(spec, tier=1, p_fail=0.6, p_recover=0.3, seed=0)
    seg = spec.segments(1)
    saw_outage = False
    for _ in range(20):
        mask = sim.step()
        assert mask.shape == (spec.num_clients,)
        # a mask is constant within every edge (correlated failure unit)
        for g in range(spec.num_nodes(1)):
            assert len(np.unique(mask[seg == g])) == 1
        saw_outage = saw_outage or mask.min() == 0.0
    assert saw_outage
