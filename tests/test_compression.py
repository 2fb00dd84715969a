"""optim.compression coverage: self-describing QuantizedTree, roundtrip
error bounds, zero-block safety, composition with aggregation, and the
cross-check that the jnp quantizers and the Pallas/ref kernel quantizer
produce identical payloads on lane-aligned shapes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import aggregation
from repro.fed import transport as tp
from repro.kernels import ops, ref
from repro.optim import compression


def make_tree(rng, scale=2.0):
    return {
        "w1": jnp.asarray(rng.normal(size=(37, 129)) * scale, jnp.float32),
        "b": jnp.asarray(rng.normal(size=(513,)) * scale, jnp.float32),
        "nested": {"w2": jnp.asarray(rng.normal(size=(8, 64)) * scale, jnp.float32)},
    }


# ---------------------------------------------------------------------------
# Self-describing QuantizedTree (no `like` tree needed)
# ---------------------------------------------------------------------------

def test_dequantize_self_describing(rng):
    tree = make_tree(rng)
    q = compression.quantize_int8(tree, block=128)
    assert q.shapes is not None and q.dtypes is not None
    back = compression.dequantize_int8(q)  # no `like`
    assert jax.tree_util.tree_structure(back) == jax.tree_util.tree_structure(tree)
    for a, b in zip(jax.tree_util.tree_leaves(back), jax.tree_util.tree_leaves(tree)):
        assert a.shape == b.shape and a.dtype == b.dtype


def test_dequantize_like_still_supported_and_equal(rng):
    tree = make_tree(rng)
    q = compression.quantize_int8(tree, block=256)
    via_meta = compression.dequantize_int8(q)
    via_like = compression.dequantize_int8(q, tree)
    for a, b in zip(jax.tree_util.tree_leaves(via_meta), jax.tree_util.tree_leaves(via_like)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_dequantize_without_metadata_requires_like(rng):
    tree = make_tree(rng)
    q = compression.quantize_int8(tree, block=256)
    legacy = compression.QuantizedTree(payload=q.payload, scales=q.scales, block=q.block)
    with pytest.raises(ValueError):
        compression.dequantize_int8(legacy)
    back = compression.dequantize_int8(legacy, tree)  # old call form
    for a, b in zip(jax.tree_util.tree_leaves(back), jax.tree_util.tree_leaves(tree)):
        assert a.shape == b.shape


def test_dequantize_preserves_dtype(rng):
    tree = {"w": jnp.asarray(rng.normal(size=(16, 128)), jnp.bfloat16)}
    q = compression.quantize_int8(tree, block=128)
    back = compression.dequantize_int8(q)
    assert back["w"].dtype == jnp.bfloat16


# ---------------------------------------------------------------------------
# Roundtrip error bound / zero-block safety / wire size
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("block", [64, 128, 256])
def test_roundtrip_error_within_half_scale(rng, block):
    tree = make_tree(rng)
    q = compression.quantize_int8(tree, block=block)
    back = compression.dequantize_int8(q)
    for x, b, s in zip(
        jax.tree_util.tree_leaves(tree),
        jax.tree_util.tree_leaves(back),
        jax.tree_util.tree_leaves(q.scales),
    ):
        # per-element error bounded by its block's scale/2 (absmax grid)
        err = np.abs(np.asarray(b, np.float32) - np.asarray(x, np.float32))
        bound = np.repeat(np.asarray(s), block)[: x.size].reshape(x.shape)
        assert np.all(err <= bound * 0.5 + 1e-7)


def test_zero_block_safety():
    tree = {"w": jnp.zeros((4, 300), jnp.float32)}
    q = compression.quantize_int8(tree, block=128)
    back = compression.dequantize_int8(q)
    assert float(jnp.max(jnp.abs(back["w"]))) == 0.0
    # mixed zero/nonzero blocks: zero blocks stay exactly zero
    x = jnp.zeros((512,), jnp.float32).at[:128].set(3.0)
    q2 = compression.quantize_int8({"w": x}, block=128)
    back2 = compression.dequantize_int8(q2)["w"]
    assert float(jnp.max(jnp.abs(back2[128:]))) == 0.0
    np.testing.assert_allclose(np.asarray(back2[:128]), 3.0, rtol=1e-6)


def test_compressed_bytes_quarter_of_fp32(rng):
    tree = make_tree(rng)
    q = compression.quantize_int8(tree, block=256)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(tree))
    wire = compression.compressed_bytes(q)
    # int8 payload (padded to block) + fp32 scale per block ≈ n/4 of fp32
    assert wire < 4 * n_params * 0.3
    assert wire >= n_params  # at least 1 byte per param


# ---------------------------------------------------------------------------
# Composition with aggregation (compress → aggregate ≈ aggregate)
# ---------------------------------------------------------------------------

def test_compress_aggregate_commutes_within_bound(rng):
    n, d = 9, 400
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    w = jnp.asarray(rng.uniform(0.5, 3.0, size=n), jnp.float32)
    seg = jnp.asarray([0, 0, 0, 0, 1, 1, 2, 2, 2], jnp.int32)
    # compress each client's row (per-client blocks, transport layout)
    q, s = tp.quantize_rows(x, 128)
    decoded = tp.dequantize_rows(q, s, d, 128)
    agg_compressed = aggregation.segment_weighted_mean(decoded, w, seg, 3)
    agg_plain = aggregation.segment_weighted_mean(x, w, seg, 3)
    # aggregation is a convex combination -> error stays within the
    # per-element roundtrip bound max(scale)/2
    bound = float(jnp.max(s)) * 0.5 + 1e-6
    assert float(jnp.max(jnp.abs(agg_compressed - agg_plain))) <= bound


# ---------------------------------------------------------------------------
# Cross-check: optim.compression (jnp) vs kernels.quantize (Pallas + ref)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape,block", [((8, 1024), 256), ((4, 512), 128), ((2048,), 256)])
def test_jnp_and_pallas_quantizers_identical(rng, shape, block):
    """On lane-aligned shapes the three quantizers are the same wire format:
    identical int8 payloads AND identical scales (compared under jit, where
    the interpret-mode Pallas kernel also runs)."""
    x = jnp.asarray(rng.normal(size=shape) * 3.0, jnp.float32)

    qk, sk, _ = ops.quantize_int8(x, qblock=block)  # Pallas (interpret, jitted)
    qr, sr = jax.jit(lambda v: ref.quantize_ref(v, qblock=block)[:2])(x)  # kernel oracle
    qo_tree = jax.jit(lambda v: compression.quantize_int8(v, block=block)[:2])({"x": x})
    qo, so = qo_tree[0]["x"], qo_tree[1]["x"]  # optim jnp quantizer

    np.testing.assert_array_equal(np.asarray(qk), np.asarray(qr))
    np.testing.assert_array_equal(np.asarray(qk).reshape(-1), np.asarray(qo).reshape(-1))
    np.testing.assert_array_equal(np.asarray(sk).reshape(-1), np.asarray(sr).reshape(-1))
    np.testing.assert_array_equal(np.asarray(sk).reshape(-1), np.asarray(so).reshape(-1))


def test_transport_rows_match_pallas_stacked(rng):
    """fed.transport.quantize_rows == kernels quantize_stacked payload
    layout, bit for bit (under jit)."""
    x = jnp.asarray(rng.normal(size=(8, 1024)), jnp.float32)
    qt, st = jax.jit(lambda v: tp.quantize_rows(v, 256))(x)
    qk, sk = ops.quantize_stacked(x, qblock=256)
    np.testing.assert_array_equal(np.asarray(qt), np.asarray(qk))
    np.testing.assert_array_equal(np.asarray(st), np.asarray(sk))
