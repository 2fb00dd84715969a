"""Per-kernel shape/dtype sweeps: pallas(interpret=True) vs ref.py oracle,
and the platform rule that picks interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


@pytest.mark.parametrize("backend,interpret", [("cpu", True), ("tpu", False), ("gpu", None)])
def test_interpret_mode_follows_the_platform(monkeypatch, backend, interpret):
    """Interpreted on CPU, compiled on TPU, refused anywhere else: nothing
    else can select interpret mode."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if interpret is None:
        with pytest.raises(RuntimeError, match=backend):
            ops.use_interpret()
    else:
        assert ops.use_interpret() is interpret
    assert not hasattr(ops, "set_interpret")


@pytest.mark.parametrize("n,groups", [(4, 2), (8, 4), (16, 2), (32, 8)])
@pytest.mark.parametrize("d", [64, 300, 513])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_grouped_mean_sweep(rng, n, groups, d, dtype):
    x = jnp.asarray(rng.normal(size=(n, d)), dtype)
    w = jnp.asarray(rng.uniform(0.5, 4.0, size=n), jnp.float32)
    got = ops.grouped_mean(x, w, groups, block_d=128)
    want = ref.grouped_mean_ref(x, w, groups)
    tol = 1e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=tol
    )


def test_grouped_mean_masked_and_dead_group(rng):
    x = jnp.asarray(rng.normal(size=(8, 256)), jnp.float32)
    w = jnp.asarray(rng.uniform(1, 2, size=8), jnp.float32).at[:4].set(0.0)
    got = ops.grouped_mean(x, w, 2)
    want = ref.grouped_mean_ref(x, w, 2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    np.testing.assert_allclose(np.asarray(got[:4]), np.asarray(x[:4]))  # dead group


@pytest.mark.parametrize("s,window", [(128, 0), (200, 0), (256, 33), (120, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(rng, s, window, dtype):
    bh, d = 3, 64
    q = jnp.asarray(rng.normal(size=(bh, s, d)), dtype)
    k = jnp.asarray(rng.normal(size=(bh, s, d)), dtype)
    v = jnp.asarray(rng.normal(size=(bh, s, d)), dtype)
    got = ops.flash_attention(q, k, v, causal=True, window=window, block_q=64, block_k=64)
    want = ref.attention_ref(q, k, v, causal=True, window=window)
    tol = 3e-4 if dtype == jnp.float32 else 4e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=tol
    )


@pytest.mark.parametrize("s,d", [(33, 96), (128, 128), (64, 200)])
def test_rglru_scan_sweep(rng, s, d):
    B = 2
    a = jnp.asarray(rng.uniform(0.7, 0.999, size=(B, s, d)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(B, s, d)) * 0.1, jnp.float32)
    h0 = jnp.asarray(rng.normal(size=(B, d)), jnp.float32)
    h, hT = ops.rglru_scan(a, b, h0)
    hr, hTr = ref.rglru_scan_ref(a, b, h0)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr), atol=1e-5)
    np.testing.assert_allclose(np.asarray(hT), np.asarray(hTr), atol=1e-5)


@pytest.mark.parametrize("shape", [(37, 129), (8, 2048), (1000,), (3, 5, 7)])
@pytest.mark.parametrize("qblock", [128, 256])
def test_quantize_roundtrip_sweep(rng, shape, qblock):
    x = jnp.asarray(rng.normal(size=shape) * 3.0, jnp.float32)
    q, s, shp = ops.quantize_int8(x, qblock=qblock)
    qr, sr, _ = ref.quantize_ref(x, qblock=qblock)
    np.testing.assert_array_equal(np.asarray(q), np.asarray(qr))
    np.testing.assert_allclose(np.asarray(s), np.asarray(sr), rtol=1e-6)
    back = ops.dequantize_int8(q, s, shp)
    # int8 absmax quantization: error bounded by scale/2 per element
    scale_max = float(jnp.max(s))
    assert float(jnp.max(jnp.abs(back - x))) <= scale_max * 0.5 + 1e-6


def test_quantize_zero_block(rng):
    x = jnp.zeros((4, 256), jnp.float32)
    q, s, shp = ops.quantize_int8(x)
    assert float(jnp.max(jnp.abs(ops.dequantize_int8(q, s, shp)))) == 0.0


SEGMENTS = {
    8: [0, 0, 0, 1, 1, 2, 2, 3],
    12: [0, 0, 0, 0, 1, 1, 2, 2, 2, 2, 3, 3],
}


@pytest.mark.parametrize("n", [8, 12])
@pytest.mark.parametrize("d,qblock,block_d", [(1024, 256, 512), (768, 128, 256), (512, 128, 512)])
def test_segment_dequant_mean_bitexact_vs_ref(rng, n, d, qblock, block_d):
    """The fused dequantize-aggregate kernel is BIT-exact against the jnp
    oracle (both jitted; the oracle mirrors the kernel's tiling)."""
    import functools

    x = jnp.asarray(rng.normal(size=(n, d)) * 0.1, jnp.float32)
    w = jnp.asarray(rng.uniform(0.5, 3.0, size=n), jnp.float32)
    seg = jnp.asarray(SEGMENTS[n], jnp.int32)
    q, s = ops.quantize_stacked(x, qblock=qblock)
    got = ops.segment_dequant_mean(q, s, w, seg, 4, block_d=block_d)
    want = jax.jit(
        functools.partial(ref.segment_dequant_mean_ref, num_segments=4, block_d=block_d)
    )(q, s, w, seg)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_segment_dequant_mean_equals_decode_then_aggregate(rng):
    """Fusion changes bytes moved, not math: fused == dequantize (kernel)
    then segment_mean (kernel) on the f32 intermediate."""
    n, d, qblock = 8, 1024, 256
    x = jnp.asarray(rng.normal(size=(n, d)) * 0.2, jnp.float32)
    w = jnp.asarray(rng.uniform(0.5, 2.0, size=n), jnp.float32)
    seg = jnp.asarray(SEGMENTS[n], jnp.int32)
    q, s = ops.quantize_stacked(x, qblock=qblock)
    fused = ops.segment_dequant_mean(q, s, w, seg, 4, block_d=512)
    rows = q.shape[1] // qblock * n
    decoded = ops.dequantize_int8(
        q.reshape(rows, qblock), s.reshape(rows, 1), (n, d)
    )
    staged = ops.segment_mean(decoded, w, seg, 4, block_d=512)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(staged), atol=1e-6)


def test_segment_dequant_mean_dead_segment_keeps_rows(rng):
    n, d = 8, 512
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    w = jnp.asarray(rng.uniform(1.0, 2.0, size=n), jnp.float32).at[3:5].set(0.0)
    seg = jnp.asarray(SEGMENTS[n], jnp.int32)  # segment 1 = rows 3..4, now dead
    q, s = ops.quantize_stacked(x, qblock=128)
    got = ops.segment_dequant_mean(q, s, w, seg, 4, block_d=512)
    decoded = np.asarray(
        q.astype(jnp.float32).reshape(n, d // 128, 128) * s.reshape(n, d // 128)[..., None]
    ).reshape(n, d)
    np.testing.assert_allclose(np.asarray(got)[3:5], decoded[3:5], atol=1e-7)


def test_segment_dequant_mean_validates_shapes(rng):
    q = jnp.zeros((4, 512), jnp.int8)
    s = jnp.zeros((4, 2), jnp.float32)
    w = jnp.ones((4,), jnp.float32)
    with pytest.raises(ValueError):  # block_d not a multiple of qblock
        ops.segment_dequant_mean(q, s, w, [0, 0, 1, 1], 2, block_d=384)
    with pytest.raises(ValueError):  # bad segment vector
        ops.segment_dequant_mean(q, s, w, [0, 0, 1], 2)
