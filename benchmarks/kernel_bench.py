"""Kernel micro-benches: correctness vs oracle + jnp-reference timing.

The kernels run as the platform dictates (interpreted on CPU, Mosaic on
TPU). CPU wall-times are only indicative (the kernels TARGET TPU); what this
bench pins down is (a) allclose vs oracle at bench shapes and (b) the
HBM-traffic model of each kernel vs its reference (the structural win).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import timed
from repro.core.hierarchy import parse_fanouts
from repro.kernels import ops, ref


def main(csv=True):
    rng = np.random.default_rng(0)
    checks = {}

    # hier_aggregate: N=32 clients, 1M-param block
    x = jnp.asarray(rng.normal(size=(32, 1 << 20)), jnp.float32)
    w = jnp.asarray(rng.uniform(1, 2, size=32), jnp.float32)
    t_ref, out_ref = timed(lambda: ref.grouped_mean_ref(x, w, 8), iters=3)
    ok = checks["hier_aggregate"] = bool(np.allclose(ops.grouped_mean(x, w, (4,)), out_ref, atol=1e-5))
    # traffic: kernel = 2 passes (read+write) vs ref ~4 passes
    print(f"kernel_hier_aggregate,ref_us={t_ref*1e6:.0f},allclose={ok},hbm_passes=2_vs_4")

    # ragged vs uniform kernel at EQUAL total parameters (same (N, D) stack,
    # same 8 groups; ragged fan-out 8,6,6,4,3,2,2,1). Acceptance: the
    # segment-boundary encoding costs < 1.25x the uniform reshape path.
    n, d, bd = 32, 1 << 16, 8192
    spec = parse_fanouts("8,6,6,4,3,2,2,1/8")
    seg = spec.segments(1)
    xs = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    t_uni, _ = timed(lambda: ops.grouped_mean(xs, w, (n // 8,)), iters=5)
    t_rag, out_rag = timed(lambda: ops.segment_mean(xs, w, seg, 8, block_d=bd), iters=5)
    ok = checks["hier_aggregate_ragged"] = bool(
        np.allclose(out_rag, ref.segment_mean_ref(xs, w, seg, 8, block_d=bd), atol=1e-5)
    )
    ratio = t_rag / t_uni
    print(
        f"kernel_hier_aggregate_ragged,uniform_us={t_uni*1e6:.0f},"
        f"ragged_us={t_rag*1e6:.0f},ratio={ratio:.2f},within_1.25x={ratio <= 1.25},"
        f"allclose={ok}"
    )

    # flash attention: 1k seq
    q = jnp.asarray(rng.normal(size=(4, 1024, 64)), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=(4, 1024, 64)), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=(4, 1024, 64)), jnp.bfloat16)
    t_ref, out_ref = timed(lambda: ref.attention_ref(q, k, v, causal=True), iters=3)
    got = ops.flash_attention(q, k, v, causal=True)
    ok = checks["flash_attention"] = bool(
        np.allclose(np.asarray(got, np.float32), np.asarray(out_ref, np.float32), atol=5e-2)
    )
    s, d = 1024, 64
    naive_hbm = s * s * 4  # score tensor per head-pair
    flash_hbm = 3 * s * d * 2 + s * d * 2
    print(f"kernel_flash_attention,ref_us={t_ref*1e6:.0f},allclose={ok},hbm_ratio={naive_hbm/flash_hbm:.1f}x")

    # rglru scan: 8k seq
    a = jnp.asarray(rng.uniform(0.9, 0.999, size=(2, 8192, 256)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(2, 8192, 256)) * 0.1, jnp.float32)
    h0 = jnp.zeros((2, 256), jnp.float32)
    t_ref, (h_ref, _) = timed(lambda: ref.rglru_scan_ref(a, b, h0), iters=3)
    h_k, _ = ops.rglru_scan(a, b, h0)
    ok = checks["rglru_scan"] = bool(np.allclose(h_k, h_ref, atol=1e-4))
    print(f"kernel_rglru_scan,ref_us={t_ref*1e6:.0f},allclose={ok},hbm_passes=1_vs_logS")

    # quantize: 8M params
    x = jnp.asarray(rng.normal(size=(8 << 20,)), jnp.float32)
    t_ref, _ = timed(lambda: ref.quantize_ref(x), iters=3)
    qk, sk, shp = ops.quantize_int8(x)
    qr, sr, _ = ref.quantize_ref(x)
    ok = checks["quantize"] = bool(np.array_equal(np.asarray(qk), np.asarray(qr)))
    print(f"kernel_quantize,ref_us={t_ref*1e6:.0f},payload_match={ok},wire_ratio=3.9x_smaller")

    # fused dequantize-aggregate: int8 link payloads reduced in one HBM pass
    # (vs dequantize-to-f32 then aggregate = 1 int8 + 2 f32 passes)
    n, d, bd = 32, 1 << 16, 8192
    dq_seg = parse_fanouts("8,6,6,4,3,2,2,1/8").segments(1)
    dq_w = jnp.asarray(rng.uniform(1, 2, size=n), jnp.float32)
    deltas = jnp.asarray(rng.normal(size=(n, d)) * 0.05, jnp.float32)
    q, s = ops.quantize_stacked(deltas, qblock=256)
    ref_jit = jax.jit(functools.partial(
        ref.segment_dequant_mean_ref, num_segments=8, block_d=bd))
    t_ref, out_ref = timed(lambda: ref_jit(q, s, dq_w, dq_seg), iters=3)
    got = ops.segment_dequant_mean(q, s, dq_w, dq_seg, 8, block_d=bd)
    bitexact = checks["dequant_aggregate"] = bool(np.array_equal(np.asarray(got), np.asarray(out_ref)))
    int8_bytes = q.size + 4 * s.size
    f32_bytes = 4 * deltas.size
    print(
        f"kernel_dequant_aggregate,ref_us={t_ref*1e6:.0f},bitexact={bitexact},"
        f"payload_bytes_ratio={f32_bytes/int8_bytes:.2f}x_smaller,hbm_passes=1_vs_3"
    )

    # fused edge-interval megakernel: kappa1 SGD steps + edge mean in one
    # pass, E=4 edges x 8 clients, P=8192 (64x128), b=2. ULP tolerance vs
    # the jnp oracle (shared step body; contraction lowering differs inside
    # the Pallas interpreter — documented in kernels/ref.py)
    ne, cpe, k1, b, feat, outd = 4, 8, 4, 2, 64, 128
    n = ne * cpe
    mp = jnp.asarray(rng.normal(size=(n, feat * outd)) * 0.05, jnp.float32)
    mx = jnp.asarray(rng.normal(size=(n, k1, b, feat)), jnp.float32)
    my = jnp.asarray(rng.normal(size=(n, k1, b, outd)), jnp.float32)
    mw = jnp.asarray(rng.uniform(1, 2, size=(n,)), jnp.float32)
    t_ref, (p_ref, l_ref, _) = timed(
        lambda: ref.edge_interval_ref(mp, mx, my, mw, ne, feat=feat, lr=0.05), iters=3
    )
    p_k, l_k, _ = ops.edge_interval(mp, mx, my, mw, num_edges=ne, feat=feat, lr=0.05)
    ok = checks["edge_interval_megakernel"] = bool(
        np.allclose(np.asarray(p_k), np.asarray(p_ref), rtol=3e-6, atol=5e-7)
        and np.allclose(np.asarray(l_k), np.asarray(l_ref), rtol=3e-6, atol=5e-7)
    )
    # traffic: params+momentum cross HBM once per interval vs once per step
    print(
        f"kernel_edge_interval,ref_us={t_ref*1e6:.0f},allclose={ok},"
        f"hbm_param_passes=2_vs_{2 * k1}"
    )

    bad = sorted(k for k, v in checks.items() if not v)
    if bad:
        # a kernel drifting off its oracle must fail the build (CI smoke step)
        raise RuntimeError(f"kernel checks failed vs oracle: {bad}")
    return checks


if __name__ == "__main__":
    main()
