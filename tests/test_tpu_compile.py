"""Compile every ``kernels.ops`` kernel, and one lm-10m superround, for a
described TPU v5e chip — no chip attached.

The TPU compiler refuses what interpret mode accepts: unaligned blocks,
vector shape casts Mosaic cannot lower, whole-vector SMEM loads. These
compiles catch that at no chip time, at the widths ``chip_smoke.py`` runs
on the chip. The topology is described inside a fixture (never at import),
which skips where it cannot be described; the persistent compilation cache
is off around these compiles, since entries written for a described chip
cannot be read back without one.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.hierarchy import parse_fanouts
from repro.kernels import ops

# chip_smoke.py's kernel widths: 16 clients x one lm-100m-sized block (2^22
# params) for the aggregation and int8 kernels, 1024 tokens for attention
# and the scan, 4 edges x 8 clients of 128x256 maps for the megakernel
N, D, QBLOCK = 16, 1 << 22, 256
SEQ, WIDTH = 1024, 4096
EDGES, CPE, K1, B, FEAT, OUT = 4, 8, 8, 8, 128, 256
SEGMENTS = parse_fanouts("5,4,4,3/4").segments(1)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs outside the checkout
    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def compiled_kernels(monkeypatch):
    """The ops API as it runs on a TPU: kernels lowered to Mosaic. The CPU
    backend would otherwise interpret them."""
    monkeypatch.setattr(ops, "use_interpret", lambda: False)


def _kernel_cases():
    f32, i8, bf16 = jnp.float32, jnp.int8, jnp.bfloat16
    mu = (N * 2, FEAT * OUT)
    return {
        "grouped_mean": (lambda x, w: ops.grouped_mean(x, w, 4), [((N, D), f32), ((N,), f32)]),
        "segment_mean": (
            lambda x, w: ops.segment_mean(x, w, SEGMENTS, 4), [((N, D), f32), ((N,), f32)]
        ),
        "segment_dequant_mean": (
            lambda q, s, w: ops.segment_dequant_mean(q, s, w, SEGMENTS, 4),
            [((N, D), i8), ((N, D // QBLOCK), f32), ((N,), f32)],
        ),
        "flash_attention": (
            lambda q, k, v: ops.flash_attention(q, k, v, causal=True), [((24, SEQ, 64), bf16)] * 3
        ),
        "rglru_scan": (ops.rglru_scan, [((2, SEQ, WIDTH), f32)] * 2 + [((2, WIDTH), f32)]),
        "edge_interval": (
            lambda p, x, y, w: ops.edge_interval(p, x, y, w, num_edges=EDGES, feat=FEAT, lr=0.05),
            [((EDGES * CPE, FEAT * OUT), f32), ((EDGES * CPE, K1, B, FEAT), f32),
             ((EDGES * CPE, K1, B, OUT), f32), ((EDGES * CPE,), f32)],
        ),
        "edge_interval_momentum": (
            lambda p, x, y, w, m: ops.edge_interval(
                p, x, y, w, num_edges=EDGES, feat=FEAT, lr=0.05, momentum=0.9, mu=m
            ),
            [(mu, f32), ((EDGES * CPE, K1, B, FEAT), f32), ((EDGES * CPE, K1, B, OUT), f32),
             ((EDGES * CPE,), f32), (mu, f32)],
        ),
        "quantize_int8": (lambda x: ops.quantize_int8(x)[:2], [((D,), f32)]),
        "quantize_stacked": (lambda x: ops.quantize_stacked(x, qblock=QBLOCK), [((N, D), f32)]),
        "dequantize_int8": (
            lambda q, s: ops.dequantize_int8(q, s, (N, D)),
            [((N * D // QBLOCK, QBLOCK), i8), ((N * D // QBLOCK, 1), f32)],
        ),
    }


@pytest.mark.parametrize("name", sorted(_kernel_cases()))
def test_kernel_compiles_for_v5e(one_chip, compiled_kernels, name):
    fn, shapes = _kernel_cases()[name]
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip) for shape, dtype in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the Mosaic kernel, not a fallback


def test_lm10m_superround_compiles_for_v5e(one_chip):
    """One cloud interval of the lm_edge_niid scenario (lm-10m, 8 clients /
    2 edges), the program the superround engine dispatches, fits one chip."""
    from repro.core.hierfavg import build_super_round, init_state
    from repro.fed import scenarios

    spec = scenarios.get("lm_edge_niid")
    runner = spec.build()
    hier = runner.hier_config
    key = jax.random.PRNGKey(0)
    state = jax.eval_shape(
        lambda k: init_state(k, spec.init_params(k), runner.optimizer, runner.topology, hier), key
    )
    batch = runner.batcher.next_batches(hier.kappa1)
    put = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
    block = jax.tree_util.tree_map(
        lambda x: put(jax.ShapeDtypeStruct((hier.kappa2_effective,) + np.shape(x), np.asarray(x).dtype)),
        batch,
    )
    fn = build_super_round(runner.loss_fn, runner.optimizer, runner.topology, hier, runner.weights)
    compiled = jax.jit(fn, donate_argnums=(0,)).lower(
        jax.tree_util.tree_map(put, state), block, None
    ).compile()
    mem = compiled.memory_analysis()
    assert 0 < mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16 << 30
