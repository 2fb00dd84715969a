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
import re

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
        "grouped_mean": (lambda x, w: ops.grouped_mean(x, w, (N // 4,)), [((N, D), f32), ((N,), f32)]),
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


def _lm10m_superround(one_chip):
    """The lm_edge_niid superround (lm-10m, 8 clients / 2 edges), compiled
    for one described v5e chip, and its parameter leaves' count."""
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
    return compiled, len(jax.tree_util.tree_leaves(state.params))


def test_lm10m_superround_compiles_for_v5e(one_chip):
    """One cloud interval of the lm_edge_niid scenario (lm-10m, 8 clients /
    2 edges), the program the superround engine dispatches, fits one chip."""
    compiled, _ = _lm10m_superround(one_chip)
    mem = compiled.memory_analysis()
    assert 0 < mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16 << 30


def _group_mean_calls(hlo: str) -> dict:
    """The ``hier_group_mean`` Mosaic calls of an HLO text, counted by the
    sync scope in their op_name (None: in no sync scope)."""
    counts: dict = {}
    for line in hlo.splitlines():
        if "tpu_custom_call" not in line or "hier_group_mean" not in line:
            continue
        scope = re.search(r'op_name="[^"]*(hierfavg\.sync\.\w+)', line)
        key = scope.group(1) if scope else None
        counts[key] = counts.get(key, 0) + 1
    return counts


def test_lm10m_superround_syncs_run_the_group_mean_kernel(one_chip, monkeypatch):
    """On TPU each sync branch of the superround holds one in-place
    ``hier_group_mean`` kernel per parameter leaf, named under its sync's
    scope (the scope the benchmark's sync metrics read)."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # what the dispatch asks
    compiled, leaves = _lm10m_superround(one_chip)
    calls = _group_mean_calls(compiled.as_text())
    assert calls == {"hierfavg.sync.edge": leaves, "hierfavg.sync.cloud": leaves}


@pytest.mark.parametrize("fanouts,per_sync", [("5,4,4,3/4", 0), ("4,4,4,4/4", 1)])
def test_ragged_tree_lowers_without_the_group_mean_kernel(one_chip, monkeypatch, fanouts, per_sync):
    """A ragged tree keeps the jnp stages on TPU too; the uniform tree of
    the same size takes the kernel in each of its two syncs."""
    from repro.core import HierFAVGConfig
    from repro.core.hierfavg import build_super_round, init_state
    from repro.optim import sgd

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    topo = parse_fanouts(fanouts)
    cfg = HierFAVGConfig(kappa1=2, kappa2=2)
    n = topo.num_clients
    loss_fn = lambda p, b, _rng: 0.5 * jnp.sum((p["w"] - b["c"]) ** 2)
    state = jax.eval_shape(
        lambda k: init_state(k, {"w": jnp.zeros((8, 128))}, sgd(0.1), topo, cfg), jax.random.PRNGKey(0)
    )
    put = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip)
    block = {"c": jax.ShapeDtypeStruct((2, 2, n, 8, 128), jnp.float32, sharding=one_chip)}
    fn = build_super_round(loss_fn, sgd(0.1), topo, cfg, jnp.ones(n))
    text = jax.jit(fn).lower(jax.tree_util.tree_map(put, state), block, None).as_text()
    assert text.count("hier_group_mean") == 2 * per_sync
