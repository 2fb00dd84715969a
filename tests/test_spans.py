"""The program's own names for its phases (docs/performance.md, "Profiling
a run"): ``jax.named_scope`` paths on the ops of every compiled superround,
and ``TraceAnnotation`` spans on the engine's and the prefetch worker's
threads in a traced run."""
import dataclasses
import glob
import os
import re
from collections import defaultdict

import jax
import numpy as np
import pytest

from repro.configs.paper import LM_100M
from repro.core import FedTopology, HierFAVGConfig
from repro.core.hierarchy import parse_fanouts
from repro.core.hierfavg import build_super_round, init_state
from repro.data import FederatedBatcher, clustered_gaussians, make_partition
from repro.fed import FederatedRunner, ParticipationSpec, RunnerConfig, TransportSpec
from repro.models import cnn, transformer
from repro.optim import adam, sgd

TINY_LM = dataclasses.replace(
    LM_100M, num_layers=2, d_model=64, num_heads=4, num_kv_heads=2, head_dim=16,
    d_ff=128, vocab_size=512, scan_layers=True,
)
SEQ, BATCH = 16, 2

GRAD = "hierfavg.local_step.grad"
LOCAL = {GRAD, "hierfavg.local_step.optimizer", "hierfavg.local_step.grad_norm"}

# ops that no scope covers, each for a reason, by the op_name path on them
BOOKKEEPING = {
    "XLA's own, with no metadata (loop-carry copies, rewrites)": r"^$",
    "per-client RNG keys": r"jit\(_threefry_split\)|/closed_call/slice$",
    "loop counters and conditions": r"/while/cond/|/while(/body)?/add$|/while$",
    "scan slicing and stacking": r"/while/body/dynamic_(update_)?slice$",
    "the losses' means": r"/closed_call/reduce_sum$",
    "constant tables (RoPE angles, causal mask) and buffer fills":
        r"/closed_call(/sin|/cos|/le|/clamp|/jit\(_where\)/broadcast_in_dim)?$",
}
INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%(\S+) = .*? (dot|fusion|reduce|convolution)\(")
SCOPE = re.compile(r"hierfavg\.[a-z_.0-9]+")


def _compiled_ops(topology, hier):
    """(name, op_name path) of every dot, fusion, reduce and convolution of
    the compiled superround of the tiny LM."""
    opt = adam(1e-3)
    params = transformer.init_params(jax.random.PRNGKey(0), TINY_LM)
    state = init_state(jax.random.PRNGKey(1), params, opt, topology, hier)
    n = jax.tree_util.tree_leaves(state.params)[0].shape[0]
    toks = np.zeros((hier.kappa2_effective, hier.kappa1, n, BATCH, SEQ + 1), np.int32)
    block = {"inputs": toks[..., :-1], "targets": toks[..., 1:]}
    fn = build_super_round(transformer.make_loss_fn(TINY_LM), opt, topology, hier, np.ones(n, np.float32))
    text = jax.jit(fn, donate_argnums=(0,)).lower(state, block, None).compile().as_text()
    out = []
    for line in text.splitlines():
        m = INSTRUCTION.match(line)
        if m:
            path = re.search(r'op_name="([^"]*)"', line)
            out.append((m.group(1), m.group(2), path.group(1) if path else ""))
    return out


@pytest.mark.parametrize(
    "topology,hier,scopes",
    [
        (
            FedTopology(num_edges=2, clients_per_edge=2),
            HierFAVGConfig(kappa1=2, kappa2=2),
            LOCAL | {"hierfavg.sync.edge", "hierfavg.sync.cloud"},
        ),
        (
            parse_fanouts("1,1,1,1/2,2"),
            HierFAVGConfig.multi_level(
                (1, 2, 2), transport=TransportSpec.parse("int8_ef:64/identity/int8_ef:64")
            ),
            LOCAL | {"hierfavg.sync.edge", "hierfavg.sync.l2", "hierfavg.sync.cloud", "hierfavg.codec"},
        ),
    ],
    ids=["two_level", "three_level_codec"],
)
def test_every_op_of_the_superround_carries_its_phase(topology, hier, scopes):
    ops = _compiled_ops(topology, hier)
    seen = defaultdict(int)
    stray = []
    for name, kind, path in ops:
        found = SCOPE.findall(path)
        if found:
            seen[found[-1]] += 1
            if found[-1] != GRAD:
                assert "transpose(" not in path, (name, path)  # backward ops lie in the gradient
        elif kind == "dot" or not any(re.search(p, path) for p in BOOKKEEPING.values()):
            stray.append((name, kind, path))
    assert not stray, f"ops outside every hierfavg scope: {stray[:10]}"
    assert set(seen) == scopes
    grad_dots = [path for name, kind, path in ops if kind == "dot" and GRAD in path]
    forward = [p for p in grad_dots if "transpose(" not in p]
    backward = [p for p in grad_dots if "transpose(" in p]
    # each matmul of the forward pass has two in the backward pass
    assert forward and len(backward) >= len(forward)


def _runner(participation=None):
    rng = np.random.default_rng(0)
    data = clustered_gaussians(rng, num_samples=240, num_classes=10, dim=(8,), class_sep=3.0)
    parts = make_partition("edge_iid", data.y, 2, 3, rng)
    batcher = FederatedBatcher({"inputs": data.x, "targets": data.y}, parts, batch_size=4, seed=0)

    def apply_fn(p, x):
        return jax.nn.relu(x @ p["w1"]) @ p["w2"]

    runner = FederatedRunner(
        loss_fn=cnn.make_cnn_loss_fn(apply_fn),
        optimizer=sgd(0.1),
        topology=FedTopology(num_edges=2, clients_per_edge=3),
        hier_config=HierFAVGConfig(kappa1=2, kappa2=2, participation=participation),
        data_sizes=batcher.data_sizes,
        batcher=batcher,
        runner_config=RunnerConfig(num_rounds=4, engine="superround"),
    )
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    params = {"w1": jax.random.normal(k1, (8, 16)) * 0.3, "w2": jax.random.normal(k2, (16, 10)) * 0.3}
    return runner, runner.init(jax.random.PRNGKey(0), params)


def _host_lines(trace_dir):
    """[[(span name, stats, start, end)]] of each host thread's line."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    data = ProfileData.from_file(path)
    host = next(p for p in data.planes if p.name == "/host:CPU")
    return [
        [(ev.name, dict(ev.stats), ev.start_ns, ev.end_ns) for ev in line.events if re.match(r"(fed|data)\.", ev.name)]
        for line in host.lines
    ]


@pytest.mark.parametrize(
    "participation,engine_only,worker_only",
    [
        (None, set(), set()),
        (ParticipationSpec(cohort_size=4, sampler="uniform"), {"fed.store_load", "fed.store_writeback"},
         {"data.cohort_sample"}),
    ],
    ids=["superround", "cohort"],
)
def test_a_traced_run_names_each_interval_and_its_block(tmp_path, participation, engine_only, worker_only):
    runner, state = _runner(participation)
    jax.profiler.start_trace(str(tmp_path))
    try:
        runner.run(state)  # two cloud intervals
    finally:
        jax.profiler.stop_trace()
    lines = [line for line in _host_lines(str(tmp_path)) if line]
    engine = [line for line in lines if any(n == "fed.interval" for n, *_ in line)]
    assert len(engine) == 1
    engine = engine[0]
    worker = [line for line in lines if line is not engine]
    assert len(worker) == 1
    worker = worker[0]

    def spans(line, name):
        return [(stats, s, e) for n, stats, s, e in line if n == name]

    intervals = spans(engine, "fed.interval")
    assert [st["step_num"] for st, _, _ in intervals] == [0, 1]
    for name in ("fed.prefetch_wait", "fed.dispatch") + tuple(sorted(engine_only)):
        got = spans(engine, name)
        assert [st["interval"] for st, _, _ in got] == [0, 1], name
        for (st, s, e), (_, s0, e0) in zip(got, intervals):
            assert s0 <= s <= e <= e0, name  # inside its interval's span
    assert spans(engine, "fed.flush") and len(spans(engine, "fed.run")) == 1
    assert {"ready"} <= set(spans(engine, "fed.prefetch_wait")[0][0])
    for name in ("data.block_gather", "data.block_upload") + tuple(sorted(worker_only)):
        assert [st["interval"] for st, _, _ in spans(worker, name)] == [0, 1], name
    assert all(st["bytes"] > 0 for st, _, _ in spans(worker, "data.block_upload"))
    assert not {n for n, *_ in worker} & {n for n, *_ in engine}
