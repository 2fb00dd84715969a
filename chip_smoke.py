"""Smoke run of HierFAVG training on a TPU, in one process.

    python chip_smoke.py             # one chip: every Pallas kernel + the lm-100m main path
    python chip_smoke.py --chips 4   # four chips: the client-sharded paths only

One chip: (a) every kernel of ``repro.kernels.ops`` runs compiled (Mosaic) at
real widths and is checked against its ``repro.kernels.ref`` oracle;
(b) the ``lm_edge_niid`` scenario trains the full-width ``lm-100m`` LM
(4 clients, 2 edges) through ExperimentSpec -> FederatedRunner ->
SuperRoundEngine, and the loss must fall.

Four chips (``--chips 4``): ``lm-100m`` at 16 clients sharded over a
``clients:4`` mesh (a state one chip cannot hold), then the sharded
superround against the one-device engine on ``lm-10m`` at 8 clients, and
the sharded cohort engine against the one-device one on ``n1m_cohort4096``.

Without a TPU it exits non-zero before doing anything; it never falls back
to the CPU. The readings it prints (compile seconds, seconds per cloud
interval, peak device bytes) are smoke readings, not benchmark metrics.
The last line of standard output is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SEED = 0
# (8 rounds at kappas 4,2) = 4 cloud intervals of 8 local steps each
LM_100M = [
    "model.arch=lm-100m", "data.vocab=32768", "data.seq_len=128",
    "schedule.kappas=4,2", "run.num_rounds=8", "run.engine=auto", f"run.seed={SEED}",
]
# the tolerances of tests/test_sharded.py: one ULP-scale psum reassociation
# per cloud boundary
PARAM_TOL = dict(rtol=3e-6, atol=2e-7)
LOSS_TOL = dict(rtol=1e-5)


def log(msg: str) -> None:
    print(msg, flush=True)


def require(ok, msg: str) -> None:
    """A check that holds under ``python -O`` too."""
    if not ok:
        raise AssertionError(msg)


class CompileWatch:
    """Counts backend compile seconds and persistent-cache hits/misses
    through ``jax.monitoring``."""

    def __init__(self, jax):
        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


# ---------------------------------------------------------------------------
# (a) kernels
# ---------------------------------------------------------------------------


def kernel_phase(jax, jnp, *, d: int = 1 << 22, seq: int = 1024, width: int = 4096) -> None:
    """Every ops kernel at real widths: 16 clients x ``d`` (one lm-100m-sized
    block) for the aggregation and int8 kernels, ``seq`` tokens for
    attention and the scan, ``width`` scan channels."""
    from repro.core.hierarchy import parse_fanouts
    from repro.kernels import ops, ref

    rng = np.random.default_rng(SEED)
    n = 16
    seg = parse_fanouts("5,4,4,3/4").segments(1)
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    w = jnp.asarray(rng.uniform(0.5, 2.0, size=n), jnp.float32)
    q, s = jax.jit(lambda v: ops.quantize_stacked(v, qblock=256))(x * 0.05)
    a = jnp.asarray(rng.uniform(0.9, 0.999, size=(2, seq, width)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(2, seq, width)) * 0.1, jnp.float32)
    h0 = jnp.asarray(rng.normal(size=(2, width)), jnp.float32)
    qkv = [jnp.asarray(rng.normal(size=(24, seq, 64)), jnp.bfloat16) for _ in range(3)]
    ne, cpe, k1, bs, feat, out = 4, 8, 8, 8, 128, 256
    mp = jnp.asarray(rng.normal(size=(ne * cpe, feat * out)) * 0.05, jnp.float32)
    mx = jnp.asarray(rng.normal(size=(ne * cpe, k1, bs, feat)), jnp.float32)
    my = jnp.asarray(rng.normal(size=(ne * cpe, k1, bs, out)), jnp.float32)
    mw = jnp.asarray(rng.uniform(1, 2, size=ne * cpe), jnp.float32)
    mmu = jnp.asarray(rng.normal(size=(ne * cpe, feat * out)) * 0.01, jnp.float32)
    flat = x[0]

    # (name, kernel fn, args, oracle fn, tolerance); oracles in full f32
    cases = [
        ("grouped_mean", lambda x, w: ops.grouped_mean(x, w, 4), (x, w),
         lambda x, w: ref.grouped_mean_ref(x, w, 4), dict(rtol=1e-5, atol=1e-5)),
        ("segment_mean", lambda x, w: ops.segment_mean(x, w, seg, 4), (x, w),
         lambda x, w: ref.segment_mean_ref(x, w, seg, 4, block_d=d), dict(rtol=1e-5, atol=1e-5)),
        ("segment_dequant_mean", lambda q, s, w: ops.segment_dequant_mean(q, s, w, seg, 4), (q, s, w),
         lambda q, s, w: ref.segment_dequant_mean_ref(q, s, w, seg, 4, block_d=d),
         dict(rtol=1e-5, atol=1e-6)),
        ("flash_attention", lambda q, k, v: ops.flash_attention(q, k, v, causal=True), tuple(qkv),
         lambda q, k, v: ref.attention_ref(q, k, v, causal=True), dict(atol=4e-2)),
        ("rglru_scan", ops.rglru_scan, (a, b, h0), ref.rglru_scan_ref, dict(rtol=1e-4, atol=1e-4)),
        ("edge_interval",
         lambda p, x, y, w, mu: ops.edge_interval(p, x, y, w, num_edges=ne, feat=feat, lr=0.05,
                                                  momentum=0.9, mu=mu),
         (mp, mx, my, mw, mmu),
         lambda p, x, y, w, mu: ref.edge_interval_ref(p, x, y, w, ne, feat=feat, lr=0.05,
                                                      momentum=0.9, mu=mu),
         dict(rtol=1e-4, atol=1e-5)),
        ("dequantize_int8", lambda q, s: ops.dequantize_int8(q.reshape(-1, 256), s.reshape(-1, 1), (n, d)),
         (q, s), lambda q, s: ref.dequantize_ref(q.reshape(-1, 256), s.reshape(-1, 1), (n, d)),
         dict(rtol=1e-6, atol=0)),
    ]
    for name, fn, args, oracle, tol in cases:
        got = _compiled_kernel(jax, name, fn, args)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(oracle)(*args)
        errs = []
        for g, w_ in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
            g, w_ = np.asarray(g, np.float32), np.asarray(w_, np.float32)
            np.testing.assert_allclose(g, w_, err_msg=name, **tol)
            errs.append(float(np.max(np.abs(g - w_))))
        log(f"kernel {name}: ok, max abs err vs oracle {max(errs):.3g}")

    # int8 payloads: a division on either side of a .5 rounding boundary may
    # land one code apart, so allow |dq| <= 1 on a vanishing share of codes
    for name, fn, args, oracle in [
        ("quantize_int8", lambda v: ops.quantize_int8(v)[:2], (flat,), lambda v: ref.quantize_ref(v)[:2]),
        ("quantize_stacked", lambda v: ops.quantize_stacked(v, qblock=256), (x * 0.05,),
         lambda v: ref.quantize_ref(v, 256)[:2]),
    ]:
        gq, gs = (np.asarray(t) for t in _compiled_kernel(jax, name, fn, args))
        wq, ws = (np.asarray(t) for t in jax.jit(oracle)(*args))
        gq, gs = gq.reshape(wq.shape), gs.reshape(ws.shape)
        np.testing.assert_allclose(gs, ws, rtol=1e-6, err_msg=name)
        dq = np.abs(gq.astype(np.int32) - wq.astype(np.int32))
        require(dq.max() <= 1 and dq.mean() < 1e-4, f"{name}: int8 codes off (max {dq.max()}, share {dq.mean()})")
        log(f"kernel {name}: ok, {int((dq > 0).sum())} of {dq.size} codes one step apart")


def _compiled_kernel(jax, name, fn, args):
    lowered = jax.jit(fn).lower(*args)
    require("tpu_custom_call" in lowered.as_text(), f"{name}: no Mosaic kernel in the lowered program")
    return lowered.compile()(*args)


# ---------------------------------------------------------------------------
# (b) the main training path
# ---------------------------------------------------------------------------


def train(jax, scenario: str, overrides, *, steady_intervals: int = 0):
    """ExperimentSpec -> FederatedRunner -> run, as ``run_experiment`` does.
    Returns (runner, final state, per-round losses, seconds per cloud
    interval over ``steady_intervals`` more intervals after the run)."""
    from repro.fed import scenarios

    spec = scenarios.get(scenario, overrides=overrides)
    runner = spec.build()
    # no reference to the unstacked params outlives the init
    state = runner.init(jax.random.PRNGKey(spec.run.seed), spec.init_params(jax.random.PRNGKey(spec.run.seed + 1)))
    state = jax.block_until_ready(runner.run(state))
    losses = [h.loss for h in runner.history]
    per_interval = None
    if steady_intervals:
        # the compiled interval again, from the last `steady_intervals` cloud
        # boundaries: no compilation, ends in block_until_ready
        k2 = runner.hier_config.kappa2_effective
        t0 = time.perf_counter()
        state = jax.block_until_ready(
            runner.run(state, start_round=spec.run.num_rounds - steady_intervals * k2)
        )
        per_interval = (time.perf_counter() - t0) / steady_intervals
    return runner, state, losses, per_interval


def check_training(jax, runner, state, losses, *, mesh_devices: int = 0) -> None:
    from repro.fed.engine import SuperRoundEngine

    k2 = runner.hier_config.kappa2_effective
    engine = runner._engine
    require(type(engine) is SuperRoundEngine and not engine.uses_megakernel, f"engine is {engine!r}")
    require((engine.mesh is not None) == bool(mesh_devices), "mesh routing does not match the spec")
    for leaf in jax.tree_util.tree_leaves(state.params):
        require(all(dev.platform == "tpu" for dev in leaf.devices()), "a params leaf is off the TPU")
    require(np.all(np.isfinite(losses)), f"non-finite loss: {losses}")
    first, last = float(np.mean(losses[:k2])), float(np.mean(losses[-k2:]))
    require(last < first, f"loss did not fall: first cloud interval {first}, last {last}")
    log(f"  loss first cloud interval {first:.4f} -> last {last:.4f}")
    if mesh_devices:
        n = runner.topology.num_clients
        for leaf in jax.tree_util.tree_leaves((state.params, state.opt_state)):
            if leaf.ndim and leaf.shape[0] == n:
                shards = leaf.addressable_shards
                require(len({sh.device for sh in shards}) == mesh_devices, "leaf not spread over the mesh")
                require(all(sh.data.shape[0] == n // mesh_devices for sh in shards),
                        f"leaf {leaf.shape} is not split in {mesh_devices} client quarters")
        log(f"  every stacked state leaf holds {n // mesh_devices} of {n} clients on each of {mesh_devices} chips")


def main_path_phase(jax, watch: CompileWatch) -> None:
    c0 = watch.compile_s
    t0 = time.perf_counter()
    runner, state, losses, per_interval = train(
        jax, "lm_edge_niid", LM_100M + ["topology.num_edges=2", "topology.clients_per_edge=2"],
        steady_intervals=2,
    )
    log(f"main path lm-100m, 4 clients / 2 edges: {time.perf_counter() - t0:.1f} s end to end")
    check_training(jax, runner, state, losses)
    log(f"  compile seconds {watch.compile_s - c0:.1f}")
    log(f"  steady seconds per cloud interval {per_interval:.3f}")
    log(f"  peak_bytes_in_use {jax.devices()[0].memory_stats()['peak_bytes_in_use']}")


# ---------------------------------------------------------------------------
# four chips: the client-sharded paths
# ---------------------------------------------------------------------------


def sharded_phase(jax, watch: CompileWatch) -> None:
    c0 = watch.compile_s
    t0 = time.perf_counter()
    mesh = ["topology.mesh_axes=clients:4"]
    runner, state, losses, per_interval = train(
        jax, "lm_edge_niid", LM_100M + ["topology.num_edges=4", "topology.clients_per_edge=4"] + mesh,
        steady_intervals=2,
    )
    log(f"sharded lm-100m, 16 clients / 4 edges over 4 chips: {time.perf_counter() - t0:.1f} s end to end")
    check_training(jax, runner, state, losses, mesh_devices=4)
    log(f"  compile seconds {watch.compile_s - c0:.1f}")
    log(f"  steady seconds per cloud interval {per_interval:.3f}")
    peaks = [dev.memory_stats()["peak_bytes_in_use"] for dev in jax.devices()]
    log(f"  peak_bytes_in_use per chip {peaks}")
    del runner, state

    # what the sharded engines are compared with: the one-device engines,
    # both in full f32 matmul precision so only the cloud psum reassociates
    lm10 = ["schedule.kappas=4,2", "run.num_rounds=8", f"run.seed={SEED}",
            "topology.num_edges=4", "topology.clients_per_edge=2"]
    cohort = ["run.num_rounds=4", f"run.seed={SEED}"]
    for name, scenario, overrides in [
        ("lm-10m 8 clients", "lm_edge_niid", lm10), ("n1m_cohort4096", "n1m_cohort4096", cohort),
    ]:
        with jax.default_matmul_precision("highest"):
            one = train(jax, scenario, overrides)
            sharded = train(jax, scenario, overrides + mesh)
        require(sharded[0]._engine.mesh is not None and one[0]._engine.mesh is None, f"{name}: engines misrouted")
        worst = 0.0
        for a, b in zip(jax.tree_util.tree_leaves(one[1].params), jax.tree_util.tree_leaves(sharded[1].params)):
            a, b = np.asarray(a), np.asarray(b)
            worst = max(worst, float(np.max(np.abs(a - b) / (PARAM_TOL["atol"] + np.abs(a)))))
            np.testing.assert_allclose(a, b, err_msg=name, **PARAM_TOL)
        np.testing.assert_allclose(one[2], sharded[2], err_msg=name, **LOSS_TOL)
        log(f"parity {name}: sharded over 4 chips == one device (max scaled param diff {worst:.3g})")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: kernels + main path on one chip; 4: the client-sharded paths only")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {devices[0].platform!r}", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} chips, found {len(devices)}", file=sys.stderr)
        return 1

    from repro import compile_cache

    log(f"compile cache: {compile_cache.enable()}")
    watch = CompileWatch(jax)
    if args.chips == 1:
        kernel_phase(jax, jnp)
        main_path_phase(jax, watch)
    else:
        sharded_phase(jax, watch)
    log(f"compile cache: {watch.hits} hits, {watch.misses} misses")
    dev = jax.devices()[0]
    print(json.dumps({"ok": True, "device": {"platform": dev.platform, "kind": dev.device_kind,
                                              "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
