"""Run one cell of the benchmark, once, on the chips of this machine.

    python3 bench/run.py --workload lm100m.s128 --seed 7 --seconds 20 --trace 0

A cell (``workloads`` in BENCHMARK.json) is a configuration
(``bench/configs/<config>.json`` for its sizes, ``<config>.py`` for the
runner it builds, its weights, FLOP count and plain reference) under a
traffic mix (``bench/traffic/<traffic>.json``, read by ``bench/data.py``).
Per-layer metrics are readers in ``bench/metrics/<metric>.py`` and each
cell's limits of the correctness numbers are in ``bench/limits/<cell>.json``;
everything is found by its name, so a new cell, configuration or metric is
new files and new entries only.

One run:

1. set-up: check the chips (a TPU whose ``device_kind`` is in
   ``bench/peaks.py``, as many as the cell asks for; anything else exits 1
   before measuring), generate the data from the seed, build the program's
   ``FederatedRunner``, make the weights, and drive the compiled cloud
   interval through its first three intervals, keeping the readings the
   correctness check compares;
2. the window: whole chunks of cloud intervals through ``runner.run`` until
   ``--seconds`` have passed, ending in ``block_until_ready``; a chunk lasts
   a few seconds, as sized from the set-up's intervals;
3. with ``--trace 1`` the window runs under the profiler and the per-layer
   metrics are read from its trace; with ``--trace 0`` the end-to-end ones;
4. the peak device memory (buffers in use, and the temporaries the
   executables reserve), then (with the program's state freed) the plain
   reference over the same three intervals, and the verdict.

The last line of standard output is one JSON object: ``correct``,
``attempted`` (cloud intervals in the window), ``failed`` (those with a
non-finite loss), ``metrics``, ``device``, with ``--trace 1`` ``breakdown``,
and last ``check``: each number compared, with its limit. The same numbers
close standard error.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent
sys.path[:0] = [str(REPO / "src"), str(REPO)]

CHECK_INTERVALS = 3  # the intervals the reference follows
CHUNK_TARGET_S = 3.0  # a window chunk's length, as sized from the set-up
TRACE_DIR = REPO / ".bench_trace"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileWatch:
    """Backend compile seconds and persistent-cache hits/misses, through
    ``jax.monitoring``."""

    def __init__(self, jax):
        self.compile_s = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += duration
            self.compiles += 1

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str, bench: dict, traffic_dir: Path, limits_dir: Path) -> dict:
    """Everything a run reads from files, by name."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"run.py: no workload {workload!r}; known: {sorted(cells)}")
    cell = cells[workload]
    config = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    return {
        "cell": cell,
        "sizes": json.loads((REPO / config["file"]).read_text()),
        "traffic": json.loads((traffic_dir / f"{cell['traffic']}.json").read_text()),
        "limits": json.loads((limits_dir / f"{workload}.json").read_text())["limits"],
        "module": load_module(BENCH / "configs" / f"{cell['config']}.py", f"bench_config_{cell['config']}"),
        "end_to_end": [m for m in bench["end_to_end"] if workload in m.get("workloads", [workload])],
        "per_layer": [m for m in bench["per_layer"] if workload in m.get("workloads", [workload])],
    }


def program_setup(jax, spec: dict, seed: int, devices) -> dict:
    """Build the program's runner and drive its first CHECK_INTERVALS cloud
    intervals through the window's own call; returns the runner, its state,
    the readings the check compares and the seconds per interval."""
    from bench.check import leaf_norms
    from bench.data import make_dataset

    mod, sizes, traffic = spec["module"], spec["sizes"], spec["traffic"]
    # the matmul precision the configuration states, for every program the
    # run compiles (the reference sets its own)
    precision = sizes["matmul_precision"]
    jax.config.update("jax_default_matmul_precision", None if precision == "default" else precision)
    mesh = None
    if traffic["mesh_devices"]:
        from repro.dist.sharding import client_mesh

        mesh = client_mesh(traffic["mesh_devices"])
    data = make_dataset(traffic, seed)
    log(f"data made at {time.perf_counter() - T_START:.1f} s")
    runner = mod.make_runner(sizes, traffic, data, seed, mesh)
    gather = runner.batcher.next_batches

    def traced_gather(count):
        # the prefetch thread's host work, named in the trace
        with jax.profiler.TraceAnnotation("bench.batch_gather"):
            return gather(count)

    runner.batcher.next_batches = traced_gather
    state = runner.init(jax.random.PRNGKey(seed % 2**32), mod.init_params(sizes, seed))
    k2 = runner.hier_config.kappa2_effective

    runner.cfg.num_rounds = k2
    state = runner.run(state, start_round=0)
    log(f"first interval done at {time.perf_counter() - T_START:.1f} s")
    first = leaf_norms(*mod.first_reading(state, mod.init_params(sizes, seed)))
    t0 = time.perf_counter()
    runner.cfg.num_rounds = CHECK_INTERVALS * k2
    state = jax.block_until_ready(runner.run(state, start_round=k2))
    per_interval = (time.perf_counter() - t0) / (CHECK_INTERVALS - 1)
    change = leaf_norms(state.params, mod.init_params(sizes, seed))
    readings = {"loss": [h.loss for h in runner.history], "first": first, "change": change}
    return {"runner": runner, "state": state, "data": data, "readings": readings,
            "per_interval_s": per_interval, "done": CHECK_INTERVALS}


def window(jax, prog: dict, seconds: float, trace: bool):
    """Whole chunks of cloud intervals through ``runner.run`` until
    ``seconds`` have passed, ending in ``block_until_ready``; a chunk lasts
    about CHUNK_TARGET_S at the set-up's pace."""
    runner, state = prog["runner"], prog["state"]
    k2 = runner.hier_config.kappa2_effective
    chunk = max(1, round(CHUNK_TARGET_S / prog["per_interval_s"]))
    done = start = prog["done"]
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # the harness's spans and the runtime's, not every Python call
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=options)
    with jax.profiler.TraceAnnotation("bench.window"):
        t0 = time.perf_counter()
        while True:
            with jax.profiler.TraceAnnotation("bench.chunk"):
                runner.cfg.num_rounds = (done + chunk) * k2
                state = runner.run(state, start_round=done * k2)
            done += chunk
            if time.perf_counter() - t0 >= seconds:
                break
        state = jax.block_until_ready(state)
        window_s = time.perf_counter() - t0
    if trace:
        jax.profiler.stop_trace()
    losses = [h.loss for h in runner.history[start * k2:done * k2]]
    failed = sum(
        not all(math.isfinite(x) for x in losses[i * k2:(i + 1) * k2]) for i in range(done - start)
    )
    prog["state"] = state
    return {"intervals": done - start, "failed": failed, "window_s": window_s, "chunk": chunk}


def free_device(jax) -> None:
    """Delete every array the program left on the chips and drop its
    executables, so that the reference that follows has the memory."""
    gc.collect()
    for a in jax.live_arrays():
        a.delete()
    jax.clear_caches()


def main(argv=None, *, require_chip: bool = True, bench: dict = None,
         traffic_dir: Path = None, limits_dir: Path = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = bench or json.loads((REPO / "BENCHMARK.json").read_text())
    spec = load_cell(args.workload, bench, traffic_dir or BENCH / "traffic", limits_dir or BENCH / "limits")
    chips = spec["cell"]["chips"]

    import jax

    from bench import check
    from bench.peaks import peaks_for

    devices = jax.devices()
    dev = devices[0]
    log(f"device: platform={dev.platform} device_kind={dev.device_kind} count={len(devices)}")
    peaks = None
    if require_chip:
        if dev.platform != "tpu":
            log(f"run.py: needs a TPU, JAX found {dev.platform!r}")
            return 1
        if len(devices) < chips:
            log(f"run.py: {args.workload} needs {chips} chips, found {len(devices)}")
            return 1
        try:
            peaks = peaks_for(dev.device_kind)
        except KeyError as e:
            log(f"run.py: {e.args[0]}")
            return 1
    used = devices[:chips]

    from repro import compile_cache

    log(f"compile cache: {compile_cache.enable()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    watch = CompileWatch(jax)

    prog = program_setup(jax, spec, args.seed, used)
    setup_s = time.perf_counter() - T_START
    log(f"set-up {setup_s:.3f} s: compile {watch.compile_s:.3f} s in {watch.compiles} compiles, "
        f"cache {watch.hits} hits / {watch.misses} misses; {prog['per_interval_s']:.4f} s per interval")

    before = watch.compiles
    win = window(jax, prog, args.seconds, bool(args.trace))
    if watch.compiles != before:
        log(f"run.py: {watch.compiles - before} compiles inside the window")
    # the allocator's buffers (state, batches) and, apart from them, the
    # temporaries each executable reserves when it is loaded
    stats = [d.memory_stats() for d in used] if require_chip else [{}]
    peak = max(st.get("peak_bytes_in_use", 0) for st in stats)
    temp = max(st.get("peak_bytes_reserved", 0) for st in stats)
    log(f"window {win['window_s']:.3f} s: {win['intervals']} intervals in chunks of {win['chunk']}, "
        f"{win['failed']} failed; peak {peak} bytes in use, {temp} reserved")

    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
              "memory_peak_bytes": peak}
    metrics = {}
    breakdown = None
    if args.trace:
        from bench.trace import reduce_trace

        red = reduce_trace(str(TRACE_DIR), [d.id for d in used])
        ctx = {"trace": red, "intervals": win["intervals"], "window_s": win["window_s"],
               "flops_per_interval": spec["module"].model_flops_per_interval(spec["sizes"], spec["traffic"]),
               "peaks": peaks, "chips": chips}
        for m in spec["per_layer"]:
            value = load_module(BENCH / "metrics" / f"{m['name']}.py", f"bench_metric_{m['name']}").read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        breakdown = {"device_ops": red["device_ops"], "idle_gaps": red["idle_gaps"]}
    else:
        values = {"interval_s": win["window_s"] / win["intervals"], "peak_hbm_gib": peak / 2**30,
                  "temp_hbm_gib": temp / 2**30,
                  "setup_s": setup_s}
        for m in spec["end_to_end"]:
            metrics[m["name"]] = {"value": values[m["name"]], "unit": m["unit"]}

    readings = prog["readings"]
    data = prog["data"]
    del prog
    free_device(jax)
    if require_chip:
        log(f"memory before the reference: {used[0].memory_stats()}")
    t0 = time.perf_counter()
    ref = spec["module"].reference(spec["sizes"], spec["traffic"], data, args.seed, used)
    log(f"reference {time.perf_counter() - t0:.3f} s")
    nums = check.numbers(readings, ref)
    correct = check.verdict(nums, spec["limits"]) and win["failed"] == 0 and win["intervals"] > 0

    out = {"correct": correct, "attempted": win["intervals"], "failed": win["failed"],
           "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["check"] = {k: {"value": nums[k], "limit": spec["limits"][k]} for k in check.NUMBERS}
    for k in check.NUMBERS:
        log(f"check {k} {nums[k]!r} limit {spec['limits'][k]!r}")
    log(f"correct {correct}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
