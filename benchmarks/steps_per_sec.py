"""Wall-clock training throughput: per-round driver vs superround engine
vs the client-sharded (mesh) superround.

The first entry in the repo's perf trajectory (``BENCH_throughput.json``).
This bench measures the *driver*, not the kernels: the model is a
deliberately small CNN (one 3x3 im2col conv + global average pool + fc, so
XLA lowers the vmapped per-client graph to batched matmuls) and the
per-client batch is tiny — the regime where the per-round loop's fixed
costs (a Python dispatch, a blocking host sync for step/loss, a
synchronous batch gather + upload, an un-donated FedState round-trip)
dominate each edge interval, exactly the overheads the superround engine
(``fed.engine``) amortizes over a whole cloud interval. The batch-8 sweep
point shows the compute-bound other end honestly: when the executable
dominates, both drivers converge — and it is where the sharded engine has
real per-device work to parallelize.

Protocol: all drivers share one compiled executable apiece; after a
warmup chunk (compile + cache warm), alternating timed chunks (order
rotated every rep to cancel clock drift) of whole cloud intervals, median
over reps.

    PYTHONPATH=src python -m benchmarks.steps_per_sec            # full sweep
    PYTHONPATH=src python -m benchmarks.steps_per_sec --json     # + BENCH_throughput.json
    PYTHONPATH=src python -m benchmarks.steps_per_sec --smoke    # CI gate:
        # headline shape only, fails if the engine is slower than per-round
    PYTHONPATH=src python -m benchmarks.steps_per_sec --devices 4 --json
        # + client-sharded rows over 4 (possibly simulated) devices
    PYTHONPATH=src python -m benchmarks.steps_per_sec --devices 4 --smoke
        # multi-device CI gate: sharded engine must not collapse vs 1 device
    PYTHONPATH=src python -m benchmarks.steps_per_sec --population --json
        # population-scale cohort engine only: steady-state client_steps_per_s
        # on the n1m_cohort4096 scenario, merged into BENCH_throughput.json
    PYTHONPATH=src python -m benchmarks.steps_per_sec --population --devices 4 --json
        # + "population_sharded": the same scenario through the sharded
        # cohort engine over a 4-way client mesh, vs the 1-device cohort
        # engine (with --smoke: fails below the 0.7x collapse floor)

``--devices K`` must be seen before JAX initializes: this module reads it
from ``sys.argv`` at import time and sets
``--xla_force_host_platform_device_count`` so a CPU host simulates the
mesh (real multi-device backends need no flag).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _early_devices() -> int:
    argv = sys.argv[1:]
    for i, a in enumerate(argv):
        if a == "--devices" and i + 1 < len(argv):
            return int(argv[i + 1])
        if a.startswith("--devices="):
            return int(a.split("=", 1)[1])
    return 0


_EARLY_DEVICES = _early_devices()
if _EARLY_DEVICES > 1 and "jax" not in sys.modules:
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={_EARLY_DEVICES}"
    )

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import compile_cache  # noqa: E402
from repro.core import FedTopology, HierFAVGConfig  # noqa: E402
from repro.data import FederatedBatcher, clustered_gaussians, make_partition  # noqa: E402
from repro.dist.sharding import client_mesh  # noqa: E402
from repro.fed import FederatedRunner, RunnerConfig  # noqa: E402
from repro.models import cnn  # noqa: E402
from repro.optim import sgd  # noqa: E402

DIM = (8, 8, 1)
HEADLINE = "N64_k4x4"
# name -> (num_clients, num_edges, kappas, batch)
SHAPES = {
    "N16_k2x2": (16, 4, (2, 2), 1),
    "N64_k4x4": (64, 8, (4, 4), 1),
    "N64_k8x2": (64, 8, (8, 2), 1),
    "N64_k4x4_b2": (64, 8, (4, 4), 2),  # middle of the b in {1,2,8} sweep
    "N64_k4x4_b8": (64, 8, (4, 4), 8),  # compute-bound contrast point
}
# the --devices sweep: N64 shapes, a batch sweep b in {1,2,8} from
# dispatch-bound to compute-bound (where per-device parallelism has actual
# work to split)
SHARDED_SHAPES = ("N64_k4x4", "N64_k4x4_b2", "N64_k4x4_b8")
SHARDED_SMOKE_SHAPE = "N64_k4x4_b8"
# the multi-device CI gate is a catastrophic-regression floor, not a
# scaling promise: simulated CPU devices split one host's cores, so the
# parallel win tracks the core count, not the device count
SHARDED_SMOKE_FLOOR = 0.5
# batch-1 floor: dispatch-bound sharding historically regressed to 0.82x of
# one device; the RNG-hoisted scan must keep it from sliding further (floor
# is lenient because alternating-chunk medians still move ~30% rep-to-rep
# on shared CI hosts)
SHARDED_B1_SHAPE = "N64_k4x4"
SHARDED_B1_FLOOR = 0.7

# the megakernel contrast point: per-client state past cache (P=307210 MLP,
# 64 -> 4096 -> 10), batch 1 — the regime the client-blocked edge-interval
# lowering targets (params block-resident across all kappa1 steps instead of
# the step-major full-stack sweep)
MEGAKERNEL_SHAPE = "mlp307k_N32_k8x2"
MEGAKERNEL_GEOM = (32, 4, (8, 2), 1)  # clients, edges, kappas, batch
MLP_HIDDEN = 4096


def _patches(x, k=3):
    """im2col: (B,H,W,C) -> (B,H-k+1,W-k+1,k*k*C) via static slices, so the
    conv is a batched matmul under vmap (fast CPU lowering)."""
    slices = [
        x[:, i : x.shape[1] - k + 1 + i, j : x.shape[2] - k + 1 + j, :]
        for i in range(k)
        for j in range(k)
    ]
    return jnp.concatenate(slices, axis=-1)


def bench_cnn_init(rng):
    k = jax.random.split(rng, 2)
    return {
        "c1w": jax.random.normal(k[0], (9, 16)) * 0.25,
        "c1b": jnp.zeros((16,)),
        "fw": jax.random.normal(k[1], (16, 10)) * 0.3,
        "fb": jnp.zeros((10,)),
    }


def bench_cnn_apply(p, x):
    x = jax.nn.relu(_patches(x) @ p["c1w"] + p["c1b"])  # (B,6,6,16)
    x = jnp.mean(x, axis=(1, 2))  # global average pool
    return x @ p["fw"] + p["fb"]


def bench_mlp_init(rng):
    k = jax.random.split(rng, 2)
    d = DIM[0] * DIM[1] * DIM[2]
    return {
        "w1": jax.random.normal(k[0], (d, MLP_HIDDEN)) / np.sqrt(d),
        "b1": jnp.zeros((MLP_HIDDEN,)),
        "w2": jax.random.normal(k[1], (MLP_HIDDEN, 10)) * 0.02,
        "b2": jnp.zeros((10,)),
    }


def bench_mlp_apply(p, x):
    x = x.reshape((x.shape[0], -1))
    x = jax.nn.relu(x @ p["w1"] + p["b1"])
    return x @ p["w2"] + p["b2"]


def _make_runner(engine, num_clients, num_edges, kappas, batch, seed=0, mesh=None, model="cnn"):
    rng = np.random.default_rng(seed)
    data = clustered_gaussians(
        rng, num_samples=num_clients * 40, num_classes=10, dim=DIM, class_sep=2.0
    )
    parts = make_partition("edge_iid", data.y, num_edges, num_clients // num_edges, rng)
    batcher = FederatedBatcher(
        {"inputs": data.x, "targets": data.y}, parts, batch_size=batch, seed=seed
    )
    apply_fn = bench_mlp_apply if model == "mlp" else bench_cnn_apply
    runner = FederatedRunner(
        loss_fn=cnn.make_cnn_loss_fn(apply_fn),
        optimizer=sgd(0.1),
        topology=FedTopology(num_edges=num_edges, clients_per_edge=num_clients // num_edges),
        hier_config=HierFAVGConfig(kappa1=kappas[0], kappa2=kappas[1]),
        data_sizes=batcher.data_sizes,
        batcher=batcher,
        runner_config=RunnerConfig(num_rounds=0, engine=engine),
        mesh=mesh,
    )
    init_fn = bench_mlp_init if model == "mlp" else bench_cnn_init
    state = runner.init(jax.random.PRNGKey(seed), init_fn(jax.random.PRNGKey(seed + 1)))
    return runner, state


def _timed_chunk(runner, state, start_round, rounds):
    runner.cfg.num_rounds = start_round + rounds
    t0 = time.perf_counter()
    state = runner.run(state, start_round=start_round)
    jax.block_until_ready(state.params)
    return time.perf_counter() - t0, state


def run_shape(name, *, reps=5, intervals=20, warmup_intervals=2, devices=0):
    """Time whole-cloud-interval chunks per driver. ``devices > 1`` adds a
    "sharded" driver: the superround engine over a ``devices``-way client
    mesh (same executable protocol, same alternation)."""
    num_clients, num_edges, kappas, batch = SHAPES[name]
    k1, k2 = kappas
    chunk = intervals * k2

    modes = ["per_round", "superround"] + (["sharded"] if devices > 1 else [])
    drivers = {}
    for mode in modes:
        mesh = client_mesh(devices) if mode == "sharded" else None
        engine = "superround" if mode == "sharded" else mode
        runner, state = _make_runner(engine, num_clients, num_edges, kappas, batch, mesh=mesh)
        _, state = _timed_chunk(runner, state, 0, warmup_intervals * k2)  # compile + warm
        drivers[mode] = {"runner": runner, "state": state, "done": warmup_intervals * k2, "times": []}

    for rep in range(reps):
        shift = rep % len(modes)
        order = modes[shift:] + modes[:shift]
        for mode in order:
            d = drivers[mode]
            dt, d["state"] = _timed_chunk(d["runner"], d["state"], d["done"], chunk)
            d["done"] += chunk
            d["times"].append(dt)

    out = {"num_clients": num_clients, "kappas": list(kappas), "batch": batch}
    for mode in modes:
        med = float(np.median(drivers[mode]["times"]))
        out[mode] = {
            "ms_per_round": round(med / chunk * 1000, 4),
            "local_steps_per_s": round(chunk * k1 / med, 2),
            "client_steps_per_s": round(chunk * k1 * num_clients / med, 1),
        }
    out["speedup"] = round(
        out["superround"]["local_steps_per_s"] / out["per_round"]["local_steps_per_s"], 3
    )
    if "sharded" in drivers:
        out["devices"] = devices
        out["sharded_speedup_vs_superround"] = round(
            out["sharded"]["local_steps_per_s"] / out["superround"]["local_steps_per_s"], 3
        )
    return out


def run_megakernel_shape(*, reps=5, intervals=8, warmup_intervals=1):
    """Time the fused edge-interval megakernel engine against the scan-fused
    superround at the megakernel's design shape (large per-client state,
    batch 1). Same executable/alternation protocol as ``run_shape``."""
    num_clients, num_edges, kappas, batch = MEGAKERNEL_GEOM
    k1, k2 = kappas
    chunk = intervals * k2

    modes = ["superround", "megakernel"]
    drivers = {}
    for mode in modes:
        runner, state = _make_runner(mode, num_clients, num_edges, kappas, batch, model="mlp")
        _, state = _timed_chunk(runner, state, 0, warmup_intervals * k2)
        if mode == "megakernel" and not runner._engine.uses_megakernel:
            raise SystemExit(
                f"megakernel engine fell back at the bench shape: "
                f"{runner._engine.megakernel_reason}"
            )
        drivers[mode] = {"runner": runner, "state": state, "done": warmup_intervals * k2, "times": []}

    for rep in range(reps):
        shift = rep % len(modes)
        order = modes[shift:] + modes[:shift]
        for mode in order:
            d = drivers[mode]
            dt, d["state"] = _timed_chunk(d["runner"], d["state"], d["done"], chunk)
            d["done"] += chunk
            d["times"].append(dt)

    out = {"num_clients": num_clients, "kappas": list(kappas), "batch": batch,
           "model": f"mlp_h{MLP_HIDDEN}", "params_per_client": 307210}
    for mode in modes:
        med = float(np.median(drivers[mode]["times"]))
        out[mode] = {
            "ms_per_round": round(med / chunk * 1000, 4),
            "local_steps_per_s": round(chunk * k1 / med, 2),
            "client_steps_per_s": round(chunk * k1 * num_clients / med, 1),
        }
    out["megakernel_speedup_vs_superround"] = round(
        out["megakernel"]["local_steps_per_s"] / out["superround"]["local_steps_per_s"], 3
    )
    return out


POPULATION_SCENARIO = "n1m_cohort4096"
# the sharded-cohort CI gate is (like the full-population one) a
# catastrophic-regression floor: simulated devices split one host's cores
POPULATION_SHARDED_FLOOR = 0.7


def run_population(name=POPULATION_SCENARIO, *, reps=3, intervals=4,
                   warmup_intervals=1, devices=0):
    """Steady-state throughput of the sampled-participation cohort engine on
    a virtual-client population scenario. Only the cohort is device-resident,
    so this times the full streaming loop: host-side cohort sampling + lazy
    per-client batch synthesis (overlapped in the prefetch worker), sticky-row
    store swap, and the donated cohort superround. One warmup interval pays
    compilation; timed chunks of whole cloud intervals, median over reps.

    With ``devices > 1`` a second driver runs the same scenario through the
    sharded cohort engine (``topology.mesh_axes=clients:K``) with the same
    alternating-chunk protocol, and a ``(single_row, sharded_section)`` pair
    is returned; otherwise ``(single_row, None)``.
    """
    from repro.fed import scenarios
    from repro.fed.engine import CohortEngine

    def make_driver(overrides):
        spec = scenarios.get(name, overrides)
        runner = spec.build()
        state = runner.init(
            jax.random.PRNGKey(spec.run.seed),
            spec.init_params(jax.random.PRNGKey(spec.run.seed + 1)),
        )
        return {"spec": spec, "runner": runner, "engine": CohortEngine(runner),
                "state": state, "intervals": 0, "times": []}

    modes = ["single"] + (["sharded"] if devices > 1 else [])
    drivers = {"single": make_driver([])}
    if devices > 1:
        drivers["sharded"] = make_driver([f"topology.mesh_axes=clients:{devices}"])
    k1 = drivers["single"]["runner"].hier_config.kappa1
    k2 = drivers["single"]["runner"].hier_config.kappa2_effective
    cohort = int(drivers["single"]["runner"].participation.cohort_size)

    def chunk(d, n):
        t0 = time.perf_counter()
        d["state"], _ = d["engine"].run_intervals(
            d["state"], start_round=d["intervals"] * k2, num_intervals=n
        )
        jax.block_until_ready(d["state"].params)
        d["intervals"] += n
        return time.perf_counter() - t0

    for mode in modes:
        chunk(drivers[mode], warmup_intervals)  # compile + first prefetch fill
    for rep in range(reps):
        shift = rep % len(modes)
        for mode in modes[shift:] + modes[:shift]:
            d = drivers[mode]
            d["times"].append(chunk(d, intervals))

    steps = intervals * k2 * k1  # local steps per timed chunk

    def row(d):
        med = float(np.median(d["times"]))
        store = d["runner"].client_store
        return {
            "scenario": name,
            "num_clients": int(len(d["runner"].batcher.data_sizes)),
            "cohort_size": cohort,
            "sampler": d["runner"].participation.sampler,
            "kappas": [k1, k2],
            "batch": d["spec"].data.batch_size,
            "ms_per_interval": round(med / intervals * 1000, 2),
            "local_steps_per_s": round(steps / med, 2),
            "client_steps_per_s": round(steps * cohort / med, 1),
            "client_store_mib": round((store.nbytes if store is not None else 0) / 2**20, 3),
        }

    single = row(drivers["single"])
    if devices <= 1:
        return single, None
    sh = row(drivers["sharded"])
    sharded = {
        "scenario": name,
        "devices": devices,
        "batch": single["batch"],
        "cohort_size": cohort,
        "sampler": single["sampler"],
        "single": {k: single[k] for k in
                   ("ms_per_interval", "local_steps_per_s", "client_steps_per_s")},
        "sharded": {k: sh[k] for k in
                    ("ms_per_interval", "local_steps_per_s", "client_steps_per_s")},
        "scaling_vs_1dev": round(
            sh["client_steps_per_s"] / single["client_steps_per_s"], 3
        ),
    }
    return single, sharded


def main(argv=None):
    compile_cache.enable()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="quick CI gate: headline shape only (plus the sharded "
                         "gate shape with --devices); exit nonzero if the "
                         "superround engine is slower than the per-round driver "
                         "or the sharded engine collapses vs one device")
    ap.add_argument("--json", nargs="?", const="BENCH_throughput.json", default=None,
                    metavar="OUT.json", help="write machine-readable results "
                    "(default path: BENCH_throughput.json)")
    ap.add_argument("--population", action="store_true",
                    help="run ONLY the population-scale cohort bench "
                         f"({POPULATION_SCENARIO}): steady-state streaming "
                         "participation over a virtual-client population; with "
                         "--json the result merges into the existing file "
                         "without clobbering the shape-sweep keys")
    ap.add_argument("--devices", type=int, default=0, metavar="K",
                    help="also time the client-sharded superround over a K-way "
                         "client mesh (read pre-import: simulates K CPU devices "
                         "via --xla_force_host_platform_device_count)")
    # argv=None means a programmatic call (benchmarks.run): parse nothing
    # rather than falling back to sys.argv — the harness's own --json flag
    # must not be absorbed here and clobber its output file
    args = ap.parse_args([] if argv is None else argv)

    if args.devices > 1 and len(jax.devices()) < args.devices:
        raise SystemExit(
            f"--devices {args.devices} needs {args.devices} visible devices but "
            f"only {len(jax.devices())} exist; run this module directly "
            f"(python -m benchmarks.steps_per_sec --devices {args.devices}) so "
            f"the pre-import hook can set XLA_FLAGS, or export XLA_FLAGS="
            f"--xla_force_host_platform_device_count={args.devices}"
        )

    if args.population:
        names = []  # the population job times the cohort engine only
        reps, intervals, warmup = 3, 8, 1
    elif args.smoke:
        names = [] if args.devices > 1 else [HEADLINE]  # the multi-device job gates sharded only
        reps, intervals, warmup = 3, 8, 1
    else:
        names = list(SHAPES)
        reps, intervals, warmup = 5, 20, 2
    shapes = {}
    for name in names:
        shapes[name] = run_shape(name, reps=reps, intervals=intervals, warmup_intervals=warmup)
        s = shapes[name]
        print(
            f"steps_per_sec_{name},per_round={s['per_round']['local_steps_per_s']},"
            f"superround={s['superround']['local_steps_per_s']},speedup={s['speedup']}"
        )

    megakernel = None
    if names and not args.smoke:  # full sweep only: the megakernel design shape
        megakernel = {MEGAKERNEL_SHAPE: run_megakernel_shape(reps=reps)}
        row = megakernel[MEGAKERNEL_SHAPE]
        print(
            f"steps_per_sec_megakernel_{MEGAKERNEL_SHAPE},"
            f"superround={row['superround']['local_steps_per_s']},"
            f"megakernel={row['megakernel']['local_steps_per_s']},"
            f"speedup={row['megakernel_speedup_vs_superround']}"
        )

    sharded = None
    if args.devices > 1 and not args.population:
        # the smoke gate times both floors: the b8 scaling shape and the
        # dispatch-bound b1 shape (the historical 0.82x regression)
        snames = (SHARDED_SMOKE_SHAPE, SHARDED_B1_SHAPE) if args.smoke else SHARDED_SHAPES
        sharded = {"devices": args.devices, "shapes": {}}
        for name in snames:
            row = run_shape(name, reps=reps, intervals=intervals,
                            warmup_intervals=warmup, devices=args.devices)
            sharded["shapes"][name] = row
            print(
                f"steps_per_sec_sharded_{name},devices={args.devices},"
                f"superround={row['superround']['client_steps_per_s']},"
                f"sharded={row['sharded']['client_steps_per_s']},"
                f"scaling_vs_1dev={row['sharded_speedup_vs_superround']}"
            )
        gate_name = SHARDED_SMOKE_SHAPE if SHARDED_SMOKE_SHAPE in sharded["shapes"] else snames[0]
        row = sharded["shapes"][gate_name]
        sharded["headline"] = {
            "shape": gate_name,
            "devices": args.devices,
            "client_steps_per_s_1dev": row["superround"]["client_steps_per_s"],
            "client_steps_per_s_sharded": row["sharded"]["client_steps_per_s"],
            "scaling_vs_1dev": row["sharded_speedup_vs_superround"],
        }

    population = population_sharded = None
    if args.population:
        population, population_sharded = run_population(
            reps=reps, intervals=4, warmup_intervals=warmup, devices=args.devices
        )
        print(
            f"steps_per_sec_population_{population['scenario']},"
            f"num_clients={population['num_clients']},"
            f"cohort={population['cohort_size']}/{population['sampler']},"
            f"client_steps_per_s={population['client_steps_per_s']},"
            f"ms_per_interval={population['ms_per_interval']}"
        )
        if population_sharded is not None:
            print(
                f"steps_per_sec_population_sharded_{population_sharded['scenario']},"
                f"devices={population_sharded['devices']},"
                f"single={population_sharded['single']['client_steps_per_s']},"
                f"sharded={population_sharded['sharded']['client_steps_per_s']},"
                f"scaling_vs_1dev={population_sharded['scaling_vs_1dev']}"
            )

    results = {
        "bench": "steps_per_sec",
        "shapes": shapes,
        "env": {"backend": jax.default_backend(), "cpu_count": os.cpu_count(),
                "devices": len(jax.devices()), "jax": jax.__version__,
                "xla_flags": os.environ.get("XLA_FLAGS", ""),
                "smoke": bool(args.smoke)},
    }
    head = shapes.get(HEADLINE)
    if head is not None:
        results["headline"] = {
            "shape": HEADLINE,
            "speedup": head["speedup"],
            "per_round_local_steps_per_s": head["per_round"]["local_steps_per_s"],
            "superround_local_steps_per_s": head["superround"]["local_steps_per_s"],
        }
    if megakernel is not None:
        results["megakernel"] = megakernel
    if sharded is not None:
        results["sharded"] = sharded
    if population is not None:
        results["population"] = population
    if population_sharded is not None:
        results["population_sharded"] = population_sharded
    if args.json:
        # partial runs (--population, --devices-only smoke) merge into the
        # existing file rather than clobbering the other benches' keys
        from benchmarks.common import merge_write_json

        merged = merge_write_json(args.json, results, skip_empty=("shapes",))
        if not isinstance(merged.get("shapes"), dict):
            merge_write_json(args.json, {"shapes": shapes})
        print(f"wrote {args.json}")
    if head is not None and head["speedup"] < 1.5:
        print(
            f"steps_per_sec_note,headline speedup {head['speedup']} < 1.5 target "
            "(dispatch-bound regime narrows on loaded/low-core CPU hosts)"
        )
    if args.smoke and head is not None and head["speedup"] < 1.0:
        raise SystemExit(
            f"superround engine slower than per-round driver at the smoke shape "
            f"(speedup {head['speedup']} < 1.0)"
        )
    # sharded gate failures must be diagnosable from the log alone: simulated
    # devices split one host's cores, so a collapse on a 1-core runner is an
    # environment fact, not a code regression
    env_note = (
        f"[cpu_count={os.cpu_count()}, "
        f"xla_flags={os.environ.get('XLA_FLAGS', '') or '<unset>'!s}]"
    )
    if args.smoke and sharded is not None:
        # gate on the headline entry so the gate and the recorded headline
        # can never disagree about which shape they describe
        gate = sharded["headline"]["scaling_vs_1dev"]
        if gate < SHARDED_SMOKE_FLOOR:
            raise SystemExit(
                f"client-sharded superround collapsed at the gate shape "
                f"({sharded['headline']['shape']}: {gate} < {SHARDED_SMOKE_FLOOR} "
                f"of the single-device engine) {env_note}"
            )
        b1_row = sharded["shapes"].get(SHARDED_B1_SHAPE)
        if b1_row is not None:
            b1 = b1_row["sharded_speedup_vs_superround"]
            if b1 < SHARDED_B1_FLOOR:
                raise SystemExit(
                    f"batch-1 sharded throughput slid below the floor "
                    f"({SHARDED_B1_SHAPE}: {b1} < {SHARDED_B1_FLOOR} of the "
                    f"single-device engine) {env_note}"
                )
    if args.smoke and population_sharded is not None:
        gate = population_sharded["scaling_vs_1dev"]
        if gate < POPULATION_SHARDED_FLOOR:
            raise SystemExit(
                f"sharded cohort engine collapsed on {population_sharded['scenario']} "
                f"({gate} < {POPULATION_SHARDED_FLOOR} of the single-device cohort "
                f"engine over {population_sharded['devices']} devices) {env_note}"
            )
    return results


if __name__ == "__main__":
    main(sys.argv[1:])
