"""Benchmark entry point: one bench per paper table/figure + framework
benches. ``PYTHONPATH=src python -m benchmarks.run [--only name]
[--json out.json]``.

``--json`` writes per-bench machine-readable results (status, wall time,
and whatever the bench's ``main()`` returned) — the start of the
``BENCH_*.json`` perf trajectory; CI runs the kernel bench through it as
a smoke gate (kernels interpreted on the CPU).

Scenario mode runs one named ``repro.fed.scenarios`` registry entry
end-to-end through the public ``ExperimentSpec`` API instead of the bench
table — the CI path that exercises declarative assembly:

    PYTHONPATH=src python -m benchmarks.run --scenario trimmed_edge \\
        --set run.num_rounds=8 --json BENCH_scenario.json
    PYTHONPATH=src python -m benchmarks.run --list-scenarios
"""
import argparse
import json
import time
import traceback

from benchmarks import (
    aggregation_scaling,
    compression_tradeoff,
    fig2_topologies,
    fig4_convergence,
    kernel_bench,
    roofline_report,
    round_time_sim,
    steps_per_sec,
    table1_cost_model,
    table2_latency_energy,
)
from repro import compile_cache

BENCHES = {
    "table1_cost_model": table1_cost_model.main,
    "fig4_convergence": fig4_convergence.main,
    "table2_latency_energy": table2_latency_energy.main,
    "fig2_topologies": fig2_topologies.main,
    "kernel_bench": kernel_bench.main,
    "aggregation_scaling": aggregation_scaling.main,
    "compression_tradeoff": compression_tradeoff.main,
    "roofline_report": roofline_report.main,
    "round_time_sim": round_time_sim.main,
    "steps_per_sec": steps_per_sec.main,
}


def run_scenario(name: str, overrides) -> dict:
    """Build + train one registry scenario; returns a summary row."""
    from repro.fed import scenarios

    spec = scenarios.get(name, overrides=overrides)
    print(spec.describe(), flush=True)
    t0 = time.time()
    runner, state = spec.run_experiment()
    accs = [h.accuracy for h in runner.history if h.accuracy is not None]
    out = {
        "scenario": name,
        "overrides": list(overrides),
        "rounds": len(runner.history),
        "steps": int(runner.history[-1].step),
        "final_loss": float(runner.history[-1].loss),
        "final_accuracy": accs[-1] if accs else None,
        "sim_time_s": runner.history[-1].sim_time_s,
        "wire_mb": runner.history[-1].wire_mb,
        "elapsed_s": round(time.time() - t0, 3),
    }
    print(
        f"scenario={name},rounds={out['rounds']},steps={out['steps']},"
        f"loss={out['final_loss']:.4f},acc={out['final_accuracy']},"
        f"elapsed_s={out['elapsed_s']:.1f}"
    )
    return out


def main() -> None:
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    ap.add_argument("--json", default=None, metavar="OUT.json",
                    help="write per-bench machine-readable results")
    ap.add_argument("--scenario", default=None, metavar="NAME",
                    help="run one repro.fed.scenarios registry entry instead of the benches")
    ap.add_argument("--set", dest="overrides", action="append", default=[],
                    metavar="PATH=VALUE", help="dotted-path spec override (with --scenario)")
    ap.add_argument("--list-scenarios", action="store_true",
                    help="print the scenario registry and exit")
    args = ap.parse_args()
    if args.list_scenarios:
        from repro.fed import scenarios

        for name, desc in scenarios.describe_all():
            print(f"{name:22s} {desc}")
        return
    if args.scenario:
        if args.only:
            raise SystemExit("--only does not apply with --scenario")
        result = run_scenario(args.scenario, args.overrides)
        if args.json:
            with open(args.json, "w") as f:
                json.dump({args.scenario: {"status": "ok", **result}}, f, indent=2, default=str)
            print(f"wrote {args.json}")
        return
    if args.overrides:
        raise SystemExit("--set only applies with --scenario")
    if args.only and args.only not in BENCHES:
        # an unknown name must not silently pass (CI gates on this entry point)
        raise SystemExit(f"unknown bench {args.only!r}; choose from {sorted(BENCHES)}")
    failures = []
    results = {}
    for name, fn in BENCHES.items():
        if args.only and args.only != name:
            continue
        print(f"\n===== {name} =====", flush=True)
        t0 = time.time()
        try:
            ret = fn()
            elapsed = time.time() - t0
            results[name] = {"status": "ok", "elapsed_s": round(elapsed, 3), "result": ret}
            print(f"{name},elapsed_s={elapsed:.1f}")
        except Exception as e:
            failures.append(name)
            results[name] = {
                "status": "failed", "elapsed_s": round(time.time() - t0, 3),
                "error": f"{type(e).__name__}: {e}",
            }
            print(f"{name},FAILED: {e}")
            traceback.print_exc()
    if args.json:
        with open(args.json, "w") as f:
            # default=str keeps numpy scalars / dataclasses serializable
            json.dump(results, f, indent=2, default=str)
        print(f"wrote {args.json}")
    if failures:
        raise SystemExit(f"benches failed: {failures}")


if __name__ == "__main__":
    main()
