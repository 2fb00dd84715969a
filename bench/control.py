"""Readings that set a cell's limits: the program's sound runs (the lower
reading) and the control and planted faults (the upper reading), in one
process on the cell's chips.

    python3 bench/control.py --workload lm100m.s128 --seeds 11,12,13 \
        --fault-seeds 3 --out control.lm100m.s128.json

For every seed it drives the program's first three cloud intervals exactly
as a benchmark run's set-up does and compares them with the plain reference
(``bench.check``). For the first ``--fault-seeds`` seeds it also puts the
reference's weaker variants in the program's place (``bench.reference``:
the control, one precision below the configuration's (bfloat16 throughout
for float32 at default matmul precision, ``high`` for ``highest``), half of
every batch, and on a multi-chip cell the cloud
mean without the exchange between chips) and compares each with the same
reference. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--fault-seeds", type=int, default=3, help="how many of the seeds also read the variants")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    import jax

    from bench import check
    from repro import compile_cache

    bench = json.loads((run.REPO / "BENCHMARK.json").read_text())
    spec = run.load_cell(args.workload, bench, run.BENCH / "traffic", run.BENCH / "limits")
    chips = spec["cell"]["chips"]
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < chips:
        print(f"control.py: needs {chips} TPU chip(s), found {devices}", file=sys.stderr)
        return 1
    used = devices[:chips]
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    # the control: the nearest precision below the one the configuration states
    control = "high" if spec["sizes"]["matmul_precision"] == "highest" else "bf16"
    variants = [control, "half_batch"] + (["no_exchange"] if chips > 1 else [])
    rows = []
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        prog = run.program_setup(jax, spec, seed, used)
        readings, data = prog["readings"], prog["data"]
        del prog
        run.free_device(jax)
        t1 = time.perf_counter()
        ref = spec["module"].reference(spec["sizes"], spec["traffic"], data, seed, used)
        t2 = time.perf_counter()
        row = {"seed": seed, "program": check.numbers(readings, ref), "program_s": t1 - t0,
               "reference_s": t2 - t1, "loss_prog": readings["loss"], "loss_ref": ref["loss"]}
        if i < args.fault_seeds:
            for v in variants:
                row[v] = check.numbers(spec["module"].reference(
                    spec["sizes"], spec["traffic"], data, seed, used, variant=v), ref)
        rows.append(row)
        print(json.dumps(row), flush=True)
    summary = {"workload": args.workload, "seeds": len(rows)}
    for k in check.NUMBERS:
        summary[k] = {"lower": max(r["program"][k] for r in rows)}
        for v in variants:
            vals = [r[v][k] for r in rows if v in r]
            summary[k][v] = min(vals) if vals else None
    print(json.dumps(summary), flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps({"summary": summary, "rows": rows}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
