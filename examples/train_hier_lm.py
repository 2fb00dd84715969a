"""End-to-end federated LM training (deliverable b), spec-driven.

    PYTHONPATH=src python examples/train_hier_lm.py              # ~10M model, fast
    PYTHONPATH=src python examples/train_hier_lm.py --preset 100m --rounds 40

Trains a decoder-only LM with HierFAVG across 8 clients / 2 edges on a
Markov-teacher token corpus with label-skewed (edge-NIID) client splits,
with checkpointing + failure injection — the full production loop on CPU,
assembled from the ``lm_edge_niid`` registry scenario. Every CLI flag is a
dotted-path override on that spec.
"""
import argparse
import time

from repro import compile_cache
from repro.fed import scenarios


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--preset", default="10m", choices=["10m", "100m"])
    ap.add_argument("--rounds", type=int, default=24)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--ckpt-dir", default="/tmp/hier_lm_ckpt")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--inject-failures", action="store_true")
    ap.add_argument("--set", dest="overrides", action="append", default=[],
                    metavar="PATH=VALUE", help="extra spec overrides, repeatable")
    args = ap.parse_args()

    overrides = [
        f"model.arch=lm-{args.preset}",
        f"run.num_rounds={args.rounds}",
        f"data.seq_len={args.seq_len}",
        f"run.checkpoint_dir={args.ckpt_dir}",
        "run.checkpoint_every=8",
    ]
    if args.inject_failures:
        overrides += ["failures.p_fail=0.1"]
    spec = scenarios.get("lm_edge_niid", overrides=overrides + args.overrides)
    print(spec.describe())

    t0 = time.time()
    runner, state = spec.run_experiment(resume=args.resume)
    for h in runner.history:
        if h.round % 4 == 0 or h.round == spec.run.num_rounds - 1:
            print(f"round {h.round:3d}  step {h.step:4d}  loss {h.loss:.4f}  alive {h.mask_alive}")
    print(f"\ntrained {int(state.step)} local steps in {time.time()-t0:.0f}s; "
          f"loss {runner.history[0].loss:.3f} -> {runner.history[-1].loss:.3f}")


if __name__ == "__main__":
    main()
