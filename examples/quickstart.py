"""Quickstart: hierarchical FL from a declarative spec, in ~15 lines.

    PYTHONPATH=src python examples/quickstart.py

Trains a small classifier across 20 clients / 4 edge servers with HierFAVG
(kappa1=4 local steps per edge aggregation, kappa2=2 edge rounds per cloud
round) and prints the accuracy + simulated wall-clock/energy per round.
The whole experiment is the ``quickstart`` registry entry — tweak any axis
with a dotted-path override, e.g.
``scenarios.get("quickstart", overrides=["schedule.kappas=6,2"])``.
"""
from repro import compile_cache
from repro.fed import scenarios


def main():
    compile_cache.enable()
    spec = scenarios.get("quickstart")
    print(spec.describe())
    runner, _ = spec.run_experiment()
    for h in runner.history:
        if h.accuracy is not None:
            print(f"round {h.round:3d}  step {h.step:4d}  loss {h.loss:.3f}  "
                  f"acc {h.accuracy:.3f}  T={h.sim_time_s:6.1f}s  E={h.sim_energy_j:5.2f}J")
    final = [h.accuracy for h in runner.history if h.accuracy is not None][-1]
    print(f"\nfinal accuracy: {final:.3f} (HierFAVG, 20 clients / 4 edges, edge-NIID)")


if __name__ == "__main__":
    main()
