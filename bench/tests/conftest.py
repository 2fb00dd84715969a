"""Tests of the benchmark's own code, on the CPU at test sizes:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

Four virtual CPU devices stand in for a four-chip host."""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")
if "jax" not in sys.modules:
    os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=4"

REPO = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO / "src"), str(REPO)]

import json  # noqa: E402

import pytest  # noqa: E402

FIX = Path(__file__).resolve().parent / "fixtures"
LIMITS = REPO / "bench" / "limits"
# each benchmark cell at test size: its own name, its config's layer types at
# small widths, its traffic's shape with fewer rows, and its limits: those
# read on the chip, or for a cell with no chip readings, limits read on the
# CPU at this size (``fixtures/limits``)
TEST_CELLS = {
    "lm100m.s128": ("lm-100m", "lm-tiny.json", "traffic-lm", 1, LIMITS),
    "cifar_cnn.k5x10": ("paper-cifar10-cnn", "cnn-tiny.json", "traffic-cnn", 1, FIX / "limits"),
    "lm100m.s128.mesh4": ("lm-100m", "lm-tiny.json", "traffic-lm-mesh4", 4, LIMITS),
}


def limits_dir(cell: str) -> Path:
    return TEST_CELLS[cell][4]


def small_bench(cell: str) -> dict:
    config, file, traffic, chips, _ = TEST_CELLS[cell]
    real = json.loads((REPO / "BENCHMARK.json").read_text())
    return {
        "configs": [{"name": config, "file": f"bench/tests/fixtures/{file}"}],
        "workloads": [{"name": cell, "config": config, "traffic": traffic, "chips": chips}],
        "end_to_end": real["end_to_end"],
        "per_layer": real["per_layer"],
    }


@pytest.fixture
def run_small(capsys):
    """Drive ``bench/run.py`` end to end on a cell at test size, without the
    look for a chip; returns (exit code, the result line as a dict)."""
    from bench import run

    def go(cell: str, seed: int = 20260917, seconds: float = 1.0):
        rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds", str(seconds)],
                      require_chip=False, bench=small_bench(cell), traffic_dir=FIX,
                      limits_dir=limits_dir(cell))
        lines = capsys.readouterr().out.strip().splitlines()
        return rc, json.loads(lines[-1])

    return go
