"""Every admitted cell's limits were set from readings on the chip: the
largest of the program's sound runs below each limit, and above it the
smallest reading of the control (counted where it reads three times the
sound one or more) or of a planted fault (ten times; a state left
unchanged, which reads 1 on ``change_gap``, three times). A limits file is
written only from such readings, never ahead of them."""
import json

import pytest

from bench import check, run

BENCH = json.loads((run.REPO / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
CONTROLS = ("bf16", "high")  # the reference one precision below the configuration's


def upper_reading(readings: dict, number: str) -> float:
    lower = readings["lower"][number]
    failing = []
    for name, reading in readings.items():
        if not isinstance(reading, dict) or name == "lower" or number not in reading:
            continue
        times = 3 if name in CONTROLS or name == "state_unchanged" else 10
        if reading[number] >= times * lower:
            failing.append(reading[number])
    return min(failing, default=float("nan"))


@pytest.mark.parametrize("cell", CELLS)
def test_limits_lie_between_sound_and_failing_chip_readings(cell):
    path = run.BENCH / "limits" / f"{cell}.json"
    assert path.exists(), f"{cell} has no limits file"
    spec = json.loads(path.read_text())
    readings = spec["readings"]
    assert isinstance(readings, dict), f"{cell}: readings are not chip readings: {readings!r}"
    assert "TPU" in readings["from"], readings["from"]
    assert sum(name in readings for name in CONTROLS) == 1, sorted(readings)
    for k in check.NUMBERS:
        lower, upper = readings["lower"][k], upper_reading(readings, k)
        assert lower < spec["limits"][k] < upper, (k, lower, spec["limits"][k], upper)
    # the control and every fault the cell read fail at least one number
    for name, reading in readings.items():
        if isinstance(reading, dict) and name != "lower":
            assert any(reading.get(k, 0.0) > spec["limits"][k] for k in check.NUMBERS), name
