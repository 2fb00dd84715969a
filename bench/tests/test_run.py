"""A whole run of ``bench/run.py`` at test size, and its refusal without a
chip."""
import json

import pytest

from bench import run


@pytest.mark.parametrize("cell", ["lm100m.s128", "cifar_cnn.k5x10"])
def test_sound_run_is_correct(cell, run_small):
    rc, out = run_small(cell)
    assert rc == 0
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(out)[-1] == "check"
    assert out["correct"] is True, out["check"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"interval_s", "peak_hbm_gib", "temp_hbm_gib", "setup_s"}


def test_no_accelerator_exits_nonzero_with_no_result(capsys):
    rc = run.main(["--workload", "lm100m.s128", "--seed", "1", "--seconds", "1"])
    assert rc != 0
    assert not capsys.readouterr().out.strip()


def test_every_cell_names_files_that_exist():
    bench = json.loads((run.REPO / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        spec = run.load_cell(w["name"], bench, run.BENCH / "traffic", run.BENCH / "limits")
        assert spec["traffic"]["num_edges"] * spec["traffic"]["clients_per_edge"] > 0
        for fn in ("make_runner", "init_params", "first_reading", "model_flops_per_interval", "reference"):
            assert callable(getattr(spec["module"], fn))
    for m in bench["per_layer"]:
        assert (run.BENCH / "metrics" / f"{m['name']}.py").exists()
