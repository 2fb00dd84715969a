"""The control at test size: the reference computed in bfloat16, put in
the program's place, must come out not correct against each cell's limits,
while the program itself passes them. (On the chip, at the cells' own
sizes, ``bench/control.py`` reads the same numbers over a dozen seeds, with
the control one precision below the configuration's: ``high`` for the CNN
at ``highest``, which the CPU, computing float32 matmuls exactly at every
precision, cannot show.)"""
import json

import jax
import pytest

from bench import check, run

from conftest import FIX, limits_dir, small_bench


@pytest.mark.parametrize("cell", ["lm100m.s128", "cifar_cnn.k5x10"])
def test_bf16_control_fails_and_program_passes(cell):
    spec = run.load_cell(cell, small_bench(cell), FIX, limits_dir(cell))
    seed = 777
    prog = run.program_setup(jax, spec, seed, jax.devices()[:1])
    args = (spec["sizes"], spec["traffic"], prog["data"], seed, jax.devices()[:1])
    ref = spec["module"].reference(*args)
    control = check.numbers(spec["module"].reference(*args, variant="bf16"), ref)
    assert check.verdict(check.numbers(prog["readings"], ref), spec["limits"])
    assert not check.verdict(control, spec["limits"]), json.dumps(control)
