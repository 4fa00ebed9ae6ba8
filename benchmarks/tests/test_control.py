"""The control comes out not correct, at a size a test run can hold.

The control is the plain reference put in the program's place and computed
one precision below the configuration's bfloat16: an fp8 step (operands to
float8_e4m3fn, their cotangents to float8_e5m2, per-tensor scales). It is
held to each cell's own limits (`benchmarks/limits/resnet50_fit.json` and
`resnet50_dp4.json`, set from chip readings at the cell's size; PERF.md
has them) on all 50 layers
at 64x64, 100 classes, batch 32, and has to fail one of the numbers. The
reference in bfloat16, which is what the configuration states, has to pass
them all. The chip runs of the control at the cell's own size are made by
`measure_limits.py`, by hand.
"""

import pytest

from benchmarks import compare, harness, reference_main


@pytest.fixture(scope="module")
def readings():
    config = dict(
        harness.load_json("tests", "configs", "resnet50_tiny.json"),
        input_shape=[64, 64, 3], label_shape=[100], batch_per_chip=32)
    traffic = harness.load_json("traffic", "fit_stream.json")
    return {mode: reference_main.reference_numbers(
        config, traffic, chips=1, seed=2 ** 31 + 3, steps=3, mode=mode)
        for mode in ("float32", "bfloat16", "float8")}


CELLS = ["resnet50_fit", "resnet50_dp4"]     # one configuration, two cells


def judged(readings, mode, cell):
    limits = harness.load_json("limits", cell + ".json")
    numbers = compare.first_steps(readings[mode], readings["float32"])
    ok = compare.judge(numbers, limits)
    return ok, numbers


@pytest.mark.parametrize("cell", CELLS)
def test_float8_control_is_not_correct(readings, cell):
    ok, numbers = judged(readings, "float8", cell)
    assert not ok, numbers
    assert not numbers["grad_diff_share"]["holds"], numbers


def test_stated_precision_is_correct(readings):
    # at `resnet50_fit`'s limits only: a loss over 32 rows is noisier than
    # one over `resnet50_dp4`'s 1,024, whose `loss_gap` limit (0.001, three
    # times what four chips read) the bfloat16 reference misses here (0.0012)
    ok, numbers = judged(readings, "bfloat16", "resnet50_fit")
    assert ok, numbers
