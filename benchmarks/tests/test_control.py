"""The control comes out not correct, at a size a test run can hold.

The control is the plain reference put in the program's place and computed
one precision below the configuration's bfloat16: an fp8 step (operands to
float8_e4m3fn, their cotangents to float8_e5m2, per-tensor scales). It is
held to the cell's own limits (`benchmarks/limits/resnet50_fit.json`, set
from chip readings at the cell's size; PERF.md has them) on all 50 layers
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


def judged(readings, mode):
    limits = harness.load_json("limits", "resnet50_fit.json")
    numbers = compare.first_steps(readings[mode], readings["float32"])
    ok = compare.judge(numbers, limits)
    return ok, numbers


def test_float8_control_is_not_correct(readings):
    ok, numbers = judged(readings, "float8")
    assert not ok, numbers
    assert not numbers["grad_diff_share"]["holds"], numbers


def test_stated_precision_is_correct(readings):
    ok, numbers = judged(readings, "bfloat16")
    assert ok, numbers
