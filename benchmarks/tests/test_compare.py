"""The comparison arithmetic, on numbers made by hand."""

import math

from benchmarks import compare


def test_worst_leaf_is_measured_against_the_median_leaf():
    ref = {"a": 1.0, "b": 2.0, "c": 1e-9}
    got = {"a": 1.1, "b": 2.0, "c": 2e-9}       # c doubles, but is ~zero
    gap, leaf = compare.worst_leaf_gap(got, ref)
    assert leaf == "a" and abs(gap - 0.1) < 1e-9


def test_missing_or_extra_leaf_is_infinite():
    assert compare.worst_leaf_gap({"a": 1.0}, {"a": 1.0, "b": 1.0})[0] \
        == math.inf
    assert compare.worst_leaf_gap({"a": 1.0, "z": 1.0}, {"a": 1.0})[0] \
        == math.inf


def test_unchanged_state_reads_one():
    ref = {"a": 3.0, "b": 5.0}
    assert compare.worst_leaf_gap({"a": 0.0, "b": 0.0}, ref)[0] == 1.0


def test_loss_gap_and_judge():
    assert abs(compare.loss_gap([1.0, 2.2], [1.0, 2.0]) - 0.1) < 1e-12
    assert compare.loss_gap([float("nan")], [1.0]) == math.inf
    numbers = {"loss_gap": {"value": 0.2}}
    assert not compare.judge(numbers, {"loss_gap": 0.1})
    assert numbers["loss_gap"]["limit"] == 0.1


def test_grad_diff_share_is_the_median_leaf_beside_the_whole_gradient():
    ref = {"a": [3.0, 4.0], "b": [5.0], "c": [1.0], "zero": [0.0]}
    got = {"a": [3.0, 4.0], "b": [10.0], "c": [1.5], "zero": [0.0]}
    ones = dict.fromkeys(ref, 1.0)
    median, whole = compare.grad_diff_share(got, ref, ones)
    assert median == 0.5                     # leaves read 0, 1, 0.5
    assert abs(whole - math.sqrt(25.25 / 51.0)) < 1e-12
    heavy = dict(ones, a=4.0)
    assert compare.grad_diff_share(got, ref, heavy)[1] < whole
    assert compare.grad_diff_share({"a": [3.0, 4.0]}, ref, ones)[0] \
        == math.inf
