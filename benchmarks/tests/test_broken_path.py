"""A run with the timed path broken underneath comes out not correct.

Drives everything `run.py` drives after its look for a chip, on the tiny
cell, with the train step replaced by one that returns its state unchanged
(parameters, momentum and batch-norm state as they came, a constant loss).
The first gradient read back from the momentum is then zero and the
parameters' change is zero: both gaps read exactly 1, over the cell's
limits.
"""

import time

import jax.numpy as jnp

from benchmarks import harness, run as bench_run
from benchmarks.tests.helpers import tiny_cell


def test_unchanged_state_is_not_correct(monkeypatch):
    from deeplearning4j_tpu.models.computation_graph import ComputationGraph

    def broken(self, key, tbptt=False):
        def step(params, opt_state, states, step, *batch_and_rng):
            return params, opt_state, states, jnp.float32(6.9)
        return step

    monkeypatch.setattr(ComputationGraph, "_get_train_step", broken)
    limits = harness.load_json("limits", "resnet50_fit.json")
    result = bench_run.run_cell(
        tiny_cell(1, "fit_stream"), seed=3, seconds=1.0, trace=False,
        require_chip=False, t_start=time.perf_counter(), limits=limits)
    assert result["correct"] is False
    assert result["attempted"] > 0
