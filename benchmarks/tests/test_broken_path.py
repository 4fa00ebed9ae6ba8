"""A run with the timed path broken underneath comes out not correct.

Drives everything `run.py` drives after its look for a chip, on a tiny
cell, with the train step replaced by one that returns its state unchanged
(parameters, optimizer state and layer state as they came, a constant
loss). The first gradient read back from the optimizer's state is then
zero and the parameters' change is zero: both gaps read exactly 1, over
the cell's limits. Once through `ComputationGraph.fit()` on images under
Nesterov, once through `MultiLayerNetwork.fit()` on integer ids and labels
under Adam.
"""

import time

import jax.numpy as jnp
import pytest

from benchmarks import harness, run as bench_run
from benchmarks.tests.helpers import LIMITS, tiny_cell


def _graph_step(self, key, tbptt=False):
    def step(params, opt_state, states, step, *batch_and_rng):
        return params, opt_state, states, jnp.float32(6.9)
    return step


def _multilayer_step(self, key):
    def step(params, opt_state, states, step, *batch_and_rng):
        return params, opt_state, states, jnp.float32(6.9), None
    return step


@pytest.mark.parametrize("config,net,broken,limits", [
    ("resnet50_tiny", "computation_graph.ComputationGraph", _graph_step,
     harness.load_json("limits", "resnet50_fit.json")),
    ("tokens_tiny", "multilayer.MultiLayerNetwork", _multilayer_step,
     LIMITS["tokens_tiny"])])
def test_unchanged_state_is_not_correct(monkeypatch, config, net, broken,
                                        limits):
    import importlib

    module, cls = net.split(".")
    monkeypatch.setattr(
        getattr(importlib.import_module("deeplearning4j_tpu.models." + module),
                cls), "_get_train_step", broken)
    result = bench_run.run_cell(
        tiny_cell(1, "fit_stream", config), seed=3, seconds=1.0, trace=False,
        require_chip=False, t_start=time.perf_counter(), limits=limits)
    assert result["correct"] is False
    assert result["attempted"] > 0
