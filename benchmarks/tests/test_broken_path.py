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


# by the configuration's `model`: the program's class that builds its train
# step, and the step that does nothing
BROKEN = {"resnet50": ("computation_graph.ComputationGraph", _graph_step),
          "tokens_tiny": ("multilayer.MultiLayerNetwork", _multilayer_step)}


def break_step(model: str, setattr_=setattr):
    """Put the unchanged-state step under every net the configuration's
    `model` builds from here on."""
    import importlib

    module, cls = BROKEN[model][0].split(".")
    setattr_(getattr(importlib.import_module(
        "deeplearning4j_tpu.models." + module), cls),
        "_get_train_step", BROKEN[model][1])


@pytest.mark.parametrize("config,limits", [
    ("resnet50_tiny", harness.load_json("limits", "resnet50_fit.json")),
    ("tokens_tiny", LIMITS["tokens_tiny"])])
def test_unchanged_state_is_not_correct(monkeypatch, config, limits):
    break_step(harness.load_json("tests", "configs", config + ".json")[
        "model"], monkeypatch.setattr)
    result = bench_run.run_cell(
        tiny_cell(1, "fit_stream", config), seed=3, seconds=1.0, trace=False,
        require_chip=False, t_start=time.perf_counter(), limits=limits)
    assert result["correct"] is False
    assert result["attempted"] > 0
