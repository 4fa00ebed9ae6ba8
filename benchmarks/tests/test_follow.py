"""The follower that holds the parameters and one gradient on the device
gives, bit for bit, what the one it replaced gave: that one (one jitted
step over parameters, state and batch, nothing donated, the initial
parameters kept on the device) stays here as the oracle. On both rules,
on integer batches and on real ones, with and without `row_blocks`; and
between two steps no entry of the rule's state that has the parameters'
structure is on the device.
"""

import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import harness, traffic_gen

follow = harness.load_module("reference", "follow.py")
CASES = [("tokens_tiny", 1), ("resnet50_tiny", 1), ("resnet50_tiny", 2)]


def oracle(loss_fn, rule, params, batches, updater, row_blocks):
    """`follow.follow` as it was up to PR 27."""
    @jax.jit
    def step(p, state, x, y):
        loss, g = follow.loss_and_grad(loss_fn, p, x, y, "float32",
                                       row_blocks)
        p_new, state_new = rule.update(p, state, g, updater)
        return (p_new, state_new, loss, follow.leaf_norms(g),
                follow.leaf_samples(g))

    @jax.jit
    def delta(p, p0):
        return follow.leaf_norms(jax.tree_util.tree_map(jnp.subtract, p, p0))

    state, p, losses = rule.init(params), params, []
    for i, (x, y) in enumerate(batches):
        p, state, loss, gn, gs = step(p, state, jnp.asarray(x),
                                      jnp.asarray(y))
        losses.append(float(loss))
        if i == 0:
            grad_norm, sample = gn, gs
    names = follow.leaf_paths(params)
    return {"loss": losses,
            "grad_norm": dict(zip(names, map(float, grad_norm))),
            "delta_norm": dict(zip(names, map(float, delta(p, params)))),
            "grad_sample": dict(zip(names, map(np.asarray, sample)))}


def cell_parts(name):
    config = harness.load_json("tests", "configs", name + ".json")
    ref = harness.load_module("reference", config["reference"] + ".py")
    rule = harness.load_module("reference", "rules",
                               config["updater"]["rule"] + ".py")
    batches = traffic_gen.make_pool(
        harness.load_json("traffic", "fit_stream.json"), config, 2 ** 31 + 5,
        config["batch_per_chip"], count=3)
    return config, ref, rule, batches


@pytest.mark.parametrize("name,row_blocks", CASES)
def test_bit_for_bit_with_the_follower_it_replaced(name, row_blocks):
    config, ref, rule, batches = cell_parts(name)
    want = oracle(ref.loss_fn, rule, ref.init_params(11, config), batches,
                  config["updater"], row_blocks)
    got = follow.follow(ref.loss_fn, rule, ref.init_params(11, config),
                        batches, config["updater"], row_blocks=row_blocks)
    assert got["loss"] == want["loss"]
    assert got["grad_norm"] == want["grad_norm"]
    assert got["delta_norm"] == want["delta_norm"]
    assert set(got["grad_sample"]) == set(want["grad_sample"])
    for leaf, sample in want["grad_sample"].items():
        assert np.array_equal(got["grad_sample"][leaf], sample), leaf


@pytest.mark.parametrize("name", ["tokens_tiny", "resnet50_tiny"])
def test_state_waits_on_the_host_between_steps(monkeypatch, name):
    config, ref, rule, batches = cell_parts(name)
    params = ref.init_params(11, config)
    # leaves larger than a gradient sample, so that no sample is taken
    # for one; how many parameters have each such shape
    large = collections.Counter(
        leaf.shape for leaf in jax.tree_util.tree_leaves(params)
        if leaf.size > follow.SAMPLE)
    assert large
    states, seen = [], []

    class Spy(follow.HostState):
        def __init__(self, *args):
            super().__init__(*args)
            states.append(self)

    def between_steps():
        for i, batch in enumerate(batches):
            if i:
                host = [leaf for entry in states[0].own for leaf in entry]
                # on the CPU a host array read from a buffer is a view of
                # it and keeps it alive: that memory is the host entry
                seen.append(([type(leaf) for leaf in host],
                             collections.Counter(
                    a.shape for a in jax.live_arrays() if a.shape in large
                    and not any(np.may_share_memory(np.asarray(a), leaf)
                                for leaf in host))))
            yield batch

    monkeypatch.setattr(follow, "HostState", Spy)
    before = collections.Counter(
        a.shape for a in jax.live_arrays() if a.shape in large)
    follow.follow(ref.loss_fn, rule, params, between_steps(),
                  config["updater"])
    assert len(seen) == 2 and states[0].own
    for kinds, on_device in seen:
        assert set(kinds) == {np.ndarray}
        # of the model's size the device holds the parameters alone
        assert on_device == before
