"""An expert layer's schedule is made once a step, with one sort.

`parallel/moe.pair_schedule` gives the pairs' order by expert, its inverse
and the experts' sizes: one single-operand sort of a packed word and a
count where the parent made two stable `argsort`s and a one-hot sum, the
same integers to the bit. `route` names the router's choice and
`held_experts` the schedule (`ops/attention.name_block_residual`,
"expert_schedule"), so a checkpointed layer keeps them and its
recomputation sorts nothing and chooses nothing: counted in the lowered
text of the layer's gradient on the CPU. Nothing here is a time.
"""

import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import InputType
from deeplearning4j_tpu.models.multilayer import _checkpointed
from deeplearning4j_tpu.parallel import moe

names = importlib.import_module("deeplearning4j_tpu.ops.attention")

N, D = 96, 16


def parent_schedule(key, count):
    """The parent's formulation (`parallel/moe.py` until PR 51)."""
    order = jnp.argsort(key, stable=True)
    place = jnp.argsort(order).astype(jnp.int32)
    sizes = jnp.sum(key[:, None] == jnp.arange(count)[None, :], axis=0,
                    dtype=jnp.int32)
    return order.astype(jnp.int32), place, sizes


schedule = jax.jit(moe.pair_schedule, static_argnums=1)


def _keys(case):
    """(key [rows] int32, count) of a case: each pair's expert among the
    `count` held, `count` where its expert is not held."""
    rng = np.random.RandomState(7)
    if case == "uniform":               # 8 of 32 experts held, 4 a token
        n, k, count, experts = 256, 4, 8, 32
        chosen = np.stack([rng.permutation(experts)[:k] for _ in range(n)])
        return np.where(chosen < count, chosen, count).reshape(-1), count
    if case == "one_expert":
        return np.full(512, 3), 8
    if case == "none_held":
        return np.full(384, 5), 5
    if case == "count_1":
        return rng.randint(0, 2, 640), 1
    if case == "k_1":                   # one pair a token
        return np.minimum(rng.randint(0, 16, 200), 4), 4
    if case == "rows_no_multiple_of_128":
        return rng.randint(0, 10, 1000 + 37), 9
    if case == "one_pair":
        return np.array([0]), 1
    if case == "past_the_word":         # 2**19 buckets x 2**13 indices
        return rng.randint(0, 2 ** 19, 4097), 2 ** 19 - 1
    raise KeyError(case)


CASES = ("uniform", "one_expert", "none_held", "count_1", "k_1",
         "rows_no_multiple_of_128", "one_pair", "past_the_word")


@pytest.mark.parametrize("case", CASES)
def test_the_schedule_is_the_argsort_pair_to_the_bit(case):
    key, count = _keys(case)
    key = jnp.asarray(key, jnp.int32)
    got = schedule(key, count)
    want = parent_schedule(key, count) if case != "past_the_word" else (
        np.argsort(np.asarray(key), kind="stable"),
        np.argsort(np.argsort(np.asarray(key), kind="stable")),
        np.bincount(np.asarray(key), minlength=count + 1)[:count])
    for name, a, b in zip(("order", "place", "sizes"), got, want):
        assert a.dtype == jnp.int32, name
        np.testing.assert_array_equal(a, b, err_msg=name)


@pytest.mark.parametrize("case", CASES)
def test_only_a_word_that_fits_is_packed(case):
    """One single-operand sort where key and index share 32 bits, the
    two-operand stable pair past that: chosen by the shape alone."""
    key, count = _keys(case)
    text = schedule.lower(jnp.asarray(key, jnp.int32), count).as_text()
    sorts = text.count("stablehlo.sort")
    assert sorts == (2 if case == "past_the_word" else 1)
    stable = text.count("is_stable = true")
    assert stable == (2 if case == "past_the_word" else 0)


# --- a checkpointed layer
LAYERS = {
    "swiglu": dict(width=24, n_experts=16, held=(2, 4), k=3,
                   score="sigmoid", n_shared=1),
    "latent": dict(width=24, n_experts=16, held=(0, 4), k=3,
                   score="sigmoid", selection_bias=True, route_norm=True,
                   route_scale=2.5, expert_form="relu2", latent=8,
                   shared_width=40),
    "grouped": dict(width=24, n_experts=16, held=(4, 4), k=3, n_group=4,
                    topk_group=2, score="softmax"),
}


def _layer(kind):
    layer = moe.ExpertFeedForward(n_in=D, **LAYERS[kind])
    params, state = layer.init_params(jax.random.PRNGKey(1),
                                      InputType.feed_forward(D))
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(N, D), jnp.float32)
    target = jnp.asarray(rng.randn(N, D), jnp.float32)
    return layer, params, state, x, target


def _loss(layer, state, x, target, checkpointing):
    if checkpointing:
        run = _checkpointed(layer.apply, None)
        apply = lambda p: run(p, x, state, None)[0]
    else:
        apply = lambda p: layer.apply(p, x, state=state, train=True)

    def loss(p):
        y, counters = apply(p)
        return jnp.mean(jnp.square(jnp.tanh(y) - target)), counters

    return loss


def _as_the_parent(monkeypatch):
    """Two `argsort`s and a one-hot sum a dispatch, and nothing of the
    schedule kept: forward and recomputed; the chosen scores and the
    pairs' weights by XLA's gathers, with scatter-adds for transposes."""
    monkeypatch.setattr(moe, "pair_schedule", parent_schedule)
    monkeypatch.setattr(moe, "_chosen", lambda s, experts:
                        jnp.take_along_axis(s, experts, axis=-1))
    monkeypatch.setattr(moe, "_in_order", lambda v, order, place: v[order])
    monkeypatch.setattr(names, "KEPT_NAMES", tuple(
        n for n in names.KEPT_NAMES if n != "expert_schedule"))


def _lowered(loss, params):
    # a new function each time: JAX caches a trace
    return jax.jit(jax.value_and_grad(lambda p: loss(p)[0])).lower(
        params).as_text()


def _sorts_and_choices(loss, params):
    """(sorts of one operand, sorts of two, `top_k`s) in the lowered text
    of a loss's gradient."""
    text = _lowered(loss, params)
    operands = [found.count("%") for found in re.findall(
        r'"stablehlo\.sort"\(([^)]*)\)', text)]
    assert len(operands) == text.count("stablehlo.sort")
    return operands.count(1), operands.count(2), text.count("chlo.top_k")


@pytest.mark.parametrize("kind", sorted(LAYERS))
def test_a_checkpointed_layer_sorts_once_and_chooses_once(monkeypatch, kind):
    """One sort makes the schedule (the packed word, one operand) and one
    `top_k` a stage the choice, forward, and the recomputation makes
    neither again; two more sorts APPLY the schedule where XLA's gather
    and scatter-add did: the weights into row order and their cotangent
    back (`_in_order`; the kept weights are not sorted again)."""
    layer, params, state, x, target = _layer(kind)
    stages = 2 if kind == "grouped" else 1      # the groups, the experts
    assert _sorts_and_choices(_loss(layer, state, x, target, True),
                              params) == (1, 2, stages)
    # no checkpoint, nothing to make again
    assert _sorts_and_choices(_loss(layer, state, x, target, False),
                              params) == (1, 2, stages)
    with monkeypatch.context() as parent:
        _as_the_parent(parent)
        text = _lowered(_loss(layer, state, x, target, True), params)
        assert _sorts_and_choices(_loss(layer, state, x, target, True),
                                  params) == (0, 4, 2 * stages)
        assert text.count("is_stable = true") == 4
    assert "is_stable = true" not in _lowered(
        _loss(layer, state, x, target, True), params)


@pytest.mark.parametrize("kind", sorted(LAYERS))
def test_no_transpose_of_the_dispatch_is_a_scatter(monkeypatch, kind):
    """The chip's compiler makes a scatter-add a sort of its indices with
    the updates for payload: the chosen scores' transpose is a one-hot sum
    and the sorted weights' a sort by the inverse permutation. (The
    scatters left write one element, the last expert's size where
    `ragged_dot` runs, at a unique index.)"""
    layer, params, state, x, target = _layer(kind)

    def scatters():     # (all, those at unique indices); a new trace each
        text = _lowered(_loss(layer, state, x, target, True), params)
        return (text.count('"stablehlo.scatter"('),
                text.count("unique_indices = true"))

    assert scatters() == (2, 2)
    with monkeypatch.context() as parent:
        _as_the_parent(parent)
        assert scatters() == (4, 2)


@pytest.mark.parametrize("kind", sorted(LAYERS))
@pytest.mark.parametrize("checkpointing", [True, False])
def test_loss_gradients_and_counters_are_the_parents_to_the_bit(
        monkeypatch, kind, checkpointing):
    layer, params, state, x, target = _layer(kind)
    got = jax.value_and_grad(_loss(layer, state, x, target, checkpointing),
                             has_aux=True)(params)
    with monkeypatch.context() as parent:
        _as_the_parent(parent)
        want = jax.value_and_grad(
            _loss(layer, state, x, target, checkpointing),
            has_aux=True)(params)
    jax.tree_util.tree_map(np.testing.assert_array_equal, got, want)
    (_, counters), gradients = got
    assert int(counters["moe_pairs_held"]) > 0
    assert int(counters["moe_pairs_dropped"]) == 0
    moved = [float(jnp.max(jnp.abs(g)))
             for g in jax.tree_util.tree_leaves(gradients)]
    assert max(moved) > 0 and all(np.isfinite(moved))


@pytest.mark.parametrize("kind", sorted(LAYERS))
def test_a_checkpointed_layer_names_its_choice_and_its_schedule(kind):
    """`experts`, `order`, `place`, `sizes` and the weights in row order:
    five values a layer, and the routed sum where a latent's
    up-projection reads it."""
    layer, params, state, x, _ = _layer(kind)
    run, named = _checkpointed(layer.apply, None), []
    jax.eval_shape(lambda p: named.append(run(p, x, state, None)[1]),
                   params)
    assert list(named[0]) == [0, 5 + (kind == "latent")]
