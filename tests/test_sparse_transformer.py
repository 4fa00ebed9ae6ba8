"""One chip's share of a sparse window-and-full transformer
(`zoo.SparseSandwichTransformer`), piece by piece and whole: integer ids
that stay integers through `fit()`, the attention options, the expert
layer's shares and its dispatch, and the zoo model against the benchmark's
plain reference through `fit()`. CPU, small sizes, seeded weights,
float32 unless a case says bf16."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.models import ComputationGraph, MultiLayerNetwork
from deeplearning4j_tpu.nn.config import NeuralNetConfiguration
from deeplearning4j_tpu.nn.inputs import InputType
from deeplearning4j_tpu.nn.layers.attention import (
    MultiHeadAttention, SandwichTransformerBlock, rms_norm, rope_rotate,
)
from deeplearning4j_tpu.nn.layers.feedforward import EmbeddingSequenceLayer
from deeplearning4j_tpu.nn.layers.normalization import RMSNormalization
from deeplearning4j_tpu.nn.layers.recurrent import RnnOutputLayer
from deeplearning4j_tpu.optim.updaters import Sgd
from deeplearning4j_tpu.parallel.moe import ExpertFeedForward, _row_tiers

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------- ids stay integers
def _id_net(kind, dtype="bfloat16", vocab=1024, width=8):
    layers = (EmbeddingSequenceLayer(n_in=vocab, n_out=width,
                                     activation="identity"),
              RnnOutputLayer(n_in=width, n_out=vocab, activation="softmax",
                             loss="sparse_mcxent"))
    base = (NeuralNetConfiguration.builder().seed(3).updater(Sgd(0.5))
            .weight_init("xavier").dtype(dtype))
    if kind == "graph":
        conf = (base.graph_builder().add_inputs("ids")
                .add_layer("embed", layers[0], "ids")
                .add_layer("out", layers[1], "embed")
                .set_outputs("out").build())
        return ComputationGraph(conf).init()
    return MultiLayerNetwork(base.list(*layers).build()).init()


def _embedding(net):
    tree = net.params_tree
    name = next(n for n in tree if "embed" in n)
    return np.asarray(tree[name]["W"], np.float32)


@pytest.mark.parametrize("path", ["one_step", "fused", "data_parallel",
                                  "graph"])
def test_ids_over_256_fetch_their_own_rows_in_a_bf16_net(path):
    """bfloat16 holds integers exactly only to 256: 776 and 777 both read
    776 there. Trained on a batch that holds 777 and not 776, row 777 has
    to move and row 776 must not, on every path `_batch_args` feeds."""
    net = _id_net("graph" if path == "graph" else "multilayer")
    before = _embedding(net)
    ids = np.full((8, 4), 777, np.int32)
    batch = DataSet(ids, np.full((8, 4), 5, np.int32))
    if path == "fused":
        net.fit([batch, batch], steps_per_dispatch=2)
    elif path == "data_parallel":
        from deeplearning4j_tpu.parallel import ParallelWrapper, make_mesh

        ParallelWrapper(net, mesh=make_mesh({"data": 8})).fit([batch])
    else:
        net.fit([batch])
    moved = np.abs(_embedding(net) - before).sum(axis=1)
    assert moved[777] > 0
    assert moved[776] == 0
    out = net.output(np.asarray([[776, 777]], np.int32))
    out = out[0] if isinstance(out, (list, tuple)) else out
    assert not np.array_equal(np.asarray(out[0, 0], np.float32),
                              np.asarray(out[0, 1], np.float32))


def test_float_features_are_cast_and_integer_images_too():
    """Only an input that an embedding looks up keeps its integers: a
    dense net's uint8 features still arrive in the net's dtype."""
    from deeplearning4j_tpu.optim.step import as_features

    assert as_features(np.ones((2, 3), np.uint8), jnp.bfloat16).dtype \
        == jnp.bfloat16
    assert as_features(np.ones((2, 3), np.float32), jnp.bfloat16).dtype \
        == jnp.bfloat16
    kept = as_features(np.ones((2, 3), np.int32), jnp.bfloat16, ids=True)
    assert kept.dtype == jnp.int32
    assert as_features(np.ones((2, 3), np.float32), jnp.bfloat16,
                       ids=True).dtype == jnp.bfloat16


def test_check_input_takes_two_dimensional_ids():
    conf = (NeuralNetConfiguration.builder().seed(0).weight_init("xavier")
            .list(EmbeddingSequenceLayer(n_in=50, n_out=4,
                                         activation="identity"),
                  RnnOutputLayer(n_out=50, activation="softmax",
                                 loss="sparse_mcxent"))
            .set_input_type(InputType.recurrent(1, 6)).build())
    net = MultiLayerNetwork(conf).init()
    net._check_input(np.zeros((3, 6), np.int32))
    net._check_input(np.zeros((3, 6, 1), np.int32))
    with pytest.raises(ValueError):
        net._check_input(np.zeros((3, 7), np.int32))


def test_textgen_refuses_an_id_past_the_vocabulary():
    from deeplearning4j_tpu.utils.textgen import generate
    from deeplearning4j_tpu.zoo import TextGenerationTransformer

    net = TextGenerationTransformer(
        num_classes=32, input_shape=(8, 1), d_model=16, num_heads=2,
        num_blocks=1).init()
    assert generate(net, np.asarray([[1, 31]]), 2, greedy=True).shape == (1, 2)
    with pytest.raises(ValueError, match=r"\[0, 32\)"):
        generate(net, np.asarray([[1, 32]]), 2, greedy=True)


def test_embedding_scale_and_rms_layer():
    emb = EmbeddingSequenceLayer(n_in=10, n_out=4, scale=3.0,
                                 activation="identity")
    w = jnp.arange(40.0).reshape(10, 4)
    out, _ = emb.apply({"W": w}, jnp.asarray([[2, 7]]))
    np.testing.assert_allclose(out[0], 3.0 * w[jnp.asarray([2, 7])])
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 5, 8))
    g = jnp.linspace(0.5, 1.5, 8)
    got, _ = RMSNormalization(n_out=8, activation="identity").apply(
        {"gamma": g}, x)
    want = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-5) * g
    np.testing.assert_allclose(got, want, rtol=1e-6)


# ------------------------------------------------------ attention options
def _attention_by_hand(p, x, *, heads, kv_heads, dh, rope, window):
    """The gated, normed, grouped attention written out."""
    b, t, _ = x.shape
    q = (x @ p["Wq"]).reshape(b, t, heads, dh)
    k = (x @ p["Wk"]).reshape(b, t, kv_heads, dh)
    v = (x @ p["Wv"]).reshape(b, t, kv_heads, dh)
    q, k = rms_norm(q, p["q_norm"]), rms_norm(k, p["k_norm"])
    if rope:
        q, k = rope_rotate(q, jnp.arange(t)), rope_rotate(k, jnp.arange(t))
    k = jnp.repeat(k, heads // kv_heads, axis=2)
    v = jnp.repeat(v, heads // kv_heads, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(dh)
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    seen = j <= i
    if window is not None:
        seen = seen & (j > i - window)
    o = jnp.einsum("bhqk,bkhd->bqhd",
                   jax.nn.softmax(jnp.where(seen, s, -1e30), -1), v)
    return (o.reshape(b, t, heads * dh) * jax.nn.sigmoid(x @ p["Wg"])) \
        @ p["Wo"]


@pytest.mark.parametrize("rope,window", [(True, 5), (False, None),
                                         (True, None), (False, 5),
                                         (True, 64)])
def test_gated_normed_attention_with_a_head_size_of_its_own(rope, window):
    """48 heads of 128 over 3,072 in small: 6 heads of 8 over 16, two KV
    heads, no bias; window and full, with and without positions."""
    layer = MultiHeadAttention(
        n_in=16, n_out=16, num_heads=6, num_kv_heads=2, head_dim=8,
        qk_norm=True, output_gate=True, bias=False, causal=True, rope=rope,
        window=window, activation="identity", weight_init="xavier")
    p, _ = layer.init_params(jax.random.PRNGKey(0),
                             InputType.recurrent(16, 12))
    assert set(p) == {"Wq", "Wk", "Wv", "Wo", "Wg", "q_norm", "k_norm"}
    assert p["Wq"].shape == (16, 48) and p["Wk"].shape == (16, 16)
    assert p["Wo"].shape == (48, 16)
    p["q_norm"] = p["q_norm"] * 1.3
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 16))
    got, _ = layer.apply(p, x)
    want = _attention_by_hand(p, x, heads=6, kv_heads=2, dh=8, rope=rope,
                              window=window)
    np.testing.assert_allclose(got, want, atol=2e-6)


def test_positions_change_a_layer_only_where_it_has_them():
    """A layer without positions gives a permuted sequence's last token
    the same output; one with rotary positions does not."""
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 6, 16))
    swapped = x[:, jnp.asarray([1, 0, 2, 3, 4, 5])]
    for rope in (False, True):
        layer = MultiHeadAttention(
            n_in=16, n_out=16, num_heads=2, causal=True, rope=rope,
            bias=False, activation="identity", weight_init="xavier")
        p, _ = layer.init_params(jax.random.PRNGKey(0),
                                 InputType.recurrent(16, 6))
        same = np.allclose(layer.apply(p, x)[0][0, -1],
                           layer.apply(p, swapped)[0][0, -1], atol=1e-6)
        assert same is (not rope)


def test_old_attention_leaves_and_numbers_are_what_they_were():
    layer = MultiHeadAttention(n_in=8, n_out=8, num_heads=2, causal=True,
                               activation="identity", weight_init="xavier")
    p, _ = layer.init_params(jax.random.PRNGKey(0), InputType.recurrent(8, 4))
    assert set(p) == {"Wq", "Wk", "Wv", "Wo", "b"}
    assert p["Wo"].shape == (8, 8)


def test_decode_steps_agree_with_the_whole_sequence_under_the_options():
    layer = MultiHeadAttention(
        n_in=16, n_out=16, num_heads=4, num_kv_heads=2, head_dim=8,
        qk_norm=True, output_gate=True, bias=False, causal=True, rope=True,
        max_cache=8, activation="identity", weight_init="xavier")
    p, _ = layer.init_params(jax.random.PRNGKey(0), InputType.recurrent(16, 8))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 16))
    whole, _ = layer.apply(p, x)
    carry = layer.decode_carry(2)
    steps = []
    for t in range(8):
        y, carry = layer.apply(p, x[:, t:t + 1], state=carry)
        steps.append(y)
    np.testing.assert_allclose(jnp.concatenate(steps, 1), whole, atol=2e-6)


def test_sandwich_block_steps_agree_with_the_whole_sequence():
    block = SandwichTransformerBlock(
        n_in=16, num_heads=4, num_kv_heads=2, head_dim=8, qk_norm=True,
        output_gate=True, rope=True, window=3, max_cache=8, ffn_width=24,
        weight_init="xavier")
    p, _ = block.init_params(jax.random.PRNGKey(0), InputType.recurrent(16, 8))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 16))
    whole, _ = block.apply(p, x)
    state, steps = block.decode_carry(2), []
    for t in range(8):
        y, state = block.apply(p, x[:, t:t + 1], state=state)
        steps.append(y)
    np.testing.assert_allclose(jnp.concatenate(steps, 1), whole, atol=3e-6)


# ------------------------------------------------------------ expert layer
def _expert_layer(held, **kw):
    return ExpertFeedForward(
        n_in=16, width=24, n_experts=16, held=held, k=4, score="sigmoid",
        selection_bias=True, route_norm=True, route_scale=2.448,
        weight_init="xavier", **kw)


def _whole_layer_by_hand(p, x, n_shared=1):
    """Every expert applied to every token, weighted by the router."""
    s = jax.nn.sigmoid(x @ p["router"])
    _, sel = jax.lax.top_k(s + p["bias"], 4)
    wt = jnp.take_along_axis(s, sel, -1)
    wt = wt / (wt.sum(-1, keepdims=True) + 1e-20) * 2.448
    swiglu = lambda w1, w3, w2: (jax.nn.silu(x @ w1) * (x @ w3)) @ w2
    y = swiglu(p["shared_w1"], p["shared_w3"], p["shared_w2"]) \
        if n_shared else 0.0
    for e in range(p["w1"].shape[0]):
        weight = jnp.sum(jnp.where(sel == e, wt, 0.0), -1)
        y = y + weight[:, None] * swiglu(p["w1"][e], p["w3"][e], p["w2"][e])
    return y


def test_the_shares_of_an_expert_layer_add_up_to_the_whole_layer():
    """16 experts as 4 shares of 4, each share what one device computes;
    the shared expert is on every device and counted once."""
    whole = _expert_layer(None, n_shared=1)
    p, _ = whole.init_params(jax.random.PRNGKey(0),
                             InputType.recurrent(16, 8))
    p["bias"] = 0.01 * jax.random.normal(jax.random.PRNGKey(9), (16,))
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 16))
    want = _whole_layer_by_hand(p, x.reshape(-1, 16)).reshape(x.shape)
    got, counters = whole.apply(p, x)
    np.testing.assert_allclose(got, want, atol=3e-6)
    assert int(counters["moe_pairs_held"]) == 4 * 8 * 4
    total, held_pairs = 0.0, 0
    for first in (0, 4, 8, 12):
        share = _expert_layer((first, 4), n_shared=1 if first == 0 else 0)
        sp = {k: v for k, v in p.items()
              if first == 0 or not k.startswith("shared")}
        sp.update({k: p[k][first:first + 4] for k in ("w1", "w3", "w2")})
        y, counters = share.apply(sp, x)
        total = total + y
        held_pairs += int(counters["moe_pairs_held"])
        assert int(counters["moe_pairs_dropped"]) == 0
    np.testing.assert_allclose(total, want, atol=5e-6)
    assert held_pairs == 4 * 8 * 4        # every pair fell on one share


def test_no_pair_is_dropped_when_every_token_prefers_one_expert():
    """A bias that sends every token to experts 0 to 3, all held: four
    times the tokens fall here, 16 times the uniform load, and every one
    is computed (the one-hot path drops over 1.25)."""
    layer = _expert_layer((0, 4))
    p, _ = layer.init_params(jax.random.PRNGKey(0),
                             InputType.recurrent(16, 8))
    p["bias"] = jnp.where(jnp.arange(16) < 4, 10.0, 0.0)
    x = jax.random.normal(jax.random.PRNGKey(1), (64, 16))
    y, counters = layer.apply(p, x)
    assert int(counters["moe_pairs_held"]) == 64 * 4
    assert int(counters["moe_pairs_dropped"]) == 0
    assert int(counters["moe_load_min"]) == int(counters["moe_load_max"]) == 64
    full = dict(p, w1=jnp.concatenate([p["w1"], jnp.zeros((12, 16, 24))]),
                w3=jnp.concatenate([p["w3"], jnp.zeros((12, 16, 24))]),
                w2=jnp.concatenate([p["w2"], jnp.zeros((12, 24, 16))]))
    np.testing.assert_allclose(
        y, _whole_layer_by_hand(full, x, n_shared=0), atol=3e-6)


def test_rows_past_the_pairs_held_may_hold_anything(monkeypatch):
    """On the chip a grouped product leaves the rows that are in no group
    undefined, in its result and in the cotangent of its left operand
    (the CPU leaves zeros). With both poisoned the layer's output and
    gradients are what they were."""
    plain = jax.lax.ragged_dot

    def dead(rows, group_sizes):
        return (jnp.arange(rows) >= jnp.sum(group_sizes))[:, None]

    @jax.custom_vjp
    def poisoned(lhs, rhs, group_sizes):
        return jnp.where(dead(lhs.shape[0], group_sizes), jnp.nan,
                         plain(lhs, rhs, group_sizes))

    def fwd(lhs, rhs, group_sizes):
        out, vjp = jax.vjp(lambda a, b: plain(a, b, group_sizes), lhs, rhs)
        return (jnp.where(dead(lhs.shape[0], group_sizes), jnp.nan, out),
                (vjp, group_sizes, lhs.shape[0]))

    def bwd(res, g):
        vjp, group_sizes, rows = res
        d_lhs, d_rhs = vjp(jnp.where(dead(rows, group_sizes), 0.0, g))
        return (jnp.where(dead(rows, group_sizes), jnp.nan, d_lhs), d_rhs,
                None)

    poisoned.defvjp(fwd, bwd)
    layer = _expert_layer((4, 4), n_shared=1)
    p, _ = layer.init_params(jax.random.PRNGKey(0),
                             InputType.recurrent(16, 8))
    x = jax.random.normal(jax.random.PRNGKey(1), (64, 16))
    loss = lambda p, x: jnp.sum(jnp.sin(layer.apply(p, x)[0]))
    want = jax.grad(loss, argnums=(0, 1))(p, x)
    monkeypatch.setattr(jax.lax, "ragged_dot",
                        lambda a, b, group_sizes: poisoned(a, b, group_sizes))
    got = jax.grad(loss, argnums=(0, 1))(p, x)
    assert int(layer.apply(p, x)[1]["moe_pairs_held"]) < 128   # dead rows
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(g, w, atol=1e-6)
    np.testing.assert_allclose(layer.apply(p, x)[0], _whole_layer_by_hand(
        dict(p, w1=jnp.zeros((16, 16, 24)).at[4:8].set(p["w1"]),
             w3=jnp.zeros((16, 16, 24)).at[4:8].set(p["w3"]),
             w2=jnp.zeros((16, 24, 16)).at[4:8].set(p["w2"])), x),
        atol=3e-6)


def test_the_rows_multiplied_follow_the_pairs_that_fell_here():
    """Tiers from four times the uniform share up; one expert of 32 held:
    a uniform load runs the first tier, every token on the held expert the
    second, and both give what every expert over every token gives."""
    assert _row_tiers(32768, 8 / 256) == (4096, 8192, 16384, 32768)
    assert _row_tiers(512, 1 / 32) == (128, 256, 512)
    assert _row_tiers(256, 4 / 16) == (256,)
    # all that can fall here tops the ladder: k pairs on distinct experts
    assert _row_tiers(1024, 1 / 64, 512) == (128, 256, 512)
    layer = ExpertFeedForward(n_in=16, width=24, n_experts=32, held=(5, 1),
                              k=2, score="softmax", selection_bias=True,
                              weight_init="xavier")
    p, _ = layer.init_params(jax.random.PRNGKey(0),
                             InputType.recurrent(16, 8))
    x = jax.random.normal(jax.random.PRNGKey(1), (256, 16))

    def by_hand(p, x):
        s = jax.nn.softmax(x @ p["router"], -1)
        _, sel = jax.lax.top_k(s + p["bias"], 2)
        weight = jnp.sum(jnp.where(sel == 5, jnp.take_along_axis(s, sel, -1),
                                   0.0), -1)
        return weight[:, None] * ((jax.nn.silu(x @ p["w1"][0])
                                   * (x @ p["w3"][0])) @ p["w2"][0])

    for bias, pairs in ((jnp.zeros(32), None),
                        (jnp.where(jnp.arange(32) == 5, 10.0, 0.0), 256)):
        q = dict(p, bias=bias)
        y, counters = layer.apply(q, x)
        if pairs is None:
            assert int(counters["moe_pairs_held"]) <= 128
        else:
            assert int(counters["moe_pairs_held"]) == pairs
        assert int(counters["moe_pairs_dropped"]) == 0
        np.testing.assert_allclose(y, by_hand(q, x), atol=2e-6)
        got = jax.grad(lambda q: jnp.sum(jnp.sin(layer.apply(q, x)[0])))(q)
        want = jax.grad(lambda q: jnp.sum(jnp.sin(by_hand(q, x))))(q)
        for name in ("router", "w1", "w3", "w2"):
            np.testing.assert_allclose(got[name], want[name], atol=2e-6)


def test_the_selection_bias_gets_no_gradient_and_the_router_does():
    layer = _expert_layer((4, 4), n_shared=1)
    p, _ = layer.init_params(jax.random.PRNGKey(0),
                             InputType.recurrent(16, 8))
    x = jax.random.normal(jax.random.PRNGKey(1), (32, 16))
    g = jax.grad(lambda p: jnp.sum(jnp.sin(layer.apply(p, x)[0])))(p)
    assert float(jnp.max(jnp.abs(g["bias"]))) == 0.0
    assert float(jnp.max(jnp.abs(g["router"]))) > 0.0
    by_hand = dict(p, w1=jnp.zeros((16, 16, 24)).at[4:8].set(p["w1"]),
                   w3=jnp.zeros((16, 16, 24)).at[4:8].set(p["w3"]),
                   w2=jnp.zeros((16, 24, 16)).at[4:8].set(p["w2"]))
    gh = jax.grad(lambda q: jnp.sum(jnp.sin(_whole_layer_by_hand(q, x))))(
        by_hand)
    np.testing.assert_allclose(g["router"], gh["router"], atol=2e-5)
    np.testing.assert_allclose(g["w2"], gh["w2"][4:8], atol=2e-5)


def test_sandwich_block_leaves_and_serde():
    from deeplearning4j_tpu.utils.serde import from_json, to_json

    block = SandwichTransformerBlock(
        n_in=16, num_heads=4, num_kv_heads=2, head_dim=8, qk_norm=True,
        output_gate=True, rope=True, window=4, n_experts=8,
        experts_held=(2, 2), moe_k=2, expert_width=12, n_shared=1,
        score="sigmoid", selection_bias=True, route_norm=True,
        route_scale=2.0, weight_init="xavier")
    p, state = block.init_params(jax.random.PRNGKey(0),
                                 InputType.recurrent(16, 8))
    assert {"ln1_g", "ln2_g", "ln3_g", "ln4_g", "attn_Wg", "attn_q_norm",
            "moe_router", "moe_bias", "moe_shared_w2"} <= set(p)
    assert not any(k.endswith("_b") or k == "attn_b" for k in p)
    assert p["moe_w1"].shape == (2, 16, 12)
    assert "moe_pairs_dropped" in state
    again = from_json(to_json(block))
    # JSON has no tuples: the held share comes back as a list
    assert tuple(again.experts_held) == block.experts_held
    assert dataclasses.replace(again, experts_held=(2, 2)) == block


# ----------------------------------------- the zoo model, against the plain
def test_the_zoo_model_follows_the_plain_reference_through_fit():
    """`benchmarks/reference/trinity_large.py` (plain `jax.numpy`, every
    held expert over every token, no dispatch) and the zoo model through
    `MultiLayerNetwork.fit()`, from the same seeded weights at
    `trinity_tiny`'s size: the three losses, the first gradient read back
    out of Adam's first moment, and the parameters' change after three
    steps."""
    import sys

    sys.path.insert(0, ROOT)
    from benchmarks import harness
    from benchmarks.runners import fit as runner

    with open(os.path.join(ROOT, "benchmarks", "tests", "configs",
                           "trinity_tiny.json"), encoding="utf-8") as fh:
        config = json.load(fh)
    traffic = harness.load_json("traffic", "fit_stream.json")
    cell = {"name": "tiny", "chips": 1, "config_data": config,
            "traffic_data": traffic}
    ready = runner.prepare(cell, 11, ())    # no wrapper: no mesh to make
    reference = harness.load_module("reference_main.py").reference_numbers(
        config, traffic, chips=1, seed=11, steps=3, mode="float32")
    program = ready["program"]
    np.testing.assert_allclose(program["loss"], reference["loss"], rtol=2e-6)
    for leaf, norm in reference["grad_norm"].items():
        assert program["grad_norm"][leaf] == pytest.approx(
            norm, rel=1e-4, abs=1e-7), leaf
    assert reference["grad_norm"][
        "layer2_sandwichtransformerblock/moe_bias"] == 0.0
    for leaf, norm in reference["delta_norm"].items():
        assert program["delta_norm"][leaf] == pytest.approx(
            norm, rel=2e-3, abs=1e-7), leaf
    state = ready["net"].state_tree["layer2_sandwichtransformerblock"]
    assert int(state["moe_pairs_dropped"]) == 0
    assert 0 < int(state["moe_pairs_held"]) < int(state["moe_pairs_routed"])
