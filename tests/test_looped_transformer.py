"""The loop layer, the exit-gated head, the rotary base and the model built
from them (`ouro_2_6b`), small, on the CPU, float32, with seeded weights:
`zoo.LoopedSandwichTransformer` against the benchmark's plain reference
(loss, every leaf's gradient, three Adam steps); the looped net against its
blocks written out pass by pass with copied leaves; one pass against the
plain list of layers, to the last bit; one copy a leaf in the tree, the
optimizer and a saved model; checkpointing on and off; the gate's
distribution and its gauges; what the layers refuse."""

import dataclasses
import json
import os
import zipfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.models import MultiLayerNetwork
from deeplearning4j_tpu.nn.config import (
    MultiLayerConfiguration, NeuralNetConfiguration,
)
from deeplearning4j_tpu.nn.inputs import InputType
from deeplearning4j_tpu.nn.layers import (
    BatchNormalization, DenseLayer, EmbeddingSequenceLayer,
    ExitGatedOutputLayer, LoopedStack, MultiHeadAttention, RMSNormalization,
    RnnOutputLayer, SandwichTransformerBlock,
)
from deeplearning4j_tpu.nn.layers.attention import rope_rotate
from deeplearning4j_tpu.observe import get_registry
from deeplearning4j_tpu.optim.updaters import Adam

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
EMBED, LOOP, HEAD = ("layer0_embeddingsequencelayer", "layer1_loopedstack",
                     "layer2_exitgatedoutputlayer")
T, V, D = 32, 256, 64


def _close(got, want, tol):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert np.linalg.norm(got - want) <= tol * max(np.linalg.norm(want),
                                                   1e-30)


def _tiny(**changes):
    with open(os.path.join(ROOT, "benchmarks", "tests", "configs",
                           "ouro_2_6b_tiny.json"), encoding="utf-8") as fh:
        return {**json.load(fh), **changes}


def _reference():
    from benchmarks import harness

    return harness.load_module("reference", "ouro_2_6b.py")


def _net(cfg, **kw):
    from deeplearning4j_tpu.zoo import LoopedSandwichTransformer

    return MultiLayerNetwork(LoopedSandwichTransformer(
        cfg, timesteps=cfg["input_shape"][0], **kw).conf())


def _ids(seed, rows=2):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.integers(0, V, (rows, T)), jnp.int32)
                 for _ in range(2))


def _loss(net, params, x, y, mask=None):
    return net._loss(params, net.state_tree, x, y, None, mask, None,
                     train=True)[0]


def _gauges(name):
    return {tuple(v for _, v in g.labels): g.value
            for g in get_registry().series() if g.name == name}


# ----------------------------------------------- against the plain reference
@pytest.mark.parametrize("checkpointing", [False, True])
def test_zoo_model_is_the_plain_reference(checkpointing):
    """Loss and every leaf's gradient to 1e-5: 2 blocks, 3 passes, 4 heads
    of 16 at rope base 1e6, a SwiGLU of 128, 256 rows, 32 tokens."""
    cfg, ref = _tiny(), _reference()
    params = ref.init_params(7, cfg)
    net = _net(cfg, gradient_checkpointing=checkpointing).init()
    assert (jax.tree_util.tree_structure(params)
            == jax.tree_util.tree_structure(net.params_tree))
    x, y = _ids(0)
    want, want_g = jax.value_and_grad(ref.loss_fn)(params, x, y)
    got, got_g = jax.value_and_grad(lambda p: _loss(net, p, x, y))(params)
    assert abs(float(got) - float(want)) <= 1e-5 * float(want)
    for layer, leaves in want_g.items():
        for name, leaf in leaves.items():
            assert float(jnp.linalg.norm(leaf)) > 0, (layer, name)
            _close(got_g[layer][name], leaf, 1e-5)


def test_three_adam_steps_are_the_reference_steps():
    """`fit()` thrice against the benchmark's own follower of the plain
    reference under its Adam rule: each step's loss and every leaf's
    change."""
    from benchmarks import harness

    cfg, ref = _tiny(), _reference()
    follow = harness.load_module("reference", "follow.py")
    rule = harness.load_module("reference", "rules", "adam.py")
    batches = [tuple(np.asarray(a) for a in _ids(10 + i)) for i in range(3)]
    start = ref.init_params(8, cfg)
    net = _net(cfg, gradient_checkpointing=True,
               updater=Adam(3e-4, 0.9, 0.95, 1e-8)).init()
    net.params_tree = jax.tree_util.tree_map(jnp.array, start)
    want = follow.follow(ref.loss_fn, rule, start, batches, cfg["updater"])
    losses = []
    for x, y in batches:
        net.fit(DataSet(x, y))
        losses.append(net.score_)
    np.testing.assert_allclose(losses, want["loss"], rtol=1e-5)
    first = ref.init_params(8, cfg)
    for path, norm in want["delta_norm"].items():
        layer, leaf = path.split("/")
        moved = np.linalg.norm(np.asarray(net.params_tree[layer][leaf])
                               - np.asarray(first[layer][leaf]))
        assert moved == pytest.approx(norm, rel=2e-3), path


def test_the_model_counts_what_the_configuration_says():
    """The reference's parameters and multiply-adds at the cell's size, and
    what a pass costs of them."""
    with open(os.path.join(ROOT, "benchmarks", "configs", "ouro_2_6b.json"),
              encoding="utf-8") as fh:
        cfg = json.load(fh)
    ref = _reference()
    shapes = jax.eval_shape(lambda: ref.init_params(0, cfg))
    count = sum(int(np.prod(leaf.shape))
                for leaf in jax.tree_util.tree_leaves(shapes))
    assert count == 612_438_017 == cfg["parameters"]["held_here"]
    assert sum(int(np.prod(s)) for s in ref._block_shapes(cfg).values()) \
        == 51_388_416
    assert ref.forward_macs(cfg) == pytest.approx(21.166e12, rel=1e-4)
    assert cfg["layer_types"] == ["full_attention"] * 8
    assert cfg["reduced"] == ["num_hidden_layers", "layer_types"]


# ------------------------------------------- against the blocks written out
def _block(**kw):
    return SandwichTransformerBlock(num_heads=4, head_dim=16, causal=True,
                                    rope=True, rope_base=1e6, max_cache=T,
                                    eps=1e-6, ffn_width=128, **kw)


def _conf(*layers, checkpointing=False):
    builder = (NeuralNetConfiguration.builder().seed(3)
               .updater(Adam(3e-4)).activation("identity")
               .weight_init("xavier"))
    if checkpointing:
        builder = builder.gradient_checkpointing()
    return (builder.list(
        EmbeddingSequenceLayer(n_in=V, n_out=D, activation="identity"),
        *layers).set_input_type(InputType.recurrent(1, T)).build())


def _looped(passes, blocks=2, checkpointing=False, **head):
    return MultiLayerNetwork(_conf(
        LoopedStack(layers=(_block(),) * blocks, passes=passes,
                    norm=RMSNormalization(eps=1e-6)),
        ExitGatedOutputLayer(n_out=V, passes=passes, activation="softmax",
                             **head), checkpointing=checkpointing)).init()


@pytest.mark.parametrize("passes", [2, 3])
def test_the_looped_net_is_its_blocks_written_out(passes):
    """The same blocks applied pass by pass, each pass with a COPY of the
    leaves: the same loss, and each shared leaf's gradient is the sum of
    its copies'."""
    net = _looped(passes)
    loop, head = net.layers[1], net.layers[2]
    one = dataclasses.replace(loop, passes=1)
    x, y = _ids(1)

    def written_out(copies, rest):
        h, _ = net.layers[0].apply(rest[EMBED], x)
        states = []
        for leaves in copies:
            h, _ = one.apply(leaves, h)
            states.append(h)
        return head.score(rest[HEAD], jnp.stack(states), y)

    copies = [net.params_tree[LOOP]] * passes
    want, (by_copy, rest_g) = jax.value_and_grad(written_out, (0, 1))(
        copies, net.params_tree)
    got, got_g = jax.value_and_grad(lambda p: _loss(net, p, x, y))(
        net.params_tree)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    for name, leaf in got_g[LOOP].items():
        _close(leaf, sum(c[name] for c in by_copy), 1e-5)
        assert all(float(jnp.linalg.norm(c[name])) > 0 for c in by_copy)
    for layer in (EMBED, HEAD):
        for name, leaf in got_g[layer].items():
            _close(leaf, rest_g[layer][name], 1e-5)


@pytest.mark.parametrize("checkpointing", [False, True])
def test_one_pass_is_the_plain_list_to_the_last_bit(checkpointing):
    """`passes=1`: the loop layer is the plain span, the gate's p_1 is 1
    and its entropy 0, so loss and gradients are those of the same blocks
    as a list with `RMSNormalization` and `RnnOutputLayer` behind them."""
    looped = _looped(1, checkpointing=checkpointing)
    plain = MultiLayerNetwork(_conf(
        _block(), _block(), RMSNormalization(eps=1e-6),
        RnnOutputLayer(n_out=V, has_bias=False, activation="softmax",
                       loss="sparse_mcxent"),
        checkpointing=checkpointing)).init()
    ours = looped.params_tree
    theirs = {
        EMBED: ours[EMBED],
        "layer1_sandwichtransformerblock": LoopedStack._of(
            ours[LOOP], looped.layers[1].layers[0]),
        "layer2_sandwichtransformerblock": LoopedStack._of(
            ours[LOOP], looped.layers[1].layers[1]),
        "layer3_rmsnormalization": {"gamma": ours[LOOP]["norm_gamma"]},
        "layer4_rnnoutputlayer": {"W": ours[HEAD]["W"]}}
    assert (jax.tree_util.tree_structure(theirs)
            == jax.tree_util.tree_structure(plain.params_tree))
    x, y = _ids(2)
    got, got_g = jax.value_and_grad(lambda p: _loss(looped, p, x, y))(ours)
    want, want_g = jax.value_and_grad(lambda p: _loss(plain, p, x, y))(theirs)
    assert float(got) == float(want)
    same = np.testing.assert_array_equal
    same(got_g[EMBED]["W"], want_g[EMBED]["W"])
    same(got_g[HEAD]["W"], want_g["layer4_rnnoutputlayer"]["W"])
    same(got_g[LOOP]["norm_gamma"], want_g["layer3_rmsnormalization"]["gamma"])
    for i in (0, 1):
        for name, leaf in want_g[f"layer{i + 1}_sandwichtransformerblock"
                                 ].items():
            same(got_g[LOOP][f"block{i}_{name}"], leaf)
    assert not np.any(np.asarray(got_g[HEAD]["gate_W"]))
    plain.params_tree = theirs
    np.testing.assert_array_equal(np.asarray(looped.output(x)),
                                  np.asarray(plain.output(x)))


def test_one_pass_scores_as_rnn_output_layer_to_the_last_bit():
    head = ExitGatedOutputLayer(name="h", n_in=D, n_out=V, passes=1,
                                activation="softmax", weight_init="xavier")
    plain = RnnOutputLayer(name="p", n_in=D, n_out=V, has_bias=False,
                           activation="softmax", loss="sparse_mcxent")
    params, state = head.init_params(jax.random.PRNGKey(0), None)
    h = jax.random.normal(jax.random.PRNGKey(1), (2, T, D))
    _, y = _ids(3)
    mask = (jnp.arange(T)[None, :] < jnp.array([[T], [T // 2]])).astype(
        jnp.float32)
    for m in (None, mask):
        got, new = head.score_and_state(params, h, y, state, m)
        assert float(got) == float(plain.score({"W": params["W"]}, h, y, m))
        assert float(new["exit_entropy"]) == 0.0
        assert new["exit_mass"].tolist() == [1.0]


# ---------------------------------------------------------- one copy a leaf
def test_a_shared_leaf_is_held_once(tmp_path):
    """The tree, the optimizer's state and a saved model hold one copy a
    leaf whatever the passes; a loaded net goes on as the saved one."""
    from deeplearning4j_tpu.models.serialize import load_model, save_model

    nets = {passes: _looped(passes) for passes in (1, 4)}
    block = 4 * D + 4 * D * D + 3 * D * 128
    for net in nets.values():
        assert net.num_params() == V * D + 2 * block + D + D * V + D + 1
    net = nets[4]
    x, y = _ids(4)
    net.fit(x, y)
    shape = lambda tree: jax.tree_util.tree_map(jnp.shape, tree)
    assert shape(net.updater_state[LOOP]["m"]) == shape(net.params_tree[LOOP])
    path = os.path.join(tmp_path, "looped.zip")
    save_model(net, path)
    with zipfile.ZipFile(path) as zf:
        saved = np.load(zf.open("coefficients.npz")).files
    assert sorted(saved) == sorted(
        f"{layer}/{leaf}" for layer, leaves in net.params_tree.items()
        for leaf in leaves)
    back = load_model(path)
    assert back.layers[1].passes == 4 and len(back.layers[1].layers) == 2
    np.testing.assert_array_equal(np.asarray(back.output(x)),
                                  np.asarray(net.output(x)))
    back.fit(x, y)
    net.fit(x, y)
    for name, leaf in net.params_tree[LOOP].items():
        np.testing.assert_array_equal(np.asarray(back.params_tree[LOOP][name]),
                                      np.asarray(leaf))


def test_the_conf_round_trips():
    conf = _net(_tiny(), gradient_checkpointing=True).conf
    back = MultiLayerConfiguration.from_json(conf.to_json())
    assert back.to_json() == conf.to_json()
    loop = back.layers[1]
    assert isinstance(loop, LoopedStack) and loop.passes == 3
    assert [type(l) for l in loop.layers] == [SandwichTransformerBlock] * 2
    assert loop.layers[1].rope_base == 1e6 and loop.layers[1].name == "block1"
    assert isinstance(loop.norm, RMSNormalization) and loop.norm.eps == 1e-6
    assert back.layers[2].passes == 3 and back.layers[2].beta == 0.1


# ------------------------------------------------------------ checkpointing
def test_checkpointing_changes_no_gradient_and_counts_applications():
    """The unit is one block's one application: 2 blocks x 3 passes name
    two values each, and the loop layer is not wrapped a second time."""
    x, y = _ids(5)
    grads = {}
    for on in (False, True):
        net = _looped(3, checkpointing=on)
        grads[on] = jax.grad(lambda p: _loss(net, p, x, y))(net.params_tree)
        kept = _gauges("block_residuals_kept")[("MultiLayerNetwork",)]
        assert kept == (12 if on else 0)
        assert _gauges("loop_passes")[("MultiLayerNetwork",)] == 3
        assert _gauges("loop_block_applications")[
            ("MultiLayerNetwork",)] == 6
    for layer, leaves in grads[False].items():
        for name, leaf in leaves.items():
            _close(grads[True][layer][name], leaf, 1e-6)


def test_the_passes_are_one_traced_body():
    """Four passes lower to ONE loop whose body holds each block once."""
    net = _looped(4, checkpointing=True)
    x, y = _ids(6)
    text = jax.jit(jax.grad(lambda p: _loss(net, p, x, y))).lower(
        net.params_tree).as_text()
    assert text.count("stablehlo.while") >= 2       # forward and backward
    jaxpr = str(jax.make_jaxpr(lambda p: _loss(net, p, x, y))(
        net.params_tree))
    assert jaxpr.count("length=4") >= 1 and jaxpr.count("logistic") < 20


# ------------------------------------------------------------ the rotary base
def test_rope_base_is_the_references_rotation_and_10000_is_unchanged():
    ref = _reference()
    q = jax.random.normal(jax.random.PRNGKey(0), (1, T, 4, 16))
    positions = jnp.arange(T)
    _close(rope_rotate(q, positions, 1e6)[0], ref.rope(q[0], 1e6), 1e-6)
    np.testing.assert_array_equal(
        np.asarray(rope_rotate(q, positions)),
        np.asarray(rope_rotate(q, positions, 10000.0)))
    assert float(jnp.max(jnp.abs(rope_rotate(q, positions, 1e6)
                                 - rope_rotate(q, positions)))) > 0.1


@pytest.mark.parametrize("base", [10000.0, 1e6])
def test_attention_rotates_at_its_base_in_training_and_decode(base):
    """The layer's training pass is `rope_rotate` at its base round plain
    attention, and decoding token by token through its cache gives the
    same rows."""
    attn = MultiHeadAttention(name="a", n_in=D, n_out=D, num_heads=4,
                              causal=True, rope=True, rope_base=base,
                              bias=False, max_cache=T, activation="identity",
                              weight_init="xavier")
    assert MultiHeadAttention().rope_base == 10000.0
    assert SandwichTransformerBlock().rope_base == 10000.0
    params, _ = attn.init_params(jax.random.PRNGKey(0), None)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, T, D))
    full, _ = attn.apply(params, x)
    split = lambda w: (x @ w).reshape(1, T, 4, 16)
    pos = jnp.arange(T)
    q, k, v = (rope_rotate(split(params["Wq"]), pos, base),
               rope_rotate(split(params["Wk"]), pos, base),
               split(params["Wv"]))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / 4.0
    s = jnp.where(pos[None, :] <= pos[:, None], s, -1e30)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
    _close(full, o.reshape(1, T, D) @ params["Wo"], 1e-5)
    carry = attn.decode_carry(1)
    rows = []
    for t in range(T):
        row, carry = attn.apply(params, x[:, t:t + 1], state=carry)
        rows.append(row)
    _close(jnp.concatenate(rows, axis=1), full, 1e-5)


@pytest.mark.parametrize("theta", [10000, 500000.0])
def test_the_sparse_sandwich_model_hands_its_rope_theta_through(theta):
    from deeplearning4j_tpu.zoo import SparseSandwichTransformer

    with open(os.path.join(ROOT, "benchmarks", "tests", "configs",
                           "trinity_tiny.json"), encoding="utf-8") as fh:
        cfg = {**json.load(fh), "rope_theta": theta}
    conf = SparseSandwichTransformer(
        cfg, timesteps=cfg["input_shape"][0],
        experts_held=tuple(cfg["experts_held"]),
        vocabulary_held=cfg["vocabulary_held"]).conf()
    blocks = [l for l in conf.layers
              if isinstance(l, SandwichTransformerBlock)]
    assert blocks and all(b.rope_base == float(theta) for b in blocks)
    assert all(b._sub()[0].rope_base == float(theta) for b in blocks)


# ------------------------------------------------------------------ the gate
def test_the_gate_is_a_distribution_and_its_gauges_read_the_reference():
    """p sums to 1 over the passes at every token; after `fit()` the
    gauges `exit_mass{pass=}` and `exit_entropy` are the reference's means
    for the step's batch and the weights before it."""
    cfg, ref = _tiny(), _reference()
    params = ref.init_params(11, cfg)
    # a gate away from its start, so that the passes differ
    params[HEAD]["gate_b"] = params[HEAD]["gate_b"] + 0.7
    params[HEAD]["gate_W"] = params[HEAD]["gate_W"] * 20.0
    net = _net(cfg).init()
    net.params_tree = jax.tree_util.tree_map(jnp.array, params)
    x, y = _ids(7)
    states = ref.pass_states(params, x, cfg)
    p = ref.exit_distribution(params, states)
    np.testing.assert_allclose(np.asarray(jnp.sum(p, axis=0)), 1.0,
                               rtol=1e-6)
    head = net.layers[2]
    logp, ours = head.exit_distribution(params[HEAD], jnp.stack(states))
    _close(ours, p, 1e-5)
    np.testing.assert_allclose(np.asarray(jnp.sum(ours, axis=0)), 1.0,
                               rtol=1e-6)
    get_registry().reset()
    net.fit(DataSet(np.asarray(x), np.asarray(y)))
    mass = _gauges("exit_mass")
    for t in range(3):
        assert mass[(HEAD, str(t + 1))] == pytest.approx(
            float(jnp.mean(p[t])), rel=1e-5)
    assert _gauges("exit_entropy")[(HEAD,)] == pytest.approx(
        float(jnp.mean(ref.entropy(p))), rel=1e-5)
    assert 0.2 < _gauges("exit_entropy")[(HEAD,)] < np.log(3.0)


def test_the_gate_starts_near_a_half_a_quarter_and_the_rest():
    """At the assumed init (kernel normal 0.02, bias 0) four exits start
    near (1/2, 1/4, 1/8, 1/8): an entropy of 1.21, 87.5% of ln 4."""
    cfg, ref = _tiny(total_ut_steps=4), _reference()
    params = ref.init_params(12, cfg)
    x, _ = _ids(8)
    p = ref.exit_distribution(params, ref.pass_states(params, x, cfg))
    np.testing.assert_allclose(np.asarray(jnp.mean(p, axis=(1, 2))),
                               [0.5, 0.25, 0.125, 0.125], atol=0.02)
    assert float(jnp.mean(ref.entropy(p))) / np.log(4.0) == pytest.approx(
        0.875, abs=0.01)


@pytest.mark.parametrize("masked", [False, True])
def test_dense_labels_score_as_integer_labels(masked):
    sparse = _looped(3)
    dense = _looped(3, loss="mcxent")
    x, y = _ids(9)
    mask = None
    if masked:
        mask = (jnp.arange(T)[None, :] < jnp.array([[T], [T // 4]])).astype(
            jnp.float32)
    want = _loss(sparse, sparse.params_tree, x, y, mask)
    got = _loss(dense, sparse.params_tree, x, jax.nn.one_hot(y, V), mask)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    if masked:      # the masked rows change nothing
        y2 = y.at[1, T // 4:].set(0)
        assert float(_loss(sparse, sparse.params_tree, x, y2, mask)) \
            == pytest.approx(float(want), rel=1e-6)


def test_leaving_out_part_of_the_loss_is_seen():
    """Without the entropy term, or scoring the last exit alone, the loss
    moves by far more than any limit: 0.1 x 1.2 of 5.5, and more."""
    cfg, ref = _tiny(), _reference()
    params = ref.init_params(13, cfg)
    x, y = _ids(10)
    whole = float(ref.loss_fn(params, x, y))
    states = ref.pass_states(params, x, cfg)
    p = ref.exit_distribution(params, states)
    ce = ref.exit_losses(params, states, y)
    no_entropy = float(jnp.mean(jnp.sum(p * ce, axis=0)))
    last_only = float(jnp.mean(ce[-1]))
    assert abs(no_entropy - whole) > 0.01 * whole
    assert abs(last_only - whole) > 0.01 * whole


# ------------------------------------------------------------------ refusals
@pytest.mark.parametrize("member,why", [
    (BatchNormalization(), "keeps"),
    (SandwichTransformerBlock(num_heads=4, n_experts=4, moe_k=2,
                              expert_width=32), "keeps"),
    (DenseLayer(n_out=2 * D), "keeps its shape")])
def test_a_member_with_state_or_another_shape_is_refused_by_name(member,
                                                                 why):
    with pytest.raises(ValueError, match=why) as err:
        MultiLayerNetwork(_conf(
            LoopedStack(layers=(_block(), member), passes=2),
            ExitGatedOutputLayer(n_out=V, passes=2,
                                 activation="softmax"))).init()
    assert "block1" in str(err.value)
    assert type(member).__name__ in str(err.value)


def test_a_head_that_scores_other_passes_than_it_gets_is_refused():
    net = MultiLayerNetwork(_conf(
        LoopedStack(layers=(_block(),), passes=2),
        ExitGatedOutputLayer(n_out=V, passes=3, activation="softmax"))).init()
    x, y = _ids(11)
    with pytest.raises(ValueError, match="3 passes"):
        _loss(net, net.params_tree, x, y)
    with pytest.raises(ValueError, match="hinge"):
        _looped(2, loss="hinge")


def test_decode_names_the_layer_it_cannot_serve():
    net = _net(_tiny()).init()
    with pytest.raises(NotImplementedError, match=LOOP):
        net.layers[1].decode_carry(1)
    with pytest.raises(NotImplementedError, match="LoopedStack"):
        net.rnn_time_step(np.zeros((1, 1), np.int32))


@pytest.mark.parametrize("key,value", [
    ("layer_types", ["full_attention", "sliding_attention"]),
    ("layer_types", ["full_attention"]),
    ("tie_word_embeddings", True), ("hidden_act", "gelu")])
def test_a_configuration_the_builder_does_not_know_is_an_error(key, value):
    with pytest.raises(ValueError, match=key.split("_")[0]):
        _net(_tiny(**{key: value}))


def test_inference_runs_every_pass_and_reads_the_last():
    cfg, ref = _tiny(), _reference()
    params = ref.init_params(14, cfg)
    net = _net(cfg).init()
    net.params_tree = jax.tree_util.tree_map(jnp.array, params)
    x, _ = _ids(12)
    last = ref.pass_states(params, x, cfg)[-1]
    want = jax.nn.softmax(last @ params[HEAD]["W"], axis=-1)
    _close(net.output(np.asarray(x)), want, 1e-5)
    acts = net.feed_forward(np.asarray(x))
    assert acts[1].shape == (3, 2, T, D) and acts[2].shape == (2, T, V)
