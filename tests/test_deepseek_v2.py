"""Latent attention, group-limited routing and the model built from them
(`deepseek_v2`), small, on the CPU, in float32 with seeded weights: the
three latent-attention kernels (interpret mode) against the dense form,
what they leave unread poisoned with NaN, YaRN's constants, the
`LatentAttention` layer against the benchmark's plain reference, `route`
with groups against a per-token loop, the shares of heads and of experts
adding up to the uncut reference, and `zoo.LatentSparseTransformer` against
the reference, loss and every leaf's gradient."""

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.inputs import InputType
from deeplearning4j_tpu.nn.layers.attention import (
    LatentAttention, PreNormBlock, rope_rotate, yarn_factors, yarn_inv_freq,
)
from deeplearning4j_tpu.ops import latent_attention as la
from deeplearning4j_tpu.parallel import moe
from deeplearning4j_tpu.parallel.moe import ExpertFeedForward

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HI = jax.lax.Precision.HIGHEST
PUBLISHED_YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                  "mscale": 0.707, "mscale_all_dim": 0.707,
                  "original_max_position_embeddings": 4096, "type": "yarn"}


def _normal(seed, *shapes):
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    return [jax.random.normal(k, s, jnp.float32) for k, s in zip(keys, shapes)]


def _close(got, want, tol):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert np.linalg.norm(got - want) <= tol * max(np.linalg.norm(want),
                                                   1e-30)


def _tiny(**changes):
    with open(os.path.join(ROOT, "benchmarks", "tests", "configs",
                           "deepseek_v2_tiny.json"), encoding="utf-8") as fh:
        return {**json.load(fh), **changes}


def _reference():
    from benchmarks import harness

    return harness.load_module("reference", "deepseek_v2.py")


# ------------------------------------------------------------ the kernels
def _core_case(t=256, b=2, h=3, dn=16, dr=8, dv=24, seed=0):
    return _normal(seed, (b, t, h, dn + dr), (b, t, h, dn), (b, t, dr),
                   (b, t, h, dv), (b, t, h, dv))


@pytest.mark.parametrize("block_q,block_k", [(64, 128), (128, 64), (32, 32)])
def test_latent_kernels_are_the_dense_form(block_q, block_k):
    """Values and all four gradients, the rope key's summed over the
    heads; a query 24 wide against values of 24 and keys of 16 + 8."""
    q, kn, kr, v, w = _core_case()
    kernels = lambda *a: la.latent_attention(*a, 0.2, block_q, block_k, True)
    dense = lambda *a: la.dense_latent_attention(*a, 0.2)
    _close(kernels(q, kn, kr, v), dense(q, kn, kr, v), 1e-5)
    loss = lambda fn: (lambda *a: jnp.sum(fn(*a) * w))
    got = jax.grad(loss(kernels), (0, 1, 2, 3))(q, kn, kr, v)
    want = jax.grad(loss(dense), (0, 1, 2, 3))(q, kn, kr, v)
    assert got[2].shape == kr.shape
    for a, b in zip(got, want):
        _close(a, b, 1e-5)


def test_the_dense_form_is_two_products_under_one_softmax():
    q, kn, kr, v, _ = _core_case(t=40, b=1)
    s = (jnp.einsum("bqhd,bkhd->bhqk", q[..., :16], kn, precision=HI)
         + jnp.einsum("bqhd,bkd->bhqk", q[..., 16:], kr, precision=HI)) * 0.2
    s = jnp.where(jnp.tril(jnp.ones((40, 40), bool)), s, -jnp.inf)
    want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v,
                      precision=HI)
    _close(la.dense_latent_attention(q, kn, kr, v, 0.2), want, 1e-5)


@pytest.mark.parametrize("t,kind", [(200, "dense"), (64, "dense"),
                                    (256, "kernel"), (8192, "kernel")])
def test_a_length_the_kernels_do_not_tile_takes_the_dense_form(
        monkeypatch, t, kind):
    from deeplearning4j_tpu.ops.kernel_defaults import latent_policy

    assert latent_policy(t).kind == "dense"          # no TPU here
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert latent_policy(t).kind == kind
    monkeypatch.setenv("DL4J_TPU_ATTN", "dense")
    assert latent_policy(t).kind == "dense"


def test_the_kernels_refuse_a_length_they_do_not_tile():
    q, kn, kr, v, _ = _core_case(t=200, b=1)
    with pytest.raises(ValueError, match="200"):
        la.latent_attention(q, kn, kr, v, 0.2, 128, 128, False)


@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_tiles_above_the_diagonal_are_never_read(direction):
    """Forward: the last K tile (keys 384 on) holds NaN in both keys and
    the values; it lies above the diagonal for every earlier Q tile, whose
    rows' outputs and dQ are those of the same tensors with zeros there.
    Backward: the first Q tile's rows (and their cotangent) hold NaN; for
    every later K tile they lie above the diagonal, and dK of both kinds
    and dV of those keys are what zeros there give. A kernel that computed
    a dead tile, or fetched it and multiplied by zero, would show."""
    t, tile = 512, 128
    q, kn, kr, v, w = _core_case(t=t, b=1)
    fn = lambda *a: la.latent_attention(*a, 0.2, tile, tile, True)
    grads = lambda q, kn, kr, v, w: jax.grad(
        lambda *a: jnp.sum(fn(*a) * w), (0, 1, 2, 3))(q, kn, kr, v)
    if direction == "forward":
        dead, rows = slice(t - tile, t), slice(0, t - tile)
        put = lambda a, x: a.at[:, dead].set(x)
        runs = [(fn(q, put(kn, x), put(kr, x), put(v, x)),
                 grads(q, put(kn, x), put(kr, x), put(v, x), w)[0])
                for x in (jnp.nan, 0.0)]
    else:
        dead, rows = slice(0, tile), slice(tile, t)
        put = lambda a, x: a.at[:, dead].set(x)
        runs = [grads(put(q, x), kn, kr, v, put(w, x))[1:]
                for x in (jnp.nan, 0.0)]
    for got, want in zip(*runs):
        assert bool(jnp.all(jnp.isfinite(got[:, rows])))
        _close(got[:, rows], want[:, rows], 1e-6)


# ------------------------------------------------------------------- yarn
def test_yarn_frequencies_and_scale_are_the_published_ones():
    """64 rope lanes, 32 pairs: pairs 0 to 10 unscaled, 23 to 31 divided
    by 40, a linear ramp between (low 10, high 23); cos and sin times 1;
    the softmax scale 0.114721."""
    inv = yarn_inv_freq(64, 10000.0, PUBLISHED_YARN)
    plain = [10000.0 ** (-2 * i / 64) for i in range(32)]
    assert len(inv) == 32 and inv[0] == 1.0
    for i in range(11):
        assert inv[i] == pytest.approx(plain[i], rel=1e-12)
    for i in range(23, 32):
        assert inv[i] == pytest.approx(plain[i] / 40, rel=1e-12)
    for i in range(11, 23):
        ramp = (i - 10) / 13
        assert inv[i] == pytest.approx(
            plain[i] * (1 - ramp) + plain[i] / 40 * ramp, rel=1e-12)
    assert plain[12] / 40 < inv[12] < plain[12]
    amplitude, sharper = yarn_factors(PUBLISHED_YARN)
    assert amplitude == 1.0
    assert 192 ** -0.5 * sharper == pytest.approx(0.114721, abs=5e-7)
    layer = LatentAttention(qk_nope_head_dim=128, qk_rope_head_dim=64,
                            rope_scaling=PUBLISHED_YARN)
    freqs, amplitude, scale = layer._rope
    assert freqs == inv and amplitude == 1.0
    assert scale == pytest.approx(0.114721, abs=5e-7)
    # and the reference works the same numbers out on its own
    ref_inv, ref_amp, ref_scale = _reference().yarn(
        {"qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
         "rope_theta": 10000, "rope_scaling": PUBLISHED_YARN})
    np.testing.assert_allclose(ref_inv, inv, rtol=1e-12)
    assert ref_amp == 1.0 and ref_scale == pytest.approx(scale, rel=1e-12)


def test_rope_rotate_with_given_frequencies():
    x, = _normal(3, (2, 12, 3, 8))
    pos = jnp.arange(12)
    plain = 10000.0 ** (-jnp.arange(4, dtype=jnp.float32) / 4)
    # the computed frequencies, handed in, give the default path's bits
    assert bool(jnp.all(rope_rotate(x, pos) == rope_rotate(
        x, pos, inv_freq=plain)))
    assert bool(jnp.all(rope_rotate(x, pos, 500.0) == rope_rotate(
        x, pos, inv_freq=500.0 ** (-jnp.arange(4, dtype=jnp.float32) / 4))))
    # halved frequencies at twice the position are the same angles
    _close(rope_rotate(x, 2 * pos, inv_freq=plain / 2), rope_rotate(x, pos),
           1e-6)
    with pytest.raises(ValueError, match="3 frequencies"):
        rope_rotate(x, pos, inv_freq=(1.0, 0.5, 0.25))


# -------------------------------------------------------------- the layer
def _layer(cfg, held=None):
    return LatentAttention(
        n_in=cfg["hidden_size"], n_out=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"], heads_held=held,
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], rope_theta=cfg["rope_theta"],
        rope_scaling=cfg["rope_scaling"], norm_eps=cfg["rms_norm_eps"],
        weight_init="xavier", name="mla")


def _mixer_leaves(block):
    return {k[6:]: v for k, v in block.items() if k.startswith("mixer_")}


def _head_slices(cfg, p, first, count):
    """The leaves of the share that holds heads `first` to `first + count`
    out of the uncut layer's `p`."""
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    cut = lambda a, w, axis: jax.lax.slice_in_dim(
        a, first * w, (first + count) * w, axis=axis)
    return {**p, "Wqb": cut(p["Wqb"], dn + dr, 1),
            "Wkvb": cut(p["Wkvb"], dn + dv, 1), "Wo": cut(p["Wo"], dv, 0)}


@pytest.mark.parametrize("t", [128, 50])
def test_latent_attention_layer_is_the_reference_mla(t):
    """Values and every leaf's gradient, two of four heads held, at twice
    the rope's original length and at a length no tile divides."""
    cfg, ref = _tiny(heads_held=[1, 2]), _reference()
    p = _mixer_leaves(ref.init_params(3, cfg)["layer1_prenormblock"])
    x, w = _normal(4, (1, t, 32), (1, t, 32))
    layer = _layer(cfg, (1, 2))
    shapes, _ = jax.eval_shape(lambda: layer.init_params(
        jax.random.PRNGKey(0), InputType.recurrent(32, t)))
    assert {k: v.shape for k, v in shapes.items()} == {
        k: v.shape for k, v in p.items()}
    got = lambda p, x: layer.apply(p, x)[0]
    want = lambda p, x: ref.mla({"mixer_" + k: v for k, v in p.items()},
                                x[0], cfg, "float32")[None]
    _close(got(p, x), want(p, x), 1e-5)
    gp, gx = jax.grad(lambda p, x: jnp.sum(got(p, x) * w), (0, 1))(p, x)
    wp, wx = jax.grad(lambda p, x: jnp.sum(want(p, x) * w), (0, 1))(p, x)
    _close(gx, wx, 1e-4)
    for name in p:
        _close(gp[name], wp[name], 1e-4)


def test_the_shares_of_the_heads_add_up_to_the_whole_layer():
    """Four heads as four shares of one: each share's `Wo` output is what
    one device of a 4-way tensor-parallel layer gives before the
    all-reduce, and their sum is the uncut reference's attention output.
    A share's own init is the whole layer's, cut: a head's slices come
    from its published index."""
    cfg, ref = _tiny(heads_held=[0, 4]), _reference()
    p = _mixer_leaves(ref.init_params(5, cfg)["layer1_prenormblock"])
    x, = _normal(6, (2, 128, 32))
    want = jnp.stack([ref.mla({"mixer_" + k: v for k, v in p.items()}, seq,
                              cfg, "float32") for seq in x])
    whole = _layer(cfg)
    _close(whole.apply(p, x)[0], want, 1e-5)
    total = sum(_layer(cfg, (h, 1)).apply(_head_slices(cfg, p, h, 1), x)[0]
                for h in range(4))
    _close(total, want, 1e-5)
    key, kind = jax.random.PRNGKey(1), InputType.recurrent(32, 128)
    own, _ = _layer(cfg, (1, 2)).init_params(key, kind)
    cut = _head_slices(cfg, whole.init_params(key, kind)[0], 1, 2)
    for name in own:
        assert bool(jnp.all(own[name] == cut[name])), name


def test_heads_held_outside_the_layer_and_decode_are_refused():
    cfg = _tiny()
    with pytest.raises(ValueError, match="heads_held"):
        _layer(cfg, (3, 2)).init_params(jax.random.PRNGKey(0),
                                        InputType.recurrent(32, 8))
    with pytest.raises(NotImplementedError, match="mla"):
        _layer(cfg).decode_carry(1)
    with pytest.raises(ValueError, match="linear"):
        LatentAttention(n_in=32, n_out=32, rope_scaling={
            **PUBLISHED_YARN, "type": "linear"}).init_params(
            jax.random.PRNGKey(0), InputType.recurrent(32, 8))


# ------------------------------------------------------------ the routing
def _route_loop(s, k, n_group, topk_group):
    """A token at a time, plainly: the groups by their best score (of
    equal ones the lower), then the experts inside them (of equal ones
    the lower)."""
    e = s.shape[1]
    per = e // n_group
    out = []
    for row in np.asarray(s, np.float64):
        best = [max(row[g * per:(g + 1) * per]) for g in range(n_group)]
        groups = sorted(range(n_group), key=lambda g: (-best[g], g))
        kept = set(groups[:topk_group])
        masked = [row[i] if i // per in kept else 0.0 for i in range(e)]
        out.append(sorted(range(e), key=lambda i: (-masked[i], i))[:k])
    return np.asarray(out)


def _routed(logits, **kw):
    """`route` over logits given as they are: a router that is the
    identity."""
    e = logits.shape[1]
    return moe.route(logits, jnp.eye(e, dtype=jnp.float32), None,
                     score="softmax", route_norm=False, route_scale=16.0,
                     **kw)


@pytest.mark.parametrize("case", ["random", "ties_between_groups",
                                  "ties_inside_a_group", "all_equal"])
def test_group_limited_routing_is_the_per_token_loop(case):
    n, e, k, groups, kept = 64, 24, 5, 6, 2
    logits = np.asarray(_normal(7, (n, e))[0])
    if case == "ties_between_groups":
        # every group's best is the same number: the lower groups win
        logits = np.minimum(logits, 1.0)
        logits[:, ::e // groups] = 1.0
    elif case == "ties_inside_a_group":
        logits = np.round(logits * 2) / 2        # many equal scores
    elif case == "all_equal":
        logits = np.zeros_like(logits)
    experts, weights = _routed(jnp.asarray(logits), k=k, n_group=groups,
                               topk_group=kept)
    prob = np.asarray(jax.nn.softmax(jnp.asarray(logits), axis=-1))
    want = _route_loop(prob, k, groups, kept)
    assert (np.asarray(experts) == want).all()
    # the weights are the chosen scores themselves, times the scale, and
    # do not sum to it
    np.testing.assert_allclose(
        weights, 16.0 * np.take_along_axis(prob, want, -1), rtol=1e-6)
    assert len({int(i) // (e // groups) for i in want[0]}) <= kept
    if case == "all_equal":
        assert (want == np.arange(k)).all()


def test_one_group_is_the_flat_choice_bit_for_bit():
    x, router, bias = _normal(8, (32, 16), (16, 24), (24,))
    for score, norm in (("sigmoid", True), ("softmax", False)):
        kw = dict(k=4, score=score, route_norm=norm, route_scale=2.448)
        flat = moe.route(x, router, 0.01 * bias, **kw)
        one = moe.route(x, router, 0.01 * bias, n_group=1, topk_group=1, **kw)
        s = (jax.nn.sigmoid if score == "sigmoid" else
             lambda z: jax.nn.softmax(z, axis=-1))(
            jnp.dot(x, router, preferred_element_type=jnp.float32))
        _, by_hand = jax.lax.top_k(s + 0.01 * bias, 4)
        assert (np.asarray(one[0]) == np.asarray(by_hand)).all()
        for a, b in zip(flat, one):
            assert bool(jnp.all(a == b))
        traced = str(jax.make_jaxpr(lambda x: moe.route(
            x, router, None, n_group=1, **kw))(x))
        assert traced == str(jax.make_jaxpr(lambda x: moe.route(
            x, router, None, **kw))(x))


def test_groups_that_do_not_divide_the_experts_are_refused():
    with pytest.raises(ValueError, match="groups"):
        _routed(jnp.zeros((4, 10)), k=2, n_group=4, topk_group=2)
    with pytest.raises(ValueError, match="chosen"):
        _routed(jnp.zeros((4, 12)), k=7, n_group=4, topk_group=2)


def _expert_layer(cfg, held):
    return ExpertFeedForward(
        n_in=cfg["hidden_size"], width=cfg["moe_intermediate_size"],
        n_experts=cfg["n_routed_experts"], held=held,
        k=cfg["num_experts_per_tok"], score="softmax", route_norm=False,
        route_scale=cfg["routed_scaling_factor"],
        n_shared=cfg["n_shared_experts"], n_group=cfg["n_group"],
        topk_group=cfg["topk_group"], weight_init="xavier")


def test_the_shares_of_the_experts_add_up_to_the_whole_layer():
    """Twenty experts in four groups as twenty shares of one, each share
    what one device computes; the shared experts are on every device and
    counted once. Their sum is the uncut reference's expert layer, every
    pair fell on exactly one share and none was dropped; a token is
    counted by the shares its pairs fell on."""
    cfg = _tiny(n_routed_experts=20, experts_held=[0, 20])
    ref = _reference()
    p = {k[4:]: v for k, v in
         ref.init_params(9, cfg)["layer2_prenormblock"].items()
         if k.startswith("moe_")}
    x, = _normal(10, (2, 64, 32))
    tokens = x.reshape(-1, 32)
    want = ref.experts({"moe_" + k: v for k, v in p.items()}, tokens, cfg,
                       "float32").reshape(x.shape)
    got, counters = _expert_layer(cfg, None).apply(p, x)
    _close(got, want, 1e-5)
    assert int(counters["moe_tokens_held"]) == 128
    total, pairs, reach = 0.0, 0, 0
    for e in range(20):
        share = (_expert_layer(cfg, (e, 1)) if e == 0 else
                 _expert_layer({**cfg, "n_shared_experts": 0}, (e, 1)))
        sp = {k: v for k, v in p.items()
              if e == 0 or not k.startswith("shared")}
        sp.update({k: p[k][e:e + 1] for k in ("w1", "w3", "w2")})
        y, counters = share.apply(sp, x)
        total = total + y
        pairs += int(counters["moe_pairs_held"])
        reach += int(counters["moe_tokens_held"])
        assert int(counters["moe_pairs_dropped"]) == 0
    _close(total, want, 1e-5)
    assert pairs == reach == 128 * cfg["num_experts_per_tok"]
    # and the reference chooses what the program chooses
    sel, wt = ref.route({"moe_router": p["router"]}, tokens, cfg)
    experts, weights = moe.route(
        tokens, p["router"], None, k=3, score="softmax", route_norm=False,
        route_scale=16, n_group=4, topk_group=2)
    assert (np.asarray(sel) == np.asarray(experts)).all()
    np.testing.assert_allclose(wt, weights, rtol=1e-6)


def test_tokens_held_counts_tokens_and_flat_layers_do_not_have_it():
    cfg = _tiny()
    layer = _expert_layer(cfg, (0, 4))
    p, state = layer.init_params(jax.random.PRNGKey(2),
                                 InputType.recurrent(32, 8))
    assert set(state) == set(moe.COUNTERS) | {moe.TOKENS_HELD}
    x, = _normal(11, (96, 32))
    _, counters = layer.apply(p, x)
    experts, _ = moe.route(x, p["router"], None, k=3, score="softmax",
                           route_norm=False, route_scale=16.0, n_group=4,
                           topk_group=2)
    here = np.asarray(experts) < 4
    assert int(counters["moe_tokens_held"]) == here.any(-1).sum()
    assert int(counters["moe_pairs_held"]) == here.sum()
    flat = ExpertFeedForward(n_in=32, width=16, n_experts=16, held=(0, 4),
                             k=3, weight_init="xavier")
    assert set(flat.init_params(jax.random.PRNGKey(2),
                                InputType.recurrent(32, 8))[1]) \
        == set(moe.COUNTERS)
    assert set(flat.apply(flat.init_params(
        jax.random.PRNGKey(2), InputType.recurrent(32, 8))[0], x)[1]) \
        == set(moe.COUNTERS)


# -------------------------------------------------------------- the model
def _net(cfg, **kw):
    from deeplearning4j_tpu.models import MultiLayerNetwork
    from deeplearning4j_tpu.zoo import LatentSparseTransformer

    return MultiLayerNetwork(LatentSparseTransformer(
        cfg, timesteps=cfg["input_shape"][0],
        heads_held=tuple(cfg["heads_held"]),
        experts_held=tuple(cfg["experts_held"]),
        vocabulary_held=cfg["vocabulary_held"], **kw).conf())


def _gauges(name):
    from deeplearning4j_tpu.observe import get_registry

    return {dict(g.labels).get("layer"): g.value
            for g in get_registry().series() if g.name == name}


@pytest.mark.parametrize("checkpointing", [False, True])
def test_zoo_model_is_the_plain_reference(checkpointing):
    """Loss to 1e-5 and every leaf's gradient to 1e-4, 128 tokens (twice
    the rope's original 64), float32."""
    cfg, ref = _tiny(), _reference()
    params = ref.init_params(7, cfg)
    net = _net(cfg, gradient_checkpointing=checkpointing).init()
    assert (jax.tree_util.tree_structure(params)
            == jax.tree_util.tree_structure(net.params_tree))
    rng = np.random.default_rng(0)
    x, y = (jnp.asarray(rng.integers(0, 600, (2, 128)), jnp.int32)
            for _ in range(2))
    want, want_g = jax.value_and_grad(ref.loss_fn)(params, x, y)
    got, got_g = jax.value_and_grad(
        lambda p: net._loss(p, net.state_tree, x, y, None, None, None,
                            train=True)[0])(params)
    assert abs(float(got) - float(want)) <= 1e-5 * float(want)
    for layer, leaves in want_g.items():
        for name, leaf in leaves.items():
            _close(got_g[layer][name], leaf, 1e-4)


def test_the_model_counts_what_the_configuration_says():
    """The reference's count of parameters and of multiply-adds, at the
    published widths and the cell's cut."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "deepseek_v2.json"), encoding="utf-8") as fh:
        cfg = json.load(fh)
    ref = _reference()
    count = sum(math.prod(shape) for i in range(cfg["num_hidden_layers"])
                for shape in ref.layer_shapes(cfg, i).values())
    count += 2 * cfg["vocabulary_held"] * cfg["hidden_size"] \
        + cfg["hidden_size"]
    assert count == 1_493_959_680
    assert ref.forward_macs(cfg) / 8192 == pytest.approx(911.4e6, rel=1e-4)
    assert ref.yarn(cfg)[2] == pytest.approx(0.114721, abs=5e-7)


def test_fit_publishes_the_routing_gauges_by_layer():
    from deeplearning4j_tpu.data.dataset import DataSet

    cfg = _tiny()
    net = _net(cfg, gradient_checkpointing=True).init()
    rng = np.random.default_rng(2)
    x, y = (rng.integers(0, 600, (2, 128)).astype(np.int32) for _ in range(2))
    net.fit(DataSet(x, y))
    assert not net.state_tree["layer1_prenormblock"]      # the dense layer
    for name in ("layer2_prenormblock", "layer3_prenormblock"):
        state = net.state_tree[name]
        assert int(state["moe_pairs_routed"]) == 2 * 128 * 3
        assert int(state["moe_pairs_dropped"]) == 0
        assert 0 < int(state["moe_tokens_held"]) <= int(
            state["moe_pairs_held"]) < 2 * 128 * 3
        for counter in (*moe.COUNTERS, moe.TOKENS_HELD):
            assert _gauges(counter)[name] == int(state[counter]), counter


def test_decode_names_the_layer_it_cannot_serve():
    net = _net(_tiny()).init()
    block = net.layers[1]
    with pytest.raises(NotImplementedError, match=block.name):
        block.decode_carry(1)


@pytest.mark.parametrize("key,value", [
    ("topk_method", "noaux_tc"), ("scoring_func", "sigmoid"),
    ("rope_scaling", {**PUBLISHED_YARN, "type": "longrope"}),
    ("moe_layer_freq", 2)])
def test_a_configuration_the_builder_does_not_know_is_an_error(key, value):
    from deeplearning4j_tpu.zoo import LatentSparseTransformer

    with pytest.raises(ValueError, match=key):
        LatentSparseTransformer({**_tiny(), key: value})


def test_the_conf_round_trips_and_a_plain_block_is_what_it_was():
    from deeplearning4j_tpu.utils.serde import from_json, to_json

    conf = _net(_tiny()).conf
    again = from_json(to_json(conf))
    dense, sparse = again.layers[1], again.layers[2]
    assert dense.ffn is None and isinstance(sparse.ffn, ExpertFeedForward)
    assert (sparse.ffn.n_group, sparse.ffn.topk_group) == (4, 2)
    assert sparse.ffn._held == (0, 4)
    assert isinstance(sparse.mixer, LatentAttention)
    assert sparse.mixer._held == (0, 2)
    assert sparse.mixer._rope == conf.layers[2].mixer._rope
    kind = InputType.recurrent(32, 8)
    leaves = lambda block: {k: v.shape for k, v in jax.eval_shape(
        lambda: block.infer_n_in(kind).init_params(
            jax.random.PRNGKey(0), kind))[0].items()}
    assert leaves(sparse) == leaves(conf.layers[2])
    assert {k for k in leaves(sparse) if not k.startswith("mixer_")} == {
        "ln1_g", "ln2_g", "moe_router", "moe_w1", "moe_w3", "moe_w2",
        "moe_shared_w1", "moe_shared_w3", "moe_shared_w2"}
    assert {k for k in leaves(dense) if not k.startswith("mixer_")} == {
        "ln1_g", "ln2_g", "ffn_w1", "ffn_w3", "ffn_w2"}
    plain = PreNormBlock(n_in=32, mixer=conf.layers[1].mixer, ffn_width=64,
                         name="b", weight_init="xavier")
    assert plain.ffn is None and leaves(plain) == leaves(dense)
