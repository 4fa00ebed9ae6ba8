"""The one-sublayer block, the Mamba-2 share by groups with its per-group
gated norm, the LatentMoE form of the expert layer, the two-term head and
the model built from them (`nemotron_3_super`), small, on the CPU, with
seeded weights: `zoo.HybridLatentExpertTransformer` against the
benchmark's plain reference, loss, both terms, every leaf's gradient and
three Adam steps; the shares of the groups and of the experts adding up to
the uncut layer; the accepted layers' defaults unchanged to the bit; the
head with weight 0 against `RnnOutputLayer`; save and load."""

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.inputs import InputType
from deeplearning4j_tpu.nn.layers import (
    MultiHeadAttention, MultiTokenOutputLayer, PreNormSublayer,
    RnnOutputLayer, SelectiveStateSpace,
)
from deeplearning4j_tpu.nn.layers.attention import rms_norm
from deeplearning4j_tpu.optim.updaters import Adam
from deeplearning4j_tpu.parallel import moe
from deeplearning4j_tpu.parallel.moe import ExpertFeedForward

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HEAD = "layer6_multitokenoutputlayer"


def _normal(seed, *shapes):
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    return [jax.random.normal(k, s, jnp.float32) for k, s in zip(keys, shapes)]


def _close(got, want, tol):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert np.linalg.norm(got - want) <= tol * max(np.linalg.norm(want),
                                                   1e-30)


def _same_bits(got, want):
    assert got.dtype == want.dtype and got.shape == want.shape
    assert bool(jnp.all(got == want))


def _tiny(**changes):
    with open(os.path.join(ROOT, "benchmarks", "tests", "configs",
                           "nemotron_3_super_tiny.json"),
              encoding="utf-8") as fh:
        return {**json.load(fh), **changes}


def _reference():
    from benchmarks import harness

    return harness.load_module("reference", "nemotron_3_super.py")


def _inner(leaves):
    """A sublayer's `f_` leaves as the layer inside names them."""
    return {k[2:]: v for k, v in leaves.items() if k.startswith("f_")}


# ------------------------------------------------- the mixer, by groups
def _ssm_layer(cfg, held=None, **kw):
    return SelectiveStateSpace(
        n_in=cfg["hidden_size"], n_out=cfg["hidden_size"],
        num_heads=cfg["mamba_num_heads"], heads_held=held,
        head_dim=cfg["mamba_head_dim"], state_size=cfg["ssm_state_size"],
        n_groups=cfg["n_groups"], conv_kernel=cfg["conv_kernel"],
        chunk=cfg["chunk_size"], norm_eps=cfg["layer_norm_epsilon"],
        activation="identity", weight_init="xavier", name="ssm", **kw)


def _group_slices(cfg, p, first, count):
    """The leaves of the groups [first, first + count), heads, B and C,
    cut out of the whole layer's `p`."""
    h, hp, n = (cfg["mamba_num_heads"], cfg["mamba_head_dim"],
                cfg["ssm_state_size"])
    g, per = cfg["n_groups"], cfg["mamba_num_heads"] // cfg["n_groups"]
    inner = h * hp
    heads = np.arange(first * per, (first + count) * per)
    lanes = np.arange(heads[0] * hp, (heads[-1] + 1) * hp)
    b = np.arange(first * n, (first + count) * n)
    bc = np.concatenate([b, g * n + b])
    cols = np.concatenate([lanes, inner + lanes, 2 * inner + bc,
                           2 * inner + 2 * g * n + heads])
    chans = np.concatenate([lanes, inner + bc])
    return {"in_proj": p["in_proj"][:, cols], "conv_w": p["conv_w"][:, chans],
            "conv_b": p["conv_b"][chans], "dt_bias": p["dt_bias"][heads],
            "A_log": p["A_log"][heads], "D": p["D"][heads],
            "norm": p["norm"][lanes], "out_proj": p["out_proj"][lanes]}


@pytest.mark.parametrize("t", [128, 50])
def test_a_layer_of_two_groups_is_the_reference_mixer(t):
    """All eight heads in two groups of B and C, the gated norm over each
    group's 32 lanes on its own, against the reference's token-at-a-time
    mixer, at four chunks of 32 and at a ragged 50 tokens."""
    cfg, ref = _tiny(heads_held=[0, 8]), _reference()
    p = ref.init_params(3, cfg)["layer1_prenormsublayer"]
    x, = _normal(4, (2, t, 32))
    want = jnp.stack([ref.mamba(p, seq, cfg, "float32") for seq in x])
    got, state = _ssm_layer(cfg).apply(_inner(p), x)
    _close(got, want, 1e-5)
    assert 0.0 < float(state["ssm_chunk_carry"]) < 1.0


@pytest.mark.parametrize("groups", [2, 4])
def test_the_shares_by_groups_add_up_with_no_exchange(groups):
    """Eight heads in `groups` groups as that many shares of one group:
    each share norms its own group's lanes, nothing crosses the shares but
    `out_proj`'s partial sums, and their plain sum is the uncut layer. A
    share's own init is the whole layer's, cut."""
    cfg = _tiny(heads_held=[0, 8], n_groups=groups)
    key, kind = jax.random.PRNGKey(5), InputType.recurrent(32, 128)
    whole = _ssm_layer(cfg)
    p, _ = whole.init_params(key, kind)
    p = {**p, "norm": 1.0 + 0.1 * _normal(6, p["norm"].shape)[0],
         "conv_b": 0.1 * _normal(7, p["conv_b"].shape)[0]}
    x, = _normal(8, (2, 128, 32))
    want = whole.apply(p, x)[0]
    per = 8 // groups
    parts = [_ssm_layer(cfg, (g * per, per)).apply(
        _group_slices(cfg, p, g, 1), x)[0] for g in range(groups)]
    _close(sum(parts), want, 1e-5)
    assert np.linalg.norm(parts[0] - want) > 1e-2 * np.linalg.norm(want)
    for g in range(groups):
        own, _ = _ssm_layer(cfg, (g * per, per)).init_params(key, kind)
        cut = _group_slices(cfg, whole.init_params(key, kind)[0], g, 1)
        for name in own:
            _same_bits(own[name], cut[name])


def test_the_gated_norm_is_per_group():
    """Scaling one group's z leaves the other group's normed lanes as they
    were: nothing of the norm's statistic crosses groups. (The lanes are
    read through an identity in `out_proj`'s place.)"""
    cfg = _tiny(heads_held=[0, 8])
    layer = SelectiveStateSpace(**{
        **{f.name: getattr(_ssm_layer(cfg), f.name)
           for f in SelectiveStateSpace.__dataclass_fields__.values()},
        "n_out": 64})
    p, _ = layer.init_params(jax.random.PRNGKey(9),
                             InputType.recurrent(32, 64))
    p = {**p, "out_proj": jnp.eye(64)}
    x, = _normal(10, (1, 64, 32))
    # z's columns are the first 64 of in_proj; group 1's are 32..63
    louder = {**p, "in_proj": p["in_proj"].at[:, 32:64].multiply(3.0)}
    a, b = layer.apply(p, x)[0], layer.apply(louder, x)[0]
    _close(b[..., :32], a[..., :32], 1e-6)      # group 0 untouched
    assert np.linalg.norm(b[..., 32:] - a[..., 32:]) > 1e-3


@pytest.mark.parametrize("held,kw,match", [
    ((0, 2), {}, "whole groups"), ((2, 4), {}, "whole groups"),
    ((0, 4), {"norm_axis": "heads"}, "per group"),
    ((6, 4), {}, "heads_held")])
def test_a_share_that_is_no_whole_groups_is_refused(held, kw, match):
    with pytest.raises(ValueError, match=match):
        _ssm_layer(_tiny(), held, **kw).init_params(
            jax.random.PRNGKey(0), InputType.recurrent(32, 8))


def _legacy_ssm(layer, params, x):
    """`SelectiveStateSpace.apply` as PR 48 had it (one group, the gated
    norm over all lanes held), written out."""
    from deeplearning4j_tpu.ops.selective_scan import selective_scan

    B, T, _ = x.shape
    H, P, K = layer._held[1], layer.head_dim, layer.conv_kernel
    G, N = layer.n_groups, layer.state_size
    inner = H * P
    zxbcdt = x @ params["in_proj"]
    z, xbc, dt = (zxbcdt[..., :inner],
                  zxbcdt[..., inner:2 * inner + 2 * G * N],
                  zxbcdt[..., 2 * inner + 2 * G * N:])
    padded = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    xbc = jax.nn.silu(sum(padded[:, k:k + T] * params["conv_w"][k]
                          for k in range(K)) + params["conv_b"])
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + params["dt_bias"].astype(jnp.float32))
    a = -jnp.exp(params["A_log"].astype(jnp.float32))
    y = selective_scan(
        xbc[..., :inner].reshape(B, T, H, P), dt, a,
        xbc[..., inner:inner + G * N].reshape(B, T, G, N),
        xbc[..., inner + G * N:].reshape(B, T, G, N), params["D"],
        chunk=layer.chunk)
    y = rms_norm(y.reshape(B, T, inner) * jax.nn.silu(z), params["norm"],
                 layer.norm_eps, layer.norm_axis)
    return y @ params["out_proj"]


@pytest.mark.parametrize("held", [None, (2, 4)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_one_group_is_unchanged_to_the_bit(held, dtype):
    """granite's mixer (`n_groups` 1), whole and as a share of the heads:
    the leaves PR 48's init made (from the keys it used) and the output
    its `apply` gave."""
    cfg = _tiny(n_groups=1)
    layer = _ssm_layer(cfg, held)
    key, kind = jax.random.PRNGKey(11), InputType.recurrent(32, 64)
    p, _ = layer.init_params(key, kind, dtype)
    first, count = layer._held
    ks = jax.random.split(key, 9)
    winit = layer._winit()
    bc = winit(ks[2], (32, 2 * 16), dtype)
    assert p["in_proj"].shape == (32, 2 * count * 8 + 32 + count)
    _same_bits(p["in_proj"][:, 2 * count * 8:2 * count * 8 + 32], bc)
    _same_bits(p["in_proj"][:, :8],
               winit(jax.random.fold_in(ks[0], first), (32, 8), dtype))
    x = _normal(12, (2, 64, 32))[0].astype(dtype)
    _same_bits(layer.apply(p, x)[0], _legacy_ssm(layer, p, x))


# ------------------------------------------------------------- the experts
def _expert_layer(cfg, held, **kw):
    return ExpertFeedForward(
        n_in=cfg["hidden_size"], width=cfg["moe_intermediate_size"],
        n_experts=cfg["n_routed_experts"], held=held,
        k=cfg["num_experts_per_tok"], score="sigmoid", selection_bias=True,
        route_norm=cfg["norm_topk_prob"],
        route_scale=float(cfg["routed_scaling_factor"]),
        expert_form="relu2", latent=cfg["moe_latent_size"],
        shared_width=cfg["moe_shared_expert_intermediate_size"],
        weight_init="xavier", **kw)


def test_the_latent_layer_is_the_reference_and_routes_as_it_does():
    cfg, ref = _tiny(experts_held=[0, 8]), _reference()
    p = ref.init_params(9, cfg)["layer2_prenormsublayer"]
    x, = _normal(10, (2, 64, 32))
    tokens = x.reshape(-1, 32)
    want = ref.experts(p, tokens, cfg, "float32").reshape(x.shape)
    got, counters = _expert_layer(cfg, None).apply(_inner(p), x)
    _close(got, want, 1e-5)
    assert int(counters["moe_pairs_held"]) == 128 * 3
    sel, wt = ref.route(p, tokens, cfg)
    experts, weights = moe.route(
        tokens, p["f_router"], p["f_bias"], k=3, score="sigmoid",
        route_norm=True, route_scale=5.0)
    assert (np.asarray(sel) == np.asarray(experts)).all()
    np.testing.assert_allclose(wt, weights, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 5.0, rtol=1e-5)


@pytest.mark.parametrize("per_share", [1, 2, 4])
def test_the_shares_of_the_latent_experts_add_up(per_share):
    """Eight experts as shares of `per_share`, each what one device
    computes: its held experts' weighted sum in the latent through
    `latent_up`, beside the shared expert, which with both projections is
    whole on every device and so counted once. The sum is the uncut
    reference's layer; every pair fell on exactly one share, none
    dropped."""
    cfg, ref = _tiny(experts_held=[0, 8]), _reference()
    p = ref.init_params(13, cfg)["layer2_prenormsublayer"]
    x, = _normal(14, (2, 64, 32))
    tokens = x.reshape(-1, 32)
    want = ref.experts(p, tokens, cfg, "float32").reshape(x.shape)
    shared = ref._relu2(tokens, p["f_shared_w1"], p["f_shared_w2"],
                        "float32").reshape(x.shape)
    total, pairs = 0.0, 0
    for first in range(0, 8, per_share):
        sp = _inner(p)
        sp.update({k: sp[k][first:first + per_share] for k in ("w1", "w2")})
        y, counters = _expert_layer(cfg, (first, per_share)).apply(sp, x)
        total = total + (y - shared)        # the routed part of this share
        pairs += int(counters["moe_pairs_held"])
        assert int(counters["moe_pairs_dropped"]) == 0
    _close(total + shared, want, 1e-5)
    assert pairs == 128 * cfg["num_experts_per_tok"]


def test_a_share_starts_as_the_whole_layer_cut():
    cfg = _tiny()
    key, kind = jax.random.PRNGKey(15), InputType.recurrent(32, 8)
    whole, _ = _expert_layer(cfg, None).init_params(key, kind)
    own, state = _expert_layer(cfg, (2, 3)).init_params(key, kind)
    assert set(own) == {"router", "bias", "w1", "w2", "shared_w1",
                        "shared_w2", "latent_down", "latent_up"}
    assert own["w1"].shape == (3, 16, 24) and own["w2"].shape == (3, 24, 16)
    assert own["shared_w1"].shape == (32, 40)
    assert own["latent_down"].shape == (32, 16)
    for name in own:
        cut = whole[name][2:5] if name in ("w1", "w2") else whole[name]
        _same_bits(own[name], cut)
    assert set(state) == set(moe.COUNTERS)


def _legacy_experts(layer, params, x):
    """`ExpertFeedForward.apply` as PR 48 had it (SwiGLU experts at the
    model's width), written out."""
    tokens = x.reshape(-1, x.shape[-1])
    experts, weights = moe.route(
        tokens, params["router"], params.get("bias"), k=layer.k,
        score=layer.score, route_norm=layer.route_norm,
        route_scale=layer.route_scale, n_group=layer.n_group,
        topk_group=layer.topk_group)
    first, _ = layer._held
    y, _ = moe.held_experts(
        tokens, experts, weights.astype(tokens.dtype), params["w1"],
        params["w3"], params["w2"], first=first, n_experts=layer.n_experts)
    if layer.n_shared:
        y = y + moe._swiglu(tokens, params["shared_w1"], params["shared_w3"],
                            params["shared_w2"], jnp.dot)
    return y.reshape(x.shape)


@pytest.mark.parametrize("kw", [
    # granite's and trinity's tiny layers
    dict(width=16, n_experts=8, held=(0, 4), k=3, score="softmax",
         route_norm=True, n_shared=2),
    dict(width=16, n_experts=8, held=(2, 4), k=2, score="sigmoid",
         selection_bias=True, route_norm=True, route_scale=2.5, n_shared=1),
    dict(width=16, n_experts=8, held=None, k=2)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_the_swiglu_defaults_are_unchanged_to_the_bit(kw, dtype):
    """The leaves PR 48's init made, by name, shape and value (from the
    keys it used), and the output its `apply` gave."""
    layer = ExpertFeedForward(n_in=32, weight_init="xavier", **kw)
    key = jax.random.PRNGKey(16)
    p, _ = layer.init_params(key, InputType.recurrent(32, 8), dtype)
    ks = jax.random.split(key, 7)
    winit = layer._winit()
    first, count = layer._held
    want = {"router", "w1", "w3", "w2"}
    want |= {"bias"} if layer.selection_bias else set()
    want |= {"shared_w1", "shared_w3", "shared_w2"} if layer.n_shared \
        else set()
    assert set(p) == want
    _same_bits(p["router"], winit(ks[0], (32, 8), dtype))
    for name, k, shape in (("w1", ks[1], (32, 16)), ("w3", ks[2], (32, 16)),
                           ("w2", ks[3], (16, 32))):
        _same_bits(p[name], jnp.stack([winit(
            jax.random.fold_in(k, first + i), shape, dtype)
            for i in range(count)]))
    if layer.n_shared:
        fs = layer.n_shared * 16
        _same_bits(p["shared_w1"], winit(ks[4], (32, fs), dtype))
        _same_bits(p["shared_w3"], winit(ks[5], (32, fs), dtype))
        _same_bits(p["shared_w2"], winit(ks[6], (fs, 32), dtype))
    x = _normal(17, (2, 32, 32))[0].astype(dtype)
    _same_bits(layer.apply(p, x)[0], _legacy_experts(layer, p, x))


def test_an_expert_form_nobody_wired_is_refused():
    with pytest.raises(ValueError, match="geglu"):
        ExpertFeedForward(n_in=8, width=4, expert_form="geglu").init_params(
            jax.random.PRNGKey(0), InputType.recurrent(8, 4))


# ------------------------------------------------------------ the sublayer
def test_a_sublayer_is_one_norm_one_layer_and_the_stream():
    attention = MultiHeadAttention(num_heads=2, num_kv_heads=1, head_dim=8,
                                   causal=True, rope=False, bias=False,
                                   max_cache=16)
    block = PreNormSublayer(n_in=32, layer=attention, weight_init="xavier",
                            name="b")
    p, state = block.init_params(jax.random.PRNGKey(1),
                                 InputType.recurrent(32, 16))
    assert set(p) == {"ln_g", "f_Wq", "f_Wk", "f_Wv", "f_Wo"} and not state
    p = {**p, "ln_g": 1.0 + 0.1 * _normal(2, (32,))[0]}
    x, = _normal(3, (2, 16, 32))
    inner = block._f()
    want = x + inner.apply(_inner(p), rms_norm(x, p["ln_g"], 1e-5))[0]
    _close(block.apply(p, x)[0], want, 1e-6)
    # an expert layer's counters are the sublayer's state
    experts = PreNormSublayer(n_in=32, layer=_expert_layer(_tiny(), (0, 4)),
                              weight_init="xavier", name="e")
    p, state = experts.init_params(jax.random.PRNGKey(4),
                                   InputType.recurrent(32, 16))
    assert set(state) == set(moe.COUNTERS)
    _, new = experts.apply(p, x, state=state)
    assert int(new["moe_pairs_routed"]) == 2 * 16 * 3
    assert experts.decode_carry(1) == {}
    with pytest.raises(ValueError, match="needs a layer"):
        PreNormSublayer(n_in=8).init_params(jax.random.PRNGKey(0),
                                            InputType.recurrent(8, 4))


# ---------------------------------------------------------------- the head
def _head(cfg, weight=0.1, layers=None):
    from deeplearning4j_tpu.zoo import HybridLatentExpertTransformer

    zoo = HybridLatentExpertTransformer(
        cfg, timesteps=16, attention_heads_held=(0, 2),
        kv_heads_held=(0, 1), experts_held=(0, 4), vocabulary_held=50)
    if layers is None:
        layers = tuple(zoo._layer(c) for c in "*E")
    return MultiTokenOutputLayer(
        n_in=32, n_out=50, tied_to=0, layers=layers, mtp_weight=weight,
        weight_init="xavier", activation="softmax", name="head")


def _head_params(layer, seed=0):
    p, state = layer.init_params(jax.random.PRNGKey(seed),
                                 InputType.recurrent(32, 16))
    p = {k: 1.0 + 0.1 * _normal(100 + i, v.shape)[0]
         if v.ndim == 1 and "bias" not in k else v
         for i, (k, v) in enumerate(sorted(p.items()))}
    return {**p, "embedding": _normal(seed + 1, (50, 32))[0]}, state


@pytest.mark.parametrize("masked", [False, True])
def test_the_head_with_weight_zero_is_rnn_output_layers_loss(masked):
    layer = _head(_tiny(), weight=0.0)
    p, state = _head_params(layer)
    x, = _normal(5, (2, 16, 32))
    y = jnp.asarray(np.random.default_rng(0).integers(0, 50, (2, 16)))
    mask = jnp.asarray(np.random.default_rng(1).integers(0, 2, (2, 16)),
                       jnp.float32) if masked else None
    plain = RnnOutputLayer(n_in=32, n_out=50, has_bias=False,
                           activation="softmax", loss="sparse_mcxent")
    want = plain.score({"W": p["W"]}, rms_norm(x, p["norm_f"], 1e-5), y,
                       mask)
    got, new = layer.score_and_state(p, x, y, state, mask)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    assert float(new["main_loss"]) == pytest.approx(float(want), rel=1e-6)
    assert float(new["mtp_loss"]) > 0
    # and its output is the main head's softmax
    out, _ = layer.apply(p, x)
    _close(out, plain.apply({"W": p["W"]},
                            rms_norm(x, p["norm_f"], 1e-5))[0], 1e-6)


def test_the_second_term_scores_the_token_after_the_next():
    """Written out for a module of no layers: g = [norm_h(h); norm_e(e[y])]
    W_eh, scored by the main head's W against y shifted by one, over the
    T - 1 positions that have such a target; the last position's target
    and the first label move nothing of it."""
    layer = _head(_tiny(), weight=0.25, layers=())
    p, state = _head_params(layer, 2)
    x, = _normal(6, (2, 16, 32))
    y = np.random.default_rng(2).integers(0, 50, (2, 16))
    e = p["embedding"][y]
    g = jnp.concatenate([rms_norm(x, p["mtp_norm_h"], 1e-5),
                         rms_norm(e, p["mtp_norm_e"], 1e-5)],
                        axis=-1) @ p["mtp_eh_proj"]
    logp = jax.nn.log_softmax(rms_norm(g, p["mtp_norm"], 1e-5) @ p["W"])
    mtp = -np.mean(np.take_along_axis(np.asarray(logp)[:, :-1],
                                      y[:, 1:, None], axis=-1))
    main = -np.mean(np.take_along_axis(np.asarray(jax.nn.log_softmax(
        rms_norm(x, p["norm_f"], 1e-5) @ p["W"])), y[..., None], axis=-1))
    score, new = layer.score_and_state(p, x, jnp.asarray(y), state)
    assert float(new["mtp_loss"]) == pytest.approx(mtp, rel=1e-5)
    assert float(score) == pytest.approx(main + 0.25 * mtp, rel=1e-5)
    # the embedding's table takes a gradient from the labels' lookup
    grad = jax.grad(lambda p: layer.score_and_state(
        p, x, jnp.asarray(y), state)[0])(p)
    assert float(jnp.linalg.norm(grad["embedding"])) > 0
    assert float(jnp.linalg.norm(grad["W"])) > 0


@pytest.mark.parametrize("pattern", ["*E", "E*", "ME", "EM"])
def test_the_modules_counters_are_the_steps_own_in_any_order(pattern):
    """A layer that follows the expert layer hands back no stale copy of
    its counters: the module's layers are given no state, so whatever the
    order each key of the new state is what its own layer wrote in this
    step, and the structure is what `init_params` declared."""
    from deeplearning4j_tpu.zoo import HybridLatentExpertTransformer

    zoo = HybridLatentExpertTransformer(
        _tiny(), timesteps=16, heads_held=(0, 4),
        attention_heads_held=(0, 2), kv_heads_held=(0, 1),
        experts_held=(0, 4), vocabulary_held=50)
    layer = _head(_tiny(), layers=tuple(zoo._layer(c) for c in pattern))
    p, state = _head_params(layer)
    x, = _normal(7, (2, 16, 32))
    y = jnp.asarray(np.random.default_rng(3).integers(0, 50, (2, 16)))
    _, new = layer.score_and_state(p, x, y, state)
    assert set(new) == set(state)
    assert int(state["moe_pairs_routed"]) == 0
    assert int(new["moe_pairs_routed"]) == 2 * 16 * 3
    assert int(new["moe_pairs_held"]) > 0
    if "M" in pattern:
        assert 0 < float(new["ssm_chunk_carry"]) < 1


@pytest.mark.parametrize("what", ["loss", "tied_to", "decode", "labels"])
def test_the_head_refuses_by_name(what):
    cfg = _tiny()
    kind = InputType.recurrent(32, 16)
    if what == "loss":
        layer = MultiTokenOutputLayer(n_in=32, n_out=50, tied_to=0,
                                      loss="mcxent", name="head")
        with pytest.raises(ValueError, match="sparse_mcxent"):
            layer.init_params(jax.random.PRNGKey(0), kind)
    elif what == "tied_to":
        with pytest.raises(ValueError, match="tied_to"):
            MultiTokenOutputLayer(n_in=32, n_out=50, name="head") \
                .init_params(jax.random.PRNGKey(0), kind)
    elif what == "decode":
        with pytest.raises(NotImplementedError, match="head"):
            _head(cfg).decode_carry(1)
    else:
        layer = _head(cfg, layers=())
        p, state = _head_params(layer)
        with pytest.raises(ValueError, match="integer labels"):
            layer.score_and_state(p, jnp.zeros((2, 16, 32)),
                                  jnp.zeros((2, 16, 50)), state)


# --------------------------------------------------------------- the model
def _net(cfg, **kw):
    from deeplearning4j_tpu.models import MultiLayerNetwork
    from deeplearning4j_tpu.zoo import HybridLatentExpertTransformer

    return MultiLayerNetwork(HybridLatentExpertTransformer(
        cfg, timesteps=cfg["input_shape"][0],
        heads_held=tuple(cfg["heads_held"]),
        attention_heads_held=tuple(cfg["attention_heads_held"]),
        kv_heads_held=tuple(cfg["kv_heads_held"]),
        experts_held=tuple(cfg["experts_held"]),
        vocabulary_held=cfg["vocabulary_held"], **kw).conf())


def _gauges(name):
    from deeplearning4j_tpu.observe import get_registry

    return {dict(g.labels).get("layer"): g.value
            for g in get_registry().series() if g.name == name}


def _batch(seed, cfg):
    ids = np.random.default_rng(seed).integers(
        0, cfg["vocabulary_held"], (2, cfg["input_shape"][0] + 1))
    return ids[:, :-1].astype(np.int32), ids[:, 1:].astype(np.int32)


@pytest.mark.parametrize("checkpointing", [False, True])
def test_zoo_model_is_the_plain_reference(checkpointing):
    """Loss to 1e-5, both of its terms, and every leaf's gradient to 1e-4,
    128 tokens (four chunks of 32), float32: the embedding's (read by the
    trunk and by the module) and the head's (applied twice) among them."""
    cfg, ref = _tiny(), _reference()
    params = ref.init_params(7, cfg)
    net = _net(cfg, gradient_checkpointing=checkpointing).init()
    assert (jax.tree_util.tree_structure(params)
            == jax.tree_util.tree_structure(net.params_tree))
    x, y = map(jnp.asarray, _batch(0, cfg))
    want, want_g = jax.value_and_grad(ref.loss_fn)(params, x, y)
    (got, states), got_g = jax.value_and_grad(
        lambda p: net._loss(p, net.state_tree, x, y, None, None, None,
                            train=True), has_aux=True)(params)
    assert abs(float(got) - float(want)) <= 1e-5 * float(want)
    main, mtp = ref.loss_terms(params, x, y)
    assert float(states[HEAD]["main_loss"]) == pytest.approx(float(main),
                                                             rel=1e-5)
    assert float(states[HEAD]["mtp_loss"]) == pytest.approx(float(mtp),
                                                            rel=1e-5)
    for layer, leaves in want_g.items():
        for name, leaf in leaves.items():
            if name.endswith("f_bias"):     # steers the choice alone
                assert not np.any(np.asarray(got_g[layer][name]))
                assert not np.any(np.asarray(leaf))
                continue
            assert float(jnp.linalg.norm(leaf)) > 0, (layer, name)
            _close(got_g[layer][name], leaf, 1e-4)


@pytest.mark.parametrize("term", ["main", "mtp"])
def test_each_term_alone_is_the_references(term):
    """The gradient of ONE term (the other's weight at zero, or the main
    term taken out): the module's leaves move under its own term alone,
    and the trunk's under both."""
    cfg, ref = _tiny(), _reference()
    params = ref.init_params(17, cfg)
    net = _net(cfg).init()
    x, y = map(jnp.asarray, _batch(1, cfg))
    pick = 0 if term == "main" else 1
    want_g = jax.grad(lambda p: ref.loss_terms(p, x, y)[pick])(params)
    got_g = jax.grad(lambda p: net._loss(
        p, net.state_tree, x, y, None, None, None,
        train=True)[1][HEAD][term + "_loss"])(params)
    module = [k for k in want_g[HEAD] if k.startswith("mtp_")
              and not k.endswith("f_bias")]
    for name in module:
        if term == "main":
            assert not np.any(np.asarray(got_g[HEAD][name])), name
        else:
            assert float(jnp.linalg.norm(want_g[HEAD][name])) > 0, name
    for layer, leaves in want_g.items():
        for name, leaf in leaves.items():
            _close(got_g[layer][name], leaf, 1e-4)


def test_three_adam_steps_are_the_reference_steps():
    """`fit()` thrice against the benchmark's own follower of the plain
    reference under its Adam rule: each step's loss and every leaf's
    change."""
    from benchmarks import harness
    from deeplearning4j_tpu.data.dataset import DataSet

    cfg, ref = _tiny(), _reference()
    follow = harness.load_module("reference", "follow.py")
    rule = harness.load_module("reference", "rules", "adam.py")
    batches = [_batch(3 + i, cfg) for i in range(3)]
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
        assert moved == pytest.approx(norm, rel=2e-3, abs=1e-9), path


def test_fit_publishes_both_terms_and_the_modules_routing():
    from deeplearning4j_tpu.data.dataset import DataSet

    cfg = _tiny()
    net = _net(cfg, gradient_checkpointing=True).init()
    net.fit(DataSet(*_batch(2, cfg)))
    state = net.state_tree[HEAD]
    for name in ("main_loss", "mtp_loss"):
        assert _gauges(name)[HEAD] == pytest.approx(float(state[name]),
                                                    rel=1e-6)
    assert math.log(600) * 0.9 < float(state["mtp_loss"]) < math.log(600) * 1.2
    # three expert layers keep counters: the trunk's two and the module's
    for name in ("layer2_prenormsublayer", "layer5_prenormsublayer", HEAD):
        st = net.state_tree[name]
        assert int(st["moe_pairs_routed"]) == 2 * 128 * 3
        assert int(st["moe_pairs_dropped"]) == 0
        for counter in moe.COUNTERS:
            assert _gauges(counter)[name] == int(st[counter]), counter
    for name in ("layer1_prenormsublayer", "layer4_prenormsublayer"):
        assert _gauges("ssm_chunk_carry")[name] == pytest.approx(
            float(net.state_tree[name]["ssm_chunk_carry"]), rel=1e-6)


def test_save_and_load_round_trip_the_new_leaves(tmp_path):
    from deeplearning4j_tpu.models.serialize import load_model, save_model

    cfg = _tiny()
    net = _net(cfg).init()
    net.params_tree = jax.tree_util.tree_map(
        jnp.array, _reference().init_params(21, cfg))
    x, y = _batch(4, cfg)
    path = str(tmp_path / "nemotron.zip")
    save_model(net, path)
    back = load_model(path)
    assert (jax.tree_util.tree_structure(back.params_tree)
            == jax.tree_util.tree_structure(net.params_tree))
    for layer, leaves in net.params_tree.items():
        for name, leaf in leaves.items():
            _same_bits(back.params_tree[layer][name], leaf)
    assert {"mtp_eh_proj", "mtp_layer1_f_latent_down", "norm_f"} \
        <= set(back.params_tree[HEAD])
    np.testing.assert_array_equal(np.asarray(back.output(x)),
                                  np.asarray(net.output(x)))
    assert float(back.score(x, y)) == float(net.score(x, y))


def test_the_conf_round_trips():
    from deeplearning4j_tpu.nn.config import MultiLayerConfiguration

    conf = _net(_tiny(), gradient_checkpointing=True).conf
    back = MultiLayerConfiguration.from_json(conf.to_json())
    assert back.to_json() == conf.to_json()
    mixer = back.layers[1].layer
    assert isinstance(mixer, SelectiveStateSpace)
    assert tuple(mixer.heads_held) == (0, 4) and mixer.n_groups == 2
    experts = back.layers[2].layer
    assert experts.expert_form == "relu2" and experts.latent == 16
    head = back.layers[-1]
    assert isinstance(head, MultiTokenOutputLayer) and head.tied_to == 0
    assert [type(l.layer).__name__ for l in head.layers] == [
        "MultiHeadAttention", "ExpertFeedForward"]
    assert head.remat is True


def test_without_a_module_the_head_is_a_norm_and_an_untied_matrix():
    cfg = _tiny(num_nextn_predict_layers=0)
    net = _net(cfg).init()
    assert [type(l).__name__ for l in net.layers[-2:]] == [
        "RMSNormalization", "RnnOutputLayer"]
    assert net.params_tree[net.layers[-1].name]["W"].shape == (32, 600)


def test_decode_names_the_layer_it_cannot_serve():
    net = _net(_tiny()).init()
    with pytest.raises(NotImplementedError, match="SelectiveStateSpace"):
        net.rnn_time_step(np.zeros((1, 1), np.int32))
    with pytest.raises(NotImplementedError, match=HEAD):
        net.layers[-1].decode_carry(1)


@pytest.mark.parametrize("key,value", [
    ("hybrid_override_pattern", "ME-ME"), ("mtp_hybrid_override_pattern", "*-"),
    ("num_hidden_layers", 4), ("num_nextn_predict_layers", 2),
    ("mlp_hidden_act", "silu"), ("mamba_hidden_act", "gelu"),
    ("tie_word_embeddings", True), ("mamba_proj_bias", True),
    ("use_conv_bias", False), ("n_group", 2), ("expand", 4),
    ("time_step_max", 0.2), ("n_shared_experts", 2)])
def test_a_configuration_the_builder_does_not_know_is_an_error(key, value):
    with pytest.raises(ValueError):
        _net(_tiny(**{key: value}))


def test_the_model_counts_what_the_configuration_says():
    """The built net's parameters at the published widths and the cell's
    cut, from shapes alone, are the reference's and the file's."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "nemotron_3_super.json"), encoding="utf-8") as fh:
        cfg = json.load(fh)
    ref = _reference()
    net = _net(cfg, dtype="bfloat16")
    shapes = jax.eval_shape(lambda: net.init().params_tree)
    built = {layer: {k: v.shape for k, v in leaves.items()}
             for layer, leaves in shapes.items()}
    blocks, head = ref._names(cfg)
    want = {"layer0_embeddingsequencelayer": {"W": (16384, 4096)},
            head: ref.head_shapes(cfg),
            **{name: ref.sublayer_shapes(cfg, letter) for name, letter in
               zip(blocks, cfg["hybrid_override_pattern"])}}
    assert built == want
    count = sum(math.prod(s) for leaves in built.values()
                for s in leaves.values())
    assert count == 1_102_491_120
    assert cfg["hybrid_override_pattern"] == "MEMEMEM*EME"
    assert ref.forward_macs(cfg) / 8192 == pytest.approx(606.34e6, rel=1e-4)
