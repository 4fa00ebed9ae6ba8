"""The selective state-space scan, its layer, the tied head, the given
softmax scale and the model built from them (`granite_4_0_h_small`), small,
on the CPU, with seeded weights: the chunked op against the token-at-a-time
recurrence, values and gradients; the shares of the Mamba heads (under a
named axis, with the gated norm's `psum`) and of the experts adding up to
the uncut layer; `route`'s softmax over the chosen logits; one tied leaf
read twice; and `zoo.HybridStateSpaceTransformer` against the benchmark's
plain reference, loss, every leaf's gradient and three Adam steps."""

import functools
import importlib
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.config import NeuralNetConfiguration
from deeplearning4j_tpu.nn.inputs import InputType
from deeplearning4j_tpu.nn.layers import (
    EmbeddingSequenceLayer, MultiHeadAttention, RMSNormalization,
    RnnOutputLayer, SelectiveStateSpace,
)
from deeplearning4j_tpu.nn.layers.attention import rms_norm
from deeplearning4j_tpu.ops import selective_scan as ss
from deeplearning4j_tpu.optim.updaters import Adam
from deeplearning4j_tpu.parallel import moe
from deeplearning4j_tpu.parallel.moe import ExpertFeedForward

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _normal(seed, *shapes):
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    return [jax.random.normal(k, s, jnp.float32) for k, s in zip(keys, shapes)]


def _close(got, want, tol):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert np.linalg.norm(got - want) <= tol * max(np.linalg.norm(want),
                                                   1e-30)


def _tiny(**changes):
    with open(os.path.join(ROOT, "benchmarks", "tests", "configs",
                           "granite_4_0_h_small_tiny.json"),
              encoding="utf-8") as fh:
        return {**json.load(fh), **changes}


def _reference():
    from benchmarks import harness

    return harness.load_module("reference", "granite_4_0_h_small.py")


# ------------------------------------------------------------------ the op
def _scan_case(t=150, b=2, h=4, p=8, g=1, n=16, seed=0, dtype=jnp.float32):
    """Heads from one that barely decays (`dt A` about -1e-4 a token) to
    one whose state dies inside a chunk (about -3 a token)."""
    x, bm, cm, raw = _normal(seed, (b, t, h, p), (b, t, g, n), (b, t, g, n),
                             (b, t, h))
    dt = jax.nn.softplus(raw - 2.0)
    a = -jnp.asarray(np.geomspace(1e-3, 24.0, h), jnp.float32)
    d = jnp.linspace(0.5, 1.5, h)
    return (x.astype(dtype), dt, a, bm.astype(dtype), cm.astype(dtype), d)


@pytest.mark.parametrize("groups", [1, 4])
@pytest.mark.parametrize("chunk", [1, 64, 150, 256])
def test_chunked_scan_is_the_recurrence(chunk, groups):
    """Values to 1e-5 and all six gradients, float32, 150 tokens (no
    multiple of 64: the tail is padded with zero steps)."""
    args = _scan_case(g=groups)
    _close(ss.selective_scan(*args, chunk=chunk),
           ss.selective_scan_recurrence(*args), 1e-5)
    w, = _normal(1, args[0].shape)
    loss = lambda fn, **kw: (lambda *a: jnp.sum(fn(*a, **kw) * w))
    got = jax.grad(loss(ss.selective_scan, chunk=chunk),
                   tuple(range(6)))(*args)
    want = jax.grad(loss(ss.selective_scan_recurrence),
                    tuple(range(6)))(*args)
    for a, b in zip(got, want):
        assert bool(jnp.all(jnp.isfinite(a)))
        _close(a, b, 2e-5)


@pytest.mark.parametrize("chunk", [64, 150])
def test_chunked_scan_in_bfloat16_is_the_recurrence_to_its_rounding(chunk):
    args = _scan_case(dtype=jnp.bfloat16)
    got = ss.selective_scan(*args, chunk=chunk)
    assert got.dtype == jnp.bfloat16
    _close(got.astype(jnp.float32), ss.selective_scan_recurrence(*args),
           2e-2)
    w, = _normal(1, args[0].shape)
    grad = lambda fn, **kw: jax.grad(
        lambda *a: jnp.sum(fn(*a, **kw).astype(jnp.float32) * w),
        (0, 1, 3, 4))(*args)
    for a, b in zip(grad(ss.selective_scan, chunk=chunk),
                    grad(ss.selective_scan_recurrence)):
        _close(a.astype(jnp.float32), b.astype(jnp.float32), 3e-2)


def test_a_dead_head_and_a_lasting_one_stay_finite_and_exact():
    """`dt A` of -60 a token (the state is gone within a token: every
    exponent of the chunk's decay matrix underflows, none overflows) beside
    0 (nothing ever decays: the scan is a running sum)."""
    x, dt, _, bm, cm, _ = _scan_case(t=128, h=2)
    dt = jnp.ones_like(dt)
    a = jnp.asarray([-60.0, 0.0])
    got = ss.selective_scan(x, dt, a, bm, cm, chunk=32)
    assert bool(jnp.all(jnp.isfinite(got)))
    _close(got, ss.selective_scan_recurrence(x, dt, a, bm, cm), 1e-5)
    # the dead head reads its own token alone, the lasting one a plain sum
    own = jnp.einsum("btp,btn,btn->btp", x[:, :, 0], bm[:, :, 0], cm[:, :, 0])
    _close(got[:, :, 0], own, 1e-5)
    running = jnp.einsum("bsp,bsn,btn->btsp", x[:, :, 1], bm[:, :, 0],
                         cm[:, :, 0])
    seen = jnp.tril(jnp.ones((128, 128)))[None, :, :, None]
    _close(got[:, :, 1], jnp.sum(running * seen, axis=2), 1e-4)
    g = jax.grad(lambda a: jnp.sum(ss.selective_scan(x, dt, a, bm, cm,
                                                     chunk=32)))(a)
    assert bool(jnp.all(jnp.isfinite(g)))


def test_chunk_carry_is_what_survives_a_chunk():
    _, dt, a, _, _, _ = _scan_case(t=150)
    got = ss.chunk_carry(dt, a, chunk=64)
    whole = np.asarray(dt[:, :128] * a).reshape(2, 2, 64, 4).sum(2)
    assert float(got) == pytest.approx(np.exp(whole).mean(), rel=1e-5)
    assert 0.0 < float(got) < 1.0


def test_groups_that_do_not_divide_the_heads_are_refused():
    x, dt, a, _, _, d = _scan_case(h=4)
    bm, cm = _normal(2, (2, 150, 3, 16), (2, 150, 3, 16))
    with pytest.raises(ValueError, match="groups"):
        ss.selective_scan(x, dt, a, bm, cm, d)


# --------------------------------------------------------------- the layer
def _mixer_leaves(block):
    return {k[6:]: v for k, v in block.items() if k.startswith("mixer_")}


def _ssm_layer(cfg, held=None, **kw):
    return SelectiveStateSpace(
        n_in=cfg["hidden_size"], n_out=cfg["hidden_size"],
        num_heads=cfg["mamba_n_heads"], heads_held=held,
        head_dim=cfg["mamba_d_head"], state_size=cfg["mamba_d_state"],
        n_groups=cfg["mamba_n_groups"], conv_kernel=cfg["mamba_d_conv"],
        chunk=cfg["mamba_chunk_size"], norm_eps=cfg["rms_norm_eps"],
        activation="identity", weight_init="xavier", name="ssm", **kw)


def _head_slices(cfg, p, first, count):
    """The leaves of the heads [first, first + count) cut out of the whole
    layer's `p`."""
    h, hp, n = cfg["mamba_n_heads"], cfg["mamba_d_head"], cfg["mamba_d_state"]
    inner = h * hp
    lanes = np.arange(first * hp, (first + count) * hp)
    heads = np.arange(first, first + count)
    bc = np.arange(2 * n)
    cols = np.concatenate([lanes, inner + lanes, 2 * inner + bc,
                           2 * inner + 2 * n + heads])
    chans = np.concatenate([lanes, inner + bc])
    return {"in_proj": p["in_proj"][:, cols], "conv_w": p["conv_w"][:, chans],
            "conv_b": p["conv_b"][chans], "dt_bias": p["dt_bias"][heads],
            "A_log": p["A_log"][heads], "D": p["D"][heads],
            "norm": p["norm"][lanes], "out_proj": p["out_proj"][lanes]}


@pytest.mark.parametrize("t", [128, 50])
def test_selective_state_space_layer_is_the_reference_mixer(t):
    """The chunked layer against the reference's token-at-a-time mixer, at
    four chunks of 32 and at a ragged 50 tokens."""
    cfg, ref = _tiny(heads_held=[0, 8]), _reference()
    p = _mixer_leaves(ref.init_params(3, cfg)["layer1_prenormblock"])
    x, = _normal(4, (2, t, 32))
    want = jnp.stack([ref.mamba({"mixer_" + k: v for k, v in p.items()}, seq,
                                cfg, "float32") for seq in x])
    got, state = _ssm_layer(cfg).apply(p, x)
    _close(got, want, 1e-5)
    assert 0.0 < float(state["ssm_chunk_carry"]) < 1.0


def test_the_shares_of_the_heads_add_up_to_the_whole_layer():
    """Eight heads as four shares of two under a named axis: the gated
    norm sums its squares and its lane count over the axis and `out_proj`'s
    partial outputs are summed, as a 4-way tensor-parallel layer does; the
    sum is the uncut layer, which is the reference's mixer. Without the
    axis a share norms over the lanes it holds, which is another number. A
    share's own init is the whole layer's, cut."""
    cfg, ref = _tiny(heads_held=[0, 8]), _reference()
    p = _mixer_leaves(ref.init_params(5, cfg)["layer1_prenormblock"])
    x, = _normal(6, (2, 128, 32))
    want = _ssm_layer(cfg).apply(p, x)[0]
    shares = [_head_slices(cfg, p, 2 * i, 2) for i in range(4)]
    stacked = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *shares)
    share = _ssm_layer(cfg, (0, 2), norm_axis="heads")
    parts = jax.vmap(lambda sp: share.apply(sp, x)[0],
                     axis_name="heads")(stacked)
    _close(jnp.sum(parts, axis=0), want, 1e-5)
    alone = sum(_ssm_layer(cfg, (0, 2)).apply(sp, x)[0] for sp in shares)
    assert np.linalg.norm(alone - want) > 1e-2 * np.linalg.norm(want)
    key, kind = jax.random.PRNGKey(1), InputType.recurrent(32, 128)
    own, _ = _ssm_layer(cfg, (2, 4)).init_params(key, kind)
    cut = _head_slices(cfg, _ssm_layer(cfg).init_params(key, kind)[0], 2, 4)
    for name in own:
        assert bool(jnp.all(own[name] == cut[name])), name


def test_the_layer_starts_as_mamba2_publishes():
    p, state = _ssm_layer(_tiny()).init_params(
        jax.random.PRNGKey(0), InputType.recurrent(32, 128))
    dt = jax.nn.softplus(p["dt_bias"])
    assert bool(jnp.all((dt >= 1e-3 * 0.999) & (dt <= 1e-1 * 1.001)))
    a = jnp.exp(p["A_log"])
    assert bool(jnp.all((a >= 1.0) & (a <= 16.0)))
    assert bool(jnp.all(p["D"] == 1.0)) and bool(jnp.all(p["norm"] == 1.0))
    assert set(state) == {"ssm_chunk_carry"}


def test_rms_norm_over_an_axis_is_the_norm_over_all_lanes():
    x, g = _normal(7, (3, 5, 24), (24,))
    want = rms_norm(x, g, 1e-5)
    parts = jax.vmap(lambda xs, gs: rms_norm(xs, gs, 1e-5, "lanes"),
                     in_axes=(2, 0), out_axes=2, axis_name="lanes")(
        x.reshape(3, 5, 4, 6), g.reshape(4, 6))
    _close(parts.reshape(3, 5, 24), want, 1e-6)


def test_heads_held_outside_the_layer_and_decode_are_refused():
    cfg = _tiny()
    with pytest.raises(ValueError, match="heads_held"):
        _ssm_layer(cfg, (6, 4)).init_params(jax.random.PRNGKey(0),
                                            InputType.recurrent(32, 8))
    with pytest.raises(NotImplementedError, match="ssm"):
        _ssm_layer(cfg).decode_carry(1)
    with pytest.raises(ValueError, match="mask"):
        layer = _ssm_layer(cfg)
        p, _ = layer.init_params(jax.random.PRNGKey(0),
                                 InputType.recurrent(32, 8))
        layer.apply(p, jnp.zeros((1, 8, 32)), mask=jnp.ones((1, 8)))


# ------------------------------------------------------------- the experts
def _expert_layer(cfg, held, shared=True):
    return ExpertFeedForward(
        n_in=cfg["hidden_size"], width=cfg["intermediate_size"],
        n_experts=cfg["num_local_experts"], held=held,
        k=cfg["num_experts_per_tok"], score="softmax", route_norm=True,
        n_shared=(cfg["shared_intermediate_size"]
                  // cfg["intermediate_size"]) if shared else 0,
        weight_init="xavier")


def test_route_is_a_softmax_over_the_chosen_logits():
    """72 experts, 10 a token, as published: `score="softmax"` with
    `route_norm` is the softmax over the 10 largest logits."""
    x, router = _normal(8, (64, 32), (32, 72))
    experts, weights = moe.route(x, router, None, k=10, score="softmax",
                                 route_norm=True, route_scale=1.0)
    logits = np.asarray(jnp.dot(x, router,
                                precision=jax.lax.Precision.HIGHEST))
    order = np.argsort(-logits, axis=-1, kind="stable")[:, :10]
    assert (np.asarray(experts) == order).all()
    chosen = np.take_along_axis(logits, order, axis=-1)
    want = np.exp(chosen - chosen.max(-1, keepdims=True))
    want /= want.sum(-1, keepdims=True)
    np.testing.assert_allclose(weights, want, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 1.0, rtol=1e-6)


def test_the_shares_of_the_experts_add_up_to_the_whole_layer():
    """Eight experts as eight shares of one, each what one device
    computes; the shared expert is on every device and counted once. Their
    sum is the uncut reference's expert layer, every pair fell on exactly
    one share and none was dropped; and the reference chooses what the
    program chooses."""
    cfg = _tiny(experts_held=[0, 8])
    ref = _reference()
    p = {k[4:]: v for k, v in
         ref.init_params(9, cfg)["layer1_prenormblock"].items()
         if k.startswith("moe_")}
    x, = _normal(10, (2, 64, 32))
    tokens = x.reshape(-1, 32)
    want = ref.experts({"moe_" + k: v for k, v in p.items()}, tokens, cfg,
                       "float32").reshape(x.shape)
    _close(_expert_layer(cfg, None).apply(p, x)[0], want, 1e-5)
    total, pairs = 0.0, 0
    for e in range(8):
        sp = {k: v for k, v in p.items()
              if e == 0 or not k.startswith("shared")}
        sp.update({k: p[k][e:e + 1] for k in ("w1", "w3", "w2")})
        y, counters = _expert_layer(cfg, (e, 1), shared=e == 0).apply(sp, x)
        total = total + y
        pairs += int(counters["moe_pairs_held"])
        assert int(counters["moe_pairs_dropped"]) == 0
    _close(total, want, 1e-5)
    assert pairs == 128 * cfg["num_experts_per_tok"]
    sel, wt = ref.route({"moe_router": p["router"]}, tokens, cfg)
    experts, weights = moe.route(tokens, p["router"], None, k=3,
                                 score="softmax", route_norm=True,
                                 route_scale=1.0)
    assert (np.asarray(sel) == np.asarray(experts)).all()
    np.testing.assert_allclose(wt, weights, rtol=1e-5)


@pytest.mark.parametrize("rows,share,most,tiers", [
    # the accepted cells' ladders, to the row
    (32768, 8 / 256, 8192 * 4, (4096, 8192, 16384, 32768)),
    (49152, 8 / 160, 8192 * 6, (9856, 19712, 39424, 49152)),
    # nine of 72 at ten a token: twice the first tier (40,960) passes
    # the 73,728 pairs that can fall here, so there is one tier
    (81920, 9 / 72, 8192 * 9, (73728,)),
    (512, 2 / 16, 512, (512,)),
])
def test_a_ladder_of_one_rung_is_one_tier(rows, share, most, tiers):
    """Every step of such a layer costs the same, whichever way a
    seed's routers fall."""
    assert moe._row_tiers(rows, share, most) == tiers


def test_a_router_collapsed_onto_the_experts_held_drops_nothing():
    """Every token's best experts are the ones held: 90% of the pairs
    fall here, which is all that can (a token's ten lie on distinct
    experts, nine held) and what `_row_tiers`' one tier holds: nothing
    is dropped and the result is the reference's."""
    cfg = _tiny(num_local_experts=40, num_experts_per_tok=10,
                experts_held=[0, 9])
    ref = _reference()
    p = {k[4:]: v for k, v in
         ref.init_params(12, cfg)["layer1_prenormblock"].items()
         if k.startswith("moe_")}
    x, = _normal(13, (2, 64, 32))
    tokens = x.reshape(-1, 32)
    for boost in (0.0, 3.0, 30.0):
        router = p["router"].at[:, :9].add(boost)    # the rows are positive
        q = {**p, "router": router}
        want = ref.experts({"moe_" + k: v for k, v in q.items()},
                           jnp.abs(tokens), cfg, "float32")
        got, counters = _expert_layer(cfg, (0, 9)).apply(q, jnp.abs(tokens))
        _close(got, want, 1e-5)
        assert int(counters["moe_pairs_dropped"]) == 0
    assert int(counters["moe_pairs_held"]) == 128 * 9


def _output_and_gradients(layer, p, x, ct):
    """(y, the gradients of sum(y * ct) by `p` and `x`, counters)."""
    def loss(p, x):
        y, counters = layer.apply(p, x)
        return jnp.sum(y * ct), (y, counters)
    grads, (y, counters) = jax.grad(loss, (0, 1), has_aux=True)(p, x)
    return y, grads, counters


def _pairs_of_the_experts_held(layer, p, x):
    """How many of the tokens' pairs fall on each expert held."""
    experts, _ = moe.route(x.reshape(-1, x.shape[-1]), p["router"], None,
                           k=layer.k, score="softmax", route_norm=True,
                           route_scale=1.0)
    first, count = layer._held
    return np.bincount(np.asarray(experts).ravel(),
                       minlength=layer.n_experts)[first:first + count]


def _one_rung_case(boost=0.0):
    """Nine of 72 experts held, ten a token: one tier of 1,024 x 9 rows,
    of which uniform routing fills a seventh; with `boost` the router has
    collapsed onto the experts held and every row of the tier is a pair."""
    cfg = _tiny(num_local_experts=72, num_experts_per_tok=10,
                experts_held=[0, 9])
    layer = _expert_layer(cfg, (0, 9))
    p, _ = layer.init_params(jax.random.PRNGKey(3),
                             InputType.recurrent(32, 512))
    x, ct = _normal(14, (2, 512, 32), (2, 512, 32))
    if boost:       # the rows are positive, so the boost is every token's
        p = {**p, "router": p["router"].at[:, :9].add(boost)}
        x = jnp.abs(x)
    return (functools.partial(_output_and_gradients, layer, p, x, ct),
            _pairs_of_the_experts_held(layer, p, x))


@jax.custom_vjp
def _poison(v, live):
    """`v` with NaN in every row at or past `live`, and its cotangent
    too: what a kernel may leave in a row it never writes."""
    return jnp.where((jnp.arange(v.shape[0]) < live)[:, None], v, jnp.nan)


_poison.defvjp(lambda v, live: (_poison(v, live), live),
               lambda live, g: (_poison(g, live), None))


def _kernels_in_interpret_mode(monkeypatch, product="kernel"):
    """An expert layer traced after this takes the path of the TPU on one
    device, its kernels in interpret mode (with `product` "ragged_dot",
    XLA's product between `ops/row_gather`'s kernels), and a call of XLA's
    own gathers fails. With the kernel's products every row past the
    pairs held holds NaN after every gather and product, forward and
    backward. Returns the list the products' schedules are put on as they
    are traced, each beside its left operand's rows."""
    gm = importlib.import_module("deeplearning4j_tpu.ops.grouped_matmul")
    rg = importlib.import_module("deeplearning4j_tpu.ops.row_gather")
    seen = []
    dead = _poison if product == "kernel" else (lambda v, live: v)
    products = {
        "ragged_dot": lambda a, w, plan: jax.lax.ragged_dot(
            a, w, group_sizes=plan.end - plan.start),
        "kernel": functools.partial(gm.grouped_dot, interpret=True)}

    def grouped(a, w, plan):
        seen.append((a.shape[0], plan))
        return dead(products[product](a, w, plan), plan.end[-1])

    take_rows, sum_rows = rg.take_rows, rg.sum_rows
    monkeypatch.setattr(moe, "_kernel_runs", lambda: True)
    monkeypatch.setattr(gm, "grouped_dot", grouped)
    monkeypatch.setattr(
        rg, "take_rows", lambda v, index, back, live, copies: tuple(
            dead(t, live)
            for t in take_rows(v, index, back, live, copies, True)))
    monkeypatch.setattr(
        rg, "sum_rows", lambda v, weight, index, back, live: sum_rows(
            dead(v, live), weight, index, back, live, True))
    monkeypatch.setattr(moe, "_take_rows", lambda *a: 1 / 0)
    return seen


def _rows_of_the_kernels(sizes, tier):
    """(visited, gathered): the rows the grouped products multiply, the
    visits of their schedule times a tile's rows, and the rows the gather
    moves, the tiles that hold a pair times a tile's rows, of experts
    holding `sizes` pairs in a tier of `tier` rows."""
    gm = importlib.import_module("deeplearning4j_tpu.ops.grouped_matmul")
    rows = gm.tile_rows(tier)
    end = np.cumsum(sizes)
    visits = sum((e - 1) // rows - (e - n) // rows + 1
                 for n, e in zip(sizes, end) if n)
    return visits * rows, -(-sizes.sum() // rows) * rows


@pytest.mark.parametrize("router", ["uniform", "collapsed"])
@pytest.mark.parametrize("product", ["ragged_dot", "kernel"])
def test_rows_in_no_group_change_nothing_in_a_layer_of_one_tier(
        product, router, monkeypatch):
    """Where the kernels run, a layer of one tier leaves the rows past the
    pairs held in no group and no gather moves them: output and every
    gradient (the input's, the three kernels', the router's) are what they
    are on the CPU's path, XLA's gathers with those rows counted to the
    last expert held and put at zero, whether `ragged_dot` or the kernel
    (in interpret mode) makes the products between `ops/row_gather`'s
    kernels (in interpret mode), at a seventh of the tier held and at all
    of it. With the kernel's products every row past the pairs held holds
    NaN after every gather and product, forward and backward: nothing
    reads one. And the counters read the tier on the path that walks every
    row, the tiles that hold a pair through the kernels."""
    run, sizes = _one_rung_case(30.0 if router == "collapsed" else 0.0)
    want, want_grads, counters = run()
    tier, held = 1024 * 9, sizes.sum()
    assert held == tier if router == "collapsed" else held < tier // 4
    for name in ("moe_rows_tier", "moe_rows_visited", "moe_rows_gathered"):
        assert int(counters[name]) == tier
    assert int(counters["moe_pairs_held"]) == held

    seen = _kernels_in_interpret_mode(monkeypatch, product)
    got, got_grads, counters = run()
    assert len(seen) == 3           # a tier is traced once
    _close(got, want, 1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(got_grads),
                    jax.tree_util.tree_leaves(want_grads)):
        assert np.isfinite(np.asarray(a)).all()
        _close(a, b, 1e-5)
    visited, gathered = _rows_of_the_kernels(sizes, tier)
    assert int(counters["moe_rows_tier"]) == tier
    assert int(counters["moe_rows_visited"]) == visited <= tier
    assert int(counters["moe_rows_gathered"]) == gathered
    assert int(counters["moe_pairs_dropped"]) == 0


def _laddered_layer(n_experts, count, k):
    layer = ExpertFeedForward(n_in=32, width=16, n_experts=n_experts,
                              held=(0, count), k=k, score="softmax",
                              route_norm=True, weight_init="xavier")
    p, _ = layer.init_params(jax.random.PRNGKey(4),
                             InputType.recurrent(32, 512))
    return layer, p


def test_a_layer_with_a_ladder_counts_every_row_to_a_group(monkeypatch):
    """Two of 64 experts at two a token have four tiers where the kernels
    do not run (the CPU, any mesh context): such a layer gives
    `ragged_dot` every row of the tier that runs, the rows past the pairs
    held counted to the last expert, so a tier costs the same whatever
    fell into it, XLA's gathers move its rows, and the counters read the
    tier."""
    gm = importlib.import_module("deeplearning4j_tpu.ops.grouped_matmul")
    layer, p = _laddered_layer(64, 2, 2)
    x, = _normal(15, (1, 512, 32))
    assert len(moe._row_tiers(1024, 2 / 64, 1024)) == 4
    want, _ = layer.apply(p, x)
    seen, ragged_dot = [], jax.lax.ragged_dot

    def spy(a, w, group_sizes):     # only the tier that runs calls back
        jax.debug.callback(
            lambda n: seen.append((a.shape[0], int(n))), jnp.sum(group_sizes))
        return ragged_dot(a, w, group_sizes=group_sizes)

    rg = importlib.import_module("deeplearning4j_tpu.ops.row_gather")
    assert not moe._kernel_runs()
    monkeypatch.setattr(gm, "grouped_dot", lambda *a, **kw: 1 / 0)
    monkeypatch.setattr(rg, "take_rows", lambda *a, **kw: 1 / 0)
    monkeypatch.setattr(rg, "sum_rows", lambda *a, **kw: 1 / 0)
    monkeypatch.setattr(jax.lax, "ragged_dot", spy)
    got, counters = layer.apply(p, x)
    jax.effects_barrier()
    np.testing.assert_array_equal(got, want)
    assert seen == [(128, 128)] * 3
    assert int(counters["moe_pairs_held"]) < 128
    assert int(counters["moe_rows_visited"]) \
        == int(counters["moe_rows_gathered"]) \
        == int(counters["moe_rows_tier"]) == 128


@pytest.mark.parametrize("n_experts,count,k", [(64, 2, 2), (160, 8, 6)])
def test_where_the_kernels_run_a_laddered_layer_has_one_tier(
        n_experts, count, k, monkeypatch):
    """The same layer where the kernels run (in interpret mode here), and
    eight of 160 experts at six a token: ONE tier of all that can fall on
    the experts held and no switch, the rows past the pairs held in no
    group and moved by no gather (they hold NaN after every gather and
    product, forward and backward), output and every gradient what the
    ladder's tier gives, the counters the tier's rows and the tiles that
    hold a pair, nothing dropped."""
    layer, p = _laddered_layer(n_experts, count, k)
    x, ct = _normal(15, (1, 512, 32), (1, 512, 32))
    most = 512 * k
    tiers = moe._row_tiers(most, count / n_experts, most)
    assert len(tiers) == 4 and tiers[-1] == most
    run = functools.partial(_output_and_gradients, layer, p, x, ct)
    want, want_grads, counters = run()
    assert int(counters["moe_rows_tier"]) < most
    seen = _kernels_in_interpret_mode(monkeypatch)
    monkeypatch.setattr(jax.lax, "switch", lambda *a, **kw: 1 / 0)
    monkeypatch.setattr(jax.lax, "ragged_dot", lambda *a, **kw: 1 / 0)
    got, got_grads, counters = run()
    assert [rows for rows, _ in seen] == [most] * 3    # traced once
    _close(got, want, 1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(got_grads),
                    jax.tree_util.tree_leaves(want_grads)):
        assert np.isfinite(np.asarray(a)).all()
        _close(a, b, 1e-5)
    sizes = _pairs_of_the_experts_held(layer, p, x)
    visited, gathered = _rows_of_the_kernels(sizes, most)
    assert int(counters["moe_pairs_held"]) == sizes.sum()
    assert int(counters["moe_rows_tier"]) == most
    assert int(counters["moe_rows_visited"]) == visited < most
    assert int(counters["moe_rows_gathered"]) == gathered < most
    assert int(counters["moe_pairs_dropped"]) == 0


# ---------------------------------------------------------- the tied head
def _small_net(tied, **head):
    from deeplearning4j_tpu.models import MultiLayerNetwork

    return MultiLayerNetwork(
        NeuralNetConfiguration.builder().seed(1).updater(Adam(1e-2))
        .activation("identity").weight_init("xavier").list(
            EmbeddingSequenceLayer(n_in=11, n_out=8, activation="identity",
                                   scale=3.0),
            RMSNormalization(),
            RnnOutputLayer(n_out=11, has_bias=False, activation="softmax",
                           loss="sparse_mcxent",
                           tied_to=tied, **head))
        .set_input_type(InputType.recurrent(1, 6)).build())


def _ids(seed, rows=4, t=6, top=11):
    rng = np.random.default_rng(seed)
    return (rng.integers(0, top, (rows, t)).astype(np.int32),
            rng.integers(0, top, (rows, t)).astype(np.int32))


@pytest.mark.parametrize("tied", [0, "layer0_embeddingsequencelayer"])
def test_a_tied_head_is_one_leaf_whose_gradient_sums_both_uses(tied):
    net = _small_net(tied).init()
    assert net.params_tree["layer2_rnnoutputlayer"] == {}
    assert net.num_params() == 11 * 8 + 8
    # and so one updater state: the head's own holds nothing
    assert not jax.tree_util.tree_leaves(
        net.updater_state["layer2_rnnoutputlayer"])
    x, y = _ids(0)
    loss = lambda p: net._loss(p, net.state_tree, jnp.asarray(x),
                               jnp.asarray(y), None, None, None,
                               train=True)[0]
    got = jax.grad(loss)(net.params_tree)["layer0_embeddingsequencelayer"]["W"]

    def apart(e, h):            # the same numbers, the two uses told apart
        g = net.params_tree["layer1_rmsnormalization"]["gamma"]
        a = rms_norm(jnp.take(e, x, axis=0) * 3.0, g, 1e-5)
        logp = jax.nn.log_softmax(a @ h.T, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, y[..., None], axis=-1))

    w = net.params_tree["layer0_embeddingsequencelayer"]["W"]
    assert float(loss(net.params_tree)) == pytest.approx(float(apart(w, w)),
                                                         rel=1e-6)
    as_rows, as_head = jax.grad(apart, (0, 1))(w, w)
    assert float(jnp.linalg.norm(as_rows)) > 0 < float(
        jnp.linalg.norm(as_head))
    _close(got, as_rows + as_head, 1e-5)


def test_a_saved_and_loaded_net_is_still_tied(tmp_path):
    from deeplearning4j_tpu.models.serialize import load_model, save_model

    net = _small_net(0).init()
    x, y = _ids(1)
    net.fit(x, y)
    path = os.path.join(tmp_path, "tied.zip")
    save_model(net, path)
    import zipfile

    with zipfile.ZipFile(path) as zf:
        saved = np.load(zf.open("coefficients.npz")).files
    assert sorted(saved) == ["layer0_embeddingsequencelayer/W",
                             "layer1_rmsnormalization/gamma"]
    back = load_model(path)
    assert back.params_tree["layer2_rnnoutputlayer"] == {}
    assert back._tied == net._tied == {
        "layer2_rnnoutputlayer": "layer0_embeddingsequencelayer"}
    np.testing.assert_array_equal(np.asarray(back.output(x)),
                                  np.asarray(net.output(x)))
    back.fit(x, y)
    net.fit(x, y)
    np.testing.assert_array_equal(
        np.asarray(back.params_tree["layer0_embeddingsequencelayer"]["W"]),
        np.asarray(net.params_tree["layer0_embeddingsequencelayer"]["W"]))


def test_an_untied_net_is_what_it_was():
    """No layer named: every layer is handed its own leaves, the very
    objects of the tree, the head has its own `W`, and a step's loss is
    the plain formula's to the bit."""
    net = _small_net(None).init()
    assert net._tied == {}
    for layer in net.layers:
        assert net._params_of(net.params_tree, layer) \
            is net.params_tree[layer.name]
    assert net.params_tree["layer2_rnnoutputlayer"]["W"].shape == (8, 11)
    x, y = _ids(2)
    p = net.params_tree
    a = rms_norm(jnp.take(p["layer0_embeddingsequencelayer"]["W"], x, axis=0)
                 * 3.0, p["layer1_rmsnormalization"]["gamma"], 1e-5)
    want = net.layers[-1].score(p["layer2_rnnoutputlayer"], a,
                                jnp.asarray(y))
    got = net._loss(p, net.state_tree, jnp.asarray(x), jnp.asarray(y), None,
                    None, None, train=True)[0]
    assert float(got) == float(want)


@pytest.mark.parametrize("tied,why", [(5, "names no other"),
                                      ("nowhere", "names no other"),
                                      (2, "names no other"),
                                      (1, "whose W is")])
def test_a_head_tied_to_nothing_it_can_read_is_refused(tied, why):
    with pytest.raises(ValueError, match=why):
        _small_net(tied).init()


# ------------------------------------------------- the given softmax scale
def _attention_layer(cfg, **kw):
    hq, hkv = cfg["attention_heads_held"][1], cfg["kv_heads_held"][1]
    return MultiHeadAttention(
        n_in=cfg["hidden_size"], n_out=cfg["hidden_size"], num_heads=hq,
        num_kv_heads=hkv,
        head_dim=cfg["hidden_size"] // cfg["num_attention_heads"],
        causal=True, bias=False, softmax_scale=cfg["attention_multiplier"],
        activation="identity", weight_init="xavier", **kw)


def test_attention_at_a_given_scale_is_the_reference_attention():
    """Two of four query heads over one of two KV heads, heads of 8 at a
    scale of 1/8 (not 8^-0.5), no positions."""
    cfg, ref = _tiny(), _reference()
    p = _mixer_leaves(ref.init_params(11, cfg)["layer2_prenormblock"])
    x, = _normal(12, (2, 128, 32))
    want = jnp.stack([ref.attention(
        {"mixer_" + k: v for k, v in p.items()}, seq, cfg, "float32")
        for seq in x])
    layer = _attention_layer(cfg)
    _close(layer.apply(p, x)[0], want, 1e-5)
    derived = MultiHeadAttention(**{**layer.__dict__, "softmax_scale": None})
    assert np.linalg.norm(derived.apply(p, x)[0] - want) \
        > 1e-3 * np.linalg.norm(want)
    # a padding mask takes the dense masked path: the same scale there
    _close(layer.apply(p, x, mask=jnp.ones((2, 128)))[0], want, 1e-5)


def test_the_flash_kernel_takes_the_given_scale():
    from deeplearning4j_tpu.ops.attention import flash_attention
    from deeplearning4j_tpu.parallel.ring_attention import attention

    q, k, v = _normal(13, (1, 256, 4, 16), (1, 256, 2, 16), (1, 256, 2, 16))
    w, = _normal(14, (1, 256, 4, 16))
    rep = lambda a: jnp.repeat(a, 2, axis=2)
    kernel = lambda q, k, v: flash_attention(q, k, v, True, 1.0 / 128, 128,
                                             128, True)
    dense = lambda q, k, v: attention(q, rep(k), rep(v), causal=True,
                                      scale=1.0 / 128)
    _close(kernel(q, k, v), dense(q, k, v), 1e-5)
    loss = lambda fn: (lambda *a: jnp.sum(fn(*a) * w))
    for a, b in zip(jax.grad(loss(kernel), (0, 1, 2))(q, k, v),
                    jax.grad(loss(dense), (0, 1, 2))(q, k, v)):
        _close(a, b, 1e-5)


def test_decode_steps_at_the_given_scale_are_the_full_pass():
    from deeplearning4j_tpu.models import MultiLayerNetwork

    net = MultiLayerNetwork(
        NeuralNetConfiguration.builder().seed(3).activation("identity")
        .weight_init("xavier").list(
            MultiHeadAttention(num_heads=2, head_dim=4, causal=True,
                               bias=False, softmax_scale=0.03, max_cache=8),
            RnnOutputLayer(n_out=5, activation="softmax", loss="mcxent"))
        .set_input_type(InputType.recurrent(8, 6)).build()).init()
    x, = _normal(15, (2, 6, 8))
    whole = np.asarray(net.output(x))
    net.rnn_clear_previous_state()
    steps = [np.asarray(net.rnn_time_step(x[:, i:i + 1])) for i in range(6)]
    np.testing.assert_allclose(np.concatenate(steps, axis=1), whole,
                               rtol=2e-5, atol=1e-6)


@pytest.mark.parametrize("kw", [{"window": 4}, {"sparse": {}}])
def test_a_given_scale_goes_with_no_window_and_no_selection(kw):
    with pytest.raises(ValueError, match="softmax_scale"):
        _attention_layer(_tiny(), **kw).init_params(
            jax.random.PRNGKey(0), InputType.recurrent(32, 8))


# -------------------------------------------------------------- the model
def _net(cfg, **kw):
    from deeplearning4j_tpu.models import MultiLayerNetwork
    from deeplearning4j_tpu.zoo import HybridStateSpaceTransformer

    return MultiLayerNetwork(HybridStateSpaceTransformer(
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


@pytest.mark.parametrize("checkpointing", [False, True])
def test_zoo_model_is_the_plain_reference(checkpointing):
    """Loss to 1e-5 and every leaf's gradient to 1e-4, 128 tokens (four
    chunks of 32), float32; the tied embedding's gradient among them."""
    cfg, ref = _tiny(), _reference()
    params = ref.init_params(7, cfg)
    net = _net(cfg, gradient_checkpointing=checkpointing).init()
    ours = {k: v for k, v in net.params_tree.items() if v}
    assert (jax.tree_util.tree_structure(params)
            == jax.tree_util.tree_structure(ours))
    assert net.params_tree["layer5_rnnoutputlayer"] == {}
    params["layer5_rnnoutputlayer"] = {}
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


def test_three_adam_steps_are_the_reference_steps():
    """`fit()` thrice against the benchmark's own follower of the plain
    reference under its Adam rule: each step's loss and every leaf's
    change."""
    from benchmarks import harness
    from deeplearning4j_tpu.data.dataset import DataSet

    cfg, ref = _tiny(), _reference()
    follow = harness.load_module("reference", "follow.py")
    rule = harness.load_module("reference", "rules", "adam.py")
    rng = np.random.default_rng(3)
    batches = [tuple(rng.integers(0, 600, (2, 128)).astype(np.int32)
                     for _ in range(2)) for _ in range(3)]
    start = ref.init_params(8, cfg)
    net = _net(cfg, gradient_checkpointing=True,
               updater=Adam(3e-4, 0.9, 0.95, 1e-8)).init()
    net.params_tree = {**jax.tree_util.tree_map(jnp.array, start),
                       "layer5_rnnoutputlayer": {}}
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
    """The reference's count of parameters and of multiply-adds, at the
    published widths and the cell's cut; the tied embedding once."""
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "granite_4_0_h_small.json"),
              encoding="utf-8") as fh:
        cfg = json.load(fh)
    ref = _reference()
    count = sum(math.prod(shape) for i in range(cfg["num_hidden_layers"])
                for shape in ref.layer_shapes(cfg, i).values())
    count += cfg["vocabulary_held"] * cfg["hidden_size"] + cfg["hidden_size"]
    assert count == 1_340_223_584
    mamba = sum(math.prod(s) for k, s in ref.layer_shapes(cfg, 0).items()
                if k.startswith("mixer_"))
    assert mamba == 26_359_136
    assert ref.forward_macs(cfg) / 8192 == pytest.approx(621.84e6, rel=1e-4)
    assert cfg["layer_types"] == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4


def test_fit_publishes_the_carry_and_the_routing_gauges_by_layer():
    from deeplearning4j_tpu.data.dataset import DataSet

    cfg = _tiny()
    net = _net(cfg, gradient_checkpointing=True).init()
    rng = np.random.default_rng(2)
    x, y = (rng.integers(0, 600, (2, 128)).astype(np.int32) for _ in range(2))
    net.fit(DataSet(x, y))
    carried = _gauges("ssm_chunk_carry")
    for name in ("layer1_prenormblock", "layer3_prenormblock"):
        state = net.state_tree[name]
        assert 0.0 < float(state["ssm_chunk_carry"]) < 1.0
        assert carried[name] == pytest.approx(
            float(state["ssm_chunk_carry"]), rel=1e-6)
    assert "ssm_chunk_carry" not in net.state_tree["layer2_prenormblock"]
    assert "layer2_prenormblock" not in carried
    for name in ("layer1_prenormblock", "layer2_prenormblock",
                 "layer3_prenormblock"):
        state = net.state_tree[name]
        assert int(state["moe_pairs_routed"]) == 2 * 128 * 3
        assert int(state["moe_pairs_dropped"]) == 0
        for counter in moe.COUNTERS:
            assert _gauges(counter)[name] == int(state[counter]), counter


def test_decode_names_the_layer_it_cannot_serve():
    net = _net(_tiny()).init()
    block = net.layers[1]
    with pytest.raises(NotImplementedError, match=block.name):
        block.decode_carry(1)
    with pytest.raises(NotImplementedError, match="SelectiveStateSpace"):
        net.rnn_time_step(np.zeros((1, 1), np.int32))


@pytest.mark.parametrize("key,value", [
    ("layer_types", ["mamba", "rwkv", "mamba"]),
    ("position_embedding_type", "rope"), ("tie_word_embeddings", False),
    ("mamba_proj_bias", True), ("mamba_expand", 4),
    ("shared_intermediate_size", 40)])
def test_a_configuration_the_builder_does_not_know_is_an_error(key, value):
    with pytest.raises(ValueError, match=key.split("_")[0]):
        _net(_tiny(**{key: value}))


def test_the_conf_round_trips():
    from deeplearning4j_tpu.nn.config import MultiLayerConfiguration

    conf = _net(_tiny(), gradient_checkpointing=True).conf
    back = MultiLayerConfiguration.from_json(conf.to_json())
    assert back.to_json() == conf.to_json()
    mixer = back.layers[1].mixer
    assert isinstance(mixer, SelectiveStateSpace)
    assert tuple(mixer.heads_held) == (0, 4)
    assert back.layers[2].mixer.softmax_scale == 0.125
    assert back.layers[-1].tied_to == 0
