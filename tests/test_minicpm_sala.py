"""The two mixers of the `minicpm_sala` family and the model built from
them, small, on the CPU, in float32 with seeded weights: the chunked
linear attention against the recurrence and the quadratic form, the block
selection against a plain per-token loop, the block-sparse kernels
(interpret mode) against a dense masked softmax, what the kernels leave
unread poisoned with NaN, and `zoo.HybridLinearSparseTransformer` against
the benchmark's plain reference, loss and every leaf's gradient."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.ops import linear_attention as la
from deeplearning4j_tpu.ops import sparse_attention as sp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HI = jax.lax.Precision.HIGHEST


def _normal(seed, *shapes):
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes))
    return [jax.random.normal(k, s, jnp.float32) for k, s in zip(keys, shapes)]


def _close(got, want, tol):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert np.linalg.norm(got - want) <= tol * max(np.linalg.norm(want),
                                                   1e-30)


# ------------------------------------------------------- linear attention
def _quadratic(q, k, v, rates):
    """((Q K^T) * D) V with D_ts = lambda^(t-s), all of it at once."""
    t, d = q.shape[1], q.shape[-1]
    gap = (jnp.arange(t)[:, None] - jnp.arange(t)[None, :]).astype(jnp.float32)
    decay = jnp.where(gap >= 0, jnp.exp(
        -jnp.asarray(rates)[:, None, None] * jnp.maximum(gap, 0)), 0.0)
    s = jnp.einsum("bthd,bshd->bhts", q, k, precision=HI) * decay[None]
    return jnp.einsum("bhts,bshd->bthd", s, v, precision=HI) / np.sqrt(d)


@pytest.mark.parametrize("oracle", ["recurrence", "quadratic"])
@pytest.mark.parametrize("chunk", [8, 16])
def test_chunked_linear_attention_is_the_recurrence(chunk, oracle):
    # 37 tokens: no multiple of either chunk; layer 1 of 32 decays fast
    # enough (lambda^16 = 2e-6 for the first head) to show a truncation
    b, t, h, d = 2, 37, 4, 8
    q, k, v, w = _normal(0, *[(b, t, h, d)] * 4)
    rates = la.decay_rates(h, 1, 32)
    plain = (la.linear_attention_recurrence if oracle == "recurrence"
             else _quadratic)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * w)

    chunked = lambda q, k, v: la.linear_attention(q, k, v, rates, chunk=chunk)
    want = lambda q, k, v: plain(q, k, v, rates)
    _close(chunked(q, k, v), want(q, k, v), 1e-5)
    for got, ref in zip(jax.grad(loss(chunked), (0, 1, 2))(q, k, v),
                        jax.grad(loss(want), (0, 1, 2))(q, k, v)):
        _close(got, ref, 1e-5)


def test_decay_follows_the_published_layer_index():
    first, last = la.decay_rates(32, 0, 32), la.decay_rates(32, 31, 32)
    assert first.shape == (32,) and np.all(np.diff(first) < 0)
    np.testing.assert_allclose(first[-1], 2.0 ** -8 * (1 + 1e-5), rtol=1e-6)
    np.testing.assert_allclose(last, first / (1 + 1e-5) * 1e-5, rtol=1e-4)


# ---------------------------------------------------------- the selection
def _select_loop(q, k, sel):
    """Steps (1) to (4) a token at a time, in float64."""
    q, k = np.asarray(q, np.float64), np.asarray(k, np.float64)
    b, t, h, d = q.shape
    hkv = k.shape[2]
    g, bs = h // hkv, sel.block_size
    nb = -(-t // bs)
    windows = range((t - sel.kernel_size) // sel.kernel_stride + 1)
    out = np.zeros((b, hkv, t, nb), bool)
    for bi in range(b):
        for gi in range(hkv):
            for ti in range(t):
                seen = [j for j in windows
                        if j * sel.kernel_stride + sel.kernel_size - 1 <= ti]
                score = np.zeros(nb)
                if seen:
                    kc = np.stack([k[bi, j * sel.kernel_stride:
                                     j * sel.kernel_stride + sel.kernel_size,
                                     gi].mean(0) for j in seen])
                    p = np.zeros(len(seen))
                    for hi in range(gi * g, (gi + 1) * g):
                        s = kc @ q[bi, ti, hi] / np.sqrt(d)
                        e = np.exp(s - s.max())
                        p += e / e.sum()
                    for n in range(nb):
                        over = [p[i] for i, j in enumerate(seen)
                                if j * sel.kernel_stride + sel.kernel_size - 1
                                >= n * bs
                                and j * sel.kernel_stride <= n * bs + bs - 1]
                        score[n] = max(over, default=0.0)
                own = ti // bs
                first = max(ti - sel.window_size + 1, 0) // bs
                forced = [n for n in range(own + 1)
                          if n < sel.init_blocks or n >= first]
                rest = sorted((n for n in range(own + 1) if n not in forced),
                              key=lambda n: (-score[n], n))
                keep = forced + rest[:max(sel.topk - len(forced), 0)]
                out[bi, gi, ti, keep] = True
    return out


SEL = sp.BlockSelection(block_size=8, topk=5, init_blocks=1, window_size=12,
                        kernel_size=4, kernel_stride=2, dense_len=16)
SELECTIONS = {
    "seeded": (SEL, 128, False),
    # queries of zero: every window scores the same, the lower block wins
    "ties": (SEL, 64, True),
    "just_over_dense_len": (SEL, 17, False),
    "fewer_blocks_than_topk": (SEL._replace(topk=40), 96, False),
    "two_initial_blocks_wide_window": (
        SEL._replace(init_blocks=2, window_size=20, topk=7), 80, False),
}


@pytest.mark.parametrize("case", sorted(SELECTIONS))
def test_selection_is_the_per_token_loops(case):
    sel, t, ties = SELECTIONS[case]
    q, k = _normal(1, (1, t, 4, 16), (1, t, 2, 16))
    if ties:
        q = jnp.zeros_like(q)
    got = np.asarray(sp.select_blocks(q, k, sel))
    want = _select_loop(q, k, sel)
    assert (got != want).sum() == 0
    kept, causal = sp.selection_counts(jnp.asarray(got), sel.block_size)
    assert int(kept) == want.sum()
    assert int(causal) == 2 * sum(i // sel.block_size + 1 for i in range(t))
    # no token reads more than topk blocks, nor a block ahead of its own
    assert got.sum(-1).max() <= sel.topk
    ahead = (np.arange(got.shape[-1])[None, :]
             > (np.arange(t) // sel.block_size)[:, None])
    assert not (got & ahead).any()


def test_future_keys_do_not_move_the_choice():
    """Keys ahead of a token are NaN: its row of the mask is what it was
    (the scores over windows not yet ended are masked before the softmax,
    and a row with fewer than topk blocks to choose from lists no other)."""
    sel, t, cut = SEL, 64, 24
    q, k = _normal(2, (1, t, 4, 16), (1, t, 2, 16))
    want = np.asarray(sp.select_blocks(q, k, sel))
    poisoned = k.at[:, cut:].set(jnp.nan)
    got = np.asarray(sp.select_blocks(q, poisoned, sel))
    assert (got[:, :, :cut] == want[:, :, :cut]).all()


# ------------------------------------------------------------ the kernels
def _sparse_case(t=256, h=4, hkv=2, d=16, seed=3):
    q, k, v, w = _normal(seed, (1, t, h, d), (1, t, hkv, d), (1, t, hkv, d),
                         (1, t, h, d))
    return q, k, v, w


@pytest.mark.parametrize("block_q,block_k", [(64, 128), (128, 256), (32, 128)])
def test_sparse_kernels_are_the_masked_softmax(block_q, block_k):
    sel = SEL._replace(window_size=20, topk=6)
    q, k, v, w = _sparse_case()
    allow = sp.select_blocks(q, k, sel)
    assert sp.sparse_eligible(256, sel.block_size, block_q, block_k)

    kernels = lambda q, k, v: sp.block_sparse_attention(
        q, k, v, allow, sel.block_size, None, block_q, block_k, True)
    masked = lambda q, k, v: sp.masked_attention(q, k, v, allow,
                                                 sel.block_size)
    _close(kernels(q, k, v), masked(q, k, v), 1e-5)
    loss = lambda fn: (lambda q, k, v: jnp.sum(fn(q, k, v) * w))
    for got, ref in zip(jax.grad(loss(kernels), (0, 1, 2))(q, k, v),
                        jax.grad(loss(masked), (0, 1, 2))(q, k, v)):
        _close(got, ref, 1e-5)


def test_with_every_block_kept_it_is_dense_causal_attention():
    sel = SEL._replace(topk=64)
    q, k, v, _ = _sparse_case()
    allow = sp.select_blocks(q, k, sel)
    assert int(allow.sum()) == int(sp.selection_counts(allow, sel.block_size)[1])
    got = sp.block_sparse_attention(q, k, v, allow, sel.block_size, None,
                                    64, 128, True)
    kk, vv = (jnp.repeat(a, 2, axis=2) for a in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, kk, precision=HI) / 4.0
    s = jnp.where(jnp.tril(jnp.ones((256, 256), bool)), s, -1e30)
    want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), vv,
                      precision=HI)
    _close(got, want, 1e-5)


@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_tiles_nobody_lists_are_never_read(direction):
    """A K tile (128 keys, 16 blocks of 8) that no token lists holds NaN
    in K and V: the kernels fetch and compute
    only tiles some token of the Q tile lists, so the result and every
    gradient are those of the same tensors with zeros there, and dK, dV
    of the unread keys are exactly zero. (On the chip an unvisited step
    reads whatever the last fetch left: a kernel that computed it anyway
    would show here.)"""
    t, bs = 512, 8
    q, k, v, w = _sparse_case(t=t)
    tok = np.arange(t)[:, None]
    blk = np.arange(t // bs)[None, :]
    # a local window of 4 blocks and the first block, and of K tile 1
    # (keys 128 to 255) nothing, not even for its own tokens
    allow = (blk <= tok // bs) & ((blk >= tok // bs - 3) | (blk == 0))
    dead = slice(128, 256)
    allow[:, 128 // bs:256 // bs] = False
    allow = jnp.asarray(np.broadcast_to(allow, (1, 2, t, t // bs)))
    poison = lambda a: a.at[:, dead].set(jnp.nan)
    zero = lambda a: a.at[:, dead].set(0.0)

    def run(k, v):
        fn = lambda q, k, v: sp.block_sparse_attention(
            q, k, v, allow, bs, None, 64, 128, True)
        if direction == "forward":
            return (fn(q, k, v),)
        return jax.grad(lambda q, k, v: jnp.sum(fn(q, k, v) * w),
                        (0, 1, 2))(q, k, v)

    got, want = run(poison(k), poison(v)), run(zero(k), zero(v))
    for a, b in zip(got, want):
        assert bool(jnp.all(jnp.isfinite(a)))
        _close(a, b, 1e-6)
    if direction == "backward":
        assert float(jnp.abs(got[1][:, dead]).max()) == 0.0
        assert float(jnp.abs(got[2][:, dead]).max()) == 0.0


# -------------------------------------------------------------- the model
def _tiny():
    with open(os.path.join(ROOT, "benchmarks", "tests", "configs",
                           "minicpm_sala_tiny.json"), encoding="utf-8") as fh:
        return json.load(fh)


def _net(cfg, timesteps=None, **kw):
    from deeplearning4j_tpu.models import MultiLayerNetwork
    from deeplearning4j_tpu.zoo import HybridLinearSparseTransformer

    return MultiLayerNetwork(HybridLinearSparseTransformer(
        cfg, timesteps=timesteps or cfg["input_shape"][0],
        vocabulary_held=cfg["vocabulary_held"], layers_published=32,
        **kw).conf())


def _gauge(name):
    from deeplearning4j_tpu.observe import get_registry

    return [g.value for g in get_registry().series() if g.name == name]


@pytest.mark.parametrize("checkpointing", [False, True])
def test_zoo_model_is_the_plain_reference(checkpointing):
    """Loss and every leaf's gradient, 128 tokens (twice `dense_len`: the
    selection runs), float32: to 1e-4 relative."""
    from benchmarks import harness

    cfg = _tiny()
    ref = harness.load_module("reference", "minicpm_sala.py")
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
    assert _gauge("sparse_layers_dense")[-1] == 0


def test_reference_chooses_the_blocks_the_program_chooses():
    from benchmarks import harness

    cfg = _tiny()
    ref = harness.load_module("reference", "minicpm_sala.py")
    sizes = ref.sparse_sizes(cfg)
    q, k = _normal(5, (1, 128, 4, 8), (1, 128, 2, 8))
    got = sp.select_blocks(q, k, sp.BlockSelection(**sizes))[0]
    want = ref.chosen_blocks(q[0], k[0], jnp.arange(128), sizes, 8 ** -0.5)
    assert (np.asarray(got) != np.moveaxis(np.asarray(want), 0, 1)).sum() == 0


def test_short_sequences_run_the_selecting_layer_dense():
    cfg = _tiny()
    net = _net(cfg, timesteps=64).init()        # 64 = dense_len
    rng = np.random.default_rng(1)
    x, y = (rng.integers(0, 600, (2, 64)).astype(np.int32) for _ in range(2))
    from deeplearning4j_tpu.data.dataset import DataSet

    net.fit(DataSet(x, y))
    assert _gauge("sparse_layers_dense")[-1] == 1
    kept, causal = _gauge("sparse_blocks_kept"), _gauge("sparse_blocks_causal")
    assert kept and kept == causal


def test_fit_publishes_the_blocks_kept():
    cfg = _tiny()
    net = _net(cfg, gradient_checkpointing=True).init()
    rng = np.random.default_rng(2)
    x, y = (rng.integers(0, 600, (2, 128)).astype(np.int32) for _ in range(2))
    from deeplearning4j_tpu.data.dataset import DataSet

    net.fit(DataSet(x, y))
    state = net.state_tree["layer1_prenormblock"]
    # 2 sequences x 2 KV groups: 128 tokens in blocks of 8, 6 kept
    assert int(state["sparse_blocks_causal"]) == 4 * sum(
        i // 8 + 1 for i in range(128))
    assert int(state["sparse_blocks_kept"]) == 4 * sum(
        min(i // 8 + 1, 6) for i in range(128))
    assert _gauge("sparse_blocks_kept")[-1] == int(state["sparse_blocks_kept"])


@pytest.mark.parametrize("layer", [1, 2])
def test_decode_names_the_layer_it_cannot_serve(layer):
    cfg = _tiny()
    net = _net(cfg).init()
    block = net.layers[layer]
    with pytest.raises(NotImplementedError, match=block.name):
        block.decode_carry(1)


def test_unknown_mixer_is_an_error_and_the_conf_round_trips():
    from deeplearning4j_tpu.utils.serde import from_json, to_json
    from deeplearning4j_tpu.zoo import HybridLinearSparseTransformer

    cfg = _tiny()
    with pytest.raises(ValueError, match="mamba"):
        HybridLinearSparseTransformer(
            {**cfg, "mixer_types": ["minicpm4", "mamba", "lightning-attn"]})
    conf = _net(cfg).conf
    again = from_json(to_json(conf))
    block = again.layers[1]
    assert block.mixer._selection == conf.layers[1].mixer._selection
    assert again.layers[2].mixer.decay_layer == 1
