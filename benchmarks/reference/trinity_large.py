"""Plain reference: one chip's share of an `afmoe` language model (Arcee
Trinity), written out from the published configuration's keys.

    h = E[ids] * sqrt(d)                                    (mup_enabled)
    every layer:   a = norm(h; g1)
                   q, k, v, gate = a Wq, a Wk, a Wv, sigmoid(a Wg)
                   q, k = norm(q; gq), norm(k; gk)   over each head's 128
                   sliding_attention: rotary positions on q and k, key j
                     visible to query i iff i - w < j <= i
                   full_attention: causal, no positions at all
                   h = h + norm((softmax(q k^T / sqrt(128)) v * gate) Wo; g2)
                   b = norm(h; g3);  h = h + norm(m; g4)
    dense layer:   m = (silu(b W1) * (b W3)) W2
    expert layer:  s = sigmoid(b Wr);  sel = the k largest of s + bias
                   wt = s[sel] / (sum s[sel] + 1e-20) * route_scale
                   m = shared(b) + sum over e in sel HELD HERE of
                       wt_e expert_e(b)
    loss = mean next-token cross-entropy of norm(h; gf) W_head over the
           rows of the vocabulary held here

`norm(x; g) = x / sqrt(mean(x^2) + eps) * g`; there is no bias anywhere.
What the experts on other chips would add to a token is left out, as the
program leaves it out: the cell is one rank of the deployment the
configuration's file describes, without its exchange.

Straightforward `jax.numpy` in float32 at matmul precision "highest";
imports nothing of the program; makes its own weights from the seed under
the program's leaf names. Each held expert is applied to EVERY token and
weighted by that token's weight for it, zero for most: no sorting, no
grouped product, nothing of the program's dispatch. So that the float32
parameters and their gradient (8 bytes a parameter) leave the activations
room on one chip, each half of every layer is under `jax.checkpoint` and
attention, the dense SwiGLU and the head's loss go a block of rows at a
time, which changes no arithmetic. Modes as in `resnet50.py`.
"""

from __future__ import annotations

import math
import os

import jax
import jax.numpy as jnp

from benchmarks.reference.arithmetic import operands, stored

# The runner follows this reference, minutes of float32 steps, BEFORE it
# builds the program's net. A checkout whose program cannot build this
# configuration (an older one under these benchmark files) is told so here
# and now, from the program's source text: nothing of it is imported.
_ZOO = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "deeplearning4j_tpu", "zoo",
    "transformer.py")
with open(_ZOO, encoding="utf-8") as _fh:
    if "class SparseSandwichTransformer" not in _fh.read():
        raise SystemExit("trinity_large: this checkout's program has no "
                         "zoo.SparseSandwichTransformer to build it with")

EMBED = "layer0_embeddingsequencelayer"
ROWS = 128          # query rows of attention at a time
WIDE_ROWS = 1024    # rows of the dense SwiGLU and of the head at a time
# `follow.py` calls `loss_fn(params, x, y, mode)` and hands it no
# configuration, and what a layer is (window or full, dense or experts,
# which experts are held) is not in the parameters' shapes: `init_params`,
# which every caller calls first, leaves its configuration here.
_CONFIG = {}


def _names(cfg):
    n = cfg["num_hidden_layers"]
    return ([f"layer{i}_sandwichtransformerblock" for i in range(1, n + 1)],
            f"layer{n + 1}_rmsnormalization", f"layer{n + 2}_rnnoutputlayer")


def causal_pairs(t: int, window=None) -> int:
    """(query, key) pairs of a causal layer over `t` tokens: query i sees
    `min(i + 1, window)` keys."""
    if window is None or window >= t:
        return t * (t + 1) // 2
    return window * (window + 1) // 2 + (t - window) * window


def forward_macs(cfg) -> int:
    """Multiply-accumulates of one sequence's forward pass, from the
    shapes. Attention's two products are counted over the pairs the causal
    band really has (25.2M a window layer and 33.6M a full one at 8,192),
    and the routed experts at their EXPECTATION under uniform routing:
    `k * held / n_experts` = 0.125 experts a token, not what one step's
    router sends here."""
    t, d = cfg["input_shape"][0], cfg["hidden_size"]
    h, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    f = cfg["moe_intermediate_size"]
    macs = 0
    for i, kind in enumerate(cfg["layer_types"]):
        macs += t * d * dh * (3 * h + 2 * hkv)       # Wq, Wg, Wo; Wk, Wv
        macs += 2 * h * dh * causal_pairs(
            t, cfg["sliding_window"] if kind == "sliding_attention" else None)
        if i < cfg["num_dense_layers"]:
            macs += t * 3 * d * cfg["intermediate_size"]
        else:
            routed = (cfg["num_experts_per_tok"] * cfg["experts_held"][1]
                      / cfg["num_experts"])
            macs += t * d * cfg["num_experts"]
            macs += int(t * 3 * d * f * (cfg["num_shared_experts"] + routed))
    return macs + t * d * cfg["vocabulary_held"]


def init_params(seed: int, cfg):
    """Kernels normal 0.02, embedding rows normal 1/sqrt(d), norm gains
    1 + normal 0.02, the selection bias normal `selection_bias_std`, all
    from the seed, one key a leaf."""
    _CONFIG.clear()
    _CONFIG.update(cfg)
    d, v = cfg["hidden_size"], cfg["vocabulary_held"]
    h, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    f, e = cfg["moe_intermediate_size"], cfg["num_experts"]
    held = cfg["experts_held"][1]
    blocks, last_norm, head = _names(cfg)

    def shapes(i):
        leaves = {"ln1_g": (d,), "ln2_g": (d,), "ln3_g": (d,), "ln4_g": (d,),
                  "attn_Wq": (d, h * dh), "attn_Wk": (d, hkv * dh),
                  "attn_Wv": (d, hkv * dh), "attn_Wo": (h * dh, d),
                  "attn_Wg": (d, h * dh), "attn_q_norm": (dh,),
                  "attn_k_norm": (dh,)}
        if i < cfg["num_dense_layers"]:
            w = cfg["intermediate_size"]
            leaves.update(ffn_w1=(d, w), ffn_w3=(d, w), ffn_w2=(w, d))
        else:
            fs = f * cfg["num_shared_experts"]
            leaves.update(moe_router=(d, e), moe_bias=(e,),
                          moe_w1=(held, d, f), moe_w3=(held, d, f),
                          moe_w2=(held, f, d), moe_shared_w1=(d, fs),
                          moe_shared_w3=(d, fs), moe_shared_w2=(fs, d))
        return leaves

    def leaf(key, name, shape):
        z = jax.random.normal(key, shape, jnp.float32)
        if name.endswith("_g") or name.endswith("_norm") or name == "gamma":
            return 1.0 + 0.02 * z
        if name == "moe_bias":
            return cfg["selection_bias_std"] * z
        return 0.02 * z

    @jax.jit
    def make(key):
        tree = {EMBED: {"W": (v, d)}, last_norm: {"gamma": (d,)},
                head: {"W": (d, v)},
                **{name: shapes(i) for i, name in enumerate(blocks)}}
        out = {}
        for li, (layer, leaves) in enumerate(sorted(tree.items())):
            out[layer] = {
                name: leaf(jax.random.fold_in(jax.random.fold_in(key, li),
                                              ni), name, shape)
                for ni, (name, shape) in enumerate(sorted(leaves.items()))}
        out[EMBED]["W"] = jax.random.normal(
            jax.random.fold_in(key, 10_000), (v, d), jnp.float32
        ) / math.sqrt(d)
        return out

    return make(jax.random.PRNGKey(seed % (2 ** 31)))


# ---------------------------------------------------------------- pieces
def _mm(a, b, mode):
    a, b, precision = operands(a, b, mode)
    return jnp.dot(a, b, precision=precision,
                   preferred_element_type=jnp.float32)


def _norm(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _in_row_blocks(fn, x, rows: int):
    """`fn` over `x`'s leading axis, `rows` at a time, each block under
    `jax.checkpoint`; the blocks' outputs joined again."""
    n = x.shape[0]
    rows = min(rows, n)
    if n % rows:
        raise ValueError(f"{n} rows do not divide into blocks of {rows}")
    out = jax.lax.map(jax.checkpoint(fn),
                      x.reshape((n // rows, rows) + x.shape[1:]))
    return out.reshape((n,) + out.shape[2:])


def _rope(x, theta: float):
    """Rotary positions on [T, heads, Dh]: the halves (x1, x2) of a head
    turn by position x theta^(-i / half)."""
    t, _, dh = x.shape
    half = dh // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    # (x1 c - x2 s, x2 c + x1 s), with (x2, x1) as a roll by half a head
    return x * jnp.concatenate([c, c], axis=-1) \
        + jnp.roll(x, half, axis=-1) * jnp.concatenate([-s, s], axis=-1)


def _attention(p, a, cfg, kind, mode):
    """One sequence's attention half before its output norm: a [T, d]."""
    t = a.shape[0]
    h, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps = cfg["rms_norm_eps"]
    q = _mm(a, p["attn_Wq"], mode).reshape(t, h, dh)
    k = _mm(a, p["attn_Wk"], mode).reshape(t, hkv, dh)
    v = stored(_mm(a, p["attn_Wv"], mode), mode).reshape(t, hkv, dh)
    gate = jax.nn.sigmoid(_mm(a, p["attn_Wg"], mode))
    q, k = _norm(q, p["attn_q_norm"], eps), _norm(k, p["attn_k_norm"], eps)
    window = None
    if kind == "sliding_attention":
        q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
        window = cfg["sliding_window"]
    q, k = stored(q, mode), stored(k, mode)
    key_ids = jnp.arange(t)[None, :]

    def block(args):
        qb, row_ids = args                       # [R, H, Dh], [R]
        qg = qb.reshape(-1, hkv, h // hkv, dh)   # head h reads KV h // G
        qo, ko, precision = operands(qg, k, mode)
        s = jnp.einsum("qhgd,khd->hgqk", qo, ko, precision=precision,
                       preferred_element_type=jnp.float32) / math.sqrt(dh)
        seen = key_ids <= row_ids[:, None]
        if window is not None:
            seen = seen & (key_ids > row_ids[:, None] - window)
        w = jax.nn.softmax(jnp.where(seen[None, None], s, -1e30), axis=-1)
        wo, vo, precision = operands(w, v, mode)
        o = jnp.einsum("hgqk,khd->qhgd", wo, vo, precision=precision,
                       preferred_element_type=jnp.float32)
        return o.reshape(-1, h * dh)

    rows = min(ROWS, t)
    o = jax.lax.map(jax.checkpoint(block),
                    (q.reshape(t // rows, rows, h, dh),
                     jnp.arange(t).reshape(t // rows, rows)))
    return _mm(stored(o.reshape(t, h * dh) * gate, mode), p["attn_Wo"], mode)


def _swiglu(x, w1, w3, w2, mode):
    return _mm(stored(jax.nn.silu(_mm(x, w1, mode)) * _mm(x, w3, mode),
                      mode), w2, mode)


def route(p, b, cfg):
    """(experts [T, k], weights [T, k]) of an expert layer, in float32
    from whatever `b` is."""
    s = jax.nn.sigmoid(jnp.dot(b, p["moe_router"],
                               precision=jax.lax.Precision.HIGHEST))
    _, sel = jax.lax.top_k(s + p["moe_bias"], cfg["num_experts_per_tok"])
    wt = jnp.take_along_axis(s, sel, axis=-1)
    if cfg["route_norm"]:
        wt = wt / (jnp.sum(wt, axis=-1, keepdims=True) + 1e-20)
    return sel, wt * cfg["route_scale"]


def _experts(p, b, cfg, mode):
    sel, wt = route(p, b, cfg)
    m = _swiglu(b, p["moe_shared_w1"], p["moe_shared_w3"],
                p["moe_shared_w2"], mode)
    first, count = cfg["experts_held"]
    # a token's weight for each expert held: zero for most
    weights = jnp.stack([jnp.sum(jnp.where(sel == first + e, wt, 0.0),
                                 axis=-1) for e in range(count)])

    @jax.checkpoint
    def one(b, w1, w3, w2, weight):
        return weight[:, None] * _swiglu(b, w1, w3, w2, mode)

    m, _ = jax.lax.scan(
        lambda m, expert: (m + one(b, *expert), None), m,
        (p["moe_w1"], p["moe_w3"], p["moe_w2"], weights))
    return m


def _layer(p, h, cfg, index, mode):
    """One layer over one sequence: h [T, d]. Each half is under a
    `jax.checkpoint` of its own, so the backward pass holds one half's
    activations at a time."""
    eps = cfg["rms_norm_eps"]
    kind = cfg["layer_types"][index]

    @jax.checkpoint
    def attention_half(p, h):
        a = stored(_norm(h, p["ln1_g"], eps), mode)
        return h + _norm(_attention(p, a, cfg, kind, mode), p["ln2_g"], eps)

    @jax.checkpoint
    def other_half(p, h):
        b = stored(_norm(h, p["ln3_g"], eps), mode)
        if index < cfg["num_dense_layers"]:
            m = _in_row_blocks(
                lambda rows: _swiglu(rows, p["ffn_w1"], p["ffn_w3"],
                                     p["ffn_w2"], mode), b, WIDE_ROWS)
        else:
            m = _experts(p, b, cfg, mode)
        return stored(h + _norm(m, p["ln4_g"], eps), mode)

    return other_half(p, attention_half(p, h))


def hidden_states(params, x, cfg, mode="float32", upto=None):
    """h [B, T, d] after `upto` layers (all of them by default)."""
    blocks, _, _ = _names(cfg)
    h = stored(jnp.take(params[EMBED]["W"], x, axis=0)
               * math.sqrt(cfg["hidden_size"]), mode)
    for i, name in enumerate(blocks[:upto]):
        h = jax.vmap(lambda seq, p=params[name], i=i: _layer(
            p, seq, cfg, i, mode))(h)
    return h


def loss_fn(params, x, y, mode="float32"):
    """Mean next-token cross-entropy of one batch. x, y: [B, T] int32."""
    cfg = _CONFIG
    _, last_norm, head = _names(cfg)
    h = hidden_states(params, x, cfg, mode)
    h = stored(_norm(h, params[last_norm]["gamma"], cfg["rms_norm_eps"]),
               mode).reshape(-1, cfg["hidden_size"])

    def block(args):
        rows, targets = args
        logp = jax.nn.log_softmax(_mm(rows, params[head]["W"], mode),
                                  axis=-1)
        return -jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0]

    n = h.shape[0]
    rows = min(WIDE_ROWS, n)
    nll = jax.lax.map(jax.checkpoint(block),
                      (h.reshape(n // rows, rows, -1),
                       y.reshape(n // rows, rows)))
    return jnp.mean(nll)
