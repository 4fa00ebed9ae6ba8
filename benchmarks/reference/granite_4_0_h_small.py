"""Plain reference: one chip's share of a `granitemoehybrid` language model
(Granite-4.0-H-Small), written out from the published configuration's keys.

With `norm(x; g) = x / sqrt(mean(x^2) + rms_norm_eps) * g` (no bias but
the convolution's), every layer is pre-norm and both halves enter the
stream times `residual_multiplier`:

    x0 = embedding_multiplier * E[ids]
    h  = x + r mixer(norm(x; g1))
    x' = h + r (experts(norm(h; g2)) + shared(norm(h; g2)))
    logits = norm(x_L; gf) E^T / logits_scaling         (E tied)
    loss   = mean next-token cross-entropy over the rows of E held here

"mamba" mixer (Mamba-2) over the H heads HELD HERE (`heads_held`, of
`mamba_n_heads`), `a` the normed input [T, d], P = `mamba_d_head`,
N = `mamba_d_state`, one group of B and C for all heads (`mamba_n_groups`
1), K = `mamba_d_conv`:

    [z | xBC | dt] = a W_in           z [T, H P], xBC [T, H P + 2 N], dt [T, H]
    xBC = silu(conv(xBC) + b_conv)    causal, depthwise: out_t = sum_k
                                      w[k] xBC_{t-K+1+k}, k = 0..K-1
    x [T, H, P], B [T, N], C [T, N] = split(xBC)
    dt = softplus(dt + dt_bias)       (no clamp: `time_step_limit` (0, inf))
    A_h = -exp(A_log_h)
    S_t = exp(dt_t,h A_h) S_{t-1} + dt_t,h x_t,h B_t^T      S [P, N] a head
    y_t,h = S_t C_t + D_h x_t,h
    mixer = norm(y * silu(z); g_ssm) W_out     the norm over the H P lanes
                                               held (what a rank has before
                                               the exchange of the statistic)

"attention" mixer over the query and KV heads held (`attention_heads_held`
of `num_attention_heads`, `kv_heads_held` of `num_key_value_heads`), heads
of `hidden_size / num_attention_heads`, no positions, no bias:
`softmax_{j<=i}(q_i . k_j * attention_multiplier) v`, `Wo` after.

Expert layer over `b = norm(h; g2)`: `logits = b W_r` in float32 over all
`num_local_experts`; the `num_experts_per_tok` largest (of equal ones the
lower expert); `w` = softmax over those; the sum over (e, w) with e HELD
HERE (`experts_held`) of `w swiglu_e(b)`, beside `shared(b) = swiglu(b)`
of `shared_intermediate_size`. The choice carries no gradient, the weights
do. LEFT OUT, not guessed: any balance loss (the config gives no
coefficient). What the absent experts and the absent heads would add is
left out, as the program leaves it out: the cell is one rank of the
deployment the configuration's file describes, without its exchange.

Straightforward `jax.numpy` in float32 at matmul precision "highest";
imports nothing of the program; makes its own weights from the seed under
the program's leaf names. The scan is THE RECURRENCE ITSELF, a token at a
time: no chunk algebra, no decay matrix, nothing of the program's op. An
outer `lax.scan` goes over blocks of `SCAN_BLOCK` tokens and each block's
inner scan is under `jax.checkpoint`, so the gradient keeps one state a
block and one block's states (268 MB at 32 heads), where the bare scan
over 8,192 tokens would keep 8.6 GB; that changes no arithmetic.
Attention is a dense masked softmax, a block of query rows against all
keys at a time; each held expert is applied to EVERY token and weighted
by that token's weight for it, zero for most. Each half of every layer is
under `jax.checkpoint` and the SwiGLUs and the head's loss go a block of
rows at a time. Modes as in `resnet50.py`.
"""

from __future__ import annotations

import math
import os

import jax
import jax.numpy as jnp

from benchmarks.reference.arithmetic import operands, stored

# A checkout whose program cannot build this configuration (an older one
# under these benchmark files) is told so here, before minutes of float32
# steps, from the program's source text: nothing of it is imported.
_ZOO = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "deeplearning4j_tpu", "zoo",
    "transformer.py")
with open(_ZOO, encoding="utf-8") as _fh:
    if "class HybridStateSpaceTransformer" not in _fh.read():
        raise SystemExit("granite_4_0_h_small: this checkout's program has "
                         "no zoo.HybridStateSpaceTransformer to build it "
                         "with")

EMBED = "layer0_embeddingsequencelayer"
ROWS = 128          # query rows of attention at a time
WIDE_ROWS = 1024    # rows of a SwiGLU and of the head at a time
SCAN_BLOCK = 256    # tokens of the recurrence under one checkpoint
# `follow.py` hands `loss_fn` no configuration: `init_params`, which every
# caller calls first, leaves it here
_CONFIG = {}


def _names(cfg):
    n = cfg["num_hidden_layers"]
    return ([f"layer{i}_prenormblock" for i in range(1, n + 1)],
            f"layer{n + 1}_rmsnormalization")


def heads_held(cfg):
    return tuple(cfg.get("heads_held", (0, cfg["mamba_n_heads"])))


def attention_held(cfg):
    """(query heads, KV heads) held here."""
    return (tuple(cfg.get("attention_heads_held",
                          (0, cfg["num_attention_heads"])))[1],
            tuple(cfg.get("kv_heads_held",
                          (0, cfg["num_key_value_heads"])))[1])


def experts_held(cfg):
    return tuple(cfg.get("experts_held", (0, cfg["num_local_experts"])))


def _sizes(cfg):
    """(H held, P, N, K, conv channels) of a mamba layer."""
    if cfg["mamba_n_groups"] != 1:
        raise NotImplementedError("more than one group of B and C")
    h, p, n = heads_held(cfg)[1], cfg["mamba_d_head"], cfg["mamba_d_state"]
    return h, p, n, cfg["mamba_d_conv"], h * p + 2 * n


def forward_macs(cfg) -> int:
    """Multiply-accumulates of one sequence's forward pass, from the
    shapes, over the heads, experts and rows held, counted as the
    mathematics needs them. A mamba mixer: its two projections, the
    convolution's taps, and the recurrence's state update and read-out,
    `2 P N` a head a token (the chunked form's masked `C B^T` and the
    exponentials are elementwise work and bytes, not counted). Attention's
    two products over the causal pairs. The routed experts at their
    EXPECTATION under uniform routing, `k x held / num_local_experts`
    experts a token, not what one step's router sends here."""
    t, d = cfg["input_shape"][0], cfg["hidden_size"]
    h, p, n, k, channels = _sizes(cfg)
    hq, hkv = attention_held(cfg)
    dh = d // cfg["num_attention_heads"]
    f, e = cfg["intermediate_size"], cfg["num_local_experts"]
    routed = cfg["num_experts_per_tok"] * experts_held(cfg)[1] / e
    macs = 0
    for kind in cfg["layer_types"]:
        if kind == "mamba":
            macs += t * d * (2 * h * p + 2 * n + h) + t * h * p * d
            macs += t * (k * channels + 2 * h * p * n + h * p)
        else:
            macs += t * d * dh * (2 * hq + 2 * hkv)
            macs += 2 * hq * dh * (t * (t + 1) // 2)
        macs += t * d * e
        macs += int(t * 3 * d * (cfg["shared_intermediate_size"]
                                 + f * routed))
    return macs + t * d * cfg["vocabulary_held"]


def layer_shapes(cfg, index: int) -> dict:
    d = cfg["hidden_size"]
    f, held = cfg["intermediate_size"], experts_held(cfg)[1]
    fs = cfg["shared_intermediate_size"]
    leaves = {"ln1_g": (d,), "ln2_g": (d,),
              "moe_router": (d, cfg["num_local_experts"]),
              "moe_w1": (held, d, f), "moe_w3": (held, d, f),
              "moe_w2": (held, f, d), "moe_shared_w1": (d, fs),
              "moe_shared_w3": (d, fs), "moe_shared_w2": (fs, d)}
    if cfg["layer_types"][index] == "mamba":
        h, p, n, k, channels = _sizes(cfg)
        leaves.update(mixer_in_proj=(d, 2 * h * p + 2 * n + h),
                      mixer_conv_w=(k, channels), mixer_conv_b=(channels,),
                      mixer_dt_bias=(h,), mixer_A_log=(h,), mixer_D=(h,),
                      mixer_norm=(h * p,), mixer_out_proj=(h * p, d))
    else:
        hq, hkv = attention_held(cfg)
        dh = d // cfg["num_attention_heads"]
        leaves.update(mixer_Wq=(d, hq * dh), mixer_Wk=(d, hkv * dh),
                      mixer_Wv=(d, hkv * dh), mixer_Wo=(hq * dh, d))
    return leaves


def init_params(seed: int, cfg):
    """Kernels, router and the convolution's taps and bias normal 0.02,
    embedding rows normal 1/sqrt(d), norm gains 1 + normal 0.02; Mamba-2's
    own for the rest: dt log-uniform in [1e-3, 1e-1] with `dt_bias` its
    inverse softplus, A uniform in [1, 16] with `A_log` its logarithm,
    D 1. All from the seed, one key a leaf. The head has no leaf: it is
    the embedding."""
    _CONFIG.clear()
    _CONFIG.update(cfg)
    d, v = cfg["hidden_size"], cfg["vocabulary_held"]
    blocks, last_norm = _names(cfg)

    def leaf(key, name, shape):
        if name == "mixer_dt_bias":
            dt = jnp.exp(jax.random.uniform(
                key, shape, jnp.float32, math.log(1e-3), math.log(1e-1)))
            return dt + jnp.log(-jnp.expm1(-dt))
        if name == "mixer_A_log":
            return jnp.log(jax.random.uniform(key, shape, jnp.float32,
                                              1.0, 16.0))
        if name == "mixer_D":
            return jnp.ones(shape, jnp.float32)
        z = jax.random.normal(key, shape, jnp.float32)
        if name.endswith("_g") or name.endswith("_norm") or name == "gamma":
            return 1.0 + 0.02 * z
        return 0.02 * z

    @jax.jit
    def make(key):
        tree = {EMBED: {"W": (v, d)}, last_norm: {"gamma": (d,)},
                **{name: layer_shapes(cfg, i)
                   for i, name in enumerate(blocks)}}
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


def _in_row_blocks(fn, xs, rows: int):
    """`fn` over the leading axis of every array of the tuple `xs`, `rows`
    at a time, each block under `jax.checkpoint`; the outputs joined."""
    n = xs[0].shape[0]
    rows = min(rows, n)
    if n % rows:
        raise ValueError(f"{n} rows do not divide into blocks of {rows}")
    out = jax.lax.map(jax.checkpoint(fn), tuple(
        x.reshape((n // rows, rows) + x.shape[1:]) for x in xs))
    return out.reshape((n,) + out.shape[2:])


def recurrence(x, dt, a, b, c):
    """`y_t = S_t C_t` with `S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T`, a
    token at a time: x [T, H, P], dt [T, H], a [H], b and c [T, N]."""
    t, h, p = x.shape
    n = b.shape[-1]
    block = min(SCAN_BLOCK, t)
    if t % block:
        raise ValueError(f"{t} tokens do not divide into blocks of {block}")
    hi = jax.lax.Precision.HIGHEST

    def token(state, row):
        xt, dtt, bt, ct = row                   # [H, P], [H], [N], [N]
        state = (jnp.exp(dtt * a)[:, None, None] * state
                 + (dtt[:, None] * xt)[:, :, None] * bt[None, None, :])
        return state, jnp.einsum("hpn,n->hp", state, ct, precision=hi)

    @jax.checkpoint
    def tokens(state, rows):
        return jax.lax.scan(token, state, rows)

    _, y = jax.lax.scan(tokens, jnp.zeros((h, p, n), jnp.float32), tuple(
        v.reshape((t // block, block) + v.shape[1:]) for v in (x, dt, b, c)))
    return y.reshape(t, h, p)


def mamba(p, a, cfg, mode):
    """The Mamba-2 mixer over one sequence, the heads held: a [T, d] ->
    [T, d]."""
    t = a.shape[0]
    h, hp, n, taps, channels = _sizes(cfg)
    inner = h * hp
    zxbcdt = _mm(a, p["mixer_in_proj"], mode)
    z, xbc, dt = (zxbcdt[:, :inner], zxbcdt[:, inner:inner + channels],
                  zxbcdt[:, inner + channels:])
    # out_t = sum_k w[k] in_{t - (K - 1) + k}: the last tap on the token
    past = jnp.concatenate([jnp.zeros((taps - 1, channels), xbc.dtype),
                            stored(xbc, mode)])
    conv = p["mixer_conv_b"] + sum(
        p["mixer_conv_w"][k] * jax.lax.dynamic_slice_in_dim(past, k, t)
        for k in range(taps))
    xbc = stored(jax.nn.silu(conv), mode)
    x = xbc[:, :inner].reshape(t, h, hp)
    b, c = xbc[:, inner:inner + n], xbc[:, inner + n:]
    dt = jax.nn.softplus(dt + p["mixer_dt_bias"])
    y = recurrence(x, dt, -jnp.exp(p["mixer_A_log"]), b, c)
    y = y + p["mixer_D"][:, None] * x
    y = _norm(stored(y.reshape(t, inner), mode) * jax.nn.silu(z),
              p["mixer_norm"], cfg["rms_norm_eps"])
    return _mm(stored(y, mode), p["mixer_out_proj"], mode)


def attention(p, a, cfg, mode):
    """GQA softmax attention over one sequence, the heads held, no
    positions, the scale given: a [T, d] -> [T, d]."""
    t = a.shape[0]
    hq, hkv = attention_held(cfg)
    dh = cfg["hidden_size"] // cfg["num_attention_heads"]
    q = stored(_mm(a, p["mixer_Wq"], mode), mode).reshape(t, hkv, hq // hkv,
                                                          dh)
    k = stored(_mm(a, p["mixer_Wk"], mode), mode).reshape(t, hkv, dh)
    v = stored(_mm(a, p["mixer_Wv"], mode), mode).reshape(t, hkv, dh)
    key_ids = jnp.arange(t)[None, :]

    def block(args):
        qb, row_ids = args                          # [R, Hkv, G, Dh], [R]
        qo, ko, precision = operands(qb, k, mode)
        s = jnp.einsum("qhgd,khd->hgqk", qo, ko, precision=precision,
                       preferred_element_type=jnp.float32) \
            * cfg["attention_multiplier"]
        seen = key_ids <= row_ids[:, None]
        w = jax.nn.softmax(jnp.where(seen[None, None], s, -1e30), axis=-1)
        wo, vo, precision = operands(w, v, mode)
        o = jnp.einsum("hgqk,khd->qhgd", wo, vo, precision=precision,
                       preferred_element_type=jnp.float32)
        return o.reshape(-1, hq * dh)

    o = _in_row_blocks(block, (q, jnp.arange(t)), ROWS)
    return _mm(stored(o, mode), p["mixer_Wo"], mode)


def _swiglu(x, w1, w3, w2, mode):
    return _mm(stored(jax.nn.silu(_mm(x, w1, mode)) * _mm(x, w3, mode),
                      mode), w2, mode)


def route(p, b, cfg):
    """(experts [T, k], weights [T, k]) of an expert layer, in float32
    from whatever `b` is: the k largest logits and a softmax over them."""
    logits = jnp.dot(b, p["moe_router"], precision=jax.lax.Precision.HIGHEST)
    # `top_k` puts the lower index first among equal values
    _, sel = jax.lax.top_k(jax.lax.stop_gradient(logits),
                           cfg["num_experts_per_tok"])
    return sel, jax.nn.softmax(jnp.take_along_axis(logits, sel, axis=-1),
                               axis=-1)


def experts(p, b, cfg, mode):
    """The expert layer over one sequence's normed rows b [T, d]."""
    sel, wt = route(p, b, cfg)
    m = _in_row_blocks(
        lambda args: _swiglu(args[0], p["moe_shared_w1"], p["moe_shared_w3"],
                             p["moe_shared_w2"], mode), (b,), WIDE_ROWS)
    first, count = experts_held(cfg)
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
    eps, r = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    mixer = mamba if cfg["layer_types"][index] == "mamba" else attention

    @jax.checkpoint
    def mixer_half(p, h):
        a = stored(_norm(h, p["ln1_g"], eps), mode)
        return h + r * mixer(p, a, cfg, mode)

    @jax.checkpoint
    def other_half(p, h):
        b = stored(_norm(h, p["ln2_g"], eps), mode)
        return stored(h + r * experts(p, b, cfg, mode), mode)

    return other_half(p, mixer_half(p, h))


def hidden_states(params, x, cfg, mode="float32", upto=None):
    """h [B, T, d] after `upto` layers (all of them by default)."""
    blocks, _ = _names(cfg)
    h = stored(jnp.take(params[EMBED]["W"], x, axis=0)
               * cfg["embedding_multiplier"], mode)
    for i, name in enumerate(blocks[:upto]):
        h = jax.vmap(lambda seq, p=params[name], i=i: _layer(
            p, seq, cfg, i, mode))(h)
    return h


def loss_fn(params, x, y, mode="float32"):
    """Mean next-token cross-entropy of one batch. x, y: [B, T] int32."""
    cfg = _CONFIG
    _, last_norm = _names(cfg)
    d = cfg["hidden_size"]
    h = hidden_states(params, x, cfg, mode)
    h = _norm(h, params[last_norm]["gamma"], cfg["rms_norm_eps"]) \
        / cfg["logits_scaling"]
    h = stored(h, mode).reshape(-1, d)
    head = params[EMBED]["W"].T                 # tied: the embedding itself

    def block(args):
        rows, targets = args
        logp = jax.nn.log_softmax(_mm(rows, head, mode), axis=-1)
        return -jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0]

    return jnp.mean(_in_row_blocks(block, (h, y.reshape(-1)), WIDE_ROWS))
