"""Plain reference: one chip's share of a `nemotron_h` language model
(NVIDIA-Nemotron-3-Super-120B-A12B), written out from the published
configuration's keys.

With `norm(x; g) = x / sqrt(mean(x^2) + layer_norm_epsilon) * g` (no bias
but the convolution's), every layer is ONE pre-norm residual sublayer,
`a <- a + f(norm(a; g))`, f by `hybrid_override_pattern`'s letter:

    a0 = E[ids]
    M: Mamba-2     *: attention     E: LatentMoE experts
    logits = norm(a_L; norm_f) W                      (W untied)

`M` over the H heads HELD HERE (`heads_held`, of `mamba_num_heads`: whole
groups of `mamba_num_heads / n_groups` heads), b the normed input [T, d],
P = `mamba_head_dim`, N = `ssm_state_size`, G the groups held,
K = `conv_kernel`:

    [z | xBC | dt] = b W_in        z [T, H P], xBC [T, H P + 2 G N], dt [T, H]
    xBC = silu(conv(xBC) + b_conv)    causal, depthwise: out_t = sum_k
                                      w[k] xBC_{t-K+1+k}, k = 0..K-1
    x [T, H, P], B [T, G, N], C [T, G, N] = split(xBC)
    dt = softplus(dt + dt_bias);  A_h = -exp(A_log_h)
    S_t = exp(dt_t,h A_h) S_{t-1} + dt_t,h x_t,h B_t,g(h)^T     [P, N] a head
    y_t,h = S_t C_t,g(h) + D_h x_t,h         g(h) = h // (H / G)
    f = groupnorm(y * silu(z); g_ssm) W_out  the RMS norm over each
                                             group's H P / G lanes on its own

`*` over the query and KV heads held (`attention_heads_held` of
`num_attention_heads`, `kv_heads_held` of `num_key_value_heads`), heads of
`head_dim`, NO positions (the family applies no rotary embedding), no
bias: `softmax_{j<=i}(q_i . k_j / sqrt(head_dim)) v`, `Wo` after.

`E` over b [T, d]: `s = sigmoid(b W_r)` in float32 over all
`n_routed_experts`; the `num_experts_per_tok` largest of `s + bias` (of
equal ones the lower expert; the bias steers the choice alone);
`w = routed_scaling_factor x s_chosen / (sum s_chosen + 1e-20)`;

    u = b W_lat_down                                        [T, moe_latent_size]
    f = (sum over (e, w) with e HELD HERE of w relu(u W_up,e)^2 W_down,e) W_lat_up
        + relu(b W_s,up)^2 W_s,down

The choice carries no gradient, the weights do. LEFT OUT, not guessed: any
balance loss and any update of the bias (the config gives no coefficient).

The multi-token-prediction module (`num_nextn_predict_layers` 1,
`mtp_hybrid_override_pattern`), DeepSeek-V3's (arXiv:2412.19437, 2.2),
with h the trunk's output BEFORE `norm_f` and y the labels:

    g_t = [norm(h_t; norm_h) ; norm(E[y_t]; norm_e)] W_eh        2 d -> d
    g   = the pattern's sublayers over g, as above
    logits2_t = norm(g_t; norm_mtp) W                 the main head's W
    loss = mean_t ce(logits_t, y_t)
           + mtp_loss_scaling_factor x mean_{t < T-1} ce(logits2_t, y_{t+1})

What the absent experts and the absent heads would add is left out, as
the program leaves it out: the cell is one rank of the deployment the
configuration's file describes, without its exchange.

Straightforward `jax.numpy` in float32 at matmul precision "highest";
imports nothing of the program; makes its own weights from the seed under
the program's leaf names. The scan is THE RECURRENCE ITSELF, a token at a
time, in blocks of `SCAN_BLOCK` tokens under `jax.checkpoint`. Attention
is a dense masked softmax, a block of query rows against all keys at a
time; each held expert is applied to EVERY token and weighted by that
token's weight for it, zero for most. Every layer is under a
`jax.checkpoint` of its own, the two head passes apart, and the wide
products go a block of rows at a time in Python loops. Modes as in
`resnet50.py`.
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
    if "class HybridLatentExpertTransformer" not in _fh.read():
        raise SystemExit("nemotron_3_super: this checkout's program has no "
                         "zoo.HybridLatentExpertTransformer to build it "
                         "with")

EMBED = "layer0_embeddingsequencelayer"
ROWS = 128          # query rows of attention at a time
WIDE_ROWS = 1024    # rows of a wide product and of the head at a time
SCAN_BLOCK = 256    # tokens of the recurrence under one checkpoint
# `follow.py` hands `loss_fn` no configuration: `init_params`, which every
# caller calls first, leaves it here
_CONFIG = {}


def _names(cfg):
    n = cfg["num_hidden_layers"]
    return ([f"layer{i}_prenormsublayer" for i in range(1, n + 1)],
            f"layer{n + 1}_multitokenoutputlayer")


def heads_held(cfg):
    return tuple(cfg.get("heads_held", (0, cfg["mamba_num_heads"])))


def attention_held(cfg):
    """(query heads, KV heads) held here."""
    return (tuple(cfg.get("attention_heads_held",
                          (0, cfg["num_attention_heads"])))[1],
            tuple(cfg.get("kv_heads_held",
                          (0, cfg["num_key_value_heads"])))[1])


def experts_held(cfg):
    return tuple(cfg.get("experts_held", (0, cfg["n_routed_experts"])))


def mtp_pattern(cfg) -> str:
    return cfg["mtp_hybrid_override_pattern"] \
        if cfg.get("num_nextn_predict_layers", 0) else ""


def _sizes(cfg):
    """(H held, P, G held, N, K, conv channels) of an `M` layer."""
    first, h = heads_held(cfg)
    per = cfg["mamba_num_heads"] // cfg["n_groups"]
    if first % per or h % per:
        raise ValueError(f"heads_held {(first, h)} is no whole groups of "
                         f"{per} heads")
    p, n, g = cfg["mamba_head_dim"], cfg["ssm_state_size"], h // per
    return h, p, g, n, cfg["conv_kernel"], h * p + 2 * g * n


def layer_macs(cfg, letter: str) -> int:
    """Forward multiply-accumulates of one sublayer over one sequence."""
    t, d = cfg["input_shape"][0], cfg["hidden_size"]
    if letter == "M":
        h, p, g, n, k, channels = _sizes(cfg)
        return (t * d * (2 * h * p + 2 * g * n + h) + t * h * p * d
                + t * (k * channels + 2 * h * p * n + h * p))
    if letter == "*":
        hq, hkv = attention_held(cfg)
        dh = cfg["head_dim"]
        return (t * d * dh * (2 * hq + 2 * hkv)
                + 2 * hq * dh * (t * (t + 1) // 2))
    latent, e = cfg["moe_latent_size"], cfg["n_routed_experts"]
    routed = cfg["num_experts_per_tok"] * experts_held(cfg)[1] / e
    return (t * d * e + 2 * t * d * latent
            + 2 * t * d * cfg["moe_shared_expert_intermediate_size"]
            + int(t * routed * 2 * latent * cfg["moe_intermediate_size"]))


def forward_macs(cfg) -> int:
    """Multiply-accumulates of one sequence's forward pass, from the
    shapes, over the heads, experts and rows held, counted as the
    mathematics needs them. An `M` layer: its two projections, the
    convolution's taps, and the recurrence's state update and read-out,
    `2 P N` a head a token. Attention's two products over the causal
    pairs. An `E` layer: the router, both latent projections, the shared
    expert's two products and the routed experts' at their EXPECTATION
    under uniform routing, `k x held / n_routed_experts` experts a token.
    The head once, and for the prediction module `W_eh`, its sublayers and
    the head again (over all T positions: the last has no target and is a
    8,192nd of them)."""
    t, d, v = cfg["input_shape"][0], cfg["hidden_size"], \
        cfg["vocabulary_held"]
    macs = sum(layer_macs(cfg, letter)
               for letter in cfg["hybrid_override_pattern"]) + t * d * v
    if mtp_pattern(cfg):
        macs += t * 2 * d * d + t * d * v + sum(
            layer_macs(cfg, letter) for letter in mtp_pattern(cfg))
    return macs


def sublayer_shapes(cfg, letter: str) -> dict:
    d = cfg["hidden_size"]
    if letter == "M":
        h, p, g, n, k, channels = _sizes(cfg)
        inner = dict(in_proj=(d, 2 * h * p + 2 * g * n + h),
                     conv_w=(k, channels), conv_b=(channels,),
                     dt_bias=(h,), A_log=(h,), D=(h,), norm=(h * p,),
                     out_proj=(h * p, d))
    elif letter == "*":
        hq, hkv = attention_held(cfg)
        dh = cfg["head_dim"]
        inner = dict(Wq=(d, hq * dh), Wk=(d, hkv * dh), Wv=(d, hkv * dh),
                     Wo=(hq * dh, d))
    elif letter == "E":
        held, e = experts_held(cfg)[1], cfg["n_routed_experts"]
        f, latent = cfg["moe_intermediate_size"], cfg["moe_latent_size"]
        fs = cfg["moe_shared_expert_intermediate_size"]
        inner = dict(router=(d, e), bias=(e,), w1=(held, latent, f),
                     w2=(held, f, latent), shared_w1=(d, fs),
                     shared_w2=(fs, d), latent_down=(d, latent),
                     latent_up=(latent, d))
    else:
        raise ValueError(f"pattern letter {letter!r}")
    return {"ln_g": (d,), **{"f_" + k: s for k, s in inner.items()}}


def head_shapes(cfg) -> dict:
    d, v = cfg["hidden_size"], cfg["vocabulary_held"]
    leaves = {"norm_f": (d,), "W": (d, v)}
    if mtp_pattern(cfg):
        leaves.update(mtp_norm_h=(d,), mtp_norm_e=(d,),
                      mtp_eh_proj=(2 * d, d), mtp_norm=(d,))
        for i, letter in enumerate(mtp_pattern(cfg)):
            leaves.update({f"mtp_layer{i}_{k}": s for k, s in
                           sublayer_shapes(cfg, letter).items()})
    return leaves


def init_params(seed: int, cfg):
    """Kernels, router and the convolution's taps and bias normal 0.02,
    the selection bias normal `selection_bias_std`, embedding rows normal
    1/sqrt(d), norm gains 1 + normal 0.02; Mamba-2's own for the rest: dt
    log-uniform in [`time_step_min`, `time_step_max`] (never under
    `time_step_floor`) with `dt_bias` its inverse softplus, A uniform in
    [1, 16] with `A_log` its logarithm, D 1. All from the seed, one key a
    leaf."""
    _CONFIG.clear()
    _CONFIG.update(cfg)
    d, v = cfg["hidden_size"], cfg["vocabulary_held"]
    blocks, head = _names(cfg)
    lo, hi = cfg["time_step_min"], cfg["time_step_max"]

    def leaf(key, name, shape):
        if name.endswith("f_dt_bias"):
            dt = jnp.maximum(jnp.exp(jax.random.uniform(
                key, shape, jnp.float32, math.log(lo), math.log(hi))),
                cfg["time_step_floor"])
            return dt + jnp.log(-jnp.expm1(-dt))
        if name.endswith("f_A_log"):
            return jnp.log(jax.random.uniform(key, shape, jnp.float32,
                                              1.0, 16.0))
        if name.endswith("f_D"):
            return jnp.ones(shape, jnp.float32)
        z = jax.random.normal(key, shape, jnp.float32)
        if name.endswith("f_bias"):
            return cfg["selection_bias_std"] * z
        if len(shape) == 1 and not name.endswith("f_conv_b"):
            return 1.0 + 0.02 * z       # every other vector is a norm's gain
        return 0.02 * z

    @jax.jit
    def make(key):
        tree = {EMBED: {"W": (v, d)}, head: head_shapes(cfg),
                **{name: sublayer_shapes(cfg, letter) for name, letter
                   in zip(blocks, cfg["hybrid_override_pattern"])}}
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


def _in_row_blocks(fn, *xs, rows: int):
    """`fn` over the leading axis of `xs`, `rows` at a time in a Python
    loop, each block under `jax.checkpoint`; the blocks' outputs joined
    again."""
    n = xs[0].shape[0]
    rows = min(rows, n)
    if n % rows:
        raise ValueError(f"{n} rows do not divide into blocks of {rows}")
    return jnp.concatenate([
        jax.checkpoint(fn)(*(x[i:i + rows] for x in xs))
        for i in range(0, n, rows)])


def recurrence(x, dt, a, b, c):
    """`y_t,h = S_t,h C_t,g(h)` with `S_t,h = exp(dt_t,h A_h) S_{t-1},h +
    dt_t,h x_t,h B_t,g(h)^T`, a token at a time: x [T, H, P], dt [T, H],
    a [H], b and c [T, G, N], head h in group `h // (H / G)`."""
    t, h, p = x.shape
    g, n = b.shape[1:]
    block = min(SCAN_BLOCK, t)
    if t % block:
        raise ValueError(f"{t} tokens do not divide into blocks of {block}")
    hi = jax.lax.Precision.HIGHEST

    def token(state, row):
        xt, dtt, bt, ct = row               # [H, P], [H], [G, N], [G, N]
        bt, ct = (jnp.repeat(v, h // g, axis=0) for v in (bt, ct))  # [H, N]
        state = (jnp.exp(dtt * a)[:, None, None] * state
                 + (dtt[:, None] * xt)[:, :, None] * bt[:, None, :])
        return state, jnp.einsum("hpn,hn->hp", state, ct, precision=hi)

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
    h, hp, g, n, taps, channels = _sizes(cfg)
    inner = h * hp
    zxbcdt = _mm(a, p["f_in_proj"], mode)
    z, xbc, dt = (zxbcdt[:, :inner], zxbcdt[:, inner:inner + channels],
                  zxbcdt[:, inner + channels:])
    # out_t = sum_k w[k] in_{t - (K - 1) + k}: the last tap on the token
    past = jnp.concatenate([jnp.zeros((taps - 1, channels), xbc.dtype),
                            stored(xbc, mode)])
    conv = p["f_conv_b"] + sum(
        p["f_conv_w"][k] * jax.lax.dynamic_slice_in_dim(past, k, t)
        for k in range(taps))
    xbc = stored(jax.nn.silu(conv), mode)
    x = xbc[:, :inner].reshape(t, h, hp)
    b = xbc[:, inner:inner + g * n].reshape(t, g, n)
    c = xbc[:, inner + g * n:].reshape(t, g, n)
    dt = jax.nn.softplus(dt + p["f_dt_bias"])
    y = recurrence(x, dt, -jnp.exp(p["f_A_log"]), b, c)
    y = y + p["f_D"][:, None] * x
    y = stored(y.reshape(t, inner), mode) * jax.nn.silu(z)
    # each group's lanes normed on their own
    y = _norm(y.reshape(t, g, inner // g),
              p["f_norm"].reshape(g, inner // g),
              cfg["layer_norm_epsilon"]).reshape(t, inner)
    return _mm(stored(y, mode), p["f_out_proj"], mode)


def attention(p, a, cfg, mode):
    """GQA softmax attention over one sequence, the heads held, no
    positions, scale 1/sqrt(head_dim): a [T, d] -> [T, d]."""
    t = a.shape[0]
    hq, hkv = attention_held(cfg)
    dh = cfg["head_dim"]
    q = stored(_mm(a, p["f_Wq"], mode), mode).reshape(t, hkv, hq // hkv, dh)
    k = stored(_mm(a, p["f_Wk"], mode), mode).reshape(t, hkv, dh)
    v = stored(_mm(a, p["f_Wv"], mode), mode).reshape(t, hkv, dh)
    key_ids = jnp.arange(t)[None, :]

    def block(qb, row_ids):                         # [R, Hkv, G, Dh], [R]
        qo, ko, precision = operands(qb, k, mode)
        s = jnp.einsum("qhgd,khd->hgqk", qo, ko, precision=precision,
                       preferred_element_type=jnp.float32) / math.sqrt(dh)
        seen = key_ids <= row_ids[:, None]
        w = jax.nn.softmax(jnp.where(seen[None, None], s, -1e30), axis=-1)
        wo, vo, precision = operands(w, v, mode)
        o = jnp.einsum("hgqk,khd->qhgd", wo, vo, precision=precision,
                       preferred_element_type=jnp.float32)
        return o.reshape(-1, hq * dh)

    o = _in_row_blocks(block, q, jnp.arange(t), rows=ROWS)
    return _mm(stored(o, mode), p["f_Wo"], mode)


def _relu2(x, up, down, mode):
    h = _mm(x, up, mode)
    return _mm(stored(jnp.square(jnp.where(h > 0, h, 0.0)), mode), down,
               mode)


def route(p, b, cfg):
    """(experts [T, k], weights [T, k]) of an expert layer, in float32
    from whatever `b` is."""
    s = jax.nn.sigmoid(jnp.dot(b, p["f_router"],
                               precision=jax.lax.Precision.HIGHEST))
    # `top_k` puts the lower index first among equal values
    _, sel = jax.lax.top_k(jax.lax.stop_gradient(s + p["f_bias"]),
                           cfg["num_experts_per_tok"])
    wt = jnp.take_along_axis(s, sel, axis=-1)
    if cfg["norm_topk_prob"]:
        wt = wt / (jnp.sum(wt, axis=-1, keepdims=True) + 1e-20)
    return sel, wt * cfg["routed_scaling_factor"]


def experts(p, b, cfg, mode):
    """The LatentMoE layer over one sequence's normed rows b [T, d]."""
    sel, wt = route(p, b, cfg)
    shared = _in_row_blocks(
        lambda rows: _relu2(rows, p["f_shared_w1"], p["f_shared_w2"], mode),
        b, rows=WIDE_ROWS)
    u = stored(_mm(b, p["f_latent_down"], mode), mode)
    first, count = experts_held(cfg)
    # a token's weight for each expert held: zero for most
    weights = jnp.stack([jnp.sum(jnp.where(sel == first + e, wt, 0.0),
                                 axis=-1) for e in range(count)])

    @jax.checkpoint
    def one(u, up, down, weight):
        return weight[:, None] * _relu2(u, up, down, mode)

    m, _ = jax.lax.scan(
        lambda m, expert: (m + one(u, *expert), None), jnp.zeros_like(u),
        (p["f_w1"], p["f_w2"], weights))
    return shared + _mm(stored(m, mode), p["f_latent_up"], mode)


KINDS = {"M": mamba, "*": attention, "E": experts}


def sublayer(p, a, cfg, letter, mode):
    """One layer over one sequence, a [T, d], under a `jax.checkpoint` of
    its own: `a + f(norm(a))`."""
    @jax.checkpoint
    def run(p, a):
        b = stored(_norm(a, p["ln_g"], cfg["layer_norm_epsilon"]), mode)
        return stored(a + KINDS[letter](p, b, cfg, mode), mode)

    return run(p, a)


def hidden_states(params, x, cfg, mode="float32", upto=None):
    """h [B, T, d] after `upto` layers (all of them by default), before
    the last norm."""
    blocks, _ = _names(cfg)
    h = stored(jnp.take(params[EMBED]["W"], x, axis=0), mode)
    for name, letter in list(zip(blocks,
                                 cfg["hybrid_override_pattern"]))[:upto]:
        h = jax.vmap(lambda seq, p=params[name], letter=letter: sublayer(
            p, seq, cfg, letter, mode))(h)
    return h


def _token_losses(h, gain, head, targets, cfg, mode):
    """Per-token cross-entropy of `norm(h; gain) head` against `targets`:
    h [R, d], targets [R], a block of rows at a time."""
    def block(rows, targets):
        rows = stored(_norm(rows, gain, cfg["layer_norm_epsilon"]), mode)
        logp = jax.nn.log_softmax(_mm(rows, head, mode), axis=-1)
        return -jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0]

    return _in_row_blocks(block, h, targets, rows=WIDE_ROWS)


def mtp_states(params, h, y, cfg, mode="float32"):
    """g [B, T, d]: the prediction module's output before its last norm,
    from the trunk's h and the labels y."""
    _, head = _names(cfg)
    p, eps = params[head], cfg["layer_norm_epsilon"]
    e = jnp.take(params[EMBED]["W"], y, axis=0)
    both = jnp.concatenate([_norm(h, p["mtp_norm_h"], eps),
                            _norm(e, p["mtp_norm_e"], eps)], axis=-1)
    g = stored(_mm(stored(both, mode), p["mtp_eh_proj"], mode), mode)
    for i, letter in enumerate(mtp_pattern(cfg)):
        prefix = f"mtp_layer{i}_"
        leaves = {k[len(prefix):]: v for k, v in p.items()
                  if k.startswith(prefix)}
        g = jax.vmap(lambda seq, leaves=leaves, letter=letter: sublayer(
            leaves, seq, cfg, letter, mode))(g)
    return g


def loss_terms(params, x, y, mode="float32"):
    """(main, mtp): the next-token cross-entropy, mean over all positions,
    and the module's, mean over the positions that have a target two
    ahead (0.0 without a module). x, y: [B, T] int32."""
    cfg = _CONFIG
    _, head = _names(cfg)
    p, d = params[head], cfg["hidden_size"]
    h = hidden_states(params, x, cfg, mode)
    main = jnp.mean(_token_losses(h.reshape(-1, d), p["norm_f"], p["W"],
                                  y.reshape(-1), cfg, mode))
    if not mtp_pattern(cfg):
        return main, jnp.zeros((), jnp.float32)
    g = mtp_states(params, h, y, cfg, mode)
    # position t scores y_{t+1}; the last has none. All T rows are scored
    # (whole blocks) and the last left out of the mean
    ahead = jnp.concatenate([y[:, 1:], jnp.zeros_like(y[:, :1])], axis=1)
    ce = _token_losses(g.reshape(-1, d), p["mtp_norm"], p["W"],
                       ahead.reshape(-1), cfg, mode).reshape(y.shape)
    return main, jnp.mean(ce[:, :-1])


def loss_fn(params, x, y, mode="float32"):
    """`main + mtp_loss_scaling_factor x mtp` of one batch."""
    main, mtp = loss_terms(params, x, y, mode)
    return main + _CONFIG.get("mtp_loss_scaling_factor", 0.1) * mtp
