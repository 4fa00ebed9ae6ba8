"""Plain reference: one chip's share of a `deepseek_v2` language model
(DeepSeek-V2), written out from the published configuration's keys.

With `norm(x; g) = x / sqrt(mean(x^2) + rms_norm_eps) * g` (no bias
anywhere), every layer is pre-norm:

    h  = x + MLA(norm(x; g1))
    x' = h + F(norm(h; g2))      F = a dense SwiGLU of `intermediate_size`
                                 in the first `first_k_dense_replace`
                                 layers, the expert layer after them

MLA (multi-head latent attention) over the H heads HELD HERE
(`heads_held`, of `num_attention_heads`), `a` the normed input [T, d],
Dn / Dr / Dv = `qk_nope_head_dim` / `qk_rope_head_dim` / `v_head_dim`:

    c_q           = norm(a Wqa; gq)                   [T, q_lora_rank]
    [q_nope|q_pe] = c_q Wqb, a head at a time         [T, H, Dn | Dr]
    [c_kv | k_pe] = a Wkva                            [T, kv_lora_rank | Dr]
    c_kv          = norm(c_kv; gkv)      (the rope key is NOT normed)
    [k_nope | v]  = c_kv Wkvb, a head at a time       [T, H, Dn | Dv]
    q_pe, k_pe    = rope(q_pe), rope(k_pe)            (k_pe one for all heads)
    s_h[i, j]     = (q_nope_h[i] . k_nope_h[j] + q_pe_h[i] . k_pe[j]) scale,
                    j <= i
    MLA           = concat_h(softmax_j(s_h) v_h) Wo   [H Dv] -> d

YaRN (`rope_scaling`, over the Dr / 2 pairs): `f_i = theta^(-2i/Dr)`;
`low = floor(pair(beta_fast))`, `high = ceil(pair(beta_slow))` with
`pair(n) = Dr ln(L / (2 pi n)) / (2 ln theta)`, L the original length;
`ramp_i = clip((i - low) / (high - low), 0, 1)`; `inv_freq_i = f_i (1 -
ramp_i) + f_i / factor * ramp_i`; cos and sin times `m(mscale) /
m(mscale_all_dim)`, `scale = (Dn + Dr)^-0.5 m(mscale_all_dim)^2`, with
`m(c) = 0.1 c ln(factor) + 1`. As published: low 10, high 23, the factor on
cos and sin 1, scale 0.114721. ASSUMED: a head's pairs are its halves
(x1, x2); the published code lays interleaved pairs out as halves first,
which on seeded weights is a fixed permutation of Dr columns of Wqb and
Wkva.

Expert layer over `b = norm(h; g2)` [T, d] (`topk_method`
"group_limited_greedy", `scoring_func` "softmax"):

    p     = softmax(b Wr) over all `n_routed_experts`, float32
    G_g   = the largest p among group g's experts (expert e is in group
            e // (n_routed_experts / n_group))
    keep  = the `topk_group` groups of largest G (of equal ones the lower)
    p~    = p where the expert's group is kept, else 0
    E, w  = the `num_experts_per_tok` largest of p~ (of equal ones the
            lower expert) and their p, NOT normalised (`norm_topk_prob`
            false)
    F(b)  = swiglu(b; shared, `n_shared_experts` x `moe_intermediate_size`
            wide) + `routed_scaling_factor` x the sum over (e, w) of (E, w)
            with e HELD HERE (`experts_held`) of w swiglu_e(b)

The choice carries no gradient, the weights do. LEFT OUT, not guessed: the
balance losses (`seq_aux` and the device- and communication-level ones,
whose coefficients the configuration does not give) and the paper's
capacity-based token dropping. What the absent experts and the absent
heads would add to a token is left out, as the program leaves it out: the
cell is one rank of the deployment the configuration's file describes,
without its exchange and without Wo's all-reduce.

    loss = mean next-token cross-entropy of norm(h; gf) W_head over the
           rows of the vocabulary held here

Straightforward `jax.numpy` in float32 at matmul precision "highest";
imports nothing of the program; makes its own weights from the seed under
the program's leaf names. Attention is a dense masked softmax over both
score terms, a block of query rows against all keys at a time; each held
expert is applied to EVERY token and weighted by that token's weight for
it, zero for most: no kernel, no sorting, no grouped product. So that the
float32 parameters and their gradient (8 bytes a parameter) leave the
activations room on one chip, each half of every layer is under
`jax.checkpoint` and attention, the SwiGLUs and the head's loss go a block
of rows at a time, which changes no arithmetic. Modes as in `resnet50.py`.
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
    if "class LatentSparseTransformer" not in _fh.read():
        raise SystemExit("deepseek_v2: this checkout's program has no "
                         "zoo.LatentSparseTransformer to build it with")

EMBED = "layer0_embeddingsequencelayer"
ROWS = 128          # query rows of attention at a time
WIDE_ROWS = 1024    # rows of a SwiGLU and of the head at a time
# `follow.py` hands `loss_fn` no configuration: `init_params`, which every
# caller calls first, leaves it here
_CONFIG = {}


def _names(cfg):
    n = cfg["num_hidden_layers"]
    return ([f"layer{i}_prenormblock" for i in range(1, n + 1)],
            f"layer{n + 1}_rmsnormalization", f"layer{n + 2}_rnnoutputlayer")


def heads_held(cfg):
    return tuple(cfg.get("heads_held", (0, cfg["num_attention_heads"])))


def experts_held(cfg):
    return tuple(cfg.get("experts_held", (0, cfg["n_routed_experts"])))


def yarn(cfg):
    """(inv_freq [Dr / 2], the factor on cos and sin, the softmax scale)
    of the configuration's rope."""
    dr, theta = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    scale = (cfg["qk_nope_head_dim"] + dr) ** -0.5
    plain = [theta ** (-2.0 * i / dr) for i in range(dr // 2)]
    sc = cfg.get("rope_scaling")
    if sc is None:
        return plain, 1.0, scale
    if sc["type"] != "yarn":
        raise NotImplementedError(f"rope_scaling of type {sc['type']!r}")
    factor, span = sc["factor"], sc["original_max_position_embeddings"]
    pair = lambda turns: (dr * math.log(span / (2 * math.pi * turns))
                          / (2 * math.log(theta)))
    low = max(math.floor(pair(sc["beta_fast"])), 0)
    high = min(math.ceil(pair(sc["beta_slow"])), dr - 1)
    # (the published code too keeps the ramp's ends apart)
    ramp = [min(max((i - low) / max(high - low, 1e-3), 0.0), 1.0)
            for i in range(dr // 2)]
    m = lambda c: 0.1 * c * math.log(factor) + 1.0 if factor > 1 else 1.0
    every = m(sc.get("mscale_all_dim", 0))
    return ([f * (1 - r) + f / factor * r for f, r in zip(plain, ramp)],
            m(sc.get("mscale", 1)) / every, scale * every * every)


def forward_macs(cfg) -> int:
    """Multiply-accumulates of one sequence's forward pass, from the
    shapes, over the heads, experts and rows held. The core's two products
    are counted over the causal pairs (192 + 128 a pair and head), and the
    routed experts at their EXPECTATION under uniform routing: `k x held /
    n_routed_experts` = 0.3 experts a token, not what one step's router
    sends here."""
    t, d = cfg["input_shape"][0], cfg["hidden_size"]
    h = heads_held(cfg)[1]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    ql, kl = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    f, e = cfg["moe_intermediate_size"], cfg["n_routed_experts"]
    mla = t * (d * ql + ql * h * (dn + dr) + d * (kl + dr)
               + kl * h * (dn + dv) + h * dv * d)
    mla += h * (dn + dr + dv) * (t * (t + 1) // 2)
    macs = 0
    for i in range(cfg["num_hidden_layers"]):
        macs += mla
        if i < cfg["first_k_dense_replace"]:
            macs += t * 3 * d * cfg["intermediate_size"]
        else:
            routed = cfg["num_experts_per_tok"] * experts_held(cfg)[1] / e
            macs += t * d * e
            macs += int(t * 3 * d * f * (cfg["n_shared_experts"] + routed))
    return macs + t * d * cfg["vocabulary_held"]


def layer_shapes(cfg, index: int) -> dict:
    d, h = cfg["hidden_size"], heads_held(cfg)[1]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    ql, kl = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    leaves = {"ln1_g": (d,), "ln2_g": (d,),
              "mixer_Wqa": (d, ql), "mixer_q_norm": (ql,),
              "mixer_Wqb": (ql, h * (dn + dr)),
              "mixer_Wkva": (d, kl + dr), "mixer_kv_norm": (kl,),
              "mixer_Wkvb": (kl, h * (dn + dv)), "mixer_Wo": (h * dv, d)}
    if index < cfg["first_k_dense_replace"]:
        w = cfg["intermediate_size"]
        leaves.update(ffn_w1=(d, w), ffn_w3=(d, w), ffn_w2=(w, d))
    else:
        f, held = cfg["moe_intermediate_size"], experts_held(cfg)[1]
        fs = f * cfg["n_shared_experts"]
        leaves.update(moe_router=(d, cfg["n_routed_experts"]),
                      moe_w1=(held, d, f), moe_w3=(held, d, f),
                      moe_w2=(held, f, d), moe_shared_w1=(d, fs),
                      moe_shared_w3=(d, fs), moe_shared_w2=(fs, d))
    return leaves


def init_params(seed: int, cfg):
    """Kernels and router normal 0.02, embedding rows normal 1/sqrt(d),
    norm gains 1 + normal 0.02, all from the seed, one key a leaf."""
    _CONFIG.clear()
    _CONFIG.update(cfg)
    d, v = cfg["hidden_size"], cfg["vocabulary_held"]
    blocks, last_norm, head = _names(cfg)

    def leaf(key, name, shape):
        z = jax.random.normal(key, shape, jnp.float32)
        if name.endswith("_g") or name.endswith("_norm") or name == "gamma":
            return 1.0 + 0.02 * z
        return 0.02 * z

    @jax.jit
    def make(key):
        tree = {EMBED: {"W": (v, d)}, last_norm: {"gamma": (d,)},
                head: {"W": (d, v)},
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


def _rope(x, inv_freq, factor: float):
    """Rotary positions on [T, heads, Dr]: the halves (x1, x2) of a head
    turn by position x inv_freq, cos and sin times `factor`."""
    t, _, dr = x.shape
    ang = (jnp.arange(t, dtype=jnp.float32)[:, None]
           * jnp.asarray(inv_freq, jnp.float32))
    c = (jnp.cos(ang) * factor)[:, None, :]
    s = (jnp.sin(ang) * factor)[:, None, :]
    return x * jnp.concatenate([c, c], axis=-1) \
        + jnp.roll(x, dr // 2, axis=-1) * jnp.concatenate([-s, s], axis=-1)


def mla(p, a, cfg, mode):
    """Latent attention over one sequence, the heads held: a [T, d] ->
    [T, d]."""
    t = a.shape[0]
    h = heads_held(cfg)[1]
    dn, dv = cfg["qk_nope_head_dim"], cfg["v_head_dim"]
    rank, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    inv_freq, factor, scale = yarn(cfg)
    c_q = stored(_norm(_mm(a, p["mixer_Wqa"], mode), p["mixer_q_norm"], eps),
                 mode)
    q = _mm(c_q, p["mixer_Wqb"], mode).reshape(t, h, -1)
    kva = _mm(a, p["mixer_Wkva"], mode)
    c_kv = stored(_norm(kva[:, :rank], p["mixer_kv_norm"], eps), mode)
    kv = _mm(c_kv, p["mixer_Wkvb"], mode).reshape(t, h, dn + dv)
    k_nope, v = stored(kv[..., :dn], mode), stored(kv[..., dn:], mode)
    q_nope = stored(q[..., :dn], mode)
    q_pe = stored(_rope(q[..., dn:], inv_freq, factor), mode)
    k_pe = stored(_rope(kva[:, None, rank:], inv_freq, factor)[:, 0], mode)
    key_ids = jnp.arange(t)[None, :]

    def block(args):
        qn, qp, row_ids = args                  # [R, H, Dn], [R, H, Dr], [R]
        a1, b1, precision = operands(qn, k_nope, mode)
        a2, b2, _ = operands(qp, k_pe, mode)
        s = (jnp.einsum("qhd,khd->hqk", a1, b1, precision=precision,
                        preferred_element_type=jnp.float32)
             + jnp.einsum("qhd,kd->hqk", a2, b2, precision=precision,
                          preferred_element_type=jnp.float32)) * scale
        seen = key_ids <= row_ids[:, None]
        w = jax.nn.softmax(jnp.where(seen[None], s, -1e30), axis=-1)
        wo, vo, precision = operands(w, v, mode)
        o = jnp.einsum("hqk,khd->qhd", wo, vo, precision=precision,
                       preferred_element_type=jnp.float32)
        return o.reshape(-1, h * dv)

    o = _in_row_blocks(block, (q_nope, q_pe, jnp.arange(t)), ROWS)
    return _mm(stored(o, mode), p["mixer_Wo"], mode)


def _swiglu(x, w1, w3, w2, mode):
    return _mm(stored(jax.nn.silu(_mm(x, w1, mode)) * _mm(x, w3, mode),
                      mode), w2, mode)


def route(p, b, cfg):
    """(experts [T, k], weights [T, k]) of an expert layer, in float32
    from whatever `b` is."""
    prob = jax.nn.softmax(jnp.dot(b, p["moe_router"],
                                  precision=jax.lax.Precision.HIGHEST),
                          axis=-1)
    t, e = prob.shape
    groups = cfg["n_group"]
    best = jnp.max(prob.reshape(t, groups, e // groups), axis=-1)
    # `top_k` puts the lower index first among equal values
    _, kept = jax.lax.top_k(best, cfg["topk_group"])
    group_of = jnp.arange(e) // (e // groups)
    keep = jnp.any(group_of[None, :, None] == kept[:, None, :], axis=-1)
    _, sel = jax.lax.top_k(jnp.where(keep, prob, 0.0),
                           cfg["num_experts_per_tok"])
    sel = jax.lax.stop_gradient(sel)
    wt = jnp.take_along_axis(prob, sel, axis=-1)
    if cfg["norm_topk_prob"]:
        wt = wt / (jnp.sum(wt, axis=-1, keepdims=True) + 1e-20)
    return sel, wt * cfg["routed_scaling_factor"]


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
    eps = cfg["rms_norm_eps"]

    @jax.checkpoint
    def attention_half(p, h):
        a = stored(_norm(h, p["ln1_g"], eps), mode)
        return h + mla(p, a, cfg, mode)

    @jax.checkpoint
    def other_half(p, h):
        b = stored(_norm(h, p["ln2_g"], eps), mode)
        if index < cfg["first_k_dense_replace"]:
            m = _in_row_blocks(
                lambda args: _swiglu(args[0], p["ffn_w1"], p["ffn_w3"],
                                     p["ffn_w2"], mode), (b,), WIDE_ROWS)
        else:
            m = experts(p, b, cfg, mode)
        return stored(h + m, mode)

    return other_half(p, attention_half(p, h))


def hidden_states(params, x, cfg, mode="float32", upto=None):
    """h [B, T, d] after `upto` layers (all of them by default)."""
    blocks, _, _ = _names(cfg)
    h = stored(jnp.take(params[EMBED]["W"], x, axis=0), mode)
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

    return jnp.mean(_in_row_blocks(block, (h, y.reshape(-1)), WIDE_ROWS))
