"""Plain reference: one pipeline stage's share of a `minicpm_sala` language
model (MiniCPM-SALA), written out from the published configuration's keys.

With d = hidden_size, r = scale_depth / sqrt(mup_denominator), and
`norm(x; g) = x / sqrt(mean(x^2) + rms_norm_eps) * g` (no bias anywhere):

    h = scale_emb * E[ids]
    every layer:  h = h + r mixer(norm(h; g1))
                  n = norm(h; g2);  h = h + r (silu(n W1) * (n W3)) W2
    logits = (norm(h; gf) / (d / dim_model_base)) W_head
    loss   = mean next-token cross-entropy over the rows of the vocabulary
             held here

"lightning-attn" mixer (`lightning_nh` heads of `lightning_head_dim`):
    q, k, v = x Wq, x Wk, x Wv;  q, k = norm(q; gq), norm(k; gk) over each
    head (`qk_norm`); rotary positions on q and k (`lightning_use_rope`)
    o_t = sum_{s<=t} lambda_h^(t-s) (q_t . k_s) v_s / sqrt(Dh)
          (the recurrence S_t = lambda_h S_{t-1} + k_t^T v_t, o_t = q_t S_t
          / sqrt(Dh): `lightning_scale` "1/sqrt(d)"; no softmax)
    out = (norm(o; go) * sigmoid(x Wg)) Wo, the norm over all heads' lanes
          together (`use_output_norm`, `use_output_gate`)
    ASSUMED (the config does not give them; Lightning Attention-2 /
    MiniMax-01's convention): lambda_h = exp(-s_h (1 - l/(L-1) + 1e-5)),
    s_h = 2^(-8h/H), h = 1..H, `l` the layer's index in the PUBLISHED
    model of L layers; no activation on q, k, v.

"minicpm4" mixer (`num_attention_heads` query heads over
`num_key_value_heads` KV heads of `head_dim`; InfLLM-V2):
    q, k, v, gate as above with `qk_norm`, NO positions (`attn_use_rope`
    false). For T <= dense_len plain causal softmax attention. Else, for
    query t and KV group g (the query heads that share a KV head):
    (1) kc_j = mean(k[stride j : stride j + kernel]) over the windows that
        end at or before t;
    (2) p_{h,j} = softmax_j(q_h . kc_j / sqrt(Dh)), summed over the group;
    (3) a block's score: the largest p of the windows that overlap it;
    (4) chosen: the first `init_blocks` blocks, the blocks that cover
        [t - window_size + 1, t], and of the rest the best-scored until
        `topk` blocks are held (all, where fewer exist; of equal scores
        the lower block first);
    (5) o_h = softmax over the chosen blocks' keys s <= t of
        (q_h . k_s / sqrt(Dh)), times v;   out = (o * sigmoid(x Wg)) Wo.
    (1) to (4) carry no gradient. ASSUMED (`sparse_config` in the file, as
    MiniCPM4 publishes it; the catalog's row has only "block top-64"):
    block 64, topk 64 with the forced blocks counted, 1 initial block,
    window 2048, kernel 32, stride 16, dense_len 8192; (3)'s pooling as
    written here.

Straightforward `jax.numpy` in float32 at matmul precision "highest";
imports nothing of the program; makes its own weights from the seed under
the program's leaf names. The linear layers go by the QUADRATIC form,
`((Q K^T) * D) V` with `D_ts = lambda^(t-s)`, a block of query rows against
all keys at a time: no chunks, no carried state, nothing of the program's
scan. The sparse layer is a dense masked softmax over a per-token key mask
built from (1) to (4) by sorting: no kernel, no top-k threshold. So that
the float32 parameters and their gradient (8 bytes a parameter) leave the
activations room on one chip, each half of every layer is under
`jax.checkpoint` and the mixers, the SwiGLU and the head's loss go a block
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
    if "class HybridLinearSparseTransformer" not in _fh.read():
        raise SystemExit("minicpm_sala: this checkout's program has no "
                         "zoo.HybridLinearSparseTransformer to build it "
                         "with")

EMBED = "layer0_embeddingsequencelayer"
ROWS = 128          # query rows of a mixer at a time
WIDE_ROWS = 1024    # rows of the SwiGLU and of the head at a time
SPARSE_CONFIG = {"block_size": 64, "topk": 64, "init_blocks": 1,
                 "window_size": 2048, "kernel_size": 32, "kernel_stride": 16,
                 "dense_len": 8192}
# `follow.py` hands `loss_fn` no configuration: `init_params`, which every
# caller calls first, leaves it here
_CONFIG = {}


def _names(cfg):
    n = cfg["num_hidden_layers"]
    return ([f"layer{i}_prenormblock" for i in range(1, n + 1)],
            f"layer{n + 1}_rmsnormalization", f"layer{n + 2}_rnnoutputlayer")


def sparse_sizes(cfg) -> dict:
    return {**SPARSE_CONFIG, **cfg.get("sparse_config", {})}


def published_layers(cfg) -> int:
    return cfg.get("published", {}).get("num_hidden_layers",
                                        cfg["num_hidden_layers"])


def kept_pairs(t: int, sizes: dict) -> int:
    """(query, key) pairs one query head reads over `t` tokens past
    `dense_len`: for each token the blocks kept, all `i // block + 1`
    reachable ones where those are no more than `topk`, else `topk` (the
    forced blocks never pass it at the published sizes), each whole but
    the token's own, of which its causal part."""
    bs = sizes["block_size"]
    return sum((min(i // bs + 1, sizes["topk"]) - 1) * bs + i % bs + 1
               for i in range(t))


def forward_macs(cfg) -> int:
    """Multiply-accumulates of one sequence's forward pass, counted as the
    mathematics needs them. A linear layer's mixer: the state's update
    and its read-out, `2 Dh^2` a head a token. A sparse layer's: its two
    products over the (query, key) pairs of the blocks kept, a whole block
    counted for each visit but the query's own, of which its causal part,
    plus the scores over the compressed keys (every window that ends at or
    before the query); dense causal pairs where `T <= dense_len`."""
    t, d = cfg["input_shape"][0], cfg["hidden_size"]
    h, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    lh, ldh = cfg["lightning_nh"], cfg["lightning_head_dim"]
    sizes = sparse_sizes(cfg)
    ks, kk = sizes["kernel_stride"], sizes["kernel_size"]
    macs = 0
    for kind in cfg["mixer_types"]:
        if kind == "lightning-attn":
            macs += t * d * ldh * (4 * lh + cfg["lightning_nkv"])  # q,g,o,k,v
            macs += 2 * t * lh * ldh * ldh
        else:
            macs += t * d * dh * (3 * h + 2 * hkv)
            if t <= sizes["dense_len"]:
                pairs = t * (t + 1) // 2
            else:
                pairs = kept_pairs(t, sizes)
                macs += h * dh * sum(max((i - kk + 1) // ks + 1, 0)
                                     for i in range(t))
            macs += 2 * h * dh * pairs
        macs += t * 3 * d * cfg["intermediate_size"]
    return macs + t * d * cfg["vocabulary_held"]


def init_params(seed: int, cfg):
    """Kernels normal 0.02, embedding rows normal 1/sqrt(d), norm gains
    1 + normal 0.02, all from the seed, one key a leaf."""
    _CONFIG.clear()
    _CONFIG.update(cfg)
    d, v, w = cfg["hidden_size"], cfg["vocabulary_held"], \
        cfg["intermediate_size"]
    h, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    lh, lkv, ldh = (cfg["lightning_nh"], cfg["lightning_nkv"],
                    cfg["lightning_head_dim"])
    blocks, last_norm, head = _names(cfg)

    def shapes(kind):
        leaves = {"ln1_g": (d,), "ln2_g": (d,), "ffn_w1": (d, w),
                  "ffn_w3": (d, w), "ffn_w2": (w, d)}
        if kind == "lightning-attn":
            leaves.update(mixer_Wq=(d, lh * ldh), mixer_Wk=(d, lkv * ldh),
                          mixer_Wv=(d, lkv * ldh), mixer_Wg=(d, lh * ldh),
                          mixer_Wo=(lh * ldh, d), mixer_q_norm=(ldh,),
                          mixer_k_norm=(ldh,), mixer_o_norm=(lh * ldh,))
        else:
            leaves.update(mixer_Wq=(d, h * dh), mixer_Wk=(d, hkv * dh),
                          mixer_Wv=(d, hkv * dh), mixer_Wg=(d, h * dh),
                          mixer_Wo=(h * dh, d), mixer_q_norm=(dh,),
                          mixer_k_norm=(dh,))
        return leaves

    def leaf(key, name, shape):
        z = jax.random.normal(key, shape, jnp.float32)
        if name.endswith("_g") or name.endswith("_norm") or name == "gamma":
            return 1.0 + 0.02 * z
        return 0.02 * z

    @jax.jit
    def make(key):
        tree = {EMBED: {"W": (v, d)}, last_norm: {"gamma": (d,)},
                head: {"W": (d, v)},
                **{name: shapes(kind)
                   for name, kind in zip(blocks, cfg["mixer_types"])}}
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


def _rope(x, theta: float):
    """Rotary positions on [T, heads, Dh]: the halves (x1, x2) of a head
    turn by position x theta^(-i / half)."""
    t, _, dh = x.shape
    half = dh // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    return x * jnp.concatenate([c, c], axis=-1) \
        + jnp.roll(x, half, axis=-1) * jnp.concatenate([-s, s], axis=-1)


def _qkv(p, a, heads, kv_heads, dh, eps, mode):
    t = a.shape[0]
    q = _mm(a, p["mixer_Wq"], mode).reshape(t, heads, dh)
    k = _mm(a, p["mixer_Wk"], mode).reshape(t, kv_heads, dh)
    v = stored(_mm(a, p["mixer_Wv"], mode), mode).reshape(t, kv_heads, dh)
    gate = jax.nn.sigmoid(_mm(a, p["mixer_Wg"], mode))
    return (_norm(q, p["mixer_q_norm"], eps),
            _norm(k, p["mixer_k_norm"], eps), v, gate)


def decay_rates(cfg, index: int):
    """-log lambda_h of the heads of the layer at `index` of this stage
    (the stage holds the published model's first layers)."""
    heads = cfg["lightning_nh"]
    h = jnp.arange(1, heads + 1, dtype=jnp.float32)
    factor = 1.0 - index / max(published_layers(cfg) - 1, 1) + 1e-5
    return 2.0 ** (-8.0 * h / heads) * factor


def _linear_mixer(p, a, cfg, index, mode):
    """The quadratic form, a block of query rows against every key."""
    t = a.shape[0]
    heads, dh = cfg["lightning_nh"], cfg["lightning_head_dim"]
    eps = cfg["rms_norm_eps"]
    q, k, v, gate = _qkv(p, a, heads, cfg["lightning_nkv"], dh, eps, mode)
    if cfg["lightning_use_rope"]:
        q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    q, k = stored(q, mode), stored(k, mode)
    rates = decay_rates(cfg, index)
    key_ids = jnp.arange(t)

    def block(args):
        qb, row_ids = args                          # [R, H, Dh], [R]
        qo, ko, precision = operands(qb, k, mode)
        s = jnp.einsum("qhd,khd->hqk", qo, ko, precision=precision,
                       preferred_element_type=jnp.float32)
        gap = (row_ids[:, None] - key_ids[None, :]).astype(jnp.float32)
        decay = jnp.where(gap >= 0, jnp.exp(
            -rates[:, None, None] * jnp.maximum(gap, 0.0)), 0.0)
        wo, vo, precision = operands(s * decay, v, mode)
        o = jnp.einsum("hqk,khd->qhd", wo, vo, precision=precision,
                       preferred_element_type=jnp.float32)
        return o.reshape(-1, heads * dh) / math.sqrt(dh)

    o = _in_row_blocks(block, (q, jnp.arange(t)), ROWS)
    if cfg["use_output_norm"]:
        o = _norm(o, p["mixer_o_norm"], eps)
    if cfg["use_output_gate"]:
        o = o * gate
    return _mm(stored(o, mode), p["mixer_Wo"], mode)


def chosen_blocks(q, k, row_ids, sizes, scale):
    """(1) to (4) for a block of query rows: [R, Hkv, NB] bool, the blocks
    each row reads for each KV group. q [R, H, Dh] (the rows'), k
    [T, Hkv, Dh] (all keys); float32 throughout, whatever the mode: the
    choice is the model's, not the arithmetic under test."""
    q, k = jax.lax.stop_gradient(q), jax.lax.stop_gradient(k)
    t, hkv, dh = k.shape
    g = q.shape[1] // hkv
    bs, ks, kk = (sizes["block_size"], sizes["kernel_stride"],
                  sizes["kernel_size"])
    nb = -(-t // bs)
    nw = (t - kk) // ks + 1
    hi = jax.lax.Precision.HIGHEST
    starts = jnp.arange(nw) * ks
    kc = jnp.mean(k[starts[:, None] + jnp.arange(kk)[None, :]], axis=1)  # (1)
    s = jnp.einsum("qhgd,whd->hgqw", q.reshape(-1, hkv, g, dh), kc,
                   precision=hi) * scale
    seen = (starts + kk - 1)[None, :] <= row_ids[:, None]    # [R, NW]
    w = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1)
    p = jnp.sum(jnp.where(seen, w, 0.0), axis=1)             # (2) [Hkv,R,NW]
    blocks = jnp.arange(nb)
    overlap = ((starts[None, :] + kk - 1 >= blocks[:, None] * bs)
               & (starts[None, :] <= blocks[:, None] * bs + bs - 1))
    score = jnp.max(jnp.where(overlap[None, None], p[:, :, None, :], 0.0),
                    axis=-1)                                 # (3) [Hkv,R,NB]
    own = row_ids[:, None] // bs
    reachable = blocks[None, :] <= own                       # [R, NB]
    first = jnp.maximum(row_ids[:, None] - sizes["window_size"] + 1, 0) // bs
    forced = reachable & ((blocks[None, :] < sizes["init_blocks"])
                          | (blocks[None, :] >= first))
    key = jnp.where(forced[None], jnp.inf,
                    jnp.where(reachable[None], score, -jnp.inf))
    # (4) a stable sort, best first: a block's place among its row's
    order = jnp.argsort(-key, axis=-1, stable=True)
    place = jnp.argsort(order, axis=-1, stable=True)
    return jnp.moveaxis((place < sizes["topk"]) & reachable[None], 0, 1)


def _softmax_mixer(p, a, cfg, mode):
    """The `minicpm4` mixer: dense causal up to `dense_len` tokens, past
    it over the chosen blocks' keys, by one masked softmax either way."""
    t = a.shape[0]
    h, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    sizes = sparse_sizes(cfg)
    q, k, v, gate = _qkv(p, a, h, hkv, dh, cfg["rms_norm_eps"], mode)
    if cfg["attn_use_rope"]:
        raise NotImplementedError("minicpm4 layers with positions")
    q, k = stored(q, mode), stored(k, mode)
    key_ids = jnp.arange(t)[None, :]
    scale = 1.0 / math.sqrt(dh)

    def block(args):
        qb, row_ids = args                          # [R, H, Dh], [R]
        seen = jnp.broadcast_to((key_ids <= row_ids[:, None])[:, None],
                                (qb.shape[0], hkv, t))
        if t > sizes["dense_len"]:
            chosen = chosen_blocks(qb, k, row_ids, sizes, scale)
            seen = seen & jnp.repeat(chosen, sizes["block_size"],
                                     axis=-1)[..., :t]
        seen = jnp.moveaxis(seen, 0, 1)[:, None]    # [Hkv, 1, R, T]
        qo, ko, precision = operands(qb.reshape(-1, hkv, h // hkv, dh), k,
                                     mode)
        s = jnp.einsum("qhgd,khd->hgqk", qo, ko, precision=precision,
                       preferred_element_type=jnp.float32) * scale
        w = jax.nn.softmax(jnp.where(seen, s, -1e30), axis=-1)
        wo, vo, precision = operands(w, v, mode)
        o = jnp.einsum("hgqk,khd->qhgd", wo, vo, precision=precision,
                       preferred_element_type=jnp.float32)
        return o.reshape(-1, h * dh)

    o = _in_row_blocks(block, (q, jnp.arange(t)), ROWS)
    if cfg["attn_use_output_gate"]:
        o = o * gate
    return _mm(stored(o, mode), p["mixer_Wo"], mode)


def _layer(p, h, cfg, index, mode):
    """One layer over one sequence: h [T, d]. Each half is under a
    `jax.checkpoint` of its own."""
    eps = cfg["rms_norm_eps"]
    r = cfg["scale_depth"] / math.sqrt(cfg["mup_denominator"])
    kind = cfg["mixer_types"][index]

    @jax.checkpoint
    def mixer_half(p, h):
        a = stored(_norm(h, p["ln1_g"], eps), mode)
        m = (_linear_mixer(p, a, cfg, index, mode)
             if kind == "lightning-attn" else _softmax_mixer(p, a, cfg, mode))
        return h + r * m

    @jax.checkpoint
    def other_half(p, h):
        n = stored(_norm(h, p["ln2_g"], eps), mode)
        m = _in_row_blocks(
            lambda args: _mm(stored(
                jax.nn.silu(_mm(args[0], p["ffn_w1"], mode))
                * _mm(args[0], p["ffn_w3"], mode), mode), p["ffn_w2"], mode),
            (n,), WIDE_ROWS)
        return stored(h + r * m, mode)

    return other_half(p, mixer_half(p, h))


def hidden_states(params, x, cfg, mode="float32", upto=None):
    """h [B, T, d] after `upto` layers (all of them by default)."""
    blocks, _, _ = _names(cfg)
    h = stored(jnp.take(params[EMBED]["W"], x, axis=0) * cfg["scale_emb"],
               mode)
    for i, name in enumerate(blocks[:upto]):
        h = jax.vmap(lambda seq, p=params[name], i=i: _layer(
            p, seq, cfg, i, mode))(h)
    return h


def loss_fn(params, x, y, mode="float32"):
    """Mean next-token cross-entropy of one batch. x, y: [B, T] int32."""
    cfg = _CONFIG
    _, last_norm, head = _names(cfg)
    d = cfg["hidden_size"]
    h = hidden_states(params, x, cfg, mode)
    h = _norm(h, params[last_norm]["gamma"], cfg["rms_norm_eps"]) \
        / (d / cfg["dim_model_base"])
    h = stored(h, mode).reshape(-1, d)

    def block(args):
        rows, targets = args
        logp = jax.nn.log_softmax(_mm(rows, params[head]["W"], mode),
                                  axis=-1)
        return -jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0]

    return jnp.mean(_in_row_blocks(block, (h, y.reshape(-1)), WIDE_ROWS))
