"""Plain reference: one pipeline stage of an `ouro` language model
(ByteDance Ouro-2.6B), a looped decoder written out from the published
configuration's keys. ONE stack of L sandwich-norm blocks is run
`total_ut_steps` = P times over the same weights, the last norm after
every pass; one head scores every pass and a learned gate weighs them.

    N(x; g) = x / sqrt(mean(x^2) + eps) * g          (eps = rms_norm_eps)
    s_0 = E[ids]
    pass t = 1..P:   u = s_{t-1}
      block l = 1..L:  a = N(u; ln1)
                       q, k, v = a Wq, a Wk, a Wv   in heads of head_dim
                       q, k rotated at positions 0..T-1 with
                         inv_freq_i = rope_theta^(-i / (head_dim / 2)),
                         the halves [:half] and [half:] of a head paired
                       o = softmax(q k^T / sqrt(head_dim) + causal) v
                       u = u + N(o Wo; ln2)
                       m = N(u; ln3)
                       u = u + N((silu(m W1) * (m W3)) W2; ln4)
      h_t = N(u; g);  s_t = h_t      (the NORMED state starts the next pass)
    gate:  lam_t = sigmoid(h_t . w_g + b_g) per token, t = 1..P-1
           p_t = lam_t prod_{s<t} (1 - lam_s),  p_P = prod_{s<P} (1 - lam_s)
           (the gate's value on the last pass is not used)
    loss:  ce_t[i] = -log softmax(h_t[i] W_h)[y_i]
           loss = mean_i (sum_t p_t[i] ce_t[i] - beta H(p[i])),
           H(p) = -sum_t p_t log p_t,  beta = exit_entropy_beta (0.1)

There is no bias but the gate's. Departures from the published model and
guesses are the configuration file's `assumed`: that the normed state
feeds the next pass, no projection biases, the objective and its beta, the
gate as one Linear(d, 1) read from h_t, the initial values, the updater,
the data. The leaves are the program's, one set a LAYER and not one a
pass: a shared leaf's gradient is the sum over the passes by
differentiation.

Straightforward `jax.numpy` in float32 at matmul precision "highest";
imports nothing of the program; Python loops over passes and layers, no
scan. So that the float32 parameters and their gradient (8 bytes a
parameter) leave the activations room on one chip, every block
APPLICATION is under `jax.checkpoint` with each half under one more inside
it (the backward pass keeps every application's input and holds one
half's activations), and attention, the SwiGLU and each exit's loss go a
block of rows at a time, which changes no arithmetic. The SwiGLU's and the
exits' blocks are a Python loop too: as `lax.map`s (308 more `while`
loops, forward and backward) the compiler laid the same live bytes out in
a heap 1.7 times their size, and the child did not fit at 8 blocks
(PERF.md, section 4). Attention's 64 blocks of query rows stay a
`lax.map`. Modes as in `resnet50.py`; a kernel that many row blocks read
is rounded once for all of them (`_mm(..., rounded=True)`).
"""

from __future__ import annotations

import math
import os

import jax
import jax.numpy as jnp

from benchmarks.reference.arithmetic import fp8, operands, stored

# The runner follows this reference, minutes of float32 steps, BEFORE it
# builds the program's net. A checkout whose program cannot build this
# configuration (an older one under these benchmark files) is told so here
# and now, from the program's source text: nothing of it is imported.
_ZOO = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "deeplearning4j_tpu", "zoo",
    "transformer.py")
with open(_ZOO, encoding="utf-8") as _fh:
    if "class LoopedSandwichTransformer" not in _fh.read():
        raise SystemExit("ouro_2_6b: this checkout's program has no "
                         "zoo.LoopedSandwichTransformer to build it with")

EMBED = "layer0_embeddingsequencelayer"
LOOP = "layer1_loopedstack"
HEAD = "layer2_exitgatedoutputlayer"
ROWS = 128          # query rows of attention at a time
WIDE_ROWS = 2048    # rows of the SwiGLU at a time
EXIT_ROWS = 512     # rows of an exit's loss at a time
BETA = 0.1          # where the configuration gives no `exit_entropy_beta`
# `follow.py` calls `loss_fn(params, x, y, mode)` and hands it no
# configuration: `init_params`, which every caller calls first, leaves
# its configuration here.
_CONFIG = {}


def causal_pairs(t: int) -> int:
    """(query, key) pairs of a causal layer over `t` tokens."""
    return t * (t + 1) // 2


def forward_macs(cfg) -> int:
    """Multiply-accumulates of one sequence's forward pass, from the
    shapes, counting what RUNS: every block's products and attention's two
    products over the causal pairs once a PASS, the head once a pass (every
    exit is scored), the gate once a pass but the last. At the cell's size
    (8 blocks, 4 passes, 8,192 tokens) 13.469 + 4.399 + 3.299 T = 21.166 T."""
    t, d, f = (cfg["input_shape"][0], cfg["hidden_size"],
               cfg["intermediate_size"])
    h, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    passes, layers = cfg["total_ut_steps"], cfg["num_hidden_layers"]
    block = t * d * dh * (2 * h + 2 * hkv) + t * 3 * d * f
    attention = 2 * h * dh * causal_pairs(t)
    return (passes * (layers * (block + attention)
                      + t * d * cfg["vocab_size"])
            + (passes - 1) * t * d)


def _block_shapes(cfg) -> dict:
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    return {"ln1_g": (d,), "ln2_g": (d,), "ln3_g": (d,), "ln4_g": (d,),
            "attn_Wq": (d, h * dh), "attn_Wk": (d, hkv * dh),
            "attn_Wv": (d, hkv * dh), "attn_Wo": (h * dh, d),
            "ffn_w1": (d, f), "ffn_w3": (d, f), "ffn_w2": (f, d)}


def init_params(seed: int, cfg):
    """Kernels normal 0.02, embedding rows normal 1/sqrt(d), norm gains
    1 + normal 0.02, the gate's kernel normal 0.02 and its bias 0, all
    from the seed, one key a leaf. One set of leaves a layer, under the
    loop layer's name."""
    _CONFIG.clear()
    _CONFIG.update(cfg)
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    loop = {f"block{i}_{leaf}": shape
            for i in range(cfg["num_hidden_layers"])
            for leaf, shape in _block_shapes(cfg).items()}
    loop["norm_gamma"] = (d,)

    def leaf(key, name, shape):
        z = jax.random.normal(key, shape, jnp.float32)
        if name.endswith("_g") or name.endswith("gamma"):
            return 1.0 + 0.02 * z
        if name == "gate_b":
            return jnp.zeros(shape, jnp.float32)
        return 0.02 * z

    @jax.jit
    def make(key):
        tree = {EMBED: {"W": (v, d)}, LOOP: loop,
                HEAD: {"W": (d, v), "gate_W": (d,), "gate_b": (1,)}}
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
def _operand(a, mode):
    """One operand of a product as `arithmetic.operands` rounds it."""
    if mode == "float32":
        return a
    if mode == "float8":
        a = fp8(a)
    return a.astype(jnp.bfloat16).astype(jnp.float32)


def _mm(a, b, mode, rounded=False):
    """a b in `mode`. With `rounded`, b is an `_operand` already: a weight
    that many row blocks read is rounded once for all of them, so that its
    cotangent is rounded once too, as a step's is, and the blocks' parts
    of it are summed as the float32 products they are."""
    precision = (jax.lax.Precision.HIGHEST if mode == "float32"
                 else jax.lax.Precision.DEFAULT)
    return jnp.dot(_operand(a, mode), b if rounded else _operand(b, mode),
                   precision=precision, preferred_element_type=jnp.float32)


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


def rope(x, theta: float):
    """Rotary positions 0..T-1 on [T, heads, Dh]: the halves (x1, x2) of a
    head turn by position x theta^(-i / half)."""
    t, _, dh = x.shape
    half = dh // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * freqs
    c, s = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    # (x1 c - x2 s, x2 c + x1 s), with (x2, x1) as a roll by half a head
    return x * jnp.concatenate([c, c], axis=-1) \
        + jnp.roll(x, half, axis=-1) * jnp.concatenate([-s, s], axis=-1)


def _attention(p, a, cfg, mode):
    """One sequence's attention half before its output norm: a [T, d]."""
    t = a.shape[0]
    h, hkv, dh = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    q = rope(_mm(a, p["attn_Wq"], mode).reshape(t, h, dh), cfg["rope_theta"])
    k = rope(_mm(a, p["attn_Wk"], mode).reshape(t, hkv, dh),
             cfg["rope_theta"])
    v = stored(_mm(a, p["attn_Wv"], mode), mode).reshape(t, hkv, dh)
    q, k = stored(q, mode), stored(k, mode)
    key_ids = jnp.arange(t)[None, :]

    def block(args):
        qb, row_ids = args                       # [R, H, Dh], [R]
        qg = qb.reshape(-1, hkv, h // hkv, dh)   # head h reads KV h // G
        qo, ko, precision = operands(qg, k, mode)
        s = jnp.einsum("qhgd,khd->hgqk", qo, ko, precision=precision,
                       preferred_element_type=jnp.float32) / math.sqrt(dh)
        seen = key_ids <= row_ids[:, None]
        w = jax.nn.softmax(jnp.where(seen[None, None], s, -1e30), axis=-1)
        wo, vo, precision = operands(w, v, mode)
        o = jnp.einsum("hgqk,khd->qhgd", wo, vo, precision=precision,
                       preferred_element_type=jnp.float32)
        return o.reshape(-1, h * dh)

    rows = min(ROWS, t)
    o = jax.lax.map(jax.checkpoint(block),
                    (q.reshape(t // rows, rows, h, dh),
                     jnp.arange(t).reshape(t // rows, rows)))
    return _mm(stored(o.reshape(t, h * dh), mode), p["attn_Wo"], mode)


def _swiglu(x, w1, w3, w2, mode):
    """Over a block of rows; the three kernels are `_operand`s already."""
    return _mm(stored(jax.nn.silu(_mm(x, w1, mode, True))
                      * _mm(x, w3, mode, True), mode), w2, mode, True)


def _block(p, u, cfg, mode):
    """One application of one block over one sequence: u [T, d]. The
    application is under a `jax.checkpoint` and each half under one of
    its own inside it, so the backward pass keeps one [T, d] an
    application and holds one half's activations at a time."""
    eps = cfg["rms_norm_eps"]

    @jax.checkpoint
    def attention_half(p, u):
        a = stored(_norm(u, p["ln1_g"], eps), mode)
        return u + _norm(_attention(p, a, cfg, mode), p["ln2_g"], eps)

    @jax.checkpoint
    def other_half(p, u):
        m = stored(_norm(u, p["ln3_g"], eps), mode)
        w1, w3, w2 = (_operand(p["ffn_" + w], mode)
                      for w in ("w1", "w3", "w2"))
        y = _in_row_blocks(lambda rows: _swiglu(rows, w1, w3, w2, mode), m,
                           rows=WIDE_ROWS)
        return stored(u + _norm(y, p["ln4_g"], eps), mode)

    return jax.checkpoint(
        lambda p, u: other_half(p, attention_half(p, u)))(p, u)


def pass_states(params, x, cfg, mode="float32", passes=None):
    """[h_1, ..., h_P], each [B, T, d]: the normed state after every pass
    (the first `passes` of them), from ids x [B, T]."""
    loop = params[LOOP]
    blocks = [{leaf: loop[f"block{i}_{leaf}"] for leaf in _block_shapes(cfg)}
              for i in range(cfg["num_hidden_layers"])]
    s = stored(jnp.take(params[EMBED]["W"], x, axis=0), mode)
    states = []
    for _ in range(passes or cfg["total_ut_steps"]):
        u = s
        for p in blocks:
            u = jax.vmap(lambda seq, p=p: _block(p, seq, cfg, mode))(u)
        s = stored(_norm(u, loop["norm_gamma"], cfg["rms_norm_eps"]), mode)
        states.append(s)
    return states


def exit_distribution(params, states):
    """p [P, B, T]: with what probability a token leaves after each pass,
    from the states of all passes. The gate reads every pass but the
    last."""
    w, b = params[HEAD]["gate_W"], params[HEAD]["gate_b"]
    stay = jnp.ones(states[0].shape[:-1], jnp.float32)
    out = []
    for h in states[:-1]:
        lam = jax.nn.sigmoid(jnp.sum(h * w, axis=-1) + b[0])
        out.append(stay * lam)
        stay = stay * (1.0 - lam)
    return jnp.stack(out + [stay])


def exit_losses(params, states, y, mode="float32"):
    """ce [P, B, T]: every exit's next-token cross-entropy a token."""
    targets = y.reshape(-1)
    out = []
    for h in states:
        head = _operand(params[HEAD]["W"], mode)    # once an exit

        def block(rows, targets, head=head):
            logp = jax.nn.log_softmax(_mm(rows, head, mode, True), axis=-1)
            return -jnp.take_along_axis(logp, targets[:, None],
                                        axis=-1)[:, 0]

        out.append(_in_row_blocks(
            block, h.reshape(targets.shape[0], -1), targets,
            rows=EXIT_ROWS).reshape(y.shape))
    return jnp.stack(out)


def entropy(p):
    """H(p) = -sum_t p_t log p_t over the passes, [B, T]."""
    return -jnp.sum(p * jnp.log(p), axis=0)


def loss_fn(params, x, y, mode="float32"):
    """The expectation of the exits' cross-entropies under the gate's exit
    distribution, less beta times its entropy, mean over the tokens of one
    batch. x, y: [B, T] int32."""
    cfg = _CONFIG
    states = pass_states(params, x, cfg, mode)
    p = exit_distribution(params, states)
    ce = exit_losses(params, states, y, mode)
    beta = cfg.get("exit_entropy_beta", BETA)
    return jnp.mean(jnp.sum(p * ce, axis=0) - beta * entropy(p))
