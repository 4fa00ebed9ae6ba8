"""Autoregressive text generation via stateful stepping.

Reference parity: the DL4J text-generation flow samples one token at a
time through `rnnTimeStep` (`zoo/model/TextGenerationLSTM.java` trains
the model; the sampling loop lives in the GravesLSTM character-modelling
example pattern built on `MultiLayerNetwork.rnnTimeStep`). This helper
drives the same contract on this framework's networks and works for
both statefulness mechanisms: LSTM h/c carries and transformer KV
caches (`decode_carry` seeding in `MultiLayerNetwork.rnn_time_step`) —
so a prompt is consumed once and each new token costs one step, not a
full-prefix re-run.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from deeplearning4j_tpu.utils.sampling import (
    SamplingParams, sample_next, sample_token, truncate_probs,
)


def _resolve_net(net):
    """(first_layer, vocab) for a MultiLayerNetwork or a single-input /
    single-output ComputationGraph (the two shapes `rnn_time_step` can
    drive one autoregressive stream through)."""
    if hasattr(net, "layers"):            # MultiLayerNetwork
        return net.layers[0], net.layers[-1].n_out
    conf = getattr(net, "conf", None)
    if conf is None or not hasattr(conf, "network_inputs"):
        raise TypeError(
            f"generate() needs a MultiLayerNetwork or ComputationGraph, "
            f"got {type(net).__name__}")
    if len(conf.network_inputs) != 1 or len(conf.network_outputs) != 1:
        raise ValueError(
            "generate() drives one autoregressive stream: the graph must "
            "have exactly one network input and one output (got "
            f"{list(conf.network_inputs)} -> "
            f"{list(conf.network_outputs)}); drive multi-IO graphs "
            "through rnn_time_step directly")
    # first layer = first layer-bearing vertex downstream of the input
    frontier = {conf.network_inputs[0]}
    first = None
    for name in conf.topological_order:
        if frontier & set(conf.vertex_inputs.get(name, ())):
            lyr = getattr(conf.vertices[name], "layer", None)
            if lyr is not None:
                first = lyr
                break
            frontier.add(name)           # pass-through vertex: keep walking
    if first is None:
        raise ValueError("no layer vertex found downstream of the "
                         "network input")
    out_v = conf.vertices[conf.network_outputs[0]]
    vocab = getattr(getattr(out_v, "layer", None) or out_v, "n_out", None)
    if vocab is None:
        raise ValueError(
            f"output vertex {conf.network_outputs[0]!r} has no n_out; "
            "generate() needs a per-timestep classification head")
    return first, vocab


def _input_encoding(first_layer) -> str:
    """'ids' for embedding-fronted stacks ([B, T, 1] token ids), 'onehot'
    for vocab-width inputs ([B, T, V])."""
    from deeplearning4j_tpu.nn.layers.feedforward import (
        EmbeddingSequenceLayer,
    )

    return ("ids" if isinstance(first_layer, EmbeddingSequenceLayer)
            else "onehot")


def _encode(ids: np.ndarray, encoding: str, vocab: int) -> np.ndarray:
    """ids: [B, T] -> model input [B, T, 1] (integers, as the embedding
    looks them up) or one-hot [B, T, V]. An id outside the vocabulary is
    refused, not folded back into it."""
    if ids.size and (ids.min() < 0 or ids.max() >= vocab):
        raise ValueError(
            f"token ids must lie in [0, {vocab}); got {int(ids.min())} to "
            f"{int(ids.max())}")
    if encoding == "ids":
        return ids[..., None].astype(np.int32)
    return np.eye(vocab, dtype=np.float32)[ids]


# Truncation moved to utils/sampling.py so served decode shares the one
# tested implementation; the old private name stays importable.
_truncate = truncate_probs


def _prefill(net, prompt_ids, encoding, vocab, chunk: Optional[int]):
    """Feed the prompt through the stateful stepper, optionally in
    fixed-size chunks (bounds prefill memory; REQUIRED when a
    rolling-cache layer's ring cannot hold the whole prompt in one
    step). Returns the last chunk's output."""
    if chunk is None or prompt_ids.shape[1] <= chunk:
        return np.asarray(net.rnn_time_step(
            _encode(prompt_ids, encoding, vocab)))
    if chunk < 1:
        raise ValueError(f"prefill_chunk must be >= 1, got {chunk}")
    out = None
    for s in range(0, prompt_ids.shape[1], chunk):
        out = np.asarray(net.rnn_time_step(
            _encode(prompt_ids[:, s:s + chunk], encoding, vocab)))
    return out


def generate(net, prompt_ids, n_tokens: int, *, temperature: float = 1.0,
             greedy: bool = False, top_k: Optional[int] = None,
             top_p: Optional[float] = None,
             repetition_penalty: float = 1.0,
             prefill_chunk: Optional[int] = None,
             rng: Optional[np.random.Generator] = None) -> np.ndarray:
    """Sample `n_tokens` continuations of `prompt_ids` ([B, Tp] ints).

    The network's output layer must produce per-timestep class
    probabilities (softmax). Decoding controls compose in the standard
    order: `repetition_penalty` > 1 suppresses tokens already in the
    prompt or generated so far (probability-space CTRL variant: seen
    tokens' probabilities are raised to that power before
    renormalization), then `temperature` rescales (p^(1/τ)), then
    `top_k` keeps the k most probable tokens, then `top_p` keeps the
    smallest nucleus reaching that cumulative mass; `greedy` takes the
    argmax (after the repetition penalty; the truncation knobs are
    moot). `prefill_chunk` feeds the prompt in chunks of that many
    tokens (bounds prefill memory; lets a rolling-cache net consume
    prompts longer than its ring allows in one step). Returns the
    sampled ids, [B, n_tokens]."""
    prompt_ids = np.asarray(prompt_ids)
    if prompt_ids.ndim == 1:
        prompt_ids = prompt_ids[None, :]
    if top_k is not None and top_k < 1:
        raise ValueError(f"top_k must be >= 1, got {top_k}")
    if top_p is not None and not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")
    if repetition_penalty < 1.0:
        raise ValueError(
            f"repetition_penalty must be >= 1, got {repetition_penalty}")
    B = prompt_ids.shape[0]
    first_layer, vocab = _resolve_net(net)
    encoding = _input_encoding(first_layer)
    if rng is None:
        rng = np.random.default_rng(0)
    params = SamplingParams(temperature=temperature, top_k=top_k,
                            top_p=top_p, greedy=greedy)

    penalize = repetition_penalty != 1.0
    net.rnn_clear_previous_state()
    out = _prefill(net, prompt_ids, encoding, vocab, prefill_chunk)
    if penalize:    # the prefill has refused any id past the vocabulary
        seen = np.zeros((B, vocab), dtype=bool)
        np.put_along_axis(seen, prompt_ids.astype(np.int64), True, axis=-1)
    generated = np.empty((B, n_tokens), dtype=np.int64)
    for i in range(n_tokens):
        p = out[:, -1, :].astype(np.float64)
        if penalize:
            # floor AFTER the power too: a huge penalty on a small vocab
            # can underflow every seen prob to exactly 0, and once all
            # tokens are seen the renormalization would divide by zero
            p = np.where(seen,
                         np.maximum(np.power(np.maximum(p, 1e-30),
                                             repetition_penalty), 1e-300),
                         p)
            p = p / p.sum(axis=-1, keepdims=True)
        if params.greedy:
            # the one shared implementation (utils/sampling.sample_token)
            # also backs the served fused decode window; greedy here is
            # bit-identical to the numpy path by contract
            tok = np.asarray(sample_token(p, params, None)).astype(np.int64)
        else:
            tok = sample_next(p, params, rng)
        generated[:, i] = tok
        if penalize:
            seen[np.arange(B), tok] = True
        if i + 1 < n_tokens:
            out = np.asarray(net.rnn_time_step(
                _encode(tok[:, None], encoding, vocab)))
    return generated


def beam_search(net, prompt_ids, n_tokens: int, *, beam_width: int = 4,
                length_penalty: float = 0.6,
                eos_id: Optional[int] = None,
                prefill_chunk: Optional[int] = None) -> np.ndarray:
    """Beam-search decoding over the same stateful stepping as
    `generate`. The prompt is prefilled ONCE per batch row; the KV
    caches are then tiled to the beams (`net.rnn_reorder_state`) and
    gathered to each beam's chosen parent on reselection, so no prefix
    is ever recomputed.

    Scores are sum-of-log-probs normalized by the GNMT length penalty
    ((5+len)/6)^alpha with alpha=`length_penalty` (0 disables). With
    `eos_id`, finished beams stop growing (further steps append eos at
    no cost) and the best-scoring finished-or-final beam wins. Returns
    [B, n_tokens] ids (the best beam per batch row, padded with eos
    after finish)."""
    prompt_ids = np.asarray(prompt_ids)
    if prompt_ids.ndim == 1:
        prompt_ids = prompt_ids[None, :]
    if beam_width < 1:
        raise ValueError(f"beam_width must be >= 1, got {beam_width}")
    B = prompt_ids.shape[0]
    W = beam_width
    if n_tokens < 1:
        return np.zeros((B, 0), dtype=np.int64)
    first_layer, vocab = _resolve_net(net)
    encoding = _input_encoding(first_layer)

    net.rnn_clear_previous_state()
    # prefill once per row, then tile the carries to the W beams
    out = _prefill(net, prompt_ids, encoding, vocab, prefill_chunk)
    net.rnn_reorder_state(np.repeat(np.arange(B), W))
    # every beam of a row starts from the same distribution: [B, 1, V]
    # broadcasts against the [B, W] scores
    logp_next = np.log(np.maximum(out[:, -1, :], 1e-30))[:, None, :]

    scores = np.full((B, W), -np.inf)
    scores[:, 0] = 0.0        # identical beams: expand only beam 0 first
    tokens = np.zeros((B, W, n_tokens), dtype=np.int64)
    done = np.zeros((B, W), dtype=bool)
    identity = np.arange(B * W)

    def _norm(s, length):
        if not length_penalty:
            return s
        return s / (((5.0 + length) / 6.0) ** length_penalty)

    for t in range(n_tokens):
        cand = scores[:, :, None] + logp_next            # [B, W, V]
        if eos_id is not None:
            # finished beams extend ONLY with eos, at no cost
            frozen = np.full((vocab,), -np.inf)
            frozen[eos_id] = 0.0
            cand = np.where(done[:, :, None],
                            scores[:, :, None] + frozen[None, None], cand)
        flat = np.broadcast_to(cand, (B, W, vocab)).reshape(B, W * vocab)
        top = np.argsort(-flat, axis=-1, kind="stable")[:, :W]
        parent = top // vocab                            # [B, W]
        tok = top % vocab
        scores = np.take_along_axis(flat, top, axis=-1)
        tokens = np.take_along_axis(
            tokens, parent[:, :, None], axis=1)
        tokens[:, :, t] = tok
        done = np.take_along_axis(done, parent, axis=1)
        if eos_id is not None:
            done = done | (tok == eos_id)
        # reorder the KV caches to the chosen parents (skip the common
        # identity case — a full cache gather per token is pure HBM
        # waste when every beam kept its own parent), then step
        flat_idx = (np.arange(B)[:, None] * W + parent).reshape(-1)
        if not np.array_equal(flat_idx, identity):
            net.rnn_reorder_state(flat_idx)
        if t + 1 < n_tokens and not done.all():
            out = np.asarray(net.rnn_time_step(
                _encode(tok.reshape(-1, 1), encoding, vocab)))
            logp_next = np.log(np.maximum(out[:, -1, :], 1e-30)).reshape(
                B, W, vocab)
    if eos_id is not None:
        finished = (tokens == eos_id).any(-1)
        lengths = np.where(finished,
                           np.argmax(tokens == eos_id, axis=-1) + 1,
                           n_tokens)
    else:
        lengths = np.full((B, W), n_tokens)
    best = np.argmax(_norm(scores, lengths), axis=-1)    # [B]
    return tokens[np.arange(B), best]
