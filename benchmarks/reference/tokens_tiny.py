"""Plain reference: the smallest token model. An embedding row per id,
`hidden_layers` tanh layers (1 where the configuration names none) at
every time step, a softmax over the vocabulary, and the mean cross-entropy
of the next token at every position.

Straightforward `jax.numpy` in float32 at matmul precision "highest".
Imports nothing of the program; makes its own weights from the seed under
the program's layer names (`layer<i>_<type>`). Modes as in `resnet50.py`.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmarks.reference.arithmetic import operands, stored

EMBED = "layer0_embeddingsequencelayer"


def _names(n: int):
    """(the `n` hidden layers' names, the output layer's name), as the
    program numbers them."""
    return ([f"layer{i}_convolution1dlayer" for i in range(1, n + 1)],
            f"layer{n + 1}_rnnoutputlayer")


def forward_macs(cfg) -> int:
    """Multiply-accumulates of one sequence's forward pass, from the
    shapes: the matrix products at every position (the embedding is a
    gather)."""
    width, vocab = cfg["width"], cfg["vocabulary_held"]
    return cfg["input_shape"][0] * (
        cfg.get("hidden_layers", 1) * width * width + width * vocab)


def init_params(seed: int, cfg):
    """Normal embedding rows of variance 1 / width, Xavier-normal kernels
    and zero biases, from the seed."""
    width, vocab = cfg["width"], cfg["vocabulary_held"]
    hidden, out = _names(cfg.get("hidden_layers", 1))

    @jax.jit
    def make(key):
        # keys 0, 1, 2 are the embedding's, the first hidden layer's and
        # the output layer's, as before there could be more hidden layers
        k = [jax.random.fold_in(key, i) for i in range(2 + len(hidden))]
        return {
            EMBED: {"W": jax.random.normal(k[0], (vocab, width), jnp.float32)
                    / math.sqrt(width)},
            **{name: {"W": math.sqrt(2.0 / (2 * width)) * jax.random.normal(
                k_i, (1, width, width), jnp.float32),
                "b": jnp.zeros((width,), jnp.float32)}
               for name, k_i in zip(hidden, [k[1]] + k[3:])},
            out: {"W": math.sqrt(2.0 / (width + vocab)) * jax.random.normal(
                k[2], (width, vocab), jnp.float32),
                "b": jnp.zeros((vocab,), jnp.float32)}}

    return make(jax.random.PRNGKey(seed % (2 ** 31)))


def _dense(h, layer, mode):
    a, w, precision = operands(
        h, layer["W"].reshape(layer["W"].shape[-2:]), mode)
    return jnp.dot(a, w, precision=precision,
                   preferred_element_type=jnp.float32) + layer["b"]


def loss_fn(params, x, y, mode="float32"):
    """Mean next-token cross-entropy of one batch. x, y: [B, T] int32."""
    hidden, out = _names(len(params) - 2)
    h = stored(jnp.take(params[EMBED]["W"], x, axis=0), mode)
    for name in hidden:
        h = stored(jnp.tanh(_dense(h, params[name], mode)), mode)
    logp = jax.nn.log_softmax(_dense(h, params[out], mode), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, y[..., None], axis=-1))
