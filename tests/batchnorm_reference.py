"""Batch norm's training pass as `jnp.mean` / `jnp.var` under plain
autodiff: what `BatchNormalization.apply` was before `batch_norm_train`.
Kept as the reference that `test_batchnorm_passes.py` and the pass count
in `test_tpu_compile.py` compare the one-pass form and its written-out
backward against."""

import jax.numpy as jnp


def two_pass_apply(layer, params, x, state):
    """`layer.apply(params, x, state=state, train=True)` in the two-pass
    form: (activations, new state)."""
    axes = tuple(range(x.ndim - 1))
    mean = jnp.mean(x, axis=axes)
    var = jnp.var(x, axis=axes)     # mean((x - mean)**2): a second pass
    y = (x - mean) * (1.0 / jnp.sqrt(var + layer.eps))
    if not layer.lock_gamma_beta:
        if layer.scale:
            y = y * params["gamma"]
        if layer.center:
            y = y + params["beta"]
    d = layer.decay
    return layer._act(y), {"mean": d * state["mean"] + (1 - d) * mean,
                           "var": d * state["var"] + (1 - d) * var}
