"""Update rule `adam`: Adam with bias correction, as the program's
`optim/updaters.Adam.apply` writes it (epsilon outside the root).

    m' = b1 m + (1 - b1) g        v' = b2 v + (1 - b2) g^2
    p' = p - lr sqrt(1 - b2^t) / (1 - b1^t) m' / (sqrt(v') + eps),  t = 1, 2, ..

The file's contract is `nesterov.py`'s: `init` and `update` are the plain
reference's arithmetic (its state carries the step count, which the
program keeps elsewhere), and `first_gradient` reads the first step's
gradient back out of the PROGRAM's optimizer state. Nothing here imports
the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def init(params):
    """The reference's state before the first step: zero moments, t = 0."""
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    return {"m": zeros, "v": zeros, "t": jnp.zeros((), jnp.float32)}


def update(params, state, grads, updater: dict):
    lr, b1, b2, eps = (updater[k] for k in (
        "learning_rate", "beta1", "beta2", "epsilon"))
    tm = jax.tree_util.tree_map
    t = state["t"] + 1.0
    m = tm(lambda m, g: b1 * m + (1 - b1) * g, state["m"], grads)
    v = tm(lambda v, g: b2 * v + (1 - b2) * g * g, state["v"], grads)
    scale = lr * jnp.sqrt(1.0 - b2 ** t) / (1.0 - b1 ** t)
    p_new = tm(lambda p, m, v: p - scale * m / (jnp.sqrt(v) + eps),
               params, m, v)
    return p_new, {"m": m, "v": v, "t": t}


def first_gradient(state, updater: dict):
    """The first step's gradient, as {vertex: {leaf: float32 array}}, from
    the program's optimizer state after that step: the first moment starts
    at zero, so g = m / (1 - beta1). The program keeps a vertex's first
    moment under the key "m"; a vertex without parameters has none."""
    return {name: jax.tree_util.tree_map(
        lambda m: m.astype(jnp.float32) / (1.0 - updater["beta1"]),
        s.get("m", {})) for name, s in state.items()}
