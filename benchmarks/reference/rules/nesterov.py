"""Update rule `nesterov`: ND4J's Nesterov momentum, written out.

    v' = mu v - lr g        p' = p + mu v' - lr g

A rule's file gives the plain reference its arithmetic (`init`, `update`)
and tells the harness how to read the first step's gradient back out of
the PROGRAM's optimizer state (`first_gradient`), which is all the harness
sees of it: `fit()` hands a listener the state, never the gradient. A
configuration names its rule in `updater.rule`, and the harness finds this
file by that name. Nothing here imports the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def init(params):
    """The reference's state before the first step: zero velocity."""
    return jax.tree_util.tree_map(jnp.zeros_like, params)


def update(params, velocity, grads, updater: dict):
    lr, mu = updater["learning_rate"], updater["momentum"]
    tm = jax.tree_util.tree_map
    v_new = tm(lambda v, g: mu * v - lr * g, velocity, grads)
    p_new = tm(lambda p, v, g: p + mu * v - lr * g, params, v_new, grads)
    return p_new, v_new


def first_gradient(state, updater: dict):
    """The first step's gradient, as {vertex: {leaf: float32 array}}, from
    the program's optimizer state after that step: the velocity starts at
    zero, so g = -v / lr. The program keeps a vertex's velocity under the
    key "v"; a vertex without parameters has none."""
    return {name: jax.tree_util.tree_map(
        lambda v: -v.astype(jnp.float32) / updater["learning_rate"],
        s.get("v", {})) for name, s in state.items()}
