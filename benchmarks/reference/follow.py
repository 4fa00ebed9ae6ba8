"""Follows the first training steps of a cell with a plain reference.

The reference module of a configuration gives `init_params(seed, cfg)` and
`loss_fn(params, x, y, mode)`, and `reference/rules/<rule>.py` the update
rule the configuration names; this file steps them and reads the numbers
the harness compares:

    loss[i]        the loss of step i, before its update
    grad_norm      per leaf, the norm of the first gradient
    delta_norm     per leaf, the norm of (parameters after the last step
                   - initial parameters)
    grad_sample    per leaf, a strided sample of the first gradient's
                   elements, for the one comparison that is of a difference

Nothing here imports the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def leaf_paths(tree) -> list:
    """'vertex/leaf' names in the order `jax.tree_util.tree_leaves` gives."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in flat]


def leaf_norms(tree):
    """One float32 vector: the L2 norm of every leaf."""
    return jnp.stack([
        jnp.sqrt(jnp.sum(jnp.square(leaf.astype(jnp.float32))))
        for leaf in jax.tree_util.tree_leaves(tree)])


SAMPLE = 16384


def leaf_samples(tree):
    """Every leaf's elements at a fixed stride, at most SAMPLE of them, as
    float32 vectors in `leaf_paths` order: enough of a gradient to compare
    it element by element, small enough to pass between processes."""
    out = []
    for leaf in jax.tree_util.tree_leaves(tree):
        flat = leaf.reshape(-1).astype(jnp.float32)
        out.append(flat[::max(1, flat.shape[0] // SAMPLE)][:SAMPLE])
    return out


def sample_weights(tree) -> dict:
    """Per leaf, how many elements each sampled one stands for."""
    out = {}
    for name, leaf in zip(leaf_paths(tree), jax.tree_util.tree_leaves(tree)):
        n = max(1, leaf.size)
        out[name] = n / min(SAMPLE, -(-n // max(1, n // SAMPLE)))
    return out


def loss_and_grad(loss_fn, params, x, y, mode, row_blocks: int = 1):
    """Loss and gradient of the whole batch, computed `row_blocks` equal
    blocks of rows at a time and averaged, so that the float32 reference
    fits beside nothing else on one chip. Exact only where no row's loss
    depends on another row (no batch statistics): the configuration says
    how many blocks its reference may take."""
    if row_blocks == 1:
        return jax.value_and_grad(loss_fn)(params, x, y, mode)
    xs = x.reshape((row_blocks, -1) + x.shape[1:])
    ys = y.reshape((row_blocks, -1) + y.shape[1:])

    def block(carry, xy):
        loss, g = jax.value_and_grad(loss_fn)(params, xy[0], xy[1], mode)
        return jax.tree_util.tree_map(jnp.add, carry, (loss, g)), None

    zero = (jnp.zeros((), jnp.float32),
            jax.tree_util.tree_map(jnp.zeros_like, params))
    total, _ = jax.lax.scan(block, zero, (xs, ys))
    return jax.tree_util.tree_map(lambda a: a / row_blocks, total)


def follow(loss_fn, rule, params, batches, updater: dict,
           mode: str = "float32", shard=None, row_blocks: int = 1):
    """Run `len(batches)` steps of `rule` (a module of `reference/rules/`)
    from `params`. `batches` yields (x, y) host arrays; `shard(array)`
    places one, if given. Returns host values."""
    @jax.jit
    def step(p, state, x, y):
        loss, g = loss_and_grad(loss_fn, p, x, y, mode, row_blocks)
        p_new, state_new = rule.update(p, state, g, updater)
        return p_new, state_new, loss, leaf_norms(g), leaf_samples(g)

    @jax.jit
    def delta(p, p0):
        return leaf_norms(jax.tree_util.tree_map(jnp.subtract, p, p0))

    put = shard or jnp.asarray
    p0 = params
    state = rule.init(params)
    p, losses, grad_norm, sample = params, [], None, None
    for x, y in batches:
        p, state, loss, gn, gs = step(p, state, put(x), put(y))
        losses.append(loss)
        if grad_norm is None:
            grad_norm, sample = gn, gs
    names = leaf_paths(p0)
    import numpy as np

    return {
        "grad_sample": dict(zip(names, map(np.asarray, sample))),
        "grad_sample_weight": sample_weights(p0),
        "mode": mode,
        "loss": [float(x) for x in losses],
        "grad_norm": dict(zip(names, map(float, grad_norm))),
        "delta_norm": dict(zip(names, map(float, delta(p, p0)))),
    }
