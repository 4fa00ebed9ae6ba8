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

import functools

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
    blocks of rows at a time and averaged, so that the float32 reference's
    ACTIVATIONS fit on one chip. Exact only where no row's loss depends on
    another row (no batch statistics): the configuration says how many
    blocks its reference may take. What it costs: the scan's carry holds
    the summed gradient beside each block's own, a third tree of the
    parameters' size. Right for a model whose bytes are activations (the
    image cells); a model whose bytes are weights keeps `row_blocks` 1 and
    takes its blocks inside its own `loss_fn` under `jax.checkpoint`,
    which changes no arithmetic."""
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


class HostState:
    """The rule's state between steps, off the device. An entry with the
    parameters' tree structure (Adam's `m` and `v`, Nesterov's velocity)
    is a list of host arrays in `tree_leaves` order and visits the device
    a leaf at a time (`own`); anything else (Adam's `t`) stays whole, as
    `rule.init` gives it for one leaf (`rest`). A rule's `init` starts
    every entry of the first kind at zero: they are made here from their
    shapes, so that no tree of zeros the model's size is ever made on the
    device."""

    def __init__(self, rule, params):
        import numpy as np

        treedef = jax.tree_util.tree_structure(params)

        def like_params(node):
            return jax.tree_util.tree_structure(node) == treedef

        shapes, self.treedef = jax.tree_util.tree_flatten(
            jax.eval_shape(rule.init, params), is_leaf=like_params)
        self.per_leaf = [like_params(s) for s in shapes]
        self.own = [[np.zeros(s.shape, s.dtype)
                     for s in jax.tree_util.tree_leaves(shape)]
                    for shape, per_leaf in zip(shapes, self.per_leaf)
                    if per_leaf]
        smallest = min(jax.tree_util.tree_leaves(params),
                       key=lambda a: a.size)
        self.rest = self.split(rule.init(smallest))[1]

    def join(self, own: list, rest: list):
        """The rule's state for one leaf, from the leaf's own entries and
        the rest."""
        own, rest = iter(own), iter(rest)
        return self.treedef.unflatten(
            [next(own if per_leaf else rest) for per_leaf in self.per_leaf])

    def split(self, state):
        """(own, rest) of one leaf's state."""
        entries = self.treedef.flatten_up_to(state)
        return ([e for e, p in zip(entries, self.per_leaf) if p],
                [e for e, p in zip(entries, self.per_leaf) if not p])


def follow(loss_fn, rule, params, batches, updater: dict,
           mode: str = "float32", shard=None, row_blocks: int = 1):
    """Run `len(batches)` steps of `rule` (a module of `reference/rules/`)
    from `params`, whose buffers it consumes. `batches` yields (x, y) host
    arrays; `shard(array)` places one, if given. Returns host values.

    Of the model's size the device holds the parameters and one gradient
    and nothing else: loss and gradient are one program, the rule is then
    applied a leaf at a time (`rule.update` on one-leaf trees, the leaf's
    parameter and state donated, so replaced and not doubled, its gradient
    dropped),
    the rule's state waits on the host between steps (`HostState`), and so
    does the copy of the initial parameters that `delta_norm` is read
    against. Every leaf stays where `params` had it placed."""
    import numpy as np

    state = HostState(rule, params)

    @jax.jit
    def grad_of(p, x, y):
        loss, g = loss_and_grad(loss_fn, p, x, y, mode, row_blocks)
        return loss, g, leaf_norms(g), leaf_samples(g)

    @functools.partial(jax.jit, donate_argnums=(0, 2))
    def update_leaf(p, g, own, rest):
        p_new, new = rule.update(p, state.join(own, rest), g, updater)
        return (p_new, *state.split(new))

    @jax.jit
    def delta_leaf(p, p0):
        return leaf_norms(p - p0)[0]

    put = shard or jnp.asarray
    leaves, treedef = jax.tree_util.tree_flatten(params)
    names, weights = leaf_paths(params), sample_weights(params)
    # copies: on the CPU `np.asarray` is a view of a buffer donated below
    initial = [np.array(leaf) for leaf in leaves]
    losses, grad_norm, sample = [], None, None
    for x, y in batches:
        loss, g, gn, gs = grad_of(treedef.unflatten(leaves), put(x), put(y))
        losses.append(loss)
        if grad_norm is None:
            grad_norm, sample = gn, gs
        g = treedef.flatten_up_to(g)
        for i, leaf in enumerate(leaves):
            own = [jax.device_put(entry[i], leaf.sharding)
                   for entry in state.own]
            leaves[i], own, rest = update_leaf(leaf, g[i], own, state.rest)
            g[i] = None
            for entry, new in zip(state.own, own):
                entry[i] = np.asarray(new)
        # every leaf's call gives the same rest: the step's
        state.rest = rest
    delta = [delta_leaf(leaf, jax.device_put(p0, leaf.sharding))
             for leaf, p0 in zip(leaves, initial)]
    return {
        "grad_sample": dict(zip(names, map(np.asarray, sample))),
        "grad_sample_weight": weights,
        "mode": mode,
        "loss": [float(x) for x in losses],
        "grad_norm": dict(zip(names, map(float, grad_norm))),
        "delta_norm": dict(zip(names, map(float, delta))),
    }
