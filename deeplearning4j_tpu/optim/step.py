"""The train step, built once for every trainer.

`MultiLayerNetwork`, `ComputationGraph` and `ParallelWrapper` hand this
module their loss and their updaters and get back the pure step
(`make_train_step`), its K-step `lax.scan` window (`make_fused_step`) and
the donating jit of either (`jit_step`); `build_step` is the three in a
trainer's order, as one span `step.build`. `optim/executor.py` drives what
comes out. The models keep what is theirs: the forward pass, the loss, and
turning a batch into the step's arguments.

Reference parity: the solver step of
`optimize/solvers/StochasticGradientDescent.java:58-98` (gradient, gradient
normalization, updater, `StepFunction.step`) as one traced function.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.observe import donatemon, span
from deeplearning4j_tpu.utils.pytrees import tree_norm

__all__ = ["make_train_step", "make_fused_step", "stack_step_args",
           "jit_step", "build_step", "normalize_grads", "as_features",
           "COUNTER_PREFIXES", "COUNTER_STEPS", "counters_of",
           "with_counter_sums"]

_tmap = jax.tree_util.tree_map

# Which keys of a layer's state are a step's counters, by prefix
# (`parallel/moe.COUNTERS` and `TOKENS_HELD`, `attention.SPARSE_COUNTERS`,
# `ssm_chunk_carry`, `exit_entropy` / `exit_mass`, `main_loss` /
# `mtp_loss`): the step sums them, the executor publishes them. Beside
# each counter `c` the net's state carries `c_sum` (float32, cumulative
# since `init()`), and one `counter_steps` (int32) a layer.
COUNTER_PREFIXES = ("moe_", "sparse_blocks_", "ssm_", "exit_", "mtp_",
                    "main_loss")
COUNTER_STEPS = "counter_steps"


def counters_of(state) -> list:
    """The counters among the keys of one layer's state."""
    if not isinstance(state, dict):
        return []
    return [k for k in state
            if k.startswith(COUNTER_PREFIXES) and not k.endswith("_sum")]


def with_counter_sums(states):
    """A net's state tree as its layers' `init_params` gave it, with the
    sums born at zero in every layer whose state holds a counter: the
    step's state then has one structure in and out (donation, the K-step
    scan's carry). A layer without a counter is handed back as it came."""
    out = {}
    for name, st in states.items():
        own = counters_of(st)
        if own:
            st = {**st, COUNTER_STEPS: jnp.zeros((), jnp.int32),
                  **{c + "_sum": jnp.zeros(jnp.shape(st[c]), jnp.float32)
                     for c in own}}
        out[name] = st
    return out


def _integrate(old, new):
    """One layer's new state with its counters added to the sums the OLD
    state carried. A layer that hands its incoming state back out
    (`{**state, ...}`) brings the old sums along: they are overwritten,
    never added to. A counter leaves in the dtype it came in (with x64 on
    a layer's int32 counter comes out int64: the scan's carry and the
    next step's cache key want one type). A state born without sums is
    `new` itself."""
    if not isinstance(old, dict) or COUNTER_STEPS not in old:
        return new
    own = {c: new[c].astype(old[c].dtype) for c in counters_of(new)}
    return {**new, **own, COUNTER_STEPS: old[COUNTER_STEPS] + 1,
            **{c + "_sum": old[c + "_sum"] + v.astype(jnp.float32)
               for c, v in own.items()}}


def normalize_grads(grads, mode: str, threshold: float):
    """Gradient normalization/clipping per layer subtree.
    Reference: `nn/conf/GradientNormalization.java` applied in BaseLayer."""
    if mode == "none":
        return grads
    if mode == "clip_elementwise_absolute_value":
        return _tmap(lambda g: jnp.clip(g, -threshold, threshold), grads)

    def per_layer(sub):
        if mode == "renormalize_l2_per_layer":
            n = tree_norm(sub)
            return _tmap(lambda g: g / jnp.maximum(n, 1e-8), sub)
        if mode == "clip_l2_per_layer":
            n = tree_norm(sub)
            scale = jnp.minimum(1.0, threshold / jnp.maximum(n, 1e-8))
            return _tmap(lambda g: g * scale, sub)
        if mode == "renormalize_l2_per_param_type":
            return {k: v / jnp.maximum(jnp.linalg.norm(jnp.ravel(v)), 1e-8)
                    for k, v in sub.items()}
        if mode == "clip_l2_per_param_type":
            out = {}
            for k, v in sub.items():
                n = jnp.linalg.norm(jnp.ravel(v))
                out[k] = v * jnp.minimum(1.0, threshold / jnp.maximum(n, 1e-8))
            return out
        raise ValueError(mode)

    return {name: per_layer(sub) for name, sub in grads.items()}


def make_train_step(loss_fn, updaters, *, grad_norm, stateful,
                    carry_names=None):
    """The pure (un-jitted) train step.

    `loss_fn(params, states, features, labels, fmask, lmask, rng,
    carries=...) -> (loss, new_states)` is the model's training loss;
    features, labels and masks are arrays or dicts, as that loss takes
    them. `updaters` maps
    each top-level key of `params` to its `Updater`, `grad_norm` is the
    configuration's `(mode, threshold)`, and `stateful` names the layers
    whose new state persists (batch-norm statistics; a step's counters,
    which are summed here into the `<counter>_sum` leaves the net's
    `init()` put beside them: `with_counter_sums`).

    Returns `(params, opt_state, states, loss)` and, only with
    `carry_names` (truncated BPTT), a fifth value: those layers' new states
    with the gradient stopped, for the next chunk.
    """
    mode, threshold = grad_norm

    def step_fn(params, opt_state, states, step, features, labels, fmask,
                lmask, rng, carries=None):
        def loss_of(p):
            return loss_fn(p, states, features, labels, fmask, lmask, rng,
                           carries=carries)

        (loss, new_states), grads = jax.value_and_grad(
            loss_of, has_aux=True)(params)
        grads = normalize_grads(grads, mode, threshold)
        new_params, new_opt = {}, {}
        with jax.named_scope("updater"):
            for name, u in updaters.items():
                new_params[name], new_opt[name] = u.update_with_params(
                    grads[name], opt_state[name], params[name], step)
        persist = {
            n: (_integrate(states[n], new_states[n]) if n in stateful
                else states.get(n, {}))
            for n in states
        }
        if carry_names is None:
            return new_params, new_opt, persist, loss
        # Carry RNN state to the next chunk, gradients truncated at the
        # chunk boundary (reference: `rnnUpdateStateWithTBPTTState`).
        return new_params, new_opt, persist, loss, {
            n: _tmap(jax.lax.stop_gradient, new_states[n])
            for n in carry_names}

    return step_fn


def make_fused_step(step_fn):
    """K train steps as ONE `lax.scan` over batches stacked on a leading
    axis: `(params, opt_state, states, step0, rng, features, labels, fmask,
    lmask) -> (params, opt_state, states, rng, losses)`."""

    def fused(params, opt_state, states, step0, rng, feats, labs, fms, lms):
        # rng rides in the carry and splits INSIDE the scan: the same
        # `rng, sub = split(rng)` chain as K single dispatches (bit-identical
        # subkeys), with no per-step host dispatch.
        def body(carry, xs):
            p, o, s, step, r = carry
            f, l, fm, lm = xs
            r, sub = jax.random.split(r)
            new_p, new_o, persist, loss = step_fn(
                p, o, s, step, f, l, fm, lm, sub)
            return (new_p, new_o, persist, step + 1, r), loss

        (params, opt_state, states, _, rng), losses = jax.lax.scan(
            body, (params, opt_state, states, step0, rng),
            (feats, labs, fms, lms))
        return params, opt_state, states, rng, losses

    return fused


def as_features(a, dtype, asarray=jnp.asarray, ids: bool = False):
    """A batch's features as the step takes them: in the net's `dtype`
    (cast on the device when they are there already), except integers
    into an input that looks ids up (`ids`: the consumer is an embedding),
    which stay as they came. A bf16 net holds integers exactly only to
    256, so ids cast to it fetch their neighbours' rows."""
    kind = a.dtype if hasattr(a, "dtype") else np.asarray(a).dtype
    if ids and np.issubdtype(kind, np.integer):
        return asarray(a)
    return asarray(a, dtype)


def stack_step_args(per_batch):
    """K batches' step arguments `(features, labels, fmask, lmask)` as one
    such tuple, every leaf stacked on a new leading axis: what the fused
    step scans over. Leaves that are all numpy stack on the host, so the
    caller's placement is each tensor's one transfer."""
    def stack(*leaves):
        if all(isinstance(v, np.ndarray) for v in leaves):
            return np.stack(leaves)
        return jnp.stack(leaves)

    return _tmap(stack, *per_batch)


def jit_step(fn, *, cache, key, name, in_shardings=None, out_shardings=None):
    """Jit a step (or a window of steps) with its first three arguments
    (params, opt_state, states) donated, and keep it in the owner's
    `_jit_cache` under `key`. `name` is what `donatemon` calls it
    (identity with DL4J_TPU_DONATEMON off; on, it witnesses the donation).
    With shardings both ends are pinned: the donated buffers come back
    where they were placed."""
    placed = {} if in_shardings is None else {
        "in_shardings": in_shardings, "out_shardings": out_shardings}
    cache[key] = donatemon.instrument(
        jax.jit(fn, donate_argnums=(0, 1, 2), **placed), (0, 1, 2),
        name=name, arg_names=("params", "opt_state", "states"))
    # read back through the cache: __setitem__ may have wrapped the
    # callable in the watchdog's cost/comm probe, and returning the raw
    # local lets the FIRST dispatch (often the only one in a short fit)
    # bypass the ledger
    return cache[key]


def build_step(make_step_fn, *, fused: bool = False, name, **jit_kw):
    """What a trainer does for a step its `_jit_cache` lacks, as the span
    `step.build`: the pure step from the net's `make_step_fn` (so
    `make_train_step`), its K-step window where `fused`, and `jit_step`
    with `jit_kw`. Host work only: nothing is traced or compiled before
    the step's first call, whose `xla.trace`, `xla.lower` and
    `xla.compile` spans follow this one."""
    with span("step.build", step=name, fused=fused):
        fn = make_step_fn()
        return jit_step(make_fused_step(fn) if fused else fn, name=name,
                        **jit_kw)
