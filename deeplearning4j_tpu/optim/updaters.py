"""Gradient updaters (optimizer rules) as pure pytree transforms.

Reference parity: ND4J's `GradientUpdater` implementations (Sgd, Adam, AdaMax,
Nadam, AMSGrad, Nesterovs, AdaGrad, AdaDelta, RmsProp, NoOp) applied through
DL4J's `UpdaterBlock.update()` (`nn/updater/UpdaterBlock.java:101-160`): the
reference transforms the gradient IN PLACE into the update over one contiguous
state view; here the same math is a pure function over pytrees — XLA fuses the
whole update into the train step, and optimizer state shards with the params
(ZeRO-style) under `jax.sharding` instead of living in one host-side view.

API: ``state = u.init(params)``; ``updates, state = u.apply(grads, state,
params, step)``; caller does ``params = params - updates`` (the reference's
`StepFunction.step` — `optimize/solvers/StochasticGradientDescent.java:79`).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.optim.schedules import as_schedule
from deeplearning4j_tpu.utils.serde import register_serde

_tmap = jax.tree_util.tree_map


def _lr(self, step):
    return as_schedule(self.learning_rate).value(step)


class Updater:
    """Base updater. Subclasses are frozen dataclasses (JSON-serializable).

    `sharded_state` names the state keys that are param-shaped moments —
    the leaves the sharding spine (`parallel.mesh.MeshContext`) may
    partition across the replica axis (cross-replica weight-update
    sharding, arXiv:2004.13336). Scalar or irregular state must stay off
    this list; stateless updaters leave it empty.
    """

    sharded_state = ()   # state keys holding param-shaped moments

    def init(self, params) -> Any:
        return ()

    def apply(self, grads, state, params, step):
        raise NotImplementedError

    def update_with_params(self, grads, state, params, step):
        """The whole optimizer step: `apply`, then the subtraction, and
        returns (new_params, new_state) in the dtypes they came in
        (schedules may promote to f32; bf16 training and buffer donation
        rest on parameters and state keeping theirs)."""
        upd, st = self.apply(grads, state, params, step)
        new_params = _tmap(lambda a, b: a - b.astype(a.dtype), params, upd)
        new_state = _tmap(lambda n, o: n.astype(o.dtype), st, state)
        return new_params, new_state

    # learning-rate accessor shared by all (schedule-aware)
    lr = _lr


@register_serde
@dataclasses.dataclass(frozen=True)
class NoOp(Updater):
    """Reference: NoOp updater (frozen layers use this)."""

    def apply(self, grads, state, params, step):
        return _tmap(jnp.zeros_like, grads), state


@register_serde
@dataclasses.dataclass(frozen=True)
class Sgd(Updater):
    """Reference: org.nd4j.linalg.learning.Sgd — update = lr * g."""
    learning_rate: Any = 1e-3

    def apply(self, grads, state, params, step):
        lr = self.lr(step)
        return _tmap(lambda g: lr * g, grads), state


@register_serde
@dataclasses.dataclass(frozen=True)
class Nesterovs(Updater):
    """Reference: Nesterovs momentum (DL4J default momentum 0.9).

    Matches ND4J NesterovsUpdater: v' = mu*v - lr*g; update = -(mu*v' - lr*g)
    i.e. params += mu*v' - lr*g.
    """
    learning_rate: Any = 0.1
    momentum: float = 0.9
    sharded_state = ("v",)

    def init(self, params):
        return {"v": _tmap(jnp.zeros_like, params)}

    def apply(self, grads, state, params, step):
        lr = self.lr(step)
        mu = self.momentum
        v_new = _tmap(lambda v, g: mu * v - lr * g, state["v"], grads)
        updates = _tmap(lambda vn, g: -(mu * vn - lr * g), v_new, grads)
        return updates, {"v": v_new}


@register_serde
@dataclasses.dataclass(frozen=True)
class Adam(Updater):
    """Reference: AdamUpdater (bias-corrected first/second moments)."""
    learning_rate: Any = 1e-3
    beta1: float = 0.9
    beta2: float = 0.999
    epsilon: float = 1e-8
    sharded_state = ("m", "v")

    def init(self, params):
        z = _tmap(jnp.zeros_like, params)
        return {"m": z, "v": _tmap(jnp.zeros_like, params)}

    def apply(self, grads, state, params, step):
        lr = self.lr(step)
        t = jnp.asarray(step, jnp.float32) + 1.0
        b1, b2 = self.beta1, self.beta2
        m = _tmap(lambda m, g: b1 * m + (1 - b1) * g, state["m"], grads)
        v = _tmap(lambda v, g: b2 * v + (1 - b2) * g * g, state["v"], grads)
        bc = jnp.sqrt(1.0 - b2**t) / (1.0 - b1**t)
        updates = _tmap(lambda m, v: lr * bc * m / (jnp.sqrt(v) + self.epsilon), m, v)
        return updates, {"m": m, "v": v}


@register_serde
@dataclasses.dataclass(frozen=True)
class AdaMax(Updater):
    """Reference: AdaMaxUpdater — infinity-norm Adam variant."""
    learning_rate: Any = 1e-3
    beta1: float = 0.9
    beta2: float = 0.999
    epsilon: float = 1e-8
    sharded_state = ("m", "u")

    def init(self, params):
        return {"m": _tmap(jnp.zeros_like, params), "u": _tmap(jnp.zeros_like, params)}

    def apply(self, grads, state, params, step):
        lr = self.lr(step)
        t = jnp.asarray(step, jnp.float32) + 1.0
        b1 = self.beta1
        m = _tmap(lambda m, g: b1 * m + (1 - b1) * g, state["m"], grads)
        u = _tmap(lambda u, g: jnp.maximum(self.beta2 * u, jnp.abs(g)), state["u"], grads)
        scale = lr / (1.0 - b1**t)
        updates = _tmap(lambda m, u: scale * m / (u + self.epsilon), m, u)
        return updates, {"m": m, "u": u}


@register_serde
@dataclasses.dataclass(frozen=True)
class Nadam(Updater):
    """Reference: NadamUpdater — Nesterov-accelerated Adam."""
    learning_rate: Any = 1e-3
    beta1: float = 0.9
    beta2: float = 0.999
    epsilon: float = 1e-8
    sharded_state = ("m", "v")

    def init(self, params):
        return {"m": _tmap(jnp.zeros_like, params), "v": _tmap(jnp.zeros_like, params)}

    def apply(self, grads, state, params, step):
        lr = self.lr(step)
        t = jnp.asarray(step, jnp.float32) + 1.0
        b1, b2 = self.beta1, self.beta2
        m = _tmap(lambda m, g: b1 * m + (1 - b1) * g, state["m"], grads)
        v = _tmap(lambda v, g: b2 * v + (1 - b2) * g * g, state["v"], grads)
        mc = 1.0 - b1**t
        vc = 1.0 - b2**t
        updates = _tmap(
            lambda m, v, g: lr
            * (b1 * m / mc + (1 - b1) * g / mc)
            / (jnp.sqrt(v / vc) + self.epsilon),
            m, v, grads,
        )
        return updates, {"m": m, "v": v}


@register_serde
@dataclasses.dataclass(frozen=True)
class AMSGrad(Updater):
    """Reference: AMSGradUpdater — Adam with non-decreasing v-hat."""
    learning_rate: Any = 1e-3
    beta1: float = 0.9
    beta2: float = 0.999
    epsilon: float = 1e-8
    sharded_state = ("m", "v", "vhat")

    def init(self, params):
        z = _tmap(jnp.zeros_like, params)
        return {"m": z, "v": _tmap(jnp.zeros_like, params), "vhat": _tmap(jnp.zeros_like, params)}

    def apply(self, grads, state, params, step):
        lr = self.lr(step)
        b1, b2 = self.beta1, self.beta2
        m = _tmap(lambda m, g: b1 * m + (1 - b1) * g, state["m"], grads)
        v = _tmap(lambda v, g: b2 * v + (1 - b2) * g * g, state["v"], grads)
        vhat = _tmap(jnp.maximum, state["vhat"], v)
        updates = _tmap(lambda m, vh: lr * m / (jnp.sqrt(vh) + self.epsilon), m, vhat)
        return updates, {"m": m, "v": v, "vhat": vhat}


@register_serde
@dataclasses.dataclass(frozen=True)
class AdaGrad(Updater):
    """Reference: AdaGradUpdater."""
    learning_rate: Any = 1e-1
    epsilon: float = 1e-6
    sharded_state = ("h",)

    def init(self, params):
        return {"h": _tmap(jnp.zeros_like, params)}

    def apply(self, grads, state, params, step):
        lr = self.lr(step)
        h = _tmap(lambda h, g: h + g * g, state["h"], grads)
        updates = _tmap(lambda g, h: lr * g / (jnp.sqrt(h) + self.epsilon), grads, h)
        return updates, {"h": h}


@register_serde
@dataclasses.dataclass(frozen=True)
class AdaDelta(Updater):
    """Reference: AdaDeltaUpdater (rho/epsilon; no explicit LR)."""
    rho: float = 0.95
    epsilon: float = 1e-6
    sharded_state = ("Eg", "Ex")

    def init(self, params):
        return {"Eg": _tmap(jnp.zeros_like, params), "Ex": _tmap(jnp.zeros_like, params)}

    def apply(self, grads, state, params, step):
        rho, eps = self.rho, self.epsilon
        Eg = _tmap(lambda a, g: rho * a + (1 - rho) * g * g, state["Eg"], grads)
        updates = _tmap(
            lambda g, eg, ex: g * jnp.sqrt(ex + eps) / jnp.sqrt(eg + eps),
            grads, Eg, state["Ex"],
        )
        Ex = _tmap(lambda a, u: rho * a + (1 - rho) * u * u, state["Ex"], updates)
        return updates, {"Eg": Eg, "Ex": Ex}


@register_serde
@dataclasses.dataclass(frozen=True)
class RmsProp(Updater):
    """Reference: RmsPropUpdater."""
    learning_rate: Any = 1e-1
    rms_decay: float = 0.95
    epsilon: float = 1e-8
    sharded_state = ("g2",)

    def init(self, params):
        return {"g2": _tmap(jnp.zeros_like, params)}

    def apply(self, grads, state, params, step):
        lr = self.lr(step)
        d = self.rms_decay
        g2 = _tmap(lambda a, g: d * a + (1 - d) * g * g, state["g2"], grads)
        updates = _tmap(lambda g, a: lr * g / (jnp.sqrt(a) + self.epsilon), grads, g2)
        return updates, {"g2": g2}


#: Every param-shaped moment key any built-in updater declares — the
#: sharding spine's default answer to "which updater-state leaves may be
#: partitioned across the replica axis" when it cannot see the per-layer
#: updater instances (e.g. re-sharding a checkpoint tree).
MOMENT_STATE_KEYS = frozenset(
    k for cls in (Nesterovs, Adam, AdaMax, Nadam, AMSGrad, AdaGrad,
                  AdaDelta, RmsProp)
    for k in cls.sharded_state)


def resolve_updater(u) -> Updater:
    """Accept an Updater instance or a name string ('adam', 'sgd', ...)."""
    if isinstance(u, Updater):
        return u
    names = {
        "sgd": Sgd, "adam": Adam, "adamax": AdaMax, "nadam": Nadam,
        "amsgrad": AMSGrad, "nesterovs": Nesterovs, "adagrad": AdaGrad,
        "adadelta": AdaDelta, "rmsprop": RmsProp, "noop": NoOp, "none": NoOp,
    }
    key = str(u).lower()
    if key not in names:
        raise ValueError(f"Unknown updater {u!r}; known: {sorted(names)}")
    return names[key]()
