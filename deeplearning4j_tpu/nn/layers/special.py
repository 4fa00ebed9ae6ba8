"""Special layers: FrozenLayer, CenterLossOutputLayer, VAE, RBM.

Reference parity:
- `nn/layers/FrozenLayer.java` (transfer-learning freeze wrapper)
- `nn/layers/training/CenterLossOutputLayer.java`
- `nn/layers/variational/VariationalAutoencoder.java` (1,141 LoC)
- `nn/conf/layers/RBM.java` (contrastive-divergence pretraining)
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.activations import Activation
from deeplearning4j_tpu.nn.inputs import InputType
from deeplearning4j_tpu.nn.layers.base import Layer, register_layer
from deeplearning4j_tpu.nn.layers.feedforward import OutputLayer
from deeplearning4j_tpu.nn.losses import LossFunction


@register_layer
@dataclasses.dataclass(frozen=True)
class FrozenLayer(Layer):
    """Wrapper marking an inner layer's params as non-trainable. The model
    masks the wrapped subtree's gradients to zero (reference:
    `nn/layers/FrozenLayer.java`, which swaps in a NoOp updater)."""

    layer: Optional[Any] = None
    frozen: bool = True

    def infer_n_in(self, input_type: InputType):
        return dataclasses.replace(self, layer=self.layer.infer_n_in(input_type))

    def with_defaults(self, **defaults):
        return dataclasses.replace(self, layer=self.layer.with_defaults(**defaults))

    def output_type(self, input_type: InputType) -> InputType:
        return self.layer.output_type(input_type)

    def init_params(self, key, input_type, dtype=jnp.float32):
        return self.layer.init_params(key, input_type, dtype)

    def apply(self, params, x, **kw):
        # stop_gradient makes freezing robust even outside the updater mask.
        params = jax.tree_util.tree_map(jax.lax.stop_gradient, params)
        return self.layer.apply(params, x, **kw)


@register_layer
@dataclasses.dataclass(frozen=True)
class LoopedStack(Layer):
    """A span of layers run `passes` times over ONE set of leaves: pass t
    starts from what pass t - 1 gave, `norm` (a layer, an
    `RMSNormalization` say) is applied after every pass where there is
    one, and the output is the states of all passes, `[passes, ...]` (the
    plain span's output, with no leading axis, where `passes` is 1). The
    members' leaves lie once in this layer's tree under their names
    (`block0_<leaf>`, ..., `norm_<leaf>`), so the optimizer holds one
    state a leaf, a saved model one copy, and a leaf's gradient is the sum
    over the passes by differentiation.

    The passes are one traced body under `lax.scan`, whatever their
    number. Members keep their shape and nothing from pass to pass: a
    layer with state (batch statistics, an expert layer's counters and
    `aux_loss`) is refused by name. Under `gradient_checkpointing` the
    unit is one member's ONE application (`run`), and
    `MultiLayerNetwork._forward` does not wrap the whole layer again.
    There is no decode carry yet."""

    CONSUMES = "any"

    layers: Tuple[Any, ...] = ()
    passes: int = 1
    norm: Optional[Any] = None

    def __post_init__(self):
        object.__setattr__(self, "layers", tuple(self.layers))

    def _members(self):
        """Every member in the order a pass runs them, the norm last."""
        return self.layers + (() if self.norm is None else (self.norm,))

    def with_defaults(self, **defaults):
        named = lambda l, name: dataclasses.replace(
            l.with_defaults(**defaults), name=l.name or name)
        return dataclasses.replace(
            super().with_defaults(**defaults),
            layers=tuple(named(l, f"block{i}")
                         for i, l in enumerate(self.layers)),
            norm=None if self.norm is None else named(self.norm, "norm"))

    def infer_n_in(self, input_type: InputType):
        members = []
        for l in self._members():
            l = l.infer_n_in(input_type)
            if l.output_type(input_type) != input_type:
                raise ValueError(
                    f"{self.name}: {l.name} ({type(l).__name__}) turns "
                    f"{input_type} into {l.output_type(input_type)}; a "
                    f"looped span keeps its shape")
            members.append(l)
        n = len(self.layers)
        return dataclasses.replace(
            self, layers=tuple(members[:n]),
            norm=members[n] if self.norm is not None else None)

    def init_params(self, key, input_type, dtype=jnp.float32):
        if self.passes < 1 or not self.layers:
            raise ValueError(f"{self.name}: {self.passes} passes over "
                             f"{len(self.layers)} layers")
        params = {}
        members = self._members()
        for l, k in zip(members, jax.random.split(key, len(members))):
            p, state = l.init_params(k, input_type, dtype)
            if state:
                raise ValueError(
                    f"{self.name}: {l.name} ({type(l).__name__}) keeps "
                    f"{sorted(state)} from step to step; a looped span's "
                    f"layers hold nothing across passes")
            params.update({f"{l.name}_{leaf}": v for leaf, v in p.items()})
        return params, {}

    @staticmethod
    def _of(params, member):
        prefix = member.name + "_"
        return {k[len(prefix):]: v for k, v in params.items()
                if k.startswith(prefix)}

    def regularization(self, params):
        return sum(l.regularization(self._of(params, l))
                   for l in self._members())

    def decode_carry(self, batch: int, dtype=jnp.float32, **kw):
        raise NotImplementedError(
            f"LoopedStack {self.name!r} has no decode carry yet: every "
            f"(pass, layer) pair wants a cache of its own, which "
            f"`session_carries` and serving's pool do not hold")

    def run(self, params, x, *, train=False, rng=None, mask=None,
            unit=None):
        """The states of all passes. `unit(apply, mask)` wraps one
        member's apply as `models/multilayer._checkpointed` does, a
        checkpoint of its own round every application; without it the
        members are applied as they are. Where there is more than one
        pass the body is traced ONCE: what a member names while it is
        traced, it names once for all its passes."""
        def one_pass(h, prng):
            for i, l in enumerate(self._members()):
                scope = "pass_norm" if l is self.norm else l.name
                lrng = None if prng is None else jax.random.fold_in(prng, i)
                with jax.named_scope(scope):
                    if unit is None:
                        h, _ = l.apply(self._of(params, l), h, train=train,
                                       rng=lrng, mask=mask)
                    else:
                        (h, _), _ = unit(l.apply, mask)(
                            self._of(params, l), h, None, lrng)
            return h

        if self.passes == 1:
            return one_pass(x, rng)
        def body(h, t):
            h = one_pass(h, None if rng is None
                         else jax.random.fold_in(rng, t))
            return h, h

        return jax.lax.scan(body, x, jnp.arange(self.passes))[1]

    def apply(self, params, x, *, state=None, train=False, rng=None,
              mask=None):
        return self.run(params, x, train=train, rng=rng, mask=mask), state


@register_layer
@dataclasses.dataclass(frozen=True)
class CenterLossOutputLayer(OutputLayer):
    """Output layer with center loss (Wen et al.). Reference:
    `nn/layers/training/CenterLossOutputLayer.java`: per-class feature centers
    updated by EMA (alpha), center-distance penalty weighted by lambda."""

    alpha: float = 0.05
    lambda_: float = 2e-4

    def init_params(self, key, input_type, dtype=jnp.float32):
        params, _ = super().init_params(key, input_type, dtype)
        state = {"centers": jnp.zeros((self.n_out, self.n_in), dtype)}
        return params, state

    def score_and_state(self, params, x, labels, state, mask=None):
        base = super().score(params, x, labels, mask)
        centers = state["centers"]
        cls_centers = labels @ centers                       # [B, n_in]
        diff = x - cls_centers
        center_loss = 0.5 * jnp.mean(jnp.sum(diff * diff, axis=-1))
        # EMA center update (non-gradient state transition)
        counts = jnp.maximum(jnp.sum(labels, axis=0), 1.0)   # [n_out]
        delta = (labels.T @ diff) / counts[:, None]
        new_centers = centers + self.alpha * delta
        return base + self.lambda_ * center_loss, {"centers": new_centers}

    def score(self, params, x, labels, mask=None):
        # Stateless view (centers frozen) for eval paths.
        return super().score(params, x, labels, mask)


@register_layer
@dataclasses.dataclass(frozen=True)
class VariationalAutoencoder(Layer):
    """VAE as a layer, pretrainable via the ELBO; supervised forward emits the
    latent mean. Reference: `nn/layers/variational/VariationalAutoencoder.java`
    with encoder/decoder MLPs, pzx activation, reconstruction distributions
    (gaussian | bernoulli)."""

    n_in: Optional[int] = None
    n_out: Optional[int] = None            # latent size
    encoder_sizes: Sequence[int] = (64,)
    decoder_sizes: Sequence[int] = (64,)
    reconstruction_distribution: str = "gaussian"   # gaussian | bernoulli
    num_samples: int = 1

    @property
    def is_pretrainable(self) -> bool:
        return True

    def infer_n_in(self, input_type: InputType):
        if self.n_in is None:
            return dataclasses.replace(self, n_in=input_type.flat_size())
        return self

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.feed_forward(self.n_out)

    def _mlp_init(self, key, sizes, dtype):
        ps = []
        winit = self._winit()
        for i, (a, b) in enumerate(zip(sizes[:-1], sizes[1:])):
            key, k = jax.random.split(key)
            ps.append({"W": winit(k, (a, b), dtype), "b": jnp.zeros((b,), dtype)})
        return ps, key

    def init_params(self, key, input_type, dtype=jnp.float32):
        enc_sizes = [self.n_in, *self.encoder_sizes]
        dec_sizes = [self.n_out, *self.decoder_sizes]
        enc, key = self._mlp_init(key, enc_sizes, dtype)
        dec, key = self._mlp_init(key, dec_sizes, dtype)
        key, k1, k2, k3 = jax.random.split(key, 4)
        winit = self._winit()
        eh, dh = enc_sizes[-1], dec_sizes[-1]
        rec_out = self.n_in * (2 if self.reconstruction_distribution == "gaussian" else 1)
        params = {
            "enc": {str(i): p for i, p in enumerate(enc)},
            "dec": {str(i): p for i, p in enumerate(dec)},
            "mu": {"W": winit(k1, (eh, self.n_out), dtype), "b": jnp.zeros((self.n_out,), dtype)},
            "logvar": {"W": winit(k2, (eh, self.n_out), dtype), "b": jnp.zeros((self.n_out,), dtype)},
            "rec": {"W": winit(k3, (dh, rec_out), dtype), "b": jnp.zeros((rec_out,), dtype)},
        }
        return params, {}

    def _mlp(self, blocks, x):
        act = Activation.get(self.activation or "tanh")
        for i in range(len(blocks)):
            p = blocks[str(i)]
            x = act(x @ p["W"] + p["b"])
        return x

    def encode(self, params, x):
        h = self._mlp(params["enc"], x)
        mu = h @ params["mu"]["W"] + params["mu"]["b"]
        logvar = h @ params["logvar"]["W"] + params["logvar"]["b"]
        return mu, logvar

    def decode(self, params, z):
        h = self._mlp(params["dec"], z)
        return h @ params["rec"]["W"] + params["rec"]["b"]

    def apply(self, params, x, *, state=None, train=False, rng=None, mask=None):
        mu, _ = self.encode(params, x)
        return mu, state

    def reconstruction_score(self, params, x, *, rng):
        """Negative ELBO (to MINIMIZE) — the pretraining objective."""
        mu, logvar = self.encode(params, x)
        total = 0.0
        for i in range(self.num_samples):
            rng, k = jax.random.split(rng)
            eps = jax.random.normal(k, mu.shape, mu.dtype)
            z = mu + jnp.exp(0.5 * logvar) * eps
            out = self.decode(params, z)
            if self.reconstruction_distribution == "bernoulli":
                nll = jnp.sum(
                    jax.nn.softplus(out) - x * out, axis=-1
                )  # -log p under Bernoulli(sigmoid(out))
            else:
                rmu, rlogvar = jnp.split(out, 2, axis=-1)
                nll = 0.5 * jnp.sum(
                    rlogvar + (x - rmu) ** 2 / jnp.exp(rlogvar) + jnp.log(2 * jnp.pi),
                    axis=-1,
                )
            total = total + jnp.mean(nll)
        rec = total / self.num_samples
        kl = -0.5 * jnp.mean(jnp.sum(1 + logvar - mu**2 - jnp.exp(logvar), axis=-1))
        return rec + kl


@register_layer
@dataclasses.dataclass(frozen=True)
class RBM(Layer):
    """Bernoulli RBM with CD-1 pretraining. Reference: `nn/conf/layers/RBM.java`
    + `nn/layers/feedforward/rbm/`. Supervised forward = propup probabilities."""

    n_in: Optional[int] = None
    n_out: Optional[int] = None
    k: int = 1   # CD-k steps

    @property
    def is_pretrainable(self) -> bool:
        return True

    def infer_n_in(self, input_type: InputType):
        if self.n_in is None:
            return dataclasses.replace(self, n_in=input_type.flat_size())
        return self

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.feed_forward(self.n_out)

    def init_params(self, key, input_type, dtype=jnp.float32):
        return {
            "W": self._winit()(key, (self.n_in, self.n_out), dtype),
            "hb": jnp.zeros((self.n_out,), dtype),
            "vb": jnp.zeros((self.n_in,), dtype),
        }, {}

    def propup(self, params, v):
        return jax.nn.sigmoid(v @ params["W"] + params["hb"])

    def propdown(self, params, h):
        return jax.nn.sigmoid(h @ params["W"].T + params["vb"])

    def apply(self, params, x, *, state=None, train=False, rng=None, mask=None):
        return self.propup(params, x), state

    def reconstruction_score(self, params, v0, *, rng):
        """CD-k free-energy difference surrogate: grad of this ≈ CD update.

        Uses the standard trick: loss = FE(v0) - FE(v_k) with v_k treated as
        constant (stop_gradient), so jax.grad reproduces contrastive
        divergence; the reference hand-codes the same update.
        """
        def free_energy(v):
            wx = v @ params["W"] + params["hb"]
            return -v @ params["vb"] - jnp.sum(jax.nn.softplus(wx), axis=-1)

        vk = v0
        for _ in range(self.k):
            rng, k1, k2 = jax.random.split(rng, 3)
            h = jax.random.bernoulli(k1, self.propup(params, vk)).astype(v0.dtype)
            vk = self.propdown(params, h)
        vk = jax.lax.stop_gradient(vk)
        return jnp.mean(free_energy(v0) - free_energy(vk))
