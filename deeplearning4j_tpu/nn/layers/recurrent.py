"""Recurrent layers: LSTM family, SimpleRnn, GRU, RnnOutputLayer, wrappers.

Reference parity: `nn/layers/recurrent/GravesLSTM.java:43` +
`LSTMHelpers.java` (shared fused fwd `:62`, bwd `:291`), configs in
`nn/conf/layers/{GravesLSTM,GravesBidirectionalLSTM,LSTM,RnnOutputLayer}.java`.

TPU-first redesign:
- Activations are [batch, time, features] (the reference is [b, f, t]).
- The time loop is ONE `lax.scan`; the input projection for ALL timesteps is
  hoisted out of the scan as a single [B*T, F] @ [F, 4H] matmul on the MXU —
  only the small recurrent matmul stays sequential. This is the fusion the
  reference got from hand-written `LSTMHelpers` (and cuDNN never provided at
  this snapshot — see SURVEY §2.3 note).
- Backprop-through-time comes from `jax.grad` through the scan; truncated BPTT
  is done at the model level by slicing the sequence (reference:
  `MultiLayerNetwork.doTruncatedBPTT`).
- Stateful stepping (`rnnTimeStep`) maps to passing/returning the explicit
  carry in the `state` dict under keys "h"/"c".
- Param names follow the reference's GravesLSTMParamInitializer: "W" (input
  weights), "RW" (recurrent weights), "b".
- Per-timestep masking: when mask[t]==0 the carry is held (the reference's
  variable-length masking semantics).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.nn.activations import Activation
from deeplearning4j_tpu.nn.inputs import InputType
from deeplearning4j_tpu.nn.layers.base import Layer, Params, register_layer
from deeplearning4j_tpu.nn.layers.attention import rms_norm
from deeplearning4j_tpu.nn.layers.feedforward import OutputLayer
from deeplearning4j_tpu.nn.losses import LossFunction, _reduce


def _mask_carry(new, old, m):
    """Hold the carry where mask==0. m: [B] for one step."""
    return jnp.where(m[:, None] > 0, new, old)


@register_layer
@dataclasses.dataclass(frozen=True)
class BaseRecurrentLayer(Layer):
    n_in: Optional[int] = None
    n_out: Optional[int] = None
    gate_activation: str = "sigmoid"
    forget_gate_bias_init: float = 1.0

    def infer_n_in(self, input_type: InputType):
        if self.n_in is None:
            return dataclasses.replace(self, n_in=input_type.size)
        return self

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.recurrent(self.n_out, input_type.timesteps)

    def initial_carry(self, batch: int, dtype=jnp.float32):
        raise NotImplementedError


@register_layer
@dataclasses.dataclass(frozen=True)
class LSTM(BaseRecurrentLayer):
    """Standard (peephole-free) LSTM. Reference: `nn/conf/layers/LSTM` /
    `LSTMHelpers.activateHelper` with peephole=false. Gate order i,f,g,o."""

    peephole: bool = False
    # Fused Pallas sequence kernel (ops/lstm.py — the LSTMHelpers-equivalent
    # fusion, SURVEY §7): None = auto (on TPU when gate/cell activations are
    # the standard sigmoid/tanh), True/False = force.
    fused: Optional[bool] = None

    def init_params(self, key, input_type, dtype=jnp.float32):
        h = self.n_out
        k1, k2, k3 = jax.random.split(key, 3)
        winit = self._winit()
        params = {
            "W": winit(k1, (self.n_in, 4 * h), dtype),
            "RW": winit(k2, (h, 4 * h), dtype),
            "b": jnp.zeros((4 * h,), dtype)
            .at[h:2 * h].set(self.forget_gate_bias_init),
        }
        if self.peephole:
            params["P"] = jnp.zeros((3, h), dtype)  # peep for i, f, o
        return params, {}

    def initial_carry(self, batch: int, dtype=jnp.float32):
        h = self.n_out
        return {"h": jnp.zeros((batch, h), dtype), "c": jnp.zeros((batch, h), dtype)}

    def _use_fused(self) -> bool:
        from deeplearning4j_tpu.ops.lstm import fused_lstm_available

        # NB: activation=None means IDENTITY (Activation.get(None)), not
        # tanh — the kernel hard-codes sigmoid/tanh, so require them exactly.
        ok = fused_lstm_available(self.gate_activation, self.activation)
        if self.fused is not None:
            if self.fused and not ok:
                raise ValueError(
                    f"fused=True requires gate_activation='sigmoid' and "
                    f"activation='tanh'; got {self.gate_activation!r}/"
                    f"{self.activation!r}")
            return self.fused
        from deeplearning4j_tpu.ops.kernel_defaults import lstm_policy

        return (ok and jax.default_backend() == "tpu"
                and lstm_policy() == "fused")

    def _step(self, params, carry, xw_t, m_t):
        """One scan step. xw_t: precomputed x_t @ W + b, [B, 4H]."""
        h_prev, c_prev = carry["h"], carry["c"]
        hsz = self.n_out
        gates = xw_t + h_prev @ params["RW"]
        i_, f_, g_, o_ = jnp.split(gates, 4, axis=-1)
        gate_act = Activation.get(self.gate_activation)
        if self.peephole:
            p = params["P"]
            i_ = i_ + c_prev * p[0]
            f_ = f_ + c_prev * p[1]
        i = gate_act(i_)
        f = gate_act(f_)
        g = self._act(g_)
        c = f * c_prev + i * g
        if self.peephole:
            o_ = o_ + c * params["P"][2]
        o = gate_act(o_)
        h = o * self._act(c)
        if m_t is not None:
            h = _mask_carry(h, h_prev, m_t)
            c = _mask_carry(c, c_prev, m_t)
        return {"h": h, "c": c}

    def apply(self, params, x, *, state=None, train=False, rng=None, mask=None):
        x = self._maybe_dropout(x, train, rng)
        B, T, _ = x.shape
        carry = state if state and "h" in state else self.initial_carry(B, x.dtype)
        # Hoist the big input matmul out of the scan: one [B*T,F]@[F,4H] MXU op.
        xw = x.reshape(B * T, -1) @ params["W"] + params["b"]
        xw = xw.reshape(B, T, -1).transpose(1, 0, 2)  # [T, B, 4H]
        m = None if mask is None else mask.astype(x.dtype).T  # [T, B]

        if self._use_fused():
            from deeplearning4j_tpu.ops.lstm import fused_lstm

            p = params.get("P")
            if p is None:
                p = jnp.zeros((3, self.n_out), x.dtype)
            mm = m if m is not None else jnp.ones((T, B), x.dtype)
            hs, hT, cT = fused_lstm(
                xw, params["RW"], p, carry["h"], carry["c"], mm,
                jax.default_backend() != "tpu")
            return hs.transpose(1, 0, 2), {"h": hT, "c": cT}

        def step(c, inp):
            xw_t, m_t = inp
            new = self._step(params, c, xw_t, m_t)
            return new, new["h"]

        carry, hs = lax.scan(step, carry, (xw, m) if m is not None else (xw, jnp.ones((T, B), x.dtype)))
        y = hs.transpose(1, 0, 2)  # [B, T, H]
        return y, carry


@register_layer
@dataclasses.dataclass(frozen=True)
class GravesLSTM(LSTM):
    """LSTM with peephole connections — the reference's workhorse RNN
    (`nn/layers/recurrent/GravesLSTM.java:43`, Graves 2013 variant)."""

    peephole: bool = True


@register_layer
@dataclasses.dataclass(frozen=True)
class GRU(BaseRecurrentLayer):
    """GRU — modern extension (the reference snapshot has no GRU impl).

    `reset_after` picks where the reset gate applies: True (default, the
    cuDNN/Keras-2 GRU-v2 variant) multiplies r into the already-computed
    recurrent matmul (n = act(xW + r·(h RW))); False (classic Cho et al. /
    Keras reset_after=False) multiplies r into the hidden state BEFORE the
    matmul (n = act(xW + (r·h) RW)). `recurrent_bias=True` adds a separate
    bias on the recurrent matmul (only meaningful with reset_after=True) —
    both are needed for exact Keras import."""

    reset_after: bool = True
    recurrent_bias: bool = False

    def init_params(self, key, input_type, dtype=jnp.float32):
        h = self.n_out
        k1, k2 = jax.random.split(key)
        winit = self._winit()
        params = {
            "W": winit(k1, (self.n_in, 3 * h), dtype),
            "RW": winit(k2, (h, 3 * h), dtype),
            "b": jnp.zeros((3 * h,), dtype),
        }
        if self.recurrent_bias:
            params["rb"] = jnp.zeros((3 * h,), dtype)
        return params, {}

    def initial_carry(self, batch: int, dtype=jnp.float32):
        return {"h": jnp.zeros((batch, self.n_out), dtype)}

    def apply(self, params, x, *, state=None, train=False, rng=None, mask=None):
        x = self._maybe_dropout(x, train, rng)
        B, T, _ = x.shape
        hsz = self.n_out
        carry = state if state and "h" in state else self.initial_carry(B, x.dtype)
        xw = (x.reshape(B * T, -1) @ params["W"] + params["b"]).reshape(B, T, -1)
        xw = xw.transpose(1, 0, 2)
        m = (mask.astype(x.dtype).T if mask is not None
             else jnp.ones((T, B), x.dtype))
        gate_act = Activation.get(self.gate_activation)

        def step(c, inp):
            xw_t, m_t = inp
            h_prev = c["h"]
            if self.reset_after:
                rh = h_prev @ params["RW"]
                if "rb" in params:
                    rh = rh + params["rb"]
                r = gate_act(xw_t[:, :hsz] + rh[:, :hsz])
                z = gate_act(xw_t[:, hsz:2 * hsz] + rh[:, hsz:2 * hsz])
                n = self._act(xw_t[:, 2 * hsz:] + r * rh[:, 2 * hsz:])
            else:
                rz = h_prev @ params["RW"][:, :2 * hsz]
                r = gate_act(xw_t[:, :hsz] + rz[:, :hsz])
                z = gate_act(xw_t[:, hsz:2 * hsz] + rz[:, hsz:])
                n = self._act(xw_t[:, 2 * hsz:]
                              + (r * h_prev) @ params["RW"][:, 2 * hsz:])
            h = (1 - z) * n + z * h_prev
            h = _mask_carry(h, h_prev, m_t)
            return {"h": h}, h

        carry, hs = lax.scan(step, carry, (xw, m))
        return hs.transpose(1, 0, 2), carry


@register_layer
@dataclasses.dataclass(frozen=True)
class SimpleRnn(BaseRecurrentLayer):
    """Vanilla RNN: h = act(x W + h_prev RW + b)."""

    def init_params(self, key, input_type, dtype=jnp.float32):
        h = self.n_out
        k1, k2 = jax.random.split(key)
        winit = self._winit()
        return {
            "W": winit(k1, (self.n_in, h), dtype),
            "RW": winit(k2, (h, h), dtype),
            "b": jnp.zeros((h,), dtype),
        }, {}

    def initial_carry(self, batch: int, dtype=jnp.float32):
        return {"h": jnp.zeros((batch, self.n_out), dtype)}

    def apply(self, params, x, *, state=None, train=False, rng=None, mask=None):
        x = self._maybe_dropout(x, train, rng)
        B, T, _ = x.shape
        carry = state if state and "h" in state else self.initial_carry(B, x.dtype)
        xw = (x.reshape(B * T, -1) @ params["W"] + params["b"]).reshape(B, T, -1)
        xw = xw.transpose(1, 0, 2)
        m = (mask.astype(x.dtype).T if mask is not None
             else jnp.ones((T, B), x.dtype))

        def step(c, inp):
            xw_t, m_t = inp
            h = self._act(xw_t + c["h"] @ params["RW"])
            h = _mask_carry(h, c["h"], m_t)
            return {"h": h}, h

        carry, hs = lax.scan(step, carry, (xw, m))
        return hs.transpose(1, 0, 2), carry


@register_layer
@dataclasses.dataclass(frozen=True)
class Bidirectional(Layer):
    """Bidirectional wrapper over any recurrent layer; merge modes CONCAT /
    ADD / MUL / AVERAGE (reference: GravesBidirectionalLSTM merges and the
    later Bidirectional wrapper)."""

    layer: Optional[Any] = None
    merge: str = "concat"
    # False = emit only the final state of each direction, merged (Keras
    # Bidirectional(..., return_sequences=False)): forward's last step with
    # backward's FULL-sequence state (which aligns with t=0) — NOT the last
    # timestep of the re-flipped backward output.
    return_sequences: bool = True

    def infer_n_in(self, input_type: InputType):
        return dataclasses.replace(self, layer=self.layer.infer_n_in(input_type))

    def with_defaults(self, **defaults):
        inner = self.layer.with_defaults(**defaults) if self.layer else self.layer
        return dataclasses.replace(super().with_defaults(**defaults), layer=inner)

    def output_type(self, input_type: InputType) -> InputType:
        inner = self.layer.output_type(input_type)
        size = inner.size * 2 if self.merge == "concat" else inner.size
        if not self.return_sequences:
            return InputType.feed_forward(size)
        return InputType.recurrent(size, inner.timesteps)

    def init_params(self, key, input_type, dtype=jnp.float32):
        kf, kb = jax.random.split(key)
        pf, sf = self.layer.init_params(kf, input_type, dtype)
        pb, sb = self.layer.init_params(kb, input_type, dtype)
        return {"fwd": pf, "bwd": pb}, {}

    def apply(self, params, x, *, state=None, train=False, rng=None, mask=None):
        rf = rb = None
        if rng is not None:
            rf, rb = jax.random.split(rng)
        yf, _ = self.layer.apply(params["fwd"], x, train=train, rng=rf, mask=mask)
        xr = jnp.flip(x, axis=1)
        mr = None if mask is None else jnp.flip(mask, axis=1)
        yb, _ = self.layer.apply(params["bwd"], xr, train=train, rng=rb, mask=mr)
        if not self.return_sequences:
            # Forward: last unmasked step. Backward: its own final scan step
            # (reversed time puts right-padding first, where the mask carries
            # the initial state through, so index -1 is the full-seq state).
            if mask is None:
                hf = yf[:, -1, :]
            else:
                idx = jnp.maximum(
                    jnp.sum(mask, axis=1).astype(jnp.int32) - 1, 0)
                hf = jnp.take_along_axis(
                    yf, idx[:, None, None], axis=1)[:, 0, :]
            hb = yb[:, -1, :]
            return self._merge(hf, hb), state
        yb = jnp.flip(yb, axis=1)
        return self._merge(yf, yb), state

    def _merge(self, yf, yb):
        if self.merge == "concat":
            return jnp.concatenate([yf, yb], axis=-1)
        if self.merge == "add":
            return yf + yb
        if self.merge == "mul":
            return yf * yb
        if self.merge in ("ave", "average"):
            return 0.5 * (yf + yb)
        raise ValueError(f"Unknown merge {self.merge!r}")


@register_layer
@dataclasses.dataclass(frozen=True)
class GravesBidirectionalLSTM(Layer):
    """Reference: `nn/layers/recurrent/GravesBidirectionalLSTM.java` —
    bidirectional peephole LSTM with concatenated fwd/bwd activations,
    implemented here as Bidirectional(GravesLSTM, merge=concat)."""

    n_in: Optional[int] = None
    n_out: Optional[int] = None

    def _inner(self) -> Bidirectional:
        return Bidirectional(
            layer=GravesLSTM(
                n_in=self.n_in, n_out=self.n_out,
                activation=self.activation, weight_init=self.weight_init,
            ),
            merge="concat",
        )

    def infer_n_in(self, input_type: InputType):
        if self.n_in is None:
            return dataclasses.replace(self, n_in=input_type.size)
        return self

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.recurrent(self.n_out * 2, input_type.timesteps)

    def init_params(self, key, input_type, dtype=jnp.float32):
        return self._inner().init_params(key, input_type, dtype)

    def apply(self, params, x, *, state=None, train=False, rng=None, mask=None):
        return self._inner().apply(params, x, state=state, train=train, rng=rng, mask=mask)


@register_layer
@dataclasses.dataclass(frozen=True)
class RnnOutputLayer(OutputLayer):
    """Per-timestep dense + loss over time. Reference:
    `nn/conf/layers/RnnOutputLayer.java` (3-D in/out, time-distributed W·x+b,
    masked loss).

    `tied_to` names a layer of the same `MultiLayerNetwork` (its name, or
    its index) whose `W` [n_out, n_in] this head reads transposed (a tied
    embedding): the head then has no `W` of its own, the net hands it the
    other layer's, and that one leaf's gradient is the sum of both uses."""

    tied_to: Optional[Any] = None

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.recurrent(self.n_out, input_type.timesteps)

    def init_params(self, key, input_type, dtype=jnp.float32):
        if self.tied_to is None:
            return super().init_params(key, input_type, dtype)
        if not self.has_bias:
            return {}, {}
        return {"b": jnp.full((self.n_out,), self.bias_init or 0.0,
                              dtype)}, {}

    def infer_n_in(self, input_type: InputType):
        if self.n_in is None:
            return dataclasses.replace(self, n_in=input_type.size)
        return self

    def pre_output(self, params: Params, x):
        y = x @ params["W"]  # [B,T,nIn]@[nIn,nOut] batches on the MXU
        if self.has_bias:
            y = y + params["b"]
        return y

    def score(self, params, x, labels, mask=None):
        preout = self.pre_output(params, x)  # [B, T, nOut]
        return LossFunction.get(self.loss)(labels, preout, self.activation, mask)


@register_layer
@dataclasses.dataclass(frozen=True)
class ExitGatedOutputLayer(Layer):
    """The head of a looped model (`LoopedStack`): ONE matrix `W`
    [n_in, n_out] scores the state of every pass, a one-column gate
    (`gate_W` [n_in], `gate_b` [1]) says after each pass but the last with
    what probability a token leaves there, and the loss is the expectation
    of the passes' cross-entropies under that exit distribution, less
    `beta` times its entropy:

        lam_t = sigmoid(h_t . gate_W + gate_b),  t < passes
        p_t   = lam_t prod_{s<t} (1 - lam_s),    p_passes = prod (1 - lam_s)
        loss  = mean_i (sum_t p_t[i] ce_t[i] + beta sum_t p_t[i] log p_t[i])

    Its input is the loop layer's output `[passes, B, T, n_in]` (`[B, T,
    n_in]` where `passes` is 1, and the score is then `RnnOutputLayer`'s
    to the bit: p_1 = 1, no entropy). The log-softmax, the gate's products
    and the entropy are float32 where the model's dtype is narrower; the
    exits are scored one at a time, each under a checkpoint, so one
    `[B, T, n_out]` tensor is alive at a time, forward and backward. `apply` gives the
    last pass's softmax. Labels and masks as `RnnOutputLayer` takes them
    (`loss` "sparse_mcxent" with integer labels, or "mcxent"). State: the
    last step's `exit_mass` (the mean of `p_t` over the tokens, `[passes]`)
    and `exit_entropy` (the mean of the distribution's entropy), which
    `fit()` publishes as gauges where an epoch synchronises."""

    CONSUMES = "rnn"

    n_in: Optional[int] = None
    n_out: Optional[int] = None
    passes: int = 1
    beta: float = 0.1
    loss: Any = "sparse_mcxent"

    @property
    def is_output_layer(self) -> bool:
        return True

    def infer_n_in(self, input_type: InputType):
        if self.n_in is None:
            return dataclasses.replace(self, n_in=input_type.size)
        return self

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.recurrent(self.n_out, input_type.timesteps)

    def init_params(self, key, input_type, dtype=jnp.float32):
        if str(self.loss).lower() not in ("sparse_mcxent", "mcxent"):
            raise ValueError(f"{self.name}: the exits are scored by "
                             f"sparse_mcxent or mcxent, not {self.loss!r}")
        if self.passes < 1:
            raise ValueError(f"{self.name}: {self.passes} passes")
        kw, kg = jax.random.split(key)
        winit = self._winit()
        return ({"W": winit(kw, (self.n_in, self.n_out), dtype),
                 "gate_W": winit(kg, (self.n_in, 1), dtype)[:, 0],
                 "gate_b": jnp.zeros((1,), dtype)},
                {"exit_mass": jnp.zeros((self.passes,), jnp.float32),
                 "exit_entropy": jnp.zeros((), jnp.float32)})

    def _states(self, x):
        """`[passes, B, T, n_in]` of the loop layer's output."""
        if self.passes == 1 and x.ndim == 3:
            x = x[None]
        if x.ndim != 4 or x.shape[0] != self.passes:
            raise ValueError(f"{self.name}: {self.passes} passes scored, "
                             f"the input is {x.shape}")
        return x

    def exit_distribution(self, params, x):
        """(log p, p), each `[passes, B, T]` and float32 (or wider, where
        the states are), from the states of all passes."""
        f32 = jnp.promote_types(x.dtype, jnp.float32)
        with jax.named_scope("exit_gate"):
            z = (jnp.sum(x[:-1].astype(f32) * params["gate_W"].astype(f32),
                         axis=-1) + params["gate_b"].astype(f32))
            last = jnp.zeros((1,) + x.shape[1:3], f32)
            # log of: still in after passes 1..t-1, then out at t
            stayed = jnp.concatenate(
                [last, jnp.cumsum(jax.nn.log_sigmoid(-z), axis=0)])
            logp = stayed + jnp.concatenate([jax.nn.log_sigmoid(z), last])
            return logp, jnp.exp(logp)

    def _exit_losses(self, params, x, labels):
        """Every exit's per-token cross-entropy, `[passes, B, T]`."""
        sparse = str(self.loss).lower() == "sparse_mcxent"

        @jax.checkpoint
        def one(h):
            logits = h @ params["W"]
            logp = jax.nn.log_softmax(logits.astype(jnp.promote_types(
                logits.dtype, jnp.float32)), axis=-1)
            if sparse:
                return -jnp.take_along_axis(
                    logp, labels[..., None].astype(jnp.int32), axis=-1)[..., 0]
            return -jnp.sum(labels * logp, axis=-1)

        with jax.named_scope("exit_loss"):
            return lax.map(one, x)

    def score_and_state(self, params, x, labels, state, mask=None):
        x = self._states(x)
        logp, p = self.exit_distribution(params, x)
        ce = self._exit_losses(params, x, labels)
        entropy = -jnp.sum(p * logp, axis=0)
        score = _reduce(jnp.sum(p * ce, axis=0) - self.beta * entropy, mask)
        return score, {
            "exit_mass": jax.vmap(lambda p_t: _reduce(p_t, mask))(p),
            "exit_entropy": _reduce(entropy, mask)}

    def score(self, params, x, labels, mask=None):
        return self.score_and_state(params, x, labels, None, mask)[0]

    def apply(self, params, x, *, state=None, train=False, rng=None,
              mask=None):
        return self._act(self._states(x)[-1] @ params["W"]), state


@register_layer
@dataclasses.dataclass(frozen=True)
class MultiTokenOutputLayer(Layer):
    """The head of a model with a multi-token-prediction (MTP) module, as
    DeepSeek-V3 trains one (arXiv:2412.19437, section 2.2): beside the main
    head's next-token loss a second term scores the token after the next,
    from the trunk's state and the NEXT token's embedding, teacher-forced.
    With `h_t` the layer's input (the trunk's output BEFORE its last norm,
    which is this layer's `norm_f`), `y` the labels, `e` the embedding
    layer's table (`tied_to`: that layer's `W`, read as it lies, a lookup
    of the labels; one leaf in the tree, read twice) and every norm RMS
    with a gain:

        logits_t  = norm_f(h_t) W
        g_t       = [norm_h(h_t) ; norm_e(e[y_t])] W_eh        2 d -> d
        g         = the module's `layers` over g, in turn, each given whole
                    (`PreNormSublayer`s: an attention, an expert layer)
        logits2_t = norm_mtp(g_t) W                the main head's own W
        loss = ce(logits, y) + mtp_weight x ce(logits2_t, y_{t+1}), t < T - 1

    The labels are the ids shifted by one, so `e[y_t]` is the next token's
    embedding and `y_{t+1}` the target two ahead; the last position has
    no second target and weighs nothing (it is computed and multiplied by
    zero: every shape stays whole). Both heads are scored one at a time,
    each under a checkpoint, log-softmax in float32, so one `[B, T, n_out]`
    tensor is alive at a time, forward and backward; with `remat` each of
    the module's layers is a checkpoint of its own too (the policy of
    `gradient_checkpointing`: a kernel's named residuals stay). Leaves:
    `norm_f`, `W` [n_in, n_out], `mtp_norm_h`, `mtp_norm_e`, `mtp_eh_proj`
    [2 n_in, n_in], `mtp_norm`, and `mtp_layer<i>_<leaf>` for the module's
    layers. State: the last step's `main_loss` and `mtp_loss` (each term
    before its weight) and what the module's layers wrote in that step
    (an expert layer's routing counters; the layers are handed no state:
    nothing of it is read in training, and what comes back is the step's
    own), which `fit()` publishes as gauges where an epoch synchronises.
    Scope `mtp` round the module, its head pass and its cross-entropy.
    `apply` gives the main head's softmax; labels are integers
    (`sparse_mcxent`). Training and scoring only: at inference the module
    would draft a second token, which serving does not do."""

    CONSUMES = "rnn"
    # `models/multilayer.py` hands the `tied_to` layer's W under this key,
    # transposed or (here) as it lies
    TIED_AS = ("embedding", False)
    # the score's own state: each term before its weight
    LOSS_STATE = ("main_loss", "mtp_loss")

    n_in: Optional[int] = None
    n_out: Optional[int] = None
    tied_to: Optional[Any] = None       # the embedding layer
    layers: Tuple[Any, ...] = ()        # the MTP module's, in order
    mtp_weight: float = 0.1
    eps: float = 1e-5
    remat: bool = False
    loss: Any = "sparse_mcxent"

    @property
    def is_output_layer(self) -> bool:
        return True

    infer_n_in = ExitGatedOutputLayer.infer_n_in
    output_type = ExitGatedOutputLayer.output_type

    def _layers(self):
        return [dataclasses.replace(
            l, n_in=self.n_in, weight_init=l.weight_init or self.weight_init,
            name=l.name or f"{self.name}.mtp_layer{i}")
            for i, l in enumerate(self.layers)]

    def init_params(self, key, input_type, dtype=jnp.float32):
        if str(self.loss).lower() != "sparse_mcxent":
            raise ValueError(f"{self.name}: the module looks the labels up "
                             f"as ids: sparse_mcxent, not {self.loss!r}")
        if self.tied_to is None:
            raise ValueError(f"{self.name}: tied_to names the embedding "
                             f"layer whose table the module reads")
        d = self.n_in
        kw, ke, *kl = jax.random.split(key, 2 + len(self.layers))
        winit = self._winit()
        one = lambda: jnp.ones((d,), dtype)
        params = {"norm_f": one(), "W": winit(kw, (d, self.n_out), dtype),
                  "mtp_norm_h": one(), "mtp_norm_e": one(),
                  "mtp_eh_proj": winit(ke, (2 * d, d), dtype),
                  "mtp_norm": one()}
        state = dict.fromkeys(self.LOSS_STATE, jnp.zeros((), jnp.float32))
        for i, (layer, k) in enumerate(zip(self._layers(), kl)):
            lp, st = layer.init_params(k, input_type, dtype)
            if set(st) & set(state):
                raise ValueError(f"{self.name}: two of the module's layers "
                                 f"keep {sorted(set(st) & set(state))}")
            params.update({f"mtp_layer{i}_{n}": v for n, v in lp.items()})
            state.update(st)
        return params, state

    def decode_carry(self, batch: int, dtype=jnp.float32, **kw):
        raise NotImplementedError(
            f"MultiTokenOutputLayer {self.name!r} has no decode carry: its "
            f"module reads the NEXT token's embedding, which at inference "
            f"is a draft to verify (speculative decoding from the model's "
            f"own second head), and serving does not have that")

    def _ce(self, params, h, gain, targets):
        """Per-token cross-entropy `[B, T]` (float32) of `norm(h; gain) W`
        against `targets`, nothing of `[B, T, n_out]` kept."""
        @jax.checkpoint
        def one(h, gain, w, targets):
            logits = rms_norm(h, gain, self.eps) @ w
            logp = jax.nn.log_softmax(logits.astype(jnp.promote_types(
                logits.dtype, jnp.float32)), axis=-1)
            return -jnp.take_along_axis(
                logp, targets[..., None].astype(jnp.int32), axis=-1)[..., 0]

        return one(h, gain, params["W"], targets)

    def _module(self, params, x, labels):
        """(g [B, T, d], what the layers wrote as state) of the MTP
        module."""
        from deeplearning4j_tpu.ops.attention import KEPT_NAMES
        from deeplearning4j_tpu.ops.embedding import lookup

        e = lookup(params["embedding"], labels.astype(jnp.int32))
        g = jnp.concatenate(
            [rms_norm(x, params["mtp_norm_h"], self.eps),
             rms_norm(e.astype(x.dtype), params["mtp_norm_e"], self.eps)],
            axis=-1) @ params["mtp_eh_proj"]
        states = {}
        for i, layer in enumerate(self._layers()):
            prefix = f"mtp_layer{i}_"
            lp = {k[len(prefix):]: v for k, v in params.items()
                  if k.startswith(prefix)}
            run = lambda p, g, _l=layer: _l.apply(p, g, train=True)
            if self.remat:
                run = jax.checkpoint(
                    run, policy=jax.checkpoint_policies
                    .save_only_these_names(*KEPT_NAMES))
            with jax.named_scope(f"mtp_layer{i}"):
                g, st = run(lp, g)
            states.update(st or {})
        return g, states

    def score_and_state(self, params, x, labels, state, mask=None):
        if labels.ndim != 2:
            raise ValueError(f"{self.name}: integer labels [batch, time], "
                             f"not {labels.shape}")
        main = _reduce(self._ce(params, x, params["norm_f"], labels), mask)
        with jax.named_scope("mtp"):
            g, states = self._module(params, x, labels)
            # position t scores y_{t+1}; the last has none (its target
            # wraps round and weighs nothing)
            ahead = jnp.roll(labels, -1, axis=1)
            live = jnp.broadcast_to(
                jnp.arange(labels.shape[1]) < labels.shape[1] - 1,
                labels.shape).astype(jnp.float32)
            if mask is not None:    # both targets must be real
                m = jnp.broadcast_to(mask, labels.shape).astype(jnp.float32)
                live = live * m * jnp.roll(m, -1, axis=1)
            mtp = _reduce(self._ce(params, g, params["mtp_norm"], ahead),
                          live)
        score = main + self.mtp_weight * mtp
        return score, {**states, "main_loss": main.astype(jnp.float32),
                       "mtp_loss": mtp.astype(jnp.float32)}

    def score(self, params, x, labels, mask=None):
        return self.score_and_state(params, x, labels, None, mask)[0]

    def apply(self, params, x, *, state=None, train=False, rng=None,
              mask=None):
        return self._act(rms_norm(x, params["norm_f"], self.eps)
                         @ params["W"]), state


@register_layer
@dataclasses.dataclass(frozen=True)
class LastTimeStep(Layer):
    """Wrapper: emit only the last (unmasked) timestep of an RNN layer.
    Reference: `nn/conf/layers/recurrent/LastTimeStep` vertex/wrapper."""

    layer: Optional[Any] = None

    def infer_n_in(self, input_type: InputType):
        return dataclasses.replace(self, layer=self.layer.infer_n_in(input_type))

    def with_defaults(self, **defaults):
        inner = self.layer.with_defaults(**defaults) if self.layer else self.layer
        return dataclasses.replace(super().with_defaults(**defaults), layer=inner)

    def output_type(self, input_type: InputType) -> InputType:
        inner = self.layer.output_type(input_type)
        return InputType.feed_forward(inner.size)

    def init_params(self, key, input_type, dtype=jnp.float32):
        return self.layer.init_params(key, input_type, dtype)

    def apply(self, params, x, *, state=None, train=False, rng=None, mask=None):
        y, st = self.layer.apply(params, x, state=state, train=train, rng=rng, mask=mask)
        if mask is None:
            return y[:, -1, :], st
        idx = jnp.maximum(jnp.sum(mask, axis=1).astype(jnp.int32) - 1, 0)  # [B]
        return jnp.take_along_axis(y, idx[:, None, None], axis=1)[:, 0, :], st
