"""Network configuration DSL — config-as-data with a fluent builder.

Reference parity: `nn/conf/NeuralNetConfiguration.java:515` (Builder),
`.list():686` → `MultiLayerConfiguration`, `.graphBuilder():717` →
`ComputationGraphConfiguration`. Global defaults (activation, weightInit,
updater, l1/l2, dropout, seed — reference `:728-854`) cascade into every layer
config that didn't set its own, exactly as the reference clones the base conf
per layer. The built configuration is a frozen dataclass that JSON round-trips
(`to_json`/`from_json`), mirroring the reference's Jackson serde
(`MultiLayerConfiguration.toJson`).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

from deeplearning4j_tpu.nn.inputs import InputType
from deeplearning4j_tpu.nn.layers.base import Layer
from deeplearning4j_tpu.nn.preprocessors import Preprocessor, auto_preprocessor
from deeplearning4j_tpu.optim.updaters import Updater, resolve_updater, Sgd
from deeplearning4j_tpu.utils.serde import register_serde, to_json, from_json


class GradientNormalization:
    """Reference: `nn/conf/GradientNormalization.java` enum."""

    NONE = "none"
    RENORMALIZE_L2_PER_LAYER = "renormalize_l2_per_layer"
    RENORMALIZE_L2_PER_PARAM_TYPE = "renormalize_l2_per_param_type"
    CLIP_ELEMENTWISE_ABSOLUTE_VALUE = "clip_elementwise_absolute_value"
    CLIP_L2_PER_LAYER = "clip_l2_per_layer"
    CLIP_L2_PER_PARAM_TYPE = "clip_l2_per_param_type"


@register_serde
@dataclasses.dataclass(frozen=True)
class MultiLayerConfiguration:
    """Finalized sequential-network config. Reference:
    `nn/conf/MultiLayerConfiguration.java`."""

    layers: Tuple[Layer, ...] = ()
    input_type: Optional[InputType] = None
    preprocessors: Dict[int, Preprocessor] = dataclasses.field(default_factory=dict)
    seed: int = 12345
    updater: Any = None
    dtype: str = "float32"
    gradient_normalization: str = "none"
    gradient_normalization_threshold: float = 1.0
    mini_batch: bool = True
    # remat every layer's activations in the backward pass — trades
    # ~33% more FLOPs for O(depth) less activation memory (the
    # jax.checkpoint lever for deep nets / long context; TPU-native
    # extension, no reference counterpart); what stays on the device:
    # `Builder.gradient_checkpointing`
    gradient_checkpointing: bool = False
    tbptt_fwd_length: int = 0       # 0 = no truncated BPTT
    tbptt_back_length: int = 0
    backprop: bool = True
    pretrain: bool = False
    # Reference: OptimizationAlgorithm enum (`optimizationAlgo:746`) —
    # stochastic_gradient_descent | conjugate_gradient | lbfgs |
    # line_gradient_descent. Non-SGD algorithms run `solver_iterations`
    # full-batch solver steps per fit batch (optim/solvers.py).
    optimization_algo: str = "stochastic_gradient_descent"
    solver_iterations: int = 100

    def to_json(self) -> str:
        return to_json(self)

    @staticmethod
    def from_json(s: str) -> "MultiLayerConfiguration":
        conf = from_json(s)
        # JSON dict keys are strings; restore int preprocessor indices.
        pp = {int(k): v for k, v in conf.preprocessors.items()}
        return dataclasses.replace(
            conf, layers=tuple(conf.layers), preprocessors=pp
        )

    def layer_names(self) -> List[str]:
        return [l.name for l in self.layers]


class NeuralNetConfiguration:
    """Entry point: `NeuralNetConfiguration.builder()` (reference `:515`)."""

    @staticmethod
    def builder() -> "Builder":
        return Builder()


class Builder:
    """Fluent builder holding global defaults; `.list(...)` produces a
    ListBuilder (reference `.list():686`), `.graph_builder()` a
    GraphBuilder (reference `.graphBuilder():717`)."""

    def __init__(self):
        self._seed = 12345
        self._activation: Optional[str] = None
        self._weight_init: Optional[str] = None
        self._updater: Any = None
        self._learning_rate: Any = None
        self._l1: Optional[float] = None
        self._l2: Optional[float] = None
        self._dropout: Optional[float] = None
        self._dtype: str = "float32"
        self._grad_norm: str = "none"
        self._grad_norm_threshold: float = 1.0
        self._mini_batch = True
        self._grad_ckpt = False
        self._opt_algo = "stochastic_gradient_descent"
        self._solver_iterations = 100

    # -- fluent setters (names mirror the reference builder methods) --
    def seed(self, s: int) -> "Builder":
        self._seed = int(s)
        return self

    def activation(self, a) -> "Builder":
        self._activation = a
        return self

    def weight_init(self, w) -> "Builder":
        self._weight_init = w
        return self

    def updater(self, u) -> "Builder":
        self._updater = resolve_updater(u)
        return self

    def learning_rate(self, lr) -> "Builder":
        self._learning_rate = lr
        return self

    def l1(self, v: float) -> "Builder":
        self._l1 = v
        return self

    def l2(self, v: float) -> "Builder":
        self._l2 = v
        return self

    def dropout(self, p: float) -> "Builder":
        self._dropout = p
        return self

    def dtype(self, d: str) -> "Builder":
        self._dtype = d
        return self

    def gradient_normalization(self, mode: str, threshold: float = 1.0) -> "Builder":
        self._grad_norm = mode
        self._grad_norm_threshold = threshold
        return self

    def mini_batch(self, v: bool) -> "Builder":
        self._mini_batch = v
        return self

    def gradient_checkpointing(self, v: bool = True) -> "Builder":
        """Rematerialize layer activations in the backward pass
        (jax.checkpoint per layer/vertex) — memory for FLOPs. What stays
        on the device between the passes is each layer's input and what
        the layers name with `ops/attention.KEPT_NAMES`: of a Pallas
        attention kernel its output and its rows' log-sum-exp (one
        hidden-sized tensor and T floats a head), so the recomputed
        forward does not run that kernel a second time; and of a block
        what its recomputation would remake only to read it again, so
        that the product which made it is left out: two `[B, T, d]`
        tensors a `SandwichTransformerBlock` (the outputs of both
        halves: 2 x B x T x d x the dtype's bytes a block), one a
        `PreNormBlock` (the stream between its halves), and a sparse
        `MultiHeadAttention`'s block selection (B x KV heads x T x
        blocks bytes). A model that checkpoints for memory pays those on
        top of the layers' inputs; the gradients are the same numbers."""
        self._grad_ckpt = v
        return self

    def optimization_algo(self, algo: str,
                          iterations: Optional[int] = None) -> "Builder":
        """Reference: `optimizationAlgo(OptimizationAlgorithm...)`:746.
        Accepts reference enum-style or snake_case names."""
        algo = str(algo).lower()
        aliases = {
            "sgd": "stochastic_gradient_descent",
            "cg": "conjugate_gradient",
        }
        algo = aliases.get(algo, algo)
        known = {"stochastic_gradient_descent", "conjugate_gradient",
                 "lbfgs", "line_gradient_descent"}
        if algo not in known:
            raise ValueError(
                f"Unknown optimization algorithm {algo!r}; known: "
                f"{sorted(known)}")
        self._opt_algo = algo
        if iterations is not None:
            self._solver_iterations = int(iterations)
        return self

    # -- terminals --
    def list(self, *layers: Layer) -> "ListBuilder":
        if len(layers) == 1 and isinstance(layers[0], (list, tuple)):
            layers = tuple(layers[0])
        return ListBuilder(self, list(layers))

    def graph_builder(self):
        from deeplearning4j_tpu.nn.graph import GraphBuilder  # noqa: PLC0415

        return GraphBuilder(self)  # ComputationGraph DSL (nn/graph.py)

    # -- internals shared with graph builder --
    def _defaults(self) -> Dict[str, Any]:
        upd = self._updater
        if upd is None:
            upd = Sgd(self._learning_rate if self._learning_rate is not None else 1e-2)
        elif self._learning_rate is not None and hasattr(upd, "learning_rate"):
            upd = dataclasses.replace(upd, learning_rate=self._learning_rate)
        return dict(
            activation=self._activation,
            weight_init=self._weight_init or "xavier",
            updater=upd,
            l1=self._l1,
            l2=self._l2,
            dropout=self._dropout,
        )


class ListBuilder:
    """Reference: `NeuralNetConfiguration.ListBuilder` — collects layers,
    wires shapes/preprocessors, and builds a MultiLayerConfiguration."""

    def __init__(self, base: Builder, layers: List[Layer]):
        self._base = base
        self._layers = layers
        self._input_type: Optional[InputType] = None
        self._preprocessors: Dict[int, Preprocessor] = {}
        self._tbptt_fwd = 0
        self._tbptt_back = 0
        self._pretrain = False
        self._backprop = True

    def layer(self, l: Layer) -> "ListBuilder":
        self._layers.append(l)
        return self

    def set_input_type(self, it: InputType) -> "ListBuilder":
        self._input_type = it
        return self

    def input_preprocessor(self, idx: int, pp: Preprocessor) -> "ListBuilder":
        self._preprocessors[idx] = pp
        return self

    def tbptt(self, fwd_length: int, back_length: Optional[int] = None) -> "ListBuilder":
        """Truncated BPTT lengths (reference: `tBPTTForwardLength` etc.)."""
        self._tbptt_fwd = fwd_length
        self._tbptt_back = back_length if back_length is not None else fwd_length
        return self

    def pretrain(self, v: bool) -> "ListBuilder":
        self._pretrain = v
        return self

    def backprop(self, v: bool) -> "ListBuilder":
        self._backprop = v
        return self

    def build(self) -> MultiLayerConfiguration:
        if (self._base._opt_algo != "stochastic_gradient_descent"
                and self._tbptt_fwd > 0):
            raise ValueError(
                "Truncated BPTT is only supported with "
                "stochastic_gradient_descent; full-batch solvers "
                f"({self._base._opt_algo}) cannot carry tBPTT state")
        defaults = self._base._defaults()
        layers: List[Layer] = []
        preprocessors = dict(self._preprocessors)
        cur = self._input_type

        for i, layer in enumerate(self._layers):
            layer = layer.with_defaults(**defaults)
            if layer.name is None:
                layer = dataclasses.replace(
                    layer, name=f"layer{i}_{type(layer).__name__.lower()}"
                )
            _validate_layer(layer, i)
            if cur is not None:
                # auto-insert preprocessor on family transitions
                if i not in preprocessors:
                    pp = auto_preprocessor(cur, _expected_kind(layer, cur))
                    if pp is not None:
                        preprocessors[i] = pp
                if i in preprocessors:
                    cur = preprocessors[i].output_type(cur)
                layer = layer.infer_n_in(cur)
                cur = layer.output_type(cur)
            else:
                # No input type declared: propagate from layers with explicit
                # dims (reference allows nIn-explicit configs without
                # setInputType).
                try:
                    cur = layer.output_type(cur)
                except Exception:
                    cur = None
            layers.append(layer)

        return MultiLayerConfiguration(
            layers=tuple(layers),
            input_type=self._input_type,
            preprocessors=preprocessors,
            seed=self._base._seed,
            updater=defaults["updater"],
            dtype=self._base._dtype,
            gradient_normalization=self._base._grad_norm,
            gradient_normalization_threshold=self._base._grad_norm_threshold,
            mini_batch=self._base._mini_batch,
            gradient_checkpointing=self._base._grad_ckpt,
            tbptt_fwd_length=self._tbptt_fwd,
            tbptt_back_length=self._tbptt_back,
            backprop=self._backprop,
            pretrain=self._pretrain,
            optimization_algo=self._base._opt_algo,
            solver_iterations=self._base._solver_iterations,
        )


def _validate_layer(layer: Layer, idx: int) -> None:
    """Fail fast at build() on unresolvable names (the reference validates
    in the builder too), instead of at first forward trace."""
    from deeplearning4j_tpu.nn.activations import Activation
    from deeplearning4j_tpu.nn.initializers import WeightInit
    from deeplearning4j_tpu.nn.losses import LossFunction

    try:
        Activation.get(layer.activation)
        WeightInit.get(layer.weight_init)
        if hasattr(layer, "loss"):
            LossFunction.get(layer.loss)
    except ValueError as e:
        raise ValueError(f"layer {idx} ({layer.name}): {e}") from None


def _expected_kind(layer: Layer, cur: InputType) -> str:
    """What input family does this layer consume? Drives preprocessor
    auto-insertion (reference: per-layer getPreProcessorForInputType)."""
    from deeplearning4j_tpu.nn.layers import convolution as conv_mod
    from deeplearning4j_tpu.nn.layers import recurrent as rnn_mod
    from deeplearning4j_tpu.nn.layers.normalization import (
        BatchNormalization, LocalResponseNormalization,
    )
    from deeplearning4j_tpu.nn.layers.pooling import GlobalPoolingLayer

    cnn_types = (
        conv_mod.ConvolutionLayer, conv_mod.SubsamplingLayer,
        conv_mod.ZeroPaddingLayer, conv_mod.Upsampling2DLayer,
        conv_mod.Cropping2DLayer, conv_mod.DepthwiseConvolution2DLayer,
        conv_mod.SeparableConvolution2DLayer,
    )
    from deeplearning4j_tpu.nn.layers.attention import MultiHeadAttention

    rnn_types = (
        rnn_mod.BaseRecurrentLayer, rnn_mod.Bidirectional,
        rnn_mod.GravesBidirectionalLSTM, rnn_mod.RnnOutputLayer,
        rnn_mod.LastTimeStep, conv_mod.Convolution1DLayer,
        conv_mod.Subsampling1DLayer, MultiHeadAttention,
    )
    # Layers that declare their input family explicitly ("any" = shape-
    # preserving, consume whatever arrives) bypass the type tables.
    declared = getattr(layer, "CONSUMES", None)
    if declared == "any":
        return cur.kind
    if declared is not None:
        return declared
    if isinstance(layer, cnn_types):
        return "cnn"
    if isinstance(layer, rnn_types):
        return "rnn"
    if isinstance(layer, (BatchNormalization, LocalResponseNormalization,
                          GlobalPoolingLayer)):
        return cur.kind  # shape-preserving: consume whatever arrives
    return "ff"
