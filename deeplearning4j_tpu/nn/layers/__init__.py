"""Layer configs + pure-functional implementations.

Reference parity: `nn/conf/layers/` (declarative configs) + `nn/layers/`
(imperative impls). Here config and implementation are ONE frozen dataclass:
hyperparameters are fields (JSON-serializable), behavior is pure methods
(`init_params`, `apply`, `output_type`) — so a model is data all the way down
and the whole forward pass traces into a single XLA computation.
"""

from deeplearning4j_tpu.nn.layers.base import Layer, LAYER_REGISTRY
from deeplearning4j_tpu.nn.layers.feedforward import (
    DenseLayer, OutputLayer, LossLayer, ActivationLayer, DropoutLayer,
    EmbeddingLayer, EmbeddingSequenceLayer, AutoEncoder, PReLULayer,
)
from deeplearning4j_tpu.nn.layers.convolution import (
    ConvolutionLayer, Convolution1DLayer, SubsamplingLayer, Subsampling1DLayer,
    ZeroPaddingLayer, Upsampling2DLayer, SeparableConvolution2DLayer,
    Deconvolution2DLayer, DepthwiseConvolution2DLayer, Cropping2DLayer,
    FusedConvBNLayer,
    SpaceToDepthLayer,
)
from deeplearning4j_tpu.nn.layers.normalization import (
    BatchNormalization, LocalResponseNormalization, LayerNormalization,
    RMSNormalization,
)
from deeplearning4j_tpu.nn.layers.pooling import GlobalPoolingLayer, PoolingType
from deeplearning4j_tpu.nn.layers.recurrent import (
    LSTM, GravesLSTM, GravesBidirectionalLSTM, SimpleRnn, GRU, RnnOutputLayer,
    Bidirectional, ExitGatedOutputLayer, LastTimeStep,
    MultiTokenOutputLayer,
)
from deeplearning4j_tpu.nn.layers.special import (
    FrozenLayer, CenterLossOutputLayer, LoopedStack, VariationalAutoencoder,
    RBM,
)
from deeplearning4j_tpu.nn.layers.attention import (
    LatentAttention, LinearAttention, MultiHeadAttention, PreNormBlock,
    PreNormSublayer, SandwichTransformerBlock, SelectiveStateSpace,
)

__all__ = [
    "Layer", "LAYER_REGISTRY",
    "DenseLayer", "OutputLayer", "LossLayer", "ActivationLayer", "DropoutLayer",
    "EmbeddingLayer", "EmbeddingSequenceLayer", "AutoEncoder", "PReLULayer",
    "ConvolutionLayer", "Convolution1DLayer", "SubsamplingLayer",
    "Subsampling1DLayer", "ZeroPaddingLayer", "Upsampling2DLayer",
    "SeparableConvolution2DLayer", "Deconvolution2DLayer",
    "DepthwiseConvolution2DLayer", "Cropping2DLayer", "SpaceToDepthLayer",
    "FusedConvBNLayer",
    "BatchNormalization", "LocalResponseNormalization", "LayerNormalization",
    "RMSNormalization",
    "GlobalPoolingLayer", "PoolingType",
    "LSTM", "GravesLSTM", "GravesBidirectionalLSTM", "SimpleRnn", "GRU",
    "RnnOutputLayer", "Bidirectional", "LastTimeStep",
    "FrozenLayer", "CenterLossOutputLayer", "VariationalAutoencoder", "RBM",
    "MultiHeadAttention", "SandwichTransformerBlock", "LinearAttention",
    "LatentAttention", "PreNormBlock", "SelectiveStateSpace",
    "LoopedStack", "ExitGatedOutputLayer", "PreNormSublayer",
    "MultiTokenOutputLayer",
]
