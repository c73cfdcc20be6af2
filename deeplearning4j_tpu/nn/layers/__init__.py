"""Layer configs + implementations (config IS the layer; pure-function apply).

Parity surface: reference nn/conf/layers/* (declarative configs) fused with
nn/layers/** (imperative impls). In this framework a layer is one dataclass:
hyperparameters are fields, ``init`` builds a params pytree, ``apply`` is a
pure function, and the backward pass is ``jax.grad`` of the container loss.
"""

from deeplearning4j_tpu.nn.layers.base import Layer, LAYER_REGISTRY, layer_from_dict
from deeplearning4j_tpu.nn.layers.core import (
    DenseLayer, OutputLayer, LossLayer, ActivationLayer, DropoutLayer,
    EmbeddingLayer, EmbeddingSequenceLayer, PReLULayer,
    ElementWiseMultiplicationLayer, ReshapeLayer, FlattenLayer,
)
from deeplearning4j_tpu.nn.layers.conv import (
    ConvolutionLayer, Convolution1DLayer, SeparableConvolution2D,
    DepthwiseConvolution2D, Deconvolution2D, SubsamplingLayer,
    Subsampling1DLayer, Upsampling1D, Upsampling2D, ZeroPaddingLayer,
    ZeroPadding1DLayer, Cropping2D, BatchNormalization,
    LocalResponseNormalization, SpaceToDepthLayer, SpaceToBatchLayer,
)
from deeplearning4j_tpu.nn.layers.rnn import (
    LSTM, GravesLSTM, GravesBidirectionalLSTM, SimpleRnn, Bidirectional,
    RnnOutputLayer, RnnLossLayer, LastTimeStep,
)
from deeplearning4j_tpu.nn.layers.special import (
    GlobalPoolingLayer, AutoEncoder, VariationalAutoencoder,
    CenterLossOutputLayer, Yolo2OutputLayer, FrozenLayer,
)
from deeplearning4j_tpu.nn.layers.attention import (
    MultiHeadAttention, LayerNormalization, PositionalEmbedding,
)
from deeplearning4j_tpu.nn.layers.decoder import (
    RMSNorm, SwiGLU, RotaryGQAttention, ExpertLayer,
)
from deeplearning4j_tpu.nn.layers.ssm import Mamba2Mixer
from deeplearning4j_tpu.nn.layers.pretrain import RBM

__all__ = [
    "Layer", "LAYER_REGISTRY", "layer_from_dict",
    "DenseLayer", "OutputLayer", "LossLayer", "ActivationLayer", "DropoutLayer",
    "EmbeddingLayer", "EmbeddingSequenceLayer", "PReLULayer",
    "ElementWiseMultiplicationLayer", "ReshapeLayer", "FlattenLayer",
    "ConvolutionLayer", "Convolution1DLayer", "SeparableConvolution2D",
    "DepthwiseConvolution2D", "Deconvolution2D", "SubsamplingLayer",
    "Subsampling1DLayer", "Upsampling1D", "Upsampling2D", "ZeroPaddingLayer",
    "ZeroPadding1DLayer", "Cropping2D", "BatchNormalization",
    "LocalResponseNormalization", "SpaceToDepthLayer", "SpaceToBatchLayer",
    "LSTM", "GravesLSTM", "GravesBidirectionalLSTM", "SimpleRnn", "Bidirectional",
    "RnnOutputLayer", "RnnLossLayer", "LastTimeStep",
    "GlobalPoolingLayer", "AutoEncoder", "VariationalAutoencoder",
    "CenterLossOutputLayer", "Yolo2OutputLayer", "FrozenLayer",
    "MultiHeadAttention", "LayerNormalization", "PositionalEmbedding", "RBM",
    "RMSNorm", "SwiGLU", "RotaryGQAttention", "ExpertLayer", "Mamba2Mixer",
]
