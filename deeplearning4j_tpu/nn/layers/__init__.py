from deeplearning4j_tpu.nn.layers.feedforward import (
    DenseLayer, EmbeddingLayer, ActivationLayer, DropoutLayer,
    OutputLayer, CenterLossOutputLayer, LossLayer, AutoEncoder,
    ElementWiseMultiplicationLayer,
    RepeatVector, PermuteLayer, ReshapeLayer,
)
from deeplearning4j_tpu.nn.layers.convolution import (
    ConvolutionLayer, Convolution1DLayer, SubsamplingLayer,
    Subsampling1DLayer, Upsampling2D, ZeroPaddingLayer, GlobalPoolingLayer,
    Deconvolution2D, SeparableConvolution2D, DepthwiseConvolution2D,
    SpaceToDepthLayer, SpaceToBatchLayer, Cropping2D, CnnLossLayer,
    Cropping1D, Upsampling1D, ZeroPadding1DLayer,
    LocallyConnected1D, LocallyConnected2D,
)
from deeplearning4j_tpu.nn.layers.normalization import (
    BatchNormalization, LocalResponseNormalization,
)
from deeplearning4j_tpu.nn.layers.recurrent import (
    GRU, LSTM, GravesLSTM, GravesBidirectionalLSTM, SimpleRnn, Bidirectional,
    RnnOutputLayer, RnnLossLayer, LastTimeStep, MaskZeroLayer,
)
from deeplearning4j_tpu.nn.layers.variational import VariationalAutoencoder
from deeplearning4j_tpu.nn.layers.samediff import SameDiffLayer, FrozenLayerWrapper
from deeplearning4j_tpu.nn.layers.objdetect import Yolo2OutputLayer
from deeplearning4j_tpu.nn.layers.attention import (
    EmbeddingSequenceLayer, GatedMLP, LayerNormLayer, LightningIndexer,
    LinearProjection, MixerBlock, MoEFeedForward, attach_auxiliary_loss,
    RMSNormLayer, MultiHeadAttention, PositionalEmbeddingLayer,
    TransformerBlock,
)
from deeplearning4j_tpu.nn.layers.linear_attention import (
    GatedShortConv, KimiDeltaAttention, Mamba2Mixer,
    MultiHeadLatentAttention,
)
from deeplearning4j_tpu.nn.layers.hyper_connection import (
    HyperConnectedBlock,
)

__all__ = [
    "DenseLayer", "EmbeddingLayer", "ActivationLayer", "DropoutLayer",
    "OutputLayer", "CenterLossOutputLayer", "LossLayer", "AutoEncoder",
    "ElementWiseMultiplicationLayer",
    "RepeatVector", "PermuteLayer", "ReshapeLayer",
    "ConvolutionLayer", "Convolution1DLayer", "SubsamplingLayer",
    "Subsampling1DLayer", "Upsampling2D", "ZeroPaddingLayer",
    "GlobalPoolingLayer", "Deconvolution2D", "SeparableConvolution2D",
    "DepthwiseConvolution2D", "SpaceToDepthLayer", "SpaceToBatchLayer",
    "Cropping2D", "CnnLossLayer",
    "Cropping1D", "Upsampling1D", "ZeroPadding1DLayer",
    "LocallyConnected1D", "LocallyConnected2D",
    "BatchNormalization", "LocalResponseNormalization",
    "GRU", "LSTM", "GravesLSTM", "GravesBidirectionalLSTM", "SimpleRnn",
    "Bidirectional", "RnnOutputLayer", "RnnLossLayer", "LastTimeStep",
    "MaskZeroLayer", "VariationalAutoencoder", "SameDiffLayer",
    "FrozenLayerWrapper", "Yolo2OutputLayer",
    "MultiHeadAttention", "TransformerBlock", "MoEFeedForward",
    "LightningIndexer", "attach_auxiliary_loss",
    "RMSNormLayer", "GatedMLP", "LinearProjection", "KimiDeltaAttention",
    "GatedShortConv", "Mamba2Mixer", "MixerBlock",
    "MultiHeadLatentAttention",
    "HyperConnectedBlock",
    "LayerNormLayer", "PositionalEmbeddingLayer", "EmbeddingSequenceLayer",
]
