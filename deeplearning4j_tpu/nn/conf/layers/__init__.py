"""Layer configurations + functional implementations.

Unlike the reference — which splits ``nn/conf/layers`` (Jackson config) from
``nn/layers`` (imperative impls with hand-written ``backpropGradient``) — each layer
here is ONE dataclass carrying its hyperparameters (JSON round-trippable) and its
pure-functional ``init_params``/``forward``. Backward passes come from ``jax.grad``;
correctness is enforced by finite-difference gradient-check tests exactly as the
reference does (gradientcheck/GradientCheckUtil.java:41-80).
"""

from deeplearning4j_tpu.nn.conf.layers.base import Layer, BaseLayer, FeedForwardLayer
from deeplearning4j_tpu.nn.conf.layers.core import (
    DenseLayer,
    GatedFeedForwardLayer,
    OutputLayer,
    LossLayer,
    ActivationLayer,
    DropoutLayer,
    EmbeddingLayer,
    AutoEncoder,
)
from deeplearning4j_tpu.nn.conf.layers.convolution import (
    ConvolutionLayer,
    Convolution1DLayer,
    SubsamplingLayer,
    Subsampling1DLayer,
    ZeroPaddingLayer,
    SeparableConvolution2D,
    Upsampling2D,
    Deconvolution2D,
)
from deeplearning4j_tpu.nn.conf.layers.normalization import (
    BatchNormalization,
    LayerNormalization,
    LocalResponseNormalization,
    RMSNormalization,
)
from deeplearning4j_tpu.nn.conf.layers.pooling import GlobalPoolingLayer, PoolingType
from deeplearning4j_tpu.nn.conf.layers.recurrent import (
    LSTM,
    GravesLSTM,
    GravesBidirectionalLSTM,
    RnnOutputLayer,
    SimpleRnn,
    LastTimeStep,
)
from deeplearning4j_tpu.nn.conf.layers.variational import (
    VariationalAutoencoder,
    GaussianReconstructionDistribution,
    BernoulliReconstructionDistribution,
    ExponentialReconstructionDistribution,
    CompositeReconstructionDistribution,
    LossFunctionWrapper,
)
from deeplearning4j_tpu.nn.conf.layers.misc import (
    FrozenLayer,
    CenterLossOutputLayer,
)
from deeplearning4j_tpu.nn.conf.layers.rbm import RBM
from deeplearning4j_tpu.nn.conf.layers.moe import MixtureOfExpertsLayer
from deeplearning4j_tpu.nn.conf.layers.attention import (
    PositionalEncodingLayer,
    SelfAttentionLayer,
)
from deeplearning4j_tpu.nn.conf.layers.latent_attention import (
    LatentAttentionLayer,
)
from deeplearning4j_tpu.nn.conf.layers.mamba import Mamba2Layer
from deeplearning4j_tpu.nn.conf.layers.gated_delta import GatedDeltaNetLayer
