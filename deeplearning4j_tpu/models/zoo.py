"""Model zoo: instantiable standard architectures.

Reference: deeplearning4j-zoo/src/main/java/org/deeplearning4j/zoo/ —
ZooModel.java:23 (abstract model), InstantiableModel.java:9, and the ten
models under zoo/model/. Architectures and hyperparameters follow the
reference files (cited per class); layouts are TPU-first (NHWC images,
[B,T,F] sequences) and every model compiles to a single XLA program through
MultiLayerNetwork / ComputationGraph.

Divergences from the reference, by design:
- ``init_pretrained`` raises: the reference downloads pretrained zips from
  blob.deeplearning4j.org (ZooModel.java:40-52); this environment has no
  egress. Weights can instead be restored from a local model zip.
- GoogLeNet's head uses global average pooling instead of the reference's
  fixed 7x7 average pool (GoogLeNet.java:114 assumes a 7x7 feature map that
  its own downsampling stack never produces — a known bug in that vintage).
"""

from __future__ import annotations

from typing import Optional

from deeplearning4j_tpu.nn.conf.builders import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.graph_conf import (ElementWiseVertex,
                                                   MergeVertex, ScaleVertex)
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers.convolution import (
    ConvolutionLayer,
    SubsamplingLayer,
    ZeroPaddingLayer,
)
from deeplearning4j_tpu.nn.conf.layers.core import (
    ActivationLayer,
    DenseLayer,
    DropoutLayer,
    GatedFeedForwardLayer,
    OutputLayer,
)
from deeplearning4j_tpu.nn.conf.layers.attention import (
    PositionalEncodingLayer,
    SelfAttentionLayer,
)
from deeplearning4j_tpu.nn.conf.layers.latent_attention import (
    LatentAttentionLayer,
)
from deeplearning4j_tpu.nn.conf.layers.mamba import Mamba2Layer
from deeplearning4j_tpu.nn.conf.layers.misc import CenterLossOutputLayer
from deeplearning4j_tpu.nn.conf.layers.moe import MixtureOfExpertsLayer
from deeplearning4j_tpu.nn.conf.layers.normalization import (
    BatchNormalization,
    LayerNormalization,
    LocalResponseNormalization,
    RMSNormalization,
)
from deeplearning4j_tpu.nn.conf.layers.pooling import GlobalPoolingLayer
from deeplearning4j_tpu.nn.conf.layers.recurrent import (
    GravesLSTM,
    RnnOutputLayer,
)
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.nn.updater import (
    AdaDelta,
    Adam,
    Nesterovs,
    RmsProp,
    Sgd,
)
from deeplearning4j_tpu.nn.weights import Distribution


class ZooModel:
    """Base for instantiable zoo models (reference: zoo/ZooModel.java:23,
    zoo/InstantiableModel.java:9).

    ``input_shape`` is (height, width, channels) — NHWC, unlike the
    reference's (channels, height, width).
    """

    def __init__(self, num_labels: int = 1000, seed: int = 123,
                 input_shape: Optional[tuple] = None, dtype: str = "float32",
                 compute_dtype: Optional[str] = None,
                 quantize: Optional[str] = None):
        self.num_labels = num_labels
        self.seed = seed
        self.dtype = dtype
        self.compute_dtype = compute_dtype
        #: "int8" quantizes the initialized net's dense/conv/attention
        #: weights in place at init() (optimize/quantize.py); None (the
        #: default) keeps full-precision params bit-exact
        self.quantize = quantize
        if input_shape is not None:
            self.input_shape = tuple(input_shape)

    def conf(self):
        raise NotImplementedError

    def init(self):
        c = self.conf()
        c.compute_dtype = self.compute_dtype
        net = (ComputationGraph(c)
               if type(c).__name__ == "ComputationGraphConfiguration"
               else MultiLayerNetwork(c))
        net = net.init()
        if self.quantize is not None:
            from deeplearning4j_tpu.optimize.quantize import quantize_net
            net = quantize_net(net, self.quantize)
        return net

    def init_pretrained(self, pretrained_type: str = "imagenet"):
        raise NotImplementedError(
            "Pretrained weights require network access (reference downloads "
            "from blob.deeplearning4j.org, ZooModel.java:40-52). Restore from "
            "a local zip via utils.model_serializer.load_model instead.")

    def model_type(self) -> str:
        return "MultiLayerNetwork"


class LeNet(ZooModel):
    """LeNet-5 for MNIST (reference: zoo/model/LeNet.java:31,79-108).
    conv5x5(20) -> max2 -> conv5x5(50) -> max2 -> dense500 -> softmax."""

    input_shape = (28, 28, 1)

    def __init__(self, num_labels: int = 10, **kw):
        super().__init__(num_labels=num_labels, **kw)

    def conf(self):
        h, w, c = self.input_shape
        return (NeuralNetConfiguration.builder()
                .seed(self.seed).activation("identity").weight_init("xavier")
                .updater(AdaDelta()).dtype(self.dtype)
                .list(
                    ConvolutionLayer(name="cnn1", n_out=20, kernel_size=(5, 5),
                                     stride=(1, 1), convolution_mode="same",
                                     activation="relu"),
                    SubsamplingLayer(name="maxpool1", kernel_size=(2, 2),
                                     stride=(2, 2)),
                    ConvolutionLayer(name="cnn2", n_out=50, kernel_size=(5, 5),
                                     stride=(1, 1), convolution_mode="same",
                                     activation="relu"),
                    SubsamplingLayer(name="maxpool2", kernel_size=(2, 2),
                                     stride=(2, 2)),
                    DenseLayer(name="ffn1", n_out=500, activation="relu"),
                    OutputLayer(name="output", n_out=self.num_labels,
                                activation="softmax", loss="mcxent"))
                .set_input_type(InputType.convolutional(h, w, c))
                .build())


class SimpleCNN(ZooModel):
    """Five conv/BN blocks + global-avg-pool head (reference:
    zoo/model/SimpleCNN.java:71-131)."""

    input_shape = (48, 48, 1)

    def __init__(self, num_labels: int = 10, **kw):
        super().__init__(num_labels=num_labels, **kw)

    def conf(self):
        h, w, c = self.input_shape

        def block(k, n, drop=True):
            layers = [
                ConvolutionLayer(n_out=n, kernel_size=(k, k),
                                 convolution_mode="same"),
                BatchNormalization(),
                ConvolutionLayer(n_out=n, kernel_size=(k, k),
                                 convolution_mode="same"),
                BatchNormalization(),
                ActivationLayer(activation="relu"),
                SubsamplingLayer(pooling_type="avg", kernel_size=(2, 2),
                                 stride=(2, 2)),
            ]
            if drop:
                layers.append(DropoutLayer(dropout=0.5))
            return layers

        layers = (block(7, 16) + block(5, 32) + block(3, 64) + block(3, 128)
                  + [ConvolutionLayer(n_out=256, kernel_size=(3, 3),
                                      convolution_mode="same"),
                     BatchNormalization(),
                     ConvolutionLayer(n_out=self.num_labels,
                                      kernel_size=(3, 3),
                                      convolution_mode="same"),
                     GlobalPoolingLayer(pooling_type="avg"),
                     ActivationLayer(activation="softmax"),
                     # loss head over the softmaxed pooled logits
                     ])
        # The reference ends at the softmax ActivationLayer (SimpleCNN.java:
        # 124-126) and trains via an external loss; here we make the net
        # trainable standalone by using an OutputLayer head instead of the
        # last Activation+GlobalPooling pair.
        layers = layers[:-2] + [GlobalPoolingLayer(pooling_type="avg"),
                                OutputLayer(n_out=self.num_labels,
                                            activation="softmax",
                                            loss="mcxent")]
        return (NeuralNetConfiguration.builder()
                .seed(self.seed).activation("identity").weight_init("relu")
                .updater(AdaDelta()).dtype(self.dtype)
                .list(*layers)
                .set_input_type(InputType.convolutional(h, w, c))
                .build())


class AlexNet(ZooModel):
    """AlexNet, one-tower variant (reference: zoo/model/AlexNet.java:41,88-140).
    Keeps the reference's (quirky) strides so layer shapes match."""

    input_shape = (224, 224, 3)

    def conf(self):
        h, w, c = self.input_shape
        non_zero_bias = 1.0
        return (NeuralNetConfiguration.builder()
                .seed(self.seed).activation("relu")
                .weight_init("distribution")
                .dist(Distribution.normal(0.0, 0.01))
                .updater(Nesterovs(learning_rate=1e-2, momentum=0.9))
                .l2(5e-4).dtype(self.dtype)
                .list(
                    ConvolutionLayer(name="cnn1", n_out=64,
                                     kernel_size=(11, 11), stride=(4, 4),
                                     padding=(2, 2),
                                     convolution_mode="truncate"),
                    SubsamplingLayer(name="maxpool1", kernel_size=(3, 3),
                                     stride=(2, 2), padding=(1, 1),
                                     convolution_mode="truncate"),
                    ConvolutionLayer(name="cnn2", n_out=192,
                                     kernel_size=(5, 5), stride=(2, 2),
                                     padding=(2, 2),
                                     convolution_mode="truncate",
                                     bias_init=non_zero_bias),
                    SubsamplingLayer(name="maxpool2", kernel_size=(3, 3),
                                     stride=(2, 2)),
                    ConvolutionLayer(name="cnn3", n_out=384,
                                     kernel_size=(3, 3), stride=(1, 1),
                                     padding=(1, 1)),
                    ConvolutionLayer(name="cnn4", n_out=256,
                                     kernel_size=(3, 3), stride=(1, 1),
                                     padding=(1, 1), bias_init=non_zero_bias),
                    ConvolutionLayer(name="cnn5", n_out=256,
                                     kernel_size=(3, 3), stride=(1, 1),
                                     padding=(1, 1), bias_init=non_zero_bias),
                    SubsamplingLayer(name="maxpool3", kernel_size=(3, 3),
                                     stride=(7, 7)),
                    DenseLayer(name="ffn1", n_out=4096,
                               dist=Distribution.normal(0, 0.005),
                               weight_init="distribution",
                               bias_init=non_zero_bias, dropout=0.5),
                    DenseLayer(name="ffn2", n_out=4096,
                               dist=Distribution.normal(0, 0.005),
                               weight_init="distribution",
                               bias_init=non_zero_bias, dropout=0.5),
                    OutputLayer(name="output", n_out=self.num_labels,
                                activation="softmax", loss="mcxent"))
                .set_input_type(InputType.convolutional(h, w, c))
                .build())


def _vgg_convs(*spec):
    """spec: sequence of channel counts; 'M' inserts a 2x2 max pool."""
    layers = []
    for s in spec:
        if s == "M":
            layers.append(SubsamplingLayer(pooling_type="max",
                                           kernel_size=(2, 2), stride=(2, 2)))
        else:
            layers.append(ConvolutionLayer(n_out=s, kernel_size=(3, 3),
                                           stride=(1, 1), padding=(1, 1)))
    return layers


class VGG16(ZooModel):
    """VGG-16 (reference: zoo/model/VGG16.java:35,91-160; conv-only head as in
    the reference, which comments out the 4096 dense layers)."""

    input_shape = (224, 224, 3)

    def conf(self):
        h, w, c = self.input_shape
        convs = _vgg_convs(64, 64, "M", 128, 128, "M", 256, 256, 256, "M",
                           512, 512, 512, "M", 512, 512, 512, "M")
        return (NeuralNetConfiguration.builder()
                .seed(self.seed).activation("relu")
                .updater(Nesterovs(learning_rate=1e-2, momentum=0.9))
                .dtype(self.dtype)
                .list(*convs,
                      OutputLayer(name="output", n_out=self.num_labels,
                                  activation="softmax", loss="mcxent"))
                .set_input_type(InputType.convolutional(h, w, c))
                .build())


class VGG19(ZooModel):
    """VGG-19 (reference: zoo/model/VGG19.java)."""

    input_shape = (224, 224, 3)

    def conf(self):
        h, w, c = self.input_shape
        convs = _vgg_convs(64, 64, "M", 128, 128, "M", 256, 256, 256, 256, "M",
                           512, 512, 512, 512, "M", 512, 512, 512, 512, "M")
        return (NeuralNetConfiguration.builder()
                .seed(self.seed).activation("relu")
                .updater(Nesterovs(learning_rate=1e-2, momentum=0.9))
                .dtype(self.dtype)
                .list(*convs,
                      OutputLayer(name="output", n_out=self.num_labels,
                                  activation="softmax", loss="mcxent"))
                .set_input_type(InputType.convolutional(h, w, c))
                .build())


class ResNet50(ZooModel):
    """ResNet-50 as a ComputationGraph (reference: zoo/model/ResNet50.java:
    33,82 graphBuilder, :91-125 identityBlock, :128-172 convBlock). The
    residual blocks are ElementWiseVertex(add) joins — on TPU the whole graph
    is one XLA program; BN+ReLU fuse into the convolutions.

    Note: the reference's fan-in-independent N(0, 0.5) weight init
    (ResNet50.java:178-179, reproduced below) makes the UNTRAINED network's
    eval-mode forward overflow float32 (~24x activation growth per conv
    through 50 layers; BN running stats are identity at init). This matches
    the reference; training is finite from step one because train-mode BN
    normalizes with batch statistics. Use ``weight_init("relu")`` on a
    custom build if you need sane eval-mode activations at init."""

    input_shape = (224, 224, 3)

    def _conv_bn_act(self, g, name, n_out, kernel, stride, mode, input_name,
                     act="relu"):
        g.add_layer(name, ConvolutionLayer(n_out=n_out, kernel_size=kernel,
                                           stride=stride,
                                           convolution_mode=mode), input_name)
        g.add_layer(name + "_bn", BatchNormalization(), name)
        if act is None:
            return name + "_bn"
        g.add_layer(name + "_act", ActivationLayer(activation=act),
                    name + "_bn")
        return name + "_act"

    def _identity_block(self, g, kernel, filters, stage, block, input_name):
        n = f"res{stage}{block}"
        f1, f2, f3 = filters
        a = self._conv_bn_act(g, n + "_2a", f1, (1, 1), (1, 1), "truncate",
                              input_name)
        b = self._conv_bn_act(g, n + "_2b", f2, kernel, (1, 1), "same", a)
        c = self._conv_bn_act(g, n + "_2c", f3, (1, 1), (1, 1), "truncate", b,
                              act=None)
        g.add_vertex(n + "_add", ElementWiseVertex(op="add"), c, input_name)
        g.add_layer(n, ActivationLayer(activation="relu"), n + "_add")
        return n

    def _conv_block(self, g, kernel, filters, stage, block, stride,
                    input_name):
        n = f"res{stage}{block}"
        f1, f2, f3 = filters
        a = self._conv_bn_act(g, n + "_2a", f1, (1, 1), stride, "truncate",
                              input_name)
        b = self._conv_bn_act(g, n + "_2b", f2, kernel, (1, 1), "same", a)
        c = self._conv_bn_act(g, n + "_2c", f3, (1, 1), (1, 1), "truncate", b,
                              act=None)
        s = self._conv_bn_act(g, n + "_1", f3, (1, 1), stride, "truncate",
                              input_name, act=None)
        g.add_vertex(n + "_add", ElementWiseVertex(op="add"), c, s)
        g.add_layer(n, ActivationLayer(activation="relu"), n + "_add")
        return n

    def conf(self):
        h, w, c = self.input_shape
        g = (NeuralNetConfiguration.builder()
             .seed(self.seed).activation("identity")
             .updater(RmsProp(learning_rate=0.1, rms_decay=0.96, epsilon=0.001))
             .weight_init("distribution").dist(Distribution.normal(0.0, 0.5))
             .l1(1e-7).l2(5e-5).dtype(self.dtype)
             .graph_builder()
             .add_inputs("input"))
        g.add_layer("stem_zero", ZeroPaddingLayer(pad_top=3, pad_bottom=3,
                                                  pad_left=3, pad_right=3),
                    "input")
        stem = self._conv_bn_act(g, "stem_cnn1", 64, (7, 7), (2, 2),
                                 "truncate", "stem_zero")
        g.add_layer("stem_maxpool1",
                    SubsamplingLayer(pooling_type="max", kernel_size=(3, 3),
                                     stride=(2, 2)), stem)

        x = self._conv_block(g, (3, 3), (64, 64, 256), 2, "a", (2, 2),
                             "stem_maxpool1")
        x = self._identity_block(g, (3, 3), (64, 64, 256), 2, "b", x)
        x = self._identity_block(g, (3, 3), (64, 64, 256), 2, "c", x)

        x = self._conv_block(g, (3, 3), (128, 128, 512), 3, "a", (2, 2), x)
        for blk in "bcd":
            x = self._identity_block(g, (3, 3), (128, 128, 512), 3, blk, x)

        x = self._conv_block(g, (3, 3), (256, 256, 1024), 4, "a", (2, 2), x)
        for blk in "bcdef":
            x = self._identity_block(g, (3, 3), (256, 256, 1024), 4, blk, x)

        x = self._conv_block(g, (3, 3), (512, 512, 2048), 5, "a", (2, 2), x)
        x = self._identity_block(g, (3, 3), (512, 512, 2048), 5, "b", x)
        x = self._identity_block(g, (3, 3), (512, 512, 2048), 5, "c", x)

        g.add_layer("avgpool", GlobalPoolingLayer(pooling_type="avg"), x)
        g.add_layer("output", OutputLayer(n_out=self.num_labels,
                                          activation="softmax", loss="mcxent"),
                    "avgpool")
        g.set_outputs("output")
        g.set_input_types(InputType.convolutional(h, w, c))
        return g.build()

    def model_type(self) -> str:
        return "ComputationGraph"


class GoogLeNet(ZooModel):
    """GoogLeNet / Inception-v1 as a ComputationGraph (reference:
    zoo/model/GoogLeNet.java:84-96 inception, :99-175 conf)."""

    input_shape = (224, 224, 3)

    def _inception(self, g, name, config, input_name):
        (c1,), (c3r, c3), (c5r, c5), (pp,) = config
        g.add_layer(f"{name}-cnn1",
                    ConvolutionLayer(n_out=c1, kernel_size=(1, 1),
                                     bias_init=0.2, activation="relu"),
                    input_name)
        g.add_layer(f"{name}-cnn2",
                    ConvolutionLayer(n_out=c3r, kernel_size=(1, 1),
                                     bias_init=0.2, activation="relu"),
                    input_name)
        g.add_layer(f"{name}-cnn3",
                    ConvolutionLayer(n_out=c5r, kernel_size=(1, 1),
                                     bias_init=0.2, activation="relu"),
                    input_name)
        g.add_layer(f"{name}-max1",
                    SubsamplingLayer(pooling_type="max", kernel_size=(3, 3),
                                     stride=(1, 1), padding=(1, 1)),
                    input_name)
        g.add_layer(f"{name}-cnn4",
                    ConvolutionLayer(n_out=c3, kernel_size=(3, 3),
                                     padding=(1, 1), bias_init=0.2,
                                     activation="relu"), f"{name}-cnn2")
        g.add_layer(f"{name}-cnn5",
                    ConvolutionLayer(n_out=c5, kernel_size=(5, 5),
                                     padding=(2, 2), bias_init=0.2,
                                     activation="relu"), f"{name}-cnn3")
        g.add_layer(f"{name}-cnn6",
                    ConvolutionLayer(n_out=pp, kernel_size=(1, 1),
                                     bias_init=0.2, activation="relu"),
                    f"{name}-max1")
        g.add_vertex(f"{name}-depthconcat1", MergeVertex(), f"{name}-cnn1",
                     f"{name}-cnn4", f"{name}-cnn5", f"{name}-cnn6")
        return f"{name}-depthconcat1"

    def conf(self):
        h, w, c = self.input_shape
        g = (NeuralNetConfiguration.builder()
             .seed(self.seed).activation("relu").weight_init("xavier")
             .updater(Nesterovs(learning_rate=1e-2, momentum=0.9))
             .l2(2e-4).dtype(self.dtype)
             .graph_builder()
             .add_inputs("input"))
        g.add_layer("cnn1", ConvolutionLayer(n_out=64, kernel_size=(7, 7),
                                             stride=(2, 2), padding=(3, 3),
                                             bias_init=0.2), "input")
        g.add_layer("max1", SubsamplingLayer(pooling_type="max",
                                             kernel_size=(3, 3),
                                             stride=(2, 2), padding=(1, 1)),
                    "cnn1")
        g.add_layer("lrn1", LocalResponseNormalization(n=5, alpha=1e-4,
                                                       beta=0.75), "max1")
        g.add_layer("cnn2", ConvolutionLayer(n_out=64, kernel_size=(1, 1),
                                             bias_init=0.2), "lrn1")
        g.add_layer("cnn3", ConvolutionLayer(n_out=192, kernel_size=(3, 3),
                                             padding=(1, 1), bias_init=0.2),
                    "cnn2")
        g.add_layer("lrn2", LocalResponseNormalization(n=5, alpha=1e-4,
                                                       beta=0.75), "cnn3")
        g.add_layer("max2", SubsamplingLayer(pooling_type="max",
                                             kernel_size=(3, 3),
                                             stride=(2, 2), padding=(1, 1)),
                    "lrn2")
        x = self._inception(g, "3a", ((64,), (96, 128), (16, 32), (32,)),
                            "max2")
        x = self._inception(g, "3b", ((128,), (128, 192), (32, 96), (64,)), x)
        g.add_layer("max3", SubsamplingLayer(pooling_type="max",
                                             kernel_size=(3, 3),
                                             stride=(2, 2), padding=(1, 1)),
                    x)
        x = self._inception(g, "4a", ((192,), (96, 208), (16, 48), (64,)),
                            "max3")
        x = self._inception(g, "4b", ((160,), (112, 224), (24, 64), (64,)), x)
        x = self._inception(g, "4c", ((128,), (128, 256), (24, 64), (64,)), x)
        x = self._inception(g, "4d", ((112,), (144, 288), (32, 64), (64,)), x)
        x = self._inception(g, "4e", ((256,), (160, 320), (32, 128), (128,)),
                            x)
        g.add_layer("max4", SubsamplingLayer(pooling_type="max",
                                             kernel_size=(3, 3),
                                             stride=(2, 2), padding=(1, 1)),
                    x)
        x = self._inception(g, "5a", ((256,), (160, 320), (32, 128), (128,)),
                            "max4")
        x = self._inception(g, "5b", ((384,), (192, 384), (48, 128), (128,)),
                            x)
        g.add_layer("avg3", GlobalPoolingLayer(pooling_type="avg"), x)
        g.add_layer("fc1", DenseLayer(n_out=1024, dropout=0.4), "avg3")
        g.add_layer("output", OutputLayer(n_out=self.num_labels,
                                          activation="softmax", loss="mcxent"),
                    "fc1")
        g.set_outputs("output")
        g.set_input_types(InputType.convolutional(h, w, c))
        return g.build()

    def model_type(self) -> str:
        return "ComputationGraph"


class FaceNetNN4Small2(ZooModel):
    """FaceNet NN4.small2 embedding net with center-loss head (reference:
    zoo/model/FaceNetNN4Small2.java:80-340 — stem, inception-2..5 blocks,
    avg-pool, bottleneck dense, CenterLossOutputLayer). Inception internals
    follow zoo/model/helper/FaceNetHelper.appendGraph."""

    input_shape = (96, 96, 3)
    embedding_size = 128

    def __init__(self, num_labels: int = 1000, **kw):
        super().__init__(num_labels=num_labels, **kw)

    def _conv_bn(self, g, name, n_out, kernel, stride, pad, input_name):
        g.add_layer(name, ConvolutionLayer(n_out=n_out, kernel_size=kernel,
                                           stride=stride, padding=pad),
                    input_name)
        g.add_layer(name + "_bn", BatchNormalization(), name)
        g.add_layer(name + "_act", ActivationLayer(activation="relu"),
                    name + "_bn")
        return name + "_act"

    def _inception(self, g, name, reduce_sizes, out_sizes, input_name):
        """4 branches: 1x1, 1x1->3x3, 1x1->5x5, pool->1x1 (FaceNetHelper);
        reduce_sizes = (3x3-reduce, 5x5-reduce, pool-proj, 1x1)."""
        r3, r5, p1, c1 = reduce_sizes
        c3, c5 = out_sizes
        branches = []
        if c1:
            branches.append(self._conv_bn(g, f"{name}-1x1", c1, (1, 1),
                                          (1, 1), (0, 0), input_name))
        a = self._conv_bn(g, f"{name}-3x3r", r3, (1, 1), (1, 1), (0, 0),
                          input_name)
        branches.append(self._conv_bn(g, f"{name}-3x3", c3, (3, 3), (1, 1),
                                      (1, 1), a))
        if r5 and c5:  # reference 5a block omits the 5x5 branch
            b = self._conv_bn(g, f"{name}-5x5r", r5, (1, 1), (1, 1), (0, 0),
                              input_name)
            branches.append(self._conv_bn(g, f"{name}-5x5", c5, (5, 5),
                                          (1, 1), (2, 2), b))
        g.add_layer(f"{name}-pool",
                    SubsamplingLayer(pooling_type="max", kernel_size=(3, 3),
                                     stride=(1, 1), padding=(1, 1)),
                    input_name)
        branches.append(self._conv_bn(g, f"{name}-poolproj", p1, (1, 1),
                                      (1, 1), (0, 0), f"{name}-pool"))
        g.add_vertex(f"{name}-merge", MergeVertex(), *branches)
        return f"{name}-merge"

    def conf(self):
        h, w, c = self.input_shape
        g = (NeuralNetConfiguration.builder()
             .seed(self.seed).activation("relu").weight_init("relu")
             .updater(Nesterovs(learning_rate=1e-3, momentum=0.9))
             .dtype(self.dtype)
             .graph_builder()
             .add_inputs("input"))
        x = self._conv_bn(g, "stem-cnn1", 64, (7, 7), (2, 2), (3, 3), "input")
        g.add_layer("stem-pool1",
                    SubsamplingLayer(pooling_type="max", kernel_size=(3, 3),
                                     stride=(2, 2), padding=(1, 1)), x)
        x = self._conv_bn(g, "inception-2-cnn1", 64, (1, 1), (1, 1), (0, 0),
                          "stem-pool1")
        x = self._conv_bn(g, "inception-2-cnn2", 192, (3, 3), (1, 1), (1, 1),
                          x)
        g.add_layer("inception-2-pool1",
                    SubsamplingLayer(pooling_type="max", kernel_size=(3, 3),
                                     stride=(2, 2), padding=(1, 1)), x)
        x = self._inception(g, "3a", (96, 16, 32, 64), (128, 32),
                            "inception-2-pool1")
        x = self._inception(g, "3b", (96, 32, 64, 64), (128, 64), x)
        g.add_layer("3c-pool",
                    SubsamplingLayer(pooling_type="max", kernel_size=(3, 3),
                                     stride=(2, 2), padding=(1, 1)), x)
        x = self._inception(g, "4a", (96, 32, 128, 256), (192, 64),
                            "3c-pool")
        g.add_layer("4e-pool",
                    SubsamplingLayer(pooling_type="max", kernel_size=(3, 3),
                                     stride=(2, 2), padding=(1, 1)), x)
        x = self._inception(g, "5a", (96, 0, 96, 256), (384, 0), "4e-pool")
        g.add_layer("avgpool", GlobalPoolingLayer(pooling_type="avg"), x)
        g.add_layer("bottleneck", DenseLayer(n_out=self.embedding_size,
                                             activation="identity"), "avgpool")
        g.add_layer("lossLayer",
                    CenterLossOutputLayer(n_out=self.num_labels,
                                          activation="softmax", loss="mcxent",
                                          alpha=0.1, lambda_=3e-4),
                    "bottleneck")
        g.set_outputs("lossLayer")
        g.set_input_types(InputType.convolutional(h, w, c))
        return g.build()

    def model_type(self) -> str:
        return "ComputationGraph"


class InceptionResNetV1(ZooModel):
    """Inception-ResNet v1 embedding net (reference:
    zoo/model/InceptionResNetV1.java:60-322 — stem, 5x block35, reduction-A,
    10x block17, reduction-B, 5x block8, avgpool, bottleneck, center-loss).
    Block counts follow the reference; residual joins are
    ElementWiseVertex(add) with a post-add activation."""

    input_shape = (160, 160, 3)
    embedding_size = 128

    def __init__(self, num_labels: int = 1000, **kw):
        super().__init__(num_labels=num_labels, **kw)

    def _conv_bn(self, g, name, n_out, kernel, stride, pad, input_name,
                 act="relu"):
        g.add_layer(name, ConvolutionLayer(n_out=n_out, kernel_size=kernel,
                                           stride=stride, padding=pad),
                    input_name)
        g.add_layer(name + "_bn", BatchNormalization(), name)
        if act is None:
            return name + "_bn"
        g.add_layer(name + "_act", ActivationLayer(activation=act),
                    name + "_bn")
        return name + "_act"

    def _block35(self, g, name, input_name, ch=256):
        b1 = self._conv_bn(g, f"{name}-b1", 32, (1, 1), (1, 1), (0, 0),
                           input_name)
        b2 = self._conv_bn(g, f"{name}-b2a", 32, (1, 1), (1, 1), (0, 0),
                           input_name)
        b2 = self._conv_bn(g, f"{name}-b2b", 32, (3, 3), (1, 1), (1, 1), b2)
        b3 = self._conv_bn(g, f"{name}-b3a", 32, (1, 1), (1, 1), (0, 0),
                           input_name)
        b3 = self._conv_bn(g, f"{name}-b3b", 32, (3, 3), (1, 1), (1, 1), b3)
        b3 = self._conv_bn(g, f"{name}-b3c", 32, (3, 3), (1, 1), (1, 1), b3)
        g.add_vertex(f"{name}-merge", MergeVertex(), b1, b2, b3)
        up = self._conv_bn(g, f"{name}-up", ch, (1, 1), (1, 1), (0, 0),
                           f"{name}-merge", act=None)
        g.add_vertex(f"{name}-add", ElementWiseVertex(op="add"), input_name,
                     up)
        g.add_layer(f"{name}", ActivationLayer(activation="relu"),
                    f"{name}-add")
        return f"{name}"

    def _block17(self, g, name, input_name, ch=896):
        b1 = self._conv_bn(g, f"{name}-b1", 128, (1, 1), (1, 1), (0, 0),
                           input_name)
        b2 = self._conv_bn(g, f"{name}-b2a", 128, (1, 1), (1, 1), (0, 0),
                           input_name)
        b2 = self._conv_bn(g, f"{name}-b2b", 128, (1, 7), (1, 1), (0, 3), b2)
        b2 = self._conv_bn(g, f"{name}-b2c", 128, (7, 1), (1, 1), (3, 0), b2)
        g.add_vertex(f"{name}-merge", MergeVertex(), b1, b2)
        up = self._conv_bn(g, f"{name}-up", ch, (1, 1), (1, 1), (0, 0),
                           f"{name}-merge", act=None)
        g.add_vertex(f"{name}-add", ElementWiseVertex(op="add"), input_name,
                     up)
        g.add_layer(f"{name}", ActivationLayer(activation="relu"),
                    f"{name}-add")
        return f"{name}"

    def _block8(self, g, name, input_name, ch=1792):
        b1 = self._conv_bn(g, f"{name}-b1", 192, (1, 1), (1, 1), (0, 0),
                           input_name)
        b2 = self._conv_bn(g, f"{name}-b2a", 192, (1, 1), (1, 1), (0, 0),
                           input_name)
        b2 = self._conv_bn(g, f"{name}-b2b", 192, (1, 3), (1, 1), (0, 1), b2)
        b2 = self._conv_bn(g, f"{name}-b2c", 192, (3, 1), (1, 1), (1, 0), b2)
        g.add_vertex(f"{name}-merge", MergeVertex(), b1, b2)
        up = self._conv_bn(g, f"{name}-up", ch, (1, 1), (1, 1), (0, 0),
                           f"{name}-merge", act=None)
        g.add_vertex(f"{name}-add", ElementWiseVertex(op="add"), input_name,
                     up)
        g.add_layer(f"{name}", ActivationLayer(activation="relu"),
                    f"{name}-add")
        return f"{name}"

    def conf(self):
        h, w, c = self.input_shape
        g = (NeuralNetConfiguration.builder()
             .seed(self.seed).activation("relu").weight_init("relu")
             .updater(RmsProp(learning_rate=0.1, rms_decay=0.96, epsilon=0.001))
             .dtype(self.dtype)
             .graph_builder()
             .add_inputs("input"))
        # stem (InceptionResNetV1.java stem: 3x conv, maxpool, 3x conv)
        x = self._conv_bn(g, "stem1", 32, (3, 3), (2, 2), (0, 0), "input")
        x = self._conv_bn(g, "stem2", 32, (3, 3), (1, 1), (0, 0), x)
        x = self._conv_bn(g, "stem3", 64, (3, 3), (1, 1), (1, 1), x)
        g.add_layer("stem-pool",
                    SubsamplingLayer(pooling_type="max", kernel_size=(3, 3),
                                     stride=(2, 2)), x)
        x = self._conv_bn(g, "stem4", 80, (1, 1), (1, 1), (0, 0), "stem-pool")
        x = self._conv_bn(g, "stem5", 192, (3, 3), (1, 1), (0, 0), x)
        x = self._conv_bn(g, "stem6", 256, (3, 3), (2, 2), (0, 0), x)
        for i in range(5):
            x = self._block35(g, f"block35-{i}", x)
        # reduction-A
        ra1 = self._conv_bn(g, "redA-b1", 384, (3, 3), (2, 2), (0, 0), x)
        ra2 = self._conv_bn(g, "redA-b2a", 192, (1, 1), (1, 1), (0, 0), x)
        ra2 = self._conv_bn(g, "redA-b2b", 192, (3, 3), (1, 1), (1, 1), ra2)
        ra2 = self._conv_bn(g, "redA-b2c", 256, (3, 3), (2, 2), (0, 0), ra2)
        g.add_layer("redA-pool",
                    SubsamplingLayer(pooling_type="max", kernel_size=(3, 3),
                                     stride=(2, 2)), x)
        g.add_vertex("redA", MergeVertex(), ra1, ra2, "redA-pool")
        x = "redA"
        for i in range(10):
            x = self._block17(g, f"block17-{i}", x)
        # reduction-B
        rb1 = self._conv_bn(g, "redB-b1a", 256, (1, 1), (1, 1), (0, 0), x)
        rb1 = self._conv_bn(g, "redB-b1b", 384, (3, 3), (2, 2), (0, 0), rb1)
        rb2 = self._conv_bn(g, "redB-b2a", 256, (1, 1), (1, 1), (0, 0), x)
        rb2 = self._conv_bn(g, "redB-b2b", 256, (3, 3), (2, 2), (0, 0), rb2)
        rb3 = self._conv_bn(g, "redB-b3a", 256, (1, 1), (1, 1), (0, 0), x)
        rb3 = self._conv_bn(g, "redB-b3b", 256, (3, 3), (1, 1), (1, 1), rb3)
        rb3 = self._conv_bn(g, "redB-b3c", 256, (3, 3), (2, 2), (0, 0), rb3)
        g.add_layer("redB-pool",
                    SubsamplingLayer(pooling_type="max", kernel_size=(3, 3),
                                     stride=(2, 2)), x)
        g.add_vertex("redB", MergeVertex(), rb1, rb2, rb3, "redB-pool")
        x = "redB"
        for i in range(5):
            x = self._block8(g, f"block8-{i}", x)
        g.add_layer("avgpool", GlobalPoolingLayer(pooling_type="avg"), x)
        g.add_layer("bottleneck", DenseLayer(n_out=self.embedding_size,
                                             activation="identity"),
                    "avgpool")
        g.add_layer("lossLayer",
                    CenterLossOutputLayer(n_out=self.num_labels,
                                          activation="softmax", loss="mcxent",
                                          alpha=0.1, lambda_=3e-4),
                    "bottleneck")
        g.set_outputs("lossLayer")
        g.set_input_types(InputType.convolutional(h, w, c))
        return g.build()

    def model_type(self) -> str:
        return "ComputationGraph"


class TextGenerationLSTM(ZooModel):
    """Char-level text-generation LSTM (reference:
    zoo/model/TextGenerationLSTM.java:77-94): GravesLSTM(256) x2 +
    RnnOutputLayer, truncated BPTT 50/50. On TPU the LSTM is a lax.scan whose
    per-step gate matmul hits the MXU."""

    def __init__(self, num_labels: int = 77, max_length: int = 40, **kw):
        super().__init__(num_labels=num_labels, **kw)
        self.max_length = max_length
        self.input_shape = (max_length, num_labels)

    def conf(self):
        return (NeuralNetConfiguration.builder()
                .seed(self.seed).weight_init("xavier")
                .updater(RmsProp(learning_rate=0.01)).l2(0.001)
                .dtype(self.dtype)
                .list(
                    GravesLSTM(n_out=256, activation="tanh"),
                    GravesLSTM(n_out=256, activation="tanh"),
                    RnnOutputLayer(n_out=self.num_labels,
                                   activation="softmax", loss="mcxent"))
                .set_input_type(InputType.recurrent(self.num_labels))
                .t_bptt_lengths(50, 50)
                .build())


class TransformerLM(ZooModel):
    """Causal transformer language model (beyond reference parity — the
    2017-era zoo's sequence model is TextGenerationLSTM; this is its
    modern sibling, built from the same framework pieces so the flash
    attention path has a model-level consumer).

    Pre-norm residual blocks as a ComputationGraph: one-hot tokens ->
    Dense embed + sinusoidal positions -> n_blocks x [LN -> causal
    multi-head SelfAttention (helper='auto': Pallas flash kernel when
    supported) -> +residual -> LN -> Dense(4D, gelu) -> Dense(D) ->
    +residual] -> LN -> RnnOutputLayer softmax/mcxent per timestep.
    """

    def __init__(self, num_labels: int = 256, max_length: int = 128,
                 d_model: int = 256, n_heads: int = 8, n_blocks: int = 4,
                 remat: bool = False, **kw):
        super().__init__(num_labels=num_labels, **kw)
        self.max_length = max_length
        self.d_model = d_model
        self.n_heads = n_heads
        self.n_blocks = n_blocks
        # jax.checkpoint the attention / FFN-expansion vertices: backward
        # recomputes their internal activations at the cost of one extra
        # forward. Per-vertex boundaries mean boundary outputs are still
        # stored as residuals (see LayerVertex.remat) — the saving is the
        # inside-vertex intermediates, not whole-block memory.
        self.remat = remat
        self.input_shape = (max_length, num_labels)

    def conf(self):
        D = self.d_model
        g = (NeuralNetConfiguration.builder()
             .seed(self.seed).weight_init("xavier")
             .updater(Adam(learning_rate=3e-4))
             .dtype(self.dtype)
             .graph_builder()
             .add_inputs("tokens")
             .set_input_types(InputType.recurrent(self.num_labels,
                                                  self.max_length)))
        g.add_layer("embed", DenseLayer(n_out=D, activation="identity"),
                    "tokens")
        g.add_layer("pos", PositionalEncodingLayer(), "embed")
        x = "pos"
        for i in range(self.n_blocks):
            g.add_layer(f"ln{i}a", LayerNormalization(), x)
            g.add_layer(f"attn{i}",
                        SelfAttentionLayer(n_out=D, n_heads=self.n_heads,
                                           causal=True, helper="auto"),
                        f"ln{i}a", remat=self.remat)
            g.add_vertex(f"res{i}a", ElementWiseVertex(op="add"),
                         x, f"attn{i}")
            g.add_layer(f"ln{i}b", LayerNormalization(), f"res{i}a")
            g.add_layer(f"ff{i}a", DenseLayer(n_out=4 * D,
                                              activation="gelu"),
                        f"ln{i}b", remat=self.remat)
            g.add_layer(f"ff{i}b", DenseLayer(n_out=D,
                                              activation="identity"),
                        f"ff{i}a")
            g.add_vertex(f"res{i}b", ElementWiseVertex(op="add"),
                         f"res{i}a", f"ff{i}b")
            x = f"res{i}b"
        g.add_layer("ln_f", LayerNormalization(), x)
        g.add_layer("output",
                    RnnOutputLayer(n_out=self.num_labels,
                                   activation="softmax", loss="mcxent"),
                    "ln_f")
        g.set_outputs("output")
        return g.build()

    def model_type(self) -> str:
        return "ComputationGraph"


class GraniteMoeHybridLM(ZooModel):
    """Hybrid state-space / attention language model with routed experts,
    after IBM's ``granitemoehybrid`` (granite-4.0-h): ``layer_types`` names
    each layer's mixer, ``"mamba"`` (``Mamba2Layer``) or ``"attention"``
    (grouped-query ``SelfAttentionLayer``, no positions, a stated score
    scale), and every layer ends in a routed ``MixtureOfExpertsLayer`` with
    gated experts and a shared expert beside them.

        x = embed(tokens) * embedding_multiplier
        x = x + residual_multiplier * Mixer(RMSNorm(x))
        x = x + residual_multiplier * MoE(RMSNorm(x))        per layer
        probs = softmax(RMSNorm(x) W / logits_scaling)       in float32

    A ComputationGraph as ``TransformerLM`` is, served by the same
    ``GenerationServer``: the attention layers' KV goes to the page pool,
    the Mamba-2 layers' state to the server's per-slot state.
    ``experts_held=(first, count)`` builds one chip's share of an
    expert-parallel deployment (see ``MixtureOfExpertsLayer``). The
    embedding is the zoo's Dense over one-hot tokens and the head has a
    kernel of its own. The updater is stateless (plain SGD): ``init()``
    then allocates nothing beside the weights, which is what lets a model
    that fills most of a chip be built for serving at all."""

    def __init__(self, num_labels: int = 256, max_length: int = 128,
                 d_model: int = 64, layer_types=("mamba", "attention"),
                 n_heads: int = 4, n_kv_heads: int = 2,
                 attention_multiplier: float = 0.0,
                 embedding_multiplier: float = 1.0,
                 residual_multiplier: float = 1.0,
                 logits_scaling: float = 1.0, rms_eps: float = 1e-5,
                 n_experts: int = 8, experts_held=None, top_k: int = 2,
                 expert_width: int = 32, shared_width: int = 64,
                 mamba_heads: int = 4, mamba_head_dim: int = 32,
                 mamba_d_state: int = 16, mamba_n_groups: int = 1,
                 mamba_d_conv: int = 4, mamba_chunk: int = 256,
                 dtype: str = "bfloat16", **kw):
        super().__init__(num_labels=num_labels, dtype=dtype, **kw)
        self.max_length = max_length
        self.d_model = d_model
        self.layer_types = tuple(layer_types)
        bad = set(self.layer_types) - {"mamba", "attention"}
        if bad:
            raise ValueError(f"layer_types may name 'mamba' and "
                             f"'attention', got {sorted(bad)}")
        self.n_heads, self.n_kv_heads = n_heads, n_kv_heads
        self.attention_multiplier = attention_multiplier
        self.embedding_multiplier = embedding_multiplier
        self.residual_multiplier = residual_multiplier
        self.logits_scaling = logits_scaling
        self.rms_eps = rms_eps
        self.n_experts, self.top_k = n_experts, top_k
        self.experts_held = None if experts_held is None \
            else tuple(experts_held)
        self.expert_width, self.shared_width = expert_width, shared_width
        self.mamba = dict(n_heads=mamba_heads, head_dim=mamba_head_dim,
                          d_state=mamba_d_state, n_groups=mamba_n_groups,
                          d_conv=mamba_d_conv, chunk_size=mamba_chunk,
                          norm_eps=rms_eps)
        self.input_shape = (max_length, num_labels)

    def conf(self):
        D = self.d_model
        g = (NeuralNetConfiguration.builder()
             .seed(self.seed).weight_init("xavier")
             .updater(Sgd(learning_rate=1e-3))
             .dtype(self.dtype)
             .graph_builder()
             .add_inputs("tokens")
             .set_input_types(InputType.recurrent(self.num_labels,
                                                  self.max_length)))
        g.add_layer("embed", DenseLayer(n_out=D, activation="identity"),
                    "tokens")
        g.add_vertex("embed_scaled",
                     ScaleVertex(scale=self.embedding_multiplier), "embed")
        x = "embed_scaled"
        norm = lambda: RMSNormalization(eps=self.rms_eps)  # noqa: E731
        res = lambda: ScaleVertex(scale=self.residual_multiplier)  # noqa: E731
        for i, kind in enumerate(self.layer_types):
            g.add_layer(f"n{i}a", norm(), x)
            if kind == "mamba":
                mixer = Mamba2Layer(n_out=D, **self.mamba)
            else:
                mixer = SelfAttentionLayer(
                    n_out=D, n_heads=self.n_heads,
                    n_kv_heads=self.n_kv_heads, causal=True,
                    helper="stock", has_bias=False,
                    score_scale=self.attention_multiplier)
            g.add_layer(f"mix{i}", mixer, f"n{i}a")
            g.add_vertex(f"s{i}a", res(), f"mix{i}")
            g.add_vertex(f"res{i}a", ElementWiseVertex(op="add"),
                         x, f"s{i}a")
            g.add_layer(f"n{i}b", norm(), f"res{i}a")
            g.add_layer(f"moe{i}", MixtureOfExpertsLayer(
                n_out=D, n_experts=self.n_experts, top_k=self.top_k,
                expert_hidden=self.expert_width, activation="silu",
                dispatch="routed", experts_held=self.experts_held,
                gated=True, shared_hidden=self.shared_width,
                has_bias=False), f"n{i}b")
            g.add_vertex(f"s{i}b", res(), f"moe{i}")
            g.add_vertex(f"res{i}b", ElementWiseVertex(op="add"),
                         f"res{i}a", f"s{i}b")
            x = f"res{i}b"
        g.add_layer("n_f", norm(), x)
        g.add_layer("output",
                    RnnOutputLayer(n_out=self.num_labels,
                                   activation="softmax", loss="mcxent",
                                   logits_divisor=self.logits_scaling),
                    "n_f")
        g.set_outputs("output")
        return g.build()

    def model_type(self) -> str:
        return "ComputationGraph"


class FalconH1LM(ZooModel):
    """Parallel-hybrid language model after TII's ``falcon_h1``: in every
    block a Mamba-2 mixer and a rotary grouped-query attention mixer read
    the same normed input and are summed into the stream, each with a
    multiplier going in and coming out; then a dense gated feed-forward.

        x = embed(tokens) * embedding_multiplier
        h = RMSNorm(x)
        x = x + ssm_out * Mamba(ssm_in * h) + attention_out * Attn(attention_in * h)
        x = x + MLP(RMSNorm'(x))                              per block
        probs = softmax(RMSNorm_f(x) W * lm_head_multiplier)  in float32

    ``Attn`` has ``n_heads`` query and ``n_kv_heads`` key/value heads of
    ``head_dim`` (not ``d_model / n_heads``), no biases, keys times
    ``key_multiplier``, queries and keys rotated at ``rope_theta``;
    ``Mamba`` has ``mamba_n_groups`` groups with the gated norm taken per
    group and ``ssm_multipliers`` on the segments (z, x, B, C, dt) of its
    input projection; ``MLP`` is ``GatedFeedForwardLayer`` with
    ``mlp_multipliers`` (gate, down). A ComputationGraph as
    ``GraniteMoeHybridLM`` is, served by the same ``GenerationServer``:
    every block owns a paged KV layer and a per-slot state layer. The
    embedding is the zoo's Dense over one-hot tokens, the head has a kernel
    of its own, the updater is stateless."""

    def __init__(self, num_labels: int = 256, max_length: int = 128,
                 d_model: int = 64, n_layers: int = 2, n_heads: int = 4,
                 n_kv_heads: int = 2, head_dim: int = 8,
                 rope_theta: float = 1e4, mlp_width: int = 128,
                 mamba_heads: int = 4, mamba_head_dim: int = 16,
                 mamba_d_state: int = 16, mamba_n_groups: int = 2,
                 mamba_d_conv: int = 4, mamba_chunk: int = 128,
                 embedding_multiplier: float = 1.0,
                 attention_in_multiplier: float = 1.0,
                 attention_out_multiplier: float = 1.0,
                 key_multiplier: float = 1.0,
                 ssm_in_multiplier: float = 1.0,
                 ssm_out_multiplier: float = 1.0,
                 ssm_multipliers=(1.0, 1.0, 1.0, 1.0, 1.0),
                 mlp_multipliers=(1.0, 1.0),
                 lm_head_multiplier: float = 1.0, rms_eps: float = 1e-5,
                 dtype: str = "bfloat16", **kw):
        super().__init__(num_labels=num_labels, dtype=dtype, **kw)
        self.max_length = max_length
        self.d_model, self.n_layers = d_model, n_layers
        self.attention = dict(n_heads=n_heads, n_kv_heads=n_kv_heads,
                              head_dim=head_dim, rope_theta=rope_theta,
                              key_scale=key_multiplier)
        self.mamba = dict(n_heads=mamba_heads, head_dim=mamba_head_dim,
                          d_state=mamba_d_state, n_groups=mamba_n_groups,
                          d_conv=mamba_d_conv, chunk_size=mamba_chunk,
                          norm_eps=rms_eps,
                          proj_multipliers=tuple(ssm_multipliers))
        self.mlp = dict(hidden=mlp_width, gate_scale=mlp_multipliers[0],
                        out_scale=mlp_multipliers[1])
        self.embedding_multiplier = embedding_multiplier
        self.attention_scales = (attention_in_multiplier,
                                 attention_out_multiplier)
        self.ssm_scales = (ssm_in_multiplier, ssm_out_multiplier)
        self.lm_head_multiplier = lm_head_multiplier
        self.rms_eps = rms_eps
        self.input_shape = (max_length, num_labels)

    def conf(self):
        D = self.d_model
        g = (NeuralNetConfiguration.builder()
             .seed(self.seed).weight_init("xavier")
             .updater(Sgd(learning_rate=1e-3))
             .dtype(self.dtype)
             .graph_builder()
             .add_inputs("tokens")
             .set_input_types(InputType.recurrent(self.num_labels,
                                                  self.max_length)))
        g.add_layer("embed", DenseLayer(n_out=D, activation="identity"),
                    "tokens")
        g.add_vertex("embed_scaled",
                     ScaleVertex(scale=self.embedding_multiplier), "embed")
        x = "embed_scaled"
        norm = lambda: RMSNormalization(eps=self.rms_eps)  # noqa: E731
        for i in range(self.n_layers):
            g.add_layer(f"n{i}a", norm(), x)
            branches = []
            for tag, (s_in, s_out), mixer in (
                    ("ssm", self.ssm_scales,
                     Mamba2Layer(n_out=D, **self.mamba)),
                    ("attn", self.attention_scales,
                     SelfAttentionLayer(n_out=D, causal=True, helper="stock",
                                        has_bias=False, **self.attention))):
                g.add_vertex(f"{tag}{i}_in", ScaleVertex(scale=s_in),
                             f"n{i}a")
                g.add_layer(f"{tag}{i}", mixer, f"{tag}{i}_in")
                g.add_vertex(f"{tag}{i}_out", ScaleVertex(scale=s_out),
                             f"{tag}{i}")
                branches.append(f"{tag}{i}_out")
            g.add_vertex(f"res{i}a", ElementWiseVertex(op="add"),
                         x, *branches)
            g.add_layer(f"n{i}b", norm(), f"res{i}a")
            g.add_layer(f"mlp{i}", GatedFeedForwardLayer(
                n_out=D, activation="silu", **self.mlp), f"n{i}b")
            g.add_vertex(f"res{i}b", ElementWiseVertex(op="add"),
                         f"res{i}a", f"mlp{i}")
            x = f"res{i}b"
        g.add_layer("n_f", norm(), x)
        g.add_layer("output",
                    RnnOutputLayer(n_out=self.num_labels,
                                   activation="softmax", loss="mcxent",
                                   logits_divisor=1.0
                                   / self.lm_head_multiplier),
                    "n_f")
        g.set_outputs("output")
        return g.build()

    def model_type(self) -> str:
        return "ComputationGraph"


class DeepSeekV2LM(ZooModel):
    """Latent-attention language model with group-limited routed experts
    beside shared ones, after DeepSeek's ``deepseek_v2``:

        x = embed(tokens)
        x = x + MLA(RMSNorm(x))
        x = x + FFN(RMSNorm'(x))                              per block
        probs = softmax(RMSNorm_f(x) W)                       in float32

    ``MLA`` is ``LatentAttentionLayer`` (queries through a normed
    ``q_rank`` bottleneck, keys and values rebuilt from a normed
    ``kv_rank`` latent, a ``rope_dim``-wide rotary key shared by all
    heads, YaRN frequencies and score factor); ``FFN`` is the dense
    ``GatedFeedForwardLayer`` of ``mlp_width`` in the first
    ``dense_layers`` blocks and a routed ``MixtureOfExpertsLayer`` after
    them: softmax over all ``n_experts``, the ``top_k`` of the
    ``groups_kept`` best of ``expert_groups`` groups, weights as they
    stand times ``routed_scale``, gated experts of ``expert_width`` beside
    a shared expert of ``shared_width``. A ComputationGraph as
    ``GraniteMoeHybridLM`` is, served by the same ``GenerationServer``:
    each block's cache is ONE latent plane of the page pool, so the prefix
    cache and copy-on-write stay on. ``experts_held=(first, count)``
    builds one chip's share of an expert-parallel deployment. The
    embedding is the zoo's Dense over one-hot tokens, the head has a
    kernel of its own (as published), the updater is stateless."""

    def __init__(self, num_labels: int = 256, max_length: int = 128,
                 d_model: int = 64, n_layers: int = 2, dense_layers: int = 1,
                 n_heads: int = 4, q_rank: int = 24, kv_rank: int = 16,
                 nope_dim: int = 8, rope_dim: int = 4, v_dim: int = 8,
                 rope_theta: float = 1e4, yarn_factor: float = 0.0,
                 yarn_original_positions: int = 0,
                 yarn_beta_fast: float = 32.0, yarn_beta_slow: float = 1.0,
                 yarn_mscale: float = 1.0, yarn_mscale_all_dim: float = 0.0,
                 mlp_width: int = 128, n_experts: int = 8,
                 experts_held=None, top_k: int = 2, expert_groups: int = 0,
                 groups_kept: int = 0, routed_scale: float = 1.0,
                 expert_width: int = 32, shared_width: int = 64,
                 rms_eps: float = 1e-6, dtype: str = "bfloat16", **kw):
        super().__init__(num_labels=num_labels, dtype=dtype, **kw)
        self.max_length = max_length
        self.d_model, self.n_layers = d_model, n_layers
        self.dense_layers = dense_layers
        self.attention = dict(
            n_heads=n_heads, q_rank=q_rank, kv_rank=kv_rank,
            nope_dim=nope_dim, rope_dim=rope_dim, v_dim=v_dim,
            norm_eps=rms_eps, rope_theta=rope_theta,
            yarn_factor=yarn_factor,
            yarn_original_positions=yarn_original_positions,
            yarn_beta_fast=yarn_beta_fast, yarn_beta_slow=yarn_beta_slow,
            yarn_mscale=yarn_mscale,
            yarn_mscale_all_dim=yarn_mscale_all_dim)
        self.mlp_width = mlp_width
        self.experts = dict(
            n_experts=n_experts, top_k=top_k, expert_hidden=expert_width,
            experts_held=None if experts_held is None
            else tuple(experts_held), shared_hidden=shared_width,
            gate_over="all", expert_groups=expert_groups,
            groups_kept=groups_kept, routed_scale=routed_scale)
        self.rms_eps = rms_eps
        self.input_shape = (max_length, num_labels)

    def conf(self):
        D = self.d_model
        g = (NeuralNetConfiguration.builder()
             .seed(self.seed).weight_init("xavier")
             .updater(Sgd(learning_rate=1e-3))
             .dtype(self.dtype)
             .graph_builder()
             .add_inputs("tokens")
             .set_input_types(InputType.recurrent(self.num_labels,
                                                  self.max_length)))
        g.add_layer("embed", DenseLayer(n_out=D, activation="identity"),
                    "tokens")
        x = "embed"
        norm = lambda: RMSNormalization(eps=self.rms_eps)  # noqa: E731
        for i in range(self.n_layers):
            g.add_layer(f"n{i}a", norm(), x)
            g.add_layer(f"mla{i}", LatentAttentionLayer(
                n_out=D, **self.attention), f"n{i}a")
            g.add_vertex(f"res{i}a", ElementWiseVertex(op="add"),
                         x, f"mla{i}")
            g.add_layer(f"n{i}b", norm(), f"res{i}a")
            if i < self.dense_layers:
                ffn = GatedFeedForwardLayer(n_out=D, activation="silu",
                                            hidden=self.mlp_width)
            else:
                ffn = MixtureOfExpertsLayer(
                    n_out=D, activation="silu", dispatch="routed",
                    gated=True, has_bias=False, **self.experts)
            g.add_layer(f"ffn{i}", ffn, f"n{i}b")
            g.add_vertex(f"res{i}b", ElementWiseVertex(op="add"),
                         f"res{i}a", f"ffn{i}")
            x = f"res{i}b"
        g.add_layer("n_f", norm(), x)
        g.add_layer("output",
                    RnnOutputLayer(n_out=self.num_labels,
                                   activation="softmax", loss="mcxent"),
                    "n_f")
        g.set_outputs("output")
        return g.build()

    def model_type(self) -> str:
        return "ComputationGraph"


class TrinityLM(ZooModel):
    """Sliding-window and full attention layers mixed, gated attention,
    sigmoid-routed experts beside a shared one, after Arcee's ``afmoe``
    (Trinity):

        x = embed(tokens) * embedding_multiplier
        h = x + RMSNorm2(Attn(RMSNorm1(x)))
        x = h + RMSNorm4(FFN(RMSNorm3(h)))                    per block
        probs = softmax(RMSNorm_f(x) W)                       in float32

    four norms a block, one before and one after each branch. ``Attn`` is a
    grouped-query ``SelfAttentionLayer`` with an RMS norm on queries and
    keys per head and a sigmoid gate on the heads' output; ``layer_types``
    names each block ``"sliding"`` (queries and keys rotated at
    ``rope_theta``, a key visible for ``window`` tokens) or ``"full"`` (no
    positions at all, causal over everything). ``FFN`` is the dense
    ``GatedFeedForwardLayer`` of ``mlp_width`` in the first ``dense_layers``
    blocks and a routed ``MixtureOfExpertsLayer`` after them: a sigmoid
    score per expert, the ``top_k`` by score plus a stored selection bias,
    weights the unbiased scores over their sum times ``routed_scale``,
    gated experts of ``expert_width`` beside a shared expert of
    ``shared_width``. A ComputationGraph as ``GraniteMoeHybridLM`` is,
    served by the same ``GenerationServer``, which keeps the sliding
    blocks' pages in a class of their own and frees those behind the
    window. ``experts_held=(first, count)`` builds one chip's share of an
    expert-parallel deployment. The embedding is the zoo's Dense over
    one-hot tokens, the head has a kernel of its own (as published), the
    updater is stateless."""

    def __init__(self, num_labels: int = 256, max_length: int = 128,
                 d_model: int = 64, layer_types=("sliding", "full"),
                 dense_layers: int = 1, n_heads: int = 4,
                 n_kv_heads: int = 2, head_dim: int = 16, window: int = 32,
                 rope_theta: float = 1e4, embedding_multiplier: float = 1.0,
                 mlp_width: int = 128, n_experts: int = 8,
                 experts_held=None, top_k: int = 2,
                 routed_scale: float = 1.0, expert_width: int = 32,
                 shared_width: int = 32, rms_eps: float = 1e-5,
                 dtype: str = "bfloat16", **kw):
        super().__init__(num_labels=num_labels, dtype=dtype, **kw)
        self.max_length = max_length
        self.d_model = d_model
        self.layer_types = tuple(layer_types)
        bad = set(self.layer_types) - {"sliding", "full"}
        if bad:
            raise ValueError(f"layer_types may name 'sliding' and 'full', "
                             f"got {sorted(bad)}")
        self.dense_layers = dense_layers
        self.attention = dict(n_heads=n_heads, n_kv_heads=n_kv_heads,
                              head_dim=head_dim, qk_norm=True,
                              qk_norm_eps=rms_eps, gated=True,
                              softmax_barrier=True)
        self.sliding = dict(window=window, rope_theta=rope_theta)
        self.embedding_multiplier = embedding_multiplier
        self.mlp_width = mlp_width
        self.experts = dict(
            n_experts=n_experts, top_k=top_k, expert_hidden=expert_width,
            experts_held=None if experts_held is None
            else tuple(experts_held), shared_hidden=shared_width,
            score="sigmoid", routed_scale=routed_scale)
        self.rms_eps = rms_eps
        self.input_shape = (max_length, num_labels)

    def conf(self):
        D = self.d_model
        g = (NeuralNetConfiguration.builder()
             .seed(self.seed).weight_init("xavier")
             .updater(Sgd(learning_rate=1e-3))
             .dtype(self.dtype)
             .graph_builder()
             .add_inputs("tokens")
             .set_input_types(InputType.recurrent(self.num_labels,
                                                  self.max_length)))
        g.add_layer("embed", DenseLayer(n_out=D, activation="identity"),
                    "tokens")
        g.add_vertex("embed_scaled",
                     ScaleVertex(scale=self.embedding_multiplier), "embed")
        x = "embed_scaled"
        norm = lambda: RMSNormalization(eps=self.rms_eps)  # noqa: E731
        for i, kind in enumerate(self.layer_types):
            g.add_layer(f"n{i}a", norm(), x)
            g.add_layer(f"attn{i}", SelfAttentionLayer(
                n_out=D, causal=True, helper="stock", has_bias=False,
                **self.attention,
                **(self.sliding if kind == "sliding" else {})), f"n{i}a")
            g.add_layer(f"n{i}c", norm(), f"attn{i}")
            g.add_vertex(f"res{i}a", ElementWiseVertex(op="add"),
                         x, f"n{i}c")
            g.add_layer(f"n{i}b", norm(), f"res{i}a")
            if i < self.dense_layers:
                ffn = GatedFeedForwardLayer(n_out=D, activation="silu",
                                            hidden=self.mlp_width)
            else:
                ffn = MixtureOfExpertsLayer(
                    n_out=D, activation="silu", dispatch="routed",
                    gated=True, has_bias=False, **self.experts)
            g.add_layer(f"ffn{i}", ffn, f"n{i}b")
            g.add_layer(f"n{i}d", norm(), f"ffn{i}")
            g.add_vertex(f"res{i}b", ElementWiseVertex(op="add"),
                         f"res{i}a", f"n{i}d")
            x = f"res{i}b"
        g.add_layer("n_f", norm(), x)
        g.add_layer("output",
                    RnnOutputLayer(n_out=self.num_labels,
                                   activation="softmax", loss="mcxent"),
                    "n_f")
        g.set_outputs("output")
        return g.build()

    def model_type(self) -> str:
        return "ComputationGraph"


def lm_stream_forward(net):
    """One streaming forward chunk through ``net`` as a pure function:
    ``fwd(params, state, x, carry, mask=None) -> (out, new_carry)``.

    Papering over the MultiLayerNetwork/ComputationGraph `_forward`
    signature split in ONE place so every decode program family —
    `_device_generate`'s fused scan, GenerationServer's prefill-into-slot
    and pooled decode step — traces the same forward."""
    is_graph = hasattr(net.conf, "network_inputs")

    def fwd(params, state, x, carry, mask=None):
        if is_graph:
            outs, _, new_carry, _, _ = net._forward(
                params, state, [x], [mask], train=False, rng=None,
                carry=carry)
            return outs[0], new_carry
        out, _, new_carry, _ = net._forward(params, state, x, mask,
                                            train=False, rng=None,
                                            carry=carry)
        return out, new_carry

    return fwd


def kth_largest(logits, k):
    """Every row's k-th largest value, by selection: ``[B, V]`` floats and
    ``[B]`` ints -> ``[B, 1]``, for ``1 <= k <= V`` bit for bit what
    ``jnp.sort(logits)[V - k]`` holds; a row with ``k <= 0`` gets NaN (the
    sampler's mask does not read it).

    A float's bits, with the low ones of a negative flipped, order as the
    floats do. The answer is the largest such key with at least ``k`` keys
    at or above it, settled a bit at a time from the sign down: one
    compare-and-count over the row a bit, no sort and no bound on ``k``
    (at ``[16, 261120]`` on a v5e 0.2 ms against the sort's 5.3: PERF.md,
    PR 37)."""
    import jax
    import jax.numpy as jnp
    import numpy as np_

    nbits = jnp.finfo(logits.dtype).bits
    itype = np_.dtype(f"int{nbits}")
    # the sign bit alone (as a key, the lowest of all) and every other bit
    sign, rest = np_.iinfo(itype).min, np_.iinfo(itype).max

    def ordered(bits):                        # its own inverse
        return jnp.where(bits < 0, bits ^ rest, bits)

    keys = ordered(jax.lax.bitcast_convert_type(logits, itype))
    k = k[:, None]

    def settle(cut, bit):
        # ``bit`` is still clear in ``cut``, so flipping it sets it (the
        # sign's turn comes first, on the lowest key: flipped, that is 0)
        raised = cut ^ bit
        n = jnp.sum(keys >= raised, axis=-1, keepdims=True, dtype=jnp.int32)
        return jnp.where(n >= k, raised, cut), None

    bits = np_.array([sign] + [1 << b for b in range(nbits - 2, -1, -1)],
                     itype)
    cut, _ = jax.lax.scan(settle, jnp.full(k.shape, sign, itype), bits)
    return jax.lax.bitcast_convert_type(ordered(cut), logits.dtype)


def sampled_next_token(probs, keys, temperature, top_k):
    """Next-token select with TRACED per-row sampling params.

    probs: [B, V] softmax outputs; keys: [B, 2] uint32 PRNG keys;
    temperature/top_k: [B] float/int arrays — traced VALUES, not static
    args, so a batch mixing greedy and sampled requests (any temp/top_k
    combination) shares one compiled program. Rows with temperature <= 0
    take the argmax — the same op `_device_generate` compiles for its
    greedy path, so greedy results are bit-identical between the two.
    """
    import jax
    import jax.numpy as jnp

    greedy = jnp.argmax(probs, axis=-1)
    logits = jnp.log(jnp.maximum(probs, 1e-30)) \
        / jnp.maximum(temperature, 1e-30)[:, None]
    # per-row k-th-largest threshold; top_k <= 0 rows disable the cut
    kth = kth_largest(logits, top_k)
    cut = (top_k[:, None] > 0) & (logits < kth)
    logits = jnp.where(cut, -1e30, logits)
    sampled = jax.vmap(jax.random.categorical)(keys, logits)
    return jnp.where(temperature <= 0, greedy, sampled)


def spec_verify_tokens(probs, base_keys, counts, temperature, top_k):
    """Target-model token selection at K consecutive positions per row —
    the verification half of speculative decoding.

    probs: [B, K, V] softmax outputs of one chunked forward over
    [last_token, draft_1, ..., draft_{K-1}]; base_keys: [B, 2] uint32;
    counts: [B] index of the FIRST token being selected; temperature /
    top_k: [B] traced per-row values. Position i of row b selects with
    ``fold_in(base_keys[b], counts[b] + i)`` — the SAME key schedule the
    serial decode uses for that token index, which is what makes
    speculative acceptance bit-exact: every emitted token is literally
    the target model's selection under the serial schedule, regardless
    of what the draft proposed."""
    import jax
    import jax.numpy as jnp

    B, K, V = probs.shape
    idx = counts[:, None] + jnp.arange(K, dtype=counts.dtype)   # [B, K]
    keys = jax.vmap(jax.vmap(jax.random.fold_in, (None, 0)),
                    (0, 0))(base_keys, idx)                     # [B, K, 2]
    flat = sampled_next_token(probs.reshape(B * K, V),
                              keys.reshape(B * K, 2),
                              jnp.repeat(temperature, K),
                              jnp.repeat(top_k, K))
    return flat.reshape(B, K)


def greedy_generate(net, prompt_ids, steps: int, vocab: int,
                    device_loop: bool = True):
    """Greedy decoding — ``sample_generate`` with temperature 0 (see
    there for the KV-cache / device-loop mechanics)."""
    return sample_generate(net, prompt_ids, steps, vocab,
                           temperature=0.0, device_loop=device_loop)


def sample_generate(net, prompt_ids, steps: int, vocab: int,
                    temperature: float = 1.0, top_k: int = 0,
                    seed: int = 0, device_loop: bool = True):
    """Autoregressive decoding via KV-cache streaming: the prompt is
    consumed once, then each new token costs ONE incremental attention
    row (cached keys/values — O(T) per token) instead of a full O(T^2)
    re-forward. Works with any one-hot-input causal LM (TransformerLM;
    TextGenerationLSTM streams through its h/c the same way).

    ``temperature``: 0 = greedy argmax; otherwise tokens are sampled
    from softmax probabilities sharpened by 1/temperature (the
    char-modelling example's sampleFromDistribution semantics).
    ``top_k``: when > 0, restrict sampling to the k most likely tokens.

    ``device_loop=True`` (default) compiles the WHOLE decode as one XLA
    program — a ``lax.scan`` whose body is forward + next-token select +
    one-hot feedback (sampling uses jax.random.categorical with a
    per-step folded key) — so the host pays a single dispatch instead of
    one round-trip per token. ``device_loop=False`` streams through
    ``rnn_time_step`` one token at a time (same math, host-driven;
    sampling then uses numpy's RNG, so the two paths agree exactly only
    at temperature 0).

    prompt_ids: [B, T0] int array. Returns [B, steps] generated ids.
    """
    import numpy as np_

    if steps < 1:
        raise ValueError(f"steps must be >= 1, got {steps}")
    if temperature < 0:
        raise ValueError(f"temperature must be >= 0, got {temperature}")
    if top_k < 0 or top_k > vocab:
        raise ValueError(f"top_k must be in [0, vocab], got {top_k}")
    prompt_ids = np_.asarray(prompt_ids)
    if device_loop:
        return np_.asarray(_device_generate(net, prompt_ids, steps, vocab,
                                            temperature, top_k, seed))

    rs = np_.random.RandomState(seed)

    def pick(probs):  # [B, V] -> [B]
        if temperature <= 0:
            return probs.argmax(-1)
        logp = np_.log(np_.maximum(probs, 1e-30)) / temperature
        if top_k > 0:
            kth = np_.sort(logp, axis=-1)[:, -top_k][:, None]
            logp = np_.where(logp >= kth, logp, -1e30)
        p = np_.exp(logp - logp.max(-1, keepdims=True))
        p /= p.sum(-1, keepdims=True)
        return np_.stack([rs.choice(vocab, p=row) for row in p])

    eye = np_.eye(vocab, dtype=np_.float32)
    net.rnn_clear_previous_state()
    out = net.rnn_time_step(eye[prompt_ids])          # [B, T0, V]
    last = pick(np_.asarray(out)[:, -1])              # [B]
    generated = [last]
    for _ in range(steps - 1):
        out = net.rnn_time_step(eye[last][:, None, :])  # [B, 1, V]
        last = pick(np_.asarray(out)[:, 0])
        generated.append(last)
    return np_.stack(generated, axis=1)


def _device_generate(net, prompt_ids, steps: int, vocab: int,
                     temperature: float, top_k: int, seed: int):
    """One jitted program: consume the prompt, then lax.scan the
    token-by-token decode on device (KV caches ride in the scan carry)."""
    import jax
    import jax.numpy as jnp

    B = prompt_ids.shape[0]
    # generation is its own stream: any live rnn_time_step stream is
    # CLEARED (seeding below resets the overflow accounting, so leaving
    # the old carry in place would let a continued stream bypass the
    # guard and silently clamp-corrupt its cache)
    net.rnn_clear_previous_state()
    carry0 = net._seed_streaming_carry(B)
    cap = net._stream_capacity
    needed = prompt_ids.shape[1] + steps - 1
    if cap is not None and needed > cap:
        raise ValueError(
            f"KV cache overflow: prompt + generated positions ({needed}) "
            f"> max_cache ({cap}); raise SelfAttentionLayer.max_cache")

    # one compiled program per (shapes, steps, sampling config): cached
    # on the net like rnn_time_step's step fn — a serving loop must not
    # re-trace the whole scan program per request
    # at temperature 0 the traced pick() is a pure argmax that ignores
    # top_k: normalize it out of the key so greedy programs are not
    # recompiled once per distinct (ignored) top_k value
    key = ("generate", B, prompt_ids.shape[1], steps, vocab,
           float(temperature), int(top_k) if temperature > 0 else 0)
    if key not in net._output_cache:
        fwd = lm_stream_forward(net)

        def pick(probs, k):  # [B, V], key -> [B]
            if temperature <= 0:
                return jnp.argmax(probs, axis=-1)
            logits = jnp.log(jnp.maximum(probs, 1e-30)) / temperature
            if top_k > 0:
                kth = kth_largest(
                    logits, jnp.full(logits.shape[:1], top_k, jnp.int32))
                logits = jnp.where(logits >= kth, logits, -1e30)
            return jax.random.categorical(k, logits)

        def generate(params, state, prompt_onehot, carry, rng):
            out, carry = fwd(params, state, prompt_onehot, carry)
            last = pick(out[:, -1], jax.random.fold_in(rng, 0))
            if steps == 1:
                return last[:, None]

            def body(c, i):
                carry, last = c
                x = jax.nn.one_hot(last, vocab,
                                   dtype=prompt_onehot.dtype)[:, None, :]
                o, carry = fwd(params, state, x, carry)
                nxt = pick(o[:, 0], jax.random.fold_in(rng, i))
                return (carry, nxt), nxt

            (_, _), rest = jax.lax.scan(body, (carry, last),
                                        jnp.arange(1, steps))
            return jnp.concatenate([last[:, None],
                                    jnp.moveaxis(rest, 0, 1)], axis=1)

        net._output_cache[key] = jax.jit(generate)

    eye = jnp.eye(vocab, dtype=jnp.dtype(net.conf.dtype))
    out = net._output_cache[key](net.params, net.state, eye[prompt_ids],
                                 carry0, jax.random.PRNGKey(seed))
    # the generation stream's carry lived only inside the program;
    # leave the net with no half-open stream
    net.rnn_clear_previous_state()
    return out


def zoo_models() -> dict:
    """Name -> ZooModel class registry (reference: zoo/ModelSelector.java;
    ``transformerlm`` is beyond-parity)."""
    return {
        "alexnet": AlexNet,
        "facenetnn4small2": FaceNetNN4Small2,
        "googlenet": GoogLeNet,
        "inceptionresnetv1": InceptionResNetV1,
        "lenet": LeNet,
        "resnet50": ResNet50,
        "simplecnn": SimpleCNN,
        "textgenlstm": TextGenerationLSTM,
        "transformerlm": TransformerLM,
        "granitemoehybridlm": GraniteMoeHybridLM,
        "falconh1lm": FalconH1LM,
        "deepseekv2lm": DeepSeekV2LM,
        "trinitylm": TrinityLM,
        "vgg16": VGG16,
        "vgg19": VGG19,
    }
