"""Pipeline parallelism tests: stage balancing, and the GPipe parity
contract — microbatched pipeline training over multiple devices equals
single-device full-batch training."""

import numpy as np
import pytest

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.nn.conf.builders import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import (
    ConvolutionLayer,
    DenseLayer,
    OutputLayer,
    SubsamplingLayer,
)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.nn.updater import Adam, Sgd
from deeplearning4j_tpu.parallel.pipeline import (PipelineTrainer,
                                                  balanced_stages)


def _mlp(updater):
    conf = (NeuralNetConfiguration.builder()
            .seed(3).updater(updater)
            .list(DenseLayer(n_out=32, activation="tanh"),
                  DenseLayer(n_out=24, activation="relu"),
                  DenseLayer(n_out=16, activation="tanh"),
                  OutputLayer(n_out=3, activation="softmax", loss="mcxent"))
            .set_input_type(InputType.feed_forward(6)).build())
    return MultiLayerNetwork(conf).init()


def _data(seed=0, n=32):
    rs = np.random.RandomState(seed)
    labels = rs.randint(0, 3, n)
    return ((rs.randn(n, 6) + labels[:, None]).astype(np.float64),
            np.eye(3)[labels])


class TestStageBalance:
    def test_contiguous_cover_all_layers(self):
        net = _mlp(Sgd(learning_rate=0.1))
        for n_stages in (2, 3, 4):
            stages = balanced_stages(net, n_stages)
            assert len(stages) == n_stages
            flat = [i for st in stages for i in st]
            assert flat == list(range(len(net.layers)))


class TestPipelineParity:
    # atol 1e-8 was set against XLA's optimised CPU code; unoptimised, the
    # reordered additions of the 4-stage case land 2 ulps apart
    @pytest.mark.usefixtures("full_xla_optimizations")
    @pytest.mark.parametrize("updater,stages,micro,atol", [
        # SGD is linear in the gradient: microbatch sum/M reorders float
        # additions only -> exact. Adam's m/sqrt(v)+eps amplifies the
        # reordering noise to ~1e-7 (stable, non-accumulating).
        (Sgd(learning_rate=0.1), 2, 4, 1e-8),
        (Sgd(learning_rate=0.1), 4, 2, 1e-8),
        (Adam(learning_rate=0.01), 2, 4, 1e-6),
    ])
    def test_matches_single_device(self, updater, stages, micro, atol):
        x, y = _data()
        single = _mlp(updater)
        pipe_net = _mlp(updater)
        pt = PipelineTrainer(pipe_net, n_stages=stages, n_micro=micro)
        for _ in range(4):
            single.do_step(x, y)
            pt.do_step(x, y)
        pt._sync_back()
        np.testing.assert_allclose(pipe_net.params_flat(),
                                   single.params_flat(), atol=atol)
        assert pt.iteration == 4

    def test_fit_and_predict_through_wrapped_net(self):
        x, y = _data(1, 64)
        net = _mlp(Adam(learning_rate=0.05))
        pt = PipelineTrainer(net, n_stages=2, n_micro=4)
        s0 = None
        for _ in range(30):
            s = pt.do_step(x, y)
            s0 = s0 or s
        pt._sync_back()
        assert pt.score_value < s0  # learning
        pred = np.argmax(np.asarray(net.output(x.astype(np.float32))), 1)
        assert (pred == np.argmax(y, 1)).mean() > 0.8

    def test_conv_stack_pipeline(self):
        conf = (NeuralNetConfiguration.builder()
                .seed(5).updater(Sgd(learning_rate=0.05))
                .list(ConvolutionLayer(n_out=4, kernel_size=(3, 3),
                                       activation="relu"),
                      SubsamplingLayer(kernel_size=(2, 2), stride=(2, 2)),
                      DenseLayer(n_out=16, activation="relu"),
                      OutputLayer(n_out=2, activation="softmax",
                                  loss="mcxent"))
                .set_input_type(InputType.convolutional(8, 8, 1)).build())
        rs = np.random.RandomState(0)
        x = rs.randn(16, 8, 8, 1).astype(np.float32)
        y = np.eye(2, dtype=np.float32)[rs.randint(0, 2, 16)]
        single = MultiLayerNetwork(conf).init()
        pnet = MultiLayerNetwork(conf).init()
        pt = PipelineTrainer(pnet, n_stages=2, n_micro=4)
        for _ in range(3):
            single.do_step(x, y)
            pt.do_step(x, y)
        pt._sync_back()
        np.testing.assert_allclose(pnet.params_flat(),
                                   single.params_flat(), atol=1e-8)

    def test_regularization_clipping_and_layer_lr_parity(self):
        """The silent-parity-gap traps: l2 weight decay, gradient
        clipping, and per-layer LR overrides must all flow through the
        pipeline exactly as on a single device."""
        def build():
            conf = (NeuralNetConfiguration.builder()
                    .seed(9).updater(Sgd(learning_rate=0.1))
                    .l2(1e-3)
                    .gradient_normalization("clip_l2_per_layer")
                    .gradient_normalization_threshold(0.5)
                    .list(DenseLayer(n_out=24, activation="tanh"),
                          DenseLayer(n_out=16, activation="relu",
                                     learning_rate=0.02),
                          OutputLayer(n_out=3, activation="softmax",
                                      loss="mcxent"))
                    .set_input_type(InputType.feed_forward(6)).build())
            return MultiLayerNetwork(conf).init()

        x, y = _data(7)
        single = build()
        pnet = build()
        pt = PipelineTrainer(pnet, n_stages=2, n_micro=4)
        for _ in range(4):
            single.do_step(x, y)
            pt.do_step(x, y)
        pt._sync_back()
        np.testing.assert_allclose(pnet.params_flat(),
                                   single.params_flat(), atol=1e-8)

    def test_dropout_is_active_under_pipeline(self):
        conf = (NeuralNetConfiguration.builder()
                .seed(4).updater(Sgd(learning_rate=0.0))
                .list(DenseLayer(n_out=64, activation="identity",
                                 dropout=0.5),
                      OutputLayer(n_out=3, activation="softmax",
                                  loss="mcxent"))
                .set_input_type(InputType.feed_forward(6)).build())
        net = MultiLayerNetwork(conf).init()
        pt = PipelineTrainer(net, n_stages=2, n_micro=2)
        x, y = _data(8, 16)
        # lr=0: params frozen; the LOSS still varies across steps iff the
        # dropout masks are actually being drawn
        losses = {round(pt.do_step(x, y), 10) for _ in range(4)}
        assert len(losses) > 1, "dropout inactive: identical losses"

    def test_bn_running_stats_update_in_last_stage(self):
        from deeplearning4j_tpu.nn.conf.layers import BatchNormalization
        conf = (NeuralNetConfiguration.builder()
                .seed(6).updater(Sgd(learning_rate=0.01))
                .list(DenseLayer(n_out=8, activation="relu"),
                      BatchNormalization(),
                      OutputLayer(n_out=3, activation="softmax",
                                  loss="mcxent"))
                .set_input_type(InputType.feed_forward(6)).build())
        net = MultiLayerNetwork(conf).init()
        pt = PipelineTrainer(net, n_stages=2, n_micro=2)
        bn_stage = next(s for s, idxs in enumerate(pt.stages) if 1 in idxs)
        assert bn_stage == len(pt.stages) - 1  # BN sits in the LAST stage
        x, y = _data(9, 16)
        for _ in range(3):
            pt.do_step(x, y)
        pt._sync_back()
        mean = np.asarray(net.state["1"]["mean"])
        assert not np.allclose(mean, 0.0), "BN running stats never updated"

    def test_indivisible_batch_rejected(self):
        net = _mlp(Sgd(learning_rate=0.1))
        pt = PipelineTrainer(net, n_stages=2, n_micro=4)
        x, y = _data(2, 30)
        with pytest.raises(ValueError):
            pt.do_step(x, y)
