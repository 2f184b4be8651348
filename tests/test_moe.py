"""Mixture-of-Experts layer: routing semantics, gradients, serde, training
quality, and expert-parallel sharding parity."""

import numpy as np
import pytest

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.nn.conf.builders import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import (
    DenseLayer,
    MixtureOfExpertsLayer,
    OutputLayer,
)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.nn.updater import Adam, Sgd


def _net(top_k=2, n_experts=4, lb=0.0, dtype=None):
    b = (NeuralNetConfiguration.builder()
         .seed(2).updater(Adam(learning_rate=0.01)))
    if dtype:
        b = b.dtype(dtype)
    conf = (b.list(MixtureOfExpertsLayer(n_out=16, n_experts=n_experts,
                                         top_k=top_k, expert_hidden=24,
                                         load_balance_coef=lb),
                   OutputLayer(n_out=3, activation="softmax",
                               loss="mcxent"))
            .set_input_type(InputType.feed_forward(6)).build())
    return MultiLayerNetwork(conf).init()


class TestRouting:
    def test_topk_gates_sparse_and_normalized(self):
        import jax.numpy as jnp
        net = _net(top_k=2, n_experts=5)
        layer = net.layers[0]
        rs = np.random.RandomState(0)
        x = jnp.asarray(rs.randn(8, 6), jnp.float32)
        gates = np.asarray(layer._gate(net.params["0"], x))
        assert gates.shape == (8, 5)
        assert ((gates > 0).sum(axis=1) <= 2).all()      # top-2 sparsity
        np.testing.assert_allclose(gates.sum(axis=1), 1.0, atol=1e-6)
        # exact top-k even under ties: a zero row gives uniform logits
        zgates = np.asarray(layer._gate(net.params["0"],
                                        jnp.zeros((1, 6), jnp.float32)))
        assert (zgates > 0).sum() == 2

    def test_top1_equals_argmax_expert(self):
        import jax.numpy as jnp
        net = _net(top_k=1, n_experts=3)
        layer = net.layers[0]
        p = net.params["0"]
        rs = np.random.RandomState(1)
        x = jnp.asarray(rs.randn(4, 6), jnp.float32)
        out, _ = layer.forward(p, {}, x)
        logits = np.asarray(x @ p["Wg"])
        pick = np.argmax(logits, axis=1)
        # manual single-expert FFN for each example
        import jax
        h = np.maximum(np.einsum("bd,edh->beh", np.asarray(x),
                                 np.asarray(p["W1"]))
                       + np.asarray(p["b1"]), 0)
        y = np.einsum("beh,eho->beo", h, np.asarray(p["W2"])) \
            + np.asarray(p["b2"])
        expected = y[np.arange(4), pick]
        np.testing.assert_allclose(np.asarray(out), expected, atol=1e-5)

    def test_full_softmax_when_topk_equals_experts(self):
        net = _net(top_k=4, n_experts=4)
        rs = np.random.RandomState(2)
        out = net.output(rs.randn(5, 6).astype(np.float32))
        assert np.asarray(out).shape == (5, 3)


class TestTraining:
    def test_gradcheck_through_moe(self):
        # top_k == n_experts: the gate is a plain softmax and the whole
        # layer is smooth, so central differences validate every einsum /
        # FFN / gate gradient. (With top_k < E the hard selection is
        # piecewise-constant BY DESIGN — finite differences straddling a
        # routing boundary measure the jump, not the gradient; autodiff
        # within a region is exercised by the training test.)
        from deeplearning4j_tpu.gradientcheck import check_gradients
        net = _net(top_k=4, n_experts=4, dtype="float64")
        rs = np.random.RandomState(3)
        x = rs.randn(4, 6)
        y = np.eye(3)[rs.randint(0, 3, 4)]
        # 400 of the net's 2,347 scalars, drawn at random over every
        # parameter tensor (walking all of them is 4,694 loss evaluations)
        assert check_gradients(net, x, y, subset=400)

    def test_learns_partitioned_function(self):
        # two input regimes with different linear maps: an MoE should
        # specialize experts and beat chance easily
        rs = np.random.RandomState(4)
        n = 256
        regime = rs.randint(0, 2, n)
        x = rs.randn(n, 6).astype(np.float32)
        x[:, 0] = regime * 4 - 2           # routing signal
        labels = np.where(regime == 0,
                          (x[:, 1] > 0).astype(int),
                          2 * (x[:, 2] > 0).astype(int))
        y = np.eye(3, dtype=np.float32)[labels]
        net = _net(top_k=1)
        ds = DataSet(x, y)
        for _ in range(150):
            net.fit(ds)
        pred = np.argmax(np.asarray(net.output(x)), 1)
        assert (pred == labels).mean() > 0.9

    def test_serde_round_trip(self, tmp_path):
        from deeplearning4j_tpu.utils.model_serializer import (load_model,
                                                               save_model)
        net = _net()
        p = str(tmp_path / "moe.zip")
        save_model(net, p)
        back = load_model(p)
        rs = np.random.RandomState(5)
        x = rs.randn(3, 6).astype(np.float32)
        np.testing.assert_allclose(np.asarray(back.output(x)),
                                   np.asarray(net.output(x)), atol=1e-6)
        assert back.layers[0].n_experts == 4

    def test_validation(self):
        with pytest.raises(ValueError):
            _net(top_k=9, n_experts=4)


class TestExpertParallel:
    def test_sharded_matches_single_device(self):
        from deeplearning4j_tpu.parallel import data_model_mesh
        from deeplearning4j_tpu.parallel.model_sharding import (
            network_param_specs, shard_network)
        from jax.sharding import PartitionSpec as P

        rs = np.random.RandomState(6)
        labels = rs.randint(0, 3, 32)
        x = (rs.randn(32, 6) + labels[:, None]).astype(np.float32)
        y = np.eye(3, dtype=np.float32)[labels]
        ds = DataSet(x, y)

        def build():
            conf = (NeuralNetConfiguration.builder()
                    .seed(7).updater(Sgd(learning_rate=0.05))
                    .list(MixtureOfExpertsLayer(n_out=16, n_experts=4,
                                                top_k=2, expert_hidden=24),
                          OutputLayer(n_out=3, activation="softmax",
                                      loss="mcxent"))
                    .set_input_type(InputType.feed_forward(6)).build())
            return MultiLayerNetwork(conf).init()

        single = build()
        sharded = build()
        mesh = data_model_mesh(2, 4)
        specs = network_param_specs(sharded, 4)
        # expert tensors shard on the EXPERT axis
        assert specs["0"]["W1"] == P("model", None, None)
        assert specs["0"]["b1"] == P("model", None)
        shard_network(sharded, mesh)
        for _ in range(4):
            single.do_step(x, y)
            sharded.do_step(x, y)
        np.testing.assert_allclose(np.asarray(sharded.params_flat()),
                                   np.asarray(single.params_flat()),
                                   atol=1e-5)
