"""ComputationGraph tests.

Ports the intent of the reference's CompGraph suites: gradient checks
(gradientcheck/GradientCheckTestsComputationGraph.java), basic graph tests
(nn/graph/ComputationGraphTestRNN.java / TestComputationGraphNetwork.java) —
topo/cycle validation, multi-input/output fit, vertex ops, serialization
round-trip, skip-connection training.
"""

import numpy as np
import pytest

from deeplearning4j_tpu.datasets.dataset import DataSet, MultiDataSet
from deeplearning4j_tpu.gradientcheck import check_gradients
from deeplearning4j_tpu.nn.conf.builders import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.graph_conf import (
    DuplicateToTimeSeriesVertex,
    ElementWiseVertex,
    L2NormalizeVertex,
    L2Vertex,
    LastTimeStepVertex,
    MergeVertex,
    ReshapeVertex,
    ScaleVertex,
    ShiftVertex,
    StackVertex,
    SubsetVertex,
    UnstackVertex,
)
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers.convolution import (
    ConvolutionLayer,
    SubsamplingLayer,
)
from deeplearning4j_tpu.nn.conf.layers.core import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.conf.layers.recurrent import LSTM, RnnOutputLayer
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.updater import Adam, Sgd


def _rs(seed=0):
    return np.random.RandomState(seed)


def _onehot(idx, n):
    return np.eye(n, dtype=np.float64)[idx]


def _simple_graph(updater=None, dtype="float64"):
    """x -> dense a, dense b -> merge -> out (2-branch merge)."""
    conf = (NeuralNetConfiguration.builder()
            .seed(12345)
            .updater(updater or Sgd(learning_rate=0.1))
            .dtype(dtype)
            .graph_builder()
            .add_inputs("in")
            .add_layer("a", DenseLayer(n_out=5, activation="tanh"), "in")
            .add_layer("b", DenseLayer(n_out=4, activation="relu"), "in")
            .add_vertex("merge", MergeVertex(), "a", "b")
            .add_layer("out", OutputLayer(n_out=3, activation="softmax",
                                          loss="mcxent"), "merge")
            .set_outputs("out")
            .set_input_types(InputType.feed_forward(6))
            .build())
    return ComputationGraph(conf).init()


class TestGraphStructure:
    def test_topo_sort_and_nin_inference(self):
        net = _simple_graph()
        conf = net.conf
        assert conf.topo_order.index("a") < conf.topo_order.index("merge")
        assert conf.topo_order.index("b") < conf.topo_order.index("merge")
        assert conf.topo_order.index("merge") < conf.topo_order.index("out")
        # nIn inferred through merge: 5 + 4 = 9
        assert conf.vertices["out"].layer.n_in == 9
        assert conf.vertices["a"].layer.n_in == 6

    def test_cycle_detection(self):
        b = (NeuralNetConfiguration.builder().graph_builder()
             .add_inputs("in")
             .add_layer("a", DenseLayer(n_in=3, n_out=3), "b")
             .add_layer("b", DenseLayer(n_in=3, n_out=3), "a")
             .set_outputs("b"))
        with pytest.raises(ValueError, match="[Cc]ycle"):
            b.build()

    def test_dangling_input_rejected(self):
        b = (NeuralNetConfiguration.builder().graph_builder()
             .add_inputs("in")
             .add_layer("a", DenseLayer(n_in=3, n_out=3), "nope")
             .set_outputs("a"))
        with pytest.raises(ValueError, match="not a network input"):
            b.build()

    def test_duplicate_name_rejected(self):
        b = (NeuralNetConfiguration.builder().graph_builder()
             .add_inputs("in")
             .add_layer("a", DenseLayer(n_in=3, n_out=3), "in"))
        with pytest.raises(ValueError, match="[Dd]uplicate"):
            b.add_layer("a", DenseLayer(n_in=3, n_out=3), "in")


class TestGraphGradients:
    """CompGraph gradient checks (reference:
    GradientCheckTestsComputationGraph.java)."""

    def test_merge_graph_gradients(self):
        net = _simple_graph()
        rs = _rs(1)
        x = rs.randn(4, 6)
        y = _onehot(rs.randint(0, 3, 4), 3)
        assert check_gradients(net, x, y, eps=1e-6, max_rel_error=1e-5)

    def test_elementwise_add_skip_connection_gradients(self):
        conf = (NeuralNetConfiguration.builder()
                .seed(7).updater(Sgd(learning_rate=0.1)).dtype("float64")
                .graph_builder()
                .add_inputs("in")
                .add_layer("d1", DenseLayer(n_out=5, activation="tanh"), "in")
                .add_layer("d2", DenseLayer(n_out=5, activation="tanh"), "d1")
                .add_vertex("add", ElementWiseVertex(op="add"), "d1", "d2")
                .add_layer("out", OutputLayer(n_out=2, activation="softmax",
                                              loss="mcxent"), "add")
                .set_outputs("out")
                .set_input_types(InputType.feed_forward(4))
                .build())
        net = ComputationGraph(conf).init()
        rs = _rs(2)
        x = rs.randn(3, 4)
        y = _onehot(rs.randint(0, 2, 3), 2)
        assert check_gradients(net, x, y, eps=1e-6, max_rel_error=1e-5)

    def test_multi_input_multi_output_gradients(self):
        conf = (NeuralNetConfiguration.builder()
                .seed(3).updater(Sgd(learning_rate=0.1)).dtype("float64")
                .graph_builder()
                .add_inputs("in1", "in2")
                .add_layer("d1", DenseLayer(n_out=4, activation="tanh"), "in1")
                .add_layer("d2", DenseLayer(n_out=4, activation="tanh"), "in2")
                .add_vertex("merge", MergeVertex(), "d1", "d2")
                .add_layer("shared", DenseLayer(n_out=6, activation="tanh"),
                           "merge")
                .add_layer("out1", OutputLayer(n_out=2, activation="softmax",
                                               loss="mcxent"), "shared")
                .add_layer("out2", OutputLayer(n_out=3, activation="identity",
                                               loss="mse"), "shared")
                .set_outputs("out1", "out2")
                .set_input_types(InputType.feed_forward(3),
                                 InputType.feed_forward(5))
                .build())
        net = ComputationGraph(conf).init()
        rs = _rs(4)
        x = [rs.randn(3, 3), rs.randn(3, 5)]
        y = [_onehot(rs.randint(0, 2, 3), 2), rs.randn(3, 3)]
        assert check_gradients(net, x, y, eps=1e-6, max_rel_error=1e-5)

    def test_lstm_last_time_step_gradients(self):
        conf = (NeuralNetConfiguration.builder()
                .seed(5).updater(Sgd(learning_rate=0.1)).dtype("float64")
                .graph_builder()
                .add_inputs("in")
                .add_layer("lstm", LSTM(n_out=4, activation="tanh"), "in")
                .add_vertex("last", LastTimeStepVertex(mask_input="in"), "lstm")
                .add_layer("out", OutputLayer(n_out=2, activation="softmax",
                                              loss="mcxent"), "last")
                .set_outputs("out")
                .set_input_types(InputType.recurrent(3))
                .build())
        net = ComputationGraph(conf).init()
        rs = _rs(6)
        x = rs.randn(2, 5, 3)
        y = _onehot(rs.randint(0, 2, 2), 2)
        mask = np.array([[1, 1, 1, 0, 0], [1, 1, 1, 1, 1]], np.float64)
        assert check_gradients(net, x, y, input_mask=mask, eps=1e-6,
                               max_rel_error=1e-5)


class TestVertexOps:
    def _run_vertex(self, vertex, inputs):
        out, _ = vertex.forward({}, {}, [np.asarray(a) for a in inputs])
        return np.asarray(out)

    def test_elementwise_ops(self):
        a = np.array([[1.0, 2.0]])
        b = np.array([[3.0, -1.0]])
        assert np.allclose(self._run_vertex(ElementWiseVertex(op="add"),
                                            [a, b]), [[4, 1]])
        assert np.allclose(self._run_vertex(ElementWiseVertex(op="subtract"),
                                            [a, b]), [[-2, 3]])
        assert np.allclose(self._run_vertex(ElementWiseVertex(op="product"),
                                            [a, b]), [[3, -2]])
        assert np.allclose(self._run_vertex(ElementWiseVertex(op="average"),
                                            [a, b]), [[2, 0.5]])
        assert np.allclose(self._run_vertex(ElementWiseVertex(op="max"),
                                            [a, b]), [[3, 2]])

    def test_subset_vertex_inclusive(self):
        x = np.arange(12.0).reshape(2, 6)
        out = self._run_vertex(SubsetVertex(from_index=1, to_index=3), [x])
        assert out.shape == (2, 3)
        assert np.allclose(out, x[:, 1:4])

    def test_stack_unstack_roundtrip(self):
        a = _rs(0).randn(2, 3)
        b = _rs(1).randn(2, 3)
        stacked = self._run_vertex(StackVertex(), [a, b])
        assert stacked.shape == (4, 3)
        back = self._run_vertex(UnstackVertex(from_index=1, stack_size=2),
                                [stacked])
        assert np.allclose(back, b)

    def test_scale_shift(self):
        x = np.ones((2, 2))
        assert np.allclose(self._run_vertex(ScaleVertex(scale=3.0), [x]), 3.0)
        assert np.allclose(self._run_vertex(ShiftVertex(shift=-1.5), [x]), -0.5)

    def test_l2_vertex(self):
        a = np.array([[3.0, 0.0], [0.0, 0.0]])
        b = np.array([[0.0, 4.0], [0.0, 0.0]])
        out = self._run_vertex(L2Vertex(), [a, b])
        assert out.shape == (2, 1)
        assert np.allclose(out[0, 0], 5.0, atol=1e-3)

    def test_l2_normalize_vertex(self):
        x = np.array([[3.0, 4.0]])
        out = self._run_vertex(L2NormalizeVertex(), [x])
        assert np.allclose(out, [[0.6, 0.8]], atol=1e-4)

    def test_reshape_vertex(self):
        x = np.arange(24.0).reshape(2, 12)
        out = self._run_vertex(ReshapeVertex(shape=(3, 4)), [x])
        assert out.shape == (2, 3, 4)

    def test_last_time_step_noncontiguous_mask(self):
        """Interior-zero masks must pick the last *nonzero* step (reference:
        rnn/LastTimeStepVertex uses the final nonzero index)."""
        x = np.arange(2 * 4 * 3, dtype=np.float64).reshape(2, 4, 3)
        mask = np.array([[1, 0, 1, 0], [1, 1, 0, 0]], np.float64)
        v = LastTimeStepVertex(mask_input="in")
        out, _ = v.forward({}, {}, [x], ctx={"input_masks": {"in": mask}})
        assert np.allclose(out[0], x[0, 2])  # last active = index 2
        assert np.allclose(out[1], x[1, 1])

    def test_duplicate_to_time_series(self):
        x = np.array([[1.0, 2.0]])
        ref = np.zeros((1, 5, 7))
        v = DuplicateToTimeSeriesVertex(input_name="seq")
        out, _ = v.forward({}, {}, [x], ctx={"input_arrays": {"seq": ref},
                                             "input_masks": {}})
        assert out.shape == (1, 5, 2)
        assert np.allclose(out[0, 3], [1.0, 2.0])


class TestGraphTraining:
    def test_skip_connection_cnn_trains(self):
        """Residual-style CNN (the ResNet building block) trains: loss drops."""
        conf = (NeuralNetConfiguration.builder()
                .seed(42).updater(Adam(learning_rate=1e-2)).dtype("float32")
                .graph_builder()
                .add_inputs("in")
                .add_layer("c1", ConvolutionLayer(n_out=4, kernel_size=(3, 3),
                                                  padding=(1, 1),
                                                  activation="relu"), "in")
                .add_layer("c2", ConvolutionLayer(n_out=4, kernel_size=(3, 3),
                                                  padding=(1, 1),
                                                  activation="identity"), "c1")
                .add_vertex("res", ElementWiseVertex(op="add"), "c1", "c2")
                .add_layer("pool", SubsamplingLayer(kernel_size=(2, 2),
                                                    stride=(2, 2)), "res")
                .add_layer("out", OutputLayer(n_out=3, activation="softmax",
                                              loss="mcxent"), "pool")
                .set_outputs("out")
                .set_input_types(InputType.convolutional(8, 8, 2))
                .build())
        net = ComputationGraph(conf).init()
        rs = _rs(9)
        x = rs.randn(16, 8, 8, 2).astype(np.float32)
        y = _onehot(rs.randint(0, 3, 16), 3).astype(np.float32)
        first, _ = net.do_step(x, y)
        for _ in range(30):
            last, _ = net.do_step(x, y)
        assert last < first * 0.7

    def test_multi_io_fit_with_multidataset(self):
        conf = (NeuralNetConfiguration.builder()
                .seed(11).updater(Adam(learning_rate=1e-2)).dtype("float32")
                .graph_builder()
                .add_inputs("in1", "in2")
                .add_layer("d1", DenseLayer(n_out=8, activation="relu"), "in1")
                .add_layer("d2", DenseLayer(n_out=8, activation="relu"), "in2")
                .add_vertex("m", MergeVertex(), "d1", "d2")
                .add_layer("out1", OutputLayer(n_out=2, activation="softmax",
                                               loss="mcxent"), "m")
                .add_layer("out2", OutputLayer(n_out=1, activation="identity",
                                               loss="mse"), "m")
                .set_outputs("out1", "out2")
                .set_input_types(InputType.feed_forward(4),
                                 InputType.feed_forward(3))
                .build())
        net = ComputationGraph(conf).init()
        rs = _rs(12)
        mds = MultiDataSet([rs.randn(8, 4).astype(np.float32),
                            rs.randn(8, 3).astype(np.float32)],
                           [_onehot(rs.randint(0, 2, 8), 2).astype(np.float32),
                            rs.randn(8, 1).astype(np.float32)])
        s0 = net.score(mds)
        net.fit(mds, epochs=40)
        assert net.score(mds) < s0 * 0.8
        outs = net.output(*mds.features)
        assert outs[0].shape == (8, 2)
        assert outs[1].shape == (8, 1)

    def test_rnn_graph_tbptt(self):
        conf = (NeuralNetConfiguration.builder()
                .seed(21).updater(Adam(learning_rate=5e-3)).dtype("float32")
                .graph_builder()
                .add_inputs("in")
                .add_layer("lstm", LSTM(n_out=6, activation="tanh"), "in")
                .add_layer("out", RnnOutputLayer(n_out=2, activation="softmax",
                                                 loss="mcxent"), "lstm")
                .set_outputs("out")
                .set_input_types(InputType.recurrent(3))
                .t_bptt_lengths(4)
                .build())
        net = ComputationGraph(conf).init()
        rs = _rs(13)
        x = rs.randn(2, 12, 3).astype(np.float32)
        y = _onehot(rs.randint(0, 2, (2, 12)).ravel(), 2).reshape(
            2, 12, 2).astype(np.float32)
        ds = DataSet(x, y)
        s0 = net.score(ds)
        for _ in range(25):
            net.fit(ds)
        assert net.score(ds) < s0

    def test_evaluate_single_output(self):
        net = _simple_graph(updater=Adam(learning_rate=1e-2), dtype="float32")
        rs = _rs(14)
        x = rs.randn(30, 6).astype(np.float32)
        labels = rs.randint(0, 3, 30)
        y = _onehot(labels, 3).astype(np.float32)
        net.fit(DataSet(x, y), epochs=60)
        ev = net.evaluate(DataSet(x, y))
        assert ev.accuracy() > 0.5


class TestGraphSerialization:
    def test_json_roundtrip(self):
        net = _simple_graph()
        from deeplearning4j_tpu.nn.conf.graph_conf import \
            ComputationGraphConfiguration

        s = net.conf.to_json()
        conf2 = ComputationGraphConfiguration.from_json(s)
        assert conf2.topo_order == net.conf.topo_order
        assert conf2.network_outputs == net.conf.network_outputs
        assert conf2.vertices["out"].layer.n_in == 9
        net2 = ComputationGraph(conf2).init()
        assert net2.params_flat().size == net.params_flat().size

    def test_model_zip_roundtrip(self, tmp_path):
        from deeplearning4j_tpu.utils.model_serializer import (
            load_model,
            save_model,
        )

        net = _simple_graph(updater=Adam(learning_rate=1e-2), dtype="float32")
        rs = _rs(15)
        x = rs.randn(8, 6).astype(np.float32)
        y = _onehot(rs.randint(0, 3, 8), 3).astype(np.float32)
        net.fit(DataSet(x, y), epochs=3)
        p = str(tmp_path / "graph.zip")
        save_model(net, p)
        net2 = load_model(p)
        assert np.allclose(net.params_flat(), net2.params_flat())
        assert np.allclose(np.asarray(net.output(x)),
                           np.asarray(net2.output(x)), atol=1e-6)
        # restored model continues training
        s0 = net2.score(DataSet(x, y))
        net2.fit(DataSet(x, y), epochs=5)
        assert net2.score(DataSet(x, y)) < s0

    def test_flat_params_roundtrip(self):
        net = _simple_graph()
        flat = net.params_flat()
        flat2 = flat * 2.0
        net.set_params_flat(flat2)
        assert np.allclose(net.params_flat(), flat2)


class TestRemat:
    def test_remat_matches_plain_training_and_rematerializes(self):
        """jax.checkpoint vertices: numerically identical training, and
        the compiled HLO actually carries rematerialized computations."""
        import jax

        from deeplearning4j_tpu.datasets.dataset import DataSet
        from deeplearning4j_tpu.models import TransformerLM

        V, T = 7, 8
        rs = np.random.RandomState(3)
        idx = rs.randint(0, V, (4, T + 1))
        x = np.eye(V, dtype=np.float32)[idx[:, :-1]]
        y = np.eye(V, dtype=np.float32)[idx[:, 1:]]

        def train(remat):
            m = TransformerLM(num_labels=V, max_length=T, d_model=16,
                              n_heads=2, n_blocks=2, seed=9,
                              remat=remat).init()
            for _ in range(3):
                m.fit(DataSet(x, y))
            return m

        a, b = train(False), train(True)
        np.testing.assert_allclose(
            np.asarray(b.params_flat()), np.asarray(a.params_flat()),
            rtol=1e-5, atol=1e-6)

        # the jaxpr of the remat'd loss gradient contains remat calls
        def loss(params):
            val, _ = b._loss(params, b.state, [x], [y], None, None,
                             train=True, rng=jax.random.PRNGKey(0))
            return val
        jaxpr = str(jax.make_jaxpr(jax.grad(loss))(b.params))
        assert "remat" in jaxpr or "checkpoint" in jaxpr
