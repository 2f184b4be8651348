"""How the serving programs embed token ids (``zoo.lm_stream_forward``):
where the token input feeds one plain ``DenseLayer`` over the vocabulary
(``zoo.embedding_vertex``: every zoo LM), that vertex's output is its
table's rows, read by a gather (``DenseLayer.rows``); every other net takes
a one-hot block times the whole table, as before. A one-hot row's product
has one non-zero term, so the two give the same bits: the logits, the
served tokens (greedy and sampled, decode and chunked prefill) and the
serial ``greedy_generate``, in float32, bfloat16 and int8.
``generation_embed_rows_total{path}`` books what each dispatch embedded."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models import zoo
from deeplearning4j_tpu.models.zoo import (TransformerLM, embedding_vertex,
                                           greedy_generate,
                                           lm_stream_forward)
from deeplearning4j_tpu.nn.conf.builders import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.graph_conf import ElementWiseVertex
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers.attention import SelfAttentionLayer
from deeplearning4j_tpu.nn.conf.layers.core import (ActivationLayer,
                                                    DenseLayer)
from deeplearning4j_tpu.nn.conf.layers.recurrent import RnnOutputLayer
from deeplearning4j_tpu.nn.conf.preprocessors import ZeroMeanPrePreProcessor
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.optimize.quantize import quantize_net
from deeplearning4j_tpu.parallel.generation import GenerationServer

pytestmark = pytest.mark.generation

V = 29
KINDS = ("float32", "bfloat16", "int8")
#: (prompt length, new tokens, temperature, top_k, seed): prompts past the
#: prefill chunk of 8 take several rounds
REQUESTS = ((13, 6, 0.0, 0, 0), (5, 7, 0.9, 4, 11), (19, 5, 0.0, 0, 0),
            (9, 6, 1.1, 0, 12))


def _lm(kind):
    """A fresh tiny graph LM (its own program caches) of one kind."""
    net = TransformerLM(num_labels=V, max_length=48, d_model=16, n_heads=2,
                        n_blocks=1, seed=3,
                        dtype="float32" if kind == "int8" else kind).init()
    return quantize_net(net) if kind == "int8" else net


def _one_hot_only(monkeypatch):
    """Every net is taken as if its structure did not allow the gather."""
    monkeypatch.setattr(zoo, "embedding_vertex", lambda net, vocab: None)


def _serve(net, prompts):
    """The requests through one server: the tokens, the counters, the
    prefill buckets dispatched, the decode dispatches and the row width."""
    srv = GenerationServer(net, V, slots=2, page_size=4, prefill_chunk=8,
                           steps_per_dispatch=3)
    buckets = []
    program = srv._prefill_program

    def recording(bucket):
        buckets.append(bucket)
        return program(bucket)

    srv._prefill_program = recording
    try:
        futs = [srv.submit(p, n, temperature=t, top_k=k, seed=s)
                for p, (_, n, t, k, s) in zip(prompts, REQUESTS)]
        toks = [f.result(timeout=120) for f in futs]
        snap = srv.metrics.snapshot()
        return {"tokens": toks, "snap": snap, "buckets": buckets,
                "width": srv._prefill_rows}
    finally:
        srv.close()


@pytest.fixture(scope="module", params=KINDS)
def served(request):
    """Each kind's requests served through a gather server and a one-hot
    server over two fresh nets with the same weights, and the serial
    greedy references."""
    rs = np.random.RandomState(5)
    prompts = [rs.randint(0, V, n) for n, *_ in REQUESTS]
    net = _lm(request.param)
    refs = {i: greedy_generate(net, prompts[i][None], REQUESTS[i][1], V)[0]
            for i, r in enumerate(REQUESTS) if r[2] == 0.0}
    gather = _serve(net, prompts)
    with pytest.MonkeyPatch.context() as mp:
        _one_hot_only(mp)
        one_hot = _serve(_lm(request.param), prompts)
    return {"gather": gather, "one_hot": one_hot, "refs": refs}


def test_the_zoo_lm_embeds_by_its_table_rows():
    net = _lm("float32")
    assert embedding_vertex(net, V) == "embed"
    assert embedding_vertex(net, V + 1) is None      # n_in is not the vocab


@pytest.mark.parametrize("kind", KINDS)
def test_the_gather_and_the_product_give_the_same_logits(kind, monkeypatch):
    """A prompt chunk under a mask, then token by token: the outputs and
    the carried caches are bit for bit the one-hot product's."""
    net = _lm(kind)
    rs = np.random.RandomState(7)
    ids = rs.randint(0, V, (2, 8))
    mask = np.ones((2, 8), np.float32)
    mask[1, 5:] = 0

    def run(fwd):
        carry = net._seed_streaming_carry(2)
        net.rnn_clear_previous_state()
        out, carry = fwd(net.params, net.state, ids, carry, mask)
        outs = [out]
        last = jnp.argmax(out[:, -1], axis=-1)
        for _ in range(3):
            out, carry = fwd(net.params, net.state, last[:, None], carry)
            outs.append(out)
            last = jnp.argmax(out[:, 0], axis=-1)
        return [np.asarray(o) for o in outs], carry

    gathered, gc = run(jax.jit(lm_stream_forward(net, V)))
    _one_hot_only(monkeypatch)
    product, pc = run(jax.jit(lm_stream_forward(net, V)))
    for g, p in zip(gathered, product):
        np.testing.assert_array_equal(g, p)
    for g, p in zip(jax.tree_util.tree_leaves(gc),
                    jax.tree_util.tree_leaves(pc)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(p))


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("activation", ["identity", "tanh"])
def test_rows_are_the_forward_of_a_one_hot_block(kind, activation):
    """``DenseLayer.rows`` against the layer's own forward of the one-hot
    block, cast for cast (the int8 table's scale included)."""
    dtype = jnp.float32 if kind == "int8" else jnp.dtype(kind)
    layer = DenseLayer(n_in=V, n_out=8, activation=activation)
    layer.finalize()
    params = layer.init_params(jax.random.PRNGKey(1), dtype)
    params["b"] = jnp.linspace(-1, 1, 8).astype(dtype)
    if kind == "int8":
        from deeplearning4j_tpu.optimize.quantize import quantize_array

        params["W"], params["W_scale"] = quantize_array(params["W"])
    ids = np.random.RandomState(2).randint(0, V, (3, 5))
    want, _ = jax.jit(lambda p, i: layer.forward(
        p, {}, jax.nn.one_hot(i, V, dtype=dtype)))(params, ids)
    got = jax.jit(lambda p, i: layer.rows(p, i, dtype))(params, ids)
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_served_tokens_are_the_same_on_both_paths(served):
    for got, want in zip(served["gather"]["tokens"],
                         served["one_hot"]["tokens"]):
        np.testing.assert_array_equal(got, want)


def test_the_server_serves_what_greedy_generate_does(served):
    for i, ref in served["refs"].items():
        np.testing.assert_array_equal(served["gather"]["tokens"][i], ref)


@pytest.mark.parametrize("path", ["gather", "one_hot"])
def test_each_dispatch_books_the_rows_it_embedded(served, path):
    """rows x micro-steps a decode dispatch, row width x bucket a prefill
    dispatch, all of it under the path the net's structure picked."""
    run = served[path]
    snap = run["snap"]
    decode = snap["generation_decode_steps_total"] * 2 * 3
    prefill = sum(run["width"] * b for b in run["buckets"])
    assert len(run["buckets"]) == snap["generation_prefill_rounds_total"]
    other = "one_hot" if path == "gather" else "gather"
    assert snap["generation_embed_rows_total"] == {
        f"path={path}": float(decode + prefill), f"path={other}": 0.0}


def _graph(first=None, preprocessor=None):
    """A small servable graph LM whose token input feeds ``first`` (a
    layer before the embedding) or the embedding itself, behind
    ``preprocessor`` if given."""
    g = (NeuralNetConfiguration.builder().seed(3).weight_init("xavier")
         .graph_builder().add_inputs("tokens")
         .set_input_types(InputType.recurrent(V, 48)))
    src = "tokens"
    if first is not None:
        g.add_layer("first", first, "tokens")
        src = "first"
    g.add_layer("embed", DenseLayer(n_out=16, activation="identity"), src,
                preprocessor=preprocessor)
    g.add_layer("attn", SelfAttentionLayer(n_out=16, n_heads=2, causal=True),
                "embed")
    g.add_vertex("res", ElementWiseVertex(op="add"), "embed", "attn")
    g.add_layer("output", RnnOutputLayer(n_out=V, activation="softmax",
                                         loss="mcxent"), "res")
    g.set_outputs("output")
    return ComputationGraph(g.build()).init()


@pytest.mark.parametrize("how", ["non_dense_first", "preprocessor"])
def test_a_net_of_another_structure_keeps_the_product(how):
    net = (_graph(first=ActivationLayer(activation="identity"))
           if how == "non_dense_first"
           else _graph(preprocessor=ZeroMeanPrePreProcessor()))
    assert embedding_vertex(net, V) is None
    rs = np.random.RandomState(3)
    srv = GenerationServer(net, V, slots=2, page_size=4, prefill_chunk=8,
                           steps_per_dispatch=2)
    try:
        out = srv.submit(rs.randint(0, V, 6), 3).result(timeout=120)
        rows = srv.metrics.snapshot()["generation_embed_rows_total"]
    finally:
        srv.close()
    assert out.shape == (3,)
    assert rows["path=gather"] == 0.0 and rows["path=one_hot"] > 0


def test_a_token_input_read_twice_keeps_the_product():
    g = (NeuralNetConfiguration.builder().seed(3).graph_builder()
         .add_inputs("tokens").set_input_types(InputType.recurrent(V, 8)))
    g.add_layer("embed", DenseLayer(n_out=V, activation="identity"),
                "tokens")
    g.add_vertex("skip", ElementWiseVertex(op="add"), "embed", "tokens")
    g.add_layer("output", RnnOutputLayer(n_out=V, activation="softmax",
                                         loss="mcxent"), "skip")
    g.set_outputs("output")
    assert embedding_vertex(ComputationGraph(g.build()), V) is None


def test_fit_and_output_keep_the_product(monkeypatch):
    """Training and ``output()`` never ask the structure: the train step
    and the inference forward lower to the same text whatever it answers,
    and that text multiplies the one-hot block into the table."""
    import re

    net = _lm("float32")
    rs = np.random.RandomState(9)
    x, y = (np.asarray(jax.nn.one_hot(rs.randint(0, V, (2, 8)), V,
                                      dtype=jnp.float32)) for _ in range(2))

    def texts():
        step = net._make_step(with_carry=False).lower(
            net.params, net.updater_state, net.state, jax.random.PRNGKey(0),
            0, [x], [y], [None], [None], None).as_text()
        fwd = jax.jit(lambda p, a: net._forward(
            p, net.state, [a], [None], train=False, rng=None)[0]).lower(
            net.params, x).as_text()
        return step, fwd

    before = texts()
    _one_hot_only(monkeypatch)
    assert texts() == before
    table = re.compile(rf"dot_general.*tensor<2x8x{V}xf32>.*tensor<{V}x16xf32>")
    for text in before:
        assert table.search(text)
    net.fit(x, y)
    assert np.isfinite(float(net.score()))
