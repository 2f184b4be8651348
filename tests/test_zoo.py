"""Model zoo tests (reference: deeplearning4j-zoo/src/test TestInstantiation).

Every zoo model must build (config + shape inference), initialise, and run a
forward pass; the small ones must train. Reduced input sizes keep the CPU
suite fast; full size is covered on the chip for the three models of
``BENCHMARK.json`` (ResNet50 among them) and by ``chip_smoke.py``.
"""

import numpy as np
import pytest

from deeplearning4j_tpu.models import (
    AlexNet,
    FaceNetNN4Small2,
    GoogLeNet,
    InceptionResNetV1,
    LeNet,
    ResNet50,
    SimpleCNN,
    TextGenerationLSTM,
    TransformerLM,
    VGG16,
    VGG19,
    zoo_models,
)


def test_registry_complete():
    names = set(zoo_models())
    assert names == {"alexnet", "facenetnn4small2", "googlenet",
                     "inceptionresnetv1", "lenet", "resnet50", "simplecnn",
                     "textgenlstm", "transformerlm", "vgg16", "vgg19",
                     "granitemoehybridlm", "falconh1lm", "deepseekv2lm",
                     "trinitylm"}


@pytest.mark.parametrize("cls,kw,x_shape", [
    (LeNet, {}, (2, 28, 28, 1)),
    # slow: ~18s compile; LeNet + the LSTM keep the forward+train path in
    # tier-1 (see the tier-1 duration budget note in conftest.py)
    pytest.param(SimpleCNN, {}, (2, 48, 48, 1), marks=pytest.mark.slow),
    (TextGenerationLSTM, {"num_labels": 11, "max_length": 8}, (2, 8, 11)),
])
def test_small_models_forward_and_train(cls, kw, x_shape):
    m = cls(**kw)
    net = m.init()
    rs = np.random.RandomState(0)
    x = rs.randn(*x_shape).astype(np.float32)
    n_out = net.conf.layers[-1].n_out if hasattr(net, "layers") else None
    if x.ndim == 3:  # rnn: per-timestep labels
        y = np.eye(n_out, dtype=np.float32)[
            rs.randint(0, n_out, x.shape[:2])]
    else:
        y = np.eye(n_out, dtype=np.float32)[rs.randint(0, n_out, x.shape[0])]
    out = np.asarray(net.output(x))
    assert out.shape[0] == x.shape[0]
    first, _ = net.do_step(x, y)
    for _ in range(8):
        last, _ = net.do_step(x, y)
    assert np.isfinite(last) and last < first * 1.5


@pytest.mark.parametrize("cls,shape,n_params_min", [
    (AlexNet, (64, 64, 3), 1_000_000),
    (VGG16, (32, 32, 3), 10_000_000),
    (VGG19, (32, 32, 3), 15_000_000),
    # slow: the three heaviest compiles (~15-24s each); the four tier-1
    # params above/below exercise the same build-graph/init/forward path
    # (see the tier-1 duration budget note in conftest.py)
    pytest.param(ResNet50, (64, 64, 3), 20_000_000,
                 marks=pytest.mark.slow),
    pytest.param(GoogLeNet, (64, 64, 3), 5_000_000,
                 marks=pytest.mark.slow),
    (FaceNetNN4Small2, (64, 64, 3), 1_000_000),
    pytest.param(InceptionResNetV1, (96, 96, 3), 15_000_000,
                 marks=pytest.mark.slow),
])
def test_big_models_instantiate_and_forward(cls, shape, n_params_min):
    """Reduced input sizes (zoo models accept input_shape overrides like the
    reference's setInputShape)."""
    m = cls(num_labels=10, input_shape=shape)
    if cls is AlexNet:
        # AlexNet's fixed stride stack needs the full 224 input
        m = cls(num_labels=10)
        shape = m.input_shape
    net = m.init()
    assert net.num_params() > n_params_min
    x = np.random.RandomState(1).randn(2, *shape).astype(np.float32)
    # train-mode forward: BN uses batch stats — inference-mode stats are
    # meaningless before training (esp. ResNet50's reference Normal(0,0.5)
    # init, which saturates a 50-layer stack)
    out = np.asarray(net.output(x, train=True))
    assert out.shape == (2, 10)
    assert np.all(np.isfinite(out))
    assert np.allclose(out.sum(axis=1), 1.0, atol=1e-4)  # softmax head


def test_resnet50_residual_structure():
    conf = ResNet50(num_labels=10, input_shape=(64, 64, 3)).conf()
    # 16 residual joins: 4 conv blocks + 12 identity blocks
    from deeplearning4j_tpu.nn.conf.graph_conf import ElementWiseVertex
    adds = [v for v in conf.vertices.values()
            if isinstance(v, ElementWiseVertex)]
    assert len(adds) == 16


def test_zoo_model_serialization_roundtrip(tmp_path):
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.utils.model_serializer import (
        load_model,
        save_model,
    )

    net = LeNet(num_labels=10).init()
    rs = np.random.RandomState(3)
    x = rs.randn(4, 28, 28, 1).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[rs.randint(0, 10, 4)]
    net.fit(DataSet(x, y), epochs=2)
    p = str(tmp_path / "lenet.zip")
    save_model(net, p)
    net2 = load_model(p)
    assert np.allclose(np.asarray(net.output(x)), np.asarray(net2.output(x)),
                       atol=1e-6)


def test_init_pretrained_raises_clearly():
    with pytest.raises(NotImplementedError, match="network access"):
        LeNet().init_pretrained()


@pytest.fixture(scope="module")
def cyclic_lm():
    """ONE TransformerLM trained on the +1 mod V cyclic language, shared by
    the two tests that need a trained model (init + two compiles + the fit
    loop are paid once). 120 steps reach loss ~0.07 and accuracy 1.0."""
    from deeplearning4j_tpu.datasets.dataset import DataSet

    V, T = 11, 16
    m = TransformerLM(num_labels=V, max_length=T, d_model=32, n_heads=4,
                      n_blocks=2, seed=5).init()
    rs = np.random.RandomState(0)
    # token t+1 = (token t + 1) mod V, random start per sequence
    starts = rs.randint(0, V, 64)
    seq = (starts[:, None] + np.arange(T + 1)[None, :]) % V
    x = np.eye(V, dtype=np.float32)[seq[:, :-1]]
    y = np.eye(V, dtype=np.float32)[seq[:, 1:]]
    ds = DataSet(x, y)
    s0 = m.score(ds)
    for _ in range(120):
        m.fit(ds)
    return m, V, seq, x, ds, s0


def test_transformer_lm_learns_next_token(cyclic_lm):
    """Beyond-parity TransformerLM: causal attention + pre-norm residual
    blocks learn a deterministic cyclic-sequence next-token task."""
    m, _, seq, x, ds, s0 = cyclic_lm
    s1 = m.score(ds)
    assert s1 < s0 * 0.5, (s0, s1)
    pred = np.asarray(m.output(x)).argmax(-1)
    acc = float((pred == seq[:, 1:]).mean())
    assert acc > 0.9, acc


def test_transformer_lm_causality():
    """Changing a future token must not change past predictions."""
    V, T = 7, 12
    m = TransformerLM(num_labels=V, max_length=T, d_model=16, n_heads=2,
                      n_blocks=1, seed=3).init()
    rs = np.random.RandomState(1)
    idx = rs.randint(0, V, (2, T))
    x1 = np.eye(V, dtype=np.float32)[idx]
    idx2 = idx.copy()
    idx2[:, -1] = (idx2[:, -1] + 1) % V  # perturb ONLY the last token
    x2 = np.eye(V, dtype=np.float32)[idx2]
    o1 = np.asarray(m.output(x1))
    o2 = np.asarray(m.output(x2))
    np.testing.assert_allclose(o1[:, :-1], o2[:, :-1], atol=1e-5)
    assert np.abs(o1[:, -1] - o2[:, -1]).max() > 1e-6


def test_transformer_streaming_matches_full_forward():
    """KV-cache incremental decode == full forward, token by token: the
    streaming path (rnn_time_step seeding kcache/vcache/cache_pos carry)
    must reproduce the full causal forward's logits at every position."""
    V, T = 9, 10
    m = TransformerLM(num_labels=V, max_length=T, d_model=16, n_heads=2,
                      n_blocks=2, seed=8).init()
    rs = np.random.RandomState(4)
    idx = rs.randint(0, V, (3, T))
    x = np.eye(V, dtype=np.float32)[idx]
    full = np.asarray(m.output(x))                 # [B, T, V]

    m.rnn_clear_previous_state()
    stream = []
    for t in range(T):
        out = m.rnn_time_step(x[:, t:t + 1, :])    # one token at a time
        stream.append(np.asarray(out)[:, 0])
    stream = np.stack(stream, axis=1)
    np.testing.assert_allclose(stream, full, atol=1e-5, rtol=1e-4)

    # a fresh stream after clearing starts from scratch (prefix parity)
    m.rnn_clear_previous_state()
    out0 = np.asarray(m.rnn_time_step(x[:, :4, :]))  # 4-token prompt chunk
    np.testing.assert_allclose(out0, full[:, :4], atol=1e-5, rtol=1e-4)


def test_transformer_generation_follows_learned_rule(cyclic_lm):
    """Trained on the +1 mod V cyclic language, greedy-generate with the
    KV cache: continuations must follow the rule."""
    from deeplearning4j_tpu.models import greedy_generate

    m, V, seq, *_ = cyclic_lm
    prompt = seq[:4, :6]                           # 6-token prompts
    gen = greedy_generate(m, prompt, steps=8, vocab=V)
    expected = (prompt[:, -1:] + 1 + np.arange(8)[None, :]) % V
    assert (gen == expected).mean() > 0.9, (gen[0], expected[0])


def test_streaming_cache_overflow_raises():
    V = 5
    m = TransformerLM(num_labels=V, max_length=4, d_model=8, n_heads=2,
                      n_blocks=1, seed=1).init()
    # shrink the attention cache to 4 positions
    for v in m.conf.vertices.values():
        lyr = getattr(v, "layer", None)
        if lyr is not None and hasattr(lyr, "max_cache"):
            lyr.max_cache = 4
    x = np.eye(V, dtype=np.float32)[np.zeros((1, 3), np.int64)]
    m.rnn_clear_previous_state()
    m.rnn_time_step(x)                 # 3 of 4 slots used
    with pytest.raises(ValueError, match="KV cache overflow"):
        m.rnn_time_step(x)             # 3 more would exceed 4


@pytest.mark.parametrize("device_loop", [True, False])
def test_sample_generate_temperature_and_topk(device_loop):
    """temperature=0 == greedy; sampled tokens vary with seed but stay
    inside the top-k support set — both the device lax.scan path and the
    host-driven rnn_time_step path."""
    from deeplearning4j_tpu.models import greedy_generate, sample_generate

    V, T = 13, 12
    m = TransformerLM(num_labels=V, max_length=T, d_model=16, n_heads=2,
                      n_blocks=1, seed=6).init()
    rs = np.random.RandomState(1)
    prompt = rs.randint(0, V, (2, 4))
    kw = dict(vocab=V, device_loop=device_loop)

    g = greedy_generate(m, prompt, steps=6, vocab=V)
    s0 = sample_generate(m, prompt, steps=6, temperature=0.0, **kw)
    np.testing.assert_array_equal(g, s0)  # temp 0 IS greedy

    a = sample_generate(m, prompt, steps=6, temperature=1.5, seed=1, **kw)
    b = sample_generate(m, prompt, steps=6, temperature=1.5, seed=2, **kw)
    c = sample_generate(m, prompt, steps=6, temperature=1.5, seed=1, **kw)
    np.testing.assert_array_equal(a, c)   # deterministic in seed
    assert (a != b).any()                 # varies across seeds

    # top_k=1 is greedy regardless of temperature
    k1 = sample_generate(m, prompt, steps=6, temperature=2.0, top_k=1,
                         seed=3, **kw)
    np.testing.assert_array_equal(k1, g)

    with pytest.raises(ValueError, match="top_k"):
        sample_generate(m, prompt, steps=2, top_k=V + 1, **kw)
    with pytest.raises(ValueError, match="temperature"):
        sample_generate(m, prompt, steps=2, temperature=-0.5, **kw)
