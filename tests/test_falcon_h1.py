"""The parallel-hybrid language model (``FalconH1LM``) and what it forced:
rotary positions, a head size of its own and a key factor in
``SelfAttentionLayer``'s three forwards, ``Mamba2Layer`` at two groups with
the gated norm taken per group and multipliers on its projection's
segments, the dense gated feed-forward, and a paged layer beside a
slot-state layer in every block of a net served by ``GenerationServer`` —
each held against a plain statement of the same mathematics
(``benchmarks/references/falcon_h1_34b_instruct.py``, or a loop written
here).

Everything is float32 at toy widths, so agreement is to rounding: the
tolerances below are a few float32 ulps of values of order one, summed over
tens of terms (1e-5), and every planted fault misses them by orders of
magnitude.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.metrics.exposition import render_text
from deeplearning4j_tpu.models import FalconH1LM
from deeplearning4j_tpu.nn.conf.layers import (GatedFeedForwardLayer,
                                               Mamba2Layer,
                                               SelfAttentionLayer)
from deeplearning4j_tpu.nn.conf.layers import attention as attention_module
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.parallel.generation import GenerationServer

TOL = 1e-5


def _reference():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "references",
        "falcon_h1_34b_instruct.py")
    spec = importlib.util.spec_from_file_location("falcon_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()

V = 48
# two groups, five query heads a key/value head, a head size (4) that is
# not d_model / heads (3.2), every multiplier off 1 and different
SIZES = {"vocab": V, "d_model": 32, "layers": 2, "heads": 10, "kv_heads": 2,
         "head_dim": 4, "rope_theta": 100.0, "mlp_width": 48,
         "mamba_heads": 4, "mamba_head_dim": 8, "d_state": 8, "n_groups": 2,
         "d_conv": 4, "chunk": 8, "embedding_multiplier": 5.656854249492381,
         "attention_in_multiplier": 0.9, "attention_out_multiplier": 0.6,
         "key_multiplier": 0.5, "ssm_in_multiplier": 0.7,
         "ssm_out_multiplier": 0.8,
         "ssm_multipliers": [0.9, 0.8, 0.7, 1.2, 1.1],
         "mlp_multipliers": [0.7, 0.6], "lm_head_multiplier": 0.25,
         "rms_eps": 1e-5}
INIT = {"std": {"embed": 0.18, "q": 0.3, "k": 0.3, "v": 0.2, "o": 0.2,
                "ssm_in": 0.3, "ssm_out": 0.2, "mlp_in": 0.2,
                "mlp_down": 0.2, "head": 0.5},
        "a_min": 1.0, "a_max": 4.0, "dt_min": 0.001, "dt_max": 0.1}


def tiny_falcon(seed=5):
    """The zoo model at toy widths in float32, holding the reference's
    (bfloat16-valued) weights: (net, params, sizes)."""
    sz = SIZES
    params = REF.make_params(seed, sz, INIT)
    model = FalconH1LM(
        num_labels=V, max_length=128, d_model=sz["d_model"],
        n_layers=sz["layers"], n_heads=sz["heads"],
        n_kv_heads=sz["kv_heads"], head_dim=sz["head_dim"],
        rope_theta=sz["rope_theta"], mlp_width=sz["mlp_width"],
        mamba_heads=sz["mamba_heads"], mamba_head_dim=sz["mamba_head_dim"],
        mamba_d_state=sz["d_state"], mamba_n_groups=sz["n_groups"],
        mamba_chunk=sz["chunk"],
        **{k: sz[k] for k in sz if k.endswith(("_multiplier",
                                               "_multipliers"))},
        dtype="float32")
    conf = model.conf()
    for v in conf.vertices.values():
        layer = getattr(v, "layer", None)
        if layer is not None and hasattr(layer, "max_cache"):
            layer.max_cache = 128
    net = ComputationGraph(conf)
    net.init(params={n: params.get(n, {}) for n in conf.topo_order})
    return net, params, sz


@pytest.fixture(scope="module")
def falcon():
    return tiny_falcon()


# ---------------------------------------------------------------- attention
def _rotary_layer(**kw):
    layer = SelfAttentionLayer(
        **{**dict(n_in=16, n_out=16, n_heads=10, n_kv_heads=2, head_dim=4,
                  causal=True, helper="stock", has_bias=False,
                  rope_theta=100.0, key_scale=0.5, max_cache=32), **kw})
    layer.finalize()
    layer.validate()
    return layer


def _attention_by_hand(layer, p, x):
    """One row ``[T, n_in]`` with the rotation written out pair by pair."""
    T = x.shape[0]
    H, Hkv, d = layer.n_heads, layer.kv_heads, layer.d_head
    q = np.asarray(x @ p["Wq"], np.float64).reshape(T, H, d)
    k = np.asarray(x @ p["Wk"], np.float64).reshape(T, Hkv, d) \
        * layer.key_scale
    v = np.asarray(x @ p["Wv"], np.float64).reshape(T, Hkv, d)

    def turn(t):
        out = np.empty_like(t)
        for pos in range(T):
            for i in range(d // 2):
                ang = pos * layer.rope_theta ** (-2.0 * i / d)
                a, b = t[pos, :, i], t[pos, :, i + d // 2]
                out[pos, :, i] = a * np.cos(ang) - b * np.sin(ang)
                out[pos, :, i + d // 2] = b * np.cos(ang) + a * np.sin(ang)
        return out

    q, k = turn(q), turn(k)
    rows = []
    for j in range(H):
        s = q[:, j] @ k[:, j // (H // Hkv)].T / np.sqrt(d)
        s = np.where(np.tril(np.ones((T, T), bool)), s, -1e30)
        w = np.exp(s - s.max(-1, keepdims=True))
        rows.append((w / w.sum(-1, keepdims=True)) @ v[:, j // (H // Hkv)])
    return np.stack(rows, 1).reshape(T, H * d) @ np.asarray(p["Wo"])


@pytest.fixture(scope="module")
def rotary_case():
    layer = _rotary_layer()
    p = layer.init_params(jax.random.PRNGKey(0))
    assert p["Wq"].shape == (16, 40) and p["Wo"].shape == (40, 16)
    assert p["Wk"].shape == p["Wv"].shape == (16, 8) and "b" not in p
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 13, 16), jnp.float32)
    want = np.stack([_attention_by_hand(layer, p, np.asarray(r))
                     for r in x])
    return layer, p, x, want


def _paged(layer, p, x, cuts, lens=None):
    """Rows through a page pool in the chunks ``cuts`` names; with ``lens``
    the first chunk is right-padded and masked to each row's true length,
    the caller sets the rows' watermarks (as the server does) and the rest
    of each row follows token by token at the row's own position."""
    fwd = jax.jit(lambda st, xx, mk: layer.forward(p, st, xx, mask=mk))
    pool = layer.init_paged_carry(9, 4)
    assert pool["kpages"].shape == (9, 4, 8)       # kv heads, head size
    bt = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8]], jnp.int32)
    pos = jnp.zeros((2,), jnp.int32)
    outs = []
    for a, b in zip(cuts[:-1], cuts[1:]):
        mask = None
        chunk = x[:, a:b]
        if lens is not None and a == 0:
            mask = (jnp.arange(b)[None, :] < lens[:, None]).astype(
                jnp.float32)
        elif lens is not None:
            # row r's next true token is x[r, pos[r]]
            chunk = jnp.take_along_axis(x, pos[:, None, None], axis=1)
        o, ns = fwd(dict(pool, block_table=bt, cache_pos=pos), chunk, mask)
        pool = {"kpages": ns["kpages"], "vpages": ns["vpages"]}
        pos = ns["cache_pos"] if mask is None else lens.astype(jnp.int32)
        outs.append(o)
    return outs


@pytest.mark.parametrize("how", ["contiguous", "streaming", "paged",
                                 "paged_right_padded"])
def test_rotary_grouped_attention_every_forward_is_the_plain_rotation(
        rotary_case, how):
    """Five query heads a key/value head of size 4 inside a model of width
    16, keys halved and both rotated: the whole sequence, a dense cache fed
    in uneven chunks, a page pool fed a chunk and then token by token, and
    a right-padded masked chunk whose rows continue from their own true
    lengths are all the rotation written out pair by pair."""
    layer, p, x, want = rotary_case
    if how == "contiguous":
        got, _ = jax.jit(lambda xx: layer.forward(p, {}, xx))(x)
    elif how == "streaming":
        fwd = jax.jit(lambda st, xx: layer.forward(p, st, xx))
        st = layer.init_streaming_carry(2)
        assert st["kcache"].shape == (2, 2, 32, 4)
        outs = []
        for a, b in ((0, 4), (4, 5), (5, 13)):
            o, st = fwd(st, x[:, a:b])
            outs.append(o)
        got = jnp.concatenate(outs, 1)
    elif how == "paged":
        got = jnp.concatenate(
            _paged(layer, p, x, (0, 6) + tuple(range(7, 14))), 1)
    else:
        lens = jnp.asarray([7, 4])
        outs = _paged(layer, p, x, (0, 8) + tuple(range(9, 15)), lens)
        for r, n in enumerate((7, 4)):
            np.testing.assert_allclose(outs[0][r, :n], want[r, :n],
                                       atol=TOL)
            assert not np.any(np.asarray(outs[0][r, n:]))
            tail = np.concatenate([np.asarray(o[r]) for o in outs[1:]])
            np.testing.assert_allclose(tail, want[r, n:n + 6], atol=TOL)
        return
    np.testing.assert_allclose(got, want, atol=TOL)


def test_an_unrotated_or_unscaled_key_is_not_the_plain_rotation(rotary_case):
    layer, p, x, want = rotary_case
    for kw in (dict(rope_theta=0.0), dict(key_scale=0.0)):
        other = _rotary_layer(**kw)
        got, _ = jax.jit(lambda xx: other.forward(p, {}, xx))(x)
        assert float(np.abs(np.asarray(got) - want).max()) > 100 * TOL


def test_attention_defaults_trace_as_the_classic_layer():
    """With ``head_dim``, ``rope_theta`` and ``key_scale`` at their
    defaults nothing of them is traced: the paged forward's program is the
    one an explicit ``head_dim = n_out // n_heads`` gives, and holds no
    ``cos``; with the rotation on it does."""
    def text(**kw):
        layer = SelfAttentionLayer(n_in=16, n_out=16, n_heads=4,
                                   n_kv_heads=2, causal=True, helper="stock",
                                   has_bias=False, **kw)
        layer.finalize()
        p = layer.init_params(jax.random.PRNGKey(0))
        st = dict(layer.init_paged_carry(5, 4),
                  block_table=jnp.zeros((2, 4), jnp.int32),
                  cache_pos=jnp.zeros((2,), jnp.int32))
        return str(jax.make_jaxpr(
            lambda s, xx: layer.forward(p, s, xx))(
                st, jnp.zeros((2, 3, 16), jnp.float32)))

    assert text() == text(head_dim=4)
    assert " cos " not in text() and " cos " in text(rope_theta=1e4)


def test_a_rotary_layer_refuses_an_odd_head_and_the_sequence_mesh():
    with pytest.raises(ValueError):
        _rotary_layer(head_dim=3)
    with pytest.raises(ValueError):
        _rotary_layer(head_dim=0, n_out=16, n_heads=10, rope_theta=0.0)
    from deeplearning4j_tpu.parallel.sequence import (
        sequence_parallel_self_attention)

    with pytest.raises(NotImplementedError):
        sequence_parallel_self_attention(_rotary_layer(), {},
                                         jnp.zeros((1, 8, 16)), mesh=None)


# ------------------------------------------------------------ Mamba2Layer
MULT = (0.9, 0.8, 0.7, 1.2, 1.1)


def _mamba(groups=2, mult=MULT):
    layer = Mamba2Layer(n_in=16, n_out=16, n_heads=4, head_dim=8, d_state=8,
                        n_groups=groups, chunk_size=4, weight_init="xavier",
                        proj_multipliers=mult)
    layer.finalize()
    layer.validate()
    p = layer.init_params(jax.random.PRNGKey(0))
    # off their initial values, so that every parameter matters
    p["conv_b"] = 0.1 * jax.random.normal(jax.random.PRNGKey(1),
                                          p["conv_b"].shape)
    p["D"] = p["D"] + 0.3 * jax.random.normal(jax.random.PRNGKey(2),
                                              p["D"].shape)
    p["norm_w"] = p["norm_w"] + 0.2 * jax.random.normal(
        jax.random.PRNGKey(3), p["norm_w"].shape)
    return layer, p


def _sequential(layer, p, x, norm_groups=None):
    """The layer's docstring, one position at a time, for one row; the
    gated norm over ``norm_groups`` groups (the layer's own by default)."""
    H, P, N, K = layer.n_heads, layer.head_dim, layer.d_state, layer.d_conv
    G = layer.n_groups
    di, cd, gn = layer.d_inner, layer.conv_dim, layer.n_groups * layer.d_state
    m = jnp.concatenate([jnp.full((w,), s) for w, s in zip(
        (di, di, gn, gn, H), layer.proj_multipliers or (1.0,) * 5)])
    proj = (x @ p["W_in"]) * m
    z, xbc, dt = proj[:, :di], proj[:, di:di + cd], proj[:, di + cd:]
    pad = jnp.concatenate([jnp.zeros((K - 1, cd)), xbc])
    xbc = jax.nn.silu(p["conv_b"] + sum(
        p["conv_w"][:, j] * pad[j:j + x.shape[0]] for j in range(K)))
    xs = xbc[:, :di].reshape(-1, H, P)
    bm = xbc[:, di:di + gn].reshape(-1, G, N)
    cm = xbc[:, di + gn:].reshape(-1, G, N)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    a = -jnp.exp(p["A_log"])
    S = jnp.zeros((H, P, N))
    ys = []
    for t in range(x.shape[0]):
        # head j reads group j // (H // G)
        b_t = jnp.repeat(bm[t], H // G, axis=0)
        c_t = jnp.repeat(cm[t], H // G, axis=0)
        S = jnp.exp(dt[t] * a)[:, None, None] * S \
            + (dt[t][:, None] * xs[t])[:, :, None] * b_t[:, None, :]
        ys.append(jnp.einsum("hpn,hn->hp", S, c_t)
                  + p["D"][:, None] * xs[t])
    y = jnp.stack(ys).reshape(-1, di) * jax.nn.silu(z)
    g = norm_groups or G
    y = y.reshape(-1, g, di // g)
    y = y / jnp.sqrt(jnp.mean(y * y, -1, keepdims=True) + layer.norm_eps)
    return (p["norm_w"] * y.reshape(-1, di)) @ p["W_out"], S


@pytest.fixture(scope="module")
def mamba_case():
    layer, p = _mamba()
    x = jax.random.normal(jax.random.PRNGKey(7), (3, 13, 16), jnp.float32)
    want = jax.jit(jax.vmap(lambda r: _sequential(layer, p, r)))(x)
    return layer, p, x, want


@pytest.mark.parametrize("cuts", [
    None, (0, 13), (0, 5, 6, 13), tuple(range(14))],
    ids=["whole", "one_chunk", "uneven_chunks", "tokens"])
def test_mamba2_at_two_groups_every_forward_is_the_sequential_scan(
        mamba_case, cuts):
    """Two groups of ``B`` and ``C``, the gated norm taken per group, a
    factor on each segment of the projection: the whole sequence, streamed
    chunks and the one-token recurrence are the sequential scan."""
    layer, p, x, (want, state) = mamba_case
    if cuts is None:
        got, _ = jax.jit(lambda xx: layer.forward(p, {}, xx))(x)
    else:
        fwd = jax.jit(lambda st, xx: layer.forward(p, st, xx))
        st = layer.init_streaming_carry(3)
        outs = []
        for a, b in zip(cuts[:-1], cuts[1:]):
            o, st = fwd(st, x[:, a:b])
            outs.append(o)
        got = jnp.concatenate(outs, 1)
        np.testing.assert_allclose(st["ssm_state"], state, atol=TOL)
    np.testing.assert_allclose(got, want, atol=TOL)


def test_the_grouped_norm_is_not_one_norm_over_both_groups(mamba_case):
    """On the same weights and inputs the per-group norm and one norm over
    all of ``d_inner`` differ by far more than rounding, the layer gives
    the first, and at one group the two are one (granite's layer)."""
    layer, p, x, (want, _) = mamba_case
    one, _ = jax.jit(jax.vmap(
        lambda r: _sequential(layer, p, r, norm_groups=1)))(x)
    assert float(jnp.abs(one - want).max()) > 1000 * TOL
    single, ps = _mamba(groups=1, mult=None)
    got, _ = jax.jit(lambda xx: single.forward(ps, {}, xx))(x)
    ref, _ = jax.jit(jax.vmap(lambda r: _sequential(single, ps, r)))(x)
    np.testing.assert_allclose(got, ref, atol=TOL)


def test_mamba2_refuses_multipliers_that_do_not_name_the_five_segments():
    with pytest.raises(ValueError):
        _mamba(mult=(1.0, 2.0))


# ------------------------------------------------------ gated feed-forward
def test_gated_feed_forward_is_its_formula():
    layer = GatedFeedForwardLayer(n_in=16, n_out=16, hidden=24,
                                  gate_scale=0.7, out_scale=0.6)
    layer.finalize()
    p = layer.init_params(jax.random.PRNGKey(0))
    assert list(p) == ["W1", "W2"] and p["W1"].shape == (16, 48)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 5, 16), jnp.float32)
    got, _ = jax.jit(lambda xx: layer.forward(p, {}, xx))(x)
    gate, up = x @ p["W1"][:, :24], x @ p["W1"][:, 24:]
    want = ((jax.nn.silu(0.7 * gate) * up) @ p["W2"]) * 0.6
    np.testing.assert_allclose(got, want, atol=TOL)
    low, _ = layer.forward(
        jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16), p), {},
        x.astype(jnp.bfloat16))
    assert low.dtype == jnp.bfloat16
    np.testing.assert_allclose(low.astype(jnp.float32), want, atol=0.05)


# -------------------------------------------------- the model and the server
def _gaps(params, sizes, prompt, tokens):
    """By how much each served token's reference logit lies below the
    reference's best at its position."""
    ids = np.concatenate([prompt, tokens])
    n = len(tokens)
    want = np.asarray(REF.sequence_logits(params, ids, len(prompt) - 1, n,
                                          sizes))
    return want.max(-1) - want[np.arange(n), tokens]


def test_whole_sequence_probabilities_are_the_references(falcon):
    net, params, sizes = falcon
    ids = np.random.default_rng(0).integers(0, V, 21)
    x = np.eye(V, dtype=np.float32)[ids][None]
    want = np.asarray(jax.nn.softmax(
        REF.sequence_logits(params, ids, 0, 21, sizes), axis=-1))
    np.testing.assert_allclose(np.asarray(net.output(x))[0], want, atol=1e-6)


def _streamed_error(net, params, sizes):
    """Prefill two chunks (the second starts at position 7), then decode
    token by token through the streaming carry: the widest difference from
    the reference's log-probabilities."""
    ids = np.random.default_rng(4).integers(0, V, 24)
    x = np.eye(V, dtype=np.float32)[ids][None]
    want = np.asarray(jax.nn.log_softmax(
        REF.sequence_logits(params, ids, 0, 24, sizes), axis=-1))
    net.rnn_clear_previous_state()
    got = [np.asarray(net.rnn_time_step(x[:, :7]))[0],
           np.asarray(net.rnn_time_step(x[:, 7:12]))[0]]
    got += [np.asarray(net.rnn_time_step(x[:, t:t + 1]))[0]
            for t in range(12, 24)]
    net.rnn_clear_previous_state()
    return float(np.abs(np.log(np.concatenate(got)) - want).max())


def _restart_rotation_at_each_chunk(monkeypatch):
    """Every chunk of more than one token is rotated from position 0: a
    request's first prefill round is sound, a later one is not."""
    real = SelfAttentionLayer._qkv
    monkeypatch.setattr(
        SelfAttentionLayer, "_qkv",
        lambda self, p, x, start=None: real(
            self, p, x, None if x.shape[1] > 1 else start))


FAULTS = ("state_in_bfloat16", "key_unrotated", "rotation_restarted",
          "one_norm_over_both_groups", "multiplier_dropped")


@pytest.mark.parametrize("fault", (None,) + FAULTS)
def test_prefill_then_decode_is_the_reference_and_each_fault_is_not(
        falcon, fault, monkeypatch):
    """Log-probabilities of two prefilled chunks and twelve decoded tokens
    against the reference's full forward: sound to 3e-6 (a few float32 ulps
    of values near -4); each planted fault reads over 1e-5: the scan state
    rounded to bfloat16 after each call (the smallest, about 3e-5), keys
    left unrotated, the rotation restarted at a chunk boundary, one norm
    over both groups, the projection's multipliers dropped."""
    net, params, sizes = falcon
    restore = None
    if fault == "state_in_bfloat16":
        real = Mamba2Layer._mix

        def rounded(self, p, h, conv, ssm, mask):
            out, conv, ssm = real(self, p, h, conv, ssm, mask)
            return out, conv, ssm.astype(jnp.bfloat16).astype(jnp.float32)

        monkeypatch.setattr(Mamba2Layer, "_mix", rounded)
    elif fault == "key_unrotated":
        real = attention_module.rotate_half_pairs
        monkeypatch.setattr(
            attention_module, "rotate_half_pairs",
            lambda t, pos, theta: t.astype(jnp.float32)
            if t.shape[1] == sizes["kv_heads"] else real(t, pos, theta))
    elif fault == "rotation_restarted":
        _restart_rotation_at_each_chunk(monkeypatch)
    elif fault == "one_norm_over_both_groups":
        def one_norm(self, y, z, w):
            y = y * jax.nn.silu(z.astype(jnp.float32))
            return y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True)
                                     + self.norm_eps) * w
        monkeypatch.setattr(Mamba2Layer, "_gated_norm", one_norm)
    elif fault == "multiplier_dropped":
        layer = net.conf.vertices["ssm1"].layer
        restore = (layer, layer.proj_multipliers)
        layer.proj_multipliers = None
    net._output_cache.clear()          # programs traced without the fault
    try:
        err = _streamed_error(net, params, sizes)
    finally:
        net._output_cache.clear()
        if restore:
            restore[0].proj_multipliers = restore[1]
    if fault is None:
        assert err <= 3e-6
    else:
        assert err > 1e-5, err


def _serve(net, reqs, **kw):
    srv = GenerationServer(net, V, **{**dict(slots=3, page_size=8,
                                             prefill_chunk=16,
                                             steps_per_dispatch=2), **kw})
    try:
        keys = set(srv.stats())
        futs = [srv.submit(p, k) for p, k in reqs]
        outs = [f.result(timeout=120) for f in futs]
        assert set(srv.stats()) == keys
        return (srv, outs, srv.metrics.snapshot(),
                render_text([({}, srv.metrics)]))
    finally:
        srv.close()


REQS = ((5, 6), (40, 9), (17, 5), (3, 12), (33, 4), (9, 7), (21, 8))


@pytest.mark.generation
def test_served_through_slots_is_the_references_full_forward(falcon):
    """Seven greedy requests through three slots: prompts of 3 to 40 tokens
    over one to three prefill rounds of at most 16, slots retired and used
    again, row groups prefilled while other slots decode. Every block owns
    a paged layer and a slot-state layer; every served token is the
    reference's best at its position to rounding (a gap of 1e-5 in logits
    of order one); each slot's state was zeroed once a request; and the
    decode dispatches counted the keys they had to read against the keys
    the dense view holds."""
    net, params, sizes = falcon
    rng = np.random.default_rng(1)
    reqs = [(rng.integers(0, V, n), k) for n, k in REQS]
    srv, outs, snap, text = _serve(net, reqs)
    assert srv._paged_names == ["attn0", "attn1"]
    assert srv._slot_names == ["ssm0", "ssm1"] and srv._pa == "xla"
    # kv heads of the layer's own head size, two layers, float32
    assert srv._page_token_bytes == 2 * (2 * 2 * 4 * 4)
    worst = max(float(_gaps(params, sizes, p, t).max())
                for (p, _), t in zip(reqs, outs))
    assert worst <= TOL
    assert all(t.shape == (k,) for (_, k), t in zip(reqs, outs))
    assert snap["generation_slot_state_resets_total"] == 7
    assert snap["generation_slot_state_bytes"] == srv._slot_state_bytes \
        == 2 * 3 * (3 * 64 * 4 + 4 * 8 * 8 * 4)
    live = snap["generation_kv_live_tokens_total"]["program=decode"]
    viewed = snap["generation_kv_viewed_tokens_total"]["program=decode"]
    steps = snap["generation_decode_steps_total"]
    # the dense view: every slot's whole capacity, each micro-step and layer
    assert viewed == steps * 2 * 3 * 128 * 2
    # a decoded token at context c read c keys a layer; the dispatch of two
    # may run one micro-step past a request's end
    need = 2 * sum(sum(range(len(p) + 1, len(p) + k)) for p, k in reqs)
    assert need <= live <= need + 2 * sum(len(p) + k for p, k in reqs)
    assert 0 < live < viewed
    for name in ("generation_kv_live_tokens_total",
                 "generation_kv_viewed_tokens_total"):
        assert name + '{program="decode"}' in text


@pytest.mark.generation
def test_served_through_the_paged_kernel_is_the_xla_servers(falcon):
    """The same seven requests under ``paged_attention="pallas"`` (the
    kernel interpreted): a decode step of the grouped layers (two query
    heads a key/value head, told which rows advance) is the Mosaic read of
    the pages in place, chunked prefill keeps its reads over gathered
    views. The tokens are the XLA server's and the reference's best; the
    decode dispatches booked what the kernel fetched, each advancing row's
    live pages, and nothing of a dense view."""
    net, params, sizes = falcon
    rng = np.random.default_rng(1)
    reqs = [(rng.integers(0, V, n), k) for n, k in REQS]
    srv, outs, snap, _ = _serve(net, reqs, paged_attention="pallas")
    assert srv._pa == "pallas"
    _, want, _, _ = _serve(net, reqs)
    for got, w in zip(outs, want):
        np.testing.assert_array_equal(got, w)
    worst = max(float(_gaps(params, sizes, p, t).max())
                for (p, _), t in zip(reqs, outs))
    assert worst <= TOL
    live = snap["generation_kv_live_tokens_total"]["program=decode"]
    viewed = snap["generation_kv_viewed_tokens_total"]["program=decode"]
    steps = snap["generation_decode_steps_total"]
    # whole pages of 8: at most 7 keys a row, layer and micro-step more
    assert viewed % 8 == 0
    assert live < viewed <= live + steps * 2 * 3 * 7 * 2


@pytest.mark.generation
@pytest.mark.parametrize("fault", ["rotation_restarted",
                                   "slot_state_not_reset"])
def test_a_fault_in_the_served_path_is_not_the_reference(falcon, fault,
                                                         monkeypatch):
    """The served-token comparison with the server's part broken
    underneath: a later prefill round rotated from position 0 instead of
    where the round starts, or a slot's state left from the request before.
    Long prompts over several rounds, then short ones into used slots: the
    gap misses the tolerance by orders of magnitude."""
    from deeplearning4j_tpu.parallel import generation

    net, params, sizes = falcon
    if fault == "rotation_restarted":
        _restart_rotation_at_each_chunk(monkeypatch)
    else:
        real = generation._seed_extras
        monkeypatch.setattr(
            generation, "_seed_extras",
            lambda carry, pool, slot_st, stats, fresh=None: real(
                carry, pool, slot_st, stats))
    net._output_cache.clear()          # programs traced without the fault
    rng = np.random.default_rng(2)
    reqs = [(rng.integers(0, V, n), 12) for n in (30, 2, 25, 3, 28, 2)]
    try:
        _, outs, _, _ = _serve(net, reqs, slots=1)
    finally:
        net._output_cache.clear()
    worst = max(float(_gaps(params, sizes, p, t).max())
                for (p, _), t in zip(reqs, outs))
    assert worst > 100 * TOL, worst


def test_zoo_lists_the_model():
    from deeplearning4j_tpu.models import zoo_models

    assert zoo_models()["falconh1lm"] is FalconH1LM
