"""The hybrid state-space / attention / routed-expert language model
(``GraniteMoeHybridLM``) and what it forced: ``Mamba2Layer``'s three
forwards over one set of equations, grouped-query attention through the
contiguous, streaming and paged paths, routed expert dispatch and a chip's
share of the experts, and per-slot state beside the paged KV pool in
``GenerationServer`` — each held against a plain statement of the same
mathematics (``benchmarks/references/granite_4.0_h_small.py``, or a loop
written here).

Everything is float32 at toy widths, so agreement is to rounding: the
tolerances below are a few float32 ulps of values of order one, summed over
tens of terms (1e-5), and every planted fault misses them by orders of
magnitude.
"""

import importlib.util
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models import GraniteMoeHybridLM
from deeplearning4j_tpu.nn.conf.layers import (DenseLayer, Mamba2Layer,
                                               MixtureOfExpertsLayer,
                                               RMSNormalization,
                                               RnnOutputLayer,
                                               SelfAttentionLayer)
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.parallel.generation import GenerationServer
from deeplearning4j_tpu.parallel.handoff import SnapshotUnsupported
from deeplearning4j_tpu.parallel.mesh import MeshGeometryError

TOL = 1e-5


def _reference():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "references",
        "granite_4.0_h_small.py")
    spec = importlib.util.spec_from_file_location("granite_reference", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()

V = 48
SIZES = {"vocab": V, "d_model": 32,
         "layer_types": ["mamba", "attention", "mamba"],
         "heads": 4, "kv_heads": 2, "head_dim": 8,
         "experts": 8, "experts_held": [0, 4], "top_k": 3,
         "expert_width": 16, "shared_width": 24,
         "mamba_heads": 4, "mamba_head_dim": 16, "d_state": 8,
         "n_groups": 1, "d_conv": 4, "chunk": 8,
         "attention_multiplier": 0.25, "embedding_multiplier": 12,
         "residual_multiplier": 1.0, "logits_scaling": 4, "rms_eps": 1e-5}
INIT = {"kernel_std": 0.1, "head_std": 0.5, "a_min": 1.0, "a_max": 16.0,
        "dt_min": 0.001, "dt_max": 0.1}


def tiny_granite(seed=5, experts_held=(0, 4)):
    """The zoo model at toy widths in float32, holding the reference's
    (bfloat16-valued) weights: (net, params, sizes)."""
    sizes = dict(SIZES, experts_held=list(experts_held))
    params = REF.make_params(seed, sizes, INIT)
    model = GraniteMoeHybridLM(
        num_labels=V, max_length=128, d_model=32,
        layer_types=sizes["layer_types"], n_heads=4, n_kv_heads=2,
        attention_multiplier=0.25, embedding_multiplier=12,
        residual_multiplier=sizes["residual_multiplier"], logits_scaling=4,
        n_experts=8,
        experts_held=experts_held, top_k=3, expert_width=16,
        shared_width=24, mamba_heads=4, mamba_head_dim=16, mamba_d_state=8,
        mamba_chunk=8, dtype="float32")
    conf = model.conf()
    for v in conf.vertices.values():
        layer = getattr(v, "layer", None)
        if layer is not None and hasattr(layer, "max_cache"):
            layer.max_cache = 128
    net = ComputationGraph(conf)
    net.init(params={n: params.get(n, {}) for n in conf.topo_order})
    return net, params, sizes


@pytest.fixture(scope="module")
def granite():
    return tiny_granite()


# ------------------------------------------------------------ Mamba2Layer
def _mamba(chunk=4):
    layer = Mamba2Layer(n_in=16, n_out=16, n_heads=4, head_dim=8, d_state=8,
                        chunk_size=chunk, weight_init="xavier")
    layer.finalize()
    p = layer.init_params(jax.random.PRNGKey(0))
    # off their initial values, so that every parameter matters
    p["conv_b"] = 0.1 * jax.random.normal(jax.random.PRNGKey(1),
                                          p["conv_b"].shape)
    p["D"] = p["D"] + 0.3 * jax.random.normal(jax.random.PRNGKey(2),
                                              p["D"].shape)
    p["norm_w"] = p["norm_w"] + 0.2 * jax.random.normal(
        jax.random.PRNGKey(3), p["norm_w"].shape)
    return layer, p


def _sequential(layer, p, x):
    """The layer's docstring, one position at a time, for one row."""
    H, P, N, K = layer.n_heads, layer.head_dim, layer.d_state, layer.d_conv
    di, cd = layer.d_inner, layer.conv_dim
    proj = x @ p["W_in"]
    z, xbc, dt = proj[:, :di], proj[:, di:di + cd], proj[:, di + cd:]
    pad = jnp.concatenate([jnp.zeros((K - 1, cd)), xbc])
    xbc = jax.nn.silu(p["conv_b"] + sum(
        p["conv_w"][:, j] * pad[j:j + x.shape[0]] for j in range(K)))
    xs = xbc[:, :di].reshape(-1, H, P)
    bm, cm = xbc[:, di:di + N], xbc[:, di + N:]
    dt = jax.nn.softplus(dt + p["dt_bias"])
    a = -jnp.exp(p["A_log"])
    S = jnp.zeros((H, P, N))
    ys, states = [], []
    for t in range(x.shape[0]):
        S = jnp.exp(dt[t] * a)[:, None, None] * S \
            + (dt[t][:, None] * xs[t])[:, :, None] * bm[t][None, None, :]
        ys.append(S @ cm[t] + p["D"][:, None] * xs[t])
        states.append(S)
    y = jnp.stack(ys).reshape(-1, di) * jax.nn.silu(z)
    y = p["norm_w"] * y / jnp.sqrt(jnp.mean(y * y, -1, keepdims=True)
                                   + layer.norm_eps)
    return y @ p["W_out"], jnp.stack(states)


@pytest.fixture(scope="module")
def mamba_case():
    layer, p = _mamba()
    x = jax.random.normal(jax.random.PRNGKey(7), (3, 13, 16), jnp.float32)
    want = jax.jit(jax.vmap(lambda r: _sequential(layer, p, r)))(x)
    return layer, p, x, want


def _stream(layer, p, x, cuts, masks=None):
    """Feed ``x`` in the chunks ``cuts`` names, carrying the state."""
    fwd = jax.jit(lambda st, xx, mk: layer.forward(p, st, xx, mask=mk))
    st = layer.init_streaming_carry(x.shape[0])
    outs = []
    for a, b in zip(cuts[:-1], cuts[1:]):
        o, st = fwd(st, x[:, a:b], None if masks is None else masks[:, a:b])
        outs.append(o)
    return jnp.concatenate(outs, axis=1), st


@pytest.mark.parametrize("cuts", [
    None,                                   # the whole sequence, no carry
    (0, 13),                                # one streamed chunk from zeros
    (0, 5, 6, 13),                          # uneven chunks, one of them a token
    tuple(range(14)),                       # token by token: the recurrence
], ids=["whole", "one_chunk", "uneven_chunks", "tokens"])
def test_mamba2_every_forward_is_the_sequential_scan(mamba_case, cuts):
    layer, p, x, (want, states) = mamba_case
    if cuts is None:
        got, _ = jax.jit(lambda xx: layer.forward(p, {}, xx))(x)
    else:
        got, st = _stream(layer, p, x, cuts)
        np.testing.assert_allclose(st["ssm_state"], states[:, -1], atol=TOL)
        # the tail of the convolution is its last d_conv - 1 inputs
        xbc = (x @ p["W_in"])[..., layer.d_inner:
                              layer.d_inner + layer.conv_dim]
        np.testing.assert_allclose(st["conv_state"], xbc[:, -3:], atol=TOL)
    np.testing.assert_allclose(got, want, atol=TOL)


def test_mamba2_right_padded_rows_end_their_state_at_their_last_token(
        mamba_case):
    """Rows of true lengths 13, 9 and 4 in one batch, fed as chunks of 6
    and 7 columns with masks: each row's outputs and final state are those
    of the row alone, padded columns change nothing (the third row's state
    passes through the second chunk untouched), and the carry crosses the
    chunk boundary inside rows one and two."""
    layer, p, x, (want, states) = mamba_case
    lens = np.array([13, 9, 4])
    mask = (np.arange(13)[None, :] < lens[:, None]).astype(np.float32)
    got, st = _stream(layer, p, x, (0, 6, 13), masks=jnp.asarray(mask))
    for r, n in enumerate(lens):
        np.testing.assert_allclose(got[r, :n], want[r, :n], atol=TOL)
        assert not np.any(np.asarray(got[r, n:]))
        np.testing.assert_allclose(st["ssm_state"][r], states[r, n - 1],
                                   atol=TOL)
    _, st6 = _stream(layer, p, x, (0, 6), masks=jnp.asarray(mask))
    assert np.array_equal(np.asarray(st["ssm_state"][2]),
                          np.asarray(st6["ssm_state"][2]))
    assert np.array_equal(np.asarray(st["conv_state"][2]),
                          np.asarray(st6["conv_state"][2]))


def test_mamba2_state_is_float32_whatever_the_network():
    layer, _ = _mamba()
    c = layer.init_streaming_carry(2, jnp.bfloat16)
    assert c["ssm_state"].dtype == jnp.float32
    assert c["conv_state"].dtype == jnp.bfloat16
    assert c["ssm_state"].shape == (2, 4, 8, 8)
    assert c["conv_state"].shape == (2, 3, layer.conv_dim)


# ------------------------------------------------------- norm and the head
def test_rms_normalization_is_the_formula():
    layer = RMSNormalization(n_out=8, eps=1e-5)
    layer.finalize()
    p = {"gamma": jnp.linspace(0.5, 1.5, 8)}
    x = jax.random.normal(jax.random.PRNGKey(0), (3, 5, 8))
    got, _ = layer.forward(p, {}, x)
    want = p["gamma"] * x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True)
                                     + 1e-5)
    np.testing.assert_allclose(got, want, atol=1e-6)
    assert list(layer.init_params(None)) == ["gamma"]
    low, _ = layer.forward({"gamma": p["gamma"].astype(jnp.bfloat16)}, {},
                           x.astype(jnp.bfloat16))
    assert low.dtype == jnp.bfloat16


def test_lm_head_gives_float32_probabilities_over_a_bfloat16_trunk():
    head = RnnOutputLayer(n_in=8, n_out=6, activation="softmax",
                          logits_divisor=4.0)
    head.finalize()
    w = jax.random.normal(jax.random.PRNGKey(0), (8, 6)).astype(jnp.bfloat16)
    p = {"W": w, "b": jnp.zeros((6,), jnp.bfloat16)}
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 3, 8)).astype(
        jnp.bfloat16)
    probs, _ = head.forward(p, {}, x)
    assert probs.dtype == jnp.float32
    want = jax.nn.softmax(
        x.astype(jnp.float32) @ w.astype(jnp.float32) / 4.0, axis=-1)
    np.testing.assert_allclose(probs, want, atol=1e-6)
    # over a float32 trunk and with no divisor it is DenseLayer's head,
    # operation for operation
    plain = RnnOutputLayer(n_in=8, n_out=6, activation="softmax")
    plain.finalize()
    p32 = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p)
    x32 = x.astype(jnp.float32)
    assert str(jax.make_jaxpr(lambda a: plain.preactivate(p32, a))(x32)) \
        == str(jax.make_jaxpr(
            lambda a: DenseLayer.preactivate(plain, p32, a))(x32))


# ------------------------------------------------------------ expert layer
def _moe(**kw):
    layer = MixtureOfExpertsLayer(n_in=16, n_out=16, n_experts=8, top_k=3,
                                  expert_hidden=12, **kw)
    layer.finalize()
    return layer


def test_routed_dispatch_is_todays_dense_dispatch_on_the_same_weights():
    dense, routed = _moe(), _moe(dispatch="routed")
    p = dense.init_params(jax.random.PRNGKey(0))
    p["b1"] = jax.random.normal(jax.random.PRNGKey(1), p["b1"].shape)
    p["b2"] = jax.random.normal(jax.random.PRNGKey(2), p["b2"].shape)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 5, 16))
    want, _ = jax.jit(lambda xx: dense.forward(p, {}, xx))(x)
    got, _ = jax.jit(lambda xx: routed.forward(p, {}, xx))(x)
    np.testing.assert_allclose(got, want, atol=TOL)
    # and with the defaults nothing streams: no carry, today's parameters
    assert dense.init_streaming_carry(2) == {}
    assert dense.param_order() == ("Wg", "W1", "W2", "b1", "b2")


def test_routed_dispatch_backward_is_dense_dispatchs():
    """The grouped product differentiates to what the dense einsums do, in
    the input, the router and every expert: what has to hold before dense
    dispatch can be retired (ROADMAP R3)."""
    dense, routed = _moe(), _moe(dispatch="routed")
    p = dense.init_params(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 5, 16))

    def grads(layer):
        return jax.jit(jax.grad(
            lambda pp, xx: jnp.sum(jnp.sin(layer.forward(pp, {}, xx)[0])),
            argnums=(0, 1)))(p, x)

    for got, want in zip(jax.tree_util.tree_leaves(grads(routed)),
                         jax.tree_util.tree_leaves(grads(dense))):
        np.testing.assert_allclose(got, want, atol=TOL)


def test_expert_shares_add_up_to_the_uncut_layer():
    """The two halves of an expert-parallel pair, each computing what its
    own experts give, plus the shared expert counted once, are the whole
    layer; and the counts say where every (token, expert) pair went."""
    kw = dict(dispatch="routed", gated=True, shared_hidden=6,
              has_bias=False, activation="silu")
    whole = _moe(**kw)
    p = whole.init_params(jax.random.PRNGKey(5))
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 5, 16))
    want, _ = jax.jit(lambda xx: whole.forward(p, {}, xx))(x)
    no_shared = _moe(**dict(kw, shared_hidden=0))
    routed_only, _ = no_shared.forward(
        {k: v for k, v in p.items() if not k.startswith("Ws")}, {}, x)
    total = 0.0
    counts = []
    for first in (0, 4):
        share = _moe(**kw, experts_held=(first, 4))
        ps = dict(p, W1=p["W1"][first:first + 4], W2=p["W2"][first:first + 4])
        assert jax.tree_util.tree_map(jnp.shape, ps) == \
            jax.tree_util.tree_map(jnp.shape,
                                   share.init_params(jax.random.PRNGKey(0)))
        y, st = share.forward(ps, share.init_streaming_carry(2), x)
        total = total + y
        counts.append(np.asarray(st["call_counts"]))
    shared = want - routed_only
    np.testing.assert_allclose(total - shared, want, atol=TOL)
    (h0, a0, c0), (h1, a1, c1) = counts
    assert h0 + a0 == h1 + a1 == 2 * 5 * 3          # tokens x top_k
    assert (h0, a0) == (a1, h1)                     # one's held, other's absent
    assert 1 <= c0 <= 4 and 1 <= c1 <= 4


def test_routed_dispatch_counts_no_masked_token():
    layer = _moe(dispatch="routed", experts_held=(0, 4))
    p = layer.init_params(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 5, 16))
    mask = jnp.asarray([[1, 1, 1, 0, 0], [1, 0, 0, 0, 0]], jnp.float32)
    _, st = layer.forward(p, layer.init_streaming_carry(2), x, mask=mask)
    held, absent, _ = np.asarray(st["call_counts"])
    assert held + absent == 4 * 3


@pytest.mark.parametrize("bad", [
    dict(experts_held=(0, 4)), dict(gated=True), dict(shared_hidden=8),
    dict(has_bias=False), dict(dispatch="routed", experts_held=(6, 4)),
    dict(dispatch="sparse")],
    ids=["held", "gated", "shared", "no_bias", "held_past_end", "name"])
def test_expert_layer_refuses_what_dense_dispatch_cannot_do(bad):
    with pytest.raises(ValueError):
        _moe(**bad)


# ---------------------------------------------------------------- attention
def test_grouped_query_paged_and_streaming_forwards_equal_the_contiguous():
    layer = SelfAttentionLayer(n_in=16, n_out=16, n_heads=4, n_kv_heads=2,
                               causal=True, helper="stock", has_bias=False,
                               score_scale=0.3, max_cache=32)
    layer.finalize()
    layer.validate()
    p = layer.init_params(jax.random.PRNGKey(0))
    assert p["Wk"].shape == p["Wv"].shape == (16, 8) and "b" not in p
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 11, 16))
    want, _ = jax.jit(lambda xx: layer.forward(p, {}, xx))(x)
    # by hand, for the first row: query head j reads key/value head j // 2
    q = (x[0] @ p["Wq"]).reshape(11, 4, 4)
    k = (x[0] @ p["Wk"]).reshape(11, 2, 4)
    v = (x[0] @ p["Wv"]).reshape(11, 2, 4)
    rows = []
    for j in range(4):
        s = 0.3 * q[:, j] @ k[:, j // 2].T
        s = jnp.where(jnp.tril(jnp.ones((11, 11), bool)), s, -1e30)
        rows.append(jax.nn.softmax(s, -1) @ v[:, j // 2])
    by_hand = jnp.stack(rows, 1).reshape(11, 16) @ p["Wo"]
    np.testing.assert_allclose(want[0], by_hand, atol=TOL)
    # dense streaming cache, in chunks
    fwd = jax.jit(lambda st, xx: layer.forward(p, st, xx))
    st = layer.init_streaming_carry(2)
    assert st["kcache"].shape == (2, 2, 32, 4)
    outs = []
    for a, b in ((0, 4), (4, 5), (5, 11)):
        o, st = fwd(st, x[:, a:b])
        outs.append(o)
    np.testing.assert_allclose(jnp.concatenate(outs, 1), want, atol=TOL)
    # paged pool of 2 key/value heads, rows at their own positions
    pool = layer.init_paged_carry(9, 4)
    assert pool["kpages"].shape == (9, 4, 8)
    bt = jnp.asarray([[1, 2, 3, 0], [4, 5, 6, 0]], jnp.int32)
    pos = jnp.zeros((2,), jnp.int32)
    outs = []
    for a, b in ((0, 4), (4, 5), (5, 11)):
        o, ns = fwd(dict(pool, block_table=bt, cache_pos=pos), x[:, a:b])
        pool = {"kpages": ns["kpages"], "vpages": ns["vpages"]}
        pos = ns["cache_pos"]
        outs.append(o)
    np.testing.assert_allclose(jnp.concatenate(outs, 1), want, atol=TOL)


def test_grouped_heads_are_read_through_xla_and_never_through_pallas():
    """A grouped layer's prefill chunks (``T > 1``) are read through XLA
    over gathered views whatever the server resolved, never through the
    Mosaic paged kernel; its decode step (``T`` 1) is the kernel's on a
    TPU, where its four query heads a key/value head fit VMEM."""
    from deeplearning4j_tpu.nn.conf.layers.paged_attention import (
        resolve_paged_backend)

    layer = SelfAttentionLayer(n_in=512, n_out=512, n_heads=4, n_kv_heads=1,
                               causal=True, score_scale=0.3)
    assert layer.paged_kernel_read(256) == (1, 4)
    assert layer.reads_in_place("pallas", 1)
    for T in (2, 16, 256):
        assert not layer.reads_in_place("pallas", T)
        assert not layer.reads_in_place("xla", T)
    geo = dict(page_size=16, head_dim=128, n_pages=128)
    assert resolve_paged_backend("auto", platform="tpu", chunk=1, group=4,
                                 **geo) == "pallas"
    # a read the kernel cannot take keeps the family on XLA
    assert resolve_paged_backend("auto", platform="tpu", group=None,
                                 **geo) == "xla"
    with pytest.raises(ValueError):
        resolve_paged_backend("pallas", platform="tpu", group=None, **geo)


# -------------------------------------------------- the model and the server
def _gaps(params, sizes, prompt, tokens):
    """By how much each served token's reference logit lies below the
    reference's best at its position."""
    ids = np.concatenate([prompt, tokens])
    n = len(tokens)
    want = np.asarray(REF.sequence_logits(params, ids, len(prompt) - 1, n,
                                          sizes))
    return want.max(-1) - want[np.arange(n), tokens]


def test_whole_sequence_and_streamed_probabilities_are_the_references(
        granite):
    net, params, sizes = granite
    ids = np.random.default_rng(0).integers(0, V, 21)
    x = np.eye(V, dtype=np.float32)[ids][None]
    want = np.asarray(jax.nn.softmax(
        REF.sequence_logits(params, ids, 0, 21, sizes), axis=-1))
    np.testing.assert_allclose(np.asarray(net.output(x))[0], want, atol=1e-6)
    net.rnn_clear_previous_state()
    got = [np.asarray(net.rnn_time_step(x[:, a:b]))[0]
           for a, b in ((0, 9), (9, 10), (10, 21))]
    net.rnn_clear_previous_state()
    np.testing.assert_allclose(np.concatenate(got), want, atol=1e-6)


@pytest.mark.generation
def test_served_through_slots_is_the_references_full_forward(granite):
    """Seven greedy requests through three slots: prompts of 3 to 40
    tokens over one to three prefill rounds of at most 16, slots retired
    and used again, waves prefilled while other slots decode. Every served
    token is the reference's best at its position to rounding (a gap of
    1e-5 in logits of order one: float32 sums of some hundred terms), the
    expert counts add up, and the slot state was reset once per request."""
    net, params, sizes = granite
    rng = np.random.default_rng(1)
    reqs = [(rng.integers(0, V, n), k) for n, k in
            ((5, 6), (40, 9), (17, 5), (3, 12), (33, 4), (9, 7), (21, 8))]
    srv = GenerationServer(net, V, slots=3, page_size=8, prefill_chunk=16,
                           steps_per_dispatch=2)
    try:
        keys = set(srv.stats())
        assert srv._slot_names == ["mix0", "mix2"] and srv._pa == "xla"
        assert srv._page_token_bytes == 2 * 2 * 8 * 4    # kv_heads, not heads
        futs = [srv.submit(p, k) for p, k in reqs]
        worst = 0.0
        for (p, k), f in zip(reqs, futs):
            toks = f.result(timeout=120)
            assert toks.shape == (k,)
            worst = max(worst, float(_gaps(params, sizes, p, toks).max()))
        assert worst <= TOL
        st = srv.stats()
        assert set(st) == keys and st["prefills"] == 7
        assert st["pages"]["prefix_hits"] == 0
        snap = srv.metrics.snapshot()
        assert snap["generation_slot_state_resets_total"] == 7
        # dispatches: 1 + 3 + 2 + 1 + 3 + 1 + 2 = 13 row-chunks of at most
        # 16 tokens, one to a dispatch if no two shared a row group, and
        # at best PREFILL_ROWS to a dispatch
        assert -(-13 // srv._prefill_rows) \
            <= snap["generation_prefill_rounds_total"] <= 13
        rows = snap["generation_prefill_rows_total"]
        assert rows["kind=admitted"] == 13
        assert rows["kind=computed"] == srv._prefill_rows \
            * snap["generation_prefill_rounds_total"]
        assert snap["generation_slot_state_bytes"] == srv._slot_state_bytes \
            == 2 * 3 * (3 * 80 * 4 + 4 * 16 * 8 * 4)
        pairs = snap["generation_moe_assignments_total"]
        calls = snap["generation_moe_expert_calls_total"]

        def routed(program):
            return sum(pairs[f"held={h}|program={program}"]
                       for h in ("yes", "no"))

        # every prompt token once: padding, and the rows that ride along
        # in a wave, are not routed
        assert routed("prefill") == sum(len(p) for p, _ in reqs) * 3 * 3
        # every decoded token once, plus the micro-step a dispatch of two
        # runs past a request's end; a free slot's stale token is not routed
        decoded = sum(k - 1 for _, k in reqs) * 3 * 3
        assert decoded <= routed("decode") <= decoded + 7 * 9
        for program in ("prefill", "decode"):
            assert 0 < calls[f"program={program}"] \
                <= pairs[f"held=yes|program={program}"]
    finally:
        srv.close()


def _streamed_error(net, params, sizes):
    """Prefill a chunk, then decode token by token through the streaming
    carry: the widest difference from the reference's log-probabilities
    (differences of logits, whatever their level)."""
    ids = np.random.default_rng(4).integers(0, V, 24)
    x = np.eye(V, dtype=np.float32)[ids][None]
    want = np.asarray(jax.nn.log_softmax(
        REF.sequence_logits(params, ids, 0, 24, sizes), axis=-1))
    net.rnn_clear_previous_state()
    got = [np.asarray(net.rnn_time_step(x[:, :12]))[0]]
    got += [np.asarray(net.rnn_time_step(x[:, t:t + 1]))[0]
            for t in range(12, 24)]
    net.rnn_clear_previous_state()
    return float(np.abs(np.log(np.concatenate(got)) - want).max())


@pytest.mark.parametrize("fault", [None, "state_in_bfloat16",
                                   "expert_skipped"])
def test_prefill_then_decode_fails_a_bfloat16_state_and_a_skipped_expert(
        granite, fault, monkeypatch):
    """Log-probabilities of a prefilled chunk and twelve decoded tokens
    against the reference's full forward: sound to 3e-6 (a few float32
    ulps of values near -4; it reads 1e-6); with the scan state rounded to
    bfloat16 after each call it reads 3e-5, with one held expert's output
    dropped far more."""
    net, params, sizes = granite
    if fault == "state_in_bfloat16":
        real = Mamba2Layer._mix

        def rounded(self, p, h, conv, ssm, mask):
            out, conv, ssm = real(self, p, h, conv, ssm, mask)
            return out, conv, ssm.astype(jnp.bfloat16).astype(jnp.float32)

        monkeypatch.setattr(Mamba2Layer, "_mix", rounded)
    elif fault == "expert_skipped":
        real = MixtureOfExpertsLayer._routed

        def skipped(self, p, x, mask):
            return real(self, dict(p, W2=p["W2"].at[1].set(0.0)), x, mask)

        monkeypatch.setattr(MixtureOfExpertsLayer, "_routed", skipped)
    net._output_cache.clear()          # programs traced without the fault
    try:
        err = _streamed_error(net, params, sizes)
    finally:
        net._output_cache.clear()
    if fault is None:
        assert err <= 3e-6
    else:
        assert err > 1e-5, err


@pytest.mark.generation
def test_a_slot_that_is_not_reset_at_admission_is_not_the_reference(
        granite, monkeypatch):
    """The served-token comparison with the reset broken underneath: the
    second request into a slot starts from the first one's state and
    misses the tolerance by orders of magnitude."""
    from deeplearning4j_tpu.parallel import generation

    net, params, sizes = granite
    real = generation._seed_extras
    monkeypatch.setattr(
        generation, "_seed_extras",
        lambda carry, pool, slot_st, stats, fresh=None: real(
            carry, pool, slot_st, stats))
    net._output_cache.clear()          # programs traced without the fault
    rng = np.random.default_rng(2)
    # short prompts after long ones: what the last request left in the
    # slot then weighs most
    reqs = [(rng.integers(0, V, n), 12) for n in (30, 2, 25, 3, 28, 2)]
    srv = GenerationServer(net, V, slots=1, page_size=8, prefill_chunk=16,
                           steps_per_dispatch=2)
    try:
        worst = 0.0
        for p, k in reqs:
            toks = srv.submit(p, k).result(timeout=120)
            worst = max(worst, float(_gaps(params, sizes, p, toks).max()))
    finally:
        srv.close()
        net._output_cache.clear()
    assert worst > 100 * TOL, worst


@pytest.mark.generation
def test_a_preempted_request_resumes_by_recomputing(granite):
    """A pool too small for three long answers at once: the newest slot is
    preempted, takes no snapshot, and comes back through prefill with the
    same tokens the unpressed server gives."""
    net, params, sizes = granite
    rng = np.random.default_rng(3)
    reqs = [(rng.integers(0, V, 20), 40) for _ in range(3)]

    def serve(pages):
        srv = GenerationServer(net, V, slots=3, page_size=8,
                               prefill_chunk=16, steps_per_dispatch=2,
                               pages=pages)
        try:
            futs = [srv.submit(p, k, temperature=0.7 * (i % 2), top_k=5,
                               seed=i) for i, (p, k) in enumerate(reqs)]
            outs = [f.result(timeout=180) for f in futs]
            return outs, srv.stats()
        finally:
            srv.close()

    roomy, st0 = serve(None)
    tight, st1 = serve(17)                   # 16 usable: two requests' worth
    assert st0["pages"]["preempted"] == 0 and st1["pages"]["preempted"] > 0
    assert st1["handoff"]["preempt_resumes"] == 0
    for a, b in zip(roomy, tight):
        assert np.array_equal(a, b)
    assert float(_gaps(params, sizes, reqs[0][0], roomy[0]).max()) <= TOL


# case, prompt tokens, new tokens, temperature, top_k, seed: through three
# slots, so that the last is admitted while the first still decodes
ROUND_SPECS = (("three_rounds", 40, 12, 0.0, 0, 0),
               ("one_round", 12, 4, 0.8, 5, 12),
               ("two_rounds", 30, 5, 0.8, 5, 30),
               ("three_rounds", 40, 4, 0.8, 5, 40))
ROUND_CASES = ("one_round", "two_rounds", "three_rounds")


@pytest.fixture(scope="module")
def round_refs(granite):
    """The prompts of ``ROUND_SPECS`` and what ``sample_generate`` returns
    for each with the same seed (the serial path, no server)."""
    from deeplearning4j_tpu.models.zoo import sample_generate

    net = granite[0]
    rng = np.random.default_rng(29)
    prompts = [rng.integers(0, V, plen) for _, plen, *_ in ROUND_SPECS]
    refs = [sample_generate(net, p[None], n, V, temperature=t, top_k=k,
                            seed=sd)[0]
            for p, (_, _, n, t, k, sd) in zip(prompts, ROUND_SPECS)]
    return prompts, refs


@pytest.fixture(scope="module")
def rounds_served(granite, round_refs):
    """One server, ``prefill_chunk=16``, the requests of ``ROUND_SPECS``:
    by case, (served, serial) pairs; and the counters."""
    net = granite[0]
    prompts, refs = round_refs
    srv = GenerationServer(net, V, slots=3, page_size=8, prefill_chunk=16,
                           steps_per_dispatch=2)
    try:
        futs = [srv.submit(p, n, temperature=t, top_k=k, seed=sd)
                for p, (_, _, n, t, k, sd) in zip(prompts, ROUND_SPECS)]
        outs = [f.result(timeout=180) for f in futs]
        snap = srv.metrics.snapshot()
    finally:
        srv.close()
    pairs = {}
    for (case, *_), got, ref in zip(ROUND_SPECS, outs, refs):
        pairs.setdefault(case, []).append((got, ref))
    return pairs, snap


@pytest.mark.generation
@pytest.mark.parametrize("case", ROUND_CASES)
def test_a_prompt_continued_across_rounds_is_the_serial_path(rounds_served,
                                                             case):
    """Rounds arrive as token ids and the program builds the one-hot rows:
    a request whose prompt takes one, two or three rounds of sixteen,
    greedy or sampled, with the slot state carried from round to round
    while another slot decodes, returns the serial path's tokens, token
    for token."""
    pairs, snap = rounds_served
    for got, ref in pairs[case]:
        assert np.array_equal(got, ref)
    rounds = snap["generation_prefill_rounds_total"]
    # dispatches: 3 + 1 + 2 + 3 = 9 row-chunks, one to a dispatch if no two
    # prompts shared a row group. The first three admitted in one wave at
    # two rows a group: 2 dispatches, then 1, then 1, and the last prompt's
    # three: 7; no packing of nine row-chunks two to a group is under 5
    assert 5 <= rounds <= 9
    assert snap["generation_slot_state_resets_total"] == len(ROUND_SPECS)
    assert 0 < snap["generation_prefill_host_bytes_total"] / rounds \
        < 3 * 16 * 16


@pytest.mark.generation
def test_row_groups_narrower_than_a_wave_are_the_serial_path(
        granite, round_refs, monkeypatch):
    """Row groups of two through four slots: the 40-token request decodes
    while the other three are admitted as ONE wave (a group of two and a
    group of one beside a padding row a round, fewer as prompts end), each
    prompt's slot state carried from group to group over its one, two or
    three rounds of sixteen. Every completion is ``sample_generate``'s,
    token for token; each slot was zeroed once; computed rows are
    dispatches x 2 and admitted rows the 16-token chunks of the prompts."""
    monkeypatch.setattr(GenerationServer, "PREFILL_ROWS", 2)
    net = granite[0]
    prompts, refs = round_refs
    srv = GenerationServer(net, V, slots=4, page_size=8, prefill_chunk=16,
                           steps_per_dispatch=2)
    try:
        srv.set_active_slots(1)
        futs = [srv.submit(p, n, temperature=t, top_k=k, seed=sd)
                for p, (_, _, n, t, k, sd) in zip(prompts, ROUND_SPECS)]
        t_end = time.monotonic() + 120
        while srv.stats()["active_slots"] < 1:
            assert time.monotonic() < t_end, "never admitted"
            time.sleep(0.001)
        srv.set_active_slots(4)
        outs = [f.result(timeout=180) for f in futs]
        snap = srv.metrics.snapshot()
    finally:
        srv.close()
    for got, ref in zip(outs, refs):
        assert np.array_equal(got, ref)
    rows = snap["generation_prefill_rows_total"]
    dispatches = snap["generation_prefill_rounds_total"]
    assert rows["kind=admitted"] == 3 + 1 + 2 + 3
    assert rows["kind=computed"] == 2 * dispatches
    # the runner's three rounds alone, then the wave's: (2 + 1 rows), (2),
    # (1): two dispatches in its first round, one in each later round
    assert dispatches == 3 + 2 + 1 + 1
    assert snap["generation_slot_state_resets_total"] == len(ROUND_SPECS)


@pytest.mark.generation
def test_a_row_group_writes_the_slots_it_names_and_no_other(granite,
                                                            monkeypatch):
    """The prefill program driven by hand on an idle server's pool, three
    slots of noise as slot state, groups of two rows. A group of slot 1
    and a padding row: slots 0 and 2 (a decoder riding beside, a free slot)
    keep their state to the bit, and slot 1, fresh, ends where a pool of
    zeros ends, whatever it held. Its next chunk, dispatched with the
    padding row FIRST, continues that state: the same two chunks dispatched
    beside a live neighbour instead of padding leave slot 1 the same to
    the bit (no row reads another), and never touch slot 0."""
    monkeypatch.setattr(GenerationServer, "PREFILL_ROWS", 2)
    net = granite[0]
    srv = GenerationServer(net, V, slots=3, page_size=8, prefill_chunk=8,
                           steps_per_dispatch=2)
    try:
        prog = srv._prefill_program(8)
        names = srv._slot_names
        rng = np.random.default_rng(31)
        bt = np.zeros_like(srv._bt)
        for slot in range(3):
            bt[slot, :2] = 1 + 2 * slot + np.arange(2)
        chunks = rng.integers(0, V, (2, 8)).astype(np.int32)
        other = rng.integers(0, V, (2, 8)).astype(np.int32)
        pad = np.zeros(8, np.int32)
        PAD = srv.slots

        def noisy(pool):
            noise = {vn: {k: jnp.asarray(
                rng.standard_normal(a.shape), a.dtype)
                for k, a in pool[vn].items()} for vn in names}
            return {**pool, **noise}

        def state(pool):
            return jax.device_get({vn: pool[vn] for vn in names})

        def dispatch(pool, rows, pos0, ids):
            rows = np.asarray(rows, np.int32)
            live = rows < PAD
            out = prog(*srv._weights(), pool, bt, rows,
                       np.asarray(pos0, np.int32), np.stack(ids),
                       np.repeat(live[:, None], 8, 1).astype(np.float32),
                       np.where(live, 8, 1).astype(np.int32),
                       np.zeros(2, np.float32), np.zeros(2, np.int32),
                       np.zeros((2, 2), np.uint32))
            return out[0]

        def same(a, b, slot):
            return all(np.array_equal(a[vn][k][slot], b[vn][k][slot])
                       for vn in names for k in a[vn])

        pool = noisy(srv._pool)
        before = state(pool)
        pool = dispatch(pool, [1, PAD], [0, 0], [chunks[0], pad])
        after1 = state(pool)
        assert same(before, after1, 0) and same(before, after1, 2)
        assert not same(before, after1, 1)
        pool = dispatch(pool, [PAD, 1], [0, 8], [pad, chunks[1]])
        after2 = state(pool)
        assert same(before, after2, 0) and same(before, after2, 2)
        assert not same(after1, after2, 1)
        # from zeros and beside a live neighbour: slot 1 ends the same
        pool = srv._fresh_pool()
        pool = dispatch(pool, [1, PAD], [0, 0], [chunks[0], pad])
        assert same(state(pool), after1, 1)
        pool = noisy(pool)
        held = state(pool)
        pool = dispatch(pool, [2, 1], [0, 0], [other[0], chunks[0]])
        pool = dispatch(pool, [1, 2], [8, 8], [chunks[1], other[1]])
        beside = state(pool)
        assert same(beside, after2, 1)
        assert same(beside, held, 0) and not same(beside, held, 2)
        srv._pool = pool
    finally:
        srv.close()


@pytest.mark.generation
@pytest.mark.parametrize("what", ["prefix_cache", "export_request",
                                  "adopt_request", "export_kv",
                                  "snapshot_every", "role_prefill",
                                  "draft_net", "tp"])
def test_what_still_takes_pages_for_the_whole_state_is_refused_typed(
        granite, what):
    net = granite[0]
    kw = dict(slots=2, page_size=8, prefill_chunk=16)
    if what == "snapshot_every":
        with pytest.raises(ValueError, match="per-slot state"):
            GenerationServer(net, V, snapshot_every=4, **kw)
        return
    if what == "role_prefill":
        with pytest.raises(ValueError, match="per-slot state"):
            GenerationServer(net, V, role="prefill", **kw)
        return
    if what == "draft_net":
        with pytest.raises(ValueError, match="per-slot state"):
            GenerationServer(net, V, draft_net=net, **kw)
        return
    if what == "tp":
        with pytest.raises(MeshGeometryError, match="per-slot state"):
            GenerationServer(net, V, tp=2, **kw)
        return
    srv = GenerationServer(net, V, prefix_cache=True, **kw)
    try:
        if what == "prefix_cache":
            assert srv.prefix_cache is False
            p = np.arange(1, 20)
            a = srv.submit(p, 3).result(timeout=120)
            b = srv.submit(p, 3).result(timeout=120)
            assert np.array_equal(a, b)
            pages = srv.stats()["pages"]
            assert pages["prefix_hits"] == 0 and pages["pages_cached"] == 0
        elif what == "export_request":
            fut = srv.submit(np.arange(1, 6), 4)
            with pytest.raises(SnapshotUnsupported, match="per-slot state"):
                srv.export_request(fut)
            fut.result(timeout=120)
        elif what == "adopt_request":
            with pytest.raises(SnapshotUnsupported, match="per-slot state"):
                srv.adopt_request(object())
        else:
            with pytest.raises(SnapshotUnsupported, match="per-slot state"):
                srv.submit(np.arange(1, 6), 4, export_kv=True)
    finally:
        srv.close()


@pytest.mark.generation
def test_a_closed_server_lets_go_of_its_network(granite):
    """What fills most of a chip has to be freed when its server closes:
    nothing process-wide (the loop supervisor's thread, a registry) may
    keep the server, and through it the weights, alive."""
    import gc
    import weakref

    srv = GenerationServer(granite[0], V, slots=2, page_size=8,
                           prefill_chunk=16)
    srv.submit(np.arange(1, 6), 3).result(timeout=120)
    srv.close()
    gone = weakref.ref(srv)
    del srv
    gc.collect()
    assert gone() is None
