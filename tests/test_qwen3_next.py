"""Qwen3-Next (``Qwen3NextLM``) and what it forced: ``GatedDeltaNetLayer``
with fewer key heads than value heads (``n_key_heads``), the partial
rotation of ``SelfAttentionLayer`` (``rope_dims``), zero-centred norms
(``RMSNormalization(zero_centred=True)``, ``qk_norm_zero_centred``), the
sigmoid-gated shared
expert (``MixtureOfExpertsLayer(shared_gate=True)``) and the server's
gauge of one held expert's bytes: each held against a plain statement of
the same mathematics (``benchmarks/references/qwen3_next_80b_a3b_instruct
.py``: one causal pass, the recurrence position by position, the experts
a loop, no cache, no chunks).

Everything is float32 at toy widths (two key heads for four value heads,
heads of 32 with 8 channels rotated, 16 experts, chunks of 8, pages of
16), so agreement is to rounding (``TOL`` as in ``test_olmo_hybrid.py``),
and every planted fault misses it by orders of magnitude.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models import Qwen3NextLM
from deeplearning4j_tpu.nn.conf.layers import (GatedDeltaNetLayer,
                                               MixtureOfExpertsLayer,
                                               SelfAttentionLayer)
from deeplearning4j_tpu.nn.conf.layers.normalization import RMSNormalization
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.parallel.generation import GenerationServer

TOL = 1e-5


def _benchmark_module(*path):
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", *path)
    spec = importlib.util.spec_from_file_location(
        "qwen3_next_" + path.rsplit("/", 1)[-1][:-3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _benchmark_module("references", "qwen3_next_80b_a3b_instruct.py")
_faults = _benchmark_module("tests", "qwen3_next_faults.py")
plant, FAULTS = _faults.plant, _faults.FAULTS

V = 96
# three linear blocks and a full one: one period of the published pattern
SIZES = {"vocab": V, "d_model": 32, "layers": 4,
         "layer_types": ["linear", "linear", "linear", "full"], "heads": 4,
         "kv_heads": 2, "head_dim": 32, "rope_theta": 10000.0,
         "rope_dims": 8, "linear_key_heads": 2, "linear_heads": 4,
         "linear_key_dim": 8, "linear_value_dim": 8, "linear_conv": 4,
         "experts": 16, "experts_held": [4, 4], "top_k": 3,
         "expert_width": 8, "shared_width": 8, "rms_eps": 1e-6}
INIT = {"std": {"embed": 1.0, "q": 0.3, "k": 0.3, "v": 0.3, "o": 0.2,
                "gate": 0.3, "lin_q": 0.3, "lin_k": 0.3, "lin_v": 0.3,
                "lin_z": 0.3, "lin_a": 0.3, "lin_b": 0.4, "lin_o": 0.2,
                "router": 0.4, "expert_in": 0.3, "expert_down": 0.3,
                "shared_in": 0.3, "shared_down": 0.3, "shared_gate": 0.4,
                "head": 0.2},
        "conv_bound": 0.5, "A_range": [1.0, 16.0], "dt_range": [0.001, 0.1],
        "qk_norm_w": 0.4}
CHUNK = 8


def _model(sz, max_length=256):
    return Qwen3NextLM(
        num_labels=sz["vocab"], max_length=max_length, d_model=sz["d_model"],
        layer_types=sz["layer_types"], n_heads=sz["heads"],
        n_kv_heads=sz["kv_heads"], head_dim=sz["head_dim"],
        rope_theta=sz["rope_theta"], rope_dims=sz["rope_dims"],
        linear_heads=sz["linear_heads"],
        linear_key_heads=sz["linear_key_heads"],
        linear_key_dim=sz["linear_key_dim"],
        linear_value_dim=sz["linear_value_dim"],
        linear_conv=sz["linear_conv"], linear_chunk=CHUNK,
        n_experts=sz["experts"], experts_held=sz["experts_held"],
        top_k=sz["top_k"], expert_width=sz["expert_width"],
        shared_width=sz["shared_width"], rms_eps=sz["rms_eps"],
        dtype="float32")


def _float32(params):
    return {n: jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), p)
            for n, p in params.items()}


@pytest.fixture(scope="module")
def qwen():
    """The toy net in float32 over the reference's bfloat16-valued
    weights, ``max_cache`` 256."""
    params = REF.make_params(5, SIZES, INIT)
    conf = _model(SIZES).conf()
    for v in conf.vertices.values():
        layer = getattr(v, "layer", None)
        if layer is not None and hasattr(layer, "max_cache"):
            layer.max_cache = 256
    net = ComputationGraph(conf)
    net.init(params={n: _float32(params).get(n, {})
                     for n in conf.topo_order})
    net.updater_state = None
    return net, params, SIZES


# ---------------------------------------------- the grouped-key delta rule
def _delta_layer(**kw):
    layer = GatedDeltaNetLayer(**{**dict(
        n_in=32, n_out=32, n_heads=4, n_key_heads=2, key_dim=8, value_dim=8,
        chunk_size=CHUNK), **kw})
    layer.validate()
    return layer


def _delta_params():
    sz = dict(SIZES, layer_types=["linear"], layers=1)
    return {k: a.astype(jnp.float32)
            for k, a in REF.make_params(7, sz, INIT)["delta0"].items()}


_ref_linear = jax.jit(lambda p, x: REF._linear(p, x, SIZES, "float32"))


def _stream(layer, p, x, cuts):
    fwd = jax.jit(lambda st, xx: layer.forward(p, st, xx))
    st = layer.init_streaming_carry(x.shape[0])
    outs = []
    for a, b in zip(cuts[:-1], cuts[1:]):
        o, st = fwd(st, x[:, a:b])
        outs.append(o)
    return jnp.concatenate(outs, 1)


@pytest.mark.parametrize("case", ["whole_sequence", "streamed_chunks",
                                  "token_by_token"])
def test_the_three_forwards_of_the_grouped_key_rule_are_the_recurrence(
        case):
    """Two query and key heads read by four value heads (value head j reads
    key head j // 2): the chunked form over 29 tokens, streamed chunks
    whose edges straddle the chunk edges, and the one-token step, each the
    reference's position-by-position scan; the state is per VALUE head
    and the convolution's tail spans the two key heads' widths."""
    layer = _delta_layer()
    carry = layer.init_streaming_carry(2)
    assert carry["recurrent_state"].shape == (2, 4, 8, 8)
    assert carry["conv_state"].shape == (2, 3, 2 * 2 * 8 + 4 * 8)
    p = _delta_params()
    assert p["W_in"].shape == (32, 2 * 16 + 2 * 32 + 2 * 4)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 29, 32), jnp.float32)
    want = np.stack([np.asarray(_ref_linear(p, r)) for r in x])
    if case == "whole_sequence":
        got, _ = jax.jit(lambda xx: layer.forward(p, {}, xx))(x)
    elif case == "streamed_chunks":
        got = _stream(layer, p, x, (0, 5, 6, 19, 29))
    else:
        got = _stream(layer, p, x, list(range(30)))
    assert np.abs(want).max() > 0.3
    np.testing.assert_allclose(got, want, atol=TOL)


def _lowered(fn, *args):
    return jax.jit(fn).lower(*args).as_text()


def test_the_options_at_their_defaults_are_todays_layers_bit_for_bit():
    """``n_key_heads == n_heads`` lowers the program that ``n_key_heads``
    0 does (Olmo's layer), ``rope_dims == head_dim`` the one that
    ``rope_dims`` 0 does (falcon's), and each new option is off by
    default: ``qk_norm_zero_centred``, ``RMSNormalization.zero_centred``,
    ``MixtureOfExpertsLayer.shared_gate``."""
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 12, 32), jnp.float32)
    base = _delta_layer(n_key_heads=0)
    same = _delta_layer(n_key_heads=4)
    p = base.init_params(jax.random.PRNGKey(0))
    assert jax.tree_util.tree_map(jnp.shape, p) == jax.tree_util.tree_map(
        jnp.shape, same.init_params(jax.random.PRNGKey(0)))
    for lay in (base, same):
        assert lay.key_heads == 4
    assert _lowered(lambda xx: base.forward(p, {}, xx)[0], x) \
        == _lowered(lambda xx: same.forward(p, {}, xx)[0], x)
    st = base.init_streaming_carry(2)
    assert _lowered(lambda s, xx: base.forward(p, s, xx), st, x[:, :1]) \
        == _lowered(lambda s, xx: same.forward(p, s, xx), st, x[:, :1])

    kw = dict(n_in=32, n_out=32, n_heads=4, n_kv_heads=2, head_dim=16,
              causal=True, helper="stock", has_bias=False, rope_theta=1e4,
              qk_norm="head")
    whole = SelfAttentionLayer(**kw)
    full = SelfAttentionLayer(**kw, rope_dims=16)
    whole.validate()
    full.validate()
    pa = whole.init_params(jax.random.PRNGKey(3))
    assert _lowered(lambda xx: whole.forward(pa, {}, xx)[0], x) \
        == _lowered(lambda xx: full.forward(pa, {}, xx)[0], x)
    assert not whole.qk_norm_zero_centred and whole.rope_dims == 0
    assert float(pa["q_norm"][0]) == 1.0
    assert not RMSNormalization().zero_centred
    assert not MixtureOfExpertsLayer().shared_gate
    assert GatedDeltaNetLayer().n_key_heads == 0


def test_the_options_refuse_what_they_cannot_mean():
    with pytest.raises(ValueError, match="n_key_heads"):
        _delta_layer(n_key_heads=3)
    with pytest.raises(ValueError, match="rope_dims"):
        SelfAttentionLayer(n_in=32, n_out=32, n_heads=2, rope_dims=8
                           ).validate()
    with pytest.raises(ValueError, match="rope_dims"):
        SelfAttentionLayer(n_in=32, n_out=32, n_heads=2, rope_theta=1e4,
                           rope_dims=32).validate()
    with pytest.raises(ValueError, match="odd"):
        SelfAttentionLayer(n_in=32, n_out=32, n_heads=2, rope_theta=1e4,
                           rope_dims=5).validate()
    with pytest.raises(ValueError, match="shared_gate"):
        MixtureOfExpertsLayer(n_in=8, n_out=8, n_experts=4, top_k=2,
                              dispatch="routed", shared_gate=True
                              ).finalize()


# ------------------------------------------------ the partial rotation
def test_a_quarter_of_each_head_turns_on_every_forward():
    """Queries and keys with the first 8 of 32 channels rotated (the two
    halves of those 8 paired) and the rest passed through, a zero-centred
    norm per head and the gate: the whole sequence, a dense cache fed in
    uneven chunks, and a page pool fed chunks then single tokens through
    the XLA read and the Pallas kernel (interpreted here) are the
    reference's causal pass; keys enter the pool rotated."""
    from deeplearning4j_tpu.nn.conf.layers.attention import SERVED_BY

    layer = SelfAttentionLayer(
        n_in=32, n_out=32, n_heads=4, n_kv_heads=2, head_dim=32,
        causal=True, helper="stock", has_bias=False, rope_theta=1e4,
        rope_dims=8, qk_norm="head", qk_norm_eps=1e-6,
        qk_norm_zero_centred=True, gated=True, max_cache=64)
    layer.validate()
    sz = dict(SIZES, layer_types=["full"], layers=1)
    p = _float32(REF.make_params(9, sz, INIT))["attn0"]
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 29, 32), jnp.float32)
    ref = jax.jit(lambda r: REF._full(p, r, SIZES, "float32"))
    want = np.stack([np.asarray(ref(r)) for r in x])
    got = {"contiguous": jax.jit(lambda xx: layer.forward(p, {}, xx))(x)[0]}
    fwd = jax.jit(lambda st, xx: layer.forward(p, st, xx))
    st = layer.init_streaming_carry(2)
    outs = []
    for a, b in ((0, 5), (5, 6), (6, 13), (13, 29)):
        o, st = fwd(st, x[:, a:b])
        outs.append(o)
    got["streaming"] = jnp.concatenate(outs, 1)
    for backend in ("xla", "pallas"):
        pfwd = jax.jit(lambda st, xx, b=backend: layer.forward(
            p, {**st, SERVED_BY: (b, None)}, xx))
        pool = layer.init_paged_carry(9, 8)
        table = jnp.asarray(np.arange(1, 9, dtype=np.int32).reshape(2, 4))
        pos = jnp.zeros((2,), jnp.int32)
        outs = []
        for a, b in ((0, 6), (6, 13), (13, 14), (14, 15), (15, 29)):
            o, ns = pfwd(dict(pool, block_table=table, cache_pos=pos),
                         x[:, a:b])
            pool = {k: ns[k] for k in pool}
            pos = ns["cache_pos"]
            outs.append(o)
        got["paged_" + backend] = jnp.concatenate(outs, 1)
    assert np.abs(want).max() > 0.1
    for how, out in got.items():
        np.testing.assert_allclose(out, want, atol=TOL, err_msg=how)
    # a key of the pool is the rotated one: its first channels moved
    kp = np.asarray(pool["kpages"]).reshape(9, 8, 2, 32)
    raw = np.asarray(layer._qkv(p, x[:1, 9:10])[1])[0, :, 0]    # at 0
    turned = np.asarray(layer._qkv(p, x[:1, 9:10], 9)[1])[0, :, 0]
    np.testing.assert_allclose(kp[2, 1], turned, atol=TOL)
    assert np.abs(turned[:, :8] - raw[:, :8]).max() > 1e-2
    np.testing.assert_array_equal(turned[:, 8:], raw[:, 8:])


# ---------------------------------------------------- the expert layer
def test_the_four_shares_add_up_to_the_uncut_layer():
    """The share test: one expert layer of 16 experts cut four ways, as
    four chips would hold it (4 experts each, router, shared expert and
    its gate whole on each). Each share's result less the gated shared
    expert's, summed, plus the gated shared expert's once, is the uncut
    reference layer's: nothing is lost or counted twice by holding a
    share."""
    sz = dict(SIZES, experts_held=[0, 16])
    whole = _float32(REF.make_params(3, sz, INIT))["moe0"]
    h = jax.random.normal(jax.random.PRNGKey(9), (11, sz["d_model"]))
    want = np.asarray(REF._moe(whole, h, sz, "float32"))
    shared = np.asarray(jax.nn.sigmoid(h @ whole["Wsg"]) * REF._ffn(
        h, whole["Ws1"], whole["Ws2"], "float32"))
    total = shared.copy()
    for first in (0, 4, 8, 12):
        layer = MixtureOfExpertsLayer(
            n_in=32, n_out=32, n_experts=16, top_k=sz["top_k"],
            expert_hidden=sz["expert_width"], activation="silu",
            dispatch="routed", experts_held=(first, 4), gated=True,
            shared_hidden=sz["shared_width"], shared_gate=True,
            has_bias=False)
        layer.finalize()
        p = {k: (v[first:first + 4] if k in ("W1", "W2") else v)
             for k, v in whole.items()}
        out, _ = jax.jit(lambda pp: layer.forward(pp, {}, h))(p)
        part = REF._moe(p, h, dict(sz, experts_held=[first, 4]), "float32")
        np.testing.assert_allclose(out, part, atol=TOL)
        total += np.asarray(out) - shared
    np.testing.assert_allclose(total, want, atol=3 * TOL)
    assert np.abs(want - shared).max() > 0.1        # the experts weigh in
    gate = np.asarray(jax.nn.sigmoid(h @ whole["Wsg"]))
    assert gate.min() < 0.4 and gate.max() > 0.6    # the gate weighs in


# ----------------------------------------------------- the model, streamed
def _streamed_error(net, params, sizes, mode="float32", streamed=True):
    """120 tokens as a whole sequence, and (``streamed``) 70 of them again
    as two chunks (the second straddling chunk edges) and then token by
    token through the streaming carry: the widest difference from the
    reference's log-probabilities."""
    ids = np.random.default_rng(4).integers(0, V, 120)
    x = np.eye(V, dtype=np.float32)[ids][None]
    want = np.asarray(jax.nn.log_softmax(REF.sequence_logits(
        params, ids, 0, 120, sizes, mode=mode, pad_to=256, rows=120),
        axis=-1))
    whole = np.log(np.asarray(net.output(x))[0])
    if not streamed:
        return float(np.abs(whole - want).max())
    net.rnn_clear_previous_state()
    got = [np.asarray(net.rnn_time_step(x[:, :21]))[0],
           np.asarray(net.rnn_time_step(x[:, 21:38]))[0]]
    got += [np.asarray(net.rnn_time_step(x[:, t:t + 1]))[0]
            for t in range(38, 70)]
    net.rnn_clear_previous_state()
    return max(float(np.abs(whole - want).max()),
               float(np.abs(np.log(np.concatenate(got)) - want[:70]).max()))


@pytest.mark.parametrize("fault", (None, "bfloat16") + FAULTS)
def test_the_forward_is_the_reference_and_each_fault_is_not(qwen, fault,
                                                            monkeypatch):
    """Sound to 1e-4: float32 rounding of log-probabilities, carried
    through four blocks and a state that sums 120 positions. The
    reference's own pass in bfloat16 reads over 1e-2, and each planted
    fault in the whole sequence's pass over 1e-3: the key heads tiled, all
    channels rotated, the shared expert ungated, the norms multiplying by
    w and not 1 + w, beta doubled, the attention's gate dropped, the
    routing weights not renormalised."""
    net, params, sizes = qwen
    if fault == "bfloat16":
        assert _streamed_error(net, params, sizes, mode="bf16",
                               streamed=False) > 1e-2
        return
    if fault is not None:
        plant(fault, monkeypatch)
    net._output_cache.clear()          # programs traced without the fault
    try:
        err = _streamed_error(net, params, sizes, streamed=fault is None)
    finally:
        net._output_cache.clear()
    if fault is None:
        assert err <= 1e-4, err
    else:
        assert err > 1e-3, (fault, err)


# --------------------------------------------------------------- the server
def _gaps(params, sizes, prompt, tokens):
    """By how much each served token's reference logit lies below the
    reference's best at its position."""
    ids = np.concatenate([prompt, tokens])
    n = len(tokens)
    want = np.asarray(REF.sequence_logits(params, ids, len(prompt) - 1, n,
                                          sizes, pad_to=256, rows=64))
    return want.max(-1) - want[np.arange(n), tokens]


@pytest.fixture(scope="module")
def served(qwen):
    """Five requests through two slots in prefill rounds of 16: prompts of
    one to four rounds, long answers beside short ones, one sampled."""
    from deeplearning4j_tpu.metrics.registry import global_registry

    net, _, _ = qwen
    rng = np.random.default_rng(0)
    sizes = [(50, 30), (7, 12), (33, 40), (61, 9), (16, 25)]
    how = [{}, {}, dict(temperature=0.8, top_k=5, seed=7), {}, {}]
    reqs = [(rng.integers(0, V, n), m, h) for (n, m), h in zip(sizes, how)]
    before = global_registry().snapshot().get(
        "generation_decode_steps_total", 0.0)
    srv = GenerationServer(net, V, slots=2, page_size=16, prefill_chunk=16,
                           steps_per_dispatch=2)
    try:
        futs = [srv.submit(r[0], r[1], **r[2]) for r in reqs]
        outs = [np.asarray(f.result(timeout=300)) for f in futs]
        stats, snap = srv.stats(), srv.metrics.snapshot()
    finally:
        srv.close()
    after = global_registry().snapshot()
    return reqs, srv, outs, stats, snap, after, before


@pytest.mark.generation
def test_served_through_slots_is_the_references_full_forward(qwen, served):
    """Prefill in rounds of 16 (the linear state and the convolution's tail
    carried from round to round), then decode with the state per slot and
    the full layer's rotated keys in pages, each slot reused after a
    retirement. Every greedy token is the reference's best at its
    position to rounding."""
    net, params, sizes = qwen
    reqs, srv, outs, stats, _, _, _ = served
    assert srv._slot_names == ["delta0", "delta1", "delta2"]
    assert srv._paged_names == ["attn3"]
    assert not srv.prefix_cache
    assert all(t.shape == (r[1],) for r, t in zip(reqs, outs))
    worst = max(float(_gaps(params, sizes, r[0], t).max())
                for r, t in zip(reqs, outs) if not r[2])
    assert worst <= TOL
    assert stats["pages"]["preempted"] == 0


@pytest.mark.generation
def test_the_server_publishes_one_held_experts_bytes(served):
    """The gauge of one held routed expert's weights as the net stores
    them (float32 here: 3 x 32 x 8 x 4 bytes), and decode dispatches and
    expert calls on the process-wide registry, what
    ``moe_expert_stream_pct`` divides."""
    _, _, _, stats, snap, after, before = served
    assert snap["generation_moe_expert_bytes"] == 3 * 32 * 8 * 4
    assert after["generation_moe_expert_bytes"] == 3 * 32 * 8 * 4
    assert after["generation_decode_steps_total"] - before \
        == stats["decode_steps"] > 0
    calls = after["generation_moe_expert_calls_total"]["program=decode"]
    assert calls > 0


def test_zoo_lists_the_model():
    from deeplearning4j_tpu.models import zoo_models

    assert zoo_models()["qwen3nextlm"] is Qwen3NextLM
    with pytest.raises(ValueError, match="layer_types"):
        Qwen3NextLM(layer_types=("linear", "sliding"))
