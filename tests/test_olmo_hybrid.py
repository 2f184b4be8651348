"""The hybrid of gated delta-rule and full attention (``OlmoHybridLM``) and
what it forced: ``GatedDeltaNetLayer`` (a matrix state a head, erased
along each key and then written under a decay, in a chunked form for whole
sequences and streamed chunks and a one-token step for decode),
``SelfAttentionLayer``'s norm over the whole query and key projection, and
a ``GenerationServer`` that hosts the new state per slot beside the paged
keys of the full layers: each held against a plain statement of the same
mathematics (``benchmarks/references/olmo_hybrid_7b.py``: one causal pass,
the recurrence position by position, no cache, no chunks).

Everything is float32 at toy widths (4 heads of keys 8 and values 16,
chunks of 8, pages of 16 or 8), so agreement is to rounding: the chunked
form's triangular solve and products at ``highest`` precision against the
reference's scan are a few float32 ulps of outputs of order one (measured
under 1e-6; ``TOL`` 1e-5 leaves room for sums over tens of terms), and
every planted fault, a state or pass in bfloat16, misses it by orders of
magnitude.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models import OlmoHybridLM
from deeplearning4j_tpu.nn.conf.layers import (GatedDeltaNetLayer,
                                               SelfAttentionLayer)
from deeplearning4j_tpu.nn.conf.layers.attention import SERVED_BY
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.parallel.generation import GenerationServer

TOL = 1e-5


def _benchmark_module(*path):
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", *path)
    spec = importlib.util.spec_from_file_location(
        "olmo_" + path.rsplit("/", 1)[-1][:-3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _benchmark_module("references", "olmo_hybrid_7b.py")
_faults = _benchmark_module("tests", "olmo_hybrid_faults.py")
plant, FAULTS = _faults.plant, _faults.FAULTS

V = 96
# three linear blocks and a full one: one period of the published pattern
SIZES = {"vocab": V, "d_model": 32, "layers": 4,
         "layer_types": ["linear", "linear", "linear", "full"], "heads": 4,
         "head_dim": 8, "linear_heads": 4, "linear_key_dim": 8,
         "linear_value_dim": 16, "linear_conv": 4, "allow_neg_eigval": True,
         "mlp_width": 48, "rms_eps": 1e-6}
INIT = {"std": {"embed": 1.0, "q": 0.3, "k": 0.3, "v": 0.3, "o": 0.2,
                "lin_q": 0.3, "lin_k": 0.3, "lin_v": 0.3, "lin_z": 0.3,
                "lin_a": 0.3, "lin_b": 0.4, "lin_o": 0.2, "mlp_in": 0.2,
                "mlp_down": 0.2, "head": 0.2},
        "conv_bound": 0.5, "A_range": [1.0, 16.0], "dt_range": [0.001, 0.1],
        "qk_norm_gain": 1.4}
CHUNK = 8


def _model(sz, max_length=256):
    return OlmoHybridLM(
        num_labels=sz["vocab"], max_length=max_length, d_model=sz["d_model"],
        layer_types=sz["layer_types"], n_heads=sz["heads"],
        head_dim=sz["head_dim"], linear_heads=sz["linear_heads"],
        linear_key_dim=sz["linear_key_dim"],
        linear_value_dim=sz["linear_value_dim"],
        linear_conv=sz["linear_conv"], linear_chunk=CHUNK,
        allow_neg_eigval=sz["allow_neg_eigval"], mlp_width=sz["mlp_width"],
        rms_eps=sz["rms_eps"], dtype="float32")


@pytest.fixture(scope="module")
def olmo():
    """The toy net in float32 over the reference's bfloat16-valued
    weights, ``max_cache`` 256."""
    params = REF.make_params(5, SIZES, INIT)
    conf = _model(SIZES).conf()
    for v in conf.vertices.values():
        layer = getattr(v, "layer", None)
        if layer is not None and hasattr(layer, "max_cache"):
            layer.max_cache = 256
    net = ComputationGraph(conf)
    net.init(params={n: jax.tree_util.tree_map(
        lambda a: a.astype(jnp.float32), params.get(n, {}))
        for n in conf.topo_order})
    net.updater_state = None
    return net, params, SIZES


# ------------------------------------------------------- the linear layer
def _delta_layer():
    layer = GatedDeltaNetLayer(n_in=32, n_out=32, n_heads=4, key_dim=8,
                               value_dim=16, chunk_size=CHUNK,
                               allow_neg_eigval=True)
    layer.validate()
    return layer


def _delta_params(extreme=False):
    """The reference's weights for one linear layer, float32. ``extreme``:
    decays from about 1e-9 to 0.9999 by head and token, and ``b`` wide
    enough that beta lies over 1 for about half of the tokens and near 2
    for some."""
    sz = dict(SIZES, layer_types=["linear"], layers=1)
    init = INIT
    if extreme:
        init = dict(INIT, std=dict(INIT["std"], lin_a=1.0, lin_b=1.5),
                    dt_range=[1e-4, 1.0])
    p = REF.make_params(11 if extreme else 7, sz, init)["delta0"]
    return {k: a.astype(jnp.float32) for k, a in p.items()}


#: the reference's linear mixer over one row, compiled once a length
_ref_linear = jax.jit(lambda p, x: REF._linear(p, x, SIZES, "float32"))


def _reference_rows(p, x):
    return np.stack([np.asarray(_ref_linear(p, r)) for r in x])


def _stream(layer, p, x, cuts, mask=None):
    fwd = jax.jit(lambda st, xx, m: layer.forward(p, st, xx, mask=m))
    st = layer.init_streaming_carry(x.shape[0])
    outs = []
    for a, b in zip(cuts[:-1], cuts[1:]):
        o, st = fwd(st, x[:, a:b], None if mask is None else mask[:, a:b])
        outs.append(o)
    return jnp.concatenate(outs, 1), st


@pytest.mark.parametrize("case", ["whole_sequence", "streamed_chunks",
                                  "token_by_token", "extreme_decay_and_beta"])
def test_the_chunked_form_and_the_step_are_the_recurrence(case):
    """29 tokens, chunks of 8: the whole sequence in the chunked form
    (four chunks, the last cut short), streamed chunks whose edges straddle
    the chunk edges (5, 1, 13 and 10 tokens) carried through the state and
    the convolution's tail, and the one-token step alone, all the
    reference's position-by-position scan. The same with decays from 1e-9
    to 0.9999 and beta over 1 for about half of the tokens: the
    transition's eigenvalue 1 - beta reaches -1."""
    layer = _delta_layer()
    extreme = case == "extreme_decay_and_beta"
    p = _delta_params(extreme)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 29, 32), jnp.float32)
    want = _reference_rows(p, x)
    if case == "whole_sequence" or extreme:
        got, _ = jax.jit(lambda xx: layer.forward(p, {}, xx))(x)
        if extreme:
            proj = np.asarray(x @ p["W_in"])
            beta = 2 / (1 + np.exp(-proj[..., -4:]))
            log_alpha = -np.exp(np.asarray(p["A_log"])) * np.logaddexp(
                0, proj[..., -8:-4] + np.asarray(p["dt_bias"]))
            assert 0.3 < (beta > 1).mean() < 0.7 and beta.max() > 1.9
            assert np.exp(log_alpha).min() < 1e-6
            assert np.exp(log_alpha).max() > 0.999
            # streamed too, token by token over the same extremes
            step, _ = _stream(layer, p, x, list(range(30)))
            np.testing.assert_allclose(step, want, atol=TOL)
    elif case == "streamed_chunks":
        got, _ = _stream(layer, p, x, (0, 5, 6, 19, 29))
    else:
        got, _ = _stream(layer, p, x, list(range(30)))
    assert np.abs(want).max() > 0.5
    np.testing.assert_allclose(got, want, atol=TOL)


def test_padded_rows_end_at_their_last_token_and_an_empty_row_keeps_its_state():
    """A chunk of 13 right-padded to three true lengths, one of them none:
    a padded row's outputs are zeros past its length and its state and tail
    are what its true tokens alone leave (the next chunk continues from
    them to the reference); the row with no true token keeps the state and
    tail it came with, bit for bit."""
    layer = _delta_layer()
    p = _delta_params()
    x = jax.random.normal(jax.random.PRNGKey(2), (3, 30, 32), jnp.float32)
    fwd = jax.jit(lambda st, xx, m: layer.forward(p, st, xx, mask=m))
    st = layer.init_streaming_carry(3)
    _, st = fwd(st, x[:, :6], None)                  # some state to keep
    kept = jax.device_get(st)
    lens = np.array([13, 4, 0])
    mask = (np.arange(13)[None, :] < lens[:, None]).astype(np.float32)
    out, st = fwd(st, x[:, 6:19], mask)
    for r, n in enumerate(lens):
        assert not np.asarray(out[r, n:]).any()
    for k in st:
        np.testing.assert_array_equal(np.asarray(st[k][2]), kept[k][2])
    # rows 0 and 1 continue from their own last true token
    nxt = jnp.stack([x[0, 19:26], x[1, 10:17], x[2, 6:13]])
    out2, _ = fwd(st, nxt, None)
    for r, seq in ((0, x[0, :26]), (1, x[1, :17]), (2, jnp.concatenate(
            [x[2, :6], x[2, 6:13]]))):
        want = np.asarray(_ref_linear(p, seq))
        np.testing.assert_allclose(out2[r], want[-7:], atol=TOL)
    np.testing.assert_allclose(out[0, :13], _reference_rows(
        p, x[:1, :19])[0, 6:], atol=TOL)


def test_the_state_is_float32_and_a_bfloat16_state_is_not_the_recurrence():
    """The carry's state is float32 in a bfloat16 network, and holding it
    in bfloat16 between tokens moves the outputs a hundred times past the
    tolerance: the tolerance sees the precision the layer states."""
    layer = _delta_layer()
    carry = layer.init_streaming_carry(2, jnp.bfloat16)
    assert carry["recurrent_state"].dtype == jnp.float32
    assert carry["conv_state"].dtype == jnp.bfloat16
    p = _delta_params()
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 40, 32), jnp.float32)
    want = _reference_rows(p, x)
    fwd = jax.jit(lambda st, xx: layer.forward(p, st, xx))
    st = layer.init_streaming_carry(2)
    outs = []
    for t in range(40):
        o, st = fwd(st, x[:, t:t + 1])
        st = dict(st, recurrent_state=st["recurrent_state"].astype(
            jnp.bfloat16).astype(jnp.float32))
        outs.append(o)
    err = np.abs(np.asarray(jnp.concatenate(outs, 1)) - want).max()
    assert err > 100 * TOL, err


# --------------------------------------------- the whole-projection norm
A_SZ = {"heads": 4, "head_dim": 8, "rms_eps": 1e-6}


@pytest.fixture(scope="module")
def full_case():
    layer = SelfAttentionLayer(n_in=32, n_out=32, n_heads=4, head_dim=8,
                               causal=True, helper="stock", has_bias=False,
                               qk_norm="projection", qk_norm_eps=1e-6,
                               max_cache=64)
    layer.validate()
    assert layer.plain
    p = layer.init_params(jax.random.PRNGKey(0))
    assert p["q_norm"].shape == (32,) and p["k_norm"].shape == (32,)
    p["q_norm"] = 1.0 + 0.3 * jax.random.normal(jax.random.PRNGKey(2), (32,))
    p["k_norm"] = 1.0 + 0.3 * jax.random.normal(jax.random.PRNGKey(3), (32,))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 29, 32), jnp.float32)
    # rows whose heads differ in size: a per-head norm is not this one
    x = x * (1.0 + jnp.arange(32) / 8.0)
    full = jax.jit(lambda p, r: REF._full(p, r, A_SZ, "float32"))
    want = np.stack([np.asarray(full(p, r)) for r in x])
    return layer, p, x, want


@pytest.mark.parametrize("how", ["contiguous", "streaming", "paged_xla",
                                 "paged_pallas", "per_head_is_not_it"])
def test_the_whole_projection_norm_on_every_forward(full_case, how):
    """Queries and keys normed over all 32 channels of their projection,
    a weight that spans them, no positions: the whole sequence, a dense
    cache fed in uneven chunks, and a page pool of pages of 8 fed chunks and
    then single tokens through the XLA read and through the Pallas kernel
    (interpreted here) are the reference's causal pass. A norm taken per
    head with the same weight is not."""
    layer, p, x, want = full_case
    if how == "per_head_is_not_it":
        h = x @ p["Wq"]
        per_head = h.reshape(2, 29, 4, 8)
        per_head = per_head * jax.lax.rsqrt(
            jnp.mean(per_head ** 2, -1, keepdims=True) + 1e-6)
        whole = layer._proj_norm(h, jnp.ones(32))
        assert np.abs(np.asarray(per_head.reshape(2, 29, 32) - whole)).max() \
            > 0.1
        return
    if how == "contiguous":
        got, _ = jax.jit(lambda xx: layer.forward(p, {}, xx))(x)
    elif how == "streaming":
        fwd = jax.jit(lambda st, xx: layer.forward(p, st, xx))
        st = layer.init_streaming_carry(2)
        outs = []
        for a, b in ((0, 5), (5, 6), (6, 13), (13, 29)):
            o, st = fwd(st, x[:, a:b])
            outs.append(o)
        got = jnp.concatenate(outs, 1)
    else:
        backend = how.split("_")[1]
        fwd = jax.jit(lambda st, xx: layer.forward(
            p, {**st, SERVED_BY: (backend, None)}, xx))
        pool = layer.init_paged_carry(9, 8)
        table = jnp.asarray(np.arange(1, 9, dtype=np.int32).reshape(2, 4))
        pos = jnp.zeros((2,), jnp.int32)
        outs = []
        for a, b in ((0, 6), (6, 13), (13, 14), (14, 15), (15, 29)):
            o, ns = fwd(dict(pool, block_table=table, cache_pos=pos),
                        x[:, a:b])
            pool = {k: ns[k] for k in pool}
            pos = ns["cache_pos"]
            outs.append(o)
        got = jnp.concatenate(outs, 1)
    np.testing.assert_allclose(got, want, atol=TOL)


def test_the_whole_projection_norm_refuses_a_head_parallel_shard():
    layer = SelfAttentionLayer(n_in=32, n_out=32, n_heads=4, causal=True,
                               qk_norm="projection")
    with pytest.raises(NotImplementedError, match="projection"):
        layer._served({SERVED_BY: ("xla", object())})
    # a per-head norm is taken inside each head, so a shard takes it alone
    per_head = SelfAttentionLayer(n_in=32, n_out=32, n_heads=4, causal=True,
                                  qk_norm="head")
    assert per_head._served({SERVED_BY: ("xla", "mesh")})[2] == "mesh"


@pytest.mark.parametrize("given,read", [
    (None, None), (False, None), (True, "head"), ("head", "head"),
    ("projection", "projection")])
def test_qk_norm_reads_one_of_three_settings_and_older_booleans(given, read):
    """``qk_norm`` is one setting of three; a configuration written while
    it was a boolean (``true``: per head) loads as the per-head norm, and
    each setting survives the JSON round trip with its weights' shapes."""
    from deeplearning4j_tpu.utils.serde import from_json, to_json

    layer = SelfAttentionLayer(n_in=32, n_out=32, n_heads=4, head_dim=8,
                               has_bias=False, qk_norm=given)
    layer.validate()
    assert layer.qk_norm == read
    old = to_json(layer).replace('"qk_norm": "head"', '"qk_norm": true')
    assert ('"qk_norm": true' in old) == (read == "head")
    again = from_json(old)
    assert again.qk_norm == read
    shapes = {k: v.shape for k, v in
              again.init_params(jax.random.PRNGKey(0)).items()
              if k.endswith("_norm")}
    assert shapes == {None: {}, "head": {"q_norm": (8,), "k_norm": (8,)},
                      "projection": {"q_norm": (32,),
                                     "k_norm": (32,)}}[read]
    with pytest.raises(ValueError, match="qk_norm"):
        SelfAttentionLayer(n_in=32, n_out=32, n_heads=4,
                           qk_norm="channel").validate()


# ----------------------------------------------------- the model, streamed
def _gaps(params, sizes, prompt, tokens):
    """By how much each served token's reference logit lies below the
    reference's best at its position."""
    ids = np.concatenate([prompt, tokens])
    n = len(tokens)
    want = np.asarray(REF.sequence_logits(params, ids, len(prompt) - 1, n,
                                          sizes, pad_to=256, rows=64))
    return want.max(-1) - want[np.arange(n), tokens]


def _streamed_error(net, params, sizes, mode="float32"):
    """120 tokens as a whole sequence, and 70 of them again as two chunks
    (the second straddling chunk edges) and then token by token through the
    streaming carry: the widest difference from the reference's
    log-probabilities."""
    ids = np.random.default_rng(4).integers(0, V, 120)
    x = np.eye(V, dtype=np.float32)[ids][None]
    want = np.asarray(jax.nn.log_softmax(REF.sequence_logits(
        params, ids, 0, 120, sizes, mode=mode, pad_to=256, rows=120),
        axis=-1))
    whole = np.log(np.asarray(net.output(x))[0])
    net.rnn_clear_previous_state()
    got = [np.asarray(net.rnn_time_step(x[:, :21]))[0],
           np.asarray(net.rnn_time_step(x[:, 21:38]))[0]]
    got += [np.asarray(net.rnn_time_step(x[:, t:t + 1]))[0]
            for t in range(38, 70)]
    net.rnn_clear_previous_state()
    return max(float(np.abs(whole - want).max()),
               float(np.abs(np.log(np.concatenate(got)) - want[:70]).max()))


LAYER_FAULTS = ("beta_not_doubled", "gate_before_norm",
                "decay_after_delta_correction", "full_norm_per_head")


@pytest.mark.parametrize("fault", (None, "bfloat16") + LAYER_FAULTS)
def test_streamed_is_the_reference_and_each_fault_is_not(olmo, fault,
                                                         monkeypatch):
    """Sound to 1e-4: float32 rounding of log-probabilities near -5 (their
    ulp is 5e-7), carried through four blocks and a state that sums 120
    positions (3.3e-5 measured). The reference's own pass in bfloat16
    reads over 1e-2, and each planted
    fault in a layer over 1e-3: beta not doubled, the gate before the norm,
    the decay after the delta correction, the full layers' norm per
    head."""
    net, params, sizes = olmo
    if fault == "bfloat16":
        assert _streamed_error(net, params, sizes, mode="bf16") > 1e-2
        return
    if fault is not None:
        plant(fault, monkeypatch)
    net._output_cache.clear()          # programs traced without the fault
    try:
        err = _streamed_error(net, params, sizes)
    finally:
        net._output_cache.clear()
    if fault is None:
        assert err <= 1e-4, err
    else:
        assert err > 1e-3, (fault, err)


# --------------------------------------------------------------- the server
def _serve(net, reqs, **kw):
    srv = GenerationServer(net, V, **{**dict(slots=2, page_size=16,
                                             prefill_chunk=16,
                                             steps_per_dispatch=2), **kw})
    try:
        futs = [srv.submit(r[0], r[1], **(r[2] if len(r) > 2 else {}))
                for r in reqs]
        outs = [np.asarray(f.result(timeout=300)) for f in futs]
        return srv, outs, srv.stats(), srv.metrics.snapshot()
    finally:
        srv.close()


def _requests(rng):
    """Prompts of one to four prefill rounds of 16, long answers beside
    short ones, more requests than slots (each slot taken again after a
    retirement); one sampled."""
    sizes = [(50, 30), (7, 12), (33, 40), (61, 9), (16, 25)]
    how = [{}, {}, dict(temperature=0.8, top_k=5, seed=7), {}, {}]
    return [(rng.integers(0, V, n), m, h) for (n, m), h in zip(sizes, how)]


@pytest.fixture(scope="module")
def served(olmo):
    net, params, sizes = olmo
    reqs = _requests(np.random.default_rng(0))
    return reqs, _serve(net, reqs)


@pytest.mark.generation
def test_served_through_slots_is_the_references_full_forward(olmo, served):
    """Five requests through two slots: chunked prefill in rounds of 16
    (prompts of one to four rounds, the state and the convolution's tail
    carried from round to round), then decode with the state per slot and
    the full layer's keys in pages, contexts up to 80, each slot reused
    after a retirement. Every greedy token is the reference's best at its
    position to rounding."""
    net, params, sizes = olmo
    reqs, (srv, outs, stats, snap) = served
    assert srv._slot_names == ["delta0", "delta1", "delta2"]
    assert srv._paged_names == ["attn3"]
    assert srv._pa == "xla" and not srv.prefix_cache
    assert all(t.shape == (r[1],) for r, t in zip(reqs, outs))
    worst = max(float(_gaps(params, sizes, r[0], t).max())
                for r, t in zip(reqs, outs) if not r[2])
    assert worst <= TOL
    assert stats["pages"]["preempted"] == 0
    assert snap["generation_slot_state_resets_total"] == len(reqs)


@pytest.mark.generation
def test_a_preempted_request_resumes_bit_equal(olmo):
    """A pool too small for three long answers at once (two fit): the
    newest slot is preempted, takes no snapshot, and comes back through
    prefill (its state rebuilt from zeros) with the tokens the unpressed
    server gives."""
    net, params, sizes = olmo
    rng = np.random.default_rng(3)
    reqs = [(rng.integers(0, V, 20), 60, dict(temperature=0.7 * (i % 2),
                                               top_k=5, seed=i))
            for i in range(3)]
    _, roomy, st0, _ = _serve(net, reqs, slots=3, page_size=8)
    # 20 usable pages of 8: two requests' 80 tokens
    _, tight, st1, _ = _serve(net, reqs, slots=3, page_size=8, pages=21)
    assert st0["pages"]["preempted"] == 0 and st1["pages"]["preempted"] > 0
    assert st1["handoff"]["preempt_resumes"] == 0
    for a, b in zip(roomy, tight):
        assert np.array_equal(a, b)
    assert float(_gaps(params, sizes, reqs[0][0], roomy[0]).max()) <= TOL


SERVER_FAULTS = ("slot_state_not_reset", "conv_tail_zeroed_after_prefill")


@pytest.mark.generation
@pytest.mark.parametrize("fault", SERVER_FAULTS)
def test_each_fault_of_the_served_path_is_not_the_reference(olmo, fault,
                                                            monkeypatch):
    """The served-token comparison with the loop's part broken underneath:
    one slot, long prompts followed by short ones, so that a state left by
    the last occupant weighs on the next, and every decode starts from what
    its prefill left of the convolution's tail."""
    net, params, sizes = olmo
    plant(fault, monkeypatch)
    net._output_cache.clear()          # programs traced without the fault
    rng = np.random.default_rng(2)
    reqs = [(rng.integers(0, V, n), 10) for n in (40, 3, 30, 2)]
    try:
        _, outs, _, _ = _serve(net, reqs, slots=1)
    finally:
        net._output_cache.clear()
    worst = max(float(_gaps(params, sizes, r[0], t).max())
                for r, t in zip(reqs, outs))
    assert worst > 100 * TOL, worst


@pytest.mark.generation
@pytest.mark.parametrize("what", ["prefix_cache", "export_request",
                                  "snapshot_every", "draft_net", "tp"])
def test_what_a_slot_state_net_cannot_carry_is_refused_typed(olmo, what):
    from deeplearning4j_tpu.parallel.handoff import SnapshotUnsupported
    from deeplearning4j_tpu.parallel.mesh import MeshGeometryError

    net, _, _ = olmo
    kw = dict(slots=2, page_size=16, prefill_chunk=16)
    refused = {"snapshot_every": (ValueError, dict(snapshot_every=4)),
               "draft_net": (ValueError, dict(draft_net=net)),
               "tp": (MeshGeometryError, dict(tp=2))}
    if what in refused:
        exc, extra = refused[what]
        with pytest.raises(exc, match="per-slot state"):
            GenerationServer(net, V, **kw, **extra)
        return
    srv = GenerationServer(net, V, prefix_cache=True, **kw)
    try:
        assert srv.prefix_cache is False
        fut = srv.submit(np.arange(1, 6), 3)
        if what == "export_request":
            with pytest.raises(SnapshotUnsupported, match="per-slot state"):
                srv.export_request(fut)
        fut.result(timeout=120)
    finally:
        srv.close()


def test_zoo_lists_the_model():
    from deeplearning4j_tpu.models import zoo_models

    assert zoo_models()["olmohybridlm"] is OlmoHybridLM
    with pytest.raises(ValueError, match="layer_types"):
        OlmoHybridLM(layer_types=("linear", "sliding"))
