"""The window-and-full language model (``TrinityLM``) and what it forced:
``SelfAttentionLayer``'s sliding window, per-head RMS norm on queries and
keys and sigmoid gate; ``MixtureOfExpertsLayer``'s sigmoid router with a
selection bias; and a ``GenerationServer`` that keeps the window layers'
pages in a class of their own and frees those behind the window: each held
against a plain statement of the same mathematics
(``benchmarks/references/trinity_large_preview.py``: one full pass, a
``[T, T]`` mask per layer type, no cache).

Everything is float32 at toy widths (window 32 or 8, pages of 16 or 4), so
agreement is to rounding: the tolerances below are a few float32 ulps of
values of order one, summed over tens of terms (1e-5; 2e-5 on
log-probabilities near -8), and every planted fault, and the same pass in
bfloat16, misses them by orders of magnitude.
"""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models import TrinityLM
from deeplearning4j_tpu.nn.conf.layers import (MixtureOfExpertsLayer,
                                               SelfAttentionLayer)
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.parallel.generation import GenerationServer

TOL = 1e-5


def _benchmark_module(*path):
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", *path)
    spec = importlib.util.spec_from_file_location(
        "trinity_" + path.rsplit("/", 1)[-1][:-3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _benchmark_module("references", "trinity_large_preview.py")
_faults = _benchmark_module("tests", "trinity_faults.py")
plant, FAULTS = _faults.plant, _faults.FAULTS

V = 96
# a dense sliding block, a sliding expert block, a full expert block; two
# query heads a key/value head; 16 experts, 2 chosen, the first four held
SIZES = {"vocab": V, "d_model": 32, "layers": 3, "dense_layers": 1,
         "layer_types": ["sliding", "sliding", "full"], "heads": 4,
         "kv_heads": 2, "head_dim": 8, "window": 32, "rope_theta": 10000.0,
         "rms_eps": 1e-5, "mlp_width": 48, "experts": 16,
         "experts_held": [0, 4], "top_k": 2, "routed_scale": 2.448,
         "expert_width": 16, "shared_width": 16,
         "embedding_multiplier": 32 ** 0.5}
INIT = {"std": {"embed": 1.0, "q": 0.3, "k": 0.3, "v": 0.3, "o": 0.2,
                "gate": 0.3, "mlp_in": 0.2, "mlp_down": 0.2, "router": 0.3,
                "expert_in": 0.2, "expert_down": 0.2, "shared_in": 0.2,
                "shared_down": 0.2, "select_bias": 0.1, "head": 0.2}}


def _model(sz, max_length=256):
    return TrinityLM(
        num_labels=sz["vocab"], max_length=max_length,
        d_model=sz["d_model"], layer_types=sz["layer_types"],
        dense_layers=sz["dense_layers"], n_heads=sz["heads"],
        n_kv_heads=sz["kv_heads"], head_dim=sz["head_dim"],
        window=sz["window"], rope_theta=sz["rope_theta"],
        embedding_multiplier=sz["embedding_multiplier"],
        mlp_width=sz["mlp_width"], n_experts=sz["experts"],
        experts_held=sz["experts_held"], top_k=sz["top_k"],
        routed_scale=sz["routed_scale"], expert_width=sz["expert_width"],
        shared_width=sz["shared_width"], rms_eps=sz["rms_eps"],
        dtype="float32")


@pytest.fixture(scope="module")
def trinity():
    """The toy net in float32 over the reference's bfloat16-valued
    weights, ``max_cache`` 256: contexts past four windows of 32."""
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


# ------------------------------------------------------------- the layer
W, PS = 8, 4            # a window of two pages
LSZ = {"heads": 4, "kv_heads": 2, "head_dim": 8, "window": W,
       "rope_theta": 10000.0, "rms_eps": 1e-5}


def _layer(sliding=True):
    layer = SelfAttentionLayer(
        n_in=16, n_out=16, n_heads=4, n_kv_heads=2, head_dim=8, causal=True,
        helper="stock", has_bias=False, qk_norm=True, gated=True,
        max_cache=32, **(dict(window=W, rope_theta=10000.0) if sliding
                         else {}))
    layer.validate()
    return layer


@pytest.fixture(scope="module", params=["sliding", "full"])
def attention_case(request):
    sliding = request.param == "sliding"
    layer = _layer(sliding)
    p = layer.init_params(jax.random.PRNGKey(0))
    assert p["Wq"].shape == (16, 32) and p["Wk"].shape == (16, 16)
    assert p["Wg"].shape == (16, 32) and p["q_norm"].shape == (8,)
    # norm weights that are not 1, so that leaving them out would show
    p["q_norm"] = 1.0 + 0.3 * jax.random.normal(jax.random.PRNGKey(2), (8,))
    p["k_norm"] = 1.0 + 0.3 * jax.random.normal(jax.random.PRNGKey(3), (8,))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 29, 16), jnp.float32)
    want = np.stack([np.asarray(REF._attention(p, r, LSZ, "float32",
                                               sliding)) for r in x])
    return layer, p, x, want


def _paged(layer, p, x, cuts, lens=None, free_behind=False):
    """Rows through a page pool in the chunks ``cuts`` names, as
    ``tests/test_deepseek_v2.py`` does for the latent layer. With
    ``free_behind`` the table entries behind each row's first live page
    point at a page of 1e4 before every call, as if freed and reused."""
    fwd = jax.jit(lambda st, xx, mk: layer.forward(p, st, xx, mask=mk))
    pool = layer.init_paged_carry(18, PS)
    assert set(pool) == {"kpages", "vpages"}
    pool = {k: a.at[17].set(1e4) for k, a in pool.items()}
    table = np.arange(1, 17, dtype=np.int32).reshape(2, 8)
    pos = jnp.zeros((2,), jnp.int32)
    outs = []
    for a, b in zip(cuts[:-1], cuts[1:]):
        mask = None
        chunk = x[:, a:b]
        if lens is not None and a == 0:
            mask = (jnp.arange(b)[None, :] < lens[:, None]).astype(
                jnp.float32)
        elif lens is not None:
            chunk = jnp.take_along_axis(x, pos[:, None, None], axis=1)
        bt = table.copy()
        if free_behind and layer.window:
            for r, q in enumerate(np.asarray(pos)):
                bt[r, :max(q - layer.window + 1, 0) // PS] = 17
        o, ns = fwd(dict(pool, block_table=jnp.asarray(bt), cache_pos=pos),
                    chunk, mask)
        pool = {k: ns[k] for k in pool}
        pos = ns["cache_pos"] if mask is None else lens.astype(jnp.int32)
        outs.append(o)
    return outs, pool, table, pos


@pytest.mark.parametrize("how", ["contiguous", "streaming", "paged",
                                 "paged_right_padded", "dense_view"])
def test_every_forward_of_the_layer_is_the_reference(attention_case, how):
    """Grouped heads with a norm on queries and keys, a window of 8 and a
    sigmoid gate: the whole sequence, a dense cache fed in uneven chunks
    (one of 7 tokens straddles the window's edge: its first query still
    sees position 0, its last no longer does), a page pool fed chunks and
    then token by token with the pages behind the window pointing at a
    page of 1e4, a right-padded masked chunk whose rows continue from
    their own true lengths, and the decode family's dense view of a window
    class (three pages from the row's first live page) are all the
    reference's ``[T, T]`` mask; and the same five for a full layer that
    rotates nothing."""
    layer, p, x, want = attention_case
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
    elif how == "paged":
        outs, *_ = _paged(layer, p, x, (0, 6, 13, 14, 15, 22, 23, 29),
                          free_behind=True)
        got = jnp.concatenate(outs, 1)
    elif how == "paged_right_padded":
        lens = jnp.asarray([5, 11])
        outs, *_ = _paged(layer, p, x, (0, 12, 13, 14, 15), lens=lens)
        for r, n in enumerate((5, 11)):
            np.testing.assert_allclose(outs[0][r, :n], want[r, :n],
                                       atol=TOL)
            for j, o in enumerate(outs[1:]):
                np.testing.assert_allclose(o[r, 0], want[r, n + j],
                                           atol=TOL)
            assert not np.asarray(outs[0][r, n:]).any()   # masked: zeros
        return
    else:
        # 21 tokens through the pool, then two steps over dense views as
        # gen_decode gathers them: the full layer's whole table, the window
        # layer's window_pages(2, 4) = 3 pages from its first live page
        _, pool, table, pos = _paged(layer, p, x, (0, 9, 21))
        bt = jnp.asarray(table)
        if layer.window:
            first = layer.first_live_page(pos, PS)
            assert layer.window_pages(2, PS) == 3 and int(first[0]) == 3
            views = layer.paged_views(pool, bt, first, 3)
            assert views["kcache"].shape == (2, 2, 12, 8)
            st = dict(views, view_base=first * PS, cache_pos=pos)
        else:
            st = dict(layer.paged_views(pool, bt), cache_pos=pos)
        fwd = jax.jit(lambda st, xx: layer.forward(p, st, xx))
        outs = []
        for t in (21, 22):
            o, st = fwd(st, x[:, t:t + 1])
            outs.append(o)
        np.testing.assert_allclose(jnp.concatenate(outs, 1), want[:, 21:23],
                                   atol=TOL)
        return
    np.testing.assert_allclose(got, want, atol=TOL)


def test_the_layers_defaults_are_the_old_layer():
    """Off at their defaults: no new parameter, no window, and the
    attention core the layer had."""
    layer = SelfAttentionLayer(n_in=16, n_out=16, n_heads=4, causal=True,
                               bias_init=0.0)
    assert layer.param_order() == ["Wq", "Wk", "Wv", "Wo", "b"]
    assert layer.plain and layer.PAGED_WINDOW is None
    assert set(layer.init_params(jax.random.PRNGKey(0))) == {
        "Wq", "Wk", "Wv", "Wo", "b"}
    assert not SelfAttentionLayer(n_in=16, n_out=16, n_heads=4, causal=True,
                                  window=8).plain


# ------------------------------------------------------------ the router
def _moe_layer(held, E=16):
    layer = MixtureOfExpertsLayer(
        n_in=32, n_out=32, n_experts=E, top_k=SIZES["top_k"],
        expert_hidden=SIZES["expert_width"], activation="silu",
        dispatch="routed", experts_held=held, gated=True,
        shared_hidden=SIZES["shared_width"], has_bias=False,
        score="sigmoid", routed_scale=SIZES["routed_scale"])
    layer.finalize()
    return layer


def test_the_sigmoid_router_is_the_written_out_selection():
    """Scores by sigmoid; the bias takes part in the choice (it changes
    the chosen pair for a share of the tokens) and in nothing else; the
    chosen scores are renormalised; the factor is on the sum."""
    layer = _moe_layer(None)
    assert "select_bias" in layer.param_order()
    logits = 1.5 * jax.random.normal(jax.random.PRNGKey(0), (64, 16))
    bias = 0.3 * jax.random.normal(jax.random.PRNGKey(1), (16,))
    w, idx = jax.jit(layer._choose)(logits, bias)
    scores = jax.nn.sigmoid(logits)
    want = REF.route(scores, bias, SIZES) / SIZES["routed_scale"]
    got = np.zeros((64, 16), np.float32)
    got[np.arange(64)[:, None], np.asarray(idx)] = np.asarray(w)
    np.testing.assert_allclose(got, want, atol=1e-6)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 1.0, atol=1e-6)
    _, plain = jax.lax.top_k(scores, 2)
    moved = (np.sort(np.asarray(idx)) != np.sort(np.asarray(plain))).any(-1)
    assert 5 < moved.sum() < 60
    with pytest.raises(ValueError, match="dispatch='routed'"):
        MixtureOfExpertsLayer(n_in=8, n_out=8, score="sigmoid").finalize()


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """One expert layer of 16 experts cut eight ways, as eight chips would
    hold it (2 experts each, router, bias and shared expert whole on
    each). Each share's result less the shared expert's, summed, plus the
    shared expert's once, is the uncut reference layer's."""
    sz = dict(SIZES, experts_held=[0, 16])
    whole = REF.make_params(3, sz, INIT)["ffn1"]
    h = jax.random.normal(jax.random.PRNGKey(9), (11, sz["d_model"]))
    want = np.asarray(REF._moe(whole, h, sz, "float32"))
    shared = np.asarray(REF._ffn(h, whole["Ws1"], whole["Ws2"], "float32"))
    total = shared.copy()
    for first in range(0, 16, 2):
        layer = _moe_layer((first, 2))
        p = {k: (v[first:first + 2] if k in ("W1", "W2") else v).astype(
            jnp.float32) for k, v in whole.items()}
        out, _ = jax.jit(lambda pp: layer.forward(pp, {}, h))(p)
        total += np.asarray(out) - shared
    np.testing.assert_allclose(total, want, atol=3 * TOL)
    assert np.abs(want - shared).max() > 0.1        # the experts weigh in


# ----------------------------------------------------- the model, streamed
def _gaps(params, sizes, prompt, tokens):
    """By how much each served token's reference logit lies below the
    reference's best at its position."""
    ids = np.concatenate([prompt, tokens])
    n = len(tokens)
    want = np.asarray(REF.sequence_logits(params, ids, len(prompt) - 1, n,
                                          sizes, pad_to=256, rows=48))
    return want.max(-1) - want[np.arange(n), tokens]


def _streamed_error(net, params, sizes, mode="float32"):
    """150 tokens as a whole sequence, and 80 of them again as two chunks
    (the second straddles the window's edge) and then token by token
    through the streaming carry, past two windows: the widest difference
    from the reference's log-probabilities."""
    ids = np.random.default_rng(4).integers(0, V, 150)
    x = np.eye(V, dtype=np.float32)[ids][None]
    want = np.asarray(jax.nn.log_softmax(REF.sequence_logits(
        params, ids, 0, 150, sizes, mode=mode, pad_to=304, rows=150),
        axis=-1))
    whole = np.log(np.asarray(net.output(x))[0])
    net.rnn_clear_previous_state()
    got = [np.asarray(net.rnn_time_step(x[:, :27]))[0],
           np.asarray(net.rnn_time_step(x[:, 27:40]))[0]]
    got += [np.asarray(net.rnn_time_step(x[:, t:t + 1]))[0]
            for t in range(40, 80)]
    net.rnn_clear_previous_state()
    return max(float(np.abs(whole - want).max()),
               float(np.abs(np.log(np.concatenate(got)) - want[:80]).max()))


@pytest.mark.parametrize("fault", (None, "bfloat16") + tuple(
    f for f in FAULTS if f != "window_page_freed_early"))
def test_streamed_is_the_reference_and_each_fault_is_not(trinity, fault,
                                                         monkeypatch):
    """Sound to 2e-5 (some float32 ulps of log-probabilities near -8).
    The reference's own pass in bfloat16 reads over 1e-2, and each planted
    fault over 1e-3: a sliding layer that attends to everything, the full
    layer rotated, the gate left out, the selection bias in the weights,
    the chosen scores not renormalised."""
    net, params, sizes = trinity
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
        assert err <= 2e-5, err
    else:
        assert err > 1e-3, (fault, err)


# --------------------------------------------------------------- the server
def _serve(net, reqs, **kw):
    srv = GenerationServer(net, V, **{**dict(slots=3, page_size=16,
                                             prefill_chunk=48,
                                             steps_per_dispatch=2), **kw})
    try:
        futs = [srv.submit(r[0], r[1], **(r[2] if len(r) > 2 else {}))
                for r in reqs]
        outs = [np.asarray(f.result(timeout=300)) for f in futs]
        return srv, outs, srv.stats(), srv.metrics.snapshot()
    finally:
        srv.close()


def _requests(rng):
    """Short and long prompts in one queue, from a quarter of a window to
    over six; more requests than slots, so that freed pages are taken
    again by other slots; one sampled."""
    sizes = [(150, 12), (20, 30), (200, 8), (7, 40), (90, 20), (170, 10)]
    how = [{}, {}, {}, dict(temperature=0.8, top_k=5, seed=7), {}, {}]
    return [(rng.integers(0, V, n), m, h) for (n, m), h in zip(sizes, how)]


@pytest.fixture(scope="module")
def served(trinity):
    net, params, sizes = trinity
    reqs = _requests(np.random.default_rng(0))
    return reqs, _serve(net, reqs)


@pytest.mark.generation
def test_served_through_two_page_classes_is_the_references_full_forward(
        trinity, served):
    """Six requests through three slots: chunked prefill (rounds of 48
    tokens) and decode through both page classes at contexts up to 210,
    past six windows of 32. Every greedy token is the reference's best at
    its position to rounding, so a freed page taken by another slot
    changed no logit. The window class never holds more than a window and
    a chunk a slot while the full class grows with the prompts."""
    net, params, sizes = trinity
    reqs, (srv, outs, stats, snap) = served
    assert [(c.name, c.window, c.layers) for c in srv._classes] == [
        ("full", None, ("attn2",)), ("window", 32, ("attn0", "attn1"))]
    assert srv._pa == "xla" and not srv.prefix_cache
    assert all(t.shape == (r[1],) for r, t in zip(reqs, outs))
    worst = max(float(_gaps(params, sizes, r[0], t).max())
                for r, t in zip(reqs, outs) if not r[2])
    assert worst <= TOL
    pages = stats["pages"]
    full, window = pages["classes"]["full"], pages["classes"]["window"]
    # kv_heads x head_dim x (key + value) x 4 bytes a layer
    assert full["bytes_per_token"] == 128 and window["bytes_per_token"] == 256
    assert pages["bytes_per_token"] == srv._page_token_bytes == 384
    # a slot's window pages: ceil((32 + 48 + 14) / 16) = 6, whatever the
    # prompt's length; the full class held a 200-token prompt whole
    assert window["pages_total"] == 3 * 6 + 1
    assert window["peak_pages_in_use"] <= 3 * 6
    assert full["peak_pages_in_use"] >= 200 // 16 + 150 // 16
    assert window["pages_released"] >= sum(
        (len(r[0]) + r[1] - 32) // 16 for r in reqs if len(r[0]) > 64)
    assert window["pages_in_use"] == full["pages_in_use"] == 0
    assert pages["preempted"] == 0 and pages["prefix_hits"] == 0
    assert snap["generation_window_pages_released_total"] \
        == window["pages_released"]
    live, viewed = (snap[f"generation_cache_kv_{k}_tokens_total"]
                    for k in ("live", "viewed"))
    # a window layer's view is window_pages(2, 16) = 3 pages wide, the
    # full layer's the table's 16; live in a window layer is at most 32
    assert viewed["cache=window|program=decode"] * 16 \
        == viewed["cache=full|program=decode"] * 3 * 2
    assert live["cache=window|program=decode"] \
        < 2 * live["cache=full|program=decode"]
    assert snap["generation_kv_live_tokens_total"]["program=decode"] \
        == sum(live.values())
    resident = snap["generation_kv_resident_bytes_total"]
    assert 0 < resident["layout=classes"] < resident["layout=uniform"]


@pytest.mark.generation
def test_one_row_dispatches_through_two_page_classes_are_the_reference(
        trinity, monkeypatch):
    """The benchmark cell's row group: a chunk that fills the server's
    position budget, so every prefill dispatch is one row and none is
    padding. Short and long prompts admitted as one wave still take and
    free their window pages round by round, each on its own."""
    net, params, sizes = trinity
    monkeypatch.setattr(GenerationServer, "PREFILL_POSITIONS", 48)
    reqs = [r[:2] for r in _requests(np.random.default_rng(5))][:4]
    srv, outs, stats, snap = _serve(net, reqs)
    assert srv._prefill_rows == 1
    worst = max(float(_gaps(params, sizes, r[0], t).max())
                for r, t in zip(reqs, outs))
    assert worst <= TOL
    rows = snap["generation_prefill_rows_total"]
    assert rows["kind=computed"] == rows["kind=admitted"] \
        == sum(-(-len(r[0]) // 48) for r in reqs)
    window = stats["pages"]["classes"]["window"]
    assert window["peak_pages_in_use"] <= 3 * 6 and window["pages_released"]
    assert stats["pages"]["preempted"] == 0


@pytest.mark.generation
def test_a_window_page_freed_early_is_not_the_reference(trinity,
                                                         monkeypatch):
    """The served-token comparison with the loop's part broken underneath:
    a window class's page goes back to the pool one page early, so the
    window's oldest tokens are read from the garbage page."""
    net, params, sizes = trinity
    plant("window_page_freed_early", monkeypatch)
    net._output_cache.clear()          # programs traced without the fault
    reqs = [r[:2] for r in _requests(np.random.default_rng(2))][:4]
    try:
        _, outs, stats, _ = _serve(net, reqs)
    finally:
        net._output_cache.clear()
    worst = max(float(_gaps(params, sizes, r[0], t).max())
                for r, t in zip(reqs, outs))
    assert worst > 100 * TOL, worst


@pytest.mark.generation
@pytest.mark.parametrize("what", ["kv_dtype_int8", "tp", "snapshot_every",
                                  "role_prefill", "draft_net", "export",
                                  "adopt", "pages_of_no_class"])
def test_what_two_page_classes_cannot_carry_is_refused_by_name(trinity,
                                                               what):
    from deeplearning4j_tpu.parallel.handoff import SnapshotUnsupported
    from deeplearning4j_tpu.parallel.mesh import MeshGeometryError

    net, _, _ = trinity
    kw = dict(slots=2, page_size=16)
    refused = {
        "kv_dtype_int8": (ValueError, "kv_dtype='int8'",
                          dict(kv_dtype="int8")),
        "tp": (MeshGeometryError, "window", dict(tp=2)),
        "snapshot_every": (ValueError, "snapshot_every",
                           dict(snapshot_every=4)),
        "role_prefill": (ValueError, "role='prefill'", dict(role="prefill")),
        "draft_net": (ValueError, "draft_net", dict(draft_net=net)),
        "pages_of_no_class": (ValueError, "page classes",
                              dict(pages={"full": 40, "latent": 9}))}
    if what in refused:
        exc, match, extra = refused[what]
        with pytest.raises(exc, match=match):
            GenerationServer(net, V, **kw, **extra)
        return
    # the prefix cache is off, whatever was asked for
    srv = GenerationServer(net, V, prefix_cache=True,
                           pages={"full": 40, "window": 13}, **kw)
    try:
        assert not srv.prefix_cache
        assert [c.pages_total for c in srv._classes] == [40, 13]
        fut = srv.submit(np.arange(5), 3)
        with pytest.raises(SnapshotUnsupported, match="window page class"):
            if what == "export":
                srv.export_request(fut)
            else:
                srv.adopt_request(None)
        fut.result(timeout=120)
    finally:
        srv.close()


@pytest.mark.generation
def test_a_net_of_one_class_is_served_as_before(lm):
    """One class, one table handed to the programs as an array, no
    ``classes`` block, none of the window class's counters."""
    srv = GenerationServer(lm, 17, slots=2)
    try:
        assert [c.name for c in srv._classes] == ["full"]
        assert srv._bt_arg() is srv._bt is srv._classes[0].bt
        assert srv._page_pool is srv._classes[0].pool
        srv.submit(np.arange(5) % 17, 3).result(timeout=120)
        assert "classes" not in srv.stats()["pages"]
        assert "generation_window_pages_released_total" \
            not in srv.metrics.snapshot()
    finally:
        srv.close()


def test_zoo_lists_the_model():
    from deeplearning4j_tpu.models import zoo_models

    assert zoo_models()["trinitylm"] is TrinityLM
    with pytest.raises(ValueError, match="layer_types"):
        TrinityLM(layer_types=("sliding", "global"))
