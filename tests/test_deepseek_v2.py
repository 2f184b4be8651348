"""The latent-attention language model (``DeepSeekV2LM``) and what it
forced: ``LatentAttentionLayer`` (one cached row for all heads, read in the
absorbed form; YaRN frequencies on adjacent channel pairs), the router's
options in ``MixtureOfExpertsLayer`` (softmax over all experts,
group-limited selection, weights as they stand times a factor), and a
``GenerationServer`` whose pool, byte accounting, page copies and prefix
cache carry whatever planes a paged layer declares — each held against a
plain statement of the same mathematics
(``benchmarks/references/deepseek_v2.py``, non-absorbed, or a loop written
here).

Everything is float32 at toy widths, so agreement is to rounding: the
tolerances below are a few float32 ulps of values of order one, summed over
tens of terms (1e-5), and every planted fault misses them by orders of
magnitude.
"""

import importlib.util
import math
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models import DeepSeekV2LM
from deeplearning4j_tpu.nn.conf.layers import (LatentAttentionLayer,
                                               MixtureOfExpertsLayer)
from deeplearning4j_tpu.nn.conf.layers import attention as attention_module
from deeplearning4j_tpu.nn.conf.layers import latent_attention
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.parallel.generation import GenerationServer

TOL = 1e-5


def _benchmark_module(*path):
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", *path)
    spec = importlib.util.spec_from_file_location(
        "deepseek_" + path.rsplit("/", 1)[-1][:-3], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _benchmark_module("references", "deepseek_v2.py")
#: the faults planted under the served path, shared with the benchmark's
#: own tests and with the readings on the chip
plant = _benchmark_module("tests", "deepseek_faults.py").plant

V = 48
YARN = {"factor": 4.0, "original_positions": 64, "beta_fast": 8.0,
        "beta_slow": 1.0, "mscale": 0.707, "mscale_all_dim": 0.5}
# one dense block and two expert blocks; a rotary width (8) that is not the
# other head sizes (4, 6); 16 experts in 4 groups, 2 groups kept, 3 chosen,
# the first two groups held; the two mscales differ, so cos and sin carry a
# factor
SIZES = {"vocab": V, "d_model": 32, "layers": 3, "dense_layers": 1,
         "heads": 4, "q_rank": 12, "kv_rank": 10, "nope_dim": 4,
         "rope_dim": 8, "v_dim": 6, "mlp_width": 48, "experts": 16,
         "experts_held": [0, 8], "top_k": 3, "expert_groups": 4,
         "groups_kept": 2, "routed_scale": 4.0, "expert_width": 16,
         "shared_width": 24, "rope_theta": 10000.0, "yarn": YARN,
         "rms_eps": 1e-6}
INIT = {"std": {"embed": 0.5, "dq": 0.2, "uq": 0.3, "dkv": 0.2, "uk": 0.3,
                "uv": 0.3, "o": 0.2, "mlp_in": 0.2, "mlp_down": 0.2,
                "router": 0.2, "expert_in": 0.2, "expert_down": 0.2,
                "shared_in": 0.2, "shared_down": 0.2, "head": 0.4}}


def _model(sz):
    y = sz["yarn"]
    return DeepSeekV2LM(
        num_labels=sz["vocab"], max_length=128, d_model=sz["d_model"],
        n_layers=sz["layers"], dense_layers=sz["dense_layers"],
        n_heads=sz["heads"], q_rank=sz["q_rank"], kv_rank=sz["kv_rank"],
        nope_dim=sz["nope_dim"], rope_dim=sz["rope_dim"],
        v_dim=sz["v_dim"], rope_theta=sz["rope_theta"],
        yarn_factor=y["factor"],
        yarn_original_positions=y["original_positions"],
        yarn_beta_fast=y["beta_fast"], yarn_beta_slow=y["beta_slow"],
        yarn_mscale=y["mscale"], yarn_mscale_all_dim=y["mscale_all_dim"],
        mlp_width=sz["mlp_width"], n_experts=sz["experts"],
        experts_held=sz["experts_held"], top_k=sz["top_k"],
        expert_groups=sz["expert_groups"], groups_kept=sz["groups_kept"],
        routed_scale=sz["routed_scale"], expert_width=sz["expert_width"],
        shared_width=sz["shared_width"], rms_eps=sz["rms_eps"],
        dtype="float32")


def tiny_deepseek(seed=5):
    """The zoo model at toy widths in float32, holding the reference's
    (bfloat16-valued) weights: (net, params, sizes)."""
    params = REF.make_params(seed, SIZES, INIT)
    conf = _model(SIZES).conf()
    for v in conf.vertices.values():
        layer = getattr(v, "layer", None)
        if layer is not None and hasattr(layer, "max_cache"):
            layer.max_cache = 128
    net = ComputationGraph(conf)
    net.init(params={n: params.get(n, {}) for n in conf.topo_order})
    return net, params, SIZES


@pytest.fixture(scope="module")
def deepseek():
    return tiny_deepseek()


# --------------------------------------------------------------- rotation
def test_yarn_at_the_published_sizes():
    """DeepSeek-V2's own numbers: the ramp runs from channel pair 10 to 23
    of 32, the fast pairs keep their frequency, the slow ones are divided
    by 40, the score factor is 0.11472, cos and sin carry 1."""
    f = attention_module.rotary_frequencies(64, 10000.0, (40, 4096, 32, 1))
    plain = 10000.0 ** (-2.0 * np.arange(32) / 64)
    np.testing.assert_allclose(f[:11], plain[:11], rtol=1e-12)
    np.testing.assert_allclose(f[23:], plain[23:] / 40, rtol=1e-12)
    r = (np.arange(11, 23) - 10) / 13
    np.testing.assert_allclose(
        f[11:23], plain[11:23] * (1 - r) + plain[11:23] / 40 * r, rtol=1e-12)
    sz = {"rope_dim": 64, "rope_theta": 10000, "nope_dim": 128,
          "yarn": {"factor": 40, "original_positions": 4096,
                   "beta_fast": 32, "beta_slow": 1, "mscale": 0.707,
                   "mscale_all_dim": 0.707}}
    np.testing.assert_allclose(REF.yarn_frequencies(sz), f, rtol=1e-12)
    layer = LatentAttentionLayer(
        n_in=8, n_out=8, n_heads=2, q_rank=4, kv_rank=4, nope_dim=128,
        rope_dim=64, v_dim=128, yarn_factor=40, yarn_original_positions=4096,
        yarn_mscale=0.707, yarn_mscale_all_dim=0.707)
    assert layer._score_scale() == pytest.approx(0.11472, rel=1e-4)
    assert REF.score_scale(sz) == pytest.approx(layer._score_scale())
    assert attention_module.yarn_mscale(40, 0.707) \
        == pytest.approx(1.2608, rel=1e-4)


# ------------------------------------------------------------ latent layer
def _latent_layer(**kw):
    layer = LatentAttentionLayer(
        **{**dict(n_in=16, n_out=16, n_heads=4, q_rank=12, kv_rank=10,
                  nope_dim=4, rope_dim=8, v_dim=6, rope_theta=10000.0,
                  yarn_factor=4.0, yarn_original_positions=64,
                  yarn_beta_fast=8.0, yarn_mscale=0.707,
                  yarn_mscale_all_dim=0.5, max_cache=32), **kw})
    layer.finalize()
    layer.validate()
    return layer


def _latent_by_hand(layer, p, x):
    """One row ``[T, n_in]`` in float64, non-absorbed, the rotation written
    out pair by pair."""
    p = {k: np.asarray(v, np.float64) for k, v in p.items()}
    T = x.shape[0]
    H, n, R = layer.n_heads, layer.nope_dim, layer.rope_dim
    c = layer.kv_rank
    m = lambda a: 0.1 * a * math.log(layer.yarn_factor) + 1.0  # noqa: E731

    def rms(t, g):
        return t / np.sqrt((t * t).mean(-1, keepdims=True)
                           + layer.norm_eps) * g

    def corr(b):
        return R * math.log(layer.yarn_original_positions
                            / (2 * math.pi * b)) \
            / (2 * math.log(layer.rope_theta))

    low, high = math.floor(corr(layer.yarn_beta_fast)), \
        math.ceil(corr(layer.yarn_beta_slow))
    assert (low, high) == (0, 2)            # a ramp of three pairs

    def turn(t):                            # [T, ..., R], adjacent pairs
        out = np.empty_like(t)
        for pos in range(T):
            for i in range(R // 2):
                f = layer.rope_theta ** (-2.0 * i / R)
                r = min(max((i - low) / (high - low), 0.0), 1.0)
                ang = pos * (f * (1 - r) + f / layer.yarn_factor * r)
                a, b = t[pos, ..., 2 * i], t[pos, ..., 2 * i + 1]
                k = m(layer.yarn_mscale) / m(layer.yarn_mscale_all_dim)
                out[pos, ..., i] = (a * np.cos(ang) - b * np.sin(ang)) * k
                out[pos, ..., i + R // 2] = (b * np.cos(ang)
                                             + a * np.sin(ang)) * k
        return out

    x = np.asarray(x, np.float64)
    q = (rms(x @ p["Wdq"], p["q_gamma"]) @ p["Wuq"]).reshape(T, H, n + R)
    q_nope, q_rope = q[..., :n], turn(q[..., n:])
    kv = x @ p["Wdkv"]
    lat, k_r = rms(kv[:, :c], p["kv_gamma"]), turn(kv[:, c:])
    s = (n + R) ** -0.5 * m(layer.yarn_mscale_all_dim) ** 2
    rows = []
    for j in range(H):
        k_nope = lat @ p["Wuk"][j].T                       # [T, n]
        v = lat @ p["Wuv"][j]                              # [T, v]
        sc = s * (q_nope[:, j] @ k_nope.T + q_rope[:, j] @ k_r.T)
        sc = np.where(np.tril(np.ones((T, T), bool)), sc, -1e30)
        w = np.exp(sc - sc.max(-1, keepdims=True))
        rows.append((w / w.sum(-1, keepdims=True)) @ v)
    return np.concatenate(rows, -1) @ p["Wo"]


@pytest.fixture(scope="module")
def latent_case():
    layer = _latent_layer()
    p = layer.init_params(jax.random.PRNGKey(0))
    assert p["Wuq"].shape == (12, 4 * 12) and p["Wdkv"].shape == (16, 18)
    assert p["Wuk"].shape == (4, 4, 10) and p["Wuv"].shape == (4, 10, 6)
    assert p["Wo"].shape == (24, 16) and "b" not in p
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 13, 16), jnp.float32)
    want = np.stack([_latent_by_hand(layer, p, np.asarray(r)) for r in x])
    return layer, p, x, want


def _paged(layer, p, x, cuts, lens=None):
    """Rows through a latent page pool in the chunks ``cuts`` names; with
    ``lens`` the first chunk is right-padded and masked to each row's true
    length, the caller sets the rows' watermarks (as the server does) and
    the rest of each row follows token by token at its own position."""
    fwd = jax.jit(lambda st, xx, mk: layer.forward(p, st, xx, mask=mk))
    pool = layer.init_paged_carry(9, 4)
    # ONE plane, no head axis: the latent beside the shared rotary key
    assert set(pool) == {"latent_pages"}
    assert pool["latent_pages"].shape == (9, 4, 18)
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
            chunk = jnp.take_along_axis(x, pos[:, None, None], axis=1)
        o, ns = fwd(dict(pool, block_table=bt, cache_pos=pos), chunk, mask)
        pool = {"latent_pages": ns["latent_pages"]}
        pos = ns["cache_pos"] if mask is None else lens.astype(jnp.int32)
        outs.append(o)
    return outs


@pytest.mark.parametrize("how", ["contiguous", "streaming", "paged",
                                 "paged_right_padded", "head_blocks"])
def test_latent_attention_every_forward_is_the_plain_form(latent_case, how,
                                                          monkeypatch):
    """Four heads reading one cached row of 10 + 8 numbers: the whole
    sequence (plain form), a dense cache fed in uneven chunks, a page pool
    fed a chunk and then token by token, a right-padded masked chunk whose
    rows continue from their own true lengths (absorbed form, all three),
    and the absorbed read taken a block of heads at a time are all the
    non-absorbed mathematics written out head by head in float64."""
    layer, p, x, want = latent_case
    if how == "contiguous":
        got, _ = jax.jit(lambda xx: layer.forward(p, {}, xx))(x)
    elif how in ("streaming", "head_blocks"):
        if how == "head_blocks":
            # 2 rows x 4 heads x 8 queries x 32 keys of float32 are 8 KiB
            monkeypatch.setattr(latent_attention, "SCORE_BYTES", 4096)
        fwd = jax.jit(lambda st, xx: layer.forward(p, st, xx))
        st = layer.init_streaming_carry(2)
        assert set(st) == {"latent_cache", "cache_pos"}
        assert st["latent_cache"].shape == (2, 32, 18)
        outs = []
        for a, b in ((0, 4), (4, 5), (5, 13)):
            o, st = fwd(st, x[:, a:b])
            outs.append(o)
        got = jnp.concatenate(outs, 1)
    elif how == "paged":
        got = jnp.concatenate(
            _paged(layer, p, x, (0, 6, 7, 8, 9, 10, 11, 12, 13)), 1)
    else:
        lens = jnp.asarray([5, 8])
        outs = _paged(layer, p, x, (0, 8, 9, 10, 11), lens=lens)
        # the padded chunk's true rows, then each row's next three tokens
        for r, n in enumerate((5, 8)):
            np.testing.assert_allclose(outs[0][r, :n], want[r, :n],
                                       atol=TOL)
            for j, o in enumerate(outs[1:]):
                np.testing.assert_allclose(o[r, 0], want[r, n + j],
                                           atol=TOL)
            assert not np.asarray(outs[0][r, n:]).any()   # masked: zeros
        return
    np.testing.assert_allclose(got, want, atol=TOL)


def test_the_latent_layer_refuses_what_it_cannot_carry(latent_case):
    layer, p, x, _ = latent_case
    with pytest.raises(ValueError, match="latent cache"):
        layer.init_paged_carry(4, 4, kv_dtype="int8")
    assert layer.paged_token_bytes("float32") == 18 * 4
    assert layer.paged_token_bytes("bfloat16") == 18 * 2
    pool = layer.init_paged_carry(4, 4)
    st = dict(pool, block_table=jnp.zeros((2, 2), jnp.int32),
              cache_pos=jnp.zeros((2,), jnp.int32),
              **{attention_module.SERVED_BY: ("pallas", None)})
    with pytest.raises(NotImplementedError, match="XLA paged backend"):
        layer.forward(p, st, x[:, :2])
    with pytest.raises(ValueError, match="rope_dim"):
        _latent_layer(rope_dim=7)


# ------------------------------------------------------------------ router
def _route_by_hand(logits, E, K, G, kept):
    """DeepSeek-V2's group-limited greedy selection, token by token."""
    out = []
    for row in np.asarray(logits, np.float64):
        p = np.exp(row - row.max())
        p /= p.sum()
        size = E // G
        best = [p[g * size:(g + 1) * size].max() for g in range(G)]
        groups = sorted(range(G), key=lambda g: -best[g])[:kept]
        allowed = [e for e in range(E) if e // size in groups]
        chosen = sorted(allowed, key=lambda e: -p[e])[:K]
        out.append({e: p[e] for e in chosen})
    return out


@pytest.mark.parametrize("G,kept,K", [(4, 2, 3), (8, 3, 6), (2, 1, 4),
                                      (0, 0, 3)])
def test_group_limited_selection_is_the_written_out_loop(G, kept, K):
    """Softmax over all 16 experts, the best ``kept`` of ``G`` groups by
    their best expert, the ``K`` most probable inside them, weights as they
    stand (not renormalised): the layer's choice is the loop's, token by
    token; without groups it is the K most probable of all."""
    E = 16
    layer = MixtureOfExpertsLayer(
        n_in=8, n_out=8, n_experts=E, top_k=K, expert_hidden=4,
        dispatch="routed", gated=True, has_bias=False, gate_over="all",
        expert_groups=G, groups_kept=kept)
    layer.finalize()
    logits = 2.0 * jax.random.normal(jax.random.PRNGKey(G + K), (50, E))
    w, idx = jax.jit(layer._choose)(logits)
    want = _route_by_hand(logits, E, K, G or 1, kept or 1)
    for t in range(50):
        assert set(np.asarray(idx[t]).tolist()) == set(want[t])
        for e, g in zip(np.asarray(idx[t]).tolist(), np.asarray(w[t])):
            assert g == pytest.approx(want[t][e], rel=1e-5)
    if G:
        # the group limit bit: the unlimited top-K differs somewhere
        free = np.argsort(-np.asarray(logits), axis=-1)[:, :K]
        assert any(set(free[t].tolist()) != set(want[t]) for t in range(50))


def test_the_routers_defaults_are_the_old_router():
    layer = MixtureOfExpertsLayer(n_in=8, n_out=8, n_experts=6, top_k=2,
                                  expert_hidden=4, dispatch="routed")
    layer.finalize()
    logits = jax.random.normal(jax.random.PRNGKey(0), (9, 6))
    w, idx = layer._choose(logits)
    top, want = jax.lax.top_k(logits, 2)
    np.testing.assert_array_equal(idx, want)
    np.testing.assert_array_equal(w, jax.nn.softmax(top, axis=-1))
    for bad in (dict(gate_over="some"), dict(expert_groups=4, groups_kept=1),
                dict(expert_groups=3, groups_kept=5),
                dict(dispatch="dense", routed_scale=2.0)):
        with pytest.raises(ValueError):
            MixtureOfExpertsLayer(n_in=8, n_out=8, n_experts=6, top_k=2,
                                  expert_hidden=4,
                                  **{**dict(dispatch="routed"),
                                     **bad}).finalize()


def test_the_four_shares_add_up_to_the_uncut_layer():
    """The share test: one expert layer of 16 experts cut four ways, as
    four chips would hold it (4 experts each, router and shared expert
    whole on each). Each share's result less the shared expert's, summed,
    plus the shared expert's once, is the uncut reference layer's: nothing
    is lost or counted twice by holding a share."""
    sz = dict(SIZES, experts_held=[0, 16])
    whole = REF.make_params(3, sz, INIT)["ffn1"]
    h = jax.random.normal(jax.random.PRNGKey(9), (11, sz["d_model"]))
    want = np.asarray(REF._moe(whole, h, sz, "float32"))
    shared = np.asarray(REF._ffn(h, whole["Ws1"], whole["Ws2"], "float32"))
    total = shared.copy()
    for first in (0, 4, 8, 12):
        layer = MixtureOfExpertsLayer(
            n_in=32, n_out=32, n_experts=16, top_k=sz["top_k"],
            expert_hidden=sz["expert_width"], activation="silu",
            dispatch="routed", experts_held=(first, 4), gated=True,
            shared_hidden=sz["shared_width"], has_bias=False,
            gate_over="all", expert_groups=4, groups_kept=2,
            routed_scale=sz["routed_scale"])
        layer.finalize()
        p = {k: (v[first:first + 4] if k in ("W1", "W2") else v).astype(
            jnp.float32) for k, v in whole.items()}
        out, _ = jax.jit(lambda pp: layer.forward(pp, {}, h))(p)
        # the same share in the reference's own words
        part = REF._moe(p, h, dict(sz, experts_held=[first, 4]), "float32")
        np.testing.assert_allclose(out, part, atol=TOL)
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
                                          sizes))
    return want.max(-1) - want[np.arange(n), tokens]


def test_whole_sequence_probabilities_are_the_references(deepseek):
    net, params, sizes = deepseek
    ids = np.random.default_rng(0).integers(0, V, 21)
    x = np.eye(V, dtype=np.float32)[ids][None]
    want = np.asarray(jax.nn.softmax(
        REF.sequence_logits(params, ids, 0, 21, sizes), axis=-1))
    # probabilities up to 0.3: a few float32 ulps (1.3e-6 read)
    np.testing.assert_allclose(np.asarray(net.output(x))[0], want, atol=3e-6)


def _streamed_error(net, params, sizes):
    """Prefill two chunks (the second starts at position 7), then decode
    token by token through the streaming carry, absorbed: the widest
    difference from the reference's log-probabilities."""
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


FAULTS = ("rotation_restarted", "yarn_left_out", "key_cached_unrotated",
          "latent_cached_before_its_norm",
          "selection_without_the_group_limit", "weights_renormalised")


@pytest.mark.parametrize("fault", (None,) + FAULTS)
def test_prefill_then_decode_is_the_reference_and_each_fault_is_not(
        deepseek, fault, monkeypatch):
    """Log-probabilities of two prefilled chunks and twelve tokens decoded
    in the absorbed form against the reference's non-absorbed full pass:
    sound to 2e-5 (the absorbed form sums in another order: some float32
    ulps of values near -8; 7.6e-6 read); each planted fault reads over
    1e-4: the rotation restarted at a chunk boundary, YaRN
    left out, the shared key cached unrotated, the latent cached before its
    norm, selection without the group limit, weights renormalised."""
    net, params, sizes = deepseek
    if fault is not None:
        plant(fault, monkeypatch)
    net._output_cache.clear()          # programs traced without the fault
    try:
        err = _streamed_error(net, params, sizes)
    finally:
        net._output_cache.clear()
    if fault is None:
        assert err <= 2e-5
    else:
        assert err > 1e-4, err


# --------------------------------------------------------------- the server
def _serve(net, reqs, **kw):
    """Requests one after another (each finds what the ones before left in
    the prefix cache) unless ``together``."""
    together = kw.pop("together", False)
    srv = GenerationServer(net, V, **{**dict(slots=3, page_size=8,
                                             prefill_chunk=16,
                                             steps_per_dispatch=2), **kw})
    try:
        submit = lambda r: srv.submit(  # noqa: E731
            r[0], r[1], **(r[2] if len(r) > 2 else {}))
        if together:
            outs = [f.result(timeout=120) for f in [submit(r) for r in reqs]]
        else:
            outs = [submit(r).result(timeout=120) for r in reqs]
        return srv, outs, srv.stats(), srv.metrics.snapshot()
    finally:
        srv.close()


def _doc_requests(rng, doc=37):
    """A shared document of 37 tokens (four whole pages and a part), then
    each request's own question; the second repeats the first's prompt
    whole; two are sampled."""
    shared = rng.integers(0, V, doc)
    own = [rng.integers(0, V, n) for n in (6, 6, 19, 1, 11)]
    own[1] = own[0]
    how = [{}, {}, dict(temperature=0.8, top_k=5, seed=7), {},
           dict(temperature=0.7, top_k=0, seed=11)]
    return [(np.concatenate([shared, o]), k, h)
            for o, k, h in zip(own, (9, 12, 7, 10, 8), how)]


@pytest.fixture(scope="module")
def served(deepseek):
    net, params, sizes = deepseek
    reqs = _doc_requests(np.random.default_rng(1))
    return reqs, _serve(net, reqs)


@pytest.mark.generation
def test_served_from_shared_latent_pages_is_the_references_full_forward(
        deepseek, served):
    """Five requests about one 37-token document through the prefix cache:
    the first prefills it cold, the others take its four whole pages from
    the cache and prefill their own part at position 32 on; the repeated
    prompt is taken whole and copies its last page on write. Every greedy
    token is the reference's best at its position to rounding; the pool is
    one plane a layer and the server reckons a token's bytes from it."""
    net, params, sizes = deepseek
    reqs, (srv, outs, stats, snap) = served
    assert srv._paged_names == ["mla0", "mla1", "mla2"]
    assert srv._slot_names == [] and srv._pa == "xla" and srv.prefix_cache
    pages = stats["pages"]
    # three layers x (10 + 8) numbers x 4 bytes, whatever the head count
    assert pages["bytes_per_token"] == srv._page_token_bytes == 3 * 18 * 4
    assert pages["planes"] == {"latent_pages": 3}
    assert snap["generation_kv_bytes_per_token"] == 3 * 18 * 4
    assert all(t.shape == (r[1],) for r, t in zip(reqs, outs))
    worst = max(float(_gaps(params, sizes, r[0], t).max())
                for r, t in zip(reqs, outs) if len(r) < 3 or not r[2])
    assert worst <= TOL
    # four later requests hit; the repeat matched all 43 tokens but one
    assert pages["prefix_hits"] == 4
    assert pages["prefix_tokens_reused"] == 42 + 3 * 32
    assert pages["prompt_tokens_admitted"] == sum(len(r[0]) for r in reqs)
    assert pages["cow_copies"] >= 2
    for name in ("generation_prefix_tokens_reused_total",
                 "generation_prompt_tokens_admitted_total",
                 "generation_cow_copies_total"):
        assert snap[name] > 0


@pytest.mark.generation
def test_shared_pages_and_copies_on_write_change_no_bit(deepseek, served):
    """The same five requests with the prefix cache off (every prompt
    prefilled whole into pages of its own): the same tokens, greedy and
    sampled, bit for bit. Sampled tokens are drawn from the served
    probabilities, so a shared page that differed in a bit would show."""
    net, _, _ = deepseek
    reqs, (_, outs, stats, _) = served
    _, alone, plain, _ = _serve(net, reqs, prefix_cache=False)
    assert plain["pages"]["prefix_hits"] == 0
    assert plain["pages"]["cow_copies"] == 0
    for a, b in zip(outs, alone):
        np.testing.assert_array_equal(a, b)
    # and admitted together, rows of one wave beside each other
    _, wave, _, _ = _serve(net, reqs, together=True, slots=5)
    for a, b in zip(outs, wave):
        np.testing.assert_array_equal(a, b)


@pytest.mark.generation
@pytest.mark.parametrize("fault", ["stale_page_after_a_copy",
                                   "rotation_restarted"])
def test_a_fault_in_the_served_path_is_not_the_reference(deepseek, fault,
                                                         monkeypatch):
    """The served-token comparison with the server's part broken
    underneath: a copy-on-write that repoints the block table and copies
    nothing (the row then reads a page of zeros where its document's last
    tokens were), or a later prefill round rotated from position 0."""
    net, params, sizes = deepseek
    if fault == "stale_page_after_a_copy":
        monkeypatch.setattr(
            GenerationServer, "_page_copy_program",
            lambda self: lambda pool, src, dst: pool)
    else:
        plant(fault, monkeypatch)
    net._output_cache.clear()          # programs traced without the fault
    reqs = [r[:2] for r in _doc_requests(np.random.default_rng(2))]
    try:
        _, outs, stats, _ = _serve(net, reqs)
    finally:
        net._output_cache.clear()
    assert stats["pages"]["cow_copies"] >= 1
    worst = max(float(_gaps(params, sizes, r[0], t).max())
                for r, t in zip(reqs, outs))
    assert worst > 100 * TOL, worst


@pytest.mark.generation
def test_what_a_latent_plane_cannot_carry_is_refused_by_name(deepseek):
    from deeplearning4j_tpu.parallel.handoff import SnapshotUnsupported
    from deeplearning4j_tpu.parallel.mesh import MeshGeometryError

    net, _, _ = deepseek
    kw = dict(slots=2, page_size=8)
    with pytest.raises(ValueError, match="kv_dtype='int8'"):
        GenerationServer(net, V, kv_dtype="int8", **kw)
    with pytest.raises(MeshGeometryError, match="no head axis"):
        GenerationServer(net, V, tp=2, **kw)
    with pytest.raises(ValueError, match="snapshot_every"):
        GenerationServer(net, V, snapshot_every=4, **kw)
    with pytest.raises(ValueError, match="role='prefill'"):
        GenerationServer(net, V, role="prefill", **kw)
    with pytest.raises(ValueError, match="draft_net"):
        GenerationServer(net, V, draft_net=net, **kw)
    with pytest.raises(ValueError, match="'pallas'"):
        GenerationServer(net, V, paged_attention="pallas", **kw)
    srv = GenerationServer(net, V, **kw)
    try:
        fut = srv.submit(np.arange(5), 3)
        with pytest.raises(SnapshotUnsupported, match="no head axis"):
            srv.export_request(fut)
        fut.result(timeout=120)
    finally:
        srv.close()


@pytest.mark.generation
def test_a_migrating_drain_fetches_no_latent_page(deepseek):
    """A latent plane has no snapshot wire form, so a drain with
    ``migrate=`` moves its live requests out snapshot-free: typed
    ``RequestMigrated``, no snapshot to the sink, and no page fetched
    for one."""
    from deeplearning4j_tpu.parallel.handoff import RequestMigrated
    from deeplearning4j_tpu.parallel.resilience import ChaosPolicy

    net, _, _ = deepseek
    # each decode dispatch stalls, so both requests are live at the drain
    srv = GenerationServer(net, V, slots=2, page_size=8,
                           steps_per_dispatch=1,
                           chaos=ChaosPolicy(seed=9, stall_rate=1.0,
                                             stall_s=0.02))
    fetches = []
    fetch_program = srv._page_fetch_program
    srv._page_fetch_program = lambda: fetches.append(1) or fetch_program()
    collected = []
    try:
        futs = [srv.submit(np.arange(1, 6) + i, 60) for i in range(2)]
        while srv.stats()["active_slots"] < 2:
            time.sleep(0.005)
        assert srv.drain(timeout=60.0, migrate=collected.append)
        for fut in futs:
            with pytest.raises(RequestMigrated):
                fut.result(timeout=0)
            assert getattr(fut, "_kv_snapshot", None) is None
        assert srv.stats()["handoff"]["migrated"] == 2
    finally:
        srv.close()
    assert fetches == [] and collected == []


def test_zoo_lists_the_model():
    from deeplearning4j_tpu.models import zoo_models

    assert zoo_models()["deepseekv2lm"] is DeepSeekV2LM
