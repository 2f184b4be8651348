"""Each streaming layer states its carry once (``Layer.cache_spec()``), and
the server decides what it can serve beside it from one table
(``generation._LIMITS``). One case per streaming layer kind holds the
declaration against the carries the layer builds, dense and paged; one
case per zoo language model holds the server's table against the
refusals the models' own test files pin typed.
"""

import jax.numpy as jnp
import pytest

from deeplearning4j_tpu.nn.conf.layers import (GatedDeltaNetLayer,
                                               LatentAttentionLayer,
                                               Mamba2Layer,
                                               MixtureOfExpertsLayer)
from deeplearning4j_tpu.nn.conf.layers.attention import (
    PositionalEncodingLayer, SelfAttentionLayer)
from deeplearning4j_tpu.parallel.generation import GenerationServer
from tests import (serving_helpers, test_deepseek_v2, test_falcon_h1,
                   test_granite_hybrid, test_olmo_hybrid, test_qwen3_next,
                   test_trinity)

CAP, PS = 32, 8


def _attention(**kw):
    return SelfAttentionLayer(n_in=16, n_out=16, n_heads=4, causal=True,
                              max_cache=CAP, **kw)


LAYERS = {
    "attention_plain": lambda: _attention(),
    "attention_grouped": lambda: _attention(n_kv_heads=2),
    "attention_windowed": lambda: _attention(n_kv_heads=2, window=8),
    "latent": lambda: LatentAttentionLayer(
        n_in=16, n_out=16, n_heads=4, q_rank=12, kv_rank=10, nope_dim=4,
        rope_dim=8, v_dim=6, max_cache=CAP),
    "mamba2": lambda: Mamba2Layer(n_in=16, n_out=16, n_heads=4, head_dim=8,
                                  d_state=8, chunk_size=4),
    "gated_delta": lambda: GatedDeltaNetLayer(
        n_in=32, n_out=32, n_heads=4, key_dim=8, value_dim=16,
        chunk_size=8),
    "gated_delta_grouped_keys": lambda: GatedDeltaNetLayer(
        n_in=32, n_out=32, n_heads=4, n_key_heads=2, key_dim=8,
        value_dim=16, chunk_size=8),
    "moe_routed": lambda: MixtureOfExpertsLayer(
        n_in=16, n_out=16, n_experts=8, top_k=3, expert_hidden=12,
        dispatch="routed"),
    "positions": lambda: PositionalEncodingLayer(),
}
KIND = {"attention_plain": "pages", "attention_grouped": "pages",
        "attention_windowed": "pages", "latent": "pages",
        "mamba2": "slot_state", "gated_delta": "slot_state",
        "gated_delta_grouped_keys": "slot_state",
        "moe_routed": "counts", "positions": "positions"}


@pytest.mark.parametrize("case", sorted(LAYERS))
def test_a_layers_cache_spec_is_the_carry_it_builds(case):
    layer = LAYERS[case]()
    layer.finalize()
    spec = layer.cache_spec()
    assert spec.kind == KIND[case]
    carry = layer.init_streaming_carry(2)
    views = {view: axis for view, axis in spec.planes.values()}
    assert set(spec.keys) <= set(carry) <= set(spec.keys) | set(views)
    if spec.kind != "pages":
        assert set(carry) == set(spec.keys)
        assert not spec.planes and not spec.head_axes
        assert spec.window is None
        assert len(spec.counters) == (
            carry["call_counts"].shape[0] if spec.kind == "counts" else 0)
        return
    assert set(spec.head_axes) == set(spec.planes)
    assert spec.window == (8 if case == "attention_windowed" else None)
    headless = None in spec.head_axes.values()
    # a plane with no head axis has no int8 form (no scale a head)
    for kv_dtype in (None,) if headless else (None, "int8"):
        dense = carry if kv_dtype is None \
            else layer.init_streaming_carry(2, kv_dtype=kv_dtype)
        assert set(spec.keys) <= set(dense) <= set(spec.keys) | set(views)
        pool = layer.init_paged_carry(3, PS, kv_dtype=kv_dtype)
        assert set(pool) <= set(spec.planes)
        # every row's table names every page: the views are the dense
        # carry's caches, each token axis as long as the capacity
        got = layer.paged_views(pool, jnp.zeros((2, CAP // PS), jnp.int32))
        assert set(got) == {spec.planes[k][0] for k in pool}
        for k, plane in pool.items():
            view, axis = spec.planes[k]
            assert got[view].shape == dense[view].shape
            assert got[view].shape[axis] == CAP
            heads = spec.head_axes[k]
            if heads is not None:
                assert plane.shape[heads] in (
                    layer.kv_heads, layer.kv_heads * layer.d_head)


# each zoo LM at its test file's tiny sizes: (net, vocabulary)
ZOO = {
    "transformer": lambda: (serving_helpers.tiny_lm(max_length=64),
                            serving_helpers.V),
    "granite": lambda: (test_granite_hybrid.tiny_granite()[0],
                        test_granite_hybrid.V),
    "falcon": lambda: (test_falcon_h1.tiny_falcon()[0], test_falcon_h1.V),
    "deepseek": lambda: (test_deepseek_v2.tiny_deepseek()[0],
                         test_deepseek_v2.V),
    "trinity": lambda: (test_trinity._model(test_trinity.SIZES).init(),
                        test_trinity.V),
    "olmo": lambda: (test_olmo_hybrid._model(test_olmo_hybrid.SIZES).init(),
                     test_olmo_hybrid.V),
    "qwen3next": lambda: (test_qwen3_next._model(test_qwen3_next.SIZES)
                          .init(), test_qwen3_next.V),
}

SLOT_STATE = ({"tp", "snapshot", "draft", "prefix_cache"}, "per-slot state")
# what each zoo LM's test file refuses typed, and the phrase it matches
LIMITS = {
    "transformer": (set(), None),
    "granite": SLOT_STATE,
    "falcon": SLOT_STATE,
    "olmo": SLOT_STATE,
    "qwen3next": SLOT_STATE,
    "trinity": ({"int8", "tp", "snapshot", "draft", "prefix_cache"},
                "window page class"),
    "deepseek": ({"int8", "tp", "snapshot", "draft"}, "no head axis"),
}


@pytest.mark.parametrize("name", sorted(LIMITS))
def test_what_a_zoo_net_cannot_serve_is_one_table(name):
    net, vocab = ZOO[name]()
    features, phrase = LIMITS[name]
    srv = GenerationServer(net, vocab, slots=2, page_size=16,
                           prefill_chunk=16, prefix_cache=True)
    try:
        assert set(srv._limits) == features
        assert all(phrase in why for why in srv._limits.values())
        if name == "deepseek":
            assert "latent cache" in srv._limits["int8"]
        assert srv.prefix_cache == ("prefix_cache" not in features)
    finally:
        srv.close()
