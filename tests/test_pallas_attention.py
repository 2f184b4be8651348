"""Accelerated-kernel (Pallas flash attention) vs stock-XLA parity.

Ports the reference's helper-vs-stock test pattern
(deeplearning4j-cuda/src/test/: cuDNN helper output must equal the pure
ND4J layer output) to the TPU build's one accelerated kernel: the
flash-attention forward (ops/pallas_attention.py) behind
SelfAttentionLayer's ``helper`` switch. On the CPU test mesh the kernel
runs in interpreter mode; no cell of ``benchmarks/`` measures the kernel
yet (PERF.md §7).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.conf.layers.attention import (
    SelfAttentionLayer,
    scaled_dot_attention,
)
from deeplearning4j_tpu.ops.pallas_attention import flash_attention, supports


def _qkv(B=2, H=3, T=256, d=64, seed=0):
    rs = np.random.RandomState(seed)
    return tuple(jnp.asarray(rs.randn(B, H, T, d), jnp.float32)
                 for _ in range(3))


class TestFlashAttentionKernel:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_stock(self, causal):
        q, k, v = _qkv()
        ref = scaled_dot_attention(q, k, v, causal=causal)
        out = flash_attention(q, k, v, causal=causal, block_q=128,
                              block_k=128)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_gradients_match_stock(self, causal):
        q, k, v = _qkv(T=128, d=32)

        def loss_ref(q, k, v):
            return jnp.sum(scaled_dot_attention(q, k, v, causal=causal) ** 2)

        def loss_new(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal=causal) ** 2)

        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        g_new = jax.grad(loss_new, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_ref, g_new):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, rtol=1e-4)

    @pytest.mark.parametrize("causal", [False, True])
    def test_key_mask_matches_stock(self, causal):
        """[B, T] key-mask parity, forward (round-5 mask support)."""
        q, k, v = _qkv(T=256)
        rs = np.random.RandomState(9)
        mask = jnp.asarray(rs.rand(2, 256) > 0.3, jnp.float32)
        # every row keeps at least its first key valid so the softmax
        # row is well-defined in both implementations
        mask = mask.at[:, 0].set(1.0)
        ref = scaled_dot_attention(q, k, v, causal=causal, mask=mask)
        out = flash_attention(q, k, v, causal=causal, mask=mask,
                              block_q=128, block_k=64)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

    @pytest.mark.parametrize("causal", [False, True])
    def test_key_mask_gradients_match_stock(self, causal):
        q, k, v = _qkv(T=128, d=32)
        mask = jnp.ones((2, 128), jnp.float32).at[0, 96:].set(0.0) \
            .at[1, 64:].set(0.0)

        def loss_ref(q, k, v):
            return jnp.sum(scaled_dot_attention(
                q, k, v, causal=causal, mask=mask) ** 2)

        def loss_new(q, k, v):
            return jnp.sum(flash_attention(
                q, k, v, causal=causal, mask=mask, block_q=64,
                block_k=64) ** 2)

        g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
        g_new = jax.grad(loss_new, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_ref, g_new):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, rtol=1e-4)

    def test_nonzero_is_valid_mask_semantics(self):
        """Stock treats mask.astype(bool): ANY nonzero value is a valid
        key. The kernel must match — negative validity markers included."""
        q, k, v = _qkv(T=128, d=32)
        mask = jnp.where(jnp.asarray(
            np.random.RandomState(4).rand(2, 128) > 0.4), -1.0, 0.0) \
            .at[:, 0].set(-1.0)
        ref = scaled_dot_attention(q, k, v, mask=mask)
        out = flash_attention(q, k, v, mask=mask, block_q=64, block_k=64)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

    def test_wrong_mask_shape_raises(self):
        """A transposed / wrong-sized mask must fail loudly, not be
        silently reshaped into wrong attention."""
        q, k, v = _qkv(T=128, d=32)
        with pytest.raises(ValueError, match="key mask shape"):
            flash_attention(q, k, v, mask=jnp.ones((128, 2)))
        with pytest.raises(ValueError, match="key mask shape"):
            flash_attention(q, k, v, mask=jnp.ones((2, 64)))

    def test_uneven_q_k_blocks_causal(self):
        # block_q != block_k exercises the diagonal-block arithmetic
        q, k, v = _qkv(T=256)
        ref = scaled_dot_attention(q, k, v, causal=True)
        out = flash_attention(q, k, v, causal=True, block_q=128, block_k=64)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

    def test_blocks_clamp_to_short_sequences(self):
        q, k, v = _qkv(T=64)
        ref = scaled_dot_attention(q, k, v)
        out = flash_attention(q, k, v)  # default blocks 512 -> clamped
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)

    def test_supports_gating(self):
        ok = dict(mask=None, backend="tpu")
        assert supports((2, 3, 256, 64), **ok)
        assert supports((2, 3, 250, 64), **ok)  # clamps to one block
        # larger than a block but not divisible -> stock fallback
        assert not supports((2, 3, 600, 64), **ok)
        # [B, T] key masks route to the kernel since round 5; any other
        # mask shape still falls back to stock
        assert supports((2, 3, 256, 64), mask=np.ones((2, 256)),
                        backend="tpu")
        assert not supports((2, 3, 256, 64), mask=np.ones((2, 3, 256)),
                            backend="tpu")
        assert not supports((2, 3, 256, 64), mask=np.ones((2, 128)),
                            backend="tpu")
        # f32-accumulating kernel must decline float64 networks, but
        # narrower dtypes only gain precision through it
        assert not supports((2, 3, 256, 64), dtype=jnp.float64, **ok)
        assert supports((2, 3, 256, 64), dtype=jnp.bfloat16, **ok)
        # off-TPU the kernel would run in interpret mode: decline
        assert not supports((2, 3, 256, 64), mask=None, backend="cpu")
        # full K/V live in VMEM per program: decline past the ceiling
        # (empirical on v5e: 4096x128 compiles, 8192x128 does not)
        assert supports((2, 3, 4096, 128), **ok)
        assert supports((2, 3, 8192, 64), **ok)
        assert not supports((2, 3, 8192, 128), **ok)
        assert not supports((2, 3, 16384, 128), **ok)


class TestSelfAttentionHelperSwitch:
    def _layer(self, helper, causal=False):
        lyr = SelfAttentionLayer(n_in=32, n_out=32, n_heads=4,
                                 causal=causal, helper=helper,
                                 bias_init=0.0)
        params = lyr.init_params(jax.random.PRNGKey(0))
        return lyr, params

    @pytest.mark.parametrize("causal", [False, True])
    def test_pallas_equals_stock(self, causal):
        rs = np.random.RandomState(1)
        x = jnp.asarray(rs.randn(2, 128, 32), jnp.float32)
        l_stock, p = self._layer("stock", causal)
        l_pallas, _ = self._layer("pallas", causal)
        out_s, _ = l_stock.forward(p, {}, x)
        out_p, _ = l_pallas.forward(p, {}, x)
        np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_s),
                                   atol=1e-5, rtol=1e-5)

    def test_auto_falls_back_on_mask(self):
        rs = np.random.RandomState(2)
        x = jnp.asarray(rs.randn(2, 64, 32), jnp.float32)
        mask = jnp.ones((2, 64), jnp.float32).at[:, 40:].set(0.0)
        l_auto, p = self._layer("auto")
        l_stock, _ = self._layer("stock")
        out_a, _ = l_auto.forward(p, {}, x, mask=mask)
        out_s, _ = l_stock.forward(p, {}, x, mask=mask)
        np.testing.assert_allclose(np.asarray(out_a), np.asarray(out_s),
                                   atol=1e-6)

    def test_pallas_with_mask_equals_stock(self):
        """Round 5: masked workloads route through the kernel — the layer
        output must equal the stock path's, masked rows included."""
        rs = np.random.RandomState(3)
        x = jnp.asarray(rs.randn(2, 128, 32), jnp.float32)
        mask = jnp.ones((2, 128), jnp.float32).at[0, 100:].set(0.0) \
            .at[1, 64:].set(0.0)
        l_pallas, p = self._layer("pallas")
        l_stock, _ = self._layer("stock")
        out_p, _ = l_pallas.forward(p, {}, x, mask=mask)
        out_s, _ = l_stock.forward(p, {}, x, mask=mask)
        np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_s),
                                   atol=1e-5, rtol=1e-5)
