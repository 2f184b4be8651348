"""Ring attention (sequence parallel) + tensor parallel equivalence tests.

Core invariant (the distributed==single-device contract of the test suite,
applied to the new parallelism modes): sharded execution must reproduce the
single-device math to float tolerance.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from deeplearning4j_tpu.nn.conf.layers.attention import (
    SelfAttentionLayer,
    scaled_dot_attention,
)
from deeplearning4j_tpu.parallel.sequence import (
    ring_attention,
    sequence_parallel_self_attention,
)
from deeplearning4j_tpu.parallel.tensor import (
    dp_tp_mesh,
    tp_mlp_train_step,
)


def _seq_mesh(n=8):
    return Mesh(np.asarray(jax.devices()[:n]), ("seq",))


class TestRingAttention:
    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_single_device(self, causal):
        rs = np.random.RandomState(0)
        B, H, T, d = 2, 3, 32, 8  # T = 32 over 8 devices -> blocks of 4
        q = jnp.asarray(rs.randn(B, H, T, d), jnp.float32)
        k = jnp.asarray(rs.randn(B, H, T, d), jnp.float32)
        v = jnp.asarray(rs.randn(B, H, T, d), jnp.float32)
        expected = scaled_dot_attention(q, k, v, causal=causal)
        got = ring_attention(q, k, v, mesh=_seq_mesh(), axis="seq",
                             causal=causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                                   atol=2e-5, rtol=2e-5)

    def test_gradients_flow_through_ring(self):
        """The ring is differentiable: grads wrt q/k/v match the dense
        attention's grads (ppermute transposes to the reverse rotation)."""
        rs = np.random.RandomState(1)
        B, H, T, d = 1, 2, 16, 4
        q = jnp.asarray(rs.randn(B, H, T, d), jnp.float32)
        k = jnp.asarray(rs.randn(B, H, T, d), jnp.float32)
        v = jnp.asarray(rs.randn(B, H, T, d), jnp.float32)
        mesh = _seq_mesh()

        def ring_loss(q, k, v):
            return jnp.sum(ring_attention(q, k, v, mesh=mesh, axis="seq",
                                          causal=True) ** 2)

        def dense_loss(q, k, v):
            return jnp.sum(scaled_dot_attention(q, k, v, causal=True) ** 2)

        # jitted, as a train step runs them: eagerly every primitive of the
        # shard_map backward is its own 8-device compile
        g_ring = jax.jit(jax.grad(ring_loss, argnums=(0, 1, 2)))(q, k, v)
        g_dense = jax.jit(jax.grad(dense_loss, argnums=(0, 1, 2)))(q, k, v)
        for gr, gd in zip(g_ring, g_dense):
            np.testing.assert_allclose(np.asarray(gr), np.asarray(gd),
                                       atol=3e-5, rtol=3e-5)

    def test_layer_wrapper_matches_layer_forward(self):
        layer = SelfAttentionLayer(n_in=12, n_out=12, n_heads=3, causal=True)
        layer.finalize(None)
        params = layer.init_params(jax.random.PRNGKey(0), jnp.float32)
        rs = np.random.RandomState(2)
        x = jnp.asarray(rs.randn(2, 24, 12), jnp.float32)
        expected, _ = layer.forward(params, {}, x)
        got = sequence_parallel_self_attention(layer, params, x,
                                               mesh=_seq_mesh())
        np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                                   atol=2e-5, rtol=2e-5)


class TestSelfAttentionLayer:
    def test_gradcheck_in_network(self):
        from deeplearning4j_tpu.gradientcheck import check_gradients
        from deeplearning4j_tpu.nn.conf.builders import \
            NeuralNetConfiguration
        from deeplearning4j_tpu.nn.conf.inputs import InputType
        from deeplearning4j_tpu.nn.conf.layers.recurrent import \
            RnnOutputLayer
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
        from deeplearning4j_tpu.nn.updater import Sgd

        conf = (NeuralNetConfiguration.builder()
                .seed(5).updater(Sgd(learning_rate=0.1)).dtype("float64")
                .list(SelfAttentionLayer(n_out=8, n_heads=2, causal=True),
                      RnnOutputLayer(n_out=3, activation="softmax",
                                     loss="mcxent"))
                .set_input_type(InputType.recurrent(4))
                .build())
        net = MultiLayerNetwork(conf).init()
        rs = np.random.RandomState(3)
        x = rs.randn(2, 6, 4)
        y = np.eye(3)[rs.randint(0, 3, (2, 6))]
        assert check_gradients(net, x, y, eps=1e-6, max_rel_error=1e-5,
                               subset=60)

    def test_key_mask_excludes_padded_positions(self):
        layer = SelfAttentionLayer(n_in=4, n_out=4, n_heads=1)
        layer.finalize(None)
        params = layer.init_params(jax.random.PRNGKey(1), jnp.float32)
        rs = np.random.RandomState(4)
        x = jnp.asarray(rs.randn(1, 5, 4), jnp.float32)
        mask = jnp.asarray([[1, 1, 1, 0, 0]], jnp.float32)
        out_masked, _ = layer.forward(params, {}, x, mask=mask)
        # perturbing a masked position must not change unmasked outputs
        x2 = x.at[0, 4].set(99.0)
        out2, _ = layer.forward(params, {}, x2, mask=mask)
        np.testing.assert_allclose(np.asarray(out_masked[0, :3]),
                                   np.asarray(out2[0, :3]), atol=1e-6)


class TestTensorParallel:
    def test_dp_tp_step_matches_single_device(self):
        """4-device (data=2, model=2) sharded MLP training step == the same
        step computed densely on one device."""
        rs = np.random.RandomState(5)
        B, I, Hd, O = 8, 6, 12, 4
        x = rs.randn(B, I).astype(np.float32)
        y = rs.randn(B, O).astype(np.float32)
        params = {
            "w1": rs.randn(I, Hd).astype(np.float32) * 0.3,
            "b1": np.zeros(Hd, np.float32),
            "w2": rs.randn(Hd, O).astype(np.float32) * 0.3,
            "b2": np.zeros(O, np.float32),
        }

        def loss_fn(out, y):
            return (out - y) ** 2

        mesh = dp_tp_mesh(2, 2)
        step = tp_mlp_train_step(mesh, jax.nn.tanh, loss_fn, lr=0.1)
        new_params, loss = step(
            {k: jnp.asarray(v) for k, v in params.items()},
            jnp.asarray(x), jnp.asarray(y))

        # dense single-device reference
        def dense_loss(p):
            h = jax.nn.tanh(x @ p["w1"] + p["b1"])
            out = h @ p["w2"] + p["b2"]
            return jnp.mean((out - y) ** 2)

        ref_loss, ref_g = jax.value_and_grad(dense_loss)(
            {k: jnp.asarray(v) for k, v in params.items()})
        assert abs(float(loss) - float(ref_loss)) < 1e-5
        for k in params:
            ref_new = np.asarray(params[k]) - 0.1 * np.asarray(ref_g[k])
            np.testing.assert_allclose(np.asarray(new_params[k]), ref_new,
                                       atol=1e-5, rtol=1e-5,
                                       err_msg=f"param {k}")

    def test_tp_trains_to_lower_loss(self):
        rs = np.random.RandomState(6)
        x = rs.randn(16, 5).astype(np.float32)
        y = (x @ rs.randn(5, 2).astype(np.float32))
        params = {"w1": rs.randn(5, 8).astype(np.float32) * 0.3,
                  "b1": np.zeros(8, np.float32),
                  "w2": rs.randn(8, 2).astype(np.float32) * 0.3,
                  "b2": np.zeros(2, np.float32)}
        params = {k: jnp.asarray(v) for k, v in params.items()}
        mesh = dp_tp_mesh(4, 2)
        step = tp_mlp_train_step(mesh, jax.nn.tanh,
                                 lambda o, t: (o - t) ** 2, lr=0.05)
        params, first = step(params, jnp.asarray(x), jnp.asarray(y))
        for _ in range(60):
            params, loss = step(params, jnp.asarray(x), jnp.asarray(y))
        assert float(loss) < float(first) * 0.5


class TestUlyssesAttention:
    """All-to-all sequence parallelism (Jacobs et al. 2023): the second SP
    implementation, head-sharded compute between two all_to_alls."""

    @pytest.mark.parametrize("causal", [False, True])
    def test_matches_single_device(self, causal):
        from deeplearning4j_tpu.parallel.sequence import ulysses_attention

        rs = np.random.RandomState(2)
        B, H, T, d = 2, 8, 32, 4  # H = 8 over 8 devices -> 1 head each
        q = jnp.asarray(rs.randn(B, H, T, d), jnp.float32)
        k = jnp.asarray(rs.randn(B, H, T, d), jnp.float32)
        v = jnp.asarray(rs.randn(B, H, T, d), jnp.float32)
        expected = scaled_dot_attention(q, k, v, causal=causal)
        got = ulysses_attention(q, k, v, mesh=_seq_mesh(), axis="seq",
                                causal=causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                                   atol=2e-5, rtol=2e-5)

    def test_gradients_match_dense(self):
        from deeplearning4j_tpu.parallel.sequence import ulysses_attention

        rs = np.random.RandomState(3)
        B, H, T, d = 1, 8, 16, 4
        q = jnp.asarray(rs.randn(B, H, T, d), jnp.float32)
        k = jnp.asarray(rs.randn(B, H, T, d), jnp.float32)
        v = jnp.asarray(rs.randn(B, H, T, d), jnp.float32)
        mesh = _seq_mesh()

        def u_loss(q, k, v):
            return jnp.sum(ulysses_attention(q, k, v, mesh=mesh,
                                             axis="seq", causal=True) ** 2)

        def dense_loss(q, k, v):
            return jnp.sum(scaled_dot_attention(q, k, v, causal=True) ** 2)

        gu = jax.jit(jax.grad(u_loss, argnums=(0, 1, 2)))(q, k, v)
        gd = jax.jit(jax.grad(dense_loss, argnums=(0, 1, 2)))(q, k, v)
        for a, b in zip(gu, gd):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-5, rtol=2e-5)

    def test_indivisible_heads_rejected(self):
        from deeplearning4j_tpu.parallel.sequence import ulysses_attention

        rs = np.random.RandomState(4)
        q = jnp.asarray(rs.randn(1, 3, 16, 4), jnp.float32)  # 3 heads, 8 dev
        with pytest.raises(ValueError, match="divisible"):
            ulysses_attention(q, q, q, mesh=_seq_mesh(), axis="seq")

    def test_layer_wrapper_ulysses_impl(self):
        from deeplearning4j_tpu.nn.conf.layers.attention import (
            SelfAttentionLayer,
        )
        from deeplearning4j_tpu.parallel.sequence import (
            sequence_parallel_self_attention,
        )

        rs = np.random.RandomState(5)
        layer = SelfAttentionLayer(n_in=16, n_out=16, n_heads=8,
                                   causal=True, activation="identity")
        layer.finalize(None)
        params = layer.init_params(jax.random.PRNGKey(0))
        x = jnp.asarray(rs.randn(2, 32, 16), jnp.float32)
        expected, _ = layer.forward(params, {}, x, train=False)
        got = sequence_parallel_self_attention(layer, params, x,
                                               mesh=_seq_mesh(),
                                               impl="ulysses")
        np.testing.assert_allclose(np.asarray(got), np.asarray(expected),
                                   atol=2e-5, rtol=2e-5)
