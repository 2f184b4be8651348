"""Core feed-forward layers: Dense, Output, Loss, Activation, Dropout, Embedding,
AutoEncoder.

Reference impls these replace: nn/layers/feedforward/dense/DenseLayer.java (im2col-free
XW+b), nn/layers/BaseOutputLayer.java (loss+gradient), nn/layers/feedforward/embedding/
EmbeddingLayer.java, nn/layers/feedforward/autoencoder/AutoEncoder.java. Backward
passes are jax.grad; dense matmuls hit the MXU directly via jnp.dot.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers.base import BaseLayer, FeedForwardLayer, Layer
from deeplearning4j_tpu.ops.losses import LossFunction, get_loss
from deeplearning4j_tpu.utils.serde import register_serializable


@register_serializable
@dataclass
class DenseLayer(FeedForwardLayer):
    """Fully-connected layer: activation(x @ W + b). W: [n_in, n_out].

    Serves int8-quantized weights when the params tree carries a
    ``W_scale`` sibling (optimize/quantize.py): the dequant is fused
    into the matmul epilogue — ``(x @ W_q.astype(x)) * scale`` — so W
    stays int8 in memory. Presence of the scale is a pytree-STRUCTURE
    property, i.e. part of the jit cache key: f32 and int8 param trees
    each trace their own program, and the f32 path is untouched."""

    QUANT_PARAMS = ("W",)

    def param_order(self):
        return ["W", "b"]

    def init_params(self, rng, dtype=jnp.float32):
        kw, _ = jax.random.split(rng)
        W = self._init_w(kw, (self.n_in, self.n_out), self.n_in, self.n_out, dtype)
        b = jnp.full((self.n_out,), self.bias_init, dtype)
        return {"W": W, "b": b}

    def preactivate(self, params, x):
        scale = params.get("W_scale")
        if scale is None:
            return jnp.dot(x, params["W"]) + params["b"]
        out = jnp.dot(x, params["W"].astype(x.dtype)) * scale
        return out.astype(x.dtype) + params["b"]

    def forward(self, params, state, x, *, mask=None, train=False, rng=None):
        x = self.apply_input_dropout(x, train=train, rng=rng)
        return self.act()(self.preactivate(params, x)), state

    def rows(self, params, ids, dtype):
        """The inference forward of ``one_hot(ids, n_in, dtype)``, read as
        rows of ``W`` by a gather instead of a product that streams the
        whole table: ``act(W[ids] + b)``. A one-hot row's product has one
        non-zero term, so this is the value ``preactivate`` returns, cast
        for cast, int8 ``W_scale`` included."""
        w = params["W"][ids]
        scale = params.get("W_scale")
        if scale is None:
            pre = w.astype(jnp.result_type(dtype, w.dtype)) + params["b"]
        else:
            pre = (w.astype(dtype) * scale).astype(dtype) + params["b"]
        return self.act()(pre)


def gated_unit(h, act, gate_scale: float = 1.0):
    """``act(gate_scale * a) * b`` with ``[a | b] = h``: the nonlinearity
    between the two products of a gated feed-forward, the gate the first
    half. One piece of code for ``GatedFeedForwardLayer`` and for the
    experts of ``MixtureOfExpertsLayer(gated=True)``."""
    half = h.shape[-1] // 2
    a = h[..., :half]
    if gate_scale != 1.0:
        a = a * gate_scale
    return act(a) * h[..., half:]


@register_serializable
@dataclass
class GatedFeedForwardLayer(FeedForwardLayer):
    """Dense gated feed-forward without biases over ``[..., n_in]``::

        [a | b] = x W1                     W1: [n_in, 2 * hidden], gate first
        y = (act(gate_scale * a) * b) W2 * out_scale      W2: [hidden, n_out]

    Gate and up projection are one product (a layout: the two halves of
    ``W1``). Both products accumulate in float32 and both factors are
    applied to the accumulator, whatever the network's dtype; the
    activations between and after take the network's dtype."""

    hidden: int = 0
    activation: str = "silu"
    gate_scale: float = 1.0
    out_scale: float = 1.0

    def finalize(self, g=None) -> None:
        super().finalize(g)
        if self.hidden == 0:
            self.hidden = 4 * self.n_out

    def param_order(self):
        return ["W1", "W2"]

    def bias_param_names(self):
        return frozenset()

    def init_params(self, rng, dtype=jnp.float32):
        k1, k2 = jax.random.split(rng)
        D, H, O = self.n_in, self.hidden, self.n_out
        return {"W1": self._init_w(k1, (D, 2 * H), D, H, dtype),
                "W2": self._init_w(k2, (H, O), H, O, dtype)}

    def forward(self, params, state, x, *, mask=None, train=False, rng=None):
        x = self.apply_input_dropout(x, train=train, rng=rng)
        f32 = jnp.float32
        with jax.named_scope("gated_mlp"):
            h = jnp.einsum("...d,dh->...h", x, params["W1"],
                           preferred_element_type=f32)
            h = gated_unit(h, self.act(), self.gate_scale).astype(x.dtype)
            y = jnp.einsum("...h,ho->...o", h, params["W2"],
                           preferred_element_type=f32)
            if self.out_scale != 1.0:
                y = y * self.out_scale
        return y.astype(x.dtype), state


@register_serializable
@dataclass
class OutputLayer(DenseLayer):
    """Dense + loss head (reference: nn/conf/layers/OutputLayer + BaseOutputLayer).

    The training loss is computed from this layer's *pre-activations* so fused
    softmax/sigmoid cross-entropy forms can be used.
    """

    loss: str = "mcxent"

    DEFAULT_ACTIVATION = "softmax"

    def loss_fn(self) -> LossFunction:
        return get_loss(self.loss)

    def compute_loss_per_example(self, params, x, labels, weights=None):
        pre = self.preactivate(params, x)
        return self.loss_fn().per_example(labels, pre, self.act(), weights)


@register_serializable
@dataclass
class LossLayer(BaseLayer):
    """Loss-only head, no params (reference: nn/conf/layers/LossLayer)."""

    loss: str = "mcxent"

    DEFAULT_ACTIVATION = "identity"

    def loss_fn(self) -> LossFunction:
        return get_loss(self.loss)

    def preactivate(self, params, x):
        return x

    def forward(self, params, state, x, *, mask=None, train=False, rng=None):
        x = self.apply_input_dropout(x, train=train, rng=rng)
        return self.act()(x), state

    def compute_loss_per_example(self, params, x, labels, weights=None):
        return self.loss_fn().per_example(labels, x, self.act(), weights)


@register_serializable
@dataclass
class ActivationLayer(BaseLayer):
    """Parameterless activation (reference: nn/conf/layers/ActivationLayer)."""

    def forward(self, params, state, x, *, mask=None, train=False, rng=None):
        x = self.apply_input_dropout(x, train=train, rng=rng)
        return self.act()(x), state


@register_serializable
@dataclass
class DropoutLayer(BaseLayer):
    """Standalone dropout layer (reference: nn/conf/layers/DropoutLayer)."""

    DEFAULT_ACTIVATION = "identity"

    def forward(self, params, state, x, *, mask=None, train=False, rng=None):
        x = self.apply_input_dropout(x, train=train, rng=rng)
        return self.act()(x), state


@register_serializable
@dataclass
class EmbeddingLayer(FeedForwardLayer):
    """Index lookup: int inputs [B] or [B,1] -> rows of W, plus bias.

    Reference: nn/layers/feedforward/embedding/EmbeddingLayer.java (equivalent to a
    one-hot matmul; implemented as a gather, which XLA lowers to dynamic-slice —
    efficient on TPU for inference; the backward is a scatter-add).
    """

    DEFAULT_ACTIVATION = "identity"

    def param_order(self):
        return ["W", "b"]

    def init_params(self, rng, dtype=jnp.float32):
        kw, _ = jax.random.split(rng)
        W = self._init_w(kw, (self.n_in, self.n_out), self.n_in, self.n_out, dtype)
        b = jnp.full((self.n_out,), self.bias_init, dtype)
        return {"W": W, "b": b}

    def forward(self, params, state, x, *, mask=None, train=False, rng=None):
        idx = x.astype(jnp.int32)
        if idx.ndim == 2 and idx.shape[-1] == 1:
            idx = idx[:, 0]
        out = jnp.take(params["W"], idx, axis=0) + params["b"]
        return self.act()(out), state


@register_serializable
@dataclass
class AutoEncoder(FeedForwardLayer):
    """Denoising autoencoder (reference: nn/conf/layers/AutoEncoder +
    nn/layers/feedforward/autoencoder/AutoEncoder.java).

    Supervised forward acts as the encoder (Dense). Pretraining uses
    ``reconstruction_loss``: corrupt input, encode, decode with tied-ish weights
    (W^T + visible bias), score vs the clean input.
    """

    corruption_level: float = 0.3
    sparsity: float = 0.0
    loss: str = "mse"

    def param_order(self):
        return ["W", "b", "vb"]

    def bias_param_names(self):
        return frozenset({"b", "vb"})

    def init_params(self, rng, dtype=jnp.float32):
        kw, _ = jax.random.split(rng)
        W = self._init_w(kw, (self.n_in, self.n_out), self.n_in, self.n_out, dtype)
        return {"W": W, "b": jnp.full((self.n_out,), self.bias_init, dtype),
                "vb": jnp.zeros((self.n_in,), dtype)}

    def preactivate(self, params, x):
        return jnp.dot(x, params["W"]) + params["b"]

    def forward(self, params, state, x, *, mask=None, train=False, rng=None):
        x = self.apply_input_dropout(x, train=train, rng=rng)
        return self.act()(self.preactivate(params, x)), state

    def reconstruction_loss_per_example(self, params, x, rng=None):
        corrupted = x
        if rng is not None and self.corruption_level > 0:
            keep = 1.0 - self.corruption_level
            m = jax.random.bernoulli(rng, keep, x.shape)
            corrupted = jnp.where(m, x, 0.0)
        hidden = self.act()(jnp.dot(corrupted, params["W"]) + params["b"])
        recon_pre = jnp.dot(hidden, params["W"].T) + params["vb"]
        return get_loss(self.loss).per_example(x, recon_pre, self.act(), None)
