"""Normalization layers: BatchNormalization, LocalResponseNormalization,
LayerNormalization, RMSNormalization.

Reference impls: nn/layers/normalization/BatchNormalization.java (+ the cuDNN helper
CudnnBatchNormalizationHelper.java:45) and LocalResponseNormalization.java (+ cuDNN
LRN helper). On TPU both are plain fused elementwise/reduction XLA graphs; running
stats live in the layer *state* pytree (not params) and are updated functionally.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers.base import BaseLayer, Layer
from deeplearning4j_tpu.utils.serde import register_serializable


@dataclass
class _FeatureAffineNorm(BaseLayer):
    """Shared base for feature-axis normalizers with learned gamma/beta:
    nIn inference (channels for conv inputs, size otherwise), shape
    passthrough, and the never-weight-decayed convention (reference:
    BatchNormalization.java:70-76 calcL1/calcL2 -> 0)."""

    n_out: int = 0
    eps: float = 1e-5
    gamma_init: float = 1.0
    beta_init: float = 0.0

    DEFAULT_ACTIVATION = "identity"

    def set_n_in(self, input_type: InputType) -> None:
        if self.n_out == 0:
            if input_type.kind == "convolutional":
                self.n_out = input_type.channels
            else:
                self.n_out = input_type.size

    def output_type(self, input_type: InputType) -> InputType:
        return input_type

    def param_order(self):
        return ["gamma", "beta"]

    def regularization(self, params: dict):
        return 0.0  # gamma/beta never decayed

    def regularization_grad(self, params: dict) -> dict:
        return {}  # mirrors regularization() == 0

    def init_params(self, rng, dtype=jnp.float32):
        return {"gamma": jnp.full((self.n_out,), self.gamma_init, dtype),
                "beta": jnp.full((self.n_out,), self.beta_init, dtype)}


@register_serializable
@dataclass
class BatchNormalization(_FeatureAffineNorm):
    """Batch norm over the feature (last) axis; works for [B,F], [B,T,F], [B,H,W,C].

    Running-stat update matches the reference: global = decay*global + (1-decay)*batch
    (nn/layers/normalization/BatchNormalization.java). gamma/beta trainable unless
    ``lock_gamma_beta``.
    """

    decay: float = 0.9
    lock_gamma_beta: bool = False
    minibatch_stats: bool = True  # use minibatch stats in training (ref: isMinibatch)

    def param_order(self):
        return [] if self.lock_gamma_beta else ["gamma", "beta"]

    def init_params(self, rng, dtype=jnp.float32):
        if self.lock_gamma_beta:
            return {}
        return super().init_params(rng, dtype)

    def init_state(self, dtype=jnp.float32):
        return {"mean": jnp.zeros((self.n_out,), dtype),
                "var": jnp.ones((self.n_out,), dtype)}

    def forward(self, params, state, x, *, mask=None, train=False, rng=None):
        x = self.apply_input_dropout(x, train=train, rng=rng)
        axes = tuple(range(x.ndim - 1))
        if train and self.minibatch_stats:
            mean = jnp.mean(x, axis=axes)
            var = jnp.var(x, axis=axes)
            new_state = {
                "mean": self.decay * state["mean"] + (1.0 - self.decay) * mean,
                "var": self.decay * state["var"] + (1.0 - self.decay) * var,
            }
        else:
            mean, var = state["mean"], state["var"]
            new_state = state
        xhat = (x - mean) * lax.rsqrt(var + self.eps)
        if not self.lock_gamma_beta:
            xhat = xhat * params["gamma"] + params["beta"]
        return self.act()(xhat), new_state


@register_serializable
@dataclass
class LocalResponseNormalization(Layer):
    """Across-channel LRN: x / (k + alpha*sum_window(x^2))^beta over NHWC channels.

    Reference: nn/layers/normalization/LocalResponseNormalization.java with defaults
    k=2, n=5, alpha=1e-4, beta=0.75.
    """

    k: float = 2.0
    n: int = 5
    alpha: float = 1e-4
    beta: float = 0.75

    def forward(self, params, state, x, *, mask=None, train=False, rng=None):
        half = self.n // 2
        sq = x * x
        window = (1,) * (x.ndim - 1) + (self.n,)
        strides = (1,) * x.ndim
        padding = [(0, 0)] * (x.ndim - 1) + [(half, half)]
        ssum = lax.reduce_window(sq, 0.0, lax.add, window, strides, padding)
        denom = (self.k + self.alpha * ssum) ** self.beta
        return x / denom, state


@register_serializable
@dataclass
class LayerNormalization(_FeatureAffineNorm):
    """Per-example normalization over the feature (last) axis with learned
    gamma/beta — no running stats, identical in train and eval.

    Beyond reference parity: the 2017-era reference has no LayerNorm (its
    normalizers are BatchNormalization.java and LRN); this layer exists so
    transformer stacks (SelfAttentionLayer + residual blocks, zoo
    TransformerLM) are buildable first-class. Shares the nIn-inference and
    never-weight-decayed gamma/beta convention with BatchNormalization via
    ``_FeatureAffineNorm``.
    """

    def forward(self, params, state, x, *, mask=None, train=False, rng=None):
        x = self.apply_input_dropout(x, train=train, rng=rng)
        mean = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
        xhat = (x - mean) * lax.rsqrt(var + self.eps)
        return self.act()(xhat * params["gamma"] + params["beta"]), state


@register_serializable
@dataclass
class RMSNormalization(_FeatureAffineNorm):
    """``gamma * x / sqrt(mean(x^2) + eps)`` over the feature (last) axis:
    no mean subtraction, no beta. The statistics are taken in float32
    whatever the network's dtype, and the result returns to it."""

    def param_order(self):
        return ["gamma"]

    def init_params(self, rng, dtype=jnp.float32):
        return {"gamma": jnp.full((self.n_out,), self.gamma_init, dtype)}

    def forward(self, params, state, x, *, mask=None, train=False, rng=None):
        x = self.apply_input_dropout(x, train=train, rng=rng)
        xf = x.astype(jnp.float32)
        xhat = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True)
                              + self.eps)
        out = xhat * params["gamma"].astype(jnp.float32)
        return self.act()(out.astype(x.dtype)), state
