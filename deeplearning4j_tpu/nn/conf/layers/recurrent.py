"""Recurrent layers: LSTM, GravesLSTM (peepholes), bidirectional, SimpleRnn,
RnnOutputLayer.

Reference impl replaced: nn/layers/recurrent/LSTMHelpers.java:172-288 (fwd) and
:368-560 (bwd) — a hand-written per-timestep Java loop shared by LSTM/GravesLSTM/
GravesBidirectionalLSTM. TPU-native design: the input projection for ALL timesteps is
one big [B*T, n_in]x[n_in, 4H] matmul (MXU-friendly), then a `lax.scan` carries
(h, c) with only the [B, H]x[H, 4H] recurrent matmul per step; the backward pass is
jax autodiff through the scan. Masking uses carry-through semantics (masked steps
propagate previous h/c), and TBPTT state carry is exposed via ``initial_state`` /
returned final state (reference: MultiLayerNetwork.java:1364 doTruncatedBPTT,
rnnTimeStep).

Data layout: [batch, time, features] (the reference uses [batch, features, time]).
Gate order in the fused 4H dimension: [i, f, o, g].
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers.base import BaseLayer, FeedForwardLayer
from deeplearning4j_tpu.nn.conf.layers.core import DenseLayer
from deeplearning4j_tpu.ops.activations import get_activation
from deeplearning4j_tpu.ops.losses import get_loss
from deeplearning4j_tpu.utils.serde import register_serializable


@register_serializable
@dataclass
class BaseRecurrentLayer(FeedForwardLayer):
    gate_activation: str = "sigmoid"

    INPUT_KIND = "rnn"
    DEFAULT_ACTIVATION = "tanh"

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.recurrent(self.n_out, input_type.timeseries_length)

    def set_n_in(self, input_type: InputType) -> None:
        if self.n_in == 0:
            self.n_in = input_type.size


def _lstm_scan(x_proj, rw, c0, h0, gate_act, cell_act, mask, peepholes=None):
    """Scan an LSTM over time.

    x_proj: [B, T, 4H] precomputed input projections (+bias)
    rw:     [H, 4H] recurrent weights
    mask:   [B, T] or None
    Returns (outputs [B, T, H], (h_T, c_T)).
    """
    H = rw.shape[0]

    def step(carry, inp):
        h_prev, c_prev = carry
        if mask is not None:
            xt, mt = inp
        else:
            xt = inp
        z = xt + jnp.dot(h_prev, rw)
        zi, zf, zo, zg = jnp.split(z, 4, axis=-1)
        if peepholes is not None:
            pi, pf, po = peepholes
            i = gate_act(zi + pi * c_prev)
            f = gate_act(zf + pf * c_prev)
            g = cell_act(zg)
            c = f * c_prev + i * g
            o = gate_act(zo + po * c)
        else:
            i = gate_act(zi)
            f = gate_act(zf)
            g = cell_act(zg)
            c = f * c_prev + i * g
            o = gate_act(zo)
        h = o * cell_act(c)
        if mask is not None:
            m = mt[:, None]
            h = m * h + (1.0 - m) * h_prev
            c = m * c + (1.0 - m) * c_prev
        return (h, c), h

    xs = jnp.swapaxes(x_proj, 0, 1)  # [T, B, 4H]
    if mask is not None:
        ms = jnp.swapaxes(mask.astype(x_proj.dtype), 0, 1)  # [T, B]
        (hT, cT), outs = lax.scan(step, (h0, c0), (xs, ms))
    else:
        (hT, cT), outs = lax.scan(step, (h0, c0), xs)
    return jnp.swapaxes(outs, 0, 1), (hT, cT)


@register_serializable
@dataclass
class LSTM(BaseRecurrentLayer):
    """Standard (peephole-free) LSTM. Params: W [n_in,4H], RW [H,4H], b [4H]."""

    forget_gate_bias_init: float = 1.0

    def param_order(self):
        return ["W", "RW", "b"]

    def init_params(self, rng, dtype=jnp.float32):
        k1, k2 = jax.random.split(rng)
        H = self.n_out
        W = self._init_w(k1, (self.n_in, 4 * H), self.n_in, H, dtype)
        RW = self._init_w(k2, (H, 4 * H), H, H, dtype)
        b = jnp.zeros((4 * H,), dtype)
        # forget-gate bias block [H:2H] gets forget_gate_bias_init (ref: GravesLSTM
        # forgetGateBiasInit, nn/conf/layers/GravesLSTM.java)
        b = b.at[H:2 * H].set(self.forget_gate_bias_init)
        return {"W": W, "RW": RW, "b": b}

    def _peepholes(self, params):
        return None

    def forward(self, params, state, x, *, mask=None, train=False, rng=None):
        x = self.apply_input_dropout(x, train=train, rng=rng)
        B = x.shape[0]
        H = self.n_out
        x_proj = jnp.dot(x, params["W"]) + params["b"]
        h0 = state.get("h", jnp.zeros((B, H), x.dtype))
        c0 = state.get("c", jnp.zeros((B, H), x.dtype))
        outs, (hT, cT) = _lstm_scan(
            x_proj, params["RW"], c0, h0,
            get_activation(self.gate_activation), self.act(), mask,
            self._peepholes(params))
        new_state = dict(state)
        new_state["h"], new_state["c"] = hT, cT
        return outs, new_state

    def step(self, params, state, x_t):
        """Single-timestep streaming inference (reference: rnnTimeStep)."""
        out, new_state = self.forward(params, state, x_t[:, None, :])
        return out[:, 0, :], new_state


@register_serializable
@dataclass
class GravesLSTM(LSTM):
    """LSTM with peephole connections (Graves 2013 formulation), the reference's
    workhorse RNN (nn/conf/layers/GravesLSTM.java). Adds pi/pf/po peephole params."""

    def param_order(self):
        return ["W", "RW", "b", "pi", "pf", "po"]

    def init_params(self, rng, dtype=jnp.float32):
        params = super().init_params(rng, dtype)
        H = self.n_out
        params["pi"] = jnp.zeros((H,), dtype)
        params["pf"] = jnp.zeros((H,), dtype)
        params["po"] = jnp.zeros((H,), dtype)
        return params

    def _peepholes(self, params):
        return (params["pi"], params["pf"], params["po"])


@register_serializable
@dataclass
class GravesBidirectionalLSTM(GravesLSTM):
    """Bidirectional Graves LSTM; forward+backward direction outputs are ADDED
    (reference: GravesBidirectionalLSTM via LSTMHelpers, combine mode add)."""

    def param_order(self):
        base = super().param_order()
        return [f"f_{k}" for k in base] + [f"b_{k}" for k in base]

    def bias_param_names(self):
        return frozenset({"f_b", "b_b"})

    def init_params(self, rng, dtype=jnp.float32):
        kf, kb = jax.random.split(rng)
        fwd = GravesLSTM.init_params(self, kf, dtype)
        bwd = GravesLSTM.init_params(self, kb, dtype)
        out = {f"f_{k}": v for k, v in fwd.items()}
        out.update({f"b_{k}": v for k, v in bwd.items()})
        return out

    def forward(self, params, state, x, *, mask=None, train=False, rng=None):
        x = self.apply_input_dropout(x, train=train, rng=rng)
        B, H = x.shape[0], self.n_out
        gact, cact = get_activation(self.gate_activation), self.act()

        def run(prefix, xx, mm):
            x_proj = jnp.dot(xx, params[f"{prefix}_W"]) + params[f"{prefix}_b"]
            h0 = jnp.zeros((B, H), x.dtype)
            c0 = jnp.zeros((B, H), x.dtype)
            peep = (params[f"{prefix}_pi"], params[f"{prefix}_pf"], params[f"{prefix}_po"])
            outs, _ = _lstm_scan(x_proj, params[f"{prefix}_RW"], c0, h0, gact, cact,
                                 mm, peep)
            return outs

        fwd = run("f", x, mask)
        x_rev = jnp.flip(x, axis=1)
        mask_rev = jnp.flip(mask, axis=1) if mask is not None else None
        bwd = jnp.flip(run("b", x_rev, mask_rev), axis=1)
        return fwd + bwd, state


@register_serializable
@dataclass
class SimpleRnn(BaseRecurrentLayer):
    """Vanilla RNN: h_t = act(x_t W + h_{t-1} RW + b)."""

    def param_order(self):
        return ["W", "RW", "b"]

    def init_params(self, rng, dtype=jnp.float32):
        k1, k2 = jax.random.split(rng)
        H = self.n_out
        return {"W": self._init_w(k1, (self.n_in, H), self.n_in, H, dtype),
                "RW": self._init_w(k2, (H, H), H, H, dtype),
                "b": jnp.full((H,), self.bias_init, dtype)}

    def forward(self, params, state, x, *, mask=None, train=False, rng=None):
        x = self.apply_input_dropout(x, train=train, rng=rng)
        B, H = x.shape[0], self.n_out
        act = self.act()
        x_proj = jnp.dot(x, params["W"]) + params["b"]
        h0 = state.get("h", jnp.zeros((B, H), x.dtype))

        def step(h_prev, inp):
            if mask is not None:
                xt, mt = inp
            else:
                xt = inp
            h = act(xt + jnp.dot(h_prev, params["RW"]))
            if mask is not None:
                m = mt[:, None]
                h = m * h + (1.0 - m) * h_prev
            return h, h

        xs = jnp.swapaxes(x_proj, 0, 1)
        if mask is not None:
            ms = jnp.swapaxes(mask.astype(x.dtype), 0, 1)
            hT, outs = lax.scan(step, h0, (xs, ms))
        else:
            hT, outs = lax.scan(step, h0, xs)
        new_state = dict(state)
        new_state["h"] = hT
        return jnp.swapaxes(outs, 0, 1), new_state


@register_serializable
@dataclass
class RnnOutputLayer(DenseLayer):
    """Per-timestep dense + loss over [B,T,F] (reference: nn/conf/layers/
    RnnOutputLayer + nn/layers/recurrent/RnnOutputLayer.java). Label mask [B,T]
    excludes masked steps from the loss mean."""

    loss: str = "mcxent"
    # The logits are divided by this before the activation and the loss.
    # Over a trunk narrower than float32 they are accumulated, divided and
    # activated in float32 (an argmax over bfloat16 probabilities of some
    # 50,000 ids ties); over a float32 trunk this is DenseLayer's head.
    logits_divisor: float = 1.0

    INPUT_KIND = "rnn"
    DEFAULT_ACTIVATION = "softmax"

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.recurrent(self.n_out, input_type.timeseries_length)

    def loss_fn(self):
        return get_loss(self.loss)

    def preactivate(self, params, x):
        with jax.named_scope("lm_head"):
            if jnp.dtype(x.dtype).itemsize < 4 and "W_scale" not in params:
                pre = jnp.dot(x, params["W"],
                              preferred_element_type=jnp.float32) \
                    + params["b"].astype(jnp.float32)
            else:
                pre = super().preactivate(params, x)
            return pre if self.logits_divisor == 1.0 \
                else pre / self.logits_divisor

    def compute_loss_per_example(self, params, x, labels, weights=None):
        pre = self.preactivate(params, x)  # [B, T, n_out]
        return self.loss_fn().per_example(labels, pre, self.act(), weights)  # [B, T]


@register_serializable
@dataclass
class LastTimeStep(BaseRecurrentLayer):
    """Select the last (unmasked) timestep: [B,T,F] -> [B,F] (reference:
    rnn/LastTimeStepVertex)."""

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.feed_forward(input_type.size)

    def param_order(self):
        return []

    def init_params(self, rng, dtype=jnp.float32):
        return {}

    def feed_forward_mask(self, mask, current_mask_state: str = "active"):
        return None

    def forward(self, params, state, x, *, mask=None, train=False, rng=None):
        if mask is None:
            return x[:, -1, :], state
        # Index of the LAST nonzero mask entry (correct for non-contiguous masks,
        # reference: rnn/LastTimeStepVertex uses the last set bit, not the count).
        T = x.shape[1]
        t_idx = jnp.arange(T, dtype=jnp.int32)[None, :]
        idx = jnp.max(jnp.where(mask > 0, t_idx, -1), axis=1)
        idx = jnp.maximum(idx, 0)  # all-masked rows fall back to step 0
        out = jnp.take_along_axis(x, idx[:, None, None], axis=1)[:, 0, :]
        return out, state
