"""Mamba-2 mixer (Dao & Gu, "Transformers are SSMs", 2024): a selective
state-space layer whose per-sequence state is a fixed-size matrix, not a
cache that grows with the context.

One set of equations, three forwards::

    [z | xBC | dt] = (h W_in) * m            widths d_inner | d_inner + 2 G N | H
    xBC_t = silu(b_c + sum_j w_c[:, j] * xBC_{t-K+1+j})     depthwise, causal
    x, B, C = split(xBC)                     x: H heads of P, B and C: G groups of N
    dt = softplus(dt + dt_bias),  A = -exp(A_log)            per head
    S_t = exp(dt_t A) S_{t-1} + dt_t * x_t (outer) B_t       S: [H, P, N]
    y_t = S_t C_t + D * x_t
    out = GroupRMSNorm(y * silu(z)) W_out    gate first, then the norm per group

``m`` is all ones unless ``proj_multipliers`` gives one factor for each of
the projection's segments ``z``, ``x``, ``B``, ``C``, ``dt``. The gated norm
takes its mean of squares over each group's ``d_inner / G`` channels (head
``j`` is of group ``j // (H / G)``, as for ``B`` and ``C``), which at one
group is one norm over ``d_inner``; its weight spans ``d_inner`` either way.

- the whole sequence and a streamed chunk run the chunked form (SSD:
  inside a chunk of ``chunk_size`` positions the recurrence is a masked
  matrix product, between chunks a short scan carries the state), a
  streamed chunk starting from the carry instead of from zeros;
- one token (``T == 1`` with a carry) runs the recurrence itself.

The streaming carry is per SEQUENCE — ``conv_state`` ``[B, K-1, conv_dim]``
(the convolution's tail) and ``ssm_state`` ``[B, H, P, N]`` — which is what
``GenerationServer`` hosts per slot beside the paged KV pool. A ``[B, T]``
mask marks each row's true prefix of the chunk: a right-padded row ends
its state at its last true token, and an all-zero row leaves its state as
it was.

Precision: ``dt``, the decay and the state are float32 whatever the
network's dtype; products that take the float32 state or a decay-weighted
operand run at ``highest`` precision (they are a small share of the
layer's operations), the two projections accumulate in float32.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers.base import BaseLayer, CacheSpec
from deeplearning4j_tpu.utils.serde import register_serializable

HI = lax.Precision.HIGHEST
F32 = jnp.float32
#: the published initialisation: A uniform in A_INIT, dt log-uniform in
#: DT_INIT through the inverse softplus, D = 1
A_INIT = (1.0, 16.0)
DT_INIT = (1e-3, 1e-1)


@register_serializable
@dataclass
class Mamba2Layer(BaseLayer):
    """Mamba-2 mixer over ``[B, T, n_in]`` -> ``[B, T, n_out]``."""

    n_in: int = 0
    n_out: int = 0
    n_heads: int = 8
    head_dim: int = 64
    d_state: int = 128
    n_groups: int = 1
    d_conv: int = 4
    chunk_size: int = 256
    norm_eps: float = 1e-5
    # One factor on each segment (z, x, B, C, dt) of the input projection's
    # output, applied to the float32 accumulator; None leaves it as it is.
    proj_multipliers: Optional[tuple] = None

    INPUT_KIND = "rnn"
    DEFAULT_ACTIVATION = "identity"

    # ------------------------------------------------------------ config
    @property
    def d_inner(self) -> int:
        return self.n_heads * self.head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.d_state

    def set_n_in(self, input_type: InputType) -> None:
        if self.n_in == 0:
            self.n_in = input_type.size
        if self.n_out == 0:
            self.n_out = self.n_in

    def validate(self) -> None:
        super().validate()
        if self.n_heads % self.n_groups:
            raise ValueError(f"n_heads={self.n_heads} not divisible by "
                             f"n_groups={self.n_groups}")
        if self.d_conv < 2:
            raise ValueError(f"d_conv must be >= 2, got {self.d_conv}")
        if self.proj_multipliers is not None \
                and len(self.proj_multipliers) != 5:
            raise ValueError("proj_multipliers names one factor for each "
                             "of z, x, B, C and dt, got "
                             f"{self.proj_multipliers!r}")

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.recurrent(self.n_out, input_type.timeseries_length)

    def param_order(self):
        return ["W_in", "conv_w", "conv_b", "dt_bias", "A_log", "D",
                "norm_w", "W_out"]

    def bias_param_names(self):
        return frozenset(("conv_b", "dt_bias"))

    def regularization(self, params):
        return 0.0      # as the norms: the recurrence's scalars never decay

    def regularization_grad(self, params):
        return {}

    def init_params(self, rng, dtype=jnp.float32):
        k_in, k_conv, k_a, k_dt, k_out = jax.random.split(rng, 5)
        D, H, Di, Cd = self.n_in, self.n_heads, self.d_inner, self.conv_dim
        width = 2 * Di + 2 * self.n_groups * self.d_state + H
        a = jax.random.uniform(k_a, (H,), F32, *A_INIT)
        lo, hi = (math.log(v) for v in DT_INIT)
        dt = jnp.exp(jax.random.uniform(k_dt, (H,), F32, lo, hi))
        bound = 1.0 / math.sqrt(self.d_conv)
        return {
            "W_in": self._init_w(k_in, (D, width), D, width, dtype),
            "conv_w": jax.random.uniform(k_conv, (Cd, self.d_conv), F32,
                                         -bound, bound).astype(dtype),
            "conv_b": jnp.zeros((Cd,), dtype),
            # inverse softplus: softplus(dt_bias) == dt
            "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
            "A_log": jnp.log(a).astype(dtype),
            "D": jnp.ones((H,), dtype),
            "norm_w": jnp.ones((Di,), dtype),
            "W_out": self._init_w(k_out, (Di, self.n_out), Di, self.n_out,
                                  dtype),
        }

    def cache_spec(self):
        """Its carry is per sequence, not per page."""
        return CacheSpec("slot_state", keys=("conv_state", "ssm_state"))

    def init_streaming_carry(self, batch: int, dtype=jnp.float32) -> dict:
        """Zeros: a sequence that has consumed nothing. The convolution's
        tail holds activations and takes the network's dtype; the scan
        state is float32 always."""
        return {
            "conv_state": jnp.zeros((batch, self.d_conv - 1, self.conv_dim),
                                    dtype),
            "ssm_state": jnp.zeros((batch, self.n_heads, self.head_dim,
                                    self.d_state), F32),
        }

    # ----------------------------------------------------------- forward
    def forward(self, params, state, x, *, mask=None, train=False, rng=None):
        x = self.apply_input_dropout(x, train=train, rng=rng)
        streaming = "ssm_state" in state
        B, T, _ = x.shape
        if mask is not None:
            mask = jnp.asarray(mask)
            if mask.shape != (B, T):
                raise ValueError(
                    f"Mamba2Layer mask must be [batch, chunk] = ({B}, {T}), "
                    f"got {mask.shape}")
        if streaming:
            conv_state, ssm_state = state["conv_state"], state["ssm_state"]
        else:
            zeros = self.init_streaming_carry(B, x.dtype)
            conv_state, ssm_state = zeros["conv_state"], zeros["ssm_state"]
        with jax.named_scope("mamba2"):
            out, conv_state, ssm_state = self._mix(
                params, x, conv_state, ssm_state, mask)
        if mask is not None:
            out = out * mask.astype(out.dtype)[:, :, None]
        if not streaming:
            return self.act()(out), state
        new_state = dict(state)
        new_state["conv_state"] = conv_state
        new_state["ssm_state"] = ssm_state
        return self.act()(out), new_state

    def _mix(self, params, h, conv_state, ssm_state, mask):
        B, T, _ = h.shape
        H, P, N, G = self.n_heads, self.head_dim, self.d_state, self.n_groups
        Di, Cd = self.d_inner, self.conv_dim
        proj = jnp.einsum("btd,dw->btw", h, params["W_in"],
                          preferred_element_type=F32)
        if self.proj_multipliers is not None:
            proj = proj * np.repeat(
                np.asarray(self.proj_multipliers, np.float32),
                (Di, Di, G * N, G * N, H))
        proj = proj.astype(h.dtype)
        z, xbc, dt = proj[..., :Di], proj[..., Di:Di + Cd], proj[..., Di + Cd:]
        xbc, conv_state = self._conv(params, xbc, conv_state, mask)
        xs = xbc[..., :Di].reshape(B, T, G, H // G, P)
        Bm = xbc[..., Di:Di + G * N].reshape(B, T, G, N)
        Cm = xbc[..., Di + G * N:].reshape(B, T, G, N)
        dt = jax.nn.softplus(dt.astype(F32) + params["dt_bias"].astype(F32))
        if mask is not None:
            # a padded position neither decays the state nor adds to it
            dt = dt * mask.astype(F32)[:, :, None]
        dt = dt.reshape(B, T, G, H // G)
        A = -jnp.exp(params["A_log"].astype(F32)).reshape(G, H // G)
        S = ssm_state.reshape(B, G, H // G, P, N)
        if T == 1:
            y, S = self._step(xs, Bm, Cm, dt, A, S)
        else:
            y, S = self._chunked(xs, Bm, Cm, dt, A, S)
        y = y + params["D"].astype(F32).reshape(G, H // G, 1) * xs.astype(F32)
        y = self._gated_norm(y.reshape(B, T, Di), z, params["norm_w"])
        out = jnp.einsum("bti,io->bto", y.astype(h.dtype), params["W_out"],
                         preferred_element_type=F32).astype(h.dtype)
        return out, conv_state, S.reshape(B, H, P, N)

    def _gated_norm(self, y, z, w):
        """``w * GroupRMSNorm(y * silu(z))`` in float32: the gate first,
        the mean of squares over each group's ``d_inner / G`` channels."""
        y = y * jax.nn.silu(z.astype(F32))
        # one group keeps its expression without the reshapes, so that a
        # one-group net's programs lower to the text they had
        if self.n_groups > 1:
            shape = y.shape
            y = y.reshape(shape[:-1] + (self.n_groups, -1))
            y = y * lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                              + self.norm_eps)
            return y.reshape(shape) * w.astype(F32)
        return y * lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                             + self.norm_eps) * w.astype(F32)

    def _conv(self, params, xbc, tail, mask):
        """Depthwise causal convolution over [tail | chunk], and the new
        tail: the last ``K - 1`` inputs up to each row's true length."""
        B, T, Cd = xbc.shape
        K = self.d_conv
        seq = jnp.concatenate([tail.astype(xbc.dtype), xbc], axis=1)
        w = params["conv_w"].astype(F32)                     # [Cd, K]
        acc = params["conv_b"].astype(F32)
        for j in range(K):
            acc = acc + w[:, j] * seq[:, j:j + T, :].astype(F32)
        out = jax.nn.silu(acc).astype(xbc.dtype)
        if mask is None:
            new_tail = seq[:, T:, :]
        else:
            n_true = jnp.sum(mask.astype(jnp.int32), axis=1)          # [B]
            idx = n_true[:, None] + jnp.arange(K - 1)[None, :]        # [B,K-1]
            new_tail = jnp.take_along_axis(seq, idx[:, :, None], axis=1)
        return out, new_tail.astype(tail.dtype)

    @staticmethod
    def _step(xs, Bm, Cm, dt, A, S):
        """The recurrence itself, one position: all elementwise, float32."""
        x0 = xs[:, 0].astype(F32)                       # [B,G,Hg,P]
        b0 = Bm[:, 0].astype(F32)                       # [B,G,N]
        c0 = Cm[:, 0].astype(F32)
        d0 = dt[:, 0]                                   # [B,G,Hg]
        S = S * jnp.exp(d0 * A)[..., None, None] \
            + (d0[..., None] * x0)[..., None] * b0[:, :, None, None, :]
        y = jnp.sum(S * c0[:, :, None, None, :], axis=-1)
        return y[:, None], S

    def _chunked(self, xs, Bm, Cm, dt, A, S0):
        """SSD: the same recurrence over chunks of ``Q`` positions. Inside
        a chunk, ``y_i = sum_{j<=i} (C_i . B_j) exp(a_j+1..i) dt_j x_j``
        is a masked matrix product; each chunk's contribution to the
        state and the decay across it feed a scan over the chunks."""
        B, T, G, Hg, P = xs.shape
        Q = min(self.chunk_size, T)
        pad = -T % Q
        if pad:
            # positions past the end: dt = 0, so they change nothing
            padt = lambda a: jnp.pad(  # noqa: E731
                a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
            xs, Bm, Cm, dt = padt(xs), padt(Bm), padt(Cm), padt(dt)
        nc = (T + pad) // Q
        ch = lambda a: a.reshape((B, nc, Q) + a.shape[2:])  # noqa: E731
        xs, Bm, Cm, dt = ch(xs), ch(Bm), ch(Cm), ch(dt)
        xf, bf, cf = xs.astype(F32), Bm.astype(F32), Cm.astype(F32)
        acs = jnp.cumsum(dt * A, axis=2)                 # [B,nc,Q,G,Hg]
        # within a chunk; the [Q, Q] planes are kept minor, as the MXU wants
        cb = jnp.einsum("bcign,bcjgn->bcgij", cf, bf, precision=HI)
        acs_t = jnp.moveaxis(acs, 2, -1)                 # [B,nc,G,Hg,Q]
        seg = acs_t[..., :, None] - acs_t[..., None, :]  # [B,nc,G,Hg,i,j]
        decay = jnp.exp(jnp.where(jnp.tril(jnp.ones((Q, Q), bool)), seg,
                                  -jnp.inf))
        m = cb[:, :, :, None] * decay * jnp.moveaxis(dt, 2, -1)[..., None, :]
        y = jnp.einsum("bcghij,bcjghp->bcighp", m, xf, precision=HI)
        # each chunk's own contribution to the state at its end
        to_end = jnp.exp(acs[:, :, -1:, :, :] - acs) * dt   # [B,nc,Q,G,Hg]
        local = jnp.einsum("bcjgn,bcjghp->bcghpn", bf,
                           xf * to_end[..., None], precision=HI)
        across = jnp.exp(acs[:, :, -1])                  # [B,nc,G,Hg]

        def carry_on(S, inp):
            loc, dec = inp
            return S * dec[..., None, None] + loc, S

        S_end, S_in = lax.scan(carry_on, S0,
                               (jnp.moveaxis(local, 1, 0),
                                jnp.moveaxis(across, 1, 0)))
        S_in = jnp.moveaxis(S_in, 0, 1)                  # [B,nc,G,Hg,P,N]
        # what the state a chunk starts from adds to its outputs
        y = y + jnp.einsum("bcign,bcghpn->bcighp", cf, S_in,
                           precision=HI) * jnp.exp(acs)[..., None]
        y = y.reshape(B, nc * Q, G, Hg, P)[:, :T]
        return y, S_end
