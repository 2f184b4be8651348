"""Gated delta-rule linear attention (Yang, Kautz & Hatamizadeh, "Gated
Delta Networks", arXiv:2412.06464), the layer flash-linear-attention
calls ``GatedDeltaNet``: a per-sequence state that is one matrix a head,
erased along each key and then written, under a learned decay.

One set of equations, three forwards::

    [q~ | k~ | v~ | z | a | b] = x W_in   widths Hk dk | Hk dk | H dv | H dv | H | H
    [q~ | k~ | v~] = silu(sum_j w_c[:, j] * [q~ | k~ | v~]_{t-K+1+j})   depthwise,
                                                       causal, no bias
    q = q~ / ||q~|| * dk^-1/2,  k = k~ / ||k~||        L2 over a head's dk
    beta  = s * sigmoid(b)                  s = 2 under allow_neg_eigval, else 1
    alpha = exp(-exp(A_log) * softplus(a + dt_bias))   a decay in (0, 1)
    S_t = alpha_t S_{t-1} (I - beta_t k_t k_t^T) + beta_t v_t k_t^T     S: [dv, dk]
    o_t = S_t q_t
    value head j reads query and key head j // (H / Hk)   (Hk = H: its own)
    out = (RMSNorm_head(o) * silu(z)) W_out  the norm first (one weight of dv,
                                             shared by the heads), then the gate

With ``beta`` up to 2 the transition ``I - beta k k^T`` has an eigenvalue
down to -1: the state can flip its sign along a key.

``n_key_heads`` (``Hk``, 0 = ``n_heads``) gives fewer query and key heads
than value heads: the convolution and its tail span ``Hk`` query and key
heads, and each is repeated for the ``H / Hk`` value heads that read it
(``repeat_interleave``, not tiling) before the recurrence, so the state is
``[H, dv, dk]`` a sequence and every forward below runs as for ``H`` heads.

- the whole sequence and a streamed chunk run the chunked form (DeltaNet's
  WY representation, arXiv:2406.06484 section 3, with the decay): inside a
  chunk of ``chunk_size`` positions one unit-lower-triangular solve gives
  the chunk's keys and values in the form ``S_t = gamma_t S_0 +
  sum_i (gamma_t / gamma_i) u_i k_i^T``, which makes each chunk's end an
  affine map of its start, ``S_C = S_0 T_c + L_c``; a scan over the chunks
  composes those maps and the outputs follow from each chunk's start in
  one product; a streamed chunk starts from the carry instead of zeros;
- one token (``T == 1`` with a carry) runs the recurrence itself.

The streaming carry is per SEQUENCE — ``conv_state`` ``[B, K-1, conv_dim]``
(the convolution's tail, in the network's dtype) and ``recurrent_state``
``[B, H, dv, dk]`` (float32, a state a value head) — which is what
``GenerationServer`` hosts per slot beside the paged KV pool. A ``[B, T]``
mask marks each row's true prefix of the chunk: a padded position neither
decays the state nor writes it, so a right-padded row ends its state at its
last true token, and a row with no true token keeps its state and tail bit
for bit.

Precision: the decay, ``beta``, the convolution's accumulation, the norms
and the state are float32 whatever the network's dtype; the input
projection accumulates in float32 (``z``, ``a`` and ``b`` stay so, ``q``,
``k`` and ``v`` are rounded to the network's dtype before the convolution,
as its tail holds them); every product that takes the state or a
decay-weighted operand runs at ``highest`` precision.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers.base import BaseLayer, CacheSpec
from deeplearning4j_tpu.utils.serde import register_serializable

HI = lax.Precision.HIGHEST
F32 = jnp.float32
#: the published initialisation (flash-linear-attention's GatedDeltaNet):
#: A uniform in A_INIT, dt log-uniform in DT_INIT through the inverse
#: softplus
A_INIT = (1.0, 16.0)
DT_INIT = (1e-3, 1e-1)
#: under the square root of a query's or key's L2 norm
L2_EPS = 1e-6


def l2_normalize(t):
    """``t / sqrt(sum(t^2) + L2_EPS)`` over the last axis, float32."""
    t = t.astype(F32)
    return t * lax.rsqrt(jnp.sum(t * t, axis=-1, keepdims=True) + L2_EPS)


@register_serializable
@dataclass
class GatedDeltaNetLayer(BaseLayer):
    """Gated delta-rule mixer over ``[B, T, n_in]`` -> ``[B, T, n_out]``."""

    n_in: int = 0
    n_out: int = 0
    n_heads: int = 4
    key_dim: int = 16
    value_dim: int = 32
    d_conv: int = 4
    chunk_size: int = 64
    # beta = 2 sigmoid(b): the transition's eigenvalues reach -1
    allow_neg_eigval: bool = False
    norm_eps: float = 1e-6
    # query and key heads (0 = n_heads): value head j reads key head
    # j // (n_heads // n_key_heads)
    n_key_heads: int = 0

    INPUT_KIND = "rnn"
    DEFAULT_ACTIVATION = "identity"

    # ------------------------------------------------------------ config
    @property
    def key_heads(self) -> int:
        return self.n_key_heads or self.n_heads

    @property
    def key_width(self) -> int:
        return self.key_heads * self.key_dim

    @property
    def value_width(self) -> int:
        return self.n_heads * self.value_dim

    @property
    def conv_dim(self) -> int:
        return 2 * self.key_width + self.value_width

    def set_n_in(self, input_type: InputType) -> None:
        if self.n_in == 0:
            self.n_in = input_type.size
        if self.n_out == 0:
            self.n_out = self.n_in

    def validate(self) -> None:
        super().validate()
        if self.d_conv < 2:
            raise ValueError(f"d_conv must be >= 2, got {self.d_conv}")
        if self.chunk_size < 1:
            raise ValueError(f"chunk_size must be >= 1, got "
                             f"{self.chunk_size}")
        if self.n_heads % self.key_heads:
            raise ValueError(f"n_heads={self.n_heads} not divisible by "
                             f"n_key_heads={self.key_heads}")

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.recurrent(self.n_out, input_type.timeseries_length)

    def param_order(self):
        return ["W_in", "conv_w", "dt_bias", "A_log", "norm_w", "W_out"]

    def bias_param_names(self):
        return frozenset(("dt_bias",))

    def regularization(self, params):
        return 0.0      # as the norms: the recurrence's scalars never decay

    def regularization_grad(self, params):
        return {}

    def init_params(self, rng, dtype=jnp.float32):
        k_in, k_conv, k_a, k_dt, k_out = jax.random.split(rng, 5)
        D, H, Vw = self.n_in, self.n_heads, self.value_width
        width = self.conv_dim + Vw + 2 * H
        a = jax.random.uniform(k_a, (H,), F32, *A_INIT)
        lo, hi = (math.log(v) for v in DT_INIT)
        dt = jnp.exp(jax.random.uniform(k_dt, (H,), F32, lo, hi))
        bound = 1.0 / math.sqrt(self.d_conv)
        return {
            "W_in": self._init_w(k_in, (D, width), D, width, dtype),
            "conv_w": jax.random.uniform(k_conv, (self.conv_dim, self.d_conv),
                                         F32, -bound, bound).astype(dtype),
            # inverse softplus: softplus(dt_bias) == dt
            "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype),
            "A_log": jnp.log(a).astype(dtype),
            "norm_w": jnp.ones((self.value_dim,), dtype),
            "W_out": self._init_w(k_out, (Vw, self.n_out), Vw, self.n_out,
                                  dtype),
        }

    def cache_spec(self):
        """Its carry is per sequence, not per page."""
        return CacheSpec("slot_state", keys=("conv_state", "recurrent_state"))

    def init_streaming_carry(self, batch: int, dtype=jnp.float32) -> dict:
        """Zeros: a sequence that has consumed nothing. The convolution's
        tail holds activations and takes the network's dtype; the state is
        float32 always."""
        return {
            "conv_state": jnp.zeros((batch, self.d_conv - 1, self.conv_dim),
                                    dtype),
            "recurrent_state": jnp.zeros(
                (batch, self.n_heads, self.value_dim, self.key_dim), F32),
        }

    # ----------------------------------------------------------- forward
    def forward(self, params, state, x, *, mask=None, train=False, rng=None):
        x = self.apply_input_dropout(x, train=train, rng=rng)
        streaming = "recurrent_state" in state
        B, T, _ = x.shape
        if mask is not None:
            mask = jnp.asarray(mask)
            if mask.shape != (B, T):
                raise ValueError(
                    f"GatedDeltaNetLayer mask must be [batch, chunk] = "
                    f"({B}, {T}), got {mask.shape}")
        if streaming:
            conv_state = state["conv_state"]
            S = state["recurrent_state"]
        else:
            zeros = self.init_streaming_carry(B, x.dtype)
            conv_state, S = zeros["conv_state"], zeros["recurrent_state"]
        with jax.named_scope("gated_delta"):
            out, new_conv, new_S = self._mix(params, x, conv_state, S, mask)
        if mask is not None:
            out = out * mask.astype(out.dtype)[:, :, None]
            # a row with no true token keeps its carry bit for bit
            any_true = jnp.any(mask != 0, axis=1)
            new_conv = jnp.where(any_true[:, None, None], new_conv,
                                 conv_state)
            new_S = jnp.where(any_true[:, None, None, None], new_S, S)
        if not streaming:
            return self.act()(out), state
        new_state = dict(state)
        new_state["conv_state"] = new_conv
        new_state["recurrent_state"] = new_S
        return self.act()(out), new_state

    def _mix(self, params, h, conv_state, S, mask):
        B, T, _ = h.shape
        H, dk, dv = self.n_heads, self.key_dim, self.value_dim
        Hk = self.key_heads
        Cd, Vw = self.conv_dim, self.value_width
        proj = jnp.einsum("btd,dw->btw", h, params["W_in"],
                          preferred_element_type=F32)
        qkv = proj[..., :Cd].astype(h.dtype)
        z = proj[..., Cd:Cd + Vw].reshape(B, T, H, dv)
        a = proj[..., Cd + Vw:Cd + Vw + H]
        b = proj[..., Cd + Vw + H:]
        qkv, conv_state = self._conv(params, qkv, conv_state, mask)
        Kw = self.key_width
        with jax.named_scope("qk_l2norm"):
            q = l2_normalize(qkv[..., :Kw].reshape(B, T, Hk, dk)) \
                * dk ** -0.5
            k = l2_normalize(qkv[..., Kw:2 * Kw].reshape(B, T, Hk, dk))
        if Hk != H:
            q, k = self._share_keys(q, k)
        v = qkv[..., 2 * Kw:].reshape(B, T, H, dv)
        beta = jax.nn.sigmoid(b)
        if self.allow_neg_eigval:
            beta = 2.0 * beta
        # the decay's logarithm, never the log of the decay: finite however
        # close to 0 the decay comes
        log_alpha = -jnp.exp(params["A_log"].astype(F32)) * jax.nn.softplus(
            a + params["dt_bias"].astype(F32))
        if mask is not None:
            # a padded position neither decays the state nor writes it
            m = mask.astype(F32)[:, :, None]
            beta, log_alpha = beta * m, log_alpha * m
        o, S = self._delta(q, k, v, log_alpha, beta, S)
        y = self._gated_norm(o, z, params["norm_w"]).reshape(B, T, Vw)
        out = jnp.einsum("bti,io->bto", y.astype(h.dtype), params["W_out"],
                         preferred_element_type=F32).astype(h.dtype)
        return out, conv_state, S

    def _share_keys(self, q, k):
        """Query and key heads ``[B, T, Hk, dk]`` repeated for the value
        heads that read them: value head ``j`` reads head ``j // (H //
        Hk)``."""
        with jax.named_scope("delta_grouped_keys"):
            G = self.n_heads // self.key_heads
            return jnp.repeat(q, G, axis=2), jnp.repeat(k, G, axis=2)

    def _delta(self, q, k, v, log_alpha, beta, S):
        """The recurrence over ``[B, T, H, .]`` operands from state ``S``:
        ``(o [B, T, H, dv], S at the end)``, float32."""
        if q.shape[1] == 1:
            with jax.named_scope("delta_step"):
                return self._step(q, k, v, log_alpha, beta, S)
        with jax.named_scope("delta_chunked"):
            return self._chunked(q, k, v, log_alpha, beta, S)

    def _gated_norm(self, o, z, w):
        """``RMSNorm_head(o) * silu(z)`` in float32: the mean of squares over
        each head's ``dv`` channels, one weight of ``dv`` for all heads, and
        the gate after the norm."""
        with jax.named_scope("gated_rmsnorm"):
            o = o * lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                              + self.norm_eps) * w.astype(F32)
            return o * jax.nn.silu(z)

    def _conv(self, params, xs, tail, mask):
        """Depthwise causal convolution over [tail | chunk] with no bias,
        accumulated in float32, then silu; and the new tail: the last
        ``K - 1`` inputs up to each row's true length."""
        B, T, _ = xs.shape
        K = self.d_conv
        seq = jnp.concatenate([tail.astype(xs.dtype), xs], axis=1)
        w = params["conv_w"].astype(F32)                     # [Cd, K]
        acc = w[:, 0] * seq[:, 0:T, :].astype(F32)
        for j in range(1, K):
            acc = acc + w[:, j] * seq[:, j:j + T, :].astype(F32)
        if mask is None:
            new_tail = seq[:, T:, :]
        else:
            n_true = jnp.sum((mask != 0).astype(jnp.int32), axis=1)    # [B]
            idx = n_true[:, None] + jnp.arange(K - 1)[None, :]         # [B,K-1]
            new_tail = jnp.take_along_axis(seq, idx[:, :, None], axis=1)
        return jax.nn.silu(acc), new_tail.astype(tail.dtype)

    @staticmethod
    def _step(q, k, v, log_alpha, beta, S):
        """The recurrence itself, one position: elementwise, float32."""
        q0, k0, v0 = q[:, 0], k[:, 0], v[:, 0].astype(F32)  # [B,H,dk|dv]
        alpha = jnp.exp(log_alpha[:, 0])[..., None, None]   # [B,H,1,1]
        b0 = beta[:, 0][..., None]                          # [B,H,1]
        S = alpha * S
        # what the decayed state reads along the key, then the correction
        u = b0 * (v0 - jnp.sum(S * k0[:, :, None, :], axis=-1))
        S = S + u[..., :, None] * k0[:, :, None, :]
        o = jnp.sum(S * q0[:, :, None, :], axis=-1)
        return o[:, None], S

    def _chunked(self, q, k, v, log_alpha, beta, S0):
        """The same recurrence over chunks of ``C`` positions. Within a
        chunk, with ``g`` the running sum of ``log alpha`` and ``G_ti =
        exp(g_t - g_i)`` for ``i <= t``, the state is ``S_t = e^{g_t} S_0 +
        sum_{i<=t} G_ti u_i k_i^T``, where ``(I + A) [W | U~] = [diag(beta
        e^g) K | diag(beta) V]``, ``A_ti = beta_t G_ti k_t . k_i`` below the
        diagonal, and ``u = U~ - W S_0^T``. So the chunk's end is ``S_C =
        S_0 T_c + L_c`` with ``T_c = e^{g_C} I - W^T diag(G_C) K`` and
        ``L_c = U~^T diag(G_C) K``, a scan over chunks gives each chunk's
        start, and its outputs are ``P U~ + (diag(e^g) Q - P W) S_0^T``
        with ``P = G * (Q K^T)``."""
        B, T, H, dk = q.shape
        dv = v.shape[-1]
        C = min(self.chunk_size, T)
        pad = -T % C
        if pad:
            # positions past the end: beta = 0 and no decay, so they change
            # nothing
            padt = lambda a: jnp.pad(  # noqa: E731
                a, [(0, 0), (0, pad)] + [(0, 0)] * (a.ndim - 2))
            q, k, v, log_alpha, beta = (padt(q), padt(k), padt(v),
                                        padt(log_alpha), padt(beta))
        nc = (T + pad) // C

        def heads_first(a):            # [B, T, H, ...] -> [B, nc, H, C, ...]
            a = a.reshape((B, nc, C) + a.shape[2:])
            return jnp.moveaxis(a, 2, 3)

        q, k, v = heads_first(q), heads_first(k), heads_first(v.astype(F32))
        g = jnp.cumsum(heads_first(log_alpha), axis=-1)       # [B,nc,H,C]
        beta = heads_first(beta)
        lower = jnp.tril(jnp.ones((C, C), bool))
        # G_ti = exp(g_t - g_i) at and below the diagonal, 0 above
        G = jnp.exp(jnp.where(lower, g[..., :, None] - g[..., None, :],
                              -jnp.inf))
        kk = jnp.einsum("bnhtd,bnhsd->bnhts", k, k, precision=HI)
        A = jnp.where(jnp.tril(lower, -1),
                      beta[..., :, None] * G * kk, 0.0)
        eye = jnp.eye(C, dtype=F32)
        rhs = jnp.concatenate([(beta * jnp.exp(g))[..., None] * k,
                               beta[..., None] * v], axis=-1)
        wu = lax.linalg.triangular_solve(eye + A, rhs, left_side=True,
                                         lower=True, unit_diagonal=True)
        W, U = wu[..., :dk], wu[..., dk:]          # [.., C, dk], [.., C, dv]
        P = G * jnp.einsum("bnhtd,bnhsd->bnhts", q, k, precision=HI)
        to_end = jnp.exp(g[..., -1:] - g)                     # [B,nc,H,C]
        kd = to_end[..., None] * k
        T_c = jnp.exp(g[..., -1])[..., None, None] * jnp.eye(dk, dtype=F32) \
            - jnp.einsum("bnhcd,bnhce->bnhde", W, kd, precision=HI)
        L_c = jnp.einsum("bnhcv,bnhcd->bnhvd", U, kd, precision=HI)

        def carry_on(S, inp):
            t_c, l_c = inp
            return jnp.einsum("bhvd,bhde->bhve", S, t_c,
                              precision=HI) + l_c, S

        S_end, S_in = lax.scan(carry_on, S0, (jnp.moveaxis(T_c, 1, 0),
                                              jnp.moveaxis(L_c, 1, 0)))
        S_in = jnp.moveaxis(S_in, 0, 1)                   # [B,nc,H,dv,dk]
        q_in = jnp.exp(g)[..., None] * q - jnp.einsum(
            "bnhts,bnhsd->bnhtd", P, W, precision=HI)
        o = jnp.einsum("bnhts,bnhsv->bnhtv", P, U, precision=HI) \
            + jnp.einsum("bnhtd,bnhvd->bnhtv", q_in, S_in, precision=HI)
        o = jnp.moveaxis(o, 3, 2).reshape(B, nc * C, H, dv)[:, :T]
        return o, S_end
