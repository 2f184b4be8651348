"""Attention layers.

The 0.8.x reference has no attention (SURVEY §5: long-context = TBPTT only);
later DL4J releases added SelfAttentionLayer/RecurrentAttentionLayer — these
provide that capability, TPU-first: one fused softmax(QK^T/sqrt(d))V program
whose matmuls are MXU-shaped [B*H, T, d], with optional causal masking and
time-mask support. The sequence-parallel (ring) execution of the same math
lives in parallel/sequence.py.
"""

from __future__ import annotations

import contextlib
import os
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers.base import BaseLayer, Layer
from deeplearning4j_tpu.utils.serde import register_serializable

NEG_INF = -1e30

#: Key under which a serving program's carry tells an attention layer what
#: the server resolved for it: ``(backend, mesh)``, the paged-read backend
#: by name and the tensor-parallel ``jax.sharding.Mesh`` or ``None``. Two
#: Python values fixed at trace time, put into the carry inside the traced
#: function and taken out by the layer: never an operand, never in a state
#: the layer returns.
SERVED_BY = "served_by"


def _debug_paged_overflow(pos, T, NP, ps):
    """Debug-mode paged-capacity assert (DL4J_TPU_PAGED_DEBUG=1). In
    production the check lives in the CALLER's page-accounting admission
    (GenerationServer.submit/adopt know the budget before dispatch); the
    per-dispatch ``int(jnp.max(pos))`` here is a device→host sync the hot
    decode loop must not pay, so it is opt-in only."""
    if os.environ.get("DL4J_TPU_PAGED_DEBUG") != "1":
        return
    if isinstance(pos, jax.core.Tracer):
        return
    hi = int(jnp.max(pos))
    if hi + T > NP * ps:
        raise ValueError(
            f"paged KV overflow: position {hi} + {T} new tokens > "
            f"block table capacity {NP} pages x {ps} = {NP * ps}")


#: an int8 pool's scale planes, ``[pages, H, page_size]``; its value planes
#: hold a token's heads side by side, ``[pages, page_size, H * d]``
_SCALE_PLANES = ("kscales", "vscales")


def _write_chunk(kp, vp, ksp, vsp, k, v, ksc, vsc, pg, off):
    """Scatter a fresh chunk's keys and values (``[B, H, T, d]``; an int8
    pool's scales ``[B, H, T]``) into row ``off[b, t]`` of page
    ``pg[b, t]``: a token's heads are one ``[H * d]`` row of a page."""
    B, _, T, _ = k.shape
    kp = kp.at[pg, off, :].set(
        k.astype(kp.dtype).transpose(0, 2, 1, 3).reshape(B, T, -1))
    vp = vp.at[pg, off, :].set(
        v.astype(vp.dtype).transpose(0, 2, 1, 3).reshape(B, T, -1))
    if ksp is not None:
        ksp = ksp.at[pg, :, off].set(ksc.transpose(0, 2, 1))
        vsp = vsp.at[pg, :, off].set(vsc.transpose(0, 2, 1))
    return kp, vp, ksp, vsp


def chunk_mask(mask, B, T):
    """A streamed or paged chunk's ``[B, T]`` validity mask, or ``None``.
    Any other shape is an error: silently dropping it would let padded
    garbage attend as real keys."""
    if mask is None:
        return None
    mask = jnp.asarray(mask)
    if mask.shape != (B, T):
        raise ValueError(
            f"streaming attention mask must be [batch, chunk] = "
            f"({B}, {T}), got {mask.shape}; per-feature or "
            "flattened masks cannot be applied to the KV cache")
    return mask


def scaled_dot_attention(q, k, v, *, causal: bool = False, mask=None):
    """softmax(q k^T / sqrt(d)) v over [..., T, d] arrays.

    mask: [B, T] validity of the KEY positions (broadcast over heads).
    """
    d = q.shape[-1]
    logits = jnp.einsum("...qd,...kd->...qk", q, k) / jnp.sqrt(
        jnp.asarray(d, q.dtype))
    T_q, T_k = logits.shape[-2], logits.shape[-1]
    if causal:
        causal_mask = jnp.tril(jnp.ones((T_q, T_k), bool))
        logits = jnp.where(causal_mask, logits, NEG_INF)
    if mask is not None:
        key_mask = mask.astype(bool)[:, None, None, :]  # [B,1,1,Tk]
        logits = jnp.where(key_mask, logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("...qk,...kd->...qd", w, v)


def grouped_attention(q, k, v, valid, scale, barrier=False):
    """Attention with fewer key/value heads than query heads and a stated
    score scale: query head ``j`` reads key/value head ``j // (H // Hkv)``,
    nothing is repeated. q ``[B, H, T, d]``, k and v ``[B, Hkv, S, d]``,
    ``valid`` a bool plane broadcastable to ``[B, 1, T, S]`` (causal and
    key masks already combined). Scores and the softmax are float32
    whatever the inputs' dtype; the context returns in q's. ``barrier``
    holds the softmax's row maximum behind ``lax.optimization_barrier``:
    fused with its own broadcast, the TPU compiler rewrites the pair as a
    ``reduce-window`` as wide as the row (``latent_attention._weights``;
    ROADMAP S15). The same sums either way; a layer asks for it
    (``softmax_barrier``), so the nets that do not keep their programs."""
    B, H, T, d = q.shape
    Hkv = k.shape[1]
    with jax.named_scope("gqa_attention"):
        qg = q.reshape(B, Hkv, H // Hkv, T, d)
        s = jnp.einsum("bkgtd,bksd->bkgts", qg, k,
                       preferred_element_type=jnp.float32) * scale
        s = jnp.where(valid[:, :, None], s, NEG_INF)
        if barrier:
            e = jnp.exp(s - jax.lax.optimization_barrier(
                jnp.max(s, axis=-1, keepdims=True)))
            w = (e / jnp.sum(e, axis=-1, keepdims=True)).astype(v.dtype)
        else:
            w = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        o = jnp.einsum("bkgts,bksd->bkgtd", w, v,
                       preferred_element_type=jnp.float32)
    return o.astype(q.dtype).reshape(B, H, T, d)


def rotary_frequencies(dim: int, theta: float, yarn=None) -> np.ndarray:
    """The ``dim / 2`` rotary frequencies ``f_i = theta ** (-2 i / dim)`` as
    float64 constants. ``yarn`` (``factor``, ``original_positions``,
    ``beta_fast``, ``beta_slow``) stretches them as YaRN publishes it: with
    ``corr(b) = dim ln(original_positions / (2 pi b)) / (2 ln theta)``,
    ``low = floor(corr(beta_fast))``, ``high = ceil(corr(beta_slow))`` and
    the ramp ``r_i = clip((i - low) / (high - low), 0, 1)``, frequency ``i``
    becomes ``f_i (1 - r_i) + (f_i / factor) r_i``: the fast channels keep
    their wavelength, the slow ones are interpolated by ``factor``."""
    half = dim // 2
    freq = np.power(float(theta), -np.arange(half) * 2.0 / dim)
    if yarn is None:
        return freq
    factor, original, beta_fast, beta_slow = yarn

    def corr(b):
        return dim * np.log(original / (b * 2.0 * np.pi)) \
            / (2.0 * np.log(float(theta)))

    low = max(int(np.floor(corr(beta_fast))), 0)
    high = min(int(np.ceil(corr(beta_slow))), dim - 1)
    ramp = np.clip((np.arange(half) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return freq * (1.0 - ramp) + freq / float(factor) * ramp


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's magnitude factor ``0.1 mscale ln(factor) + 1`` (1 where the
    positions are not stretched)."""
    if factor <= 1.0 or not mscale:
        return 1.0
    return 0.1 * float(mscale) * float(np.log(factor)) + 1.0


def rotate_pairs(t, positions, freq, *, adjacent: bool = False):
    """Rotary positions on ``t`` ``[..., T, d]`` at the absolute
    ``positions`` ``[B, T]`` (``t``'s leading axes are ``[B, ...]``): pair
    ``i`` of ``d / 2`` turns by the angle ``p freq[i]``. The half-split
    pairing takes ``(t_i, t_{i + d/2})``. ``adjacent`` takes ``(t_{2i},
    t_{2i+1})`` and leaves the result de-interleaved, first members in the
    first half (what a model that de-interleaves before a half-split
    rotation computes; queries and keys are laid out alike, so scores do
    not see the order). Angles, ``cos``/``sin`` and the rotation are
    float32 whatever ``t``'s dtype, and the result is float32: the caller
    rounds it once."""
    half = t.shape[-1] // 2
    lead = (slice(None),) + (None,) * (t.ndim - 3) + (slice(None), None)
    ang = positions.astype(jnp.float32)[lead] \
        * jnp.asarray(freq, jnp.float32)                  # [B, .., T, d/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    t = t.astype(jnp.float32)
    if adjacent:
        a, b = t[..., 0::2], t[..., 1::2]
    else:
        a, b = t[..., :half], t[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def rotate_half_pairs(t, positions, theta: float):
    """Rotary positions on ``t`` ``[B, H, T, d]`` at the absolute
    ``positions`` ``[B, T]``: with ``f_i = theta ** (-2 i / d)`` for ``i <
    d / 2``, the pair ``(t_i, t_{i + d/2})`` turns by the angle ``p f_i``
    (the half-split pairing of the published ``rotate_half``, not the
    interleaved one). Frequencies are float64 constants rounded once;
    angles, ``cos``/``sin`` and the rotation are float32 whatever ``t``'s
    dtype, and the result is float32: the caller rounds it once."""
    return rotate_pairs(t, positions,
                        rotary_frequencies(t.shape[-1], theta))


@register_serializable
@dataclass
class SelfAttentionLayer(BaseLayer):
    """Multi-head self-attention over [B, T, F] (post-reference-vintage DL4J
    SelfAttentionLayer; here with projection output Wo and optional causal
    masking for autoregressive stacks).

    Beyond the classic layer, each off at its default: **grouped heads**
    (``n_kv_heads``; every cache and pool holds the key/value heads, none
    repeated), a stated ``score_scale``, a **head size of its own**
    (``head_dim``: the heads then span ``n_heads * head_dim`` channels
    inside a model of width ``n_out``; ``Wq`` is ``[n_in, n_heads *
    head_dim]`` and ``Wo`` ``[n_heads * head_dim, n_out]``), a factor on the
    keys (``key_scale``) and **rotary positions** (``rope_theta``): queries
    and keys are turned by each token's absolute position in the
    contiguous, the streaming and the paged forward alike (a streamed or
    paged chunk starts at its carry's ``cache_pos``, a right-padded row's
    true tokens stand at their true positions), and keys enter the cache
    or the pool already rotated, so a read never rotates. A **sliding
    window** (``window``: key ``j`` is visible to query ``i`` iff ``0 <=
    i - j < window``) on every forward alike: the paged read gathers only
    the pages a chunk's window reaches, ``window + chunk + page_size``
    tokens a row at most, from the row's first live page (the pages
    behind it are the caller's to free: ``PAGED_WINDOW``). An **RMS norm
    on queries and keys** per head (``qk_norm``: one weight vector each
    over the head's channels, shared by the heads, applied before the
    rotation, so keys enter the pool normed). A **sigmoid gate** on the
    heads' concatenated output before ``Wo`` (``gated``: ``Wg`` is
    ``[n_in, n_heads * head_dim]``)."""

    n_in: int = 0
    n_out: int = 0
    n_heads: int = 1
    causal: bool = False
    project_input: bool = True
    # KV-cache capacity for streaming decode (rnn_time_step); caches are
    # allocated lazily per stream, so this costs nothing until streaming
    max_cache: int = 512
    # Accelerated-kernel switch (the AlgoMode / cuDNN-helper analog,
    # reference: ConvolutionLayer.java:68-79 reflective helper load):
    # "auto" uses the Pallas flash kernel whenever it supports the case
    # (incl. [B,T] key masks since round 5; T divisible by its block),
    # "pallas" forces it, "stock" forces the XLA softmax(QK^T)V path.
    helper: str = "auto"
    # Grouped-query attention: key/value heads (0 = n_heads, the classic
    # multi-head layer); query head j reads key/value head
    # j // (n_heads // n_kv_heads), and every cache and pool holds
    # n_kv_heads. ``score_scale`` (0 = 1/sqrt(head size)) is the factor on
    # q k^T. Either one set routes every path through
    # ``grouped_attention`` (float32 scores and softmax; XLA paged read).
    n_kv_heads: int = 0
    score_scale: float = 0.0
    has_bias: bool = True
    # Size of one head (0 = n_out // n_heads, the classic layer).
    head_dim: int = 0
    # Rotary positions: the base of the frequencies (0 = no rotation).
    rope_theta: float = 0.0
    # Factor on the keys, applied before the rotation (0 = none).
    key_scale: float = 0.0
    # Sliding window: keys a query sees, its own among them (0 = all).
    window: int = 0
    # RMS norm over each head's channels of queries and keys.
    qk_norm: bool = False
    qk_norm_eps: float = 1e-5
    # Sigmoid gate on the heads' output before Wo.
    gated: bool = False
    # grouped_attention's ``barrier`` (ROADMAP S15): off, the programs of
    # the nets that came before it stand as they were
    softmax_barrier: bool = False

    INPUT_KIND = "rnn"
    DEFAULT_ACTIVATION = "identity"
    #: projection weights eligible for int8 per-output-channel
    #: quantization (optimize/quantize.py); dequant is fused into the
    #: einsum epilogue by _proj
    QUANT_PARAMS = ("Wq", "Wk", "Wv", "Wo")
    #: pool plane -> (its dense view's name, the view's token axis): what
    #: ``init_paged_carry`` may return and ``init_streaming_carry`` names.
    #: The pool's own order is this layer's alone: ``paged_views`` makes
    #: views of pages, ``paged_settle`` page rows of a view's column
    PAGED_PLANES = {"kpages": ("kcache", 2), "vpages": ("vcache", 2),
                    "kscales": ("kscale", 2), "vscales": ("vscale", 2)}
    #: pool plane -> the axis that holds its heads (a tensor-parallel pool
    #: is split along it): a value plane's lanes, a scale plane's rows
    PAGED_HEAD_AXIS = {"kpages": 2, "vpages": 2, "kscales": 1, "vscales": 1}

    def set_n_in(self, input_type: InputType) -> None:
        if self.n_in == 0:
            self.n_in = input_type.size
        if self.n_out == 0:
            self.n_out = self.n_in

    def validate(self) -> None:
        super().validate()
        if not self.head_dim and self.n_out % self.n_heads:
            raise ValueError(f"n_out={self.n_out} not divisible by "
                             f"n_heads={self.n_heads}")
        if self.rope_theta and self.d_head % 2:
            raise ValueError(f"rotary positions pair the two halves of a "
                             f"head: head size {self.d_head} is odd")
        if self.n_heads % self.kv_heads:
            raise ValueError(f"n_heads={self.n_heads} not divisible by "
                             f"n_kv_heads={self.kv_heads}")

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def d_head(self) -> int:
        return self.head_dim or self.n_out // self.n_heads

    @property
    def plain(self) -> bool:
        """The classic layer: as many key/value heads as query heads and
        the 1/sqrt(d) scale, every earlier key visible — what the Pallas
        kernels were written for."""
        return (self.kv_heads == self.n_heads and not self.score_scale
                and not self.window)

    @property
    def PAGED_WINDOW(self):
        """How many of a row's latest tokens this layer's paged read can
        still see (``None``: all of them): a server may free the pages
        behind them."""
        return self.window or None

    def _scale(self) -> float:
        return self.score_scale or 1.0 / self.d_head ** 0.5

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.recurrent(self.n_out, input_type.timeseries_length)

    def param_order(self):
        return (["Wq", "Wk", "Wv", "Wo"] + (["b"] if self.has_bias else [])
                + (["Wg"] if self.gated else [])
                + (["q_norm", "k_norm"] if self.qk_norm else []))

    def init_params(self, rng, dtype=jnp.float32):
        kq, kk, kv, ko = jax.random.split(rng, 4)
        D, O = self.n_in, self.n_out
        A = self.d_head * self.n_heads
        KV = self.d_head * self.kv_heads
        out = {
            "Wq": self._init_w(kq, (D, A), D, A, dtype),
            "Wk": self._init_w(kk, (D, KV), D, KV, dtype),
            "Wv": self._init_w(kv, (D, KV), D, KV, dtype),
            "Wo": self._init_w(ko, (A, O), A, O, dtype),
        }
        if self.has_bias:
            out["b"] = jnp.full((O,), self.bias_init, dtype)
        if self.gated:
            out["Wg"] = self._init_w(jax.random.fold_in(rng, 4), (D, A), D,
                                     A, dtype)
        if self.qk_norm:
            out["q_norm"] = jnp.ones((self.d_head,), dtype)
            out["k_norm"] = jnp.ones((self.d_head,), dtype)
        return out

    def _split_heads(self, x):
        B, T, O = x.shape
        d = self.d_head
        return x.reshape(B, T, O // d, d).transpose(0, 2, 1, 3)  # [B,H,T,d]

    def _qkv(self, params, x, start=None):
        """The three projections as heads. With ``key_scale``,
        ``rope_theta`` or ``qk_norm`` set, queries and keys are accumulated
        in float32, normed per head, the keys scaled, both rotated at the
        chunk's absolute positions (``start``, a scalar or one per row,
        plus the column; ``None`` starts at 0), and rounded to the
        activations' dtype once."""
        if not (self.rope_theta or self.key_scale or self.qk_norm):
            q = self._split_heads(self._proj(params, x, "Wq"))
            k = self._split_heads(self._proj(params, x, "Wk"))
            return q, k, self._split_heads(self._proj(params, x, "Wv"))
        f32 = jnp.float32
        q = self._split_heads(self._proj(params, x, "Wq", accumulate=f32))
        k = self._split_heads(self._proj(params, x, "Wk", accumulate=f32))
        v = self._split_heads(self._proj(params, x, "Wv"))
        if self.qk_norm:
            with jax.named_scope("qk_norm"):
                q = self._head_norm(q, params["q_norm"])
                k = self._head_norm(k, params["k_norm"])
        if self.key_scale:
            k = k * self.key_scale
        if self.rope_theta:
            positions = jnp.arange(x.shape[1])[None, :]
            if start is not None:
                positions = positions + jnp.reshape(start, (-1, 1))
            with jax.named_scope("rope" if self.plain
                                 else "gqa_attention/rope"):
                q = rotate_half_pairs(q, positions, self.rope_theta)
                k = rotate_half_pairs(k, positions, self.rope_theta)
        return q.astype(x.dtype), k.astype(x.dtype), v

    def _head_norm(self, t, weight):
        """RMS norm over the channels of each head of float32 ``t``."""
        return t * jax.lax.rsqrt(
            jnp.mean(t * t, axis=-1, keepdims=True) + self.qk_norm_eps) \
            * weight.astype(jnp.float32)

    def _project_out(self, params, o, x=None):
        """``Wo`` on the merged heads ``o`` ``[B, T, H * d]``; a gated
        layer first multiplies them by ``sigmoid(x Wg)``, in float32."""
        if self.gated:
            with jax.named_scope("attn_gate"):
                g = self._proj(params, x, "Wg", accumulate=jnp.float32)
                o = (o.astype(jnp.float32) * jax.nn.sigmoid(g)).astype(
                    o.dtype)
        out = self._proj(params, o, "Wo", "bto,op->btp")
        return out + params["b"] if self.has_bias else out

    def _sees(self, q_pos, key_pos):
        """Whether the query at ``q_pos`` sees the key at ``key_pos``
        (arrays that broadcast): causal, and inside the window."""
        seen = key_pos <= q_pos
        return seen & (q_pos - key_pos < self.window) if self.window \
            else seen

    def _proj(self, params, x, name, spec="btf,fo->bto", accumulate=None):
        """One projection matmul, serving int8-quantized weights when
        the params tree carries a ``<name>_scale`` sibling: the
        per-output-channel dequant is fused into the einsum epilogue
        (``(x @ W_q.astype(x)) * scale``), which XLA folds — weights
        stay int8 in memory. The scale's presence is pytree structure,
        so f32 and quantized trees each trace their own program and the
        f32 math is untouched."""
        w = params[name]
        scale = params.get(name + "_scale")
        if accumulate is not None:
            # the product as its accumulator holds it, for what follows in
            # that precision (a factor, a rotation) before one rounding
            out = jnp.einsum(spec, x, w.astype(x.dtype),
                             preferred_element_type=accumulate)
            return out if scale is None else out * scale
        if scale is None:
            return jnp.einsum(spec, x, w)
        return (jnp.einsum(spec, x, w.astype(x.dtype)) * scale).astype(
            x.dtype)

    def _attend(self, q, k, v, mask):
        from deeplearning4j_tpu.ops import pallas_attention as pa

        if self.helper not in ("auto", "pallas", "stock"):
            raise ValueError(f"Unknown helper '{self.helper}'")
        if not self.plain:
            T = q.shape[2]
            if not self.causal:
                valid = jnp.ones((1, 1, T, T), bool)
            elif self.window:
                at = jnp.arange(T)
                valid = self._sees(at[:, None], at[None, :])[None, None]
            else:
                valid = jnp.tril(jnp.ones((T, T), bool))[None, None]
            if mask is not None:
                valid = valid & mask.astype(bool)[:, None, None, :]
            return grouped_attention(q, k, v, valid, self._scale(),
                                     self.softmax_barrier)
        use_pallas = self.helper == "pallas" or (
            self.helper == "auto"
            and pa.supports(q.shape, mask=mask, dtype=q.dtype))
        if use_pallas:
            return pa.flash_attention(q, k, v, causal=self.causal,
                                      mask=mask)
        return scaled_dot_attention(q, k, v, causal=self.causal, mask=mask)

    def forward(self, params, state, x, *, mask=None, train=False, rng=None):
        if "kpages" in state:
            return self._paged_forward(params, state, x, mask=mask)
        if "kcache" in state:
            return self._streaming_forward(params, state, x, mask=mask)
        x = self.apply_input_dropout(x, train=train, rng=rng)
        q, k, v = self._qkv(params, x)
        o = self._attend(q, k, v, mask)
        B, H, T, d = o.shape
        o = o.transpose(0, 2, 1, 3).reshape(B, T, H * d)
        out = self._project_out(params, o, x)
        if mask is not None:
            out = out * mask.astype(out.dtype)[:, :, None]
        return self.act()(out), state

    @staticmethod
    def _served(state):
        """``(state without SERVED_BY, backend, mesh)``: what a serving
        program decided for this call, ``None`` for each when the layer
        is driven directly (it then picks its own backend, on one chip).
        The entry is taken out here so that it never returns in a new
        state."""
        state = dict(state)
        backend, mesh = state.pop(SERVED_BY, (None, None))
        return state, backend, mesh

    # ------------------------------------------------- streaming decode
    def init_paged_carry(self, pages: int, page_size: int,
                         dtype=jnp.float32, kv_dtype=None) -> dict:
        """KV cache as a POOL of fixed-size pages (vLLM-style) instead of
        one contiguous [B, max_cache] strip per stream. The pool is shared
        by every slot of a serving batch: a ``[B, n_pages]`` block table
        (passed per call in ``state``) maps each row to its page list, so
        HBM cost is proportional to tokens actually resident — and two
        rows whose block tables name the same page share it (copy-on-write
        is the CALLER's job: this layer never checks refcounts, it just
        reads/writes where the table points). Only causal layers stream;
        non-causal layers return no carry (same rule as
        ``init_streaming_carry``).

        A value plane is ``[pages, page_size, H * d]``: a token's heads
        side by side in one row, the order the page write's scatter runs
        in place and the read kernel takes as it lies (see
        ``paged_attention``'s module docstring).

        ``kv_dtype="int8"`` stores pages int8 with per-page-row f32
        scales (``kscales``/``vscales``, ``[pages, H, page_size]``: one
        scale per token per head): writes quantize, gathers dequantize —
        ~4x less HBM per resident token at a bounded accuracy delta."""
        if not self.causal:
            return {}
        H = self.kv_heads
        row = H * self.d_head
        if kv_dtype == "int8":
            return {
                "kpages": jnp.zeros((pages, page_size, row), jnp.int8),
                "vpages": jnp.zeros((pages, page_size, row), jnp.int8),
                "kscales": jnp.zeros((pages, H, page_size), jnp.float32),
                "vscales": jnp.zeros((pages, H, page_size), jnp.float32),
            }
        if kv_dtype is not None:
            raise ValueError(f"unsupported kv_dtype {kv_dtype!r} "
                             "(None or 'int8')")
        return {
            "kpages": jnp.zeros((pages, page_size, row), dtype),
            "vpages": jnp.zeros((pages, page_size, row), dtype),
        }

    def paged_views(self, planes: dict, bt, first=None, count=None) -> dict:
        """The pages each row of ``bt`` names, as the dense caches
        ``init_streaming_carry`` would hold: ``{view: [S, H, NP * ps, d]}``
        (scales ``[S, H, NP * ps]``). With ``first`` (``[S]``) and
        ``count``, only each row's ``count`` logical pages from ``first``
        on: a window layer's view, whose column 0 is the row's position
        ``first * ps`` (``view_base`` in the streaming carry)."""
        from deeplearning4j_tpu.nn.conf.layers import paged_attention as ppa

        if first is not None:
            bt = jnp.take_along_axis(bt, jnp.minimum(
                first[:, None] + jnp.arange(count)[None, :],
                bt.shape[1] - 1), axis=1)
        views = {}
        for k, a in planes.items():
            rows = a[bt]
            views[self.PAGED_PLANES[k][0]] = ppa.dense_scales(rows) \
                if k in _SCALE_PLANES else ppa.dense_values(rows, self.d_head)
        return views

    def paged_settle(self, planes: dict, cols: dict, pg, off) -> dict:
        """``planes`` with a written column of each plane's dense view
        (``cols[plane]``: ``[S, H, d]``, scales ``[S, H]``) in row
        ``off[s]`` of page ``pg[s]``."""
        return {k: a.at[pg, :, off].set(cols[k]) if k in _SCALE_PLANES
                else a.at[pg, off, :].set(cols[k].reshape(pg.shape[0], -1))
                for k, a in planes.items()}

    def paged_to_wire(self, stacks: dict) -> dict:
        """A fetched ``[NP, ...]`` stack of each plane (host arrays), from
        the pool's order to the snapshot wire format's canonical
        ``[NP, H, ps, d]``; the scale planes are the same in both."""
        return {k: a if k in _SCALE_PLANES else a.reshape(
            a.shape[:2] + (-1, self.d_head)).transpose(0, 2, 1, 3)
            for k, a in stacks.items()}

    def paged_from_wire(self, stacks: dict) -> dict:
        """The inverse: canonical ``[NP, H, ps, d]`` stacks in the pool's
        order, ``[NP, ps, H * d]``."""
        return {k: a if k in _SCALE_PLANES else a.transpose(
            0, 2, 1, 3).reshape(a.shape[0], a.shape[2], -1)
            for k, a in stacks.items()}

    def paged_token_bytes(self, dtype, kv_dtype=None) -> int:
        """Bytes a resident token costs in this layer's pool planes: a key
        and a value per key/value head, int8 with a float32 scale each
        under ``kv_dtype="int8"``."""
        if kv_dtype == "int8":
            return 2 * self.kv_heads * (self.d_head + 4)
        return 2 * self.kv_heads * self.d_head * jnp.dtype(dtype).itemsize

    def init_streaming_carry(self, batch: int, dtype=jnp.float32,
                             kv_dtype=None) -> dict:
        """KV cache for incremental decode (the transformer analog of the
        LSTM's h/c streaming state behind rnnTimeStep): keys/values of
        already-consumed positions stay cached, so each new token costs
        one attention row instead of a full O(T^2) re-forward. Only
        causal layers can stream — a non-causal layer would need future
        tokens — so they return no carry (per-chunk attention then
        applies, matching the pre-cache behavior).

        ``kv_dtype="int8"`` is the dense-strip analog of the int8 paged
        pool: int8 caches plus per-token-per-head f32 ``kscale``/
        ``vscale`` strips."""
        if not self.causal:
            return {}
        H = self.kv_heads
        d = self.d_head
        if kv_dtype == "int8":
            return {
                "kcache": jnp.zeros((batch, H, self.max_cache, d), jnp.int8),
                "vcache": jnp.zeros((batch, H, self.max_cache, d), jnp.int8),
                "kscale": jnp.zeros((batch, H, self.max_cache), jnp.float32),
                "vscale": jnp.zeros((batch, H, self.max_cache), jnp.float32),
                "cache_pos": jnp.zeros((), jnp.int32),
            }
        if kv_dtype is not None:
            raise ValueError(f"unsupported kv_dtype {kv_dtype!r} "
                             "(None or 'int8')")
        return {
            "kcache": jnp.zeros((batch, H, self.max_cache, d), dtype),
            "vcache": jnp.zeros((batch, H, self.max_cache, d), dtype),
            "cache_pos": jnp.zeros((), jnp.int32),
        }

    @staticmethod
    def _quantize_kv(t):
        """Absmax per-(row, head, token) int8 of a fresh KV chunk
        ``[B, H, T, d]`` -> (int8 values, f32 scales ``[B, H, T]``).
        All-zero rows get scale 0 and reconstruct as exact zeros."""
        m = jnp.max(jnp.abs(t), axis=-1)
        scale = (m / 127.0).astype(jnp.float32)
        safe = jnp.where(scale > 0, scale, 1.0).astype(t.dtype)
        q = jnp.clip(jnp.round(t / safe[..., None]), -127, 127).astype(
            jnp.int8)
        return q, scale

    def _streaming_forward(self, params, state, x, mask=None):
        """Incremental decode over the KV cache.

        ``cache_pos`` may be a scalar (one shared stream position — the
        classic rnn_time_step path) or a ``[B]`` vector of PER-ROW
        positions (slot-pooled serving: each batch row is an independent
        sequence at its own depth, so attention is masked per-row by that
        row's true length and the new chunk is scattered at per-row
        offsets).

        ``mask``: optional ``[B, T]`` validity of the NEW chunk's
        positions. Masked positions contribute no attention keys and
        their outputs are zeroed (matching the non-streaming path), but
        they still occupy cache columns — ``cache_pos`` advances by the
        full chunk length; callers that right-pad (bucketed prefill) must
        set their own true-length watermark afterwards. Any other mask
        shape is an error: silently dropping it would let padded garbage
        attend as real keys.
        """
        B, T, _ = x.shape
        state, _, mesh = self._served(state)
        kc, vc, pos = state["kcache"], state["vcache"], state["cache_pos"]
        Tmax = kc.shape[2]
        per_row = getattr(pos, "ndim", 0) == 1
        # a window class's dense view starts at the row's first live page:
        # column c of row b holds position ``view_base[b] + c``
        base = state.get("view_base")
        at = pos if base is None else pos - base
        if not isinstance(at, jax.core.Tracer):
            hi = int(jnp.max(at)) if per_row else int(at)
            if hi + T > Tmax:
                raise ValueError(
                    f"KV cache overflow: position {hi} + {T} new tokens "
                    f"> max_cache {Tmax}; raise SelfAttentionLayer.max_cache "
                    "or rnn_clear_previous_state() to start a new stream")
        mask = chunk_mask(mask, B, T)
        q, k, v = self._qkv(params, x, pos)
        # int8 KV mode is keyed by the carry STRUCTURE (scale strips
        # present), so it is part of the jit cache key — never a retrace
        # hazard. Fresh chunks quantize on write; attention reads the
        # dequantized view (XLA fuses the widen into the QK^T matmul).
        quant = "kscale" in state
        ks = vs = ksc = vsc = None
        if quant:
            ks, vs = state["kscale"], state["vscale"]
            k, ksc = self._quantize_kv(k)
            v, vsc = self._quantize_kv(v)
        if per_row:
            # write each row's chunk at its own offset as a vmapped
            # dynamic-update-slice: unlike an advanced-index scatter
            # (which XLA CPU lowers to an element loop) this aliases
            # in-place inside donated decode scans — the slot-pooled
            # decode step pays this write 2x per layer per token
            z = jnp.zeros((), pos.dtype)
            kc = jax.vmap(
                lambda c, u, p: jax.lax.dynamic_update_slice(
                    c, u, (z, p, z)))(kc, k.astype(kc.dtype), at)
            vc = jax.vmap(
                lambda c, u, p: jax.lax.dynamic_update_slice(
                    c, u, (z, p, z)))(vc, v.astype(vc.dtype), at)
            if quant:
                ks = jax.vmap(
                    lambda c, u, p: jax.lax.dynamic_update_slice(
                        c, u, (z, p)))(ks, ksc, at)
                vs = jax.vmap(
                    lambda c, u, p: jax.lax.dynamic_update_slice(
                        c, u, (z, p)))(vs, vsc, at)
        else:
            z = jnp.zeros((), jnp.int32)  # index dtypes must all match pos's
            kc = jax.lax.dynamic_update_slice(kc, k.astype(kc.dtype),
                                              (z, z, pos, z))
            vc = jax.lax.dynamic_update_slice(vc, v.astype(vc.dtype),
                                              (z, z, pos, z))
            if quant:
                ks = jax.lax.dynamic_update_slice(ks, ksc, (z, z, pos))
                vs = jax.lax.dynamic_update_slice(vs, vsc, (z, z, pos))
        if quant:
            kd = kc.astype(q.dtype) * ks[..., None].astype(q.dtype)
            vd = vc.astype(q.dtype) * vs[..., None].astype(q.dtype)
        else:
            kd, vd = kc, vc
        d = q.shape[-1]
        if self.plain:
            logits = jnp.einsum("bhtd,bhkd->bhtk", q, kd) / jnp.sqrt(
                jnp.asarray(d, q.dtype))
        col = jnp.arange(Tmax)[None, None, None, :]
        row = jnp.arange(T)[None, None, :, None]
        p4 = at.reshape(-1, 1, 1, 1) if per_row else at
        valid = self._sees(p4 + row, col)
        if self.plain:
            logits = jnp.where(valid, logits, NEG_INF)
        if mask is not None:
            # key validity over the cache axis: columns belonging to this
            # chunk take the chunk mask; everything older stays valid
            colv = jnp.arange(Tmax)[None, :]
            rel = colv - (at[:, None] if per_row else at)       # [B?,Tmax]
            rel = jnp.broadcast_to(rel, (B, Tmax))
            chunk_valid = jnp.take_along_axis(
                mask.astype(bool), jnp.clip(rel, 0, T - 1), axis=1)
            key_valid = jnp.where((rel >= 0) & (rel < T), chunk_valid, True)
            if self.plain:
                logits = jnp.where(key_valid[:, None, None, :], logits,
                                   NEG_INF)
            else:
                valid = valid & key_valid[:, None, None, :]
        if self.plain:
            o = jnp.einsum("bhtk,bhkd->bhtd",
                           jax.nn.softmax(logits, axis=-1), vd)
        else:
            with jax.named_scope("swa_attention") if self.window \
                    else contextlib.nullcontext():
                o = grouped_attention(q, kd, vd, valid, self._scale(),
                                      self.softmax_barrier)
        if mesh is not None:
            # tensor-parallel decode gathers the paged pool into dense
            # views sharded on the head axis; GSPMD keeps every op so
            # far per-head (no cross-shard reduction). Pin the contexts
            # replicated HERE — an exact all-gather of disjoint head
            # slices — so the head-merging reshape below can never turn
            # the Wo contraction into a partial-sum all-reduce (float
            # reordering would break tp-vs-single-chip bit-exactness).
            from jax.sharding import NamedSharding, PartitionSpec

            o = jax.lax.with_sharding_constraint(
                o, NamedSharding(mesh, PartitionSpec()))
        o = o.transpose(0, 2, 1, 3).reshape(B, T, -1)
        out = self._project_out(params, o, x)
        if mask is not None:
            out = out * mask.astype(out.dtype)[:, :, None]
        new_state = dict(state)
        new_state["kcache"] = kc
        new_state["vcache"] = vc
        if quant:
            new_state["kscale"] = ks
            new_state["vscale"] = vs
        new_state["cache_pos"] = pos + T
        return self.act()(out), new_state

    def _paged_forward(self, params, state, x, mask=None):
        """Incremental decode over a paged KV pool (see init_paged_carry).

        ``state`` carries, besides the pool itself:
          - ``block_table``: ``[B, n_pages]`` int32, row b's i-th logical
            page lives in pool page ``block_table[b, i]``. Rows may share
            pages (prefix sharing); the caller guarantees copy-on-write,
            i.e. a page a row WRITES into this call is owned by that row
            alone (or is a designated garbage page).
          - ``cache_pos``: ``[B]`` per-row stream positions, exactly as in
            the per-row ``_streaming_forward`` path.
          - ``SERVED_BY`` (optional): the read backend and the mesh of the
            serving program that makes this call.

        The attend over the resident pages routes through the
        PagedAttentionHelper seam (nn/conf/layers/paged_attention.py):
        the XLA backend attends over the gathered
        ``[B, H, n_pages*page_size, d]`` view — the dense per-row path
        verbatim, so outputs are bit-identical to a contiguous cache of
        capacity ``n_pages * page_size`` holding the same tokens — and
        the Pallas backend reads each row's live pages in place via the
        block table, parity-pinned against the XLA path to float32
        rounding. The chunk WRITE
        below never enters the seam: every backend sees the same
        scatter, garbage-page routing and COW contract.

        Capacity is the caller's page-accounting admission to enforce
        (GenerationServer budgets pages before dispatch); set
        ``DL4J_TPU_PAGED_DEBUG=1`` to re-enable the per-dispatch
        host-sync overflow assert when debugging a new caller.
        """
        B, T, _ = x.shape
        state, backend, mesh = self._served(state)
        kp, vp = state["kpages"], state["vpages"]
        bt = state["block_table"]
        pos = state["cache_pos"]
        if getattr(pos, "ndim", 0) != 1:
            raise ValueError("paged attention requires per-row [B] "
                             f"cache_pos, got shape {getattr(pos, 'shape', ())}")
        ps = kp.shape[1]
        NP = bt.shape[1]
        _debug_paged_overflow(pos, T, NP, ps)
        mask = chunk_mask(mask, B, T)
        q, k, v = self._qkv(params, x, pos)
        # int8 pool (scale planes present — a structure check, so part
        # of the jit key): quantize the fresh chunk on write, with its
        # per-token-per-head scales scattered through the SAME page
        # routing (masked columns land on garbage page 0 for values and
        # scales alike)
        quant = "kscales" in state
        ksp = vsp = ksc = vsc = None
        if quant:
            ksp, vsp = state["kscales"], state["vscales"]
            k, ksc = self._quantize_kv(k)
            v, vsc = self._quantize_kv(v)
        # scatter the chunk at per-row offsets, routed through the block
        # table: logical position p of row b lands in pool page
        # bt[b, p // ps] at offset p % ps, a whole [H * d] row of it.
        t_abs = pos[:, None] + jnp.arange(T)[None, :]            # [B,T]
        pg = jnp.take_along_axis(bt, jnp.minimum(t_abs // ps, NP - 1),
                                 axis=1)                         # [B,T]
        off = t_abs % ps
        if mask is not None:
            # masked (right-padding) columns write pool page 0 — the
            # caller-reserved garbage sink — so padded prefill chunks
            # never dirty real pages and a row needs page backing for
            # its true tokens only
            pg = jnp.where(mask.astype(bool), pg, 0)
        if mesh is not None and not self.plain:
            raise NotImplementedError(
                "tensor-parallel paged attention splits query heads; with "
                "n_kv_heads < n_heads, a stated score scale or a window it "
                "is not built yet")
        if quant and self.window:
            raise NotImplementedError(
                "a window layer's paged read has no int8 form yet")
        from deeplearning4j_tpu.nn.conf.layers import paged_attention as ppa

        if backend is None:
            # no server chose: trace-time static (the geometry is shapes)
            backend = ppa.resolve_paged_backend(
                "auto", page_size=ps, head_dim=self.d_head,
                n_pages=NP, chunk=T, quant=quant, plain=self.plain)
        if mesh is not None:
            kp, vp, ksp, vsp, o = self._sharded_write_attend(
                backend, mesh, q, k, v, ksc, vsc, kp, vp, ksp, vsp, bt,
                pos, pg, off, mask, quant)
        else:
            kp, vp, ksp, vsp = _write_chunk(kp, vp, ksp, vsp, k, v, ksc,
                                            vsc, pg, off)
            # read side: attend over the resident pages through the
            # selected helper backend; a window layer reads its own few
            if self.window:
                o = self._window_read(q, kp, vp, bt, pos, mask)
            else:
                o = ppa.paged_attend(
                    backend, q, kp, vp, bt, pos, mask=mask, kscales=ksp,
                    vscales=vsp, scale=None if self.plain else self._scale(),
                    barrier=self.softmax_barrier)
        o = o.transpose(0, 2, 1, 3).reshape(B, T, -1)
        out = self._project_out(params, o, x)
        if mask is not None:
            out = out * mask.astype(out.dtype)[:, :, None]
        new_state = dict(state)
        new_state["kpages"] = kp
        new_state["vpages"] = vp
        if quant:
            new_state["kscales"] = ksp
            new_state["vscales"] = vsp
        new_state["cache_pos"] = pos + T
        return self.act()(out), new_state

    def window_pages(self, chunk: int, page_size: int) -> int:
        """Logical pages that hold every key a chunk of ``chunk`` queries
        can see through the window, counted from the page of the first
        query's oldest visible key: at most ``window + chunk + page_size -
        2`` tokens."""
        return -(-(self.window + chunk + page_size - 2) // page_size)

    def first_live_page(self, pos, page_size: int):
        """The logical page of the oldest key the query at ``pos`` sees;
        every page before it is dead to that query and all later ones."""
        return jnp.maximum(pos - self.window + 1, 0) // page_size

    def _window_read(self, q, kp, vp, bt, pos, mask):
        """A window layer's paged read on the XLA path: gather each row's
        ``window_pages`` from its first live page on (not the table's
        whole width) and attend under the causal and window test on
        absolute positions. A table entry behind the first live page is
        never dereferenced, so the caller may have freed it."""
        from deeplearning4j_tpu.nn.conf.layers import paged_attention as ppa

        T = q.shape[2]
        ps = kp.shape[1]
        nv = min(bt.shape[1], self.window_pages(T, ps))
        first = self.first_live_page(pos, ps)                    # [B]
        with jax.named_scope("swa_attention"):
            # a slot past the table's end repeats its last page under a
            # position no query reaches
            view = self.paged_views({"kpages": kp, "vpages": vp}, bt, first,
                                    nv)
            # positions counted from the view's column 0
            at = pos - first * ps
            valid = self._sees((at[:, None] + jnp.arange(T))[:, :, None],
                               jnp.arange(nv * ps)[None, None, :])
            if mask is not None:
                valid = valid & ppa._key_valid_plane(mask, at, T,
                                                     nv * ps)[:, None, :]
            return grouped_attention(q, view["kcache"], view["vcache"],
                                     valid[:, None], self._scale(),
                                     self.softmax_barrier)

    def _sharded_write_attend(self, backend, mesh, q, k, v, ksc, vsc, kp,
                              vp, ksp, vsp, bt, pos, pg, off, mask, quant):
        """Head-parallel write + attend over ``mesh``'s ``model`` axis.

        The math is the single-chip ``_paged_forward`` body verbatim,
        run per-shard on the ``H/tp`` local head slice: q/k/v chunks and
        the pool leaves split on their head axis, the block table / page
        routing replicated (every shard scatters into the SAME pages of
        its own head slice). Attention contexts are independent per
        head, so the shard outputs are disjoint and the head-axis
        all-gather of ``o`` (forced by the caller's replication
        constraint before Wo) is exact concatenation — no reduction, no
        float reordering — which is what makes tp>1 outputs bit-exact
        against tp=1. Both helper backends serve the local view
        unchanged: the XLA gather sees a ``[P, ps, (H/tp) * d]`` pool (a
        contiguous ``H/tp`` heads of each row's lanes), the Pallas kernel
        groups the ``H/tp`` local heads.
        """
        from jax.sharding import NamedSharding, PartitionSpec as P

        from deeplearning4j_tpu.nn.conf.layers import paged_attention as ppa
        from deeplearning4j_tpu.parallel.mesh import MODEL_AXIS

        head4 = P(None, MODEL_AXIS, None, None)  # [B,H,T,d]
        head3 = P(None, MODEL_AXIS, None)        # [B,H,T]   / [P,H,ps]
        rows = P(None, None, MODEL_AXIS)         # [P,ps,H*d]
        has_mask = mask is not None

        def local(q, k, v, kp, vp, bt, pos, pg, off, ksc, vsc, ksp, vsp,
                  mask):
            kp, vp, ksp, vsp = _write_chunk(kp, vp, ksp, vsp, k, v, ksc,
                                            vsc, pg, off)
            o = ppa.paged_attend(backend, q, kp, vp, bt, pos,
                                 mask=mask if has_mask else None,
                                 kscales=ksp, vscales=vsp)
            out = [kp, vp, o]
            if quant:
                out += [ksp, vsp]
            return tuple(out)

        # None operands have no leaves, so any placeholder spec works;
        # the quant/mask STRUCTURE is already part of the jit cache key
        in_specs = (head4, head4, head4, rows, rows, P(), P(), P(), P(),
                    head3 if quant else P(), head3 if quant else P(),
                    head3 if quant else P(), head3 if quant else P(),
                    P())
        out_specs = (rows, rows, head4) + ((head3, head3) if quant
                                             else ())
        fn = jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                           out_specs=out_specs, check_vma=False)
        res = fn(q, k, v, kp, vp, bt, pos, pg, off, ksc, vsc, ksp, vsp,
                 mask if has_mask else None)
        kp, vp, o = res[0], res[1], res[2]
        if quant:
            ksp, vsp = res[3], res[4]
        # replicate the per-head contexts before the (replicated) Wo
        # projection: an exact all-gather — each shard contributed a
        # disjoint head slice, so no arithmetic happens in the collective
        o = jax.lax.with_sharding_constraint(
            o, NamedSharding(mesh, P()))
        return kp, vp, ksp, vsp, o


@register_serializable
@dataclass
class PositionalEncodingLayer(Layer):
    """Add the fixed sinusoidal position table to a [B, T, F] sequence
    (Vaswani et al. encoding; parameterless, so serde is trivial and the
    table is a compile-time constant folded into the XLA program).

    Beyond reference parity: exists (with LayerNormalization) so
    transformer stacks are buildable first-class — the 2017-era reference
    predates them.
    """

    max_wavelength: float = 10000.0

    INPUT_KIND = "rnn"

    def output_type(self, input_type: InputType) -> InputType:
        return input_type

    def init_streaming_carry(self, batch: int, dtype=jnp.float32) -> dict:
        # streaming decode: chunk t must receive the encoding of its
        # ABSOLUTE position, so the consumed-token count is carried
        return {"cache_pos": jnp.zeros((), jnp.int32)}

    def forward(self, params, state, x, *, mask=None, train=False, rng=None):
        T, F = x.shape[-2], x.shape[-1]
        start = state.get("cache_pos")
        if start is not None and getattr(start, "ndim", 0) == 1:
            # per-row stream positions (slot-pooled decode): [B, T, 1]
            pos = start.astype(jnp.float32)[:, None, None] \
                + jnp.arange(T, dtype=jnp.float32)[None, :, None]
        else:
            pos = jnp.arange(T, dtype=jnp.float32)[:, None] \
                + (0.0 if start is None else start.astype(jnp.float32))
        half = (F + 1) // 2
        freq = jnp.exp(-jnp.log(self.max_wavelength)
                       * jnp.arange(half, dtype=jnp.float32) / max(half, 1))
        ang = pos * freq                          # [..., T, half]
        pe = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)[..., :F]
        out = x + pe.astype(x.dtype)
        if start is None:
            return out, state
        new_state = dict(state)
        new_state["cache_pos"] = start + T
        return out, new_state
