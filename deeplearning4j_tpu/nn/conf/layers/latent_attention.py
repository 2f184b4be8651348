"""Latent attention (MLA, DeepSeek-V2): keys and values are rebuilt from a
narrow normed latent, so a cached token is one row of ``kv_rank + rope_dim``
numbers for ALL heads instead of a key and a value per head.

    c_Q = RMSNorm(x W_DQ)                          [q_rank]
    [q_nope | q_rope] = c_Q W_UQ      per head     [nope_dim | rope_dim]
    [c_KV | k_r] = x W_DKV                         [kv_rank | rope_dim]
    c_KV = RMSNorm(c_KV)
    q_rope, k_r rotated at the token's absolute position (k_r: one key for
    every head)
    k_nope = c_KV W_UK, v = c_KV W_UV per head     [nope_dim], [v_dim]
    score = s (q_nope . k_nope + q_rope . k_r);  causal softmax in float32
    out = concat_heads(sum p v) W_O

The cache row is ``[c_KV | k_r]`` after norm and rotation. Two forms of the
same mathematics read it:

- **plain** (the contiguous forward: training, ``output()``): keys and
  values are rebuilt for every position, once.
- **absorbed** (every forward over a cache: streaming, paged prefill
  rounds, decode): ``q' = q_nope W_UK^T`` (``kv_rank`` a head), ``score = s
  ([q' | q_rope] . [c_KV | k_r])``, ``o = (sum p c_KV) W_UV``. Nothing is
  rebuilt for a cached position; all heads of a row read one
  ``[S, kv_rank + rope_dim]`` plane, whose first ``kv_rank`` columns are
  the "values".

``W_UK`` and ``W_UV`` are held as the two per-head factors the absorbed
form multiplies, ``Wuk [heads, nope_dim, kv_rank]`` (transposed) and ``Wuv
[heads, kv_rank, v_dim]``: a layout of the numbers of the published
``[kv_rank, heads * (nope_dim + v_dim)]`` matrix.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers.attention import (
    NEG_INF, SERVED_BY, _debug_paged_overflow, chunk_mask,
    rotary_frequencies, rotate_pairs, yarn_mscale)
from deeplearning4j_tpu.nn.conf.layers.base import BaseLayer
from deeplearning4j_tpu.nn.conf.layers.paged_attention import (
    _key_valid_plane)
from deeplearning4j_tpu.utils.serde import register_serializable

#: float32 scores ``[rows, heads, chunk, keys]`` of one read are held to
#: this many bytes: past it the heads are read a block at a time
SCORE_BYTES = 1 << 28


def _weights(s, valid, dtype):
    """Float32 scores ``[B, H, T, S]`` under ``valid [B, T, S]`` -> the
    softmax over keys, in the activations' dtype. The row maximum is held
    behind an optimisation barrier: fused with its own broadcast, the TPU
    compiler rewrites the pair as a ``reduce-window`` as wide as the row
    (2 S - 1 comparisons for each of S keys: 11.4 ms for a 184 MB block of
    scores at S = 5,632 where the passes beside it take 0.25 ms; my chip
    runs, PR 34)."""
    s = jnp.where(valid[:, None], s, NEG_INF)
    top = jax.lax.optimization_barrier(jnp.max(s, axis=-1, keepdims=True))
    e = jnp.exp(s - top)
    return (e / jnp.sum(e, axis=-1, keepdims=True)).astype(dtype)


def _rms(t, gamma, eps):
    """RMS norm over the last axis in float32; the result stays float32."""
    t = t.astype(jnp.float32)
    return t * jax.lax.rsqrt(jnp.mean(t * t, axis=-1, keepdims=True) + eps) \
        * gamma.astype(jnp.float32)


@register_serializable
@dataclass
class LatentAttentionLayer(BaseLayer):
    """Causal multi-head latent attention over ``[B, T, F]`` (module
    docstring). Streams through a cache of latent rows: a dense
    ``latent_cache [B, max_cache, kv_rank + rope_dim]`` or, under
    ``GenerationServer``, ONE pool plane ``latent_pages [pages, page_size,
    kv_rank + rope_dim]`` with no head axis, read through the XLA paged
    backend's dense view. ``yarn_factor > 1`` stretches the rotary
    frequencies (``rotary_frequencies``) and scales the scores by
    ``yarn_mscale(factor, yarn_mscale_all_dim) ** 2``."""

    n_in: int = 0
    n_out: int = 0
    n_heads: int = 1
    q_rank: int = 0
    kv_rank: int = 0
    nope_dim: int = 0
    rope_dim: int = 0
    v_dim: int = 0
    norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    # YaRN (0 = the plain frequencies): factor over the original positions
    yarn_factor: float = 0.0
    yarn_original_positions: int = 0
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    yarn_mscale: float = 1.0
    yarn_mscale_all_dim: float = 0.0
    max_cache: int = 512

    INPUT_KIND = "rnn"
    DEFAULT_ACTIVATION = "identity"
    #: pool plane -> (its dense view's name, the view's token axis)
    PAGED_PLANES = {"latent_pages": ("latent_cache", 1)}
    #: no plane has a head axis (what head-parallel sharding, the snapshot
    #: wire format and the int8 scale planes are written for)
    PAGED_HEAD_AXIS = None
    #: read through the XLA paged backend (``resolve_paged_backend``)
    plain = False

    def set_n_in(self, input_type: InputType) -> None:
        if self.n_in == 0:
            self.n_in = input_type.size
        if self.n_out == 0:
            self.n_out = self.n_in

    def validate(self) -> None:
        super().validate()
        for attr in ("q_rank", "kv_rank", "nope_dim", "rope_dim", "v_dim"):
            if getattr(self, attr) <= 0:
                raise ValueError(f"latent attention needs {attr} > 0, got "
                                 f"{getattr(self, attr)}")
        if self.rope_dim % 2:
            raise ValueError(f"rotary positions pair channels: rope_dim "
                             f"{self.rope_dim} is odd")
        if self.yarn_factor > 1.0 and self.yarn_original_positions <= 0:
            raise ValueError("yarn_factor needs yarn_original_positions")

    @property
    def d_head(self) -> int:
        """Width of a head's scores: the key a query is held against."""
        return self.nope_dim + self.rope_dim

    @property
    def latent_width(self) -> int:
        return self.kv_rank + self.rope_dim

    def output_type(self, input_type: InputType) -> InputType:
        return InputType.recurrent(self.n_out, input_type.timeseries_length)

    def param_order(self):
        return ["Wdq", "q_gamma", "Wuq", "Wdkv", "kv_gamma", "Wuk", "Wuv",
                "Wo"]

    def bias_param_names(self):
        return frozenset()

    def init_params(self, rng, dtype=jnp.float32):
        ks = jax.random.split(rng, 6)
        D, O, H = self.n_in, self.n_out, self.n_heads
        rq, c = self.q_rank, self.kv_rank
        n, v = self.nope_dim, self.v_dim
        return {
            "Wdq": self._init_w(ks[0], (D, rq), D, rq, dtype),
            "q_gamma": jnp.ones((rq,), dtype),
            "Wuq": self._init_w(ks[1], (rq, H * self.d_head), rq,
                                H * self.d_head, dtype),
            "Wdkv": self._init_w(ks[2], (D, self.latent_width), D,
                                 self.latent_width, dtype),
            "kv_gamma": jnp.ones((c,), dtype),
            "Wuk": self._init_w(ks[3], (H, n, c), c, H * n, dtype),
            "Wuv": self._init_w(ks[4], (H, c, v), c, H * v, dtype),
            "Wo": self._init_w(ks[5], (H * v, O), H * v, O, dtype),
        }

    # ------------------------------------------------------------- pieces
    def _frequencies(self):
        yarn = None
        if self.yarn_factor > 1.0:
            yarn = (self.yarn_factor, self.yarn_original_positions,
                    self.yarn_beta_fast, self.yarn_beta_slow)
        return rotary_frequencies(self.rope_dim, self.rope_theta, yarn)

    def _score_scale(self) -> float:
        return self.d_head ** -0.5 * yarn_mscale(
            self.yarn_factor, self.yarn_mscale_all_dim) ** 2

    def _rotate(self, t, positions):
        """Adjacent channel pairs turned at ``positions``; float32."""
        with jax.named_scope("yarn_rope"):
            t = rotate_pairs(t, positions, self._frequencies(),
                             adjacent=True)
            factor = yarn_mscale(self.yarn_factor, self.yarn_mscale) \
                / yarn_mscale(self.yarn_factor, self.yarn_mscale_all_dim)
            return t if factor == 1.0 else t * factor

    def _queries_and_rows(self, params, x, start):
        """``(q_nope [B,H,T,nope], q_rope [B,H,T,rope], rows [B,T,C])`` of
        a chunk whose first token stands at ``start`` (``None``: 0; a
        scalar; or one per row). ``rows`` is what the cache holds: the
        normed latent beside the rotated shared key. Products accumulate
        in float32, norm and rotation are float32, each result is rounded
        to the activations' dtype once."""
        f32 = jnp.float32
        B, T, _ = x.shape
        H, n, c = self.n_heads, self.nope_dim, self.kv_rank
        positions = jnp.arange(T)[None, :]
        if start is not None:
            positions = positions + jnp.reshape(start, (-1, 1))
        positions = jnp.broadcast_to(positions, (B, T))
        cq = jnp.einsum("btf,fr->btr", x, params["Wdq"],
                        preferred_element_type=f32)
        cq = _rms(cq, params["q_gamma"], self.norm_eps).astype(x.dtype)
        # heads next to the batch, as every read wants them: scores then
        # come out [B, H, T, keys] with the keys, which the softmax
        # reduces over, as the minor axis
        q = jnp.einsum("btr,ro->bto", cq, params["Wuq"],
                       preferred_element_type=f32).reshape(
                           B, T, H, -1).transpose(0, 2, 1, 3)
        q_rope = self._rotate(q[..., n:], positions).astype(x.dtype)
        kv = jnp.einsum("btf,fc->btc", x, params["Wdkv"],
                        preferred_element_type=f32)
        rows = jnp.concatenate(
            [_rms(kv[..., :c], params["kv_gamma"], self.norm_eps),
             self._rotate(kv[..., c:], positions)], axis=-1).astype(x.dtype)
        return q[..., :n].astype(x.dtype), q_rope, rows

    def _head_blocks(self, fn, q_nope, q_rope, params, keys: int):
        """``fn(q_nope, q_rope, Wuk, Wuv) -> [B, T, heads, v_dim]`` over
        all heads at once, or a block of heads at a time where the float32
        scores of all of them would pass ``SCORE_BYTES``."""
        B, H, T, _ = q_nope.shape
        hb = H
        while hb > 1 and (B * hb * T * keys * 4 > SCORE_BYTES or H % hb):
            hb -= 1
        wuk, wuv = params["Wuk"], params["Wuv"]
        if hb == H:
            return fn(q_nope, q_rope, wuk, wuv)
        nb = H // hb

        def split(t, axis):
            shape = t.shape[:axis] + (nb, hb) + t.shape[axis + 1:]
            return jnp.moveaxis(t.reshape(shape), axis, 0)

        o = jax.lax.map(lambda a: fn(*a), (split(q_nope, 1),
                                           split(q_rope, 1),
                                           split(wuk, 0), split(wuv, 0)))
        return jnp.moveaxis(o, 0, 2).reshape(B, T, H, -1)

    def _read_absorbed(self, params, q_nope, q_rope, view, valid):
        """The absorbed read of ``view [B, S, C]`` (cache rows, the chunk's
        own among them) under ``valid [B, T, S]``: context ``[B, T, H *
        v_dim]``."""
        f32 = jnp.float32
        c, scale = self.kv_rank, self._score_scale()
        dt = q_nope.dtype

        def read(qn, qr, wuk, wuv):
            with jax.named_scope("mla_absorb"):
                qa = jnp.einsum("bhtn,hnc->bhtc", qn, wuk,
                                preferred_element_type=f32).astype(dt)
                qf = jnp.concatenate([qa, qr], axis=-1)
            with jax.named_scope("mla_read"):
                s = jnp.einsum("bhtc,bsc->bhts", qf, view,
                               preferred_element_type=f32) * scale
                ol = jnp.einsum("bhts,bsc->bhtc", _weights(s, valid, dt),
                                view[..., :c],
                                preferred_element_type=f32).astype(dt)
            with jax.named_scope("mla_absorb"):
                return jnp.einsum("bhtc,hcv->bthv", ol, wuv,
                                  preferred_element_type=f32).astype(dt)

        o = self._head_blocks(read, q_nope, q_rope, params, view.shape[1])
        return o.reshape(o.shape[0], o.shape[1], -1)

    def _read_plain(self, params, q_nope, q_rope, rows, valid):
        """The plain form over the chunk's own ``rows [B, T, C]``: keys and
        values rebuilt per head."""
        f32 = jnp.float32
        c, scale = self.kv_rank, self._score_scale()
        dt = q_nope.dtype
        lat, k_r = rows[..., :c], rows[..., c:]

        def read(qn, qr, wuk, wuv):
            k = jnp.einsum("bsc,hnc->bhsn", lat, wuk,
                           preferred_element_type=f32).astype(dt)
            v = jnp.einsum("bsc,hcv->bhsv", lat, wuv,
                           preferred_element_type=f32).astype(dt)
            s = (jnp.einsum("bhtn,bhsn->bhts", qn, k,
                            preferred_element_type=f32)
                 + jnp.einsum("bhtr,bsr->bhts", qr, k_r,
                              preferred_element_type=f32)) * scale
            return jnp.einsum("bhts,bhsv->bthv", _weights(s, valid, dt), v,
                              preferred_element_type=f32).astype(dt)

        o = self._head_blocks(read, q_nope, q_rope, params, rows.shape[1])
        return o.reshape(o.shape[0], o.shape[1], -1)

    def _project_out(self, params, o, mask):
        out = jnp.einsum("bto,op->btp", o, params["Wo"],
                         preferred_element_type=jnp.float32).astype(o.dtype)
        if mask is not None:
            out = out * mask.astype(out.dtype)[:, :, None]
        return self.act()(out)

    @staticmethod
    def _valid(pos, T, S, mask):
        """``[B?, T, S]``: column ``s`` is at or before the query's own
        position, and not a masked column of this chunk."""
        p3 = pos.reshape(-1, 1, 1)
        valid = jnp.arange(S)[None, None, :] \
            <= p3 + jnp.arange(T)[None, :, None]
        if mask is not None:
            pos_b = jnp.broadcast_to(pos.reshape(-1), (mask.shape[0],))
            valid = valid & _key_valid_plane(mask, pos_b, T, S)[:, None, :]
        return valid

    # ----------------------------------------------------------- forwards
    def forward(self, params, state, x, *, mask=None, train=False, rng=None):
        with jax.named_scope("mla_attention"):
            if "latent_pages" in state:
                return self._paged_forward(params, state, x, mask)
            if "latent_cache" in state:
                return self._streaming_forward(params, state, x, mask)
            x = self.apply_input_dropout(x, train=train, rng=rng)
            B, T, _ = x.shape
            q_nope, q_rope, rows = self._queries_and_rows(params, x, None)
            valid = jnp.tril(jnp.ones((T, T), bool))[None]
            if mask is not None:
                valid = valid & jnp.asarray(mask).astype(bool)[:, None, :]
            o = self._read_plain(params, q_nope, q_rope, rows, valid)
            return self._project_out(params, o, mask), state

    def init_streaming_carry(self, batch: int, dtype=jnp.float32) -> dict:
        """One dense plane of cached rows per stream (no copy of the
        latent as "values") and the stream position."""
        return {"latent_cache": jnp.zeros(
            (batch, self.max_cache, self.latent_width), dtype),
            "cache_pos": jnp.zeros((), jnp.int32)}

    def init_paged_carry(self, pages: int, page_size: int,
                         dtype=jnp.float32, kv_dtype=None) -> dict:
        """The cache as a pool of pages shared by the rows of a serving
        batch (``SelfAttentionLayer.init_paged_carry``), ONE plane
        ``[pages, page_size, kv_rank + rope_dim]``: no head axis, and the
        values are the plane's first ``kv_rank`` columns."""
        self._refuse_kv_dtype(kv_dtype)
        return {"latent_pages": jnp.zeros(
            (pages, page_size, self.latent_width), dtype)}

    def paged_views(self, planes: dict, bt) -> dict:
        """The pages each row of ``bt`` names, side by side: the dense
        cache ``[S, NP * page_size, width]``."""
        rows = planes["latent_pages"][bt]
        return {"latent_cache": rows.reshape(
            rows.shape[:1] + (-1,) + rows.shape[3:])}

    def paged_settle(self, planes: dict, cols: dict, pg, off) -> dict:
        """The plane with a written column of the dense cache
        (``[S, width]``) in row ``off[s]`` of page ``pg[s]``."""
        return {k: a.at[pg, off, :].set(cols[k]) for k, a in planes.items()}

    def paged_token_bytes(self, dtype, kv_dtype=None) -> int:
        """Bytes a resident token costs in this layer's pool plane."""
        self._refuse_kv_dtype(kv_dtype)
        return self.latent_width * jnp.dtype(dtype).itemsize

    @staticmethod
    def _refuse_kv_dtype(kv_dtype):
        if kv_dtype is not None:
            raise ValueError(
                f"kv_dtype {kv_dtype!r} is not built for a latent cache: "
                "one row serves every head, so it has no per-head scale "
                "plane to quantize against (None keeps the conf dtype)")

    def _streaming_forward(self, params, state, x, mask):
        """Incremental decode over the dense latent cache; ``cache_pos``
        a scalar (one shared position) or ``[B]`` (a row each), as in
        ``SelfAttentionLayer._streaming_forward``."""
        B, T, _ = x.shape
        state = dict(state)
        state.pop(SERVED_BY, None)
        cache, pos = state["latent_cache"], state["cache_pos"]
        S = cache.shape[1]
        per_row = getattr(pos, "ndim", 0) == 1
        if not isinstance(pos, jax.core.Tracer):
            hi = int(jnp.max(pos)) if per_row else int(pos)
            if hi + T > S:
                raise ValueError(
                    f"latent cache overflow: position {hi} + {T} new "
                    f"tokens > max_cache {S}; raise LatentAttentionLayer."
                    "max_cache or rnn_clear_previous_state()")
        mask = chunk_mask(mask, B, T)
        q_nope, q_rope, rows = self._queries_and_rows(params, x, pos)
        rows = rows.astype(cache.dtype)
        if per_row:
            z = jnp.zeros((), pos.dtype)
            cache = jax.vmap(lambda c, u, p: jax.lax.dynamic_update_slice(
                c, u, (p, z)))(cache, rows, pos)
        else:
            z = jnp.zeros((), jnp.int32)
            cache = jax.lax.dynamic_update_slice(cache, rows, (z, pos, z))
        o = self._read_absorbed(params, q_nope, q_rope, cache,
                                self._valid(pos, T, S, mask))
        state["latent_cache"] = cache
        state["cache_pos"] = pos + T
        return self._project_out(params, o, mask), state

    def _paged_forward(self, params, state, x, mask):
        """Incremental decode over the latent page pool: the chunk's rows
        are scattered through the block table (masked columns to the
        garbage page 0, copy-on-write the caller's job), then every row's
        pages are gathered into its dense view and read absorbed."""
        B, T, _ = x.shape
        state = dict(state)
        backend, mesh = state.pop(SERVED_BY, (None, None))
        if backend not in (None, "xla") or mesh is not None:
            raise NotImplementedError(
                "a latent page plane is read through the XLA paged "
                f"backend on one chip, not {backend!r} over mesh {mesh}")
        pages, bt = state["latent_pages"], state["block_table"]
        pos = state["cache_pos"]
        if getattr(pos, "ndim", 0) != 1:
            raise ValueError("paged attention requires per-row [B] "
                             "cache_pos, got shape "
                             f"{getattr(pos, 'shape', ())}")
        ps, NP = pages.shape[1], bt.shape[1]
        _debug_paged_overflow(pos, T, NP, ps)
        mask = chunk_mask(mask, B, T)
        q_nope, q_rope, rows = self._queries_and_rows(params, x, pos)
        t_abs = pos[:, None] + jnp.arange(T)[None, :]            # [B, T]
        pg = jnp.take_along_axis(bt, jnp.minimum(t_abs // ps, NP - 1),
                                 axis=1)
        if mask is not None:
            pg = jnp.where(mask.astype(bool), pg, 0)
        pages = pages.at[pg, t_abs % ps, :].set(rows.astype(pages.dtype))
        view = pages[bt].reshape(B, NP * ps, pages.shape[-1])
        o = self._read_absorbed(params, q_nope, q_rope, view,
                                self._valid(pos, T, NP * ps, mask))
        state["latent_pages"] = pages
        state["cache_pos"] = pos + T
        return self._project_out(params, o, mask), state
