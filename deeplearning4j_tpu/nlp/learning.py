"""SkipGram / CBOW training updates as single jitted XLA programs.

Reference: models/embeddings/learning/impl/elements/SkipGram.java:215-272 —
the reference fuses hierarchical softmax + negative sampling into the native
``AggregateSkipGram`` ND4J op (per-pair dot/axpy on syn0/syn1 rows). The
TPU-native equivalent batches B (center, context) pairs into index arrays and
executes ONE jitted step per batch: gather rows -> sigmoid dots -> scatter-add
updates (``.at[].add``, XLA scatter — duplicate indices accumulate, matching
the reference's sequential row axpys up to summation order).

Gradients are closed-form (logistic regression), not autodiff: the update is
its own derivative, and hand-coding keeps it one fused kernel.

Duplicate-row stabilisation: the reference applies pairs SEQUENTIALLY, so a
row touched by many pairs is re-read after every axpy. A batched scatter
instead accumulates all contributions computed from the SAME stale row; when
one row appears hundreds of times in a batch (tiny vocab or very frequent
word) the summed step grows with the duplicate count and training diverges
(count * lr >> 1). Every scatter below therefore caps the accumulated
per-row step at DUP_CAP effective contributions: scale = min(1, cap/count).
Rows with <= cap duplicates per batch sum exactly like the reference; hotter
rows get a bounded step (cap * lr < 1, the SGD stability region). A full
mean (1/count) is NOT used — it collapses a whole batch into one effective
step per row and stalls learning when batch >> vocab.

HS pair layout: for each center/context pair, up to L huffman (point, code)
levels with a validity mask. NS layout: K negatives per pair sampled on host
from the unigram^0.75 table (reference: InMemoryLookupTable sampling table).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


DUP_CAP = 16.0  # max effective duplicate contributions per row per batch


def _row_mean_scale(num_rows, idx, weights, cap):
    """Per-element scale min(1, cap/count), where count is how much batch
    weight lands on the element's destination row (see module docstring:
    stale-read duplicate stabilisation). idx/weights: same shape; weight 0 =
    padding. cap=inf disables the cap (pure reference-style summation — used
    by doc2vec label training, where a single row takes a full-batch
    gradient against near-frozen targets and summation is stable)."""
    cnt = jnp.zeros((num_rows,), weights.dtype).at[idx].add(weights)
    return jnp.minimum(1.0, cap / jnp.maximum(cnt[idx], 1.0))


def _segment_row_add(row_idx, updates, weights, cap, stacked):
    """Add ``updates`` into ``stacked`` rows WITHOUT a duplicate-index
    scatter: sort by destination row, per-row-count dup_cap scale, segment
    sums, then ONE scatter whose indices are provably sorted and unique.

    Rationale (historical): the round-3 hypothesis was that XLA lowers a
    duplicate-index scatter-add to a serialized per-row loop on TPU, making
    the sort-then-unique-scatter form faster. The round-4 A/B on the real
    v5e chip REFUTED this: the plain ``.at[].add`` path measures ~3x faster
    end-to-end (184k vs 49k words/s at batch 8192, 128k vs 67k at 16384 —
    record deleted at PR 21), because the argsort dominates.
    ``segment_updates`` therefore defaults to False everywhere; this path
    is kept as a tested alternative for backends where duplicate scatters
    do serialize.
    Numerically identical to the `.at[].add` path up to float summation
    order (same per-element min(1, cap/count) scale as _row_mean_scale).

    row_idx [M] int32; updates [M, D] pre-masked (weight-0 elements carry a
    zero update); weights [M] (0 = padding); cap scalar or per-element [M]
    (label rows train uncapped while word rows stay capped); stacked
    [R, D]. Segments that do not exist land on distinct dummy rows past R
    (zero contribution), so indices stay unique without a dynamic segment
    count."""
    M, D = updates.shape
    R = stacked.shape[0]
    order = jnp.argsort(row_idx)
    si = row_idx[order]
    su = updates[order]
    sw = weights[order]
    sc = jnp.broadcast_to(cap, (M,))[order]
    start = jnp.concatenate([jnp.ones((1,), bool), si[1:] != si[:-1]])
    seg = jnp.cumsum(start.astype(jnp.int32)) - 1
    cnt = jax.ops.segment_sum(sw, seg, num_segments=M)
    scale = jnp.minimum(1.0, sc / jnp.maximum(cnt[seg], 1.0))
    summed = jax.ops.segment_sum(su * scale[:, None], seg, num_segments=M)
    nseg = jnp.sum(start.astype(jnp.int32))
    rep = jax.ops.segment_max(si, seg, num_segments=M)
    j = jnp.arange(M)
    rep = jnp.where(j < nseg, rep, R + j)
    padded = jnp.concatenate([stacked, jnp.zeros((M, D), stacked.dtype)])
    padded = padded.at[rep].add(summed, indices_are_sorted=True,
                                unique_indices=True)
    return padded[:R]


@partial(jax.jit, static_argnames=("use_hs", "use_ns"))
def skipgram_step(syn0, syn1, syn1neg, centers, points, codes, code_mask,
                  neg_targets, neg_labels, lr, dup_cap, *, use_hs: bool,
                  use_ns: bool):
    """One batched skipgram update.

    syn0: [V, D] input vectors; syn1: [V, D] HS inner nodes; syn1neg: [V, D].
    centers: [B] int32 — the word whose syn0 row moves.
    points/codes/code_mask: [B, L] — HS path (padded).
    neg_targets: [B, 1+K] (positive target first), neg_labels: [B, 1+K].
    Returns updated (syn0, syn1, syn1neg).
    """
    V = syn0.shape[0]
    h = syn0[centers]  # [B, D]
    grad_h = jnp.zeros_like(h)

    if use_hs:
        w1 = syn1[points]  # [B, L, D]
        f = jax.nn.sigmoid(jnp.einsum("bd,bld->bl", h, w1))
        # g = (1 - code - f) * lr, masked (reference SkipGram HS sign form)
        g = (1.0 - codes - f) * code_mask * lr
        grad_h = grad_h + jnp.einsum("bl,bld->bd", g, w1)
        dw1 = jnp.einsum("bl,bd->bld", g, h)
        s1 = _row_mean_scale(V, points, code_mask, dup_cap)
        syn1 = syn1.at[points].add(dw1 * s1[..., None])

    if use_ns:
        wn = syn1neg[neg_targets]  # [B, 1+K, D]
        f = jax.nn.sigmoid(jnp.einsum("bd,bkd->bk", h, wn))
        g = (neg_labels - f) * lr
        grad_h = grad_h + jnp.einsum("bk,bkd->bd", g, wn)
        dwn = jnp.einsum("bk,bd->bkd", g, h)
        sn = _row_mean_scale(V, neg_targets,
                             jnp.ones(neg_targets.shape, syn0.dtype),
                             dup_cap)
        syn1neg = syn1neg.at[neg_targets].add(dwn * sn[..., None])

    s0 = _row_mean_scale(V, centers, jnp.ones(centers.shape, syn0.dtype),
                         dup_cap)
    syn0 = syn0.at[centers].add(grad_h * s0[:, None])
    return syn0, syn1, syn1neg


@partial(jax.jit,
         static_argnames=("window", "batch", "neg_k", "use_hs", "use_ns",
                          "segment_updates"),
         donate_argnums=(0, 1, 2))
def skipgram_corpus_epoch(syn0, syn1, syn1neg, tokens, key,
                          lr_start, lr_end, dup_cap, points_tab, codes_tab,
                          cmask_tab, neg_table, *, window: int, batch: int,
                          neg_k: int, use_hs: bool, use_ns: bool,
                          segment_updates: bool = False):
    """One skipgram epoch generated AND trained on device.

    The round-3 v1 fast path staged pre-built pair/negative batches from
    host, but the host->device link is the scarce resource (the reference's
    AggregateSkipGram runs host-side so never pays it): ~25 bytes/pair of
    wire traffic capped throughput far below device speed. This kernel
    uploads only the TOKEN STREAM (4 bytes/token + sentence ids) and derives
    everything else on device:

    - pairs: per-offset shifted views of the padded token stream, validity =
      same sentence AND |offset| <= per-position random window
      (win = window - rand % window, the reference's shrinking window),
      laid out corpus-ordered [N, 2W] -> [S, B];
    - negatives: unigram^0.75 table lookups with jax.random, per batch;
    - HS paths: gathers from device-resident [V, L] huffman tables;
    - LR: linear lr_start -> lr_end across the S batches.

    tokens: [N] int32 stream with -1 as sentence separator AND tail
    padding, sized so N*2W % batch == 0 (separator/padding positions
    produce pair_mask 0; sentence ids are a device-side cumsum over the
    separators). Per-batch update math matches ``skipgram_step`` (same
    dup-cap stabilisation).
    """
    N = tokens.shape[0]
    W = window
    kw, kn = jax.random.split(key)
    win = jax.random.randint(kw, (N,), 1, W + 1, dtype=jnp.int32)
    sent_id = jnp.cumsum((tokens < 0).astype(jnp.int32))
    tok_pad = jnp.pad(tokens, W, constant_values=-1)
    sid_pad = jnp.pad(sent_id, W, constant_values=-2)
    ctxs, valids = [], []
    for d in range(-W, W + 1):
        if d == 0:
            continue
        ctx_d = jax.lax.dynamic_slice(tok_pad, (W + d,), (N,))
        sid_d = jax.lax.dynamic_slice(sid_pad, (W + d,), (N,))
        valids.append((sid_d == sent_id) & (jnp.abs(d) <= win)
                      & (tokens >= 0) & (ctx_d >= 0))
        ctxs.append(ctx_d)
    ctx = jnp.stack(ctxs, 1)                       # [N, 2W] corpus order
    val = jnp.stack(valids, 1)
    P = N * 2 * W
    S = P // batch
    # rows that move = context words; predicted = centers (reference
    # SkipGram iterateSample(currentWord=center, lastWord=context)
    # updates syn0[lastWord])
    rows = jnp.maximum(ctx, 0).reshape(S, batch)
    pred = jnp.broadcast_to(tokens[:, None], ctx.shape)
    pred = jnp.maximum(pred, 0).reshape(S, batch)
    pm = val.reshape(S, batch).astype(syn0.dtype)
    lrs = jnp.linspace(lr_start, lr_end, S).astype(syn0.dtype)
    return _pair_scan(syn0, syn1, syn1neg, rows, pred, pm, lrs, kn,
                      points_tab, codes_tab, cmask_tab, neg_table, dup_cap,
                      dup_cap, batch=batch, neg_k=neg_k, use_hs=use_hs,
                      use_ns=use_ns, segment_updates=segment_updates)


def _pair_scan(syn0, syn1, syn1neg, rows, pred, pm, lrs, kn, points_tab,
               codes_tab, cmask_tab, neg_table, dup_cap, syn0_cap, *,
               batch: int, neg_k: int, use_hs: bool, use_ns: bool,
               segment_updates: bool):
    """The skipgram family's inner loop: scan over [S, B] (row, predicted)
    pair batches. ``rows`` move in syn0 (skipgram: context words; DBOW: doc
    labels); ``pred`` supply the HS path / NS positive. syn0_cap is the
    dup-cap for syn0 row updates, separate from the table cap so label
    training (one row in every pair of a batch) can run uncapped
    (syn0_cap=inf) while hot word targets stay stabilised."""
    V = syn0.shape[0]
    V1 = syn1.shape[0]
    tsize = neg_table.shape[0]
    S = rows.shape[0]

    def body(carry, xs):
        syn0, syn1, syn1neg = carry
        c, p_idx, pm_b, lr, i = xs
        h = syn0[c]
        grad_h = jnp.zeros_like(h)
        # segment_updates=True: collect (destination row in the STACKED
        # [syn0; syn1; syn1neg] row space, update, weight, cap) tuples and
        # apply them in one sorted-unique scatter at the end (see
        # _segment_row_add); False keeps the plain scatter-adds for A/B.
        idx_parts, upd_parts, w_parts, cap_parts = [], [], [], []
        if use_hs:
            pts = points_tab[p_idx]                # [B, L]
            cd = codes_tab[p_idx]
            cm = cmask_tab[p_idx] * pm_b[:, None]
            w1 = syn1[pts]
            f = jax.nn.sigmoid(jnp.einsum("bd,bld->bl", h, w1))
            g = (1.0 - cd - f) * cm * lr
            grad_h = grad_h + jnp.einsum("bl,bld->bd", g, w1)
            dw1 = jnp.einsum("bl,bd->bld", g, h)
            if segment_updates:
                idx_parts.append(pts.reshape(-1) + V)
                upd_parts.append(dw1.reshape(-1, h.shape[1]))
                w_parts.append(cm.reshape(-1))
                cap_parts.append(jnp.full((pts.size,), dup_cap, syn0.dtype))
            else:
                s1 = _row_mean_scale(V, pts, cm, dup_cap)
                syn1 = syn1.at[pts].add(dw1 * s1[..., None])
        if use_ns:
            draws = jax.random.randint(jax.random.fold_in(kn, i),
                                       (batch, neg_k), 0, tsize,
                                       dtype=jnp.int32)
            nt = jnp.concatenate([p_idx[:, None], neg_table[draws]], axis=1)
            nl = jnp.zeros((batch, 1 + neg_k), syn0.dtype).at[:, 0].set(1.0)
            wn = syn1neg[nt]
            f = jax.nn.sigmoid(jnp.einsum("bd,bkd->bk", h, wn))
            g = (nl - f) * pm_b[:, None] * lr
            grad_h = grad_h + jnp.einsum("bk,bkd->bd", g, wn)
            dwn = jnp.einsum("bk,bd->bkd", g, h)
            if segment_updates:
                idx_parts.append(nt.reshape(-1) + (V + V1))
                upd_parts.append(dwn.reshape(-1, h.shape[1]))
                w_parts.append(
                    jnp.broadcast_to(pm_b[:, None], nt.shape).reshape(-1))
                cap_parts.append(jnp.full((nt.size,), dup_cap, syn0.dtype))
            else:
                sn = _row_mean_scale(V, nt,
                                     jnp.broadcast_to(pm_b[:, None],
                                                      nt.shape),
                                     dup_cap)
                syn1neg = syn1neg.at[nt].add(dwn * sn[..., None])
        if segment_updates:
            idx_parts.append(c)
            upd_parts.append(grad_h)
            w_parts.append(pm_b)
            cap_parts.append(jnp.full((c.shape[0],), syn0_cap, syn0.dtype))
            stacked = jnp.concatenate([syn0, syn1, syn1neg], 0)
            stacked = _segment_row_add(jnp.concatenate(idx_parts),
                                       jnp.concatenate(upd_parts),
                                       jnp.concatenate(w_parts),
                                       jnp.concatenate(cap_parts), stacked)
            syn0 = stacked[:V]
            syn1 = stacked[V:V + V1]
            syn1neg = stacked[V + V1:]
        else:
            s0 = _row_mean_scale(V, c, pm_b, syn0_cap)
            syn0 = syn0.at[c].add(grad_h * s0[:, None])
        return (syn0, syn1, syn1neg), None

    (syn0, syn1, syn1neg), _ = jax.lax.scan(
        body, (syn0, syn1, syn1neg),
        (rows, pred, pm, lrs, jnp.arange(S, dtype=jnp.int32)))
    return syn0, syn1, syn1neg


@partial(jax.jit, static_argnames=("use_hs", "use_ns"))
def cbow_step(syn0, syn1, syn1neg, context, context_mask, points, codes,
              code_mask, neg_targets, neg_labels, lr, dup_cap, *,
              use_hs: bool, use_ns: bool):
    """One batched CBOW update (reference: elements/CBOW.java — the context
    mean predicts the center; the input gradient is spread over the context).

    context: [B, C] int32 context-word ids (padded), context_mask: [B, C].
    points/codes relate to the CENTER word's huffman path; neg_targets[...,0]
    is the center (label 1).
    """
    V = syn0.shape[0]
    ctx_vec = syn0[context]  # [B, C, D]
    denom = jnp.maximum(context_mask.sum(axis=1, keepdims=True), 1.0)
    h = (ctx_vec * context_mask[..., None]).sum(axis=1) / denom  # [B, D]
    grad_h = jnp.zeros_like(h)

    if use_hs:
        w1 = syn1[points]
        f = jax.nn.sigmoid(jnp.einsum("bd,bld->bl", h, w1))
        g = (1.0 - codes - f) * code_mask * lr
        grad_h = grad_h + jnp.einsum("bl,bld->bd", g, w1)
        s1 = _row_mean_scale(V, points, code_mask, dup_cap)
        syn1 = syn1.at[points].add(jnp.einsum("bl,bd->bld", g, h)
                                   * s1[..., None])

    if use_ns:
        wn = syn1neg[neg_targets]
        f = jax.nn.sigmoid(jnp.einsum("bd,bkd->bk", h, wn))
        g = (neg_labels - f) * lr
        grad_h = grad_h + jnp.einsum("bk,bkd->bd", g, wn)
        sn = _row_mean_scale(V, neg_targets,
                             jnp.ones(neg_targets.shape, syn0.dtype),
                             dup_cap)
        syn1neg = syn1neg.at[neg_targets].add(jnp.einsum("bk,bd->bkd", g, h)
                                              * sn[..., None])

    # spread input gradient over contributing context words (mean -> /count),
    # then normalise duplicate context rows across the batch
    per_ctx = (grad_h[:, None, :] * context_mask[..., None]) / denom[..., None]
    sc = _row_mean_scale(V, context, context_mask, dup_cap)
    syn0 = syn0.at[context].add(per_ctx * sc[..., None])
    return syn0, syn1, syn1neg


@partial(jax.jit,
         static_argnames=("window", "batch", "neg_k", "use_hs", "use_ns",
                          "with_labels", "segment_updates"),
         donate_argnums=(0, 1, 2))
def cbow_corpus_epoch(syn0, syn1, syn1neg, tokens, labels, key, lr_start,
                      lr_end, dup_cap, label_cap, points_tab, codes_tab,
                      cmask_tab, neg_table, *, window: int, batch: int,
                      neg_k: int, use_hs: bool, use_ns: bool,
                      with_labels: bool, segment_updates: bool = False):
    """One CBOW epoch on device — and, with_labels=True, one doc2vec DM
    epoch (reference: elements/CBOW.java, sequence/DM.java).

    Same token-stream-only contract as ``skipgram_corpus_epoch`` (tokens
    [N] with -1 separators, N % batch == 0), with the roles flipped: every
    position is a CENTER whose context is the 2W shifted views; the
    masked context mean predicts the center. ``labels`` [N] carries a
    syn0 row id per position (-1 = none) and is prepended as an extra
    always-on context slot — the DM trick, streamed. The label slot's
    dup-cap is ``label_cap`` (inf for label training: one row per doc
    appears in EVERY window of that doc; capping would attenuate its
    gradient ~batch/cap-fold), word slots keep ``dup_cap``.
    """
    N = tokens.shape[0]
    W = window
    kw, kn = jax.random.split(key)
    win = jax.random.randint(kw, (N,), 1, W + 1, dtype=jnp.int32)
    sent_id = jnp.cumsum((tokens < 0).astype(jnp.int32))
    tok_pad = jnp.pad(tokens, W, constant_values=-1)
    sid_pad = jnp.pad(sent_id, W, constant_values=-2)
    ctxs, valids = [], []
    for d in range(-W, W + 1):
        if d == 0:
            continue
        ctx_d = jax.lax.dynamic_slice(tok_pad, (W + d,), (N,))
        sid_d = jax.lax.dynamic_slice(sid_pad, (W + d,), (N,))
        valids.append((sid_d == sent_id) & (jnp.abs(d) <= win)
                      & (tokens >= 0) & (ctx_d >= 0))
        ctxs.append(ctx_d)
    ctx = jnp.stack(ctxs, 1)                       # [N, 2W]
    val = jnp.stack(valids, 1)
    if with_labels:
        ctx = jnp.concatenate([labels[:, None], ctx], 1)
        val = jnp.concatenate([((labels >= 0) & (tokens >= 0))[:, None],
                               val], 1)
    C = ctx.shape[1]
    S = N // batch
    V = syn0.shape[0]
    V1 = syn1.shape[0]
    tsize = neg_table.shape[0]
    ctx_b = jnp.maximum(ctx, 0).reshape(S, batch, C)
    # center is trainable iff in-vocab with >=1 live context slot; context
    # slots are additionally masked by their center's validity
    pm = ((tokens >= 0) & val.any(axis=1)).astype(syn0.dtype)
    cm_b = (val.astype(syn0.dtype) * pm[:, None]).reshape(S, batch, C)
    pm_b = pm.reshape(S, batch)
    cen_b = jnp.maximum(tokens, 0).reshape(S, batch)
    lrs = jnp.linspace(lr_start, lr_end, S).astype(syn0.dtype)
    if with_labels:
        slot_cap = jnp.concatenate(
            [jnp.broadcast_to(label_cap, (1,)).astype(syn0.dtype),
             jnp.full((C - 1,), 1.0, syn0.dtype) * dup_cap])
    else:
        slot_cap = jnp.full((C,), 1.0, syn0.dtype) * dup_cap

    def body(carry, xs):
        syn0, syn1, syn1neg = carry
        cx, cm, p_idx, pm_b, lr, i = xs
        denom = jnp.maximum(cm.sum(axis=1, keepdims=True), 1.0)
        h = (syn0[cx] * cm[..., None]).sum(axis=1) / denom    # [B, D]
        grad_h = jnp.zeros_like(h)
        idx_parts, upd_parts, w_parts, cap_parts = [], [], [], []
        if use_hs:
            pts = points_tab[p_idx]
            cd = codes_tab[p_idx]
            hm = cmask_tab[p_idx] * pm_b[:, None]
            w1 = syn1[pts]
            f = jax.nn.sigmoid(jnp.einsum("bd,bld->bl", h, w1))
            g = (1.0 - cd - f) * hm * lr
            grad_h = grad_h + jnp.einsum("bl,bld->bd", g, w1)
            dw1 = jnp.einsum("bl,bd->bld", g, h)
            if segment_updates:
                idx_parts.append(pts.reshape(-1) + V)
                upd_parts.append(dw1.reshape(-1, h.shape[1]))
                w_parts.append(hm.reshape(-1))
                cap_parts.append(jnp.full((pts.size,), 1.0, syn0.dtype)
                                 * dup_cap)
            else:
                s1 = _row_mean_scale(V, pts, hm, dup_cap)
                syn1 = syn1.at[pts].add(dw1 * s1[..., None])
        if use_ns:
            draws = jax.random.randint(jax.random.fold_in(kn, i),
                                       (batch, neg_k), 0, tsize,
                                       dtype=jnp.int32)
            nt = jnp.concatenate([p_idx[:, None], neg_table[draws]], axis=1)
            nl = jnp.zeros((batch, 1 + neg_k), syn0.dtype).at[:, 0].set(1.0)
            wn = syn1neg[nt]
            f = jax.nn.sigmoid(jnp.einsum("bd,bkd->bk", h, wn))
            g = (nl - f) * pm_b[:, None] * lr
            grad_h = grad_h + jnp.einsum("bk,bkd->bd", g, wn)
            dwn = jnp.einsum("bk,bd->bkd", g, h)
            if segment_updates:
                idx_parts.append(nt.reshape(-1) + (V + V1))
                upd_parts.append(dwn.reshape(-1, h.shape[1]))
                w_parts.append(
                    jnp.broadcast_to(pm_b[:, None], nt.shape).reshape(-1))
                cap_parts.append(jnp.full((nt.size,), 1.0, syn0.dtype)
                                 * dup_cap)
            else:
                sn = _row_mean_scale(V, nt,
                                     jnp.broadcast_to(pm_b[:, None],
                                                      nt.shape),
                                     dup_cap)
                syn1neg = syn1neg.at[nt].add(dwn * sn[..., None])
        # spread the input gradient over contributing context slots
        per_ctx = (grad_h[:, None, :] * cm[..., None]) / denom[..., None]
        cap_b = jnp.broadcast_to(slot_cap[None, :], cm.shape)
        if segment_updates:
            idx_parts.append(cx.reshape(-1))
            upd_parts.append(per_ctx.reshape(-1, h.shape[1]))
            w_parts.append(cm.reshape(-1))
            cap_parts.append(cap_b.reshape(-1))
            stacked = jnp.concatenate([syn0, syn1, syn1neg], 0)
            stacked = _segment_row_add(jnp.concatenate(idx_parts),
                                       jnp.concatenate(upd_parts),
                                       jnp.concatenate(w_parts),
                                       jnp.concatenate(cap_parts), stacked)
            syn0 = stacked[:V]
            syn1 = stacked[V:V + V1]
            syn1neg = stacked[V + V1:]
        else:
            sc = _row_mean_scale(V, cx, cm, cap_b)
            syn0 = syn0.at[cx].add(per_ctx * sc[..., None])
        return (syn0, syn1, syn1neg), None

    (syn0, syn1, syn1neg), _ = jax.lax.scan(
        body, (syn0, syn1, syn1neg),
        (ctx_b, cm_b, cen_b, pm_b, lrs, jnp.arange(S, dtype=jnp.int32)))
    return syn0, syn1, syn1neg


@partial(jax.jit,
         static_argnames=("batch", "neg_k", "use_hs", "use_ns",
                          "segment_updates"),
         donate_argnums=(0, 1, 2))
def dbow_corpus_epoch(syn0, syn1, syn1neg, tokens, labels, key, lr_start,
                      lr_end, dup_cap, label_cap, points_tab, codes_tab,
                      cmask_tab, neg_table, *, batch: int, neg_k: int,
                      use_hs: bool, use_ns: bool,
                      segment_updates: bool = False):
    """One doc2vec DBOW epoch on device (reference: sequence/DBOW.java):
    the document's label row predicts every document word — the skipgram
    inner loop with rows = ``labels`` [N] (syn0 row per position, -1 =
    none) and predicted = ``tokens``. Label syn0 updates run with
    ``label_cap`` (inf: full-batch gradient on the one moving row); word
    HS/NS tables keep ``dup_cap``."""
    N = tokens.shape[0]
    S = N // batch
    _, kn = jax.random.split(key)
    pm = ((tokens >= 0) & (labels >= 0)).astype(syn0.dtype).reshape(S, batch)
    rows = jnp.maximum(labels, 0).reshape(S, batch)
    pred = jnp.maximum(tokens, 0).reshape(S, batch)
    lrs = jnp.linspace(lr_start, lr_end, S).astype(syn0.dtype)
    return _pair_scan(syn0, syn1, syn1neg, rows, pred, pm, lrs, kn,
                      points_tab, codes_tab, cmask_tab, neg_table, dup_cap,
                      label_cap, batch=batch, neg_k=neg_k, use_hs=use_hs,
                      use_ns=use_ns, segment_updates=segment_updates)


class BatchBuilder:
    """Host-side pair/batch assembly shared by the elements learners.

    Converts tokenized sentences into padded index arrays for the jitted
    steps; implements the reference's dynamic window (b = rand % window),
    subsampling, and unigram^0.75 negative table (reference:
    InMemoryLookupTable.java:55-97,120 makeTable / SkipGram.java:215-224)."""

    def __init__(self, cache, window=5, negative=0, use_hs=True,
                 sampling=0.0, table_size=None, seed=12345,
                 max_code_length=40):
        self.cache = cache
        self.window = window
        self.negative = int(negative)
        self.use_hs = use_hs
        self.sampling = sampling
        self.rng = np.random.RandomState(seed)
        self.max_code_len = max(
            (len(cache.element_at_index(i).codes)
             for i in range(cache.num_words())), default=1) or 1
        self.max_code_len = min(self.max_code_len, max_code_length)
        counts = cache.counts_array()
        if table_size is None:
            # ~32 slots per word on average (capped) so even unigram^0.75
            # tail words keep a nonzero draw probability; the reference's
            # table is a fixed 1e8 entries (InMemoryLookupTable), far more
            # memory for the same quantisation role
            table_size = int(min(max(100000, 32 * cache.num_words()),
                                 1 << 24))
        if self.negative > 0 and counts.size:
            p = counts ** 0.75
            self._neg_cum = np.cumsum(p / p.sum())
            # quantised unigram^0.75 table (reference
            # InMemoryLookupTable.makeTable): sampling = one randint + one
            # gather instead of a searchsorted per draw
            self._neg_table = np.searchsorted(
                self._neg_cum,
                (np.arange(table_size) + 0.5) / table_size).astype(np.int32)
        else:
            self._neg_cum = None
            self._neg_table = None
        # precomputed huffman path arrays [V, L]
        V = cache.num_words()
        L = self.max_code_len
        self.points = np.zeros((V, L), np.int32)
        self.codes = np.zeros((V, L), np.float32)
        self.code_mask = np.zeros((V, L), np.float32)
        for i in range(V):
            w = cache.element_at_index(i)
            n = min(len(w.codes), L)
            if n:
                self.points[i, :n] = w.points[:n]
                self.codes[i, :n] = w.codes[:n]
                self.code_mask[i, :n] = 1.0

    def lookup_indices(self, tokens) -> np.ndarray:
        """Vocab indices for in-vocab tokens, NO subsampling (callers that
        train multiple epochs re-draw subsampling per epoch)."""
        idx = [self.cache.index_of(t) for t in tokens]
        return np.array([i for i in idx if i >= 0], np.int32)

    def subsample(self, idx: np.ndarray) -> np.ndarray:
        """One frequency-subsampling draw (word2vec keep probability)."""
        if self.sampling <= 0 or not idx.size:
            return idx
        counts = self.cache.counts_array()
        total = self.cache.total_word_count
        freq = counts[idx] / total
        keep_p = (np.sqrt(freq / self.sampling) + 1) * self.sampling / freq
        return idx[self.rng.random_sample(idx.size) < keep_p]

    def sentence_to_indices(self, tokens) -> np.ndarray:
        return self.subsample(self.lookup_indices(tokens))

    def pairs_from_sentence(self, idx: np.ndarray):
        """(centers, contexts) for one sentence — same shrinking random
        window as the corpus-level path (single source of truth)."""
        return self.pairs_from_corpus([idx])

    def pairs_from_corpus(self, sent_indices):
        """All (center, context) pairs of a corpus in one vectorised pass.

        ``sent_indices``: list of per-sentence index arrays. Same shrinking
        random window as ``pairs_from_sentence`` (b = rand % window), but one
        boolean mask per offset over the WHOLE concatenated corpus — the
        per-sentence Python loop disappears. Sentence boundaries are enforced
        by comparing the shifted position against each token's own sentence
        start/end."""
        sent_indices = [s for s in sent_indices if s.size]
        if not sent_indices:
            return np.zeros(0, np.int32), np.zeros(0, np.int32)
        lens = np.array([s.size for s in sent_indices])
        idx = np.concatenate(sent_indices).astype(np.int32)
        n = idx.size
        starts = np.repeat(np.cumsum(lens) - lens, lens)   # [n] own-sentence start
        ends = starts + np.repeat(lens, lens)              # [n] own-sentence end
        pos = np.arange(n)
        win = self.window - self.rng.randint(0, self.window, size=n)
        centers, contexts = [], []
        for d in range(-self.window, self.window + 1):
            if d == 0:
                continue
            j = pos + d
            m = (np.abs(d) <= win) & (j >= starts) & (j < ends)
            if m.any():
                centers.append(idx[m])
                contexts.append(idx[j[m]])
        if not centers:
            return np.zeros(0, np.int32), np.zeros(0, np.int32)
        return (np.concatenate(centers), np.concatenate(contexts))

    def sample_negatives(self, positives: np.ndarray,
                         rng: Optional[np.random.RandomState] = None
                         ) -> np.ndarray:
        """[B] -> [B, 1+K] target ids, positive first. ``rng`` overrides the
        builder's stream (deterministic inference)."""
        B, K = positives.size, self.negative
        targets = np.empty((B, 1 + K), np.int32)
        targets[:, 0] = positives
        if K:
            draws = (rng or self.rng).randint(
                0, self._neg_table.size, size=(B, K))
            targets[:, 1:] = self._neg_table[draws]
        return targets

    def neg_labels(self, B: int) -> np.ndarray:
        lab = np.zeros((B, 1 + self.negative), np.float32)
        lab[:, 0] = 1.0
        return lab

    def hs_arrays(self, predicted: np.ndarray):
        """Huffman paths for the predicted words: ([B,L] points, codes, mask)."""
        return (self.points[predicted], self.codes[predicted],
                self.code_mask[predicted])
