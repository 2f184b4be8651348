"""Continuous-batching generation serving over a PAGED KV-cache pool.

``sample_generate`` compiles a whole decode into one program per request —
great latency for ONE caller, but N concurrent callers run N programs
back-to-back. ``GenerationServer`` applies iteration-level (continuous)
batching — Orca (Yu et al., OSDI '22) — over a fixed pool of S decode
slots, and stores every slot's KV cache in a shared pool of fixed-size
PAGES behind a block table (vLLM, Kwon et al., SOSP '23):

- The device carry is ONE donated pytree of ``[pages, page_size, H * d]``
  K/V pools per attention layer. A host-owned ``[S, max_pages]`` int32
  block table maps each slot to its page list and rides into every
  dispatch as DATA, so HBM cost is proportional to tokens actually
  resident — not slots x worst-case length — and occupancy churn, page
  churn, or sharing changes NEVER retrace. Page 0 is reserved as the
  garbage page that inactive slots harmlessly write into.
- PREFIX SHARING: prompts are hashed per page-aligned chunk with a
  chained digest; a prompt whose leading chunks match pages already
  resident shares them refcounted read-only and prefills only its
  suffix. Shared (or prefix-cache-registered) pages are copy-on-write:
  the first divergent write — including a request's own first decode
  token landing in its registered tail page — copies the page off with
  a tiny compiled page-copy program and repoints the block table.
- PREFILL computes the rows it admitted: a wave advances in chunk rounds
  (``prefill_chunk``), and a round's rows are packed into row groups of
  the server's own width; a group is one dispatch whose rows are
  gathered by slot index, so slots outside it (free, or decoding) cost
  nothing, and there is one program per column bucket.
- One compiled decode program advances all active slots by
  ``steps_per_dispatch`` micro-steps (a ``lax.scan``) per host round
  trip, with ONE batched token fetch — the serial key schedule
  (``fold_in(base_key, token_index)``) makes the result bit-identical
  to ``greedy_generate``/``sample_generate`` token-for-token.
- SPECULATIVE DECODING (``draft_net`` + ``spec_k``): a small draft model
  with a dense slot cache proposes K-1 tokens per slot under the SAME
  key schedule, and the target verifies all K positions in one chunked
  paged dispatch. Emitted tokens are always the TARGET's selections
  under the serial schedule, so outputs are bit-exact regardless of
  draft quality — the draft only buys throughput (accept rate is
  surfaced in ``stats()``).
- Admission is PAGE accounting, not slot counting: ``submit()`` rejects
  a request whose prompt + max_tokens (+ look-ahead margin) cannot fit
  the page budget with a typed ``ServerOverloaded`` up front, and under
  transient pressure the newest slot is preempted — its pages freed, the
  request requeued at the front; the deterministic key schedule makes
  the re-decode bit-identical, so preemption is invisible in outputs.

The serving posture mirrors ``ParallelInference`` (parallel/resilience.py):
``submit(...) -> Future``, an ``AdmissionController`` watermark on the
waiting queue, per-request deadlines checked between steps, a circuit
breaker over dispatch health, retries for transient faults, and a
``drain()``/``close()`` lifecycle that resolves every outstanding future.
"""

from __future__ import annotations

import collections
import contextlib
import hashlib
import math
import threading
import time
from collections import OrderedDict, deque
from concurrent.futures import Future
from concurrent.futures import TimeoutError as FutureTimeout
from typing import Optional

import numpy as np

from deeplearning4j_tpu.metrics.registry import (MetricsRegistry,
                                                 global_registry)
from deeplearning4j_tpu.metrics.spans import SpanClock
from deeplearning4j_tpu.optimize.bucketing import bucket_length, bucket_pages
from deeplearning4j_tpu.parallel.handoff import (WIRE_VERSION, KVSnapshot,
                                                 RequestMigrated,
                                                 SnapshotInvalid,
                                                 SnapshotUnavailable,
                                                 SnapshotUnsupported,
                                                 corrupt_snapshot,
                                                 pack_snapshot,
                                                 padded_payload,
                                                 truncate_snapshot)
from deeplearning4j_tpu.parallel.resilience import (AdmissionController,
                                                    ChaosPolicy,
                                                    CircuitBreaker,
                                                    CircuitOpen, Deadline,
                                                    DeadlineExceeded,
                                                    RetryPolicy,
                                                    ServerOverloaded)
from deeplearning4j_tpu.parallel.runtime import (CLOSED, DRAINING,
                                                 LoopCrashed, ServingLoop,
                                                 supervisor)

_UNSET = object()

#: the loop's phases whose seconds are ``generation_busy_seconds_total``
#: (by prefix): serving work, as against idle_wait, housekeeping, compile
#: and tick_other
BUSY_PHASES = ("admit", "prefill_", "decode_")

#: what a program that has run before is called under: no span
_WARM = contextlib.nullcontext()

#: pool page 0 never backs real tokens: inactive slots' block-table rows
#: are all zeros, so their masked garbage writes land here
GARBAGE_PAGE = 0

#: what a server cannot serve beside a kind of cache, by the fact about a
#: layer's ``cache_spec()`` that denies it: (the features, the reason a
#: refusal gives, formatted with the layer's name). The features are
#: ``int8`` (``kv_dtype="int8"``), ``tp`` (a head-parallel pool),
#: ``snapshot`` (``snapshot_every``, ``role="prefill"``, ``export_request``,
#: ``adopt_request`` and the snapshot a preempted or migrated slot leaves),
#: ``draft`` (``draft_net``) and ``prefix_cache`` (turned off, not refused)
_LIMITS = {
    "slot_state": (("tp", "snapshot", "draft", "prefix_cache"),
                   "per-slot state ({}): a request's state is more than its "
                   "pages, and a rejected draft token cannot be taken back "
                   "out of it"),
    "window": (("int8", "tp", "snapshot", "draft", "prefix_cache"),
               "a window page class ({}): the pages behind a window are "
               "freed while the request lives"),
    "headless": (("int8", "tp", "snapshot", "draft"),
                 "a plane with no head axis ({}, a latent cache): int8 "
                 "scales, the head-parallel shard and the KVSnapshot's "
                 "leaves are per head"),
}


def _facts(spec) -> tuple:
    """The rows of ``_LIMITS`` that a layer's cache declaration meets."""
    return tuple(fact for fact, held in (
        ("slot_state", spec.kind == "slot_state"),
        ("window", spec.window is not None),
        ("headless", None in spec.head_axes.values())) if held)


def assemble_passage_prefix(doc_ids, passages, *, page_size: int,
                            pad_id: int = 0, query_ids=None):
    """Assemble retrieved passages into a canonical chunk-aligned prompt
    prefix — the admission contract that turns the prefix cache into a
    device-resident document cache.

    Two rules make the page digests collide exactly when the content
    does (``_match_prefix`` hashes ``page_size`` chunks under a chained
    digest, so byte-identical leading pages are the sharing unit):

    - **Canonical order.** Retrieved doc ids are deduplicated and
      sorted ascending, so every request hitting the same documents
      assembles the same byte stream regardless of retrieval-score
      order. Under a skewed (Zipf) query mix the hot documents sort
      first, giving concurrent requests long shared leading runs.
    - **Chunk alignment.** Each passage is padded to a ``page_size``
      multiple with ``pad_id``, so a passage always starts on a page
      boundary and its pages hash identically no matter which
      passages precede it in the shared run.

    Negative ids (IVF empty-slot padding) are dropped. ``query_ids``
    (the user's own prompt tokens) are appended unpadded after the
    prefix — they are per-request and never shared.

    Returns ``(prompt_ids int64, doc_order, prefix_len)``: the full
    prompt, the canonical doc order actually assembled, and how many
    leading tokens are shareable passage prefix."""
    ps = int(page_size)
    if ps < 1:
        raise ValueError(f"page_size must be >= 1, got {page_size}")
    order = sorted({int(i) for i in np.asarray(doc_ids).ravel()
                    if int(i) >= 0})
    parts = []
    for d in order:
        p = np.asarray(passages[d], np.int64).ravel()
        if p.size == 0:
            continue
        pad = -p.size % ps
        if pad:
            p = np.concatenate([p, np.full(pad, int(pad_id), np.int64)])
        parts.append(p)
    prefix = np.concatenate(parts) if parts else np.zeros(0, np.int64)
    plen = int(prefix.size)
    if query_ids is not None:
        q = np.asarray(query_ids, np.int64).ravel()
        prompt = np.concatenate([prefix, q]) if plen else q
    else:
        prompt = prefix
    return prompt, order, plen


class _Request:
    __slots__ = ("prompt", "max_tokens", "temperature", "top_k", "seed",
                 "eos_id", "deadline", "future", "tokens", "t_submit",
                 "t_last", "snapshot", "export_kv")

    def __init__(self, prompt, max_tokens, temperature, top_k, seed,
                 eos_id, deadline):
        self.prompt = prompt
        self.max_tokens = max_tokens
        self.temperature = temperature
        self.top_k = top_k
        self.seed = seed
        self.eos_id = eos_id
        self.deadline = deadline
        self.future = Future()
        self.tokens: list = []
        self.t_submit = time.monotonic()
        # when the loop last handed this request tokens (the first time
        # is the TTFT stamp): ``generation_token_gap_ms`` reads from it
        self.t_last = 0.0
        # a KVSnapshot to resume from instead of prefilling from token 0
        # (set by adopt_request and by a preemption that saved its state)
        self.snapshot = None
        # disaggregated prefill tier: after prefill, export the slot as
        # a KVSnapshot (the future's RESULT) instead of decoding here
        self.export_kv = False


class _PagePool:
    """Host-side accounting for the device page pool: a free stack,
    per-page refcounts, and an LRU prefix cache mapping chained content
    digests to resident pages. Page 0 is the reserved garbage page.
    Owned by the serving loop thread — like ``_slot_req``, never locked;
    ``stats()`` reads are racy-but-atomic snapshots."""

    def __init__(self, pages: int):
        self.total = int(pages)
        self.free = list(range(self.total - 1, 0, -1))  # pop() -> page 1
        self.ref = [0] * self.total
        self.cache: OrderedDict = OrderedDict()  # digest -> page (LRU)
        self.tag: dict = {}                      # page -> digest
        self.evictions = 0
        self.peak = 0

    def in_use(self) -> int:
        """Pages holding live data: refcounted by a slot OR retained by
        the prefix cache (reclaimable, but resident)."""
        return self.total - 1 - len(self.free)

    def alloc(self) -> Optional[int]:
        """One page at refcount 1, evicting the oldest reclaimable
        cached page when the free list is dry; None when exhausted."""
        if not self.free:
            for digest, page in list(self.cache.items()):  # oldest first
                if self.ref[page] == 0:
                    self._uncache(digest, page)
                    self.evictions += 1
                    break
        if not self.free:
            return None
        page = self.free.pop()
        self.ref[page] = 1
        self.peak = max(self.peak, self.in_use())
        return page

    def _uncache(self, digest: bytes, page: int) -> None:
        del self.cache[digest]
        del self.tag[page]
        if self.ref[page] == 0:
            self.free.append(page)

    def share(self, page: int) -> None:
        self.ref[page] += 1

    def release(self, page: int) -> None:
        self.ref[page] -= 1
        if self.ref[page] == 0 and page not in self.tag:
            self.free.append(page)

    def protected(self, page: int) -> bool:
        """True when a write to ``page`` must copy first: another slot or
        the prefix cache depends on its current content."""
        return self.ref[page] > 1 or page in self.tag

    def lookup(self, digest: bytes) -> Optional[int]:
        page = self.cache.get(digest)
        if page is not None:
            self.cache.move_to_end(digest)
        return page

    def register(self, digest: bytes, page: int) -> None:
        """Publish ``page`` for future prefix matches. No-op when the
        digest is already cached (the pristine original wins — a COW
        copy of it is about to diverge) or the page already tagged."""
        if digest in self.cache or page in self.tag:
            return
        self.cache[digest] = page
        self.tag[page] = digest

    def shared_count(self) -> int:
        return sum(1 for r in self.ref if r > 1)

    def refcounted(self) -> int:
        return sum(1 for r in self.ref if r > 0)


class _PageClass:
    """The paged layers whose pages live and die alike, and what the loop
    keeps for them: a page pool, a ``[slots, NP]`` block table and each
    slot's page list, all indexed by logical page (``position //
    page_size``, the same in every class, so a position means the same
    everywhere). ``window`` is ``None`` for layers that keep every token
    of a request (the class ``full``); for layers that read only a row's
    last ``window`` tokens (the class ``window``) the loop frees the pages
    behind them: ``slot_lo[s]`` is slot ``s``'s first live logical page,
    the list's entries before it are the garbage page."""

    def __init__(self, window, layers, token_bytes: int):
        self.name = "full" if window is None else "window"
        self.window = window
        self.layers = tuple(layers)
        self.token_bytes = int(token_bytes)
        self.pages_total = 0
        self.pool = self.bt = self.slot_pages = self.slot_lo = None

    def reset(self, slots: int, n_pages: int) -> None:
        self.pool = _PagePool(self.pages_total)
        self.bt = np.zeros((slots, n_pages), np.int32)
        self.slot_pages = [[] for _ in range(slots)]
        self.slot_lo = [0] * slots


def _tables(bt) -> tuple:
    """A program's ``bt`` operand as a tuple of tables by class."""
    return bt if isinstance(bt, tuple) else (bt,)


def _per_row(flags, like):
    """``[S]`` flags shaped to broadcast over the rows of ``like``."""
    return flags.reshape((-1,) + (1,) * (like.ndim - 1))


def _seed_extras(carry, pool, slot_st, counted, fresh=None):
    """Into a dispatch's carry: each slot-state layer's block out of the
    pool (rows ``fresh`` zeroed first), and zeroed counts for each layer
    that carries some out (``counted``: name and how many). Traced; no-ops
    for a net with neither."""
    import jax.numpy as jnp

    for vn in slot_st:
        block = pool[vn]
        if fresh is not None:
            block = {k: jnp.where(_per_row(fresh, a),
                                  jnp.zeros((), a.dtype), a)
                     for k, a in block.items()}
        carry[vn] = dict(block)
    for vn, n in counted:
        carry[vn] = {"call_counts": jnp.zeros((n,), jnp.int32)}


def _keep_rows(pool, nc, slot_st, advanced):
    """Slot state after a dispatch, by layer name: what the forward left
    for the rows it ``advanced``, and exactly what was there for every
    other row."""
    import jax.numpy as jnp

    return {vn: {k: jnp.where(_per_row(advanced, a), nc[vn][k], a)
                 for k, a in pool[vn].items()}
            for vn in slot_st}


def _take_rows(pool, slot_st, rows):
    """Each slot-state layer's block at the slots ``rows`` names, by layer
    name. A padding row's index lies past the last slot and reads the last
    slot's block; ``_put_rows`` never writes it back."""
    import jax.numpy as jnp

    return {vn: {k: jnp.take(a, rows, axis=0, mode="clip")
                 for k, a in pool[vn].items()}
            for vn in slot_st}


def _put_rows(pool, nc, slot_st, rows):
    """Slot state after a row group's forward, by layer name: what the
    forward left for row ``i`` written into slot ``rows[i]``, every other
    slot exactly as it was. An index past the last slot (a padding row) is
    dropped, and the live rows of a group name distinct slots, so no slot
    is written twice."""
    return {vn: {k: a.at[rows].set(nc[vn][k], mode="drop")
                 for k, a in pool[vn].items()}
            for vn in slot_st}


def _call_counts(nc, counted):
    """What each counting layer counted in one forward, by layer name."""
    return {vn: nc[vn]["call_counts"] for vn, _ in counted}


class GenerationServer:
    """Paged continuous-batching decode server for a causal LM.

    ``net`` must stream through an explicit cache carry (TransformerLM:
    attention kcache/vcache + positional counters); the caches are
    re-homed into a page pool (``init_paged_carry``). ``submit`` returns
    a ``concurrent.futures.Future`` resolving to the generated token ids
    (numpy int array, EOS token included when hit).

    Each streaming layer declares its carry in a ``cache_spec()``
    (``nn/conf/layers/base.CacheSpec``), and what the server cannot serve
    beside a kind of cache is one table, ``_LIMITS``: ``_probe_net`` reads
    both once, refuses typed what was asked for and cannot be served, and
    every later check reads the table (``_limits``).

    The pool carries whatever planes a paged layer declares
    (``planes``: pool plane -> its dense view and the view's token
    axis): a key and a value per head for ``SelfAttentionLayer``, a
    token's heads side by side in one row of a page
    (``[pages, page_size, H * d]``: the order its page write and its
    read kernel share, so no program transposes the pool), ONE latent
    row for all heads for ``LatentAttentionLayer``. What order a plane
    has is its layer's to know: the layer makes dense views of pages
    (``paged_views``), page rows of a view's written column
    (``paged_settle``), and the snapshot wire format's canonical stacks
    of fetched ones (``paged_to_wire`` / ``paged_from_wire``). A
    resident token costs the
    sum of the layers' ``paged_token_bytes`` (``stats()["pages"]
    ["bytes_per_token"]``, cross-checked against the allocated arrays);
    page copies, the prefix cache and the decode family's dense views work
    per plane, by the layer's declaration, never by a plane's name.

    Paging knobs: ``page_size`` tokens per KV page (must divide the
    attention ``max_cache``); ``pages`` total pool pages (default
    ``slots * max_cache/page_size + 1`` — dense-equivalent capacity; set
    lower to serve long-tail workloads in less memory); ``prefix_cache``
    toggles chunk-hash prefix sharing; ``steps_per_dispatch`` decode
    micro-steps fused per host round trip; ``prefill_chunk`` caps the
    tokens a prefill round consumes per row (Sarathi-style chunked
    prefill — long prompts advance through several bounded dispatches
    instead of one huge one, without changing any output bit).

    Prefill computes the rows it admitted: a round's rows are packed into
    row groups, each one dispatch over ``width x bucket`` positions whose
    rows are gathered by slot index; slots outside the group (free, or
    decoding) are not in the dispatch. The width is the server's
    (``PREFILL_ROWS``, at most ``slots``, and as many rows of
    ``prefill_chunk`` columns as ``PREFILL_POSITIONS`` holds: one row
    under a chunk of 1,024), one number whatever the bucket, so there is
    one prefill program per bucket.

    ``kv_dtype="int8"`` stores the page pool int8 with per-page-row f32
    scales (attention quantizes on write, dequantizes on gather): a
    resident token of a key/value layer costs a byte a number and a scale
    a head instead of the conf dtype's itemsize a number — ~3.5x more
    tokens per HBM byte at f32 — at the
    price of a bounded greedy-agreement delta instead of bit-exactness
    (the default ``None`` keeps the conf dtype and stays bit-exact).
    COW page copies and the prefix cache carry the scale planes with
    the values, so sharing semantics are unchanged.

    Slot state: a layer whose streaming carry is per SEQUENCE rather than
    per token (kind ``slot_state``: a state-space layer's convolution tail
    and scan state) gets a ``[slots, ...]`` block in the same donated pool,
    beside the pages. A slot's block is zeroed when a request's first
    prefill round is admitted into it, continues across the rounds of a
    long prompt (gathered into the round's row group and scattered back),
    and is left bit-identical for every slot that a dispatch does not
    advance.

    Page classes: a paged layer declares how much of a request it reads
    (``window``: ``None`` keeps everything, a number is a sliding
    window). Layers are grouped by it into classes (``_PageClass``), each
    with a page pool, a page count (``pages`` may be a dict by class name,
    ``full`` / ``window``), a block table and planes of its own. A window
    class allocates a prompt's pages round by round, and before every
    dispatch the loop returns to the pool the pages whose last token no
    query of that dispatch can see and points their table entries at the
    garbage page; its dense view in the decode family is ``window +
    steps_per_dispatch + page_size`` tokens wide and starts at the row's
    first live page. Admission, reservation, preemption (resume by
    recomputing) and ``stats()["pages"]["classes"]`` reckon per class. A
    net whose layers all keep everything is one class and runs the
    programs, the arguments and the code path it ran before classes
    existed.

    Speculative decoding: pass a small ``draft_net`` (same vocab, its own
    weights, ``max_cache >= `` the target's) and ``spec_k >= 2``; each
    round the draft proposes ``spec_k - 1`` tokens and the target
    verifies all ``spec_k`` positions in one chunked dispatch. Bit-exact
    with the non-speculative paths by construction.
    """

    # Decode-loop-owned state (conc-loop-ownership, see
    # analysis/concurrency_rules.py): every write happens under ``_cond``
    # but the tick thread reads it lock-free between dispatches.
    _LOOP_OWNED = ("_slot_req",)
    _LOOP_LOCK = "_cond"
    #: rows of a prefill dispatch (a row group), whatever the column bucket;
    #: the server's, not the user's. Two: a steady stream admits one or two
    #: requests a round, and per dispatch the fixed part (the weights read
    #: once, the pool) is small beside a row of 256 columns, so a wave of up
    #: to six rows is no slower than one dispatch over every slot, and only
    #: a burst of more pays for its extra dispatches (PERF.md, PR 31)
    PREFILL_ROWS = 2
    #: positions (rows x the chunk's columns) a prefill dispatch computes at
    #: most: a row group is as wide as fits, so a server whose chunk is
    #: 1,024 columns dispatches one row. Beside a row that wide the fixed
    #: part is small, and a second row is padding whenever one request is
    #: admitted alone, at a live row's price (PERF.md, PR 38)
    PREFILL_POSITIONS = 1024

    def __init__(self, net, vocab: int, *, slots: int = 8,
                 eos_id: Optional[int] = None,
                 max_pending: int = 64,
                 request_deadline_s: Optional[float] = None,
                 min_prefill_bucket: int = 8,
                 prefill_chunk: int = 256,
                 page_size: int = 16,
                 pages=None,
                 prefix_cache: bool = True,
                 steps_per_dispatch: int = 4,
                 kv_dtype: Optional[str] = None,
                 paged_attention: Optional[str] = None,
                 mesh=None,
                 tp: Optional[int] = None,
                 draft_net=None,
                 spec_k: int = 4,
                 snapshot_every: int = 0,
                 role: str = "unified",
                 retry: Optional[RetryPolicy] = None,
                 breaker: Optional[CircuitBreaker] = None,
                 chaos: Optional[ChaosPolicy] = None,
                 registry: Optional[MetricsRegistry] = None):
        if slots < 1:
            raise ValueError(f"slots must be >= 1, got {slots}")
        if page_size < 1:
            raise ValueError(f"page_size must be >= 1, got {page_size}")
        if steps_per_dispatch < 1:
            raise ValueError(f"steps_per_dispatch must be >= 1, got "
                             f"{steps_per_dispatch}")
        if prefill_chunk < 1:
            raise ValueError(f"prefill_chunk must be >= 1, got "
                             f"{prefill_chunk}")
        self.net = net
        self.vocab = int(vocab)
        self.slots = int(slots)
        self.eos_id = eos_id
        self.request_deadline_s = request_deadline_s
        self.min_prefill_bucket = int(min_prefill_bucket)
        self.prefill_chunk = int(prefill_chunk)
        if kv_dtype not in (None, "int8"):
            raise ValueError(f"unsupported kv_dtype {kv_dtype!r} "
                             "(None or 'int8')")
        # paged-attention read backend (the PagedAttentionHelper seam):
        # the one place it can be set. None is "auto"; the RESOLVED
        # backend (_probe_net) tags every serving program's cache key, so
        # xla/pallas families never share traces, and reaches the layers
        # in the carry (_carry_builder).
        if paged_attention not in (None, "auto", "xla", "pallas"):
            raise ValueError(
                f"unsupported paged_attention {paged_attention!r} "
                "(None, 'auto', 'xla' or 'pallas')")
        self.paged_attention = paged_attention
        self.prefix_cache = bool(prefix_cache)
        self.steps_per_dispatch = int(steps_per_dispatch)
        self.kv_dtype = kv_dtype
        self._kv_quant = kv_dtype == "int8"
        self.spec_k = int(spec_k)
        # crash-durable serving: every `snapshot_every` generated tokens
        # a long-running slot's KV state is exported to a KVSnapshot and
        # attached to its future (0 = off). The draft's dense cache is
        # not part of the wire format, so speculative servers cannot
        # snapshot.
        self.snapshot_every = max(0, int(snapshot_every))
        # disaggregated serving tier. "prefill": submits default to
        # export_kv=True — chunked wave prefill runs to completion, then
        # the request ships out as a KVSnapshot (the future's result)
        # instead of entering the decode loop. "decode": a tier label
        # for routers; the server itself serves adoptions AND plain
        # submits (the token-0 fallback target). "unified": classic
        # co-located serving.
        if role not in ("unified", "prefill", "decode", "generate"):
            raise ValueError(f"role must be 'unified', 'prefill', "
                             f"'decode' or 'generate', got {role!r}")
        self.role = role
        self.admission = AdmissionController(max_pending)
        self.retry = retry if retry is not None else RetryPolicy()
        self.breaker = breaker if breaker is not None else CircuitBreaker()
        self._chaos = chaos

        # tensor-parallel decode: the paged KV pool shards head-parallel
        # over the mesh's "model" axis ([P, ps, (H/tp) * d] per chip) while
        # weights, activations and the host-owned block table stay
        # replicated — the only collective in the whole decode step is
        # an exact all-gather of disjoint per-head contexts, so outputs
        # are bit-identical to the single-chip path at every tp.
        # ``tp=`` is the convenience spelling (builds a model_mesh over
        # the first tp devices); an explicit ``mesh=`` wins and lets a
        # fleet pin each replica group to its own device subset.
        # tp in (None, 1) keeps the single-chip path byte-for-byte.
        from deeplearning4j_tpu.parallel.mesh import (MODEL_AXIS,
                                                      MeshGeometryError,
                                                      model_mesh)
        if mesh is None and tp is not None and int(tp) != 1:
            mesh = model_mesh(int(tp))
        if mesh is not None:
            if MODEL_AXIS not in mesh.axis_names:
                raise MeshGeometryError(
                    f"GenerationServer mesh needs a {MODEL_AXIS!r} axis "
                    f"to shard KV heads over, got axes {mesh.axis_names}")
            if tp is not None and int(tp) != mesh.shape[MODEL_AXIS]:
                raise MeshGeometryError(
                    f"tp={tp} disagrees with the mesh's "
                    f"{mesh.shape[MODEL_AXIS]}-way {MODEL_AXIS!r} axis")
        self._mesh = None if (mesh is None
                              or mesh.shape[MODEL_AXIS] == 1) else mesh
        self._tp = 1 if self._mesh is None \
            else int(self._mesh.shape[MODEL_AXIS])
        # where this server's pool and weights live: replicated over the
        # tensor-parallel mesh, on the ONE chip a fleet pinned this
        # replica to (a 1-device mesh, device_groups(n, 1)), or None —
        # jax's default device, where the net's arrays already are
        from jax.sharding import (NamedSharding, PartitionSpec,
                                  SingleDeviceSharding)
        if self._mesh is not None:
            self._home = NamedSharding(self._mesh, PartitionSpec())
        elif mesh is not None:
            self._home = SingleDeviceSharding(mesh.devices.flat[0])
        else:
            self._home = None
        self._placed_weights: dict = {}

        self._ps = int(page_size)
        # prefill rounds advance at most this many (page-aligned) tokens
        # per dispatch, bounding the transient [S, chunk, ...] prefill
        # activations regardless of prompt length
        self._chunk_cap = max(self._ps,
                              self.prefill_chunk // self._ps * self._ps)
        self._prefill_rows = max(1, min(
            self.PREFILL_ROWS, self.slots,
            self.PREFILL_POSITIONS // self._chunk_cap))
        self._draft = draft_net
        self._draft_cap = None
        self._probe_net()
        # decode-write look-ahead per dispatch: M fused micro-steps, or
        # the K-token speculative chunk
        self._lookahead = self.spec_k if draft_net is not None \
            else self.steps_per_dispatch
        # the pool may be SMALLER than slots x full capacity (that is the
        # point: HBM ∝ resident tokens) — submit() rejects any single
        # request the budget cannot cover, and transient multi-slot
        # pressure preempts the newest slot; only the garbage page plus
        # one usable page are unconditionally required
        by_class = pages if isinstance(pages, dict) else {
            c.name: pages for c in self._classes}
        unknown = set(by_class) - {c.name for c in self._classes}
        if unknown:
            raise ValueError(f"pages names {sorted(unknown)}: this net's "
                             f"page classes are "
                             f"{[c.name for c in self._classes]}")
        for c in self._classes:
            n = by_class.get(c.name)
            if n is None:
                n = self.slots * self._live_pages(c) + 1
            c.pages_total = int(n)
            if c.pages_total < 2:
                raise ValueError(f"pages={c.pages_total} must be >= 2 "
                                 "(the reserved garbage page + one usable)")
        self.pages_total = self._classes[0].pages_total
        self._page_bytes = self._page_token_bytes * self._ps

        if draft_net is not None:
            if self.spec_k < 2:
                raise ValueError(f"spec_k must be >= 2 (one verified "
                                 f"chunk needs at least one draft token), "
                                 f"got {self.spec_k}")
            self._probe_draft()
        self._cond = threading.Condition()
        self._queue: deque = deque()
        self._slot_req: list = [None] * self.slots
        self._n_active = 0
        self._active_cap = self.slots
        # distinguishes a deliberate close() from a crash-forced CLOSED
        # state: the supervisor only restarts the loop when this is False
        self._user_close = False

        # host mirrors of the per-slot decode state fed to the step
        self._last = np.zeros(self.slots, np.int32)
        self._counts = np.zeros(self.slots, np.int32)
        self._temp = np.zeros(self.slots, np.float32)
        self._topk = np.zeros(self.slots, np.int32)
        self._keys = np.zeros((self.slots, 2), np.uint32)
        # host-owned paging state: per-slot positions, block table, and
        # page lists (loop-thread-owned, like _slot_req)
        self._pos = np.zeros(self.slots, np.int32)
        self._reset_paging()
        self._slot_seq = [0] * self.slots
        self._admit_seq = 0
        # handoff state: per-slot token count at the last snapshot, the
        # export handshake queue ((request future, out future) pairs the
        # loop services between dispatches), and the drain-migrate flag
        self._snap_counts = [0] * self.slots
        self._export_q: deque = deque()
        self._migrating = False
        self._migrate_cb = None

        # serving counters live in the (leaf-locked) registry, so the
        # loop thread publishes without ever touching ``_cond`` and a
        # scrape never blocks admission; ``_cond`` only guards queue and
        # slot structure
        self.metrics = registry if registry is not None \
            else MetricsRegistry()
        m = self.metrics
        self._m_admitted = m.counter(
            "generation_admitted_total", "requests committed to a slot")
        self._m_expired = m.counter(
            "generation_expired_total", "requests failed on deadline")
        self._m_retired = m.counter(
            "generation_retired_total", "slots retired")
        self._m_completed = m.counter(
            "generation_completed_total", "requests completed")
        self._m_failed = m.counter(
            "generation_failed_total", "requests failed on error")
        self._m_retried = m.counter(
            "generation_retried_total", "dispatch retries")
        self._m_pool_rebuilds = m.counter(
            "generation_pool_rebuilds_total",
            "device-state rebuilds after a hard dispatch fault")
        self._m_prefills = m.counter(
            "generation_prefills_total", "prompts prefilled")
        self._m_decode_steps = m.counter(
            "generation_decode_steps_total", "decode dispatches")
        self._m_tokens = m.counter(
            "generation_tokens_total", "tokens generated")
        self._m_busy_s = m.counter(
            "generation_busy_seconds_total",
            "wall seconds of the loop thread's admission, prefill and "
            "decode phases (a prefill wave once, however many rows)")
        # process-wide too: a reader reaches these after the server is gone
        regs = [m] if m is global_registry() else [m, global_registry()]
        # the loop thread's phases: ``gen:<phase>`` spans in a profiler's
        # trace, and their seconds and counts here, published once a tick
        loop_s = [reg.counter(
            "generation_loop_seconds_total",
            "seconds of the loop thread by phase; every second of a "
            "working tick is under exactly one (tick_other: what no named "
            "phase covered)", labels=("phase",)) for reg in regs]
        loop_n = [reg.counter(
            "generation_loop_spans_total",
            "spans of the loop thread by phase: decode_dispatch and "
            "prefill_dispatch count dispatches, tick_other counts ticks",
            labels=("phase",)) for reg in regs]

        def phase_counters(phase):
            seconds = [f.labels(phase=phase) for f in loop_s]
            if phase.startswith(BUSY_PHASES):
                seconds.append(self._m_busy_s)
            return seconds, [f.labels(phase=phase) for f in loop_n]

        self._clock = SpanClock("gen:", phase_counters)
        #: the programs this server has called (see ``_first_call``)
        self._called = set()
        self._m_queue_wait = [reg.histogram(
            "generation_queue_wait_ms",
            "submit() to the loop taking the request off the queue; a "
            "re-queued or resumed request observes again")
            for reg in regs]
        self._m_token_gap = [reg.histogram(
            "generation_token_gap_ms",
            "between two deliveries of tokens to one request (a delivery "
            "is what one decode fetch hands it, the first is its first "
            "token): what a streaming client waits between bursts")
            for reg in regs]
        self._m_cow_copies = [reg.counter(
            "generation_cow_copies_total", "copy-on-write page copies")
            for reg in regs]
        self._m_preempted = m.counter(
            "generation_preempted_total",
            "slots preempted under page-pool pressure")
        self._m_prefix_hits = m.counter(
            "generation_prefix_hits_total",
            "prompts that reused a cached prefix")
        self._m_prefix_reused = [reg.counter(
            "generation_prefix_tokens_reused_total",
            "prompt tokens served from the prefix cache") for reg in regs]
        self._m_prompt_tokens = [reg.counter(
            "generation_prompt_tokens_admitted_total",
            "prompt tokens of requests staged into a slot, those taken "
            "from cached pages among them") for reg in regs]
        self._m_spec_rounds = m.counter(
            "generation_spec_rounds_total", "speculative decode rounds")
        self._m_spec_proposed = m.counter(
            "generation_spec_proposed_total", "draft tokens proposed")
        self._m_spec_accepted = m.counter(
            "generation_spec_accepted_total", "draft tokens accepted")
        self._m_handoff_snapshots = m.counter(
            "generation_handoff_snapshots_total",
            "KV snapshots exported (periodic, explicit, and migrate)")
        self._m_handoff_bytes = m.counter(
            "generation_handoff_bytes_total",
            "wire bytes of exported KV snapshots")
        self._m_handoff_resumes = m.counter(
            "generation_handoff_resumes_total",
            "requests resumed from an adopted KV snapshot")
        self._m_handoff_saved = m.counter(
            "generation_handoff_tokens_saved_total",
            "decoded tokens NOT regenerated thanks to snapshot resume")
        self._m_handoff_fallbacks = m.counter(
            "generation_handoff_fallbacks_total",
            "adoptions that fell back to token-0 prefill")
        self._m_preempt_resumes = m.counter(
            "generation_handoff_preempt_resumes_total",
            "preemptions that saved a snapshot instead of recomputing")
        self._m_migrated = m.counter(
            "generation_handoff_migrated_total",
            "requests migrated off this server by drain(migrate=...)")
        self._m_prefill_exports = m.counter(
            "generation_prefill_exports_total",
            "requests exported as KVSnapshots after prefill "
            "(disaggregated prefill tier)")
        self._m_prefill_rounds = m.counter(
            "generation_prefill_rounds_total",
            "prefill dispatches (one per row group of a wave's chunk round)")
        self._m_prefill_host_bytes = m.counter(
            "generation_prefill_host_bytes_total",
            "bytes of the host arrays built for prefill dispatches (slot "
            "indices, token ids, mask, positions, lengths, sampling rows, "
            "keys; not the standing block table; weights and pool live "
            "on the device)")
        self._m_prefill_rows = {
            kind: [reg.counter(
                "generation_prefill_rows_total",
                "rows of prefill dispatches: admitted (rows that served a "
                "request's chunk) and computed (the dispatch's row width)",
                labels=("kind",)).labels(kind=kind) for reg in regs]
            for kind in ("admitted", "computed")}
        # what a decode dispatch's paged reads had to fetch, and what the
        # read backend fetched: reckoned on the host from the positions the
        # loop holds (no fetch), times the paged layers
        self._m_kv_tokens = {
            kind: [reg.counter(
                f"generation_kv_{kind}_tokens_total", chelp,
                labels=("program",)).labels(program="decode")
                for reg in regs]
            for kind, chelp in (
                ("live", "keys a paged read had to fetch: the context "
                 "length of each advancing row, summed over rows, "
                 "micro-steps and paged layers"),
                ("viewed", "keys the read backend fetched for them: under "
                 "xla rows x capacity a micro-step (the pool gathered into a "
                 "dense view), under pallas each advancing row's live pages "
                 "x page size (pages read in place)"))}
        # how a prefill dispatch's grouped paged reads took their query
        # rows (``chunk_read``): blocked over the keys, or over float32
        # scores of the whole view; reckoned from the bucket by the
        # layer's own predicate, published only where such a layer is.
        # Queries and views are the conf dtype (an int8 pool's views are
        # dequantised to the queries' dtype before the read)
        self._read_dtypes = (self.net.conf.dtype,) * 2
        self._chunk_readers = [
            layer for layer in map(self._layer_by_name.get,
                                   self._paged_names)
            if layer.prefill_read_path(self._chunk_cap, *self._read_dtypes)]
        self._m_prefill_read = {
            path: [reg.counter(
                "generation_prefill_read_rows_total",
                "query rows of prefill dispatches (row width x column "
                "bucket) summed over the grouped paged layers, by how the "
                "read took them: blocked (one kernel over key blocks, "
                "scores in VMEM) or materialized (float32 scores of the "
                "whole view in HBM)", labels=("path",)).labels(path=path)
                for reg in regs]
            for path in ("blocked", "materialized")
        } if self._chunk_readers else None
        # which branch of gen_decode's sampler the dispatched ``temp``
        # picks: the program's own predicate, evaluated on the host
        # a net with a window class: the same two counts split by class
        # (a sibling family: the registry holds one label set a family, and
        # readers of the two above read their ``program=decode`` child),
        # the pages each class holds, the pages the loop freed behind a
        # window, and the bytes resident at each decode dispatch beside
        # what one table for all paged layers would hold for the same slots
        self._m_class = None
        if self._windowed:
            self._m_class = {
                "tokens": {
                    (kind, c.name): [reg.counter(
                        f"generation_cache_kv_{kind}_tokens_total",
                        f"generation_kv_{kind}_tokens_total by page class "
                        "(live in a window class: min(context, window))",
                        labels=("cache", "program")).labels(
                            cache=c.name, program="decode") for reg in regs]
                    for kind in ("live", "viewed") for c in self._classes},
                "released": [reg.counter(
                    "generation_window_pages_released_total",
                    "pages of a window class returned to its pool because "
                    "no query of the next dispatch could see their tokens")
                    for reg in regs],
                "resident": {
                    layout: [reg.counter(
                        "generation_kv_resident_bytes_total",
                        "bytes of the pages in use, summed over decode "
                        "dispatches: as the page classes hold them "
                        "(classes) and as one table for every paged layer "
                        "would for the same slots (uniform)",
                        labels=("layout",)).labels(layout=layout)
                        for reg in regs]
                    for layout in ("classes", "uniform")},
                # set by the loop at each decode dispatch
                "pages": {
                    c.name: [reg.gauge(
                        "generation_cache_pages_in_use",
                        "pages holding live data, by page class",
                        labels=("cache",)).labels(cache=c.name)
                        for reg in regs]
                    for c in self._classes}}
        self._m_sampler_steps = {
            path: [reg.counter(
                "generation_sampler_steps_total",
                "micro-steps of decode dispatches by the sampler's branch: "
                "select (some row has a temperature above 0: every row's "
                "top-k cut is selected, then sampled) or greedy (argmax "
                "alone)", labels=("path",)).labels(path=path)
                for reg in regs]
            for path in ("select", "greedy")}
        # how the programs embed their tokens (``lm_stream_forward``): rows
        # of the table by a gather where the net's structure allows, else a
        # one-hot product over the whole table; a decode or prefill
        # dispatch books the token rows it embedded under the net's path
        from deeplearning4j_tpu.models.zoo import embedding_vertex

        self._embed_path = ("gather" if embedding_vertex(self.net, self.vocab)
                            else "one_hot")
        self._m_embed_rows = {
            path: [reg.counter(
                "generation_embed_rows_total",
                "token rows the serving programs embedded (decode: rows x "
                "micro-steps; prefill: row width x column bucket), by how: "
                "gather (the table's rows) or one_hot (a one-hot block's "
                "product with the whole table)", labels=("path",))
                .labels(path=path) for reg in regs]
            for path in ("gather", "one_hot")}
        self._m_slot_resets = m.counter(
            "generation_slot_state_resets_total",
            "per-slot state blocks zeroed at admission")
        # what the net's layers count per forward call (their
        # ``cache_spec().counters``), summed per dispatch and split by the
        # program that ran; also on the process-wide registry, where a
        # reader can reach them after the server is gone
        self._m_counted = {
            program: {
                name: [[reg.counter("generation_" + cname, chelp,
                                    labels=(*lbl, "program"))
                        .labels(program=program, **lbl) for reg in regs]
                       for cname, chelp, lbl
                       in self._layer_by_name[name].cache_spec().counters]
                for name, _ in self._counted}
            for program in ("prefill", "decode")}
        # decode dispatches on the process-wide registry too, beside the
        # counts above: a reader's denominator after the server is gone
        self._m_decode_steps_pw = [reg.counter(
            "generation_decode_steps_total", "decode dispatches")
            for reg in regs[1:]]
        # the bytes of one held routed expert as the net stores them: the
        # parameters a counting layer declares stacked by expert, one
        # slice each, averaged over such layers; none where no layer has
        # experts
        held = []
        for name, _ in self._counted:
            leaves = [self.net.params[name][p] for p in
                      self._layer_by_name[name].cache_spec().expert_params]
            if leaves:
                held.append(sum(math.prod(a.shape) * np.dtype(a.dtype)
                                .itemsize for a in leaves)
                            / leaves[0].shape[0])
        if held:
            expert_bytes = sum(held) / len(held)
            m.gauge("generation_moe_expert_bytes",
                    "bytes of one held routed expert's weights as the net "
                    "stores them (the mean over expert layers)",
                    fn=lambda: expert_bytes)
            for reg in regs[1:]:
                reg.gauge("generation_moe_expert_bytes",
                          "bytes of one held routed expert's weights, of "
                          "the server built last").set(expert_bytes)
        m.gauge("generation_slot_state_bytes",
                "bytes of per-slot state beside the page pool",
                fn=lambda: self._slot_state_bytes)
        m.gauge("generation_slots", "decode slot pool size",
                fn=lambda: self.slots)
        m.gauge("generation_active_slots", "slots currently decoding",
                fn=lambda: self._n_active)
        m.gauge("generation_active_slot_cap",
                "autoscaler admission cap on concurrently active slots",
                fn=lambda: self._active_cap)
        m.gauge("generation_queue_depth", "requests waiting for a slot",
                fn=lambda: len(self._queue))
        m.gauge("generation_pending", "admitted-but-unresolved requests",
                fn=lambda: self.admission.pending)
        m.gauge("generation_accepted", "requests accepted by admission",
                fn=lambda: self.admission.accepted)
        m.gauge("generation_rejected", "requests rejected by admission",
                fn=lambda: self.admission.rejected)
        m.gauge("generation_breaker_open",
                "circuit state (0 closed, 0.5 half-open, 1 open)",
                fn=self._breaker_level)
        m.gauge("generation_pages_free", "unallocated KV pages",
                fn=lambda: sum(len(c.pool.free) for c in self._classes))
        m.gauge("generation_pages_cached", "prefix-cache-pinned KV pages",
                fn=lambda: len(self._page_pool.cache))
        m.gauge("generation_resident_kv_bytes", "bytes of resident KV",
                fn=self._resident_bytes)
        # KV-residency telemetry on the Prometheus surface, not just
        # /stats: total/in-use/shared occupancy, the high-water mark,
        # and the cache geometry (bytes/token + int8 flag)
        m.gauge("generation_pages_total", "KV page-pool size "
                "(incl. the reserved garbage page)",
                fn=lambda: sum(c.pages_total for c in self._classes))
        m.gauge("generation_pages_in_use",
                "pages holding live data (refcounted or prefix-cached)",
                fn=lambda: sum(c.pool.in_use() for c in self._classes))
        m.gauge("generation_pages_shared",
                "pages refcounted by more than one slot",
                fn=lambda: self._page_pool.shared_count())
        m.gauge("generation_peak_resident_kv_bytes",
                "high-water resident KV bytes",
                fn=lambda: self._resident_bytes(peak=True))
        m.gauge("generation_kv_bytes_per_token",
                "bytes a resident token costs over all paged layers, as "
                "they declare it (paged_token_bytes)",
                fn=lambda: self._page_token_bytes)
        for reg in regs[1:]:
            reg.gauge("generation_kv_bytes_per_token",
                      "bytes a resident token costs over all paged layers "
                      "of the server built last").set(self._page_token_bytes)
        m.gauge("generation_kv_cache_int8",
                "1 when pages store int8 (+f32 scales), 0 for conf dtype",
                fn=lambda: 1.0 if self._kv_quant else 0.0)

        self._pool = self._fresh_pool()
        self._dpool = None if draft_net is None else self._fresh_draft_pool()
        self._runtime = ServingLoop("generation-server",
                                    tick=self._tick_once,
                                    wake=self._wake_loop, chaos=chaos)
        self._runtime.start()
        supervisor().watch(self._runtime, on_death=self._on_loop_death,
                           restart=True)

    # ------------------------------------------------- lifecycle state
    @property
    def _closing(self) -> bool:
        """True once the lifecycle left RUNNING (draining or closed)."""
        return self._runtime.state in (DRAINING, CLOSED)

    @property
    def _stop(self) -> bool:
        return self._runtime.state is CLOSED

    def _breaker_level(self) -> float:
        if self.breaker is None:
            return 0.0
        return {"closed": 0.0, "half_open": 0.5,
                "open": 1.0}.get(self.breaker.state, 0.0)

    @property
    def active_slot_cap(self) -> int:
        """Admission cap on concurrently ACTIVE slots. Slot count is
        baked into the compiled program shapes, so autoscaling never
        resizes the pool — it bounds how many slots ``_admit_free_slots``
        may fill, which is retrace-free."""
        with self._cond:
            return self._active_cap

    def set_active_slots(self, n: int) -> int:
        """Clamp and apply a new active-slot admission cap (autoscaler
        hook). Lowering the cap never evicts running requests; it only
        stops new admissions until occupancy falls below the cap."""
        n = max(1, min(int(n), self.slots))
        with self._cond:
            self._active_cap = n
            self._cond.notify_all()
        return n

    def queue_depth(self) -> int:
        with self._cond:
            return len(self._queue)

    # ------------------------------------------------------ introspection
    def _probe_net(self):
        """Classify the net's streaming layers for the paged carry by what
        each declares (``cache_spec()``): which hold pageable caches (and
        which planes, at how many bytes a token), which carry positions,
        per-slot state or call counts; what the server cannot serve beside
        them (``_limits``, from ``_LIMITS``), refusing what was asked for
        of it; and the block-table geometry from the cache capacity."""
        from deeplearning4j_tpu.parallel.mesh import MeshGeometryError

        net = self.net
        net.rnn_clear_previous_state()
        probe = net._seed_streaming_carry(1)
        cap = net._stream_capacity
        net.rnn_clear_previous_state()
        self._paged_names: list = []
        self._pos_names: list = []
        self._slot_names: list = []     # per-slot state beside the pages
        self._counted: list = []        # (name, how many) call counts
        self._layer_by_name: dict = {}
        #: feature -> why the first layer that denies it does
        self._limits: dict = {}
        by_kind = {"pages": self._paged_names, "positions": self._pos_names,
                   "slot_state": self._slot_names}
        for name, layer in net._stream_layers():
            c = probe.get(name)
            if not c:
                continue
            spec = layer.cache_spec()
            if spec is None or not set(spec.keys) <= set(c) <= set(
                    spec.keys).union(v for v, _ in spec.planes.values()):
                raise ValueError(
                    f"layer {name!r} streams through a carry the pool "
                    "cannot host: its keys are not what its cache_spec() "
                    "declares")
            self._layer_by_name[name] = layer
            if spec.kind == "counts":
                self._counted.append((name, len(spec.counters)))
            else:
                by_kind[spec.kind].append(name)
            for fact in _facts(spec):
                features, why = _LIMITS[fact]
                for feature in features:
                    self._limits.setdefault(feature, why.format(repr(name)))
        if self._draft is not None:
            self._limits.setdefault(
                "snapshot", "draft_net: the speculative draft's dense KV "
                "cache is not part of the KVSnapshot wire format")
        for feature, asked, what, exc in (
                ("int8", self._kv_quant, "kv_dtype='int8'", ValueError),
                ("tp", self._mesh is not None, "tp > 1", MeshGeometryError),
                ("snapshot", self.snapshot_every, "snapshot_every",
                 ValueError),
                ("snapshot", self.role == "prefill", "role='prefill'",
                 ValueError),
                ("draft", self._draft is not None, "draft_net",
                 ValueError)):
            if asked:
                self._refuse(feature, what, exc)
        if "prefix_cache" in self._limits:
            # a cached page holds its tokens' keys and values, not a slot's
            # state at the page's end, nor a window class's pages for the
            # prefix's last tokens, which were freed (ROADMAP R4)
            self.prefix_cache = False
        if not self._paged_names or cap is None:
            raise ValueError(
                "net has no seedable streaming KV carry — GenerationServer "
                "serves KV-cache streaming language models (TransformerLM)")
        self._page_token_bytes = 0
        by_window: dict = {}            # window -> (names, bytes)
        for name in self._paged_names:
            layer = self._layer_by_name[name]
            # past the refusals, a paged layer over a mesh has head axes:
            # an attention layer's key/value heads
            if self._mesh is not None and layer.kv_heads % self._tp:
                raise MeshGeometryError(
                    f"layer {name!r} has {layer.kv_heads} heads, not "
                    f"divisible by tp={self._tp}: the head-parallel pool "
                    "shard [pages, page_size, (H/tp) * d] would be ragged")
            # admission accounting tracks the CACHE dtype, not the conf
            # dtype, by the layer's own reckoning (the _fresh_pool
            # allocation cross-checks it against the real array bytes)
            nbytes = layer.paged_token_bytes(net.conf.dtype, self.kv_dtype)
            self._page_token_bytes += nbytes
            held = by_window.setdefault(layer.cache_spec().window, [[], 0])
            held[0].append(name)
            held[1] += nbytes
        if cap % self._ps:
            raise ValueError(
                f"page_size {self._ps} must divide the KV-cache capacity "
                f"{cap} (attention max_cache) so the paged view is bit-"
                "identical to the contiguous cache")
        self._capacity = cap
        self._cap_tokens = cap
        self._np = cap // self._ps
        if len(by_window) > 2:
            raise ValueError(
                "paged layers declare windows "
                f"{sorted(w for w in by_window if w)}: one window class "
                "beside the full one is what the page classes take")
        # the class that keeps everything first; a net of one class is the
        # server as it was before classes
        self._classes = [_PageClass(w, *by_window[w]) for w in sorted(
            by_window, key=lambda w: (w is not None, w))]
        self._windowed = any(c.window is not None for c in self._classes)
        # resolve the paged-attention backend ONCE against the real pool
        # geometry and the largest chunk this server dispatches: this is
        # the program-cache tag (xla/pallas families must never share
        # traces), picks the decode dispatch family, and is what the
        # layers are handed in every carry.
        # Resolution is host config + static shapes — never traced data.
        from deeplearning4j_tpu.nn.conf.layers.paged_attention import (
            resolve_paged_backend)
        first = self._layer_by_name[self._paged_names[0]]
        cols, group = self._kernel_read(max(self._chunk_cap, self.spec_k))
        self._pa = resolve_paged_backend(
            self.paged_attention or "auto", page_size=self._ps,
            head_dim=first.d_head, n_pages=self._np, chunk=cols,
            quant=self._kv_quant, group=group)

    def _refuse(self, feature: str, what: str, exc=ValueError):
        """Raise ``exc`` if a layer's cache denies ``feature`` (see
        ``_LIMITS``); ``what`` names what asked for it."""
        why = self._limits.get(feature)
        if why is not None:
            raise exc(f"{what} is incompatible with {why}")

    def _kernel_read(self, chunk):
        """``(query columns, query heads a key/value head)`` of the widest
        program the Mosaic paged read would run for this net's paged
        layers, where the server dispatches chunks of up to ``chunk``
        columns (each layer's ``paged_kernel_read``); the group is
        ``None`` where the kernel cannot take one of them: a layer whose
        read says so (a latent plane), or one that reads it at a decode
        step alone over a mesh, whose grouped head-parallel read is not
        built."""
        reads = []
        for name in self._paged_names:
            layer = self._layer_by_name[name]
            cols, group = layer.paged_kernel_read(chunk)
            if group is None or (self._mesh is not None and not layer.plain):
                return 1, None
            reads.append((cols, group))
        return max(reads, key=lambda r: r[0] * r[1])

    def _live_pages(self, c) -> int:
        """The most pages of class ``c`` one slot holds at once: the
        table's width, or what the window and the longest write of a
        dispatch (a prefill chunk, a decode dispatch's steps) span."""
        if c.window is None:
            return self._np
        return min(self._np, self._layer_by_name[c.layers[0]].window_pages(
            max(self._chunk_cap, self._lookahead), self._ps))

    def _resident_bytes(self, peak: bool = False) -> int:
        """Bytes of the pages in use (or of each class's high-water mark)
        over every class."""
        return sum((c.pool.peak if peak else c.pool.in_use())
                   * c.token_bytes * self._ps for c in self._classes)

    def _reset_paging(self):
        """Fresh host paging state for every class; the first class's is
        also the server's own (``_page_pool``, ``_bt``, ``_slot_pages``:
        all there is for a net of one class)."""
        for c in self._classes:
            c.reset(self.slots, self._np)
        first = self._classes[0]
        self._page_pool, self._bt = first.pool, first.bt
        self._slot_pages = first.slot_pages

    def _bt_arg(self):
        """The block tables as a program takes them: the one table of a
        one-class net, a tuple by class otherwise."""
        if len(self._classes) == 1:
            return self._bt
        return tuple(c.bt for c in self._classes)

    def _class_of(self) -> dict:
        """Paged layer name -> the index of its class: which table of a
        program's ``bt`` operand (see ``_bt_arg``; ``_tables`` makes it a
        tuple) is that layer's."""
        return {vn: i for i, c in enumerate(self._classes)
                for vn in c.layers}

    def _probe_draft(self):
        draft = self._draft
        draft.rnn_clear_previous_state()
        probe = draft._seed_streaming_carry(1)
        dcap = draft._stream_capacity
        draft.rnn_clear_previous_state()
        self._d_attn_names: list = []
        self._d_pos_names: list = []
        for name, layer in draft._stream_layers():
            c = probe.get(name)
            if not c:
                continue
            if "kcache" in c:
                self._d_attn_names.append(name)
            elif "cache_pos" in c:
                self._d_pos_names.append(name)
        if not self._d_attn_names or dcap is None:
            raise ValueError("draft_net has no seedable streaming KV "
                             "carry — speculative decoding needs a "
                             "KV-cache streaming draft model")
        if dcap < self._cap_tokens:
            raise ValueError(
                f"draft_net max_cache {dcap} < target capacity "
                f"{self._cap_tokens}: the draft must reach every "
                "position the target can")
        self._draft_cap = dcap

    # ----------------------------------------------------------- programs
    def _fresh_pool(self):
        """The donated device carry: the planes each paged layer declares
        (``init_paged_carry``: [pages, page_size, H * d] keys and values,
        a token's heads side by side, plus [pages, H, page_size] f32
        scale planes under ``kv_dtype="int8"``, for an attention layer;
        one [pages, page_size, width] plane for a latent one). Positions and block tables
        are HOST state threaded in per dispatch, so this is all the
        device keeps. The admission bookkeeping's bytes-per-page is
        cross-checked against the REAL allocated array bytes here — the
        two accounting paths are not allowed to diverge."""
        import jax
        import jax.numpy as jnp

        dtype = jnp.dtype(self.net.conf.dtype)
        pool = {name: self._layer_by_name[name].init_paged_carry(
            c.pages_total, self._ps, dtype, kv_dtype=self.kv_dtype)
            for c in self._classes for name in c.layers}
        # a page of every class, as allocated
        self._page_bytes_actual = sum(
            int(leaf.nbytes) // c.pages_total for c in self._classes
            for name in c.layers
            for leaf in jax.tree_util.tree_leaves(pool[name]))
        # plane name -> how many layers page one
        self._plane_layers = dict(collections.Counter(
            k for planes in pool.values() for k in planes))
        # per-slot state rides in the same donated tree, beside the pages
        slot_state = {name: self._layer_by_name[name].init_streaming_carry(
            self.slots, dtype) for name in self._slot_names}
        self._slot_state_bytes = sum(
            int(leaf.nbytes)
            for leaf in jax.tree_util.tree_leaves(slot_state))
        pool.update(slot_state)
        if self._page_bytes_actual != self._page_bytes:
            raise AssertionError(
                f"KV admission accounting diverged from the allocated "
                f"pool: {self._page_bytes} bytes/page expected from the "
                f"conf, {self._page_bytes_actual} allocated "
                f"(kv_dtype={self.kv_dtype!r})")
        return self._shard_pool(pool)

    def _shard_pool(self, pool):
        """Home the page pool on device: a ``device_put`` onto this
        server's chip, or NamedSharding placement over the tensor-
        parallel mesh, each plane split along the axis its layer says
        holds the heads (``cache_spec().head_axes``: the lanes of a
        ``[P, ps, H * d]`` value plane, the rows of a ``[P, H, ps]``
        int8 scale plane), so each chip holds ``H/tp`` heads of every
        page and the per-chip page budget is 1/tp of the single-chip
        pool. Placement only — on the graftcheck hot list, so no host
        syncs in here."""
        import jax

        if self._mesh is None:
            return jax.device_put(pool, self._home)
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from deeplearning4j_tpu.parallel.mesh import MODEL_AXIS

        def put(vn, k, leaf):
            heads = self._layer_by_name[vn].cache_spec().head_axes[k]
            return jax.device_put(leaf, NamedSharding(self._mesh, P(*(
                MODEL_AXIS if axis == heads else None
                for axis in range(leaf.ndim)))))

        return {vn: {k: put(vn, k, leaf) for k, leaf in planes.items()}
                for vn, planes in pool.items()}

    def _reshard_snapshot(self, payload):
        """Adopt-side reshard: a snapshot's canonical host-layout page
        payload (leaves ``[NP, H, ps, d]`` / ``[NP, H, ps]``) put in the
        pool's own order by each layer (``paged_from_wire``) and, over a
        mesh, placed in this server's pool sharding before the donated
        store dispatch, so a snapshot exported at any tp scatters
        straight into a pool sharded at THIS server's tp — each chip
        uploads only its own head slice. Single-chip servers leave the
        placing to the store program's jit. On the graftcheck hot list:
        host reshapes and placement only, no host syncs."""
        payload = {vn: self._layer_by_name[vn].paged_from_wire(stacks)
                   for vn, stacks in payload.items()}
        if self._mesh is None:
            return payload
        return self._shard_pool(payload)

    def _weights(self, net=None):
        """``(params, state)`` of ``net`` (default: the served net) where
        this server's programs run. A default-device server hands out the
        net's own arrays. A pinned or mesh server places them ONCE per
        tree — a dispatch whose weights live on another chip copies them
        across on every call — and places again only when the net's
        trees were replaced (``fit`` rebinds them each step). Loop-thread
        only, like every dispatch."""
        import jax

        net = self.net if net is None else net
        src = (net.params, net.state)
        if self._home is None:
            return src
        hit = self._placed_weights.get(id(net))
        if hit is None or hit[0][0] is not src[0] or hit[0][1] is not src[1]:
            hit = (src, jax.device_put(src, self._home))
            self._placed_weights[id(net)] = hit
        return hit[1]

    def _get_program(self, cache_net, key, build, donate=()):
        """Compile-or-fetch a serving program: ``build()`` returns the plain
        function, jitted here under its own name. What the server decided
        for the layers (mesh, read backend) reaches them in the carry the
        function builds while it is traced (``_carry_builder``), and the
        key carries both, so families never share traces. The program
        outlives this server (the cache belongs to the net): ``build``
        closes over names and values, never over ``self``."""
        import jax

        return cache_net._get_output(
            key, lambda: jax.jit(build(), donate_argnums=donate))

    def _first_call(self, prog):
        """The context a serving program is called in. Its first call by
        this server traces, lowers and compiles (or loads from the compile
        cache): seconds of set-up, not of a dispatch, so they are a
        ``gen:compile`` span INSIDE the calling phase's span and the phase
        ``compile`` in the counters, and the calling phase keeps only its
        own (in a run that compiles, first calls were two thirds of the
        loop's seconds). Later calls get no span."""
        if prog in self._called:
            return _WARM
        self._called.add(prog)
        return self._clock.span("compile")

    def _carry_builder(self):
        """``carry(pool, pos, bt=..., views=..., fresh=...)``: what a
        serving program hands the net's streaming layers for one forward,
        built inside the traced function. Position layers get ``pos``; a
        paged layer gets its pool leaves and the block table ``bt`` (or,
        for the decode family that gathers once a dispatch, its dense
        ``views`` and no table; ``base``: by window layer, the position of
        its view's column 0 in each row), ``pos``, and under ``SERVED_BY``
        the read
        backend and the mesh this server resolved: the only place where
        they cross from server to layer. Slot state (rows ``fresh``
        zeroed) and call counts come from ``pool`` through
        ``_seed_extras``."""
        from deeplearning4j_tpu.nn.conf.layers.attention import SERVED_BY

        paged, pos_only = tuple(self._paged_names), tuple(self._pos_names)
        slot_st, counted = tuple(self._slot_names), tuple(self._counted)
        served_by = (self._pa, self._mesh)
        class_of = self._class_of()

        def carry(pool, pos, *, bt=None, views=None, fresh=None, base=None):
            out = {vn: {"cache_pos": pos} for vn in pos_only}
            for vn in paged:
                # generic over kv dtypes: an int8 pool's scale planes ride
                # beside its pages
                if views is None:
                    out[vn] = {**pool[vn],
                               "block_table": _tables(bt)[class_of[vn]]}
                else:
                    out[vn] = dict(views[vn])
                    if base and vn in base:
                        # a window layer's view starts at its row's first
                        # live page, not at position 0
                        out[vn]["view_base"] = base[vn]
                out[vn]["cache_pos"] = pos
                out[vn][SERVED_BY] = served_by
            _seed_extras(out, pool, slot_st, counted, fresh)
            return out

        return carry

    def _fresh_draft_pool(self):
        """Dense [S, H, cap, d] slot caches for the draft model (the
        draft is small — paging it would buy little and cost a second
        block table)."""
        import jax

        draft = self._draft
        draft.rnn_clear_previous_state()
        seed = draft._seed_streaming_carry(self.slots)
        draft.rnn_clear_previous_state()
        dpool = {name: {"kcache": seed[name]["kcache"],
                        "vcache": seed[name]["vcache"]}
                 for name in self._d_attn_names}
        return jax.device_put(dpool, self._home)

    def _decode_program(self):
        """The fused decode dispatch: ``steps_per_dispatch`` micro-steps
        of id feedback + streaming forward + traced per-slot
        sampling, scanned on device so the host pays one round trip per
        M tokens. Compiled ONCE — occupancy, positions, block tables and
        sampling params are all data, not shape.

        Rows write-clamp at the per-slot capacity: a row whose position
        reaches ``NP * ps`` freezes (token, position, count all hold and
        its column write is routed to the garbage page). Only overshoot
        tokens past a request's ``max_tokens`` can hit the clamp — the
        host truncates those anyway — so admission needs NO look-ahead
        margin and ``steps_per_dispatch`` can exceed a request's
        remaining budget safely.

        One scan body, two ways to reach the pool, chosen by the resolved
        read backend. ``in_place`` (``pallas``): each micro-step threads
        pool and block table through ``_paged_forward``, whose kernel
        reads the pages where they lie (a grouped, scaled or windowed
        layer's too: its one-column step is the kernel's); a frozen row's
        whole block-table row is swapped for the garbage page in every
        class's table, so its clamped write cannot land on real KV at
        capacity-1. ``dense_view`` (``xla``): the pool
        is gathered into a dense ``[S, H, Tmax, d]`` view ONCE per
        dispatch (the page indirection paid per M tokens, not per token),
        the micro-steps run the per-row dense streaming path over it
        (exactly the cache a contiguous layout would hold), and each
        step's freshly written column is scattered into its page inside
        the donated scan; which view a plane becomes and where its token
        axis lies is the layer's ``cache_spec().planes``. The two are keyed
        apart in the program cache, write the same pool bit for bit and
        serve the same tokens (tests/test_paged_attention.py pins it)."""
        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu.models.zoo import (lm_stream_forward,
                                                   sampled_next_token)

        net, vocab = self.net, self.vocab
        m_steps = self.steps_per_dispatch
        paged = tuple(self._paged_names)
        slot_st = tuple(self._slot_names)
        counted = tuple(self._counted)
        pa, ps = self._pa, self._ps
        # by layer: plane -> (dense view, the view's token axis); what
        # order a pool plane itself has is the layer's to know
        layers = {vn: self._layer_by_name[vn] for vn in paged}
        planes = {vn: layers[vn].cache_spec().planes for vn in paged}
        carry_for = self._carry_builder()
        class_of = self._class_of()
        # window layer -> pages of its dense view: what the window and a
        # dispatch's micro-steps span, from the row's first live page
        narrow = {vn: self._layer_by_name[vn].window_pages(m_steps, ps)
                  for c in self._classes if c.window is not None
                  for vn in c.layers}
        key = ("gen_decode", self.slots, vocab, m_steps, self.kv_dtype,
               self._mesh, pa)

        def build():
            fwd = lm_stream_forward(net, vocab)
            # what the layers count rides the scan and the one fetch; a
            # net without such layers carries nothing more
            extra = ({vn: jnp.zeros((n,), jnp.int32)
                      for vn, n in counted},) if counted else ()
            # a net of pages only keeps nothing of a row that holds: its
            # write lands on the garbage page. Per-slot state and counts
            # are kept, so their layers are told which rows advance: a
            # free or frozen slot's stale token is neither routed nor
            # counted, and its state stands
            told = bool(slot_st or counted)

            # a strategy: (views to scan over, a step's carry, the views
            # and pages after a step's forward)
            def in_place(pool, bt, positions):
                def seed(views, pool, act, posw):
                    # a free or frozen row's table is the garbage page in
                    # every class's table
                    return carry_for(pool, posw, bt=tuple(
                        jnp.where(act[:, None], t, 0) for t in _tables(bt)))

                def settle(views, pool, nc, act, posw):
                    return None, {vn: {k: nc[vn][k] for k in pool[vn]}
                                  for vn in paged}

                return None, seed, settle

            def dense_view(pool, bt, positions):
                views, base = {}, {}
                for vn in paged:
                    table = _tables(bt)[class_of[vn]]
                    if vn in narrow:
                        first = layers[vn].first_live_page(positions, ps)
                        views[vn] = layers[vn].paged_views(
                            pool[vn], table, first,
                            min(narrow[vn], table.shape[1]))
                        base[vn] = first * ps
                    else:
                        views[vn] = layers[vn].paged_views(pool[vn], table)

                def seed(views, pool, act, posw):
                    return carry_for(pool, posw, views=views, base=base)

                def settle(views, pool, nc, act, posw):
                    views = {vn: {k: nc[vn][k] for k in views[vn]}
                             for vn in paged}
                    # scatter the column this step wrote into its page:
                    # in-place inside the donated scan. Frozen/inactive
                    # rows land on the garbage page (COW upstream keeps
                    # real targets exclusively owned)
                    # the written page, by class
                    page = [jnp.where(act, jnp.take_along_axis(
                        table, (posw // ps)[:, None], axis=1)[:, 0], 0)
                        for table in _tables(bt)]
                    off = posw % ps
                    every = (slice(None),)
                    index = {}      # the written column against a view
                    pages = {}
                    for vn in paged:
                        at = posw - base[vn] if vn in base else posw
                        # an int8 pool's dequant scales ride into the pool
                        # through the same routing as its values
                        cols = {}
                        for k in pool[vn]:
                            name, axis = planes[vn][k]
                            view = views[vn][name]
                            if (vn in base, view.ndim) not in index:
                                index[vn in base, view.ndim] = at[
                                    every + (None,) * (view.ndim - 1)]
                            cols[k] = jnp.take_along_axis(
                                view, index[vn in base, view.ndim],
                                axis=axis)[every * axis + (0,)]
                        pages[vn] = layers[vn].paged_settle(
                            pool[vn], cols, page[class_of[vn]], off)
                    return views, pages

                return views, seed, settle

            strategy = in_place if pa == "pallas" else dense_view

            def gen_decode(params, state, pool, bt, positions, last, active,
                           temp, topk, base_keys, counts):
                cap = jax.tree_util.tree_leaves(bt)[0].shape[1] * ps
                views, seed, settle = strategy(
                    pool, bt, jnp.minimum(positions, cap - 1))

                def body(cs, _):
                    views, pool, pos, cur, cnt, *cnts = cs
                    # write-clamp: overshoot rows at capacity freeze
                    act = active & (pos < cap)
                    posw = jnp.minimum(pos, cap - 1)
                    carry = seed(views, pool, act, posw)
                    out, nc = fwd(
                        params, state, cur[:, None], carry,
                        act[:, None].astype(jnp.float32) if told else None)
                    kept = _keep_rows(pool, nc, slot_st, act)
                    cnts = [jax.tree_util.tree_map(
                        jnp.add, c, _call_counts(nc, counted))
                        for c in cnts]
                    views, pages = settle(views, pool, nc, act, posw)
                    pool = {**pages, **kept}

                    # all-greedy batches skip the PRNG fold-ins and the
                    # top-k selection entirely — lax.cond picks the branch
                    # at RUN time, so mixed batches still share this one
                    # program, and the greedy op is the same argmax
                    # sampled_next_token takes for temp<=0 rows (bit-exact)
                    def _greedy(out0):
                        return jnp.argmax(out0, axis=-1).astype(jnp.int32)

                    def _sampled(out0):
                        keys = jax.vmap(jax.random.fold_in)(base_keys, cnt)
                        return sampled_next_token(
                            out0, keys, temp, topk).astype(jnp.int32)

                    nxt = jax.lax.cond(jnp.all(temp <= 0.0),
                                       _greedy, _sampled, out[:, 0])
                    # frozen rows hold: token, position and count all
                    # stall so their garbage stays on the garbage page
                    # (cast: argmax may widen to int64 under x64 mode)
                    nxt = jnp.where(act, nxt, cur).astype(cur.dtype)
                    pos = jnp.where(act, pos + 1, pos)
                    cnt = jnp.where(act, cnt + 1, cnt)
                    return (views, pool, pos, nxt, cnt, *cnts), nxt

                (_, pool, _, _, _, *cnts), seq = jax.lax.scan(
                    body, (views, pool, positions, last, counts, *extra),
                    None, length=m_steps)
                return (pool, seq.T, *cnts)                # [S, M]

            return gen_decode

        return self._get_program(net, key, build, donate=(2,))

    def _prefill_program(self, bucket: int):
        """Batched suffix prefill for one page-aligned bucket and one row
        group: ``rows`` (``int32 [R]``, ``R`` the server's ``_prefill_rows``)
        names the slot each row of the group serves, and every other
        operand arrives at ``[R, ...]``. Each row consumes its
        (right-padded, masked) suffix at its shared-prefix offset through
        ONE paged forward over ``R x bucket`` positions: KV lands directly
        in its slot's pages (the slot's row of the standing block table,
        gathered here), weights are read once for the group, and its first
        token is sampled from its last TRUE position. Slots outside the
        group (free, or mid-decode) are not computed at all. Per-slot
        state is gathered by ``rows`` into the carry and scattered back
        into the donated block. A group with fewer live rows than ``R``
        is padded with rows whose index is ``slots``, one past the last
        slot: id 0 under a zero mask, every write routed to the garbage
        page, the slot-state scatter dropped. The round arrives as token
        ids, embedded on the device (``lm_stream_forward``: rows of the
        table by a gather where the net's structure allows, else a one-hot
        product), as the decode body embeds its own. One program per
        bucket: the width is one number a server."""
        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu.models.zoo import (lm_stream_forward,
                                                   sampled_next_token)

        net, vocab, slots = self.net, self.vocab, self.slots
        paged = tuple(self._paged_names)
        slot_st = tuple(self._slot_names)
        counted = tuple(self._counted)
        carry_for = self._carry_builder()
        key = ("gen_prefill", slots, self._prefill_rows, vocab, bucket,
               self.kv_dtype, self._mesh, self._pa)

        def build():
            fwd = lm_stream_forward(net, vocab)

            def gen_prefill(params, state, pool, bt, rows, pos0, ids, mask,
                            sufflen, temp, topk, base_keys):
                live = rows < slots
                # a padding row writes the garbage page. A request's first
                # round starts its slot's state from zeros; a later round
                # of a long prompt continues it
                carry = carry_for(
                    {**pool, **_take_rows(pool, slot_st, rows)}, pos0,
                    bt=jax.tree_util.tree_map(
                        lambda t: jnp.where(
                            live[:, None],
                            jnp.take(t, rows, axis=0, mode="clip"), 0), bt),
                    fresh=live & (pos0 == 0))
                out, nc = fwd(params, state, ids, carry, mask)
                new_pool = {**{vn: {k: nc[vn][k] for k in pool[vn]}
                               for vn in paged},
                            **_put_rows(pool, nc, slot_st, rows)}
                last = jnp.take_along_axis(
                    out, (sufflen - 1)[:, None, None], axis=1)[:, 0]
                k0 = jax.vmap(jax.random.fold_in)(
                    base_keys, jnp.zeros_like(sufflen))
                first = sampled_next_token(last, k0, temp, topk)
                if counted:
                    return new_pool, first, _call_counts(nc, counted)
                return new_pool, first

            return gen_prefill

        return self._get_program(net, key, build, donate=(2,))

    def _page_copy_program(self):
        """Copy-on-write: duplicate one pool page (all layers) into a
        fresh page. Traced page ids — compiled once."""
        paged = tuple(self._paged_names)
        key = ("gen_page_copy", self._mesh)

        def build():
            def gen_page_copy(pool, src, dst):
                # generic per-leaf copy: int8 pools also carry scale
                # planes, and COW must duplicate them with the values
                return {**pool,
                        **{vn: {k: a.at[dst].set(a[src])
                                for k, a in pool[vn].items()}
                           for vn in paged}}

            return gen_page_copy

        return self._get_program(self.net, key, build, donate=(0,))

    def _page_fetch_program(self):
        """Snapshot export: gather a block-table-width stack of pool
        pages (all layers, scale planes included) in one dispatch. NOT
        donating — the pool stays live; page ids are traced data, so
        every export replays this one program."""
        paged = tuple(self._paged_names)
        key = ("gen_page_fetch", self._mesh)

        def build():
            def gen_page_fetch(pool, idx):
                return {vn: {k: a[idx] for k, a in pool[vn].items()}
                        for vn in paged}

            return gen_page_fetch

        return self._get_program(self.net, key, build)

    def _page_store_program(self):
        """Snapshot adopt: scatter a block-table-width stack of page
        payloads into pool rows ``dst`` (all layers, scale planes
        included). Rows the adopter does not need (padding, or pages
        deduped against the prefix cache) are routed to the garbage
        page. Donating in-place, rebound by the caller — compiled
        once."""
        paged = tuple(self._paged_names)
        key = ("gen_page_store", self._mesh)

        def build():
            def gen_page_store(pool, dst, data):
                return {**pool,
                        **{vn: {k: a.at[dst].set(data[vn][k])
                                for k, a in pool[vn].items()}
                           for vn in paged}}

            return gen_page_store

        return self._get_program(self.net, key, build, donate=(0,))

    def _draft_prefill_program(self, bucket: int):
        """Draft-side prefill for one pow2 token bucket: consume the full
        (padded, masked) prompt, given as token ids (``int32 [1,
        bucket]``, embedded on the device), with a fresh batch-1
        dense carry and scatter the filled caches into draft pool row
        ``slot``. No sampling — the draft only needs its cache primed."""
        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu.models.zoo import lm_stream_forward

        draft, vocab = self._draft, self.vocab
        d_attn = tuple(self._d_attn_names)
        d_pos = tuple(self._d_pos_names)
        key = ("gen_draft_prefill", self.slots, vocab, bucket)

        def build():
            dfwd = lm_stream_forward(draft, vocab)

            def dprefill(dparams, dstate, dpool, slot, ids, mask):
                one = {}
                for vn in d_pos:
                    one[vn] = {"cache_pos": jnp.zeros((), jnp.int32)}
                for vn in d_attn:
                    kc = dpool[vn]["kcache"]
                    one[vn] = {
                        "kcache": jnp.zeros((1,) + kc.shape[1:], kc.dtype),
                        "vcache": jnp.zeros((1,) + kc.shape[1:], kc.dtype),
                        "cache_pos": jnp.zeros((), jnp.int32)}
                _, c1 = dfwd(dparams, dstate, ids, one, mask)
                return {vn: {
                    "kcache": dpool[vn]["kcache"].at[slot].set(
                        c1[vn]["kcache"][0]),
                    "vcache": dpool[vn]["vcache"].at[slot].set(
                        c1[vn]["vcache"][0])} for vn in d_attn}

            return jax.jit(dprefill, donate_argnums=(2,))

        return draft._get_output(key, build)

    def _spec_program(self):
        """One speculative round, fused: the draft scans K-1 proposal
        steps over its dense cache (same fold_in key schedule the target
        would use for those token indices), then the target verifies all
        K positions in ONE chunked paged forward. Returns the target's
        selections [S, K] and the per-slot count of leading draft
        matches — everything the host needs to emit min(acc+1, K)
        tokens, every one of them a TARGET selection under the serial
        schedule (bit-exactness by construction)."""
        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu.models.zoo import (lm_stream_forward,
                                                   sampled_next_token,
                                                   spec_verify_tokens)

        net, draft, vocab = self.net, self._draft, self.vocab
        k_spec = self.spec_k
        paged = tuple(self._paged_names)
        d_attn = tuple(self._d_attn_names)
        d_pos = tuple(self._d_pos_names)
        carry_for = self._carry_builder()
        # the closure captures BOTH nets, so the program lives in the
        # DRAFT's cache (it dies with the draft) keyed by the target's
        # identity — a draft shared across servers never replays a
        # program traced against a different target
        key = ("gen_spec", id(net), self.slots, vocab, k_spec,
               self.kv_dtype, self._mesh, self._pa)

        def build():
            fwd = lm_stream_forward(net, vocab)
            dfwd = lm_stream_forward(draft, vocab)

            def dcarry(dp, pos):
                carry = {}
                for vn in d_pos:
                    carry[vn] = {"cache_pos": pos}
                for vn in d_attn:
                    carry[vn] = {"kcache": dp[vn]["kcache"],
                                 "vcache": dp[vn]["vcache"],
                                 "cache_pos": pos}
                return carry

            def strip_d(nc):
                return {vn: {"kcache": nc[vn]["kcache"],
                             "vcache": nc[vn]["vcache"]} for vn in d_attn}

            def gen_spec(params, state, dparams, dstate, pool, dpool, bt,
                         positions, last, active, temp, topk, base_keys,
                         counts):
                def body(cs, _):
                    dp, pos, cur, cnt = cs
                    out, nc = dfwd(dparams, dstate, cur[:, None],
                                   dcarry(dp, pos))
                    keys = jax.vmap(jax.random.fold_in)(base_keys, cnt)
                    prop = sampled_next_token(out[:, 0], keys, temp, topk)
                    prop = jnp.where(active, prop, cur).astype(cur.dtype)
                    return (strip_d(nc), jnp.where(active, pos + 1, pos),
                            prop, jnp.where(active, cnt + 1, cnt)), prop

                (dpool, pos_f, cur_f, _), props = jax.lax.scan(
                    body, (dpool, positions, last, counts), None,
                    length=k_spec - 1)
                # feed the last proposal too (output unused): a
                # full-accept round then leaves the draft cache
                # hole-free at position pos + K - 1
                _, nc = dfwd(dparams, dstate, cur_f[:, None],
                             dcarry(dpool, pos_f))
                dpool = strip_d(nc)

                drafts = props.T                         # [S, K-1]
                chunk = jnp.concatenate([last[:, None], drafts], axis=1)
                out, nc = fwd(params, state, chunk,
                              carry_for(pool, positions, bt=bt))  # [S, K, V]
                new_pool = {vn: {k: nc[vn][k] for k in pool[vn]}
                            for vn in paged}
                true = spec_verify_tokens(out, base_keys, counts, temp,
                                          topk)          # [S, K]
                match = (drafts == true[:, :k_spec - 1]).astype(jnp.int32)
                acc = jnp.sum(jnp.cumprod(match, axis=1), axis=1)
                return new_pool, dpool, true, acc

            return gen_spec

        return self._get_program(draft, key, build, donate=(4, 5))

    # ------------------------------------------------------------- submit
    def _outside_vocab(self, ids) -> bool:
        """Whether any id lies outside ``[0, vocab)``: the one range check
        of prompt ids, made where a request enters (the programs' one-hot
        maps such an id to a zero row, their gather to a clamped row, and
        neither says anything)."""
        return bool(ids.size) and bool(ids.min() < 0
                                       or ids.max() >= self.vocab)

    def submit(self, prompt_ids, max_tokens: int, *,
               temperature: float = 0.0, top_k: int = 0, seed: int = 0,
               eos_id=_UNSET, deadline_s: Optional[float] = None,
               export_kv: Optional[bool] = None) -> Future:
        """Queue one generation request; returns a Future resolving to
        the generated ids ([<= max_tokens] numpy int array — shorter when
        the per-request ``eos_id`` / server default is produced, which is
        included). Raises a typed ``ServerOverloaded`` when the request
        cannot fit the page budget (up front — never mid-prefill after a
        slot is consumed) or past the admission watermark, and
        ``CircuitOpen`` while dispatches are failing. A prompt id outside
        ``[0, vocab)`` raises ``ValueError`` here, before anything is
        queued: prefill rounds ship ids and the device embeds an id out of
        range as a silent wrong row, so this is the one range check, and
        it costs no other request anything.

        ``export_kv`` selects the disaggregated-prefill outcome: True
        resolves the future to a ``KVSnapshot`` right after prefill
        (first token included in its header) for a decode-tier server
        to adopt; False decodes to completion here. The default (None)
        follows the server ``role`` — True on a prefill-role server,
        False otherwise — so a degraded fleet can co-locate decode on
        the prefill tier by passing ``export_kv=False`` explicitly."""
        prompt = np.asarray(prompt_ids)
        if prompt.ndim != 1 or prompt.shape[0] < 1:
            raise ValueError(f"prompt_ids must be a non-empty 1-D id "
                             f"array, got shape {prompt.shape}")
        if max_tokens < 1:
            raise ValueError(f"max_tokens must be >= 1, got {max_tokens}")
        if temperature < 0:
            raise ValueError(f"temperature must be >= 0, got {temperature}")
        if top_k < 0 or top_k > self.vocab:
            raise ValueError(f"top_k must be in [0, {self.vocab}], "
                             f"got {top_k}")
        prompt = prompt.astype(np.int64)
        if self._outside_vocab(prompt):
            raise ValueError(f"prompt ids must be in [0, {self.vocab}), "
                             f"got {prompt.min()}..{prompt.max()}")
        plen = int(prompt.shape[0])
        # page-budget feasibility, up front: prompt + generated positions
        # (+ the speculative look-ahead margin a verify chunk writes —
        # the plain decode dispatch write-clamps at capacity, so it
        # needs none) must fit the block table AND the pool with the
        # garbage page excluded; prefill padding writes the garbage
        # page, so buckets add no transient page pressure
        margin = self.spec_k - 1 if self._draft is not None else 0
        need_tokens = plen + int(max_tokens) + margin - 1
        if need_tokens > self._cap_tokens:
            raise ServerOverloaded(
                f"infeasible request: prompt {plen} + max_tokens "
                f"{max_tokens} (+{margin} look-ahead) exceeds the per-"
                f"slot KV capacity {self._cap_tokens} "
                f"({self._np} pages x {self._ps})")
        for c in self._classes:
            need_pages = min(-(-need_tokens // self._ps),
                             self._live_pages(c))
            if need_pages > c.pages_total - 1:
                raise ServerOverloaded(
                    f"infeasible request: needs {need_pages} pages but the "
                    f"pool capacity is {c.pages_total - 1} usable pages "
                    f"of {self._ps} tokens")
        with self._cond:
            if self._closing:
                raise RuntimeError("GenerationServer is closed")
        if not self.breaker.allow():
            raise CircuitOpen("circuit breaker is open: recent decode "
                              "dispatches failed above threshold")
        budget = deadline_s if deadline_s is not None \
            else self.request_deadline_s
        req = _Request(prompt, int(max_tokens),
                       float(temperature), int(top_k), int(seed),
                       self.eos_id if eos_id is _UNSET else eos_id,
                       None if budget is None else Deadline(budget))
        req.export_kv = (self.role == "prefill") if export_kv is None \
            else bool(export_kv)
        if req.export_kv:
            self._refuse("snapshot", "export", SnapshotUnsupported)
        # export_request / the fleet clamp their waits to the request's
        # own remaining budget through this stamp
        req.future._deadline = req.deadline
        self.admission.acquire()  # raises ServerOverloaded at watermark
        req.future.add_done_callback(lambda _f: self.admission.release())
        with self._cond:
            if self._closing:
                # lost the race with close(): fail typed, not hung
                self._fail(req, RuntimeError("GenerationServer is closed"))
                return req.future
            self._queue.append(req)
            self._cond.notify_all()
        return req.future

    # ---------------------------------------------------------- the loop
    def _wake_loop(self):
        """Runtime wake hook: nudge a tick blocked on ``_cond``."""
        with self._cond:
            self._cond.notify_all()

    def _tick_once(self) -> bool:
        """One scheduling round of the decode loop, hosted by the
        ``ServingLoop`` tick thread ("generation-server"). Returns False
        only on a clean stop (loop CLOSED)."""
        with self._cond:
            if self._stop:
                return False
            migrating = self._migrating
            n_active = self._n_active
            idle = self._nothing_to_do()
        if idle:
            # the span closes (and publishes) after ``_cond`` is released
            with self._clock.span("idle_wait"), self._cond:
                # looked at again: a wake-up may have come in between
                if not self._stop and self._nothing_to_do():
                    self._cond.wait(timeout=0.5)
            return True
        # every second of a working tick is under exactly one phase: the
        # spans below are siblings, and what none covers is tick_other
        span = self._clock.span
        with span("tick", own="tick_other", active=n_active):
            try:
                if migrating:
                    with span("housekeeping"):
                        if self._chaos is not None:
                            # a migrate-out sweep IS a drain phase:
                            # shutdown-phase chaos (kill_during_drain)
                            # attacks it too, and the LoopKilled it raises
                            # is a BaseException precisely so it escapes
                            # the except below into the supervisor
                            fault = getattr(self._chaos, "drain_fault",
                                            None)
                            if fault is not None:
                                fault()
                        self._migrate_out()
                self._admit_free_slots()
                with self._cond:
                    n_active = self._n_active
                if n_active:
                    if self._draft is not None:
                        self._spec_decode_once()
                    else:
                        self._decode_once()
                with span("housekeeping"):
                    self._expire_active()
                    # handoff housekeeping rides BETWEEN dispatches:
                    # explicit exports first (a caller is blocked on
                    # them), then at most one periodic low-priority
                    # snapshot per iteration
                    self._service_exports()
                    self._maybe_snapshot_slots()
            except Exception as e:  # noqa: BLE001 — a loop death would
                # hang every outstanding future; fail them typed instead
                self._fail_all(e)
        return True

    def _nothing_to_do(self) -> bool:
        """Under ``_cond``: no request queued or decoding, no export
        waiting, no migration asked for."""
        return (not self._queue and self._n_active == 0
                and not self._export_q and not self._migrating)

    def _on_loop_death(self, loop, exc) -> bool:
        """Supervisor recovery hook: the decode tick thread died (a chaos
        kill or an untrappable fault that escaped ``_fail_all``). Fail
        every in-flight future and pending export typed, release the dead
        slots' pages, and — unless the server was deliberately closed —
        rebuild device state so the supervised restart serves cleanly."""
        err = LoopCrashed("generation-server loop died with the request "
                          f"in flight: {exc!r}")
        with self._cond:
            stragglers = [s for s in range(self.slots)
                          if self._slot_req[s] is not None]
            victims = [self._slot_req[s] for s in stragglers]
            victims += list(self._queue)
            self._queue.clear()
            self._slot_req = [None] * self.slots
            self._n_active = 0
            exports = list(self._export_q)
            self._export_q.clear()
            # a kill mid-migration resolved every live future (below), so
            # the migration is over — a latched flag would make the
            # restarted tick re-enter the drain path forever
            self._migrating = False
            self._migrate_cb = None
            again = not self._user_close
            self._cond.notify_all()
        self._m_failed.inc(len(victims))
        for req in victims:
            self._fail(req, err)
        for _fut, out in exports:  # never leave an exporter hung
            self._fail_export(out, SnapshotUnavailable(
                "generation loop died before the export was serviced"))
        for s in stragglers:  # tick thread is dead: safe to touch pages
            self._release_slot_pages(s)
        if again:
            self._m_pool_rebuilds.inc()
            self._reset_device_state()
        return again

    def _pop_admittable(self):
        """Next queued request still worth prefilling (expired ones fail
        typed on the way — counted and resolved OUTSIDE ``_cond``)."""
        while True:
            with self._cond:
                if not self._queue:
                    return None
                req = self._queue.popleft()
            if req.deadline is not None and req.deadline.expired():
                self._m_expired.inc()
                self._fail(req, DeadlineExceeded(
                    "request budget exhausted while queued "
                    f"({-req.deadline.remaining() * 1e3:.1f} ms over)"))
                continue
            return req

    def _admit_free_slots(self):
        """Admit every queued request a free slot and the page pool can
        take, then prefill the wave together: each chunk round packs its
        rows into row groups of ``_prefill_rows``, one batched dispatch a
        group (Orca-style iteration-level scheduling: weights
        are read once per group, not once per request, and a dispatch
        computes the group's rows, not the slot pool)."""
        with self._clock.span("admit"):
            staged = self._stage_free_slots()
        if staged:
            self._prefill_wave(staged)

    def _stage_free_slots(self):
        """The admission half of a wave: pop, adopt or stage. Returns the
        ``(slot, request, first position to prefill, prompt length)`` of
        every request that needs the prefill."""
        staged = []
        waits = []
        with self._cond:
            # the autoscaler's admission cap bounds occupancy, not the
            # pool: slots past the cap stay empty until it rises again
            budget = self._active_cap - self._n_active
        for s in range(self.slots):
            if len(staged) >= budget:
                break
            if self._slot_req[s] is not None:
                continue
            req = self._pop_admittable()
            if req is None:
                break
            waits.append((time.monotonic() - req.t_submit) * 1e3)
            if req.snapshot is not None and self._adopt_into_slot(s, req):
                continue
            # no snapshot (or adoption fell back): token-0 prefill
            plen = req.prompt.shape[0]
            try:
                pos0 = self._stage_prompt_pages(s, req.prompt, plen)
            except RuntimeError as e:  # pool exhausted during staging
                self._release_slot_pages(s)
                if staged:
                    # transient pressure from this same admission wave:
                    # requeue and batch what already staged — their
                    # completions free the pages this request needs
                    with self._cond:
                        self._queue.appendleft(req)
                    break
                self._m_failed.inc()
                self._fail(req, e)
                continue
            except Exception as e:  # noqa: BLE001 — typed failure for
                # this request only; the slot stays free for the next one
                self._release_slot_pages(s)
                if isinstance(e, DeadlineExceeded):
                    self._m_expired.inc()
                else:
                    self._m_failed.inc()
                self._fail(req, e)
                continue
            staged.append((s, req, pos0, plen))
        if waits:
            for h in self._m_queue_wait:
                h.observe_many(waits)
        return staged

    # -------------------------------------------------- page bookkeeping
    def _release_slot_pages(self, slot: int):
        for c in self._classes:
            sp = c.slot_pages[slot]
            for page in sp[c.slot_lo[slot]:]:
                c.pool.release(page)
            sp.clear()
            c.slot_lo[slot] = 0
            c.bt[slot, :] = 0
        self._pos[slot] = 0

    def _slide_windows(self, slot: int, pos: int):
        """Before a dispatch whose first query for ``slot`` stands at
        ``pos``: every window class gives back the slot's pages whose last
        token that query, and so every later one, can no longer see (those
        before logical page ``(pos - window + 1) // page_size``), and their
        table entries point at the garbage page."""
        for c in self._classes:
            if c.window is None:
                continue
            sp, lo = c.slot_pages[slot], c.slot_lo[slot]
            dead = min(max(pos - c.window + 1, 0) // self._ps, len(sp))
            if dead <= lo:
                continue
            for idx in range(lo, dead):
                c.pool.release(sp[idx])
                sp[idx] = GARBAGE_PAGE
            c.bt[slot, lo:dead] = GARBAGE_PAGE
            c.slot_lo[slot] = dead
            for m in self._m_class["released"]:
                m.inc(dead - lo)

    def _pick_victim(self, keep_slot: int):
        best, best_seq = None, -1
        for s in range(self.slots):
            if s == keep_slot or self._slot_req[s] is None:
                continue
            if self._slot_seq[s] > best_seq:
                best, best_seq = s, self._slot_seq[s]
        return best

    def _preempt(self, slot: int):
        """Free the most recently admitted slot's pages under pool
        pressure: its request is requeued at the FRONT. A victim with at
        least a page's worth of decoded state snapshots BEFORE its pages
        are freed, so re-admission ADOPTS the snapshot and resumes at
        position N instead of recomputing the prefix (the deterministic
        key schedule makes either path bit-identical, so preemption is
        invisible in outputs — the snapshot only saves the recompute)."""
        req = self._slot_req[slot]
        if (req.snapshot is None and "snapshot" not in self._limits
                and len(req.tokens) >= self._ps):
            try:
                snap = self._snapshot_slot(slot)
            except Exception:  # noqa: BLE001 — best-effort: a failed
                # snapshot degrades to the legacy recompute, never fails
                # the request
                snap = None
            if snap is not None:
                req.snapshot = snap
                self._m_preempt_resumes.inc()
        if req.snapshot is None:
            req.tokens.clear()
        self._release_slot_pages(slot)
        self._m_preempted.inc()
        with self._cond:
            self._slot_req[slot] = None
            self._n_active -= 1
            self._queue.appendleft(req)
            self._cond.notify_all()

    def _alloc_page(self, for_slot: int, c=None) -> int:
        pool = self._page_pool if c is None else c.pool
        while True:
            page = pool.alloc()
            if page is not None:
                return page
            victim = self._pick_victim(for_slot)
            if victim is None:
                raise RuntimeError(
                    "page pool exhausted with nothing left to preempt — "
                    "admission should have rejected this request")
            self._preempt(victim)

    def _ensure_writable(self, slot: int, idx: int):
        """Copy-on-write: the slot is about to write into its idx-th
        logical page; if that page is shared (or pinned pristine by the
        prefix cache) copy it off and repoint the block table."""
        sp = self._slot_pages[slot]
        page = sp[idx]
        if not self._page_pool.protected(page):
            return
        dst = self._alloc_page(slot)
        prog = self._page_copy_program()
        with self._first_call(prog):
            self._pool = prog(self._pool, np.int32(page), np.int32(dst))
        for c in self._m_cow_copies:
            c.inc()
        self._page_pool.release(page)
        sp[idx] = dst
        self._bt[slot, idx] = dst

    def _ensure_slot_pages(self, slot: int, upto: int, write_from: int,
                           windows: Optional[bool] = None):
        """Slot ``slot`` is about to write positions
        [write_from, upto): allocate any missing pages and COW the
        shared ones in the write range. ``windows`` False: only in the
        classes that keep everything (a prompt staged whole at
        admission); True: only in the window classes (which take a
        prompt's pages round by round, ``_slide_windows`` giving back
        those behind); None: in every class."""
        n = -(-upto // self._ps)
        if n > self._np:
            raise RuntimeError(
                f"slot {slot} needs {n} pages > block table width "
                f"{self._np} — admission should have rejected this")
        for c in self._classes:
            if windows is not None and windows != (c.window is not None):
                continue
            sp = c.slot_pages[slot]
            while len(sp) < n:
                page = self._alloc_page(slot, c)
                c.bt[slot, len(sp)] = page
                sp.append(page)
        if self.prefix_cache:
            # only the prefix cache shares or pins a page (one class)
            for idx in range(write_from // self._ps,
                             (upto - 1) // self._ps + 1):
                self._ensure_writable(slot, idx)

    def _reserve_decode_pages(self):
        """Page capacity for one decode dispatch: every active slot gets
        pages covering its next ``lookahead`` writes (alloc + COW),
        preempting the newest slots under pressure."""
        look = self._lookahead
        for s in range(self.slots):
            if self._slot_req[s] is None:
                continue
            pos = int(self._pos[s])
            # the dispatch write-clamps at capacity, so pages past the
            # per-slot cap are never touched (overshoot lands on the
            # garbage page)
            upto = min(pos + look, self._cap_tokens)
            if self._windowed:
                self._slide_windows(s, pos)
            if upto > pos:
                self._ensure_slot_pages(s, upto, write_from=pos)

    def _prefix_digest(self, digest: bytes, chunk) -> bytes:
        return hashlib.sha1(digest + chunk.tobytes()).digest()

    def _match_prefix(self, prompt, plen: int):
        """Longest shared prefix already resident: full page-aligned
        chunks under the chained digest, then the exact whole-prompt
        tail. Returns (shared page list, matched token count) with the
        shares already refcounted; at least one suffix token is always
        left to prefill (the sampled first token needs a true
        position)."""
        if not self.prefix_cache:
            return [], 0
        pool = self._page_pool
        ps = self._ps
        digest = b""
        pages: list = []
        matched = 0
        full = plen // ps
        for i in range(full):
            digest = self._prefix_digest(digest, prompt[i * ps:(i + 1) * ps])
            page = pool.lookup(digest)
            if page is None:
                break
            pages.append(page)
            matched += ps
        else:
            rem = prompt[full * ps:]
            if rem.size:
                tkey = hashlib.sha1(digest + b"T" + rem.tobytes()).digest()
                page = pool.lookup(tkey)
                if page is not None:
                    pages.append(page)
                    matched = plen
        if matched >= plen:
            # whole prompt resident: un-share the final token — its
            # 1-token suffix prefill writes into the shared page, which
            # COWs off the slot's private copy (the genuine COW trigger)
            matched = plen - 1
        for page in pages:
            pool.share(page)
        return pages, matched

    def _stage_prompt_pages(self, slot: int, prompt, plen: int):
        """Assemble the slot's block-table row for prefill: adopt shared
        prefix pages, then allocate private pages for the true suffix
        tokens only — bucket padding inside a prefill round writes the
        garbage page, so it needs no backing. Returns the suffix
        offset."""
        shared, matched = self._match_prefix(prompt, plen)
        sp = self._slot_pages[slot]
        sp.extend(shared)
        for i, page in enumerate(shared):
            self._bt[slot, i] = page
        if matched:
            self._m_prefix_hits.inc()
            for c in self._m_prefix_reused:
                c.inc(matched)
        for c in self._m_prompt_tokens:
            c.inc(plen)
        self._ensure_slot_pages(slot, plen, write_from=matched,
                                windows=False if self._windowed else None)
        return matched

    def _trim_slot_pages(self, slot: int, plen: int):
        """Drop prefill bucket over-allocation: pages wholly beyond the
        next write position hold only padding garbage — return them to
        the pool; decode re-allocates on demand."""
        keep = plen // self._ps + 1
        for c in self._classes:
            sp = c.slot_pages[slot]
            while len(sp) > keep:
                page = sp.pop()
                c.bt[slot, len(sp)] = 0
                c.pool.release(page)

    def _register_prefix(self, slot: int, prompt, plen: int):
        """Publish the slot's prompt pages in the prefix cache: full
        page-aligned chunks under the chained digest, plus the whole-
        prompt partial tail. Registered pages become copy-protected —
        the first divergent write (this slot's own next decode token
        included) COWs off a private copy, leaving the cached original
        pristine for future sharers."""
        if not self.prefix_cache:
            return
        sp = self._slot_pages[slot]
        pool = self._page_pool
        ps = self._ps
        digest = b""
        full = plen // ps
        for i in range(full):
            digest = self._prefix_digest(digest, prompt[i * ps:(i + 1) * ps])
            pool.register(digest, sp[i])
        rem = prompt[full * ps:]
        if rem.size and full < len(sp):
            tkey = hashlib.sha1(digest + b"T" + rem.tobytes()).digest()
            pool.register(tkey, sp[full])

    # ------------------------------------------------------ prefill path
    def _prefill_wave(self, group):
        """Batched chunked prefill for one admission wave: every staged
        slot advances through rounds of at most ``prefill_chunk`` suffix
        tokens (Sarathi-style chunked prefill: the transient per-round
        activations stay bounded no matter how long the prompts are). A
        round's rows with suffix left, longest chunk first, are packed
        into row groups: a group takes at most ``_prefill_rows`` rows and
        the column bucket of ITS longest chunk, ONE dispatch and one fetch
        a group, so a round of ``n`` rows is ``ceil(n / width)`` dispatches
        of ``width x bucket`` positions and a lone admission computes
        ``width`` rows, never ``slots``. Slots outside a group
        are not in its dispatch. Chunk and group boundaries are
        numerically transparent: each token's attention reduces over
        exactly the columns at or before its true position in the same
        order and no row's forward reads another row, so outputs are
        bit-identical to a single full-length prefill of each prompt. A
        row samples its first token in the round consuming its final
        chunk; a dispatch failure fails the whole wave typed (pages
        released, slots stay free).

        A group ships slot indices (``int32 [R]``, ``slots`` for a padding
        row) and token ids (``int32 [R, bucket]``, id 0 under a zero mask
        for padding columns and padding rows); the program embeds them on
        the device: what the host hands a dispatch is
        a few kilobytes (``generation_prefill_host_bytes_total``), never a
        block with the vocabulary as a dimension. ``submit()`` and
        ``adopt_request`` hold every id inside ``[0, vocab)``."""
        import jax

        span = self._clock.span
        keys = {}
        cur = {}
        first = {}
        deadline = None
        with span("prefill_keys", rows=len(group)):
            for s, req, pos0, _ in group:
                cur[s] = pos0
                keys[s] = jax.device_get(jax.random.PRNGKey(req.seed))
                if req.deadline is not None and (
                        deadline is None or req.deadline.remaining()
                        < deadline.remaining()):
                    deadline = req.deadline
        while True:
            live = [(s, req, plen) for s, req, _, plen in group
                    if cur[s] < plen]
            if not live:
                break
            chunk = {s: min(plen - cur[s], self._chunk_cap)
                     for s, _, plen in live}
            # longest first (ties in slot order): a group's first row
            # sets its bucket, so short chunks share narrow-column groups
            live.sort(key=lambda e: -chunk[e[0]])
            width = self._prefill_rows
            while live:
                members, live = live[:width], live[width:]
                try:
                    toks = self._prefill_group(members, chunk, cur, keys,
                                               deadline)
                except Exception as e:  # noqa: BLE001 — typed failure for
                    # the wave; every staged slot stays free for the next
                    for s, req, *_ in group:
                        self._release_slot_pages(s)
                        if isinstance(e, DeadlineExceeded):
                            self._m_expired.inc()
                        else:
                            self._m_failed.inc()
                        self._fail(req, e)
                    return
                # a wave is many dispatches in one tick: its seconds reach
                # the counters a dispatch at a time, like a decode step's
                self._clock.publish()
                for (s, _, plen), tok in zip(members, toks):
                    cur[s] += chunk[s]
                    if cur[s] >= plen:
                        # this round consumed the row's final chunk, so
                        # its sampled token came from the true last
                        # position; earlier rounds' samples are padding
                        # garbage
                        first[s] = tok
        with span("prefill_commit", rows=len(group)):
            for s, req, pos0, plen in group:
                if self._draft is not None:
                    try:
                        self._draft_prefill(s, req, plen)
                    except Exception as e:  # noqa: BLE001
                        self._release_slot_pages(s)
                        if isinstance(e, DeadlineExceeded):
                            self._m_expired.inc()
                        else:
                            self._m_failed.inc()
                        self._fail(req, e)
                        continue
                self._commit_slot(s, req, plen, first[s], keys[s])
            # disaggregated prefill: export the wave's export_kv slots
            # that are still live (a request that finished on its first
            # token was already retired with a complete result — no
            # handoff needed)
            exports = [(s, req) for s, req, *_ in group
                       if req.export_kv and self._slot_req[s] is req]
            if exports:
                self._transfer_loop(exports)

    def _prefill_group(self, members, chunk, cur, keys, deadline):
        """One row group's dispatch and fetch: ``members`` (at most
        ``_prefill_rows`` of a round's ``(slot, request, prompt length)``,
        longest chunk first) each consume ``chunk[slot]`` tokens from
        ``cur[slot]`` on, in the column bucket of the first. Returns the
        token sampled for each member, in order; raises what the dispatch
        raised once the retry policy gave up."""
        import jax

        span = self._clock.span
        with span("prefill_build", rows=len(members)):
            target = max(chunk[members[0][0]], self.min_prefill_bucket)
            bucket = bucket_pages(
                target, self._ps,
                maximum=min(self._np, max(1, self._chunk_cap // self._ps))
            ) * self._ps
            prog = self._prefill_program(bucket)
            width = self._prefill_rows
            # rows past the members are padding: slot index `slots`, which
            # the program routes to the garbage page and never scatters
            rows = np.full((width,), self.slots, np.int32)
            ids = np.zeros((width, bucket), np.int32)
            mask = np.zeros((width, bucket), np.float32)
            positions = np.zeros((width,), np.int32)
            sufflen = np.ones((width,), np.int32)
            temp = np.zeros((width,), np.float32)
            topk = np.zeros((width,), np.int32)
            base_keys = np.zeros((width, 2), np.uint32)
            for i, (s, req, _) in enumerate(members):
                n = chunk[s]
                if self._windowed:
                    self._slide_windows(s, cur[s])
                    self._ensure_slot_pages(s, cur[s] + n,
                                            write_from=cur[s], windows=True)
                rows[i] = s
                ids[i, :n] = req.prompt[cur[s]:cur[s] + n]
                mask[i, :n] = 1
                positions[i] = cur[s]
                sufflen[i] = n
                temp[i] = req.temperature
                topk[i] = req.top_k
                base_keys[i] = keys[s]
            # what this dispatch built for the device (the block table is
            # the loop's standing host mirror, not built per dispatch)
            built = (rows, positions, ids, mask, sufflen, temp, topk,
                     base_keys)
            dispatch = prog if self._chaos is None \
                else self._chaos.wrap(prog)

        def attempt():
            try:
                with self._first_call(prog):
                    out = dispatch(*self._weights(), self._pool,
                                   self._bt_arg(), *built)
            except Exception:
                self.breaker.record_failure()
                raise
            self.breaker.record_success()
            return out

        # the jitted call returning: argument copies and the enqueue (a
        # bucket's first call traces and compiles in here)
        with span("prefill_dispatch", rows=len(members), bucket=bucket):
            new_pool, sampled, *counts = self.retry.call(
                attempt, deadline=deadline, on_retry=self._count_retry)
        self._pool = new_pool
        # ONE fetch per dispatch (the layers' counts ride it)
        with span("prefill_fetch"):
            toks, counts = jax.device_get((sampled, counts))
        with span("prefill_commit"):
            self._m_prefill_rounds.inc()
            self._m_prefill_host_bytes.inc(sum(a.nbytes for a in built))
            for how, n in (("admitted", len(members)), ("computed", width)):
                for c in self._m_prefill_rows[how]:
                    c.inc(n)
            self._publish_counts("prefill", counts)
            for c in self._m_embed_rows[self._embed_path]:
                c.inc(width * bucket)
            if self._m_prefill_read:
                for layer in self._chunk_readers:
                    path = layer.prefill_read_path(bucket,
                                                   *self._read_dtypes)
                    for c in self._m_prefill_read[path]:
                        c.inc(width * bucket)
            if self._slot_names:
                self._m_slot_resets.inc(
                    sum(1 for s, _, _ in members if cur[s] == 0))
            return toks.tolist()[:len(members)]

    def _commit_slot(self, slot: int, req: _Request, plen: int, tok, key):
        """Publish one prefilled slot: trim the bucket over-allocation,
        register its prefix pages, seed the decode mirrors, and mark the
        slot active."""
        self._trim_slot_pages(slot, plen)
        self._register_prefix(slot, req.prompt, plen)
        self._last[slot] = tok
        self._counts[slot] = 1
        self._snap_counts[slot] = 0  # fresh stream: restart the cadence
        self._temp[slot] = req.temperature
        self._topk[slot] = req.top_k
        self._keys[slot] = key
        self._pos[slot] = plen
        req.tokens.append(tok)
        # TTFT stamp: the first token exists NOW, even when the request
        # later crosses the tier boundary (fleet histograms read this)
        req.future._t_first = req.t_last = time.monotonic()
        self._admit_seq += 1
        self._slot_seq[slot] = self._admit_seq
        with self._cond:
            self._slot_req[slot] = req
            self._n_active += 1
        self._m_prefills.inc()
        self._m_admitted.inc()
        self._m_tokens.inc()
        if self._finished(req, tok):
            self._retire(slot, req)

    def _draft_prefill(self, slot: int, req: _Request, plen: int):
        """Prime the draft's dense cache row for ``slot`` with the full
        prompt (the dense draft cache cannot share pages)."""
        bucket = bucket_length(plen, minimum=self.min_prefill_bucket,
                               maximum=self._draft_cap)
        prog = self._draft_prefill_program(bucket)
        ids = np.zeros((1, bucket), np.int32)
        ids[0, :plen] = req.prompt
        mask = np.zeros((1, bucket), np.float32)
        mask[0, :plen] = 1
        dispatch = prog if self._chaos is None else self._chaos.wrap(prog)

        def attempt():
            try:
                with self._first_call(prog):
                    out = dispatch(*self._weights(self._draft),
                                   self._dpool, np.int32(slot), ids, mask)
            except Exception:
                self.breaker.record_failure()
                raise
            self.breaker.record_success()
            return out

        self._dpool = self.retry.call(attempt, deadline=req.deadline,
                                      on_retry=self._count_retry)

    # ------------------------------------------------------- decode path
    def _active_mask(self):
        return np.array([r is not None for r in self._slot_req])

    def _decode_once(self):
        import jax

        span = self._clock.span
        m_steps = self.steps_per_dispatch
        with span("decode_reserve"):
            prog = self._decode_program()
            self._reserve_decode_pages()
            active = self._active_mask()
            dispatch = prog if self._chaos is None \
                else self._chaos.wrap(prog)

        def attempt():
            try:
                with self._first_call(prog):
                    out = dispatch(*self._weights(), self._pool,
                                   self._bt_arg(), self._pos, self._last,
                                   active, self._temp, self._topk,
                                   self._keys, self._counts)
            except Exception:
                self.breaker.record_failure()
                raise
            self.breaker.record_success()
            return out

        # the jitted call returning: argument copies and the enqueue
        with span("decode_dispatch", rows=np.count_nonzero(active),
                  steps=m_steps):
            try:
                new_pool, seq, *counts = self.retry.call(
                    attempt, on_retry=self._count_retry)
            except Exception as e:  # noqa: BLE001 — pool state is now
                # suspect (possibly donated away): fail the batch typed
                # and restart from a fresh pool so later requests still
                # serve
                self._fail_all(e)
                return
        self._pool = new_pool
        # ONE [S, M] fetch per dispatch (the layers' counts ride it)
        with span("decode_fetch"):
            toks, counts = jax.device_get((seq, counts))
        with span("decode_walk"):
            self._stamp_deliveries()
            self._publish_counts("decode", counts)
            self._count_kv_reads(active)
            path = "greedy" if np.all(self._temp <= 0) else "select"
            for c in self._m_sampler_steps[path]:
                c.inc(m_steps)
            for c in self._m_embed_rows[self._embed_path]:
                c.inc(self.slots * m_steps)
            ntok = 0
            for s in range(self.slots):
                req = self._slot_req[s]
                if req is None:
                    continue
                done = False
                for tok in toks[s].tolist():
                    req.tokens.append(tok)
                    ntok += 1
                    if self._finished(req, tok):
                        done = True
                        break
                # the device advanced the full window regardless of where
                # the request finished; mirrors track the device (which
                # write-clamps position and count at capacity)
                adv = min(m_steps, self._cap_tokens - self._pos[s])
                self._counts[s] += adv
                self._pos[s] += adv
                self._last[s] = toks[s, m_steps - 1]
                if done:
                    self._retire(s, req)
            # ONE registry publish per decode step, not one per token
            self._m_decode_steps.inc()
            for c in self._m_decode_steps_pw:
                c.inc()
            self._m_tokens.inc(ntok)

    def _stamp_deliveries(self):
        """A decode fetch just handed every active request tokens: observe
        how long each had waited since its last delivery (the first is its
        first token). One clock read and one locked publish a dispatch."""
        now = time.monotonic()
        gaps = []
        for req in self._slot_req:
            if req is not None:
                gaps.append((now - req.t_last) * 1e3)
                req.t_last = now
        for h in self._m_token_gap:
            h.observe_many(gaps)

    def _count_kv_reads(self, active):
        """What a decode dispatch's paged reads had to fetch and what the
        backend fetched, from the host's mirrors before they advance: a
        row at position p reads p + 1 keys (its own among them), one more
        each micro-step, until it freezes at capacity. The Pallas kernel
        copies whole pages, the live ones; the dense view holds every
        slot's capacity."""
        m_steps = self.steps_per_dispatch
        ctx = self._pos[active][:, None] + np.arange(1, m_steps + 1)
        ctx = ctx[ctx <= self._cap_tokens]
        if self._windowed:
            return self._count_class_reads(ctx)
        live = ctx.sum().item()
        viewed = (-(-ctx // self._ps) * self._ps).sum().item() \
            if self._pa == "pallas" \
            else self.slots * self._cap_tokens * m_steps
        for kind, n in (("live", live), ("viewed", viewed)):
            for c in self._m_kv_tokens[kind]:
                c.inc(n * len(self._paged_names))

    def _count_class_reads(self, ctx):
        """The same two counts for a net with a window class, by class and
        in sum: a window layer has to fetch ``min(context, window)`` keys.
        Under the Pallas backend the kernel fetches whole pages, a full
        layer's live ones and a window layer's from the row's first live
        page on; a dense view (XLA) is as wide as the table, a window
        layer's as the window and a dispatch's steps span. And, at this
        decode dispatch, the pages each class holds beside what one table
        for all paged layers would hold for the same slots."""
        m_steps, ps = self.steps_per_dispatch, self._ps
        total = {"live": 0, "viewed": 0}
        for c in self._classes:
            pages = -(-ctx // ps)
            if c.window is None:
                live, width = ctx.sum().item(), self._cap_tokens
            else:
                live = np.minimum(ctx, c.window).sum().item()
                width = ps * min(self._np, self._layer_by_name[
                    c.layers[0]].window_pages(m_steps, ps))
                # the query at ctx - 1 sees nothing before this page
                pages = pages - np.maximum(ctx - c.window, 0) // ps
            viewed = (pages * ps).sum().item() if self._pa == "pallas" \
                else self.slots * width * m_steps
            for kind, n in (("live", live), ("viewed", viewed)):
                n *= len(c.layers)
                total[kind] += n
                for m in self._m_class["tokens"][kind, c.name]:
                    m.inc(n)
            for g in self._m_class["pages"][c.name]:
                g.set(c.pool.in_use())
        for kind, n in total.items():
            for m in self._m_kv_tokens[kind]:
                m.inc(n)
        uniform = sum(max(len(c.slot_pages[s]) for c in self._classes)
                      for s in range(self.slots))
        for layout, n in (("classes", self._resident_bytes()),
                          ("uniform", uniform * self._page_bytes)):
            for m in self._m_class["resident"][layout]:
                m.inc(n)

    def _publish_counts(self, program, counts):
        """A dispatch's call counts by layer, already on the host, to the
        counters their layers declared."""
        for by_layer in counts:
            for name, vec in by_layer.items():
                for children, n in zip(self._m_counted[program][name],
                                       vec.tolist()):
                    for c in children:
                        c.inc(n)

    def _spec_decode_once(self):
        import jax

        span = self._clock.span
        k_spec = self.spec_k
        with span("decode_reserve"):
            prog = self._spec_program()
            self._reserve_decode_pages()
            active = self._active_mask()
            dispatch = prog if self._chaos is None \
                else self._chaos.wrap(prog)

        def attempt():
            try:
                with self._first_call(prog):
                    out = dispatch(*self._weights(),
                                   *self._weights(self._draft),
                                   self._pool, self._dpool, self._bt,
                                   self._pos, self._last, active,
                                   self._temp, self._topk, self._keys,
                                   self._counts)
            except Exception:
                self.breaker.record_failure()
                raise
            self.breaker.record_success()
            return out

        with span("decode_dispatch", rows=np.count_nonzero(active),
                  steps=k_spec):
            try:
                new_pool, new_dpool, true, acc = self.retry.call(
                    attempt, on_retry=self._count_retry)
            except Exception as e:  # noqa: BLE001 — both pools suspect
                self._fail_all(e)
                return
        self._pool = new_pool
        self._dpool = new_dpool
        with span("decode_fetch"):
            true, acc = jax.device_get((true, acc))  # ONE fetch per round
        with span("decode_walk"):
            self._stamp_deliveries()
            ntok = 0
            proposed = 0
            accepted = 0
            for s in range(self.slots):
                req = self._slot_req[s]
                if req is None:
                    continue
                n = min(acc[s] + 1, k_spec)
                proposed += k_spec - 1
                accepted += n - 1
                done = False
                for tok in true[s, :n].tolist():
                    req.tokens.append(tok)
                    ntok += 1
                    if self._finished(req, tok):
                        done = True
                        break
                self._counts[s] += n
                self._pos[s] += n
                self._last[s] = true[s, n - 1]
                if done:
                    self._retire(s, req)
            # ONE registry publish per speculative round, not one per slot
            self._m_spec_rounds.inc()
            self._m_spec_proposed.inc(proposed)
            self._m_spec_accepted.inc(accepted)
            self._m_decode_steps.inc()
            for c in self._m_decode_steps_pw:
                c.inc()
            self._m_tokens.inc(ntok)

    def _finished(self, req: _Request, tok) -> bool:
        if req.eos_id is not None and tok == req.eos_id:
            return True
        return len(req.tokens) >= req.max_tokens

    def _retire(self, slot: int, req: _Request):
        self._release_slot_pages(slot)
        with self._cond:
            self._slot_req[slot] = None
            self._n_active -= 1
            self._cond.notify_all()
        self._m_retired.inc()
        self._m_completed.inc()
        try:
            req.future.set_result(np.asarray(req.tokens, np.int64))
        except Exception:  # future cancelled/resolved by the caller
            pass

    def _expire_active(self):
        for s in range(self.slots):
            req = self._slot_req[s]
            if req is None or req.deadline is None \
                    or not req.deadline.expired():
                continue
            self._release_slot_pages(s)
            with self._cond:
                self._slot_req[s] = None
                self._n_active -= 1
                self._cond.notify_all()
            self._m_expired.inc()
            self._fail(req, DeadlineExceeded(
                "request budget exhausted mid-generation after "
                f"{len(req.tokens)} tokens"))

    def _fail(self, req: _Request, exc: BaseException):
        try:
            req.future.set_exception(exc)
        except Exception:  # already resolved/cancelled
            pass

    def _fail_all(self, exc: BaseException):
        """Hard dispatch fault: every in-flight request fails typed
        (never hangs) and the page pool + device carries are rebuilt
        from zeros. The rebuild decision is taken under ``_cond`` so a
        chaos kill racing ``close()``/``drain()`` cannot resurrect device
        state on a server that is already shutting down — after the
        victims fail there is nothing left to serve, so a closing server
        skips the rebuild entirely (idempotent with close)."""
        with self._cond:
            victims = [r for r in self._slot_req if r is not None]
            victims += list(self._queue)
            self._queue.clear()
            self._slot_req = [None] * self.slots
            self._n_active = 0
            rebuild = not (self._closing or self._stop)
            self._cond.notify_all()
        self._m_failed.inc(len(victims))
        if rebuild:
            self._m_pool_rebuilds.inc()
        for req in victims:
            self._fail(req, exc)
        if rebuild:
            self._reset_device_state()

    def _reset_device_state(self):
        self._reset_paging()
        self._pos[:] = 0
        self._pool = self._fresh_pool()
        if self._draft is not None:
            self._dpool = self._fresh_draft_pool()

    def _count_retry(self, attempt, exc):
        self._m_retried.inc()

    # ------------------------------------------------- snapshot/handoff
    def _snapshot_slot(self, slot: int) -> KVSnapshot:
        """Serialize slot ``slot``'s live state into a KVSnapshot: the
        pages holding KV positions [0, pos) — look-ahead pages beyond
        the stream position hold garbage and are skipped — fetched in
        ONE non-donating dispatch + ONE device_get, the prefix-cache
        digests of still-pristine chunk pages, and the resume header
        from the host mirrors. Loop-thread only; all host-side scalar
        conversion happens in ``pack_snapshot`` (this function is on the
        graftcheck hot list)."""
        import jax

        req = self._slot_req[slot]
        pos = self._pos[slot]
        n = -(-pos // self._ps)            # pages holding [0, pos)
        sp = self._slot_pages[slot]
        pool = self._page_pool
        digests = [pool.tag.get(p) for p in sp[:n]]
        idx = np.zeros(self._np, np.int32)  # pad rows fetch page 0
        idx[:n] = sp[:n]
        prog = self._page_fetch_program()
        # device_get of the (possibly head-sharded) gather assembles
        # full stacks in the pool's order, which each layer turns into
        # the CANONICAL host layout — [NP, H, ps, d] — so the wire
        # payload is tp-independent and any-tp adopters re-shard locally
        # (_reshard_snapshot); the header records this server's shard
        # count for diagnostics only
        with self._first_call(prog):
            stacks = prog(self._pool, idx)
        fetched = {vn: self._layer_by_name[vn].paged_to_wire(planes)
                   for vn, planes in jax.device_get(stacks).items()}
        return pack_snapshot(
            req=req, pos=pos, count=self._counts[slot],
            last=self._last[slot], key=self._keys[slot].copy(),
            kv_dtype=self.kv_dtype, page_size=self._ps,
            page_token_bytes=self._page_token_bytes,
            page_digests=digests, fetched=fetched, n_pages=n,
            shards=self._tp, head_layout="canonical")

    def _publish_snapshot(self, req: _Request, snap: KVSnapshot):
        """Count the export, run the chaos injector, and attach the
        snapshot to the request's future — the transport: whoever holds
        the future (the fleet's done-callback, a migration driver) reads
        ``future._kv_snapshot`` when the request fails mid-stream. An
        injected ``drop`` makes the transfer vanish (nothing attached —
        the consumer falls back to whatever it already had); ``corrupt``
        and ``truncate`` damage the wire content so the adopter's
        checksum fails."""
        mode = None if self._chaos is None \
            else self._chaos.handoff_fault_mode()
        if mode == "drop":
            return
        if mode == "corrupt":
            corrupt_snapshot(snap)
        elif mode == "truncate":
            truncate_snapshot(snap)
        self._m_handoff_snapshots.inc()
        self._m_handoff_bytes.inc(snap.wire_bytes())
        req.future._kv_snapshot = snap

    def _transfer_loop(self, exports):
        """Disaggregated-prefill transfer: ship each freshly prefilled
        ``export_kv`` slot across the tier boundary — the future
        resolves to the ``KVSnapshot`` itself, the slot's pages free
        immediately (this is where the prefill tier's short slot
        residency comes from), and a decode-tier server adopts the
        snapshot to stream the rest. Failure never loses the request: a
        snapshot failure degrades to co-located decode in this server's
        own loop, and an injected transfer drop fails the future typed
        (``SnapshotUnavailable``, no snapshot attached) so a fleet
        re-prefills on a sibling. Loop-thread only; on the graftcheck
        hot list, so scalar host syncs stay in ``pack_snapshot``."""
        for slot, req in exports:
            if self._slot_req[slot] is not req:
                continue  # retired/expired between commit and transfer
            try:
                snap = self._snapshot_slot(slot)
            except Exception:  # noqa: BLE001 — degrade to co-located
                # decode: the slot stays active and this server streams
                # the completion itself (always correct, never lost)
                self._m_handoff_fallbacks.inc()
                continue
            mode = None if self._chaos is None \
                else self._chaos.handoff_fault_mode()
            if mode == "corrupt":
                corrupt_snapshot(snap)
            elif mode == "truncate":
                truncate_snapshot(snap)
            self._release_slot_pages(slot)
            with self._cond:
                self._slot_req[slot] = None
                self._n_active -= 1
                self._cond.notify_all()
            if mode == "drop":
                # the transfer vanished in flight: fail typed WITHOUT a
                # snapshot attached — the consumer re-runs the prefill
                # elsewhere (zero lost futures, some recompute)
                self._m_failed.inc()
                self._fail(req, SnapshotUnavailable(
                    "handoff transfer dropped in flight"))
                continue
            self._m_handoff_snapshots.inc()
            self._m_handoff_bytes.inc(snap.wire_bytes())
            self._m_prefill_exports.inc()
            self._m_retired.inc()
            self._m_completed.inc()
            try:
                req.future.set_result(snap)
            except Exception:  # caller gave up
                pass

    def _maybe_snapshot_slots(self):
        """Periodic low-priority snapshotting: at most ONE slot per loop
        iteration — the most overdue one — so exports never crowd out
        decode dispatches. Best-effort by design: a failed export leaves
        the slot exactly as it was (the fleet then falls back to token-0
        regeneration, which is always correct)."""
        if not self.snapshot_every:
            return
        best, best_lag = -1, 0
        for s in range(self.slots):
            if self._slot_req[s] is None:
                continue
            lag = int(self._counts[s]) - self._snap_counts[s]
            if lag >= self.snapshot_every and lag > best_lag:
                best, best_lag = s, lag
        if best < 0:
            return
        req = self._slot_req[best]
        try:
            snap = self._snapshot_slot(best)
        except Exception:  # noqa: BLE001 — best-effort
            return
        self._snap_counts[best] = int(self._counts[best])
        self._publish_snapshot(req, snap)

    def _service_exports(self):
        """Resolve queued ``export_request`` handshakes between
        dispatches (loop thread — the only thread allowed near the
        pool). Each resolves to a snapshot or fails typed; a request no
        longer resident in a slot is ``SnapshotUnavailable``."""
        while True:
            with self._cond:
                if not self._export_q:
                    return
                fut_in, out = self._export_q.popleft()
            slot = -1
            for s in range(self.slots):
                r = self._slot_req[s]
                if r is not None and r.future is fut_in:
                    slot = s
                    break
            if slot < 0:
                self._fail_export(out, SnapshotUnavailable(
                    "request is not resident in a decode slot (never "
                    "admitted, already retired, or failed)"))
                continue
            try:
                snap = self._snapshot_slot(slot)
            except Exception as e:  # noqa: BLE001 — typed to the caller
                self._fail_export(out, e)
                continue
            self._snap_counts[slot] = int(self._counts[slot])
            self._publish_snapshot(self._slot_req[slot], snap)
            try:
                out.set_result(snap)
            except Exception:  # caller gave up
                pass

    @staticmethod
    def _fail_export(out: Future, exc: BaseException):
        try:
            out.set_exception(exc)
        except Exception:  # caller gave up
            pass

    def export_request(self, future, timeout: Optional[float] = 30.0
                       ) -> KVSnapshot:
        """Snapshot the live request behind ``future`` (as returned by
        ``submit``). Blocks until the serving loop services the export
        between dispatches — never longer than the request's OWN
        remaining deadline budget: the wait is
        ``min(timeout, deadline.remaining())`` and expiry raises the
        typed ``DeadlineExceeded``, not a generic timeout. Raises
        ``SnapshotUnavailable`` when the request is not resident in a
        slot, ``SnapshotUnsupported`` where the server cannot snapshot
        (a speculative server, or a cache ``_LIMITS`` names)."""
        self._refuse("snapshot", "export", SnapshotUnsupported)
        deadline = getattr(future, "_deadline", None)
        eff = timeout
        if deadline is not None:
            rem = deadline.remaining()
            if rem <= 0:
                raise DeadlineExceeded(
                    "request budget exhausted before the export "
                    f"({-rem * 1e3:.1f} ms over)")
            eff = rem if eff is None else min(eff, rem)
        out: Future = Future()
        with self._cond:
            if self._closing:
                raise RuntimeError("GenerationServer is closed")
            self._export_q.append((future, out))
            self._cond.notify_all()
        try:
            return out.result(timeout=eff)
        except FutureTimeout:
            if deadline is not None and deadline.expired():
                raise DeadlineExceeded(
                    "request budget exhausted waiting for the export "
                    f"({-deadline.remaining() * 1e3:.1f} ms over)")
            raise

    def adopt_request(self, snapshot: KVSnapshot, *,
                      deadline_s: Optional[float] = None) -> Future:
        """Rebuild a snapshotted request into this server and resume
        decoding at position N. Validation is all up front and typed:
        ``SnapshotInvalid`` (bad checksum/version/shape — the caller
        falls back to token-0 regeneration), ``SnapshotUnsupported``
        (kv_dtype/page-geometry mismatch or a speculative server),
        ``ServerOverloaded`` (cannot fit the page budget / admission
        watermark), ``CircuitOpen``. The resumed completion is
        byte-identical to the never-interrupted one: the serial
        ``fold_in(key, token_index)`` schedule rides in the snapshot."""
        self._refuse("snapshot", "adopt", SnapshotUnsupported)
        # v2 snapshots (single-chip geometry, no shard header) adopt as
        # the legacy fallback: their payload layout IS the canonical
        # shards=1 layout, so only the header generation differs
        if snapshot.version not in (WIRE_VERSION - 1, WIRE_VERSION):
            raise SnapshotInvalid(
                f"KVSnapshot wire version {snapshot.version} != "
                f"supported {WIRE_VERSION}")
        if not snapshot.verify():
            raise SnapshotInvalid("KVSnapshot checksum mismatch")
        if (snapshot.kv_dtype != self.kv_dtype
                or snapshot.page_size != self._ps
                or snapshot.page_token_bytes != self._page_token_bytes
                or snapshot.head_layout != "canonical"):
            raise SnapshotUnsupported(
                f"snapshot geometry (kv_dtype={snapshot.kv_dtype!r}, "
                f"page_size={snapshot.page_size}, "
                f"{snapshot.page_token_bytes} B/token, "
                f"head_layout={snapshot.head_layout!r}) does not match "
                f"this server (kv_dtype={self.kv_dtype!r}, "
                f"page_size={self._ps}, {self._page_token_bytes} "
                f"B/token, head_layout='canonical'); the exporter's "
                f"shard count ({snapshot.shards}) is free to differ — "
                "adopt re-shards to the local mesh")
        plen = int(snapshot.prompt.shape[0])
        # a later preemption re-prefills from the prompt, whose ids the
        # device would embed as silent wrong rows out of range
        if self._outside_vocab(snapshot.prompt):
            raise SnapshotInvalid(
                f"KVSnapshot prompt ids outside [0, {self.vocab})")
        if (snapshot.count != len(snapshot.tokens)
                or snapshot.pos != plen + snapshot.count - 1
                or snapshot.n_pages != -(-snapshot.pos // self._ps)):
            raise SnapshotInvalid(
                "inconsistent KVSnapshot header: position/count/page "
                "stack disagree with the token history")
        need_tokens = plen + snapshot.max_tokens - 1
        need_pages = -(-need_tokens // self._ps)
        if need_tokens > self._cap_tokens \
                or need_pages > self.pages_total - 1:
            raise ServerOverloaded(
                f"infeasible adoption: prompt {plen} + max_tokens "
                f"{snapshot.max_tokens} needs {need_pages} pages / "
                f"{need_tokens} tokens against capacity "
                f"{self.pages_total - 1} pages / {self._cap_tokens} "
                "tokens")
        if not self.breaker.allow():
            raise CircuitOpen("circuit breaker is open: recent decode "
                              "dispatches failed above threshold")
        # remaining-budget propagation across the tier boundary: an
        # explicit deadline_s wins, then the remaining budget the
        # snapshot carried from the exporting server (a duration — it
        # re-arms here against THIS host's monotonic clock), then this
        # server's default
        budget = deadline_s
        if budget is None:
            budget = snapshot.deadline_remaining
        if budget is None:
            budget = self.request_deadline_s
        req = _Request(snapshot.prompt.astype(np.int64),
                       snapshot.max_tokens, snapshot.temperature,
                       snapshot.top_k, snapshot.seed, snapshot.eos_id,
                       None if budget is None else Deadline(budget))
        req.tokens = list(snapshot.tokens)
        req.snapshot = snapshot
        req.future._deadline = req.deadline
        self.admission.acquire()  # raises ServerOverloaded at watermark
        req.future.add_done_callback(lambda _f: self.admission.release())
        with self._cond:
            if self._closing:
                self._fail(req, RuntimeError("GenerationServer is closed"))
                return req.future
            self._queue.append(req)
            self._cond.notify_all()
        return req.future

    def _adopt_into_slot(self, slot: int, req: _Request) -> bool:
        """Rebuild ``req.snapshot`` into slot ``slot``: pages whose
        chunk digest is already resident are SHARED out of the prefix
        cache (no upload — shared prefixes re-dedupe on arrival), the
        rest are uploaded in ONE donated store dispatch, pristine prompt
        chunk pages are re-registered for future sharers, and the decode
        mirrors resume at position N. Returns False after rolling back
        (pool pressure) — the caller falls back to a token-0 prefill,
        which is always correct. Loop-thread only; on the graftcheck hot
        list, so scalar host syncs stay out of here."""
        snap = req.snapshot
        pool = self._page_pool
        sp = self._slot_pages[slot]
        n = snap.n_pages
        shared = set()
        try:
            for i in range(n):
                d = snap.page_digests[i]
                page = pool.lookup(d) \
                    if (d is not None and self.prefix_cache) else None
                if page is not None:
                    pool.share(page)
                    shared.add(i)
                else:
                    page = self._alloc_page(slot)
                self._bt[slot, i] = page
                sp.append(page)
        except RuntimeError:
            # pool exhausted mid-adoption: roll back and fall back to
            # the token-0 prefill path (fewer pages via prefix match,
            # and admission already proved the request itself feasible)
            self._release_slot_pages(slot)
            req.snapshot = None
            req.tokens.clear()
            self._m_handoff_fallbacks.inc()
            return False
        dst = np.zeros(self._np, np.int32)  # pad/dedup rows -> garbage
        for i in range(n):
            if i not in shared:
                dst[i] = self._bt[slot, i]
        prog = self._page_store_program()
        with self._first_call(prog):
            self._pool = prog(self._pool, dst, self._reshard_snapshot(
                padded_payload(snap, self._np)))
        # re-hash the pristine prompt chunk pages into this server's
        # prefix cache (the tail page already holds decoded tokens and
        # must NOT be registered under the whole-prompt tail key)
        plen = req.prompt.shape[0]
        if self.prefix_cache:
            digest = b""
            ps = self._ps
            for i in range(min(plen // ps, n)):
                digest = self._prefix_digest(
                    digest, req.prompt[i * ps:(i + 1) * ps])
                pool.register(digest, sp[i])
        self._last[slot] = snap.last
        self._counts[slot] = snap.count
        self._temp[slot] = req.temperature
        self._topk[slot] = req.top_k
        self._keys[slot] = snap.key
        self._pos[slot] = snap.pos
        self._admit_seq += 1
        self._slot_seq[slot] = self._admit_seq
        self._snap_counts[slot] = snap.count
        req.snapshot = None
        req.t_last = time.monotonic()   # the gap restarts where it resumes
        with self._cond:
            self._slot_req[slot] = req
            self._n_active += 1
        self._m_admitted.inc()
        self._m_handoff_resumes.inc()
        self._m_handoff_saved.inc(len(req.tokens))
        if req.tokens and self._finished(req, req.tokens[-1]):
            self._retire(slot, req)
        return True

    def _migrate_out(self):
        """Drain-migrate sweep (loop thread): every live slot is
        snapshotted at its exact stream position and failed typed with
        ``RequestMigrated`` — the snapshot rides on the failed future,
        so a fleet (or any migration driver) adopts it elsewhere and
        loses zero tokens. Queued requests migrate with whatever
        snapshot they already carry (usually none: token-0 redispatch).
        A speculative server migrates snapshot-free — still zero lost
        futures, just recomputed."""
        with self._cond:
            cb = self._migrate_cb
            self._migrating = False
            self._migrate_cb = None
            queued = list(self._queue)
            self._queue.clear()
            self._cond.notify_all()
        for req in queued:
            if req.snapshot is not None:
                req.future._kv_snapshot = req.snapshot
            self._m_migrated.inc()
            self._fail(req, RequestMigrated(
                "request migrated off a draining server before prefill"))
        for s in range(self.slots):
            req = self._slot_req[s]
            if req is None:
                continue
            snap = None
            if "snapshot" not in self._limits:
                try:
                    snap = self._snapshot_slot(s)
                except Exception:  # noqa: BLE001 — degrade to token-0
                    snap = None
            if snap is not None:
                self._publish_snapshot(req, snap)
                if cb is not None:
                    try:
                        cb(snap)
                    except Exception:  # sink errors never lose requests
                        pass
            self._release_slot_pages(s)
            with self._cond:
                self._slot_req[s] = None
                self._n_active -= 1
                self._cond.notify_all()
            self._m_migrated.inc()
            self._fail(req, RequestMigrated(
                "request migrated off a draining server after "
                f"{len(req.tokens)} tokens"))

    # --------------------------------------------------------- lifecycle
    def drain(self, timeout: Optional[float] = None, *,
              migrate=False) -> bool:
        """Block until every queued and in-flight request has resolved
        (completed, expired, or failed). Returns False on timeout.

        ``migrate`` truthy flips the drain from wait-out to move-out:
        live requests are snapshotted and failed ``RequestMigrated``
        (snapshot attached to the failed future) instead of being
        decoded to completion — a fleet resumes them on another replica
        with zero recompute. Pass a callable to also receive each
        ``KVSnapshot`` as it is exported."""
        if migrate:
            with self._cond:
                self._migrating = True
                self._migrate_cb = migrate if callable(migrate) else None
                self._cond.notify_all()
        deadline = None if timeout is None else time.monotonic() + timeout
        with self._cond:
            while self._queue or self._n_active:
                left = None if deadline is None \
                    else deadline - time.monotonic()
                if left is not None and left <= 0:
                    return False
                self._cond.wait(timeout=0.05 if left is None
                                else min(left, 0.05))
        return True

    def close(self, timeout: float = 30.0) -> None:
        """Stop admitting, drain what is in flight, stop the loop. Any
        request still unresolved past ``timeout`` fails typed — a closed
        server never leaves a hung future behind (and never leaks its
        pages). Idempotent and re-entrant: safe from any thread, twice,
        or concurrently — the runtime serializes the actual shutdown."""
        with self._cond:
            # before the drain begins, so a chaos kill landing mid-drain
            # cannot win a restart race against this deliberate close
            self._user_close = True
        self._runtime.begin_drain()   # submit() now rejects typed
        self.drain(timeout)
        self._runtime.close(max(timeout, 1.0))
        with self._cond:
            stragglers = [s for s in range(self.slots)
                          if self._slot_req[s] is not None]
            victims = [self._slot_req[s] for s in stragglers]
            victims += list(self._queue)
            self._queue.clear()
            self._slot_req = [None] * self.slots
            self._n_active = 0
            exports = list(self._export_q)
            self._export_q.clear()
        for _fut, out in exports:  # never leave an exporter hung
            self._fail_export(out, SnapshotUnavailable(
                "GenerationServer closed before the export was serviced"))
        for s in stragglers:   # loop thread is joined: safe to touch
            self._release_slot_pages(s)
        for req in victims:
            self._fail(req, RuntimeError("GenerationServer closed with "
                                         "the request still in flight"))

    # ------------------------------------------------------------- stats
    def stats(self) -> dict:
        """Serving counters: the observable surface for /stats, the
        bench, and ops. Counters come off the registry, so ``_cond`` is
        held only for the structural reads (occupancy and queue depth);
        the legacy key set and order are preserved byte-for-byte. The
        ``pages`` block carries the paged-KV gauges (pool occupancy,
        sharing, COW, speculative accept rate)."""
        with self._cond:
            n_active = self._n_active
            queued = len(self._queue)
        busy_s = self._m_busy_s.value
        tokens = int(self._m_tokens.value)
        out = {
            "slots": self.slots,
            "active_slots": n_active,
            "queued": queued,
            "admitted": int(self._m_admitted.value),
            "expired": int(self._m_expired.value),
            "retired": int(self._m_retired.value),
            "completed": int(self._m_completed.value),
            "failed": int(self._m_failed.value),
            "retried": int(self._m_retried.value),
            "pool_rebuilds": int(self._m_pool_rebuilds.value),
            "prefills": int(self._m_prefills.value),
            "decode_steps": int(self._m_decode_steps.value),
            "tokens_generated": tokens,
            "tokens_per_s": (tokens / busy_s if busy_s > 0 else 0.0),
        }
        out.update(accepted=self.admission.accepted,
                   rejected=self.admission.rejected,
                   pending=self.admission.pending,
                   breaker_state=self.breaker.state)
        # page/spec gauges are loop-thread-owned (read unlocked, like
        # _slot_req): a racy snapshot, never a torn structure
        classes = self._classes
        pool = self._page_pool
        proposed = int(self._m_spec_proposed.value)
        accepted = int(self._m_spec_accepted.value)
        out["pages"] = {
            "page_size": self._ps,
            "pages_total": sum(c.pages_total for c in classes),
            "pages_free": sum(len(c.pool.free) for c in classes),
            "pages_cached": len(pool.cache),
            "pages_shared": pool.shared_count(),
            "pages_refcounted": sum(c.pool.refcounted() for c in classes),
            "resident_kv_bytes": self._resident_bytes(),
            "peak_resident_kv_bytes": self._resident_bytes(peak=True),
            "cow_copies": int(self._m_cow_copies[0].value),
            "prefix_hits": int(self._m_prefix_hits.value),
            "prefix_tokens_reused": int(self._m_prefix_reused[0].value),
            "evictions": int(pool.evictions),
            "preempted": int(self._m_preempted.value),
            "spec_k": self.spec_k if self._draft is not None else 0,
            "spec_rounds": int(self._m_spec_rounds.value),
            "spec_proposed": proposed,
            "spec_accepted": accepted,
            "spec_accept_rate": (accepted / proposed) if proposed else 0.0,
            "kv_cache_dtype": self.kv_dtype or str(
                np.dtype(self.net.conf.dtype)),
            "bytes_per_token": self._page_token_bytes,
            "paged_attention": self._pa,
            # behind the legacy keys, whose order clients see: what the
            # layers declared (plane name -> layers that page one)
            "prompt_tokens_admitted": int(self._m_prompt_tokens[0].value),
            "planes": dict(self._plane_layers),
        }
        if self._windowed:
            # behind every key a one-class net reports
            out["pages"]["classes"] = {c.name: {
                "window": c.window, "layers": len(c.layers),
                "bytes_per_token": c.token_bytes,
                "pages_total": c.pages_total,
                "pages_in_use": c.pool.in_use(),
                "peak_pages_in_use": c.pool.peak,
                # one window class at most: the counter is its own
                "pages_released": 0 if c.window is None else int(
                    self._m_class["released"][0].value)} for c in classes}
        out["handoff"] = {
            "snapshot_every": self.snapshot_every,
            "snapshots": int(self._m_handoff_snapshots.value),
            "bytes": int(self._m_handoff_bytes.value),
            "resumes": int(self._m_handoff_resumes.value),
            "tokens_saved": int(self._m_handoff_saved.value),
            "fallbacks": int(self._m_handoff_fallbacks.value),
            "preempt_resumes": int(self._m_preempt_resumes.value),
            "migrated": int(self._m_migrated.value),
            "prefill_exports": int(self._m_prefill_exports.value),
        }
        out["role"] = self.role
        # the admission ledger must agree with the bytes XLA actually
        # allocated for the pool — satellite guard for the itemsize fix
        assert self._page_bytes_actual == self._page_bytes, (
            f"page accounting diverged: predicted {self._page_bytes} "
            f"bytes/page, allocated {self._page_bytes_actual}")
        return out
