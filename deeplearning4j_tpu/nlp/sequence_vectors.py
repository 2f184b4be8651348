"""SequenceVectors: the generic embedding trainer engine.

Reference: models/sequencevectors/SequenceVectors.java:187-216 (fit: build
vocab -> reset weights -> spawn VectorCalculationsThreads), :336-356
(trainSequence dispatch to elements/sequence learning algorithms).

TPU-native redesign: instead of worker threads racing on shared syn0/syn1
(the reference's Hogwild-style update), sentences are tokenized on host,
minibatches of (center, context) pairs are assembled by ``BatchBuilder``, and
each batch is ONE jitted scatter step (nlp/learning.py). Linear LR decay
matches the reference (alpha * (1 - progress), floored at min_learning_rate).

Word relationship queries (similarity, words_nearest) ride on the normalised
syn0 matrix — one [V, D] @ [D] matmul on device.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nlp.learning import (
    DUP_CAP,
    BatchBuilder,
    cbow_corpus_epoch,
    skipgram_corpus_epoch,
)
from deeplearning4j_tpu.nlp.tokenization import DefaultTokenizerFactory
from deeplearning4j_tpu.nlp.vocab import AbstractCache, VocabConstructor


class SequenceVectors:
    """Configurable embedding trainer (reference builder fields map to
    keyword arguments of the same meaning)."""

    def __init__(self, layer_size: int = 100, window: int = 5,
                 min_word_frequency: int = 1, epochs: int = 1,
                 iterations: int = 1, learning_rate: float = 0.025,
                 min_learning_rate: float = 1e-4, negative: int = 0,
                 use_hierarchic_softmax: bool = True, sampling: float = 0.0,
                 batch_size: int = 512, seed: int = 12345,
                 elements_algorithm: str = "skipgram",
                 tokenizer_factory=None, backend: str = "auto"):
        if backend not in ("auto", "device", "native"):
            raise ValueError(f"Unknown backend '{backend}'")
        self.layer_size = layer_size
        self.window = window
        self.min_word_frequency = min_word_frequency
        self.epochs = epochs
        self.iterations = iterations
        self.learning_rate = learning_rate
        self.min_learning_rate = min_learning_rate
        self.negative = negative
        self.use_hs = use_hierarchic_softmax
        if not use_hierarchic_softmax and negative <= 0:
            raise ValueError("Need hierarchical softmax and/or negative>0")
        self.sampling = sampling
        self.batch_size = batch_size
        self.seed = seed
        self.elements_algorithm = elements_algorithm.lower()
        self.tokenizer_factory = tokenizer_factory or \
            DefaultTokenizerFactory()
        self.backend = backend
        self.vocab: Optional[AbstractCache] = None
        self.syn0 = None
        self.syn1 = None
        self.syn1neg = None
        self._builder: Optional[BatchBuilder] = None

    # ------------------------------------------------------------------ vocab
    def build_vocab(self, sentences) -> None:
        self.vocab = VocabConstructor(
            min_word_frequency=self.min_word_frequency,
            tokenizer_factory=self.tokenizer_factory,
            build_huffman=True).build_vocab(sentences)

    def reset_weights(self) -> None:
        """syn0 ~ U(-0.5/D, 0.5/D), syn1/syn1neg zeros (reference:
        InMemoryLookupTable.resetWeights).

        Tables start HOST-side when the native backend will train (it
        would otherwise pay a device round-trip of the full tables);
        jnp consumers (queries, the device path,
        shard_embedding_tables) convert on demand."""
        V, D = self.vocab.num_words(), self.layer_size
        rng = np.random.RandomState(self.seed)
        syn0 = ((rng.random_sample((V, D)) - 0.5) / D).astype(np.float32)
        if self._native_eligible_config():
            self.syn0 = syn0
            self.syn1 = np.zeros((V, D), np.float32)
            self.syn1neg = np.zeros((V, D), np.float32)
        else:
            self.syn0 = jnp.asarray(syn0)
            self.syn1 = jnp.zeros((V, D), jnp.float32)
            self.syn1neg = jnp.zeros((V, D), jnp.float32)
        self._builder = BatchBuilder(
            self.vocab, window=self.window, negative=self.negative,
            use_hs=self.use_hs, sampling=self.sampling, seed=self.seed)

    # -------------------------------------------------------------------- fit
    def fit(self, sentences) -> "SequenceVectors":
        """Build vocab (if absent) and train (reference: fit :187-216).

        Pairs from MANY sentences accumulate into one fixed-size device batch
        before each jitted step — the dispatch-granularity change that makes
        this fast on TPU (the reference instead runs many threads of tiny
        native ops; here one scatter step carries ~batch_size pairs, so the
        host->device round-trip amortises and XLA sees constant shapes)."""
        if self.vocab is None:
            self.build_vocab(sentences)
        if self.syn0 is None:
            self.reset_weights()
        if self.elements_algorithm not in ("skipgram", "cbow"):
            raise ValueError("Unknown elements algorithm "
                             f"'{self.elements_algorithm}'")
        if self._use_native_backend():
            return self._fit_native(sentences)
        return self._fit_element_epochs(sentences)

    def _use_native_backend(self) -> bool:
        """Route eligible configs to the native C hot loop — the
        reference's own architecture (SkipGram.java's hot op is a native
        libnd4j kernel, not JVM code): plain negative-sampling skip-gram
        is a scatter-bound workload a CPU inner loop beats the device
        scatter path at (a record from before the chip; no cell
        measures this); CBOW has its own native kernel. The device path keeps hierarchic softmax, subsampling,
        and SHARDED embedding tables (nlp/distributed.py EP training),
        which the host loops cannot see."""
        from deeplearning4j_tpu.native import skipgram_native_available

        if self.backend == "device":
            return False
        sh = getattr(self.syn0, "sharding", None)
        unsharded = sh is None or len(sh.device_set) <= 1
        eligible = self._native_eligible_config() and unsharded
        if self.backend == "native":
            if not eligible:
                raise ValueError(
                    "backend='native' requires a config the native "
                    "kernels support — negative-sampling skip-gram/CBOW "
                    "(Word2Vec) or DBOW / DM without train_words "
                    "(ParagraphVectors) on unsharded tables; no HS, no "
                    "subsampling — and the C toolchain")
            return True
        return eligible

    def _native_common_eligible(self) -> bool:
        """Conditions shared by every native kernel (subclass eligibility
        composes with this — one place for the rule set). layer_size is
        part of it: the C accumulator is a fixed NATIVE_MAX_LAYER buffer
        and a runtime rejection would otherwise silently fall back AFTER
        consuming a possibly non-restartable sentence stream."""
        from deeplearning4j_tpu.native import (NATIVE_MAX_LAYER,
                                               skipgram_native_available)

        return (self.backend != "device"
                and not self.use_hs and self.negative > 0
                and self.sampling == 0.0
                and self.layer_size <= NATIVE_MAX_LAYER
                and skipgram_native_available())

    def _native_eligible_config(self) -> bool:
        """Config-level (pre-array) native-backend eligibility. The
        per-kernel availability probes guard against a stale .so missing
        the newer symbols — a runtime rejection would otherwise fall back
        AFTER consuming a possibly non-restartable sentence stream."""
        from deeplearning4j_tpu.native import (NATIVE_MAX_WINDOW,
                                               cbow_native_available)

        if not (self._native_common_eligible() and self.window >= 1):
            return False
        if self.elements_algorithm == "skipgram":
            return True
        return (self.elements_algorithm == "cbow"
                and self.window <= NATIVE_MAX_WINDOW
                and cbow_native_available())

    def _native_tables(self):
        """(syn0, syn1neg, unigram^0.75 table) as host arrays for the C
        kernels. Host tables train in place; a device-resident table is
        pulled once (and stays host-side after — queries convert on
        demand). One implementation for every native consumer."""
        counts = self.vocab.counts_array()
        p = counts ** 0.75
        p /= p.sum()
        table = np.repeat(np.arange(len(p), dtype=np.int32),
                          np.maximum(1, (p * 1_000_000).astype(np.int64)))
        syn0 = np.ascontiguousarray(np.asarray(self.syn0), np.float32)
        syn1neg = np.ascontiguousarray(np.asarray(self.syn1neg),
                                       np.float32)
        return syn0, syn1neg, table

    def _fit_native(self, sentences) -> "SequenceVectors":
        """Train via native/skipgram.c in place of the jitted epoch
        (skip-gram or CBOW — the AggregateSkipGram / CBOW.java loops)."""
        from deeplearning4j_tpu.native import cbow_train, skipgram_train

        if hasattr(sentences, "reset"):
            sentences.reset()
        cache = self.vocab
        corpus = []
        for sentence in sentences:
            tokens = self.tokenizer_factory.create(sentence).tokens() \
                if isinstance(sentence, str) else list(sentence)
            any_tok = False
            for tok in tokens:
                i = cache.index_of(tok)
                if i >= 0:
                    corpus.append(i)
                    any_tok = True
            if any_tok:
                corpus.append(-1)
        if not corpus:
            return self
        syn0, syn1neg, table = self._native_tables()
        kernel = (skipgram_train if self.elements_algorithm == "skipgram"
                  else cbow_train)
        out = kernel(
            syn0, syn1neg, np.asarray(corpus, np.int32), table,
            window=self.window, negative=self.negative,
            alpha=self.learning_rate, min_alpha=self.min_learning_rate,
            epochs=self.epochs * self.iterations, seed=self.seed or 1)
        if out is None:  # toolchain raced away: device fallback
            # ``sentences`` may be a one-shot generator the corpus walk
            # above already consumed — re-iterating it would train on
            # NOTHING. Rebuild token sentences from the materialized
            # index corpus instead (vocab words only, which is exactly
            # the token stream the device path trains on anyway).
            rebuilt, cur = [], []
            for i in corpus:
                if i < 0:
                    rebuilt.append(cur)
                    cur = []
                else:
                    cur.append(cache.word_at_index(i))
            if cur:
                rebuilt.append(cur)
            return self._fit_element_epochs(rebuilt)
        _, self.syn0, self.syn1neg = out
        return self

    def _fit_element_epochs(self, sentences) -> "SequenceVectors":
        """Device-resident skipgram/CBOW training, transfer-minimal: the host
        uploads only the TOKEN STREAM (4 bytes/token, -1 sentence
        separators); pair generation, negative sampling, huffman-path
        gathers, and the whole batched update scan run inside ONE jitted
        program per corpus block (``skipgram_corpus_epoch``). Rationale:
        staging pre-built pair batches costs ~25 bytes/pair over the
        host->device link and was the measured round-3 bottleneck.

        Blocks of ~BLOCK_TOKENS bound device/host memory; token streams are
        padded to power-of-two buckets so compile count stays logarithmic.
        LR decays linearly over the whole run to min_learning_rate
        (reference: words-seen decay)."""
        b = self._builder
        if hasattr(sentences, "reset"):
            sentences.reset()
        BLOCK_TOKENS = 1 << 21
        blocks, cur, cur_tokens, total_tokens = [], [], 0, 0
        for sentence in sentences:
            tokens = self.tokenizer_factory.create(sentence).tokens() \
                if isinstance(sentence, str) else list(sentence)
            idx = b.lookup_indices(tokens)
            if idx.size == 0:
                continue
            cur.append(idx)
            cur_tokens += idx.size
            total_tokens += idx.size
            if cur_tokens >= BLOCK_TOKENS:
                blocks.append(cur)
                cur, cur_tokens = [], 0
        if cur:
            blocks.append(cur)
        if not blocks:
            return self
        B, W, K = self.batch_size, self.window, self.negative
        L = b.max_code_len
        # device-resident lookup tables, uploaded once per fit
        if self.use_hs:
            points_tab = jnp.asarray(b.points)
            codes_tab = jnp.asarray(b.codes)
            cmask_tab = jnp.asarray(b.code_mask)
        else:
            points_tab = jnp.zeros((1, 1), jnp.int32)
            codes_tab = jnp.zeros((1, 1), jnp.float32)
            cmask_tab = jnp.zeros((1, 1), jnp.float32)
        neg_table = (jnp.asarray(b._neg_table) if K > 0
                     else jnp.zeros((1,), jnp.int32))
        total_units = max(total_tokens * self.epochs * self.iterations, 1)
        done = 0
        for e in range(self.epochs):
            for block in blocks:
                for it in range(self.iterations):
                    # fresh subsampling draw per pass (reference resamples
                    # every epoch/iteration); dynamic windows are drawn on
                    # device from the per-call rng key
                    sent_idx = [b.subsample(sid) for sid in block] \
                        if self.sampling > 0 else block
                    mode = ("pairs" if self.elements_algorithm == "skipgram"
                            else "positions")
                    stream = self._token_stream(sent_idx, B, W, mode=mode)
                    if stream is None:
                        continue
                    raw = sum(sid.size for sid in block)
                    lr0 = self._alpha(min(done / total_units, 1.0))
                    lr1 = self._alpha(min((done + raw) / total_units, 1.0))
                    key = jax.random.fold_in(
                        jax.random.PRNGKey(self.seed + 1),
                        done + e * 131071 + it)
                    if self.elements_algorithm == "skipgram":
                        self.syn0, self.syn1, self.syn1neg = \
                            skipgram_corpus_epoch(
                                self.syn0, self.syn1, self.syn1neg,
                                stream, key, jnp.float32(lr0),
                                jnp.float32(lr1), jnp.float32(DUP_CAP),
                                points_tab, codes_tab, cmask_tab, neg_table,
                                window=W, batch=B, neg_k=max(K, 0),
                                use_hs=self.use_hs, use_ns=K > 0)
                    else:
                        self.syn0, self.syn1, self.syn1neg = \
                            cbow_corpus_epoch(
                                self.syn0, self.syn1, self.syn1neg,
                                stream, stream, key, jnp.float32(lr0),
                                jnp.float32(lr1), jnp.float32(DUP_CAP),
                                jnp.float32(DUP_CAP),
                                points_tab, codes_tab, cmask_tab, neg_table,
                                window=W, batch=B, neg_k=max(K, 0),
                                use_hs=self.use_hs, use_ns=K > 0,
                                with_labels=False)
                    done += raw
        return self

    # Above this size, stream shapes snap to multiples of it instead of
    # powers of two: pow2 rounding wastes up to 50% of the scan on -1
    # padding for large corpora (a 2.1M-token block would pad to 4.2M),
    # while quantum rounding caps waste at Q/size (<7%) and still bounds
    # the number of compiled shapes.
    _STREAM_QUANTUM = 1 << 17

    @classmethod
    def _bucket_size(cls, size: int, batch: int, window: int,
                     mode: str) -> int:
        """Bucketed stream length N: powers of two below _STREAM_QUANTUM
        (small corpora, tests), multiples of it above (large corpora) —
        logarithmic-then-linear shape count, bounded padding waste either
        way. mode 'pairs' (skipgram: N*2W pairs reshape to batches) needs
        N*2W % batch == 0; 'positions' (CBOW/DBOW: one unit per position)
        needs N % batch == 0."""
        def ok(n):
            return ((n * 2 * window) % batch == 0 if mode == "pairs"
                    else n % batch == 0)

        q = cls._STREAM_QUANTUM
        if size <= q:
            n = max(int(batch), 2)
            while n < size or not ok(n):
                n *= 2
        else:
            n = ((size + q - 1) // q) * q
            while not ok(n):
                n += q
        return n

    @classmethod
    def _token_stream(cls, sent_idx, batch: int, window: int,
                      mode: str = "pairs"):
        """Concatenate sentences with -1 separators, pad with -1 to the
        bucketed length (see _bucket_size)."""
        parts = []
        for sid in sent_idx:
            if sid.size:
                parts.append(sid.astype(np.int32))
                parts.append(np.full(1, -1, np.int32))
        if not parts:
            return None
        stream = np.concatenate(parts)
        n = cls._bucket_size(stream.size, batch, window, mode)
        return jnp.asarray(np.concatenate(
            [stream, np.full(n - stream.size, -1, np.int32)]))

    def _alpha(self, progress: float) -> float:
        return max(self.min_learning_rate,
                   self.learning_rate * (1.0 - progress))

    # ------------------------------------------------------------ query API
    def word_vector(self, word: str) -> Optional[np.ndarray]:
        i = self.vocab.index_of(word)
        return None if i < 0 else np.asarray(self.syn0[i])

    def has_word(self, word: str) -> bool:
        return self.vocab is not None and self.vocab.contains_word(word)

    def _norm_syn0(self) -> np.ndarray:
        # slice off any mesh-padding rows (nlp/distributed.py pads tables
        # to a multiple of the model-axis size) so zero pad rows can never
        # rank in nearest-neighbour queries
        s = np.asarray(self.syn0)[:self.vocab.num_words()]
        n = np.linalg.norm(s, axis=1, keepdims=True)
        return s / np.maximum(n, 1e-12)

    def similarity(self, a: str, b: str) -> float:
        """Cosine similarity (reference: WordVectorsImpl.similarity)."""
        ia, ib = self.vocab.index_of(a), self.vocab.index_of(b)
        if ia < 0 or ib < 0:
            return float("nan")
        s = self._norm_syn0()
        return float(np.dot(s[ia], s[ib]))

    def words_nearest(self, word_or_vec, top_n: int = 10) -> list:
        """Top-N cosine neighbours (reference: wordsNearest)."""
        if isinstance(word_or_vec, str):
            i = self.vocab.index_of(word_or_vec)
            if i < 0:
                return []
            vec = np.asarray(self.syn0[i])
            exclude = {i}
        else:
            vec = np.asarray(word_or_vec)
            exclude = set()
        s = self._norm_syn0()
        v = vec / max(np.linalg.norm(vec), 1e-12)
        sims = s @ v
        order = np.argsort(-sims)
        out = []
        for j in order:
            if int(j) in exclude:
                continue
            out.append((self.vocab.word_at_index(int(j)), float(sims[j])))
            if len(out) >= top_n:
                break
        return out

    def words_nearest_sum(self, positive: list, negative: list,
                          top_n: int = 10) -> list:
        """king - man + woman style analogy (reference: wordsNearestSum)."""
        s = self._norm_syn0()
        vec = np.zeros(self.layer_size, np.float64)
        exclude = set()
        for w in positive:
            i = self.vocab.index_of(w)
            if i >= 0:
                vec += s[i]
                exclude.add(i)
        for w in negative:
            i = self.vocab.index_of(w)
            if i >= 0:
                vec -= s[i]
                exclude.add(i)
        v = vec / max(np.linalg.norm(vec), 1e-12)
        sims = s @ v
        order = np.argsort(-sims)
        out = []
        for j in order:
            if int(j) in exclude:
                continue
            out.append((self.vocab.word_at_index(int(j)), float(sims[j])))
            if len(out) >= top_n:
                break
        return out
