"""Retrieval-augmented serving tests (parallel/rag.py + the /rag route).

The contracts under test:

* ``assemble_passage_prefix`` is canonical — retrieval order, duplicate
  hits and IVF pad slots never change the assembled byte stream, and
  every passage lands chunk-aligned so page digests collide exactly
  when content does;
* the two-tier ``RagPipeline`` is BIT-exact vs the single-server
  non-RAG reference given the same assembled prompt (greedy AND
  sampled — the retrieval tier must add zero numerical surface);
* hot documents dedupe prefill through the prefix cache
  (``prefix_hits``/``prefix_tokens_reused`` climb, the document-cache
  headline) and the rag ledger balances with zero lost futures;
* query churn and occupancy churn add ZERO compiled programs on either
  tier after warmup (knn program cache + generation output cache);
* one deadline crosses the tier boundary: an exhausted budget fails
  typed ``DeadlineExceeded``, never a hang, and the pipeline serves on;
* caller errors raise typed ValueError synchronously; admission sheds
  ``ServerOverloaded``; close is idempotent and drains clean;
* the /rag HTTP route returns tokens + retrieval metadata and the
  one-scrape /metrics carries both tiers' registries under tier labels.

The fleet-building drills are ALSO marked slow (tier-1 runs within ~2%
of its own timeout cap — run them with ``-m rag``); the pure-function
assembly/validation tests stay in tier-1.
"""

import json
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu.models.zoo import greedy_generate, sample_generate
from deeplearning4j_tpu.nearestneighbors.index import EmbeddingIndex
from deeplearning4j_tpu.parallel.generation import (GenerationServer,
                                                    assemble_passage_prefix)
from deeplearning4j_tpu.parallel.rag import RagPipeline
from deeplearning4j_tpu.parallel.resilience import (DeadlineExceeded,
                                                    ServerOverloaded)
from tests.serving_helpers import V, tiny_lm

pytestmark = pytest.mark.rag

D = 8
NDOCS = 64
PS = 4  # page size on BOTH tiers — the chunk-alignment contract


def _corpus(seed=0):
    """Well-separated doc vectors + variable-length passages (3..10
    tokens, so chunk padding actually pads)."""
    rs = np.random.RandomState(seed)
    vecs = rs.randn(NDOCS, D).astype(np.float32) * 4.0
    passages = [rs.randint(1, V, size=rs.randint(3, 11)).astype(np.int64)
                for _ in range(NDOCS)]
    return vecs, passages


@pytest.fixture(scope="module")
def lm():
    return tiny_lm(max_length=64)


@pytest.fixture(scope="module")
def corpus():
    return _corpus()


@pytest.fixture(scope="module")
def rag(lm, corpus):
    """ONE shared two-tier pipeline (exact f32 knn tier — no training;
    paged generate tier) for the whole module: the fleet build and the
    prefill/decode compiles are paid once."""
    vecs, passages = corpus
    indexes = []

    def knn_factory(rid):
        idx = EmbeddingIndex(vecs)
        indexes.append(idx)
        return idx

    pipe = RagPipeline(
        knn_factory,
        lambda rid: GenerationServer(lm, V, slots=4, page_size=PS),
        passages, page_size=PS, k=2)
    pipe._test_indexes = indexes  # reach the knn replicas' program cache
    yield pipe
    pipe.close()


# ---------------------------------------------------------------------------
# canonical prefix assembly — pure function, tier-1
# ---------------------------------------------------------------------------

class TestAssemblePassagePrefix:
    def test_canonical_under_order_dups_and_padding(self):
        _vecs, passages = _corpus(1)
        q = np.array([1, 2, 3], np.int64)
        base, order, plen = assemble_passage_prefix(
            [7, 3, 11], passages, page_size=PS, query_ids=q)
        # retrieval-score order, duplicate hits, IVF -1 pad slots: the
        # assembled stream must not move a byte
        for ids in ([11, 7, 3], [3, 3, 7, 11, 11], [7, -1, 3, -1, 11]):
            prompt, o, n = assemble_passage_prefix(
                ids, passages, page_size=PS, query_ids=q)
            np.testing.assert_array_equal(prompt, base)
            assert o == order == [3, 7, 11] and n == plen
        # chunk alignment: every passage starts on a page boundary and
        # is padded to a page multiple; the query rides unpadded
        off = 0
        for d in order:
            p = passages[d]
            np.testing.assert_array_equal(base[off:off + p.size], p)
            off += p.size + (-p.size % PS)
        assert off == plen and plen % PS == 0
        np.testing.assert_array_equal(base[plen:], q)

    def test_empty_retrieval_and_validation(self):
        _vecs, passages = _corpus(1)
        q = np.array([4, 5], np.int64)
        prompt, order, plen = assemble_passage_prefix(
            [-1, -1], passages, page_size=PS, query_ids=q)
        np.testing.assert_array_equal(prompt, q)
        assert order == [] and plen == 0
        with pytest.raises(ValueError, match="page_size"):
            assemble_passage_prefix([0], passages, page_size=0)

    def test_pipeline_ctor_validation_precedes_fleet(self):
        def boom(_rid):
            raise AssertionError("factory ran before validation")

        for kw in ({"k": 0}, {"page_size": 0}, {"knn_replicas": 0},
                   {"generate_replicas": 0}):
            with pytest.raises(ValueError):
                RagPipeline(boom, boom, [], **kw)


# ---------------------------------------------------------------------------
# two-tier pipeline — fleet-building drills (slow; run with -m rag)
# ---------------------------------------------------------------------------

@pytest.mark.slow
class TestRagPipeline:
    def test_greedy_bit_exact_vs_non_rag_reference(self, rag, lm, corpus):
        """The bit-exactness contract: given the same assembled prompt,
        the two-tier flow returns exactly what the single-server
        non-RAG path generates."""
        vecs, passages = corpus
        rs = np.random.RandomState(7)
        prompt = rs.randint(1, V, 5)
        fut = rag.submit(prompt, 6, query_vec=vecs[5] + 0.01)
        out = fut.result(timeout=120)
        assert 5 in fut._rag_docs
        assert fut._rag_docs == sorted(set(fut._rag_docs))
        assert fut._rag_prefix_len % PS == 0
        # the riding prompt is the canonical assembly of the docs
        ref_prompt, _o, plen = assemble_passage_prefix(
            fut._rag_docs, passages, page_size=PS, query_ids=prompt)
        np.testing.assert_array_equal(fut._rag_prompt, ref_prompt)
        assert plen == fut._rag_prefix_len
        ref = greedy_generate(lm, fut._rag_prompt[None], 6, V)[0]
        np.testing.assert_array_equal(out, ref)

    def test_sampled_bit_exact_vs_non_rag_reference(self, rag, lm, corpus):
        vecs, _passages = corpus
        rs = np.random.RandomState(8)
        prompt = rs.randint(1, V, 4)
        fut = rag.submit(prompt, 5, query_vec=vecs[9] - 0.01,
                         temperature=0.8, top_k=5, seed=11)
        out = fut.result(timeout=120)
        ref = sample_generate(lm, fut._rag_prompt[None], 5, V,
                              temperature=0.8, top_k=5, seed=11)[0]
        np.testing.assert_array_equal(out, ref)

    def test_hot_documents_dedupe_prefill(self, rag, corpus):
        """Concurrent requests retrieving the SAME documents share
        prefix pages: the document-cache counters climb and the rag
        ledger balances with zero lost futures."""
        vecs, _passages = corpus
        rs = np.random.RandomState(9)
        before = rag.stats()
        futs = [rag.submit(rs.randint(1, V, 5), 4, query_vec=vecs[21])
                for _ in range(6)]
        outs = [f.result(timeout=120) for f in futs]
        docs = futs[0]._rag_docs
        assert all(f._rag_docs == docs for f in futs)
        for o in outs:
            assert o.shape == (4,)
        st = rag.stats()
        assert st["prefix_hits"] > before["prefix_hits"]
        assert st["prefix_tokens_reused"] > before["prefix_tokens_reused"]
        assert st["inflight"] == 0
        assert st["submitted"] == (st["completed"] + st["failed"]
                                   + st["expired"] + st["rejected"])

    def test_zero_retrace_under_query_and_occupancy_churn(
            self, rag, lm, corpus):
        """After warming each document set once, query churn (different
        retrieved docs), occupancy churn (concurrent mixed admits) and
        sampling-parameter churn add ZERO compiled programs on EITHER
        tier — knn program cache and generation output cache both."""
        vecs, _passages = corpus
        rs = np.random.RandomState(10)
        hot = [31, 32, 33, 34]
        for d in hot:  # warm every bucket these doc sets produce
            rag.submit(rs.randint(1, V, 5), 3,
                       query_vec=vecs[d]).result(timeout=120)
        # one repeat so the prefix-share/COW page-copy path is compiled
        rag.submit(rs.randint(1, V, 5), 3,
                   query_vec=vecs[hot[0]]).result(timeout=120)
        knn_warm = sum(i.stats()["programs"] for i in rag._test_indexes)
        gen_warm = len(lm._output_cache)
        futs = [rag.submit(rs.randint(1, V, 5), 3,
                           query_vec=vecs[hot[i % len(hot)]] + 0.01,
                           temperature=0.5 * (i % 2), top_k=4 * (i % 2),
                           seed=i)
                for i in range(8)]
        for f in futs:
            assert f.result(timeout=120).shape == (3,)
        assert sum(i.stats()["programs"]
                   for i in rag._test_indexes) == knn_warm
        assert len(lm._output_cache) == gen_warm

    def test_deadline_propagates_across_tiers_typed(self, rag, corpus):
        """One budget armed at submit covers BOTH tiers: a 1 ms budget
        dies inside the pipeline (knn coalescing window alone is 2 ms)
        and fails typed DeadlineExceeded — then the pipeline serves the
        next request untouched."""
        vecs, _passages = corpus
        before = rag.stats()["expired"]
        prompt = np.array([1, 2, 3, 4, 5], np.int64)
        f = rag.submit(prompt, 3, query_vec=vecs[40], deadline_s=0.001)
        with pytest.raises(DeadlineExceeded):
            f.result(timeout=120)
        assert rag.stats()["expired"] == before + 1
        ok = rag.submit(prompt, 3, query_vec=vecs[40])
        assert ok.result(timeout=120).shape == (3,)

    def test_caller_errors_typed_synchronously(self, rag, corpus):
        vecs, _passages = corpus
        good = np.array([1, 2, 3], np.int64)
        with pytest.raises(ValueError, match="non-empty"):
            rag.submit([], 3, query_vec=vecs[0])
        with pytest.raises(ValueError, match="max_tokens"):
            rag.submit(good, 0, query_vec=vecs[0])
        with pytest.raises(ValueError, match="k must be"):
            rag.submit(good, 3, query_vec=vecs[0], k=0)
        with pytest.raises(ValueError, match="deadline_s"):
            rag.submit(good, 3, query_vec=vecs[0], deadline_s=0.0)
        with pytest.raises(ValueError, match="encoder"):
            rag.submit(good, 3)  # no query_vec and no encoder attached

    def test_admission_watermark_sheds_typed(self, rag, corpus):
        """At the watermark the submit itself raises ServerOverloaded
        BEFORE entering the ledger — nothing to lose, nothing leaks."""
        vecs, _passages = corpus
        before = rag.stats()
        free = rag.admission.max_pending - rag.admission.pending
        for _ in range(free):
            rag.admission.acquire()
        try:
            with pytest.raises(ServerOverloaded):
                rag.submit(np.array([1, 2], np.int64), 3,
                           query_vec=vecs[0])
        finally:
            for _ in range(free):
                rag.admission.release()
        st = rag.stats()
        assert st["submitted"] == before["submitted"]
        assert st["rejected"] == before["rejected"]
        f = rag.submit(np.array([1, 2], np.int64), 3, query_vec=vecs[0])
        assert f.result(timeout=120).shape == (3,)

    def test_metrics_sources_carry_tier_labels(self, rag):
        labels = [lbl for lbl, _reg in rag.metrics_sources()]
        assert labels == [{}, {}, {"tier": "knn"}, {"tier": "generate"}]

    def test_tier_stats_and_slot_lever(self, rag):
        """Both tiers expose the autoscaler observation surface and the
        capacity lever through the pipeline."""
        for role in ("knn", "generate"):
            st = rag.tier_stats(role)
            assert st["replicas"] == 1 and st["slots"] > 0
        cap = rag.tier_stats("generate")["slots"]
        assert rag.set_tier_active_slots("generate", 1) == 1
        try:
            assert rag.tier_stats("generate")["active_slots"] <= 1
        finally:
            rag.set_tier_active_slots("generate", cap)

    def test_close_idempotent_and_submit_after_close(self, lm, corpus):
        vecs, passages = corpus
        pipe = RagPipeline(
            lambda rid: EmbeddingIndex(vecs),
            lambda rid: GenerationServer(lm, V, slots=2, page_size=PS),
            passages, page_size=PS, k=2)
        f = pipe.submit(np.array([1, 2, 3], np.int64), 3,
                        query_vec=vecs[3])
        pipe.close()
        pipe.close()  # idempotent
        assert f.done()  # drained, not abandoned
        with pytest.raises(RuntimeError, match="closed"):
            pipe.submit(np.array([1], np.int64), 2, query_vec=vecs[0])


# ---------------------------------------------------------------------------
# /rag HTTP route (slow; run with -m rag)
# ---------------------------------------------------------------------------

@pytest.mark.slow
class TestRagRoute:
    def test_rag_route_end_to_end(self, lm, corpus):
        from deeplearning4j_tpu.modelimport.server import KerasBackendServer

        vecs, passages = corpus
        srv = KerasBackendServer()
        mid = srv.attach_rag(lm, vocab=V, passages=passages,
                             doc_vectors=vecs, k=2, slots=2,
                             page_size=PS, mid="rag0")
        port = srv.start()
        base = f"http://127.0.0.1:{port}"
        try:
            body = json.dumps({
                "model": mid, "prompt_ids": [1, 2, 3], "max_tokens": 4,
                "query_vec": [float(x) for x in vecs[12]],
            }).encode()
            req = urllib.request.Request(
                base + "/rag", body,
                {"Content-Type": "application/json"})
            out = json.loads(urllib.request.urlopen(req).read())
            assert len(out["tokens"]) == 4
            assert 12 in out["docs"]
            assert out["prefix_len"] % PS == 0

            text = urllib.request.urlopen(base + "/metrics").read().decode()
            # one exposition pass: the rag ledger, the knn tier and the
            # generate tier all present, tier-labeled
            assert 'rag_completed_total{model="rag0"} 1' in text
            assert 'rag_ttft_ms_count{model="rag0"} 1' in text
            assert 'rag_e2e_ms_count{model="rag0"} 1' in text
            assert f'knn_points{{model="rag0",tier="knn"}} {NDOCS}' in text
            assert 'knn_recall{model="rag0",tier="knn"}' in text
            assert 'generation_slots{model="rag0",tier="generate"} 2' \
                in text

            stats = json.loads(
                urllib.request.urlopen(base + "/stats").read())
            assert stats["rag"][mid]["completed"] == 1
        finally:
            srv.stop()
