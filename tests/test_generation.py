"""Continuous-batching generation serving tests (parallel/generation.py).

Covers the GenerationServer contract end to end on the CPU mesh:
correctness (greedy bit-parity with greedy_generate, sampled parity with
sample_generate under the shared fold_in key schedule), scheduling
(EOS/max-tokens slot retirement, occupancy churn with ZERO decode-step
recompiles), and the PR-4 resilience posture carried over wholesale
(deadlines queued and mid-generation, admission watermark, chaos with
retries, typed hard-fault recovery, drain/close never leaving a hung
future). Streaming-mask unit tests for the attention layer ride along —
they are the layer-level property the prefill path depends on.
"""

import time

import jax
import numpy as np
import pytest

from deeplearning4j_tpu.models.zoo import (TransformerLM, greedy_generate,
                                           lm_stream_forward,
                                           sample_generate)
from deeplearning4j_tpu.parallel.generation import GenerationServer
from deeplearning4j_tpu.parallel.resilience import (ChaosPolicy,
                                                    CircuitBreaker,
                                                    DeadlineExceeded,
                                                    ResilienceError,
                                                    RetryPolicy,
                                                    ServerOverloaded)
from tests.serving_helpers import (V, serial_refs, serving,
                                   tiny_lm)


@pytest.mark.generation
class TestGenerationCorrectness:
    def test_greedy_parity_mixed_length_concurrent(self, lm, greedy_refs):
        """Six concurrent requests of three prompt lengths through three
        slots (occupancy churns as short requests retire) decode
        BIT-identically to per-request greedy_generate."""
        reqs, refs = greedy_refs
        with serving(lm, V, slots=3) as srv:
            futs = [srv.submit(p, s) for p, s in reqs]
            outs = [f.result(timeout=120) for f in futs]
            st = srv.stats()
        for got, ref in zip(outs, refs):
            np.testing.assert_array_equal(got, ref)
        assert st["completed"] == len(reqs)
        assert st["failed"] == 0 and st["expired"] == 0
        assert st["prefills"] == len(reqs)
        assert st["tokens_generated"] == sum(s for _, s in reqs)

    def test_sampled_parity_and_determinism(self, lm):
        """Sampled requests share sample_generate's per-token key schedule
        (fold_in(PRNGKey(seed), token_index)), so the pooled batch-S path
        reproduces the serial batch-1 path exactly; same seed twice in
        DIFFERENT slots of one batch is also identical."""
        rs = np.random.RandomState(5)
        prompt = rs.randint(0, V, 4)
        ref = sample_generate(lm, prompt[None], 6, V, temperature=0.9,
                              top_k=5, seed=7)[0]
        with serving(lm, V, slots=3) as srv:
            f1 = srv.submit(prompt, 6, temperature=0.9, top_k=5, seed=7)
            f2 = srv.submit(prompt, 6, temperature=0.9, top_k=5, seed=7)
            a, b = f1.result(timeout=120), f2.result(timeout=120)
        np.testing.assert_array_equal(a, ref)
        np.testing.assert_array_equal(a, b)

    def test_mixed_sampling_params_one_batch(self, lm, greedy_refs):
        """Greedy and sampled requests coexist in one pooled batch (the
        params are traced per-slot values): the greedy row still matches
        its serial reference exactly."""
        reqs, refs = greedy_refs
        (gp, gs), gref = reqs[0], refs[0]
        rs = np.random.RandomState(8)
        sp = rs.randint(0, V, 5)
        sref = sample_generate(lm, sp[None], 4, V, temperature=1.3,
                               top_k=0, seed=11)[0]
        with serving(lm, V, slots=3) as srv:
            fg = srv.submit(gp, gs)
            fs = srv.submit(sp, 4, temperature=1.3, top_k=0, seed=11)
            np.testing.assert_array_equal(fg.result(timeout=120), gref)
            np.testing.assert_array_equal(fs.result(timeout=120), sref)

    def test_eos_retires_slot_early(self, lm, greedy_refs):
        """A per-request eos_id truncates the output at (and including)
        the EOS token and frees the slot; a sibling request without EOS
        runs to max_tokens untouched."""
        reqs, refs = greedy_refs
        (p0, s0), ref0 = reqs[0], refs[0]
        (p1, s1), ref1 = reqs[1], refs[1]
        eos = int(ref0[3])
        k = int(np.where(ref0 == eos)[0][0])        # first occurrence
        with serving(lm, V, slots=3) as srv:
            fe = srv.submit(p0, s0, eos_id=eos)
            fn = srv.submit(p1, s1)
            got = fe.result(timeout=120)
            np.testing.assert_array_equal(fn.result(timeout=120), ref1)
            st = srv.stats()
        np.testing.assert_array_equal(got, ref0[:k + 1])
        assert len(got) == k + 1 < s0               # actually truncated
        assert st["completed"] == 2

    def test_submit_validation(self, lm):
        with serving(lm, V, slots=3) as srv:
            with pytest.raises(ValueError, match="prompt_ids"):
                srv.submit(np.zeros((0,), np.int64), 4)
            with pytest.raises(ValueError, match="prompt_ids"):
                srv.submit(np.zeros((2, 3), np.int64), 4)
            with pytest.raises(ValueError, match="max_tokens"):
                srv.submit(np.array([1, 2]), 0)
            with pytest.raises(ValueError, match="temperature"):
                srv.submit(np.array([1, 2]), 4, temperature=-1.0)
            with pytest.raises(ValueError, match="top_k"):
                srv.submit(np.array([1, 2]), 4, top_k=V + 1)
            # infeasible size is a typed, shed-able overload — admission
            # rejects up front, never mid-prefill after a slot is burned
            with pytest.raises(ServerOverloaded, match="capacity"):
                srv.submit(np.array([1, 2]), 100000)

    def test_rejects_model_without_kv_carry(self):
        """GenerationServer serves explicit-KV-carry streamers; a model
        whose streaming carry is not seedable up front fails at
        construction, not mid-serve."""
        from deeplearning4j_tpu.nn.conf.builders import \
            NeuralNetConfiguration
        from deeplearning4j_tpu.nn.conf.inputs import InputType
        from deeplearning4j_tpu.nn.conf.layers import (DenseLayer,
                                                       OutputLayer)
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

        conf = (NeuralNetConfiguration.builder().seed(0)
                .weight_init("xavier").activation("relu")
                .list(DenseLayer(n_out=8),
                      OutputLayer(n_out=3, loss="mcxent",
                                  activation="softmax"))
                .set_input_type(InputType.feed_forward(4))
                .build())
        net = MultiLayerNetwork(conf).init()
        with pytest.raises(ValueError, match="KV carry"):
            GenerationServer(net, 3, slots=2)


@pytest.mark.generation
class TestGenerationScheduling:
    def test_no_recompile_on_occupancy_churn(self):
        """The whole point of page pooling: after warmup (ONE decode
        program, one prefill program per PAGE bucket, one page-copy
        program), arbitrary occupancy churn — admits, retirements, mixed
        lengths, idle slots, page sharing and COW — adds ZERO compiled
        programs. Block tables, positions and refcounts are all data."""
        net = TransformerLM(num_labels=V, max_length=16, d_model=8,
                            n_heads=2, n_blocks=1, seed=9).init()
        rs = np.random.RandomState(0)
        with serving(net, V, slots=3, min_prefill_bucket=4) as srv:
            base = len(net._output_cache)
            warm = [srv.submit(rs.randint(0, V, 3), 5),
                    srv.submit(rs.randint(0, V, 7), 2)]
            for f in warm:
                f.result(timeout=120)
            warmed = len(net._output_cache)
            # the decode step, the 1-page prefill bucket (every prompt
            # here covers one page, so they ALL share one program), and
            # the COW page-copy — nothing else
            assert warmed - base == 3

            churn = [(4, 3), (2, 7), (6, 1), (8, 4), (3, 2), (5, 6)]
            futs = []
            for plen, mt in churn:
                futs.append(srv.submit(rs.randint(0, V, plen), mt))
                time.sleep(0.02)  # stagger: arrive at varied occupancy
            for f, (_plen, mt) in zip(futs, churn):
                assert f.result(timeout=120).shape == (mt,)
            assert len(net._output_cache) == warmed
            st = srv.stats()
        assert st["completed"] == 8
        assert st["decode_steps"] > 0

    def test_deadline_expired_while_queued(self, lm, greedy_refs):
        reqs, refs = greedy_refs
        (p0, s0), ref0 = reqs[0], refs[0]
        with serving(lm, V, slots=3) as srv:
            f = srv.submit(p0, s0, deadline_s=0.0)
            with pytest.raises(DeadlineExceeded, match="queued"):
                f.result(timeout=30)
            # the server is unharmed: the next request serves normally
            np.testing.assert_array_equal(
                srv.submit(p0, s0).result(timeout=120), ref0)
            st = srv.stats()
        assert st["expired"] == 1 and st["completed"] == 1

    def test_deadline_expired_mid_generation(self, lm, greedy_refs):
        """A request whose budget runs out mid-decode fails typed AND
        frees its slot — with every dispatch slowed by injected latency
        the 200-token ask cannot finish inside 180 ms."""
        reqs, refs = greedy_refs
        (p0, s0), ref0 = reqs[0], refs[0]
        chaos = ChaosPolicy(latency_rate=1.0, latency_s=0.05)
        with serving(lm, V, slots=3, chaos=chaos) as srv:
            f = srv.submit(p0, 200, deadline_s=0.18)
            with pytest.raises(DeadlineExceeded):
                f.result(timeout=30)
            st = srv.stats()
            assert st["expired"] == 1
            assert st["active_slots"] == 0              # slot freed
            chaos.latency_rate = 0.0
            np.testing.assert_array_equal(
                srv.submit(p0, s0).result(timeout=120), ref0)

    def test_admission_watermark_sheds_load(self, lm, greedy_refs):
        reqs, refs = greedy_refs
        (p0, s0), ref0 = reqs[0], refs[0]
        chaos = ChaosPolicy(latency_rate=1.0, latency_s=0.2)
        with serving(lm, V, slots=3, max_pending=1, chaos=chaos) as srv:
            f1 = srv.submit(p0, s0)
            with pytest.raises(ServerOverloaded):
                srv.submit(p0, s0)
            np.testing.assert_array_equal(f1.result(timeout=120), ref0)
            # admission released on resolution: capacity is back
            chaos.latency_rate = 0.0
            np.testing.assert_array_equal(
                srv.submit(p0, s0).result(timeout=120), ref0)
            st = srv.stats()
        assert st["rejected"] == 1 and st["completed"] == 2


@pytest.mark.generation
class TestGenerationResilience:
    def test_chaos_transients_retry_zero_lost_futures(self, lm,
                                                      greedy_refs):
        """Under a 35% transient-fault rate every future still resolves —
        almost always to the exact greedy reference (retries), in the
        worst case to a typed ResilienceError — and never hangs."""
        reqs, refs = greedy_refs
        chaos = ChaosPolicy(seed=2, transient_rate=0.35)
        retry = RetryPolicy(max_attempts=6, base_s=0.001, cap_s=0.01,
                            seed=0, sleep=lambda _s: None)
        breaker = CircuitBreaker(failure_threshold=1.1)  # never trips
        with serving(lm, V, slots=3, retry=retry, breaker=breaker,
                     chaos=chaos) as srv:
            futs = [srv.submit(p, s) for p, s in reqs]
            ok = 0
            for f, ref in zip(futs, refs):
                try:
                    got = f.result(timeout=120)
                except ResilienceError:
                    continue  # typed, not lost — acceptable under chaos
                np.testing.assert_array_equal(got, ref)
                ok += 1
            st = srv.stats()
        assert all(f.done() for f in futs)              # zero lost
        assert ok >= 1                                  # retries do work
        assert chaos.injected_transient > 0
        assert st["retried"] > 0

    def test_hard_decode_fault_fails_typed_and_recovers(self, lm,
                                                        greedy_refs):
        """A hard (non-retryable) decode fault fails the in-flight batch
        typed, the pooled carry is rebuilt from zeros, and the next
        request decodes correctly — the server never wedges."""
        reqs, refs = greedy_refs
        (p0, s0), ref0 = reqs[0], refs[0]
        chaos = ChaosPolicy(latency_rate=1.0, latency_s=0.05)
        breaker = CircuitBreaker(failure_threshold=1.1)
        with serving(lm, V, slots=3, breaker=breaker, chaos=chaos) as srv:
            f = srv.submit(p0, 200)
            for _ in range(600):                  # wait until mid-decode
                if srv.stats()["prefills"] >= 1:
                    break
                time.sleep(0.01)
            chaos.hard_rate = 1.0                 # next dispatch dies hard
            with pytest.raises(RuntimeError, match="hard fault"):
                f.result(timeout=30)
            chaos.hard_rate = 0.0
            chaos.latency_rate = 0.0
            np.testing.assert_array_equal(
                srv.submit(p0, s0).result(timeout=120), ref0)
            st = srv.stats()
        assert st["failed"] >= 1 and st["completed"] == 1

    def test_drain_resolves_everything(self, lm, greedy_refs):
        reqs, refs = greedy_refs
        with serving(lm, V, slots=2) as srv:
            futs = [srv.submit(p, s) for p, s in reqs]
            assert srv.drain(timeout=120)
            assert all(f.done() for f in futs)
            for f, ref in zip(futs, refs):
                np.testing.assert_array_equal(f.result(timeout=1), ref)

    def test_close_fails_stragglers_typed(self, lm):
        """close() with work still in flight past its timeout resolves
        the stragglers with a typed error instead of leaving hung
        futures; submitting after close is refused."""
        rs = np.random.RandomState(12)
        chaos = ChaosPolicy(latency_rate=1.0, latency_s=0.25)
        srv = GenerationServer(lm, V, slots=3, chaos=chaos)
        f = srv.submit(rs.randint(0, V, 3), 400)
        srv.close(timeout=0.3)
        assert f.done()
        with pytest.raises(RuntimeError, match="closed"):
            f.result(timeout=1)
        with pytest.raises(RuntimeError, match="closed"):
            srv.submit(rs.randint(0, V, 3), 2)


@pytest.mark.generation
class TestStreamingMask:
    """Layer-level property the prefill bucket path depends on: a right-
    padded prompt with a [B, T] validity mask streams identically to the
    unpadded prompt, and inapplicable mask shapes fail loudly."""

    def _carry(self, lm, batch=1):
        lm.rnn_clear_previous_state()
        seed = lm._seed_streaming_carry(batch)
        lm.rnn_clear_previous_state()
        return seed

    def test_masked_right_pad_matches_unpadded(self, lm):
        rs = np.random.RandomState(13)
        plen, bucket = 5, 8
        ids = rs.randint(0, V, plen)
        eye = np.eye(V, dtype=np.float32)
        # jitted, as the server's prefill traces it: eagerly every op of
        # the forward compiles once per prompt shape
        fwd = jax.jit(lm_stream_forward(lm))

        x_pad = np.zeros((1, bucket, V), np.float32)
        x_pad[0, :plen] = eye[ids]
        mask = np.zeros((1, bucket), np.float32)
        mask[0, :plen] = 1
        out_pad, _ = fwd(lm.params, lm.state, x_pad, self._carry(lm), mask)
        out_raw, _ = fwd(lm.params, lm.state, eye[ids][None],
                         self._carry(lm), None)
        # true positions identical; the padded tail is garbage the caller
        # never reads (prefill samples from position plen-1 only)
        np.testing.assert_allclose(np.asarray(out_pad)[:, :plen],
                                   np.asarray(out_raw), atol=1e-6)

    def test_bad_mask_shape_raises(self, lm):
        rs = np.random.RandomState(14)
        x = np.eye(V, dtype=np.float32)[rs.randint(0, V, 4)][None]
        fwd = jax.jit(lm_stream_forward(lm))
        with pytest.raises(ValueError, match="streaming attention mask"):
            fwd(lm.params, lm.state, x, self._carry(lm),
                np.ones((1, 4, 1), np.float32))
        with pytest.raises(ValueError, match="streaming attention mask"):
            fwd(lm.params, lm.state, x, self._carry(lm),
                np.ones((2, 4), np.float32))  # batch mismatch


@pytest.mark.generation
class TestGenerationLockDiscipline:
    """Targeted regressions for the graftcheck generation-lock fixes:
    the closing flag is checked under self._cond in submit(), and the
    decode counters are batched into one condition acquisition per step."""

    def test_submit_close_race_never_hangs(self, lm):
        import threading

        srv = GenerationServer(lm, V, slots=2)
        futs, refused = [], []
        go = threading.Event()

        def submitter(i):
            go.wait(10)
            try:
                futs.append(srv.submit(np.array([1 + i % 5]), 3))
            except (RuntimeError, ResilienceError) as e:
                refused.append(e)  # typed refusal is a valid outcome

        ts = [threading.Thread(target=submitter, args=(i,))
              for i in range(8)]
        for t in ts:
            t.start()
        go.set()
        srv.close()
        for t in ts:
            t.join(30)
        assert len(futs) + len(refused) == 8
        for f in futs:
            try:
                f.result(timeout=30)
            except Exception:
                pass  # resolved with an error: fine — just never hung
            assert f.done()

    def test_counters_batched_per_decode_step(self, lm):
        with serving(lm, V, slots=2) as srv:
            futs = [srv.submit(np.array([1, 2, 3]), 4) for _ in range(3)]
            outs = [f.result(timeout=120) for f in futs]
            st = srv.stats()
        assert st["prefills"] == 3
        assert st["completed"] == 3
        # every generated token is counted exactly once, via ONE condition
        # acquisition per decode step (not one per token)
        assert st["tokens_generated"] == sum(len(o) for o in outs)
        assert 1 <= st["decode_steps"] <= 4 * 3


@pytest.mark.generation
class TestPagedSharing:
    """Paged-pool properties layered on the serving contract: prefix
    sharing with copy-on-write parity, page-budget admission, preemption
    under pool pressure, and refcounts draining to zero when the server
    empties."""

    def test_prefix_sharing_cow_parity(self, lm):
        """Two prompts sharing a 32-token (two-page) prefix: the second
        adopts the first's registered pages read-only and prefills only
        its suffix — outputs stay BIT-identical to the serial references
        because every divergent write copies the page off first."""
        rs = np.random.RandomState(21)
        pre = rs.randint(0, V, 32)
        p1 = np.concatenate([pre, rs.randint(0, V, 5)])
        p2 = np.concatenate([pre, rs.randint(0, V, 7)])
        r1 = greedy_generate(lm, p1[None], 4, V)[0]
        r2 = greedy_generate(lm, p2[None], 4, V)[0]
        with serving(lm, V, slots=2) as srv:
            np.testing.assert_array_equal(
                srv.submit(p1, 4).result(timeout=120), r1)
            np.testing.assert_array_equal(
                srv.submit(p2, 4).result(timeout=120), r2)
            pg = srv.stats()["pages"]
        assert pg["prefix_hits"] >= 1
        assert pg["prefix_tokens_reused"] >= 32     # both prefix pages
        assert pg["cow_copies"] >= 1                # divergence copied off

    def test_identical_prompt_tail_page_shared(self, lm):
        """A byte-identical re-submission (same seed) reuses everything
        up to the LAST prompt token — the partial tail page is shared via
        the whole-prompt digest — and still matches exactly."""
        rs = np.random.RandomState(22)
        p = rs.randint(0, V, 11)                    # sub-page prompt
        ref = greedy_generate(lm, p[None], 5, V)[0]
        with serving(lm, V, slots=2) as srv:
            np.testing.assert_array_equal(
                srv.submit(p, 5).result(timeout=120), ref)
            np.testing.assert_array_equal(
                srv.submit(p, 5).result(timeout=120), ref)
            pg = srv.stats()["pages"]
        assert pg["prefix_hits"] == 1
        assert pg["prefix_tokens_reused"] == 10     # plen - 1

    def test_refcounts_drain_when_idle(self, lm):
        """After every request resolves, no page is refcounted: the pool
        is free pages + reclaimable prefix-cache pages, nothing leaked."""
        rs = np.random.RandomState(23)
        with serving(lm, V, slots=3) as srv:
            futs = [srv.submit(rs.randint(0, V, 4 + i), 3)
                    for i in range(5)]
            for f in futs:
                f.result(timeout=120)
            assert srv.drain(timeout=60)
            pg = srv.stats()["pages"]
        assert pg["pages_refcounted"] == 0
        assert pg["pages_free"] + pg["pages_cached"] \
            == pg["pages_total"] - 1                # all but garbage page

    def test_page_budget_admission(self, lm):
        """submit() validates the whole-lifetime page need against the
        pool budget up front: an infeasible request is a typed
        ServerOverloaded before any slot or page is consumed, and a
        feasible one on the same server still serves exactly."""
        rs = np.random.RandomState(24)
        p = rs.randint(0, V, 3)
        ref = greedy_generate(lm, p[None], 4, V)[0]
        with serving(lm, V, slots=2, pages=4) as srv:  # 3 usable pages
            with pytest.raises(ServerOverloaded, match="page"):
                srv.submit(p, 60)                   # needs 4 pages
            np.testing.assert_array_equal(
                srv.submit(p, 4).result(timeout=120), ref)
            st = srv.stats()
        assert st["completed"] == 1 and st["failed"] == 0

    def test_preemption_under_pool_pressure(self, lm):
        """Two long requests whose combined page need exceeds the pool:
        the newest slot is preempted (pages freed, request requeued at
        the FRONT) — and because decode is deterministic under the
        fold_in key schedule, BOTH still complete bit-exactly."""
        rs = np.random.RandomState(25)
        pa = rs.randint(0, V, 40)                   # 3 pages of prompt
        pb = rs.randint(0, V, 40)
        ra = greedy_generate(lm, pa[None], 30, V)[0]
        rb = greedy_generate(lm, pb[None], 30, V)[0]
        # each request needs 5 pages end to end; 9 usable < 10 combined
        with serving(lm, V, slots=2, pages=10, prefix_cache=False) as srv:
            fa = srv.submit(pa, 30)
            fb = srv.submit(pb, 30)
            np.testing.assert_array_equal(fa.result(timeout=180), ra)
            np.testing.assert_array_equal(fb.result(timeout=180), rb)
            st = srv.stats()
        assert st["pages"]["preempted"] >= 1
        assert st["completed"] == 2 and st["failed"] == 0

    def test_lru_eviction_reclaims_cached_pages(self, lm):
        """Prefix-cache pages are reclaimable, not leaked: when the free
        list runs dry the oldest unreferenced cached page is evicted to
        serve new allocations, and serving continues exactly."""
        rs = np.random.RandomState(26)
        prompts = [rs.randint(0, V, 16) for _ in range(6)]
        refs = [greedy_generate(lm, p[None], 3, V)[0] for p in prompts]
        # 6 distinct one-page prompts through a 4-usable-page pool: the
        # prefix cache must evict to keep admitting
        with serving(lm, V, slots=1, pages=5) as srv:
            for p, ref in zip(prompts, refs):
                np.testing.assert_array_equal(
                    srv.submit(p, 3).result(timeout=120), ref)
            pg = srv.stats()["pages"]
        assert pg["evictions"] >= 1
        assert pg["pages_refcounted"] == 0


@pytest.mark.generation
class TestSpeculative:
    """Speculative decoding: the draft proposes, the target verifies all
    K positions in one chunked dispatch, and every emitted token is the
    TARGET's selection under the serial fold_in schedule — so outputs are
    bit-exact regardless of draft quality."""

    def test_perfect_draft_all_accept(self, lm, greedy_refs):
        """Draft == target: every proposal verifies, the accept rate is
        ~1, and all completions match the serial references exactly."""
        reqs, refs = greedy_refs
        with serving(lm, V, slots=3, draft_net=lm, spec_k=3) as srv:
            futs = [srv.submit(p, s) for p, s in reqs]
            outs = [f.result(timeout=180) for f in futs]
            pg = srv.stats()["pages"]
        for got, ref in zip(outs, refs):
            np.testing.assert_array_equal(got, ref)
        assert pg["spec_rounds"] > 0
        assert pg["spec_accept_rate"] > 0.9

    def test_mismatched_draft_still_bit_exact(self, lm, greedy_refs):
        """A draft with unrelated weights proposes mostly-rejected tokens:
        throughput degrades, correctness does not — greedy AND sampled
        completions still match the serial paths token-for-token."""
        reqs, refs = greedy_refs
        draft = TransformerLM(num_labels=V, max_length=16, d_model=8,
                              n_heads=2, n_blocks=1, seed=99).init()
        rs = np.random.RandomState(31)
        sp = rs.randint(0, V, 4)
        sref = sample_generate(lm, sp[None], 6, V, temperature=0.9,
                               top_k=5, seed=7)[0]
        with serving(lm, V, slots=3, draft_net=draft, spec_k=4) as srv:
            futs = [srv.submit(p, s) for p, s in reqs]
            fs = srv.submit(sp, 6, temperature=0.9, top_k=5, seed=7)
            outs = [f.result(timeout=180) for f in futs]
            sout = fs.result(timeout=180)
            pg = srv.stats()["pages"]
        for got, ref in zip(outs, refs):
            np.testing.assert_array_equal(got, ref)
        np.testing.assert_array_equal(sout, sref)
        assert pg["spec_accept_rate"] < 1.0

    def test_eos_mid_speculative_round(self, lm, greedy_refs):
        """EOS produced inside a verified chunk truncates the emission at
        (and including) the EOS token, exactly as the serial path."""
        reqs, refs = greedy_refs
        (p0, s0), ref0 = reqs[0], refs[0]
        eos = int(ref0[3])
        k = int(np.where(ref0 == eos)[0][0])
        with serving(lm, V, slots=2, draft_net=lm, spec_k=4) as srv:
            got = srv.submit(p0, s0, eos_id=eos).result(timeout=180)
        np.testing.assert_array_equal(got, ref0[:k + 1])

    def test_spec_zero_recompiles_on_churn(self):
        """Speculative serving compiles one spec round + one draft
        prefill per token bucket (on the DRAFT's cache) and one target
        prefill per page bucket + the page copy (on the target's) — then
        occupancy churn and accept/reject variation add ZERO programs."""
        net = TransformerLM(num_labels=V, max_length=16, d_model=8,
                            n_heads=2, n_blocks=1, seed=9).init()
        draft = TransformerLM(num_labels=V, max_length=16, d_model=8,
                              n_heads=2, n_blocks=1, seed=10).init()
        rs = np.random.RandomState(32)
        with serving(net, V, slots=3, draft_net=draft, spec_k=3) as srv:
            nb, db = len(net._output_cache), len(draft._output_cache)
            warm = [srv.submit(rs.randint(0, V, 3), 5),
                    srv.submit(rs.randint(0, V, 7), 2)]
            for f in warm:
                f.result(timeout=180)
            nw, dw = len(net._output_cache), len(draft._output_cache)
            assert nw - nb == 2     # page-bucket prefill + page copy
            assert dw - db == 2     # spec round + draft prefill bucket
            churn = [(4, 3), (2, 7), (6, 1), (8, 4), (3, 2), (5, 6)]
            futs = [srv.submit(rs.randint(0, V, plen), mt)
                    for plen, mt in churn]
            for f, (_plen, mt) in zip(futs, churn):
                assert f.result(timeout=180).shape == (mt,)
            assert len(net._output_cache) == nw
            assert len(draft._output_cache) == dw

    def test_draft_validation(self, lm):
        """Constructor contract: spec_k < 2 and a draft that cannot reach
        the target's positions are loud construction-time errors."""
        with pytest.raises(ValueError, match="spec_k"):
            GenerationServer(lm, V, slots=2, draft_net=lm, spec_k=1)


@pytest.fixture(scope="module")
def lm40():
    """Room for a prompt of three ``prefill_chunk=8`` rounds and its
    answer."""
    return tiny_lm(max_length=40)


@pytest.fixture(scope="module")
def round_refs(lm40):
    """Greedy and sampled requests whose prompts take one, two and three
    rounds of eight, with what the non-server path generates for each
    (computed while no server is live)."""
    rs = np.random.RandomState(29)
    specs = []
    for i, (plen, steps) in enumerate(((5, 4), (12, 5), (20, 4))):
        specs.append((rs.randint(0, V, plen), steps, 0.0, 0, 0))
        specs.append((rs.randint(0, V, plen), steps, 0.9, 5, 290 + i))
    runner = (rs.randint(0, V, 6), 12, 0.0, 0, 0)
    return [runner] + specs, serial_refs(lm40, [runner] + specs)


def _round_server(lm40, draft):
    """The server both ``TestPrefillShipsIds`` round tests drive: rounds
    of eight columns, with or without a speculative draft."""
    kw = dict(draft_net=lm40, spec_k=3) if draft else {}
    return serving(lm40, V, slots=3, page_size=8, prefill_chunk=8,
                   steps_per_dispatch=2, **kw)


def _spy_on_prefill_programs(srv):
    """Wraps the compiled prefill and draft-prefill programs of ``srv``:
    returns the list that collects, per dispatch, the (shape, dtype) of
    every operand the host hands over."""
    seen = []

    def spy(getter):
        def get(bucket):
            prog = getter(bucket)

            def call(*args):
                seen.append([(a.shape, a.dtype) for a in args
                             if isinstance(a, np.ndarray)])
                return prog(*args)

            return call

        return get

    srv._prefill_program = spy(srv._prefill_program)
    srv._draft_prefill_program = spy(srv._draft_prefill_program)
    return seen


@pytest.mark.generation
class TestPrefillShipsIds:
    """A prefill round hands the device token ids; the one-hot operand of
    the embedding product is built inside the program. Nothing the host
    builds has the vocabulary as a dimension, and what is served is what
    the non-server path generates, bit for bit."""

    @pytest.mark.parametrize("draft", [False, True],
                             ids=["plain", "draft_net"])
    def test_no_host_operand_has_a_vocabulary_dimension(self, lm40,
                                                        round_refs, draft):
        specs, _ = round_refs
        slots, bucket = 3, 8
        with _round_server(lm40, draft) as srv:
            seen = _spy_on_prefill_programs(srv)
            futs = [srv.submit(p, n, temperature=t, top_k=k, seed=sd)
                    for p, n, t, k, sd in specs]
            for f in futs:
                f.result(timeout=180)
            snap = srv.metrics.snapshot()
        # the counter counts dispatches: one a row group, so at least one
        # a round, and the 20-token prompts alone need three rounds
        rounds = snap["generation_prefill_rounds_total"]
        assert rounds >= 3
        assert len(seen) == rounds + (len(specs) if draft else 0)
        for operands in seen:
            assert operands, "a dispatch took no host operand at all"
            for shape, dtype in operands:
                assert V not in shape, (shape, dtype)
                assert len(shape) <= 2, (shape, dtype)
            assert any(dtype == np.int32 and len(shape) == 2
                       and shape[1] % bucket == 0
                       for shape, dtype in operands)
        per_round = snap["generation_prefill_host_bytes_total"] / rounds
        # the block a round used to upload was slots x bucket x V x 4; a
        # dispatch's row group is narrower than the slots
        assert 0 < per_round < slots * bucket * 16

    @pytest.mark.parametrize("draft", [False, True],
                             ids=["plain", "draft_net"])
    def test_rounds_beside_decodes_serve_the_serial_tokens(self, lm40,
                                                           round_refs,
                                                           draft):
        """Prompts of one, two and three rounds, greedy and sampled,
        admitted while an earlier request is decoding: every completion
        is bit-equal to ``greedy_generate`` / ``sample_generate``."""
        specs, refs = round_refs
        with _round_server(lm40, draft) as srv:
            p, n, t, k, sd = specs[0]
            running = srv.submit(p, n, temperature=t, top_k=k, seed=sd)
            t_end = time.monotonic() + 120
            while srv.stats()["active_slots"] < 1:
                assert time.monotonic() < t_end, "never admitted"
                time.sleep(0.002)
            futs = [running] + [
                srv.submit(p, n, temperature=t, top_k=k, seed=sd)
                for p, n, t, k, sd in specs[1:]]
            outs = [f.result(timeout=180) for f in futs]
            st = srv.stats()
            rounds = srv.metrics.snapshot()["generation_prefill_rounds_total"]
        for got, ref in zip(outs, refs):
            np.testing.assert_array_equal(got, ref)
        assert st["failed"] == 0 and st["prefills"] == len(specs)
        # dispatches: the runner's one, and three rounds for the longest
        assert rounds >= 1 + 3

    @pytest.mark.parametrize("bad", [V, -1], ids=["vocab", "minus_one"])
    def test_an_id_out_of_range_is_refused_at_submit(self, lm, greedy_refs,
                                                     bad):
        """The device's one-hot would turn it into a silent zero row, so
        ``submit()`` is the range check; it costs the requests in flight
        nothing (the host scatter it replaces failed them all)."""
        reqs, refs = greedy_refs
        with serving(lm, V, slots=3) as srv:
            futs = [srv.submit(p, s) for p, s in reqs[:3]]
            with pytest.raises(ValueError, match="prompt ids"):
                srv.submit(np.array([1, bad, 2]), 4)
            outs = [f.result(timeout=120) for f in futs]
            # and the server still serves
            again = srv.submit(*reqs[3]).result(timeout=120)
            st = srv.stats()
        for got, ref in zip(outs + [again], refs[:4]):
            np.testing.assert_array_equal(got, ref)
        assert st["failed"] == 0 and st["completed"] == 4


def _row_groups_of(monkeypatch, rows):
    """Pins the row width the tests' arithmetic is written for, whatever
    the server's constant becomes."""
    monkeypatch.setattr(GenerationServer, "PREFILL_ROWS", rows)


def _prefill_programs(net):
    return [k for k in net._output_cache if k[0] == "gen_prefill"]


@pytest.mark.generation
class TestPrefillRowGroups:
    """A prefill dispatch computes a row group of ``PREFILL_ROWS`` rows
    gathered by slot index, not every slot: a round of more live rows
    than the width is several dispatches, one of fewer is padded with rows
    that write nothing, and what is served is what the non-server path
    generates, bit for bit."""

    @pytest.mark.parametrize("draft", [False, True],
                             ids=["plain", "draft_net"])
    def test_wide_and_narrow_waves_beside_a_decode_serve_the_serial_tokens(
            self, lm40, round_refs, draft, monkeypatch):
        """Width 2, four slots. A runner decodes; three requests of one,
        two and three rounds of eight are admitted as ONE wave (groups of
        2 and 1 + a padding row, then fewer as the short ones finish),
        then three more: every completion, the runner's included, is
        ``greedy_generate`` / ``sample_generate``'s, and the counters add
        up: computed = dispatches x width, admitted = the 8-token chunks
        of all prompts."""
        _row_groups_of(monkeypatch, 2)
        specs, refs = round_refs
        kw = dict(draft_net=lm40, spec_k=3) if draft else {}
        done_at = {}
        with serving(lm40, V, slots=4, page_size=8, prefill_chunk=8,
                     steps_per_dispatch=2, prefix_cache=False, **kw) as srv:
            seen = _spy_on_prefill_programs(srv)
            pages_a_slot = srv._bt.shape[1]
            srv.set_active_slots(1)
            p, n, t, k, sd = specs[0]
            running = srv.submit(p, n, temperature=t, top_k=k, seed=sd)
            running.add_done_callback(
                lambda _f: done_at.setdefault("runner", time.monotonic()))
            wave = [srv.submit(p, n, temperature=t, top_k=k, seed=sd)
                    for p, n, t, k, sd in specs[1:4]]
            t_end = time.monotonic() + 120
            while srv.stats()["active_slots"] < 1:
                assert time.monotonic() < t_end, "never admitted"
                time.sleep(0.001)
            srv.set_active_slots(4)         # the three queued: one wave
            outs = [f.result(timeout=180) for f in [running] + wave]
            rest = [srv.submit(p, n, temperature=t, top_k=k, seed=sd)
                    for p, n, t, k, sd in specs[4:]]
            outs += [f.result(timeout=180) for f in rest]
            snap = srv.metrics.snapshot()
        for got, ref in zip(outs, refs):
            np.testing.assert_array_equal(got, ref)
        dispatches = snap["generation_prefill_rounds_total"]
        rows = snap["generation_prefill_rows_total"]
        assert rows["kind=computed"] == dispatches * 2
        chunks = sum(-(-len(p) // 8) for p, *_ in specs)
        assert rows["kind=admitted"] == chunks == 13
        # the wave of three was two dispatches a round while all three
        # lived, so more dispatches than rounds, and padding rows exist
        assert dispatches > 3 and rows["kind=computed"] > chunks
        assert len(seen) == dispatches + (len(specs) if draft else 0)
        # beside the standing block table ([slots, pages a slot]), every
        # operand of a target prefill dispatch is two rows wide
        table = ((4, pages_a_slot), np.dtype(np.int32))
        target = [ops for ops in seen if table in ops]
        assert len(target) == dispatches, seen[:2]
        for ops in target:
            assert {shape[0] for shape, dt in ops
                    if (shape, dt) != table} == {2}, ops
        if not draft and "runner" in done_at:
            # the wave's first tokens came while the runner still decoded
            assert min(f._t_first for f in wave) < done_at["runner"]

    def test_one_program_a_column_bucket_whatever_the_wave(self, lm40,
                                                           monkeypatch):
        """Waves of 1, 2 and ``slots`` rows at two column buckets: the
        program cache holds one prefill program per bucket, as before the
        row group, and none is added by a wave's size."""
        _row_groups_of(monkeypatch, 2)
        rs = np.random.RandomState(41)
        net = tiny_lm(max_length=40, seed=11)
        with serving(net, V, slots=4, page_size=8, prefill_chunk=16,
                     steps_per_dispatch=2, prefix_cache=False) as srv:
            for wave in (1, 2, 4):
                # 5 tokens: bucket 8; 12 tokens: bucket 16
                for plen in (5, 12):
                    # queued under the loop's own lock: admitted together
                    with srv._cond:
                        futs = [srv.submit(rs.randint(0, V, plen), 3)
                                for _ in range(wave)]
                    for f in futs:
                        assert f.result(timeout=180).shape == (3,)
                assert sorted(k[4] for k in _prefill_programs(net)) \
                    == [8, 16], wave
            rows = srv.metrics.snapshot()["generation_prefill_rows_total"]
        assert rows["kind=admitted"] == 2 * (1 + 2 + 4)
        # a wave of 1 (a padding row), of 2, and of 4 (two dispatches)
        assert rows["kind=computed"] == 2 * 2 * (1 + 1 + 2)
        assert all(k[2] == 2 for k in _prefill_programs(net))

    def test_the_width_is_the_servers_and_at_most_the_slots(self, lm):
        """No constructor argument, no environment variable: the class's
        constant, clamped to the slot pool."""
        import inspect

        assert not [p for p in inspect.signature(
            GenerationServer.__init__).parameters if "row" in p]
        with serving(lm, V, slots=1) as one:
            assert one._prefill_rows == 1
        with serving(lm, V, slots=5) as five:
            assert five._prefill_rows == GenerationServer.PREFILL_ROWS <= 5

    @pytest.mark.parametrize("positions, width", [(8, 1), (7, 1), (16, 2),
                                                  (64, 2)])
    def test_a_row_group_holds_the_position_budget_in_chunks(
            self, lm40, round_refs, monkeypatch, positions, width):
        """``PREFILL_POSITIONS`` bounds rows x the chunk's columns: at one
        chunk of budget (or less) a dispatch computes one row and no
        padding row, at two or more ``PREFILL_ROWS``; what is served is
        the serial path's either way."""
        _row_groups_of(monkeypatch, 2)
        monkeypatch.setattr(GenerationServer, "PREFILL_POSITIONS", positions)
        specs, refs = round_refs
        with serving(lm40, V, slots=4, page_size=8, prefill_chunk=8,
                     steps_per_dispatch=2, prefix_cache=False) as srv:
            assert srv._prefill_rows == width
            with srv._cond:                 # admitted as one wave
                futs = [srv.submit(p, n, temperature=t, top_k=k, seed=sd)
                        for p, n, t, k, sd in specs[:4]]
            outs = [f.result(timeout=180) for f in futs]
            outs += [srv.submit(p, n, temperature=t, top_k=k,
                                seed=sd).result(timeout=180)
                     for p, n, t, k, sd in specs[4:]]
            snap = srv.metrics.snapshot()
        for got, ref in zip(outs, refs):
            np.testing.assert_array_equal(got, ref)
        rows = snap["generation_prefill_rows_total"]
        chunks = sum(-(-len(p) // 8) for p, *_ in specs)
        assert rows["kind=admitted"] == chunks
        assert rows["kind=computed"] \
            == snap["generation_prefill_rounds_total"] * width
        if width == 1:
            assert rows["kind=computed"] == chunks


@pytest.mark.generation
class TestBucketPages:
    """bucket_pages: the page-granular sibling of bucket_length that the
    paged prefill keys its program cache on."""

    def test_pow2_page_counts(self):
        from deeplearning4j_tpu.optimize.bucketing import bucket_pages
        assert bucket_pages(1, 16) == 1
        assert bucket_pages(16, 16) == 1
        assert bucket_pages(17, 16) == 2
        assert bucket_pages(40, 16) == 4            # ceil 3 -> pow2 4
        # distant token counts collapse onto one page bucket
        assert bucket_pages(810, 16) == bucket_pages(900, 16) == 64

    def test_maximum_caps_and_rejects(self):
        from deeplearning4j_tpu.optimize.bucketing import bucket_pages
        assert bucket_pages(70, 16, maximum=5) == 5  # pow2 8 capped at 5
        with pytest.raises(ValueError, match="page budget"):
            bucket_pages(81, 16, maximum=5)          # 81 > 5*16
        with pytest.raises(ValueError, match="page_size"):
            bucket_pages(8, 0)
        with pytest.raises(ValueError, match="token"):
            bucket_pages(0, 16)
