"""Disaggregated prefill/decode serving-tier tests (parallel/fleet.py +
parallel/generation.py role modes).

Covers the tier boundary end to end on the CPU mesh: a prefill-role
server exporting freshly prefilled requests as KVSnapshots (first token
included), decode-tier adoption finishing the stream bit-exactly vs a
unified single-tier server (greedy + sampled, f32 + int8), remaining
deadline budget crossing the wire as a duration, role-aware fleet
routing behind the same ``submit() -> Future`` surface with TTFT and
inter-token latency in separate histograms, and the robustness core:
mid-handoff kills on either side of the boundary, corrupt / truncated /
dropped transfers falling back without losing a future, and the
decode-tier-dark degraded mode with automatic recovery.
"""

import time

import numpy as np
import pytest

from deeplearning4j_tpu.models.zoo import TransformerLM, greedy_generate
from deeplearning4j_tpu.parallel.fleet import ReplicaFleet
from deeplearning4j_tpu.parallel.generation import GenerationServer
from deeplearning4j_tpu.parallel.handoff import (KVSnapshot,
                                                 SnapshotUnsupported,
                                                 export_request)
from deeplearning4j_tpu.parallel.resilience import (ChaosPolicy, Deadline,
                                                    DeadlineExceeded,
                                                    TransientDispatchError)
from tests.serving_helpers import (GREEDY, SAMPLED, V, fleet_of,
                                   mixed_specs, serial_refs, serving,
                                   submit_with_backoff)


def _tier_factory(lm, roles, chaos_by_rid=None, **gen_kw):
    kw = dict(slots=2, page_size=4, steps_per_dispatch=1)
    kw.update(gen_kw)

    def factory(rid):
        extra = {}
        if chaos_by_rid and rid in chaos_by_rid:
            extra["chaos"] = chaos_by_rid[rid]
        return GenerationServer(lm, V, role=roles[rid], **kw, **extra)

    return factory


def _assert_zero_lost(st):
    """The cross-tier ledger: once idle, every accepted request is
    accounted for — nothing vanished in a handoff."""
    assert st["submitted"] == (st["completed"] + st["failed"]
                               + st["expired"] + st["rejected_submits"]), st
    assert st["inflight"] == 0 and st["parked"] == 0


@pytest.mark.disagg
class TestPrefillExport:
    def test_export_and_adopt_bitexact(self, lm):
        """A prefill-role server resolves the future to a KVSnapshot
        holding exactly the first token; adopting it on a separate
        decode-role server finishes byte-identical to the serial
        reference — greedy and sampled."""
        for spec in (GREEDY, SAMPLED):
            p, steps, temp, top_k, seed = spec
            ref = serial_refs(lm, [spec])[0]
            with serving(lm, V, slots=2, page_size=4,
                         role="prefill") as pre:
                snap = pre.submit(p, steps, temperature=temp, top_k=top_k,
                                  seed=seed).result(timeout=120)
                assert isinstance(snap, KVSnapshot)
                assert snap.count == 1 and snap.tokens == [int(ref[0])]
                st = pre.stats()
                assert st["role"] == "prefill"
                assert st["handoff"]["prefill_exports"] == 1
                # the slot frees at export: short slot residency is the
                # whole point of the prefill tier
                assert st["active_slots"] == 0 and st["queued"] == 0
            with serving(lm, V, slots=2, page_size=4,
                         role="decode") as dec:
                out = dec.adopt_request(snap).result(timeout=120)
                np.testing.assert_array_equal(np.asarray(out), ref)
                assert dec.stats()["role"] == "decode"

    def test_export_int8_bitexact_vs_unified_int8(self, lm):
        """int8 tier transfer: prefill-export from an int8 pool adopted
        into an int8 decode pool matches the unified int8 server's own
        completion token-for-token."""
        p, steps, temp, top_k, seed = SAMPLED
        with serving(lm, V, slots=2, page_size=4,
                     kv_dtype="int8") as uni:
            ref = np.asarray(uni.submit(
                p, steps, temperature=temp, top_k=top_k,
                seed=seed).result(timeout=120))
        with serving(lm, V, slots=2, page_size=4, kv_dtype="int8",
                     role="prefill") as pre:
            snap = pre.submit(p, steps, temperature=temp, top_k=top_k,
                              seed=seed).result(timeout=120)
        assert snap.kv_dtype == "int8"
        with serving(lm, V, slots=2, page_size=4, kv_dtype="int8",
                     role="decode") as dec:
            out = dec.adopt_request(snap).result(timeout=120)
        np.testing.assert_array_equal(np.asarray(out), ref)

    def test_single_token_request_returns_tokens(self, lm):
        """max_tokens=1 finishes ON the prefill token: the request
        retires normally with a token array — never a snapshot of an
        already-complete stream."""
        p = np.array([1, 2, 3], np.int64)
        ref = greedy_generate(lm, p[None], 1, V)[0]
        with serving(lm, V, slots=2, page_size=4, role="prefill") as pre:
            out = pre.submit(p, 1).result(timeout=120)
            assert not isinstance(out, KVSnapshot)
            np.testing.assert_array_equal(np.asarray(out), ref)
            assert pre.stats()["handoff"]["prefill_exports"] == 0

    def test_role_validation(self, lm):
        with pytest.raises(ValueError):
            GenerationServer(lm, V, role="bogus")
        with pytest.raises(ValueError):
            ReplicaFleet(lambda rid: GenerationServer(lm, V), replicas=2,
                         roles=("prefill",))  # length mismatch
        with pytest.raises(ValueError):
            ReplicaFleet(lambda rid: GenerationServer(lm, V), replicas=2,
                         roles=("prefill", "prefill"))  # no decode tier
        with pytest.raises(ValueError):
            # declared roles must match what the factory builds
            ReplicaFleet(lambda rid: GenerationServer(lm, V), replicas=2,
                         roles=("prefill", "decode"))


@pytest.mark.disagg
class TestDeadlineAcrossTiers:
    def test_snapshot_carries_remaining_budget(self, lm):
        """The wire format ships the request's REMAINING deadline budget
        as a duration (never a timestamp): present after export, bounded
        by the original budget, and preserved by a byte round-trip."""
        p, steps, _, _, _ = GREEDY
        with serving(lm, V, slots=2, page_size=4, role="prefill") as pre:
            snap = pre.submit(p, steps, deadline_s=120.0).result(
                timeout=120)
        assert snap.deadline_remaining is not None
        assert 0.0 < snap.deadline_remaining <= 120.0
        back = KVSnapshot.from_bytes(snap.to_bytes())
        assert back.deadline_remaining == snap.deadline_remaining
        # a request submitted WITHOUT a deadline exports None
        with serving(lm, V, slots=2, page_size=4, role="prefill") as pre:
            snap2 = pre.submit(p, steps).result(timeout=120)
        assert snap2.deadline_remaining is None
        assert KVSnapshot.from_bytes(
            snap2.to_bytes()).deadline_remaining is None

    def test_adopting_exhausted_budget_fails_typed(self, lm):
        """A snapshot whose carried budget is already spent is rejected
        with the typed DeadlineExceeded at adoption — the decode tier
        never burns slots on a request that cannot meet its SLO."""
        p, steps, _, _, _ = GREEDY
        with serving(lm, V, slots=2, page_size=4, role="prefill") as pre:
            snap = pre.submit(p, steps).result(timeout=120)
        kw = {s: getattr(snap, s) for s in KVSnapshot.__slots__
              if s != "checksum"}
        kw["deadline_remaining"] = 1e-4
        expired = KVSnapshot(**kw)
        with serving(lm, V, slots=2, page_size=4, role="decode") as dec:
            with pytest.raises(DeadlineExceeded):
                dec.adopt_request(expired).result(timeout=120)

    def test_export_request_clamps_to_deadline(self, lm):
        """``export_request`` waits ``min(timeout, remaining)`` and
        raises the typed expiry: an exhausted budget fails fast even
        with the default 30 s timeout."""
        p = np.array([1, 2, 3, 4], np.int64)
        with serving(lm, V, slots=2, page_size=4) as srv:
            fut = srv.submit(p, 12)
            fut._deadline = Deadline(1e-4)  # budget already spent
            time.sleep(0.005)
            t0 = time.monotonic()
            with pytest.raises(DeadlineExceeded):
                export_request(srv, fut, timeout=30.0)
            assert time.monotonic() - t0 < 5.0
            fut._deadline = None  # let the request finish normally
            fut.result(timeout=120)


@pytest.mark.disagg
class TestTieredFleet:
    def test_mixed_bitexact_ledger_and_slos(self, lm):
        """The full tier pipeline behind one submit(): every completion
        bit-exact vs serial, every request crossing the boundary exactly
        once, zero lost futures, and TTFT / inter-token latency observed
        in SEPARATE registry histograms."""
        rng = np.random.default_rng(42)
        specs = mixed_specs(8, rng)
        refs = serial_refs(lm, specs)
        roles = ("prefill", "decode")
        with fleet_of(_tier_factory(lm, roles), 2, roles=roles) as fl:
            futs = [submit_with_backoff(fl, sp) for sp in specs]
            for fut, ref in zip(futs, refs):
                np.testing.assert_array_equal(
                    np.asarray(fut.result(timeout=240)), ref)
            st = fl.stats()
            assert st["tier_handoffs"] >= len(specs)
            assert st["degraded_mode"] is False
            assert st["tiers"]["prefill"]["replicas"] == 1
            assert st["tiers"]["decode"]["replicas"] == 1
            assert st["completed"] == len(specs)
            _assert_zero_lost(st)
            assert fl.ttft_hist.count == len(specs)
            assert fl.itl_hist.count == len(specs)
            assert fl.ttft_hist.sum > 0 and fl.itl_hist.sum > 0
            # per-tier levers move capacity independently
            assert fl.set_tier_active_slots("decode", 1) == 1
            assert fl.tier_stats("decode")["active_slots"] == 1
            assert fl.tier_stats("prefill")["active_slots"] == 2
            assert fl.set_tier_active_slots("decode", 2) == 2

    def test_int8_tiered_matches_unified(self, lm):
        specs = [GREEDY, SAMPLED]
        with serving(lm, V, slots=2, page_size=4, kv_dtype="int8") as uni:
            refs = [np.asarray(uni.submit(
                p, steps, temperature=t, top_k=k, seed=s).result(
                    timeout=120))
                for p, steps, t, k, s in specs]
        roles = ("prefill", "decode")
        with fleet_of(_tier_factory(lm, roles, kv_dtype="int8"), 2,
                      roles=roles) as fl:
            futs = [submit_with_backoff(fl, sp) for sp in specs]
            for fut, ref in zip(futs, refs):
                np.testing.assert_array_equal(
                    np.asarray(fut.result(timeout=240)), ref)
            _assert_zero_lost(fl.stats())

    def test_decode_tier_dark_degraded_and_recovery(self, lm):
        """Kill the only decode replica: the fleet flips the
        degraded_mode gauge, serves every request co-located on the
        prefill tier (bit-exact), then clears the flag automatically
        when the supervised restart heals the tier."""
        ref = serial_refs(lm, [GREEDY])[0]
        roles = ("prefill", "decode")
        # a long restart backoff keeps the tier dark across the whole
        # degraded pass, so the assertions race nothing
        with fleet_of(_tier_factory(lm, roles), 2, roles=roles,
                      restart_backoff_s=5.0) as fl:
            assert fl.kill_replica(1)
            futs = [submit_with_backoff(fl, GREEDY) for _ in range(3)]
            for fut in futs:
                np.testing.assert_array_equal(
                    np.asarray(fut.result(timeout=240)), ref)
            st = fl.stats()
            assert st["degraded_mode"] is True
            assert st["degraded_submits"] >= 3
            # supervised restart brings the tier back -> flag clears
            t_end = time.monotonic() + 90.0
            while fl.stats()["degraded_mode"]:
                assert time.monotonic() < t_end, "degraded mode stuck"
                time.sleep(0.02)
            before = fl.stats()["tier_handoffs"]
            fut = submit_with_backoff(fl, GREEDY)
            np.testing.assert_array_equal(
                np.asarray(fut.result(timeout=240)), ref)
            st = fl.stats()
            assert st["tier_handoffs"] > before  # pipeline is back
            _assert_zero_lost(st)

    def test_no_recompile_on_tier_churn(self):
        """Zero-retrace across the boundary: after one greedy and one
        sampled request have crossed the tiers, further tiered traffic
        adds ZERO compiled programs."""
        net = TransformerLM(num_labels=V, max_length=16, d_model=8,
                            n_heads=2, n_blocks=1, seed=9).init()
        roles = ("prefill", "decode")
        with fleet_of(_tier_factory(net, roles), 2, roles=roles) as fl:
            for sp in (GREEDY, SAMPLED):
                submit_with_backoff(fl, sp).result(timeout=240)
            warmed = len(net._output_cache)
            specs = mixed_specs(4, np.random.default_rng(5))
            futs = [submit_with_backoff(fl, sp) for sp in specs]
            for fut in futs:
                fut.result(timeout=240)
            assert len(net._output_cache) == warmed


@pytest.mark.disagg
class TestTierChaos:
    def test_midhandoff_prefill_kill(self, lm):
        """Killing a prefill replica with requests in flight re-prefills
        them on the sibling: all complete bit-exact, zero lost."""
        rng = np.random.default_rng(7)
        specs = mixed_specs(6, rng)
        refs = serial_refs(lm, specs)
        roles = ("prefill", "prefill", "decode")
        with fleet_of(_tier_factory(lm, roles), 3, roles=roles) as fl:
            futs = [submit_with_backoff(fl, sp) for sp in specs]
            fl.kill_replica(0)  # mid-prefill for whatever it holds
            for fut, ref in zip(futs, refs):
                np.testing.assert_array_equal(
                    np.asarray(fut.result(timeout=240)), ref)
            st = fl.stats()
            assert st["completed"] == len(specs)
            _assert_zero_lost(st)

    def test_midhandoff_decode_kill(self, lm):
        """Killing a decode replica mid-stream re-adopts (or token-0
        regenerates) its requests elsewhere: all complete bit-exact,
        zero lost."""
        rng = np.random.default_rng(11)
        specs = mixed_specs(6, rng, shapes=((3, 12), (4, 12), (3, 13)))
        refs = serial_refs(lm, specs)
        roles = ("prefill", "decode", "decode")
        with fleet_of(_tier_factory(lm, roles, snapshot_every=4), 3,
                      roles=roles) as fl:
            futs = [submit_with_backoff(fl, sp) for sp in specs]
            # event-driven: kill a decode replica once it is visibly
            # streaming (poll, don't sleep-calibrate)
            victim = None
            t_end = time.monotonic() + 90.0
            while victim is None and time.monotonic() < t_end:
                for blk in fl.stats()["replicas"]:
                    srv = blk["server"] or {}
                    if (blk["role"] == "decode" and blk["state"] == "ready"
                            and srv.get("active_slots", 0) >= 1):
                        victim = blk["rid"]
                        break
                else:
                    time.sleep(0.005)
            if victim is not None:
                fl.kill_replica(victim)
            for fut, ref in zip(futs, refs):
                np.testing.assert_array_equal(
                    np.asarray(fut.result(timeout=240)), ref)
            st = fl.stats()
            assert st["completed"] == len(specs)
            _assert_zero_lost(st)

    def test_corrupt_transfer_token0_fallback(self, lm):
        """A corrupted tier transfer (checksum breaks in flight) is
        dropped at adoption and the request regenerates from token 0 on
        the decode tier — bit-exact, typed, never lost."""
        self._faulty_transfer_case(lm, ChaosPolicy(
            seed=5, snapshot_corrupt_rate=1.0))

    def test_truncated_transfer_token0_fallback(self, lm):
        """A truncated transfer (partial wire bytes) fails checksum
        verification exactly like corruption: token-0 fallback."""
        self._faulty_transfer_case(lm, ChaosPolicy(
            seed=6, handoff_truncate_rate=1.0))

    @staticmethod
    def _faulty_transfer_case(lm, chaos):
        specs = [GREEDY, SAMPLED]
        refs = serial_refs(lm, specs)
        roles = ("prefill", "decode")
        factory = _tier_factory(lm, roles, chaos_by_rid={0: chaos})
        with fleet_of(factory, 2, roles=roles) as fl:
            futs = [submit_with_backoff(fl, sp) for sp in specs]
            for fut, ref in zip(futs, refs):
                np.testing.assert_array_equal(
                    np.asarray(fut.result(timeout=240)), ref)
            st = fl.stats()
            assert st["handoff_fallbacks"] >= len(specs)
            assert st["completed"] == len(specs) and st["failed"] == 0
            _assert_zero_lost(st)

    def test_dropped_transfer_reprefills_on_sibling(self, lm):
        """A transfer that vanishes in flight fails the attempt typed
        (SnapshotUnavailable, no snapshot) and the fleet re-prefills on
        the clean sibling prefill replica."""
        specs = [GREEDY, SAMPLED, (np.array([2, 5, 1], np.int64),
                                   10, 0.0, 0, 0)]
        refs = serial_refs(lm, specs)
        chaos = ChaosPolicy(seed=8, handoff_drop_rate=1.0)
        roles = ("prefill", "prefill", "decode")
        factory = _tier_factory(lm, roles, chaos_by_rid={0: chaos})
        with fleet_of(factory, 3, roles=roles) as fl:
            futs = [submit_with_backoff(fl, sp) for sp in specs]
            for fut, ref in zip(futs, refs):
                np.testing.assert_array_equal(
                    np.asarray(fut.result(timeout=240)), ref)
            st = fl.stats()
            assert st["completed"] == len(specs) and st["failed"] == 0
            if chaos.injected_handoff_drop:  # routing hit the faulty rep
                assert st["redispatched"] >= 1
            _assert_zero_lost(st)

    def test_speculative_prefill_role_rejected(self, lm):
        """Speculative decoding cannot export mid-stream KV: a
        prefill-role server with a draft net is a config error, typed
        at construction."""
        draft = TransformerLM(num_labels=V, max_length=16, d_model=8,
                              n_heads=2, n_blocks=1, seed=4).init()
        with pytest.raises((ValueError, SnapshotUnsupported)):
            GenerationServer(lm, V, role="prefill", draft_net=draft)


@pytest.mark.disagg
class TestChaosPinning:
    def test_handoff_fault_modes_deterministic_and_exclusive(self):
        """Same seed -> same corrupt/stall/drop/truncate sequence; at
        most one fault per draw; counters match the emitted modes."""
        def run():
            sleeps = []
            ch = ChaosPolicy(seed=7, snapshot_corrupt_rate=0.1,
                             handoff_stall_rate=0.1, handoff_stall_s=0.5,
                             handoff_drop_rate=0.1,
                             handoff_truncate_rate=0.1,
                             sleep=sleeps.append)
            modes = [ch.handoff_fault_mode() for _ in range(400)]
            return modes, sleeps, ch

        m1, s1, c1 = run()
        m2, s2, c2 = run()
        assert m1 == m2 and s1 == s2
        assert m1.count("corrupt") == c1.injected_snapshot_corrupt > 0
        assert m1.count("drop") == c1.injected_handoff_drop > 0
        assert m1.count("truncate") == c1.injected_handoff_truncate > 0
        assert len(s1) == c1.injected_handoff_stall > 0
        assert c1.injected_handoff_drop == c2.injected_handoff_drop
        assert c1.injected_handoff_truncate == c2.injected_handoff_truncate

    def test_legacy_sequences_pinned(self):
        """Zero-rate drop/truncate knobs draw NOTHING from the chaos
        RNG: a seeded policy's replica-fault sequence is byte-identical
        with the new parameters present and interleaved fault checks."""
        def pattern(**kw):
            ch = ChaosPolicy(seed=11, transient_rate=0.3, hard_rate=0.1,
                             **kw)
            fn = ch.wrap(lambda: "ok")
            seq = []
            for _ in range(200):
                if kw:
                    assert ch.handoff_fault() is False
                    assert ch.handoff_fault_mode() is None
                try:
                    seq.append(fn() is not None)
                except TransientDispatchError:
                    seq.append("transient")
                except RuntimeError:
                    seq.append("hard")
            return seq

        assert pattern() == pattern(handoff_drop_rate=0.0,
                                    handoff_truncate_rate=0.0)
        # and the PR-11 knobs stay pinned alongside the new ones
        assert pattern() == pattern(snapshot_corrupt_rate=0.0,
                                    handoff_stall_rate=0.0,
                                    handoff_drop_rate=0.0,
                                    handoff_truncate_rate=0.0)
