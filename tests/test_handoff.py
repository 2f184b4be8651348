"""Crash-durable generation tests (parallel/handoff.py).

Covers the KV-snapshot/live-migration contract end to end on the CPU
mesh: snapshot export of a live request (resident KV pages, block-table
row, stream position, RNG fold-in state, accepted tokens) with a
versioned checksummed wire format, adoption into a DIFFERENT server
resuming at position N bit-exactly (greedy and sampled, f32 and int8
pools), corrupted-checksum detection falling back to token-0 replay,
fleet failover resuming from the newest harvested snapshot after a
mid-stream replica kill (zero lost futures), drain-migrate handoff on
both the plain server and ``retire_replica(migrate=True)``, the
preempt-resume path, the seeded ChaosPolicy handoff fault modes, and
the zero-retrace property under repeated adoption.
"""

import time

import numpy as np
import pytest

from deeplearning4j_tpu.models.zoo import (TransformerLM, greedy_generate,
                                           sample_generate)
from deeplearning4j_tpu.parallel.fleet import RETIRED
from deeplearning4j_tpu.parallel.generation import GenerationServer
from deeplearning4j_tpu.parallel.handoff import (WIRE_VERSION, KVSnapshot,
                                                 RequestMigrated,
                                                 SnapshotInvalid,
                                                 SnapshotUnavailable,
                                                 SnapshotUnsupported,
                                                 adopt_request,
                                                 corrupt_snapshot,
                                                 downgrade_snapshot,
                                                 export_request)
from deeplearning4j_tpu.parallel.resilience import (ChaosPolicy,
                                                    ServerOverloaded,
                                                    TransientDispatchError)
from tests.serving_helpers import (GREEDY, SAMPLED, V, fleet_of,
                                   mixed_specs, serial_refs, serving,
                                   submit_with_backoff,
                                   wait_replica_midstream)


def _run_to_snapshot(lm, spec, **server_kw):
    """Run one request to completion on a periodically-snapshotting
    server; return (completed tokens, last published KVSnapshot)."""
    p, steps, temp, top_k, seed = spec
    kw = dict(slots=2, page_size=4, snapshot_every=4,
              steps_per_dispatch=2)
    kw.update(server_kw)
    with serving(lm, V, **kw) as srv:
        fut = srv.submit(p, steps, temperature=temp, top_k=top_k,
                         seed=seed)
        out = np.asarray(fut.result(timeout=120))
        st = srv.stats()["handoff"]
    snap = getattr(fut, "_kv_snapshot", None)
    assert snap is not None, "snapshot_every published no snapshot"
    assert st["snapshots"] >= 1 and st["bytes"] > 0
    return out, snap


@pytest.mark.handoff
class TestSnapshotRoundTrip:
    def test_greedy_f32_resume_bitexact(self, lm):
        """A mid-stream snapshot adopted into a DIFFERENT server resumes
        at position N and finishes byte-identical to the uninterrupted
        greedy stream — no token is recomputed differently."""
        p = GREEDY[0]
        ref = greedy_generate(lm, p[None], 12, V)[0]
        out, snap = _run_to_snapshot(lm, GREEDY)
        np.testing.assert_array_equal(out, ref)
        assert 0 < snap.count < 12          # genuinely mid-stream
        assert snap.version == WIRE_VERSION
        assert list(snap.tokens) == list(ref[:snap.count])
        with serving(lm, V, slots=2, page_size=4) as dst:
            res = adopt_request(dst, snap).result(timeout=120)
            st = dst.stats()["handoff"]
        np.testing.assert_array_equal(np.asarray(res), ref)
        assert st["resumes"] == 1
        assert st["tokens_saved"] == snap.count
        assert st["fallbacks"] == 0

    def test_sampled_f32_resume_bitexact(self, lm):
        """The fold_in key schedule is server-state-free, so a SAMPLED
        stream resumes bit-exactly on the adopting server too."""
        p, steps, temp, top_k, seed = SAMPLED
        ref = sample_generate(lm, p[None], steps, V, temperature=temp,
                              top_k=top_k, seed=seed)[0]
        out, snap = _run_to_snapshot(lm, SAMPLED)
        np.testing.assert_array_equal(out, ref)
        with serving(lm, V, slots=2, page_size=4) as dst:
            res = adopt_request(dst, snap).result(timeout=120)
        np.testing.assert_array_equal(np.asarray(res), ref)

    def test_int8_resume_bitexact_and_wire_ratio(self, lm):
        """An int8 pool snapshots its quantized pages + scale planes:
        adoption reproduces the uninterrupted int8 stream bit-exactly,
        and the wire image ships >= 2.5x smaller than the f32 one at
        the same stream position."""
        out_q, snap_q = _run_to_snapshot(lm, GREEDY, kv_dtype="int8")
        _out_f, snap_f = _run_to_snapshot(lm, GREEDY)
        assert snap_q.kv_dtype == "int8"
        assert snap_f.count == snap_q.count  # same publish schedule
        assert snap_q.wire_bytes() < snap_f.wire_bytes()
        # page payload (the part that scales with context) shrinks by
        # the int8 + per-row-scale factor; the JSON header is constant
        pf = sum(a.nbytes for _, _, a in _leaves(snap_f))
        pq = sum(a.nbytes for _, _, a in _leaves(snap_q))
        ratio = pf / pq
        assert ratio >= 2.5, f"int8 KV payload only {ratio:.2f}x smaller"
        with serving(lm, V, slots=2, page_size=4, kv_dtype="int8") as dst:
            res = adopt_request(dst, snap_q).result(timeout=120)
        np.testing.assert_array_equal(np.asarray(res), out_q)

    def test_wire_bytes_roundtrip(self, lm):
        """to_bytes/from_bytes is lossless: every header field and every
        payload leaf round-trips, and the checksum re-verifies."""
        _out, snap = _run_to_snapshot(lm, SAMPLED)
        blob = snap.to_bytes()
        assert len(blob) == snap.wire_bytes()
        back = KVSnapshot.from_bytes(blob)
        assert back.verify()
        for f in ("version", "pos", "count", "last", "temperature",
                  "top_k", "seed", "kv_dtype", "page_size",
                  "page_token_bytes", "page_digests"):
            assert getattr(back, f) == getattr(snap, f), f
        assert list(back.tokens) == list(snap.tokens)
        np.testing.assert_array_equal(back.prompt, snap.prompt)
        np.testing.assert_array_equal(back.key, snap.key)
        for (vn, leaf, a), (vn2, leaf2, b) in zip(
                _leaves(snap), _leaves(back)):
            assert (vn, leaf) == (vn2, leaf2)
            np.testing.assert_array_equal(a, b)

    @pytest.mark.parametrize("kv_dtype", [None, "int8"])
    def test_export_is_the_canonical_payload_whatever_the_pool_order(
            self, lm, kv_dtype):
        """The wire is not the pool: a server's pool holds a token's heads
        side by side (``[pages, page_size, H * d]``) and the v3 payload is
        ``[n, H, page_size, d]`` stacks. An export is byte for byte
        (header, checksum, payload) the snapshot built BY HAND from the
        keys and values the device pool held when it was taken, head h of
        a token read from lanes ``[h * d, (h + 1) * d)`` of its page row;
        and that hand-built snapshot, through its bytes, imports into the
        pool and serves the rest of the tokens."""
        import jax

        p, steps, temp, top_k, seed = SAMPLED
        seen = {}
        with serving(lm, V, slots=2, page_size=4, snapshot_every=4,
                     steps_per_dispatch=2, kv_dtype=kv_dtype) as srv:
            export = srv._snapshot_slot

            def recording(slot):          # loop thread, between dispatches
                n = -(-int(srv._pos[slot]) // 4)
                seen.update(pages=list(srv._slot_pages[slot][:n]),
                            pool=jax.device_get(srv._pool))
                return export(slot)

            srv._snapshot_slot = recording
            fut = srv.submit(p, steps, temperature=temp, top_k=top_k,
                             seed=seed)
            out = np.asarray(fut.result(timeout=120))
            layers = {vn: srv._layer_by_name[vn]
                      for vn in srv._paged_names}
        snap = fut._kv_snapshot
        assert snap.version == WIRE_VERSION == 3
        assert snap.head_layout == "canonical" and 0 < snap.count < steps
        by_hand = {}
        for vn, layer in layers.items():
            H, d = layer.kv_heads, layer.d_head
            planes = {k: np.asarray(a)[seen["pages"]]
                      for k, a in seen["pool"][vn].items()}
            by_hand[vn] = {
                k: np.stack([planes[k][:, :, h * d:(h + 1) * d]
                             for h in range(H)], axis=1)
                for k in ("kpages", "vpages")}
            for k in ("kpages", "vpages"):
                assert by_hand[vn][k].shape == (snap.n_pages, H, 4, d)
            for k in set(planes) - {"kpages", "vpages"}:
                assert planes[k].shape == (snap.n_pages, H, 4)
                by_hand[vn][k] = planes[k]
        fields = {f: getattr(snap, f) for f in KVSnapshot.__slots__
                  if f not in ("payload", "checksum")}
        hand = KVSnapshot(payload=by_hand, **fields)
        assert hand.checksum == snap.checksum
        blob = hand.to_bytes()
        assert blob == snap.to_bytes()
        with serving(lm, V, slots=2, page_size=4,
                     kv_dtype=kv_dtype) as dst:
            res = adopt_request(dst, KVSnapshot.from_bytes(blob)).result(
                timeout=120)
            st = dst.stats()["handoff"]
        np.testing.assert_array_equal(np.asarray(res), out)
        assert st["resumes"] == 1 and st["fallbacks"] == 0

    def test_wire_rejects_garbage(self, lm):
        _out, snap = _run_to_snapshot(lm, GREEDY)
        blob = snap.to_bytes()
        with pytest.raises(SnapshotInvalid, match="byte stream"):
            KVSnapshot.from_bytes(b"XXXX" + blob[4:])
        # flip one payload byte: the sha256 gate catches it
        mid = len(blob) // 2
        bad = blob[:mid] + bytes([blob[mid] ^ 0xFF]) + blob[mid + 1:]
        with pytest.raises(SnapshotInvalid):
            KVSnapshot.from_bytes(bad)


def _leaves(snap):
    from deeplearning4j_tpu.parallel.handoff import _leaf_items
    return list(_leaf_items(snap.payload))


@pytest.mark.handoff
class TestWireV3ForwardCompat:
    """The v3 wire generation: sharded-geometry header fields, the
    typed cross-version refusal (BEFORE the checksum — a version skew
    must never masquerade as corruption), and the v2 downgrade bridge
    for fleet tiers still running v2-geometry readers."""

    def test_v3_header_roundtrip_tp1(self, lm):
        """A single-chip server emits v3 with the implied single-chip
        geometry, and the new fields survive the wire round-trip."""
        _out, snap = _run_to_snapshot(lm, GREEDY)
        assert snap.version == WIRE_VERSION == 3
        assert snap.shards == 1
        assert snap.head_layout == "canonical"
        back = KVSnapshot.from_bytes(snap.to_bytes())
        assert back.verify()
        assert (back.shards, back.head_layout) == (1, "canonical")

    def test_v3_rejected_by_v2_reader_typed(self, lm):
        """A v2-geometry reader (``supported=2``) refuses a v3 blob
        with SnapshotUnsupported naming the full geometry tuple —
        never a checksum error, never a silent truncation. Flipping a
        payload byte first proves the refusal fires BEFORE the
        integrity gate even looks."""
        _out, snap = _run_to_snapshot(lm, GREEDY)
        blob = snap.to_bytes()
        with pytest.raises(SnapshotUnsupported, match="geometry") as ei:
            KVSnapshot.from_bytes(blob, supported=2)
        msg = str(ei.value)
        for frag in ("version=3", "shards=1", "head_layout='canonical'",
                     "page_size="):
            assert frag in msg, msg
        assert "checksum" not in msg
        mid = len(blob) - 8                    # corrupt payload bytes
        bad = blob[:mid] + bytes([blob[mid] ^ 0xFF]) + blob[mid + 1:]
        with pytest.raises(SnapshotUnsupported, match="geometry"):
            KVSnapshot.from_bytes(bad, supported=2)

    def test_v2_rejected_by_v3_reader_typed(self, lm):
        """And the mirror image: a v2 blob at this (v3-geometry) reader
        fails the same typed way with the same tuple in the message."""
        _out, snap = _run_to_snapshot(lm, GREEDY)
        blob2 = downgrade_snapshot(snap).to_bytes()
        with pytest.raises(SnapshotUnsupported, match="geometry") as ei:
            KVSnapshot.from_bytes(blob2)
        assert "version=2" in str(ei.value)
        assert "checksum" not in str(ei.value)

    def test_unknown_version_invalid_before_parse(self, lm):
        """A version NO reader generation knows is SnapshotInvalid (not
        Unsupported): nothing about the header can be trusted, and the
        gate fires before the (now stale) checksum can confuse it."""
        _out, snap = _run_to_snapshot(lm, GREEDY)
        snap.version = 99
        with pytest.raises(SnapshotInvalid, match="version"):
            KVSnapshot.from_bytes(snap.to_bytes())

    def test_downgraded_v2_snapshot_adopts_bitexact(self, lm):
        """downgrade_snapshot emits a wire image a v2 reader parses
        (same payload, version-2 header/checksum), and the adopt gate
        keeps a one-generation legacy fallback: the v2 snapshot resumes
        bit-exactly on a live server."""
        p = GREEDY[0]
        ref = greedy_generate(lm, p[None], 12, V)[0]
        out, snap = _run_to_snapshot(lm, GREEDY)
        np.testing.assert_array_equal(out, ref)
        v2 = KVSnapshot.from_bytes(downgrade_snapshot(snap).to_bytes(),
                                   supported=2)
        assert v2.version == 2
        assert v2.verify()
        with serving(lm, V, slots=2, page_size=4) as dst:
            res = adopt_request(dst, v2).result(timeout=120)
            st = dst.stats()["handoff"]
        np.testing.assert_array_equal(np.asarray(res), ref)
        assert st["resumes"] == 1 and st["fallbacks"] == 0


@pytest.mark.handoff
class TestExportAndValidation:
    def test_export_live_request_midstream(self, lm):
        """export_request snapshots a request WHILE it streams; the
        exported state adopts elsewhere and both copies finish
        identical to the serial reference."""
        p = GREEDY[0]
        ref = greedy_generate(lm, p[None], 12, V)[0]
        chaos = ChaosPolicy(seed=5, stall_rate=1.0, stall_s=0.03)
        with serving(lm, V, slots=2, page_size=4, steps_per_dispatch=1,
                     chaos=chaos) as src:
            fut = src.submit(p, 12)
            time.sleep(0.05)                 # a few stalled dispatches in
            snap = export_request(src, fut, timeout=60.0)
            assert 1 <= snap.count <= 12
            with serving(lm, V, slots=2, page_size=4) as dst:
                res = adopt_request(dst, snap).result(timeout=120)
            out = fut.result(timeout=120)
        np.testing.assert_array_equal(np.asarray(out), ref)
        np.testing.assert_array_equal(np.asarray(res), ref)

    def test_export_completed_request_unavailable(self, lm):
        with serving(lm, V, slots=2, page_size=4) as src:
            fut = src.submit(GREEDY[0], 4)
            fut.result(timeout=120)
            with pytest.raises(SnapshotUnavailable):
                export_request(src, fut, timeout=30.0)

    def test_speculative_server_unsupported(self, lm):
        """Draft lookahead pages make a slot's KV non-reconstructible
        mid-round: export refuses typed, and snapshot_every refuses at
        construction."""
        with pytest.raises(ValueError, match="snapshot_every"):
            GenerationServer(lm, V, slots=2, draft_net=lm, spec_k=3,
                             snapshot_every=4)
        with serving(lm, V, slots=2, draft_net=lm, spec_k=3) as src:
            fut = src.submit(GREEDY[0], 4)
            with pytest.raises(SnapshotUnsupported):
                export_request(src, fut)
            fut.result(timeout=120)

    def test_adopt_rejects_corrupt_version_and_geometry(self, lm):
        _out, snap = _run_to_snapshot(lm, GREEDY)
        with serving(lm, V, slots=2, page_size=8) as dst:
            with pytest.raises(SnapshotUnsupported, match="geometry"):
                adopt_request(dst, snap)
        with serving(lm, V, slots=2, page_size=4, kv_dtype="int8") as dst:
            with pytest.raises(SnapshotUnsupported, match="geometry"):
                adopt_request(dst, snap)
        with serving(lm, V, slots=2, page_size=4) as dst:
            snap.version = WIRE_VERSION + 1
            with pytest.raises(SnapshotInvalid, match="version"):
                adopt_request(dst, snap)
            snap.version = WIRE_VERSION
            corrupt_snapshot(snap)
            assert not snap.verify()
            with pytest.raises(SnapshotInvalid, match="checksum"):
                adopt_request(dst, snap)

    @pytest.mark.parametrize("bad", [V, -1], ids=["vocab", "minus_one"])
    def test_adopt_rejects_prompt_ids_out_of_range(self, lm, bad):
        """A preempted adoptee is re-prefilled from ``snapshot.prompt``,
        and the device's one-hot turns an id out of range into a silent
        zero row: adoption holds the prompt to ``[0, vocab)`` up front,
        behind a checksum that is sound."""
        _out, snap = _run_to_snapshot(lm, GREEDY)
        snap.prompt = snap.prompt.copy()
        snap.prompt[1] = bad
        snap.checksum = snap.content_digest()
        assert snap.verify()
        with serving(lm, V, slots=2, page_size=4) as dst:
            with pytest.raises(SnapshotInvalid, match="prompt ids"):
                adopt_request(dst, snap)
            assert dst.stats()["handoff"]["resumes"] == 0

    def test_adopt_infeasible_sheds_typed(self, lm):
        _out, snap = _run_to_snapshot(lm, GREEDY)
        with serving(lm, V, slots=1, page_size=4, pages=3) as dst:
            with pytest.raises(ServerOverloaded):
                adopt_request(dst, snap)


@pytest.mark.handoff
class TestChaosHandoffModes:
    def test_handoff_faults_deterministic_and_exclusive(self):
        """Same seed -> same corrupt/stall sequence; at most one handoff
        fault per draw; stalls sleep outside the policy lock via the
        injected sleeper."""
        def run():
            sleeps = []
            ch = ChaosPolicy(seed=7, snapshot_corrupt_rate=0.15,
                             handoff_stall_rate=0.25, handoff_stall_s=0.5,
                             sleep=sleeps.append)
            outcomes = [ch.handoff_fault() for _ in range(200)]
            return outcomes, sleeps, ch

        o1, s1, c1 = run()
        o2, s2, c2 = run()
        assert o1 == o2 and s1 == s2
        assert c1.injected_snapshot_corrupt == c2.injected_snapshot_corrupt
        assert c1.injected_handoff_stall == c2.injected_handoff_stall
        assert c1.injected_snapshot_corrupt == sum(o1) > 0
        assert c1.injected_handoff_stall == len(s1) > 0
        assert all(s == 0.5 for s in s1)

    def test_legacy_sequences_pinned(self):
        """Zero-rate handoff knobs draw NOTHING from the chaos RNG: the
        replica-fault sequence of a seeded policy is byte-identical with
        the new parameters present and handoff_fault() interleaved."""
        def pattern(**kw):
            ch = ChaosPolicy(seed=11, transient_rate=0.3, hard_rate=0.1,
                             **kw)
            fn = ch.wrap(lambda: "ok")
            seq = []
            for _ in range(200):
                if kw:
                    assert ch.handoff_fault() is False
                try:
                    seq.append(fn() is not None)
                except TransientDispatchError:
                    seq.append("transient")
                except RuntimeError:
                    seq.append("hard")
            return seq

        assert pattern() == pattern(snapshot_corrupt_rate=0.0,
                                    handoff_stall_rate=0.0)


LONG_SHAPES = ((3, 8), (5, 9), (4, 10))


@pytest.mark.handoff
class TestFleetHandoff:
    def _factory(self, lm, **chaos_kw):
        def factory(rid):
            chaos = ChaosPolicy(seed=1000 + rid, **chaos_kw)
            return GenerationServer(lm, V, slots=4, page_size=4,
                                    snapshot_every=1, steps_per_dispatch=1,
                                    chaos=chaos)
        return factory

    def test_midstream_kill_resumes_from_snapshot(self, lm):
        """The headline failover: a replica dies under mid-stream
        requests; the fleet harvests each future's newest snapshot and
        the survivor resumes at position N — zero lost futures, every
        completion bit-exact, recompute saved on the handoff counters."""
        rng = np.random.default_rng(21)
        specs = mixed_specs(24, rng, shapes=LONG_SHAPES)
        refs = serial_refs(lm, specs)
        factory = self._factory(lm, stall_rate=1.0, stall_s=0.008)
        with fleet_of(factory, replicas=2, max_pending=64,
                      restart_backoff_s=0.02) as fl:
            futs = [submit_with_backoff(fl, sp) for sp in specs]
            wait_replica_midstream(fl, 0)    # streams mid-generation...
            fl.kill_replica(0)                # ...die under them
            outs = [f.result(timeout=600) for f in futs]
            st = fl.stats()
        assert len(outs) == 24
        for got, ref in zip(outs, refs):
            np.testing.assert_array_equal(np.asarray(got), ref)
        assert st["completed"] == 24
        assert st["failed"] == 0 and st["expired"] == 0
        assert st["deaths"] >= 1
        assert st["handoff_resumes"] >= 1, \
            "kill resumed nothing from snapshots"

    def test_corrupted_snapshots_fall_back_to_token0(self, lm):
        """snapshot_corrupt chaos poisons every published snapshot: the
        checksum gate rejects them at adoption, the fleet falls back to
        token-0 replay — still zero lost futures and bit-exact, with the
        fallbacks (not resumes) counter telling the story."""
        rng = np.random.default_rng(22)
        specs = mixed_specs(16, rng, shapes=LONG_SHAPES)
        refs = serial_refs(lm, specs)
        factory = self._factory(lm, stall_rate=1.0, stall_s=0.008,
                                snapshot_corrupt_rate=1.0)
        with fleet_of(factory, replicas=2, max_pending=64,
                      restart_backoff_s=0.02) as fl:
            futs = [submit_with_backoff(fl, sp) for sp in specs]
            wait_replica_midstream(fl, 0)
            fl.kill_replica(0)
            outs = [f.result(timeout=600) for f in futs]
            st = fl.stats()
        for got, ref in zip(outs, refs):
            np.testing.assert_array_equal(np.asarray(got), ref)
        assert st["completed"] == 16
        assert st["failed"] == 0 and st["expired"] == 0
        assert st["handoff_resumes"] == 0
        assert st["handoff_fallbacks"] >= 1, \
            "corrupted snapshots never hit the fallback path"

    def test_retire_migrate_hands_off_live_streams(self, lm):
        """retire_replica(migrate=True) drains by HANDING OFF: live
        slots snapshot at their exact position, requeue through the
        fleet, and finish on the survivor bit-exactly."""
        rng = np.random.default_rng(23)
        specs = [(rng.integers(1, V, size=4).astype(np.int64), 10,
                  0.0, 0, 0) for _ in range(16)]
        refs = serial_refs(lm, specs)
        factory = self._factory(lm, stall_rate=1.0, stall_s=0.01)
        with fleet_of(factory, replicas=2, max_pending=64,
                      restart_backoff_s=0.02) as fl:
            futs = [submit_with_backoff(fl, sp) for sp in specs]
            wait_replica_midstream(fl, 0, min_snapshots=2)
            assert fl.retire_replica(0, timeout=60.0, migrate=True)
            outs = [f.result(timeout=600) for f in futs]
            st = fl.stats()
        for got, ref in zip(outs, refs):
            np.testing.assert_array_equal(np.asarray(got), ref)
        assert st["completed"] == 16
        assert st["failed"] == 0 and st["expired"] == 0
        assert st["replicas"][0]["state"] == RETIRED
        assert st["handoff_resumes"] >= 1, "migration resumed nothing"


@pytest.mark.handoff
class TestServerMigrateAndPreempt:
    def test_drain_migrate_fails_typed_with_adoptable_snapshots(self, lm):
        """Plain-server drain(migrate=...): every live request fails
        typed with RequestMigrated, its snapshot rides both the sink
        callback and the future — and adopting it elsewhere completes
        the stream bit-exactly."""
        rs = np.random.RandomState(31)
        prompts = [rs.randint(1, V, 4) for _ in range(3)]
        refs = [greedy_generate(lm, p[None], 12, V)[0] for p in prompts]
        chaos = ChaosPolicy(seed=9, stall_rate=1.0, stall_s=0.02)
        collected = []
        with serving(lm, V, slots=4, page_size=4, steps_per_dispatch=1,
                     chaos=chaos) as src:
            futs = [src.submit(p, 12) for p in prompts]
            while src.stats()["active_slots"] < 3:
                time.sleep(0.005)             # wait until all prefilled
            src.drain(timeout=60.0, migrate=collected.append)
            st = src.stats()["handoff"]
        assert st["migrated"] == 3
        assert len(collected) == 3
        with serving(lm, V, slots=4, page_size=4) as dst:
            for fut, ref in zip(futs, refs):
                with pytest.raises(RequestMigrated):
                    fut.result(timeout=0)
                snap = fut._kv_snapshot
                assert snap.verify() and snap.count >= 1
                res = adopt_request(dst, snap).result(timeout=120)
                np.testing.assert_array_equal(np.asarray(res), ref)
            dst_st = dst.stats()["handoff"]
        assert dst_st["resumes"] == 3
        assert dst_st["tokens_saved"] == sum(
            f._kv_snapshot.count for f in futs)

    def test_preempt_snapshots_instead_of_discarding(self, lm):
        """Pool-pressure preemption keeps the decoded stream: the victim
        requeues WITH a snapshot, resumes via the adopt path when pages
        free up, and both requests still finish bit-exactly."""
        rs = np.random.RandomState(25)
        pa = rs.randint(1, V, 12)             # 3 pages of prompt each
        pb = rs.randint(1, V, 12)
        ra = greedy_generate(lm, pa[None], 10, V)[0]
        rb = greedy_generate(lm, pb[None], 10, V)[0]
        # each needs 6 pages end to end; 9 usable < 12 combined
        with serving(lm, V, slots=2, page_size=4, pages=10,
                     prefix_cache=False) as srv:
            fa = srv.submit(pa, 10)
            fb = srv.submit(pb, 10)
            np.testing.assert_array_equal(fa.result(timeout=180), ra)
            np.testing.assert_array_equal(fb.result(timeout=180), rb)
            st = srv.stats()
        assert st["pages"]["preempted"] >= 1
        assert st["handoff"]["preempt_resumes"] >= 1
        assert st["handoff"]["resumes"] >= 1
        assert st["handoff"]["tokens_saved"] >= srv._ps
        assert st["completed"] == 2 and st["failed"] == 0

    def test_no_recompile_on_adoption_churn(self):
        """Zero-retrace survives handoff: snapshotting compiles ONE
        gather program, adoption ONE scatter program — then repeated
        adoptions of fresh snapshots add ZERO compiled programs."""
        net = TransformerLM(num_labels=V, max_length=16, d_model=8,
                            n_heads=2, n_blocks=1, seed=9).init()
        specs = [GREEDY, SAMPLED,
                 (np.array([2, 5, 1, 3], np.int64), 12, 0.0, 0, 0)]
        snaps = []
        for sp in specs:
            out, snap = _run_to_snapshot(net, sp)
            snaps.append((snap, out))
        with serving(net, V, slots=2, page_size=4) as dst:
            res0 = adopt_request(dst, snaps[0][0]).result(timeout=120)
            np.testing.assert_array_equal(np.asarray(res0), snaps[0][1])
            warmed = len(net._output_cache)
            for snap, out in snaps[1:]:
                res = adopt_request(dst, snap).result(timeout=120)
                np.testing.assert_array_equal(np.asarray(res), out)
            assert len(net._output_cache) == warmed
