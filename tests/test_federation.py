"""Cross-host fleet federation tests (parallel/federation.py).

Covers the federation contract end to end on localhost sockets:
health-scored routing over N in-process FleetHosts with bit-exact
completions, typed shedding when no host can accept, heartbeat gossip
marking a host SUSPECT on missed beats BEFORE any TCP error surfaces,
host-down/heal cycles with degraded-mode entry and auto-clear,
drain-migrate across host boundaries, framed-RPC structural validation
(oversize and corrupt frames rejected typed on both sides), the
ChaosPolicy network fault modes with their legacy-sequence pinning, the
federated stats block, per-host metrics label injection — and the
headline drill: SIGKILL of an entire fleet-host *process* mid-stream
with bit-exact resumed completions via cross-host snapshot adoption and
a balanced federated ledger.

Tier split: the wire/chaos/shed tests are pure-Python-fast and ride
tier-1; every test that builds a real fleet (XLA compiles per host) or
spawns a host process is ALSO marked ``slow`` — tier-1 runs within ~2%
of its own 870 s timeout cap, so the drills run via ``-m federation``
(or the slow set) instead of inflating the default gate.
"""

import os
import socket
import time
from contextlib import contextmanager

import numpy as np
import pytest

from deeplearning4j_tpu.metrics.exposition import render_text
from deeplearning4j_tpu.parallel.elastic import Heartbeat
from deeplearning4j_tpu.parallel.federation import (
    DEAD, READY, SUSPECT, FederationProtocolError, FleetFederation,
    FleetHost, HostUnavailable, _read_msg, _send_msg,
    build_generation_fleet, spawn_host)
from deeplearning4j_tpu.parallel.resilience import (ChaosPolicy,
                                                    ResilienceError,
                                                    TransientDispatchError)
from deeplearning4j_tpu.streaming.broker import FrameTooLarge, read_frame
from tests.serving_helpers import mixed_specs, serial_refs, tiny_lm


@pytest.fixture(scope="module")
def lm():
    return tiny_lm(max_length=32)


def _mixed_specs(n, rng, steps=6):
    return mixed_specs(n, rng, shapes=((3, steps), (5, steps - 1),
                                       (4, steps + 1)))


def _submit_all(fed, specs, deadline_s=240.0):
    futs = []
    for p, steps, temp, top_k, seed in specs:
        while True:
            try:
                futs.append(fed.submit(p, steps, temperature=temp,
                                       top_k=top_k, seed=seed,
                                       deadline_s=deadline_s))
                break
            except ResilienceError:
                time.sleep(0.02)
    return futs


def _assert_ledger(fed):
    st = fed.stats()["federation"]
    assert st["submitted"] == (st["completed"] + st["failed"]
                               + st["expired"] + st["rejected_submits"]), st
    assert st["inflight"] == 0 and st["parked"] == 0, st
    return st


@contextmanager
def host_pair(hb_dir=None, hids=("h0", "h1"), **fleet_kw):
    """Two in-process FleetHosts over their own single-replica fleets —
    real localhost sockets, no subprocess."""
    fleet_kw.setdefault("replicas", 1)
    fleet_kw.setdefault("max_length", 32)
    fleets, hosts = [], []
    try:
        for hid in hids:
            fl = build_generation_fleet(**fleet_kw)
            hb = (os.path.join(hb_dir, f"{hid}.heartbeat")
                  if hb_dir else None)
            fleets.append(fl)
            hosts.append(FleetHost(fl, hid=hid, heartbeat_path=hb,
                                   heartbeat_interval=0.05))
        yield hosts
    finally:
        for h in hosts:
            h.close()
        for fl in fleets:
            fl.close()


def _wait(pred, timeout=60.0, tick=0.02, msg="condition"):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if pred():
            return
        time.sleep(tick)
    raise AssertionError(f"timed out waiting for {msg}")


# ---------------------------------------------------------------- routing

@pytest.mark.federation
class TestFederationRouting:
    @pytest.mark.slow
    def test_routing_bit_exact_and_balanced(self, lm):
        """Mixed greedy+sampled traffic over two hosts: every completion
        bit-exact vs serial, both hosts share the load, ledger balances.
        Rides the same federation to pin the stats-block contract and
        the synchronous validation errors (one host pair serves all
        three claims — fleet builds dominate this suite's runtime)."""
        rng = np.random.default_rng(0)
        specs = _mixed_specs(10, rng)
        refs = serial_refs(lm, specs)
        with host_pair() as hosts:
            with FleetFederation(hosts) as fed:
                st = fed.stats()
                assert list(st["federation"].keys()) == [
                    "hosts", "ready", "suspect", "deaths", "reconnects",
                    "submitted", "rejected_submits", "completed",
                    "failed", "expired", "redispatched", "migrated",
                    "handoff_resumes", "handoff_fallbacks", "snapshots",
                    "parked", "inflight", "degraded_mode"]
                assert st["federation"]["hosts"] == 2
                assert st["federation"]["ready"] == 2
                assert {b["hid"] for b in st["hosts"]} == {"h0", "h1"}
                with pytest.raises(ValueError):
                    fed.submit(np.array([[1, 2]]), 4)   # 2-D prompt
                with pytest.raises(ValueError):
                    fed.submit(np.array([1, 2]), 4, deadline_s=-1.0)
                futs = _submit_all(fed, specs)
                for fut, ref in zip(futs, refs):
                    got = fut.result(timeout=240)
                    assert np.array_equal(got, ref)
                st = _assert_ledger(fed)
                assert st["completed"] == 10
                per = {b["hid"]: b for b in fed.stats()["hosts"]}
                assert per["h0"]["dispatched"] > 0
                assert per["h1"]["dispatched"] > 0

    def test_submit_sheds_typed_when_no_host(self):
        """A federation whose only endpoint refuses connections sheds
        typed at submit — and the shed request counts rejected, keeping
        the ledger balanced."""
        probe = socket.socket()
        probe.bind(("127.0.0.1", 0))
        dead_port = probe.getsockname()[1]
        probe.close()
        fed = FleetFederation([("h0", dead_port)],
                              reconnect_backoff_s=10.0)
        try:
            with pytest.raises(HostUnavailable):
                fed.submit(np.array([1, 2, 3]), 4)
            st = fed.stats()["federation"]
            assert st["rejected_submits"] == 1
            _assert_ledger(fed)
        finally:
            fed.close()


# ----------------------------------------------------------------- gossip

@pytest.mark.federation
@pytest.mark.slow
class TestFederationGossip:
    def test_heartbeat_suspect_before_tcp_error(self, tmp_path, lm):
        """The ISSUE headline gossip drill: a host whose heartbeat goes
        stale (wedged process — sockets still open, NO TCP error ever
        fires) is marked SUSPECT and routed around; when beats resume it
        auto-clears back to READY."""
        hb = str(tmp_path)
        with host_pair(hb_dir=hb) as hosts:
            h1 = hosts[1]
            with FleetFederation(hosts, heartbeat_dir=hb,
                                 suspect_after_s=0.3, dead_after_s=600.0,
                                 gossip_tick_s=0.03) as fed:
                _wait(lambda: fed.stats()["federation"]["ready"] == 2,
                      msg="both hosts READY")
                h1.heartbeat.stop()   # the 'wedge': beats stop, sockets live
                _wait(lambda: fed.stats()["federation"]["suspect"] == 1,
                      msg="h1 SUSPECT on missed beats")
                st = fed.stats()
                assert st["federation"]["deaths"] == 0   # no TCP error
                per = {b["hid"]: b for b in st["hosts"]}
                assert per["h1"]["state"] == SUSPECT
                assert per["h1"]["suspect_reason"] == "heartbeat"
                # traffic routes around the suspect host
                before = per["h1"]["dispatched"]
                specs = _mixed_specs(2, np.random.default_rng(1))
                for fut, ref in zip(_submit_all(fed, specs),
                                    serial_refs(lm, specs)):
                    assert np.array_equal(fut.result(timeout=240), ref)
                per = {b["hid"]: b for b in fed.stats()["hosts"]}
                assert per["h1"]["dispatched"] == before
                assert per["h0"]["dispatched"] >= 2
                # beats resume -> auto-clear, no reconnect needed
                h1.heartbeat = Heartbeat(h1.heartbeat.path,
                                         interval=0.05).start()
                _wait(lambda: fed.stats()["federation"]["suspect"] == 0,
                      msg="h1 recovered on fresh beats")
                assert fed.stats()["federation"]["deaths"] == 0
                _assert_ledger(fed)

    def test_host_down_heal_and_degraded_mode(self, lm):
        """In-process whole-host death: the federation enters degraded
        mode (gauge + typed transition, fleet-style), serves everything
        on the survivor, then auto-clears when a replacement host comes
        up on the same endpoint and the reconnect loop heals the link —
        the same path a healed network partition takes."""
        rng = np.random.default_rng(2)
        fl_new = None
        h_new = None
        with host_pair() as hosts:
            h0, h1 = hosts
            with FleetFederation(hosts, reconnect_backoff_s=0.05,
                                 gossip_tick_s=0.03) as fed:
                try:
                    port1 = h1.port
                    h1.kill()
                    _wait(lambda: fed.stats()["federation"]["degraded_mode"],
                          msg="degraded mode entered")
                    gauge = {g["name"]: g for g in
                             fed.metrics._snapshot_families()}
                    assert gauge["fed_degraded_mode"]["samples"][0][1] == 1.0
                    specs = _mixed_specs(2, rng)
                    for fut, ref in zip(_submit_all(fed, specs),
                                        serial_refs(lm, specs)):
                        assert np.array_equal(fut.result(timeout=240), ref)
                    per = {b["hid"]: b for b in fed.stats()["hosts"]}
                    assert per["h0"]["completed"] >= 2
                    # replacement host on the SAME endpoint: the
                    # reconnect loop heals without operator action
                    fl_new = build_generation_fleet(replicas=1,
                                                    max_length=32)
                    h_new = FleetHost(fl_new, hid="h1", port=port1)
                    _wait(lambda: not
                          fed.stats()["federation"]["degraded_mode"],
                          msg="degraded mode cleared on heal")
                    st = fed.stats()["federation"]
                    assert st["reconnects"] >= 1 and st["deaths"] >= 1
                    _assert_ledger(fed)
                finally:
                    if h_new is not None:
                        h_new.close()
                    if fl_new is not None:
                        fl_new.close()

    def test_drain_migrate_across_hosts(self, lm):
        """retire_host(migrate=True) hands a host's in-flight work back
        to the router as RequestMigrated (+ newest snapshots) and the
        requests finish bit-exact on the surviving host."""
        rng = np.random.default_rng(3)
        specs = _mixed_specs(4, rng, steps=14)
        refs = serial_refs(lm, specs)
        with host_pair(snapshot_every=1, steps_per_dispatch=1,
                       chaos={"stall_rate": 1.0, "stall_s": 0.01}) as hosts:
            with FleetFederation(hosts, gossip_tick_s=0.03) as fed:
                futs = _submit_all(fed, specs)
                _wait(lambda: any(b["inflight"] > 0 and b["hid"] == "h0"
                                  for b in fed.stats()["hosts"]),
                      msg="h0 has in-flight work")
                assert fed.retire_host("h0", migrate=True, timeout=30)
                for fut, ref in zip(futs, refs):
                    assert np.array_equal(fut.result(timeout=240), ref)
                st = _assert_ledger(fed)
                assert st["migrated"] >= 1
                per = {b["hid"]: b for b in fed.stats()["hosts"]}
                assert per["h0"]["state"] == "retired"


# ------------------------------------------------------------ crash drill

@pytest.mark.federation
@pytest.mark.slow
class TestFederationCrash:
    def test_sigkill_whole_process_bit_exact(self, tmp_path, lm):
        """The acceptance drill, as a test: two fleet-host *processes*
        behind one router; SIGKILL one mid-stream once the router holds
        published snapshots; every completion bit-exact (cross-host
        snapshot adoption for the victims), zero lost futures, balanced
        federated ledger, handoff_resumes counted."""
        hb = str(tmp_path)
        spec = {"platform": "cpu", "heartbeat_dir": hb,
                "heartbeat_interval": 0.05,
                "builder_kwargs": {
                    "replicas": 1, "snapshot_every": 1, "max_length": 32,
                    "steps_per_dispatch": 1,
                    "chaos": {"stall_rate": 1.0, "stall_s": 0.02}}}
        hh0 = spawn_host(dict(spec, hid="h0"))
        hh1 = spawn_host(dict(spec, hid="h1"))
        fed = None
        try:
            fed = FleetFederation([hh0, hh1], heartbeat_dir=hb,
                                  suspect_after_s=0.5, dead_after_s=600.0)
            rng = np.random.default_rng(4)
            specs = _mixed_specs(6, rng, steps=20)
            refs = serial_refs(lm, specs)
            futs = _submit_all(fed, specs)
            _wait(lambda: fed.stats()["federation"]["snapshots"] >= 2,
                  timeout=120, msg="router holds published snapshots")
            hh1.kill()          # SIGKILL: no flush, no goodbye
            assert not hh1.alive
            for fut, ref in zip(futs, refs):
                got = fut.result(timeout=240)
                assert np.array_equal(got, ref)
            st = _assert_ledger(fed)
            assert st["completed"] == 6
            assert st["deaths"] >= 1
            assert st["handoff_resumes"] >= 1
            assert st["degraded_mode"] is True
        finally:
            if fed is not None:
                fed.close()
            hh0.terminate()
            if hh1.alive:
                hh1.kill()


# ------------------------------------------------------------ wire safety

@pytest.mark.federation
class TestFederationWire:
    def _pair(self):
        a, b = socket.socketpair()
        return a, b

    def test_read_msg_roundtrip(self):
        a, b = self._pair()
        try:
            _send_msg(a, {"op": "stats", "id": 7}, b"payload")
            hdr, blob = _read_msg(b)
            assert hdr == {"op": "stats", "id": 7} and blob == b"payload"
        finally:
            a.close(); b.close()

    def test_read_msg_rejects_oversize_typed(self):
        """A length header above the cap is rejected typed BEFORE any
        allocation — the poisoned-length defense, federation side."""
        a, b = self._pair()
        try:
            a.sendall((1 << 30).to_bytes(4, "big"))
            with pytest.raises(FrameTooLarge):
                _read_msg(b, max_frame_bytes=1 << 20)
        finally:
            a.close(); b.close()

    def test_broker_read_frame_rejects_oversize_typed(self):
        """Same discipline on the streaming broker's framed reader."""
        a, b = self._pair()
        try:
            # op(1) topic_len(2) topic payload_len(4): oversize payload
            import struct as _s
            a.sendall(_s.pack(">cH", b"P", 1) + b"t"
                      + _s.pack(">I", 1 << 29))
            with pytest.raises(FrameTooLarge):
                read_frame(b, max_frame_bytes=1 << 20)
        finally:
            a.close(); b.close()

    def test_corrupt_header_rejected_typed(self):
        a, b = self._pair()
        try:
            hdr = b"\x00\x00\x00\x10" + b"not json at all!"
            a.sendall((len(hdr)).to_bytes(4, "big") + hdr)
            with pytest.raises(FederationProtocolError):
                _read_msg(b)
        finally:
            a.close(); b.close()

    def test_chaos_corrupt_draw_breaks_frame_typed(self):
        """A frame_corrupt_rate draw mangles the frame in flight; the
        receiver rejects it typed (FederationProtocolError), never
        crashes, never mis-parses."""
        a, b = self._pair()
        try:
            ch = ChaosPolicy(seed=3, frame_corrupt_rate=1.0)
            _send_msg(a, {"op": "stats", "id": 1}, chaos=ch)
            assert ch.injected_frame_corrupt == 1
            with pytest.raises(FederationProtocolError):
                _read_msg(b)
        finally:
            a.close(); b.close()

    def test_host_answers_protocol_error_and_closes(self):
        """A FleetHost that receives a structurally invalid frame
        answers with a typed protocol_error frame and drops the
        connection — the stream can no longer be trusted. The fleet is
        a bare stub: the corrupt frame is rejected before any op could
        dispatch into it (and a real fleet build costs seconds)."""
        from deeplearning4j_tpu.metrics.registry import MetricsRegistry
        host = FleetHost(object(), hid="hx", registry=MetricsRegistry())
        try:
            s = socket.create_connection(("127.0.0.1", host.port),
                                         timeout=10)
            hdr = b"\xff\xff\xff\xf0" + b"x" * 12   # header_len overrun
            s.sendall((len(hdr)).to_bytes(4, "big") + hdr)
            reply = _read_msg(s)
            assert reply is not None
            assert reply[0]["op"] == "protocol_error"
            assert reply[0]["etype"] == "FederationProtocolError"
            assert _read_msg(s) is None   # connection closed after
            s.close()
        finally:
            host.close()


# ----------------------------------------------------------- chaos modes

@pytest.mark.federation
def test_host_spec_must_state_its_platform():
    """No default platform: a host that silently came up on another
    backend than its router expects would still answer, with other
    floats. Rejected before any process is spawned."""
    with pytest.raises(ValueError, match="must state 'platform'"):
        spawn_host({"hid": "h0"})


@pytest.mark.federation
class TestFederationChaos:
    def test_network_faults_deterministic(self):
        def run():
            sleeps = []
            ch = ChaosPolicy(seed=9, conn_refused_rate=0.3,
                             partition_rate=0.2, partition_s=0.0,
                             frame_corrupt_rate=0.2,
                             sleep=sleeps.append)
            seq = []
            for _ in range(120):
                try:
                    ch.net_connect_fault()
                    seq.append("ok")
                except ConnectionRefusedError:
                    seq.append("refused")
                seq.append(ch.net_fault_mode(64))
            return seq, ch

        s1, c1 = run()
        s2, c2 = run()
        assert s1 == s2
        assert c1.injected_conn_refused == c2.injected_conn_refused > 0
        assert c1.injected_partition == c2.injected_partition > 0
        assert c1.injected_frame_corrupt == c2.injected_frame_corrupt > 0

    def test_partition_window_and_slow_link(self):
        sleeps = []
        ch = ChaosPolicy(seed=1, partition_rate=1.0, partition_s=30.0,
                         sleep=sleeps.append)
        assert not ch.net_partitioned()
        assert ch.net_fault_mode(100) == "partition"
        assert ch.net_partitioned()   # window armed
        a, b = socket.socketpair()
        try:
            with pytest.raises(OSError):
                _send_msg(a, {"op": "stats"}, chaos=ch)
        finally:
            a.close(); b.close()
        slow = ChaosPolicy(seed=1, slow_link_factor=3.0,
                           sleep=sleeps.append)
        assert slow.net_fault_mode(ChaosPolicy.LINK_BYTES_PER_S) is None
        assert slow.injected_slow_link == 1
        assert sleeps and abs(sleeps[-1] - 2.0) < 1e-9

    def test_legacy_sequences_pinned(self):
        """Zero-rate network knobs draw NOTHING from the chaos RNG: a
        seeded policy's replica-fault sequence is byte-identical with
        the new parameters present and the net hooks interleaved."""
        def pattern(**kw):
            ch = ChaosPolicy(seed=11, transient_rate=0.3, hard_rate=0.1,
                             **kw)
            fn = ch.wrap(lambda: "ok")
            seq = []
            for _ in range(200):
                if kw:
                    ch.net_connect_fault()          # rate 0: no draw
                    assert ch.net_fault_mode(64) is None
                    assert not ch.net_partitioned()
                try:
                    seq.append(fn() is not None)
                except TransientDispatchError:
                    seq.append("transient")
                except RuntimeError:
                    seq.append("hard")
            return seq

        assert pattern() == pattern(conn_refused_rate=0.0,
                                    partition_rate=0.0, partition_s=5.0,
                                    slow_link_factor=1.0,
                                    frame_corrupt_rate=0.0)


# -------------------------------------------------------------- metrics

@pytest.mark.federation
@pytest.mark.metrics
@pytest.mark.slow
class TestFederationMetrics:
    def test_one_scrape_shows_every_host(self, lm):
        """metrics_sources() exposes the router registry plus each
        host's last gossiped families under an injected host= label, so
        a single exposition page covers the whole federation — and
        KerasBackendServer.metrics_text composes model= on top of
        host= for a federated target (same pair, one fleet build)."""
        from deeplearning4j_tpu.modelimport.server import \
            KerasBackendServer
        with host_pair() as hosts:
            with FleetFederation(hosts, stats_every_s=0.05,
                                 gossip_tick_s=0.03) as fed:
                specs = _mixed_specs(4, np.random.default_rng(5))
                for fut in _submit_all(fed, specs):
                    fut.result(timeout=240)
                _wait(lambda: len(fed.metrics_sources()) == 3,
                      msg="both hosts gossiped families")
                text = render_text(fed.metrics_sources())
                assert "fed_submitted_total 4" in text
                assert 'fleet_submitted_total{host="h0"}' in text
                assert 'fleet_submitted_total{host="h1"}' in text
                assert "fed_degraded_mode 0" in text
                srv = KerasBackendServer()
                with srv._lock:
                    srv._generators["m0"] = fed
                text = srv.metrics_text()
                assert 'fed_submitted_total{model="m0"} 4' in text
                assert ('fleet_submitted_total{model="m0",host="h0"}'
                        in text)
