"""ReplicaFleet serving tests (parallel/fleet.py).

Covers the fleet contract end to end on the CPU mesh: health-weighted
routing over N replicas, typed load shedding at submit
(ReplicaUnavailable / CircuitOpen / ServerOverloaded), failover
re-dispatch with bit-exact deterministic regeneration (the fold_in key
schedule makes a re-dispatched generation identical on any replica),
supervised restart with backoff after replica death, request hedging
(first-result-wins, loser cancelled), the replica-targeted ChaosPolicy
fault modes, the KerasBackendServer fleet wiring, and the headline chaos
soak: 200 mixed greedy+sampled requests at ~10% injected replica faults
including a mid-generation kill — zero lost futures, every completion
bit-exact vs serial.
"""

import threading
import time

import numpy as np
import pytest

from deeplearning4j_tpu.models.zoo import greedy_generate
from deeplearning4j_tpu.parallel.fleet import (DEAD, READY, RETIRED,
                                               ReplicaFleet)
from deeplearning4j_tpu.parallel.generation import GenerationServer
from deeplearning4j_tpu.parallel.inference import ParallelInference
from deeplearning4j_tpu.parallel.resilience import (ChaosPolicy,
                                                    CircuitOpen,
                                                    DeadlineExceeded,
                                                    ReplicaKilled,
                                                    ReplicaUnavailable,
                                                    ServerOverloaded,
                                                    TransientDispatchError)
from tests.serving_helpers import (V, fleet_of, mixed_specs,
                                   serial_refs, submit_with_backoff)


def _gen_factory(lm, **chaos_kw):
    """Factory of GenerationServer replicas; chaos_kw seeds each replica's
    own deterministic fault injector (seed derived from the rid)."""
    def factory(rid):
        chaos = (ChaosPolicy(seed=1000 + rid, **chaos_kw)
                 if chaos_kw else None)
        return GenerationServer(lm, V, slots=4, chaos=chaos)
    return factory


@pytest.mark.fleet
class TestChaosPolicyReplicaModes:
    def test_modes_deterministic_and_exclusive(self):
        """Same seed -> same injected fault sequence; at most one
        replica-targeted fault per call."""
        def run():
            sleeps = []
            ch = ChaosPolicy(seed=7, kill_rate=0.1, stall_rate=0.2,
                             stall_s=0.5, slow_rate=0.2, slow_factor=3.0,
                             sleep=sleeps.append)
            fn = ch.wrap(lambda: "ok")
            outcomes = []
            for _ in range(200):
                try:
                    outcomes.append(fn() is not None)
                except ReplicaKilled:
                    outcomes.append("killed")
            return outcomes, sleeps, ch

        o1, s1, c1 = run()
        o2, s2, c2 = run()
        assert o1 == o2                       # same fault sequence
        assert len(s1) == len(s2)             # same injection points
        # stall sleeps are the fixed duration; slow-mode pads scale with
        # the measured run time and are timing-dependent by design
        assert [v for v in s1 if v == 0.5] == [v for v in s2 if v == 0.5]
        assert c1.injected_kill == c2.injected_kill > 0
        assert c1.injected_stall == c2.injected_stall > 0
        assert c1.injected_slow == c2.injected_slow > 0
        assert (c1.injected_kill + c1.injected_stall + c1.injected_slow
                <= 200)

    def test_legacy_sequences_unchanged(self):
        """With the replica rates at zero, the rng draw sequence is the
        pre-extension one: same seed reproduces the same transient/hard
        pattern as before the replica modes existed."""
        def pattern(**kw):
            ch = ChaosPolicy(seed=11, transient_rate=0.3, hard_rate=0.1,
                             **kw)
            fn = ch.wrap(lambda: 0)
            out = []
            for _ in range(100):
                try:
                    fn()
                    out.append("ok")
                except TransientDispatchError:
                    out.append("t")
                except RuntimeError:
                    out.append("h")
            return out

        assert pattern() == pattern(kill_rate=0.0, stall_rate=0.0,
                                    slow_rate=0.0)

    def test_slow_mode_runs_fn_then_pads(self):
        calls = []
        sleeps = []
        ch = ChaosPolicy(seed=0, slow_rate=1.0, slow_factor=4.0,
                         sleep=sleeps.append)
        fn = ch.wrap(lambda: calls.append(1) or 42)
        assert fn() == 42
        assert calls == [1]          # slow mode still runs the dispatch
        assert len(sleeps) == 1      # ... then pads it out
        assert ch.injected_slow == 1


@pytest.mark.fleet
class TestFleetRouting:
    def test_routes_spread_and_results_bitexact(self, lm):
        rng = np.random.default_rng(5)
        specs = mixed_specs(12, rng)
        refs = serial_refs(lm, specs)
        with fleet_of(_gen_factory(lm), replicas=2) as fl:
            futs = [fl.submit(p, s, temperature=t, top_k=k, seed=sd,
                              deadline_s=120.0)
                    for p, s, t, k, sd in specs]
            outs = [f.result(timeout=180) for f in futs]
            st = fl.stats()
        for got, ref in zip(outs, refs):
            np.testing.assert_array_equal(np.asarray(got), ref)
        assert st["completed"] == len(specs)
        assert st["failed"] == 0 and st["expired"] == 0
        # both replicas took traffic (least-loaded routing spreads a burst)
        assert all(r["dispatched"] > 0 for r in st["replicas"])

    def test_sick_replica_sheds_into_healthy_one(self, lm):
        """A replica that fails every dispatch trips its breaker; traffic
        re-dispatches to the survivor and every completion stays correct."""
        def factory(rid):
            chaos = (ChaosPolicy(seed=9, hard_rate=1.0) if rid == 0
                     else None)
            return GenerationServer(lm, V, slots=4, chaos=chaos)

        rng = np.random.default_rng(6)
        specs = mixed_specs(8, rng)
        refs = serial_refs(lm, specs)
        with fleet_of(factory, replicas=2) as fl:
            futs = [submit_with_backoff(fl, sp) for sp in specs]
            outs = [f.result(timeout=180) for f in futs]
            st = fl.stats()
        for got, ref in zip(outs, refs):
            np.testing.assert_array_equal(np.asarray(got), ref)
        sick = st["replicas"][0]
        assert sick["failed"] > 0
        assert st["redispatched"] > 0
        assert st["completed"] == len(specs)

    def test_submit_sheds_typed_when_everything_is_down(self, lm):
        with fleet_of(_gen_factory(lm), replicas=2, restart=False) as fl:
            fl.kill_replica(0)
            fl.kill_replica(1)
            deadline = time.monotonic() + 30.0
            with pytest.raises(ReplicaUnavailable):
                while time.monotonic() < deadline:
                    # the kill is async (monitor closes the corpse): poll
                    # until both replicas report dead, then submit
                    st = fl.stats()
                    if all(r["state"] != READY for r in st["replicas"]):
                        fl.submit(np.array([1, 2], np.int64), 2)
                        break
                    time.sleep(0.01)

    def test_validation_error_propagates_sync(self, lm):
        with fleet_of(_gen_factory(lm), replicas=2) as fl:
            with pytest.raises(ValueError):
                fl.submit(np.array([1, 2], np.int64), 2, deadline_s=-1.0)
            with pytest.raises(ValueError):
                # empty prompt: server-side caller-error validation
                fl.submit(np.array([], np.int64), 2)
            with pytest.raises(ServerOverloaded):
                # infeasible page budget rejects typed on every replica
                fl.submit(np.array([1, 2], np.int64), 10_000)
            st = fl.stats()
        assert st["inflight"] == 0 and fl.admission.pending == 0
        # sync rejections (caller error + typed shed) never count as
        # failures — they land in rejected_submits
        assert st["rejected_submits"] == 2 and st["failed"] == 0


@pytest.mark.fleet
class TestFleetLifecycle:
    def test_kill_restarts_with_counters(self, lm):
        rng = np.random.default_rng(7)
        specs = mixed_specs(10, rng)
        refs = serial_refs(lm, specs)
        with fleet_of(_gen_factory(lm), replicas=2,
                      restart_backoff_s=0.02) as fl:
            futs = [fl.submit(p, s, temperature=t, top_k=k, seed=sd,
                              deadline_s=180.0)
                    for p, s, t, k, sd in specs]
            time.sleep(0.2)           # let generation get going
            assert fl.kill_replica(0)
            outs = [f.result(timeout=240) for f in futs]
            deadline = time.monotonic() + 60.0
            while time.monotonic() < deadline:
                st = fl.stats()
                if st["replicas"][0]["state"] == READY \
                        and st["replicas"][0]["restarts"] >= 1:
                    break
                time.sleep(0.02)
        for got, ref in zip(outs, refs):
            np.testing.assert_array_equal(np.asarray(got), ref)
        assert st["deaths"] >= 1
        assert st["restarts"] >= 1
        assert st["replicas"][0]["restarts"] >= 1

    def test_retire_drains_for_good(self, lm):
        with fleet_of(_gen_factory(lm), replicas=2) as fl:
            assert fl.retire_replica(0)
            st = fl.stats()
            assert st["replicas"][0]["state"] == RETIRED
            # retired replicas never restart; the survivor still serves
            out = fl.submit(np.array([1, 2, 3], np.int64), 3).result(
                timeout=120)
            assert len(out) == 3
            st = fl.stats()
            assert st["replicas"][0]["state"] == RETIRED
            assert st["replicas"][1]["dispatched"] >= 1

    def test_close_never_leaves_hung_futures(self, lm):
        fl = ReplicaFleet(_gen_factory(lm), replicas=2)
        futs = [fl.submit(np.array([1, 2, 3], np.int64), 4)
                for _ in range(6)]
        fl.close(timeout=120.0)
        done = [f for f in futs if f.done()]
        assert len(done) == len(futs)       # zero lost futures at close
        fl.close()                          # idempotent

    def test_spawn_failure_backs_off_exponentially(self):
        calls = []

        class _Dud:
            def close(self, timeout=0.0):
                pass

            def submit(self, *a, **k):
                raise ReplicaKilled("dud replica")

            def drain(self, timeout=None):
                return True

            def stats(self):
                return {}

        def factory(rid):
            calls.append(time.monotonic())
            if len(calls) >= 4:
                return _Dud()
            if len(calls) > 1:
                raise RuntimeError("spawn flake")
            return _Dud()

        fl = ReplicaFleet(factory, replicas=1, restart_backoff_s=0.02,
                          restart_backoff_cap_s=0.08)
        try:
            fl.kill_replica(0)
            deadline = time.monotonic() + 30.0
            while time.monotonic() < deadline:
                st = fl.stats()
                if st["replicas"][0]["state"] == READY \
                        and st["replicas"][0]["restarts"] >= 1:
                    break
                time.sleep(0.01)
            st = fl.stats()
            assert st["replicas"][0]["spawn_failures"] >= 2
            assert st["replicas"][0]["restarts"] >= 1
        finally:
            fl.close()


@pytest.mark.fleet
class TestFleetHedging:
    def test_straggler_hedged_first_result_wins(self, lm):
        """Replica 0 stalls every dispatch; with hedging on, parked tail
        requests duplicate onto the healthy replica and finish fast."""
        def factory(rid):
            chaos = (ChaosPolicy(seed=3, stall_rate=1.0, stall_s=0.25)
                     if rid == 0 else None)
            return GenerationServer(lm, V, slots=4, chaos=chaos)

        rng = np.random.default_rng(8)
        specs = mixed_specs(6, rng)
        refs = serial_refs(lm, specs)
        with fleet_of(factory, replicas=2, hedge_after_s=0.15,
                      max_hedges=1) as fl:
            futs = [fl.submit(p, s, temperature=t, top_k=k, seed=sd,
                              deadline_s=180.0)
                    for p, s, t, k, sd in specs]
            outs = [f.result(timeout=240) for f in futs]
            st = fl.stats()
        for got, ref in zip(outs, refs):
            np.testing.assert_array_equal(np.asarray(got), ref)
        assert st["completed"] == len(specs)
        # the stalled replica forced at least one hedge; the duplicate's
        # loser was cancelled, not leaked
        assert st["hedged"] >= 1
        assert st["losers_cancelled"] >= 1


@pytest.mark.fleet
class TestFleetOverParallelInference:
    def test_failover_and_bitexact_rows(self):
        from tests.test_inference_server import _features, _mln

        net = _mln()
        x = _features(24, seed=11)
        ref = np.asarray(net.output(x))

        def factory(rid):
            chaos = ChaosPolicy(seed=50 + rid, stall_rate=0.1,
                                stall_s=0.01)
            return ParallelInference(net, workers=8, max_batch=8,
                                     max_wait_ms=1.0, chaos=chaos)

        with fleet_of(factory, replicas=2, restart_backoff_s=0.02) as fl:
            futs = [fl.submit(x[i:i + 1], deadline_s=60.0)
                    for i in range(12)]
            fl.kill_replica(0)
            futs += [fl.submit(x[i:i + 1], deadline_s=60.0)
                     for i in range(12, 24)]
            outs = [np.asarray(f.result(timeout=120))[0] for f in futs]
            st = fl.stats()
        # Rows are served in coalesced batches of 1-8, the reference is ONE
        # net.output on the batch of 24: two XLA programs, and XLA promises
        # no bit equality between programs (measured gap: 1 ulp in one
        # element of three). What failover must keep is the row, so every
        # row equals its reference to a few float32 ulps. The
        # GenerationServer tests, where both sides run the SAME program,
        # stay exact.
        for i, row in enumerate(outs):
            np.testing.assert_array_max_ulp(row, ref[i], maxulp=4)
        assert st["completed"] == 24
        assert st["deaths"] >= 1


@pytest.mark.fleet
class TestKerasBackendServerFleet:
    def test_generate_predict_and_stats_through_fleet(self, lm):
        import json
        from urllib.request import Request, urlopen

        from tests.test_inference_server import _features, _mln
        from deeplearning4j_tpu.modelimport.server import KerasBackendServer

        net = _mln()
        x = _features(4, seed=12)
        ref = np.asarray(net.output(x))
        gref = greedy_generate(lm, np.array([[1, 2, 3]], np.int64), 4, V)[0]

        srv = KerasBackendServer()
        try:
            gmid = srv.attach_generation(lm, vocab=V, slots=4, replicas=2)
            pmid = srv.attach_inference(net, replicas=2,
                                        max_batch=8, max_wait_ms=1.0)
            port = srv.start()

            def post(path, body):
                req = Request(f"http://127.0.0.1:{port}{path}",
                              data=json.dumps(body).encode(),
                              headers={"Content-Type": "application/json"})
                with urlopen(req, timeout=120) as r:
                    return json.loads(r.read())

            out = post("/generate", {"model": gmid,
                                     "prompt_ids": [1, 2, 3],
                                     "max_tokens": 4})
            np.testing.assert_array_equal(np.asarray(out["tokens"]), gref)

            out = post("/predict", {"model": pmid,
                                    "features": x.tolist()})
            np.testing.assert_allclose(np.asarray(out["output"]), ref,
                                       rtol=1e-6, atol=1e-6)

            with urlopen(f"http://127.0.0.1:{port}/stats",
                         timeout=60) as r:
                st = json.loads(r.read())
            for block in (st["generation"][gmid], st["inference"][pmid]):
                reps = block["replicas"]
                assert len(reps) == 2
                for rep in reps:
                    assert {"health_score", "breaker", "inflight",
                            "restarts", "state"} <= set(rep)
        finally:
            srv.stop()

    def test_all_replicas_down_maps_to_503(self, lm):
        import json
        from urllib.error import HTTPError
        from urllib.request import Request, urlopen

        from deeplearning4j_tpu.modelimport.server import KerasBackendServer

        srv = KerasBackendServer()
        try:
            gmid = srv.attach_generation(lm, vocab=V, slots=4, replicas=2,
                                         fleet_kw={"restart": False})
            port = srv.start()
            gen = srv._generators[gmid]
            gen.kill_replica(0)
            gen.kill_replica(1)
            deadline = time.monotonic() + 30.0
            while time.monotonic() < deadline:
                if all(r["state"] != READY
                       for r in gen.stats()["replicas"]):
                    break
                time.sleep(0.01)
            req = Request(f"http://127.0.0.1:{port}/generate",
                          data=json.dumps({
                              "model": gmid, "prompt_ids": [1, 2],
                              "max_tokens": 2}).encode(),
                          headers={"Content-Type": "application/json"})
            with pytest.raises(HTTPError) as ei:
                urlopen(req, timeout=60)
            assert ei.value.code == 503
            body = json.loads(ei.value.read())
            assert body["type"] in ("ReplicaUnavailable", "CircuitOpen")
        finally:
            srv.stop()


@pytest.mark.fleet
class TestGenerationFailAllCloseRace:
    """Satellite regression: a chaos kill racing close() must not rebuild
    the device pools on a server that is already shutting down."""

    def test_fail_all_after_close_skips_rebuild(self, lm):
        srv = GenerationServer(lm, V, slots=2)
        srv.submit(np.array([1, 2], np.int64), 2).result(timeout=120)
        srv.close()
        pool_before = srv._pool
        page_pool_before = srv._page_pool
        srv._fail_all(RuntimeError("late chaos fault"))
        assert srv._pool is pool_before          # no resurrection
        assert srv._page_pool is page_pool_before
        assert srv.stats()["pool_rebuilds"] == 0

    def test_chaos_kill_racing_close_resolves_everything(self, lm):
        chaos = ChaosPolicy(seed=13, kill_rate=0.25)
        srv = GenerationServer(lm, V, slots=4, chaos=chaos)
        futs = [srv.submit(np.array([1, 2, 3], np.int64), 5)
                for _ in range(8)]
        closer = threading.Thread(target=srv.close, kwargs={"timeout": 60})
        closer.start()
        for f in futs:
            try:
                f.result(timeout=120)
            except Exception:
                pass                              # typed failure is fine
        closer.join(timeout=120)
        assert not closer.is_alive()
        assert all(f.done() for f in futs)        # zero hung futures
        assert srv._runtime.alive_workers == 0    # loop truly stopped

    def test_fail_all_still_rebuilds_on_live_server(self, lm):
        """Complement of the guard: on a server that is NOT shutting
        down, a hard fault still rebuilds the pools and later requests
        keep serving from the fresh state."""
        srv = GenerationServer(lm, V, slots=2)
        try:
            srv.submit(np.array([1, 2], np.int64), 2).result(timeout=120)
            srv._fail_all(RuntimeError("injected hard fault"))
            assert srv.stats()["pool_rebuilds"] == 1
            out = srv.submit(np.array([1, 2, 3], np.int64),
                             3).result(timeout=120)
            assert len(out) == 3
        finally:
            srv.close()


@pytest.mark.fleet
class TestFleetChaosSoak:
    def test_soak_200_mixed_requests_zero_lost_bitexact(self, lm):
        """The headline invariant: 200 mixed greedy+sampled requests at
        ~10% injected replica faults (transient, stall, slow-decode, and
        seeded kills) plus one guaranteed mid-generation replica kill —
        zero lost futures, every completion bit-exact vs the serial
        reference, and the breaker/restart counters consistent."""
        rng = np.random.default_rng(42)
        specs = mixed_specs(200, rng)
        refs = serial_refs(lm, specs)
        factory = _gen_factory(lm, transient_rate=0.04, kill_rate=0.015,
                               stall_rate=0.02, stall_s=0.005,
                               slow_rate=0.025, slow_factor=2.0)
        with fleet_of(factory, replicas=2, max_pending=256,
                      restart_backoff_s=0.02) as fl:
            futs = []
            for i, sp in enumerate(specs):
                futs.append(submit_with_backoff(fl, sp))
                if i == 60:
                    time.sleep(0.05)          # requests mid-generation...
                    fl.kill_replica(0)        # ...then kill under them
            outs = [f.result(timeout=600) for f in futs]
            st = fl.stats()

        # zero lost futures: every single request resolved with a result
        assert len(outs) == 200
        for got, ref in zip(outs, refs):
            np.testing.assert_array_equal(np.asarray(got), ref)

        # counters consistent: every accepted request completed exactly
        # once; typed sheds the client retried count as rejected_submits,
        # never as failed/expired — zero lost futures
        assert st["completed"] == 200
        assert st["submitted"] == (st["completed"] + st["failed"]
                                   + st["expired"] + st["rejected_submits"])
        assert st["failed"] == 0 and st["expired"] == 0
        assert st["inflight"] == 0 and st["parked"] == 0
        # the explicit kill (plus any seeded ones) died and restarted
        assert st["deaths"] >= 1
        assert st["restarts"] >= 1
        per = st["replicas"]
        assert sum(r["restarts"] for r in per) == st["restarts"]
        # each fleet completion had >= 1 successful replica attempt (a
        # cancelled hedge loser may also have completed server-side)
        assert sum(r["completed"] for r in per) >= st["completed"]
        for r in per:
            assert r["breaker_trips"] >= 0
            assert r["state"] in (READY, DEAD)  # nothing wedged mid-state
        assert fl.admission.pending == 0
