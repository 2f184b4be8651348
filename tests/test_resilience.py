"""Serving resilience (parallel/resilience.py + its wiring through
ParallelInference and KerasBackendServer).

The contract under test is the SRE one: an admitted request either
resolves or fails promptly with a typed error (DeadlineExceeded /
ServerOverloaded / CircuitOpen / the original error once the retry budget
is spent) — never hangs, never silently disappears. The headline is the
chaos end-to-end: a saturating burst of submits with 10% injected
transient faults loses ZERO futures.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu.parallel.inference import ParallelInference
from deeplearning4j_tpu.parallel.resilience import (
    AdmissionController,
    ChaosPolicy,
    CircuitBreaker,
    CircuitOpen,
    Deadline,
    DeadlineExceeded,
    ResilienceError,
    RetryPolicy,
    ServerOverloaded,
    TransientDispatchError,
)

from tests.test_fused_fit import _iris_like, _mln

pytestmark = pytest.mark.serving

TYPED = (DeadlineExceeded, ServerOverloaded, CircuitOpen,
         TransientDispatchError)


def _features(n, seed=0):
    return np.asarray(_iris_like(n, seed=seed).features)


# --------------------------------------------------------------- primitives
class TestDeadline:
    def test_remaining_counts_down_and_expires(self):
        t = [0.0]
        d = Deadline(1.0, clock=lambda: t[0])
        assert d.remaining() == pytest.approx(1.0)
        assert not d.expired()
        t[0] = 0.75
        assert d.remaining() == pytest.approx(0.25)
        t[0] = 1.25
        assert d.expired() and d.remaining() < 0

    def test_zero_budget_is_born_expired(self):
        assert Deadline(0.0).expired()


class TestRetryPolicy:
    def test_gives_up_after_budget_with_original_error(self):
        calls = []
        policy = RetryPolicy(max_attempts=3, seed=0, sleep=lambda s: None)

        def always_transient():
            calls.append(1)
            raise TransientDispatchError("flaky")

        with pytest.raises(TransientDispatchError, match="flaky"):
            policy.call(always_transient)
        assert len(calls) == 3

    def test_succeeds_mid_budget(self):
        calls = []
        policy = RetryPolicy(max_attempts=4, seed=0, sleep=lambda s: None)

        def flaky():
            calls.append(1)
            if len(calls) < 3:
                raise TransientDispatchError("flaky")
            return "ok"

        assert policy.call(flaky) == "ok"
        assert len(calls) == 3

    def test_non_transient_errors_are_not_retried(self):
        calls = []
        policy = RetryPolicy(max_attempts=5, seed=0, sleep=lambda s: None)

        def hard():
            calls.append(1)
            raise ValueError("hard")

        with pytest.raises(ValueError):
            policy.call(hard)
        assert len(calls) == 1

    def test_backoff_is_capped_and_jittered_deterministically(self):
        a = RetryPolicy(base_s=0.01, cap_s=0.05, seed=7)
        b = RetryPolicy(base_s=0.01, cap_s=0.05, seed=7)
        seq_a = [a.backoff_s(0.01) for _ in range(20)]
        seq_b = [b.backoff_s(0.01) for _ in range(20)]
        assert seq_a == seq_b  # seeded: reproducible
        assert all(0.01 <= s <= 0.05 for s in seq_a)

    def test_deadline_too_tight_for_backoff_gives_up(self):
        sleeps = []
        policy = RetryPolicy(max_attempts=5, base_s=0.05, cap_s=0.05,
                             seed=0, sleep=sleeps.append)
        deadline = Deadline(0.01)  # cannot cover even one 50 ms backoff

        def always_transient():
            raise TransientDispatchError("flaky")

        with pytest.raises(TransientDispatchError):
            policy.call(always_transient, deadline=deadline)
        assert sleeps == []  # gave up instead of sleeping past the budget


class TestCircuitBreaker:
    def _breaker(self, t):
        return CircuitBreaker(failure_threshold=0.5, window=8, min_calls=4,
                              reset_timeout_s=10.0, clock=lambda: t[0])

    def test_closed_to_open_on_failure_rate(self):
        t = [0.0]
        br = self._breaker(t)
        assert br.state == CircuitBreaker.CLOSED
        for _ in range(3):
            br.record_failure()
        assert br.state == CircuitBreaker.CLOSED  # under min_calls
        br.record_failure()
        assert br.state == CircuitBreaker.OPEN
        assert not br.allow()
        assert br.open_count == 1

    def test_successes_keep_failure_rate_under_threshold(self):
        t = [0.0]
        br = self._breaker(t)
        for _ in range(8):
            br.record_success()
        for _ in range(3):
            br.record_failure()  # 3/8 failures in window < 0.5
        assert br.state == CircuitBreaker.CLOSED

    def test_half_open_probe_then_close_on_success(self):
        t = [0.0]
        br = self._breaker(t)
        for _ in range(4):
            br.record_failure()
        assert not br.allow()
        t[0] = 10.0  # reset timeout elapses
        assert br.state == CircuitBreaker.HALF_OPEN
        assert br.allow()       # the single probe
        assert not br.allow()   # probe budget spent
        br.record_success()
        assert br.state == CircuitBreaker.CLOSED
        assert br.allow()

    def test_half_open_probe_failure_reopens(self):
        t = [0.0]
        br = self._breaker(t)
        for _ in range(4):
            br.record_failure()
        t[0] = 10.0
        assert br.allow()
        br.record_failure()
        assert br.state == CircuitBreaker.OPEN
        assert not br.allow()
        assert br.open_count == 2

    def test_lost_probe_does_not_wedge_half_open(self):
        """A probe that never reports (e.g. its request expired before
        dispatch) must not leave the breaker rejecting forever."""
        t = [0.0]
        br = self._breaker(t)
        for _ in range(4):
            br.record_failure()
        t[0] = 10.0
        assert br.allow()       # probe vanishes without an outcome
        assert not br.allow()
        t[0] = 20.0             # another reset window passes
        assert br.allow()       # probe budget replenished


class TestAdmissionController:
    def test_rejects_typed_at_watermark_and_releases(self):
        adm = AdmissionController(max_pending=2)
        adm.acquire()
        adm.acquire()
        with pytest.raises(ServerOverloaded):
            adm.acquire()
        assert (adm.accepted, adm.rejected, adm.pending) == (2, 1, 2)
        adm.release()
        adm.acquire()  # capacity freed
        assert adm.accepted == 3


class TestChaosPolicy:
    def test_deterministic_under_seed(self):
        def run(seed):
            chaos = ChaosPolicy(seed=seed, transient_rate=0.3,
                                hard_rate=0.1)
            fn = chaos.wrap(lambda: "ok")
            out = []
            for _ in range(50):
                try:
                    out.append(fn())
                except TransientDispatchError:
                    out.append("transient")
                except RuntimeError:
                    out.append("hard")
            return out

        assert run(3) == run(3)
        assert run(3) != run(4)

    def test_shutdown_modes_at_zero_rate_keep_legacy_sequence(self):
        # the PR-14 shutdown-phase draws are gated on their own rates:
        # calling them at rate 0 consumes NO rng draws, so every chaos
        # sequence recorded before they existed replays byte-identically
        def run(call_new_hooks):
            chaos = ChaosPolicy(seed=7, transient_rate=0.3, hard_rate=0.1)
            fn = chaos.wrap(lambda: "ok")
            out = []
            for _ in range(30):
                if call_new_hooks:
                    chaos.drain_fault()      # rate 0: no draw, no fault
                    chaos.sentinel_fault()
                try:
                    out.append(fn())
                except TransientDispatchError:
                    out.append("transient")
                except RuntimeError:
                    out.append("hard")
            return out

        assert run(True) == run(False)

    def test_shutdown_mode_draws_are_seeded(self):
        def seq(seed):
            chaos = ChaosPolicy(seed=seed, kill_during_drain_rate=0.5,
                                stall_sentinel_rate=0.5)
            hits = []
            for _ in range(40):
                try:
                    chaos.drain_fault()
                    hits.append(False)
                except BaseException:  # noqa: B036 — LoopKilled by design
                    hits.append(True)
            assert chaos.injected_drain_kill == sum(hits)
            return hits

        assert seq(5) == seq(5)
        assert seq(5) != seq(6)
        assert 0 < sum(seq(5)) < 40  # an actual mix at rate 0.5

    def test_rates_and_counters(self):
        chaos = ChaosPolicy(seed=0, transient_rate=0.5)
        fn = chaos.wrap(lambda: "ok")
        outcomes = []
        for _ in range(200):
            try:
                outcomes.append(fn())
            except TransientDispatchError:
                outcomes.append(None)
        n_faults = outcomes.count(None)
        assert n_faults == chaos.injected_transient
        assert 60 <= n_faults <= 140  # ~50% of 200
        assert chaos.injected_hard == 0

    def test_latency_injection(self):
        slept = []
        chaos = ChaosPolicy(seed=0, latency_rate=1.0, latency_s=0.05,
                            sleep=slept.append)
        assert chaos.wrap(lambda: "ok")() == "ok"
        assert slept == [0.05]
        assert chaos.injected_latency == 1


# ----------------------------------------------------- ParallelInference
class TestDeadlinesInServer:
    def test_born_expired_request_fails_typed_pre_dispatch(self):
        """Deadline expiry PRE-queue: a zero-budget submit fails with
        DeadlineExceeded and never costs a dispatch."""
        net = _mln()
        with ParallelInference(net, workers=8, max_wait_ms=5) as inf:
            inf.submit(_features(1)).result(timeout=30)  # warm
            base = inf.dispatch_count
            fut = inf.submit(_features(1), deadline_s=0.0)
            with pytest.raises(DeadlineExceeded):
                fut.result(timeout=10)
            assert inf.dispatch_count == base
            assert inf.stats()["expired"] == 1

    def test_request_expiring_mid_queue_fails_typed(self):
        """Deadline expiry MID-queue: requests stuck behind a slow
        dispatch expire in the coalescer, not on the device."""
        net = _mln()
        chaos = ChaosPolicy(seed=0, latency_rate=1.0, latency_s=0.4)
        with ParallelInference(net, workers=8, max_wait_ms=1,
                               chaos=chaos) as inf:
            ok = inf.submit(_features(1))
            time.sleep(0.1)  # ok's batch is now mid-dispatch (chaos sleep)
            dead = inf.submit(_features(1, seed=1), deadline_s=0.05)
            assert ok.result(timeout=30).shape == (1, 3)
            with pytest.raises(DeadlineExceeded):
                dead.result(timeout=30)

    def test_generous_deadline_resolves_normally(self):
        net = _mln()
        with ParallelInference(net, workers=8, max_wait_ms=5) as inf:
            ref = inf.output(_features(2))
            got = inf.submit(_features(2), deadline_s=60.0).result(timeout=30)
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)

    def test_short_deadline_flushes_batch_early(self):
        """Remaining-time propagation: a member with less budget than the
        coalesce window dispatches before expiry instead of after."""
        net = _mln()
        with ParallelInference(net, workers=8, max_batch=64,
                               max_wait_ms=10_000) as inf:
            inf.output(_features(1))  # warm the 1-row bucket
            fut = inf.submit(_features(1), deadline_s=1.0)
            # without the early flush this would wait out the 10 s window
            assert fut.result(timeout=5).shape == (1, 3)


class TestAdmissionInServer:
    def test_burst_beyond_watermark_sheds_typed(self):
        """Overload shedding: a burst past max_pending rejects immediately
        with ServerOverloaded; every ADMITTED request still resolves."""
        net = _mln()
        chaos = ChaosPolicy(seed=0, latency_rate=1.0, latency_s=0.05)
        with ParallelInference(net, workers=8, max_batch=4, max_wait_ms=1,
                               inflight=1, max_pending=8,
                               chaos=chaos) as inf:
            inf.output(_features(4))
            admitted, shed = [], 0
            for i in range(40):
                try:
                    admitted.append(inf.submit(_features(1, seed=i)))
                except ServerOverloaded:
                    shed += 1
            assert shed > 0, "burst never hit the watermark"
            for f in admitted:
                assert f.result(timeout=60).shape == (1, 3)
            st = inf.stats()
            assert st["rejected"] == shed
            assert st["accepted"] == len(admitted)
            assert st["pending"] == 0

    def test_rejected_submit_does_not_leak_pending(self):
        net = _mln()
        with ParallelInference(net, workers=8, max_pending=1,
                               max_wait_ms=5) as inf:
            inf.submit(_features(1)).result(timeout=30)
            assert inf.stats()["pending"] == 0


class TestBreakerInServer:
    def test_open_breaker_fast_fails_submits(self):
        """Sustained dispatch failure trips the breaker; subsequent
        submits fail with CircuitOpen without touching the queue."""
        net = _mln()
        chaos = ChaosPolicy(seed=0, hard_rate=1.0)  # every dispatch dies
        breaker = CircuitBreaker(failure_threshold=0.5, window=8,
                                 min_calls=2, reset_timeout_s=60.0)
        retry = RetryPolicy(max_attempts=1)
        with ParallelInference(net, workers=8, max_wait_ms=1, chaos=chaos,
                               breaker=breaker, retry=retry) as inf:
            failures = [inf.submit(_features(1, seed=i)) for i in range(4)]
            for f in failures:
                with pytest.raises(RuntimeError):
                    f.result(timeout=30)
            deadline = time.monotonic() + 10
            while (breaker.state != CircuitBreaker.OPEN
                   and time.monotonic() < deadline):
                try:
                    f = inf.submit(_features(1))
                except CircuitOpen:
                    break
                with pytest.raises(RuntimeError):
                    f.result(timeout=30)
            with pytest.raises(CircuitOpen):
                inf.submit(_features(1))
            assert inf.stats()["breaker_state"] == "open"
            assert inf.stats()["rejected_circuit"] >= 1

    def test_breaker_recovers_after_faults_stop(self):
        """Half-open probe succeeds once the fault source is gone and the
        server serves again."""
        net = _mln()
        chaos = ChaosPolicy(seed=0, hard_rate=1.0)
        breaker = CircuitBreaker(failure_threshold=0.5, window=8,
                                 min_calls=2, reset_timeout_s=0.2)
        retry = RetryPolicy(max_attempts=1)
        inf = ParallelInference(net, workers=8, max_wait_ms=1, chaos=chaos,
                                breaker=breaker, retry=retry)
        try:
            for i in range(3):
                with pytest.raises(RuntimeError):
                    inf.submit(_features(1, seed=i)).result(timeout=30)
            # stop the chaos: dispatches are healthy again
            chaos.hard_rate = 0.0
            deadline = time.monotonic() + 15
            out = None
            while out is None and time.monotonic() < deadline:
                try:
                    out = inf.submit(_features(1)).result(timeout=30)
                except (CircuitOpen, RuntimeError):
                    time.sleep(0.05)  # waits out reset_timeout_s
            assert out is not None and out.shape == (1, 3)
            assert breaker.state == CircuitBreaker.CLOSED
        finally:
            inf.close()


class TestRetryInServer:
    def test_transient_faults_are_retried_to_success(self):
        """A fault rate well under the retry budget: every request
        resolves, and the retry counter shows the policy worked."""
        net = _mln()
        chaos = ChaosPolicy(seed=1, transient_rate=0.3)
        retry = RetryPolicy(max_attempts=6, base_s=1e-4, cap_s=1e-3, seed=0)
        # max_batch=1: one dispatch per request, so the seeded fault
        # sequence (.T..T.T..T..) is reached whatever the timing — with the
        # default the 30 submits can coalesce into ONE dispatch, whose draw
        # is clean (failed 10 of 19 standalone runs on the parent)
        with ParallelInference(net, workers=8, max_batch=1, max_wait_ms=1,
                               chaos=chaos, breaker=False,
                               retry=retry) as inf:
            ref = inf.output(_features(1))
            futs = [inf.submit(_features(1)) for _ in range(30)]
            for f in futs:
                np.testing.assert_allclose(f.result(timeout=60), ref,
                                           rtol=1e-5, atol=1e-6)
            assert inf.stats()["retried"] >= 1
            assert chaos.injected_transient >= 1

    def test_retry_budget_exhaustion_surfaces_original_error(self):
        net = _mln()
        chaos = ChaosPolicy(seed=0, transient_rate=1.0)  # never heals
        retry = RetryPolicy(max_attempts=3, base_s=1e-4, cap_s=1e-3, seed=0)
        with ParallelInference(net, workers=8, max_wait_ms=1, chaos=chaos,
                               breaker=False, retry=retry) as inf:
            fut = inf.submit(_features(1))
            with pytest.raises(TransientDispatchError):
                fut.result(timeout=30)


class TestDrainAndClose:
    def test_drain_completes_inflight_and_rejects_new(self):
        net = _mln()
        chaos = ChaosPolicy(seed=0, latency_rate=1.0, latency_s=0.05)
        inf = ParallelInference(net, workers=8, max_batch=2, max_wait_ms=1,
                                chaos=chaos)
        try:
            inf.output(_features(2))
            futs = [inf.submit(_features(1, seed=i)) for i in range(6)]
            drainer = {}

            def drain():
                drainer["ok"] = inf.drain(timeout=60)

            t = threading.Thread(target=drain)
            t.start()
            time.sleep(0.01)  # let drain flip the draining flag
            with pytest.raises(RuntimeError, match="draining"):
                inf.submit(_features(1))
            t.join(70)
            assert drainer["ok"] is True
            for f in futs:
                assert f.result(timeout=1).shape == (1, 3)  # already done
            assert inf.stats()["pending"] == 0
        finally:
            inf.close()

    def test_drain_idle_server_returns_immediately(self):
        net = _mln()
        inf = ParallelInference(net, workers=8)
        assert inf.drain(timeout=1) is True
        inf.close()

    def test_close_still_resolves_everything(self):
        """close() (drain + shutdown) leaves no unresolved future."""
        net = _mln()
        inf = ParallelInference(net, workers=8, max_wait_ms=1)
        futs = [inf.submit(_features(1, seed=i)) for i in range(8)]
        inf.close()
        for f in futs:
            assert f.done()
            # each either resolved with rows or failed typed by shutdown
            if f.exception() is None:
                assert f.result().shape == (1, 3)

    def test_submit_after_close_still_raises_closed(self):
        net = _mln()
        inf = ParallelInference(net, workers=8)
        inf.close()
        with pytest.raises(RuntimeError, match="closed"):
            inf.submit(_features(1))


class TestChaosEndToEnd:
    def test_200_submits_10pct_faults_zero_lost_futures(self):
        """THE acceptance criterion: a saturating burst of 200 submits
        with 10% injected transient faults — every future resolves or
        fails with a typed error; none is lost or left pending."""
        net = _mln()
        chaos = ChaosPolicy(seed=42, transient_rate=0.10)
        retry = RetryPolicy(max_attempts=4, base_s=1e-4, cap_s=2e-3, seed=0)
        with ParallelInference(net, workers=8, max_batch=16, max_wait_ms=1,
                               max_pending=512, retry=retry,
                               chaos=chaos) as inf:
            ref = inf.output(_features(1))
            futs, shed = [], 0
            for i in range(200):
                try:
                    futs.append(inf.submit(_features(1)))
                except (ServerOverloaded, CircuitOpen):
                    shed += 1  # typed at submit: also not lost
            resolved = failed_typed = 0
            for f in futs:
                try:
                    out = f.result(timeout=120)
                    np.testing.assert_allclose(out, ref, rtol=1e-5,
                                               atol=1e-6)
                    resolved += 1
                except TYPED:
                    failed_typed += 1
            assert resolved + failed_typed == len(futs)
            assert resolved + failed_typed + shed == 200
            for f in futs:
                assert f.done(), "a future was left pending"
            st = inf.stats()
            assert st["pending"] == 0
            assert st["completed"] == resolved
            assert chaos.injected_transient > 0, "chaos never fired"
            # at 10% faults with a 4-attempt budget, retries recover the
            # overwhelming majority of requests
            assert resolved >= 0.95 * len(futs)


# ------------------------------------------------------ KerasBackendServer
class _FakeNet:
    """Stands in for an imported Keras model: deterministic output, no
    keras dependency, optional injected latency."""

    def __init__(self, latency_s=0.0):
        self.latency_s = latency_s

    def output(self, x):
        if self.latency_s:
            time.sleep(self.latency_s)
        x = np.asarray(x, np.float32)
        return x * 2.0


class _Http:
    def __init__(self, port):
        self.base = f"http://127.0.0.1:{port}"

    def post(self, path, payload, raw=None):
        body = raw if raw is not None else json.dumps(payload).encode()
        req = urllib.request.Request(
            self.base + path, body, {"Content-Type": "application/json"})
        try:
            resp = urllib.request.urlopen(req)
            return resp.status, json.loads(resp.read())
        except urllib.error.HTTPError as e:
            return e.code, json.loads(e.read())

    def get(self, path):
        resp = urllib.request.urlopen(self.base + path)
        return resp.status, json.loads(resp.read())


@pytest.fixture
def http_server():
    from deeplearning4j_tpu.modelimport.server import KerasBackendServer

    def make(**kwargs):
        srv = KerasBackendServer(**kwargs)
        srv._models["m0"] = _FakeNet()
        servers.append(srv)
        return srv, _Http(srv.start())

    servers = []
    try:
        yield make
    finally:
        for s in servers:
            s.stop()


class TestHttpErrorContract:
    def test_malformed_json_is_structured_400(self, http_server):
        srv, http = http_server()
        status, body = http.post("/predict", None, raw=b"{not json]")
        assert status == 400
        assert body["type"] == "BadRequest" and "error" in body

    def test_non_object_json_is_400(self, http_server):
        srv, http = http_server()
        status, body = http.post("/predict", None, raw=b"[1, 2, 3]")
        assert status == 400 and body["type"] == "BadRequest"

    def test_unknown_model_is_404(self, http_server):
        srv, http = http_server()
        status, body = http.post("/predict", {"model": "nope",
                                              "features": [[1.0]]})
        assert status == 404
        assert body["type"] == "UnknownModelError"
        assert "nope" in body["error"]

    def test_missing_field_is_400_not_404(self, http_server):
        srv, http = http_server()
        status, body = http.post("/predict", {"model": "m0"})
        assert status == 400 and body["type"] == "BadRequest"

    def test_oversized_body_is_413_without_buffering(self, http_server):
        srv, http = http_server(max_body_bytes=128)
        big = {"model": "m0", "features": [[0.0] * 1000]}
        status, body = http.post("/predict", big)
        assert status == 413 and body["type"] == "BodyTooLarge"

    def test_multi_megabyte_oversized_body_still_gets_its_413(
            self, http_server):
        """The client must RECEIVE the 413 even when its send is still in
        flight — the server drains (discards) the oversized body instead
        of slamming the socket into the client's sendall."""
        srv, http = http_server(max_body_bytes=1 << 20)
        big = {"model": "m0", "features": [[0.0] * 784] * 400}  # > 1 MB
        status, body = http.post("/predict", big)
        assert status == 413 and body["type"] == "BodyTooLarge"

    def test_unknown_route_is_404(self, http_server):
        srv, http = http_server()
        status, body = http.post("/nope", {})
        assert status == 404

    def test_happy_path_predict_and_stats(self, http_server):
        srv, http = http_server()
        status, body = http.post("/predict", {"model": "m0",
                                              "features": [[1.0, 2.0]]})
        assert status == 200
        assert body["output"] == [[2.0, 4.0]]
        status, st = http.get("/stats")
        assert status == 200
        assert st["completed"] == 1 and st["accepted"] == 1
        assert st["breaker_state"] == "closed"


class TestHttpResilienceMapping:
    def test_deadline_maps_to_504(self, http_server):
        srv, http = http_server()
        status, body = http.post(
            "/predict",
            {"model": "m0", "features": [[1.0]], "deadline_s": 0.0})
        assert status == 504 and body["type"] == "DeadlineExceeded"
        assert srv.stats()["expired"] == 1

    def test_overload_maps_to_429(self, http_server):
        srv, http = http_server(max_pending=1)
        srv._models["m0"].latency_s = 0.5
        results = []

        def hit():
            results.append(http.post("/predict", {"model": "m0",
                                                  "features": [[1.0]]}))

        threads = [threading.Thread(target=hit) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
        codes = sorted(status for status, _ in results)
        assert 429 in codes, codes
        assert 200 in codes, codes  # the admitted request still served
        rejected = [b for s, b in results if s == 429]
        assert all(b["type"] == "ServerOverloaded" for b in rejected)
        assert srv.stats()["rejected"] == codes.count(429)

    def test_open_breaker_maps_to_503(self, http_server):
        chaos = ChaosPolicy(seed=0, hard_rate=1.0)
        breaker = CircuitBreaker(failure_threshold=0.5, window=4,
                                 min_calls=2, reset_timeout_s=60.0)
        srv, http = http_server(
            chaos=chaos, breaker=breaker,
            retry=RetryPolicy(max_attempts=1))
        for _ in range(3):
            status, _ = http.post("/predict", {"model": "m0",
                                               "features": [[1.0]]})
            assert status in (500, 503)
        status, body = http.post("/predict", {"model": "m0",
                                              "features": [[1.0]]})
        assert status == 503 and body["type"] == "CircuitOpen"
        assert srv.stats()["breaker_state"] == "open"

    def test_transient_faults_retried_transparently(self, http_server):
        chaos = ChaosPolicy(seed=1, transient_rate=0.4)
        srv, http = http_server(
            chaos=chaos,
            retry=RetryPolicy(max_attempts=6, base_s=1e-4, cap_s=1e-3,
                              seed=0))
        for _ in range(10):
            status, body = http.post("/predict", {"model": "m0",
                                                  "features": [[3.0]]})
            assert status == 200 and body["output"] == [[6.0]]
        assert srv.stats()["retried"] >= 1


# ------------------------------------------------- stats-lock discipline
class TestServerStatsLockDiscipline:
    def test_concurrent_predicts_count_exactly(self):
        """Every stats counter moves under self._stats_lock (graftcheck
        conc-mixed-lock gate): hammer predict() from many threads while a
        reader spins on stats(); the final completed count must be exact
        and no intermediate snapshot may exceed it."""
        from deeplearning4j_tpu.modelimport.server import KerasBackendServer

        class _Net:
            def output(self, x):
                return np.asarray(x) * 2.0

        srv = KerasBackendServer(max_pending=64)
        srv._models["m0"] = _Net()

        threads, per, errs = 8, 25, []
        snapshots = []
        stop = threading.Event()

        def reader():
            while not stop.is_set():
                snapshots.append(srv.stats()["completed"])

        def hammer():
            try:
                for _ in range(per):
                    out = srv.predict("m0", [[1.0, 2.0]])
                    assert out == [[2.0, 4.0]]
            except Exception as e:  # pragma: no cover - failure detail
                errs.append(e)

        r = threading.Thread(target=reader, daemon=True)
        r.start()
        ts = [threading.Thread(target=hammer) for _ in range(threads)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(60)
        stop.set()
        r.join(10)
        assert errs == []
        st = srv.stats()
        assert st["completed"] == threads * per
        assert st["failed"] == 0
        assert all(0 <= s <= threads * per for s in snapshots)
