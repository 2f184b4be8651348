"""Observability stack tests (deeplearning4j_tpu/metrics/).

Covers the four layers the tentpole added, each at its contract:

- registry — thread-safe counters/gauges/histograms, reservoir
  quantiles against numpy's nearest-rank, label sets, the NullRegistry
  twin;
- exposition — a golden Prometheus 0.0.4 text render, multi-source
  merge with injected labels;
- autoscaler — the hysteresis state machine driven by a fake clock and
  a fake target: scale-up, cooldown, no-flap under oscillation,
  scale-down on idle, floor/ceiling clamps;
- load harness — deterministic seeded arrival schedules, the
  zero-lost-futures ledger, typed synchronous rejections;

plus the serving integration: the legacy ``stats()`` dict shapes of
all five surfaces (generation, inference, fleet, broker, HTTP server)
pinned key-for-key in order, and one end-to-end ``GET /metrics``
scrape over a live KerasBackendServer with inference + generation
models attached, a broker registered, and a health guard publishing.
"""

import json
import threading
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu.metrics.autoscale import Autoscaler
from deeplearning4j_tpu.metrics.exposition import CONTENT_TYPE, render_text
from deeplearning4j_tpu.metrics.loadgen import (LoadGenerator,
                                                poisson_arrivals,
                                                ramp_profile, spike_profile)
from deeplearning4j_tpu.metrics.registry import (Histogram, MetricsRegistry,
                                                 NullRegistry, nearest_rank)

pytestmark = pytest.mark.metrics


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


class TestRegistry:
    def test_threaded_counter_correctness(self):
        """8 racing incrementers lose no updates (the leaf lock is the
        whole thread-safety story — no serving lock involved)."""
        reg = MetricsRegistry()
        c = reg.counter("hits_total", "hits")
        lab = reg.counter("typed_total", "typed", labels=("kind",))

        def hammer():
            for _ in range(10_000):
                c.inc()
                lab.labels(kind="a").inc(2)

        threads = [threading.Thread(target=hammer) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert int(c.value) == 80_000
        assert int(lab.labels(kind="a").value) == 160_000

    def test_counter_rejects_negative(self):
        c = MetricsRegistry().counter("c_total", "c")
        with pytest.raises(ValueError):
            c.inc(-1)
        c.inc(0)          # zero and float increments are legal
        c.inc(2.5)
        assert c.value == 2.5

    def test_gauge_set_and_callback(self):
        reg = MetricsRegistry()
        g = reg.gauge("level", "setter-style")
        g.set(7)
        assert g.value == 7.0
        state = {"n": 3}
        reg.gauge("depth", "callback-style", fn=lambda: state["n"])
        assert reg.snapshot()["depth"] == 3.0
        state["n"] = 9
        assert reg.snapshot()["depth"] == 9.0

    def test_reservoir_quantiles_match_numpy(self):
        """With the reservoir holding every observation, quantile() must
        equal numpy's nearest-rank over the same sample."""
        rng = np.random.default_rng(7)
        xs = rng.lognormal(mean=2.0, sigma=0.8, size=1000)
        h = Histogram(reservoir=len(xs))
        for v in xs:
            h.observe(float(v))
        s = sorted(float(v) for v in xs)
        for q in (0.5, 0.9, 0.99, 0.999):
            expect = s[max(0, int(np.ceil(q * len(s))) - 1)]
            assert h.quantile(q) == pytest.approx(expect)
            assert nearest_rank(s, q) == pytest.approx(expect)

    def test_nearest_rank_is_not_off_by_one(self):
        """The bench's old inline math indexed int(n * 0.99) — rank 100
        of 100 (and past the end at exact multiples). Nearest-rank p99
        of 100 samples is rank 99 (index 98)."""
        s = list(range(100))
        assert nearest_rank(s, 0.99) == 98
        assert nearest_rank(s, 0.5) == 49
        assert nearest_rank(s, 1.0) == 99
        assert nearest_rank([5.0], 0.99) == 5.0

    def test_subsampling_reservoir_stays_plausible(self):
        """Past the reservoir bound the quantiles are estimates — they
        must still land inside the observed range, deterministically
        for a fixed seed."""
        h1 = Histogram(reservoir=128)
        h2 = Histogram(reservoir=128)
        rng = np.random.default_rng(3)
        xs = [float(v) for v in rng.uniform(10.0, 20.0, size=5000)]
        for v in xs:
            h1.observe(v)
            h2.observe(v)
        assert 10.0 <= h1.quantile(0.99) <= 20.0
        assert h1.quantile(0.99) == h2.quantile(0.99)  # seeded, no wall clock

    def test_null_registry_is_inert(self):
        reg = NullRegistry()
        c = reg.counter("x_total", "x")
        c.inc(5)
        assert c.value == 0.0
        assert reg.snapshot() == {}
        assert render_text([({}, reg)]) == ""


# ---------------------------------------------------------------------------
# exposition
# ---------------------------------------------------------------------------


class TestExposition:
    def test_content_type(self):
        assert CONTENT_TYPE == "text/plain; version=0.0.4; charset=utf-8"

    def test_golden_render(self):
        """Byte-exact 0.0.4 exposition: merged same-named families
        across sources, injected labels prepended, histogram as the
        bucket/sum/count triple, integral floats bare."""
        reg = MetricsRegistry()
        c = reg.counter("demo_requests_total", "requests served",
                        labels=("route",))
        c.labels(route="/predict").inc(3)
        c.labels(route="/generate").inc()
        reg.gauge("demo_temperature", "a gauge").set(36.6)
        h = reg.histogram("demo_latency_ms", "latency", buckets=(1.0, 5.0))
        for v in (0.5, 3.0, 7.0):
            h.observe(v)
        other = MetricsRegistry()
        other.counter("demo_requests_total", "requests served",
                      labels=("route",)).labels(route="/predict").inc(2)
        golden = (
            '# HELP demo_requests_total requests served\n'
            '# TYPE demo_requests_total counter\n'
            'demo_requests_total{route="/predict"} 3\n'
            'demo_requests_total{route="/generate"} 1\n'
            'demo_requests_total{model="m0",route="/predict"} 2\n'
            '# HELP demo_temperature a gauge\n'
            '# TYPE demo_temperature gauge\n'
            'demo_temperature 36.6\n'
            '# HELP demo_latency_ms latency\n'
            '# TYPE demo_latency_ms histogram\n'
            'demo_latency_ms_bucket{le="1"} 1\n'
            'demo_latency_ms_bucket{le="5"} 2\n'
            'demo_latency_ms_bucket{le="+Inf"} 3\n'
            'demo_latency_ms_sum 10.5\n'
            'demo_latency_ms_count 3\n'
        )
        assert render_text([({}, reg), ({"model": "m0"}, other)]) == golden

    def test_label_escaping(self):
        reg = MetricsRegistry()
        reg.counter("esc_total", "esc", labels=("path",)).labels(
            path='a"b\\c\nd').inc()
        text = render_text([({}, reg)])
        assert 'esc_total{path="a\\"b\\\\c\\nd"} 1' in text


# ---------------------------------------------------------------------------
# autoscaler
# ---------------------------------------------------------------------------


class _FakeTarget:
    """Scriptable target: the test sets depth/miss before each tick."""

    name = "fake"
    min_level = 1
    max_level = 4

    def __init__(self, level=2):
        self.level = level
        self.depth = 0
        self.miss = 0.0
        self.set_calls = []

    def observe(self):
        return self.depth, self.miss

    def get(self):
        return self.level

    def set(self, n):
        self.level = n
        self.set_calls.append(n)


class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


class TestAutoscaler:
    def _scaler(self, target, **kw):
        clock = _FakeClock()
        kw.setdefault("high_depth", 8)
        kw.setdefault("low_depth", 1)
        kw.setdefault("up_ticks", 2)
        kw.setdefault("down_ticks", 3)
        kw.setdefault("cooldown_s", 5.0)
        return Autoscaler([target], clock=clock, **kw), clock

    def test_scale_up_needs_sustained_breach(self):
        tgt = _FakeTarget(level=2)
        sc, clock = self._scaler(tgt)
        tgt.depth = 20
        assert sc.tick() == []          # 1 hot tick: not yet
        clock.t += 1
        made = sc.tick()                # 2nd consecutive: scale up
        assert [d.action for d in made] == ["scale_up"]
        assert tgt.level == 3
        assert made[0].level_from == 2 and made[0].level_to == 3

    def test_cooldown_quarantines_after_change(self):
        tgt = _FakeTarget(level=1)
        sc, clock = self._scaler(tgt)
        tgt.depth = 20
        sc.tick()
        clock.t += 1
        sc.tick()
        assert tgt.level == 2
        # still breaching, but inside the 5 s cooldown: no second step
        for _ in range(4):
            clock.t += 1
            sc.tick()
        assert tgt.level == 2
        clock.t += 5                    # cooldown expires
        sc.tick()                       # hi streak rebuilt during cooldown
        assert tgt.level == 3

    def test_oscillation_produces_zero_decisions(self):
        """Queue flapping above/below the threshold every tick must
        never flap capacity — the consecutive-tick streak resets."""
        tgt = _FakeTarget(level=2)
        sc, clock = self._scaler(tgt)
        for i in range(40):
            tgt.depth = 20 if i % 2 == 0 else 4
            clock.t += 1
            sc.tick()
        assert tgt.set_calls == []
        assert list(sc.decisions) == []

    def test_scale_down_on_idle_and_floor(self):
        tgt = _FakeTarget(level=2)
        sc, clock = self._scaler(tgt)
        tgt.depth = 0
        for _ in range(3):
            clock.t += 1
            sc.tick()
        assert tgt.level == 1           # one step down after down_ticks
        clock.t += 10
        for _ in range(6):
            clock.t += 1
            sc.tick()
        assert tgt.level == 1           # clamped at min_level

    def test_ceiling_clamp(self):
        tgt = _FakeTarget(level=4)
        sc, clock = self._scaler(tgt)
        tgt.depth = 100
        for _ in range(6):
            clock.t += 1
            sc.tick()
        assert tgt.level == 4 and tgt.set_calls == []

    def test_miss_rate_alone_scales_up(self):
        """Deadline-miss rate is an OR trigger with queue depth."""
        tgt = _FakeTarget(level=1)
        sc, clock = self._scaler(tgt, high_miss_rate=0.05)
        tgt.depth = 0
        tgt.miss = 0.5
        sc.tick()
        clock.t += 1
        sc.tick()
        assert tgt.level == 2

    def test_decisions_land_in_registry(self):
        reg = MetricsRegistry()
        tgt = _FakeTarget(level=1)
        clock = _FakeClock()
        sc = Autoscaler([tgt], up_ticks=1, cooldown_s=0.0, registry=reg,
                        clock=clock)
        tgt.depth = 100
        sc.tick()
        text = render_text([({}, reg)])
        assert ('autoscale_decisions_total{target="fake",'
                'action="scale_up"} 1') in text
        assert 'autoscale_level{target="fake"} 2' in text


# ---------------------------------------------------------------------------
# load harness
# ---------------------------------------------------------------------------


class _InstantFuture:
    def add_done_callback(self, cb):
        cb(self)

    def exception(self):
        return None


class TestLoadGen:
    def test_poisson_schedule_deterministic(self):
        rate = ramp_profile(50.0, 200.0, 1.0)
        a = poisson_arrivals(rate, 2.0, 200.0, seed=11)
        b = poisson_arrivals(rate, 2.0, 200.0, seed=11)
        c = poisson_arrivals(rate, 2.0, 200.0, seed=12)
        assert a == b
        assert a != c
        assert all(0.0 <= t < 2.0 for t in a)
        assert a == sorted(a)

    def test_profiles(self):
        r = ramp_profile(100.0, 300.0, 2.0)
        assert r(0.0) == 100.0 and r(1.0) == 200.0
        assert r(2.0) == 300.0 and r(99.0) == 300.0
        s = spike_profile(100.0, 900.0, at_s=1.0, dur_s=0.5)
        assert s(0.9) == 100.0 and s(1.0) == 900.0
        assert s(1.49) == 900.0 and s(1.5) == 100.0

    def test_open_loop_ledger_and_determinism(self):
        """Same seed -> same schedule, same request indices; every
        future accounted for (lost == 0)."""
        def run():
            issued = []

            def submit(i):
                issued.append(i)
                return _InstantFuture()

            lg = LoadGenerator(submit, seed=5)
            res = lg.run_open(lambda t: 400.0, 0.4, 400.0, timeout_s=30)
            return issued, res

        issued_a, res_a = run()
        issued_b, res_b = run()
        assert issued_a == issued_b
        assert res_a.submitted == res_b.submitted == len(issued_a) > 0
        assert res_a.lost == 0 and res_a.failed == 0
        assert res_a.completed == res_a.submitted

    def test_synchronous_rejection_counts_as_typed_failure(self):
        def submit(i):
            if i % 5 == 0:
                raise ValueError("shed")
            return _InstantFuture()

        lg = LoadGenerator(submit, seed=1)
        res = lg.run_open(lambda t: 300.0, 0.3, 300.0, timeout_s=30)
        assert res.lost == 0
        assert res.failed == res.errors.get("ValueError")
        assert res.completed + res.failed == res.submitted
        assert res.failed > 0

    def test_closed_loop(self):
        lg = LoadGenerator(lambda i: _InstantFuture(), seed=2)
        res = lg.run_closed(workers=3, requests_per_worker=5,
                            timeout_s=30)
        assert res.submitted == 15
        assert res.lost == 0 and res.completed == 15

    def test_latency_publishes_into_registry(self):
        reg = MetricsRegistry()
        lg = LoadGenerator(lambda i: _InstantFuture(), seed=0,
                           registry=reg)
        lg.run_open(lambda t: 200.0, 0.2, 200.0, timeout_s=30)
        snap = reg.snapshot()
        assert snap["soak_submitted_total"] > 0
        assert snap["soak_completed_total"] == snap["soak_submitted_total"]


# ---------------------------------------------------------------------------
# legacy stats() shapes — the five re-homed surfaces
# ---------------------------------------------------------------------------


GEN_KEYS = ["slots", "active_slots", "queued", "admitted", "expired",
            "retired", "completed", "failed", "retried", "pool_rebuilds",
            "prefills", "decode_steps", "tokens_generated", "tokens_per_s",
            "accepted", "rejected", "pending", "breaker_state", "pages",
            "handoff", "role"]
GEN_HANDOFF_KEYS = ["snapshot_every", "snapshots", "bytes", "resumes",
                    "tokens_saved", "fallbacks", "preempt_resumes",
                    "migrated", "prefill_exports"]
GEN_PAGE_KEYS = ["page_size", "pages_total", "pages_free", "pages_cached",
                 "pages_shared", "pages_refcounted", "resident_kv_bytes",
                 "peak_resident_kv_bytes", "cow_copies", "prefix_hits",
                 "prefix_tokens_reused", "evictions", "preempted", "spec_k",
                 "spec_rounds", "spec_proposed", "spec_accepted",
                 "spec_accept_rate", "kv_cache_dtype", "bytes_per_token",
                 "paged_attention",
                 # appended by PR 34, behind the legacy keys
                 "prompt_tokens_admitted", "planes"]
INF_KEYS = ["retried", "expired", "rejected_circuit", "completed", "failed",
            "dispatches", "accepted", "rejected", "pending", "breaker_state"]
FLEET_KEYS = ["replica_count", "submitted", "rejected_submits", "completed",
              "failed", "expired", "redispatched", "hedged",
              "losers_cancelled", "deaths", "restarts", "parked", "inflight",
              "handoff_resumes", "handoff_fallbacks",
              "admission", "replicas", "tier_handoffs", "degraded_submits",
              "degraded_mode"]
FLEET_REPLICA_KEYS = ["rid", "state", "role", "generation", "health_score",
                      "ewma_latency_ms", "failure_ewma", "inflight",
                      "restarts", "spawn_failures", "dispatched", "completed",
                      "failed", "rejected", "breaker", "breaker_trips",
                      "admission", "server"]
BROKER_KEYS = ["subscribers", "frames_dropped", "subscribers_disconnected",
               "dropped_by_topic"]
SERVER_KEYS = ["retried", "expired", "rejected_circuit", "completed",
               "failed", "accepted", "rejected", "pending", "breaker_state",
               "models"]
RAG_KEYS = ["submitted", "completed", "failed", "expired", "rejected",
            "inflight", "k", "page_size", "prefix_hits",
            "prefix_tokens_reused", "tiers"]
RAG_TIER_KEYS = ["replicas", "queued", "expired", "completed",
                 "active_slots", "slots"]


class TestLegacyStatsShapes:
    """The re-home moved every serving counter into the registry; the
    public dicts — key set AND order, which is the JSON serialization
    order clients see — must not have moved an inch."""

    def test_generation_server(self, lm):
        from deeplearning4j_tpu.parallel.generation import GenerationServer

        srv = GenerationServer(lm, 17, slots=2)
        try:
            st = srv.stats()
        finally:
            srv.close()
        assert list(st.keys()) == GEN_KEYS
        assert list(st["pages"].keys()) == GEN_PAGE_KEYS
        assert list(st["handoff"].keys()) == GEN_HANDOFF_KEYS
        assert isinstance(st["completed"], int)
        assert isinstance(st["tokens_per_s"], float)

    def test_parallel_inference(self):
        from deeplearning4j_tpu.parallel.inference import ParallelInference

        from tests.test_fused_fit import _mln

        with ParallelInference(_mln(), workers=8) as inf:
            st = inf.stats()
        assert list(st.keys()) == INF_KEYS
        assert all(isinstance(st[k], int) for k in INF_KEYS[:-1])

    def test_replica_fleet(self, lm):
        from deeplearning4j_tpu.parallel.fleet import ReplicaFleet
        from deeplearning4j_tpu.parallel.generation import GenerationServer

        fl = ReplicaFleet(lambda rid: GenerationServer(lm, 17, slots=2),
                          replicas=1)
        try:
            st = fl.stats()
        finally:
            fl.close()
        assert list(st.keys()) == FLEET_KEYS
        assert list(st["replicas"][0].keys()) == FLEET_REPLICA_KEYS

    def test_streaming_broker(self):
        from deeplearning4j_tpu.streaming.broker import StreamingBroker

        b = StreamingBroker().start()
        try:
            st = b.stats()
        finally:
            b.stop()
        assert list(st.keys()) == BROKER_KEYS

    def test_keras_backend_server(self):
        from deeplearning4j_tpu.modelimport.server import KerasBackendServer

        st = KerasBackendServer().stats()
        assert list(st.keys()) == SERVER_KEYS

    @pytest.mark.slow  # builds a two-tier fleet: tier-1 timing headroom
    def test_rag_pipeline(self, lm):
        from deeplearning4j_tpu.nearestneighbors.index import EmbeddingIndex
        from deeplearning4j_tpu.parallel.generation import GenerationServer
        from deeplearning4j_tpu.parallel.rag import RagPipeline

        vecs = np.random.RandomState(0).randn(16, 4).astype(np.float32)
        pipe = RagPipeline(
            lambda rid: EmbeddingIndex(vecs),
            lambda rid: GenerationServer(lm, 17, slots=2, page_size=4),
            [np.arange(1, 5, dtype=np.int64)] * 16, page_size=4, k=2)
        try:
            st = pipe.stats()
        finally:
            pipe.close()
        assert list(st.keys()) == RAG_KEYS
        assert list(st["tiers"].keys()) == ["knn", "generate"]
        for role in ("knn", "generate"):
            assert list(st["tiers"][role].keys()) == RAG_TIER_KEYS


# ---------------------------------------------------------------------------
# GET /metrics end to end
# ---------------------------------------------------------------------------


class TestMetricsEndpoint:
    def test_one_scrape_covers_every_surface(self, lm):
        """A single /metrics page carries the server's own counters, the
        attached inference AND generation registries (labeled by model
        id), a registered broker registry, and a health guard — while
        /stats keeps serving the legacy JSON from the same counters."""
        from deeplearning4j_tpu.modelimport.server import KerasBackendServer
        from deeplearning4j_tpu.optimize.health import HealthPolicy
        from deeplearning4j_tpu.streaming.broker import StreamingBroker

        from tests.test_fused_fit import _mln

        srv = KerasBackendServer()
        broker = StreamingBroker().start()
        guard_reg = MetricsRegistry()
        HealthPolicy(registry=guard_reg)
        srv.attach_inference(_mln(), mid="inf0", max_wait_ms=5.0)
        srv.attach_generation(lm, vocab=17, mid="gen0", slots=2)
        srv.register_metrics({"component": "broker"}, broker.metrics)
        srv.register_metrics({"component": "health"}, guard_reg)
        port = srv.start()
        base = f"http://127.0.0.1:{port}"
        try:
            req = urllib.request.Request(
                base + "/predict",
                json.dumps({"model": "inf0",
                            "features": [[0.0, 0.0, 0.0, 0.0]]}).encode(),
                {"Content-Type": "application/json"})
            out = json.loads(urllib.request.urlopen(req).read())
            assert "output" in out

            resp = urllib.request.urlopen(base + "/metrics")
            assert resp.headers["Content-Type"] == CONTENT_TYPE
            text = resp.read().decode("utf-8")
            # server's own serving ledger
            assert "server_completed_total 1" in text
            # attached inference registry, injected model label
            assert ('inference_completed_total{model="inf0"} 1'
                    in text)
            assert 'inference_batch_rows_bucket{model="inf0",le="1"}' in text
            # attached generation registry (gauges registered at ctor)
            assert 'generation_slots{model="gen0"} 2' in text
            assert 'generation_active_slot_cap{model="gen0"} 2' in text
            # registered extras keep their injected labels
            assert 'broker_subscribers{component="broker"} 0' in text
            assert ('health_consecutive_skips{component="health"} 0'
                    in text)

            # the legacy JSON view survives, fed from the same registry
            stats = json.loads(
                urllib.request.urlopen(base + "/stats").read())
            assert list(stats.keys())[:10] == SERVER_KEYS
            assert stats["completed"] == 1
            assert stats["inference"]["inf0"]["completed"] == 1
            assert list(stats["inference"]["inf0"].keys()) == INF_KEYS
            assert list(stats["generation"]["gen0"].keys()) == GEN_KEYS
        finally:
            srv.stop()
            broker.stop()
