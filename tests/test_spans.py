"""The span helper (``metrics/spans.py``), the phases the generation
server's loop thread books with it, the two request stamps beside them
(queue wait, token gap), and what a profiler session sees of all that.
"""

import glob
import math
import threading
import time

import jax
import numpy as np
import pytest

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.metrics.registry import (Histogram, MetricsRegistry,
                                                 global_registry)
from deeplearning4j_tpu.metrics.spans import SpanClock
from deeplearning4j_tpu.parallel.generation import (BUSY_PHASES,
                                                    GenerationServer)
from tests.serving_helpers import V, tiny_lm

pytestmark = [pytest.mark.metrics]

#: every phase a working server books (the closed set of the loop thread);
#: ``compile`` is a program's first call, inside the phase that made it
PHASES = ("idle_wait", "admit", "prefill_keys", "prefill_build",
          "prefill_dispatch", "prefill_fetch", "prefill_commit",
          "decode_reserve", "decode_dispatch", "decode_fetch",
          "decode_walk", "housekeeping", "compile", "tick_other")


# ------------------------------------------------------------- the helper
def labelled_clock(prefix="t:"):
    reg = MetricsRegistry()
    seconds = reg.counter("t_seconds_total", "", labels=("phase",))
    spans = reg.counter("t_spans_total", "", labels=("phase",))
    clock = SpanClock(prefix, lambda phase: (
        [seconds.labels(phase=phase)], [spans.labels(phase=phase)]))

    def read():
        # a labelled family with no child yet snapshots as 0.0
        snap = reg.snapshot()
        return tuple(snap[name] or {}
                     for name in ("t_seconds_total", "t_spans_total"))

    return clock, read


def test_a_span_books_its_own_seconds_and_its_parent_the_rest():
    clock, read = labelled_clock()
    t0 = time.perf_counter()
    with clock.span("round", own="round_other", active=3):
        time.sleep(0.01)
        for _ in range(2):
            with clock.span("work", rows=2, bucket=8):
                time.sleep(0.02)
                # nothing reaches the counters before the outermost end
                assert read() == ({}, {})
        time.sleep(0.01)
    wall = time.perf_counter() - t0
    seconds, spans = read()
    assert spans == {"phase=work": 2.0, "phase=round_other": 1.0}
    assert seconds["phase=work"] >= 0.04
    assert seconds["phase=round_other"] >= 0.02
    # the two phases split the round: nothing is booked twice
    # (booked twice, the 0.04 s of work would put the sum past the wall)
    assert sum(seconds.values()) <= wall
    assert sum(seconds.values()) == pytest.approx(wall, abs=0.02)


def test_publish_inside_a_round_hands_over_what_is_booked_so_far():
    clock, read = labelled_clock()
    with clock.span("round", own="round_other"):
        with clock.span("work"):
            time.sleep(0.01)
        clock.publish()
        seconds, spans = read()
        assert spans["phase=work"] == 1.0
        assert not spans.get("phase=round_other")    # the round is open
        assert seconds["phase=work"] >= 0.01
        early = seconds.get("phase=round_other", 0.0)
        with clock.span("work"):
            pass
        time.sleep(0.01)
    seconds, spans = read()
    assert spans == {"phase=work": 2.0, "phase=round_other": 1.0}
    assert seconds["phase=round_other"] >= early + 0.01


def test_a_lone_span_publishes_at_once_and_adds_up():
    clock, read = labelled_clock()
    for _ in range(3):
        with clock.span("wait"):
            pass
    seconds, spans = read()
    assert spans == {"phase=wait": 3.0}
    assert 0.0 <= seconds["phase=wait"] < 0.5


def test_an_exception_leaves_the_books_straight():
    clock, read = labelled_clock()
    with pytest.raises(KeyError):
        with clock.span("round", own="round_other"):
            with clock.span("work"):
                raise KeyError("inside")
    seconds, spans = read()
    assert spans == {"phase=work": 1.0, "phase=round_other": 1.0}
    # and the thread's stack is empty again: the next span is outermost
    with clock.span("work"):
        pass
    assert read()[1]["phase=work"] == 2.0


def test_each_thread_nests_under_its_own_spans():
    clock, read = labelled_clock()
    inside = threading.Event()
    leave = threading.Event()

    def other():
        with clock.span("theirs"):
            inside.set()
            assert leave.wait(timeout=30)

    t = threading.Thread(target=other)
    with clock.span("mine"):
        t.start()
        assert inside.wait(timeout=30)
    # this thread's outermost span closed: its books are out, while the
    # other thread's open span has charged this thread nothing
    assert read()[1] == {"phase=mine": 1.0}
    leave.set()
    t.join(timeout=30)
    assert not t.is_alive()
    assert read()[1] == {"phase=mine": 1.0, "phase=theirs": 1.0}


def test_a_phase_may_have_no_counter_and_the_sink_is_asked_once():
    reg = MetricsRegistry()
    blocks = reg.counter("blocks_total", "")
    asked = []

    def sink(phase):
        asked.append(phase)
        return ([], [blocks] if phase == "dispatch" else [])

    clock = SpanClock("fit:", sink)
    for _ in range(4):
        with clock.span("dispatch", steps=2):
            pass
        with clock.span("fetch_wait"):
            pass
    assert blocks.value == 4.0
    assert sorted(asked) == ["dispatch", "fetch_wait"]


def test_observe_many_is_many_observes_under_one_lock():
    values = [0.2, 3.0, 7.5, 7.5, 40.0, 900.0, 1e6]
    one, many = Histogram(seed="h"), Histogram(seed="h")
    for v in values:
        one.observe(v)
    many.observe_many(values)
    many.observe_many([])
    assert many._snapshot() == one._snapshot()
    assert many.count == len(values)
    fam = MetricsRegistry().histogram("gap_ms", "")
    fam.observe_many(values)
    assert fam.count == len(values) and fam.quantile(0.9) == 1e6


# ------------------------------------------------- the server's loop thread
#: (prompt tokens, max_tokens): a 20-token prompt is three chunk rounds at
#: ``prefill_chunk=8``, and six requests through four slots queue
SHAPES = ((20, 6), (5, 5), (12, 7), (3, 4), (9, 6), (17, 3))
STEPS = 2


@pytest.fixture(scope="module")
def lm():
    return tiny_lm(max_length=64)


def serve(lm, registry=None):
    """Six requests through a four-slot server, to completion. Returns the
    server's registry snapshot, ``stats()``, the per-request records and
    the wall seconds from construction to the last result."""
    rs = np.random.RandomState(7)
    t0 = time.monotonic()
    srv = GenerationServer(lm, V, slots=4, page_size=4, prefill_chunk=8,
                           steps_per_dispatch=STEPS, registry=registry)
    try:
        records = []
        for plen, ntok in SHAPES:
            rec = {"max_tokens": ntok, "t_submit": time.monotonic()}
            fut = srv.submit(rs.randint(0, V, plen), ntok)
            fut.add_done_callback(
                lambda _f, rec=rec: rec.setdefault("t_done",
                                                   time.monotonic()))
            rec["future"] = fut
            records.append(rec)
        for rec in records:
            rec["tokens"] = rec["future"].result(timeout=150)
            rec["t_first"] = rec["future"]._t_first
        wall = time.monotonic() - t0
        # the loop's last tick publishes when it ends, after the result
        deadline = time.monotonic() + 30
        while (srv.metrics.snapshot()["generation_loop_spans_total"].get(
                "phase=tick_other", 0) < srv.stats()["decode_steps"]
               and time.monotonic() < deadline):
            time.sleep(0.005)
        return srv.metrics.snapshot(), srv.stats(), records, wall
    finally:
        srv.close()


@pytest.fixture(scope="module")
def served(lm):
    return serve(lm)


@pytest.mark.generation
@pytest.mark.parametrize("phase", [p for p in PHASES if p != "housekeeping"])
def test_every_phase_of_a_working_server_has_seconds(served, phase):
    snap = served[0]
    assert snap["generation_loop_seconds_total"]["phase=" + phase] > 0
    assert snap["generation_loop_spans_total"]["phase=" + phase] >= 1


@pytest.mark.generation
def test_the_phases_are_a_closed_set(served):
    snap = served[0]
    assert set(snap["generation_loop_seconds_total"]) <= {
        "phase=" + p for p in PHASES}


@pytest.mark.generation
def test_dispatch_spans_count_dispatches(served):
    snap, stats, records, _ = served
    spans = snap["generation_loop_spans_total"]
    assert spans["phase=decode_dispatch"] \
        == snap["generation_decode_steps_total"] == stats["decode_steps"] > 0
    assert spans["phase=prefill_dispatch"] \
        == snap["generation_prefill_rounds_total"]
    # more dispatches than waves: the 20-token prompt alone is three
    # chunk rounds
    assert spans["phase=prefill_dispatch"] \
        >= spans["phase=prefill_keys"] + 2
    for other in ("fetch", "build"):
        assert spans["phase=prefill_" + other] \
            == spans["phase=prefill_dispatch"]
    # a program's first call is a span of its own inside its dispatch's:
    # the decode program and a prefill program a column bucket
    assert 2 <= spans["phase=compile"] <= 1 + spans["phase=prefill_dispatch"]
    seconds = snap["generation_loop_seconds_total"]
    assert seconds["phase=compile"] > 10 * (
        seconds["phase=prefill_dispatch"] + seconds["phase=decode_dispatch"])


@pytest.mark.generation
def test_busy_seconds_are_the_serving_phases_once(served):
    snap, stats, _, wall = served
    seconds = snap["generation_loop_seconds_total"]
    serving = sum(v for k, v in seconds.items()
                  if k[len("phase="):].startswith(BUSY_PHASES))
    busy = snap["generation_busy_seconds_total"]
    assert busy == pytest.approx(serving, rel=1e-9)
    # a wave of several rows is charged once: busy time fits in wall time
    assert 0 < busy <= wall
    assert stats["tokens_per_s"] == pytest.approx(
        stats["tokens_generated"] / busy)
    working = sum(v for k, v in seconds.items() if k != "phase=idle_wait")
    assert working <= wall
    assert seconds["phase=tick_other"] < 0.05 * working


@pytest.mark.generation
def test_queue_wait_is_observed_once_a_request(served):
    snap, _, records, _ = served
    waits = snap["generation_queue_wait_ms"]
    assert waits["count"] == len(records)
    # a request's wait in the queue is the first part of its TTFT
    ttft = [1e3 * (r["t_first"] - r["t_submit"]) for r in records]
    assert 0 < waits["sum"] <= sum(ttft)
    assert waits["quantiles"][0.9] <= max(ttft)


@pytest.mark.generation
def test_token_gap_counts_deliveries_after_the_first(served):
    snap, _, records, _ = served
    gaps = snap["generation_token_gap_ms"]
    # a request's first delivery is its first token (no gap before it);
    # every later one is what a decode fetch handed it: STEPS tokens
    later = sum(math.ceil((r["max_tokens"] - 1) / STEPS) for r in records)
    assert [len(r["tokens"]) for r in records] \
        == [r["max_tokens"] for r in records]
    assert gaps["count"] == later
    spent = sum(1e3 * (r["t_done"] - r["t_first"]) for r in records)
    assert 0 < gaps["sum"] <= spent


@pytest.mark.generation
def test_both_registries_hold_the_loop_families(lm):
    before = global_registry().snapshot()
    own = MetricsRegistry()
    snap, stats, records, _ = serve(lm, registry=own)
    after = global_registry().snapshot()

    def grew(name, key):
        return after[name][key] - before.get(name, {}).get(key, 0)

    assert grew("generation_loop_spans_total", "phase=decode_dispatch") \
        >= stats["decode_steps"]
    assert grew("generation_queue_wait_ms", "count") >= len(records)
    assert grew("generation_token_gap_ms", "count") \
        >= snap["generation_token_gap_ms"]["count"]
    # busy seconds stay the server's own
    assert "generation_busy_seconds_total" in snap


# ------------------------------------------------------ under the profiler
def traced_host_lines(tmp_path, work):
    """Runs ``work()`` under a profiler session as the benchmark starts
    one (python tracer off) and returns the host plane's lines (one a
    thread), each a list of ``(name, start_ns, end_ns, stats)``."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    options.enable_hlo_proto = False
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        out = work()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                          / "*.xplane.pb"))
    lines = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:       # several threads share a name
            lines.append([
                (e.name, e.start_ns, e.start_ns + e.duration_ns,
                 dict(e.stats)) for e in line.events])
    return out, lines


def inside(child, parents):
    return any(p[1] <= child[1] and child[2] <= p[2] for p in parents)


@pytest.mark.generation
def test_a_profiler_session_sees_the_loop_threads_phases(lm, tmp_path):
    (snap, stats, _, _), lines = traced_host_lines(
        tmp_path, lambda: serve(lm))
    loop_lines = [evs for evs in lines
                  if any(n == "gen:tick" for n, *_ in evs)]
    assert len(loop_lines) == 1, "gen:tick on one host line: the loop thread"
    events = loop_lines[0]
    ticks = [e for e in events if e[0] == "gen:tick"]
    assert ticks and all("active" in e[3] for e in ticks)
    named = {e[0] for e in events if e[0].startswith("gen:")}
    assert named == {"gen:tick"} | {
        "gen:" + p for p in PHASES if p != "tick_other"}
    # every phase but the idle wait is a child of a tick, on its clock
    for e in events:
        if e[0].startswith("gen:") and e[0] not in ("gen:tick",
                                                    "gen:idle_wait"):
            assert inside(e, ticks), e[0]
    fetches = [e for e in events if e[0] == "gen:decode_fetch"]
    assert len(fetches) == stats["decode_steps"]
    dispatches = [e for e in events if e[0] == "gen:decode_dispatch"]
    assert all(e[3]["steps"] == STEPS and 1 <= e[3]["rows"] <= 4
               for e in dispatches)
    assert all(e[3]["bucket"] >= 8 and e[3]["rows"] >= 1
               for e in events if e[0] == "gen:prefill_dispatch")
    # the counters and the trace time the same ticks: a span reads its
    # clock inside its annotation, so the trace's tick is the longer by
    # the few microseconds between the two
    seconds = snap["generation_loop_seconds_total"]
    working = sum(v for k, v in seconds.items() if k != "phase=idle_wait")
    traced = sum(e[2] - e[1] for e in ticks) / 1e9
    assert working <= traced < working + 0.02
    assert not any(n.startswith("bench:")
                   for evs in lines for n, *_ in evs)


def test_a_profiler_session_sees_the_fit_drivers_spans(tmp_path):
    from deeplearning4j_tpu.nn.conf.builders import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.nn.updater import Adam
    from deeplearning4j_tpu.optimize.listeners import (
        CollectScoresIterationListener)

    conf = (NeuralNetConfiguration.builder().seed(1).updater(Adam(0.02))
            .weight_init("xavier").activation("relu")
            .list(DenseLayer(n_out=8),
                  OutputLayer(n_out=3, loss="mcxent", activation="softmax"))
            .set_input_type(InputType.feed_forward(4)).build())
    net = MultiLayerNetwork(conf).init()
    net.set_listeners(CollectScoresIterationListener())
    rs = np.random.RandomState(0)
    data = [DataSet(rs.rand(16, 4).astype(np.float32),
                    np.eye(3, dtype=np.float32)[rs.randint(0, 3, 16)])
            for _ in range(6)]
    reg = global_registry()
    before = {n: reg.counter(n).value for n in (
        "fit_blocks_dispatched_total", "fit_fetch_wait_seconds_total")}
    _, lines = traced_host_lines(
        tmp_path, lambda: net.fit(data, epochs=1, fused_steps=2))
    events = [e for evs in lines for e in evs if e[0].startswith("fit:")]
    blocks = [e for e in events if e[0] == "fit:block"]
    assert [e[3]["step_num"] for e in blocks] == [0, 2, 4]
    for name in ("fit:dispatch", "fit:fetch_wait"):
        found = [e for e in events if e[0] == name]
        assert len(found) == 3 and all(inside(e, blocks) for e in found)
    assert reg.counter("fit_blocks_dispatched_total").value \
        - before["fit_blocks_dispatched_total"] == 3
    assert reg.counter("fit_fetch_wait_seconds_total").value \
        > before["fit_fetch_wait_seconds_total"]
