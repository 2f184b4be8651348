"""The five readers of the server loop's phases and stamps on made-up
registries: nothing where the program publishes no such family (the
parent of the PR that brought them), the defined ratio or quantile where
it does. (A file of its own: ``test_harness.py`` belongs to the accepted
benchmark.)"""

import pytest

from benchmarks.harness import loader

READERS = ("loop_host_share_pct", "decode_host_ms_per_dispatch",
           "prefill_host_ms_per_dispatch", "queue_wait_p90_ms",
           "token_gap_p90_ms")

#: seconds and spans of a made-up run, by phase
SECONDS = {"idle_wait": 30.0, "admit": 0.2, "prefill_keys": 0.3,
           "prefill_build": 0.1, "prefill_dispatch": 0.8,
           "prefill_fetch": 3.0, "prefill_commit": 0.6,
           "decode_reserve": 0.05, "decode_dispatch": 0.45,
           "decode_fetch": 7.0, "decode_walk": 0.5, "housekeeping": 0.01,
           "compile": 120.0, "tick_other": 0.04}
SPANS = {"prefill_dispatch": 40, "decode_dispatch": 20, "decode_fetch": 20}


@pytest.fixture
def made_up(monkeypatch):
    from deeplearning4j_tpu.metrics import registry

    reg = registry.MetricsRegistry()
    monkeypatch.setattr(registry, "global_registry", lambda: reg)
    return reg


def publish_phases(reg):
    seconds = reg.counter("generation_loop_seconds_total", "",
                          labels=("phase",))
    spans = reg.counter("generation_loop_spans_total", "",
                        labels=("phase",))
    for phase, s in SECONDS.items():
        seconds.labels(phase=phase).inc(s)
    for phase, n in SPANS.items():
        spans.labels(phase=phase).inc(n)


@pytest.mark.parametrize("name", READERS)
def test_a_reader_reads_nothing_without_its_family(made_up, name):
    reader = loader.load_module("metrics", name)
    assert reader.read(None) is None
    # other families of the server are not these
    made_up.counter("generation_decode_steps_total", "").inc(5)
    made_up.histogram("latency_ms", "").observe(3.0)
    assert reader.read(None) is None


@pytest.mark.parametrize("name", READERS)
def test_a_reader_reads_nothing_from_a_family_nothing_was_booked_in(
        made_up, name):
    made_up.counter("generation_loop_seconds_total", "", labels=("phase",))
    made_up.counter("generation_loop_spans_total", "", labels=("phase",))
    made_up.histogram("generation_queue_wait_ms", "")
    made_up.histogram("generation_token_gap_ms", "")
    assert loader.load_module("metrics", name).read(None) is None


def test_the_three_phase_readers_on_made_up_phases(made_up):
    publish_phases(made_up)
    # neither the idle wait nor a program's first call is serving time
    working = sum(SECONDS.values()) - SECONDS["idle_wait"] \
        - SECONDS["compile"]
    share = loader.load_module("metrics", "loop_host_share_pct").read(None)
    assert share == pytest.approx(100.0 * (working - 10.0) / working)
    decode = loader.load_module(
        "metrics", "decode_host_ms_per_dispatch").read(None)
    assert decode == pytest.approx(1e3 * (0.05 + 0.45 + 0.5) / 20)
    prefill = loader.load_module(
        "metrics", "prefill_host_ms_per_dispatch").read(None)
    assert prefill == pytest.approx(
        1e3 * (0.2 + 0.3 + 0.1 + 0.8 + 0.6) / 40)


@pytest.mark.parametrize("name,family", [
    ("queue_wait_p90_ms", "generation_queue_wait_ms"),
    ("token_gap_p90_ms", "generation_token_gap_ms")])
def test_the_two_stamp_readers_give_the_histograms_p90(made_up, name,
                                                       family):
    made_up.histogram(family, "").observe_many(range(1, 101))
    assert loader.load_module("metrics", name).read(None) == 90.0


def test_each_reader_has_its_entry_over_the_serve_cells():
    # by name, wherever a later PR's entries put them in the list
    bench = loader.load_benchmark()
    serve = [m for m in bench["end_to_end"]
             if m["name"] == "serve_tokens_per_s"][0]
    for name in READERS:
        m, = [m for m in bench["per_layer"] if m["name"] == name]
        assert set(serve["workloads"]) <= set(m["workloads"])
        assert (m["moves"], m["layer"], m["source"], m["better"]) == (
            "serve_tokens_per_s", "generation server", "program_counter",
            "lower")
