"""The ``sampler_select_step_pct`` reader on made-up counters: nothing where
the program publishes none (the parent of the PR that brought it), the
share where it does. (A file of its own: ``test_harness.py`` belongs to
the accepted benchmark.)"""

import pytest

from benchmarks.harness import loader


def test_the_share_of_selecting_steps(monkeypatch):
    from deeplearning4j_tpu.metrics import registry

    reader = loader.load_module("metrics", "sampler_select_step_pct")
    reg = registry.MetricsRegistry()
    monkeypatch.setattr(registry, "global_registry", lambda: reg)
    assert reader.read(None) is None
    # other counters of the server are not this one
    reg.counter("generation_decode_steps_total", "").inc(5)
    assert reader.read(None) is None
    steps = reg.counter("generation_sampler_steps_total", "",
                        labels=("path",))
    assert reader.read(None) is None          # nothing dispatched yet
    steps.labels(path="greedy").inc(8)
    assert reader.read(None) == 0.0           # an all-greedy load
    steps.labels(path="select").inc(24)
    assert reader.read(None) == pytest.approx(75.0)


def test_the_entry_lists_the_serve_cells():
    bench = loader.load_benchmark()
    entry = [m for m in bench["per_layer"]
             if m["name"] == "sampler_select_step_pct"]
    assert len(entry) == 1
    serve = [m for m in bench["end_to_end"]
             if m["name"] == "serve_tokens_per_s"][0]
    assert entry[0]["moves"] == "serve_tokens_per_s"
    assert entry[0]["workloads"] == serve["workloads"]
