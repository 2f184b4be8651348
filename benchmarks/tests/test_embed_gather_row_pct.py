"""The ``embed_gather_row_pct`` reader on made-up counters: nothing where
the program publishes none (the parent of the PR that brought it), the
share where it does. (A file of its own: ``test_harness.py`` belongs to
the accepted benchmark.)"""

import pytest

from benchmarks.harness import loader


def test_the_share_of_gathered_rows(monkeypatch):
    from deeplearning4j_tpu.metrics import registry

    reader = loader.load_module("metrics", "embed_gather_row_pct")
    reg = registry.MetricsRegistry()
    monkeypatch.setattr(registry, "global_registry", lambda: reg)
    assert reader.read(None) is None
    # other counters of the server are not this one
    reg.counter("generation_prefill_rounds_total", "").inc(5)
    assert reader.read(None) is None
    rows = reg.counter("generation_embed_rows_total", "", labels=("path",))
    assert reader.read(None) is None          # nothing dispatched yet
    rows.labels(path="gather").inc(64)
    assert reader.read(None) == 100.0         # every row gathered
    rows.labels(path="one_hot").inc(192)
    assert reader.read(None) == pytest.approx(25.0)


def test_the_entry_lists_the_serve_cells():
    bench = loader.load_benchmark()
    entry = [m for m in bench["per_layer"]
             if m["name"] == "embed_gather_row_pct"]
    assert len(entry) == 1
    assert entry[0]["moves"] == "serve_tokens_per_s"
    assert entry[0]["layer"] == "LM step, embedding"
    assert entry[0]["source"] == "program_counter"
    serve = [m for m in bench["end_to_end"]
             if m["name"] == "serve_tokens_per_s"][0]["workloads"]
    assert sorted(entry[0]["workloads"]) == sorted(serve)
