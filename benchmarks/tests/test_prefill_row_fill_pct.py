"""The ``prefill_row_fill_pct`` reader on made-up counters: nothing where
the program publishes none (the parent of the PR that brought it), the
share where it does. (A file of its own: ``test_harness.py`` belongs to
the accepted benchmark.)"""

import pytest

from benchmarks.harness import loader


def test_prefill_row_fill_reads_nothing_without_the_counter(monkeypatch):
    from deeplearning4j_tpu.metrics import registry

    reader = loader.load_module("metrics", "prefill_row_fill_pct")
    empty = registry.MetricsRegistry()
    monkeypatch.setattr(registry, "global_registry", lambda: empty)
    assert reader.read(None) is None
    # a counter of that name without the labels is not this counter
    empty.counter("generation_prefill_rounds_total", "").inc(5)
    assert reader.read(None) is None
    rows = empty.counter("generation_prefill_rows_total", "",
                         labels=("kind",))
    rows.labels(kind="computed").inc(0)
    assert reader.read(None) is None          # nothing dispatched yet
    rows.labels(kind="computed").inc(40)
    assert reader.read(None) is None          # and nothing admitted
    rows.labels(kind="admitted").inc(26)
    assert reader.read(None) == pytest.approx(65.0)


def test_the_entry_lists_both_serve_cells():
    bench = loader.load_benchmark()
    entry = [m for m in bench["per_layer"]
             if m["name"] == "prefill_row_fill_pct"]
    assert len(entry) == 1 and bench["per_layer"][-1] is entry[0]
    serve = [m for m in bench["end_to_end"]
             if m["name"] == "serve_tokens_per_s"][0]
    assert entry[0]["moves"] == "serve_tokens_per_s"
    assert entry[0]["workloads"] == serve["workloads"]
