"""The ``moe_expert_stream_pct`` reader on made-up facts and registry
snapshots: nothing where the program publishes no gauge or counters (the
parent of the PR that brought it), the share where it does. (A file of
its own: ``test_harness.py`` belongs to the accepted benchmark.)"""

import types

import pytest

from benchmarks.harness import loader

CELL = "qwen3next_serve_closed32_chat1k"


def _ctx(**facts):
    return types.SimpleNamespace(
        facts=dict({"decode_dispatches": 500, "window_s": 50.0}, **facts),
        peaks={"hbm_bytes_s": 819e9}, run=types.SimpleNamespace(chips=1))


def test_the_share_of_the_touched_experts_bytes(monkeypatch):
    from deeplearning4j_tpu.metrics import registry

    reader = loader.load_module("metrics", "moe_expert_stream_pct")
    reg = registry.MetricsRegistry()
    monkeypatch.setattr(registry, "global_registry", lambda: reg)
    assert reader.read(_ctx()) is None
    calls = reg.counter("generation_moe_expert_calls_total", "",
                        labels=("program",))
    steps = reg.counter("generation_decode_steps_total", "")
    assert reader.read(_ctx()) is None        # nothing dispatched yet
    calls.labels(program="prefill").inc(4096)
    steps.inc(600)
    assert reader.read(_ctx()) is None        # no decode calls, no gauge
    calls.labels(program="decode").inc(600 * 2880)
    assert reader.read(_ctx()) is None        # the parent: no gauge
    reg.gauge("generation_moe_expert_bytes", "").set(6291456)
    # 2,880 expert calls a dispatch (60 experts x 12 layers x 4 steps) x
    # 500 dispatches x 6.29 MB over 50 s x 819 GB/s
    want = 100.0 * 2880 * 500 * 6291456 / (50.0 * 819e9)
    assert reader.read(_ctx()) == pytest.approx(want)
    assert 0 < want < 100
    # the window's dispatches scale it; a window with none reads nothing
    assert reader.read(_ctx(decode_dispatches=250)) \
        == pytest.approx(want / 2)
    assert reader.read(_ctx(decode_dispatches=0)) is None


def test_the_entry_lists_the_new_cell_alone():
    bench = loader.load_benchmark()
    entry = [m for m in bench["per_layer"]
             if m["name"] == "moe_expert_stream_pct"]
    assert len(entry) == 1
    assert entry[0]["moves"] == "serve_tokens_per_s"
    assert entry[0]["layer"] == "expert layer"
    assert entry[0]["source"] == "program_counter"
    assert entry[0]["workloads"] == [CELL]
