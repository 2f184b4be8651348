"""Loader names, percentile and window arithmetic, the trace reduction on
the small recorded trace beside this file, operation counts against
hand-worked values."""

import math
import os

import pytest

from benchmarks.harness import clock, loader, trace

HERE = os.path.dirname(os.path.abspath(__file__))


# ------------------------------------------------------------------ loader
@pytest.mark.parametrize("bad", ["a/b", "../x", "a b", "a,b", "", ".x",
                                 "-x", "x" * 65, "café", "a\tb", None])
def test_loader_refuses_a_name_outside_the_alphabet(bad):
    with pytest.raises(ValueError):
        loader.check_name(bad)
    with pytest.raises(ValueError):
        loader.load_json("configs", bad)
    with pytest.raises(ValueError):
        loader.load_module("metrics", bad)


@pytest.mark.parametrize("good", ["a", "resnet50_fit_b256",
                                  "device_idle_pct.train", "1x", "_x",
                                  "a-b.c_d", "x" * 64])
def test_loader_takes_a_name(good):
    assert loader.check_name(good) == good


def test_every_entry_of_the_benchmark_finds_its_files():
    bench = loader.load_benchmark()
    for cell in bench["workloads"]:
        w = loader.load_json("workloads", cell["name"])
        assert (w["config"], w["traffic"], w["chips"]) == (
            cell["config"], cell["traffic"], cell["chips"])
        t = loader.load_json("traffic", cell["traffic"])
        assert hasattr(loader.load_module("drivers", t["driver"]), "window")
        loader.load_module("references", cell["config"])
        loader.load_module("ops", cell["config"])
        reports = {m["name"] for g in ("end_to_end", "per_layer")
                   for m in loader.metrics_for(bench, g, cell)}
        assert "setup_s" in reports and len(reports) >= 3
    for cfg in bench["configs"]:
        assert loader.load_json("configs", cfg["name"])["reduced"] \
            == cfg["reduced"]
    for m in bench["per_layer"]:
        assert callable(loader.load_module("metrics", m["name"]).read)


# ------------------------------------------------------------------- clock
def test_percentile_is_numpys_linear_one():
    import numpy as np

    xs = [5.0, 1.0, 9.0, 3.0, 7.0, 11.0, 2.0]
    for q in (0, 10, 50, 90, 95, 100):
        assert clock.percentile(xs, q) == pytest.approx(
            float(np.percentile(xs, q)))
    assert clock.percentile([4.0], 90) == 4.0
    with pytest.raises(ValueError):
        clock.percentile([], 90)


def test_rate_is_over_all_the_time_of_the_window():
    assert clock.rate(1000, 10.0, 14.0) == 250.0
    with pytest.raises(ValueError):
        clock.rate(1, 2.0, 2.0)


def test_spread_is_the_contracts():
    import statistics

    xs = [100.0, 101.0, 99.0, 102.0, 98.0, 100.5]
    q1, _, q3 = statistics.quantiles(xs, n=4)
    assert clock.spread(xs) == (q3 - q1) / statistics.median(xs)


def test_serve_window_arithmetic_on_made_up_stamps():
    import numpy as np

    drv = loader.load_module("drivers", "serve_closed")
    traffic = {"server": {"slots": 2, "steps_per_dispatch": 4,
                          "prefill_chunk": 256}}

    def rec(t_submit, t_first, t_done, n, plen=10, err=False):
        r = {"t_submit": t_submit, "t_first": t_first, "t_done": t_done,
             "tokens": np.zeros(n, int), "plen": plen}
        if err:
            r["error"] = "x"
        return r

    records = [
        rec(9.0, 9.5, 10.5, 11),              # submitted before the window
        rec(10.0, 10.2, 11.2, 11),            # ttft 200 ms, tpot 100 ms
        rec(11.0, 11.4, 13.4, 5),             # ttft 400 ms, tpot 500 ms
        rec(12.0, 12.1, 20.0, 80),            # ttft 100 ms, done after close
        rec(13.0, None, 13.5, 0, err=True),   # failed: no place in a tail
        rec(15.0, 15.1, 16.0, 3),             # submitted after the close
    ]
    s0 = {"tokens_generated": 100, "prefills": 5, "decode_steps": 20,
          "tokens_per_s": 50.0}
    s1 = {"tokens_generated": 400, "prefills": 9, "decode_steps": 70,
          "tokens_per_s": 80.0}
    f = drv.reduce_window(records, s0, s1, 10.0, 14.0, traffic)
    assert (f["attempted"], f["failed"]) == (4, 1)
    assert f["ttft_samples"] == 3 and f["tpot_samples"] == 3
    e = f["end_to_end"]
    assert e["serve_tokens_per_s"] == 300 / 4.0
    assert e["ttft_p90_ms"] == pytest.approx(
        clock.percentile([200.0, 400.0, 100.0], 90))
    assert e["tpot_p90_ms"] == pytest.approx(
        clock.percentile([100.0, 100.0, 500.0], 90))
    assert f["busy_s"] == pytest.approx(400 / 80.0 - 100 / 50.0)
    occ = loader.load_module("metrics", "decode_slot_occupancy_pct")
    import types

    got = occ.read(types.SimpleNamespace(facts=f))
    assert got == pytest.approx(100.0 * (300 - 4) / (50 * 4 * 2))


def test_work_spans_split_prefill_and_decode_by_stamps():
    import numpy as np

    drv = loader.load_module("drivers", "serve_closed")
    r = {"t_first": 10.0, "t_done": 20.0, "tokens": np.zeros(11, int),
         "plen": 100}
    # decoded tokens 1..10 at 11, 12, ... 20 s; contexts 101..110
    assert drv.work_spans([r], 9.0, 21.0, 256) == [(1, 100, 256),
                                                   (101, 10, 1)]
    assert drv.work_spans([r], 12.5, 15.5, 256) == [(103, 3, 1)]
    assert drv.work_spans([r], 30.0, 31.0, 256) == []


# ------------------------------------------------------------------- trace
def test_trace_reduction_on_the_recorded_trace():
    """Four dispatches of a jitted scan of three matmuls and one Pallas
    add, 5 ms of host sleep between them (record_trace.py, TPU v5 lite)."""
    r = trace.reduce_xplane(os.path.join(HERE, "data",
                                         "small_trace.xplane.pb"), 1)
    assert r["chips"] == 1 and r["mosaic_calls"] == 4
    assert r["ops"]["fixture_add"] == pytest.approx(r["mosaic_s"])
    assert r["mosaic_s"] == pytest.approx(7.163e-6, rel=1e-3)
    # the while encloses its body: exclusive time leaves it next to nothing
    assert r["ops"]["while"] < 1e-6 < r["ops"]["convolution_tanh_fusion"]
    assert r["busy_s"] == pytest.approx(4.0862e-05, rel=1e-3)
    assert sum(r["ops"].values()) == pytest.approx(r["busy_s"], rel=1e-6)
    assert 0.015 < r["window_s"] < 0.03
    assert r["device_ops"][0][0] == "convolution_tanh_fusion"
    assert r["idle_gaps"][0][0] == "host_gap"
    assert r["idle_gaps"][0][1] == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-2)


def test_trace_reduction_arithmetic_on_made_up_events():
    mosaic = ('%k.2 = f32[] custom-call(), '
              'custom_call_target="tpu_custom_call"')
    ev = [[(0, 100, "%a.1 = f32[] fusion(x)"),
           (200, 100, "%while.1 = () while(x)"),
           (210, 50, mosaic),
           (600, 100, "%a.7 = f32[] fusion(x)")]]
    spans = [(0, 1000, "bench:slice"), (90, 120, "bench:wait"),
             (300, 290, "bench:client_wait")]
    r = trace.reduce_events(ev, spans)
    assert r["window_s"] == pytest.approx(1e-6)
    assert r["busy_s"] == pytest.approx(3e-7)
    assert r["ops"] == {"a": pytest.approx(2e-7), "k": pytest.approx(5e-8),
                        "while": pytest.approx(5e-8)}
    assert r["mosaic_s"] == pytest.approx(5e-8) and r["mosaic_calls"] == 1
    gaps = dict(r["idle_gaps"])
    assert gaps["wait"] == pytest.approx(1e-7)
    assert gaps["client_wait"] == pytest.approx(3e-7)
    assert gaps["unattributed"] == pytest.approx(3e-7)
    with pytest.raises(ValueError):
        trace.reduce_events([[]], [])
    assert trace.short_name("%fusion.12 = f32[2]{0} fusion(%p)") == "fusion"


# --------------------------------------------------------------------- ops
def test_resnet50_operation_counts_against_hand_worked_values():
    ops = loader.load_module("ops", "resnet50_imagenet")
    sizes = loader.load_json("configs", "resnet50_imagenet")["sizes"]
    # the paper's geometry: 3.86 G multiply-adds with the head (the
    # "4.1 G" usually quoted counts the same network a little differently)
    assert ops.forward_macs(sizes, paper_geometry=True) == 3_857_973_248
    table = {r["name"]: r for r in ops.layer_table(sizes)}
    # the zoo's: 230 -> 112 -> 55 -> 28 -> 14 -> 7 -> 4
    assert table["stem_cnn1"]["hout"] == 112
    assert table["stem_maxpool1"]["hout"] == 55
    assert [table[f"res{s}a_2a"]["hout"] for s in (2, 3, 4, 5)] \
        == [28, 14, 7, 4]
    stem = 112 * 112 * 7 * 7 * 3 * 64
    # stage 2, by hand: block a = 1x1 64->64, 3x3 64->64, 1x1 64->256 and
    # the 1x1 64->256 shortcut at 28x28; blocks b, c take 256 channels in
    px = 28 * 28
    a = px * (64 * 64 + 9 * 64 * 64 + 64 * 256 + 64 * 256)
    bc = px * (256 * 64 + 9 * 64 * 64 + 64 * 256)
    got = sum(r["hout"] ** 2 * r["k"] ** 2 * r["cin"] * r["cout"]
              for r in ops.layer_table(sizes) if r["kind"] == "conv"
              and r["name"].startswith(("res2", "stem")))
    assert got == stem + a + 2 * bc
    assert ops.forward_macs(sizes) == 1_110_573_056
    assert ops.train_flops_per_sample(sizes) == 6 * 1_110_573_056
    assert ops.parameter_count(sizes) == 25_583_592


def test_lm_operation_counts_against_hand_worked_values():
    ops = loader.load_module("ops", "cerebras_gpt_590m")
    sizes = loader.load_json("configs", "cerebras_gpt_590m")["sizes"]
    blocks = 18 * (4 * 1536 * 1536 + 2 * 1536 * 6144)
    assert ops.block_matmul_params(sizes) == blocks == 509_607_936
    assert ops.matmul_params_per_token(sizes) == blocks + 1536 * 50257 \
        == 586_802_688
    assert ops.kv_bytes_per_token(sizes) == 2 * 18 * 1536 * 4 == 221_184
    assert ops.token_flops(sizes, 100) == 2 * 586_802_688 \
        + 4 * 100 * 1536 * 18
    # a prompt of 3 tokens is contexts 1, 2, 3
    assert ops.requests_flops(sizes, [(1, 3)]) == pytest.approx(
        sum(ops.token_flops(sizes, c) for c in (1, 2, 3)))
    need = ops.paged_read(sizes, [(101, 2, 1), (1, 300, 256)])
    assert need["bytes"] == 221_184 * (101 + 102 + 256 + 300)
    assert need["ops"] == pytest.approx(
        4 * 1536 * 18 * (101 + 102 + sum(range(1, 301))))
    assert math.isclose(ops.parameter_count(sizes), 664e6, rel_tol=1e-3)
