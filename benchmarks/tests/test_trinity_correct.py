"""``correct`` has been shown to fail for
``trinity_large_serve_closed16_1k_14k``: a sound rehearsal is correct, and
one with the timed path broken underneath is not (``trinity_faults.py``: a
sliding layer that attends to everything, a window class's page freed one
page early, the full layer rotated, the gate left out, the selection bias
in the weights, the chosen scores not renormalised). Driven like
``test_deepseek_correct.py``: the harness's look for a chip skipped
(``rehearse``), the rest of a run on the CPU at the files' ``rehearse``
sizes, in float32, with limits stated here for those sizes. Also the new
configuration's operation counts against hand-worked values, its file
against the catalog's rules, and the two new readers on made-up numbers."""

import json

import pytest

from benchmarks import run as runner
from benchmarks.harness import loader
from benchmarks.tests import trinity_faults

CELL = "trinity_large_serve_closed16_1k_14k"
CONFIG = "trinity_large_preview"
LATENT = "deepseekv2_serve_closed16_doc4k"
#: on the CPU the program's float32 is the reference's: a served greedy
#: token is the reference's best to rounding
LIMITS = {"served_logit_gap": 1e-4, "bad_completions": 0}


def drive(seed, seconds=1.5):
    run = runner.make_run(loader.load_benchmark(), CELL, seed, seconds,
                          False, rehearse=True)
    run.limits = dict(LIMITS)
    meas, state, _, compared = runner.execute(run)
    return meas, compared, state


def test_sound_run_is_correct():
    meas, compared, _ = drive(3_800_000_123)
    assert compared.correct, compared.as_dict()
    assert meas["attempted"] > 0 and meas["failed"] == 0
    assert {"serve_tokens_per_s", "ttft_p90_ms", "tpot_p90_ms",
            "setup_s"} <= set(meas["end_to_end"])
    # the program's counters reached the registry a reader can reach: pages
    # behind the 32-token window were freed, and a window layer's view is
    # three pages wide whatever the table's 16
    share = loader.load_module("metrics",
                               "kv_resident_vs_uniform_pct").read(None)
    assert 10.0 < share < 100.0
    live = loader.load_module("metrics", "window_view_live_pct").read(None)
    assert 10.0 < live <= 100.0
    view = loader.load_module("metrics", "paged_view_live_pct").read(None)
    assert 0.0 < view < live
    held = loader.load_module("metrics", "moe_held_assignment_pct").read(None)
    assert 5.0 < held < 95.0


@pytest.mark.parametrize("fault", trinity_faults.FAULTS)
def test_a_planted_fault_is_not_correct(fault, monkeypatch):
    trinity_faults.plant(fault, monkeypatch)
    _, compared, _ = drive(3_800_000_124)
    assert not compared.correct
    assert compared.as_dict()["served_logit_gap"]["value"] \
        > 10 * LIMITS["served_logit_gap"]


# ------------------------------------------------------------------ counts
def test_operation_counts_against_hand_worked_values():
    ops = loader.load_module("ops", CONFIG)
    sizes = loader.load_json("configs", CONFIG)["sizes"]
    # the issue's arithmetic, in millions of parameters
    attn = 3 * 3072 * 6144 + 2 * 3072 * 1024
    assert ops.attention_matmul_params(sizes) == attn
    assert round(attn / 1e6, 3) == 62.915
    assert round(ops.mlp_params(sizes) / 1e6, 3) == 113.246
    assert round(ops.shared_expert_params(sizes) / 1e6, 2) == 28.31
    assert round(ops.expert_params(sizes) / 1e6, 2) == 28.31
    assert round(ops.router_params(sizes) / 1e6, 3) == 0.786
    assert ops.experts_per_token_here(sizes) == 0.5
    assert round(ops.parameter_count(sizes) / 1e6, 1) == 4321.9
    assert round(2 * ops.parameter_count(sizes) / 1e9, 2) == 8.64
    # a key and a value for 8 heads of 128 at 2 bytes, one layer
    assert ops.kv_bytes_per_token(sizes) == 4096
    per_token = 5 * attn + 113_246_208 + 4 * (
        786_432 + 28_311_552 + 0.5 * 28_311_552) + 3072 * 25024
    assert ops.matmul_params_per_token(sizes) == per_token
    assert ops.read_ops_per_key(sizes) == 4 * 48 * 128
    # a sliding layer sees min(context, 4096) keys, the full one all
    assert ops.token_flops(sizes, 100) == 2 * per_token + 24576 * 5 * 100
    assert ops.token_flops(sizes, 10_000) == 2 * per_token \
        + 24576 * (10_000 + 4 * 4096)
    assert ops.requests_flops(sizes, [(4090, 12)]) == sum(
        ops.token_flops(sizes, c) for c in range(4090, 4102))
    assert ops.requests_flops(sizes, [(1, 300)]) == sum(
        ops.token_flops(sizes, c) for c in range(1, 301))
    assert ops.visible_keys(5, 7, 6) == sum(
        min(c, 6) for c in range(5, 12))
    # the floor: attention, the dense block, routers, shared experts, head
    floor = 2 * (5 * attn + 113_246_208 + 4 * (786_432 + 28_311_552)
                 + 3072 * 25024)
    assert ops.decode_step_min_bytes(sizes) == floor
    assert round(floor / 1e9, 2) == 1.24


def test_the_configuration_keeps_every_published_number():
    """Every number of the catalog row's ``config`` under the same key;
    depth with its layer types, the dense blocks, the experts held and the
    vocabulary differ, and ``reduced`` says so; the builder's arguments and
    the reference's sizes carry the same numbers."""
    cfg = loader.load_json("configs", CONFIG)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    row = [r for r in rows if r["name"] == "Trinity-Large-Preview"][0]
    assert cfg["source"] == row["source_url"]
    differ = sorted(k for k, v in row["config"].items() if cfg.get(k) != v)
    assert differ == sorted(cfg["reduced"]) == [
        "layer_types", "num_dense_layers", "num_experts",
        "num_hidden_layers", "vocab_size"]
    a, s = cfg["arguments"], cfg["sizes"]
    for key, arg, size in (
            ("hidden_size", "d_model", "d_model"),
            ("num_attention_heads", "n_heads", "heads"),
            ("num_key_value_heads", "n_kv_heads", "kv_heads"),
            ("head_dim", "head_dim", "head_dim"),
            ("sliding_window", "window", "window"),
            ("intermediate_size", "mlp_width", "mlp_width"),
            ("moe_intermediate_size", "expert_width", "expert_width"),
            ("num_experts_per_tok", "top_k", "top_k"),
            ("route_scale", "routed_scale", "routed_scale"),
            ("num_dense_layers", "dense_layers", "dense_layers"),
            ("rope_theta", "rope_theta", "rope_theta"),
            ("rms_norm_eps", "rms_eps", "rms_eps"),
            ("vocab_size", "num_labels", "vocab")):
        assert cfg[key] == a[arg] == s[size], key
    assert cfg["num_hidden_layers"] == s["layers"] == len(a["layer_types"])
    assert [k.split("_")[0] for k in cfg["layer_types"]] \
        == list(a["layer_types"]) == s["layer_types"]
    pub = cfg["published"]
    assert (pub["num_hidden_layers"], pub["num_dense_layers"],
            pub["num_experts"], pub["vocab_size"]) == (60, 6, 256, 200192)
    # the router keeps its published width; an eighth of the experts held
    assert a["n_experts"] == s["experts"] == pub["num_experts"]
    assert a["experts_held"] == s["experts_held"] == [0, 32]
    assert cfg["num_experts"] == 32 == 256 // 8
    assert a["shared_width"] == cfg["num_shared_experts"] \
        * cfg["moe_intermediate_size"]
    assert a["embedding_multiplier"] == s["embedding_multiplier"] \
        == cfg["hidden_size"] ** 0.5
    assert cfg["score_func"] == "sigmoid" and cfg["route_norm"]
    # the floors of the guide: a whole period (three sliding, one full) and
    # four layers after the dense one, at least 8 experts, an eighth of the
    # vocabulary
    assert s["layer_types"][-4:] == ["sliding"] * 3 + ["full"]
    assert s["layers"] - s["dense_layers"] >= 4 and s["experts_held"][1] >= 8
    assert 8 * s["vocab"] >= pub["vocab_size"]
    # the traffic fits what is served, and both pools hold what it can ask
    t = loader.load_json("traffic", "closed16_1k_14k")
    assert t["prompt_tokens"]["max"] + t["max_tokens"]["max"] \
        == cfg["max_cache"] == a["max_length"] == s["positions"] == 14720
    srv = t["server"]
    assert not srv["prefix_cache"] and cfg["max_cache"] % srv["page_size"] == 0
    assert srv["pages"]["full"] == srv["slots"] * 14720 // 16 + 1
    assert srv["pages"]["window"] == srv["slots"] * -(-(
        4096 + srv["prefill_chunk"] + 14) // 16) + 1
    assert max(t["warmup_prompts"][:-1]) > srv["prefill_chunk"] // 2


def test_the_entries_are_appended_and_nothing_else_changed():
    """After what the benchmark had (not "last": a later PR appends its
    own entries behind these)."""
    bench = loader.load_benchmark()

    def index(section, name):
        return [e["name"] for e in bench[section]].index(name)

    assert index("configs", CONFIG) > index("configs", "deepseek_v2")
    assert index("workloads", CELL) > index("workloads", LATENT)
    cell = bench["workloads"][index("workloads", CELL)]
    assert cell["chips"] == 1 and cell["traffic"] == "closed16_1k_14k"
    assert index("per_layer", "window_view_live_pct") \
        > index("per_layer", "kv_resident_vs_uniform_pct") \
        > index("per_layer", "sampler_select_step_pct")
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert by_name["kv_resident_vs_uniform_pct"]["workloads"] == [CELL]
    assert by_name["kv_resident_vs_uniform_pct"]["layer"] \
        == by_name["prefix_reuse_pct"]["layer"]
    assert by_name["window_view_live_pct"]["workloads"] == [CELL]
    assert by_name["window_view_live_pct"]["layer"] \
        == by_name["paged_view_live_pct"]["layer"]
    lists = {m["name"]: m.get("workloads") for m in
             bench["end_to_end"] + bench["per_layer"]}
    for name in ("serve_tokens_per_s", "decode_slot_occupancy_pct",
                 "server_busy_share_pct", "ttft_p90_ms", "tpot_p90_ms",
                 "serve_mfu_pct", "device_idle_pct.serve",
                 "hbm_peak_pct.serve", "serve_hbm_stream_pct",
                 "moe_tokens_per_expert_call", "prefill_row_fill_pct",
                 "paged_view_live_pct", "moe_held_assignment_pct",
                 "loop_host_share_pct", "decode_host_ms_per_dispatch",
                 "prefill_host_ms_per_dispatch", "queue_wait_p90_ms",
                 "token_gap_p90_ms", "sampler_select_step_pct"):
        assert lists[name][-1] == CELL and LATENT in lists[name], name
    for name in ("paged_attn_roofline_pct", "train_mfu_pct",
                 "prefix_reuse_pct"):
        assert CELL not in lists[name], name


def test_the_new_readers_read_nothing_where_nothing_is_published(
        monkeypatch):
    from deeplearning4j_tpu.metrics import registry

    share = loader.load_module("metrics", "kv_resident_vs_uniform_pct")
    live = loader.load_module("metrics", "window_view_live_pct")
    empty = registry.MetricsRegistry()
    monkeypatch.setattr(registry, "global_registry", lambda: empty)
    assert share.read(None) is None and live.read(None) is None
    held = empty.counter("generation_kv_resident_bytes_total", "",
                         labels=("layout",))
    held.labels(layout="classes").inc(300)
    assert share.read(None) is None          # nothing to hold it against
    held.labels(layout="uniform").inc(1200)
    assert share.read(None) == pytest.approx(25.0)
    for kind, n in (("live", 4096), ("viewed", 4128)):
        fam = empty.counter(f"generation_cache_kv_{kind}_tokens_total", "",
                            labels=("cache", "program"))
        fam.labels(cache="full", program="decode").inc(7)
        assert live.read(None) is None       # the full class's is not it
        fam.labels(cache="window", program="decode").inc(n)
    assert live.read(None) == pytest.approx(100.0 * 4096 / 4128)
