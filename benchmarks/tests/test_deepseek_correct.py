"""``correct`` has been shown to fail for ``deepseekv2_serve_closed16_doc4k``:
a sound rehearsal is correct, and one with the timed path broken underneath
is not — the shared rotary key cached unrotated, the latent cached before
its norm, selection without the group limit, weights renormalised, a stale
page after a copy-on-write, a later prefill round rotated from position 0
(``deepseek_faults.py``). Driven like ``test_falcon_correct.py``: the
harness's look for a chip skipped (``rehearse``), the rest of a run on the
CPU at the files' ``rehearse`` sizes, in float32 (the configuration's
``rehearse_note``), with limits stated here for those sizes. Also the new
configuration's operation counts against hand-worked values, its file
against the catalog's rules, and the two new readers on made-up numbers."""

import json

import pytest

from benchmarks import run as runner
from benchmarks.harness import loader
from benchmarks.tests import deepseek_faults

CELL = "deepseekv2_serve_closed16_doc4k"
CONFIG = "deepseek_v2"
GRANITE = "granite4hs_serve_closed16_chat"
#: on the CPU the program's float32 is the reference's: a served greedy
#: token is the reference's best to rounding (the absorbed form sums in
#: another order than the reference's plain one)
LIMITS = {"served_logit_gap": 1e-4, "bad_completions": 0}


def drive(seed, seconds=1.5):
    run = runner.make_run(loader.load_benchmark(), CELL, seed, seconds,
                          False, rehearse=True)
    run.limits = dict(LIMITS)
    meas, state, _, compared = runner.execute(run)
    return meas, compared, state


def test_sound_run_is_correct():
    meas, compared, _ = drive(3_400_000_123)
    assert compared.correct, compared.as_dict()
    assert meas["attempted"] > 0 and meas["failed"] == 0
    assert {"serve_tokens_per_s", "ttft_p90_ms", "tpot_p90_ms",
            "setup_s"} <= set(meas["end_to_end"])
    # the program's counters reached the registry a reader can reach: the
    # document's four pages came from the cache for every request but the
    # first wave's, and 8 of 16 experts are held
    reuse = loader.load_module("metrics", "prefix_reuse_pct").read(None)
    assert 10.0 < reuse < 100.0
    held = loader.load_module("metrics", "moe_held_assignment_pct").read(None)
    assert 5.0 < held < 95.0
    view = loader.load_module("metrics", "paged_view_live_pct").read(None)
    assert 0.0 < view < 100.0


@pytest.mark.parametrize("fault", [
    "key_cached_unrotated", "latent_cached_before_its_norm",
    "selection_without_the_group_limit", "weights_renormalised",
    "stale_page_after_a_copy", "rotation_restarted"])
def test_a_planted_fault_is_not_correct(fault, monkeypatch):
    deepseek_faults.plant(fault, monkeypatch)
    _, compared, _ = drive(3_400_000_124)
    assert not compared.correct
    assert compared.as_dict()["served_logit_gap"]["value"] \
        > 10 * LIMITS["served_logit_gap"]


# ------------------------------------------------------------------ counts
def test_operation_counts_against_hand_worked_values():
    ops = loader.load_module("ops", CONFIG)
    sizes = loader.load_json("configs", CONFIG)["sizes"]
    # the issue's arithmetic, in millions of parameters
    attn = 5120 * 1536 + 1536 * 24576 + 5120 * 576 + 512 * 32768 \
        + 16384 * 5120
    assert ops.attention_matmul_params(sizes) == attn
    assert round(attn / 1e6, 2) == 149.23
    assert round(ops.mlp_params(sizes) / 1e6, 2) == 188.74
    assert round(ops.shared_expert_params(sizes) / 1e6, 2) == 47.19
    assert round(ops.expert_params(sizes) / 1e6, 2) == 23.59
    assert round(ops.router_params(sizes) / 1e6, 2) == 0.82
    assert ops.experts_per_token_here(sizes) == 1.5
    assert round(ops.parameter_count(sizes) / 1e9, 2) == 5.16
    assert round(2 * ops.parameter_count(sizes) / 1e9, 2) == 10.33
    # five layers of one 576-wide row at 2 bytes, whatever the 128 heads
    assert ops.kv_bytes_per_token(sizes) == 5760
    per_token = 5 * attn + 188_743_680 + 4 * (
        819_200 + 47_185_920 + 1.5 * 23_592_960) + 5120 * 25600
    assert ops.matmul_params_per_token(sizes) == per_token
    # the absorbed read: 2 x 128 x (576 + 512) a cached position a layer
    assert ops.read_ops_per_position(sizes) == 5 * 2 * 128 * 1088
    assert ops.token_flops(sizes, 100) == 2 * per_token \
        + 100 * 5 * 278_528
    assert ops.requests_flops(sizes, [(7, 3)]) == sum(
        ops.token_flops(sizes, c) for c in (7, 8, 9))
    # a prompt longer than the cached document counts as its own part
    assert ops.requests_flops(sizes, [(1, 4100)]) == sum(
        ops.token_flops(sizes, c) for c in range(4097, 4101))
    assert ops.requests_flops(sizes, [(1, 600)]) == sum(
        ops.token_flops(sizes, c) for c in range(1, 601))
    # a decoded token reads its whole context; a prefilled chunk shares it
    assert ops.paged_read(sizes, [(10, 2, 1)])["bytes"] == 5760 * (10 + 11)
    assert ops.paged_read(sizes, [(1, 4396, 256)])["bytes"] \
        == 5760 * (4352 + 4396)
    # the floor: attention, the dense block, routers, shared experts, head:
    # 2.52 GB of the 10.33 GB held
    floor = 2 * (5 * attn + 188_743_680 + 4 * (819_200 + 47_185_920)
                 + 5120 * 25600)
    assert ops.decode_step_min_bytes(sizes) == floor
    assert round(floor / 1e9, 2) == 2.52


def test_the_configuration_keeps_every_published_number():
    """Every number of the catalog row's ``config`` under the same key;
    depth, experts held and vocabulary differ, and ``reduced`` says so; the
    builder's arguments and the reference's sizes carry the same numbers."""
    cfg = loader.load_json("configs", CONFIG)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    row = [r for r in rows if r["name"] == "DeepSeek-V2"][0]
    assert cfg["source"] == row["source_url"]
    differ = sorted(k for k, v in row["config"].items() if cfg.get(k) != v)
    assert differ == sorted(cfg["reduced"]) \
        == ["n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert cfg["rope_scaling"] == row["config"]["rope_scaling"]
    a, s = cfg["arguments"], cfg["sizes"]
    for key, arg, size in (
            ("hidden_size", "d_model", "d_model"),
            ("num_attention_heads", "n_heads", "heads"),
            ("q_lora_rank", "q_rank", "q_rank"),
            ("kv_lora_rank", "kv_rank", "kv_rank"),
            ("qk_nope_head_dim", "nope_dim", "nope_dim"),
            ("qk_rope_head_dim", "rope_dim", "rope_dim"),
            ("v_head_dim", "v_dim", "v_dim"),
            ("intermediate_size", "mlp_width", "mlp_width"),
            ("moe_intermediate_size", "expert_width", "expert_width"),
            ("num_experts_per_tok", "top_k", "top_k"),
            ("n_group", "expert_groups", "expert_groups"),
            ("topk_group", "groups_kept", "groups_kept"),
            ("routed_scaling_factor", "routed_scale", "routed_scale"),
            ("first_k_dense_replace", "dense_layers", "dense_layers"),
            ("rope_theta", "rope_theta", "rope_theta"),
            ("rms_norm_eps", "rms_eps", "rms_eps"),
            ("num_hidden_layers", "n_layers", "layers"),
            ("vocab_size", "num_labels", "vocab")):
        assert cfg[key] == a[arg] == s[size], key
    pub = cfg["published"]
    assert (pub["num_hidden_layers"], pub["n_routed_experts"],
            pub["vocab_size"]) == (60, 160, 102400)
    # the router keeps its published width; a whole number of groups held
    assert a["n_experts"] == s["experts"] == pub["n_routed_experts"]
    assert a["experts_held"] == s["experts_held"] == [0, 40]
    assert cfg["n_routed_experts"] == 40 == 2 * 160 // cfg["n_group"]
    assert a["shared_width"] == cfg["n_shared_experts"] \
        * cfg["moe_intermediate_size"]
    scaling = cfg["rope_scaling"]
    for key, arg in (("factor", "yarn_factor"),
                     ("original_max_position_embeddings",
                      "yarn_original_positions"),
                     ("beta_fast", "yarn_beta_fast"),
                     ("beta_slow", "yarn_beta_slow"),
                     ("mscale", "yarn_mscale"),
                     ("mscale_all_dim", "yarn_mscale_all_dim")):
        assert scaling[key] == a[arg] == s["yarn"][arg[len("yarn_"):]]
    assert not cfg["norm_topk_prob"] and cfg["scoring_func"] == "softmax"
    # the floors of the guide: a whole period and four layers after the
    # dense one, at least 8 experts, at least an eighth of the vocabulary
    assert s["layers"] - s["dense_layers"] >= 4 and s["experts_held"][1] >= 8
    assert 8 * s["vocab"] >= pub["vocab_size"]
    # the traffic fits what is served
    t = loader.load_json("traffic", "closed16_doc4k")
    assert t["prompt_tokens"]["max"] + t["max_tokens"]["max"] \
        <= cfg["max_cache"] == a["max_length"] == s["positions"]
    assert t["shared_prefix_tokens"] == s["cached_prefix_tokens"] == 4096
    assert t["shared_prefix_tokens"] % t["server"]["page_size"] == 0
    assert t["rehearse"]["shared_prefix_tokens"] \
        == cfg["rehearse"]["sizes"]["cached_prefix_tokens"]


def test_the_entries_are_appended_and_nothing_else_changed():
    """After what the benchmark had (not "last": a later PR appends its
    own entries behind these)."""
    bench = loader.load_benchmark()

    def index(section, name):
        return [e["name"] for e in bench[section]].index(name)

    assert index("configs", CONFIG) \
        > index("configs", "falcon_h1_34b_instruct")
    assert index("workloads", CELL) \
        > index("workloads", "falconh1_34b_serve_closed16_chat")
    cell = bench["workloads"][index("workloads", CELL)]
    assert cell["chips"] == 1 and cell["traffic"] == "closed16_doc4k"
    assert index("per_layer", "moe_held_assignment_pct") \
        > index("per_layer", "prefix_reuse_pct") \
        > index("per_layer", "paged_view_live_pct")
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert by_name["prefix_reuse_pct"]["workloads"] == [CELL]
    assert by_name["prefix_reuse_pct"]["layer"] == "page pool"
    assert by_name["moe_held_assignment_pct"]["workloads"] == [CELL, GRANITE]
    assert by_name["moe_held_assignment_pct"]["layer"] \
        == by_name["moe_tokens_per_expert_call"]["layer"]
    lists = {m["name"]: m.get("workloads") for m in
             bench["end_to_end"] + bench["per_layer"]}
    for name in ("serve_tokens_per_s", "decode_slot_occupancy_pct",
                 "server_busy_share_pct", "ttft_p90_ms", "tpot_p90_ms",
                 "serve_mfu_pct", "device_idle_pct.serve",
                 "hbm_peak_pct.serve", "serve_hbm_stream_pct",
                 "moe_tokens_per_expert_call", "prefill_row_fill_pct",
                 "paged_view_live_pct"):
        assert lists[name].index(CELL) > lists[name].index(GRANITE), name
    for name in ("paged_attn_roofline_pct", "train_mfu_pct"):
        assert CELL not in lists[name], name


def test_the_new_readers_read_nothing_where_nothing_is_published(
        monkeypatch):
    from deeplearning4j_tpu.metrics import registry

    reuse = loader.load_module("metrics", "prefix_reuse_pct")
    held = loader.load_module("metrics", "moe_held_assignment_pct")
    empty = registry.MetricsRegistry()
    monkeypatch.setattr(registry, "global_registry", lambda: empty)
    assert reuse.read(None) is None and held.read(None) is None
    # the parent's registry: reused tokens, but none counted as admitted
    empty.counter("generation_prefix_tokens_reused_total", "").inc(300)
    assert reuse.read(None) is None
    admitted = empty.counter("generation_prompt_tokens_admitted_total", "")
    assert reuse.read(None) is None         # nothing admitted yet
    admitted.inc(1200)
    assert reuse.read(None) == pytest.approx(25.0)
    pairs = empty.counter("generation_moe_assignments_total", "",
                          labels=("held", "program"))
    pairs.labels(held="yes", program="prefill").inc(10)
    assert held.read(None) is None          # not a decode dispatch's
    pairs.labels(held="yes", program="decode").inc(30)
    assert held.read(None) is None          # the absent ones not counted
    pairs.labels(held="no", program="decode").inc(90)
    assert held.read(None) == pytest.approx(25.0)
