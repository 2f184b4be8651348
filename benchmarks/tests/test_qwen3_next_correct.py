"""``correct`` has been shown to fail for
``qwen3next_serve_closed32_chat1k``: a sound rehearsal is correct, and one
with the timed path broken underneath is not (``qwen3_next_faults.py``:
the key heads tiled, all channels rotated, the shared expert ungated, the
norms multiplying by w, beta doubled, the attention's gate dropped, the
routing weights not renormalised). Driven like
``test_olmo_hybrid_correct.py``: the harness's look for a chip skipped
(``rehearse``), the rest of a run on the CPU at the files' ``rehearse``
sizes, in float32, with limits stated here for those sizes. Also the new
configuration's operation counts against hand-worked values, its file
against the catalog's rules, and the entries it appends."""

import pytest

from benchmarks import run as runner
from benchmarks.harness import loader
from benchmarks.tests import qwen3_next_faults

CELL = "qwen3next_serve_closed32_chat1k"
CONFIG = "qwen3_next_80b_a3b_instruct"
TRAFFIC = "closed32_chat1k"
PUBLISHED_SOURCE = ("https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/"
                    "blob/main/config.json")
#: the catalog row's config, as published
PUBLISHED = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512, "num_experts_per_tok": 10,
    "num_hidden_layers": 48, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936}
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
    meas, compared, _ = drive(4_500_000_123)
    assert compared.correct, compared.as_dict()
    assert meas["attempted"] > 0 and meas["failed"] == 0
    assert {"serve_tokens_per_s", "ttft_p90_ms", "tpot_p90_ms",
            "setup_s"} <= set(meas["end_to_end"])


@pytest.mark.parametrize("fault", qwen3_next_faults.FAULTS)
def test_a_planted_fault_is_not_correct(fault, monkeypatch):
    qwen3_next_faults.plant(fault, monkeypatch)
    _, compared, _ = drive(4_500_000_124)
    assert not compared.correct
    assert compared.as_dict()["served_logit_gap"]["value"] \
        > 10 * LIMITS["served_logit_gap"]


# ------------------------------------------------------------------ counts
def test_operation_counts_against_hand_worked_values():
    ops = loader.load_module("ops", CONFIG)
    sizes = loader.load_json("configs", CONFIG)["sizes"]
    # the configuration's arithmetic, in millions of parameters
    lin = 2048 * (2048 + 2048 + 4096 + 4096 + 32 + 32) + 4096 * 2048 \
        + 8192 * 4
    assert ops.linear_mixer_params(sizes) == lin
    assert round(lin / 1e6, 2) == 33.72
    assert round(ops.full_mixer_params(sizes) / 1e6, 3) == 27.263
    assert ops.expert_params(sizes) == 3 * 2048 * 512
    assert ops.router_params(sizes) == 2048 * 512
    assert round(ops.parameter_count(sizes) / 1e6, 1) == 5423.1
    assert round(2 * ops.parameter_count(sizes) / 1e9, 2) == 10.85
    # a key and a value of 2 x 256 in bfloat16 in each of 3 full layers
    assert ops.kv_bytes_per_token(sizes) == 6144
    # a slot's state: 9 x (32 x 128 x 128 x 4 + 3 x 8,192 x 2) bytes
    assert ops.linear_state_bytes(sizes) == 9 * (
        32 * 128 * 128 * 4 + 3 * 8192 * 2)
    assert round(32 * ops.linear_state_bytes(sizes) / 1e9, 2) == 0.62
    assert ops.experts_per_token_here(sizes) == 2.5
    per_token = 9 * lin + 3 * (3 * 2048 * 4096 + 2 * 2048 * 512) \
        + 12 * (2048 * 512 + 3 * 2048 * 512 + 2048
                + 2.5 * 3 * 2048 * 512) + 2048 * 37984
    assert ops.matmul_params_per_token(sizes) == per_token
    delta = 9 * 3 * 2 * 32 * 128 * 128
    assert ops.delta_rule_ops_per_token(sizes) == delta
    assert ops.read_ops_per_key(sizes) == 4 * 16 * 256
    assert ops.token_flops(sizes, 100) == 2 * per_token + delta \
        + 3 * 4 * 4096 * 100
    assert ops.requests_flops(sizes, [(1, 300)]) == pytest.approx(sum(
        ops.token_flops(sizes, c) for c in range(1, 301)))
    need = ops.paged_read(sizes, [(10, 4, 1), (1, 40, 16)])
    assert need["ops"] == 3 * 4 * 4096 * (10 + 11 + 12 + 13 + sum(
        range(1, 41)))
    assert need["bytes"] == 6144 * (10 + 11 + 12 + 13 + 16 + 32 + 40)
    # one held expert, and the floor: every block's weights but the
    # routed experts', and the head
    assert ops.held_expert_bytes(sizes) == 6291456
    assert round(ops.decode_step_min_bytes(sizes) / 1e9, 3) == 1.027


def test_the_configuration_keeps_every_published_number():
    """Every number of the catalog row's config under the same key; depth
    with its layer types, the experts held and the vocabulary differ, and
    ``reduced`` says so; the model's arguments and the reference's sizes
    carry the same numbers."""
    cfg = loader.load_json("configs", CONFIG)
    assert cfg["source"] == PUBLISHED_SOURCE
    differ = sorted(k for k, v in PUBLISHED.items() if cfg.get(k) != v)
    assert differ == ["num_experts", "num_hidden_layers", "vocab_size"]
    assert sorted(cfg["reduced"]) == sorted(differ + ["layer_types"])
    a, s = cfg["arguments"], cfg["sizes"]
    for key, arg, size in (
            ("hidden_size", "d_model", "d_model"),
            ("num_attention_heads", "n_heads", "heads"),
            ("num_key_value_heads", "n_kv_heads", "kv_heads"),
            ("head_dim", "head_dim", "head_dim"),
            ("rope_theta", "rope_theta", "rope_theta"),
            ("linear_num_key_heads", "linear_key_heads", "linear_key_heads"),
            ("linear_num_value_heads", "linear_heads", "linear_heads"),
            ("linear_key_head_dim", "linear_key_dim", "linear_key_dim"),
            ("linear_value_head_dim", "linear_value_dim",
             "linear_value_dim"),
            ("linear_conv_kernel_dim", "linear_conv", "linear_conv"),
            ("moe_intermediate_size", "expert_width", "expert_width"),
            ("shared_expert_intermediate_size", "shared_width",
             "shared_width"),
            ("num_experts_per_tok", "top_k", "top_k"),
            ("rms_norm_eps", "rms_eps", "rms_eps"),
            ("vocab_size", "num_labels", "vocab")):
        assert cfg[key] == a[arg] == s[size], key
    # the router keeps its published width; the file holds 128 of 512
    assert a["n_experts"] == s["experts"] == PUBLISHED["num_experts"]
    assert a["experts_held"] == s["experts_held"] == [0, cfg["num_experts"]]
    assert cfg["published"]["num_experts"] == 512
    assert a["rope_dims"] == s["rope_dims"] \
        == int(PUBLISHED["head_dim"] * PUBLISHED["partial_rotary_factor"])
    assert cfg["num_hidden_layers"] == s["layers"] == len(a["layer_types"])
    assert [k.split("_")[0] for k in cfg["layer_types"]] \
        == list(a["layer_types"]) == s["layer_types"]
    # the floors of the guide: whole periods (three linear, one full), at
    # least 8 experts a layer, at least an eighth of the vocabulary
    assert s["layer_types"] == ["linear", "linear", "linear", "full"] * 3
    assert cfg["num_experts"] >= 8
    assert 8 * cfg["vocab_size"] >= PUBLISHED["vocab_size"]
    assert cfg["published"]["vocab_size"] == PUBLISHED["vocab_size"]
    # the traffic fits what is served, and the pool holds what it can ask
    t = loader.load_json("traffic", TRAFFIC)
    assert t["prompt_tokens"]["max"] + t["max_tokens"]["max"] \
        == cfg["max_cache"] == a["max_length"] == s["positions"] == 4096
    srv = t["server"]
    assert not srv["prefix_cache"] and cfg["max_cache"] % srv["page_size"] == 0
    assert srv["pages"] == srv["slots"] * 4096 // 16 + 1
    assert srv["slots"] == t["clients"] == 32
    assert srv["prefill_chunk"] == 1024
    assert max(t["warmup_prompts"]) > 2 * srv["prefill_chunk"]


def test_the_entries_are_appended_and_nothing_else_changed():
    """After what the benchmark had (not "last": a later PR appends its
    own entries behind these)."""
    bench = loader.load_benchmark()

    def index(section, name):
        return [e["name"] for e in bench[section]].index(name)

    assert index("configs", CONFIG) > index("configs", "olmo_hybrid_7b")
    assert index("workloads", CELL) > index(
        "workloads", "olmohybrid7b_serve_closed16_reason3k")
    cell = bench["workloads"][index("workloads", CELL)]
    assert cell["chips"] == 1 and cell["traffic"] == TRAFFIC
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
                 "token_gap_p90_ms", "sampler_select_step_pct",
                 "prefill_blocked_read_pct", "embed_gather_row_pct"):
        assert lists[name].index(CELL) > lists[name].index(
            "granite4hs_serve_closed16_chat"), name
    for name in ("train_mfu_pct", "prefix_reuse_pct",
                 "kv_resident_vs_uniform_pct", "window_view_live_pct",
                 "paged_attn_roofline_pct"):
        assert CELL not in lists[name], name
    assert lists["moe_expert_stream_pct"] == [CELL]
