"""``correct`` has been shown to fail for
``olmohybrid7b_serve_closed16_reason3k``: a sound rehearsal is correct, and
one with the timed path broken underneath is not (``olmo_hybrid_faults.py``:
beta not doubled, the gate before the norm, the decay after the delta
correction, the full layers' norm per head, a slot's state not reset, the
convolution's tail zeroed after prefill). Driven like
``test_trinity_correct.py``: the harness's look for a chip skipped
(``rehearse``), the rest of a run on the CPU at the files' ``rehearse``
sizes, in float32, with limits stated here for those sizes. Also the new
configuration's operation counts against hand-worked values, its file
against the catalog's rules, and the entries it appends."""

import pytest

from benchmarks import run as runner
from benchmarks.harness import loader
from benchmarks.tests import olmo_hybrid_faults

CELL = "olmohybrid7b_serve_closed16_reason3k"
CONFIG = "olmo_hybrid_7b"
TRAFFIC = "closed16_reason3k"
PUBLISHED_SOURCE = ("https://huggingface.co/allenai/Olmo-Hybrid-7B/blob/main/"
                    "config.json")
#: the published config.json's keys that say something about the model's
#: shape, as published
PUBLISHED = {
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
    "intermediate_size": 11008, "num_hidden_layers": 32,
    "num_attention_heads": 30, "num_key_value_heads": 30,
    "hidden_act": "silu", "max_position_embeddings": 65536,
    "attention_bias": False, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False,
    "layer_types": (["linear_attention"] * 3 + ["full_attention"]) * 8,
    "linear_num_key_heads": 30, "linear_num_value_heads": 30,
    "linear_key_head_dim": 96, "linear_value_head_dim": 192,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None}}
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
    meas, compared, _ = drive(4_000_000_123)
    assert compared.correct, compared.as_dict()
    assert meas["attempted"] > 0 and meas["failed"] == 0
    assert {"serve_tokens_per_s", "ttft_p90_ms", "tpot_p90_ms",
            "setup_s"} <= set(meas["end_to_end"])


@pytest.mark.parametrize("fault", olmo_hybrid_faults.FAULTS)
def test_a_planted_fault_is_not_correct(fault, monkeypatch):
    olmo_hybrid_faults.plant(fault, monkeypatch)
    _, compared, _ = drive(4_000_000_124)
    assert not compared.correct
    assert compared.as_dict()["served_logit_gap"]["value"] \
        > 10 * LIMITS["served_logit_gap"]


# ------------------------------------------------------------------ counts
def test_operation_counts_against_hand_worked_values():
    ops = loader.load_module("ops", CONFIG)
    sizes = loader.load_json("configs", CONFIG)["sizes"]
    # the configuration's arithmetic, in millions of parameters
    lin = 3840 * (2880 + 2880 + 5760 + 5760 + 30 + 30) + 5760 * 3840 \
        + 11520 * 4
    assert ops.linear_mixer_params(sizes) == lin
    assert round(lin / 1e6, 2) == 88.75
    assert round(ops.full_mixer_params(sizes) / 1e6, 2) == 58.98
    assert round(ops.mlp_params(sizes) / 1e6, 2) == 126.81
    assert round(ops.parameter_count(sizes) / 1e6, 1) == 4100.9
    assert round(2 * ops.parameter_count(sizes) / 1e9, 2) == 8.2
    # a key and a value of 3,840 in bfloat16 in each of 4 full layers
    assert ops.kv_bytes_per_token(sizes) == 61440
    # a slot's state: 12 x (30 x 192 x 96 x 4 + 3 x 11,520 x 2) bytes
    assert ops.linear_state_bytes(sizes) == 12 * (
        30 * 192 * 96 * 4 + 3 * 11520 * 2)
    assert round(16 * ops.linear_state_bytes(sizes) / 1e9, 2) == 0.44
    per_token = 12 * lin + 4 * 4 * 3840 * 3840 + 16 * 3 * 3840 * 11008 \
        + 3840 * 100352
    assert ops.matmul_params_per_token(sizes) == per_token
    delta = 12 * 3 * 2 * 30 * 96 * 192
    assert ops.delta_rule_ops_per_token(sizes) == delta
    assert ops.read_ops_per_key(sizes) == 4 * 30 * 128
    assert ops.token_flops(sizes, 100) == 2 * per_token + delta \
        + 4 * 15360 * 100
    assert ops.requests_flops(sizes, [(1, 300)]) == sum(
        ops.token_flops(sizes, c) for c in range(1, 301))
    # the read: 4 decoded tokens at contexts 10-13, a prompt of 40 in chunks
    # of 16 reading its context once a chunk
    need = ops.paged_read(sizes, [(10, 4, 1), (1, 40, 16)])
    assert need["ops"] == 4 * 15360 * (10 + 11 + 12 + 13 + sum(
        range(1, 41)))
    assert need["bytes"] == 61440 * (10 + 11 + 12 + 13 + 16 + 32 + 40)
    # the floor: the 16 blocks and the head, 7.43 GB
    assert round(ops.decode_step_min_bytes(sizes) / 1e9, 2) == 7.43


def test_the_configuration_keeps_every_published_number():
    """Every number of the published ``config.json`` under the same key;
    depth with its layer types differ, and ``reduced`` says so; the
    model's arguments and the reference's sizes carry the same
    numbers."""
    cfg = loader.load_json("configs", CONFIG)
    assert cfg["source"] == PUBLISHED_SOURCE
    differ = sorted(k for k, v in PUBLISHED.items() if cfg.get(k) != v)
    assert differ == sorted(cfg["reduced"]) == ["layer_types",
                                                "num_hidden_layers"]
    a, s = cfg["arguments"], cfg["sizes"]
    for key, arg, size in (
            ("hidden_size", "d_model", "d_model"),
            ("num_attention_heads", "n_heads", "heads"),
            ("linear_num_key_heads", "linear_heads", "linear_heads"),
            ("linear_num_value_heads", "linear_heads", "linear_heads"),
            ("linear_key_head_dim", "linear_key_dim", "linear_key_dim"),
            ("linear_value_head_dim", "linear_value_dim",
             "linear_value_dim"),
            ("linear_conv_kernel_dim", "linear_conv", "linear_conv"),
            ("linear_allow_neg_eigval", "allow_neg_eigval",
             "allow_neg_eigval"),
            ("intermediate_size", "mlp_width", "mlp_width"),
            ("rms_norm_eps", "rms_eps", "rms_eps"),
            ("vocab_size", "num_labels", "vocab")):
        assert cfg[key] == a[arg] == s[size], key
    assert cfg["num_key_value_heads"] == cfg["num_attention_heads"]
    assert a["head_dim"] == s["head_dim"] == 3840 // 30
    assert cfg["num_hidden_layers"] == s["layers"] == len(a["layer_types"])
    assert [k.split("_")[0] for k in cfg["layer_types"]] \
        == list(a["layer_types"]) == s["layer_types"]
    assert cfg["layer_types"] == PUBLISHED["layer_types"][:16]
    assert cfg["published"]["num_hidden_layers"] == 32
    assert cfg["rope_parameters"] == {"rope_theta": None}
    # the floors of the guide: whole periods (three linear, one full), all
    # of them after no leading dense block; the whole vocabulary
    assert s["layer_types"] == ["linear"] * 3 + ["full"] + \
        s["layer_types"][4:] and s["layers"] % 4 == 0 and s["layers"] >= 4
    # the traffic fits what is served, and the pool holds what it can ask
    t = loader.load_json("traffic", TRAFFIC)
    assert t["prompt_tokens"]["max"] + t["max_tokens"]["max"] \
        == cfg["max_cache"] == a["max_length"] == s["positions"] == 4096
    srv = t["server"]
    assert not srv["prefix_cache"] and cfg["max_cache"] % srv["page_size"] == 0
    assert srv["pages"] == srv["slots"] * 4096 // 16 + 1
    assert srv["prefill_chunk"] == t["prompt_tokens"]["max"]
    assert max(t["warmup_prompts"]) > srv["prefill_chunk"] // 2


def test_the_entries_are_appended_and_nothing_else_changed():
    """After what the benchmark had (not "last": a later PR appends its
    own entries behind these)."""
    bench = loader.load_benchmark()

    def index(section, name):
        return [e["name"] for e in bench[section]].index(name)

    assert index("configs", CONFIG) > index("configs", "trinity_large_preview")
    assert index("workloads", CELL) > index(
        "workloads", "trinity_large_serve_closed16_1k_14k")
    cell = bench["workloads"][index("workloads", CELL)]
    assert cell["chips"] == 1 and cell["traffic"] == TRAFFIC
    lists = {m["name"]: m.get("workloads") for m in
             bench["end_to_end"] + bench["per_layer"]}
    for name in ("serve_tokens_per_s", "decode_slot_occupancy_pct",
                 "server_busy_share_pct", "ttft_p90_ms", "tpot_p90_ms",
                 "serve_mfu_pct", "device_idle_pct.serve",
                 "hbm_peak_pct.serve", "serve_hbm_stream_pct",
                 "prefill_row_fill_pct", "paged_view_live_pct",
                 "loop_host_share_pct", "decode_host_ms_per_dispatch",
                 "prefill_host_ms_per_dispatch", "queue_wait_p90_ms",
                 "token_gap_p90_ms", "sampler_select_step_pct"):
        assert lists[name].index(CELL) > lists[name].index(
            "trinity_large_serve_closed16_1k_14k"), name
    for name in ("train_mfu_pct", "prefix_reuse_pct",
                 "moe_tokens_per_expert_call", "moe_held_assignment_pct",
                 "kv_resident_vs_uniform_pct", "window_view_live_pct",
                 "prefill_blocked_read_pct"):
        assert CELL not in lists[name], name
    roofline = lists["paged_attn_roofline_pct"]
    assert roofline[0] == "cgpt590m_serve_closed16"
    assert roofline.index(CELL) > 0
