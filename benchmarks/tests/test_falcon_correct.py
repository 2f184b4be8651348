"""``correct`` has been shown to fail for ``falconh1_34b_serve_closed16_chat``:
a sound rehearsal is correct, and one with the timed path broken underneath
is not — the per-slot state not reset at admission, or a later prefill
round of a long prompt rotated from position 0 instead of where the round
starts. Driven like ``test_granite_correct.py``: the harness's look for a
chip skipped (``rehearse``), the rest of a run on the CPU at the files'
``rehearse`` sizes, in float32 (the configuration's ``rehearse_note``), with
limits stated here for those sizes. Also the new configuration's operation
counts against hand-worked values, its file against the catalog's rules,
and the new reader on made-up numbers."""

import pytest

from benchmarks import run as runner
from benchmarks.harness import loader

CELL = "falconh1_34b_serve_closed16_chat"
CONFIG = "falcon_h1_34b_instruct"
#: on the CPU the program's float32 is the reference's: a served greedy
#: token is the reference's best to rounding
LIMITS = {"served_logit_gap": 1e-4, "bad_completions": 0}


def drive(seed, seconds=1.5):
    run = runner.make_run(loader.load_benchmark(), CELL, seed, seconds,
                          False, rehearse=True)
    run.limits = dict(LIMITS)
    meas, _, _, compared = runner.execute(run)
    return meas, compared


def test_sound_run_is_correct():
    meas, compared = drive(3_200_000_123)
    assert compared.correct, compared.as_dict()
    assert meas["attempted"] > 0 and meas["failed"] == 0
    assert {"serve_tokens_per_s", "ttft_p90_ms", "tpot_p90_ms",
            "setup_s"} <= set(meas["end_to_end"])
    # the program's counters reached the registry a reader can reach: two
    # paged layers of 4 slots x 2,048 positions viewed a micro-step
    share = loader.load_module("metrics", "paged_view_live_pct").read(None)
    assert 0.0 < share < 100.0


def test_slot_state_not_reset_at_admission_is_not_correct(monkeypatch):
    from deeplearning4j_tpu.parallel import generation

    real = generation._seed_extras
    monkeypatch.setattr(
        generation, "_seed_extras",
        lambda carry, pool, slot_st, stats, fresh=None: real(
            carry, pool, slot_st, stats))
    _, compared = drive(3_200_000_124)
    assert not compared.correct
    assert compared.as_dict()["served_logit_gap"]["value"] \
        > 10 * LIMITS["served_logit_gap"]


def test_a_later_prefill_round_rotated_from_zero_is_not_correct(monkeypatch):
    from deeplearning4j_tpu.nn.conf.layers.attention import (
        SelfAttentionLayer)

    real = SelfAttentionLayer._qkv
    # a chunk of more than one token is a prefill round: its first is
    # sound (it starts at 0), a later one is rotated as if it did too
    monkeypatch.setattr(
        SelfAttentionLayer, "_qkv",
        lambda self, p, x, start=None: real(
            self, p, x, None if x.shape[1] > 1 else start))
    _, compared = drive(3_200_000_125)
    assert not compared.correct
    assert compared.as_dict()["served_logit_gap"]["value"] \
        > 10 * LIMITS["served_logit_gap"]


# ------------------------------------------------------------------ counts
def test_operation_counts_against_hand_worked_values():
    ops = loader.load_module("ops", CONFIG)
    sizes = loader.load_json("configs", CONFIG)["sizes"]
    # the issue's arithmetic, in millions of parameters
    assert ops.mamba_matmul_params(sizes) == 5120 * 9248 + 4096 * 5120
    assert round(ops.mamba_matmul_params(sizes) / 1e6, 2) == 68.32
    assert ops.attention_matmul_params(sizes) \
        == 2 * 5120 * 2560 + 2 * 5120 * 512
    assert round(ops.attention_matmul_params(sizes) / 1e6, 2) == 31.46
    assert round(ops.mlp_params(sizes) / 1e6, 2) == 330.30
    assert round(ops.block_matmul_params(sizes) / 1e6, 2) == 430.08
    assert round(ops.parameter_count(sizes) / 1e9, 2) == 5.25
    # six layers of 4 key/value heads of 128 at 2 bytes: 12 KB a token
    assert ops.kv_bytes_per_token(sizes) == 12288
    per_token = 6 * 430_080_000 + 5120 * 261120
    assert ops.matmul_params_per_token(sizes) == per_token
    scan = 6 * (2 * 4 * 5120 + 4 * 4096 * 256)
    assert ops.token_flops(sizes, 100) == 2 * per_token + scan \
        + 4 * 100 * 2560 * 6
    assert ops.requests_flops(sizes, [(1, 3)]) == sum(
        ops.token_flops(sizes, c) for c in (1, 2, 3))
    # a decoded token reads its whole context; a prefilled chunk shares it
    assert ops.paged_read(sizes, [(10, 2, 1)])["bytes"] == 12288 * (10 + 11)
    assert ops.paged_read(sizes, [(1, 300, 256)])["bytes"] \
        == 12288 * (256 + 300)
    # the floor: the blocks' and the head's weights, 7.84 GB, under the
    # 10.51 GB held (the embedding's table is not in it)
    assert ops.decode_step_min_bytes(sizes) == 2 * per_token
    assert round(ops.decode_step_min_bytes(sizes) / 1e9, 2) == 7.83
    assert ops.decode_step_min_bytes(sizes) \
        < 2 * ops.parameter_count(sizes) - 2 * 5120 * 261120


def test_the_configuration_keeps_every_published_number():
    """Every number of the catalog row's ``config`` under the same key;
    only the depth differs, and ``reduced`` says so; the builder's
    arguments and the reference's sizes carry the same numbers."""
    import json

    cfg = loader.load_json("configs", CONFIG)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f]
    row = [r for r in rows if r["name"] == "Falcon-H1-34B-Instruct"][0]
    assert cfg["source"] == row["source_url"]
    differ = [k for k, v in row["config"].items() if cfg.get(k) != v]
    assert differ == cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["num_hidden_layers"] == cfg["sizes"]["layers"] \
        == cfg["arguments"]["n_layers"] == 6
    a, s = cfg["arguments"], cfg["sizes"]
    for key, arg, size in (
            ("hidden_size", "d_model", "d_model"),
            ("num_attention_heads", "n_heads", "heads"),
            ("num_key_value_heads", "n_kv_heads", "kv_heads"),
            ("head_dim", "head_dim", "head_dim"),
            ("rope_theta", "rope_theta", "rope_theta"),
            ("intermediate_size", "mlp_width", "mlp_width"),
            ("mamba_n_heads", "mamba_heads", "mamba_heads"),
            ("mamba_d_head", "mamba_head_dim", "mamba_head_dim"),
            ("mamba_d_state", "mamba_d_state", "d_state"),
            ("mamba_n_groups", "mamba_n_groups", "n_groups"),
            ("mamba_d_conv", "mamba_d_conv", "d_conv"),
            ("mamba_chunk_size", "mamba_chunk", "chunk"),
            ("vocab_size", "num_labels", "vocab"),
            ("rms_norm_eps", "rms_eps", "rms_eps")):
        assert cfg[key] == a[arg] == s[size], key
    for key in ("embedding_multiplier", "key_multiplier", "ssm_multipliers",
                "mlp_multipliers", "lm_head_multiplier",
                "attention_in_multiplier", "attention_out_multiplier",
                "ssm_in_multiplier", "ssm_out_multiplier"):
        assert cfg[key] == a[key] == s[key], key
    assert cfg["mamba_d_ssm"] == a["mamba_heads"] * a["mamba_head_dim"]
    # the rehearsal keeps what the configuration forced
    r = cfg["rehearse"]["sizes"]
    assert r["n_groups"] == 2 and r["heads"] // r["kv_heads"] == 5
    assert r["head_dim"] * r["heads"] != r["d_model"]


def test_the_entries_are_appended_and_nothing_else_changed():
    """After what the benchmark had (not "last": a later PR appends its
    own entries behind these)."""
    bench = loader.load_benchmark()

    def index(section, name):
        return [e["name"] for e in bench[section]].index(name)

    assert index("configs", CONFIG) > index("configs", "granite_4.0_h_small")
    assert index("workloads", CELL) \
        > index("workloads", "granite4hs_serve_closed16_chat")
    assert bench["workloads"][index("workloads", CELL)]["chips"] == 1
    assert index("per_layer", "paged_view_live_pct") \
        > index("per_layer", "prefill_row_fill_pct")
    entry = bench["per_layer"][index("per_layer", "paged_view_live_pct")]
    assert entry["moves"] == "serve_tokens_per_s"
    assert entry["workloads"] == [CELL, "granite4hs_serve_closed16_chat"]
    lists = {m["name"]: m.get("workloads") for m in
             bench["end_to_end"] + bench["per_layer"]}
    for name in ("serve_tokens_per_s", "decode_slot_occupancy_pct",
                 "server_busy_share_pct", "ttft_p90_ms", "tpot_p90_ms",
                 "serve_mfu_pct", "device_idle_pct.serve",
                 "hbm_peak_pct.serve", "serve_hbm_stream_pct",
                 "prefill_row_fill_pct"):
        assert lists[name].index(CELL) \
            > lists[name].index("granite4hs_serve_closed16_chat"), name
    for name in ("paged_attn_roofline_pct", "moe_tokens_per_expert_call",
                 "train_mfu_pct"):
        assert CELL not in lists[name], name


def test_view_share_reads_nothing_where_nothing_is_published(monkeypatch):
    from deeplearning4j_tpu.metrics import registry

    reader = loader.load_module("metrics", "paged_view_live_pct")
    empty = registry.MetricsRegistry()
    monkeypatch.setattr(registry, "global_registry", lambda: empty)
    assert reader.read(None) is None
    live = empty.counter("generation_kv_live_tokens_total", "",
                         labels=("program",))
    live.labels(program="decode").inc(300)
    assert reader.read(None) is None        # nothing viewed yet
    viewed = empty.counter("generation_kv_viewed_tokens_total", "",
                           labels=("program",))
    viewed.labels(program="prefill").inc(10)
    assert reader.read(None) is None        # not a decode dispatch's
    viewed.labels(program="decode").inc(2400)
    assert reader.read(None) == pytest.approx(12.5)
