"""``correct`` has been shown to fail for ``granite4hs_serve_closed16_chat``:
a sound rehearsal is correct, and one with the timed path broken underneath
is not — the per-slot state not reset at admission, or one held expert's
output dropped. Driven like ``test_correct.py``: the harness's look for a
chip skipped (``rehearse``), the rest of a run on the CPU at the files'
``rehearse`` sizes, in float32 (the configuration's ``rehearse_note``), with
limits stated here for those sizes. Also the new configuration's operation
counts against hand-worked values, and the two new readers on made-up
numbers."""

import types

import pytest

from benchmarks import run as runner
from benchmarks.harness import loader

CELL = "granite4hs_serve_closed16_chat"
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
    meas, compared = drive(2_800_000_123)
    assert compared.correct, compared.as_dict()
    assert meas["attempted"] > 0 and meas["failed"] == 0
    assert {"serve_tokens_per_s", "ttft_p90_ms", "tpot_p90_ms",
            "setup_s"} <= set(meas["end_to_end"])
    # the program's counters reached the registry a reader can reach
    reader = loader.load_module("metrics", "moe_tokens_per_expert_call")
    assert reader.read(None) > 1.0


def test_slot_state_not_reset_at_admission_is_not_correct(monkeypatch):
    from deeplearning4j_tpu.parallel import generation

    real = generation._seed_extras
    monkeypatch.setattr(
        generation, "_seed_extras",
        lambda carry, pool, slot_st, stats, fresh=None: real(
            carry, pool, slot_st, stats))
    _, compared = drive(2_800_000_124)
    assert not compared.correct
    assert compared.as_dict()["served_logit_gap"]["value"] \
        > 10 * LIMITS["served_logit_gap"]


def test_one_held_experts_output_dropped_is_not_correct(monkeypatch):
    from deeplearning4j_tpu.nn.conf.layers.moe import MixtureOfExpertsLayer

    real = MixtureOfExpertsLayer._routed

    def dropped(self, p, x, mask):
        return real(self, dict(p, W2=p["W2"].at[1].set(0.0)), x, mask)

    monkeypatch.setattr(MixtureOfExpertsLayer, "_routed", dropped)
    _, compared = drive(2_800_000_125)
    assert not compared.correct
    assert compared.as_dict()["served_logit_gap"]["value"] \
        > 10 * LIMITS["served_logit_gap"]


# ------------------------------------------------------------------ counts
def test_operation_counts_against_hand_worked_values():
    ops = loader.load_module("ops", "granite_4.0_h_small")
    sizes = loader.load_json("configs", "granite_4.0_h_small")["sizes"]
    # the issue's arithmetic, in millions of parameters
    assert ops.mamba_matmul_params(sizes) == 4096 * 16768 + 8192 * 4096
    assert round(ops.mamba_matmul_params(sizes) / 1e6, 2) == 102.24
    assert round(ops.attention_matmul_params(sizes) / 1e6, 2) == 41.94
    assert round(ops.expert_params(sizes) / 1e6, 2) == 9.44
    assert round(ops.shared_expert_params(sizes) / 1e6, 2) == 18.87
    assert round(ops.router_params(sizes) / 1e6, 2) == 0.29
    assert ops.experts_per_token_here(sizes) == 5.0
    assert round(ops.parameter_count(sizes) / 1e9, 2) == 4.96
    # one layer of 8 key/value heads of 128 at 2 bytes: 4 KB a token
    assert ops.kv_bytes_per_token(sizes) == 4096
    per_token = (9 * 102_236_160 + 41_943_040
                 + 10 * (294_912 + 18_874_368 + 5 * 9_437_184)
                 + 4096 * 50176)
    assert ops.matmul_params_per_token(sizes) == per_token
    scan = 9 * (2 * 4 * 8448 + 4 * 8192 * 128)
    assert ops.token_flops(sizes, 100) == 2 * per_token + scan \
        + 4 * 100 * 4096
    assert ops.requests_flops(sizes, [(1, 3)]) == sum(
        ops.token_flops(sizes, c) for c in (1, 2, 3))
    # a decoded token reads its whole context; a prefilled chunk shares it
    assert ops.paged_read(sizes, [(10, 2, 1)])["bytes"] == 4096 * (10 + 11)
    assert ops.paged_read(sizes, [(1, 300, 256)])["bytes"] \
        == 4096 * (256 + 300)
    floor = 2 * (9 * 102_236_160 + 41_943_040
                 + 10 * (294_912 + 18_874_368) + 4096 * 50176)
    assert ops.decode_step_min_bytes(sizes) == floor
    assert floor < 2 * ops.parameter_count(sizes)


def test_hbm_stream_share_on_made_up_numbers():
    reader = loader.load_module("metrics", "serve_hbm_stream_pct")
    ops = types.SimpleNamespace(decode_step_min_bytes=lambda sizes: 1e9)
    ctx = types.SimpleNamespace(
        facts={"decode_dispatches": 50, "steps_per_dispatch": 4,
               "window_s": 10.0},
        ops=ops, peaks={"hbm_bytes_s": 800e9}, state={"sizes": {}},
        run=types.SimpleNamespace(chips=1))
    assert reader.read(ctx) == pytest.approx(100 * 200e9 / 8000e9)
    ctx.ops = types.SimpleNamespace()      # a configuration with no floor
    assert reader.read(ctx) is None
    ctx.ops, ctx.facts = ops, {}
    assert reader.read(ctx) is None


def test_tokens_per_expert_call_reads_nothing_where_nothing_is_published(
        monkeypatch):
    from deeplearning4j_tpu.metrics import registry

    reader = loader.load_module("metrics", "moe_tokens_per_expert_call")
    empty = registry.MetricsRegistry()
    monkeypatch.setattr(registry, "global_registry", lambda: empty)
    assert reader.read(None) is None
    held = empty.counter("generation_moe_assignments_total", "",
                         labels=("held", "program"))
    held.labels(held="yes", program="decode").inc(90)
    held.labels(held="no", program="decode").inc(70)
    held.labels(held="yes", program="prefill").inc(9000)
    calls = empty.counter("generation_moe_expert_calls_total", "",
                          labels=("program",))
    calls.labels(program="prefill").inc(36)
    assert reader.read(None) is None        # no decode dispatch yet
    calls.labels(program="decode").inc(30)
    assert reader.read(None) == 3.0
