"""``correct`` has been shown to fail: the control comes out as not
correct, and a run with the timed path broken underneath does too. These
skip the harness's look for a chip (``rehearse``) and drive the rest of a
run on the CPU at the files' ``rehearse`` sizes, with limits stated here
for those sizes (the cells' own limits, in ``workloads/``, are for the
chip's sizes and arithmetic)."""

import pytest

from benchmarks import run as runner
from benchmarks.harness import loader

SERVE = "cgpt590m_serve_closed16"
TRAIN = "resnet50_fit_b256"
#: on the CPU the program's float32 is the reference's: served greedy
#: tokens are the reference's best to rounding
SERVE_LIMITS = {"served_logit_gap": 1e-4, "bad_completions": 0}


def drive(cell, seed, limits, seconds=1.5):
    run = runner.make_run(loader.load_benchmark(), cell, seed, seconds,
                          False, rehearse=True)
    run.limits = dict(limits)
    meas, _, _, compared = runner.execute(run)
    return meas, compared


def test_serve_sound_run_is_correct():
    meas, compared = drive(SERVE, 2_500_000_123, SERVE_LIMITS)
    assert compared.correct, compared.as_dict()
    assert meas["attempted"] > 0 and meas["failed"] == 0
    assert {"serve_tokens_per_s", "ttft_p90_ms", "tpot_p90_ms",
            "setup_s"} <= set(meas["end_to_end"])


def test_serve_token_altered_where_it_is_produced_is_not_correct(
        monkeypatch):
    from deeplearning4j_tpu.models import zoo

    real = zoo.sampled_next_token

    def altered(probs, keys, temperature, top_k):
        return (real(probs, keys, temperature, top_k) + 1) % probs.shape[-1]

    monkeypatch.setattr(zoo, "sampled_next_token", altered)
    _, compared = drive(SERVE, 2_500_000_124, SERVE_LIMITS)
    assert not compared.correct
    gap = compared.as_dict()["served_logit_gap"]["value"]
    assert gap > 100 * SERVE_LIMITS["served_logit_gap"]


def test_serve_short_answer_is_not_correct(monkeypatch):
    """An answer that says the wrong thing on its face: one token short."""
    drv = loader.load_module("drivers", "serve_closed")
    real = drv.checked_sample

    def cut(run, st):
        for r in st["records"][:1]:
            r["tokens"] = r["tokens"][:-1]
        return real(run, st)

    monkeypatch.setattr(drv, "checked_sample", cut)
    _, compared = drive(SERVE, 2_500_000_125, SERVE_LIMITS)
    assert not compared.correct
    assert compared.as_dict()["bad_completions"]["value"] == 1


def test_serve_control_reads_above_the_program():
    run = runner.make_run(loader.load_benchmark(), SERVE, 2_500_000_126,
                          1.5, False, rehearse=True)
    drv = loader.load_module("drivers", "serve_closed")
    out = drv.readings(run, ["program", "control", "fault_token_altered"])
    lower = out["program"]["served_logit_gap"]
    assert lower <= SERVE_LIMITS["served_logit_gap"]
    assert out["control"]["served_logit_gap"] > max(
        3 * lower, SERVE_LIMITS["served_logit_gap"])
    assert out["fault_token_altered"]["served_logit_gap"] > max(
        10 * lower, SERVE_LIMITS["served_logit_gap"])


# ------------------------------------------------------------------ train
#: at the rehearsal's sizes the program computes in float32 (see the
#: configuration's ``rehearse_note``), so its first step's loss is the
#: reference's to rounding and its two-step change within a few percent
TRAIN_LIMITS = {"loss_step1": 1e-3, "grad_norm_worst_leaf": 1e-2,
                "update_norm_worst_leaf": 0.4}


def test_train_sound_run_is_correct():
    meas, compared = drive(TRAIN, 2_500_000_200, TRAIN_LIMITS, seconds=1.0)
    assert compared.correct, compared.as_dict()
    assert meas["attempted"] > 0 and meas["failed"] == 0
    assert meas["end_to_end"]["train_samples_per_s"] > 0


def test_train_step_that_returns_its_state_unchanged_is_not_correct(
        monkeypatch):
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.optimize import fused_fit

    real = fused_fit.build_fused_step

    def broken(net, guarded=False):
        fused = real(net, guarded=guarded)

        def unchanged(params, opt_state, state, *rest):
            copy = lambda t: jax.tree_util.tree_map(jnp.copy, t)  # noqa: E731
            out = fused(copy(params), copy(opt_state), copy(state), *rest)
            return (params, opt_state, state) + tuple(out[3:])

        return unchanged

    monkeypatch.setattr(fused_fit, "build_fused_step", broken)
    _, compared = drive(TRAIN, 2_500_000_201, TRAIN_LIMITS, seconds=1.0)
    assert not compared.correct
    assert compared.as_dict()["update_norm_worst_leaf"]["value"] \
        == pytest.approx(1.0)


def test_train_half_of_the_batch_left_out_is_not_correct(monkeypatch):
    """Half of the rows masked out of the loss, the mean over the rest."""
    from deeplearning4j_tpu.optimize.fused_fit import FusedFitDriver

    real = FusedFitDriver._stack

    def half(self, items):
        xs, ys, ims, lms = real(self, items)
        lms = lms.copy()
        lms[:, lms.shape[1] // 2:] = 0.0
        return xs, ys, ims, lms

    monkeypatch.setattr(FusedFitDriver, "_stack", half)
    _, compared = drive(TRAIN, 2_500_000_202, TRAIN_LIMITS, seconds=1.0)
    assert not compared.correct
    assert compared.as_dict()["loss_step1"]["value"] \
        > 10 * TRAIN_LIMITS["loss_step1"]


def test_train_control_reads_above_the_program():
    run = runner.make_run(loader.load_benchmark(), TRAIN, 2_500_000_203,
                          1.0, False, rehearse=True)
    drv = loader.load_module("drivers", "fit_window")
    out = drv.readings(run, ["program", "control", "fault_half_batch",
                             "fault_state_unchanged"])
    print(out)
    for name, limit in TRAIN_LIMITS.items():
        assert out["program"][name] <= limit
    # the control and each fault fail one of the cell's numbers, not each
    for kind in ("control", "fault_half_batch", "fault_state_unchanged"):
        assert any(out[kind][name] > max(3 * out["program"][name], limit)
                   for name, limit in TRAIN_LIMITS.items()), kind
    assert out["fault_state_unchanged"]["update_norm_worst_leaf"] == 1.0
