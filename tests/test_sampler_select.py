"""The sampler's top-k cut by selection: ``kth_largest`` returns, bit for
bit, what a sort of the row holds at ``V - k``, and ``sampled_next_token``
draws the tokens the sort formulation drew under the same keys. The sort
formulation lives on here, as the reference."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.models.zoo import kth_largest, sampled_next_token

VOCABS = (1000, 4096, 50257)
#: 64 and 65 straddle what a bounded top-k would have held
KS = ("1", "2", "40", "64", "65", "V")


def sorted_kth(logits, k):
    """The cut as the sampler read it before: one sort of the whole row."""
    V = logits.shape[-1]
    srt = jnp.sort(logits, axis=-1)
    return jnp.take_along_axis(srt, jnp.clip(V - k, 0, V - 1)[:, None],
                               axis=-1)


def sampled_by_sort(probs, keys, temperature, top_k):
    """``sampled_next_token`` as it stood while it sorted the vocabulary."""
    greedy = jnp.argmax(probs, axis=-1)
    logits = jnp.log(jnp.maximum(probs, 1e-30)) \
        / jnp.maximum(temperature, 1e-30)[:, None]
    kth = sorted_kth(logits, top_k)
    cut = (top_k[:, None] > 0) & (logits < kth)
    logits = jnp.where(cut, -1e30, logits)
    sampled = jax.vmap(jax.random.categorical)(keys, logits)
    return jnp.where(temperature <= 0, greedy, sampled)


def rows(V, seed):
    """Rows that try a selection: plain, ties at the cut, all equal, -inf
    and a greedy row's magnitudes, coarse values (ties everywhere)."""
    rng = np.random.default_rng(seed)
    x = (rng.standard_normal((6, V)) * 4).astype(np.float32)
    # ties at the cut: the 40th largest value five times over
    at = np.argsort(x[1])[-40]
    x[1, rng.choice(V, 5, replace=False)] = x[1, at]
    x[2] = np.float32(-1.25)
    x[3, rng.choice(V, V // 3, replace=False)] = -np.inf
    # what a greedy row holds: log(p) / 1e-30, from 0 down to -6.9e31
    p = np.exp(x[4] - x[4].max())
    x[4] = np.log(np.maximum(p / p.sum(), 1e-30)) / np.float32(1e-30)
    x[5] = np.round(x[5])
    return x


def same_bits(a, b):
    return np.array_equal(np.asarray(a).view(np.int32),
                          np.asarray(b).view(np.int32))


@pytest.fixture(scope="module")
def selected():
    return jax.jit(kth_largest), jax.jit(sorted_kth)


@pytest.mark.parametrize("k", KS)
@pytest.mark.parametrize("V", VOCABS)
def test_kth_largest_is_the_sorted_rows_entry(selected, V, k):
    select, by_sort = selected
    x = jnp.asarray(rows(V, seed=V))
    ks = jnp.full((x.shape[0],), V if k == "V" else int(k), jnp.int32)
    assert same_bits(select(x, ks), by_sort(x, ks))


@pytest.mark.parametrize("V", VOCABS)
def test_rows_with_k_of_their_own_and_k_zero_rows_ignored(selected, V):
    select, by_sort = selected
    x = jnp.asarray(rows(V, seed=V + 1))
    ks = np.array([40, 0, 1, V, 0, 65], np.int32)
    got, want = select(x, jnp.asarray(ks)), by_sort(x, jnp.asarray(ks))
    assert got.shape == (6, 1)
    # a k = 0 row may hold anything: the sampler's mask never reads it
    assert same_bits(np.asarray(got)[ks > 0], np.asarray(want)[ks > 0])


@pytest.mark.parametrize("dtype", ("bfloat16", "float16", "float64"))
def test_other_float_widths_select_the_same_entry(dtype):
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((4, 777)) * 3, dtype)
    ks = jnp.array([1, 5, 777, 300], jnp.int32)
    got = kth_largest(x, ks)
    assert got.dtype == x.dtype
    assert np.array_equal(np.asarray(got, np.float64),
                          np.asarray(sorted_kth(x, ks), np.float64))


@pytest.mark.parametrize("V", VOCABS)
def test_sampled_tokens_are_the_sort_formulations(V):
    """A mixed greedy / sampled batch, every top_k a request may ask for."""
    rng = np.random.default_rng(V)
    B = 12
    logits = (rng.standard_normal((B, V)) * 3).astype(np.float32)
    logits[3, rng.choice(V, 7, replace=False)] = logits[3].max()   # ties
    p = np.exp(logits - logits.max(-1, keepdims=True))
    probs = jnp.asarray(p / p.sum(-1, keepdims=True))
    temp = jnp.asarray([0.8, 0.0, 0.8, 1.3, 0.0, 0.7, 0.8, 0.0, 2.0, 0.8,
                        0.0, 0.5], jnp.float32)
    top_k = jnp.asarray([40, 0, 0, 5, 40, 1, V, V, 65, 64, 0, 2], jnp.int32)
    new, old = jax.jit(sampled_next_token), jax.jit(sampled_by_sort)
    for seed in range(3):
        keys = jax.vmap(jax.random.fold_in, (None, 0))(
            jax.random.PRNGKey(seed), jnp.arange(B))
        got, want = new(probs, keys, temp, top_k), \
            old(probs, keys, temp, top_k)
        assert np.array_equal(np.asarray(got), np.asarray(want))
        greedy = np.asarray(temp) <= 0
        assert np.array_equal(np.asarray(got)[greedy],
                              np.asarray(jnp.argmax(probs, -1))[greedy])
        # top_k = 1 is the row's best whatever the key
        assert int(got[5]) == int(jnp.argmax(probs[5]))


@pytest.mark.generation
def test_the_loop_books_the_branch_each_decode_dispatch_takes(lm):
    """``generation_sampler_steps_total{path}``: the predicate of the decode
    program's ``lax.cond`` on the temperatures the loop ships, so many
    micro-steps a dispatch, in the server's registry and the process-wide
    one. A retired slot keeps its temperature until it is taken again, as
    it does in the program's eyes."""
    from deeplearning4j_tpu.metrics.registry import global_registry
    from tests.serving_helpers import GREEDY, SAMPLED, V, serving

    def booked(reg):
        steps = reg.snapshot().get("generation_sampler_steps_total", {})
        return (steps.get("path=select", 0), steps.get("path=greedy", 0))

    before = booked(global_registry())
    with serving(lm, V, slots=2, steps_per_dispatch=4) as srv:
        prompt, steps, _, _, _ = GREEDY
        srv.submit(prompt, steps).result(timeout=120)
        dispatches = srv.stats()["decode_steps"]
        assert dispatches > 0
        assert booked(srv.metrics) == (0, 4 * dispatches)
        prompt, steps, temp, top_k, seed = SAMPLED
        srv.submit(prompt, steps, temperature=temp, top_k=top_k,
                   seed=seed).result(timeout=120)
        sampled = srv.stats()["decode_steps"] - dispatches
        assert booked(srv.metrics) == (4 * sampled, 4 * dispatches)
        mine = booked(srv.metrics)
    after = booked(global_registry())
    assert (after[0] - before[0], after[1] - before[1]) == mine
