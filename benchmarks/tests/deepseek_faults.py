"""Faults planted under DeepSeek-V2's served path, one place for the CPU
tests (``tests/test_deepseek_v2.py``, ``test_deepseek_correct.py``) and for
the readings on the chip that the cell's limits are set from: each breaks
one piece of what the configuration forced, in the program only, and the
comparison with the plain reference has to see it. ``plant(name,
monkeypatch)`` patches the program's classes; programs traced before it
have to be traced anew."""

FAULTS = ("rotation_restarted", "yarn_left_out", "key_cached_unrotated",
          "latent_cached_before_its_norm",
          "selection_without_the_group_limit", "weights_renormalised",
          "stale_page_after_a_copy")


def plant(fault, monkeypatch):
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.conf.layers import attention, latent_attention
    from deeplearning4j_tpu.nn.conf.layers.latent_attention import (
        LatentAttentionLayer as L)
    from deeplearning4j_tpu.nn.conf.layers.moe import (
        MixtureOfExpertsLayer as M)
    from deeplearning4j_tpu.parallel.generation import GenerationServer

    if fault == "rotation_restarted":
        # every chunk of more than one token is rotated from position 0: a
        # request's first prefill round is sound, a later one (its own part
        # behind a cached document among them) is not
        real = L._queries_and_rows
        monkeypatch.setattr(
            L, "_queries_and_rows", lambda self, p, x, start: real(
                self, p, x, None if x.shape[1] > 1 else start))
    elif fault == "yarn_left_out":
        real_f = attention.rotary_frequencies
        monkeypatch.setattr(
            latent_attention, "rotary_frequencies",
            lambda dim, theta, yarn=None: real_f(dim, theta))
    elif fault == "key_cached_unrotated":
        # the shared key (no head axis) enters the cache as projected
        real_r = L._rotate
        monkeypatch.setattr(
            L, "_rotate", lambda self, t, pos: t.astype(jnp.float32)
            if t.ndim == 3 else real_r(self, t, pos))
    elif fault == "latent_cached_before_its_norm":
        # the latent goes into the cache as projected; the query's
        # bottleneck keeps its norm
        real_n, real_q = latent_attention._rms, L._queries_and_rows
        monkeypatch.setattr(
            latent_attention, "_rms",
            lambda t, g, eps: t.astype(jnp.float32) if g is None
            else real_n(t, g, eps))
        monkeypatch.setattr(
            L, "_queries_and_rows", lambda self, p, x, start: real_q(
                self, {**p, "kv_gamma": None}, x, start))
    elif fault == "selection_without_the_group_limit":
        real_c = M._choose

        def free(self, logits):
            kept, self.expert_groups = self.expert_groups, 0
            try:
                return real_c(self, logits)
            finally:
                self.expert_groups = kept
        monkeypatch.setattr(M, "_choose", free)
    elif fault == "weights_renormalised":
        real_c = M._choose

        def renorm(self, logits):
            w, idx = real_c(self, logits)
            return w / jnp.sum(w, axis=-1, keepdims=True), idx
        monkeypatch.setattr(M, "_choose", renorm)
    elif fault == "stale_page_after_a_copy":
        # copy-on-write repoints the block table and copies nothing: the
        # row then reads whatever the fresh page held where its document's
        # last tokens were
        monkeypatch.setattr(GenerationServer, "_page_copy_program",
                            lambda self: lambda pool, src, dst: pool)
    else:
        raise ValueError(f"no fault {fault!r}")
