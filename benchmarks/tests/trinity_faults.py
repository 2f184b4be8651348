"""Faults planted under Trinity's served path, one place for the CPU tests
(``tests/test_trinity.py``) and for the readings on the chip that the
cell's limits are set from: each breaks one piece of what the
configuration forced, in the program only, and the comparison with the
plain reference has to see it. ``plant(name, monkeypatch)`` patches the
program's classes; programs traced before it have to be traced anew."""

FAULTS = ("sliding_layer_attends_to_everything", "window_page_freed_early",
          "full_layer_rotated", "gate_left_out",
          "select_bias_in_the_weights", "chosen_scores_not_renormalised")


def plant(fault, monkeypatch):
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.conf.layers.attention import (
        SelfAttentionLayer as A)
    from deeplearning4j_tpu.nn.conf.layers.moe import (
        MixtureOfExpertsLayer as M)
    from deeplearning4j_tpu.parallel.generation import GenerationServer

    def without(cls, name, field, value):
        """``cls.name`` run with ``self.field`` set to ``value``."""
        real = getattr(cls, name)

        def patched(self, *a, **kw):
            kept = getattr(self, field)
            setattr(self, field, value(self, kept))
            try:
                return real(self, *a, **kw)
            finally:
                setattr(self, field, kept)
        monkeypatch.setattr(cls, name, patched)

    if fault == "sliding_layer_attends_to_everything":
        # the window test is dropped: whatever a read gathered and lies at
        # or before the query is visible (the keys just behind the window
        # that share its first page or a chunk's reach)
        without(A, "_sees", "window", lambda self, w: 0)
    elif fault == "window_page_freed_early":
        # the loop gives a window class's page back one page early: the
        # window's oldest tokens are read from the garbage page
        real = GenerationServer._slide_windows
        monkeypatch.setattr(
            GenerationServer, "_slide_windows",
            lambda self, slot, pos: real(self, slot, pos + self._ps))
    elif fault == "full_layer_rotated":
        # the layers that carry no positions rotate like the sliding ones
        without(A, "_qkv", "rope_theta",
                lambda self, theta: theta or 10000.0)
    elif fault == "gate_left_out":
        without(A, "_project_out", "gated", lambda self, g: False)
    elif fault == "select_bias_in_the_weights":
        # the biased scores choose AND weigh
        def biased(self, logits, select_bias=None):
            scores = jax.nn.sigmoid(logits) + select_bias.astype(jnp.float32)
            top, idx = jax.lax.top_k(scores, self.top_k)
            return top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20), idx
        monkeypatch.setattr(M, "_choose", biased)
    elif fault == "chosen_scores_not_renormalised":
        def raw(self, logits, select_bias=None):
            scores = jax.nn.sigmoid(logits)
            _, idx = jax.lax.top_k(
                scores + select_bias.astype(jnp.float32), self.top_k)
            return jnp.take_along_axis(scores, idx, axis=-1), idx
        monkeypatch.setattr(M, "_choose", raw)
    else:
        raise ValueError(f"no fault {fault!r}")
