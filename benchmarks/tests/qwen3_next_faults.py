"""Faults planted under Qwen3-Next's served path, one place for the CPU
tests (``tests/test_qwen3_next.py``) and for the readings on the chip that
the cell's limits are set from: each breaks one piece of what the
configuration forced, in the program only, and the comparison with the
plain reference has to see it. ``plant(name, monkeypatch)`` patches the
program's classes; programs traced before it have to be traced anew."""

FAULTS = ("key_heads_tiled", "all_channels_rotated", "shared_expert_ungated",
          "norms_not_zero_centred", "beta_doubled", "attention_gate_dropped",
          "routing_not_renormalised")


def _flag_off(monkeypatch, cls, method, flag, value):
    """``cls.method`` run with ``self.flag`` set to ``value``."""
    real = getattr(cls, method)

    def patched(self, *a, **kw):
        kept = getattr(self, flag)
        setattr(self, flag, value)
        try:
            return real(self, *a, **kw)
        finally:
            setattr(self, flag, kept)
    monkeypatch.setattr(cls, method, patched)


def plant(fault, monkeypatch):
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.conf.layers.attention import (
        SelfAttentionLayer as A)
    from deeplearning4j_tpu.nn.conf.layers.gated_delta import (
        GatedDeltaNetLayer as L)
    from deeplearning4j_tpu.nn.conf.layers.moe import (
        MixtureOfExpertsLayer as M)
    from deeplearning4j_tpu.nn.conf.layers.normalization import (
        RMSNormalization as N)

    if fault == "key_heads_tiled":
        # value head j reads key head j % Hk: the key heads tiled, not
        # each repeated for its neighbours
        def tiled(self, q, k):
            G = self.n_heads // self.key_heads
            return jnp.tile(q, (1, 1, G, 1)), jnp.tile(k, (1, 1, G, 1))
        monkeypatch.setattr(L, "_share_keys", tiled)
    elif fault == "all_channels_rotated":
        # every channel of a head turned, at frequencies of the whole head
        monkeypatch.setattr(A, "rotated_dims",
                            property(lambda self: self.d_head))
    elif fault == "shared_expert_ungated":
        _flag_off(monkeypatch, M, "_routed_forward", "shared_gate", False)
    elif fault == "norms_not_zero_centred":
        # every zero-centred norm multiplies by its stored w, not 1 + w
        _flag_off(monkeypatch, N, "forward", "zero_centred", False)
        monkeypatch.setattr(A, "_norm_weight",
                            lambda self, w: w.astype(jnp.float32))
    elif fault == "beta_doubled":
        # beta = 2 sigmoid(b), the transition's eigenvalues down to -1
        _flag_off(monkeypatch, L, "_mix", "allow_neg_eigval", True)
    elif fault == "attention_gate_dropped":
        # the full layers' heads go to Wo without sigmoid(x Wg)
        _flag_off(monkeypatch, A, "_project_out", "gated", False)
    elif fault == "routing_not_renormalised":
        # a chosen expert weighs its softmax over all experts as it stands
        _flag_off(monkeypatch, M, "_choose", "gate_over", "all")
    else:
        raise ValueError(f"no fault {fault!r}")
