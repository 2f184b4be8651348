"""Faults planted under Olmo-Hybrid's served path, one place for the CPU
tests (``tests/test_olmo_hybrid.py``) and for the readings on the chip that
the cell's limits are set from: each breaks one piece of what the
configuration forced, in the program only, and the comparison with the
plain reference has to see it. ``plant(name, monkeypatch)`` patches the
program's classes and the server's module; programs traced before it have
to be traced anew."""

FAULTS = ("beta_not_doubled", "gate_before_norm",
          "decay_after_delta_correction", "full_norm_per_head",
          "slot_state_not_reset", "conv_tail_zeroed_after_prefill")


def plant(fault, monkeypatch):
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.conf.layers.attention import (
        SelfAttentionLayer as A)
    from deeplearning4j_tpu.nn.conf.layers.gated_delta import (
        GatedDeltaNetLayer as L)
    from deeplearning4j_tpu.parallel import generation

    f32 = jnp.float32
    if fault == "beta_not_doubled":
        # beta = sigmoid(b): the transition's eigenvalues stay in [0, 1]
        real = L._mix

        def mix(self, *a, **kw):
            kept = self.allow_neg_eigval
            self.allow_neg_eigval = False
            try:
                return real(self, *a, **kw)
            finally:
                self.allow_neg_eigval = kept
        monkeypatch.setattr(L, "_mix", mix)
    elif fault == "gate_before_norm":
        # Mamba-2's order: gate first, then the norm over the head
        def gated_norm(self, o, z, w):
            o = o * jax.nn.silu(z)
            return o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                                     + self.norm_eps) * w.astype(f32)
        monkeypatch.setattr(L, "_gated_norm", gated_norm)
    elif fault == "decay_after_delta_correction":
        # S_t = alpha S + beta (v - S k) k^T: what the state reads along the
        # key is taken before the decay, position by position
        def late_decay(self, q, k, v, log_alpha, beta, S):
            def step(S, inp):
                qt, kt, vt, la, bt = inp
                u = bt[..., None] * (vt - jnp.sum(S * kt[:, :, None, :], -1))
                S = jnp.exp(la)[..., None, None] * S \
                    + u[..., :, None] * kt[:, :, None, :]
                return S, jnp.sum(S * qt[:, :, None, :], -1)

            S, o = jax.lax.scan(step, S, tuple(
                jnp.moveaxis(a, 1, 0)
                for a in (q, k, v.astype(f32), log_alpha, beta)))
            return jnp.moveaxis(o, 0, 1), S
        monkeypatch.setattr(L, "_delta", late_decay)
    elif fault == "full_norm_per_head":
        # the full layers' query and key norms taken over each head's
        # channels, as a per-head norm would, with the same weight
        def per_head(self, t, weight):
            shape = t.shape
            h = t.reshape(shape[:-1] + (-1, self.d_head))
            h = h * jax.lax.rsqrt(jnp.mean(h * h, axis=-1, keepdims=True)
                                  + self.qk_norm_eps)
            return h.reshape(shape) * weight.astype(f32)
        monkeypatch.setattr(A, "_proj_norm", per_head)
    elif fault == "slot_state_not_reset":
        # a request admitted into a slot starts from the state its last
        # occupant left
        real = generation._seed_extras
        monkeypatch.setattr(
            generation, "_seed_extras",
            lambda carry, pool, slot_st, counted, fresh=None: real(
                carry, pool, slot_st, counted))
    elif fault == "conv_tail_zeroed_after_prefill":
        # what a prefill dispatch leaves of the convolution's tail is lost:
        # the first decode step convolves over zeros
        real = generation._put_rows

        def put_rows(pool, nc, slot_st, rows):
            out = real(pool, nc, slot_st, rows)
            return {vn: {k: a.at[rows].set(0, mode="drop")
                         if k == "conv_state" else a
                         for k, a in planes.items()}
                    for vn, planes in out.items()}
        monkeypatch.setattr(generation, "_put_rows", put_rows)
    else:
        raise ValueError(f"no fault {fault!r}")
