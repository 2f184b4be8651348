"""Mixture-of-Experts layer: top-k routed expert FFNs.

Beyond reference parity (SURVEY §2.4 taxonomy: "EP (expert parallel /
MoE): absent" in DL4J; the charter lists modern-parallelism coverage as an
idiomatic TPU extension). Two dispatches over one router:

- ``dispatch="dense"`` (the default, and the only one with a sharding
  rule; to be retired once routed dispatch has one and a measured
  ``fit()`` step, ROADMAP R3): every token computes through every
  expert and the top-k softmax gate weights combine them. Static-shaped
  einsums whose result equals ideal (infinite-capacity) sparse routing;
  it pays E/k times the operations, which is tolerable only at a handful
  of experts.
- ``dispatch="routed"``: each token's ``top_k`` (token, expert) pairs are
  sorted by expert and the experts run as ONE grouped product
  (``jax.lax.ragged_dot``) over exactly the rows routed to them — no
  capacity factor, no dropped token, an expert nobody chose is not read.
  ``experts_held=(first, count)`` makes the layer one chip's share of an
  expert-parallel deployment: the router keeps all ``n_experts`` outputs
  and the published ``top_k``, the parameters hold ``count`` experts, and
  the result is the part of the sum that those experts give; what the
  absent ones would add is left out (the exchange that would fetch it is
  not here). ``gated=True`` makes each expert ``(act(a) * b) W_out`` with
  ``[a | b] = x W_in``; ``shared_hidden > 0`` adds an always-on expert of
  that width beside the routed ones, counted once whatever the share.
  Router logits, the softmax over the chosen ``top_k`` and the weighted
  combine are float32 whatever the network's dtype.

  The router's options (each off at its default, which is granite's
  router: the ``top_k`` largest logits, a softmax over those):
  ``gate_over="all"`` takes the softmax over ALL ``n_experts`` first and
  weighs a chosen expert by its probability as it stands, not
  renormalised over the chosen; ``expert_groups`` and ``groups_kept``
  limit the choice to groups (DeepSeek-V2's group-limited greedy
  selection: expert ``e`` is of group ``e // (n_experts //
  expert_groups)``, the ``groups_kept`` groups with the largest best
  score stay, and the ``top_k`` are taken inside them), which with
  ``experts_held`` a whole number of groups is device-limited routing;
  ``routed_scale`` multiplies the routed sum (not the shared expert);
  ``score="sigmoid"`` scores each expert by the sigmoid of its logit on
  its own instead of a softmax across experts, chooses the ``top_k`` by
  score plus ``select_bias`` (a stored per-expert vector that takes part
  in the choice and in nothing else), and weighs a chosen expert by its
  unbiased score over the sum of the chosen scores (plus 1e-20).

  With a streaming carry (``call_counts``, declared by ``CALL_COUNTERS``)
  the routed layer counts, per call, (token, expert) pairs that went to
  held experts, pairs that went to absent ones, and held experts that got
  at least one token; masked positions are not routed and count nothing.
  ``GenerationServer`` sums and publishes whatever a layer declares so.

- **Expert parallelism via GSPMD** (dense dispatch): the stacked expert
  params [E, ...] shard on their leading expert axis over the mesh model
  axis (parallel/model_sharding.py recognises this layer) — each device
  owns E/m experts, XLA partitions the expert einsums and inserts the
  combine reduction over ICI. Sharded == single-device, parity-tested.
- **load_balance_coef** is a UNIFORM-ROUTING PULL, not the Switch-style
  batch auxiliary: it penalizes the gate weights' L2 norm, nudging
  routing toward uniform when the data gives no signal. The Switch
  auxiliary (gate-probability x realized usage fraction) needs batch
  statistics from inside forward, which the per-layer loss plumbing does
  not carry — a deliberate scope cut, stated here so nobody mistakes the
  knob for collapse protection.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.conf.layers.base import FeedForwardLayer
from deeplearning4j_tpu.nn.conf.layers.core import gated_unit
from deeplearning4j_tpu.ops.activations import get_activation
from deeplearning4j_tpu.utils.serde import register_serializable


@register_serializable
@dataclass
class MixtureOfExpertsLayer(FeedForwardLayer):
    """y = sum_{e in topk} softmax_gate_e(x) * FFN_e(x).

    Input [B, F] or [B, T, F]; each expert is a 2-layer FFN with hidden
    width ``expert_hidden`` (defaults to 4 * n_out, the transformer
    convention)."""

    n_experts: int = 4
    top_k: int = 2
    expert_hidden: int = 0
    activation: str = "relu"
    load_balance_coef: float = 0.0
    # see the module docstring: "dense" | "routed"
    dispatch: str = "dense"
    # (first, count) of the experts whose parameters this layer holds;
    # None holds all of them. Routed dispatch only.
    experts_held: Optional[tuple] = None
    gated: bool = False
    shared_hidden: int = 0
    has_bias: bool = True
    # the router (module docstring): "chosen" | "all"
    gate_over: str = "chosen"
    # group-limited selection: 0 groups = none
    expert_groups: int = 0
    groups_kept: int = 0
    # factor on the routed experts' weighted sum
    routed_scale: float = 1.0
    # an expert's score: "softmax" (across experts) | "sigmoid" (its own)
    score: str = "softmax"

    #: the routed layer's per-call counts, an int32 vector under the
    #: streaming-carry key ``call_counts``: (counter, help, labels) each
    CALL_COUNTERS = (
        ("moe_assignments_total", "(token, expert) pairs routed to held / "
         "absent experts", {"held": "yes"}),
        ("moe_assignments_total", "(token, expert) pairs routed to held / "
         "absent experts", {"held": "no"}),
        ("moe_expert_calls_total", "held experts that got at least one "
         "token, per layer and forward pass", {}),
    )

    def finalize(self, g=None) -> None:
        super().finalize(g)
        if self.expert_hidden == 0:
            self.expert_hidden = 4 * self.n_out
        if not 1 <= self.top_k <= self.n_experts:
            raise ValueError(f"top_k {self.top_k} not in [1, n_experts "
                             f"{self.n_experts}]")
        if self.dispatch not in ("dense", "routed"):
            raise ValueError(f"dispatch {self.dispatch!r} is neither "
                             "'dense' nor 'routed'")
        if self.experts_held is not None:
            self.experts_held = tuple(int(v) for v in self.experts_held)
        first, count = self.held
        if not (0 <= first and count >= 1
                and first + count <= self.n_experts):
            raise ValueError(f"experts_held {self.experts_held} does not "
                             f"lie within the {self.n_experts} experts")
        if self.gate_over not in ("chosen", "all"):
            raise ValueError(f"gate_over {self.gate_over!r} is neither "
                             "'chosen' nor 'all'")
        if self.expert_groups and (
                self.n_experts % self.expert_groups
                or not 1 <= self.groups_kept <= self.expert_groups
                or self.groups_kept * (self.n_experts // self.expert_groups)
                < self.top_k):
            raise ValueError(
                f"expert_groups {self.expert_groups} has to divide the "
                f"{self.n_experts} experts, and groups_kept "
                f"{self.groups_kept} of them have to hold top_k "
                f"{self.top_k}")
        if self.score not in ("softmax", "sigmoid"):
            raise ValueError(f"score {self.score!r} is neither 'softmax' "
                             "nor 'sigmoid'")
        if self.score == "sigmoid" and (self.gate_over != "chosen"
                                        or self.expert_groups):
            raise ValueError("score='sigmoid' renormalises over the chosen "
                             "experts and has no group limit")
        if self.dispatch == "dense" and (
                self.experts_held is not None or self.gated
                or self.shared_hidden or not self.has_bias
                or self.gate_over != "chosen" or self.expert_groups
                or self.routed_scale != 1.0 or self.score != "softmax"):
            raise ValueError("experts_held, gated, shared_hidden, "
                             "has_bias=False and the router's options "
                             "need dispatch='routed'")

    @property
    def held(self) -> tuple:
        return self.experts_held or (0, self.n_experts)

    def param_order(self):
        names = ["Wg", "W1", "W2"]
        if self.has_bias:
            names += ["b1", "b2"]
        if self.shared_hidden:
            names += ["Ws1", "Ws2"]
        if self.score == "sigmoid":
            names += ["select_bias"]
        return tuple(names)

    def init_params(self, rng, dtype=jnp.float32):
        kg, k1, k2, k3, k4 = jax.random.split(rng, 5)
        E, D, H, O = (self.n_experts, self.n_in, self.expert_hidden,
                      self.n_out)
        n = self.held[1]
        wide = 2 if self.gated else 1
        out = {
            "Wg": self._init_w(kg, (D, E), D, E, dtype),
            "W1": self._init_w(k1, (n, D, wide * H), D, H, dtype),
            "W2": self._init_w(k2, (n, H, O), H, O, dtype),
        }
        if self.has_bias:
            out["b1"] = jnp.zeros((n, wide * H), dtype)
            out["b2"] = jnp.zeros((n, O), dtype)
        if self.shared_hidden:
            Hs = self.shared_hidden
            out["Ws1"] = self._init_w(k3, (D, wide * Hs), D, Hs, dtype)
            out["Ws2"] = self._init_w(k4, (Hs, O), Hs, O, dtype)
        if self.score == "sigmoid":
            out["select_bias"] = jnp.zeros((E,), jnp.float32)
        return out

    def bias_param_names(self):
        return frozenset(("b1", "b2", "select_bias"))

    def init_streaming_carry(self, batch: int, dtype=jnp.float32) -> dict:
        if self.dispatch != "routed":
            return {}
        return {"call_counts": jnp.zeros((len(self.CALL_COUNTERS),),
                                         jnp.int32)}

    def _gate(self, params, x):
        """[..., E] combine weights: softmax over ALL experts, then top-k
        mask + renormalize (gradients flow through the kept gates).
        Selection is by ``lax.top_k`` INDICES, not a >=threshold test, so
        exactly top_k experts are kept even under ties (uniform logits
        from a zero-padded token would otherwise keep all E)."""
        logits = jnp.einsum("...d,de->...e", x, params["Wg"])
        probs = jax.nn.softmax(logits, axis=-1)
        if self.top_k < self.n_experts:
            _, idx = jax.lax.top_k(probs, self.top_k)
            mask = jnp.sum(jax.nn.one_hot(idx, self.n_experts,
                                          dtype=probs.dtype), axis=-2)
            kept = probs * mask
            probs = kept / jnp.maximum(
                jnp.sum(kept, axis=-1, keepdims=True), 1e-9)
        return probs

    def _ffn(self, h):
        """An expert's nonlinearity on its first product."""
        act = get_activation(self.activation)
        return gated_unit(h, act) if self.gated else act(h)

    def _choose(self, logits, select_bias=None):
        """Float32 ``logits [N, E]`` -> the weights and the indices of each
        token's ``top_k`` experts, ``[N, K]`` each."""
        if self.score == "sigmoid":
            with jax.named_scope("moe_sigmoid_route"):
                scores = jax.nn.sigmoid(logits)
                _, idx = jax.lax.top_k(
                    scores + select_bias.astype(jnp.float32), self.top_k)
                top = jnp.take_along_axis(scores, idx, axis=-1)
                return top / (jnp.sum(top, axis=-1, keepdims=True)
                              + 1e-20), idx
        if self.gate_over == "chosen" and not self.expert_groups:
            top, idx = jax.lax.top_k(logits, self.top_k)
            return jax.nn.softmax(top, axis=-1), idx
        pick = scores = jax.nn.softmax(logits, axis=-1) \
            if self.gate_over == "all" else logits
        if self.expert_groups:
            with jax.named_scope("moe_group_route"):
                G = self.expert_groups
                best = jnp.max(scores.reshape(-1, G, self.n_experts // G),
                               axis=-1)                     # [N, G]
                _, keep = jax.lax.top_k(best, self.groups_kept)
                kept = jnp.sum(jax.nn.one_hot(keep, G, dtype=jnp.int32),
                               axis=-2) > 0                 # [N, G]
                pick = jnp.where(
                    jnp.repeat(kept, self.n_experts // G, axis=-1),
                    scores, -jnp.inf)
        top, idx = jax.lax.top_k(pick, self.top_k)
        if self.gate_over == "chosen":
            top = jax.nn.softmax(top, axis=-1)
        return top, idx

    def _routed(self, params, x, mask):
        """[N, D] tokens through the held experts they were routed to, as
        one grouped product over the (token, expert) pairs sorted by
        expert. Returns the float32 partial sum [N, O] and the counts."""
        N = x.shape[0]
        E, K = self.n_experts, self.top_k
        first, count = self.held
        f32 = jnp.float32
        logits = jnp.einsum("nd,de->ne", x, params["Wg"],
                            preferred_element_type=f32)
        gates, idx = self._choose(logits, params["select_bias"]) \
            if self.score == "sigmoid" \
            else self._choose(logits)                       # [N, K] each
        local = idx - first
        held = (local >= 0) & (local < count)
        if mask is not None:
            held = held & mask[:, None]
        # pairs for absent experts (and masked tokens) sort behind every
        # held group, where the grouped product does not reach
        key = jnp.where(held, local, count).reshape(-1)     # [N*K]
        order = jnp.argsort(key, stable=True)
        sizes = jnp.bincount(key, length=count + 1)[:count].astype(
            jnp.int32)
        rows = x[order // K]                                # [N*K, D]
        # a row's expert, for the biases (absent pairs borrow the last)
        group = jnp.minimum(key[order], count - 1) if self.has_bias else None
        h = jax.lax.ragged_dot(rows, params["W1"], sizes,
                               preferred_element_type=f32)
        if self.has_bias:
            h = h + params["b1"].astype(f32)[group]
        h = self._ffn(h).astype(x.dtype)
        y = jax.lax.ragged_dot(h, params["W2"], sizes,
                               preferred_element_type=f32)
        if self.has_bias:
            y = y + params["b2"].astype(f32)[group]
        # an expert's output is an activation: it takes the network's
        # dtype, goes back to token order, and is weighted and summed over
        # its token's top_k in float32. Pairs that reached no held expert
        # weigh nothing, whatever the product left in their rows.
        back = jnp.zeros((N * K,), order.dtype).at[order].set(
            jnp.arange(N * K, dtype=order.dtype))
        y = y.astype(x.dtype)[back].reshape(N, K, -1)
        w = jnp.where(held, gates, 0.0)[..., None]
        out = jnp.sum(jnp.where(w > 0, y.astype(f32) * w, 0.0), axis=1)
        if self.routed_scale != 1.0:
            out = out * self.routed_scale
        n_held = jnp.sum(sizes)
        n_all = (N if mask is None else jnp.sum(mask.astype(jnp.int32))) * K
        stats = jnp.stack([n_held, n_all - n_held,
                           jnp.sum((sizes > 0).astype(jnp.int32))])
        return out, stats.astype(jnp.int32)

    def _routed_forward(self, params, state, x, mask):
        lead = x.shape[:-1]
        flat = x.reshape(-1, x.shape[-1])
        m = None if mask is None or mask.shape != lead \
            else jnp.asarray(mask).astype(bool).reshape(-1)
        with jax.named_scope("moe_routed"):
            out, stats = self._routed(params, flat, m)
            if self.shared_hidden:
                f32 = jnp.float32
                h = jnp.einsum("nd,dh->nh", flat, params["Ws1"],
                               preferred_element_type=f32)
                h = self._ffn(h).astype(x.dtype)
                out = out + jnp.einsum("nh,ho->no", h, params["Ws2"],
                                       preferred_element_type=f32)
        out = out.astype(x.dtype).reshape(lead + (out.shape[-1],))
        if "call_counts" in state:
            state = dict(state)
            state["call_counts"] = state["call_counts"] + stats
        return out, state

    def forward(self, params, state, x, *, mask=None, train=False,
                rng=None):
        x = self.apply_input_dropout(x, train=train, rng=rng)
        if self.dispatch == "routed":
            return self._routed_forward(params, state, x, mask)
        gates = self._gate(params, x)                       # [..., E]
        act = get_activation(self.activation)
        h = act(jnp.einsum("...d,edh->...eh", x, params["W1"])
                + params["b1"])
        y = jnp.einsum("...eh,eho->...eo", h, params["W2"]) + params["b2"]
        out = jnp.einsum("...e,...eo->...o", gates, y)
        return out, state

    def regularization(self, params):
        reg = super().regularization(params)
        # the Switch-style auxiliary needs gate statistics, which only
        # exist inside forward; a coefficient without batch statistics
        # reduces to an L2-like pull on the gate weights toward uniform
        # routing — documented approximation, off by default
        if self.load_balance_coef:
            reg = reg + self.load_balance_coef * jnp.sum(
                jnp.square(params["Wg"]))
        return reg

    def regularization_grad(self, params):
        out = super().regularization_grad(params)
        # closed form of the coef*sum(Wg^2) term above (no 0.5 factor,
        # unlike the base l2 form). ``params`` may be a partial (even
        # empty) subtree — layerwise pretraining passes only the
        # pretrained layer's params through add_regularization_grads.
        if self.load_balance_coef and "Wg" in params:
            g = 2.0 * self.load_balance_coef * params["Wg"]
            out["Wg"] = out.get("Wg", 0) + g
        return out
