"""Layer base classes.

Reference: nn/conf/layers/Layer.java + BaseLayer hyperparameter fields, and the
runtime contract of nn/api/Layer.java:37 (activate/backprop/masking). Here the
contract is functional:

- ``init_params(rng, dtype) -> dict[str, Array]``  (param shapes; flat-buffer order
  given by ``param_order``)
- ``init_state() -> dict``                          (e.g. BN running stats)
- ``forward(params, state, x, *, mask, train, rng) -> (out, new_state)``

``forward`` must be jax-traceable: no data-dependent Python control flow, static
shapes only, so whole networks compile to one XLA program.

Hyperparameter inheritance matches the reference's builder: fields left as ``None``
on a layer are filled from the global ``NeuralNetConfiguration`` at build time
(``finalize``), falling back to per-class defaults (``DEFAULT_ACTIVATION`` etc.).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.weights import Distribution, init_weight
from deeplearning4j_tpu.ops.activations import Activation, get_activation


@dataclass(frozen=True)
class CacheSpec:
    """What a layer's streaming carry is, as a serving pool hosts it
    (``Layer.cache_spec``). Facts only: what a server can serve beside
    each kind is the server's to decide.

    - ``kind``: ``"pages"`` (per-token caches re-homed into a page pool),
      ``"slot_state"`` (one block a sequence), ``"positions"`` (a bare
      ``cache_pos``) or ``"counts"`` (per-call counters).
    - ``keys``: the carry keys beside the planes' dense views.
    - ``planes``: pool plane -> (its dense view's carry key, the view's
      token axis); what ``init_paged_carry`` may return.
    - ``head_axes``: pool plane -> the axis that holds its heads (a
      tensor-parallel pool is split along it), ``None`` for a plane with
      no head axis.
    - ``window``: how many of a row's latest tokens the paged read still
      sees, ``None`` for all of them (a server may free the pages behind).
    - ``counters``: (counter, help, labels) of each entry of a counting
      layer's ``call_counts`` vector.
    - ``expert_params``: a counting layer's parameters that are stacked by
      held expert along their first axis (one slice is one expert's).
    """

    kind: str
    keys: tuple = ()
    planes: dict = field(default_factory=dict)
    head_axes: dict = field(default_factory=dict)
    window: Optional[int] = None
    counters: tuple = ()
    expert_params: tuple = ()


@dataclass
class Layer:
    """Base for all layer configs. ``dropout`` is the probability of dropping each
    input activation (inverted dropout on the layer *input*, matching the placement
    in the reference's BaseLayer.activate -> Dropout.applyDropout,
    nn/layers/BaseLayer.java:540-551)."""

    name: Optional[str] = None
    dropout: Optional[float] = None
    # Gradient normalization/clipping applied between backprop and the
    # updater (reference: nn/conf/GradientNormalization.java, applied in
    # BaseMultiLayerUpdater.preApply :310-352). Modes: none |
    # renormalize_l2_per_layer | renormalize_l2_per_param_type |
    # clip_element_wise_absolute_value | clip_l2_per_layer |
    # clip_l2_per_param_type
    gradient_normalization: Optional[str] = None
    gradient_normalization_threshold: Optional[float] = None

    # what array kind this layer consumes: ff | cnn | rnn | any
    INPUT_KIND = "any"

    # ---- config plumbing -------------------------------------------------------
    def finalize(self, g=None) -> None:
        """Fill None fields from the global conf ``g`` (NeuralNetConfiguration)."""
        if self.dropout is None:
            self.dropout = (g.dropout if g is not None and g.dropout is not None
                            else 0.0)
        if self.gradient_normalization is None:
            self.gradient_normalization = (
                g.gradient_normalization
                if g is not None and g.gradient_normalization is not None
                else "none")
        if self.gradient_normalization_threshold is None:
            self.gradient_normalization_threshold = (
                g.gradient_normalization_threshold
                if g is not None
                and g.gradient_normalization_threshold is not None else 1.0)

    def output_type(self, input_type: InputType) -> InputType:
        return input_type

    def set_n_in(self, input_type: InputType) -> None:
        """Infer nIn-like fields from the previous layer's output type (parity with
        FeedForwardLayer.setNIn auto-config)."""

    def validate(self) -> None:
        """Config sanity, run at build() time so bad configs fail with a
        named-layer message instead of a raw XLA shape error at fit time
        (reference: the checks behind exceptions/TestInvalidConfigurations
        — nIn/nOut == 0 raise at init, DL4JInvalidConfigException).

        Only WEIGHTED layers need n_in/n_out: paramless passthroughs
        (LastTimeStep, ActivationLayer, ...) inherit the fields without
        consuming them."""
        if not self.param_order():
            return
        for attr in ("n_in", "n_out"):
            v = getattr(self, attr, None)
            if isinstance(v, int) and v <= 0:
                label = self.name or type(self).__name__
                hint = (" (set an InputType on the builder, or pass "
                        f"{attr} explicitly)" if attr == "n_in" else "")
                raise ValueError(
                    f"Invalid configuration for layer '{label}': "
                    f"{attr} must be > 0, got {v}{hint}")

    # ---- params ----------------------------------------------------------------
    def param_order(self) -> list[str]:
        return []

    def init_params(self, rng, dtype=jnp.float32) -> dict:
        return {}

    def init_state(self, dtype=jnp.float32) -> dict:
        return {}

    def init_streaming_carry(self, batch: int, dtype=jnp.float32) -> dict:
        """Initial carry for streaming inference (rnn_time_step). LSTMs
        need none (their h/c default lazily to zeros); attention layers
        return a KV cache here so incremental decode is O(T) per token
        instead of re-running the full O(T^2) forward."""
        return {}

    def cache_spec(self) -> Optional[CacheSpec]:
        """What ``init_streaming_carry`` returns, declared (``None``
        exactly where that carry is empty)."""
        return None

    def has_params(self) -> bool:
        return bool(self.param_order())

    def regularization(self, params: dict):
        """L1/L2 penalty contribution (reference: BaseLayer.calcL1/calcL2)."""
        return 0.0

    def regularization_grad(self, params: dict) -> dict:
        """Analytic penalty gradient per leaf (see BaseLayer override)."""
        return {}

    # ---- compute ---------------------------------------------------------------
    def forward(self, params: dict, state: dict, x, *, mask=None, train: bool = False,
                rng=None):
        raise NotImplementedError

    def apply_input_dropout(self, x, *, train: bool, rng):
        p = self.dropout or 0.0
        if train and p > 0.0 and rng is not None:
            keep = 1.0 - p
            m = jax.random.bernoulli(rng, keep, x.shape)
            return jnp.where(m, x / keep, 0.0)
        return x

    def feed_forward_mask(self, mask, current_mask_state: str = "active"):
        """How this layer transforms a time-mask (reference: Layer.feedForwardMaskArray)."""
        return mask


@dataclass
class BaseLayer(Layer):
    """Layers with weights: activation + init + regularisation hyperparams."""

    activation: Optional[str] = None
    weight_init: Optional[str] = None
    dist: Optional[Distribution] = None
    bias_init: Optional[float] = None
    l1: Optional[float] = None
    l2: Optional[float] = None
    l1_bias: Optional[float] = None
    l2_bias: Optional[float] = None
    # Per-layer learning-rate override (reference: BaseLayer.learningRate /
    # biasLearningRate). None -> use the global updater learning rate.
    learning_rate: Optional[float] = None
    bias_learning_rate: Optional[float] = None

    DEFAULT_ACTIVATION = "sigmoid"

    def finalize(self, g=None) -> None:
        super().finalize(g)
        if self.activation is None:
            self.activation = ((g.activation if g is not None else None)
                               or self.DEFAULT_ACTIVATION)
        if self.weight_init is None:
            self.weight_init = ((g.weight_init if g is not None else None) or "xavier")
        if self.dist is None and g is not None:
            self.dist = g.dist
        if self.bias_init is None:
            self.bias_init = (g.bias_init if g is not None and g.bias_init is not None
                              else 0.0)
        for f, gf in (("l1", "l1"), ("l2", "l2"), ("l1_bias", "l1_bias"),
                      ("l2_bias", "l2_bias")):
            if getattr(self, f) is None:
                gv = getattr(g, gf, None) if g is not None else None
                setattr(self, f, gv if gv is not None else 0.0)

    def act(self) -> Activation:
        return get_activation(self.activation or self.DEFAULT_ACTIVATION)

    def _init_w(self, rng, shape, fan_in, fan_out, dtype):
        return init_weight(rng, shape, fan_in, fan_out,
                           self.weight_init or "xavier", self.dist, dtype)

    def bias_param_names(self) -> frozenset:
        """Params that take l1_bias/l2_bias instead of l1/l2 (reference: the
        ParamInitializer weight/bias split used by conf.getL2ByParam). Layers with
        non-'b' bias names override this explicitly."""
        return frozenset({"b"})

    def regularization(self, params: dict):
        reg = 0.0
        l1 = self.l1 or 0.0
        l2 = self.l2 or 0.0
        l1b = self.l1_bias or 0.0
        l2b = self.l2_bias or 0.0
        biases = self.bias_param_names()
        for k, v in params.items():
            if k in biases:
                if l2b > 0:
                    reg = reg + 0.5 * l2b * jnp.sum(v * v)
                if l1b > 0:
                    reg = reg + l1b * jnp.sum(jnp.abs(v))
            else:
                if l2 > 0:
                    reg = reg + 0.5 * l2 * jnp.sum(v * v)
                if l1 > 0:
                    reg = reg + l1 * jnp.sum(jnp.abs(v))
        return reg

    def regularization_grad(self, params: dict) -> dict:
        """Analytic d(regularization)/d(param) per leaf: l2*W + l1*sign(W).

        The train step adds these to the data-loss gradients instead of
        differentiating ``regularization()`` — same math (the penalty is a
        closed form), but the elementwise terms fuse into the updater while
        autodiff-through-reductions materialised a separate backward pass
        (measured 30% of the ResNet50 step, record deleted at PR 21). This is
        also the reference's own architecture: DL4J applies l1/l2 inside
        the updater (BaseUpdater.postApply), not through backprop."""
        l1 = self.l1 or 0.0
        l2 = self.l2 or 0.0
        l1b = self.l1_bias or 0.0
        l2b = self.l2_bias or 0.0
        biases = self.bias_param_names()
        out = {}
        for k, v in params.items():
            c2, c1 = (l2b, l1b) if k in biases else (l2, l1)
            g = None
            if c2 > 0:
                g = c2 * v
            if c1 > 0:
                g = (0 if g is None else g) + c1 * jnp.sign(v)
            if g is not None:
                out[k] = g
        return out


@dataclass
class FeedForwardLayer(BaseLayer):
    """Dense-style layers with explicit nIn/nOut (reference: FeedForwardLayer.java)."""

    n_in: int = 0
    n_out: int = 0

    INPUT_KIND = "ff"

    def set_n_in(self, input_type: InputType) -> None:
        if self.n_in == 0:
            self.n_in = input_type.flat_size()

    def output_type(self, input_type: InputType) -> InputType:
        if input_type.kind == "recurrent":
            return InputType.recurrent(self.n_out, input_type.timeseries_length)
        return InputType.feed_forward(self.n_out)
