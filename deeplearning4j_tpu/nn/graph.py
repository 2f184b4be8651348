"""ComputationGraph: DAG-network training stack.

Reference: nn/graph/ComputationGraph.java:83 (3118 LoC) — topological init
(:358,1084-1186), multi-input/output fit (:753-1030), computeGradientAndScore
(:1189-1235), vertex-map feedForward (:1247-1290).

TPU-native design mirrors MultiLayerNetwork (nn/multilayer.py): params are a
pytree ``{vertex_name: {param: Array}}``; one fit iteration — forward over the
topo-sorted DAG, summed output losses, jax.grad backward, updater — is ONE
jitted XLA program. Score is the sum of output-layer losses plus regularization
counted once (parity with ComputationGraph.java:1214-1228).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nn.conf.graph_conf import (
    ComputationGraphConfiguration,
    LayerVertex,
)
from deeplearning4j_tpu.nn.conf.layers.misc import CenterLossOutputLayer
from deeplearning4j_tpu.nn.conf.preprocessors import preprocessor_key
from deeplearning4j_tpu.nn.regularization import penalty_value
from deeplearning4j_tpu.nn.multilayer import _split_state
from deeplearning4j_tpu.optimize.bucketing import (BoundedCache, bucket_rows,
                                                   pad_rows)


def _as_list(x):
    if x is None:
        return None
    if isinstance(x, (list, tuple)):
        return list(x)
    return [x]


class ComputationGraph:
    def __init__(self, conf: ComputationGraphConfiguration):
        self.conf = conf
        self.params: dict = {}
        self.state: dict = {}
        self.updater_state: dict = {}
        self.iteration = 0
        self.epoch = 0
        self.listeners: list = []
        # score_value contract: array-like scalar, never guaranteed to be a
        # Python float — see MultiLayerNetwork (score() coerces)
        self.score_value = float("nan")
        # active numerical-health policy (optimize/health.py) — set by fit()
        # for its duration; see MultiLayerNetwork
        self._health = None
        self._base_key = None             # cached PRNGKey(seed), see _rng_base
        self._base_key_seed = None
        self._step_cache: dict = {}
        # inference/eval program cache: LRU-bounded, batch dim bucketed —
        # see optimize/bucketing.py
        self._output_cache = BoundedCache()
        self._rnn_state: Optional[dict] = None
        self._stream_pos = 0              # tokens consumed this stream
        self._stream_capacity = None      # min attention max_cache, if any

    # ------------------------------------------------------------------ init
    def init(self, params: Optional[dict] = None) -> "ComputationGraph":
        dtype = jnp.dtype(self.conf.dtype)
        rng = jax.random.PRNGKey(self.conf.seed)
        order = self.conf.topo_order
        keys = jax.random.split(rng, max(len(order), 1))
        if params is None:
            from deeplearning4j_tpu.utils.pytree import run_fused_on_tpu

            self.params = run_fused_on_tpu(
                lambda ks: {name: self.conf.vertices[name].init_params(
                    ks[i], dtype) for i, name in enumerate(order)}, keys)
        else:
            self.params = params
        self.state = {name: self.conf.vertices[name].init_state(dtype)
                      for name in order}
        self.updater_state = self.conf.updater.init(self.params)
        return self

    # --------------------------------------------------------------- forward
    def _forward(self, params, state, inputs, masks, *, train, rng, carry=None,
                 collect_loss_inputs=False):
        """Traverse the DAG in topo order.

        inputs/masks: lists parallel to conf.network_inputs. Returns
        (outputs list, new_states, new_carry, output_masks list, loss_inputs)
        where loss_inputs[name] is the post-preprocessor input to each output
        LayerVertex (what its loss head consumes).
        """
        conf = self.conf
        acts: dict = {k: v for k, v in zip(conf.network_inputs, inputs)}
        act_masks: dict = {k: m for k, m in zip(conf.network_inputs,
                                                masks or [None] * len(inputs))}
        ctx = {"input_arrays": dict(acts), "input_masks": dict(act_masks)}
        new_states: dict = {}
        new_carry: dict = {}
        loss_inputs: dict = {}
        if rng is not None:
            keys = jax.random.split(rng, max(len(conf.topo_order), 1))
        for i, name in enumerate(conf.topo_order):
            v = conf.vertices[name]
            v_in = [acts[k] for k in conf.vertex_inputs[name]]
            v_masks = [act_masks.get(k) for k in conf.vertex_inputs[name]]
            vertex_state = dict(state.get(name, {}))
            if carry is not None and name in carry:
                vertex_state.update(carry[name])
            k = keys[i] if rng is not None else None
            if (collect_loss_inputs and name in conf.network_outputs
                    and isinstance(v, LayerVertex)
                    and hasattr(v.layer, "compute_loss_per_example")):
                x = v_in[0]
                if v.preprocessor is not None:
                    # same derived key as LayerVertex.forward uses, so this
                    # collected loss input is bit-identical to the vertex's
                    # own activation even for stochastic preprocessors
                    x = v.preprocessor.forward(x, rng=preprocessor_key(k))
                loss_inputs[name] = x
            out, ns = v.forward(params.get(name, {}), vertex_state, v_in,
                                masks=v_masks, ctx=ctx, train=train, rng=k)
            persistent, rnn_carry = _split_state(ns)
            new_states[name] = persistent
            if rnn_carry:
                new_carry[name] = rnn_carry
            acts[name] = out
            act_masks[name] = v.feed_forward_mask(v_masks)
        outs = [acts[o] for o in conf.network_outputs]
        out_masks = [act_masks.get(o) for o in conf.network_outputs]
        return outs, new_states, new_carry, out_masks, loss_inputs

    def feed_forward(self, *inputs, train: bool = False):
        """All vertex activations as {name: array} (reference:
        ComputationGraph.feedForward :1247-1290)."""
        conf = self.conf
        acts = {k: jnp.asarray(v) for k, v in zip(conf.network_inputs, inputs)}
        ctx = {"input_arrays": dict(acts), "input_masks": {}}
        for name in conf.topo_order:
            v = conf.vertices[name]
            v_in = [acts[k] for k in conf.vertex_inputs[name]]
            out, _ = v.forward(self.params.get(name, {}),
                               self.state.get(name, {}), v_in,
                               masks=None, ctx=ctx, train=train)
            acts[name] = out
        return acts

    # ------------------------------------------------------------------ loss
    def _loss(self, params, state, x, y, input_mask, label_mask, *, train, rng,
              carry=None):
        conf = self.conf
        xs = _as_list(x)
        ys = _as_list(y)
        ims = _as_list(input_mask) or [None] * len(xs)
        lms = _as_list(label_mask) or [None] * len(ys)
        cd = getattr(conf, "compute_dtype", None)
        fwd_params = params
        if cd is not None:
            # mixed precision (see MultiLayerNetwork._loss): non-output
            # vertices compute in cd; loss heads keep the param dtype
            cdt = jnp.dtype(cd)
            outs_set = set(conf.network_outputs)
            fwd_params = {
                k: (jax.tree_util.tree_map(lambda a: a.astype(cdt), v)
                    if k not in outs_set else v)
                for k, v in params.items()}
            xs = [a.astype(cdt) for a in xs]
        _, new_states, new_carry, out_masks, loss_inputs = self._forward(
            fwd_params, state, xs, ims, train=train, rng=rng, carry=carry,
            collect_loss_inputs=True)
        if cd is not None:
            pdt = jnp.dtype(conf.dtype)
            loss_inputs = {k: v.astype(pdt) for k, v in loss_inputs.items()}
        total = 0.0
        last_in_by_out = {}
        for j, name in enumerate(conf.network_outputs):
            v = conf.vertices[name]
            if not (isinstance(v, LayerVertex)
                    and hasattr(v.layer, "compute_loss_per_example")):
                raise ValueError(f"Output vertex '{name}' has no loss head")
            last_in = loss_inputs[name]
            last_in_by_out[name] = last_in
            if isinstance(v.layer, CenterLossOutputLayer):
                per_ex = v.layer.compute_loss_per_example(
                    params[name], last_in, ys[j], state=state.get(name))
            else:
                per_ex = v.layer.compute_loss_per_example(params[name], last_in,
                                                          ys[j])
            lm = lms[j] if lms[j] is not None else out_masks[j]
            if lm is not None:
                lm = lm.reshape(per_ex.shape).astype(per_ex.dtype)
                total = total + jnp.sum(per_ex * lm) / jnp.maximum(jnp.sum(lm),
                                                                   1.0)
            else:
                total = total + jnp.mean(per_ex)
            new_states[name] = state.get(name, {})
        # penalty value reported, not differentiated — the step adds the
        # closed-form regularization_grad (see MultiLayerNetwork._loss);
        # computed fused over concatenated params, not per-tensor (480
        # micro-reductions measured 43% of the bf16 ResNet50 b128 step)
        reg = penalty_value(self, params)
        if not isinstance(reg, float):
            reg = jax.lax.stop_gradient(reg)
        return total + reg, (new_states, new_carry, last_in_by_out)

    # ------------------------------------------------------------ train step
    def _lr_mult_tree(self):
        """Per-leaf LR multipliers honoring per-layer learning_rate overrides
        (mirrors MultiLayerNetwork._lr_mult_tree)."""
        base_lr = getattr(self.conf.updater, "learning_rate", None)
        if not base_lr:
            return None
        any_override = False
        tree: dict = {}
        for name in self.conf.topo_order:
            v = self.conf.vertices[name]
            layer = v.layer if isinstance(v, LayerVertex) else None
            layer_lr = getattr(layer, "learning_rate", None)
            bias_lr = getattr(layer, "bias_learning_rate", None)
            biases = (layer.bias_param_names()
                      if layer is not None and hasattr(layer, "bias_param_names")
                      else frozenset())
            leaf = {}
            for pname in self.params.get(name, {}):
                lr = (bias_lr if (pname in biases and bias_lr is not None)
                      else layer_lr)
                leaf[pname] = (lr / base_lr) if lr is not None else 1.0
                if lr is not None:
                    any_override = True
            tree[name] = leaf
        return tree if any_override else None

    def _rng_base(self):
        """Cached base PRNG key (see MultiLayerNetwork._rng_base)."""
        if self._base_key is None or self._base_key_seed != self.conf.seed:
            self._base_key = jax.random.PRNGKey(self.conf.seed)
            self._base_key_seed = self.conf.seed
        return self._base_key

    def _make_step(self, with_carry: bool, guarded: bool = False):
        from deeplearning4j_tpu.optimize.fused_fit import build_step_core

        # shared step body — also scanned by the fused K-step driver and
        # ParallelWrapper's device round (see optimize/fused_fit.py)
        core = build_step_core(self, guarded=guarded)

        def step(params, opt_state, state, rng, iteration, xs, ys, ims, lms,
                 carry):
            return core(params, opt_state, state, rng, iteration, xs, ys,
                        ims, lms, carry if with_carry else None)

        # donated: do_step rebinds params/opt/state from the outputs
        return jax.jit(step, donate_argnums=(0, 1, 2))

    def _get_step(self, key):
        if key not in self._step_cache:
            if key[0] == "fused":
                from deeplearning4j_tpu.optimize.fused_fit import \
                    build_fused_step
                self._step_cache[key] = build_fused_step(self,
                                                         guarded=key[-1])
            else:
                self._step_cache[key] = self._make_step(with_carry=key[-2],
                                                        guarded=key[-1])
        return self._step_cache[key]

    def do_step(self, xs, ys, input_masks=None, label_masks=None, carry=None):
        """One SGD iteration; returns (loss, new_carry)."""
        xs = [jnp.asarray(a) for a in _as_list(xs)]
        ys = [jnp.asarray(a) for a in _as_list(ys)]
        ims = ([None if m is None else jnp.asarray(m)
                for m in _as_list(input_masks)] if input_masks is not None
               else None)
        lms = ([None if m is None else jnp.asarray(m)
                for m in _as_list(label_masks)] if label_masks is not None
               else None)
        with_carry = carry is not None
        health = self._health
        guarded = health is not None
        key = (tuple(a.shape for a in xs), tuple(a.shape for a in ys),
               ims is not None and any(m is not None for m in ims),
               lms is not None and any(m is not None for m in lms), with_carry,
               guarded)
        step = self._get_step(key)
        rng = jax.random.fold_in(self._rng_base(), self.iteration)
        out = step(
            self.params, self.updater_state, self.state, rng,
            jnp.asarray(self.iteration, jnp.float32), xs, ys, ims, lms,
            carry if with_carry else {})
        if guarded:
            (self.params, self.updater_state, self.state, new_carry, loss,
             skip) = out
        else:
            self.params, self.updater_state, self.state, new_carry, loss = out
        self.iteration += 1
        # device scalar, not float(): no forced sync per step (see
        # MultiLayerNetwork.do_step)
        self.score_value = loss
        it_done = self.iteration
        if guarded:
            # observe BEFORE listener dispatch — see MultiLayerNetwork
            # .do_step: gated checkpointers need this step's skip state
            score_h, skip_h = jax.device_get((loss, skip))
            health.observe(self, score_h, skip_h, it_done - 1)
        for listener in self.listeners:
            listener.iteration_done(self, it_done)
        return self.score_value, new_carry

    # ------------------------------------------------------------------- fit
    def fit(self, data, labels=None, epochs: int = 1, *,
            fused_steps: Optional[int] = None, prefetch_depth: int = 2,
            health_guard=True):
        """Train on a DataSet / MultiDataSet / iterator of either (reference:
        ComputationGraph.fit :753-1030).

        Single-input single-output DataSet streams default to the fused
        K-step fast path (see MultiLayerNetwork.fit and
        optimize/fused_fit.py); ``fused_steps=1`` opts out. MultiDataSet
        batches and TBPTT always take the per-minibatch path.

        ``health_guard`` (default ON): device-side skip of non-finite
        steps + host-side recovery ladder — see MultiLayerNetwork.fit and
        optimize/health.py. Pass ``None``/``False`` to opt out, or a
        configured ``optimize.health.HealthPolicy``."""
        from deeplearning4j_tpu.datasets.dataset import DataSet, MultiDataSet
        from deeplearning4j_tpu.optimize.fused_fit import (FusedFitDriver,
                                                           resolve_fused_steps)
        from deeplearning4j_tpu.optimize.health import resolve_health_policy

        if labels is not None:
            data = DataSet(np.asarray(data), np.asarray(labels))
        K = resolve_fused_steps(self, fused_steps)
        policy = resolve_health_policy(health_guard)
        prev_health = self._health
        if policy is not None:
            policy.bind(self)
        self._health = policy
        try:
            if isinstance(data, (DataSet, MultiDataSet)):
                if K > 1 and epochs > 1 and isinstance(data, DataSet):
                    # repeated single-batch fit: fuse the epochs loop (this
                    # path fires no epoch listeners, so semantics are
                    # unchanged)
                    FusedFitDriver(self, K, prefetch_depth).fit_stream(
                        data for _ in range(epochs))
                    return self
                for _ in range(epochs):
                    self._fit_batch(data)
                return self
            driver = (FusedFitDriver(self, K, prefetch_depth)
                      if K > 1 else None)
            for _ in range(epochs):
                for listener in self.listeners:
                    listener.on_epoch_start(self)
                if hasattr(data, "reset"):
                    data.reset()
                if driver is not None:
                    driver.fit_stream(iter(data))
                else:
                    for ds in data:
                        self._fit_batch(ds)
                for listener in self.listeners:
                    listener.on_epoch_end(self)
                self.epoch += 1
            return self
        finally:
            self._health = prev_health

    def _fit_batch(self, ds):
        from deeplearning4j_tpu.datasets.dataset import MultiDataSet

        if isinstance(ds, MultiDataSet):
            self.do_step(ds.features, ds.labels,
                         ds.features_masks if any(m is not None
                                                  for m in ds.features_masks)
                         else None,
                         ds.labels_masks if any(m is not None
                                                for m in ds.labels_masks)
                         else None)
            return
        if (self.conf.backprop_type == "tbptt" and ds.features.ndim == 3
                and len(self.conf.network_inputs) == 1):
            self._fit_tbptt(ds)
        else:
            self.do_step(ds.features, ds.labels, ds.features_mask,
                         ds.labels_mask)

    def _fit_tbptt(self, ds):
        """Truncated BPTT over single-input single-output rnn graphs (reference:
        ComputationGraph TBPTT path, rnnActivateUsingStoredState :1192-1200)."""
        T = ds.features.shape[1]
        L = self.conf.tbptt_fwd_length
        n_seg = max(1, math.ceil(T / L))
        carry: dict = {}
        for s in range(n_seg):
            sl = slice(s * L, min((s + 1) * L, T))
            fx = ds.features[:, sl]
            fy = ds.labels[:, sl] if ds.labels.ndim == 3 else ds.labels
            fm = ds.features_mask[:, sl] if ds.features_mask is not None else None
            lm = ds.labels_mask[:, sl] if ds.labels_mask is not None else None
            _, carry = self.do_step(fx, fy, fm, lm, carry=carry)
            carry = jax.tree_util.tree_map(jax.lax.stop_gradient, carry)

    # -------------------------------------------------------------- inference
    def _get_output(self, key, build):
        """Bounded cache for the inference/eval program family (forward,
        rnn-stream, fused-eval) — see MultiLayerNetwork._get_output."""
        if key not in self._output_cache:
            self._output_cache[key] = build()
        return self._output_cache[key]

    def output(self, *inputs, train: bool = False, masks=None):
        """Output-vertex activations; single output returns the bare array
        (reference: ComputationGraph.output). The shared batch dim is
        bucketed to the next power of two (see optimize/bucketing.py) and
        the padding stripped from every output."""
        xs = [jnp.asarray(a) for a in inputs]
        ms = ([None if m is None else jnp.asarray(m) for m in _as_list(masks)]
              if masks is not None else [None] * len(xs))
        n = xs[0].shape[0]
        B = bucket_rows(n)
        if B != n:
            xs = [pad_rows(a, B) for a in xs]
            ms = [None if m is None else pad_rows(m, B) for m in ms]
        key = (tuple(a.shape for a in xs), train,
               tuple(m is not None for m in ms))

        def build():
            def fwd(params, state, xs, ms):
                outs, _, _, _, _ = self._forward(params, state, xs, ms,
                                                 train=train, rng=None)
                return outs
            return jax.jit(fwd)

        outs = self._get_output(key, build)(self.params, self.state, xs, ms)
        if B != n:
            outs = [o[:n] for o in outs]
        return outs[0] if len(outs) == 1 else outs

    def score(self, ds=None, x=None, y=None) -> float:
        from deeplearning4j_tpu.datasets.dataset import MultiDataSet

        if ds is None and x is None:
            # coerce the device-side score_value to a host float on demand
            return float(self.score_value)
        if isinstance(ds, MultiDataSet):
            x, y = ds.features, ds.labels
            im = (ds.features_masks if any(m is not None
                                           for m in ds.features_masks) else None)
            lm = (ds.labels_masks if any(m is not None
                                         for m in ds.labels_masks) else None)
        elif ds is not None:
            x, y = ds.features, ds.labels
            im, lm = ds.features_mask, ds.labels_mask
        else:
            im = lm = None
        xs = [jnp.asarray(a) for a in _as_list(x)]
        ys = [jnp.asarray(a) for a in _as_list(y)]
        loss, _ = self._loss(
            self.params, self.state, xs, ys,
            None if im is None else [None if m is None else jnp.asarray(m)
                                     for m in _as_list(im)],
            None if lm is None else [None if m is None else jnp.asarray(m)
                                     for m in _as_list(lm)],
            train=False, rng=None)
        return float(loss)

    def evaluate(self, data, labels=None, *, top_n: int = 1, fused=None,
                 eval_batches: Optional[int] = None, prefetch_depth: int = 2):
        """Single-output classification evaluation (reference:
        ComputationGraph.evaluate). Defaults to the device-resident fused
        evaluator (evaluation/fused_eval.py — K batches per dispatch, one
        small fetch per call); pass ``fused=False`` for the per-batch
        ``output()`` + host numpy path."""
        from deeplearning4j_tpu.datasets.dataset import DataSet
        from deeplearning4j_tpu.evaluation.classification import Evaluation

        ev = Evaluation(top_n=top_n)
        if labels is not None:
            data = [DataSet(np.asarray(data), np.asarray(labels))]
        elif isinstance(data, DataSet):
            data = [data]
        elif hasattr(data, "reset"):
            data.reset()
        if fused is None or fused:
            from deeplearning4j_tpu.evaluation.fused_eval import \
                FusedEvalDriver
            return FusedEvalDriver(self, eval_batches,
                                   prefetch_depth).evaluate(data, ev)
        for ds in data:
            out = self.output(ds.features, masks=ds.features_mask)
            ev.eval(ds.labels, np.asarray(out), mask=ds.labels_mask)
        return ev

    # -------------------------------------------------------- rnn streaming
    def rnn_clear_previous_state(self):
        self._rnn_state = None
        self._stream_pos = 0
        self._stream_capacity = None

    def _stream_layers(self):
        """(name, layer) pairs keyed exactly as the streaming carry dict —
        the shared vocabulary between ``_seed_streaming_carry`` and
        carry-restructuring callers (GenerationServer's paged pool)."""
        for name, v in self.conf.vertices.items():
            layer = getattr(v, "layer", None)
            if layer is not None and hasattr(layer, "init_streaming_carry"):
                yield name, layer

    def _seed_streaming_carry(self, batch: int) -> dict:
        """Initial streaming carry (attention KV caches / positional
        counters) + side effects: resets the static overflow accounting."""
        dtype = jnp.dtype(self.conf.dtype)
        seed = {}
        caps = []
        for name, layer in self._stream_layers():
            c = layer.init_streaming_carry(batch, dtype)
            if c:
                seed[name] = c
                if hasattr(layer, "max_cache"):
                    caps.append(layer.max_cache)
        self._stream_pos = 0
        self._stream_capacity = min(caps) if caps else None
        return seed

    def rnn_time_step(self, *inputs):
        """Streaming inference with persistent rnn state (reference:
        ComputationGraph.rnnTimeStep)."""
        xs = []
        squeeze = False
        for x in inputs:
            x = jnp.asarray(x)
            if x.ndim == 2:
                x = x[:, None, :]
                squeeze = True
            xs.append(x)
        if self._rnn_state is None:
            # fresh stream: seed explicit streaming caches (attention KV
            # caches / positional counters); see MultiLayerNetwork
            self._rnn_state = self._seed_streaming_carry(xs[0].shape[0])
        # static overflow accounting — under jit the layer's cache_pos is
        # a tracer and dynamic_update_slice would silently clamp
        T_in = xs[0].shape[1]
        if self._stream_capacity is not None and \
                self._stream_pos + T_in > self._stream_capacity:
            raise ValueError(
                f"KV cache overflow: stream position {self._stream_pos} + "
                f"{T_in} new tokens > max_cache {self._stream_capacity}; "
                "raise SelfAttentionLayer.max_cache or "
                "rnn_clear_previous_state()")
        self._stream_pos += T_in
        carry = self._rnn_state or {}
        # ONE jitted program per (shapes, carry structure): the eager
        # per-op path costs ~100 host-device round trips per step of a
        # 4-block transformer
        key = ("rnn_stream", tuple(a.shape for a in xs),
               jax.tree_util.tree_structure(carry))

        def build():
            def fwd(params, state, xs, carry):
                outs, _, new_carry, _, _ = self._forward(
                    params, state, xs, [None] * len(xs), train=False,
                    rng=None, carry=carry)
                return outs, new_carry
            return jax.jit(fwd)

        outs, new_carry = self._get_output(key, build)(self.params,
                                                       self.state, xs, carry)
        self._rnn_state = new_carry
        outs = [o[:, 0] if squeeze and o.ndim == 3 else o for o in outs]
        return outs[0] if len(outs) == 1 else outs

    # ------------------------------------------------------- params plumbing
    def params_flat(self) -> np.ndarray:
        """Contiguous param vector in (topo order, param_order) order —
        the graph analogue of MultiLayerNetwork.params()."""
        chunks = []
        for name in self.conf.topo_order:
            v = self.conf.vertices[name]
            lp = self.params.get(name, {})
            for pname in v.param_order():
                if pname in lp:
                    chunks.append(np.asarray(lp[pname]).ravel())
        if not chunks:
            return np.zeros((0,), np.float32)
        return np.concatenate(chunks)

    def set_params_flat(self, flat) -> None:
        flat = np.asarray(flat).ravel()
        off = 0
        out = {}
        for name in self.conf.topo_order:
            v = self.conf.vertices[name]
            lp = dict(self.params.get(name, {}))
            for pname in v.param_order():
                if pname in lp:
                    tmpl = lp[pname]
                    n = int(np.prod(tmpl.shape)) if tmpl.shape else 1
                    lp[pname] = jnp.asarray(
                        flat[off:off + n].reshape(tmpl.shape),
                        dtype=tmpl.dtype)
                    off += n
            out[name] = lp
        if off != flat.size:
            raise ValueError(f"Flat param size {flat.size} != expected {off}")
        self.params = out

    def num_params(self) -> int:
        return int(sum(np.prod(v.shape) for lp in self.params.values()
                       for v in lp.values()))

    def set_listeners(self, *listeners):
        self.listeners = list(listeners)
        return self

    def add_listener(self, listener):
        self.listeners.append(listener)
        return self

    def clone(self) -> "ComputationGraph":
        import copy
        net = ComputationGraph(copy.deepcopy(self.conf))
        net.init()
        # leaf .copy(): the train step donates its input buffers, so a
        # reference-sharing clone would be invalidated by further training
        net.params = jax.tree_util.tree_map(lambda a: a.copy(), self.params)
        net.state = jax.tree_util.tree_map(lambda a: a.copy(), self.state)
        net.updater_state = jax.tree_util.tree_map(lambda a: a.copy(),
                                                   self.updater_state)
        net.iteration = self.iteration
        return net
