"""MultiLayerNetwork: the sequential-network training stack.

Reference: nn/multilayer/MultiLayerNetwork.java:82 (2909 LoC) — init/param-flattening
(:443-493), fit loop (:1047-1145), feedForward (:753), backprop (:1148,1163), TBPTT
(:1364), output (:1717-1760), rnnTimeStep streaming state.

TPU-native design: parameters are a pytree ``{layer_idx: {name: Array}}``; the whole
fit iteration — forward, loss, jax.grad backward, updater — is ONE jitted XLA program
(the reference's Solver/StochasticGradientDescent/updater call stack collapses into
it). The reference's flat-parameter-view contract (one contiguous buffer, layer
params as views) is preserved through ``params_flat()``/``set_params_flat`` for
serialization and parameter-averaging parity.

TBPTT matches MultiLayerNetwork.doTruncatedBPTT: the sequence is segmented on the
time axis, hidden state (h, c) carries across segments with stop_gradient, and each
segment is one jitted step.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.nn.conf.builders import MultiLayerConfiguration
from deeplearning4j_tpu.nn.conf.layers.misc import CenterLossOutputLayer
from deeplearning4j_tpu.nn.conf.preprocessors import preprocessor_key
from deeplearning4j_tpu.nn.regularization import (add_regularization_grads,
                                                  penalty_value)
from deeplearning4j_tpu.optimize.bucketing import (BoundedCache, bucket_rows,
                                                   pad_rows)
from deeplearning4j_tpu.utils.pytree import flatten_params, unflatten_params

_RNN_KEYS = ("h", "c", "kcache", "vcache", "cache_pos",
             "kpages", "vpages", "block_table",
             "kscale", "vscale", "kscales", "vscales",
             "latent_cache", "latent_pages",
             "conv_state", "ssm_state", "recurrent_state", "call_counts")


def _split_state(state):
    """Split a layer-state dict into (persistent, rnn-carry) parts.

    h/c: recurrent hidden state (LSTM family). kcache/vcache/cache_pos:
    attention KV-cache streaming state (SelfAttentionLayer /
    PositionalEncodingLayer incremental decode) — present only when a
    streaming carry was seeded by rnn_time_step, never during training.
    kpages/vpages/block_table: the paged-pool variant of the same carry
    (GenerationServer's block-table serving path). kscale(s)/vscale(s):
    the per-token dequant planes riding an int8 KV-cache — carry, for
    the same reason the caches they describe are. latent_cache/
    latent_pages: a latent-attention layer's one plane of cached rows,
    dense and paged (LatentAttentionLayer). conv_state/ssm_state:
    a state-space layer's per-sequence state (Mamba2Layer).
    conv_state/recurrent_state: a linear-attention layer's
    (GatedDeltaNetLayer). call_counts: what a layer counts per forward
    call (its ``cache_spec().counters``), carried out of a serving
    program the same way."""
    persistent, carry = {}, {}
    for k, v in state.items():
        (carry if k in _RNN_KEYS else persistent)[k] = v
    return persistent, carry


class MultiLayerNetwork:
    def __init__(self, conf: MultiLayerConfiguration):
        self.conf = conf
        self.layers = conf.layers
        self.params: dict = {}
        self.state: dict = {}
        self.updater_state: dict = {}
        self.iteration = 0
        self.epoch = 0
        self.listeners: list = []
        # score_value CONTRACT: the most recent minibatch loss as an
        # array-like scalar — a device array after do_step (float() would
        # force a per-step sync and stall the dispatch pipeline), a numpy
        # scalar after a fused-fit block, float("nan") before any step. It
        # is NEVER guaranteed to be a Python float; coerce via score() (the
        # no-argument form) or float().
        self.score_value = float("nan")
        # active numerical-health policy (optimize/health.py) — set by fit()
        # for its duration (health_guard is ON by default there); do_step /
        # FusedFitDriver / CheckpointListener read it
        self._health = None
        self._base_key = None             # cached PRNGKey(seed), see _rng_base
        self._base_key_seed = None
        self._step_cache: dict = {}
        # inference/eval program cache: LRU-bounded, batch dim bucketed —
        # see optimize/bucketing.py (a serving workload with arbitrary
        # request sizes must not compile and hold a program per size)
        self._output_cache = BoundedCache()
        self._rnn_state: Optional[dict] = None  # streaming rnnTimeStep state
        self._stream_pos = 0              # tokens consumed this stream
        self._stream_capacity = None      # min attention max_cache, if any
        out = self.layers[-1] if self.layers else None
        self._has_loss_head = hasattr(out, "compute_loss_per_example")

    # ------------------------------------------------------------------ init
    def init(self, params: Optional[dict] = None) -> "MultiLayerNetwork":
        dtype = jnp.dtype(self.conf.dtype)
        rng = jax.random.PRNGKey(self.conf.seed)
        keys = jax.random.split(rng, max(len(self.layers), 1))
        if params is None:
            from deeplearning4j_tpu.utils.pytree import run_fused_on_tpu

            self.params = run_fused_on_tpu(
                lambda ks: {str(i): l.init_params(ks[i], dtype)
                            for i, l in enumerate(self.layers)}, keys)
        else:
            self.params = params
        self.state = {str(i): l.init_state(dtype) for i, l in enumerate(self.layers)}
        self.updater_state = self.conf.updater.init(self._trainable(self.params))
        return self

    def _trainable(self, params):
        return params

    # ------------------------------------------------------------- forward
    def _forward(self, params, state, x, mask, *, train, rng, carry=None,
                 upto: Optional[int] = None):
        """Run layers [0, upto). Returns (x_out, new_states, new_carry, mask_out)."""
        n = len(self.layers) if upto is None else upto
        new_states = {}
        new_carry = {}
        cur_mask = mask
        if rng is not None:
            keys = jax.random.split(rng, max(n, 1))
        for i in range(n):
            layer = self.layers[i]
            if i in self.conf.preprocessors:
                # derived, never keys[i] itself: a stochastic preprocessor
                # must not share its key with the layer behind it
                pk = preprocessor_key(keys[i]) if rng is not None else None
                x = self.conf.preprocessors[i].forward(x, rng=pk)
                cur_mask = self.conf.preprocessors[i].feed_forward_mask(cur_mask)
            layer_state = dict(state.get(str(i), {}))
            if carry is not None and str(i) in carry:
                layer_state.update(carry[str(i)])
            k = keys[i] if rng is not None else None
            x, ns = layer.forward(params[str(i)], layer_state, x, mask=cur_mask,
                                  train=train, rng=k)
            persistent, rnn_carry = _split_state(ns)
            new_states[str(i)] = persistent
            if rnn_carry:
                new_carry[str(i)] = rnn_carry
            cur_mask = layer.feed_forward_mask(cur_mask)
        return x, new_states, new_carry, cur_mask

    def feed_forward(self, x, train: bool = False):
        """All layer activations (reference: MultiLayerNetwork.feedForward :753)."""
        x = jnp.asarray(x)
        acts = [x]
        cur = x
        for i, layer in enumerate(self.layers):
            if i in self.conf.preprocessors:
                cur = self.conf.preprocessors[i].forward(cur)
            cur, _ = layer.forward(self.params[str(i)], self.state.get(str(i), {}),
                                   cur, train=train)
            acts.append(cur)
        return acts

    # --------------------------------------------------------------- loss
    def _loss(self, params, state, x, y, input_mask, label_mask, *, train, rng,
              carry=None):
        out_idx = len(self.layers) - 1
        cd = getattr(self.conf, "compute_dtype", None)
        fwd_params = params
        if cd is not None:
            # mixed precision: body layers compute in cd (bfloat16 -> MXU
            # fast path); the loss head and its params stay in the param
            # dtype. Gradients flow back through the casts to full-precision
            # leaves automatically.
            cdt = jnp.dtype(cd)
            fwd_params = {
                k: (jax.tree_util.tree_map(lambda a: a.astype(cdt), v)
                    if k != str(out_idx) else v)
                for k, v in params.items()}
            x = x.astype(cdt)
        last_in, new_states, new_carry, cur_mask = self._forward(
            fwd_params, state, x, input_mask, train=train, rng=rng,
            carry=carry, upto=out_idx)
        if cd is not None:
            last_in = last_in.astype(jnp.dtype(self.conf.dtype))
        out_layer = self.layers[out_idx]
        if out_idx in self.conf.preprocessors:
            # rng was already split inside _forward; consume only a derived
            # key here, never the parent itself
            last_in = self.conf.preprocessors[out_idx].forward(
                last_in, rng=preprocessor_key(rng))
        p_out = params[str(out_idx)]
        if isinstance(out_layer, CenterLossOutputLayer):
            per_ex = out_layer.compute_loss_per_example(
                p_out, last_in, y, state=state.get(str(out_idx)))
        else:
            per_ex = out_layer.compute_loss_per_example(p_out, last_in, y)
        lm = label_mask if label_mask is not None else cur_mask
        if lm is not None:
            lm = lm.reshape(per_ex.shape).astype(per_ex.dtype)
            data_loss = jnp.sum(per_ex * lm) / jnp.maximum(jnp.sum(lm), 1.0)
        else:
            data_loss = jnp.mean(per_ex)
        # the penalty VALUE stays in the reported score (reference:
        # computeScore adds fullNetworkL1+L2) but is not differentiated —
        # the train step adds the closed-form regularization_grad instead
        # (autodiff through these reductions measured 30% of the ResNet50
        # step, record deleted at PR 21); computed fused, not per-tensor
        # (per-tensor micro-reductions measured 43% of the bf16 step)
        reg = penalty_value(self, params)
        if not isinstance(reg, float):
            reg = jax.lax.stop_gradient(reg)
        new_states[str(out_idx)] = state.get(str(out_idx), {})
        return data_loss + reg, (new_states, new_carry, last_in)

    # ---------------------------------------------------------- train step
    def _lr_mult_tree(self):
        """Per-leaf learning-rate multiplier pytree (structure == params), honoring
        per-layer ``learning_rate`` and ``bias_learning_rate`` overrides (reference:
        BaseMultiLayerUpdater per-param LR resolution). Returns None when every
        multiplier is 1 (the common case — keeps the update one fused tree_map)."""
        base_lr = getattr(self.conf.updater, "learning_rate", None)
        if not base_lr:
            return None
        any_override = False
        tree: dict = {}
        for i, layer in enumerate(self.layers):
            layer_lr = getattr(layer, "learning_rate", None)
            bias_lr = getattr(layer, "bias_learning_rate", None)
            biases = (layer.bias_param_names()
                      if hasattr(layer, "bias_param_names") else frozenset())
            leaf = {}
            for name in self.params.get(str(i), {}):
                lr = bias_lr if (name in biases and bias_lr is not None) else layer_lr
                leaf[name] = (lr / base_lr) if lr is not None else 1.0
                if lr is not None:
                    any_override = True
            tree[str(i)] = leaf
        return tree if any_override else None

    def _rng_base(self):
        """Cached base PRNG key — rebuilt only when conf.seed changes. The
        per-step key is fold_in(base, iteration); reconstructing PRNGKey
        (an XLA dispatch) every do_step was pure per-iteration overhead."""
        if self._base_key is None or self._base_key_seed != self.conf.seed:
            self._base_key = jax.random.PRNGKey(self.conf.seed)
            self._base_key_seed = self.conf.seed
        return self._base_key

    def _make_step(self, with_carry: bool, guarded: bool = False):
        from deeplearning4j_tpu.optimize.fused_fit import build_step_core

        # the step body (forward/loss/grad/regularization/normalization/
        # updater/center-update) is the SHARED core also scanned by the
        # fused K-step driver and ParallelWrapper's device round
        core = build_step_core(self, guarded=guarded)

        def step(params, opt_state, state, rng, iteration, x, y, input_mask,
                 label_mask, carry):
            return core(params, opt_state, state, rng, iteration, x, y,
                        input_mask, label_mask,
                        carry if with_carry else None)

        # params/opt/state buffers are dead after the call (do_step rebinds
        # them from the outputs) — donation lets XLA update in place instead
        # of allocating a second copy of the model (VERDICT r2: trains held
        # 2x param memory for no reason)
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

    def do_step(self, x, y, input_mask=None, label_mask=None, carry=None):
        """One SGD iteration on one minibatch; returns the minibatch loss."""
        x = jnp.asarray(x)
        y = jnp.asarray(y)
        input_mask = jnp.asarray(input_mask) if input_mask is not None else None
        label_mask = jnp.asarray(label_mask) if label_mask is not None else None
        with_carry = carry is not None
        health = self._health
        guarded = health is not None
        key = (x.shape, y.shape, input_mask is not None, label_mask is not None,
               with_carry, guarded)
        step = self._get_step(key)
        rng = jax.random.fold_in(self._rng_base(), self.iteration)
        out = step(
            self.params, self.updater_state, self.state, rng,
            jnp.asarray(self.iteration, jnp.float32), x, y, input_mask, label_mask,
            carry if with_carry else {})
        if guarded:
            (self.params, self.updater_state, self.state, new_carry, loss,
             skip) = out
        else:
            self.params, self.updater_state, self.state, new_carry, loss = out
        self.iteration += 1
        # score_value stays a device scalar: float() would force a sync every
        # step and stall the dispatch pipeline; it coerces on first use
        self.score_value = loss
        it_done = self.iteration
        if guarded:
            # observe BEFORE listener dispatch: health-gated checkpoint
            # listeners (elastic.CheckpointListener) must see THIS step's
            # skip state, and a recovery/raise precedes the listener round
            score_h, skip_h = jax.device_get((loss, skip))
            health.observe(self, score_h, skip_h, it_done - 1)
        for listener in self.listeners:
            listener.iteration_done(self, it_done)
        return self.score_value, new_carry

    # ------------------------------------------------------------------ fit
    def fit(self, data, labels=None, epochs: int = 1, *,
            fused_steps: Optional[int] = None, prefetch_depth: int = 2,
            health_guard=True):
        """Train. ``data`` may be (features, labels) arrays, a DataSet, or a
        DataSetIterator (reference: MultiLayerNetwork.fit :1047).

        The default fast path fuses ``fused_steps`` minibatches (default
        ``optimize.fused_fit.DEFAULT_FUSED_STEPS``) into one jitted
        ``lax.scan`` block, each minibatch placed on the device as it is
        pulled, up to ``prefetch_depth`` blocks ahead of the block that
        runs (``optimize/fused_fit.py``) — pass ``fused_steps=1`` to opt
        out and run one jitted program per minibatch. TBPTT always runs
        unfused. Listeners still fire per iteration but scores materialize
        per block (one device fetch per ``fused_steps`` iterations);
        listener hooks observe end-of-block parameters.

        ``health_guard`` (default ON) fuses the numerical-health guard into
        the step: a non-finite loss/gradient microbatch is skipped on
        device (identity update) and a host-side recovery ladder handles
        divergence — LR backoff, then rollback to the last healthy-gated
        checkpoint (when the policy has a store), then ``DivergenceError``.
        Pass ``None``/``False`` to opt out, or an
        ``optimize.health.HealthPolicy`` to configure thresholds and attach
        an ``elastic.CheckpointStore``. Recovery events fire
        ``on_health(model, report)`` on attached listeners."""
        from deeplearning4j_tpu.datasets.dataset import DataSet
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
            if isinstance(data, DataSet):
                if K > 1 and epochs > 1:
                    # repeated single-batch fit: the epochs loop IS the
                    # stream — fuse it (the DataSet path fires no epoch
                    # listeners, so semantics are unchanged)
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
        if self.conf.backprop_type == "tbptt" and ds.features.ndim == 3:
            self._fit_tbptt(ds)
        else:
            self.do_step(ds.features, ds.labels, ds.features_mask, ds.labels_mask)

    def _fit_tbptt(self, ds):
        """Truncated BPTT (reference: MultiLayerNetwork.java:1364 doTruncatedBPTT)."""
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

    # ------------------------------------------------------------- inference
    def _get_output(self, key, build):
        """Bounded cache for the inference/eval program family (forward,
        rnn-stream, fused-eval). One hook point, so the test suite's
        recompile guard can count cache misses per network instance."""
        if key not in self._output_cache:
            self._output_cache[key] = build()
        return self._output_cache[key]

    def output(self, x, train: bool = False, mask=None):
        """Final-layer activations (reference: MultiLayerNetwork.output :1717,
        incl. the mask-array overload — masks flow through the layers so e.g.
        LastTimeStep / masked global pooling are correct for padded batches).

        The batch dim is BUCKETED (padded to the next power of two by
        replicating the last row, stripped from the result) so the jit cache
        holds O(log max_batch) programs instead of one per request size."""
        x = jnp.asarray(x)
        mask = jnp.asarray(mask) if mask is not None else None
        n = x.shape[0]
        B = bucket_rows(n)
        if B != n:
            x = pad_rows(x, B)
            if mask is not None:
                mask = pad_rows(mask, B)
        key = (x.shape, train, mask is not None)

        def build():
            def fwd(params, state, xx, mm):
                out, _, _, _ = self._forward(params, state, xx, mm,
                                             train=train, rng=None)
                return out
            return jax.jit(fwd)

        out = self._get_output(key, build)(self.params, self.state, x, mask)
        return out if B == n else out[:n]

    def score(self, ds=None, x=None, y=None) -> float:
        """Loss (incl. regularization) on a dataset, as a Python float
        (reference: computeGradientAndScore). With NO arguments, coerces and
        returns the last training minibatch's loss — the float view of the
        ``score_value`` contract (score_value itself stays device-side)."""
        if ds is None and x is None:
            return float(self.score_value)
        if ds is not None:
            x, y = ds.features, ds.labels
            im, lm = ds.features_mask, ds.labels_mask
        else:
            im = lm = None
        loss, _ = self._loss(self.params, self.state, jnp.asarray(x), jnp.asarray(y),
                             None if im is None else jnp.asarray(im),
                             None if lm is None else jnp.asarray(lm),
                             train=False, rng=None)
        return float(loss)

    def evaluate(self, data, labels=None, *, top_n: int = 1, fused=None,
                 eval_batches: Optional[int] = None, prefetch_depth: int = 2):
        """Classification evaluation (reference: MultiLayerNetwork.evaluate).

        The default fast path is the device-resident fused evaluator
        (evaluation/fused_eval.py): forward + argmax + masked scatter-add
        into a donated device accumulator, ``eval_batches`` batches per
        dispatch, ONE small fetch per call instead of per-batch logit
        transfers. Pass ``fused=False`` to opt out (per-batch ``output()``
        + host numpy counting)."""
        from deeplearning4j_tpu.evaluation.classification import Evaluation
        from deeplearning4j_tpu.datasets.dataset import DataSet

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
            out = self.output(ds.features, mask=ds.features_mask)
            ev.eval(ds.labels, np.asarray(out), mask=ds.labels_mask)
        return ev

    # ------------------------------------------------------- rnn streaming
    def rnn_clear_previous_state(self):
        self._rnn_state = None
        self._stream_pos = 0
        self._stream_capacity = None

    def rnn_time_step(self, x):
        """Streaming single/multi-step inference with persistent state (reference:
        MultiLayerNetwork.rnnTimeStep)."""
        x = jnp.asarray(x)
        squeeze = False
        if x.ndim == 2:  # [B, F] -> single timestep
            x = x[:, None, :]
            squeeze = True
        if self._rnn_state is None:
            # fresh stream: layers that stream through explicit caches
            # (attention KV caches) seed their carry here; LSTMs need
            # nothing (h/c default lazily to zeros)
            self._rnn_state = self._seed_streaming_carry(x.shape[0])
        # overflow must be caught HERE (static position accounting): the
        # jitted step's cache_pos is a tracer, and dynamic_update_slice
        # would silently clamp and corrupt the cache tail
        if self._stream_capacity is not None and \
                self._stream_pos + x.shape[1] > self._stream_capacity:
            raise ValueError(
                f"KV cache overflow: stream position {self._stream_pos} + "
                f"{x.shape[1]} new tokens > max_cache "
                f"{self._stream_capacity}; raise SelfAttentionLayer."
                "max_cache or rnn_clear_previous_state()")
        self._stream_pos += x.shape[1]
        carry = self._rnn_state or {}
        # jitted per (shape, carry structure) — see ComputationGraph
        # .rnn_time_step: eager per-op dispatch dominates streaming cost
        key = ("rnn_stream", x.shape, jax.tree_util.tree_structure(carry))

        def build():
            def fwd(params, state, x, carry):
                out, _, new_carry, _ = self._forward(
                    params, state, x, None, train=False, rng=None,
                    carry=carry)
                return out, new_carry
            return jax.jit(fwd)

        out, new_carry = self._get_output(key, build)(self.params, self.state,
                                                      x, carry)
        self._rnn_state = new_carry
        return out[:, 0] if squeeze and out.ndim == 3 else out

    def _stream_layers(self):
        """(name, layer) pairs keyed exactly as the streaming carry dict —
        the shared vocabulary between ``_seed_streaming_carry`` and
        carry-restructuring callers (GenerationServer's paged pool)."""
        for i, layer in enumerate(self.layers):
            yield str(i), layer

    def _seed_streaming_carry(self, batch: int) -> dict:
        """Initial streaming carry + resets static overflow accounting."""
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

    # ---------------------------------------------------------- pretraining
    def pretrain(self, data_iterator, epochs: int = 1):
        """Layerwise unsupervised pretraining for VAE/AutoEncoder layers
        (reference: MultiLayerNetwork.pretrain)."""
        for i, layer in enumerate(self.layers):
            if not hasattr(layer, "pretrain_loss_per_example") and \
               not hasattr(layer, "reconstruction_loss_per_example"):
                continue
            self._pretrain_layer(i, data_iterator, epochs)
        return self

    def _pretrain_layer(self, idx, data_iterator, epochs):
        layer = self.layers[idx]
        updater = self.conf.updater
        opt_state = updater.init({str(idx): self.params[str(idx)]})

        @jax.jit
        def pstep(p_layer, opt_state, all_params, rng, iteration, x):
            # three independent keys: lower-stack forward (so stochastic
            # preprocessors BELOW idx resample fresh every step instead of
            # freezing on their rng=None fallback), this layer's input
            # preprocessor, and the pretrain loss itself
            k_fwd, k_prep, k_loss = jax.random.split(rng, 3)
            feats, _, _, _ = self._forward(all_params, self.state, x, None,
                                           train=False, rng=k_fwd, upto=idx)
            if idx in self.conf.preprocessors:
                feats = self.conf.preprocessors[idx].forward(feats,
                                                             rng=k_prep)

            def loss_fn(pl):
                if hasattr(layer, "pretrain_loss_per_example"):
                    per = layer.pretrain_loss_per_example(pl[str(idx)], feats,
                                                          k_loss)
                else:
                    per = layer.reconstruction_loss_per_example(
                        pl[str(idx)], feats, k_loss)
                return jnp.mean(per)

            loss, grads = jax.value_and_grad(loss_fn)(p_layer)
            # regularization.py invariant: every jax.grad consumer adds the
            # closed-form l1/l2 gradient (DL4J's BaseUpdater.postApply
            # applies decay during layerwise pretraining too); layers
            # outside p_layer contribute nothing
            grads = add_regularization_grads(self, p_layer, grads)
            steps, new_opt = updater.step(grads, opt_state, iteration)
            new_p = jax.tree_util.tree_map(lambda p, s: p - s, p_layer, steps)
            return new_p, new_opt, loss

        it = 0
        for _ in range(epochs):
            if hasattr(data_iterator, "reset"):
                data_iterator.reset()
            iterable = (data_iterator if not hasattr(data_iterator, "features")
                        else [data_iterator])
            for ds in iterable:
                rng = jax.random.fold_in(jax.random.PRNGKey(self.conf.seed + idx), it)
                p_layer = {str(idx): self.params[str(idx)]}
                p_layer, opt_state, loss = pstep(
                    p_layer, opt_state, self.params, rng,
                    jnp.asarray(it, jnp.float32), jnp.asarray(ds.features))
                self.params[str(idx)] = p_layer[str(idx)]
                it += 1

    # ------------------------------------------------------- params plumbing
    def params_flat(self) -> np.ndarray:
        """One contiguous parameter vector (reference: MultiLayerNetwork.params() /
        flattenedParams, :103,443-493). Order: layer index, then param_order."""
        return flatten_params(self.params, self.layers)

    def set_params_flat(self, flat) -> None:
        self.params = unflatten_params(flat, self.params, self.layers)

    def num_params(self) -> int:
        return int(sum(np.prod(v.shape) for lp in self.params.values()
                       for v in lp.values()))

    def set_listeners(self, *listeners):
        self.listeners = list(listeners)
        return self

    def add_listener(self, listener):
        self.listeners.append(listener)
        return self

    def clone(self) -> "MultiLayerNetwork":
        import copy
        net = MultiLayerNetwork(copy.deepcopy(self.conf))
        net.init()
        # leaf .copy(): the train step donates its input buffers, so a
        # reference-sharing clone would be invalidated by further training
        net.params = jax.tree_util.tree_map(lambda a: a.copy(), self.params)
        net.state = jax.tree_util.tree_map(lambda a: a.copy(), self.state)
        net.updater_state = jax.tree_util.tree_map(lambda a: a.copy(),
                                           self.updater_state)
        net.iteration = self.iteration
        return net
