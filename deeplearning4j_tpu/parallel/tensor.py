"""Tensor parallelism: Megatron-style sharded dense pairs over a mesh axis.

No reference equivalent (SURVEY §2.4 checklist: TP absent in DL4J) — this is
the idiomatic TPU extension for models whose weights exceed one chip: the
first dense of a pair is COLUMN-sharded (activations stay sharded, no
communication), the second is ROW-sharded and finishes with ONE ``psum``
over the model axis (Shoeybi et al. 2019). On a 2-D (data, model) mesh this
composes freely with the data-parallel trainer: batch sharded over "data",
weights over "model".

These are building blocks: ``tp_mlp_block`` is the fused two-layer shard_map
pattern. For tensor-parallel training of full networks (MultiLayerNetwork /
ComputationGraph / zoo models) use ``parallel.model_sharding.ShardedTrainer``,
which shards the network's own jitted step via GSPMD instead.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

MODEL_AXIS = "model"
DATA_AXIS = "data"


def dp_tp_mesh(data: int, model: int, devices=None) -> Mesh:
    """2-D (data, model) mesh over the first data*model devices."""
    devices = np.asarray(devices if devices is not None
                         else jax.devices()[:data * model])
    return Mesh(devices.reshape(data, model), (DATA_AXIS, MODEL_AXIS))


def tp_mlp_block(x, w1, b1, w2, b2, activation, *, axis: str = MODEL_AXIS):
    """Column-parallel dense -> activation -> row-parallel dense -> psum.

    Call INSIDE shard_map with w1 sharded on its output axis and w2 on its
    input axis (specs from ``tp_specs``). x is replicated across ``axis``;
    the return is too. Exactly one collective (the psum) per block."""
    h = activation(jnp.einsum("bi,ih->bh", x, w1) + b1)  # local columns
    partial_out = jnp.einsum("bh,ho->bo", h, w2)          # local rows
    out = lax.psum(partial_out, axis)
    return out + b2  # b2 replicated; added after the reduction


def tp_specs():
    """PartitionSpecs for (x, w1, b1, w2, b2) of tp_mlp_block."""
    return (P(DATA_AXIS, None), P(None, MODEL_AXIS), P(MODEL_AXIS),
            P(MODEL_AXIS, None), P(None))


def tp_mlp_train_step(mesh: Mesh, activation, loss_fn, lr: float = 0.1):
    """A complete dp x tp sharded training step factory for a 2-layer MLP —
    the minimal end-to-end pattern combining data parallelism (batch sharded
    over 'data', gradients psum-averaged) with tensor parallelism (weights
    sharded over 'model'). Returns a jitted fn
    ``step(params, x, y) -> (params, loss)``."""

    x_spec, w1_spec, b1_spec, w2_spec, b2_spec = tp_specs()
    param_specs = {"w1": w1_spec, "b1": b1_spec, "w2": w2_spec, "b2": b2_spec}

    def local_step(params, x, y):
        def local_loss(p):
            out = tp_mlp_block(x, p["w1"], p["b1"], p["w2"], p["b2"],
                               activation)
            return jnp.mean(loss_fn(out, y))

        loss, grads = jax.value_and_grad(local_loss)(params)
        # The loss is computed (identically) on EVERY model-axis device, so
        # every leaf's cotangent arrives n_model-times over-counted (the
        # VMA-tracking shard_map hands each model-axis copy's cotangent
        # to the psum transpose) — scale by 1/n_model to recover the
        # gradient of the single logical loss
        n_model = lax.psum(1, MODEL_AXIS)
        grads = {k: g / n_model for k, g in grads.items()}
        # DP reduction: every leaf is averaged over the data axis. TP needs
        # no further gradient collective: each device owns its weight shard.
        grads = lax.pmean(grads, DATA_AXIS)
        # replicated leaves (b2) carry identical grads across model now
        loss = lax.pmean(lax.pmean(loss, DATA_AXIS), MODEL_AXIS)
        new_params = jax.tree_util.tree_map(lambda p, g: p - lr * g, params,
                                            grads)
        return new_params, loss

    # check_vma stays ON: with it off, the transpose of the forward psum is
    # mis-typed (replicated cotangents get re-summed) and sharded-weight
    # gradients come out wrong — VMA tracking inserts the correct
    # pbroadcast/psum pairing for the backward pass.
    fn = jax.shard_map(
        local_step, mesh=mesh,
        in_specs=(param_specs, x_spec, P(DATA_AXIS, None)),
        out_specs=(param_specs, P()))
    return jax.jit(fn)
