"""Device-mesh helpers.

The reference pins model replicas to devices round-robin
(ParallelWrapper.java:148-245, trainer/DefaultTrainer.java device affinity);
here device placement is a jax.sharding.Mesh and XLA lays out the collectives
over ICI. One axis name is used throughout the data-parallel stack: ``data``.
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh

DATA_AXIS = "data"
MODEL_AXIS = "model"


class MeshGeometryError(ValueError):
    """Typed, loud mesh-geometry failure: the requested tensor-parallel
    degree does not divide the device count, or (raised at pool-build
    time by the serving layer) the model's head count. A plain
    ``ValueError`` subclass so legacy ``except ValueError`` callers keep
    working, but catchable on its own by fleet factories that want to
    fall back to a smaller tp."""


def model_mesh(tp: int, devices=None) -> Mesh:
    """1-D head-parallel (tensor-parallel) mesh over ``tp`` devices on
    the ``model`` axis — the mesh the sharded paged decode path runs
    over. Validation is loud and typed (``MeshGeometryError``): a silent
    fallback to fewer chips would change the page budget the server
    admitted against."""
    if devices is None:
        devices = jax.devices()
    if tp < 1:
        raise MeshGeometryError(f"tensor-parallel degree must be >= 1, got {tp}")
    if tp > len(devices):
        raise MeshGeometryError(
            f"tensor-parallel degree {tp} exceeds the {len(devices)} "
            "available devices")
    if len(devices) % tp != 0:
        raise MeshGeometryError(
            f"device count {len(devices)} is not divisible by tp={tp}: "
            "replica groups would overlap — pass an explicit device "
            "subset instead")
    return Mesh(np.array(devices[:tp]), (MODEL_AXIS,))


def data_mesh(num_devices: Optional[int] = None, devices=None) -> Mesh:
    """1-D data-parallel mesh over the first ``num_devices`` devices (default all)."""
    if devices is None:
        devices = jax.devices()
    if num_devices is not None:
        if num_devices > len(devices):
            raise ValueError(
                f"Requested {num_devices} devices but only {len(devices)} present")
        devices = devices[:num_devices]
    return Mesh(np.array(devices), (DATA_AXIS,))


def data_model_mesh(data: int, model: int, devices=None) -> Mesh:
    """2-D mesh: ``data`` x ``model`` axes (DP x TP)."""
    if devices is None:
        devices = jax.devices()
    n = data * model
    if n > len(devices):
        raise MeshGeometryError(
            f"Mesh {data}x{model} needs {n} devices, have {len(devices)}")
    return Mesh(np.array(devices[:n]).reshape(data, model), (DATA_AXIS, MODEL_AXIS))
