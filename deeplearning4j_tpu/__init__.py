"""tpu-dl4j: a TPU-native deep-learning framework with DeepLearning4j's capabilities.

A ground-up JAX/XLA/Pallas re-design of the DL4J framework layer (reference:
dawncc/deeplearning4j). Where DL4J hand-writes per-layer forward/backward over ND4J
kernels, this framework expresses layers as pure functions over pytrees, differentiates
with `jax.grad`, compiles whole training steps with `jax.jit`, and scales out with a
single sharded step over a `jax.sharding.Mesh` (replacing ParallelWrapper thread
averaging, Spark parameter averaging, and the Aeron parameter server).

Package map (mirrors the reference's module inventory, SURVEY.md section 2):

- ``ops``       -- tensor op facade (activations, losses, conv, rng) over jax.numpy/lax
- ``nn``        -- config system, layers, MultiLayerNetwork, ComputationGraph, updaters
- ``optimize``  -- listeners
- ``evaluation`` -- Evaluation / RegressionEvaluation / ROC
- ``datasets``  -- DataSet / iterators / built-in datasets
- ``utils``     -- serialization (ModelSerializer-style zips), pytree helpers
"""

__version__ = "0.1.0"


def enable_compile_cache():
    """Turn on jax's persistent XLA compilation cache and return the
    directory in use.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set nothing is set in code —
    jax reads the variable itself, and the launcher that exported it
    owns the location. Otherwise the cache lives at ``<checkout>/
    .xla_cache`` (git-ignored): the directory is part of the cache key,
    so it must not move between runs. Called by launchers
    (``chip_smoke.py``, ``benchmarks/run.py``); importing the package never
    touches the jax config."""
    import os

    directory = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if directory:
        return directory
    import jax

    directory = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".xla_cache")
    jax.config.update("jax_compilation_cache_dir", directory)
    return directory
