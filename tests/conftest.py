"""Test config: run everything on a virtual 8-device CPU mesh.

Mirrors the reference's backend-swap test strategy (Maven profile test-nd4j-native
vs test-nd4j-cuda, pom.xml:313-356): the same suite runs clusterless on CPU; the
chip is checked separately by ``chip_smoke.py``. Distributed tests see 8 XLA host
devices (the local[N] / BaseSparkTest equivalent). The platform is pinned through
jax.config before any backend initialises, so the suite stays on CPU even where
``JAX_PLATFORMS`` is not exported.
"""

# NOTE: do NOT enable jax's persistent compilation cache here. The suite
# is compile-dominated and the cache looks like a free 1.5x, but with
# this jaxlib the CPU executable DESERIALIZATION path is unsound: two
# full-suite runs with the cache enabled segfaulted at random points
# (one mid-trace "Garbage-collecting", one on a plain Python line — the
# signature of delayed heap corruption), while cache-less runs of the
# identical tree are stable.

import os

import jax
import pytest

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
jax.config.update("jax_enable_x64", True)

# A jitted train step compiled per minibatch (instead of per shape bucket)
# turns every fit loop into a compile loop. The fused/unfused step builders
# both route through Model._get_step, so counting cache misses per network
# instance catches any reintroduced per-batch recompile: a leak compiles
# once per iteration and blows well past this bound, while legitimate tests
# (a few shape buckets + mask/carry combos) stay under it.
MAX_STEP_COMPILES_PER_NET = 8


@pytest.fixture(autouse=True)
def _step_recompile_guard(request):
    if request.node.get_closest_marker("allow_step_recompiles"):
        yield
        return
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    counts: dict = {}
    patched = []

    def instrument(cls):
        orig = cls._get_step

        def counted(self, key, _orig=orig):
            if key not in self._step_cache:
                counts[id(self)] = counts.get(id(self), 0) + 1
            return _orig(self, key)

        cls._get_step = counted
        patched.append((cls, orig))

    instrument(MultiLayerNetwork)
    instrument(ComputationGraph)
    try:
        yield
    finally:
        for cls, orig in patched:
            cls._get_step = orig
    worst = max(counts.values(), default=0)
    assert worst <= MAX_STEP_COMPILES_PER_NET, (
        f"a single network compiled {worst} distinct train-step programs in "
        f"one test (cap {MAX_STEP_COMPILES_PER_NET}) — a jitted step is "
        "being allocated per iteration instead of per shape bucket; use the "
        "bucketed fused-fit path or mark the test @pytest.mark."
        "allow_step_recompiles if the shapes are genuinely diverse")


# Same idea for the inference side: output()/evaluate() route through
# Model._get_output with shape-bucketed keys (batch padded to a bucket), so
# a stream of arbitrary batch sizes compiles O(log max_batch) forward
# programs plus a fused-eval block and its K=1 tail variant. A per-batch
# leak compiles once per output() call and blows past this cap.
MAX_OUTPUT_COMPILES_PER_NET = 10


@pytest.fixture(autouse=True)
def _output_recompile_guard(request):
    if request.node.get_closest_marker("allow_output_recompiles"):
        yield
        return
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    counts: dict = {}
    patched = []

    def instrument(cls):
        orig = cls._get_output

        def counted(self, key, build, _orig=orig):
            if key not in self._output_cache:
                counts[id(self)] = counts.get(id(self), 0) + 1
            return _orig(self, key, build)

        cls._get_output = counted
        patched.append((cls, orig))

    instrument(MultiLayerNetwork)
    instrument(ComputationGraph)
    try:
        yield
    finally:
        for cls, orig in patched:
            cls._get_output = orig
    worst = max(counts.values(), default=0)
    assert worst <= MAX_OUTPUT_COMPILES_PER_NET, (
        f"a single network compiled {worst} distinct inference programs in "
        f"one test (cap {MAX_OUTPUT_COMPILES_PER_NET}) — output()/evaluate() "
        "is compiling per batch instead of per shape bucket; route through "
        "the bucketed cache or mark the test @pytest.mark."
        "allow_output_recompiles if the shapes are genuinely diverse")


# Tier-1 duration budget (pinned 2026-08-07, PR 18): the `-m 'not slow'`
# suite measured 938s against its own 870s timeout cap on the single-core
# CI box (845 passed, `--durations=25`). To restore >=5% headroom
# (<=826s), the heaviest compile-bound entries moved to `slow`, chosen so
# every code path keeps a cheaper tier-1 sibling:
#   test_zoo big-model params InceptionResNetV1 (23.5s), GoogLeNet
#     (20.6s), ResNet50 (15.2s) — AlexNet/VGG16/VGG19/FaceNet still run;
#   test_zoo small-model param SimpleCNN (17.7s) — LeNet + LSTM still run;
#   test_examples lenet_mesh_dataparallel.py (19.9s),
#     transformer_text_generation.py (12.8s), keras_residual_import.py
#     (11.4s) — each subsystem has a dedicated tier-1 module.
# ~121s moved -> ~818s estimated. Every NEW test that builds a fleet or
# trains an index must be marked slow (see the federation/rag marker
# descriptions below); re-run with --durations=25 before adding anything
# >5s to tier-1.
def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running test")
    config.addinivalue_line(
        "markers",
        "health: numerical-health guard / NaN-injection tests (CPU-fast; "
        "runs in tier-1, deliberately NOT in the slow set)")
    config.addinivalue_line(
        "markers",
        "serving: serving-path resilience tests (deadlines, admission "
        "control, breaker, chaos — CPU-fast; runs in tier-1, deliberately "
        "NOT in the slow set)")
    config.addinivalue_line(
        "markers",
        "generation: continuous-batching generation serving tests "
        "(slot-pooled KV cache, prefill buckets, decode-step recompile "
        "guard — CPU-fast; runs in tier-1, deliberately NOT in the slow "
        "set)")
    config.addinivalue_line(
        "markers",
        "fleet: replica-fleet serving tests (health routing, failover "
        "redispatch, supervised restart, hedging, chaos soak — CPU-fast; "
        "runs in tier-1, deliberately NOT in the slow set)")
    config.addinivalue_line(
        "markers",
        "metrics: observability tests (metrics registry, Prometheus "
        "exposition, autoscaler, load harness — CPU-fast; runs in "
        "tier-1, deliberately NOT in the slow set)")
    config.addinivalue_line(
        "markers",
        "allow_step_recompiles: opt out of the per-test train-step "
        "recompile-count guard")
    config.addinivalue_line(
        "markers",
        "allow_output_recompiles: opt out of the per-test inference "
        "recompile-count guard")
    config.addinivalue_line(
        "markers",
        "analysis: graftcheck static-analyzer tests (AST rules, baseline "
        "gate, lock-order instrumentation — CPU-fast; the zero-unbaselined"
        "-findings gate runs in tier-1, deliberately NOT in the slow set)")
    config.addinivalue_line(
        "markers",
        "quant: int8 quantization tests (per-channel weight quant "
        "round-trip and eval parity, int8 paged/streaming KV-cache greedy "
        "agreement, quantization-off bit-exactness — CPU-fast; runs in "
        "tier-1, deliberately NOT in the slow set)")
    config.addinivalue_line(
        "markers",
        "handoff: KV-snapshot/migration serving tests (snapshot "
        "round-trip bit-exactness, corrupted-checksum fallback, "
        "mid-stream failover resume, drain-migrate — CPU-fast; runs in "
        "tier-1, deliberately NOT in the slow set)")
    config.addinivalue_line(
        "markers",
        "disagg: disaggregated prefill/decode tier tests (prefill-export "
        "-> decode-adopt bit-exactness, mid-handoff kills on each side, "
        "corrupt/drop/truncate transfer fallback, decode-tier-dark "
        "degraded mode + recovery — CPU-fast; runs in tier-1, "
        "deliberately NOT in the slow set)")
    config.addinivalue_line(
        "markers",
        "runtime: serving-runtime lifecycle tests (ServingLoop state "
        "machine, LoopSupervisor crash recovery, shutdown-phase chaos, "
        "idempotent drain/close across all servers — CPU-fast; runs in "
        "tier-1, deliberately NOT in the slow set)")
    config.addinivalue_line(
        "markers",
        "knn: retrieval serving tests (EmbeddingIndex exact/int8/IVF "
        "stores, query coalescer parity, recall gates, hardened /knn "
        "HTTP tier — CPU-fast; runs in tier-1, deliberately NOT in the "
        "slow set)")
    config.addinivalue_line(
        "markers",
        "mesh: tensor-parallel mesh-sharded decode tests (head-sharded "
        "page pool over a model mesh, tp>1 greedy/sampled parity, "
        "cross-TP snapshot handoff, replica-group fleets — CPU-fast on "
        "8 forced virtual devices; runs in tier-1, deliberately NOT in "
        "the slow set)")
    config.addinivalue_line(
        "markers",
        "pallas: Pallas-kernel parity tests (paged-attention helper seam "
        "XLA-vs-kernel bit-exactness in interpret mode, backend "
        "selection, backend-tagged program caches, cross-platform TPU "
        "lowering of every kernel variant — CPU-fast; runs in tier-1, "
        "deliberately NOT in the slow set)")
    config.addinivalue_line(
        "markers",
        "federation: cross-host fleet federation tests (framed host RPC, "
        "heartbeat gossip suspect detection, whole-process SIGKILL with "
        "bit-exact cross-host snapshot adoption, partition heal, "
        "degraded mode). The wire/chaos/shed tests are CPU-fast and run "
        "in tier-1; the drills that build real fleets or spawn host "
        "processes are ALSO marked slow — tier-1 already runs within "
        "~2% of its own timeout cap, so per-drill fleet builds cannot "
        "ride in it (run them with -m federation)")
    config.addinivalue_line(
        "markers",
        "rag: retrieval-augmented serving tests (two-tier knn->generate "
        "RagPipeline, canonical passage-prefix assembly, prefix-cache "
        "dedupe across hot documents, deadline propagation across the "
        "tier boundary, /rag HTTP route). The unit/parity tests are "
        "CPU-fast and run in tier-1; the drills that build fleets or "
        "train sharded k-means are ALSO marked slow — tier-1 runs "
        "within ~2% of its own timeout cap (run them with -m rag)")


@pytest.fixture(autouse=True)
def _lock_order_debug(request):
    """Opt-in runtime lock-order assertion: with DL4J_TPU_LOCK_DEBUG=1,
    tests under the serving/generation markers run with the serving
    locks wrapped in rank-checked OrderedLocks (analysis/instrument.py),
    so any out-of-order acquisition fails the test instead of deadlocking
    in production."""
    if os.environ.get("DL4J_TPU_LOCK_DEBUG") != "1" or not (
            request.node.get_closest_marker("serving")
            or request.node.get_closest_marker("generation")
            or request.node.get_closest_marker("fleet")
            or request.node.get_closest_marker("metrics")
            or request.node.get_closest_marker("quant")
            or request.node.get_closest_marker("handoff")
            or request.node.get_closest_marker("disagg")
            or request.node.get_closest_marker("runtime")
            or request.node.get_closest_marker("knn")
            or request.node.get_closest_marker("pallas")
            or request.node.get_closest_marker("mesh")
            or request.node.get_closest_marker("federation")
            or request.node.get_closest_marker("rag")):
        yield
        return
    from deeplearning4j_tpu.analysis import instrument
    instrument.install()
    try:
        yield
    finally:
        instrument.uninstall()
