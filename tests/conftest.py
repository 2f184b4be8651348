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
import signal

# Keras 3 picks the TensorFlow backend unless told otherwise, and importing
# TensorFlow costs ~13 s in every worker that touches Keras (test_modelimport,
# test_quantize). The import tests only need Keras to write an h5 file and to
# predict a reference; the jax backend does both. Test configuration only:
# the package never reads this variable.
os.environ.setdefault("KERAS_BACKEND", "jax")

# The suite is compile-bound (62% of its seconds are XLA CPU compiles of
# programs that then run for milliseconds), so ask jax for what its own
# documentation offers for that case: skip most XLA optimisation passes.
# Measured at -30% CPU seconds per file with every assertion unchanged. Set
# through the environment so that the subprocesses tests spawn (examples,
# producers, hosts) compile the same way as the process that checks them.
os.environ.setdefault("JAX_DISABLE_MOST_OPTIMIZATIONS", "1")

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


# Tier-1 budget, measured for PR 26 (2026-09-30). Command: the driver's,
# `pytest tests/ -q -m 'not slow' -p xdist -n 6 --dist loadfile` under
# `timeout 1470`, with --durations=0 added. Machine: the 8-core CPU sandbox;
# the driver's machine reads about seven times these seconds.
#   parent (ee8b776): 848 passed 2 failed, wall 240 s, summed 1,218 s,
#                     1,584 CPU s (user+sys); heaviest file 102 s, entry 21 s
#   this tree:        851 passed 0 failed, wall 136 s, summed 603 s,
#                     800 CPU s; with JAX_DISABLE_MOST_OPTIMIZATIONS=0
#                     exported: 851 passed, wall 170 s, summed 820 s
# Ten heaviest files, summed setup+call+teardown seconds on this tree:
#   test_zoo 43, test_gradients 39, test_examples 38, test_graph 28,
#   test_generation 27, test_handoff 24, test_disagg 24, test_modelimport 23,
#   test_quantize 21, test_sequence_tensor_parallel 21
# Entries over 5 s (12, none over 10): test_zoo cyclic_lm fixture 9.1 and
#   FaceNet 7.1; examples elastic_training 8.9 and long_context_attention
#   8.0; test_quantize keras knob 7.5; test_ml_and_guesser Keras server 7.1;
#   test_scaleout facade evaluate 7.0; the three fleet drills (handoff 6.5,
#   fleet routes 5.8, mesh groups 5.7); moe gradcheck 5.5; disagg dark
#   tier 5.1.
# Added by PR 28 (2026-10-01), test_granite_hybrid.py, 35 entries, 62 s summed
#   in one process: three over 5 s, each one GenerationServer over a
#   three-layer hybrid model whose prefill buckets and decode program compile
#   anew: served_through_slots 8.8 (seven requests, three buckets),
#   preempted_request_resumes 7.5 (two servers), slot_not_reset 6.8.
# Added by PR 29 (2026-10-01): test_granite_hybrid.py's `rounds_served`
#   fixture 6.9 (one server beside four `sample_generate` programs, one per
#   prompt length and sampling setting, which are the serial references).
# Added by PR 32 (2026-10-02), test_falcon_h1.py, 25 entries, 45 s summed in
#   one process: one over 5 s, served_through_slots 6.9 (one GenerationServer
#   over a two-block parallel-hybrid model: three prefill buckets and the
#   decode program compile anew, then seven reference passes); the two
#   served-path faults 4.4 and 2.7 (one server each, programs traced anew
#   with the fault underneath).
# Added by PR 34 (2026-10-03), test_deepseek_v2.py, 27 entries, 50 s summed in
#   one process: two over 5 s, the stale-page fault 5.3 (one GenerationServer
#   over a three-block latent-attention model: three prefill buckets, the
#   decode program and five reference passes, all traced anew with the fault
#   underneath) and the share test 5.1 (four expert layers and eight
#   reference layers, each compiled once); the `served` fixture 3.5.
# Added by PR 38 (2026-10-05), test_trinity.py, 38 entries, about 70 s summed
#   in one process: three over 5 s, the `served` fixture 6.9 (one
#   GenerationServer over a three-block window-and-full model: four prefill
#   buckets, the decode program over two page classes and five reference
#   passes of 256 tokens), the share test 6.3 (eight expert layers, each
#   compiled once) and the page-freed-early fault 5.5 (a server traced anew
#   with the fault underneath); the streamed faults 3.5-5.4 each (the whole
#   net traced anew, 150 tokens whole and 80 streamed).
# Added by PR 39 (2026-10-15): test_paged_attention.py's
#   TestPrefillReadCounter::test_rows_booked_by_path_and_the_tokens_are_the_same
#   5.5 (two GenerationServers over a two-block window-and-full model with
#   128-lane heads, the second with the blocked read interpreted: both
#   prefill programs and the decode program compile anew);
#   test_tpu_lowering.py's test_grouped_prefill_reads_blocked_in_the_compiled
#   _program 5.3 (one gen_prefill at a 1,024 bucket compiled for the
#   described v5e).
# Added 2026-10-15, test_olmo_hybrid.py, 33 entries, about 40 s
#   summed in one process: one over 5 s, the preempted-request test 5.1 (two
#   GenerationServers over a four-block linear-and-full model, three requests
#   of 80 tokens each, one server preempting under a pool of 20 pages: the
#   prefill buckets and the decode program compile anew in each); the served
#   fixture 3.5, the layer's whole-sequence case 3.2 (the reference's scan
#   and the chunked form compiled once each).
# Added 2026-10-17, the Mosaic paged read at grouped decode steps: three
#   over 5 s. test_falcon_h1.py's served_through_the_paged_kernel 11.7 (two
#   GenerationServers over the two-block parallel-hybrid model, the first
#   with the kernel interpreted in its decode program: prefill buckets and
#   the decode program compile anew in each); test_trinity.py's
#   served_through_the_paged_kernel 7.4 (one server over the three-block
#   window-and-full model, the kernel interpreted, beside the module's
#   XLA-served fixture); test_paged_attention.py's TestBackendByStructure
#   each_net_is_read_as_its_layers_say 5.0 (three nets built, a latent one
#   among them, and a server constructed over each; nothing compiles).
# Added 2026-10-18, test_qwen3_next.py, 19 entries, about 54 s summed in one
#   process: three over 5 s. a_quarter_of_each_head_turns_on_every_forward
#   8.4 (one gated attention layer fed whole, streamed and through both
#   paged reads, the Pallas kernel interpreted: each chunk shape compiles);
#   the_forward_is_the_reference[None] 7.4 (the four-block net's whole pass,
#   two streamed chunks and the one-token step compile anew, beside the
#   reference's pass with its expert loop); the grouped-key rule's
#   whole_sequence case 5.9 (the reference's scan and the chunked form
#   compiled once each). The `served` fixture 4.4 (one GenerationServer:
#   prefill buckets and the decode program).
# Rule for new tests: nothing over 5 s on the sandbox enters tier-1 without a
# line in this table. Before shrinking sizes, look for eager jax code: a
# forward, a grad or a shard_map called outside jax.jit compiles every
# primitive on its own (the Barnes-Hut ladder tests fell from 33 s to 2.5 s
# by jitting the call, at the same body count).
def pytest_configure(config):
    for name, selects in (
            ("slow", "outside tier-1: long-running, or builds a real fleet, "
                     "spawns host processes or trains a sharded index"),
            ("health", "numerical-health guard and NaN-injection tests"),
            ("serving", "serving-path resilience: deadlines, admission "
                        "control, breaker, chaos"),
            ("generation", "continuous-batching GenerationServer tests"),
            ("fleet", "ReplicaFleet routing, failover, restart, hedging"),
            ("metrics", "metrics registry, exposition, autoscaler, load "
                        "harness"),
            ("allow_step_recompiles", "opt out of the per-test train-step "
                                      "recompile-count guard"),
            ("allow_output_recompiles", "opt out of the per-test inference "
                                        "recompile-count guard"),
            ("analysis", "graftcheck static-analyzer tests and its "
                         "zero-unbaselined-findings gate"),
            ("quant", "int8 weight and KV-cache quantization tests"),
            ("handoff", "KV-snapshot export, adoption and migration tests"),
            ("disagg", "disaggregated prefill/decode tier tests"),
            ("runtime", "ServingLoop / LoopSupervisor lifecycle tests"),
            ("knn", "EmbeddingIndex stores, query coalescer, /knn tier"),
            ("mesh", "tensor-parallel mesh-sharded decode tests"),
            ("pallas", "Pallas-kernel parity and TPU lowering tests"),
            ("federation", "cross-host fleet federation tests"),
            ("rag", "retrieval-augmented serving tests")):
        config.addinivalue_line("markers", f"{name}: {selects}")


# One stuck test must cost one test, not the run's 1,470 s. After the PR 26
# shrinks no tier-1 entry is over 10 s on the sandbox and the driver's
# machine is about seven times slower, so the limit sits near 180 s. Tests
# that wait on subprocesses keep their own timeout= below it; `slow` tests
# (the chip_smoke rehearsal runs for minutes) are not limited.
TEST_LIMIT_S = 180


@pytest.fixture(autouse=True)
def _per_test_limit(request):
    if request.node.get_closest_marker("slow"):
        yield
        return

    def on_alarm(signum, frame):
        pytest.fail(f"{request.node.nodeid} ran past the per-test limit of "
                    f"{TEST_LIMIT_S} s")

    previous = signal.signal(signal.SIGALRM, on_alarm)
    signal.setitimer(signal.ITIMER_REAL, TEST_LIMIT_S)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, previous)


@pytest.fixture
def full_xla_optimizations():
    """Compile this test's programs with XLA's default optimisation level:
    for a tolerance that was set against optimised code and is tighter than
    two different programs owe each other (see the environment note at the
    top of this file)."""
    previous = jax.config.read("jax_disable_most_optimizations")
    jax.config.update("jax_disable_most_optimizations", False)
    try:
        yield
    finally:
        jax.config.update("jax_disable_most_optimizations", previous)


@pytest.fixture(scope="module")
def lm():
    """The tiny TransformerLM the serving test files share (one per module:
    its program caches must not leak between files). A file that needs
    another length or head count overrides this with ``tiny_lm(...)``."""
    from tests.serving_helpers import tiny_lm

    return tiny_lm()


@pytest.fixture(scope="module")
def greedy_refs(lm):
    """Mixed-length request set + serial greedy references (computed while
    no server is live, so the reference scan programs compile without a
    concurrent cache writer)."""
    import numpy as np

    from deeplearning4j_tpu.models.zoo import greedy_generate
    from tests.serving_helpers import V

    rs = np.random.RandomState(4)
    shapes = [(3, 6), (5, 4), (9, 5), (3, 5), (5, 6), (9, 4)]
    reqs = [(rs.randint(0, V, p), s) for p, s in shapes]
    refs = [greedy_generate(lm, p[None], s, V)[0] for p, s in reqs]
    return reqs, refs


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
