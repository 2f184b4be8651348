"""Int8 quantization tests (ISSUE-10 acceptance surface).

Two quantized execution paths, both default-OFF:
- per-output-channel int8 WEIGHTS with the dequant fused into each
  matmul/conv (optimize/quantize.py + layer ``QUANT_PARAMS`` opt-ins),
  gated on eval parity (``confusion_delta``);
- int8 paged/streaming KV-CACHE with per-token-per-head scales
  (``kv_dtype="int8"`` on GenerationServer / ``init_paged_carry``),
  gated on greedy agreement vs the f32 reference.

Everything with quantization off must stay BIT-exact — asserted here
against the same serial references the f32 serving tests pin.
"""

import time

import numpy as np
import pytest

from deeplearning4j_tpu.models.zoo import TransformerLM, greedy_generate
from deeplearning4j_tpu.optimize.quantize import (confusion_delta,
                                                  dequantize_array,
                                                  greedy_agreement,
                                                  quantize_array,
                                                  quantize_net,
                                                  quantize_params)
from deeplearning4j_tpu.parallel.generation import GenerationServer
from tests.serving_helpers import V, serving


@pytest.mark.quant
class TestWeightQuantization:
    def test_roundtrip_error_bound(self):
        """q * scale reconstructs within half a quantization step per
        output channel; all-zero channels reconstruct exactly."""
        rs = np.random.RandomState(0)
        for shape in [(7, 5), (3, 3, 2, 4), (16, 16)]:
            w = (rs.randn(*shape) * rs.uniform(0.01, 10)).astype(np.float32)
            w[..., -1] = 0.0  # an all-zero output channel
            q, scale = quantize_array(w)
            q, scale = np.asarray(q), np.asarray(scale)
            assert q.dtype == np.int8 and scale.dtype == np.float32
            assert scale.shape == (shape[-1],)
            rt = dequantize_array(q, scale)
            step = scale.reshape((1,) * (w.ndim - 1) + (-1,))
            assert np.all(np.abs(rt - w) <= 0.5001 * np.maximum(step, 1e-12))
            np.testing.assert_array_equal(rt[..., -1], 0.0)

    def test_quantize_params_targets_and_scales(self, lm):
        """Only QUANT_PARAMS weights quantize: attention projections and
        dense W go int8 with f32 ``*_scale`` siblings; biases, norms and
        embeddings are untouched — and the source net's params are not
        mutated."""
        before = {k: {p: np.asarray(a) for p, a in v.items()}
                  for k, v in lm.params.items() if isinstance(v, dict)}
        qparams, scales = quantize_params(lm)
        assert scales  # at least the attention block quantized
        n_int8 = 0
        for key, lp in qparams.items():
            if not isinstance(lp, dict):
                continue
            for pname, arr in lp.items():
                if pname.endswith("_scale"):
                    continue
                if np.asarray(arr).dtype == np.int8:
                    n_int8 += 1
                    assert pname + "_scale" in lp
                    assert pname in scales[key]
                elif pname in ("b", "gamma", "beta"):
                    np.testing.assert_array_equal(np.asarray(arr),
                                                  before[key][pname])
        assert n_int8 == sum(len(v) for v in scales.values()) > 0
        # source untouched (no int8 leaked into the original tree)
        for key, lp in lm.params.items():
            if isinstance(lp, dict):
                for pname, arr in lp.items():
                    assert not pname.endswith("_scale")
                    assert np.asarray(arr).dtype != np.int8

    def test_bad_mode_rejected(self, lm):
        with pytest.raises(ValueError, match="int8"):
            quantize_net(lm, "int4")

    def test_lenet_eval_parity(self):
        """LeNet via the zoo ``quantize="int8"`` knob: int8 weights keep
        classification decisions — confusion delta vs f32 stays inside
        the gate on a synthetic eval set."""
        from deeplearning4j_tpu.datasets.dataset import DataSet
        from deeplearning4j_tpu.models.zoo import LeNet

        net = LeNet(num_labels=10, seed=1).init()
        qnet = LeNet(num_labels=10, seed=1, quantize="int8").init()
        rs = np.random.RandomState(2)
        x = rs.randn(64, 28, 28, 1).astype(np.float32)
        y = np.eye(10, dtype=np.float32)[rs.randint(0, 10, 64)]
        ev_f = net.evaluate(DataSet(x, y))
        ev_q = qnet.evaluate(DataSet(x, y))
        assert confusion_delta(ev_f, ev_q) <= 0.05
        # and the raw outputs are numerically close, not just argmax-equal
        of = np.asarray(net.output(x))
        oq = np.asarray(qnet.output(x))
        np.testing.assert_allclose(of, oq, atol=5e-2)

    def test_keras_import_quantize_knob(self, tmp_path):
        """An imported-then-quantized Keras model serves through the same
        fused-dequant path: eval parity vs the f32 import."""
        keras = pytest.importorskip("keras")
        from keras import layers

        from deeplearning4j_tpu.datasets.dataset import DataSet
        from deeplearning4j_tpu.modelimport import \
            import_keras_sequential_model_and_weights

        m = keras.Sequential([
            keras.Input((6,)),
            layers.Dense(12, activation="relu"),
            layers.Dense(3, activation="softmax"),
        ])
        path = str(tmp_path / "mlp.h5")
        m.save(path)
        net = import_keras_sequential_model_and_weights(path)
        qnet = import_keras_sequential_model_and_weights(path,
                                                         quantize="int8")
        rs = np.random.RandomState(3)
        x = rs.randn(48, 6).astype(np.float32)
        y = np.eye(3, dtype=np.float32)[rs.randint(0, 3, 48)]
        assert confusion_delta(net.evaluate(DataSet(x, y)),
                               qnet.evaluate(DataSet(x, y))) <= 0.05

    def test_parallel_inference_int8_and_source_untouched(self, lm):
        """ParallelInference(quantize="int8") serves quantized weights;
        the caller's net keeps serving bit-exact f32."""
        from deeplearning4j_tpu.parallel.inference import ParallelInference

        rs = np.random.RandomState(5)
        ids = rs.randint(0, V, (4, 8))
        import jax
        x = np.asarray(jax.nn.one_hot(ids, V, dtype=np.float32))
        ref = np.asarray(lm.output(x))
        with ParallelInference(lm, workers=2, quantize="int8") as inf:
            got = np.asarray(inf.output(x))
        assert got.shape == ref.shape
        assert (got.argmax(-1) == ref.argmax(-1)).mean() >= 0.9
        # f32 source still bit-exact after the quantized server existed
        np.testing.assert_array_equal(np.asarray(lm.output(x)), ref)


@pytest.mark.quant
class TestInt8KVCache:
    def test_bad_kv_dtype_rejected(self, lm):
        with pytest.raises(ValueError, match="kv_dtype"):
            GenerationServer(lm, V, slots=2, kv_dtype="fp8")

    def test_greedy_agreement_and_capacity(self, lm, greedy_refs):
        """Mixed-length concurrent requests through an int8 pool agree
        with the serial f32 greedy references, and the per-token KV
        footprint shrinks >= 1.8x vs the f32 pool at identical config."""
        reqs, refs = greedy_refs
        with serving(lm, V, slots=3, kv_dtype="int8") as srv:
            futs = [srv.submit(p, s) for p, s in reqs]
            outs = [f.result(timeout=120) for f in futs]
            st_q = srv.stats()
        for got, ref in zip(outs, refs):
            assert greedy_agreement(got, ref) >= 0.95
        assert st_q["completed"] == len(reqs) and st_q["failed"] == 0
        assert st_q["pages"]["kv_cache_dtype"] == "int8"
        with serving(lm, V, slots=3) as srv:
            st_f = srv.stats()
        assert st_f["pages"]["kv_cache_dtype"] == "float32"
        ratio = st_f["pages"]["bytes_per_token"] \
            / st_q["pages"]["bytes_per_token"]
        assert ratio >= 1.8, f"int8 KV shrinks only {ratio:.2f}x"

    def test_f32_default_stays_bit_exact(self, lm, greedy_refs):
        """Quantization off = the seed behavior, bit for bit."""
        reqs, refs = greedy_refs
        with serving(lm, V, slots=3) as srv:
            outs = [srv.submit(p, s).result(timeout=120) for p, s in reqs]
        for got, ref in zip(outs, refs):
            np.testing.assert_array_equal(got, ref)

    def test_cow_preserves_scales(self, lm):
        """Prefix sharing + copy-on-write under int8: divergent
        continuations off a shared prefix page stay correct (the COW
        page copy must duplicate the scale planes with the values), and
        a second identical pass reproduces the first exactly."""
        rs = np.random.RandomState(7)
        base = rs.randint(0, V, 8)  # spans a full page -> shareable
        prompts = [np.concatenate([base, [t]]) for t in (1, 2, 3)]
        refs = [greedy_generate(lm, p[None], 5, V)[0] for p in prompts]
        with serving(lm, V, slots=3, kv_dtype="int8") as srv:
            outs = [srv.submit(p, 5).result(timeout=120) for p in prompts]
            outs2 = [srv.submit(p, 5).result(timeout=120) for p in prompts]
            st = srv.stats()
        for got, ref in zip(outs, refs):
            assert greedy_agreement(got, ref) >= 0.95
        for a, b in zip(outs, outs2):
            np.testing.assert_array_equal(a, b)
        assert st["pages"]["prefix_hits"] > 0
        assert st["pages"]["cow_copies"] > 0

    def test_no_recompile_on_churn_int8(self):
        """The zero-retrace property survives quantization: one decode
        program, one prefill bucket, one page copy — then occupancy
        churn over int8 pages adds ZERO compiled programs (the scale
        planes ride the same traced pool structure)."""
        net = TransformerLM(num_labels=V, max_length=16, d_model=8,
                            n_heads=2, n_blocks=1, seed=9).init()
        rs = np.random.RandomState(0)
        with serving(net, V, slots=3, min_prefill_bucket=4,
                     kv_dtype="int8") as srv:
            base = len(net._output_cache)
            warm = [srv.submit(rs.randint(0, V, 3), 5),
                    srv.submit(rs.randint(0, V, 7), 2)]
            for f in warm:
                f.result(timeout=120)
            warmed = len(net._output_cache)
            assert warmed - base == 3
            churn = [(4, 3), (2, 7), (6, 1), (8, 4), (3, 2), (5, 6)]
            futs = []
            for plen, mt in churn:
                futs.append(srv.submit(rs.randint(0, V, plen), mt))
                time.sleep(0.02)
            for f, (_plen, mt) in zip(futs, churn):
                assert f.result(timeout=120).shape == (mt,)
            assert len(net._output_cache) == warmed

    def test_pages_telemetry_gauges(self, lm):
        """The pool's quantization posture is on the Prometheus surface:
        occupancy/peak/geometry gauges render with live values."""
        from deeplearning4j_tpu.metrics.exposition import render_text
        from deeplearning4j_tpu.metrics.registry import MetricsRegistry

        reg = MetricsRegistry()
        with serving(lm, V, slots=2, kv_dtype="int8", registry=reg) as srv:
            st = srv.stats()
            text = render_text([({}, reg)])
        for name in ("generation_pages_total", "generation_pages_in_use",
                     "generation_pages_shared",
                     "generation_peak_resident_kv_bytes",
                     "generation_kv_bytes_per_token",
                     "generation_kv_cache_int8"):
            assert name in text, f"missing gauge {name}"
        assert f"generation_pages_total {st['pages']['pages_total']}" \
            in text
        assert "generation_kv_cache_int8 1" in text
        assert ("generation_kv_bytes_per_token "
                f"{st['pages']['bytes_per_token']}") in text

    def test_streaming_carry_int8(self, lm):
        """The dense (non-paged) streaming carry also supports int8:
        token-by-token decode through ``init_streaming_carry(...,
        kv_dtype="int8")`` tracks the full forward's decisions."""
        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu.models.zoo import lm_stream_forward

        rs = np.random.RandomState(11)
        ids = rs.randint(0, V, (2, 10))
        oh = np.asarray(jax.nn.one_hot(ids, V, dtype=jnp.float32))
        full = np.asarray(lm.output(oh))
        # jitted, as the server decodes: one program for all ten steps
        fwd = jax.jit(lm_stream_forward(lm))
        carry = {}
        for name, layer in lm._stream_layers():
            if hasattr(layer, "init_paged_carry"):
                carry[name] = layer.init_streaming_carry(
                    2, kv_dtype="int8")
            else:
                carry[name] = layer.init_streaming_carry(2)
        outs = []
        for t in range(ids.shape[1]):
            o, carry = fwd(lm.params, lm.state, oh[:, t:t + 1], carry)
            outs.append(np.asarray(o))
        stream = np.concatenate(outs, axis=1)
        agree = (stream.argmax(-1) == full.argmax(-1)).mean()
        assert agree >= 0.9


@pytest.mark.quant
class TestAccuracyGates:
    def test_confusion_delta(self):
        a = np.array([[5, 0], [0, 5]])
        assert confusion_delta(a, a.copy()) == 0.0
        b = np.array([[4, 1], [0, 5]])  # one example moved cells
        assert confusion_delta(a, b) == pytest.approx(0.1)
        with pytest.raises(ValueError, match="example counts"):
            confusion_delta(a, np.array([[9, 1], [0, 5]]))
        with pytest.raises(ValueError, match="shapes"):
            confusion_delta(a, np.zeros((3, 3), int))

    def test_greedy_agreement(self):
        assert greedy_agreement([1, 2, 3], [1, 2, 3]) == 1.0
        assert greedy_agreement([1, 2, 3], [1, 2, 4]) == pytest.approx(2 / 3)
        # missing tail counts as disagreement
        assert greedy_agreement([1, 2], [1, 2, 3]) == pytest.approx(2 / 3)
        assert greedy_agreement([], []) == 1.0
