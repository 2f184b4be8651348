"""Round-4 real-chip measurement chain (run manually on the chip, one
process; results land in profiles/chip_session_results.json, a run-time
output — the round-4/5 record was deleted at PR 21).

1. word2vec A/B: segment_updates {True, False} x batch {8k, 16k, 32k, 64k}
   on the real chip — the sorted-segment path exists because XLA serializes
   duplicate-index scatter-adds on TPU; only chip numbers can pick the
   default.
2. flash-attention fwd and fwd+bwd timings.
3. ResNet50 bf16 jax.profiler trace -> profiles/resnet50_bf16_trace/.

Usage: python profiles/chip_session.py [w2v|attn|resnet|all]
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def w2v_ab():
    import bench
    from deeplearning4j_tpu.nlp import learning, sequence_vectors

    orig = learning.skipgram_corpus_epoch
    results = {}
    for seg in (True, False):
        sequence_vectors.skipgram_corpus_epoch = functools.partial(
            orig, segment_updates=seg)
        for batch in (8192, 16384, 32768, 65536):
            t0 = time.time()
            wps = _w2v_once(batch)
            results[f"seg={seg} batch={batch}"] = round(wps)
            print(f"# w2v seg={seg} batch={batch}: {wps:,.0f} words/s "
                  f"({time.time() - t0:.0f}s)", flush=True)
    sequence_vectors.skipgram_corpus_epoch = orig
    return results


def _w2v_once(batch_size: int, n_sentences: int = 50000):
    from deeplearning4j_tpu.nlp import CollectionSentenceIterator, Word2Vec

    rs = np.random.RandomState(3)
    vocab = [f"w{i}" for i in range(30000)]
    zipf = np.minimum(rs.zipf(1.3, size=n_sentences * 20) - 1,
                      len(vocab) - 1)
    sentences = [" ".join(vocab[z] for z in zipf[i * 20:(i + 1) * 20])
                 for i in range(n_sentences)]
    w2v = Word2Vec(layer_size=128, window=5, min_word_frequency=2,
                   negative=5, use_hierarchic_softmax=False, epochs=1,
                   batch_size=batch_size)
    w2v.build_vocab(sentences)
    w2v.reset_weights()
    w2v.fit(CollectionSentenceIterator(sentences))  # warmup/compile
    w2v.reset_weights()
    t0 = time.perf_counter()
    w2v.fit(CollectionSentenceIterator(sentences))
    import bench as _b
    _b._sync(w2v.syn0)
    return n_sentences * 20 / (time.perf_counter() - t0)


def attn():
    import bench

    s, f = bench.bench_attention()
    print(f"# attention T=4096 fwd: stock {s:.2f} ms, flash {f:.2f} ms "
          f"({s / f:.2f}x)", flush=True)
    sb, fb = bench.bench_attention_bwd()
    print(f"# attention T=2048 fwd+bwd: stock {sb:.2f} ms, flash {fb:.2f} "
          f"ms ({sb / fb:.2f}x)", flush=True)
    return {"fwd_stock_ms": s, "fwd_flash_ms": f,
            "bwd_stock_ms": sb, "bwd_flash_ms": fb}


def resnet_profile():
    import jax

    import bench

    out = {}
    with jax.profiler.trace("profiles/resnet50_bf16_trace"):
        med, windows = bench.bench_resnet50(compute_dtype="bfloat16")
    out["bf16_img_s"], out["bf16_windows"] = med, windows
    print(f"# resnet50 bf16 (traced): {med:.0f} img/s median of {windows}",
          flush=True)
    med, windows = bench.bench_resnet50()
    out["f32_img_s"], out["f32_windows"] = med, windows
    print(f"# resnet50 f32: {med:.0f} img/s median of {windows}", flush=True)
    return out


if __name__ == "__main__":
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    res = {}
    if which in ("w2v", "all"):
        res["w2v"] = w2v_ab()
    if which in ("attn", "all"):
        res["attn"] = attn()
    if which in ("resnet", "all"):
        res["resnet"] = resnet_profile()
    # read-merge-write so partial runs (w2v|attn|resnet) don't clobber
    # previously recorded sections
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "chip_session_results.json")
    merged = {}
    if os.path.exists(path):
        with open(path) as fh:
            merged = json.load(fh)
    merged.update(res)
    with open(path, "w") as fh:
        json.dump(merged, fh, indent=1)
    print(json.dumps(merged))
