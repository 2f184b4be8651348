#!/usr/bin/env python3
"""Standing proof that the main path starts on the chip.

``python chip_smoke.py`` drives, in ONE process on the default jax
backend, which must be a TPU:

- *trainer*: zoo ResNet50 (bf16 compute, 224x224x3, batch 128) through
  ``net.fit`` (fused K-step scan, health guard on) and ``net.evaluate``
  (fused eval), then a bf16 TransformerLM at T=512 so the flash-attention
  forward and backward kernels compile inside a real train step;
- *server*: a d_model=1024 TransformerLM behind ``GenerationServer``
  (32 slots, 16-token pages, Pallas paged attention), once with the f32
  page pool and once with the int8 pool, one request through
  ``KerasBackendServer`` ``POST /generate``;
- *kernels*: every Pallas variant those phases reach, shown Mosaic-compiled
  and compared with the stock XLA path on the same seeded inputs;
- *multichip* (only when jax reports >= 4 devices): ParallelWrapper
  data-parallel parity, ``GenerationServer(tp=4)``, and a four-replica
  ``ReplicaFleet`` with replica k's pool and weights on chip k.

Weights are random from a seed; every phase fails the run by raising.
Per-phase wall and compile seconds go on a ``summary:`` line; the last
line of stdout is ``{"ok": true, "device": {"platform", "kind",
"count"}}`` as jax reported it, with no other key. Without a TPU the
script exits non-zero before any phase and prints nothing to stdout.

``--rehearse`` runs the same phases at toy widths on whatever backend is
up (the CPU sandbox), kernels with ``interpret=True``; its last line is
the summary with ``"rehearsal": true`` instead of the result line. The
default invocation never degrades to it.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import urllib.request

import numpy as np

class CompileClock:
    """Seconds and count of XLA backend compiles (persistent-cache
    retrievals included), read off jax's own monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax.monitoring

        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.seconds += duration
            self.count += 1

    def mark(self):
        return self.seconds, self.count

    def since(self, mark):
        return self.seconds - mark[0], self.count - mark[1]


def check(cond, msg):
    if not cond:
        raise AssertionError(msg)


# ------------------------------------------------------------------ trainer
def phase_trainer(cfg, clock):
    import jax

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
    from deeplearning4j_tpu.models import ResNet50, TransformerLM
    from deeplearning4j_tpu.ops import pallas_attention
    from deeplearning4j_tpu.optimize.fused_fit import (_unroll_fused,
                                                       resolve_fused_steps)
    from deeplearning4j_tpu.optimize.listeners import TrainingListener

    class Losses(TrainingListener):
        def __init__(self):
            self.values = []

        def iteration_done(self, model, iteration):
            self.values.append(float(model.score_value))

    def fit_and_check(net, iterator, n_iter, label):
        k = resolve_fused_steps(net, None)
        if not cfg["rehearsal"]:
            check(k == 4 and not _unroll_fused(),
                  f"{label}: expected the rolled K=4 scan on the chip, "
                  f"got K={k} unroll={_unroll_fused()}")
        leaf0 = np.asarray(jax.tree_util.tree_leaves(net.params)[0])
        rec = Losses()
        net.set_listeners(rec)
        net.fit(iterator, epochs=1)
        check(net.iteration == n_iter,
              f"{label}: iteration {net.iteration} != {n_iter}")
        check(len(rec.values) == n_iter and np.all(np.isfinite(rec.values)),
              f"{label}: losses {rec.values}")
        leaf1 = np.asarray(jax.tree_util.tree_leaves(net.params)[0])
        check(np.all(np.isfinite(leaf1)) and not np.array_equal(leaf0, leaf1),
              f"{label}: parameters did not change")
        keys = list(net._step_cache)
        # key: ("fused", K, x shape, y shape, has im, has lm, guarded)
        check(len(keys) == 1 and keys[0][:2] == ("fused", k) and keys[0][-1],
              f"{label}: expected ONE guarded fused K={k} step program, "
              f"got {keys}")
        check(net._step_cache[keys[0]]._cache_size() == 1,
              f"{label}: the fused step retraced")
        print(f"  {label}: K={k} losses {rec.values[0]:.4f} -> "
              f"{rec.values[-1]:.4f}", flush=True)

    rs = np.random.RandomState(0)
    B, image, classes = cfg["rn_batch"], cfg["rn_image"], cfg["rn_classes"]
    n_iter = 8
    net = ResNet50(num_labels=classes, input_shape=(image, image, 3),
                   compute_dtype="bfloat16").init()
    n = n_iter * B
    x = rs.standard_normal((n, image, image, 3)).astype(np.float32)
    y = np.eye(classes, dtype=np.float32)[rs.randint(0, classes, n)]
    fit_and_check(net, ListDataSetIterator(DataSet(x, y), batch_size=B),
                  n_iter, "resnet50 fit")
    # 5 full batches + a ragged tail: one fused eval block plus its tail
    n_eval = 5 * B + B // 3
    ev = net.evaluate(ListDataSetIterator(
        DataSet(x[:n_eval], y[:n_eval]), batch_size=B))
    acc = ev.accuracy()
    check(int(ev.confusion.sum()) == n_eval,
          f"eval counted {int(ev.confusion.sum())} of {n_eval} examples")
    check(0.0 <= acc <= 1.0 and np.isfinite(ev.eval_loss),
          f"eval accuracy {acc} loss {ev.eval_loss}")
    print(f"  resnet50 evaluate: {n_eval} examples, accuracy {acc:.4f}, "
          f"loss {ev.eval_loss:.4f}", flush=True)
    del net, x, y

    T, V, LB = cfg["lm_T"], cfg["lm_vocab"], cfg["lm_batch"]
    lm = TransformerLM(num_labels=V, max_length=T, d_model=cfg["lm_d"],
                       n_heads=cfg["lm_heads"], n_blocks=cfg["lm_blocks"],
                       compute_dtype="bfloat16").init()
    if not cfg["rehearsal"]:
        head = cfg["lm_d"] // cfg["lm_heads"]
        check(pallas_attention.supports((LB, cfg["lm_heads"], T, head),
                                        mask=None, dtype="bfloat16"),
              "flash attention declined the LM train shape")
    n = n_iter * LB
    ids = rs.randint(0, V, (n, T + 1))
    eye = np.eye(V, dtype=np.float32)
    fit_and_check(lm, ListDataSetIterator(
        DataSet(eye[ids[:, :-1]], eye[ids[:, 1:]]), batch_size=LB),
        n_iter, "transformer_lm fit")


# ------------------------------------------------------------------- server
def build_serving_lm(cfg, seed=3):
    from deeplearning4j_tpu.models import TransformerLM

    net = TransformerLM(num_labels=cfg["srv_vocab"], max_length=64,
                        d_model=cfg["srv_d"], n_heads=cfg["srv_heads"],
                        n_blocks=cfg["srv_blocks"], seed=seed).init()
    for v in net.conf.vertices.values():
        lyr = getattr(v, "layer", None)
        if lyr is not None and hasattr(lyr, "max_cache"):
            lyr.max_cache = cfg["srv_cache"]
    return net


def serving_requests(cfg):
    """(warm, main): prompt/max_tokens/temperature/top_k/seed tuples.
    ``warm`` is served one request at a time so every prefill bucket
    (1..16 pages), the chunked >256-token prefill and the decode program
    compile deterministically; ``main`` is the mixed burst."""
    rs = np.random.RandomState(7)
    V = cfg["srv_vocab"]

    def prompt(n):
        return rs.randint(1, V, n).astype(np.int64)

    shared = prompt(64)
    warm = [(prompt(n), 6, 0.0, 0, 0) for n in (5, 20, 40, 100, 200, 300)]
    warm.append((np.concatenate([shared, prompt(20)]), 6, 0.8, 20, 11))
    plens = [5, 9, 14, 23, 31, 47, 64, 90, 120, 160, 210, 256, 257, 300,
             380, 450, 512, 530, 600, 650, 700, 12]
    ntoks = [32, 48, 200, 64, 96, 150, 40, 33, 128, 72, 180, 56, 100, 36,
             64, 90, 44, 120, 38, 160, 50, 200]
    main = []
    for i, (n, m) in enumerate(zip(plens, ntoks)):
        main.append((prompt(n), m, 0.0, 0, 0) if i % 2 == 0
                    else (prompt(n), m, 0.9, 20, 100 + i))
    main.append((np.concatenate([shared, prompt(33)]), 80, 0.0, 0, 0))
    main.append((np.concatenate([shared, prompt(7)]), 60, 0.7, 10, 5))
    return warm, main


def serve_and_check(srv, net, cfg, clock, label, http=None):
    warm, main = serving_requests(cfg)
    V = cfg["srv_vocab"]

    def finish(fut, spec):
        out = np.asarray(fut.result(timeout=900))
        check(out.shape == (spec[1],) and out.min() >= 0 and out.max() < V,
              f"{label}: bad completion {out.shape} for max_tokens "
              f"{spec[1]}")
        return out

    def submit(spec):
        p, m, temp, top_k, seed = spec
        return srv.submit(p, m, temperature=temp, top_k=top_k, seed=seed)

    for spec in warm:
        finish(submit(spec), spec)
    programs = len(net._output_cache)
    mark = clock.mark()
    futs = [submit(spec) for spec in main]
    if http is not None:
        kbs, mid = http
        p, m, temp, top_k, seed = main[3]
        body = json.dumps({"model": mid, "prompt_ids": p.tolist(),
                           "max_tokens": m, "temperature": temp,
                           "top_k": top_k, "seed": seed}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{kbs.port}/generate", data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=900) as resp:
            via_http = json.loads(resp.read())["tokens"]
    outs = [finish(f, spec) for f, spec in zip(futs, main)]
    if http is not None:
        # same prompt, sampling params and seed as main[3]: the key
        # schedule is per request, so the two completions are identical
        check(via_http == outs[3].tolist(),
              f"{label}: POST /generate disagrees with submit()")
    _, compiles = clock.since(mark)
    check(compiles == 0 and len(net._output_cache) == programs,
          f"{label}: {compiles} compiles / "
          f"{len(net._output_cache) - programs} new programs after warm-up")
    for key, prog in net._output_cache.items():
        if isinstance(key, tuple) and str(key[0]).startswith("gen_"):
            check(prog._cache_size() == 1, f"{label}: {key[0]} retraced")
    st = srv.stats()
    n_req = len(warm) + len(main) + (1 if http is not None else 0)
    check(st["failed"] == 0 and st["expired"] == 0 and st["pending"] == 0
          and st["accepted"] == st["completed"] == n_req,
          f"{label}: ledger does not balance: {st}")
    check(st["pages"]["prefix_hits"] >= 2,
          f"{label}: the shared 64-token prefix never hit the cache")
    backend = st["pages"]["paged_attention"]
    want = "xla" if cfg["rehearsal"] else "pallas"
    check(backend == want,
          f"{label}: paged attention backend {backend!r}, wanted {want!r}")
    print(f"  {label}: {n_req} requests, {st['tokens_generated']} tokens, "
          f"{st['decode_steps']} decode dispatches, backend {backend}, "
          f"peak KV {st['pages']['peak_resident_kv_bytes'] >> 20} MiB",
          flush=True)


def phase_server(cfg, clock):
    from deeplearning4j_tpu.modelimport.server import KerasBackendServer
    from deeplearning4j_tpu.parallel.generation import GenerationServer

    net = build_serving_lm(cfg)
    kw = dict(slots=cfg["srv_slots"], page_size=16, max_pending=64)
    kbs = KerasBackendServer(port=0)
    kbs.start()
    try:
        mid = kbs.attach_generation(net, vocab=cfg["srv_vocab"], **kw)
        serve_and_check(kbs._generators[mid], net, cfg, clock,
                        "server f32 pool", http=(kbs, mid))
    finally:
        kbs.stop()
    # a second net object: programs cache per net, so the int8 server's
    # compile and retrace counts start from nothing
    net = build_serving_lm(cfg)
    srv = GenerationServer(net, cfg["srv_vocab"], kv_dtype="int8", **kw)
    try:
        serve_and_check(srv, net, cfg, clock, "server int8 pool")
    finally:
        srv.close()


# ------------------------------------------------------------------ kernels
def compiled_with_mosaic(fn, args, n_kernels, interpret):
    """Compile ``fn`` and return the executable that will run; unless
    interpreting, its HLO must hold ``n_kernels`` Mosaic custom calls."""
    import jax

    compiled = jax.jit(fn).lower(*args).compile()
    if not interpret:
        found = compiled.as_text().count("tpu_custom_call")
        check(found >= n_kernels,
              f"expected {n_kernels} Mosaic custom calls, found {found}")
    return compiled


def errors(got, want):
    """max |got - want| / (1 + |want|) per output leaf."""
    import jax

    out = []
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        g = np.asarray(g, np.float32)
        w = np.asarray(w, np.float32)
        check(g.shape == w.shape and np.all(np.isfinite(g)),
              f"kernel output shape {g.shape} vs {w.shape} or non-finite")
        out.append(float(np.max(np.abs(g - w) / (1.0 + np.abs(w)))))
    return out


def phase_kernels(cfg, clock):
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.conf.layers.attention import (
        SelfAttentionLayer, scaled_dot_attention)
    from deeplearning4j_tpu.nn.conf.layers.paged_attention import (
        PallasPagedAttention, XlaPagedAttention)
    from deeplearning4j_tpu.ops.pallas_attention import flash_attention

    interpret = cfg["rehearsal"]
    rs = np.random.RandomState(5)

    # Tolerances are on |got - want| / (1 + |want|), both sides traced
    # under default_matmul_precision("highest") so every dot is f32 to
    # rounding. Measured on a v5e (PR 21) and set ~10x above, still far
    # under the >= 1e-1 a wrong mask, page, block or scale produces:
    # - paged read, 1e-5: kernel and stock path evaluate the same
    #   expressions in another summation order; measured <= 2e-7.
    # - flash f32, 1e-3: the forward agrees to 4e-7, but the backward
    #   rebuilds probabilities as exp(s - logsumexp) where the stock vjp
    #   reuses normalised softmax outputs, and the chip's exp is good to
    #   6e-6 relative (measured): over 512-term sums dq/dk/dv differ by
    #   5e-5..1.1e-4 (against float64 the stock path is the closer one).
    # - flash bf16, 3e-2: outputs round to bf16 (ulp 2**-8 = 4e-3) and
    #   delta = sum(dO * O) is taken from the rounded O; measured 7e-3.
    TOL_PAGED = 1e-5
    TOL_FLASH = {"float32": 1e-3, "bfloat16": 3e-2}

    # ---- flash attention, forward + dq + dk/dv, as the LM train step
    B, H, T, d = cfg["k_flash"]
    for dtype in ("bfloat16", "float32"):
        for masked in (False, True):
            q, k, v, g = (jnp.asarray(rs.standard_normal((B, H, T, d)),
                                      dtype) for _ in range(4))
            mask = None
            if masked:
                lens = rs.randint(T // 2, T + 1, B)
                mask = jnp.asarray(np.arange(T)[None, :] < lens[:, None],
                                   jnp.float32)

            def run(attn):
                def f(q, k, v, g):
                    out, vjp = jax.vjp(attn, q, k, v)
                    return (out,) + vjp(g)
                return f

            with jax.default_matmul_precision("highest"):
                kern = compiled_with_mosaic(
                    run(lambda q, k, v: flash_attention(
                        q, k, v, causal=True, mask=mask,
                        interpret=interpret)),
                    (q, k, v, g), 3, interpret)
                # the reference sees the same VALUES, widened: its error
                # is then rounding of f32 math, not of bf16 logits
                wide = [a.astype(jnp.float32) for a in (q, k, v, g)]
                ref = jax.jit(run(lambda q, k, v: scaled_dot_attention(
                    q, k, v, causal=True, mask=mask)))(*wide)
            errs = errors(kern(q, k, v, g), ref)
            print(f"  flash {dtype} mask={masked}: err o/dq/dk/dv "
                  + "/".join(f"{e:.1e}" for e in errs)
                  + f" (tol {TOL_FLASH[dtype]:.0e})", flush=True)
            check(max(errs) <= TOL_FLASH[dtype], "flash attention "
                  f"disagrees with the stock path: {errs} > "
                  f"{TOL_FLASH[dtype]}")

    # ---- paged attention read, as the serving prefill / decode programs
    B, H, d, ps, NP = cfg["k_paged"]
    P = B * NP + 1
    Tmax = NP * ps
    # the pool's order: a token's heads side by side in a page row
    kf = jnp.asarray(rs.standard_normal((P, ps, H * d)), jnp.float32)
    vf = jnp.asarray(rs.standard_normal((P, ps, H * d)), jnp.float32)
    # head-major [1, H, P*ps, d] view for the layer's own quantizer
    def quantize(pool):
        flat = pool.reshape(1, P * ps, H, d).transpose(0, 2, 1, 3)
        q8, sc = SelfAttentionLayer._quantize_kv(flat)
        return (q8.transpose(0, 2, 1, 3).reshape(P, ps, H * d),
                sc.reshape(H, P, ps).transpose(1, 0, 2))

    k8, ks = quantize(kf)
    v8, vs = quantize(vf)
    bt = jnp.asarray(rs.permutation(P - 1)[:B * NP].reshape(B, NP) + 1,
                     jnp.int32)
    pallas = PallasPagedAttention(interpret=interpret)
    stock = XlaPagedAttention()
    for quant in (False, True):
        kp, vp, ksc, vsc = (k8, v8, ks, vs) if quant \
            else (kf, vf, None, None)
        for T in (1, cfg["k_chunk"]):
            for masked in (False, True):
                q = jnp.asarray(rs.standard_normal((B, H, T, d)),
                                jnp.float32)
                pos = jnp.asarray(rs.randint(0, Tmax - T + 1, B),
                                  jnp.int32)
                mask = None
                if masked:
                    lens = rs.randint(1, T + 1, B)
                    mask = jnp.asarray(
                        np.arange(T)[None, :] < lens[:, None], jnp.float32)

                def call(helper):
                    return lambda q, kp, vp, bt, pos, mask, ksc, vsc: \
                        helper.attend(q, kp, vp, bt, pos, mask=mask,
                                      kscales=ksc, vscales=vsc)

                args = (q, kp, vp, bt, pos, mask, ksc, vsc)
                with jax.default_matmul_precision("highest"):
                    kern = compiled_with_mosaic(call(pallas), args, 1,
                                                interpret)
                    ref = jax.jit(call(stock))(*args)
                err = errors(kern(*args), ref)[0]
                print(f"  paged read {'int8' if quant else 'f32'} T={T} "
                      f"mask={masked}: err {err:.1e} "
                      f"(tol {TOL_PAGED:.0e})", flush=True)
                check(err <= TOL_PAGED, "paged attention disagrees "
                      f"with the stock path: {err} > {TOL_PAGED}")


# ---------------------------------------------------------------- multichip
def phase_multichip(cfg, clock):
    import jax

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
    from deeplearning4j_tpu.models import LeNet
    from deeplearning4j_tpu.parallel import ParallelWrapper
    from deeplearning4j_tpu.parallel.fleet import (ReplicaFleet,
                                                   device_groups)
    from deeplearning4j_tpu.parallel.generation import GenerationServer
    from deeplearning4j_tpu.parallel.mesh import data_mesh, model_mesh

    devices = jax.devices()[:4]

    # ---- data parallel: shared-gradients rounds over 4 chips x per-chip
    # batch 2 are, mathematically, single-device steps at batch 8. The
    # tolerance covers float reduction order only (pmean of four per-chip
    # means against one 8-row mean); it is the one the old driver entry
    # pinned for this assertion, where the drift measured 8e-5.
    rs = np.random.RandomState(1)
    x = rs.standard_normal((16, 28, 28, 1)).astype(np.float32)
    y = np.eye(10, dtype=np.float32)[rs.randint(0, 10, 16)]
    single = LeNet(num_labels=10).init()
    multi = LeNet(num_labels=10).init()
    for r in range(2):
        single.do_step(x[8 * r:8 * r + 8], y[8 * r:8 * r + 8])
    pw = ParallelWrapper(multi, mesh=data_mesh(4, devices=devices),
                         mode="shared_gradients")
    pw.fit(ListDataSetIterator(DataSet(x, y), batch_size=2))
    check(multi.iteration == 2, f"dp ran {multi.iteration} rounds, not 2")
    np.testing.assert_allclose(
        np.asarray(multi.params_flat(), np.float32),
        np.asarray(single.params_flat(), np.float32), rtol=5e-3, atol=2e-4,
        err_msg="data-parallel x4 diverged from the single-device steps")
    print("  data-parallel x4 == single device (rtol 5e-3, atol 2e-4)",
          flush=True)

    # ---- tensor-parallel decode: the pool splits by heads, 1/4 per chip
    net = build_serving_lm(cfg)
    kw = dict(slots=cfg["srv_slots"], page_size=16, max_pending=64)
    srv = GenerationServer(net, cfg["srv_vocab"], tp=4, **kw)
    try:
        serve_and_check(srv, net, cfg, clock, "server tp=4")
        for leaf in jax.tree_util.tree_leaves(srv._pool):
            shard = leaf.addressable_shards[0].data
            check(len(leaf.sharding.device_set) == 4
                  and shard.nbytes * 4 == leaf.nbytes,
                  f"tp=4 pool leaf {leaf.shape} is not split 4 ways")
    finally:
        srv.close()

    # ---- four one-chip replicas: replica k's pool AND weights on chip k
    groups = device_groups(4, 1, devices=devices)
    built = {}

    def factory(rid):
        built[rid] = GenerationServer(
            net, cfg["srv_vocab"],
            mesh=model_mesh(1, devices=groups[rid]), **kw)
        return built[rid]

    fleet = ReplicaFleet(factory, replicas=4)
    try:
        # placement, not load, is what this leg checks: half the burst
        main = serving_requests(cfg)[1][:12]
        futs = [fleet.submit(p, m, temperature=t, top_k=k, seed=s)
                for p, m, t, k, s in main]
        for f, spec in zip(futs, main):
            check(np.asarray(f.result(timeout=900)).shape == (spec[1],),
                  "fleet completion has the wrong length")
        st = fleet.stats()
        check(st["completed"] == len(main) and st["failed"] == 0,
              f"fleet ledger: {st}")
        served = []
        for rid in range(4):
            rep = built[rid]
            for what, tree in (("pool", rep._pool),
                               ("weights", rep._weights())):
                for leaf in jax.tree_util.tree_leaves(tree):
                    check(leaf.devices() == {devices[rid]},
                          f"replica {rid} {what} on {leaf.devices()}, "
                          f"wanted {devices[rid]}")
            served.append(rep.stats()["completed"])
        check(min(served) >= 1, f"a replica served nothing: {served}")
        print(f"  fleet of 4 one-chip replicas: completions per replica "
              f"{served}; pool and weights of replica k on chip k",
              flush=True)
    finally:
        fleet.close()


# --------------------------------------------------------------------- main
PHASES = {"trainer": phase_trainer, "server": phase_server,
          "kernels": phase_kernels, "multichip": phase_multichip}
FULL = dict(rehearsal=False,
            rn_batch=128, rn_image=224, rn_classes=1000,
            lm_T=512, lm_vocab=256, lm_batch=32, lm_d=256, lm_heads=8,
            lm_blocks=4,
            srv_vocab=256, srv_d=1024, srv_heads=8, srv_blocks=4,
            srv_cache=2048, srv_slots=32,
            k_flash=(4, 8, 512, 32), k_paged=(8, 8, 128, 16, 128),
            k_chunk=256)
TOY = dict(rehearsal=True,
           rn_batch=4, rn_image=32, rn_classes=10,
           lm_T=64, lm_vocab=32, lm_batch=4, lm_d=32, lm_heads=2,
           lm_blocks=1,
           srv_vocab=32, srv_d=32, srv_heads=4, srv_blocks=1,
           srv_cache=2048, srv_slots=32,
           k_flash=(1, 2, 128, 32), k_paged=(2, 2, 32, 16, 8), k_chunk=32)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse", action="store_true",
                    help="toy widths on the current backend, kernels "
                         "interpreted; never a chip result")
    ap.add_argument("--phases", default=",".join(PHASES),
                    help="comma-separated subset of " + ",".join(PHASES))
    args = ap.parse_args(argv)
    phases = [p for p in args.phases.split(",") if p]
    unknown = set(phases) - set(PHASES)
    if unknown:
        ap.error(f"unknown phases {sorted(unknown)}")

    t_start = time.perf_counter()
    import jax

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if not args.rehearse and device["platform"] != "tpu":
        # nothing on stdout: a refusal prints no result
        print(f"chip_smoke: jax found no TPU ({device}); refusing to run "
              "(--rehearse is the CPU dry run)", file=sys.stderr)
        return 2
    print(f"device: {device}", flush=True)
    cfg = TOY if args.rehearse else FULL

    import deeplearning4j_tpu

    print(f"compile cache: {deeplearning4j_tpu.enable_compile_cache()}",
          flush=True)
    clock = CompileClock()
    timings = {}
    for name, run in PHASES.items():
        if name not in phases:
            continue
        if name == "multichip" and len(devs) < 4:
            print(f"phase multichip: skipped, {len(devs)} device(s)",
                  flush=True)
            continue
        print(f"phase {name}:", flush=True)
        mark, t0 = clock.mark(), time.perf_counter()
        run(cfg, clock)
        compile_s, n = clock.since(mark)
        timings[name] = {"wall_s": round(time.perf_counter() - t0, 1),
                         "compile_s": round(compile_s, 1), "compiles": n}
        print(f"phase {name}: ok {timings[name]}", flush=True)

    summary = {"phases": timings,
               "total_s": round(time.perf_counter() - t_start, 1)}
    if args.rehearse:
        # a rehearsal ends on its own summary, never on the result line
        print(json.dumps({"ok": True, "rehearsal": True, "device": device,
                          **summary}), flush=True)
        return 0
    print(f"summary: {json.dumps(summary)}", flush=True)
    # the result line: these keys and no others
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
