"""Plays a ``serve_closed`` traffic file against ``GenerationServer``: a
closed loop of ``clients`` callers, each submitting its next request when
its last one resolves.

Traffic parameters: ``server`` (the constructor's arguments), ``clients``,
``prompt_tokens`` and ``max_tokens`` (log-normal: median, sigma, clipped
to min..max), ``shapes`` sizes drawn once from ``shapes_seed`` and cycled ``cycles``
times (the same sizes in the same order for every ``--seed``, which draws
the ids, the sampling seeds and the weights: the seed does not change the
work),
``sampling`` for odd and even requests, ``shared_prefix_tokens``,
``warmup_prompts`` (one request per prefill bucket the traffic can reach
and one multi-chunk prompt, served one at a time), ``checked_requests``.

The window opens once every slot has been active at once and closes
``--seconds`` later; requests in flight at the close are drained after it
(up to a minute), so that their stamps exist. What the program gives from
outside, and nothing else, is read: ``submit()``'s future, its
``_t_first`` stamp, a done-callback stamp, and ``stats()``.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from benchmarks.harness import loader
from benchmarks.harness.clock import percentile, rate
from benchmarks.harness.program import build_net, merged

#: the clients only wait; what the device idles through happens inside
#: the server's loop thread, which the program does not annotate yet
UNATTRIBUTED = "server_loop_unattributed"


# ------------------------------------------------------------- traffic
def _lognormal(rng, spec, n):
    x = spec["median"] * np.exp(spec["sigma"] * rng.standard_normal(n))
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)


def request_shapes(traffic: dict):
    """The (prompt tokens, max_tokens) sizes: the same for every seed."""
    rng = np.random.default_rng(int(traffic["shapes_seed"]))
    n = int(traffic["shapes"])
    return list(zip(_lognormal(rng, traffic["prompt_tokens"], n).tolist(),
                    _lognormal(rng, traffic["max_tokens"], n).tolist()))


def make_requests(seed: int, traffic: dict, vocab: int):
    """The requests: request j goes to client j mod ``clients``. Odd ones
    greedy, even ones sampled. Sizes and order are the traffic file's,
    cycled ``cycles`` times; ids and sampling seeds are the seed's, fresh
    for every request, so that no prompt comes twice in a run."""
    shapes = request_shapes(traffic) * int(traffic["cycles"])
    rng = np.random.default_rng([int(seed), 25])
    shared = rng.integers(0, vocab, int(traffic["shared_prefix_tokens"]))
    out = []
    for j, (plen, ntok) in enumerate(shapes):
        own = rng.integers(0, vocab, max(1, plen - shared.size))
        how = traffic["sampling"]["odd" if j % 2 else "even"]
        out.append({"j": j, "prompt": np.concatenate([shared, own]),
                    "max_tokens": int(ntok),
                    "temperature": float(how["temperature"]),
                    "top_k": int(how["top_k"]),
                    "seed": int(rng.integers(0, 2 ** 31 - 1))})
    return out


# --------------------------------------------------------------- set-up
def setup(run):
    from deeplearning4j_tpu.parallel.generation import GenerationServer

    config = merged(run.config, run.rehearse)
    traffic = merged(run.traffic, run.rehearse)
    sizes = config["sizes"]
    ref = loader.load_module("references", run.cell["config"])
    params = ref.make_params(run.seed, sizes, config["init"])
    net = build_net(config, params, serving_only=True)
    del params
    srv = GenerationServer(net, sizes["vocab"], **traffic["server"])
    rng = np.random.default_rng([int(run.seed), 26])
    with run.annotate("warmup"):
        for i, n in enumerate(traffic["warmup_prompts"]):
            how = traffic["sampling"]["odd" if i % 2 else "even"]
            out = srv.submit(rng.integers(0, sizes["vocab"], n),
                             traffic["warmup_max_tokens"],
                             temperature=how["temperature"],
                             top_k=how["top_k"], seed=i).result(timeout=900)
            if np.asarray(out).shape != (traffic["warmup_max_tokens"],):
                raise RuntimeError("a warm-up request came back short")
    requests = make_requests(run.seed, traffic, sizes["vocab"])
    return {"net": net, "srv": srv, "config": config, "traffic": traffic,
            "requests": requests, "records": [], "sizes": sizes}


def busy_seconds(stats: dict) -> float:
    """``generation_busy_seconds_total`` through the public surface:
    ``stats()`` gives tokens and tokens over busy seconds."""
    rate_ = stats["tokens_per_s"]
    return stats["tokens_generated"] / rate_ if rate_ > 0 else 0.0


# --------------------------------------------------------------- window
def _client(c, st, stop):
    srv, requests, records = st["srv"], st["requests"], st["records"]
    n_clients = st["traffic"]["clients"]
    i = c
    while not stop.is_set():
        spec = requests[i % len(requests)]
        i += n_clients
        rec = {"j": spec["j"], "client": c, "plen": int(spec["prompt"].size),
               "max_tokens": spec["max_tokens"],
               "greedy": spec["temperature"] <= 0, "spec": spec}
        done = threading.Event()

        def on_done(_f, rec=rec, done=done):
            rec["t_done"] = time.monotonic()
            done.set()

        rec["t_submit"] = time.monotonic()
        try:
            fut = srv.submit(spec["prompt"], spec["max_tokens"],
                             temperature=spec["temperature"],
                             top_k=spec["top_k"], seed=spec["seed"])
            fut.add_done_callback(on_done)
            if not done.wait(timeout=600):
                raise TimeoutError("no answer in 600 s")
            rec["tokens"] = np.asarray(fut.result())
            rec["t_first"] = getattr(fut, "_t_first", None)
        except Exception as exc:  # noqa: BLE001 — a failed request is data
            rec["error"] = repr(exc)
            rec.setdefault("t_done", time.monotonic())
        records.append(rec)


def window(run, st):
    srv, traffic = st["srv"], st["traffic"]
    slots = traffic["server"]["slots"]
    stop = threading.Event()
    threads = [threading.Thread(target=_client, args=(c, st, stop),
                                name=f"client{c}", daemon=True)
               for c in range(traffic["clients"])]
    for t in threads:
        t.start()
    t_wait = time.monotonic()
    while srv.stats()["active_slots"] < min(slots, traffic["clients"]):
        if time.monotonic() - t_wait > 300:
            stop.set()
            raise RuntimeError("the slots never filled")
        time.sleep(0.01)
    s0 = srv.stats()
    t_open = time.monotonic()
    slice_ = None
    if run.trace is not None:
        # the slice is the window's last seconds; it is stopped once the
        # window has closed, so that writing it out costs the window nothing
        lead = min(traffic["trace_seconds"], run.seconds / 2)
        time.sleep(max(0.0, t_open + run.seconds - lead - time.monotonic()))
        run.trace.start()
        slice_ = time.monotonic()
    time.sleep(max(0.0, t_open + run.seconds - time.monotonic()))
    s1 = srv.stats()
    t_close = time.monotonic()
    stop.set()
    if run.trace is not None:
        slice_ = (slice_, time.monotonic())
        run.trace.stop()
    for t in threads:
        t.join(timeout=max(1.0, t_close + 90 - time.monotonic()))
    late = [t.name for t in threads if t.is_alive()]
    s2 = srv.stats()
    records = list(st["records"])
    st["facts"] = facts = reduce_window(records, s0, s1, t_open, t_close,
                                        traffic, slice_)
    facts["late_clients"] = late
    run.log(f"window: {facts['window_s']:.3f} s, submitted "
            f"{facts['attempted']}, failed {facts['failed']}, completed in "
            f"window {facts['completed_in_window']}, ttft samples "
            f"{facts['ttft_samples']}, tpot samples {facts['tpot_samples']}, "
            f"tokens {facts['tokens']}, prefills {facts['prefills']}, decode "
            f"dispatches {facts['decode_dispatches']}, late clients {late}; "
            f"after the drain: completed {s2['completed']} failed "
            f"{s2['failed']} expired {s2['expired']} preempted "
            f"{s2['pages']['preempted']} prefix hits "
            f"{s2['pages']['prefix_hits']} backend "
            f"{s2['pages']['paged_attention']} peak KV "
            f"{s2['pages']['peak_resident_kv_bytes'] >> 20} MiB")
    failed = facts["failed"] + len(late)
    return {"attempted": facts["attempted"], "failed": failed,
            "end_to_end": facts["end_to_end"], "facts": facts}


def reduce_window(records, s0, s1, t_open, t_close, traffic, slice_=None):
    """From request records and two ``stats()`` readings to the window's
    numbers. Pure arithmetic on stamps: tested on made-up ones."""
    inside = [r for r in records if t_open <= r["t_submit"] < t_close]
    ok = [r for r in inside if "error" not in r]
    ttft = [1e3 * (r["t_first"] - r["t_submit"]) for r in ok
            if r.get("t_first") is not None]
    done_in = [r for r in records if "error" not in r
               and t_open <= r["t_done"] <= t_close]
    tpot = [1e3 * (r["t_done"] - r["t_first"]) / (len(r["tokens"]) - 1)
            for r in done_in
            if r.get("t_first") is not None and len(r["tokens"]) >= 2]
    tokens = s1["tokens_generated"] - s0["tokens_generated"]
    facts = {
        "t_open": t_open, "t_close": t_close, "window_s": t_close - t_open,
        "attempted": len(inside), "failed": len(inside) - len(ok),
        "completed_in_window": len(done_in),
        "ttft_samples": len(ttft), "tpot_samples": len(tpot),
        "tokens": tokens,
        "prefills": s1["prefills"] - s0["prefills"],
        "decode_dispatches": s1["decode_steps"] - s0["decode_steps"],
        "busy_s": busy_seconds(s1) - busy_seconds(s0),
        "slots": traffic["server"]["slots"],
        "steps_per_dispatch": traffic["server"]["steps_per_dispatch"],
        "prefill_chunk": traffic["server"]["prefill_chunk"],
        "slice": slice_, "records": records}
    e2e = {"serve_tokens_per_s": rate(tokens, t_open, t_close)}
    if ttft:
        e2e["ttft_p90_ms"] = percentile(ttft, 90)
    if tpot:
        e2e["tpot_p90_ms"] = percentile(tpot, 90)
    facts["end_to_end"] = e2e
    return facts


def work_spans(records, t0, t1, chunk):
    """What the model had to do between two stamps, from request records:
    (first context, count, chunk) spans. A prompt counts whole where its
    first token came inside the interval; a request's decoded tokens are
    taken as evenly spaced between its first token and its end."""
    spans = []
    for r in records:
        if "error" in r or r.get("t_first") is None:
            continue
        if t0 <= r["t_first"] <= t1:
            spans.append((1, r["plen"], chunk))
        n = len(r["tokens"])
        if n < 2:
            continue
        step = (r["t_done"] - r["t_first"]) / (n - 1)
        if step <= 0:
            continue
        lo = max(1, int(np.ceil((t0 - r["t_first"]) / step)))
        hi = min(n - 1, int(np.floor((t1 - r["t_first"]) / step)))
        if hi >= lo:
            # decoded token j reads a context of plen + j tokens
            spans.append((r["plen"] + lo, hi - lo + 1, 1))
    return spans


def release(st):
    st["srv"].close(timeout=30.0)
    for k in ("srv", "net"):
        st.pop(k, None)


# ----------------------------------------------------------- comparison
def checked_sample(run, st):
    """Finished greedy requests to compare: the longest, and others drawn
    from the seed, ``checked_requests`` in all."""
    done = [r for r in st["records"] if "error" not in r and r["greedy"]]
    if not done:
        return []
    done.sort(key=lambda r: r["j"])
    longest = max(done, key=lambda r: r["plen"] + len(r["tokens"]))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([int(run.seed), 27])
    k = min(len(rest), int(st["traffic"]["checked_requests"]) - 1)
    picks = [rest[i] for i in rng.choice(len(rest), size=k, replace=False)] \
        if k > 0 else []
    return [longest] + picks


def bad_completions(st) -> int:
    """Answers that say the wrong thing on their face: not ``max_tokens``
    ids (the traffic sets no end-of-sequence id), or ids out of range."""
    vocab = st["sizes"]["vocab"]
    bad = 0
    for r in st["records"]:
        if "error" in r:
            continue
        t = r["tokens"]
        if t.shape != (r["max_tokens"],) or t.min() < 0 or t.max() >= vocab:
            bad += 1
    return bad


def logit_gaps(run, st, sample, mode=None, alter=False):
    """Widest gap by which a token's reference logit lies below the
    reference's best, over the sample's served positions. ``mode`` None:
    the served tokens. ``mode`` a precision: the tokens that the reference
    in that precision puts first at the same positions (the control).
    ``alter``: the served tokens with one in each request replaced, as a
    token altered where it is produced would read."""
    import jax

    ref = loader.load_module("references", run.cell["config"])
    config, sizes, traffic = st["config"], st["sizes"], st["traffic"]
    params = st.get("ref_params")
    if params is None:
        params = st["ref_params"] = ref.make_params(run.seed, sizes,
                                                    config["init"])
    rows = int(traffic["max_tokens"]["max"])
    pad = int(traffic["prompt_tokens"]["max"]) + 2 * rows
    pad = -(-pad // 128) * 128
    worst, total, squares, count, mismatched = 0.0, 0.0, 0.0, 0, 0
    for r in sample:
        toks = np.asarray(r["tokens"], np.int64)
        if alter:
            toks = toks.copy()
            k = r["j"] % len(toks)
            toks[k] = (toks[k] + 1 + r["j"]) % sizes["vocab"]
        ids = np.concatenate([r["spec"]["prompt"], toks])
        n = len(toks)
        want = np.asarray(jax.device_get(ref.sequence_logits(
            params, ids, r["plen"] - 1, n, sizes, pad_to=pad, rows=rows)))
        if mode is None:
            picked = toks
        else:
            got = np.asarray(jax.device_get(ref.sequence_logits(
                params, ids, r["plen"] - 1, n, sizes, mode=mode, pad_to=pad,
                rows=rows)))
            picked = got.argmax(-1)
        gap = want.max(-1) - want[np.arange(n), picked]
        worst = max(worst, float(gap.max()))
        total += float(gap.sum())
        squares += float(np.square(gap).sum())
        count += n
        mismatched += int((picked != want.argmax(-1)).sum())
    # the widest gap swings by its nature (it is set by the one closest
    # call among some thousand); over all compared tokens the mean gap
    # grows with the square of the logits' noise and the mean squared gap
    # with its cube, which is what tells a lower precision from this one
    return {"served_logit_gap": worst,
            "served_logit_gap_mean": total / max(count, 1),
            "served_logit_gap_meansq": squares / max(count, 1),
            "_tokens_compared": count,
            "_tokens_not_reference_best": mismatched}


def check(run, st, compared):
    sample = checked_sample(run, st)
    nums = {"bad_completions": bad_completions(st)}
    if not sample:
        compared.fail("served_logit_gap", "no greedy request finished")
    else:
        nums.update(logit_gaps(run, st, sample))
    st.pop("ref_params", None)
    compared.take(nums, run.limits, run.log)
    return nums


# ------------------------------------------------- readings for the limits
def readings(run, kinds):
    """For ``benchmarks/readings.py``: one seed's numbers after a short
    window at the cell's own load. ``program``: the served tokens.
    ``control``: the reference in the configuration's ``control``
    precision at the same positions. ``fault_token_altered``: one served
    token in each compared request replaced."""
    st = setup(run)
    meas = window(run, st)
    release(st)
    sample = checked_sample(run, st)
    out = {}
    for kind in kinds:
        if kind == "program":
            nums = logit_gaps(run, st, sample)
            nums["bad_completions"] = bad_completions(st)
        elif kind == "control":
            nums = logit_gaps(run, st, sample, mode=st["config"]["control"])
        elif kind == "fault_token_altered":
            nums = logit_gaps(run, st, sample, alter=True)
        else:
            raise ValueError(f"serve_closed has no reading {kind!r}")
        nums["_requests_compared"] = len(sample)
        nums["_tokens_per_s"] = meas["end_to_end"]["serve_tokens_per_s"]
        out[kind] = nums
    st.pop("ref_params", None)
    return out
