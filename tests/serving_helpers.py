"""Shared helpers of the serving test files (generation, handoff, disagg,
quantize, fleet, runtime, metrics, federation, mesh_generation, rag): the
tiny TransformerLM they all serve, the server/fleet context managers, the
mixed greedy+sampled request specs with their serial references, and the
client-side backoff and mid-stream polls. The ``lm`` fixture itself lives
in ``conftest.py``; a file that needs another length or head count
overrides it with ``tiny_lm(...)``.
"""

import time
from contextlib import contextmanager

import numpy as np

from deeplearning4j_tpu.models.zoo import (TransformerLM, greedy_generate,
                                           sample_generate)
from deeplearning4j_tpu.parallel.fleet import ReplicaFleet
from deeplearning4j_tpu.parallel.generation import GenerationServer
from deeplearning4j_tpu.parallel.resilience import ResilienceError

V = 17

# one request spec = (prompt, steps, temperature, top_k, seed)
GREEDY = (np.array([1, 2, 3, 4], np.int64), 12, 0.0, 0, 0)
SAMPLED = (np.array([1, 2, 3, 4], np.int64), 12, 0.9, 5, 77)


def tiny_lm(max_length=16, n_heads=2, seed=3):
    return TransformerLM(num_labels=V, max_length=max_length, d_model=16,
                         n_heads=n_heads, n_blocks=1, seed=seed).init()


@contextmanager
def serving(*args, **kwargs):
    srv = GenerationServer(*args, **kwargs)
    try:
        yield srv
    finally:
        srv.close()


@contextmanager
def fleet_of(factory, replicas=2, **kw):
    fl = ReplicaFleet(factory, replicas=replicas, **kw)
    try:
        yield fl
    finally:
        fl.close()


def mixed_specs(n, rng, shapes=((3, 4), (5, 5), (4, 6))):
    """n mixed greedy+sampled request specs over three prompt shapes (so
    the serial references compile a bounded program set)."""
    specs = []
    for i in range(n):
        plen, steps = shapes[i % len(shapes)]
        p = rng.integers(1, V, size=plen).astype(np.int64)
        if i % 2 == 0:
            specs.append((p, steps, 0.0, 0, 0))
        else:
            specs.append((p, steps, 0.9, 5, 2000 + i))
    return specs


def serial_refs(lm, specs):
    refs = []
    for p, steps, temp, top_k, seed in specs:
        if temp == 0.0:
            refs.append(greedy_generate(lm, p[None], steps, V)[0])
        else:
            refs.append(sample_generate(lm, p[None], steps, V,
                                        temperature=temp, top_k=top_k,
                                        seed=seed)[0])
    return refs


def submit_with_backoff(fleet, spec, deadline_s=240.0, budget_s=60.0):
    """Client-side 429/503 handling: typed shed at submit means back off
    and resubmit, exactly what an HTTP client does with Retry-After."""
    p, steps, temp, top_k, seed = spec
    t_end = time.monotonic() + budget_s
    while True:
        try:
            return fleet.submit(p, steps, temperature=temp, top_k=top_k,
                                seed=seed, deadline_s=deadline_s)
        except ResilienceError:
            if time.monotonic() > t_end:
                raise
            time.sleep(0.02)


def wait_replica_midstream(fl, rid, min_snapshots=4, min_active=2,
                           timeout=90.0):
    """Poll until replica ``rid`` is visibly mid-stream: >= min_active live
    slots AND enough published snapshots that the live slots are covered.
    Event-driven, not sleep-calibrated — compile time on a cold program
    cache just extends the poll."""
    t_end = time.monotonic() + timeout
    while True:
        rep = fl.stats()["replicas"][rid]
        srv = rep["server"] or {}
        ho = srv.get("handoff", {})
        if (srv.get("active_slots", 0) >= min_active
                and ho.get("snapshots", 0) >= min_snapshots):
            return
        assert time.monotonic() < t_end, (
            f"replica {rid} never reached a snapshotted mid-stream "
            f"state: {srv.get('active_slots')} active, "
            f"{ho.get('snapshots')} snapshots")
        time.sleep(0.005)
