"""Serving-runtime tests (parallel/runtime.py): the ServingLoop state
machine and sentinel discipline, LoopSupervisor crash recovery, and the
tentpole proof — shutdown-phase chaos across every runtime-hosted
server. A loop thread killed or stalled mid-drain / mid-close /
mid-migration must lose ZERO futures: every submitted request resolves
(result or typed error) within the deadline, and the admission ledger
ends balanced. The seeded submit-vs-close stress (N threads hammering
submit while close lands mid-burst) rides along, parametrized over the
runtime-hosted servers.
"""

import threading
import time
from concurrent.futures import TimeoutError as FuturesTimeout

import numpy as np
import pytest

from deeplearning4j_tpu.parallel import runtime as rt
from deeplearning4j_tpu.parallel.fleet import ReplicaFleet
from deeplearning4j_tpu.parallel.generation import GenerationServer
from deeplearning4j_tpu.parallel.inference import ParallelInference
from deeplearning4j_tpu.parallel.resilience import ChaosPolicy
from deeplearning4j_tpu.parallel.runtime import (IllegalLoopTransition,
                                                 LoopClosed, LoopState,
                                                 LoopSupervisor, ServingLoop)

from tests.serving_helpers import V
from tests.test_fused_fit import _iris_like, _mln

pytestmark = pytest.mark.runtime


def _wait_until(pred, timeout=10.0, step=0.005):
    limit = time.monotonic() + timeout
    while time.monotonic() < limit:
        if pred():
            return True
        time.sleep(step)
    return pred()


def _resolve_all(futs, timeout=30.0):
    """Resolve every future within the deadline; a HUNG future (timeout)
    fails the test — that is the zero-lost-futures criterion."""
    out = []
    for f in futs:
        try:
            out.append(("ok", f.result(timeout=timeout)))
        except FuturesTimeout:
            pytest.fail("future left unresolved past the deadline")
        except Exception as e:  # noqa: BLE001 - typed failure is fine
            out.append(("err", e))
    return out


# ---------------------------------------------------------------------------
# ServingLoop state machine
# ---------------------------------------------------------------------------

class TestStateMachine:
    def test_lifecycle_and_idempotent_transitions(self):
        done = []
        loop = ServingLoop("sm", handler=done.append)
        assert loop.state is LoopState.NEW
        loop.start()
        assert loop.state is LoopState.RUNNING
        loop.begin_drain()
        assert loop.state is LoopState.DRAINING
        loop.begin_drain()  # idempotent no-op
        assert loop.state is LoopState.DRAINING
        loop.close(timeout=5)
        assert loop.state is LoopState.CLOSED
        loop.close(timeout=5)  # idempotent
        with pytest.raises(LoopClosed):
            loop.put("late")

    def test_start_twice_raises(self):
        loop = ServingLoop("sm2", handler=lambda i: None).start()
        try:
            with pytest.raises(IllegalLoopTransition, match="start"):
                loop.start()
        finally:
            loop.close(timeout=5)

    def test_restart_from_running_raises(self):
        loop = ServingLoop("sm3", handler=lambda i: None).start()
        try:
            with pytest.raises(IllegalLoopTransition, match="restart"):
                loop.restart()
        finally:
            loop.close(timeout=5)

    def test_restart_after_deliberate_close_raises(self):
        loop = ServingLoop("sm4", handler=lambda i: None).start()
        loop.close(timeout=5)
        # a deliberate close is FINAL: a racing supervised restart must
        # never resurrect the loop
        with pytest.raises(IllegalLoopTransition, match="deliberate"):
            loop.restart()

    def test_tick_false_is_a_clean_exit(self):
        calls = []

        def tick():
            calls.append(1)
            return len(calls) < 3

        loop = ServingLoop("tick-clean", tick=tick).start()
        assert _wait_until(lambda: loop.alive_workers == 0)
        assert loop.crashed is None  # clean exit, not a crash
        assert len(calls) == 3
        loop.close(timeout=5)


# ---------------------------------------------------------------------------
# worker pool: sentinel walk, EXIT, carry, scaling, leftovers
# ---------------------------------------------------------------------------

class TestWorkerPool:
    def test_one_sentinel_walks_whole_pool_down(self):
        seen = []
        lock = threading.Lock()

        def handle(item):
            with lock:
                seen.append(item)

        loop = ServingLoop("pool", handler=handle, workers=3,
                           max_workers=3).start()
        for i in range(9):
            loop.put(i)
        loop.close(timeout=10)
        assert sorted(seen) == list(range(9))  # nothing dropped
        assert loop.alive_workers == 0         # the ONE sentinel got all 3

    def test_handler_exit_token_retires_worker(self):
        loop = ServingLoop(
            "exiter", workers=2, max_workers=2,
            handler=lambda item: rt.EXIT if item == "quit" else None).start()
        assert loop.alive_workers == 2
        loop.put("quit")
        assert _wait_until(lambda: loop.alive_workers == 1)
        loop.put("quit")
        assert _wait_until(lambda: loop.alive_workers == 0)
        loop.close(timeout=5)

    def test_carried_item_becomes_next_head(self):
        seen = []

        def handle(item):
            seen.append(item)
            if isinstance(item, tuple):
                return item[1]  # carry: handed straight back as next head
            return None

        loop = ServingLoop("carry", handler=handle).start()
        loop.put(("carry", "head"))
        assert _wait_until(lambda: "head" in seen)
        assert seen == [("carry", "head"), "head"]
        loop.close(timeout=5)

    def test_set_workers_scales_both_ways(self):
        loop = ServingLoop("scale", handler=lambda i: None,
                           workers=1, max_workers=4).start()
        loop.set_workers(3)
        assert _wait_until(lambda: loop.alive_workers == 3)
        loop.set_workers(1)  # resign tokens retire exactly two
        assert _wait_until(lambda: loop.alive_workers == 1)
        loop.close(timeout=5)

    def test_leftovers_failed_on_close(self):
        failed = []
        loop = ServingLoop("leftover", handler=lambda i: None,
                           on_leftover=failed.append).start()
        loop.put(rt._RESIGN)  # retire the sole worker: queue goes unserved
        assert _wait_until(lambda: loop.alive_workers == 0)
        for i in range(3):
            loop.put(i)
        loop.close(timeout=5)
        assert sorted(failed) == [0, 1, 2]  # failed typed, never stranded


# ---------------------------------------------------------------------------
# supervisor: crash detection, recovery verdicts, restart
# ---------------------------------------------------------------------------

class TestSupervisor:
    def test_crash_is_detected_restarted_and_resumes(self):
        seen, deaths = [], []
        sup = LoopSupervisor(poll_s=0.005)

        def handle(item):
            if item == "poison":
                raise ValueError("boom")
            seen.append(item)

        loop = ServingLoop("crashy", handler=handle).start()
        sup.watch(loop, on_death=lambda lp, e: deaths.append(e) or True,
                  restart=True)
        try:
            loop.put("a")
            assert _wait_until(lambda: "a" in seen)
            loop.put("poison")
            assert _wait_until(lambda: loop.restarts >= 1)
            assert _wait_until(lambda: loop.state is LoopState.RUNNING)
            assert len(deaths) == 1
            assert isinstance(deaths[0], ValueError)
            loop.put("b")  # the restarted loop actually serves
            assert _wait_until(lambda: "b" in seen)
        finally:
            loop.close(timeout=5)
            sup.shutdown()

    def test_on_death_false_vetoes_restart(self):
        sup = LoopSupervisor(poll_s=0.005)
        loop = ServingLoop(
            "vetoed",
            handler=lambda i: (_ for _ in ()).throw(ValueError(i))).start()
        sup.watch(loop, on_death=lambda lp, e: False, restart=True)
        try:
            loop.put("x")
            assert _wait_until(lambda: loop.state is LoopState.CLOSED)
            time.sleep(0.05)  # a few scan periods: still no resurrection
            assert loop.restarts == 0
            assert loop.state is LoopState.CLOSED
        finally:
            loop.close(timeout=5)
            sup.shutdown()

    def test_deliberate_close_is_never_treated_as_crash(self):
        sup = LoopSupervisor(poll_s=0.005)
        loop = ServingLoop("calm", handler=lambda i: None).start()
        sup.watch(loop, restart=True)
        try:
            loop.close(timeout=5)
            time.sleep(0.05)
            assert loop.restarts == 0
            assert sup.recoveries == 0
        finally:
            sup.shutdown()


# ---------------------------------------------------------------------------
# tentpole proof: shutdown-phase chaos, zero lost futures
# ---------------------------------------------------------------------------

def _pi_ledger_balanced(st):
    # every accepted request resolved exactly once (_on_done fires on
    # every path), nothing still pending after close
    return st["pending"] == 0 and \
        st["accepted"] == st["completed"] + st["failed"]


class TestShutdownChaos:
    def test_pi_kill_during_drain_loses_nothing(self):
        chaos = ChaosPolicy(seed=7, kill_during_drain_rate=1.0)
        x = np.asarray(_iris_like(8, seed=0).features)
        inf = ParallelInference(_mln(), workers=2, max_wait_ms=5,
                                chaos=chaos)
        futs = [inf.submit(x[i:i + 1]) for i in range(8)]
        inf.close(timeout=3)
        assert chaos.injected_drain_kill >= 1  # the kill actually landed
        _resolve_all(futs, timeout=10)
        assert _pi_ledger_balanced(inf.stats())

    def test_pi_sentinel_stall_close_stays_bounded(self):
        chaos = ChaosPolicy(seed=3, stall_sentinel_rate=1.0,
                            stall_sentinel_s=0.4)
        x = np.asarray(_iris_like(4, seed=1).features)
        inf = ParallelInference(_mln(), workers=2, max_wait_ms=5,
                                chaos=chaos)
        futs = [inf.submit(x[i:i + 1]) for i in range(4)]
        t0 = time.monotonic()
        inf.close(timeout=2)
        assert time.monotonic() - t0 < 15  # stalled retire never hangs close
        assert chaos.injected_sentinel_stall >= 1
        _resolve_all(futs, timeout=10)
        assert _pi_ledger_balanced(inf.stats())

    def test_generation_kill_mid_close_loses_nothing(self, lm):
        chaos = ChaosPolicy(seed=11, kill_during_drain_rate=1.0)
        srv = GenerationServer(lm, V, slots=2, chaos=chaos)
        rs = np.random.RandomState(2)
        futs = [srv.submit(rs.randint(0, V, 3), 4) for _ in range(4)]
        srv.close(timeout=8)
        assert chaos.injected_drain_kill >= 1
        _resolve_all(futs, timeout=10)
        st = srv.stats()
        assert st["pending"] == 0
        assert st["active_slots"] == 0 and st["queued"] == 0

    def test_generation_kill_mid_migration_recovers(self, lm):
        chaos = ChaosPolicy(seed=13, kill_during_drain_rate=1.0)
        srv = GenerationServer(lm, V, slots=2, chaos=chaos)
        rs = np.random.RandomState(5)
        futs = [srv.submit(rs.randint(0, V, 3), 6) for _ in range(3)]
        # move-out drain: the tick's migration pass IS a drain phase, so
        # the chaos kill lands there and the supervisor must absorb it
        assert srv.drain(timeout=10, migrate=True) is True
        assert chaos.injected_drain_kill >= 1
        _resolve_all(futs, timeout=10)
        # supervised restart rebuilt device state: the server still serves
        assert _wait_until(
            lambda: srv._runtime.state is LoopState.RUNNING, timeout=10)
        f = srv.submit(np.array([3, 1, 4]), 2)
        out = f.result(timeout=60)
        assert 1 <= len(out) <= 2
        assert srv.stats()["pool_rebuilds"] >= 1
        srv.close(timeout=8)

    def test_fleet_kill_mid_close_loses_nothing(self, lm):
        chaos = ChaosPolicy(seed=17, kill_during_drain_rate=1.0)
        fl = ReplicaFleet(lambda rid: GenerationServer(lm, V, slots=2),
                          replicas=1, chaos=chaos)
        rs = np.random.RandomState(9)
        futs = [fl.submit(rs.randint(0, V, 3), 3) for _ in range(3)]
        fl.close(timeout=10)
        assert chaos.injected_drain_kill >= 1
        _resolve_all(futs, timeout=10)


# ---------------------------------------------------------------------------
# satellite: seeded submit-vs-close stress across the hosted servers
# ---------------------------------------------------------------------------

N_THREADS = 4
PER_THREAD = 6


@pytest.mark.parametrize("kind", ["inference", "generation", "fleet"])
def test_submit_vs_close_stress(kind, lm):
    """N threads hammer submit() while close() lands mid-burst: every
    accepted future resolves within the deadline, every rejected submit
    raises typed — no caller ever hangs, no future is lost."""
    if kind == "inference":
        srv = ParallelInference(_mln(), workers=4, max_wait_ms=5)
        x = np.asarray(_iris_like(1, seed=0).features)
        do_submit = lambda: srv.submit(x)  # noqa: E731
    elif kind == "generation":
        srv = GenerationServer(lm, V, slots=2)
        do_submit = lambda: srv.submit(np.array([3, 1, 4]), 2)  # noqa: E731
    else:
        srv = ReplicaFleet(lambda rid: GenerationServer(lm, V, slots=2),
                           replicas=1)
        do_submit = lambda: srv.submit(np.array([3, 1, 4]), 2)  # noqa: E731

    futs, bad = [], []
    flock = threading.Lock()
    start_evt = threading.Event()

    def hammer(tid):
        jitter = np.random.RandomState(100 + tid)  # seeded per thread
        start_evt.wait(5)
        for _ in range(PER_THREAD):
            try:
                f = do_submit()
                with flock:
                    futs.append(f)
            except Exception as e:  # noqa: BLE001 - typed check below
                with flock:
                    bad.append(e)
            time.sleep(float(jitter.uniform(0.0, 0.004)))

    threads = [threading.Thread(target=hammer, args=(t,))
               for t in range(N_THREADS)]
    for t in threads:
        t.start()
    start_evt.set()
    time.sleep(0.01)  # let the burst begin, then close mid-flight
    srv.close(timeout=15)
    for t in threads:
        t.join(timeout=30)
    assert not any(t.is_alive() for t in threads)  # no submitter hung

    _resolve_all(futs, timeout=30)
    # rejects are all typed shutdown/backpressure errors, never raw
    for e in bad:
        assert isinstance(e, Exception)
        assert e.args, f"untyped rejection: {e!r}"
    srv.close(timeout=5)  # still idempotent after the storm
