"""Named spans on the profiler's clock that also count their seconds.

There is no second tracing system here: the profiler's trace is the span
store and the registry is the counter store. A span of a
:class:`SpanClock` is a context manager that

- enters ``jax.profiler.TraceAnnotation(prefix + name, **ids)``: whenever
  a profiler session is on (``jax.profiler.start_trace``, the
  ``ProfilerListener``, a benchmark's traced run) the span is an event on
  the calling thread's line of the ``/host:CPU`` plane, nested under the
  span its thread has open, on the clock the device plane uses. With no
  session on, the annotation is a TraceMe that checks one flag;
- books its OWN seconds (its duration less what the spans opened inside
  it cover) and one count under a phase name, by default its own. Every
  second between an outermost span's two ends is therefore booked under
  exactly one phase: a parent's phase holds what no child covered.

Seconds and counts gather per thread and go to the counters when the
thread's outermost span closes, one locked add a counter: a loop that
wraps each round in one span publishes once a round, not once a phase.
A round that can last long calls ``publish()`` at its own milestones, so
that a reader of the counters lags by one milestone and not by a round.
Identifiers (``rows=``, ``bucket=``) are the annotation's keyword
arguments and reach the trace only; keep them cheap ints.

    seconds = reg.counter("loop_seconds_total", "", labels=("phase",))
    spans = reg.counter("loop_spans_total", "", labels=("phase",))
    clock = SpanClock("loop:", lambda phase: (
        [seconds.labels(phase=phase)], [spans.labels(phase=phase)]))
    with clock.span("round", own="round_other", active=3):
        with clock.span("dispatch", rows=3):
            step()
"""

from __future__ import annotations

import threading
import time

from jax.profiler import TraceAnnotation

__all__ = ["SpanClock"]


class _ThreadState:
    __slots__ = ("stack", "mark", "booked")

    def __init__(self):
        self.stack = []      # phases of the spans this thread has open
        self.mark = 0.0      # since when the innermost one is being charged
        self.booked = {}     # phase -> [seconds, spans], not yet published


class SpanClock:
    """Spans named ``prefix + name``. ``sink(phase)`` gives the counters
    of a phase as ``(seconds counters, span counters)``, either of which
    may be empty; it is asked once a phase."""

    def __init__(self, prefix: str, sink):
        self.prefix = prefix
        self._sink = sink
        self._sinks = {}
        self._local = threading.local()

    def span(self, name: str, own: str | None = None, **ids):
        """A span ``prefix + name`` carrying ``ids`` into the trace; its
        own seconds are booked under the phase ``own`` (``name`` where
        none is given)."""
        return _Span(self, TraceAnnotation(self.prefix + name, **ids),
                     own or name)

    def publish(self):
        """What the calling thread has booked so far, to the counters now
        (the spans it has open keep counting and book the rest later)."""
        self._publish(self._state().booked)

    def _state(self) -> _ThreadState:
        st = getattr(self._local, "state", None)
        if st is None:
            st = self._local.state = _ThreadState()
        return st

    def _publish(self, booked: dict):
        for phase, (seconds, spans) in booked.items():
            sinks = self._sinks.get(phase)
            if sinks is None:
                sinks = self._sinks[phase] = self._sink(phase)
            for counter in sinks[0]:
                counter.inc(seconds)
            for counter in sinks[1]:
                counter.inc(spans)
        booked.clear()


class _Span:
    __slots__ = ("_clock", "_note", "_phase", "_st")

    def __init__(self, clock, note, phase):
        self._clock = clock
        self._note = note
        self._phase = phase
        self._st = None

    def __enter__(self):
        self._note.__enter__()
        st = self._st = self._clock._state()
        now = time.perf_counter()
        if st.stack:
            # the parent is charged up to here, and again from our end on
            st.booked.setdefault(st.stack[-1], [0.0, 0])[0] += now - st.mark
        st.stack.append(self._phase)
        st.mark = now
        return self

    def __exit__(self, exc_type, exc, tb):
        st = self._st
        now = time.perf_counter()
        cell = st.booked.setdefault(self._phase, [0.0, 0])
        cell[0] += now - st.mark
        cell[1] += 1
        st.stack.pop()
        st.mark = now
        self._note.__exit__(exc_type, exc, tb)
        if not st.stack:
            self._clock._publish(st.booked)
        return False
