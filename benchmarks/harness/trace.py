"""From a profiler trace to busy and idle time, an operation table and the
longest idle gaps. Reads the ``.xplane.pb`` that ``jax.profiler`` writes
with nothing but jax (``jax.profiler.ProfileData``).

What the trace of a TPU holds (looked at by hand, PR 25, jax 0.9.0): one
plane per chip, ``/device:TPU:<n>``, whose line ``XLA Ops`` has one event
per executed HLO instruction, named by the instruction's whole text
(``%name = type opcode(...)``); a ``while`` or a ``conditional`` is an
event that encloses its body's events, so times are taken exclusive of
what an event encloses. A Mosaic (Pallas) kernel is a ``custom-call``
whose text holds ``custom_call_target="tpu_custom_call"``. The plane
``/host:CPU`` has one line per host thread; ``TraceAnnotation`` spans
appear there under their own names, on a clock within about a millisecond
of the device's.
"""

from __future__ import annotations

import glob
import os
import re
import shutil

OPS_LINE = "XLA Ops"
MOSAIC_MARK = 'custom_call_target="tpu_custom_call"'
_SUFFIX = re.compile(r"\.\d+$")
#: the host span that marks the traced slice, after the prefix
SLICE = "slice"


class TraceSlice:
    """Profiles a slice of the window into a directory of the checkout and
    removes it after the reduction: a run writes megabytes, not gigabytes."""

    def __init__(self, directory: str, prefix: str = "bench:",
                 unattributed: str = "unattributed"):
        self.directory = directory
        self.prefix = prefix
        #: what a gap is called that no span of the benchmark's covers
        self.unattributed = unattributed
        self.started = None
        self.stopped = None

    def start(self):
        import time

        import jax

        shutil.rmtree(self.directory, ignore_errors=True)
        os.makedirs(self.directory, exist_ok=True)
        # the Python tracer slowed a host-bound window eightfold (PR 25):
        # off; host TraceMe spans (level 1) and the device stay on
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        options.enable_hlo_proto = False
        jax.profiler.start_trace(self.directory, profiler_options=options)
        self._span = jax.profiler.TraceAnnotation(self.prefix + SLICE)
        self._span.__enter__()
        self.started = time.perf_counter()

    def stop(self):
        import time

        import jax

        self.stopped = time.perf_counter()
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()

    @property
    def active(self) -> bool:
        return self.started is not None and self.stopped is None

    def discard(self):
        shutil.rmtree(self.directory, ignore_errors=True)

    def reduce(self, chips: int) -> dict:
        paths = glob.glob(os.path.join(self.directory, "plugins", "profile",
                                       "*", "*.xplane.pb"))
        if not paths:
            raise FileNotFoundError(f"no trace under {self.directory}")
        try:
            return reduce_xplane(paths[0], chips, self.prefix,
                                 self.unattributed)
        finally:
            shutil.rmtree(self.directory, ignore_errors=True)


def short_name(text: str) -> str:
    """``%fusion.12 = f32[..] fusion(...)`` -> ``fusion``."""
    head = text.split(" = ", 1)[0].strip().lstrip("%")
    return _SUFFIX.sub("", head) or head


def exclusive_times(events):
    """``events``: (start_ns, dur_ns, text) of ONE line. Yields
    (text, self_ns): an event's duration less what it encloses."""
    stack = []          # [end_ns, text, self_ns]
    out = []
    for s, d, text in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        e = s + d
        while stack and s >= stack[-1][0]:
            top = stack.pop()
            out.append((top[1], top[2]))
        if stack:
            stack[-1][2] -= min(d, stack[-1][0] - s)
        stack.append([e, text, d])
    while stack:
        top = stack.pop()
        out.append((top[1], top[2]))
    return out


def top_level_intervals(events):
    """Merged busy intervals [(start_ns, end_ns)] of one line."""
    merged = []
    for s, d, _ in sorted(events, key=lambda ev: ev[0]):
        e = s + d
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return merged


def reduce_events(device_lines, host_spans, prefix="bench:", top=10,
                  unattributed="unattributed"):
    """``device_lines``: per chip, the (start_ns, dur_ns, text) events of
    its ``XLA Ops`` line. ``host_spans``: (start_ns, dur_ns, name) of the
    host's annotated spans. Returns the reduction as a dict:

    - ``window_s``: the host's ``<prefix>slice`` span where there is one
      (device events are clipped to it), else the first device event's
      start to the last one's end over all chips;
    - ``busy_s``: union of op intervals, averaged over the chips;
    - ``ops``: {short name: self seconds}, summed over chips;
    - ``mosaic_s``: self seconds in Mosaic kernels, summed over chips;
    - ``mosaic_calls``: how many such events;
    - ``device_ops`` / ``idle_gaps``: the ``top`` largest, as lists.
    """
    whole = [(s, s + d) for s, d, n in host_spans if n == prefix + SLICE]
    lines = [ev for ev in device_lines if ev]
    if whole:
        # the slice as the host marked it: idle time at its edges counts
        t0, t1 = whole[0]
        lines = [[(max(s, t0), min(s + d, t1) - max(s, t0), text)
                  for s, d, text in ev if s < t1 and s + d > t0]
                 for ev in lines]
        lines = [ev for ev in lines if ev]
    if not lines:
        raise ValueError("the trace holds no device operation")
    if not whole:
        t0 = min(s for ev in lines for s, _, _ in ev)
        t1 = max(s + d for ev in lines for s, d, _ in ev)
    busy = 0.0
    ops: dict = {}
    mosaic_s = 0.0
    mosaic_calls = 0
    gaps: dict = {}
    spans = sorted((s, s + d, n) for s, d, n in host_spans
                   if n.startswith(prefix) and n != prefix + SLICE)
    for ev in lines:
        merged = top_level_intervals(ev)
        busy += sum(e - s for s, e in merged) / 1e9
        for text, self_ns in exclusive_times(ev):
            name = short_name(text)
            ops[name] = ops.get(name, 0.0) + self_ns / 1e9
            if MOSAIC_MARK in text:
                mosaic_s += self_ns / 1e9
                mosaic_calls += 1
        edges = [t0] + [x for iv in merged for x in iv] + [t1]
        for i in range(0, len(edges), 2):
            gs, ge = edges[i], edges[i + 1]
            if ge <= gs:
                continue
            name = _attribute(gs, ge, spans, prefix, unattributed)
            gaps[name] = gaps.get(name, 0.0) + (ge - gs) / 1e9
    n = len(lines)

    def biggest(table):
        return [[k, v] for k, v in sorted(table.items(),
                                          key=lambda kv: -kv[1])[:top]]

    return {"window_s": (t1 - t0) / 1e9, "busy_s": busy / n, "chips": n,
            "ops": ops, "mosaic_s": mosaic_s, "mosaic_calls": mosaic_calls,
            "device_ops": biggest(ops), "idle_gaps": biggest(gaps)}


def _attribute(gs, ge, spans, prefix, unattributed):
    """Name of the host span that covers most of the gap."""
    best, cover = unattributed, 0.0
    for s, e, n in spans:
        if s >= ge:
            break
        c = min(e, ge) - max(s, gs)
        if c > cover:
            best, cover = n[len(prefix):], c
    return best


def idle_pct(reduction):
    """Share of the traced slice in which no operation ran on the device,
    or None where there is no reduction to read."""
    if not reduction or reduction["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - reduction["busy_s"] / reduction["window_s"])


def reduce_xplane(path: str, chips: int, prefix: str = "bench:",
                  unattributed: str = "unattributed") -> dict:
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    device_lines, host_spans = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    device_lines.append([(e.start_ns, e.duration_ns, e.name)
                                         for e in line.events])
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(prefix):
                        host_spans.append((e.start_ns, e.duration_ns,
                                           e.name))
    return reduce_events(device_lines[:chips], host_spans, prefix,
                         unattributed=unattributed)
