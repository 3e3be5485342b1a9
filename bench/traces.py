"""Reduction of a profiler trace to device busy time, idle gaps and the
operations that took most time; and the count of compiles in a window.

A trace is the ``.xplane.pb`` file that ``jax.profiler`` writes.  Device
planes are named ``/device:<KIND>:<i>``; on each, the line ``XLA Ops`` holds
one event per operation that ran (``XLA Modules`` when a plane has no such
line).  Busy time is the union of those events' intervals inside the
window, averaged over the device planes.  The window and the labels of idle
gaps come from the benchmark's own host spans (``jax.profiler
.TraceAnnotation`` names that start with ``bench.``), which the profiler
puts on the same clock.
"""

from __future__ import annotations

import contextlib
import glob
import os
import re
from collections import defaultdict

import jax
from jax import monitoring

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def span(name: str):
    """A host span that the profiler records when a trace is on."""
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)


class CompileCounter:
    """Counts the executables built (compiled or loaded from the persistent
    cache) while it is active."""

    def __init__(self):
        self.count = 0

    def _listener(self, event: str, duration: float, **kwargs) -> None:
        if event == COMPILE_EVENT:
            self.count += 1

    @contextlib.contextmanager
    def counting(self):
        monitoring.register_event_duration_secs_listener(self._listener)
        try:
            yield self
        finally:
            monitoring.unregister_event_duration_listener(self._listener)


def op_label(text: str) -> str:
    """A short label of an HLO operation's trace name: its instruction name,
    operation, fusion kind and the start of its result type, e.g.
    ``%fusion.121 fusion kCustom s32[4194304]``."""
    head, _, rest = text.partition(" = ")
    op = re.search(r"\s([a-z][\w-]*)\(", rest)
    if not op:
        return text[:96]
    kind = re.search(r"kind=(\w+)", rest)
    result = rest[:op.start()].strip()
    return " ".join(filter(None, (head, op.group(1), kind and kind.group(1),
                                  result[:48])))


def find_trace(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(
            f"expected one .xplane.pb under {trace_dir}, found {len(paths)}")
    return paths[0]


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def read_events(path: str):
    """``(devices, spans)``: per device plane the list of
    ``(start_ns, end_ns, name)`` op events, and the benchmark's host spans
    as ``(start_ns, end_ns, name)`` with the prefix removed."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in data.planes:
        lines = {line.name: line for line in plane.lines}
        if plane.name.startswith("/device:"):
            line = lines.get("XLA Ops") or lines.get("XLA Modules")
            if line is not None:
                devices[plane.name] = [
                    (e.start_ns, e.start_ns + e.duration_ns, e.name)
                    for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                      e.name[len(SPAN_PREFIX):]))
    return devices, spans


def reduce_trace(path: str, top: int = 10) -> dict:
    """Busy and idle time of the device planes inside the window span.

    Returns ``busy_s`` (mean over device planes), ``window_s``,
    ``idle_share`` (0 to 1), ``device_ops`` (the ``top`` operations by
    summed device seconds over all planes, an operation that contains others,
    such as a loop, counting its whole span) and ``idle_gaps`` (the ``top``
    longest gaps of the first device plane, each labelled by the innermost
    host span that covers its middle, ``idle`` where none does)."""
    devices, spans = read_events(path)
    if not devices:
        raise ValueError(f"no device plane with op events in {path}")
    windows = [s for s in spans if s[2] == WINDOW_SPAN[len(SPAN_PREFIX):]]
    if windows:
        w0, w1 = windows[0][0], windows[0][1]
    else:
        w0 = min(ev[0] for evs in devices.values() for ev in evs)
        w1 = max(ev[1] for evs in devices.values() for ev in evs)
    busy = []
    op_time = defaultdict(float)
    for name in sorted(devices):
        clipped = [(max(s, w0), min(e, w1), op)
                   for s, e, op in devices[name] if e > w0 and s < w1]
        for s, e, op in clipped:
            op_time[op_label(op)] += (e - s) / 1e9
        busy.append(_union((s, e) for s, e, _ in clipped))
    busy_s = sum(sum(e - s for s, e in b) for b in busy) / len(busy) / 1e9
    window_s = (w1 - w0) / 1e9
    inner = [s for s in spans if s[2] != WINDOW_SPAN[len(SPAN_PREFIX):]]
    gaps = []
    edges = [w0] + [x for iv in busy[0] for x in iv] + [w1]
    for s, e in zip(edges[0::2], edges[1::2]):
        if e > s:
            mid = (s + e) / 2
            cover = [sp for sp in inner if sp[0] <= mid <= sp[1]]
            label = min(cover, key=lambda sp: sp[1] - sp[0])[2] \
                if cover else "idle"
            gaps.append([label, (e - s) / 1e9])
    gaps.sort(key=lambda g: -g[1])
    ops = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    return dict(
        busy_s=busy_s, window_s=window_s,
        idle_share=1.0 - busy_s / window_s if window_s > 0 else None,
        device_ops=[[k, v] for k, v in ops], idle_gaps=gaps[:top],
    )
