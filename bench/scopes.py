"""Reduction of a profiler trace by the program's own names: device time per
named scope, host time per span, and idle gaps and top operations labelled
with them.

    python3 bench/scopes.py <trace.xplane.pb, or a directory holding one>

prints the reduction as one JSON object.

The program names its device work with ``jax.named_scope`` and its host
work with ``jax.profiler.TraceAnnotation``, both under the one prefix
``ppr.`` (``ppr.push``, ``ppr.fold``, ``ppr.build.chunk``, ...).  A device
operation's event in the trace carries only its HLO instruction; the scope
path is in that instruction's ``op_name`` metadata.  The ``.xplane.pb``
file holds the optimized ``HloProto`` of every program that ran, as a bytes
stat on the event metadata of its ``/host:metadata`` plane, under the
program's name (``jit_f(<fingerprint>)``): the same name as that program's
events on a device plane's ``XLA Modules`` line.  So an operation is joined
to its scopes through the module event around it, that module's HLO and the
instruction's ``op_name``.  ``jax.profiler.ProfileData`` does not expose
event metadata stats, so the file is walked in protobuf's wire format.
"""

from __future__ import annotations

import bisect
import itertools
import json
import os
import re
import sys
from collections import defaultdict

from traces import WINDOW_SPAN, _union, find_trace, op_label

SCOPE_PREFIX = "ppr."
SPAN_PREFIXES = ("bench.", SCOPE_PREFIX)
_SCOPE = re.compile(r"ppr(?:\.\w+)+")
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?(%[\w.-]+) = .*?metadata=\{op_name=\"([^\"]*)\"", re.M)

# field numbers of the messages walked (tsl/profiler/protobuf/xplane.proto,
# xla/service/hlo.proto)
_XSPACE_PLANES, _XPLANE_NAME, _XPLANE_EVENT_METADATA = 1, 2, 4
_MAP_VALUE, _XEVENT_METADATA_NAME, _XEVENT_METADATA_STATS = 2, 2, 5
_XSTAT_BYTES, _HLOPROTO_MODULE = 6, 1


def _varint(buf: bytes, i: int):
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return value, i


def _fields(buf: bytes):
    """``(field number, value)`` of each field of one serialized message:
    an int for varints, bytes for the other wire types."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, value


def hlo_modules(path: str) -> dict:
    """The HLO text of every program the trace holds, by program name."""
    from jax._src.lib import xla_client

    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for field, plane in _fields(space):
        if field != _XSPACE_PLANES:
            continue
        fields = list(_fields(plane))
        if bytes(dict(fields).get(_XPLANE_NAME, b"")) != b"/host:metadata":
            continue
        for field, entry in fields:
            if field != _XPLANE_EVENT_METADATA:
                continue
            meta = dict(_fields(entry)).get(_MAP_VALUE)
            if meta is None:
                continue
            name = None
            for mf, value in _fields(meta):
                if mf == _XEVENT_METADATA_NAME:
                    name = bytes(value).decode()
                elif mf == _XEVENT_METADATA_STATS:
                    blob = dict(_fields(value)).get(_XSTAT_BYTES)
                    module = blob and dict(_fields(blob)).get(
                        _HLOPROTO_MODULE)
                    if module:
                        out[name] = xla_client._xla.HloModule \
                            .from_serialized_hlo_module_proto(
                                bytes(module)).to_string()
    return out


def scope_path(op_name: str) -> tuple:
    """The ``ppr.`` scopes of an ``op_name``, outermost first, each once."""
    return tuple(dict.fromkeys(_SCOPE.findall(op_name)))


def instruction_scopes(hlo_text: str) -> dict:
    """``%instruction -> scope path`` for every instruction of a module
    that carries an ``op_name``."""
    return {name: scope_path(op)
            for name, op in _INSTRUCTION.findall(hlo_text)}


def read_scoped_events(path: str):
    """``(devices, spans)``: per device plane the op events as ``(start_ns,
    end_ns, name, scopes)``, and the host spans named ``bench.*`` or
    ``ppr.*`` as ``(start_ns, end_ns, name)``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    modules = hlo_modules(path)
    scopes_of = {}
    devices, spans = {}, []
    for plane in data.planes:
        lines = {line.name: line for line in plane.lines}
        if plane.name.startswith("/device:"):
            ops = lines.get("XLA Ops") or lines.get("XLA Modules")
            if ops is None:
                continue
            mods = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                          for e in (lines["XLA Modules"].events
                                    if "XLA Modules" in lines else ()))
            starts = [m[0] for m in mods]
            events = []
            for e in ops.events:
                start, end = e.start_ns, e.start_ns + e.duration_ns
                k = bisect.bisect_right(starts, start) - 1
                module = mods[k][2] if k >= 0 and start < mods[k][1] else None
                if module not in scopes_of:
                    scopes_of[module] = instruction_scopes(
                        modules.get(module, ""))
                inst = e.name.partition(" = ")[0]
                events.append((start, end, e.name,
                               scopes_of[module].get(inst, ())))
            devices[plane.name] = events
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIXES):
                        spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                      e.name))
    return devices, spans


def _keys(scopes: tuple):
    """Each scope, and each nested pair ``outer/inner``, of a path."""
    yield from scopes
    for outer, inner in itertools.combinations(scopes, 2):
        yield f"{outer}/{inner}"


def _length(intervals) -> float:
    return sum(e - s for s, e in _union(intervals))


def reduce_scopes(path: str, top: int = 10) -> dict:
    """Device and host time by the program's names inside the window span.

    Returns ``busy_s`` and ``window_s`` as ``traces.reduce_trace`` computes
    them; ``scopes``: per ``ppr.`` scope, and per nested pair
    ``outer/inner`` (e.g. ``ppr.push/ppr.fold``), the union of the intervals
    of the op events under it, in seconds, averaged over device planes;
    ``unscoped_s``: busy time under no scope; ``host_spans``: per ``ppr.``
    span ``[seconds, count]`` inside the window; ``device_ops``: the ``top``
    operations by summed seconds, each label led by its innermost scope;
    ``idle_gaps``: the ``top`` longest gaps of the first device plane, each
    labelled by the innermost ``bench.`` or ``ppr.`` span over its middle
    (``idle`` where none is)."""
    devices, spans = read_scoped_events(path)
    if not devices:
        raise ValueError(f"no device plane with op events in {path}")
    windows = [s for s in spans if s[2] == WINDOW_SPAN]
    if windows:
        w0, w1 = windows[0][0], windows[0][1]
    else:
        w0 = min(ev[0] for evs in devices.values() for ev in evs)
        w1 = max(ev[1] for evs in devices.values() for ev in evs)
    busy, scoped = 0.0, 0.0
    scope_time = defaultdict(float)
    op_time = defaultdict(float)
    first_busy = None
    for name in sorted(devices):
        clipped = [(max(s, w0), min(e, w1), op, sc)
                   for s, e, op, sc in devices[name] if e > w0 and s < w1]
        by_key = defaultdict(list)
        for s, e, op, sc in clipped:
            label = op_label(op)
            op_time[f"{sc[-1]} {label}" if sc else label] += (e - s) / 1e9
            for key in _keys(sc):
                by_key[key].append((s, e))
        for key, intervals in by_key.items():
            scope_time[key] += _length(intervals)
        plane_busy = _union((s, e) for s, e, _, _ in clipped)
        if first_busy is None:
            first_busy = plane_busy
        busy += sum(e - s for s, e in plane_busy)
        scoped += _length((s, e) for s, e, _, sc in clipped if sc)
    planes = len(devices) * 1e9
    busy_s = busy / planes
    host = defaultdict(lambda: [0.0, 0])
    for s, e, name in spans:
        if name.startswith(SCOPE_PREFIX) and e > w0 and s < w1:
            host[name][0] += (min(e, w1) - max(s, w0)) / 1e9
            host[name][1] += 1
    inner = [s for s in spans if s[2] != WINDOW_SPAN]
    gaps = []
    edges = [w0] + [x for iv in first_busy for x in iv] + [w1]
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
        busy_s=busy_s, window_s=(w1 - w0) / 1e9,
        scopes={k: v / planes for k, v in sorted(scope_time.items())},
        unscoped_s=busy_s - scoped / planes,
        host_spans={k: v for k, v in sorted(host.items())},
        device_ops=[[k, v] for k, v in ops], idle_gaps=gaps[:top],
    )


def main(argv=None) -> int:
    args = sys.argv[1:] if argv is None else argv
    if len(args) != 1:
        print(__doc__.split("\n\n")[1], file=sys.stderr)
        return 2
    path = find_trace(args[0]) if os.path.isdir(args[0]) else args[0]
    print(json.dumps(reduce_scopes(path), indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
