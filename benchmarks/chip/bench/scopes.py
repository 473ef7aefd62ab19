"""Split a traced window by the program's own host spans and device scopes.

``bench/trace.py`` reduces a trace by XLA module and by the benchmark's
``bench.*`` spans.  This module reads what the program names itself:

* the ``tf_op`` of each device op: the op_name path JAX writes into the
  HLO metadata (``jit(fn)/while/body/cbp.greedy/...``).
  ``jax.profiler.ProfileData`` does not expose an event's metadata, so
  :func:`op_names` reads it from the xplane protobuf itself (the wire
  format by hand, no extra dependency);
* self time: an op's duration less the ops nested inside it on the same
  line (a while loop's event holds its body's ops);
* ``scope_self_s``: device-0 self time per innermost ``cbp.*`` scope;
* ``span_s`` and ``span_calls``: the program's ``cbp.*`` host spans,
  summed by name;
* ``span_idle_s``: device-0 idle time inside each ``cbp.*`` span;
* ``gaps``: the longest device-0 idle gaps, each named by the innermost
  ``bench.*`` or ``cbp.*`` span around it.

An op is tied to its metadata by the program it ran in (the XLA module
event around it on the ``XLA Modules`` line, whose name ends in the
program id) and its event name.
"""
from __future__ import annotations

import bisect
import dataclasses
import itertools
import re
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

from bench import trace

#: Prefix of the program's own spans and scopes.
PROGRAM_PREFIX = "cbp."
_PROGRAM_ID = re.compile(r"\((\d+)\)$")

#: ``(program id, op event name) -> tf_op``, per device plane name.
OpNames = Dict[str, Dict[Tuple[int, str], str]]


@dataclasses.dataclass
class Layers:
    """The window's time by the program's spans and scopes (device 0)."""

    scope_self_s: Dict[str, float]
    span_s: Dict[str, float]
    span_calls: Dict[str, int]
    span_idle_s: Dict[str, float]
    module_calls: int                  # XLA module executions, device 0
    gaps: List[Tuple[str, float]]      # longest first


# ----------------------------------------------------- xplane metadata


def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """``(field number, value)`` of one protobuf message: an int for a
    varint or fixed-width field, a memoryview for a length-delimited one."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 1:
            value, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 5:
            value, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, value


def _map_value(entry) -> Tuple[int, object]:
    key, value = 0, b""
    for f, v in _fields(entry):
        if f == 1:
            key = v
        elif f == 2:
            value = v
    return key, value


def _plane_op_names(plane) -> Tuple[str, Dict[Tuple[int, str], str]]:
    # XPlane: 2 name, 4 event_metadata map, 5 stat_metadata map.
    # XEventMetadata: 2 name, 5 stats.  XStat: 1 metadata_id, 3/4 an
    # integer, 5 a string, 7 a reference to a stat metadata's name.
    # XStatMetadata: 2 name.
    name, events, stat_names = "", [], {}
    for f, v in _fields(plane):
        if f == 2:
            name = bytes(v).decode()
        elif f == 4:
            events.append(_map_value(v)[1])
        elif f == 5:
            sid, meta = _map_value(v)
            stat_names[sid] = next(
                (bytes(x).decode() for g, x in _fields(meta) if g == 2), "")
    names: Dict[Tuple[int, str], Optional[str]] = {}
    for meta in events:
        ev_name, tf_op, program = "", None, None
        for f, v in _fields(meta):
            if f == 2:
                ev_name = bytes(v).decode()
            elif f == 5:
                stat = dict(_fields(v))
                kind = stat_names.get(stat.get(1))
                if kind == "tf_op":
                    tf_op = (bytes(stat[5]).decode() if 5 in stat
                             else stat_names.get(stat.get(7)))
                elif kind == "program_id":
                    program = stat.get(3, stat.get(4))
        if tf_op is None or program is None:
            continue
        key = (program, ev_name)
        # Two metadata entries for one op that disagree name nothing.
        names[key] = tf_op if names.get(key, tf_op) == tf_op else None
    return name, {k: v for k, v in names.items() if v is not None}


def op_names(path: str) -> OpNames:
    """Per device plane, ``(program id, op event name) -> tf_op`` from the
    xplane protobuf at ``path`` (XSpace field 1: the planes)."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out: OpNames = {}
    for field, plane in _fields(buf):
        if field == 1:
            name, names = _plane_op_names(plane)
            if name.startswith("/device:"):
                out[name] = names
    return out


# ----------------------------------------------------------- reduction


def self_times(ops: Sequence[trace.Event]) -> List[float]:
    """Each op's duration less the ops nested inside it, in ns (ops of
    one line nest or follow each other)."""
    own = [e.dur_ns for e in ops]
    order = sorted(range(len(ops)),
                   key=lambda i: (ops[i].start_ns, -ops[i].dur_ns))
    stack: List[int] = []
    for i in order:
        e = ops[i]
        while stack and ops[stack[-1]].end_ns <= e.start_ns:
            stack.pop()
        if stack:
            parent = ops[stack[-1]]
            own[stack[-1]] -= min(e.end_ns, parent.end_ns) - e.start_ns
        stack.append(i)
    return [max(t, 0.0) for t in own]


def scope_of(tf_op: str) -> Optional[str]:
    """The innermost ``cbp.*`` component of an op_name path."""
    parts = [p for p in tf_op.split("/") if p.startswith(PROGRAM_PREFIX)]
    return parts[-1] if parts else None


def _programs(modules: Sequence[trace.Event]):
    """Module events sorted by start, with their program ids."""
    mods = sorted(modules, key=lambda e: e.start_ns)
    ids = []
    for e in mods:
        m = _PROGRAM_ID.search(e.name)
        ids.append(int(m.group(1)) if m else None)
    return mods, [e.start_ns for e in mods], ids


def scope_self_s(dev: trace.Plane,
                 names: Dict[Tuple[int, str], str]) -> Dict[str, float]:
    """Self seconds per innermost ``cbp.*`` scope on one device plane."""
    ops = dev.lines.get("XLA Ops", [])
    mods, starts, ids = _programs(dev.lines.get("XLA Modules", []))
    out: Dict[str, float] = {}
    for e, own in zip(ops, self_times(ops)):
        k = bisect.bisect_right(starts, e.start_ns) - 1
        if k < 0 or e.start_ns > mods[k].end_ns or ids[k] is None:
            continue
        tf_op = names.get((ids[k], e.name))
        scope = scope_of(tf_op) if tf_op else None
        if scope is not None:
            out[scope] = out.get(scope, 0.0) + own * 1e-9
    return out


def spans(planes: Sequence[trace.Plane],
          prefixes: Tuple[str, ...]) -> List[trace.Event]:
    """Host spans whose names start with one of ``prefixes``."""
    return [e for p in planes if p.name.startswith("/host:")
            for events in p.lines.values() for e in events
            if e.name.startswith(prefixes)]


def busy_within(busy: Sequence[Tuple[float, float]], starts: Sequence[float],
                start: float, end: float) -> float:
    """Busy ns in [start, end] of the sorted disjoint intervals ``busy``,
    whose starts are ``starts``."""
    i = max(bisect.bisect_right(starts, start) - 1, 0)
    total = 0.0
    for s, e in itertools.islice(busy, i, None):
        if s >= end:
            break
        total += max(0.0, min(e, end) - max(s, start))
    return total


def layers(planes: Sequence[trace.Plane], names: OpNames) -> Layers:
    devs = trace.device_planes(planes)
    if not devs:
        raise ValueError("the trace holds no device plane with XLA Ops")
    dev = devs[0]
    busy = trace.union([(e.start_ns, e.end_ns)
                        for e in dev.lines["XLA Ops"]])
    starts = [s for s, _ in busy]
    program = spans(planes, (PROGRAM_PREFIX,))
    span_s: Dict[str, float] = {}
    span_calls: Dict[str, int] = {}
    span_idle_s: Dict[str, float] = {}
    for s in program:
        span_s[s.name] = span_s.get(s.name, 0.0) + s.dur_ns * 1e-9
        span_calls[s.name] = span_calls.get(s.name, 0) + 1
        idle = s.dur_ns - busy_within(busy, starts, s.start_ns, s.end_ns)
        span_idle_s[s.name] = span_idle_s.get(s.name, 0.0) + idle * 1e-9
    return Layers(
        scope_self_s=scope_self_s(dev, names.get(dev.name, {})),
        span_s=span_s,
        span_calls=span_calls,
        span_idle_s=span_idle_s,
        module_calls=len(dev.lines.get("XLA Modules", [])),
        gaps=trace.gaps(busy, spans(planes, (trace.SPAN_PREFIX,
                                             PROGRAM_PREFIX))),
    )


def load(path: str) -> Tuple[trace.Summary, Layers]:
    """Both reductions of the xplane at ``path``."""
    planes = trace.load(path)
    return trace.summarize(planes), layers(planes, op_names(path))
