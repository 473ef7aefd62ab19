"""Reduce a JAX profiler trace (``*.xplane.pb``) to the benchmark's numbers.

The reduction is benchmark code so that every PR computes the same number
the same way.  It reads three things from the trace:

* device busy time: the union of the intervals in which an XLA operation
  ran on a device plane (``/device:TPU:<i>``, line ``XLA Ops``);
* device time per XLA module (line ``XLA Modules``), summed by module name
  with the ``(<id>)`` suffix removed;
* the longest idle gaps between device operations, each attributed to the
  innermost host span that the benchmark opened around its own calls with
  ``jax.profiler.TraceAnnotation("bench.<what>")``;
* the span of the recorded device events, and whether they stop well
  before the benchmark's host spans do: the profiler keeps a bounded
  number of device events (a TPU v5e trace of the sweep cell kept 4,089
  module and 6.2 million op executions, its first 5.0 s of an 8 s window),
  and a window cut so reads short.

A plane, line or event here is plain data, so the tests can hand-build a
trace as well as read a recorded one.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

#: Prefix of the host spans the benchmark opens around its calls.
SPAN_PREFIX = "bench."
_MODULE_ID = re.compile(r"\(\d+\)$")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Plane:
    name: str
    lines: Dict[str, List[Event]]


@dataclasses.dataclass
class Summary:
    """What the per-layer metric readers get from one traced window."""

    n_devices: int
    busy_s: float                      # mean over the devices
    module_s: Dict[str, float]         # summed over the devices
    module_calls: Dict[str, int]
    op_s: Dict[str, float]             # summed over the devices
    gaps: List[Tuple[str, float]]      # longest first, device 0
    span_s: float = 0.0                # recorded device events, mean
    cut: bool = False                  # device events stop early


def find_xplane(trace_dir: str) -> str:
    """The newest ``*.xplane.pb`` under ``trace_dir``."""
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def planes_from_profile(profile) -> List[Plane]:
    """Plain planes from a ``jax.profiler.ProfileData``."""
    out = []
    for pl in profile.planes:
        lines: Dict[str, List[Event]] = {}
        for line in pl.lines:
            lines.setdefault(line.name, []).extend(
                Event(e.name, float(e.start_ns), float(e.duration_ns))
                for e in line.events)
        out.append(Plane(pl.name, lines))
    return out


def load(path: str) -> List[Plane]:
    from jax.profiler import ProfileData

    return planes_from_profile(ProfileData.from_file(path))


def device_planes(planes: Sequence[Plane]) -> List[Plane]:
    """Accelerator planes (``/device:TPU:0`` ...), in device order."""
    devs = [p for p in planes
            if p.name.startswith("/device:") and "CPU" not in p.name
            and "XLA Ops" in p.lines]
    return sorted(devs, key=lambda p: p.name)


def union(intervals: Sequence[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _busy(ops: Sequence[Event]) -> List[Tuple[float, float]]:
    return union([(e.start_ns, e.end_ns) for e in ops])


def host_spans(planes: Sequence[Plane]) -> List[Event]:
    """The benchmark's own host spans, from every host plane and line."""
    spans = []
    for p in planes:
        if not p.name.startswith("/host:"):
            continue
        for events in p.lines.values():
            spans.extend(e for e in events if e.name.startswith(SPAN_PREFIX))
    return spans


def attribute(t_ns: float, spans: Sequence[Event]) -> str:
    """Name of the innermost benchmark span around ``t_ns``."""
    best: Optional[Event] = None
    for s in spans:
        if s.start_ns <= t_ns <= s.end_ns and (
                best is None or s.dur_ns < best.dur_ns):
            best = s
    return best.name if best is not None else "outside bench spans"


def gaps(busy: Sequence[Tuple[float, float]], spans: Sequence[Event],
         top: int = 10) -> List[Tuple[str, float]]:
    """The ``top`` longest idle gaps between busy intervals, in seconds,
    each named by the host span around its midpoint."""
    out = []
    for (_, e0), (s1, _) in zip(busy, busy[1:]):
        out.append((attribute(0.5 * (e0 + s1), spans), (s1 - e0) * 1e-9))
    out.sort(key=lambda g: -g[1])
    return out[:top]


def module_name(name: str) -> str:
    return _MODULE_ID.sub("", name)


def looks_cut(device_end_ns: float, spans: Sequence[Event]) -> bool:
    """Whether the device events stop before the benchmark's host spans
    end by more than a tenth of those spans' extent (and half a second):
    the host's own work after the last device operation of a window is
    far shorter."""
    if not spans:
        return False
    start = min(s.start_ns for s in spans)
    end = max(s.end_ns for s in spans)
    return end - device_end_ns > max(0.5e9, 0.1 * (end - start))


def summarize(planes: Sequence[Plane]) -> Summary:
    devs = device_planes(planes)
    if not devs:
        raise ValueError("the trace holds no device plane with XLA Ops")
    spans = host_spans(planes)
    busy_s, module_s, module_calls, op_s = [], {}, {}, {}
    span_s, cut = [], False
    first_busy: List[Tuple[float, float]] = []
    for i, dev in enumerate(devs):
        ops = dev.lines["XLA Ops"]
        busy = _busy(ops)
        if i == 0:
            first_busy = busy
        busy_s.append(sum(e - s for s, e in busy) * 1e-9)
        if busy:
            span_s.append((busy[-1][1] - busy[0][0]) * 1e-9)
            cut = cut or looks_cut(busy[-1][1], spans)
        for e in ops:
            op_s[e.name] = op_s.get(e.name, 0.0) + e.dur_ns * 1e-9
        for e in dev.lines.get("XLA Modules", []):
            name = module_name(e.name)
            module_s[name] = module_s.get(name, 0.0) + e.dur_ns * 1e-9
            module_calls[name] = module_calls.get(name, 0) + 1
    return Summary(
        n_devices=len(devs),
        busy_s=sum(busy_s) / len(busy_s),
        module_s=module_s,
        module_calls=module_calls,
        op_s=op_s,
        gaps=gaps(first_busy, spans),
        span_s=sum(span_s) / len(span_s) if span_s else 0.0,
        cut=cut,
    )


def top_ops(summary: Summary, top: int = 10) -> List[List]:
    ops = sorted(summary.op_s.items(), key=lambda kv: -kv[1])[:top]
    return [[name, secs] for name, secs in ops]
