"""The serving program's host spans in a profiler trace, and the device's
idle time put down to them.

``repro.core.telemetry.Tracer`` mirrors every span it records into a
``jax.profiler.TraceAnnotation`` of the same name, so a trace holds them
on the host plane, on the same clock as the device's programs. ``load``
keeps the host events whose names are span names of the program (the
profiler's own host events, such as the Python tracer's calls, are
dropped) as :class:`trace_reduce.Event` records whose ``device`` is the
host plane and whose ``line`` is the thread. ``idle_by_span`` then gives
every interval in which no operation ran on the device to the innermost
span open on the host at that time, and what no span covers to
``"untraced"``.
"""
from __future__ import annotations

import collections
import heapq
from typing import Dict, Iterable, List, Optional, Tuple

from bench import trace_reduce

UNTRACED = "untraced"


def load(path: str, names: Iterable[str]) -> List[trace_reduce.Event]:
    """The host plane's events named in ``names``, from an
    ``.xplane.pb``."""
    from jax.profiler import ProfileData
    keep = set(names)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out += [trace_reduce.Event(plane.name, line.name, e.name,
                                       float(e.start_ns),
                                       float(e.duration_ns))
                    for e in line.events if e.name in keep]
    return out


def _busy(events: List[trace_reduce.Event], device: str
          ) -> List[Tuple[float, float]]:
    """The device's busy intervals, merged: the union of its ops (of its
    program executions where the trace has no ops)."""
    mine = [e for e in events if e.device == device]
    ops = [e for e in mine if e.line == "XLA Ops"] or \
        [e for e in mine if e.line == "XLA Modules"]
    out: List[Tuple[float, float]] = []
    for s, e in sorted((e.start_ns, e.start_ns + e.dur_ns) for e in ops):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _idle(busy: List[Tuple[float, float]], lo: float, hi: float
          ) -> List[Tuple[float, float]]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(a, b) for a, b in out if b > a]


def idle_by_span(events: List[trace_reduce.Event],
                 lo: Optional[float] = None,
                 hi: Optional[float] = None) -> Dict[str, float]:
    """Seconds of device-idle time by the innermost host span open during
    it, mean over the devices, within ``[lo, hi]`` ns (by default from
    the first event's start to the last one's end). The innermost span
    at a time is the open one that started last. The parts sum to the
    window less the devices' mean busy time."""
    dev = sorted({e.device for e in events if e.line in trace_reduce.LINES})
    spans = sorted((e.start_ns, e.start_ns + e.dur_ns, e.name)
                   for e in events if e.line not in trace_reduce.LINES)
    if lo is None:
        lo = min(e.start_ns for e in events)
    if hi is None:
        hi = max(e.start_ns + e.dur_ns for e in events)
    out: Dict[str, float] = collections.defaultdict(float)
    for d in dev:
        for name, sec in _cover(spans, _idle(_busy(events, d), lo, hi)):
            out[name] += sec * 1e-9 / len(dev)
    return dict(out)


def _cover(spans: List[Tuple[float, float, str]],
           idle: List[Tuple[float, float]]) -> List[Tuple[str, float]]:
    """The idle intervals (sorted, disjoint) cut at every span boundary,
    each piece named by the innermost span open over it (``UNTRACED``
    where none is): one sweep over the sorted boundaries."""
    cuts = sorted({t for iv in idle for t in iv}
                  | {t for s, e, _ in spans for t in (s, e)})
    heap: List[Tuple[float, float, str]] = []   # (-start, end, name)
    i = k = 0
    out = []
    for t0, t1 in zip(cuts, cuts[1:]):
        while k < len(idle) and idle[k][1] <= t0:
            k += 1
        if k == len(idle):
            break
        if idle[k][0] > t0:
            continue                            # the device is busy
        while i < len(spans) and spans[i][0] <= t0:
            s, e, name = spans[i]
            heapq.heappush(heap, (-s, e, name))
            i += 1
        while heap and heap[0][1] <= t0:
            heapq.heappop(heap)
        out.append((heap[0][2] if heap else UNTRACED, t1 - t0))
    return out
