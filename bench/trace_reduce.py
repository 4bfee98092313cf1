"""Reduce a JAX profiler trace to device busy time, per-program and
per-kernel device time, the heaviest operations and the idle gaps.

``load(path)`` reads the ``.xplane.pb`` the profiler wrote and keeps the
device planes' ``XLA Modules`` (one event per program execution) and
``XLA Ops`` (one per operation) lines as plain records. ``attach`` then
gives each op the program whose execution encloses it in time and a
short name. ``save``/``load_json`` keep records as JSON, which is how
the small recorded trace of the tests is stored; ``reduce`` works on
attached records alone.

On a TPU v5e with JAX 0.9 an op event's name is its HLO line, e.g.
``%closed_call.13 = bf16[16,32,128]{...} custom-call(...),
custom_call_target="tpu_custom_call", ...``: a Pallas kernel is a
``tpu_custom_call``. Programs are named by the jitted function behind
``jit_`` with the ``(id)`` suffix cut (``jit_decode_fn``,
``jit_prefill_suffix_fn``, ``jit_encode_fn``, ``jit_copy_fn``,
``jit_insert_fn``). A kernel is the ops of one kind inside one program
(``by_kind["jit_decode_fn/custom-call:tpu_custom_call"]``): each
metric's reader names the program and kind it reads.
"""
from __future__ import annotations

import bisect
import collections
import json
import re
from dataclasses import asdict, dataclass, replace
from typing import Dict, Iterable, List, Optional, Tuple

LINES = ("XLA Modules", "XLA Ops")
# ops whose interval holds other ops: counted by what runs inside them
CONTROL = ("while", "conditional", "call")
_SUFFIX = re.compile(r"(\(\d+\)|\.\d+)+$")
_HLO = re.compile(r"^%?([\w.-]+) = .*? ([a-z][\w-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


@dataclass(frozen=True)
class Event:
    device: str        # plane name, e.g. /device:TPU:0
    line: str          # XLA Modules | XLA Ops
    name: str
    start_ns: float
    dur_ns: float
    module: str = ""   # program the op ran in (ops, once attached)
    kind: str = ""     # HLO opcode, with the custom-call target


def program(name: str) -> str:
    """``jit_decode_fn(123)`` -> ``jit_decode_fn``."""
    return _SUFFIX.sub("", name.strip())


def load(path: str) -> List[Event]:
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            if line.name in LINES:
                out += [Event(plane.name, line.name, e.name,
                              float(e.start_ns), float(e.duration_ns))
                        for e in line.events]
    return attach(out)


def attach(events: List[Event]) -> List[Event]:
    """Ops get the program whose execution on their device encloses their
    start, a short name (``closed_call.13``) and their kind
    (``custom-call:tpu_custom_call``)."""
    runs: Dict[str, List[Tuple[float, float, str]]] = \
        collections.defaultdict(list)
    for e in events:
        if e.line == "XLA Modules":
            runs[e.device].append((e.start_ns, e.start_ns + e.dur_ns,
                                   program(e.name)))
    for r in runs.values():
        r.sort()
    starts = {d: [s for s, _, _ in r] for d, r in runs.items()}
    out = []
    for e in events:
        if e.line != "XLA Ops":
            out.append(e)
            continue
        m = _HLO.match(e.name)
        name, kind = (m.group(1), m.group(2)) if m else (e.name[:80], "")
        t = _TARGET.search(e.name)
        if t:
            kind = f"{kind}:{t.group(1)}"
        module = ""
        i = bisect.bisect_right(starts.get(e.device, []), e.start_ns) - 1
        if i >= 0:
            s, end, p = runs[e.device][i]
            if e.start_ns < end:
                module = p
        out.append(replace(e, name=name, module=module, kind=kind))
    return out


def save(events: Iterable[Event], path: str) -> None:
    with open(path, "w") as f:
        json.dump([asdict(e) for e in events], f, separators=(",", ":"))


def load_json(path: str) -> List[Event]:
    with open(path) as f:
        return [Event(**e) for e in json.load(f)]


def _union_ns(spans: List[Tuple[float, float]]) -> float:
    total, end = 0.0, float("-inf")
    for s, e in sorted(spans):
        if e <= end:
            continue
        total += e - max(s, end)
        end = e
    return total


@dataclass
class Reduced:
    busy_s: float                         # union of op intervals, mean/chip
    programs: Dict[str, float]            # seconds per program
    executions: Dict[str, int]            # runs per program
    by_kind: Dict[str, float]             # seconds per "program/op kind"
    calls_by_kind: Dict[str, int]
    top_ops: List[Tuple[str, float]]      # heaviest "program/op", seconds
    idle_gaps: List[Tuple[str, float]]    # "prev -> next" program, seconds


def reduce(events: List[Event], top: int = 10) -> Reduced:
    """Reduce attached events (see ``attach``)."""
    devices = sorted({e.device for e in events})
    ops = [e for e in events if e.line == "XLA Ops"]
    mods = [e for e in events if e.line == "XLA Modules"]
    busy = [_union_ns([(e.start_ns, e.start_ns + e.dur_ns)
                       for e in (ops or mods) if e.device == d])
            for d in devices]
    programs: Dict[str, float] = collections.defaultdict(float)
    runs: Dict[str, int] = collections.defaultdict(int)
    for e in mods:
        p = program(e.name)
        programs[p] += e.dur_ns * 1e-9
        runs[p] += 1
    ksec: Dict[str, float] = collections.defaultdict(float)
    kcalls: Dict[str, int] = collections.defaultdict(int)
    by_op: Dict[str, float] = collections.defaultdict(float)
    for e in ops:
        if e.kind.split(":")[0] in CONTROL:
            continue
        by_op[f"{e.module}/{e.name}"] += e.dur_ns * 1e-9
        ksec[f"{e.module}/{e.kind}"] += e.dur_ns * 1e-9
        kcalls[f"{e.module}/{e.kind}"] += 1
    gaps: Dict[str, float] = collections.defaultdict(float)
    for d in devices:
        seq = sorted((e.start_ns, e.start_ns + e.dur_ns, program(e.name))
                     for e in mods if e.device == d)
        end, prev = None, None
        for s, t, p in seq:
            if end is not None and s > end:
                gaps[f"{prev} -> {p}"] += (s - end) * 1e-9 / len(devices)
            if end is None or t > end:
                end, prev = t, p
    rank = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]
    return Reduced(
        busy_s=sum(busy) * 1e-9 / max(len(devices), 1),
        programs=dict(programs), executions=dict(runs),
        by_kind=dict(ksec), calls_by_kind=dict(kcalls),
        top_ops=[[k, v] for k, v in rank(by_op)],
        idle_gaps=[[k, v] for k, v in rank(gaps)])


def find_xplane(root: str) -> Optional[str]:
    import glob
    found = sorted(glob.glob(f"{root}/**/*.xplane.pb", recursive=True))
    return found[-1] if found else None
