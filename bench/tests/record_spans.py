#!/usr/bin/env python3
"""Re-record ``trace_v5e_spans.json``: device events and the serving
program's host spans of a few decode steps, which the span tests read.

    python3 bench/tests/record_spans.py --workload deepseek-chat-batch48 \
        --seed 7 --seconds 30 --nth 20 --steps 3 \
        --out bench/tests/trace_v5e_spans.json

On a TPU, from the root of a checkout: one ``--trace 1`` run of the cell.
The rows kept run from the start of the ``--nth`` ``decode.step`` span
of the traced part to the end of the ``--steps``-th after it, widened to
hold every device event that overlaps them whole: device events as
``record_trace.py`` keeps them, and every host span of the program that
overlaps that window. Times are in ns from the window's start.
"""
import argparse
import json
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]


def rows(device_events, spans, nth: int, steps: int):
    """(device, window length in ns, rows) of the cut described above."""
    from bench.tests.record_trace import cut
    device = device_events[0].device
    marks = sorted((s for s in spans if s.name == "decode.step"),
                   key=lambda s: s.start_ns)
    last = marks[nth + steps - 1]
    lo, hi = marks[nth].start_ns, last.start_ns + last.dur_ns
    dev = [e for e in device_events if e.device == device
           and e.start_ns < hi and e.start_ns + e.dur_ns > lo]
    lo = min([lo] + [e.start_ns for e in dev])
    hi = max([hi] + [e.start_ns + e.dur_ns for e in dev])
    host = [s for s in spans if s.start_ns < hi and s.start_ns + s.dur_ns > lo]
    out = [[e.line.split()[-1],
            e.name if e.line == "XLA Modules" else cut(e.name),
            int(e.start_ns - lo), int(e.dur_ns)]
           for e in sorted(dev, key=lambda e: e.start_ns)]
    out += [["Span", s.name, int(s.start_ns - lo), int(s.dur_ns)]
            for s in sorted(host, key=lambda s: s.start_ns)]
    return device, int(hi - lo), out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--nth", type=int, default=20)
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--out", type=Path, required=True)
    args = ap.parse_args(argv)
    sys.path[0:0] = [str(REPO), str(REPO / "src")]
    from bench import harness, host_spans, trace_reduce

    got = {}
    load, attach = trace_reduce.load, trace_reduce.attach
    traced = harness.Traced.__init__

    def keep_traced(self, cluster, *a, **kw):
        got["cluster"] = cluster
        traced(self, cluster, *a, **kw)

    def keep_spans(path):              # read before the trace is removed
        names = {s.name for s in got.pop("cluster").tracer.spans}
        got["spans"] = host_spans.load(path, names)
        return load(path)

    def keep_device(events):           # the device events as read
        got["device"] = list(events)
        return attach(events)

    trace_reduce.load, trace_reduce.attach = keep_spans, keep_device
    harness.Traced.__init__ = keep_traced
    try:
        harness.run_cell(harness.resolve(args.workload), seed=args.seed,
                         seconds=args.seconds, trace=True,
                         t_process=time.perf_counter(),
                         out_dir=REPO / ".bench_out", log=print)
    finally:
        trace_reduce.load, trace_reduce.attach = load, attach
        harness.Traced.__init__ = traced
    device, window, out = rows(got["device"], got["spans"], args.nth,
                               args.steps)
    with open(args.out, "w") as f:
        json.dump({"about": f"device events and host spans of "
                            f"{args.workload}, seed {args.seed}, from "
                            f"decode step {args.nth} of the traced part, "
                            f"{args.steps} steps",
                   "device": device, "window_ns": window, "rows": out},
                  f, separators=(",", ":"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
