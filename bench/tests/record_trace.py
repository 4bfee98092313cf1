#!/usr/bin/env python3
"""Re-record ``trace_v5e.json``, the small trace the reduction tests read.

    python3 bench/tests/record_trace.py --workload llava-sharegpt4o-burst16 \
        --seed 7 --seconds 30 --first jit_prefill_suffix_fn --nth 6 \
        --modules 5 --out bench/tests/trace_v5e.json

On a TPU, from the root of a checkout: one ``--trace 1`` run of the cell,
whose device events are kept from the ``--nth`` execution of program
``--first`` for ``--modules`` program executions, with every op inside
them. Op names are cut to the HLO line's name, shape, opcode and
custom-call target; times are in ns from the first event kept.
"""
import argparse
import json
import re
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
_LINE = re.compile(r"^(%?[\w.-]+ = .*? [a-z][\w-]*)\(")
_TARGET = re.compile(r'custom_call_target="[^"]+"')


def cut(name: str) -> str:
    m = _LINE.match(name)
    if not m:
        return name[:80]
    t = _TARGET.search(name)
    return m.group(1) + "()" + (f", {t.group(0)}" if t else "")


def rows(events, first: str, nth: int, modules: int):
    from bench import trace_reduce as T
    device = events[0].device
    mine = sorted((e for e in events if e.device == device),
                  key=lambda e: e.start_ns)
    mods = [e for e in mine if e.line == "XLA Modules"]
    starts = [i for i, e in enumerate(mods) if T.program(e.name) == first]
    keep = mods[starts[nth]:starts[nth] + modules]
    lo = keep[0].start_ns
    hi = max(e.start_ns + e.dur_ns for e in keep)
    t0 = min(e.start_ns for e in mine if lo <= e.start_ns < hi)
    return device, [[e.line.split()[-1],
                     e.name if e.line == "XLA Modules" else cut(e.name),
                     int(e.start_ns - t0), int(e.dur_ns)]
                    for e in mine if lo <= e.start_ns < hi]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--first", required=True)
    ap.add_argument("--nth", type=int, default=0)
    ap.add_argument("--modules", type=int, default=5)
    ap.add_argument("--out", type=Path, required=True)
    args = ap.parse_args(argv)
    sys.path[0:0] = [str(REPO), str(REPO / "src")]
    from bench import harness, trace_reduce

    raw = []
    attach = trace_reduce.attach

    def keep(events):                  # the trace's events as read
        raw[:] = events
        return attach(events)

    trace_reduce.attach = keep
    try:
        harness.run_cell(harness.resolve(args.workload), seed=args.seed,
                         seconds=args.seconds, trace=True,
                         t_process=time.perf_counter(),
                         out_dir=REPO / ".bench_out", log=print)
    finally:
        trace_reduce.attach = attach
    device, out = rows(raw, args.first, args.nth, args.modules)
    with open(args.out, "w") as f:
        json.dump({"about": f"device events of {args.workload}, seed "
                            f"{args.seed}, from execution {args.nth} of "
                            f"{args.first}", "device": device,
                   "rows": out}, f, separators=(",", ":"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
