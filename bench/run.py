#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1

From the root of a checkout. Builds the cell's configuration with random
weights drawn from ``--seed``, warms up every program shape the cell's
traffic produces, serves bursts back to back for ``--seconds`` and
checks a sample of what it served against the plain float32 reference.
With ``--trace 0`` the result carries the cell's end-to-end metrics,
with ``--trace 1`` its per-layer metrics, read from a profiler trace of
part of the window. The last stdout line is the result as JSON; the
numbers compared and their limits are also the last stderr lines.

Exits non-zero, printing no result, when JAX finds no TPU or fewer
chips than the cell asks for. The compile cache is
``$JAX_COMPILATION_CACHE_DIR`` where set, else ``<checkout>/.jax_cache``.
"""
import time

T_PROCESS = time.perf_counter()  # set-up is measured from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

REPO = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[0] = str(REPO)
    from bench import harness
    cell = harness.resolve(args.workload)

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"bench: needs a TPU, JAX found {devs[0].platform!r}",
              file=sys.stderr)
        return 2
    if len(devs) < cell.workload["chips"]:
        print(f"bench: {args.workload} needs {cell.workload['chips']} "
              f"chips, JAX found {len(devs)}", file=sys.stderr)
        return 2
    harness.peaks(devs[0].device_kind)        # an unknown chip is an error

    sys.path.insert(1, str(REPO / "src"))
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(REPO / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    result = harness.run_cell(
        cell, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        t_process=T_PROCESS, out_dir=REPO / ".bench_out",
        log=lambda s: print(s, flush=True))
    for name, c in result["check"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
