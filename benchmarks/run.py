# One function per paper table/figure (benchmarks.paper_tables) plus
# kernel/engine microbenchmarks. Prints CSV rows: name,...,derived.
from __future__ import annotations

import sys
import time


def main() -> None:
    t_start = time.time()
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    from benchmarks.extensions import EXTENSION_BENCHMARKS
    from benchmarks.kernel_bench import (bench_engine, bench_kernels,
                                         bench_paged_kv)
    from benchmarks.paper_tables import ALL_BENCHMARKS

    argv = list(sys.argv[1:])
    trace_path = None
    if "--trace" in argv:
        i = argv.index("--trace")
        if i + 1 >= len(argv):
            raise SystemExit("usage: run.py [only] [--trace out.json]")
        trace_path = argv[i + 1]
        del argv[i:i + 2]
    only = argv[0] if argv else None
    for fn in ALL_BENCHMARKS + EXTENSION_BENCHMARKS:
        if only and only not in fn.__name__:
            continue
        t0 = time.time()
        for row in fn():
            print(row)
        print(f"# {fn.__name__} done in {time.time() - t0:.1f}s", flush=True)
    if only is None or "kernel" in only or "engine" in only:
        for row in bench_kernels():
            print(row)
        for row in bench_engine():
            print(row)
    if only is None or "paged" in only:
        for row in bench_paged_kv():
            print(row)
    if only is None or "prefix" in only:
        from benchmarks.prefix_bench import bench_prefix_cache
        for row in bench_prefix_cache():
            print(row)
    if only is None or "chunked" in only:
        from benchmarks.chunked_prefill_bench import bench_chunked_prefill
        for row in bench_chunked_prefill():
            print(row)
    if only is None or "batching" in only:
        from benchmarks.continuous_batching_bench import \
            bench_continuous_batching
        for row in bench_continuous_batching():
            print(row)
    if only is None or "preempt" in only:
        from benchmarks.preemption_bench import bench_preemption
        for row in bench_preemption():
            print(row)
    if only is None or "fault" in only:
        from benchmarks.fault_bench import bench_faults
        for row in bench_faults():
            print(row)
    if only is None or "encode" in only:
        from benchmarks.encode_bench import bench_encode
        for row in bench_encode():
            print(row)
    # --trace forces the traced observability workload so there is
    # always a Perfetto trace to export, whatever the filter says
    if only is None or "observ" in only or trace_path:
        from benchmarks.observability_bench import bench_observability
        for row in bench_observability(trace_path=trace_path):
            print(row)
    print(f"# total {time.time() - t_start:.1f}s")


if __name__ == "__main__":
    main()
