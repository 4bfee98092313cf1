"""Observability benchmark / smoke: traced chaos workload + trace export.

Two halves, both seeded and deterministic:

1. REAL cluster (smollm reduced, chunked prefill, wire faults): run
   with tracing ON and assert the telemetry invariants hold under
   chaos — every span balanced, every request's queue/compute/transfer/
   swap/retry components sum to its end-to-end latency (<= 1%), the
   retry component reconciling exactly with the registry's
   retry-time counter — then export the Chrome/Perfetto trace and
   validate it (well-formed events, non-empty Prefill AND Decode
   tracks).

2. SIMULATOR (smollm on simulated time, chunked prefill): the exported
   trace must show the streaming overlap the chunked planner schedules:
   chunk k's ``kv.wire`` span on the P->D link track overlapping chunk
   k+1's ``prefill.chunk`` span on the prefill compute track.

Writes BENCH_observability.json (attribution report + metrics-registry
snapshot under the common ``"telemetry"`` key). ``trace_path`` — wired
to ``benchmarks/run.py --trace out.json`` — additionally writes the
cluster run's Perfetto-loadable trace JSON there.
"""
from __future__ import annotations

import json
import os
from typing import List, Optional


def bench_observability(trace_path: Optional[str] = None) -> List[str]:
    import jax
    from repro.configs import get_config
    from repro.core.cluster import EPDCluster
    from repro.core.faults import SITE_TRANSFER_WIRE, FaultPlan
    from repro.core.simulator import SHAREGPT_4O, simulate
    from repro.core.telemetry import Tracer
    from repro.core.trace_export import (overlap, to_trace_events,
                                         validate_trace, write_trace)
    from repro.models.model import init_params
    from repro.serving.request import Request

    import dataclasses

    rows = ["observability,value,derived"]
    snap = {}

    # ---- 1. REAL cluster: traced chaos run + invariants --------------------
    cfg = get_config("smollm-135m").reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    tracer = Tracer(enabled=True)
    cl = EPDCluster(cfg, params, max_batch=2, max_len=96, paged=True,
                    page_size=8, prefix_cache=True, chunked_prefill=True,
                    prefill_chunk=8,
                    faults=FaultPlan(seed=11,
                                     rates={SITE_TRANSFER_WIRE: 0.3}),
                    tracer=tracer)
    reqs = [Request(prompt_tokens=list(range(3 + i, 27 + i)),
                    max_new_tokens=6) for i in range(4)]
    for r in reqs:
        cl.submit(r)
    done = cl.run_until_done()
    assert len(done) == len(reqs) and not cl.report.lost

    tracer.assert_balanced()
    cl.acc.assert_all_closed()
    cl.acc.check_all(tol=0.01)           # components sum to e2e
    att = cl.attribution()
    retry_comp = cl.acc.component_total("retry")
    assert abs(retry_comp - cl.report.retry_time_total) <= 1e-9, \
        "retry component must reconcile with retry_time_seconds_total"

    doc = {"traceEvents": to_trace_events(tracer),
           "displayTimeUnit": "ms"}
    counts = validate_trace(doc, require_tracks=["P0", "D0"])
    if trace_path:
        n = write_trace(tracer, trace_path)
        rows.append(f"trace_written,{n},events_to_{trace_path}")
    snap["cluster"] = {
        "n_requests": len(done),
        "transfer_retries": cl.report.transfer_retries,
        "retry_time_ms": round(cl.report.retry_time_total * 1e3, 3),
        "trace_tracks": counts,
        "attribution": att,
    }
    snap["telemetry"] = cl.metrics.snapshot()
    rows.append(f"cluster_spans,{sum(counts.values())},"
                f"tracks_{'_'.join(sorted(counts))}")
    rows.append(f"cluster_attribution,sum_eq_e2e,"
                f"mean_e2e_{att['mean_e2e_ms']}ms")

    # ---- 2. simulator: chunk-k wire under chunk-k+1 compute ----------------
    # long prompts + 1k-token chunks: per-chunk compute must exceed the
    # link handshake or every group just queues behind it (no overlap)
    model = get_config("deepseek-7b")
    ds = dataclasses.replace(SHAREGPT_4O, mm_fraction=0.0,
                             text_tokens_mean=4096.0, output_tokens=8)
    sim_tr = Tracer(enabled=True)
    m = simulate(model, "E-P-D", ds, rate=2.0, n_requests=6, seed=3,
                 kv_page_tokens=16, chunked_prefill=True,
                 prefill_chunk_tokens=1024, tracer=sim_tr)
    sim_doc = {"traceEvents": to_trace_events(sim_tr),
               "displayTimeUnit": "ms"}
    sim_tracks = validate_trace(sim_doc)
    p_track = next(t for t, n in sim_tr.tracks().items()
                   if "->" not in t and any(
                       s.track == t and s.name == "prefill.chunk"
                       for s in sim_tr.spans))
    link = next(t for t in sim_tr.tracks() if "->" in t)
    ov = overlap(sim_doc, p_track, "prefill.chunk", link, "kv.wire")
    assert ov > 0, "chunked streaming must overlap transfer with compute"
    # the specific schedule shape: chunk k's wire span rides under chunk
    # k+1's compute span. The sim's plan prepends a cached-prefix
    # segment, so plan group g is compute chunk g-1 and its wire rides
    # under compute chunk g.
    chunk_spans = [s for s in sim_tr.spans if s.name == "prefill.chunk"]
    wire_spans = [s for s in sim_tr.spans if s.name == "kv.wire"]
    adjacent = any(
        w.request_id == c.request_id
        and c.attrs.get("chunk") == w.attrs.get("group", -2)
        and min(w.end, c.end) > max(w.start, c.start)
        for w in wire_spans for c in chunk_spans)
    assert adjacent, "no chunk-k wire span overlapped chunk-k+1 compute"
    # attribution invariant holds on simulated time too
    for r in m.attribution["requests"]:
        s = sum(r["components_ms"].values())
        assert abs(s - r["e2e_ms"]) <= 0.01 * max(r["e2e_ms"], 1e-6) + 1e-6
    snap["simulator"] = {
        "overlap_ms": round(ov * 1e3, 4),
        "trace_tracks": sim_tracks,
        "mean_components_ms": m.attribution["mean_components_ms"],
    }
    rows.append(f"sim_stream_overlap,{ov * 1e3:.2f}ms,"
                f"chunk_k_wire_under_chunk_k+1_compute")

    out = os.path.join(os.path.dirname(__file__), "..",
                       "BENCH_observability.json")
    with open(os.path.abspath(out), "w") as f:
        json.dump(snap, f, indent=2, sort_keys=True)
        f.write("\n")
    return rows


if __name__ == "__main__":
    for row in bench_observability():
        print(row)
