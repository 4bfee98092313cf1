"""Stage-level telemetry: quantiles, registry, tracer, attribution,
trace export, and the no-behavior-change guarantee when disabled."""
import json

import pytest

from repro.core.telemetry import (COMPONENTS, NULL_SPAN, NULL_TRACER,
                                  LatencyAccountant, MetricsRegistry,
                                  Tracer, quantile)
from repro.core.trace_export import (overlap, to_trace_events,
                                     validate_trace, write_trace)


# ---------------------------------------------------------------------------
# quantile (the single implementation behind every p50/p99 in the repo)
# ---------------------------------------------------------------------------

def test_quantile_empty_is_zero():
    assert quantile([], 0.5) == 0.0
    assert quantile([], 0.99) == 0.0


def test_quantile_single_sample_every_p():
    for p in (0.0, 0.5, 0.99, 1.0):
        assert quantile([7.5], p) == 7.5


def test_quantile_interpolates():
    xs = [0.0, 10.0]
    assert quantile(xs, 0.5) == 5.0
    assert quantile(xs, 0.25) == 2.5
    assert quantile(list(range(101)), 0.99) == 99.0


def test_quantile_clamps_and_sorts():
    xs = [3.0, 1.0, 2.0]
    assert quantile(xs, -1.0) == 1.0
    assert quantile(xs, 2.0) == 3.0
    assert quantile(xs, 0.5) == 2.0     # unsorted input


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_registry_get_or_create_identity():
    r = MetricsRegistry()
    a = r.counter("x_total", engine="D0")
    b = r.counter("x_total", engine="D0")
    assert a is b
    a.inc(3)
    assert r.value("x_total", engine="D0") == 3.0
    assert r.value("x_total", engine="D1") == 0.0   # never touched


def test_registry_total_sums_label_sets():
    r = MetricsRegistry()
    r.counter("retries_total", site="a").inc(2)
    r.counter("retries_total", site="b").inc(5)
    assert r.total("retries_total") == 7.0


def test_registry_type_conflict_raises():
    r = MetricsRegistry()
    r.counter("m")
    with pytest.raises(ValueError):
        r.gauge("m", pool="p")


def test_counter_rejects_decrease():
    r = MetricsRegistry()
    with pytest.raises(ValueError):
        r.counter("c").inc(-1)


def test_gauge_max_high_water_mark():
    g = MetricsRegistry().gauge("peak")
    g.max(5)
    g.max(3)
    assert g.value == 5.0


def test_snapshot_shape_and_histogram():
    """A snapshot holds counters and gauges, keyed ``name{k=v}``, and
    nothing else: the registry keeps no histograms."""
    r = MetricsRegistry()
    r.counter("c_total", k="v").inc()
    r.gauge("g").set(0.5)
    snap = r.snapshot()
    assert set(snap) == {"counters", "gauges"}
    assert snap["counters"] == {"c_total{k=v}": 1.0}
    assert snap["gauges"] == {"g": 0.5}
    assert not hasattr(r, "histogram")
    json.dumps(snap)                    # JSON-able


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------

class AnnotationRecorder:
    """Stands in for ``jax.profiler.TraceAnnotation``: records each
    annotation's name and metadata as it is entered and left."""

    def __init__(self):
        self.events = []

    def __call__(self, name, **meta):
        rec = self

        class _Annotation:
            def __enter__(self):
                rec.events.append(("enter", name, meta))

            def __exit__(self, *exc):
                rec.events.append(("exit", name, meta))

        return _Annotation()


@pytest.fixture
def annotations(monkeypatch):
    import jax.profiler
    rec = AnnotationRecorder()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", rec)
    return rec


def test_disabled_tracer_records_nothing(annotations):
    t = Tracer(enabled=False)
    cm = t.span("phase", track="x", request_id=1)
    assert cm is NULL_SPAN              # shared no-op, zero allocation
    with cm:
        pass
    t.add("modeled", 0.0, 1.0)
    assert t.spans == []
    assert not t.want_decode_span(0)
    assert annotations.events == []     # nothing reaches the profiler


def test_enabled_tracer_mirrors_each_span_into_the_profiler(annotations):
    """Each span enters one profiler annotation of the same name, with
    the request id and the step or chunk index as its metadata; ``add``
    reaches only the tracer."""
    clk = {"t": 0.0}
    t = Tracer(enabled=True, now=lambda: clk["t"])
    with t.span("sched.step", track="router", step=4, n_chunks=2):
        with t.span("prefill.chunk", track="P0", request_id=9, chunk=1,
                    tokens=16):
            clk["t"] = 1.0
    t.add("queue.wait", 0.0, 1.0, track="router", request_id=9)
    assert annotations.events == [
        ("enter", "sched.step", {"step": 4}),
        ("enter", "prefill.chunk", {"chunk": 1, "request_id": 9}),
        ("exit", "prefill.chunk", {"chunk": 1, "request_id": 9}),
        ("exit", "sched.step", {"step": 4})]
    assert [s.name for s in t.spans] == ["prefill.chunk", "sched.step",
                                         "queue.wait"]


def test_span_nesting_records_parent():
    t = Tracer(enabled=True, now=lambda: 1.0)
    with t.span("outer", track="e"):
        with t.span("inner", track="e"):
            pass
    t.assert_balanced()
    inner, outer = sorted(t.spans, key=lambda s: s.name)
    assert inner.parent == "outer" and outer.parent is None


def test_unbalanced_span_fails_audit():
    t = Tracer(enabled=True, now=lambda: 0.0)
    cm = t.span("leak", track="e")
    cm.__enter__()
    with pytest.raises(AssertionError):
        t.assert_balanced()


def test_add_rejects_backwards_span():
    t = Tracer(enabled=True)
    with pytest.raises(ValueError):
        t.add("bad", 2.0, 1.0)


def test_decode_sampling():
    t = Tracer(enabled=True, decode_sample=4)
    assert [s for s in range(8) if t.want_decode_span(s)] == [0, 4]
    with pytest.raises(ValueError):
        Tracer(decode_sample=0)


def test_null_tracer_is_disabled():
    assert not NULL_TRACER.enabled and NULL_TRACER.spans == []


# ---------------------------------------------------------------------------
# latency accountant (fake wall clock)
# ---------------------------------------------------------------------------

class FakeWall:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_accountant_wall_segments_charge_by_state():
    w = FakeWall()
    acc = LatencyAccountant(wall=w)
    acc.open(1)                          # state: queue
    w.t = 2.0
    acc.set_state(1, "compute")          # syncs: 2s of queue charged
    w.t = 5.0
    acc.close(1, n_output_tokens=4)      # 3s of compute
    rec = acc.records[1]
    assert rec.components["queue"] == pytest.approx(2.0)
    assert rec.components["compute"] == pytest.approx(3.0)
    assert rec.e2e == pytest.approx(5.0)
    rec.check(tol=0.0)


def test_accountant_advance_overrides_one_request():
    acc = LatencyAccountant()            # simulated time: no wall
    acc.open(1, "compute")
    acc.open(2, "queue")
    acc.advance(1.0, 2, "retry")         # 2 retries; 1 keeps computing
    assert acc.records[1].components["compute"] == pytest.approx(1.0)
    assert acc.records[2].components["retry"] == pytest.approx(1.0)
    assert acc.records[2].components["queue"] == 0.0


def test_accountant_note_is_zero_sum_and_clamped():
    acc = LatencyAccountant()
    acc.open(1, "queue")
    acc.advance(2.0)
    moved = acc.note(1, "swap", 5.0, source="queue")   # only 2s available
    assert moved == pytest.approx(2.0)
    rec = acc.records[1]
    assert rec.components["queue"] == 0.0
    assert rec.components["swap"] == pytest.approx(2.0)
    acc.close(1)
    rec.check(tol=0.0)                   # invariant survives the move


def test_accountant_ttft_snapshot_and_alias():
    acc = LatencyAccountant()
    acc.open(1, "compute")
    acc.advance(1.0)
    acc.mark_first_token(1)
    acc.alias(999, 1)
    acc.advance(0.5, 999, "transfer")    # billed to request 1
    acc.close(1, n_output_tokens=3)
    rec = acc.records[1]
    assert rec.ttft == pytest.approx(1.0)
    assert rec.ttft_components["compute"] == pytest.approx(1.0)
    assert rec.decode_components()["transfer"] == pytest.approx(0.5)
    assert rec.n_output_tokens == 3


def test_accountant_open_is_requeue_safe():
    acc = LatencyAccountant()
    acc.open(1, "queue")
    acc.advance(1.0)
    acc.open(1, "queue")                 # requeue: must not reset ledger
    assert acc.records[1].components["queue"] == pytest.approx(1.0)
    assert acc.n_open == 1
    with pytest.raises(AssertionError):
        acc.assert_all_closed()
    acc.close(1)
    acc.assert_all_closed()


def test_accountant_report_is_jsonable():
    acc = LatencyAccountant()
    acc.open(1, "compute")
    acc.advance(2.0)
    acc.close(1, 2)
    rep = acc.report()
    assert rep["n_requests"] == 1
    assert set(rep["mean_components_ms"]) == set(COMPONENTS)
    json.dumps(rep)


# ---------------------------------------------------------------------------
# trace export
# ---------------------------------------------------------------------------

def _traced():
    clk = {"t": 0.0}
    t = Tracer(enabled=True, now=lambda: clk["t"])
    with t.span("prefill", track="P0", request_id=1):
        clk["t"] = 1.0
    t.add("kv.wire", 0.5, 0.8, track="P0->D0", request_id=1)
    t.add("decode.step", 1.0, 1.2, track="D0")
    return t


def test_export_and_validate_roundtrip(tmp_path):
    t = _traced()
    path = tmp_path / "trace.json"
    n = write_trace(t, str(path))
    doc = json.loads(path.read_text())
    counts = validate_trace(doc, require_tracks=["P0", "D0"])
    assert n == 3 and counts == {"P0": 1, "P0->D0": 1, "D0": 1}
    # timestamps are microseconds of the tracer clock
    x = [e for e in doc["traceEvents"]
         if e["ph"] == "X" and e["name"] == "kv.wire"][0]
    assert x["ts"] == pytest.approx(0.5e6)
    assert x["dur"] == pytest.approx(0.3e6)


def test_validate_requires_tracks():
    doc = {"traceEvents": to_trace_events(_traced())}
    with pytest.raises(AssertionError):
        validate_trace(doc, require_tracks=["E0"])


def test_overlap_helper():
    doc = {"traceEvents": to_trace_events(_traced())}
    # wire [0.5, 0.8] rides under prefill [0.0, 1.0]
    assert overlap(doc, "P0", "prefill", "P0->D0", "kv.wire") == \
        pytest.approx(0.3)
    assert overlap(doc, "P0", "prefill", "D0", "decode.step") == 0.0


# ---------------------------------------------------------------------------
# integration: real cluster + simulator invariants
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def smollm():
    import jax
    from repro.configs import get_config
    from repro.models.model import init_params
    cfg = get_config("smollm-135m").reduced()
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def _run_cluster(cfg, params, tracer=None, faults=None):
    from repro.core.cluster import EPDCluster
    from repro.serving.request import Request
    cl = EPDCluster(cfg, params, max_batch=2, max_len=64, paged=True,
                    page_size=8, chunked_prefill=True, prefill_chunk=8,
                    faults=faults, tracer=tracer)
    reqs = [Request(prompt_tokens=list(range(3 + i, 20 + i)),
                    max_new_tokens=6) for i in range(3)]
    for r in reqs:
        cl.submit(r)
    done = cl.run_until_done()
    assert len(done) == 3
    return cl, [r.output_tokens for r in reqs]


def test_cluster_attribution_invariants(smollm):
    from repro.core.faults import SITE_TRANSFER_WIRE, FaultPlan
    cfg, params = smollm
    tr = Tracer(enabled=True)
    cl, _ = _run_cluster(cfg, params, tracer=tr,
                         faults=FaultPlan(
                             seed=11, rates={SITE_TRANSFER_WIRE: 0.3}))
    tr.assert_balanced()
    cl.acc.assert_all_closed()
    cl.acc.check_all(tol=0.01)           # components sum to e2e
    # the retry component reconciles exactly with the registry counter
    assert cl.acc.component_total("retry") == \
        pytest.approx(cl.report.retry_time_total, abs=1e-9)
    # spans landed on the engine tracks the exporter renders
    tracks = tr.tracks()
    assert tracks.get("P0") and tracks.get("D0")
    doc = {"traceEvents": to_trace_events(tr)}
    validate_trace(doc, require_tracks=["P0", "D0"])


def test_cluster_tracing_disabled_no_behavior_change(smollm):
    cfg, params = smollm
    cl_off, out_off = _run_cluster(cfg, params, tracer=None)
    tr = Tracer(enabled=True)
    cl_on, out_on = _run_cluster(cfg, params, tracer=tr)
    # greedy outputs bit-identical with tracing on vs off
    assert out_on == out_off
    # untraced run recorded zero spans anywhere (NULL_TRACER untouched)
    assert cl_off.tracer.spans == [] and not cl_off.tracer.enabled
    assert len(tr.spans) > 0
    # counter migration: the registry agrees with the legacy names
    e = cl_on.prefill_engine
    assert e.prefill_tokens_total == \
        int(cl_on.metrics.value("prefill_tokens_total", engine="P0"))


def test_cluster_report_counter_backcompat(smollm):
    """The migrated ClusterReport counters read through to the registry."""
    from repro.core.faults import SITE_STORE_FETCH
    cfg, params = smollm
    cl, _ = _run_cluster(cfg, params)
    assert cl.report.store_retries == 0
    assert cl.report.transfer_retries == 0
    assert cl.report.transfer_replans == 0
    assert cl.report.retry_time_total == 0.0
    cl.metrics.counter("recovery_retries_total",
                       site=SITE_STORE_FETCH).inc(2)
    cl.metrics.counter("retry_time_seconds_total", site="transfer").inc(0.5)
    assert cl.report.store_retries == 2
    assert cl.report.retry_time_total == 0.5


def test_simulator_attribution_sums_exactly():
    import dataclasses
    from repro.configs import get_config
    from repro.core.faults import SITE_TRANSFER_WIRE, FaultPlan
    from repro.core.simulator import SHAREGPT_4O, simulate
    model = get_config("openpangu-7b-vl")
    ds = dataclasses.replace(SHAREGPT_4O, mm_fraction=0.25)
    m = simulate(model, "E-P-D", ds, rate=8.0, n_requests=24, seed=3,
                 kv_page_tokens=16, decode_kv_pages=512, preemption=True,
                 faults=FaultPlan(seed=7,
                                  rates={SITE_TRANSFER_WIRE: 0.05}))
    att = m.attribution
    assert att["n_requests"] == 24
    for r in att["requests"]:
        total = sum(r["components_ms"].values())
        assert total == pytest.approx(r["e2e_ms"], rel=0.01, abs=1e-6)
    # registry snapshot rides along under the common key
    assert m.telemetry["counters"][
        f"recovery_retries_total{{site=transfer}}"] == m.transfer_retries


def test_simulator_tracing_does_not_change_results():
    import dataclasses
    from repro.configs import get_config
    from repro.core.simulator import SHAREGPT_4O, simulate
    model = get_config("openpangu-7b-vl")
    ds = dataclasses.replace(SHAREGPT_4O, mm_fraction=0.5)
    kw = dict(rate=8.0, n_requests=16, seed=3, kv_page_tokens=16)
    off = simulate(model, "E-P-D", ds, **kw)
    tr = Tracer(enabled=True)
    on = simulate(model, "E-P-D", ds, tracer=tr, **kw)
    assert on.mean_ttft_ms == off.mean_ttft_ms
    assert on.p99_tpot_ms == off.p99_tpot_ms
    assert on.makespan == off.makespan
    assert len(tr.spans) > 0
    tr.assert_balanced()
