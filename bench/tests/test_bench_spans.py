"""Device-idle time put down to the serving program's host spans
(``bench/host_spans.py``), on hand-made events and on a few decode steps
recorded on a TPU v5e (``trace_v5e_spans.json``), and the reader of the
scheduler's stall share."""
from pathlib import Path

import pytest

from bench import harness, host_spans as H, stats, trace_reduce as T

HERE = Path(__file__).resolve().parent
M, O = "XLA Modules", "XLA Ops"


def _dev(line, name, start, dur, dev="/device:TPU:0"):
    return T.Event(dev, line, name, float(start), float(dur))


def _span(name, start, dur):
    return T.Event("/host:CPU", "python", name, float(start), float(dur))


def test_a_gap_under_one_span():
    evs = [_dev(O, "a", 0, 10), _span("decode.key_split", 8, 20),
           _dev(O, "b", 25, 5)]
    assert H.idle_by_span(evs) == pytest.approx({"decode.key_split": 15e-9})


def test_the_innermost_span_takes_the_gap():
    """An idle gap from 10 to 40 under decode.step, whose child
    decode.grow_pages is open from 15 to 30."""
    evs = [_dev(O, "a", 0, 10), _dev(O, "b", 40, 10),
           _span("decode.step", 5, 50), _span("decode.grow_pages", 15, 15)]
    assert H.idle_by_span(evs, lo=0, hi=50) == pytest.approx({
        "decode.step": 15e-9, "decode.grow_pages": 15e-9})


def test_a_gap_under_no_span_is_untraced():
    evs = [_dev(O, "a", 0, 10), _span("loop.on_step", 12, 3),
           _dev(O, "b", 20, 10)]
    assert H.idle_by_span(evs) == pytest.approx({
        H.UNTRACED: 7e-9, "loop.on_step": 3e-9})


def test_two_devices_give_their_mean():
    """Device 1 idles from 10 to 30 under the span, device 0 never."""
    evs = [_dev(O, "a", 0, 30, dev="/device:TPU:0"),
           _dev(O, "a", 0, 10, dev="/device:TPU:1"),
           _span("sched.plan", 5, 30)]
    assert H.idle_by_span(evs, lo=0, hi=30) == pytest.approx(
        {"sched.plan": 10e-9})


def test_a_window_bounds_the_gaps():
    evs = [_dev(O, "a", 10, 10), _span("sched.plan", 0, 40)]
    assert H.idle_by_span(evs, lo=5, hi=30) == pytest.approx(
        {"sched.plan": 15e-9})


def _ctx(counts):
    ctx = harness.Context(stats.Window(start=0.0), setup_s=1.0)
    ctx.counts = counts
    return ctx


def test_stall_share_reads_the_scheduler_counters():
    read = harness.reader("prefill_stall_pct").read
    assert read(_ctx({"sched_stalls_total": 45.0,
                      "sched_chunks_total": 5.0})) == pytest.approx(90.0)
    # the stall counter is made at the first stall: chunks without it
    # read no stall
    assert read(_ctx({"sched_chunks_total": 5.0})) == 0.0
    assert read(_ctx({"sched_stalls_total": 2.0})) == pytest.approx(100.0)
    assert read(_ctx({})) is None
    assert read(_ctx({"sched_stalls_total": 0.0})) is None
    assert read(_ctx(None)) is None


def _recorded():
    doc = harness.read_json(HERE / "trace_v5e_spans.json")
    dev = [T.Event(doc["device"], "XLA " + line, name, float(s), float(d))
           for line, name, s, d in doc["rows"] if line != "Span"]
    spans = [_span(name, s, d) for line, name, s, d in doc["rows"]
             if line == "Span"]
    return doc["window_ns"], T.attach(dev), spans


def test_recorded_idle_parts_sum_to_the_idle_time():
    window, dev, spans = _recorded()
    parts = H.idle_by_span(dev + spans, lo=0.0, hi=window)
    idle = window * 1e-9 - T.reduce(dev).busy_s
    assert idle > 0
    assert sum(parts.values()) == pytest.approx(idle, abs=1e-6)
    # the serving loop's spans cover the recorded steps' idle time
    assert parts.get(H.UNTRACED, 0.0) <= 0.1 * idle


def test_recorded_decode_runs_after_its_dispatch_span():
    """Host spans and device programs share the profiler's clock: every
    decode program starts no earlier than the ``decode.dispatch`` span
    that issued it (within 0.1 ms), and before the next one."""
    _, dev, spans = _recorded()
    runs = sorted(e.start_ns for e in dev if e.line == M
                  and T.program(e.name) == "jit_decode_fn")
    disp = sorted(s.start_ns for s in spans if s.name == "decode.dispatch")
    assert len(runs) == len(disp) >= 2
    for k, t in enumerate(runs):
        assert t >= disp[k] - 1e5
        assert k + 1 == len(disp) or t < disp[k + 1]


def test_recording_cuts_whole_decode_steps():
    """The recorder's cut: from the nth ``decode.step`` span to the end
    of the last step asked for, widened to whole device events."""
    from bench.tests import record_spans as R
    window, dev, spans = _recorded()
    raw = [T.Event(e.device, e.line,
                   e.name if e.line == M else f"%{e.name} = x {e.kind}()",
                   e.start_ns, e.dur_ns) for e in dev]
    device, length, rows = R.rows(raw, spans, 1, 2)
    assert device == "/device:TPU:0" and 0 < length < window
    steps = [r for r in rows if r[:2] == ["Span", "decode.step"]]
    assert len(steps) == 2 and steps[0][2] >= 0
    assert all(r[2] >= 0 and r[2] + r[3] <= length
               for r in rows if r[0] != "Span")
    runs = [r for r in rows if r[0] == "Modules"
            and T.program(r[1]) == "jit_decode_fn"]
    assert len(runs) == 2


def test_decode_sample_leaves_the_served_spans_alone(tiny_cell):
    """``Tracer.decode_sample`` thins only the simulator's spans: the
    real cluster records the same spans with it at its default and at
    one in 2**62 decode steps."""
    import jax.numpy as jnp
    from repro.core.cluster import EPDCluster
    from repro.core.telemetry import Tracer

    cell = tiny_cell("deepseek-chat-batch48")
    doc, traffic = cell.config, cell.traffic
    cfg = harness.model_config(doc)
    params = harness.make_weights(cfg, 3, jnp.dtype(doc["dtype"]),
                                  harness.model_module(doc))
    gen = harness.generator(traffic)

    def spans(tracer):
        specs = next(gen.bursts(traffic, 3, vocab=cfg.vocab, image_tokens=0,
                                max_len=doc["serving"]["max_len"]))[:3]
        reqs = [harness.to_request(s, 0) for s in specs]
        first = min(r.request_id for r in reqs)
        EPDCluster(cfg, params, tracer=tracer,
                   **doc["serving"]).run_continuous(reqs)
        return [(s.name, s.track, s.parent, s.attrs,
                 None if s.request_id is None else s.request_id - first)
                for s in tracer.spans]

    got = spans(Tracer(enabled=True))
    assert any(name == "decode.step" for name, *_ in got)
    assert spans(Tracer(enabled=True, decode_sample=1 << 62)) == got
