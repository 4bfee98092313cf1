"""The trace reduction: device busy time, per-program and per-kernel
time, heaviest ops and idle gaps, on hand-made events and on a small
trace recorded on a TPU v5e (``trace_v5e.json``, device events of one
serving window cut to a few steps)."""
from pathlib import Path

import pytest

from bench import harness, trace_reduce as T

HERE = Path(__file__).resolve().parent


PALLAS = ', custom_call_target="tpu_custom_call"'
PALLAS_KIND = "custom-call:tpu_custom_call"
FLASH = f"jit_prefill_suffix_fn/{PALLAS_KIND}"
PAGED = f"jit_decode_fn/{PALLAS_KIND}"


def _ev(line, name, start, dur, dev="/device:TPU:0"):
    return T.Event(dev, line, name, float(start), float(dur))


def test_program_names_lose_their_ids():
    assert T.program("jit_decode_fn(12)") == "jit_decode_fn"
    assert T.program("jit_copy_fn.3") == "jit_copy_fn"
    assert T.program("jit_prefill_suffix_fn") == "jit_prefill_suffix_fn"


def test_attach_names_ops_and_their_programs():
    M, O = "XLA Modules", "XLA Ops"
    evs = T.attach([
        _ev(M, "jit_decode_fn(1)", 0, 100),
        _ev(O, "%closed_call.7 = bf16[2] custom-call(%a)" + PALLAS, 10, 5),
        _ev(O, "%fusion.3 = f32[4]{0} fusion(%b)", 150, 5)])
    assert (evs[1].name, evs[1].module, evs[1].kind) == (
        "closed_call.7", "jit_decode_fn", PALLAS_KIND)
    assert (evs[2].name, evs[2].module, evs[2].kind) == ("fusion.3", "",
                                                         "fusion")


def test_reduce_by_hand():
    M, O = "XLA Modules", "XLA Ops"
    kern = "%closed_call.2 = bf16[2] custom-call()" + PALLAS
    evs = T.attach([
        _ev(M, "jit_decode_fn(1)", 0, 100),
        _ev(O, "%while.1 = (s32[]) while(%t)", 0, 100),
        _ev(O, "%fusion.1 = f32[4] fusion(%x)", 0, 40),
        _ev(O, kern, 30, 50),
        _ev(M, "jit_copy_fn(4)", 150, 20),
        _ev(O, "%copy.1 = bf16[2] copy(%y)", 150, 20),
        _ev(M, "jit_decode_fn(1)", 200, 100),
        _ev(O, kern, 200, 60),
    ])
    r = T.reduce(evs)
    assert r.busy_s == pytest.approx((100 + 20 + 60) * 1e-9)   # op union
    assert r.programs == pytest.approx({"jit_decode_fn": 200e-9,
                                        "jit_copy_fn": 20e-9})
    assert r.executions == {"jit_decode_fn": 2, "jit_copy_fn": 1}
    assert r.by_kind == pytest.approx({
        f"jit_decode_fn/{PALLAS_KIND}": 110e-9,
        "jit_decode_fn/fusion": 40e-9, "jit_copy_fn/copy": 20e-9})
    assert r.calls_by_kind[f"jit_decode_fn/{PALLAS_KIND}"] == 2
    assert r.top_ops[0] == ["jit_decode_fn/closed_call.2",
                            pytest.approx(110e-9)]
    assert "jit_decode_fn/while.1" not in dict(r.top_ops)
    assert dict(r.idle_gaps) == pytest.approx({
        "jit_decode_fn -> jit_copy_fn": 50e-9,
        "jit_copy_fn -> jit_decode_fn": 30e-9})


def test_busy_time_is_the_mean_over_chips():
    O = "XLA Ops"
    evs = [_ev(O, "%a.1 = f32[1] add()", 0, 100, dev="/device:TPU:0"),
           _ev(O, "%a.1 = f32[1] add()", 0, 300, dev="/device:TPU:1")]
    assert T.reduce(T.attach(evs)).busy_s == pytest.approx(200e-9)


def test_saved_events_read_back(tmp_path):
    evs = T.attach([_ev("XLA Ops", "%x.1 = f32[1] add(%a)", 1, 2)])
    T.save(evs, str(tmp_path / "e.json"))
    assert T.load_json(str(tmp_path / "e.json")) == evs


def test_a_traced_run_reads_its_layers(tiny_cell, tmp_path, monkeypatch):
    """The --trace 1 path end to end on the CPU: the trace is taken and
    reduced, and the readers that need no device read their counts."""
    import time
    v5e = harness.peaks("TPU v5 lite")
    monkeypatch.setattr(harness, "peaks", lambda kind: v5e)
    cell = tiny_cell("deepseek-chat-batch48")
    out = harness.run_cell(cell, seed=5, seconds=1.5, trace=True,
                           t_process=time.perf_counter(), out_dir=tmp_path,
                           log=lambda s: None)
    assert out["correct"], out["check"]
    assert {"busy_s", "window_s"} <= set(out["device"])
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    assert 0 < out["metrics"]["decode_occupancy_pct"]["value"] <= 100
    assert not (tmp_path / "trace").exists()      # the trace is removed


def _recorded():
    doc = harness.read_json(HERE / "trace_v5e.json")
    return [T.Event(doc["device"], "XLA " + line, name, float(s), float(d))
            for line, name, s, d in doc["rows"]]


def test_recorded_trace_reduces():
    raw = _recorded()
    evs = T.attach(raw)
    ops = [e for e in evs if e.line == "XLA Ops"]
    assert all(e.module for e in ops)           # every op lies in a program
    r = T.reduce(evs)
    mods = [e for e in raw if e.line == "XLA Modules"]
    want = {}
    for e in mods:
        p = e.name.split("(")[0]
        want[p] = want.get(p, 0.0) + e.dur_ns * 1e-9
    assert r.programs == pytest.approx(want)
    for p in ("jit_prefill_suffix_fn", "jit_copy_fn", "jit_insert_fn",
              "jit_decode_fn"):
        assert r.executions[p] == 1
    # one Pallas kernel per layer of the 8-layer model in each program
    pallas = {k: n for k, n in r.calls_by_kind.items() if PALLAS_KIND in k}
    assert pallas == {FLASH: 8, PAGED: 8}
    assert 0 < r.by_kind[FLASH] < r.programs["jit_prefill_suffix_fn"]
    assert 0 < r.by_kind[PAGED] < r.programs["jit_decode_fn"]
    span = (max(e.start_ns + e.dur_ns for e in raw)
            - min(e.start_ns for e in raw)) * 1e-9
    assert sum(want.values()) * 0.99 <= r.busy_s <= span
    assert r.idle_gaps[0][0] == "jit__argmax -> jit_copy_fn"
    assert all(not n.split("/")[1].startswith("while") for n, _ in r.top_ops)


def test_recorded_flash_window_is_under_its_roofline():
    """The traced window is the last prefill window of a 2.9k-token
    image request; counted at the fewest keys it can hold (the window
    from 2560 to at most 2896 is at most 336 tokens), its share of the
    roofline stays below 100%."""
    from bench import work
    r = T.reduce(T.attach(_recorded()))
    d = work.Dims(8, 4096, 32, 8, 128, 14336, 32064, 1024)
    flops, nbytes = work.flash_chunk(d, 2560, 2896)
    least, bound = work.roofline_s(8 * flops, 8 * nbytes, 197e12, 819e9)
    assert bound == "compute"
    assert 0 < least / r.by_kind[FLASH] < 1


def test_recording_cuts_names_and_keeps_whole_programs():
    from bench.tests import record_trace as R
    line = ('%fusion.3 = bf16[16,128]{1,0} custom-call(%a, %b), '
            'custom_call_target="tpu_custom_call", backend_config="x"')
    assert R.cut(line) == ('%fusion.3 = bf16[16,128]{1,0} custom-call(), '
                           'custom_call_target="tpu_custom_call"')
    raw = _recorded()
    device, rows = R.rows(raw, "jit_decode_fn", 0, 1)
    assert device == "/device:TPU:0"
    assert [r[1].split("(")[0] for r in rows if r[0] == "Modules"] == [
        "jit_decode_fn"]
    assert rows[0][2] == 0 and len(rows) > 8
