"""Continuous batching: the iteration-level scheduler (core.batching),
the fused-engine continuous driver (Engine.submit/step/drain_continuous)
and the disaggregated cluster driver (EPDCluster.run_continuous).

The load-bearing property is the PR's hard constraint: continuous-
batched greedy outputs are BIT-IDENTICAL to the serial per-request path
across {paged, prefix_cache, chunked_prefill, preemption, multimodal}
configurations — both drivers execute the same PrefillTask chunk
sequence and the same jitted forwards, so any divergence is a real
scheduling bug, not numerics."""
import jax
import pytest

from repro.configs import get_config
from repro.core.batching import (BatchPlan, IterationScheduler, PrefillJob,
                                 StreamTimeline)
from repro.core.cluster import EPDCluster
from repro.models.model import init_params
from repro.serving.engine import Engine
from repro.serving.request import Request


# ---------------------------------------------------------------------------
# scheduler unit tests (no jax, no engines)
# ---------------------------------------------------------------------------

def _job(n_tokens=32, chunk=16, **kw):
    return PrefillJob(req=Request(prompt_tokens=list(range(n_tokens)),
                                  max_new_tokens=4),
                      n_tokens=n_tokens, chunk=chunk, **kw)


def test_plan_interleaves_round_robin():
    s = IterationScheduler(max_live_prefills=2)
    a, b, c = _job(), _job(), _job()
    for j in (a, b, c):
        s.submit(j)
    p1 = s.plan()
    # live window caps concurrent prefills; both live jobs get a chunk
    assert p1.chunks in ([a, b], [b, a])
    assert c in s.waiting
    p2 = s.plan()
    # round-robin cursor rotates the chunk order across steps
    assert p2.chunks[0] is not p1.chunks[0]


def test_admission_fifo_capped_and_requeue():
    s = IterationScheduler()
    jobs = [_job() for _ in range(3)]
    for j in jobs:
        s.submit(j)
        s.plan()                               # promote to live
    for j in list(s.live):
        j.result = (0, None)
        s.mark_ready(j)
    p = s.plan(free_slots=2)
    assert p.admit == jobs[:2]                 # FIFO, capped at free slots
    assert p.decode                            # an admission decodes this step
    s.requeue_ready(p.admit[0])
    assert s.ready[0] is jobs[0]               # back at the head, no overtake
    assert s.stall_counts["admission"] == 1


def test_barriers_gate_chunks_and_idle_jump():
    s = IterationScheduler()
    late = _job(ready_at=5.0)
    img = _job(feature_ready_at=3.0)
    img.req.mm_payload = b"x"
    img.req.mm_tokens = 8
    img.req.mm_pos = 2                          # run starts inside chunk 0
    txt = _job()
    for j in (late, img, txt):
        s.submit(j)
    p = s.plan(now=0.0)
    assert p.chunks == [txt]
    reasons = dict((id(j), r) for j, r in p.stalled)
    assert reasons[id(late)] == "sync_barrier"
    assert reasons[id(img)] == "feature_barrier"
    p = s.plan(now=5.0)
    assert set(map(id, p.chunks)) == {id(late), id(img), id(txt)}


def test_next_barrier_time_is_idle_jump_target():
    # only barrier-stalled jobs live: the plan comes back empty and the
    # earliest arrival is where the executor jumps the modeled clock
    s = IterationScheduler()
    late = _job(ready_at=5.0)
    img = _job(feature_ready_at=3.0)
    img.req.mm_payload = b"x"
    img.req.mm_tokens = 8
    img.req.mm_pos = 2
    s.submit(late)
    s.submit(img)
    p = s.plan(now=0.0)
    assert p.empty
    assert s.next_barrier_time() == 3.0


def test_pre_image_text_chunks_ignore_feature_barrier():
    # image run starts in chunk 1: chunk 0 (pure text) may run before
    # the feature lands — the E->P barrier is a dependency edge on the
    # overlapping chunk only
    j = _job(n_tokens=32, chunk=16, feature_ready_at=9.0)
    j.req.mm_payload = b"x"
    j.req.mm_tokens = 8
    j.req.mm_pos = 20
    assert j.blocked_reason(now=0.0) is None


def test_chunk_budget_limits_iteration_tokens():
    s = IterationScheduler(max_live_prefills=4, chunk_budget_tokens=20)
    jobs = [_job(chunk=16) for _ in range(3)]
    for j in jobs:
        s.submit(j)
    p = s.plan()
    assert len(p.chunks) == 1                  # 16 fits, 32 would not
    assert any(r == "budget" for _, r in p.stalled)


def test_stream_timeline_fused_vs_streams():
    tl = StreamTimeline()
    tl.charge_prefill(2.0)
    tl.charge_decode(1.0)
    assert tl.makespan == 2.0                  # separate devices: max
    t = tl.charge_decode(1.0, not_before=5.0)  # dependency edge
    assert t == 6.0
    fused = StreamTimeline(fused=True)
    fused.charge_prefill(2.0)
    fused.charge_decode(1.0)
    assert fused.makespan == 3.0               # one device: sum


def test_batch_plan_empty_and_token_count():
    p = BatchPlan(step=1)
    assert p.empty
    p.chunks.append(_job(n_tokens=40, chunk=16))
    assert p.prefill_tokens == 16
    assert not p.empty


# ---------------------------------------------------------------------------
# fused-engine parity matrix: continuous == serial, bit-for-bit
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def smollm():
    cfg = get_config("smollm-135m").reduced()
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


PROMPTS = [list(range(1, 30)), list(range(5, 17)),
           list(range(2, 50)), [7, 8, 9],
           list(range(2, 50)),                 # exact repeat (prefix hit)
           list(range(40, 11, -1))]


def _engine(cfg, params, **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_len", 64)
    kw.setdefault("paged", True)
    kw.setdefault("page_size", 8)
    return Engine(cfg, params, **kw)


def _serial_outputs(cfg, params, prompts, n=6, **kw):
    eng = _engine(cfg, params, **kw)
    return [eng.run_request(Request(prompt_tokens=p, max_new_tokens=n))
            for p in prompts]


@pytest.mark.parametrize("mode", ["chunked", "prefix", "chunked_prefix",
                                  "chunked_preempt"])
def test_continuous_matches_serial_matrix(smollm, mode):
    cfg, params = smollm
    kw = dict(
        chunked=dict(chunked_prefill=True, prefill_chunk=16),
        prefix=dict(prefix_cache=True),
        chunked_prefix=dict(chunked_prefill=True, prefill_chunk=16,
                            prefix_cache=True),
        chunked_preempt=dict(chunked_prefill=True, prefill_chunk=16,
                             preemption=True,
                             n_pool_pages=1 + 3 * 8),
    )[mode]
    serial = _serial_outputs(cfg, params, PROMPTS, **kw)
    eng = _engine(cfg, params, **kw)
    reqs = [Request(prompt_tokens=p, max_new_tokens=6) for p in PROMPTS]
    for r in reqs:
        eng.submit(r)
    eng.drain_continuous()
    assert [r.output_tokens for r in reqs] == serial
    eng.assert_no_page_leaks()
    assert eng.scheduler.steps > 0
    if mode == "chunked_preempt":
        # the tight pool forces scheduler-driven stalls/preemption at
        # least once — and the audit above proves nothing leaked
        assert (eng.preempt_count > 0
                or eng.scheduler.stall_counts.get("pool", 0) > 0
                or eng.scheduler.stall_counts.get("admission", 0) > 0)


def test_continuous_staggered_arrivals_mid_stream(smollm):
    """Requests submitted while earlier ones are mid-prefill/mid-decode
    (the continuous-batching point) still match the serial outputs."""
    cfg, params = smollm
    kw = dict(chunked_prefill=True, prefill_chunk=16, prefix_cache=True)
    serial = _serial_outputs(cfg, params, PROMPTS, **kw)
    eng = _engine(cfg, params, **kw)
    reqs = [Request(prompt_tokens=p, max_new_tokens=6) for p in PROMPTS]
    for r in reqs[:2]:
        eng.submit(r)
    for _ in range(3):                        # some chunks + admissions run
        eng.step()
    for r in reqs[2:]:                        # late arrivals join mid-stream
        eng.submit(r)
    eng.drain_continuous()
    assert [r.output_tokens for r in reqs] == serial
    eng.assert_no_page_leaks()


def test_mid_drain_leak_audit_under_pressure(smollm):
    """assert_balanced holds at EVERY iteration boundary while the
    scheduler stalls, admits, and preempts against a tight pool —
    in-flight tasks and ready payloads are first-class page holders."""
    cfg, params = smollm
    eng = _engine(cfg, params, max_batch=2, chunked_prefill=True,
                  prefill_chunk=16, preemption=True,
                  n_pool_pages=1 + 4 * 8)
    reqs = [Request(prompt_tokens=p, max_new_tokens=5)
            for p in PROMPTS[:4]]
    for r in reqs:
        eng.submit(r)
    steps = 0
    while eng.scheduler.has_work or eng.n_active or eng.preempted:
        eng.step()
        eng.assert_no_page_leaks()
        steps += 1
        assert steps < 500
    assert all(len(r.output_tokens) == 5 for r in reqs)


# ---------------------------------------------------------------------------
# disaggregated cluster: run_continuous == submit/run_until_done
# ---------------------------------------------------------------------------

def _cluster(cfg, params, **kw):
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_len", 64)
    kw.setdefault("paged", True)
    kw.setdefault("page_size", 8)
    kw.setdefault("chunked_prefill", True)
    kw.setdefault("prefill_chunk", 16)
    return EPDCluster(cfg, params, **kw)


def test_cluster_continuous_matches_serial(smollm):
    cfg, params = smollm
    cl = _cluster(cfg, params, prefix_cache=True)
    reqs = [Request(prompt_tokens=p, max_new_tokens=6) for p in PROMPTS]
    for r in reqs:
        cl.submit(r)
    cl.run_until_done()
    serial = [r.output_tokens for r in reqs]

    cl2 = _cluster(cfg, params, prefix_cache=True)
    reqs2 = [Request(prompt_tokens=p, max_new_tokens=6) for p in PROMPTS]
    done = cl2.run_continuous(reqs2)
    assert [r.output_tokens for r in reqs2] == serial
    assert len(done) == len(reqs2)
    cl2.prefill_engine.assert_no_page_leaks()
    for d in cl2.decode_engines:
        d.assert_no_page_leaks()
    # ground-truth Router: the drained P instance reads idle and its
    # per-request pending ledger fully conserved back to zero
    st = cl2.router.status[cl2.prefill_engine.name]
    assert st.pending_tokens == 0.0
    assert st.pending_by_req == {}
    assert st.load(cl2.continuous_timeline.makespan) == pytest.approx(
        0.0, abs=1e-9)


def test_cluster_continuous_multimodal_text_mix(smollm):
    """VLM + text mix through the full E->P->D loop: the async E->P
    feature barrier is a real dependency edge, yet outputs stay
    bit-identical to the serial driver."""
    cfg = get_config("llava-next-mistral-7b").reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))

    def reqs():
        return [Request(prompt_tokens=list(range(1, 20)), max_new_tokens=5,
                        mm_payload=b"imgA", mm_tokens=8, mm_pos=4),
                Request(prompt_tokens=list(range(3, 30)), max_new_tokens=5),
                Request(prompt_tokens=list(range(1, 20)), max_new_tokens=5,
                        mm_payload=b"imgA", mm_tokens=8, mm_pos=4),
                Request(prompt_tokens=list(range(9, 40)), max_new_tokens=4)]

    cl = _cluster(cfg, params, max_batch=2, prefix_cache=True,
                  ep_overlap="async")
    rs = reqs()
    for r in rs:
        cl.submit(r)
    cl.run_until_done()
    serial = [r.output_tokens for r in rs]

    cl2 = _cluster(cfg, params, max_batch=2, prefix_cache=True,
                   ep_overlap="async")
    rs2 = reqs()
    cl2.run_continuous(rs2)
    assert [r.output_tokens for r in rs2] == serial
    cl2.prefill_engine.assert_no_page_leaks()
    for d in cl2.decode_engines:
        d.assert_no_page_leaks()


def test_cluster_continuous_accepts_fault_plans(smollm):
    """The fault-plan guard is gone: run_continuous composes with the
    chaos layer. Under seeded wire loss every request still completes
    bit-identical to the zero-fault run (deeper matrix lives in
    tests/test_batching_faults.py)."""
    cfg, params = smollm
    from repro.core.faults import SITE_TRANSFER_WIRE, FaultPlan
    cl0 = _cluster(cfg, params, prefix_cache=True)
    ref = [Request(prompt_tokens=p, max_new_tokens=6) for p in PROMPTS]
    cl0.run_continuous(ref)

    plan = FaultPlan(seed=7, rates={SITE_TRANSFER_WIRE: 0.3})
    cl = _cluster(cfg, params, prefix_cache=True, faults=plan)
    reqs = [Request(prompt_tokens=p, max_new_tokens=6) for p in PROMPTS]
    done = cl.run_continuous(reqs)
    assert len(done) == len(reqs) and not cl.report.lost
    assert [r.output_tokens for r in reqs] == \
        [r.output_tokens for r in ref]
    cl.prefill_engine.assert_no_page_leaks()
    for d in cl.decode_engines:
        d.assert_no_page_leaks()


# ---------------------------------------------------------------------------
# serving-loop spans: the host's clock, measured intervals only
# ---------------------------------------------------------------------------

def _mm_reqs():
    return [Request(prompt_tokens=list(range(1, 20)), max_new_tokens=5,
                    mm_payload=b"imgA", mm_tokens=8, mm_pos=4),
            Request(prompt_tokens=list(range(3, 30)), max_new_tokens=5),
            Request(prompt_tokens=list(range(1, 24)), max_new_tokens=6,
                    mm_payload=b"imgB", mm_tokens=8, mm_pos=4),
            Request(prompt_tokens=list(range(9, 40)), max_new_tokens=4)]


@pytest.fixture(scope="module")
def llava():
    cfg = get_config("llava-next-mistral-7b").reduced()
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def traced_run(llava):
    """One traced continuous run of an image + text mix: the cluster,
    its requests, the run's host-clock start and its iterations."""
    import time
    from types import SimpleNamespace
    from repro.core.telemetry import Tracer
    cfg, params = llava
    cl = _cluster(cfg, params, max_batch=2, prefix_cache=True,
                  ep_overlap="async", tracer=Tracer(enabled=True))
    reqs = _mm_reqs()
    iters = []
    t0 = time.perf_counter()
    done = cl.run_continuous(reqs, on_step=iters.append)
    assert len(done) == len(reqs)
    cl.tracer.assert_balanced()
    return SimpleNamespace(cl=cl, reqs=reqs, t0=t0, n_iters=len(iters))


def test_every_decode_step_has_its_phase_spans(traced_run):
    cl = traced_run.cl
    eng = cl.decode_engines[0]
    spans = [s for s in cl.tracer.spans if s.track == eng.name]
    steps = [s for s in spans if s.name == "decode.step"]
    assert len(steps) == eng._decode_steps > 0
    phases = ("decode.len_sync", "decode.grow_pages", "decode.key_split",
              "decode.dispatch", "decode.readback", "decode.commit")
    children = [s for s in spans if s.name.startswith("decode.")
                and s.name != "decode.step"]
    assert {s.name for s in children} == set(phases)
    assert all(s.parent == "decode.step" for s in children)
    for st in steps:
        inside = [s.name for s in children
                  if st.start <= s.start and s.end <= st.end]
        assert inside == list(phases)          # once each, in order


def test_serving_loop_phases_are_spans(traced_run):
    """Each iteration of run_continuous records its plan, its step, its
    bookkeeping and the caller's hook, all on the router's track."""
    cl, n_iters = traced_run.cl, traced_run.n_iters
    count = {}
    for s in cl.tracer.spans:
        if s.track == "router":
            count[s.name] = count.get(s.name, 0) + 1
    for name in ("sched.plan", "sched.step", "loop.bookkeeping",
                 "loop.on_step"):
        assert count[name] == n_iters, name
    assert count["queue.wait"] == 4


def test_one_queue_wait_per_request_ends_at_its_first_chunk(traced_run):
    spans = traced_run.cl.tracer.spans
    for r in traced_run.reqs:
        waits = [s for s in spans if s.name == "queue.wait"
                 and s.request_id == r.request_id]
        chunks = [s for s in spans if s.name == "prefill.chunk"
                  and s.request_id == r.request_id]
        assert len(waits) == 1 and chunks
        assert traced_run.t0 <= waits[0].start <= waits[0].end \
            <= chunks[0].start


def test_image_features_are_fetched_under_a_span(traced_run):
    fetched = {s.request_id for s in traced_run.cl.tracer.spans
               if s.name == "ep.fetch"}
    assert fetched == {r.request_id for r in traced_run.reqs
                       if r.mm_payload}


@pytest.mark.parametrize("driver", ["cluster", "engine"])
def test_stall_counter_matches_stall_counts(smollm, llava, driver):
    """Every stall the scheduler notes also lands in the registry under
    ``sched_stalls_total{reason=...}``, reason by reason."""
    if driver == "cluster":
        cfg, params = llava
        cl = _cluster(cfg, params, max_batch=2, prefix_cache=True,
                      ep_overlap="async")
        cl.run_continuous(_mm_reqs())
        sched, metrics = cl.continuous_scheduler, cl.metrics
    else:
        cfg, params = smollm
        eng = _engine(cfg, params, chunked_prefill=True, prefill_chunk=16)
        for k, p in enumerate(PROMPTS):
            # request k waits for clock k; the clock steps by a quarter
            eng.submit(Request(prompt_tokens=p, max_new_tokens=6),
                       ready_at=float(k))
        clock = iter(range(10_000))
        eng.drain_continuous(now_fn=lambda: float(next(clock)) / 4)
        sched, metrics = eng.scheduler, eng.metrics
    assert sched.stall_counts
    for reason, n in sched.stall_counts.items():
        assert metrics.value("sched_stalls_total", reason=reason) == n
    assert metrics.total("sched_stalls_total") == \
        sum(sched.stall_counts.values())


@pytest.mark.parametrize("driver", ["serial", "continuous"])
def test_real_cluster_spans_are_measured_on_the_host_clock(llava, driver):
    """Under faults that charge modeled transfer exposure and backoff
    (which the accountant adds to its own clock), every span still lies
    inside the run's host-clock interval, and none is a modeled
    transfer, prefetch or retry interval."""
    import time
    from repro.core.faults import (SITE_STORE_FETCH,
                                   SITE_TRANSFER_HANDSHAKE,
                                   SITE_TRANSFER_WIRE, FaultPlan)
    from repro.core.telemetry import Tracer
    cfg, params = llava
    plan = FaultPlan(seed=5, rates={SITE_TRANSFER_WIRE: 0.4,
                                    SITE_TRANSFER_HANDSHAKE: 0.3,
                                    SITE_STORE_FETCH: 0.5})
    tr = Tracer(enabled=True)
    cl = _cluster(cfg, params, max_batch=2, prefix_cache=True,
                  ep_overlap="async", faults=plan, tracer=tr)
    reqs = _mm_reqs()
    t0 = time.perf_counter()
    if driver == "serial":
        for r in reqs:
            cl.submit(r)
        done = cl.run_until_done()
    else:
        done = cl.run_continuous(reqs)
    t1 = time.perf_counter()
    assert len(done) == len(reqs)
    # the faults drew: modeled retry and transfer time was charged
    assert cl.report.retry_time_total > 0
    assert cl.acc.component_total("transfer") > 0
    assert tr.spans
    for s in tr.spans:
        assert t0 <= s.start <= s.end <= t1, s
    modeled = {"kv.handshake", "kv.wire", "ep.prefetch"}
    assert not [s for s in tr.spans
                if s.name in modeled or s.name.startswith(("retry.",
                                                           "kv.retry."))]


def test_simulator_still_records_modeled_transfer_spans():
    from repro.core.simulator import SHAREGPT_4O, simulate
    from repro.core.telemetry import Tracer
    tr = Tracer(enabled=True)
    simulate(get_config("deepseek-7b"), "E-P-D", SHAREGPT_4O, rate=2.0,
             n_requests=4, seed=3, kv_page_tokens=16, tracer=tr)
    names = {s.name for s in tr.spans}
    assert {"kv.handshake", "kv.wire"} <= names
