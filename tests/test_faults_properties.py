"""Hypothesis chaos suite for the fault plane: injector replay
determinism, transfer-recovery payload conservation, swap-tier loss
under arbitrary pool interleavings, engine-level swap-loss recovery
during preempt/resume chaos, and full-cluster runs under random
per-site fault rates — through every arm, page refcounts and swap
handles must balance and no request may be silently dropped. Honors
HYPOTHESIS_PROFILE=ci (conftest)."""
import pytest

hypothesis = pytest.importorskip("hypothesis")
import hypothesis.strategies as st
from hypothesis import given, settings

from conftest import hyp_max_examples
from repro.core import kv_transfer as kt
from repro.core.faults import (DEFAULT_RETRY, NO_RETRY, SITE_DECODE_CRASH,
                               SITE_SWAP_IN, SITE_TRANSFER_HANDSHAKE,
                               SITE_TRANSFER_WIRE, SITES, FaultInjector,
                               FaultPlan, RetryPolicy, SwapLost,
                               TransferError)
from repro.serving.kv_pool import PagePool, PoolExhausted
from repro.serving.request import Request

SITE_LIST = sorted(SITES)


# ---------------------------------------------------------------------------
# injector: pure-function determinism under arbitrary plans
# ---------------------------------------------------------------------------

@settings(max_examples=hyp_max_examples(80), deadline=None)
@given(st.integers(0, 2**31), st.floats(0.0, 1.0),
       st.lists(st.tuples(st.integers(0, len(SITE_LIST) - 1),
                          st.integers(0, 5), st.integers(0, 3)),
                min_size=1, max_size=60))
def test_injector_is_pure_function_of_plan(seed, rate, calls):
    """Two injectors with the same plan agree on every decision, in any
    call order; fired count == number of True decisions; rate 0 never
    fires and rate 1 always fires (modulo the cap)."""
    plan = FaultPlan(seed=seed, rates={s: rate for s in SITE_LIST})
    a, b = FaultInjector(plan), FaultInjector(plan)
    seq = [(SITE_LIST[i], k, at) for i, k, at in calls]
    ra = [a.should_fail(s, key=k, attempt=at) for s, k, at in seq]
    rb = [b.should_fail(s, key=k, attempt=at)
          for s, k, at in reversed(seq)]
    assert ra == list(reversed(rb))
    assert a.n_fired() == sum(ra)
    if rate == 0.0:
        assert not any(ra)
    if rate == 1.0:
        assert all(ra)


# ---------------------------------------------------------------------------
# transfer recovery: payload conservation for every (plan, rates) draw
# ---------------------------------------------------------------------------

@settings(max_examples=hyp_max_examples(60), deadline=None)
@given(st.integers(0, 2**31), st.floats(0.0, 0.9), st.floats(0.0, 0.9),
       st.integers(1, 16), st.integers(0, 4), st.booleans())
def test_recover_plan_conserves_payload_or_raises_typed(
        seed, hs_rate, wire_rate, n_layers, group_size, replan):
    """For arbitrary fault rates, recover_plan either raises
    TransferError or returns a plan that delivers every source group
    exactly once, never touches the compute timeline, and only ever
    inflates latency — with the recovery record internally consistent."""
    p = kt.plan("grouped", n_layers=n_layers, bytes_per_layer=1e6,
                per_layer_compute=1e-3, handshake=1e-3, link_bw=1e9,
                group_size=group_size)
    inj = FaultInjector(FaultPlan(seed=seed, rates={
        SITE_TRANSFER_HANDSHAKE: hs_rate, SITE_TRANSFER_WIRE: wire_rate}))
    policy = RetryPolicy(max_attempts=3, backoff_base=1e-4, seed=seed)
    try:
        out, rec = kt.recover_plan(p, injector=inj, policy=policy,
                                   handshake=1e-3, link_bw=1e9,
                                   key=seed, replan=replan)
    except TransferError as e:
        assert e.site in (SITE_TRANSFER_HANDSHAKE, SITE_TRANSFER_WIRE)
        assert isinstance(e, RuntimeError)
        return
    assert sorted(g.start for g in out.groups) == \
        sorted(g.start for g in p.groups)
    assert abs(sum(g.nbytes for g in out.groups)
               - sum(g.nbytes for g in p.groups)) < 1e-6
    assert out.prefill_end == p.prefill_end
    assert out.kv_latency >= p.kv_latency
    assert out.exposed_latency >= p.exposed_latency
    assert rec.retries >= rec.faults
    assert rec.retry_time >= 0.0
    if rec.faults == 0:
        assert out is p
    # every delivered group lands no earlier than physics allows
    for g in out.groups:
        assert g.t_done >= g.t_ready


# ---------------------------------------------------------------------------
# swap tier: SwapLost under arbitrary pool interleavings
# ---------------------------------------------------------------------------

@settings(max_examples=hyp_max_examples(50), deadline=None)
@given(st.integers(0, 2**31), st.floats(0.0, 0.8),
       st.lists(st.tuples(st.sampled_from(["alloc", "free", "out", "in"]),
                          st.integers(0, 7), st.integers(1, 6)),
                min_size=1, max_size=40))
def test_pool_swap_loss_keeps_audit_balanced(seed, rate, ops):
    """Under random swap-in losses, a lost handle is consumed (no
    device pages allocated, host entry dropped) and the allocator /
    swap audit balances after every operation — no arm leaks."""
    inj = FaultInjector(FaultPlan(seed=seed, rates={SITE_SWAP_IN: rate}))
    pool = PagePool(33, 4, injector=inj)
    live, swapped, losses = {}, {}, 0
    rid = 0
    for op, pick, n in ops:
        if op == "alloc" and pool.n_free >= n:
            live[rid] = pool.alloc(n)
            rid += 1
        elif op == "free" and live:
            k = sorted(live)[pick % len(live)]
            pool.free(live.pop(k))
        elif op == "out" and live:
            k = sorted(live)[pick % len(live)]
            ids = live.pop(k)
            swapped[k] = pool.swap_out(ids, data=len(ids))
        elif op == "in" and swapped:
            k = sorted(swapped)[pick % len(swapped)]
            h = swapped[k]
            try:
                ids, data = pool.swap_in(h)
            except SwapLost as e:
                assert e.handle_id == h.handle_id
                assert e.n_pages == h.n_pages
                del swapped[k]              # consumed: must not be reused
                losses += 1
                with pytest.raises(ValueError):
                    pool.swap_in(h)
            except PoolExhausted:
                pass                        # handle stays valid
            else:
                assert data == len(ids) == h.n_pages
                del swapped[k]
                live[k] = ids
        pool.assert_balanced(live.values(),
                             swap_handles=swapped.values())
    assert pool.swap_lost_total == losses


# ---------------------------------------------------------------------------
# REAL engine: preempt/resume chaos with swap-loss recovery
# ---------------------------------------------------------------------------

_ENGINE = None


def _chaos_engine():
    global _ENGINE
    if _ENGINE is None:
        import jax
        from repro.configs import get_config
        from repro.models.model import init_params
        from repro.serving.engine import Engine
        cfg = get_config("smollm-135m").reduced()
        params = init_params(cfg, jax.random.PRNGKey(0))
        # prefix_cache gives the engine its suffix-prefill path, which
        # the swap-loss arm reuses for the §re-fault recompute
        _ENGINE = Engine(cfg, params, max_batch=2, max_len=32, paged=True,
                         page_size=4, prefix_cache=True, preemption=True,
                         n_pool_pages=24, faults=FaultInjector())
    return _ENGINE


def _reset(eng):
    from repro.serving.prefix_cache import PrefixCache
    for i, r in enumerate(eng.slots):
        if r is not None:
            eng.slots[i] = None
            eng._release_slot(i)
    for pr in eng.preempted:
        if pr.handle is not None:
            eng.pool.swap_free(pr.handle)
    eng.preempted.clear()
    eng._resume_marks.clear()
    eng.lost.clear()
    eng.prefix_cache.evict(eng.pool.n_pages)
    eng.prefix_cache = PrefixCache(eng.page_size, eng.pool)
    assert eng.pool.n_used == 0, "reset must drain the pool"
    assert eng.pool.n_swapped_pages == 0, "reset must drain the swap"


@settings(max_examples=hyp_max_examples(20), deadline=None)
@given(st.integers(0, 2**31), st.floats(0.0, 0.6),
       st.lists(st.tuples(
           st.sampled_from(["prefill", "insert", "decode", "preempt",
                            "resume"]),
           st.integers(0, 3), st.integers(1, 12)),
           min_size=1, max_size=12))
def test_engine_chaos_swap_loss_never_leaks_or_drops(seed, rate, ops):
    """Arbitrary interleavings of prefill / insert / decode / preempt /
    resume with a random swap-in loss rate: every SwapLost is absorbed
    by the suffix-recompute arm (or surfaced in eng.lost), the page /
    swap audit balances after every op, and at drain time every request
    that entered a slot is accounted live, finished, or lost — never
    silently gone."""
    eng = _chaos_engine()
    _reset(eng)
    eng.pool.injector = FaultInjector(
        FaultPlan(seed=seed, rates={SITE_SWAP_IN: rate}))
    pending, entered, finished = [], [], []
    try:
        for op, pick, ln in ops:
            if op == "prefill":
                prompt = [pick * 500 + j // 2 for j in range(ln)]
                r = Request(prompt_tokens=prompt, max_new_tokens=4)
                try:
                    f, p = eng.prefill_request(r)
                    pending.append((r, f, p))
                except RuntimeError:
                    pass                    # pool exhausted: atomic unwind
            elif op == "insert" and pending:
                r, f, p = pending.pop(pick % len(pending))
                try:
                    eng.insert(r, p, f)
                    entered.append(r)
                except RuntimeError:
                    pending.append((r, f, p))
            elif op == "decode" and eng.n_active:
                try:
                    for r, tok, done in eng.decode_step():
                        if done:
                            finished.append(r)
                except RuntimeError:
                    pass
            elif op == "preempt":
                active = [i for i, r in enumerate(eng.slots)
                          if r is not None]
                if active:
                    eng.preempt_slot(active[pick % len(active)])
            elif op == "resume":
                eng.try_resume()            # may take the SwapLost arm
            eng.assert_no_page_leaks(
                extra_holders=[p.page_ids for _, _, p in pending])
        # no silent drops: everything that entered a slot is live,
        # parked, finished, or surfaced as lost
        in_slots = [r for r in eng.slots if r is not None]
        parked = [pr.req for pr in eng.preempted]
        for r in entered:
            assert (any(r is x for x in in_slots)
                    or any(r is x for x in parked)
                    or any(r is x for x in finished)
                    or any(r is x for x in eng.lost)), \
                "request silently dropped"
        assert all(r.killed for r in eng.lost)
    finally:
        for _, _, p in pending:
            eng.release_payload(p)
        _reset(eng)
        eng.pool.injector = FaultInjector()


# ---------------------------------------------------------------------------
# REAL cluster: end-to-end chaos accounting
# ---------------------------------------------------------------------------

_CLUSTER_DEPS = None


def _cluster_deps():
    global _CLUSTER_DEPS
    if _CLUSTER_DEPS is None:
        import jax
        from repro.configs import get_config
        from repro.models.model import init_params
        cfg = get_config("smollm-135m").reduced()
        _CLUSTER_DEPS = (cfg, init_params(cfg, jax.random.PRNGKey(0)))
    return _CLUSTER_DEPS


@settings(max_examples=hyp_max_examples(8), deadline=None)
@given(st.integers(0, 2**31), st.floats(0.0, 0.15),
       st.integers(0, 6), st.booleans())
def test_cluster_chaos_accounting_closes(seed, wire_rate, crash_step,
                                         recovery):
    """A 2-decode-instance cluster under random wire-fault rates plus
    one armed mid-run crash: with recovery every completion is exact-
    length and losses are surfaced (never silent) — done + lost ==
    submitted — and the surviving engines end leak-free with the retry
    time accounted."""
    from repro.core.cluster import EPDCluster
    from repro.core.faults import ArmedFault
    cfg, params = _cluster_deps()
    plan = FaultPlan(
        seed=seed,
        rates={SITE_TRANSFER_WIRE: wire_rate},
        armed=[ArmedFault(SITE_DECODE_CRASH, key=(0, crash_step))])
    cl = EPDCluster(cfg, params, max_batch=2, max_len=64, paged=True,
                    page_size=8, prefix_cache=True, n_decode=2,
                    faults=plan, recovery=recovery)
    reqs = [Request(prompt_tokens=list(range(3 + i, 19 + i)),
                    max_new_tokens=6) for i in range(3)]
    for r in reqs:
        cl.submit(r)
    done = cl.run_until_done(max_steps=400)
    # accounting closes: every submitted request is done or lost
    assert len(done) + len(cl.report.lost) == len(reqs)
    assert all(r.killed for r in cl.report.lost)
    for r in done:
        assert len(r.output_tokens) == r.max_new_tokens
    if recovery:
        assert not cl.report.lost       # every arm healed
    assert cl.report.retry_time_total >= 0.0
    if cl.report.transfer_retries == 0 and cl.report.store_retries == 0:
        assert cl.report.retry_time_total == 0.0
    for i in cl.live_decode_indices():
        cl.decode_engines[i].assert_no_page_leaks()
    cl.prefill_engine.assert_no_page_leaks()
