"""Encode stage on the serving path: MM Store correctness fixes
(dedup-put reconciliation, oversized-entry eviction, pin/unpin),
EPPrefetcher announce/fire race handling, the EncodeEngine itself, and
the cluster-level E->P overlap arms (async / sync / inline) — which must
be bit-identical in output and differ only in modeled accounting."""
import random

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.cluster import EPDCluster
from repro.core.costmodel import CostModel
from repro.core.ep_prefetch import EPPrefetcher
from repro.core.events import EventLoop
from repro.core.mm_store import MMStore
from repro.core.telemetry import Tracer
from repro.models import frontend as FE
from repro.models.model import init_params
from repro.serving.encode_engine import EncodeEngine
from repro.serving.engine import Engine
from repro.serving.request import Request


@pytest.fixture(scope="module")
def llava():
    cfg = get_config("llava-next-mistral-7b").reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params


# ---------------------------------------------------------------------------
# MM Store bugfixes
# ---------------------------------------------------------------------------

def test_dedup_put_updates_value_and_bytes():
    """A re-put under a known key must adopt the new tuple and reconcile
    byte accounting (the old code silently kept the stale value AND the
    stale size)."""
    s = MMStore()
    s.put("k", "old", 100)
    s.put("k", "new", 60)
    assert s.get("k", record=False) == "new"
    assert s.nbytes("k") == 60
    assert s.stats.bytes_stored == 60 == s.resident_bytes()
    assert s.stats.dedup_puts == 1 and s.stats.puts == 1


def test_dedup_put_growth_reconverges_budget():
    """A dedup re-put that GROWS the entry can push the store over
    budget — eviction must reconverge (and the re-put key, freshly
    touched, must not be the LRU victim)."""
    s = MMStore(capacity_bytes=200)
    s.put("k1", b"a", 100)
    s.put("k2", b"b", 100)
    s.put("k1", b"A", 180)          # 280 > 200 -> evict LRU (k2)
    assert s.contains("k1") and not s.contains("k2")
    assert s.stats.bytes_stored == 180 == s.resident_bytes()
    assert s.stats.evictions == 1


def test_oversized_new_put_rejected():
    """An entry that alone exceeds capacity can never fit: admitting it
    would hold bytes_stored above budget forever (the old `len > 1`
    eviction guard did exactly that). It must be rejected and counted."""
    s = MMStore(capacity_bytes=100)
    s.put("big", b"x", 150)
    assert len(s) == 0 and s.stats.bytes_stored == 0
    assert s.stats.rejected_puts == 1 and s.stats.puts == 0


def test_single_oversized_entry_is_evicted_not_retained():
    """The `len > 1` guard retained a lone over-budget entry forever.
    Grow an admitted entry past capacity via the dedup-put path: the
    evictor must now evict down to an EMPTY store rather than hold it."""
    s = MMStore(capacity_bytes=100)
    s.put("k", b"a", 50)
    s.put("k", b"A" * 3, 150)       # dedup-put grows past budget
    assert len(s) == 0
    assert s.stats.bytes_stored == 0 == s.resident_bytes()
    assert s.stats.evictions == 1


def test_pin_exempts_from_eviction_until_unpin():
    s = MMStore(capacity_bytes=100)
    s.put("k1", b"a", 60)
    assert s.pin("k1")
    s.put("k2", b"b", 60)           # over budget; k1 pinned -> k2 evicted
    assert s.contains("k1") and not s.contains("k2")
    s.unpin("k1")
    s.put("k3", b"c", 60)           # k1 evictable again -> k1 evicted
    assert s.contains("k3") and not s.contains("k1")
    assert s.stats.bytes_stored == 60 == s.resident_bytes()
    assert not s.pin("absent")      # nothing to pin


def test_unpin_reconverges_held_over_budget_store():
    """Pins may legitimately hold the store above budget; the release
    must immediately reconverge."""
    s = MMStore(capacity_bytes=100)
    s.put("k", b"a", 80)
    s.pin("k")
    s.put("k", b"A", 150)           # grown over budget but pinned: held
    assert s.contains("k") and s.stats.bytes_stored == 150
    s.unpin("k")
    assert len(s) == 0 and s.stats.bytes_stored == 0
    assert s.stats.evictions == 1


def test_store_bytes_invariant_random_ops():
    """bytes_stored == sum of resident entry sizes under arbitrary
    interleavings of put / dedup-put / get / pin / unpin (seeded
    deterministic sweep; the hypothesis variant below widens it)."""
    rng = random.Random(0)
    for cap in (None, 64, 256, 1024):
        s = MMStore(capacity_bytes=cap)
        pins = []
        for _ in range(400):
            op = rng.randrange(5)
            key = f"k{rng.randrange(8)}"
            if op == 0:
                s.put(key, b"v", rng.randrange(1, 200))
            elif op == 1:
                s.get(key, record=bool(rng.randrange(2)))
            elif op == 2:
                if s.pin(key):
                    pins.append(key)
            elif op == 3 and pins:
                s.unpin(pins.pop(rng.randrange(len(pins))))
            else:
                s.contains(key)
            assert s.stats.bytes_stored == s.resident_bytes()
            if cap is not None and not pins:
                assert s.stats.bytes_stored <= cap
        while pins:
            s.unpin(pins.pop())
        if cap is not None:
            assert s.stats.bytes_stored <= cap


def test_store_bytes_invariant_hypothesis():
    pytest.importorskip("hypothesis")
    import hypothesis.strategies as st
    from hypothesis import given, settings

    from conftest import hyp_max_examples

    @settings(max_examples=hyp_max_examples(60), deadline=None)
    @given(st.integers(16, 512),
           st.lists(st.tuples(st.integers(0, 3), st.integers(0, 7),
                              st.integers(1, 300)),
                    min_size=1, max_size=120))
    def run(cap, ops):
        s = MMStore(capacity_bytes=cap)
        pinned = []
        for op, k, nb in ops:
            key = f"k{k}"
            if op == 0:
                s.put(key, nb, nb)
            elif op == 1:
                s.get(key, record=False)
            elif op == 2:
                if s.pin(key):
                    pinned.append(key)
            elif pinned:
                s.unpin(pinned.pop())
            assert s.stats.bytes_stored == s.resident_bytes()
            if not pinned:
                assert s.stats.bytes_stored <= cap

    run()


# ---------------------------------------------------------------------------
# EPPrefetcher: announce-time check vs fire-time consumption race
# ---------------------------------------------------------------------------

def _prefetch_rig(cfg_params, *, pin, capacity=None):
    cfg, _ = cfg_params
    loop = EventLoop()
    store = MMStore(capacity_bytes=capacity)
    cost = CostModel(cfg)
    return loop, store, EPPrefetcher(loop, store, cost,
                                     async_mode=True, pin=pin), cost


def test_prefetch_fire_time_eviction_routes_to_recompute(llava):
    """Unpinned prefetcher: an eviction between announce and fire used
    to hand Prefill a vanished entry while on_ready reported a clean
    transfer. The fire-time re-check must route through the recompute
    arm (with its modeled delay) and surface the event."""
    loop, store, pf, cost = _prefetch_rig(llava, pin=False, capacity=100)
    store.put("feat", b"f", 80)
    fired = []
    pf.notify(1, "feat", 8, on_ready=fired.append)
    store.put("other", b"o", 80)           # evicts "feat" mid-flight
    assert not store.contains("feat")
    loop.run()
    assert fired == [True]                 # consumer sees the recompute
    rec = pf.records[0]
    assert rec.evicted_in_flight and rec.recomputed
    assert pf.inflight_evictions == 1
    # the recompute delay landed on the loop clock after the announce
    assert loop.now >= cost.encode_time(8)


def test_prefetch_pin_protects_entry_until_fire(llava):
    """Pinned (default) prefetcher: the announce pins the feature so an
    interleaved eviction cannot vanish it; the fire releases the pin and
    normal LRU pressure resumes."""
    loop, store, pf, _ = _prefetch_rig(llava, pin=True, capacity=100)
    store.put("feat", b"f", 80)
    fired = []
    pf.notify(1, "feat", 8, on_ready=fired.append)
    store.put("other", b"o", 80)           # would evict "feat" if unpinned
    assert store.contains("feat")          # pin held it ("other" evicted)
    loop.run()
    assert fired == [False] and pf.inflight_evictions == 0
    assert not pf.records[0].evicted_in_flight
    # pin released at fire: the next over-budget put may claim it
    store.put("later", b"l", 80)
    assert not store.contains("feat")


def test_prefetch_sync_blocks_encode_async_does_not(llava):
    cfg, _ = llava
    store = MMStore()
    store.put("k", b"f", 64)
    cost = CostModel(cfg)
    a = EPPrefetcher(EventLoop(), store, cost, async_mode=True)
    s = EPPrefetcher(EventLoop(), store, cost, async_mode=False)
    assert a.notify(1, "k", 8, on_ready=lambda _r: None) == 0.0
    assert s.notify(1, "k", 8, on_ready=lambda _r: None) > 0.0


# ---------------------------------------------------------------------------
# EncodeEngine
# ---------------------------------------------------------------------------

def test_encode_engine_requires_frontend():
    cfg = get_config("smollm-135m").reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    with pytest.raises(ValueError):
        EncodeEngine(cfg, params, store=MMStore())


def test_encode_engine_dedup_and_metrics(llava):
    cfg, params = llava
    store = MMStore()
    eng = EncodeEngine(cfg, params, store=store, name="E0")
    r1 = Request(prompt_tokens=[1, 2], mm_payload=b"img", mm_tokens=8)
    r2 = Request(prompt_tokens=[3, 4], mm_payload=b"img", mm_tokens=8)
    k1, k2 = eng.encode_request(r1), eng.encode_request(r2)
    assert k1 == k2 == FE.content_hash(b"img")
    assert store.stats.puts == 1 and store.stats.hits == 1
    assert eng.metrics.value("encode_requests_total", engine="E0") == 2
    assert eng.metrics.value("encode_dedup_total", engine="E0") == 1
    assert eng.metrics.value("encode_tokens_total", engine="E0") == 8


def test_recompute_is_bit_identical_to_stored_features(llava):
    cfg, params = llava
    store = MMStore()
    eng = EncodeEngine(cfg, params, store=store)
    r = Request(prompt_tokens=[1], mm_payload=b"img", mm_tokens=8)
    key = eng.encode_request(r)
    stored = store.get(key, record=False)
    again = eng.compute_features(b"img", 8)
    assert stored.dtype == np.float32
    np.testing.assert_array_equal(stored, again)


def test_mm_key_run_is_deterministic_and_disjoint_from_vocab():
    a = FE.mm_key_run("deadbeef", 16)
    assert a == FE.mm_key_run("deadbeef", 16)
    assert len(a) == 16 and len(set(a)) == 16
    assert all(t < 0 for t in a)           # never collides with token ids
    assert a != FE.mm_key_run("cafebabe", 16)
    assert a == FE.mm_key_run("deadbeef", 32)[:16]


# ---------------------------------------------------------------------------
# Cluster: E->P overlap arms + (mm-hash, token-run) prefix reuse
# ---------------------------------------------------------------------------

def _mm_cluster(cfg, params, arm, tracer=None):
    return EPDCluster(cfg, params, max_batch=2, max_len=96, paged=True,
                      page_size=8, prefix_cache=True, ep_overlap=arm,
                      tracer=tracer)


def test_overlap_arms_bit_identical_and_accounted(llava):
    """The three E->P hand-off arms differ ONLY in modeled accounting:
    greedy output must be bit-identical across them and match the
    monolithic engine; every traced run must satisfy the components-
    sum-to-e2e ledger invariant; and async must never charge MORE
    E->P exposure than sync."""
    cfg, params = llava
    prompt = list(range(5, 15))
    outs, xfer = {}, {}
    for arm in ("async", "sync", "inline"):
        tr = Tracer(enabled=True)
        cl = _mm_cluster(cfg, params, arm, tracer=tr)
        r = Request(prompt_tokens=list(prompt), max_new_tokens=5,
                    mm_payload=b"arm-img", mm_tokens=8, mm_pos=4)
        cl.submit(r)
        cl.run_until_done()
        cl.acc.check_all()
        outs[arm] = list(r.output_tokens)
        row = cl.attribution()["requests"][0]
        xfer[arm] = row["components_ms"]["transfer"]
        if arm != "inline":
            assert xfer[arm] > 0    # the modeled E->P hand-off is charged
        cl.prefill_engine.assert_no_page_leaks()
        cl.decode_engine.assert_no_page_leaks()
    mono = Engine(cfg, params, max_batch=2, max_len=96)
    rm = Request(prompt_tokens=list(prompt), max_new_tokens=5,
                 mm_payload=b"arm-img", mm_tokens=8, mm_pos=4)
    mono.run_request(rm)
    assert outs["async"] == outs["sync"] == outs["inline"] \
        == list(rm.output_tokens)
    # P->D exposure is identical across arms, so the ordering isolates
    # the E->P charge: inline none < async hidden <= sync serial
    assert xfer["inline"] < xfer["async"] <= xfer["sync"]


def test_prefix_key_composes_mm_dedup_with_kv_reuse(llava):
    """Same image + same prompt prefix, longer suffix: the (mm-hash,
    token-run) radix key must cover the whole image run, so the second
    request skips the encode forward AND the feature fetch outright —
    while still decoding the same tokens a cold cluster produces."""
    cfg, params = llava
    cl = _mm_cluster(cfg, params, "async")
    r1 = Request(prompt_tokens=list(range(5, 15)), max_new_tokens=4,
                 mm_payload=b"reuse-img", mm_tokens=8, mm_pos=4)
    cl.submit(r1)
    cl.run_until_done()
    assert cl.report.encode_skips == 0
    r2 = Request(prompt_tokens=list(range(5, 15)) + [77, 78],
                 max_new_tokens=4, mm_payload=b"reuse-img",
                 mm_tokens=8, mm_pos=4)
    cl.submit(r2)
    cl.run_until_done()
    assert cl.report.encode_skips == 1
    assert cl.store.stats.puts == 1                  # no second encode
    assert cl.metrics.value("encode_requests_total", engine="E0") == 1
    # correctness: a cold cluster (no reuse at all) agrees bit-for-bit
    cold = _mm_cluster(cfg, params, "async")
    rc = Request(prompt_tokens=list(range(5, 15)) + [77, 78],
                 max_new_tokens=4, mm_payload=b"reuse-img",
                 mm_tokens=8, mm_pos=4)
    cold.submit(rc)
    cold.run_until_done()
    assert cold.report.encode_skips == 0
    assert list(r2.output_tokens) == list(rc.output_tokens)
    cl.prefill_engine.assert_no_page_leaks()
    cl.decode_engine.assert_no_page_leaks()


def test_overlap_gauge_and_records(llava):
    cfg, params = llava
    cl = _mm_cluster(cfg, params, "async")
    r = Request(prompt_tokens=list(range(5, 15)), max_new_tokens=3,
                mm_payload=b"gauge-img", mm_tokens=8, mm_pos=4)
    cl.submit(r)
    cl.run_until_done()
    assert len(cl.prefetcher.records) == 1
    ratio = cl.metrics.value("ep_overlap_ratio")
    assert 0.0 <= ratio <= 1.0
    assert ratio == pytest.approx(cl.prefetcher.mean_overlap_ratio)


def test_cluster_rejects_bad_ep_args(llava):
    cfg, params = llava
    with pytest.raises(ValueError):
        EPDCluster(cfg, params, ep_overlap="magic")
    with pytest.raises(ValueError):
        EPDCluster(cfg, params, n_encode=0)
