"""Paged KV-cache subsystem: pool allocator, Pallas paged decode
attention vs. oracle, paged engine parity with dense, and the O(pages)
P->D insert path."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.paged_decode_attention import (paged_decode_attention,
                                                 paged_decode_attention_ref)
from repro.serving.kv_pool import PagePool, pages_for

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------------------
# page pool allocator
# ---------------------------------------------------------------------------

def test_page_pool_alloc_free_cycle():
    pool = PagePool(9, page_size=16)
    assert pool.n_free == 8
    a = pool.alloc(3)
    b = pool.alloc(2)
    assert pool.n_used == 5
    assert 0 not in set(a) | set(b)          # trash page never handed out
    assert len(set(a) | set(b)) == 5         # all distinct
    pool.free(a)
    c = pool.alloc(6)
    assert pool.n_free == 0
    assert len(set(c) | set(b)) == 8


def test_page_pool_exhaustion_and_misuse():
    pool = PagePool(4, page_size=8)
    ids = pool.alloc(3)
    with pytest.raises(RuntimeError, match="exhausted"):
        pool.alloc(1)
    with pytest.raises(ValueError, match="trash"):
        pool.free([0])
    pool.free(ids)
    with pytest.raises(ValueError, match="double free"):
        pool.free([int(ids[0])])
    with pytest.raises(ValueError):
        PagePool(1, page_size=8)


def test_pages_for():
    assert pages_for(1, 16) == 1
    assert pages_for(16, 16) == 1
    assert pages_for(17, 16) == 2
    assert pages_for(0, 16) == 1             # even empty requests hold a page


# ---------------------------------------------------------------------------
# paged decode attention: ref vs dense oracle, kernel vs ref
# ---------------------------------------------------------------------------

def _paged_case(b, page, max_pages, nkv, hd, seed=0, dtype=jnp.float32):
    """Random pool + block tables + ragged lengths (>=1 per slot)."""
    n_pages = b * max_pages + 1
    ks = jax.random.split(jax.random.fold_in(KEY, seed), 2)
    k_pool = jax.random.normal(ks[0], (n_pages, page, nkv, hd), dtype)
    v_pool = jax.random.normal(ks[1], (n_pages, page, nkv, hd), dtype)
    rng = np.random.RandomState(seed)
    tbl = np.zeros((b, max_pages), np.int32)
    lens = np.array([rng.randint(1, max_pages * page + 1) for _ in range(b)],
                    np.int32)
    free = list(range(1, n_pages))
    rng.shuffle(free)                         # non-contiguous physical pages
    for i in range(b):
        for j in range(pages_for(int(lens[i]), page)):
            tbl[i, j] = free.pop()
    return k_pool, v_pool, jnp.asarray(tbl), jnp.asarray(lens)


def test_paged_ref_equals_dense_ref():
    """With an identity block table the paged oracle IS the dense one."""
    b, page, max_pages, nq, nkv, hd = 2, 8, 4, 4, 2, 32
    S = page * max_pages
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, nq, hd))
    k = jax.random.normal(ks[1], (b, S, nkv, hd))
    v = jax.random.normal(ks[2], (b, S, nkv, hd))
    lens = jnp.asarray([S - 3, 17], jnp.int32)
    # pack the dense caches into a pool: slot i's pages are contiguous
    k_pool = jnp.concatenate(
        [jnp.zeros((1, page, nkv, hd)), k.reshape(b * max_pages, page, nkv, hd)])
    v_pool = jnp.concatenate(
        [jnp.zeros((1, page, nkv, hd)), v.reshape(b * max_pages, page, nkv, hd)])
    tbl = (jnp.arange(b * max_pages, dtype=jnp.int32).reshape(b, max_pages)
           + 1)
    out = paged_decode_attention_ref(q, k_pool, v_pool, tbl, lens)
    pos = jnp.arange(S, dtype=jnp.int32)[None]
    kv_pos = jnp.where(pos < lens[:, None], pos, -1)
    ref = decode_attention_ref(q, k, v, lens - 1, kv_pos)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


PAGED_CASES = [
    # b, page, max_pages, nq, nkv, hd, window
    (2, 16, 4, 4, 2, 64, None),              # GQA g=2
    (3, 8, 6, 8, 1, 32, None),               # MQA g=8, ragged
    (2, 16, 8, 4, 4, 64, 20),                # MHA + sliding window
    (1, 32, 3, 6, 2, 128, None),             # big page, odd group g=3
    (2, 8, 5, 8, 2, 64, 12),                 # GQA + window < page span
]


@pytest.mark.parametrize("case", PAGED_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_kernel_matches_ref(case, dtype):
    b, page, max_pages, nq, nkv, hd, win = case
    k_pool, v_pool, tbl, lens = _paged_case(b, page, max_pages, nkv, hd,
                                            seed=hash(case) % 1000,
                                            dtype=dtype)
    q = jax.random.normal(jax.random.fold_in(KEY, 7), (b, nq, hd), dtype)
    out = paged_decode_attention(q, k_pool, v_pool, tbl, lens, window=win,
                                 interpret=True)
    ref = paged_decode_attention_ref(q, k_pool, v_pool, tbl, lens, window=win)
    tol = dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 \
        else dict(atol=1e-3, rtol=1e-3)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **tol)


def test_paged_kernel_page_boundary_lengths():
    """Exact page-multiple lengths (the off-by-one hot spot)."""
    b, page, max_pages, nq, nkv, hd = 3, 8, 4, 4, 2, 32
    k_pool, v_pool, tbl, _ = _paged_case(b, page, max_pages, nkv, hd, seed=3)
    q = jax.random.normal(jax.random.fold_in(KEY, 9), (b, nq, hd))
    for lens in ([page, 2 * page, max_pages * page], [1, page + 1, page - 1]):
        lens = jnp.asarray(lens, jnp.int32)
        out = paged_decode_attention(q, k_pool, v_pool, tbl, lens,
                                     interpret=True)
        ref = paged_decode_attention_ref(q, k_pool, v_pool, tbl, lens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-3, rtol=1e-3)


# ---------------------------------------------------------------------------
# paged engine: parity with dense, zero-copy insert, page accounting
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def smollm():
    from repro.models.model import init_params
    cfg = get_config("smollm-135m").reduced()
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


def test_paged_engine_matches_dense(smollm):
    from repro.serving.engine import Engine
    from repro.serving.request import Request
    cfg, params = smollm
    dense = Engine(cfg, params, max_batch=2, max_len=48)
    paged = Engine(cfg, params, max_batch=2, max_len=48, paged=True,
                   page_size=8)
    for wave in range(2):
        outs = []
        for eng in (dense, paged):
            reqs = [Request(prompt_tokens=[5 + wave, 6, 7],
                            max_new_tokens=5) for _ in range(2)]
            for r in reqs:
                first, payload = eng.prefill_request(r)
                eng.insert(r, payload, first)
            while eng.n_active:
                eng.decode_step()
            outs.append([r.output_tokens for r in reqs])
        assert outs[0] == outs[1]
    # fused-engine insert is a block-table handoff: zero KV bytes moved
    assert paged.kv_insert_bytes_total == 0
    assert dense.kv_insert_bytes_total > 0
    # all pages reclaimed after the requests completed
    assert paged.pool.n_free == paged.pool.n_pages - 1
    assert paged.free_slots() == [0, 1]
    paged.assert_no_page_leaks()


def test_paged_engine_grows_pages_across_boundaries(smollm):
    """Decode past several page boundaries allocates pages on the fly."""
    from repro.serving.engine import Engine
    from repro.serving.request import Request
    cfg, params = smollm
    eng = Engine(cfg, params, max_batch=1, max_len=32, paged=True,
                 page_size=4)
    req = Request(prompt_tokens=[3, 4, 5], max_new_tokens=12)
    first, payload = eng.prefill_request(req)
    eng.insert(req, payload, first)
    assert len(eng._slot_pages[0]) == 1       # 3 tokens -> 1 page of 4
    while eng.n_active:
        eng.decode_step()
    assert len(req.output_tokens) == 12
    assert eng.pool.n_free == eng.pool.n_pages - 1
    eng.assert_no_page_leaks()


def test_paged_insert_bytes_ratio_acceptance(smollm):
    """Acceptance: per-insert KV bytes >=4x smaller than dense at
    max_batch=4, max_len=128, prompt=8 (page 16 -> one page vs 128)."""
    from repro.serving.engine import Engine
    from repro.serving.request import Request
    cfg, params = smollm
    dense = Engine(cfg, params, max_batch=4, max_len=128)
    paged = Engine(cfg, params, max_batch=4, max_len=128, paged=True,
                   page_size=16)
    cluster_src = Engine(cfg, params, max_batch=1, max_len=128, paged=True,
                         page_size=16)
    req = Request(prompt_tokens=list(range(2, 10)), max_new_tokens=2)
    first, payload = dense.prefill_request(req)
    dense.insert(req, payload, first)
    req2 = Request(prompt_tokens=list(range(2, 10)), max_new_tokens=2)
    first2, payload2 = cluster_src.prefill_request(req2)
    # prompt 8 @ page 16 is exactly one page (insert neutralizes the
    # payload's page list, so snapshot before)
    assert payload2.n_pages == 1
    paged.insert(req2, payload2, first2)      # cross-engine: O(pages) copy
    assert paged.kv_insert_bytes > 0
    ratio = dense.kv_insert_bytes / paged.kv_insert_bytes
    assert ratio >= 4.0, f"insert bytes ratio {ratio:.1f} < 4"
    # cross-engine insert drained the source pool; dest holds slot pages
    cluster_src.assert_no_page_leaks()
    paged.assert_no_page_leaks()


def test_paged_cluster_e2e_whisper():
    """Enc-dec arch through the paged disaggregated pipeline: cross-KV
    and lengths ride the side-state insert; attention KV moves by page."""
    from repro.core.cluster import EPDCluster
    from repro.models.model import init_params
    from repro.serving.request import Request
    cfg = get_config("whisper-base").reduced()
    params = init_params(cfg, jax.random.PRNGKey(0))
    cluster = EPDCluster(cfg, params, max_batch=2, max_len=48, paged=True,
                         page_size=8)
    reqs = [Request(prompt_tokens=[1, 2, 3], max_new_tokens=4,
                    mm_payload=b"audio-%d" % i, mm_tokens=0)
            for i in range(3)]
    for r in reqs:
        cluster.submit(r)
    done = cluster.run_until_done()
    assert len(done) == 3
    assert all(len(r.output_tokens) == 4 for r in done)
    # the decode engine imported pages (cross-engine), never whole caches
    assert cluster.decode_engine.kv_insert_bytes_total > 0
    page_layer = cluster.cost.kv_page_bytes_per_layer()
    assert page_layer > 0
    for p in cluster.report.kv_plans:
        for g in p.groups:
            assert g.nbytes % page_layer == pytest.approx(0.0, abs=1e-6)
        # rounding to pages must not inflate the payload by more than
        # one page slice per layer (guards the per-layer quantum)
        payload = sum(g.nbytes for g in p.groups)
        raw = cluster.decode_engine.kv_insert_bytes
        assert payload < raw + cfg.n_layers * page_layer + 1
    # both pools drained back to empty
    assert cluster.prefill_engine.pool.n_used == 0
    assert cluster.decode_engine.pool.n_used == 0
    cluster.prefill_engine.assert_no_page_leaks()
    cluster.decode_engine.assert_no_page_leaks()


def test_paged_cache_pytree_shapes(smollm):
    from repro.models.transformer import make_caches
    cfg, _ = smollm
    c = make_caches(cfg, 4, 64, dtype=jnp.float32, layout="paged",
                    page_size=16, n_pages=10)
    assert c["pages"].shape == (4, 4)
    for e in c["attn"]:
        if e is None:
            continue
        assert e.k.shape[1:3] == (10, 16)
        assert e.k.shape[0] == cfg.n_repeats
    with pytest.raises(ValueError, match="multiple"):
        make_caches(cfg, 4, 60, layout="paged", page_size=16, n_pages=10)
    with pytest.raises(ValueError, match="n_pages"):
        make_caches(cfg, 4, 64, layout="paged", page_size=16, n_pages=1)


def test_paged_insert_failure_keeps_payload_retryable(smollm):
    """A full engine rejects insert without touching the payload; the
    payload can be inserted later or explicitly released."""
    from repro.serving.engine import Engine
    from repro.serving.request import Request
    cfg, params = smollm
    eng = Engine(cfg, params, max_batch=1, max_len=32, paged=True,
                 page_size=8)
    r1 = Request(prompt_tokens=[3, 4, 5], max_new_tokens=20)
    f1, p1 = eng.prefill_request(r1)
    eng.insert(r1, p1, f1)
    r2 = Request(prompt_tokens=[6, 7], max_new_tokens=2)
    f2, p2 = eng.prefill_request(r2)
    used = eng.pool.n_used
    with pytest.raises(RuntimeError, match="no free decode slot"):
        eng.insert(r2, p2, f2)
    assert eng.pool.n_used == used            # nothing mutated
    eng.decode_step()                         # drain slot 0 eventually
    while eng.n_active:
        eng.decode_step()
    eng.insert(r2, p2, f2)                    # retry succeeds
    # the payload's refs now belong to the slot: a stray release is a
    # no-op instead of freeing pages out from under the live request
    eng.release_payload(p2)
    eng.assert_no_page_leaks()
    while eng.n_active:
        eng.decode_step()
    assert len(r2.output_tokens) >= 2
    # abandoning a payload returns its pages (and is idempotent)
    r3 = Request(prompt_tokens=[8, 9], max_new_tokens=2)
    _, p3 = eng.prefill_request(r3)
    assert eng.pool.n_used == p3.n_pages
    eng.assert_no_page_leaks(extra_holders=[p3.page_ids])
    eng.release_payload(p3)
    eng.release_payload(p3)
    assert eng.pool.n_used == 0
    eng.assert_no_page_leaks()


def test_paged_grow_pages_exhaustion_is_atomic(smollm):
    """Pool exhaustion mid-decode must not desync host/device tables:
    after the error, freeing capacity lets decode continue correctly."""
    from repro.serving.engine import Engine
    from repro.serving.request import Request
    cfg, params = smollm
    # 2 slots x 2 pages prompts fit, but growth beyond has no headroom
    eng = Engine(cfg, params, max_batch=2, max_len=32, paged=True,
                 page_size=8, n_pool_pages=5)   # 4 usable pages
    reqs = [Request(prompt_tokens=list(range(2, 18)), max_new_tokens=30)
            for _ in range(2)]                  # 16 tokens = 2 pages each
    for r in reqs:
        f, p = eng.prefill_request(r)
        eng.insert(r, p, f)
    with pytest.raises(RuntimeError, match="exhausted"):
        while eng.n_active:
            eng.decode_step()
    snapshot = [None if p is None else list(p) for p in eng._slot_pages]
    # host bookkeeping must agree with pool accounting after the error
    assert sum(len(p) for p in snapshot if p) == eng.pool.n_used
    # free one slot's pages (simulated preemption) and decode proceeds
    victim = next(i for i, r in enumerate(eng.slots) if r is not None)
    eng.slots[victim] = None
    eng._release_slot(victim)
    for _ in range(8):
        if not eng.n_active:
            break
        eng.decode_step()
    assert eng.pool.n_used <= eng.pool.n_pages - 1


# ---------------------------------------------------------------------------
# dense insert edge cases (satellite): dtype cast + seq pad
# ---------------------------------------------------------------------------

def test_dense_insert_dtype_cast_and_seq_pad(smollm):
    """P engine at a shorter max_len / wider dtype than the D engine:
    insert must pad the sequence dim (kv_pos with -1) and cast KV."""
    from repro.models.transformer import make_caches
    from repro.serving.steps import make_insert_fn
    cfg, _ = smollm
    src = make_caches(cfg, 1, 16, dtype=jnp.float32)
    # fill src with recognizable values
    src["attn"] = tuple(
        type(e)(jnp.ones_like(e.k), jnp.full_like(e.v, 2.0),
                jnp.zeros_like(e.kv_pos)) if e is not None else None
        for e in src["attn"])
    src["len"] = jnp.asarray([7], jnp.int32)
    dst = make_caches(cfg, 3, 32, dtype=jnp.float32, kv_dtype=jnp.bfloat16)
    out = make_insert_fn(cfg)(src, dst, 1)
    e = out["attn"][0]
    assert e.k.dtype == jnp.bfloat16                       # cast applied
    np.testing.assert_array_equal(np.asarray(e.k[:, 1, :16]), 1.0)
    np.testing.assert_array_equal(np.asarray(e.k[:, 1, 16:]), 0.0)  # pad
    np.testing.assert_array_equal(np.asarray(e.kv_pos[:, 1, 16:]), -1)
    np.testing.assert_array_equal(np.asarray(e.kv_pos[:, 1, :16]), 0)
    assert int(out["len"][1]) == 7
    # untouched slots stay zero
    np.testing.assert_array_equal(np.asarray(out["attn"][0].k[:, 0]), 0.0)


@pytest.mark.parametrize("paged", [False, True])
def test_kv_dtype_follows_param_dtype(smollm, paged):
    """A bf16 model gets bf16 KV in every engine of a cluster; an explicit
    kv_dtype still wins."""
    from repro.core.cluster import EPDCluster
    from repro.models.model import init_params
    from repro.serving.engine import Engine
    cfg, _ = smollm
    params = init_params(cfg, KEY, dtype=jnp.bfloat16)
    cluster = EPDCluster(cfg, params, max_batch=2, max_len=32, paged=paged)
    for eng in (cluster.prefill_engine, *cluster.decode_engines):
        for e in eng.caches["attn"]:
            if e is not None:
                assert e.k.dtype == e.v.dtype == jnp.bfloat16
    eng = Engine(cfg, params, max_batch=1, max_len=32, paged=paged,
                 kv_dtype=jnp.float32)
    assert eng.caches["attn"][0].k.dtype == jnp.float32
