#!/usr/bin/env python3
"""Chip smoke test: the E->P->D serving path on one TPU.

    python3 chip_smoke.py

One process, one chip. Builds llava-next-mistral-7b with every width as
published and the depth cut to 8 layers (random bf16 weights from
``--seed``). Runs each main-path attention kernel (flash prefill chunk,
paged decode, dense decode at batch 4) at the served shapes against its
``ref.py`` oracle, then serves four requests through
``EPDCluster.run_continuous`` (paged KV, chunked prefill, radix prefix
cache) and checks that all of them complete with in-vocab tokens and
that no pool leaks a page. Wall-clock times are printed as such; no
modeled clock is.

The last line of stdout is ``{"ok": true, "device": {...}}``. The script
exits non-zero, without that line, when JAX finds no TPU or any phase or
check fails. The compile cache is ``$JAX_COMPILATION_CACHE_DIR`` when
set, else ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "llava-next-mistral-7b"
N_LAYERS = 8
TEXT_LEN = 64           # text tokens beside each 2880-token image run
LONG_LEN = 1000         # the text-only request
MAX_NEW = 32
# Kernel vs oracle, one output row (one head's head_dim values) at a
# time, by relative L2 error ||out - ref|| / ||ref||: a row's bound then
# scales with the row. An absolute bound cannot, since with N(0, 1)
# inputs a row attending over n tokens has outputs of order sqrt(e / n),
# 0.026 at n = 4096. The kernels compute in float32 from bf16 inputs;
# the oracles round the probabilities to bf16 before P@V, and both round
# the output to bf16 (2^-9 relative each), so a faithful row is off by
# about 2^-9 ~ 2e-3. A row that loses one 16-token page of 4096 moves by
# about sqrt(16 / 4096) = 6%. The bound sits between the two.
ROW_RTOL = 1e-2
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")


def _compile_clock():
    """Seconds JAX spent tracing, lowering and compiling (cache loads
    included), summed from its monitoring events."""
    import jax
    spent = {"s": 0.0}

    def on_event(event, duration, **_):
        if event in _COMPILE_EVENTS:
            spent["s"] += duration

    jax.monitoring.register_event_duration_secs_listener(on_event)
    return lambda: spent["s"]


def compare(name, out, ref) -> bool:
    """Prints the max abs error and the worst row's relative L2 error of
    ``out`` against ``ref``; True when every row is within ROW_RTOL."""
    import numpy as np
    out = np.asarray(out, np.float32)
    ref = np.asarray(ref, np.float32)
    assert out.shape == ref.shape, (name, out.shape, ref.shape)
    assert np.isfinite(out).all(), f"{name}: non-finite kernel output"
    err = float(np.max(np.abs(out - ref)))
    hd = out.shape[-1]
    diff = np.linalg.norm((out - ref).reshape(-1, hd), axis=1)
    size = np.linalg.norm(ref.reshape(-1, hd), axis=1)
    row_err = float(np.max(diff / np.maximum(size, 1e-30)))
    ok = row_err <= ROW_RTOL
    print(f"kernel {name}: shape={out.shape} max_abs_err={err:.3e} "
          f"max_row_rel_err={row_err:.3e} tol={ROW_RTOL} "
          f"{'PASS' if ok else 'FAIL'}", flush=True)
    return ok


def check_kernels(cfg, serving, seed: int) -> bool:
    """Each main-path kernel vs its oracle at the shapes just served."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.decode_attention import (decode_attention,
                                                decode_attention_ref)
    from repro.kernels.flash_attention import attention_ref, flash_attention
    from repro.kernels.paged_decode_attention import (
        paged_decode_attention, paged_decode_attention_ref)

    nq, nkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    page, max_len = serving["page_size"], serving["max_len"]
    chunk, max_batch = serving["prefill_chunk"], serving["max_batch"]
    max_pages = max_len // page
    mm_len = cfg.frontend.tokens_per_item + TEXT_LEN
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 16))

    def normal(shape):
        return jax.random.normal(next(keys), shape, jnp.bfloat16)

    ok = True
    # flash: the second prefill chunk of a request, against the gathered
    # block-table row (first chunk resident) plus the chunk itself
    S = max_len + chunk
    q = normal((1, chunk, nq, hd))
    k, v = normal((1, S, nkv, hd)), normal((1, S, nkv, hd))
    qp = jnp.arange(chunk, 2 * chunk, dtype=jnp.int32)[None]
    row = jnp.arange(max_len, dtype=jnp.int32)
    kp = jnp.concatenate([jnp.where(row < chunk, row, -1), qp[0]])[None]
    ok &= compare("flash_attention", flash_attention(q, k, v, qp, kp),
                  attention_ref(q, k, v, qp, kp))

    # paged decode: every slot of the decode pool, ragged lengths (page
    # boundaries, the served prompt sizes before and after decode, a
    # full row) on scattered physical pages
    n_pool = 1 + (max_batch + 1) * max_pages
    want = [1, page - 1, page, page + 1, chunk, LONG_LEN, LONG_LEN + MAX_NEW,
            mm_len, mm_len + MAX_NEW, max_len - page, max_len - 1, max_len]
    lens = np.resize(np.clip(want, 1, max_len), max_batch).astype(np.int32)
    free = np.random.RandomState(seed).permutation(np.arange(1, n_pool))
    tbl = np.zeros((max_batch, max_pages), np.int32)
    used = 0
    for i, n in enumerate(lens):
        n_pg = -(-int(n) // page)
        tbl[i, :n_pg] = free[used:used + n_pg]
        used += n_pg
    q = normal((max_batch, nq, hd))
    k_pool, v_pool = (normal((n_pool, page, nkv, hd)),
                      normal((n_pool, page, nkv, hd)))
    tbl, lens = jnp.asarray(tbl), jnp.asarray(lens)
    ok &= compare("paged_decode_attention",
                  paged_decode_attention(q, k_pool, v_pool, tbl, lens),
                  paged_decode_attention_ref(q, k_pool, v_pool, tbl, lens))
    del k_pool, v_pool

    # dense decode at b=4 over max_len cache rows, ragged fill
    b = 4
    q = normal((b, nq, hd))
    k, v = normal((b, max_len, nkv, hd)), normal((b, max_len, nkv, hd))
    fill = jnp.asarray(np.clip([1, LONG_LEN, mm_len, max_len], 1, max_len),
                       jnp.int32)
    pos = jnp.arange(max_len, dtype=jnp.int32)[None]
    kp = jnp.where(pos < fill[:, None], pos, -1)
    ok &= compare("decode_attention",
                  decode_attention(q, k, v, fill - 1, kp),
                  decode_attention_ref(q, k, v, fill - 1, kp))
    return ok


def requests(cfg):
    """Two multimodal requests on one image (MM Store dedup), one on a
    second image, and one text-only request (the P-D path)."""
    from repro.serving.request import Request

    def text(n, base):
        return [(base + 7 * i) % (cfg.vocab - 2) + 2 for i in range(n)]

    def req(prompt, image=None):
        return Request(prompt_tokens=prompt, max_new_tokens=MAX_NEW,
                       mm_payload=image, eos_token=-1,
                       mm_tokens=cfg.frontend.tokens_per_item if image else 0)

    return [req(text(TEXT_LEN, 11), b"image-a"),
            req(text(TEXT_LEN, 23), b"image-a"),
            req(text(TEXT_LEN, 31), b"image-b"),
            req(text(LONG_LEN, 5))]


def serve(cfg, cluster, compile_s) -> None:
    """Serve ``requests(cfg)`` through run_continuous; raises unless every
    request completes with MAX_NEW in-vocab tokens, nothing is lost, and
    every page pool balances."""
    reqs = requests(cfg)
    c0, t0 = compile_s(), time.perf_counter()
    done = cluster.run_continuous(reqs)
    wall = time.perf_counter() - t0
    assert not cluster.report.lost, f"lost requests: {cluster.report.lost}"
    assert sorted(r.request_id for r in done) == sorted(
        r.request_id for r in reqs), "not every request completed"
    for r in done:
        assert len(r.output_tokens) == MAX_NEW, (r.request_id,
                                                 r.output_tokens)
        assert all(0 <= t < cfg.vocab for t in r.output_tokens), \
            (r.request_id, r.output_tokens)
        path = "E-P-D" if r.is_multimodal else "P-D"
        print(f"req {r.request_id} [{path}] prompt={r.total_prompt_len} "
              f"out={len(r.output_tokens)} first8={r.output_tokens[:8]}")
    pools = (cluster.prefill_engine, *cluster.decode_engines)
    for eng in pools:
        eng.assert_no_page_leaks()
    pe = cluster.prefill_engine
    print(f"served {len(done)} requests, {len(done) * MAX_NEW} tokens; "
          f"leaked pages: 0 in {len(pools)} pools; MM store "
          f"{cluster.store.stats}; prefill tokens computed "
          f"{pe.prefill_tokens_computed}/{pe.prefill_tokens_total}")
    print(f"serve wall {wall:.2f}s, of which JAX compile "
          f"{compile_s() - c0:.2f}s (wall clock)", flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if not __debug__:
        print("chip_smoke: its checks are asserts; run it without -O",
              file=sys.stderr)
        return 2

    import jax
    import jax.numpy as jnp
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2

    from repro.configs import get_config
    from repro.kernels import dispatch
    from repro.launch.compile_cache import use_compile_cache
    from repro.launch.serve import CHIP_SERVING, build_cluster
    from repro.models.params import count_params

    cache_dir = use_compile_cache()
    compile_s = _compile_clock()
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(jax.devices())}")
    print(f"compile cache: {cache_dir}")
    print(f"use_pallas={dispatch.use_pallas()} "
          f"interpret={dispatch.interpret()}", flush=True)
    assert dispatch.use_pallas() and not dispatch.interpret(), \
        "the chip path must run the compiled Pallas kernels"

    t0 = time.perf_counter()
    cfg = dataclasses.replace(get_config(ARCH), n_layers=N_LAYERS)
    cluster = build_cluster(cfg, dtype=jnp.bfloat16, seed=args.seed,
                            **CHIP_SERVING)
    params = cluster.prefill_engine.params
    jax.block_until_ready(params)
    pool = cluster.decode_engine.caches["attn"][0].k
    print(f"model: {cfg.name}, depth cut {get_config(ARCH).n_layers} -> "
          f"{cfg.n_layers} layers; widths as published: "
          f"d_model={cfg.d_model} heads={cfg.n_heads}/{cfg.n_kv_heads} "
          f"head_dim={cfg.head_dim} d_ff={cfg.d_ff} vocab={cfg.vocab} "
          f"image_tokens={cfg.frontend.tokens_per_item} "
          f"feature_dim={cfg.frontend.feature_dim}")
    print(f"params: {count_params(params):,} {params['embed'].dtype}; "
          f"decode pool {pool.dtype} {pool.shape}; build wall "
          f"{time.perf_counter() - t0:.2f}s", flush=True)

    ok = check_kernels(cfg, CHIP_SERVING, args.seed)
    serve(cfg, cluster, compile_s)
    stats = dev.memory_stats() or {}
    print(f"compile total {compile_s():.2f}s (wall clock); peak device "
          f"bytes {stats.get('peak_bytes_in_use')} of "
          f"{stats.get('bytes_limit')}")
    if not ok:
        print("chip_smoke: a kernel disagrees with its oracle",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
