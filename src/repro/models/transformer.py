"""Unified decoder backbone.

One code path covers every assigned arch: per-layer pattern of
{attn | swa | ssm} mixers and {mlp | moe | none} ffns, optional encoder
(whisper) and optional multimodal embedding merge (VLM / audio / early
fusion). Layers run under ``lax.scan`` over pattern repeats so 40-layer
models lower to compact HLO for the 512-chip dry-run.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import moe as M
from repro.models import ssm as S
from repro.models.partitioning import shard


# ---------------------------------------------------------------------------
# Caches
# ---------------------------------------------------------------------------

def make_caches(cfg: ModelConfig, batch: int, max_len: int, *,
                dtype=jnp.bfloat16, kv_dtype=None, abstract: bool = False,
                for_decode: bool = False, layout: str = "dense",
                page_size: int = 16, n_pages: int = 0,
                with_attn: bool = True) -> Dict[str, Any]:
    """Cache pytree for serving. One entry per pattern position.

    for_decode=True clamps sliding-window caches to the window (ring
    buffer) — decode-only dry-runs. Prefill-capable caches keep max_len so
    a full prompt fits before eviction.
    kv_dtype: storage dtype for attention KV only (e.g. fp8_e4m3 — the
    beyond-paper decode optimization in EXPERIMENTS.md §Perf); SSM state
    and conv tails keep ``dtype``/f32.
    layout="paged": attention KV lives in a shared page pool of
    ``n_pages`` physical pages of ``page_size`` tokens (page 0 reserved
    as trash — see serving.kv_pool) and the pytree grows a "pages" block
    table (batch, max_len // page_size). Sliding-window caches are not
    ring-clamped on the paged path — the window is enforced by masking,
    and page-level eviction is the follow-up that reclaims the memory.
    SSM state and cross-KV stay slot-indexed (fixed per-slot size).
    with_attn=False skips the attention-KV allocations (entries stay
    None) — for side-state-only pytrees whose "attn" the caller swaps
    in from a shared page pool (paged prefill staging).
    """
    kv_dtype = kv_dtype or dtype
    paged = layout == "paged"
    if paged:
        if max_len % page_size:
            raise ValueError(
                f"max_len {max_len} not a multiple of page_size {page_size}")
        if n_pages < 2:
            raise ValueError("paged layout needs n_pages >= 2 "
                             "(page 0 is the reserved trash page)")
    attn = []
    ssm = []
    for spec in cfg.pattern:
        if spec.mixer in ("attn", "swa"):
            if not with_attn:
                attn.append(None)
                ssm.append(None)
                continue
            if paged:
                attn.append(L.make_paged_attn_cache(
                    cfg, cfg.n_repeats, n_pages, page_size, kv_dtype,
                    abstract))
                ssm.append(None)
                continue
            window = (cfg.sliding_window
                      if spec.mixer == "swa" and for_decode else None)
            attn.append(L.make_attn_cache(cfg, cfg.n_repeats, batch, max_len,
                                          window, kv_dtype, abstract))
            ssm.append(None)
        elif spec.mixer == "ssm":
            attn.append(None)
            ssm.append(S.make_ssm_cache(cfg, cfg.n_repeats, batch, dtype,
                                        abstract))
        else:
            attn.append(None)
            ssm.append(None)
    cross = None
    if cfg.encoder is not None:
        t = cfg.encoder.n_ctx
        kshape = (cfg.n_repeats, batch, t, cfg.n_kv_heads, cfg.head_dim)
        pshape = (cfg.n_repeats, batch, t)
        if abstract:
            cross = (jax.ShapeDtypeStruct(kshape, dtype),
                     jax.ShapeDtypeStruct(kshape, dtype),
                     jax.ShapeDtypeStruct(pshape, jnp.int32))
        else:
            cross = (jnp.zeros(kshape, dtype), jnp.zeros(kshape, dtype),
                     jnp.full(pshape, -1, jnp.int32))
    lengths = (jax.ShapeDtypeStruct((batch,), jnp.int32) if abstract
               else jnp.zeros((batch,), jnp.int32))
    caches = {"attn": tuple(attn), "ssm": tuple(ssm), "cross": cross,
              "len": lengths}
    if paged:
        tshape = (batch, max_len // page_size)
        caches["pages"] = (jax.ShapeDtypeStruct(tshape, jnp.int32) if abstract
                           else jnp.zeros(tshape, jnp.int32))
    return caches


def cache_pspecs(cfg: ModelConfig, rules, layout: str = "dense"
                 ) -> Dict[str, Any]:
    """PartitionSpecs matching make_caches structure.

    KV-cache sharding adapts per arch: heads when n_kv_heads divides the
    model axis (classic TP), else the sequence dim (flash-decode style) —
    e.g. smollm's kv=3 or glm4's kv=2 cannot split 16 ways by head.
    layout="paged": the pool's page axis takes the role of the sequence
    axis (pages spread flash-decode style); the block table and lengths
    stay batch-sharded.
    """
    from repro.models.partitioning import logical_to_pspec as lp
    paged = layout == "paged"
    head_ok = (rules is not None and rules.size("kv_heads") > 1 and
               cfg.n_kv_heads % rules.size("kv_heads") == 0)
    seq_pref = rules is not None and rules.size("kv_seq") > 1
    if paged:
        # (repeats, n_pages, page, nkv, hd)
        kv_axes = ("layers", "kv_seq", None,
                   "kv_heads" if head_ok else None, None)
        pos_axes = None
    elif rules is not None and not head_ok and not seq_pref:
        # fall back to sequence sharding on whatever axis 'kv_heads' used
        kv_axes = ("layers", "batch", "kv_heads", None, None)
        pos_axes = ("layers", "batch", "kv_heads")
    else:
        kv_axes = ("layers", "batch", "kv_seq",
                   "kv_heads" if head_ok else None, None)
        pos_axes = ("layers", "batch", "kv_seq")
    attn, ssm = [], []
    for spec in cfg.pattern:
        if spec.mixer in ("attn", "swa"):
            kv = lp(kv_axes, rules)
            if paged:
                attn.append(L.PagedAttnCache(kv, kv))
            else:
                pos = lp(pos_axes, rules)
                attn.append(L.AttnCache(kv, kv, pos))
            ssm.append(None)
        elif spec.mixer == "ssm":
            st = lp(("layers", "batch", "act_heads", None, None), rules)
            cv = lp(("layers", "batch", None, "act_inner"), rules)
            attn.append(None)
            ssm.append(S.SSMCache(st, cv))
        else:
            attn.append(None)
            ssm.append(None)
    cross = None
    if cfg.encoder is not None:
        kv = lp(("layers", "batch", None, "kv_heads", None), rules)
        cross = (kv, kv, lp(("layers", "batch", None), rules))
    specs = {"attn": tuple(attn), "ssm": tuple(ssm), "cross": cross,
             "len": lp(("batch",), rules)}
    if paged:
        specs["pages"] = lp(("batch", None), rules)
    return specs


# ---------------------------------------------------------------------------
# Embedding / input merge
# ---------------------------------------------------------------------------

def embed_inputs(params, cfg: ModelConfig, tokens,
                 mm_embeds: Optional[jax.Array] = None):
    """tokens: (B, S_text) int32; mm_embeds: (B, n_mm, feature_dim) or None.

    Multimodal embeddings (from the stubbed frontend) are projected to
    d_model and PREPENDED to the text sequence (early fusion). Returns
    (x (B, S, d), positions (B, S)).
    """
    x = params["embed"][tokens]                       # (B, S_t, d)
    if mm_embeds is not None:
        mm = mm_embeds.astype(x.dtype) @ params["projector"]
        x = jnp.concatenate([mm, x], axis=1)
    b, s = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    return shard(x, "batch", None, "act_embed"), positions


def scatter_mm_features(x, positions, mm_feats, mm_start):
    """Overwrite image-token positions of the embedding stream with
    projected multimodal features (the Encode-stage E->P hand-off).

    x: (B, S, d) token embeddings for this (possibly suffix) chunk;
    positions: (B, S) ABSOLUTE positions; mm_feats: (B, n_mm, d) already
    projected to d_model; mm_start: scalar/(B,) absolute position of the
    first image token. Positions outside [mm_start, mm_start + n_mm) keep
    their text embeddings, so a chunk that only overlaps part of the image
    run scatters exactly its slice.
    """
    n_mm = mm_feats.shape[1]
    start = jnp.asarray(mm_start, jnp.int32)
    if start.ndim == 0:
        start = jnp.broadcast_to(start, (x.shape[0],))
    rel = positions - start[:, None]                  # (B, S)
    valid = (rel >= 0) & (rel < n_mm)
    gathered = jnp.take_along_axis(
        mm_feats.astype(x.dtype),
        jnp.clip(rel, 0, n_mm - 1)[..., None], axis=1)
    return jnp.where(valid[..., None], gathered, x)


def lm_logits(params, cfg: ModelConfig, h):
    w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return shard(h @ w.astype(h.dtype), "batch", None, "act_vocab")


# ---------------------------------------------------------------------------
# Encoder (whisper-style)
# ---------------------------------------------------------------------------

def run_encoder(params, cfg: ModelConfig, frames):
    """frames: (B, T, feature_dim) stub embeddings -> (B, T, d_model)."""
    enc = params["encoder"]
    x = frames.astype(params["projector"].dtype) @ params["projector"]
    x = x + enc["pos_embed"][None, : x.shape[1]].astype(x.dtype)
    b, t = x.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (b, t))

    def body(carry, p):
        h = carry
        h, _ = L.attention_block(p["attn"], h, positions, cfg, causal=False)
        h = L.mlp_block(p["mlp"], h, cfg)
        return h, None

    x, _ = jax.lax.scan(body, x, enc["layers"])
    return L.rms_norm(x, enc["final_norm"], cfg.norm_eps), positions


# ---------------------------------------------------------------------------
# Decoder stack
# ---------------------------------------------------------------------------

def run_decoder(params, cfg: ModelConfig, x, positions, *,
                caches: Optional[Dict[str, Any]] = None,
                enc_out: Optional[Tuple[jax.Array, jax.Array]] = None,
                remat: bool = False,
                prefix_len: Optional[jax.Array] = None,
                pos_base: Optional[jax.Array] = None):
    """Run all decoder layers.

    caches: cache pytree from make_caches (serving) or None (training).
    enc_out: (enc_hidden, enc_pos) — only during prefill/training of an
      enc-dec arch; during decode the cross-KV comes from caches['cross'].
    prefix_len / pos_base: paged suffix-prefill against a cached prefix
      (see layers.attention_block) — x covers positions from the
      page-aligned ``pos_base`` only.
    Returns (h, new_caches, aux_loss).
    """
    pat = cfg.pattern
    cur_len = caches["len"] if caches is not None else None
    pages = caches.get("pages") if caches is not None else None
    attn_cls = L.PagedAttnCache if pages is not None else L.AttnCache
    decode = caches is not None and x.shape[1] == 1
    # Paged decode threads each attention pool through the scan's CARRY,
    # flattened (R, P, page, nkv, hd) -> (R * P, page, nkv, hd) (a
    # bitcast): repeat r's pages are r * P + the block table's entries, so
    # a layer writes its one new token into the pool in place and the
    # kernel reads its pages from the whole pool. As xs/ys, every layer's
    # slab would be sliced out, re-stacked and copied back on every step.
    pooled = decode and pages is not None
    n_rep = cfg.n_repeats
    pools = ()
    if pooled:
        pools = tuple(None if c is None else
                      tuple(a.reshape(-1, *a.shape[2:]) for a in c)
                      for c in caches["attn"])

    def body(carry, xs):
        h, aux, pools = carry
        p_list, attn_c, ssm_c, cross_c, r = xs
        new_attn, new_ssm = [], []
        new_pools = list(pools)
        new_cross = None
        for i, spec in enumerate(pat):
            p = p_list[i]
            if spec.mixer in ("attn", "swa"):
                window = cfg.sliding_window if spec.mixer == "swa" else None
                if pooled:
                    # pool pages of repeat r; page r * P is its trash page
                    cache = pools[i]
                    layer_pages = pages + r * (cache[0].shape[0] // n_rep)
                else:
                    cache = (tuple(attn_c[i]) if attn_c[i] is not None
                             else None)
                    layer_pages = pages
                h, nc = L.attention_block(
                    p["attn"], h, positions, cfg, window=window,
                    cache=cache, cur_len=cur_len, pages=layer_pages,
                    prefix_len=prefix_len, pos_base=pos_base)
                if pooled:
                    new_pools[i] = nc
                    new_attn.append(None)
                else:
                    new_attn.append(attn_cls(*nc) if nc is not None
                                    else None)
                if cfg.encoder is not None:
                    if decode:
                        ckv = cross_c
                    else:
                        ckv = L.compute_cross_kv(p["attn"], enc_out[0],
                                                 enc_out[1], cfg)
                        new_cross = ckv
                    h = L.cross_attention_block(p["attn"], h, positions, ckv,
                                                cfg)
            elif spec.mixer == "ssm":
                h, nc = S.ssm_block(
                    p["ssm"], h, cfg,
                    cache=tuple(ssm_c[i]) if ssm_c[i] is not None else None,
                    positions=positions)
                new_ssm.append(S.SSMCache(*nc) if nc is not None else None)
            else:
                new_attn.append(None)
                new_ssm.append(None)
            if spec.ffn == "mlp":
                h = L.mlp_block(p["mlp"], h, cfg)
            elif spec.ffn == "moe":
                h, a = M.moe_block(p["moe"], h, cfg)
                aux = aux + a
            if spec.mixer in ("attn", "swa"):
                new_ssm.append(None)
            elif spec.mixer == "ssm":
                new_attn.append(None)
        ys = (tuple(new_attn), tuple(new_ssm), new_cross)
        return (h, aux, tuple(new_pools)), ys

    if remat:
        body = jax.checkpoint(body)

    attn_xs = (caches["attn"] if caches is not None and not pooled
               else tuple(None for _ in pat))
    ssm_xs = (caches["ssm"] if caches is not None
              else tuple(None for _ in pat))
    cross_xs = caches["cross"] if caches is not None else None
    reps = jnp.arange(n_rep, dtype=jnp.int32) if pooled else None
    xs = (params["blocks"], attn_xs, ssm_xs, cross_xs, reps)
    (h, aux, pools), ys = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32), pools), xs)
    new_attn, new_ssm, new_cross = ys
    if pooled:
        new_attn = tuple(
            None if c is None else
            attn_cls(*(a.reshape(n_rep, -1, *a.shape[1:]) for a in c))
            for c in pools)

    h = L.rms_norm(h, params["final_norm"], cfg.norm_eps)

    new_caches = None
    if caches is not None:
        step = x.shape[1] if not decode else 1
        new_caches = {
            "attn": new_attn, "ssm": new_ssm,
            "cross": (new_cross if cfg.encoder is not None and not decode
                      else caches["cross"]),
            "len": caches["len"] + (jnp.int32(step) if decode
                                    else positions.shape[1]),
        }
        if pages is not None:
            new_caches["pages"] = pages
    return h, new_caches, aux
