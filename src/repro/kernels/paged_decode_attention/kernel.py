"""Pallas TPU paged decode attention: one query token vs. a paged KV pool.

Flash-decode over a block table instead of a contiguous cache. The KV
pool is a flat array of fixed-size pages shared by all slots; each
slot's block table row names the physical page of every logical page.
The page dimension is the innermost (sequential) grid axis and the
block table + per-slot lengths ride in via scalar prefetch, so the
pipeline's k/v index map resolves the *physical* page to DMA before the
kernel body runs.

One grid step handles one page for ALL kv heads: the pool is viewed as
(P, page * nkv, hd), so the KV block's last two dims are whole array
dims and the TPU tiling rule holds for any page size and head count.
On the TPU's (8, 128)-tiled layout that view is a bitcast, not a copy,
whenever nkv is a multiple of 8 (each token's (nkv, hd) rows are whole
tiles); tests/test_tpu_compile.py checks it for the served geometry.
The (nq, hd) queries meet the page's (page * nkv, hd) rows in one 2-D
matmul and a query row keeps only the columns of its own kv head
(row // group == column % nkv). A (1, page, nkv, hd) block would be
legal too, but each head would then read a sublane-strided
(page, hd) slice and run a (group x page) product far below one MXU
tile; the flat view spends nkv x the MXU and exp work on masked columns
instead, which decode (bound by HBM bytes, intensity ~= group size)
can afford. Operands are computed in float32, as in the prefill
kernel.

HBM traffic is proportional to each slot's ACTUAL length, not the pool
or table width: for grid steps past the slot's last page the index map
clamps to the last real page — Pallas elides the DMA when consecutive
grid steps map the same block — and the compute is skipped with
``pl.when``. This is the Decode-stage hot loop of the disaggregated
serving system.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
            acc_ref, m_ref, l_ref, *, scale: float, window: Optional[int],
            page: int, nkv: int, group: int, n_pages_max: int):
    bi = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[bi]                               # valid tokens incl. q
    n_pages = (length + page - 1) // page

    @pl.when(j < n_pages)
    def _compute():
        q = q_ref[0].astype(jnp.float32)               # (nq, hd)
        k = k_ref[0].astype(jnp.float32)               # (page * nkv, hd)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale

        # column c holds token c // nkv of the page for kv head c % nkv
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kpos = j * page + col // nkv
        qpos = length - 1
        valid = (col % nkv == row // group) & (kpos < length)
        if window is not None:
            valid &= kpos > qpos - window
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_ref[...]                            # (nq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(valid, p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == n_pages_max - 1)
    def _done():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)


def paged_decode_attention(q, k_pool, v_pool, block_tbl, lengths, *,
                           window: Optional[int] = None,
                           interpret: bool = False):
    """q: (b, nq, hd); k_pool, v_pool: (P, page, nkv, hd);
    block_tbl: (b, max_pages) int32; lengths: (b,) int32 valid tokens
    including the current one. Returns (b, nq, hd)."""
    b, nq, hd = q.shape
    n_phys, page, nkv = k_pool.shape[:3]
    n_pages_max = block_tbl.shape[1]

    tbl = block_tbl.astype(jnp.int32)
    lens = lengths.astype(jnp.int32)
    kf = k_pool.reshape(n_phys, page * nkv, hd)
    vf = v_pool.reshape(n_phys, page * nkv, hd)

    def kv_page_index(bi, j, tbl_ref, len_ref):
        # Clamp trailing grid steps to the slot's LAST real page so the
        # pipeline re-maps the same block (no fresh DMA) once past the
        # actual length; compute for those steps is masked off above.
        n_pages = (len_ref[bi] + page - 1) // page
        jj = jnp.minimum(j, jnp.maximum(n_pages - 1, 0))
        return (tbl_ref[bi, jj], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_pages_max),
        in_specs=[
            pl.BlockSpec((1, nq, hd), lambda bi, j, t, s: (bi, 0, 0)),
            pl.BlockSpec((1, page * nkv, hd), kv_page_index),
            pl.BlockSpec((1, page * nkv, hd), kv_page_index),
        ],
        out_specs=pl.BlockSpec((1, nq, hd), lambda bi, j, t, s: (bi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((nq, hd), jnp.float32),
            pltpu.VMEM((nq, 1), jnp.float32),
            pltpu.VMEM((nq, 1), jnp.float32),
        ],
    )
    kern = functools.partial(_kernel, scale=hd ** -0.5, window=window,
                             page=page, nkv=nkv, group=nq // nkv,
                             n_pages_max=n_pages_max)
    return pl.pallas_call(
        kern,
        name="paged_decode_attention",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, nq, hd), q.dtype),
        interpret=interpret,
    )(tbl, lens, q, kf, vf)
