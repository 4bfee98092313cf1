"""Pallas TPU flash attention (prefill): online-softmax, BlockSpec-tiled.

Grid: (batch, q_heads, q_blocks, kv_blocks) — the last dim iterates
sequentially on a TensorCore, so the (m, l, acc) running state lives in
VMEM scratch across kv-block steps. GQA is handled in the k/v index_map
(q-head h reads kv-head h // group), so KV is never materialized per
q-head in HBM.

Block sizes default to (128, 512) — q tile rows are MXU-aligned (128) and
the kv tile keeps the f32 scores block (128 x 512 = 256 KiB) plus k/v
tiles comfortably inside the ~16 MiB VMEM budget of a v5e core.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(qpos_ref, kpos_ref, q_ref, k_ref, v_ref, o_ref,
            acc_ref, m_ref, l_ref, *, scale: float, causal: bool,
            window: Optional[int], nk: int):
    j = pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)              # (bq, hd)
    k = k_ref[0, 0].astype(jnp.float32)              # (bk, hd)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    qpos = qpos_ref[0]                               # (bq, 1)
    kpos = kpos_ref[0]                               # (1, bk)
    valid = (kpos >= 0) & (qpos >= 0)
    if causal:
        valid &= kpos <= qpos
        if window is not None:
            valid &= kpos > qpos - window
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[:, None])
    p = jnp.where(valid, p, 0.0)                     # kill fully-masked rows
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
    acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(j == nk - 1)
    def _done():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)              # padded query rows
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention(q, k, v, q_pos, kv_pos, *, window: Optional[int] = None,
                    causal: bool = True, block_q: int = 128,
                    block_k: int = 512, interpret: bool = False):
    """q: (b, s, nq, hd); k, v: (b, S, nkv, hd); positions as in ref.py."""
    b, s, nq, hd = q.shape
    S, nkv = k.shape[1], k.shape[2]
    g = nq // nkv
    block_q = min(block_q, s)
    block_k = min(block_k, S)

    # pad sequence dims to block multiples; padding has position -1
    def pad_to(x, m, axis, value=0):
        r = (-x.shape[axis]) % m
        if r == 0:
            return x
        pads = [(0, 0)] * x.ndim
        pads[axis] = (0, r)
        return jnp.pad(x, pads, constant_values=value)

    qt = pad_to(jnp.moveaxis(q, 2, 1), block_q, 2)   # (b, nq, s', hd)
    kt = pad_to(jnp.moveaxis(k, 2, 1), block_k, 2)   # (b, nkv, S', hd)
    vt = pad_to(jnp.moveaxis(v, 2, 1), block_k, 2)
    # positions as a (b, s', 1) column and a (b, 1, S') row: the blocks'
    # last two dims then satisfy the TPU tiling rule at any batch size
    qp = pad_to(q_pos.astype(jnp.int32), block_q, 1, -1)[:, :, None]
    kp = pad_to(kv_pos.astype(jnp.int32), block_k, 1, -1)[:, None, :]
    sp, Sp = qt.shape[2], kt.shape[2]
    ni, nk = sp // block_q, Sp // block_k

    grid = (b, nq, ni, nk)
    kern = functools.partial(_kernel, scale=hd ** -0.5, causal=causal,
                             window=window, nk=nk)
    out = pl.pallas_call(
        kern,
        name="flash_attention",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, 1), lambda bi, h, i, j: (bi, i, 0)),
            pl.BlockSpec((1, 1, block_k), lambda bi, h, i, j: (bi, 0, j)),
            pl.BlockSpec((1, 1, block_q, hd), lambda bi, h, i, j: (bi, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda bi, h, i, j: (bi, h // g, j, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda bi, h, i, j: (bi, h // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, hd),
                               lambda bi, h, i, j: (bi, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, nq, sp, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
        ],
        interpret=interpret,
    )(qp, kp, qt, kt, vt)
    return jnp.moveaxis(out[:, :, :s], 1, 2)         # (b, s, nq, hd)
