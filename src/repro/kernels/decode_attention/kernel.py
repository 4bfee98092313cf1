"""Pallas TPU decode attention: one query token vs. a long KV cache.

Flash-decode adapted to the TPU grid model: instead of CUDA-style split-K
across SMs + a second reduction kernel, the kv-block dim is the innermost
(sequential) grid dimension and the running (m, l, acc) lives in VMEM
scratch — the TensorCore streams KV blocks HBM->VMEM while the per-block
math stays on the VPU/MXU. All q-heads of one kv group are processed
together so the (group x block_k) score tile is 2D (MXU/VPU friendly)
even though there is a single query token.

This is the Decode-stage hot loop of the paper's disaggregated serving
system (memory-bound, arithmetic intensity ~= group size).
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
            acc_ref, m_ref, l_ref, *, scale: float, window: Optional[int],
            nk: int):
    bi = pl.program_id(0)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)              # (g, hd)
    k = k_ref[0, 0].astype(jnp.float32)              # (bk, hd)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    qpos = qpos_ref[bi]                              # scalar (SMEM)
    kpos = kpos_ref[0]                               # (1, bk)
    valid = (kpos >= 0) & (kpos <= qpos)
    if window is not None:
        valid &= kpos > qpos - window
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[...]                              # (g, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    p = jnp.where(valid, p, 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(j == nk - 1)
    def _done():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def decode_attention(q, k, v, q_pos, kv_pos, *, window: Optional[int] = None,
                     block_k: int = 512, interpret: bool = False):
    """q: (b, nq, hd); k, v: (b, S, nkv, hd); q_pos: (b,); kv_pos: (b, S)."""
    b, nq, hd = q.shape
    S, nkv = k.shape[1], k.shape[2]
    g = nq // nkv
    block_k = min(block_k, S)

    r = (-S) % block_k
    kt = jnp.moveaxis(k, 2, 1)                        # (b, nkv, S, hd)
    vt = jnp.moveaxis(v, 2, 1)
    kp = kv_pos
    if r:
        kt = jnp.pad(kt, ((0, 0), (0, 0), (0, r), (0, 0)))
        vt = jnp.pad(vt, ((0, 0), (0, 0), (0, r), (0, 0)))
        kp = jnp.pad(kv_pos, ((0, 0), (0, r)), constant_values=-1)
    nk = kt.shape[2] // block_k

    qg = q.reshape(b, nkv, g, hd)
    qp = q_pos.astype(jnp.int32)                      # (b,) scalar prefetch
    kp = kp.astype(jnp.int32)[:, None, :]             # (b, 1, S')

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, nkv, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_k), lambda bi, h, j, p: (bi, 0, j)),
            pl.BlockSpec((1, 1, g, hd), lambda bi, h, j, p: (bi, h, 0, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda bi, h, j, p: (bi, h, j, 0)),
            pl.BlockSpec((1, 1, block_k, hd),
                         lambda bi, h, j, p: (bi, h, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, hd),
                               lambda bi, h, j, p: (bi, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g, hd), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
            pltpu.VMEM((g, 1), jnp.float32),
        ],
    )
    kern = functools.partial(_kernel, scale=hd ** -0.5, window=window, nk=nk)
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, nkv, g, hd), q.dtype),
        interpret=interpret,
    )(qp, kp, qg, kt, vt)
    return out.reshape(b, nq, hd)
