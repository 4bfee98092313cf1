"""Pallas TPU kernel for the Mamba2 SSD chunked scan.

Grid: (batch, heads, chunks) — chunks iterate sequentially per core, the
running (P x N) state lives in VMEM scratch. Intra-chunk work is pure
matmul (MXU): the (L x L) decay-masked score block, the (L x N) chunk
state update, and the (L x P) outputs. This is the TPU-native adaptation
of the SSD algorithm (arXiv:2405.21060): the GPU version leans on warp
shuffles for the intra-chunk cumsum; here the cumsum is a vector op over
an (L,) VMEM tile and everything else is systolic matmul.

B and C are shared across heads (single SSD group) — their index_map
ignores the head coordinate, so each (b, chunk) B/C tile is fetched once
per head loop from HBM but never duplicated in HBM itself.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, dsk_ref, s0_ref,
            y_ref, fin_ref, state_ref, *, nc: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = s0_ref[0, 0].astype(jnp.float32)

    x = x_ref[0, 0, 0].astype(jnp.float32)           # (L, P)
    dt = dt_ref[0, 0, 0, :, 0].astype(jnp.float32)   # (L,)
    a = a_ref[0, 0]                                  # scalar
    bm = b_ref[0, 0].astype(jnp.float32)             # (L, N)
    cm = c_ref[0, 0].astype(jnp.float32)             # (L, N)
    dsk = dsk_ref[0, 0]

    log_da = dt * a                                  # (L,)
    cum = jnp.cumsum(log_da)                         # (L,)
    L = x.shape[0]

    # intra-chunk: y_diag[i] = sum_{j<=i} (C_i.B_j) exp(cum_i-cum_j) dt_j x_j
    seg = cum[:, None] - cum[None, :]
    ii = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    tril = ii >= jj
    decay = jnp.where(tril, jnp.exp(seg), 0.0)
    cb = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    scores = cb * decay                              # (L, L)
    xdt = x * dt[:, None]                            # (L, P)
    y = jax.lax.dot_general(scores, xdt, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)

    # inter-chunk: y_off[i] = (C_i exp(cum_i)) . state_prev^T
    state = state_ref[...]                           # (P, N)
    c_in = cm * jnp.exp(cum)[:, None]                # (L, N)
    y += jax.lax.dot_general(c_in, state, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)

    y_ref[0, 0, 0] = (y + x * dsk).astype(y_ref.dtype)

    # state update: state = state * exp(cum_last) + xdt^T @ (B * decay_to_end)
    decay_end = jnp.exp(cum[-1] - cum)               # (L,)
    b_in = bm * (decay_end * dt)[:, None]            # (L, N)
    new_state = state * jnp.exp(cum[-1]) + jax.lax.dot_general(
        x, b_in, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    state_ref[...] = new_state

    @pl.when(ci == nc - 1)
    def _done():
        fin_ref[0, 0] = new_state


def ssd_scan(x, dt, a, b, c, d_skip, chunk: int,
             init_state: Optional[jax.Array] = None, *,
             interpret: bool = False):
    """Shapes as ssd_chunked: x (B,S,H,P), dt (B,S,H), a (H,), b/c (B,S,N),
    d_skip (H,), init_state (B,H,P,N) or None. Returns (y, final_state)."""
    B, S, H, P = x.shape
    N = b.shape[-1]
    nc = S // chunk
    assert nc * chunk == S, f"seq {S} % chunk {chunk} != 0"

    xt = jnp.moveaxis(x, (1, 2), (2, 1)).reshape(B, H, nc, chunk, P)
    dtt = jnp.moveaxis(dt, 1, 2).reshape(B, H, nc, chunk, 1)
    bt = b.reshape(B, nc, chunk, N)
    ct = c.reshape(B, nc, chunk, N)
    a2 = jnp.broadcast_to(a.astype(jnp.float32)[None], (B, H))
    d2 = jnp.broadcast_to(d_skip.astype(jnp.float32)[None], (B, H))
    s0 = (jnp.zeros((B, H, P, N), jnp.float32) if init_state is None
          else init_state.astype(jnp.float32))

    grid = (B, H, nc)
    kern = functools.partial(_kernel, nc=nc)
    y, fin = pl.pallas_call(
        kern,
        name="ssd_scan",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, chunk, P), lambda bi, h, ci: (bi, h, ci, 0, 0)),
            pl.BlockSpec((1, 1, 1, chunk, 1), lambda bi, h, ci: (bi, h, ci, 0, 0)),
            pl.BlockSpec((1, 1), lambda bi, h, ci: (bi, h)),
            pl.BlockSpec((1, 1, chunk, N), lambda bi, h, ci: (bi, ci, 0, 0)),
            pl.BlockSpec((1, 1, chunk, N), lambda bi, h, ci: (bi, ci, 0, 0)),
            pl.BlockSpec((1, 1), lambda bi, h, ci: (bi, h)),
            pl.BlockSpec((1, 1, P, N), lambda bi, h, ci: (bi, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, chunk, P), lambda bi, h, ci: (bi, h, ci, 0, 0)),
            pl.BlockSpec((1, 1, P, N), lambda bi, h, ci: (bi, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, nc, chunk, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(xt, dtt, a2, bt, ct, d2, s0)
    y = jnp.moveaxis(y.reshape(B, H, S, P), 1, 2)    # (B,S,H,P)
    return y, fin
