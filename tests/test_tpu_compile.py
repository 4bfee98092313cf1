"""Compile the main-path attention kernels for a TPU v5e without the chip.

The TPU compiler is installed with libtpu and compiles for a chip that is
described (``jax.experimental.topologies``) rather than attached. These
tests catch what interpret mode cannot: block shapes the Mosaic tiling
rules refuse and VMEM/SMEM overruns. Shapes are those of
llava-next-mistral-7b at its published widths (32 q heads, 8 kv heads,
head dim 128) served with page 16, prefill chunk 512, max_len 4096 and 16
decode slots, in bf16. Nothing here runs a kernel; results are checked
against the ``ref.py`` oracles on the chip by ``chip_smoke.py``.
"""
import dataclasses
import math
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import dispatch
from repro.kernels.decode_attention.kernel import decode_attention
from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.paged_decode_attention.kernel import paged_decode_attention
from repro.models.model import init_params
from repro.models.transformer import make_caches
from repro.serving.steps import make_decode_fn

NQ, NKV, HD = 32, 8, 128
PAGE, MAX_LEN, MAX_BATCH, CHUNK = 16, 4096, 16, 512
POOL_PAGES = 1 + (MAX_BATCH + 1) * (MAX_LEN // PAGE)
DEPTH = 8                                  # layers of the compiled step
# one layer's K (or V) pool as the engine holds it, and the kernel's view
POOL = f"bf16[{POOL_PAGES},{PAGE},{NKV},{HD}]"
FLAT = f"bf16[{POOL_PAGES},{PAGE * NKV},{HD}]"
# the decode step's stacked pool flattened over layers, and its view
STACKED = f"bf16[{DEPTH * POOL_PAGES},{PAGE},{NKV},{HD}]"
STACKED_FLAT = f"bf16[{DEPTH * POOL_PAGES},{PAGE * NKV},{HD}]"
SLAB_ELEMS = POOL_PAGES * PAGE * NKV * HD
_INSTR = re.compile(r"\s*(?:ROOT )?%(\S+) = (\S+?)\{.*?\} ([\w-]+)\((.*?)\)")
_COMP = re.compile(r"%(\S+) \(.*\) -> .* \{$")


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                   # no libtpu / no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo
    return hlo


def _instructions(hlo):
    """{name: (shape, opcode, operands, line)} of every array-valued HLO
    instruction, over all computations."""
    instrs = {}
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if m:
            name, shape, op, operands = m.groups()
            instrs[name] = (shape, op, operands.split(", "), line)
    return instrs


def _assert_pool_view_is_bitcast(hlo, pool=POOL, flat=FLAT):
    """The paged kernel reads each pool through a bitcast of the array
    the engine holds, ``pool`` (P, page, nkv, hd): no copy or transpose
    makes the ``flat`` (P, page * nkv, hd) view, so the view moves no HBM
    bytes."""
    instrs = _instructions(hlo)
    views = [o.lstrip("%")
             for shape, op, operands, line in instrs.values()
             if 'custom_call_target="tpu_custom_call"' in line
             for o in operands
             if instrs.get(o.lstrip("%"), ("",))[0] == flat]
    assert len(views) == 2, views                      # K and V
    for v in views:
        _, op, (src,), _ = instrs[v]
        assert op == "bitcast", instrs[v][3]
        assert instrs[src.lstrip("%")][0] == pool, instrs[v][3]
    assert not [line for shape, op, _, line in instrs.values()
                if op in ("copy", "transpose") and shape in (pool, flat)]


def _pool_sized(shape):
    """An array of pages (page, nkv, hd) or of their kernel view
    (page * nkv, hd), at least as large as one layer's pool slab."""
    dims = [int(d) for d in re.search(r"\[(.*)\]", shape).group(1).split(",")
            if d]
    return math.prod(dims) >= SLAB_ELEMS and (
        dims[-3:] == [PAGE, NKV, HD] or dims[-2:] == [PAGE * NKV, HD])


@pytest.mark.parametrize("b", [1, 2])
def test_flash_attention_compiles_for_v5e(one_chip, b):
    """One prefill chunk against the gathered 4096-token row plus itself
    (the suffix/chunk prefill of the paged prefill engine, batch 1), and
    the same at batch 2, where per-row position blocks must stay legal."""
    S = MAX_LEN + CHUNK
    _compile(flash_attention, one_chip,
             ((b, CHUNK, NQ, HD), jnp.bfloat16),
             ((b, S, NKV, HD), jnp.bfloat16),
             ((b, S, NKV, HD), jnp.bfloat16),
             ((b, CHUNK), jnp.int32), ((b, S), jnp.int32))


def test_paged_decode_attention_compiles_for_v5e(one_chip):
    """All decode slots against the decode engine's full page pool."""
    hlo = _compile(paged_decode_attention, one_chip,
                   ((MAX_BATCH, NQ, HD), jnp.bfloat16),
                   ((POOL_PAGES, PAGE, NKV, HD), jnp.bfloat16),
                   ((POOL_PAGES, PAGE, NKV, HD), jnp.bfloat16),
                   ((MAX_BATCH, MAX_LEN // PAGE), jnp.int32),
                   ((MAX_BATCH,), jnp.int32))
    _assert_pool_view_is_bitcast(hlo)


@pytest.fixture(scope="module")
def decode_step_hlo(one_chip):
    """The model's whole paged decode step as the chip deployment runs
    it, compiled: llava-next-mistral-7b at published widths, 8 layers,
    bf16, the compiled kernels."""
    cfg = dataclasses.replace(get_config("llava-next-mistral-7b"),
                              n_layers=DEPTH)
    params = jax.eval_shape(
        lambda key: init_params(cfg, key, dtype=jnp.bfloat16),
        jax.random.PRNGKey(0))
    caches = make_caches(cfg, MAX_BATCH, MAX_LEN, dtype=jnp.bfloat16,
                         abstract=True, layout="paged", page_size=PAGE,
                         n_pages=POOL_PAGES)

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params, caches = jax.tree.map(on_chip, (params, caches))
    tokens = jax.ShapeDtypeStruct((MAX_BATCH,), jnp.int32, sharding=one_chip)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("REPRO_USE_PALLAS", "1")
        mp.setattr(dispatch, "interpret", lambda: False)
        return make_decode_fn(cfg).lower(params, tokens, caches,
                                         key).compile().as_text()


def test_paged_decode_step_compiles_for_v5e(decode_step_hlo):
    """Inside the layer scan the kernel reads the whole stacked pool,
    flattened over layers (its block table is offset to the layer's
    pages), through a bitcast: no layer's slab is sliced out for it."""
    _assert_pool_view_is_bitcast(decode_step_hlo, STACKED, STACKED_FLAT)


def test_paged_decode_step_moves_no_pool_sized_buffer(decode_step_hlo):
    """A decode step writes one token per slot and layer into the pool,
    in place: no copy, transpose, slice, update-slice or fusion makes an
    array as large as one layer's pool slab. The one pool-sized result
    allowed is the in-place scatter of the new tokens, a fusion whose
    root is a ``scatter`` into the carried pool (copy insertion would
    have put an explicit ``copy`` before it if it could not alias)."""
    instrs = _instructions(decode_step_hlo)
    roots, comp = {}, None
    for line in decode_step_hlo.splitlines():
        m = _COMP.match(line)
        if m:
            comp = m.group(1)
        elif line.lstrip().startswith("ROOT ") and (r := _INSTR.match(line)):
            roots[comp] = r.group(3)
    moved = []
    for name, (shape, op, operands, line) in instrs.items():
        if (op not in ("copy", "transpose", "dynamic-slice",
                       "dynamic-update-slice", "fusion")
                or not _pool_sized(shape)):
            continue
        if op == "fusion":
            called = re.search(r"calls=%([\w.-]+)", line).group(1)
            src = instrs.get(operands[0].lstrip("%"), ("",))[0]
            if roots.get(called) == "scatter" and src == shape:
                continue
        moved.append(line.strip()[:200])
    assert not moved, moved
    scatters = [n for n, (shape, op, _, _) in instrs.items()
                if op == "fusion" and shape == STACKED]
    assert len(scatters) == 2, scatters                # K and V


def test_paged_decode_step_names_its_kernel(decode_step_hlo):
    """A profiler trace names each op by its HLO instruction: inside the
    layer scan the paged kernel's call is ``paged_decode_attention.N``,
    the ``name`` its ``pallas_call`` gives, so a trace says which kernel
    ran."""
    names = [m.group(1) for line in decode_step_hlo.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line
             for m in [_INSTR.match(line)] if m]
    assert names
    assert all(n.startswith("paged_decode_attention") for n in names), names


def test_decode_attention_compiles_for_v5e(one_chip):
    """Dense-cache decode at batch 4 over max_len positions."""
    b = 4
    _compile(decode_attention, one_chip,
             ((b, NQ, HD), jnp.bfloat16),
             ((b, MAX_LEN, NKV, HD), jnp.bfloat16),
             ((b, MAX_LEN, NKV, HD), jnp.bfloat16),
             ((b,), jnp.int32), ((b, MAX_LEN), jnp.int32))
