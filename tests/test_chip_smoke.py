"""chip_smoke.py rehearsed on the CPU at a reduced size: the same cluster
builder, traffic, serving checks and kernel-vs-oracle comparisons (the
kernels in interpret mode), its refusal to run without a TPU, and faults
planted to show the kernel comparison catches them."""
import importlib.util
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

import repro.kernels.paged_decode_attention as paged_pkg
from repro.configs import get_config
from repro.kernels.paged_decode_attention import paged_decode_attention_ref
from repro.launch.serve import build_cluster

ROOT = Path(__file__).resolve().parents[1]
SERVING = dict(paged=True, chunked_prefill=True, prefix_cache=True,
               page_size=8, prefill_chunk=16, max_len=64, max_batch=4)


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _reduced():
    return get_config("llava-next-mistral-7b").reduced()


def test_chip_smoke_refuses_without_tpu(smoke, monkeypatch, capsys):
    monkeypatch.setattr(sys, "argv", ["chip_smoke.py"])
    assert smoke.main() != 0
    assert '"ok"' not in capsys.readouterr().out


def test_chip_smoke_serves_reduced_cluster(smoke, monkeypatch, capsys):
    monkeypatch.setattr(smoke, "TEXT_LEN", 8)
    monkeypatch.setattr(smoke, "LONG_LEN", 24)
    monkeypatch.setattr(smoke, "MAX_NEW", 6)
    cfg = _reduced()
    cluster = build_cluster(cfg, dtype=jnp.float32, **SERVING)
    smoke.serve(cfg, cluster, lambda: 0.0)
    out = capsys.readouterr().out
    assert "served 4 requests, 24 tokens; leaked pages: 0" in out
    # the two requests on one image share one encode
    assert cluster.store.stats.hits >= 1


def test_chip_smoke_kernels_match_oracles_reduced(smoke, capsys):
    assert smoke.check_kernels(_reduced(), SERVING, seed=0)
    assert capsys.readouterr().out.count("PASS") == 3


def test_chip_smoke_kernel_check_catches_dropped_tail_page(smoke,
                                                           monkeypatch,
                                                           capsys):
    """A paged kernel that skips each multi-page slot's last page fails
    the check, while the other two kernels still pass."""
    faithful = paged_pkg.paged_decode_attention

    def drops_tail_page(q, k_pool, v_pool, tbl, lens, **kw):
        page = k_pool.shape[1]
        lens = jnp.where(lens > page, (lens - 1) // page * page, lens)
        return faithful(q, k_pool, v_pool, tbl, lens, **kw)

    monkeypatch.setattr(paged_pkg, "paged_decode_attention", drops_tail_page)
    assert not smoke.check_kernels(_reduced(), SERVING, seed=0)
    out = capsys.readouterr().out
    assert out.count("PASS") == 2
    assert "kernel paged_decode_attention" in out.split("FAIL")[0]


def test_row_bound_catches_dropped_page_of_full_length_row(smoke, capsys):
    """At the served length (one 4096-token slot, page 16, head_dim 128)
    losing the last page moves each row by ~6%, past the bound, while
    the oracle agrees with itself."""
    page, n, hd = 16, 4096, 128
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (1, 4, hd), jnp.bfloat16)
    k_pool = jax.random.normal(kk, (1 + n // page, page, 1, hd),
                               jnp.bfloat16)
    v_pool = jax.random.normal(kv, k_pool.shape, jnp.bfloat16)
    tbl = jnp.arange(1, 1 + n // page, dtype=jnp.int32)[None]

    def attend(length):
        return paged_decode_attention_ref(
            q, k_pool, v_pool, tbl, jnp.array([length], jnp.int32))

    full = attend(n)
    assert smoke.compare("paged_decode_attention", full, full)
    assert not smoke.compare("paged_decode_attention", attend(n - page),
                             full)
    assert "FAIL" in capsys.readouterr().out
