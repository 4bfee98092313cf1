"""A whole run, the look for a chip skipped, with the timed path broken
underneath: ``correct`` has to come out false for each fault a serving
cell can have, and true with none."""
import functools
import time

import jax
import jax.numpy as jnp
import pytest

from bench import harness
from repro.serving import engine as E


def _decode_fault(kind):
    make = E.make_decode_fn

    def broken(cfg, temperature=0.0):
        fn = make(cfg, temperature)

        @functools.wraps(fn)
        def step(params, tokens, caches, key):
            if kind == "token":          # a token altered where produced
                tok, new = fn(params, tokens, caches, key)
                return (tok + 1) % cfg.vocab, new
            # the step returns its state unchanged: no KV written, the
            # lengths not advanced (the step donates what it is given)
            keep = jax.tree.map(jnp.copy, caches)
            tok, _ = fn(params, tokens, caches, key)
            return tok, keep
        return step
    return broken


def _prefill_token_fault(monkeypatch):
    finish = E.PrefillTask.finish

    def broken(self):
        first, payload = finish(self)
        return (first + 1) % self.eng.cfg.vocab, payload
    monkeypatch.setattr(E.PrefillTask, "finish", broken)


@pytest.mark.parametrize("fault", ["none", "decode_token", "decode_state",
                                   "prefill_token"])
def test_correct_sees_the_fault(tiny_cell, monkeypatch, fault, tmp_path):
    if fault == "decode_token":
        monkeypatch.setattr(E, "make_decode_fn", _decode_fault("token"))
    elif fault == "decode_state":
        monkeypatch.setattr(E, "make_decode_fn", _decode_fault("state"))
    elif fault == "prefill_token":
        _prefill_token_fault(monkeypatch)
    cell = tiny_cell("llava-sharegpt4o-burst16")
    out = harness.run_cell(cell, seed=2**31 + 7, seconds=0.5, trace=False,
                           t_process=time.perf_counter(), out_dir=tmp_path,
                           log=lambda s: None)
    assert out["correct"] is (fault == "none"), out["check"]
    assert list(out)[-1] == "check"
    assert set(out["metrics"]) == {"ttft_p50_ms", "itl_p95_ms",
                                   "output_tok_per_s", "setup_s"}
