"""The dense decoder family (Llama / Mistral / DeepSeek LLM, and LLaVA's
language model): attention and a gated MLP in every layer, rotary
embeddings, an untied LM head, an optional linear image projector.

A configuration file names its family by ``"reference"``; the harness
finds ``bench/models/<reference>.py`` and takes from it:

- ``Arch.from_doc(model)`` and ``logits(params, arch, *, seq, read,
  image, image_pos, length, n_read, quant=None)``: the plain float32
  reference and, with ``quant="fp8"``, its control;
- ``work(doc)``: the counts the per-layer readers divide by;
- ``init_rules`` (optional): ``{init: fn(key, shape, dtype)}`` for every
  ``ParamSpec.init`` beyond ``normal``, ``ones`` and ``zeros``. This
  family has none.

Here the reference is ``bench/reference.py`` and the counts are
``bench/work.py``'s, with every layer an attention layer.
"""
from __future__ import annotations

from typing import Iterable, Tuple

from bench import reference
from bench import work as W

Arch = reference.Arch
logits = reference.logits


class Counts:
    """Operations and bytes of the model's work, for the readers.

    ``attn_layers`` is how many layers hold the attention kernels: the
    kernels' counts are one layer's (``work.flash_chunk``,
    ``work.paged_decode``) times that many, so a family whose other
    layers are not attention reuses this with its own number."""

    def __init__(self, dims: W.Dims, attn_layers: int):
        self.dims = dims
        self.attn_layers = attn_layers

    def prefill_flops(self, chunks: Iterable[Tuple[int, int, bool]]) -> int:
        """Model flops of prefill chunks, each (start, end, last)."""
        return W.prefill_flops(self.dims, chunks)

    def decode_flops(self, steps: Iterable[Iterable[int]]) -> int:
        """Model flops of decode steps, each the key lengths of its slots."""
        return W.decode_flops(self.dims, steps)

    def encode_flops(self, image_tokens: int) -> int:
        return W.encode_flops(self.dims, image_tokens)

    def flash(self, chunks: Iterable[Tuple[int, int, bool]]
              ) -> Tuple[int, int]:
        """(flops, bytes) of the flash_attention kernel over the prefill
        chunks, summed over the attention layers."""
        counts = [W.flash_chunk(self.dims, a, b) for a, b, _ in chunks]
        L = self.attn_layers
        return L * sum(f for f, _ in counts), L * sum(b for _, b in counts)

    def paged_decode(self, steps: Iterable[Iterable[int]]
                     ) -> Tuple[int, int]:
        """(flops, bytes) of the paged_decode_attention kernel over the
        decode steps, summed over the attention layers."""
        counts = [W.paged_decode(self.dims, ls) for ls in steps]
        L = self.attn_layers
        return L * sum(f for f, _ in counts), L * sum(b for _, b in counts)

    roofline_s = staticmethod(W.roofline_s)


def dims(doc: dict) -> W.Dims:
    m = doc["model"]
    return W.Dims(m["num_hidden_layers"], m["hidden_size"],
                  m["num_attention_heads"], m["num_key_value_heads"],
                  m["head_dim"], m["intermediate_size"], m["vocab_size"],
                  m.get("vision_feature_dim", 0))


def work(doc: dict) -> Counts:
    d = dims(doc)
    return Counts(d, d.n_layers)
