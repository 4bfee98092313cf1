"""Operations and bytes the algorithm needs, from shapes and true lengths.

These count the work of the computation, not of one implementation of
it: a causal query at absolute position p attends p + 1 keys, an idle
decode slot does nothing, and columns a kernel masks do not count. So
the same work reads the same whatever computes it, and a share of a
roofline or of the chip's peak cannot pass 100% unless the time left
out part of the work.

``Dims`` holds a dense decoder's widths (attention + gated MLP per layer,
untied LM head, optional linear image projector). Byte counts assume
``act_bytes`` per element of weights, activations and KV (bf16: 2).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Iterable, Tuple


@dataclass(frozen=True)
class Dims:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    feature_dim: int = 0          # image feature width (0: no projector)
    act_bytes: int = 2

    @property
    def layer_params(self) -> int:
        """Matmul weights of one layer: q, k, v, o and gate, up, down."""
        d, q = self.d_model, self.n_heads * self.head_dim
        kv = self.n_kv_heads * self.head_dim
        return d * q + 2 * d * kv + q * d + 3 * d * self.d_ff


def causal_keys(start: int, end: int) -> int:
    """Keys attended by the queries at positions [start, end) of a
    causal sequence: sum over p of (p + 1)."""
    if not 0 <= start <= end:
        raise ValueError(f"bad span [{start}, {end})")
    return (end * (end + 1) - start * (start + 1)) // 2


def attention_flops(dims: Dims, keys: int) -> int:
    """Q.K^T and P.V over ``keys`` (query, key) pairs, every head."""
    return 4 * dims.n_heads * dims.head_dim * keys


def flash_chunk(dims: Dims, start: int, end: int) -> Tuple[int, int]:
    """(flops, bytes) of one layer's causal attention for the prefill
    chunk of positions [start, end) over keys [0, end): reads q, k, v of
    those positions once and writes the output once."""
    n = end - start
    hd = dims.head_dim
    flops = attention_flops(dims, causal_keys(start, end))
    elems = (2 * n * dims.n_heads * hd            # q in, out
             + 2 * end * dims.n_kv_heads * hd)     # k, v
    return flops, elems * dims.act_bytes


def paged_decode(dims: Dims, lengths: Iterable[int]) -> Tuple[int, int]:
    """(flops, bytes) of one layer's decode attention for the active
    slots, each attending ``length`` keys (the new token included):
    every key and value read once, one query in and one row out."""
    hd = dims.head_dim
    flops = elems = 0
    for n in lengths:
        flops += attention_flops(dims, n)
        elems += 2 * n * dims.n_kv_heads * hd + 2 * dims.n_heads * hd
    return flops, elems * dims.act_bytes


def prefill_chunk_flops(dims: Dims, start: int, end: int,
                        last: bool) -> int:
    """Model flops of one prefill chunk of positions [start, end): every
    layer's matmuls and attention, plus the LM head once, for the logits
    of the request's last prompt token (``last``)."""
    n = end - start
    f = dims.n_layers * (2 * dims.layer_params * n
                         + attention_flops(dims, causal_keys(start, end)))
    if last:
        f += 2 * dims.d_model * dims.vocab
    return f


def decode_step_flops(dims: Dims, lengths: Iterable[int]) -> int:
    """Model flops of one decode step over the active slots."""
    f = 0
    for n in lengths:
        f += dims.n_layers * (2 * dims.layer_params
                              + attention_flops(dims, n))
        f += 2 * dims.d_model * dims.vocab
    return f


def prefill_flops(dims: Dims, chunks: Iterable[Tuple[int, int, bool]]
                  ) -> int:
    """Model flops of prefill chunks, each (start, end, last)."""
    return sum(prefill_chunk_flops(dims, a, b, last) for a, b, last in chunks)


def decode_flops(dims: Dims, steps: Iterable[Iterable[int]]) -> int:
    """Model flops of decode steps, each the key lengths of its slots."""
    return sum(decode_step_flops(dims, ls) for ls in steps)


def encode_flops(dims: Dims, image_tokens: int) -> int:
    """The image projector: features (tokens, feature_dim) @ (feature_dim,
    d_model)."""
    return 2 * image_tokens * dims.feature_dim * dims.d_model


def roofline_s(flops: int, nbytes: int, peak_flops: float,
               peak_bytes_s: float) -> Tuple[float, str]:
    """Least time the chip could take, and which bound sets it."""
    tf, tb = flops / peak_flops, nbytes / peak_bytes_s
    return (tf, "compute") if tf >= tb else (tb, "memory")
