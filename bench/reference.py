"""Plain float32 reference of a dense decoder: the yardstick for ``correct``.

Written from the published architecture (Llama/Mistral family: RMSNorm,
rotary embeddings in the rotate-half form, grouped-query causal
attention, SiLU-gated MLP, untied LM head; LLaVA's image features
projected to the model width and placed at the image's positions). It
imports nothing of the program: it reads the weights the benchmark made
by their names in the served parameter tree, and computes everything
else itself, in float32 under ``highest`` matmul precision.

It runs layer by layer (a scan over the stacked layers, each layer's
bfloat16 weights widened to float32 inside it) and attends in blocks of
queries, so a whole served sequence fits beside the weights.

``quant="fp8"`` gives the control: the same forward with every matmul
operand and the keys and values rounded to float8 e4m3 (per-tensor
absmax scale), the precision below the bfloat16 the configurations
state. It has to come out as not correct.
"""
from __future__ import annotations

import functools
import hashlib
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

_Q_BLOCK = 512
_E4M3_MAX = 448.0


@dataclass(frozen=True)
class Arch:
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    eps: float
    image_tokens: int = 0
    feature_dim: int = 0

    @classmethod
    def from_doc(cls, model: dict) -> "Arch":
        return cls(model["num_hidden_layers"], model["hidden_size"],
                   model["num_attention_heads"], model["num_key_value_heads"],
                   model["head_dim"], model["intermediate_size"],
                   model["vocab_size"], model["rope_theta"],
                   model["rms_norm_eps"], model.get("image_tokens", 0),
                   model.get("vision_feature_dim", 0))


def image_patches(payload: bytes, n: int, dim: int) -> jax.Array:
    """The image as the served system sees it: its encoder is a stub
    whose patch embeddings are N(0, 0.02^2) draws keyed by the first four
    bytes of the image's SHA-256. This is the input, as pixels would be;
    the projection and everything after it are computed here."""
    seed = int.from_bytes(hashlib.sha256(payload).digest()[:4], "big")
    return jax.random.normal(jax.random.PRNGKey(seed), (n, dim),
                             jnp.float32) * 0.02


def _fp8(x):
    amax = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
    s = _E4M3_MAX / amax
    return (x * s).astype(jnp.float8_e4m3fn).astype(jnp.float32) / s


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None, None].astype(jnp.float32) * inv       # (L, 1, half)
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _forward(params, tokens, feats, mm_pos, read_pos, *, a: Arch,
             quant: Optional[str]):
    q8 = _fp8 if quant == "fp8" else (lambda x: x)
    f32 = lambda x: x.astype(jnp.float32)

    def mm(x, w):
        return q8(x) @ q8(f32(w))

    L = tokens.shape[0]
    pos = jnp.arange(L, dtype=jnp.int32)
    x = f32(params["embed"][tokens])
    if feats is not None:
        img = mm(feats, params["projector"])                   # (n, d)
        rel = pos - mm_pos
        inside = (rel >= 0) & (rel < img.shape[0])
        x = jnp.where(inside[:, None],
                      img[jnp.clip(rel, 0, img.shape[0] - 1)], x)
    nq, nkv, hd = a.n_heads, a.n_kv_heads, a.head_dim
    g = nq // nkv

    def layer(x, p):
        at, ml = p["attn"], p["mlp"]
        h = _rms(x, f32(at["norm"]), a.eps)
        q = _rope(mm(h, at["wq"]).reshape(L, nq, hd), pos, a.rope_theta)
        k = _rope(mm(h, at["wk"]).reshape(L, nkv, hd), pos, a.rope_theta)
        v = mm(h, at["wv"]).reshape(L, nkv, hd)
        k, v = q8(k), q8(v)
        outs = []
        for s in range(0, L, _Q_BLOCK):
            qb = q[s:s + _Q_BLOCK].reshape(-1, nkv, g, hd)
            sc = jnp.einsum("qkgh,Tkh->kgqT", qb, k) * hd ** -0.5
            causal = pos[None, :] <= pos[s:s + _Q_BLOCK, None]
            sc = jnp.where(causal[None, None], sc, -jnp.inf)
            pr = jax.nn.softmax(sc, axis=-1)
            outs.append(jnp.einsum("kgqT,Tkh->qkgh", pr, v)
                        .reshape(-1, nq * hd))
        x = x + mm(jnp.concatenate(outs, 0), at["wo"])
        h = _rms(x, f32(ml["norm"]), a.eps)
        gate, up = mm(h, ml["wg"]), mm(h, ml["wi"])
        return x + mm(jax.nn.silu(gate) * up, ml["wo"]), None

    x, _ = jax.lax.scan(layer, x, params["blocks"][0])
    h = _rms(x[read_pos], f32(params["final_norm"]), a.eps)
    return mm(h, params["lm_head"])                           # (R, vocab)


@functools.lru_cache(maxsize=None)
def _compiled(a: Arch, quant: Optional[str], with_image: bool):
    def fn(params, tokens, feats, mm_pos, read_pos):
        with jax.default_matmul_precision("highest"):
            return _forward(params, tokens, feats if with_image else None,
                            mm_pos, read_pos, a=a, quant=quant)
    return jax.jit(fn)


def logits(params, a: Arch, *, seq, read, image=None, image_pos=0,
           length: int, n_read: int, quant: Optional[str] = None
           ) -> np.ndarray:
    """Logits (len(read), vocab) at positions ``read`` of the token
    sequence ``seq`` (placeholders where the image sits). ``length`` and
    ``n_read`` are the padded sizes, fixed per configuration so that one
    program serves every request."""
    if len(seq) > length or len(read) > n_read:
        raise ValueError(f"sequence {len(seq)}/{length} or reads "
                         f"{len(read)}/{n_read} beyond the padded sizes")
    toks = np.zeros(length, np.int32)
    toks[:len(seq)] = seq
    rp = np.zeros(n_read, np.int32)
    rp[:len(read)] = read
    feats = (image_patches(image, a.image_tokens, a.feature_dim)
             if image is not None else jnp.zeros((1, 1), jnp.float32))
    fn = _compiled(a, quant, image is not None)
    out = fn(params, jnp.asarray(toks), feats, jnp.int32(image_pos),
             jnp.asarray(rp))
    return np.asarray(out)[:len(read)]
