"""Bursts of requests submitted at once, back to back.

One generator for every burst or batch mix; a mix is a data file beside
it (``<mix>.json`` with ``"generator": "burst"``) that gives:

- ``burst``: requests submitted together; the next burst is submitted
  when the previous one has drained.
- ``table``: how many request sizes make up the mix. The sizes are the
  quantiles (i + 0.5) / table of each length distribution, paired and
  ordered by fixed permutations (``table_seed``), so every seed serves
  the same sizes in the same order and with the same arrivals: the seed
  draws only the token ids and the images. ``table`` is a multiple of
  ``burst``; bursts walk the table in that order, pass after pass.
- ``text_tokens`` and ``output_tokens``: distributions, each one of
  ``{"dist": "normal", "mean", "std"}``, ``{"dist": "lognormal",
  "median", "sigma"}`` or ``{"dist": "uniform"}``, clipped to
  ``[min, max]`` and rounded to whole tokens.
- ``template_tokens``: a chat template every prompt starts with (the
  BOS token and the role marker): that many token ids, the same for
  every request and seed, ahead of the drawn text.
- ``image_share``: share of the table's requests that carry one image
  of the configuration's image tokens, placed right after the template
  (one distinct image per request).
- ``warmup_slots`` and ``warmup_hold``: set-up fills that many decode
  slots at once, each held for that many output tokens, so that every
  slot index the mix fills is inserted once before the window.

A request never asks for more than the configuration's ``max_len``:
its output is cut to ``max_len - 1 - prompt`` where needed.
"""
from __future__ import annotations

import math
import statistics
from dataclasses import dataclass
from typing import Dict, Iterator, List, Tuple

import numpy as np

_NORMAL = statistics.NormalDist()


@dataclass(frozen=True)
class Spec:
    """One request: text token ids, output tokens, image payload (or
    None) and the image's position among the prompt tokens."""

    prompt: Tuple[int, ...]
    max_new: int
    image: bytes = None
    image_pos: int = 0


def quantile(dist: Dict, q: float) -> int:
    kind = dist["dist"]
    if kind == "normal":
        x = dist["mean"] + dist["std"] * _NORMAL.inv_cdf(q)
    elif kind == "lognormal":
        x = dist["median"] * math.exp(dist["sigma"] * _NORMAL.inv_cdf(q))
    elif kind == "uniform":
        x = dist["min"] + q * (dist["max"] - dist["min"])
    else:
        raise ValueError(f"unknown distribution {kind!r}")
    return int(min(max(round(x), dist["min"]), dist["max"]))


def size_table(doc: Dict, *, image_tokens: int, max_len: int
               ) -> List[Tuple[int, int, bool]]:
    """The mix's request sizes in the order they are served: (prompt
    text tokens, the template's included; output tokens; has image)."""
    n, burst = doc["table"], doc["burst"]
    if n % burst:
        raise ValueError(f"table {n} is not a multiple of burst {burst}")
    fixed = np.random.RandomState(doc["table_seed"])
    pair = fixed.permutation(n)
    with_image = set(fixed.permutation(n)[:round(doc["image_share"] * n)])
    out = []
    for i in fixed.permutation(n):
        text = quantile(doc["text_tokens"], (i + 0.5) / n) \
            + doc["template_tokens"]
        new = quantile(doc["output_tokens"], (pair[i] + 0.5) / n)
        img = i in with_image
        prompt = text + (image_tokens if img else 0)
        if prompt + 2 > max_len:
            raise ValueError(f"prompt of {prompt} tokens leaves no room "
                             f"for output under max_len {max_len}")
        out.append((text, min(new, max_len - 1 - prompt), img))
    return out


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.Generator(
        np.random.PCG64(np.random.SeedSequence([int(seed), stream])))


def template(doc: Dict, vocab: int) -> Tuple[int, ...]:
    rng = _rng(0, 3)
    return tuple(int(t) for t in rng.integers(3, vocab,
                                              size=doc["template_tokens"]))


def _spec(rng, text, new, img, vocab, head, tag) -> Spec:
    toks = head + tuple(int(t) for t in rng.integers(3, vocab, size=text))
    image = (b"img:" + tag + rng.bytes(16)) if img else None
    return Spec(toks, new, image, len(head) if img else 0)


def bursts(doc: Dict, seed: int, *, vocab: int, image_tokens: int,
           max_len: int) -> Iterator[List[Spec]]:
    """Endless bursts: the size table in its order, pass after pass, cut
    into bursts of ``burst``, with token ids and images drawn from
    ``seed``."""
    table = size_table(doc, image_tokens=image_tokens, max_len=max_len)
    rng = _rng(seed, 0)
    head = template(doc, vocab)
    b = doc["burst"]
    k = 0
    while True:
        for i in range(0, len(table), b):
            out = []
            for text, new, img in table[i:i + b]:
                out.append(_spec(rng, text - len(head), new, img, vocab,
                                 head, b"%d:%d" % (seed, k)))
                k += 1
            yield out


def warmup(doc: Dict, *, vocab: int, image_tokens: int, max_len: int,
           page: int) -> List[Spec]:
    """Requests that compile every program shape the mix produces, the
    same for every seed. First ``warmup_slots`` requests (at least one;
    the distinct (image or not, prompt pages) classes of the table in
    turn) that each hold a decode slot for ``warmup_hold`` tokens, so
    that every slot index the mix fills is inserted. Then, with two
    output tokens, every class not yet served after the first request:
    the first one found the prefix cache empty, and every later prompt
    hits it for the template, as the window's do. The prefill window
    widths and page-copy lengths follow from the classes."""
    table = size_table(doc, image_tokens=image_tokens, max_len=max_len)
    classes = {}
    for text, new, img in table:
        prompt = text + (image_tokens if img else 0)
        classes.setdefault((img, -(-prompt // page)), (text, img))
    reps = [classes[k] for k in sorted(classes)]
    holders = max(doc["warmup_slots"], 1)
    picks = [(reps[i % len(reps)], doc["warmup_hold"])
             for i in range(holders)]
    seen = {i % len(reps) for i in range(1, holders)}
    picks += [(r, 2) for i, r in enumerate(reps) if i not in seen]
    rng = _rng(0, 1)
    head = template(doc, vocab)
    out = []
    for i, ((text, img), hold) in enumerate(picks):
        prompt = text + (image_tokens if img else 0)
        out.append(_spec(rng, text - len(head),
                         min(hold, max_len - 1 - prompt), img, vocab, head,
                         b"warm%d" % i))
    return out
