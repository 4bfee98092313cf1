"""The comparison that decides ``correct``.

After the window: a sample of the requests the window finished, drawn
from the seed with the longest among them, until it holds
``check_tokens`` served tokens. The reference (the plain one of the
configuration's model family, ``bench/models/<reference>.py``) runs
once over each prompt with its served tokens; at every position that
produced a served token it reads how far the served token's logit lies
below the reference's best logit there. The widest such gap over the
sample is compared with the configuration's limit. A greedy server that
computes what the reference computes reads 0 up to rounding near ties.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

import numpy as np


@dataclass
class Served:
    """One finished request as the client saw it."""

    prompt: Sequence[int]
    image: Optional[bytes]
    image_pos: int
    image_tokens: int
    output: Sequence[int]

    @property
    def prompt_len(self) -> int:
        return len(self.prompt) + (self.image_tokens if self.image else 0)

    def sequence(self) -> List[int]:
        p = list(self.prompt)
        if self.image:
            p = p[:self.image_pos] + [0] * self.image_tokens \
                + p[self.image_pos:]
        return p + list(self.output[:-1])

    def read_positions(self) -> List[int]:
        n = self.prompt_len
        return list(range(n - 1, n - 1 + len(self.output)))


def sample(served: List[Served], rng: np.random.Generator,
           tokens: int) -> List[Served]:
    """The longest request first, then others in seeded order, until the
    sample holds ``tokens`` served tokens (or every request)."""
    if not served:
        return []
    longest = max(range(len(served)),
                  key=lambda i: (len(served[i].output), served[i].prompt_len))
    order = [longest] + [int(i) for i in rng.permutation(len(served))
                         if i != longest]
    out, n = [], 0
    for i in order:
        if n >= tokens:
            break
        out.append(served[i])
        n += len(served[i].output)
    return out


def compared(got: Dict[str, float], limit: Optional[float], *,
             failed: int) -> Dict[str, Dict[str, float]]:
    """Each number compared beside its limit: the widest logit gap and
    the requests that never finished."""
    return {"max_logit_gap": {"value": got["max_logit_gap"], "limit": limit},
            "failed_requests": {"value": failed, "limit": 0}}


def verdict(numbers: Dict[str, Dict[str, float]],
            got: Dict[str, float]) -> bool:
    """``correct``: something was compared, and every number compared is
    within its limit (a limit of ``None`` is never met)."""
    return got["tokens_compared"] > 0 and all(
        c["limit"] is not None and c["value"] <= c["limit"]
        for c in numbers.values())


def gaps(ref_logits: np.ndarray, tokens: Sequence[int]) -> np.ndarray:
    """How far each token's logit lies below the best logit of its row."""
    ref_logits = np.asarray(ref_logits, np.float64)
    rows = np.arange(len(tokens))
    return ref_logits.max(-1) - ref_logits[rows, np.asarray(tokens)]


def readings(params, model, arch, reqs: List[Served], *, length: int,
             n_read: int, control: bool = False) -> Dict[str, float]:
    """``max_logit_gap`` of the served tokens over ``reqs``, by the
    reference of the configuration's model family (``model.logits`` on
    ``arch``, its ``Arch``); with ``control`` also that of the tokens the
    fp8 control would put first at the same positions
    (``control_max_logit_gap``)."""
    worst = ctl = 0.0
    n = 0
    for r in reqs:
        kw = dict(seq=r.sequence(), read=r.read_positions(),
                  image=r.image, image_pos=r.image_pos, length=length,
                  n_read=n_read)
        ref = model.logits(params, arch, **kw)
        if not np.isfinite(ref).all():
            raise FloatingPointError("reference logits are not finite")
        worst = max(worst, float(gaps(ref, r.output).max()))
        n += len(r.output)
        if control:
            low = model.logits(params, arch, quant="fp8", **kw)
            ctl = max(ctl, float(gaps(ref, low.argmax(-1)).max()))
    out = {"max_logit_gap": worst, "tokens_compared": n,
           "requests_compared": len(reqs)}
    if control:
        out["control_max_logit_gap"] = ctl
    return out
