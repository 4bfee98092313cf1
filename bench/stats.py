"""Window arithmetic: percentiles, token gaps and rates of one run.

Every request submitted inside the window counts. A request that never
completes reads as a miss: its time to first token is +inf.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional


def percentile(values: Iterable[float], p: float) -> float:
    """Nearest-rank percentile (the smallest value with at least ``p``
    percent of the sample at or below it). +inf entries sort last, so a
    tail that holds a miss reads +inf. An empty sample is an error."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of an empty sample")
    if not 0 < p <= 100:
        raise ValueError(f"percentile {p} outside (0, 100]")
    rank = math.ceil(p / 100.0 * len(xs))
    return xs[rank - 1]


@dataclass
class RequestTimes:
    """Host-clock times of one request: its burst's submit time and the
    time each output token was first seen on the host."""

    submit: float
    tokens: List[float] = field(default_factory=list)
    failed: bool = False

    @property
    def ttft(self) -> float:
        if self.failed or not self.tokens:
            return math.inf
        return self.tokens[0] - self.submit

    def gaps(self) -> List[float]:
        t = self.tokens
        return [b - a for a, b in zip(t, t[1:])]


@dataclass
class Window:
    """All requests of one measured window, keyed by request id."""

    start: float
    requests: Dict[int, RequestTimes] = field(default_factory=dict)

    @property
    def end(self) -> float:
        """Last completion: the latest token seen (the start when none)."""
        last = [r.tokens[-1] for r in self.requests.values() if r.tokens]
        return max(last, default=self.start)

    @property
    def seconds(self) -> float:
        return self.end - self.start

    @property
    def attempted(self) -> int:
        return len(self.requests)

    @property
    def failed(self) -> int:
        return sum(r.failed for r in self.requests.values())

    @property
    def output_tokens(self) -> int:
        return sum(len(r.tokens) for r in self.requests.values()
                   if not r.failed)

    def ttft_p(self, p: float) -> float:
        return percentile((r.ttft for r in self.requests.values()), p)

    def itl_p(self, p: float) -> Optional[float]:
        gaps = [g for r in self.requests.values() if not r.failed
                for g in r.gaps()]
        return percentile(gaps, p) if gaps else None

    def tokens_per_s(self) -> float:
        s = self.seconds
        return self.output_tokens / s if s > 0 else 0.0
