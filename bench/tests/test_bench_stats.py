"""Percentiles and the window's arithmetic."""
import math

import pytest

from bench import stats


def test_nearest_rank_percentile():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 95) == 95
    assert stats.percentile(xs, 100) == 100
    assert stats.percentile([7.0], 95) == 7.0
    assert stats.percentile([3, 1, 2], 50) == 2


def test_percentile_refuses_nothing_to_read():
    with pytest.raises(ValueError):
        stats.percentile([], 95)
    with pytest.raises(ValueError):
        stats.percentile([1], 0)


def test_a_failed_request_is_a_miss():
    w = stats.Window(start=0.0)
    for i in range(19):
        w.requests[i] = stats.RequestTimes(0.0, [1.0 + i * 0.01, 2.0])
    w.requests[19] = stats.RequestTimes(0.0, [], failed=True)
    assert w.attempted == 20 and w.failed == 1
    assert w.ttft_p(95) == pytest.approx(1.18)
    w.requests[18].failed = True
    assert math.isinf(w.ttft_p(95))


def test_gaps_window_and_rate():
    w = stats.Window(start=10.0)
    w.requests[0] = stats.RequestTimes(10.0, [10.5, 10.5, 10.7, 11.0])
    w.requests[1] = stats.RequestTimes(10.0, [12.0, 12.25])
    assert w.requests[0].gaps() == pytest.approx([0.0, 0.2, 0.3])
    assert w.end == 12.25 and w.seconds == pytest.approx(2.25)
    assert w.output_tokens == 6
    assert w.tokens_per_s() == pytest.approx(6 / 2.25)
    assert w.itl_p(100) == pytest.approx(0.3)
    assert w.ttft_p(100) == pytest.approx(2.0)


def test_prefill_chunks_from_spans():
    """Chunk positions from the program's ``prefill.chunk`` spans: a try
    that stalled on the page pool repeats its chunk index and counts
    once; a restart begins at chunk 0 again; the first chunk starts
    where the prefix cache left off."""
    from bench.harness import _chunks
    from repro.core.telemetry import Span

    def chunk(rid, k, n):
        return Span("prefill.chunk", "P0", 0.0, 0.0, rid, None,
                    {"chunk": k, "tokens": n})

    spans = [chunk(1, 0, 508), chunk(2, 0, 508), chunk(2, 0, 508),
             chunk(1, 1, 300), chunk(2, 1, 100),
             chunk(3, 0, 512), chunk(3, 1, 100),
             chunk(3, 0, 512), chunk(3, 0, 512), chunk(3, 1, 88)]
    got = _chunks(spans, {1: 812, 2: 612, 3: 604}, 0, len(spans))
    assert sorted(got) == sorted([
        (4, 512, False), (512, 812, True),
        (4, 512, False), (512, 612, True),
        (4, 516, False), (516, 616, False),    # the aborted run
        (4, 516, False), (516, 604, True)])
    # only the spans inside [lo, hi) are returned
    assert _chunks(spans, {1: 812, 2: 612, 3: 604}, 3, 5) == [
        (512, 812, True), (512, 612, True)]
