"""The window's end-to-end statistics.  Every one is taken over all the
requests and all the time of the window: no median of passes, no chunk
dropped.  A stall therefore moves all three."""

from __future__ import annotations

import math
from typing import Dict, Sequence


def nearest_rank(sorted_values: Sequence[float], pct: float) -> float:
    """The `pct`-th percentile by the nearest-rank rule: the smallest
    value with at least pct % of the sample at or below it."""
    if not sorted_values:
        raise ValueError("percentile of an empty sample")
    rank = max(1, math.ceil(pct / 100.0 * len(sorted_values)))
    return sorted_values[rank - 1]


def window_stats(sent_s: Sequence[float], done_s: Sequence[float]) -> Dict[str, float]:
    """`sent_s[i]`, `done_s[i]`: request i's send and answer times on one
    clock.  Rate = all requests over first send -> last answer."""
    if not sent_s:
        raise ValueError("the window completed no request")
    lat_ms = sorted((d - s) * 1e3 for s, d in zip(sent_s, done_s))
    span_s = max(done_s) - min(sent_s)
    return {
        "queries_per_s": len(lat_ms) / span_s,
        "latency_p50_ms": nearest_rank(lat_ms, 50),
        "latency_p95_ms": nearest_rank(lat_ms, 95),
        "window_s": span_s,
        "samples": len(lat_ms),
    }
