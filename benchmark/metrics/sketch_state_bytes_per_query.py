"""`sketch_state_bytes_per_query` (see sketch_state_bytes_per_query.json)."""

import statistics


def read(window):
    """Mean QueryMetrics.sketch_state_bytes over the window's requests;
    None where the program has no such counter (before PR 39)."""
    values = [
        r.metrics.sketch_state_bytes for r in window.requests
        if r.metrics is not None and hasattr(r.metrics, "sketch_state_bytes")
    ]
    return statistics.fmean(values) if values else None
