"""`host_groups_per_query` (see host_groups_per_query.json)."""

import statistics


def read(window):
    """Mean QueryMetrics.host_groups over the window's requests; None
    where the program has no such counter."""
    values = [
        r.metrics.host_groups for r in window.requests
        if r.metrics is not None and hasattr(r.metrics, "host_groups")
    ]
    return statistics.fmean(values) if values else None
