import statistics


def read(window):
    """Mean device program launches per request, from the receipt the
    program stamps on QueryMetrics (its span tree's launch spans)."""
    counts = [
        r.metrics.receipt["dispatch_count"] for r in window.requests
        if r.metrics is not None and r.metrics.receipt
        and "dispatch_count" in r.metrics.receipt
    ]
    return statistics.fmean(counts) if counts else None
