import statistics


def read(window):
    """Mean bytes a device sent in a request's collectives, as the program
    counted them (`QueryMetrics.collective_bytes`).  None where the
    program has no such field."""
    values = [
        r.metrics.collective_bytes for r in window.requests
        if r.metrics is not None and hasattr(r.metrics, "collective_bytes")
    ]
    return float(statistics.fmean(values)) if values else None
