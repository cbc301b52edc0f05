import statistics


def read(window):
    """Median of what a request spent outside the engine: client wall
    minus QueryMetrics.total_ms."""
    outside = [
        r.wall_ms - r.metrics.total_ms for r in window.requests
        if r.metrics is not None
    ]
    return statistics.median(outside) if outside else None
