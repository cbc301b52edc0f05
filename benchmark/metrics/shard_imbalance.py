import statistics


def read(window):
    """Mean of (steps every shard ran x shards) / segments in scope: 1.0
    is an even deal of the scope.  None where the program has no such
    fields or no request stepped a shard through a segment in scope."""
    ratios = []
    for r in window.requests:
        m = r.metrics
        if m is None or not hasattr(m, "shard_steps"):
            continue
        if m.segments and m.shards and m.shard_steps:
            ratios.append(m.shard_steps * m.shards / m.segments)
    return float(statistics.fmean(ratios)) if ratios else None
