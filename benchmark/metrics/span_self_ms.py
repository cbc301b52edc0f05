"""Shared by the per-layer metrics that read the program's span tree.

The program's tracer folds every request's span tree into a receipt and
stamps it on the request's `QueryMetrics` when the trace closes; under
`receipt["spans"]` it keeps, by span name, how often the span ran and its
self time: its duration less its children's.  The self times of one
request add up to the receipt's `wall_ms`, the root's duration.  A
program without such a key (the parent of the PR that brought it) gives
the readers nothing to read, and they return None.
"""

import statistics


def receipts(window):
    """The receipts of the window's requests that hold `spans`."""
    out = []
    for r in window.requests:
        receipt = getattr(r.metrics, "receipt", None)
        if receipt and "spans" in receipt:
            out.append((r, receipt))
    return out


def median_self_ms(window, names):
    """Median over the window's requests of the summed self time of the
    spans called `names`; None where no request has a span tree."""
    sums = [
        sum(receipt["spans"][n]["self_ms"] for n in names
            if n in receipt["spans"])
        for _, receipt in receipts(window)
    ]
    return statistics.median(sums) if sums else None
