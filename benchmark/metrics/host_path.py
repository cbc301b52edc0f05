"""Shared by the per-layer metrics that name the host path (PR 37): the
ones that read a span the program did not have before, and the ones that
read a field of the receipt other than `spans`.

`span_self_ms.median_self_ms` counts a span a request lacks as nothing,
so over a program that has no such span at all it reads 0.0.  These
readers return None there (the parent of the PR that brought the span:
the metric is left out of the line), and likewise where no receipt holds
the field.
"""

import importlib.util
import os
import statistics

# the shared helper beside this file, loaded by path under a name of its
# own: nothing is added to sys.path or sys.modules
_spec = importlib.util.spec_from_file_location(
    "bench_span_self_ms", os.path.join(os.path.dirname(__file__), "span_self_ms.py")
)
span_self_ms = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(span_self_ms)


def median_span_ms(window, name):
    """Median over the window's requests of the self time of the span
    `name`; None where no request's span tree holds one."""
    if not any(name in receipt["spans"]
               for _, receipt in span_self_ms.receipts(window)):
        return None
    return span_self_ms.median_self_ms(window, (name,))


def receipt_values(window, *path):
    """`receipt[path[0]][path[1]]...` of every request whose receipt
    holds it."""
    out = []
    for r in window.requests:
        at = getattr(r.metrics, "receipt", None)
        for key in path:
            at = at.get(key) if isinstance(at, dict) else None
        if at is not None:
            out.append(at)
    return out


def median_field(window, *path):
    values = receipt_values(window, *path)
    return statistics.median(values) if values else None


def mean_field(window, *path):
    values = receipt_values(window, *path)
    return statistics.fmean(values) if values else None
