"""`untraced_ms`: what the client waited for and no span of the program
covers (see untraced_ms.json)."""

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


def read(window):
    outside = [
        r.wall_ms - receipt["wall_ms"]
        for r, receipt in span_self_ms.receipts(window)
    ]
    return statistics.median(outside) if outside else None
