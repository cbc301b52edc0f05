"""`fetch_wait_ms`: the self time of SPANS (see fetch_wait_ms.json)."""

import importlib.util
import os

# the shared helper beside this file, loaded by path under a name of its
# own: nothing is added to sys.path or sys.modules
_spec = importlib.util.spec_from_file_location(
    "bench_span_self_ms", os.path.join(os.path.dirname(__file__), "span_self_ms.py")
)
span_self_ms = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(span_self_ms)

SPANS = ('device_fetch',)


def read(window):
    return span_self_ms.median_self_ms(window, SPANS)
