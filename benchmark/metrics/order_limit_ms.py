"""`order_limit_ms` (see order_limit_ms.json)."""

import importlib.util
import os

# the shared helper beside this file, loaded by path under a name of its
# own: nothing is added to sys.path or sys.modules
_spec = importlib.util.spec_from_file_location(
    "bench_span_self_ms", os.path.join(os.path.dirname(__file__), "span_self_ms.py")
)
span_self_ms = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(span_self_ms)

SPAN = "order_limit"


def read(window):
    """Median self time of `order_limit` over the window's requests, a
    request without one counted as 0; None where the program registers
    no such span."""
    from spark_druid_olap_tpu.obs import SPAN_NAMES

    if SPAN not in SPAN_NAMES:
        return None
    return span_self_ms.median_self_ms(window, (SPAN,))
