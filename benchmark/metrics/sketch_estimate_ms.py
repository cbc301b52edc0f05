"""`sketch_estimate_ms` (see sketch_estimate_ms.json)."""

import importlib.util
import os

# the shared helper beside this file, loaded by path under a name of its
# own: nothing is added to sys.path or sys.modules
_spec = importlib.util.spec_from_file_location(
    "bench_host_path", os.path.join(os.path.dirname(__file__), "host_path.py")
)
host_path = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(host_path)

SPAN = "sketch_estimate"


def read(window):
    return host_path.median_span_ms(window, SPAN)
