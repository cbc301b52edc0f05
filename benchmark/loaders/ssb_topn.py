"""Loader of the `ssb-sf10-topn-hll` configuration: the SSB system of
`ssb.py` (the same data from the seed, ingest, dictionaries and served
`OlapServer`), sent the traffic file's native Druid JSON, and the topN
reference child `ssb_topn_data.py`.

`ssb.py` is loaded by path and reused as it is; only what is sent, what
the reference computes and how an answer is read differ.  An answer is
read as the long frame `(rank, key, measure, value)` the reference makes:
`harness/compare.py` then checks the ranks, the 100 keys and both measures
without a change (keys are every column but the last).
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))

_spec = importlib.util.spec_from_file_location(
    "bench_ssb_for_topn", os.path.join(HERE, "ssb.py")
)
ssb = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ssb)

MEASURES = ("revenue", "uniq_custs")  # the traffic's aggregation names
COLUMNS = ["rank", "key", "measure", "value"]  # ssb_topn_data.COLUMNS


class Reference(ssb.Reference):
    """`ssb_topn_data.py` as a child process, with the queries' native
    JSON handed over in a file of its own."""

    def __init__(self, config, queries, seed, scale, precisions):
        fd, self.queries_file = tempfile.mkstemp(
            prefix="bench_ref_q_", suffix=".json"
        )
        with os.fdopen(fd, "w") as f:
            json.dump({q["name"]: q["native"] for q in queries}, f)
        fd, self.out = tempfile.mkstemp(prefix="bench_ref_", suffix=".pkl")
        os.close(fd)
        cmd = [
            sys.executable, os.path.join(HERE, "ssb_topn_data.py"),
            "--scale", repr(float(scale)), "--seed", str(int(seed)),
            "--queries", self.queries_file, "--out", self.out,
        ]
        for p in precisions:
            cmd += ["--precision", p]
        import subprocess

        self.proc = subprocess.Popen(
            cmd, env={**os.environ, "JAX_PLATFORMS": "cpu"},
            stdout=subprocess.DEVNULL,
        )

    def close(self):
        super().close()
        if os.path.exists(self.queries_file):
            os.remove(self.queries_file)


def start_reference(config, queries, seed, scale, precisions=("float32",)):
    return Reference(config, queries, seed, scale, precisions)


class System(ssb.System):
    """The SSB system, sent native JSON at the configuration's endpoint."""

    def send(self, query):
        status, body = ssb.post(
            self.server.port, self.endpoint, query["native"]
        )
        return status, body, (self.ctx.last_metrics if status == 200 else None)


def start_system(config, seed, scale, say):
    return System(config, seed, scale, say)


def to_frame(body):
    """Druid's topN answer `[{"timestamp", "result": [row, ...]}]` as the
    long frame: per row its rank, its dimension value (the one key that is
    not a measure) and one line per measure."""
    import pandas as pd

    rows = []
    for bucket in body:
        for rank, row in enumerate(bucket["result"]):
            (key,) = [str(v) for k, v in row.items() if k not in MEASURES]
            for m in MEASURES:
                rows.append((rank, key, m, float(row[m])))
    return pd.DataFrame(rows, columns=COLUMNS)
