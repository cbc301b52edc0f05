"""Loader of the SSB configurations: the data from the seed, the system
under test (ingest -> context -> `OlapServer` over HTTP) and the reference
child.  The generator and the reference live in `ssb_data.py`, which
imports nothing of the program; this file is the only one of the pair
that does, and only to hand the program its input in the program's own
ingest format (dictionaries, star schema) and to read its counters.

The served loop (`post`, one connection per request, `ctx.last_metrics`
after each answer) is COPIED from `chip_smoke.py` at commit 1f06452 and
not to track it.
"""

from __future__ import annotations

import http.client
import json
import os
import subprocess
import sys
import tempfile
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
import ssb_data  # noqa: E402  (beside this file, found by path)

sys.path.remove(HERE)

VALID = "__valid"  # pseudo-columns of the traffic files' column lists
TIME = "__time"


# ---------------------------------------------------------------------------
# the reference child
# ---------------------------------------------------------------------------


class Reference:
    """`ssb_data.py` as a child process: started before ingest, joined
    after the window.  `JAX_PLATFORMS=cpu` in its environment, and it
    imports no JAX at all: it cannot touch the chip."""

    def __init__(self, config, queries, seed, scale, precisions):
        columns = sorted(
            {c for q in queries for c in q["columns"] if not c.startswith("__")}
        )
        fd, self.out = tempfile.mkstemp(prefix="bench_ref_", suffix=".pkl")
        os.close(fd)
        cmd = [
            sys.executable, os.path.join(HERE, "ssb_data.py"),
            "--scale", repr(float(scale)), "--seed", str(int(seed)),
            "--queries", ",".join(q["name"] for q in queries),
            "--columns", ",".join(columns), "--out", self.out,
        ]
        for p in precisions:
            cmd += ["--precision", p]
        self.proc = subprocess.Popen(
            cmd, env={**os.environ, "JAX_PLATFORMS": "cpu"},
            stdout=subprocess.DEVNULL,
        )

    def join(self, timeout_s: float) -> dict:
        """{precision: {query: answer}, "seconds": ...}; raises when the
        child failed or is not done `timeout_s` after the call.  The caller
        closes, whatever happens."""
        import pickle

        rc = self.proc.wait(timeout=timeout_s)
        if rc != 0:
            raise RuntimeError(f"the reference child exited with {rc}")
        with open(self.out, "rb") as f:
            return pickle.load(f)

    def close(self):
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait()
        for p in (self.out, self.out + ".tmp"):
            if os.path.exists(p):
                os.remove(p)


def start_reference(config, queries, seed, scale, precisions=("float32",)):
    return Reference(config, queries, seed, scale, precisions)


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------


def _attr_dicts(tables):
    """Per flat attribute: (the program's dictionary, encoded dim-table
    codes): built on the small dimension tables; fact rows gather
    through the FK.  (From `workloads/ssb._attr_dicts`.)"""
    from spark_druid_olap_tpu.catalog.segment import DimensionDict, code_dtype

    out = {}
    for attr, (table, _) in ssb_data.DIM_ATTRS.items():
        vals = tables[table][attr]
        if vals.dtype.kind in ("U", "S", "O"):
            d = DimensionDict.build(list(vals))
            dim_codes = d.encode(list(vals))
        else:
            uniq = np.unique(vals.astype(np.int64))
            d = DimensionDict(values=tuple(int(v) for v in uniq))
            dim_codes = d.encode_numeric(vals)
        out[attr] = (d, dim_codes.astype(code_dtype(d.cardinality)))
    return out


def _flat_chunk(lo, tables, attr_dicts):
    """One chunk of fact rows -> flat encoded columns (gathers only)."""
    cols = {
        "lo_orderdate": lo["lo_orderdate"],
        **{m: lo[m] for m in ssb_data.FLAT_METRICS},
    }
    idx = {}
    for attr, (table, fk_col) in ssb_data.DIM_ATTRS.items():
        if table not in idx:
            idx[table] = ssb_data._fk_row_index(
                lo, fk_col, table, tables["dwdate"]
            )
        cols[attr] = attr_dicts[attr][1][idx[table]]
    return cols


def post(port, path, body):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=600)
    try:
        conn.request(
            "POST", path, json.dumps(body),
            {"Content-Type": "application/json"},
        )
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read())
    finally:
        conn.close()


class System:
    """The program, loaded and serving: built from the configuration's
    settings, fed through its own ingest entry, reached over HTTP."""

    def __init__(self, config, seed, scale, say):
        from spark_druid_olap_tpu import TPUOlapContext
        from spark_druid_olap_tpu.config import SessionConfig
        from spark_druid_olap_tpu.ingest.shard import build_datasource_sharded
        from spark_druid_olap_tpu.server import OlapServer
        from spark_druid_olap_tpu.utils import compile_cache

        self.cache_dir = compile_cache.enable()
        cfg = SessionConfig.load_calibrated()
        for key, value in config["settings"].items():
            if not hasattr(cfg, key):
                raise KeyError(f"SessionConfig has no setting {key!r}")
            setattr(cfg, key, value)
        self.ctx = TPUOlapContext(cfg)
        say(phase="calibration", calibration_meta=cfg.calibration_meta,
            compile_cache_dir=self.cache_dir)

        t0 = time.perf_counter()
        tables = ssb_data.gen_dim_tables(scale, np.random.default_rng(seed))
        ad = _attr_dicts(tables)
        chunk_rows = ssb_data.CHUNK_ROWS
        chunks = (
            _flat_chunk(
                ssb_data.gen_fact_chunk(ci, scale, seed, chunk_rows, tables),
                tables, ad,
            )
            for ci in range(ssb_data.n_fact_chunks(scale, chunk_rows))
        )
        ds = build_datasource_sharded(
            config["fact_table"], chunks,
            dimension_cols=list(ssb_data.DIM_ATTRS),
            metric_cols=ssb_data.FLAT_METRICS, time_col="lo_orderdate",
            rows_per_segment=int(config["rows_per_segment"]),
            dicts={attr: d for attr, (d, _) in ad.items()},
        )
        self.ctx.register_datasource(ds, star_schema=config["star_schema"])
        self.ctx.register_table(
            "dwdate", tables["dwdate"], time_column="d_datekey"
        )
        for t in ("customer", "supplier", "part"):
            self.ctx.register_table(t, tables[t])
        self.datasource = ds
        say(phase="load", scale=scale, seed=seed, rows=ds.num_rows,
            segments=len(ds.segments),
            ingest_s=round(time.perf_counter() - t0, 2))
        self.server = OlapServer(self.ctx, port=0).start()
        self.endpoint = config["endpoint"]

    def send(self, query):
        """(HTTP status, decoded answer, this request's QueryMetrics).
        The counters are the context's last: sound with one client; a mix
        with more has to look them up by query id (not built)."""
        status, body = post(
            self.server.port, self.endpoint, {"query": query["sql"]}
        )
        return status, body, (self.ctx.last_metrics if status == 200 else None)

    def column_bytes(self):
        """Bytes per row of each resident column, from the dtypes the
        segments hold, with the validity mask and the time column under
        `__valid` and `__time`."""
        seg = self.datasource.segments[0]
        out = {name: a.dtype.itemsize for name, a in seg.dims.items()}
        out.update({name: a.dtype.itemsize for name, a in seg.metrics.items()})
        out[VALID] = seg.valid.dtype.itemsize
        if seg.time is not None:
            out[TIME] = seg.time.dtype.itemsize
        return out

    def close(self):
        self.server.shutdown()


def start_system(config, seed, scale, say):
    return System(config, seed, scale, say)


def to_frame(body):
    import pandas as pd

    return pd.DataFrame(body)
