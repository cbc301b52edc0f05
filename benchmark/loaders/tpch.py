"""Loader of the `tpch-sf10-1chip` configuration: TPC-H's lineitem star
from the seed (`tpch_data.py`), fed through the program's ingest entry and
served by `OlapServer`, sent the traffic file's SQL; and the reference
child `tpch_data.py`.

The served loop and the system's shell (server, `send`, `column_bytes`,
`close`) are `ssb.py`'s, loaded by path and reused as they are; what
differs is the data, its dictionaries and star schema, the reference and
how an answer is read: as `tpch_data.frame` makes it, the same function
the reference's rows go through.
"""

from __future__ import annotations

import importlib.util
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

_spec = importlib.util.spec_from_file_location(
    "bench_ssb_for_tpch", os.path.join(HERE, "ssb.py")
)
ssb = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ssb)

sys.path.insert(0, HERE)
import tpch_data  # noqa: E402  (beside this file, found by path)

sys.path.remove(HERE)

D = tpch_data


class Reference(ssb.Reference):
    """`tpch_data.py` as a child process: started before ingest, joined
    after the window; it imports no JAX and cannot touch the chip."""

    def __init__(self, config, queries, seed, scale, precisions):
        import subprocess
        import tempfile

        fd, self.out = tempfile.mkstemp(prefix="bench_ref_", suffix=".pkl")
        os.close(fd)
        cmd = [
            sys.executable, os.path.join(HERE, "tpch_data.py"),
            "--scale", repr(float(scale)), "--seed", str(int(seed)),
            "--queries", ",".join(q["name"] for q in queries),
            "--out", self.out,
        ]
        for p in precisions:
            cmd += ["--precision", p]
        self.proc = subprocess.Popen(
            cmd, env={**os.environ, "JAX_PLATFORMS": "cpu"},
            stdout=subprocess.DEVNULL,
        )


def start_reference(config, queries, seed, scale, precisions=("float32",)):
    return Reference(config, queries, seed, scale, precisions)


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------


def _string_dict(values):
    """(the program's dictionary over `values`, code of each index into
    `values`): the dictionary sorts its values."""
    from spark_druid_olap_tpu.catalog.segment import DimensionDict

    d = DimensionDict(values=tuple(sorted(values)))
    return d, np.asarray([d.values.index(v) for v in values], dtype=np.int64)


def _numeric_dict(uniq):
    from spark_druid_olap_tpu.catalog.segment import DimensionDict

    return DimensionDict(values=tuple(int(v) for v in uniq))


def dictionaries(tables, scale):
    """Per flat dimension: (the program's dictionary, a function from a
    fact chunk to its codes).  Built on the small tables; a fact row
    gathers its codes through its keys."""
    from spark_druid_olap_tpu.catalog.segment import code_dtype

    n = D.sizes(scale)
    o, cu = tables["orders"], tables["customer"]
    s_nat = tables["supplier"]["nation"]
    part = tables["part"]
    days = np.unique(o["orderday"])
    years = np.unique(D.year_of_day(days))
    day_ms = (D.DAY0 + days.astype("timedelta64[D]")).astype("datetime64[ms]")
    out = {}

    def put(name, d, codes_of):
        dt = code_dtype(d.cardinality)
        out[name] = (d, lambda c: codes_of(c).astype(dt))

    def cust(c):
        return o["custkey"][c["l_orderkey"]]

    def strings(name, values, index_of):
        d, rank = _string_dict(values)
        put(name, d, lambda c: rank[index_of(c)])

    strings("o_orderpriority", D.PRIORITIES,
            lambda c: o["priority"][c["l_orderkey"]])
    put("o_orderdate", _numeric_dict(day_ms.astype(np.int64)),
        lambda c: np.searchsorted(days, o["orderday"][c["l_orderkey"]]))
    put("o_orderdate_year", _numeric_dict(years), lambda c: np.searchsorted(
        years, D.year_of_day(o["orderday"][c["l_orderkey"]])
    ))
    put("c_custkey", _numeric_dict(np.arange(n["customer"])), cust)
    put("c_name", _names(n["customer"]), cust)
    strings("c_mktsegment", D.SEGMENTS, lambda c: cu["mktsegment"][cust(c)])
    strings("c_nation", D.NATIONS, lambda c: cu["nation"][cust(c)])
    strings("c_region", D.REGIONS, lambda c: cu["nation"][cust(c)] // 5)
    strings("s_nation", D.NATIONS, lambda c: s_nat[c["suppkey"]])
    strings("s_region", D.REGIONS, lambda c: s_nat[c["suppkey"]] // 5)
    strings("p_brand", D.BRANDS, lambda c: part["brand"][c["partkey"]])
    strings("p_type", D.TYPES, lambda c: part["type"][c["partkey"]])
    strings("l_returnflag", D.RETURNFLAGS, lambda c: c["returnflag"])
    strings("l_linestatus", D.LINESTATUS, lambda c: c["linestatus"])
    strings("l_shipmode", D.SHIPMODES, lambda c: c["shipmode"])
    put("l_orderkey", _numeric_dict(np.arange(n["orders"])),
        lambda c: c["l_orderkey"])
    return out


def _names(n_customers):
    """c_name's dictionary: `Customer#` and the key in nine digits, so its
    sorted order is the keys' and a name's code is its customer's key."""
    from spark_druid_olap_tpu.catalog.segment import DimensionDict

    return DimensionDict(values=tuple(
        f"Customer#{k:09d}" for k in range(n_customers)
    ))


def flat_chunk(c, dicts):
    """One fact chunk as the program's flat columns: every dimension's
    codes, the metrics and the time column `l_shipdate` in ms."""
    day0_ms = int(D.DAY0.astype("datetime64[ms]").astype(np.int64))
    cols = {name: codes_of(c) for name, (_, codes_of) in dicts.items()}
    cols["l_shipdate"] = c["shipday"].astype(np.int64) * D.MS_DAY + day0_ms
    for m in D.METRICS:
        cols[m] = c[m]
    return cols


class System(ssb.System):
    """The program, loaded and serving: built from the configuration's
    settings, fed through `build_datasource_sharded`, reached over HTTP."""

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
        tables = D.gen_tables(scale, seed)
        keys = D.lineitem_keys(scale, seed, tables)
        dicts = dictionaries(tables, scale)
        chunks = (
            flat_chunk(D.lineitem_chunk(ci, scale, seed, tables, keys), dicts)
            for ci in range(D.n_chunks(scale))
        )
        ds = build_datasource_sharded(
            config["fact_table"], chunks,
            dimension_cols=list(dicts), metric_cols=D.METRICS,
            time_col="l_shipdate",
            rows_per_segment=int(config["rows_per_segment"]),
            dicts={name: d for name, (d, _) in dicts.items()},
        )
        del keys
        self.ctx.register_datasource(ds, star_schema=config["star_schema"])
        self.datasource = ds
        say(phase="load", scale=scale, seed=seed, rows=ds.num_rows,
            segments=len(ds.segments),
            ingest_s=round(time.perf_counter() - t0, 2))
        self.server = OlapServer(self.ctx, port=0).start()
        self.endpoint = config["endpoint"]


def start_system(config, seed, scale, say):
    _refuse_without_host_groups()
    return System(config, seed, scale, say)


def _refuse_without_host_groups():
    """The cell runs on a program whose group-bys past the adaptive tier's
    cap have a bounded device path, and which counts the groups its host
    finalizes (`QueryMetrics.host_groups`, brought with that path).  A
    program without it answers Q10 at this scale on the sparse tier's
    ladder of unrolled programs, which does not finish within a run's
    time limit: it is refused at once, before ingest."""
    import dataclasses

    from spark_druid_olap_tpu.exec.metrics import QueryMetrics

    if "host_groups" not in {f.name for f in dataclasses.fields(QueryMetrics)}:
        raise RuntimeError(
            "the program has no bounded device path for group-bys past the "
            "adaptive cap (QueryMetrics.host_groups): this cell cannot run it"
        )


def to_frame(body):
    """The SQL answer's rows as `tpch_data.frame` reads them."""
    return D.frame(body)
