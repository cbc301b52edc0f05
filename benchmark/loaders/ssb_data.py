"""SSB data and its float64 pandas reference, numpy and pandas only.

COPIED from `spark_druid_olap_tpu/workloads/ssb.py` at commit 1f06452
(generator, chunk geometry, `oracle`, `merge_oracle_parts`,
`flat_frame_chunk`) and NOT to track it: this is the benchmark's
yardstick, so a later change to the program's copy must not move it.  It
imports nothing of the program and takes nothing the program made: the
same seed gives the same tables, and the reference decodes them with
`np.unique` over the small dimension tables, not with the engine's
dictionaries.  Changes against the original: `flat_frame_chunk` builds
only the columns it is asked for; `precision` rounds the metric columns
(the low-precision control); `main` is the child process's entry.

As a script it is the reference child of `loaders/ssb.py`: it computes
the answers of the named queries from the seed and pickles them to
`--out`.  It never touches JAX.
"""

from __future__ import annotations

import argparse
import os
import pickle
import sys
import time
from typing import Dict, Tuple

import numpy as np

_MS_DAY = 86_400_000

REGIONS = np.array(["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"])
NATIONS_BY_REGION = {
    "AFRICA": ["ALGERIA", "ETHIOPIA", "KENYA", "MOROCCO", "MOZAMBIQUE"],
    "AMERICA": ["ARGENTINA", "BRAZIL", "CANADA", "PERU", "UNITED STATES"],
    "ASIA": ["CHINA", "INDIA", "INDONESIA", "JAPAN", "VIETNAM"],
    "EUROPE": ["FRANCE", "GERMANY", "ROMANIA", "RUSSIA", "UNITED KINGDOM"],
    "MIDDLE EAST": ["EGYPT", "IRAN", "IRAQ", "JORDAN", "SAUDI ARABIA"],
}

# attribute -> owning dim table, foreign-key column on the fact
DIM_ATTRS = {
    "d_year": ("dwdate", "lo_orderdate"),
    "d_yearmonthnum": ("dwdate", "lo_orderdate"),
    "d_yearmonth": ("dwdate", "lo_orderdate"),
    "d_weeknuminyear": ("dwdate", "lo_orderdate"),
    "c_region": ("customer", "lo_custkey"),
    "c_nation": ("customer", "lo_custkey"),
    "c_city": ("customer", "lo_custkey"),
    "s_region": ("supplier", "lo_suppkey"),
    "s_nation": ("supplier", "lo_suppkey"),
    "s_city": ("supplier", "lo_suppkey"),
    "p_mfgr": ("part", "lo_partkey"),
    "p_category": ("part", "lo_partkey"),
    "p_brand1": ("part", "lo_partkey"),
}

FLAT_DIMS = list(DIM_ATTRS)
FLAT_METRICS = [
    "lo_quantity", "lo_extendedprice", "lo_discount", "lo_revenue",
    "lo_supplycost",
    # FK retained on the flat fact for approx-distinct workloads
    # (BASELINE configs #3/#5: HLL/theta over lo_custkey)
    "lo_custkey",
]


def _geo(n: int, rng) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    reg = rng.choice(REGIONS, size=n)
    nation = np.empty(n, dtype=object)
    for r in REGIONS:
        m = reg == r
        nation[m] = rng.choice(
            np.array(NATIONS_BY_REGION[r]), size=int(m.sum())
        )
    city = np.char.add(
        np.asarray(nation, dtype=str), rng.integers(0, 10, size=n).astype(str)
    )
    return reg.astype(object), nation, city.astype(object)


def gen_dim_tables(scale: float, rng) -> Dict[str, Dict[str, np.ndarray]]:
    """The four SSB dimension tables (small at any scale factor; SF100
    customer is 3M rows — the fact is what needs streaming)."""
    # dwdate: one row per calendar day 1992-01-01 .. 1998-12-31
    d0 = np.datetime64("1992-01-01")
    days = np.arange(d0, np.datetime64("1999-01-01"), dtype="datetime64[D]")
    years = days.astype("datetime64[Y]").astype(int) + 1970
    months = days.astype("datetime64[M]").astype(int) % 12 + 1
    day_of_year = (days - days.astype("datetime64[Y]")).astype(int) + 1
    dwdate = {
        "d_datekey": days.astype("datetime64[ms]").astype(np.int64),
        "d_year": years.astype(np.int32),
        "d_yearmonthnum": (years * 100 + months).astype(np.int32),
        "d_yearmonth": np.array(
            [f"{y}-{m:02d}" for y, m in zip(years, months)], dtype=object
        ),
        "d_weeknuminyear": ((day_of_year - 1) // 7 + 1).astype(np.int32),
    }

    n_c = max(100, int(30_000 * scale))
    c_region, c_nation, c_city = _geo(n_c, rng)
    customer = {
        "c_custkey": np.arange(n_c, dtype=np.int64),
        "c_region": c_region, "c_nation": c_nation, "c_city": c_city,
    }

    n_s = max(50, int(2_000 * scale))
    s_region, s_nation, s_city = _geo(n_s, rng)
    supplier = {
        "s_suppkey": np.arange(n_s, dtype=np.int64),
        "s_region": s_region, "s_nation": s_nation, "s_city": s_city,
    }

    n_p = max(200, int(200_000 * scale))
    mfgr = np.char.add("MFGR#", rng.integers(1, 6, size=n_p).astype(str))
    category = np.char.add(
        np.asarray(mfgr, dtype=str), rng.integers(1, 6, size=n_p).astype(str)
    )
    brand = np.char.add(
        np.asarray(category, dtype=str),
        np.char.add("-", rng.integers(1, 41, size=n_p).astype(str)),
    )
    part = {
        "p_partkey": np.arange(n_p, dtype=np.int64),
        "p_mfgr": np.asarray(mfgr, dtype=object),
        "p_category": np.asarray(category, dtype=object),
        "p_brand1": np.asarray(brand, dtype=object),
    }
    return {
        "dwdate": dwdate, "customer": customer,
        "supplier": supplier, "part": part,
    }


def _gen_fact(n: int, rng, datekeys, n_c: int, n_s: int, n_p: int,
              date_lo: int = 0, date_hi: int | None = None):
    # Dates are generated PRE-SORTED (np.sort on the small int16 draw is
    # ~2x faster than even the radix argsort it replaces, measured here),
    # and every other column is iid — so sorting only the
    # date draw yields a stream identical in distribution to
    # generate-then-timesort while eliminating the per-chunk argsort AND
    # the 17-column permutation gather that dominated the ingest profile
    # (5.2 s of a 15.2 s SF2 ingest, measured round 5).  Consumers see
    # time-sorted chunks the same as before; only the row<->value pairing
    # of the synthetic stream changed (bench.py bumps its oracle-cache
    # version for exactly this).
    date_idx = np.sort(rng.integers(
        date_lo, len(datekeys) if date_hi is None else date_hi, size=n,
        dtype=np.int16,
    ))
    quantity = rng.integers(1, 51, size=n).astype(np.float32)
    extendedprice = rng.random(n).astype(np.float32) * 55_450 + 90
    discount = rng.integers(0, 11, size=n).astype(np.float32)
    return {
        "lo_orderdate": np.asarray(datekeys)[date_idx],
        # int32 keys: segment encode casts metrics to int32 anyway, so
        # generating narrow saves a 12M-row astype + half the gather bytes
        # per chunk (values are < 2^31 at any SSB scale)
        "lo_custkey": rng.integers(0, n_c, size=n, dtype=np.int32),
        "lo_suppkey": rng.integers(0, n_s, size=n, dtype=np.int32),
        "lo_partkey": rng.integers(0, n_p, size=n, dtype=np.int32),
        "lo_quantity": quantity,
        "lo_extendedprice": extendedprice,
        "lo_discount": discount,
        "lo_revenue": extendedprice * (1 - discount / 100),
        "lo_supplycost": extendedprice * 0.6,
    }


def _fk_row_index(lo, fk_col: str, table: str, dwdate) -> np.ndarray:
    fk = lo[fk_col]
    if table == "dwdate":
        base = int(dwdate["d_datekey"][0])
        return ((fk - base) // _MS_DAY).astype(np.int64)
    return fk.astype(np.int64)  # dense 0..n-1 keys


def n_fact_chunks(scale: float, chunk_rows: int) -> int:
    return -(-int(6_000_000 * scale) // chunk_rows)


def gen_fact_chunk(ci: int, scale: float, seed: int, chunk_rows: int,
                   tables):
    """Fact chunk `ci` from its own deterministic stream
    default_rng((seed, SSB_FACT_STREAM, ci)) — reproducible given the SAME
    (scale, seed, chunk_rows), so the chunked ORACLE must iterate with the
    chunk geometry the ingest used (both bench callers do), and any chunk
    can be produced on any worker process.

    Chunk ci covers ITS slice of the date span — events arrive in time
    order, exactly how Druid ingests (segments ARE time partitions):
    date-derived predicates then prune across the WHOLE stream, not just
    within a chunk.  Slices are proportional to ROW position (not chunk
    index), so a ragged last chunk gets a proportionally narrower slice
    and per-day density stays uniform over the span.  This is the ONE
    definition of the chunk geometry — ingest (serial and parallel) and
    oracle all draw from here."""
    n = int(6_000_000 * scale)
    datekeys = tables["dwdate"]["d_datekey"]
    n_days = len(datekeys)
    start = ci * chunk_rows
    rows = min(chunk_rows, n - start)
    rng = np.random.default_rng((seed, _FACT_STREAM, ci))
    lo = (start * n_days) // n
    hi = max(lo + 1, ((start + rows) * n_days) // n)
    return _gen_fact(
        rows, rng, datekeys,
        len(tables["customer"]["c_custkey"]),
        len(tables["supplier"]["s_suppkey"]),
        len(tables["part"]["p_partkey"]),
        lo, hi,
    )


def fact_chunks(scale: float, seed: int, chunk_rows: int, tables):
    """Generator of lineorder chunks at SF `scale` without ever holding the
    full fact (one gen_fact_chunk per step)."""
    for ci in range(n_fact_chunks(scale, chunk_rows)):
        yield gen_fact_chunk(ci, scale, seed, chunk_rows, tables)


_FACT_STREAM = 90_001  # spawn-key tag separating fact chunks from dim draws


def oracle_categories(tables):
    """Per string attribute: (sorted distinct values, per-dim-row codes),
    from `np.unique` over the small dimension tables — the oracle's own
    encoding, independent of the engine's dictionaries."""
    out = {}
    for attr, (table, _) in DIM_ATTRS.items():
        vals = np.asarray(tables[table][attr])
        if vals.dtype.kind in ("U", "S", "O"):
            out[attr] = np.unique(vals.astype(str), return_inverse=True)
    return out


def _round_to(values, precision: str):
    """The metric column as stored at `precision`, widened to float64.
    "float32" is what the generator emits (the configuration's stated
    storage); "bfloat16" is the control: the nearest precision below."""
    if precision == "float32":
        return np.asarray(values, dtype=np.float64)
    if precision == "bfloat16":
        import ml_dtypes

        return np.asarray(values).astype(ml_dtypes.bfloat16).astype(np.float64)
    raise ValueError(f"unknown precision {precision!r}")


def flat_frame_chunk(tables, lo, categories=None, columns=None,
                     precision="float32"):
    """Decoded flat pandas frame for ONE fact chunk (the chunked-oracle
    unit), holding only `columns` (all when None).  String attributes are
    pandas categoricals over their decoded values: the fact rows gather
    small int codes through the FK, never strings (group by them with
    `observed=True`, as `oracle` does: a pandas that defaults to False
    emits every combination of categories).  A chunked caller passes
    `oracle_categories(tables)` once instead of paying the
    dimension-table sort per chunk."""
    import pandas as pd

    if categories is None:
        categories = oracle_categories(tables)
    want = None if columns is None else set(columns)
    data = {}
    if want is None or "lo_orderdate" in want:
        data["lo_orderdate"] = lo["lo_orderdate"]
    for m in FLAT_METRICS:
        if want is None or m in want:
            data[m] = _round_to(lo[m], precision)
    idx_cache: Dict[str, np.ndarray] = {}
    for attr, (table, fk_col) in DIM_ATTRS.items():
        if want is not None and attr not in want:
            continue
        if table not in idx_cache:
            idx_cache[table] = _fk_row_index(
                lo, fk_col, table, tables["dwdate"]
            )
        if attr in categories:
            values, codes = categories[attr]
            data[attr] = pd.Categorical.from_codes(
                codes[idx_cache[table]], categories=values
            )
        else:
            data[attr] = np.asarray(tables[table][attr])[idx_cache[table]]
    return pd.DataFrame(data)


def merge_oracle_parts(parts):
    """Merge per-chunk `oracle` results into the full-table result.  Sound
    because every SSB aggregate is a SUM (scalar or grouped): partials
    concatenate and re-sum by the group columns."""
    import pandas as pd

    if isinstance(parts[0], float):
        return float(sum(parts))
    # drop EMPTY partials before concat: date-sliced chunks make filtered
    # queries miss whole chunks, and concat with empties promotes int
    # group columns to float
    nonempty = [p for p in parts if len(p)]
    if not nonempty:
        return parts[0]
    df = pd.concat(nonempty, ignore_index=True)
    vcol = df.columns[-1]  # oracle puts the measure last
    g = [c for c in df.columns if c != vcol]
    return df.groupby(g, as_index=False, observed=True)[vcol].sum()


def oracle(f, name: str):
    """Reference result for QUERIES[name] over flat_frame output, grouped
    results sorted by their group columns (callers re-sort `got` the same
    way before comparing)."""
    if name in ("q1_1", "q1_2", "q1_3"):
        q = np.asarray(f.lo_quantity)
        dc = np.asarray(f.lo_discount)
    if name == "q1_1":
        m = (f.d_year == 1993) & (dc >= 1) & (dc <= 3) & (q < 25)
        return float((f.lo_extendedprice[m] * dc[m]).sum())
    if name == "q1_2":
        m = (f.d_yearmonthnum == 199401) & (dc >= 4) & (dc <= 6) & (q >= 26) & (q <= 35)
        return float((f.lo_extendedprice[m] * dc[m]).sum())
    if name == "q1_3":
        m = ((f.d_weeknuminyear == 6) & (f.d_year == 1994)
             & (dc >= 5) & (dc <= 7) & (q >= 26) & (q <= 35))
        return float((f.lo_extendedprice[m] * dc[m]).sum())
    if name in ("q2_1", "q2_2", "q2_3"):
        if name == "q2_1":
            m = (f.p_category == "MFGR#12") & (f.s_region == "AMERICA")
        elif name == "q2_2":
            b = f.p_brand1.astype(str)
            m = (b >= "MFGR#22-1") & (b <= "MFGR#22-8") & (f.s_region == "ASIA")
        else:
            m = (f.p_brand1 == "MFGR#22-9") & (f.s_region == "EUROPE")
        return (
            f[m].groupby(["d_year", "p_brand1"], observed=True)
            .lo_revenue.sum()
            .reset_index().rename(columns={"lo_revenue": "revenue"})
        )
    if name in ("q3_1", "q3_2", "q3_3", "q3_4"):
        yr = (f.d_year >= 1992) & (f.d_year <= 1997)
        if name == "q3_1":
            m = (f.c_region == "ASIA") & (f.s_region == "ASIA") & yr
            g = ["c_nation", "s_nation", "d_year"]
        elif name == "q3_2":
            m = ((f.c_nation == "UNITED STATES")
                 & (f.s_nation == "UNITED STATES") & yr)
            g = ["c_city", "s_city", "d_year"]
        else:
            cities = ["UNITED KINGDOM1", "UNITED KINGDOM5"]
            m = f.c_city.isin(cities) & f.s_city.isin(cities)
            m &= yr if name == "q3_3" else (f.d_yearmonth == "1997-12")
            g = ["c_city", "s_city", "d_year"]
        return (
            f[m].groupby(g, observed=True).lo_revenue.sum()
            .reset_index().rename(columns={"lo_revenue": "revenue"})
        )
    if name in ("q4_1", "q4_2", "q4_3"):
        prof = f.lo_revenue - f.lo_supplycost
        if name == "q4_1":
            m = ((f.c_region == "AMERICA") & (f.s_region == "AMERICA")
                 & f.p_mfgr.isin(["MFGR#1", "MFGR#2"]))
            g = ["d_year", "c_nation"]
        elif name == "q4_2":
            m = ((f.c_region == "AMERICA") & (f.s_region == "AMERICA")
                 & f.d_year.isin([1997, 1998])
                 & f.p_mfgr.isin(["MFGR#1", "MFGR#2"]))
            g = ["d_year", "s_nation", "p_category"]
        else:
            m = ((f.c_region == "AMERICA") & (f.s_nation == "UNITED STATES")
                 & f.d_year.isin([1997, 1998]) & (f.p_category == "MFGR#14"))
            g = ["d_year", "s_city", "p_brand1"]
        return (
            f[m].assign(profit=prof).groupby(g, observed=True)
            .profit.sum().reset_index()
        )
    raise KeyError(name)


# ---------------------------------------------------------------------------
# the reference child
# ---------------------------------------------------------------------------

CHUNK_ROWS = 1 << 22  # the ingest's chunk geometry: both sides draw from it


def reference_answers(scale, seed, queries, columns, precision="float32"):
    """{query name: float | DataFrame}: the answers of `queries` over the
    fact table that (`scale`, `seed`) define, chunk by chunk."""
    tables = gen_dim_tables(scale, np.random.default_rng(seed))
    categories = oracle_categories(tables)
    parts = {name: [] for name in queries}
    for lo in fact_chunks(scale, seed, CHUNK_ROWS, tables):
        f = flat_frame_chunk(tables, lo, categories, columns, precision)
        for name in queries:
            parts[name].append(oracle(f, name))
    return {name: merge_oracle_parts(parts[name]) for name in queries}


def main(argv=None):
    ap = argparse.ArgumentParser(description="SSB reference child")
    ap.add_argument("--scale", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--queries", required=True, help="comma-separated")
    ap.add_argument("--columns", required=True, help="comma-separated")
    ap.add_argument("--precision", action="append", required=True,
                    help="one answer set per precision, in this order")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    os.nice(10)  # the window's client and server threads come first
    t0 = time.perf_counter()
    queries = args.queries.split(",")
    columns = args.columns.split(",")
    out = {
        p: reference_answers(args.scale, args.seed, queries, columns, p)
        for p in args.precision
    }
    out["seconds"] = time.perf_counter() - t0
    tmp = args.out + ".tmp"
    with open(tmp, "wb") as f:
        pickle.dump(out, f)
    os.replace(tmp, args.out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
