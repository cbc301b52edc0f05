"""Star Schema Benchmark (SSB): normalized tables, star declaration, the 13
queries Q1.1-Q4.3 in joined-SQL form, and pandas oracles.

Reference parity: the reference's test/benchmark corpus is TPC-H/SSB-style
star queries over a Druid datasource that is the *denormalized* star, with
the normalized tables + star-schema JSON declared in the DDL so JoinTransform
can eliminate the dimension joins (SURVEY.md §2 JoinTransform/StarSchema rows,
§4 TPCH suites `[U]`; BASELINE.md configs #2 and the SSB north star).  Here:

* `gen_tables(scale)` builds the normalized star (lineorder fact + dwdate /
  customer / supplier / part dims; "dwdate" because DATE is a SQL keyword —
  several SSB kits rename it the same way).
* `flat_columns(tables)` pre-joins it into the dictionary-encoded flat
  datasource (the "Druid index"): string attributes become int32 codes via
  per-attribute dictionaries built on the SMALL dim tables, then gathered
  through the fact's foreign keys — no 6M-row string materialization.
* `register(ctx, ...)` registers the flat fact (with the star schema) plus
  the four dimension tables, so joined SQL resolves and collapses.
* `QUERIES` are the 13 SSB queries written AS JOINS — executing them
  exercises parse -> star-join elimination -> filter/agg pushdown -> kernels.
  Filter constants are adapted to this generator's value domains; the query
  *shapes* (join pattern, filter arity, group-bys, ordering) follow the SSB
  spec.
"""

from __future__ import annotations

from typing import Dict, Tuple

import numpy as np

from ..catalog.segment import DimensionDict
from ..catalog.star import FunctionalDependency, StarRelationInfo, StarSchemaInfo

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

STAR_SCHEMA = StarSchemaInfo(
    fact_table="lineorder",
    relations=(
        StarRelationInfo("dwdate", (("lo_orderdate", "d_datekey"),)),
        StarRelationInfo("customer", (("lo_custkey", "c_custkey"),)),
        StarRelationInfo("supplier", (("lo_suppkey", "s_suppkey"),)),
        StarRelationInfo("part", (("lo_partkey", "p_partkey"),)),
    ),
    functional_dependencies=(
        FunctionalDependency("customer", "c_city", "c_nation"),
        FunctionalDependency("customer", "c_nation", "c_region"),
        FunctionalDependency("supplier", "s_city", "s_nation"),
        FunctionalDependency("supplier", "s_nation", "s_region"),
        FunctionalDependency("part", "p_brand1", "p_category"),
        FunctionalDependency("part", "p_category", "p_mfgr"),
        FunctionalDependency("dwdate", "d_datekey", "d_year"),
    ),
)


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


def gen_tables(
    scale: float = 0.01, seed: int = 7, fact_rows: int | None = None
) -> Dict[str, Dict[str, np.ndarray]]:
    """Normalized SSB star at ~SF `scale` (SF1: 6M lineorder rows).  Keys are
    dense 0..n-1 so the pre-join is a direct gather.  `fact_rows` cuts the
    fact alone: dimension tables at SF1 already hold every attribute value
    SF10 has, so a small fact under them gives tests SF10's dictionaries,
    lowerings and G.

    Materializes the WHOLE fact host-side — use at test scales.  Large
    scale factors go through `register_streamed`, which
    generate and encode the fact chunk-by-chunk."""
    rng = np.random.default_rng(seed)
    out = gen_dim_tables(scale, rng)
    n_c = len(out["customer"]["c_custkey"])
    n_s = len(out["supplier"]["s_suppkey"])
    n_p = len(out["part"]["p_partkey"])
    out["lineorder"] = _gen_fact(
        int(6_000_000 * scale) if fact_rows is None else fact_rows,
        rng, out["dwdate"]["d_datekey"], n_c, n_s, n_p,
    )
    return out


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


def _dim_row_index(tables, fk_col: str, table: str) -> np.ndarray:
    return _fk_row_index(
        tables["lineorder"], fk_col, table, tables["dwdate"]
    )


def _attr_dicts(tables) -> Dict[str, Tuple[DimensionDict, np.ndarray]]:
    """Per flat attribute: (dictionary, encoded dim-table codes) — built on
    the SMALL dimension tables once; fact rows gather through the FK."""
    from ..catalog.segment import code_dtype

    out: Dict[str, Tuple[DimensionDict, np.ndarray]] = {}
    for attr, (table, _) in DIM_ATTRS.items():
        vals = tables[table][attr]
        if vals.dtype.kind in ("U", "S", "O"):
            d = DimensionDict.build(list(vals))
            dim_codes = d.encode(list(vals))
        else:
            uniq = np.unique(vals.astype(np.int64))
            d = DimensionDict(values=tuple(int(v) for v in uniq))
            dim_codes = d.encode_numeric(vals)
        # narrow at the SOURCE: every fact-row gather, time-sort shuffle,
        # and segment pad downstream then moves 1-2 byte codes instead of
        # int32 (the ingest hot loop is memory-bound numpy)
        out[attr] = (d, dim_codes.astype(code_dtype(d.cardinality)))
    return out


def _flat_chunk(lo, tables, attr_dicts) -> Dict[str, np.ndarray]:
    """One chunk of fact rows -> flat encoded columns (gathers only)."""
    cols: Dict[str, np.ndarray] = {
        "lo_orderdate": lo["lo_orderdate"],
        **{m: lo[m] for m in FLAT_METRICS},
    }
    idx_cache: Dict[str, np.ndarray] = {}
    for attr, (table, fk_col) in DIM_ATTRS.items():
        if table not in idx_cache:
            idx_cache[table] = _fk_row_index(
                lo, fk_col, table, tables["dwdate"]
            )
        cols[attr] = attr_dicts[attr][1][idx_cache[table]]
    return cols


def flat_columns(tables) -> Tuple[Dict[str, np.ndarray], Dict[str, DimensionDict]]:
    """Pre-join the star into the dictionary-encoded flat datasource.

    Per attribute: build the dictionary on the dim table (small), encode the
    dim rows, gather codes through the fact FK — the flat table never holds
    6M strings.  Returns (columns, dicts) for build_datasource; string-dict
    columns arrive pre-encoded (see the build_datasource caller contract).
    """
    ad = _attr_dicts(tables)
    cols = _flat_chunk(tables["lineorder"], tables, ad)
    return cols, {attr: d for attr, (d, _) in ad.items()}


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


def _sorted_flat_chunk(ci, scale, seed, chunk_rows, tables, ad):
    """Chunk ci: generate -> flat-encode -> time-sort.  The one body both
    the serial and the parallel ingest paths run."""
    c = _flat_chunk(
        gen_fact_chunk(ci, scale, seed, chunk_rows, tables), tables, ad
    )
    dates = c["lo_orderdate"]
    # _gen_fact emits pre-sorted dates (see its docstring); the O(n) check
    # keeps this function correct for any other chunk source, falling back
    # to the radix argsort + permutation gather only when actually needed
    if np.all(dates[1:] >= dates[:-1]):
        return c
    day = ((dates - dates.min()) // _MS_DAY).astype(np.int16)
    order = np.argsort(day, kind="stable")
    return {k: np.asarray(v)[order] for k, v in c.items()}


def register_streamed(ctx, scale: float, seed: int = 7,
                      rows_per_segment: int = 1 << 19,
                      chunk_rows: int = 1 << 22,
                      workers: int | None = None):
    """Register the SSB star at a LARGE scale factor: the fact is
    generated, encoded, and segmented chunk-by-chunk through the SHARDED
    ingest pipeline (`ingest.shard.build_datasource_sharded`, ISSUE 8
    follow-up 2(a)) — never materialized whole.  Chunks are date-sliced
    (fact_chunks) and time-sorted before segmenting, so a segment spans
    roughly 1/(8 x n_chunks) of the date range — date-derived predicates
    prune via zone maps across the whole stream.

    Workers are THREADS (the sharded pipeline's contract): the old fork
    pool — and its fork-vs-live-JAX deadlock hazard plus the
    SD_INGEST_WORKERS opt-in gate — is retired.  `workers=None` resolves
    via `ingest.shard.sharded_ingest_workers` (SD_INGEST_WORKERS env >
    cpu count); `workers=0` forces the single-threaded inline pipeline.
    Output segments are row/code/stats-identical to the retired streamed
    path (per-shard encode through the same `build_datasource`, ordered
    reassembly).  Returns the dimension tables (for oracle use)."""
    from ..ingest.shard import build_datasource_sharded

    tables = gen_dim_tables(scale, np.random.default_rng(seed))
    ad = _attr_dicts(tables)
    dicts = {attr: d for attr, (d, _) in ad.items()}

    chunks = (
        _sorted_flat_chunk(ci, scale, seed, chunk_rows, tables, ad)
        for ci in range(n_fact_chunks(scale, chunk_rows))
    )
    ds = build_datasource_sharded(
        "lineorder", chunks,
        dimension_cols=FLAT_DIMS, metric_cols=FLAT_METRICS,
        time_col="lo_orderdate",
        rows_per_segment=rows_per_segment, dicts=dicts,
        workers=1 if workers == 0 else workers,
    )
    ctx.register_datasource(ds, star_schema=STAR_SCHEMA)
    ctx.register_table("dwdate", tables["dwdate"], time_column="d_datekey")
    for t in ("customer", "supplier", "part"):
        ctx.register_table(t, tables[t])
    return tables


def register(ctx, scale: float = 0.01, seed: int = 7,
             rows_per_segment: int = 1 << 19, tables=None,
             sort_by=("lo_orderdate",)):
    """Register the flat fact datasource (with the star schema) and the four
    normalized dimension tables into a TPUOlapContext.

    Rows are TIME-SORTED into 512K-row segments by default — exactly how
    Druid ingests (segments ARE time partitions): the date-derived SSB
    predicates (d_year, d_yearmonthnum, ...) then prune most segments via
    zone maps before any kernel runs, which is where Druid's (and the
    reference's) interactive latency comes from."""
    tables = tables if tables is not None else gen_tables(scale, seed)
    cols, dicts = flat_columns(tables)
    ctx.register_table(
        "lineorder", cols,
        dimensions=FLAT_DIMS, metrics=FLAT_METRICS,
        time_column="lo_orderdate", star_schema=STAR_SCHEMA,
        rows_per_segment=rows_per_segment, dicts=dicts,
        sort_by=list(sort_by),
    )
    ctx.register_table("dwdate", tables["dwdate"], time_column="d_datekey")
    for t in ("customer", "supplier", "part"):
        ctx.register_table(t, tables[t])
    return tables


# ---------------------------------------------------------------------------
# The 13 SSB queries, joined form (constants adapted to gen_tables domains)
# ---------------------------------------------------------------------------

_J_DATE = "JOIN dwdate ON lo_orderdate = d_datekey"
_J_CUST = "JOIN customer ON lo_custkey = c_custkey"
_J_SUPP = "JOIN supplier ON lo_suppkey = s_suppkey"
_J_PART = "JOIN part ON lo_partkey = p_partkey"

QUERIES: Dict[str, str] = {
    "q1_1": f"""
        SELECT sum(lo_extendedprice * lo_discount) AS revenue
        FROM lineorder {_J_DATE}
        WHERE d_year = 1993 AND lo_discount BETWEEN 1 AND 3
          AND lo_quantity < 25
    """,
    "q1_2": f"""
        SELECT sum(lo_extendedprice * lo_discount) AS revenue
        FROM lineorder {_J_DATE}
        WHERE d_yearmonthnum = 199401 AND lo_discount BETWEEN 4 AND 6
          AND lo_quantity BETWEEN 26 AND 35
    """,
    "q1_3": f"""
        SELECT sum(lo_extendedprice * lo_discount) AS revenue
        FROM lineorder {_J_DATE}
        WHERE d_weeknuminyear = 6 AND d_year = 1994
          AND lo_discount BETWEEN 5 AND 7 AND lo_quantity BETWEEN 26 AND 35
    """,
    "q2_1": f"""
        SELECT sum(lo_revenue) AS revenue, d_year, p_brand1
        FROM lineorder {_J_DATE} {_J_PART} {_J_SUPP}
        WHERE p_category = 'MFGR#12' AND s_region = 'AMERICA'
        GROUP BY d_year, p_brand1 ORDER BY d_year, p_brand1
    """,
    "q2_2": f"""
        SELECT sum(lo_revenue) AS revenue, d_year, p_brand1
        FROM lineorder {_J_DATE} {_J_PART} {_J_SUPP}
        WHERE p_brand1 BETWEEN 'MFGR#22-1' AND 'MFGR#22-8'
          AND s_region = 'ASIA'
        GROUP BY d_year, p_brand1 ORDER BY d_year, p_brand1
    """,
    "q2_3": f"""
        SELECT sum(lo_revenue) AS revenue, d_year, p_brand1
        FROM lineorder {_J_DATE} {_J_PART} {_J_SUPP}
        WHERE p_brand1 = 'MFGR#22-9' AND s_region = 'EUROPE'
        GROUP BY d_year, p_brand1 ORDER BY d_year, p_brand1
    """,
    "q3_1": f"""
        SELECT c_nation, s_nation, d_year, sum(lo_revenue) AS revenue
        FROM lineorder {_J_CUST} {_J_SUPP} {_J_DATE}
        WHERE c_region = 'ASIA' AND s_region = 'ASIA'
          AND d_year >= 1992 AND d_year <= 1997
        GROUP BY c_nation, s_nation, d_year
        ORDER BY d_year ASC, revenue DESC
    """,
    "q3_2": f"""
        SELECT c_city, s_city, d_year, sum(lo_revenue) AS revenue
        FROM lineorder {_J_CUST} {_J_SUPP} {_J_DATE}
        WHERE c_nation = 'UNITED STATES' AND s_nation = 'UNITED STATES'
          AND d_year >= 1992 AND d_year <= 1997
        GROUP BY c_city, s_city, d_year
        ORDER BY d_year ASC, revenue DESC
    """,
    "q3_3": f"""
        SELECT c_city, s_city, d_year, sum(lo_revenue) AS revenue
        FROM lineorder {_J_CUST} {_J_SUPP} {_J_DATE}
        WHERE c_city IN ('UNITED KINGDOM1', 'UNITED KINGDOM5')
          AND s_city IN ('UNITED KINGDOM1', 'UNITED KINGDOM5')
          AND d_year >= 1992 AND d_year <= 1997
        GROUP BY c_city, s_city, d_year
        ORDER BY d_year ASC, revenue DESC
    """,
    "q3_4": f"""
        SELECT c_city, s_city, d_year, sum(lo_revenue) AS revenue
        FROM lineorder {_J_CUST} {_J_SUPP} {_J_DATE}
        WHERE c_city IN ('UNITED KINGDOM1', 'UNITED KINGDOM5')
          AND s_city IN ('UNITED KINGDOM1', 'UNITED KINGDOM5')
          AND d_yearmonth = '1997-12'
        GROUP BY c_city, s_city, d_year
        ORDER BY d_year ASC, revenue DESC
    """,
    "q4_1": f"""
        SELECT d_year, c_nation, sum(lo_revenue - lo_supplycost) AS profit
        FROM lineorder {_J_CUST} {_J_SUPP} {_J_PART} {_J_DATE}
        WHERE c_region = 'AMERICA' AND s_region = 'AMERICA'
          AND (p_mfgr = 'MFGR#1' OR p_mfgr = 'MFGR#2')
        GROUP BY d_year, c_nation ORDER BY d_year, c_nation
    """,
    "q4_2": f"""
        SELECT d_year, s_nation, p_category,
               sum(lo_revenue - lo_supplycost) AS profit
        FROM lineorder {_J_CUST} {_J_SUPP} {_J_PART} {_J_DATE}
        WHERE c_region = 'AMERICA' AND s_region = 'AMERICA'
          AND (d_year = 1997 OR d_year = 1998)
          AND (p_mfgr = 'MFGR#1' OR p_mfgr = 'MFGR#2')
        GROUP BY d_year, s_nation, p_category
        ORDER BY d_year, s_nation, p_category
    """,
    "q4_3": f"""
        SELECT d_year, s_city, p_brand1,
               sum(lo_revenue - lo_supplycost) AS profit
        FROM lineorder {_J_CUST} {_J_SUPP} {_J_PART} {_J_DATE}
        WHERE c_region = 'AMERICA' AND s_nation = 'UNITED STATES'
          AND (d_year = 1997 OR d_year = 1998) AND p_category = 'MFGR#14'
        GROUP BY d_year, s_city, p_brand1
        ORDER BY d_year, s_city, p_brand1
    """,
}


# ---------------------------------------------------------------------------
# pandas oracle (float64, flat string form) — test-scale only
# ---------------------------------------------------------------------------


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


def flat_frame_chunk(tables, lo, categories=None):
    """Decoded flat pandas frame for ONE fact chunk (the chunked-oracle
    unit).  String attributes are pandas categoricals over their decoded
    values: the fact rows gather small int codes through the FK, never
    strings (group by them with `observed=True`, as `oracle` does: a
    pandas that defaults to False emits every combination of categories).
    A chunked caller passes `oracle_categories(tables)` once instead of
    paying the dimension-table sort per chunk."""
    import pandas as pd

    if categories is None:
        categories = oracle_categories(tables)
    data = {
        "lo_orderdate": lo["lo_orderdate"],
        **{m: np.asarray(lo[m], dtype=np.float64) for m in FLAT_METRICS},
    }
    idx_cache: Dict[str, np.ndarray] = {}
    for attr, (table, fk_col) in DIM_ATTRS.items():
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


def flat_frame(tables):
    """Decoded flat pandas DataFrame for oracle computation (string attrs
    materialized — use at test scales only)."""
    return flat_frame_chunk(tables, tables["lineorder"])


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
