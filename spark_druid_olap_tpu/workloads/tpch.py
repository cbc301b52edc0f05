"""TPC-H star workload: normalized tables, snowflake star declaration, and
the reference test suite's query classes in joined-SQL form.

Reference parity: the reference's integration corpus is the TPC-H
*flattened star* — `orderLineItemPartSupplier` over Druid datasource `tpch`
(SURVEY.md §4 `[U]`: `TPCHTest` runs Q1/Q3/Q5/Q7/Q8-class star queries with
the star-schema JSON + functional dependencies declared in the DDL).  Here:

* `gen_tables(scale)` builds a normalized TPC-H subset: `lineitem` fact +
  `orders` / `customer` / `supplier` / `part` dims.  Nation/region attributes
  are folded into customer and supplier as strings (the reference's flat
  table does the same; a dual-role `nation` dim would need join aliasing the
  star layer deliberately doesn't model).
* customer hangs off orders (`lineitem -> orders -> customer`) — the
  snowflake edge `StarRelationInfo(parent=...)` exists for exactly this.
* `QUERIES`: Q1 (single-table agg incl. AVG rewrite), Q3 (high-cardinality
  group by l_orderkey + ORDER BY revenue LIMIT 10), Q10-class (group by
  customer attributes pruned to c_custkey by FDs + ORDER BY revenue LIMIT
  20), Q5-class (regional supplier volume), Q6 (interval + bound filters
  into an expression aggregate), Q12-class (shipmode CASE counts), Q7-class
  (OR-of-ANDs across two branches, EXTRACT(YEAR) over the time column),
  Q14-class (LIKE inside CASE, a ratio post-aggregation), Q19-class
  (disjunction of conjunct blocks with metric bounds), Q8-class over the
  materialized `o_orderdate_year` (`q8`) and over EXTRACT(YEAR FROM
  o_orderdate) (`q8_extract`).  Q4/Q21-style EXISTS semijoins are out of
  scope: the planner has no semijoin rewrite (neither does the
  reference's — those queries fell back to Spark there too).
* `oracle(tables, name)` computes each result in float64 pandas.

Constants are adapted to this generator's value domains; query *shapes*
(join pattern, predicates, grouping, ordering) follow the TPC-H spec.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

from ..catalog.segment import DimensionDict
from ..catalog.star import FunctionalDependency, StarRelationInfo, StarSchemaInfo

_MS_DAY = 86_400_000

REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
NATIONS = {
    "AFRICA": ["ALGERIA", "ETHIOPIA", "KENYA", "MOROCCO", "MOZAMBIQUE"],
    "AMERICA": ["ARGENTINA", "BRAZIL", "CANADA", "PERU", "UNITED STATES"],
    "ASIA": ["CHINA", "INDIA", "INDONESIA", "JAPAN", "VIETNAM"],
    "EUROPE": ["FRANCE", "GERMANY", "ROMANIA", "RUSSIA", "UNITED KINGDOM"],
    "MIDDLE EAST": ["EGYPT", "IRAN", "IRAQ", "JORDAN", "SAUDI ARABIA"],
}
SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "HOUSEHOLD", "MACHINERY"]
SHIPMODES = ["AIR", "FOB", "MAIL", "RAIL", "REG AIR", "SHIP", "TRUCK"]
PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]

# attribute -> (owning table, fact-side index resolver)
DIM_ATTRS = {
    "o_orderpriority": "orders",
    "o_orderdate": "orders",  # numeric-dict dimension: ~2.4k distinct days
    "o_orderdate_year": "orders",
    "c_custkey": "orders",
    "c_name": "orders",
    "c_mktsegment": "orders",  # customer attrs ride the orders row (snowflake)
    "c_nation": "orders",
    "c_region": "orders",
    "s_nation": "supplier",
    "s_region": "supplier",
    "p_brand": "part",
    "p_type": "part",
    "l_returnflag": "lineitem",
    "l_linestatus": "lineitem",
    "l_shipmode": "lineitem",
    "l_orderkey": "lineitem",
}

FLAT_METRICS = [
    "l_quantity", "l_extendedprice", "l_discount", "l_tax",
]

STAR_SCHEMA = StarSchemaInfo(
    fact_table="lineitem",
    relations=(
        StarRelationInfo("orders", (("l_orderkey", "o_orderkey"),)),
        StarRelationInfo(
            "customer", (("o_custkey", "c_custkey"),), parent="orders"
        ),
        StarRelationInfo("supplier", (("l_suppkey", "s_suppkey"),)),
        StarRelationInfo("part", (("l_partkey", "p_partkey"),)),
    ),
    functional_dependencies=(
        FunctionalDependency("customer", "c_custkey", "c_name"),
        FunctionalDependency("customer", "c_custkey", "c_nation"),
        FunctionalDependency("customer", "c_custkey", "c_mktsegment"),
        FunctionalDependency("customer", "c_nation", "c_region"),
        FunctionalDependency("supplier", "s_nation", "s_region"),
        FunctionalDependency("orders", "o_orderkey", "o_orderpriority"),
    ),
)


def _geo(n: int, rng):
    reg = rng.choice(np.array(REGIONS, dtype=object), size=n)
    nation = np.empty(n, dtype=object)
    for r in REGIONS:
        m = reg == r
        nation[m] = rng.choice(np.array(NATIONS[r], dtype=object), int(m.sum()))
    return reg, nation


def gen_tables(scale: float = 0.01, seed: int = 13) -> Dict[str, Dict[str, np.ndarray]]:
    """Normalized TPC-H subset at ~SF `scale` (SF1: 6M lineitem rows).
    Keys are dense 0..n-1 so the pre-join is a direct gather."""
    rng = np.random.default_rng(seed)

    n_c = max(100, int(150_000 * scale))
    c_region, c_nation = _geo(n_c, rng)
    customer = {
        "c_custkey": np.arange(n_c, dtype=np.int64),
        "c_name": np.array(
            [f"Customer#{k:09d}" for k in range(n_c)], dtype=object
        ),
        "c_mktsegment": rng.choice(np.array(SEGMENTS, dtype=object), n_c),
        "c_nation": c_nation,
        "c_region": c_region,
    }

    n_s = max(50, int(10_000 * scale))
    s_region, s_nation = _geo(n_s, rng)
    supplier = {
        "s_suppkey": np.arange(n_s, dtype=np.int64),
        "s_nation": s_nation,
        "s_region": s_region,
    }

    n_p = max(200, int(200_000 * scale))
    part = {
        "p_partkey": np.arange(n_p, dtype=np.int64),
        "p_brand": np.array(
            [f"Brand#{a}{b}" for a, b in zip(
                rng.integers(1, 6, n_p), rng.integers(1, 6, n_p)
            )], dtype=object,
        ),
        "p_type": rng.choice(
            np.array(
                ["ECONOMY ANODIZED STEEL", "LARGE BRUSHED BRASS",
                 "MEDIUM POLISHED COPPER", "SMALL PLATED TIN",
                 "STANDARD BURNISHED NICKEL"], dtype=object,
            ),
            n_p,
        ),
    }

    n_o = max(500, int(1_500_000 * scale))
    d0 = int(np.datetime64("1992-01-01", "ms").astype(np.int64))
    d1 = int(np.datetime64("1998-08-02", "ms").astype(np.int64))
    o_orderdate = (
        rng.integers(d0 // _MS_DAY, d1 // _MS_DAY, size=n_o) * _MS_DAY
    )
    orders = {
        "o_orderkey": np.arange(n_o, dtype=np.int64),
        "o_custkey": rng.integers(0, n_c, size=n_o).astype(np.int64),
        "o_orderdate": o_orderdate,
        "o_orderpriority": rng.choice(np.array(PRIORITIES, dtype=object), n_o),
    }

    n = int(6_001_215 * scale)
    okey = rng.integers(0, n_o, size=n).astype(np.int64)
    shipdate = orders["o_orderdate"][okey] + rng.integers(
        1, 122, size=n
    ) * _MS_DAY
    lineitem = {
        "l_orderkey": okey,
        "l_suppkey": rng.integers(0, n_s, size=n).astype(np.int64),
        "l_partkey": rng.integers(0, n_p, size=n).astype(np.int64),
        "l_shipdate": shipdate,
        "l_quantity": rng.integers(1, 51, size=n).astype(np.float32),
        "l_extendedprice": (rng.random(n).astype(np.float32) * 55_450 + 90),
        "l_discount": (rng.integers(0, 11, size=n) / 100).astype(np.float32),
        "l_tax": (rng.integers(0, 9, size=n) / 100).astype(np.float32),
        "l_returnflag": rng.choice(
            np.array(["A", "N", "R"], dtype=object), n, p=[0.25, 0.5, 0.25]
        ),
        "l_linestatus": np.where(
            shipdate < int(np.datetime64("1995-06-17", "ms").astype(np.int64)),
            "F", "O",
        ).astype(object),
        "l_shipmode": rng.choice(np.array(SHIPMODES, dtype=object), n),
    }
    return {
        "lineitem": lineitem, "orders": orders, "customer": customer,
        "supplier": supplier, "part": part,
    }


def flat_columns(tables):
    """Pre-join the snowflake into the dictionary-encoded flat datasource
    (dictionaries built on the SMALL tables, codes gathered through FKs)."""
    li = tables["lineitem"]
    o = tables["orders"]
    c = tables["customer"]
    okey = li["l_orderkey"]
    ckey = o["o_custkey"][okey]  # snowflake hop resolved at flatten time

    cols: Dict[str, np.ndarray] = {
        "l_shipdate": li["l_shipdate"],
        "o_orderdate": o["o_orderdate"][okey],
        **{m: li[m] for m in FLAT_METRICS},
    }
    dicts: Dict[str, DimensionDict] = {}

    def add(attr, values, fact_idx):
        if values.dtype.kind in ("U", "S", "O"):
            d = DimensionDict.build(list(values))
            codes = d.encode(list(values))
        else:
            uniq = np.unique(values.astype(np.int64))
            d = DimensionDict(values=tuple(int(v) for v in uniq))
            codes = d.encode_numeric(values)
        dicts[attr] = d
        cols[attr] = codes[fact_idx] if fact_idx is not None else codes

    add("o_orderpriority", o["o_orderpriority"], okey)
    year = (
        o["o_orderdate"].astype("datetime64[ms]").astype("datetime64[Y]")
        .astype(int) + 1970
    )
    add("o_orderdate_year", year.astype(np.int64), okey)
    add("c_custkey", c["c_custkey"], ckey)
    add("c_name", c["c_name"], ckey)
    add("c_mktsegment", c["c_mktsegment"], ckey)
    add("c_nation", c["c_nation"], ckey)
    add("c_region", c["c_region"], ckey)
    add("s_nation", tables["supplier"]["s_nation"], li["l_suppkey"])
    add("s_region", tables["supplier"]["s_region"], li["l_suppkey"])
    add("p_brand", tables["part"]["p_brand"], li["l_partkey"])
    add("p_type", tables["part"]["p_type"], li["l_partkey"])
    for a in ("l_returnflag", "l_linestatus", "l_shipmode"):
        add(a, li[a], None)
    add("l_orderkey", li["l_orderkey"], None)
    return cols, dicts


FLAT_DIMS = list(DIM_ATTRS)


def register(ctx, scale: float = 0.01, seed: int = 13,
             rows_per_segment: int = 1 << 22, tables=None):
    """Register the flat fact (with snowflake star schema) + normalized
    dims — the reference's orderLineItemPartSupplier DDL analog.  The fact
    is sorted by `l_shipdate` before it is segmented, as Druid partitions
    by time: a segment then spans a short run of ship dates, and an
    interval on them prunes whole segments."""
    tables = tables if tables is not None else gen_tables(scale, seed)
    cols, dicts = flat_columns(tables)
    order = np.argsort(cols["l_shipdate"], kind="stable")
    cols = {name: values[order] for name, values in cols.items()}
    ctx.register_table(
        "lineitem", cols,
        dimensions=FLAT_DIMS, metrics=FLAT_METRICS,
        time_column="l_shipdate", star_schema=STAR_SCHEMA,
        rows_per_segment=rows_per_segment, dicts=dicts,
    )
    ctx.register_table("orders", tables["orders"], time_column="o_orderdate")
    for t in ("customer", "supplier", "part"):
        ctx.register_table(t, tables[t])
    return tables


_J_ORD = "JOIN orders ON l_orderkey = o_orderkey"
_J_CUST = "JOIN customer ON o_custkey = c_custkey"
_J_SUPP = "JOIN supplier ON l_suppkey = s_suppkey"
_J_PART = "JOIN part ON l_partkey = p_partkey"

QUERIES: Dict[str, str] = {
    # Q1: pricing summary report — AVG rewrite + expression aggregates
    "q1": """
        SELECT l_returnflag, l_linestatus,
               sum(l_quantity) AS sum_qty,
               sum(l_extendedprice) AS sum_base_price,
               sum(l_extendedprice * (1 - l_discount)) AS sum_disc_price,
               sum(l_extendedprice * (1 - l_discount) * (1 + l_tax)) AS sum_charge,
               avg(l_quantity) AS avg_qty,
               avg(l_extendedprice) AS avg_price,
               avg(l_discount) AS avg_disc,
               count(*) AS count_order
        FROM lineitem
        WHERE l_shipdate <= '1998-09-02'
        GROUP BY l_returnflag, l_linestatus
        ORDER BY l_returnflag, l_linestatus
    """,
    # Q3-class: shipping priority — snowflake join + huge group domain
    # (l_orderkey: the sparse-groupby shape) + ORDER BY revenue LIMIT 10
    "q3": f"""
        SELECT l_orderkey,
               sum(l_extendedprice * (1 - l_discount)) AS revenue
        FROM lineitem {_J_ORD} {_J_CUST}
        WHERE c_mktsegment = 'BUILDING'
          AND o_orderdate < '1995-03-15'
          AND l_shipdate > '1995-03-15'
        GROUP BY l_orderkey
        ORDER BY revenue DESC
        LIMIT 10
    """,
    # Q10-class: returned-item reporting — GROUP BY customer attributes;
    # exercises FD grouping pruning (c_custkey determines c_name/c_nation:
    # the kernel groups by c_custkey alone, pruned columns ride hidden
    # code aggregations)
    "q10": f"""
        SELECT c_custkey, c_name, c_nation,
               sum(l_extendedprice * (1 - l_discount)) AS revenue
        FROM lineitem {_J_ORD} {_J_CUST}
        WHERE o_orderdate >= '1993-10-01' AND o_orderdate < '1994-01-01'
          AND l_returnflag = 'R'
        GROUP BY c_custkey, c_name, c_nation
        ORDER BY revenue DESC
        LIMIT 20
    """,
    # Q5-class: local supplier volume — both dim branches constrained to one
    # region, grouped by supplier nation
    "q5": f"""
        SELECT s_nation, sum(l_extendedprice * (1 - l_discount)) AS revenue
        FROM lineitem {_J_ORD} {_J_CUST} {_J_SUPP}
        WHERE c_region = 'ASIA' AND s_region = 'ASIA'
          AND o_orderdate >= '1994-01-01' AND o_orderdate < '1995-01-01'
        GROUP BY s_nation
        ORDER BY revenue DESC
    """,
    # Q6: forecasting revenue change — pure interval + bound filters into an
    # expression aggregate, no grouping
    "q6": """
        SELECT sum(l_extendedprice * l_discount) AS revenue
        FROM lineitem
        WHERE l_shipdate >= '1994-01-01' AND l_shipdate < '1995-01-01'
          AND l_discount >= 0.05 AND l_discount <= 0.07
          AND l_quantity < 24
    """,
    # Q12-class: shipmode line-priority counts — CASE inside SUM
    "q12": f"""
        SELECT l_shipmode,
               sum(CASE WHEN o_orderpriority = '1-URGENT'
                         OR o_orderpriority = '2-HIGH'
                        THEN 1 ELSE 0 END) AS high_line_count,
               sum(CASE WHEN o_orderpriority <> '1-URGENT'
                        AND o_orderpriority <> '2-HIGH'
                        THEN 1 ELSE 0 END) AS low_line_count
        FROM lineitem {_J_ORD}
        WHERE l_shipmode IN ('MAIL', 'SHIP')
          AND l_shipdate >= '1994-01-01' AND l_shipdate < '1995-01-01'
        GROUP BY l_shipmode
        ORDER BY l_shipmode
    """,
    # Q7-class: volume shipping between two nations — OR-of-ANDs across two
    # dimension branches + EXTRACT over the time column as a grouping dim
    "q7": f"""
        SELECT s_nation, c_nation,
               EXTRACT(YEAR FROM l_shipdate) AS l_year,
               sum(l_extendedprice * (1 - l_discount)) AS revenue
        FROM lineitem {_J_ORD} {_J_CUST} {_J_SUPP}
        WHERE ((s_nation = 'FRANCE' AND c_nation = 'GERMANY')
            OR (s_nation = 'GERMANY' AND c_nation = 'FRANCE'))
          AND l_shipdate >= '1995-01-01' AND l_shipdate <= '1996-12-31'
        GROUP BY s_nation, c_nation, EXTRACT(YEAR FROM l_shipdate)
        ORDER BY s_nation, c_nation, l_year
    """,
    # Q14-class: promo revenue — LIKE inside CASE, ratio of two aggregates
    # as a post-aggregation (constants adapted to this generator's p_type
    # domain: 'MEDIUM%' plays the role of 'PROMO%')
    "q14": f"""
        SELECT 100 * sum(CASE WHEN p_type LIKE 'MEDIUM%'
                              THEN l_extendedprice * (1 - l_discount)
                              ELSE 0 END)
                 / sum(l_extendedprice * (1 - l_discount)) AS promo_revenue
        FROM lineitem {_J_PART}
        WHERE l_shipdate >= '1995-09-01' AND l_shipdate < '1995-10-01'
    """,
    # Q19-class: discounted revenue — disjunction of conjunct blocks mixing
    # string dims and numeric metric bounds
    "q19": f"""
        SELECT sum(l_extendedprice * (1 - l_discount)) AS revenue
        FROM lineitem {_J_PART}
        WHERE (p_brand = 'Brand#12' AND l_quantity >= 1 AND l_quantity <= 11
               AND l_shipmode IN ('AIR', 'REG AIR'))
           OR (p_brand = 'Brand#23' AND l_quantity >= 10 AND l_quantity <= 20
               AND l_shipmode IN ('AIR', 'REG AIR'))
           OR (p_brand = 'Brand#34' AND l_quantity >= 20 AND l_quantity <= 30
               AND l_shipmode IN ('AIR', 'REG AIR'))
    """,
    # Q8 via EXTRACT(YEAR FROM o_orderdate) — no pre-materialized year
    # column needed (dictionary-backed EXTRACT dimension)
    "q8_extract": f"""
        SELECT EXTRACT(YEAR FROM o_orderdate) AS o_orderdate_year,
               sum(CASE WHEN s_nation = 'BRAZIL'
                        THEN l_extendedprice * (1 - l_discount)
                        ELSE 0 END) AS brazil_volume,
               sum(l_extendedprice * (1 - l_discount)) AS total_volume
        FROM lineitem {_J_ORD} {_J_CUST} {_J_SUPP} {_J_PART}
        WHERE c_region = 'AMERICA' AND p_type = 'ECONOMY ANODIZED STEEL'
          AND o_orderdate >= '1995-01-01' AND o_orderdate <= '1996-12-31'
        GROUP BY EXTRACT(YEAR FROM o_orderdate)
        ORDER BY o_orderdate_year
    """,
    # Q8-class: market share numerator/denominator via CASE over nation
    "q8": f"""
        SELECT o_orderdate_year,
               sum(CASE WHEN s_nation = 'BRAZIL'
                        THEN l_extendedprice * (1 - l_discount)
                        ELSE 0 END) AS brazil_volume,
               sum(l_extendedprice * (1 - l_discount)) AS total_volume
        FROM lineitem {_J_ORD} {_J_CUST} {_J_SUPP} {_J_PART}
        WHERE c_region = 'AMERICA' AND p_type = 'ECONOMY ANODIZED STEEL'
          AND o_orderdate >= '1995-01-01' AND o_orderdate <= '1996-12-31'
        GROUP BY o_orderdate_year
        ORDER BY o_orderdate_year
    """,
}


# ---------------------------------------------------------------------------
# pandas float64 oracle — test scales only
# ---------------------------------------------------------------------------


def flat_frame(tables):
    import pandas as pd

    li = tables["lineitem"]
    o = tables["orders"]
    okey = li["l_orderkey"]
    ckey = o["o_custkey"][okey]
    c = tables["customer"]
    s = tables["supplier"]
    p = tables["part"]
    year = (
        o["o_orderdate"].astype("datetime64[ms]").astype("datetime64[Y]")
        .astype(int) + 1970
    )
    return pd.DataFrame(
        {
            "l_orderkey": okey,
            "l_shipdate": li["l_shipdate"],
            "o_orderdate": o["o_orderdate"][okey],
            "o_orderdate_year": year[okey],
            "o_orderpriority": o["o_orderpriority"][okey],
            "c_custkey": c["c_custkey"][ckey],
            "c_name": c["c_name"][ckey],
            "c_mktsegment": c["c_mktsegment"][ckey],
            "c_nation": c["c_nation"][ckey],
            "c_region": c["c_region"][ckey],
            "s_nation": s["s_nation"][li["l_suppkey"]],
            "s_region": s["s_region"][li["l_suppkey"]],
            "p_brand": p["p_brand"][li["l_partkey"]],
            "p_type": p["p_type"][li["l_partkey"]],
            "l_returnflag": li["l_returnflag"],
            "l_linestatus": li["l_linestatus"],
            "l_shipmode": li["l_shipmode"],
            **{
                m: np.asarray(li[m], dtype=np.float64)
                for m in FLAT_METRICS
            },
        }
    )


def _ms(s: str) -> int:
    return int(np.datetime64(s, "ms").astype(np.int64))


def oracle(f, name: str):
    """float64 reference result for QUERIES[name] over flat_frame output."""
    rev = f.l_extendedprice * (1 - f.l_discount)
    if name == "q1":
        m = f.l_shipdate <= _ms("1998-09-02")
        g = f[m].assign(
            disc_price=rev[m],
            charge=rev[m] * (1 + f.l_tax[m]),
        )
        out = g.groupby(["l_returnflag", "l_linestatus"], as_index=False).agg(
            sum_qty=("l_quantity", "sum"),
            sum_base_price=("l_extendedprice", "sum"),
            sum_disc_price=("disc_price", "sum"),
            sum_charge=("charge", "sum"),
            avg_qty=("l_quantity", "mean"),
            avg_price=("l_extendedprice", "mean"),
            avg_disc=("l_discount", "mean"),
            count_order=("l_quantity", "count"),
        )
        return out.sort_values(["l_returnflag", "l_linestatus"]).reset_index(
            drop=True
        )
    if name == "q3":
        m = (
            (f.c_mktsegment == "BUILDING")
            & (f.o_orderdate < _ms("1995-03-15"))
            & (f.l_shipdate > _ms("1995-03-15"))
        )
        g = (
            f[m].assign(revenue=rev[m])
            .groupby("l_orderkey", as_index=False)["revenue"].sum()
        )
        return g.sort_values("revenue", ascending=False).head(10).reset_index(
            drop=True
        )
    if name == "q10":
        m = (
            (f.o_orderdate >= _ms("1993-10-01"))
            & (f.o_orderdate < _ms("1994-01-01"))
            & (f.l_returnflag == "R")
        )
        g = (
            f[m].assign(revenue=rev[m])
            .groupby(["c_custkey", "c_name", "c_nation"], as_index=False)[
                "revenue"
            ].sum()
        )
        return g.sort_values("revenue", ascending=False).head(20).reset_index(
            drop=True
        )
    if name == "q5":
        m = (
            (f.c_region == "ASIA") & (f.s_region == "ASIA")
            & (f.o_orderdate >= _ms("1994-01-01"))
            & (f.o_orderdate < _ms("1995-01-01"))
        )
        g = (
            f[m].assign(revenue=rev[m])
            .groupby("s_nation", as_index=False)["revenue"].sum()
        )
        return g.sort_values("revenue", ascending=False).reset_index(drop=True)
    if name == "q6":
        m = (
            (f.l_shipdate >= _ms("1994-01-01"))
            & (f.l_shipdate < _ms("1995-01-01"))
            & (f.l_discount >= 0.05) & (f.l_discount <= 0.07)
            & (f.l_quantity < 24)
        )
        return float((f.l_extendedprice[m] * f.l_discount[m]).sum())
    if name == "q12":
        m = (
            f.l_shipmode.isin(["MAIL", "SHIP"])
            & (f.l_shipdate >= _ms("1994-01-01"))
            & (f.l_shipdate < _ms("1995-01-01"))
        )
        g = f[m]
        high = g.o_orderpriority.isin(["1-URGENT", "2-HIGH"])
        out = (
            g.assign(high=high.astype(np.int64), low=(~high).astype(np.int64))
            .groupby("l_shipmode", as_index=False)
            .agg(high_line_count=("high", "sum"), low_line_count=("low", "sum"))
        )
        return out.sort_values("l_shipmode").reset_index(drop=True)
    if name == "q7":
        m = (
            (
                ((f.s_nation == "FRANCE") & (f.c_nation == "GERMANY"))
                | ((f.s_nation == "GERMANY") & (f.c_nation == "FRANCE"))
            )
            & (f.l_shipdate >= _ms("1995-01-01"))
            & (f.l_shipdate <= _ms("1996-12-31"))
        )
        g = f[m]
        l_year = (
            np.asarray(g.l_shipdate, dtype="datetime64[ms]")
            .astype("datetime64[Y]")
            .astype(int)
            + 1970
        )
        out = (
            g.assign(l_year=l_year, revenue=rev[m])
            .groupby(["s_nation", "c_nation", "l_year"], as_index=False)[
                "revenue"
            ]
            .sum()
        )
        return out.sort_values(
            ["s_nation", "c_nation", "l_year"]
        ).reset_index(drop=True)
    if name == "q14":
        m = (f.l_shipdate >= _ms("1995-09-01")) & (
            f.l_shipdate < _ms("1995-10-01")
        )
        g = f[m]
        grev = rev[m]
        promo = np.where(
            g.p_type.str.startswith("MEDIUM"), grev, 0.0
        ).sum()
        return float(100.0 * promo / grev.sum())
    if name == "q19":
        block = lambda brand, lo, hi: (
            (f.p_brand == brand)
            & (f.l_quantity >= lo)
            & (f.l_quantity <= hi)
            & f.l_shipmode.isin(["AIR", "REG AIR"])
        )
        m = block("Brand#12", 1, 11) | block("Brand#23", 10, 20) | block(
            "Brand#34", 20, 30
        )
        return float(rev[m].sum())
    if name == "q8":
        m = (
            (f.c_region == "AMERICA")
            & (f.p_type == "ECONOMY ANODIZED STEEL")
            & (f.o_orderdate >= _ms("1995-01-01"))
            & (f.o_orderdate <= _ms("1996-12-31"))
        )
        g = f[m]
        grev = rev[m]
        out = (
            g.assign(
                brazil_volume=np.where(g.s_nation == "BRAZIL", grev, 0.0),
                total_volume=grev,
            )
            .groupby("o_orderdate_year", as_index=False)
            .agg(
                brazil_volume=("brazil_volume", "sum"),
                total_volume=("total_volume", "sum"),
            )
        )
        return out.sort_values("o_orderdate_year").reset_index(drop=True)
    raise KeyError(name)
