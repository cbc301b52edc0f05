"""TPC-H data and its float64 pandas reference, numpy and pandas only.

COPIED from `spark_druid_olap_tpu/workloads/tpch.py` (`gen_tables`, the
flat layout of `flat_columns`, `oracle`) and NOT to track it: this is the
benchmark's yardstick, so a later change to the program's copy must not
move it.  It imports nothing of the program.  Changes against the
original, none of which changes a distribution:

* categorical columns are drawn as indices into their value lists (a
  nation uniform over the 25, its region the one it lies in, is `_geo`'s
  region-then-nation draw); `c_name` follows from `c_custkey`;
* the lineitem fact is drawn in two steps so that it can be streamed in
  `l_shipdate` order, as Druid partitions by time: the order key and the
  ship offset of every row first (`lineitem_keys`, sorted by ship day),
  then every other column, whose draws are independent of them, chunk by
  chunk from a stream of its own (`lineitem_chunk`);
* the reference runs over chunks, each query's partial groups merged
  before any ranking (`reference_answers`): Q3's and Q10's top-k are
  taken over whole groups;
* `precision` rounds the metric columns (the low-precision control).

An answer is one frame for every query (`frame`): the served rows and the
reference's rows go through the same function, so `harness/compare.py`
compares them without a change.

As a script it is the reference child of `loaders/tpch.py`: it computes
the answers of the named queries from the seed and pickles them to
`--out`.  It never touches JAX.
"""

from __future__ import annotations

import argparse
import os
import pickle
import sys
import time

import numpy as np

MS_DAY = 86_400_000
DAY0 = np.datetime64("1992-01-01", "D")  # day 0 of every day number here


def day(text: str) -> int:
    """The day number of an ISO date."""
    return int((np.datetime64(text, "D") - DAY0).astype(np.int64))


ORDER_DAYS = day("1998-08-02")  # o_orderdate: days 0 .. ORDER_DAYS - 1
SHIP_LAG_MAX = 121  # l_shipdate = o_orderdate + 1 .. 121 days
LINESTATUS_CUT = day("1995-06-17")  # l_linestatus F before it, O from it

REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
NATIONS = [  # five a region, in REGIONS' order: nation i lies in region i // 5
    "ALGERIA", "ETHIOPIA", "KENYA", "MOROCCO", "MOZAMBIQUE",
    "ARGENTINA", "BRAZIL", "CANADA", "PERU", "UNITED STATES",
    "CHINA", "INDIA", "INDONESIA", "JAPAN", "VIETNAM",
    "FRANCE", "GERMANY", "ROMANIA", "RUSSIA", "UNITED KINGDOM",
    "EGYPT", "IRAN", "IRAQ", "JORDAN", "SAUDI ARABIA",
]
SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "HOUSEHOLD", "MACHINERY"]
SHIPMODES = ["AIR", "FOB", "MAIL", "RAIL", "REG AIR", "SHIP", "TRUCK"]
PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
RETURNFLAGS = ["A", "N", "R"]
RETURNFLAG_P = [0.25, 0.5, 0.25]
LINESTATUS = ["F", "O"]
BRANDS = [f"Brand#{a}{b}" for a in range(1, 6) for b in range(1, 6)]
TYPES = ["ECONOMY ANODIZED STEEL", "LARGE BRUSHED BRASS",
         "MEDIUM POLISHED COPPER", "SMALL PLATED TIN",
         "STANDARD BURNISHED NICKEL"]

METRICS = ["l_quantity", "l_extendedprice", "l_discount", "l_tax"]

# the answer frame: key columns, then the value (harness/compare.py reads
# every column but the last as a key)
COLUMNS = ["part", "id", "measure", "exact", "value"]
# the traffic's measure names; the rest of an answer's columns are keys
MEASURES = {
    "sum_qty", "sum_base_price", "sum_disc_price", "sum_charge", "avg_qty",
    "avg_price", "avg_disc", "count_order", "revenue", "high_line_count",
    "low_line_count", "brazil_volume", "total_volume", "promo_revenue",
}
# counts: integers the device sums exactly (every group under 2^24 rows)
EXACT = {"count_order", "high_line_count", "low_line_count"}

_DIM_STREAM = 71_001  # spawn-key tags: the tables, the fact's keys, its chunks
_KEY_STREAM = 71_002
_CHUNK_STREAM = 71_003
CHUNK_ROWS = 1 << 22  # the fact's chunk geometry: both sides draw from it


def sizes(scale: float) -> dict:
    """Row counts at scale factor `scale` (workloads/tpch.gen_tables)."""
    return {
        "customer": max(100, int(150_000 * scale)),
        "supplier": max(50, int(10_000 * scale)),
        "part": max(200, int(200_000 * scale)),
        "orders": max(500, int(1_500_000 * scale)),
        "lineitem": int(6_001_215 * scale),
    }


def gen_tables(scale: float, seed: int) -> dict:
    """customer, supplier, part and orders, every categorical column as an
    index into its value list, dates as day numbers from 1992-01-01."""
    n = sizes(scale)
    rng = np.random.default_rng((seed, _DIM_STREAM))
    return {
        "customer": {
            "nation": rng.integers(0, 25, n["customer"]).astype(np.int8),
            "mktsegment": rng.integers(0, 5, n["customer"]).astype(np.int8),
        },
        "supplier": {
            "nation": rng.integers(0, 25, n["supplier"]).astype(np.int8),
        },
        "part": {
            "brand": rng.integers(0, 25, n["part"]).astype(np.int8),
            "type": rng.integers(0, 5, n["part"]).astype(np.int8),
        },
        "orders": {
            "orderday": rng.integers(0, ORDER_DAYS, n["orders"]).astype(np.int16),
            "custkey": rng.integers(0, n["customer"], n["orders"]).astype(np.int32),
            "priority": rng.integers(0, 5, n["orders"]).astype(np.int8),
        },
    }


def lineitem_keys(scale: float, seed: int, tables: dict):
    """(l_orderkey int32, ship day int16) of every fact row, sorted by
    ship day: an order key uniform over the orders, a ship day 1..121 days
    after its order's."""
    n = sizes(scale)
    rng = np.random.default_rng((seed, _KEY_STREAM))
    okey = rng.integers(0, n["orders"], n["lineitem"]).astype(np.int32)
    shipday = tables["orders"]["orderday"][okey] + rng.integers(
        1, SHIP_LAG_MAX + 1, n["lineitem"]
    ).astype(np.int16)
    order = np.argsort(shipday, kind="stable")
    return okey[order], shipday[order]


def n_chunks(scale: float) -> int:
    return -(-sizes(scale)["lineitem"] // CHUNK_ROWS)


def lineitem_chunk(ci: int, scale: float, seed: int, tables: dict, keys,
                   precision: str = "float32") -> dict:
    """Fact chunk `ci` (rows ci * CHUNK_ROWS ...): its keys, and every other
    column from its own stream default_rng((seed, _CHUNK_STREAM, ci)).
    Metric columns float32 as the configuration stores them, rounded to
    `precision` (the control's bfloat16) where asked."""
    okey_all, shipday_all = keys
    lo = ci * CHUNK_ROWS
    okey = okey_all[lo:lo + CHUNK_ROWS]
    shipday = shipday_all[lo:lo + CHUNK_ROWS]
    n = len(okey)
    size = sizes(scale)
    rng = np.random.default_rng((seed, _CHUNK_STREAM, ci))
    cols = {
        "l_orderkey": okey,
        "shipday": shipday,
        "suppkey": rng.integers(0, size["supplier"], n).astype(np.int32),
        "partkey": rng.integers(0, size["part"], n).astype(np.int32),
        "l_quantity": rng.integers(1, 51, n).astype(np.float32),
        "l_extendedprice": rng.random(n).astype(np.float32) * 55_450 + 90,
        "l_discount": (rng.integers(0, 11, n) / 100).astype(np.float32),
        "l_tax": (rng.integers(0, 9, n) / 100).astype(np.float32),
        "returnflag": rng.choice(3, n, p=RETURNFLAG_P).astype(np.int8),
        "shipmode": rng.integers(0, 7, n).astype(np.int8),
    }
    cols["linestatus"] = (shipday >= LINESTATUS_CUT).astype(np.int8)
    if precision != "float32":
        for m in METRICS:
            cols[m] = _round_to(cols[m], precision)
    return cols


def _round_to(values, precision: str):
    """The metric column as stored at `precision`: "bfloat16" is the
    control, the nearest precision below the configuration's float32."""
    if precision == "bfloat16":
        import ml_dtypes

        return np.asarray(values).astype(ml_dtypes.bfloat16).astype(np.float32)
    raise ValueError(f"unknown precision {precision!r}")


def year_of_day(days) -> np.ndarray:
    return (DAY0 + np.asarray(days).astype("timedelta64[D]")).astype(
        "datetime64[Y]"
    ).astype(int) + 1970


# ---------------------------------------------------------------------------
# the reference: each query's partial groups a chunk, merged, then ranked
# ---------------------------------------------------------------------------


def chunk_frame(tables, c):
    """One fact chunk, decoded to what the queries read (float64 metrics,
    index columns for the categoricals)."""
    import pandas as pd

    o, cu = tables["orders"], tables["customer"]
    okey = c["l_orderkey"]
    ckey = o["custkey"][okey]
    return pd.DataFrame({
        "l_orderkey": okey,
        "shipday": c["shipday"],
        "orderday": o["orderday"][okey],
        "priority": o["priority"][okey],
        "c_custkey": ckey,
        "c_nation": cu["nation"][ckey],
        "c_mktsegment": cu["mktsegment"][ckey],
        "s_nation": tables["supplier"]["nation"][c["suppkey"]],
        "p_brand": tables["part"]["brand"][c["partkey"]],
        "p_type": tables["part"]["type"][c["partkey"]],
        "returnflag": c["returnflag"],
        "linestatus": c["linestatus"],
        "shipmode": c["shipmode"],
        **{m: np.asarray(c[m], dtype=np.float64) for m in METRICS},
    })


def _sum(f, mask, by, **values):
    """Sums of `values` (name -> float64 array over f) over the rows of
    `mask`, grouped by `by` (a scalar row where `by` is empty)."""
    import pandas as pd

    g = pd.DataFrame({k: np.asarray(v)[mask] for k, v in values.items()})
    if not by:
        return g.sum().to_frame().T
    for k in by:
        g[k] = np.asarray(f[k])[mask]
    return g.groupby(by, as_index=False).sum()


def partial(f, name: str):
    """Query `name`'s partial sums over one chunk frame."""
    rev = f.l_extendedprice * (1 - f.l_discount)
    if name == "q1":
        m = f.shipday <= day("1998-09-02")
        return _sum(
            f, m, ["returnflag", "linestatus"],
            sum_qty=f.l_quantity, sum_base_price=f.l_extendedprice,
            sum_disc_price=rev, sum_charge=rev * (1 + f.l_tax),
            sum_disc=f.l_discount, count_order=np.ones(len(f)),
        )
    if name == "q3":
        m = (
            (f.c_mktsegment == SEGMENTS.index("BUILDING"))
            & (f.orderday < day("1995-03-15"))
            & (f.shipday > day("1995-03-15"))
        )
        return _sum(f, m, ["l_orderkey"], revenue=rev)
    if name == "q10":
        m = (
            (f.orderday >= day("1993-10-01")) & (f.orderday < day("1994-01-01"))
            & (f.returnflag == RETURNFLAGS.index("R"))
        )
        return _sum(f, m, ["c_custkey"], revenue=rev)
    if name == "q5":
        asia = [NATIONS.index(x) for x in NATIONS[10:15]]
        m = (
            f.c_nation.isin(asia) & f.s_nation.isin(asia)
            & (f.orderday >= day("1994-01-01")) & (f.orderday < day("1995-01-01"))
        )
        return _sum(f, m, ["s_nation"], revenue=rev)
    if name == "q6":
        m = (
            (f.shipday >= day("1994-01-01")) & (f.shipday < day("1995-01-01"))
            & (f.l_discount >= 0.05) & (f.l_discount <= 0.07)
            & (f.l_quantity < 24)
        )
        return _sum(f, m, [], revenue=f.l_extendedprice * f.l_discount)
    if name == "q12":
        high = f.priority.isin([0, 1])
        m = (
            f.shipmode.isin([SHIPMODES.index("MAIL"), SHIPMODES.index("SHIP")])
            & (f.shipday >= day("1994-01-01")) & (f.shipday < day("1995-01-01"))
        )
        return _sum(
            f, m, ["shipmode"], high_line_count=high.astype(np.float64),
            low_line_count=(~high).astype(np.float64),
        )
    if name == "q7":
        fr, de = NATIONS.index("FRANCE"), NATIONS.index("GERMANY")
        m = (
            (((f.s_nation == fr) & (f.c_nation == de))
             | ((f.s_nation == de) & (f.c_nation == fr)))
            & (f.shipday >= day("1995-01-01")) & (f.shipday <= day("1996-12-31"))
        )
        g = f.assign(l_year=year_of_day(f.shipday))
        return _sum(g, m, ["s_nation", "c_nation", "l_year"], revenue=rev)
    if name == "q14":
        m = (f.shipday >= day("1995-09-01")) & (f.shipday < day("1995-10-01"))
        promo = f.p_type.isin(
            [i for i, t in enumerate(TYPES) if t.startswith("MEDIUM")]
        )
        return _sum(f, m, [], promo=np.where(promo, rev, 0.0), total=rev)
    if name == "q19":
        air = f.shipmode.isin([SHIPMODES.index("AIR"), SHIPMODES.index("REG AIR")])

        def block(brand, lo, hi):
            return (
                (f.p_brand == BRANDS.index(brand))
                & (f.l_quantity >= lo) & (f.l_quantity <= hi) & air
            )

        m = (
            block("Brand#12", 1, 11) | block("Brand#23", 10, 20)
            | block("Brand#34", 20, 30)
        )
        return _sum(f, m, [], revenue=rev)
    if name == "q8":
        america = [NATIONS.index(x) for x in NATIONS[5:10]]
        m = (
            f.c_nation.isin(america)
            & (f.p_type == TYPES.index("ECONOMY ANODIZED STEEL"))
            & (f.orderday >= day("1995-01-01")) & (f.orderday <= day("1996-12-31"))
        )
        g = f.assign(o_orderdate_year=year_of_day(f.orderday))
        brazil = f.s_nation == NATIONS.index("BRAZIL")
        return _sum(
            g, m, ["o_orderdate_year"],
            brazil_volume=np.where(brazil, rev, 0.0), total_volume=rev,
        )
    raise KeyError(name)


def answer(name: str, parts, tables) -> list:
    """The merged partials of query `name` as its SQL answer: rows (dicts
    by output column, in the answer's order)."""
    import pandas as pd

    parts = [p for p in parts if len(p)]
    f = pd.concat(parts, ignore_index=True)
    keys = {
        "q1": ["returnflag", "linestatus"], "q3": ["l_orderkey"],
        "q10": ["c_custkey"], "q5": ["s_nation"], "q12": ["shipmode"],
        "q7": ["s_nation", "c_nation", "l_year"],
        "q8": ["o_orderdate_year"],
    }.get(name, [])
    f = f.groupby(keys, as_index=False).sum() if keys else f.sum().to_frame().T
    if name == "q1":
        f["avg_qty"] = f.sum_qty / f.count_order
        f["avg_price"] = f.sum_base_price / f.count_order
        f["avg_disc"] = f.sum_disc / f.count_order
        f["l_returnflag"] = [RETURNFLAGS[i] for i in f.returnflag]
        f["l_linestatus"] = [LINESTATUS[i] for i in f.linestatus]
        f = f.sort_values(["l_returnflag", "l_linestatus"])
        cols = ["l_returnflag", "l_linestatus", "sum_qty", "sum_base_price",
                "sum_disc_price", "sum_charge", "avg_qty", "avg_price",
                "avg_disc", "count_order"]
    elif name == "q3":
        f = f.sort_values("revenue", ascending=False, kind="stable").head(10)
        cols = ["l_orderkey", "revenue"]
    elif name == "q10":
        f = f.sort_values("revenue", ascending=False, kind="stable").head(20)
        nation = tables["customer"]["nation"][f.c_custkey.to_numpy()]
        f["c_name"] = [f"Customer#{int(k):09d}" for k in f.c_custkey]
        f["c_nation"] = [NATIONS[i] for i in nation]
        cols = ["c_custkey", "c_name", "c_nation", "revenue"]
    elif name == "q5":
        f["s_nation"] = [NATIONS[i] for i in f.s_nation]
        f = f.sort_values("revenue", ascending=False, kind="stable")
        cols = ["s_nation", "revenue"]
    elif name == "q12":
        f["l_shipmode"] = [SHIPMODES[i] for i in f.shipmode]
        f = f.sort_values("l_shipmode")
        cols = ["l_shipmode", "high_line_count", "low_line_count"]
    elif name == "q7":
        f["s_nation"] = [NATIONS[i] for i in f.s_nation]
        f["c_nation"] = [NATIONS[i] for i in f.c_nation]
        f = f.sort_values(["s_nation", "c_nation", "l_year"])
        cols = ["s_nation", "c_nation", "l_year", "revenue"]
    elif name == "q8":
        f = f.sort_values("o_orderdate_year")
        cols = ["o_orderdate_year", "brazil_volume", "total_volume"]
    elif name == "q14":
        f["promo_revenue"] = 100.0 * f.promo / f.total
        cols = ["promo_revenue"]
    else:  # q6, q19
        cols = ["revenue"]
    return f[cols].to_dict("records")


def _key_text(v) -> str:
    """A key as text: JSON and pandas disagree on int against float."""
    if isinstance(v, (float, np.floating)) and float(v).is_integer():
        return str(int(v))
    if isinstance(v, (np.integer,)):
        return str(int(v))
    return str(v)


def frame(rows):
    """An answer (rows, each a dict by output column, in answer order) as
    the long frame `(part, id, measure, exact, value)`:

    * `("group", key, measure, ...)` per group and measure: the group's
      key columns joined by "|" as `id`, so every group of the answer and
      each of its sums is compared;
    * `("rank", r, measure, ...)` per row and measure: the value at the
      r-th place of the answer, so the order is compared (a sort on the
      wrong value or a group at another place reads as an error of the
      value; two groups within the sum's error of each other may trade
      places);
    * a count (`EXACT`) is an integer carried as text in `exact`, a key:
      it must be equal; every other measure's `exact` is empty and its
      value is compared against the sum limit."""
    import pandas as pd

    out = []
    for r, row in enumerate(rows):
        keys = [k for k in row if k not in MEASURES]
        gid = "|".join(_key_text(row[k]) for k in keys)
        for m in row:
            if m not in MEASURES:
                continue
            v = row[m]
            v = float("nan") if v is None else float(v)
            exact = str(int(round(v))) if m in EXACT else ""
            out.append(("group", gid, m, exact, v))
            out.append(("rank", str(r), m, exact, v))
    return pd.DataFrame(out, columns=COLUMNS)


def reference_answers(scale, seed, queries, precision="float32"):
    """{query name: answer frame} of `queries` over the data that
    (`scale`, `seed`) define, chunk by chunk."""
    tables = gen_tables(scale, seed)
    keys = lineitem_keys(scale, seed, tables)
    parts = {name: [] for name in queries}
    for ci in range(n_chunks(scale)):
        f = chunk_frame(
            tables, lineitem_chunk(ci, scale, seed, tables, keys, precision)
        )
        for name in queries:
            parts[name].append(partial(f, name))
    return {
        name: frame(answer(name, parts[name], tables)) for name in queries
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description="TPC-H reference child")
    ap.add_argument("--scale", type=float, required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--queries", required=True, help="comma-separated")
    ap.add_argument("--precision", action="append", required=True,
                    help="one answer set per precision, in this order")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    os.nice(10)  # the window's client and server threads come first
    t0 = time.perf_counter()
    queries = args.queries.split(",")
    out = {
        p: reference_answers(args.scale, args.seed, queries, p)
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
