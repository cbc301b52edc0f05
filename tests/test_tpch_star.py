"""TPC-H star workload: join elimination + query parity (the reference's
TPCHTest analog, SURVEY.md §4).

Two assertion styles, mirroring upstream: (1) the rewrite happened — explain
output shows the collapsed fact scan (the "plan contains DruidQuery" check);
(2) exact/near-exact parity against a float64 pandas oracle on the same
generated rows."""

import numpy as np
import pandas as pd
import pytest

import spark_druid_olap_tpu as sd
from spark_druid_olap_tpu.workloads import tpch

SCALE = 0.004  # ~24k lineitem rows


@pytest.fixture(scope="module")
def ctx_tables():
    ctx = sd.TPUOlapContext()
    tables = tpch.register(ctx, scale=SCALE, rows_per_segment=8192)
    return ctx, tables


@pytest.fixture(scope="module")
def frame(ctx_tables):
    return tpch.flat_frame(ctx_tables[1])


def test_star_join_collapses(ctx_tables):
    """The rewrite collapses all dim joins onto the fact table (explain echoes
    the *logical* plan, which legitimately contains Join nodes — assert on the
    rewrite result, not the explain text)."""
    ctx, _ = ctx_tables
    rw = ctx.plan_sql(tpch.QUERIES["q5"])
    assert rw.datasource == "lineitem"
    assert rw.query.datasource == "lineitem"
    plan = ctx.explain(tpch.QUERIES["q5"])
    assert "Rewrite FAILED" not in plan, plan
    assert '"dataSource": "lineitem"' in plan, plan


def test_snowflake_customer_edge_collapses(ctx_tables):
    ctx, _ = ctx_tables
    rw = ctx.plan_sql(tpch.QUERIES["q3"])
    assert rw.datasource == "lineitem"
    plan = ctx.explain(tpch.QUERIES["q3"])
    assert "Rewrite FAILED" not in plan, plan


def test_q1_parity(ctx_tables, frame):
    ctx, _ = ctx_tables
    got = ctx.sql(tpch.QUERIES["q1"])
    want = tpch.oracle(frame, "q1")
    assert list(got["l_returnflag"]) == list(want["l_returnflag"])
    assert list(got["l_linestatus"]) == list(want["l_linestatus"])
    np.testing.assert_array_equal(got["count_order"], want["count_order"])
    for c in ("sum_qty", "sum_base_price", "sum_disc_price", "sum_charge"):
        np.testing.assert_allclose(got[c], want[c], rtol=2e-5)
    for c in ("avg_qty", "avg_price", "avg_disc"):
        np.testing.assert_allclose(got[c], want[c], rtol=2e-5)


def test_q3_parity_top10(ctx_tables, frame):
    ctx, _ = ctx_tables
    got = ctx.sql(tpch.QUERIES["q3"])
    want = tpch.oracle(frame, "q3")
    assert len(got) == len(want) == 10
    # revenue ordering may tie-break differently; compare the value sets
    np.testing.assert_allclose(
        np.sort(got["revenue"])[::-1], want["revenue"], rtol=2e-5
    )


def test_q5_parity(ctx_tables, frame):
    ctx, _ = ctx_tables
    got = ctx.sql(tpch.QUERIES["q5"]).sort_values("s_nation").reset_index(drop=True)
    want = tpch.oracle(frame, "q5").sort_values("s_nation").reset_index(drop=True)
    assert list(got["s_nation"]) == list(want["s_nation"])
    np.testing.assert_allclose(got["revenue"], want["revenue"], rtol=2e-5)


def test_q6_parity(ctx_tables, frame):
    ctx, _ = ctx_tables
    got = ctx.sql(tpch.QUERIES["q6"])
    want = tpch.oracle(frame, "q6")
    np.testing.assert_allclose(float(got["revenue"][0]), want, rtol=2e-5)


def test_q12_parity(ctx_tables, frame):
    ctx, _ = ctx_tables
    got = ctx.sql(tpch.QUERIES["q12"])
    want = tpch.oracle(frame, "q12")
    assert list(got["l_shipmode"]) == list(want["l_shipmode"])
    np.testing.assert_array_equal(got["high_line_count"], want["high_line_count"])
    np.testing.assert_array_equal(got["low_line_count"], want["low_line_count"])


def test_q8_parity(ctx_tables, frame):
    ctx, _ = ctx_tables
    got = ctx.sql(tpch.QUERIES["q8"])
    want = tpch.oracle(frame, "q8")
    assert len(got) == len(want)
    np.testing.assert_array_equal(
        np.asarray(got["o_orderdate_year"], dtype=np.int64),
        np.asarray(want["o_orderdate_year"], dtype=np.int64),
    )
    np.testing.assert_allclose(got["brazil_volume"], want["brazil_volume"], rtol=2e-5)
    np.testing.assert_allclose(got["total_volume"], want["total_volume"], rtol=2e-5)


def test_q8_extract_year_parity(ctx_tables, frame):
    """EXTRACT(YEAR FROM o_orderdate) in GROUP BY plans as a dictionary-
    backed dimension (VERDICT r1 missing #7) — no pre-materialized year
    column; results must match the q8 oracle exactly."""
    ctx, _ = ctx_tables
    got = ctx.sql(tpch.QUERIES["q8_extract"])
    want = tpch.oracle(frame, "q8")
    assert len(got) == len(want)
    np.testing.assert_array_equal(
        np.asarray(got["o_orderdate_year"], dtype=np.int64),
        np.asarray(want["o_orderdate_year"], dtype=np.int64),
    )
    np.testing.assert_allclose(got["brazil_volume"], want["brazil_volume"], rtol=2e-5)
    np.testing.assert_allclose(got["total_volume"], want["total_volume"], rtol=2e-5)


def test_q7_parity(ctx_tables, frame):
    """OR-of-ANDs across two dimension branches + EXTRACT over the fact's
    own time column as a grouping dimension."""
    ctx, _ = ctx_tables
    got = ctx.sql(tpch.QUERIES["q7"])
    want = tpch.oracle(frame, "q7")
    keys = ["s_nation", "c_nation", "l_year"]
    got = got.sort_values(keys).reset_index(drop=True)
    want = want.sort_values(keys).reset_index(drop=True)
    assert len(got) == len(want)
    for k in ("s_nation", "c_nation"):
        assert list(got[k]) == list(want[k])
    np.testing.assert_array_equal(
        np.asarray(got["l_year"], dtype=np.int64),
        np.asarray(want["l_year"], dtype=np.int64),
    )
    np.testing.assert_allclose(got["revenue"], want["revenue"], rtol=2e-5)


def test_q14_parity(ctx_tables, frame):
    """LIKE inside CASE + ratio of two aggregates as a post-aggregation."""
    ctx, _ = ctx_tables
    got = ctx.sql(tpch.QUERIES["q14"])
    want = tpch.oracle(frame, "q14")
    np.testing.assert_allclose(float(got["promo_revenue"][0]), want, rtol=2e-5)


def test_q19_parity(ctx_tables, frame):
    """Disjunction of conjunct blocks mixing string dims and metric bounds."""
    ctx, _ = ctx_tables
    got = ctx.sql(tpch.QUERIES["q19"])
    want = tpch.oracle(frame, "q19")
    np.testing.assert_allclose(float(got["revenue"][0]), want, rtol=2e-5)


def test_q3_uses_sparse_path(ctx_tables):
    """l_orderkey grouping has a huge domain — confirm the sparse
    accelerator actually answered it (not the scatter fallback)."""
    ctx, _ = ctx_tables
    eng = ctx.engine
    ctx.sql(tpch.QUERIES["q3"])
    assert not any(
        "lineitem" in k[0] and "l_orderkey" in k[0] for k in eng._sparse_disabled
    )


def test_q10_parity_fd_pruning(ctx_tables, frame):
    """Q10: GROUP BY c_custkey, c_name, c_nation — the declared functional
    dependencies (c_custkey -> c_name/c_nation) must prune the dependent
    columns from the kernel grouping (hidden code-max carriers), keeping the
    group domain at |custkey| instead of the cardinality product."""
    ctx, tables = ctx_tables
    rw = ctx.plan_sql(tpch.QUERIES["q10"])
    assert rw.fd_restores, "FD pruning did not engage"
    restored = {r[0] for r in rw.fd_restores}
    assert restored == {"c_name", "c_nation"}
    kernel_dims = {d.name for d in rw.query.dimensions} if hasattr(
        rw.query, "dimensions"
    ) else {rw.query.dimension.name}
    assert "c_name" not in kernel_dims and "c_nation" not in kernel_dims

    got = ctx.sql(tpch.QUERIES["q10"]).reset_index(drop=True)
    want = tpch.oracle(frame, "q10")
    assert list(got.columns)[:4] == ["c_custkey", "c_name", "c_nation", "revenue"]
    assert len(got) == len(want)
    np.testing.assert_allclose(
        got["revenue"].astype(float), want["revenue"], rtol=2e-5
    )
    # ties in revenue could reorder rows; compare as sets of customers
    assert set(got["c_custkey"].astype(int)) == set(
        want["c_custkey"].astype(int)
    )
    # restored attribute values are consistent with the source table
    cust = tables["customer"]
    for _, row in got.iterrows():
        k = int(row["c_custkey"])
        assert row["c_name"] == cust["c_name"][k]
        assert row["c_nation"] == cust["c_nation"][k]


def test_fd_pruning_respects_order_by_and_cube(ctx_tables, frame):
    """A column referenced by the device-side ORDER BY must not be pruned;
    grouping-set queries skip pruning entirely (set indices reference the
    full dimension list)."""
    ctx, _ = ctx_tables
    sql = (
        "SELECT c_custkey, c_name, sum(l_extendedprice) AS s "
        "FROM lineitem JOIN orders ON l_orderkey = o_orderkey "
        "JOIN customer ON o_custkey = c_custkey "
        "GROUP BY c_custkey, c_name ORDER BY c_name LIMIT 5"
    )
    rw = ctx.plan_sql(sql)
    pruned = {r[0] for r in rw.fd_restores}
    assert "c_name" not in pruned
    got = ctx.sql(sql)
    assert list(got["c_name"]) == sorted(got["c_name"])

    cube = (
        "SELECT c_custkey, c_name, count(*) AS n "
        "FROM lineitem JOIN orders ON l_orderkey = o_orderkey "
        "JOIN customer ON o_custkey = c_custkey "
        "GROUP BY CUBE (c_custkey, c_name)"
    )
    rw2 = ctx.plan_sql(cube)
    assert rw2.fd_restores == ()


def test_lineitem_is_time_sorted_so_q6_prunes_segments(ctx_tables):
    """`register` sorts lineitem by l_shipdate before segmenting: Q6's one
    year of ship dates scans fewer segments than the table has."""
    ctx, _ = ctx_tables
    ds = ctx.catalog.get("lineitem")
    ship = np.concatenate([np.asarray(s.time) for s in ds.segments])
    assert (np.diff(ship[np.asarray(np.concatenate(
        [np.asarray(s.valid) for s in ds.segments]), dtype=bool)]) >= 0).all()
    from spark_druid_olap_tpu.exec.engine import segments_in_scope

    scope = segments_in_scope(ctx.plan_sql(tpch.QUERIES["q6"]).query, ds)
    assert 0 < len(scope) < len(ds.segments)
