"""Per-segment zone maps (SURVEY.md §2 metadata "stats"): filters that
provably cannot match a segment prune it before dispatch — and pruning must
never change results."""

import numpy as np
import pandas as pd
import pytest

import spark_druid_olap_tpu as sd


@pytest.fixture(scope="module")
def clustered():
    """Data CLUSTERED by key: segment i holds keys [i*25, (i+1)*25) — the
    layout where zone maps bite (time-sorted/partitioned ingest)."""
    n, segs = 40_000, 4
    keys = np.sort(np.random.default_rng(5).integers(0, 100, n))
    vals = np.random.default_rng(6).random(n).astype(np.float32) * 100
    cities = np.array([f"c{k:03d}" for k in keys], dtype=object)
    ctx = sd.TPUOlapContext()
    ctx.register_table(
        "cl",
        {"city": cities, "k": keys, "v": vals},
        dimensions=["city", "k"],
        metrics=["v"],
        rows_per_segment=n // segs,
    )
    df = pd.DataFrame(
        {"city": cities, "k": keys.astype(np.int64),
         "v": vals.astype(np.float64)}
    )
    return ctx, df


def test_selector_prunes_to_one_segment(clustered):
    ctx, df = clustered
    ds = ctx.catalog.get("cl")
    target = "c010"  # lives only in the first quarter of the keys
    eng = ctx.engine
    segs = eng._segments_in_scope(
        ctx.plan_sql(
            f"SELECT count(*) AS n FROM cl WHERE city = '{target}'"
        ).query,
        ds,
    )
    assert len(segs) < len(ds.segments)
    got = ctx.sql(f"SELECT count(*) AS n FROM cl WHERE city = '{target}'")
    assert int(got["n"].iloc[0]) == int((df.city == target).sum())


def test_absent_value_prunes_everything(clustered):
    ctx, df = clustered
    got = ctx.sql("SELECT count(*) AS n FROM cl WHERE city = 'nope'")
    assert int(got["n"].iloc[0]) == 0
    got2 = ctx.sql(
        "SELECT count(*) AS n FROM cl WHERE city IN ('nope', 'nada')"
    )
    assert int(got2["n"].iloc[0]) == 0


def test_numeric_bound_prunes_and_stays_exact(clustered):
    ctx, df = clustered
    # v is uniform across segments -> no pruning from v; k is clustered
    for sql, mask in [
        ("SELECT sum(v) AS s, count(*) AS n FROM cl WHERE v > 150",
         df.v > 150),  # beyond global max: zero rows
        ("SELECT sum(v) AS s, count(*) AS n FROM cl WHERE v <= 50",
         df.v <= 50),
    ]:
        got = ctx.sql(sql)
        want_n = int(mask.sum())
        assert int(got["n"].iloc[0]) == want_n
        if want_n:
            np.testing.assert_allclose(
                float(got["s"].iloc[0]), df.v[mask].sum(), rtol=2e-5
            )


def test_in_filter_parity_under_pruning(clustered):
    ctx, df = clustered
    vals = ["c005", "c050", "c095"]  # spans three different segments
    frag = ", ".join(f"'{v}'" for v in vals)
    got = ctx.sql(
        f"SELECT city, count(*) AS n FROM cl WHERE city IN ({frag}) "
        "GROUP BY city ORDER BY city"
    )
    want = (
        df[df.city.isin(vals)]
        .groupby("city", as_index=False)
        .size()
        .rename(columns={"size": "n"})
        .sort_values("city")
    )
    assert list(got["city"]) == list(want["city"])
    np.testing.assert_array_equal(got["n"].values, want["n"].values)


def test_stats_survive_persistence(tmp_path, clustered):
    ctx, df = clustered
    from spark_druid_olap_tpu.catalog.persist import (
        load_datasource,
        save_datasource,
    )

    d = save_datasource(ctx.catalog.get("cl"), str(tmp_path / "cl"))
    ds2, _ = load_datasource(d)
    assert all(s.stats for s in ds2.segments)
    s0 = ds2.segments[0]
    assert s0.stats["k"][0] == 0.0  # first segment holds the smallest keys


def test_sort_by_ingest_enables_pruning():
    """register_table(sort_by=...): unsorted input gets clustered at ingest
    so zone maps prune — and results are identical to the unsorted table."""
    rng = np.random.default_rng(11)
    n = 20_000
    key = rng.integers(0, 100, n)  # UNSORTED
    val = rng.random(n).astype(np.float32)
    plain = sd.TPUOlapContext()
    plain.register_table(
        "u", {"k": key, "v": val}, dimensions=["k"], metrics=["v"],
        rows_per_segment=n // 4,
    )
    sorted_ctx = sd.TPUOlapContext()
    sorted_ctx.register_table(
        "u", {"k": key, "v": val}, dimensions=["k"], metrics=["v"],
        rows_per_segment=n // 4, sort_by=["k"],
    )
    sql = "SELECT count(*) AS n, sum(v) AS s FROM u WHERE k = 7"
    a = plain.sql(sql)
    b = sorted_ctx.sql(sql)
    assert int(a["n"].iloc[0]) == int(b["n"].iloc[0])
    np.testing.assert_allclose(
        float(a["s"].iloc[0]), float(b["s"].iloc[0]), rtol=2e-5
    )
    # the sorted table's scope collapses to a single segment
    ds = sorted_ctx.catalog.get("u")
    rw = sorted_ctx.plan_sql(sql)
    assert len(sorted_ctx.engine._segments_in_scope(rw.query, ds)) == 1
    assert len(
        plain.engine._segments_in_scope(
            plain.plan_sql(sql).query, plain.catalog.get("u")
        )
    ) == 4


def test_sort_by_unknown_column_rejected():
    ctx = sd.TPUOlapContext()
    with pytest.raises(ValueError, match="unknown columns"):
        ctx.register_table(
            "x", {"a": np.arange(10)}, dimensions=["a"], sort_by=["nope"]
        )


def test_distributed_zone_map_pruning():
    """The SPMD mesh path prunes segments by zone maps too — and stays
    exact."""
    import jax

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device CPU mesh")
    from spark_druid_olap_tpu.parallel.distributed import DistributedEngine
    from spark_druid_olap_tpu.parallel.mesh import make_mesh

    n, segs = 32_000, 4
    keys = np.sort(np.random.default_rng(15).integers(0, 100, n))
    vals = np.random.default_rng(16).random(n).astype(np.float32)
    ctx = sd.TPUOlapContext()
    ctx.register_table(
        "dcl", {"k": keys, "v": vals},
        dimensions=["k"], metrics=["v"], rows_per_segment=n // segs,
    )
    ds = ctx.catalog.get("dcl")
    rw = ctx.plan_sql("SELECT count(*) AS n, sum(v) AS s FROM dcl WHERE k = 7")
    eng = DistributedEngine(mesh=make_mesh(n_data=8))
    got = eng.execute(rw.query, ds)
    df = pd.DataFrame({"k": keys, "v": vals.astype(np.float64)})
    want_n = int((df.k == 7).sum())
    assert int(got["n"].iloc[0]) == want_n
    np.testing.assert_allclose(
        float(got["s"].iloc[0]), df.v[df.k == 7].sum(), rtol=2e-5
    )
    # pruning actually engaged: post-prune metrics cover ONE segment, and
    # the shard cache holds only that segment's rows
    assert eng.last_metrics.segments == 1
    assert eng.last_metrics.rows_scanned == ds.segments[0].num_rows
    assert eng.last_metrics.rows_scanned < ds.num_rows


@pytest.fixture(scope="module")
def metric_clustered():
    """A table whose METRIC m is clustered across segments (m rises with
    row order), so numeric-bound zone maps prune — the canvas for the
    virtual-column shadowing cases (metric shadowing is value-space and
    therefore supported end to end)."""
    n, segs = 40_000, 4
    m = np.sort(
        np.random.default_rng(9).integers(0, 100, n)
    ).astype(np.float32)
    cities = np.array([f"g{int(x) // 20}" for x in m], dtype=object)
    v = np.random.default_rng(10).random(n).astype(np.float32)
    ctx = sd.TPUOlapContext()
    ctx.register_table(
        "mcl",
        {"city": cities, "m": m, "v": v},
        dimensions=["city"],
        metrics=["m", "v"],
        rows_per_segment=n // segs,
    )
    df = pd.DataFrame(
        {"city": cities, "m": m.astype(np.float64),
         "v": v.astype(np.float64)}
    )
    return ctx, df


def _shadow_query():
    from spark_druid_olap_tpu.models.aggregations import DoubleSum
    from spark_druid_olap_tpu.models.dimensions import DimensionSpec
    from spark_druid_olap_tpu.models.filters import Bound
    from spark_druid_olap_tpu.models.query import GroupByQuery, VirtualColumn
    from spark_druid_olap_tpu.plan.expr import Literal, col

    # "m" is redefined as 100 - m: the filter m < 10 selects the HIGH
    # physical values, which live in the LAST segment
    return GroupByQuery(
        datasource="mcl",
        dimensions=(DimensionSpec("city"),),
        aggregations=(DoubleSum("s", "v"),),
        virtual_columns=(
            VirtualColumn("m", Literal(100.0) - col("m")),
        ),
        filter=Bound("m", upper="10", ordering="numeric"),
    )


def test_virtual_column_shadow_disables_pruning(metric_clustered):
    """A virtual column SHADOWING a physical metric: the filter evaluates
    against the virtual values at execution, so pruning it against the
    physical column's zone map would silently drop live segments
    (round-2 advisor finding) — and the whole query must run correctly."""
    import dataclasses

    from spark_druid_olap_tpu.models.filters import Bound

    ctx, df = metric_clustered
    ds = ctx.catalog.get("mcl")
    eng = ctx.engine
    q = _shadow_query()
    segs = eng._segments_in_scope(q, ds)
    assert len(segs) == len(ds.segments)  # no pruning on shadowed name
    # the same bound WITHOUT the virtual column does prune
    q2 = dataclasses.replace(q, virtual_columns=())
    assert len(eng._segments_in_scope(q2, ds)) < len(ds.segments)
    # end-to-end: correct rows (virtual m < 10 means physical m > 90)
    got = eng.execute(q, ds)
    w = df[100.0 - df.m <= 10].groupby("city")["v"].sum()
    got_by = {r["city"]: float(r["s"]) for _, r in got.iterrows()}
    assert set(got_by) == set(w.index)
    for city, s in w.items():
        np.testing.assert_allclose(got_by[city], s, rtol=2e-5)


def test_vcol_shadowing_dict_dimension_rejected(clustered):
    """Shadowing a DICTIONARY-ENCODED dimension cannot be honored soundly
    (filters/groupings compile into code space) — clear refusal, not a
    wrong answer."""
    from spark_druid_olap_tpu.models.aggregations import DoubleSum
    from spark_druid_olap_tpu.models.dimensions import DimensionSpec
    from spark_druid_olap_tpu.models.filters import Bound
    from spark_druid_olap_tpu.models.query import GroupByQuery, VirtualColumn
    from spark_druid_olap_tpu.plan.expr import Literal, col

    ctx, _ = clustered
    ds = ctx.catalog.get("cl")
    q = GroupByQuery(
        datasource="cl",
        dimensions=(DimensionSpec("city"),),
        aggregations=(DoubleSum("s", "v"),),
        virtual_columns=(
            VirtualColumn("k", Literal(100) - col("k"), dtype="long"),
        ),
        filter=Bound("k", upper="10", ordering="numeric"),
    )
    with pytest.raises(ValueError, match="shadow"):
        ctx.engine.execute(q, ds)


def test_sort_by_encoded_dims_nulls_last():
    """sort_by over PRE-ENCODED dimension codes (caller-supplied dicts):
    null codes are negative and must still cluster LAST (round-2 advisor
    finding — raw code order put them first)."""
    from spark_druid_olap_tpu.catalog.segment import DimensionDict

    c = sd.TPUOlapContext()
    codes = np.array([1, -1, 0, 1, -1], dtype=np.int32)
    c.register_table(
        "enc",
        {"c": codes, "v": np.arange(5, dtype=np.float32)},
        dimensions=["c"],
        metrics=["v"],
        dicts={"c": DimensionDict(values=("a", "b"))},
        sort_by=["c"],
        rows_per_segment=2,
    )
    ds = c.catalog.get("enc")
    phys = np.concatenate(
        [np.asarray(s.dims["c"])[s.valid] for s in ds.segments]
    )
    nulls = phys < 0
    assert not nulls[:3].any() and nulls[3:].all()
    assert list(phys[:3]) == sorted(phys[:3])


def test_distributed_vcol_shadow_disables_pruning(metric_clustered):
    """Review finding: the mesh path must apply the same virtual-column
    shadow rule as the local engine — a shadowed filter name must not
    prune against physical stats."""
    import jax

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device CPU mesh")
    from spark_druid_olap_tpu.parallel.distributed import DistributedEngine
    from spark_druid_olap_tpu.parallel.mesh import make_mesh

    ctx, df = metric_clustered
    ds = ctx.catalog.get("mcl")
    q = _shadow_query()
    eng = DistributedEngine(mesh=make_mesh(n_data=8))
    got = eng.execute(q, ds)
    # filter selects virtual m < 10 i.e. physical m > 90 — the LAST
    # segment's rows.  With wrong pruning those segments vanish -> empty.
    w = df[100.0 - df.m <= 10].groupby("city")["v"].sum()
    assert eng.last_metrics.segments == len(ds.segments)  # nothing pruned
    got_by = {r["city"]: float(r["s"]) for _, r in got.iterrows()}
    assert set(got_by) == set(w.index)
    for city, s in w.items():
        np.testing.assert_allclose(got_by[city], s, rtol=2e-5)


def test_nested_and_or_conjuncts_prune(clustered):
    """The planner builds Ands PAIRWISE and year-style disjunctions as
    Or(Bound, Bound): both shapes must still prune (round-3 fix — the SSB
    q1/q4 latency class depends on it)."""
    ctx, df = clustered
    ds = ctx.catalog.get("cl")
    eng = ctx.engine
    # nested And: (k = 7 AND v > 0) AND v < 1000 — k=7 lives in segment 0
    rw = ctx.plan_sql(
        "SELECT count(*) AS n FROM cl WHERE k = 7 AND v > 0 AND v < 1000"
    )
    assert len(eng._segments_in_scope(rw.query, ds)) == 1
    # Or of bounds on the clustered key: only the segments holding 7 or 80
    rw2 = ctx.plan_sql(
        "SELECT count(*) AS n FROM cl WHERE k = 7 OR k = 80"
    )
    segs2 = eng._segments_in_scope(rw2.query, ds)
    assert len(segs2) == 2
    got = ctx.sql("SELECT count(*) AS n FROM cl WHERE k = 7 OR k = 80")
    assert int(got["n"].iloc[0]) == int(((df.k == 7) | (df.k == 80)).sum())


# ---------------------------------------------------------------------------
# The walk translates each literal once (ISSUE 38): parity with the plain
# per-segment pruner it replaced, kept here literal for literal
# ---------------------------------------------------------------------------


def _reference_scope(q, ds):
    """`segments_in_scope` as it stood before ISSUE 38: every conjunct's
    literals translated anew for every segment."""
    from spark_druid_olap_tpu.models import filters as F
    from spark_druid_olap_tpu.ops.filters import numeric_dict_code_bounds

    vcol_names = frozenset(
        v.name for v in getattr(q, "virtual_columns", ()) or ()
    )

    def conjuncts(f):
        if isinstance(f, F.And):
            return [y for x in f.fields for y in conjuncts(x)]
        return [f]

    def excluded(seg, c) -> bool:
        if getattr(c, "dimension", None) in vcol_names:
            return False
        if isinstance(c, F.Or):
            return bool(c.fields) and all(excluded(seg, x) for x in c.fields)
        if isinstance(c, F.And):
            return any(excluded(seg, x) for x in c.fields)
        st = seg.stats or {}
        if isinstance(c, F.Selector):
            if c.value is None or c.dimension not in ds.dicts:
                return False
            code = ds.dicts[c.dimension].code_of(c.value)
            if code is None:
                return True
            b = st.get(c.dimension)
            return b is not None and not (b[0] <= code <= b[1])
        if isinstance(c, F.InFilter):
            if c.dimension not in ds.dicts:
                return False
            if any(v is None for v in c.values):
                return False
            codes = [
                x
                for x in (ds.dicts[c.dimension].code_of(v) for v in c.values)
                if x is not None
            ]
            if not codes:
                return True
            b = st.get(c.dimension)
            return b is not None and not any(
                b[0] <= x <= b[1] for x in codes
            )
        if isinstance(c, F.Bound) and c.ordering == "numeric":
            b = st.get(c.dimension)
            if b is None:
                return False
            if c.dimension in ds.dicts:
                nv = ds.dicts[c.dimension].numeric_values
                if nv is None:
                    return False
                cb = numeric_dict_code_bounds(c, np.asarray(nv))
                if cb is None:
                    return False
                lo_code, hi_code = cb
                if lo_code is not None and b[1] < lo_code:
                    return True
                if hi_code is not None and b[0] > hi_code:
                    return True
                return False
            try:
                if c.lower is not None:
                    lo = float(c.lower)
                    if b[1] < lo or (c.lower_strict and b[1] <= lo):
                        return True
                if c.upper is not None:
                    hi = float(c.upper)
                    if b[0] > hi or (c.upper_strict and b[0] >= hi):
                        return True
            except ValueError:
                return False
            return False
        return False

    segs = list(ds.segments)
    if q.intervals:
        segs = [
            s for s in segs
            if s.interval is None
            or any(a <= s.interval[1] and s.interval[0] < b
                   for a, b in q.intervals)
        ]
    filt = getattr(q, "filter", None)
    if filt is not None and segs:
        cs = conjuncts(filt)
        segs = [s for s in segs if not any(excluded(s, c) for c in cs)]
    return segs


@pytest.fixture(scope="module")
def shapes_ds():
    """Eight time-sorted segments whose string dimension, numeric
    dimension and bare metric all rise with row order, so every zone map
    prunes; the third segment has lost its `stats`, the sixth its
    `interval`."""
    import dataclasses

    n, segs = 16_000, 8
    k = np.sort(np.random.default_rng(38).integers(0, 200, n)) * 5
    ctx = sd.TPUOlapContext()
    ctx.register_table(
        "shp",
        {
            "city": np.array([f"c{x:04d}" for x in k], dtype=object),
            "k": k,
            "m": k.astype(np.float32) / 2,
            "v": np.random.default_rng(39).random(n).astype(np.float32),
            "t": (np.arange(n) * 1_000).astype(np.int64),
        },
        dimensions=["city", "k"],
        metrics=["m", "v"],
        time_column="t",
        rows_per_segment=n // segs,
    )
    ds = ctx.catalog.get("shp")
    held = list(ds.segments)
    assert len(held) == segs and all(s.stats and s.interval for s in held)
    held[2] = dataclasses.replace(held[2], stats=None)
    held[5] = dataclasses.replace(held[5], interval=None)
    return dataclasses.replace(ds, segments=tuple(held))


def _filter_shapes():
    from spark_druid_olap_tpu.models.filters import (
        And, Bound, InFilter, Not, Or, Selector,
    )

    def num(dim, **kw):
        return Bound(dim, ordering="numeric", **kw)

    present, absent = "c0100", "c0101"  # k is a multiple of 5
    return {
        "selector-present": Selector("city", present),
        "selector-absent": Selector("city", absent),
        "selector-none": Selector("city", None),
        "selector-numeric-dict": Selector("k", "500"),
        "selector-numeric-dict-absent": Selector("k", "501"),
        "selector-numeric-dict-unparsable": Selector("k", "five"),
        "selector-unknown-dimension": Selector("nope", "x"),
        "selector-on-metric": Selector("m", "3"),
        "in-all-present": InFilter("city", ("c0005", "c0900", "c0500")),
        "in-some-absent": InFilter("city", (absent, "c0900", "zzz")),
        "in-all-absent": InFilter("city", (absent, "zzz")),
        "in-with-none": InFilter("city", ("c0005", None)),
        "in-numeric-dict": InFilter("k", ("995", "0", "7")),
        "in-unknown-dimension": InFilter("nope", ("a",)),
        "bound-dict-closed": num("k", lower="100", upper="300"),
        "bound-dict-strict": num(
            "k", lower="100", upper="300", lower_strict=True,
            upper_strict=True,
        ),
        # 120 and 875 are a segment's first and another's last value: the
        # strict end excludes that segment, the closed end keeps it
        "bound-dict-lower-only": num("k", lower="875"),
        "bound-dict-lower-only-strict": num(
            "k", lower="875", lower_strict=True
        ),
        "bound-dict-upper-only": num("k", upper="120"),
        "bound-dict-upper-only-strict": num(
            "k", upper="120", upper_strict=True
        ),
        "bound-dict-between-values": num("k", lower="101", upper="104"),
        "bound-dict-unparsable": num("k", lower="abc"),
        "bound-dict-date-literal": num("k", upper="1970-01-01"),
        "bound-string-dict-numeric-ordering": num("city", lower="3"),
        "bound-lexicographic": Bound("city", lower="c0100", upper="c0200"),
        "bound-metric-closed": num("m", lower="50", upper="150"),
        "bound-metric-strict": num(
            "m", lower="50", upper="150", lower_strict=True,
            upper_strict=True,
        ),
        "bound-metric-lower-only": num("m", lower="437.5"),
        "bound-metric-lower-only-strict": num(
            "m", lower="437.5", lower_strict=True
        ),
        "bound-metric-upper-only": num("m", upper="60"),
        "bound-metric-upper-only-strict": num(
            "m", upper="60", upper_strict=True
        ),
        "bound-metric-unparsable-lower": num("m", lower="x", upper="10"),
        "bound-metric-unparsable-upper": num("m", lower="400", upper="x"),
        "bound-unknown-column": num("nope", lower="1"),
        "and-nested": And((
            And((Selector("city", present), num("m", lower="10"))),
            num("k", upper="900"),
        )),
        "and-with-absent": And((
            num("m", lower="10"), Selector("city", absent),
        )),
        "or-of-bounds": Or((
            num("k", upper="50"), num("k", lower="900"),
        )),
        "or-with-absent": Or((
            Selector("city", absent), Selector("city", "c0900"),
        )),
        "or-all-absent": Or((
            Selector("city", absent), InFilter("city", ("zzz",)),
        )),
        "or-with-unprunable": Or((
            Selector("city", present), Not(Selector("city", present)),
        )),
        "or-empty": Or(()),
        "or-of-ands": Or((
            And((num("k", upper="50"), num("m", upper="10"))),
            And((Selector("city", "c0900"), Selector("city", absent))),
        )),
        "and-of-ors": And((
            Or((Selector("k", "0"), Selector("k", "995"))),
            Or((num("m", upper="100"), Selector("city", None))),
        )),
        "not": Not(Selector("city", present)),
    }


def _scope_query(filt=None, intervals=(), virtual_columns=()):
    from spark_druid_olap_tpu.models.aggregations import DoubleSum
    from spark_druid_olap_tpu.models.query import GroupByQuery

    return GroupByQuery(
        datasource="shp", dimensions=(),
        aggregations=(DoubleSum("s", "v"),), filter=filt,
        intervals=intervals, virtual_columns=virtual_columns,
    )


_SHAPES = _filter_shapes()
# what a shape must do besides agree with the reference: how many of the
# eight segments it keeps, where that is what the shape is for
_KEPT = {
    "selector-absent": 0, "selector-none": 8, "in-all-absent": 0,
    "in-with-none": 8, "selector-unknown-dimension": 8,
    "bound-dict-unparsable": 8, "bound-lexicographic": 8,
    "bound-metric-unparsable-lower": 8, "and-with-absent": 0,
    "or-all-absent": 0, "or-with-unprunable": 8, "or-empty": 8, "not": 8,
    "bound-dict-lower-only": 3, "bound-dict-lower-only-strict": 2,
    "bound-dict-upper-only": 3, "bound-dict-upper-only-strict": 2,
    "bound-metric-lower-only": 3, "bound-metric-lower-only-strict": 2,
    "bound-metric-upper-only": 3, "bound-metric-upper-only-strict": 2,
}


@pytest.mark.parametrize("shape", sorted(_SHAPES))
def test_translated_walk_keeps_what_the_plain_walk_kept(shapes_ds, shape):
    """Same kept uids, in the same order, for every filter shape the
    pruner knows, alone and under intervals; a segment without `stats`
    is never pruned by a zone map, one without `interval` never by
    time."""
    from spark_druid_olap_tpu.exec.engine import segments_in_scope

    ds = shapes_ds
    for intervals in ((), ((2_000_000, 9_000_000),),
                      ((0, 1_000), (15_000_000, 15_000_001))):
        q = _scope_query(_SHAPES[shape], intervals)
        got = [s.uid for s in segments_in_scope(q, ds)]
        assert got == [s.uid for s in _reference_scope(q, ds)]
        if got:
            assert ds.segments[2].uid in got or intervals
        if not intervals and shape in _KEPT:
            assert len(got) == _KEPT[shape]


@pytest.mark.parametrize("case", [
    "intervals-alone", "no-filter-no-intervals", "shadowed-metric",
    "shadowed-inside-or",
])
def test_translated_walk_parity_beside_the_filter(shapes_ds, case):
    """`intervals` alone, nothing at all, and a virtual column shadowing
    the filtered name (at the top level and inside an `Or`): the shadow
    switches that conjunct's pruning off, and only that conjunct's."""
    from spark_druid_olap_tpu.exec.engine import segments_in_scope
    from spark_druid_olap_tpu.models.filters import And, Bound, Or
    from spark_druid_olap_tpu.models.query import VirtualColumn
    from spark_druid_olap_tpu.plan.expr import Literal, col

    ds = shapes_ds
    shadow = (VirtualColumn("m", Literal(500.0) - col("m")),)
    low_m = Bound("m", upper="10", ordering="numeric")
    low_k = Bound("k", upper="300", ordering="numeric")
    q = {
        "intervals-alone": _scope_query(None, ((3_000_000, 5_000_000),)),
        "no-filter-no-intervals": _scope_query(),
        "shadowed-metric": _scope_query(
            And((low_m, low_k)), virtual_columns=shadow
        ),
        "shadowed-inside-or": _scope_query(
            Or((low_m, low_k)), virtual_columns=shadow
        ),
    }[case]
    got = [s.uid for s in segments_in_scope(q, ds)]
    assert got == [s.uid for s in _reference_scope(q, ds)]
    if case == "intervals-alone":
        # the segment that lost its interval is always in scope
        assert ds.segments[5].uid in got and 1 < len(got) < 8
    elif case == "no-filter-no-intervals":
        assert got == [s.uid for s in ds.segments]
    elif case == "shadowed-metric":
        # low_k still prunes; low_m alone would have kept fewer
        plain = _scope_query(And((low_m, low_k)))
        assert len(segments_in_scope(plain, ds)) < len(got) < 8
    else:
        assert len(got) == 8  # an unprunable disjunct keeps everything


@pytest.fixture(scope="module")
def ssb_115():
    """SSB's flat lineorder at the cells' 115 segments (scale 0.01,
    522 rows a segment)."""
    from spark_druid_olap_tpu.workloads import ssb

    ctx = sd.TPUOlapContext()
    ssb.register(
        ctx, tables=ssb.gen_tables(scale=0.01, seed=7), rows_per_segment=522
    )
    return ctx, ssb.QUERIES


@pytest.mark.parametrize("name", [
    "q1_1", "q1_2", "q1_3", "q2_1", "q2_2", "q2_3", "q3_1", "q3_2", "q3_3",
    "q3_4", "q4_1", "q4_2", "q4_3",
])
def test_translated_walk_parity_on_the_ssb_filters(ssb_115, name):
    from spark_druid_olap_tpu.exec.engine import segments_in_scope

    ctx, queries = ssb_115
    rw = ctx.plan_sql(queries[name])
    ds = ctx.catalog.get(rw.datasource)
    assert len(ds.segments) == 115
    got = [s.uid for s in segments_in_scope(rw.query, ds)]
    assert got == [s.uid for s in _reference_scope(rw.query, ds)]
    assert name not in ("q1_1", "q1_2", "q1_3") or 0 < len(got) < 40
