"""Distributed (8-device CPU mesh) execution parity vs the local engine.

The multi-chip contract (SURVEY.md §4 implication #3): sharded execution with
ICI-collective merge must produce the same results as single-device — exact
for counts/min/max/sketch states, tight rtol for float sums (different
reduction grouping)."""

import jax
import numpy as np
import pytest

from spark_druid_olap_tpu.exec.engine import Engine
from spark_druid_olap_tpu.models.aggregations import (
    Count,
    DoubleMax,
    DoubleMin,
    DoubleSum,
    ExpressionAgg,
    HyperUnique,
    ThetaSketch,
)
from spark_druid_olap_tpu.models.dimensions import DimensionSpec
from spark_druid_olap_tpu.models.filters import Bound, Selector
from spark_druid_olap_tpu.models.query import GroupByQuery, TopNQuery
from spark_druid_olap_tpu.parallel.distributed import DistributedEngine
from spark_druid_olap_tpu.parallel.mesh import make_mesh
from spark_druid_olap_tpu.plan.expr import col


@pytest.fixture(scope="module")
def dist8():
    assert len(jax.devices()) >= 8, "conftest must provide 8 CPU devices"
    return DistributedEngine(mesh=make_mesh(n_data=8))


@pytest.fixture(scope="module")
def dist4x2():
    return DistributedEngine(mesh=make_mesh(n_data=4, n_groups=2))


def _q1():
    return GroupByQuery(
        datasource="tpch",
        dimensions=(
            DimensionSpec("l_returnflag"),
            DimensionSpec("l_linestatus"),
        ),
        aggregations=(
            DoubleSum("sum_qty", "l_quantity"),
            ExpressionAgg(
                "sum_disc_price",
                col("l_extendedprice") * (1 - col("l_discount")),
            ),
            DoubleMin("min_p", "l_extendedprice"),
            DoubleMax("max_p", "l_extendedprice"),
            Count("n"),
        ),
        filter=Selector("l_linestatus", "F"),
    )


def _check_against_local(dist, q, ds):
    got = dist.execute(q, ds)
    want = Engine().execute(q, ds)
    key = [d.name for d in q.dimensions] if isinstance(q, GroupByQuery) else None
    if key:
        got = got.sort_values(key).reset_index(drop=True)
        want = want.sort_values(key).reset_index(drop=True)
    assert list(got.columns) == list(want.columns)
    for c in got.columns:
        if got[c].dtype.kind in ("f",):
            np.testing.assert_allclose(got[c], want[c], rtol=1e-5)
        else:
            np.testing.assert_array_equal(np.asarray(got[c]), np.asarray(want[c]))


def test_dp8_groupby_parity(dist8, lineitem_ds):
    _check_against_local(dist8, _q1(), lineitem_ds)


def test_dp4_tp2_groups_sharded_parity(dist4x2, lineitem_ds):
    _check_against_local(dist4x2, _q1(), lineitem_ds)


def test_dp8_sketches_parity(dist8, lineitem_ds):
    q = GroupByQuery(
        datasource="tpch",
        dimensions=(DimensionSpec("l_returnflag"),),
        aggregations=(
            HyperUnique("hll", "l_orderkey"),
            ThetaSketch("theta", "l_orderkey", size=1024),
            Count("n"),
        ),
    )
    _check_against_local(dist8, q, lineitem_ds)


def test_dp8_topn(dist8, ssb_ds):
    q = TopNQuery(
        datasource="ssb",
        dimension=DimensionSpec("c_city"),
        metric="rev",
        threshold=5,
        aggregations=(DoubleSum("rev", "lo_revenue"),),
        filter=Bound("d_year", lower="1993", upper="1995", ordering="numeric"),
    )
    got = DistributedEngine(mesh=make_mesh(n_data=8)).execute(q, ssb_ds)
    want = Engine().execute(q, ssb_ds)
    assert list(got.c_city) == list(want.c_city)
    np.testing.assert_allclose(got.rev, want.rev, rtol=1e-5)


def test_distributed_transient_retry(lineitem_ds):
    """A transient RuntimeError in the SPMD path evicts shards/programs and
    re-dispatches once (mirror of the local engine's retry)."""
    dist = DistributedEngine(mesh=make_mesh(n_data=8))
    # pin the legacy per-shard path: this test poisons its builder
    # (`_spmd_fn`); the arena path's retry is covered separately below
    dist.arena_execution = False
    q = _q1()
    # make the SPMD program fail exactly once via the builder
    calls = {"n": 0}
    orig = DistributedEngine._spmd_fn

    def flaky(self, lowering, local_rows, ds, col_keys, strategy="dense",
              key_extra=()):
        fn = orig(self, lowering, local_rows, ds, col_keys, strategy,
                  key_extra=key_extra)
        if calls["n"] == 0:
            def poisoned(cols):
                calls["n"] += 1
                raise RuntimeError("injected transient SPMD failure")

            return poisoned
        return fn

    dist._spmd_fn = flaky.__get__(dist)
    got = dist.execute(q, lineitem_ds)
    want = Engine().execute(q, lineitem_ds)
    assert calls["n"] == 1  # poisoned program ran exactly once
    key = [d.name for d in q.dimensions]
    got = got.sort_values(key).reset_index(drop=True)
    want = want.sort_values(key).reset_index(drop=True)
    np.testing.assert_array_equal(got["n"], want["n"])
    np.testing.assert_allclose(got["sum_qty"], want["sum_qty"], rtol=1e-5)


# -- kernel ladder on the mesh (VERDICT r4 #1) ------------------------------
#
# The distributed engine routes the same four-rung ladder as the local one:
# dense/Pallas one-hot, segment scatter, sparse sort-compaction (slots
# ladder included), and adaptive dictionary-domain compaction.  These pin
# every tier at G >= 500K on the 8-device CPU mesh, with group-domain
# sharding (groups axis) covered too.


def _high_g_ds(n=120_000, da=900, db=900, populated=2_000, seed=3, segs=4,
               name="hcm"):
    """Combined domain da*db = 810K (> 500K), few distinct pairs present —
    the SSB q3_x/q4_x shape that was modelled-only on the round-4 mesh."""
    from spark_druid_olap_tpu.catalog.segment import (
        DimensionDict,
        build_datasource,
    )

    rng = np.random.default_rng(seed)
    pairs = rng.choice(da * db, size=populated, replace=False)
    pick = rng.integers(0, populated, size=n)
    cols = {
        "a": (pairs[pick] // db).astype(np.int64),
        "b": (pairs[pick] % db).astype(np.int64),
        "v": (rng.random(n) * 100).astype(np.float32),
    }
    ds = build_datasource(
        name,
        cols,
        dimension_cols=["a", "b"],
        metric_cols=["v"],
        rows_per_segment=n // segs,
        dicts={
            "a": DimensionDict(values=tuple(range(da))),
            "b": DimensionDict(values=tuple(range(db))),
        },
    )
    return ds, cols


def _high_g_query(name="hcm", filter=None):
    from spark_druid_olap_tpu.models.aggregations import DoubleMax, DoubleMin

    return GroupByQuery(
        datasource=name,
        dimensions=(DimensionSpec("a"), DimensionSpec("b")),
        aggregations=(
            Count("n"),
            DoubleSum("s", "v"),
            DoubleMin("lo", "v"),
            DoubleMax("hi", "v"),
        ),
        filter=filter,
    )


@pytest.mark.parametrize("mesh_shape", [(8, 1), (4, 2)])
def test_mesh_sparse_high_cardinality_parity(mesh_shape):
    """Sparse sort-compaction SPMD at G=810K: per-device compaction,
    all_gather+merge fold over the data axis, group-domain sharding over
    the groups axis.  Parity vs the local engine."""
    ds, _ = _high_g_ds()
    q = _high_g_query()
    dist = DistributedEngine(
        mesh=make_mesh(n_data=mesh_shape[0], n_groups=mesh_shape[1]),
        strategy="sparse",
    )
    got = dist.execute(q, ds)
    assert dist.last_metrics.strategy == "sparse"
    want = Engine(strategy="sparse").execute(q, ds)
    key = ["a", "b"]
    got = got.sort_values(key).reset_index(drop=True)
    want = want.sort_values(key).reset_index(drop=True)
    assert len(got) == len(want) == 2_000
    np.testing.assert_array_equal(got["n"], want["n"])
    for c in ("s", "lo", "hi"):
        np.testing.assert_allclose(got[c], want[c], rtol=2e-5)


def test_mesh_sparse_slots_ladder_rungs_up():
    """More distinct present than SPARSE_SLOTS: the mesh engine reruns on
    the segmented-reduce rung (slots ladder) instead of failing, and the
    rung is remembered for repeats."""
    ds, cols = _high_g_ds(n=90_000, populated=6_000, name="hcm2")
    q = _high_g_query(name="hcm2")
    dist = DistributedEngine(mesh=make_mesh(n_data=8), strategy="sparse")
    got = dist.execute(q, ds)
    # the DS-level 6000 distinct overflowed the 4096-slot one-hot tier: a
    # segmented-reduce rung was remembered so repeats skip the base tier
    from spark_druid_olap_tpu.exec.lowering import (
        groupby_with_time_granularity,
        memo_key,
    )

    # learned rungs key segment-set-independently (the ingest tier's
    # memo contract, shared with the local engine)
    qkey = memo_key(groupby_with_time_granularity(q), ds)
    assert dist._sparse_slots.get(qkey, 0) > 4096
    import pandas as pd

    df = pd.DataFrame({k: np.asarray(v) for k, v in cols.items()})
    want = (
        df.groupby(["a", "b"], as_index=False)
        .agg(n=("v", "count"), s=("v", "sum"))
        .sort_values(["a", "b"])
        .reset_index(drop=True)
    )
    got = got.sort_values(["a", "b"]).reset_index(drop=True)
    assert len(got) == len(want) == 6_000
    np.testing.assert_array_equal(got["n"], want["n"])
    np.testing.assert_allclose(got["s"], want["s"], rtol=2e-5)
    # repeat goes straight through (remembered rung or base tier), parity
    got2 = dist.execute(q, ds)
    got2 = got2.sort_values(["a", "b"]).reset_index(drop=True)
    np.testing.assert_array_equal(got2["n"], want["n"])


def test_mesh_adaptive_compaction_parity():
    """Adaptive domain compaction as a distributed phase A/B: presence
    counts psum-merge over the data axis, kept-code LUTs broadcast, phase B
    runs the compact domain.  A selective filter keeps few codes."""
    ds, cols = _high_g_ds(name="hcm3")
    keep = list(range(0, 30))
    from spark_druid_olap_tpu.models.filters import InFilter

    q = _high_g_query(name="hcm3", filter=InFilter("a", tuple(keep)))
    dist = DistributedEngine(mesh=make_mesh(n_data=8), strategy="adaptive")
    got = dist.execute(q, ds)
    assert dist.last_metrics.strategy == "adaptive"
    # compacted domain engaged: far fewer groups than the full 810K
    assert dist.last_metrics.num_groups < 100_000
    mask = np.isin(cols["a"], keep)
    import pandas as pd

    df = pd.DataFrame({k: np.asarray(v) for k, v in cols.items()})[mask]
    want = (
        df.groupby(["a", "b"], as_index=False)
        .agg(n=("v", "count"), s=("v", "sum"))
        .sort_values(["a", "b"])
        .reset_index(drop=True)
    )
    got = got.sort_values(["a", "b"]).reset_index(drop=True)
    assert len(got) == len(want)
    np.testing.assert_array_equal(got["n"], want["n"])
    np.testing.assert_allclose(got["s"], want["s"], rtol=2e-5)
    # kept sets cached: repeat skips phase A (still correct)
    got2 = dist.execute(q, ds).sort_values(["a", "b"]).reset_index(drop=True)
    np.testing.assert_array_equal(got2["n"], want["n"])


def test_mesh_auto_routes_high_g_and_matches_local():
    """'auto' on the mesh routes by the same calibrated cost model as the
    local engine — a G=810K query EXECUTES (round 4: modelled-only) and
    matches the local result, whatever class the platform picks."""
    ds, _ = _high_g_ds(name="hcm4")
    q = _high_g_query(name="hcm4")
    dist = DistributedEngine(mesh=make_mesh(n_data=8))
    got = dist.execute(q, ds)
    assert dist.last_metrics.strategy in (
        "segment", "sparse", "adaptive", "dense", "pallas"
    )
    want = Engine().execute(q, ds)
    key = ["a", "b"]
    got = got.sort_values(key).reset_index(drop=True)
    want = want.sort_values(key).reset_index(drop=True)
    np.testing.assert_array_equal(got["n"], want["n"])
    np.testing.assert_allclose(got["s"], want["s"], rtol=2e-5)


def test_mesh_shard_residency_durable_across_queries():
    """VERDICT r4 #3: shard assembly is keyed on (datasource, column), not
    the query's pruned scope — a second, differently-filtered query over
    the same columns reuses the placed shards (h2d_ms ~ 0)."""
    ds, _ = _high_g_ds(name="hcm5")
    dist = DistributedEngine(mesh=make_mesh(n_data=8), strategy="segment")
    q1 = _high_g_query(name="hcm5")
    dist.execute(q1, ds)
    first_h2d = dist.last_metrics.h2d_bytes
    assert first_h2d > 0  # first touch pays assembly
    from spark_druid_olap_tpu.models.filters import Selector

    q2 = _high_g_query(name="hcm5", filter=Selector("a", 3))
    dist.execute(q2, ds)
    assert dist.last_metrics.h2d_bytes == 0  # durable residency: no re-place
    assert dist.last_metrics.h2d_ms == 0.0


def test_mesh_adaptive_interval_scoped_query():
    """Review r5 regression: phase A must fetch the PHYSICAL time column —
    an interval-scoped query used to KeyError out of the presence pass and
    silently decline adaptive (both engines)."""
    from spark_druid_olap_tpu.catalog.segment import (
        DimensionDict,
        build_datasource,
    )

    rng = np.random.default_rng(5)
    n, da, db = 60_000, 900, 900
    cols = {
        "a": rng.integers(0, 40, n),  # few present codes: compaction wins
        "b": rng.integers(0, 40, n),
        "t": np.sort(rng.integers(0, 1000, n)),
        "v": np.ones(n, np.float32),
    }
    ds = build_datasource(
        "hcm6", cols, dimension_cols=["a", "b"], metric_cols=["v"],
        time_col="t", rows_per_segment=30_000,
        dicts={
            "a": DimensionDict(values=tuple(range(da))),
            "b": DimensionDict(values=tuple(range(db))),
        },
    )
    q = GroupByQuery(
        datasource="hcm6",
        dimensions=(DimensionSpec("a"), DimensionSpec("b")),
        aggregations=(Count("n"), DoubleSum("s", "v")),
        intervals=((0, 500),),
    )
    dist = DistributedEngine(mesh=make_mesh(n_data=8), strategy="adaptive")
    got = dist.execute(q, ds)
    assert dist.last_metrics.strategy == "adaptive"  # no silent decline
    import pandas as pd

    df = pd.DataFrame({k: np.asarray(v) for k, v in cols.items()})
    df = df[df.t < 500]
    want = (
        df.groupby(["a", "b"], as_index=False)
        .agg(n=("v", "count"), s=("v", "sum"))
        .sort_values(["a", "b"]).reset_index(drop=True)
    )
    got = got.sort_values(["a", "b"]).reset_index(drop=True)
    np.testing.assert_array_equal(got["n"], want["n"])
    # the local engine too (same shared presence-column helper)
    eng = Engine(strategy="adaptive")
    lgot = eng.execute(q, ds).sort_values(["a", "b"]).reset_index(drop=True)
    assert eng.last_metrics.strategy == "adaptive"
    np.testing.assert_array_equal(lgot["n"], want["n"])


def test_mesh_adaptive_rekeys_sketches():
    """Adaptive phase B re-keys SKETCH states through the compacted domain
    (the compact program IS the normal SPMD program over a rewritten
    lowering) — HLL estimates must match the local engine's adaptive path
    exactly on both mesh shapes."""
    from spark_druid_olap_tpu.catalog.segment import (
        DimensionDict,
        build_datasource,
    )
    from spark_druid_olap_tpu.models.aggregations import HyperUnique
    from spark_druid_olap_tpu.models.filters import InFilter

    rng = np.random.default_rng(7)
    n, da, db = 100_000, 900, 900
    pairs = rng.choice(da * db, size=1500, replace=False)
    pick = pairs[rng.integers(0, 1500, n)]
    cols = {
        "a": (pick // db).astype(np.int64),
        "b": (pick % db).astype(np.int64),
        "k": rng.integers(0, 5000, n).astype(np.int64),
        "v": rng.random(n).astype(np.float32),
    }
    ds = build_datasource(
        "hcsk", cols, dimension_cols=["a", "b"], metric_cols=["v", "k"],
        rows_per_segment=25_000,
        dicts={
            "a": DimensionDict(values=tuple(range(da))),
            "b": DimensionDict(values=tuple(range(db))),
        },
    )
    q = GroupByQuery(
        datasource="hcsk",
        dimensions=(DimensionSpec("a"), DimensionSpec("b")),
        aggregations=(
            Count("n"),
            DoubleSum("s", "v"),
            HyperUnique("u", "k"),
        ),
        filter=InFilter("a", tuple(range(0, 40))),
    )
    want = Engine(strategy="adaptive").execute(q, ds)
    for shape in ((8, 1), (4, 2)):
        dist = DistributedEngine(
            mesh=make_mesh(n_data=shape[0], n_groups=shape[1]),
            strategy="adaptive",
        )
        got = dist.execute(q, ds)
        assert dist.last_metrics.strategy == "adaptive", shape
        key = ["a", "b"]
        g = got.sort_values(key).reset_index(drop=True)
        w = want.sort_values(key).reset_index(drop=True)
        np.testing.assert_array_equal(g["n"], w["n"])
        # HLL registers merge by max: estimates are deterministic integers
        np.testing.assert_array_equal(
            g["u"].astype(np.int64), w["u"].astype(np.int64)
        )
        np.testing.assert_allclose(g["s"], w["s"], rtol=2e-5)


def test_mesh_sparse_filtered_aggs_and_minmax():
    """Per-agg FILTER masks and min/max identities survive the sparse
    mesh path's compaction + cross-device merge fold."""
    from spark_druid_olap_tpu.catalog.segment import (
        DimensionDict,
        build_datasource,
    )
    from spark_druid_olap_tpu.models.aggregations import (
        DoubleMax,
        DoubleMin,
        FilteredAgg,
    )
    from spark_druid_olap_tpu.models.filters import Selector

    rng = np.random.default_rng(13)
    n, da, db = 80_000, 700, 700
    pairs = rng.choice(da * db, size=900, replace=False)
    pick = pairs[rng.integers(0, 900, n)]
    cols = {
        "a": (pick // db).astype(np.int64),
        "b": (pick % db).astype(np.int64),
        "flag": rng.integers(0, 3, n).astype(np.int64),
        "v": (rng.random(n) * 50).astype(np.float32),
    }
    ds = build_datasource(
        "hcfa", cols, dimension_cols=["a", "b", "flag"],
        metric_cols=["v"], rows_per_segment=20_000,
        dicts={
            "a": DimensionDict(values=tuple(range(da))),
            "b": DimensionDict(values=tuple(range(db))),
            "flag": DimensionDict(values=(0, 1, 2)),
        },
    )
    q = GroupByQuery(
        datasource="hcfa",
        dimensions=(DimensionSpec("a"), DimensionSpec("b")),
        aggregations=(
            Count("n"),
            FilteredAgg(Selector("flag", 1), DoubleSum("s1", "v")),
            DoubleMin("lo", "v"),
            DoubleMax("hi", "v"),
        ),
    )
    dist = DistributedEngine(mesh=make_mesh(n_data=8), strategy="sparse")
    got = dist.execute(q, ds)
    assert dist.last_metrics.strategy == "sparse"
    import pandas as pd

    df = pd.DataFrame({k: np.asarray(x) for k, x in cols.items()})
    df["v64"] = df.v.astype(np.float64)
    want = df.groupby(["a", "b"], as_index=False).agg(
        n=("v64", "count"), lo=("v64", "min"), hi=("v64", "max")
    )
    s1 = (
        df[df.flag == 1].groupby(["a", "b"])["v64"].sum()
        .reindex(list(zip(want.a, want.b)), fill_value=0.0)
        .to_numpy()
    )
    got = got.sort_values(["a", "b"]).reset_index(drop=True)
    want = want.sort_values(["a", "b"]).reset_index(drop=True)
    np.testing.assert_array_equal(got["n"], want["n"])
    np.testing.assert_allclose(got["lo"], want["lo"], rtol=1e-6)
    np.testing.assert_allclose(got["hi"], want["hi"], rtol=1e-6)
    np.testing.assert_allclose(
        np.nan_to_num(got["s1"].to_numpy(np.float64)), s1, rtol=2e-5,
        atol=1e-9,
    )


def test_mesh_shards_only_pruned_scope(dist8):
    """r5->r6 mesh regression guard, restated for the unified executor
    core: the regression was re-placing and re-scanning the FULL segment
    set per query.  The SPMD arena inverts the old fix — placement is
    scope-INDEPENDENT (one durable stacked layout keyed on the full
    segment signature, never a query's scope) and the pruned scope rides
    as DATA (membership + window), so a second, disjoint-scope query
    must place ZERO new shards and both scoped results must still match
    the local engine exactly."""
    from spark_druid_olap_tpu.catalog.segment import build_datasource
    from spark_druid_olap_tpu.exec.engine import segments_in_scope

    n = 16_384
    rng = np.random.default_rng(5)
    cols = {
        "d": np.array(
            [f"k{i}" for i in rng.integers(0, 4, size=n)], dtype=object
        ),
        # integer-valued f32 keeps the psum merge bit-exact
        "v": rng.integers(0, 1000, size=n).astype(np.float32),
        "t": (np.arange(n) * 1_000).astype(np.int64),
    }
    ds = build_datasource(
        "mesh_scope", cols, dimension_cols=["d"], metric_cols=["v"],
        time_col="t", rows_per_segment=2_048,
    )

    def scoped(lo, hi):
        return GroupByQuery(
            datasource="mesh_scope",
            dimensions=(DimensionSpec("d"),),
            aggregations=(Count("n"), DoubleSum("s", "v")),
            intervals=((lo, hi),),
        )

    q1 = scoped(0, 4_096_000)
    q2 = scoped(8_192_000, 12_288_000)  # disjoint from q1's segments
    s1 = {s.uid for s in segments_in_scope(q1, ds)}
    s2 = {s.uid for s in segments_in_scope(q2, ds)}
    assert 0 < len(s1) < len(ds.segments)
    assert s1.isdisjoint(s2) and s2

    dist8.clear_cache()
    got1 = dist8.execute(q1, ds)
    keys1 = {k for k in dist8._shard_cache if k[0] == "mesh_scope"}
    # the arena's keys carry the FULL segment signature, never a scope:
    # one "spmd_arena"-tagged stack per column (+ validity)
    assert keys1 and all(k[1] == "spmd_arena" for k in keys1)
    all_uids = tuple(s.uid for s in ds.segments)
    assert all(k[3] == all_uids for k in keys1)
    got2 = dist8.execute(q2, ds)
    keys2 = {k for k in dist8._shard_cache if k[0] == "mesh_scope"}
    # disjoint scope, zero new placements: scope is data, not placement
    assert keys2 == keys1
    for q, got in ((q1, got1), (q2, got2)):
        want = Engine().execute(q, ds)
        got = got.sort_values(["d"]).reset_index(drop=True)
        want = want.sort_values(["d"]).reset_index(drop=True)
        np.testing.assert_array_equal(
            np.asarray(got["n"]), np.asarray(want["n"])
        )
        np.testing.assert_array_equal(
            np.asarray(got["s"]), np.asarray(want["s"])
        )


# -- unified executor core (ISSUE 15) ---------------------------------------
#
# The mesh is a PLACEMENT STRATEGY over the segment-stacked arena: both
# backends lower the one fold program, so every serving feature must
# produce byte-identical answers on the virtual mesh.  Integer-valued
# float32 metrics keep the psum boundary merge bit-exact (sums of
# integers inside the f32 exact range), making assert_array_equal the
# right oracle — not allclose.


def _unified_ds(name="unified", n=32_768, rows_per_segment=2_048):
    from spark_druid_olap_tpu.catalog.segment import build_datasource

    rng = np.random.default_rng(0)
    cols = {
        "d": rng.integers(0, 7, n),
        "e": rng.integers(0, 5, n),
        "v": rng.integers(0, 100, n).astype(np.float32),
        "t": (np.arange(n) * 100).astype(np.int64),
    }
    return build_datasource(
        name, cols, dimension_cols=["d", "e"], metric_cols=["v"],
        time_col="t", rows_per_segment=rows_per_segment,
    )


def _unified_queries(name="unified"):
    q1 = GroupByQuery(
        datasource=name, dimensions=(DimensionSpec("d"),),
        aggregations=(
            Count("n"), DoubleSum("s", "v"),
            DoubleMin("lo", "v"), DoubleMax("hi", "v"),
        ),
    )
    q2 = GroupByQuery(
        datasource=name, dimensions=(DimensionSpec("e"),),
        aggregations=(Count("n"), DoubleSum("s", "v")),
    )
    q3 = TopNQuery(
        datasource=name, dimension=DimensionSpec("d"), metric="s",
        threshold=3, aggregations=(DoubleSum("s", "v"),),
    )
    return q1, q2, q3


def _frames_identical(got, want, key=None):
    if key:
        got = got.sort_values(key).reset_index(drop=True)
        want = want.sort_values(key).reset_index(drop=True)
    assert list(got.columns) == list(want.columns)
    for c in got.columns:
        np.testing.assert_array_equal(np.asarray(got[c]), np.asarray(want[c]))


def test_distributed_transient_retry_arena():
    """The arena path's mirror of the transient-retry contract: a
    poisoned SPMD-arena program fails once, the shared retry policy
    evicts this query's programs + shards, and the re-dispatch answers
    exactly."""
    ds = _unified_ds("retry_arena")
    q1, _, _ = _unified_queries("retry_arena")
    dist = DistributedEngine(mesh=make_mesh(n_data=8))
    calls = {"n": 0}
    orig = DistributedEngine._arena_spmd_fn

    def flaky(self, lowering, dsrc, layout, Lk, strategy, tree):
        fn = orig(self, lowering, dsrc, layout, Lk, strategy, tree)
        if calls["n"] == 0:
            def poisoned(cols, j_lo, memb):
                calls["n"] += 1
                raise RuntimeError("injected transient SPMD failure")

            return poisoned
        return fn

    dist._arena_spmd_fn = flaky.__get__(dist)
    got = dist.execute(q1, ds)
    assert calls["n"] == 1  # poisoned program ran exactly once
    _frames_identical(got, Engine().execute(q1, ds), key=["d"])


@pytest.fixture(scope="module")
def unified_ds():
    return _unified_ds()


@pytest.fixture(
    scope="module", params=["flat8", "slice2x4"],
    ids=["mesh8", "slice2x4"],
)
def unified_dist(request):
    from spark_druid_olap_tpu.parallel.mesh import make_slice_mesh

    if request.param == "flat8":
        return DistributedEngine(mesh=make_mesh(n_data=8))
    return DistributedEngine(mesh=make_slice_mesh(2, 4))


def test_unified_matrix_exact_and_fused(unified_dist, unified_ds):
    """Feature-parity matrix rows 1-2: plain execution and micro-batch
    fusion are byte-identical to the single-device engine, on both the
    flat mesh and the 2-slice topology (whose merge tree the cost model
    picks)."""
    eng = Engine()
    q1, q2, q3 = _unified_queries()
    _frames_identical(
        unified_dist.execute(q1, unified_ds), eng.execute(q1, unified_ds),
        key=["d"],
    )
    assert all(unified_dist.fusable(q, unified_ds) for q in (q1, q2, q3))
    got = unified_dist.execute_fused(
        [q1, q2, q3], unified_ds, query_ids=["a", "b", "c"]
    )
    want = eng.execute_fused(
        [q1, q2, q3], unified_ds, query_ids=["a", "b", "c"]
    )
    for (gdf, gst, gm), (wdf, wst, wm) in zip(got, want):
        assert gm.distributed and gm.fused_batch == 3
        for k in ("sums", "mins", "maxs"):
            np.testing.assert_array_equal(getattr(gst, k), getattr(wst, k))
        _frames_identical(
            gdf.reset_index(drop=True), wdf.reset_index(drop=True)
        )


def test_unified_matrix_result_cache_states(unified_dist, unified_ds):
    """Matrix row 3: the result cache's currency — captured state, delta
    partials, ⊕-merge, finalize — is byte-identical across backends, and
    delta scans of one query share ONE compiled program (scope is data,
    not a program key)."""
    eng = Engine()
    q1, _, _ = _unified_queries()
    with unified_dist.state_capture() as cap_d:
        unified_dist.execute(q1, unified_ds)
    with eng.state_capture() as cap_e:
        eng.execute(q1, unified_ds)
    assert cap_d["state"] is not None
    for k in ("sums", "mins", "maxs"):
        np.testing.assert_array_equal(
            getattr(cap_d["state"], k), getattr(cap_e["state"], k)
        )

    uids = [s.uid for s in unified_ds.segments]
    sa, ra = unified_dist.groupby_partials_host(
        q1, unified_ds, within_uids=uids[:5]
    )
    wa, wr = eng.groupby_partials_host(q1, unified_ds, within_uids=uids[:5])
    assert ra == wr
    for k in ("sums", "mins", "maxs"):
        np.testing.assert_array_equal(getattr(sa, k), getattr(wa, k))
    sb, _ = unified_dist.groupby_partials_host(
        q1, unified_ds, within_uids=uids[5:]
    )
    merged = unified_dist.merge_groupby_states(q1, unified_ds, sa, sb)
    full = unified_dist.finalize_groupby_state(q1, unified_ds, merged)
    _frames_identical(full, eng.execute(q1, unified_ds), key=["d"])
    # delta reuse: an equal-width window of the SAME query compiles no
    # new program — membership/window ride as data
    before = len(unified_dist._spmd_cache)
    unified_dist.groupby_partials_host(q1, unified_ds, within_uids=uids[2:7])
    assert len(unified_dist._spmd_cache) == before


def test_mesh_and_local_states_merge_to_the_whole_scope(
    unified_dist, unified_ds
):
    """A state the mesh made over one half of a scope and one the local
    engine made over the other ⊕ through the ONE `merge_groupby_states`
    both engines share, either way round, and finalize to the whole
    scope's answer."""
    eng = Engine()
    assert (
        type(unified_dist).merge_groupby_states
        is type(eng).merge_groupby_states
    )
    assert (
        type(unified_dist).finalize_groupby_state
        is type(eng).finalize_groupby_state
    )
    q1, _, _ = _unified_queries()
    uids = [s.uid for s in unified_ds.segments]
    mesh_half, _ = unified_dist.groupby_partials_host(
        q1, unified_ds, within_uids=uids[:4]
    )
    local_half, _ = eng.groupby_partials_host(
        q1, unified_ds, within_uids=uids[4:]
    )
    want = eng.execute(q1, unified_ds)
    for a, b in ((mesh_half, local_half), (local_half, mesh_half)):
        merged = eng.merge_groupby_states(q1, unified_ds, a, b)
        _frames_identical(
            unified_dist.finalize_groupby_state(q1, unified_ds, merged),
            want, key=["d"],
        )


def test_unified_matrix_deadline_partials(unified_dist, unified_ds):
    """Matrix row 4: deadline partials.  A roomy deadline answers in
    full (coverage 1.0) byte-identically; an expired-at-entry deadline
    degrades to the SAME best-effort empty answer the local engine
    gives (partial, coverage 0.0, zero rows) instead of raising."""
    from spark_druid_olap_tpu.resilience import deadline_scope, partial_scope

    eng = Engine()
    q1, _, _ = _unified_queries()
    with partial_scope(True) as pc, deadline_scope(60_000):
        got = unified_dist.execute(q1, unified_ds)
    assert not pc.is_partial and pc.coverage() == 1.0
    _frames_identical(got, eng.execute(q1, unified_ds), key=["d"])

    with partial_scope(True) as pc_d, deadline_scope(0.001):
        got_d = unified_dist.execute(q1, unified_ds)
    with partial_scope(True) as pc_e, deadline_scope(0.001):
        got_e = eng.execute(q1, unified_ds)
    assert pc_d.is_partial and pc_e.is_partial
    assert pc_d.coverage() == pc_e.coverage() == 0.0
    assert len(got_d) == len(got_e) == 0


def test_unified_matrix_prefetch_residency(unified_ds):
    """Matrix row 5: the PR 10 prefetch plan feeds the arena placement —
    a prefetched query pays ZERO foreground h2d bytes, and residency is
    durable across queries (no re-placement on re-execution)."""
    q1, _, _ = _unified_queries()
    dist = DistributedEngine(mesh=make_mesh(n_data=8))
    assert dist.prefetch(q1, unified_ds)
    dist.execute(q1, unified_ds)
    assert dist.last_metrics.h2d_bytes == 0
    dist.execute(q1, unified_ds)
    assert dist.last_metrics.h2d_bytes == 0
