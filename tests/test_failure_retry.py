"""Failure detection / idempotent re-dispatch (SURVEY.md §5 failure row).

The reference delegates retry to Spark task re-execution of a DruidRDD
partition — read-only queries make retry unconditionally safe.  The engine
mirrors that: a RuntimeError out of the device path evicts the query's
cached programs + resident columns and re-dispatches exactly once; static
planning errors propagate immediately."""

import numpy as np
import pytest

from spark_druid_olap_tpu.catalog.segment import build_datasource
from spark_druid_olap_tpu.exec.engine import Engine, _query_key
from spark_druid_olap_tpu.exec.lowering import groupby_with_time_granularity
from spark_druid_olap_tpu.models.aggregations import Count, DoubleSum
from spark_druid_olap_tpu.models.dimensions import DimensionSpec
from spark_druid_olap_tpu.models.query import GroupByQuery
from spark_druid_olap_tpu.plan.cost import concrete_kernel


@pytest.fixture(scope="module")
def ds():
    n = 10_000
    rng = np.random.default_rng(9)
    return build_datasource(
        "r",
        {
            "d": rng.integers(0, 8, n).astype(np.int64),
            "v": rng.random(n).astype(np.float32),
        },
        dimension_cols=["d"],
        metric_cols=["v"],
    )


def _q():
    return GroupByQuery(
        datasource="r",
        dimensions=(DimensionSpec("d"),),
        aggregations=(DoubleSum("s", "v"), Count("n")),
    )


def _oracle(ds):
    import pandas as pd

    seg = ds.segments[0]
    d = ds.dicts["d"].decode(np.asarray(seg.dims["d"])[seg.valid])
    v = np.asarray(seg.metrics["v"], np.float64)[seg.valid]
    return (
        pd.DataFrame({"d": d, "v": v})
        .groupby("d", as_index=False)
        .agg(s=("v", "sum"), n=("v", "count"))
    )


def test_transient_failure_retries_once(ds):
    eng = Engine()
    q = groupby_with_time_granularity(_q())
    lowering = eng._lowering_for(q, ds)
    strategy = concrete_kernel(eng.strategy, lowering.num_groups)
    calls = {"n": 0}

    def poisoned(cols_list):
        calls["n"] += 1
        raise RuntimeError("injected transient device failure")

    eng._query_fn_cache[_query_key(q, ds) + ("fused", strategy)] = poisoned
    got = eng.execute(_q(), ds).sort_values("d").reset_index(drop=True)
    want = _oracle(ds).sort_values("d").reset_index(drop=True)
    assert calls["n"] >= 1  # the poisoned program actually ran
    np.testing.assert_array_equal(got["n"], want["n"])
    np.testing.assert_allclose(got["s"], want["s"], rtol=2e-5)


def test_retry_evicts_transformed_query_identity(ds):
    """A granularity GroupBy is rewritten (adds a __time dimension) before
    caching; the retry must evict under the TRANSFORMED identity or the
    poisoned program survives and the retry fails identically."""
    import dataclasses

    n = 4_096
    rng = np.random.default_rng(3)
    tds = build_datasource(
        "rt",
        {
            "d": rng.integers(0, 4, n).astype(np.int64),
            "v": rng.random(n).astype(np.float32),
            "t": (
                np.int64(1_600_000_000_000)
                + rng.integers(0, 3, n).astype(np.int64) * 86_400_000
            ),
        },
        dimension_cols=["d"],
        metric_cols=["v"],
        time_col="t",
    )
    raw = GroupByQuery(
        datasource="rt",
        dimensions=(DimensionSpec("d"),),
        aggregations=(Count("n"),),
        granularity="day",
    )
    eng = Engine()
    qt = groupby_with_time_granularity(raw)
    assert qt is not raw  # the transform actually rewrote it
    lowering = eng._lowering_for(qt, tds)
    strategy = concrete_kernel(eng.strategy, lowering.num_groups)

    def poisoned(cols_list):
        raise RuntimeError("injected transient device failure")

    eng._query_fn_cache[_query_key(qt, tds) + ("fused", strategy)] = poisoned
    got = eng.execute(raw, tds)
    assert int(got["n"].sum()) == n


def test_persistent_failure_surfaces(ds):
    eng = Engine()
    q = groupby_with_time_granularity(_q())

    def always_fail(self, q, ds, lowering, **kwargs):
        def fn(cols_list):
            raise RuntimeError("device permanently unreachable")

        return fn

    eng._segment_program = always_fail.__get__(eng)
    with pytest.raises(RuntimeError, match="permanently unreachable"):
        eng.execute(_q(), ds)


def test_static_errors_do_not_retry(ds):
    eng = Engine()
    calls = {"n": 0}

    def counting(self, q, ds, *route):
        calls["n"] += 1
        raise ValueError("static planning error")

    eng._dispatch_groupby_once = counting.__get__(eng)
    with pytest.raises(ValueError):
        eng.execute(_q(), ds)
    assert calls["n"] == 1  # no second dispatch for non-transient errors


# --- the adaptive and sparse tiers: a device error is never a reason to
# hand the query to another tier (it would answer right and hide the fault)


@pytest.fixture(scope="module")
def wide():
    """Combined domain 300 x 300 >> the scatter cutover, 700 pairs present:
    the shape both the adaptive and the sparse tier take."""
    from spark_druid_olap_tpu.catalog.segment import DimensionDict

    n, da, db = 30_000, 300, 300
    rng = np.random.default_rng(3)
    pairs = rng.choice(da * db, size=700, replace=False)[
        rng.integers(0, 700, size=n)
    ]
    cols = {
        "a": (pairs // db).astype(np.int64),
        "b": (pairs % db).astype(np.int64),
        "v": np.ones(n, np.float32),
    }
    ds = build_datasource(
        "wide", cols, dimension_cols=["a", "b"], metric_cols=["v"],
        rows_per_segment=n // 3,
        dicts={
            "a": DimensionDict(values=tuple(range(da))),
            "b": DimensionDict(values=tuple(range(db))),
        },
    )
    hit = cols["a"] < 40
    want_groups = len(np.unique(pairs[hit]))
    return ds, want_groups, int(hit.sum())


def _wide_q():
    from spark_druid_olap_tpu.models.filters import And, Bound, InFilter

    # few `a` codes pass, so the marginals shrink (adaptive does not
    # decline); `b` stays unpinned, so adaptive must MEASURE its kept sets
    # with a presence pass on the device instead of deriving them
    return GroupByQuery(
        datasource="wide",
        dimensions=(DimensionSpec("a"), DimensionSpec("b")),
        aggregations=(Count("n"), DoubleSum("s", "v")),
        filter=And((
            InFilter("a", tuple(range(40))),
            Bound("v", lower=0, ordering="numeric"),
        )),
    )


@pytest.fixture
def armed():
    from spark_druid_olap_tpu.resilience import injector

    injector().disarm()
    yield injector()
    injector().disarm()


@pytest.mark.parametrize("tier", ["adaptive", "sparse"])
def test_transient_failure_in_a_tier_is_retried_on_it_and_counted(
    wide, armed, tier
):
    ds, want_groups, want_rows = wide
    eng = Engine(strategy=tier)
    eng._retry_backoff_ms = 0.0
    armed.arm("device_dispatch", "error", times=1)
    got = eng.execute(_wide_q(), ds)
    assert len(got) == want_groups and int(got["n"].sum()) == want_rows
    m = eng.last_metrics
    assert m.strategy == tier  # same tier answered, not the next one down
    assert m.retries == 1  # and the re-dispatch shows


@pytest.mark.parametrize(
    "tier, program",
    [("adaptive", "_presence_program"), ("sparse", "_sparse_program")],
)
def test_static_failure_in_a_tier_raises(wide, monkeypatch, tier, program):
    eng = Engine(strategy=tier)

    def refused(*a, **kw):
        raise TypeError("the compiler refused this program")

    monkeypatch.setattr(eng, program, refused)
    with pytest.raises(TypeError, match="refused"):
        eng.execute(_wide_q(), wide[0])
    assert not eng._adaptive_declined and not eng._sparse_disabled
