"""The highcard route: a group-by whose kept groups pass the adaptive
tier's cap scatters over its whole domain on the device and fetches its
merged state once (`plan.cost.compact_route`).  Also the host's share of
an answer: `QueryMetrics.host_groups` / `result_rows` and the
`order_limit` span."""

import types

import numpy as np
import pytest

import spark_druid_olap_tpu as sd
from spark_druid_olap_tpu.plan import cost
from spark_druid_olap_tpu.workloads import tpch

SCALE = 0.04  # 6,000 customers and 60,000 orders: both past the scatter cutover


def _ctx():
    """One chip's context, as the TPC-H cell runs: the single-device engine,
    every request executed."""
    cfg = sd.SessionConfig()
    cfg.prefer_distributed = False
    cfg.result_cache_entries = 0
    return sd.TPUOlapContext(cfg)


@pytest.fixture(scope="module")
def world():
    ctx = _ctx()
    tables = tpch.register(ctx, scale=SCALE, seed=5, rows_per_segment=1 << 15)
    return ctx, tpch.flat_frame(tables)


@pytest.fixture
def adaptive_first(monkeypatch):
    """The plan SF10 gets for Q3 and Q10 (the adaptive class, ROADMAP W8),
    at this scale, with the adaptive tier's cap lowered to 64 kept groups
    through the cost model's own parameter."""
    choose = cost.choose_query_kernel

    def prefer_adaptive(q, ds, num_groups, cfg, exclude=(), costs=None):
        if costs is not None and costs.get("adaptive", float("inf")) < float("inf"):
            return "adaptive"
        return choose(q, ds, num_groups, cfg, exclude=exclude, costs=costs)

    monkeypatch.setattr(cost, "choose_query_kernel", prefer_adaptive)
    monkeypatch.setattr(cost, "ADAPTIVE_MAX_COMPACT_GROUPS", 64)


def _walk(node):
    yield node
    for c in node.get("children", ()):
        yield from _walk(c)


@pytest.mark.parametrize("name, key, limit", [
    ("q3", "l_orderkey", 10),
    ("q10", "c_custkey", 20),
])
def test_highcard_answers_q3_and_q10_as_numpy(world, adaptive_first, name, key, limit):
    """Q3 (grouped by l_orderkey, 60,000 keys) and Q10 (by c_custkey after
    the FD pruning, 6,000 keys) keep more groups than the lowered cap: the
    route is `highcard`, on the first request (phase A decides) and on a
    repeat (the memo), and the top-k equals a float64 numpy reference over
    every group."""
    ctx, f = world
    ctx.engine._adaptive_declined.clear()
    ctx.engine._highcard.clear()
    want = tpch.oracle(f, name)
    rev = (f.l_extendedprice * (1 - f.l_discount)).to_numpy()
    if name == "q3":
        m = ((f.c_mktsegment == "BUILDING")
             & (f.o_orderdate < tpch._ms("1995-03-15"))
             & (f.l_shipdate > tpch._ms("1995-03-15"))).to_numpy()
    else:
        m = ((f.o_orderdate >= tpch._ms("1993-10-01"))
             & (f.o_orderdate < tpch._ms("1994-01-01"))
             & (f.l_returnflag == "R")).to_numpy()
    keys = f[key].to_numpy()[m]
    uniq, inv = np.unique(keys, return_inverse=True)
    sums = np.bincount(inv, weights=rev[m])
    top = np.argsort(-sums, kind="stable")[:limit]
    for _ in range(2):
        got = ctx.sql(tpch.QUERIES[name])
        mt = ctx.last_metrics
        assert mt.strategy == "highcard" and mt.executor == "device"
        assert list(got[key]) == list(uniq[top]) == list(want[key])
        np.testing.assert_allclose(got["revenue"], sums[top], rtol=1e-5)
        assert mt.host_groups == len(uniq) > 64
        assert mt.result_rows == limit
    if name == "q10":  # the FD-pruned columns ride the hidden code aggregations
        assert list(got["c_name"]) == list(want["c_name"])
        assert list(got["c_nation"]) == list(want["c_nation"])
    doc = ctx.tracer.last_trace_dict()
    launches = [s for s in _walk(doc["spans"])
                if s["name"] == "segment_dispatch" and s["attrs"].get("tier") == "highcard"]
    assert launches
    (order,) = [s for s in _walk(doc["spans"]) if s["name"] == "order_limit"]
    assert order["attrs"] == {"rows_in": len(uniq), "rows_out": limit}


def _shape(num_groups, sketch=False):
    """(query, lowering) stand-ins: a sum and a max, or a theta sketch."""
    from spark_druid_olap_tpu.models.aggregations import (
        DoubleMax, DoubleSum, ThetaSketch,
    )

    aggs = (DoubleSum("s", "v"), DoubleMax("m", "v"))
    if sketch:
        aggs += (ThetaSketch("t", "k", 16384),)
    la = types.SimpleNamespace(sketch_aggs=aggs[2:])
    return (types.SimpleNamespace(aggregations=aggs),
            types.SimpleNamespace(num_groups=num_groups, la=la))


@pytest.mark.parametrize("kept, groups, sketch, budget, route", [
    (321_000, 1_500_000, False, 16 << 30, "highcard"),  # Q10 at SF10
    (321_000, 1_500_000, False, 1 << 20, "declined"),  # state past the share
    (321_000, 1_500_000, True, 16 << 30, "declined"),  # a sketch state
    (115_000, 15_000_000, False, 16 << 30, "adaptive"),  # Q3 at SF10
    (600, 504_000, False, 16 << 30, "adaptive"),  # SSB q3_2's shape
    (400_000, 600_000, False, 16 << 30, "declined"),  # little shrink
])
def test_compact_route(kept, groups, sketch, budget, route):
    """`compact_route` alone says where phase A's kept count sends a
    query: under the adaptive cap and shrink to the adaptive tier; past
    the cap to highcard where the dense state fits the budget's share and
    has no sketch; else declined."""
    q, lowering = _shape(groups, sketch=sketch)
    assert cost.compact_route(kept, q, lowering, sd.SessionConfig(), budget) == route


def test_host_groups_and_result_rows_of_an_ordered_limit():
    """`host_groups` counts the groups finalize decoded, `result_rows` the
    rows answered; the one `order_limit` span says both."""
    ctx = _ctx()
    rng = np.random.default_rng(0)
    ctx.register_table(
        "t", {"d": rng.integers(0, 40, 5000).astype(str),
              "v": rng.random(5000).astype(np.float32)},
        dimensions=["d"], metrics=["v"],
    )
    df = ctx.sql("SELECT d, sum(v) AS s FROM t GROUP BY d ORDER BY s DESC LIMIT 7")
    m = ctx.last_metrics
    assert (m.host_groups, m.result_rows, len(df)) == (40, 7, 7)
    spans = [s for s in _walk(ctx.tracer.last_trace_dict()["spans"])
             if s["name"] == "order_limit"]
    assert [s["attrs"] for s in spans] == [{"rows_in": 40, "rows_out": 7}]
    df = ctx.sql("SELECT d, sum(v) AS s FROM t GROUP BY d")
    m = ctx.last_metrics
    assert (m.host_groups, m.result_rows) == (40, 40)
    assert not [s for s in _walk(ctx.tracer.last_trace_dict()["spans"])
                if s["name"] == "order_limit"]


def test_host_groups_of_a_cube_count_every_set():
    """A CUBE's sets are finalized one by one: `host_groups` adds them up,
    the rows every set answered."""
    ctx = _ctx()
    rng = np.random.default_rng(1)
    ctx.register_table(
        "c", {"a": rng.integers(0, 3, 2000).astype(str),
              "b": rng.integers(0, 4, 2000).astype(str),
              "v": rng.random(2000).astype(np.float32)},
        dimensions=["a", "b"], metrics=["v"],
    )
    df = ctx.sql("SELECT a, b, sum(v) AS s FROM c GROUP BY CUBE(a, b)")
    m = ctx.last_metrics
    assert m.host_groups == m.result_rows == len(df) == 3 * 4 + 3 + 4 + 1
