"""Druid topN with hyperUnique against a plain numpy HLL (PR 39).

`tests/hll_reference.py` imports nothing of the program.  Held to it on
the CPU at small sizes: `_rho` on every value of its window, the engine's
merged HLL registers bit for bit (filters, intervals that cut segments,
many batches, arena and pipeline on and off), served native topN answers
(a tie forced at the 100th place), SQL's `approx_count_distinct` against
the native query, and the sketch path's spans, scopes and counter."""

import http.client
import json

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd
import pytest

import hll_reference as ref
import spark_druid_olap_tpu as sd
from spark_druid_olap_tpu.catalog.segment import build_datasource
from spark_druid_olap_tpu.config import SessionConfig
from spark_druid_olap_tpu.exec.engine import Engine
from spark_druid_olap_tpu.models.aggregations import DoubleSum, HyperUnique
from spark_druid_olap_tpu.models.dimensions import DimensionSpec
from spark_druid_olap_tpu.models.filters import Selector
from spark_druid_olap_tpu.models.query import GroupByQuery
from spark_druid_olap_tpu.ops.hll import _rho
from spark_druid_olap_tpu.server import OlapServer

DAY_MS = 86_400_000
SUM_REL_TOL = 2e-5  # f32 device sums against float64: the program's documented tolerance

_rho_jit = jax.jit(_rho, static_argnums=1)


def rho_mismatches(p: int):
    """(values, mismatching values of w) of `ops.hll._rho` over every
    value w of the (32 - p)-bit window h >> p, the low p bits drawn at
    random, against the reference's integer bit length.  Runs on
    whatever device JAX gives (PR 39 also ran it on the chip)."""
    w = np.arange(1 << (32 - p), dtype=np.uint64)
    low = np.random.default_rng(p).integers(0, 1 << p, w.size, dtype=np.uint64)
    h = (w << np.uint64(p)) | low
    got = np.asarray(_rho_jit(jnp.asarray(h.astype(np.uint32)), p))
    return w.size, w[got != ref.rho(h, p)]


@pytest.mark.parametrize("p", [11, 10])
def test_rho_is_exact_over_every_window_value(p):
    n, bad = rho_mismatches(p)
    assert n == 1 << (32 - p)
    assert bad.size == 0, bad[:10]


def _table(seed, n, card, time_days=28, key_domain=3000):
    """Seeded rows: a string dimension `d` of `card` values, a filter
    column `f`, keys `k` (half from a small domain so that they repeat,
    half over all of int32), a float32 measure `r`, time `t` over
    `time_days` days, sorted."""
    rng = np.random.default_rng(seed)
    keys = np.where(
        rng.random(n) < 0.5,
        rng.integers(0, key_domain, n),
        rng.integers(-(2**31), 2**31 - 1, n),
    ).astype(np.int32)
    return {
        "d": np.array([f"v{i:04d}" for i in rng.integers(0, card, n)], dtype=object),
        "f": rng.choice(np.array(["x", "y", "z"], dtype=object), n),
        "k": keys,
        "r": (rng.random(n) * 100).astype(np.float32),
        "t": np.sort(rng.integers(0, time_days * DAY_MS, n)).astype(np.int64),
    }


def _ds(cols, rows_per_segment, name="hll_t"):
    return build_datasource(
        name, cols, ["d", "f"], ["k", "r"], time_col="t",
        rows_per_segment=rows_per_segment,
    )


def _kept(cols, selector, interval):
    keep = np.ones(len(cols["k"]), dtype=bool)
    if selector:
        keep &= cols["f"] == "x"
    if interval is not None:
        keep &= (cols["t"] >= interval[0]) & (cols["t"] < interval[1])
    return keep


CUT = (5 * DAY_MS + 1234, 19 * DAY_MS + 777)  # cuts segments at both ends


@pytest.mark.parametrize(
    "seed, card, n, rows_per_segment, selector, interval, arena, pipeline",
    [
        (1, 1, 4_000, 4_096, False, None, True, True),
        (2, 7, 20_000, 5_000, True, None, True, True),
        (3, 250, 30_000, 4_096, False, CUT, True, True),
        (4, 1000, 40_000, 4_096, True, CUT, True, True),
        (5, 1000, 40_000, 4_096, False, None, False, False),
        (6, 250, 30_000, 2_048, True, CUT, True, False),
        (7, 30, 20_000, 2_048, False, CUT, False, True),
    ],
    ids=["g1", "g7-filter", "g250-interval", "g1000-filter-interval",
         "g1000-arena-off-pipeline-off", "g250-arena-on-pipeline-off",
         "g30-arena-off-pipeline-on"],
)
def test_engine_registers_equal_the_reference(
    seed, card, n, rows_per_segment, selector, interval, arena, pipeline
):
    cols = _table(seed, n, card)
    ds = _ds(cols, rows_per_segment)
    q = GroupByQuery(
        datasource="hll_t",
        dimensions=(DimensionSpec("d"),),
        aggregations=(HyperUnique("u", "k"), DoubleSum("rev", "r")),
        filter=Selector("f", "x") if selector else None,
        intervals=(interval,) if interval else (),
    )
    eng = Engine()
    eng.arena_execution = arena
    eng._pipeline.enabled = pipeline
    dims, la, G, sums, mins, maxs, sk = eng._partials_for_query(q, ds)
    got = np.asarray(sk["u"])
    assert got.shape == (G, 2048) and got.dtype == np.int32

    keep = _kept(cols, selector, interval)
    names, codes = np.unique(cols["d"][keep].astype(str), return_inverse=True)
    want = ref.registers(cols["k"][keep], codes, len(names))
    by_value = dict(zip(names, want))
    decoded = dims[0].decode(np.arange(G))
    zero = np.zeros(2048, np.int32)
    for gid in range(G):
        np.testing.assert_array_equal(
            got[gid], by_value.pop(str(decoded[gid]), zero), err_msg=str(decoded[gid])
        )
    assert not by_value  # every present value has its group


# ---------------------------------------------------------------------------
# Served native topN
# ---------------------------------------------------------------------------


def _tied_table(seed):
    """130 values in 20 blocks: every value of block j holds each key of
    one set S_j (and repeats), so the values of a block have identical
    registers and tie; blocks of 7 and 6 put a tie across the 100th
    place."""
    rng = np.random.default_rng(seed)
    d, k = [], []
    for i in range(130):
        keys = np.arange(60 + 25 * (i % 20), dtype=np.int64) * 7919 + 13
        keys = np.concatenate([keys, rng.choice(keys, 40)])
        d += [f"c{i:03d}"] * len(keys)
        k.append(keys)
    k = np.concatenate(k).astype(np.int32)
    n = len(k)
    order = rng.permutation(n)
    return {
        "d": np.array(d, dtype=object)[order],
        "f": rng.choice(np.array(["x", "y"], dtype=object), n),
        "k": k[order],
        "r": (rng.random(n) * 100).astype(np.float32),
        "t": np.sort(rng.integers(0, 28 * DAY_MS, n)).astype(np.int64),
    }


@pytest.fixture(scope="module")
def served():
    cfg = SessionConfig.load_calibrated()
    cfg.result_cache_entries = 0  # every request executes
    ctx = sd.TPUOlapContext(cfg)
    tables = {"hll_a": _table(11, 60_000, 1000), "hll_tie": _tied_table(12)}
    for name, cols in tables.items():
        ctx.register_table(
            name, cols, dimensions=["d", "f"], metrics=["k", "r"],
            time_column="t", rows_per_segment=8_192,
        )
    srv = OlapServer(ctx, port=0).start()
    try:
        yield ctx, srv, tables
    finally:
        srv.shutdown()


def _post(port, path, body):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=300)
    try:
        conn.request("POST", path, json.dumps(body),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        out = resp.read()
        assert resp.status == 200, out
        return json.loads(out)
    finally:
        conn.close()


def _native(table, selector=False, interval="1970-01-01/1970-02-01", qid=None):
    q = {
        "queryType": "topN", "dataSource": table, "dimension": "d",
        "threshold": 100, "metric": "uniq_custs", "granularity": "all",
        "intervals": [interval],
        "aggregations": [
            {"type": "doubleSum", "name": "revenue", "fieldName": "r"},
            {"type": "hyperUnique", "name": "uniq_custs", "fieldName": "k"},
        ],
    }
    if selector:
        q["filter"] = {"type": "selector", "dimension": "f", "value": "x"}
    if qid:
        q["context"] = {"queryId": qid}
    return q


def _check_topn(rows, want):
    got = pd.DataFrame(rows)
    assert list(got["d"]) == list(want["value"])  # same 100, same ranks
    np.testing.assert_array_equal(got["uniq_custs"].to_numpy(np.int64), want["uniq"])
    np.testing.assert_allclose(got["revenue"], want["revenue"], rtol=SUM_REL_TOL)


@pytest.mark.parametrize("table, selector, interval", [
    ("hll_a", False, None),
    ("hll_a", True, None),
    ("hll_a", False, (3 * DAY_MS, 17 * DAY_MS + 5)),
    ("hll_tie", False, None),
    ("hll_tie", True, None),
])
def test_served_native_topn_equals_the_reference(served, table, selector, interval):
    ctx, srv, tables = served
    cols = tables[table]
    text = "1970-01-01/1970-02-01"
    if interval is not None:
        text = "/".join(
            str(np.datetime64(v, "ms")) for v in interval
        )
    body = _post(srv.port, "/druid/v2", _native(table, selector, text))
    keep = _kept(cols, selector, interval)
    want = ref.topn(cols["d"][keep], cols["k"][keep], {"revenue": cols["r"][keep]})
    if table == "hll_tie" and not selector:
        # the tie this table is built for lies across the 100th place
        full = ref.topn(cols["d"], cols["k"], {}, threshold=1000)
        assert full["uniq"][99] == full["uniq"][100]
    (bucket,) = body
    _check_topn(bucket["result"], want)
    m = ctx.last_metrics
    assert m.executor == "device" and not (m.degraded or m.partial or m.retries)


@pytest.mark.parametrize("table, selector", [("hll_a", True), ("hll_tie", False)])
def test_sql_approx_count_distinct_equals_the_native_topn(served, table, selector):
    ctx, srv, tables = served
    where = "WHERE f = 'x' " if selector else ""
    sql = (
        f"SELECT d, APPROX_COUNT_DISTINCT(k) AS uniq_custs, SUM(r) AS revenue "
        f"FROM {table} {where}GROUP BY d ORDER BY uniq_custs DESC, d LIMIT 100"
    )
    rows = _post(srv.port, "/druid/v2/sql", {"query": sql})
    (bucket,) = _post(srv.port, "/druid/v2", _native(table, selector))
    native = bucket["result"]
    assert [r["d"] for r in rows] == [r["d"] for r in native]
    assert [r["uniq_custs"] for r in rows] == [r["uniq_custs"] for r in native]
    np.testing.assert_allclose(
        [r["revenue"] for r in rows], [r["revenue"] for r in native],
        rtol=SUM_REL_TOL,
    )


# ---------------------------------------------------------------------------
# Spans, scopes and the counter of the sketch path
# ---------------------------------------------------------------------------


def _walk(node):
    yield node
    for c in node.get("children", ()):
        yield from _walk(c)


@pytest.mark.parametrize("sketch", [True, False], ids=["hyperUnique", "sum-only"])
def test_sketch_requests_carry_their_span_counter_and_arena_decline(served, sketch):
    """A sketch request's receipt holds `sketch_estimate` and its
    `QueryMetrics.sketch_state_bytes` are the fetched register histogram
    (PR 40: 23 integers a group at p = 11); the segment dispatches of a
    scope the arena would have taken say `arena="declined:sketch"`.  A
    request without sketches has none of it and reads 0."""
    ctx, srv, tables = served
    q = _native("hll_a", qid=f"obs-{sketch}")
    if not sketch:
        q["aggregations"] = q["aggregations"][:1]
        q["metric"] = "revenue"
    _post(srv.port, "/druid/v2", q)
    doc = ctx.tracer.ring.get(f"obs-{sketch}")
    m = ctx.last_metrics
    spans = doc["receipt"]["spans"]
    dispatches = [
        s for s in _walk(doc["spans"])
        if s["name"] == "segment_dispatch" and "sketch" not in s["attrs"]
    ]
    declined = [s for s in dispatches if s["attrs"].get("arena") == "declined:sketch"]
    if sketch:
        assert spans["sketch_estimate"]["n"] == 1
        assert m.sketch_state_bytes == m.num_groups * 23 * 4 > 0
        assert dispatches and declined == dispatches  # 8 segments, 4 batches
    else:
        assert "sketch_estimate" not in spans
        assert m.sketch_state_bytes == 0
        assert not declined


@pytest.mark.parametrize("sketch", [True, False], ids=["hyperUnique", "sum-only"])
def test_sketch_fold_and_merge_scopes_name_the_lowered_program(sketch):
    cols = _table(21, 8_192, 40)
    ds = _ds(cols, 4_096)
    aggs = (DoubleSum("rev", "r"),) + ((HyperUnique("u", "k"),) if sketch else ())
    q = GroupByQuery(datasource="hll_t", dimensions=(DimensionSpec("d"),),
                     aggregations=aggs)
    eng = Engine(strategy="dense")
    lowering = eng._lowering_for(q, ds)
    program = eng._segment_program(q, ds, lowering)
    cols_list = [eng._cols_for_segment(s, ds, lowering.columns) for s in ds.segments]
    text = program.lower(cols_list).as_text(debug_info=True)
    for scope in ("sdol.sketch_fold", "sdol.sketch_merge"):
        assert (scope in text) == sketch, scope
