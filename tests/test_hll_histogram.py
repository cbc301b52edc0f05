"""The HLL estimate from a register histogram made on the device (PR 40).

`ops/hll.register_histogram` counts each group's registers by value on
the device, so the host fetches int32[G, 34 - p] instead of int32[G, 2^p]
and estimates from it.  Held here: the device histogram equals its numpy
twin; the estimate from a histogram equals the float64 estimate over the
registers bit for bit; a served topN with a hyperUniqueCardinality
post-agg answers as the reference does while fetching the histogram;
and a state captured for the result cache keeps its registers, so a
delta merge over it answers as before."""

import http.client
import json

import jax.numpy as jnp
import numpy as np
import pytest

import hll_reference as ref
import spark_druid_olap_tpu as sd
from spark_druid_olap_tpu.catalog.segment import DimensionDict, build_datasource
from spark_druid_olap_tpu.config import SessionConfig
from spark_druid_olap_tpu.exec.engine import Engine
from spark_druid_olap_tpu.models.aggregations import DoubleSum, HyperUnique
from spark_druid_olap_tpu.models.dimensions import DimensionSpec
from spark_druid_olap_tpu.models.filters import And, InFilter
from spark_druid_olap_tpu.models.query import GroupByQuery
from spark_druid_olap_tpu.ops import hll
from spark_druid_olap_tpu.server import OlapServer

DAY_MS = 86_400_000


def _estimate_registers(registers) -> np.ndarray:
    """The estimator as it stood before PR 40: float64 over the registers
    themselves.  The histogram's estimate is held to it bit for bit."""
    regs = np.asarray(registers, dtype=np.float64)
    m = regs.shape[-1]
    if m >= 128:
        alpha = 0.7213 / (1 + 1.079 / m)
    elif m == 64:
        alpha = 0.709
    elif m == 32:
        alpha = 0.697
    else:
        alpha = 0.673
    est = alpha * m * m / np.sum(np.exp2(-regs), axis=-1)
    zeros = np.sum(regs == 0, axis=-1)
    with np.errstate(divide="ignore"):
        lc = m * np.log(np.where(zeros > 0, m / np.maximum(zeros, 1), 1.0))
    est = np.where((est <= 2.5 * m) & (zeros > 0), lc, est)
    two32 = 2.0**32
    return np.where(est > two32 / 30.0, -two32 * np.log1p(-est / two32), est)


def _random_registers(rng, groups, p):
    """Registers of `groups` groups at precision p: random values over
    0 … 33 - p, one all-zero group and one at rho = 33 - p throughout."""
    top = 33 - p
    regs = rng.integers(0, top + 1, (groups, 1 << p)).astype(np.int32)
    regs[0] = 0
    if groups > 1:
        regs[-1] = top
    return regs


@pytest.mark.parametrize("p, groups", [
    (4, 1), (4, 1001), (11, 1), (11, 1001), (14, 1), (14, 9), (16, 1), (16, 3),
])
def test_device_histogram_equals_its_numpy_twin(p, groups):
    regs = _random_registers(np.random.default_rng(p * 7 + groups), groups, p)
    got = np.asarray(hll.register_histogram(jnp.asarray(regs), p))
    want = hll.histogram_np(regs, p)
    assert got.dtype == np.int32 and got.shape == (groups, hll.histogram_width(p))
    np.testing.assert_array_equal(got, want)
    assert (want.sum(axis=-1) == 1 << p).all()
    assert want[0, 0] == 1 << p  # the all-zero group
    if groups > 1:
        assert want[-1, -1] == 1 << p  # every register at 33 - p


def test_numpy_twin_of_no_groups_is_empty():
    hist = hll.histogram_np(np.zeros((0, 2048), np.int32), 11)
    assert hist.shape == (0, 23) and hll.estimate_from_histogram(hist, 2048).shape == (0,)


def test_numpy_twin_refuses_a_register_out_of_range():
    regs = np.zeros((2, 2048), np.int32)
    regs[1, 5] = 23  # 33 - 11 = 22 is the largest rho at p = 11
    with pytest.raises(ValueError):
        hll.histogram_np(regs, 11)


def _cardinality_sweep(p):
    """Registers of real HLLs (the reference's hash and fold) over
    cardinalities from 1 to 20 m, dense around the linear-counting edge
    at 2.5 m, and synthetic groups past the large-range threshold."""
    m = 1 << p
    rng = np.random.default_rng(p)
    sizes = np.unique(np.concatenate([
        np.arange(1, 40),
        np.linspace(2.0 * m, 3.2 * m, 160).astype(np.int64),
        np.geomspace(1, 20 * m, 60).astype(np.int64),
    ]))
    keys = rng.permutation(np.arange(int(sizes.sum()), dtype=np.int64))
    groups = np.repeat(np.arange(len(sizes)), sizes)
    regs = ref.registers(keys.astype(np.int32), groups, len(sizes), p)
    # past 2^32 / 30: registers near the top of the window
    top = 33 - p
    big = rng.integers(top - 7, top + 1, (24, m)).astype(np.int32)
    return np.concatenate([regs, big])


@pytest.mark.parametrize("p", [4, 5, 6, 7, 11, 14])
def test_estimate_from_histogram_is_bit_identical(p):
    regs = _cardinality_sweep(p)
    m = 1 << p
    want = _estimate_registers(regs)
    got = hll.estimate_from_histogram(hll.histogram_np(regs, p), m)
    assert np.array_equal(got.view(np.uint64), want.view(np.uint64))
    dev = np.asarray(hll.register_histogram(jnp.asarray(regs), p))
    assert np.array_equal(
        hll.estimate_from_histogram(dev, m).view(np.uint64), want.view(np.uint64)
    )
    assert np.array_equal(hll.estimate(regs).view(np.uint64), want.view(np.uint64))
    # the sweep reaches every branch of the estimator
    hist = hll.histogram_np(regs, p)
    alpha = 0.7213 / (1 + 1.079 / m) if m >= 128 else {64: 0.709, 32: 0.697}.get(m, 0.673)
    raw = alpha * m * m / (hist @ np.exp2(-np.arange(hist.shape[-1])))
    lin = (raw <= 2.5 * m) & (hist[:, 0] > 0)
    assert lin.any() and (~lin & (raw <= 2.5 * m * 1.1)).any()
    assert (want > 2.0**32 / 30).any() or p < 11


# ---------------------------------------------------------------------------
# Served: a native topN with hyperUnique and hyperUniqueCardinality
# ---------------------------------------------------------------------------


def _table(seed, n, card):
    rng = np.random.default_rng(seed)
    return {
        "d": np.array([f"v{i:04d}" for i in rng.integers(0, card, n)], dtype=object),
        "k": np.where(
            rng.random(n) < 0.5,
            rng.integers(0, 3000, n),
            rng.integers(-(2**31), 2**31 - 1, n),
        ).astype(np.int32),
        "r": (rng.random(n) * 100).astype(np.float32),
        "t": np.sort(rng.integers(0, 28 * DAY_MS, n)).astype(np.int64),
    }


def _ctx(cache_entries):
    cfg = SessionConfig.load_calibrated()
    cfg.result_cache_entries = cache_entries
    cfg.prefer_distributed = False
    return sd.TPUOlapContext(cfg)


def _post(port, body):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=300)
    try:
        conn.request("POST", "/druid/v2", json.dumps(body),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        out = resp.read()
        assert resp.status == 200, out
        return json.loads(out)
    finally:
        conn.close()


def _topn(table, qid):
    return {
        "queryType": "topN", "dataSource": table, "dimension": "d",
        "threshold": 100, "metric": "uniq_custs", "granularity": "all",
        "intervals": ["1970-01-01/1970-02-01"],
        "aggregations": [
            {"type": "doubleSum", "name": "revenue", "fieldName": "r"},
            {"type": "hyperUnique", "name": "uniq_custs", "fieldName": "k"},
        ],
        "postAggregations": [
            {"type": "hyperUniqueCardinality", "name": "card",
             "fieldName": "uniq_custs"},
        ],
        "context": {"queryId": qid},
    }


def _walk(node):
    yield node
    for c in node.get("children", ()):
        yield from _walk(c)


@pytest.mark.parametrize("cache_entries, source", [(0, "histogram"), (64, "registers")])
def test_served_topn_answers_alike_from_histogram_and_registers(cache_entries, source):
    """With the result cache off the fetch carries the histogram; with it
    on the serial path captures the state and keeps the registers.  Both
    answer as the reference does, the post-agg equal to the estimate
    before its `rint`, bit for bit."""
    cols = _table(40, 60_000, 1000)
    ctx = _ctx(cache_entries)
    ctx.register_table("hh", cols, dimensions=["d"], metrics=["k", "r"],
                       time_column="t", rows_per_segment=8_192)
    srv = OlapServer(ctx, port=0).start()
    try:
        (bucket,) = _post(srv.port, _topn("hh", f"hist-{source}"))
    finally:
        srv.shutdown()
    rows = bucket["result"]
    want = ref.topn(cols["d"], cols["k"], {})
    assert [r["d"] for r in rows] == list(want["value"])
    assert [r["uniq_custs"] for r in rows] == list(want["uniq"])
    names, codes = np.unique(cols["d"].astype(str), return_inverse=True)
    est = dict(zip(names, ref.estimate(ref.registers(cols["k"], codes, len(names)))))
    assert [r["card"] for r in rows] == [est[r["d"]] for r in rows]

    m = ctx.last_metrics
    assert m.executor == "device" and not (m.degraded or m.partial or m.retries)
    width = hll.histogram_width(11) if source == "histogram" else 2048
    assert m.sketch_state_bytes == m.num_groups * width * 4 > 0
    doc = ctx.tracer.ring.get(f"hist-{source}")
    (est_span,) = [s for s in _walk(doc["spans"]) if s["name"] == "sketch_estimate"]
    assert est_span["attrs"]["source"] == source
    launches = [
        s for s in _walk(doc["spans"])
        if s["name"] == "segment_dispatch" and s["attrs"].get("sketch") == "histogram"
    ]
    assert len(launches) == (source == "histogram")


def test_adaptive_tier_fetches_the_histogram():
    """Phase B of the adaptive tier feeds finalize alone: its fetch is the
    histogram of the compact groups, and the estimates are the
    reference's."""
    rng = np.random.default_rng(9)
    n, da, db = 60_000, 400, 400
    cols = {
        "a": rng.integers(0, da, n),
        "b": rng.integers(0, db, n),
        "v": (rng.random(n) * 100).astype(np.float32),
        "k": rng.integers(-(2**31), 2**31 - 1, n).astype(np.int32),
    }
    ds = build_datasource(
        "adh", cols, dimension_cols=["a", "b"], metric_cols=["v", "k"],
        rows_per_segment=n // 3,
        dicts={"a": DimensionDict(values=tuple(range(da))),
               "b": DimensionDict(values=tuple(range(db)))},
    )
    q = GroupByQuery(
        datasource="adh",
        dimensions=(DimensionSpec("a"), DimensionSpec("b")),
        aggregations=(DoubleSum("s", "v"), HyperUnique("u", "k")),
        filter=And((InFilter("a", tuple(range(6))), InFilter("b", tuple(range(6))))),
    )
    eng = Engine(strategy="adaptive")
    got = eng.execute(q, ds).sort_values(["a", "b"]).reset_index(drop=True)
    m = eng.last_metrics
    assert m.strategy == "adaptive"
    # G' = 6 x 6 compact groups, 23 integers each
    assert m.sketch_state_bytes == 36 * hll.histogram_width(11) * 4
    keep = np.isin(cols["a"], range(6)) & np.isin(cols["b"], range(6))
    gid = cols["a"][keep] * db + cols["b"][keep]
    present, codes = np.unique(gid, return_inverse=True)
    want = np.rint(ref.estimate(ref.registers(cols["k"][keep], codes, len(present))))
    assert list(got["a"] * db + got["b"]) == list(present)
    np.testing.assert_array_equal(got["u"].to_numpy(np.int64), want.astype(np.int64))


def test_captured_state_keeps_registers_and_its_delta_merge_answers():
    """The result cache's capture needs registers (the next append merges
    them by max): the captured state holds int32[G, 2^p], and a refresh
    after an append, served as cached ⊕ delta, answers as a fresh run."""
    cols = _table(41, 20_000, 30)
    ctx = _ctx(64)
    ctx.register_table("hc", cols, dimensions=["d"], metrics=["k", "r"],
                       time_column="t", rows_per_segment=4_096)
    ds = ctx.catalog.get("hc")
    q = GroupByQuery(datasource="hc", dimensions=(DimensionSpec("d"),),
                     aggregations=(HyperUnique("u", "k"),))
    with ctx.engine.state_capture() as cap:
        ctx.engine.execute(q, ds)
    regs = cap["state"]["sketches"]["u"]
    assert regs.dtype == np.int32 and regs.shape[-1] == 2048

    sql = "SELECT d, APPROX_COUNT_DISTINCT(k) AS u FROM hc GROUP BY d ORDER BY d"
    ctx.sql(sql)
    new_keys = np.arange(500, dtype=np.int32) * 104729 + 17
    ctx.append_rows("hc", [
        {"d": "v0003", "k": int(k), "r": 1.0, "t": DAY_MS} for k in new_keys
    ])
    got = ctx.sql(sql)
    assert ctx.last_metrics.strategy == "result-cache-delta"
    ctx.serve.result_cache.clear()
    want = ctx.sql(sql)
    assert ctx.last_metrics.strategy != "result-cache-delta"
    np.testing.assert_array_equal(got["u"].to_numpy(), want["u"].to_numpy())
    assert list(got["d"]) == list(want["d"])
    keys = np.concatenate([cols["k"], new_keys])
    vals = np.concatenate([cols["d"].astype(str), np.full(500, "v0003")])
    names, codes = np.unique(vals, return_inverse=True)
    exact = np.rint(ref.estimate(ref.registers(keys, codes, len(names))))
    np.testing.assert_array_equal(got["u"].to_numpy(np.int64), exact.astype(np.int64))


def test_histogram_program_is_named_by_its_device_scope():
    text = hll.register_histogram.lower(jnp.zeros((3, 2048), jnp.int32), 11).as_text(
        debug_info=True
    )
    assert "sdol.sketch_histogram" in text
    assert "scatter" not in text
