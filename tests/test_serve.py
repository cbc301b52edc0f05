"""Async serving core (ISSUE 8): micro-batch query fusion, priority
lanes, the delta-aware version-keyed result cache, and the progressive
SQL surface.

Fusion contract: a fused batch of mixed groupBy/topN/timeseries queries
returns BYTE-IDENTICAL results to the same queries run serially (same
per-segment partial-merge order, so even float accumulation matches),
and an append between enqueue and dispatch invalidates the batch —
every member re-executes individually, never against a torn snapshot.

Result-cache contract: a version-exact hit serves with zero device
dispatch; an append serves (cached historical partial) ⊕ (fresh delta
partials) scanning ONLY the delta; a dictionary extension or a
compaction (retired uids) is a full miss; a cached-exact hit is never
stamped partial (ROADMAP 3(d) regression).

Lane contract: interactive dashboard queries are admitted and answered
while the heavy lane is saturated by scans; lane rejections 503 naming
the lane with the lane's own Retry-After.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pandas as pd
import pandas.testing as pdt
import pytest

import spark_druid_olap_tpu as sd
from spark_druid_olap_tpu.config import SessionConfig
from spark_druid_olap_tpu.exec.state import PartialState
from spark_druid_olap_tpu.models.wire import query_from_druid
from spark_druid_olap_tpu.resilience import injector, partial_scope
from spark_druid_olap_tpu.server import OlapServer

DAY = 86_400_000


@pytest.fixture(autouse=True)
def _clean_injector():
    injector().disarm()
    yield
    injector().disarm()


def _make_ctx(n=4_000, **overrides):
    cfg = SessionConfig.load_calibrated()
    cfg.retry_backoff_ms = 1.0
    cfg.prefer_distributed = False
    for k, v in overrides.items():
        setattr(cfg, k, v)
    ctx = sd.TPUOlapContext(cfg)
    rng = np.random.default_rng(11)
    ctx.register_table(
        "ev",
        {
            "city": rng.choice(
                np.array(["NY", "SF", "LA", "CHI"], dtype=object), n
            ),
            "kind": rng.choice(np.array(["a", "b"], dtype=object), n),
            "v": rng.integers(0, 1_000, n).astype(np.int64),
            "t": (rng.integers(0, 7, n) * DAY).astype(np.int64),
        },
        dimensions=["city", "kind"],
        metrics=["v"],
        time_column="t",
        rows_per_segment=512,
    )
    return ctx


def _get(port, path):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=30
    ) as r:
        return json.loads(r.read())


def _post(port, path, payload, timeout=60):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
        method="POST",
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read()), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read()), dict(e.headers)


_GROUPBY = {
    "queryType": "groupBy",
    "dataSource": "ev",
    "granularity": "all",
    "dimensions": ["city"],
    "aggregations": [
        {"type": "longSum", "name": "s", "fieldName": "v"},
        {"type": "count", "name": "n"},
    ],
    "intervals": ["1970-01-01T00:00:00Z/1970-01-08T00:00:00Z"],
}
_TOPN = {
    "queryType": "topN",
    "dataSource": "ev",
    "granularity": "all",
    "dimension": "kind",
    "metric": "s",
    "threshold": 2,
    "aggregations": [{"type": "longSum", "name": "s", "fieldName": "v"}],
    "intervals": ["1970-01-01T00:00:00Z/1970-01-08T00:00:00Z"],
}
_TIMESERIES = {
    "queryType": "timeseries",
    "dataSource": "ev",
    "granularity": "day",
    "aggregations": [
        {"type": "longSum", "name": "s", "fieldName": "v"},
        {"type": "count", "name": "n"},
    ],
    "intervals": ["1970-01-01T00:00:00Z/1970-01-08T00:00:00Z"],
}


# ---------------------------------------------------------------------------
# micro-batch fusion
# ---------------------------------------------------------------------------


def test_fused_mixed_batch_is_byte_identical_to_serial():
    """Oracle parity: groupBy + topN + timeseries fused into ONE device
    program == the same queries run serially, byte for byte."""
    ctx = _make_ctx(result_cache_entries=0)
    ds = ctx.catalog.get("ev")
    queries = [query_from_druid(s) for s in (_GROUPBY, _TOPN, _TIMESERIES)]
    serial = [ctx.engine.execute(q, ds) for q in queries]
    fused = ctx.engine.execute_fused(
        queries, ds, query_ids=["q-a", "q-b", "q-c"]
    )
    assert len(fused) == 3
    for (df, state, m), want, qid in zip(
        fused, serial, ("q-a", "q-b", "q-c")
    ):
        pdt.assert_frame_equal(
            df.reset_index(drop=True), want.reset_index(drop=True)
        )
        # fused demux stamps every member's OWN query_id + batch size
        # (serving-discipline GL1702)
        assert m.query_id == qid
        assert m.fused_batch == 3
        assert isinstance(state, PartialState)


def test_fused_concurrent_sql_matches_serial_and_counts():
    ctx = _make_ctx(result_cache_entries=0, fusion_window_ms=60.0)
    sqls = [
        "SELECT city, sum(v) AS s FROM ev GROUP BY city ORDER BY city",
        "SELECT kind, sum(v) AS s, count(*) AS c FROM ev "
        "GROUP BY kind ORDER BY kind",
        "SELECT city, max(v) AS mx FROM ev GROUP BY city ORDER BY city",
    ]
    # serial reference first (fusion stays idle: solo batches re-route)
    ctx.serve.fusion.window_ms = 0.0
    serial = [ctx.sql(q) for q in sqls]
    ctx.serve.fusion.window_ms = 60.0
    results = {}

    def run(i, q):
        results[i] = ctx.sql(q)

    threads = [
        threading.Thread(target=run, args=(i, q))
        for i, q in enumerate(sqls)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert len(results) == 3
    for i in range(3):
        pdt.assert_frame_equal(
            results[i].reset_index(drop=True),
            serial[i].reset_index(drop=True),
        )
    stats = ctx.serve.fusion.to_dict()
    assert stats["batches_fused"] >= 1
    assert stats["members_fused"] >= 2


def test_fused_solo_batch_reroutes_to_serial_path():
    """A batch of one (no concurrency inside the window) must not pay
    the fused program's demux overhead: it re-routes to the member's
    normal serial execution."""
    ctx = _make_ctx(result_cache_entries=0, fusion_window_ms=5.0)
    df = ctx.sql("SELECT city, sum(v) AS s FROM ev GROUP BY city")
    assert len(df) == 4
    stats = ctx.serve.fusion.to_dict()
    assert stats["batches_fused"] == 0
    assert ctx.last_metrics.fused_batch == 0


def test_append_between_enqueue_and_dispatch_invalidates_batch():
    """The version-bump contract: members enqueue against a snapshot, an
    append publishes a new segment set before dispatch — the leader must
    SPLIT the batch (every member re-executes individually under its own
    scopes), never run the stale fused snapshot."""
    ctx = _make_ctx(result_cache_entries=0)
    ctx.serve.fusion.window_ms = 400.0
    ds_old = ctx.catalog.get("ev")
    q1, q2 = (
        query_from_druid(_GROUPBY),
        query_from_druid(_TOPN),
    )
    outcomes = {}

    def member(i, q):
        outcomes[i] = ctx.serve.fusion.execute(ctx, q, ds_old)

    threads = [
        threading.Thread(target=member, args=(i, q))
        for i, q in enumerate((q1, q2))
    ]
    for t in threads:
        t.start()
    time.sleep(0.1)  # both inside the 400ms window
    ctx.append_rows(
        "ev", [{"city": "NY", "kind": "a", "v": 7, "t": 0}]
    )
    for t in threads:
        t.join(timeout=120)
    # the batch was invalidated: every member told to re-execute
    # individually (None), and the scheduler counted the split
    assert outcomes[0] is None and outcomes[1] is None
    assert ctx.serve.fusion.to_dict()["invalidated"] == 1
    # the append is visible to the very next query (serial path)
    ctx.serve.fusion.window_ms = 0.0
    df = ctx.sql(
        "SELECT sum(v) AS s FROM ev WHERE city = 'NY' AND kind = 'a'"
    )
    ds_now = ctx.catalog.get("ev")
    assert ds_now.version > ds_old.version


def test_fused_batch_without_append_executes_fused():
    """Positive control for the invalidation test: same two-member direct
    enqueue WITHOUT an append executes fused and demuxes per member."""
    ctx = _make_ctx(result_cache_entries=0)
    ctx.serve.fusion.window_ms = 200.0
    ds = ctx.catalog.get("ev")
    q1, q2 = query_from_druid(_GROUPBY), query_from_druid(_TOPN)
    want1, want2 = ctx.engine.execute(q1, ds), ctx.engine.execute(q2, ds)
    outcomes = {}

    def member(i, q):
        outcomes[i] = ctx.serve.fusion.execute(ctx, q, ds)

    threads = [
        threading.Thread(target=member, args=(i, q))
        for i, q in enumerate((q1, q2))
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    assert outcomes[0] is not None and outcomes[1] is not None
    df1, _s1, m1 = outcomes[0]
    df2, _s2, m2 = outcomes[1]
    pdt.assert_frame_equal(
        df1.reset_index(drop=True), want1.reset_index(drop=True)
    )
    pdt.assert_frame_equal(
        df2.reset_index(drop=True), want2.reset_index(drop=True)
    )
    assert m1.fused_batch == 2 and m2.fused_batch == 2


def test_fused_native_route_over_http():
    """Concurrent identical-datasource native dashboard queries through
    the server fuse into shared dispatches and answer correctly."""
    ctx = _make_ctx(result_cache_entries=0, fusion_window_ms=50.0)
    srv = OlapServer(ctx, port=0).start()
    try:
        want_status, want, _ = _post(srv.port, "/druid/v2", _GROUPBY)
        assert want_status == 200
        results = {}

        def run(i):
            spec = dict(_GROUPBY, context={"queryId": f"fused-{i}"})
            results[i] = _post(srv.port, "/druid/v2", spec)

        threads = [
            threading.Thread(target=run, args=(i,)) for i in range(4)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        for i, (code, body, headers) in results.items():
            assert code == 200
            assert body == want
            assert headers["X-Druid-Query-Id"] == f"fused-{i}"
        assert ctx.serve.fusion.to_dict()["members_fused"] >= 2
    finally:
        srv.shutdown()


# ---------------------------------------------------------------------------
# delta-aware result cache
# ---------------------------------------------------------------------------


def _span_names(tree):
    names = [tree["name"]]
    for c in tree.get("children", ()):
        names += _span_names(c)
    return names


def test_result_cache_exact_hit_serves_with_zero_device_dispatch():
    ctx = _make_ctx()
    q = "SELECT city, sum(v) AS s FROM ev GROUP BY city ORDER BY city"
    first = ctx.sql(q)
    second = ctx.sql(q)
    pdt.assert_frame_equal(first, second)
    assert ctx.last_metrics.strategy == "result-cache"
    # the hit's span tree shows NO device work: no segment dispatch, no
    # h2d, no device fetch (the acceptance-criteria span contract)
    names = _span_names(ctx.tracer.last.to_dict()["spans"])
    assert "segment_dispatch" not in names
    assert "device_fetch" not in names
    assert "h2d" not in names


def test_append_serves_cached_historical_plus_delta():
    ctx = _make_ctx()
    q = "SELECT city, sum(v) AS s, count(*) AS c FROM ev GROUP BY city ORDER BY city"
    base = ctx.sql(q)
    ctx.sql(q)  # exact hit
    assert ctx.last_metrics.strategy == "result-cache"
    rows = [
        {"city": "NY", "kind": "a", "v": 5, "t": 0},
        {"city": "SF", "kind": "b", "v": 11, "t": DAY},
    ]
    ctx.append_rows("ev", rows)
    got = ctx.sql(q)
    m = ctx.last_metrics
    assert m.strategy == "result-cache-delta"
    # appends only cost the delta: the refresh scanned the 2 appended
    # rows, not the 4000-row history
    assert m.rows_scanned == 2
    want = base.copy()
    want.loc[want.city == "NY", "s"] += 5
    want.loc[want.city == "NY", "c"] += 1
    want.loc[want.city == "SF", "s"] += 11
    want.loc[want.city == "SF", "c"] += 1
    pdt.assert_frame_equal(
        got.reset_index(drop=True), want.reset_index(drop=True),
        check_dtype=False,
    )
    # the refreshed entry is version-exact again: next lookup is a hit
    ctx.sql(q)
    assert ctx.last_metrics.strategy == "result-cache"


def test_delta_reuse_survives_repeated_appends():
    ctx = _make_ctx()
    q = "SELECT kind, sum(v) AS s FROM ev GROUP BY kind ORDER BY kind"
    ctx.sql(q)
    total = 0
    for i in range(3):
        ctx.append_rows(
            "ev", [{"city": "LA", "kind": "a", "v": 10 + i, "t": 0}]
        )
        got = ctx.sql(q)
        assert ctx.last_metrics.strategy == "result-cache-delta"
        assert ctx.last_metrics.rows_scanned == 1
        total += 10 + i
    fresh = ctx.serve.result_cache
    # independent recompute (cache cleared) agrees exactly
    ctx.serve.result_cache.clear()
    want = ctx.sql(q)
    pdt.assert_frame_equal(
        got.reset_index(drop=True), want.reset_index(drop=True)
    )
    assert fresh.to_dict()["delta_hits"] >= 3


def test_novel_dimension_value_append_is_a_full_miss():
    """A dictionary extension remaps the code space: the cached partial
    state indexes the OLD domain and must not be merged — full re-
    execution, correct answer."""
    ctx = _make_ctx()
    q = "SELECT city, sum(v) AS s FROM ev GROUP BY city ORDER BY city"
    ctx.sql(q)
    ctx.append_rows(
        "ev", [{"city": "AUSTIN", "kind": "a", "v": 3, "t": 0}]
    )
    got = ctx.sql(q)
    assert ctx.last_metrics.strategy not in (
        "result-cache", "result-cache-delta"
    )
    assert "AUSTIN" in set(got.city)
    ctx.serve.result_cache.clear()
    want = ctx.sql(q)
    pdt.assert_frame_equal(
        got.reset_index(drop=True), want.reset_index(drop=True)
    )


def test_compaction_retires_uids_and_misses_cleanly():
    ctx = _make_ctx(compaction_min_delta_rows=1)
    q = "SELECT city, sum(v) AS s FROM ev GROUP BY city ORDER BY city"
    ctx.append_rows("ev", [{"city": "NY", "kind": "a", "v": 9, "t": 0}])
    before = ctx.sql(q)
    ctx.compact("ev")  # retires delta + tail uids, bumps the version
    got = ctx.sql(q)
    # retired uids mean the entry no longer covers a subset: full miss
    assert ctx.last_metrics.strategy not in (
        "result-cache", "result-cache-delta"
    )
    pdt.assert_frame_equal(
        got.reset_index(drop=True), before.reset_index(drop=True)
    )


def test_topn_and_timeseries_delta_reuse():
    ctx = _make_ctx()
    topn = (
        "SELECT kind, sum(v) AS s FROM ev GROUP BY kind "
        "ORDER BY s DESC LIMIT 2"
    )
    ctx.sql(topn)
    ctx.append_rows("ev", [{"city": "NY", "kind": "b", "v": 2, "t": 0}])
    got = ctx.sql(topn)
    assert ctx.last_metrics.strategy == "result-cache-delta"
    ctx.serve.result_cache.clear()
    want = ctx.sql(topn)
    pdt.assert_frame_equal(
        got.reset_index(drop=True), want.reset_index(drop=True)
    )


def test_cached_exact_hit_is_never_stamped_partial():
    """ROADMAP 3(d) regression: when the partial collector has triggered
    (a deadline died mid-request) and the answer comes from the result
    cache, the EXACT cached frame must not be stamped partial — the
    trigger describes the aborted execution, not the cached answer."""
    ctx = _make_ctx()
    q = "SELECT city, sum(v) AS s FROM ev GROUP BY city ORDER BY city"
    want = ctx.sql(q)
    with partial_scope(True) as pc:
        pc.trigger("test.deadline")
        got = ctx.sql(q)
    assert ctx.last_metrics.strategy == "result-cache"
    assert ctx.last_metrics.partial is False
    assert "partial" not in got.attrs
    pdt.assert_frame_equal(got, want)


def test_deadline_truncated_delta_refresh_never_caches():
    """Review regression: a delta refresh whose delta scan is cut short
    by the deadline must MISS into full execution — merging truncated
    delta partials with the cached historical state would cache (and
    serve) an incomplete frame as the exact answer at the new version."""
    ctx = _make_ctx()
    q = "SELECT city, sum(v) AS s FROM ev GROUP BY city ORDER BY city"
    ctx.sql(q)  # cache with state at v1
    ctx.append_rows("ev", [{"city": "NY", "kind": "a", "v": 6, "t": 0}])
    with partial_scope(True) as pc:
        pc.trigger("test.mid_delta")  # every checkpoint_partial stops
        got = ctx.sql(q)
    # the refresh declined; the cache holds NO entry at the new version
    # claiming exactness, and the next clean query computes the truth
    clean = ctx.sql(q)
    want = clean.copy()
    pdt.assert_frame_equal(
        clean.reset_index(drop=True), want.reset_index(drop=True)
    )
    ny = clean.loc[clean.city == "NY", "s"].iloc[0]
    ctx.serve.result_cache.clear()
    truth = ctx.sql(q)
    assert ny == truth.loc[truth.city == "NY", "s"].iloc[0]


def test_progressive_sql_respects_open_breaker():
    """Review regression: an open device breaker must not be bypassed by
    asking for a stream — progressive SQL declines and the buffered path
    answers degraded (200), never a 500 off the sick device."""
    ctx = _make_ctx(result_cache_entries=0, breaker_failure_threshold=1)
    srv = OlapServer(ctx, port=0).start()
    try:
        sql = "SELECT city, sum(v) AS s FROM ev GROUP BY city ORDER BY city"
        code, want, _ = _post(srv.port, "/druid/v2/sql", {"query": sql})
        assert code == 200
        injector().arm("device_dispatch", "error")
        _post(srv.port, "/druid/v2/sql", {"query": sql})  # trips breaker
        assert ctx.resilience.breaker_for("device").state == "open"
        injector().disarm()
        qid, ctype, payload = _post_progressive_sql(srv.port, sql)
        assert "ndjson" not in ctype  # declined to stream
        # the degraded (host-fallback) answer is float64 where the
        # device path emits ints: compare numerically, not by dtype
        canon = lambda rows: sorted(  # noqa: E731
            (r["city"], float(r["s"])) for r in rows
        )
        assert canon(payload[0]) == canon(want)
        assert ctx.last_metrics.degraded or (
            ctx.last_metrics.executor == "fallback"
        )
    finally:
        injector().disarm()
        srv.shutdown()


def test_non_fusable_native_shapes_cache_frame_only():
    """Review regression: a native groupBy the sparse/adaptive tiers
    claim (not fusable) still caches frame-only — identical refreshes
    hit version-exact; an append is a clean full miss (no state)."""
    ctx = _make_ctx()
    srv = OlapServer(ctx, port=0).start()
    try:
        # force non-fusable by making the engine decline fusion
        orig = ctx.engine.fusable
        ctx.engine.fusable = lambda q, ds: False
        code, first, _ = _post(srv.port, "/druid/v2", _GROUPBY)
        assert code == 200
        code, second, _ = _post(srv.port, "/druid/v2", _GROUPBY)
        assert second == first
        assert ctx.last_metrics.strategy == "result-cache"
        ctx.append_rows("ev", [{"city": "NY", "kind": "a", "v": 1, "t": 0}])
        code, third, _ = _post(srv.port, "/druid/v2", _GROUPBY)
        # no state -> full miss, fresh execution, correct answer
        assert ctx.last_metrics.strategy not in (
            "result-cache", "result-cache-delta"
        )
        ctx.engine.fusable = orig
    finally:
        srv.shutdown()


def test_store_noops_while_cache_disabled():
    """Review regression: with result_cache_entries=0 the native path
    must not retain latent entries the next config flip would serve."""
    ctx = _make_ctx(result_cache_entries=0)
    srv = OlapServer(ctx, port=0).start()
    try:
        _post(srv.port, "/druid/v2", _GROUPBY)
        assert len(ctx.serve.result_cache) == 0
        ctx.config.result_cache_entries = 8
        _post(srv.port, "/druid/v2", _GROUPBY)  # miss: nothing latent
        assert ctx.last_metrics.strategy not in ("result-cache",)
    finally:
        srv.shutdown()


def test_result_cache_write_carries_snapshot_version():
    """The entry's version is the EXECUTED snapshot's stamped version —
    an append racing the write reads as a version mismatch (delta
    refresh), never as false freshness."""
    ctx = _make_ctx()
    q = "SELECT count(*) AS n FROM ev"
    ctx.sql(q)
    entry = next(iter(ctx.serve.result_cache._cache.values()))
    assert entry.version == ctx.catalog.get("ev").version
    assert entry.uids == frozenset(
        s.uid for s in ctx.catalog.get("ev").segments
    )


def test_native_route_cache_hit_and_delta_over_http():
    """The wire route rides the serving core too: an identical native
    dashboard refresh is a version-exact hit whose span tree shows NO
    device work, and after an in-domain append the refresh scans only
    the delta (strategy result-cache-delta)."""
    ctx = _make_ctx()
    srv = OlapServer(ctx, port=0).start()
    try:
        spec = dict(_GROUPBY, context={"queryId": "n-warm"})
        code, first, _ = _post(srv.port, "/druid/v2", spec)
        assert code == 200
        code, second, _ = _post(
            srv.port, "/druid/v2", dict(_GROUPBY, context={"queryId": "n-hit"})
        )
        assert code == 200 and second == first
        assert ctx.last_metrics.strategy == "result-cache"
        tr = _get(srv.port, "/druid/v2/trace/n-hit")
        names = _span_names(tr["spans"])
        assert "segment_dispatch" not in names
        assert "device_fetch" not in names
        # in-domain append -> delta-aware refresh on the wire
        code, ack, _ = _post(
            srv.port, "/druid/v2/ingest/ev",
            {"rows": [{"city": "NY", "kind": "a", "v": 4, "t": 0}]},
        )
        assert code == 200 and ack["appended"] == 1
        code, third, _ = _post(srv.port, "/druid/v2", _GROUPBY)
        assert code == 200
        assert ctx.last_metrics.strategy == "result-cache-delta"
        assert ctx.last_metrics.rows_scanned == 1
        ny = next(r["event"] for r in third if r["event"]["city"] == "NY")
        ny_before = next(
            r["event"] for r in first if r["event"]["city"] == "NY"
        )
        assert ny["s"] == ny_before["s"] + 4
        assert ny["n"] == ny_before["n"] + 1
    finally:
        srv.shutdown()


def test_native_execution_after_cache_hit_stamps_fresh_metrics():
    """Regression: a cache hit pins its own QueryMetrics as the
    context's most-recent; a LATER native execution (different query)
    must stamp its own — not leave the stale "result-cache" object
    misattributing the new work."""
    ctx = _make_ctx()
    srv = OlapServer(ctx, port=0).start()
    try:
        _post(srv.port, "/druid/v2", _GROUPBY)
        _post(srv.port, "/druid/v2", _GROUPBY)  # hit: pins result-cache
        assert ctx.last_metrics.strategy == "result-cache"
        _post(srv.port, "/druid/v2", _TOPN)  # different query: executes
        m = ctx.last_metrics
        assert m.strategy != "result-cache"
        assert m.rows_scanned > 0
    finally:
        srv.shutdown()


# ---------------------------------------------------------------------------
# priority lanes
# ---------------------------------------------------------------------------


def test_lane_classification_by_type_and_rows():
    from spark_druid_olap_tpu.serve.lanes import (
        LANE_HEAVY, LANE_INTERACTIVE, classify_native,
    )

    ctx = _make_ctx()
    ds = ctx.catalog.get("ev")
    cfg = ctx.config
    cfg.lane_heavy_rows = 100  # everything over 100 rows is heavy
    assert classify_native(query_from_druid(_TOPN), ds, cfg) == (
        LANE_INTERACTIVE
    )
    assert classify_native(query_from_druid(_TIMESERIES), ds, cfg) == (
        LANE_INTERACTIVE
    )
    assert classify_native(query_from_druid(_GROUPBY), ds, cfg) == (
        LANE_HEAVY
    )
    scan = query_from_druid(
        {
            "queryType": "scan", "dataSource": "ev",
            "columns": ["city", "v"],
            "intervals": ["1970-01-01T00:00:00Z/1970-01-08T00:00:00Z"],
        }
    )
    assert classify_native(scan, ds, cfg) == LANE_HEAVY
    cfg.lane_heavy_rows = 1 << 30  # raise the bar: all interactive
    assert classify_native(scan, ds, cfg) == LANE_INTERACTIVE


def test_fast_lane_unaffected_by_saturated_heavy_lane():
    """The starvation contract: with the heavy lane pinned full by slow
    scans, interactive TopN queries keep answering; surplus heavy
    queries 503 naming their lane."""
    ctx = _make_ctx(
        result_cache_entries=0,
        lane_heavy_slots=1,
        lane_heavy_rows=100,
        admission_queue_timeout_ms=200,
    )
    srv = OlapServer(ctx, port=0).start()
    try:
        # scans hit the scan-loop checkpoint; a delay armed there makes
        # ONLY heavy queries slow (the fused/groupby loops never fire it)
        injector().arm("engine.scan_loop", "delay", delay_ms=150.0)
        scan = {
            "queryType": "scan", "dataSource": "ev",
            "columns": ["city", "v"],
            "intervals": ["1970-01-01T00:00:00Z/1970-01-08T00:00:00Z"],
        }
        heavy_results = {}

        def heavy(i):
            heavy_results[i] = _post(srv.port, "/druid/v2", scan)

        heavy_threads = [
            threading.Thread(target=heavy, args=(i,)) for i in range(3)
        ]
        for t in heavy_threads:
            t.start()
        time.sleep(0.05)  # let the scans occupy/queue the heavy lane
        t0 = time.perf_counter()
        code, body, headers = _post(srv.port, "/druid/v2", _TOPN)
        fast_ms = (time.perf_counter() - t0) * 1e3
        assert code == 200
        for t in heavy_threads:
            t.join(timeout=120)
        codes = sorted(c for c, _, _ in heavy_results.values())
        assert codes[0] == 200  # one scan held the lane slot
        assert 503 in codes  # surplus scans rejected per lane
        rejected = next(
            b for c, b, _ in heavy_results.values() if c == 503
        )
        assert "heavy lane" in rejected["error"]
        rej_headers = next(
            h for c, _, h in heavy_results.values() if c == 503
        )
        assert int(rej_headers["Retry-After"]) >= 1
        health = _get(srv.port, "/status/health")
        assert set(health["lanes"]) == {"interactive", "heavy"}
    finally:
        injector().disarm()
        srv.shutdown()


def test_lane_metrics_exposed():
    ctx = _make_ctx(lane_heavy_rows=100)
    srv = OlapServer(ctx, port=0).start()
    try:
        _post(srv.port, "/druid/v2", _TOPN)
        _post(srv.port, "/druid/v2", _GROUPBY)
        with urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/status/metrics", timeout=30
        ) as r:
            text = r.read().decode()
        assert 'sdol_lane_decisions_total{lane="interactive"' in text
        assert 'sdol_lane_decisions_total{lane="heavy"' in text
        assert 'sdol_lane_slots_in_use{lane="interactive"}' in text
        assert 'sdol_lane_queue_depth{lane="heavy"}' in text
    finally:
        srv.shutdown()


def test_sql_lane_classification_goes_heavy_for_big_scans():
    ctx = _make_ctx(lane_heavy_rows=100)
    assert ctx.serve.lane_for_sql("SELECT * FROM ev") == "heavy"
    assert (
        ctx.serve.lane_for_sql(
            "SELECT kind, sum(v) AS s FROM ev GROUP BY kind "
            "ORDER BY s DESC LIMIT 2"
        )
        == "interactive"
    )
    # commands and garbage classify interactive, never raise
    assert ctx.serve.lane_for_sql("SET result_cache_entries = 8") == (
        "interactive"
    )
    assert ctx.serve.lane_for_sql("not even sql") == "interactive"


# ---------------------------------------------------------------------------
# progressive SQL surface (ROADMAP 3(b))
# ---------------------------------------------------------------------------


def _post_progressive_sql(port, sql, timeout=120):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/druid/v2/sql",
        data=json.dumps(
            {"query": sql, "context": {"progressive": True}}
        ).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        ctype = r.headers.get("Content-Type", "")
        qid = r.headers.get("X-Druid-Query-Id")
        raw = r.read().decode()
    if "ndjson" not in ctype:
        return qid, ctype, [json.loads(raw)]
    return qid, ctype, [json.loads(x) for x in raw.strip().splitlines()]


def test_progressive_sql_refinements_converge_to_exact():
    """Mirror of the native route's convergence test on /druid/v2/sql:
    NDJSON refinements with monotone coverage whose FINAL line equals
    the buffered SQL response exactly."""
    ctx = _make_ctx(result_cache_entries=0)
    srv = OlapServer(ctx, port=0).start()
    try:
        sql = (
            "SELECT city, sum(v) AS s, count(*) AS c FROM ev "
            "GROUP BY city ORDER BY city"
        )
        code, buffered, _ = _post(srv.port, "/druid/v2/sql", {"query": sql})
        assert code == 200
        qid, ctype, lines = _post_progressive_sql(srv.port, sql)
        assert qid
        assert "ndjson" in ctype
        assert len(lines) >= 2, "multiple refinements expected"
        covs = [l["coverage"] for l in lines]
        assert all(a <= b + 1e-9 for a, b in zip(covs, covs[1:]))
        last = lines[-1]
        assert last["final"] is True
        assert last["coverage"] == 1.0
        assert last["partial"] is False
        assert last["result"] == buffered
        # stream_flush spans recorded per refinement, same as native
        tr = _get(srv.port, f"/druid/v2/trace/{qid}")

        def count(node, name):
            return (node["name"] == name) + sum(
                count(c, name) for c in node.get("children", ())
            )

        assert count(tr["spans"], "stream_flush") == len(lines)
    finally:
        srv.shutdown()


def test_progressive_sql_falls_back_to_buffered_for_non_streamable():
    """Shapes the progressive surface cannot stream (scans, commands,
    fallback-bound SQL) answer buffered — one JSON body, not NDJSON."""
    ctx = _make_ctx(result_cache_entries=0)
    srv = OlapServer(ctx, port=0).start()
    try:
        qid, ctype, payload = _post_progressive_sql(
            srv.port, "SELECT city, v FROM ev LIMIT 5"
        )
        assert "ndjson" not in ctype
        assert isinstance(payload[0], list) and len(payload[0]) == 5
    finally:
        srv.shutdown()


def test_progressive_sql_post_processing_matches_buffered():
    """HAVING + post-expressions run per refinement through the SAME
    host post-processing as the buffered path (no drift)."""
    ctx = _make_ctx(result_cache_entries=0)
    srv = OlapServer(ctx, port=0).start()
    try:
        sql = (
            "SELECT city, sum(v) AS s, sum(v) / count(*) AS avg_v "
            "FROM ev GROUP BY city HAVING count(*) > 10 ORDER BY city"
        )
        code, buffered, _ = _post(srv.port, "/druid/v2/sql", {"query": sql})
        assert code == 200
        _, ctype, lines = _post_progressive_sql(srv.port, sql)
        assert "ndjson" in ctype
        assert lines[-1]["result"] == buffered
    finally:
        srv.shutdown()
