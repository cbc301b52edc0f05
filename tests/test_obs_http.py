"""HTTP observability surface (ISSUE 4 satellites): X-Druid-Query-Id
echo + context.queryId passthrough, the trace ring endpoint (span trees
whose phase durations sum to ≈ total_ms), Prometheus exposition at
/status/metrics with monotonic counters, trace ring eviction, the
structured access log, and concurrent-query span-tree isolation."""

import json
import logging
import re
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

import spark_druid_olap_tpu as sd
from spark_druid_olap_tpu.config import SessionConfig
from spark_druid_olap_tpu.server import OlapServer


def _make_ctx(**overrides):
    cfg = SessionConfig.load_calibrated()
    cfg.result_cache_entries = 0
    for k, v in overrides.items():
        setattr(cfg, k, v)
    ctx = sd.TPUOlapContext(cfg)
    rng = np.random.default_rng(5)
    n = 3_000
    ctx.register_table(
        "ev",
        {
            "city": rng.choice(
                np.array(["NY", "SF", "LA"], dtype=object), n
            ),
            "v": rng.random(n).astype(np.float32),
        },
        dimensions=["city"],
        metrics=["v"],
    )
    return ctx


@pytest.fixture()
def srv():
    ctx = _make_ctx()
    server = OlapServer(ctx, port=0).start()
    try:
        yield ctx, server
    finally:
        server.shutdown()


def _get(port, path):
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}{path}", timeout=30
    ) as r:
        return r.status, r.read(), dict(r.headers)


def _get_json(port, path):
    code, body, headers = _get(port, path)
    return code, json.loads(body), headers


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


_SQL = {"query": "SELECT city, sum(v) AS s FROM ev GROUP BY city"}


def _get_trace(port, qid, tries=200):
    """Fetch a trace, tolerating the benign registration race: the ring
    put happens a hair after the response bytes land (same shape as the
    admission-slot release in test_server_resilience)."""
    import time

    for _ in range(tries):
        code, body, _ = _get_json_allow_error(
            port, f"/druid/v2/trace/{qid}"
        )
        if code == 200:
            return body
        time.sleep(0.01)
    raise AssertionError(f"trace {qid!r} never appeared")


# ---------------------------------------------------------------------------
# query_id end-to-end
# ---------------------------------------------------------------------------


def test_context_query_id_passthrough_and_echo(srv):
    ctx, server = srv
    code, rows, headers = _post(
        server.port, "/druid/v2/sql",
        {**_SQL, "context": {"queryId": "dash-42"}},
    )
    assert code == 200
    assert headers["X-Druid-Query-Id"] == "dash-42"
    # the id reached the engine: last_metrics carries it
    assert ctx.last_metrics.query_id == "dash-42"


def test_generated_query_id_when_client_sets_none(srv):
    ctx, server = srv
    code, rows, h1 = _post(server.port, "/druid/v2/sql", _SQL)
    assert code == 200
    qid1 = h1["X-Druid-Query-Id"]
    assert qid1
    code, rows, h2 = _post(server.port, "/druid/v2/sql", _SQL)
    assert h2["X-Druid-Query-Id"] != qid1  # fresh id per request


def test_native_query_id_echo_and_error_responses_carry_id(srv):
    ctx, server = srv
    native = {
        "queryType": "groupBy",
        "dataSource": "ev",
        "granularity": "all",
        "dimensions": [{"type": "default", "dimension": "city"}],
        "aggregations": [{"type": "count", "name": "n"}],
        "context": {"queryId": "native-7"},
    }
    code, body, headers = _post(server.port, "/druid/v2", native)
    assert code == 200
    assert headers["X-Druid-Query-Id"] == "native-7"
    # a client error still echoes the id (Druid parity: errors correlate)
    bad = {**native, "dataSource": "nope", "context": {"queryId": "bad-1"}}
    code, body, headers = _post(server.port, "/druid/v2", bad)
    assert code == 400
    assert headers["X-Druid-Query-Id"] == "bad-1"


# ---------------------------------------------------------------------------
# Trace endpoint + acceptance: phase durations sum ≈ total_ms
# ---------------------------------------------------------------------------


def test_trace_endpoint_returns_span_tree_with_phase_sums(srv):
    ctx, server = srv
    code, rows, headers = _post(
        server.port, "/druid/v2/sql",
        {**_SQL, "context": {"queryId": "traced-1"}},
    )
    assert code == 200
    trace = _get_trace(server.port, "traced-1")
    assert trace["query_id"] == "traced-1"
    root = trace["spans"]
    assert root["name"] == "query"
    total = trace["total_ms"]
    assert total > 0
    names = [c["name"] for c in root["children"]]
    assert "admission" in names and "plan" in names and "execute" in names
    # contiguous top-level phases: their durations sum to ≈ total_ms
    # (never more; the gaps between spans are microseconds of glue)
    phase_sum = sum(c["duration_ms"] for c in root["children"])
    assert phase_sum <= total * 1.01 + 0.5
    assert phase_sum >= total * 0.5
    # the execute phase contains the engine spans
    # (under `engine`, the span around the call into it: ISSUE 37)
    execute = next(c for c in root["children"] if c["name"] == "execute")
    engine = next(c for c in execute["children"] if c["name"] == "engine")
    inner = {c["name"] for c in engine.get("children", ())}
    assert "segment_dispatch" in inner or "lower" in inner


def test_trace_endpoint_404_for_unknown_id(srv):
    ctx, server = srv
    code, body, _ = _get_json_allow_error(server.port, "/druid/v2/trace/nope")
    assert code == 404
    assert body["errorClass"] == "NotFound"


def _get_json_allow_error(port, path):
    try:
        code, body, _ = _get(port, path)
        return code, json.loads(body), _
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read()), dict(e.headers)


def test_trace_ring_eviction_over_http():
    ctx = _make_ctx(trace_ring_capacity=2)
    server = OlapServer(ctx, port=0).start()
    try:
        for qid in ("r1", "r2", "r3"):
            code, _, _ = _post(
                server.port, "/druid/v2/sql",
                {**_SQL, "context": {"queryId": qid}},
            )
            assert code == 200
        # wait for the LAST trace to register (ring put trails the
        # response bytes by a hair), then r1 must be the evicted one
        for qid in ("r2", "r3"):
            assert _get_trace(server.port, qid)["query_id"] == qid
        code, _, _ = _get_json_allow_error(
            server.port, "/druid/v2/trace/r1"
        )
        assert code == 404  # evicted (capacity 2, FIFO)
    finally:
        server.shutdown()


# ---------------------------------------------------------------------------
# Prometheus exposition
# ---------------------------------------------------------------------------

_SAMPLE_RE = re.compile(r"^([a-zA-Z_:][a-zA-Z0-9_:]*(?:\{[^}]*\})?) (\S+)$")


def _scrape(port):
    code, body, headers = _get(port, "/status/metrics")
    assert code == 200
    assert headers["Content-Type"].startswith("text/plain")
    text = body.decode()
    samples = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = _SAMPLE_RE.match(line)
        assert m, f"unparseable exposition line: {line!r}"
        samples[m.group(1)] = float(m.group(2))
    return text, samples


def test_status_metrics_parses_and_counters_never_decrease(srv):
    ctx, server = srv
    _post(server.port, "/druid/v2/sql", _SQL)
    text1, s1 = _scrape(server.port)
    assert any(k.startswith("sdol_queries_total") for k in s1)
    assert "# TYPE sdol_queries_total counter" in text1
    assert "# TYPE sdol_query_phase_ms histogram" in text1
    for _ in range(3):
        assert _post(server.port, "/druid/v2/sql", _SQL)[0] == 200
    text2, s2 = _scrape(server.port)
    # monotonicity: every counter/histogram sample present in scrape 1
    # is >= in scrape 2 (gauges may move either way)
    for key, v1 in s1.items():
        name = key.split("{")[0]
        if name.endswith(("_total", "_bucket", "_count", "_sum")):
            assert s2.get(key, 0) >= v1, key
    # and the query counter visibly incremented
    qkey = next(
        k for k in s2
        if k.startswith("sdol_queries_total") and 'outcome="ok"' in k
        and 'executor="device"' in k and 'query_type="groupBy"' in k
    )
    assert s2[qkey] >= s1.get(qkey, 0) + 3
    # the http counter covers the serving surface itself
    assert any(k.startswith("sdol_http_requests_total") for k in s2)


def test_status_folds_registry_summary(srv):
    ctx, server = srv
    _post(server.port, "/druid/v2/sql", _SQL)
    code, st, _ = _get_json(server.port, "/status")
    assert code == 200
    metrics = st["metrics"]
    assert metrics["sdol_queries_total"]["type"] == "counter"
    phase = metrics["sdol_query_phase_ms"]
    assert phase["type"] == "histogram"
    total = phase["values"]["total"]
    assert total["count"] >= 1 and total["p50"] is not None


# ---------------------------------------------------------------------------
# Access log (ISSUE 4 satellite: structured DEBUG replaces the silence)
# ---------------------------------------------------------------------------


def test_access_log_structured_at_debug(srv, caplog):
    ctx, server = srv
    with caplog.at_level(
        logging.DEBUG, logger="spark_druid_olap_tpu.server"
    ):
        code, _, headers = _post(
            server.port, "/druid/v2/sql",
            {**_SQL, "context": {"queryId": "logged-1"}},
        )
        assert code == 200
    msgs = [r.getMessage() for r in caplog.records]
    access = [m for m in msgs if m.startswith("access ")]
    assert access, msgs
    line = next(m for m in access if "query_id=logged-1" in m)
    assert "method=POST" in line
    assert "path=/druid/v2/sql" in line
    assert "status=200" in line
    assert re.search(r"duration_ms=\d+\.\d+", line)


# ---------------------------------------------------------------------------
# Concurrency: span trees stay per-query under a hammer
# ---------------------------------------------------------------------------


def test_concurrent_query_span_trees_do_not_interleave(srv):
    """8 threads, unique queryIds: every trace must contain exactly its
    own query's phases (one admission, one plan, one execute) — a shared
    or leaked contextvar would double spans up or cross-file them."""
    ctx, server = srv
    results = {}
    lock = threading.Lock()

    def hit(i):
        qid = f"conc-{i}"
        r = _post(
            server.port, "/druid/v2/sql",
            {**_SQL, "context": {"queryId": qid}},
        )
        with lock:
            results[qid] = r

    threads = [threading.Thread(target=hit, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert len(results) == 8
    for qid, (code, rows, headers) in results.items():
        assert code == 200, (qid, rows)
        assert headers["X-Druid-Query-Id"] == qid
        trace = _get_trace(server.port, qid)
        assert trace["query_id"] == qid
        names = [c["name"] for c in trace["spans"]["children"]]
        # exactly one of each top-level phase: no cross-query bleed
        assert names.count("admission") == 1, (qid, names)
        # the SQL route looks its plan up twice, and both show: the
        # lane classifier (before admission), then ctx.sql
        assert names.count("plan") == 2, (qid, names)
        assert names.count("execute") == 1, (qid, names)


# ---------------------------------------------------------------------------
# The whole served request in one tree (ISSUE 25)
# ---------------------------------------------------------------------------


def _spans(node):
    yield node
    for c in node.get("children", ()):
        yield from _spans(c)


def test_served_request_tree_covers_front_end_to_response(srv):
    """A served SQL request from the handler's first line to the buffered
    answer: body read, both plan lookups (the first one parses), the cost
    model's choices, the program lookup, the answer's encoding — and the
    self times by name add up to the root."""
    ctx, server = srv
    code, rows, _ = _post(
        server.port, "/druid/v2/sql",
        {**_SQL, "context": {"queryId": "tree-1"}},
    )
    assert code == 200 and len(rows) == 3
    doc = _get_trace(server.port, "tree-1")
    root = doc["spans"]
    names = [s["name"] for s in _spans(root)]
    assert {"http_read", "sql_parse", "route", "program_lookup",
            "respond", "lane", "admission", "plan", "execute"} <= set(names)
    top = [c["name"] for c in root["children"]]
    # `http_accept` (this connection's first request) starts the root,
    # the body read follows; the answer is encoded last, inside the tree
    assert top[:2] == ["http_accept", "http_read"] and top[-1] == "respond"
    assert root["children"][0]["start_ms"] == 0.0
    plans = [c for c in root["children"] if c["name"] == "plan"]
    assert [p["attrs"]["cache_hit"] for p in plans] == [False, True]
    assert [c["name"] for c in plans[0]["children"]] == ["sql_parse", "route"]
    rc = doc["receipt"]
    assert rc["wall_ms"] == doc["total_ms"]
    assert sum(v["self_ms"] for v in rc["spans"].values()) == pytest.approx(
        rc["wall_ms"], abs=0.001 * len(rc["spans"])
    )
    # what the request's QueryMetrics holds is that receipt, not an
    # earlier provisional one
    assert ctx.last_metrics.receipt == rc
    # a repeat hits the plan cache both times and parses nothing
    _post(server.port, "/druid/v2/sql",
          {**_SQL, "context": {"queryId": "tree-2"}})
    again = [s["name"] for s in _spans(_get_trace(server.port, "tree-2")["spans"])]
    assert "sql_parse" not in again and again.count("plan") == 2


def test_native_request_tree_has_read_and_respond(srv):
    _, server = srv
    code, _, _ = _post(
        server.port, "/druid/v2",
        {
            "queryType": "groupBy", "dataSource": "ev",
            "dimensions": ["city"], "granularity": "all",
            "aggregations": [
                {"type": "doubleSum", "name": "s", "fieldName": "v"}
            ],
            "context": {"queryId": "tree-native"},
        },
    )
    assert code == 200
    root = _get_trace(server.port, "tree-native")["spans"]
    top = [c["name"] for c in root["children"]]
    assert top[:2] == ["http_accept", "http_read"] and top[-1] == "respond"
    names = [s["name"] for s in _spans(root)]
    assert "program_lookup" in names and "engine" in names
