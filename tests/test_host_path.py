"""The host path named from inside (ISSUE 37): the `scope`, `engine`,
`post_process` and `http_accept` spans, and the receipt's `phases` and
`close_ms`.  Times are asserted under the injectable clock or as
identities of one tree, never as wall time."""

import http.client
import json
import os

import numpy as np
import pytest

import spark_druid_olap_tpu as sd
from spark_druid_olap_tpu.config import SessionConfig
from spark_druid_olap_tpu.exec import engine as engine_mod
from spark_druid_olap_tpu.obs import (
    SPAN_DEVICE_FETCH,
    SPAN_ENGINE,
    SPAN_EXECUTE,
    SPAN_HTTP_ACCEPT,
    SPAN_NAMES,
    SPAN_PLAN,
    SPAN_POST_PROCESS,
    SPAN_SCOPE,
    SPAN_SEGMENT_DISPATCH,
    Tracer,
    span,
)
from spark_druid_olap_tpu.server import OlapServer


class TickClock:
    """Each call returns the next tick and counts itself."""

    def __init__(self, step=1.0):
        self.t = 0.0
        self.step = step
        self.calls = 0

    def __call__(self):
        self.calls += 1
        v = self.t
        self.t += self.step
        return v


def _walk(node):
    yield node
    for c in node.get("children", ()):
        yield from _walk(c)


ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("name, value", [
    (SPAN_SCOPE, "scope"), (SPAN_ENGINE, "engine"),
    (SPAN_POST_PROCESS, "post_process"), (SPAN_HTTP_ACCEPT, "http_accept"),
])
def test_new_span_names_are_registered(name, value):
    assert name == value and name in SPAN_NAMES


def test_new_spans_pass_the_span_discipline_lint():
    """Every `span(...)` and `early_span(...)` of the instrumented
    modules, the new ones among them, names a registered constant."""
    from tools.graftlint import run_lint

    res = run_lint(ROOT, ["spark_druid_olap_tpu"],
                   pass_names=["span-discipline"])
    assert res.new == [], "\n".join(f.render() for f in res.new)


# ---------------------------------------------------------------------------
# A served SSB-shaped request
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def served():
    from spark_druid_olap_tpu.workloads import ssb

    cfg = SessionConfig.load_calibrated()
    cfg.result_cache_entries = 0  # every request executes
    ctx = sd.TPUOlapContext(cfg)
    ssb.register(ctx, tables=ssb.gen_tables(scale=0.01, seed=7))
    srv = OlapServer(ctx, port=0).start()
    try:
        yield ctx, srv, ssb.QUERIES
    finally:
        srv.shutdown()


def _post(conn, sql, qid):
    conn.request(
        "POST", "/druid/v2/sql",
        json.dumps({"query": sql, "context": {"queryId": qid}}),
        {"Content-Type": "application/json"},
    )
    resp = conn.getresponse()
    body = resp.read()
    assert resp.status == 200, body
    return json.loads(body)


def _self_times_add_up(doc):
    rc = doc["receipt"]
    assert sum(v["self_ms"] for v in rc["spans"].values()) == pytest.approx(
        rc["wall_ms"], abs=0.001 * sum(v["n"] for v in rc["spans"].values())
    )


@pytest.mark.parametrize("query", ["q1_1", "q2_1", "q4_3"])
def test_served_request_counts_its_scope_walks(served, monkeypatch, query):
    """`spans["scope"]["n"]` is the number of `segments_in_scope` calls
    the request made, whoever made them; with `http_accept` adopted the
    root starts at accept and the self times still add up to `wall_ms`."""
    ctx, srv, queries = served
    calls = []
    real = engine_mod.segments_in_scope
    monkeypatch.setattr(
        engine_mod, "segments_in_scope",
        lambda q, ds: calls.append(1) or real(q, ds),
    )
    conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=120)
    try:
        _post(conn, queries[query], "walks-" + query)
    finally:
        conn.close()
    doc = ctx.tracer.ring.get("walks-" + query)
    rc = doc["receipt"]
    assert len(calls) >= 2  # the lane classifier's and the engine's
    assert rc["spans"]["scope"]["n"] == len(calls)
    scopes = [s for s in _walk(doc["spans"]) if s["name"] == "scope"]
    assert all(
        0 < s["attrs"]["kept"] <= s["attrs"]["segments"] for s in scopes
    )
    first, second = doc["spans"]["children"][:2]
    assert (first["name"], second["name"]) == ("http_accept", "http_read")
    assert first["start_ms"] == 0.0  # the root starts at accept
    accepted_end = first["start_ms"] + first["duration_ms"]
    assert accepted_end <= second["start_ms"] + 0.001
    assert rc["spans"]["http_accept"]["n"] == 1
    assert {"engine", "post_process"} <= set(rc["spans"])
    engine = next(s for s in _walk(doc["spans"]) if s["name"] == "engine")
    assert engine["attrs"]["backend"] in ("device", "mesh")
    _self_times_add_up(doc)


def test_kept_alive_connection_has_one_http_accept(served):
    """Only a connection's first request began at `accept()`: the second
    on a kept-alive connection has no `http_accept`, and neither has a
    POST that follows a GET; no stamp is left behind."""
    ctx, srv, queries = served
    conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=120)
    try:
        _post(conn, queries["q1_1"], "ka-1")
        _post(conn, queries["q1_1"], "ka-2")
    finally:
        conn.close()
    first = ctx.tracer.ring.get("ka-1")
    second = ctx.tracer.ring.get("ka-2")
    assert first["spans"]["children"][0]["name"] == "http_accept"
    assert "http_accept" not in second["receipt"]["spans"]
    assert second["spans"]["children"][0]["name"] == "http_read"
    _self_times_add_up(second)
    # a stale stamp would back-date the second root over the idle
    # connection: its wall is its own
    assert second["receipt"]["wall_ms"] < first["receipt"]["wall_ms"] + 1e3
    conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=120)
    try:
        conn.request("GET", "/status/health")
        conn.getresponse().read()
        _post(conn, queries["q1_1"], "ka-3")
    finally:
        conn.close()
    assert "http_accept" not in ctx.tracer.ring.get("ka-3")["receipt"]["spans"]
    assert srv.httpd.accepted_at == {}


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def test_phases_exact_under_injected_clock():
    """root start -> first launch span's start -> last `device_fetch`'s
    end -> root end; a later launch with no fetch after it ends the
    flight itself."""
    clk = TickClock(step=1.0)
    tracer = Tracer(clock=clk)
    with tracer.query_trace(query_id="q-ph") as tr:  # root: tick 0
        with span(SPAN_PLAN):  # 1, 2
            pass
        with span(SPAN_EXECUTE):  # 3 ... 12
            with span(SPAN_SEGMENT_DISPATCH):  # 4, 5
                pass
            with span(SPAN_SEGMENT_DISPATCH):  # 6, 7
                pass
            with span(SPAN_DEVICE_FETCH):  # 8, 9
                pass
            with span(SPAN_POST_PROCESS):  # 10, 11
                pass
    rc = tr.receipt  # root end: tick 13
    assert rc["phases"] == {
        "pre_launch_ms": 4_000.0, "in_flight_ms": 5_000.0,
        "post_fetch_ms": 4_000.0,
    }
    assert sum(rc["phases"].values()) == rc["wall_ms"] == 13_000.0
    assert rc["dispatch_count"] == 2
    # `close_ms`: the tracer's close, two reads of the same clock after
    # the root has ended
    assert rc["close_ms"] == 1_000.0
    assert tracer.ring.get("q-ph")["receipt"]["close_ms"] == 1_000.0

    with tracer.query_trace(query_id="q-nofetch") as tr:
        t0 = tr.root.start
        with span(SPAN_SEGMENT_DISPATCH):
            pass
    rc = tr.receipt
    assert rc["phases"] == {
        "pre_launch_ms": 1_000.0, "in_flight_ms": 1_000.0,
        "post_fetch_ms": 1_000.0,
    }
    assert tr.root.end - t0 == 3.0


@pytest.fixture(scope="module")
def segmented():
    cfg = SessionConfig()
    cfg.prefer_distributed = False
    ctx = sd.TPUOlapContext(cfg)
    rng = np.random.default_rng(37)
    n = 8_192
    ctx.register_table(
        "ph_t",
        {
            "k": rng.choice(np.array(["x", "y", "z"], dtype=object), n),
            "v": rng.random(n).astype(np.float32),
            "t": (np.arange(n) * 1_000).astype(np.int64),
        },
        dimensions=["k"],
        metrics=["v"],
        time_column="t",
        rows_per_segment=512,
    )
    return ctx


@pytest.mark.parametrize("launches", ["one", "several", "none"])
def test_phases_add_up_to_the_wall(segmented, launches):
    """One launch (the arena's one call), several (per-batch calls under
    a deadline), none (a result-cache hit): the three phases add up to
    `wall_ms`, and their bounds are the tree's own spans'."""
    from spark_druid_olap_tpu.resilience import deadline_scope

    ctx = segmented
    sql = f"SELECT k, sum(v) AS s_{launches} FROM ph_t GROUP BY k"
    ctx.sql(sql)  # compiles; fills the result cache
    if launches == "none":
        ctx.sql(sql)
    else:
        entries = ctx.config.result_cache_entries
        ctx.config.result_cache_entries = 0
        try:
            if launches == "several":
                with deadline_scope(60_000):
                    ctx.sql(sql)
            else:
                ctx.sql(sql)
        finally:
            ctx.config.result_cache_entries = entries
    doc = ctx.tracer.last_trace_dict()
    rc = doc["receipt"]
    ph = rc["phases"]
    assert sum(ph.values()) == pytest.approx(rc["wall_ms"], abs=0.002)
    assert all(v >= 0 for v in ph.values())
    spans = list(_walk(doc["spans"]))
    launched = [s for s in spans if s["name"] == "segment_dispatch"]
    fetched = [s for s in spans if s["name"] == "device_fetch"]
    assert len(launched) == rc["dispatch_count"]
    if launches == "none":
        assert not launched
        assert ph == {"pre_launch_ms": rc["wall_ms"], "in_flight_ms": 0.0,
                      "post_fetch_ms": 0.0}
        return
    assert (len(launched) == 1) == (launches == "one")
    assert ph["pre_launch_ms"] == min(s["start_ms"] for s in launched)
    assert ph["pre_launch_ms"] + ph["in_flight_ms"] == pytest.approx(
        max(s["start_ms"] + s["duration_ms"] for s in fetched), abs=0.002
    )
    assert ph["post_fetch_ms"] > 0  # finalize and post_process lie after
    assert rc["close_ms"] >= 0
