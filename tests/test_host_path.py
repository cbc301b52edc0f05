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
    # eight time-sorted segments, so that the zone maps have what to prune
    ssb.register(
        ctx, tables=ssb.gen_tables(scale=0.01, seed=7), rows_per_segment=8192
    )
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


def _count_calls(monkeypatch, name):
    """Count the calls of `exec.engine.<name>` (every caller looks it up
    on the module when it calls)."""
    calls = []
    real = getattr(engine_mod, name)

    def counted(*args, **kwargs):
        calls.append(1)
        return real(*args, **kwargs)

    monkeypatch.setattr(engine_mod, name, counted)
    return calls


def _scope_spans(doc):
    return [s for s in _walk(doc["spans"]) if s["name"] == "scope"]


@pytest.mark.parametrize("query", [
    "q1_1", "q1_2", "q1_3", "q2_1", "q2_2", "q2_3", "q3_1", "q3_2", "q3_3",
    "q3_4", "q4_1", "q4_2", "q4_3",
])
def test_served_request_counts_its_scope_walks(served, monkeypatch, query):
    """A served request walks the segments' zone maps ONCE (ISSUE 38):
    `spans["scope"]["n"]` is 1 whoever asked, the span's `asks` is the
    number of `segments_in_scope` calls the request made (the lane
    classifier's and the engine's at least); with `http_accept` adopted
    the root starts at accept and the self times still add up to
    `wall_ms`."""
    ctx, srv, queries = served
    asks = _count_calls(monkeypatch, "segments_in_scope")
    walks = _count_calls(monkeypatch, "_walk_segments")
    conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=120)
    try:
        _post(conn, queries[query], "walks-" + query)
    finally:
        conn.close()
    doc = ctx.tracer.ring.get("walks-" + query)
    rc = doc["receipt"]
    assert rc["spans"]["scope"]["n"] == len(walks) == 1
    (scope,) = _scope_spans(doc)
    assert scope["attrs"]["asks"] == len(asks) >= 2
    assert 0 <= scope["attrs"]["kept"] <= scope["attrs"]["segments"] == 8
    # the classifier asked first: the one walk lies under the root, in
    # front of the engine
    assert scope in doc["spans"]["children"]
    first, second = doc["spans"]["children"][:2]
    assert (first["name"], second["name"]) == ("http_accept", "http_read")
    assert first["start_ms"] == 0.0  # the root starts at accept
    accepted_end = first["start_ms"] + first["duration_ms"]
    assert accepted_end <= second["start_ms"] + 0.001
    assert rc["spans"]["http_accept"]["n"] == 1
    assert {"engine", "post_process"} <= set(rc["spans"])
    engine = next(s for s in _walk(doc["spans"]) if s["name"] == "engine")
    assert engine["attrs"]["backend"] in ("device", "mesh")
    # the engine scanned what the walk kept
    m = ctx.last_metrics
    assert m.segments == scope["attrs"]["kept"]
    _self_times_add_up(doc)


def test_nothing_of_a_scope_outlives_its_request(served, monkeypatch):
    """Two requests on one connection, the same query: each walks once
    and has its own `scope` span; the closed trace holds no scope."""
    ctx, srv, queries = served
    walks = _count_calls(monkeypatch, "_walk_segments")
    conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=120)
    try:
        _post(conn, queries["q1_1"], "twice-1")
        assert ctx.tracer.last.scopes == []
        _post(conn, queries["q1_1"], "twice-2")
    finally:
        conn.close()
    assert len(walks) == 2
    docs = [ctx.tracer.ring.get(f"twice-{i}") for i in (1, 2)]
    for doc in docs:
        assert doc["receipt"]["spans"]["scope"]["n"] == 1
        (scope,) = _scope_spans(doc)
        assert scope["attrs"]["asks"] >= 2
    assert ctx.tracer.last.query_id == "twice-2"
    assert ctx.tracer.last.scopes == []


@pytest.fixture()
def appendable():
    cfg = SessionConfig()
    cfg.prefer_distributed = False
    cfg.result_cache_entries = 0
    ctx = sd.TPUOlapContext(cfg)
    n = 4_096
    ctx.register_table(
        "ap_t",
        {
            "k": np.array(["x", "y"], dtype=object)[np.arange(n) % 2],
            "v": np.ones(n, np.float32),
            "t": (np.arange(n) * 1_000).astype(np.int64),
        },
        dimensions=["k"], metrics=["v"], time_column="t",
        rows_per_segment=1_024,
    )
    srv = OlapServer(ctx, port=0).start()
    try:
        yield ctx, srv
    finally:
        srv.shutdown()


def test_request_after_an_append_walks_the_new_segment_set(appendable):
    """A streamed append publishes a new `DataSource`: the next request
    walks it anew and its scope holds the appended segment; a scope held
    for the old object never answers for the new one, inside one trace
    either."""
    from spark_druid_olap_tpu.exec.engine import segments_in_scope

    ctx, srv = appendable
    sql = "SELECT k, sum(v) AS s FROM ap_t WHERE k = 'x' GROUP BY k"

    def ask(qid):
        conn = http.client.HTTPConnection("127.0.0.1", srv.port, timeout=120)
        try:
            body = _post(conn, sql, qid)
        finally:
            conn.close()
        (scope,) = _scope_spans(ctx.tracer.ring.get(qid))
        return body, scope["attrs"]

    body, before = ask("ap-1")
    assert (before["segments"], before["kept"]) == (4, 4)
    assert body[0]["s"] == 2_048.0
    old = ctx.catalog.get("ap_t")
    ack = ctx.append_rows(
        "ap_t", [{"k": "x", "v": 5.0, "t": 5_000_000}] * 3
    )
    new = ctx.catalog.get("ap_t")
    assert new is not old and new.version == ack["datasourceVersion"]
    body, after = ask("ap-2")
    assert (after["segments"], after["kept"]) == (5, 5)
    assert body[0]["s"] == 2_048.0 + 15.0
    # one trace, two DataSource objects of one name: two walks
    q = ctx.plan_sql(sql).query
    with ctx.tracer.query_trace(query_id="ap-3") as tr:
        assert len(segments_in_scope(q, old)) == 4
        assert len(segments_in_scope(q, new)) == 5
        assert len(segments_in_scope(q, old)) == 4
        assert [w.attrs["asks"] for *_, w in tr.scopes] == [2, 1]
    assert tr.receipt["spans"]["scope"]["n"] == 2


def test_held_scope_is_keyed_by_value_and_handed_out_as_a_copy(served):
    """Inside one trace: an equal filter built anew shares the walk,
    another filter or other intervals walk for themselves, an explicit
    `segs=` never asks, and no caller can alter what the next one gets."""
    import dataclasses

    from spark_druid_olap_tpu.exec.engine import segments_in_scope

    ctx, _, queries = served
    q = ctx.plan_sql(queries["q1_1"]).query
    ds = ctx.catalog.get(q.datasource)
    same = dataclasses.replace(
        q, filter=dataclasses.replace(q.filter), limit_spec=None
    )
    other = ctx.plan_sql(queries["q1_2"]).query
    assert same.filter is not q.filter and same.filter == q.filter
    with ctx.tracer.query_trace(query_id="held") as tr:
        first = segments_in_scope(q, ds)
        kept = list(first)
        first.clear()  # a caller that filters its list in place
        assert segments_in_scope(same, ds) == kept
        assert segments_in_scope(other, ds) != kept
        assert segments_in_scope(
            dataclasses.replace(q, intervals=((0, 1),)), ds
        ) == []
        ctx.engine._partials_for_query(q, ds, segs=kept[:1])
        assert [w.attrs["asks"] for *_, w in tr.scopes] == [2, 1, 1]
    assert tr.receipt["spans"]["scope"]["n"] == 3
    assert tr.scopes == []


def test_fused_batch_walks_once_a_filter(served):
    """A fused batch resolves every member's scope inside the leader's
    request: members of one filter share a walk, a member of another
    filter walks for itself."""
    ctx, _, queries = served
    qa = ctx.plan_sql(queries["q1_1"]).query
    qb = ctx.plan_sql(queries["q1_2"]).query
    ds = ctx.catalog.get(qa.datasource)
    with ctx.tracer.query_trace(query_id="fused-walks") as tr:
        out = ctx.engine.execute_fused([qa, qb, qa], ds)
    assert len(out) == 3
    assert out[0][2].segments != out[1][2].segments
    scopes = [
        s for s in _walk(tr.to_dict()["spans"]) if s["name"] == "scope"
    ]
    assert [s["attrs"]["asks"] for s in scopes] == [2, 1]
    assert [s["attrs"]["kept"] for s in scopes] == [
        out[0][2].segments, out[1][2].segments,
    ]
    assert tr.receipt["spans"]["scope"]["n"] == 2


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
