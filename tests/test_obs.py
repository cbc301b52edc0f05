"""Unit coverage for the obs/ observability subsystem (ISSUE 4):
span-tree exactness under an injectable clock, trace ring eviction,
thread isolation of concurrent traces, metrics-registry semantics +
Prometheus exposition, the slow-query log, and the tracer-overhead
budget asserted by COUNTING clock calls (never wall-time)."""

import logging
import threading

import numpy as np
import pytest

import spark_druid_olap_tpu as sd
from spark_druid_olap_tpu.config import SessionConfig
from spark_druid_olap_tpu.obs import (
    SPAN_EXECUTE,
    SPAN_FINALIZE,
    SPAN_PLAN,
    MetricsRegistry,
    Tracer,
    current_query_id,
    get_registry,
    span,
)


class TickClock:
    """Deterministic clock: each call returns the next value and counts
    itself — tracer overhead = call count, not wall time."""

    def __init__(self, step=1.0):
        self.t = 0.0
        self.step = step
        self.calls = 0

    def __call__(self):
        self.calls += 1
        v = self.t
        self.t += self.step
        return v


# ---------------------------------------------------------------------------
# Span trees
# ---------------------------------------------------------------------------


def test_span_tree_exact_under_injected_clock():
    clk = TickClock(step=1.0)  # 1 simulated second per clock read
    tracer = Tracer(clock=clk)
    with tracer.query_trace(query_id="q-1", query_type="unit") as tr:
        with span(SPAN_PLAN):
            pass
        with span(SPAN_EXECUTE):
            with span(SPAN_FINALIZE):
                pass
    d = tr.to_dict()
    assert d["query_id"] == "q-1"
    root = d["spans"]
    assert root["name"] == "query"
    names = [c["name"] for c in root["children"]]
    assert names == ["plan", "execute"]
    execute = root["children"][1]
    assert [c["name"] for c in execute["children"]] == ["finalize"]
    # clock ticks once per read: plan = 1 tick wide, finalize = 1,
    # execute = 3 (start, finalize's 2, end)
    assert root["children"][0]["duration_ms"] == 1000.0
    assert execute["children"][0]["duration_ms"] == 1000.0
    assert execute["duration_ms"] == 3000.0
    # children cover the root minus the one tick between them: the
    # phase-sum ≈ total property the acceptance criteria name
    assert sum(c["duration_ms"] for c in root["children"]) <= d["total_ms"]
    assert d["total_ms"] == root["duration_ms"]


def test_span_outside_trace_is_noop(monkeypatch, ssb_ds):
    with span(SPAN_PLAN) as s:
        assert s is None
    assert current_query_id() == ""
    # the cost without a trace is ONE contextvar read (there is no
    # tracer, so no clock to read), also for a span that lives inside a
    # shared function: the `scope`
    # span of `segments_in_scope` (ISSUE 37), called here as a direct
    # Engine user would
    from spark_druid_olap_tpu.exec.engine import segments_in_scope
    from spark_druid_olap_tpu.models import query as Q
    from spark_druid_olap_tpu.obs import trace as trace_mod

    class CountingVar:
        reads = 0

        def get(self):
            CountingVar.reads += 1
            return None

    monkeypatch.setattr(trace_mod, "_active_trace", CountingVar())
    q = Q.GroupByQuery(datasource="ssb", dimensions=(), aggregations=())
    assert segments_in_scope(q, ssb_ds) == list(ssb_ds.segments)
    assert CountingVar.reads == 1


def test_query_trace_outermost_wins():
    tracer = Tracer()
    with tracer.query_trace(query_id="outer") as t1:
        with tracer.query_trace(query_id="inner") as t2:
            assert t2 is t1
            assert current_query_id() == "outer"
    # only ONE trace landed in the ring
    assert tracer.ring.ids() == ["outer"]


def test_trace_ring_eviction_fifo():
    tracer = Tracer(capacity=2)
    for qid in ("a", "b", "c"):
        with tracer.query_trace(query_id=qid):
            pass
    assert tracer.ring.get("a") is None  # oldest evicted
    assert tracer.ring.get("b") is not None
    assert tracer.ring.get("c") is not None
    assert len(tracer.ring) == 2


def test_concurrent_traces_do_not_interleave():
    """Each thread's spans land in ITS trace only (contextvars give every
    thread an isolated active trace/span)."""
    tracer = Tracer()
    errs = []

    def work(i):
        try:
            with tracer.query_trace(query_id=f"q{i}") as tr:
                for _ in range(5):
                    with span(SPAN_EXECUTE, worker=i):
                        pass
                assert len(tr.root.children) == 5
                assert all(
                    c.attrs.get("worker") == i for c in tr.root.children
                )
        except Exception as e:  # pragma: no cover - surfaced below
            errs.append(e)

    threads = [threading.Thread(target=work, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not errs
    assert len(tracer.ring) == 8
    for i in range(8):
        d = tracer.ring.get(f"q{i}")
        assert len(d["spans"]["children"]) == 5


def test_slow_query_log_renders_span_tree(caplog):
    tracer = Tracer()
    with caplog.at_level(
        logging.WARNING, logger="spark_druid_olap_tpu.obs.trace"
    ):
        with tracer.query_trace(query_id="slow-1", slow_ms=1e-9):
            with span(SPAN_PLAN):
                pass
    msgs = [r.getMessage() for r in caplog.records]
    assert any("slow query slow-1" in m and "plan" in m for m in msgs)
    # under the threshold: silent
    caplog.clear()
    with caplog.at_level(
        logging.WARNING, logger="spark_druid_olap_tpu.obs.trace"
    ):
        with tracer.query_trace(query_id="fast-1", slow_ms=60_000.0):
            pass
    assert not [r for r in caplog.records if "slow query" in r.getMessage()]


# ---------------------------------------------------------------------------
# Metrics registry
# ---------------------------------------------------------------------------


def test_registry_counter_and_labels():
    reg = MetricsRegistry()
    c = reg.counter("t_total", "help", labels=("kind",))
    c.labels(kind="a").inc()
    c.labels(kind="a").inc(2)
    c.labels(kind="b").inc()
    assert c.labels(kind="a").value == 3
    with pytest.raises(ValueError):
        c.labels(kind="a").inc(-1)  # counters only go up
    with pytest.raises(ValueError):
        c.labels(wrong="a")
    # re-registration with the same shape returns the same family
    assert reg.counter("t_total", labels=("kind",)) is c
    with pytest.raises(ValueError):
        reg.gauge("t_total")  # kind mismatch


def test_registry_histogram_quantiles_and_render():
    reg = MetricsRegistry()
    h = reg.histogram("lat_ms", "latency", buckets=(1, 10, 100))
    for v in (0.5, 5, 5, 50):
        h.observe(v)
    child = h.labels()
    assert child.count == 4
    assert child.quantile(0.5) is not None
    assert 1 <= child.quantile(0.5) <= 10
    # past the last bucket clamps to it
    h.observe(1e9)
    assert child.quantile(0.999) == 100
    text = reg.render_prometheus()
    assert "# TYPE lat_ms histogram" in text
    assert 'lat_ms_bucket{le="+Inf"} 5' in text
    assert "lat_ms_count 5" in text


def test_registry_gauge_callback():
    reg = MetricsRegistry()
    g = reg.gauge("depth", "queue depth")
    state = {"v": 3}
    g.set_function(lambda: state["v"])
    assert "depth 3" in reg.render_prometheus()
    state["v"] = 7
    assert "depth 7" in reg.render_prometheus()


def test_prometheus_label_escaping():
    reg = MetricsRegistry()
    c = reg.counter("esc_total", labels=("msg",))
    c.labels(msg='say "hi"\nback\\slash').inc()
    text = reg.render_prometheus()
    assert 'msg="say \\"hi\\"\\nback\\\\slash"' in text


# ---------------------------------------------------------------------------
# Tracer overhead (acceptance: <= 5% on a cached-program SSB query,
# asserted with the injectable clock — by COUNTING, not timing)
# ---------------------------------------------------------------------------


def test_tracer_overhead_on_cached_ssb_query_counted_not_timed():
    from spark_druid_olap_tpu.workloads import ssb

    cfg = SessionConfig.load_calibrated()
    cfg.result_cache_entries = 0  # must execute, not cache-hit
    ctx = sd.TPUOlapContext(cfg)
    ssb.register(ctx, tables=ssb.gen_tables(scale=0.01, seed=7))
    q = ssb.QUERIES["q1_1"]
    ctx.sql(q)  # compile
    ctx.sql(q)  # warm
    assert ctx.last_metrics.program_cache_hit

    clk = TickClock(step=0.0)  # frozen clock: pure call counting
    ctx.tracer = Tracer(clock=clk)
    ctx.sql(q)
    assert ctx.last_metrics.program_cache_hit
    # Every tracer action is a clock read + O(1) bookkeeping; at a very
    # conservative 2us per action (perf_counter + lock + append), the
    # budget for <=5% overhead on a 10ms cached-program SSB query floor
    # is 0.05 * 10ms / 2us = 250 actions.  The deterministic count makes
    # the 5% acceptance bound wall-time-free: N_calls * 2us <= 500us.
    # (ISSUE 25: the count is two reads for each span of the tree and
    # nothing else — the receipt is folded once, from the closed tree,
    # and reads no clock.  On one device that is 22: the root, plan,
    # execute, route x2, lower, program_lookup, h2d, segment_dispatch,
    # device_fetch, finalize; it was 17.  On the eight virtual devices
    # of conftest.py the mesh serves the query with two more since
    # ISSUE 27: its launch and its fetch are a span each.)
    assert 0 < clk.calls <= 250, clk.calls
    # and the instrumentation actually produced the span tree
    d = ctx.tracer.last.to_dict()
    names = {c["name"] for c in d["spans"]["children"]}
    assert {"plan", "execute"} <= names
    n_spans = sum(
        v["n"] for v in d["receipt"]["spans"].values()
    )
    # ISSUE 37: and two for the tracer's own close (`close_ms`), which
    # under the frozen clock reads 0: it is counted on the injected
    # clock like every span, never in wall time
    assert clk.calls == 2 * n_spans + 2, (clk.calls, d["receipt"]["spans"])
    assert d["receipt"]["close_ms"] == 0.0
    assert {"scope", "engine", "post_process"} <= set(d["receipt"]["spans"])


def test_engine_publishes_into_process_registry():
    before = (
        get_registry()
        .counter(
            "sdol_queries_total",
            labels=("query_type", "executor", "outcome"),
        )
        .snapshot()
    )
    ctx = sd.TPUOlapContext()
    rng = np.random.default_rng(3)
    ctx.register_table(
        "obs_t",
        {
            "k": rng.choice(np.array(["x", "y"], dtype=object), 500),
            "v": rng.random(500).astype(np.float32),
        },
        dimensions=["k"],
        metrics=["v"],
    )
    ctx.sql("SELECT k, sum(v) AS s FROM obs_t GROUP BY k")
    after = (
        get_registry()
        .counter(
            "sdol_queries_total",
            labels=("query_type", "executor", "outcome"),
        )
        .snapshot()
    )
    key = "groupBy,device,ok"
    assert after.get(key, 0) >= before.get(key, 0) + 1
    # the query_id on the metrics snapshot matches the trace ring entry
    m = ctx.last_metrics
    assert m.query_id
    assert ctx.tracer.ring.get(m.query_id) is not None


# ---------------------------------------------------------------------------
# Span events + exemplars (ISSUE 5 obs satellites)
# ---------------------------------------------------------------------------


def test_span_event_attaches_to_active_span():
    from spark_druid_olap_tpu.obs import span_event

    clk = TickClock(step=1.0)
    tracer = Tracer(clock=clk)
    with tracer.query_trace(query_id="q-ev") as tr:
        with span(SPAN_EXECUTE):
            span_event("breaker_state", state="open", trips=2)
    d = tr.to_dict()
    execute = d["spans"]["children"][0]
    assert execute["name"] == "execute"
    events = execute["events"]
    assert len(events) == 1
    assert events[0]["name"] == "breaker_state"
    assert events[0]["attrs"] == {"state": "open", "trips": 2}
    # the event timestamp is trace-relative, inside the span
    assert 0 <= events[0]["at_ms"] <= d["total_ms"]
    # events show up in the rendered tree (slow-query log body)
    assert "@ breaker_state" in tr.render()


def test_span_event_outside_trace_is_noop():
    from spark_druid_olap_tpu.obs import span_event

    span_event("breaker_state", state="open")  # must not raise


def test_histogram_exemplars_link_buckets_to_trace_ids():
    reg = MetricsRegistry()
    h = reg.histogram("t_ms", "test", buckets=(10.0, 100.0))
    h.observe(5.0, exemplar="qid-fast")
    h.observe(50.0, exemplar="qid-mid")
    h.observe(5000.0, exemplar="qid-slow")
    h.observe(2.0)  # no exemplar: must not clobber qid-fast
    text = reg.render_prometheus()
    assert '# exemplar t_ms_bucket{le="10"} trace_id="qid-fast"' in text
    assert '# exemplar t_ms_bucket{le="100"} trace_id="qid-mid"' in text
    assert '# exemplar t_ms_bucket{le="+Inf"} trace_id="qid-slow"' in text
    # comment lines must not break a scrape: every non-comment line
    # still parses as `name{labels} value`
    for line in text.splitlines():
        if line.startswith("#"):
            continue
        assert len(line.rsplit(" ", 1)) == 2, line
    ex = reg.to_dict()["t_ms"]["values"][""]["exemplars"]
    assert ex["10"]["trace_id"] == "qid-fast"
    assert ex["100"]["trace_id"] == "qid-mid"
    assert ex["+Inf"]["trace_id"] == "qid-slow"
    # a newer observation in the same bucket takes the slot over
    h.observe(6.0, exemplar="qid-faster")
    ex = reg.to_dict()["t_ms"]["values"][""]["exemplars"]
    assert ex["10"]["trace_id"] == "qid-faster"


def test_query_metrics_publish_exemplars_and_obs_dump_renders_them():
    import tools.obs_dump as obs_dump

    ctx = sd.TPUOlapContext()
    rng = np.random.default_rng(5)
    ctx.register_table(
        "obs_ex",
        {
            "k": rng.choice(np.array(["x", "y"], dtype=object), 400),
            "v": rng.random(400).astype(np.float32),
        },
        dimensions=["k"],
        metrics=["v"],
    )
    ctx.sql("SELECT k, sum(v) AS s FROM obs_ex GROUP BY k")
    qid = ctx.last_metrics.query_id
    assert qid
    fam = get_registry().to_dict()["sdol_query_phase_ms"]
    total = fam["values"].get("total", {})
    exemplars = total.get("exemplars", {})
    assert any(e["trace_id"] == qid for e in exemplars.values())
    # the exposition carries the link as a comment
    text = get_registry().render_prometheus()
    assert f'trace_id="{qid}"' in text
    # and obs_dump renders the /status-shaped doc's exemplar table
    rendered = obs_dump.dump({"metrics": get_registry().to_dict()})
    assert "histogram exemplars" in rendered
    assert qid in rendered


def test_degraded_trace_records_breaker_state_event():
    """ROADMAP obs follow-up (c): a degraded-path trace must SAY why the
    fallback was chosen — the breaker state observed at routing time
    rides on the `degraded` span as an event."""
    from spark_druid_olap_tpu.resilience import injector

    cfg = SessionConfig.load_calibrated()
    cfg.result_cache_entries = 0
    cfg.retry_backoff_ms = 1.0
    ctx = sd.TPUOlapContext(cfg)
    rng = np.random.default_rng(9)
    ctx.register_table(
        "obs_deg",
        {
            "k": rng.choice(np.array(["x", "y"], dtype=object), 400),
            "v": rng.random(400).astype(np.float32),
        },
        dimensions=["k"],
        metrics=["v"],
    )
    try:
        injector().arm("device_dispatch", "error")
        ctx.sql("SELECT k, sum(v) AS s FROM obs_deg GROUP BY k")
    finally:
        injector().disarm()
    assert ctx.last_metrics.degraded

    def find_spans(node, name, out):
        if node.get("name") == name:
            out.append(node)
        for c in node.get("children", ()):
            find_spans(c, name, out)
        return out

    degraded = find_spans(ctx.tracer.last.to_dict()["spans"], "degraded", [])
    assert degraded, "degraded span missing from the trace"
    events = [
        e for s in degraded for e in s.get("events", ())
        if e["name"] == "breaker_state"
    ]
    assert events, "breaker_state event missing from the degraded span"
    attrs = events[0]["attrs"]
    assert attrs["state"] in ("closed", "open", "half_open")
    assert "consecutive_failures" in attrs and "trips" in attrs


# -- self-hosted telemetry: the __sys datasource (ISSUE 19) -------------------


def _telemetry_ctx(tmp_path):
    ctx = sd.TPUOlapContext(SessionConfig(storage_dir=str(tmp_path)))
    rng = np.random.default_rng(7)
    n = 1500
    t0 = int(np.datetime64("2023-01-01", "ms").astype(np.int64))
    ctx.register_table(
        "ev",
        {
            "city": rng.choice(
                np.array(["austin", "boston"], dtype=object), n
            ),
            "qty": rng.integers(1, 100, n).astype(np.int64),
            "ts": np.full(n, t0, dtype=np.int64),
        },
        dimensions=["city"], metrics=["qty"], time_column="ts",
    )
    return ctx


def test_sys_sampler_registers_and_appends_through_ingest(tmp_path):
    from spark_druid_olap_tpu.obs.telemetry import SYS_TABLE

    ctx = _telemetry_ctx(tmp_path)
    ctx.sql("SELECT count(*) FROM ev")
    s = ctx.start_sys_sampler(interval_s=60)
    try:
        n1 = s.sample_once()
        assert n1 > 0
        ds = ctx.catalog.get(SYS_TABLE)
        assert ds is not None
        assert ds.rollup_granularity == "second"
        n2 = s.sample_once()
        assert n2 > 0 and s.status()["ticks"] == 2
        assert s.status()["errors"] == 0
        # the second tick went through the ingest tier (WAL-journaled),
        # not a re-registration
        assert ctx.catalog.get(SYS_TABLE).num_rows >= n1
    finally:
        ctx.stop_sys_sampler()


def test_sys_select_returns_qps_and_latency_history_under_churn(
    tmp_path,
):
    """The ISSUE 19 acceptance cell: with the sampler running and
    appends churning the store, a SELECT over __sys returns QPS and
    latency history end-to-end."""
    ctx = _telemetry_ctx(tmp_path)
    s = ctx.start_sys_sampler(interval_s=60)
    rng = np.random.default_rng(11)
    t0 = int(np.datetime64("2023-01-02", "ms").astype(np.int64))
    try:
        for i in range(3):
            # append churn: user-table ingests interleave the ticks
            ctx.append_rows("ev", {
                "city": np.array(["austin"] * 50, dtype=object),
                "qty": rng.integers(1, 9, 50).astype(np.int64),
                "ts": np.full(50, t0 + i, dtype=np.int64),
            })
            ctx.sql(f"SELECT city, sum(qty) FROM ev GROUP BY city "
                    f"LIMIT {40 + i}")
            assert s.sample_once() > 0
        # QPS history: the per-tick delta of the query counter
        qps = ctx.sql(
            "SELECT sum(delta) AS d, max(value) AS total FROM __sys "
            "WHERE metric = 'sdol_queries_total'"
        )
        assert qps["total"].iloc[0] >= 3
        assert qps["d"].iloc[0] >= 2  # ticks after the first see deltas
        # latency history: phase p99 rows flattened from the histogram
        lat = ctx.sql(
            "SELECT labels, max(value) AS p99 FROM __sys "
            "WHERE metric = 'sdol_query_phase_ms_p99' GROUP BY labels"
        )
        assert len(lat) >= 1 and (lat["p99"] >= 0).all()
        # ingest history proves the churn itself is observable too
        ing = ctx.sql(
            "SELECT max(value) AS v FROM __sys "
            "WHERE metric = 'sdol_ingest_rows_total' "
            "AND labels LIKE '%ev%'"
        )
        assert ing["v"].iloc[0] >= 150
        st = s.status()
        assert st["errors"] == 0 and st["rows_appended"] > 0
    finally:
        ctx.stop_sys_sampler()


def test_sys_sampler_series_cap_and_fault_isolation(tmp_path):
    ctx = _telemetry_ctx(tmp_path)
    ctx.sql("SELECT count(*) FROM ev")
    s = ctx.start_sys_sampler(interval_s=60)
    try:
        s.max_series = 5  # force the cardinality guard
        assert s.sample_once() == 5
        assert s.status()["rows_dropped"] > 0
        # a failing append is fault-isolated: the tick logs and counts,
        # the loop (and the process) never dies
        orig = ctx.ingest.append_rows
        ctx.ingest.append_rows = lambda *a, **k: (_ for _ in ()).throw(
            RuntimeError("boom")
        )
        try:
            assert s.sample_once() == 0
        finally:
            ctx.ingest.append_rows = orig
        st = s.status()
        assert st["errors"] == 1 and "boom" in st["last_error"]
        assert s.sample_once() > 0  # next tick proceeds
    finally:
        ctx.stop_sys_sampler()


def test_sys_retention_drops_aged_rollup_segments(tmp_path):
    """config.sys_retention_s (ISSUE 20 satellite): aged second-
    granularity `__sys` segments are dropped whole by the compaction
    sweep — telemetry is a ring, not a leak — and the drop persists
    through the storage tier's rename-then-GC commit."""
    import time as _time

    from spark_druid_olap_tpu.obs.telemetry import SYS_TABLE

    ctx = sd.TPUOlapContext(
        SessionConfig(storage_dir=str(tmp_path), sys_retention_s=3600.0)
    )
    assert ctx.compactor.sys_retention_s == 3600.0  # config plumbed
    rng = np.random.default_rng(7)
    ctx.register_table(
        "ev",
        {
            "city": np.array(["austin"] * 50, dtype=object),
            "qty": rng.integers(1, 9, 50).astype(np.int64),
        },
        dimensions=["city"], metrics=["qty"],
    )
    ctx.sql("SELECT count(*) FROM ev")
    s = ctx.start_sys_sampler(interval_s=60)
    try:
        assert s.sample_once() > 0
        assert s.sample_once() > 0
    finally:
        ctx.stop_sys_sampler()

    # unfolded delta ticks are NEVER age-dropped (recovery would
    # resurrect them from the WAL), even against a far-future horizon —
    # only the registration-seed historical segment may age out here
    far_future = int(_time.time() * 1e3) + 10**10
    ctx.compactor.retire_aged(SYS_TABLE, 3600.0, now_ms=far_future)
    ds0 = ctx.catalog.get(SYS_TABLE)
    assert ds0.delta_segments() and ds0.delta_rows > 0

    ctx.compact(SYS_TABLE)  # fold ticks into historical segments
    ds = ctx.catalog.get(SYS_TABLE)
    assert ds.num_rows > 0 and ds.historical_segments()

    # a generous horizon with fresh data drops nothing (run_pending ride)
    assert ctx.compactor.run_pending() == []
    v0 = ctx.catalog.get(SYS_TABLE).version

    # against the far-future clock every historical segment is aged out
    res = ctx.compactor.retire_aged(SYS_TABLE, 3600.0, now_ms=far_future)
    assert res["dropped_segments"] >= 1
    ds2 = ctx.catalog.get(SYS_TABLE)
    assert ds2.num_rows == 0 and ds2.version > v0

    # the drop is durable: a restarted node does not resurrect the ring
    ctx2 = sd.TPUOlapContext(SessionConfig(storage_dir=str(tmp_path)))
    sys_ds = ctx2.catalog.get(SYS_TABLE)
    assert sys_ds is None or sys_ds.num_rows == 0
    # and the user table is untouched by the telemetry sweep
    got = ctx2.sql("SELECT count(*) AS c FROM ev")
    assert int(got["c"].iloc[0]) == 50


# ---------------------------------------------------------------------------
# One span tree for the whole request, self time by name, the profiler's
# clock, device scopes (ISSUE 25)
# ---------------------------------------------------------------------------


def _walk(node):
    yield node
    for c in node.get("children", ()):
        yield from _walk(c)


def test_receipt_self_times_by_span_name_add_up_to_the_root():
    """`receipt["spans"]`: every span's duration less its children's, by
    name; under the counting clock the sum is the root's duration exactly."""
    clk = TickClock(step=1.0)
    tracer = Tracer(clock=clk)
    with tracer.query_trace(query_id="q-self") as tr:
        with span(SPAN_PLAN):
            with span(SPAN_PLAN):  # a name met twice, nested
                pass
        with span(SPAN_EXECUTE):
            with span(SPAN_FINALIZE):
                pass
            with span(SPAN_FINALIZE):
                pass
    rc = tr.receipt
    spans = rc["spans"]
    assert {k: v["n"] for k, v in spans.items()} == {
        "query": 1, "plan": 2, "execute": 1, "finalize": 2,
    }
    assert sum(v["self_ms"] for v in spans.values()) == rc["wall_ms"]
    assert rc["wall_ms"] == tr.total_ms == 11_000.0
    # execute: 5 ticks long, two children of one tick each
    assert spans["execute"]["self_ms"] == 3_000.0
    assert spans["finalize"]["self_ms"] == 2_000.0
    # the root's own time is what the buckets call unattributed
    assert spans["query"]["self_ms"] == rc["unattributed_ms"]


def test_early_span_is_adopted_and_back_dates_the_root():
    """Work done before the trace could open (the server's body read)
    becomes the root's first child, and the root starts with it."""
    from spark_druid_olap_tpu.obs import SPAN_HTTP_READ

    clk = TickClock(step=1.0)
    tracer = Tracer(clock=clk)
    with tracer.early_span(SPAN_HTTP_READ) as read:  # ticks 0, 1
        pass
    clk()  # a tick between the read and the trace's opening
    with tracer.query_trace(query_id="q-early", early=[read]) as tr:
        with span(SPAN_PLAN):
            pass
    d = tr.to_dict()
    root = d["spans"]
    assert [c["name"] for c in root["children"]] == ["http_read", "plan"]
    assert root["children"][0]["start_ms"] == 0.0
    assert root["children"][0]["duration_ms"] == 1_000.0
    # root: from the read's start (tick 0) to the close (tick 6)
    assert d["total_ms"] == 6_000.0
    spans = tr.receipt["spans"]
    assert sum(v["self_ms"] for v in spans.values()) == 6_000.0
    assert spans["http_read"] == {"n": 1, "self_ms": 1_000.0}


def test_two_early_spans_are_adopted_in_their_order_in_time():
    """The server's pair (ISSUE 37): `http_accept`, made after the fact
    from a stamp of the tracer's clock, then `http_read`; the root adopts
    them in the order given and starts with the first."""
    from spark_druid_olap_tpu.obs import SPAN_HTTP_ACCEPT, SPAN_HTTP_READ

    clk = TickClock(step=1.0)
    tracer = Tracer(clock=clk)
    stamp = clk()  # tick 0: the server's `get_request`
    clk()  # tick 1: thread start, header parse
    with tracer.early_span(SPAN_HTTP_ACCEPT, start=stamp) as accepted:
        pass  # ends at tick 2, and read no clock to start
    with tracer.early_span(SPAN_HTTP_READ) as read:  # ticks 3, 4
        pass
    with tracer.query_trace(
        query_id="q-two", early=[accepted, read]
    ) as tr:  # root's own start: tick 5, back-dated to 0
        with span(SPAN_PLAN):  # 6, 7
            pass
    root = tr.to_dict()["spans"]  # root end: tick 8
    assert [(c["name"], c["start_ms"], c["duration_ms"])
            for c in root["children"]] == [
        ("http_accept", 0.0, 2_000.0), ("http_read", 3_000.0, 1_000.0),
        ("plan", 6_000.0, 1_000.0),
    ]
    assert tr.total_ms == 8_000.0
    spans = tr.receipt["spans"]
    assert sum(v["self_ms"] for v in spans.values()) == 8_000.0
    assert spans["http_accept"] == {"n": 1, "self_ms": 2_000.0}
    # nothing launched: the whole wall lies before any device work
    assert tr.receipt["phases"] == {
        "pre_launch_ms": 8_000.0, "in_flight_ms": 0.0, "post_fetch_ms": 0.0,
    }


def test_receipt_is_built_once_and_is_the_closed_traces(monkeypatch):
    """An unsampled query folds its span tree into a receipt once, at
    trace close; QueryMetrics, the result frame and the ring's doc hold
    that one receipt."""
    from spark_druid_olap_tpu.obs import prof

    ctx = sd.TPUOlapContext()
    rng = np.random.default_rng(11)
    ctx.register_table(
        "once_t",
        {
            "k": rng.choice(np.array(["x", "y"], dtype=object), 400),
            "v": rng.random(400).astype(np.float32),
        },
        dimensions=["k"],
        metrics=["v"],
    )
    built = []
    real = prof.build_receipt
    monkeypatch.setattr(
        prof, "build_receipt",
        lambda doc, scope=None: built.append(1) or real(doc, scope),
    )
    df = ctx.sql("SELECT k, sum(v) AS s FROM once_t GROUP BY k")
    assert built == [1]
    doc = ctx.tracer.last_trace_dict()
    rc = doc["receipt"]
    assert ctx.last_metrics.receipt is rc and df.attrs["receipt"] is rc
    assert rc["wall_ms"] == doc["total_ms"]
    assert {"plan", "sql_parse", "route", "execute", "lower",
            "program_lookup", "device_fetch", "finalize"} <= set(rc["spans"])
    assert sum(v["self_ms"] for v in rc["spans"].values()) == pytest.approx(
        rc["wall_ms"], abs=0.001 * len(rc["spans"])
    )


def test_program_lookup_span_names_family_and_marks_a_miss():
    cfg = SessionConfig()
    cfg.result_cache_entries = 0  # the repeat must execute, not cache-hit
    ctx = sd.TPUOlapContext(cfg)
    rng = np.random.default_rng(12)
    ctx.register_table(
        "pl_t",
        {
            "k": rng.choice(np.array(["x", "y", "z"], dtype=object), 300),
            "v": rng.random(300).astype(np.float32),
        },
        dimensions=["k"],
        metrics=["v"],
    )
    lookups = []
    for _ in range(2):
        ctx.sql("SELECT k, sum(v) AS s FROM pl_t GROUP BY k")
        lookups.append([
            s.get("attrs", {})
            for s in _walk(ctx.tracer.last_trace_dict()["spans"])
            if s["name"] == "program_lookup"
        ])
    cold, warm = lookups
    assert cold and all(a.get("compile") for a in cold)
    assert warm and not any(a.get("compile") for a in warm)
    assert {a["family"] for a in warm} == {a["family"] for a in cold}
    # the plan span says whether the text hit the plan cache
    plans = [
        s for s in _walk(ctx.tracer.last_trace_dict()["spans"])
        if s["name"] == "plan"
    ]
    assert plans and all(p["attrs"]["cache_hit"] for p in plans)


def test_arena_dispatch_span_names_its_form():
    """The arena's `segment_dispatch` says which form ran: one call that
    makes and flushes its own carry on the served default ("whole"), a
    threaded carry over per-batch calls under a deadline ("chunk"); a
    one-batch scope never takes the arena and carries no `form`."""
    from spark_druid_olap_tpu.resilience import deadline_scope

    cfg = SessionConfig()
    cfg.result_cache_entries = 0
    cfg.prefer_distributed = False
    ctx = sd.TPUOlapContext(cfg)
    rng = np.random.default_rng(33)
    n = 8_192
    ctx.register_table(
        "fm_t",
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

    def dispatches(sqlq):
        ctx.sql(sqlq)
        return [
            s["attrs"]
            for s in _walk(ctx.tracer.last_trace_dict()["spans"])
            if s["name"] == "segment_dispatch"
        ]

    sqlq = "SELECT k, sum(v) AS s FROM fm_t GROUP BY k"
    whole = dispatches(sqlq)
    assert [a["form"] for a in whole] == ["whole"]
    assert whole[0]["chunk"] == "1/1" and whole[0]["arena"] == 16
    with deadline_scope(60_000):
        chunked = dispatches(sqlq)
    assert len(chunked) > 1
    assert {a["form"] for a in chunked} == {"chunk"}
    assert sum(a["arena"] for a in chunked) == 16
    one_batch = dispatches(
        "SELECT k, sum(v) AS s FROM fm_t "
        "WHERE __time < TIMESTAMP '1970-01-01 00:00:01' GROUP BY k"
    )
    assert one_batch and not any("form" in a for a in one_batch)


def test_adaptive_spans_kept_set_and_phases():
    """The adaptive tier in the tree: `adaptive_kept` around the kept-set
    work with the phase-A probes under it, phase B's dispatch marked; a
    repeat recalls the kept set and probes nothing."""
    from spark_druid_olap_tpu.catalog.segment import (
        DimensionDict,
        build_datasource,
    )
    from spark_druid_olap_tpu.exec.engine import Engine
    from spark_druid_olap_tpu.models.aggregations import DoubleSum
    from spark_druid_olap_tpu.models.dimensions import DimensionSpec
    from spark_druid_olap_tpu.models.query import GroupByQuery

    rng = np.random.default_rng(4)
    n = 30_000
    # 400 x 400 dictionary domain, 12 x 9 codes populated: no filter pins
    # a dimension, so the kept sets have to be MEASURED (phase A runs)
    ds = build_datasource(
        "adspan",
        {
            "a": rng.integers(0, 12, size=n),
            "b": rng.integers(0, 9, size=n),
            "v": rng.random(n).astype(np.float32),
        },
        dimension_cols=["a", "b"],
        metric_cols=["v"],
        rows_per_segment=n // 3,
        dicts={
            "a": DimensionDict(values=tuple(range(400))),
            "b": DimensionDict(values=tuple(range(400))),
        },
    )
    q = GroupByQuery(
        datasource="adspan",
        dimensions=(DimensionSpec("a"), DimensionSpec("b")),
        aggregations=(DoubleSum("s", "v"),),
    )
    eng = Engine(strategy="adaptive")
    tracer = Tracer()
    docs = []
    for i in range(2):
        with tracer.query_trace(query_id=f"ad-{i}") as tr:
            df = eng.execute(q, ds)
        assert eng.last_metrics.strategy == "adaptive" and len(df) == 108
        docs.append(tr.to_dict())
    first, repeat = ([s for s in _walk(d["spans"])] for d in docs)

    def named(spans, name):
        return [s for s in spans if s["name"] == name]

    kept = named(first, "adaptive_kept")
    assert len(kept) == 1
    assert kept[0]["attrs"]["source"] == "measured"
    assert kept[0]["attrs"]["compact_groups"] == 108
    # codes 0..11 and 0..8 of 400: one run of kept codes a dim
    assert kept[0]["attrs"]["remap"] == ["runs:1", "runs:1"]
    probes = named(kept[0]["children"], "adaptive_probe")
    assert probes and all(p["attrs"]["phase"] == "A" for p in probes)
    phase_b = named(first, "segment_dispatch")
    assert phase_b and all(s["attrs"]["phase"] == "B" for s in phase_b)
    assert named(first, "device_fetch") and named(first, "finalize")
    # phase B's `route` span says which kernel the chooser named for the
    # compacted shape, and G' (PR 30)
    from spark_druid_olap_tpu.plan.cost import shape_kernel

    for spans in (first, repeat):
        routed = [
            s["attrs"] for s in named(spans, "route")
            if (s.get("attrs") or {}).get("tier") == "adaptive"
        ]
        assert routed == [{
            "tier": "adaptive", "groups": 108,
            "kernel": shape_kernel(ds.num_rows, 108, eng.config),
        }]
    # the repeat: kept set from the memo, no probe, phase B again
    kept2 = named(repeat, "adaptive_kept")
    assert kept2[0]["attrs"]["source"] == "memo"
    assert kept2[0]["attrs"]["remap"] == ["runs:1", "runs:1"]
    assert not named(repeat, "adaptive_probe")
    assert all(
        s["attrs"]["phase"] == "B" for s in named(repeat, "segment_dispatch")
    )
    assert docs[1]["receipt"]["spans"]["adaptive_kept"]["n"] == 1
    assert eng.last_metrics.finalize_ms > 0


def test_adaptive_kept_span_names_each_dims_remap_form():
    """A served SQL request's `adaptive_kept` span says which remap its
    phase-B program uses, one entry per grouping dim: a range keeps one
    run of codes, six scattered values are more runs than the CPU's cap
    (a LUT gather), a dim whose every code is present (null slot included)
    is not rewritten at all."""
    cfg = SessionConfig()
    cfg.result_cache_entries = 0  # the repeat must execute
    ctx = sd.TPUOlapContext(cfg)
    rng = np.random.default_rng(5)
    n = 40_000
    ctx.register_table(
        "remap_t",
        {
            "a": np.array(
                [f"a{k:03d}" for k in rng.integers(0, 200, n)], dtype=object
            ),
            "b": np.array(
                [f"b{k:03d}" for k in rng.integers(0, 200, n)], dtype=object
            ),
            "c": rng.choice(np.array(["x", "y", None], dtype=object), n),
            "v": np.ones(n, dtype=np.float32),
        },
        dimensions=["a", "b", "c"],
        metrics=["v"],
        rows_per_segment=1 << 13,
    )
    sql = (
        "SELECT a, b, c, sum(v) AS s FROM remap_t "
        "WHERE a BETWEEN 'a010' AND 'a019' "
        "AND b IN ('b001', 'b003', 'b005', 'b007', 'b009', 'b011') "
        "GROUP BY a, b, c"
    )
    for source in ("measured", "memo"):
        ctx.sql(sql)
        assert ctx.last_metrics.strategy == "adaptive"
        (kept,) = [
            s for s in _walk(ctx.tracer.last_trace_dict()["spans"])
            if s["name"] == "adaptive_kept"
        ]
        assert kept["attrs"]["source"] == source
        assert kept["attrs"]["compact_groups"] == 10 * 6 * 3
        assert kept["attrs"]["remap"] == ["runs:1", "lut", "identity"]


def test_spans_mirror_into_a_profiler_session(tmp_path):
    """With a `jax.profiler` session open, the root and every span lie
    on the profiler's host plane as `sdol:<name>`, each child inside its
    parent on that clock; none takes the benchmark's `request:` prefix."""
    import glob

    import jax
    from jax.profiler import ProfileData

    from spark_druid_olap_tpu.obs import SPAN_HTTP_READ

    tracer = Tracer()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("request:unit"):
            with tracer.early_span(SPAN_HTTP_READ) as read:
                pass
            with tracer.query_trace(query_id="q-mirror", early=[read]):
                with span(SPAN_PLAN):
                    pass
                with span(SPAN_EXECUTE):
                    with span(SPAN_FINALIZE):
                        pass
    finally:
        jax.profiler.stop_trace()
    # and with no session the mirror is off: spans still work
    with tracer.query_trace(query_id="q-off"):
        with span(SPAN_PLAN):
            pass
    (path,) = glob.glob(
        str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb")
    )
    events = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("sdol:", "request:")):
                    events.setdefault(e.name, []).append(
                        (e.start_ns, e.start_ns + e.duration_ns)
                    )
    assert set(events) == {
        "request:unit", "sdol:http_read", "sdol:query", "sdol:plan",
        "sdol:execute", "sdol:finalize",
    }
    assert all(len(v) == 1 for v in events.values())  # q-off left nothing

    def inside(child, parent):
        (a, b), (lo, hi) = events[child][0], events[parent][0]
        return lo <= a and b <= hi

    for name in ("sdol:http_read", "sdol:query"):
        assert inside(name, "request:unit")
    assert inside("sdol:plan", "sdol:query")
    assert inside("sdol:execute", "sdol:query")
    assert inside("sdol:finalize", "sdol:execute")
    # the read precedes the root it is adopted into
    assert events["sdol:http_read"][0][1] <= events["sdol:query"][0][0]


def test_device_scopes_are_registered_and_reach_the_lowered_program():
    """`device_scope` takes registered names only, and the traced bodies
    carry them: the lowered arena program names its scan, filter, group
    keys, kernel call and carry merge."""
    import jax
    import jax.numpy as jnp

    from spark_druid_olap_tpu.exec import arena
    from spark_druid_olap_tpu.exec.engine import Engine
    from spark_druid_olap_tpu.exec.lowering import lower_groupby
    from spark_druid_olap_tpu.obs import SCOPE_NAMES, device_scope
    from spark_druid_olap_tpu.sql.parser import parse_sql

    with pytest.raises(ValueError):
        device_scope("sdol.made_up")
    assert all(n.startswith("sdol.") for n in SCOPE_NAMES)

    ctx = sd.TPUOlapContext()
    rng = np.random.default_rng(13)
    n = 2048
    ctx.register_table(
        "sc_t",
        {
            "k": rng.choice(np.array(["x", "y", "z"], dtype=object), n),
            "v": rng.random(n).astype(np.float32),
        },
        dimensions=["k"],
        metrics=["v"],
    )
    lp, _, _ = parse_sql("SELECT k, sum(v) AS s FROM sc_t WHERE v > 0.5 GROUP BY k")
    rw = ctx._planner().plan(lp)
    ds = ctx.catalog.get(rw.datasource)
    lowering = lower_groupby(rw.query, ds)
    program = Engine(strategy="dense")._arena_program(
        rw.query, ds, lowering, "dense"
    )
    cols = ctx.engine._cols_for_segment(ds.segments[0], ds, lowering.columns)
    stacked = {k: jnp.stack([v, v]) for k, v in cols.items()}
    text = program.lower(
        (arena._member_init(lowering),), stacked,
        jnp.asarray([True, True]), jnp.ones((2, 1), bool),
    ).as_text(debug_info=True)
    for scope in ("sdol.arena_scan", "sdol.filter", "sdol.group_keys",
                  "sdol.agg_inputs", "sdol.partial_agg", "sdol.carry_merge"):
        assert scope in text, scope
