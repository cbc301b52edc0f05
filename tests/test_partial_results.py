"""Anytime answers (ISSUE 7): deadline-bounded partial results.

Layers under test:

1. `PartialCollector` / `partial_scope` / `checkpoint_partial` unit
   semantics (coverage math, pass reset, fallback accumulation, the
   disabled-scope opt-out occupying the contextvar).
2. Engine-level partials: an injected deadline pinned to the K-th
   segment checkpoint yields a coverage-stamped best-effort answer with
   the result-cache kept clean.
3. The SSB-13 deadline-sweep acceptance: at 100% device failure plus a
   deadline expiring mid-(fallback)-scan, every query answers with
   monotonically-growing coverage as the deadline loosens, never an
   error, and coverage=1.0 answers equal the oracle exactly.
4. Concurrent hammer: streamed appends racing deadline-partial count
   queries — the partial count must equal rows_seen exactly (delta rows
   can never be double-counted in coverage accounting).
5. The emit-only OTLP export flag (ROADMAP obs follow-up (d)).
"""

import json
import threading

import numpy as np
import pytest

import spark_druid_olap_tpu as sd
from spark_druid_olap_tpu.config import SessionConfig
from spark_druid_olap_tpu.resilience import (
    DeadlineExceeded,
    InjectedDeadline,
    PartialCollector,
    checkpoint,
    checkpoint_partial,
    current_partial,
    deadline_scope,
    injector,
    partial_scope,
)
from spark_druid_olap_tpu.utils.floatcmp import frames_allclose
from spark_druid_olap_tpu.workloads import ssb


@pytest.fixture(autouse=True)
def _clean_injector():
    injector().disarm()
    yield
    injector().disarm()


def _ctx(**overrides):
    cfg = SessionConfig.load_calibrated()
    cfg.result_cache_entries = 0
    cfg.retry_backoff_ms = 1.0
    # pin the single-device executors: the conftest's 8-device CPU mesh
    # would route these queries to the distributed engine, whose
    # deadline behavior is drain-to-complete, not segment-loop partials
    cfg.prefer_distributed = False
    for k, v in overrides.items():
        setattr(cfg, k, v)
    return sd.TPUOlapContext(cfg)


def _flat_table(ctx, n=20_000, segment_rows=1 << 10, name="t"):
    ctx.register_table(
        name,
        {
            "d": np.array(["a", "b", "c", "d"] * (n // 4), dtype=object),
            "v": np.ones(n, dtype=np.float32),
        },
        dimensions=["d"],
        metrics=["v"],
        rows_per_segment=segment_rows,
    )
    return n


# ---------------------------------------------------------------------------
# 1. collector semantics
# ---------------------------------------------------------------------------


def test_collector_coverage_math():
    pc = PartialCollector()
    pc.add_scope(4, 1000, delta_rows=100)
    pc.add_seen(2, 400, delta_rows=100)
    assert pc.coverage() == 0.4
    assert not pc.is_partial  # not triggered yet
    pc.trigger("x")
    assert pc.is_partial
    d = pc.to_dict()
    assert d["partial"] is True and d["site"] == "x"
    assert d["delta_rows_seen"] == 100 and d["rows_total"] == 1000


def test_collector_complete_drain_is_not_partial():
    """A trigger observed after every batch dispatched drains to the
    complete answer: coverage 1.0, is_partial False."""
    pc = PartialCollector()
    pc.add_scope(2, 100)
    pc.add_seen(2, 100)
    pc.trigger("engine.resolve")
    assert pc.coverage() == 1.0
    assert not pc.is_partial


def test_collector_declared_empty_scope_is_complete():
    """A DECLARED zero-row scope (every segment pruned, or a presence
    pass proving no group survives) is complete by vacuity: coverage
    1.0, never partial — unlike an UNDECLARED scope, which must claim
    nothing."""
    pc = PartialCollector()
    pc.trigger("engine.resolve")
    assert pc.coverage() is None and pc.is_partial  # undeclared
    pc2 = PartialCollector()
    pc2.begin_pass()
    pc2.add_scope(0, 0)
    pc2.trigger("engine.resolve")
    assert pc2.coverage() == 1.0
    assert not pc2.is_partial
    # begin_pass resets the declaration along with the counters
    pc2.begin_pass()
    assert pc2.coverage() is None


def test_collector_begin_pass_resets_unless_fallback_owned():
    pc = PartialCollector()
    pc.add_scope(4, 1000)
    pc.begin_pass()
    assert pc.to_dict()["rows_total"] == 0
    pc.in_fallback = True
    pc.add_scope(4, 1000)
    pc.begin_pass()  # assist subtrees must not reset the interpreter
    assert pc.to_dict()["rows_total"] == 1000


def test_partial_scope_outermost_wins_and_optout_occupies():
    with partial_scope(True) as outer:
        with partial_scope(False) as inner:
            assert inner is outer  # joined, not replaced
    # an explicit opt-out occupies the scope: inner defaults cannot re-arm
    with partial_scope(False):
        assert current_partial() is None
        with partial_scope(True):
            assert current_partial() is None


def test_checkpoint_partial_trigger_and_drain():
    with partial_scope(True) as pc, deadline_scope(0.0001):
        import time

        time.sleep(0.001)  # the deadline is now expired
        assert checkpoint_partial("site.a") is True
        assert pc.triggered and pc.triggered_site == "site.a"
        # drained: plain checkpoints are no-ops now, never raises
        checkpoint("site.b")
        assert checkpoint_partial("site.c") is True


def test_checkpoint_partial_without_collector_raises():
    with deadline_scope(0.0001):
        import time

        time.sleep(0.001)
        with pytest.raises(DeadlineExceeded):
            checkpoint_partial("site.a")


def test_injected_deadline_skip_is_deterministic():
    injector().arm(
        "s", "error", times=1, skip=2, error_type=InjectedDeadline
    )
    checkpoint("s")
    checkpoint("s")
    with partial_scope(True) as pc:
        assert checkpoint_partial("s") is True
    assert pc.triggered_site == "s"


# ---------------------------------------------------------------------------
# 2. engine partials
# ---------------------------------------------------------------------------


def test_engine_partial_coverage_and_attrs():
    ctx = _ctx()
    n = _flat_table(ctx)
    oracle = ctx.sql("SELECT d, sum(v) AS s FROM t GROUP BY d")
    injector().arm(
        "engine.segment_loop", "error", times=1, skip=2,
        error_type=InjectedDeadline,
    )
    got = ctx.sql("SELECT d, sum(v) AS s FROM t GROUP BY d")
    m = ctx.last_metrics
    assert m.partial is True
    assert 0.0 < m.coverage < 1.0
    assert m.rows_seen == got["s"].sum()  # v == 1: the sum IS rows seen
    assert got.attrs["partial"] is True
    assert got.attrs["coverage"] == m.coverage
    # and the answer is a true subset: per-group partial <= oracle
    merged = oracle.merge(got, on="d", suffixes=("_full", "_part"))
    assert (merged["s_part"] <= merged["s_full"]).all()


def test_partial_zero_coverage_is_well_formed():
    ctx = _ctx()
    _flat_table(ctx)
    injector().arm(
        "engine.segment_loop", "error", times=1,
        error_type=InjectedDeadline,
    )
    got = ctx.sql("SELECT d, sum(v) AS s FROM t GROUP BY d")
    assert ctx.last_metrics.partial and ctx.last_metrics.coverage == 0.0
    assert list(got.columns) == ["d", "s"]  # well-formed, empty groups
    assert len(got) == 0


def test_pruned_empty_scope_not_flagged_partial():
    """Every segment interval-pruned: the exact answer is the empty
    frame, and a deadline trigger later in the lifecycle (engine.resolve)
    must not flag it partial with an unknown denominator."""
    ctx = _ctx()
    n = 20_000
    DAY = 86_400_000
    ctx.register_table(
        "tt",
        {
            "d": np.array(["a", "b"] * (n // 2), dtype=object),
            "v": np.ones(n, dtype=np.float32),
            "ts": (np.arange(n) % 10 * DAY).astype(np.int64),
        },
        dimensions=["d"], metrics=["v"], time_column="ts",
        rows_per_segment=1 << 10,
    )
    q = f"SELECT d, sum(v) AS s FROM tt WHERE ts >= {100 * DAY} GROUP BY d"
    injector().arm(
        "engine.resolve", "error", times=1, error_type=InjectedDeadline
    )
    got = ctx.sql(q)
    m = ctx.last_metrics
    assert len(got) == 0
    assert not m.partial  # complete by vacuity, not a best-effort answer


def test_adaptive_empty_kept_set_not_flagged_partial():
    """The adaptive presence pass proving NO group survives the filter
    yields the exact empty frame — an expiry observed afterwards must
    stamp it complete (the q3_4 SSB shape: both filter values exist in
    their dictionaries but never co-occur on a row)."""
    ctx = _ctx()
    n = 40_000
    i = np.arange(n) % 200  # diagonal pairing: a_i only ever with b_i
    ctx.register_table(
        "hg",
        {
            "a": np.array([f"a{k:03d}" for k in i], dtype=object),
            "b": np.array([f"b{k:03d}" for k in i], dtype=object),
            "v": np.ones(n, dtype=np.float32),
        },
        dimensions=["a", "b"], metrics=["v"], rows_per_segment=1 << 12,
    )
    q = (
        "SELECT a, b, sum(v) AS s FROM hg "
        "WHERE a = 'a000' AND b = 'b001' GROUP BY a, b"
    )
    full = ctx.sql(q)
    assert len(full) == 0 and ctx.last_metrics.strategy == "adaptive"
    injector().arm(
        "engine.resolve", "error", times=1, error_type=InjectedDeadline
    )
    got = ctx.sql(q)
    m = ctx.last_metrics
    assert len(got) == 0 and m.strategy == "adaptive"
    assert not m.partial


def test_sparse_overflow_during_drain_declines_without_pin():
    """A partial drain that stops the sparse segment loop can leave the
    merged state overflowed; the slot/row ladder must NOT re-dispatch
    the already-stopped scope (dispatch would return None and crash the
    fetch) — it declines, so a deadline can never pin the query shape
    off the sparse tier."""
    from spark_druid_olap_tpu.catalog.segment import (
        DimensionDict,
        build_datasource,
    )
    from spark_druid_olap_tpu.exec.engine import Engine
    from spark_druid_olap_tpu.models.aggregations import Count, DoubleSum
    from spark_druid_olap_tpu.models.dimensions import DimensionSpec
    from spark_druid_olap_tpu.models.query import GroupByQuery

    n, da, db = 40_000, 300, 300  # >4096 distinct pairs per batch
    rng = np.random.default_rng(11)
    cols = {
        "a": rng.integers(0, da, n),
        "b": rng.integers(0, db, n),
        "v": np.ones(n, np.float32),
    }
    ds = build_datasource(
        "hc_drain", cols, dimension_cols=["a", "b"], metric_cols=["v"],
        dicts={
            "a": DimensionDict(values=tuple(range(da))),
            "b": DimensionDict(values=tuple(range(db))),
        },
        rows_per_segment=1 << 12,
    )
    eng = Engine(strategy="sparse")
    q = GroupByQuery(
        datasource="hc_drain",
        dimensions=(DimensionSpec("a"), DimensionSpec("b")),
        aggregations=(Count("n"), DoubleSum("s", "v")),
    )
    injector().arm(
        "sparse.segment_loop", "error", times=1, skip=1,
        error_type=InjectedDeadline,
    )
    with partial_scope(True) as pc:
        got = eng.execute(q, ds)  # must not raise
    assert pc.triggered and pc.is_partial
    assert set(got.columns) == {"a", "b", "n", "s"}
    # declined, not overflowed: the query shape is not pinned off the tier
    assert not eng._sparse_disabled


def test_partial_never_enters_result_cache():
    ctx = _ctx(result_cache_entries=16)
    _flat_table(ctx)
    q = "SELECT d, sum(v) AS s FROM t GROUP BY d"
    injector().arm(
        "engine.segment_loop", "error", times=1, skip=2,
        error_type=InjectedDeadline,
    )
    part = ctx.sql(q)
    assert ctx.last_metrics.partial
    # the rerun (no fault) must compute the EXACT answer, not serve the
    # truncated frame back from the result cache
    full = ctx.sql(q)
    assert not ctx.last_metrics.partial
    assert full["s"].sum() > part["s"].sum()
    assert full["s"].sum() == 20_000
    # and the exact answer IS cached (third run hits)
    ctx.sql(q)
    assert ctx.last_metrics.strategy == "result-cache"


def test_partial_coverage_histogram_published():
    from spark_druid_olap_tpu.obs import get_registry

    ctx = _ctx()
    _flat_table(ctx)
    before = get_registry().counter(
        "sdol_partial_results_total",
        labels=("site",),
    ).snapshot()
    injector().arm(
        "engine.segment_loop", "error", times=1, skip=1,
        error_type=InjectedDeadline,
    )
    ctx.sql("SELECT d, sum(v) AS s FROM t GROUP BY d")
    after = get_registry().counter(
        "sdol_partial_results_total", labels=("site",)
    ).snapshot()
    assert sum(after.values()) == sum(before.values()) + 1


def test_partial_span_recorded_in_trace():
    ctx = _ctx()
    _flat_table(ctx)
    injector().arm(
        "engine.segment_loop", "error", times=1, skip=1,
        error_type=InjectedDeadline,
    )
    ctx.sql("SELECT d, sum(v) AS s FROM t GROUP BY d")
    doc = ctx.tracer.last_trace_dict()

    def names(node):
        out = [node["name"]]
        for c in node.get("children", ()):
            out.extend(names(c))
        return out

    assert "partial" in names(doc["spans"])


def test_scan_partial_returns_row_prefix():
    ctx = _ctx()
    _flat_table(ctx)
    injector().arm(
        "engine.scan_loop", "error", times=1, skip=3,
        error_type=InjectedDeadline,
    )
    got = ctx.sql("SELECT d, v FROM t")
    m = ctx.last_metrics
    assert 0 < len(got) < 20_000
    pc_cov = got.attrs.get("coverage")
    assert pc_cov is not None and 0 < pc_cov < 1


# ---------------------------------------------------------------------------
# 3. SSB-13 deadline-sweep acceptance
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def ssb_tables():
    return ssb.gen_tables(scale=0.01, seed=7)


def _clear_fallback_frames(ctx):
    # the fallback's frame LRU would serve fully-decoded tables across
    # sweep points, decoupling the skip index from decode progress
    if hasattr(ctx.catalog, "_fallback_frames"):
        ctx.catalog._fallback_frames.clear()


def test_ssb13_deadline_sweep_monotone_coverage(ssb_tables):
    """The acceptance gate: 100% device failure AND a deadline expiring
    mid-scan.  Every SSB query at every deadline returns a well-formed
    answer with a coverage fraction; loosening the deadline (expiry
    pinned to later checkpoints) never shrinks coverage; coverage=1.0
    answers equal the oracle exactly."""
    ctx = _ctx()
    ssb.register(ctx, tables=ssb_tables, rows_per_segment=1 << 13)
    oracle = {}
    for name, q in ssb.QUERIES.items():
        oracle[name] = ctx.sql(q)
        assert ctx.last_metrics.executor == "device", name

    injector().arm("device_dispatch", "error")  # 100% device failure
    sweep = (0, 1, 3, 6, 12, 10_000)  # expiry at the k-th decode step
    coverages = {name: [] for name in ssb.QUERIES}
    for k in sweep:
        for name, q in ssb.QUERIES.items():
            _clear_fallback_frames(ctx)
            injector().arm(
                "fallback.decode", "error", times=1, skip=k,
                error_type=InjectedDeadline,
            )
            got = ctx.sql(q)  # must NEVER raise
            m = ctx.last_metrics
            # a query whose scope zone-map-prunes to zero segments never
            # dispatches, so it legitimately "succeeds on device" even
            # at 100% dispatch failure; everything else must degrade
            if m.executor == "device":
                assert m.rows_scanned == 0, name
            else:
                assert m.executor in ("fallback", "device+fallback"), name
            cov = m.coverage if m.partial else 1.0
            assert cov is not None and 0.0 <= cov <= 1.0, (name, k)
            coverages[name].append(cov)
            if cov == 1.0:
                ok, msg = frames_allclose(got, oracle[name])
                assert ok, f"{name}@skip={k}: {msg}"
            injector().disarm("fallback.decode")
    for name, cs in coverages.items():
        assert all(
            a <= b + 1e-9 for a, b in zip(cs, cs[1:])
        ), f"{name}: coverage not monotone over the sweep: {cs}"
        assert cs[-1] == 1.0, f"{name}: loosest deadline must be exact"


def test_interp_expiry_drain_reports_honest_coverage(monkeypatch):
    """Regression: the drain-rerun after an interpreter-level expiry
    must reset the collector's accounting (api._run_fallback) and may
    only serve segments still warm in the decode cache (decoded_frame
    drain mode).  Before the fix the aborted pass's counters doubled
    the denominator and claimed rows the rerun never aggregated — an
    answer over ZERO rows could ship stamped coverage≈0.5.  Invariant:
    a partial COUNT(*) totals exactly rows_seen."""
    from spark_druid_olap_tpu.exec import fallback as fb

    # frame cache off: the whole-table LRU would mask the rerun's decode
    monkeypatch.setattr(fb, "_FRAME_CACHE_MAX_ROWS", -1)
    monkeypatch.setattr(fb, "_decode_cache", None)
    n = 1 << 12
    sql = (
        "SELECT COUNT(*) AS c FROM a "
        "UNION ALL SELECT COUNT(*) AS c FROM b"
    )
    saw_mid_coverage = False
    for k in range(8):  # expiry pinned to the k-th interpreter node
        fb._decode_cache = None  # cold decode cache per sweep point
        ctx = _ctx(partial_results=True)
        _flat_table(ctx, n=n, name="a")
        _flat_table(ctx, n=n, name="b")
        injector().arm(
            "fallback.interp", "error", times=1, skip=k,
            error_type=InjectedDeadline,
        )
        df = ctx.sql(sql)  # set-op: fallback-only; must never raise
        m = ctx.last_metrics
        total = int(df["c"].sum()) if len(df) else 0
        if m.partial:
            assert total == m.rows_seen, (k, total, m.rows_seen)
            assert m.coverage is not None and 0.0 <= m.coverage <= 1.0
            if 0.0 < m.coverage < 1.0:
                saw_mid_coverage = True
        else:
            assert total == 2 * n, k  # drained to the exact answer
    assert saw_mid_coverage, (
        "sweep never exercised the expiry-after-one-table drain"
    )


def test_half_open_probe_on_sparse_strategy_query_stays_degraded(ssb_tables):
    """Regression: the sparse tier dispatches to the device, so it must
    pass the `device_dispatch` fault site exactly like the dense engine
    (engine.py) — before the fix it did not, and at "100% device
    failure" a breaker half-open probe routed to a sparse-strategy query
    silently succeeded on the dead device, closed the breaker, and later
    queries ran on-device (breaking the deadline-sweep premise whenever
    the 2s cooldown elapsed mid-run).  The probe must fail, the query
    must still degrade, and the breaker must re-open."""
    ctx = _ctx()
    ssb.register(ctx, tables=ssb_tables, rows_per_segment=1 << 13)
    q = ssb.QUERIES["q4_3"]  # lands on the sparse strategy at this scale
    oracle = ctx.sql(q)
    assert ctx.last_metrics.executor == "device"

    injector().arm("device_dispatch", "error")  # 100% device failure
    br = ctx.resilience.breaker_for("device")
    for _ in range(10):
        ctx.sql(q)  # degrades; consecutive failures open the breaker
        if br.state == "open":
            break
    assert br.state == "open"
    # rewind the open timestamp: the cooldown has "elapsed", so the next
    # allow() admits exactly one half-open probe, which the engine routes
    # to the same (sparse) strategy as the warm run
    br._opened_at -= (br.cooldown_ms / 1e3) + 0.01
    assert br.state == "half_open"
    got = ctx.sql(q)
    m = ctx.last_metrics
    assert m.executor in ("fallback", "device+fallback"), (
        "half-open probe must not succeed on the dead device "
        f"(executor={m.executor}, strategy={m.strategy})"
    )
    assert br.state == "open", "the failed probe must re-open the breaker"
    ok, msg = frames_allclose(got, oracle)
    assert ok, msg


# ---------------------------------------------------------------------------
# 4. appends racing deadline-partial queries
# ---------------------------------------------------------------------------


def test_hammer_appends_vs_partial_queries_never_double_count():
    """Streamed appends race deadline-partial count queries.  The
    invariant that catches double-counted delta rows exactly: a partial
    COUNT(*) equals rows_seen (every row the coverage accounting claims
    was seen is counted exactly once), and delta_rows_seen never exceeds
    the rows appended so far."""
    ctx = _ctx()
    n0 = _flat_table(ctx, n=8_192, segment_rows=1 << 10)
    stop = threading.Event()
    appended = {"rows": 0}
    batch = 256

    def appender():
        while not stop.is_set():
            ctx.append_rows(
                "t",
                {
                    "d": np.array(["a", "b"] * (batch // 2), dtype=object),
                    "v": np.ones(batch, dtype=np.float32),
                },
            )
            appended["rows"] += batch
            # leave the query thread the interpreter between batches: an
            # appender that never yields grows the table without bound on
            # a machine that schedules it generously, and every query then
            # stacks all of it (the test ran 117 s in one run of the suite
            # and over 20 minutes in another, at the same commit)
            stop.wait(0.002)

    th = threading.Thread(target=appender, daemon=True)
    th.start()
    try:
        for i in range(30):
            injector().arm(
                "engine.segment_loop", "error", times=1, skip=i % 7,
                error_type=InjectedDeadline,
            )
            got = ctx.sql("SELECT count(*) AS n FROM t")
            m = ctx.last_metrics
            count = int(got["n"][0]) if len(got) else 0
            if m.partial:
                assert count == m.rows_seen, (i, count, m.rows_seen)
                assert 0.0 <= m.coverage <= 1.0
                # delta rows are seen at most once, and only ones that
                # were actually appended by the time the snapshot ran
                assert m.delta_rows_seen <= appended["rows"] + n0
            else:
                # complete answers count exactly what their snapshot held
                assert count >= n0
            injector().disarm("engine.segment_loop")
    finally:
        stop.set()
        th.join(timeout=10)
    # quiesced final answer is exact
    injector().disarm()
    got = ctx.sql("SELECT count(*) AS n FROM t")
    assert int(got["n"][0]) == n0 + appended["rows"]
    assert not ctx.last_metrics.partial


# ---------------------------------------------------------------------------
# 5. OTLP export stub
# ---------------------------------------------------------------------------


def test_otlp_export_writes_resource_spans(tmp_path):
    path = str(tmp_path / "spans.otlp.jsonl")
    ctx = _ctx(otlp_export_path=path)
    _flat_table(ctx, n=2_000, segment_rows=1 << 10)
    ctx.sql("SELECT d, sum(v) AS s FROM t GROUP BY d")
    lines = [
        json.loads(x)
        for x in open(path, encoding="utf-8").read().splitlines()
    ]
    assert lines, "the flag must produce one OTLP line per finished trace"
    doc = lines[-1]
    spans = doc["resourceSpans"][0]["scopeSpans"][0]["spans"]
    names = {s["name"] for s in spans}
    assert "query" in names and "execute" in names
    root = next(s for s in spans if s["name"] == "query")
    assert len(root["traceId"]) == 32 and len(root["spanId"]) == 16
    children = [s for s in spans if s.get("parentSpanId")]
    assert children, "child spans must carry parentSpanId"
    for s in spans:
        assert int(s["endTimeUnixNano"]) >= int(s["startTimeUnixNano"])


def test_otlp_mapping_is_deterministic():
    from spark_druid_olap_tpu.obs.otlp import trace_to_otlp

    doc = {
        "query_id": "q-1",
        "query_type": "sql",
        "total_ms": 5.0,
        "spans": {
            "name": "query",
            "start_ms": 0.0,
            "duration_ms": 5.0,
            "children": [
                {
                    "name": "plan",
                    "start_ms": 1.0,
                    "duration_ms": 2.0,
                    "events": [
                        {"name": "breaker_state", "at_ms": 1.5,
                         "attrs": {"state": "closed"}}
                    ],
                }
            ],
        },
    }
    a = trace_to_otlp(doc, epoch_ns=1_000_000)
    b = trace_to_otlp(doc, epoch_ns=1_000_000)
    assert a == b
    spans = a["resourceSpans"][0]["scopeSpans"][0]["spans"]
    plan = next(s for s in spans if s["name"] == "plan")
    assert plan["parentSpanId"] == next(
        s for s in spans if s["name"] == "query"
    )["spanId"]
    assert plan["events"][0]["name"] == "breaker_state"
