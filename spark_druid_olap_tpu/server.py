"""L7 serving surface: HTTP endpoints for BI tools and Druid clients.

Reference parity: the reference ships a patched Spark ThriftServer
(`SparklineDataThriftServer`, SURVEY.md §1 L7 / §2 ThriftServer row `[U]`) so
BI tools reach accelerated tables over JDBC.  JDBC/Thrift is JVM machinery
with no place in a TPU-native Python runtime; the equivalent surface here is
HTTP — the SAME protocol Druid's own broker speaks, so existing Druid
clients/dashboards can point at this server:

    POST /druid/v2            native Druid query JSON -> Druid-shaped results
    POST /druid/v2/sql        {"query": "SELECT ..."} -> array of row objects
    POST /druid/v2/ingest/{datasource}    streamed row append (realtime
                                          ingest; rows queryable immediately)
    GET  /druid/v2/datasources            -> ["lineorder", ...]
    GET  /druid/v2/datasources/{name}     -> {"dimensions": .., "metrics": ..}
    GET  /druid/v2/trace/{query_id}       -> span tree of a recent query
    GET  /status, /status/health          -> liveness + metrics of last query
    GET  /status/metrics                  -> Prometheus text exposition

Every query response carries an `X-Druid-Query-Id` header (the client's
`context.queryId` when set, generated otherwise — Druid parity); the id
keys the query's span tree in the trace ring buffer (obs/).

Native queries bypass the SQL planner (they ARE the planner's output
language) and run straight on the engine; SQL goes through the full rewrite
stack.  Stdlib-only (ThreadingHTTPServer); one process serves one
TPUOlapContext.

    from spark_druid_olap_tpu.server import OlapServer
    OlapServer(ctx, port=8082).serve_forever()      # or .start() for a thread
"""

from __future__ import annotations

import dataclasses
import json
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Optional

import numpy as np

from .models import query as Q
from .models.filters import _ms_to_iso
from .models.wire import WireError, query_from_druid
from .obs import (
    SPAN_ADMISSION,
    SPAN_ENGINE,
    SPAN_HTTP_ACCEPT,
    SPAN_HTTP_READ,
    SPAN_LANE,
    SPAN_RESPOND,
    default_tracer,
    get_registry,
    new_query_id,
    span,
)
from .resilience import (
    CircuitOpenError,
    DeadlineExceeded,
    classify_error,
    current_partial,
    deadline_scope,
    fire,
    partial_scope,
)
from .utils.log import get_logger

log = get_logger("server")


def _route_label(path: str) -> str:
    """Coarse route label for the http-requests counter: bounded label
    cardinality (per-datasource / per-query-id suffixes collapse)."""
    for prefix in (
        "/druid/v2/trace",
        "/druid/v2/datasources",
        "/druid/v2/sql",
        "/druid/v2/ingest",
        "/druid/v2",
        "/status/metrics",
        "/status/health",
        "/status/profile",
        "/status",
    ):
        if path == prefix or path.startswith(prefix + "/"):
            return prefix
    return "other"


def _jsonable(v: Any):
    import datetime

    import pandas as pd

    if isinstance(v, (np.integer,)):
        return int(v)
    if isinstance(v, (np.floating,)):
        f = float(v)
        return None if np.isnan(f) else f
    if isinstance(v, np.datetime64):
        return _ms_to_iso(int(v.astype("datetime64[ms]").astype(np.int64)))
    if isinstance(v, (pd.Timestamp, datetime.datetime)):
        # Druid wire format is ISO-8601 with the Z designator, not
        # str(Timestamp)'s "YYYY-MM-DD HH:MM:SS"
        return _ms_to_iso(
            int(np.datetime64(v.replace(tzinfo=None), "ms").astype(np.int64))
        )
    if isinstance(v, np.bool_):
        return bool(v)
    if isinstance(v, float) and np.isnan(v):
        return None
    if v is None or isinstance(v, (str, int, float, bool)):
        return v
    if isinstance(v, dict):
        return {k: _jsonable(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [_jsonable(x) for x in v]
    return str(v)


def _rows(df) -> list:
    return [
        {k: _jsonable(v) for k, v in rec.items()}
        for rec in df.to_dict(orient="records")
    ]


def _result_timestamp(q) -> str:
    ivs = getattr(q, "intervals", ())
    return _ms_to_iso(ivs[0][0] if ivs else 0)


def druid_result_shape(q: Q.QuerySpec, df) -> Any:
    """Results in the shape Druid's broker returns for each query type."""
    if isinstance(q, Q.GroupByQuery):
        ts = _result_timestamp(q)
        out = []
        for rec in _rows(df):
            t = rec.pop("timestamp", ts)
            out.append({"version": "v1", "timestamp": t, "event": rec})
        return out
    if isinstance(q, Q.TimeseriesQuery):
        # wire shape always says "timestamp" whatever the SQL alias was
        return [
            {
                "timestamp": rec.pop(q.output_name, _result_timestamp(q)),
                "result": rec,
            }
            for rec in _rows(df)
        ]
    if isinstance(q, Q.TopNQuery):
        return [{"timestamp": _result_timestamp(q), "result": _rows(df)}]
    if isinstance(q, Q.ScanQuery):
        if q.result_format == "compactedList":
            # Druid compactedList: events are POSITIONAL value arrays
            # aligned with "columns", not keyed objects
            events = [
                [_jsonable(v) for v in row]
                for row in df.itertuples(index=False)
            ]
        else:
            events = _rows(df)
        return [
            {
                "segmentId": q.datasource,
                "columns": list(df.columns),
                "events": events,
            }
        ]
    if isinstance(q, Q.SearchQuery):
        return [{"timestamp": _result_timestamp(q), "result": _rows(df)}]
    if isinstance(q, Q.TimeBoundaryQuery):
        if df.empty:
            return []
        rec = _rows(df)[0]
        ts = rec.get("minTime", rec.get("maxTime"))
        return [{"timestamp": ts, "result": rec}]
    if isinstance(q, Q.DataSourceMetadataQuery):
        if df.empty:
            return []
        rec = _rows(df)[0]
        return [{"timestamp": rec["maxIngestedEventTime"], "result": rec}]
    if isinstance(q, Q.SegmentMetadataQuery):
        return _rows(df)
    return _rows(df)


def _tracer_of(ctx):
    return getattr(ctx, "tracer", None) or default_tracer()


class _Handler(BaseHTTPRequestHandler):
    # chunked transfer-encoding (the progressive streaming path) is only
    # defined for HTTP/1.1 — the stdlib default of HTTP/1.0 would make
    # spec-compliant clients read the hex chunk-size lines as body bytes.
    # Safe to enable: every buffered response carries Content-Length
    # (_begin_response) and every chunked one ends with the terminal
    # 0-chunk, so keep-alive connections can never hang.
    protocol_version = "HTTP/1.1"
    ctx = None  # set by OlapServer
    server_version = "sdol-tpu/0.2"
    _query_id: Optional[str] = None  # per-request; set by do_POST
    _req_t0: Optional[float] = None
    # trace-before-response contract (see do_POST): while a query trace
    # is open, buffered responses are captured here and written only
    # after the trace publishes to the ring
    _defer_buffered = False
    _buffered_response: Optional[tuple] = None
    # the tracer's clock at `accept()` (see _OlapHTTPServer): the start
    # of the connection's FIRST request's `http_accept` span; None for
    # every later request of a kept-alive connection
    _accepted_at: Optional[float] = None

    # -- plumbing ------------------------------------------------------------

    def setup(self):
        super().setup()
        self._accepted_at = self.server.accepted_at.pop(self.request, None)

    def handle_one_request(self):
        try:
            super().handle_one_request()
        finally:
            # whatever the first request was (a GET reads no stamp), the
            # next one on this connection did not begin at `accept()`
            self._accepted_at = None

    def log_message(self, fmt, *args):
        # library etiquette: no stderr spray; stdlib-internal messages
        # (malformed request lines etc.) surface at DEBUG instead of the
        # old silent pass (ISSUE 4 satellite)
        log.debug("http %s", (fmt % args) if args else fmt)

    def log_request(self, code="-", size="-"):
        """Structured access log at DEBUG: method, path, status, query_id,
        duration — the queryId-tagged request log Druid keeps (SURVEY.md
        §5), replacing the silenced default."""
        import time as _time

        dur_ms = (
            (_time.perf_counter() - self._req_t0) * 1e3
            if self._req_t0 is not None
            else -1.0
        )
        log.debug(
            "access method=%s path=%s status=%s query_id=%s "
            "duration_ms=%.2f",
            self.command, self.path, code, self._query_id or "-", dur_ms,
        )

    # -- response writer ----------------------------------------------------
    # ONE writer serves both the buffered and the chunked (progressive)
    # paths (ISSUE 7 ride-along): status+headers — including the
    # X-Druid-Query-Id echo — are emitted by `_begin_response` for BOTH,
    # and the http-requests counter fires exactly once per response via
    # `_finish_response`, so streamed responses can never drift from the
    # buffered contract.

    def _begin_response(
        self,
        code: int,
        content_type: str,
        headers: Optional[dict] = None,
        length: Optional[int] = None,
    ):
        """Status line + headers.  `length=None` switches the body to
        chunked transfer-encoding (`_write_chunk`/`_finish_response`);
        otherwise the caller writes exactly `length` bytes."""
        self.send_response(code)
        self.send_header("Content-Type", content_type)
        if length is not None:
            self.send_header("Content-Length", str(length))
        else:
            self.send_header("Transfer-Encoding", "chunked")
        if self._query_id:
            # Druid parity: every query response (success OR error, buffered
            # OR streamed) echoes the query's id so clients can correlate
            # logs and traces
            self.send_header("X-Druid-Query-Id", self._query_id)
        for k, v in (headers or {}).items():
            self.send_header(k, str(v))
        self.end_headers()

    def _write_chunk(self, data: bytes):
        self.wfile.write(b"%x\r\n" % len(data))
        self.wfile.write(data)
        self.wfile.write(b"\r\n")
        self.wfile.flush()

    def _finish_response(self, code: int, chunked: bool = False):
        if chunked:
            self.wfile.write(b"0\r\n\r\n")
            self.wfile.flush()
        get_registry().counter(
            "sdol_http_requests_total",
            "HTTP responses by method/route/status",
            labels=("method", "route", "code"),
        ).labels(
            method=self.command or "-",
            route=_route_label(self.path.split("?")[0].rstrip("/")),
            code=str(code),
        ).inc()

    def _send(self, code: int, payload: Any, headers: Optional[dict] = None):
        body = json.dumps(payload, default=_jsonable).encode()
        self._send_bytes(code, body, "application/json", headers)

    def _send_bytes(
        self,
        code: int,
        body: bytes,
        content_type: str,
        headers: Optional[dict] = None,
    ):
        if self._defer_buffered:
            # a query trace is open: capture the response; do_POST writes
            # it after the trace publishes so /druid/v2/trace/{id} can
            # never 404 on a query whose response was already read
            self._buffered_response = (code, body, content_type, headers)
            return
        self._begin_response(code, content_type, headers, length=len(body))
        self.wfile.write(body)
        self._finish_response(code)

    def _respond(self, shape, *result):
        """Answer 200 with `shape(*result)` (a result frame rendered
        into the route's payload).  Rendering, JSON encoding and the
        buffering of the bytes are the `respond` span; the socket write
        itself waits for the trace to close (see do_POST)."""
        with span(SPAN_RESPOND):
            self._send(
                200, shape(*result), headers=self._partial_headers()
            )

    def _error(
        self,
        code: int,
        msg: str,
        error_class: str = "QueryInterruptedException",
        headers: Optional[dict] = None,
    ):
        # Druid's structured error object: `error` stays the readable
        # message (clients and older tests read it), `errorMessage` /
        # `errorClass` carry the structure Druid clients dispatch on
        self._send(
            code,
            {"error": msg, "errorMessage": msg, "errorClass": error_class},
            headers=headers,
        )

    def _body(self) -> Optional[dict]:
        try:
            n = int(self.headers.get("Content-Length", 0))
            body = json.loads(self.rfile.read(n) or b"{}")
        except (ValueError, json.JSONDecodeError):
            return None
        # valid JSON that isn't an object (`[1,2]`, `"x"`) is equally a
        # client error, not a 500 from a surprised .get()
        return body if isinstance(body, dict) else None

    # -- routes --------------------------------------------------------------

    def _resilience(self):
        return getattr(self.ctx, "resilience", None)

    def _tracer(self):
        return _tracer_of(self.ctx)

    def do_GET(self):
        import time as _time

        # keep-alive: clear the previous request's query id (GETs have
        # none) so health/metrics/trace responses never echo a stale
        # X-Druid-Query-Id from an earlier POST on this connection
        self._query_id = None
        self._req_t0 = _time.perf_counter()
        path = self.path.split("?")[0].rstrip("/")
        if path in ("/status/health", ""):
            res = self._resilience()
            if res is None:
                return self._send(200, True)
            # breaker state + slots in use: a load balancer (or the
            # concurrent-serving test) reads degradation from here
            doc = res.health()
            storage = getattr(self.ctx, "storage", None)
            # durable-tier state (ISSUE 13): WAL sequence, last snapshot
            # version, replay-in-progress, dirty-delta counts — what an
            # operator needs to answer "what would a restart lose" (zero)
            # and "is this node still replaying"
            doc["storage"] = (
                storage.state() if storage is not None
                else {"enabled": False}
            )
            # cluster tier (ISSUE 16): per-historical liveness/breaker
            # state, the assignment epoch, and the replication deficit.
            # Served through ANY breaker state — health must stay
            # readable exactly when the cluster is degraded.
            cluster = getattr(self.ctx, "cluster", None)
            if cluster is not None:
                doc["cluster"] = cluster.state()
            return self._send(200, doc)
        if path == "/status/metrics":
            # Prometheus text exposition of the process registry (engines,
            # resilience, http counters, per-phase latency histograms).
            # ?cluster=1 on a BROKER federates the scrape: every
            # historical's registry merges in under a `node` label, with
            # unreachable nodes stamped stale — the scrape never 500s on
            # a dead historical (cluster/federation.py, ISSUE 19).
            from urllib.parse import parse_qs, urlparse

            qs = parse_qs(urlparse(self.path).query)
            cluster = getattr(self.ctx, "cluster", None)
            if qs.get("cluster", ["0"])[0] in ("1", "true") and (
                cluster is not None
            ):
                return self._send_bytes(
                    200,
                    cluster.federated_metrics().encode(),
                    "text/plain; version=0.0.4; charset=utf-8",
                )
            return self._send_bytes(
                200,
                get_registry().render_prometheus().encode(),
                "text/plain; version=0.0.4; charset=utf-8",
            )
        if path == "/status/profile":
            # workload profiler (obs/prof.py, ISSUE 9): rolling-window
            # top-K queries by device time, per-family compile totals,
            # per-lane SLO burn-rate.  ?k= and ?window_s= override the
            # configured defaults per request; ?cluster=1 on a broker
            # federates every historical's profile under its node id
            # (stale entries for unreachable nodes, never a 500).
            from urllib.parse import parse_qs, urlparse

            from .obs.prof import profile_doc

            qs = parse_qs(urlparse(self.path).query)

            def _num(name, cast):
                try:
                    return cast(qs[name][0])
                except (KeyError, IndexError, TypeError, ValueError):
                    return None

            local = profile_doc(
                config=getattr(self.ctx, "config", None),
                top_k=_num("k", int),
                window_s=_num("window_s", float),
            )
            cluster = getattr(self.ctx, "cluster", None)
            if qs.get("cluster", ["0"])[0] in ("1", "true") and (
                cluster is not None
            ):
                return self._send(
                    200, cluster.federated_profile(local)
                )
            return self._send(200, local)
        if path.startswith("/druid/v2/trace/"):
            qid = path.rsplit("/", 1)[1]
            tr = self._tracer().ring.get(qid)
            if tr is None:
                return self._error(
                    404, f"no trace for query id {qid!r} (ring holds the "
                    "most recent traces only)", "NotFound",
                )
            return self._send(200, tr)
        if path == "/status":
            m = self.ctx.last_metrics
            res = self._resilience()
            return self._send(
                200,
                {
                    "service": "spark-druid-olap-tpu",
                    "datasources": sorted(self.ctx.catalog.tables()),
                    "last_query_metrics": m.to_dict() if m else None,
                    "resilience": res.health() if res else None,
                    # serving core (serve/): fusion + result-cache stats
                    "serving": (
                        self.ctx.serve.to_dict()
                        if getattr(self.ctx, "serve", None) is not None
                        else None
                    ),
                    # registry summary: counter/gauge values + histogram
                    # p50/p95/p99 (full series live at /status/metrics)
                    "metrics": get_registry().to_dict(),
                    # __sys telemetry sampler (obs/telemetry.py): tick/
                    # row/drop counters; None when never started
                    "sys_sampler": (
                        self.ctx.sys_sampler.status()
                        if getattr(self.ctx, "sys_sampler", None)
                        is not None
                        else None
                    ),
                },
            )
        if path == "/druid/v2/datasources":
            return self._send(200, sorted(self.ctx.catalog.tables()))
        if path.startswith("/druid/v2/datasources/"):
            name = path.rsplit("/", 1)[1]
            ds = self.ctx.catalog.get(name)
            if ds is None:
                return self._error(404, f"unknown datasource {name!r}")
            return self._send(
                200,
                {
                    "dimensions": [
                        c.name for c in ds.columns if c.kind == "dimension"
                    ],
                    "metrics": [
                        c.name for c in ds.columns if c.kind == "metric"
                    ],
                    "timeColumn": ds.time_column,
                    "numRows": ds.num_rows,
                    "segments": len(ds.segments),
                },
            )
        return self._error(404, f"no route {path!r}")

    def do_POST(self):
        import time as _time

        # per-request state: with HTTP/1.1 keep-alive the SAME handler
        # instance serves every request on the connection — a stale id
        # from the previous query must never echo on this response
        self._query_id = None
        self._req_t0 = _time.perf_counter()
        tracer = self._tracer()
        early = []
        if self._accepted_at is not None:
            # thread start, request line and header parse: from the
            # server's stamp at `accept()` to here, made after the fact
            with tracer.early_span(
                SPAN_HTTP_ACCEPT, start=self._accepted_at
            ) as accepted:
                pass
            early.append(accepted)
        # the body holds the id the trace opens under, so it is read
        # before there is a trace: as an early span the query's root
        # adopts below, which makes the root start with the first of them
        with tracer.early_span(SPAN_HTTP_READ) as read:
            body = self._body()
        early.append(read)
        path = self.path.split("?")[0].rstrip("/")
        if body is None:
            return self._error(
                400, "invalid JSON body", "BadJsonQueryException"
            )
        if path.startswith("/druid/v2/ingest/"):
            return self._ingest(path.rsplit("/", 1)[1], body)
        if path == "/druid/v2/cluster/partial":
            # the historical's scatter surface (cluster/, ISSUE 16)
            return self._cluster_partial(body)
        if path not in ("/druid/v2", "/druid/v2/sql"):
            return self._error(404, f"no route {path!r}", "NotFound")
        # A non-dict context is client noise, not a server error: ignore it.
        qctx = body.get("context")
        qctx = qctx if isinstance(qctx, dict) else {}
        # query_id is born HERE, the server boundary: honor Druid's
        # `context.queryId` when the client set one, generate otherwise.
        # Echoed on every response as X-Druid-Query-Id (_send_bytes) and
        # carried through the whole execution by the active trace.
        client_qid = qctx.get("queryId")
        self._query_id = (
            str(client_qid) if client_qid else new_query_id()
        )
        cfg = getattr(self.ctx, "config", None)
        res = self._resilience()
        self._buffered_response = None
        self._defer_buffered = True
        try:
            with tracer.query_trace(
                query_id=self._query_id,
                query_type="native" if path == "/druid/v2" else "sql",
                slow_ms=cfg.slow_query_ms if cfg else 0.0,
                early=early,
            ):
                return self._handle_query(path, body, qctx, res, cfg)
        finally:
            # trace-before-response contract: the buffered response was
            # CAPTURED by _send_bytes during the query scope and is
            # written HERE — after the trace published to the ring — so
            # a client that reads it and immediately fetches
            # /druid/v2/trace/{id} can never race the publish.  (So the
            # socket write is outside the span tree: the `respond` span
            # ends where the bytes are buffered.)
            self._defer_buffered = False
            pending = self._buffered_response
            if pending is not None:
                self._buffered_response = None
                try:
                    self._send_bytes(*pending)
                except OSError:
                    pass  # client disconnected before the body landed
            # a streamed (chunked) response gets the same guarantee from
            # its terminal 0-chunk, deferred to HERE — the client's read
            # completes only on that chunk
            code = getattr(self, "_pending_chunked_finish", None)
            if code is not None:
                self._pending_chunked_finish = None
                try:
                    self._finish_response(code, chunked=True)
                except OSError:
                    # client disconnected mid-stream: the terminal
                    # 0-chunk has no socket to land on — not an error
                    pass

    def _handle_query(self, path, body, qctx, res, cfg):
        # a recovering node is BUSY, not wedged: while boot WAL replay is
        # still applying journaled appends, answering queries would serve
        # a state mid-way between the snapshot and the pre-crash tail —
        # 503 + Retry-After tells the balancer to come back, exactly like
        # an exhausted admission pool does
        storage = getattr(self.ctx, "storage", None)
        if storage is not None and storage.replay_in_progress:
            return self._error(
                503,
                "node is recovering (WAL replay in progress); retry later",
                "QueryUnavailableException",
                headers={
                    "Retry-After": res.admission.retry_after_s()
                    if res is not None
                    else 1
                },
            )
        # admission is per-route and LANE-FIRST (serve/lanes.py): the
        # query takes its priority lane's slot before the global pool,
        # so a heavy query queued on a full heavy lane never sits on a
        # global slot while waiting — that ordering is what keeps the
        # interactive lane's capacity reachable under a heavy storm
        try:
            # Druid-native per-query deadline: `context.timeout` (ms)
            # overrides the session default — including `timeout: 0`,
            # Druid's explicit "no timeout".  The scope set HERE is the
            # outermost, so ctx.sql's own scope defers to it.
            if "timeout" in qctx:
                try:
                    timeout_ms = float(qctx["timeout"])
                except (TypeError, ValueError):
                    timeout_ms = 0
                if timeout_ms <= 0:
                    # explicit opt-out: arm an INFINITE deadline so the
                    # session default inside ctx.sql (which defers to any
                    # outer scope) cannot re-arm a budget the client
                    # declined
                    timeout_ms = float("inf")
            else:
                timeout_ms = cfg.query_timeout_ms if cfg else 0
            # partial-result collection: session default, overridable per
            # request via context.partialResults (Druid-style context
            # flag).  The scope armed HERE is the outermost, so ctx.sql's
            # own scope joins it and the response headers can read the
            # collector after execution.
            p_enabled = bool(cfg.partial_results) if cfg else False
            pflag = qctx.get("partialResults")
            if isinstance(pflag, bool):
                p_enabled = pflag
            with deadline_scope(timeout_ms), partial_scope(p_enabled):
                if path == "/druid/v2":
                    return self._native_query(body, qctx)
                return self._sql_query(body, qctx)
        except WireError as e:
            return self._error(400, str(e), "BadQueryException")
        except KeyError as e:
            return self._error(400, f"missing field: {e}", "BadQueryException")
        except Q.QueryValidationError as e:
            # validation of a decoded query (unknown orderBy column,
            # __time ordering on a timeless table): client error.  Plain
            # ValueError stays a 500 — internal invariants are not the
            # client's fault
            return self._error(400, str(e), "BadQueryException")
        except CircuitOpenError as e:
            # native wire queries have no logical plan to degrade to the
            # host fallback with: an open breaker fails them FAST (503 +
            # Retry-After) instead of burning retry budget on a device
            # known to be down
            return self._error(
                503, str(e), "QueryUnavailableException",
                headers={
                    "Retry-After": res.admission.retry_after_s()
                    if res is not None
                    else 1
                },
            )
        except DeadlineExceeded as e:
            # the api layer counts SQL deadline expiry itself; only count
            # here when the exception arrives uncounted (the native path)
            if res is not None and not getattr(e, "_sdol_counted", False):
                res.note_deadline_exceeded()
            return self._error(504, str(e), "QueryTimeoutException")
        except Exception as e:
            # a 500 must not leak raw exception text (internals, paths,
            # data values) to clients: structured Druid-style error out,
            # full traceback to the server log, failure recorded on the
            # resilience counters + the query's metrics
            log.error("query failed: %s", type(e).__name__, exc_info=True)
            # the failing query's OWN metrics already carry error_class
            # (the engine retry loop stamps it); stamping last_metrics here
            # would pollute an unrelated earlier query when the failure
            # precedes execution (e.g. a parse error)
            if res is not None:
                res.note_server_error(e)
            return self._error(
                500,
                "query execution failed; see server logs",
                type(e).__name__,
            )

    def _admit(self, res) -> bool:
        """The GLOBAL admission pool — acquired AFTER the lane slot (a
        query waiting out a full lane must not hold global capacity
        while it waits).  A bounded slot pool with a queue-wait timeout
        answers 503 + Retry-After instead of piling handler threads
        behind a slow device until the process wedges."""
        with span(SPAN_ADMISSION):
            admitted = res is None or res.admission.acquire()
        if not admitted:
            self._error(
                503,
                "query capacity exceeded; retry later",
                "QueryCapacityExceededException",
                headers={"Retry-After": res.admission.retry_after_s()},
            )
        return admitted

    def _ingest(self, name: str, body: dict):
        """POST /druid/v2/ingest/{datasource}: streamed row append (the
        realtime-node push analog).  Body: {"rows": [...row objects...]}
        or {"columns": {name: [values...]}}.  Gated on the SEPARATE
        ingest admission pool (503 + Retry-After when full) so appends
        and queries cannot starve each other, and on the same per-request
        deadline contract queries get (`context.timeout` honored)."""
        res = self._resilience()
        cfg = getattr(self.ctx, "config", None)
        qctx = body.get("context")
        qctx = qctx if isinstance(qctx, dict) else {}
        client_qid = qctx.get("queryId")
        self._query_id = str(client_qid) if client_qid else new_query_id()
        rows = body.get("rows", body.get("columns"))
        if rows is None:
            return self._error(
                400,
                'body must carry "rows" (row objects) or "columns" '
                "(column arrays)",
                "BadQueryException",
            )
        with span(SPAN_ADMISSION):
            admitted = res is None or res.ingest_admission.acquire()
        if not admitted:
            return self._error(
                503,
                "ingest capacity exceeded; retry later",
                "QueryCapacityExceededException",
                headers={
                    "Retry-After": res.ingest_admission.retry_after_s()
                },
            )
        try:
            # tolerate a malformed context.timeout exactly like the query
            # route: client noise means "no timeout", never a 500
            if "timeout" in qctx:
                try:
                    timeout_ms = float(qctx["timeout"])
                except (TypeError, ValueError):
                    timeout_ms = 0
            else:
                timeout_ms = cfg.query_timeout_ms if cfg else 0
            if timeout_ms <= 0:
                timeout_ms = float("inf")
            with self._tracer().query_trace(
                query_id=self._query_id,
                query_type="ingest",
                slow_ms=cfg.slow_query_ms if cfg else 0.0,
            ), deadline_scope(timeout_ms):
                ack = self.ctx.ingest.append_rows(name, rows)
            return self._send(200, ack)
        except KeyError as e:
            return self._error(
                400, f"unknown dataSource: {e}", "BadQueryException"
            )
        except ValueError as e:
            # malformed client payload (ragged columns, unknown columns,
            # unparseable time values): 400, not a server error
            return self._error(400, str(e), "BadQueryException")
        except DeadlineExceeded as e:
            if res is not None:
                res.note_deadline_exceeded()
            return self._error(504, str(e), "QueryTimeoutException")
        except Exception as e:
            log.error("ingest failed: %s", type(e).__name__, exc_info=True)
            if res is not None:
                res.note_server_error(e)
            return self._error(
                500, "ingest failed; see server logs", type(e).__name__
            )
        finally:
            if res is not None:
                res.ingest_admission.release()

    def _cluster_partial(self, body: dict):
        """POST /druid/v2/cluster/partial: the historical's scatter
        surface (cluster/, ISSUE 16).  Body: {"query": native query
        object, "segments": [segment_id, ...] | null (full scope),
        "version": broker's expected datasource version, "context":
        {...}}.  Executes the query's HOST partial state over exactly
        the requested segments and returns it wire-encoded with the
        datasource version, the served segment ids, and this node's
        per-query cost receipt — the broker ⊕'s the states through the
        same merge tree the mesh slices use.

        Contract edges: a node still replaying its WAL answers 503 +
        Retry-After (its replicas carry the traffic; the replay-while-
        serving test pins this); a segment id or version this catalog
        cannot satisfy answers 409 (assignment skew — the broker treats
        the replica as failed and rebalances), never a wrong merge."""
        from .cluster.wire import HEADER_PARENT_SPAN, HEADER_QUERY_ID

        res = self._resilience()
        cfg = getattr(self.ctx, "config", None)
        qctx = body.get("context")
        qctx = qctx if isinstance(qctx, dict) else {}
        # trace propagation (ISSUE 19): the broker sends the query id
        # both ways (context.queryId AND the X-Druid-Query-Id header) —
        # context wins, the header covers native clients; the parent
        # span id stamps this trace's cross-process parentage so the
        # OTLP exports of both processes join under one trace id
        client_qid = qctx.get("queryId") or self.headers.get(
            HEADER_QUERY_ID
        )
        self._query_id = str(client_qid) if client_qid else new_query_id()
        parent_span = str(self.headers.get(HEADER_PARENT_SPAN) or "")
        storage = getattr(self.ctx, "storage", None)
        if storage is not None and storage.replay_in_progress:
            return self._error(
                503,
                "node is recovering (WAL replay in progress); retry later",
                "QueryUnavailableException",
                headers={
                    "Retry-After": res.admission.retry_after_s()
                    if res is not None
                    else 1
                },
            )
        qdoc = body.get("query")
        if not isinstance(qdoc, dict):
            return self._error(
                400, 'body must carry a native "query" object',
                "BadQueryException",
            )
        if not self._admit(res):
            return None
        try:
            # chaos site: an armed error IS this historical dying while
            # serving (the broker sees the failure and fails over to a
            # replica); delay mode is the slow-replica cell
            fire("cluster.historical_kill")
            from .cluster.wire import encode_state

            q = query_from_druid(qdoc)
            ds = self.ctx.catalog.get(q.datasource)
            if ds is None:
                return self._error(
                    400, f"unknown dataSource {q.datasource!r}",
                    "BadQueryException",
                )
            # snapshot-generation check (GL2301): the LIVE catalog
            # version is process-local (every republish bumps it), so
            # replicas compare the SNAPSHOT version they booted — the
            # one number identical across processes sharing the store
            have = (
                storage.snapshot_version(q.datasource)
                if storage is not None else None
            )
            if have is None:
                have = int(ds.version)
            expect = body.get("version")
            if expect is not None and have != int(expect):
                return self._error(
                    409,
                    f"datasource {q.datasource!r} at snapshot version "
                    f"{have}, broker's assignment expects {int(expect)} "
                    "— replica/assignment skew; rebalance and retry",
                    "VersionMismatchException",
                )
            want = body.get("segments")
            by_id = {s.segment_id: s.uid for s in ds.segments}
            if want is None:
                uids = None
                served = sorted(by_id)
            else:
                missing = [sid for sid in want if sid not in by_id]
                if missing:
                    return self._error(
                        409,
                        f"unknown segments {missing[:4]} (assignment vs "
                        "catalog skew) — rebalance and retry",
                        "VersionMismatchException",
                    )
                uids = frozenset(by_id[sid] for sid in want)
                served = [str(sid) for sid in want]
            with self._tracer().query_trace(
                query_id=self._query_id,
                query_type="cluster_partial",
                slow_ms=cfg.slow_query_ms if cfg else 0.0,
                parent_span_id=parent_span,
            ) as tr:
                if tr is not None:
                    tr.root.attrs["node"] = getattr(
                        self.ctx, "cluster_node_id", ""
                    )
                self.ctx._sync_engine_resilience(self.ctx.engine)
                state, rows = self.ctx.engine.groupby_partials_host(
                    q, ds, within_uids=uids
                )
            doc = {
                "node": getattr(self.ctx, "cluster_node_id", ""),
                "version": int(have),
                "rows": int(rows),
                "segments": served,
                "state": encode_state(state),
            }
            if tr is not None and tr.receipt:
                # per-historical receipt (ISSUE 16 obs satellite): the
                # broker folds this into its own receipt's cluster
                # section, so one query attributes across processes
                doc["receipt"] = tr.receipt
            if tr is not None:
                # rendered span subtree for the broker to graft under
                # its cluster_rpc span (ISSUE 19); size-capped, and any
                # defect degrades to a stub — never a failed response
                from .cluster.wire import encode_trace

                subtree = encode_trace(tr.to_dict())
                if subtree is not None:
                    doc["trace"] = subtree
            return self._send(200, doc)
        except (WireError, ValueError) as e:
            return self._error(400, str(e), "BadQueryException")
        except DeadlineExceeded as e:
            if res is not None:
                res.note_deadline_exceeded()
            return self._error(504, str(e), "QueryTimeoutException")
        except Exception as e:
            log.error(
                "cluster partial failed: %s", type(e).__name__,
                exc_info=True,
            )
            if res is not None:
                res.note_server_error(e)
            return self._error(
                500, "cluster partial failed; see server logs",
                type(e).__name__,
            )
        finally:
            if res is not None:
                res.admission.release()

    def _partial_headers(self) -> Optional[dict]:
        """X-Druid-Response-Context carrying the partial-result contract
        (ISSUE 7): when the answer about to be sent is deadline-bounded,
        the header holds {"partial": true, "coverage": ..., rows seen /
        total, delta split} — Druid's own response-context header, so
        existing clients that already parse it see the flag.

        A SAMPLED query (obs/prof.py, ISSUE 9) additionally carries its
        cost receipt under a "receipt" key — the per-query device/host/
        transfer split and cache-tier outcomes on the wire.  Unsampled
        queries keep the exact historical header behavior (absent unless
        partial)."""
        from .obs.prof import live_receipt, profiled

        rctx = {}
        pc = current_partial()
        if pc is not None and pc.is_partial:
            rctx.update(pc.to_dict())
        if profiled():
            rc = live_receipt()
            if rc is not None:
                rctx["receipt"] = rc
        if not rctx:
            return None
        return {
            "X-Druid-Response-Context": json.dumps(rctx, default=_jsonable)
        }

    # query types that never dispatch device work: answered from catalog
    # metadata, so breaker state is irrelevant to them
    _METADATA_QUERIES = (
        Q.TimeBoundaryQuery,
        Q.DataSourceMetadataQuery,
        Q.SegmentMetadataQuery,
    )

    def _acquire_lane(self, lane_name: str):
        """Gate one query on its priority lane's slot pool (serve/lanes):
        returns True when admitted, or sends the 503 (naming the lane,
        with the lane's OWN observed-load Retry-After) and returns False.
        A context without resilience state admits everything."""
        res = self._resilience()
        if res is None or not getattr(res, "lanes", None):
            return True
        pool = res.lane(lane_name)
        with span(SPAN_LANE, lane=lane_name):
            admitted = pool.acquire()
        if not admitted:
            self._error(
                503,
                f"{lane_name} lane capacity exceeded; retry later",
                "QueryCapacityExceededException",
                headers={"Retry-After": pool.retry_after_s()},
            )
        return admitted

    def _release_lane(self, lane_name: Optional[str]):
        res = self._resilience()
        if lane_name and res is not None and getattr(res, "lanes", None):
            res.lane(lane_name).release()

    def _native_query(self, body: dict, qctx: dict):
        res = self._resilience()
        serve = getattr(self.ctx, "serve", None)
        try:
            # cross-request decoded-QuerySpec plan cache (ROADMAP 1(c)):
            # dashboards POST the identical body every refresh — a hit
            # skips the wire decode entirely, shaving the fast lane's
            # per-request floor
            if serve is not None:
                q = serve.decode_native(body)
            else:
                q = query_from_druid(body)
        except ValueError as e:
            # decode-time ValueErrors (unsupported filter type, malformed
            # interval timestamps) are malformed CLIENT input — 400, same
            # as WireError; execution-time ValueErrors stay 500
            raise WireError(str(e)) from e
        ds = self.ctx.catalog.get(q.datasource)
        if ds is None:
            return self._error(400, f"unknown dataSource {q.datasource!r}")
        # priority lanes (serve/lanes.py): a cheap dashboard query takes
        # an interactive slot an SF100-scale scan cannot starve; heavy
        # work gates on its own small pool with a per-lane Retry-After
        from .obs.prof import note_lane
        from .serve.lanes import classify_native

        lane_name = classify_native(
            q, ds, getattr(self.ctx, "config", None)
        )
        note_lane(lane_name)  # the workload profiler's SLO burn key
        if not self._acquire_lane(lane_name):
            return None
        try:
            if not self._admit(res):
                return None
            try:
                return self._native_query_admitted(q, ds, body, qctx, res)
            finally:
                if res is not None:
                    res.admission.release()
        finally:
            self._release_lane(lane_name)

    def _native_query_admitted(self, q, ds, body: dict, qctx: dict, res):
        needs_device = not isinstance(q, self._METADATA_QUERIES)
        serve = getattr(self.ctx, "serve", None)
        if (
            needs_device
            and res is not None
            and not res.breaker_for("device").allow()
        ):
            # an open circuit must not cost a cached answer (same stance
            # as the SQL path): exact hits need no device — but a delta
            # refresh WOULD dispatch, so allow_delta=False
            if serve is not None:
                hit = serve.cached_native(q, ds, allow_delta=False)
                if hit is not None:
                    return self._respond(druid_result_shape, q, hit)
            # the device breaker is open: degrade the wire query through
            # the native->logical fallback interpreter instead of the old
            # blanket 503 (the completed degradation-matrix cell); shapes
            # the interpreter can't cover still fail fast with 503
            return self._native_degraded(q, None, "circuit_open")
        progressive = (
            bool(qctx.get("progressive"))
            and isinstance(
                q, (Q.GroupByQuery, Q.TimeseriesQuery, Q.TopNQuery)
            )
            and not (isinstance(q, Q.GroupByQuery) and q.subtotals)
        )
        if progressive:
            return self._progressive_query(q, ds)
        def run():
            if isinstance(q, Q.GroupByQuery) and q.subtotals:
                # wire subtotalsSpec: same grouping-set expansion the SQL
                # path uses — the engine alone would silently run only
                # the full set
                from .api import execute_grouping_sets

                with span(SPAN_ENGINE, backend="device"):
                    df = execute_grouping_sets(
                        dataclasses.replace(q, subtotals=()), q.subtotals,
                        ds, self.ctx.engine,
                    )
                # internal bitmask column; real Druid events don't carry it
                return df.drop(columns=["__grouping_id"])
            # the serving core's native path (serve/): result cache
            # (exact hit = zero device dispatch; delta-aware after an
            # append) -> micro-batch fusion -> serial state-capturing
            # execution, with the computed answer published back
            if serve is None:
                with span(SPAN_ENGINE, backend="device"):
                    return self.ctx.engine.execute(q, ds)
            # ONE key computation per request (it JSON-serializes the
            # spec), shared by lookup and store
            rkey = serve.native_key(q, ds)
            hit = serve.cached_native(q, ds, key=rkey)
            if hit is not None:
                return hit
            # broker mode (cluster/, ISSUE 16): scatter the query's
            # assigned segments to historicals and ⊕ their states — the
            # result cache above rides the broker (an exact hit never
            # scatters) and fusion stays local-only below, so coverage
            # of the two tiers composes instead of competing
            cluster = getattr(self.ctx, "cluster", None)
            if cluster is not None and cluster.covers(q, ds):
                df = cluster.execute(q, ds)
                self.ctx._last_engine_metrics = cluster.last_metrics
                pc = current_partial()
                if rkey is not None and not (
                    pc is not None and pc.triggered
                ):
                    # frame-only: a gathered answer has no LOCAL state
                    # to delta-refresh, and a coverage-stamped partial
                    # must never seed the cache
                    serve.store_native(q, ds, df, key=rkey)
                return df
            fusable = self.ctx.engine.fusable(q, ds)
            if fusable:
                with span(SPAN_ENGINE, backend="device"):
                    fused = serve.fused_execute(q, ds)
                if fused is not None:
                    df, state, m = fused
                    self.ctx._last_engine_metrics = m
                    serve.store_native(q, ds, df, state=state, key=rkey)
                    return df
            if (
                fusable
                and rkey is not None
                and self.ctx.config.result_cache_entries > 0
            ):
                # capture the merged host state alongside the serial
                # execution so the next append refreshes this entry by
                # scanning only the delta (with the cache off nothing
                # would store it, and the fetch may carry HLL register
                # histograms instead of registers)
                with span(SPAN_ENGINE, backend="device"), \
                        self.ctx.engine.state_capture() as cap:
                    df = self.ctx.engine.execute(q, ds)
                # stamp the context's most-recent metrics: an earlier
                # cache hit left its own object pinned there, and
                # ctx.last_metrics prefers it over the engine's — a
                # stale "result-cache" would misattribute THIS execution
                self.ctx._last_engine_metrics = (
                    self.ctx.engine.last_metrics
                )
                serve.store_native(q, ds, df, state=cap["state"], key=rkey)
                return df
            with span(SPAN_ENGINE, backend="device"):
                df = self.ctx.engine.execute(q, ds)
            self.ctx._last_engine_metrics = self.ctx.engine.last_metrics
            if rkey is not None:
                # non-fusable GroupBy-family shapes (sparse/adaptive
                # tiers hold no dense state) still cache frame-only:
                # identical refreshes hit version-exact, appends miss
                serve.store_native(q, ds, df, key=rkey)
            return df

        try:
            self.ctx._sync_engine_resilience(self.ctx.engine)
            try:
                df = run()
            except Exception as err:
                # deadline expiry OUTSIDE the partial-capable loops
                # (planning, a blocking fetch, a ladder rung): same
                # drain-rerun the SQL surface does in
                # api._execute_with_resilience — trigger the collector
                # so every checkpoint no-ops, and the rerun yields the
                # well-formed coverage-stamped answer instead of a 504
                pc = current_partial()
                if pc is None or classify_error(err) != "deadline":
                    raise
                pc.trigger(getattr(err, "site", "") or "deadline")
                log.warning(
                    "deadline expired outside a partial-capable loop "
                    "(%s); draining a best-effort native answer", err,
                )
                df = run()
            # partial-result discipline (GL16xx): the native surface
            # publishes a deadline-bounded answer (partial span +
            # sdol_partial_results_total/coverage histogram) exactly like
            # ctx.sql's _stamp_partial path; _partial_headers below only
            # adds the wire header.  The cost receipt (ISSUE 9) rides the
            # same stamp point.
            df = self.ctx._stamp_receipt(self.ctx._stamp_partial(df))
        except Exception as err:
            # a transient device failure that survived the engine's retry
            # budget degrades exactly like the SQL path does; static
            # errors and deadlines keep their taxonomy (handled above)
            if res is None or classify_error(err) != "transient":
                raise
            return self._native_degraded(q, err, "device_failed")
        self._respond(druid_result_shape, q, df)

    def _native_degraded(self, q, err, reason: str):
        """Degrade one wire-native query to the host fallback via the
        QuerySpec->logical interpreter.  Unsupported shapes keep the old
        fail-fast contract (503 on an open circuit, the original error
        otherwise) — a wrong degraded answer is worse than no answer."""
        from .exec.wire_fallback import WireFallbackUnsupported
        from .plan.transforms import RewriteError

        try:
            df = self.ctx.execute_native_degraded(q, err, reason=reason)
        except (WireFallbackUnsupported, NotImplementedError, RewriteError) as e:
            # RewriteError covers config.fallback_execution=False: the
            # degraded route is administratively off, so an open breaker
            # must keep the old fail-fast 503 + Retry-After contract
            # (not surface as a 500 through the generic handler)
            if err is None:
                raise CircuitOpenError(
                    "device circuit open and this native query cannot "
                    f"degrade to the host fallback ({e}) — retry after "
                    "the breaker's cooldown"
                ) from e
            raise err
        self._respond(druid_result_shape, q, df)

    def _progressive_query(self, q, ds):
        """Chunked progressive response (ISSUE 7 tentpole (b)): one
        NDJSON line per refinement — {"sequence", "coverage", "partial",
        "final", "result"} — converging to the exact answer as segment
        batches complete.  The FIRST refinement is computed before the
        status line commits, so pre-execution errors still produce
        normal structured error responses; mid-stream failures emit a
        terminal {"error": ...} line (the status is already on the
        wire)."""
        self.ctx._sync_engine_resilience(self.ctx.engine)
        gen = self.ctx.engine.execute_progressive(q, ds)
        return self._stream_refinements(gen, lambda df: druid_result_shape(q, df))

    def _stream_refinements(self, gen, shape):
        """Drive one refinement generator onto the wire as chunked
        NDJSON — shared by the native route and the SQL route (ROADMAP
        3(b)) so the line protocol, error handling, and the deferred
        terminal chunk cannot drift between surfaces.  `shape` renders a
        refinement frame into the route's result payload."""
        from .obs import SPAN_STREAM_FLUSH, span

        item = next(gen)  # may raise -> structured error path
        self._begin_response(200, "application/x-ndjson")
        try:
            while True:
                df, info = item
                line = {
                    "sequence": info["sequence"],
                    "coverage": info["coverage"],
                    "partial": bool(info.get("partial", False)),
                    "final": bool(info["final"]),
                    "rows_seen": info.get("rows_seen"),
                    "rows_total": info.get("rows_total"),
                    "result": shape(df),
                }
                if line["final"]:
                    # the FINAL refinement carries the stream's cost
                    # receipt (ISSUE 9 satellite): progressive clients
                    # get the same attribution a buffered response puts
                    # in df.attrs / the response-context header
                    from .obs.prof import live_receipt

                    rc = live_receipt()
                    if rc is not None:
                        line["receipt"] = rc
                with span(SPAN_STREAM_FLUSH, sequence=info["sequence"]):
                    self._write_chunk(
                        json.dumps(line, default=_jsonable).encode()
                        + b"\n"
                    )
                if info["final"]:
                    break
                item = next(gen)
        except OSError as e:
            # the CLIENT went away mid-stream (broken pipe / reset):
            # there is no socket to write a terminal line to, and a
            # disconnect is not a server error — swallow it here so it
            # neither attempts a second response through _error(500) nor
            # inflates the /status/health server-error counters
            log.info(
                "progressive client disconnected mid-stream: %s",
                type(e).__name__,
            )
        except Exception as e:  # fault-ok: status already sent; emit a terminal error line
            log.error(
                "progressive stream failed: %s", type(e).__name__,
                exc_info=True,
            )
            try:
                self._write_chunk(
                    json.dumps(
                        {
                            "error": "progressive stream failed; see "
                            "server logs",
                            "errorClass": type(e).__name__,
                            "final": True,
                        }
                    ).encode()
                    + b"\n"
                )
            except OSError:
                pass  # dead socket: the log line above is the record
        finally:
            # the terminal 0-chunk is DEFERRED to do_POST, past the
            # query_trace exit: the client's read() completes only on
            # that chunk, so the finished trace is guaranteed to be in
            # the ring before the client can ask /druid/v2/trace for it
            self._pending_chunked_finish = 200

    def _sql_query(self, body: dict, qctx: dict):
        sql = body.get("query")
        if not sql:
            return self._error(400, 'body must be {"query": "SELECT ..."}')
        # priority lanes: SQL classifies from its planned rewrite (via
        # the plan cache, so repeated dashboard statements pay planning
        # once); anything unplannable gates interactive
        serve = getattr(self.ctx, "serve", None)
        lane_name = serve.lane_for_sql(sql) if serve is not None else None
        if lane_name is not None:
            from .obs.prof import note_lane

            note_lane(lane_name)
        if lane_name is not None and not self._acquire_lane(lane_name):
            return None
        res = self._resilience()
        try:
            if not self._admit(res):
                return None
            try:
                if qctx.get("progressive"):
                    # progressive SQL surface (ROADMAP 3(b)): chunked
                    # NDJSON refinements converging to the exact answer,
                    # same line protocol as the native route; shapes that
                    # cannot stream fall through to the buffered response
                    gen = self.ctx.sql_progressive(sql)
                    if gen is not None:
                        return self._stream_refinements(gen, _rows)
                self._respond(_rows, self.ctx.sql(sql))
            finally:
                if res is not None:
                    res.admission.release()
        finally:
            self._release_lane(lane_name)


class _OlapHTTPServer(ThreadingHTTPServer):
    # the stdlib listen backlog is 5: a burst of concurrent dashboard
    # connections (the workload the serving core exists for) overflows
    # it, the kernel drops the SYN, and the client retries after ~1 s —
    # a full second of invisible latency the handler never sees.  128
    # accommodates hammer-scale connection bursts.
    request_queue_size = 128

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        # connection -> the tracer's clock when it was accepted, from
        # `get_request` (the serving thread) to the handler's `setup`
        # (the connection's own thread), which takes it out again
        self.accepted_at: dict = {}

    def get_request(self):
        request, client_address = super().get_request()
        self.accepted_at[request] = _tracer_of(
            self.RequestHandlerClass.ctx
        ).clock()
        return request, client_address

    def shutdown_request(self, request):
        # a connection refused before its handler ran leaves its stamp
        self.accepted_at.pop(request, None)
        super().shutdown_request(request)


class OlapServer:
    """Threaded HTTP server over one TPUOlapContext.

    Queries execute on handler threads; the engine's caches are guarded by
    the catalog lock + XLA's own thread-safe dispatch, and query programs are
    cached per (query, schema) so concurrent BI dashboards share compiles.
    """

    def __init__(self, ctx, host: str = "127.0.0.1", port: int = 8082):
        handler = type("BoundHandler", (_Handler,), {"ctx": ctx})
        self.httpd = _OlapHTTPServer((host, port), handler)
        self._thread: Optional[threading.Thread] = None

    @property
    def port(self) -> int:
        return self.httpd.server_address[1]

    def start(self) -> "OlapServer":
        self._thread = threading.Thread(
            target=self.httpd.serve_forever, daemon=True
        )
        self._thread.start()
        return self

    def serve_forever(self):
        self.httpd.serve_forever()

    def shutdown(self):
        self.httpd.shutdown()
        self.httpd.server_close()
        if self._thread is not None:
            self._thread.join(timeout=5)
