"""Per-query span tracing: the observability layer's timeline substrate.

Reference parity: Druid emits server-side query metrics keyed by a
`queryId` the client may set in the query context, echoed back as the
`X-Druid-Query-Id` response header, and request logs are queryId-tagged
(SURVEY.md §5).  The TPU build's flat last-query `QueryMetrics` snapshot
cannot answer "which concurrent query retried?" or "where did this
deadline die?"; this module can:

  * **Span tree per query** — a `QueryTrace` rooted at a `query` span,
    with children for every lifecycle phase of a served request
    (`http_accept → http_read → lane → admission → plan → execute →
    [scope, engine → lower → h2d → segment_dispatch → device_fetch →
    finalize, post_process] → respond`, plus
    `fallback`/`retry`/`degraded` when a query leaves the happy path;
    `scope` is the request's one walk of the segments' intervals and
    zone maps, made for whoever asks first: the lane classifier under
    the root, else `lower`; `QueryTrace.scopes` answers the later asks
    and the span's `asks` counts them, ISSUE 38).  Span names
    are DRAWN FROM the `SPAN_*` constant
    registry below — the span-discipline lint pass (GL11xx) rejects
    ad-hoc strings so the taxonomy cannot fragment.
  * **query_id end-to-end** — generated at the server boundary (honoring
    Druid's `context.queryId`), carried by a contextvar through engine,
    sparse/adaptive/streaming exec, resilience, and the host fallback;
    stamped onto `QueryMetrics.query_id`.
  * **Instrumentation that disappears when idle** — `span(name)` costs
    one contextvar read when no trace is active; with a trace it is two
    clock reads, two list/lock operations and one flag test.  The clock
    is injectable (tests assert tracer overhead by *counting* clock
    calls, never by timing wall-clock).
  * **One timeline with the device** — while a `jax.profiler` session
    is open (`exec/metrics.trace(logdir)`, the benchmark's `--trace 1`)
    every span also writes itself into the profiler's trace as
    `sdol:<name>`, on the clock of the device's `XLA Ops`; the traced
    programs name their parts with `device_scope(SCOPE_*)`.
  * **Trace ring buffer** — finished traces serialize to JSON and land
    in a bounded FIFO ring served by `GET /druid/v2/trace/{query_id}`.
  * **Slow-query log** — a finished trace whose total exceeds
    `SessionConfig.slow_query_ms` logs its rendered span tree at
    WARNING through `utils/log.py`.

Concurrency: the contextvars give every handler thread its own active
trace/span, so concurrent queries cannot interleave their trees; the
per-trace lock makes child-append and finish safe if a span IS opened
from another thread (the streaming producer thread deliberately sees no
active trace — a fresh thread starts with an empty context).
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
import threading
import time
import uuid
from collections import OrderedDict
from typing import Any, Callable, Dict, List, Optional, Sequence

from ..utils.log import get_logger

log = get_logger("obs.trace")


# ---------------------------------------------------------------------------
# Span-name registry (the span-discipline lint pass GL11xx enforces that
# every `span(...)` call in the exec/resilience/serving modules names one
# of these constants — add the constant HERE first, then use it)
# ---------------------------------------------------------------------------

SPAN_QUERY = "query"  # root span of every trace
SPAN_ADMISSION = "admission"  # waiting for an admission slot
SPAN_PLAN = "plan"  # parse + plan (or plan-cache lookup)
SPAN_EXECUTE = "execute"  # device/fallback execution umbrella
SPAN_LOWER = "lower"  # query lowering + segment scoping
SPAN_H2D = "h2d"  # host->device column placement for one batch
SPAN_SEGMENT_DISPATCH = "segment_dispatch"  # one fused program dispatch
SPAN_DEVICE_FETCH = "device_fetch"  # blocking host fetch of partials
SPAN_FINALIZE = "finalize"  # host-side result materialization
SPAN_FALLBACK = "fallback"  # host interpreter run
SPAN_FALLBACK_DECODE = "fallback_decode"  # fallback table materialization
SPAN_RETRY = "retry"  # one transient-failure re-attempt
SPAN_DEGRADED = "degraded"  # breaker/failure degradation to the fallback
SPAN_SPARSE_DISPATCH = "sparse_dispatch"  # sort-compaction tier dispatch
SPAN_ADAPTIVE_PROBE = "adaptive_probe"  # adaptive phase-A presence pass
SPAN_STREAM_CHUNK = "stream_chunk"  # one streaming chunk dispatch
SPAN_INGEST = "ingest"  # one streamed append (ingest tier, ISSUE 6)
SPAN_INGEST_ENCODE = "ingest_encode"  # dictionary encode of an append batch
SPAN_COMPACT = "compact"  # delta -> historical roll of one datasource
SPAN_PARTIAL = "partial"  # deadline-bounded best-effort answer (coverage)
SPAN_STREAM_FLUSH = "stream_flush"  # one progressive-response refinement
SPAN_FUSED_BATCH = "fused_batch"  # one micro-batch fused execution (serve/)
SPAN_LANE = "lane"  # waiting for a priority-lane slot (serve/lanes.py)
SPAN_PREFETCH = "prefetch"  # async h2d issue overlapped behind compute
SPAN_WAL_APPEND = "wal_append"  # fsync'd journal write of one append batch
SPAN_WAL_REPLAY = "wal_replay"  # boot-time WAL replay of one datasource
SPAN_SNAPSHOT_FLUSH = "snapshot_flush"  # persistent segment snapshot commit
SPAN_ROLLUP = "rollup"  # ingest-time pre-aggregation of an append batch
SPAN_ARENA_BUILD = "arena_build"  # segment-stacked arena assembly (exec/arena.py)
SPAN_SCATTER = "scatter"  # broker: replica fetches in flight (cluster/)
SPAN_GATHER = "gather"  # broker: decode + coverage of gathered replies
SPAN_CLUSTER_MERGE = "cluster_merge"  # broker: ⊕ fold of replica states
SPAN_CLUSTER_RPC = "cluster_rpc"  # broker: ONE replica attempt (pool thread)
SPAN_HTTP_READ = "http_read"  # server: body read + JSON decode (before the root opens)
SPAN_SQL_PARSE = "sql_parse"  # SQL text -> logical plan, on a plan-cache miss
SPAN_ROUTE = "route"  # the cost model's choice of backend, tier and kernel
SPAN_PROGRAM_LOOKUP = "program_lookup"  # program-cache lookup / jitted-fn build
SPAN_ADAPTIVE_KEPT = "adaptive_kept"  # adaptive: kept-set memo, derive, nonzero
SPAN_RESPOND = "respond"  # server: result frame -> buffered response bytes
SPAN_SCOPE = "scope"  # the request's walk of the segments' intervals and zone maps
SPAN_ENGINE = "engine"  # api/server: one call into an engine
SPAN_POST_PROCESS = "post_process"  # api: host-side shaping of the frame
SPAN_HTTP_ACCEPT = "http_accept"  # server: accept() -> do_POST's first line
SPAN_SKETCH_ESTIMATE = "sketch_estimate"  # finalize: sketch states -> estimates
SPAN_GROUPING_SETS = "grouping_sets"  # api: a CUBE / ROLLUP / subtotals expansion
SPAN_ORDER_LIMIT = "order_limit"  # finalize: a groupBy answer's ORDER BY / LIMIT

SPAN_NAMES = frozenset(
    {
        SPAN_QUERY,
        SPAN_ADMISSION,
        SPAN_PLAN,
        SPAN_EXECUTE,
        SPAN_LOWER,
        SPAN_H2D,
        SPAN_SEGMENT_DISPATCH,
        SPAN_DEVICE_FETCH,
        SPAN_FINALIZE,
        SPAN_FALLBACK,
        SPAN_FALLBACK_DECODE,
        SPAN_RETRY,
        SPAN_DEGRADED,
        SPAN_SPARSE_DISPATCH,
        SPAN_ADAPTIVE_PROBE,
        SPAN_STREAM_CHUNK,
        SPAN_INGEST,
        SPAN_INGEST_ENCODE,
        SPAN_COMPACT,
        SPAN_PARTIAL,
        SPAN_STREAM_FLUSH,
        SPAN_FUSED_BATCH,
        SPAN_LANE,
        SPAN_PREFETCH,
        SPAN_WAL_APPEND,
        SPAN_WAL_REPLAY,
        SPAN_SNAPSHOT_FLUSH,
        SPAN_ROLLUP,
        SPAN_ARENA_BUILD,
        SPAN_SCATTER,
        SPAN_GATHER,
        SPAN_CLUSTER_MERGE,
        SPAN_CLUSTER_RPC,
        SPAN_HTTP_READ,
        SPAN_SQL_PARSE,
        SPAN_ROUTE,
        SPAN_PROGRAM_LOOKUP,
        SPAN_ADAPTIVE_KEPT,
        SPAN_RESPOND,
        SPAN_SCOPE,
        SPAN_ENGINE,
        SPAN_POST_PROCESS,
        SPAN_HTTP_ACCEPT,
        SPAN_SKETCH_ESTIMATE,
        SPAN_GROUPING_SETS,
        SPAN_ORDER_LIMIT,
    }
)

# ---------------------------------------------------------------------------
# Device-scope registry: the names `device_scope(...)` puts into the HLO
# metadata (`op_name`) of the traced programs, so that a device operation
# in a profiler trace points at the part of the program it came from.
# Trace-time only: a scope changes no compiled code and no run time.
# ---------------------------------------------------------------------------

SCOPE_ARENA_SCAN = "sdol.arena_scan"  # body of the arena's scan over blocks
SCOPE_FILTER = "sdol.filter"  # intervals + the query's filter -> row mask
SCOPE_GROUP_KEYS = "sdol.group_keys"  # per-dim codes packed into one group id
SCOPE_AGG_INPUTS = "sdol.agg_inputs"  # virtual columns; the kernels' value rows
SCOPE_PARTIAL_AGG = "sdol.partial_agg"  # the partial-aggregate kernel call
SCOPE_CARRY_MERGE = "sdol.carry_merge"  # cross-segment / cross-batch fold
SCOPE_PRESENCE = "sdol.presence"  # adaptive phase A: per-dim presence counts
SCOPE_KEPT_REMAP = "sdol.kept_remap"  # adaptive phase B: code -> compact code
SCOPE_SPARSE_SORT = "sdol.sparse_sort"  # sparse tier: sort-compaction of keys
SCOPE_BOUNDARY_MERGE = "sdol.boundary_merge"  # mesh: every collective over ICI
SCOPE_SKETCH_FOLD = "sdol.sketch_fold"  # a segment's sketch partials (HLL fold)
SCOPE_SKETCH_MERGE = "sdol.sketch_merge"  # sketch states merged across segments
SCOPE_SKETCH_HISTOGRAM = "sdol.sketch_histogram"  # HLL registers -> value counts
SCOPE_SET_ROLLUP = "sdol.set_rollup"  # grouping sets reduced from one merged state

SCOPE_NAMES = frozenset(
    {
        SCOPE_ARENA_SCAN,
        SCOPE_FILTER,
        SCOPE_GROUP_KEYS,
        SCOPE_AGG_INPUTS,
        SCOPE_PARTIAL_AGG,
        SCOPE_CARRY_MERGE,
        SCOPE_PRESENCE,
        SCOPE_KEPT_REMAP,
        SCOPE_SPARSE_SORT,
        SCOPE_BOUNDARY_MERGE,
        SCOPE_SKETCH_FOLD,
        SCOPE_SKETCH_MERGE,
        SCOPE_SKETCH_HISTOGRAM,
        SCOPE_SET_ROLLUP,
    }
)


def device_scope(name: str):
    """`jax.named_scope` under a registered `SCOPE_*` name: every
    operation traced inside carries the name in its HLO metadata.  A
    context manager, and a decorator for a function that is one scope."""
    import jax

    if name not in SCOPE_NAMES:
        raise ValueError(f"unregistered device scope {name!r}")
    return jax.named_scope(name)


# ---------------------------------------------------------------------------
# The profiler mirror: with a `jax.profiler` session open, every span also
# lies on the `/host:CPU` plane of the profiler's trace, beside the
# device's `XLA Ops` and on their clock, as `sdol:<name>`.  Without a
# session it is one flag test.  (The tree keeps its own injectable clock.)
# An annotation cannot be back-dated: the root's mirror `sdol:query` opens
# where the trace does, so `sdol:http_read` (read before the query id is
# known, hence `query_id=""`) lies BEFORE it, and belongs to the
# `sdol:query` that begins next on its thread.  The tree's root, on its
# own clock, starts where `http_read` did.
# ---------------------------------------------------------------------------

PROFILER_PREFIX = "sdol:"


_NO_SESSION = contextlib.nullcontext()
_annotation = None  # jax.profiler.TraceAnnotation, imported at first use


def _mirror(name: str, query_id: str):
    """Context manager: `TraceAnnotation("sdol:<name>")` while a profiler
    session is open, nothing otherwise."""
    global _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation

        _annotation = TraceAnnotation
    if not _annotation.is_enabled():
        return _NO_SESSION
    return _annotation(PROFILER_PREFIX + name, query_id=query_id)


def new_query_id() -> str:
    """Druid-shaped opaque query id (uuid4, the broker's own format)."""
    return str(uuid.uuid4())


# ---------------------------------------------------------------------------
# Spans
# ---------------------------------------------------------------------------


class Span:
    """One timed phase.  Start/end are tracer-clock readings (seconds);
    `attrs` carry small JSON-able facts (segment index, retry attempt);
    `events` are point-in-time observations inside the phase (the
    breaker state read at routing time) — a name, a clock reading, and
    small attrs, without opening a child span.

    `grafts` hold PRE-RENDERED remote subtrees (cluster/, ISSUE 19): a
    historical's already-serialized span tree splices under the broker's
    `cluster_rpc` span at render time.  Grafted nodes keep their REMOTE
    clock origin — `start_ms` inside a graft is relative to the remote
    root, not this trace's (cross-process clocks don't join); they carry
    `attrs.remote` so consumers can tell."""

    __slots__ = ("name", "start", "end", "attrs", "children", "events",
                 "grafts")

    def __init__(self, name: str, start: float, attrs: Optional[dict] = None):
        self.name = name
        self.start = start
        self.end: Optional[float] = None
        self.attrs = attrs or {}
        self.children: List["Span"] = []
        self.events: List[Dict[str, Any]] = []
        self.grafts: List[dict] = []

    @property
    def duration_ms(self) -> float:
        if self.end is None:
            return 0.0
        return (self.end - self.start) * 1e3

    def to_dict(self, origin: float, now: Optional[float] = None) -> dict:
        # `now` supports LIVE snapshots (obs/prof.py receipt builds
        # mid-query): an unfinished span measures to the provisional
        # clock reading instead of reporting zero
        dur = self.duration_ms
        if self.end is None and now is not None:
            dur = (now - self.start) * 1e3
        d: Dict[str, Any] = {
            "name": self.name,
            "start_ms": round((self.start - origin) * 1e3, 3),
            "duration_ms": round(dur, 3),
        }
        if self.attrs:
            d["attrs"] = dict(self.attrs)
        if self.events:
            d["events"] = [
                {
                    "name": e["name"],
                    "at_ms": round((e["at"] - origin) * 1e3, 3),
                    **(
                        {"attrs": dict(e["attrs"])} if e["attrs"] else {}
                    ),
                }
                for e in self.events
            ]
        if self.children or self.grafts:
            d["children"] = [
                c.to_dict(origin, now) for c in self.children
            ] + list(self.grafts)
        return d


class QueryTrace:
    """The span tree of ONE query, rooted at a `query` span."""

    def __init__(
        self,
        query_id: str,
        clock: Callable[[], float] = time.perf_counter,
        query_type: str = "",
    ):
        self.query_id = query_id
        self.query_type = query_type
        self._clock = clock
        self._lock = threading.Lock()
        self.root = Span(SPAN_QUERY, clock())
        # per-query cost receipt (obs/prof.py), stamped at trace close;
        # rides every to_dict so the ring doc, bench detail artifacts,
        # and /druid/v2/trace/{id} all carry it
        self.receipt: Optional[dict] = None
        # cross-process parentage (cluster/, ISSUE 19): a historical
        # serving a broker RPC records the broker's span id here so the
        # OTLP export joins both processes into one tree
        self.parent_span_id: str = ""
        # who wants the receipt of the CLOSED trace: `QueryMetrics`
        # objects (their `.receipt`) and result frames' `.attrs` (their
        # "receipt" key), registered by `stamp_receipt_on` while the
        # query runs and stamped once, at close
        self._receipt_sinks: List[Any] = []
        # the segment scopes this request has resolved, one entry a walk
        # (exec/engine.py `segments_in_scope` writes and reads them): a
        # request walks the zone maps once and every later ask of the
        # same filter over the same `DataSource` is answered from here
        # (ISSUE 38).  Emptied at `finish`: nothing outlives the request
        self.scopes: List[tuple] = []

    def start_span(
        self, name: str, parent: Optional[Span], attrs: Optional[dict] = None
    ) -> Span:
        """INTERNAL pairing API — instrumented code must go through the
        `span(...)` context manager (span-discipline/GL1102): a manual
        begin/end pair leaks the span on every early return or raise."""
        s = Span(name, self._clock(), attrs)
        with self._lock:
            (parent or self.root).children.append(s)
        return s

    def end_span(self, s: Span) -> None:
        s.end = self._clock()

    def add_event(
        self, s: Span, name: str, attrs: Optional[dict] = None
    ) -> None:
        with self._lock:
            s.events.append(
                {"name": name, "at": self._clock(), "attrs": attrs or {}}
            )

    def graft(self, s: Span, subtree: dict) -> None:
        """Splice a PRE-RENDERED remote span subtree (a historical's
        `to_dict()["spans"]` or an `untraced` stub) under `s`.  Lock-safe
        like start_span — the scatter pool threads graft concurrently."""
        with self._lock:
            s.grafts.append(subtree)

    def adopt_early(self, early: Sequence[Span]) -> None:
        """Put the spans that closed before this trace opened first under
        the root, in the order given, which is the order in time (the
        server's `http_accept`, then its `http_read`), and start the
        root with the first of them."""
        with self._lock:
            self.root.start = min(self.root.start, *(s.start for s in early))
            self.root.children[0:0] = early

    def stamp_receipt_on(self, metrics=None, frame=None) -> None:
        """Register who gets the closed trace's receipt: a
        `QueryMetrics` and/or a result frame (anything with a dict
        `.attrs`; other results are skipped)."""
        with self._lock:
            if metrics is not None:
                self._receipt_sinks.append(metrics)
            if isinstance(getattr(frame, "attrs", None), dict):
                self._receipt_sinks.append(frame.attrs)

    def _stamp_receipt(self) -> None:
        with self._lock:
            sinks, self._receipt_sinks = self._receipt_sinks, []
        for sink in sinks:
            if isinstance(sink, dict):  # a result frame's attrs
                sink["receipt"] = self.receipt
            else:  # a QueryMetrics
                sink.receipt = self.receipt

    def finish(self) -> None:
        with self._lock:
            if self.root.end is None:
                self.root.end = self._clock()
        self.scopes.clear()

    @property
    def total_ms(self) -> float:
        return self.root.duration_ms

    def to_dict(self) -> dict:
        d = {
            "query_id": self.query_id,
            "query_type": self.query_type,
            "total_ms": round(self.total_ms, 3),
            "spans": self.root.to_dict(self.root.start),
        }
        if self.parent_span_id:
            d["parent_span_id"] = self.parent_span_id
        if self.receipt is not None:
            d["receipt"] = self.receipt
        return d

    def to_dict_live(self) -> dict:
        """Provisional snapshot of a trace still in flight: unfinished
        spans (including the root) measure to 'now' under the tracer's
        own clock — what obs.prof.live_receipt folds into the receipt
        the response headers and df.attrs carry."""
        now = self._clock()
        root_end = self.root.end if self.root.end is not None else now
        return {
            "query_id": self.query_id,
            "query_type": self.query_type,
            "total_ms": round((root_end - self.root.start) * 1e3, 3),
            "spans": self.root.to_dict(self.root.start, now),
        }

    def render(self) -> str:
        """Indented phase/latency lines (the slow-query-log body)."""
        lines: List[str] = []

        def walk(s: Span, depth: int) -> None:
            attrs = (
                " " + " ".join(f"{k}={v}" for k, v in sorted(s.attrs.items()))
                if s.attrs
                else ""
            )
            lines.append(
                f"{'  ' * depth}{s.name:<20} {s.duration_ms:>9.2f}ms{attrs}"
            )
            for e in s.events:
                eattrs = " ".join(
                    f"{k}={v}" for k, v in sorted(e["attrs"].items())
                )
                lines.append(
                    f"{'  ' * (depth + 1)}@ {e['name']}"
                    f"{' ' + eattrs if eattrs else ''}"
                )
            for c in s.children:
                walk(c, depth + 1)

        walk(self.root, 0)
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# Active-trace plumbing (contextvars: per-thread/per-context isolation)
# ---------------------------------------------------------------------------

_active_trace: contextvars.ContextVar[Optional[QueryTrace]] = (
    contextvars.ContextVar("sdol_active_trace", default=None)
)
_active_span: contextvars.ContextVar[Optional[Span]] = contextvars.ContextVar(
    "sdol_active_span", default=None
)


def current_trace() -> Optional[QueryTrace]:
    return _active_trace.get()


def current_query_id() -> str:
    tr = _active_trace.get()
    return tr.query_id if tr is not None else ""


def current_span() -> Optional[Span]:
    """The innermost open span of the active trace (None without one) —
    how the prof sync helpers annotate the span they fired inside."""
    return _active_span.get()


class span:
    """Open a child span of the active trace; a no-op (one contextvar
    read) when no trace is active.  THE way instrumented code creates
    spans — every early return / raise path closes the span because the
    context manager owns the pairing (span-discipline/GL1102).  `with
    span(NAME, **attrs) as s:` yields the open `Span`, or None without a
    trace.  A class with `__enter__`/`__exit__` and not a generator
    under `contextlib.contextmanager`: the same pairing at two thirds of
    the cost a span, which every request pays some twenty times
    (ISSUE 37)."""

    __slots__ = ("_name", "_attrs", "_trace", "_span", "_token", "_mirror")

    def __init__(self, name: str, **attrs):
        self._name = name
        self._attrs = attrs

    def __enter__(self) -> Optional[Span]:
        tr = self._trace = _active_trace.get()
        if tr is None:
            return None
        s = self._span = tr.start_span(
            self._name, _active_span.get(), self._attrs or None
        )
        self._token = _active_span.set(s)
        self._mirror = None
        mirror = _mirror(self._name, tr.query_id)
        if mirror is not _NO_SESSION:
            try:
                mirror.__enter__()
            except BaseException:
                self._close()
                raise
            self._mirror = mirror
        return s

    def __exit__(self, *exc) -> bool:
        if self._trace is None:
            return False
        try:
            if self._mirror is not None:
                self._mirror.__exit__(*exc)
        finally:
            self._close()
        return False

    def _close(self) -> None:
        _active_span.reset(self._token)
        self._trace.end_span(self._span)


@contextlib.contextmanager
def span_in(trace: Optional[QueryTrace], parent: Optional[Span],
            name: str, **attrs):
    """Open a span on an EXPLICIT trace handle, under an explicit parent
    — the sanctioned pairing for pool threads, where the contextvar
    trace is invisible by design (a fresh thread starts with an empty
    context).  The broker's scatter workers (cluster/broker.py) thread
    (trace, scatter-span) through to here so every replica attempt gets
    its own `cluster_rpc` span.  Owns the begin/end pairing exactly like
    `span(...)` (span-discipline/GL1102, trace-propagation/GL2702: the
    name must be a registered SPAN_* constant).  No-op when `trace` is
    None (the caller ran without an active trace)."""
    if trace is None:
        yield None
        return
    s = trace.start_span(name, parent, attrs or None)
    try:
        with _mirror(name, trace.query_id):
            yield s
    finally:
        trace.end_span(s)


def span_around(name: str):
    """Decorator form of `span(name)`: every call of the function runs
    inside a child span (for functions that ARE one phase — the program
    -cache lookups).  Same no-op without a trace, same name contract
    (span-discipline/GL1101)."""

    def decorate(fn):
        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)

        return wrapped

    return decorate


def span_event(name: str, **attrs) -> None:
    """Attach a point-in-time event to the ACTIVE span (no child span,
    no duration): the routing layer records the breaker state it
    observed, retries note which error class struck.  A no-op (one
    contextvar read) when no trace is active."""
    tr = _active_trace.get()
    if tr is None:
        return
    s = _active_span.get()
    tr.add_event(s if s is not None else tr.root, name, attrs or None)


# ---------------------------------------------------------------------------
# Ring buffer + tracer
# ---------------------------------------------------------------------------


class TraceRing:
    """Bounded FIFO of finished traces, keyed by query_id.  A repeated
    query_id overwrites in place (Druid lets clients reuse ids); capacity
    evicts the OLDEST insertion."""

    def __init__(self, capacity: int = 64):
        self.capacity = max(1, int(capacity))
        self._lock = threading.Lock()
        self._traces: "OrderedDict[str, dict]" = OrderedDict()

    def put(self, trace_dict: dict) -> None:
        qid = trace_dict.get("query_id", "")
        with self._lock:
            if qid in self._traces:
                self._traces.pop(qid)
            self._traces[qid] = trace_dict
            while len(self._traces) > self.capacity:
                self._traces.popitem(last=False)

    def get(self, query_id: str) -> Optional[dict]:
        with self._lock:
            return self._traces.get(query_id)

    def ids(self) -> List[str]:
        with self._lock:
            return list(self._traces)

    def __len__(self) -> int:
        with self._lock:
            return len(self._traces)


class Tracer:
    """Owns the clock, the finished-trace ring, and trace lifecycle.

    `clock` is injectable so tests measure tracer overhead by counting
    calls under a deterministic clock instead of timing wall-clock; the
    ring capacity is `SessionConfig.trace_ring_capacity` when built by a
    TPUOlapContext."""

    def __init__(
        self,
        clock: Callable[[], float] = time.perf_counter,
        capacity: int = 64,
        otlp_path: Optional[str] = None,
        prof_sample_rate: float = 0.0,
    ):
        self.clock = clock
        self.ring = TraceRing(capacity)
        self.last: Optional[QueryTrace] = None
        # ROADMAP obs follow-up (d): emit-only OTLP export behind a
        # config flag — finished trace dicts append (OTLP/JSON
        # ResourceSpans, one per line) to this path; no collector, no
        # network, no tier-1 dependency
        self.otlp_path = otlp_path
        # performance attribution (obs/prof.py, ISSUE 9): every owned
        # trace arms a ProfScope; the sampler decides which queries pay
        # the honest-device-timing sync points.  Deterministic (no RNG)
        # and force-armable (`force_sample_next`) so a bench can collect
        # one honest receipt per query without perturbing its timed reps.
        from .prof import RateSampler

        self.sampler = RateSampler(prof_sample_rate)

    def force_sample_next(self) -> None:
        """Arm honest device timing for the NEXT owned trace regardless
        of the configured sample rate."""
        self.sampler.force_next()

    @contextlib.contextmanager
    def early_span(self, name: str, start: Optional[float] = None, **attrs):
        """A span of work that has to happen BEFORE its trace can open:
        the server reads and decodes the request body to learn the query
        id the trace is opened under.  Yields a detached `Span` on this
        tracer's clock; `query_trace(early=...)` then adopts it, so the
        root starts where the request did and the read is a child like
        any other phase.  Its profiler mirror carries no query id and
        precedes its root's (see "The profiler mirror" above).
        `start` is an earlier reading of this tracer's clock (the
        server's stamp at `accept()`): the span is back-dated to it and
        has no mirror, since an annotation cannot be.
        Same pairing contract as `span(...)` (span-discipline/GL1102)."""
        s = Span(name, self.clock() if start is None else start, attrs or None)
        try:
            with _mirror(name, "") if start is None else _NO_SESSION:
                yield s
        finally:
            s.end = self.clock()

    @contextlib.contextmanager
    def query_trace(
        self,
        query_id: Optional[str] = None,
        query_type: str = "",
        slow_ms: float = 0.0,
        parent_span_id: str = "",
        early: Sequence[Span] = (),
    ):
        """Open (or join) the per-query trace.  The OUTERMOST scope wins,
        exactly like `resilience.deadline_scope`: the server boundary
        starts the trace and `ctx.sql` inside it joins rather than
        nesting a second root.  `parent_span_id` stamps cross-process
        parentage (a historical trace opened under a broker RPC span);
        `early` are the closed `early_span`s the new root adopts, in
        their order in time.  The receipt's `close_ms` is this method's
        own `finally`: what the tracer costs a request after its root
        has ended and before the caller goes on (the server writes the
        buffered answer only then)."""
        existing = _active_trace.get()
        if existing is not None:
            yield existing
            return
        from . import prof as _prof

        tr = QueryTrace(
            query_id or new_query_id(), clock=self.clock,
            query_type=query_type,
        )
        if parent_span_id:
            tr.parent_span_id = str(parent_span_id)
        if early:
            tr.adopt_early(early)
        tok_t = _active_trace.set(tr)
        tok_s = _active_span.set(tr.root)
        ps = _prof.ProfScope(sampled=self.sampler.take())
        tok_p = _prof.activate(ps)
        try:
            with _mirror(SPAN_QUERY, tr.query_id):
                yield tr
        finally:
            _active_span.reset(tok_s)
            _active_trace.reset(tok_t)
            tr.finish()
            t_close = self.clock()  # `wall_ms` ended; `close_ms` begins
            self.last = tr
            doc = tr.to_dict()
            # per-query cost receipt (ISSUE 9): fold the finished span
            # tree + the prof scope's counters into the attribution doc
            # and feed the rolling workload profiler — both must never
            # fail a query
            try:
                tr.receipt = _prof.build_receipt(doc, ps)
                doc["receipt"] = tr.receipt
                # the one receipt of the closed trace is what the query's
                # QueryMetrics and result frame hold from here on
                tr._stamp_receipt()
                _prof.workload_profiler().observe(doc, ps)
            except Exception:  # fault-ok: attribution must not fail queries
                log.warning("receipt build failed", exc_info=True)
            _prof.deactivate(tok_p)
            self.ring.put(doc)
            if self.otlp_path:
                from .otlp import append_otlp

                try:
                    append_otlp(self.otlp_path, doc)
                except OSError:  # fault-ok: export must never fail a query
                    log.warning(
                        "OTLP export to %s failed", self.otlp_path,
                        exc_info=True,
                    )
            if slow_ms and slow_ms > 0 and tr.total_ms >= slow_ms:
                log.warning(
                    "slow query %s: %.1fms >= %.0fms threshold\n%s",
                    tr.query_id, tr.total_ms, slow_ms, tr.render(),
                )
            if tr.receipt is not None:
                # written into the dict the sinks, the ring's doc and the
                # profiler's window already hold
                tr.receipt["close_ms"] = round(
                    (self.clock() - t_close) * 1e3, 3
                )

    def last_trace_dict(self) -> Optional[dict]:
        return self.last.to_dict() if self.last is not None else None


_default_tracer: Optional[Tracer] = None
_default_tracer_lock = threading.Lock()


def default_tracer() -> Tracer:
    """Process-default tracer for code running outside a TPUOlapContext
    (direct Engine use, tooling)."""
    global _default_tracer
    if _default_tracer is None:
        with _default_tracer_lock:
            if _default_tracer is None:
                _default_tracer = Tracer()
    return _default_tracer
