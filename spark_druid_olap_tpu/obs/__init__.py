"""Observability subsystem: per-query span tracing + process metrics.

Two halves (ISSUE 4 tentpole):

  * `obs.trace` — a lock-safe, injectable-clock span tracer producing a
    per-query span tree attached to a Druid-parity `query_id`, a bounded
    trace ring buffer served over HTTP, and the slow-query log.
  * `obs.registry` — a process-wide Prometheus-style metrics registry
    (counters / gauges / histograms) the engines, resilience layer, and
    HTTP server publish into; rendered at `GET /status/metrics`.

Instrumented code imports from HERE (`from .obs import span, SPAN_...`)
so the span-name registry and the context-manager discipline stay in one
place — the span-discipline lint pass (GL11xx) enforces both.
"""

from .registry import (  # noqa: F401
    MetricsRegistry,
    bounded_label,
    get_registry,
    record_compaction,
    record_ingest,
    record_partial,
    record_cluster_health,
    record_cluster_rpc,
    record_query_metrics,
    record_rollup,
    record_snapshot_flush,
    record_snapshot_sweep,
    record_storage_load,
    record_wal_append,
    record_wal_replay,
)
from . import prof  # noqa: F401  (performance attribution, ISSUE 9)
from .trace import (  # noqa: F401
    SCOPE_AGG_INPUTS,
    SCOPE_ARENA_SCAN,
    SCOPE_BOUNDARY_MERGE,
    SCOPE_CARRY_MERGE,
    SCOPE_FILTER,
    SCOPE_GROUP_KEYS,
    SCOPE_KEPT_REMAP,
    SCOPE_NAMES,
    SCOPE_PARTIAL_AGG,
    SCOPE_PRESENCE,
    SCOPE_SKETCH_FOLD,
    SCOPE_SKETCH_HISTOGRAM,
    SCOPE_SKETCH_MERGE,
    SCOPE_SPARSE_SORT,
    SPAN_ADAPTIVE_KEPT,
    SPAN_ADAPTIVE_PROBE,
    SPAN_ADMISSION,
    SPAN_ARENA_BUILD,
    SPAN_CLUSTER_MERGE,
    SPAN_CLUSTER_RPC,
    SPAN_COMPACT,
    SPAN_DEGRADED,
    SPAN_DEVICE_FETCH,
    SPAN_ENGINE,
    SPAN_EXECUTE,
    SPAN_FALLBACK,
    SPAN_FALLBACK_DECODE,
    SPAN_FINALIZE,
    SPAN_FUSED_BATCH,
    SPAN_GATHER,
    SPAN_H2D,
    SPAN_HTTP_ACCEPT,
    SPAN_HTTP_READ,
    SPAN_INGEST,
    SPAN_INGEST_ENCODE,
    SPAN_LANE,
    SPAN_LOWER,
    SPAN_NAMES,
    SPAN_PARTIAL,
    SPAN_PLAN,
    SPAN_POST_PROCESS,
    SPAN_PREFETCH,
    SPAN_PROGRAM_LOOKUP,
    SPAN_QUERY,
    SPAN_RESPOND,
    SPAN_RETRY,
    SPAN_ROLLUP,
    SPAN_ROUTE,
    SPAN_SCATTER,
    SPAN_SCOPE,
    SPAN_SEGMENT_DISPATCH,
    SPAN_SKETCH_ESTIMATE,
    SPAN_SNAPSHOT_FLUSH,
    SPAN_SPARSE_DISPATCH,
    SPAN_SQL_PARSE,
    SPAN_STREAM_CHUNK,
    SPAN_STREAM_FLUSH,
    SPAN_WAL_APPEND,
    SPAN_WAL_REPLAY,
    QueryTrace,
    Span,
    TraceRing,
    Tracer,
    current_query_id,
    current_trace,
    default_tracer,
    device_scope,
    new_query_id,
    span,
    span_around,
    span_event,
    span_in,
)
