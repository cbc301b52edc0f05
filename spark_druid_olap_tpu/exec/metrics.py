"""Per-query execution metrics (the observability subsystem).

Reference parity: the reference leans on Spark SQL scan-node metrics plus
Druid's server-side query metrics and has no dedicated tracer (SURVEY.md §5);
the TPU build owes the BASELINE metric set — rows/sec/chip, HBM bytes
streamed, kernel vs collective time.  `QueryMetrics` is populated by the
engines on every execution and surfaced via `TPUOlapContext.last_metrics`,
`explain_analyze()`, and bench detail JSON.

Phase semantics (wall-clock, single process):
  * `h2d_ms` / `h2d_bytes` — host->device column transfers this query caused
    (zero on residency-cache hits: the streamed-bytes metric).
  * `compile_ms` — time of the first program invocation when the XLA program
    for this (query, shape) was not yet compiled; includes that first
    execution (JAX jit compiles lazily; isolating pure-compile would need
    AOT shape pinning the segment loop doesn't want).  0 on warm paths.
  * `device_ms` — dispatch + block time of the remaining (steady-state)
    program calls plus the result fetch.
  * `est_collective_ms` — modelled ICI merge time for distributed runs
    (state bytes x ring factor / configured bandwidth); measured split of
    kernel-vs-collective inside one fused SPMD program is profiler
    territory: use `trace()` below (the collectives lie under the device
    scope `sdol.boundary_merge`).
  * `collective_bytes` / `shard_steps` / `shards` — counted, not modelled:
    what the mesh's merges moved and how the scope fell on the shards.
  * `finalize_ms` — host-side result materialization.

`trace(logdir)` wraps `jax.profiler.trace` for the deep-dive path
(tensorboard-viewable device timelines incl. per-collective timing).
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Optional


@dataclasses.dataclass
class QueryMetrics:
    query_type: str = ""
    strategy: str = ""
    # datasource the query scanned — labels the per-datasource traffic
    # counters (obs/registry.py, behind the label-cardinality guard)
    datasource: str = ""
    # the query's end-to-end id (obs/trace.py): set by the server boundary
    # (Druid's context.queryId when the client sent one) or generated at
    # the api layer; correlates this snapshot with its span tree in the
    # trace ring buffer and the slow-query log
    query_id: str = ""
    # which executor answered: "device" (local/distributed engine) or
    # "fallback" (host pandas interpreter, exec/fallback.py) — a user must
    # be able to SEE that a query left the accelerated path
    executor: str = "device"
    distributed: bool = False
    mesh_shape: Optional[tuple] = None
    rows_scanned: int = 0
    # bytes of segment data the query's kernel actually reads (needed
    # columns x rows, incl. validity/time) — the roofline numerator:
    # bytes_scanned / total_s vs the backend's measured streaming
    # bandwidth (plan/calibrate.py `stream_bytes_per_s`) says how close
    # the scan is to the memory-bound ceiling
    bytes_scanned: int = 0
    segments: int = 0
    num_groups: int = 0
    h2d_bytes: int = 0
    h2d_ms: float = 0.0
    compile_ms: float = 0.0
    device_ms: float = 0.0
    est_collective_ms: float = 0.0
    # mesh requests only (0 on a single device).  `collective_bytes`: the
    # bytes of partial state the request's collectives moved over ICI, from
    # the shapes actually merged (each merged array's bytes x the ring
    # factor 2(n-1)/n of an allreduce, x (n-1) of an all_gather) — the
    # counted sibling of `est_collective_ms`.  `shard_steps` x `shards` /
    # `segments` says how evenly the scope fell on the shards: every shard
    # runs `shard_steps` segments' worth of rows (the arena's window `Lk`,
    # whole blocks with the ones outside the scope dead; a fraction where
    # the scope's rows are laid end to end and cut evenly), so 1.0 is an
    # even deal and an arena scope of 3 segments over 4 shards reads 4/3.
    collective_bytes: int = 0
    shard_steps: float = 0.0
    shards: int = 0
    finalize_ms: float = 0.0
    total_ms: float = 0.0
    bytes_resident: int = 0
    program_cache_hit: bool = False
    # fallback observability (ADVICE r4): how many Aggregate subtrees the
    # host interpreter offloaded to the device engine this query.  Assisted
    # subtrees accumulate in f32 (vs the interpreter's float64) — rank/
    # comparison windows over near-ties can order differently; non-zero
    # here is the flag to check when chasing such a divergence
    assist_subplans: int = 0
    # query-lifecycle resilience (resilience.py): transient-failure
    # re-dispatches this query paid; whether it answered DEGRADED (device
    # path failed or breaker open -> host fallback); whether it died on its
    # deadline; the breaker state observed when the query was routed; and
    # the exception class when the query ultimately failed
    retries: int = 0
    degraded: bool = False
    deadline_exceeded: bool = False
    circuit_state: str = ""
    error_class: Optional[str] = None
    # deadline-bounded partial answers (ISSUE 7): True when the result is
    # best-effort (deadline expired mid-scan and the merged partials were
    # returned); `coverage` is the fraction of in-scope rows the answer
    # saw (None when the denominator is unknowable, e.g. an unbounded
    # stream), with the seen/total row counts and their delta-vs-
    # historical split alongside
    partial: bool = False
    coverage: Optional[float] = None
    rows_seen: int = 0
    delta_rows_seen: int = 0
    # performance attribution (obs/prof.py, ISSUE 9): the per-query cost
    # receipt — device/host/transfer split from the span tree, transfer
    # bytes, compile counts, and cache-tier outcomes (result cache,
    # fusion, residency, program cache).  Stamped by the api layer from
    # the live trace; None for direct engine use outside a trace.
    receipt: Optional[dict] = None
    # micro-batch fusion (serve/, ISSUE 8): when > 0, this query executed
    # as one member of an N-query fused device program — its dispatch
    # round trip was amortized N ways.  h2d/compile on a fused member are
    # the batch totals split evenly across members (the batch moves one
    # shared column set).
    fused_batch: int = 0
    # sketch aggregations (PR 39): the bytes of merged sketch state the
    # request fetched from the device to the host (int32[G, 2^p] HLL
    # registers per hyperUnique); 0 for a query without sketches
    sketch_state_bytes: int = 0

    @property
    def rows_per_sec(self) -> float:
        if self.total_ms <= 0:
            return 0.0
        return self.rows_scanned / (self.total_ms / 1e3)

    @property
    def scan_bytes_per_sec(self) -> float:
        if self.total_ms <= 0:
            return 0.0
        return self.bytes_scanned / (self.total_ms / 1e3)

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["rows_per_sec"] = round(self.rows_per_sec)
        d["scan_bytes_per_sec"] = round(self.scan_bytes_per_sec)
        for k, v in list(d.items()):
            if isinstance(v, float):
                d[k] = round(v, 3)
        return d

    def describe(self) -> str:
        tgt = (
            f"mesh{self.mesh_shape}" if self.distributed else "single-device"
        )
        return (
            f"QueryMetrics[{self.query_type} strategy={self.strategy} "
            f"executor={self.executor} "
            f"target={tgt} rows={self.rows_scanned} segments={self.segments} "
            f"groups={self.num_groups} total={self.total_ms:.2f}ms "
            f"(h2d={self.h2d_ms:.2f}ms/{self.h2d_bytes}B "
            f"compile={self.compile_ms:.2f}ms device={self.device_ms:.2f}ms "
            f"est_collective={self.est_collective_ms:.2f}ms "
            f"finalize={self.finalize_ms:.2f}ms) "
            f"rows/s={self.rows_per_sec:,.0f} "
            f"resident={self.bytes_resident}B "
            f"cache_hit={self.program_cache_hit}"
            + (f" retries={self.retries}" if self.retries else "")
            + (" DEGRADED" if self.degraded else "")
            + (" DEADLINE-EXCEEDED" if self.deadline_exceeded else "")
            + (
                f" PARTIAL(coverage="
                f"{'?' if self.coverage is None else round(self.coverage, 4)})"
                if self.partial
                else ""
            )
            + (
                f" circuit={self.circuit_state}"
                if self.circuit_state and self.circuit_state != "closed"
                else ""
            )
            + "]"
        )


@contextlib.contextmanager
def trace(logdir: str):
    """jax.profiler trace context for deep dives (kernel + collective
    timelines in tensorboard); no-op if the profiler is unavailable.

    Only PROFILER STARTUP is guarded: the old `try: with ...: yield`
    shape swallowed exceptions raised by the BODY and then yielded a
    second time — `RuntimeError: generator didn't stop after throw` —
    so a failing profiled query crashed with the wrong error (ISSUE 4
    satellite).  Body errors now propagate untouched; only a broken
    profiler start/stop degrades to a no-op."""
    prof = None
    try:
        import jax

        prof = jax.profiler.trace(logdir)
        prof.__enter__()
    except Exception:  # fault-ok: profiler is optional; trace degrades to no-op
        prof = None
    try:
        yield
    finally:
        if prof is not None:
            try:
                prof.__exit__(None, None, None)
            except Exception:  # fault-ok: profiler teardown must not mask body errors
                pass
