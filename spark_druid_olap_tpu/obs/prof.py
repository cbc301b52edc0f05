"""Performance attribution layer (ISSUE 9 tentpole).

The span tree (obs/trace.py) records WHEN phases ran; this module makes
the numbers HONEST and turns them into per-query cost receipts:

  * **Honest device timing** — JAX dispatch is asynchronous, so a
    wall-clock span around `segment_dispatch` measures enqueue time,
    not device time.  `dispatch_sync`/`fetch_sync` are sampling-gated
    sync points (`SessionConfig.prof_sample_rate`): on a SAMPLED query
    they `block_until_ready` the dispatched state and split the
    enclosing span into `enqueue_ms` vs `device_ms` attrs; on an
    unsampled query they are a single contextvar read — ZERO added
    syncs, so the overlap the executors engineered is never destroyed
    by default.
  * **Transfer + residency accounting** — every h2d move records bytes
    and effective MB/s into `sdol_h2d_link_mbps` (the link-bound claim
    becomes a scrapeable histogram); the engine's residency cache
    exports per-datasource resident-bytes gauges and eviction counters.
  * **Program-cache family attribution** — hit/miss counters and
    compile-time totals per tagged program family (`fused`,
    `fused-batch`, `sparse`, `adaptive-presence`, ...), so "what is
    recompiling and why" is a registry query, not archaeology.
  * **Per-query cost receipts** — `build_receipt` folds a finished span
    tree into {device_ms, host_ms, transfer_ms, unattributed_ms, ...}
    by summing each span's EXCLUSIVE time (duration minus children)
    into a bucket by span name, and keeps the same exclusive times by
    name under `spans` ({name: {n, self_ms}}, adding up to `wall_ms`).
    Only the root `query` span's exclusive time is unattributed, so
    `device + host + transfer` vs `wall` is a real claim about
    lifecycle coverage, not an identity.  The receipt is built ONCE,
    when the trace closes, and stamped into the trace doc (served at
    `/druid/v2/trace/{id}`), `QueryMetrics.receipt` and
    `df.attrs["receipt"]`; a sampled query's `X-Druid-Response-Context`
    header and a progressive stream's last line, which leave before
    the close, carry a provisional one (`live_receipt`).
  * **Workload profiler** — a process-wide rolling window of finished
    queries behind `GET /status/profile`: top-K by device time,
    per-family compile totals, per-lane SLO burn-rate against the
    `lane_*_slo_ms` latency targets.

Accounting convention: compile time happens INSIDE the first dispatch
span, so `device_ms` includes it; the receipt reports `compile_ms`
separately as attribution detail, never as an additive term.
"""

from __future__ import annotations

import contextvars
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

from ..utils.log import get_logger
from .registry import bounded_label, get_registry
from .trace import (
    SPAN_PROGRAM_LOOKUP,
    current_query_id,
    current_span,
    current_trace,
)

log = get_logger("obs.prof")

# effective host->device MB/s per transfer, up through PCIe-class links
LINK_MBPS_BUCKETS = (
    1.0, 5.0, 10.0, 25.0, 45.0, 75.0, 150.0, 500.0,
    1000.0, 5000.0, 20000.0,
)

# span-name -> receipt bucket.  Device spans either block on device work
# (device_fetch) or — on a sampled query — are split
# honestly by the sync helpers; h2d is the transfer bucket; every OTHER
# span's exclusive time is host work.  The root `query` span's exclusive
# time stays unattributed (the coverage-claim denominator).
DEVICE_SPANS = frozenset(
    {
        "segment_dispatch",
        "device_fetch",
        "sparse_dispatch",
        "adaptive_probe",
        "stream_chunk",
    }
)
TRANSFER_SPANS = frozenset({"h2d"})
# prefetch spans measure ISSUE time of transfers overlapped behind live
# compute (exec/pipeline.py): they are deliberately NOT transfer stall —
# the overlap-efficiency denominator counts only foreground h2d time the
# dispatch loop actually waited behind
PREFETCH_SPANS = frozenset({"prefetch"})
# arena assembly (exec/arena.py): host-side stacking + placement issue of
# the segment-stacked layout — its own receipt bucket so the one-dispatch
# path's build cost is visible apart from generic host work (its child
# h2d spans still land in the transfer bucket)
ARENA_SPANS = frozenset({"arena_build"})
# cluster tier (cluster/, ISSUE 16): the broker's scatter span measures
# replica RPCs in flight (its per-reply `rpc` events carry the
# per-historical latency the receipt's cluster section aggregates);
# gather is decode + coverage accounting; cluster_merge is the ⊕ fold of
# replica states.  Each gets its own receipt bucket so a slow cluster
# query attributes to the wire, the decode, or the merge — not to
# generic host time.
SCATTER_SPANS = frozenset({"scatter"})
GATHER_SPANS = frozenset({"gather"})
CLUSTER_MERGE_SPANS = frozenset({"cluster_merge"})
# per-attempt RPC spans (ISSUE 19): cluster_rpc spans run CONCURRENTLY
# on pool threads under the one scatter span, so they are an OVERLAY on
# the scatter wall, not a partition of it — their time (and the remote
# subtrees grafted beneath them, which measure on the REMOTE clock) is
# excluded from the additive local buckets and folded into the
# per-historical `cluster.nodes` section instead
CLUSTER_RPC_SPANS = frozenset({"cluster_rpc"})
ROOT_SPAN = "query"

# device LAUNCH spans — the receipt's `dispatch_count` (ISSUE 14): how
# many host->device program launches served this query.  The arena path's
# whole point is driving this from O(segments) to O(1); device_fetch is a
# read-back, not a launch, so it does not count.  The mesh launches under
# the same names: one `segment_dispatch` per SPMD program.
DISPATCH_SPANS = frozenset(
    {
        "segment_dispatch",
        "sparse_dispatch",
        "adaptive_probe",
        "stream_chunk",
    }
)
# the blocking copy back of a launch's result: where the receipt's
# `in_flight_ms` ends
FETCH_SPANS = frozenset({"device_fetch"})


class ProfScope:
    """Per-query attribution accumulators, armed by the tracer for the
    lifetime of one query trace.  `sampled` gates the sync helpers;
    the cheap counters (cache outcomes, transfer bytes) collect on
    EVERY traced query.  Contextvar-confined like the trace itself
    (fresh threads see no scope), so the mutators need no lock."""

    __slots__ = (
        "sampled",
        "lane",
        "syncs",
        "transfer_ms",
        "transfer_bytes",
        "prefetch_ms",
        "prefetch_bytes",
        "compiles",
        "compile_ms",
        "residency_hits",
        "residency_misses",
        "program_cache",
        "result_cache",
        "fused_batch",
        "pending_family",
    )

    def __init__(self, sampled: bool = False):
        self.sampled = bool(sampled)
        self.lane = ""
        self.syncs = 0
        self.transfer_ms = 0.0
        self.transfer_bytes = 0
        self.prefetch_ms = 0.0
        self.prefetch_bytes = 0
        self.compiles = 0
        self.compile_ms = 0.0
        self.residency_hits = 0
        self.residency_misses = 0
        # family -> [hits, misses]
        self.program_cache: Dict[str, List[int]] = {}
        self.result_cache: Optional[str] = None  # "hit"/"delta" when served
        self.fused_batch = 0
        self.pending_family: Optional[str] = None


_active: contextvars.ContextVar[Optional[ProfScope]] = contextvars.ContextVar(
    "sdol_active_prof", default=None
)


def current_scope() -> Optional[ProfScope]:
    return _active.get()


def activate(scope: ProfScope):
    """INTERNAL (tracer lifecycle): arm `scope` for this context."""
    return _active.set(scope)


def deactivate(token) -> None:
    _active.reset(token)


def profiled() -> bool:
    """Is the CURRENT query sampled for honest device timing?"""
    ps = _active.get()
    return ps is not None and ps.sampled


class RateSampler:
    """Deterministic rate sampler: an accumulator advances by `rate`
    per query and fires on integer crossings — rate 1.0 samples every
    query, 0.25 every fourth, 0 never.  Deterministic (no wall-clock or
    RNG) so tests and benches can reason about exactly which queries
    paid a sync."""

    def __init__(self, rate: float = 0.0):
        self.rate = float(rate)
        self._acc = 0.0
        self._force = False
        self._lock = threading.Lock()

    def force_next(self) -> None:
        with self._lock:
            self._force = True

    def take(self) -> bool:
        with self._lock:
            if self._force:
                self._force = False
                return True
            r = self.rate
            if r <= 0:
                return False
            if r >= 1.0:
                return True
            self._acc += r
            if self._acc >= 1.0:
                self._acc -= 1.0
                return True
            return False


# ---------------------------------------------------------------------------
# Sampling-gated sync points (honest device timing)
# ---------------------------------------------------------------------------


def dispatch_sync(result, t_enqueue: float):
    """Called by an executor right after an async program dispatch, with
    the pre-dispatch clock reading.  Sampled query: block until the
    dispatched state is device-complete and split the enclosing span
    into `enqueue_ms` vs `device_ms`.  Unsampled: return `result`
    untouched — one contextvar read, no sync, overlap preserved."""
    ps = _active.get()
    if ps is None or not ps.sampled:
        return result
    import jax

    t1 = time.perf_counter()
    jax.block_until_ready(result)
    t2 = time.perf_counter()
    ps.syncs += 1
    s = current_span()
    if s is not None:
        s.attrs["enqueue_ms"] = round((t1 - t_enqueue) * 1e3, 3)
        s.attrs["device_ms"] = round((t2 - t1) * 1e3, 3)
    return result


def fetch_sync(tree):
    """Called just before a blocking `device_get`: on a sampled query,
    block first so the fetch span separates device-wait from the host
    copy (`device_wait_ms` attr).  No-op otherwise."""
    ps = _active.get()
    if ps is None or not ps.sampled:
        return tree
    import jax

    t0 = time.perf_counter()
    jax.block_until_ready(tree)
    ps.syncs += 1
    s = current_span()
    if s is not None:
        s.attrs["device_wait_ms"] = round(
            (time.perf_counter() - t0) * 1e3, 3
        )
    return tree


def transfer_sync(arr):
    """On a sampled query, block on a just-issued h2d placement so the
    caller's elapsed measurement is the real link time, not the enqueue.
    No-op otherwise (the unsampled measurement is the enqueue-observed
    'effective' rate — still recorded, labeled by the sampling bit in
    the receipt)."""
    ps = _active.get()
    if ps is None or not ps.sampled:
        return arr
    import jax

    jax.block_until_ready(arr)
    ps.syncs += 1
    return arr


# ---------------------------------------------------------------------------
# Transfer / residency / program-cache accounting
# ---------------------------------------------------------------------------


def record_h2d(nbytes: int, seconds: float, prefetched: bool = False) -> None:
    """One host->device move: effective MB/s into the link-utilization
    histogram (exemplared with the query id) + the scope's transfer
    accumulators.  This is what turns 'the rollup is link-bound at
    45 MB/s' from a postmortem into a scrapeable fact.

    `prefetched` moves were issued by the transfer pipeline (exec/
    pipeline.py) BEHIND live compute: they accumulate into the scope's
    prefetch counters, never into transfer stall — the
    overlap-efficiency denominator counts only foreground waits.  They
    are also EXCLUDED from the link histogram: a prefetched put is
    never synced, so its measured window is the async enqueue
    (~microseconds) and nbytes/dt would observe absurd multi-GB/s
    samples — with the pipeline on by default, the documented '45 MB/s
    floor' fact would drown in enqueue noise."""
    ps = _active.get()
    if not prefetched:
        mbps = nbytes / max(seconds, 1e-9) / 1e6
        get_registry().histogram(
            "sdol_h2d_link_mbps",
            "effective host->device link utilization per transfer (MB/s)",
            buckets=LINK_MBPS_BUCKETS,
        ).observe(mbps, exemplar=current_query_id() or None)
    if ps is not None:
        if prefetched:
            ps.prefetch_ms += seconds * 1e3
            ps.prefetch_bytes += int(nbytes)
        else:
            ps.transfer_ms += seconds * 1e3
            ps.transfer_bytes += int(nbytes)


def record_resident(datasource: str, bytes_now: int) -> None:
    """Publish a datasource's current resident-bytes (direction 4's
    residency-aware scheduling needs this denominator)."""
    ds = bounded_label("residency_datasource", datasource or "unknown")
    get_registry().gauge(
        "sdol_resident_bytes",
        "device-resident segment bytes, by datasource",
        labels=("datasource",),
    ).labels(datasource=ds).set(bytes_now)


def record_eviction(datasource: str, n: int = 1) -> None:
    ds = bounded_label("residency_datasource", datasource or "unknown")
    get_registry().counter(
        "sdol_residency_evictions_total",
        "residency-cache evictions under byte-budget pressure, "
        "by datasource",
        labels=("datasource",),
    ).labels(datasource=ds).inc(n)


def note_residency(hit: bool) -> None:
    ps = _active.get()
    if ps is None:
        return
    if hit:
        ps.residency_hits += 1
    else:
        ps.residency_misses += 1


def note_program_cache(family: str, hit: bool) -> None:
    """One program-cache lookup under its tagged key family."""
    fam = bounded_label("program_family", family or "unknown")
    get_registry().counter(
        "sdol_program_cache_total",
        "compiled-program cache lookups, by tagged key family / outcome",
        labels=("family", "outcome"),
    ).labels(family=fam, outcome="hit" if hit else "miss").inc()
    ps = _active.get()
    if ps is not None:
        c = ps.program_cache.setdefault(family, [0, 0])
        c[0 if hit else 1] += 1
        if not hit:
            ps.pending_family = family
        # the lookups run inside a `program_lookup` span: say which
        # family it looked up, and that a miss leaves a program to build
        # (its compile is paid by the first call, in the dispatch span)
        s = current_span()
        if s is not None and s.name == SPAN_PROGRAM_LOOKUP:
            s.attrs["family"] = family
            if not hit:
                s.attrs["compile"] = True


def note_compile(ms: float, family: Optional[str] = None) -> None:
    """First-trace/compile cost of one program build, attributed to the
    family whose cache miss triggered it (the scope remembers the last
    missed family when the caller cannot name it)."""
    ps = _active.get()
    if family is None and ps is not None:
        family = ps.pending_family
    fam = bounded_label("program_family", family or "unknown")
    reg = get_registry()
    reg.counter(
        "sdol_compiles_total",
        "program trace+compile events, by program-cache family",
        labels=("family",),
    ).labels(family=fam).inc()
    reg.counter(
        "sdol_compile_ms_total",
        "cumulative trace+compile milliseconds, by program-cache family",
        labels=("family",),
    ).labels(family=fam).inc(max(0.0, float(ms)))
    if ps is not None:
        ps.compiles += 1
        ps.compile_ms += max(0.0, float(ms))


def note_result_cache(outcome: str) -> None:
    ps = _active.get()
    if ps is not None:
        ps.result_cache = outcome


def note_fusion(batch: int) -> None:
    ps = _active.get()
    if ps is not None:
        ps.fused_batch = max(ps.fused_batch, int(batch))


def note_lane(lane: str) -> None:
    ps = _active.get()
    if ps is not None and lane:
        ps.lane = str(lane)


# ---------------------------------------------------------------------------
# Receipts
# ---------------------------------------------------------------------------


def _is_remote(node: dict) -> bool:
    """A grafted remote subtree root (broker-side clocks do not apply)."""
    return bool((node.get("attrs") or {}).get("remote"))


def _is_overlay(node: dict) -> bool:
    """Spans excluded from the local timeline partition: concurrent
    cluster_rpc attempts and grafted remote subtrees."""
    return str(node.get("name", "")) in CLUSTER_RPC_SPANS or _is_remote(
        node
    )


def _new_acc() -> Dict[str, Any]:
    return {
        "device": 0.0, "transfer": 0.0, "prefetch": 0.0, "host": 0.0,
        "arena_build": 0.0, "unattributed": 0.0, "dispatch_count": 0,
        "scatter": 0.0, "gather": 0.0, "cluster_merge": 0.0,
        # span name -> [count, exclusive ms]: the same exclusive times
        # as the buckets, kept by name (the per-layer metrics read these)
        "spans": {},
        # ms from the root's start: where the first launch span began,
        # where the last launch span and the last `device_fetch` ended
        # (None: the request has none) -- the receipt's `phases`
        "first_launch": None, "last_launch": None, "last_fetch": None,
    }


def _walk_exclusive(node: dict, acc: Dict[str, Any], depth: int) -> None:
    if depth == 0 and _is_overlay(node):
        # concurrent overlay / remote clock: handled by
        # _walk_cluster_nodes into per-node attribution, never the
        # additive local buckets (their sum could exceed the wall).
        # Below the root the children are filtered before the descent.
        return
    dur = float(node.get("duration_ms", 0.0))
    children = [
        c for c in (node.get("children") or ()) if not _is_overlay(c)
    ]
    child_sum = sum(float(c.get("duration_ms", 0.0)) for c in children)
    excl = max(0.0, dur - child_sum)
    name = str(node.get("name", ""))
    by_name = acc["spans"].setdefault(name, [0, 0.0])
    by_name[0] += 1
    by_name[1] += excl
    if name in DISPATCH_SPANS:
        acc["dispatch_count"] += 1
        start = float(node.get("start_ms", 0.0))
        if acc["first_launch"] is None or start < acc["first_launch"]:
            acc["first_launch"] = start
        acc["last_launch"] = max(acc["last_launch"] or 0.0, start + dur)
    elif name in FETCH_SPANS:
        end = float(node.get("start_ms", 0.0)) + dur
        acc["last_fetch"] = max(acc["last_fetch"] or 0.0, end)
    if depth == 0 and name == ROOT_SPAN:
        acc["unattributed"] += excl
    elif name in DEVICE_SPANS:
        acc["device"] += excl
    elif name in TRANSFER_SPANS:
        acc["transfer"] += excl
    elif name in PREFETCH_SPANS:
        acc["prefetch"] += excl
    elif name in ARENA_SPANS:
        acc["arena_build"] += excl
    elif name in SCATTER_SPANS:
        acc["scatter"] += excl
    elif name in GATHER_SPANS:
        acc["gather"] += excl
    elif name in CLUSTER_MERGE_SPANS:
        acc["cluster_merge"] += excl
    else:
        acc["host"] += excl
    for c in children:
        _walk_exclusive(c, acc, depth + 1)


def _fold_remote_buckets(graft: dict) -> Dict[str, float]:
    """Per-historical device/transfer/host attribution of ONE grafted
    remote subtree.  The remote receipt (riding inside the graft root)
    is authoritative when present; otherwise the subtree folds through
    the same bucket maps — remote spans use the same registered names."""
    rc = graft.get("receipt")
    if isinstance(rc, dict):
        return {
            "device_ms": float(rc.get("device_ms", 0.0) or 0.0),
            "transfer_ms": float(rc.get("transfer_ms", 0.0) or 0.0),
            "host_ms": float(rc.get("host_ms", 0.0) or 0.0),
            "remote_wall_ms": float(rc.get("wall_ms", 0.0) or 0.0),
        }
    acc = _new_acc()
    clean = dict(graft)
    attrs = dict(clean.get("attrs") or {})
    attrs.pop("remote", None)
    clean["attrs"] = attrs
    _walk_exclusive(clean, acc, 0)
    return {
        "device_ms": round(acc["device"], 3),
        "transfer_ms": round(acc["transfer"], 3),
        "host_ms": round(acc["host"], 3),
        "remote_wall_ms": float(graft.get("duration_ms", 0.0) or 0.0),
    }


def _fold_rpc_span(c: dict, nodes: Dict[str, Dict[str, Any]]) -> None:
    """One `cluster_rpc` span (ISSUE 19) into its node's bucket: attempt
    count/latency/outcome plus the grafted remote buckets.  `untraced`
    counts grafts that degraded to a stub (their receipt, when it
    survived separately, still folds)."""
    attrs = c.get("attrs") or {}
    nid = str(attrs.get("node", "?"))
    b = nodes.setdefault(
        nid, {"ms": 0.0, "rpcs": 0, "ok": 0, "failed": 0, "segments": 0},
    )
    b["rpcs"] += 1
    ms = float(attrs.get("ms", c.get("duration_ms", 0.0)) or 0.0)
    b["ms"] = round(b["ms"] + ms, 3)
    if attrs.get("outcome") == "ok":
        b["ok"] += 1
        b["segments"] += int(attrs.get("segments", 0) or 0)
    else:
        b["failed"] += 1
    if attrs.get("hedge"):
        b["hedged"] = int(b.get("hedged", 0)) + 1
    for g in c.get("children") or ():
        if not _is_remote(g):
            continue
        if (g.get("attrs") or {}).get("untraced"):
            b["untraced"] = int(b.get("untraced", 0)) + 1
            if not isinstance(g.get("receipt"), dict):
                continue
        for k, v in _fold_remote_buckets(g).items():
            b[k] = round(float(b.get(k, 0.0)) + float(v), 3)


def _walk_cluster_nodes(node: dict, nodes: Dict[str, Dict[str, Any]]):
    """Aggregate the scatter span's per-attempt `cluster_rpc` child
    spans — plus legacy per-reply `rpc` events (lost replica groups
    still mark this way) — into per-historical receipt buckets:
    {node -> {ms, rpcs, ok, failed, segments, device_ms, transfer_ms,
    host_ms, remote_wall_ms, ...}}.  One bucket per historical the
    query touched — the obs_dump table renders these as the per-node
    attribution rows."""
    if str(node.get("name", "")) in SCATTER_SPANS:
        for e in node.get("events") or ():
            if e.get("name") != "rpc":
                continue
            attrs = e.get("attrs") or {}
            nid = str(attrs.get("node", "?"))
            b = nodes.setdefault(
                nid, {"ms": 0.0, "rpcs": 0, "ok": 0, "failed": 0,
                      "segments": 0},
            )
            b["rpcs"] += 1
            b["ms"] = round(b["ms"] + float(attrs.get("ms", 0.0)), 3)
            if attrs.get("outcome") == "ok":
                b["ok"] += 1
                b["segments"] += int(attrs.get("segments", 0))
            else:
                b["failed"] += 1
        for c in node.get("children") or ():
            if str(c.get("name", "")) in CLUSTER_RPC_SPANS:
                _fold_rpc_span(c, nodes)
    for c in node.get("children") or ():
        if _is_overlay(c):
            continue
        _walk_cluster_nodes(c, nodes)


def _phases(acc: Dict[str, Any], wall: float) -> Dict[str, float]:
    """The request's wall by where device work was in flight, on the
    tree's clock: root start -> the first launch span's start -> the
    last `device_fetch`'s end (the last launch span's where nothing was
    fetched) -> root end.  The three add up to `wall`; a request that
    launched nothing is all `pre_launch_ms`.  `in_flight_ms` overstates
    the device by the launch's own host time before its first operation
    and the copy back after its last."""
    first = acc["first_launch"]
    if first is None:
        return {"pre_launch_ms": round(wall, 3), "in_flight_ms": 0.0,
                "post_fetch_ms": 0.0}
    first = min(first, wall)
    last = acc["last_fetch"]
    if last is None or last < first:
        last = acc["last_launch"]
    last = min(max(last, first), wall)
    return {
        "pre_launch_ms": round(first, 3),
        "in_flight_ms": round(last - first, 3),
        "post_fetch_ms": round(wall - last, 3),
    }


def build_receipt(
    trace_doc: dict, scope: Optional[ProfScope] = None
) -> dict:
    """Fold one trace document (obs.trace.QueryTrace.to_dict shape) into
    a cost receipt.  Pure function of the doc + scope counters, so it
    can run live (mid-query, provisional span ends) or at trace close."""
    acc = _new_acc()
    cluster_nodes: Dict[str, Dict[str, Any]] = {}
    root = trace_doc.get("spans")
    if isinstance(root, dict):
        _walk_exclusive(root, acc, 0)
        if not SCATTER_SPANS.isdisjoint(acc["spans"]):
            # only a broker's tree has per-historical buckets to fold:
            # the second walk is not every request's to pay at close
            _walk_cluster_nodes(root, cluster_nodes)
    wall = float(trace_doc.get("total_ms") or 0.0)
    # overlap efficiency (ROADMAP direction 4's success metric):
    # device-busy time over (device-busy + transfer-stall).  Stall is the
    # FOREGROUND h2d time the dispatch loop waited behind; prefetch issue
    # time is excluded — those transfers ran behind live compute, which
    # is exactly what the metric credits.  1.0 when nothing was measured
    # (a fully-resident or dispatch-free query has no stall to hide).
    busy_stall = acc["device"] + acc["transfer"]
    receipt: Dict[str, Any] = {
        "query_id": trace_doc.get("query_id", ""),
        "wall_ms": round(wall, 3),
        "device_ms": round(acc["device"], 3),
        "host_ms": round(acc["host"], 3),
        "transfer_ms": round(acc["transfer"], 3),
        "prefetch_ms": round(acc["prefetch"], 3),
        "arena_build_ms": round(acc["arena_build"], 3),
        "unattributed_ms": round(acc["unattributed"], 3),
        # device program launches this query paid (DISPATCH_SPANS): the
        # number the one-dispatch arena acceptance criterion reads
        "dispatch_count": int(acc["dispatch_count"]),
        # exclusive time by span name; the `self_ms` add up to `wall_ms`
        # (every span's children are taken out of it and counted in
        # their own names), so nothing of the request is left unnamed
        "spans": {
            name: {"n": n, "self_ms": round(ms, 3)}
            for name, (n, ms) in acc["spans"].items()
        },
        "phases": _phases(acc, wall),
        "overlap_efficiency": (
            round(acc["device"] / busy_stall, 4) if busy_stall > 0 else 1.0
        ),
        "sampled": bool(scope.sampled) if scope is not None else False,
    }
    # cluster queries only: scatter/gather/merge attribution + the
    # per-historical buckets.  Absent on single-process receipts so the
    # existing lean shape is unchanged.
    if cluster_nodes or acc["scatter"] or acc["gather"] or (
        acc["cluster_merge"]
    ):
        receipt["scatter_ms"] = round(acc["scatter"], 3)
        receipt["gather_ms"] = round(acc["gather"], 3)
        receipt["cluster_merge_ms"] = round(acc["cluster_merge"], 3)
        receipt["cluster"] = {"nodes": cluster_nodes}
    if scope is not None:
        cache: Dict[str, Any] = {
            "result_cache": scope.result_cache,
            "fused_batch": scope.fused_batch,
            "residency": {
                "hits": scope.residency_hits,
                "misses": scope.residency_misses,
            },
            "program_cache": {
                fam: {"hits": c[0], "misses": c[1]}
                for fam, c in sorted(scope.program_cache.items())
            },
        }
        receipt.update(
            transfer_bytes=scope.transfer_bytes,
            prefetch_bytes=scope.prefetch_bytes,
            transfer_mb_per_s=(
                round(
                    scope.transfer_bytes
                    / max(scope.transfer_ms, 1e-9)
                    / 1e3,
                    1,
                )
                if scope.transfer_bytes
                else 0.0
            ),
            compiles=scope.compiles,
            compile_ms=round(scope.compile_ms, 3),
            syncs=scope.syncs,
            lane=scope.lane,
            cache=cache,
        )
    return receipt


def live_receipt() -> Optional[dict]:
    """Receipt of the ACTIVE query so far (unfinished spans measured to
    'now' under the tracer's own clock) — for what leaves before the
    trace closes: a sampled query's response-context header and a
    progressive stream's last line.  The trace doc, QueryMetrics and
    df.attrs get the closed trace's.  None outside a trace."""
    tr = current_trace()
    if tr is None:
        return None
    try:
        return build_receipt(tr.to_dict_live(), _active.get())
    except Exception:  # fault-ok: attribution must never fail a query
        log.warning("live receipt build failed", exc_info=True)
        return None


# ---------------------------------------------------------------------------
# Workload profiler (GET /status/profile)
# ---------------------------------------------------------------------------


class WorkloadProfiler:
    """Process-wide rolling window of finished-query observations.
    Like the metrics registry it survives context rebuilds; the tracer
    feeds it one observation per finished trace."""

    def __init__(self, capacity: int = 1024):
        self._lock = threading.Lock()
        self._entries: deque = deque(maxlen=max(16, int(capacity)))

    def observe(self, trace_doc: dict, scope: Optional[ProfScope]) -> None:
        rc = trace_doc.get("receipt") or {}
        entry = {
            "t": time.monotonic(),
            "query_id": trace_doc.get("query_id", ""),
            "query_type": trace_doc.get("query_type", ""),
            "lane": (scope.lane if scope is not None else "") or "",
            "wall_ms": float(rc.get("wall_ms", trace_doc.get("total_ms", 0.0)) or 0.0),
            "device_ms": float(rc.get("device_ms", 0.0) or 0.0),
            "transfer_ms": float(rc.get("transfer_ms", 0.0) or 0.0),
            "compiles": int(rc.get("compiles", 0) or 0),
            "sampled": bool(rc.get("sampled", False)),
        }
        with self._lock:
            self._entries.append(entry)

    def window(self, window_s: float) -> List[dict]:
        cutoff = time.monotonic() - max(1e-3, float(window_s))
        with self._lock:
            return [e for e in self._entries if e["t"] >= cutoff]

    def profile(
        self,
        window_s: float = 300.0,
        top_k: int = 10,
        slo_ms: Optional[Dict[str, float]] = None,
    ) -> dict:
        """Rolling-window workload profile: top-K queries by device
        time, per-lane SLO burn-rate (fraction of the lane's queries
        whose wall exceeded its latency target), and window totals."""
        now = time.monotonic()
        entries = self.window(window_s)
        top = sorted(
            entries, key=lambda e: e["device_ms"], reverse=True
        )[: max(1, int(top_k))]
        lanes: Dict[str, dict] = {}
        for e in entries:
            lane = e["lane"] or "unclassified"
            d = lanes.setdefault(
                lane, {"queries": 0, "over_slo": 0, "wall_ms_sum": 0.0}
            )
            d["queries"] += 1
            d["wall_ms_sum"] += e["wall_ms"]
            target = (slo_ms or {}).get(lane)
            if target is not None and target > 0 and e["wall_ms"] > target:
                d["over_slo"] += 1
        for lane, d in lanes.items():
            target = (slo_ms or {}).get(lane)
            d["slo_ms"] = target
            d["burn_rate"] = (
                round(d["over_slo"] / d["queries"], 4)
                if d["queries"] and target
                else 0.0
            )
            d["mean_wall_ms"] = round(
                d["wall_ms_sum"] / max(1, d["queries"]), 3
            )
            del d["wall_ms_sum"]
        return {
            "window_s": float(window_s),
            "queries_observed": len(entries),
            "lanes": lanes,
            "top_device": [
                {
                    "query_id": e["query_id"],
                    "query_type": e["query_type"],
                    "lane": e["lane"] or "unclassified",
                    "device_ms": round(e["device_ms"], 3),
                    "wall_ms": round(e["wall_ms"], 3),
                    "sampled": e["sampled"],
                    "age_s": round(now - e["t"], 1),
                }
                for e in top
            ],
        }


_profiler: Optional[WorkloadProfiler] = None
_profiler_lock = threading.Lock()


def workload_profiler() -> WorkloadProfiler:
    global _profiler
    if _profiler is None:
        with _profiler_lock:
            if _profiler is None:
                _profiler = WorkloadProfiler()
    return _profiler


def _family_totals() -> Dict[str, dict]:
    """Per-program-family compile totals + hit/miss counts from the
    process registry (the /status/profile 'what is recompiling' table)."""
    reg = get_registry()
    out: Dict[str, dict] = {}
    for key, v in reg.counter(
        "sdol_program_cache_total",
        "compiled-program cache lookups, by tagged key family / outcome",
        labels=("family", "outcome"),
    ).snapshot().items():
        fam, _, outcome = key.partition(",")
        d = out.setdefault(
            fam, {"hits": 0, "misses": 0, "compiles": 0, "compile_ms": 0.0}
        )
        d["hits" if outcome == "hit" else "misses"] += int(v)
    for key, v in reg.counter(
        "sdol_compiles_total",
        "program trace+compile events, by program-cache family",
        labels=("family",),
    ).snapshot().items():
        out.setdefault(
            key, {"hits": 0, "misses": 0, "compiles": 0, "compile_ms": 0.0}
        )["compiles"] = int(v)
    for key, v in reg.counter(
        "sdol_compile_ms_total",
        "cumulative trace+compile milliseconds, by program-cache family",
        labels=("family",),
    ).snapshot().items():
        out.setdefault(
            key, {"hits": 0, "misses": 0, "compiles": 0, "compile_ms": 0.0}
        )["compile_ms"] = round(float(v), 3)
    return out


def profile_doc(
    config=None,
    top_k: Optional[int] = None,
    window_s: Optional[float] = None,
) -> dict:
    """The `GET /status/profile` document."""
    cfg = config
    k = int(top_k or getattr(cfg, "profile_top_k", 10) or 10)
    win = float(window_s or getattr(cfg, "profile_window_s", 300.0) or 300.0)
    slo = {
        "interactive": float(
            getattr(cfg, "lane_interactive_slo_ms", 0.0) or 0.0
        ),
        "heavy": float(getattr(cfg, "lane_heavy_slo_ms", 0.0) or 0.0),
    }
    doc = workload_profiler().profile(window_s=win, top_k=k, slo_ms=slo)
    doc["compile_families"] = _family_totals()
    plan = get_registry().counter(
        "sdol_plan_cache_total",
        "decoded-QuerySpec plan cache on the wire path, by outcome",
        labels=("outcome",),
    ).snapshot()
    doc["plan_cache"] = {k2 or "none": int(v) for k2, v in plan.items()}
    return doc
