"""Micro-batch query fusion: amortize the device dispatch across
concurrent compatible queries.

The re-anchor numbers frame the problem: SF1 TPU p50 is 102 ms against
a 66 ms device round trip — the dispatch floor IS the latency budget,
and at dashboard scale the workload is many small concurrent queries
over the same hot datasource.  Computation-pushdown economics
(arXiv:2312.15405) say to amortize the boundary across queries:

  * The FIRST query to arrive for a (datasource, segment-set signature)
    becomes the batch LEADER: it holds the batch open for
    `SessionConfig.fusion_window_ms`, collecting compatible queries
    (GroupBy-family, same signature) up to `fusion_max_batch`.
  * The leader executes the whole batch as ONE fused device program
    (`Engine.execute_fused`): the union of the members' in-scope
    segments moves host->device once, every member's partial aggregation
    runs inside the same dispatch, one fetch returns all states.
  * Results demultiplex per member: each waiter receives its own
    finalized frame, host partial state (the delta-aware result cache
    stores it), and QueryMetrics stamped with ITS query_id and the batch
    size (`fused_batch`) — serving-discipline GL1702.

Compatibility is the segment-set signature (`lowering.schema_signature`:
name + dictionary content + segment uids).  An append between enqueue
and dispatch bumps the signature; the leader detects the mismatch at
dispatch time and INVALIDATES the batch — every member re-executes
individually on its own thread, against the current snapshot and under
its own deadline/partial scopes (fused execution cannot honor N
different deadline budgets, so an invalidated batch must not be run by
the leader on the members' behalf).

A batch of one (no concurrency materialized inside the window) is also
re-routed to the member's serial path: the fused program brings only
demux overhead when there is nothing to amortize.
"""

from __future__ import annotations

import itertools
import threading
import time
from typing import Dict, List, Optional, Tuple

from collections import deque

from ..obs import SPAN_FUSED_BATCH, current_query_id, prof, span, span_event
from ..utils.log import get_logger

log = get_logger("serve.fusion")

# a member blocked on its batch leader must never hang the request
# thread forever if the leader dies mid-delivery; past this it falls
# back to its own serial execution
_MEMBER_WAIT_S = 300.0


def shared_row_plan(inners) -> tuple:
    """Common-subexpression dedup over member lowerings (ROADMAP 1(a)).

    Dashboard members of one fused batch routinely share the expensive
    row-pipeline prefixes: the FILTER MASK (intervals + filter over the
    same virtual columns) and the GROUP-ID pipeline (same dimensions +
    granularity).  Without dedup the fused program re-traces both per
    member — N identical filter evaluations over the same segment
    columns in one kernel.

    Returns one `(mask_group, gid_group)` pair per member, where each
    group id is the index of the FIRST member with an identical
    sub-lowering signature: inside the fused program, later members
    reuse that member's computed mask / gid for each segment instead of
    recomputing it (engine._segment_partials threads a per-segment memo
    through `GroupByLowering.row_arrays`).  Signatures come from the
    canonical wire JSON of the rewritten inner GroupBy — the same
    identity the program cache keys on — so two members share a group
    ONLY when the traced subexpression is value-identical."""
    import json as _json

    def _sig(val):
        return _json.dumps(val, sort_keys=True, default=str)

    mask_groups: Dict[tuple, int] = {}
    gid_groups: Dict[tuple, int] = {}
    plan = []
    for i, q in enumerate(inners):
        d = q.to_druid()
        vsig = _sig(d.get("virtualColumns") or [])
        isig = _sig(d.get("intervals"))
        msig = (vsig, _sig(d.get("filter")), isig)
        # intervals belong in the gid signature too: a time-bucketed
        # dimension's codes_fn closes over the query's interval span
        # (bucket origin + cardinality), so two members with identical
        # dimensions but shifted intervals compute DIFFERENT gids —
        # sharing them returned silently wrong aggregates for the
        # second member (review finding, regression-tested)
        gsig = (
            vsig,
            _sig(d.get("dimensions") or []),
            _sig(d.get("granularity")),
            isig,
        )
        plan.append(
            (
                mask_groups.setdefault(msig, i),
                gid_groups.setdefault(gsig, i),
            )
        )
    return tuple(plan)

# delivery verdicts
_OK = "ok"
_RETRY = "retry"  # re-execute individually on the member's own thread


class _Member:
    __slots__ = (
        "query", "query_id", "strategy", "event", "verdict", "payload",
    )

    def __init__(self, query, query_id: str, strategy=None):
        self.query = query
        self.query_id = query_id
        self.strategy = strategy  # the member's planned kernel class
        self.event = threading.Event()
        self.verdict: Optional[str] = None
        self.payload = None

    def deliver(self, verdict: str, payload=None) -> None:
        self.verdict = verdict
        self.payload = payload
        self.event.set()


class _Batch:
    __slots__ = ("batch_id", "signature", "members", "closed", "engine")

    def __init__(self, batch_id: int, signature, engine=None):
        self.batch_id = batch_id
        self.signature = signature
        self.members: List[_Member] = []
        self.closed = False
        # executing backend (None = the context's local engine); the
        # signature carries a backend label so a mesh-routed query and a
        # single-device one never land in the same batch
        self.engine = engine


class FusionScheduler:
    """Leader-based micro-batcher over one context's local engine.

    `execute` returns `(df, state, metrics)` when the query ran fused,
    or None when the caller must execute it on the normal serial path
    (fusion disabled, batch of one, batch invalidated by a concurrent
    append, or the fused dispatch failed)."""

    def __init__(
        self,
        window_ms: float = 0.0,
        max_batch: int = 16,
        adaptive: bool = False,
        max_window_ms: float = 0.0,
    ):
        self.window_ms = float(window_ms)
        self.max_batch = max(2, int(max_batch))
        # adaptive window (ROADMAP 1(b)): arm the hold window from the
        # OBSERVED arrival rate — an idle queue pays no wait at all (the
        # static window taxes every solo query the full window for
        # nothing), a burst holds up to max_window_ms so more members
        # amortize the dispatch.  The decision is recorded as a
        # `fusion_window` span event on the leader's trace.
        self.adaptive = bool(adaptive)
        self.max_window_ms = (
            float(max_window_ms) if max_window_ms else 4.0 * float(window_ms)
        )
        self._arrivals: deque = deque(maxlen=64)
        self.window_decisions: Dict[str, int] = {}
        self._lock = threading.Lock()
        self._open: Dict[Tuple, _Batch] = {}
        self._ids = itertools.count(1)
        # observability: fused batches executed / member outcomes
        self.batches_fused = 0
        self.members_fused = 0
        self.invalidated = 0

    @property
    def enabled(self) -> bool:
        return self.window_ms > 0

    def _decide_window_ms(self, now: float) -> Tuple[float, str, int]:
        """(window_ms, mode, recent_arrivals) for a leader arriving at
        `now` — BEFORE its own arrival is recorded, so the decision
        reads only the queue's recent history.  idle: no arrival within
        8 windows -> no wait; burst: >=3 arrivals within 2 windows ->
        hold up to max_window_ms; base: the configured window."""
        if not self.adaptive:
            return self.window_ms, "static", 0
        horizon = 8.0 * self.window_ms / 1e3
        burst_horizon = 2.0 * self.window_ms / 1e3
        with self._lock:
            recent = [t for t in self._arrivals if now - t <= horizon]
        if not recent:
            return 0.0, "idle", 0
        burst = sum(1 for t in recent if now - t <= burst_horizon)
        if burst >= 3:
            return (
                min(self.max_window_ms, 2.0 * self.window_ms),
                "burst",
                len(recent),
            )
        return self.window_ms, "base", len(recent)

    def _note_arrival(self, now: float) -> None:
        with self._lock:
            self._arrivals.append(now)

    def execute(self, ctx, q, ds, engine=None, strategy=None):
        """Join (or lead) the micro-batch for `q` over the `ds`
        snapshot.  Returns (df, state, metrics) or None (serial path).
        `engine` is the executing backend (None = ctx.engine); distinct
        backends hash to distinct signatures, so a batch is always
        dispatched by the engine every one of its members routed to."""
        if not self.enabled:
            return None
        from ..exec.lowering import schema_signature

        if engine is None or engine is ctx.engine:
            engine, backend = None, "device"
        else:
            backend = "mesh"
        now = time.monotonic()
        window_ms, mode, n_recent = self._decide_window_ms(now)
        self._note_arrival(now)
        sig = (ds.name, backend, schema_signature(ds))
        me = _Member(q, current_query_id(), strategy)
        with self._lock:
            batch = self._open.get(sig)
            if (
                batch is None
                or batch.closed
                or len(batch.members) >= self.max_batch
            ):
                batch = _Batch(next(self._ids), sig, engine=engine)
                self._open[sig] = batch
                leader = True
            else:
                leader = False
            batch.members.append(me)
        if leader:
            # record the arrival-rate decision (ROADMAP 1(b)): the span
            # event says what the scheduler chose and why, so "why did
            # my solo query not wait" / "why did the burst hold longer"
            # reads off the trace
            with self._lock:
                self.window_decisions[mode] = (
                    self.window_decisions.get(mode, 0) + 1
                )
            span_event(
                "fusion_window",
                window_ms=round(window_ms, 3),
                mode=mode,
                recent_arrivals=n_recent,
            )
            self._lead(ctx, batch, ds, window_ms=window_ms)
        else:
            if not me.event.wait(_MEMBER_WAIT_S):
                log.warning(
                    "fused-batch member timed out waiting for its "
                    "leader; executing serially"
                )
                return None
        if me.verdict != _OK:
            return None
        df, state, m = me.payload
        # receipt attribution: every member's scope records the batch
        # size it rode (the leader's was stamped inside execute_fused)
        prof.note_fusion(len(batch.members))
        if not leader:
            # a NON-leader member's trace records that this query rode a
            # fused batch (the leader's trace already holds the real
            # fused_batch span around the execution — a second marker
            # there would double-count batches per trace); the batch id
            # + member query ids link the two traces
            with span(
                SPAN_FUSED_BATCH,
                batch=batch.batch_id,
                members=len(batch.members),
            ):
                span_event(
                    "fused_members",
                    query_ids=",".join(
                        x.query_id for x in batch.members
                    ),
                )
        return df, state, m

    def _lead(self, ctx, batch: _Batch, ds, window_ms: Optional[float] = None) -> None:
        """Leader protocol: hold the window open (the adaptive decision
        when one was made), close the batch, and either execute it fused
        or invalidate it (every member then re-executes individually on
        its own thread)."""
        from ..exec.lowering import schema_signature

        hold_ms = self.window_ms if window_ms is None else window_ms
        if hold_ms > 0:
            time.sleep(hold_ms / 1e3)
        with self._lock:
            batch.closed = True
            if self._open.get(batch.signature) is batch:
                del self._open[batch.signature]
            members = list(batch.members)
        # canonical member order: thread arrival order varies per wave,
        # and the fused program cache keys on the member sequence — an
        # order-sensitive key would recompile the SAME dashboard set on
        # every permutation (members are independent, so order is free)
        import json as _json

        members.sort(
            key=lambda m: _json.dumps(
                m.query.to_druid(), sort_keys=True, default=str
            )
        )
        try:
            if len(members) == 1:
                # nothing joined: the fused program would only add demux
                # overhead — run the member's normal serial path
                members[0].deliver(_RETRY)
                return
            current = ctx.catalog.get(ds.name)
            if current is None or (
                (ds.name, batch.signature[1], schema_signature(current))
                != batch.signature
            ):
                # an append/compaction published a new segment set
                # between enqueue and dispatch: the batch's snapshot is
                # stale — split it, each member re-executes against the
                # CURRENT snapshot under its own scopes
                with self._lock:
                    self.invalidated += 1
                log.info(
                    "fused batch %d invalidated by a segment-set version "
                    "bump on %r; %d members re-execute individually",
                    batch.batch_id, ds.name, len(members),
                )
                for m in members:
                    m.deliver(_RETRY)
                return
            with span(
                SPAN_FUSED_BATCH,
                batch=batch.batch_id,
                members=len(members),
            ):
                span_event(
                    "fused_members",
                    query_ids=",".join(m.query_id for m in members),
                )
                results = (batch.engine or ctx.engine).execute_fused(
                    [m.query for m in members],
                    current,
                    query_ids=[m.query_id for m in members],
                    strategies=[m.strategy for m in members],
                )
            with self._lock:
                self.batches_fused += 1
                self.members_fused += len(members)
            for m, payload in zip(members, results):
                m.deliver(_OK, payload)
        except Exception as err:
            # ANY fused-path failure (transient device fault, deadline,
            # compile error) re-routes every member to its own serial
            # execution — the serial path owns retries, breaker
            # accounting, and partial-result semantics per query, which
            # a shared fused dispatch cannot honor per member
            log.warning(
                "fused batch %d failed (%s: %s); %d members re-execute "
                "individually",
                batch.batch_id, type(err).__name__, err, len(members),
            )
            for m in members:
                if not m.event.is_set():
                    m.deliver(_RETRY)
        finally:
            # defensive: no member may ever be left waiting
            for m in members:
                if not m.event.is_set():
                    m.deliver(_RETRY)

    def to_dict(self) -> dict:
        with self._lock:
            return {
                "enabled": self.enabled,
                "window_ms": self.window_ms,
                "adaptive": self.adaptive,
                "max_window_ms": self.max_window_ms,
                "window_decisions": dict(self.window_decisions),
                "max_batch": self.max_batch,
                "batches_fused": self.batches_fused,
                "members_fused": self.members_fused,
                "invalidated": self.invalidated,
            }
