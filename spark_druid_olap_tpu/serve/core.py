"""ServingCore: one context's async serving machinery.

Owns the fusion scheduler, the delta-aware result cache, and the lane
classification for SQL text (native queries classify from their decoded
QuerySpec directly; SQL classifies from the planned rewrite, through
the plan cache so repeated dashboard statements pay planning once).

The api layer calls in at three points:

  * `cached_result(rw, ds)` — version-exact hit, or a delta-aware
    refresh that scans ONLY freshly-appended segments and merges them
    with the cached historical partial state;
  * `fused_execute(q, ds)` — micro-batch fusion for GroupBy-family
    rewrites (None = caller runs the serial path);
  * `store_result(rw, ds, df, state)` — publish one computed answer
    (frame + optional mergeable state) at the snapshot's version.

The server calls `lane_for_sql` / `serve.lanes.classify_native` to
route admission through `ResilienceState.lanes`.
"""

from __future__ import annotations

import time
from typing import Optional

from ..obs import current_query_id, get_registry, prof, record_query_metrics
from ..utils.log import get_logger
from .fusion import FusionScheduler
from .lanes import LANE_INTERACTIVE, classify_rewrite
from .result_cache import ResultCache

log = get_logger("serve.core")


class ServingCore:
    def __init__(self, ctx):
        self.ctx = ctx
        cfg = ctx.config
        self.fusion = FusionScheduler(
            window_ms=getattr(cfg, "fusion_window_ms", 0.0),
            max_batch=getattr(cfg, "fusion_max_batch", 16),
            adaptive=getattr(cfg, "fusion_adaptive_window", False),
            max_window_ms=getattr(cfg, "fusion_window_max_ms", 0.0),
        )
        self.result_cache = ResultCache(
            entries=getattr(cfg, "result_cache_entries", 64),
            delta_reuse=getattr(cfg, "result_cache_delta_reuse", True),
        )
        # cross-request decoded-QuerySpec plan cache on the wire path
        # (ROADMAP 1(c)): native queries re-decode JSON per request even
        # though dashboards POST the identical body every refresh — key
        # on the context-stripped body and skip `query_from_druid`
        # entirely on a hit, shaving the fast lane's floor.  Decode is a
        # pure function of the body (no catalog input), so entries never
        # need invalidation.
        from ..utils.lru import CountBudgetCache

        self.wire_plan_cache = CountBudgetCache(256)

    # -- wire plan cache (ROADMAP 1(c)) --------------------------------------

    def decode_native(self, body: dict):
        """Decode one native-query body into its QuerySpec through the
        body-hash plan cache.  `sdol_plan_cache_total{outcome}` makes
        the fast-lane floor shave visible in `/status/profile`."""
        import hashlib
        import json as _json

        from ..models.wire import query_from_druid

        ctr = get_registry().counter(
            "sdol_plan_cache_total",
            "decoded-QuerySpec plan cache on the wire path, by outcome",
            labels=("outcome",),
        )
        try:
            # context carries per-request noise (queryId, timeout, ...)
            # the SERVER consumes outside the decode — strip exactly
            # those keys so every dashboard refresh of the same query
            # hits.  Everything else in context STAYS in the key:
            # skipEmptyBuckets/outputName shape the decoded timeseries
            # spec (models/wire.py), and unknown keys are kept
            # conservatively (a miss is cheap; a false hit serves the
            # wrong QuerySpec).
            noise = ("queryId", "timeout", "progressive", "partialResults")
            qctx = body.get("context")
            canon_body = {k: v for k, v in body.items() if k != "context"}
            if isinstance(qctx, dict):
                kept = {k: v for k, v in qctx.items() if k not in noise}
                if kept:
                    canon_body["context"] = kept
            canon = _json.dumps(canon_body, sort_keys=True)
        except (TypeError, ValueError):
            ctr.labels(outcome="uncacheable").inc()
            return query_from_druid(body)
        key = hashlib.sha1(canon.encode()).digest()
        hit = self.wire_plan_cache.get(key)
        if hit is not None:
            ctr.labels(outcome="hit").inc()
            return hit
        q = query_from_druid(body)  # decode errors keep their 400 path
        self.wire_plan_cache[key] = q
        ctr.labels(outcome="miss").inc()
        return q

    # -- result cache --------------------------------------------------------

    def cached_result(self, rw, ds, key, allow_delta: bool = True):
        """Serve `rw` from the cache: a version-exact hit (zero device
        dispatch), or — when an append bumped the version but retired
        nothing — a delta-aware refresh merging the cached historical
        partial with partials over ONLY the fresh segments.  Returns the
        final frame (post-processed) or None.  `allow_delta=False` skips
        the refresh (the breaker-open path must not dispatch to a sick
        device just to freshen a cache entry)."""
        return self._cached(
            rw.query, ds, key, allow_delta,
            post=lambda df: self.ctx._post_process(rw, ds, df),
        )

    def native_key(self, q, ds):
        """Result-cache key of one wire-native QuerySpec, or None when
        it isn't cacheable (non-aggregate types, wire subtotals — their
        expansion runs through the SQL machinery).  Same shape contract
        as api._result_key: dictionary signature in, segment uids OUT
        (entries carry version + covered uids for delta reuse)."""
        import json as _json

        from ..exec.lowering import _dict_signature
        from ..models import query as Q

        if not isinstance(
            q, (Q.GroupByQuery, Q.TimeseriesQuery, Q.TopNQuery)
        ):
            return None
        if isinstance(q, Q.GroupByQuery) and q.subtotals:
            return None
        return (
            "native",
            _json.dumps(q.to_druid(), sort_keys=True, default=str),
            ds.name,
            _dict_signature(ds),
            repr(self.ctx.config),
        )

    def cached_native(self, q, ds, allow_delta: bool = True, key=None):
        """The native wire route's cache lookup: dashboards POSTing the
        same QuerySpec each refresh never reach the device (exact hit),
        and after an append pay only the delta.  None on a miss or for
        uncacheable types.  `key` lets the caller reuse one computed
        key across lookup and store (native_key JSON-serializes the
        spec — once per request, not three times)."""
        key = key if key is not None else self.native_key(q, ds)
        if key is None:
            return None
        return self._cached(q, ds, key, allow_delta, post=None)

    def _cached(self, q, ds, key, allow_delta, post):
        cfg = self.ctx.config
        if key is None or cfg.result_cache_entries <= 0:
            return None
        version = ds.version
        hit = self.result_cache.get(key, version)
        if hit is not None:
            self._stamp_hit_metrics(q, "result-cache")
            return hit
        # delta_reuse reads the LIVE session config (a SET flips it
        # mid-session), not the construction-time snapshot
        if not (
            allow_delta
            and getattr(cfg, "result_cache_delta_reuse", True)
        ):
            return None
        entry = self.result_cache.reusable_entry(
            key, version, (s.uid for s in ds.segments)
        )
        if entry is None:
            self.result_cache.note_miss()
            return None
        try:
            out = self._delta_refresh(q, ds, key, entry, post)
        except Exception:
            # a failed refresh must cost nothing but the attempt: the
            # caller falls through to normal (full) execution
            log.warning(
                "delta-aware cache refresh failed; executing in full",
                exc_info=True,
            )
            out = None
        if out is None:
            self.result_cache.note_miss()
        return out

    def _delta_refresh(self, q, ds, key, entry, post=None):
        """(cached historical partial) ⊕ (fresh delta partials): scan
        only the segments the entry has not covered, merge states,
        re-finalize (+ the surface's host post-processing), re-cache at
        the new version.  Returns None when the delta scan was
        deadline-truncated — the caller then misses into the full
        execution path, which owns partial-answer semantics."""
        from ..resilience import current_partial

        t0 = time.perf_counter()
        engine = self.ctx.engine
        fresh_uids = frozenset(
            s.uid for s in ds.segments if s.uid not in entry.uids
        )
        delta_state, delta_rows = engine.groupby_partials_host(
            q, ds, within_uids=fresh_uids
        )
        pc = current_partial()
        if pc is not None and pc.triggered:
            # the deadline expired mid-delta-scan: the segment loop
            # returned TRUNCATED partials without raising (that is the
            # anytime-answer contract) — merging them would cache an
            # incomplete frame as the exact answer at the new version
            log.warning(
                "delta-aware refresh deadline-truncated; missing into "
                "full execution"
            )
            return None
        merged = engine.merge_groupby_states(
            q, ds, entry.state, delta_state
        )
        df = engine.finalize_groupby_state(q, ds, merged)
        if post is not None:
            df = post(df)
        self.result_cache.put(
            key, df,
            version=ds.version,
            uids=frozenset(s.uid for s in ds.segments),
            state=merged,
        )
        self.result_cache.note_delta_hit(entry)
        m = self._stamp_hit_metrics(q, "result-cache-delta")
        m.rows_scanned = delta_rows
        m.delta_rows_seen = delta_rows
        m.total_ms = (time.perf_counter() - t0) * 1e3
        log.info(
            "delta-aware cache refresh on %r: %d fresh segments / %d "
            "rows merged onto the cached historical partial",
            ds.name, len(fresh_uids), delta_rows,
        )
        return df.copy()

    def _stamp_hit_metrics(self, q, strategy: str):
        """QueryMetrics for a cache-served answer (wire-style query_type
        so the hit lands on the same metric series as executed
        siblings), stamped as the context's most-recent metrics."""
        from ..exec.metrics import QueryMetrics

        try:
            qt = q.to_druid().get("queryType", type(q).__name__)
        except Exception:  # fault-ok: metrics labeling must not fail a hit
            qt = type(q).__name__
        m = QueryMetrics(
            query_type=qt,
            strategy=strategy,
            executor="device",
            query_id=current_query_id(),
        )
        self.ctx._last_engine_metrics = m
        record_query_metrics(m, "ok")
        # cost-receipt cache attribution (obs/prof.py): the receipt's
        # result_cache outcome — "hit" (zero dispatch) vs "delta"
        prof.note_result_cache(
            "delta" if strategy == "result-cache-delta" else "hit"
        )
        return m

    def store_result(self, rw, ds, key, df, state=None) -> None:
        """Publish one computed answer at the executed snapshot's OWN
        stamped version (never the live catalog's — an append racing
        this write must read as a version mismatch, not as freshness the
        answer does not have)."""
        if key is None or self.ctx.config.result_cache_entries <= 0:
            return
        self.result_cache.put(
            key, df,
            version=ds.version,
            uids=frozenset(s.uid for s in ds.segments),
            state=state,
        )

    def store_native(self, q, ds, df, state=None, key=None) -> None:
        """Publish one native answer — with the partial-hygiene guard
        here (the SQL surface's equivalent guard lives in
        execute_rewrite): a deadline-truncated frame must never be
        served back as the exact answer.  No-ops when the session's
        cache is off (the capacity floor of 1 must not retain a latent
        entry a later config flip would serve)."""
        from ..resilience import current_partial

        if self.ctx.config.result_cache_entries <= 0:
            return
        key = key if key is not None else self.native_key(q, ds)
        if key is None:
            return
        pc = current_partial()
        if pc is not None and pc.triggered:
            return
        self.result_cache.put(
            key, df,
            version=ds.version,
            uids=frozenset(s.uid for s in ds.segments),
            state=state,
        )

    # -- fusion --------------------------------------------------------------

    def fused_execute(
        self, q, ds, engine=None, strategy=None
    ) -> Optional[tuple]:
        """Micro-batch fusion entry: (df, state, metrics) or None.
        `engine` selects the executing backend (None = the context's
        local engine; the mesh's DistributedEngine batches through its
        unified SPMD arena) — backends never share a batch.  `strategy`
        is the member's planned kernel class (None = the engine's)."""
        if not self.fusion.enabled:
            return None
        return self.fusion.execute(
            self.ctx, q, ds, engine=engine, strategy=strategy
        )

    # -- lanes ---------------------------------------------------------------

    def lane_for_sql(self, sql_text: str) -> str:
        """Admission lane of one SQL statement, from its planned rewrite
        (through the plan cache, so repeated dashboard statements pay
        planning once — and ctx.sql then hits the same entry).  Anything
        unplannable (commands, fallback-bound shapes, parse errors)
        classifies interactive; real errors resurface on the execution
        path with their proper taxonomy."""
        ctx = self.ctx
        try:
            from ..sql.commands import parse_command

            if parse_command(sql_text) is not None:
                return LANE_INTERACTIVE
            rw, _lp, _explain, _err = ctx._plan_cached(sql_text)
            if rw is None:  # EXPLAIN, or a shape bound for the fallback
                return LANE_INTERACTIVE
            return classify_rewrite(rw, ctx.catalog, ctx.config)
        except Exception:  # fault-ok: lane routing must never fail a query
            return LANE_INTERACTIVE

    def to_dict(self) -> dict:
        return {
            "fusion": self.fusion.to_dict(),
            "result_cache": self.result_cache.to_dict(),
            "wire_plan_cache_entries": len(self.wire_plan_cache),
        }
