"""User-facing surface: register tables, run SQL, explain rewrites.

Reference parity (SURVEY.md L6 `[U]`): the reference's surface is
`CREATE TEMPORARY TABLE ... USING org.sparklinedata.druid OPTIONS(...)` +
ordinary Spark SQL with `DruidPlanner` strategies injected, plus the
`EXPLAIN DRUID REWRITE` command.  Here:

    import spark_druid_olap_tpu as sd
    ctx = sd.TPUOlapContext()
    ctx.register_table("lineitem", cols, dimensions=[...], metrics=[...],
                       time_column="l_shipdate", star_schema=...)
    df  = ctx.sql("SELECT l_returnflag, sum(l_quantity) FROM lineitem "
                  "GROUP BY l_returnflag")
    print(ctx.explain("SELECT ..."))      # EXPLAIN DRUID REWRITE analog
    ctx.clear_cache()                      # clear-metadata-cache analog

Execution routes through the planner's PhysicalPlan: local Engine or
DistributedEngine (mesh), with grouping-set (CUBE/ROLLUP) expansion and
host-side residual having/projection evaluation handled here — the
"projection fixup over the scan node" role of the reference's DruidStrategy.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

import numpy as np

from .catalog.cache import MetadataCache
from .catalog.segment import DataSource, build_datasource
from .catalog.star import StarSchemaInfo
from .config import SessionConfig, TableOptions
from .exec.engine import Engine
from .models import query as Q
from .obs import (
    SPAN_DEGRADED,
    SPAN_ENGINE,
    SPAN_EXECUTE,
    SPAN_FALLBACK,
    SPAN_GROUPING_SETS,
    SPAN_PARTIAL,
    SPAN_PLAN,
    SPAN_POST_PROCESS,
    SPAN_ROUTE,
    SPAN_SQL_PARSE,
    Tracer,
    current_query_id,
    record_partial,
    record_query_metrics,
    span,
    span_event,
)
from .plan import expr as E
from .plan import logical as L
from .plan.planner import Planner, Rewrite, RewriteError
from .sql.parser import parse_sql
from .utils.log import get_logger

log = get_logger("api")


def _breaker_observation(br) -> dict:
    """Small JSON-able snapshot of the circuit breaker as the routing
    layer saw it — what degraded-path span events carry.  Carries which
    BACKEND's breaker was consulted (device / mesh / fallback): with
    per-backend granularity, "why did this query degrade" needs to name
    the breaker that said no."""
    d = br.to_dict()
    return {
        "backend": d["backend"],
        "state": d["state"],
        "consecutive_failures": d["consecutive_failures"],
        "trips": d["trips"],
    }


class TPUOlapContext:
    def __init__(self, config: Optional[SessionConfig] = None):
        # Default to measured cost constants (calibration.json when it was
        # produced on this backend, else the platform profile): the class
        # defaults are v5e-flavoured and route CPU kernels pathologically
        # (an uncalibrated CPU session would run a G=8008 GroupBy dense —
        # ~200x slower than scatter there).
        self.config = config or SessionConfig.load_calibrated()
        self.catalog = MetadataCache()
        self.engine = Engine(config=self.config)
        # overlapped h2d transfer pipeline (exec/pipeline.py, ISSUE 10):
        # prefetch depth / speculation byte cap / on-off come from config
        self.engine.configure_pipeline(self.config)
        self._dist_engine = None
        self._last_engine_metrics = None  # metrics of the engine that last ran
        # query-lifecycle resilience (resilience.py): the breaker every
        # engine reports transient failures to, the admission pool the
        # serving layer gates on, and the health counters
        from .resilience import ResilienceState

        self.resilience = ResilienceState(self.config)
        self._sync_engine_resilience(self.engine)
        # per-query span tracing (obs/): the ring buffer behind
        # GET /druid/v2/trace/{query_id}; the metrics registry itself is
        # process-global (obs.registry.get_registry)
        self.tracer = Tracer(
            capacity=self.config.trace_ring_capacity,
            otlp_path=self.config.otlp_export_path,
            prof_sample_rate=self.config.prof_sample_rate,
        )
        # SQL-text -> Rewrite cache (the reference re-plans every Catalyst
        # round; locally a repeated dashboard query should pay parse+plan
        # once).  Keyed on catalog version + config so any re-registration
        # or session-flag change invalidates.
        from .utils.lru import CountBudgetCache

        self._plan_cache = CountBudgetCache(256)
        # async serving core (serve/, ISSUE 8): micro-batch query fusion,
        # the delta-aware version-keyed result cache, and SQL lane
        # classification.  The result cache (Druid broker result cache
        # analog) lives inside it: identical (query, schema) pairs skip
        # execution entirely, and on append it serves (cached historical
        # partial) ⊕ (fresh delta partials) instead of invalidating.
        from .serve import ServingCore

        self.serve = ServingCore(self)
        # CREATE VIEW registry: view name -> defining SELECT text; the
        # parser expands references as derived tables
        self.views: Dict[str, str] = {}
        # real-time ingestion tier (ingest/): streamed appends -> delta
        # segments, plus the versioned background compactor.  Retired
        # segment uids (compaction, dictionary-extension remaps) evict
        # from the engine's device residency immediately.
        from .ingest import Compactor, IngestManager

        self.ingest = IngestManager(self.catalog, self.config)
        self.ingest.on_segments_dropped = self._on_segments_dropped
        self.compactor = Compactor(
            self.ingest,
            rows_per_segment=self.config.compaction_rows_per_segment,
            min_delta_rows=self.config.compaction_min_delta_rows,
            interval_s=self.config.compaction_interval_s,
            sys_retention_s=self.config.sys_retention_s,
        )
        # cluster tier (cluster/, ISSUE 16): set by ClusterClient.attach
        # when this context runs as a BROKER — the serving paths scatter
        # covered queries to historicals instead of executing locally.
        # None (default) keeps every query in-process.
        self.cluster = None
        # durable storage tier (storage.py, ISSUE 13): append WAL +
        # crash-safe persistent segment snapshots.  Opt-in via
        # config.storage_dir; recovery runs NOW, before the context is
        # handed to callers — a restarted process serves the pre-crash
        # state (snapshot mmap + WAL replay) from its first query.
        self.storage = None
        if self.config.storage_dir:
            from .storage import DurableStorage

            self.storage = DurableStorage(
                self.config.storage_dir,
                self.catalog,
                self.ingest,
                fsync=self.config.storage_fsync,
            )
            self.ingest.storage = self.storage
            self.compactor.storage = self.storage
            self.storage.recover(self.resilience)
            if self.config.snapshot_flush_s > 0:
                self.storage.start_flush_sweep(
                    self.config.snapshot_flush_s
                )
        # self-hosted telemetry (obs/telemetry.py, ISSUE 19): registry ->
        # `__sys` datasource through the ingest/WAL tier.  Built lazily —
        # config.sys_sampler_s > 0 starts the daemon tick loop here;
        # start_sys_sampler() is the manual/test entry point.
        self.sys_sampler = None
        if self.config.sys_sampler_s > 0:
            self.start_sys_sampler()

    # -- registration (CREATE TABLE ... USING ... OPTIONS analog) -----------

    def register_table(
        self,
        name: str,
        source,
        dimensions: Sequence[str] = (),
        metrics: Sequence[str] = (),
        time_column: Optional[str] = None,
        star_schema: Optional[StarSchemaInfo] = None,
        column_mapping: Optional[Mapping[str, str]] = None,
        rows_per_segment: int = 1 << 22,
        dicts: Optional[Mapping] = None,
        sort_by: Sequence[str] = (),
        rollup_granularity: Optional[str] = None,
    ) -> DataSource:
        """Register a datasource from a pandas DataFrame, a dict of numpy
        columns, or a parquet/csv path (catalog/ingest.py).  `dicts` supplies
        pre-built dimension dictionaries for already-encoded columns.

        `sort_by` orders rows by the named columns before segmenting (the
        Druid secondary-partitioning analog): filters on those columns then
        prune whole segments via zone maps instead of masking rows.

        `rollup_granularity` opts the datasource into Druid-style
        ingest-time rollup: streamed appends pre-aggregate under the
        declared granularity (time truncated to the bucket, metrics
        summed per distinct dimension tuple) BEFORE journaling/publish.
        Fixed-period granularities only ('second' .. 'week'); requires a
        time column.  Changes count(*) semantics to "rolled-up rows" —
        the documented Druid rollup trade."""
        from .catalog.ingest import to_columns_encoded

        cols, native_dicts = to_columns_encoded(source)
        if column_mapping:
            cols = {column_mapping.get(k, k): v for k, v in cols.items()}
            native_dicts = {
                column_mapping.get(k, k): v for k, v in native_dicts.items()
            }
        if dicts:
            # caller-supplied dictionaries win — by re-encoding the raw
            # values, never by reinterpreting native rank codes under a
            # different domain (codes are ranks over the FILE's domain)
            for k in [k for k in native_dicts if k in dicts]:
                cols[k] = native_dicts.pop(k).decode(np.asarray(cols[k]))
        if time_column and time_column in native_dicts:
            # a string-typed time column arrived as rank codes; translate
            # through the (tiny) dictionary: parse each distinct value once
            d = native_dicts.pop(time_column)
            codes = np.asarray(cols[time_column])
            try:
                ms = np.asarray(d.values, dtype="datetime64[ms]").astype(
                    np.int64
                )
                if (codes < 0).any():
                    raise ValueError("null time values")
                cols[time_column] = ms[codes]
            except Exception:
                # non-datetime strings: surface the raw values so
                # build_datasource fails loudly (pandas-path behavior)
                cols[time_column] = d.decode(codes)
        if native_dicts:
            merged = dict(native_dicts)
            if dicts:
                merged.update(dicts)
            dicts = merged
            if not dimensions and not metrics:
                # encoded string columns are int32 codes now; classify the
                # ones with a native dictionary as dimensions
                dims, mets = _infer_schema(cols, time_column)
                dims += [m for m in mets if m in native_dicts]
                mets = [m for m in mets if m not in native_dicts]
                dimensions, metrics = dims, mets
        if not dimensions and not metrics:
            dimensions, metrics = _infer_schema(cols, time_column)
        if sort_by:
            missing = [c for c in sort_by if c not in cols]
            if missing:
                raise ValueError(f"sort_by names unknown columns {missing}")

            def sort_keys(c):
                # null-safe keys: object columns with None cannot lexsort
                # directly; nulls order LAST (flag more significant than
                # value, so it follows the value key in the lexsort tuple)
                a = np.asarray(cols[c])
                if a.dtype.kind == "O":
                    nulls = np.array([v is None for v in a])
                    vals = np.array(
                        [("" if v is None else str(v)) for v in a]
                    )
                    return [vals, nulls]
                if c in (dicts or {}) and a.dtype.kind in "iu":
                    # pre-encoded dimension codes: null codes are negative
                    # and would cluster FIRST on a raw sort — add the same
                    # nulls-last flag key the object path uses
                    return [a, a < 0]
                return [a]

            # stable lexsort (last key primary); encoded dims sort by code,
            # which is value order (dictionaries are sorted)
            keys: list = []
            for c in reversed(sort_by):
                keys.extend(sort_keys(c))
            order = np.lexsort(tuple(keys))
            cols = {k: np.asarray(v)[order] for k, v in cols.items()}
        ds = build_datasource(
            name,
            cols,
            dimension_cols=list(dimensions),
            metric_cols=list(metrics),
            time_col=time_column,
            rows_per_segment=rows_per_segment,
            dicts=dicts,
        )
        if rollup_granularity is not None:
            from .utils.granularity import granularity_period_ms

            if time_column is None:
                raise ValueError(
                    "rollup_granularity requires a time column"
                )
            if granularity_period_ms(rollup_granularity) is None:
                raise ValueError(
                    f"rollup_granularity {rollup_granularity!r} has no "
                    "fixed period; use second/minute/.../week"
                )
            ds = dataclasses.replace(
                ds, rollup_granularity=str(rollup_granularity).lower()
            )
        if star_schema is not None and not isinstance(star_schema, StarSchemaInfo):
            star_schema = StarSchemaInfo.from_json(star_schema)
        # put() stamps the monotonic per-datasource version; return the
        # stamped snapshot so callers observe the same object queries see
        published = self.catalog.put(ds, star_schema)
        if self.storage is not None:
            # registration is durable too: the snapshot commits before
            # the call returns, so a post-registration crash restores
            # the table by mmap instead of demanding a re-ingest
            self.storage.flush(name)
        return published

    def register_datasource(self, ds: DataSource, star_schema=None):
        """Register an ALREADY-BUILT DataSource (streamed/chunked ingest via
        catalog.segment.build_datasource_streamed, or one loaded from
        catalog.persist) under its own name."""
        if star_schema is not None and not isinstance(star_schema, StarSchemaInfo):
            star_schema = StarSchemaInfo.from_json(star_schema)
        published = self.catalog.put(ds, star_schema)
        if self.storage is not None:
            self.storage.flush(ds.name)
        return published

    # -- streamed ingest (the Druid realtime-node analog, ISSUE 6) ----------

    def append_rows(self, name: str, rows) -> dict:
        """Append streamed rows (list of row dicts or a column mapping) to
        a registered datasource.  The rows are queryable by the very next
        query — delta partials merge with historical partials through the
        same mergeable-aggregate machinery every executor already uses.
        Returns an ack: {"appended", "datasourceVersion", "totalRows"}."""
        with self.tracer.query_trace(
            query_type="ingest", slow_ms=self.config.slow_query_ms
        ):
            return self.ingest.append_rows(name, rows)

    def compact(self, name: str) -> dict:
        """Roll `name`'s delta segments into tiled historical segments now
        (the background compactor does this on a sweep; this is the
        synchronous entry point).  Query results are preserved verbatim;
        the datasource version bumps so result caches invalidate."""
        with self.tracer.query_trace(
            query_type="compaction", slow_ms=self.config.slow_query_ms
        ):
            return self.compactor.compact(name)

    def start_compaction(self):
        """Start the background compaction sweep (daemon thread)."""
        self.compactor.start()
        return self

    def stop_compaction(self):
        self.compactor.stop()

    def start_sys_sampler(self, interval_s: Optional[float] = None):
        """Start (or restart) the `__sys` telemetry sampler: the metrics
        registry flushes into the `__sys` datasource every tick so
        operational history is SQL-queryable (obs/telemetry.py)."""
        from .obs.telemetry import SysSampler

        if self.sys_sampler is None:
            self.sys_sampler = SysSampler(
                self,
                interval_s=(
                    interval_s
                    if interval_s is not None
                    else self.config.sys_sampler_s or 5.0
                ),
                max_series=self.config.sys_sampler_max_series,
            )
        self.sys_sampler.start()
        return self.sys_sampler

    def stop_sys_sampler(self):
        if self.sys_sampler is not None:
            self.sys_sampler.stop()

    def _on_segments_dropped(self, uids):
        self.engine.evict_segments(uids)
        # the fallback's decoded-frame cache keys on the same uids
        from .exec.fallback import evict_decoded_segments

        evict_decoded_segments(uids)
        if self._dist_engine is not None:
            # shard assemblies key on the full segment-uid signature, so
            # stale entries can never be SERVED — but they still pin HBM
            # until LRU pressure; a retired segment set clears them now
            self._dist_engine.clear_cache()

    def register_lookup(self, name: str, mapping: Mapping[str, str]):
        """Register a query-time lookup table (Druid lookup extraction):
        `LOOKUP(dim, 'name')` in GROUP BY maps dimension values through it
        host-side (a dictionary rewrite — never per-row string work)."""
        self.catalog.put_lookup(name, dict(mapping))

    def save_table(self, name: str, directory: str) -> str:
        """Persist a registered datasource (encoded segments + dictionaries
        + star schema) to a directory; `load_table` or `CREATE TABLE ...
        USING tpu_olap OPTIONS (path '<dir>')` restores it without
        re-ingest/re-encode (the Druid-index-as-persistence analog)."""
        from .catalog.persist import save_datasource

        ds = self.catalog.get(name)
        if ds is None:
            raise KeyError(f"table {name!r} does not exist")
        return save_datasource(ds, directory, self.catalog.star_schema(name))

    def load_table(self, directory: str, name: Optional[str] = None):
        from .catalog.persist import load_datasource

        ds, star = load_datasource(directory, name=name)
        # drop first: put() only overwrites the star when one is provided,
        # and a star-less load over an existing starred table must not keep
        # the stale star (it describes different data)
        self.catalog.drop(ds.name)
        return self.catalog.put(ds, star)

    def drop_table(self, name: str):
        self.catalog.drop(name)

    @property
    def _result_cache(self):
        """The serving core's result cache under its pre-serve name —
        `SET result_cache_entries` (sql/commands.py) resizes through
        this, and existing callers keep working."""
        return self.serve.result_cache

    def clear_cache(self):
        """Reference's clear-metadata-cache command + HBM residency drop."""
        self.catalog.clear()
        self.engine.clear_cache()
        self._plan_cache.clear()
        self.serve.result_cache.clear()
        if self._dist_engine is not None:
            self._dist_engine.clear_cache()

    # -- planning ------------------------------------------------------------

    def _planner(self) -> Planner:
        import jax

        return Planner(self.catalog, self.config, n_devices=len(jax.devices()))

    def plan_sql(self, sql_text: str) -> Rewrite:
        lp, _, _ = parse_sql(sql_text, views=self.views)
        return self._planner().plan(lp)

    def explain(self, sql_text: str) -> str:
        """EXPLAIN DRUID REWRITE analog: logical plan -> chosen query spec
        JSON -> physical plan."""
        lp, _, _ = parse_sql(sql_text, views=self.views)
        return self._planner().explain(lp)

    @property
    def last_metrics(self):
        """QueryMetrics of the most recent execution (exec/metrics.py) —
        rows/sec, H2D bytes streamed, compile/device/collective/finalize
        phase times — from whichever engine ran it."""
        # _last_engine_metrics is stamped on every completed execution —
        # engine runs (execute_rewrite) AND host-fallback runs — so it is
        # the authoritative "most recent"; the engine objects are only a
        # fallback for direct engine.execute() use outside the context
        if self._last_engine_metrics is not None:
            return self._last_engine_metrics
        dm = self._dist_engine.last_metrics if self._dist_engine else None
        em = self.engine.last_metrics
        return em if dm is None else (dm if em is None else em)

    def explain_analyze(self, sql_text: str):
        """EXPLAIN ANALYZE analog: run the query, return (DataFrame,
        explain text + measured QueryMetrics + the span tree).  Bypasses
        the result cache — the metrics must describe THIS execution, not
        a cache lookup."""
        from .obs import current_trace

        lp, _, _ = parse_sql(sql_text, views=self.views)
        planner = self._planner()
        # finishing pins the root duration so the appended render shows a
        # real total — but only when WE opened the trace (a joined outer
        # trace must not be truncated mid-request)
        owned = current_trace() is None
        with self.tracer.query_trace(
            query_type="explain_analyze", slow_ms=self.config.slow_query_ms
        ) as tr:
            try:
                with span(SPAN_PLAN):
                    rw = planner.plan(lp)
            except RewriteError as err:
                df = self._run_fallback(lp, err)
                text = f"== Host Fallback ==\nrewrite failed: {err}"
                m = self.last_metrics
                if m is not None:
                    text += "\n\n== Execution Metrics ==\n" + m.describe()
                if owned:
                    tr.finish()
                return df, text + "\n\n== Span Tree ==\n" + tr.render()
            with span(SPAN_EXECUTE):
                df = self.execute_rewrite(rw, use_result_cache=False)
            text = planner.explain(lp)
            m = self.last_metrics
            if m is not None:
                text += "\n\n== Execution Metrics ==\n" + m.describe()
            if owned:
                tr.finish()
            return df, text + "\n\n== Span Tree ==\n" + tr.render()

    # -- execution -----------------------------------------------------------

    def _plan_cache_key(self, sql_text: str):
        import jax

        return (
            sql_text,
            self.catalog.version,
            tuple(sorted(self.views.items())),  # view redefinition invalidates
            repr(self.config),
            len(jax.devices()),
        )

    def _plan_cached(self, sql_text: str):
        """(rewrite, logical plan, is-EXPLAIN, RewriteError or None) of
        one SQL statement, through the plan cache: the one place SQL text
        is parsed and planned (`sql`, `sql_progressive` and the server's
        lane classifier all come here, so a served request's second
        lookup hits what its first one stored).  The `plan` span's own
        time is the cache key and lookup, the plan build and the
        star-join collapse; the parse is its `sql_parse` child, the cost
        model's choice its `route` child (plan/planner.py).  The rewrite
        is None for an EXPLAIN and for a plan the rewriter refused."""
        with span(SPAN_PLAN) as sp:
            key = self._plan_cache_key(sql_text)
            cached = self._plan_cache.get(key)
            if sp is not None:
                sp.attrs["cache_hit"] = cached is not None
            if cached is not None:
                rw, lp = cached
                return rw, lp, False, None
            with span(SPAN_SQL_PARSE):
                lp, explain, _ = parse_sql(sql_text, views=self.views)
            if explain:
                return None, lp, True, None
            try:
                rw = self._planner().plan(lp)
            except RewriteError as err:
                return None, lp, False, err
            self._plan_cache[key] = (rw, lp)
            return rw, lp, False, None

    def sql(self, sql_text: str):
        from .resilience import deadline_scope, partial_scope
        from .sql.commands import parse_command, run_command

        cmd = parse_command(sql_text)
        if cmd is not None:
            return run_command(self, cmd)
        # per-query deadline: the session default arms here unless an outer
        # scope (the server's wire `context.timeout`) is already active.
        # The query trace joins the server's when one is active (outermost
        # wins, same contract as deadline_scope); a direct ctx.sql call
        # gets its own generated query_id.  The partial-result collector
        # arms under the same outermost-wins rule: deadline expiry then
        # degrades to a coverage-stamped best-effort answer.
        with self.tracer.query_trace(
            query_type="sql", slow_ms=self.config.slow_query_ms
        ), deadline_scope(self.config.query_timeout_ms), partial_scope(
            self.config.partial_results
        ):
            rw, lp, explain, plan_err = self._plan_cached(sql_text)
            if explain:
                import pandas as pd

                return pd.DataFrame(
                    {"plan": self._planner().explain(lp).split("\n")}
                )
            if rw is None:
                return self._stamp_receipt(
                    self._stamp_partial(self._run_fallback(lp, plan_err))
                )
            with span(SPAN_EXECUTE):
                df = self._stamp_partial(
                    self._execute_with_resilience(rw, lp)
                )
            return self._stamp_receipt(df)

    def sql_progressive(self, sql_text: str):
        """Progressive execution of one SQL statement (ROADMAP 3(b)): a
        generator of `(df, info)` refinements converging to the exact
        answer — the SQL-surface twin of the native route's
        `context.progressive`.  Returns None when the statement cannot
        stream (commands, EXPLAIN, unplannable/fallback shapes, grouping
        sets, exact-distinct, mesh-routed) — the caller then answers
        buffered.  Each refinement passes through the SAME host post-
        processing (`_post_process`) the buffered path applies, so a
        stream's final frame is exactly `ctx.sql`'s answer."""
        from .sql.commands import parse_command

        if parse_command(sql_text) is not None:
            return None
        rw, _lp, _explain, _err = self._plan_cached(sql_text)
        if rw is None:
            return None  # EXPLAIN and fallback shapes answer buffered
        if rw.exact_distinct is not None or rw.grouping_sets:
            return None
        q = rw.query
        if not isinstance(
            q, (Q.GroupByQuery, Q.TimeseriesQuery, Q.TopNQuery)
        ):
            return None
        if isinstance(q, Q.GroupByQuery) and q.subtotals:
            return None
        if self._backend_for(rw) == "mesh":
            return None  # mesh execution has no per-batch refinement yet
        # an open device breaker must not be bypassed just because the
        # client asked for a stream: decline here and the buffered path
        # (ctx.sql -> _execute_with_resilience) degrades properly
        if not self.resilience.breaker_for(
            self._backend_for(rw)
        ).allow():
            return None
        ds = self.catalog.get(rw.datasource)
        if ds is None:
            return None
        engine = self._engine_for(rw)

        def refinements():
            for df, info in engine.execute_progressive(
                q, ds, strategy=rw.physical.strategy
            ):
                with span(SPAN_POST_PROCESS):
                    df = self._post_process(rw, ds, df)
                yield df, info
            self._last_engine_metrics = getattr(
                engine, "last_metrics", None
            )

        return refinements()

    def _sync_engine_resilience(self, engine, backend: str = "device"):
        """Point an engine at this context's breaker for `backend`
        ("device" for the local engine, "mesh" for the distributed one —
        per-backend breakers mean a sick mesh cannot darken the
        single-device path) and sync the retry budget from the session
        config (engines construct with standalone defaults so direct
        Engine() use keeps working)."""
        engine.breaker = self.resilience.breaker_for(backend)
        engine._retry_attempts = self.config.retry_max_attempts
        engine._retry_backoff_ms = self.config.retry_backoff_ms

    def _backend_for(self, rw: Rewrite) -> str:
        """Which execution backend this rewrite will run on — the same
        decision _engine_for makes, shared so breaker routing and engine
        selection can never disagree."""
        phys = rw.physical
        if phys.distributed and phys.mesh_shape is not None:
            import jax

            if len(jax.devices()) >= phys.mesh_shape[0] * phys.mesh_shape[1]:
                return "mesh"
        return "device"

    def _execute_with_resilience(self, rw: Rewrite, lp):
        """Device execution under the target backend's circuit breaker,
        degrading to the host fallback on an open circuit or a transient
        failure that survived the engine's retry budget — the runtime
        extension of the reference's 'a failed rewrite is never an error'
        stance.  Static errors surface unchanged; deadline expiry
        degrades to a coverage-stamped PARTIAL answer when the collector
        is armed (config.partial_results), and surfaces otherwise
        (retrying a timed-out query would only time out slower)."""
        from .resilience import classify_error, current_partial

        res = self.resilience
        backend = self._backend_for(rw)
        br = res.breaker_for(backend)
        can_degrade = (
            lp is not None
            and self.config.fallback_execution
        )
        if can_degrade and not br.allow():
            # an open circuit must not cost a cached answer: the result
            # cache holds exact device-quality frames that need NO device
            # allow_delta=False: a delta refresh dispatches device work,
            # and the breaker just said the device is sick
            hit = self._cached_result(rw, allow_delta=False)
            if hit is not None:
                return hit
            log.warning(
                "%s circuit open; answering on the host fallback", backend
            )
            with span(SPAN_DEGRADED, reason="circuit_open"):
                # the breaker state OBSERVED at routing time: the trace
                # must show WHY the fallback was chosen, not leave the
                # reader to reconstruct it from counters (ROADMAP obs
                # follow-up (c))
                span_event("breaker_state", **_breaker_observation(br))
                df = self._run_fallback(
                    lp, None, reason=f"{backend} circuit open"
                )
            self._stamp_degraded(None, backend=backend)
            return df
        try:
            df = self.execute_rewrite(rw)
        except Exception as err:
            kind = classify_error(err)
            if kind == "deadline":
                pc = current_partial()
                if pc is not None:
                    # partial-aware caching (ROADMAP 3(d)): before
                    # draining a low-coverage best-effort answer, serve
                    # a cached EXACT one if a concurrent identical query
                    # populated the cache since this one started — a
                    # complete cached frame beats any partial, and a
                    # cached-exact hit is never stamped partial
                    hit = self._cached_result(rw, allow_delta=False)
                    if hit is not None:
                        return hit
                    # the deadline expired OUTSIDE the partial-capable
                    # loops (planning, a blocking fetch, a ladder rung):
                    # trigger the collector and drain-rerun — every
                    # checkpoint is now a no-op and the executor loops
                    # stop at their first batch, so the rerun costs one
                    # lowering + an empty finalize and yields the
                    # well-formed zero-or-low-coverage answer instead of
                    # a 504
                    pc.trigger(getattr(err, "site", "") or "deadline")
                    log.warning(
                        "deadline expired outside a partial-capable "
                        "loop (%s); draining a best-effort answer", err,
                    )
                    return self.execute_rewrite(rw)
                res.note_deadline_exceeded()
                err._sdol_counted = True  # the server layer must not re-count
                m = self.last_metrics
                if m is not None:
                    m.deadline_exceeded = True
                raise
            if kind != "transient" or not can_degrade:
                raise
            log.warning(
                "%s execution failed (%s: %s) after retries; "
                "degrading to the host fallback",
                backend, type(err).__name__, err,
            )
            with span(SPAN_DEGRADED, reason="device_failed"):
                span_event(
                    "breaker_state",
                    error_class=type(err).__name__,
                    **_breaker_observation(br),
                )
                df = self._run_fallback(
                    lp, err, reason=f"{backend} execution failed"
                )
            self._stamp_degraded(err, backend=backend)
            return df
        m = self.last_metrics
        # report to the breaker for EVERY query type: the GroupBy engines
        # record internally, but a half-open probe served by a timeseries/
        # topN/scan (or a result-cache hit that never touched the device)
        # must not leave the lease dangling and the breaker half-open on a
        # healthy device
        if m is not None and m.strategy == "result-cache":
            br.release_probe()
        else:
            br.record_success()
        if m is not None and not m.circuit_state:
            m.circuit_state = br.state
        return df

    def _stamp_degraded(self, err, backend: str = "device"):
        """Mark the (fallback) metrics of a degraded answer and count it."""
        self.resilience.note_degraded()
        m = self.last_metrics
        if m is not None:
            m.degraded = True
            m.circuit_state = self.resilience.breaker_for(backend).state
            if err is not None:
                m.error_class = type(err).__name__

    def _stamp_partial(self, df):
        """Stamp a deadline-bounded PARTIAL answer: the result frame's
        attrs carry {"partial": True, "coverage": ...} (the SQL-surface
        contract; the server folds the same dict into
        X-Druid-Response-Context), the metrics carry partial/coverage,
        and the `partial` span + coverage histogram record it for the
        trace and the fleet (partial-result discipline, GL16xx).  A
        no-op for complete answers."""
        from .resilience import current_partial

        pc = current_partial()
        if pc is None or not pc.is_partial:
            return df
        # partial-aware caching (ROADMAP 3(d)): a result served FROM the
        # cache is an exact answer computed before the deadline existed —
        # a triggered collector describes the aborted execution, not the
        # cached frame, and must never stamp it down to partial
        m_hit = self._last_engine_metrics
        if m_hit is not None and str(
            getattr(m_hit, "strategy", "")
        ).startswith("result-cache"):
            return df
        info = pc.to_dict()
        with span(
            SPAN_PARTIAL,
            coverage=info["coverage"],
            site=info["site"],
            rows_seen=info["rows_seen"],
            rows_total=info["rows_total"],
        ):
            record_partial(
                info["coverage"], site=info["site"] or "",
                query_id=current_query_id(),
            )
        m = self.last_metrics
        if m is not None:
            m.partial = True
            m.coverage = info["coverage"]
            m.rows_seen = info["rows_seen"]
            m.delta_rows_seen = info["delta_rows_seen"]
        try:
            df.attrs.update(info)
        except AttributeError:  # fault-ok: non-pandas results skip attrs
            pass
        return df

    def _stamp_receipt(self, df):
        """Ask the active trace to stamp its cost receipt (obs/prof.py,
        ISSUE 9) onto the answer when it closes: `df.attrs["receipt"]`
        (the SQL-surface contract) and `QueryMetrics.receipt` hold the
        receipt of the CLOSED trace, the one the trace doc carries —
        built once.  A no-op outside a trace (direct engine use without
        a context).  The answer's row count is `QueryMetrics.result_rows`."""
        from .obs import current_trace

        m = self.last_metrics
        if m is not None and hasattr(df, "__len__"):
            m.result_rows = len(df)
        tr = current_trace()
        if tr is not None:
            tr.stamp_receipt_on(metrics=self.last_metrics, frame=df)
        return df

    def execute_native_degraded(
        self, q, err=None, reason: str = "native degradation",
        backend: str = "device",
    ):
        """Answer a wire-native QuerySpec on the host fallback — the
        degradation-matrix cell that used to 503.  The spec decodes to a
        logical plan (exec/wire_fallback.py, riding the WIRE_AGG_FALLBACK
        registry) and runs through the SAME `_run_fallback` gate SQL
        queries degrade through, so policy (fallback_execution, size
        ceiling, fallback breaker) cannot drift between surfaces.
        Raises WireFallbackUnsupported for specs outside the
        interpreter's coverage — the server then falls back to the old
        fail-fast 503."""
        from .exec.wire_fallback import native_to_logical, shape_native_result

        ds = self.catalog.get(q.datasource)
        if ds is None:
            raise RewriteError(f"unknown table {q.datasource!r}")
        lp = native_to_logical(q, ds)  # may raise WireFallbackUnsupported
        with span(SPAN_DEGRADED, reason="native_" + reason):
            span_event(
                "breaker_state",
                **_breaker_observation(self.resilience.breaker_for(backend)),
            )
            df = self._run_fallback(lp, err, reason=reason)
        self._stamp_degraded(err, backend=backend)
        # partial-result discipline (GL16xx): a deadline-bounded degraded
        # answer publishes its coverage (partial span + fleet counter)
        # exactly like the SQL surface — the server only adds the header.
        # Receipts survive the degraded path too (ISSUE 9 satellite):
        # the fallback's host time is attributed like any other query's.
        return shape_native_result(
            q, ds, self._stamp_receipt(self._stamp_partial(df))
        )

    def _run_fallback(self, lp, err, reason: str = "rewrite failed"):
        """The reference's vanilla-Spark fallback: a failed rewrite runs
        the logical plan host-side instead of erroring — observably
        (QueryMetrics.executor = "fallback") and size-guarded
        (SessionConfig.fallback_max_rows).  Policy rejections and a
        disabled fallback re-raise the original RewriteError — the gate
        lives HERE so every caller (sql, explain_analyze, circuit-broken
        degradation) agrees.  `err` may be None (breaker-open routing:
        there is no triggering exception)."""
        import time as _time

        from .exec.fallback import execute_fallback, plan_input_rows
        from .exec.metrics import QueryMetrics
        from .plan.transforms import RewritePolicyError

        if isinstance(err, RewritePolicyError):
            raise err  # explicit policy/validation rejection — no fallback
        if not self.config.fallback_execution:
            if err is not None:
                raise err
            raise RewriteError("fallback execution is disabled")
        # the FALLBACK breaker: the host interpreter is itself a backend
        # that can be sick (torn decodes, host I/O faults) — consecutive
        # transient failures open it, and while open a degraded query
        # fails FAST with the original error instead of re-grinding a
        # known-bad path; a half-open probe recovers it.  Per-backend
        # granularity: this breaker never touches device/mesh routing.
        fb = self.resilience.breaker_for("fallback")
        if not fb.allow():
            from .resilience import CircuitOpenError

            log.warning(
                "host-fallback circuit open; failing fast (%s)", reason
            )
            if err is not None:
                raise err
            raise CircuitOpenError(
                "host-fallback circuit open and no healthier backend "
                "remains — retry after the breaker's cooldown"
            )

        log.warning(
            "%s (%s); executing on the host fallback", reason, err
        )
        t0 = _time.perf_counter()
        assists = {"n": 0}

        def device_subplan(sub_lp):
            """Device-assist hook: offer an Aggregate subtree to the normal
            rewrite path.  Any failure means 'interpret it host-side' —
            the assist must never turn a working fallback into an error.

            The decision is COST-BASED (VERDICT r4 #6): assist engages when
            the modelled engine kernel time at the subtree's group
            cardinality (plan/cost.query_kernel_costs, calibrated) clearly
            beats rows x cost_per_row_interp.  This separates the two
            fallback shapes a row threshold cannot: a q2-class subtree
            (tiny G over a big base — engine wins 15-100x measured) from a
            q18-class one (G ~ rows/4 — the interpreter's single pass
            wins).  Small bases stay on the (float64-exact, instant)
            interpreter regardless: see device_assist_min_rows."""
            try:
                rows = plan_input_rows(sub_lp, self.catalog)
                if rows < self.config.device_assist_min_rows:
                    return None
                rw = self._planner().plan(sub_lp)
                from .exec.lowering import lower_groupby
                from .models import query as Q
                from .plan.cost import query_kernel_costs

                if rw.exact_distinct is not None or not isinstance(
                    rw.query, Q.GroupByQuery
                ):
                    # uncostable shape (timeseries/topn/exact-distinct
                    # subtree): no kernel-cost estimate exists, so apply a
                    # HIGH row bar instead — huge bases still offload (the
                    # r4 behavior), everything else interprets
                    if rows < max(
                        self.config.device_assist_min_rows, 1 << 23
                    ) and not self.config.device_assist_force:
                        return None
                else:
                    ds = self.catalog.get(rw.datasource)
                    lowering = lower_groupby(rw.query, ds)
                    G = lowering.num_groups
                    # h2d: columns of the subtree's base not yet resident in
                    # the engine's device cache must cross the host->device
                    # link first, at the calibrated link rate
                    # (h2d_bytes_per_s).  Amortized /3 like the adaptive
                    # probe: the cache keeps columns warm across the repeat
                    # queries this workload shape is built around.
                    phys = rw.physical
                    if phys.distributed and phys.mesh_shape is not None:
                        # mesh execution: the DistributedEngine's shard
                        # residency is not visible here — price the
                        # transfer fully cold (conservative: borderline
                        # assists decline, the never-slower direction)
                        miss_bytes = 4 * (len(lowering.columns) + 1) * rows
                    else:
                        miss_bytes = self.engine.missing_resident_bytes(
                            ds, lowering.columns
                        )
                    h2d_us = (
                        miss_bytes / self.config.h2d_bytes_per_s * 1e6
                    )
                    assist_us = (
                        min(
                            query_kernel_costs(
                                rw.query, ds, G, self.config
                            ).values()
                        )
                        + self.config.cost_dispatch_us
                        + h2d_us / 3.0
                        # the assisted path re-pays host work PER RESULT
                        # GROUP (decode, frame build, downstream
                        # interpretation)
                        + G * self.config.cost_per_group_decode
                    )
                    interp_us = rows * self.config.cost_per_row_interp
                    # 3x modelled margin: q17-class subtrees (G ~ rows/20)
                    # land within noise of the boundary at 2x and measured
                    # a 0.9-1.1x wash either way — never-slower means
                    # declining the coin flips, not just the clear losses
                    if (
                        assist_us * 3 >= interp_us
                        and not self.config.device_assist_force
                    ):
                        return None
            except RewriteError:
                return None
            except Exception:
                # quirk-shaped internal subtrees (decorrelator output) may
                # crash the planner rather than decline; the assist must
                # never turn a working fallback into an error
                log.warning(
                    "device-assist planning failed; interpreting host-side",
                    exc_info=True,
                )
                return None
            try:
                out = self.execute_rewrite(rw, use_result_cache=False)
            except Exception:
                log.warning(
                    "device-assist subplan failed; interpreting host-side",
                    exc_info=True,
                )
                return None
            assists["n"] += 1
            return out

        from .resilience import DeadlineExceeded, classify_error, current_partial

        pc = current_partial()
        if pc is not None:
            # the interpreter owns ONE accounting pass spanning every
            # table it decodes; assist subtrees must not reset it
            pc.begin_pass()
            pc.in_fallback = True
        try:
            with span(SPAN_FALLBACK, reason=reason):
                df = execute_fallback(
                    lp, self.catalog,
                    max_rows=self.config.fallback_max_rows,
                    device_exec=device_subplan,
                )
        except DeadlineExceeded as dl_err:
            # expiry at an interpretation checkpoint (decode-site expiry
            # is absorbed inline by checkpoint_partial): trigger the
            # collector and drain-rerun — the decoded-frame cache makes
            # the second decode ~free, checkpoints are now no-ops, and
            # the interpreter finishes over the full frames, so the
            # "partial" usually drains to the complete answer
            if pc is None:
                raise
            pc.trigger(dl_err.site or "fallback.interp")
            # the rerun's own accounting is the truth about what the
            # final answer saw: the aborted pass's scope/seen counters
            # would double the denominator and claim rows the rerun
            # never serves (decoded_frame in drain mode only includes
            # warm-cached segments)
            pc.reset_for_drain()
            with span(SPAN_FALLBACK, reason="deadline_drain"):
                df = execute_fallback(
                    lp, self.catalog,
                    max_rows=self.config.fallback_max_rows,
                    device_exec=device_subplan,
                )
            fb.record_success()
        except Exception as fb_err:
            # only TRANSIENT failures (decode faults, host I/O) count on
            # the fallback breaker — a static plan/shape gap is a
            # property of the query, not of the backend's health
            if classify_error(fb_err) == "transient":
                fb.record_failure()
            raise
        else:
            fb.record_success()
        finally:
            if pc is not None:
                pc.in_fallback = False
        from .exec.fallback import plan_tables

        tables = sorted(plan_tables(lp))
        m = QueryMetrics(
            query_type="fallback",
            strategy="host-pandas",
            executor="device+fallback" if assists["n"] else "fallback",
            datasource=tables[0] if len(tables) == 1 else "",
            query_id=current_query_id(),
            rows_scanned=plan_input_rows(lp, self.catalog),
            total_ms=(_time.perf_counter() - t0) * 1e3,
            assist_subplans=assists["n"],
        )
        if pc is not None and pc.is_partial:
            # partial-result discipline (GL16xx): partial=True always
            # travels with its coverage fraction
            m.partial = True
            m.coverage = pc.coverage()
            m.rows_seen = pc.rows_seen
            m.delta_rows_seen = pc.delta_rows_seen
        self._last_engine_metrics = m
        # the host interpreter publishes into the process registry like
        # the device engines do (obs/): fallback traffic must be visible
        # in the fleet-level counts, not just last_metrics
        record_query_metrics(m, "partial" if m.partial else "ok")
        return df

    def _result_key(self, rw: Rewrite, ds=None):
        """Result-cache key of a rewrite, or None when it isn't cacheable
        (unknown table / exact-distinct outer shape).  Deliberately
        EXCLUDES the segment uid set and the datasource version: entries
        carry the version they were computed at (serve/result_cache.py),
        which is what lets an append REUSE the cached historical partial
        instead of missing outright.  Dictionary content stays in the
        key — a dictionary extension remaps code spaces, and a stale
        state under extended dictionaries would decode wrong groups."""
        if rw.exact_distinct is not None:
            return None
        ds = ds or self.catalog.get(rw.datasource)
        if ds is None:
            return None
        from .exec.lowering import _dict_signature

        return (
            rw.to_json(),
            ds.name,
            _dict_signature(ds),
            repr(rw.output_columns),
            repr(rw.grouping_sets),
            repr(rw.host_post_exprs),
            repr(rw.residual_having),
            repr(self.config),
        )

    def _cached_result(self, rw: Rewrite, rkey=None, allow_delta=True):
        """Serve a result-cache hit — version-exact, or delta-aware when
        only appends separate the cached snapshot from the live one
        (serve/result_cache.py).  The serving core restamps last_metrics
        so they describe THIS query (a prior fallback would otherwise
        leave executor="fallback" pinned on a cached device hit).
        Returns None on a miss."""
        if self.config.result_cache_entries <= 0:
            return None
        ds = self.catalog.get(rw.datasource)
        if ds is None:
            return None
        rkey = rkey or self._result_key(rw, ds)
        if rkey is None:
            return None
        return self.serve.cached_result(
            rw, ds, rkey, allow_delta=allow_delta
        )

    def _post_process(self, rw: Rewrite, ds, df):
        """Host-side result shaping every engine answer passes through —
        shared by live execution, the delta-aware cache refresh, and the
        progressive SQL surface so the three can never drift."""
        # FD grouping pruning: decode the hidden max-over-codes carriers
        # back into the pruned columns BEFORE residuals/projection, so
        # downstream expressions see the restored values
        for out_name, hidden, dim_col in rw.fd_restores:
            raw = np.asarray(df[hidden], dtype=np.float64)
            codes = np.where(np.isnan(raw), -1, raw).astype(np.int64)
            df[out_name] = ds.dicts[dim_col].decode(codes)
            df = df.drop(columns=[hidden])

        # host-side residuals (the DruidStrategy projection-fixup analog)
        for name, e in rw.host_post_exprs:
            df[name] = _eval_host(e, df)
        if rw.residual_having is not None:
            mask = np.asarray(_eval_host(rw.residual_having, df), dtype=bool)
            df = df[mask].reset_index(drop=True)
        if rw.output_columns:
            cols = [c for c in rw.output_columns if c in df.columns]
            extra = [c for c in df.columns if c not in cols and c == "__grouping_id"]
            df = df[cols + extra]
        return df

    def _fusable(self, rw: Rewrite, ds) -> bool:
        """May this rewrite ride the micro-batch fusion / state-capture
        path?  GroupBy-family only, no grouping sets (their expansion
        already batches), and the executing backend's own gate — both
        the single-device engine and the mesh's unified SPMD arena
        (parallel/distributed.py) implement `fusable`, so mesh-routed
        dashboards batch exactly like local ones (sparse/adaptive tiers
        and arena-ineligible layouts decline on either backend)."""
        if rw.grouping_sets or rw.exact_distinct is not None:
            return False
        if not isinstance(
            rw.query, (Q.GroupByQuery, Q.TimeseriesQuery, Q.TopNQuery)
        ):
            return False
        return self._engine_for(rw).fusable(
            rw.query, ds, strategy=rw.physical.strategy
        )

    def execute_rewrite(self, rw: Rewrite, use_result_cache: bool = True):
        import pandas as pd

        if rw.exact_distinct is not None:
            return self._execute_exact_distinct(
                rw.exact_distinct, use_result_cache=use_result_cache
            )
        ds = self.catalog.get(rw.datasource)
        if ds is None:
            raise RewriteError(f"unknown table {rw.datasource!r}")

        rkey = None
        if use_result_cache and self.config.result_cache_entries > 0:
            rkey = self._result_key(rw, ds)
            hit = self._cached_result(rw, rkey)
            if hit is not None:
                return hit

        # broker mode (cluster/, ISSUE 16): a covered SQL query scatters
        # to the historicals and gathers through the merge tree; the
        # result cache above rides the broker (exact hits never leave
        # this process), fusion below stays local-only.  Partial answers
        # never enter the cache (the pc.triggered guard at the bottom).
        if self.cluster is not None and self.cluster.covers(rw.query, ds):
            if not rw.grouping_sets and rw.exact_distinct is None:
                df = self.cluster.execute(rw.query, ds)
                self._last_engine_metrics = self.cluster.last_metrics
                with span(SPAN_POST_PROCESS):
                    df = self._post_process(rw, ds, df)
                if rkey is not None:
                    from .resilience import current_partial

                    pc = current_partial()
                    if pc is None or not pc.triggered:
                        self.serve.store_result(rw, ds, rkey, df)
                return df

        with span(SPAN_ROUTE):
            engine = self._engine_for(rw)
        # the plan's kernel class and this session's cost constants
        # travel with the query: arguments of the call, never a field
        # written on the (shared) engine
        route = {"strategy": rw.physical.strategy, "cfg": self.config}
        state = None
        fusable = self._fusable(rw, ds)
        # one span around whichever call into the engine serves the
        # request: its self time is the engine's own code between the
        # engine's spans (memo key, QueryMetrics, batching, retries)
        backend = "mesh" if engine is self._dist_engine else "device"
        with span(SPAN_ENGINE, backend=backend):
            fused = (
                self.serve.fused_execute(
                    rw.query, ds, engine=engine, strategy=route["strategy"]
                )
                if fusable else None
            )
            if fused is not None:
                df, state, m = fused
                self._last_engine_metrics = m
            elif rw.grouping_sets and isinstance(rw.query, Q.GroupByQuery):
                df = execute_grouping_sets(
                    rw.query, rw.grouping_sets, ds, engine, **route
                )
                self._last_engine_metrics = getattr(
                    engine, "last_metrics", None
                )
            elif (
                fusable
                and rkey is not None
                and self.config.result_cache_delta_reuse
            ):
                # capture the merged host partial state alongside the
                # normal execution: the delta-aware result cache stores
                # it so the NEXT append refreshes this answer by
                # scanning only the delta (serve/result_cache.py)
                with engine.state_capture() as cap:
                    df = engine.execute(rw.query, ds, **route)
                state = cap["state"]
                self._last_engine_metrics = getattr(
                    engine, "last_metrics", None
                )
            else:
                df = engine.execute(rw.query, ds, **route)
                self._last_engine_metrics = getattr(
                    engine, "last_metrics", None
                )

        with span(SPAN_POST_PROCESS):
            df = self._post_process(rw, ds, df)
        if rkey is not None:
            from .resilience import current_partial

            pc = current_partial()
            # a deadline-truncated answer must NEVER enter the result
            # cache: it would be served back as the exact answer to the
            # next identical (undeadlined) query
            if pc is None or not pc.triggered:
                self.serve.store_result(rw, ds, rkey, df, state=state)
        return df

    def _execute_exact_distinct(self, spec, use_result_cache: bool = True):
        """Two-phase exact COUNT(DISTINCT): run the inner rewrite (grouped by
        dims + distinct columns on device), then re-aggregate on host —
        the reference's pushHLLTODruid=false shape, where Spark finished the
        distinct exactly after the Druid scan."""
        import pandas as pd

        inner = self.execute_rewrite(
            spec.inner, use_result_cache=use_result_cache
        )
        agg_kwargs = {
            name: pd.NamedAgg(column=name, aggfunc=op)
            for name, op in spec.outer_ops
        }
        for out, col in spec.distinct_outs:
            # pandas nunique skips None/NaN — SQL COUNT(DISTINCT) semantics
            agg_kwargs[out] = pd.NamedAgg(column=col, aggfunc="nunique")
        if spec.dim_names:
            df = (
                inner.groupby(list(spec.dim_names), as_index=False, dropna=False)
                .agg(**agg_kwargs)
            )
        else:
            df = pd.DataFrame(
                {
                    name: [getattr(inner[a.column], a.aggfunc)()]
                    for name, a in agg_kwargs.items()
                }
            )
        for c in spec.count_like:
            if c in df:
                df[c] = df[c].astype(np.int64)
        for out, _ in spec.distinct_outs:
            df[out] = df[out].astype(np.int64)
        for name, s, c in spec.avg_div:
            with np.errstate(divide="ignore", invalid="ignore"):
                df[name] = np.where(
                    df[c] != 0, df[s] / np.where(df[c] == 0, 1, df[c]), np.nan
                )
        for name, e in spec.post_exprs:
            df[name] = _eval_host(e, df)
        if spec.having is not None:
            mask = np.asarray(_eval_host(spec.having, df), dtype=bool)
            df = df[mask].reset_index(drop=True)
        if spec.sort_keys:
            df = df.sort_values(
                [c for c, _ in spec.sort_keys],
                ascending=[a for _, a in spec.sort_keys],
                kind="stable",
            )
        if spec.offset:
            df = df.iloc[spec.offset:]
        if spec.limit is not None:
            df = df.head(spec.limit)
        cols = [c for c in spec.output_columns if c in df.columns]
        return df[cols].reset_index(drop=True)

    def _engine_for(self, rw: Rewrite):
        """The engine of the rewrite's backend.  Nothing of the request
        is written on it (the breaker sync aside): engines are shared by
        every request in flight."""
        # ONE routing decision, shared with breaker selection: branching
        # on _backend_for here is what keeps its "can never disagree"
        # docstring true — an edit to the mesh condition lands on both
        if self._backend_for(rw) == "mesh":
            if self._dist_engine is None:
                from .parallel.distributed import DistributedEngine
                from .parallel.mesh import make_mesh

                self._dist_engine = DistributedEngine(
                    mesh=make_mesh(*rw.physical.mesh_shape),
                    config=self.config,
                )
            # the mesh path reports to ITS OWN breaker: a sick mesh
            # trips only itself, single-device queries stay routed
            self._sync_engine_resilience(self._dist_engine, "mesh")
            return self._dist_engine
        self._sync_engine_resilience(self.engine)
        return self.engine

    # -- DataFrame-ish builder (the reference's "sourceDataframe" analog) ----

    def sql_arrow(self, sql_text: str):
        """`sql()` with the result as a `pyarrow.Table` (SURVEY §7 L-api:
        results as Arrow/pandas).  NULLs in dimension columns become Arrow
        nulls; NaN metrics stay floating-point NaN (SQL NULL for floats)."""
        return _to_arrow(self.sql(sql_text))

    def table(self, name: str) -> "TableQuery":
        return TableQuery(self, name)


def _eval_host(e: E.Expr, df) -> np.ndarray:
    """Evaluate a residual expression over the result table (aggregate
    outputs / dimensions) host-side — tiny data, numpy semantics."""
    from .plan.expr import compile_expr

    cols = {c: np.asarray(df[c]) for c in df.columns}
    # raw_strings: result columns hold decoded strings, so HAVING/post-expr
    # string comparisons use plain numpy elementwise semantics
    fn = compile_expr(_aggref_to_col(e), raw_strings=True)
    return np.asarray(fn(cols))


def execute_grouping_sets(
    q: Q.GroupByQuery, grouping_sets, ds, engine, **route
):
    """CUBE/ROLLUP/GROUPING SETS: absent dimensions emitted as nulls, plus
    a __grouping_id bitmask (SQL GROUPING_ID semantics: bit i set => dim i
    aggregated away).

    Two forms, the cost model's to choose (`plan.cost.rollup_takes`, asked
    by the engine's `execute_rollup`): the rollup, ONE scan grouped by the
    union of the sets' dimensions with every set reduced from its merged
    state on the device; else one scan a set.  An engine without a rollup
    (the mesh) runs the per-set form.  Either way the engine's
    `last_metrics` ends as the whole request's (`grouping_sets`,
    `set_scans`, every scan counted, any set's fault the request's).

    Shared by the SQL path (rw.grouping_sets) and the serving path (a wire
    groupBy's subtotalsSpec, server.py) — the two must not drift.  `route`
    is the plan's `strategy=` / `cfg=` for the engine (none on the wire
    path: the engine's own)."""
    import pandas as pd

    all_dims = q.dimensions
    k = len(all_dims)
    with span(SPAN_GROUPING_SETS, sets=len(grouping_sets)) as sp:
        rollup = getattr(engine, "execute_rollup", None)
        results = (
            rollup(q, grouping_sets, ds, **route) if rollup is not None
            else None
        )
        if sp is not None:
            sp.attrs["form"] = "per_set" if results is None else "rollup"
        if results is None:
            results = _per_set_results(q, grouping_sets, ds, engine, route)
        frames = []
        for s, f in zip(grouping_sets, results):
            gid = 0
            present = set(s)
            for i in range(k):
                if i not in present:
                    gid |= 1 << (k - 1 - i)
                    f[all_dims[i].name] = None
            f["__grouping_id"] = gid
            frames.append(f)
        df = pd.concat(frames, ignore_index=True)
        order = [d.name for d in all_dims]
        rest = [c for c in df.columns if c not in order]
        df = df[order + rest]
        if q.limit_spec is not None:
            from .exec.finalize import apply_limit_spec

            df = apply_limit_spec(df, q.limit_spec).reset_index(drop=True)
        return df


def _per_set_results(q, grouping_sets, ds, engine, route):
    """The per-set form of `execute_grouping_sets`: one scan a set."""
    from .exec.metrics import fold_set_metrics
    from .resilience import current_partial

    all_dims = q.dimensions
    # the limit/order spec applies to the COMBINED result, not per set —
    # and a per-set sort would crash on sets that drop the orderBy dimension
    subs = [
        dataclasses.replace(
            q,
            dimensions=tuple(all_dims[i] for i in s),
            subtotals=(),
            limit_spec=None,
        )
        for s in grouping_sets
    ]
    # per-grouping-set coverage attribution (ROADMAP 3(c)): each set's
    # scan is its OWN accounting pass; the collector archives every pass
    # (labeled with the set's dimension list) instead of letting the
    # last subquery's begin_pass erase its predecessors — coverage and
    # the partial histogram then describe the WHOLE expansion, and
    # df.attrs carries the per-set breakdown
    pc = current_partial()
    set_labels = None
    if pc is not None:
        # under the collector's lock: collect_sets is `_lock`-owned and
        # the runtime witness enforces it (an off-lock flip here was the
        # first divergence graftsan caught on the shipped tree)
        pc.arm_set_collection()
        set_labels = [
            ",".join(all_dims[i].name for i in s) or "()"
            for s in grouping_sets
        ]
    # dispatch every set's device program before fetching any result:
    # N sequential executions pay N full round trips; the batch path
    # overlaps them (and folds the sets' metrics into the request's)
    if hasattr(engine, "execute_groupby_batch"):
        results = engine.execute_groupby_batch(
            subs, ds, set_labels=set_labels, **route
        )
    else:
        results, parts = [], []
        for i, sub in enumerate(subs):
            if pc is not None and set_labels is not None:
                pc.set_label = set_labels[i]
            results.append(engine.execute(sub, ds, **route))
            parts.append(engine.last_metrics)
        if all(p is not None for p in parts):
            engine.last_metrics = fold_set_metrics(parts, len(subs))
    if pc is not None:
        pc.finish_sets()
    return results


def _aggref_to_col(e: E.Expr) -> E.Expr:
    if isinstance(e, E.AggRef):
        return E.Col(e.name)
    if isinstance(e, (E.Literal, E.Col)):
        return e
    kw = {}
    for f in dataclasses.fields(e):  # type: ignore[arg-type]
        v = getattr(e, f.name)
        if isinstance(v, E.Expr):
            kw[f.name] = _aggref_to_col(v)
        elif isinstance(v, tuple) and v and isinstance(v[0], E.Expr):
            kw[f.name] = tuple(_aggref_to_col(x) for x in v)
        else:
            kw[f.name] = v
    return type(e)(**kw)


def _infer_schema(cols, time_column):
    dims, mets = [], []
    for k, v in cols.items():
        if k == time_column:
            continue
        arr = np.asarray(v)
        if arr.dtype.kind in ("U", "S", "O"):
            dims.append(k)
        else:
            mets.append(k)
    return dims, mets


# ---------------------------------------------------------------------------
# Fluent DataFrame-style query builder
# ---------------------------------------------------------------------------


class TableQuery:
    """Fluent DataFrame-style API over the same planner — the analog of
    driving the reference through Spark DataFrames instead of SQL.  Every
    method returns a NEW TableQuery (immutable chaining, like DataFrames);
    `collect()` plans, executes on the device, and falls back to the host
    interpreter exactly like the SQL path when the rewrite fails."""

    def __init__(self, ctx: TPUOlapContext, table: str):
        self.ctx = ctx
        self._table = table
        self._filter: Optional[E.Expr] = None
        self._select: List[Tuple[str, E.Expr]] = []
        self._groups: List[Tuple[str, E.Expr]] = []
        self._aggs: List[L.AggExpr] = []
        self._having: Optional[E.Expr] = None
        self._sort: List[L.SortKey] = []
        self._limit: Optional[int] = None
        self._offset: int = 0

    def _copy(self) -> "TableQuery":
        out = TableQuery(self.ctx, self._table)
        out._filter = self._filter
        out._select = list(self._select)
        out._groups = list(self._groups)
        out._aggs = list(self._aggs)
        out._having = self._having
        out._sort = list(self._sort)
        out._limit = self._limit
        out._offset = self._offset
        return out

    @staticmethod
    def _as_expr(x) -> E.Expr:
        return E.Col(x) if isinstance(x, str) else x

    def filter(self, e: E.Expr) -> "TableQuery":
        out = self._copy()
        out._filter = e if out._filter is None else E.BoolOp(
            "and", (out._filter, e)
        )
        return out

    where = filter  # Spark/SQL spelling

    def select(self, *exprs, **named) -> "TableQuery":
        """Projection for non-aggregate queries: select("a", "b") or
        select(rev=E.Col("price") * E.Col("qty"))."""
        out = self._copy()
        for x in exprs:
            e = self._as_expr(x)
            out._select.append((x if isinstance(x, str) else str(e), e))
        for name, x in named.items():
            out._select.append((name, self._as_expr(x)))
        return out

    def group_by(self, *exprs, **named) -> "TableQuery":
        out = self._copy()
        for x in exprs:
            e = self._as_expr(x)
            out._groups.append((x if isinstance(x, str) else str(e), e))
        for name, x in named.items():
            out._groups.append((name, self._as_expr(x)))
        return out

    def agg(self, **named) -> "TableQuery":
        """agg(total=("sum", "revenue"), n=("count", None), ...); the arg
        may be a column name or an Expr (sum over an expression)."""
        out = self._copy()
        for name, spec in named.items():
            fn, arg = spec if isinstance(spec, tuple) else (spec, None)
            arg_e = self._as_expr(arg) if arg is not None else None
            out._aggs.append(L.AggExpr(name, fn, arg_e))
        return out

    def having(self, e: E.Expr) -> "TableQuery":
        """Filter over aggregate outputs: reference agg outputs by their
        `agg(...)` names via E.AggRef (or E.Col of the output name)."""
        out = self._copy()
        out._having = e if out._having is None else E.BoolOp(
            "and", (out._having, e)
        )
        return out

    def order_by(self, key, ascending: bool = True) -> "TableQuery":
        out = self._copy()
        out._sort.append(L.SortKey(self._as_expr(key), ascending))
        return out

    def limit(self, n: int, offset: int = 0) -> "TableQuery":
        out = self._copy()
        out._limit = n
        out._offset = offset
        return out

    def _logical(self) -> L.LogicalPlan:
        base: L.LogicalPlan = L.Scan(self._table)
        if self._filter is not None:
            base = L.Filter(self._filter, base)
        if self._groups or self._aggs:
            if self._select:
                raise ValueError(
                    "select() is for non-aggregate queries; grouped "
                    "outputs are named by group_by()/agg()"
                )
            post = tuple(
                (n, E.Col(n)) for n, _ in self._groups
            ) + tuple((a.name, E.AggRef(a.name)) for a in self._aggs)
            plan: L.LogicalPlan = L.Aggregate(
                tuple(self._groups),
                tuple(self._aggs),
                base,
                post_exprs=post,
            )
            if self._having is not None:
                plan = L.Having(_col_to_aggref(self._having, self._aggs), plan)
        else:
            if self._having is not None:
                raise ValueError("having() requires group_by()/agg()")
            plan = (
                L.Project(tuple(self._select), base) if self._select else base
            )
        if self._sort:
            keys = tuple(
                L.SortKey(_col_to_aggref(k.expr, self._aggs), k.ascending)
                for k in self._sort
            )
            plan = L.Sort(keys, plan)
        if self._limit is not None:
            plan = L.Limit(self._limit, plan, self._offset)
        return plan

    def collect(self):
        from .resilience import deadline_scope, partial_scope

        lp = self._logical()
        with self.ctx.tracer.query_trace(
            query_type="dataframe", slow_ms=self.ctx.config.slow_query_ms
        ), deadline_scope(self.ctx.config.query_timeout_ms), partial_scope(
            self.ctx.config.partial_results
        ):
            with span(SPAN_PLAN):
                try:
                    rw = self.ctx._planner().plan(lp)
                except RewriteError as err:
                    rw, plan_err = None, err
            if rw is None:
                return self.ctx._stamp_receipt(
                    self.ctx._stamp_partial(
                        self.ctx._run_fallback(lp, plan_err)
                    )
                )
            with span(SPAN_EXECUTE):
                df = self.ctx._stamp_partial(
                    self.ctx._execute_with_resilience(rw, lp)
                )
            return self.ctx._stamp_receipt(df)

    def collect_arrow(self):
        """`collect()` as a `pyarrow.Table`."""
        return _to_arrow(self.collect())

    def explain(self) -> str:
        return self.ctx._planner().explain(self._logical())


def _to_arrow(df):
    import pyarrow as pa

    return pa.Table.from_pandas(df, preserve_index=False)


def _col_to_aggref(e: E.Expr, aggs) -> E.Expr:
    """In HAVING/ORDER BY over a grouped TableQuery, a Col naming an agg
    output means the aggregate (SQL alias semantics)."""
    names = {a.name for a in aggs}
    return E.map_expr(
        e,
        lambda x: E.AggRef(x.name)
        if isinstance(x, E.Col) and x.name in names
        else x,
    )


# module-level default context (the implicit SQLContext analog)
_default_ctx: Optional[TPUOlapContext] = None


def default_context() -> TPUOlapContext:
    global _default_ctx
    if _default_ctx is None:
        _default_ctx = TPUOlapContext()
    return _default_ctx


def register_table(*a, **kw):
    return default_context().register_table(*a, **kw)


def sql(text: str):
    return default_context().sql(text)


def table(name: str) -> TableQuery:
    return default_context().table(name)


def explain(text: str) -> str:
    return default_context().explain(text)
