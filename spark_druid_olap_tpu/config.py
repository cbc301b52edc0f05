"""Two-tier configuration: session flags + per-table options.

Reference parity (SURVEY.md §5 config row `[U]`): the reference has (1)
per-table options in `CREATE TABLE ... USING ... OPTIONS(...)` (DefaultSource
row of SURVEY.md §2) and (2) session flags registered by `DruidPlanner` under
SQLConf keys `spark.sparklinedata.druid.*` (rewrite enables, cost-model
constants, max cardinality, smile encoding, historical-query toggles).  We
mirror both tiers with dataclasses; option names keep the reference's
vocabulary where a TPU equivalent exists, and each field documents the
mapping.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple


def _log():
    from .utils.log import get_logger

    return get_logger("config")


def _current_platform() -> str:
    """Live backend platform ("cpu"/"tpu"/...)."""
    import jax

    return jax.devices()[0].platform


def _current_device_str() -> str:
    import jax

    return str(jax.devices()[0])


@dataclasses.dataclass
class SessionConfig:
    """Session-wide planner/engine flags (the SQLConf analog)."""

    # rewrite enables (reference: per-transform enable flags)
    enable_rewrites: bool = True
    enable_topn_rewrite: bool = True  # Sort+Limit -> TopN
    enable_timeseries_rewrite: bool = True  # time-only groupby -> Timeseries
    enable_join_collapse: bool = True  # star-schema join elimination

    # approx-distinct mapping (reference: pushHLLTODruid / useApproxCountDistinct)
    approx_count_distinct_sketch: str = "hll"  # "hll" | "theta"
    hll_precision: int = 11
    theta_size: int = 4096
    # COUNT(DISTINCT x) handling: "approx" rewrites to a sketch (Druid
    # default); "exact" uses the exact distinct path; "error" rejects.
    count_distinct_mode: str = "approx"
    # APPROX_QUANTILE sample size K (quantilesDoublesSketch k analog):
    # rank error ~ O(sqrt(p(1-p)/K)), ~±1.5% at the median for 1024
    quantiles_k: int = 1024
    # When the planner cannot rewrite a query (unconforming join, an
    # expression no transform covers), interpret the logical plan over
    # decoded host frames instead of erroring — the reference's vanilla-
    # Spark fallback (SURVEY.md §3.2).  False surfaces RewriteError
    # (useful for asserting pushdown coverage).
    fallback_execution: bool = True
    # ceiling on the SUMMED base-table rows a host-fallback query may touch:
    # the fallback is single-threaded pandas with full materialization, and
    # silently grinding through an arbitrarily large input is worse than a
    # clear error telling the user they left the accelerated path.
    # 0 disables the guard.
    fallback_max_rows: int = 50_000_000
    # device-assist inside the fallback (Aggregate subtrees run on the
    # engine, only the aggregated frame is interpreted host-side) engages
    # above this input-row count.  Below it the host interpreter is
    # instant anyway AND float64-exact — rank/comparison windows over
    # f32-accumulated device sums could tie differently on tiny frames.
    device_assist_min_rows: int = 1 << 18
    # Assist decision constants (see api._run_fallback.device_subplan).
    # cost_per_row_interp: ONE vectorized pandas grouped-agg pass over the
    # subtree's base (~0.1 us/row measured on this container — NOT the
    # whole fallback query, which runs several passes).  A deliberate
    # under-estimate: assist engages only when the modelled engine side
    # wins 2x (never-slower bar).  cost_per_group_decode: host cost per
    # RESULT group the assisted path re-pays (dictionary decode + frame
    # build + downstream interpretation) — this is what makes
    # G ~ rows/4-shaped subtrees (TPC-H q18) a wash that assist must
    # decline, while G << rows shapes (q2's rank base) win 15-100x.
    # Both run on the HOST on every backend, so neither flips with the
    # device platform.
    cost_per_row_interp: float = 0.1
    cost_per_group_decode: float = 1.0
    # bypass the assist cost gate (row floor still applies): the bench's
    # crossover probe needs to MEASURE the losing regimes the gate exists
    # to avoid; not a user knob
    device_assist_force: bool = False

    # cost model (reference: DruidQueryCostModel constants via SQLConf).
    # Units are MICROSECONDS so the constants are physically measurable:
    # `plan/calibrate.py` measures them on the live backend and
    # `SessionConfig.load_calibrated()` picks up the saved values; the
    # defaults below are v5e-flavoured estimates used until calibration runs.
    dense_max_groups: int = 1 << 17  # dense one-hot vs scatter cutover
    onehot_vmem_budget_mb: int = 32
    # device VMEM capacity class, MiB: the budget kernel tile sets must
    # fit (double-buffered) — ~16 MiB/core on v5e-class parts.  The
    # calibrated files carry the authoritative per-platform figure as
    # `vmem_budget_bytes`; this default is the fallback graftlint's
    # resource-budget pass (GL12xx) and future tile autotuning read when
    # no calibration exists for the target platform
    vmem_budget_mb: int = 16
    # us per row per 128-wide group tile for the dense one-hot kernel (MXU)
    cost_per_row_dense: float = 1e-4
    # us per row for the scatter (segment-sum) kernel — serializes on TPU
    cost_per_row_scatter: float = 0.05
    # us per row for scatter at a LARGE group domain (state no longer fits
    # cache: random writes miss).  The model interpolates per-row scatter
    # cost log-linearly in G between (scatter_lo_groups, cost_per_row_
    # scatter) and (scatter_hi_groups, cost_per_row_scatter_hi) — measured
    # on CPU: 0.0015us/row at G=1K vs 0.0071us/row at G=2M, a 5x cliff the
    # flat model missed (it routed SSB q3_2 SF100 to scatter: 12.1s, losing
    # to pandas).  On TPU scatter serializes regardless, so the default is
    # flat until hardware calibration says otherwise.
    cost_per_row_scatter_hi: float = 0.05
    scatter_lo_groups: int = 1024
    scatter_hi_groups: int = 1 << 21
    # us per row for the sort-compaction (sparse) path
    cost_per_row_sparse: float = 5e-3
    # us per row for the FILTER-COMPACTION pass (mask -> survivor slots):
    # the linear scan sparse pays over ALL rows before sorting only the
    # survivors.  Estimate until calibrated; the dense/scatter/compact
    # ratio is what routes selective high-cardinality queries
    cost_per_row_compact: float = 2e-3
    # us per group of dense scatter state (alloc + merge traffic)
    cost_per_group_state: float = 2e-5
    # merge-collective throughput, bytes per us (ICI ring allreduce)
    collective_bytes_per_us: float = 40_000.0
    # cross-slice merge throughput, bytes per us (DCN allreduce between
    # slices).  ~25 GB/s per-host DCN vs ~100+ GB/s ICI: the gap is what
    # makes the hierarchical merge tree (slice-local psum first, then one
    # small state over DCN) win once state_bytes is large enough —
    # plan/cost.choose_merge_tree prices both trees with this constant
    dcn_bytes_per_us: float = 25_000.0
    # fixed overhead of one SPMD dispatch + multi-device host gather, us
    cost_dispatch_us: float = 300.0
    # host->device transfer bandwidth, bytes/s.  Default is PCIe-class;
    # calibration measures the real link (the constant that decides
    # whether shipping a fallback subtree's base to the device can ever
    # pay for itself)
    h2d_bytes_per_s: float = 1e10

    # result guards (reference: maxCardinality / maxResultCardinality)
    max_result_cardinality: int = 1 << 22
    # non-aggregate queries (reference: nonAggregateQueryHandling = push/scan)
    non_aggregate_query_handling: str = "scan"  # "scan" | "error"

    # distributed execution (reference: queryHistoricalServers,
    # numSegmentsPerHistoricalQuery -> mesh shape decisions).  With
    # prefer_distributed=True (default) the cost model picks the mesh
    # whenever the modelled distributed cost beats single-device cost.
    prefer_distributed: bool = True
    mesh_data_axis: Optional[int] = None
    mesh_groups_axis: int = 1

    # result-level cache (the Druid broker's result cache analog: repeated
    # dashboard queries skip execution entirely).  Entries key on query JSON
    # + datasource schema signature, so re-ingestion can never serve stale
    # rows.  0 disables.
    result_cache_entries: int = 64
    # delta-aware result-cache reuse (serve/result_cache.py, ISSUE 8): on
    # a streamed append the cache serves `(cached historical partial) ⊕
    # (fresh delta partials)` instead of invalidating outright — the
    # refresh scans ONLY the appended segments.  Requires the cached
    # entry's dictionaries to be unchanged (a dictionary extension remaps
    # code spaces and is a full miss).  False restores version-exact
    # hits only.
    result_cache_delta_reuse: bool = True

    # -- async serving core (serve/, ISSUE 8) -------------------------------
    # micro-batch query fusion: compatible concurrent queries (same
    # datasource + segment-set signature) queue for this many ms and
    # execute as ONE fused device program, amortizing the per-dispatch
    # round trip N ways.  0 disables (every query dispatches solo —
    # the right default for single-client sessions; the server/bench
    # enable it for concurrent dashboard traffic).
    fusion_window_ms: float = 0.0
    # ceiling on queries fused into one device program (compile time and
    # demux cost grow with the batch)
    fusion_max_batch: int = 16
    # priority lanes (serve/lanes.py): separate admission slot pools so
    # cheap dashboard queries (TopN/timeseries/small groupBys) are never
    # queued behind SF100-scale scans.  A query routes to the heavy lane
    # when its in-scope row count exceeds lane_heavy_rows (scans and
    # groupBys); TopN/timeseries/metadata queries stay interactive.
    lane_interactive_slots: int = 6
    lane_heavy_slots: int = 2
    lane_heavy_rows: int = 4 << 20

    # -- query-lifecycle resilience (resilience.py) -------------------------
    # wall-clock budget per query; 0 = unbounded.  The wire path's
    # Druid-native `context.timeout` (ms) overrides it per request.
    query_timeout_ms: int = 0
    # serving admission control: bounded slot pool + queue-wait timeout;
    # a full pool answers 503 + Retry-After instead of piling handler
    # threads behind a slow device
    max_concurrent_queries: int = 8
    admission_queue_timeout_ms: int = 2000
    # device circuit breaker: consecutive TRANSIENT failures before queries
    # route straight to the host fallback, and how long the breaker stays
    # open before a half-open probe may try the device again
    breaker_failure_threshold: int = 3
    breaker_cooldown_ms: int = 2000
    # transient-failure retry budget for one device execution (attempts
    # TOTAL, so 2 = one retry — the historical behavior) and the base
    # backoff between attempts (doubles per retry, clipped to the active
    # deadline's remaining budget)
    retry_max_attempts: int = 2
    retry_backoff_ms: float = 25.0
    # deadline-bounded PARTIAL answers (ISSUE 7): when a deadline expires
    # at an executor checkpoint, merge the per-segment partials
    # accumulated so far and return them stamped partial=True with a
    # coverage fraction, instead of erroring.  Every aggregate state in
    # the engine is mergeable, so "the rows seen so far" is a safe
    # answer (Partial Partial Aggregates).  False restores hard
    # DeadlineExceeded errors; the wire context key `partialResults`
    # overrides per request.
    partial_results: bool = True

    # -- real-time ingestion tier (ingest/) ---------------------------------
    # rows per published delta segment before an append batch splits; the
    # floor is catalog.segment.ROW_PAD (padding granularity)
    delta_seal_rows: int = 1 << 16
    # background compaction: sweep period and the delta-row backlog below
    # which a datasource is left alone (compacting single tiny deltas
    # would churn versions — and result caches — for nothing)
    compaction_interval_s: float = 5.0
    compaction_min_delta_rows: int = 1 << 15
    # rows per historical segment compaction emits
    compaction_rows_per_segment: int = 1 << 19
    # ingest admission: a SEPARATE small slot pool so streamed appends
    # (encode + possible dictionary-extension remap) can't starve query
    # slots, and a query burst can't starve ingest
    max_concurrent_ingests: int = 2
    ingest_queue_timeout_ms: int = 2000

    # -- durable storage tier (storage.py / ingest/wal.py, ISSUE 13) --------
    # root directory of the persistent tier: per-datasource append WALs +
    # versioned columnar snapshots.  None (default) keeps the catalog
    # purely in-process — nothing survives a restart, exactly the
    # pre-ISSUE-13 behavior.  When set, a context constructor RECOVERS:
    # snapshot mmap-load + WAL replay, zero re-ingest/re-encode.
    storage_dir: Optional[str] = None
    # fsync each WAL record before the publish/ack (the durability
    # contract).  False trades the acked-append-survives-crash guarantee
    # for append latency — tests and bulk loads only.
    storage_fsync: bool = True
    # background snapshot-flush sweep (ISSUE 14 satellite): every
    # `snapshot_flush_s` seconds a daemon thread flushes any datasource
    # whose published version moved past its on-disk snapshot, so dirty
    # delta segments reach disk without waiting for the next
    # registration or compaction (a restart then mmaps instead of
    # replaying them from the WAL).  0 (default) disables the timer;
    # appends stay durable either way via the WAL.
    snapshot_flush_s: float = 0.0

    # -- cluster tier (cluster/, ISSUE 16) ----------------------------------
    # replicas per segment in the broker's assignment map (rendezvous
    # hashing over historical node ids); clamped to the live node count
    cluster_replication: int = 2
    # per-replica RPC budget: one scatter attempt must answer within
    # this or the broker fails over to the next replica in the chain
    cluster_rpc_timeout_ms: float = 5000.0
    # extra attempts across the replica chain after the first failure
    # (the chain is bounded by replication anyway; this caps re-walks)
    cluster_rpc_retries: int = 1
    # tail-latency hedging: if the primary replica hasn't answered
    # within this, the broker issues the same fetch to the next replica
    # and takes whichever returns first.  0 disables hedging.
    cluster_hedge_ms: float = 0.0
    # per-historical circuit breaker (generalizes the device/mesh
    # breakers): consecutive scatter failures to one node before its
    # breaker opens, and how long it cools before a probe
    cluster_breaker_failures: int = 3
    cluster_breaker_cooldown_ms: float = 2000.0
    # federated observability scrape (ISSUE 19): per-node budget for the
    # broker's /status/metrics?cluster=1 and /status/profile?cluster=1
    # fan-out — a node slower than this is stamped stale for the scrape
    cluster_scrape_timeout_ms: float = 2000.0

    # -- observability (obs/) -----------------------------------------------
    # slow-query log: a finished query whose span-tree total exceeds this
    # logs the rendered tree at WARNING through utils/log.py; 0 disables
    slow_query_ms: float = 0.0
    # finished span trees retained for GET /druid/v2/trace/{query_id}
    # (FIFO eviction past the capacity)
    trace_ring_capacity: int = 64
    # emit-only OTLP export (ROADMAP obs follow-up (d)): when set, every
    # finished trace appends one OTLP/JSON ResourceSpans line to this
    # file (obs/otlp.py) — no collector or network dependency; None
    # disables
    otlp_export_path: Optional[str] = None
    # self-hosted telemetry (obs/telemetry.py, ISSUE 19): when > 0, a
    # daemon sampler flushes the metrics registry into the `__sys`
    # datasource every this-many seconds (ingest/WAL tier, rollup at
    # `second` granularity) so QPS/p99/breaker history is SQL-queryable.
    # 0 (default) never registers `__sys` and starts no thread.
    sys_sampler_s: float = 0.0
    # per-tick series cap for the `__sys` sampler (cardinality guard)
    sys_sampler_max_series: int = 512
    # age-based `__sys` retention: a second-granularity telemetry
    # segment whose NEWEST row is older than this many seconds is
    # dropped by the background compaction sweep (whole segments only —
    # never a partial rewrite), so self-hosted telemetry is a ring, not
    # a leak.  0 (default) retains everything.
    sys_retention_s: float = 0.0

    # -- performance attribution (obs/prof.py, ISSUE 9) ---------------------
    # fraction of queries sampled for HONEST device timing: a sampled
    # query pays sync points (block_until_ready) at its dispatch/fetch
    # sites so the segment_dispatch/device_fetch spans split into
    # enqueue vs device-complete time.  0 (default) adds ZERO syncs —
    # the dispatch overlap the executors engineered is never destroyed
    # by default; 1.0 profiles every query (bench receipt reps).
    prof_sample_rate: float = 0.0
    # GET /status/profile rolling window + top-K size
    profile_window_s: float = 300.0
    profile_top_k: int = 10
    # per-lane latency targets the profiler burns SLO against: the
    # fraction of a lane's queries whose wall exceeded its target is
    # that lane's burn rate.  0 disables the burn computation for a lane.
    lane_interactive_slo_ms: float = 250.0
    lane_heavy_slo_ms: float = 30_000.0
    # -- overlapped h2d transfer pipeline (exec/pipeline.py, ISSUE 10) ------
    # double-buffered segment streaming: the engine issues async
    # device placement of the NEXT dispatch batches' cold columns while
    # the current batch's program runs, and dispatches already-resident
    # batches first so cold segments stream behind live compute instead
    # of in front of it.  Results are byte-identical either way (the
    # partial-state fold order is pinned); False restores fully
    # synchronous per-batch transfers.
    transfer_pipeline: bool = True
    # prefetch lookahead, in dispatch batches
    prefetch_depth: int = 2
    # byte cap (MiB) for SPECULATIVE prefetch of next-interval segments
    # OUTSIDE the query's pruned scope (a dashboard scanning [t0, t1)
    # usually asks for the adjacent interval next).  0 disables
    # speculation; in-scope prefetch is unaffected.
    prefetch_speculative_mb: int = 0
    # -- one-dispatch arena execution (exec/arena.py, ISSUE 14) -------------
    # segment-stacked resident arena: in-scope segments of equal padded
    # shape stack into one device-resident [B, R] layout and the whole
    # scope lowers as ONE lax.scan program (partial fold inside the trace
    # in canonical batch order, donated fold-state carry, one fetch) —
    # dispatches-per-query drop from O(segments) to O(1).  Results are
    # byte-identical to the per-batch dispatch loop (the scan replicates
    # the exact f32 fold association); scopes the arena cannot host
    # (sketch aggs, non-uniform segment shapes, sparse/adaptive routes)
    # fall back to the loop path per query.  False disables globally.
    arena_execution: bool = True

    # adaptive micro-batch fusion window (ROADMAP 1(b)): when True the
    # scheduler arms the window from the observed arrival rate — no wait
    # on an idle queue, up to fusion_window_max_ms under bursts — and
    # records the decision as a `fusion_window` span event.  False keeps
    # the static fusion_window_ms.
    fusion_adaptive_window: bool = False
    # burst ceiling for the adaptive window; 0 = 4x fusion_window_ms
    fusion_window_max_ms: float = 0.0

    # provenance of the cost constants (set by load_calibrated): {path,
    # device, partial, applied, mismatch?} or None when never loaded from
    # a file — artifacts record it so "which platform routed this" is
    # always answerable (VERDICT r4 weak #5)
    calibration_meta: Optional[dict] = None

    @classmethod
    def load_calibrated(
        cls,
        path: Optional[str] = None,
        strict_device: bool = False,
        root: Optional[str] = None,
    ) -> "SessionConfig":
        """SessionConfig with measured cost constants, when a calibration
        file (plan/calibrate.py) exists AND was measured on the current
        backend device; platform-profile defaults otherwise.

        The stale-device check matters: constants measured on a TPU applied
        to the CPU backend (or vice versa) route kernels pathologically —
        the dense/scatter ratio inverts between the two backends.  With
        `strict_device=True` a mismatched file RAISES instead of warning
        (bench.py uses it so an artifact can never quietly carry
        wrong-platform routing; VERDICT r4 #8).

        The returned config carries `calibration_meta` — {path, device,
        partial, applied} — so artifacts can record where their cost
        constants came from."""
        import json
        import os

        cfg = cls()
        # `root` overrides the repo-root discovery (tests point it at a
        # tmp dir so the sidecar fallback is pinned without touching the
        # real calibration files)
        if root is None:
            root = os.path.dirname(
                os.path.dirname(os.path.abspath(__file__))
            )
        p = path or os.path.join(root, "calibration.json")

        def _read(fp):
            try:
                with open(fp) as f:
                    d = json.load(f)
            except (OSError, ValueError):
                return None
            return d if isinstance(d, dict) else None

        data = None
        primary_unreadable = False
        if os.path.exists(p):
            data = _read(p)
            primary_unreadable = data is None
        # A CPU bench run and a TPU window alternate on this host, each
        # overwriting calibration.json; plan/calibrate.py therefore also
        # saves calibration.<platform>.json (plan.calibrate.sidecar_path
        # owns the naming).  Whenever the primary file cannot serve this
        # backend — measured elsewhere, unreadable, or missing — prefer
        # the platform-matching sidecar over falling all the way back to
        # profile guesses (the round-5 TPU constants exist precisely so a
        # later CPU run cannot erase them).
        if path is None:
            cur = _current_device_str()
            if data is None or data.get("device") not in (None, cur):
                from .plan.calibrate import sidecar_path

                alt = sidecar_path(_current_platform(), root)
                alt_data = _read(alt) if os.path.exists(alt) else None
                if alt_data is not None and alt_data.get("device") == cur:
                    p, data, primary_unreadable = alt, alt_data, False
        if primary_unreadable:
            # only warn once the sidecar fallback ALSO failed — an
            # operator reading "using the platform cost profile" must be
            # able to trust that profile guesses are really in effect
            _log().warning(
                "ignoring unreadable calibration file %s; using the "
                "platform cost profile", p,
            )
        if data is not None and data.get("device") not in (
            None,
            _current_device_str(),
        ):
            if strict_device:
                raise RuntimeError(
                    f"calibration file {p} was measured on "
                    f"{data.get('device')} but the execution backend is "
                    f"{_current_device_str()}; rerun plan/calibrate.py on "
                    "this backend (strict_device=True refuses the "
                    "platform-profile fallback)"
                )
            _log().warning(
                "ignoring calibration file %s measured on %s (current "
                "backend device is %s); using the platform cost profile — "
                "rerun plan/calibrate.py on this backend",
                p, data.get("device"), _current_device_str(),
            )
            cfg.calibration_meta = {
                "path": p,
                "device": data.get("device"),
                "partial": data.get("partial"),
                "applied": False,
                "mismatch": True,
            }
            data = None  # measured on a different backend: do not apply
        if data is not None:
            # platform profile FIRST, measured keys on top: a PARTIAL
            # calibration file (budget-clipped sweep) must fall back to
            # platform-correct values for its missing keys, not the class's
            # v5e-flavoured defaults.  Round 3's SF100 q3_2 regression came
            # from exactly this mix: measured CPU scatter cost + v5e
            # cost_per_group_state routed a 504K-group query to scatter.
            cfg.apply_platform_profile()
            for k in (
                "cost_per_row_dense",
                "cost_per_row_scatter",
                "cost_per_row_scatter_hi",
                "cost_per_row_sparse",
                "cost_per_row_compact",
                "cost_per_group_state",
                "collective_bytes_per_us",
                "dcn_bytes_per_us",
                "cost_dispatch_us",
                "h2d_bytes_per_s",
            ):
                if k in data and data[k] is not None and data[k] > 0:
                    setattr(cfg, k, float(data[k]))
            for k in ("scatter_lo_groups", "scatter_hi_groups"):
                if k in data and data[k] is not None and data[k] > 0:
                    setattr(cfg, k, int(data[k]))
            vb = data.get("vmem_budget_bytes")
            if vb is not None and vb > 0:
                cfg.vmem_budget_mb = max(1, int(vb) >> 20)
            cfg.calibration_meta = {
                "path": p,
                "device": data.get("device"),
                "partial": data.get("partial"),
                "applied": True,
            }
            return cfg
        return cfg.apply_platform_profile()

    def apply_platform_profile(self) -> "SessionConfig":
        """Overwrite (in place) the v5e-flavoured default cost constants with
        a profile matching the live backend when that backend is CPU.

        The class defaults model an MXU: dense one-hot nearly free per lane
        tile, scatter expensive (serialized updates).  XLA:CPU is the
        opposite — segment_sum streams at memory bandwidth for any G while
        the one-hot materializes B x G blocks (measured: scatter ~flat
        450 Mrows/s from G=1 to G=8008; dense 42 Mrows/s at G=8, 7 Mrows/s
        at G=64).  Without this, a fresh uncalibrated CPU session routes a
        G=8008 GroupBy to dense: ~65 s instead of ~0.3 s at SF1.  Values
        are a committed CPU calibration snapshot (plan/calibrate.py on
        TFRT_CPU; see the round-3 session notes) — a real calibration run
        still refines them."""
        if _current_platform() != "cpu":
            return self
        self.cost_per_row_dense = 0.58
        self.cost_per_row_scatter = 0.0012
        # measured on this container (8M rows, segment_sum): 0.00145us/row
        # at G=1024 rising to 0.00707us/row at G=2M as the state outgrows
        # cache — the G-dependence that routes huge-domain GroupBys off
        # raw scatter
        self.cost_per_row_scatter_hi = 0.0071
        self.scatter_lo_groups = 1024
        self.scatter_hi_groups = 1 << 21
        self.cost_per_row_sparse = 0.49
        self.cost_per_row_compact = 0.0065
        self.cost_per_group_state = 0.0023
        # "collective" on a CPU mesh is shared-memory copies and a local
        # dispatch is function-call cheap — the ICI/RPC-flavoured defaults
        # would misprice the distributed-vs-local choice
        self.collective_bytes_per_us = 10_000.0
        # a virtual slice boundary on CPU is still shared memory, but the
        # modelled DCN gap must survive so the merge-tree choice exercises
        # the same decision the pod makes
        self.dcn_bytes_per_us = 2_500.0
        self.cost_dispatch_us = 100.0
        # "h2d" on CPU is a memcpy into the runtime's buffer
        self.h2d_bytes_per_s = 2e10
        # small-frame floor only: the COST MODEL now makes the real
        # assist decision per subtree (api._run_fallback compares the
        # modelled engine kernel cost at the subtree's G against
        # rows x cost_per_row_interp).  The r4 blunt 8.4M-row threshold
        # blocked q2-class subtrees the engine wins 15-100x (tiny G over
        # a big base) to protect against q18-class losses (G ~ rows/4);
        # the model separates the two shapes directly.
        self.device_assist_min_rows = 1 << 18
        return self


@dataclasses.dataclass
class TableOptions:
    """Per-table registration options (the OPTIONS(...) map analog).

    Reference option -> field mapping:
      timeDimensionColumn      -> time_column
      druidDatasource          -> (the registered name)
      columnMapping            -> column_mapping
      functionalDependencies   -> functional_dependencies (catalog/star.py)
      starSchema               -> star_schema (catalog/star.py)
      rows per segment/historical -> rows_per_segment
      loadMetadataFromAllSegments -> eager_stats
    """

    time_column: Optional[str] = None
    dimensions: Tuple[str, ...] = ()
    metrics: Tuple[str, ...] = ()
    column_mapping: Optional[dict] = None  # source col -> datasource col
    rows_per_segment: int = 1 << 22
    eager_stats: bool = True
    star_schema: Optional[object] = None  # catalog.star.StarSchemaInfo
    functional_dependencies: Tuple = ()


DEFAULT_SESSION = SessionConfig()
