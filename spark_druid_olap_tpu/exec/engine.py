"""Single-device query engine: QuerySpec × DataSource -> result table.

Reference parity: this layer replaces the external Druid cluster.  In
spark-druid-olap, `DruidRDD.compute` POSTs the query JSON to a broker /
historical and streams result rows back (SURVEY.md §3.3 `[U]`); the actual
aggregation happens inside Druid.  Here `Engine.execute` runs the same query
spec locally: segment columns (dictionary codes + metrics) are moved to TPU
HBM once and cached (the analog of Druid's segment residency / page cache),
the filter+aggregate runs as fused XLA (ops/groupby.py), and only the tiny
[G, M] aggregate state returns to host for finalization (decode group ids,
post-aggregations, having, sort/limit — the work Druid's broker does after
its scatter-gather merge).

Distributed execution (the broker scatter-gather analog over ICI) lives in
parallel/distributed.py.  Query lowering is exec/lowering.py and host-side
result finalization is exec/finalize.py — both shared by the local,
distributed, and streaming executors and re-exported here.
"""

from __future__ import annotations

import bisect
import contextlib
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..catalog.segment import DataSource, Segment
from ..models import aggregations as A
from ..models import filters as F
from ..models import query as Q
from ..ops import hll as hll_ops
from ..ops import quantiles as quantiles_ops
from ..ops import theta as theta_ops
from ..ops.filters import compile_filter, numeric_dict_code_bounds
from ..ops.groupby import partial_aggregate
from ..plan.cost import concrete_kernel, lowered_tier_takes, rollup_takes

# Lowering + finalization were split out of this module (VERDICT r1 weak #8);
# re-exported here because the distributed/streaming executors and external
# users import them from exec.engine.
from .lowering import (  # noqa: F401
    GroupByLowering,
    LoweredAggs,
    ResolvedDim,
    _agg_columns,
    _decoded_expr_fn,
    _filter_columns,
    _query_key,
    groupby_with_time_granularity,
    lower_groupby,
    memo_key,
    schema_signature,
    timeseries_to_groupby,
    topn_to_groupby,
)
from .finalize import (  # noqa: F401
    _eval_having,
    apply_limit_spec,
    eval_post_agg,
    finalize_groupby,
    finalize_timeseries,
    finalize_topn,
    rollup_states,
)
from .state import (
    PartialState,
    estimable,
    merge,
    sketch_nbytes,
    to_host,
    with_histograms,
)
from ..obs import (
    SCOPE_CARRY_MERGE,
    SCOPE_SKETCH_FOLD,
    SPAN_DEVICE_FETCH,
    SPAN_FINALIZE,
    SPAN_H2D,
    SPAN_LOWER,
    SPAN_PROGRAM_LOOKUP,
    SPAN_ROUTE,
    SPAN_SCOPE,
    SPAN_SEGMENT_DISPATCH,
    current_query_id,
    current_trace,
    prof,
    record_query_metrics,
    span,
    span_around,
    device_scope,
)
from ..resilience import checkpoint, checkpoint_partial, current_partial, fire
from ..utils.log import get_logger
from .adaptive_exec import AdaptiveDomainMixin
from .sparse_exec import SparseExecMixin

log = get_logger("exec.engine")


class _Rollup(NamedTuple):
    """The rollup form of a grouping-set request (`Engine.execute_rollup`):
    `union` and each of `sets` are tuples of the request's dimension
    indices; `queries` holds each set's query over the union's lowering."""

    union: Tuple[int, ...]
    sets: Tuple[Tuple[int, ...], ...]
    queries: Tuple[Q.GroupByQuery, ...]


def _bytes_scanned(segs, columns) -> int:
    """Bytes of segment data a query's kernel reads: needed columns plus
    the validity mask (and time when fetched) over REAL rows — the
    roofline numerator (QueryMetrics.bytes_scanned)."""
    total = 0
    # graftlint: disable=checkpoint-coverage -- O(segments) host metadata sum, no dispatch/decode per iteration
    for s in segs:
        row_bytes = 1  # valid mask
        for n in columns:
            try:
                row_bytes += s.column(n).dtype.itemsize
            except KeyError:
                pass  # virtual columns are computed, not read
        total += row_bytes * s.num_rows
    return total


def _row_counts(segs) -> Tuple[int, int]:
    """(total real rows, delta-segment rows) of a segment list — the
    partial-result coverage accounting unit.  Delta rows are reported
    separately so a best-effort answer can say how much of it came from
    freshly-appended data vs historicals (and the ingest hammer can
    assert deltas are never double-counted)."""
    from ..catalog.segment import DeltaSegment

    rows = delta = 0
    # graftlint: disable=checkpoint-coverage -- O(segments) host metadata sum, no dispatch/decode per iteration
    for s in segs:
        rows += s.num_rows
        if isinstance(s, DeltaSegment):
            delta += s.num_rows
    return rows, delta


def _outside_codes(dim, codes):
    """Zone-map test of a Selector / In conjunct in code space: a segment
    whose [min, max] of `dim` holds none of the sorted `codes` cannot match."""

    def excluded(st) -> bool:
        b = st.get(dim)
        if b is None:
            return False
        i = bisect.bisect_left(codes, b[0])
        return i == len(codes) or codes[i] > b[1]

    return excluded


def _outside_bounds(dim, lo, lo_strict, hi, hi_strict):
    """The same for a numeric Bound, either end possibly None: two floats
    over a bare metric column, or two codes, their strictness already
    folded in, over a numeric dictionary."""

    def excluded(st) -> bool:
        b = st.get(dim)
        if b is None:
            return False
        if lo is not None and (b[1] < lo or (lo_strict and b[1] <= lo)):
            return True
        return hi is not None and (
            b[0] > hi or (hi_strict and b[0] >= hi)
        )

    return excluded


def _code_space_test(c, ds: DataSource, vcol_names):
    """One filter node translated ONCE, before the segment loop, into what
    the loop compares each segment's zone map with: None (excludes no
    segment: a shape the pruner leaves to the row kernel, a null literal,
    an unknown or virtual-column-shadowed dimension, an unparsable bound),
    True (excludes every segment: its literals are in no dictionary) or a
    test `stats -> excluded`."""
    if getattr(c, "dimension", None) in vcol_names:
        return None
    if isinstance(c, (F.Or, F.And)):
        parts = [_code_space_test(x, ds, vcol_names) for x in c.fields]
        if isinstance(c, F.Or):
            # a disjunction can only match if SOME disjunct can
            if not parts or any(p is None for p in parts):
                return None
            tests = [p for p in parts if p is not True]
            if not tests:
                return True
            return lambda st: all(t(st) for t in tests)
        if any(p is True for p in parts):
            return True
        tests = [p for p in parts if p is not None]
        if not tests:
            return None
        return lambda st: any(t(st) for t in tests)
    if isinstance(c, (F.Selector, F.InFilter)):
        values = (c.value,) if isinstance(c, F.Selector) else c.values
        d = ds.dicts.get(c.dimension)
        if d is None or any(v is None for v in values):
            return None  # null stats / null membership aren't tracked
        codes = sorted(
            x for x in (d.code_of(v) for v in values) if x is not None
        )
        if not codes:
            return True  # none of the values exist in the datasource
        return _outside_codes(c.dimension, codes)
    if isinstance(c, F.Bound) and c.ordering == "numeric":
        d = ds.dicts.get(c.dimension)
        if d is not None:
            # numeric dictionary: translate to code space with the SAME
            # helper the kernel compile uses (ops/filters.py), then
            # compare against the code-space zone map
            nv = d.numeric_values
            if nv is None:
                return None
            cb = numeric_dict_code_bounds(c, nv)
            if cb is None:
                return None
            return _outside_bounds(c.dimension, cb[0], False, cb[1], False)
        try:
            lo = None if c.lower is None else float(c.lower)
        except ValueError:
            return None
        try:
            hi = None if c.upper is None else float(c.upper)
        except ValueError:
            hi = None  # unparsable: the lower end prunes alone
        return _outside_bounds(
            c.dimension, lo, c.lower_strict, hi, c.upper_strict
        )
    return None


def _prune_by_stats(segs, filt, ds: DataSource, vcol_names=frozenset()):
    """Zone-map pruning on a CONSERVATIVE filter subset: top-level AND
    conjuncts that are Selector/In over dictionary columns (matched in code
    space — dictionaries are datasource-global, so codes compare across
    segments) or numeric Bounds over metric columns, and ORs / nested
    ANDs of those.  Everything else (NOT, expressions, string bounds) is
    left to the row kernel — pruning may only ever REMOVE provably-empty
    segments.

    Each conjunct's literals are translated to code space once, here
    (`_code_space_test`); the segment loop compares zone maps with plain
    integers (ISSUE 38: translated per segment, a walk of 115 segments
    under two `IN` lists and two bounds was 3.1 ms of `searchsorted`).

    `vcol_names`: virtual-column names defined by the query.  A filter on
    a virtual column that SHADOWS a physical column evaluates against the
    virtual values at execution, so pruning it against the physical
    column's stats would silently drop live segments — skip those."""

    def _conjuncts(f):
        # the planner builds Ands pairwise (And(And(a, b), c)): flatten
        # recursively or buried conjuncts never get a pruning look
        if isinstance(f, F.And):
            out = []
            for x in f.fields:
                out.extend(_conjuncts(x))
            return out
        return [f]

    tests = [
        t
        for t in (
            _code_space_test(c, ds, vcol_names) for c in _conjuncts(filt)
        )
        if t is not None
    ]
    if any(t is True for t in tests):
        out = []
    elif tests:
        out = []
        # graftlint: disable=checkpoint-coverage -- zone-map pruning is O(segments) metadata comparisons, no per-iteration work
        for s in segs:
            st = s.stats or {}
            for t in tests:
                if t(st):
                    break
            else:
                out.append(s)
    else:
        out = segs
    if len(out) < len(segs):
        log.info(
            "zone maps pruned %d of %d segments", len(segs) - len(out),
            len(segs),
        )
    return out


def _walk_segments(intervals, filt, vcol_names, ds: DataSource):
    """One walk of the table's segments: those an interval overlaps and
    no zone map excludes, in the table's order."""
    segs = list(ds.segments)
    if intervals:
        out = []
        # graftlint: disable=checkpoint-coverage -- interval pruning is O(segments) metadata arithmetic, no per-iteration work
        for s in segs:
            if s.interval is None:
                out.append(s)
                continue
            lo, hi = s.interval
            if any(a <= hi and lo < b for a, b in intervals):
                out.append(s)
        segs = out
    if filt is not None and segs:
        segs = _prune_by_stats(segs, filt, ds, vcol_names)
    return segs


def segments_in_scope(q, ds: DataSource) -> List[Segment]:
    """Segment pruning: by time interval (the analog of the reference
    narrowing the Druid query interval from time predicates, §3.2) and
    by per-segment zone maps (SURVEY.md §2 metadata "stats" row) —
    a top-level filter conjunct whose values provably fall outside a
    segment's [min, max] excludes that segment without a dispatch.
    Module-level: the distributed engine shares this exact policy for its
    metrics scope (its shards span the full set; the row mask excludes).

    A served request resolves its scope ONCE (ISSUE 38): the walk is one
    `scope` span, and the request's trace holds what it found
    (`QueryTrace.scopes`) for whoever asks next — the lane classifier
    asks first, then the engine, on a differently built query of the
    same filter.  The held scope answers only for the `DataSource`
    object it walked (a streamed append publishes a new one, which is
    walked anew) and for equal `(intervals, filter, virtual-column
    names)`, all this function reads of `q`; an ask it answers opens no
    span and counts itself on the walk's `asks`.  It dies with the
    request; outside a trace (library calls, the broker, ingest) every
    call walks.  Each call returns a list of its own."""
    filt = getattr(q, "filter", None)
    vcols = frozenset(
        v.name for v in getattr(q, "virtual_columns", ()) or ()
    )
    tr = current_trace()
    if tr is None:
        return _walk_segments(q.intervals, filt, vcols, ds)
    key = (q.intervals, filt, vcols)
    for held_ds, held_key, kept, walk in tr.scopes:
        if held_ds is ds and held_key == key:
            walk.attrs["asks"] += 1
            return list(kept)
    with span(SPAN_SCOPE, segments=len(ds.segments), asks=1) as sp:
        segs = _walk_segments(q.intervals, filt, vcols, ds)
        sp.attrs["kept"] = len(segs)
        tr.scopes.append((ds, key, tuple(segs), sp))
    return segs


# Above this many in-scope segments a query stops unrolling them into one
# fused program (compile time grows linearly with the unroll) and falls back
# to the per-segment dispatch loop.  Below it, the whole query is ONE device
# dispatch + ONE host fetch — the difference between ~4 and ~N+2 round trips.
MULTI_SEGMENT_UNROLL_MAX = 32

# On the CPU backend the fused mega-program is a double loss: XLA schedules
# the long unrolled scatter/one-hot chain ~2x slower than the same work as
# small programs (measured at SF2: 57 -> 111 Mrows/s on a G=504k scatter by
# capping the unroll at 2), and compile time is brutal (~60 s for a
# 12-segment unroll of an 8k-group scatter vs ~2 s for the pair program).
# Local dispatch costs microseconds, so small batches only forgo RPC
# amortization that CPU never needed; the cross-batch partial merge in
# _partials_for_query is unchanged.
CPU_SEGMENT_UNROLL_MAX = 2

# A theta state is K hashes a group.  A batch program folds its segments'
# rows in one sort by (group, hash) (`_batch_partials`), and batches merge
# by sorting [G, 2K] each.  A scope with a theta aggregation unrolls at
# most this many segments: each batch adds a launch and a 12.7 ms merge at
# G = 288 beside the one fold of its 8 segments' rows.
THETA_SEGMENT_UNROLL_MAX = 8


def _platform_unroll_max(la=None) -> int:
    """Segments a batch program unrolls: `la` (the query's lowered
    aggregations) bounds a theta scope further."""
    from ..config import _current_platform

    if _current_platform() == "cpu":
        return CPU_SEGMENT_UNROLL_MAX
    if la is not None and any(
        isinstance(a, A.ThetaSketch) for a in la.sketch_aggs
    ):
        return THETA_SEGMENT_UNROLL_MAX
    return MULTI_SEGMENT_UNROLL_MAX


def _segment_partials(
    lowering: "GroupByLowering", strategy: str, cols, memo=None, share=None
):
    """Partial-aggregate one segment's columns under one query lowering —
    the traced body of `_batch_partials` (the single-query fused program
    and the multi-query fused-batch program, serve/ micro-batch fusion)
    and of the arenas: virtual columns, row pipeline, dense partial
    aggregation, sketch partials; a theta aggregation's are its rows
    (hashes, group ids, mask), folded once a batch.

    `memo`/`share` power the fused-batch common-subexpression dedup
    (serve/fusion.shared_row_plan, ROADMAP 1(a)): `share` is
    `(mask_group, gid_group, segment_index)` and `memo` a per-trace dict
    — members whose filter/dimension sub-lowerings are identical reuse
    ONE traced mask / gid per segment instead of re-tracing them.

    This function runs DURING jit tracing: the sketch-op modules it
    needs are imported at engine module scope (below), never here — a
    first import inside a trace would create their module-level jnp
    constants (theta.SENTINEL) as tracers that leak into later traces."""
    la, G = lowering.la, lowering.num_groups
    cols = lowering.add_virtual(dict(cols))  # sketches read virtuals
    mask0 = gid0 = None
    if memo is not None and share is not None:
        mg, gg, j = share
        mask0 = memo.get(("mask", mg, j))
        gid0 = memo.get(("gid", gg, j))
    gid, mask, sv, mmv, mmm = lowering.row_arrays(
        cols, mask=mask0, gid=gid0, strategy=strategy
    )
    if memo is not None and share is not None:
        memo.setdefault(("mask", mg, j), mask)
        memo.setdefault(("gid", gg, j), gid)
    s, mn, mx = partial_aggregate(
        gid, mask, sv, mmv, mmm,
        num_groups=G,
        num_min=len(la.min_names),
        num_max=len(la.max_names),
        strategy=strategy,
    )
    sk = {}
    with device_scope(SCOPE_SKETCH_FOLD):
        for agg in la.sketch_aggs:
            # per-agg FILTER mask (SQL `agg(...) FILTER (WHERE ...)`)
            # composes with the row mask — sketches must honor it the
            # same way sum/min/max columns do
            mfn = la.mask_fns.get(agg.name)
            amask = mask & mfn(cols) if mfn is not None else mask
            if isinstance(agg, (A.HyperUnique, A.CardinalityAgg)):
                sk[agg.name] = hll_ops.partial_hll(agg, cols, gid, amask, G)
            elif isinstance(agg, A.QuantilesSketch):
                sk[agg.name] = quantiles_ops.partial_quantiles(
                    agg, cols, gid, amask, G
                )
            else:
                # the rows, folded once a batch (`_batch_partials`)
                sk[agg.name] = (theta_ops.theta_hash(agg, cols), gid, amask)
    return PartialState(s, mn, mx, sk)


def _theta_aggs(la) -> list:
    return [a for a in la.sketch_aggs if isinstance(a, A.ThetaSketch)]


def _theta_attrs(n_theta: int, cols_list) -> dict:
    """A batch launch's span attr `theta_rows`: the rows its one theta
    fold sorts (every segment's, padding included); none without theta."""
    if not n_theta:
        return {}
    return {"theta_rows": sum(int(c["__valid"].shape[0]) for c in cols_list)}


def _batch_partials(lowering, strategy, cols_seq, memo=None, shares=None):
    """A batch program's partial state over its segments' columns: sums
    add, mins and maxs fold, HLL and quantiles states merge segment by
    segment; each theta aggregation folds ONCE over the rows of all the
    batch's segments (`theta.batch_theta`), which equals the per-segment
    states merged pairwise.  `shares[i]` is segment i's `share` of
    `_segment_partials` (the fused batch's common subexpressions).  A query
    without a theta aggregation traces what the per-segment loop did."""
    la = lowering.la
    thetas = _theta_aggs(la)
    state = None
    rows: Dict[str, list] = {}  # a theta aggregation's rows a segment
    for i, cols in enumerate(cols_seq):
        seg = _segment_partials(
            lowering, strategy, cols, memo=memo,
            share=None if shares is None else shares[i],
        )
        for agg in thetas:
            rows.setdefault(agg.name, []).append(seg.sketches.pop(agg.name))
        with device_scope(SCOPE_CARRY_MERGE):
            state = merge(la, state, seg)
    if rows:  # none where a fused member has no segment in the batch
        sketches = dict(state.sketches)
        with device_scope(SCOPE_SKETCH_FOLD):
            for agg in thetas:
                hs, gids, masks = zip(*rows[agg.name])
                sketches[agg.name] = theta_ops.batch_theta(
                    hs, gids, masks, lowering.num_groups, agg.size
                )
        state = state._replace(sketches=sketches)
    return state


def _default_device_budget() -> int:
    """Residency byte budget when the caller does not pin one.

    TPU/GPU: 3/4 of the HBM the device itself reports, leaving the rest
    for kernel workspace and merge states.  A device that reports no
    memory stats is an error, not a guess: a budget sized for a chip
    this is not turns graceful eviction into a hard OOM.
    CPU backend: "device" buffers ARE host RAM, so evicting to re-copy is
    pure waste — budget half the machine's memory instead (SF100's 51 GB
    of encoded segments stays resident across queries on a 125 GB host
    rather than re-streaming ~15 GB per query through a 4 GiB window)."""
    import os

    import jax

    dev = jax.devices()[0]
    if dev.platform != "cpu":
        return int(dev.memory_stats()["bytes_limit"]) * 3 // 4
    pages = os.sysconf("SC_PHYS_PAGES")
    page = os.sysconf("SC_PAGE_SIZE")
    return max(4 << 30, int(pages * page) // 2)


def groupby_family(q: Q.QuerySpec, ds: DataSource):
    """Normalize a GroupBy-family query to its inner GroupBy plus the
    per-type result shaper: the one mapping execute, execute_progressive,
    execute_fused and the state-capture paths of both engines share.
    (None, None) for any other query type."""
    if isinstance(q, Q.TimeseriesQuery):
        return (
            timeseries_to_groupby(q),
            lambda df: finalize_timeseries(df, q, ds),
        )
    if isinstance(q, Q.TopNQuery):
        return topn_to_groupby(q), lambda df: finalize_topn(df, q)
    if isinstance(q, Q.GroupByQuery):
        return q, lambda df: df
    return None, None


class HostStateSurface:
    """The host partial-state surface both engines (this one and
    `parallel.distributed.DistributedEngine`) serve to the result cache
    and the cluster broker: state capture, ⊕ and finalize of host
    `PartialState`s.  Either engine's state merges with the other's."""

    @contextlib.contextmanager
    def state_capture(self):
        """Capture the merged HOST partial state of the next execution on
        this thread (the dense path, on the mesh the SPMD arena, stashes
        it just before finalize).  Yields a dict whose "state" key holds
        the capture — None when the execution took a path with no dense
        state (sparse/adaptive/fallback, the mesh's legacy paths) or was
        deadline-truncated (a partial state must never seed the
        delta-aware result cache)."""
        holder = {"state": None}
        self._m_local.capture = holder
        try:
            yield holder
        finally:
            self._m_local.capture = None

    def _family_lowering(self, q: Q.QuerySpec, ds: DataSource):
        """(inner GroupBy, result shaper, lowering) of a GroupBy-family
        query; ValueError for any other type."""
        inner, shape = groupby_family(q, ds)
        if inner is None:
            raise ValueError(f"{type(q).__name__} has no partial state")
        inner = groupby_with_time_granularity(inner)
        return inner, shape, self._lowering_for(inner, ds)

    def merge_groupby_states(self, q: Q.QuerySpec, ds: DataSource, a, b):
        """a ⊕ b, two host partial states of the SAME query over the same
        dictionary domain.  Raises ValueError on a shape mismatch (a
        dictionary change reshapes G — callers treat that as a cache
        miss)."""
        if a.sums.shape != b.sums.shape:
            raise ValueError(
                f"partial-state shape mismatch {a.sums.shape} vs "
                f"{b.sums.shape} (dictionary domain changed)"
            )
        _, _, lowering = self._family_lowering(q, ds)
        return merge(lowering.la, a, b)

    def finalize_groupby_state(self, q: Q.QuerySpec, ds: DataSource, state):
        """Host partial state -> the query's final result frame (the same
        finalize the live execution path runs)."""
        inner, shape, lowering = self._family_lowering(q, ds)
        with span(SPAN_FINALIZE):
            df = finalize_groupby(inner, lowering.dims, lowering.la, state)
        return shape(df)


class Engine(AdaptiveDomainMixin, SparseExecMixin, HostStateSurface):
    """Executes query specs on the local device set.

    Which kernel runs is plan/cost.py's to say.  A planned query brings
    its class and the session's cost constants with it, as `strategy=` /
    `cfg=` of the call that executes it; the constructor's `strategy`
    and `config` are what a call without a plan runs under (a directly
    built engine: tests, streaming, the wire path).  Nothing writes
    either after construction: concurrent requests share the engine."""

    def __init__(
        self,
        strategy: str = "auto",
        device_cache_bytes: Optional[int] = None,
        program_cache_entries: int = 256,
        config=None,
    ):
        from ..config import SessionConfig
        from ..utils.lru import ByteBudgetCache, CountBudgetCache

        if device_cache_bytes is None:
            device_cache_bytes = _default_device_budget()
        self.strategy = strategy
        self.config = config or SessionConfig.load_calibrated()
        # observability (SURVEY.md §5): populated on every execution
        self.last_metrics = None
        # metrics object being filled during one execution — THREAD-LOCAL
        # (the `_m` property below): the serving layer runs concurrent
        # queries through ONE engine, and a shared field let query A's
        # finish() null the object query B was mid-way through stamping
        # (crash) while both garbled each other's h2d/compile attribution
        import threading as _threading

        self._m_local = _threading.local()
        # resilience wiring (resilience.py): transient device failures and
        # recoveries are reported to the breaker; TPUOlapContext replaces
        # this default with its shared per-context breaker and syncs the
        # retry budget from SessionConfig.  The breaker never gates THIS
        # layer — routing around an open circuit is the api's job.
        from ..resilience import CircuitBreaker

        self.breaker = CircuitBreaker()
        self._retry_attempts = 2  # total attempts (2 = one retry)
        self._retry_backoff_ms = 25.0
        # queries pinned off the sparse accelerator because compaction
        # deterministically overflowed SPARSE_SLOTS distinct groups
        self._sparse_disabled: set = set()
        # queries whose survivors overflowed the base row-compaction
        # capacity: the kernel reports the exact survivor count, the engine
        # picks the smallest adequate ROW_CAPACITY_LADDER rung (None = full
        # sort) — deterministic for a given (query, data), so repeats go
        # straight to the remembered rung
        self._sparse_row_capacity: Dict = {}
        # adaptive dictionary-domain compaction (exec/adaptive_exec.py):
        # per-query kept code sets and the decline memo
        self._adaptive_kept: Dict = {}
        self._adaptive_declined: set = set()
        # queries phase A sent to the highcard route (plan.cost.compact_route)
        self._highcard: set = set()
        # queries whose distinct-present count overflowed the one-hot slot
        # tier: remembered SLOTS_LADDER rung for the segmented-reduce tier
        # (sparse_exec.fetch_slot_laddered)
        self._sparse_slots: Dict = {}
        # LRU residency cache under a byte budget (VERDICT r1 weak #7: the
        # unbounded caches OOMed HBM over long sessions).  4 GiB default
        # leaves headroom on a 16 GiB v5e chip for kernel workspace.
        self._device_cache = ByteBudgetCache(device_cache_bytes)
        # residency attribution (obs/prof.py, ISSUE 9): per-datasource
        # resident-bytes gauges + budget-pressure eviction counters need
        # a key -> (datasource, bytes) side table (cache keys carry only
        # segment uids)
        self._resident_lock = _threading.Lock()
        self._resident_meta: Dict = {}
        self._resident_by_ds: Dict[str, int] = {}
        self._device_cache.on_evict = (
            lambda key, arr: self._note_resident_drop(key, evicted=True)
        )
        # (query-json, datasource, strategy) -> jitted per-segment program.
        # One fused XLA program per query shape: without this, every eager op
        # in the row pipeline is a separate device dispatch.
        self._query_fn_cache = CountBudgetCache(program_cache_entries)
        # (query-json, datasource) -> GroupByLowering.  Lowering is host work
        # that also stages device constants (dictionary remaps, bucket tables,
        # filter literal sets); rebuilding it per execution pays one blocking
        # H2D transfer per constant.
        self._lowering_cache = CountBudgetCache(program_cache_entries)
        # overlapped h2d transfer pipeline (exec/pipeline.py, ISSUE 10):
        # prefetches the next dispatch batches' cold columns behind the
        # current batch's compute and orders dispatch resident-first.
        # TPUOlapContext re-configures it from SessionConfig
        # (configure_pipeline); a bare Engine() gets the defaults.
        from .pipeline import TransferPipeline

        self._pipeline = TransferPipeline(self)
        # one-dispatch arena execution (exec/arena.py, ISSUE 14): stack
        # the uniform-shape prefix of a scope and fold it inside ONE
        # scanned program instead of one dispatch per segment batch.
        # TPUOlapContext syncs this from SessionConfig.arena_execution
        # (configure_pipeline); per-query opt-out via
        # arena.arena_disabled().
        self.arena_execution = True

    @property
    def _m(self):
        """The execution THIS THREAD is currently stamping metrics into
        (None outside an execution).  `last_metrics` stays shared —
        "most recent" is a cross-thread statement by design."""
        return getattr(self._m_local, "m", None)

    @_m.setter
    def _m(self, value):
        self._m_local.m = value

    # -- segment residency ---------------------------------------------------

    def _note_resident_add(self, key, ds_name: str, nbytes: int) -> None:
        with self._resident_lock:
            prev = self._resident_meta.get(key)
            if prev is not None:  # re-put of a live key: replace, not add
                self._resident_by_ds[prev[0]] = max(
                    0, self._resident_by_ds.get(prev[0], 0) - prev[1]
                )
            self._resident_meta[key] = (ds_name, nbytes)
            self._resident_by_ds[ds_name] = (
                self._resident_by_ds.get(ds_name, 0) + nbytes
            )
            now = self._resident_by_ds[ds_name]
        prof.record_resident(ds_name, now)

    def _note_resident_drop(self, key, evicted: bool = False) -> None:
        with self._resident_lock:
            meta = self._resident_meta.pop(key, None)
            if meta is None:
                return
            ds_name, nbytes = meta
            now = max(0, self._resident_by_ds.get(ds_name, 0) - nbytes)
            self._resident_by_ds[ds_name] = now
        prof.record_resident(ds_name, now)
        if evicted:
            prof.record_eviction(ds_name)

    def _put_device_col(
        self, key, host, ds_name: str, prefetched: bool = False
    ) -> jnp.ndarray:
        """The ONE sanctioned host->device placement of a segment column
        into the residency cache (graftlint transfer-discipline/GL19xx):
        fires the `h2d` fault site, issues the (async) placement,
        registers residency meta, and records link accounting.  Both the
        foreground miss path (`_device_cols`) and the transfer
        pipeline's prefetch issue (exec/pipeline.py) ride it —
        `prefetched` puts never sync (blocking a prefetch would destroy
        the overlap it exists to create) and account into the prefetch
        bucket instead of transfer stall."""
        import time as _time

        # DISK rung of the residency ladder (ISSUE 13): snapshot-restored
        # columns arrive as np.memmap views over the persisted .npy files
        # (catalog/persist.LazyColumnMap).  Materialize to host RAM HERE —
        # the one chokepoint both the foreground miss path and the
        # prefetch pipeline ride — so page-fault time lands inside the
        # measured transfer window (prefetched puts thus overlap the DISK
        # read behind compute too, not just the link), and the device
        # never holds a buffer aliasing a file that compaction may retire.
        from ..catalog.persist import is_disk_backed, materialize

        if is_disk_backed(host):
            host = materialize(host)
        fire("h2d")  # fault-injection site: host->device transfer
        t0 = _time.perf_counter()
        arr = jnp.asarray(host)
        if not prefetched:
            # sampled query: block so the measured window is the real
            # link time, not the enqueue (obs/prof.py; no-op otherwise)
            arr = prof.transfer_sync(arr)
        dt = _time.perf_counter() - t0
        nbytes = int(np.asarray(host).nbytes)
        # residency meta registers BEFORE the cache insert: a
        # concurrent put can budget-evict this key the instant it
        # lands, and on_evict must find the meta to drop — the
        # reverse order leaked phantom resident bytes
        self._note_resident_add(key, ds_name or "unknown", nbytes)
        self._device_cache[key] = arr
        # a successful landing supersedes any STALE poison for this key
        # (a failed prefetch whose owning query was truncated before
        # consuming it must not resurface on a future cache miss)
        self._pipeline.clear_poison(key)
        # link-utilization accounting: bytes + effective MB/s into
        # the scrapeable histogram (the 45 MB/s h2d floor claim)
        prof.record_h2d(nbytes, dt, prefetched=prefetched)
        if self._m is not None:  # streamed-bytes metric (cache misses only)
            self._m.h2d_bytes += nbytes
            self._m.h2d_ms += dt * 1e3
        return arr

    def configure_pipeline(self, config) -> None:
        """Apply SessionConfig's execution knobs (api context): the
        transfer-pipeline tunables plus the arena-execution gate."""
        self._pipeline.configure(config)
        self.arena_execution = bool(
            getattr(config, "arena_execution", True)
        )

    def _device_cols(
        self, seg: Segment, names, ds_name: str = ""
    ) -> Dict[str, jnp.ndarray]:
        cols: Dict[str, jnp.ndarray] = {}

        def lookup(key, host_fn):
            arr = self._device_cache.get(key)
            if arr is not None:
                prof.note_residency(hit=True)
                return arr
            # a prefetched put that FAILED (injected h2d fault, real
            # backend error) poisoned this key: re-raise in query
            # context so the retry/breaker machinery sees the failure
            # exactly as if the foreground transfer had raised
            exc = self._pipeline.take_poison(key)
            if exc is not None:
                raise exc
            prof.note_residency(hit=False)
            return self._put_device_col(key, host_fn(), ds_name)

        # "col"/"valid" tags: a user column literally named "__valid"
        # must not alias the validity-mask entry (jit-collision/GL1301)
        for n in names:
            cols[n] = lookup(
                (seg.uid, "col", n), lambda n=n: seg.column(n)
            )
        cols["__valid"] = lookup((seg.uid, "valid"), lambda: seg.valid)
        return cols

    def bytes_resident(self) -> int:
        """HBM bytes held by the segment residency cache."""
        return self._device_cache.bytes_used

    def missing_resident_bytes(self, ds, cols) -> int:
        """Estimated bytes a query over `cols` would have to move
        host->device before executing — 0 when everything is already
        resident.  Owns the cache-key scheme AND the buffer set
        (_device_cols: per-segment columns plus the validity buffer) so
        planner-side h2d costing (api device-assist) never re-encodes
        either.  4 bytes/row/buffer: codes are <=4 B, metric values f32."""
        need = [("col", c) for c in cols] + [("valid",)]
        return sum(
            4 * seg.num_rows
            for seg in ds.segments
            for tail in need
            if (seg.uid,) + tail not in self._device_cache
        )

    def clear_cache(self):
        """Analog of the reference's metadata/cache clear command.  Drops the
        program cache too: compiled programs close over their lowering's
        staged device constants, so leaving them would pin the HBM this is
        documented to release."""
        self._device_cache.clear()
        self._lowering_cache.clear()
        self._query_fn_cache.clear()
        with self._resident_lock:
            self._resident_meta.clear()
            dropped = list(self._resident_by_ds)
            self._resident_by_ds.clear()
        for name in dropped:
            prof.record_resident(name, 0)

    def drop_residency(self) -> None:
        """Evict EVERY device-resident segment column while keeping the
        compiled programs and lowerings (unlike `clear_cache`) and
        WITHOUT retiring uids (unlike `evict_segments` — the segments
        stay live and prefetchable).  The overlap bench uses it to
        re-cold the link between pipeline-on/off counterfactual runs."""
        for k in list(self._device_cache):
            self._device_cache.pop(k)
            self._note_resident_drop(k)

    def evict_segments(self, uids) -> None:
        """Drop device residency of specific segments — the ingestion
        tier's hook: compaction (and dictionary-extension remaps) retire
        segment uids from the published set, and their HBM should come
        back immediately rather than waiting for LRU pressure."""
        uids = set(uids)
        # queued-but-unissued prefetches for these uids must never land:
        # a put issued after this evict would re-resident a dead segment
        self._pipeline.note_retired(uids)
        from . import arena as _arena

        # arena slices stack MANY uids under one ("arena", *uids) key:
        # any intersection with the retired set invalidates the whole
        # stack (a later query re-plans and re-stacks the live segments)
        for k in [
            k
            for k in self._device_cache
            if k[0] in uids
            or (_arena.is_arena_key(k) and uids.intersection(k[0][1:]))
        ]:
            self._device_cache.pop(k)
            self._note_resident_drop(k)

    def _segment_batches(self, segs, names, la=None):
        """Split in-scope segments into dispatch batches: each batch becomes
        ONE fused program call.  Bounded by MULTI_SEGMENT_UNROLL_MAX (compile
        time grows with the unroll; THETA_SEGMENT_UNROLL_MAX where `la`
        holds a theta sketch) and by the device-cache byte budget (a
        batch pins every member's columns on device simultaneously, so an
        unbounded batch would defeat the residency budget)."""
        budget = self._device_cache.budget_bytes
        unroll_max = _platform_unroll_max(la)
        batch: List[Segment] = []
        batch_bytes = 0
        # graftlint: disable=checkpoint-coverage -- batching is nbytes arithmetic; every CONSUMER of these batches checkpoints per batch
        for seg in segs:
            est = int(seg.valid.nbytes) + sum(
                int(seg.column(n).nbytes) for n in names
            )
            if batch and (
                len(batch) >= unroll_max
                or batch_bytes + est > budget
            ):
                yield batch
                batch, batch_bytes = [], 0
            batch.append(seg)
            batch_bytes += est
        if batch:
            yield batch

    def _lowering_for(self, q: Q.GroupByQuery, ds: DataSource):
        from .lowering import cached_lowering

        return cached_lowering(self._lowering_cache, q, ds)

    def _cols_for_segment(self, seg: Segment, ds: DataSource, names):
        cols = self._device_cols(seg, names, ds_name=ds.name)
        if ds.time_column and ds.time_column in cols:
            cols["__time"] = cols[ds.time_column]
        return cols

    # -- entry points --------------------------------------------------------

    def execute(self, q: Q.QuerySpec, ds: DataSource, strategy=None, cfg=None):
        """`strategy` / `cfg`: the plan's kernel class and the session's
        cost constants (class docstring); None = the constructor's."""
        inner, shape = groupby_family(q, ds)
        if inner is not None:
            return shape(self._execute_groupby(inner, ds, strategy, cfg))
        if isinstance(q, Q.ScanQuery):
            return self._execute_scan(q, ds)
        if isinstance(q, Q.SearchQuery):
            return self._execute_search(q, ds)
        if isinstance(q, Q.TimeBoundaryQuery):
            return self._execute_time_boundary(q, ds)
        if isinstance(q, Q.DataSourceMetadataQuery):
            return self._execute_datasource_metadata(q, ds)
        if isinstance(q, Q.SegmentMetadataQuery):
            return self._execute_segment_metadata(q, ds)
        raise NotImplementedError(type(q).__name__)

    # -- groupby -------------------------------------------------------------

    def _segments_in_scope(self, q, ds: DataSource) -> List[Segment]:
        return segments_in_scope(q, ds)

    def _partials_for_query(
        self,
        q: Q.GroupByQuery,
        ds: DataSource,
        lowering=None,
        key_extra=(),
        strategy_override=None,
        segs=None,
        span_attrs=None,
    ):
        """Compute merged partial state across local segments.

        `key_extra` disambiguates the program cache when the SAME query runs
        over a rewritten lowering (adaptive domain compaction passes the
        compacted cardinalities).  `segs` is the scanned segment list,
        already scope-pruned: `_dispatch_groupby_once` and the adaptive
        tier hand down the scope they resolved, the delta-aware result
        cache passes just the freshly-appended segments; only a caller
        with none (None) has it resolved here.  `span_attrs` go on every
        dispatch span (the adaptive tier marks its phase B with them).

        Returns the merged device `PartialState`."""
        if lowering is None:
            lowering = self._lowering_for(q, ds)
        la, G = lowering.la, lowering.num_groups
        need = lowering.columns

        state = None
        if segs is None:
            segs = self._segments_in_scope(q, ds)
        pc = current_partial()
        if not segs:
            # empty time range is a valid query: zero-row result, not an
            # error — and a COMPLETE one.  Declare the empty scope so a
            # deadline trigger later in the lifecycle cannot flag the
            # exact empty answer partial with an unknown denominator.
            if pc is not None:
                pc.begin_pass()
                pc.add_scope(0, 0)
            return PartialState.empty(la, G)
        # deadline-bounded partial answers: declare the pass's scope so a
        # mid-scan expiry can stamp an honest coverage fraction onto the
        # merged partials (resilience.PartialCollector)
        if pc is not None:
            pc.begin_pass()
            pc.add_scope(len(segs), *_row_counts(segs))
        # remainder segments fuse into batched programs (partial agg +
        # cross-segment merge inside; built lazily below — the arena may
        # cover the whole scope, needing no per-batch program at all)

        def fold(new):
            nonlocal state
            state = merge(la, state, new)

        batches = list(self._segment_batches(segs, need, la))
        # transfer pipeline (exec/pipeline.py): residency-aware dispatch
        # order + async prefetch of the next batches' cold columns behind
        # this batch's compute; speculative next-interval segments trail
        # the plan under their own byte cap.  CanonicalFold pins the
        # merge order to canonical batch order (f32 partial sums and the
        # sketch scatter merges are not reassociation-safe) so
        # pipeline-on results stay byte-identical to pipeline-off.
        from .pipeline import CanonicalFold

        # one-dispatch arena (exec/arena.py, ISSUE 14): the uniform-shape
        # whole-batch PREFIX of the scope stacks into resident [B, R]
        # columns and folds inside ONE scanned program — one dispatch
        # where this loop pays one per batch.  Remainder batches (shape
        # -change tail, deltas, budget overflow) fall through to the loop
        # below with the fold continuing in canonical order, so results
        # stay byte-identical arena-on vs arena-off.  Sketch aggs decline
        # (their merge states carry no exact in-scan fold identity): a
        # scope the arena would take runs the loop below, and its
        # dispatch spans say so (ROADMAP M6).
        phase = span_attrs or {}
        plan = run = None
        if self.arena_execution:
            from . import arena as _arena

            if not _arena.query_disabled():
                plan = _arena.plan_for(self, batches, need)
            if plan is not None and la.sketch_aggs:
                plan = None
                phase = {**phase, "arena": "declined:sketch"}
        if plan is not None:
            strategy = strategy_override or concrete_kernel(self.strategy, G)
            run = self._pipeline.start(
                ds, plan.remainder, need,
                speculative=self._pipeline.speculative_candidates(
                    q, ds, segs
                ),
            )
            # remainder prefetch issues BEFORE the arena dispatch: the
            # async puts land behind the scanned program's compute
            run.advance(-1)
            program = self._arena_program(
                q, ds, lowering, strategy, key_extra=key_extra
            )
            # the arena IS the segment loop, scanned: it checkpoints
            # under the same site name, so deadline tests and armed
            # injections drive its chunked truncation exactly like
            # the dispatch loop's
            states, _done = _arena.run_plan(
                self, ds, plan, need, program, pc=pc,
                checkpoint_site="engine.segment_loop", span_attrs=phase,
            )
            batches = plan.remainder
            if plan.folded:  # the arena declines sketches
                state = PartialState(*states[0][:3], {})
            if plan.folded < len(plan.batches):
                # truncated mid-arena: the remainder must not run
                # (and its pending prefetch cancels with it)
                run.cancel()
        if run is None:
            run = self._pipeline.start(
                ds, batches, need,
                speculative=self._pipeline.speculative_candidates(
                    q, ds, segs
                ),
            )
        seg_fn = None
        if batches and not run.cancelled:
            seg_fn = self._segment_program(
                q, ds, lowering, key_extra=key_extra,
                strategy_override=strategy_override,
            )
        folder = CanonicalFold(fold)
        n_theta = len(_theta_aggs(la))
        for pos, bi in enumerate(run.order if seg_fn is not None else ()):
            # cooperative deadline checkpoint: a query with a wall-clock
            # budget cancels between batch dispatches, not at the very
            # end — and with a partial collector armed, expiry STOPS the
            # dispatch loop instead of erroring (the partials accumulated
            # so far merge into a best-effort answer).  Any pending
            # prefetch cancels with it.
            if checkpoint_partial("engine.segment_loop"):
                run.cancel()
                break
            folder.add(bi, self._dispatch_batch(
                seg_fn, ds, need, batches[bi], bi, run, pos, pc, n_theta,
                phase,
            ))
        # a truncation can leave batches dispatched AHEAD of canonical
        # order un-folded: drain them (still canonical) so every batch
        # pc accounted merges
        folder.drain()
        if state is None:
            # the deadline expired before the FIRST batch dispatched: the
            # well-formed zero-coverage answer is the empty partial state
            state = PartialState.empty(la, G)
        return state

    def _dispatch_batch(
        self, seg_fn, ds, need, batch, bi, run, pos, pc, n_theta, phase
    ) -> PartialState:
        """One batch of a single-query loop: its columns to the device
        (`h2d`), the pipeline's next step (`run.advance(pos)`), the
        program's launch (`segment_dispatch`, with `phase` as attrs), and
        the batch counted as seen.  Returns the batch's state."""
        with span(SPAN_H2D, batch=bi, segments=len(batch)):
            cols_list = [
                self._cols_for_segment(seg, ds, need) for seg in batch
            ]
        run.advance(pos)
        with span(
            SPAN_SEGMENT_DISPATCH, batch=bi, segments=len(batch),
            **phase, **_theta_attrs(n_theta, cols_list),
        ):
            state = self._call_segment_program(seg_fn, cols_list)
        if self._m is not None:
            self._m.theta_folds += n_theta
        if pc is not None:
            pc.add_seen(len(batch), *_row_counts(batch))
        return state

    def _call_segment_program(self, seg_fn, cols_list):
        """Run one segment program over a list of per-segment column
        dicts.  A kernel the compiler refuses raises like any other
        static error: there is no second path to hide it behind."""
        # fault-injection site: an injected (or real pre-dispatch)
        # transient fault reaches the retry/breaker machinery
        fire("device_dispatch")
        return self._timed_launch(seg_fn, cols_list)

    def _timed_launch(self, fn, cols_list):
        """Launch a program; its first call on a newly built program (a
        trace and compile) is this thread's `compile_ms`."""
        import time as _time

        m = self._m  # one read: this thread's in-flight metrics object
        # first call of a newly-built program = trace+compile (+async
        # dispatch); attribute it to compile_ms (see metrics.py)
        t0 = (
            _time.perf_counter()
            if m is not None
            and not m.program_cache_hit
            and m.compile_ms == 0
            else None
        )
        t_call = _time.perf_counter()
        result = fn(cols_list)
        # sampled query: block here so the enclosing dispatch span
        # splits into enqueue vs device-complete time (obs/prof.py);
        # a literal no-op at the default sample rate of 0
        result = prof.dispatch_sync(result, t_call)
        if t0 is not None:
            m.compile_ms = (_time.perf_counter() - t0) * 1e3
            # first-trace/compile attributed to the tagged program
            # family whose cache miss built this program
            prof.note_compile(m.compile_ms)
        return result

    @span_around(SPAN_PROGRAM_LOOKUP)
    def _segment_program(
        self,
        q: Q.GroupByQuery,
        ds: DataSource,
        lowering: "GroupByLowering",
        key_extra=(),
        strategy_override=None,
    ) -> Callable:
        """One fused, cached XLA program per query: row pipeline (virtual
        columns, filter mask, group ids) + partial aggregation + sketch
        partials for EVERY in-scope segment, merged in-program — a single
        dispatch.  The analog of Druid compiling a query into one engine pass,
        with the broker's cross-segment merge folded in."""
        G = lowering.num_groups
        strategy = strategy_override or concrete_kernel(self.strategy, G)
        # _query_key includes schema_signature: a re-ingested datasource
        # (new dict cardinalities => new G) must not reuse a stale program.
        # The "fused" tag pins this key family apart from the tagged
        # sparse/adaptive/stream families sharing this cache: without it
        # nothing stops `strategy` + key_extra from ever spelling another
        # family's tuple (graftlint jit-collision/GL1301)
        key = _query_key(q, ds) + ("fused", strategy) + tuple(key_extra)
        family = (
            "fused" if not key_extra else f"fused/{key_extra[0]}"
        )
        cached = self._query_fn_cache.get(key)
        if cached is not None:
            if self._m is not None:
                self._m.program_cache_hit = True
            prof.note_program_cache(family, hit=True)
            return cached
        prof.note_program_cache(family, hit=False)
        fire("compile")  # fault-injection site: new program build

        @jax.jit
        def seg_fn(cols_list):
            return _batch_partials(lowering, strategy, cols_list)

        self._query_fn_cache[key] = seg_fn
        return seg_fn

    @span_around(SPAN_PROGRAM_LOOKUP)
    def _arena_program(
        self, q, ds, lowering, strategy: str, key_extra=()
    ) -> Callable:
        """The one-dispatch arena program for one query (exec/arena.py):
        a single traced `lax.scan` over the stacked segment blocks with
        the cross-batch fold inside the trace.  Cached under its own
        "arena"-tagged key family: the tag keeps it disjoint from the
        per-batch "fused" programs sharing this cache, and the strategy
        component lets the Pallas-fallback eviction sweep find it
        (jit-collision/GL1301)."""
        from . import arena as _arena

        key = _query_key(q, ds) + ("arena", strategy) + tuple(key_extra)
        family = "arena" if not key_extra else f"arena/{key_extra[0]}"
        cached = self._query_fn_cache.get(key)
        if cached is not None:
            if self._m is not None:
                self._m.program_cache_hit = True
            prof.note_program_cache(family, hit=True)
            return cached
        prof.note_program_cache(family, hit=False)
        fire("compile")  # fault-injection site: new program build
        fn = _arena.build_arena_program([lowering], [strategy])
        self._query_fn_cache[key] = fn
        return fn

    # -- micro-batch fusion (serve/, ISSUE 8) --------------------------------

    def fusable(self, q: Q.QuerySpec, ds: DataSource, strategy=None) -> bool:
        """May this query join a fused micro-batch / the state-capturing
        dense path?  GroupBy-family only (mergeable partial state), no
        wire subtotals, and neither the sparse nor the adaptive
        accelerator would engage under `strategy` (those tiers have
        their own dispatch protocols a fused program cannot host)."""
        inner, _ = groupby_family(q, ds)
        if inner is None or inner.subtotals:
            return False
        try:
            lowering = self._lowering_for(
                groupby_with_time_granularity(inner), ds
            )
        except Exception:  # fault-ok: an unlowerable query declines fusion
            return False
        strategy = strategy or self.strategy
        return not (
            lowered_tier_takes("sparse", lowering, strategy)
            or lowered_tier_takes("adaptive", lowering, strategy)
        )

    def execute_fused(
        self, queries, ds: DataSource, query_ids=None, strategies=None
    ):
        """Execute N compatible GroupBy-family queries as ONE fused device
        program per segment batch: the union of the members' in-scope
        segments moves host->device once (shared residency), every
        member's partial aggregation runs inside the same dispatch, and
        ONE host fetch returns all members' states — the 66 ms dispatch
        round trip is paid once for the batch instead of once per query.

        Returns a list of (df, state, metrics) per member, in order:
        `df` is the finalized per-query result (identical to a serial
        `execute`), `state` the merged HOST partial state (the delta-aware
        result cache stores it), `metrics` the member's own QueryMetrics
        (query_id stamped per member — serving-discipline GL1702).
        `strategies`: each member's planned class (None = the
        constructor's, for every member)."""
        import time as _time

        from .metrics import QueryMetrics

        t0 = _time.perf_counter()
        n = len(queries)
        prof.note_fusion(n)  # the leader's receipt records the batch size
        query_ids = list(query_ids or [""] * n)
        members = []
        for q in queries:
            inner, shape = groupby_family(q, ds)
            if inner is None:
                raise ValueError(
                    f"{type(q).__name__} is not fusable (GroupBy-family "
                    "queries only)"
                )
            inner = groupby_with_time_granularity(inner)
            lowering = self._lowering_for(inner, ds)
            segs = self._segments_in_scope(inner, ds)
            members.append((q, inner, shape, lowering, segs))
        # union of member scopes, in datasource segment order; each member
        # aggregates ONLY its own in-scope subset inside the program
        member_uids = [frozenset(s.uid for s in m[4]) for m in members]
        union_segs = [
            s
            for s in ds.segments
            if any(s.uid in u for u in member_uids)
        ]
        names = dict.fromkeys(
            c for m in members for c in m[3].columns
        )
        strategies = tuple(
            concrete_kernel(st or self.strategy, m[3].num_groups)
            for st, m in zip(strategies or [None] * n, members)
        )
        batch_m = QueryMetrics(query_type="fused")  # h2d/compile accumulator
        self._m = batch_m
        acc: List[Any] = [None] * n
        n_theta = [len(_theta_aggs(m[3].la)) for m in members]
        theta_folds = [0] * n

        def fold(outs):
            for i, st in enumerate(outs):
                if st is not None:  # None: no member segment in the batch
                    acc[i] = merge(members[i][3].la, acc[i], st)

        try:
            from .pipeline import CanonicalFold

            batches = list(self._segment_batches(union_segs, list(names)))
            # one-dispatch arena (exec/arena.py, ISSUE 14): the fused
            # micro-batch executes against ONE shared arena — every
            # member's fold runs inside the same scanned program, with
            # per-block membership flags as DATA (one compiled program
            # serves any member->segment mapping).  Remainder batches
            # fall through to the per-batch fused loop below.
            plan = run = None
            if self.arena_execution and not any(
                m[3].la.sketch_aggs for m in members
            ):
                from . import arena as _arena

                if not _arena.query_disabled():
                    plan = _arena.plan_for(self, batches, list(names))
            if plan is not None:
                # the fused deadline contract, checked once up front: an
                # expiry re-routes every member to its own serial
                # (partial-capable) path — exactly what the loop's
                # per-batch checkpoint would do
                checkpoint("engine.fused_loop")
                run = self._pipeline.start(ds, plan.remainder, list(names))
                run.advance(-1)
                memb = np.array(
                    [
                        [s.uid in u for u in member_uids]
                        for s in plan.segs
                    ],
                    dtype=bool,
                )
                fn = self._arena_fused_program(members, ds, strategies)
                try:
                    # run_plan stamps batch_m's compile attribution on
                    # the first (trace+compile) dispatch
                    states, _done = _arena.run_plan(
                        self, ds, plan, list(names), fn, memb=memb,
                        single_chunk=True,
                    )
                except BaseException:
                    run.cancel()
                    raise
                for i in range(n):
                    # membership is host-known: a member with no covered
                    # block keeps acc[i] = None (the loop's None-skip)
                    if len(plan.segs) and memb[:, i].any():
                        acc[i] = PartialState(*states[i][:3], {})
                batches = plan.remainder
            # transfer pipeline: resident batches dispatch first, cold
            # batches' columns stream behind the fused compute; the
            # per-member fold stays pinned to canonical batch order
            # (byte-identical to the serial path)
            if run is None:
                run = self._pipeline.start(ds, batches, list(names))
            folder = CanonicalFold(fold)
            for pos, bi in enumerate(run.order):
                # deadline checkpoint between fused batch dispatches; an
                # expiry here surfaces to the scheduler, which re-routes
                # every member to its own serial (partial-capable) path
                try:
                    checkpoint("engine.fused_loop")
                except BaseException:
                    run.cancel()
                    raise
                batch = batches[bi]
                sel = tuple(
                    tuple(
                        j
                        for j, seg in enumerate(batch)
                        if seg.uid in member_uids[i]
                    )
                    for i in range(n)
                )
                with span(SPAN_H2D, batch=bi, segments=len(batch)):
                    cols_list = [
                        self._cols_for_segment(seg, ds, list(names))
                        for seg in batch
                    ]
                run.advance(pos)
                fn = self._fused_program(members, ds, strategies, sel)
                with span(
                    SPAN_SEGMENT_DISPATCH, batch=bi, segments=len(batch),
                    fused=n,
                ):
                    outs = self._timed_launch(fn, cols_list)
                folder.add(bi, outs)
                for i in range(n):
                    theta_folds[i] += n_theta[i] if sel[i] else 0
            folder.drain()
        finally:
            self._m = None
        # members whose whole scope was pruned hold no accumulated state:
        # fill with empty partials, fetched in the SAME single round trip
        # as the live states (a per-member fetch would re-pay the device
        # round trip the fused batch exists to amortize)
        empties = {
            i: PartialState.empty(m[3].la, m[3].num_groups)
            for i, m in enumerate(members)
            if acc[i] is None
        }
        with span(SPAN_DEVICE_FETCH, fused=n):
            prof.fetch_sync(acc)
            acc_h, empties_h = to_host((acc, empties))
        out = []
        elapsed_ms = (_time.perf_counter() - t0) * 1e3
        # graftlint: disable=checkpoint-coverage -- demux loop: all device states are already fetched; discarding finished answers at expiry would re-pay the whole batch
        for i, (q, inner, shape, lowering, segs) in enumerate(members):
            state = empties_h[i] if acc_h[i] is None else acc_h[i]
            counted = QueryMetrics()
            with span(SPAN_FINALIZE, member=i):
                df = shape(finalize_groupby(
                    inner, lowering.dims, lowering.la, state,
                    metrics=counted,
                ))
            try:
                qt = q.to_druid().get("queryType", type(q).__name__)
            except Exception:  # fault-ok: metrics labeling only
                qt = type(q).__name__
            rows, _delta = _row_counts(segs)
            m = QueryMetrics(
                query_type=qt,
                strategy=strategies[i],
                datasource=ds.name,
                query_id=query_ids[i],
                rows_scanned=rows,
                bytes_scanned=_bytes_scanned(segs, lowering.columns),
                segments=len(segs),
                num_groups=lowering.num_groups,
                # the batch's shared h2d/compile split evenly: the fused
                # program moved ONE column set for all members
                h2d_bytes=batch_m.h2d_bytes // n,
                h2d_ms=batch_m.h2d_ms / n,
                compile_ms=batch_m.compile_ms,
                total_ms=elapsed_ms,
                fused_batch=n,
                program_cache_hit=batch_m.program_cache_hit,
                theta_folds=theta_folds[i],
                host_groups=counted.host_groups,
            )
            record_query_metrics(m, "ok")
            out.append((df, state, m))
        self.last_metrics = out[-1][2] if out else None
        return out

    @span_around(SPAN_PROGRAM_LOOKUP)
    def _fused_program(self, members, ds, strategies, sel) -> Callable:
        """One jitted program computing EVERY member's partial state over
        one segment batch.  Cached in the engine's program cache under the
        `("fused-batch", ...)` family — anchored on the first member's
        `_query_key` plus the remaining members' query identities, the
        resolved strategies, and the batch's member->segment selection, so
        no other key family can spell the same tuple (jit-collision
        GL1301)."""
        import json as _json

        key = _query_key(members[0][1], ds) + (
            "fused-batch",
            tuple(
                _json.dumps(m[1].to_druid(), sort_keys=True, default=str)
                for m in members[1:]
            ),
            strategies,
            sel,
        )
        cached = self._query_fn_cache.get(key)
        if cached is not None:
            if self._m is not None:
                self._m.program_cache_hit = True
            prof.note_program_cache("fused-batch", hit=True)
            return cached
        prof.note_program_cache("fused-batch", hit=False)
        fire("compile")  # fault-injection site: new program build
        # common-subexpression plan over the member lowerings (ROADMAP
        # 1(a)): members sharing filter/dimension sub-lowerings reuse one
        # traced mask/gid per segment inside the program.  A pure
        # function of the member JSONs already serialized into the key,
        # so it is computed ONLY on a miss (the hot serving path's cache
        # hits skip the per-member to_druid + json passes).  Lazy
        # import: serve/ imports exec/ at module load, not the reverse.
        from ..serve.fusion import shared_row_plan

        share = shared_row_plan([m[1] for m in members])
        lowerings = [m[3] for m in members]

        @jax.jit
        def fused_fn(cols_list):
            outs = []
            memo: Dict[Any, Any] = {}  # per-trace CSE memo (mask/gid)
            for i, lowering in enumerate(lowerings):
                outs.append(_batch_partials(
                    lowering, strategies[i],
                    [cols_list[j] for j in sel[i]], memo=memo,
                    shares=[share[i] + (j,) for j in sel[i]],
                ))
            return outs

        self._query_fn_cache[key] = fused_fn
        return fused_fn

    @span_around(SPAN_PROGRAM_LOOKUP)
    def _arena_fused_program(self, members, ds, strategies) -> Callable:
        """The one-dispatch arena program for a fused micro-batch (exec/
        arena.py): every member's fold over the stacked scope inside one
        scanned program.  Unlike `_fused_program`, the member->segment
        selection is NOT in the key — membership rides as data, so one
        compiled program serves every batch shape of the same member
        set."""
        import json as _json

        from . import arena as _arena

        key = _query_key(members[0][1], ds) + (
            "arena-fused",
            tuple(
                _json.dumps(m[1].to_druid(), sort_keys=True, default=str)
                for m in members[1:]
            ),
            strategies,
        )
        cached = self._query_fn_cache.get(key)
        if cached is not None:
            if self._m is not None:
                self._m.program_cache_hit = True
            prof.note_program_cache("arena-fused", hit=True)
            return cached
        prof.note_program_cache("arena-fused", hit=False)
        fire("compile")  # fault-injection site: new program build
        from ..serve.fusion import shared_row_plan

        share = shared_row_plan([m[1] for m in members])
        fn = _arena.build_arena_program(
            [m[3] for m in members], strategies, share=share
        )
        self._query_fn_cache[key] = fn
        return fn

    # -- host partial-state surface (delta-aware result cache, ISSUE 8) -----

    def groupby_partials_host(
        self, q: Q.QuerySpec, ds: DataSource, within_uids=None
    ):
        """Merged HOST partial state of a GroupBy-family query, restricted
        to in-scope segments whose uid is in `within_uids` (None = the
        full scope).  The delta-aware result cache calls this with the
        freshly-appended uids so a dashboard refresh after an append
        scans ONLY the delta.  Returns (state, rows_scanned)."""
        inner, _, lowering = self._family_lowering(q, ds)
        segs = self._segments_in_scope(inner, ds)
        if within_uids is not None:
            within_uids = frozenset(within_uids)
            segs = [s for s in segs if s.uid in within_uids]
        state = self._partials_for_query(
            inner, ds, lowering=lowering, segs=segs
        )
        return to_host(state), sum(s.num_rows for s in segs)

    def _execute_groupby(
        self, q: Q.GroupByQuery, ds: DataSource, strategy=None, cfg=None
    ):
        """GroupBy with idempotent re-dispatch on transient device failure
        — the analog of Spark retrying a DruidRDD partition (SURVEY.md §5
        failure-detection row: queries are read-only, so a retry is always
        safe) — generalized (resilience.run_device_attempts) into
        retry-with-backoff under a budget, with every outcome reported to
        the circuit breaker.  Static errors (RewriteError / ValueError,
        NotImplementedError — a RuntimeError subclass — and
        DeadlineExceeded) propagate immediately and never touch the
        breaker."""
        from ..resilience import run_device_attempts

        # normalize ONCE so the retry evicts under the same cache identity
        # the execution cached under (granularity adds a __time dimension)
        q = groupby_with_time_granularity(q)
        return run_device_attempts(
            self,
            lambda: self._dispatch_groupby_once(q, ds, strategy, cfg)(),
            lambda: self._evict_query_state(q, ds),
        )

    def _evict_query_state(self, q: Q.GroupByQuery, ds: DataSource):
        """Drop everything a failed dispatch may have poisoned: this query's
        compiled programs and lowering (staged device constants) plus the
        datasource's resident columns (buffers may be orphaned if the
        backend restarted)."""
        base = _query_key(q, ds)
        for k in [k for k in self._query_fn_cache if k[:2] == base]:
            self._query_fn_cache.pop(k)
        self._lowering_cache.pop(base)
        uids = {seg.uid for seg in ds.segments}
        for k in [k for k in self._device_cache if k[0] in uids]:
            self._device_cache.pop(k)
            self._note_resident_drop(k)

    def execute_rollup(
        self, q: Q.GroupByQuery, grouping_sets, ds: DataSource,
        strategy=None, cfg=None,
    ):
        """The rollup form of a grouping-set request: ONE scan grouped by
        the union of the sets' dimensions on the dense path, every set
        reduced from its merged state on the device in one launch before
        the fetch (`finalize.rollup_states`), each set finalized as a
        scan of its own would be.  One frame a set, in order; None where
        `plan.cost.rollup_takes` leaves the request to the per-set form.
        `q` carries the request's dimensions, filter, aggregations and
        post-aggregations; its limit is the caller's."""
        import dataclasses

        from ..resilience import run_device_attempts

        sets = tuple(tuple(s) for s in grouping_sets)
        union = tuple(sorted(set().union(*sets)))
        uq = dataclasses.replace(
            q, dimensions=tuple(q.dimensions[i] for i in union),
            subtotals=(), limit_spec=None,
        )
        strategy = strategy or self.strategy
        with span(SPAN_ROUTE):
            try:
                lowering = self._lowering_for(uq, ds)
            except ValueError:  # no dense domain for the union
                return None
            if not rollup_takes(
                uq, sets, lowering, strategy, cfg or self.config,
                self._device_cache.budget_bytes,
            ):
                return None
        rollup = _Rollup(union, sets, tuple(
            dataclasses.replace(uq, dimensions=tuple(q.dimensions[i] for i in s))
            for s in sets
        ))
        return run_device_attempts(
            self,
            lambda: self._dispatch_groupby_once(
                uq, ds, strategy, cfg, rollup=rollup
            )(),
            lambda: self._evict_query_state(uq, ds),
        )

    @span_around(SPAN_PROGRAM_LOOKUP)
    def _rollup_program(self, uq, ds, lowering, rollup) -> Callable:
        """The rollup's one program for (union query, sets): the union's
        merged state -> each set's state, HLL registers leaving as their
        register histograms (what `exec/state.py estimable` makes on the
        other paths).  Cached under its own "rollup"-tagged key family."""
        union, sets = rollup.union, rollup.sets
        key = _query_key(uq, ds) + ("rollup", union, sets)
        cached = self._query_fn_cache.get(key)
        if cached is not None:
            prof.note_program_cache("rollup", hit=True)
            return cached
        prof.note_program_cache("rollup", hit=False)
        if self._m is not None:
            self._m.program_cache_hit = False
        fire("compile")  # fault-injection site: new program build
        la = lowering.la
        cards = {d: lowering.dims[j].cardinality for j, d in enumerate(union)}

        @jax.jit
        def rollup_fn(state):
            return [
                with_histograms(la, s)
                for s in rollup_states(la, cards, union, sets, state)
            ]

        self._query_fn_cache[key] = rollup_fn
        return rollup_fn

    def execute_groupby_batch(
        self, queries, ds: DataSource, set_labels=None, strategy=None,
        cfg=None,
    ):
        """Execute N GroupBy queries with overlapped device round trips:
        dispatch every query's program first (async), then resolve in
        order, so the fetch latency of query i hides the compute of i+1..N.
        This is what a grouping-set (CUBE/ROLLUP) expansion calls — N
        sequential executions would pay N full round trips.  Per-query transient failures fall back to the normal
        retrying execution path, serially (rare; correctness first).

        `set_labels` (ROADMAP 3(c)): per-query labels for the partial
        collector's per-grouping-set accounting — each sub-query's pass
        archives under its own set instead of erasing its predecessor.
        The batch is one request: `last_metrics` ends as the fold of its
        queries' metrics (`metrics.fold_set_metrics`), every scan counted."""
        from .metrics import fold_set_metrics

        pc = current_partial()

        def _label(i):
            if pc is not None and set_labels is not None:
                pc.set_label = set_labels[i]

        resolves = []
        for i, q in enumerate(queries):
            _label(i)
            try:
                resolves.append(
                    self._dispatch_groupby_once(q, ds, strategy, cfg)
                )
            except NotImplementedError:
                raise
            except RuntimeError as err:
                log.warning(
                    "batch dispatch failed (%s: %s); query will run on the "
                    "serial path", type(err).__name__, err,
                )
                self._evict_query_state(
                    groupby_with_time_granularity(q), ds
                )
                resolves.append(None)
        out, parts = [], []
        for i, (q, resolve) in enumerate(zip(queries, resolves)):
            _label(i)  # sparse/adaptive re-passes attribute to their set
            resolves[i] = None  # release the closure (and its device state)
            if resolve is None:
                out.append(self._execute_groupby(q, ds, strategy, cfg))
                self.last_metrics.retries += 1  # the failed batch dispatch
                parts.append(self.last_metrics)
                continue
            try:
                out.append(resolve())
            except NotImplementedError:
                raise
            except RuntimeError as err:
                log.warning(
                    "transient device failure in batch resolve (%s: %s); "
                    "evicting cached state and re-dispatching once",
                    type(err).__name__, err,
                )
                self._evict_query_state(
                    groupby_with_time_granularity(q), ds
                )
                out.append(self._execute_groupby(q, ds, strategy, cfg))
                self.last_metrics.retries += 1  # the failed batch resolve
            parts.append(self.last_metrics)
        if parts:
            self.last_metrics = fold_set_metrics(parts, len(queries))
        return out

    def _dispatch_groupby_once(
        self, q: Q.GroupByQuery, ds: DataSource, strategy=None, cfg=None,
        rollup: Optional[_Rollup] = None,
    ):
        """Phase 1 of one GroupBy execution: build/launch the device
        programs (async dispatch, no fetch) and return `resolve() -> df`,
        which fetches, finalizes, and publishes metrics.  The synchronous
        path is `self._dispatch_groupby_once(q, ds)()`; batch callers
        dispatch all queries before resolving any.  `strategy` / `cfg`
        and the segment scope are read once, here, and handed down: no
        tier looks at the engine's own, or walks the zone maps again,
        while the request runs.  With `rollup` (a grouping-set request
        `plan.cost.rollup_takes` gave the dense path) resolve first
        reduces the merged state to every set's on the device, fetches
        them together and returns one frame a set."""
        import time as _time

        from .metrics import QueryMetrics

        t_total = _time.perf_counter()
        with span(SPAN_LOWER):
            q = groupby_with_time_granularity(q)
            lowering = self._lowering_for(q, ds)
            segs = self._segments_in_scope(q, ds)
        # learned-memo identity: segment-set independent (memo_key), so a
        # streamed append neither forgets learned rungs nor grows the
        # memo dicts per batch
        qkey = memo_key(q, ds)
        # which tier gets the query: adaptive first (it covers sketch
        # aggs too, and repeats skip its presence pass via the kept-set
        # memo), then the highcard route where phase A sent the query
        # there, then sparse, else the dense partials path at `kernel`
        with span(SPAN_ROUTE):
            strategy = strategy or self.strategy
            kernel = concrete_kernel(strategy, lowering.num_groups)
            eligible = bool(
                segs and lowered_tier_takes("adaptive", lowering, strategy)
            )
            try_adaptive = eligible and qkey not in self._adaptive_declined
            try_sparse = bool(
                segs
                and lowered_tier_takes("sparse", lowering, strategy)
                and qkey not in self._sparse_disabled
            )
        m = self._m = QueryMetrics(
            query_type="groupBy",
            strategy=kernel,
            datasource=ds.name,
            query_id=current_query_id(),
            rows_scanned=sum(s.num_rows for s in segs),
            bytes_scanned=_bytes_scanned(segs, lowering.columns),
            segments=len(segs),
            num_groups=lowering.num_groups,
        )
        if rollup is not None:
            m.grouping_sets, m.set_scans = len(rollup.sets), 1

        # In batch mode resolve() runs long after dispatch, with other
        # queries' fetch+finalize in between — timings anchored at dispatch
        # would absorb all of it.  So: phase 1 records its own elapsed time,
        # and resolve() measures from its own entry (for the synchronous
        # path resolve starts immediately after dispatch, so the split is
        # equivalent to the old dispatch-anchored measurement).
        dispatch_ms = 0.0
        t_resolve = None
        outcome = {"v": "ok"}  # finish() publishes it; except paths set it

        def finish():
            now = _time.perf_counter()
            if t_resolve is not None:
                m.total_ms = dispatch_ms + (now - t_resolve) * 1e3
            else:  # phase-1 failure: resolve never started
                m.total_ms = (now - t_total) * 1e3
            m.bytes_resident = self.bytes_resident()
            # deadline-bounded partial answer: stamp the coverage the
            # collector accounted (partial-result discipline: a
            # partial=True result ALWAYS carries its coverage fraction)
            pc = current_partial()
            if pc is not None and pc.is_partial:
                m.partial = True
                m.coverage = pc.coverage()
                m.rows_seen = pc.rows_seen
                m.delta_rows_seen = pc.delta_rows_seen
                if outcome["v"] == "ok":
                    outcome["v"] = "partial"
            self.last_metrics = m
            self._m = None
            # every completed execution publishes into the process metrics
            # registry (obs/): fleet-level counts + phase histograms
            record_query_metrics(m, outcome["v"])
            log.info("%s", m.describe())

        adaptive_resolve = None
        sparse_resolve = None
        dense_state = None
        try:
            # a None return from the adaptive tier means it declined at
            # dispatch time and the sparse/dense paths proceed
            if try_adaptive:
                adaptive_resolve = self._dispatch_groupby_adaptive(
                    q, ds, lowering, segs, cfg or self.config
                )
                if adaptive_resolve is not None:
                    m.strategy = "adaptive"
            if adaptive_resolve is None and eligible and qkey in self._highcard:
                m.strategy = "highcard"
                dense_state = self._partials_for_query(
                    q, ds, lowering=lowering, strategy_override=kernel,
                    segs=segs, span_attrs={"tier": "highcard"},
                )
            elif adaptive_resolve is None and try_sparse:
                m.strategy = "sparse"
                sparse_resolve = self._dispatch_groupby_sparse(
                    q, ds, lowering, segs
                )
            elif adaptive_resolve is None:
                dense_state = self._partials_for_query(
                    q, ds, lowering=lowering, strategy_override=kernel,
                    segs=segs,
                )
        except BaseException as err:
            from ..resilience import DeadlineExceeded

            if isinstance(err, DeadlineExceeded):
                m.deadline_exceeded = True
                outcome["v"] = "deadline"
            else:
                outcome["v"] = "error"
            finish()
            raise
        dispatch_ms = (_time.perf_counter() - t_total) * 1e3
        # h2d/compile recorded so far belong to the phase-1 dispatch window;
        # anything recorded later (the sparse-declined dense fallback inside
        # resolve) is outside both timing windows and must not be subtracted
        phase1_h2d_ms = m.h2d_ms
        phase1_compile_ms = m.compile_ms

        def resolve():
            nonlocal dense_state, t_resolve
            self._m = m
            t_resolve = _time.perf_counter()
            try:
                # deadline checkpoint between dispatch and the blocking
                # fetch: a budget blown during dispatch cancels before
                # paying the device round trip — unless partials are
                # collected, in which case every batch has already been
                # dispatched and draining the fetch yields the complete
                # answer (is_partial stays False)
                checkpoint_partial("engine.resolve")
                if adaptive_resolve is not None:
                    out = adaptive_resolve()
                    m.device_ms = (
                        (_time.perf_counter() - t_resolve) * 1e3
                        + dispatch_ms
                    )
                    return out
                if sparse_resolve is not None:
                    out, reason = sparse_resolve()
                    if out is not None:
                        m.device_ms = (
                            (_time.perf_counter() - t_resolve) * 1e3
                            + dispatch_ms
                        )
                        return out
                    # the two planned declines, neither a device error
                    # (those raise): "overflow" is deterministic — more
                    # distinct groups than slots — so the query is pinned
                    # off this tier; "declined" is a partial drain with
                    # nothing dispatched
                    if reason == "overflow":
                        self._sparse_disabled.add(qkey)
                    m.strategy = kernel
                    log.warning(
                        "sparse path declined (%s); falling back to %s%s",
                        reason,
                        m.strategy,
                        " (pinned)" if reason == "overflow" else "",
                    )
                    # serial fallback dispatch (rare): sparse declined, so
                    # the dense program launches now
                    dense_state = self._partials_for_query(
                        q, ds, lowering=lowering, strategy_override=kernel,
                        segs=segs,
                    )
                t_fetch = _time.perf_counter()
                dims, la = lowering.dims, lowering.la
                state, dense_state = dense_state, None  # freed promptly
                # state capture (serve/result_cache.py delta-aware reuse)
                # merges the registers later: only without it does the
                # fetch carry HLL register histograms instead.  A
                # grouping-set request is never captured: the cache keeps
                # one query's state
                holder = getattr(self._m_local, "capture", None)
                if rollup is not None:
                    holder = None
                    program = self._rollup_program(q, ds, lowering, rollup)
                    with span(SPAN_SEGMENT_DISPATCH, rollup=len(rollup.sets)):
                        parts = self._call_segment_program(program, state)
                    targets = [
                        (sq, [dims[rollup.union.index(d)] for d in s])
                        for sq, s in zip(rollup.queries, rollup.sets)
                    ]
                else:
                    if holder is None:
                        state = estimable(la, state)
                    parts, targets = [state], [(q, dims)]
                del state
                # ONE device_get for everything: each separate host fetch
                # of a device buffer pays a full round trip; a single
                # pytree fetch pays one.
                with span(SPAN_DEVICE_FETCH):
                    # sampled query: separate device-wait from the host
                    # copy inside the fetch span (obs/prof.py)
                    prof.fetch_sync(parts)
                    parts = to_host(parts)
                m.sketch_state_bytes = sum(sketch_nbytes(p) for p in parts)
                # state capture: stash the merged HOST state for the
                # caller — only on this dense path (sparse/adaptive
                # returned above) and only when the scan was NOT
                # deadline-truncated (a partial state must never seed
                # the cache)
                pc_cap = current_partial()
                if holder is not None and (
                    pc_cap is None or not pc_cap.triggered
                ):
                    holder["state"] = parts[0]
                # the phase-1 dispatch share (minus its h2d/compile) plus
                # this query's own fetch wait is the device time; overlap
                # hidden behind other queries' resolves is deliberately NOT
                # attributed here
                m.device_ms = max(
                    0.0,
                    (_time.perf_counter() - t_fetch) * 1e3
                    + dispatch_ms
                    - phase1_h2d_ms
                    - phase1_compile_ms,
                )
                t0 = _time.perf_counter()
                frames = []
                for (sq, sdims), part in zip(targets, parts):
                    with span(SPAN_FINALIZE):
                        frames.append(finalize_groupby(
                            sq, sdims, la, part, metrics=m
                        ))
                m.finalize_ms = (_time.perf_counter() - t0) * 1e3
                return frames if rollup is not None else frames[0]
            except BaseException as err:
                from ..resilience import DeadlineExceeded

                if isinstance(err, DeadlineExceeded):
                    m.deadline_exceeded = True
                    outcome["v"] = "deadline"
                else:
                    outcome["v"] = "error"
                raise
            finally:
                finish()

        return resolve

    # -- scan / search -------------------------------------------------------

    def _execute_scan(self, q: Q.ScanQuery, ds: DataSource):
        import pandas as pd

        filter_fn = compile_filter(q.filter, ds) if q.filter is not None else None
        vcol_fns = {
            v.name: _decoded_expr_fn(v.expression, ds)
            for v in q.virtual_columns
        }
        order_cols = [c.dimension for c in q.order_by]
        if "__time" in order_cols and not ds.time_column:
            # legacy wire `order` implies time ordering; a timeless table
            # cannot honor it — clean error, not a KeyError from the fetch
            raise Q.QueryValidationError(
                f"scan ordering by __time: datasource {ds.name!r} has no "
                "time column"
            )
        sortable = (
            set(q.columns)
            | {c.name for c in ds.columns}
            | set(vcol_fns)
            | {"__time"}
        )
        for c in order_cols:
            # wire queries arrive unplanned — validate here so a bad
            # orderBy is a clean 400, not a KeyError mid-fetch
            if c not in sortable:
                raise Q.QueryValidationError(
                    f"scan orderBy unknown column {c!r}"
                )
        fetch_list = list(
            dict.fromkeys(list(q.columns) + order_cols)
        )
        need = [c for c in fetch_list if c not in vcol_fns and c != "__time"]
        if q.filter is not None:
            need += [c for c in _filter_columns(q.filter) if c != "__time"]
        for v in q.virtual_columns:
            need += [c for c in v.expression.columns() if c != "__time"]
        if ds.time_column:
            need.append(ds.time_column)
        need = dict.fromkeys(need)
        frames = []
        # early per-segment truncation only when no ordering (an ordered
        # scan must see every surviving row before sorting); with an offset
        # the first `offset` rows still have to be produced before skipping
        remaining = (
            None
            if q.order_by
            else (q.limit + q.offset if q.limit is not None else None)
        )
        scan_segs = self._segments_in_scope(q, ds)
        pc = current_partial()
        if pc is not None:
            pc.begin_pass()
            pc.add_scope(len(scan_segs), *_row_counts(scan_segs))
        # prefetch-only pipeline (reorder=False): scan row order is part
        # of the result contract, so dispatch order stays canonical and
        # only the NEXT segments' columns stream behind the current fetch
        run = self._pipeline.start(
            ds, [[s] for s in scan_segs], list(need), reorder=False
        )
        for pos, seg in enumerate(scan_segs):
            # partial-aware checkpoint: a scan past its deadline returns
            # the rows fetched so far (a row subset IS the scan's natural
            # partial) with a coverage fraction
            if checkpoint_partial("engine.scan_loop"):
                run.cancel()
                break
            cols = self._device_cols(seg, need, ds_name=ds.name)
            run.advance(pos)
            if ds.time_column and ds.time_column in cols:
                cols["__time"] = cols[ds.time_column]
            for name, fn in vcol_fns.items():
                cols[name] = jnp.asarray(fn(cols))
            mask = cols["__valid"]
            if q.intervals:
                t = cols["__time"]
                im = jnp.zeros(t.shape, jnp.bool_)
                for a, b in q.intervals:
                    im = im | ((t >= a) & (t < b))
                mask = mask & im
            if filter_fn is not None:
                mask = mask & filter_fn(cols)
            # one round trip for the mask + all projected columns
            with span(SPAN_DEVICE_FETCH):
                fetched = jax.device_get(
                    {"__mask": mask, **{c: cols[c] for c in fetch_list}}
                )
            keep = fetched.pop("__mask")
            data = {}
            for c in fetch_list:
                arr = fetched[c][keep]
                if c in ds.dicts:
                    arr = ds.dicts[c].decode(arr)
                data[c] = arr
            f = pd.DataFrame(data)
            if remaining is not None:
                f = f.head(remaining)
                remaining -= len(f)
            elif q.order_by and q.limit is not None:
                # ordered + limited: only each segment's top-(limit+offset)
                # can appear in the global result — truncate before concat
                # so a small LIMIT never materializes the whole table
                f = apply_limit_spec(
                    f, Q.LimitSpec(q.limit + q.offset, q.order_by, 0)
                )
            frames.append(f)
            if pc is not None:
                pc.add_seen(1, *_row_counts((seg,)))
            if remaining is not None and remaining <= 0:
                run.cancel()  # LIMIT satisfied: stop prefetch issue too
                break
        out = (
            pd.concat(frames, ignore_index=True)
            if frames
            else pd.DataFrame(columns=fetch_list)
        )
        out = apply_limit_spec(
            out, Q.LimitSpec(q.limit, q.order_by, q.offset)
        )
        return out[list(q.columns)].reset_index(drop=True)

    def _execute_time_boundary(self, q: Q.TimeBoundaryQuery, ds: DataSource):
        """Druid `timeBoundary` — answered from segment metadata (the
        reference learned these bounds from the coordinator, SURVEY.md §3.1);
        no kernel dispatch."""
        import pandas as pd

        iv = ds.interval()
        if iv is None:
            return pd.DataFrame(columns=["minTime", "maxTime"])
        lo, hi = iv
        row = {}
        if q.bound in (None, "minTime"):
            row["minTime"] = np.datetime64(int(lo), "ms")
        if q.bound in (None, "maxTime"):
            row["maxTime"] = np.datetime64(int(hi), "ms")
        return pd.DataFrame([row])

    def _execute_datasource_metadata(
        self, q: "Q.DataSourceMetadataQuery", ds: DataSource
    ):
        """Druid `dataSourceMetadata` — newest ingested event time from
        segment metadata; no kernel dispatch."""
        import pandas as pd

        iv = ds.interval()
        if iv is None:
            return pd.DataFrame(columns=["maxIngestedEventTime"])
        return pd.DataFrame(
            [{"maxIngestedEventTime": np.datetime64(int(iv[1]), "ms")}]
        )

    def _execute_segment_metadata(
        self, q: Q.SegmentMetadataQuery, ds: DataSource
    ):
        """Druid `segmentMetadata` — the catalog rendered per segment (the
        query the reference's metadata cache bootstraps from)."""
        import pandas as pd

        from ..models.filters import _ms_to_iso

        # schema is datasource-level: one columns dict shared by all segments
        cols = {
            c.name: {
                "type": c.kind,
                "dtype": c.dtype,
                "cardinality": c.cardinality,
            }
            for c in ds.columns
        }
        rows = []
        # graftlint: disable=checkpoint-coverage -- segmentMetadata renders catalog dicts, no column data touched
        for seg in self._segments_in_scope(q, ds):
            rows.append(
                {
                    "id": seg.segment_id,
                    "intervals": (
                        [
                            "%s/%s"
                            % (
                                _ms_to_iso(int(seg.interval[0])),
                                _ms_to_iso(int(seg.interval[1])),
                            )
                        ]
                        if seg.interval is not None
                        else []
                    ),
                    "numRows": seg.num_rows,
                    "columns": cols,
                }
            )
        return pd.DataFrame(
            rows, columns=["id", "intervals", "numRows", "columns"]
        )

    def _execute_search(self, q: Q.SearchQuery, ds: DataSource):
        """Dimension-value search: candidate values come from the (host)
        dictionaries, but the Druid wire contract includes a per-value
        `count` of MATCHING ROWS — so rows in scope (intervals, zone maps,
        filter) are counted per code, and zero-count values are omitted,
        exactly like Druid's broker response."""
        import pandas as pd

        # candidate codes come from the host dictionaries FIRST: a needle
        # matching nothing (or nothing beyond earlier dimensions' limit)
        # must not pay a row scan
        needle = q.query.lower()
        matching = {
            dim: [
                code
                for code, v in enumerate(ds.dicts[dim].values)
                if needle in str(v).lower()
            ]
            for dim in q.dimensions
        }
        live_dims = [d for d in q.dimensions if matching[d]]
        if not live_dims:
            return pd.DataFrame(columns=["dimension", "value", "count"])
        segs = self._segments_in_scope(q, ds)
        fmask_fn = (
            compile_filter(q.filter, ds) if q.filter is not None else None
        )
        counts = {
            dim: np.zeros(ds.dicts[dim].cardinality, np.int64)
            for dim in live_dims
        }
        pc = current_partial()
        if pc is not None:
            pc.begin_pass()
            pc.add_scope(len(segs), *_row_counts(segs))
        for seg in segs:
            # per-segment filter evaluation + bincount is real work on a
            # wide segment: honor the deadline between segments (partial
            # counts over the segments seen so far are a safe answer)
            if checkpoint_partial("engine.search_loop"):
                break
            base = np.asarray(seg.valid)
            if q.intervals and seg.time is not None:
                t = np.asarray(seg.time)
                im = np.zeros(base.shape, bool)
                for a, b in q.intervals:
                    im |= (t >= a) & (t < b)
                base = base & im
            if fmask_fn is not None:
                # ride the residency cache (transfer-discipline/GL19xx):
                # repeated searches hit instead of re-moving the filter
                # columns every time
                cols = self._device_cols(
                    seg, list(_filter_columns(q.filter)), ds_name=ds.name
                )
                base = base & np.asarray(fmask_fn(cols))
            for dim in live_dims:
                sel = np.asarray(seg.dims[dim])[base]
                sel = sel[sel >= 0]
                counts[dim] += np.bincount(
                    sel, minlength=len(counts[dim])
                )
            if pc is not None:
                pc.add_seen(1, *_row_counts((seg,)))
        rows = []
        for dim in live_dims:
            if len(rows) >= q.limit:
                break
            d = ds.dicts[dim]
            for code in matching[dim]:
                if counts[dim][code] > 0:
                    rows.append(
                        {
                            "dimension": dim,
                            "value": d.values[code],
                            "count": int(counts[dim][code]),
                        }
                    )
                    if len(rows) >= q.limit:
                        break
        return pd.DataFrame(rows, columns=["dimension", "value", "count"])

    # -- progressive execution (chunked refinement, ISSUE 7 tentpole (b)) ----

    def execute_progressive(self, q: Q.QuerySpec, ds: DataSource, strategy=None):
        """Generator of progressively-refined results for one aggregate
        query: after each segment-batch dispatch the running partial
        state is fetched and finalized, yielding `(df, info)` where
        `info` carries {"sequence", "coverage", "rows_seen", "rows_total",
        "final"}.  The LAST emission is the exact answer (coverage 1.0)
        — unless an armed deadline expires mid-scan, in which case the
        last emission is the best-effort partial, flagged via
        info["partial"]=True.

        Interactive exploration over SF100 sees the first refinement
        after ONE batch (milliseconds of scan) and watches the answer
        converge; the per-batch device fetch + finalize is the price of
        visibility, so this path is opt-in (`context.progressive` on the
        wire).  Non-aggregate query types have no mergeable state to
        refine: they execute normally and emit once."""
        inner, shape = groupby_family(q, ds)
        if inner is None:
            df = self.execute(q, ds)
            # no mergeable state to refine, but execute() can still have
            # drained to a deadline partial (e.g. the scan loop under an
            # armed collector): the single emission must carry the real
            # partial/coverage stamp, not claim exactness (GL16xx)
            info = {
                "sequence": 0, "coverage": 1.0, "final": True,
                "partial": False,
            }
            pc = current_partial()
            if pc is not None and pc.is_partial:
                d = pc.to_dict()
                info.update(
                    partial=True, coverage=d["coverage"],
                    rows_seen=d["rows_seen"], rows_total=d["rows_total"],
                )
            yield df, info
            return

        import time as _time

        from .metrics import QueryMetrics

        t0 = _time.perf_counter()
        inner = groupby_with_time_granularity(inner)
        with span(SPAN_LOWER):
            lowering = self._lowering_for(inner, ds)
            segs = self._segments_in_scope(inner, ds)
        dims, la, G = lowering.dims, lowering.la, lowering.num_groups
        need = lowering.columns
        rows_total, delta_total = _row_counts(segs)
        kernel = concrete_kernel(strategy or self.strategy, G)
        m = self._m = QueryMetrics(
            query_type="progressive",
            strategy=kernel,
            datasource=ds.name,
            query_id=current_query_id(),
            rows_scanned=rows_total,
            segments=len(segs),
            num_groups=G,
        )
        pc = current_partial()
        if pc is not None:
            pc.begin_pass()
            pc.add_scope(len(segs), rows_total, delta_total)
        state = None
        rows_seen = 0
        seen_segs = 0
        seq = 0
        truncated = False
        n_theta = len(_theta_aggs(la))
        try:
            if segs:
                seg_fn = self._segment_program(
                    inner, ds, lowering, strategy_override=kernel
                )
                batches = list(self._segment_batches(segs, need))
                # prefetch-only (reorder=False): the refinement sequence
                # is user-visible, so batches dispatch in canonical order
                # while the next batches' columns stream behind compute
                run = self._pipeline.start(ds, batches, need, reorder=False)
                for bi, batch in enumerate(batches):
                    if checkpoint_partial("engine.progressive_loop"):
                        run.cancel()
                        truncated = True
                        break
                    state = merge(la, state, self._dispatch_batch(
                        seg_fn, ds, need, batch, bi, run, bi, pc, n_theta,
                        {},
                    ))
                    rows_seen += _row_counts(batch)[0]
                    seen_segs += len(batch)
                    final = bi + 1 == len(batches)
                    with span(SPAN_DEVICE_FETCH, batch=bi):
                        # graftlint: disable=trace-purity -- per-batch fetch IS progressive streaming: each refinement ships the running state to the client
                        host = to_host(state)
                    with span(SPAN_FINALIZE, batch=bi):
                        df = shape(finalize_groupby(inner, dims, la, host))
                    yield df, {
                        "sequence": seq,
                        "coverage": (
                            rows_seen / rows_total if rows_total else 1.0
                        ),
                        "rows_seen": rows_seen,
                        "rows_total": rows_total,
                        "segments_seen": seen_segs,
                        "segments_total": len(segs),
                        "final": final,
                        "partial": False,
                    }
                    seq += 1
            if not segs or truncated or state is None:
                # empty scope, or a deadline cut the scan short: emit the
                # (possibly empty) merged state as the final answer with
                # its honest coverage
                if state is None:
                    state = PartialState.empty(la, G)
                host = to_host(state)
                with span(SPAN_FINALIZE):
                    df = shape(finalize_groupby(inner, dims, la, host))
                cov = rows_seen / rows_total if rows_total else (
                    None if truncated else 1.0
                )
                yield df, {
                    "sequence": seq,
                    "coverage": cov,
                    "rows_seen": rows_seen,
                    "rows_total": rows_total,
                    "segments_seen": seen_segs,
                    "segments_total": len(segs),
                    "final": True,
                    "partial": truncated,
                }
        finally:
            m.total_ms = (_time.perf_counter() - t0) * 1e3
            if pc is not None and pc.is_partial:
                m.partial = True
                m.coverage = pc.coverage()
                m.rows_seen = pc.rows_seen
                m.delta_rows_seen = pc.delta_rows_seen
            self.last_metrics = m
            self._m = None
            record_query_metrics(
                m, "partial" if m.partial else "ok"
            )


