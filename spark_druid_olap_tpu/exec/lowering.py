"""Query lowering: dimensions, aggregations, and the row-kernel ABI.

Split out of exec/engine.py (it had become a god-module — VERDICT r1 weak
#8).  This module holds everything that turns a QuerySpec x DataSource into
device-executable pieces, shared by the local engine (exec/engine.py), the
distributed engine (parallel/distributed.py), and the streaming executor
(exec/streaming.py):

* dimension resolution (dictionary remaps, time bucketing, extractions),
* aggregation lowering into the kernel ABI merge classes,
* `GroupByLowering` (columns, row_arrays, filter mask),
* query-shape rewrites (Timeseries/TopN -> GroupBy, implicit granularity),
* program/state cache identity (`_query_key`, `schema_signature`).

Reference parity: the planning-side counterpart of Druid's per-segment query
engine setup (SURVEY.md §3.3 `[U]`): what the reference serializes into query
JSON for Druid to interpret, we lower into jit-traceable closures.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Mapping, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from ..catalog.segment import DataSource
from ..models import aggregations as A
from ..models import query as Q
from ..models.dimensions import DimensionSpec
from ..models.filters import Filter
from ..obs import SCOPE_AGG_INPUTS, SCOPE_FILTER, SCOPE_GROUP_KEYS, device_scope
from ..ops.filters import DecodedView, compile_filter
from ..ops.groupby import combine_group_ids
from ..plan.expr import compile_expr
from ..utils.granularity import bucket_starts

# ---------------------------------------------------------------------------
# Dimension resolution
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ResolvedDim:
    """A dimension lowered to: device code producer + cardinality + decoder."""

    spec: DimensionSpec
    cardinality: int  # including the null slot when present
    codes_fn: Callable[[Mapping[str, jnp.ndarray]], jnp.ndarray]
    decode: Callable[[np.ndarray], np.ndarray]  # codes -> python values


def _resolve_dims(
    dims: Sequence[DimensionSpec],
    ds: DataSource,
    intervals: Tuple[Tuple[int, int], ...],
) -> List[ResolvedDim]:
    out: List[ResolvedDim] = []
    for spec in dims:
        if spec.dimension == "__time" or spec.granularity is not None:
            out.append(_resolve_time_dim(spec, ds, intervals))
            continue
        d = ds.dicts[spec.dimension]
        if spec.extraction is not None:
            # Host-side dictionary rewrite: apply fn to each dict value once,
            # build remap table code -> new code (SURVEY.md dimension-spec row).
            # Extraction fns are string fns; numeric dictionaries stringify.
            extracted = spec.extraction.apply_to_dict(
                [v if isinstance(v, str) else str(v) for v in d.values]
            )
            # extraction fns may emit None (lookup with no retain/replace):
            # those values fold into the null slot
            new_vals = sorted({v for v in extracted if v is not None})
            index = {v: i for i, v in enumerate(new_vals)}
            card = len(new_vals) + 1  # + null slot
            remap = np.array(
                [
                    index[v] if v is not None else card - 1
                    for v in extracted
                ],
                dtype=np.int32,
            )
            remap_dev = jnp.asarray(remap)
            name = spec.dimension

            def codes_fn(cols, remap_dev=remap_dev, name=name, card=card):
                c = cols[name]
                return jnp.where(c >= 0, remap_dev[jnp.maximum(c, 0)],
                                 jnp.int32(card - 1))

            vals_arr = np.asarray(new_vals, dtype=object)

            def decode(codes, vals_arr=vals_arr, card=card):
                o = np.empty(len(codes), dtype=object)
                isnull = codes == card - 1
                o[~isnull] = vals_arr[codes[~isnull]]
                o[isnull] = None
                return o

            out.append(ResolvedDim(spec, card, codes_fn, decode))
        else:
            card = d.cardinality + 1  # last slot = null
            name = spec.dimension

            def codes_fn(cols, name=name, card=card):
                c = cols[name]
                return jnp.where(c >= 0, c, jnp.int32(card - 1))

            vals_arr = np.asarray(d.values, dtype=object)

            def decode(codes, vals_arr=vals_arr, card=card):
                o = np.empty(len(codes), dtype=object)
                isnull = codes == card - 1
                o[~isnull] = vals_arr[codes[~isnull]]
                o[isnull] = None
                return o

            out.append(ResolvedDim(spec, card, codes_fn, decode))
    return out


def _resolve_time_dim(
    spec: DimensionSpec, ds: DataSource, intervals
) -> ResolvedDim:
    gran = spec.granularity or "all"
    iv = intervals[0] if intervals else ds.interval()
    if iv is None:
        raise ValueError("time-bucketed dimension requires a time column")
    lo, hi = iv
    if intervals:
        lo = min(a for a, _ in intervals)
        hi = max(b for _, b in intervals)
        # open-ended predicate intervals (t >= x -> hi = 2^62) would expand
        # the bucket table unboundedly; the data's own range bounds it
        dsiv = ds.interval()
        if dsiv is not None:
            lo = max(lo, dsiv[0])
            hi = max(lo, min(hi, dsiv[1]))
    starts = bucket_starts(lo, hi, gran)  # host-computed bucket boundaries
    card = len(starts)
    starts_dev = jnp.asarray(starts)

    from ..utils.granularity import granularity_period_ms

    period = granularity_period_ms(gran) if gran.lower() != "all" else None

    def bucket_idx(t, first=int(starts[0]), period=period,
                   starts_dev=starts_dev, card=card):
        if period is not None:
            # FIXED-period granularity (minute/hour/day/week): plain
            # integer arithmetic — one fused op instead of searchsorted's
            # log-N scan passes (~135 ms per 2M-row chunk on CPU).
            # Out-of-range rows clip into the edge buckets; the interval
            # row-mask already excludes them.
            return jnp.clip((t - first) // period, 0, card - 1).astype(
                jnp.int32
            )
        # calendar granularities (month/quarter/year): boundaries are
        # irregular — searchsorted over the host-computed starts
        return (
            jnp.searchsorted(starts_dev, t, side="right").astype(jnp.int32)
            - 1
        )

    if spec.extraction is not None:
        # EXTRACT-style dims: many buckets fold to one extracted value
        # (e.g. MONTH over 3 years: 36 buckets -> 12 groups).  Host-side
        # remap over bucket starts; the kernel adds one tiny gather.
        extracted = spec.extraction.apply_to_dict([int(s) for s in starts])
        new_vals = sorted(set(extracted))
        index = {v: i for i, v in enumerate(new_vals)}
        remap_dev = jnp.asarray(
            np.array([index[v] for v in extracted], dtype=np.int32)
        )

        def codes_fn(cols, remap_dev=remap_dev):
            b = bucket_idx(cols["__time"])
            return remap_dev[jnp.clip(b, 0, remap_dev.shape[0] - 1)]

        vals_arr = np.asarray(new_vals, dtype=object)

        def decode(codes, vals_arr=vals_arr):
            return vals_arr[np.clip(codes, 0, len(vals_arr) - 1)]

        return ResolvedDim(spec, len(new_vals), codes_fn, decode)

    def codes_fn(cols):
        return bucket_idx(cols["__time"])

    starts_np = np.asarray(starts)

    def decode(codes, starts_np=starts_np):
        ms = starts_np[np.clip(codes, 0, len(starts_np) - 1)]
        return ms.astype("datetime64[ms]")

    return ResolvedDim(spec, card, codes_fn, decode)


# ---------------------------------------------------------------------------
# Aggregation lowering
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class LoweredAggs:
    """Aggregations split by merge class for the kernel ABI.

    Layout contract with ops/groupby.py: sum-class aggs (psum merges) are the
    columns of `sum_values`; min-class then max-class are the columns of
    `minmax_values`.  Column 0 of sum_values is always the hidden `__rows`
    presence counter."""

    sum_names: List[str]
    min_names: List[str]
    max_names: List[str]
    sketch_aggs: List[A.Aggregation]
    long_valued: Dict[str, bool]
    value_fns: Dict[str, Callable]  # name -> fn(cols) -> f32[R]
    mask_fns: Dict[str, Optional[Callable]]  # name -> extra-mask fn or None
    count_like: set = dataclasses.field(default_factory=set)  # COUNT aggs
    # agg name -> existing sum column it READS instead of owning one: an
    # unfiltered COUNT(*) is exactly the hidden __rows presence counter,
    # and a duplicate all-ones scatter column is pure waste (the scatter
    # cost scales with the column count)
    aliased: Dict[str, str] = dataclasses.field(default_factory=dict)
    # sum name -> the stored metric column it sums as it is (no expression,
    # no dictionary decode): the Pallas kernel reads that column itself
    stored: Dict[str, str] = dataclasses.field(default_factory=dict)


def _lower_aggs(
    aggs: Sequence[A.Aggregation], ds: DataSource
) -> LoweredAggs:
    la = LoweredAggs(["__rows"], [], [], [], {"__rows": True}, {}, {})
    la.value_fns["__rows"] = lambda cols: None  # ones; handled specially
    la.mask_fns["__rows"] = None

    def add(agg: A.Aggregation, extra_filter: Optional[Filter]):
        mask_fn = (
            compile_filter(extra_filter, ds) if extra_filter is not None else None
        )
        if isinstance(agg, A.FilteredAgg):
            inner_mask = compile_filter(agg.filter, ds)
            if mask_fn is None:
                combined = inner_mask
            else:
                outer = mask_fn
                combined = lambda cols: outer(cols) & inner_mask(cols)
            _add_base(agg.aggregator, combined)
            return
        _add_base(agg, mask_fn)

    def _add_base(agg: A.Aggregation, mask_fn):
        name = agg.name
        la.mask_fns[name] = mask_fn
        if isinstance(agg, A.Count):
            la.long_valued[name] = True
            la.count_like.add(name)
            if mask_fn is None:
                la.aliased[name] = "__rows"  # reuse the presence counter
                return
            la.sum_names.append(name)
            la.value_fns[name] = lambda cols: None  # ones
        elif isinstance(agg, (A.LongSum, A.DoubleSum)):
            field = agg.field_name
            la.sum_names.append(name)
            la.long_valued[name] = isinstance(agg, A.LongSum)
            la.value_fns[name] = _field_value_fn(field, ds)
            _add_null_skip(la, name, field, ds)
            if field not in ds.dicts:
                la.stored[name] = field
        elif isinstance(agg, (A.LongMin, A.DoubleMin)):
            field = agg.field_name
            la.min_names.append(name)
            la.long_valued[name] = isinstance(agg, A.LongMin)
            la.value_fns[name] = _field_value_fn(field, ds)
            _add_null_skip(la, name, field, ds)
        elif isinstance(agg, (A.LongMax, A.DoubleMax)):
            field = agg.field_name
            la.max_names.append(name)
            la.long_valued[name] = isinstance(agg, A.LongMax)
            la.value_fns[name] = _field_value_fn(field, ds)
            _add_null_skip(la, name, field, ds)
        elif isinstance(agg, A.DimCodeMax):
            # FD grouping pruning: max over raw dictionary codes (all rows
            # of a group share one code by the declared FD); decoded back
            # to the value at the API layer.  Codes < 2^24 represent
            # exactly in f32; null rows carry -1 and never win the max
            # unless the whole group is null (-1 decodes back to null)
            field = agg.field_name
            la.max_names.append(name)
            la.long_valued[name] = True
            la.value_fns[name] = lambda cols, f=field: jnp.asarray(
                cols[f]
            ).astype(jnp.float32)
        elif isinstance(agg, A.ExpressionAgg):
            fn = compile_expr(agg.expression, ds.dicts)
            target = {
                "doubleSum": la.sum_names,
                "longSum": la.sum_names,
                "doubleMin": la.min_names,
                "doubleMax": la.max_names,
            }[agg.base]
            target.append(name)
            la.long_valued[name] = agg.base == "longSum"
            dicts = ds.dicts
            la.value_fns[name] = lambda cols, fn=fn, dicts=dicts: jnp.asarray(
                fn(DecodedView(cols, dicts))
            ).astype(jnp.float32)
        elif isinstance(
            agg,
            (A.HyperUnique, A.CardinalityAgg, A.ThetaSketch, A.QuantilesSketch),
        ):
            la.sketch_aggs.append(agg)
            la.long_valued[name] = True
        else:
            raise NotImplementedError(f"aggregation {type(agg).__name__}")

    for agg in aggs:
        add(agg, None)
    return la


def _field_value_fn(field: str, ds: DataSource):
    """Value reader for sum/min/max: metric columns pass through; numeric-
    dictionary dimension columns decode rank codes back to values (so
    sum(d_year)-style aggregates see years, not ranks)."""
    d = ds.dicts.get(field) if hasattr(ds.dicts, "get") else None
    if d is not None and d.numeric_values is not None:
        dicts = ds.dicts
        return lambda cols, field=field, dicts=dicts: DecodedView(cols, dicts)[
            field
        ].astype(jnp.float32)
    return lambda cols, field=field: cols[field].astype(jnp.float32)


def _add_null_skip(la: LoweredAggs, name: str, field: str, ds: DataSource):
    """SQL aggregates skip NULLs: for a dictionary-dimension field, rows with
    a null code (-1) must not contribute (they'd otherwise decode to -1 and
    poison SUM/MIN/MAX).  Metrics have no null representation — no-op."""
    d = ds.dicts.get(field) if hasattr(ds.dicts, "get") else None
    if d is None:
        return
    nm = lambda cols, field=field: cols[field] >= 0
    prev = la.mask_fns.get(name)
    la.mask_fns[name] = (
        nm if prev is None else lambda cols, p=prev, nm=nm: p(cols) & nm(cols)
    )


# ---------------------------------------------------------------------------
# Query lowering (shared by the local engine and parallel/distributed.py)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class GroupByLowering:
    """A GroupByQuery lowered to device-executable pieces:

    * `columns` — physical columns to fetch per segment
    * `row_arrays(cols)` — pure, jit/shard_map-traceable row-wise kernel
      producing (gid, mask, sum_values, minmax_values, minmax_masks)
    * `dims` / `la` / `num_groups` — the finalization contract
    """

    query: Q.GroupByQuery
    dims: List[ResolvedDim]
    la: LoweredAggs
    num_groups: int
    columns: List[str]
    filter_fn: Optional[Callable]
    vcol_fns: Dict[str, Callable]
    # vcol names that are ALSO read by a vcol expression (physical shadow)
    shadowed_inputs: frozenset = frozenset()

    @device_scope(SCOPE_AGG_INPUTS)
    def add_virtual(self, cols: Dict[str, jnp.ndarray]) -> Dict[str, jnp.ndarray]:
        """Compute virtual columns from the PHYSICAL inputs.  Idempotent:
        a virtual column that shadows a physical column it reads saves the
        physical values under __phys__<name>, so a second application (the
        engine calls this once for sketches and once in row_arrays)
        recomputes from the same inputs instead of compounding."""
        if not self.vcol_fns:
            return cols
        inputs = dict(cols)
        # restore/save ALL physical shadows before any compute: a vcol
        # declared before a later-declared shadow still reads the
        # physical values on a second application
        for name in self.shadowed_inputs:
            phys = "__phys__" + name
            if phys in cols:
                inputs[name] = cols[phys]
            elif name in cols:
                cols[phys] = cols[name]
        for name, fn in self.vcol_fns.items():  # declaration order
            out = jnp.asarray(fn(inputs))
            cols[name] = out
            if name not in self.shadowed_inputs:
                # chained vcols: a LATER vcol may read this output; a
                # shadowed name keeps exposing its physical values to
                # vcol expressions instead
                inputs[name] = out
        return cols

    @device_scope(SCOPE_FILTER)
    def row_mask(self, cols) -> jnp.ndarray:
        mask = cols["__valid"]
        q = self.query
        if q.intervals:
            t = cols["__time"]
            im = jnp.zeros(t.shape, jnp.bool_)
            for a, b in q.intervals:
                im = im | ((t >= a) & (t < b))
            mask = mask & im
        if self.filter_fn is not None:
            mask = mask & self.filter_fn(cols)
        return mask

    def row_arrays(
        self,
        cols: Dict[str, jnp.ndarray],
        mask: Optional[jnp.ndarray] = None,
        gid: Optional[jnp.ndarray] = None,
        strategy: Optional[str] = None,
    ):
        """cols: name -> row-aligned device array (must include "__valid",
        and "__time" when the query touches time).  Returns the kernel ABI
        tuple for ops/groupby.py.

        `mask`/`gid` accept PRECOMPUTED row pipelines: the fused-batch
        common-subexpression pass (serve/fusion.shared_row_plan) computes
        the filter mask / group-id pipeline once per segment for members
        whose (virtualColumns, filter, intervals) / (virtualColumns,
        dimensions) sub-lowerings are identical, instead of re-tracing
        them per member inside the fused program.

        `strategy` is the kernel `plan/cost.py` chose for the call the
        tuple goes to.  The XLA one-hot scan and the scatter (and a call
        that names none) get `sum_values` as `f32[R, Ms]`, every column
        multiplied by the row mask: their one-hot and their trash slot
        need it.  "pallas" masks by the group id alone
        (`ops/pallas_groupby.py`) and gets one entry a sum column instead:
        the value's `[R]` row NOT multiplied by the row mask (a stored
        float32 / int32 metric column as it lies resident, no fusion in
        front of the kernel), or `None` where the column counts the kept
        rows.  An aggregator's own mask is multiplied in for either."""
        cols = dict(cols)
        self.add_virtual(cols)
        if mask is None:
            mask = self.row_mask(cols)
        la = self.la
        if gid is None:
            with device_scope(SCOPE_GROUP_KEYS):
                gid, _ = combine_group_ids(
                    [d.codes_fn(cols) for d in self.dims],
                    [d.cardinality for d in self.dims],
                )
                if not self.dims:
                    gid = jnp.zeros(mask.shape, jnp.int32)
        with device_scope(SCOPE_AGG_INPUTS):
            R = mask.shape[0]
            if strategy == "pallas":
                sum_values = [self._unmasked_sum_row(n, cols) for n in la.sum_names]
            else:
                maskf = mask.astype(jnp.float32)
                sum_cols = []
                for n in la.sum_names:
                    base = (
                        la.value_fns[n](cols)
                        if la.value_fns[n] is not None else None
                    )
                    v = maskf if base is None else base * maskf
                    mfn = la.mask_fns.get(n)
                    if mfn is not None:
                        v = v * mfn(cols).astype(jnp.float32)
                    sum_cols.append(v)
                sum_values = jnp.stack(sum_cols, axis=1)
            mm_names = la.min_names + la.max_names
            if mm_names:
                mm_vals, mm_masks = [], []
                for n in mm_names:
                    mm_vals.append(la.value_fns[n](cols))
                    mfn = la.mask_fns.get(n)
                    mm_masks.append(
                        mfn(cols) if mfn is not None
                        else jnp.ones((R,), jnp.bool_)
                    )
                minmax_values = jnp.stack(mm_vals, axis=1)
                minmax_masks = jnp.stack(mm_masks, axis=1)
            else:
                minmax_values = jnp.zeros((R, 0), jnp.float32)
                minmax_masks = jnp.zeros((R, 0), jnp.bool_)
        return gid, mask, sum_values, minmax_values, minmax_masks

    def _unmasked_sum_row(self, name: str, cols) -> Optional[jnp.ndarray]:
        """Sum column `name` as the Pallas kernel takes it (`row_arrays`)."""
        la = self.la
        stored = cols.get(la.stored.get(name))
        if stored is not None and stored.dtype in (jnp.float32, jnp.int32):
            base = stored
        else:
            base = la.value_fns[name](cols)  # None: a count
        mfn = la.mask_fns.get(name)
        if mfn is None:
            return base
        own = mfn(cols).astype(jnp.float32)
        return own if base is None else base.astype(jnp.float32) * own


def _query_key(q: Q.QuerySpec, ds: DataSource) -> Tuple:
    """Identity of (query, datasource-schema) for program/state caches —
    single definition so every cache keys the same way."""
    import json as _json

    return (
        _json.dumps(q.to_druid(), sort_keys=True, default=str),
        schema_signature(ds),
    )


def schema_signature(ds: DataSource) -> Tuple:
    """Identity of a datasource's schema for program caches: name + per-column
    kind/cardinality + dictionary content + segment ids.  Dictionary content
    matters because rank codes are data-dependent: re-ingesting a same-name
    datasource with an equal-cardinality but different value domain must MISS
    the cache (compiled filters bake in literal->code translations)."""
    return (
        ds.name,
        _dict_signature(ds),
        tuple(s.uid for s in ds.segments),
    )


def _dict_signature(ds: DataSource) -> Tuple:
    return tuple(
        (
            c.name,
            c.kind,
            c.cardinality,
            ds.dicts[c.name].content_key if c.name in ds.dicts else None,
        )
        for c in ds.columns
    )


def memo_key(q: Q.QuerySpec, ds: DataSource) -> Tuple:
    """Segment-set-INDEPENDENT identity of (query, datasource schema) for
    the engine's LEARNED memos (sparse capacity rungs, adaptive kept
    sets, sparse-overflow pins).  Unlike `_query_key`, the segment uid
    tuple is excluded: a streamed append publishes a new segment set
    every batch, and keying memos on uids would (a) forget every learned
    rung per append and (b) grow the memo dicts without bound under
    continuous ingest.  Dictionary content stays in the key — a
    dictionary extension changes cardinalities/code meanings, which is
    exactly when a learned rung goes stale."""
    import json as _json

    return (
        _json.dumps(q.to_druid(), sort_keys=True, default=str),
        ds.name,
        _dict_signature(ds),
    )


def timeseries_to_groupby(q: Q.TimeseriesQuery) -> Q.GroupByQuery:
    """Shared Timeseries->GroupBy rewrite (a Timeseries is a GroupBy whose
    only dimension is the time bucket) — used by both engines so semantics
    cannot drift."""
    return Q.GroupByQuery(
        datasource=q.datasource,
        dimensions=(
            DimensionSpec(
                "__time", q.output_name, granularity=q.granularity
            ),
        ),
        aggregations=q.aggregations,
        post_aggregations=q.post_aggregations,
        filter=q.filter,
        intervals=q.intervals,
        virtual_columns=q.virtual_columns,
    )


def topn_to_groupby(q: Q.TopNQuery) -> Q.GroupByQuery:
    """Shared TopN->GroupBy rewrite (exact TopN: full groupby then rank;
    Druid's native TopN is approximate — ours is exact and still one kernel)."""
    return Q.GroupByQuery(
        datasource=q.datasource,
        dimensions=(q.dimension,),
        aggregations=q.aggregations,
        post_aggregations=q.post_aggregations,
        filter=q.filter,
        intervals=q.intervals,
        granularity=q.granularity,
        virtual_columns=q.virtual_columns,
    )


def cached_lowering(cache, q: Q.GroupByQuery, ds: DataSource) -> "GroupByLowering":
    """Shared lowering-cache lookup (local + distributed engines): lowering
    stages device constants, so rebuilding it per execution pays one blocking
    H2D transfer per constant."""
    key = _query_key(q, ds)
    lowering = cache.get(key)
    if lowering is None:
        lowering = lower_groupby(q, ds)
        cache[key] = lowering
    return lowering


def lower_groupby(q: Q.GroupByQuery, ds: DataSource) -> GroupByLowering:
    dims = _resolve_dims(q.dimensions, ds, q.intervals)
    la = _lower_aggs(q.aggregations, ds)
    G = 1
    for d in dims:
        G *= d.cardinality
    if G > (1 << 26):
        raise ValueError(
            f"combined group cardinality {G} too large for dense domain; "
            "sort-based path not yet wired for this size"
        )
    filter_fn = compile_filter(q.filter, ds) if q.filter is not None else None
    vcol_fns = {
        v.name: _decoded_expr_fn(v.expression, ds) for v in q.virtual_columns
    }
    # Shadowing a VALUE-SPACE (metric/numeric) column is supported: every
    # consumer reads plain values.  Shadowing a dictionary-encoded
    # dimension is REFUSED: filters/aggs/dims on dictionary names compile
    # into code space, and a value-space virtual array under that name
    # would be silently mis-evaluated (refuse rather than be wrong).
    for v in q.virtual_columns:
        if v.name in ds.dicts:
            raise ValueError(
                f"virtual column {v.name!r} shadows dictionary-encoded "
                f"dimension {v.name!r} of {ds.name!r}: filters and "
                "groupings on dictionary dimensions evaluate in code "
                "space, so the shadow cannot be honored soundly.  Name "
                "the virtual column differently."
            )
    vcol_inputs = {
        c for v in q.virtual_columns for c in v.expression.columns()
    }
    phys_names = {c.name for c in ds.columns}
    return GroupByLowering(
        q,
        dims,
        la,
        G,
        _needed_columns(q, ds, dims),
        filter_fn,
        vcol_fns,
        shadowed_inputs=frozenset(vcol_fns) & vcol_inputs & phys_names,
    )


def _decoded_expr_fn(expression, ds: DataSource):
    """Compile an expression so dimension references read decoded values."""
    fn = compile_expr(expression, ds.dicts)
    dicts = ds.dicts
    return lambda cols, fn=fn, dicts=dicts: fn(DecodedView(cols, dicts))


def _needed_columns(q, ds: DataSource, dims) -> List[str]:
    names: List[str] = []
    for d in dims:
        if d.spec.dimension != "__time" and d.spec.granularity is None:
            names.append(d.spec.dimension)
    for a in q.aggregations:
        names.extend(_agg_columns(a))
    if q.filter is not None:
        names.extend(_filter_columns(q.filter))
    for v in q.virtual_columns:
        names.extend(v.expression.columns())
    virt = {v.name for v in q.virtual_columns}
    # A name produced by a virtual column is not fetched — UNLESS it is a
    # SHADOW: a physical column that a vcol expression also reads (the vcol
    # computes from the physical values, every other consumer reads the
    # virtual ones).  A vcol name read only by ANOTHER vcol (chained
    # virtual columns) is not physical and must not be fetched.
    phys = {c.name for c in ds.columns}
    vcol_inputs = {
        c for v in q.virtual_columns for c in v.expression.columns()
    }
    shadows = virt & vcol_inputs & phys
    need = [
        n
        for n in dict.fromkeys(names)
        if (n not in virt or n in shadows) and n != "__time"
    ]
    if ds.time_column and (
        any(d.spec.dimension == "__time" or d.spec.granularity for d in dims)
        or q.intervals
        or "__time" in names
    ):
        need.append(ds.time_column)
    return need


def groupby_with_time_granularity(q: Q.GroupByQuery) -> Q.GroupByQuery:
    """Druid semantics shared by all executors: a non-'all' granularity on
    GroupBy adds an implicit leading time-bucket dimension (one result row
    per bucket per group)."""
    if q.granularity in ("all", None) or any(
        d.dimension == "__time" or d.granularity for d in q.dimensions
    ):
        return q
    return dataclasses.replace(
        q,
        dimensions=(
            DimensionSpec("__time", "timestamp", granularity=q.granularity),
        )
        + tuple(q.dimensions),
        granularity="all",
    )


def _agg_columns(a: A.Aggregation) -> List[str]:
    if isinstance(a, A.FilteredAgg):
        return _filter_columns(a.filter) + _agg_columns(a.aggregator)
    if isinstance(a, A.ExpressionAgg):
        return list(a.expression.columns())
    if isinstance(a, A.Count):
        return []
    if isinstance(a, A.CardinalityAgg):
        return list(a.field_names)
    return [a.field_name]  # type: ignore[attr-defined]


def _filter_columns(f: Filter) -> List[str]:
    from ..models import filters as F

    if isinstance(f, (F.Selector, F.InFilter, F.Bound, F.Regex, F.LikeFilter)):
        return [f.dimension]
    if isinstance(f, (F.And, F.Or)):
        out: List[str] = []
        for x in f.fields:
            out.extend(_filter_columns(x))
        return out
    if isinstance(f, F.Not):
        return _filter_columns(f.field)
    if isinstance(f, F.IntervalFilter):
        return ["__time"] if f.dimension == "__time" else [f.dimension]
    if isinstance(f, F.ExpressionFilter):
        return list(f.expression.columns())
    return []
