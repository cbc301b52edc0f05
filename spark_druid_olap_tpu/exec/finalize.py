"""Host-side result finalization (the broker-merge tail of SURVEY.md §3.3).

Split out of exec/engine.py (VERDICT r1 weak #8).  Everything that turns
merged partial aggregate state into the result DataFrame — group-id decode,
post-aggregations, having, sort/limit, empty-bucket fill, TopN ranking — plus
the rollup of a grouping-set request's merged state.  The state itself and
its merge are exec/state.py's.
"""

from __future__ import annotations

import contextlib
from typing import Dict, List, Mapping, Optional

import numpy as np

from ..catalog.segment import DataSource
from ..models import aggregations as A
from ..models import query as Q
from ..obs import (
    SCOPE_SET_ROLLUP,
    SPAN_ORDER_LIMIT,
    SPAN_SKETCH_ESTIMATE,
    device_scope,
    span,
)
from ..utils.granularity import bucket_starts
from .lowering import LoweredAggs, ResolvedDim
from .state import PartialState, is_hll

_NO_SPAN = contextlib.nullcontext()


def finalize_timeseries(df, q: Q.TimeseriesQuery, ds: DataSource):
    """Shared Timeseries finalization: empty-bucket zero-fill + ordering."""
    import pandas as pd

    tcol = q.output_name
    if not q.skip_empty_buckets:
        iv = q.intervals[0] if q.intervals else ds.interval()
        if iv is not None:
            lo = min(a for a, _ in q.intervals) if q.intervals else iv[0]
            hi = max(b for _, b in q.intervals) if q.intervals else iv[1]
            # interval ends are EXCLUSIVE: a bucket starting exactly at
            # `hi` is outside the query (Druid emits no zero bucket there)
            all_buckets = bucket_starts(
                lo, max(lo, hi - 1), q.granularity
            ).astype("datetime64[ms]")
            df = (
                df.set_index(tcol)
                .reindex(pd.Index(all_buckets, name=tcol))
                .reset_index()
            )
            for a in q.aggregations:
                if a.merge_op == "psum" and a.name in df:
                    filled = df[a.name].fillna(0)
                    if df[a.name].dtype.kind in ("i", "u"):
                        filled = filled.astype(np.int64)
                    df[a.name] = filled
    df = df.sort_values(tcol, ascending=not q.descending)
    return df.reset_index(drop=True)


def _order_limit_span(df, order_limit):
    """`order_limit(df)` inside the one `order_limit` span an answer's
    ORDER BY / LIMIT takes, attrs `rows_in` and `rows_out`."""
    with span(SPAN_ORDER_LIMIT, rows_in=len(df)) as sp:
        df = order_limit(df)
        if sp is not None:
            sp.attrs["rows_out"] = len(df)
    return df


def finalize_topn(df, q: Q.TopNQuery):
    """Shared TopN ranking, including per-bucket ranking under a non-'all'
    granularity: a topN's ORDER BY and LIMIT."""
    return _order_limit_span(df, lambda df: _rank_topn(df, q))


def _rank_topn(df, q: Q.TopNQuery):
    df = df.sort_values(q.metric, ascending=not q.descending, kind="stable")
    if q.granularity not in ("all", None):
        df = (
            df.groupby("timestamp", sort=True, group_keys=False)
            .head(q.threshold)
            .sort_values(
                ["timestamp", q.metric],
                ascending=[True, not q.descending],
                kind="stable",
            )
        )
        return df.reset_index(drop=True)
    return df.head(q.threshold).reset_index(drop=True)


# ---------------------------------------------------------------------------
# Post-aggregation / having / limit finalization (host-side, tiny)
# ---------------------------------------------------------------------------


def eval_post_agg(
    p: A.PostAggregation,
    table: Mapping[str, np.ndarray],
    states: Optional[Mapping[str, np.ndarray]] = None,
) -> np.ndarray:
    """`states` maps sketch-agg name -> raw per-group sketch state (HLL
    register histograms / theta hash sets); sketch post-aggs must finalize
    from the raw state, not from the already-finalized estimate column in
    `table`."""
    if isinstance(p, A.FieldAccess):
        return np.asarray(table[p.field_name])
    if isinstance(p, A.ConstantPost):
        return np.asarray(p.value)
    if isinstance(p, A.Arithmetic):
        vals = [eval_post_agg(f, table, states) for f in p.fields]
        acc = vals[0].astype(np.float64)
        for v in vals[1:]:
            if p.fn == "+":
                acc = acc + v
            elif p.fn == "-":
                acc = acc - v
            elif p.fn == "*":
                acc = acc * v
            elif p.fn == "pow":
                acc = acc ** v
            elif p.fn in ("/", "quotient"):
                with np.errstate(divide="ignore", invalid="ignore"):
                    # x/0 -> 0 is Druid arithmetic-post-agg behavior; but a
                    # NULL numerator stays NULL (the AVG rewrite over a
                    # zero-row group divides NaN sum by 0 count and must
                    # yield SQL NULL, not 0)
                    acc = np.where(
                        v != 0,
                        acc / np.where(v == 0, 1, v),
                        np.where(np.isnan(acc), np.nan, 0.0),
                    )
            else:
                raise ValueError(f"arithmetic fn {p.fn!r}")
        return acc
    if isinstance(p, A.HyperUniqueCardinality):
        from ..ops import hll as hll_ops

        if states is None or p.field_name not in states:
            raise KeyError(
                f"hyperUniqueCardinality over {p.field_name!r}: no raw HLL "
                "state available (field must name a hyperUnique/cardinality "
                "aggregation in the same query)"
            )
        # finalize_groupby hands every HLL state on as its register
        # histogram, K = 34 - p columns: m = 2^p = 2^(34 - K)
        hist = np.asarray(states[p.field_name])
        m = 1 << (34 - hist.shape[-1])
        return hll_ops.estimate_from_histogram(hist, m)
    if isinstance(p, A.ExpressionPost):
        from ..plan.expr import compile_expr

        fn = compile_expr(p.expression, raw_strings=True)
        cols = {k: np.asarray(v) for k, v in table.items()}
        return np.asarray(fn(cols))
    if isinstance(p, A.QuantileFromSketch):
        from ..ops.quantiles import estimate as quantile_estimate

        if states is None or p.field_name not in states:
            raise KeyError(
                f"quantilesDoublesSketchToQuantile over {p.field_name!r}: "
                "no raw quantiles state available (field must name a "
                "quantilesDoublesSketch aggregation in the same query)"
            )
        return quantile_estimate(states[p.field_name], p.fraction)
    if isinstance(p, A.ThetaSketchEstimate):
        from ..ops.theta import estimate as theta_estimate

        if states is None or p.field_name not in states:
            raise KeyError(
                f"thetaSketchEstimate over {p.field_name!r}: no raw theta "
                "state available (field must name a thetaSketch aggregation "
                "in the same query)"
            )
        return theta_estimate(states[p.field_name])
    if isinstance(p, A.ThetaSketchSetOp):
        from ..ops.theta import set_op_estimate

        bad = [
            f
            for f in p.field_names
            if states is None
            or f not in states
            # theta KMV states are uint32 hash arrays; an HLL register
            # array here would silently produce a garbage estimate
            or np.asarray(states[f]).dtype != np.uint32
        ]
        if bad:
            raise KeyError(
                f"thetaSketchSetOp over {bad}: fields must name "
                "thetaSketch aggregations in the same query"
            )
        return set_op_estimate(p.fn, [states[f] for f in p.field_names])
    raise NotImplementedError(f"post-aggregation {type(p).__name__}")


def _eval_having(h: Q.Having, table: Mapping[str, np.ndarray]) -> np.ndarray:
    if isinstance(h, Q.HavingCompare):
        v = np.asarray(table[h.aggregation], dtype=np.float64)
        return {
            ">": v > h.value,
            "<": v < h.value,
            ">=": v >= h.value,
            "<=": v <= h.value,
            "==": v == h.value,
            "!=": v != h.value,
        }[h.op]
    if isinstance(h, Q.HavingAnd):
        m = _eval_having(h.specs[0], table)
        for s in h.specs[1:]:
            m &= _eval_having(s, table)
        return m
    if isinstance(h, Q.HavingOr):
        m = _eval_having(h.specs[0], table)
        for s in h.specs[1:]:
            m |= _eval_having(s, table)
        return m
    if isinstance(h, Q.HavingNot):
        return ~_eval_having(h.spec, table)
    raise NotImplementedError(type(h).__name__)


# ---------------------------------------------------------------------------
# Grouping-set rollup
# ---------------------------------------------------------------------------


def rollup_states(la: LoweredAggs, cards, union, sets, state):
    """Traced: the merged state of the union set -> the state of each
    grouping set, exactly as a scan grouped by that set would merge it.

    `union` and each set are tuples of dimension indices; `cards` maps an
    index to its cardinality (null slot included).  The dense gid is
    row-major over the union's dimensions, so the union's arrays reshape
    to [card, ..., card, ...] and a coarser set is a reduction over the
    axes it drops, its kept axes moved to its own order: sums add, mins
    and maxs fold, HLL registers take the max, theta states take the KMV
    union of a group's children.  A set reduces from the smallest state
    already made that covers it (the lattice), larger sets first.
    `state` is a `PartialState`; returns one a set."""
    with device_scope(SCOPE_SET_ROLLUP):
        made = {tuple(union): state}
        out = [None] * len(sets)
        for i in sorted(range(len(sets)), key=lambda i: -len(sets[i])):
            s = tuple(sets[i])
            if s not in made:
                src = min(
                    (k for k in made if set(s) <= set(k)),
                    key=lambda k: (int(np.prod([cards[d] for d in k])), k),
                )
                made[s] = _reduce_state(la, cards, src, s, made[src])
            out[i] = made[s]
        return out


def _reduce_state(la: LoweredAggs, cards, src, dst, state):
    """One rollup step: the state over the dimensions `src` -> over `dst`,
    a subset of them (see `rollup_states`)."""
    from ..ops import theta as theta_ops

    keep = [src.index(d) for d in dst]
    perm = keep + [p for p in range(len(src)) if p not in keep]
    shape = [cards[d] for d in src]
    g = int(np.prod([cards[d] for d in dst]))
    n = int(np.prod(shape)) // g

    def children(x):
        """[G_src, ...] -> [G_dst, n, ...]: each coarse group's children."""
        lead = len(shape)
        x = x.reshape(shape + list(x.shape[1:]))
        x = x.transpose(perm + list(range(lead, x.ndim)))
        return x.reshape((g, n) + x.shape[lead:])

    sums, mins, maxs, sketches = state
    out = {}
    for agg in la.sketch_aggs:
        c = children(sketches[agg.name])
        if is_hll(agg):
            out[agg.name] = c.max(axis=1)
        else:  # rollup_takes admits HLL and theta sketches only
            out[agg.name] = theta_ops.union_children(c, agg.size)
    return PartialState(
        children(sums).sum(axis=1, dtype=sums.dtype),
        children(mins).min(axis=1),
        children(maxs).max(axis=1),
        out,
    )


# ---------------------------------------------------------------------------
# Shared finalization (also used by the distributed path)
# ---------------------------------------------------------------------------


def finalize_groupby(
    q: Q.GroupByQuery,
    dims: List[ResolvedDim],
    la: LoweredAggs,
    state: PartialState,
    slot_gids: Optional[np.ndarray] = None,
    metrics=None,
):
    """Merged host partial state -> result DataFrame (decode, post-aggs,
    having, order/limit) — the broker-side finalization of SURVEY.md §3.3.

    `slot_gids` switches to sparse-state layout (ops/sparse_groupby.py):
    arrays are slot-indexed and slot_gids maps slot -> combined gid (-1 =
    empty slot).  `metrics` (the request's QueryMetrics) counts the
    groups decoded here in `host_groups`."""
    import pandas as pd

    sums, mins, maxs, sketch_states = state
    rows_per_group = sums[:, 0]
    if slot_gids is not None:
        present = (slot_gids >= 0) & (rows_per_group > 0)
        sel = np.nonzero(present)[0]
        idx = slot_gids[sel].astype(np.int64)  # combined gid per kept row
        empty_group = np.zeros(len(sel), dtype=bool)
    else:
        present = rows_per_group > 0
        if not dims:
            # SQL: a global aggregate always yields one row (COUNT=0, SUM/
            # MIN/MAX=NULL when nothing matched) — never an empty result
            present = np.ones_like(present, dtype=bool)
        sel = np.nonzero(present)[0]
        idx = sel.astype(np.int64)
        empty_group = rows_per_group[sel] == 0

    if metrics is not None:
        metrics.host_groups += len(sel)
    table: Dict[str, np.ndarray] = {}
    # decode combined gid -> per-dimension codes (row-major order)
    rem = idx
    codes_list = []
    for d in reversed(dims):
        codes_list.append((rem % d.cardinality).astype(np.int64))
        rem = rem // d.cardinality
    codes_list.reverse()
    for d, codes in zip(dims, codes_list):
        table[d.spec.name] = d.decode(codes)

    for j, n in enumerate(la.sum_names):
        if n == "__rows":
            continue
        v = sums[sel, j].astype(np.float64)
        if n in la.count_like or not empty_group.any():
            table[n] = np.rint(v).astype(np.int64) if la.long_valued[n] else v
        else:
            # SQL: SUM over zero rows is NULL; COUNT stays 0
            table[n] = np.where(empty_group, np.nan, v)
    for n, src in la.aliased.items():
        # unfiltered COUNT reads the __rows presence counter directly
        j = la.sum_names.index(src)
        table[n] = np.rint(sums[sel, j].astype(np.float64)).astype(np.int64)
    def _finalize_extremum(v: np.ndarray, long_valued: bool) -> np.ndarray:
        v = v.astype(np.float64)
        v = np.where(np.isinf(v), np.nan, v)
        if long_valued and not np.isnan(v).any():
            return np.rint(v).astype(np.int64)
        return v

    for j, n in enumerate(la.min_names):
        table[n] = _finalize_extremum(mins[sel, j], la.long_valued[n])
    for j, n in enumerate(la.max_names):
        table[n] = _finalize_extremum(maxs[sel, j], la.long_valued[n])

    from ..ops import hll as hll_ops

    raw_states: Dict[str, np.ndarray] = {}
    # an HLL state arrives as its register histogram where the fetch made
    # one (`state.estimable`), else as registers: the span says which
    fetched = {
        sketch_states[a.name].shape[-1] == hll_ops.histogram_width(a.precision)
        for a in la.sketch_aggs
        if is_hll(a)
    }
    attrs = (
        {"source": "histogram" if fetched == {True} else "registers"}
        if fetched else {}
    )
    # the states -> estimates work and the post-aggs that read the raw
    # states are one span, opened only where there are sketches
    with span(SPAN_SKETCH_ESTIMATE, **attrs) if la.sketch_aggs else _NO_SPAN:
        for agg in la.sketch_aggs:
            from ..ops import theta as theta_ops

            st = sketch_states[agg.name][sel]
            if is_hll(agg):
                prec = agg.precision
                if st.shape[-1] != hll_ops.histogram_width(prec):
                    st = hll_ops.histogram_np(st, prec)
                table[agg.name] = np.rint(
                    hll_ops.estimate_from_histogram(st, 1 << prec)
                ).astype(np.int64)
            elif isinstance(agg, A.QuantilesSketch):
                from ..ops import quantiles as quantiles_ops

                # Druid finalizes a quantiles sketch to its N; the state
                # carries the exact per-group row count in its trailing
                # counter row, so this is exact at any scale.  Quantile
                # values come from the QuantileFromSketch post-agg over
                # the raw state
                table[agg.name] = quantiles_ops.count(st).astype(np.int64)
            else:
                table[agg.name] = np.rint(theta_ops.estimate(st)).astype(np.int64)
            raw_states[agg.name] = st

        for p in q.post_aggregations:
            table[p.name] = np.broadcast_to(
                eval_post_agg(p, table, raw_states), sel.shape
            ).copy()

    if q.having is not None:
        m = _eval_having(q.having, table)
        table = {k: np.asarray(v)[m] for k, v in table.items()}

    df = pd.DataFrame(table)

    # grouping-set subtotals (CUBE/ROLLUP): each set's state comes here on
    # its own (api.execute_grouping_sets), rolled up or scanned alone

    if q.limit_spec is not None:
        df = apply_limit_spec(df, q.limit_spec)
    return df.reset_index(drop=True)


def apply_limit_spec(df, ls):
    """Sort/offset/limit per a LimitSpec — the ONE implementation, shared
    by groupBy finalization and grouping-set combination (api.py); null
    keys (grouping-set rows that aggregate a sort dimension away) order
    last."""
    return _order_limit_span(df, lambda df: _sort_offset_limit(df, ls))


def _sort_offset_limit(df, ls):
    if ls.columns:
        df = df.sort_values(
            [c.dimension for c in ls.columns],
            ascending=[c.direction == "ascending" for c in ls.columns],
            kind="stable",
            na_position="last",
        )
    if ls.offset:
        df = df.iloc[ls.offset:]
    if ls.limit is not None:
        df = df.head(ls.limit)
    return df


# ---------------------------------------------------------------------------
# Column discovery helpers
# ---------------------------------------------------------------------------


