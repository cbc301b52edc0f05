"""Sparse (sort-compaction) execution orchestration.

The capacity-ladder dispatch/evict/fetch machinery for the high-cardinality
GroupBy path — an engine-within-the-engine that round 2's review flagged for
extraction (VERDICT r2 #9).  `Engine` mixes this in; every attribute it
touches (`_query_fn_cache`, `_sparse_row_capacity`,
segment iteration, metrics) lives on the engine instance, so this is purely
a file split: same methods, same behavior, pinned by the existing
tests/test_sparse_groupby.py suite.

Reference parity: the reference has no analog (Druid's own scan does the
high-cardinality work server-side); this is TPU-native machinery for keeping
huge group domains on the accelerator (SURVEY.md §2 native-components row).
"""

from __future__ import annotations

from typing import Callable, Optional

import jax

from ..catalog.segment import DataSource
from ..models import query as Q
from ..obs import SPAN_PROGRAM_LOOKUP, span_around
from ..plan.cost import sparse_inner_kernel
from ..utils.log import get_logger
from .finalize import finalize_groupby
from .lowering import GroupByLowering, _query_key, memo_key
from .state import PartialState

log = get_logger("exec.sparse")


class SparseExecMixin:

    @span_around(SPAN_PROGRAM_LOOKUP)
    def _sparse_program(
        self,
        q: Q.GroupByQuery,
        ds: DataSource,
        lowering: "GroupByLowering",
        row_capacity: Optional[int] = None,
        slots: Optional[int] = None,
    ) -> Callable:
        from ..ops.sparse_groupby import (
            SPARSE_SLOTS,
            sparse_partial_aggregate,
        )

        la = lowering.la
        slots = slots or SPARSE_SLOTS
        inner = sparse_inner_kernel()
        # structured key, NOT an f-string: interpolation collapses distinct
        # identities (None vs "None") (graftlint jit-cache/GL103)
        key = _query_key(q, ds) + ("sparse", inner, row_capacity, slots)
        from ..obs import prof

        cached = self._query_fn_cache.get(key)
        if cached is not None:
            if self._m is not None:
                self._m.program_cache_hit = True
            prof.note_program_cache("sparse", hit=True)
            return cached
        prof.note_program_cache("sparse", hit=False)

        from ..ops.sparse_groupby import merge_sparse_states

        def one_segment(cols):
            gid, mask, sv, mmv, mmm = lowering.row_arrays(dict(cols))
            return sparse_partial_aggregate(
                gid, mask, sv, mmv, mmm,
                num_groups=lowering.num_groups,
                num_min=len(la.min_names),
                num_max=len(la.max_names),
                slots=slots,
                inner_strategy=inner,
                row_capacity=row_capacity,
            )

        @jax.jit
        def seg_fn(cols_list):
            state = None
            for cols in cols_list:
                st = one_segment(cols)
                state = (
                    st
                    if state is None
                    else merge_sparse_states(
                        state, st, num_groups=lowering.num_groups
                    )
                )
            return state

        self._query_fn_cache[key] = seg_fn
        return seg_fn

    def _dispatch_groupby_sparse(
        self, q: Q.GroupByQuery, ds: DataSource, lowering: "GroupByLowering",
        segs,
    ):
        """Sparse execution attempt over the (non-empty) segment scope
        `segs`, as `_dispatch_groupby_once` resolved it, split into an
        eager dispatch phase and a deferred fetch so N queries (a
        grouping-set expansion) can overlap their device round trips.

        Dispatches the tier-1 program asynchronously and returns
        `resolve() -> (df, reason)`: df is None when declining, with reason
        "overflow" (deterministic — more distinct groups than slots: the
        caller pins the query off this path) or "declined" (a partial
        drain left nothing to answer from).  A failure of the sparse
        program is not a decline: it raises, here or in resolve(), into
        the engine's retry machinery like any other device error."""
        from ..ops.sparse_groupby import merge_sparse_states

        G = lowering.num_groups
        # The selective-filter fast path only makes sense when rows can
        # actually be masked out (a filter or time intervals); an unfiltered
        # segment would overflow the capacity by construction.
        selective = q.filter is not None or bool(q.intervals)

        def dispatch(row_capacity=None, slots=None):
            from ..obs import SPAN_SPARSE_DISPATCH, span
            from ..resilience import checkpoint_partial, current_partial, fire
            from .engine import _row_counts

            # fault-injection site: the sparse tier IS a device dispatch,
            # so "100% device failure" (`device_dispatch` armed) must take
            # it down exactly like the dense engine's — otherwise a
            # breaker half-open probe routed to a sparse-strategy query
            # succeeds and closes the breaker while the device is dead.
            fire("device_dispatch")
            seg_fn = self._sparse_program(
                q, ds, lowering, row_capacity=row_capacity, slots=slots
            )
            pc = current_partial()
            if pc is not None:
                pc.begin_pass()
                pc.add_scope(len(segs), *_row_counts(segs))
            state = None
            from .pipeline import CanonicalFold

            batches = list(self._segment_batches(segs, lowering.columns))
            # transfer pipeline (exec/pipeline.py): resident batches
            # dispatch first, the next cold batches' columns stream
            # behind the sparse compute.  The merge below is a scatter
            # (order-sensitive in float), so CanonicalFold pins it to
            # canonical batch order regardless of dispatch order —
            # pipeline-on stays byte-identical to pipeline-off.
            run = self._pipeline.start(ds, batches, lowering.columns)

            def fold_one(st):
                nonlocal state
                state = (
                    st
                    if state is None
                    else merge_sparse_states(state, st, num_groups=G)
                )

            folder = CanonicalFold(fold_one)
            for pos, bi in enumerate(run.order):
                # cooperative deadline checkpoint between batch
                # dispatches — same lifecycle contract as the dense
                # engine's segment loop (checkpoint-coverage/GL901);
                # with a partial collector armed, expiry stops the loop
                # (and any pending prefetch) and the merged sparse state
                # so far becomes the answer
                if checkpoint_partial("sparse.segment_loop"):
                    run.cancel()
                    break
                batch = batches[bi]
                with span(SPAN_SPARSE_DISPATCH, batch=bi, segments=len(batch)):
                    import time as _time

                    from ..obs import prof

                    cols_list = [
                        self._cols_for_segment(seg, ds, lowering.columns)
                        for seg in batch
                    ]
                    run.advance(pos)
                    t_call = _time.perf_counter()
                    st = seg_fn(cols_list)
                    # sampled query: honest enqueue-vs-device split on
                    # the sparse dispatch span (obs/prof.py; no-op off)
                    st = prof.dispatch_sync(st, t_call)
                    folder.add(bi, st)
                if pc is not None:
                    pc.add_seen(len(batch), *_row_counts(batch))
            folder.drain()
            return state

        # learned rungs key segment-set-independently (see lowering.memo_key):
        # appends must not forget them or leak one entry per delta publish
        qkey = memo_key(q, ds)
        from ..ops import sparse_groupby as _sg

        # tier 1: filter-compacted sort.  The initial capacity rung comes
        # from the planner's selectivity estimate with 2x headroom (the
        # remembered rung from a previous overflow wins when present) —
        # sorting a fixed 128K slots per segment regardless of survivors
        # was round 3's hidden per-segment cost.  A None rung = full sort.
        if not selective:
            cap = None
        elif qkey in self._sparse_row_capacity:
            cap = self._sparse_row_capacity[qkey]
        else:
            from ..plan.cost import estimate_selectivity

            sel = (
                estimate_selectivity(q.filter, ds)
                if q.filter is not None
                else 1.0
            )
            if sel >= 1.0:
                # unmodeled filter or interval-only scope: no estimate to
                # act on — keep the historical default rung (the overflow
                # ladder corrects upward, never a full-segment sort here)
                cap = _sg.ROW_CAPACITY
            else:
                seg_rows = max((s.num_rows for s in segs), default=1)
                need = 2.0 * sel * seg_rows
                cap = next(
                    (c for c in _sg.ROW_CAPACITY_LADDER if c >= need), None
                )
        # slot capacity: SPARSE_SLOTS one-hot by default, or the remembered
        # SLOTS_LADDER rung (segmented-reduce tier) from a prior overflow
        slots0 = self._sparse_slots.get(qkey, _sg.SPARSE_SLOTS)

        def fetch_tiered(state, row_capacity, slots):
            # On row overflow the kernel's exact survivor count picks the
            # smallest adequate ROW_CAPACITY_LADDER rung (full-R sort only
            # past the top rung) — sort cost grows ~linearly with capacity,
            # so q3_1-class queries (180K survivors of 6M rows) stay 3-4x
            # off the full sort.  The rung is deterministic per (query,
            # data) and remembered.  Slot overflow is handled by the
            # caller's SLOTS_LADDER loop.
            from ..obs import SPAN_DEVICE_FETCH, span
            from ..resilience import current_partial

            with span(SPAN_DEVICE_FETCH):
                host = jax.device_get(state)
            if row_capacity is not None and bool(host["row_overflow"]):
                pc = current_partial()
                if pc is not None and pc.triggered:
                    # partial drain: a ladder rerun would re-dispatch an
                    # already-stopped scope (dispatch() breaks at its
                    # first checkpoint and returns None) — decline this
                    # execution instead; the dense drain answers
                    return None
                n = int(host["n_rows"])
                new_cap = next(
                    (
                        c
                        for c in _sg.ROW_CAPACITY_LADDER
                        if c >= n and c > row_capacity
                    ),
                    None,
                )
                self._sparse_row_capacity[qkey] = new_cap
                log.info(
                    "sparse row compaction overflowed %d of capacity %d; "
                    "rerunning at %s (remembered for repeats)",
                    n, row_capacity,
                    "full-segment sort" if new_cap is None else new_cap,
                )
                host = jax.device_get(
                    dispatch(row_capacity=new_cap, slots=slots)
                )
            return host

        def fetch_slot_laddered(state, row_capacity, slots):
            # Slot-capacity ladder (VERDICT r3 #2): when more groups are
            # GENUINELY populated than the one-hot slot tier holds, rung up
            # through the segmented-reduce capacities instead of abandoning
            # the device path.  The kernel's exact distinct-present count
            # (`n_real`) picks the smallest adequate rung; only past the
            # ladder top does the query fall back to raw scatter.
            from ..resilience import checkpoint, current_partial

            host = fetch_tiered(state, row_capacity, slots)
            while host is not None and bool(host["overflow"]):
                pc = current_partial()
                if pc is not None and pc.triggered:
                    # partial drain: no rung rerun (see fetch_tiered)
                    return None, slots
                # every ladder rung re-dispatches the whole segment
                # scope — a deadlined query must cancel between rungs,
                # not after the ladder converges
                checkpoint("sparse.slots_ladder")
                n_est = int(host["n_real"])
                new_slots = next(
                    (
                        s
                        for s in _sg.SLOTS_LADDER
                        if s >= n_est and s > slots
                    ),
                    None,
                )
                if new_slots is None:
                    # an overflowed merge reports max-per-state n_real — a
                    # LOWER bound (ADVICE r4) — so a bound past the ladder
                    # top does not prove the true count is: ladder up one
                    # rung at a time and let the rerun's exact count decide.
                    new_slots = next(
                        (s for s in _sg.SLOTS_LADDER if s > slots), None
                    )
                if new_slots is None:
                    return host, slots  # beyond the ladder: caller declines
                self._sparse_slots[qkey] = new_slots
                log.info(
                    "sparse slots overflowed (~%d distinct present > %d); "
                    "rerunning on the segmented-reduce tier at %d slots "
                    "(remembered for repeats)",
                    n_est, slots, new_slots,
                )
                slots = new_slots
                row_capacity = self._sparse_row_capacity.get(
                    qkey, row_capacity
                )
                host = fetch_tiered(
                    dispatch(row_capacity=row_capacity, slots=slots),
                    row_capacity,
                    slots,
                )
            return host, slots

        # phase 1: dispatch (async — no fetch)
        state = dispatch(row_capacity=cap, slots=slots0)

        def resolve():
            nonlocal state
            if state is None:
                # a partial drain armed BEFORE this dispatch started:
                # nothing was dispatched, so there is no sparse state
                # to answer from — decline and let the dense path
                # produce the zero-coverage answer
                return None, "declined"
            try:
                host, _ = fetch_slot_laddered(state, cap, slots0)
            finally:
                state = None  # free the device partials promptly
            if host is None:
                # a partial drain stopped a ladder rerun mid-scope:
                # decline — the dense drain produces the best-effort
                # answer
                return None, "declined"
            if bool(host["overflow"]):
                return None, "overflow"
            df = finalize_groupby(
                q, lowering.dims, lowering.la,
                PartialState(host["sums"], host["mins"], host["maxs"], {}),
                slot_gids=host["gids"], metrics=self._m,
            )
            return df, "ok"

        return resolve

