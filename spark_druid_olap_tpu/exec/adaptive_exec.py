"""Adaptive dictionary-domain compaction for huge combined group domains.

The OLAP reality behind SSB q3/q4-class queries: the COMBINED dictionary
domain is huge (c_city x s_city x d_year = 504K cells) but the filter admits
only a few codes per dimension (c_nation = 'UNITED STATES' leaves 10 of 250
cities).  Raw scatter pays the full-domain price per row (cache-missing
state) and per segment (one [G, M] state each); the sort-compaction path
pays a per-segment sort.  Both ignore what the dictionaries already know.

This tier measures the per-dimension PRESENT code sets first, then runs the
normal aggregation over the compacted domain:

  phase A  one fused pass: per-dim presence counts under the query's row
           mask — a tiny GroupBy per dimension (cardinality-sized states,
           one data read for all dims), merged across segments.
  host     kept_d = codes with count > 0;  G' = prod(|kept_d|).  If G' is
           small enough, build the remap code -> compact code (-1 = absent).
  phase B  the UNMODIFIED segment program machinery over a *compacted
           lowering*: same query, same aggs (sketches included), dims
           rewritten through the remap (identity / one compare-select per
           run of kept codes / LUT gather — see compacted_lowering) —
           so the kernel runs dense/Pallas at G' instead of scatter at G.

Soundness: presence is computed under exactly the row mask phase B applies,
so every masked-in row's codes are in kept_d by construction; a -1 from the
remap can only occur on rows the mask already excludes (combine_group_ids
clamps them into slot 0, which the mask keeps out of every aggregate).

The kept sets are cached per (query, datasource-version): repeat queries
skip phase A entirely and run ONE compact-domain pass — dashboard-shaped
workloads converge to the speed of a low-cardinality GroupBy.

Reference parity: Druid's historicals get the same effect from per-segment
dictionary scans + bitmap indexes (SURVEY.md §1 L1 row `[U]`); this is the
TPU-native equivalent where the "index" is a presence bitmap measured on
device at full scan speed.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..catalog.segment import DataSource
from ..models import query as Q
from ..obs import (
    SCOPE_KEPT_REMAP,
    SCOPE_PRESENCE,
    SPAN_ADAPTIVE_KEPT,
    SPAN_ADAPTIVE_PROBE,
    SPAN_DEVICE_FETCH,
    SPAN_FINALIZE,
    SPAN_PROGRAM_LOOKUP,
    SPAN_ROUTE,
    device_scope,
    prof,
    span,
    span_around,
)
from ..plan.cost import presence_kernels, shape_kernel
from ..resilience import DeadlineExceeded, checkpoint, current_partial, fire
from ..utils.log import get_logger
from .finalize import (
    estimable_sketch_states,
    finalize_groupby,
    state_nbytes,
)
from .lowering import (
    GroupByLowering,
    ResolvedDim,
    _query_key,
    empty_partials,
    memo_key,
)

log = get_logger("exec.adaptive")

# Decline compaction when the compacted domain is still bigger than this:
# past it the dense/one-hot inner gains nothing and phase A was the only
# cost (one scan), which the decline memo makes one-time.
ADAPTIVE_MAX_COMPACT_GROUPS = 1 << 17

# ... and when the domain barely shrinks, compaction cannot pay for its
# extra pass even once.
ADAPTIVE_MIN_SHRINK = 0.5

def _compare_chain_max() -> int:
    """Most RUNS of consecutive kept codes a dim's remap spells out as
    compare-selects (see compacted_lowering, kept_runs); past it the remap
    is a device LUT gather.  One run costs the same few elementwise ops
    whatever its length, so the cap only matters to a kept set of scattered codes.
    On the v5e a step of such a chain that XLA does not keep in one fusion
    is a pass over the segment block in the Pallas kernel operand's padded
    `[R, 1]` layout, ~0.34 ms a segment (PERF.md section 6, PR 26), so 64
    steps still undercut the ~360 ms a gather over 52M rows was profiled
    at in round 5.  On the CPU a small LUT gather is one L1-resident load
    a row, and the chain is capped near the width XLA itself would
    select-lower."""
    import jax

    return 64 if jax.default_backend() == "tpu" else 4


def _run_starts(kd: np.ndarray) -> np.ndarray:
    """Positions in `kd` (unique codes, in compact-code order) where a
    maximal run of consecutive codes starts."""
    if len(kd) == 0:
        return np.zeros(0, np.int64)
    steps = np.diff(np.asarray(kd, dtype=np.int64))
    return np.concatenate(([0], np.flatnonzero(steps != 1) + 1))


def kept_runs(kd: np.ndarray) -> List[Tuple[int, int, int]]:
    """`kd` as its maximal runs of consecutive codes: `(lo, hi, offset)`
    per run, `offset` = the number of kept codes before it, so code `c` of
    a run is compact `c - lo + offset`.

    Kept sets are mostly runs because dictionaries are sorted: a filter on
    a parent attribute or a range keeps neighbours (q2_1's 40 brands of
    one category, a lexicographic Bound, every `d_year` but the null
    slot)."""
    starts = _run_starts(kd)
    ends = np.concatenate((starts[1:], [len(kd)])) - 1
    return [
        (int(kd[s]), int(kd[e]), int(s)) for s, e in zip(starts, ends)
    ]


def remap_form(kd: np.ndarray, cardinality: int) -> str:
    """Which remap compacted_lowering gives a dim with kept codes `kd`:
    `"identity"`, `"runs:<r>"` or `"lut"` (the `adaptive_kept` span's
    `remap` attr, one entry per dim)."""
    if len(kd) == cardinality:
        return "identity"
    r = len(_run_starts(kd))
    return f"runs:{r}" if r <= _compare_chain_max() else "lut"


def presence_columns(q, lowering: GroupByLowering, ds=None):
    """Columns phase A reads: only what the mask + dim codes need —
    aggregate input columns stay on the host until phase B.  The PHYSICAL
    time column must survive the cut whenever the lowering fetches it:
    row_mask reads cols["__time"], which the engines alias from
    ds.time_column (review r5: dropping it made every interval-scoped
    query KeyError out of phase A and silently decline adaptive).  Shared
    by the local mixin and parallel/distributed.py."""
    from .lowering import _filter_columns

    keep = {"__valid", "__time"}
    tc = getattr(ds, "time_column", None) if ds is not None else None
    if tc:
        keep.add(tc)
    for d in lowering.dims:
        keep.add(d.spec.dimension)
    if q.filter is not None:
        keep.update(_filter_columns(q.filter))
    for v in q.virtual_columns:
        keep.update(v.expression.columns())
    return [c for c in lowering.columns if c in keep]


def filter_derived_kept(
    q, lowering: GroupByLowering, ds
) -> Optional[List[np.ndarray]]:
    """Phase A WITHOUT the scan: per-dim kept-code sets derived from the
    query's own filter, evaluated over the host-side dictionaries.

    When every grouping dim is directly pinned by a dictionary-evaluable
    conjunct (Selector / In / Bound on that dim), the accepted-code sets
    are computable in O(cardinality) host work — the Druid bitmap-index /
    dictionary-pruning analog (SURVEY.md §1 L1 row) with zero device
    passes.  Soundness: every masked-in row satisfies every conjunct, so
    its code for a pinned dim lies in that conjunct's accepted set; the
    derived kept is a (possibly proper) SUPERSET of measured presence,
    which only costs a few empty compact slots.  Returns None when any
    dim is unpinned or not dictionary-backed — callers fall back to the
    measured presence pass.

    Only AND-conjuncts pin a dim: disjunctions/negations/expressions may
    admit rows their sub-predicates reject, so they derive nothing (the
    dim counts as unpinned unless another conjunct covers it)."""
    from ..models import filters as F

    conjuncts: List[object] = []

    def collect(f):
        if isinstance(f, F.And):
            for c in f.fields:
                collect(c)
        elif f is not None:
            conjuncts.append(f)

    collect(getattr(q, "filter", None))

    kept: List[np.ndarray] = []
    for d in lowering.dims:
        spec = d.spec
        if (
            spec.dimension == "__time"
            or getattr(spec, "granularity", None) is not None
            or getattr(spec, "extraction", None) is not None
            or spec.dimension not in getattr(ds, "dicts", {})
        ):
            return None  # not a plain dictionary dim: cannot derive
        dic = ds.dicts[spec.dimension]
        # Accepted codes per derivable conjunct, intersected.  Every
        # branch MIRRORS the device filter compiler's translation
        # (ops/filters.py) exactly — kept must be a superset of the
        # device-true codes, so the two sides must agree on how literals
        # map into code space (review r5: values.index() diverged from
        # code_of on numeric dictionaries and silently dropped rows).
        acc: Optional[set] = None
        for f in conjuncts:
            if getattr(f, "dimension", None) != spec.dimension:
                continue
            cur: Optional[set] = None
            if isinstance(f, F.Selector):
                if f.value is None:
                    cur = {d.cardinality - 1}  # the null slot
                else:
                    c = dic.code_of(f.value)
                    cur = set() if c is None else {c}
            elif isinstance(f, F.InFilter):
                cur = {
                    c
                    for c in (dic.code_of(v) for v in f.values)
                    if c is not None
                }
            elif isinstance(f, F.Bound):
                cur = _bound_accepted_codes(f, dic)
            if cur is not None:
                acc = cur if acc is None else (acc & cur)
        if acc is None:
            return None  # unpinned dim: a device presence pass is needed
        kept.append(np.array(sorted(acc), dtype=np.int32))
    return kept


def _bound_accepted_codes(f, dic) -> Optional[set]:
    """Dictionary codes a Bound conjunct accepts, mirroring the device
    compile branch-for-branch (ops/filters.py Bound handling); None when
    the branch cannot be mirrored soundly (the conjunct then derives
    nothing and the dim falls back to the presence scan)."""
    import numpy as _np

    from ..ops.filters import numeric_dict_code_bounds

    nv = dic.numeric_values
    card = dic.cardinality
    if nv is not None:
        cb = numeric_dict_code_bounds(f, _np.asarray(nv))
        if cb is not None:
            lo_c, hi_c = cb
            lo_c = 0 if lo_c is None else lo_c
            hi_c = card - 1 if hi_c is None else hi_c
            return set(range(max(0, lo_c), min(card - 1, hi_c) + 1))
        # non-numeric literal: device compares STRINGIFIED values
        vals = [str(v) for v in dic.values]
        ok = set(range(card))
        if f.lower is not None:
            lo_s = str(f.lower)
            ok = {
                i for i in ok
                if (vals[i] > lo_s if f.lower_strict else vals[i] >= lo_s)
            }
        if f.upper is not None:
            hi_s = str(f.upper)
            ok = {
                i for i in ok
                if (vals[i] < hi_s if f.upper_strict else vals[i] <= hi_s)
            }
        return ok
    if f.ordering == "lexicographic":
        vals = _np.asarray(dic.values, dtype=str)
        lo_c, hi_c = 0, card - 1
        if f.lower is not None:
            side = "right" if f.lower_strict else "left"
            lo_c = int(_np.searchsorted(vals, f.lower, side=side))
        if f.upper is not None:
            side = "left" if f.upper_strict else "right"
            hi_c = int(_np.searchsorted(vals, f.upper, side=side)) - 1
        return set(range(max(0, lo_c), min(card - 1, hi_c) + 1))
    # string dictionary + numeric ordering: the device falls through to a
    # raw-CODE numeric compare (a degenerate legacy semantic) — decline
    # rather than risk a kept set narrower than the device mask
    return None


def compacted_lowering(
    lowering: GroupByLowering, kept: List[np.ndarray]
) -> GroupByLowering:
    """The same lowered query over the compacted code domain.

    Each dim's codes_fn remaps original -> compact codes in the form
    `remap_form` names, chosen from the run structure of its kept set:
    identity (every code kept: no rewrite at all), a compare-select per
    RUN of consecutive kept codes (`c - lo + offset` under `lo <= c <= hi`:
    the number of elementwise steps grows with the runs, not with |kept|),
    or a device LUT gather (more runs than `_compare_chain_max()`).  All
    three emit int32 compact codes in `kept` order and -1 for every absent
    code, which only masked-out rows can carry; decode() maps compact
    codes back through kept_d then the original decoder — so
    finalize_groupby, combine_group_ids and every kernel work unchanged."""
    new_dims: List[ResolvedDim] = []
    G = 1
    for d, kd in zip(lowering.dims, kept):
        form = remap_form(kd, d.cardinality)
        if form == "identity":
            codes_fn = d.codes_fn
        elif form == "lut":
            lut = np.full(d.cardinality, -1, np.int32)
            lut[kd] = np.arange(len(kd), dtype=np.int32)
            lut_dev = jnp.asarray(lut)

            def codes_fn(cols, base=d.codes_fn, lut_dev=lut_dev):
                c = base(cols)
                with device_scope(SCOPE_KEPT_REMAP):
                    return lut_dev[c]
        else:
            # One select per run, not one per kept code.  Two things the
            # v5e compiler does with this expression shape it (PERF.md
            # section 6, PR 26; both read off the compiled HLO):
            # - a chain too long to stay one fusion is materialised step
            #   by step in the Pallas kernel operand's padded `[R, 1]`
            #   layout, 268 MB a step a segment: q2_1's 40 + 7 per-code
            #   selects were 27 such `%copy`, 2.05 s of its 2.26 s;
            # - a select whose value operand is the bare code column
            #   carries that padded layout up through the group-id
            #   arithmetic the same way, and `c - 0` folds to the bare
            #   column — hence `minimum(c, hi)`, which equals `c` inside
            #   the run and is never folded away.
            # Absent codes sum to 0 and leave as -1.
            def codes_fn(cols, base=d.codes_fn, runs=kept_runs(kd)):
                c = base(cols).astype(jnp.int32)
                with device_scope(SCOPE_KEPT_REMAP):
                    acc = jnp.zeros(c.shape, jnp.int32)
                    for lo, hi, offset in runs:
                        acc = acc + jnp.where(
                            (c >= lo) & (c <= hi),
                            jnp.minimum(c, hi) - (lo - offset - 1),
                            0,
                        )
                    return acc - 1

        def decode(codes, base=d.decode, kd=kd):
            return base(kd[np.asarray(codes, dtype=np.int64)])

        new_dims.append(ResolvedDim(d.spec, len(kd), codes_fn, decode))
        G *= len(kd)
    return dataclasses.replace(lowering, dims=new_dims, num_groups=G)


class AdaptiveDomainMixin:
    """Engine mixin (exec/engine.py): the adaptive-compaction dispatch.

    Attributes it relies on are created in Engine.__init__:
    `_adaptive_kept` (qkey -> kept code arrays), `_adaptive_declined`
    (qkey set).  Everything else reuses the engine's program machinery.
    """

    def _presence_columns(self, q, lowering: GroupByLowering, ds=None):
        return presence_columns(q, lowering, ds)

    @span_around(SPAN_PROGRAM_LOOKUP)
    def _presence_program(self, q, ds, lowering: GroupByLowering):
        """Fused per-segment program: presence COUNTS per grouping dim under
        the query's row mask — one data read covers every dim."""
        from ..ops.groupby import partial_aggregate

        strategies = presence_kernels(d.cardinality for d in lowering.dims)
        key = _query_key(q, ds) + ("adaptive-presence", strategies)
        cached = self._query_fn_cache.get(key)
        if cached is not None:
            prof.note_program_cache("adaptive-presence", hit=True)
            return cached
        prof.note_program_cache("adaptive-presence", hit=False)

        @jax.jit
        def seg_fn(cols_list):
            counts = None
            for cols in cols_list:
                cols = lowering.add_virtual(dict(cols))
                mask = lowering.row_mask(cols)
                ones = mask.astype(jnp.float32)[:, None]
                zero_mm = jnp.zeros((ones.shape[0], 0), jnp.float32)
                zero_mmm = jnp.zeros((ones.shape[0], 0), jnp.bool_)
                per = []
                for d, strat in zip(lowering.dims, strategies):
                    with device_scope(SCOPE_PRESENCE):
                        s, _, _ = partial_aggregate(
                            d.codes_fn(cols), mask,
                            # the kernel counts from its match tile
                            (None,) if strat == "pallas" else ones,
                            zero_mm, zero_mmm,
                            num_groups=d.cardinality, num_min=0, num_max=0,
                            strategy=strat,
                        )
                    per.append(s[:, 0])
                counts = (
                    per
                    if counts is None
                    else [a + b for a, b in zip(counts, per)]
                )
            return counts

        self._query_fn_cache[key] = seg_fn
        return seg_fn

    def _adaptive_kept_codes(
        self, q, ds, lowering: GroupByLowering, segs
    ) -> Optional[List[np.ndarray]]:
        """Phase A: measure (or recall) per-dim present code sets.  Returns
        None when compaction should be declined for this query.

        The `adaptive_kept` span's own time is the memo lookup, the
        dictionary derivation and the host `nonzero` over the presence
        counts; the presence dispatches are its `adaptive_probe` children
        (phase A), so a repeat shows none.  Its `remap` attr names the form
        of each dim's code remap in the phase-B program (`remap_form`)."""
        # The memo keys segment-set-independently (lowering.memo_key) so
        # continuous streamed ingest neither forgets query shapes nor
        # leaks one entry per published delta — but a MEASURED kept set
        # is only valid for the exact segment set it scanned (a fresh
        # delta may contain codes the old scan never saw; reusing the
        # stale set would silently drop those rows), so measured entries
        # carry their segment signature and miss-and-REPLACE when it
        # moved.  Dictionary-DERIVED kept sets are supersets by
        # construction and stay valid across appends (only a dictionary
        # extension, which changes the memo key, retires them).
        qkey = memo_key(q, ds)
        seg_sig = tuple(s.uid for s in segs)

        def measure():
            need = self._presence_columns(q, lowering, ds)
            # fault-injection site: phase A dispatches the presence
            # program to the device (phase B goes through the engine's
            # _call_segment_program, which has its own site).  A failure
            # of the pass, injected or real, raises into the engine's
            # retry machinery like any other device error: it is never a
            # reason to hand the query to another tier.
            fire("device_dispatch")
            seg_fn = self._presence_program(q, ds, lowering)
            counts = None
            for bi, batch in enumerate(self._segment_batches(segs, need)):
                # phase A dispatches the full segment scope too: a
                # deadlined query cancels between presence batches
                # (checkpoint-coverage/GL901)
                checkpoint("adaptive.presence_loop")
                with span(SPAN_ADAPTIVE_PROBE, batch=bi, phase="A"):
                    cols_list = [
                        self._cols_for_segment(seg, ds, need)
                        for seg in batch
                    ]
                    t_call = time.perf_counter()
                    out = seg_fn(cols_list)
                    out = prof.dispatch_sync(out, t_call)
                counts = (
                    out
                    if counts is None
                    else [a + b for a, b in zip(counts, out)]
                )
            return [
                np.nonzero(np.asarray(c) > 0)[0].astype(np.int32)
                for c in counts
            ]

        with span(SPAN_ADAPTIVE_KEPT) as sp:
            entry = self._adaptive_kept.get(qkey)
            kept, source = None, "memo"
            if entry is not None:
                if entry[0] == "derived":
                    kept = entry[1]
                elif entry[1] == seg_sig:
                    kept = entry[2]
            if kept is None:
                # dictionary-derived shortcut: when the filter itself pins
                # every grouping dim, phase A needs NO device pass at all —
                # O(cardinality) host work over the dictionaries replaces
                # the full presence scan (and its dispatch round-trip)
                kept, source = filter_derived_kept(q, lowering, ds), "derived"
                if kept is not None:
                    self._adaptive_kept[qkey] = ("derived", kept)
            if kept is None:
                kept, source = measure(), "measured"
                self._adaptive_kept[qkey] = ("measured", seg_sig, kept)
            Gc = 1
            for kd in kept:
                Gc *= len(kd)
            declined = Gc > ADAPTIVE_MAX_COMPACT_GROUPS or (
                Gc > ADAPTIVE_MIN_SHRINK * lowering.num_groups
            )
            if sp is not None:
                sp.attrs.update(
                    source=source, compact_groups=Gc, declined=declined,
                    remap=[
                        remap_form(kd, d.cardinality)
                        for d, kd in zip(lowering.dims, kept)
                    ],
                )
            if declined:
                log.info(
                    "adaptive compaction declined: G'=%d of G=%d",
                    Gc, lowering.num_groups,
                )
                self._adaptive_declined.add(qkey)
                self._adaptive_kept.pop(qkey, None)
                return None
            return kept

    def _dispatch_groupby_adaptive(
        self, q: Q.GroupByQuery, ds: DataSource, lowering: GroupByLowering,
        segs, cfg,
    ):
        """Adaptive-compaction attempt.  Returns None when declining at
        dispatch time (no shrink to be had; caller falls through to the
        sparse/scatter paths in the same phase), else resolve() -> df.
        A device error in either phase raises.  `segs`: the query's
        scope as `_dispatch_groupby_once` resolved it, for both phases;
        `cfg`: the cost constants phase B's kernel is chosen by."""
        if not segs:
            return None
        try:
            kept = self._adaptive_kept_codes(q, ds, lowering, segs)
        except DeadlineExceeded as err:
            # the deadline expired during phase A: there are no aggregate
            # partials yet (presence counts are not an answer).  With a
            # partial collector armed, trigger it and decline — the dense
            # path then drains immediately to the well-formed
            # zero-coverage answer; without one, expiry stays an error.
            pc = current_partial()
            if pc is None:
                raise
            pc.trigger(err.site or "adaptive.presence_loop")
            return None
        if kept is None:
            return None
        if any(len(kd) == 0 for kd in kept):
            # some grouping dim has NO present code under the filter: the
            # exact result is the empty grouped frame.  The presence pass
            # scanned the full scope to prove it, so account the pass as
            # fully seen — a deadline trigger later in the lifecycle must
            # stamp coverage 1.0, not flag the exact answer partial.
            pc = current_partial()
            if pc is not None:
                from .engine import _row_counts

                pc.begin_pass()
                pc.add_scope(len(segs), *_row_counts(segs))
                pc.add_seen(len(segs), *_row_counts(segs))
            la = lowering.la
            sums, mins, maxs, sketch_states = empty_partials(la, 0)
            df = finalize_groupby(
                q, lowering.dims, la,
                np.asarray(sums), np.asarray(mins), np.asarray(maxs),
                {k: np.asarray(v) for k, v in sketch_states.items()},
            )
            return lambda: df

        clow = compacted_lowering(lowering, kept)
        cards = tuple(d.cardinality for d in clow.dims)
        # the compact program's kernel comes from the CALIBRATED cost
        # model at the compacted cardinality, not the cutover rule: on a
        # CPU the dense one-hot below it is the wrong side of a ~200x
        # inversion (measured: a 60M-row phase B at G'=600 ran 49 s dense
        # vs sub-second scatter).  On a TPU the dense class is priced on
        # the Pallas kernel it runs as (plan/calibrate.py) and takes every
        # compacted domain the kernel does; the span says which ran
        with span(SPAN_ROUTE, tier="adaptive") as sp:
            strat = shape_kernel(ds.num_rows, clow.num_groups, cfg)
            if sp is not None:
                sp.attrs.update(kernel=strat, groups=clow.num_groups)
        state = self._partials_for_query(
            q, ds, lowering=clow, key_extra=("adaptive",) + cards,
            strategy_override=strat, segs=segs, span_attrs={"phase": "B"},
        )

        def resolve():
            dims, la, G, sums, mins, maxs, sketch_states = state
            # nothing merges this state after the fetch (the tier never
            # captures it): HLL registers cross as their histograms
            sketch_states = estimable_sketch_states(la, sketch_states)
            with span(SPAN_DEVICE_FETCH):
                prof.fetch_sync((sums, mins, maxs, sketch_states))
                sums, mins, maxs, sketch_states = jax.device_get(
                    (sums, mins, maxs, sketch_states)
                )
            if self._m is not None:
                self._m.sketch_state_bytes = state_nbytes(sketch_states)
            t0 = time.perf_counter()
            with span(SPAN_FINALIZE):
                out = finalize_groupby(
                    q, dims, la,
                    np.asarray(sums), np.asarray(mins), np.asarray(maxs),
                    {k: np.asarray(v) for k, v in sketch_states.items()},
                )
            if self._m is not None:
                self._m.finalize_ms = (time.perf_counter() - t0) * 1e3
            return out

        return resolve
