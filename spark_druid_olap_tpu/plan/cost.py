"""Cost model: execution-strategy choice for a lowered query.

Reference parity: `DruidQueryCostModel` (SURVEY.md §2 `[U]`, expected
`org/apache/spark/sql/sources/druid/DruidQueryCostModel.scala`) chooses
between one broker scatter-gather query and N direct per-historical queries,
from tunable per-row/shuffle cost constants.  The TPU analog chooses:

* **kernel strategy** — dense one-hot matmul (MXU; cost grows with G) vs
  scatter segment-sum (VPU serial; cost per row ~constant but high);
* **execution target** — single device vs SPMD mesh (the broker-vs-
  historicals analog: one device is the "broker-only" plan, the mesh is
  "query the historicals directly and merge"), weighing the per-group
  collective bytes against per-device row savings.

Constants live in SessionConfig (the SQLConf analog) so they are tunable the
same way the reference's are.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

from ..catalog.segment import DataSource
from ..config import SessionConfig
from ..models import query as Q


@dataclasses.dataclass(frozen=True)
class PhysicalPlan:
    """The planner's final execution decision for one query spec."""

    query: Q.QuerySpec
    strategy: str  # "dense" | "segment"
    distributed: bool
    mesh_shape: Optional[Tuple[int, int]]  # (data, groups) or None
    est_cost_local: float
    est_cost_dist: float
    num_groups: int
    rows: int

    def describe(self) -> str:
        tgt = (
            f"mesh(data={self.mesh_shape[0]}, groups={self.mesh_shape[1]})"
            if self.distributed and self.mesh_shape
            else "single-device"
        )
        return (
            f"TPUAggregateScan[strategy={self.strategy}, target={tgt}, "
            f"groups={self.num_groups}, rows={self.rows}, "
            f"cost(local)={self.est_cost_local:.3g}, "
            f"cost(dist)={self.est_cost_dist:.3g}]"
        )


def groupby_state_bytes(q: Q.QuerySpec, num_groups: int, cfg: SessionConfig) -> int:
    """Bytes of per-group aggregate state that must cross the merge
    collective (the analog of broker-merge payload size)."""
    from ..models import aggregations as A

    per_group = 0
    aggs = getattr(q, "aggregations", ())
    for a in aggs:
        base = a.aggregator if isinstance(a, A.FilteredAgg) else a
        if isinstance(base, (A.HyperUnique, A.CardinalityAgg)):
            per_group += 4 * (1 << base.precision)
        elif isinstance(base, A.ThetaSketch):
            per_group += 4 * base.size
        else:
            per_group += 4
    return (per_group + 4) * num_groups  # +4: hidden __rows counter


def allreduce_factor(n: int) -> float:
    """Bytes each device sends in a ring allreduce over `n` devices, as a
    multiple of the state's bytes: 2(n-1)/n.  One owner: the planner's
    estimate and the mesh's counted `collective_bytes` both use it."""
    return 2.0 * (n - 1) / max(1, n)


def allgather_factor(n: int) -> float:
    """As `allreduce_factor`, for an all_gather: (n-1) x one device's state."""
    return float(max(0, n - 1))


def choose_merge_tree(
    state_bytes: int,
    n_slices: int,
    nd_per_slice: int,
    cfg: SessionConfig,
) -> Tuple[str, float, float]:
    """Pick the collective merge tree for a multi-slice partial-state
    merge (the arXiv:2603.26698 playbook priced with this platform's
    calibrated constants).  Returns (tree, flat_us, hier_us) where tree
    is "flat" or "hierarchical":

    * flat — one allreduce over slice x data.  Ring cost is
      2(N-1)/N * bytes, but every hop is priced at DCN speed because the
      ring crosses the slice boundary.
    * hierarchical — slice-local allreduce over ICI (2(nd-1)/nd * bytes
      at ICI speed), then one allreduce of the already-merged state over
      the slice axis only (2(ns-1)/ns * bytes at DCN speed).

    With one slice the ring never leaves ICI (flat is priced at ICI
    speed and the trees coincide); flat wins ties so the single-program
    path stays the default."""
    n = max(1, n_slices * nd_per_slice)
    flat_bw = (
        cfg.dcn_bytes_per_us if n_slices > 1 else cfg.collective_bytes_per_us
    )
    flat_us = allreduce_factor(n) * state_bytes / max(1.0, flat_bw)
    hier_us = allreduce_factor(nd_per_slice) * (
        state_bytes / max(1.0, cfg.collective_bytes_per_us)
    ) + allreduce_factor(n_slices) * (
        state_bytes / max(1.0, cfg.dcn_bytes_per_us)
    )
    tree = "hierarchical" if hier_us < flat_us else "flat"
    return tree, flat_us, hier_us


def _g_tiles(num_groups: int) -> int:
    """128-wide vector-lane tiles the one-hot block spans."""
    return max(1, -(-num_groups // 128))


def dense_class_cap(cfg: SessionConfig) -> int:
    """The widest domain the model offers the dense class at: as far as
    `cost_per_row_dense` prices what would run.  The constant is measured
    (plan/calibrate.py) on the kernel `concrete_kernel("dense", g)` names
    on the backend: on a TPU the Pallas kernel, which takes the class only
    up to the one-hot cap — past it the class would launch the XLA one-hot
    at a price taken from another kernel, 12 x apart on the v5e (PR 30).
    Anywhere else the XLA one-hot is what runs and what was timed."""
    from ..ops.groupby import SCATTER_CUTOVER

    if _pallas_ok():
        return min(cfg.dense_max_groups, SCATTER_CUTOVER)
    return cfg.dense_max_groups


def scatter_row_cost(num_groups: int, cfg: SessionConfig) -> float:
    """Per-row scatter cost at this group-domain size: log-linear
    interpolation between the calibrated low-G and high-G anchor points,
    clamped outside them.  Models the cache cliff — random scatter into a
    state that outgrows cache costs several times a cache-resident one
    (measured 0.0015 -> 0.0071 us/row from G=1K to G=2M on CPU); a flat
    per-row constant routed SSB q3_2-class queries onto a 12 s scatter."""
    import math

    lo_g = max(1, cfg.scatter_lo_groups)
    hi_g = max(lo_g + 1, cfg.scatter_hi_groups)
    lo = cfg.cost_per_row_scatter
    # a partial calibration can pair a measured lo with the profile's hi;
    # scatter must never get CHEAPER as G grows
    hi = max(cfg.cost_per_row_scatter_hi, lo)
    if num_groups <= lo_g:
        return lo
    if num_groups >= hi_g:
        return hi
    f = math.log(num_groups / lo_g) / math.log(hi_g / lo_g)
    return lo + (hi - lo) * f


def _kernel_costs(
    rows: int,
    num_groups: int,
    cfg: SessionConfig,
    sparse_ok: bool,
    selectivity: float = 1.0,
    n_segments: int = 1,
    adaptive_ok: bool = False,
    ndims: int = 1,
) -> Tuple[Tuple[str, float], ...]:
    """(strategy, modelled us) for each kernel class (inf = inapplicable).

    `selectivity` is the estimated surviving-row fraction of the query's
    filter (estimate_selectivity).  `n_segments` matters because scatter
    state and the sparse tier's sort network are paid PER SEGMENT (round 3
    modelled them once and underpriced both by ~1000x at SF100's 982
    segments).  The ADAPTIVE class models dictionary-domain compaction
    (exec/adaptive_exec.py): one probe pass measuring per-dim presence,
    then the best kernel over the compacted domain, estimated as
    G' ~ G * selectivity (per-dim admitted fractions multiply the same way
    row selectivities do)."""
    n_segments = max(1, n_segments)
    dense_cap = dense_class_cap(cfg)

    def dense_at(g: int) -> float:
        if g > dense_cap:
            return float("inf")
        return rows * cfg.cost_per_row_dense * _g_tiles(g)

    dense = dense_at(num_groups)

    def scatter_at(g: int) -> float:
        return (
            rows * scatter_row_cost(g, cfg)
            + g * cfg.cost_per_group_state * n_segments
        )

    scatter = scatter_at(num_groups)
    # The compact constant is floored at the scatter per-row cost
    # defensively (see plan/calibrate.py — an over-subtracted constant
    # from an older calibration file must not flip large scans onto the
    # sparse path)
    compact = max(cfg.cost_per_row_compact, cfg.cost_per_row_scatter)
    if not sparse_ok:
        sparse = float("inf")
    elif selectivity >= 1.0:
        sparse = rows * cfg.cost_per_row_sparse  # full-row sort, no compact
    else:
        from ..ops.sparse_groupby import ROW_CAPACITY_LADDER

        # the engine picks the smallest capacity rung covering the
        # estimated survivors PER SEGMENT and sorts that many slots in
        # EVERY segment
        seg_rows = max(1.0, rows / n_segments)
        need = 2.0 * selectivity * seg_rows
        rung = next(
            (c for c in ROW_CAPACITY_LADDER if c >= need), seg_rows
        )
        sorted_rows = n_segments * min(seg_rows, float(rung))
        sparse = rows * compact + sorted_rows * cfg.cost_per_row_sparse
    if not adaptive_ok:
        adaptive = float("inf")
    else:
        g_c = max(1, min(num_groups, round(num_groups * selectivity)))
        probe = rows * ndims * min(
            cfg.cost_per_row_dense, cfg.cost_per_row_scatter
        )
        main = min(scatter_at(g_c), dense_at(g_c))
        # probe amortized over repeats: the kept-set cache (the engine's
        # analog of Druid's bitmap indexes) makes every later execution of
        # the query a single compact-domain pass, and the OLAP workload
        # shape this system exists for (dashboards; the reference's result
        # cache carries the same assumption) repeats queries.  /3 keeps a
        # one-shot query's worst case bounded at ~1.3x the best
        # alternative while routing repeat-heavy shapes onto the path
        # that wins them.  The phase-A probe is also a SEPARATE dispatch;
        # its fixed overhead amortizes with the probe itself — the
        # kept-set cache skips phase A on repeats.
        adaptive = (probe + cfg.cost_dispatch_us) / 3.0 + main
    return (
        ("dense", dense),
        ("segment", scatter),
        ("sparse", sparse),
        ("adaptive", adaptive),
    )


def estimate_selectivity(filt, ds: DataSource) -> float:
    """Estimated surviving-row fraction of a filter spec — dictionary-based
    uniformity assumptions, the classic textbook estimator (the reference's
    DruidQueryCostModel reasoned from segment metadata the same coarse
    way).  Conservative: anything unmodeled estimates 1.0."""
    from ..models import filters as F

    if filt is None:
        return 1.0
    if isinstance(filt, F.And):
        s = 1.0
        for x in filt.fields:
            s *= estimate_selectivity(x, ds)
        return s
    if isinstance(filt, F.Or):
        s = 0.0
        for x in filt.fields:
            s += estimate_selectivity(x, ds)
        return min(1.0, s)
    if isinstance(filt, F.Not):
        return max(0.0, 1.0 - estimate_selectivity(filt.field, ds))
    if isinstance(filt, F.Selector):
        d = ds.dicts.get(filt.dimension)
        if d is None or not d.cardinality:
            return 1.0
        if filt.value is not None and d.code_of(filt.value) is None:
            return 0.0
        return 1.0 / d.cardinality
    if isinstance(filt, F.InFilter):
        d = ds.dicts.get(filt.dimension)
        if d is None or not d.cardinality:
            return 1.0
        hits = sum(1 for v in filt.values if d.code_of(v) is not None)
        return min(1.0, hits / d.cardinality)
    if isinstance(filt, F.Bound):
        d = ds.dicts.get(filt.dimension)
        if d is not None and d.cardinality:
            # fraction of the (sorted) code space the bound admits
            from ..ops.filters import numeric_dict_code_bounds

            nv = d.numeric_values
            if nv is not None and filt.ordering != "lexicographic":
                import numpy as np

                cb = numeric_dict_code_bounds(filt, np.asarray(nv))
                if cb is None:
                    return 1.0
                lo, hi = cb
                lo = 0 if lo is None else max(0, lo)
                hi = d.cardinality - 1 if hi is None else min(
                    d.cardinality - 1, hi
                )
                return max(0.0, (hi - lo + 1) / d.cardinality)
        return 1.0 / 3.0  # classic guess for an un-modeled range
    return 1.0


def choose_kernel_strategy(
    rows: int, num_groups: int, cfg: SessionConfig, sparse_ok: bool = False
) -> str:
    """Min-cost kernel CLASS for a (rows, groups) shape."""
    return min(
        _kernel_costs(rows, num_groups, cfg, sparse_ok), key=lambda kv: kv[1]
    )[0]


# -- which kernel runs: the one place that says ------------------------------
#
# The model above prices CLASSES ("dense" one-hot, "segment" scatter, and
# the high-cardinality tiers "sparse" / "adaptive").  Everything below
# turns a class into what an executor launches; the engines (exec/engine,
# adaptive_exec, sparse_exec, streaming, parallel/distributed) call these
# and keep no rule of their own.


def _pallas_ok() -> bool:
    """Is the compiled Pallas kernel there to route to (a TPU backend)?
    The only routing call of `pallas_available()`."""
    from ..ops.pallas_groupby import pallas_available

    return pallas_available()


def concrete_kernel(strategy: str, groups_per_device: int) -> str:
    """Class -> the kernel of the dense-state path.  "dense" is a class:
    the Pallas kernel is its hand-scheduled implementation and takes it
    whenever a TPU is present and the domain fits the one-hot cap (the
    mesh passes its per-device slice Gl, everybody else G).  "auto",
    and a high-cardinality tier that declined, resolve by the cutover;
    an explicit kernel ("pallas", "segment", "scatter") is honoured."""
    from ..ops.groupby import SCATTER_CUTOVER

    small = groups_per_device <= SCATTER_CUTOVER
    if strategy in ("auto", "sparse", "adaptive"):
        strategy = "dense" if small else "segment"
    if strategy == "dense" and small and _pallas_ok():
        return "pallas"
    return strategy


def shape_kernel(rows: int, groups_per_device: int, cfg: SessionConfig) -> str:
    """The kernel for a bare (rows, groups per device) shape, by the
    calibrated model: what a stream's dispatch and the adaptive tier's
    phase B (one chip and mesh alike) launch."""
    return concrete_kernel(
        choose_kernel_strategy(rows, groups_per_device, cfg),
        groups_per_device,
    )


def presence_kernels(cardinalities) -> Tuple[str, ...]:
    """Per-dimension kernels of the adaptive probe (phase A): one-hot
    kernels on a TPU within the one-hot cap, scatter everywhere else (a
    cardinality-sized scatter state is cache-resident on a CPU, where
    the dense one-hot took 55 s for one SF10 presence pass)."""
    from ..ops.groupby import SCATTER_CUTOVER

    pallas = _pallas_ok()
    return tuple(
        "pallas" if pallas and c <= SCATTER_CUTOVER else "segment"
        for c in cardinalities
    )


def sparse_inner_kernel() -> str:
    """The sparse tier's kernel over its compacted slots: the Pallas
    one-hot on a TPU, scatter elsewhere (4096-slot one-hot matmuls
    starve a CPU).  Past SPARSE_SLOTS a non-scatter inner routes to the
    segmented-reduce tier inside `sparse_partial_aggregate`."""
    return "pallas" if _pallas_ok() else "segment"


def tier_takes(
    tier: str, strategy: str, num_groups: int, has_dims: bool,
    has_sketch: bool,
) -> bool:
    """May the high-cardinality `tier` ("adaptive" | "sparse") take a
    query routed `strategy`?  Both need a domain past the scatter
    cutover and real dimensions; sparse also needs plain aggregates (a
    sketch state is [G, registers] dense and would have to be re-keyed;
    adaptive re-keys them through its rewritten lowering).  An explicit
    kernel is honoured as such: adaptive runs when the model chose it or
    under "auto"; sparse when the model chose it, as adaptive's fallback
    (marginals that did not shrink are the jointly-sparse case), and on
    a TPU in place of a dense-state scatter ("auto" / "dense": on a CPU
    raw scatter beats sort-compaction at every size).  Passing the
    tier's own name asks whether it is eligible at all."""
    from ..ops.groupby import SCATTER_CUTOVER

    if num_groups <= SCATTER_CUTOVER or not has_dims:
        return False
    if tier == "adaptive":
        return strategy in ("auto", "adaptive")
    return not has_sketch and (
        strategy in ("sparse", "adaptive")
        or (strategy in ("auto", "dense") and _pallas_ok())
    )


def lowered_tier_takes(tier: str, lowering, strategy: str) -> bool:
    """`tier_takes` for a lowered query (its dense domain, dimensions and
    sketch aggregations): the one predicate the engine's dense path and
    `rollup_takes` ask."""
    return tier_takes(
        tier, strategy, lowering.num_groups, bool(lowering.dims),
        bool(lowering.la.sketch_aggs),
    )


# The rollup's union state may hold at most this share of the device
# budget the engine keeps residency under: the state, each set's reduced
# state and the theta union's sort temporaries live beside the columns
ROLLUP_STATE_SHARE = 16


# The adaptive tier (exec/adaptive_exec.py) declines a compacted domain
# past this many groups: past it the dense/one-hot inner gains nothing and
# phase A was the only cost (one scan), which the decline memo makes
# one-time ...
ADAPTIVE_MAX_COMPACT_GROUPS = 1 << 17

# ... and one that barely shrinks: compaction cannot pay for its extra
# pass even once.
ADAPTIVE_MIN_SHRINK = 0.5


def compact_route(
    compact_groups: int, q: Q.GroupByQuery, lowering, cfg: SessionConfig,
    budget_bytes: int,
) -> str:
    """Where phase A's kept count G' sends a query the adaptive tier
    probed: `"adaptive"` (phase B over the compacted domain), `"highcard"`
    or `"declined"` (the next tier, or the dense path).

    "highcard" takes G' past ADAPTIVE_MAX_COMPACT_GROUPS: the dense path's
    scatter over the whole domain and its one fetch of the merged state —
    one program a query and scope, whatever the kept set, with no remap
    and no slot ladder (on the v5e at 1.5 M groups and 11 segments 0.47 s
    against 0.75 s for the scatter at G' behind a LUT remap and ~12 s for
    the sparse tier's batch at its 2^21 rung; PERF.md section 6).  It needs
    plain aggregations (a sketch state is [G, registers]) and a dense
    state under `budget_bytes` / ROLLUP_STATE_SHARE, the share the
    rollup's union state may hold."""
    G = lowering.num_groups
    if compact_groups > ADAPTIVE_MIN_SHRINK * G:
        return "declined"
    if compact_groups <= ADAPTIVE_MAX_COMPACT_GROUPS:
        return "adaptive"
    if not lowering.la.sketch_aggs and groupby_state_bytes(
        q, G, cfg
    ) <= budget_bytes // ROLLUP_STATE_SHARE:
        return "highcard"
    return "declined"


def _has_rollup(agg) -> bool:
    """Does a coarser group's state of `agg` follow from its children's
    exactly?  Sums and counts add, mins and maxs fold, HLL registers take
    the max, theta states take the KMV union; a quantiles sketch's
    compaction does not commute with a union, so it falls back."""
    from ..models import aggregations as A

    if isinstance(agg, A.FilteredAgg):
        agg = agg.aggregator
    return isinstance(agg, (
        A.Count, A.LongSum, A.DoubleSum, A.LongMin, A.LongMax, A.DoubleMin,
        A.DoubleMax, A.DimCodeMax, A.ExpressionAgg, A.HyperUnique,
        A.CardinalityAgg, A.ThetaSketch,
    ))


def rollup_takes(
    q: Q.GroupByQuery, sets, lowering, strategy: str, cfg: SessionConfig,
    budget_bytes: int,
) -> bool:
    """May a grouping-set request (`q` grouped by the union of the sets'
    dimensions, `lowering` its lowered form) run as ONE scan, every set
    reduced from the merged state on the device?  Where the union runs
    on the dense path (no high-cardinality tier takes it), its state is
    under `budget_bytes` / ROLLUP_STATE_SHARE and every aggregation has a
    rollup; else each set scans on its own.  A time granularity (an
    implicit time dimension in every set) is left to the per-set form."""
    if not sets or q.granularity not in ("all", None):
        return False
    if any(
        lowered_tier_takes(t, lowering, strategy)
        for t in ("adaptive", "sparse")
    ):
        return False
    if groupby_state_bytes(q, lowering.num_groups, cfg) > (
        budget_bytes // ROLLUP_STATE_SHARE
    ):
        return False
    return all(_has_rollup(a) for a in q.aggregations)


def query_kernel_costs(
    q: Q.QuerySpec,
    ds: DataSource,
    num_groups: int,
    cfg: SessionConfig,
    selectivity: Optional[float] = None,
) -> dict:
    """strategy -> modelled microseconds for a PLANNED query over `ds`: the
    kernel half of `choose_physical`.  Tier eligibility is `tier_takes`,
    the predicate the engines ask."""
    from ..models import aggregations as A

    rows = ds.num_rows
    aggs = getattr(q, "aggregations", ())
    has_sketch = any(
        isinstance(
            a.aggregator if isinstance(a, A.FilteredAgg) else a,
            (A.HyperUnique, A.CardinalityAgg, A.ThetaSketch),
        )
        for a in aggs
    )
    dims = getattr(q, "dimensions", ())
    if isinstance(q, Q.TopNQuery):  # runs as a groupBy by its one dimension
        dims = (q.dimension,)
    sparse_ok, adaptive_ok = (
        tier_takes(t, t, num_groups, bool(dims), has_sketch)
        for t in ("sparse", "adaptive")
    )
    segs = getattr(ds, "segments", None)
    n_segments = (
        len(segs) if segs is not None else max(1, rows // (1 << 22))
    )
    sel = (
        selectivity
        if selectivity is not None
        else estimate_selectivity(getattr(q, "filter", None), ds)
    )
    return dict(
        _kernel_costs(
            rows, num_groups, cfg, sparse_ok,
            selectivity=sel,
            n_segments=n_segments,
            adaptive_ok=adaptive_ok,
            ndims=max(1, len(dims)),
        )
    )


def choose_query_kernel(
    q: Q.QuerySpec,
    ds: DataSource,
    num_groups: int,
    cfg: SessionConfig,
    exclude: Tuple[str, ...] = (),
    costs: Optional[dict] = None,
) -> str:
    """Min-cost kernel class for a planned query: `choose_physical`'s
    strategy choice.  `exclude` masks classes the caller will not run
    (a decline memo); `costs` accepts a precomputed query_kernel_costs
    dict so choose_physical does not pay the selectivity walk twice."""
    if costs is None:
        costs = query_kernel_costs(q, ds, num_groups, cfg)
    return min(
        (kv for kv in costs.items() if kv[0] not in exclude),
        key=lambda kv: kv[1],
    )[0]


def route_query(
    strategy: str,
    q: Q.QuerySpec,
    ds: DataSource,
    num_groups: int,
    groups_per_device: int,
    cfg: SessionConfig,
    declined: Tuple[str, ...] = (),
) -> str:
    """What the mesh runs for a query handed the class `strategy`: the
    tier itself ("adaptive" / "sparse") or the dense-state kernel.  The
    planner's answer is taken as handed; the model runs again only where
    it must: no class was chosen ("auto": an engine built without a
    plan), or a decline memo excludes the one that was."""
    if strategy == "auto" or strategy in declined:
        strategy = choose_query_kernel(
            q, ds, num_groups, cfg, exclude=declined
        )
    if strategy in ("sparse", "adaptive"):
        return strategy
    return concrete_kernel(strategy, groups_per_device)


def choose_physical(
    q: Q.QuerySpec,
    ds: DataSource,
    num_groups: int,
    cfg: SessionConfig,
    n_devices: int = 1,
) -> PhysicalPlan:
    """Pick kernel strategy + execution target (the DruidQueryCostModel
    broker-vs-historicals analog).  All costs in microseconds, from the
    calibratable SessionConfig constants (plan/calibrate.py)."""
    rows = ds.num_rows
    # Three kernel classes, chosen by modelled cost (all constants
    # calibratable on the live backend — plan/calibrate.py):
    #   dense   one-hot matmul: cost scales with ceil(G/128) lane tiles (MXU-
    #           shaped; the winner on TPU for small/medium domains)
    #   segment raw scatter: flat per-row cost (serializes on TPU, cheap on
    #           CPU) + per-group dense-state cost
    #   sparse  sort-compaction: flat-but-sort-heavy per-row cost, no dense
    #           state — the high-cardinality path where it applies (real
    #           dims, no sketch state to re-key)
    # kernel-class eligibility + costs shared with every executor
    # (query_kernel_costs); adaptive compaction re-keys sketch states
    # transparently (the compact program IS the normal program over a
    # rewritten lowering), so sketches do not disqualify it there.  The
    # selectivity tree walk runs ONCE and feeds both the local and the
    # per-device cost evaluations below.
    sel = estimate_selectivity(getattr(q, "filter", None), ds)
    costs = query_kernel_costs(q, ds, num_groups, cfg, selectivity=sel)
    strategy = choose_query_kernel(q, ds, num_groups, cfg, costs=costs)
    local_cost = costs[strategy]

    # distributed target: since round 5 the FULL kernel ladder runs SPMD
    # (parallel/distributed.py routes dense/scatter/sparse/adaptive per
    # shard), so every GroupBy-family strategy is mesh-eligible; scans
    # stay single-device by construction
    aggregate_family = isinstance(
        q, (Q.GroupByQuery, Q.TimeseriesQuery, Q.TopNQuery)
    )
    distributed = False
    mesh_shape = None
    dist_cost = local_cost
    if n_devices > 1 and aggregate_family:
        ng = max(1, cfg.mesh_groups_axis)
        nd = cfg.mesh_data_axis or max(1, n_devices // ng)
        nd = min(nd, max(1, n_devices // ng))
        # rows shard over the data axis (replicated across the groups axis);
        # the groups axis shards the group-id domain, shrinking per-device G
        # for the one-hot block, the sketch states, AND the sparse slot
        # capacity alike.  Per-shard compute comes from the SAME model at
        # the per-device shape for every class (no duplicated formulas).
        per_device_groups = -(-num_groups // ng)
        compute = dict(
            _kernel_costs(
                max(1, rows // nd), per_device_groups, cfg,
                sparse_ok=strategy == "sparse",
                selectivity=sel,
                n_segments=1,  # one shard per device
                adaptive_ok=strategy == "adaptive",
                ndims=max(1, len(getattr(q, "dimensions", ()) or ())),
            )
        )[strategy]
        # collective bytes are what the merge ACTUALLY moves: the dense/
        # scatter rungs allreduce the full [Gl, M] state, but the sparse
        # rung all_gathers only slot-compacted state and adaptive merges
        # the compacted domain — both bounded by the POPULATED group count
        # (~ G x selectivity), not the domain (pricing the full domain
        # silently kept exactly the high-G queries the mesh ladder exists
        # for off the mesh)
        if strategy in ("sparse", "adaptive"):
            g_eff = max(
                1, min(per_device_groups, round(num_groups * sel))
            )
            state_bytes = groupby_state_bytes(q, g_eff, cfg)
            factor = allgather_factor(nd)
        else:
            state_bytes = groupby_state_bytes(q, per_device_groups, cfg)
            factor = allreduce_factor(nd)
        collective = (
            factor * state_bytes / max(cfg.collective_bytes_per_us, 1e-9)
        )
        dist_cost = compute + collective + cfg.cost_dispatch_us
        distributed = cfg.prefer_distributed and dist_cost < local_cost
        if distributed:
            mesh_shape = (nd, ng)
    return PhysicalPlan(
        query=q,
        strategy=strategy,
        distributed=distributed,
        mesh_shape=mesh_shape,
        est_cost_local=local_cost,
        est_cost_dist=dist_cost,
        num_groups=num_groups,
        rows=rows,
    )
