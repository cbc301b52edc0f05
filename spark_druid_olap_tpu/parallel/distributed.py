"""Distributed GroupBy execution: shard_map over a device mesh + ICI merge.

Reference parity: this is the Druid **broker scatter-gather** rebuilt on XLA
collectives (SURVEY.md §2 parallelism table, §3.3 `[U]`).  In the reference,
the broker fans a query to historicals, each computes per-segment partial
aggregates, and the broker merges partials (sum-merge, min/max-merge, HLL
register-max, sketch union).  Here:

* historicals  → mesh devices, each holding a row shard in HBM
* HTTP fan-out → `shard_map` over the ``data`` axis (one traced program, SPMD)
* broker merge → `lax.psum` (sums/counts), `lax.pmin`/`pmax` (extrema, HLL
  registers), `all_gather` + KMV-union fold (theta) — riding ICI, with DCN
  handled transparently by the same collectives on multi-host meshes
* Spark-side final merge → `exec.engine.finalize_groupby` on the replicated
  [G, M] state (tiny)

The ``groups`` mesh axis additionally shards the group-id domain (the
TP-analog): each device matches only its slice of [0, G), shrinking the
one-hot block and sketch states by the axis size; no collective is needed on
that axis — outputs stay group-sharded until the host gathers them.

**Kernel ladder (VERDICT r4 #1).**  What runs per shard is `plan/cost.py`'s
to say, as on one chip: a planned query brings its class with it
(`execute(..., strategy=, cfg=)`) and `plan.cost.route_query` turns it into
this mesh's kernel at the per-device group slice; the model runs again only
for a query without a plan or after a decline memo.  The full ladder runs
SPMD:

* dense / Pallas one-hot  — small G (psum/pmin/pmax merge over ``data``)
* segment scatter         — large G, dense [Gl, M] state, same collectives
* sparse sort-compaction  — huge domain, few present: per-device
  `sparse_partial_aggregate` (slots ladder included), then an
  `all_gather` + `merge_sparse_states` fold over ``data`` — the broker
  merge in sparse-state form.  The ``groups`` axis shards the *group-id
  domain* (each device keeps only gids in its slice), multiplying slot
  capacity by the axis size.
* adaptive domain compaction — a distributed phase A measures per-dim
  presence counts (tiny per-dim GroupBys, psum-merged like any aggregate);
  the host builds the kept-code LUTs; phase B is the normal SPMD program
  over the compacted lowering (LUTs broadcast as staged jit constants).

**Durable shard residency (VERDICT r4 #3).**  Row shards are keyed by
(datasource, column, data-axis size, FULL segment signature) — never by a
query's pruned segment scope — so assembly is paid once per datasource
version, like Druid historicals owning their segments across queries.
Correctness needs no segment exclusion: the row mask (intervals + the full
filter) already excludes every row interval/zone pruning would have dropped,
so pruning here only narrows the *metrics* scope.

Long-context analog (SURVEY.md §5): rows are the "sequence" axis.  Blockwise
partial aggregation over row chunks + ring/allreduce merge of aggregate state
is the same communication shape ring-attention uses for KV blocks — scaling
group-by past one chip's HBM without materializing anything global.
"""

from __future__ import annotations

import threading as _threading
import time as _time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..catalog.segment import ROW_PAD, DataSource
from ..exec.engine import (
    GroupByLowering,
    HostStateSurface,
    finalize_groupby,
    groupby_family,
    groupby_with_time_granularity,
)
from ..exec.state import PartialState, estimable, to_host
from ..models import aggregations as A
from ..models import query as Q
from ..ops import hll as hll_ops
from ..ops import quantiles as quantiles_ops
from ..ops import theta as theta_ops
from ..obs import (
    SCOPE_BOUNDARY_MERGE,
    SPAN_ADAPTIVE_KEPT,
    SPAN_ADAPTIVE_PROBE,
    SPAN_ARENA_BUILD,
    SPAN_DEVICE_FETCH,
    SPAN_FINALIZE,
    SPAN_PROGRAM_LOOKUP,
    SPAN_ROUTE,
    SPAN_SEGMENT_DISPATCH,
    SPAN_SPARSE_DISPATCH,
    current_query_id,
    device_scope,
    prof,
    record_query_metrics,
    span,
    span_around,
    span_event,
)
from ..ops.groupby import (
    choose_block_rows,
    dense_partial_aggregate,
    partial_aggregate,
    scatter_partial_aggregate,
)
from ..plan.cost import (
    presence_kernels,
    route_query,
    shape_kernel,
    sparse_inner_kernel,
)
from ..utils.log import get_logger
from . import spmd_arena
from .mesh import (
    DATA_AXIS,
    GROUPS_AXIS,
    SLICE_AXIS,
    make_mesh,
    row_axes,
)
from .multihost import initialize as multihost_initialize, put_sharded

log = get_logger("parallel.distributed")

_SPARSE_STATE_KEYS = ("gids", "sums", "mins", "maxs")
_SPARSE_FLAG_KEYS = ("overflow", "row_overflow", "n_rows", "n_real")


def _launch(run, *args):
    """Call a compiled SPMD program inside the caller's launch span: the
    call returns when the program is enqueued (a sampled query waits for
    the device here, `obs/prof.py`)."""
    t_call = _time.perf_counter()
    return prof.dispatch_sync(run(*args), t_call)


def _fetch(tree):
    """The blocking copy back of a launched program's merged state, under
    `device_fetch`: the wait for the device is paid here."""
    with span(SPAN_DEVICE_FETCH):
        prof.fetch_sync(tree)
        return jax.device_get(tree)


def _nbytes(tree) -> int:
    """Bytes of a tree's arrays, host or device (no device array is
    fetched to count it)."""
    return sum(int(x.nbytes) for x in jax.tree.leaves(tree))


class DistributedEngine(HostStateSurface):
    """Executes GroupBy-family queries SPMD over a mesh.

    Row shards are built host-side by concatenating ALL segment columns and
    padding to a multiple of (mesh data size × ROW_PAD); `jax.device_put`
    with a NamedSharding places each shard in its device's HBM.  Residency
    is durable across queries (see module docstring)."""

    def __init__(
        self,
        mesh: Optional[Mesh] = None,
        shard_cache_bytes: int = 4 << 30,
        program_cache_entries: int = 128,
        strategy: str = "auto",
        config=None,
    ):
        from ..config import SessionConfig
        from ..utils.lru import ByteBudgetCache, CountBudgetCache

        # multi-host runtime formation (parallel/multihost.py) rides the
        # unified core's construction: a no-op single-process, it resolves
        # the jax.distributed cluster from env markers on real pods so the
        # mesh below spans every host's devices (ISSUE 15 satellite)
        multihost_initialize()
        if mesh is not None and SLICE_AXIS in mesh.shape:
            # virtual multi-slice topology: the slice mesh drives ONLY the
            # unified arena path (placement + merge tree).  Legacy SPMD
            # programs keep their (data, groups) contract by flattening
            # the slice x data product onto the data axis — the mesh is a
            # placement strategy, not a fork of the executor.
            self.slice_mesh = mesh
            devs = list(mesh.devices.flat)
            self.mesh = make_mesh(n_data=len(devs), n_groups=1, devices=devs)
        else:
            self.slice_mesh = None
            self.mesh = mesh if mesh is not None else make_mesh()
        # `strategy` / `config`: what a call WITHOUT a plan runs under,
        # same contract as exec.engine.Engine ("auto" routes by the
        # calibrated cost model; an explicit kernel class is honored as
        # such).  A planned query brings its own as arguments of
        # execute(); nothing writes these after construction.  Validated
        # here: an unknown
        # string would otherwise fall into the dense one-hot branch — at
        # high G that is a pathological compile, not an error message
        if strategy not in (
            "auto", "dense", "pallas", "segment", "scatter", "sparse",
            "adaptive",
        ):
            raise ValueError(f"unknown groupby strategy {strategy!r}")
        self.strategy = strategy
        self.config = config or SessionConfig.load_calibrated()
        self.last_metrics = None  # observability (exec/metrics.py)
        # row-shard cache: keyed by (ds, column, data-axis, full segment
        # signature) — durable across queries; LRU under a byte budget
        self._shard_cache = ByteBudgetCache(shard_cache_bytes)
        # compiled SPMD program cache (query shape x schema x local rows x
        # strategy); without it every execute() re-traces the shard_map
        self._spmd_cache = CountBudgetCache(program_cache_entries)
        # lowering cache: rebuilding a lowering stages device constants
        # (dictionary remaps, bucket tables) — one blocking H2D per constant
        # on every execution without it (same as exec/engine.py)
        self._lowering_cache = CountBudgetCache(program_cache_entries)
        # kernel-ladder memos, mirroring exec/engine.py Engine.__init__:
        # adaptive kept-code sets + decline memo, remembered sparse rungs,
        # and sparse declines (ladder exhausted -> route straight to
        # scatter on repeats)
        self._adaptive_kept: Dict = {}
        self._adaptive_declined: set = set()
        self._sparse_slots: Dict = {}
        self._sparse_row_capacity: Dict = {}
        self._sparse_declined: set = set()
        # resilience wiring (resilience.py): same contract as
        # exec.engine.Engine — transient failures/recoveries report to the
        # breaker (TPUOlapContext swaps in its shared one); the breaker
        # gates routing at the api layer, never execution here
        from ..resilience import CircuitBreaker

        self.breaker = CircuitBreaker()
        self._retry_attempts = 2
        self._retry_backoff_ms = 25.0
        # unified SPMD-arena core (ISSUE 15): the stacked [B, R] layout
        # shared with exec/arena.py, sharded over the row devices.
        # TPUOlapContext syncs this from SessionConfig.arena_execution,
        # same contract as the local engine's toggle.
        self.arena_execution = True
        # per-thread state-capture holder (delta-aware result cache):
        # mirrors exec.engine.Engine._m_local
        self._m_local = _threading.local()

    def _lowering_for(self, q: Q.GroupByQuery, ds: DataSource):
        from ..exec.lowering import cached_lowering

        return cached_lowering(self._lowering_cache, q, ds)

    # -- host-side row-shard assembly ---------------------------------------

    def _global_columns(self, ds: DataSource, names, segs=None):
        """Assemble (or reuse) sharded columns over a segment scope.

        Durable residency: the key has no query component beyond the
        segment scope, so every query sharing a scope against this
        datasource version reuses the same placed shards —
        `shard_assembly_ms` is paid once per (scope, column), the
        analog of historicals owning segments across queries (SURVEY.md §2
        data-parallelism row; VERDICT r4 #3).  A fixed per-scope layout
        also keeps `local_rows` constant, so SPMD programs cache across
        queries with the same scope.

        `segs` is the interval/zone-map PRUNED scope (the r5->r6 mesh
        regression fix: the mesh used to shard the FULL set for every
        query and pay a full-scope scan where the single-device engine
        pruned — profiled at SF1, ~100% of the flat ~400 ms/query floor
        was device time over rows pruning would have skipped).  None
        means the full set (streaming / scope-free callers)."""
        nd = self.mesh.shape[DATA_AXIS]
        segs = list(ds.segments) if segs is None else list(segs)
        total = sum(s.num_rows_padded for s in segs)
        chunk = nd * ROW_PAD
        padded = -(-max(total, 1) // chunk) * chunk
        sharding = NamedSharding(self.mesh, P(DATA_AXIS))
        seg_sig = tuple(s.uid for s in segs)

        def build(name: str, fill) -> jax.Array:
            # "col"/"valid" tags: a user column literally named
            # "__valid" must not alias the validity-mask entry (GL1301)
            key = (ds.name, "col", name, nd, seg_sig)
            hit = self._shard_cache.get(key)
            if hit is not None:
                return hit
            parts = [np.asarray(s.column(name)) for s in segs]
            host = np.concatenate(parts) if parts else np.zeros(0)
            if len(host) < padded:
                host = np.concatenate(
                    [host, np.full(padded - len(host), fill, dtype=host.dtype)]
                )
            arr = put_sharded(host, sharding)
            self._shard_cache[key] = arr
            return arr

        cols: Dict[str, jax.Array] = {}
        for n in names:
            fill = -1 if n in ds.dicts else 0
            cols[n] = build(n, fill)
        vkey = (ds.name, "valid", nd, seg_sig)
        valid = self._shard_cache.get(vkey)
        if valid is None:
            parts = [s.valid for s in segs]
            host = (
                np.concatenate(parts) if parts else np.zeros(0, dtype=bool)
            )
            if len(host) < padded:
                host = np.concatenate(
                    [host, np.zeros(padded - len(host), dtype=bool)]
                )
            valid = put_sharded(host, sharding)
            self._shard_cache[vkey] = valid
        cols["__valid"] = valid
        if ds.time_column and ds.time_column in cols:
            cols["__time"] = cols[ds.time_column]
        return cols, padded

    def _scope_for_metrics(self, q, ds: DataSource):
        """Interval + zone-map pruned segment scope — shared with the
        local engine's exact pruning policy.  `_execute_groupby_once`
        resolves it once and hands it down: the metrics, the arena's
        window and the shard layout all read that one list
        (`_place_shards` assembles only the pruned scope; the row mask
        still excludes within surviving segments)."""
        from ..exec.engine import segments_in_scope

        return segments_in_scope(q, ds)

    def clear_cache(self):
        self._shard_cache.clear()
        self._lowering_cache.clear()
        self._spmd_cache.clear()

    # -- SPMD programs -------------------------------------------------------

    def _mesh_key(self) -> Tuple:
        return tuple(sorted(self.mesh.shape.items()))

    def _groups_split(self, G: int) -> Tuple[int, int]:
        """(ng, Gl): group-domain shard count and per-device slice size.
        The axis must divide G; otherwise groups are replicated."""
        ng = self.mesh.shape[GROUPS_AXIS]
        if G % ng:
            ng = 1
        return ng, G // max(ng, 1)

    @span_around(SPAN_PROGRAM_LOOKUP)
    def _spmd_fn(
        self,
        lowering: GroupByLowering,
        local_rows: int,
        ds: DataSource,
        col_keys: Tuple[str, ...],
        strategy: str = "dense",
        key_extra: Tuple = (),
    ):
        """Build (or fetch) the compiled dense-state SPMD program.

        `strategy` routes the per-shard kernel (dense one-hot / Pallas /
        segment scatter); all produce the same [Gl, M] state, so the
        psum/pmin/pmax broker merge is strategy-independent.  Cached on
        (query shape, schema signature, local rows, mesh shape, strategy,
        key_extra): jit's compilation cache is keyed on callable identity,
        so rebuilding the closure per query would recompile every time."""
        from ..exec.lowering import _query_key

        # "dense-state" pins this family apart from the "sparse" /
        # "adaptive-presence" tuples sharing _spmd_cache (GL1301)
        cache_key = _query_key(lowering.query, ds) + (
            local_rows,
            self._mesh_key(),
            "dense-state",
            strategy,
        ) + tuple(key_extra)

        if cache_key in self._spmd_cache:
            prof.note_program_cache("dense-state", hit=True)
            return self._spmd_cache[cache_key]
        prof.note_program_cache("dense-state", hit=False)
        G = lowering.num_groups
        la = lowering.la
        ng, Gl = self._groups_split(G)
        num_min, num_max = len(la.min_names), len(la.max_names)
        sketches = list(la.sketch_aggs)
        block = choose_block_rows(local_rows, Gl)
        while local_rows % block:
            block -= ROW_PAD
        block = max(block, ROW_PAD)

        def shard_fn(cols: Dict[str, jax.Array]):
            cols = lowering.add_virtual(dict(cols))  # sketches read virtuals
            gid, mask, sv, mmv, mmm = lowering.row_arrays(
                cols, strategy=strategy
            )
            if ng > 1:
                off = lax.axis_index(GROUPS_AXIS).astype(jnp.int32) * Gl
                gid_l = gid - off  # ids outside [0, Gl) never match the iota
                if strategy in ("segment", "scatter"):
                    # scatter indexes the state directly — out-of-slice ids
                    # must be masked, not merely non-matching
                    mask = mask & (gid_l >= 0) & (gid_l < Gl)
            else:
                gid_l = gid
            if strategy in ("segment", "scatter"):
                sums, mins, maxs = scatter_partial_aggregate(
                    gid_l, mask, sv, mmv, mmm,
                    num_groups=Gl, num_min=num_min, num_max=num_max,
                )
            elif strategy == "pallas":
                sums, mins, maxs = partial_aggregate(
                    gid_l, mask, sv, mmv, mmm,
                    num_groups=Gl, num_min=num_min, num_max=num_max,
                    strategy="pallas",
                )
            else:
                sums, mins, maxs = dense_partial_aggregate(
                    gid_l, mask, sv, mmv, mmm,
                    num_groups=Gl, block_rows=block,
                    num_min=num_min, num_max=num_max,
                )
            # broker-merge over the data axis (ICI collectives)
            with device_scope(SCOPE_BOUNDARY_MERGE):
                sums = lax.psum(sums, DATA_AXIS)
                if num_min:
                    mins = lax.pmin(mins, DATA_AXIS)
                if num_max:
                    maxs = lax.pmax(maxs, DATA_AXIS)
            sk_out = {}
            for agg in sketches:
                # per-agg FILTER mask composes with the row mask (same
                # contract as the local engine's sketch partials)
                mfn = la.mask_fns.get(agg.name)
                amask = mask & mfn(cols) if mfn is not None else mask
                if isinstance(agg, (A.HyperUnique, A.CardinalityAgg)):
                    st = hll_ops.partial_hll(agg, cols, gid_l, amask, Gl)
                    with device_scope(SCOPE_BOUNDARY_MERGE):
                        sk_out[agg.name] = lax.pmax(st, DATA_AXIS)
                elif isinstance(agg, A.QuantilesSketch):
                    st = quantiles_ops.partial_quantiles(
                        agg, cols, gid_l, amask, Gl
                    )
                    with device_scope(SCOPE_BOUNDARY_MERGE):
                        gathered = lax.all_gather(st, DATA_AXIS)  # [nd,Gl,K+1,2]
                    acc = gathered[0]
                    for i in range(1, gathered.shape[0]):
                        acc = quantiles_ops.merge_states(
                            acc, gathered[i], agg.size
                        )
                    sk_out[agg.name] = acc
                else:
                    st = theta_ops.partial_theta(agg, cols, gid_l, amask, Gl)
                    with device_scope(SCOPE_BOUNDARY_MERGE):
                        gathered = lax.all_gather(st, DATA_AXIS)  # [nd, Gl, K]
                    acc = gathered[0]
                    for i in range(1, gathered.shape[0]):
                        acc = theta_ops.merge_states(acc, gathered[i], agg.size)
                    sk_out[agg.name] = acc
            return sums, mins, maxs, sk_out

        specs = {n: P(DATA_AXIS) for n in col_keys}
        gspec = P(GROUPS_AXIS) if ng > 1 else P()
        out_spec = (gspec, gspec, gspec, {a.name: gspec for a in sketches})
        run = jax.jit(
            jax.shard_map(
                shard_fn,
                mesh=self.mesh,
                in_specs=(specs,),
                out_specs=out_spec,
                check_vma=False,
            )
        )
        self._spmd_cache[cache_key] = run
        return run

    def _spmd_sparse_fn(
        self,
        lowering: GroupByLowering,
        local_rows: int,
        ds: DataSource,
        col_keys: Tuple[str, ...],
        slots: int,
        row_capacity: Optional[int],
    ):
        """Sparse sort-compaction SPMD program.

        Per device: compact the local shard's surviving rows, aggregate
        into `slots` sparse slots (one-hot within SPARSE_SLOTS, the
        segmented-reduce tier above).  Merge over the data axis is an
        `all_gather` + `merge_sparse_states` fold — the broker merge in
        sparse-state form.  The groups axis shards the GROUP-ID DOMAIN:
        each device keeps only gids in its slice (global ids preserved in
        the state), so the concatenated output holds up to ng × slots
        distinct groups with disjoint gid sets — finalize_groupby's
        slot_gids layout handles it unchanged.

        Returns (state, flags): state arrays are [ng*(slots+1), ...]
        (gids/sums/mins/maxs), flags are [ng] per-slice scalars
        (overflow / row_overflow / n_rows / n_real)."""
        from ..exec.lowering import _query_key
        from ..ops.sparse_groupby import (
            merge_sparse_states,
            sparse_partial_aggregate,
        )

        inner = sparse_inner_kernel()
        # structured key, NOT an f-string (graftlint jit-cache/GL103)
        cache_key = _query_key(lowering.query, ds) + (
            local_rows,
            self._mesh_key(),
            "sparse", inner, row_capacity, slots,
        )
        if cache_key in self._spmd_cache:
            return self._spmd_cache[cache_key]
        G = lowering.num_groups
        la = lowering.la
        ng, Gl = self._groups_split(G)
        num_min, num_max = len(la.min_names), len(la.max_names)
        nd = self.mesh.shape[DATA_AXIS]

        def shard_fn(cols: Dict[str, jax.Array]):
            gid, mask, sv, mmv, mmm = lowering.row_arrays(dict(cols))
            if ng > 1:
                off = lax.axis_index(GROUPS_AXIS).astype(jnp.int32) * Gl
                mask = mask & (gid >= off) & (gid < off + Gl)
            st = sparse_partial_aggregate(
                gid, mask, sv, mmv, mmm,
                num_groups=G, num_min=num_min, num_max=num_max,
                slots=slots, inner_strategy=inner,
                row_capacity=row_capacity,
            )
            with device_scope(SCOPE_BOUNDARY_MERGE):
                gathered = jax.tree.map(
                    lambda x: lax.all_gather(x, DATA_AXIS), st
                )
            acc = jax.tree.map(lambda x: x[0], gathered)
            for i in range(1, nd):
                acc = merge_sparse_states(
                    acc,
                    jax.tree.map(lambda x, i=i: x[i], gathered),
                    num_groups=G,
                )
            state = {k: acc[k] for k in _SPARSE_STATE_KEYS}
            flags = {
                k: acc[k].reshape(1) for k in _SPARSE_FLAG_KEYS
            }
            return state, flags

        specs = {n: P(DATA_AXIS) for n in col_keys}
        gspec = P(GROUPS_AXIS) if ng > 1 else P()
        out_spec = (
            {k: gspec for k in _SPARSE_STATE_KEYS},
            {k: gspec for k in _SPARSE_FLAG_KEYS},
        )
        run = jax.jit(
            jax.shard_map(
                shard_fn,
                mesh=self.mesh,
                in_specs=(specs,),
                out_specs=out_spec,
                check_vma=False,
            )
        )
        self._spmd_cache[cache_key] = run
        return run

    def _presence_fn(
        self,
        lowering: GroupByLowering,
        local_rows: int,
        ds: DataSource,
        col_keys: Tuple[str, ...],
    ):
        """Adaptive phase A as an SPMD program: per-dim presence counts
        under the query's row mask, psum-merged over the data axis like any
        aggregate (VERDICT r4 #1's prescription).  Output is replicated
        (cardinality-sized vectors, tiny)."""
        from ..exec.lowering import _query_key

        strategies = presence_kernels(d.cardinality for d in lowering.dims)
        cache_key = _query_key(lowering.query, ds) + (
            local_rows,
            self._mesh_key(),
            "adaptive-presence",
            strategies,
        )
        if cache_key in self._spmd_cache:
            return self._spmd_cache[cache_key]

        def shard_fn(cols: Dict[str, jax.Array]):
            cols = lowering.add_virtual(dict(cols))
            mask = lowering.row_mask(cols)
            ones = mask.astype(jnp.float32)[:, None]
            zero_mm = jnp.zeros((ones.shape[0], 0), jnp.float32)
            zero_mmm = jnp.zeros((ones.shape[0], 0), jnp.bool_)
            per = []
            for d, strat in zip(lowering.dims, strategies):
                s, _, _ = partial_aggregate(
                    d.codes_fn(cols), mask,
                    # the kernel counts from its match tile
                    (None,) if strat == "pallas" else ones,
                    zero_mm, zero_mmm,
                    num_groups=d.cardinality, num_min=0, num_max=0,
                    strategy=strat,
                )
                with device_scope(SCOPE_BOUNDARY_MERGE):
                    per.append(lax.psum(s[:, 0], DATA_AXIS))
            return per

        specs = {n: P(DATA_AXIS) for n in col_keys}
        run = jax.jit(
            jax.shard_map(
                shard_fn,
                mesh=self.mesh,
                in_specs=(specs,),
                out_specs=[P() for _ in lowering.dims],
                check_vma=False,
            )
        )
        self._spmd_cache[cache_key] = run
        return run

    # -- entry points --------------------------------------------------------

    def execute(self, q: Q.QuerySpec, ds: DataSource, strategy=None, cfg=None):
        """`strategy` / `cfg`: the plan's kernel class and the session's
        cost constants; None = the constructor's."""
        # Timeseries/TopN rewrites + finalization are shared with the local
        # engine (exec/engine.py) so distributed semantics cannot drift.
        q, shape = groupby_family(q, ds)
        assert q is not None, "GroupBy-family queries only"
        # idempotent re-dispatch on transient device failure, mirroring
        # exec/engine.py: the SAME shared retry/backoff/breaker policy
        # (resilience.run_device_attempts), differing only in what a
        # failed dispatch has to evict (shards + SPMD programs here)
        from ..resilience import run_device_attempts

        q = groupby_with_time_granularity(q)

        def evict():
            from ..exec.lowering import _query_key

            qkey = _query_key(q, ds)
            self._lowering_cache.pop(qkey)
            # spmd keys are _query_key + (local_rows, mesh, ...): evict
            # only this query's programs, not every cached compile
            for k in [k for k in self._spmd_cache if k[:2] == qkey]:
                self._spmd_cache.pop(k)
            for k in [k for k in self._shard_cache if k[0] == ds.name]:
                self._shard_cache.pop(k)

        return shape(run_device_attempts(
            self,
            lambda: self._execute_groupby_once(q, ds, strategy, cfg),
            evict,
            what="mesh device",
        ))

    def _route(self, q, ds, lowering, qkey, strategy=None, cfg=None) -> str:
        """`plan.cost.route_query` for this mesh: the handed class (else
        the constructor's) at the per-device group slice, with this
        engine's decline memos."""
        declined = tuple(
            tier
            for tier, memo in (
                ("adaptive", self._adaptive_declined),
                ("sparse", self._sparse_declined),
            )
            if qkey in memo
        )
        return route_query(
            strategy or self.strategy, q, ds, lowering.num_groups,
            self._groups_split(lowering.num_groups)[1],
            cfg or self.config, declined,
        )

    def _execute_groupby_once(
        self, q: Q.GroupByQuery, ds: DataSource, strategy=None, cfg=None
    ):
        from ..exec.lowering import memo_key
        from ..exec.metrics import QueryMetrics

        from ..resilience import (
            checkpoint, checkpoint_partial, current_partial, fire,
        )

        # deadline checkpoint + device-dispatch fault site: the SPMD path
        # honors the same lifecycle contract as the single-device engine.
        # With a partial collector armed, an expiry here must degrade to a
        # coverage-stamped best-effort answer (the arena path's chunk loop
        # stops before its first dispatch), not an error — the engine's
        # anytime-answer contract, now on the mesh.
        if current_partial() is None:
            checkpoint("mesh.dispatch")
        else:
            checkpoint_partial("mesh.dispatch")
        fire("device_dispatch")
        t_total = _time.perf_counter()
        lowering = self._lowering_for(q, ds)
        # learned-memo identity: segment-set independent (lowering.memo_key,
        # same contract as the local engine) so continuous streamed ingest
        # neither forgets learned rungs nor leaks one memo entry per append
        qkey = memo_key(q, ds)
        handed, cfg = strategy, cfg or self.config
        strategy = self._route(q, ds, lowering, qkey, handed, cfg)
        m = QueryMetrics(
            query_type="groupBy",
            strategy=strategy,
            datasource=ds.name,
            query_id=current_query_id(),
            distributed=True,
            mesh_shape=tuple(self.mesh.shape.values()),
            rows_scanned=ds.num_rows,
            segments=len(ds.segments),
            num_groups=lowering.num_groups,
            shards=self._row_device_count(),
        )
        # the scope, resolved once and handed to every tier below: the
        # metrics read what pruning scans (parity with the local engine's
        # numbers), the tiers place and step through the same list
        from ..exec.engine import _bytes_scanned

        scope = self._scope_for_metrics(q, ds)
        m.rows_scanned = sum(sg.num_rows for sg in scope)
        m.bytes_scanned = _bytes_scanned(scope, lowering.columns)
        m.segments = len(scope)

        out = None
        try:
            if strategy == "adaptive":
                out = self._execute_adaptive(
                    q, ds, lowering, qkey, m, cfg, scope
                )
                if out is None:  # declined: re-route without adaptive
                    strategy = self._route(q, ds, lowering, qkey, handed, cfg)
                    m.strategy = strategy
            if out is None and strategy == "sparse":
                out = self._execute_sparse(q, ds, lowering, qkey, m, scope)
                if out is None:  # ladder exhausted: dense-state scatter
                    strategy = "segment"
                    m.strategy = strategy
            if out is None and strategy in (
                "dense", "pallas", "segment", "scatter",
            ):
                # unified SPMD-arena core (ISSUE 15): the stacked-layout
                # program with scope as data.  None => ineligible (layout
                # declined / sketch aggs / groups axis) — fall through to
                # the legacy dense-state path unchanged.
                out = self._execute_arena_spmd(
                    q, ds, lowering, m, strategy, scope
                )
            if out is None:
                out = self._execute_dense_state(
                    q, ds, lowering, m, strategy, scope
                )
        except BaseException as err:
            # failed executions must reach the process registry too: a
            # dashboard's outcome="error" rate would otherwise show zero
            # for the distributed path while counting single-device ones
            from ..resilience import DeadlineExceeded

            m.total_ms = (_time.perf_counter() - t_total) * 1e3
            m.bytes_resident = self._shard_cache.bytes_used
            if isinstance(err, DeadlineExceeded):
                m.deadline_exceeded = True
            self.last_metrics = m
            record_query_metrics(
                m,
                "deadline" if isinstance(err, DeadlineExceeded) else "error",
            )
            raise
        m.total_ms = (_time.perf_counter() - t_total) * 1e3
        m.bytes_resident = self._shard_cache.bytes_used
        self.last_metrics = m
        record_query_metrics(m, "ok")
        log.info("%s", m.describe())
        return out

    def _place_shards(self, ds, columns, m, segs=None):
        """Place (or reuse) the sharded column set for the pruned scope
        `segs` — None spans the full set (scope-free callers only)."""
        from ..resilience import fire

        fire("h2d")  # fault-injection site: shard placement
        t0 = _time.perf_counter()
        known = len(self._shard_cache)
        before_bytes = self._shard_cache.bytes_used
        cols, padded = self._global_columns(ds, columns, segs=segs)
        # the scope's rows lie end to end, cut evenly: every shard runs
        # its cut of them, counted in (padded) segments' worth of rows
        nd = self.mesh.shape[DATA_AXIS]
        seg_rows = max(
            (
                sg.num_rows_padded
                for sg in (ds.segments if segs is None else segs)
            ),
            default=ROW_PAD,
        )
        m.shard_steps = padded / nd / max(1, seg_rows)
        if len(self._shard_cache) > known:  # new shards were placed
            dt = _time.perf_counter() - t0
            new_bytes = max(0, self._shard_cache.bytes_used - before_bytes)
            m.h2d_ms += dt * 1e3
            m.h2d_bytes += new_bytes
            # receipts parity with the single-device engine: the transfer
            # reaches the profiling scope's h2d accumulators, and the
            # per-shard split is recorded as a span event so mesh bench
            # artifacts are attribution-honest (ISSUE 15 satellite)
            prof.record_h2d(new_bytes, dt)
            span_event(
                "shard_h2d", datasource=ds.name, bytes=new_bytes,
                per_shard_bytes=new_bytes // max(1, nd), shards=nd,
            )
        return cols, padded

    def _execute_dense_state(
        self, q, ds, lowering, m, strategy, scope, key_extra=(),
        span_attrs=None,
    ):
        """The dense-[Gl, M]-state path (dense / Pallas / scatter kernels
        share it — only the per-shard kernel differs).  One launch span,
        then the blocking fetch under `device_fetch`, as on one device."""
        from ..plan.cost import (
            allgather_factor, allreduce_factor, groupby_state_bytes,
        )

        cols, padded = self._place_shards(ds, lowering.columns, m, scope)
        local_rows = padded // self.mesh.shape[DATA_AXIS]
        compiled = self._spmd_cache
        key_count = len(compiled)
        run = self._spmd_fn(
            lowering, local_rows, ds, tuple(cols.keys()), strategy,
            key_extra=key_extra,
        )
        m.program_cache_hit = len(compiled) == key_count
        nd = self.mesh.shape[DATA_AXIS]
        m.est_collective_ms = (
            allreduce_factor(nd)
            * groupby_state_bytes(q, lowering.num_groups, None)
            / self.config.collective_bytes_per_us
            / 1e3
        )
        t0 = _time.perf_counter()
        with span(SPAN_SEGMENT_DISPATCH, shards=nd, **(span_attrs or {})):
            out_state = _launch(run, cols)
        # what `_spmd_fn`'s collectives moved, a device: the launched
        # arrays are the merged ones (a groups axis leaves each device
        # 1/ng of them); HLL registers are reduced like the sums, theta
        # and quantile states gathered
        sums, mins, maxs, sk = out_state
        ng, _ = self._groups_split(lowering.num_groups)
        hll = _nbytes([
            sk[a.name] for a in lowering.la.sketch_aggs
            if isinstance(a, (A.HyperUnique, A.CardinalityAgg))
        ])
        m.collective_bytes += round(
            (
                allreduce_factor(nd) * (_nbytes((sums, mins, maxs)) + hll)
                + allgather_factor(nd) * (_nbytes(sk) - hll)
            ) / ng
        )
        # single host fetch (one round trip — see engine._execute_groupby):
        # it blocks on the SPMD program, so the ICI merge's wall time is
        # paid here; finalize alone reads the state, so HLL registers
        # cross as their histograms
        state = _fetch(estimable(lowering.la, PartialState(*out_state)))
        dt = (_time.perf_counter() - t0) * 1e3
        if m.program_cache_hit:
            m.device_ms = dt
        else:  # first call: trace+compile dominates (metrics.py semantics)
            m.compile_ms = dt
            prof.note_compile(dt, family="dense-state")
        t0 = _time.perf_counter()
        with span(SPAN_FINALIZE):
            out = finalize_groupby(
                q, lowering.dims, lowering.la, state, metrics=m
            )
        m.finalize_ms += (_time.perf_counter() - t0) * 1e3
        return out

    # -- sparse tier ---------------------------------------------------------

    def _initial_row_capacity(
        self, q, ds, lowering, qkey, local_rows
    ) -> Optional[int]:
        """Initial compaction rung from the planner's selectivity estimate
        with 2x headroom, per DEVICE (the distributed analog of
        exec/sparse_exec.py's per-segment rung); a remembered rung from a
        previous overflow wins.  None = full local sort."""
        from ..ops import sparse_groupby as _sg

        selective = q.filter is not None or bool(q.intervals)
        if not selective:
            return None
        if qkey in self._sparse_row_capacity:
            return self._sparse_row_capacity[qkey]
        from ..plan.cost import estimate_selectivity

        sel = (
            estimate_selectivity(q.filter, ds)
            if q.filter is not None
            else 1.0
        )
        if sel >= 1.0:
            return _sg.ROW_CAPACITY
        need = 2.0 * sel * local_rows
        return next(
            (c for c in _sg.ROW_CAPACITY_LADDER if c >= need), None
        )

    def _execute_sparse(self, q, ds, lowering, qkey, m, scope):
        """Sparse sort-compaction over the mesh with the full rung ladder
        (row capacity + slots).  Returns None when the slots ladder is
        exhausted by an exact count — the caller falls back to the
        dense-state scatter path, and the decline is remembered."""
        from ..ops import sparse_groupby as _sg
        from ..plan.cost import allgather_factor

        if lowering.la.sketch_aggs or not lowering.dims:
            # sparse states carry no sketch registers and need real dims
            # (same eligibility as exec/sparse_exec.py); an explicit
            # strategy="sparse" on such a query falls through to scatter
            self._sparse_declined.add(qkey)
            return None
        cols, padded = self._place_shards(ds, lowering.columns, m, scope)
        local_rows = padded // self.mesh.shape[DATA_AXIS]
        cap = self._initial_row_capacity(q, ds, lowering, qkey, local_rows)
        slots = self._sparse_slots.get(qkey, _sg.SPARSE_SLOTS)
        compiled = self._spmd_cache
        key_count = len(compiled)
        t0 = _time.perf_counter()
        while True:
            run = self._spmd_sparse_fn(
                lowering, local_rows, ds, tuple(cols.keys()), slots, cap
            )
            # dispatch span: the mesh receipt's dispatch_count must count
            # sparse rungs like the single-device ladder does
            with span(SPAN_SPARSE_DISPATCH, slots=slots):
                launched = _launch(run, cols)
            state, flags = _fetch(launched)
            # every rung gathers its whole slot state from every shard
            m.collective_bytes += round(
                allgather_factor(self.mesh.shape[DATA_AXIS])
                * _nbytes((state, flags))
                / self._groups_split(lowering.num_groups)[0]
            )
            if cap is not None and bool(flags["row_overflow"].any()):
                n = int(flags["n_rows"].max())
                new_cap = next(
                    (
                        c
                        for c in _sg.ROW_CAPACITY_LADDER
                        if c >= n and c > cap
                    ),
                    None,
                )
                self._sparse_row_capacity[qkey] = new_cap
                log.info(
                    "mesh sparse row compaction overflowed %d of %d; "
                    "rerunning at %s",
                    n, cap,
                    "full-shard sort" if new_cap is None else new_cap,
                )
                cap = new_cap
                continue
            if bool(flags["overflow"].any()):
                n_est = int(flags["n_real"].max())
                new_slots = next(
                    (
                        s
                        for s in _sg.SLOTS_LADDER
                        if s >= n_est and s > slots
                    ),
                    None,
                )
                if new_slots is None:
                    # n_real can be a lower bound after a truncated merge
                    # (ADVICE r4): one rung at a time before giving up
                    new_slots = next(
                        (s for s in _sg.SLOTS_LADDER if s > slots), None
                    )
                if new_slots is None:
                    log.info(
                        "mesh sparse slots ladder exhausted at %d (~%d "
                        "distinct); falling back to scatter (remembered)",
                        slots, n_est,
                    )
                    self._sparse_declined.add(qkey)
                    return None
                self._sparse_slots[qkey] = new_slots
                log.info(
                    "mesh sparse slots overflowed (~%d distinct > %d); "
                    "rerunning at %d slots",
                    n_est, slots, new_slots,
                )
                slots = new_slots
                cap = self._sparse_row_capacity.get(qkey, cap)
                continue
            break
        m.program_cache_hit = len(compiled) == key_count
        if m.program_cache_hit:
            m.device_ms = (_time.perf_counter() - t0) * 1e3
        else:
            m.compile_ms = (_time.perf_counter() - t0) * 1e3
        t0 = _time.perf_counter()
        out = finalize_groupby(
            q, lowering.dims, lowering.la,
            PartialState(state["sums"], state["mins"], state["maxs"], {}),
            slot_gids=state["gids"], metrics=m,
        )
        m.finalize_ms += (_time.perf_counter() - t0) * 1e3
        return out

    # -- adaptive tier -------------------------------------------------------

    def _execute_adaptive(self, q, ds, lowering, qkey, m, cfg, scope):
        """Adaptive dictionary-domain compaction as a distributed phase A
        (presence counts psum-merged over the data axis) + the normal SPMD
        program over the compacted lowering (phase B).  Returns None when
        declining — the caller re-routes among the remaining classes."""
        from ..exec.adaptive_exec import (
            compacted_lowering,
            filter_derived_kept,
            remap_form,
        )
        from ..plan.cost import (
            ADAPTIVE_MAX_COMPACT_GROUPS,
            ADAPTIVE_MIN_SHRINK,
        )

        # measured kept sets are only valid for the segment set they
        # scanned (a fresh delta may hold codes the scan never saw —
        # reusing a stale set would silently drop those rows); derived
        # sets are supersets by construction and survive appends.  Same
        # entry shapes, and the same `adaptive_kept` span (own time: memo,
        # derivation, host `nonzero`; children: the presence pass), as the
        # local AdaptiveDomainMixin.
        seg_sig = tuple(s.uid for s in ds.segments)
        with span(SPAN_ADAPTIVE_KEPT) as sp:
            entry = self._adaptive_kept.get(qkey)
            kept, source = None, "memo"
            if entry is not None:
                if entry[0] == "derived":
                    kept = entry[1]
                elif entry[1] == seg_sig:
                    kept = entry[2]
            if kept is None:
                # dictionary-derived shortcut (shared with the local
                # engine): a filter that pins every grouping dim replaces
                # the SPMD presence pass with O(cardinality) host work
                kept, source = filter_derived_kept(q, lowering, ds), "derived"
                if kept is not None:
                    self._adaptive_kept[qkey] = ("derived", kept)
            if kept is None:
                kept, source = (
                    self._measure_kept(q, ds, lowering, m, scope), "measured"
                )
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
                "mesh adaptive compaction declined: G'=%d of G=%d",
                Gc, lowering.num_groups,
            )
            self._adaptive_declined.add(qkey)
            self._adaptive_kept.pop(qkey, None)
            return None
        if any(len(kd) == 0 for kd in kept):
            # some grouping dim has NO present code under the filter: the
            # exact result is the empty grouped frame
            la = lowering.la
            return finalize_groupby(
                q, lowering.dims, la, to_host(PartialState.empty(la, 0))
            )
        clow = compacted_lowering(lowering, kept)
        cards = tuple(d.cardinality for d in clow.dims)
        # phase B kernel from the calibrated model at the COMPACTED
        # cardinality and the shape a device runs: the function the
        # one-chip phase B calls (exec/adaptive_exec.py)
        groups = self._groups_split(clow.num_groups)[1]
        with span(SPAN_ROUTE, tier="adaptive") as sp:
            strat = shape_kernel(
                max(1, ds.num_rows // self._row_device_count()), groups, cfg
            )
            if sp is not None:
                sp.attrs.update(kernel=strat, groups=groups)
        m.num_groups = clow.num_groups
        return self._execute_dense_state(
            q, ds, clow, m, strat, scope, key_extra=("adaptive",) + cards,
            span_attrs={"phase": "B"},
        )

    def _measure_kept(self, q, ds, lowering, m, scope) -> List[np.ndarray]:
        """Adaptive phase A on the mesh: one presence program over the
        scope's shards, its per-dim counts psum-merged, then the codes
        seen.  A failure of the pass raises (transient ones into
        execute()'s evict-and-retry path): it is never a reason to
        decline."""
        from ..exec.adaptive_exec import presence_columns
        from ..plan.cost import allreduce_factor

        # phase A reads only mask + dim-code columns (the shared helper
        # keeps the physical time column when intervals need it)
        need = presence_columns(q, lowering, ds)
        cols, padded = self._place_shards(ds, need, m, scope)
        nd = self.mesh.shape[DATA_AXIS]
        run = self._presence_fn(
            lowering, padded // nd, ds, tuple(cols.keys())
        )
        with span(SPAN_ADAPTIVE_PROBE, phase="A", shards=nd):
            launched = _launch(run, cols)
        counts = _fetch(launched)
        m.collective_bytes += round(allreduce_factor(nd) * _nbytes(counts))
        return [
            np.nonzero(np.asarray(c) > 0)[0].astype(np.int32)
            for c in counts
        ]

    # -- unified SPMD-arena core (ISSUE 15) ----------------------------------
    #
    # The stacked [B, R] arena layout (exec/arena.py) sharded over the row
    # devices is the ONE program both paths lower; the mesh contributes a
    # placement strategy (device-major permuted stacking) and a boundary
    # collective merge.  The scope rides as DATA (membership + window
    # start), so one compiled program serves every same-window-size scope.

    def _arena_mesh(self) -> Mesh:
        """The mesh the arena path shards rows over: the virtual
        multi-slice mesh when one was given, the flat data mesh
        otherwise."""
        return self.slice_mesh if self.slice_mesh is not None else self.mesh

    def _arena_mesh_key(self) -> Tuple:
        return tuple(sorted(self._arena_mesh().shape.items()))

    def _row_device_count(self) -> int:
        mesh = self._arena_mesh()
        return int(np.prod([mesh.shape[a] for a in row_axes(mesh)]))

    def _arena_layout(self, ds: DataSource):
        """The scope-independent stacked layout for `ds`, or None when
        the arena path must decline (toggle off, per-query disable,
        group-domain sharding, <2 segments, or non-uniform padded row
        counts)."""
        if not self.arena_execution:
            return None
        from ..exec import arena as _arena_mod

        if _arena_mod.query_disabled():
            return None
        if self.mesh.shape[GROUPS_AXIS] > 1:
            # the groups axis shards the gid domain — the arena program
            # folds full-domain states, so the legacy paths own that mesh
            return None
        return spmd_arena.plan_spmd_layout(ds, self._row_device_count())

    def _merge_tree_for(self, q, lowering) -> Tuple[str, float, float]:
        """(tree, flat_us, hier_us): the calibrated cost model's merge
        tree for this query's state size on this topology.  On the flat
        data mesh both trees coincide at ICI pricing and "flat" wins the
        tie — the single-program default."""
        from ..plan.cost import choose_merge_tree, groupby_state_bytes

        sbytes = groupby_state_bytes(q, lowering.num_groups, None)
        if self.slice_mesh is not None:
            ns = self.slice_mesh.shape[SLICE_AXIS]
            nd = self.slice_mesh.shape[DATA_AXIS]
        else:
            ns, nd = 1, self.mesh.shape[DATA_AXIS]
        return choose_merge_tree(sbytes, ns, nd, self.config)

    def _place_arena(self, ds: DataSource, layout, names, m):
        """Place (or reuse) the permuted [B_pad, R] column stacks.

        Keys carry the FULL segment signature and the row-device count —
        never a query's scope — so residency is durable across every
        query of the datasource version (the r4 #3 contract, now with
        program-cache generality on top).  Placement order is the PR 10
        prefetch plan ported per-device: resident stacks first (free
        cache hits), then cold stacks largest-first so the longest
        transfer issues earliest."""
        from ..exec.pipeline import placement_order
        from ..resilience import fire

        fire("h2d")  # fault-injection site: shard placement
        mesh = self._arena_mesh()
        row_el = spmd_arena._row_spec_axes(mesh)
        sharding = NamedSharding(mesh, P(row_el, None))
        base = (ds.name, "spmd_arena", layout.ndt, layout.uids)

        def ckey(name: str) -> Tuple:
            # "col"/"valid" tags: a user column literally named
            # "__valid" must not alias the validity stack (GL1301)
            if name == "__valid":
                return base + ("valid",)
            return base + ("col", name)

        def est_bytes(name: str) -> int:
            if name == "__valid":
                return layout.B_pad * layout.R  # bool stack
            proto = np.asarray(layout.segs[0].column(name))
            return layout.B_pad * layout.R * proto.dtype.itemsize

        want = list(dict.fromkeys(list(names) + ["__valid"]))
        order = placement_order(
            want, lambda n: self._shard_cache.get(ckey(n)) is not None,
            est_bytes,
        )
        t0 = _time.perf_counter()
        before = self._shard_cache.bytes_used
        cols: Dict[str, jax.Array] = {}
        placed = 0
        with span(
            SPAN_ARENA_BUILD, datasource=ds.name, blocks=layout.B,
            shards=layout.ndt,
        ):
            for name in order:
                key = ckey(name)
                hit = self._shard_cache.get(key)
                if hit is None:
                    host = spmd_arena.stack_column(layout, name)
                    hit = put_sharded(host, sharding)
                    self._shard_cache[key] = hit
                    placed += 1
                cols[name] = hit
        prof.note_residency(hit=placed == 0)
        if ds.time_column and ds.time_column in cols:
            cols["__time"] = cols[ds.time_column]
        if placed:
            dt = _time.perf_counter() - t0
            new_bytes = max(0, self._shard_cache.bytes_used - before)
            m.h2d_ms += dt * 1e3
            m.h2d_bytes += new_bytes
            prof.record_h2d(new_bytes, dt)
            span_event(
                "shard_h2d", datasource=ds.name, bytes=new_bytes,
                per_shard_bytes=new_bytes // max(1, layout.ndt),
                shards=layout.ndt, columns=placed,
            )
        return cols

    def prefetch(self, q: Q.QuerySpec, ds: DataSource) -> bool:
        """Warm the arena placement for `q` ahead of execution (the PR 10
        prefetch plan surfaced on the mesh): places the stacked column
        set in residency-aware order so a following execute() pays zero
        h2d.  Returns False when the query/datasource is not
        arena-eligible (nothing to warm)."""
        inner, _ = groupby_family(q, ds)
        if inner is None:
            return False
        inner = groupby_with_time_granularity(inner)
        lowering = self._lowering_for(inner, ds)
        layout = self._arena_layout(ds)
        if layout is None:
            return False
        from ..exec.metrics import QueryMetrics

        scratch = QueryMetrics(query_type="prefetch")
        self._place_arena(ds, layout, lowering.columns, scratch)
        return True

    @span_around(SPAN_PROGRAM_LOOKUP)
    def _arena_spmd_fn(self, lowering, ds, layout, Lk, strategy, tree):
        """The cached single-dispatch unified program.  The key carries
        the window LENGTH `Lk` but never the scope itself — two disjoint
        scopes of equal rounded size share one compiled program."""
        from ..exec.lowering import _query_key

        # literal tag at the same tuple position as the legacy families
        # ("dense-state"/"sparse"/...) so no key can alias across
        # families sharing _spmd_cache (GL1301)
        cache_key = _query_key(lowering.query, ds) + (
            layout.L,
            self._arena_mesh_key(),
            "arena-spmd", layout.R, Lk, strategy, tree,
        )
        if cache_key in self._spmd_cache:
            prof.note_program_cache("arena-spmd", hit=True)
            return self._spmd_cache[cache_key]
        prof.note_program_cache("arena-spmd", hit=False)
        run = spmd_arena.build_spmd_arena_program(
            self._arena_mesh(), [lowering], [strategy], Lk, tree=tree
        )
        self._spmd_cache[cache_key] = run
        return run

    @span_around(SPAN_PROGRAM_LOOKUP)
    def _arena_chunk_fn(self, lowering, ds, layout, strategy):
        from ..exec.lowering import _query_key

        cache_key = _query_key(lowering.query, ds) + (
            layout.L,
            self._arena_mesh_key(),
            "arena-spmd-chunk", layout.R, strategy,
        )
        if cache_key in self._spmd_cache:
            prof.note_program_cache("arena-spmd-chunk", hit=True)
            return self._spmd_cache[cache_key]
        prof.note_program_cache("arena-spmd-chunk", hit=False)
        run = spmd_arena.build_spmd_chunk_program(
            self._arena_mesh(), [lowering], [strategy]
        )
        self._spmd_cache[cache_key] = run
        return run

    @span_around(SPAN_PROGRAM_LOOKUP)
    def _arena_merge_fn(self, lowering, ds, tree):
        from ..exec.lowering import _query_key

        cache_key = _query_key(lowering.query, ds) + (
            0,
            self._arena_mesh_key(),
            "arena-spmd-merge", tree,
        )
        if cache_key in self._spmd_cache:
            prof.note_program_cache("arena-spmd-merge", hit=True)
            return self._spmd_cache[cache_key]
        prof.note_program_cache("arena-spmd-merge", hit=False)
        run = spmd_arena.build_spmd_merge_program(
            self._arena_mesh(), [lowering], tree=tree
        )
        self._spmd_cache[cache_key] = run
        return run

    def _slice_count(self) -> int:
        return (
            self.slice_mesh.shape[SLICE_AXIS]
            if self.slice_mesh is not None
            else 1
        )

    def _execute_arena_spmd(self, q, ds, lowering, m, strategy, scope):
        """The unified executor core on the mesh: ONE dispatch folds the
        scope inside the trace and merges at the boundary.  Returns None
        to decline (caller falls through to the legacy dense-state
        path)."""
        layout = self._arena_layout(ds)
        if layout is None or lowering.la.sketch_aggs:
            return None
        from ..exec.engine import _row_counts
        from ..resilience import current_deadline, current_partial

        la, G = lowering.la, lowering.num_groups
        pc = current_partial()
        if not scope:
            if pc is not None:
                pc.begin_pass()
                pc.add_scope(0, 0)
            state = to_host(PartialState.empty(la, G))
        else:
            canonical = sorted(layout.index[s.uid] for s in scope)
            j_lo, Lk = spmd_arena.scope_window(layout, canonical)
            # every shard steps through the window, a block a step, with
            # the blocks outside the scope dead: Lk x ndt >= len(scope)
            m.shard_steps = float(Lk)
            memb = spmd_arena.membership_matrix(layout, [canonical])
            tree, flat_us, hier_us = self._merge_tree_for(q, lowering)
            m.est_collective_ms = min(flat_us, hier_us) / 1e3
            cols = self._place_arena(ds, layout, lowering.columns, m)
            rows, delta = _row_counts(scope)
            if pc is not None:
                pc.begin_pass()
                pc.add_scope(len(scope), rows, delta)
            if current_deadline() is None:
                compiled = self._spmd_cache
                key_count = len(compiled)
                run = self._arena_spmd_fn(
                    lowering, ds, layout, Lk, strategy, tree
                )
                m.program_cache_hit = len(compiled) == key_count
                t0 = _time.perf_counter()
                # single launch, single fetch: the receipt's
                # dispatch_count is 1 per query
                with span(
                    SPAN_SEGMENT_DISPATCH, arena=1, merge_tree=tree,
                    shards=layout.ndt, window=Lk,
                ):
                    span_event(
                        "merge_tree", tree=tree,
                        flat_us=round(flat_us, 3),
                        hier_us=round(hier_us, 3),
                        shards=layout.ndt, slices=self._slice_count(),
                    )
                    out_state = _launch(run, cols, np.int32(j_lo), memb)
                merged = _fetch(out_state[0])
                state = PartialState(*merged[:3], {})
                m.collective_bytes += spmd_arena.boundary_merge_bytes(
                    self._arena_mesh(), tree, merged
                )
                dt = (_time.perf_counter() - t0) * 1e3
                if m.program_cache_hit:
                    m.device_ms = dt
                else:
                    m.compile_ms = dt
                    prof.note_compile(dt, family="arena-spmd")
                if pc is not None:
                    pc.add_seen(len(scope), rows, delta)
            else:
                state = self._arena_spmd_deadline(
                    ds, lowering, m, strategy, layout, cols, memb,
                    canonical, j_lo, Lk, tree, pc,
                )
        # result-cache state capture: the merged host partial state from
        # the collective — never a deadline-truncated one
        holder = getattr(self._m_local, "capture", None)
        if holder is not None and (pc is None or not pc.triggered):
            holder["state"] = state
        t0 = _time.perf_counter()
        with span(SPAN_FINALIZE):
            out = finalize_groupby(q, lowering.dims, la, state, metrics=m)
        m.finalize_ms += (_time.perf_counter() - t0) * 1e3
        return out

    def _arena_spmd_deadline(
        self, ds, lowering, m, strategy, layout, cols, memb, canonical,
        j_lo, Lk, tree, pc,
    ):
        """Deadline partials on the unified core: per-shard stop-and-merge.
        The chunk loop folds one local step per dispatch into a
        row-sharded carry; a truncation lands on a step boundary, the
        merge program runs the boundary collectives over whatever was
        folded, and coverage is accounted host-side — local step `j`
        covers exactly the canonical blocks {j*ndt + d}, summed across
        shards."""
        from ..exec.engine import _row_counts
        from ..resilience import checkpoint_partial, fire

        ndt = layout.ndt
        compiled = self._spmd_cache
        key_count = len(compiled)
        step_fn = self._arena_chunk_fn(lowering, ds, layout, strategy)
        merge_fn = self._arena_merge_fn(lowering, ds, tree)
        m.program_cache_hit = len(compiled) == key_count
        carry = spmd_arena.init_carry_stacked(self._arena_mesh(), [lowering])
        by_step: Dict[int, List] = {}
        for b in canonical:
            by_step.setdefault(b // ndt, []).append(layout.segs[b])
        t0 = _time.perf_counter()
        for j in range(j_lo, j_lo + Lk):
            if checkpoint_partial("mesh.segment_loop"):
                break
            fire("device_dispatch")
            with span(
                SPAN_SEGMENT_DISPATCH, arena=1, chunk=j - j_lo,
                shards=ndt,
            ):
                carry = _launch(step_fn, carry, cols, np.int32(j), memb)
            if pc is not None:
                segs_j = by_step.get(j, [])
                rows_j, delta_j = _row_counts(segs_j)
                pc.add_seen(len(segs_j), rows_j, delta_j)
        # the boundary merge is a program of its own here: one more launch
        with span(
            SPAN_SEGMENT_DISPATCH, arena=1, merge_tree=tree, shards=ndt,
        ):
            out_state = _launch(merge_fn, carry)
        merged = _fetch(out_state[0])
        m.collective_bytes += spmd_arena.boundary_merge_bytes(
            self._arena_mesh(), tree, merged
        )
        dt = (_time.perf_counter() - t0) * 1e3
        if m.program_cache_hit:
            m.device_ms = dt
        else:
            m.compile_ms = dt
            prof.note_compile(dt, family="arena-spmd-chunk")
        return PartialState(*merged[:3], {})

    # -- host partial-state surface (delta-aware result cache) ---------------

    def groupby_partials_host(
        self, q: Q.QuerySpec, ds: DataSource, within_uids=None
    ):
        """Merged HOST partial state over the in-scope segments whose uid
        is in `within_uids` (None = the full scope) — the delta-reuse
        entry point, same contract as the local engine's.  Membership is
        data, so the delta scan is the SAME compiled program folding only
        the fresh blocks.  Raises ValueError when the query/datasource
        cannot produce mesh partial state (callers treat it as a cache
        decline)."""
        from ..exec.lowering import memo_key

        inner, _, lowering = self._family_lowering(q, ds)
        layout = self._arena_layout(ds)
        if layout is None or lowering.la.sketch_aggs:
            raise ValueError(
                "query/datasource is not SPMD-arena eligible on the mesh"
            )
        strategy = self._route(inner, ds, lowering, memo_key(inner, ds))
        if strategy in ("sparse", "adaptive"):
            raise ValueError(
                f"{strategy} tier has no mergeable mesh partial state"
            )
        segs = self._scope_for_metrics(inner, ds)
        if within_uids is not None:
            w = frozenset(within_uids)
            segs = [s for s in segs if s.uid in w]
        la, G = lowering.la, lowering.num_groups
        if not segs:
            state = to_host(PartialState.empty(la, G))
        else:
            from ..exec.metrics import QueryMetrics

            scratch = QueryMetrics(query_type="partials")
            canonical = sorted(layout.index[s.uid] for s in segs)
            j_lo, Lk = spmd_arena.scope_window(layout, canonical)
            memb = spmd_arena.membership_matrix(layout, [canonical])
            tree, _f, _h = self._merge_tree_for(inner, lowering)
            cols = self._place_arena(ds, layout, lowering.columns, scratch)
            run = self._arena_spmd_fn(lowering, ds, layout, Lk, strategy, tree)
            with span(
                SPAN_SEGMENT_DISPATCH, arena=1, merge_tree=tree, partials=1,
            ):
                out_state = _launch(run, cols, np.int32(j_lo), memb)
            state = PartialState(*_fetch(out_state[0])[:3], {})
        return state, sum(s.num_rows for s in segs)

    # -- micro-batch fusion on the shared arena ------------------------------

    def fusable(self, q: Q.QuerySpec, ds: DataSource, strategy=None) -> bool:
        """May this query join a fused micro-batch on the mesh?  Same
        surface as the local engine's: GroupBy-family, no wire subtotals,
        and the unified arena program can host it (no sketches, no
        sparse/adaptive tier under `strategy`, layout eligible)."""
        inner, _ = groupby_family(q, ds)
        if inner is None or inner.subtotals:
            return False
        try:
            inner = groupby_with_time_granularity(inner)
            lowering = self._lowering_for(inner, ds)
        except Exception:  # fault-ok: an unlowerable query declines fusion
            return False
        if lowering.la.sketch_aggs:
            return False
        from ..exec.lowering import memo_key

        strategy = self._route(
            inner, ds, lowering, memo_key(inner, ds), strategy
        )
        if strategy in ("sparse", "adaptive"):
            return False
        return self._arena_layout(ds) is not None

    @span_around(SPAN_PROGRAM_LOOKUP)
    def _arena_spmd_fused_fn(self, members, ds, layout, Lk, strategies, tree):
        """The fused unified program: every member's fold inside ONE
        sharded scan, membership as data (one compiled program serves
        any member->scope mapping of the same window size)."""
        import json as _json

        from ..exec.lowering import _query_key

        cache_key = _query_key(members[0][1], ds) + (
            layout.L,
            self._arena_mesh_key(),
            "arena-spmd-fused",
            tuple(
                _json.dumps(mm[1].to_druid(), sort_keys=True, default=str)
                for mm in members[1:]
            ),
            strategies, layout.R, Lk, tree,
        )
        if cache_key in self._spmd_cache:
            prof.note_program_cache("arena-spmd-fused", hit=True)
            return self._spmd_cache[cache_key]
        prof.note_program_cache("arena-spmd-fused", hit=False)
        from ..serve.fusion import shared_row_plan

        share = shared_row_plan([mm[1] for mm in members])
        run = spmd_arena.build_spmd_arena_program(
            self._arena_mesh(), [mm[3] for mm in members], list(strategies),
            Lk, tree=tree, share=share,
        )
        self._spmd_cache[cache_key] = run
        return run

    def execute_fused(
        self, queries, ds: DataSource, query_ids=None, strategies=None
    ):
        """Execute N compatible GroupBy-family queries as ONE unified
        arena dispatch: members share the sharded arena via the
        membership scan input, every member's fold runs inside the same
        program, and ONE host fetch returns all merged states.  Same
        (df, state, metrics) contract as the local engine's
        execute_fused; an ineligible batch falls back to serial
        per-member execution (state still captured)."""
        from ..exec.lowering import memo_key
        from ..exec.metrics import QueryMetrics
        from ..resilience import checkpoint, fire

        t0_all = _time.perf_counter()
        n = len(queries)
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
            segs = self._scope_for_metrics(inner, ds)
            members.append((q, inner, shape, lowering, segs))
        layout = self._arena_layout(ds)
        handed = list(strategies or [None] * n)
        strategies = tuple(
            self._route(mm[1], ds, mm[3], memo_key(mm[1], ds), st)
            for st, mm in zip(handed, members)
        )
        if (
            layout is None
            or any(mm[3].la.sketch_aggs for mm in members)
            or any(s in ("sparse", "adaptive") for s in strategies)
        ):
            return self._execute_fused_serial(queries, ds, query_ids, handed)
        prof.note_fusion(n)
        checkpoint("engine.fused_loop")  # fused deadline contract
        fire("device_dispatch")
        member_scopes = [
            sorted(layout.index[s.uid] for s in mm[4]) for mm in members
        ]
        all_blocks = sorted({b for sc in member_scopes for b in sc})
        batch_m = QueryMetrics(query_type="fused")
        states = None
        tree = "flat"
        if all_blocks:
            j_lo, Lk = spmd_arena.scope_window(layout, all_blocks)
            memb = spmd_arena.membership_matrix(layout, member_scopes)
            tree, flat_us, hier_us = self._merge_tree_for(
                members[0][1], members[0][3]
            )
            names = list(
                dict.fromkeys(c for mm in members for c in mm[3].columns)
            )
            cols = self._place_arena(ds, layout, names, batch_m)
            compiled = self._spmd_cache
            key_count = len(compiled)
            fn = self._arena_spmd_fused_fn(
                members, ds, layout, Lk, strategies, tree
            )
            batch_m.program_cache_hit = len(compiled) == key_count
            t0 = _time.perf_counter()
            with span(
                SPAN_SEGMENT_DISPATCH, arena=1, merge_tree=tree, fused=n,
                shards=layout.ndt, window=Lk,
            ):
                span_event(
                    "merge_tree", tree=tree, flat_us=round(flat_us, 3),
                    hier_us=round(hier_us, 3), shards=layout.ndt,
                    slices=self._slice_count(), fused=n,
                )
                outs = _launch(fn, cols, np.int32(j_lo), memb)
            # ONE fetch for the whole batch — the round trip the fused
            # dispatch exists to amortize
            states = _fetch(outs)
            dt = (_time.perf_counter() - t0) * 1e3
            if batch_m.program_cache_hit:
                batch_m.device_ms = dt
            else:
                batch_m.compile_ms = dt
                prof.note_compile(dt, family="arena-spmd-fused")
        # empty-scope members in ONE host fetch before the demux loop
        # (GL204: no per-member device round trips while demuxing)
        empties = to_host({
            i: PartialState.empty(mm[3].la, mm[3].num_groups)
            for i, mm in enumerate(members)
            if states is None or not member_scopes[i]
        })
        out = []
        elapsed_ms = (_time.perf_counter() - t0_all) * 1e3
        from ..exec.engine import _bytes_scanned, _row_counts

        for i, (q, inner, shape, lowering, segs) in enumerate(members):
            if i in empties:
                # empty scope: dead-shard identities ARE the empty state,
                # but skip the device state entirely when nothing ran
                state = empties[i]
            else:
                state = PartialState(*states[i][:3], {})
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
            mm = QueryMetrics(
                query_type=qt,
                strategy=strategies[i],
                datasource=ds.name,
                query_id=query_ids[i],
                distributed=True,
                mesh_shape=tuple(self.mesh.shape.values()),
                rows_scanned=rows,
                bytes_scanned=_bytes_scanned(segs, lowering.columns),
                segments=len(segs),
                num_groups=lowering.num_groups,
                # the batch's shared h2d/compile split evenly: ONE
                # stacked column set moved for all members
                h2d_bytes=batch_m.h2d_bytes // n,
                h2d_ms=batch_m.h2d_ms / n,
                compile_ms=batch_m.compile_ms,
                total_ms=elapsed_ms,
                fused_batch=n,
                program_cache_hit=batch_m.program_cache_hit,
                host_groups=counted.host_groups,
            )
            record_query_metrics(mm, "ok")
            out.append((df, state, mm))
        self.last_metrics = out[-1][2] if out else None
        return out

    def _execute_fused_serial(self, queries, ds, query_ids, strategies):
        """Fallback for an arena-ineligible batch: serial per-member
        execution under state capture — the same (df, state, metrics)
        tuple contract, minus the shared dispatch."""
        out = []
        for q, qid, st in zip(queries, query_ids, strategies):
            with self.state_capture() as cap:
                df = self.execute(q, ds, strategy=st)
            mm = self.last_metrics
            if mm is not None and qid:
                mm.query_id = qid
            out.append((df, cap["state"], mm))
        return out
