"""Streaming execution: aggregate row chunks that never fit in HBM (or host
RAM) at once.

Reference parity: the reference streams Druid results row-by-row precisely so
nothing materializes in full (`DruidRDD` streaming JSON parse, SURVEY.md §3.3
`[U]`); the analogous scale problem here is on the *input* side — BASELINE
config #4 is an hourly rollup over a 1B-row event stream, far beyond one
chip's HBM.  The streaming executor holds only O(chunk) rows on device at any
moment:

  * chunks are produced on a background prefetch thread (host-side decode /
    datagen overlaps device compute),
  * every chunk is padded to one static shape, so the engine's cached
    per-query XLA program is compiled exactly once,
  * `jax.device_put` + the async dispatch queue overlap H2D transfer of
    chunk k+1 with compute of chunk k — the Python loop never blocks,
  * only the tiny [G, M] partial-aggregate state persists across chunks
    (summed / min-maxed / sketch-merged on device).

Multichip streaming (BASELINE config #4 at v5e-8 scale): pass a `mesh` and
every chunk is sharded over the mesh's data axis (`jax.device_put` with a
NamedSharding), the per-chunk program is the DistributedEngine's SPMD
shard_map (dense partials + psum/pmin/pmax/sketch merges over ICI), and only
the tiny replicated [G, M] state accumulates across chunks.  Chunk k+1's H2D
scatter overlaps chunk k's compute exactly as in the single-chip path.
"""

from __future__ import annotations

import dataclasses
import queue
import threading
from typing import Callable, Dict, Iterable, Iterator, Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..catalog.segment import NULL_ID, ROW_PAD, DataSource
from ..models import query as Q
from ..obs import SPAN_PROGRAM_LOOKUP, span_around
from ..plan.cost import concrete_kernel, shape_kernel
from .engine import (
    Engine,
    finalize_groupby,
    finalize_timeseries,
    finalize_topn,
    groupby_with_time_granularity,
    lower_groupby,
    timeseries_to_groupby,
    topn_to_groupby,
)
from .state import PartialState, merge, to_host

_STOP = object()


@dataclasses.dataclass
class StreamStats:
    rows: int = 0
    chunks: int = 0
    # pipeline-stage seconds (BASELINE config #4 observability): normalize
    # runs on the producer thread (overlapped with compute); put/dispatch
    # are consumer-side walls.  dispatch_s is async-dispatch time, NOT
    # device occupancy — the final block shows up in total wall time.
    normalize_s: float = 0.0
    put_s: float = 0.0
    dispatch_s: float = 0.0
    # bytes actually shipped host->device (post-normalization dtypes), so
    # consumers can bound throughput by the measured link rate instead of
    # guessing a bytes/row layout
    h2d_bytes: int = 0

    def to_dict(self):
        return {
            "rows": self.rows,
            "chunks": self.chunks,
            "normalize_s": round(self.normalize_s, 3),
            "put_s": round(self.put_s, 3),
            "dispatch_s": round(self.dispatch_s, 3),
            "h2d_bytes": self.h2d_bytes,
        }


class StreamExecutor:
    """Executes GroupBy/Timeseries/TopN over an iterator of host row-chunks.

    `chunks` yields dicts mapping column name -> numpy array (row-aligned;
    dimension columns already dictionary-encoded as int32 codes per the
    datasource's dictionaries — the contract native ingest and datagen both
    produce).  All chunks must have <= `chunk_rows` rows; shorter chunks are
    padded (a validity mask keeps padding out of every aggregate).
    """

    def __init__(
        self,
        engine: Optional[Engine] = None,
        prefetch: int = 2,
        mesh=None,
    ):
        self.engine = engine or Engine()
        self.prefetch = prefetch
        self.mesh = mesh  # jax.sharding.Mesh -> multichip streaming
        self.stats = StreamStats()
        self._narrow_time = jax.default_backend() != "cpu"
        # compiled chunk-reconstruction programs keyed on (time_col,
        # chunk_rows): jit caches on callable identity, so rebuilding the
        # closure per stream would re-trace/compile every execution (the
        # same convention as DistributedEngine._spmd_fn)
        self._prep_cache: Dict = {}

    def _prep_fn(self, time_col, chunk_rows: int):
        key = (time_col, chunk_rows)
        fn = self._prep_cache.get(key)
        if fn is not None:
            return fn

        @jax.jit
        def prep(dev, base, nrows):
            """Device-side chunk reconstruction: int64 time from int32
            offsets + base, validity mask from the row count.  One tiny
            extra async dispatch per chunk; the H2D savings dominate."""
            cols = dict(dev)
            off = cols.pop("__time_off", None)
            if off is not None:
                # graftlint: disable=dtype-x64 -- time is int64 ms by engine contract
                t = base + off.astype(jnp.int64)
                cols[time_col] = t
                cols["__time"] = t
            elif time_col and time_col in cols:
                cols["__time"] = cols[time_col]
            cols["__valid"] = (
                jnp.arange(chunk_rows, dtype=jnp.int32) < nrows
            )
            return cols

        self._prep_cache[key] = prep
        return prep

    # -- public entry points -------------------------------------------------

    def execute(
        self,
        q: Q.QuerySpec,
        ds: DataSource,
        chunks: Iterable[Mapping[str, np.ndarray]],
        chunk_rows: int,
    ):
        if isinstance(q, Q.TimeseriesQuery):
            df = self._execute_groupby(
                timeseries_to_groupby(q), ds, chunks, chunk_rows
            )
            return finalize_timeseries(df, q, ds)
        if isinstance(q, Q.TopNQuery):
            df = self._execute_groupby(
                topn_to_groupby(q), ds, chunks, chunk_rows
            )
            return finalize_topn(df, q)
        if isinstance(q, Q.GroupByQuery):
            return self._execute_groupby(q, ds, chunks, chunk_rows)
        raise NotImplementedError(
            f"streaming {type(q).__name__} (scan/search need no aggregation "
            "state — iterate chunks host-side instead)"
        )

    # -- core ----------------------------------------------------------------

    def _execute_groupby(
        self,
        q: Q.GroupByQuery,
        ds: DataSource,
        chunks: Iterable[Mapping[str, np.ndarray]],
        chunk_rows: int,
    ):
        q = groupby_with_time_granularity(q)
        pad_unit = ROW_PAD
        if self.mesh is not None:
            from ..parallel.mesh import DATA_AXIS

            pad_unit = ROW_PAD * self.mesh.shape[DATA_AXIS]
        if chunk_rows % pad_unit:
            chunk_rows = -(-chunk_rows // pad_unit) * pad_unit
        if (
            any(d.dimension == "__time" or d.granularity for d in q.dimensions)
            and not q.intervals
            and ds.interval() is None
        ):
            raise ValueError(
                "streaming time-bucketed queries need explicit intervals "
                "(a schema-only datasource has no segment time range to "
                "derive buckets from)"
            )
        lowering = lower_groupby(q, ds)
        la, G = lowering.la, lowering.num_groups
        need = list(lowering.columns)
        eng = self.engine

        prep = self._prep_fn(ds.time_column, chunk_rows)
        if self.mesh is not None:
            # per-chunk SPMD program shared with DistributedEngine:
            # partials on each device's row shard (kernel routed by the
            # calibrated model AT THE PER-DEVICE SHAPE, same as every
            # other executor — round 4 hard-coded dense here, which at
            # high G cannot execute), psum/pmin/pmax + sketch merges over
            # ICI, replicated [G, M] state back
            from ..parallel.distributed import DistributedEngine
            from ..parallel.mesh import DATA_AXIS

            nd = self.mesh.shape[DATA_AXIS]
            strat = self._stream_strategy(G, chunk_rows // nd)
            dist = DistributedEngine(mesh=self.mesh, config=eng.config)
            col_keys = list(need) + ["__valid"]
            if ds.time_column and ds.time_column in need:
                col_keys.append("__time")

            dist_run = dist._spmd_fn(
                lowering, chunk_rows // nd, ds, tuple(col_keys),
                strategy=strat,
            )
            run = lambda dev, base, nrows: dist_run(prep(dev, base, nrows))
        else:
            # prep (time reconstruction + validity) FUSED into the chunk
            # program: two back-to-back jits materialized a 16 MB int64
            # time column per 2M-row chunk between them (~30 ms/chunk on
            # CPU, measured) that XLA folds away entirely once fused
            strat = self._stream_strategy(G, chunk_rows)
            run = self._fused_local_fn(q, ds, lowering, prep, strat)

        state = None
        self.stats = StreamStats()
        t_disp = 0.0

        import time as _time

        from ..obs import SPAN_STREAM_CHUNK, span
        from ..resilience import checkpoint_partial, current_partial, fire

        pc = current_partial()
        if pc is not None:
            # an unbounded stream has no knowable denominator: the
            # collector records rows seen (coverage None) so a partial
            # answer still says HOW MUCH it aggregated
            pc.begin_pass()
        for dev, base, nrows in self._prefetched_device_chunks(
            chunks, need, ds, chunk_rows
        ):
            # cooperative deadline checkpoint + device-dispatch fault site:
            # a budgeted 1B-row stream cancels between chunks (or, with a
            # partial collector armed, stops consuming and answers with
            # the chunk partials merged so far), and injected device
            # faults hit the streaming path like every other executor
            if checkpoint_partial("streaming.chunk_loop"):
                break
            fire("device_dispatch")
            t0 = _time.perf_counter()
            with span(SPAN_STREAM_CHUNK, chunk=self.stats.chunks):
                from ..obs import prof

                chunk = PartialState(*run(dev, base, nrows))
                # sampled query: honest device split on the chunk span
                # (obs/prof.py; a strict no-op at the default rate)
                prof.dispatch_sync(chunk.sums, t0)
            state = merge(la, state, chunk)
            self.stats.chunks += 1
            if pc is not None:
                pc.add_seen(1, int(nrows))
            t_disp += _time.perf_counter() - t0
        self.stats.dispatch_s = t_disp

        if state is None:  # empty stream
            state = PartialState.empty(la, G)

        from ..obs import SPAN_DEVICE_FETCH, SPAN_FINALIZE

        with span(SPAN_DEVICE_FETCH):
            state = to_host(state)
        with span(SPAN_FINALIZE):
            return finalize_groupby(q, lowering.dims, la, state)

    def _stream_strategy(self, G: int, rows_per_dispatch: int) -> str:
        """Per-dispatch kernel.  An engine constructed with an explicit
        strategy is honored (the local and mesh paths agree); "auto"
        routes through the CALIBRATED model at (rows_per_dispatch, G):
        the shape each dispatch actually runs (per-device shard rows on
        a mesh).  Streaming accumulates dense [G, M] states across
        chunks, so only the dense-state kernels apply."""
        eng = self.engine
        if eng.strategy != "auto":
            return concrete_kernel(eng.strategy, G)
        return shape_kernel(rows_per_dispatch, G, eng.config)

    @span_around(SPAN_PROGRAM_LOOKUP)
    def _fused_local_fn(self, q, ds, lowering, prep, strat=None):
        """One jitted program per (query, chunk shape): prep + partial
        aggregation, cached on the engine's program cache so repeats and
        shape-identical streams reuse the compile."""
        eng = self.engine
        from .lowering import _query_key

        key = _query_key(q, ds) + (
            "stream-fused",
            prep,  # carries (time_col, chunk_rows) identity
            strat or concrete_kernel(eng.strategy, lowering.num_groups),
        )
        from ..obs import prof

        cached = eng._query_fn_cache.get(key)
        if cached is not None:
            prof.note_program_cache("stream-fused", hit=True)
            return cached
        prof.note_program_cache("stream-fused", hit=False)
        seg_fn = eng._segment_program(q, ds, lowering, strategy_override=strat)

        @jax.jit
        def fused(dev, base, nrows):
            return seg_fn([prep(dev, base, nrows)])

        eng._query_fn_cache[key] = fused
        return fused

    # -- chunk plumbing ------------------------------------------------------

    def _normalize_chunk(
        self,
        chunk: Mapping[str, np.ndarray],
        need,
        ds: DataSource,
        chunk_rows: int,
    ) -> Dict[str, np.ndarray]:
        """Host-side: select needed columns, cast to device dtypes, pad to
        the static chunk shape, add validity + __time."""
        first = next(iter(chunk.values()))
        rows = len(first)
        if rows > chunk_rows:
            raise ValueError(f"chunk has {rows} rows > chunk_rows={chunk_rows}")
        out: Dict[str, np.ndarray] = {}
        for n in need:
            a = np.asarray(chunk[n])
            if n in ds.dicts:
                a = a.astype(np.int32, copy=False)
                fill = NULL_ID
            elif ds.time_column and n == ds.time_column:
                # H2D narrowing: the stream is the H2D-bound path (BASELINE
                # config #4), and a chunk's time span virtually always fits
                # int32 ms (~24 days) — ship base + offsets, reconstruct
                # int64 on device.  Halves the widest column's bytes.
                # Skipped on the CPU backend: device_put there is a local
                # memcpy, so the narrowing's three extra host passes
                # (min/max/subtract) are pure loss (~30% of normalize time
                # at 1B rows, measured).
                a = a.astype(np.int64, copy=False)
                base = int(a[:rows].min()) if rows and self._narrow_time else 0
                span = (
                    int(a[:rows].max()) - base
                    if rows and self._narrow_time
                    else 1 << 31
                )
                if span < (1 << 31):
                    off = (a - base).astype(np.int32)
                    if rows < chunk_rows:
                        off = np.concatenate(
                            [off[:rows],
                             np.zeros(chunk_rows - rows, np.int32)]
                        )
                    out["__time_off"] = off
                    out["__time_base"] = np.int64(base)
                    continue
                fill = 0
            elif a.dtype.kind in ("i", "u", "b"):
                a = a.astype(np.int32, copy=False)
                fill = 0
            else:
                a = a.astype(np.float32, copy=False)
                fill = 0
            if rows < chunk_rows:
                pad = np.full(chunk_rows - rows, fill, dtype=a.dtype)
                a = np.concatenate([a, pad])
            out[n] = a
        # validity travels as the scalar row count (1 byte/row saved); the
        # device rebuilds the mask with one iota compare
        out["__rows"] = rows
        return out

    def _prefetched_device_chunks(
        self, chunks, need, ds: DataSource, chunk_rows: int
    ) -> Iterator[Dict[str, jnp.ndarray]]:
        """Background thread normalizes host chunks; the consumer side does
        the (async) device_put so all JAX interaction stays on one thread."""
        q: "queue.Queue" = queue.Queue(maxsize=self.prefetch)
        cancelled = threading.Event()

        def _put(item) -> bool:
            # bounded put that gives up when the consumer is gone, so a
            # failing query never leaves the producer parked in q.put
            # pinning chunk buffers and the source iterator
            while not cancelled.is_set():
                try:
                    q.put(item, timeout=0.1)
                    return True
                except queue.Full:
                    continue
            return False

        import time as _time

        def produce():
            try:
                # graftlint: disable=checkpoint-coverage -- producer THREAD: the deadline contextvar lives on the query thread; cancellation reaches this loop via cancelled.set() in the consumer's finally, and the consumer's chunk loop checkpoints
                for chunk in chunks:
                    t0 = _time.perf_counter()
                    item = self._normalize_chunk(chunk, need, ds, chunk_rows)
                    self.stats.normalize_s += _time.perf_counter() - t0
                    if not _put(item):
                        return
                _put(_STOP)
            except BaseException as e:  # fault-ok: surfaced to (re-raised by) consumer
                _put(e)

        sharding = None
        if self.mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            from ..parallel.mesh import DATA_AXIS

            sharding = NamedSharding(self.mesh, P(DATA_AXIS))

        from ..obs import SPAN_PREFETCH, span
        from .pipeline import pipelined_put

        # double buffering (exec/pipeline.py, ISSUE 10): hold ONE chunk
        # back so chunk k+1's h2d issue lands in the dispatch queue
        # BEFORE chunk k's compute program — the link streams behind the
        # device instead of serializing in front of it.  Disabled with
        # the engine's transfer pipeline (the bench's off-counterfactual).
        double_buffer = self.engine._pipeline.enabled
        held = None
        t = threading.Thread(target=produce, daemon=True)
        t.start()
        try:
            while True:
                item = q.get()
                if item is _STOP:
                    break
                if isinstance(item, BaseException):
                    raise item
                rows = item.pop("__rows")
                base = item.pop("__time_base", np.int64(0))
                t0 = _time.perf_counter()
                dev: Dict[str, jnp.ndarray] = {}
                nbytes = 0

                def put_all(item=item, dev=dev):
                    n = 0
                    for k, v in item.items():
                        dev[k], _dt, nb = pipelined_put(
                            v, sharding, prefetched=double_buffer
                        )
                        n += nb
                    return n

                if double_buffer:
                    # issue overlapped behind the previous chunk's compute
                    with span(
                        SPAN_PREFETCH, chunk=self.stats.chunks,
                        rows=int(rows),
                    ):
                        nbytes = put_all()
                else:
                    # pipeline off: this put is a foreground transfer the
                    # dispatch waits behind — honest receipt bucket is h2d
                    from ..obs import SPAN_H2D

                    with span(
                        SPAN_H2D, chunk=self.stats.chunks, rows=int(rows)
                    ):
                        nbytes = put_all()
                self.stats.put_s += _time.perf_counter() - t0
                self.stats.h2d_bytes += nbytes
                self.stats.rows += int(rows)
                if not double_buffer:
                    yield dev, base, np.int32(rows)
                    continue
                held, out = (dev, base, np.int32(rows)), held
                if out is not None:
                    yield out
            if held is not None:
                yield held
        finally:
            cancelled.set()
            while True:  # unblock a producer stuck on a full queue
                try:
                    q.get_nowait()
                except queue.Empty:
                    break
            t.join(timeout=5.0)
