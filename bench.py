"""Benchmark driver.  Prints ONE JSON line:
    {"metric": ..., "value": N, "unit": ..., "vs_baseline": N, "detail": {...}}

Modes (argv[1], default "ssb" — the BASELINE.md north star ★):

    ssb        [scale=1.0]   SSB Q1.1-Q4.3 p50 latency + rows/sec/chip
    tpch_q1    [scale=1.0]   config #1: single-table GROUP BY on lineitem
    topn_hll   [scale=1.0]   config #3: top-100 city by revenue + HLL distinct
    timeseries [chunks=12]   config #4: hourly rollup over a streamed event
                             stream (2M-row chunks; 1B rows = chunks=512)
    cube_theta [scale=0.25]  config #5: GROUP BY CUBE + approx_count_distinct

`vs_baseline` compares against single-threaded pandas/numpy float64 on the
same host and the same columns — the stand-in for the reference's
"Spark-on-Parquet without acceleration" baseline (the reference's own Druid
numbers are unavailable: empty reference mount, SURVEY.md §0/§6).  >1 means
the TPU path is faster.
"""

import json
import os
import statistics
import sys
import time


def _device() -> str:
    import jax

    return str(jax.devices()[0])


# ---------------------------------------------------------------------------
# Crash-safe artifacts: every BENCH_*.json is written atomically (tmp +
# os.replace — the oracle-cache pattern), and long per-query sweeps flush
# each completed query to a *_partial.json sidecar incrementally, so a
# watchdog kill mid-window leaves the completed queries' numbers instead of
# a zero-length .tmp (the round-5 SF100 wound).
# ---------------------------------------------------------------------------


def _atomic_write(path: str, payload):
    """Durable atomic file write (str or bytes): tmp + fsync + os.replace,
    so a kill at ANY point leaves the previous artifact whole."""
    import os as _os

    tmp = path + ".tmp"
    mode = "wb" if isinstance(payload, bytes) else "w"
    with open(tmp, mode) as f:
        f.write(payload)
        f.flush()
        _os.fsync(f.fileno())
    _os.replace(tmp, path)


# set by _run_child to BENCH_<tag>_partial.json; bench modes call
# _note_partial after each completed query
_PARTIAL = {"path": None, "mode": None, "items": {}}


def _partial_path(tag: str) -> str:
    import os as _os

    root = _os.environ.get("SD_BENCH_DETAIL_DIR") or _os.path.dirname(
        _os.path.abspath(__file__)
    )
    return _os.path.join(root, "BENCH_%s_partial.json" % tag)


def _note_partial(name, record):
    """Flush one completed query's numbers to the partial sidecar (atomic;
    best-effort — a failed flush must never fail the bench)."""
    if _PARTIAL["path"] is None:
        return
    _PARTIAL["items"][name] = record
    try:
        _atomic_write(
            _PARTIAL["path"],
            json.dumps(
                {
                    "mode": _PARTIAL["mode"],
                    "completed": _PARTIAL["items"],
                    "n_completed": len(_PARTIAL["items"]),
                    "final": False,
                },
                indent=1,
                default=str,
            ),
        )
    except OSError:
        pass


def _timed(fn, reps=3, warmup=1):
    for _ in range(warmup):
        fn()
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    return statistics.median(ts)


# ---------------------------------------------------------------------------
# ★ north star: SSB Q1.1-Q4.3
# ---------------------------------------------------------------------------


def _calibrated_ctx():
    """Context with measured cost constants (plan/calibrate.py): the planner
    then picks kernel strategy + mesh from numbers measured on THIS backend
    (e.g. on CPU the scatter kernel beats the MXU-shaped one-hot by ~200x,
    and the calibrated model routes accordingly).

    The result-level cache is DISABLED: the benchmark measures engine
    execution, and repeated reps would otherwise be served from the cache
    (the Druid-benchmark useCache=false convention)."""
    import spark_druid_olap_tpu as sd
    from spark_druid_olap_tpu.config import SessionConfig

    cfg = SessionConfig.load_calibrated()
    meta = cfg.calibration_meta
    if meta and meta.get("mismatch"):
        # fail LOUDLY: a benchmark must never quietly run with cost
        # constants measured on a different backend
        raise RuntimeError(
            "calibration platform mismatch: %s was measured on %s but the "
            "execution backend is %s — rerun `python bench.py calibrate` "
            "on this backend" % (meta["path"], meta["device"], _device())
        )
    cfg.result_cache_entries = 0
    return sd.TPUOlapContext(cfg)


def _ensure_calibration():
    """Calibrate once per backend; reuse the saved file when it was
    measured on the same device.  A sweep that fails, fails the run."""
    import jax

    from spark_druid_olap_tpu.plan import calibrate as C

    dev = str(jax.devices()[0])
    plat = jax.devices()[0].platform
    # primary file first, then the per-platform sidecar (CPU and TPU
    # runs alternate on one checkout; each overwrites the primary, and
    # SessionConfig.load_calibrated knows the same fallback)
    for cp in (C.DEFAULT_PATH, C.sidecar_path(plat)):
        if not os.path.exists(cp):
            continue
        try:
            with open(cp) as f:
                cal = json.load(f)
        except (OSError, ValueError):
            # a truncated primary (killed mid-write) must not mask a
            # valid sidecar or suppress the re-sweep below
            continue
        # same device AND every constant the routing reads -> reuse.
        # .get() truthiness, not key presence: a budget-truncated sweep
        # saves null for the constants it never reached, and reusing
        # such a file forever would leave them at the profile default
        if (
            cal.get("device") == dev
            and cal.get("stream_bytes_per_s")
            and cal.get("cost_per_row_compact")
            and cal.get("h2d_bytes_per_s")
        ):
            if cp != C.DEFAULT_PATH:
                # promote the matching sidecar so _calibrated_ctx /
                # _stream_bw (which read the primary path) see it
                import shutil

                shutil.copyfile(cp, C.DEFAULT_PATH)
            return
    # bounded so implicit calibration cannot eat the bench run
    # (unmeasured constants stay at profile defaults)
    C.calibrate(
        rows=1 << 22,
        budget_s=float(os.environ.get("SD_CALIBRATE_BUDGET_S", "600")),
    )


def _stream_bw():
    """The calibrated streaming bandwidth of THIS backend (a host-clock
    slope, for the cost model's arithmetic), or None before calibration."""
    return _cal_key("stream_bytes_per_s")


def _cal_key(key):
    """One constant out of the saved calibration file, or None."""
    import json as _json

    from spark_druid_olap_tpu.plan import calibrate as C

    try:
        with open(C.DEFAULT_PATH) as f:
            return _json.load(f).get(key)
    except Exception:
        return None


def _span_tree(ctx):
    """Span tree of the context's most recent query (obs/ tracer), for
    the bench detail artifacts — `python -m tools.obs_dump
    BENCH_<tag>_detail.json` renders it as a phase/latency table.
    Carries the query's cost receipt (obs/prof.py) when one was built."""
    try:
        return ctx.tracer.last_trace_dict()
    except Exception:  # fault-ok: artifacts must not die on a trace gap
        return None


def _receipt_rep(ctx, fn):
    """One FORCE-SAMPLED rep of `fn` for the artifact's honest cost
    receipt (obs/prof.py, ISSUE 9): the sampled rep pays the dispatch
    sync points so device/host/transfer attribution is real, while the
    TIMED reps stay unsampled (their overlap untouched).  Returns
    (receipt_dict_or_None, measured_wall_ms)."""
    import time as _t

    try:
        ctx.tracer.force_sample_next()
    except Exception:  # fault-ok: profiling must never fail a bench
        pass
    t0 = _t.perf_counter()
    fn()
    wall_ms = (_t.perf_counter() - t0) * 1e3
    doc = _span_tree(ctx) or {}
    return doc.get("receipt"), round(wall_ms, 2)


def _ssb_parity(got, want) -> float:
    """Max relative error of an engine SSB result vs the (float64, exact)
    merged oracle.  Grouped results align on sorted group columns; a
    row-set mismatch returns inf."""
    import numpy as np

    if isinstance(want, float):
        g = float(got.iloc[0, -1]) if len(got) else 0.0
        if want == 0.0:
            return abs(g)
        return abs(g - want) / abs(want)
    vcol = want.columns[-1]
    g = [c for c in want.columns if c != vcol]
    got = got.sort_values(g).reset_index(drop=True)
    want = want.sort_values(g).reset_index(drop=True)
    if len(got) != len(want):
        return float("inf")
    for c in g:
        if list(got[c].astype(str)) != list(want[c].astype(str)):
            return float("inf")
    w = np.asarray(want[vcol], dtype=float)
    gv = np.asarray(got[vcol], dtype=float)
    denom = np.where(np.abs(w) > 0, np.abs(w), 1.0)
    return float(np.max(np.abs(gv - w) / denom)) if len(w) else 0.0


def _sharded_workers() -> int:
    from spark_druid_olap_tpu.ingest.shard import sharded_ingest_workers

    return sharded_ingest_workers()


def bench_ssb_streamed(scale: float):
    """SSB at LARGE scale factors: chunked datagen -> streamed encoded
    segments (never the whole flat fact host-side), chunked float64 pandas
    oracle (exact: all SSB aggregates are sums) doubling as the
    single-threaded baseline, engine parity asserted per query."""
    import time as _t

    from spark_druid_olap_tpu.workloads import ssb

    ctx = _calibrated_ctx()
    t0 = _t.perf_counter()
    tables = ssb.register_streamed(ctx, scale=scale, seed=7)
    ingest_s = _t.perf_counter() - t0
    n_rows = ctx.catalog.get("lineorder").num_rows

    # The float64 oracle is a pure function of (scale, seed) and at SF100
    # costs ~an hour of single-core pandas — it timed out a 90-minute TPU
    # window in round 5 while the device sat idle.  Cache it on disk; a
    # cached load still asserts full parity (same exact frames), only the
    # single-threaded-baseline seconds are reused from the measuring run.
    import pickle

    oracle_cache = os.path.join(
        os.path.dirname(os.path.abspath(__file__)),
        ".ssb_oracle_sf%g_seed7.pkl" % scale,
    )
    # bump when the oracle computation OR the synthetic data stream
    # changes: a stale cache must recompute, never silently assert parity
    # against old expected frames.  v2: pre-sorted date generation
    # (workloads/ssb._gen_fact) changed the row<->value pairing.
    oracle_ver = 2
    want = t_pd = None
    if os.path.exists(oracle_cache):
        try:
            with open(oracle_cache, "rb") as f:
                ver, cached_want, cached_t_pd = pickle.load(f)
            # self-healing: recompute on version or query-set drift (a
            # renamed/added query must not KeyError an hour into a window)
            if ver == oracle_ver and set(cached_want) == set(ssb.QUERIES):
                want, t_pd = cached_want, cached_t_pd
        except Exception:
            want = t_pd = None
    if want is None:
        # one decode pass per chunk, all 13 oracle partials on it
        parts = {name: [] for name in ssb.QUERIES}
        t_pd = {name: 0.0 for name in ssb.QUERIES}
        for lo in ssb.fact_chunks(scale, 7, 1 << 22, tables):
            f = ssb.flat_frame_chunk(tables, lo)
            for name in ssb.QUERIES:
                t1 = _t.perf_counter()
                parts[name].append(ssb.oracle(f, name))
                t_pd[name] += _t.perf_counter() - t1
            del f, lo
        want = {n: ssb.merge_oracle_parts(parts[n]) for n in ssb.QUERIES}
        del parts
        try:
            # atomic + fsync'd (_atomic_write): a watchdog kill mid-dump
            # must leave the cache absent or whole, never truncated (a
            # broken pickle would force the hour-long recompute the cache
            # exists to avoid)
            _atomic_write(
                oracle_cache, pickle.dumps((oracle_ver, want, t_pd))
            )
        except Exception:
            pass

    # 3 reps at every scale: median-of-2 is a mean, and a single noisy
    # rep (this host's memory subsystem has ~2x run-to-run variance)
    # polluted round-4's first SF100 q4_1 reading by 3x
    reps = 3
    per_q, tpu_times, ratios, errs = {}, [], [], []
    for name in ssb.QUERIES:
        got = ctx.sql(ssb.QUERIES[name])  # warmup + parity in one
        err = _ssb_parity(got, want[name])
        errs.append(err)
        t_tpu = _timed(
            lambda n=name: ctx.sql(ssb.QUERIES[n]), reps=reps, warmup=0
        )
        # one force-sampled rep AFTER the timed ones: the honest cost
        # receipt for the artifact, without syncs perturbing the timings
        receipt, receipt_wall = _receipt_rep(
            ctx, lambda n=name: ctx.sql(ssb.QUERIES[n])
        )
        per_q[name] = {
            "tpu_ms": round(t_tpu * 1e3, 2),
            "pandas_ms": round(t_pd[name] * 1e3, 2),
            "max_rel_err": round(err, 8),
            "metrics": (
                ctx.last_metrics.to_dict() if ctx.last_metrics else None
            ),
            "receipt": receipt,
            "receipt_wall_ms": receipt_wall,
            "span_tree": _span_tree(ctx),
        }
        _note_partial(name, per_q[name])
        tpu_times.append(t_tpu)
        ratios.append(t_pd[name] / t_tpu)
    p50 = statistics.median(tpu_times)
    assert max(errs) < 1e-3, f"SSB parity failure: max_rel_err={max(errs)}"
    return {
        "metric": "ssb_sf%g_q1-q4_p50_latency" % scale,
        "value": round(p50 * 1e3, 2),
        "unit": "ms",
        "vs_baseline": round(statistics.median(ratios), 2),
        "detail": {
            "rows": n_rows,
            "rows_per_sec_per_chip": round(n_rows / p50),
            "ingest_s": round(ingest_s, 1),
            "ingest_rows_per_sec": round(n_rows / max(ingest_s, 1e-9)),
            "ingest_workers": _sharded_workers(),
            "ingest_path": "sharded",
            "oracle": "chunked float64 pandas, exact; parity asserted",
            "max_rel_err": round(max(errs), 8),
            "queries": per_q,
            "device": _device(),
        },
    }


def bench_ssb(scale: float):
    import spark_druid_olap_tpu as sd
    from spark_druid_olap_tpu.workloads import ssb

    if scale >= 4:
        # the full flat host frame (and its decoded oracle frame) does not
        # survive large SFs — switch to the streamed path
        return bench_ssb_streamed(scale)

    ctx = _calibrated_ctx()
    tables = ssb.gen_tables(scale=scale)
    ssb.register(ctx, tables=tables)
    n_rows = ctx.catalog.get("lineorder").num_rows

    f = ssb.flat_frame(tables)
    per_q = {}
    tpu_times, ratios = [], []
    for name in ssb.QUERIES:
        t_tpu = _timed(lambda n=name: ctx.sql(ssb.QUERIES[n]))
        t_pd = _timed(lambda n=name: ssb.oracle(f, n), reps=1, warmup=0)
        receipt, receipt_wall = _receipt_rep(
            ctx, lambda n=name: ctx.sql(ssb.QUERIES[n])
        )
        per_q[name] = {
            "tpu_ms": round(t_tpu * 1e3, 2),
            "pandas_ms": round(t_pd * 1e3, 2),
            "metrics": (
                ctx.last_metrics.to_dict() if ctx.last_metrics else None
            ),
            "receipt": receipt,
            "receipt_wall_ms": receipt_wall,
            "span_tree": _span_tree(ctx),
        }
        _note_partial(name, per_q[name])
        tpu_times.append(t_tpu)
        ratios.append(t_pd / t_tpu)
    p50 = statistics.median(tpu_times)
    return {
        "metric": "ssb_sf%g_q1-q4_p50_latency" % scale,
        "value": round(p50 * 1e3, 2),
        "unit": "ms",
        "vs_baseline": round(statistics.median(ratios), 2),
        "detail": {
            "rows": n_rows,
            "rows_per_sec_per_chip": round(n_rows / p50),
            "queries": per_q,
            "device": _device(),
        },
    }


def bench_ssb_mesh(scale: float):
    """SSB through the SPMD mesh (VERDICT r3 #3): queries run on BOTH the
    cost-model-routed single-device engine and the DistributedEngine over
    all visible devices, with parity asserted and the mesh-side costs
    (shard assembly, modelled collective) recorded per query.  ALL queries
    execute (VERDICT r4 #1): the SPMD program routes the same kernel
    ladder as the single-device engine — scatter / sparse sort-compaction
    / adaptive domain compaction above the one-hot domain — so no group
    cardinality gates mesh execution any more.

    On the virtual mesh, mesh-vs-single wall time measures SPMD OVERHEAD,
    not scaling (the 8 devices share the host cores); the honest scaling
    inputs for the ARCHITECTURE.md star-budget math are overhead_pct,
    shard-assembly ms, and the measured collective constants.  On a real
    v5e-8 the same mode measures true scaling."""
    import jax

    from spark_druid_olap_tpu.exec.engine import Engine
    from spark_druid_olap_tpu.exec.lowering import lower_groupby
    from spark_druid_olap_tpu.models import query as Q
    from spark_druid_olap_tpu.models.aggregations import (
        Count as A_Count,
        DoubleSum as A_DoubleSum,
    )
    from spark_druid_olap_tpu.parallel.distributed import DistributedEngine
    from spark_druid_olap_tpu.parallel.mesh import make_mesh
    from spark_druid_olap_tpu.plan.cost import _g_tiles, choose_physical
    from spark_druid_olap_tpu.sql.parser import parse_sql
    from spark_druid_olap_tpu.workloads import ssb

    n_dev = len(jax.devices())
    if n_dev < 2:
        raise RuntimeError(
            f"ssb_mesh needs >=2 devices, found {n_dev} (on the CPU set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8)"
        )
    ctx = _calibrated_ctx()
    if scale >= 4:
        # the sharded ingest pipeline uses THREADS, so a live JAX
        # backend (jax.devices() above) is no longer a hazard
        ssb.register_streamed(ctx, scale=scale, seed=7)
    else:
        ssb.register(ctx, tables=ssb.gen_tables(scale=scale))
    n_rows = ctx.catalog.get("lineorder").num_rows
    dist = DistributedEngine(mesh=make_mesh(n_data=n_dev))
    cfg = ctx.config

    per_q = {}
    meshes, overheads, errs = [], [], []
    for name in ssb.QUERIES:
        lp, _, _ = parse_sql(ssb.QUERIES[name])
        rw = ctx._planner().plan(lp)
        ds = ctx.catalog.get(rw.datasource)
        q = rw.query
        if not isinstance(q, Q.GroupByQuery):
            continue
        G = lower_groupby(q, ds).num_groups
        phys = choose_physical(q, ds, G, cfg, n_devices=1)
        rec = {"num_groups": G, "single_strategy": phys.strategy}
        eng = Engine(strategy=phys.strategy)
        single_df = eng.execute(q, ds)  # warmup + parity source
        t_single = _timed(lambda: eng.execute(q, ds), reps=2, warmup=0)
        rec["single_ms"] = round(t_single * 1e3, 2)
        # All 13 queries EXECUTE on the mesh (VERDICT r4 #1): the SPMD
        # program routes the same kernel ladder as the single-device
        # engine (scatter/sparse/adaptive above the one-hot domain), so
        # no G gates execution any more.  Keep the dense modelled cost as
        # context for the strategy the router rejected.
        est_us = (
            ds.num_rows / n_dev * cfg.cost_per_row_dense * _g_tiles(G)
        )
        rec["mesh_dense_modelled_ms"] = round(est_us / 1e3, 1)
        mesh_df = dist.execute(q, ds)  # warmup/compile + shard placement
        dm = dist.last_metrics
        rec["mesh_strategy"] = dm.strategy
        rec["shard_assembly_ms"] = round(dm.h2d_ms, 2)
        rec["est_collective_ms"] = round(dm.est_collective_ms, 3)
        t_mesh = _timed(lambda: dist.execute(q, ds), reps=2, warmup=0)
        rec["mesh_ms"] = round(t_mesh * 1e3, 2)
        rec["mesh_over_single"] = round(t_mesh / t_single, 2)
        overheads.append(t_mesh / t_single)
        meshes.append(t_mesh)
        err = _ssb_parity(mesh_df, single_df)
        rec["max_rel_err_vs_single"] = round(err, 8)
        errs.append(err)
        per_q[name] = rec
    assert errs, (
        "no query fit the virtual-mesh compute budget at this scale; "
        "nothing to assert parity on"
    )
    assert max(errs) < 1e-4, f"mesh parity failure: {errs}"

    # the streaming executor over the same mesh (VERDICT r3 #3: "and
    # through the streaming executor"): hourly rollup chunks sharded over
    # the data axis, dense SPMD per chunk, replicated [G, M] state
    from spark_druid_olap_tpu.exec.streaming import StreamExecutor
    from spark_druid_olap_tpu.utils import datagen

    tsq = Q.TimeseriesQuery(
        datasource="events",
        granularity="hour",
        aggregations=(
            A_Count("n"), A_DoubleSum("v", "value"),
        ),
        intervals=(datagen.event_stream_interval(),),
    )
    eds = datagen.event_stream_schema()
    chunk, nch = 1 << 21, 6
    staged = [datagen.gen_event_chunk(i, chunk) for i in range(nch)]
    sx_single = StreamExecutor()
    sx_mesh = StreamExecutor(mesh=dist.mesh)
    df_s = sx_single.execute(tsq, eds, iter(staged), chunk)
    t0 = time.perf_counter()
    df_s = sx_single.execute(tsq, eds, iter(staged), chunk)
    t_s = time.perf_counter() - t0
    df_m = sx_mesh.execute(tsq, eds, iter(staged), chunk)
    t0 = time.perf_counter()
    df_m = sx_mesh.execute(tsq, eds, iter(staged), chunk)
    t_m = time.perf_counter() - t0
    import numpy as np

    stream_err = float(
        np.max(
            np.abs(
                np.asarray(df_m["v"], float) - np.asarray(df_s["v"], float)
            )
            / np.maximum(np.abs(np.asarray(df_s["v"], float)), 1.0)
        )
    )
    assert stream_err < 1e-4, stream_err
    stream_rec = {
        "rows": nch * chunk,
        "single_rows_per_sec": round(nch * chunk / t_s),
        "mesh_rows_per_sec": round(nch * chunk / t_m),
        "mesh_over_single": round(t_m / t_s, 2),
        "max_rel_err": round(stream_err, 9),
    }

    p50 = statistics.median(meshes)
    overhead = statistics.median(overheads)
    return {
        "metric": "ssb_sf%g_mesh%d_p50_latency" % (scale, n_dev),
        "value": round(p50 * 1e3, 2),
        "unit": "ms",
        # ratio vs the single-device engine on the same backend: >1 means
        # the mesh is faster; on a shared-core virtual mesh expect <=1
        "vs_baseline": round(1.0 / overhead, 2),
        "detail": {
            "rows": n_rows,
            "n_devices": n_dev,
            "mesh_shape": dict(dist.mesh.shape),
            "distributed": True,
            "median_mesh_over_single": round(overhead, 3),
            "queries": per_q,
            "streaming_mesh": stream_rec,
            "device": _device(),
        },
    }


# ---------------------------------------------------------------------------
# config #1: TPC-H Q1
# ---------------------------------------------------------------------------


def bench_tpch_q1(scale: float):
    import numpy as np

    from spark_druid_olap_tpu.catalog.segment import build_datasource
    from spark_druid_olap_tpu.exec.engine import Engine
    from spark_druid_olap_tpu.models.aggregations import (
        Count,
        DoubleSum,
        ExpressionAgg,
    )
    from spark_druid_olap_tpu.models.dimensions import DimensionSpec
    from spark_druid_olap_tpu.models.filters import Bound
    from spark_druid_olap_tpu.models.query import GroupByQuery
    from spark_druid_olap_tpu.plan.expr import col
    from spark_druid_olap_tpu.utils import datagen

    cols = datagen.gen_lineitem(scale=scale, seed=0)
    n_rows = len(cols["l_quantity"])

    ds = build_datasource(
        "tpch",
        cols,
        dimension_cols=datagen.LINEITEM_DIMS,
        metric_cols=["l_quantity", "l_extendedprice", "l_discount", "l_tax"],
        time_col="l_shipdate",
        rows_per_segment=1 << 23,
    )

    cutoff = (
        np.datetime64("1998-09-02").astype("datetime64[D]").astype(int) + 1
    ) * 86_400_000
    q = GroupByQuery(
        datasource="tpch",
        dimensions=(
            DimensionSpec("l_returnflag"),
            DimensionSpec("l_linestatus"),
        ),
        aggregations=(
            DoubleSum("sum_qty", "l_quantity"),
            DoubleSum("sum_base_price", "l_extendedprice"),
            ExpressionAgg(
                "sum_disc_price",
                col("l_extendedprice") * (1 - col("l_discount")),
            ),
            ExpressionAgg(
                "sum_charge",
                col("l_extendedprice")
                * (1 - col("l_discount"))
                * (1 + col("l_tax")),
            ),
            DoubleSum("sum_disc", "l_discount"),
            Count("count_order"),
        ),
        filter=Bound("l_shipdate", upper=str(int(cutoff)), ordering="numeric"),
    )

    from spark_druid_olap_tpu.config import SessionConfig
    from spark_druid_olap_tpu.plan.cost import choose_kernel_strategy

    eng = Engine(
        strategy=choose_kernel_strategy(
            n_rows, 8, SessionConfig.load_calibrated()
        )
    )
    out = eng.execute(q, ds)  # warmup: compile + device transfer
    assert len(out) == 6, out
    p50 = _timed(lambda: eng.execute(q, ds), reps=5, warmup=0)
    # one traced rep for the detail artifact's span tree (direct Engine
    # use has no context tracer; the process-default one serves here)
    from spark_druid_olap_tpu.obs import default_tracer

    with default_tracer().query_trace(query_type="bench"):
        eng.execute(q, ds)
    span_tree = default_tracer().last_trace_dict()

    # pandas oracle baseline (single-threaded host groupby, float64)
    import pandas as pd

    t0 = time.perf_counter()
    m = cols["l_shipdate"] <= cutoff
    df = pd.DataFrame(
        {
            "f": cols["l_returnflag"][m],
            "s": cols["l_linestatus"][m],
            "q": cols["l_quantity"][m].astype(np.float64),
            "p": cols["l_extendedprice"][m].astype(np.float64),
            "d": cols["l_discount"][m].astype(np.float64),
            "t": cols["l_tax"][m].astype(np.float64),
        }
    )
    df["dp"] = df.p * (1 - df.d)
    df["ch"] = df.dp * (1 + df.t)
    df.groupby(["f", "s"]).agg(
        {"q": "sum", "p": "sum", "dp": "sum", "ch": "sum", "d": "sum"}
    )
    pandas_time = time.perf_counter() - t0

    return {
        "metric": "tpch_q1_sf%g_rows_per_sec_per_chip" % scale,
        "value": round(n_rows / p50),
        "unit": "rows/s",
        "vs_baseline": round(pandas_time / p50, 2),
        "detail": {
            "p50_s": round(p50, 5),
            "pandas_baseline_s": round(pandas_time, 5),
            "device": _device(),
            "rows": n_rows,
            "metrics": (
                eng.last_metrics.to_dict() if eng.last_metrics else None
            ),
            "span_tree": span_tree,
        },
    }


# ---------------------------------------------------------------------------
# config #3: TopN + HLL
# ---------------------------------------------------------------------------


def bench_topn_hll(scale: float):
    from spark_druid_olap_tpu.workloads import ssb

    ctx = _calibrated_ctx()
    tables = ssb.gen_tables(scale=scale)
    ssb.register(ctx, tables=tables)
    n_rows = ctx.catalog.get("lineorder").num_rows
    # full SQL path: the planner's TopN rewrite + HLL mapping + calibrated
    # kernel routing (a direct engine call would bypass the cost model)
    sql = (
        "SELECT c_city, sum(lo_revenue) AS revenue, "
        "approx_count_distinct(lo_custkey) AS uniq_custs "
        "FROM lineorder GROUP BY c_city ORDER BY revenue DESC LIMIT 100"
    )
    t_tpu = _timed(lambda: ctx.sql(sql))

    f = ssb.flat_frame(tables)

    def pandas_topn():
        g = f.groupby("c_city", observed=True).agg(
            revenue=("lo_revenue", "sum"),
            uniq_custs=("lo_custkey", "nunique"),
        )
        return g.sort_values("revenue", ascending=False).head(100)

    t_pd = _timed(pandas_topn, reps=1, warmup=0)
    return {
        "metric": "topn100_hll_sf%g_rows_per_sec_per_chip" % scale,
        "value": round(n_rows / t_tpu),
        "unit": "rows/s",
        "vs_baseline": round(t_pd / t_tpu, 2),
        "detail": {
            "p50_s": round(t_tpu, 5),
            "pandas_baseline_s": round(t_pd, 5),
            "device": _device(),
            "rows": n_rows,
        },
    }


def bench_sketch_mesh(scale: float):
    """Sketch merges across the mesh boundary at data size (VERDICT r4 #7):
    HLL register pmax and theta KMV union fold run inside the SPMD program
    over millions of rows, and the partial STATES are compared
    register-for-register against the single-device engine — not just the
    finalized estimates.  (The dryrun covers 442K rows; this is the SF-scale
    artifact.)"""
    import jax
    import numpy as np

    from spark_druid_olap_tpu.exec.engine import Engine
    from spark_druid_olap_tpu.models.aggregations import (
        DoubleSum as A_DoubleSum,
        HyperUnique,
        ThetaSketch,
    )
    from spark_druid_olap_tpu.models.dimensions import DimensionSpec
    from spark_druid_olap_tpu.models.query import GroupByQuery
    from spark_druid_olap_tpu.parallel.distributed import DistributedEngine
    from spark_druid_olap_tpu.parallel.mesh import DATA_AXIS, make_mesh
    from spark_druid_olap_tpu.workloads import ssb

    n_dev = len(jax.devices())
    if n_dev < 2:
        raise RuntimeError("sketch_mesh needs >=2 devices")
    ctx = _calibrated_ctx()
    ssb.register(ctx, tables=ssb.gen_tables(scale=scale))
    ds = ctx.catalog.get("lineorder")
    n_rows = ds.num_rows
    dist = DistributedEngine(mesh=make_mesh(n_data=n_dev))

    queries = {
        "topn_hll": GroupByQuery(
            datasource="lineorder",
            dimensions=(DimensionSpec("c_city"),),
            aggregations=(
                A_DoubleSum("revenue", "lo_revenue"),
                HyperUnique("uniq_custs", "lo_custkey"),
            ),
        ),
        "groupby_theta": GroupByQuery(
            datasource="lineorder",
            dimensions=(DimensionSpec("d_year"), DimensionSpec("c_nation")),
            aggregations=(
                A_DoubleSum("revenue", "lo_revenue"),
                ThetaSketch("uniq_custs", "lo_custkey", size=4096),
            ),
        ),
    }
    per_q = {}
    times = []
    for name, q in queries.items():
        eng = Engine()
        # raw partial sketch states from BOTH executors
        _, la, G, _, _, _, sk_single = eng._partials_for_query(q, ds)
        sk_single = {k: np.asarray(v) for k, v in jax.device_get(sk_single).items()}
        low = dist._lowering_for(q, ds)
        from spark_druid_olap_tpu.exec.metrics import QueryMetrics

        cols, padded = dist._place_shards(
            ds, low.columns, QueryMetrics(query_type="bench")
        )
        run = dist._spmd_fn(
            low, padded // dist.mesh.shape[DATA_AXIS], ds,
            tuple(cols.keys()), "dense",
        )
        _, _, _, sk_mesh = jax.device_get(run(cols))
        sk_mesh = {k: np.asarray(v) for k, v in sk_mesh.items()}
        reg_equal = {}
        for agg in la.sketch_aggs:
            a, b = sk_single[agg.name], sk_mesh[agg.name]
            if isinstance(agg, HyperUnique):
                # HLL registers: int32, pmax merge is order-free — exact
                reg_equal[agg.name] = bool(np.array_equal(a, b))
            else:
                # theta KMV: the kept k-min SET is order-free; compare the
                # retained hash sets per group (sentinel = 0xFFFFFFFF)
                sent = np.uint32(0xFFFFFFFF)
                eq = True
                for g in range(a.shape[0]):
                    sa = np.unique(a[g][a[g] != sent])
                    sb = np.unique(b[g][b[g] != sent])
                    if not np.array_equal(sa, sb):
                        eq = False
                        break
                reg_equal[agg.name] = bool(eq)
        assert all(reg_equal.values()), (name, reg_equal)
        # finalized parity + mesh timing through the public execute path
        mesh_df = dist.execute(q, ds)
        single_df = eng.execute(q, ds)
        t_mesh = _timed(lambda: dist.execute(q, ds), reps=2, warmup=0)
        t_single = _timed(lambda: eng.execute(q, ds), reps=2, warmup=0)
        key = [d.name for d in q.dimensions]
        mesh_df = mesh_df.sort_values(key).reset_index(drop=True)
        single_df = single_df.sort_values(key).reset_index(drop=True)
        assert (mesh_df["uniq_custs"] == single_df["uniq_custs"]).all(), name
        times.append(t_mesh)
        per_q[name] = {
            "num_groups": G,
            "register_equal": reg_equal,
            "single_ms": round(t_single * 1e3, 2),
            "mesh_ms": round(t_mesh * 1e3, 2),
            "mesh_over_single": round(t_mesh / max(t_single, 1e-9), 2),
            "estimate_equal": True,
        }
    p50 = statistics.median(times)
    return {
        "metric": "sketch_mesh_sf%g_p50_latency" % scale,
        "value": round(p50 * 1e3, 2),
        "unit": "ms",
        "vs_baseline": 1.0,  # parity artifact: register equality is the bar
        "detail": {
            "rows": n_rows,
            "n_devices": n_dev,
            "mesh_shape": dict(dist.mesh.shape),
            "queries": per_q,
            "device": _device(),
        },
    }


# ---------------------------------------------------------------------------
# config #4: streaming hourly rollup
# ---------------------------------------------------------------------------


def bench_timeseries(n_chunks: int):
    """Throughput counts end-to-end wall time including host chunk generation
    and H2D streaming — the honest streaming number.  2M-row chunks: a
    chunk's columns fit cache (8M-row chunks measured ~40% slower on CPU);
    1B rows = chunks=512."""
    from spark_druid_olap_tpu.exec.streaming import StreamExecutor
    from spark_druid_olap_tpu.models.aggregations import (
        Count,
        DoubleMax,
        DoubleSum,
    )
    from spark_druid_olap_tpu.models.query import TimeseriesQuery
    from spark_druid_olap_tpu.utils import datagen

    chunk = 1 << 21
    q = TimeseriesQuery(
        datasource="events",
        granularity="hour",
        aggregations=(
            Count("n"),
            DoubleSum("v", "value"),
            DoubleMax("mx", "latency"),
        ),
        intervals=(datagen.event_stream_interval(),),
    )
    ds = datagen.event_stream_schema()
    # pin the kernel class from calibrated constants (hourly buckets ~= the
    # span in hours; a direct Engine has no planner to route for it)
    from spark_druid_olap_tpu.config import SessionConfig
    from spark_druid_olap_tpu.exec.engine import Engine
    from spark_druid_olap_tpu.plan.cost import choose_kernel_strategy

    strat = choose_kernel_strategy(
        chunk, datagen.EVENT_SPAN_HOURS, SessionConfig.load_calibrated()
    )
    ex = StreamExecutor(engine=Engine(strategy=strat))
    # pre-stage the chunks so BOTH sides are timed on identical, already-
    # materialized data: charging the engine (but not pandas) for rng data
    # generation understated the engine ~3x in round 3's first run
    staged = [datagen.gen_event_chunk(i, chunk) for i in range(n_chunks)]
    # touch every staged page before timing EITHER side: the first read
    # pass over tens of GB of freshly-written anonymous memory runs ~5x
    # slower than warm reads on this host (measured 17.9 vs 96.8 M rows/s
    # over the identical data), and the engine always ran first — a
    # methodology bias against it.  Warm pages also match production,
    # where chunks arrive hot from the decoder.
    warm_sink = 0.0
    for c in staged:
        for a in c.values():
            warm_sink += float(a.sum())
    # warmup / compile on one chunk
    ex.execute(q, ds, iter(staged[:1]), chunk)
    t0 = time.perf_counter()
    ex.execute(q, ds, iter(staged), chunk)
    dt = time.perf_counter() - t0
    rows = ex.stats.rows

    # pandas baseline over the same staged chunks (streamed partials, the
    # way a host engine would honestly process an unbounded stream)
    import pandas as pd

    t0 = time.perf_counter()
    parts = []
    for c in staged:
        parts.append(
            pd.DataFrame(
                {"h": c["ts"] // 3_600_000, "v": c["value"],
                 "lat": c["latency"]}
            ).groupby("h").agg(
                n=("v", "count"), v=("v", "sum"), mx=("lat", "max")
            )
        )
    merged = pd.concat(parts).groupby(level=0).agg(
        n=("n", "sum"), v=("v", "sum"), mx=("mx", "max")
    )
    assert len(merged) > 0
    t_pd = time.perf_counter() - t0
    return {
        "metric": "timeseries_hourly_rollup_%dM_rows_per_sec" % (rows // 1_000_000),
        "value": round(rows / dt),
        "unit": "rows/s",
        "vs_baseline": round(t_pd / dt, 2),
        "detail": {
            "wall_s": round(dt, 2),
            "rows": rows,
            "chunks": n_chunks,
            "pandas_s": round(t_pd, 2),
            "pipeline_stages": ex.stats.to_dict(),
            # attribute streaming losses honestly: every chunk's columns
            # cross the host->device link, so the calibrated link rate
            # bounds throughput no matter how fast the device rollup is.
            # h2d bytes are the
            # MEASURED post-normalization transfer (StreamStats), not a
            # guessed layout.
            "h2d_link_bytes_per_s": (h2d_bw := _cal_key("h2d_bytes_per_s")),
            "h2d_link_bound_s": (
                round(ex.stats.h2d_bytes / h2d_bw, 2) if h2d_bw else None
            ),
            "device": _device(),
        },
    }


# ---------------------------------------------------------------------------
# config #5: CUBE + theta
# ---------------------------------------------------------------------------


def bench_cube_theta(scale: float):
    from spark_druid_olap_tpu.workloads import ssb

    ctx = _calibrated_ctx()
    tables = ssb.gen_tables(scale=scale)
    ssb.register(ctx, tables=tables)
    n_rows = ctx.catalog.get("lineorder").num_rows
    sql = (
        "SELECT c_region, s_region, d_year, sum(lo_revenue) AS revenue, "
        "approx_count_distinct(lo_custkey) AS uniq_custs "
        "FROM lineorder GROUP BY CUBE (c_region, s_region, d_year)"
    )
    t_tpu = _timed(lambda: ctx.sql(sql))

    f = ssb.flat_frame(tables)

    def pandas_cube():
        import itertools

        import pandas as pd

        dims = ["c_region", "s_region", "d_year"]
        frames = []
        for r in range(len(dims) + 1):
            for sub in itertools.combinations(dims, r):
                if sub:
                    g = f.groupby(list(sub), observed=True).agg(
                        revenue=("lo_revenue", "sum"),
                        uniq_custs=("lo_custkey", "nunique"),
                    ).reset_index()
                else:
                    g = pd.DataFrame(
                        {
                            "revenue": [f.lo_revenue.sum()],
                            "uniq_custs": [f.lo_custkey.nunique()],
                        }
                    )
                frames.append(g)
        return pd.concat(frames, ignore_index=True)

    t_pd = _timed(pandas_cube, reps=1, warmup=0)
    return {
        "metric": "cube3_theta_sf%g_latency" % scale,
        "value": round(t_tpu * 1e3, 2),
        "unit": "ms",
        "vs_baseline": round(t_pd / t_tpu, 2),
        "detail": {
            "rows": n_rows,
            "grouping_sets": 8,
            "pandas_baseline_s": round(t_pd, 5),
            "device": _device(),
        },
    }


def _assist_ctx(rows: int, mode: str):
    """Fallback-workload context: mode is "auto" (the platform-aware default
    threshold — what a user gets), "off" (assist disabled), or "force"
    (assist at any size — the crossover probe)."""
    import numpy as np
    import pandas as pd

    import spark_druid_olap_tpu as sd
    from spark_druid_olap_tpu.config import SessionConfig

    cfg = SessionConfig.load_calibrated()
    cfg.result_cache_entries = 0
    if mode == "off":
        cfg.device_assist_min_rows = 1 << 62
    elif mode == "force":
        cfg.device_assist_min_rows = 1000
        cfg.device_assist_force = True  # bypass the cost gate: the curve
        # must MEASURE the losing regimes the gate exists to avoid
    cfg.fallback_max_rows = 200_000_000
    ctx = sd.TPUOlapContext(cfg)

    rng = np.random.default_rng(3)
    n_orders = max(1000, rows // 4)
    n_parts = max(500, rows // 20)
    f = pd.DataFrame(
        {
            "l_orderkey": rng.integers(0, n_orders, rows),
            "l_partkey": rng.integers(0, n_parts, rows),
            "l_quantity": rng.integers(1, 51, rows).astype(np.float64),
            "l_extendedprice": (rng.random(rows) * 55_000 + 90).round(2),
            "c_name": np.char.add(
                "Customer#", (rng.integers(0, n_orders // 8, rows)).astype(str)
            ),
            "p_brand": np.char.add(
                "Brand#", rng.integers(11, 56, rows).astype(str)
            ),
            "s_region": rng.choice(
                ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"], rows
            ),
            "p_type": np.char.add(
                "TYPE#", rng.integers(0, 150, rows).astype(str)
            ),
        }
    )
    ctx.register_table(
        "lineitem", f,
        dimensions=(
            "l_orderkey", "l_partkey", "c_name", "p_brand",
            "s_region", "p_type",
        ),
        metrics=("l_quantity", "l_extendedprice"),
    )
    return ctx


ASSIST_QUERIES = {
    # q2-class: window rank over a grouped frame
    "q2_window_rank": """
        SELECT s_region, p_type, mn, rnk FROM
          (SELECT s_region, p_type, min(l_extendedprice) AS mn,
                  RANK() OVER (PARTITION BY s_region
                               ORDER BY min(l_extendedprice)) AS rnk
           FROM lineitem GROUP BY s_region, p_type) x
        WHERE rnk = 1 ORDER BY s_region
    """,
    # q17-class: correlated scalar AVG per part
    "q17_correlated_avg": """
        SELECT sum(l_extendedprice) / 7.0 AS avg_yearly
        FROM lineitem o
        WHERE l_quantity <
              (SELECT 0.5 * avg(l_quantity) FROM lineitem
               WHERE l_partkey = o.l_partkey)
    """,
    # q18-class: IN over a grouped HAVING subquery
    "q18_in_grouped_having": """
        SELECT c_name, l_orderkey, sum(l_quantity) AS total
        FROM lineitem
        WHERE l_orderkey IN
              (SELECT l_orderkey FROM lineitem
               GROUP BY l_orderkey HAVING sum(l_quantity) > 180)
        GROUP BY c_name, l_orderkey
        ORDER BY total DESC, l_orderkey LIMIT 10
    """,
}


def bench_assist(rows: int):
    """Device-assist: never-slower under the DEFAULT auto-threshold, with a
    committed crossover curve (VERDICT r4 #6 / weak #3).

    Round 4 measured assist with the threshold forced to 1000 rows — a
    regime the platform-aware default (SessionConfig.apply_platform_profile:
    8.4M rows on CPU, where engine and interpreter share the silicon) never
    enters, so min_speedup 0.57 told users nothing about shipped behavior.
    This mode measures three things:

    1. headline: auto (default threshold) vs assist-off at `rows` — the
       never-slower guarantee users actually get (>= 1.0 modulo timer noise
       on this shared host; the paths are IDENTICAL when auto declines).
    2. crossover curve: host vs FORCED assist at several sizes — the
       committed evidence for where the threshold should sit on this
       backend (it must lie above the largest losing size).
    3. TPU-conditional projection FROM CALIBRATION: the assisted subtree's
       modelled device time (scan bytes / calibrated stream bandwidth +
       dispatch) vs the measured host interpreter time — a number, not
       prose; refreshed automatically when a TPU calibration.json lands.
    """
    from spark_druid_olap_tpu.config import SessionConfig

    cfg = SessionConfig.load_calibrated()
    per_q = {}
    # 1. the shipped configuration: auto vs off
    ctxs = {m: _assist_ctx(rows, m) for m in ("auto", "off")}
    for name, q in ASSIST_QUERIES.items():
        rec = {}
        frames = {}
        for m, ctx in ctxs.items():
            ctx.sql(q)  # warmup (compiles, decode caches)
            rec[m + "_ms"] = round(
                _timed(lambda: frames.__setitem__(m, ctx.sql(q)),
                       reps=2, warmup=0) * 1e3, 1,
            )
        rec["auto_executor"] = ctxs["auto"].last_metrics.executor
        rec["auto_assist_subplans"] = (
            ctxs["auto"].last_metrics.assist_subplans
        )
        rec["speedup_auto_vs_off"] = round(
            rec["off_ms"] / max(rec["auto_ms"], 1e-9), 2
        )
        rec["parity_rows"] = bool(len(frames["auto"]) == len(frames["off"]))
        per_q[name] = rec
    del ctxs
    min_speedup = min(r["speedup_auto_vs_off"] for r in per_q.values())

    # 2. crossover curve (forced assist vs host at growing sizes)
    curve = []
    for n in (rows // 4, rows, rows * 4):
        cxs = {m: _assist_ctx(n, m) for m in ("off", "force")}
        pt = {"rows": n}
        for name, q in ASSIST_QUERIES.items():
            ts = {}
            for m, ctx in cxs.items():
                ctx.sql(q)
                ts[m] = _timed(lambda: ctx.sql(q), reps=1, warmup=0)
            pt[name] = round(ts["off"] / max(ts["force"], 1e-9), 2)
        curve.append(pt)
        del cxs
    # the shipped guarantee, stated directly: under the default cost gate
    # no query measures slower than assist-off beyond timer noise.  (The
    # gate may still engage subtrees that are a measured WASH — e.g. a
    # G=1 global aggregate, neutral on CPU and a win on TPU — so
    # comparing auto decisions against forced-mode losses would flag
    # noise, not harm.)
    gate_ok = min_speedup >= 0.95

    # 3. TPU-conditional projection from calibration constants: the
    # q18-class aggregate base (sum over ~rows of f32 + int keys)
    scan_bytes = rows * (4 + 4 + 1)
    bw = _stream_bw()
    projection = None
    if bw:
        modelled_device_s = scan_bytes / bw + cfg.cost_dispatch_us / 1e6
        host_s = per_q["q18_in_grouped_having"]["off_ms"] / 1e3
        projection = {
            "modelled_subtree_device_s": round(modelled_device_s, 4),
            "measured_host_interpreter_s": round(host_s, 4),
            "modelled_speedup_if_assisted": round(
                host_s / max(modelled_device_s, 1e-9), 1
            ),
            "calibration_device": cfg.calibration_meta.get("device")
            if cfg.calibration_meta
            else None,
        }
    return {
        "metric": "fallback_assist_auto_min_speedup_%drows" % rows,
        "value": min_speedup,
        "unit": "x",
        # the never-slower bar itself: >= 1.0 means the default threshold
        # never makes a fallback query slower than assist-off
        "vs_baseline": min_speedup,
        "detail": {
            "rows": rows,
            "queries": per_q,
            "crossover_curve": curve,
            "auto_never_slower_within_noise": gate_ok,
            "tpu_projection": projection,
            "device": _device(),
        },
    }


# ---------------------------------------------------------------------------
# cost-model calibration (writes calibration.json; SessionConfig.load_calibrated)
# ---------------------------------------------------------------------------


def _p95(xs):
    """Nearest-rank p95 (int(0.95*n) on a 20-sample array indexed the MAX
    — p100 — so one outlier inflated the published p95)."""
    import math

    xs = sorted(xs)
    return xs[max(0, math.ceil(0.95 * len(xs)) - 1)]


def bench_ingest(rows_m: float):
    """Ingestion-tier benchmark (ISSUE 6): bulk-load throughput of the
    sharded two-phase pipeline vs the serial seed path on an SF100-shaped
    raw workload, plus streamed append->visible latency and compaction
    equivalence.

    The workload is "SF100-shaped": the SSB flat-fact schema at SF100-like
    dimension cardinalities (c_city/s_city 250, p_brand1 1000, date
    attrs), with string attributes RAW (the bulk-load input shape a CSV
    or warehouse export presents) — the serial seed path dictionary-
    encodes them row-by-row against sorted domains; the sharded pipeline
    factorizes per shard and merges dictionaries deterministically.
    `rows_m` is millions of fact rows (memory-bounded for CI; the shape,
    not the row count, is what carries to SF100)."""
    import time as _t

    import numpy as np

    from spark_druid_olap_tpu.catalog.segment import build_datasource
    from spark_druid_olap_tpu.ingest import (
        build_datasource_sharded,
        sharded_ingest_workers,
    )

    n = int(rows_m * 1e6)
    rng = np.random.default_rng(7)
    t0_ms = 820_454_400_000  # 1996-01-01, the SSB date-range anchor

    def _vals(fmt, k):
        return np.array([fmt % i for i in range(k)])

    # SF100-shaped dimension domains (SSB spec cardinalities)
    domains = {
        "c_region": _vals("REGION#%d", 5),
        "c_nation": _vals("NATION#%02d", 25),
        "c_city": _vals("CITY#%03d", 250),
        "s_region": _vals("REGION#%d", 5),
        "s_nation": _vals("NATION#%02d", 25),
        "s_city": _vals("CITY#%03d", 250),
        "p_mfgr": _vals("MFGR#%d", 5),
        "p_category": _vals("MFGR#%02d", 25),
        "p_brand1": _vals("MFGR#%04d", 1000),
    }
    dims = list(domains) + ["d_year", "d_yearmonthnum"]
    metrics = ["lo_quantity", "lo_extendedprice", "lo_revenue",
               "lo_discount"]

    def gen_chunk(lo, hi):
        m = hi - lo
        c = {
            k: v[rng.integers(0, len(v), m)].astype(object)
            for k, v in domains.items()
        }
        year = rng.integers(1992, 1999, m)
        month = rng.integers(1, 13, m)
        c["d_year"] = year.astype(np.int64)
        c["d_yearmonthnum"] = (year * 100 + month).astype(np.int64)
        c["lo_quantity"] = rng.integers(1, 51, m).astype(np.int64)
        c["lo_extendedprice"] = rng.integers(1, 6_000_000, m).astype(
            np.int64
        )
        c["lo_revenue"] = rng.integers(1, 6_000_000, m).astype(np.int64)
        c["lo_discount"] = rng.integers(0, 11, m).astype(np.int64)
        c["lo_orderdate"] = (
            t0_ms + rng.integers(0, 7 * 365, m) * 86_400_000
        )
        return c

    # chunk == segment size: the aligned (zero-copy) reshard path — how a
    # real bulk loader sizes its batches; the misaligned/ragged buffering
    # path is pinned by tests/test_ingest.py
    chunk_rows = 1 << 19
    chunks = [
        gen_chunk(lo, min(lo + chunk_rows, n))
        for lo in range(0, n, chunk_rows)
    ]
    full = {
        k: np.concatenate([c[k] for c in chunks])
        for k in chunks[0]
    }

    # -- (a) bulk load: serial seed path vs sharded pipeline ----------------
    t0 = _t.perf_counter()
    serial_ds = build_datasource(
        "lineorder", full, dims, metrics, time_col="lo_orderdate",
        rows_per_segment=1 << 19,
    )
    t_serial = _t.perf_counter() - t0
    t0 = _t.perf_counter()
    sharded_ds = build_datasource_sharded(
        "lineorder", [dict(c) for c in chunks], dims, metrics,
        time_col="lo_orderdate", rows_per_segment=1 << 19,
    )
    t_sharded = _t.perf_counter() - t0
    # parity: identical dictionaries + identical encoded rows
    for d in dims:
        assert (
            sharded_ds.dicts[d].values == serial_ds.dicts[d].values
        ), f"dictionary drift on {d}"
    assert len(sharded_ds.segments) == len(serial_ds.segments)
    probe = serial_ds.segments[0]
    probe2 = sharded_ds.segments[0]
    for d in dims:
        np.testing.assert_array_equal(probe.dims[d], probe2.dims[d])
    speedup = t_serial / t_sharded

    # -- (b) streamed append -> visible latency -----------------------------
    ctx = _calibrated_ctx()
    ctx.register_datasource(sharded_ds)
    warm = "SELECT sum(lo_revenue) AS r FROM lineorder"
    checksum_before = float(ctx.sql(warm)["r"][0])
    append_ms, visible_ms = [], []
    batch = 128
    for i in range(20):
        rows = {
            k: v[: batch] if k != "c_city" else np.full(
                batch, "CITY#%03d" % (i % 250), dtype=object
            )
            for k, v in gen_chunk(0, batch).items()
        }
        t0 = _t.perf_counter()
        ack = ctx.append_rows("lineorder", rows)
        t1 = _t.perf_counter()
        got = ctx.sql(
            "SELECT count(*) AS n FROM lineorder"
        )
        t2 = _t.perf_counter()
        assert int(got["n"][0]) == ack["totalRows"]
        append_ms.append((t1 - t0) * 1e3)
        visible_ms.append((t2 - t0) * 1e3)
    appended_rows = 20 * batch
    append_tree = _span_tree(ctx)

    # -- (c) compaction: equivalence + version bump -------------------------
    count_q = "SELECT count(*) AS n FROM lineorder"
    checksum_mid = float(ctx.sql(warm)["r"][0])
    count_mid = int(ctx.sql(count_q)["n"][0])
    v_before = ctx.catalog.datasource_version("lineorder")
    t0 = _t.perf_counter()
    summary = ctx.compact("lineorder")
    compact_ms = (_t.perf_counter() - t0) * 1e3
    checksum_after = float(ctx.sql(warm)["r"][0])
    count_after = int(ctx.sql(count_q)["n"][0])
    # counts are exact; the f32 revenue sum may shift in the last ulp
    # when compaction re-draws segment boundaries (different partial-sum
    # association) — bounded-relative, like the SSB parity gate
    assert count_mid == count_after, "compaction changed row count"
    rel = abs(checksum_after - checksum_mid) / max(abs(checksum_mid), 1.0)
    assert rel < 1e-6, f"compaction moved the checksum by {rel}"
    assert summary["datasourceVersion"] > v_before
    compact_tree = _span_tree(ctx)

    return {
        "metric": "ingest_sf100shape_%gM_bulk_rows_per_sec" % rows_m,
        "value": round(n / t_sharded),
        "unit": "rows/s",
        "vs_baseline": round(speedup, 2),
        "detail": {
            "rows": n,
            "columns": len(dims) + len(metrics) + 1,
            "ingest_s": round(t_sharded, 2),
            "ingest_rows_per_sec": round(n / t_sharded),
            "serial_seed_s": round(t_serial, 2),
            "serial_seed_rows_per_sec": round(n / t_serial),
            "bulk_speedup": round(speedup, 2),
            "ingest_workers": sharded_ingest_workers(),
            "segments": len(sharded_ds.segments),
            "append_rows_total": appended_rows,
            "append_p50_ms": round(statistics.median(append_ms), 2),
            "append_p95_ms": round(_p95(append_ms), 2),
            "append_visible_p50_ms": round(
                statistics.median(visible_ms), 2
            ),
            "append_visible_p95_ms": round(_p95(visible_ms), 2),
            "compaction_ms": round(compact_ms, 2),
            "compaction": summary,
            "checksum_rel_drift": rel,
            "pre_append_checksum": checksum_before,
            "span_tree_append": append_tree,
            "span_tree_compact": compact_tree,
            "oracle": "serial-vs-sharded segment equality asserted; "
                      "compaction checksum equivalence asserted",
            "device": _device(),
        },
    }


def bench_deadline(scale: float):
    """Anytime-answers robustness artifact (ISSUE 7): the coverage-vs-
    deadline curve.  SSB-13 runs under a sweep of wall-clock deadlines
    (fractions of each query's measured full latency, down to ~1 ms);
    every run must return a WELL-FORMED answer — exact when the budget
    suffices, coverage-stamped partial when it expires mid-scan, never
    an exception.  The artifact records, per (query, deadline): the
    deadline, the achieved coverage, partial/exact, wall time, and
    oracle equality for coverage=1.0 answers; the headline value is the
    percentage of runs that answered well-formed (target: 100 — the
    pre-ISSUE-7 engine scores 0 on any mid-scan expiry, which all
    turned into errors)."""
    import spark_druid_olap_tpu as sd  # noqa: F401  (bench convention)
    from spark_druid_olap_tpu.utils.floatcmp import frames_allclose
    from spark_druid_olap_tpu.workloads import ssb

    ctx = _calibrated_ctx()
    # every sweep point must EXECUTE: a result-cache hit would report
    # coverage 1.0 without ever testing the deadline machinery
    ctx.config.result_cache_entries = 0
    tables = ssb.gen_tables(scale=scale)
    # smaller segments than the register default so a mid-scan expiry
    # has batch boundaries to land on at any scale
    ssb.register(ctx, tables=tables, rows_per_segment=1 << 17)
    n_rows = ctx.catalog.get("lineorder").num_rows

    oracle, full_ms = {}, {}
    for name, q in ssb.QUERIES.items():
        t = _timed(lambda n=name: ctx.sql(ssb.QUERIES[n]), reps=1)
        oracle[name] = ctx.sql(q)
        full_ms[name] = t * 1e3

    # deadline sweep: fixed 1ms floor (guaranteed mid-scan expiry on any
    # backend), then fractions of each query's own measured full latency
    fractions = (0.0, 0.1, 0.25, 0.5, 1.5)
    curves = {}
    runs = wellformed = exact_checked = 0
    worst_tree = None
    for name, q in ssb.QUERIES.items():
        points = []
        for frac in fractions:
            deadline_ms = max(1.0, frac * full_ms[name])
            ctx.config.query_timeout_ms = int(deadline_ms)
            t0 = time.perf_counter()
            point = {
                "deadline_ms": round(deadline_ms, 2),
                "fraction_of_full": frac,
            }
            runs += 1
            try:
                got = ctx.sql(q)
                m = ctx.last_metrics
                cov = (
                    None
                    if m is None
                    else (m.coverage if m.partial else 1.0)
                )
                point.update(
                    {
                        "wellformed": True,
                        "partial": bool(m.partial) if m else False,
                        "coverage": cov,
                        "rows_seen": m.rows_seen if m else None,
                        "executor": m.executor if m else None,
                        "total_ms": round(
                            (time.perf_counter() - t0) * 1e3, 2
                        ),
                    }
                )
                wellformed += 1
                if cov == 1.0:
                    ok, msg = frames_allclose(got, oracle[name])
                    point["oracle_equal"] = bool(ok)
                    exact_checked += 1
                    if not ok:
                        point["oracle_diff"] = msg[:200]
                if frac == 0.0 and worst_tree is None:
                    worst_tree = _span_tree(ctx)
            except Exception as e:  # fault-ok: the artifact records the miss
                point.update(
                    {
                        "wellformed": False,
                        "error_class": type(e).__name__,
                        "error": str(e)[:200],
                    }
                )
            _note_partial("%s@%g" % (name, frac), point)
            points.append(point)
        curves[name] = points
    ctx.config.query_timeout_ms = 0
    frac_ok = wellformed / max(1, runs)
    oracle_ok = all(
        p.get("oracle_equal", True)
        for pts in curves.values()
        for p in pts
    )
    return {
        "metric": "deadline_ssb_sf%g_wellformed_pct" % scale,
        "value": round(100.0 * frac_ok, 2),
        "unit": "%",
        # 1.0 == every deadline-bounded run answered well-formed (the
        # seed engine turns every mid-scan expiry into an error: 0.0)
        "vs_baseline": round(frac_ok if oracle_ok else 0.0, 4),
        "detail": {
            "rows": n_rows,
            "runs": runs,
            "wellformed": wellformed,
            "exact_answers_checked": exact_checked,
            "oracle_equal_all": oracle_ok,
            "full_latency_ms": {
                k: round(v, 2) for k, v in full_ms.items()
            },
            "deadline_fractions": list(fractions),
            "curves": curves,
            "span_tree_tightest_deadline": worst_tree,
            "device": _device(),
        },
    }


def bench_hammer(scale: float):
    """Async-serving-core artifact (ISSUE 8): hundreds of concurrent
    mixed-priority queries through the HTTP server with micro-batch
    fusion, priority lanes, and the delta-aware result cache armed.
    Four sections:

      1. **fusion** — N compatible concurrent dashboard queries as ONE
         fused device program vs the same N as serial dispatches (wall
         time of the wave: the dispatch-amortization claim).
      2. **result cache** — an identical dashboard refresh served with
         ZERO device dispatch (the hit's span tree is recorded and must
         contain no segment_dispatch/h2d/device_fetch), plus the
         delta-aware refresh after an append (rows_scanned == the
         delta).
      3. **lane isolation** — fast-lane (topN) p50/p95/p99 while a
         storm of slow SF-scale scans saturates the heavy lane, against
         the SAME storm with lane routing effectively off (scans
         admitted interactive): the starvation the lanes exist to
         prevent, measured.
      4. **mixed hammer** — interleaved fast + heavy waves (hundreds of
         queries) with per-lane latency percentiles and zero 500s.

    Headline: fast-lane p95 under heavy-lane saturation;
    `vs_baseline` = lanes-off p95 / lanes-on p95 (the isolation
    factor)."""
    import json as _json
    import statistics as _stats
    import threading as _threading
    import time as _t
    import urllib.request as _url

    from spark_druid_olap_tpu.resilience import injector
    from spark_druid_olap_tpu.server import OlapServer
    from spark_druid_olap_tpu.workloads import ssb

    ctx = _calibrated_ctx()
    cfg = ctx.config
    cfg.result_cache_entries = 0  # sections arm it explicitly
    cfg.fusion_window_ms = 0.0
    cfg.prefer_distributed = False
    n_rows_target = int(6_000_000 * scale)
    cfg.lane_heavy_rows = max(1, n_rows_target // 8)
    ctx.serve.fusion.window_ms = 0.0
    ssb.register(ctx, scale=scale, rows_per_segment=1 << 16)
    n_rows = ctx.catalog.get("lineorder").num_rows
    srv = OlapServer(ctx, port=0).start()
    port = srv.port

    import urllib.error as _uerr

    def post_safe(path, payload, timeout=300):
        req = _url.Request(
            "http://127.0.0.1:%d%s" % (port, path),
            data=_json.dumps(payload).encode(),
            headers={"Content-Type": "application/json"},
            method="POST",
        )
        t0 = _t.perf_counter()
        try:
            with _url.urlopen(req, timeout=timeout) as r:
                body = r.read()
                return (
                    r.status,
                    (_t.perf_counter() - t0) * 1e3,
                    dict(r.headers),
                    body,
                )
        except _uerr.HTTPError as e:
            return (
                e.code, (_t.perf_counter() - t0) * 1e3,
                dict(e.headers), e.read(),
            )

    def pcts(vals):
        if not vals:
            return {}
        s = sorted(vals)

        def q(p):
            return s[min(len(s) - 1, int(p * (len(s) - 1) + 0.5))]

        return {
            "p50_ms": round(_stats.median(s), 2),
            "p95_ms": round(q(0.95), 2),
            "p99_ms": round(q(0.99), 2),
            "n": len(s),
        }

    iv = ["1992-01-01T00:00:00Z/1999-01-01T00:00:00Z"]
    fast_specs = [
        {
            "queryType": "topN", "dataSource": "lineorder",
            "granularity": "all", "dimension": "c_region",
            "metric": "r", "threshold": 5,
            "aggregations": [
                {"type": "doubleSum", "name": "r",
                 "fieldName": "lo_revenue"},
            ],
            "intervals": iv,
        },
        {
            "queryType": "timeseries", "dataSource": "lineorder",
            "granularity": "year",
            "aggregations": [
                {"type": "doubleSum", "name": "r",
                 "fieldName": "lo_revenue"},
                {"type": "count", "name": "n"},
            ],
            "intervals": iv,
        },
        {
            "queryType": "groupBy", "dataSource": "lineorder",
            "granularity": "all", "dimensions": ["s_region"],
            "aggregations": [
                {"type": "longSum", "name": "q",
                 "fieldName": "lo_quantity"},
            ],
            "intervals": iv,
        },
        {
            "queryType": "groupBy", "dataSource": "lineorder",
            "granularity": "all", "dimensions": ["d_year"],
            "aggregations": [
                {"type": "doubleSum", "name": "r",
                 "fieldName": "lo_revenue"},
            ],
            "intervals": iv,
        },
    ]
    scan_spec = {
        "queryType": "scan", "dataSource": "lineorder",
        "columns": ["c_region", "lo_revenue"], "limit": 50,
        "intervals": iv,
    }

    def wave(specs, concurrent=True, rounds=1, ctxt=None):
        """Latency list (ms) + status counts for `rounds` waves."""
        lats, codes = [], {}
        for _ in range(rounds):
            results = {}

            def run(i, spec):
                body = dict(spec)
                if ctxt:
                    body["context"] = dict(ctxt)
                code, ms, _h, _b = post_safe("/druid/v2", body)
                results[i] = (code, ms)

            if concurrent:
                ths = [
                    _threading.Thread(target=run, args=(i, s))
                    for i, s in enumerate(specs)
                ]
                for th in ths:
                    th.start()
                for th in ths:
                    th.join()
            else:
                for i, s in enumerate(specs):
                    run(i, s)
            for code, ms in results.values():
                codes[code] = codes.get(code, 0) + 1
                if code == 200:
                    lats.append(ms)
        return lats, codes

    # -- section 1: fusion amortization ---------------------------------
    fusion_n = 8
    fused_specs = (fast_specs * 2)[:fusion_n]
    wave(fused_specs, concurrent=False)  # warm programs + residency
    t0 = _t.perf_counter()
    wave(fused_specs, concurrent=False)
    serial_wall_ms = (_t.perf_counter() - t0) * 1e3
    ctx.serve.fusion.window_ms = 6.0
    wave(fused_specs)  # warm the fused program
    fused_walls = []
    fused_lats = []
    for _ in range(3):
        t0 = _t.perf_counter()
        lats, _codes = wave(fused_specs)
        fused_walls.append((_t.perf_counter() - t0) * 1e3)
        fused_lats.extend(lats)
    fused_wall_ms = min(fused_walls)
    fusion_stats = ctx.serve.fusion.to_dict()
    ctx.serve.fusion.window_ms = 0.0

    # -- section 2: result cache (zero dispatch + delta refresh) --------
    cfg.result_cache_entries = 64
    ctx.serve.result_cache.resize(64)
    gb = fast_specs[3]
    post_safe("/druid/v2", dict(gb, context={"queryId": "hammer-warm"}))
    code, hit_ms, _h, _b = post_safe(
        "/druid/v2", dict(gb, context={"queryId": "hammer-hit"})
    )
    hit_trace = None
    for _ in range(50):  # the ring publish can trail the response bytes
        try:
            with _url.urlopen(
                "http://127.0.0.1:%d/druid/v2/trace/hammer-hit" % port,
                timeout=30,
            ) as r:
                hit_trace = _json.loads(r.read())
            break
        except Exception:
            _t.sleep(0.02)

    def span_names(node):
        out = [node["name"]]
        for c in node.get("children", ()):
            out += span_names(c)
        return out

    hit_spans = span_names(hit_trace["spans"]) if hit_trace else []
    hit_zero_dispatch = hit_trace is not None and not (
        {"segment_dispatch", "h2d", "device_fetch"} & set(hit_spans)
    )
    hit_strategy = (
        ctx.last_metrics.strategy if ctx.last_metrics else ""
    )
    # delta-aware refresh: append 3 rows, re-ask — only the delta scans.
    # Row values are drawn FROM the live dictionaries: a novel value
    # would extend a dictionary (remapping the code space), which is a
    # deliberate full miss — this section measures the append-only path
    ver_before = ctx.catalog.datasource_version("lineorder")
    dsx = ctx.catalog.get("lineorder")

    def dom(col):
        return dsx.dicts[col].values[0]

    ing_code, _ms_i, _h_i, ing_body = post_safe(
        "/druid/v2/ingest/lineorder",
        {
            "rows": [
                {
                    **{
                        c: dom(c)
                        for c in (
                            "c_region", "c_nation", "c_city",
                            "s_region", "s_nation", "s_city",
                            "p_mfgr", "p_category", "p_brand1",
                            "d_year", "d_yearmonthnum", "d_yearmonth",
                            "d_weeknuminyear",
                        )
                    },
                    "lo_orderdate": "1992-01-0%d" % (i + 1),
                    "lo_quantity": 1, "lo_extendedprice": 1.0,
                    "lo_discount": 0.0, "lo_revenue": 1.0,
                    "lo_supplycost": 1.0, "lo_custkey": 0,
                }
                for i in range(3)
            ]
        },
    )
    code, delta_ms, _h, _b = post_safe("/druid/v2", gb)
    delta_strategy = (
        ctx.last_metrics.strategy if ctx.last_metrics else ""
    )
    delta_rows_scanned = (
        ctx.last_metrics.rows_scanned if ctx.last_metrics else -1
    )
    cache_stats = ctx.serve.result_cache.to_dict()

    # -- section 3: lane isolation under a heavy-scan storm -------------
    # scans sleep at their per-segment checkpoint (injected delay —
    # deterministic slowness that releases the GIL) and the fast
    # dashboards run CACHE-WARM (zero-dispatch hits, milliseconds), so
    # the measurement isolates SLOT starvation — the thing lanes fix —
    # from single-core compute contention among the fast queries
    # themselves
    # the fast wave is the INTERACTIVE-class traffic (topN/timeseries —
    # interactive by type); the groupBy dashboards classify heavy at
    # this scale by the row-threshold policy and belong to the storm's
    # lane, not the protected one
    interactive_specs = fast_specs[:2]
    fast_wave = (interactive_specs * 6)[:12]
    wave(fast_specs, concurrent=False)  # warm every entry at this version
    wave([scan_spec], concurrent=False)  # warm the scan path (compile,
    # residency) BEFORE the delay arms: a cold first scan compiles under
    # the GIL and would pollute whichever lane configuration runs first
    injector().arm("engine.scan_loop", "delay", delay_ms=300.0)

    def storm_and_measure():
        stop = _threading.Event()
        storm_codes = {}

        def scanner():
            while not stop.is_set():
                code, _ms, _h, _b = post_safe(
                    "/druid/v2", scan_spec, timeout=600
                )
                storm_codes[code] = storm_codes.get(code, 0) + 1

        scanners = [
            _threading.Thread(target=scanner) for _ in range(6)
        ]
        for th in scanners:
            th.start()
        _t.sleep(0.3)  # let the storm occupy its lane
        lats, codes = wave(fast_wave, concurrent=True, rounds=4)
        stop.set()
        for th in scanners:
            th.join(timeout=600)
        return lats, codes, storm_codes

    baseline_lats, _codes = wave(fast_wave, concurrent=True, rounds=4)
    lanes_on_lats, lanes_on_codes, storm_on = storm_and_measure()
    # lanes OFF counterfactual: classification reads the live config —
    # with the threshold at infinity every scan admits interactive and
    # the storm occupies the interactive slots the dashboards need
    cfg.lane_heavy_rows = 1 << 62
    lanes_off_lats, lanes_off_codes, storm_off = storm_and_measure()
    cfg.lane_heavy_rows = max(1, n_rows_target // 8)
    injector().disarm("engine.scan_loop")

    # -- section 4: mixed hammer (hundreds, both lanes) -----------------
    ctx.serve.fusion.window_ms = 4.0
    mixed_fast, mixed_heavy = [], []
    mixed_codes = {}
    heavy_every = 6  # ~17% heavy traffic

    def mixed_run(i):
        heavy = i % heavy_every == 0
        spec = (
            scan_spec
            if heavy
            else interactive_specs[i % len(interactive_specs)]
        )
        code, ms, _h, _b = post_safe("/druid/v2", spec, timeout=600)
        mixed_codes[code] = mixed_codes.get(code, 0) + 1
        if code == 200:
            (mixed_heavy if heavy else mixed_fast).append(ms)

    total_mixed = 240
    batch = 24
    for lo in range(0, total_mixed, batch):
        ths = [
            _threading.Thread(target=mixed_run, args=(i,))
            for i in range(lo, min(lo + batch, total_mixed))
        ]
        for th in ths:
            th.start()
        for th in ths:
            th.join()
    ctx.serve.fusion.window_ms = 0.0
    health = ctx.resilience.health()
    srv.shutdown()

    lanes_on = pcts(lanes_on_lats)
    lanes_off = pcts(lanes_off_lats)
    isolation = (
        round(lanes_off.get("p95_ms", 0) / lanes_on["p95_ms"], 2)
        if lanes_on.get("p95_ms")
        else 0.0
    )
    return {
        "metric": "hammer_fast_lane_p95_under_heavy_storm_ms",
        "value": lanes_on.get("p95_ms", -1.0),
        "unit": "ms",
        "vs_baseline": isolation,
        "detail": {
            "rows": n_rows,
            "scale": scale,
            "fusion": {
                "n_compatible_queries": fusion_n,
                "serial_dispatches_wall_ms": round(serial_wall_ms, 2),
                "fused_batch_wall_ms": round(fused_wall_ms, 2),
                "fused_speedup": round(
                    serial_wall_ms / max(fused_wall_ms, 1e-9), 3
                ),
                "fused_member_latency": pcts(fused_lats),
                "scheduler": fusion_stats,
                "note": "the amortized quantity is the per-dispatch "
                "device round trip; on local CPU dispatch is ~free so "
                "parity is the expected floor",
            },
            "result_cache": {
                "hit_ms": round(hit_ms, 2),
                "hit_strategy": hit_strategy,
                "hit_zero_device_dispatch": hit_zero_dispatch,
                "hit_span_names": hit_spans,
                "delta_refresh_strategy": delta_strategy,
                "delta_refresh_rows_scanned": delta_rows_scanned,
                "delta_refresh_ms": round(delta_ms, 2),
                "append_status": ing_code,
                "append_ack": (
                    _json.loads(ing_body.decode())
                    if ing_body
                    else None
                ),
                "version_bumped": (
                    ctx.catalog.datasource_version("lineorder")
                    > ver_before
                ),
                "stats": cache_stats,
                "hit_span_tree": hit_trace,
            },
            "lanes": {
                "fast_baseline": pcts(baseline_lats),
                "fast_with_heavy_storm_lanes_on": lanes_on,
                "fast_with_heavy_storm_lanes_off": lanes_off,
                "isolation_factor_p95": isolation,
                "codes_lanes_on": lanes_on_codes,
                "codes_lanes_off": lanes_off_codes,
                "storm_codes_on": storm_on,
                "storm_codes_off": storm_off,
            },
            "mixed_hammer": {
                "total_queries": total_mixed,
                "fast": pcts(mixed_fast),
                "heavy": pcts(mixed_heavy),
                "codes": mixed_codes,
                "server_errors": health["counters"][
                    "server_errors_total"
                ],
            },
            "lane_health": health.get("lanes"),
            "serving": ctx.serve.to_dict(),
            "device": _device(),
        },
    }


def bench_overlap(scale: float):
    """Transfer-pipeline artifact (ISSUE 10): the pipeline-on vs
    pipeline-off counterfactual on the two link-bound paths.

    Section A — the STREAMING ROLLUP (the workload the re-anchor note
    calls link-bound at 45 MB/s): hourly rollup over staged event
    chunks, identical data both modes, receipts from a forced-sample
    trace.  With the pipeline on, chunk k+1's h2d issue precedes chunk
    k's compute dispatch (double buffering) and lands in the receipt's
    `prefetch` bucket; off, every put is a foreground stall in the
    `h2d`/transfer bucket.  Section B — SSB-13 scans, programs warm but
    residency dropped before each measured rep, so every column
    re-crosses the link: per-query receipts give transfer-stall /
    prefetch / overlap-efficiency both modes, and pipeline-on frames
    must be BYTE-identical to pipeline-off (the fold-order contract).

    Headline: mean pipeline-on overlap efficiency across SSB-13
    (device-busy over device-busy + transfer-stall, ROADMAP direction
    4's success metric); vs_baseline is the total transfer-stall ratio
    off/on (how many times less link time sits in front of compute)."""
    import spark_druid_olap_tpu as sd  # noqa: F401  (bench convention)
    from spark_druid_olap_tpu.exec.engine import Engine
    from spark_druid_olap_tpu.exec.streaming import StreamExecutor
    from spark_druid_olap_tpu.models.aggregations import (
        Count,
        DoubleMax,
        DoubleSum,
    )
    from spark_druid_olap_tpu.models.query import TimeseriesQuery
    from spark_druid_olap_tpu.utils import datagen
    from spark_druid_olap_tpu.workloads import ssb

    ctx = _calibrated_ctx()
    # every measured rep must EXECUTE (a result-cache hit moves nothing)
    ctx.config.result_cache_entries = 0

    # -- section A: streaming rollup -----------------------------------------
    chunk = 1 << 19
    n_chunks = max(4, int(round(8 * scale)))
    tsq = TimeseriesQuery(
        datasource="events",
        granularity="hour",
        aggregations=(
            Count("n"),
            DoubleSum("v", "value"),
            DoubleMax("mx", "latency"),
        ),
        intervals=(datagen.event_stream_interval(),),
    )
    events = datagen.event_stream_schema()
    staged = [datagen.gen_event_chunk(i, chunk) for i in range(n_chunks)]
    warm_sink = 0.0  # touch every staged page before timing (see ssb bench)
    for c in staged:
        for a in c.values():
            warm_sink += float(a.sum())
    stream = {}
    stream_frames = {}
    for pipe_mode in ("off", "on"):
        eng = Engine()
        eng._pipeline.enabled = pipe_mode == "on"
        ex = StreamExecutor(engine=eng)
        ex.execute(tsq, events, iter(staged[:1]), chunk)  # compile warmup
        ctx.tracer.force_sample_next()
        t0 = time.perf_counter()
        with ctx.tracer.query_trace(query_type="stream_overlap"):
            stream_frames[pipe_mode] = ex.execute(
                tsq, events, iter(staged), chunk
            )
        wall_s = time.perf_counter() - t0
        doc = ctx.tracer.last_trace_dict() or {}
        rc = doc.get("receipt") or {}
        stream[pipe_mode] = {
            "wall_s": round(wall_s, 3),
            "rows": ex.stats.rows,
            "rows_per_sec": round(ex.stats.rows / max(wall_s, 1e-9)),
            "pipeline_stages": ex.stats.to_dict(),
            "transfer_stall_ms": rc.get("transfer_ms"),
            "prefetch_ms": rc.get("prefetch_ms"),
            "overlap_efficiency": rc.get("overlap_efficiency"),
        }
        _note_partial("stream_%s" % pipe_mode, stream[pipe_mode])
    stream_identical = stream_frames["on"].equals(stream_frames["off"])

    # -- section B: SSB-13 scans ---------------------------------------------
    tables = ssb.gen_tables(scale=scale)
    ssb.register(ctx, tables=tables, rows_per_segment=1 << 17)
    n_rows = ctx.catalog.get("lineorder").num_rows
    queries = {}
    stall_total = {"on": 0.0, "off": 0.0}
    eff = []
    identical_all = True
    frames = {}
    for name, sql_q in ssb.QUERIES.items():
        per = {}
        for pipe_mode in ("off", "on"):
            ctx.engine._pipeline.enabled = pipe_mode == "on"
            ctx.sql(sql_q)  # program/lowering warm
            ctx.engine.drop_residency()  # link re-cold: columns move again
            rc, wall_ms = _receipt_rep(
                ctx,
                lambda n=name, m=pipe_mode, q=sql_q: frames.__setitem__(
                    (n, m), ctx.sql(q)
                ),
            )
            rc = rc or {}
            per[pipe_mode] = {
                "wall_ms": wall_ms,
                "transfer_stall_ms": rc.get("transfer_ms"),
                "prefetch_ms": rc.get("prefetch_ms"),
                "prefetch_bytes": rc.get("prefetch_bytes"),
                "transfer_bytes": rc.get("transfer_bytes"),
                "device_ms": rc.get("device_ms"),
                "overlap_efficiency": rc.get("overlap_efficiency"),
            }
            stall_total[pipe_mode] += float(rc.get("transfer_ms") or 0.0)
        got_on, got_off = frames.pop((name, "on")), frames.pop((name, "off"))
        per["identical"] = bool(
            got_on.reset_index(drop=True).equals(
                got_off.reset_index(drop=True)
            )
        )
        identical_all = identical_all and per["identical"]
        if per["on"]["overlap_efficiency"] is not None:
            eff.append(per["on"]["overlap_efficiency"])
        queries[name] = per
        _note_partial(name, per)
    ctx.engine._pipeline.enabled = True
    mean_eff = sum(eff) / max(1, len(eff))
    stall_ratio = stall_total["off"] / max(stall_total["on"], 1e-9)
    return {
        "metric": "overlap_ssb_sf%g_pipeline_on_efficiency" % scale,
        "value": round(mean_eff, 4),
        "unit": "ratio",
        # how many times less transfer stall sits in front of compute
        # with the pipeline on (identical data, programs warm both ways)
        "vs_baseline": round(stall_ratio, 2),
        "identical": identical_all and stream_identical,
        "detail": {
            "rows": n_rows,
            "stream_rows": stream["on"]["rows"],
            "transfer_stall_ms_on": round(stall_total["on"], 2),
            "transfer_stall_ms_off": round(stall_total["off"], 2),
            "results_identical_on_vs_off": identical_all,
            "stream_identical_on_vs_off": stream_identical,
            "streaming_rollup": stream,
            "queries": queries,
            "pipeline": ctx.engine._pipeline.to_dict(),
            "device": _device(),
        },
    }


def bench_boot(scale: float):
    """Durable-storage boot benchmark (ISSUE 13 satellite): cold-boot
    re-encode vs mmap snapshot restore over the same SSB SF`scale`
    lineorder datasource, WAL replay throughput, and byte-identical
    query results across a kill-and-restart.

    Three measured paths:
      * re-encode — what a storage-less process pays EVERY boot:
        dictionary-encode + segment the raw flat columns from scratch.
      * restore — a context built over the persisted snapshot: per-column
        .npy files open as np.memmap (catalog/persist.LazyColumnMap);
        no row is re-encoded, columns page in lazily on first query.
      * restore+replay — same, with a WAL tail of streamed appends past
        the snapshot watermark replayed through the live append path
        (the crash-recovery shape; yields rows/s replay throughput)."""
    import dataclasses as _dc
    import shutil
    import tempfile
    import time as _t

    import numpy as np

    import spark_druid_olap_tpu as sd
    from spark_druid_olap_tpu.catalog.persist import LazyColumnMap
    from spark_druid_olap_tpu.config import SessionConfig
    from spark_druid_olap_tpu.workloads import ssb

    def _cfg(storage_dir=None):
        cfg = SessionConfig.load_calibrated()
        cfg.result_cache_entries = 0  # measure boots, not cache hits
        cfg.storage_dir = storage_dir
        return cfg

    def _register(ctx):
        if scale >= 4:
            # the full flat host frame does not survive large SFs
            ssb.register_streamed(ctx, scale=scale)
        else:
            ssb.register(ctx, tables=ssb.gen_tables(scale=scale))
        return ctx.catalog.get("lineorder").num_rows

    # -- (a) the storage-less baseline: re-encode at every boot --------------
    t0 = _t.perf_counter()
    ctx_cold = sd.TPUOlapContext(_cfg())
    n_rows = _register(ctx_cold)
    t_reencode = _t.perf_counter() - t0
    del ctx_cold

    # -- (b) one durable registration, then a timed snapshot restore --------
    root = tempfile.mkdtemp(prefix="sdol_boot_bench_")
    try:
        ctx = sd.TPUOlapContext(_cfg(root))
        _register(ctx)  # snapshot flush commits before this returns
        disk_bytes = sum(
            os.path.getsize(os.path.join(dp, f))
            for dp, _, fs in os.walk(root)
            for f in fs
        )
        t0 = _t.perf_counter()
        ctx_restore = sd.TPUOlapContext(_cfg(root))
        t_restore = _t.perf_counter() - t0
        ds = ctx_restore.catalog.get("lineorder")
        disk_backed = all(
            isinstance(s.dims, LazyColumnMap)
            for s in ds.historical_segments()
        )

        # -- (c) WAL tail: streamed appends past the watermark, then a
        # restore that must replay them ---------------------------------
        # domain-value rows (decoded attrs, original int metrics) — the
        # wire shape POST /druid/v2/ingest presents; frame rows align
        # with the raw fact arrays (flat_frame preserves fact order)
        small = ssb.gen_tables(scale=0.01)
        frame = ssb.flat_frame(small)
        batch = 512
        append_cols = {}
        for c in ssb.FLAT_DIMS:
            v = frame[c].to_numpy()[:batch]
            append_cols[c] = (
                v.astype(object) if v.dtype.kind in "UO" else v
            )
        for m in ssb.FLAT_METRICS:
            append_cols[m] = np.asarray(
                small["lineorder"][m]
            )[:batch]
        append_cols["lo_orderdate"] = frame["lo_orderdate"].to_numpy()[
            :batch
        ]
        n_batches = 16
        for _i in range(n_batches):
            ctx_restore.append_rows("lineorder", dict(append_cols))
        queries = list(ssb.QUERIES)[:4]
        pre = {q: ctx_restore.sql(ssb.QUERIES[q]) for q in queries}

        t0 = _t.perf_counter()
        ctx_replayed = sd.TPUOlapContext(_cfg(root))
        t_restore_replay = _t.perf_counter() - t0
        recovery = dict(ctx_replayed.storage.last_recovery or {})
        replay_rows = int(recovery.get("replayed_rows", 0))
        replay_s = max(t_restore_replay - t_restore, 1e-9)

        # byte-identical answers across the restart (the acceptance bar)
        identical = all(
            pre[q].equals(ctx_replayed.sql(ssb.QUERIES[q]))
            for q in queries
        )
        assert identical, "restart changed query results"
        assert replay_rows == n_batches * batch, (
            "WAL replay lost rows: %d != %d"
            % (replay_rows, n_batches * batch)
        )
    finally:
        shutil.rmtree(root, ignore_errors=True)

    speedup = t_reencode / max(t_restore, 1e-9)
    return {
        "metric": "boot_ssb_sf%g_restore_speedup" % scale,
        "value": round(speedup, 2),
        "unit": "x",
        "vs_baseline": round(speedup, 2),
        "detail": {
            "rows": n_rows,
            "reencode_boot_s": round(t_reencode, 3),
            "restore_boot_s": round(t_restore, 3),
            "restore_replay_boot_s": round(t_restore_replay, 3),
            "restore_speedup": round(speedup, 2),
            "snapshot_disk_bytes": disk_bytes,
            "restored_disk_backed": disk_backed,
            "wal_replayed_records": int(
                recovery.get("replayed_records", 0)
            ),
            "wal_replayed_rows": replay_rows,
            "wal_replay_rows_per_sec": round(replay_rows / replay_s),
            "queries_identical_across_restart": identical,
            "queries_checked": queries,
            "oracle": "byte-identical DataFrames across kill-and-restart "
                      "asserted; replayed row count asserted exact",
            "device": _device(),
        },
    }


def bench_arena(scale: float):
    """One-dispatch arena artifact (ISSUE 14): the loop-vs-arena
    counterfactual on SSB-13.

    Identical data, programs warm, residency dropped before every
    measured rep (the arena re-pays its stack build each time — the
    honest comparison).  Per query both modes report the receipt's
    `dispatch_count` (the collapse the arena exists for: O(covered
    batches) -> O(1)), the arena_build bucket, device time, and wall;
    arena-on frames must be BYTE-identical to the loop path (the
    scan-carry fold replays the loop's select/fold tree op-for-op).

    Headline: total dispatch collapse ratio off/on across SSB-13;
    vs_baseline is the loop-path p50 wall over the arena p50 wall
    (how much one traced program beats the per-batch dispatch loop)."""
    import spark_druid_olap_tpu as sd  # noqa: F401  (bench convention)
    from spark_druid_olap_tpu.workloads import ssb

    ctx = _calibrated_ctx()
    # every measured rep must EXECUTE (a result-cache hit moves nothing)
    ctx.config.result_cache_entries = 0
    tables = ssb.gen_tables(scale=scale)
    ssb.register(ctx, tables=tables, rows_per_segment=1 << 17)
    n_rows = ctx.catalog.get("lineorder").num_rows

    queries = {}
    disp_total = {"on": 0, "off": 0}
    walls = {"on": [], "off": []}
    build_ms = []
    identical_all = True
    frames = {}
    for name, sql_q in ssb.QUERIES.items():
        per = {}
        for arena_mode in ("off", "on"):
            ctx.engine.arena_execution = arena_mode == "on"
            ctx.sql(sql_q)  # program/lowering warm
            ctx.engine.drop_residency()  # stack build re-paid every rep
            rc, wall_ms = _receipt_rep(
                ctx,
                lambda n=name, m=arena_mode, q=sql_q: frames.__setitem__(
                    (n, m), ctx.sql(q)
                ),
            )
            rc = rc or {}
            per[arena_mode] = {
                "wall_ms": wall_ms,
                "dispatch_count": rc.get("dispatch_count"),
                "arena_build_ms": rc.get("arena_build_ms"),
                "device_ms": rc.get("device_ms"),
                "transfer_ms": rc.get("transfer_ms"),
            }
            disp_total[arena_mode] += int(rc.get("dispatch_count") or 0)
            walls[arena_mode].append(wall_ms)
            if arena_mode == "on" and rc.get("arena_build_ms"):
                build_ms.append(float(rc["arena_build_ms"]))
        got_on, got_off = frames.pop((name, "on")), frames.pop((name, "off"))
        per["identical"] = bool(
            got_on.reset_index(drop=True).equals(
                got_off.reset_index(drop=True)
            )
        )
        identical_all = identical_all and per["identical"]
        queries[name] = per
        _note_partial(name, per)
    ctx.engine.arena_execution = True
    p50_on = statistics.median(walls["on"])
    p50_off = statistics.median(walls["off"])
    collapse = disp_total["off"] / max(disp_total["on"], 1)
    return {
        "metric": "arena_ssb_sf%g_dispatch_collapse" % scale,
        "value": round(collapse, 2),
        "unit": "ratio",
        # loop-path p50 wall over arena p50 wall: identical data,
        # programs warm, residency cold both ways
        "vs_baseline": round(p50_off / max(p50_on, 1e-9), 2),
        "identical": identical_all,
        "detail": {
            "rows": n_rows,
            "p50_wall_ms_arena": round(p50_on, 2),
            "p50_wall_ms_loop": round(p50_off, 2),
            "dispatches_arena": disp_total["on"],
            "dispatches_loop": disp_total["off"],
            "arena_build_ms_mean": round(
                sum(build_ms) / max(1, len(build_ms)), 3
            ),
            "results_identical_on_vs_off": identical_all,
            "queries": queries,
            "device": _device(),
        },
    }


def _mesh_receipt_rep(ctx, dist, q, ds, name):
    """Force-sampled rep of a DistributedEngine query under the context's
    tracer.  The mesh engine emits its spans (segment_dispatch,
    device_fetch, the shard_h2d event) through obs/span like every other
    executor, so opening the query trace HERE — outermost wins — collects
    them and the
    tracer folds the cost receipt at close, without routing through
    ctx.sql (whose cost model owns backend choice).  Returns
    (result_df, receipt_or_None, wall_ms, span_tree)."""
    import time as _t

    try:
        ctx.tracer.force_sample_next()
    except Exception:  # fault-ok: profiling must never fail a bench
        pass
    t0 = _t.perf_counter()
    with ctx.tracer.query_trace(query_id=name, query_type="bench_mesh"):
        df = dist.execute(q, ds)
    wall_ms = (_t.perf_counter() - t0) * 1e3
    doc = _span_tree(ctx) or {}
    return df, doc.get("receipt"), round(wall_ms, 2), doc


def _find_span_event(node, name):
    """First event dict called `name` in a span tree (depth-first)."""
    if not isinstance(node, dict):
        return None
    for ev in node.get("events") or ():
        if ev.get("name") == name:
            return ev
    for c in node.get("children") or ():
        hit = _find_span_event(c, name)
        if hit is not None:
            return hit
    return None


def bench_mesh_unified(scale: float):
    """Unified-executor counterfactual (ISSUE 15): the same SSB GroupBy
    set through THREE arms in one run — the single-device engine, the
    mesh with the SPMD arena off (legacy per-shard loop), and the mesh
    with the arena on (ONE shard_mapped program, scope as data input,
    collective merge at the boundary) — plus a virtual multi-slice point
    whose merge tree the calibrated cost model chooses (recorded as the
    `merge_tree` span event inside the launch's segment_dispatch).

    Steady-state serving comparison: programs warm and residency KEPT
    across reps in every arm (the arena's whole point is that the
    resident stack amortizes; `bench arena` owns the cold-build
    counterfactual).  The arena arm's receipts must show O(1)
    dispatches per query — that is the acceptance criterion's
    receipt-verified half; p50(mesh arena) vs p50(single) is the other.

    On the virtual CPU mesh the devices share host cores, so mesh-vs-
    single wall time measures SPMD overhead, not scaling; on a real
    multi-chip backend the same mode measures true scaling."""
    import jax

    from spark_druid_olap_tpu.models import query as Q
    from spark_druid_olap_tpu.parallel.distributed import DistributedEngine
    from spark_druid_olap_tpu.parallel.mesh import make_mesh, make_slice_mesh
    from spark_druid_olap_tpu.sql.parser import parse_sql
    from spark_druid_olap_tpu.workloads import ssb

    n_dev = len(jax.devices())
    if n_dev < 2:
        raise RuntimeError(
            f"mesh_unified needs >=2 devices, found {n_dev} (on the CPU "
            "set XLA_FLAGS=--xla_force_host_platform_device_count=8)"
        )
    ctx = _calibrated_ctx()
    # every measured rep must EXECUTE (a result-cache hit moves nothing)
    ctx.config.result_cache_entries = 0
    if scale >= 4:
        ssb.register_streamed(ctx, scale=scale, seed=7)
    else:
        # multi-segment registration (bench_arena's convention): one big
        # segment would make the arena decline and both mesh arms
        # silently run the legacy path — no counterfactual left
        ssb.register(
            ctx, tables=ssb.gen_tables(scale=scale),
            rows_per_segment=1 << 17,
        )
    n_rows = ctx.catalog.get("lineorder").num_rows
    dist = DistributedEngine(mesh=make_mesh(n_data=n_dev))

    per_q = {}
    walls = {"single": [], "mesh_loop": [], "mesh_arena": []}
    disp = {"mesh_loop": 0, "mesh_arena": 0}
    arena_disp_max = 0
    errs = []
    plans = []
    for name in ssb.QUERIES:
        lp, _, _ = parse_sql(ssb.QUERIES[name])
        rw = ctx._planner().plan(lp)
        ds = ctx.catalog.get(rw.datasource)
        if not isinstance(rw.query, Q.GroupByQuery):
            continue
        plans.append((name, rw.query, ds))
    assert plans, "no SSB GroupBy rewrites to run"

    for name, q, ds in plans:
        rec = {}
        # arm 1: single-device engine, its best mode (arena on)
        single_df = ctx.engine.execute(q, ds)  # warm: program + residency
        t_single = _timed(lambda: ctx.engine.execute(q, ds), reps=2, warmup=0)
        rec["single_ms"] = round(t_single * 1e3, 2)
        walls["single"].append(t_single * 1e3)
        # arms 2+3: the SAME DistributedEngine, arena off then on — the
        # counterfactual is the execution strategy, not the placement
        for mode, key in (("off", "mesh_loop"), ("on", "mesh_arena")):
            dist.arena_execution = mode == "on"
            dist.execute(q, ds)  # warm: program + shard placement
            df, rc, _w, tree = _mesh_receipt_rep(ctx, dist, q, ds, name)
            t_mesh = _timed(lambda: dist.execute(q, ds), reps=2, warmup=0)
            rc = rc or {}
            rec[key + "_ms"] = round(t_mesh * 1e3, 2)
            rec[key + "_dispatch_count"] = rc.get("dispatch_count")
            rec[key + "_device_ms"] = rc.get("device_ms")
            rec[key + "_transfer_ms"] = rc.get("transfer_ms")
            walls[key].append(t_mesh * 1e3)
            disp[key] += int(rc.get("dispatch_count") or 0)
            if key == "mesh_arena":
                arena_disp_max = max(
                    arena_disp_max, int(rc.get("dispatch_count") or 0)
                )
                err = _ssb_parity(df, single_df)
                rec["max_rel_err_vs_single"] = round(err, 8)
                errs.append(err)
        rec["mesh_over_single"] = round(
            rec["mesh_arena_ms"] / max(rec["single_ms"], 1e-9), 2
        )
        per_q[name] = rec
        _note_partial(name, rec)
    assert max(errs) < 1e-4, f"mesh_unified parity failure: {errs}"
    dist.arena_execution = True

    # multi-slice point: 2 virtual slices over the same devices; the
    # calibrated cost model picks flat-vs-hierarchical per query and
    # records its pricing as the merge_tree span event
    slice_rec = None
    if n_dev >= 4:
        dslice = DistributedEngine(mesh=make_slice_mesh(2, n_dev // 2))
        sw, trees = [], set()
        merge_ev = None
        for name, q, ds in plans:
            dslice.execute(q, ds)  # warm
            df, rc, _w, tree = _mesh_receipt_rep(
                ctx, dslice, q, ds, name + "_slice"
            )
            ev = _find_span_event(tree.get("spans"), "merge_tree")
            if ev is not None:
                trees.add(str((ev.get("attrs") or {}).get("tree")))
                merge_ev = merge_ev or ev
            t_sl = _timed(lambda: dslice.execute(q, ds), reps=2, warmup=0)
            sw.append(t_sl * 1e3)
            errs.append(_ssb_parity(df, ctx.engine.execute(q, ds)))
        assert max(errs) < 1e-4, f"multi-slice parity failure: {errs}"
        p50_slice = statistics.median(sw)
        p50_single = statistics.median(walls["single"])
        slice_rec = {
            "n_slices": 2,
            "n_devices_per_slice": n_dev // 2,
            "p50_ms": round(p50_slice, 2),
            # single-device-engine equivalents of throughput the 2-slice
            # mesh delivers (>1 = beats one device's engine)
            "slice_equivalents": round(p50_single / max(p50_slice, 1e-9), 2),
            "merge_trees_chosen": sorted(trees),
            "merge_tree_event": merge_ev,
        }

    p50_single = statistics.median(walls["single"])
    p50_loop = statistics.median(walls["mesh_loop"])
    p50_arena = statistics.median(walls["mesh_arena"])
    return {
        "metric": "mesh_unified_sf%g_mesh%d_p50_latency" % (scale, n_dev),
        "value": round(p50_arena, 2),
        "unit": "ms",
        # >=1 is the SF10 acceptance bar: mesh arena p50 <= single p50
        # in the SAME run (on virtual CPU meshes this measures overhead)
        "vs_baseline": round(p50_single / max(p50_arena, 1e-9), 2),
        "detail": {
            "rows": n_rows,
            "n_devices": n_dev,
            "p50_ms_single": round(p50_single, 2),
            "p50_ms_mesh_loop": round(p50_loop, 2),
            "p50_ms_mesh_arena": round(p50_arena, 2),
            "dispatches_mesh_loop": disp["mesh_loop"],
            "dispatches_mesh_arena": disp["mesh_arena"],
            # receipt-verified O(1)-dispatches-per-query evidence: the
            # WORST arena query, not just the total
            "arena_dispatches_per_query_max": arena_disp_max,
            "arena_vs_loop_speedup": round(
                p50_loop / max(p50_arena, 1e-9), 2
            ),
            "max_rel_err_vs_single": round(max(errs), 8),
            "multi_slice": slice_rec,
            "queries": per_q,
            "device": _device(),
        },
    }


def bench_cluster(scale: float):
    """Cluster-tier artifact (ISSUE 16): broker + 4 REAL subprocess
    historicals over one shared snapshot store on one host.  Sections:

      1. **QPS scaling** — the same covered SSB groupby workload driven
         through the broker's scatter/gather against 1, 2, and 4
         historicals (replication = min(2, n)); qps + p50/p95/p99 per
         phase.  One historical computes the full scope in one RPC;
         four split it ~4 ways across processes — the near-linear
         scaling the tier exists for.
      2. **kill-and-recover timeline** — a sequential query stream with
         one historical SIGKILLed mid-stream and respawned while
         queries keep flowing: per-query {t_ms, ms, ok, partial}
         records, zero errors asserted (replication=2 keeps every
         answer exact through the loss).
      3. **rolling restart** — every historical killed and rebooted in
         turn with queries across each window; zero failed, zero
         partial.
      4. one sampled broker receipt attributing scatter/gather/merge
         wall time with the per-historical RPC buckets.

    Headline: qps(4 historicals) / qps(1 historical)."""
    import shutil
    import signal
    import statistics as _stats
    import tempfile
    import threading as _threading
    import time as _t

    import spark_druid_olap_tpu as sd
    from spark_druid_olap_tpu.cluster import ClusterClient
    from spark_druid_olap_tpu.config import SessionConfig
    from spark_druid_olap_tpu.workloads import ssb

    n_nodes = 4
    repo_dir = os.path.dirname(os.path.abspath(__file__))
    root = tempfile.mkdtemp(prefix="sdol_cluster_bench_")
    procs = {}  # nid -> subprocess.Popen
    try:
        cfg = SessionConfig.load_calibrated()
        cfg.result_cache_entries = 0  # measure scatter, not cache hits
        cfg.storage_dir = root
        # cold first RPC per historical pays the program compile; the
        # warmup rotation absorbs it, the timeout must survive it
        cfg.cluster_rpc_timeout_ms = 120_000.0
        broker = sd.TPUOlapContext(cfg)
        ssb.register(broker, scale=scale)  # snapshot flush commits here
        n_rows = broker.catalog.get("lineorder").num_rows

        def _spawn(nid):
            ann = os.path.join(root, "%s.announce.json" % nid)
            if os.path.exists(ann):
                os.remove(ann)
            env = dict(os.environ)
            env["JAX_PLATFORMS"] = "cpu"  # N processes share one host
            t0 = _t.perf_counter()
            p = subprocess.Popen(
                [sys.executable, "-m",
                 "spark_druid_olap_tpu.cluster.historical",
                 "--storage-dir", root, "--node-id", nid,
                 "--port", "0", "--announce", ann],
                cwd=repo_dir, env=env,
                stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
            )
            procs[nid] = p
            return ann, t0

        def _await(nid, ann, t0, timeout_s=300.0):
            # the announce file is written atomically (tmp + rename):
            # existence means the node is serving
            while not os.path.exists(ann):
                if procs[nid].poll() is not None:
                    raise RuntimeError(
                        "historical %s died during boot (rc=%s)"
                        % (nid, procs[nid].returncode)
                    )
                if _t.perf_counter() - t0 > timeout_s:
                    raise RuntimeError("historical %s boot timeout" % nid)
                _t.sleep(0.1)
            with open(ann) as f:
                doc = json.load(f)
            return doc["url"], _t.perf_counter() - t0

        spawned = {nid: _spawn(nid) for nid in
                   ["h%d" % i for i in range(n_nodes)]}
        nodes, boot_s = {}, {}
        for nid, (ann, t0) in spawned.items():
            nodes[nid], boot_s[nid] = _await(nid, ann, t0)

        # covered groupby rotation (int metrics: clustered ⊕ is exact)
        qset = [
            "SELECT %s, %s FROM lineorder GROUP BY %s ORDER BY %s"
            % (d, a, d, d)
            for d, a in [
                ("d_year", "sum(lo_revenue) AS r"),
                ("c_region", "sum(lo_quantity) AS q, count(*) AS n"),
                ("s_nation", "max(lo_extendedprice) AS m, count(*) AS n"),
                ("p_mfgr", "sum(lo_supplycost) AS s"),
                ("d_yearmonth", "sum(lo_revenue) AS r, count(*) AS n"),
                ("c_nation", "sum(lo_discount) AS d2"),
            ]
        ]
        oracles = {q: broker.sql(q) for q in qset}  # local, pre-attach
        max_rel_err = [0.0]

        def _close(a, b, rtol=5e-4):
            # the local fused path and the cluster's per-segment partial
            # states accumulate float32 in different orders; answers
            # agree to float32 tolerance, not byte-for-byte (the chaos
            # sections below DO assert byte-identity, cluster vs
            # cluster, where the chain-ordered fold makes it exact)
            import numpy as np

            if a.shape != b.shape or list(a.columns) != list(b.columns):
                return False
            for c in a.columns:
                av, bv = a[c].to_numpy(), b[c].to_numpy()
                if av.dtype.kind in "iuf" and bv.dtype.kind in "iuf":
                    av = av.astype(float)
                    bv = bv.astype(float)
                    err = float(np.max(
                        np.abs(av - bv) / np.maximum(np.abs(av), 1.0)
                    ))
                    max_rel_err[0] = max(max_rel_err[0], err)
                    if err > rtol:
                        return False
                elif not (av == bv).all():
                    return False
            return True

        def pcts(vals):
            s = sorted(vals)

            def q(p):
                return s[min(len(s) - 1, int(p * (len(s) - 1) + 0.5))]

            return {
                "p50_ms": round(_stats.median(s), 2),
                "p95_ms": round(q(0.95), 2),
                "p99_ms": round(q(0.99), 2),
            }

        def _run_phase(client, n, total=32, threads=4):
            # one rotation of warmup absorbs first-touch mmap page-in
            # and per-historical program compile
            for q in qset:
                broker.sql(q)
            # sequential probe: the scatter path really engaged
            before = client.last_metrics
            df = broker.sql(qset[0])
            m = client.last_metrics
            assert m is not None and m is not before, (
                "phase %d: query did not scatter" % n
            )
            assert m.executor == "cluster" and m.distributed
            assert _close(oracles[qset[0]], df), "clustered answer drifted"
            lat, errors, partials = [], [0], [0]
            lock = _threading.Lock()
            idx = iter(range(total))

            def worker():
                while True:
                    with lock:
                        i = next(idx, None)
                    if i is None:
                        return
                    t0 = _t.perf_counter()
                    try:
                        out = broker.sql(qset[i % len(qset)])
                        ms = (_t.perf_counter() - t0) * 1e3
                        with lock:
                            lat.append(ms)
                            if out.attrs.get("partial"):
                                partials[0] += 1
                    except Exception:
                        with lock:
                            errors[0] += 1

            t0 = _t.perf_counter()
            ts = [_threading.Thread(target=worker) for _ in range(threads)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
            wall = _t.perf_counter() - t0
            return {
                "nodes": n,
                "replication": min(2, n),
                "queries": total,
                "qps": round(total / wall, 2),
                "errors": errors[0],
                "partials": partials[0],
                "segments_scattered": int(m.segments),
                **pcts(lat),
            }

        phases = []
        for n in (1, 2, 4):
            sub = {nid: nodes[nid] for nid in sorted(nodes)[:n]}
            client = ClusterClient(
                broker, nodes=sub, replication=min(2, n)
            ).attach()
            try:
                phases.append(_run_phase(client, n))
            finally:
                client.close()
        assert all(p["errors"] == 0 for p in phases), phases
        assert all(p["partials"] == 0 for p in phases), phases

        # -- full-cluster client for the chaos sections ------------------
        client = ClusterClient(broker, nodes=dict(nodes),
                               replication=2).attach()

        # the chaos oracle is the CLUSTER's own answer under this
        # assignment: the chain-ordered gather fold makes it
        # byte-identical no matter which replica serves each group, so
        # the kill/restart sections assert .equals(), not a tolerance
        cluster_oracles = {}
        for q in qset:
            df = broker.sql(q)
            assert _close(oracles[q], df), "clustered answer drifted"
            cluster_oracles[q] = df

        # one sampled GRAFTED receipt (ISSUE 19): the broker's trace now
        # carries each historical's rendered span subtree under its
        # cluster_rpc span, and the receipt folds the remote
        # device/transfer/host buckets into per-historical attribution
        # (rendered by tools/obs_dump.py)
        broker.tracer.force_sample_next()
        broker.sql(qset[0])
        tdoc = broker.tracer.last_trace_dict()
        rc = tdoc["receipt"]

        def _count_grafts(node):
            a = node.get("attrs") or {}
            n = 1 if (a.get("remote") and not a.get("untraced")) else 0
            return n + sum(
                _count_grafts(c) for c in node.get("children", ())
            )

        receipt = {
            "scatter_ms": rc.get("scatter_ms"),
            "gather_ms": rc.get("gather_ms"),
            "cluster_merge_ms": rc.get("cluster_merge_ms"),
            "wall_ms": rc.get("wall_ms"),
            "unattributed_ms": rc.get("unattributed_ms"),
            "grafted_subtrees": _count_grafts(tdoc["spans"]),
            "nodes": (rc.get("cluster") or {}).get("nodes"),
        }
        assert receipt["nodes"], "broker receipt lost its node buckets"
        # cross-process grafting: real subprocess historicals shipped
        # their subtrees back and the fold attributed their buckets
        assert receipt["grafted_subtrees"] >= 1, receipt
        assert any(
            "device_ms" in b and "transfer_ms" in b
            for b in receipt["nodes"].values()
        ), receipt
        # the ISSUE 19 accounting bar: >= 90% of wall attributed
        assert (
            receipt["unattributed_ms"] <= 0.10 * receipt["wall_ms"]
        ), receipt

        # -- kill-and-recover timeline -----------------------------------
        victim = sorted(nodes)[-1]
        timeline, events = [], []
        rejoined = False
        stream0 = _t.perf_counter()
        ann = t0v = None
        for i in range(30):
            if i == 10:
                procs[victim].send_signal(signal.SIGKILL)
                procs[victim].wait()
                events.append({
                    "t_ms": round((_t.perf_counter() - stream0) * 1e3, 1),
                    "event": "SIGKILL %s" % victim,
                })
            if i == 18:
                ann, t0v = _spawn(victim)
                events.append({
                    "t_ms": round((_t.perf_counter() - stream0) * 1e3, 1),
                    "event": "respawn %s" % victim,
                })
            if ann and not rejoined and os.path.exists(ann):
                url, _boot = _await(victim, ann, t0v)
                client.set_node_url(victim, url)
                rejoined = True
                events.append({
                    "t_ms": round((_t.perf_counter() - stream0) * 1e3, 1),
                    "event": "rejoin %s" % victim,
                })
            q = qset[i % len(qset)]
            t0 = _t.perf_counter()
            try:
                df = broker.sql(q)
                ok = cluster_oracles[q].equals(df)
                partial = bool(df.attrs.get("partial"))
            except Exception:
                ok, partial = False, False
            timeline.append({
                "t_ms": round((t0 - stream0) * 1e3, 1),
                "ms": round((_t.perf_counter() - t0) * 1e3, 2),
                "ok": ok,
                "partial": partial,
            })
        if not rejoined:  # boot outlasted the stream: finish the join
            url, _boot = _await(victim, ann, t0v)
            client.set_node_url(victim, url)
            rejoined = True
        kill_recover = {
            "events": events,
            "timeline": timeline,
            "errors": sum(1 for r in timeline if not r["ok"]),
            "partials": sum(1 for r in timeline if r["partial"]),
        }
        # replication=2: every answer through the loss is EXACT
        assert kill_recover["errors"] == 0, kill_recover
        assert kill_recover["partials"] == 0, kill_recover

        # -- rolling restart: every historical, zero failed queries ------
        _t.sleep(cfg.cluster_breaker_cooldown_ms / 1e3)
        rolled, failed = 0, 0
        for nid in sorted(nodes):
            procs[nid].send_signal(signal.SIGKILL)
            procs[nid].wait()
            for j in range(2):  # through the downtime window
                q = qset[(rolled + j) % len(qset)]
                df = broker.sql(q)
                if not (cluster_oracles[q].equals(df)
                        and not df.attrs.get("partial")):
                    failed += 1
            ann, t0v = _spawn(nid)
            url, _boot = _await(nid, ann, t0v)
            client.set_node_url(nid, url)
            _t.sleep(cfg.cluster_breaker_cooldown_ms / 1e3 + 0.05)
            for j in range(2):  # after rejoin
                q = qset[(rolled + 2 + j) % len(qset)]
                df = broker.sql(q)
                if not (cluster_oracles[q].equals(df)
                        and not df.attrs.get("partial")):
                    failed += 1
            rolled += 4
        assert failed == 0, "rolling restart failed %d queries" % failed
        client.close()
    finally:
        for p in procs.values():
            if p.poll() is None:
                p.terminate()
        for p in procs.values():
            try:
                p.wait(timeout=10)
            except Exception:
                p.kill()
        shutil.rmtree(root, ignore_errors=True)

    qps1, qps4 = phases[0]["qps"], phases[-1]["qps"]
    scaling = qps4 / max(qps1, 1e-9)
    host_cpus = os.cpu_count() or 1
    metric = "cluster_ssb_sf%g_qps_scaling_1to4" % scale
    if host_cpus < n_nodes:
        # N processes on fewer than N cores serialize on the CPU: the
        # scaling number is a property of the host, not the broker —
        # the metric name must not read like a healthy scaling run
        metric += "_corebound"
    return {
        "metric": metric,
        "value": round(scaling, 2),
        "unit": "x",
        "vs_baseline": round(scaling, 2),
        "detail": {
            "rows": n_rows,
            "n_historicals": n_nodes,
            "host_cpus": host_cpus,
            "scaling_note": (
                "host has %d cpu core(s) for %d historicals: QPS is "
                "core-bound and near-linear scaling cannot show; "
                "re-run on a >=%d-core host for the scaling headline"
                % (host_cpus, n_nodes, n_nodes)
                if host_cpus < n_nodes else "ok"
            ),
            "boot_s": {k: round(v, 2) for k, v in sorted(boot_s.items())},
            "phases": phases,
            "receipt": receipt,
            "kill_recover": kill_recover,
            "rolling_restart": {"queries": rolled, "failed": failed},
            "max_rel_err_vs_local": max_rel_err[0],
            "oracle": "every checked answer .equals() the broker's "
                      "pre-attach local answer; kill/restart sections "
                      "assert zero errors and zero partials",
            "device": _device(),
        },
    }


def bench_sanitize(scale: float):
    """graftsan overhead proof (ISSUE 18): the SSB query set runs over
    two identical contexts — fully armed (SDOL_SANITIZE=1, lock witness
    + fold recorder + schedule explorer against the committed contract
    table) vs uninstalled — and the headline is the armed/bare wall
    ratio.  The armed arm must finish with zero violations and zero
    static<->runtime divergences; the bare arm must count EXACTLY zero
    probes (disabled-means-free, measured rather than asserted)."""
    import time as _t

    from spark_druid_olap_tpu.workloads import ssb
    from tools import graftsan

    root = os.path.dirname(os.path.abspath(__file__))

    def _arm_ctx():
        ctx = _calibrated_ctx()
        ssb.register(ctx, tables=ssb.gen_tables(scale=scale))
        return ctx

    def _sweep(ctx, reps=3):
        # warm once (compiles), then best-of over the full query set
        for name in ssb.QUERIES:
            ctx.sql(ssb.QUERIES[name])
        walls = []
        for _ in range(reps):
            t0 = _t.perf_counter()
            for name in ssb.QUERIES:
                ctx.sql(ssb.QUERIES[name])
            walls.append(_t.perf_counter() - t0)
        return min(walls)

    prev_arm = os.environ.get(graftsan.ENV_ARM)
    os.environ[graftsan.ENV_ARM] = "1"
    san = graftsan.install(
        contracts_path=os.path.join(root, "graftsan_contracts.json"),
        root=root, seed=0,
    )
    try:
        ctx = _arm_ctx()  # built INSIDE the window: witnessed locks
        n_rows = ctx.catalog.get("lineorder").num_rows
        armed_s = _sweep(ctx)
        stats = graftsan.stats_doc(san)
        divergences = graftsan.divergence_report(san)
    finally:
        graftsan.uninstall()
        if prev_arm is None:
            os.environ.pop(graftsan.ENV_ARM, None)
        else:
            os.environ[graftsan.ENV_ARM] = prev_arm

    bare_s = _sweep(_arm_ctx())
    ratio = armed_s / max(bare_s, 1e-9)
    return {
        "metric": "sanitize_sf%g_overhead_ratio" % scale,
        "value": round(ratio, 3),
        "unit": "x",
        # vs_baseline reads as "armed costs this many bare runs"
        "vs_baseline": round(ratio, 3),
        "detail": {
            "rows": n_rows,
            "queries": len(ssb.QUERIES),
            "armed_wall_s": round(armed_s, 4),
            "bare_wall_s": round(bare_s, 4),
            "violations": stats["violations"],
            "divergences": stats["divergences"],
            "divergence_rows": divergences,
            "unarmed_probes": graftsan.probe_count(),
            "sanitizer_stats": stats,
            "seed": san.seed,
            "device": _device(),
        },
    }


def bench_calibrate(rows_log2: int):
    import os

    from spark_druid_olap_tpu.plan.calibrate import calibrate

    budget = os.environ.get("SD_CALIBRATE_BUDGET_S")
    out = calibrate(
        rows=1 << rows_log2,
        budget_s=float(budget) if budget else None,
    )
    return {
        "metric": "calibration_cost_per_row_dense",
        "value": out["cost_per_row_dense"],
        "unit": "us/row/tile",
        "vs_baseline": 1.0,
        "detail": out,
    }


MODES = {
    "ssb": (bench_ssb, 1.0),
    "ssb_mesh": (bench_ssb_mesh, 10.0),
    "sketch_mesh": (bench_sketch_mesh, 1.0),
    "assist": (bench_assist, 2_000_000),
    "tpch_q1": (bench_tpch_q1, 1.0),
    "topn_hll": (bench_topn_hll, 1.0),
    "timeseries": (bench_timeseries, 12),
    "cube_theta": (bench_cube_theta, 0.25),
    "ingest": (bench_ingest, 2.0),
    "deadline": (bench_deadline, 1.0),
    "hammer": (bench_hammer, 0.1),
    "overlap": (bench_overlap, 1.0),
    "boot": (bench_boot, 1.0),
    "arena": (bench_arena, 1.0),
    "mesh_unified": (bench_mesh_unified, 10.0),
    "cluster": (bench_cluster, 1.0),
    "sanitize": (bench_sanitize, 0.1),
    "calibrate": (bench_calibrate, 23),
}


def _parse_args(argv):
    mode = "ssb"
    if argv and argv[0] in MODES:
        mode = argv[0]
        argv = argv[1:]
    fn, default_arg = MODES[mode]
    arg = type(default_arg)(argv[0]) if argv else default_arg
    return mode, fn, arg


def _emit(result, tag):
    """Print the driver-facing headline as ONE COMPACT JSON line and write
    everything else to a sidecar detail file.  Round 3 broke the driver's
    parse by letting per-query metrics + probe logs grow the stdout line past
    what it reads; the contract is now: stdout stays small (asserted < 2000
    chars by tests), the full record lives in BENCH_<tag>_detail.json where
    tag is "<mode>_<arg>" (e.g. ssb_100) so runs at different scales of the
    same mode keep separate evidence."""
    root = os.environ.get("SD_BENCH_DETAIL_DIR") or os.path.dirname(
        os.path.abspath(__file__)
    )
    payload = json.dumps(result, indent=1, default=str)
    write_err = None
    detail_path = os.path.join(root, "BENCH_%s_detail.json" % tag)
    try:
        # atomic (tmp + os.replace): a watchdog kill mid-write leaves the
        # previous artifact whole, never a truncated/zero-length file
        _atomic_write(detail_path, payload)
    except OSError as e:
        detail_path, write_err = None, e
    # a non-degraded accelerator run is rare evidence: keep it under a name
    # a later CPU rerun of the same mode cannot overwrite, and point the
    # headline at THAT copy (independent of the primary write — its failure
    # must not null a valid detail_path)
    dev = str(result.get("device", "cpu")).lower()
    if not result.get("degraded") and "cpu" not in dev:
        tpu_path = os.path.join(root, "BENCH_tpu_%s_detail.json" % tag)
        try:
            _atomic_write(tpu_path, payload)
            detail_path = tpu_path
        except OSError as e:
            write_err = write_err or e
    if detail_path is not None and result.get("unit") != "error":
        # a completed run's final artifact supersedes the incremental
        # sidecar; a FAILED run keeps it (the completed queries' numbers
        # are exactly what the sidecar exists to preserve)
        try:
            os.remove(_partial_path(tag))
        except OSError:
            pass
    compact = {
        "metric": result.get("metric", tag),
        "value": result.get("value", 0.0),
        "unit": result.get("unit", "error"),
        "vs_baseline": result.get("vs_baseline", 0.0),
        "degraded": result.get("degraded", False),
        "device": str(result.get("device", "unknown")),
        "detail_artifact": detail_path,
    }
    detail = result.get("detail") or {}
    # a few small load-bearing summary fields, never the nested per-query
    # maps (strings only when short: the whole point is a bounded line)
    for k in (
        "rows", "max_rel_err", "rows_per_sec_per_chip", "ingest_s",
        "ingest_rows_per_sec",
    ):
        v = detail.get(k)
        if isinstance(v, (int, float)) or (
            isinstance(v, str) and len(v) < 100
        ):
            compact[k] = v
    if compact["unit"] == "error" or write_err is not None:
        # never lose the diagnosis to a failed sidecar write
        msg = str(detail.get("error", "")) or ""
        if write_err is not None:
            msg = ("sidecar write failed: %s; " % write_err) + msg
        compact["error"] = msg[:400]
    if detail_path is None:
        # last-ditch: the fat record goes to stderr so a redirect (the watch
        # loop captures 2>) can still recover a rare hardware run's evidence
        print(payload, file=sys.stderr)
    print(json.dumps(compact))


def main(argv=None):
    """Run the chosen mode in this process, on whatever device JAX gives,
    and name that device in the output.  A mode that fails propagates:
    the exit code is non-zero and nothing is rerun anywhere else."""
    mode, fn, arg = _parse_args(sys.argv[1:] if argv is None else argv)
    from spark_druid_olap_tpu.config import SessionConfig
    from spark_druid_olap_tpu.utils import compile_cache

    compile_cache.enable()
    # the sidecars are keyed on mode AND its argument so runs at different
    # scales of one mode keep separate evidence
    tag = "%s_%g" % (mode, arg)
    # incremental partial flush: each completed query lands in
    # BENCH_<tag>_partial.json so a kill mid-run keeps them
    _PARTIAL["path"] = _partial_path(tag)
    _PARTIAL["mode"] = mode
    if mode != "calibrate":
        _ensure_calibration()
    result = fn(arg)
    # cost-constant provenance in every artifact: which file routed the
    # kernels, measured on which device, partial or full sweep, applied
    # or refused (platform mismatch)
    result.setdefault("detail", {})["calibration"] = (
        SessionConfig.load_calibrated().calibration_meta
    )
    result["device"] = _device()
    _emit(result, tag)
    return 0


if __name__ == "__main__":
    sys.exit(main())
