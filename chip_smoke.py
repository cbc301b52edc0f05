"""Bring-up proof: the served SSB path on the attached TPU.

    python chip_smoke.py              one chip: SSB SF10 loaded, served over
                                      HTTP, all 13 queries checked against a
                                      float64 pandas oracle made in this run
    python chip_smoke.py --chips 4    the SPMD mesh over four chips, and
                                      nothing else

One process; it is the only one that touches JAX.  Without a TPU, or on a
machine whose chip count is not the phase's, it exits non-zero before any
query runs.  `--rehearse` lifts that check (CPU rehearsal and the tests,
on however many virtual devices); the last line then names the platform
it really ran on.  One JSON line per phase; the last line is
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}`.
"""

import argparse
import http.client
import json
import os
import resource
import sys
import threading
import time

SUM_RTOL = 2e-5  # f32 device sums vs the float64 oracle
NATIVE_QUERY = {
    "queryType": "groupBy",
    "dataSource": "lineorder",
    "granularity": "all",
    "dimensions": ["d_year"],
    "aggregations": [
        {"type": "count", "name": "n"},
        {"type": "doubleSum", "name": "revenue", "fieldName": "lo_revenue"},
    ],
    "intervals": ["1992-01-01T00:00:00.000Z/1999-01-01T00:00:00.000Z"],
}


def say(**line):
    print(json.dumps(line, default=str), flush=True)


def peak_rss_mb():
    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss // 1024


def cache_entries(cache_dir):
    return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0


def load_ssb(ctx, scale, seed):
    from spark_druid_olap_tpu import native
    from spark_druid_olap_tpu.workloads import ssb

    t0 = time.perf_counter()
    if scale >= 4:
        tables = ssb.register_streamed(ctx, scale=scale, seed=seed)
    else:
        tables = ssb.gen_tables(scale=scale, seed=seed)
        ssb.register(ctx, tables=tables)
    ds = ctx.catalog.get("lineorder")
    say(
        phase="load", scale=scale, seed=seed, rows=ds.num_rows,
        segments=len(ds.segments),
        ingest_s=round(time.perf_counter() - t0, 2),
        native_available=native.available(), peak_rss_mb=peak_rss_mb(),
    )
    return tables


def oracle_frames(tables, scale, seed):
    """Decoded float64 pandas frames of the fact, chunked the way the
    ingest chunked it (workloads/ssb.gen_fact_chunk owns the geometry)."""
    from spark_druid_olap_tpu.workloads import ssb

    if scale < 4:
        yield ssb.flat_frame(tables)
        return
    categories = ssb.oracle_categories(tables)
    for lo in ssb.fact_chunks(scale, seed, 1 << 22, tables):
        yield ssb.flat_frame_chunk(tables, lo, categories)


def compute_oracle(tables, scale, seed):
    """The 13 SSB answers plus the native groupBy's, in float64 pandas,
    from the seed — nothing is read from disk."""
    import pandas as pd

    from spark_druid_olap_tpu.workloads import ssb

    t0 = time.perf_counter()
    parts = {name: [] for name in ssb.QUERIES}
    native_parts = []
    for f in oracle_frames(tables, scale, seed):
        for name in ssb.QUERIES:
            parts[name].append(ssb.oracle(f, name))
        native_parts.append(
            f.groupby("d_year").agg(
                n=("lo_revenue", "size"), revenue=("lo_revenue", "sum")
            )
        )
    want = {n: ssb.merge_oracle_parts(parts[n]) for n in ssb.QUERIES}
    want_native = pd.concat(native_parts).groupby(level=0).sum().reset_index()
    say(
        phase="oracle", oracle_s=round(time.perf_counter() - t0, 2),
        peak_rss_mb=peak_rss_mb(),
    )
    return want, want_native


def parity(got, want, exact=()):
    """None when `got` equals the oracle (group keys exact, the columns in
    `exact` equal as integers, the last column's sums within SUM_RTOL),
    else what differs."""
    import numpy as np

    if isinstance(want, float):
        if len(got) != 1:
            return f"{len(got)} rows, want 1"
        g = float(got.iloc[0, -1])
        if abs(g - want) > SUM_RTOL * abs(want):
            return f"sum {g!r} vs {want!r}"
        return None
    vcol = want.columns[-1]
    keys = [c for c in want.columns if c != vcol and c not in exact]
    if len(got) != len(want):
        return f"{len(got)} groups, want {len(want)}"
    if not len(want):
        return None
    # keys compare as strings: JSON and pandas disagree on int vs str years
    got = got.assign(**{c: got[c].astype(str) for c in keys})
    want = want.assign(**{c: want[c].astype(str) for c in keys})
    got = got.sort_values(keys).reset_index(drop=True)
    want = want.sort_values(keys).reset_index(drop=True)
    for c in keys:
        if list(got[c]) != list(want[c]):
            return f"group column {c} differs"
    for c in exact:
        if list(got[c].astype(int)) != list(want[c].astype(int)):
            return f"count column {c} differs"
    w = np.asarray(want[vcol], dtype=np.float64)
    g = np.asarray(got[vcol], dtype=np.float64)
    if not np.allclose(g, w, rtol=SUM_RTOL, atol=0.0):
        return "max rel err %.3g in %s" % (
            float(np.max(np.abs(g - w) / np.maximum(np.abs(w), 1e-300))),
            vcol,
        )
    return None


def metrics_faults(m, distributed=False):
    """What in a query's QueryMetrics says it left the path this phase
    proves: the device, undegraded, first time, on one chip or the mesh.
    A tier never gives way to another on a device error (it raises into
    the retry machinery), so `retries` covers that too."""
    if m is None:
        return ["no metrics"]
    out = []
    if bool(m.distributed) != distributed:
        out.append(f"distributed={m.distributed} mesh_shape={m.mesh_shape}")
    if m.executor != "device":
        out.append(f"executor={m.executor}")
    if m.degraded:
        out.append("degraded")
    if m.retries:
        out.append(f"retries={m.retries}")
    if m.partial:
        out.append("partial")
    return out


def post(port, path, body):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=600)
    try:
        conn.request(
            "POST", path, json.dumps(body),
            {"Content-Type": "application/json"},
        )
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read())
    finally:
        conn.close()


def serve_phase(ctx, want, want_native, platform):
    """All 13 SSB queries twice (cold, warm) plus one native groupBy over
    HTTP from a client thread; returns the list of failures.  Stops at the
    first query that fails: the run is lost anyway, and a query that left
    its path can cost minutes of chip time."""
    import pandas as pd

    from spark_druid_olap_tpu.ops.pallas_groupby import pallas_available
    from spark_druid_olap_tpu.server import OlapServer
    from spark_druid_olap_tpu.workloads import ssb

    failures = []
    strategies = {}

    def sql_once(name):
        t0 = time.perf_counter()
        code, out = post(srv.port, "/druid/v2/sql", {"query": ssb.QUERIES[name]})
        ms = (time.perf_counter() - t0) * 1e3
        if code != 200:
            return ms, None, None, [f"HTTP {code}: {out}"]
        m = ctx.last_metrics
        bad = metrics_faults(m)
        diff = parity(pd.DataFrame(out), want[name])
        if diff:
            bad.append("parity: " + diff)
        return ms, out, m, bad

    def client():
        for name in ssb.QUERIES:
            cold_ms, _, mc, bad_c = sql_once(name)
            warm_ms, out, mw, bad_w = sql_once(name)
            bad = [f"cold {b}" for b in bad_c] + [f"warm {b}" for b in bad_w]
            if mw is not None and mw.segments:
                # (a filter that prunes every segment builds no program)
                if not mw.program_cache_hit:
                    bad.append("warm pass missed the program cache")
                if mw.bytes_resident <= 0:
                    bad.append("warm pass: nothing resident on the device")
            if mw is not None:
                strategies[name] = mw.strategy
            say(
                phase="query", query=name,
                cold_ms=round(cold_ms, 2), warm_ms=round(warm_ms, 2),
                compile_ms=round(mc.compile_ms, 2) if mc else None,
                strategy=mw.strategy if mw else None,
                num_groups=mw.num_groups if mw else None,
                segments=mw.segments if mw else None,
                rows_out=len(out) if out is not None else None,
                warm_h2d_bytes=mw.h2d_bytes if mw else None,
                warm_device_ms=round(mw.device_ms, 2) if mw else None,
                bytes_resident=mw.bytes_resident if mw else None,
                checked="pandas float64", ok=not bad, faults=bad,
            )
            failures.extend(f"{name}: {b}" for b in bad)
            if bad:
                return

        t0 = time.perf_counter()
        code, out = post(srv.port, "/druid/v2", NATIVE_QUERY)
        ms = (time.perf_counter() - t0) * 1e3
        if code != 200:
            bad = [f"HTTP {code}: {out}"]
        else:
            bad = metrics_faults(ctx.last_metrics)
            got = pd.DataFrame([r["event"] for r in out])
            diff = parity(got, want_native, exact=("n",))
            if diff:
                bad.append("parity: " + diff)
        say(
            phase="native_groupby", ms=round(ms, 2),
            rows_out=len(out) if code == 200 else None,
            strategy=ctx.last_metrics.strategy if code == 200 else None,
            ok=not bad, faults=bad,
        )
        failures.extend(f"native groupBy: {b}" for b in bad)

    srv = OlapServer(ctx, port=0).start()
    try:
        crash = []

        def guarded():
            try:
                client()
            except BaseException as e:  # re-raised on the main thread below
                crash.append(e)

        t = threading.Thread(target=guarded, name="smoke-client")
        t.start()
        t.join()
        if crash:
            raise crash[0]
    finally:
        srv.shutdown()

    on_kernel = sorted(n for n, s in strategies.items() if s == "pallas")
    say(
        phase="routing", strategies=strategies,
        pallas_available=pallas_available(), on_pallas_kernel=on_kernel,
    )
    if platform == "tpu":
        if not pallas_available():
            failures.append("pallas_available() is false on a TPU")
        if not on_kernel:
            failures.append("no served query ran on the compiled pallas kernel")
    return failures


def mesh_phase(ctx, want, n_chips):
    """The 13 queries through the SPMD mesh over `n_chips` devices."""
    import jax

    from spark_druid_olap_tpu.parallel.distributed import DistributedEngine
    from spark_druid_olap_tpu.parallel.mesh import make_mesh
    from spark_druid_olap_tpu.sql.parser import parse_sql
    from spark_druid_olap_tpu.workloads import ssb

    failures = []
    dist = DistributedEngine(mesh=make_mesh(n_data=n_chips))
    for name in ssb.QUERIES:
        lp, _, _ = parse_sql(ssb.QUERIES[name])
        rw = ctx._planner().plan(lp)
        ds = ctx.catalog.get(rw.datasource)
        times = []
        for _ in range(2):  # cold, warm
            t0 = time.perf_counter()
            df = ctx._post_process(rw, ds, dist.execute(rw.query, ds))
            times.append((time.perf_counter() - t0) * 1e3)
        m = dist.last_metrics
        bad = metrics_faults(m, distributed=True)
        if tuple(m.mesh_shape or ()) != (n_chips, 1):
            bad.append(f"mesh_shape={m.mesh_shape}")
        diff = parity(df, want[name])
        if diff:
            bad.append("parity: " + diff)
        say(
            phase="mesh_query", query=name, cold_ms=round(times[0], 2),
            warm_ms=round(times[1], 2), strategy=m.strategy,
            num_groups=m.num_groups, mesh_shape=m.mesh_shape,
            checked="pandas float64", ok=not bad, faults=bad,
        )
        failures.extend(f"{name}: {b}" for b in bad)
        if bad:
            return failures

    # every chip must hold its share: code that has only met virtual CPU
    # devices may place everything on device 0
    holders = set()
    for key in list(dist._shard_cache):
        arr = dist._shard_cache.get(key)
        holders |= {s.device for s in arr.addressable_shards}
    in_use = [
        (d.memory_stats() or {}).get("bytes_in_use") for d in jax.devices()
    ]
    say(
        phase="mesh_placement", devices_holding_shards=len(holders),
        shard_cache_bytes=dist._shard_cache.bytes_used,
        bytes_in_use=in_use,
    )
    if len(holders) != n_chips:
        failures.append(
            f"shards on {len(holders)} devices, want {n_chips}"
        )
    if all(b is not None for b in in_use):
        if max(in_use) >= 2 * max(min(in_use), 1):
            failures.append(f"device memory is lopsided: {in_use}")
    elif jax.devices()[0].platform == "tpu":
        failures.append("a TPU device reports no memory_stats()")
    return failures


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--scale", type=float, default=10.0,
                    help="SSB scale factor (default 10: 60M rows)")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--rehearse", action="store_true",
                    help="run without a TPU (CPU rehearsal; never the driver)")
    args = ap.parse_args(argv)
    t_start = time.perf_counter()

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        print(
            f"chip_smoke: no TPU: jax.devices()[0] is {dev} "
            f"(platform {dev.platform!r})", file=sys.stderr,
        )
        return 2
    n_dev = len(jax.devices())
    # on the chip the last line's count IS the phase: the served phase is
    # proven on a one-chip machine, the mesh on a four-chip one.  A
    # rehearsal only needs enough (virtual) devices.
    if n_dev < args.chips or (n_dev != args.chips and not args.rehearse):
        print(
            f"chip_smoke: --chips {args.chips} but JAX sees {n_dev} "
            f"device(s): run this phase on a machine with {args.chips}",
            file=sys.stderr,
        )
        return 2

    from spark_druid_olap_tpu import TPUOlapContext
    from spark_druid_olap_tpu.config import SessionConfig
    from spark_druid_olap_tpu.utils import compile_cache

    cache_dir = compile_cache.enable()
    entries_before = cache_entries(cache_dir)
    cfg = SessionConfig.load_calibrated()
    # every request must execute: a warm pass answered from the result
    # cache would say nothing about the program cache or residency
    cfg.result_cache_entries = 0
    if args.chips == 1:
        # the served phase proves the single-device engine: a rehearsal
        # host that shows more (virtual) devices must not be planned onto
        # a mesh.  With one device this changes nothing.
        cfg.prefer_distributed = False
    ctx = TPUOlapContext(cfg)
    say(
        phase="start", device=str(dev), kind=dev.device_kind,
        count=len(jax.devices()), rehearse=args.rehearse,
        compile_cache_dir=cache_dir, cache_entries_before=entries_before,
    )
    say(phase="calibration", calibration_meta=cfg.calibration_meta)

    tables = load_ssb(ctx, args.scale, args.seed)
    want, want_native = compute_oracle(tables, args.scale, args.seed)
    if args.chips == 1:
        failures = serve_phase(ctx, want, want_native, dev.platform)
    else:
        failures = mesh_phase(ctx, want, args.chips)

    say(
        phase="end", wall_s=round(time.perf_counter() - t_start, 2),
        compile_cache_dir=cache_dir,
        cache_entries_written=cache_entries(cache_dir) - entries_before,
        peak_rss_mb=peak_rss_mb(), memory_stats=dev.memory_stats(),
        failures=failures,
    )
    if failures:
        for f in failures:
            print("chip_smoke: FAILED " + f, file=sys.stderr)
        return 1
    say(ok=True, device={
        "platform": dev.platform, "kind": dev.device_kind,
        "count": n_dev,
    })
    return 0


if __name__ == "__main__":
    sys.exit(main())
