"""Measure cost-model constants on the live backend.

Reference parity: the reference's `DruidQueryCostModel` ships tunable cost
constants via SQLConf with documented defaults the operator is expected to
re-tune per deployment (SURVEY.md §2 cost-model row `[U]`).  Round 1 shipped
guessed constants; this module replaces guessing with measurement: it times
the actual kernels the engine dispatches —

* dense one-hot partial aggregation (`ops/groupby.dense_partial_aggregate`)
  -> `cost_per_row_dense` (us per row per 128-wide group tile),
* scatter segment-sum                    -> `cost_per_row_scatter` (us/row),
* psum of a [G, M] state over the mesh   -> `collective_bytes_per_us`,
* a tiny end-to-end SPMD dispatch        -> `cost_dispatch_us`

— and writes `calibration.json` at the repo root, which
`SessionConfig.load_calibrated()` reads.  Run on the TPU to get real-chip
constants; on CPU the constants are CPU-honest (the planner's choices then
match the backend that will actually execute).
"""

from __future__ import annotations

import functools
import json
import os
import time
from typing import Dict, Optional

import numpy as np

_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_PATH = os.path.join(_REPO_ROOT, "calibration.json")


def sidecar_path(platform: str, root: Optional[str] = None) -> str:
    """calibration.<platform>.json next to DEFAULT_PATH.  Single owner of
    the per-platform sidecar naming: calibrate() writes it, and both
    SessionConfig.load_calibrated and bench._ensure_calibration read it —
    three sites that must never drift apart."""
    return os.path.join(
        root if root is not None else _REPO_ROOT,
        "calibration.%s.json" % platform,
    )


def _timeit_synced(fn, reps: int = 3) -> float:
    """Median wall seconds of fn(salt) where fn must RETURN A SCALAR jax
    array and the timer fetches its 4 bytes to the host each rep.

    Two hazards this exists for: (a) a `block_until_ready` that returns
    before the work is done reads as a physically impossible bandwidth,
    so completion is proven by a device_get, and (b) a client may serve a
    repeated IDENTICAL dispatch from a cache, so every rep perturbs the
    input with a fresh `salt` argument.  The scalar
    return keeps the D2H leg at 4 bytes so the measurement is not polluted
    by result-transfer time."""
    import numpy as _np

    fn(0)  # warmup / compile (salt is a traced argument: no recompile)
    ts = []
    for i in range(reps):
        t0 = time.perf_counter()
        _np.asarray(fn(i + 1))
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2]


def _slope_us_per_row(
    fn,
    rows_hi: int,
    rows_lo: int,
    reps: int = 3,
    t_rtt: float = 0.0,
    floor: float = 1e-6,
) -> float:
    """Per-row cost in us from the SLOPE between two input sizes.

    fn(n, salt) -> scalar jax array, running the kernel over the first `n`
    rows.  Wall time at each size includes the backend's fixed dispatch +
    sync overhead, which can be larger than a kernel's entire device
    time; the slope cancels it, which is the only honest way to extract
    per-row constants through such a floor.

    An INVERTED slope (t_hi <= t_lo: the size delta sat below timer
    jitter, or the kernel pads both sizes to one internal capacity rung)
    must not persist as "this kernel is free" — that is the silent-
    miscalibration class this module exists to kill.  Below the
    plausibility `floor` (default 1e-6 us/row: a million rows per wall-us
    exceeds any single-chip memory system; callers may raise it to a
    kernel-specific bound like "sorting cannot beat a quarter-scatter")
    the single-point estimate with the measured round-trip subtracted is
    used instead."""
    t_hi = _timeit_synced(lambda s: fn(rows_hi, s), reps=reps)
    t_lo = _timeit_synced(lambda s: fn(rows_lo, s), reps=reps)
    return _slope_or_fallback(t_hi, t_lo, rows_hi, rows_lo, t_rtt, floor)


def _slope_or_fallback(
    t_hi: float,
    t_lo: float,
    n_hi: int,
    n_lo: int,
    t_rtt: float,
    floor: float = 1e-6,
) -> float:
    """The shared inverted-slope guard (see _slope_us_per_row): per-unit
    cost from the slope when plausible, else single-point minus the
    measured round-trip.  One owner so the floor and fallback formula
    cannot silently diverge between call sites."""
    slope = (t_hi - t_lo) * 1e6 / max(n_hi - n_lo, 1)
    if slope < floor:
        slope = max((t_hi - t_rtt) * 1e6 / n_hi, floor)
    return slope


def _clamp_bandwidth(bytes_per_s: float) -> float:
    """Keep a measured bandwidth inside physical reality: no link or
    memory system this code can meet moves more than 2 TB/s, and anything
    under 1 MB/s means the measurement (not the link) failed.  An
    out-of-range value would otherwise be persisted and silently load as
    'transfers are free' (or 'impossible') in every later session."""
    return min(max(bytes_per_s, 1e6), 2e12)


def measure_mesh() -> Dict[str, float]:
    """The two constants only a mesh can give, over every device JAX
    shows (real chips or a CPU-forced mesh; more than one):
    `collective_bytes_per_us` from a psum of 64 MiB of f32 merge state
    less the same program without the bytes, and `cost_dispatch_us` from
    a tiny end-to-end SPMD aggregate.  `calibrate()` ends with it; alone
    (`python -m spark_druid_olap_tpu.plan.calibrate mesh`) it is the
    probe of a mesh deployment, seconds instead of the whole sweep."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..catalog.segment import ROW_PAD
    from ..parallel.mesh import DATA_AXIS, make_mesh

    rng = np.random.default_rng(0)
    n_dev = len(jax.devices())
    mesh = make_mesh(n_data=n_dev, n_groups=1)
    # 64 MiB of f32 merge state a device: the allreduce then takes
    # milliseconds.  At the 1 MiB this probe began with, the bytes' time
    # (~40 us) lay under the host clock's noise around a 2.4 ms dispatch:
    # five readings on four v5e chips ran from 6.5e3 to 1.6e7 bytes/us
    state_g, state_m = 1 << 18, 64
    local = jnp.asarray(
        rng.random((n_dev * state_g, state_m)).astype(np.float32)
    )
    sharded = jax.device_put(local, NamedSharding(mesh, P(DATA_AXIS)))

    # salt rides INSIDE the sharded dispatch (x + salt before the
    # collective): a repeated byte-identical program+input pair is
    # exactly what a remote dispatch cache would serve without
    # executing — hazard (b) of _timeit_synced
    @jax.jit
    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(DATA_AXIS), P()),
        out_specs=P(),
        check_vma=False,
    )
    def allreduce(x, salt):
        return jnp.sum(jax.lax.psum(x + salt, DATA_AXIS))

    @jax.jit
    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(DATA_AXIS), P()),
        out_specs=P(),
        check_vma=False,
    )
    def no_comm(x, salt):
        # the baseline's tiny psum carries the SALT (not a foldable
        # constant) so it survives compilation: it charges the
        # collective's fixed launch latency to the baseline, leaving
        # t_ar - t_base as pure bytes-moved time
        return jnp.sum(jax.lax.psum(salt, DATA_AXIS)) + jnp.sum(x + salt)

    t_ar = _timeit_synced(
        lambda s: allreduce(sharded, jnp.full((1,), s, jnp.float32)), reps=5
    )
    t_base = _timeit_synced(
        lambda s: no_comm(sharded, jnp.full((1,), s, jnp.float32)), reps=5
    )
    bytes_moved = 2.0 * (n_dev - 1) / n_dev * state_g * state_m * 4
    t_comm = max(t_ar - t_base, 1e-7)
    out = {"collective_bytes_per_us": bytes_moved / (t_comm * 1e6)}

    # dispatch overhead: end-to-end tiny SPMD aggregate incl. host gather
    tiny_rows = ROW_PAD * n_dev
    tgid = jax.device_put(
        np.zeros(tiny_rows, np.int32), NamedSharding(mesh, P(DATA_AXIS))
    )
    tsv = jax.device_put(
        np.ones((tiny_rows, 1), np.float32),
        NamedSharding(mesh, P(DATA_AXIS)),
    )

    @jax.jit
    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(DATA_AXIS), P(DATA_AXIS), P()),
        out_specs=P(),
        check_vma=False,
    )
    def tiny_agg(gid, v, salt):
        return jnp.sum(
            jax.lax.psum(
                jax.ops.segment_sum(v + salt, gid, num_segments=8),
                DATA_AXIS,
            )
        )

    t_tiny = _timeit_synced(
        lambda s: tiny_agg(tgid, tsv, jnp.full((1, 1), s, jnp.float32))
    )
    out["cost_dispatch_us"] = t_tiny * 1e6
    return out


def calibrate(
    rows: int = 1 << 23,
    groups: int = 1024,
    save_path: Optional[str] = DEFAULT_PATH,
    budget_s: Optional[float] = None,
) -> Dict[str, float]:
    """`budget_s` caps wall time (every step pays a compile).  When the
    deadline passes, remaining steps are
    skipped, the file is marked `"partial": true`, and unmeasured constants
    stay at their platform-profile defaults (cost_per_row_compact falls
    back to the scatter floor so the schema check still sees it)."""
    import jax
    import jax.numpy as jnp

    deadline = (
        time.perf_counter() + budget_s if budget_s is not None else None
    )

    def over() -> bool:
        return deadline is not None and time.perf_counter() > deadline

    from ..catalog.segment import ROW_PAD
    from ..ops.groupby import dense_partial_aggregate

    rng = np.random.default_rng(0)
    half = rows // 2
    gid = jnp.asarray(rng.integers(0, groups, size=rows).astype(np.int32))
    mask = jnp.ones(rows, jnp.bool_)
    sv = jnp.asarray(rng.random((rows, 2)).astype(np.float32))
    mmv = jnp.zeros((rows, 0), jnp.float32)
    mmm = jnp.zeros((rows, 0), jnp.bool_)

    def _scalar(out):
        # reduce any kernel output pytree to one f32 on DEVICE so the
        # timing sync fetches 4 bytes, not the whole state
        leaves = [
            l.astype(jnp.float32).sum()
            for l in jax.tree_util.tree_leaves(out)
            if hasattr(l, "dtype")
        ]
        return functools.reduce(jnp.add, leaves)

    # measured round-trip of a near-empty dispatch: the fixed overhead every
    # query pays once.
    # Doubles as the single-device cost_dispatch_us; a multi-device sweep
    # below overwrites it with the SPMD-measured value.
    tiny = jnp.ones((64,), jnp.float32)

    @jax.jit
    def _trivial(x, salt):
        return jnp.sum(x) + salt

    t_rtt = _timeit_synced(lambda s: _trivial(tiny, jnp.float32(s)))
    dispatch_overhead_us = t_rtt * 1e6

    # every measured kernel takes its arrays as ARGUMENTS — a closure-
    # captured array is an XLA constant, which (a) invites the compiler to
    # fold the whole measurement away at compile time (observed with the
    # bandwidth loop: a 400 s CPU compile measuring nothing) and (b)
    # embeds megabytes of data in every program a remote-compile backend
    # must ship.  Slices for the low size are taken ONCE, outside timing.
    gid_lo, mask_lo, sv_lo = gid[:half], mask[:half], sv[:half]
    mmv_lo, mmm_lo = mmv[:half], mmm[:half]

    # dense one-hot kernel: us / row / 128-tile, from the two-size slope.
    # G=256 (2 tiles) keeps the measurement cheap on backends where dense
    # is slow (CPU: ~1 us/row/tile); the constant is per-tile, so the
    # planner scales it to any G.
    g_dense = 256
    gid_d = jnp.asarray(rng.integers(0, g_dense, size=rows).astype(np.int32))
    gid_d_lo = gid_d[:half]
    dense_fn = functools.partial(
        dense_partial_aggregate,
        num_groups=g_dense,
        block_rows=min(rows, 1 << 15),
        num_min=0,
        num_max=0,
    )

    @jax.jit
    def dense_k(g, mk, v, mv, mm, salt):
        return _scalar(dense_fn(g, mk, v + salt, mv, mm))

    def dense_at(n, salt):
        if n == rows:
            return dense_k(gid_d, mask, sv, mmv, mmm, jnp.float32(salt))
        return dense_k(
            gid_d_lo, mask_lo, sv_lo, mmv_lo, mmm_lo, jnp.float32(salt)
        )

    tiles = max(1, -(-g_dense // 128))
    cost_per_row_dense = _slope_us_per_row(
        dense_at, rows, half, t_rtt=t_rtt
    ) / tiles

    # scatter kernel: us/row at the base domain, plus the per-group state
    # cost separated from the wide domain's INTERCEPT difference (fixed
    # overheads cancel between the two domains; per-row cost is the slope)
    @functools.partial(jax.jit, static_argnames=("n_seg",))
    def scatter_k(g, v, salt, n_seg):
        return _scalar(
            jax.ops.segment_sum(v + salt, g, num_segments=n_seg)
        )

    t_sc_hi = _timeit_synced(
        lambda s: scatter_k(gid, sv, jnp.float32(s), n_seg=groups)
    )
    t_sc_lo = _timeit_synced(
        lambda s: scatter_k(gid_lo, sv_lo, jnp.float32(s), n_seg=groups)
    )
    cost_per_row_scatter = _slope_or_fallback(
        t_sc_hi, t_sc_lo, rows, half, t_rtt
    )

    wide = 1 << 20
    gid_w = jnp.asarray(rng.integers(0, wide, size=rows).astype(np.int32))
    gid_w_lo = gid_w[:half]

    cost_per_group_state = None
    cost_per_row_scatter_hi = None
    if not over():
        t_w_hi = _timeit_synced(
            lambda s: scatter_k(gid_w, sv, jnp.float32(s), n_seg=wide)
        )
        t_w_lo = _timeit_synced(
            lambda s: scatter_k(gid_w_lo, sv_lo, jnp.float32(s), n_seg=wide)
        )
        # floor: scatter at a WIDER domain can never be cheaper per row
        cost_per_row_scatter_hi = _slope_or_fallback(
            t_w_hi, t_w_lo, rows, half, t_rtt, floor=cost_per_row_scatter
        )
        # intercepts (t minus the per-row part) isolate per-domain fixed
        # work; their difference across the two domains is the per-group
        # state cost, with the backend's dispatch overhead cancelled
        icept_wide = t_w_hi * 1e6 - rows * cost_per_row_scatter_hi
        icept_lo = t_sc_hi * 1e6 - rows * cost_per_row_scatter
        cost_per_group_state = max(
            (icept_wide - icept_lo) / max(wide - groups, 1), 0.0
        )

    # sort-compaction (sparse) path: us/row on the same wide domain
    from ..ops.sparse_groupby import sparse_partial_aggregate

    sp = functools.partial(
        sparse_partial_aggregate,
        num_groups=wide,
        num_min=0,
        num_max=0,
        inner_strategy="segment",
    )
    try:
        if over():
            raise TimeoutError

        @jax.jit
        def sparse_k(g, mk, v, mv, mm, salt):
            return _scalar(sp(g, mk, v + salt, mv, mm))

        def sparse_at_n(n, salt):
            if n == rows:
                return sparse_k(gid_w, mask, sv, mmv, mmm, jnp.float32(salt))
            return sparse_k(
                gid_w_lo, mask_lo, sv_lo, mmv_lo, mmm_lo, jnp.float32(salt)
            )

        # the sparse kernel pads its sort to a capacity RUNG, so two probe
        # sizes can land on the SAME rung and their slope collapses to
        # noise (the first slope-methodology TPU sweep measured 1e-9
        # us/row — "sorting is free" — and would have routed every query
        # to sparse).  Floor: a full sort cannot plausibly beat a
        # quarter-scatter pass over the same rows.
        cost_per_row_sparse = _slope_us_per_row(
            sparse_at_n, rows, half, t_rtt=t_rtt,
            floor=cost_per_row_scatter / 4,
        )
    except Exception:
        cost_per_row_sparse = None  # declined (overflow etc.): keep default

    # filter-compaction pass measured DIRECTLY on compact_rows (cumsum +
    # searchsorted + gathers): the round-3 by-subtraction estimate came
    # out ~3x low (it credited the tier-1 sort with time the cumsum
    # actually spent), which routed SF10 q3_2 onto a sparse plan that a
    # measured scatter beat 539 ms to 763 ms.  FLOOR at the scatter
    # per-row cost: compaction reads at least as much as a scatter pass.
    cost_per_row_compact = None
    if not over():
        from ..ops.sparse_groupby import compact_rows

        sel = 0.01
        mask_sel = jnp.asarray(rng.random(rows) < sel)
        mask_sel_lo = mask_sel[:half]
        cap = max(4096, int(rows * sel * 2))
        fc = functools.partial(compact_rows, capacity=cap)
        try:
            @jax.jit
            def compact_k(g, mk, v, mv, mm, salt):
                return _scalar(fc(g, mk, v + salt, mv, mm))

            def compact_at_n(n, salt):
                if n == rows:
                    return compact_k(
                        gid_w, mask_sel, sv, mmv, mmm, jnp.float32(salt)
                    )
                return compact_k(
                    gid_w_lo, mask_sel_lo, sv_lo, mmv_lo, mmm_lo,
                    jnp.float32(salt),
                )

            cost_per_row_compact = max(
                _slope_us_per_row(compact_at_n, rows, half, t_rtt=t_rtt),
                cost_per_row_scatter,
            )
        except Exception:
            pass

    # measured streaming bandwidth: read passes over 64 MiB vs 16 MiB f32
    # arrays (a reduction — the memory-bound shape every scan kernel bottoms
    # out at), slope in bytes so the dispatch floor cancels.  This is the
    # ROOFLINE DENOMINATOR for QueryMetrics.bytes_scanned/s; "achieved",
    # not a datasheet number.
    big = jnp.asarray(rng.random(1 << 24).astype(np.float32))

    # K chained passes amplify the device-side scan until it clears the
    # dispatch floor's jitter (one 64 MiB pass is ~80 us at HBM rate —
    # invisible under a dispatch floor that wobbles; K=64 puts
    # ~5 ms of device work behind the slope).  The accumulator feeds back
    # through jnp.abs so XLA cannot factor the reduction out of the loop;
    # abs is one flop/element on a bandwidth-bound pass.
    K = 64
    stream_bytes_per_s = None
    if not over():
        # `big` must arrive as an ARGUMENT: a closure-captured array is an
        # XLA constant, and the compiler constant-folds the whole K-pass
        # loop at compile time (observed: a 400 s CPU compile producing a
        # measurement of nothing)
        @jax.jit
        def stream_k(x, salt):
            def body(_, acc):
                return acc + jnp.sum(jnp.abs(x - acc * 1e-30))

            return jax.lax.fori_loop(0, K, body, jnp.float32(salt))

        big_lo = big[: 1 << 22]
        t_bw_hi = _timeit_synced(lambda s: stream_k(big, s), reps=5)
        t_bw_lo = _timeit_synced(lambda s: stream_k(big_lo, s), reps=5)
        # bandwidths invert under jitter exactly like per-row slopes (a
        # clamped 2 TB/s 'free transfers' file was observed live in
        # review); fall back to single-point minus the measured round-trip
        stream_bytes_per_s = _clamp_bandwidth(
            K * ((1 << 24) - (1 << 22)) * 4
            / max(t_bw_hi - t_bw_lo, 1e-9)
        )
        if stream_bytes_per_s >= 2e12:
            stream_bytes_per_s = _clamp_bandwidth(
                K * (1 << 24) * 4 / max(t_bw_hi - t_rtt, 1e-9)
            )

    # host->device transfer bandwidth, slope over 64 MiB vs 16 MiB puts
    # (each synced by a 4-byte reduction fetch; a fresh salted host array
    # per rep defeats any client-side transfer cache) — the constant that
    # prices device ASSIST h2d and streaming-ingest chunk transfer.
    h2d_bytes_per_s = None
    if not over():
        h2d_host = rng.random(1 << 24).astype(np.float32)

        @jax.jit
        def _touch(x):
            return jnp.sum(x)

        def h2d_at(n, salt):
            h2d_host[0] = salt
            return _touch(jax.device_put(h2d_host[:n]))

        t_h2d_hi = _timeit_synced(lambda s: h2d_at(1 << 24, s))
        t_h2d_lo = _timeit_synced(lambda s: h2d_at(1 << 22, s))
        h2d_bytes_per_s = _clamp_bandwidth(
            ((1 << 24) - (1 << 22)) * 4 / max(t_h2d_hi - t_h2d_lo, 1e-9)
        )
        if h2d_bytes_per_s >= 2e12:  # inverted slope: single-point fallback
            h2d_bytes_per_s = _clamp_bandwidth(
                (1 << 24) * 4 / max(t_h2d_hi - t_rtt, 1e-9)
            )

    out = {
        "cost_per_row_dense": cost_per_row_dense,
        "cost_per_row_scatter": cost_per_row_scatter,
        "stream_bytes_per_s": stream_bytes_per_s,
        "h2d_bytes_per_s": h2d_bytes_per_s,
        "cost_dispatch_us": dispatch_overhead_us,
        "rows": rows,
        "groups": groups,
        "device": str(jax.devices()[0]),
        "platform": jax.devices()[0].platform,
        "n_devices": len(jax.devices()),
        # self-description (VERDICT r4 #8): every constant is the MEDIAN
        # of timed reps (one warmup compile excluded), sync-proven by a
        # 4-byte device_get and — for per-row constants — taken from a
        # two-size SLOPE so the backend's fixed dispatch overhead cancels
        # (methodology: _timeit_synced/_slope_us_per_row).  Kernel
        # constants use 3 reps per size; stream_bytes_per_s uses 5 (its
        # slope sits closest to the dispatch-jitter floor).  budget_s is
        # the wall cap the sweep ran under, None = uncapped
        "samples_per_constant": 3,
        "samples_stream_bw": 5,
        "budget_s": budget_s,
    }
    if cost_per_group_state is not None:
        out["cost_per_group_state"] = cost_per_group_state
    if cost_per_row_scatter_hi is not None:
        out["cost_per_row_scatter_hi"] = cost_per_row_scatter_hi
        out["scatter_lo_groups"] = groups
        out["scatter_hi_groups"] = wide
    if cost_per_row_sparse is not None:
        out["cost_per_row_sparse"] = cost_per_row_sparse
    # always written so consumers can distinguish "measured" from "probe
    # declined" (None) — bench's schema check keys on presence, and a
    # missing key would force recalibration on every run.  An unmeasured
    # (budget-skipped) compact pass reads at least as much as a scatter
    # pass, so the scatter cost is its honest floor
    if cost_per_row_compact is None and over():
        cost_per_row_compact = cost_per_row_scatter
    out["cost_per_row_compact"] = cost_per_row_compact
    # ALWAYS present (VERDICT r4 weak #5: the marker silently vanished in
    # round 4 when a full sweep completed): partial=True means the budget
    # clipped the sweep and unmeasured keys carry profile defaults
    out["partial"] = bool(over())

    # mesh measurements need >1 device (real chips or a CPU-forced mesh)
    if len(jax.devices()) > 1 and not over():
        out.update(measure_mesh())

    if save_path:
        with open(save_path, "w") as f:
            json.dump(out, f, indent=1)
        # per-platform sidecar: CPU and TPU runs alternate on this host and
        # each overwrites the primary file; SessionConfig.load_calibrated
        # falls back to calibration.<platform>.json on a device mismatch so
        # measured constants survive runs on the other backend
        try:
            plat_path = sidecar_path(
                out["platform"], root=os.path.dirname(save_path)
            )
            with open(plat_path, "w") as f:
                json.dump(out, f, indent=1)
        except OSError:
            pass
    return out


if __name__ == "__main__":
    import sys

    mesh_only = sys.argv[1:] == ["mesh"]
    print(json.dumps(measure_mesh() if mesh_only else calibrate()))
