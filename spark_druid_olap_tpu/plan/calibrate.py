"""Measure cost-model constants on the live backend.

Reference parity: the reference's `DruidQueryCostModel` ships tunable cost
constants via SQLConf with documented defaults the operator is expected to
re-tune per deployment (SURVEY.md §2 cost-model row `[U]`).  Round 1 shipped
guessed constants; this module replaces guessing with measurement.  What is
timed is what the chooser (`plan/cost.py`) names, called the way the engines
call it, `ops.groupby.partial_aggregate(gid[R], mask[R], values[R, 2],
strategy=<kernel>)` over whole segments' worth of rows with a filter mask
(the Pallas kernel in the form the lowering hands it: a count and one
unmasked value row):

* `cost_per_row_dense` (us per row per 128-wide group tile): the kernel
  `concrete_kernel("dense", g)` names on THIS backend — the compiled Pallas
  kernel on a TPU, the XLA one-hot scan elsewhere — at G = 128 and G = 896
  (1 and 7 tiles), the model's `rows x c x tiles` fitted through both.  A
  price of the dense class is a price of that kernel and of no other: the
  model offers the class only where `concrete_kernel` launches it
  (`cost.dense_class_cap`);
* `cost_per_row_scatter` / `_hi` (us/row at 1,024 and 2^20 groups) and
  `cost_per_group_state`: `scatter_partial_aggregate` (strategy
  "segment"), masked rows written to its trash slot as phase B's are;
* `cost_per_row_sparse`, `cost_per_row_compact`: the sort-compaction tier's
  two passes;
* `stream_bytes_per_s`, `h2d_bytes_per_s`: a streamed reduction, a put;
* psum of a [G, M] state over the mesh   -> `collective_bytes_per_us`,
* a tiny end-to-end SPMD dispatch        -> `cost_dispatch_us`

— and writes `calibration.json` at the repo root and its per-platform
sidecar, which `SessionConfig.load_calibrated()` reads.  A key the sweep did
not write (the hand-kept `vmem_budget_bytes`, a mesh reading taken on a
four-chip host, a constant a clipped sweep never reached) keeps the value
the file held for the same device.  Run on the TPU to get real-chip
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

# group domains the dense class's kernel is timed at: 1 and 7 lane tiles
# (7: SSB q4_3's compacted domain of 800, the widest phase B the cells run)
DENSE_PROBE_GROUPS = (128, 896)


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
    out["collective_measured_on"] = "%d x %s, plan/calibrate.py measure_mesh" % (
        n_dev, jax.devices()[0].device_kind,
    )
    return out


def _kept_keys(paths, out: Dict) -> Dict:
    """What the file being replaced holds beyond this sweep's `out`: the
    keys of the first of `paths` that reads as a calibration of the same
    device.  A re-run then keeps what no single sweep measures (PR 22 had
    to put `vmem_budget_bytes` back by hand after one)."""
    for path in paths:
        try:
            with open(path) as f:
                old = json.load(f)
        except (OSError, ValueError):
            continue
        if isinstance(old, dict) and old.get("device") == out["device"]:
            return {k: v for k, v in old.items() if k not in out}
    return {}


def calibrate(
    rows: int = 1 << 23,
    groups: int = 1024,
    save_path: Optional[str] = DEFAULT_PATH,
    budget_s: Optional[float] = None,
) -> Dict[str, float]:
    """`budget_s` caps wall time (every step pays a compile).  When the
    deadline passes, remaining steps are skipped, the file is marked
    `"partial": true`, and an unmeasured constant keeps what the file held
    for this device, else its platform-profile default (cost_per_row_compact
    falls back to the scatter floor so the schema check still sees it)."""
    import jax
    import jax.numpy as jnp

    deadline = (
        time.perf_counter() + budget_s if budget_s is not None else None
    )

    def over() -> bool:
        return deadline is not None and time.perf_counter() > deadline

    from ..ops.groupby import partial_aggregate
    from .cost import _g_tiles, concrete_kernel

    rng = np.random.default_rng(0)
    half = rows // 2
    gid = jnp.asarray(rng.integers(0, groups, size=rows).astype(np.int32))
    mask = jnp.ones(rows, jnp.bool_)
    # the filter mask the dense and scatter kernels are timed under: half
    # the rows are the scatter's trash-slot writes, the kernel's -1 ids
    keep = jnp.asarray(rng.random(rows) < 0.5)
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
    mask_lo, keep_lo, sv_lo = mask[:half], keep[:half], sv[:half]
    mmv_lo, mmm_lo = mmv[:half], mmm[:half]

    @functools.partial(jax.jit, static_argnames=("kernel", "n_groups"))
    def agg_k(g, mk, v, mv, mm, salt, kernel, n_groups):
        # the engines' call (exec/engine._segment_partials, the mesh's
        # dense-state shard_fn): the dispatcher, handed the kernel's name
        v = v + salt
        if kernel == "pallas":
            # the form row_arrays hands that kernel: a count and one
            # unmasked value row, as every cell's queries are
            v = (None, v[:, 0])
        return _scalar(partial_aggregate(
            g, mk, v, mv, mm, num_groups=n_groups,
            num_min=0, num_max=0, strategy=kernel,
        ))

    def agg_times(kernel, n_groups, g_hi):
        """Median seconds of `kernel` over `rows` and over `half` rows."""
        g_lo = g_hi[:half]
        return (
            _timeit_synced(lambda s: agg_k(
                g_hi, keep, sv, mmv, mmm, jnp.float32(s),
                kernel=kernel, n_groups=n_groups,
            )),
            _timeit_synced(lambda s: agg_k(
                g_lo, keep_lo, sv_lo, mmv_lo, mmm_lo, jnp.float32(s),
                kernel=kernel, n_groups=n_groups,
            )),
        )

    # dense class: us / row / 128-tile of the kernel the class RUNS as on
    # this backend, each domain's cost from the two-size slope.  Two tile
    # counts, so the per-tile constant is a fit of the model's own form
    # (rows x c x tiles, least squares through the origin: the wide domain
    # weighs 49 x the narrow one, as it does in every decision the constant
    # takes part in) and the file shows how far from linear the kernel is
    dense_us_per_row = {}
    for g_dense in DENSE_PROBE_GROUPS:
        if dense_us_per_row and over():
            break
        dense_kernel = concrete_kernel("dense", g_dense)
        t_hi, t_lo = agg_times(dense_kernel, g_dense, gid % g_dense)
        dense_us_per_row[g_dense] = _slope_or_fallback(
            t_hi, t_lo, rows, half, t_rtt
        )
    cost_per_row_dense = sum(
        c * _g_tiles(g) for g, c in dense_us_per_row.items()
    ) / sum(_g_tiles(g) ** 2 for g in dense_us_per_row)

    # scatter kernel: us/row at the base domain, plus the per-group state
    # cost separated from the wide domain's INTERCEPT difference (fixed
    # overheads cancel between the two domains; per-row cost is the slope)
    t_sc_hi, t_sc_lo = agg_times("segment", groups, gid)
    cost_per_row_scatter = _slope_or_fallback(
        t_sc_hi, t_sc_lo, rows, half, t_rtt
    )

    wide = 1 << 20
    gid_w = jnp.asarray(rng.integers(0, wide, size=rows).astype(np.int32))
    gid_w_lo = gid_w[:half]

    cost_per_group_state = None
    cost_per_row_scatter_hi = None
    if not over():
        t_w_hi, t_w_lo = agg_times("segment", wide, gid_w)
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
        # what was timed for it, and each probed domain's own us/row
        "dense_kernel": dense_kernel,
        "dense_us_per_row": {str(g): c for g, c in dense_us_per_row.items()},
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
        # per-platform sidecar: CPU and TPU runs alternate on this host and
        # each overwrites the primary file; SessionConfig.load_calibrated
        # falls back to calibration.<platform>.json on a device mismatch so
        # measured constants survive runs on the other backend
        plat_path = sidecar_path(
            out["platform"], root=os.path.dirname(save_path)
        )
        out = {**_kept_keys((plat_path, save_path), out), **out}
        with open(save_path, "w") as f:
            json.dump(out, f, indent=1)
        try:
            with open(plat_path, "w") as f:
                json.dump(out, f, indent=1)
        except OSError:
            pass
    return out


if __name__ == "__main__":
    import sys

    mesh_only = sys.argv[1:] == ["mesh"]
    print(json.dumps(measure_mesh() if mesh_only else calibrate()))
