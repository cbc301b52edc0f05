"""One-dispatch arena execution (ISSUE 14 tentpole).

Layers under test:

1. Oracle parity: arena-on results are BYTE-identical to the loop path
   (arena-off) across the dense, fused, partial-drain, and delta
   result-cache flows — the scan-carry fold replays the loop path's
   select/fold tree op-for-op, so f32 sums cannot reassociate.
2. Dispatch collapse: the cost receipt's `dispatch_count` drops from
   O(covered batches) to O(1) with the arena on, and the arena_build
   bucket appears alongside.
3. Coverage decisions: `plan_for` covers only a uniform-shape prefix of
   whole batches within the byte-budget fraction, declines scopes with
   fewer than two coverable batches, and sketch aggregations bypass the
   arena entirely.
4. Lifecycle edges: retiring a uid drops every arena slice whose stack
   contains it; the per-query opt-out and the session flag both route
   back to the loop path; donated fold-state buffers are requested
   exactly when the backend supports them.
5. One device call (ISSUE 33): a warm single-chunk request makes its zero
   carry and its final flush inside the scanned program, looks its batch
   -start flags up beside the stack, and is byte-identical to the chunked
   form that threads the carry.
"""

import numpy as np
import pandas as pd
import pytest

import spark_druid_olap_tpu as sd
from spark_druid_olap_tpu.catalog.segment import build_datasource
from spark_druid_olap_tpu.config import SessionConfig
from spark_druid_olap_tpu.exec import arena
from spark_druid_olap_tpu.exec.engine import Engine
from spark_druid_olap_tpu.models.aggregations import (
    Count,
    DoubleMax,
    DoubleMin,
    DoubleSum,
    ThetaSketch,
)
from spark_druid_olap_tpu.models.dimensions import DimensionSpec
from spark_druid_olap_tpu.models.filters import Selector
from spark_druid_olap_tpu.models.query import GroupByQuery
from spark_druid_olap_tpu.resilience import (
    InjectedDeadline,
    deadline_scope,
    injector,
    partial_scope,
)


@pytest.fixture(autouse=True)
def _clean_injector():
    injector().disarm()
    yield
    injector().disarm()


def _ctx(**overrides):
    cfg = SessionConfig.load_calibrated()
    cfg.result_cache_entries = 0
    cfg.retry_backoff_ms = 1.0
    cfg.prefer_distributed = False
    for k, v in overrides.items():
        setattr(cfg, k, v)
    return sd.TPUOlapContext(cfg)


def _flat_ds(n=8_192, seg_rows=512, name="ar", card=4, seed=3):
    """Multi-segment datasource: small segments so the CPU unroll cap
    yields MANY dispatch batches — the loop the arena collapses."""
    rng = np.random.default_rng(seed)
    cols = {
        "d": np.array(
            [f"k{i}" for i in rng.integers(0, card, size=n)], dtype=object
        ),
        "v": rng.random(n).astype(np.float32),
        "t": (np.arange(n) * 1_000).astype(np.int64),
    }
    ds = build_datasource(
        name, cols, dimension_cols=["d"], metric_cols=["v"],
        time_col="t", rows_per_segment=seg_rows,
    )
    return ds, cols


def _gb(ds_name="ar", filt=None, intervals=(), aggs=None):
    return GroupByQuery(
        datasource=ds_name,
        dimensions=(DimensionSpec("d"),),
        aggregations=tuple(
            aggs
            if aggs is not None
            else (
                Count("n"), DoubleSum("s", "v"),
                DoubleMin("mn", "v"), DoubleMax("mx", "v"),
            )
        ),
        filter=filt,
        intervals=tuple(intervals),
    )


def _exact_equal(a, b):
    pd.testing.assert_frame_equal(
        a.reset_index(drop=True), b.reset_index(drop=True), check_exact=True
    )


def _arena_keys(eng):
    return [k for k in eng._device_cache if arena.is_arena_key(k)]


def _recording_programs(monkeypatch):
    """Record `(carry, static flags)` of every arena program call."""
    calls = []
    real_build = arena.build_arena_program

    def build(*a, **kw):
        program = real_build(*a, **kw)

        def call(carry, *xs, **flags):
            calls.append((carry, flags))
            return program(carry, *xs, **flags)

        return call

    monkeypatch.setattr(arena, "build_arena_program", build)
    return calls


def _forms(calls):
    return [(carry is None, flags) for carry, flags in calls]


# ---------------------------------------------------------------------------
# 1. oracle parity: arena-on == loop path, byte-identical
# ---------------------------------------------------------------------------


def test_dense_parity_arena_on_vs_off():
    ds, _ = _flat_ds()
    q = _gb()
    on = Engine()
    off = Engine()
    with arena.arena_disabled():
        want = off.execute(q, ds)
    got = on.execute(q, ds)
    _exact_equal(got, want)
    assert _arena_keys(on), "arena never engaged"
    assert not _arena_keys(off)
    # warm repeat (stacked buffers fully resident) stays identical
    _exact_equal(on.execute(q, ds), want)


def test_filtered_and_interval_scopes_stay_identical():
    ds, _ = _flat_ds(name="ar")
    on = Engine()
    off = Engine()
    for q in (
        _gb("ar", filt=Selector("d", "k1")),
        _gb("ar", intervals=[(0, 4_096_000)]),
    ):
        with arena.arena_disabled():
            want = off.execute(q, ds)
        _exact_equal(on.execute(q, ds), want)


def test_fused_parity_arena_on_vs_off():
    ds, _ = _flat_ds(name="ar")
    queries = [
        _gb("ar"),
        _gb("ar", filt=Selector("d", "k1")),
        _gb("ar"),
    ]
    on = Engine()
    off = Engine()
    with arena.arena_disabled():
        want = off.execute_fused(queries, ds)
    got = on.execute_fused(queries, ds)
    for (df_on, _, _), (df_off, _, _) in zip(got, want):
        _exact_equal(df_on, df_off)
    # fused members must also equal their own serial executions
    for (df_on, _, _), q in zip(got, queries):
        with arena.arena_disabled():
            _exact_equal(df_on, off.execute(q, ds))
    assert _arena_keys(on), "fused arena never engaged"


def test_fused_mixed_interval_scopes_share_one_arena():
    """Members with different scopes fuse into ONE arena program: the
    membership matrix (scan data, not trace constants) gates each
    member's fold."""
    ds, _ = _flat_ds(name="ar")
    queries = [
        _gb("ar"),
        _gb("ar", intervals=[(0, 4_096_000)]),
    ]
    on = Engine()
    off = Engine()
    got = on.execute_fused(queries, ds)
    with arena.arena_disabled():
        for (df_on, _, _), q in zip(got, queries):
            _exact_equal(df_on, off.execute(q, ds))


def test_partial_drain_parity_arena_on_vs_off():
    """An injected deadline at the shared `engine.segment_loop` site
    truncates the arena at the SAME batch boundary as the loop path:
    identical coverage, byte-identical partial frames."""
    def drain(disabled):
        ctx = _ctx()
        n = 20_000
        ctx.register_table(
            "t",
            {
                "d": np.array(["a", "b", "c", "d"] * (n // 4), dtype=object),
                "v": np.ones(n, dtype=np.float32),
            },
            dimensions=["d"],
            metrics=["v"],
            rows_per_segment=1 << 10,
        )
        injector().arm(
            "engine.segment_loop", "error", times=1, skip=2,
            error_type=InjectedDeadline,
        )
        try:
            with deadline_scope(60_000), partial_scope(True):
                if disabled:
                    with arena.arena_disabled():
                        df = ctx.sql(
                            "SELECT d, COUNT(*) AS n, SUM(v) AS s "
                            "FROM t GROUP BY d"
                        )
                else:
                    df = ctx.sql(
                        "SELECT d, COUNT(*) AS n, SUM(v) AS s "
                        "FROM t GROUP BY d"
                    )
        finally:
            injector().disarm()
        return df

    got = drain(disabled=False)
    want = drain(disabled=True)
    assert got.attrs["partial"] is True and want.attrs["partial"] is True
    assert got.attrs["coverage"] == want.attrs["coverage"]
    assert 0 < got.attrs["coverage"] < 1.0
    _exact_equal(got, want)


def test_result_cache_delta_parity_with_arena():
    """The arena's captured fold state flows into the delta-aware result
    cache: an append serves (cached historical) ⊕ (delta partials) and
    stays byte-identical to a cold loop-path recompute."""
    def run(disabled):
        ctx = _ctx(result_cache_entries=16)
        n = 4_096
        rng = np.random.default_rng(7)
        ctx.register_table(
            "ev",
            {
                "d": np.array(
                    [f"k{i}" for i in rng.integers(0, 4, size=n)],
                    dtype=object,
                ),
                "v": rng.random(n).astype(np.float32),
                "t": (np.arange(n) * 1_000).astype(np.int64),
            },
            dimensions=["d"],
            metrics=["v"],
            time_column="t",
            rows_per_segment=512,
        )
        sqlq = "SELECT d, COUNT(*) AS n, SUM(v) AS s FROM ev GROUP BY d"

        def go():
            if disabled:
                with arena.arena_disabled():
                    return ctx.sql(sqlq)
            return ctx.sql(sqlq)

        go()
        go()
        assert ctx.last_metrics.strategy == "result-cache"
        ctx.append_rows(
            "ev",
            [
                {"d": "k1", "v": 5.0, "t": 0},
                {"d": "k2", "v": 11.0, "t": 1_000},
            ],
        )
        df = go()
        assert ctx.last_metrics.strategy == "result-cache-delta"
        return df

    got = run(disabled=False)
    want = run(disabled=True)
    _exact_equal(got, want)


def test_sketch_aggregations_decline_the_arena():
    """No exact scan-carry identity exists for sketch merges — the scope
    routes to the loop path untouched."""
    ds, _ = _flat_ds(name="ar")
    q = _gb(
        "ar",
        aggs=(Count("n"), DoubleSum("s", "v"), ThetaSketch("th", "d")),
    )
    on = Engine()
    off = Engine()
    got = on.execute(q, ds)
    with arena.arena_disabled():
        want = off.execute(q, ds)
    _exact_equal(got, want)
    assert not _arena_keys(on)


def test_sparse_strategy_routes_before_the_arena():
    rng = np.random.default_rng(11)
    n = 40_000
    cols = {
        "a": rng.integers(0, 300, size=n),
        "b": rng.integers(0, 300, size=n),
        "v": np.ones(n, np.float32),
    }
    from spark_druid_olap_tpu.catalog.segment import DimensionDict

    ds = build_datasource(
        "ar", cols, dimension_cols=["a", "b"], metric_cols=["v"],
        rows_per_segment=1 << 13,
        dicts={
            "a": DimensionDict(values=tuple(range(300))),
            "b": DimensionDict(values=tuple(range(300))),
        },
    )
    q = GroupByQuery(
        datasource="ar",
        dimensions=(DimensionSpec("a"), DimensionSpec("b")),
        aggregations=(Count("n"), DoubleSum("s", "v")),
    )
    on = Engine(strategy="sparse")
    off = Engine(strategy="sparse")
    got = on.execute(q, ds)
    with arena.arena_disabled():
        want = off.execute(q, ds)
    _exact_equal(got, want)
    assert not _arena_keys(on)


# ---------------------------------------------------------------------------
# 2. dispatch collapse: O(batches) -> O(1) in the cost receipt
# ---------------------------------------------------------------------------


def _receipt(ctx, sqlq):
    ctx.tracer.force_sample_next()
    return ctx.sql(sqlq).attrs["receipt"]


def test_dispatch_count_collapses_to_one():
    ctx = _ctx()
    rng = np.random.default_rng(3)
    n = 8_192
    ctx.register_table(
        "ar",
        {
            "d": np.array(
                [f"k{i}" for i in rng.integers(0, 4, size=n)], dtype=object
            ),
            "v": rng.random(n).astype(np.float32),
        },
        dimensions=["d"],
        metrics=["v"],
        rows_per_segment=512,
    )
    ds = ctx.catalog.get("ar")
    sqlq = "SELECT d, COUNT(*) AS n, SUM(v) AS s FROM ar GROUP BY d"
    ctx.engine.drop_residency()
    rc_on = _receipt(ctx, sqlq)
    ctx.engine.drop_residency()
    with arena.arena_disabled():
        rc_off = _receipt(ctx, sqlq)
    n_batches = len(
        list(ctx.engine._segment_batches(list(ds.segments), ["d", "v"]))
    )
    assert n_batches > 1
    assert rc_off["dispatch_count"] >= n_batches
    assert rc_on["dispatch_count"] == 1
    assert rc_on["arena_build_ms"] > 0


def test_warm_arena_receipt_shows_residency_hits():
    ctx = _ctx()
    ds, _ = _flat_ds(name="ar")
    ctx.catalog.put(ds)
    sqlq = "SELECT d, SUM(v) AS s FROM ar GROUP BY d"
    ctx.sql(sqlq)
    rc = _receipt(ctx, sqlq)
    assert rc["dispatch_count"] == 1
    assert rc["cache"]["residency"]["misses"] == 0
    assert rc["cache"]["residency"]["hits"] > 0
    assert rc["cache"]["program_cache"]["arena"]["hits"] == 1


# ---------------------------------------------------------------------------
# 3. coverage decisions (plan_for unit tests)
# ---------------------------------------------------------------------------


def test_plan_declines_single_batch_scope():
    ds, _ = _flat_ds(n=1_024, seg_rows=512, name="ar")
    eng = Engine()
    batches = list(eng._segment_batches(list(ds.segments), ["d", "v"]))
    if len(batches) >= 2:
        pytest.skip("unroll cap packed everything into one batch only")
    assert arena.plan_for(eng, batches, ["d", "v"]) is None


def test_plan_covers_uniform_prefix_only():
    """Mixed segment shapes stop coverage at the first non-uniform
    batch: stacking ragged shapes would force Rmax padding, and padded
    lanes change the fold inputs (no byte-identity)."""
    big, _ = _flat_ds(n=16_384, seg_rows=4_096, name="ar")
    small, _ = _flat_ds(n=2_048, seg_rows=512, name="ar2")
    eng = Engine()
    names = ["d", "v"]
    b_big = list(eng._segment_batches(list(big.segments), names))
    b_small = list(eng._segment_batches(list(small.segments), names))
    assert (
        big.segments[0].num_rows_padded != small.segments[0].num_rows_padded
    )
    plan = arena.plan_for(eng, b_big + b_small, names)
    assert plan is not None
    assert len(plan.batches) == len(b_big)
    assert len(plan.remainder) == len(b_small)
    # and a scope that leads with ONE uniform batch declines (<2 covered)
    assert arena.plan_for(eng, b_big[:1] + b_small, names) is None


def test_plan_respects_byte_budget_fraction():
    ds, _ = _flat_ds(name="ar")
    eng = Engine()
    names = ["d", "v"]
    batches = list(eng._segment_batches(list(ds.segments), names))
    full = arena.plan_for(eng, batches, names)
    assert full is not None and not full.remainder
    # shrink the device budget so only ~half the stack fits
    eng._device_cache.budget_bytes = int(
        full.nbytes / arena.ARENA_BUDGET_FRACTION / 2
    )
    capped = arena.plan_for(eng, batches, names)
    assert capped is not None
    assert 2 <= len(capped.batches) < len(batches)
    assert capped.remainder
    # partial coverage still folds byte-identically end to end
    q = _gb("ar")
    off = Engine()
    with arena.arena_disabled():
        want = off.execute(q, ds)
    _exact_equal(eng.execute(q, ds), want)
    assert _arena_keys(eng)


def test_session_flag_and_query_optout_disable_the_arena():
    ds, _ = _flat_ds(name="ar")
    q = _gb("ar")
    flagged = Engine()
    flagged.arena_execution = False
    flagged.execute(q, ds)
    assert not _arena_keys(flagged)
    scoped = Engine()
    with arena.arena_disabled():
        scoped.execute(q, ds)
    assert not _arena_keys(scoped)
    # the config knob wires through TPUOlapContext
    ctx = _ctx(arena_execution=False)
    assert ctx.engine.arena_execution is False
    ctx2 = _ctx()
    assert ctx2.engine.arena_execution is True


# ---------------------------------------------------------------------------
# 4. lifecycle edges: invalidation + donation
# ---------------------------------------------------------------------------


def test_retired_uid_invalidates_arena_slices():
    ds, _ = _flat_ds(name="ar")
    eng = Engine()
    q = _gb("ar")
    eng.execute(q, ds)
    keys = _arena_keys(eng)
    assert keys
    retired = {keys[0][0][1]}  # first uid inside the stacked key
    eng.evict_segments(retired)
    left = _arena_keys(eng)
    assert all(not retired.intersection(k[0][1:]) for k in left)
    assert len(left) < len(keys)
    # the next execution rebuilds against the surviving scope and still
    # matches the loop path
    off = Engine()
    with arena.arena_disabled():
        want = off.execute(q, ds)
    _exact_equal(eng.execute(q, ds), want)


def test_donation_requested_exactly_off_cpu(monkeypatch):
    """Fold-state carries are donated on accelerator backends (the scan
    rewrites them in place) and NOT on CPU, where donation is a no-op
    warning.  The recorder strips the kwarg so the underlying program
    still runs here on CPU — and stays byte-identical."""
    import jax

    calls = []
    real_jit = jax.jit

    def recording_jit(fn, **kw):
        calls.append(dict(kw))
        kw.pop("donate_argnums", None)  # CPU: donation is a no-op warning
        return real_jit(fn, **kw)

    monkeypatch.setattr(jax, "jit", recording_jit)
    monkeypatch.setattr(arena, "_donate_carry", lambda: True)
    ds, _ = _flat_ds(name="ar")
    q = _gb("ar")
    on = Engine()
    # an armed deadline chunks the scan: the form that threads a carry
    with deadline_scope(60_000):
        got = on.execute(q, ds)
    assert any(kw.get("donate_argnums") == (0,) for kw in calls)
    off = Engine()
    with arena.arena_disabled():
        _exact_equal(got, off.execute(q, ds))


def test_donated_carry_holds_no_buffer_twice(monkeypatch):
    """A TPU refuses a donated argument in which one buffer appears twice
    (`Attempt to donate the same buffer twice`, met on the first chip run
    of the arena); the CPU backend never donates, so only this sees it."""
    import types

    la = types.SimpleNamespace(
        sum_names=("s",), min_names=("mn",), max_names=()
    )
    carry = arena._member_init(types.SimpleNamespace(la=la, num_groups=8))
    assert len({id(leaf) for leaf in carry}) == len(carry) == 8
    # the chunked form's carry is a program's output from the second
    # chunk on (the first makes its own): eight buffers a member there too
    calls = _recording_programs(monkeypatch)
    ds, _ = _flat_ds(name="ar")
    with deadline_scope(60_000):
        Engine().execute(_gb("ar"), ds)
    threaded = [carry for carry, _flags in calls]
    assert threaded[0] is None and len(threaded) > 1
    for carries in threaded[1:]:
        for member in carries:
            ptrs = {leaf.unsafe_buffer_pointer() for leaf in member}
            assert len(ptrs) == len(member) == 8


def test_no_donation_on_cpu_backend(monkeypatch):
    import jax

    calls = []
    real_jit = jax.jit

    def recording_jit(fn, **kw):
        calls.append(dict(kw))
        return real_jit(fn, **kw)

    monkeypatch.setattr(jax, "jit", recording_jit)
    ds, _ = _flat_ds(name="ar")
    with deadline_scope(60_000):  # the chunked form: a carry is threaded
        Engine().execute(_gb("ar"), ds)
    if jax.default_backend() == "cpu":
        assert all("donate_argnums" not in kw for kw in calls)
    else:
        assert any(kw.get("donate_argnums") == (0,) for kw in calls)


def test_progressive_parity_arena_on_vs_off():
    """Progressive refinement keeps its per-batch fetch loop by design
    (the per-refinement fetch is the product); the arena flag must not
    change a single emission, and the final exact emission equals the
    arena's one-dispatch dense answer."""
    ds, _ = _flat_ds(name="ar")
    q = _gb("ar")
    on = Engine()
    off = Engine()
    got = list(on.execute_progressive(q, ds))
    with arena.arena_disabled():
        want = list(off.execute_progressive(q, ds))
    assert len(got) == len(want) >= 2
    for (df_on, info_on), (df_off, info_off) in zip(got, want):
        assert info_on == info_off
        _exact_equal(df_on, df_off)
    assert got[-1][1]["final"] is True
    _exact_equal(got[-1][0], on.execute(q, ds))


def test_append_then_compaction_invalidate_arena_slices():
    """Rows appended after an arena stack was built must show up in the
    next answer (the plan keys on the segment-set signature, so a
    changed scope can't hit the stale stack), and a compaction that
    retires uids drops every arena slice whose stack contains them."""
    ctx = _ctx()
    n = 4_096
    rng = np.random.default_rng(11)
    ctx.register_table(
        "ap",
        {
            "d": np.array(
                [f"k{i}" for i in rng.integers(0, 4, size=n)], dtype=object
            ),
            "v": rng.random(n).astype(np.float32),
            "t": (np.arange(n) * 1_000).astype(np.int64),
        },
        dimensions=["d"],
        metrics=["v"],
        time_column="t",
        rows_per_segment=512,
    )
    sqlq = "SELECT d, COUNT(*) AS n, SUM(v) AS s FROM ap GROUP BY d"
    eng = ctx.engine
    before = ctx.sql(sqlq)
    stale = set(_arena_keys(eng))
    assert stale, "arena never engaged"

    ctx.append_rows(
        "ap",
        [
            {"d": "k1", "v": 5.0, "t": 0},
            {"d": "k9", "v": 11.0, "t": 1_000},
        ],
    )
    got = ctx.sql(sqlq)
    with arena.arena_disabled():
        want = ctx.sql(sqlq)
    _exact_equal(got, want)
    assert not got.equals(before), "appended rows missing from answer"

    # compaction retires the delta (and any absorbed tail) uids: every
    # arena key whose stack contains a retired uid must be evicted, and
    # what survives references only live segments
    ctx.compact("ap")
    ds_now = ctx.catalog.get("ap")
    live = {s.uid for s in ds_now.segments}
    for k in _arena_keys(eng):
        assert set(k[0][1:]) <= live, f"stale arena stack survived: {k}"
    got2 = ctx.sql(sqlq)
    with arena.arena_disabled():
        want2 = ctx.sql(sqlq)
    _exact_equal(got2, want2)
    _exact_equal(got2, got)


def test_deadline_expired_before_build_skips_stack_and_falls_back():
    """A deadline that is already gone when the arena would START
    building skips the stack build entirely (no H2D for an answer that
    can't use it) and degrades to the loop path's truncation contract:
    same site, same coverage, byte-identical partial frames."""
    def drain(disabled):
        ctx = _ctx()
        n = 20_000
        ctx.register_table(
            "t",
            {
                "d": np.array(["a", "b", "c", "d"] * (n // 4), dtype=object),
                "v": np.ones(n, dtype=np.float32),
            },
            dimensions=["d"],
            metrics=["v"],
            rows_per_segment=1 << 10,
        )
        injector().arm(
            "engine.segment_loop", "error", times=1, skip=0,
            error_type=InjectedDeadline,
        )
        try:
            with deadline_scope(60_000), partial_scope(True):
                if disabled:
                    with arena.arena_disabled():
                        df = ctx.sql(
                            "SELECT d, COUNT(*) AS n, SUM(v) AS s "
                            "FROM t GROUP BY d"
                        )
                else:
                    df = ctx.sql(
                        "SELECT d, COUNT(*) AS n, SUM(v) AS s "
                        "FROM t GROUP BY d"
                    )
        finally:
            injector().disarm()
        return df, ctx.engine

    got, eng_on = drain(disabled=False)
    want, _ = drain(disabled=True)
    assert got.attrs["partial"] is True and want.attrs["partial"] is True
    assert got.attrs["coverage"] == want.attrs["coverage"]
    _exact_equal(got, want)
    # the stack build never ran: no arena slices entered the cache
    assert not _arena_keys(eng_on)


# ---------------------------------------------------------------------------
# 5. one device call: carry and flush inside the trace (ISSUE 33)
# ---------------------------------------------------------------------------


def _counting(monkeypatch, name):
    """Count calls of `arena.<name>` (trace-time calls included)."""
    calls = []
    real = getattr(arena, name)

    def counted(*a, **kw):
        calls.append(name)
        return real(*a, **kw)

    monkeypatch.setattr(arena, name, counted)
    return calls


def test_warm_request_is_one_program_call_and_nothing_eager(monkeypatch):
    """The zero carry and the final flush are the program's own: on a
    warm repeat neither is built on the host, and the one program call
    is the request's only device computation before the fetch."""
    inits = _counting(monkeypatch, "_member_init")
    flushes = _counting(monkeypatch, "finish_member")
    programs = _recording_programs(monkeypatch)
    ds, _ = _flat_ds(name="ar")
    q = _gb("ar")
    eng = Engine()
    first = eng.execute(q, ds)
    # the cold request traced the whole form: one init, one flush
    assert (len(inits), len(flushes), len(programs)) == (1, 1, 1)
    del inits[:], flushes[:], programs[:]
    _exact_equal(eng.execute(q, ds), first)
    assert not inits and not flushes
    assert _forms(programs) == [(True, {"init": True, "finish": True})]
    assert eng.last_metrics.h2d_bytes == 0


def _members(case):
    if case == "one_member":
        return [_gb("ar", aggs=(Count("n"), DoubleSum("s", "v")))]
    if case == "min_max":
        return [_gb("ar", aggs=(DoubleMin("mn", "v"), DoubleMax("mx", "v")))]
    return [  # fused members over different scopes
        _gb("ar"),
        _gb("ar", intervals=[(0, 4_096_000)]),
        _gb("ar", filt=Selector("d", "k1")),
    ]


@pytest.mark.parametrize("case", ["one_member", "fused_members", "min_max"])
def test_whole_form_equals_chunked_form(case, monkeypatch):
    """One call that makes and flushes its own carry against per-batch
    calls that thread it (a deadline that never fires arms chunking):
    the same fold, byte for byte, in every member's state."""
    from spark_druid_olap_tpu.plan.cost import concrete_kernel

    programs = _recording_programs(monkeypatch)
    ds, _ = _flat_ds(name="ar")
    eng = Engine()
    queries = _members(case)
    lowerings = [eng._lowering_for(q, ds) for q in queries]
    scopes = [
        frozenset(s.uid for s in eng._segments_in_scope(q, ds))
        for q in queries
    ]
    names = list(dict.fromkeys(c for lw in lowerings for c in lw.columns))
    segs = [s for s in ds.segments if any(s.uid in u for u in scopes)]
    plan = arena.plan_for(eng, eng._segment_batches(segs, names), names)
    assert plan is not None and len(plan.batches) > 2
    memb = None
    if len(queries) > 1:
        memb = np.array(
            [[s.uid in u for u in scopes] for s in plan.segs], dtype=bool
        )
    program = arena.build_arena_program(
        lowerings,
        [concrete_kernel(eng.strategy, lw.num_groups) for lw in lowerings],
    )

    def run():
        return arena.run_plan(
            eng, ds, plan, names, program, memb=memb,
            checkpoint_site="engine.segment_loop",
        )

    whole, done = run()
    assert done == len(plan.batches)
    assert _forms(programs) == [(True, {"init": True, "finish": True})]
    del programs[:]
    with deadline_scope(60_000):
        chunked, done = run()
    assert done == len(plan.batches)
    nb = len(plan.batches)
    assert _forms(programs) == [
        (ci == 0, {"init": ci == 0, "finish": ci == nb - 1})
        for ci in range(nb)
    ]
    assert len(whole) == len(chunked) == len(queries)
    for w, c in zip(whole, chunked):
        assert len(w) == len(c) == 4
        for a, b in zip(w, c):
            a, b = np.asarray(a), np.asarray(b)
            assert a.shape == b.shape and a.dtype == b.dtype
            assert a.tobytes() == b.tobytes()
        assert bool(w[3])


def test_start_flags_are_resident_beside_the_stack():
    """The batch-start flags are placed once under an arena key: the
    repeat looks them up (no host->device copy) and a retired uid takes
    them along with the stacked columns."""
    ds, _ = _flat_ds(name="ar")
    q = _gb("ar")
    eng = Engine()
    eng.execute(q, ds)
    assert eng.last_metrics.h2d_bytes > 0
    flags = [k for k in _arena_keys(eng) if k[1] == "start"]
    assert len(flags) == 1
    placed = eng._device_cache.get(flags[0])
    assert placed.dtype == bool and placed.shape == (len(flags[0][0]) - 1,)
    eng.execute(q, ds)
    assert eng.last_metrics.h2d_bytes == 0
    assert eng._device_cache.get(flags[0]) is placed
    eng.evict_segments({flags[0][0][1]})
    assert not [k for k in _arena_keys(eng) if k[1] == "start"]
