"""GROUP BY CUBE with theta distinct counts against a plain numpy KMV.

`tests/theta_reference.py` imports nothing of the program.  Held to it on
the CPU at small sizes, with K small enough that groups of a few thousand
keys are in estimation mode: the engine's merged theta state hash for
hash, the KMV union of a group's children (`ops/theta.union_children`)
against `merge_many`, and SQL CUBE answers set by set.  The two forms of a
grouping-set request, one scan rolled up on the device and one scan a
set, give the same answers; the wire subtotalsSpec takes the rollup too;
a union past the state cap and a quantiles sketch fall back to the
per-set form; a grouping-set request's counters and spans describe the
whole request."""

import http.client
import json
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pandas as pd
import pytest

import spark_druid_olap_tpu as sd
import theta_reference as ref
from spark_druid_olap_tpu.catalog.segment import build_datasource
from spark_druid_olap_tpu.config import SessionConfig
from spark_druid_olap_tpu.exec import engine as engine_mod
from spark_druid_olap_tpu.exec.engine import Engine
from spark_druid_olap_tpu.exec.state import merge
from spark_druid_olap_tpu.exec.metrics import QueryMetrics, fold_set_metrics
from spark_druid_olap_tpu.models import aggregations as A
from spark_druid_olap_tpu.models.dimensions import DimensionSpec
from spark_druid_olap_tpu.models.filters import Selector
from spark_druid_olap_tpu.models.query import GroupByQuery
from spark_druid_olap_tpu.obs.trace import SCOPE_CARRY_MERGE, device_scope
from spark_druid_olap_tpu.ops import theta as theta_ops
from spark_druid_olap_tpu.plan import cost
from spark_druid_olap_tpu.resilience import injector
from spark_druid_olap_tpu.server import OlapServer

DAY_MS = 86_400_000
SUM_REL_TOL = 2e-5  # f32 device sums against float64: the program's documented tolerance
K_EST = 64  # groups hold hundreds to thousands of distinct keys: estimation mode
ROWS = 24_000


def _table(seed, n=ROWS, key_domain=4_000):
    """Seeded rows: string dimensions `a` (3 values), `b` (4), `c` (2);
    keys `k` that repeat (a small domain) beside keys over all of int32;
    a float32 measure `r`; time `t` over 28 days, sorted."""
    rng = np.random.default_rng(seed)
    keys = np.where(
        rng.random(n) < 0.7,
        rng.integers(0, key_domain, n),
        rng.integers(-(2**31), 2**31 - 1, n),
    ).astype(np.int32)

    def pick(values):
        return rng.choice(np.array(values, dtype=object), n)

    return {
        "a": pick(["x", "y", "z"]),
        "b": pick(["p", "q", "s", "u"]),
        "c": pick(["m", "n"]),
        "k": keys,
        "r": (rng.random(n) * 100).astype(np.float32),
        "t": np.sort(rng.integers(0, 28 * DAY_MS, n)).astype(np.int64),
    }


@pytest.fixture(scope="module")
def table():
    return _table(41)


@pytest.fixture(scope="module")
def ctx(table):
    cfg = SessionConfig.load_calibrated()
    cfg.result_cache_entries = 0  # every request executes
    cfg.prefer_distributed = False
    ctx = sd.TPUOlapContext(cfg)
    ctx.register_table(
        "cube_t", table, dimensions=["a", "b", "c"], metrics=["k", "r"],
        time_column="t", rows_per_segment=4_096,
    )
    return ctx


@pytest.fixture
def per_set(monkeypatch):
    """The cost model declines every rollup: one scan a set."""
    monkeypatch.setattr(engine_mod, "rollup_takes", lambda *a, **k: False)


def _sql(group_by, k=K_EST, where="", extra=""):
    return (
        "SELECT a, b, c, SUM(r) AS rev, "
        f"APPROX_COUNT_DISTINCT_DS_THETA(k, {k}) AS u{extra} FROM cube_t"
        + (f" WHERE {where}" if where else "")
        + f" GROUP BY {group_by}"
    )


def _by_key(df, dims=("a", "b", "c")):
    """{(grouping_id, key tuple): row} of a grouping-set answer."""
    out = {}
    for row in df.to_dict("records"):
        keys = tuple(None if pd.isna(row[d]) else row[d] for d in dims)
        out[(int(row["__grouping_id"]), keys)] = row
    return out


def _walk(node):
    yield node
    for child in node.get("children", ()):
        yield from _walk(child)


def _spans(ctx, name):
    return [s for s in _walk(ctx.tracer.last_trace_dict()["spans"])
            if s["name"] == name]


# ---------------------------------------------------------------------------
# ops/theta._bottom_k: a segment's fold, slot for slot
# ---------------------------------------------------------------------------


def _key_hashing_to_sentinel():
    """The one int32 key whose theta hash is 0xFFFFFFFF: `ref.fmix32`
    undone step by step (each xor-shift and odd multiply is a bijection
    of 32-bit words)."""
    m = 0xFFFFFFFF
    h = ref.SENTINEL
    h ^= h >> 16
    h = (h * pow(0xC2B2AE35, -1, 1 << 32)) & m
    h ^= (h >> 13) ^ (h >> 26)
    h = (h * pow(0x85EBCA6B, -1, 1 << 32)) & m
    h ^= h >> 16
    h ^= (ref.THETA_SEED * 0x9E3779B9 + 0x85EBCA6B) & m
    return np.array([h], np.uint32).view(np.int32)[0]


def _fold_case(name):
    """(keys, gids, mask, G, K) of one fold case, seeded by its name."""
    rng = np.random.default_rng(sum(map(ord, name)))
    rows, G, K = 3_000, 5, 32
    keys = rng.integers(0, 20, rows)  # repeats within and across groups
    gid = rng.integers(0, G, rows)
    mask = rng.random(rows) < 0.8
    if name == "past-k":  # every group holds more than K distinct hashes
        keys = rng.integers(-(2**31), 2**31 - 1, rows)
    elif name == "empty-group":
        gid = rng.choice([0, 1, 3], rows)
    elif name == "all-masked":
        mask[:] = False
    elif name == "shifted-gids":  # a mesh shard's gids, some off its range
        gid = rng.integers(-1, G + 2, rows)
        keys = rng.integers(0, 400, rows)
    elif name == "one-group":
        G, keys = 1, rng.integers(0, 200, rows)
    elif name == "one-row":
        keys, gid, mask = keys[:1], np.array([3]), np.array([True])
    elif name == "k-1":
        K = 1
    elif name == "sentinel-hash":  # a key whose hash is the empty slot's
        keys[::7] = _key_hashing_to_sentinel()
        gid[::14] = 2  # group 2 holds it with others, ...
        gid[gid == 4] = 0
        gid[7::14] = 4  # ... group 4 holds it alone
    return keys.astype(np.int32), gid.astype(np.int32), mask, G, K


FOLD_CASES = ["repeats", "past-k", "empty-group", "all-masked",
              "shifted-gids", "one-group", "one-row", "k-1", "sentinel-hash"]


@pytest.mark.parametrize("name", FOLD_CASES)
def test_bottom_k_equals_reference(name):
    """`partial_theta`'s state against the definition, group by group: the
    K smallest distinct hashes of the group's kept rows, ascending,
    SENTINEL-padded; rows masked out or with a gid outside [0, G) add
    nothing."""
    keys, gid, mask, G, K = _fold_case(name)
    got = np.asarray(theta_ops.partial_theta(
        A.ThetaSketch("u", "k", size=K), {"k": jnp.asarray(keys)},
        jnp.asarray(gid), jnp.asarray(mask), G,
    ))
    assert got.shape == (G, K) and got.dtype == np.uint32
    for g in range(G):
        want = ref.padded(ref.bottom_k(keys[mask & (gid == g)], K), K)
        np.testing.assert_array_equal(got[g], want, err_msg=f"group {g}")
    if name == "sentinel-hash":
        assert ref.fmix32([_key_hashing_to_sentinel()], ref.THETA_SEED)[0] == ref.SENTINEL
        assert (got[4] == ref.SENTINEL).all() and (got[2] != ref.SENTINEL).any()


# ---------------------------------------------------------------------------
# ops/theta.batch_theta: a batch's segments folded in one sort of their rows
# ---------------------------------------------------------------------------


def _batch_case(name):
    """([(keys, gids, mask)] a segment, G, K) of one batch case, seeded by
    its name: 8 segments of 700 rows unless the name says otherwise, keys
    from a small domain, so most groups hold more than K distinct hashes."""
    rng = np.random.default_rng(sum(map(ord, name)) + 1)
    G, K = 6, 32
    sizes = [700] * {"n1": 1, "n2": 2, "n5": 5}.get(name, 8)
    if name == "ragged":  # a scope's last, shorter segment
        sizes[-1] = 123
    segs = [
        [rng.integers(0, 300, r), rng.integers(0, G, r), rng.random(r) < 0.8]
        for r in sizes
    ]
    for i, (keys, gid, mask) in enumerate(segs):
        if name == "shared-hash":  # one key in every segment and group
            keys[::9] = 77
        elif name == "masked-segment" and i == 3:
            mask[:] = False
        elif name == "shifted-gids":  # a mesh shard's gids, some off its range
            segs[i][1] = rng.integers(-2, G + 3, len(gid))
        elif name == "under-and-over-k":  # groups 0-2 past K, 3-5 under it
            segs[i][0] = np.where(
                gid < 3, rng.integers(-(2**31), 2**31 - 1, len(gid)),
                rng.integers(0, 5, len(gid)),
            )
    return [(k.astype(np.int32), g.astype(np.int32), m) for k, g, m in segs], G, K


BATCH_CASES = ["n1", "n2", "n5", "n8", "ragged", "shared-hash",
               "masked-segment", "shifted-gids", "under-and-over-k"]


@pytest.mark.parametrize("name", BATCH_CASES)
def test_batch_theta_equals_merged_segment_states(name):
    """One `_bottom_k` over a batch's concatenated rows is the segments'
    states merged pairwise (`merge_many`), bit for bit, and the
    definition over all the batch's kept rows, group by group."""
    segs, G, K = _batch_case(name)
    agg = A.ThetaSketch("u", "k", size=K)
    hs = [theta_ops.theta_hash(agg, {"k": jnp.asarray(k)}) for k, _, _ in segs]
    gids = [jnp.asarray(g) for _, g, _ in segs]
    masks = [jnp.asarray(m) for _, _, m in segs]
    got = np.asarray(theta_ops.batch_theta(hs, gids, masks, G, K))
    assert got.shape == (G, K) and got.dtype == np.uint32
    merged = theta_ops.merge_many([
        theta_ops._bottom_k(h, g, m, G, K) for h, g, m in zip(hs, gids, masks)
    ], K)
    np.testing.assert_array_equal(got, np.asarray(merged))
    keys, gid, mask = (np.concatenate(c) for c in zip(*segs))
    for g in range(G):
        want = ref.padded(ref.bottom_k(keys[mask & (gid == g)], K), K)
        np.testing.assert_array_equal(got[g], want, err_msg=f"group {g}")
    if name == "under-and-over-k":
        assert (got[:3] != ref.SENTINEL).all()
        assert (got[3:] == ref.SENTINEL).any(axis=1).all()


def _assert_reference_states(state, dims, table, keep, k):
    """A merged theta state uint32[G, k] of a query grouped by `b` is the
    definition's over the rows `keep` selects, group by group."""
    decoded = dims[0].decode(np.arange(state.shape[0]))
    for gid, value in enumerate(decoded):
        rows = keep & (table["b"] == value)
        want = ref.padded(ref.bottom_k(table["k"][rows], k), k)
        np.testing.assert_array_equal(state[gid], want, err_msg=str(value))


@pytest.mark.parametrize("rows_per_segment", [512, 3_000],
                         ids=["47-segments", "8-segments"])
def test_theta_scope_folds_once_a_batch(table, monkeypatch, rows_per_segment):
    """On a TPU's unroll a theta scope of N segments runs ceil(N / 8)
    batch programs, each folding its segments' rows once
    (`QueryMetrics.theta_folds`), to the reference's state; the fused
    micro-batch program, one fold a member and batch, gives the same
    states."""
    ds = build_datasource(
        "fold_t", table, ["a", "b", "c"], ["k", "r"], time_col="t",
        rows_per_segment=rows_per_segment,
    )
    aggs = (A.ThetaSketch("u", "k", size=K_EST), A.DoubleSum("rev", "r"))
    q = GroupByQuery(datasource="fold_t", dimensions=(DimensionSpec("b"),),
                     aggregations=aggs)
    q_m = GroupByQuery(datasource="fold_t", dimensions=(DimensionSpec("b"),),
                       aggregations=aggs, filter=Selector("c", "m"))
    eng = Engine()
    monkeypatch.setattr("spark_druid_olap_tpu.config._current_platform",
                        lambda: "tpu")
    batches = -(-len(ds.segments) // engine_mod.THETA_SEGMENT_UNROLL_MAX)
    eng.execute(q, ds)
    assert eng.last_metrics.theta_folds == batches
    dims = eng._lowering_for(q, ds).dims
    sk = eng._partials_for_query(q, ds).sketches
    every = np.ones(ROWS, dtype=bool)
    _assert_reference_states(np.asarray(sk["u"]), dims, table, every, K_EST)
    (_, state, m), (_, state_m, m_m) = eng.execute_fused([q, q_m], ds)
    np.testing.assert_array_equal(state.sketches["u"], np.asarray(sk["u"]))
    _assert_reference_states(state_m.sketches["u"], dims, table,
                             table["c"] == "m", K_EST)
    # a fused batch's unroll takes no theta bound: 32 segments a program
    fused = -(-len(ds.segments) // engine_mod.MULTI_SEGMENT_UNROLL_MAX)
    assert m.theta_folds == m_m.theta_folds == fused


def test_query_without_theta_traces_the_per_segment_program(table):
    """A batch program without a theta aggregation (an HLL count beside a
    sum and a min, as the topN cell sends) lowers to the very text of the
    per-segment loop that folds and merges each segment's states in turn:
    the batch fold changes no other query's program."""
    ds = build_datasource(
        "hll_t", table, ["a", "b", "c"], ["k", "r"], time_col="t",
        rows_per_segment=4_096,
    )
    q = GroupByQuery(
        datasource="hll_t", dimensions=(DimensionSpec("b"),),
        aggregations=(A.HyperUnique("h", "k"), A.DoubleSum("rev", "r"),
                      A.DoubleMin("lo", "r")),
    )
    eng = Engine()
    lowering = eng._lowering_for(q, ds)
    strategy = cost.concrete_kernel(eng.strategy, lowering.num_groups)
    program = eng._segment_program(q, ds, lowering, strategy_override=strategy)
    cols_list = [eng._cols_for_segment(seg, ds, lowering.columns)
                 for seg in ds.segments[:3]]

    @jax.jit
    # graftlint: disable=jit-cache -- one reference program a test, lowered once
    def seg_fn(cols_list):
        state = None
        for cols in cols_list:
            seg = engine_mod._segment_partials(lowering, strategy, cols)
            with device_scope(SCOPE_CARRY_MERGE):
                state = merge(lowering.la, state, seg)
        return state

    assert program.lower(cols_list).as_text() == seg_fn.lower(cols_list).as_text()


# ---------------------------------------------------------------------------
# ops/theta.union_children
# ---------------------------------------------------------------------------


def _children(seed, g, n, k, pool):
    """uint32[g, n, k] states drawn from a pool of `pool` hashes a group
    (so children share hashes), some holding fewer than k (padded), one
    child empty, as `_bottom_k` lays a state out."""
    rng = np.random.default_rng(seed)
    out = np.full((g, n, k), ref.SENTINEL, dtype=np.uint32)
    for gi in range(g):
        hashes = rng.choice(
            np.uint32(2**32 - 2), size=pool, replace=False
        ).astype(np.uint32)
        for ci in range(n):
            if (gi + ci) % 5 == 4:
                continue  # an empty child
            take = rng.integers(1, 2 * k)
            out[gi, ci] = ref.padded(np.unique(rng.choice(hashes, take))[:k], k)
    return out


@pytest.mark.parametrize("g, n, k, pool", [
    (1, 2, 8, 12), (6, 5, 16, 40), (4, 8, 64, 90), (3, 1, 32, 200),
])
def test_union_children_equals_merge_many(g, n, k, pool):
    ch = _children(g * 100 + n, g, n, k, pool)
    got = np.asarray(theta_ops.union_children(jnp.asarray(ch), k))
    want = np.asarray(
        theta_ops.merge_many([jnp.asarray(ch[:, i]) for i in range(n)], k)
    )
    np.testing.assert_array_equal(got, want)
    for gi in range(g):  # and the definition: the bottom-k of the union
        kept = ch[gi][ch[gi] != ref.SENTINEL]
        np.testing.assert_array_equal(
            got[gi], ref.padded(np.unique(kept)[:k], k)
        )


# ---------------------------------------------------------------------------
# the engine's merged theta state, hash for hash
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("k, selector, rows_per_segment", [
    (K_EST, False, 4_096), (K_EST, True, 3_000), (4_096, False, 8_192),
], ids=["estimation", "estimation-filter", "exact"])
def test_engine_theta_state_equals_reference(table, k, selector, rows_per_segment):
    ds = build_datasource(
        "theta_t", table, ["a", "b", "c"], ["k", "r"], time_col="t",
        rows_per_segment=rows_per_segment,
    )
    q = GroupByQuery(
        datasource="theta_t",
        dimensions=(DimensionSpec("b"),),
        aggregations=(A.ThetaSketch("u", "k", size=k), A.DoubleSum("rev", "r")),
        filter=Selector("c", "m") if selector else None,
    )
    eng = Engine()
    lowering = eng._lowering_for(q, ds)
    dims, G = lowering.dims, lowering.num_groups
    state = eng._partials_for_query(q, ds, lowering=lowering)
    got = np.asarray(state.sketches["u"])
    assert got.shape == (G, k) and got.dtype == np.uint32
    keep = table["c"] == "m" if selector else np.ones(ROWS, dtype=bool)
    decoded = dims[0].decode(np.arange(G))
    for gid in range(G):
        rows = keep & (table["b"] == decoded[gid])
        want = ref.padded(ref.bottom_k(table["k"][rows], k), k)
        np.testing.assert_array_equal(got[gid], want, err_msg=str(decoded[gid]))


@pytest.mark.parametrize("agg, unroll", [
    (A.ThetaSketch("u", "k", size=K_EST), engine_mod.THETA_SEGMENT_UNROLL_MAX),
    (A.HyperUnique("u", "k"), engine_mod.MULTI_SEGMENT_UNROLL_MAX),
    (A.LongSum("u", "k"), engine_mod.MULTI_SEGMENT_UNROLL_MAX),
], ids=["theta", "hll", "sum"])
def test_theta_scope_unrolls_fewer_segments_on_a_tpu(table, monkeypatch, agg, unroll):
    """A TPU batch program unrolls at most 32 segments, and at most 8 where
    the query holds a theta sketch (each of its segments sorts [G, 2K])."""
    monkeypatch.setattr("spark_druid_olap_tpu.config._current_platform",
                        lambda: "tpu")
    ds = build_datasource(
        "unroll_t", table, ["a", "b", "c"], ["k", "r"], time_col="t",
        rows_per_segment=512,
    )
    assert len(ds.segments) > engine_mod.MULTI_SEGMENT_UNROLL_MAX
    q = GroupByQuery(
        datasource="unroll_t", dimensions=(DimensionSpec("b"),),
        aggregations=(agg,),
    )
    eng = Engine()
    lowering = eng._lowering_for(q, ds)
    batches = list(eng._segment_batches(ds.segments, lowering.columns, lowering.la))
    assert max(len(b) for b in batches) == unroll
    assert sum(len(b) for b in batches) == len(ds.segments)


# ---------------------------------------------------------------------------
# SQL CUBE answers, set by set
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dims, k, where", [
    (("a", "b", "c"), K_EST, ""),
    (("a", "b"), K_EST, "c = 'n'"),
    (("b", "c"), 4_096, ""),
], ids=["cube3", "cube2-filter", "cube2-exact"])
def test_cube_answer_equals_reference(ctx, table, dims, k, where):
    sql = (
        f"SELECT {', '.join(dims)}, SUM(r) AS rev, "
        f"APPROX_COUNT_DISTINCT_DS_THETA(k, {k}) AS u FROM cube_t"
        + (f" WHERE {where}" if where else "")
        + f" GROUP BY CUBE({', '.join(dims)})"
    )
    got = _by_key(ctx.sql(sql), dims)
    m = ctx.last_metrics
    assert (m.grouping_sets, m.set_scans) == (2 ** len(dims), 1)
    frame = pd.DataFrame(table)
    if where:
        frame = frame[frame["c"] == "n"]
    want = ref.cube(frame, list(dims), "k", "r", k)
    assert set(got) == set(want)
    for key, (_, rev, uniq) in want.items():
        assert int(got[key]["u"]) == uniq, key
        np.testing.assert_allclose(got[key]["rev"], rev, rtol=SUM_REL_TOL)


# ---------------------------------------------------------------------------
# the two forms agree
# ---------------------------------------------------------------------------

FORMS = {
    "cube-sketches-extrema": (
        "SELECT a, b, c, SUM(r) AS rev, "
        f"APPROX_COUNT_DISTINCT_DS_THETA(k, {K_EST}) AS u, "
        "APPROX_COUNT_DISTINCT(k) AS h, MIN(r) AS lo, MAX(r) AS hi, "
        "COUNT(*) AS n FROM cube_t GROUP BY CUBE(a, b, c)", 8,
    ),
    "rollup": (_sql("ROLLUP(a, b, c)"), 4),
    "grouping-sets-unsorted": (_sql("GROUPING SETS ((c, a), (b), ())"), 3),
    "having-order-limit": (
        _sql("CUBE(a, b)").replace("SELECT a, b, c,", "SELECT a, b,")
        + " HAVING SUM(r) > 10000 ORDER BY rev DESC LIMIT 7", 4,
    ),
}


@pytest.mark.parametrize("name", sorted(FORMS))
def test_rollup_and_per_set_forms_agree(ctx, monkeypatch, name):
    sql, n_sets = FORMS[name]
    rolled = ctx.sql(sql)
    m = ctx.last_metrics
    assert (m.grouping_sets, m.set_scans) == (n_sets, 1)
    monkeypatch.setattr(engine_mod, "rollup_takes", lambda *a, **k: False)
    scanned = ctx.sql(sql)
    m = ctx.last_metrics
    assert (m.grouping_sets, m.set_scans) == (n_sets, n_sets)
    assert list(rolled.columns) == list(scanned.columns)
    dims = [c for c in ("a", "b", "c") if c in rolled.columns]
    if "LIMIT" in sql:  # the combined order: the same rows in the same order
        assert list(rolled["rev"].rank()) == list(scanned["rev"].rank())
    got, want = _by_key(rolled, dims), _by_key(scanned, dims)
    assert set(got) == set(want)
    for key, row in want.items():
        for col, v in row.items():
            if col == "rev":
                np.testing.assert_allclose(got[key][col], v, rtol=SUM_REL_TOL)
            elif col not in dims:
                assert got[key][col] == v, (key, col)


def _subtotals_query(sets, k=K_EST):
    return {
        "queryType": "groupBy", "dataSource": "cube_t",
        "dimensions": ["a", "b"], "granularity": "all",
        "intervals": ["1970-01-01/1970-02-01"],
        "aggregations": [
            {"type": "doubleSum", "name": "rev", "fieldName": "r"},
            {"type": "thetaSketch", "name": "u", "fieldName": "k", "size": k},
        ],
        "subtotalsSpec": sets,
    }


def _post(port, path, body):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=300)
    try:
        conn.request("POST", path, json.dumps(body),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        out = resp.read()
        assert resp.status == 200, out
        return json.loads(out)
    finally:
        conn.close()


@pytest.mark.parametrize("form", ["rollup", "per_set"])
def test_wire_subtotals_spec_takes_the_rollup(ctx, table, monkeypatch, form):
    if form == "per_set":
        monkeypatch.setattr(engine_mod, "rollup_takes", lambda *a, **k: False)
    srv = OlapServer(ctx, port=0).start()
    try:
        rows = _post(srv.port, "/druid/v2",
                     _subtotals_query([["a", "b"], ["b"], []]))
    finally:
        srv.shutdown()
    m = ctx.last_metrics
    assert (m.grouping_sets, m.set_scans) == (3, 1 if form == "rollup" else 3)
    want = ref.cube(pd.DataFrame(table), ["a", "b"], "k", "r", K_EST,
                    sets=[(0, 1), (1,), ()])
    got = {(ref.grouping_id(tuple(i for i, d in enumerate("ab")
                                  if r["event"][d] is not None), 2),
            (r["event"]["a"], r["event"]["b"])): r["event"] for r in rows}
    assert set(got) == set(want)
    for key, (_, rev, uniq) in want.items():
        assert got[key]["u"] == uniq
        np.testing.assert_allclose(got[key]["rev"], rev, rtol=SUM_REL_TOL)


# ---------------------------------------------------------------------------
# where the rollup does not take
# ---------------------------------------------------------------------------


def _state_cap_exceeded(monkeypatch):
    monkeypatch.setattr(cost, "ROLLUP_STATE_SHARE", 1 << 62)
    return _sql("CUBE(a, b)").replace("SELECT a, b, c,", "SELECT a, b,")


def _quantiles(monkeypatch):
    return (
        "SELECT a, b, SUM(r) AS rev, APPROX_QUANTILE_DS(r, 0.5) AS med "
        "FROM cube_t GROUP BY CUBE(a, b)"
    )


@pytest.mark.parametrize("case", [_state_cap_exceeded, _quantiles])
def test_declined_rollup_runs_one_scan_a_set(ctx, table, monkeypatch, case):
    sql = case(monkeypatch)
    got = _by_key(ctx.sql(sql), ("a", "b"))
    m = ctx.last_metrics
    assert (m.grouping_sets, m.set_scans) == (4, 4)
    assert m.rows_scanned == 4 * ROWS
    assert _spans(ctx, "grouping_sets")[0]["attrs"]["form"] == "per_set"
    want = ref.cube(pd.DataFrame(table), ["a", "b"], "k", "r", K_EST)
    assert set(got) == set(want)
    for key, (_, rev, _) in want.items():
        np.testing.assert_allclose(got[key]["rev"], rev, rtol=SUM_REL_TOL)


@pytest.mark.parametrize("case, takes", [
    ("dense", True),
    ("quantiles", False),
    ("time-granularity", False),
    ("state-over-cap", False),
    ("high-cardinality-tier", False),
])
def test_rollup_takes(case, takes):
    cfg = SessionConfig.load_calibrated()
    aggs = (A.DoubleSum("rev", "r"), A.ThetaSketch("u", "k", size=K_EST))
    if case == "quantiles":
        aggs += (A.QuantilesSketch("med", "r"),)
    q = GroupByQuery(
        datasource="t", dimensions=(DimensionSpec("a"), DimensionSpec("b")),
        aggregations=aggs,
        granularity="day" if case == "time-granularity" else "all",
    )
    lowering = SimpleNamespace(
        num_groups=100_000 if case == "high-cardinality-tier" else 24,
        dims=q.dimensions, la=SimpleNamespace(sketch_aggs=aggs[1:]),
    )
    budget = 1 << 10 if case == "state-over-cap" else 1 << 34
    assert cost.rollup_takes(
        q, [(0, 1), (0,), ()], lowering, "auto", cfg, budget
    ) is takes


# ---------------------------------------------------------------------------
# the request's counters and spans
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("form", ["rollup", "per_set"])
def test_grouping_set_request_counts_every_scan(ctx, monkeypatch, form):
    if form == "per_set":
        monkeypatch.setattr(engine_mod, "rollup_takes", lambda *a, **k: False)
    ctx.sql(_sql("CUBE(a, b, c)"))
    m = ctx.last_metrics
    scans = 1 if form == "rollup" else 8
    assert (m.grouping_sets, m.set_scans) == (8, scans)
    assert m.rows_scanned == scans * ROWS
    assert m.sketch_state_bytes > 0
    (gs,) = _spans(ctx, "grouping_sets")
    assert gs["attrs"] == {"sets": 8, "form": form}
    launches = _spans(ctx, "segment_dispatch")
    rolled = [s for s in launches if "rollup" in s["attrs"]]
    assert len(rolled) == (1 if form == "rollup" else 0)
    assert len(_spans(ctx, "device_fetch")) == scans
    assert m.receipt["dispatch_count"] == len(launches)
    # one theta fold a batch launch, over every row of its segments
    folds = [s["attrs"]["theta_rows"] for s in launches
             if "theta_rows" in s["attrs"]]
    assert len(folds) == m.theta_folds > 0
    assert sum(folds) >= scans * ROWS


def test_one_set_requests_have_no_grouping_set_counters(ctx):
    ctx.sql("SELECT a, SUM(r) AS rev FROM cube_t GROUP BY a")
    m = ctx.last_metrics
    assert (m.grouping_sets, m.set_scans) == (0, 0)
    assert not _spans(ctx, "grouping_sets")


def _fail_third_dispatch(monkeypatch):
    """The third set's batch dispatch fails: it reruns on the serial path."""
    monkeypatch.setattr(engine_mod, "rollup_takes", lambda *a, **k: False)
    dispatch = Engine._dispatch_groupby_once
    calls = {"n": 0}

    def flaky(self, *a, **k):
        calls["n"] += 1
        if calls["n"] == 3:
            raise RuntimeError("transient device failure")
        return dispatch(self, *a, **k)

    monkeypatch.setattr(Engine, "_dispatch_groupby_once", flaky)


def _fail_rollup_scan_once(monkeypatch):
    injector().arm("device_dispatch", times=1)


@pytest.mark.parametrize("fault", [_fail_third_dispatch, _fail_rollup_scan_once])
def test_a_fault_in_any_set_is_the_requests(ctx, monkeypatch, fault):
    sql = _sql("CUBE(a, b)").replace("SELECT a, b, c,", "SELECT a, b,")
    ctx.sql(sql)  # warm
    fault(monkeypatch)
    try:
        ctx.sql(sql)
    finally:
        injector().disarm()
    m = ctx.last_metrics
    assert m.grouping_sets == 4 and m.retries >= 1


@pytest.mark.parametrize("flag, value", [
    ("executor", "fallback"), ("degraded", True), ("partial", True),
    ("retries", 2), ("program_cache_hit", False),
])
def test_fold_set_metrics_makes_any_sets_fault_the_requests(flag, value):
    parts = [
        QueryMetrics(rows_scanned=10, bytes_scanned=40, program_cache_hit=True,
                     sketch_state_bytes=8)
        for _ in range(4)
    ]
    setattr(parts[2], flag, value)
    m = fold_set_metrics(parts, 4)
    assert getattr(m, flag) == value
    assert (m.rows_scanned, m.bytes_scanned, m.sketch_state_bytes) == (40, 160, 32)
    assert (m.grouping_sets, m.set_scans) == (4, 4)
