"""Pallas fused GroupBy kernel vs the XLA dense path (bit-parity contract).

Runs in interpret mode on the CPU test mesh; tests/test_chip_compile.py
compiles the same kernel for a described v5e."""

import jax.numpy as jnp
import numpy as np
import pytest

from spark_druid_olap_tpu.ops.groupby import dense_partial_aggregate
from spark_druid_olap_tpu.ops.pallas_groupby import (
    _split,
    pallas_partial_aggregate,
)

INTERPRET = True


def _mk(R, G, Ms, Mn, Mx, seed=0, mask_p=0.8, stray_ids=False):
    """Operands as the lowering hands them over: the last sum column is a
    count (pre-masked ones), masked rows may carry any id."""
    rng = np.random.default_rng(seed)
    gid = rng.integers(0, G, R).astype(np.int32)
    mask = rng.random(R) < mask_p
    if stray_ids:
        stray = rng.choice(
            np.asarray([-1, -7, G, G + 5, 1 << 20, np.iinfo(np.int32).max,
                        np.iinfo(np.int32).min], np.int32), R)
        gid = np.where(mask, gid, stray)
    sv = rng.random((R, Ms)).astype(np.float32)
    sv[:, -1] = 1.0
    sv = sv * mask[:, None]
    mmv = rng.standard_normal((R, Mn + Mx)).astype(np.float32)
    mmm = rng.random((R, Mn + Mx)) < 0.9
    return gid, mask, sv, mmv, mmm


def _reference(gid, mask, sv, mmv, mmm, G, Mn, Mx):
    """float64 numpy, one scatter a column: what every strategy must equal."""
    sums = np.zeros((G, sv.shape[1]))
    np.add.at(sums, gid[mask], sv[mask].astype(np.float64))
    mins = np.full((G, Mn), np.inf, np.float32)
    maxs = np.full((G, Mx), -np.inf, np.float32)
    for m in range(Mn):
        ok = mask & mmm[:, m]
        np.minimum.at(mins[:, m], gid[ok], mmv[ok, m])
    for m in range(Mx):
        ok = mask & mmm[:, Mn + m]
        np.maximum.at(maxs[:, m], gid[ok], mmv[ok, Mn + m])
    return sums, mins, maxs


# (R, G, Ms, Mn, Mx, mask_p, stray_ids)
PARITY_CASES = {
    "one_group": (1024, 1, 1, 0, 0, 0.8, False),
    "q1_tiny_g": (4096, 12, 3, 0, 0, 0.8, False),
    "g_off_the_lane_tile": (8192, 300, 4, 2, 1, 0.8, False),
    "min_only": (2048, 130, 2, 2, 0, 0.8, False),
    "max_only": (2048, 130, 2, 0, 2, 0.8, False),
    "widest_single_tile": (4096, 4096, 2, 0, 0, 0.8, False),
    "two_group_tiles": (4096, 8008, 2, 1, 1, 0.8, False),
    "all_rows_masked": (2048, 10, 2, 1, 1, 0.0, False),
    "all_rows_masked_stray_ids": (2048, 10, 2, 1, 1, 0.0, True),
    "masked_rows_carry_stray_ids": (8192, 700, 3, 1, 1, 0.6, True),
    "no_row_masked": (2048, 208, 2, 0, 0, 1.1, False),
    # 131 is prime: the only blocks that divide R are single 128-lane tiles
    "rows_with_small_divisors_only": (128 * 131, 37, 2, 1, 0, 0.8, True),
    "several_grid_steps_and_tiles": (1 << 16, 260, 3, 0, 0, 0.8, False),
}


@pytest.mark.parametrize("case", sorted(PARITY_CASES))
def test_pallas_parity(case):
    """The kernel against the XLA dense strategy and a float64 reference:
    counts exact, sums to f32 accumulation error, min/max exact, empty
    groups 0 / +inf / -inf."""
    R, G, Ms, Mn, Mx, mask_p, stray = PARITY_CASES[case]
    ops = _mk(R, G, Ms, Mn, Mx, mask_p=mask_p, stray_ids=stray)
    got = [
        np.asarray(a) for a in pallas_partial_aggregate(
            *map(jnp.asarray, ops),
            num_groups=G, num_min=Mn, num_max=Mx, interpret=INTERPRET,
        )
    ]
    want = _reference(*ops, G, Mn, Mx)
    assert [a.shape for a in got] == [(G, Ms), (G, Mn), (G, Mx)]
    np.testing.assert_array_equal(got[0][:, -1], want[0][:, -1])  # counts
    np.testing.assert_allclose(got[0], want[0], rtol=2e-6)
    np.testing.assert_array_equal(got[1], want[1])
    np.testing.assert_array_equal(got[2], want[2])
    empty = want[0][:, -1] == 0
    if mask_p == 0.0:
        assert empty.all()
    assert (got[0][empty] == 0).all()
    if not stray and R % 1024 == 0:
        # the dense strategy clamps nothing and needs in-range ids
        dense = dense_partial_aggregate(
            *map(jnp.asarray, ops),
            num_groups=G, block_rows=1024, num_min=Mn, num_max=Mx,
        )
        for g, d in zip(got, dense):
            np.testing.assert_allclose(g, np.asarray(d), rtol=1e-6)


def _rows_call(gid, mask, rows, G):
    """The kernel in the form the lowering hands it under
    `strategy="pallas"`: one unmasked `[R]` row a sum column, `None` for
    a count; no min / max."""
    R = len(gid)
    s, _, _ = pallas_partial_aggregate(
        jnp.asarray(gid), jnp.asarray(mask),
        tuple(None if r is None else jnp.asarray(r) for r in rows),
        jnp.zeros((R, 0), jnp.float32), jnp.zeros((R, 0), jnp.bool_),
        num_groups=G, num_min=0, num_max=0, interpret=INTERPRET,
    )
    return np.asarray(s)


def _wide_f32(n, seed):
    """Random float32 of both signs over 1e-30 .. 1e30."""
    rng = np.random.default_rng(seed)
    return (
        10.0 ** rng.uniform(-30, 30, n) * rng.choice([-1.0, 1.0], n)
    ).astype(np.float32)


def _bits(a):
    return np.asarray(a, np.float32).view(np.uint32)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_split_is_exact_in_three_bfloat16_parts(seed):
    """`hi + mid + lo == v` bit for bit, small parts first as the kernel
    adds them, and every part survives a round trip through bfloat16:
    what makes one bf16 MXU pass an f32 sum."""
    v = _wide_f32(1 << 14, seed)
    hi, mid, lo = (np.asarray(p) for p in _split(jnp.asarray(v)))
    np.testing.assert_array_equal(_bits((lo + mid) + hi), _bits(v))
    for part in (hi, mid, lo):
        back = np.asarray(jnp.asarray(part).astype(jnp.bfloat16), np.float32)
        np.testing.assert_array_equal(_bits(back), _bits(part))


def test_split_keeps_a_non_finite_value_whole_in_hi():
    """inf - inf would make the rest NaN, and a NaN whose payload lies in
    the low 16 bits would be cut to inf."""
    low_nan = np.array([0x7F800001], np.uint32).view(np.float32)[0]
    v = np.array([np.inf, -np.inf, np.nan, low_nan, 1.5], np.float32)
    hi, mid, lo = (np.asarray(p) for p in _split(jnp.asarray(v)))
    assert np.isinf(hi[:2]).all() and np.isnan(hi[2:4]).all()
    np.testing.assert_array_equal(_bits(hi[:2]), _bits(v[:2]))
    assert (mid[:4] == 0).all() and (lo[:4] == 0).all()
    assert hi[4] + mid[4] + lo[4] == 1.5


@pytest.mark.parametrize("dtype", ["float32", "int32"])
@pytest.mark.parametrize("seed", [3, 4])
def test_kernel_sums_one_row_groups_bit_for_bit(seed, dtype):
    """Through the kernel: a group of one row returns that row's value to
    the bit, so the split made in VMEM lost nothing; an int32 metric is
    converted on the tile as `astype(float32)` rounds it."""
    n = 2048
    if dtype == "float32":
        v = _wide_f32(n, seed)
    else:
        v = np.random.default_rng(seed).integers(
            -(1 << 31), (1 << 31) - 1, n
        ).astype(np.int32)
    got = _rows_call(np.arange(n, dtype=np.int32), np.ones(n, bool), [v, None], n)
    np.testing.assert_array_equal(_bits(got[:, 0]), _bits(v.astype(np.float32)))
    assert (got[:, 1] == 1).all()


@pytest.mark.parametrize("G", [1, 208, 800])
@pytest.mark.parametrize("kept", ["all", "half", "none"])
def test_count_column_is_the_masked_row_count(G, kept):
    """A count has no operand: it is the match tile's row sum, whatever
    the value column beside it holds."""
    R = 4096
    rng = np.random.default_rng(G)
    gid = rng.integers(0, G, R).astype(np.int32)
    mask = {"all": np.ones(R, bool), "none": np.zeros(R, bool),
            "half": rng.random(R) < 0.5}[kept]
    got = _rows_call(gid, mask, [None, _wide_f32(R, G), None], G)
    want = np.bincount(gid[mask], minlength=G)
    np.testing.assert_array_equal(got[:, 0], want)
    np.testing.assert_array_equal(got[:, 2], want)
    assert np.isfinite(got[:, 1]).all()
    assert (got[want == 0] == 0).all()


@pytest.mark.parametrize("poison", [np.inf, -np.inf, np.nan])
def test_masked_row_holding_a_non_finite_value_reaches_no_sum(poison):
    """The values are not multiplied by the row mask any more: a masked
    row's value meets the tile's 0 in every group, and `0 * inf` is NaN
    unless the kernel zeroes the row by its id first."""
    R, G = 2048, 208
    rng = np.random.default_rng(5)
    gid = rng.integers(0, G, R).astype(np.int32)
    mask = rng.random(R) < 0.5
    clean = rng.random(R).astype(np.float32)
    v = np.where(mask, clean, np.float32(poison)).astype(np.float32)
    got = _rows_call(gid, mask, [v, None], G)
    want = _rows_call(gid, mask, [clean, None], G)
    np.testing.assert_array_equal(_bits(got), _bits(want))


def test_rows_form_equals_the_array_form():
    """The kernel takes the other kernels' pre-masked `[R, Ms]` array
    column by column: the same sums, bit for bit, as the unmasked rows."""
    R, G = 8192, 300
    gid, mask, sv, mmv, mmm = _mk(R, G, 3, 0, 0)
    raw = np.random.default_rng(0).random((R, 3)).astype(np.float32)
    sv = raw * mask[:, None]
    sv[:, -1] = mask
    array, _, _ = pallas_partial_aggregate(
        *map(jnp.asarray, (gid, mask, sv, mmv, mmm)),
        num_groups=G, num_min=0, num_max=0, interpret=INTERPRET,
    )
    rows = _rows_call(gid, mask, [raw[:, 0], raw[:, 1], None], G)
    np.testing.assert_array_equal(_bits(array), _bits(rows))


def _tpch_query(aggregations, dims=("l_returnflag", "l_linestatus")):
    from spark_druid_olap_tpu.models.dimensions import DimensionSpec
    from spark_druid_olap_tpu.models.query import GroupByQuery

    return GroupByQuery(
        datasource="tpch",
        dimensions=tuple(DimensionSpec(d) for d in dims),
        aggregations=tuple(aggregations),
    )


def _lowering_cases():
    from spark_druid_olap_tpu.models.aggregations import (
        Count, DoubleSum, ExpressionAgg, FilteredAgg, LongSum,
    )
    from spark_druid_olap_tpu.models.filters import Selector
    from spark_druid_olap_tpu.plan.expr import col

    open_only = Selector("l_linestatus", "O")
    return {
        # name: (aggregations, the stored column each sum reads as it is)
        "count_only": ([Count("n")], {}),
        "bare_float_column": (
            [DoubleSum("s", "l_quantity"), Count("n")], {"s": "l_quantity"},
        ),
        "bare_int_column": (
            [LongSum("k", "l_orderkey"), Count("n")], {"k": "l_orderkey"},
        ),
        "expression": (
            [ExpressionAgg(
                "e", col("l_extendedprice") * (1 - col("l_discount"))
            ), Count("n")], {},
        ),
        "filtered_aggregators": (
            [FilteredAgg(open_only, DoubleSum("fs", "l_quantity")),
             FilteredAgg(open_only, Count("fn")),
             DoubleSum("s", "l_quantity")], {"s": "l_quantity"},
        ),
    }


@pytest.mark.parametrize("case", sorted(_lowering_cases()))
def test_lowering_hands_the_kernel_unmasked_rows(lineitem_ds, case):
    """`row_arrays(strategy="pallas")`: a count (and what aliases it) has
    no operand, a stored float32 / int32 metric is the resident column
    itself, an aggregator's own mask is multiplied in; the kernel on that
    form equals the XLA dense scan on the pre-masked array, counts to the
    unit."""
    from spark_druid_olap_tpu.exec.engine import Engine
    from spark_druid_olap_tpu.exec.lowering import lower_groupby

    aggs, stored = _lowering_cases()[case]
    q = _tpch_query(aggs)
    ds = lineitem_ds
    lowering = lower_groupby(q, ds)
    la, G = lowering.la, lowering.num_groups
    cols = Engine()._cols_for_segment(ds.segments[0], ds, lowering.columns)
    gid, mask, rows, mmv, mmm = lowering.row_arrays(cols, strategy="pallas")
    assert isinstance(rows, list) and len(rows) == len(la.sum_names)
    for name, row in zip(la.sum_names, rows):
        if name in stored:
            assert row is cols[stored[name]]
            assert row.dtype in (jnp.float32, jnp.int32)
        elif name == "__rows":
            assert row is None
        else:
            assert row.dtype == jnp.float32 and row.shape == mask.shape
    got, _, _ = pallas_partial_aggregate(
        gid, mask, rows, mmv, mmm,
        num_groups=G, num_min=0, num_max=0, interpret=INTERPRET,
    )
    dense_ops = lowering.row_arrays(cols)  # what every other kernel gets
    assert dense_ops[2].shape == (mask.shape[0], len(la.sum_names))
    want, _, _ = dense_partial_aggregate(
        *dense_ops, num_groups=G, block_rows=1024, num_min=0, num_max=0,
    )
    got, want = np.asarray(got), np.asarray(want)
    for m, name in enumerate(la.sum_names):
        if la.long_valued[name] and name not in stored:
            np.testing.assert_array_equal(got[:, m], want[:, m])  # counts
    np.testing.assert_allclose(got, want, rtol=1e-6)


def _assert_same_answer(a, b, by=("l_returnflag", "l_linestatus")):
    """Two result frames of one `_tpch_query`: keys and counts exact, sums
    to f32 accumulation error."""
    a, b = a.sort_values(list(by)), b.sort_values(list(by))
    assert list(a.columns) == list(b.columns) and len(a) == len(b)
    for name in a.columns:
        if name in by:
            assert list(a[name]) == list(b[name])
        elif name in ("n", "fn"):
            np.testing.assert_array_equal(a[name].values, b[name].values)
        else:
            np.testing.assert_allclose(a[name].values, b[name].values, rtol=1e-6)


@pytest.mark.parametrize("case", sorted(_lowering_cases()))
def test_engine_pallas_equals_dense(lineitem_ds, case):
    """Whole queries: `Engine(strategy="pallas")` (interpret mode here)
    against the XLA dense scan, every segment folded."""
    from spark_druid_olap_tpu.exec.engine import Engine

    q = _tpch_query(_lowering_cases()[case][0])
    _assert_same_answer(
        Engine(strategy="pallas").execute(q, lineitem_ds),
        Engine(strategy="dense").execute(q, lineitem_ds),
    )


@pytest.mark.parametrize("case", sorted(_lowering_cases()))
def test_mesh_pallas_equals_dense(lineitem_ds, case):
    """The mesh's dense-state program (`parallel/distributed.py`): the
    kernel once over a shard's whole rows under `shard_map`, handed the
    same unmasked rows, against the one-device XLA dense scan."""
    from spark_druid_olap_tpu.exec.engine import Engine
    from spark_druid_olap_tpu.parallel.distributed import DistributedEngine
    from spark_druid_olap_tpu.parallel.mesh import make_mesh

    q = _tpch_query(_lowering_cases()[case][0])
    mesh = DistributedEngine(mesh=make_mesh(n_data=4), strategy="pallas")
    got = mesh.execute(q, lineitem_ds)
    assert mesh.last_metrics.strategy == "pallas"
    _assert_same_answer(got, Engine(strategy="dense").execute(q, lineitem_ds))


def test_pallas_rejects_rows_it_cannot_tile():
    """An R off the 128-lane tile has no lane-dense block."""
    R = 1000
    ops = _mk(R, 4, 1, 0, 0)
    with pytest.raises(ValueError, match="multiple of 128"):
        pallas_partial_aggregate(
            *map(jnp.asarray, ops),
            num_groups=4, num_min=0, num_max=0, interpret=INTERPRET,
        )


def test_engine_pallas_strategy_parity(lineitem_ds):
    """Engine-level: strategy='pallas' (interpret on CPU) == 'dense'."""
    from spark_druid_olap_tpu.exec.engine import Engine
    from spark_druid_olap_tpu.models.aggregations import Count, DoubleSum
    from spark_druid_olap_tpu.models.dimensions import DimensionSpec
    from spark_druid_olap_tpu.models.query import GroupByQuery

    q = GroupByQuery(
        datasource="tpch",
        dimensions=(DimensionSpec("l_returnflag"), DimensionSpec("l_linestatus")),
        aggregations=(DoubleSum("s", "l_quantity"), Count("n")),
    )
    a = Engine(strategy="pallas").execute(q, lineitem_ds).sort_values(
        ["l_returnflag", "l_linestatus"]
    )
    b = Engine(strategy="dense").execute(q, lineitem_ds).sort_values(
        ["l_returnflag", "l_linestatus"]
    )
    np.testing.assert_array_equal(a.n.values, b.n.values)
    np.testing.assert_allclose(a.s.values, b.s.values, rtol=1e-6)
