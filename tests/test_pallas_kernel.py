"""Pallas fused GroupBy kernel vs the XLA dense path (bit-parity contract).

Runs in interpret mode on the CPU test mesh; tests/test_chip_compile.py
compiles the same kernel for a described v5e."""

import jax.numpy as jnp
import numpy as np
import pytest

from spark_druid_olap_tpu.ops.groupby import dense_partial_aggregate
from spark_druid_olap_tpu.ops.pallas_groupby import pallas_partial_aggregate

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
