"""Pallas fused GroupBy kernel vs the XLA dense path (bit-parity contract).

Runs in interpret mode on the CPU test mesh; tests/test_chip_compile.py
compiles the same kernel for a described v5e."""

import jax.numpy as jnp
import numpy as np
import pytest

from spark_druid_olap_tpu.ops.groupby import dense_partial_aggregate
from spark_druid_olap_tpu.ops.pallas_groupby import pallas_partial_aggregate

INTERPRET = True


def _mk(R, G, Ms, Mn, Mx, seed=0, mask_p=0.8):
    rng = np.random.default_rng(seed)
    gid = jnp.asarray(rng.integers(0, G, R).astype(np.int32))
    mask = jnp.asarray(rng.random(R) < mask_p)
    sv = jnp.asarray(
        (rng.random((R, Ms)) * np.asarray(mask)[:, None]).astype(np.float32)
    )
    mmv = jnp.asarray(rng.random((R, Mn + Mx)).astype(np.float32))
    mmm = jnp.asarray(rng.random((R, Mn + Mx)) < 0.9)
    return gid, mask, sv, mmv, mmm


@pytest.mark.parametrize(
    "R,G,Ms,Mn,Mx",
    [
        (4096, 12, 3, 0, 0),      # Q1 shape: tiny G, no extrema
        (8192, 300, 4, 2, 1),     # mid G with min/max
        (8192, 700, 2, 1, 1),     # G > one group tile => 2D grid
        (1024, 1, 1, 0, 0),       # degenerate single group
    ],
)
def test_pallas_matches_dense(R, G, Ms, Mn, Mx):
    gid, mask, sv, mmv, mmm = _mk(R, G, Ms, Mn, Mx)
    want = dense_partial_aggregate(
        gid, mask, sv, mmv, mmm,
        num_groups=G, block_rows=1024, num_min=Mn, num_max=Mx,
    )
    got = pallas_partial_aggregate(
        gid, mask, sv, mmv, mmm,
        num_groups=G, num_min=Mn, num_max=Mx, interpret=INTERPRET,
    )
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(got[1]), np.asarray(want[1]), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(got[2]), np.asarray(want[2]), rtol=1e-6)


def test_pallas_all_masked():
    gid, mask, sv, mmv, mmm = _mk(2048, 10, 2, 1, 1, mask_p=0.0)
    sums, mins, maxs = pallas_partial_aggregate(
        gid, jnp.zeros_like(mask), sv * 0, mmv, mmm,
        num_groups=10, num_min=1, num_max=1, interpret=INTERPRET,
    )
    assert float(np.abs(np.asarray(sums)).sum()) == 0.0
    assert np.isinf(np.asarray(mins)).all() and (np.asarray(mins) > 0).all()
    assert np.isinf(np.asarray(maxs)).all() and (np.asarray(maxs) < 0).all()


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
