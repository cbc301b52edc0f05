"""plan/calibrate.py times what the chooser names (PR 30).

The dense class's constant had been measured on the XLA one-hot scan while
a TPU ran the class as the Pallas kernel, 12 x apart.  These run the sweep
for real at a few thousand rows (seconds on the CPU) and watch which kernels
it reaches, and what its writer does to a file that is already there.
"""

import json

import jax
import pytest

from spark_druid_olap_tpu.ops import groupby, pallas_groupby
from spark_druid_olap_tpu.plan import calibrate as C
from spark_druid_olap_tpu.plan import cost

ROWS = 1 << 12


def _spy(patch, module, name, calls, key):
    """Record each distinct call of `module.name` (a kernel is traced once
    a row count: twice a probe), in order."""
    real = getattr(module, name)

    def wrapped(*args, **kwargs):
        seen = key(args, kwargs)
        if seen not in calls:
            calls.append(seen)
        return real(*args, **kwargs)

    patch.setattr(module, name, wrapped)


@pytest.fixture(scope="module")
def sweep():
    """sweep(pallas) -> (the file's dict, every kernel the sweep reached as
    it was traced): one whole sweep a routing, shared by the tests."""
    done = {}

    def run(pallas: bool):
        if pallas in done:
            return done[pallas]
        calls = {"dispatch": [], "pallas": [], "xla_dense": [], "scatter": []}
        with pytest.MonkeyPatch.context() as patch:
            patch.setattr(cost, "_pallas_ok", lambda: pallas)
            _spy(patch, groupby, "partial_aggregate", calls["dispatch"],
                 lambda a, k: (k["strategy"], k["num_groups"]))
            _spy(patch, pallas_groupby, "pallas_partial_aggregate",
                 calls["pallas"], lambda a, k: (k["num_groups"], k["interpret"]))
            _spy(patch, groupby, "dense_partial_aggregate",
                 calls["xla_dense"], lambda a, k: k["num_groups"])
            _spy(patch, groupby, "scatter_partial_aggregate",
                 calls["scatter"], lambda a, k: k["num_groups"])
            # the mesh probe is its own entry point (64 MiB a device)
            patch.setattr(C, "measure_mesh", lambda: {})
            done[pallas] = C.calibrate(rows=ROWS, save_path=None), calls
        return done[pallas]

    return run


@pytest.mark.parametrize("pallas", [True, False], ids=["tpu-routing", "cpu"])
def test_dense_constant_times_the_kernel_the_chooser_names(
    monkeypatch, sweep, pallas
):
    """`cost_per_row_dense` is the price of `concrete_kernel("dense", g)`:
    with the compiled kernel there to route to it is the Pallas kernel
    (interpret mode on this backend), without it the XLA one-hot scan;
    each at both probed tile counts, through `partial_aggregate`."""
    monkeypatch.setattr(cost, "_pallas_ok", lambda: pallas)
    probes = list(C.DENSE_PROBE_GROUPS)
    named = [cost.concrete_kernel("dense", g) for g in probes]
    assert set(named) == {"pallas" if pallas else "dense"}
    out, watched = sweep(pallas)
    assert out["dense_kernel"] == named[0]
    assert sorted(out["dense_us_per_row"]) == sorted(str(g) for g in probes)
    timed = [c for c in watched["dispatch"] if c[0] != "segment"]
    assert timed == list(zip(named, probes))
    if pallas:
        assert watched["pallas"] == [(g, True) for g in probes]
        assert not watched["xla_dense"]
    else:
        assert watched["xla_dense"] == probes and not watched["pallas"]
    # the per-tile constant is the model's own form fitted through both
    tiles = [cost._g_tiles(g) for g in probes]
    assert tiles == [1, 7]
    fit = sum(
        out["dense_us_per_row"][str(g)] * t for g, t in zip(probes, tiles)
    ) / sum(t * t for t in tiles)
    assert out["cost_per_row_dense"] == pytest.approx(fit)
    assert out["cost_per_row_dense"] > 0 and out["partial"] is False


def test_scatter_constants_time_the_masked_scatter(sweep):
    """Both scatter anchors (1,024 groups, 2^20) and the per-group state
    cost behind them go through `scatter_partial_aggregate` with a filter
    mask, the trash-slot write phase B pays, not a bare `segment_sum`."""
    out, watched = sweep(False)
    assert [c for c in watched["dispatch"] if c[0] == "segment"] == [
        ("segment", 1024), ("segment", 1 << 20),
    ]
    assert watched["scatter"][:2] == [1024, 1 << 20]
    assert out["scatter_lo_groups"] == 1024
    assert out["scatter_hi_groups"] == 1 << 20
    assert out["cost_per_row_scatter_hi"] >= out["cost_per_row_scatter"] > 0


def _sweep_into(tmp_path):
    out = C.calibrate(
        rows=ROWS, save_path=str(tmp_path / "calibration.json"),
        budget_s=0.0,
    )
    side = tmp_path / ("calibration.%s.json" % out["platform"])
    return out, json.loads(side.read_text()), json.loads(
        (tmp_path / "calibration.json").read_text()
    )


HAND_KEPT = {
    "vmem_budget_bytes": 16777216,
    "collective_bytes_per_us": 67768.4,
    "collective_measured_on": "4 x TPU v5 lite (2x2), a four-chip call",
}


@pytest.mark.parametrize("where", ["sidecar", "primary"])
def test_rerun_keeps_the_keys_the_sweep_does_not_measure(tmp_path, where):
    """A one-chip sweep measures no mesh and no VMEM: what the file held
    for the same device stays (PR 22 re-added `vmem_budget_bytes` by hand),
    in the primary file and the sidecar alike; what it does measure is
    replaced."""
    device = str(jax.devices()[0])
    old = {"device": device, "cost_per_row_dense": 123.0, **HAND_KEPT}
    name = (
        "calibration.%s.json" % jax.devices()[0].platform
        if where == "sidecar" else "calibration.json"
    )
    (tmp_path / name).write_text(json.dumps(old))
    out, side, primary = _sweep_into(tmp_path)
    assert side == primary == out
    for k, v in HAND_KEPT.items():
        assert out[k] == v
    assert out["cost_per_row_dense"] != 123.0
    assert out["partial"] is True and out["device"] == device


def test_rerun_takes_nothing_from_another_device(tmp_path):
    old = {"device": "TPU imaginary9", **HAND_KEPT}
    (tmp_path / "calibration.json").write_text(json.dumps(old))
    out, side, primary = _sweep_into(tmp_path)
    assert side == primary == out
    assert not set(HAND_KEPT) & set(out)


def test_a_mesh_sweep_replaces_the_kept_mesh_reading(tmp_path, monkeypatch):
    """A sweep that does measure the mesh brings its own provenance, so an
    inherited `collective_measured_on` never describes a newer number."""
    assert len(jax.devices()) > 1, "conftest must provide CPU devices"
    fresh = {
        "collective_bytes_per_us": 5.0, "cost_dispatch_us": 7.0,
        "collective_measured_on": "this sweep",
    }
    monkeypatch.setattr(C, "measure_mesh", lambda: dict(fresh))
    old = {"device": str(jax.devices()[0]), **HAND_KEPT}
    (tmp_path / "calibration.json").write_text(json.dumps(old))
    out = C.calibrate(
        rows=ROWS, save_path=str(tmp_path / "calibration.json"),
        budget_s=3600.0,
    )
    assert {k: out[k] for k in fresh} == fresh
    assert out["vmem_budget_bytes"] == HAND_KEPT["vmem_budget_bytes"]
