import os

import pytest

from harness import trace_reduce as tr

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "flight1_short.xplane.pb.gz")


def test_union_and_gaps():
    merged = tr.union([(5, 7), (0, 2), (1, 3), (7, 8), (20, 21)])
    assert merged == [(0, 3), (5, 8), (20, 21)]
    assert tr.gaps(merged, 0, 30) == [(3, 5), (8, 20), (21, 30)]
    assert tr.clip([(0, 10), (20, 30)], 5, 25) == [(5, 10), (20, 25)]


def test_op_family():
    text = "%pallas_partial_aggregate.4 = (f32[2,128]{1,0}) custom-call(s32[8] %copy.1)"
    assert tr.op_family(text) == "%pallas_partial_aggregate"
    assert tr.op_family("%while = (s32[]) while(%tuple)") == "%while"
    assert tr.op_family("%fusion.v2") == "%fusion.v2"


def test_made_up_trace():
    """Two requests of 100 ns with 50 ns between; the device runs 30 ns in
    the first (two overlapping ops), 10 ns between, nothing in the second."""
    devices = {"/device:TPU:0": [
        ("fusion.1", 10, 30), ("fusion.2", 20, 40), ("copy", 120, 130),
        ("before", -50, -10),  # outside the traced window: clipped away
    ]}
    requests = [("request:a", 0, 100), ("request:b", 150, 250)]
    out = tr.reduce_events(devices, requests)
    assert out["window_s"] == pytest.approx(250e-9)
    assert out["busy_s"] == pytest.approx(40e-9)  # union, not the sum of 50
    ops = dict(out["device_ops"])
    assert ops["fusion.1"] == pytest.approx(20e-9)
    assert ops["copy"] == pytest.approx(10e-9)
    assert "before" not in ops
    idle = dict(out["idle_gaps"])
    assert idle["request:a"] == pytest.approx(70e-9)
    assert idle["request:b"] == pytest.approx(100e-9)
    assert idle[tr.BETWEEN] == pytest.approx(40e-9)
    assert sum(idle.values()) + out["busy_s"] == pytest.approx(out["window_s"])


def test_no_device_plane_gives_nothing():
    assert tr.reduce_events({}, [("request:a", 0, 1)]) is None
    assert tr.reduce_events({"/device:TPU:0": [("x", 0, 1)]}, []) is None


def test_recorded_trace():
    """A trace recorded on one TPU v5 lite by PR 24: `--seconds 0.4
    --trace 1` of the three-query mix (numbers in README.md)."""
    out = tr.reduce_trace(FIXTURE)
    assert out is not None and out["devices"] == 1
    assert out["requests"] == 18
    # read by hand from the same file: 18 programs, 6 of 19.7 ms (q1_1)
    # and 12 of 3.8 ms, in a window of 375 ms
    assert out["window_s"] == pytest.approx(0.3748, abs=1e-3)
    assert out["busy_s"] == pytest.approx(0.1641, abs=1e-3)
    assert out["device_ops"][0][0].startswith("%pallas_partial_aggregate")
    idle_share = 1 - out["busy_s"] / out["window_s"]
    assert 0.0 < idle_share < 1.0
    assert len(out["device_ops"]) <= tr.TOP and out["device_ops"][0][1] > 0
    names = {name for name, _ in out["idle_gaps"]}
    assert any(n.startswith(tr.REQUEST_PREFIX) for n in names)
    idle_s = out["window_s"] - out["busy_s"]
    assert sum(s for _, s in out["idle_gaps"]) <= idle_s * (1 + 1e-9)


def test_device_metrics_read_the_reduced_trace(capsys):
    """`scan_roofline` and `device_idle_pct` from the recorded trace: the
    least streaming time at the chips' bandwidth together over the mean
    busy time; nothing where there is no trace."""
    import json
    from types import SimpleNamespace

    from harness import cells
    from harness.window import Request, Window

    bench = os.path.dirname(os.path.dirname(os.path.dirname(FIXTURE)))
    metrics = os.path.join(bench, "metrics")
    roofline = cells.load_module(os.path.join(metrics, "scan_roofline.py")).read
    idle = cells.load_module(os.path.join(metrics, "device_idle_pct.py")).read
    peaks = cells.read_json(os.path.join(bench, "harness", "peaks.json"))
    m = SimpleNamespace(rows_scanned=1 << 19, bytes_scanned=13 << 19)
    window = Window(
        requests=[], queries={"q": {"columns": ["a", "b"]}},
        column_bytes={"a": 4, "b": 1},
        traced=[Request("q", 0, 0.0, 1.0, 200, None, m)] * 18,
    )
    assert roofline(window) is None and idle(window) is None
    window.trace = tr.reduce_trace(FIXTURE)
    window.peaks = peaks["TPU v5 lite"]
    least_s = 18 * 5 * (1 << 19) / 819e9
    assert roofline(window) == pytest.approx(100 * least_s / window.trace["busy_s"])
    assert 0 < roofline(window) < 100
    assert idle(window) == pytest.approx(
        100 * (1 - window.trace["busy_s"] / window.trace["window_s"])
    )
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["phase"] == "scan_roofline" and line["devices"] == 1
    # four chips stream four times as fast: the same bytes are a quarter
    window.trace = {**window.trace, "devices": 4}
    assert roofline(window) == pytest.approx(
        25 * least_s / window.trace["busy_s"]
    )
