"""The four-chip cell `ssb-sf10-mesh4.power`: its entry against its files,
a rehearsal on four virtual CPU devices, and the two `mesh merge` readers
on a made-up window (a program without the fields gives them nothing)."""

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import pytest

from conftest import BENCH_DIR, ROOT
from harness import cells
from harness.window import Request, Window

CELL = "ssb-sf10-mesh4.power"
NEW_METRICS = ("collective_bytes_per_query", "shard_imbalance")


def test_the_entry_matches_its_files():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bm = json.load(f)
    entry = next(w for w in bm["workloads"] if w["name"] == CELL)
    assert (entry["config"], entry["traffic"], entry["chips"]) == (
        "ssb-sf10-mesh4", "ssb-power", 4,
    )
    config_entry = next(c for c in bm["configs"] if c["name"] == entry["config"])
    cell = cells.load_cell(ROOT, BENCH_DIR, CELL)
    assert cell.chips == cell.config["chips"] == 4
    assert cell.config["source"] == config_entry["source"]
    assert cell.config["reduced"] == config_entry["reduced"] == []
    assert cell.config["settings"] == {
        "result_cache_entries": 0, "prefer_distributed": True,
    }
    # the one-chip configuration's shapes, letter for letter
    one = cells.read_json(os.path.join(BENCH_DIR, "configs", "ssb-sf10-1chip.json"))
    for key in ("loader", "scale", "rehearse_scale", "fact_table",
                "rows_per_segment", "endpoint", "star_schema", "precision",
                "control_precision"):
        assert cell.config[key] == one[key], key
    assert set(one["guarantees"]) < set(cell.config["guarantees"])
    # the power run: the 13 queries of the two accepted mixes, copied
    theirs = [
        q
        for mix in ("ssb-flight1", "ssb-flights2-4")
        for q in cells.read_json(
            os.path.join(BENCH_DIR, "traffic", mix + ".json")
        )["queries"]
    ]
    assert cell.traffic["queries"] == theirs and len(theirs) == 13
    assert (cell.traffic["loop"], cell.traffic["clients"]) == ("closed", 1)
    assert set(cell.spec["expect_strategy"]) == {q["name"] for q in theirs}
    names = [m.name for m in cell.per_layer]
    assert set(NEW_METRICS) <= set(names)
    for other in bm["workloads"]:
        if other["name"] != CELL:
            others = cells.load_cell(ROOT, BENCH_DIR, other["name"])
            assert not set(NEW_METRICS) & {m.name for m in others.per_layer}
    for m in bm["per_layer"]:
        if m["name"] in NEW_METRICS:
            assert m["layer"] == "mesh merge" and m["workloads"] == [CELL]


def test_the_cell_rehearses_on_four_virtual_devices():
    run = subprocess.run(
        [sys.executable, os.path.join(BENCH_DIR, "run.py"), "--workload", CELL,
         "--seed", "2147487205", "--seconds", "1", "--trace", "1",
         "--rehearse"],
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT,
             "XLA_FLAGS": "--xla_force_host_platform_device_count=4"},
        capture_output=True, text=True, timeout=600, cwd=ROOT,
    )
    assert run.returncode == 0, run.stderr[-2000:]
    last = json.loads(run.stdout.strip().splitlines()[-1])
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] % 13 == 0  # whole passes of the power run
    assert last["device"] == {
        "platform": "cpu", "kind": "cpu", "count": 4, "memory_peak_bytes": 0,
    }
    metrics = last["metrics"]
    assert metrics["collective_bytes_per_query"]["value"] > 0
    assert metrics["shard_imbalance"]["value"] >= 1.0
    # the span metrics are measurements on the mesh too, one launch a request
    assert metrics["launch_ms"]["value"] > 0
    assert metrics["fetch_wait_ms"]["value"] > 0
    assert metrics["dispatches_per_query"]["value"] <= 1.0
    assert metrics["h2d_bytes_per_query"]["value"] == 0
    assert metrics["compiles_in_window"]["value"] == 0
    # a CPU rehearsal reports no device share, under any name
    assert "device_idle_pct" not in metrics and "scan_roofline" not in metrics


def _window(metrics):
    return Window(
        requests=[
            Request("q", 0, 0.0, 0.01, 200, None, m) for m in metrics
        ],
        queries={}, column_bytes={},
    )


def _reader(name):
    return cells.load_module(os.path.join(BENCH_DIR, "metrics", name + ".py"))


@pytest.mark.parametrize("name,metrics,want", [
    ("collective_bytes_per_query",
     [SimpleNamespace(collective_bytes=1200),
      SimpleNamespace(collective_bytes=0), None,
      SimpleNamespace(collective_bytes=600)], 600.0),
    # a program before the field: nothing to read, never 0
    ("collective_bytes_per_query",
     [SimpleNamespace(h2d_bytes=0), None], None),
    ("collective_bytes_per_query", [], None),
    # 3 segments in a window of one step on 4 shards; a full scan of 115
    ("shard_imbalance",
     [SimpleNamespace(shard_steps=1.0, shards=4, segments=3),
      SimpleNamespace(shard_steps=29.0, shards=4, segments=115), None],
     (4 / 3 + 116 / 115) / 2),
    # nothing in scope, or nothing stepped: not a deal, left out
    ("shard_imbalance",
     [SimpleNamespace(shard_steps=0.0, shards=4, segments=0),
      SimpleNamespace(shard_steps=0.0, shards=4, segments=2),
      SimpleNamespace(shard_steps=2.0, shards=4, segments=8)], 1.0),
    ("shard_imbalance",
     [SimpleNamespace(shard_steps=0.0, shards=4, segments=0)], None),
    ("shard_imbalance", [SimpleNamespace(segments=3), None], None),
])
def test_the_mesh_merge_readers_on_a_made_up_window(name, metrics, want):
    got = _reader(name).read(_window(metrics))
    assert got == (pytest.approx(want) if want is not None else None)
