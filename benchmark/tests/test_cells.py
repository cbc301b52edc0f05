"""A later PR adds a cell, a configuration, a mix and a per-layer metric
as new files and new entries of BENCHMARK.json, and edits no file of the
benchmark that is there."""

import hashlib
import json
import os
import shutil
import subprocess
import sys

from conftest import BENCH_DIR, ROOT
from harness import cells


def _hashes(top):
    out = {}
    for d, _, files in os.walk(top):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, top)] = hashlib.sha256(fh.read()).hexdigest()
    return out


def test_additions_take_new_files_only(tmp_path):
    root = tmp_path / "checkout"
    bench = root / "benchmark"
    shutil.copytree(
        BENCH_DIR, bench,
        ignore=shutil.ignore_patterns("tests", "__pycache__"),
    )
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root / "BENCHMARK.json")
    before = _hashes(bench)

    bm = json.loads((root / "BENCHMARK.json").read_text())
    old_cell = bm["workloads"][0]
    old_config = bm["configs"][0]

    # a configuration: its file of sizes (the loader is found by name)
    config = json.loads((root / old_config["file"]).read_text())
    config["name"] = "added-config"
    (bench / "configs" / "added-config.json").write_text(json.dumps(config))
    bm["configs"].append({**old_config, "name": "added-config",
                          "file": "benchmark/configs/added-config.json"})
    # a mix: a data file the one generator reads
    mix = json.loads(
        (bench / "traffic" / (old_cell["traffic"] + ".json")).read_text()
    )
    mix["queries"] = mix["queries"][:2]
    (bench / "traffic" / "added-mix.json").write_text(json.dumps(mix))
    # a cell: its file and its entry
    spec = json.loads(
        (bench / "workloads" / (old_cell["name"] + ".json")).read_text()
    )
    spec.update(config="added-config", traffic="added-mix")
    (bench / "workloads" / "added-config.added-mix.json").write_text(
        json.dumps(spec)
    )
    bm["workloads"].append({
        **old_cell, "name": "added-config.added-mix",
        "config": "added-config", "traffic": "added-mix",
    })
    # a per-layer metric: its file and a reader of its own
    (bench / "metrics" / "added_requests.json").write_text(json.dumps(
        {"name": "added_requests", "unit": "count",
         "reader": {"code": "added_requests.py"}}
    ))
    (bench / "metrics" / "added_requests.py").write_text(
        "def read(window):\n    return len(window.requests) or None\n"
    )
    bm["per_layer"].append({
        "name": "added_requests", "unit": "count", "better": "higher",
        "source": "program_counter", "layer": bm["per_layer"][0]["layer"],
        "moves": "queries_per_s", "workloads": ["added-config.added-mix"],
    })
    (root / "BENCHMARK.json").write_text(json.dumps(bm))

    cell = cells.load_cell(str(root), str(bench), "added-config.added-mix")
    assert cell.config["name"] == "added-config"
    assert len(cell.traffic["queries"]) == 2
    assert "added_requests" in [m.name for m in cell.per_layer]
    old = cells.load_cell(str(root), str(bench), old_cell["name"])
    assert "added_requests" not in [m.name for m in old.per_layer]

    # and the added cell runs, end to end, from the copy
    run = subprocess.run(
        [sys.executable, str(bench / "run.py"), "--workload",
         "added-config.added-mix", "--seed", "5", "--seconds", "1",
         "--trace", "1", "--rehearse"],
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": ROOT},
        capture_output=True, text=True, timeout=600,
    )
    assert run.returncode == 0, run.stderr[-2000:]
    last = json.loads(run.stdout.strip().splitlines()[-1])
    assert last["correct"] is True
    assert last["metrics"]["added_requests"]["value"] == last["attempted"]
    assert last["device"]["platform"] == "cpu"
    # a CPU rehearsal reports no device share, under any name
    assert "device_idle_pct" not in last["metrics"]
    assert "scan_roofline" not in last["metrics"]
    assert "busy_s" not in last["device"]

    after = _hashes(bench)
    assert {k: v for k, v in after.items() if k in before} == before
