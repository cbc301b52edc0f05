"""Find a cell's files by the names `BENCHMARK.json` gives.

    workloads[i].name   -> <bench>/workloads/<name>.json   limits, routing
    workloads[i].config -> configs[j].file                 the deployment
    config["loader"]    -> <bench>/loaders/<loader>.py     data, system, reference
    workloads[i].traffic-> <bench>/traffic/<traffic>.json  the mix
    traffic["loop"]     -> <bench>/loops/<loop>.py         how it is offered
    per_layer[k].name   -> <bench>/metrics/<name>.json (+ <name>.py: read(window))

No name of a cell, configuration, mix or metric appears in code: a later
PR adds one by adding files and entries.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Callable, List, Optional


@dataclasses.dataclass
class Metric:
    name: str
    unit: str
    spec: dict  # the metric's own file
    read: Optional[Callable]  # read(window) from <name>.py, if it has one


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    spec: dict  # workloads/<name>.json
    config: dict
    traffic: dict
    end_to_end: List[dict]  # BENCHMARK.json entries this cell reports
    per_layer: List[Metric]
    loader: object  # module
    loop: object  # module


def read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    """Import one file by path under a name of its own (never `sys.modules`
    by a short name: two benchmarks' `ssb.py` must not collide)."""
    name = "bench_" + os.path.splitext(os.path.basename(path))[0]
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _applies(entry: dict, cell_name: str) -> bool:
    return "workloads" not in entry or cell_name in entry["workloads"]


def load_cell(root: str, bench_dir: str, cell_name: str) -> Cell:
    """`root` holds BENCHMARK.json; `bench_dir` holds the benchmark."""
    bm = read_json(os.path.join(root, "BENCHMARK.json"))
    entries = {w["name"]: w for w in bm["workloads"]}
    if cell_name not in entries:
        raise KeyError(
            f"no workload {cell_name!r} in BENCHMARK.json "
            f"(it has: {', '.join(sorted(entries))})"
        )
    entry = entries[cell_name]
    spec = read_json(os.path.join(bench_dir, "workloads", cell_name + ".json"))
    for key in ("config", "traffic"):
        if spec.get(key) != entry[key]:
            raise ValueError(
                f"workloads/{cell_name}.json says {key}={spec.get(key)!r}, "
                f"BENCHMARK.json says {entry[key]!r}"
            )
    configs = {c["name"]: c for c in bm["configs"]}
    config = read_json(os.path.join(root, configs[entry["config"]]["file"]))
    traffic = read_json(
        os.path.join(bench_dir, "traffic", entry["traffic"] + ".json")
    )
    per_layer = []
    for m in bm["per_layer"]:
        if not _applies(m, cell_name):
            continue
        base = os.path.join(bench_dir, "metrics", m["name"])
        mspec = read_json(base + ".json")
        read = None
        if os.path.exists(base + ".py"):
            read = load_module(base + ".py").read
        per_layer.append(Metric(m["name"], m["unit"], mspec, read))
    return Cell(
        name=cell_name,
        chips=int(entry["chips"]),
        spec=spec,
        config=config,
        traffic=traffic,
        end_to_end=[m for m in bm["end_to_end"] if _applies(m, cell_name)],
        per_layer=per_layer,
        loader=load_module(
            os.path.join(bench_dir, "loaders", config["loader"] + ".py")
        ),
        loop=load_module(
            os.path.join(bench_dir, "loops", traffic["loop"] + ".py")
        ),
    )
