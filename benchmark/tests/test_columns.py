"""The byte count of `scan_roofline`'s numerator: each of the 13
queries' column lists against the catalog at `rehearse_scale`."""

import json
import os

import pytest

from conftest import BENCH_DIR, ROOT
from harness import cells
from harness.window import Request, Window


def _cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        names = [w["name"] for w in json.load(f)["workloads"]]
    return [cells.load_cell(ROOT, BENCH_DIR, n) for n in names]


@pytest.fixture(scope="module")
def loaded():
    all_cells = _cells()
    config = all_cells[0].config
    system = all_cells[0].loader.start_system(
        config, 7, config["rehearse_scale"], lambda **line: None
    )
    yield all_cells, system
    system.close()


def test_every_query_has_a_column_list_the_catalog_knows(loaded):
    all_cells, system = loaded
    widths = system.column_bytes()
    assert widths["__valid"] == 1
    seen = set()
    for cell in all_cells:
        scan = cells.load_module(
            os.path.join(BENCH_DIR, "metrics", "scan_roofline.py")
        )
        for q in cell.traffic["queries"]:
            seen.add(q["name"])
            assert q["columns"], q["name"]
            for c in q["columns"]:
                assert c in widths, f"{q['name']}: no resident column {c!r}"
            # every listed column is one the SQL text names (or the mask)
            for c in q["columns"]:
                assert c.startswith("__") or c in q["sql"], (q["name"], c)
            status, body, m = system.send(q)
            assert status == 200
            per_row = sum(widths[c] for c in q["columns"])
            window = Window(
                requests=[], column_bytes=widths,
                queries={x["name"]: x for x in cell.traffic["queries"]},
            )
            r = Request(q["name"], 0, 0.0, 1.0, status, body, m)
            assert scan.scan_bytes(window, [r]) == m.rows_scanned * per_row
            # the least bytes never pass what the program says it read
            if m.rows_scanned:
                assert m.rows_scanned * per_row <= m.bytes_scanned, q["name"]
    assert len(seen) == 13
