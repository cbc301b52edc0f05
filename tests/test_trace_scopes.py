"""`tools/trace_scopes.py` on the benchmark's recorded flight1 trace
(`--seconds 0.4 --trace 1 --keep-trace` on one TPU v5 lite, PR 25)."""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

from tools import trace_scopes

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURE = os.path.join(
    ROOT, "benchmark", "tests", "fixtures", "flight1_spans.xplane.pb.gz"
)


@pytest.mark.skipif(
    importlib.util.find_spec("tensorflow") is None,
    reason="the xplane schema comes with tensorflow",
)
def test_device_operations_by_scope_on_the_recorded_trace():
    # in a process of its own, as the tool is run: tensorflow stays out of
    # the test workers
    done = subprocess.run(
        [sys.executable, "-c",
         "import json, sys; from tools import trace_scopes; "
         "print(json.dumps(trace_scopes.summarize(sys.argv[1])))", FIXTURE],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    out = json.loads(done.stdout.splitlines()[-1])
    ops = out["device_ops"]
    # the largest operation is the kernel, named by the scope it was
    # traced under and the line that calls it
    assert ops[0]["op"] == "%pallas_partial_aggregate"
    assert ops[0]["scope"] == "sdol.partial_agg"
    assert ops[0]["source"].startswith("pallas_groupby.py:")
    # the relayout beside it (PERF.md's `%copy`) sits under the same scope
    assert ("%copy", "sdol.partial_agg") in {(o["op"], o["scope"]) for o in ops}
    # self time is total less what an operation holds
    assert all(0 <= o["self_s"] <= o["total_s"] for o in ops)
    assert [o["self_s"] for o in ops] == sorted(
        (o["self_s"] for o in ops), reverse=True
    )
    # per query: each request's operations, and their time on the device
    assert set(out["by_request"]) == {
        "request:q1_1", "request:q1_2", "request:q1_3"
    }
    for d in out["by_request"].values():
        assert d["requests"] > 0 and d["device_self_s_per_request"] > 0
    # the program's spans, by name, every one inside a request
    host = out["host"]
    assert host["outside_requests"] == 0
    assert {"sdol:query", "sdol:http_read", "sdol:device_fetch"} <= set(
        host["spans"]
    )


def test_usage_without_a_trace(capsys):
    assert trace_scopes.main([]) == 2
    assert "trace_scopes.py <trace" in capsys.readouterr().err
