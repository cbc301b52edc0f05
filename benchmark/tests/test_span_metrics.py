"""The six per-layer metrics that read the program's span tree
(`receipt["spans"]`): on a made-up window, on whole rehearsed runs, and
the recorded trace in which the program's spans lie on the profiler's
clock beside the device's operations."""

import gzip
import json
import os
from types import SimpleNamespace

import pytest

import run
from conftest import BENCH_DIR
from harness import cells
from harness import trace_reduce as tr
from harness.window import Request, Window

SIX = ["http_ms", "plan_route_ms", "untraced_ms", "lower_ms", "launch_ms",
       "fetch_wait_ms"]
FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "flight1_spans.xplane.pb.gz")


def _reader(name):
    return cells.load_module(
        os.path.join(BENCH_DIR, "metrics", name + ".py")
    )


def _request(wall_ms, spans, receipt_wall_ms=None):
    receipt = None
    if spans is not None:
        receipt = {
            "wall_ms": receipt_wall_ms,
            "spans": {k: {"n": 1, "self_ms": v} for k, v in spans.items()},
        }
    m = SimpleNamespace(receipt=receipt)
    return Request("q", 0, 0.0, wall_ms / 1e3, 200, None, m)


def test_the_six_on_a_made_up_window():
    """Three requests; each metric is the median of the per-request sums
    of its spans' self time, a span a request lacks counts nothing."""
    spans = [
        {"query": 0.3, "http_read": 0.2, "admission": 0.01, "lane": 0.01,
         "respond": 0.5, "plan": 0.4, "execute": 0.6, "route": 0.05,
         "lower": 0.2, "program_lookup": 0.1, "segment_dispatch": 1.5,
         "device_fetch": 4.0, "finalize": 0.4, "h2d": 0.03},
        {"query": 0.5, "http_read": 0.3, "respond": 0.7, "plan": 0.5,
         "sql_parse": 2.0, "route": 0.1, "execute": 0.9, "lower": 0.3,
         "program_lookup": 0.3, "adaptive_kept": 0.2, "adaptive_probe": 0.4,
         "arena_build": 0.1, "segment_dispatch": 2.0, "device_fetch": 190.0,
         "finalize": 1.0},
        {"query": 0.1, "http_read": 0.1, "respond": 0.1, "plan": 0.1,
         "execute": 0.1, "lower": 0.1, "program_lookup": 0.1,
         "sparse_dispatch": 0.5, "device_fetch": 1.0},
    ]
    walls = [sum(s.values()) for s in spans]
    window = Window(
        requests=[
            _request(w + extra, s, w)
            for s, w, extra in zip(spans, walls, (1.0, 3.0, 2.0))
        ],
        queries={}, column_bytes={},
    )
    got = {name: _reader(name).read(window) for name in SIX}
    assert got["http_ms"] == pytest.approx(0.3 + 0.2 + 0.01 + 0.01 + 0.5)
    assert got["plan_route_ms"] == pytest.approx(0.4 + 0.6 + 0.05)
    assert got["lower_ms"] == pytest.approx(0.3)
    assert got["launch_ms"] == pytest.approx(1.5)
    assert got["fetch_wait_ms"] == pytest.approx(4.0)
    assert got["untraced_ms"] == pytest.approx(2.0)
    # every span belongs to one of the six, to `finalize`, or is one of
    # the remaining (here `h2d`): per request they add up to the wall
    covered = set().union(*(
        _reader(n).SPANS for n in SIX if n != "untraced_ms"
    ))
    for s, w in zip(spans, walls):
        rest = {k: v for k, v in s.items()
                if k not in covered and k != "finalize"}
        assert set(rest) <= {"h2d"}
        assert sum(v for k, v in s.items() if k in covered) \
            + s.get("finalize", 0.0) + sum(rest.values()) \
            == pytest.approx(w)


def test_a_program_without_span_receipts_gives_nothing():
    """The parent of the PR that brought `spans` stamps receipts without
    them (and some requests have no metrics at all): the readers return
    None, the line leaves the metrics out, nothing raises."""
    old = SimpleNamespace(receipt={"wall_ms": 9.0, "dispatch_count": 1})
    window = Window(
        requests=[
            Request("q", 0, 0.0, 0.01, 200, None, old),
            Request("q", 0, 0.0, 0.01, 500, None, None),
            Request("q", 0, 0.0, 0.01, 200, None,
                    SimpleNamespace(receipt=None)),
        ],
        queries={}, column_bytes={},
    )
    for name in SIX:
        assert _reader(name).read(window) is None
    assert _reader("http_ms").read(
        Window(requests=[], queries={}, column_bytes={})
    ) is None


@pytest.mark.parametrize("cell", [
    "ssb-sf10-1chip.flight1", "ssb-sf10-1chip.flights2-4",
])
def test_a_rehearsed_run_reports_the_six_and_they_add_up(capsys, monkeypatch,
                                                         cell):
    """A whole `--trace 1 --rehearse` run: the result line holds the six
    beside the accepted metrics, and for every request of the window the
    six's spans, `finalize` and the remaining spans add up to the
    receipt's `wall_ms` (the root span), to rounding."""
    windows = []
    real = run.per_layer_metrics
    monkeypatch.setattr(
        run, "per_layer_metrics",
        lambda c, window: windows.append(window) or real(c, window),
    )
    rc = run.main(["--workload", cell, "--seed", "2147483777", "--seconds",
                   "1", "--trace", "1", "--rehearse"])
    assert rc == 0
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last["correct"] is True
    assert set(SIX) <= set(last["metrics"])
    assert {"serve_plan_ms", "dispatch_ms", "finalize_ms"} <= set(last["metrics"])
    assert all(last["metrics"][n]["unit"] == "ms" for n in SIX)
    (window,) = windows
    covered = set().union(*(
        _reader(n).SPANS for n in SIX if n != "untraced_ms"
    ))
    assert window.requests
    for r in window.requests:
        receipt = r.metrics.receipt
        spans = receipt["spans"]
        assert {"http_read", "respond", "plan", "execute"} <= set(spans)
        six = sum(v["self_ms"] for k, v in spans.items() if k in covered)
        rest = sum(v["self_ms"] for k, v in spans.items()
                   if k not in covered)  # finalize and the remaining
        assert six + rest == pytest.approx(
            receipt["wall_ms"], abs=0.001 * len(spans)
        )
        assert 0 < receipt["wall_ms"] <= r.wall_ms  # untraced_ms >= 0


def _host_events(path):
    from jax.profiler import ProfileData

    with gzip.open(path, "rb") as f:
        data = ProfileData.from_serialized_xspace(f.read())
    requests, spans, threads = [], [], []
    for plane in data.planes:
        if plane.name != tr.HOST_PLANE:
            continue
        for line in plane.lines:
            mine = []
            for e in line.events:
                at = (e.name, e.start_ns, e.start_ns + e.duration_ns)
                if e.name.startswith(tr.REQUEST_PREFIX):
                    requests.append(at)
                elif e.name.startswith("sdol:"):
                    mine.append(at)
            spans += mine
            threads.append(sorted(mine, key=lambda s: s[1]))
    return requests, spans, threads


def test_recorded_trace_holds_the_programs_spans_inside_their_requests():
    """`--seconds 0.4 --trace 1 --keep-trace` of flight1 on one TPU v5
    lite (PR 25): the program's spans lie on `/host:CPU` as `sdol:<name>`,
    each inside one `request:<query>` interval of the client, on the clock
    of the device plane; `trace_reduce` reduces the trace as before."""
    requests, spans, threads = _host_events(FIXTURE)
    assert requests and spans
    names = {n for n, _, _ in spans}
    assert {"sdol:query", "sdol:http_read", "sdol:plan", "sdol:execute",
            "sdol:program_lookup", "sdol:segment_dispatch",
            "sdol:device_fetch", "sdol:finalize", "sdol:respond"} <= names
    assert not any(n.startswith(tr.REQUEST_PREFIX) for n in names)
    for name, a, b in spans:
        assert any(lo <= a and b <= hi for _, lo, hi in requests), name
    # one root per request, and each request's device program starts
    # inside its root (the clocks are one)
    roots = sorted((a, b) for n, a, b in spans if n == "sdol:query")
    assert len(roots) == len(requests)
    # the tree's root is back-dated to `http_read`'s start, but its mirror
    # opens where the trace does: on the profiler's clock `sdol:http_read`
    # comes first and the request's `sdol:query` is the next event of its
    # thread, begun after the read ended and inside the same request
    reads = 0
    for mine in threads:
        for (name, _, b), (after, a2, _) in zip(mine, mine[1:]):
            if name == "sdol:http_read":
                reads += 1
                assert after == "sdol:query" and b <= a2
                assert any(lo <= b and a2 <= hi for _, lo, hi in requests)
    assert reads == len(requests)
    devices, _ = tr.read_planes(FIXTURE)
    (ops,) = devices.values()
    kernel = [a for n, a, _ in ops if n.startswith("%pallas_partial_aggregate")]
    assert kernel and all(
        any(lo <= a <= hi for lo, hi in roots) for a in kernel
    )
    out = tr.reduce_trace(FIXTURE)
    assert out["devices"] == 1 and out["requests"] == len(requests)
    assert 0 < out["busy_s"] < out["window_s"]
    assert out["device_ops"][0][0].startswith("%pallas_partial_aggregate")
    assert all(n.startswith(tr.REQUEST_PREFIX) or n == tr.BETWEEN
               for n, _ in out["idle_gaps"])
