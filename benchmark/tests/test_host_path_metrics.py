"""The eight per-layer metrics that name the host path (PR 37): on a
made-up window, on a program that has none of it, on a whole rehearsed
run with the identities the new spans have to keep, and on the recorded
trace in which they lie beside the device's operations."""

import json
import os
from types import SimpleNamespace

import pytest

import run
from conftest import BENCH_DIR
from harness import cells
from harness.window import Request, Window

EIGHT = ["scope_ms", "scope_walks_per_query", "engine_own_ms",
         "post_process_ms", "http_accept_ms", "trace_close_ms",
         "pre_launch_ms", "post_fetch_ms"]
# the three that gave their time to `scope`, `engine` and `post_process`
THREE = ["http_ms", "plan_route_ms", "lower_ms"]
NEW_SPANS = ("scope", "engine", "post_process", "http_accept")


def _reader(name):
    return cells.load_module(os.path.join(BENCH_DIR, "metrics", name + ".py"))


def _request(wall_ms, receipt):
    return Request("q", 0, 0.0, wall_ms / 1e3, 200, None,
                   SimpleNamespace(receipt=receipt))


def _receipt(spans, phases=None, close_ms=None):
    """`spans`: name -> (n, self_ms)."""
    receipt = {
        "wall_ms": sum(ms for _, ms in spans.values()),
        "spans": {k: {"n": n, "self_ms": ms} for k, (n, ms) in spans.items()},
    }
    if phases is not None:
        receipt["phases"] = dict(zip(
            ("pre_launch_ms", "in_flight_ms", "post_fetch_ms"), phases
        ))
    if close_ms is not None:
        receipt["close_ms"] = close_ms
    return receipt


def test_the_eight_on_a_made_up_window():
    """Three requests: a median over the requests for each time (a span
    a request lacks counts nothing: the second came on a kept-alive
    connection), a mean for the walks."""
    receipts = [
        _receipt({"query": (1, 0.3), "http_accept": (1, 0.4),
                  "scope": (3, 1.5), "engine": (1, 0.9),
                  "post_process": (1, 0.6), "execute": (1, 0.2)},
                 phases=(4.0, 5.0, 3.0), close_ms=0.2),
        _receipt({"query": (1, 0.2), "scope": (2, 1.0), "engine": (1, 0.5),
                  "post_process": (1, 0.7), "execute": (1, 0.1)},
                 phases=(3.0, 9.0, 2.0), close_ms=0.4),
        _receipt({"query": (1, 0.1), "http_accept": (1, 0.3),
                  "scope": (4, 2.1), "engine": (1, 0.7),
                  "post_process": (1, 0.5), "execute": (1, 0.3)},
                 phases=(5.0, 1.0, 4.0), close_ms=0.3),
    ]
    window = Window(
        requests=[_request(r["wall_ms"] + 2.0, r) for r in receipts],
        queries={}, column_bytes={},
    )
    got = {name: _reader(name).read(window) for name in EIGHT}
    assert got == {
        "scope_ms": 1.5, "scope_walks_per_query": 3.0, "engine_own_ms": 0.7,
        "post_process_ms": 0.6, "http_accept_ms": 0.3, "trace_close_ms": 0.3,
        "pre_launch_ms": 4.0, "post_fetch_ms": 3.0,
    }


def test_a_program_without_the_host_path_gives_nothing():
    """The parent of the PR that brought them stamps receipts with
    `spans` and none of the new names or fields (and some requests have
    no metrics at all): every reader returns None, where
    `span_self_ms.median_self_ms` alone would read 0.0."""
    old = _receipt({"query": (1, 0.5), "http_read": (1, 0.2),
                    "execute": (1, 2.0), "lower": (1, 1.2)})
    window = Window(
        requests=[
            _request(9.0, old),
            Request("q", 0, 0.0, 0.01, 500, None, None),
            _request(9.0, None),
            _request(9.0, {"wall_ms": 9.0, "dispatch_count": 1}),
        ],
        queries={}, column_bytes={},
    )
    for name in EIGHT:
        assert _reader(name).read(window) is None, name
    empty = Window(requests=[], queries={}, column_bytes={})
    assert all(_reader(name).read(empty) is None for name in EIGHT)


def _walk(node, above=()):
    yield node, above
    for c in node.get("children", ()):
        yield from _walk(c, above + (node["name"],))


def test_a_rehearsed_run_reports_the_eight_and_keeps_the_identities(
        capsys, monkeypatch):
    """A whole `--trace 1 --rehearse` run of flight1.  The result line
    holds the eight beside every metric it held before.  The identities
    (the parent's side of the first two is a chip run's: `PERF.md`), on
    every request:

    1. a new span took its time from the metric that read the span it now
       lies under: `scope` from the root's (`http_ms`), `lower`'s
       (`lower_ms`) or, under `engine`, `execute`'s (`plan_route_ms`);
       `engine` and `post_process` from `execute`'s; so the three's spans
       and the new three's add up to what the three's spans alone were;
    2. `http_accept` is the root's first child and starts it: `wall_ms`
       grew by what `untraced_ms` lost;
    3. `pre_launch_ms + in_flight_ms + post_fetch_ms = wall_ms`."""
    from spark_druid_olap_tpu.obs.trace import TraceRing

    windows, docs = [], []
    real = run.per_layer_metrics
    monkeypatch.setattr(
        run, "per_layer_metrics",
        lambda c, window: windows.append(window) or real(c, window),
    )
    put = TraceRing.put
    monkeypatch.setattr(
        TraceRing, "put",
        lambda self, doc: docs.append(doc) or put(self, doc),
    )
    rc = run.main(["--workload", "ssb-sf10-1chip.flight1", "--seed",
                   "2147483801", "--seconds", "1", "--trace", "1",
                   "--rehearse"])
    assert rc == 0
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last["correct"] is True
    bm = cells.read_json(os.path.join(os.path.dirname(BENCH_DIR),
                                      "BENCHMARK.json"))
    assert [m["name"] for m in bm["per_layer"]][-8:] == EIGHT
    assert set(EIGHT) <= set(last["metrics"])
    # every metric the cell reported before is still reported (the
    # device's need a device plane: not in a rehearsal)
    before = {m["name"] for m in bm["per_layer"][:-8]
              if "workloads" not in m and m["source"] != "device_trace"}
    assert before <= set(last["metrics"])
    assert last["metrics"]["scope_walks_per_query"]["value"] >= 2.0
    for name in EIGHT:
        assert last["metrics"][name]["value"] >= 0, name

    taken_from = {n: set(_reader(n).SPANS) for n in THREE}
    old_spans = set().union(*taken_from.values())
    (window,) = windows
    by_id = {d["query_id"]: d for d in docs}
    assert window.requests
    for r in window.requests:
        receipt = r.metrics.receipt
        doc = by_id[receipt["query_id"]]
        # 1: the nearest span above a new one that the parent had too
        for node, above in _walk(doc["spans"]):
            if node["name"] not in NEW_SPANS:
                continue
            nearest = next(a for a in reversed(above) if a not in NEW_SPANS)
            assert nearest in old_spans, (node["name"], above)
            if node["name"] in ("engine", "post_process"):
                assert nearest == "execute"
        spans = receipt["spans"]
        assert {"scope", "engine", "post_process"} <= set(spans)
        # 2
        first = doc["spans"]["children"][0]
        assert first["name"] == "http_accept" and first["start_ms"] == 0.0
        assert 0 < receipt["wall_ms"] <= r.wall_ms
        # 3
        assert sum(receipt["phases"].values()) == pytest.approx(
            receipt["wall_ms"], abs=0.002
        )
        assert receipt["phases"]["in_flight_ms"] > 0
        assert receipt["close_ms"] > 0


FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "flight1_host_path.xplane.pb.gz")


def test_recorded_trace_holds_the_new_spans_inside_their_requests():
    """`--seconds 0.4 --trace 1 --keep-trace` of flight1 on one TPU v5
    lite (PR 37): `sdol:scope`, `sdol:engine` and `sdol:post_process` lie
    on `/host:CPU`, each inside one `request:<query>` interval, three
    walks a request and one of them under `sdol:lower`; `http_accept` is
    made after the fact and has no mirror; each request's kernel lies
    inside its `sdol:engine`.  (Not asserted: that it lies after
    `sdol:segment_dispatch` begins.  On this trace the device plane reads
    1.3 ms early against the host plane, the module before the host's own
    `ExecuteLaunch` that enqueues it: `PERF.md` section 5.)"""
    import gzip

    from jax.profiler import ProfileData

    from harness import trace_reduce as tr

    with gzip.open(FIXTURE, "rb") as f:
        data = ProfileData.from_serialized_xspace(f.read())
    requests, spans = [], []
    for plane in data.planes:
        if plane.name != tr.HOST_PLANE:
            continue
        for line in plane.lines:
            for e in line.events:
                at = (e.name, e.start_ns, e.start_ns + e.duration_ns)
                if e.name.startswith(tr.REQUEST_PREFIX):
                    requests.append(at)
                elif e.name.startswith("sdol:"):
                    spans.append(at)
    assert len(requests) == 30
    names = {n for n, _, _ in spans}
    assert {"sdol:scope", "sdol:engine", "sdol:post_process"} <= names
    assert "sdol:http_accept" not in names
    devices, _ = tr.read_planes(FIXTURE)
    (ops,) = devices.values()

    def within(inner, outer):
        return outer[1] <= inner[1] and inner[2] <= outer[2]

    for request in requests:
        mine = [s for s in spans if within(s, request)]
        one = {n: [m for m in mine if m[0] == n] for n in names}
        assert len(one["sdol:scope"]) == 3
        (engine,) = one["sdol:engine"]
        (execute,) = one["sdol:execute"]
        (post,) = one["sdol:post_process"]
        (lower,) = one["sdol:lower"]
        assert within(engine, execute) and within(post, execute)
        assert engine[2] <= post[1]
        under = [within(s, lower) for s in one["sdol:scope"]]
        assert under.count(True) == 1
        # the lane classifier's walk comes before `execute` opens
        assert min(s[2] for s in one["sdol:scope"]) <= execute[1]
        kernel = [(n, a, b) for n, a, b in ops
                  if request[1] <= a <= request[2] and n.startswith("%pallas")]
        assert kernel and all(within(k, engine) for k in kernel)
    assert all(any(within(s, r) for r in requests) for s in spans)
