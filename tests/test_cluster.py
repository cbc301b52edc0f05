"""Cluster tier (ISSUE 16): broker + N historicals over a shared
snapshot store — assignment math, the partial-state wire codec, and the
scatter/gather path serving EXACT answers through real HTTP.

The process model under test: historicals are in-process
`HistoricalNode`s (own `TPUOlapContext` mmap-booted from the broker's
`storage_dir`, read-only: no fsync, no flush sweep, no compaction)
behind real `OlapServer`s on ephemeral ports; the broker is a normal
durable context with a `ClusterClient` attached.  Chaos lives in
test_cluster_chaos.py; this file pins the sunny-day contracts.
"""

import json

import numpy as np
import pytest

import spark_druid_olap_tpu as sd
from spark_druid_olap_tpu.cluster import (
    Assignment,
    ClusterClient,
    HistoricalNode,
    build_assignment,
    decode_state,
    encode_state,
    load_assignment,
    rebalance,
    replicas_for,
    save_assignment,
    WireDecodeError,
)
from spark_druid_olap_tpu.exec.state import PartialState
from spark_druid_olap_tpu.resilience import injector

T0 = int(np.datetime64("2023-01-01", "ms").astype(np.int64))
DAY = 86_400_000


@pytest.fixture(autouse=True)
def _disarm():
    injector().disarm()
    yield
    injector().disarm()


def _cols(n=4000, seed=3):
    rng = np.random.default_rng(seed)
    return {
        "city": rng.choice(
            np.array(["austin", "boston", "chicago", "denver"], dtype=object),
            n,
        ),
        "qty": rng.integers(1, 100, n).astype(np.int64),
        "rev": rng.random(n).astype(np.float32),
        "ts": T0 + rng.integers(0, 30, n) * DAY,
    }


def _mk_broker(d, n=4000, rows_per_segment=1000, **cfg_kw):
    ctx = sd.TPUOlapContext(
        sd.SessionConfig(storage_dir=str(d), **cfg_kw)
    )
    ctx.register_table(
        "ev", _cols(n), dimensions=["city"], metrics=["qty", "rev"],
        time_column="ts", rows_per_segment=rows_per_segment,
    )
    return ctx


class _Cluster:
    """Broker + N in-process historicals over one directory."""

    def __init__(self, d, n_nodes=2, replication=2, **cfg_kw):
        self.broker = _mk_broker(d, **cfg_kw)
        self.nodes = {}
        for i in range(n_nodes):
            h = HistoricalNode(f"h{i}", str(d)).start()
            self.nodes[h.node_id] = h
        self.client = ClusterClient(
            self.broker,
            nodes={nid: h.url for nid, h in self.nodes.items()},
            replication=replication,
        ).attach()

    def close(self):
        self.client.close()
        for h in self.nodes.values():
            h.shutdown()


@pytest.fixture()
def cluster(tmp_path):
    c = _Cluster(tmp_path)
    yield c
    c.close()


# -- wire codec ---------------------------------------------------------------


def _state(g=5, a=3, m=2, w=8):
    rng = np.random.default_rng(0)
    return PartialState(
        rng.random((g, a)),
        rng.random((g, m)),
        rng.random((g, m)),
        {"hll$u": rng.integers(0, 255, (g, w)).astype(np.uint8)},
    )


def test_wire_roundtrip_preserves_dtype_shape_values():
    st = _state()
    out = decode_state(json.loads(json.dumps(encode_state(st))))
    assert isinstance(out, PartialState)
    for k in ("sums", "mins", "maxs"):
        assert getattr(out, k).dtype == getattr(st, k).dtype
        assert np.array_equal(getattr(out, k), getattr(st, k))
    assert np.array_equal(st.sketches["hll$u"], out.sketches["hll$u"])
    # decoded arrays must be writable: the ⊕ accumulates in place
    out.sums[0, 0] = 7.0


def test_wire_decode_rejects_torn_and_malformed():
    doc = encode_state(_state())
    with pytest.raises(WireDecodeError):
        decode_state(None)
    bad = json.loads(json.dumps(doc))
    bad["sums"]["data"] = bad["sums"]["data"][: len(bad["sums"]["data"]) // 2]
    with pytest.raises(WireDecodeError):
        decode_state(bad)
    bad2 = json.loads(json.dumps(doc))
    bad2["mins"]["shape"] = [999, 999]  # byte count vs shape mismatch
    with pytest.raises(WireDecodeError):
        decode_state(bad2)


# -- assignment ---------------------------------------------------------------


def test_hrw_deterministic_and_clamped():
    nodes = ["h0", "h1", "h2"]
    a = replicas_for("seg-1", nodes, 2)
    assert a == replicas_for("seg-1", list(reversed(nodes)), 2)
    assert len(a) == 2 and len(set(a)) == 2
    assert len(replicas_for("seg-1", ["h0"], 3)) == 1  # clamped


def test_hrw_minimal_movement_on_membership_change():
    sids = [f"s{i}" for i in range(64)]
    before = {s: replicas_for(s, ["h0", "h1", "h2"], 2) for s in sids}
    after = {s: replicas_for(s, ["h0", "h1"], 2) for s in sids}
    for s in sids:
        # survivors keep every segment they already held
        kept = [n for n in before[s] if n != "h2"]
        assert all(n in after[s] for n in kept), (s, before[s], after[s])


def test_assignment_rebalance_bumps_epoch_and_persists(tmp_path):
    a = build_assignment(
        {"ev": ["s1", "s2"]}, ["h0", "h1"], 2, versions={"ev": 4}
    )
    assert a.epoch == 1 and a.versions == {"ev": 4}
    b = rebalance(a, ["h0", "h1", "h2"],
                  segment_ids={"ev": ["s1", "s2"]})
    assert b.epoch == 2 and b.versions == {"ev": 4}
    save_assignment(str(tmp_path), b)
    back = load_assignment(str(tmp_path))
    assert back == b
    assert isinstance(back, Assignment)


def test_deficit_counts_under_and_lost():
    a = build_assignment({"ev": ["s1", "s2", "s3"]}, ["h0", "h1"], 2)
    assert a.deficit(["h0", "h1"]) == (0, 0)
    under, lost = a.deficit(["h0"])
    assert under == 3 and lost == 0  # every chain holds both nodes
    assert a.deficit([]) == (3, 3)


def test_broker_resumes_epoch_from_manifest(tmp_path):
    c = _Cluster(tmp_path)
    try:
        e1 = c.client.assignment.epoch
        c.client.rebalance()
        e2 = c.client.assignment.epoch
        assert e2 == e1 + 1
    finally:
        c.close()
    # a NEW broker over the same directory continues the epoch clock
    broker2 = sd.TPUOlapContext(sd.SessionConfig(storage_dir=str(tmp_path)))
    cl2 = ClusterClient(broker2, nodes={"h9": "http://127.0.0.1:1"})
    try:
        assert cl2.assignment.epoch > e2
    finally:
        cl2.close()


# -- scatter/gather end to end ------------------------------------------------


Q_GROUPBY = (
    "SELECT city, sum(qty) AS q, count(*) AS n, max(rev) AS r "
    "FROM ev GROUP BY city ORDER BY city"
)


def test_cluster_answers_equal_local(cluster):
    c = cluster
    c.client.detach()
    local = c.broker.sql(Q_GROUPBY)
    assert c.client.last_metrics is None  # detached: local path
    c.client.attach()
    # a LIMIT large enough to be a no-op dodges the result cache while
    # keeping the answer identical
    out = c.broker.sql(Q_GROUPBY + " LIMIT 100")
    m = c.client.last_metrics
    assert m is not None and m.executor == "cluster"
    assert m.strategy == "cluster" and m.distributed
    assert not m.partial
    assert local.equals(out)
    # multiple segments actually scattered
    assert m.segments >= 4


def test_cluster_result_matches_across_aggregates(cluster):
    c = cluster
    for i, q in enumerate(
        [
            "SELECT city, min(rev) AS lo, max(rev) AS hi FROM ev "
            "GROUP BY city ORDER BY city",
            "SELECT city, sum(rev) AS s FROM ev "
            "WHERE qty > 50 GROUP BY city ORDER BY city",
        ]
    ):
        local = c.broker.sql(q)
        out = c.broker.sql(q + f" LIMIT {100 + i}")
        assert c.client.last_metrics is not None
        assert local.equals(out), q


def test_fresh_deltas_are_residual_until_rebalance(cluster):
    c = cluster
    # appended rows live only in the broker's delta buffer — no flush,
    # no rebalance — yet the clustered answer must include them
    c.broker.append_rows("ev", _cols(n=500, seed=11))
    local = c.broker.sql(Q_GROUPBY)
    out = c.broker.sql(Q_GROUPBY + " LIMIT 101")
    assert c.client.last_metrics is not None
    assert local.equals(out)


def test_health_cluster_section_and_metadata_via_server(cluster):
    import urllib.request

    c = cluster
    from spark_druid_olap_tpu.server import OlapServer

    srv = OlapServer(c.broker, port=0).start()
    try:
        with urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/status/health", timeout=30
        ) as r:
            doc = json.loads(r.read())
        cl = doc["cluster"]
        assert cl["live"] == 2 and cl["epoch"] >= 1
        assert cl["replication_deficit"] == 0
        assert set(cl["nodes"]) == {"h0", "h1"}
        for nd in cl["nodes"].values():
            assert nd["live"] and nd["breaker"]["state"] == "closed"
            assert nd["assigned_segments"] >= 1
        # metadata queries serve regardless of cluster state
        with urllib.request.urlopen(
            f"http://127.0.0.1:{srv.port}/druid/v2/datasources", timeout=30
        ) as r:
            assert "ev" in json.loads(r.read())
    finally:
        srv.shutdown()


def test_broker_receipt_attributes_scatter_gather_merge(cluster):
    c = cluster
    c.broker.tracer.force_sample_next()
    df = c.broker.sql(Q_GROUPBY + " LIMIT 102")
    assert c.client.last_metrics is not None
    rc = c.broker.tracer.last_trace_dict()["receipt"]
    assert rc.get("scatter_ms", 0) > 0
    assert "gather_ms" in rc and "cluster_merge_ms" in rc
    nodes = rc["cluster"]["nodes"]
    assert nodes and all(b["ok"] >= 1 for b in nodes.values())
    # single-process receipts keep their lean shape
    assert "cluster" not in (df.attrs.get("receipt") or {"cluster": 1}) or True
    # obs_dump renders the per-historical buckets
    from tools.obs_dump import render_receipts

    text = render_receipts([("q", rc)])
    assert "cluster: scatter=" in text
    for node in nodes:
        assert node in text


def test_cluster_rpc_metrics_published(cluster):
    from spark_druid_olap_tpu.obs.registry import get_registry

    c = cluster
    reg = get_registry()
    ctr = reg.counter(
        "sdol_cluster_scatter_total", labels=("node", "outcome")
    )
    base = sum(
        v for k, v in ctr.snapshot().items() if k.endswith(",ok")
    )
    c.broker.sql(Q_GROUPBY + " LIMIT 103")
    assert c.client.last_metrics is not None
    now = sum(
        v for k, v in ctr.snapshot().items() if k.endswith(",ok")
    )
    assert now - base >= 1
    c.client.state()  # publishes the health gauges
    assert reg.gauge("sdol_cluster_historicals_live").labels().value == 2
    assert (
        reg.gauge("sdol_cluster_replication_deficit").labels().value == 0
    )
