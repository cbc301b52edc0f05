"""The four-chip mesh deployment (`benchmark/configs/ssb-sf10-mesh4.json`)
at a small scale on conftest's virtual CPU devices: all 13 SSB queries
served over HTTP by a (4, 1) mesh against the plain float64 reference, and
the span layout, scope and counters a mesh request carries (ISSUE 27).

The system, the reference and the comparison are the benchmark's own
(`benchmark/loaders/ssb.py`, `loaders/ssb_data.py`, `harness/compare.py`),
loaded by path; only the segment size is cut, so that the small table still
has segments to deal to the shards, and `mesh_data_axis` names the four of
conftest's eight devices the configuration's mesh takes.
"""

import importlib.util
import json
import os

import jax
import numpy as np
import pandas as pd
import pytest

from spark_druid_olap_tpu.catalog.segment import build_datasource
from spark_druid_olap_tpu.exec.engine import Engine, segments_in_scope
from spark_druid_olap_tpu.exec.metrics import QueryMetrics
from spark_druid_olap_tpu.models.aggregations import (
    Count, DoubleMax, DoubleMin, DoubleSum,
)
from spark_druid_olap_tpu.models.dimensions import DimensionSpec
from spark_druid_olap_tpu.models.query import GroupByQuery
from spark_druid_olap_tpu.obs import prof
from spark_druid_olap_tpu.parallel.distributed import DistributedEngine
from spark_druid_olap_tpu.parallel.mesh import make_mesh
from spark_druid_olap_tpu.plan.cost import allreduce_factor, shape_kernel

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
SEEDS = (2147487101, 7)
SCALE = 0.01  # the configuration's rehearse_scale: 60,000 rows
ROWS_PER_SEGMENT = 4096  # 15 segments, dealt to 4 shards
SHARDS = 4
RING = allreduce_factor(SHARDS)  # 1.5: what an allreduce over 4 moves


def _load(path):
    spec = importlib.util.spec_from_file_location(
        "mesh_served_" + os.path.basename(path)[:-3], path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _json(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


compare = _load(os.path.join(BENCH, "harness", "compare.py"))
QUERIES = _json("traffic", "ssb-power.json")["queries"]
LIMITS = _json("workloads", "ssb-sf10-mesh4.power.json")["limits"]


@pytest.fixture(scope="module", params=SEEDS)
def served(request):
    """(system, reference answers) of the mesh configuration from one
    seed: the benchmark's own set-up, smaller segments, mesh (4, 1)."""
    loader = _load(os.path.join(BENCH, "loaders", "ssb.py"))
    config = _json("configs", "ssb-sf10-mesh4.json")
    config["rows_per_segment"] = ROWS_PER_SEGMENT
    config["settings"] = {**config["settings"], "mesh_data_axis": SHARDS}
    seed = request.param
    reference = loader.start_reference(config, QUERIES, seed, SCALE)
    try:
        system = loader.start_system(config, seed, SCALE, lambda **line: None)
        try:
            want = reference.join(240.0)["float32"]
            yield system, want
        finally:
            system.close()
    finally:
        reference.close()


@pytest.mark.parametrize("query", QUERIES, ids=[q["name"] for q in QUERIES])
def test_all_13_answer_from_the_mesh_at_parity(served, query):
    """Every SSB query, over HTTP, is executed by the (4, 1) mesh (the
    cell's guarantee: `compare.metrics_faults` with `distributed`), with
    exact keys and sums inside the cell's own limit."""
    system, want = served
    status, body, m = system.send(query)
    assert status == 200, body
    assert compare.metrics_faults(m, True) == []
    assert m.mesh_shape == (SHARDS, 1)
    assert m.shards == SHARDS
    # nothing merged and no step run only where nothing was launched: the
    # adaptive tier found a grouping dim with no code left (small scale)
    launched = m.receipt["dispatch_count"] > 0
    assert (m.collective_bytes > 0) == (m.shard_steps > 0) == launched
    n = compare.answer_numbers(pd.DataFrame(body), want[query["name"]])
    assert n["key_mismatch"] <= LIMITS["key_mismatches"]
    assert n["sum_rel_err"] <= LIMITS["sum_rel_err_max"], n


def _launches(node):
    """Launch spans in a span tree, as the receipt's `dispatch_count`
    means them."""
    own = 1 if node["name"] in prof.DISPATCH_SPANS else 0
    return own + sum(_launches(c) for c in node.get("children", ()))


def _nodes(node):
    yield node
    for c in node.get("children", ()):
        yield from _nodes(c)


def _names(node):
    return (n["name"] for n in _nodes(node))


def _find(node, name):
    return next((n for n in _nodes(node) if n["name"] == name), None)


@pytest.mark.parametrize("name", ["q1_1", "q2_1", "q4_1", "q4_3"])
def test_mesh_receipt_splits_launch_from_fetch(served, name):
    """A mesh request's span tree has the single-device engine's layout:
    the program's launch under a launch span, the blocking copy back
    under `device_fetch`, no `collective_merge`; the receipt counts one
    dispatch per program launched and its self times add up to the
    wall."""
    system, _ = served
    query = next(q for q in QUERIES if q["name"] == name)
    for _ in range(2):  # the second request is warm: memo, cached program
        status, _, m = system.send(query)
        assert status == 200 and m.distributed
        tree = system.ctx.tracer.ring.get(m.query_id)["spans"]
        receipt = m.receipt
        names = set(_names(tree))
        assert "device_fetch" in names and "collective_merge" not in names
        assert names & {"segment_dispatch", "adaptive_probe"}
        assert receipt["dispatch_count"] == _launches(tree) >= 1
        spans = receipt["spans"]
        assert spans["device_fetch"]["n"] >= 1
        assert sum(s["self_ms"] for s in spans.values()) == pytest.approx(
            receipt["wall_ms"], abs=1e-6
        )
    assert m.program_cache_hit and m.h2d_bytes == 0
    assert receipt["dispatch_count"] == 1  # warm: one SPMD launch


@pytest.mark.parametrize("name, tier", [
    ("q1_1", "dense"), ("q4_1", "dense"), ("q4_3", "adaptive"),
])
def test_mesh_request_walks_its_scope_once(served, name, tier):
    """On the mesh too a request resolves its scope once (ISSUE 38): the
    lane classifier's ask is the walk, `_execute_groupby_once`'s ask
    reuses it, and the arena, the dense-state program, the presence pass
    and the shard placement take the list as an argument."""
    system, _ = served
    query = next(q for q in QUERIES if q["name"] == name)
    for _ in range(2):  # cold (the adaptive tier measures), then warm
        status, _, m = system.send(query)
        assert status == 200 and m.distributed
        assert (m.strategy == "adaptive") == (tier == "adaptive")
        tree = system.ctx.tracer.ring.get(m.query_id)["spans"]
        assert m.receipt["spans"]["scope"]["n"] == 1
        (scope,) = [n for n in _nodes(tree) if n["name"] == "scope"]
        assert scope["attrs"]["asks"] == 2
        assert scope["attrs"]["kept"] == m.segments
        assert scope in tree["children"]  # the classifier's, under the root


def test_mesh_adaptive_kept_span_has_the_engines_attributes(served):
    """The mesh's adaptive tier names its kept-set step as the engine's
    does: `adaptive_kept` with `source`, `compact_groups`, `remap`, the
    presence pass (phase A) beneath it only when it has to measure, and
    phase B's launch marked so."""
    system, _ = served
    # at this scale the CPU's cost model sends q4_3 to the adaptive tier
    query = next(q for q in QUERIES if q["name"] == "q4_3")
    sources = []
    for _ in range(2):
        status, _, m = system.send(query)
        assert status == 200 and m.strategy == "adaptive"
        tree = system.ctx.tracer.ring.get(m.query_id)["spans"]
        kept = _find(tree, "adaptive_kept")
        assert kept is not None
        attrs = kept["attrs"]
        assert attrs["compact_groups"] == m.num_groups
        assert attrs["declined"] is False
        assert len(attrs["remap"]) == 3  # one form a grouping dim
        sources.append(attrs["source"])
        probe = _find(kept, "adaptive_probe")
        assert (probe is not None) == (attrs["source"] == "measured")
        launch = _find(tree, "segment_dispatch")
        assert launch["attrs"]["phase"] == "B"
        # which kernel phase B ran, and at what G' a device: the `route`
        # span around the mesh's call of the chooser (PR 30)
        routed = [
            n["attrs"] for n in _nodes(tree)
            if n["name"] == "route" and (n.get("attrs") or {}).get("tier")
        ]
        assert routed == [{
            "tier": "adaptive", "groups": m.num_groups,
            "kernel": shape_kernel(
                system.datasource.num_rows // 4, m.num_groups,
                system.ctx.config,
            ),
        }]
    assert sources[1] == "memo"


# ---------------------------------------------------------------------------
# the counters, at the engine (a mesh of four of the eight virtual devices)
# ---------------------------------------------------------------------------

N_SEG, SEG_ROWS = 15, 2048


@pytest.fixture(scope="module")
def dealt_ds():
    """15 time-sorted segments of 2,048 rows: one more than the shards
    deal evenly, as SF10's 115 are."""
    n = N_SEG * SEG_ROWS
    rng = np.random.default_rng(5)
    cols = {
        "d": rng.integers(0, 7, n),
        # `e` moves with time (32 codes a segment), so a date-pruned
        # scope leaves few of its 900 codes: what the adaptive tier finds
        "e": (np.arange(n) // 64) % 900,
        "f": rng.integers(0, 900, n),
        # integer-valued f32 keeps the psum merge bit-exact
        "v": rng.integers(0, 1000, n).astype(np.float32),
        "t": (np.arange(n) * 1_000).astype(np.int64),
    }
    return build_datasource(
        "dealt", cols, dimension_cols=["d", "e", "f"], metric_cols=["v"],
        time_col="t", rows_per_segment=SEG_ROWS,
    )


@pytest.fixture(scope="module")
def mesh4():
    return DistributedEngine(
        mesh=make_mesh(n_data=SHARDS, devices=jax.devices()[:SHARDS])
    )


def _dense_query(intervals=()):
    return GroupByQuery(
        datasource="dealt", dimensions=(DimensionSpec("d"),),
        aggregations=(
            Count("n"), DoubleSum("s", "v"),
            DoubleMin("lo", "v"), DoubleMax("hi", "v"),
        ),
        intervals=intervals,
    )


def _segments(lo, hi):
    """The interval that covers exactly segments lo..hi-1."""
    return ((lo * SEG_ROWS * 1_000, hi * SEG_ROWS * 1_000),)


@pytest.mark.parametrize("lo,hi,steps", [
    (0, 3, 1),  # three segments: every shard steps once, one of them dead
    (4, 7, 1),
    (2, 5, 2),  # the same three across a step boundary: two steps each
    (0, N_SEG, 4),  # the full scan: 16 slots for 15 segments
    (0, 8, 2),  # an even deal
])
def test_shard_steps_say_how_the_scope_fell_on_the_shards(
    mesh4, dealt_ds, lo, hi, steps
):
    q = _dense_query(_segments(lo, hi))
    assert len(segments_in_scope(q, dealt_ds)) == hi - lo
    mesh4.execute(q, dealt_ds)
    m = mesh4.last_metrics
    assert (m.segments, m.shards, m.shard_steps) == (hi - lo, SHARDS, steps)
    imbalance = m.shard_steps * m.shards / m.segments
    assert imbalance == pytest.approx(steps * SHARDS / (hi - lo))
    if (lo, hi) == (0, 3):
        assert imbalance == pytest.approx(4 / 3)
    if (lo, hi) == (0, N_SEG):
        assert imbalance == pytest.approx(16 / 15)


def test_collective_bytes_dense_is_the_merged_state_times_the_ring_factor(
    mesh4, dealt_ds
):
    """The arena's boundary merge allreduces the `[G, M]` sums, mins and
    maxs and a live count: their bytes x 2(n-1)/n, from the shapes
    merged."""
    q = _dense_query()
    mesh4.execute(q, dealt_ds)
    m = mesh4.last_metrics
    la = mesh4._lowering_for(q, dealt_ds).la
    columns = len(la.sum_names) + len(la.min_names) + len(la.max_names)
    assert columns >= 4  # count, sum, min, max (and any hidden counter)
    state = m.num_groups * columns * 4
    assert m.collective_bytes == round(RING * (state + 4))
    assert m.strategy != "adaptive" and m.shard_steps == 4


def test_collective_bytes_adaptive_counts_the_probe_once_then_the_compact_state(
    dealt_ds,
):
    """Adaptive on the mesh: the first request's presence pass allreduces
    a count vector a dim, and phase B the compacted `[G', M]` state; the
    repeat (kept set from the memo) moves phase B's bytes only."""
    dist = DistributedEngine(
        mesh=make_mesh(n_data=SHARDS, devices=jax.devices()[:SHARDS]),
        strategy="adaptive",
    )
    q = GroupByQuery(
        datasource="dealt",
        dimensions=(DimensionSpec("e"), DimensionSpec("f")),
        aggregations=(Count("n"), DoubleSum("s", "v")),
        intervals=_segments(0, 2),  # few rows: few codes present
    )
    want = Engine().execute(q, dealt_ds)
    got = dist.execute(q, dealt_ds)
    first = dist.last_metrics
    assert len(got) == len(want) and first.strategy == "adaptive"
    lowering = dist._lowering_for(q, dealt_ds)
    la = lowering.la
    state = first.num_groups * len(la.sum_names) * 4
    assert first.num_groups < lowering.num_groups  # compacted
    presence = sum(d.cardinality for d in lowering.dims) * 4
    assert first.collective_bytes == round(RING * presence) + round(
        RING * state
    )
    dist.execute(q, dealt_ds)
    again = dist.last_metrics
    assert again.collective_bytes == round(RING * state)
    # the scope's rows lie end to end, cut evenly: an even deal
    assert again.shard_steps * again.shards / again.segments == pytest.approx(1)


def test_single_device_requests_count_no_collective(dealt_ds):
    eng = Engine()
    eng.execute(_dense_query(), dealt_ds)
    m = eng.last_metrics
    assert not m.distributed
    assert (m.collective_bytes, m.shard_steps, m.shards) == (0, 0, 0)


# ---------------------------------------------------------------------------
# the device scope: every collective of every mesh program carries it
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("family", ["arena", "dense-state", "presence", "sparse"])
def test_mesh_programs_put_their_collectives_under_boundary_merge(
    mesh4, dealt_ds, family
):
    """The jaxpr of each SPMD program family: every `psum` / `pmin` /
    `pmax` / `all_gather` equation sits under `sdol.boundary_merge`, so
    a kept trace names the mesh's `%all-reduce`s."""
    from spark_druid_olap_tpu.parallel import spmd_arena

    q = GroupByQuery(
        datasource="dealt",
        dimensions=(DimensionSpec("e"), DimensionSpec("f")),
        aggregations=(
            Count("n"), DoubleSum("s", "v"), DoubleMin("lo", "v"),
        ),
    )
    lowering = mesh4._lowering_for(q, dealt_ds)
    scratch = QueryMetrics(query_type="placement")
    if family == "arena":
        layout = mesh4._arena_layout(dealt_ds)
        cols = mesh4._place_arena(dealt_ds, layout, lowering.columns, scratch)
        run = spmd_arena.build_spmd_arena_program(
            mesh4._arena_mesh(), [lowering], ["segment"], 4
        )
        args = (cols, np.int32(0), spmd_arena.membership_matrix(
            layout, [list(range(N_SEG))]
        ))
    else:
        cols, padded = mesh4._place_shards(
            dealt_ds, lowering.columns, scratch,
            segs=segments_in_scope(q, dealt_ds),
        )
        local_rows, keys = padded // SHARDS, tuple(cols.keys())
        if family == "dense-state":
            run = mesh4._spmd_fn(lowering, local_rows, dealt_ds, keys, "segment")
        elif family == "presence":
            run = mesh4._presence_fn(lowering, local_rows, dealt_ds, keys)
        else:
            run = mesh4._spmd_sparse_fn(
                lowering, local_rows, dealt_ds, keys, 1024, None
            )
        args = (cols,)
    collectives = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name in ("psum", "pmin", "pmax", "all_gather"):
                collectives.append(str(eqn.source_info.name_stack))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(run)(*args).jaxpr)
    assert collectives
    assert all("sdol.boundary_merge" in s for s in collectives), collectives
