"""Seeded rows, one group-by an aggregation kind, and the wire documents
of their merged host partial states (`tests/goldens/partial_state.json`).

`PYTHONPATH=. python tests/partial_state_golden.py` writes the golden
from the code it runs against.  The golden in the repository was written
by the code before the partial state had a type of its own, when the host
state was a dict of sums, mins, maxs and sketches; `tests/
test_partial_state.py` holds the typed state's wire document to it byte
for byte.  Only the engine's public surface is used here
(`groupby_partials_host`, `cluster.wire.encode_state`), which both forms
share."""

import json
import os

import numpy as np

GOLDEN = os.path.join(os.path.dirname(__file__), "goldens", "partial_state.json")
ROWS = 3_000
ROWS_PER_SEGMENT = 512  # 6 segments, 3 batch programs at the CPU's unroll of 2


def table(seed=44):
    """Dimension `b` (3 values), keys `k` from a small domain, a float32
    measure `r` and a time column."""
    rng = np.random.default_rng(seed)
    return {
        "b": rng.choice(np.array(["p", "q", "s"], dtype=object), ROWS),
        "k": rng.integers(0, 500, ROWS).astype(np.int32),
        "r": (rng.random(ROWS) * 100).astype(np.float32),
        "t": np.sort(rng.integers(0, 10 * 86_400_000, ROWS)).astype(np.int64),
    }


def datasource():
    from spark_druid_olap_tpu.catalog.segment import build_datasource

    return build_datasource(
        "ps", table(), ["b"], ["k", "r"], time_col="t",
        rows_per_segment=ROWS_PER_SEGMENT,
    )


def aggregations():
    """One aggregation of each kind: its state's merge class."""
    from spark_druid_olap_tpu.models import aggregations as A

    return {
        "sum_count": (A.Count("n"), A.DoubleSum("rev", "r")),
        "min_max": (A.DoubleMin("lo", "r"), A.DoubleMax("hi", "r")),
        "hll": (A.HyperUnique("u", "k", precision=6),),
        "quantiles": (A.QuantilesSketch("qs", "r", size=16),),
        "theta": (A.ThetaSketch("t", "k", size=16),),
    }


def query(kind):
    from spark_druid_olap_tpu.models.dimensions import DimensionSpec
    from spark_druid_olap_tpu.models.query import GroupByQuery

    return GroupByQuery(
        datasource="ps", dimensions=(DimensionSpec("b"),),
        aggregations=aggregations()[kind],
    )


def documents():
    """kind -> the wire document of its query's merged host state."""
    from spark_druid_olap_tpu.cluster.wire import encode_state
    from spark_druid_olap_tpu.exec.engine import Engine

    ds = datasource()
    return {
        kind: encode_state(Engine().groupby_partials_host(query(kind), ds)[0])
        for kind in aggregations()
    }


if __name__ == "__main__":
    # the tests' own platform (tests/conftest.py)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    ).strip()
    with open(GOLDEN, "w") as f:
        f.write(json.dumps(documents(), indent=1) + "\n")
    print("wrote", GOLDEN)
