"""The partial-aggregate state's algebra (`exec/state.py`), one
aggregation of each kind (sum and count, min and max, HLL, quantiles,
theta): the empty state is ⊕'s identity on either side, bit for bit; a
scope's batch states folded in canonical order are the bytes the serial
loop and the arena make; the host state's wire document is the golden
written before the state had a type (`tests/partial_state_golden.py`),
and decodes back to the state."""

import json

import numpy as np
import pytest

import partial_state_golden as golden
from spark_druid_olap_tpu.cluster.wire import decode_state, encode_state
from spark_druid_olap_tpu.exec.engine import Engine
from spark_druid_olap_tpu.exec.pipeline import CanonicalFold
from spark_druid_olap_tpu.exec.state import PartialState, merge, to_host

KINDS = list(golden.aggregations())


@pytest.fixture(scope="module")
def ds():
    return golden.datasource()


@pytest.fixture(scope="module")
def states(ds):
    """kind -> (lowering, the query's merged device state)."""
    eng = Engine()
    out = {}
    for kind in KINDS:
        lowering = eng._lowering_for(golden.query(kind), ds)
        out[kind] = lowering, eng._partials_for_query(
            golden.query(kind), ds, lowering=lowering
        )
    return out


def _assert_same_bytes(a, b):
    """Two host states with equal leaves: dtype, shape and bytes."""
    a, b = to_host(a), to_host(b)
    assert isinstance(a, PartialState) and isinstance(b, PartialState)
    assert list(a.sketches) == list(b.sketches)
    for x, y in zip(
        (a.sums, a.mins, a.maxs, *a.sketches.values()),
        (b.sums, b.mins, b.maxs, *b.sketches.values()),
    ):
        assert x.dtype == y.dtype and x.shape == y.shape
        assert x.tobytes() == y.tobytes()


@pytest.mark.parametrize("side", ["left", "right"])
@pytest.mark.parametrize("kind", KINDS)
def test_empty_is_the_identity(states, kind, side):
    lowering, s = states[kind]
    empty = PartialState.empty(lowering.la, lowering.num_groups)
    got = merge(lowering.la, empty, s) if side == "left" else merge(
        lowering.la, s, empty
    )
    _assert_same_bytes(got, s)
    # and on the host, where the result cache and the broker merge
    hs, he = to_host(s), to_host(empty)
    got = merge(lowering.la, he, hs) if side == "left" else merge(
        lowering.la, hs, he
    )
    _assert_same_bytes(got, hs)


@pytest.mark.parametrize("kind", KINDS)
def test_canonical_fold_is_the_serial_loop_and_the_arena(ds, states, kind):
    """Batch states dispatched last-first and folded through
    `CanonicalFold` give the serial loop's bytes, with the arena on (it
    declines sketches) and off."""
    q = golden.query(kind)
    eng = Engine()
    lowering, want = states[kind]
    need = lowering.columns
    batches = list(eng._segment_batches(
        eng._segments_in_scope(q, ds), need, lowering.la
    ))
    assert len(batches) == 3
    seg_fn = eng._segment_program(q, ds, lowering)
    acc = None

    def fold(new):
        nonlocal acc
        acc = merge(lowering.la, acc, new)

    folder = CanonicalFold(fold)
    for bi in reversed(range(len(batches))):
        folder.add(bi, seg_fn([
            eng._cols_for_segment(seg, ds, need) for seg in batches[bi]
        ]))
    folder.drain()
    for arena in (False, True):
        eng.arena_execution = arena
        got = eng._partials_for_query(q, ds, lowering=lowering)
        _assert_same_bytes(acc, got)
    _assert_same_bytes(acc, want)


@pytest.mark.parametrize("kind", KINDS)
def test_wire_document_is_the_golden(states, kind):
    with open(golden.GOLDEN) as f:
        want = json.load(f)[kind]
    host = to_host(states[kind][1])
    doc = encode_state(host)
    assert json.dumps(doc) == json.dumps(want)
    back = decode_state(json.loads(json.dumps(want)))
    _assert_same_bytes(back, host)
    assert np.asarray(back.sums).flags.writeable
