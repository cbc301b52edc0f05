"""`ssb-sf10-topn-hll.dashboard` at `rehearse_scale` on the CPU: a sound
run is correct, and `correct` comes out false for the control and for a
system whose HLL is wrong in each way the reference can see: one register
altered, a precision of 2^10 buckets in place of Druid's 2^11, one segment
left out of every scan."""

import dataclasses
import json

import pytest

import run

CELL = "ssb-sf10-topn-hll.dashboard"


def _run(capsys, *more):
    rc = run.main(["--workload", CELL, "--seed", "2147483659", "--seconds",
                   "1", "--trace", "0", "--rehearse", *more])
    assert rc == 0
    return [json.loads(line) for line in
            capsys.readouterr().out.strip().splitlines()]


def test_sound_run_is_correct_and_its_control_is_not(capsys):
    lines = _run(capsys, "--control")
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] >= 4
    checked = {x["query"] for x in lines if x.get("phase") == "checked"}
    assert checked == {"topn_scity_all", "topn_brand_1997",
                       "topn_scity_1997_asia", "topn_brand_1998h1"}
    control = [x for x in lines if x.get("phase") == "control"]
    assert len(control) == 1 and control[0]["correct"] is False
    bad = control[0]["checks"]["sum_rel_err_max"]
    assert not bad["ok"] and bad["value"] > 3 * bad["limit"]


def _one_register_altered(monkeypatch):
    """Register 0 of every group one higher as the registers reach the
    host (a zero becomes one, so a linear-counting estimate moves too)."""
    from spark_druid_olap_tpu.exec import engine

    produce = engine.finalize_groupby

    def altered(q, dims, la, sums, mins, maxs, sketch_states, *a, **kw):
        states = {k: v.copy() for k, v in sketch_states.items()}
        for v in states.values():
            v[:, 0] += 1
        return produce(q, dims, la, sums, mins, maxs, states, *a, **kw)

    monkeypatch.setattr(engine, "finalize_groupby", altered)


def _precision_10(monkeypatch):
    from spark_druid_olap_tpu.models import aggregations as A
    from spark_druid_olap_tpu.models import wire

    parse = wire.agg_from_druid

    def p10(d):
        a = parse(d)
        return dataclasses.replace(a, precision=10) if isinstance(a, A.HyperUnique) else a

    monkeypatch.setattr(wire, "agg_from_druid", p10)


def _segment_left_out(monkeypatch):
    from spark_druid_olap_tpu.exec.engine import Engine

    batches = Engine._segment_batches

    def fewer(self, segs, names):
        return batches(self, list(segs)[:-1], names)

    monkeypatch.setattr(Engine, "_segment_batches", fewer)


@pytest.mark.parametrize("fault", [
    _one_register_altered, _precision_10, _segment_left_out,
])
def test_a_wrong_sketch_is_not_correct(capsys, monkeypatch, fault):
    fault(monkeypatch)
    last = _run(capsys)[-1]
    assert last["correct"] is False
    assert last["attempted"] > 0
    failing = [n for n, c in last["checks"].items() if not c["ok"]]
    assert set(failing) <= {"key_mismatches", "sum_rel_err_max"} and failing
