"""`tpch-sf10-1chip.power` at `rehearse_scale` on the CPU: a sound run is
correct and its bfloat16 control is not, and `correct` comes out false
for a system that is wrong in each way the reference can see: one price
altered at ingest, one segment left out of every scan, the group at Q3's
top-10 cut swapped for the next one."""

import dataclasses
import json

import pytest

import run

CELL = "tpch-sf10-1chip.power"
QUERIES = {"q1", "q3", "q5", "q6", "q7", "q8", "q10", "q12", "q14", "q19"}


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
    assert last["attempted"] >= len(QUERIES)
    checked = {x["query"] for x in lines if x.get("phase") == "checked"}
    assert checked == QUERIES
    control = [x for x in lines if x.get("phase") == "control"]
    assert len(control) == 1 and control[0]["correct"] is False
    bad = control[0]["checks"]["sum_rel_err_max"]
    assert not bad["ok"] and bad["value"] > 3 * bad["limit"]


def _one_price_altered(monkeypatch):
    """The first fact row's l_extendedprice 1e6 as the program ingests it
    (a price runs to 55,540): Q1's sums over every row move by ~1e-3."""
    from spark_druid_olap_tpu.ingest import shard

    build = shard.build_datasource_sharded

    def altered(name, source, *a, **kw):
        def chunks():
            for i, c in enumerate(source):
                if i == 0:
                    c = dict(c, l_extendedprice=c["l_extendedprice"].copy())
                    c["l_extendedprice"][0] = 1e6
                yield c

        return build(name, chunks(), *a, **kw)

    monkeypatch.setattr(shard, "build_datasource_sharded", altered)


def _segment_left_out(monkeypatch):
    from spark_druid_olap_tpu.exec.engine import Engine

    batches = Engine._segment_batches

    def fewer(self, segs, names, *more):
        return batches(self, list(segs)[:-1], names, *more)

    monkeypatch.setattr(Engine, "_segment_batches", fewer)


def _swap_at_the_cut(monkeypatch):
    """A topN's last place answered by the group one place below it: Q3's
    10th order is the reference's 11th."""
    from spark_druid_olap_tpu.exec import engine

    rank = engine.finalize_topn

    def swapped(df, q):
        out = rank(df, dataclasses.replace(q, threshold=q.threshold + 1))
        return out.drop(index=q.threshold - 1).reset_index(drop=True)

    monkeypatch.setattr(engine, "finalize_topn", swapped)


@pytest.mark.parametrize("fault, failing", [
    (_one_price_altered, {"sum_rel_err_max"}),
    (_segment_left_out, {"key_mismatches"}),
    (_swap_at_the_cut, {"key_mismatches"}),
])
def test_a_wrong_answer_is_not_correct(capsys, monkeypatch, fault, failing):
    fault(monkeypatch)
    last = _run(capsys)[-1]
    assert last["correct"] is False
    assert last["attempted"] > 0
    assert failing <= {n for n, c in last["checks"].items() if not c["ok"]}
