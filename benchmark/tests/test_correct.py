"""`correct` has to come out false when it should: for the control (the
reference in the precision below, put in the program's place) and for an
answer altered where it is produced.  Each case drives a whole run of
`run.py` on the CPU at `rehearse_scale`, past the look for a chip."""

import json

import pytest

import run

CELLS = ["ssb-sf10-1chip.flight1", "ssb-sf10-1chip.flights2-4"]


def _run(capsys, cell, *more):
    rc = run.main(["--workload", cell, "--seed", "2147483659", "--seconds",
                   "1", "--trace", "0", "--rehearse", *more])
    assert rc == 0
    lines = [json.loads(line) for line in
             capsys.readouterr().out.strip().splitlines()]
    return lines


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct_and_its_control_is_not(capsys, cell):
    lines = _run(capsys, cell, "--control")
    last = lines[-1]
    assert last["correct"] is True and last["failed"] == 0
    assert list(last)[-1] == "checks"  # the compared numbers come last
    assert all(c["ok"] for c in last["checks"].values())
    control = [x for x in lines if x.get("phase") == "control"]
    assert len(control) == 1 and control[0]["correct"] is False
    bad = control[0]["checks"]["sum_rel_err_max"]
    assert not bad["ok"] and bad["value"] > 3 * bad["limit"]


def _scaled(df):
    """One sum off by a thousandth: far inside float32's range, far
    outside what rounding gives."""
    if len(df):
        df = df.copy()
        df.iloc[0, df.columns.get_loc(_sum_column(df))] *= 1.001
    return df


def _halved(df):
    """What a scan that leaves out every other segment returns: each sum
    about half, taken over the rest."""
    if len(df):
        df = df.copy()
        df[_sum_column(df)] = df[_sum_column(df)] * 0.5
    return df


def _dropped_group(df):
    return df.iloc[:-1].reset_index(drop=True) if len(df) > 1 else df


def _sum_column(df):
    return next(c for c in df.columns if c in ("revenue", "profit"))


@pytest.mark.parametrize("cell, fault, number", [
    (CELLS[0], _scaled, "sum_rel_err_max"),
    (CELLS[1], _scaled, "sum_rel_err_max"),
    (CELLS[0], _halved, "sum_rel_err_max"),
    (CELLS[1], _halved, "sum_rel_err_max"),
    (CELLS[1], _dropped_group, "key_mismatches"),
])
def test_an_altered_answer_is_not_correct(capsys, monkeypatch, cell, fault,
                                          number):
    from spark_druid_olap_tpu.api import TPUOlapContext

    produce = TPUOlapContext._post_process

    def broken(self, rw, ds, df):
        return fault(produce(self, rw, ds, df))

    monkeypatch.setattr(TPUOlapContext, "_post_process", broken)
    last = _run(capsys, cell)[-1]
    assert last["correct"] is False
    assert not last["checks"][number]["ok"]
    others = [n for n in last["checks"] if n != number
              and n != "failed_requests"]
    assert last["attempted"] > 0 and others


def test_a_request_off_the_timed_path_is_a_failed_request(capsys, monkeypatch):
    from harness import compare

    monkeypatch.setattr(compare, "metrics_faults",
                        lambda m, distributed=False: ["degraded"])
    last = _run(capsys, CELLS[0])[-1]
    assert last["correct"] is False
    assert last["checks"]["failed_requests"]["value"] == last["attempted"]
    assert last["failed"] == last["attempted"]


def test_a_failed_set_up_leaves_no_child_and_no_result(capsys, monkeypatch):
    """The reference child is started before the system: when the system
    cannot be built (a checkout without the program, a server that does not
    start) the run fails, prints no result and takes the child with it."""
    import subprocess

    from spark_druid_olap_tpu.server import OlapServer

    started = []
    popen = subprocess.Popen

    def recording(*a, **kw):
        started.append(popen(*a, **kw))
        return started[-1]

    def refuse(self):
        raise RuntimeError("no server")

    monkeypatch.setattr(subprocess, "Popen", recording)
    monkeypatch.setattr(OlapServer, "start", refuse)
    with pytest.raises(RuntimeError, match="no server"):
        run.main(["--workload", CELLS[0], "--seed", "3", "--seconds", "1",
                  "--trace", "0", "--rehearse"])
    assert started and all(p.poll() is not None for p in started)
    lines = capsys.readouterr().out.strip().splitlines()
    assert not any('"correct"' in line for line in lines)


def test_without_a_chip_the_run_fails_and_prints_no_result(capsys):
    rc = run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                   "--trace", "0"])
    assert rc != 0
    assert capsys.readouterr().out.strip() == ""
