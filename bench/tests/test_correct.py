"""`correct` has to be able to come out false.

Drives bench/run.py's whole flow on the CPU backend at a tiny scale (the
--rehearse switch is what skips the look for a chip) with the cell's process
run in-process, so the timed path can be broken underneath it: half of the
rows left out, an answer altered where it is produced. And the control — the
reference computed in float32, its answers written where the program's were —
goes through the same `run.judge` and comes out not correct.
"""

import json
import os

import pytest

import cell
import limits
import run
from run import BENCH
from lib import topology_standalone_1chip as topology

SCALE = "0.02"


def drive(capsys, workload, seed):
    rc = run.main(["--workload", workload, "--seed", str(seed), "--seconds", "0.5",
                   "--trace", "0", "--rehearse", SCALE], run_child=cell.main)
    assert rc == 0
    last = capsys.readouterr().out.strip().splitlines()[-1]
    return json.loads(last)["rehearsal"]


@pytest.mark.parametrize("workload", ["scan_agg_hot", "join_sort_hot"])
def test_a_sound_run_is_correct(workload, capsys):
    line = drive(capsys, workload, 2**31 + 5)
    assert line["correct"] is True and line["failed"] == 0
    assert list(line)[-1] == "checks" and line["device"]["platform"] == "cpu"
    assert line["checks"]["rel_err"]["value"] < 1e-12
    assert set(line["metrics"]) == {"setup_s", "hot_query_s", "first_query_s"}


def half_the_rows(monkeypatch, tmp_path):
    """The engine sees half of lineitem's files; the reference sees them all."""
    real = topology.open_session
    calls = []

    def broken(config, data_dir):
        # a directory a call: on a cold compile cache the first child is
        # thrown away and the cell opens its session a second time
        calls.append(tmp_path / f"call{len(calls)}")
        for table in config["tables"]:
            files = sorted(os.listdir(os.path.join(data_dir, table)))
            os.makedirs(calls[-1] / table)
            for f in files[:len(files) // 2] if table == "lineitem" else files:
                os.symlink(os.path.join(data_dir, table, f), calls[-1] / table / f)
        return real(config, str(calls[-1]))

    monkeypatch.setattr(topology, "open_session", broken)


class Altered:
    """A session whose answers have one cell changed where they are produced:
    the last column of the first row (q1's count +1, a revenue by 1e-6)."""

    def __init__(self, session):
        self.session = session

    def sql(self, text):
        frame = self.session.sql(text)

        class Frame:
            def collect(self):
                out = frame.collect()
                col = out.column(out.num_columns - 1).to_pylist()
                col[0] = col[0] + 1 if isinstance(col[0], int) else col[0] * (1 + 1e-6)
                import pyarrow as pa
                return out.set_column(out.num_columns - 1, out.schema.field(out.num_columns - 1),
                                      pa.array(col, out.schema.field(out.num_columns - 1).type))

        return Frame()

    def shutdown(self):
        self.session.shutdown()


def an_answer_altered(monkeypatch, tmp_path):
    real = topology.open_session
    monkeypatch.setattr(topology, "open_session", lambda c, d: Altered(real(c, d)))


@pytest.mark.parametrize("workload,fault,number", [
    ("scan_agg_hot", half_the_rows, "rel_err"),
    ("join_sort_hot", half_the_rows, "rel_err"),
    ("scan_agg_hot", an_answer_altered, "cells_off"),   # q1's count
    ("join_sort_hot", an_answer_altered, "rel_err"),    # q5's revenue
])
def test_a_broken_timed_path_is_not_correct(workload, fault, number, monkeypatch, capsys,
                                            tmp_path):
    fault(monkeypatch, tmp_path)
    line = drive(capsys, workload, 2**31 + 6)
    assert line["correct"] is False
    assert line["checks"][number]["value"] > line["checks"][number]["limit"]


def test_a_query_that_raises_is_not_correct(monkeypatch, capsys):
    real = topology.open_session

    class Raises(Altered):
        def sql(self, text):
            if "l_returnflag" in text:
                raise RuntimeError("planted")
            return self.session.sql(text)

    monkeypatch.setattr(topology, "open_session", lambda c, d: Raises(real(c, d)))
    line = drive(capsys, "scan_agg_hot", 2**31 + 7)
    assert line["correct"] is False and line["failed"] > 0
    assert line["checks"]["unanswered"]["value"] == line["failed"]


@pytest.mark.parametrize("workload", ["scan_agg_hot", "join_sort_hot"])
def test_the_float32_control_is_not_correct(workload, capsys):
    """bench/limits.py's whole flow on three seeds: the program's answers are
    `correct` through run.judge, and the control's, written in their place,
    are not — by `rel_err`, with no row or exact cell off."""
    rc = limits.main(["--workload", workload, "--seeds", "1", "2", str(2**31 + 3),
                      "--seconds", "0.3", "--rehearse", "0.05"], run_child=cell.main)
    lines = [json.loads(l) for l in capsys.readouterr().out.strip().splitlines()]
    assert rc == 0 and lines[-1]["program_correct_and_control_not_on_every_seed"] is True
    limit = lines[-1]["limits_now"]["rel_err"]
    assert len(lines) == 4
    for line in lines[:3]:
        assert line["program_correct"] is True and line["control_correct"] is False
        assert line["program"]["rel_err"] < limit < line["control"]["rel_err"]
        assert line["control"]["rows_off"] == line["control"]["unanswered"] == 0
