"""lib/spans.py on plain lists: self time, durations, counts and the
uncovered share of a root, with no program and no trace."""

import pytest

from lib import spans
from lib.readers import Run


def span(name, sid, parent, start, end, stage=None, task=None):
    return [name, sid, parent, start, end, stage, task, {}]


# one query: the client blocks in wait while two tasks run side by side on
# pool threads; each task's dispatch holds a device wait; the report of the
# first task is handled on the scheduler's thread while the second still runs
JOB = [
    span("bt.client.collect", 1, None, 0.0, 10.0),
    span("bt.client.submit", 2, 1, 0.0, 1.0),
    span("bt.sched.plan", 3, 2, 0.2, 0.7),
    span("bt.client.wait", 4, 1, 1.0, 9.0),
    span("bt.sched.stage", 5, 4, 1.0, 8.5, stage=1),
    span("bt.task.queued", 6, 5, 1.0, 1.5, 1, 1),
    span("bt.task.run", 7, 5, 1.5, 5.5, 1, 1),
    span("bt.stage.dispatch", 8, 7, 2.0, 5.0, 1, 1),
    span("bt.device.exec", 9, 8, 2.5, 4.5, 1, 1),
    span("bt.task.queued", 10, 5, 1.0, 2.0, 1, 2),
    span("bt.task.run", 11, 5, 2.0, 8.0, 1, 2),
    span("bt.stage.dispatch", 12, 11, 2.0, 7.0, 1, 2),
    span("bt.device.exec", 13, 12, 3.0, 6.0, 1, 2),
    # a helper thread's work under the second dispatch, overlapping its exec
    span("bt.compile.trace", 14, 12, 2.0, 3.5, 1, 2),
    span("bt.task.report", 15, 5, 5.5, 5.75, 1, 1),
    span("bt.task.report", 16, 5, 8.0, 8.5, 1, 2),
    span("bt.client.fetch_results", 17, 1, 9.25, 10.0),
]


def run_of(*jobs, phase="window"):
    """A Run whose executions each hold one job record (None: a program
    that records no spans)."""
    execs = []
    for i, job in enumerate(jobs):
        stages = {"stage_aaaa": {"dispatches": 8, "exec_s": 0.01}}
        if job is not None:
            stages[f"job_{i}"] = {"spans": job, "spans_dropped": 0}
        execs.append({"phase": phase, "failed": False, "query": "q1", "round": i,
                      "stages": stages})
    return Run(record={"executions": execs}, trace=None, round_bytes=0.0, peaks={})


def test_covered_is_the_union_clipped():
    assert spans.covered([(0, 2), (1, 3), (5, 6)], 0, 10) == 4
    assert spans.covered([(0, 2), (1, 3), (5, 6)], 1.5, 5.5) == 2.0
    assert spans.covered([], 0, 10) == 0
    assert spans.covered([(2, 4), (2.5, 3)], 0, 10) == 2  # nested


@pytest.mark.parametrize("names, want", [
    (["bt.client.submit"], 0.5),                 # nested child taken off
    (["bt.task.run"], (4.0 - 3.0) + (6.0 - 5.0)),  # two tasks, each minus its dispatch
    # overlapping siblings (exec 3..6, trace 2..3.5) cover 2..6 of 2..7, not 1.5 + 3
    (["bt.stage.dispatch"], (3.0 - 2.0) + (5.0 - 4.0)),
    (["bt.device.exec"], 2.0 + 3.0),             # leaves: whole duration, side by side adds up
    (["bt.client.wait"], 8.0 - 7.5),             # cross-thread child (the stage) taken off
    (["bt.client.collect"], 10.0 - (1.0 + 8.0 + 0.75)),
    (["bt.nothing"], 0.0),                       # the layer did nothing: 0, not None
])
def test_self_seconds(names, want):
    assert spans.self_seconds(run_of(JOB), names, per="query") == pytest.approx(want)


def test_duration_count_and_scale():
    run = run_of(JOB, JOB)
    assert spans.duration(run, ["bt.task.queued"], per="query", scale=1000.0) == pytest.approx(1500.0)
    assert spans.duration(run, ["bt.task.queued"], per="total") == pytest.approx(3.0)
    assert spans.duration(run, ["bt.task.queued"], per="round") == pytest.approx(1.5)
    assert spans.count(run, ["bt.task.run"], per="query") == 2.0
    assert spans.count(run, ["bt.task.run", "bt.task.report"], per="total") == 8.0


def test_uncovered_is_what_no_span_saw():
    # open throughout but for 9.0..9.25 (between wait's end and the fetch):
    # submit 0..1, queued from 1, tasks to 8, report to 8.5 — and 8.5..9.0,
    # where only the waiting spans were open
    waiting = ["bt.client.wait", "bt.sched.stage"]
    got = spans.uncovered(run_of(JOB), "bt.client.collect", waiting=waiting)
    assert got == pytest.approx(0.5 + 0.25)
    # the waiting spans counted as cover leave only 9.0..9.25
    assert spans.uncovered(run_of(JOB), "bt.client.collect") == pytest.approx(0.25)
    assert spans.uncovered(run_of(JOB), "bt.no.such.root") is None


def test_a_program_without_spans_reads_none():
    run = run_of(None, None)
    assert spans.self_seconds(run, ["bt.task.run"]) is None
    assert spans.duration(run, ["bt.task.run"]) is None
    assert spans.count(run, ["bt.task.run"]) is None
    assert spans.uncovered(run, "bt.client.collect") is None
    # only the window's executions are read
    assert spans.count(run_of(JOB, phase="first"), ["bt.task.run"]) is None
    # one query of two left no record: the sum is over what there is, per query run
    assert spans.count(run_of(JOB, None), ["bt.task.run"], per="query") == 1.0


def test_a_missing_parent_takes_nothing_off():
    # a child whose parent was dropped at the cap (or rides in from another job)
    orphan = [span("bt.client.collect", 1, None, 0.0, 4.0),
              span("bt.device.exec", 9, 99, 1.0, 2.0)]
    assert spans.self_seconds(run_of(orphan), ["bt.client.collect"]) == pytest.approx(4.0)
    assert spans.uncovered(run_of(orphan), "bt.client.collect") == pytest.approx(3.0)


NEW_METRICS = {"sched_plan_ms", "task_queue_ms", "task_overhead_ms", "tasks_per_query",
               "dispatch_s", "device_wait_s", "decode_ms", "shuffle_ms", "result_fetch_ms",
               "served_unattributed_ms"}


@pytest.mark.parametrize("workload", ["scan_agg_hot", "join_sort_hot"])
def test_a_traced_rehearsal_prints_the_span_metrics(workload, capsys):
    """The whole flow on the CPU backend with the program's spans on (they
    always are): every span metric is in the line, and the counters the
    spans must not disturb read what they read before."""
    import json

    import cell
    import run

    rc = run.main(["--workload", workload, "--seed", str(2**31 + 7), "--seconds", "0.5",
                   "--trace", "1", "--rehearse", "0.02"], run_child=cell.main)
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])["rehearsal"]
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    assert line["correct"] is True and NEW_METRICS <= set(metrics)
    assert all(metrics[m] >= 0 for m in NEW_METRICS)
    # a device stage is one task since PR 26: its task and a final stage's at least
    assert metrics["tasks_per_query"] >= 2
    # one job record a query, outside the dispatch-counting path
    assert metrics["dispatches_per_query"] == 1.0
    assert metrics["dispatch_s"] >= metrics["stage_exec_s"] > 0
