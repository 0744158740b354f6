"""lib/path.py: the critical path of a query on plain lists and on recorded
job records of the five cells (`data/path_records.json`: the first window
query of each kind from PR 35's chip runs, rows as the program recorded
them), the eight metrics that read it, and the benchmark's copy of the rule
held equal to the program's (`ballista_tpu.tracing.critical_path`)."""

import copy
import json
import os

import pytest

from lib import load_attr, path
from lib.readers import Run
from run import BENCH, ROOT

RECORDS = json.load(open(os.path.join(BENCH, "tests", "data", "path_records.json")))
CASES = [(cell, i) for cell, records in sorted(RECORDS.items()) for i in range(len(records))]
SIX = ["path_device_ms", "path_stage_host_ms", "path_host_ops_ms", "path_shuffle_ms",
       "path_sched_ms", "path_client_ms"]
EIGHT = SIX + ["host_ops_ms", "partition_ms"]


def span(name, sid, parent, start, end, stage=None, task=None, **numbers):
    return [name, sid, parent, start, end, stage, task, numbers]


def run_of(*jobs, phase="window"):
    execs = []
    for i, job in enumerate(jobs):
        stages = {"stage_aaaa": {"dispatches": 1}}
        if job is not None:
            stages[f"job_{i}"] = {"spans": job, "spans_dropped": 0}
        execs.append({"phase": phase, "failed": False, "query": "q", "round": i,
                      "stages": stages})
    return Run(record={"executions": execs}, trace=None, round_bytes=0.0, peaks={})


def metric(name, run):
    spec = json.load(open(os.path.join(BENCH, "metrics", f"{name}.json")))
    return load_attr(spec["reader"])(run, **spec["args"])


def with_ops(spans, share):
    """The record as a program that states `ops_ms` would have left it: every
    write span's operators are `share` of its self time."""
    spans = copy.deepcopy(spans)
    kids: dict = {}
    for s in spans:
        kids.setdefault(s[2], []).append((s[3], s[4]))
    for s in spans:
        if s[0] == path.WRITE:
            self_s = (s[4] - s[3]) - path.covered(kids.get(s[1], []), s[3], s[4])
            s[7] = {**s[7], "ops_ms": 1e3 * share * self_s, "partition_ms": 1.0}
    return spans


# two tasks of one stage on two slots, then a second stage of one task: the
# path takes the task that ended last; stage 1 hangs beside the client's wait
JOB = [
    span("bt.client.collect", 1, None, 0.0, 10.0),
    span("bt.client.submit", 2, 1, 0.0, 1.0),
    span("bt.sched.stage", 3, 1, 0.5, 6.0, 1),
    span("bt.client.wait", 4, 1, 1.0, 9.5),
    span("bt.task.run", 5, 3, 1.0, 4.0, 1, 1),
    span("bt.task.run", 6, 3, 1.0, 5.5, 1, 2),
    span("bt.shuffle.write", 7, 6, 1.5, 5.5, 1, 2, ops_ms=1500.0, partition_ms=250.0),
    span("bt.shuffle.read", 8, 7, 2.0, 3.0, 1, 2),
    span("bt.shuffle.commit", 9, 7, 5.0, 5.5, 1, 2),
    span("bt.sched.stage", 10, 4, 6.0, 9.0, 2),
    span("bt.task.run", 11, 10, 6.5, 9.0, 2, 3),
    span("bt.shuffle.write", 12, 11, 6.5, 9.0, 2, 3, ops_ms=0.0, partition_ms=100.0),
    span("bt.stage.dispatch", 13, 12, 6.5, 8.5, 2, 3),
    span("bt.device.exec", 14, 13, 7.0, 8.0, 2, 3),
]


def test_the_rule_on_a_hand_made_record():
    segments = path.critical_path(JOB)
    assert segments[0][0] == 0.0 and segments[-1][1] == 10.0
    assert all(a[1] == b[0] for a, b in zip(segments, segments[1:]))
    assert path.by_name(segments) == {
        "bt.client.submit": 1.0, "bt.task.run": 0.5, "bt.shuffle.write": 2.5 + 0.5,
        "bt.shuffle.read": 1.0, "bt.shuffle.commit": 0.5,
        "bt.sched.stage": 0.5 + 0.5,     # 5.5–6 behind stage 1's last task, 6–6.5 before stage 2's
        "bt.client.wait": 0.5,           # 9–9.5: the poll
        "bt.stage.dispatch": 1.0, "bt.device.exec": 1.0, "bt.client.collect": 0.5}
    assert 5 not in {sid for _, _, sid, _ in segments}  # the task that ended first is off the path
    assert path.critical_path([s for s in JOB if s[0] != "bt.client.collect"]) is None


def test_the_writers_path_time_is_split_by_what_the_span_says_it_holds():
    segments = path.critical_path(JOB)
    # span 7: 4 s long, children cover 1.5, self 2.5 s, on the path 2.5 s, ops 1.5 s: 60 %
    # span 12: self 0.5 s, on the path 0.5 s, no operator time
    ops, rest = path.write_split(JOB, segments)
    assert ops == pytest.approx(1.5) and rest == pytest.approx(1.0 + 0.5)
    run = run_of(JOB)
    assert path.seconds(run, names=[], write="ops") == pytest.approx(1.5)
    assert path.seconds(run, names=["bt.shuffle.commit"], write="rest") == pytest.approx(2.0)
    over = copy.deepcopy(JOB)
    over[6][7]["ops_ms"] = 9000.0  # operators that ran inside the read's span too: at most all
    assert path.write_split(over, segments)[0] == pytest.approx(2.5)
    six = [metric(name, run) for name in SIX]
    assert sum(six) == pytest.approx(10_000.0)
    assert dict(zip(SIX, six)) == {
        "path_device_ms": pytest.approx(1000.0), "path_stage_host_ms": pytest.approx(1000.0),
        "path_host_ops_ms": pytest.approx(1500.0), "path_shuffle_ms": pytest.approx(3000.0),
        "path_sched_ms": pytest.approx(3000.0), "path_client_ms": pytest.approx(500.0)}
    assert metric("host_ops_ms", run) == 1500.0 and metric("partition_ms", run) == 350.0


def test_a_program_that_states_no_ops_ms_has_no_operator_metrics():
    """The parent of PR 36: the path is there (its spans are), the split is
    not: `path_host_ops_ms`, `host_ops_ms`, `partition_ms` are left out and
    `path_shuffle_ms` holds the writer's path time whole."""
    old = copy.deepcopy(JOB)
    for s in old:
        s[7] = {}
    run = run_of(old)
    got = {name: metric(name, run) for name in EIGHT}
    assert got["path_host_ops_ms"] is None and got["host_ops_ms"] is None \
        and got["partition_ms"] is None
    assert got["path_shuffle_ms"] == pytest.approx(4500.0)
    assert sum(v for k, v in got.items() if k in SIX and v is not None) == pytest.approx(10_000.0)
    assert all(metric(name, run_of(None)) is None for name in EIGHT)  # no spans at all


def test_a_name_the_cell_never_records_counts_zero_once_there_is_a_path():
    lean = [s for s in JOB if s[0] not in ("bt.device.exec", "bt.stage.dispatch")]
    run = run_of(lean)
    assert metric("path_device_ms", run) == 0.0 and metric("path_stage_host_ms", run) == 0.0
    assert sum(metric(name, run) for name in SIX) == pytest.approx(10_000.0)


def test_metrics_are_means_over_the_windows_queries():
    run = run_of(JOB, JOB, None)  # the third query left no record: it still counts
    assert metric("path_device_ms", run) == pytest.approx(2000.0 / 3)


@pytest.mark.parametrize("cell, i", CASES)
def test_the_six_path_metrics_sum_to_the_recorded_collect_wall(cell, i):
    rec = RECORDS[cell][i]
    root = max((s for s in rec["spans"] if s[0] == path.ROOT), key=lambda s: s[4] - s[3])
    wall_ms = 1e3 * (root[4] - root[3])
    # as the parent left the record (no ops_ms): five metrics, the same sum
    run = run_of(rec["spans"])
    old = {name: metric(name, run) for name in SIX}
    assert old.pop("path_host_ops_ms") is None
    assert sum(old.values()) == pytest.approx(wall_ms, abs=1e-6)
    # as this program leaves it: six, the same sum, the writer's time split
    run = run_of(with_ops(rec["spans"], 0.8))
    new = {name: metric(name, run) for name in SIX}
    assert sum(new.values()) == pytest.approx(wall_ms, abs=1e-6)
    assert new["path_host_ops_ms"] + new["path_shuffle_ms"] == pytest.approx(
        old["path_shuffle_ms"], abs=1e-6)
    assert new["path_host_ops_ms"] > 0 and all(v >= 0 for v in new.values())
    # the client's own clock around collect() holds the root span and a little more
    assert 0 <= rec["collect_s"] * 1e3 - wall_ms < max(1.0, 1e-3 * wall_ms)
    assert metric("host_ops_ms", run) > 0 and metric("partition_ms", run) >= 1.0


@pytest.mark.parametrize("cell, i", CASES)
def test_the_benchmarks_copy_of_the_rule_equals_the_programs(cell, i):
    tracing = pytest.importorskip("ballista_tpu.tracing")
    spans = RECORDS[cell][i]["spans"]
    theirs = tracing.critical_path(spans)
    assert theirs["segments"] == path.critical_path(spans)
    assert theirs["seconds"] == path.by_name(path.critical_path(spans))
    assert (path.ROOT, path.WAITING, path.TOLERANCE_S) == (
        "bt.client.collect", ("bt.client.wait", "bt.sched.stage"), tracing.PATH_TOLERANCE_S)


def test_what_the_recorded_paths_read():
    """The issue's table (ms a query, PR 35's chip runs): the numbers the next
    perf_opt issues start from, held so that an edit to the rule shows."""
    def ms(cell, i, name):
        return round(1e3 * path.by_name(path.critical_path(RECORDS[cell][i]["spans"]))
                     .get(name, 0.0), 1)

    first = {c: [r["query"] for r in RECORDS[c]] for c in RECORDS}
    assert first == {"join_sort_hot": ["q3", "q5"], "scan_agg_hot": ["q1", "q6"],
                     "sort_agg_hot": ["q18"], "window_hot": ["h2o_q8"],
                     "exec_per_chip_x4": ["q3", "q5"]}
    q8 = ("window_hot", 0)
    assert ms(*q8, "bt.shuffle.write") == 3814.8 and ms(*q8, "bt.device.exec") == 4867.1
    assert ms(*q8, "bt.shuffle.read") == 1344.4 and ms(*q8, "bt.shuffle.commit") == 932.6
    assert ms(*q8, "bt.window.keys") + ms(*q8, "bt.window.emit") == pytest.approx(7304.2, abs=0.11)
    # with four slots the wait is not charged stage 1's 4.35 s: 0.8 ms of poll
    assert ms(*q8, "bt.client.wait") < 2.0
    x4 = [path.by_name(path.critical_path(r["spans"])) for r in RECORDS["exec_per_chip_x4"]]
    for seconds in x4:  # what only a deployment of several processes waits for
        assert {"bt.task.launch", "bt.sched.stage", "bt.client.wait"} <= set(seconds)
        sched = sum(seconds.get(n, 0.0) for n in json.load(open(os.path.join(
            BENCH, "metrics", "path_sched_ms.json")))["args"]["names"])
        assert 0.02 < sched < 0.07


def test_the_eight_metrics_are_declared_as_the_issue_names_them():
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    declared = {m["name"]: m for m in bench["per_layer"]}
    for name in EIGHT:
        m = declared[name]
        assert (m["unit"], m["better"], m["source"], m["moves"]) == (
            "ms", "lower", "program_span", "hot_query_s") and "workloads" not in m
    # the six partition the span names: no name in two of them
    listed = [n for name in SIX for n in json.load(open(os.path.join(
        BENCH, "metrics", f"{name}.json")))["args"].get("names", [])]
    assert len(listed) == len(set(listed)) and path.WRITE not in listed
    client = json.load(open(os.path.join(BENCH, "metrics", "path_client_ms.json")))["args"]
    assert set(client["but"]) == set(listed) | {path.WRITE}
