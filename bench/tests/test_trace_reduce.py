"""lib/trace_reduce.py on hand-made intervals and on a small trace recorded on
a v5e chip (tests/data/tiny.xplane.pb: q1+q6 over TPC-H SF0.02, three rounds)."""

import os

import pytest

from lib import trace_reduce as tr

MS = 1_000_000  # the trace counts nanoseconds


def test_union_and_clip():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert tr.clip([(0, 3), (5, 8)], 2, 6) == [(2, 3), (5, 6)]


def test_self_seconds_takes_nested_operations_off_their_parent():
    events = [(0, 100, "while"), (10, 30, "fusion.1"), (50, 40, "fusion.2"),
              (55, 10, "copy"), (200, 20, "fusion.1"), (900, 50, "outside")]
    got = tr.self_seconds(events, 0, 500)
    assert got == {"while": 30, "fusion.1": 50, "fusion.2": 30, "copy": 10}
    assert sum(got.values()) == 120  # = the union of the intervals inside the window


def test_busy_idle_ops_and_gap_labels():
    spans = [(0, 100 * MS, "q1"), (0, 10 * MS, "sql"), (10 * MS, 90 * MS, "collect"),
             (120 * MS, 40 * MS, "q6"), (120 * MS, 5 * MS, "sql"), (125 * MS, 35 * MS, "collect")]
    devices = {"/device:TPU:0": [
        (20 * MS, 30 * MS, "fusion.a"), (60 * MS, 20 * MS, "fusion.b"),
        (130 * MS, 10 * MS, "fusion.a"), (500 * MS, 10 * MS, "after_the_window")]}
    got = tr.reduce_trace(devices, spans, ["q1", "q6"])
    assert got["window_s"] == pytest.approx(0.160)
    assert got["busy_s"] == pytest.approx(0.060)
    assert got["queries_traced"] == 2 and got["device_planes"] == 1
    assert got["device_ops"] == [["fusion.a", pytest.approx(0.040)], ["fusion.b", pytest.approx(0.020)]]
    # idle: 0-10 q1.sql; 10-20, 50-60, 80-100 q1.collect; 100-120 between the queries;
    # 120-125 q6.sql; 125-130 and 140-160 q6.collect
    assert dict(map(tuple, got["idle_gaps"])) == {
        "q1.sql": pytest.approx(0.010), "q1.collect": pytest.approx(0.040),
        "between_queries": pytest.approx(0.020), "q6.sql": pytest.approx(0.005),
        "q6.collect": pytest.approx(0.025)}
    assert sum(s for _, s in got["idle_gaps"]) == pytest.approx(got["window_s"] - got["busy_s"])


def test_two_chips_average_and_nothing_to_read():
    spans = [(0, 100, "q1")]
    devices = {"/device:TPU:0": [(0, 50, "a")], "/device:TPU:1": [(0, 100, "a")],
               "/device:TPU:2": []}
    assert tr.reduce_trace(devices, spans, ["q1"])["busy_s"] == pytest.approx(75e-9)
    assert tr.reduce_trace(devices, [], ["q1"]) is None
    assert tr.reduce_trace({"/device:TPU:0": []}, spans, ["q1"]) is None


def test_recorded_v5e_trace():
    path = os.path.join(os.path.dirname(__file__), "data", "tiny.xplane.pb")
    raw = tr.read_xplane(path, ["q1", "q6"])
    assert list(raw["devices"]) == ["/device:TPU:0"]
    got = tr.reduce_trace(raw["devices"], raw["spans"], ["q1", "q6"])
    assert got["queries_traced"] == 6 and got["device_planes"] == 1
    assert 0 < got["busy_s"] < got["window_s"]
    assert sum(s for _, s in got["idle_gaps"]) == pytest.approx(got["window_s"] - got["busy_s"])
    assert 0 < sum(s for _, s in got["device_ops"]) <= got["busy_s"] * 1.0001
    allowed = {"between_queries"} | {q + part for q in ("q1", "q6")
                                       for part in ("", ".sql", ".collect")}
    assert {label for label, _ in got["idle_gaps"]} <= allowed
    assert got["idle_gaps"][0][0] == "q1.collect"  # where the host waits longest
