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


@pytest.mark.parametrize("chips,busy_ns", [(1, 75), (2, 75), (3, 50), (4, 37.5)])
def test_every_chip_the_cell_asked_for_counts(chips, busy_ns):
    """A chip that idled through the window is idle, not absent: the planes'
    busy time is shared among the chips asked for (or the planes that ran
    anything, where a process saw more); the gaps stay the busiest plane's."""
    spans = [(0, 100, "q1")]
    devices = {"a:/device:TPU:0": [(0, 50, "op")], "b:/device:TPU:0": [(0, 100, "op")],
               "c:/device:TPU:0": [(500, 10, "after_the_window")], "d:/device:TPU:0": []}
    got = tr.reduce_trace(devices, spans, ["q1"], chips)
    assert got["busy_s"] == pytest.approx(busy_ns * 1e-9)
    assert got["chips"] == chips and got["device_planes"] == 2
    assert got["gaps_plane"] == "b:/device:TPU:0" and got["idle_gaps"] == []
    assert got["plane_busy_s"] == {"a:/device:TPU:0": 50e-9, "b:/device:TPU:0": 100e-9,
                                   "c:/device:TPU:0": 0.0, "d:/device:TPU:0": 0.0}


def raw(start_wall_ns, events, spans=()):
    return {"devices": {"/device:TPU:0": list(events)}, "spans": list(spans),
            "start_wall_ns": start_wall_ns}


def test_files_of_several_processes_are_one_trace_on_the_wall_clock():
    """Each file counts from its own session's start; the marks, where no
    file has any, are the record's wall-clock ones."""
    wall = 1_790_000_000_000_000_000
    files = {"x": raw(wall + 1000, [(100, 50, "op")]), "y": raw(wall, [(1200, 100, "op")])}
    marks = [(wall + 1000, 400, "q1"), (wall + 1000, 100, "sql"), (wall + 1100, 300, "collect")]
    got = tr.merge_files(files, marks, ["q1"])
    assert got["marks"] == "record" and got["base_wall_ns"] == wall
    assert got["devices"] == {"x:/device:TPU:0": [(1100, 50, "op")],
                              "y:/device:TPU:0": [(1200, 100, "op")]}
    assert got["spans"] == [(1000, 400, "q1"), (1000, 100, "sql"), (1100, 300, "collect")]
    reduced = tr.reduce_trace(got["devices"], got["spans"], ["q1"], chips=2)
    assert reduced["window_s"] == pytest.approx(400e-9)
    assert reduced["busy_s"] == pytest.approx((50 + 100) / 2 * 1e-9)
    assert dict(map(tuple, reduced["idle_gaps"])) == {"q1.sql": pytest.approx(100e-9),
                                                      "q1.collect": pytest.approx(200e-9)}


def test_a_file_with_its_own_marks_keeps_them_and_its_clock():
    own = [(10.5, 80, "q1")]
    got = tr.merge_files({"x": raw(123456789, [(20.25, 5, "op")], own)},
                         [(999, 1, "q1")], ["q1"])
    assert got["marks"] == "annotations" and got["spans"] == own
    assert got["devices"] == {"x:/device:TPU:0": [(20.25, 5, "op")]}
    # one file that does not say when it starts still reduces by its own marks,
    # two cannot be told apart in time, and marks from the record need a start
    assert tr.merge_files({"x": raw(None, [], own)}, [], ["q1"])["spans"] == own
    assert tr.merge_files({"x": raw(None, [(0, 1, "op")])}, [(999, 1, "q1")], ["q1"])["spans"] == []
    with pytest.raises(ValueError, match="cannot be merged"):
        tr.merge_files({"x": raw(None, []), "y": raw(5, [])}, [], ["q1"])


# what the parent of the PR that let a trace be several files reduced the
# recorded file to, digit for digit
RECORDED = {
    "window_s": 0.837014378, "busy_s": 0.001282859, "device_planes": 1, "device_events": 2616,
    "queries_traced": 6,
    "device_ops": [["jit_raw(..259011)/fusion.30", 6.9296e-05],
                   ["jit_raw(..521708)/compare_and_fusion", 5.8976e-05],
                   ["jit_raw(..521708)/fusion.32", 3.1012e-05],
                   ["jit_raw(..259011)/fusion.22", 2.8611e-05],
                   ["jit_raw(..521708)/compare_and_fusion.1", 2.2737e-05],
                   ["jit_raw(..521708)/select_reduce_fusion.11", 2.1005e-05],
                   ["jit_raw(..521708)/fusion.60", 2.0162e-05],
                   ["jit_raw(..521708)/fusion.58", 2.0161e-05],
                   ["jit_raw(..521708)/fusion.45", 2.016e-05],
                   ["jit_raw(..521708)/fusion.63", 2.0159e-05]],
    "idle_gaps": [["q1.collect", 0.656246337], ["q6.collect", 0.174844835],
                  ["q1.sql", 0.002925838], ["q6.sql", 0.00122149],
                  ["between_queries", 0.000397619], ["q1", 7.7981e-05], ["q6", 1.7419e-05]]}


def test_one_file_with_annotations_reduces_as_before():
    path = os.path.join(os.path.dirname(__file__), "data", "tiny.xplane.pb")
    raw = tr.read_xplane(path, ["q1", "q6"])
    assert raw["start_wall_ns"] == 1790773304535639117  # the file's `Task Environment`
    merged = tr.merge_files({"host": raw}, [(1, 1, "q1")], ["q1", "q6"])
    assert merged["marks"] == "annotations" and merged["spans"] == raw["spans"]
    assert merged["devices"] == {"host:/device:TPU:0": raw["devices"]["/device:TPU:0"]}
    got = tr.reduce_trace(merged["devices"], merged["spans"], ["q1", "q6"], chips=1)
    assert {k: got[k] for k in RECORDED} == RECORDED
    assert got["gaps_plane"] == "host:/device:TPU:0" and got["chips"] == 1
    assert got["plane_busy_s"] == {"host:/device:TPU:0": RECORDED["busy_s"]}


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
