"""The chips of a cell may live in other processes: bench/cell.py driven end to
end (--rehearse: the CPU backend) over the fake topology `holders` (fake_lib/,
clock_experiment.py): two holder processes that each trace a jitted loop, the
process that runs the queries never importing jax, their two trace files
reduced as one trace by the record's wall-clock marks."""

import os

import pytest

import clock_experiment as ce
import run
from lib import trace_reduce


def cpu_device_lines(plane):
    """The CPU backend has no device plane: a holder's bursts are the thunk
    executor's events on the client's thread of its host plane."""
    if plane.name != "/host:CPU":
        return None
    return [e for line in plane.lines if line.name.startswith("tf_XLAPjRtCpuClient")
            for e in line.events if e.name.startswith("ThunkExecutor::Execute")], ()


@pytest.fixture(scope="module")
def driven(tmp_path_factory):
    out_dir = str(tmp_path_factory.mktemp("holders"))
    done = ce.drive_cell(out_dir, 2, {"q1": [0], "q6": [0]}, {"size": 256, "iters": 100},
                         seconds=0.3, rehearse=True)
    assert done.returncode == 0, done.stderr[-3000:]  # also: "jax" not in its sys.modules
    return out_dir


def test_the_cell_ran_on_chips_it_does_not_hold(driven, monkeypatch):
    monkeypatch.setattr(trace_reduce, "device_lines", cpu_device_lines)
    record = run.load_json(os.path.join(driven, "cell.json"))
    assert record["device"] == {"platform": "cpu", "kind": "cpu", "count": 2}
    assert set(record["memory_peaks"]) == {"holder0", "holder1"}
    assert record["traced"]["rounds"] == 2 and not any(e["failed"] for e in record["executions"])
    got = ce.compare(driven, chips=2)
    reduced = got["reduced"]
    assert got["marks"] == "record" and reduced["queries_traced"] == 4
    assert len(got["file_starts_wall_ns"]) == 2 and len(reduced["plane_busy_s"]) == 2
    busy, idle = sorted(reduced["plane_busy_s"], key=reduced["plane_busy_s"].get, reverse=True)
    assert busy.startswith("holder0/") and idle.startswith("holder1/")
    assert reduced["gaps_plane"] == busy and reduced["device_planes"] == 1
    # the idle holder's chip is idle, not absent: half of the busy one's time
    assert reduced["plane_busy_s"][idle] == 0 and reduced["chips"] == 2
    assert reduced["busy_s"] == pytest.approx(reduced["plane_busy_s"][busy] / 2)
    # every burst where its holder said it was, to the CPU backend's few ms,
    # and the window is the record's: first start to last end of the traced queries
    numbers = got["planes"][busy]
    assert numbers["bursts_said"] == numbers["stretches_traced"] == 4
    assert all(abs(ms) < 20 for ms in numbers["start_off_ms"] + numbers["end_off_ms"])
    assert numbers["traced_busy_s"] == pytest.approx(numbers["said_busy_s"], rel=0.25)
    assert abs(got["planes"][idle]["sync_start_off_ms"]) < 20
    wall = run.wall_spans(record)
    assert reduced["window_s"] == pytest.approx(
        (max(s + d for s, d, _ in wall) - min(s for s, _, _ in wall)) / 1e9)
    assert not os.path.exists(os.path.join(driven, "trace"))  # reduce_traces removed it


def test_without_a_tpu_among_the_holders_the_cell_refuses(tmp_path):
    done = ce.drive_cell(str(tmp_path / "out"), 2, {"q1": [0], "q6": [0]},
                         {"size": 64, "iters": 1}, seconds=0.1, rehearse=False)
    assert done.returncode == 2 and "No CPU fallback" in done.stderr
    assert not os.path.exists(tmp_path / "out" / "cell.json")
