"""The cell `exec_per_chip_x4` over the topology `remote_x4`, whole, on the CPU
backend (--rehearse): `run.py` starts the scheduler and the four executor
processes through `cell.py`, the per-layer metrics come from the joined
record and the summed counters, and no process of a run outlives it — after a
clean end, a refused run and a killed child. By hand, with the rest of
bench/tests; no time in it means anything."""

import json
import os
import signal
import subprocess
import sys
import time

import pytest

from run import BENCH, ROOT, load_json

CELL = "exec_per_chip_x4"
# the four that need a chip's trace or its memory_stats(): the CPU backend has
# no device plane and reports no memory
NEEDS_A_CHIP = {"stage_roofline", "device_idle_pct", "hbm_peak_gb", "chip_balance_pct"}


def cluster_processes() -> list[str]:
    """Command lines of this checkout's scheduler and executor processes."""
    out = []
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                cmd = f.read().replace(b"\0", b" ").decode(errors="replace")
            cwd = os.readlink(f"/proc/{pid}/cwd")
        except OSError:
            continue
        if ("ballista_tpu.executor" in cmd or "ballista_tpu.scheduler" in cmd) and cwd == ROOT:
            out.append(cmd)
    return out


def run_py(*args, env=None, **kwargs):
    return subprocess.Popen(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL, *args], cwd=ROOT,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu", **(env or {})}, **kwargs)


@pytest.fixture(scope="module")
def rehearsal():
    assert not cluster_processes(), "a cluster of an earlier run is still alive"
    proc = run_py("--seed", "2147483777", "--seconds", "1", "--trace", "1", "--rehearse", "0.05")
    out, err = proc.communicate(timeout=600)
    assert proc.returncode == 0, err[-3000:]
    return json.loads(out.strip().splitlines()[-1])["rehearsal"], out, err


def test_the_whole_run_is_correct_and_leaves_no_process(rehearsal):
    line, _, err = rehearsal
    assert line["correct"] and line["failed"] == 0 and line["attempted"] >= 4
    assert line["device"]["platform"] == "cpu" and line["device"]["count"] == 4
    assert "check rel_err" in err
    assert not cluster_processes()


def test_every_per_layer_metric_is_on_the_line(rehearsal):
    line, _, _ = rehearsal
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    mine = [m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [CELL])]
    assert len(mine) == 21 + 5
    assert set(line["metrics"]) == set(mine) - NEEDS_A_CHIP
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    # every executor dispatches the whole stage once: four dispatches a device stage
    assert metrics["dispatches_per_query"] == 4.0
    assert metrics["off_device_stages"] == 0 and metrics["window_compiles"] == 0
    assert metrics["tasks_per_query"] >= 4 and metrics["task_launch_ms"] > 0
    assert metrics["sched_plan_ms"] > 0 and metrics["task_queue_ms"] > 0
    assert metrics["x4_q3_hot_s"] > 0 and metrics["x4_q5_hot_s"] > 0
    # one host: a reader opens another executor's shuffle file in place
    assert metrics["remote_fetch_ms"] == 0.0


def test_four_trace_files_were_reduced_as_one(rehearsal):
    _, out, _ = rehearsal
    trace = next(json.loads(l) for l in out.splitlines() if l.startswith('{"phase": "trace"'))
    assert trace["files"] == 4 and trace["marks"] == "record"


def test_chip_balance_reads_the_planes():
    from lib import chips
    from lib.readers import Run

    def run(planes, n=4):
        return Run(record={}, trace={"plane_busy_s": planes} if planes is not None else None,
                   round_bytes=0.0, peaks={}, chips=n)

    assert chips.balance(run({"a": 5.0, "b": 4.0, "c": 5.0, "d": 2.5})) == 50.0
    assert chips.balance(run({"a": 5.0, "b": 5.0})) == 0.0  # two chips of four ran nothing
    assert chips.balance(run({"a": 3.0}, n=1)) == 100.0
    assert chips.balance(run(None)) is None and chips.balance(run({"a": 0.0})) is None


def test_a_refused_run_leaves_no_process():
    # no --rehearse: four CPU devices are not four TPU chips
    proc = run_py("--seed", "3", "--seconds", "1", "--trace", "0")
    _, err = proc.communicate(timeout=600)
    assert proc.returncode == 2 and "No CPU fallback" in err
    assert not cluster_processes()


@pytest.mark.parametrize("victim", ["cell", "run"])
def test_a_killed_run_leaves_no_process(victim):
    """The child killed in mid-run takes its cluster with it (the executors'
    death signal), and a terminated run.py kills the child's group."""
    proc = run_py("--seed", "4", "--seconds", "30", "--trace", "0", "--rehearse", "0.05",
                  start_new_session=True)
    deadline = time.time() + 300
    while len(cluster_processes()) < 5:
        assert proc.poll() is None and time.time() < deadline, "the cluster never came up"
        time.sleep(0.5)
    if victim == "cell":
        cells = subprocess.run(["pgrep", "-f", "bench/cell.py"], capture_output=True, text=True)
        for pid in cells.stdout.split():
            os.kill(int(pid), signal.SIGKILL)
    else:
        proc.terminate()
    proc.communicate(timeout=120)
    assert proc.returncode != 0
    deadline = time.time() + 20
    while cluster_processes() and time.time() < deadline:
        time.sleep(0.5)
    assert not cluster_processes()
