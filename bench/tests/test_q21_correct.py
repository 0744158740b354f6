"""The control of the cell `semi_anti_hot` (TPC-H Q21): `correct` has to be
able to come out false there, and the float32 control cannot show it.

Every other configuration's limits are set between the program's reading and
the reference computed in float32 (bench/limits.py). Q21 reads no float
column — keys, dates, names and a count — so its float32 reference is its
float64 one and `correct` would hold for it. What stands in for it is the
fault the cell exists to catch: the filtered semi / anti lowering cut to its
first match lane. Each l1 row is then checked against one line of its order in
l2 and one late line in l3, not all of them, and the answer changes. The whole
run (bench/run.py, the cell's process in-process, SF0.05 on the CPU backend)
must read `correct` false, by `cells_off` or `rows_off`; the same run with the
lowering whole reads `correct` true. Beside them, a traced rehearsal of the
cell reports every per-layer metric the cell has to.
"""

import json
import os
import subprocess
import sys

import pytest

import cell
import run

CELL = "semi_anti_hot"
SCALE = "0.05"


def drive(capsys, seed, scale=SCALE):
    rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds", "0.5",
                   "--trace", "0", "--rehearse", scale], run_child=cell.main)
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])["rehearsal"]


def cut_to_one_lane(monkeypatch):
    """Every filtered semi / anti join's build reports one row a key: the
    lowering unrolls one match lane (a key's first build row) where it
    should unroll `dup`."""
    from ballista_tpu.ops.tpu import stage_compiler as sc

    real = sc.TpuStageExec._encode_build

    def first_lane_only(self, join, ctx, mesh, grace):
        bt = real(self, join, ctx, mesh, grace)
        if join.join_type in ("right_semi", "right_anti") and join.filter is not None:
            bt.dup = 1
        return bt

    monkeypatch.setattr(sc.TpuStageExec, "_encode_build", first_lane_only)


@pytest.fixture
def fresh_caches():
    """Builds and compiled programs live in the process: none of the cut
    lowering's may serve another run, nor another's this one."""
    from ballista_tpu.ops.tpu import stage_compiler as sc

    sc.clear_device_caches()
    yield
    sc.clear_device_caches()


def test_the_whole_lowering_is_correct(capsys, fresh_caches):
    line = drive(capsys, 2**31 + 21)
    assert line["correct"] is True and line["failed"] == 0
    assert {k: c["value"] for k, c in line["checks"].items()} == {
        "rel_err": 0.0, "cells_off": 0, "rows_off": 0, "unanswered": 0}


def test_one_match_lane_is_not_correct(monkeypatch, capsys, fresh_caches):
    cut_to_one_lane(monkeypatch)
    line = drive(capsys, 2**31 + 21)
    assert line["correct"] is False and line["failed"] == 0
    checks = line["checks"]
    assert checks["cells_off"]["value"] > 0 or checks["rows_off"]["value"] > 0
    assert checks["unanswered"]["value"] == 0


# the three that need a chip's trace or its memory_stats(), and the roofline
# share, which reads the device plane's operations: the CPU backend has none
NEEDS_A_CHIP = {"stage_roofline", "device_idle_pct", "hbm_peak_gb", "semi_anti_roofline"}


def test_the_traced_line_carries_the_cells_metrics():
    """A traced rehearsal (in a process of its own, as every run is) reports
    every per-layer metric the cell has to, the new ones with what Q21 at
    SF0.05 gives: a build-cache miss in the first round, 17 match lanes over
    every row slot of the window's queries."""
    proc = subprocess.run(
        [sys.executable, os.path.join(run.BENCH, "run.py"), "--workload", CELL,
         "--seed", str(2**31 + 22), "--seconds", "1", "--trace", "1", "--rehearse", SCALE],
        cwd=run.ROOT, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])["rehearsal"]
    assert line["correct"] is True
    bench = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
    mine = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [CELL])}
    assert {"build_s", "match_lane_slots", "semi_anti_roofline", "fill_s"} <= mine
    assert set(line["metrics"]) == mine - NEEDS_A_CHIP
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    assert metrics["build_s"] > 0 and metrics["off_device_stages"] == 0
    assert metrics["match_lane_slots"] > 17 * (1 << 19)  # 17 lanes over 8 x 2^16 slots
