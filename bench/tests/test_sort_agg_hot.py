"""The cell `sort_agg_hot` (`tpch_sortagg_1chip` x [q18]), whole, on the CPU
backend (--rehearse): the new data files read — configuration, traffic, query,
reference, the two metrics — the run ends `correct`, and its traced line
carries `q18_hot_s`, `sorted_groups_out` and every per-layer metric that lists
no cells. By hand, with the rest of bench/tests; no time in it means anything."""

import json
import os
import subprocess
import sys

import pytest

from run import BENCH, ROOT, load_json

CELL = "sort_agg_hot"
SCALE = 0.05
# the three that need a chip's trace or its memory_stats(): the CPU backend has
# no device plane and reports no memory
NEEDS_A_CHIP = {"stage_roofline", "device_idle_pct", "hbm_peak_gb"}


@pytest.fixture(scope="module")
def rehearsal():
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL, "--seed", "2147483732",
         "--seconds", "1", "--trace", "1", "--rehearse", str(SCALE)],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(l) for l in proc.stdout.strip().splitlines() if l.startswith("{")]
    return lines[-1]["rehearsal"], lines, proc.stderr


def test_the_whole_run_is_correct(rehearsal):
    line, lines, err = rehearsal
    assert line["correct"] and line["failed"] == 0 and line["attempted"] >= 2
    assert line["device"]["platform"] == "cpu"
    assert {k: v["value"] for k, v in line["checks"].items()} == {
        "rel_err": pytest.approx(0.0, abs=1e-10), "cells_off": 0, "rows_off": 0, "unanswered": 0}
    assert "check rel_err" in err
    datagen = next(l for l in lines if l.get("phase") == "datagen")
    assert set(datagen["rows"]) == {"lineitem", "orders", "customer"}
    window = next(l for l in lines if l.get("phase") == "window")
    assert window["compared"] == line["attempted"] and set(window["query_seconds"]) == {"q18"}


def test_the_line_carries_the_cells_metrics(rehearsal):
    line, lines, _ = rehearsal
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    mine = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [CELL])}
    assert {"q18_hot_s", "sorted_groups_out"} <= mine
    assert set(line["metrics"]) == mine - NEEDS_A_CHIP
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    assert metrics["q18_hot_s"] > 0
    # a group an order from the subquery's stage, a group a row of the answer from the outer one
    orders = next(l for l in lines if l.get("phase") == "datagen")["rows"]["orders"]
    assert orders == 1_500_000 * SCALE
    assert orders < metrics["sorted_groups_out"] <= orders + 100
    # two partial device stages a query; nothing fell back, nothing compiled in the window
    assert metrics["dispatches_per_query"] == 2.0
    assert metrics["off_device_stages"] == 0 and metrics["window_compiles"] == 0


def test_the_configuration_is_the_join_cells_layout_and_the_query_tpch_text():
    """What `test_files.py` does not hold for every configuration: this one is
    `tpch_join_1chip`'s deployment with other tables, and its SQL is TPC-H's."""
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("tpch_sortagg_1chip", CELL, 1)
    config = load_json(os.path.join(BENCH, "configs", "tpch_sortagg_1chip.json"))
    joins = load_json(os.path.join(BENCH, "configs", "tpch_join_1chip.json"))
    for key in ("generator", "topology", "chips", "num_executors", "session", "guarantees",
                "limits", "files_per_table"):
        assert config[key] == joins[key], key
    assert sorted(config["reduced"]) == ["columns", "scale"] and config["scale"] == 1
    with open(os.path.join(BENCH, "queries", "q18.sql")) as f, \
            open(os.path.join(ROOT, "benchmarks", "tpch", "queries", "q18.sql")) as g:
        assert f.read() == g.read()
