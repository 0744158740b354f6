"""The configuration `h2o_g1_1chip` and its cell `window_hot`, by hand on the
CPU backend: the generator is the source's recipe and repeats itself, the
comparison can fail, the float32 control does, and a rehearsal's traced line
carries the cell's per-layer metrics. No time in it means anything."""

import hashlib
import json
import os
import subprocess
import sys

import numpy as np
import pandas as pd
import pyarrow.parquet as pq
import pytest

import cell
import limits
from lib import generator_h2o, window_work
from run import BENCH, ROOT, load_json

CELL = "window_hot"
CONFIG = load_json(os.path.join(BENCH, "configs", "h2o_g1_1chip.json"))
N, K = 200_000, 100
SCALE = N / CONFIG["rows_per_scale"]
# what needs a chip's trace or its memory_stats(): the CPU backend has no device plane
NEEDS_A_CHIP = {"stage_roofline", "device_idle_pct", "hbm_peak_gb", "window_roofline"}


def _digest(directory):
    h = hashlib.sha256()
    for name in sorted(os.listdir(directory)):
        with open(os.path.join(directory, name), "rb") as f:
            h.update(name.encode() + f.read())
    return h.hexdigest()


def _table(directory):
    return pq.read_table(os.path.join(directory, "x")).to_pandas()


def test_the_generator_repeats_itself_and_a_column_does_not_depend_on_the_others(tmp_path):
    seed = 2**31 + 8
    for d in ("a", "b"):
        assert generator_h2o.generate(str(tmp_path / d), CONFIG, SCALE, seed) == {"x": N}
    assert _digest(tmp_path / "a" / "x") == _digest(tmp_path / "b" / "x")
    assert len(os.listdir(tmp_path / "a" / "x")) == CONFIG["files"] == 8
    generator_h2o.generate(str(tmp_path / "w"), CONFIG, SCALE, seed, whole=True)
    narrow, whole = _table(tmp_path / "a"), _table(tmp_path / "w")
    assert list(narrow.columns) == ["id6", "v3"] and list(whole.columns) == list(generator_h2o.COLUMNS)
    assert (narrow["id6"] == whole["id6"]).all() and (narrow["v3"] == whole["v3"]).all()
    generator_h2o.generate(str(tmp_path / "c"), CONFIG, SCALE, seed + 1)
    assert not (_table(tmp_path / "c")["v3"] == narrow["v3"]).all()


def test_the_columns_domains_are_the_sources(tmp_path):
    generator_h2o.generate(str(tmp_path), CONFIG, SCALE, 5, whole=True)
    x = _table(tmp_path)
    assert len(x) == N
    for col, high in (("id4", K), ("id5", K), ("id6", N // K), ("v1", 5), ("v2", 15)):
        assert x[col].dtype == np.int32 and x[col].min() == 1 and x[col].max() == high, col
    assert x["v3"].dtype == np.float64 and 0 <= x["v3"].min() and x["v3"].max() <= 100
    assert (np.round(x["v3"], 6) == x["v3"]).all() and x["v3"].nunique() > 0.99 * N
    assert x["id1"].nunique() == x["id2"].nunique() == K and x["id3"].nunique() <= N // K
    assert x["id1"].min() == "id001" and x["id1"].max() == "id100"
    assert x["id3"].str.fullmatch(r"id\d{10}").all()
    # about K rows a group of id6, the source's ratio at every scale
    sizes = x.groupby("id6").size()
    assert len(sizes) == N // K and 60 < sizes.min() and sizes.max() < 150


def test_a_program_without_what_the_configuration_needs_is_refused_at_once(tmp_path, monkeypatch, capsys):
    """The parent of PR 34 in the program's place: its kernels.py has no
    blocked scan, the run ends with exit code 4 before a row is generated."""
    (path, text), = CONFIG["needs"]["program"].items()
    with open(os.path.join(ROOT, path)) as f:
        assert text in f.read()  # this program has it
    os.makedirs(tmp_path / "root" / os.path.dirname(path))
    with open(tmp_path / "root" / path, "w") as f:
        f.write("def _segscan(jnp, values, boundary, func):\n    ...\n")
    monkeypatch.setattr(generator_h2o, "ROOT", str(tmp_path / "root"))
    with pytest.raises(SystemExit) as refused:
        generator_h2o.generate(str(tmp_path / "data"), CONFIG, SCALE, 1)
    assert refused.value.code == 4 and not os.path.exists(tmp_path / "data")
    assert "needs 'def segmented_scan('" in capsys.readouterr().err
    monkeypatch.setattr(generator_h2o, "ROOT", str(tmp_path / "nowhere"))
    with pytest.raises(SystemExit):
        generator_h2o.generate(str(tmp_path / "data"), CONFIG, SCALE, 1)


@pytest.fixture(scope="module")
def answer(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("h2o"))
    generator_h2o.generate(d, CONFIG, SCALE, 9)
    return generator_h2o.answers(d, CONFIG, ["h2o_q8"])["h2o_q8"]


def test_compare_takes_any_order_and_fails_what_is_wrong(answer):
    limits_ = CONFIG["limits"]

    def within(numbers):
        return all(numbers[k] <= limits_[k] for k in limits_)

    shuffled = answer.sample(frac=1.0, random_state=3).reset_index(drop=True)
    assert generator_h2o.compare(shuffled, answer) == {"rows_off": 0, "cells_off": 0, "rel_err": 0.0}
    second = answer.index[answer["id6"] == 7][-1]
    lacks = generator_h2o.compare(shuffled[shuffled.index != shuffled.index[
        (shuffled["id6"] == 7) & (shuffled["largest2_v3"] == answer.loc[second, "largest2_v3"])][0]],
        answer)
    assert lacks["rows_off"] == 1 and not within(lacks)
    extra = generator_h2o.compare(pd.concat([shuffled, shuffled.iloc[:1]]), answer)
    assert extra["rows_off"] == 1 and not within(extra)
    moved = shuffled.copy()
    moved.loc[11, "largest2_v3"] *= 1 + 1e-9  # some thousand float64 steps: beyond rel_err
    off = generator_h2o.compare(moved, answer)
    assert off["rows_off"] == 0 and 1e-10 < off["rel_err"] < 1e-8 and not within(off)
    near = shuffled.copy()
    near.loc[11, "largest2_v3"] = np.nextafter(near.loc[11, "largest2_v3"], 0)
    last_bit = generator_h2o.compare(near, answer)  # one step: inside the limit, and not 0.0
    assert 0 < last_bit["rel_err"] < 1e-15 and within(last_bit)
    wrong_group = shuffled.copy()
    wrong_group.loc[5, "id6"] += 1
    assert not within(generator_h2o.compare(wrong_group, answer))


def test_the_float32_control_is_not_correct(capsys):
    """bench/limits.py's whole flow at N = 2e5 on three seeds: the program's
    answers are `correct`, the control's are not, by `rel_err` alone."""
    rc = limits.main(["--workload", CELL, "--seeds", "1", "2", str(2**31 + 3),
                      "--seconds", "0.3", "--rehearse", str(SCALE)], run_child=cell.main)
    lines = [json.loads(l) for l in capsys.readouterr().out.strip().splitlines()]
    assert rc == 0 and lines[-1]["program_correct_and_control_not_on_every_seed"] is True
    limit = lines[-1]["limits_now"]["rel_err"]
    for line in lines[:3]:
        assert line["program_correct"] is True and line["control_correct"] is False
        assert line["program"]["rel_err"] == 0.0 and limit < line["control"]["rel_err"] < 1e-6
        assert line["control"]["rows_off"] == line["control"]["cells_off"] == 0
        assert line["control"]["unanswered"] == 0


def test_window_roofline_arithmetic():
    schema = load_json(os.path.join(BENCH, "lib", "schema_h2o.json"))
    rows = 1e8
    moved = window_work.stage_bytes(rows * 12, schema, "x", ["id6", "v3"], 8)
    assert moved == rows * 20
    trace = {"busy_s": 1.0, "plane_busy_s": {"/device:TPU:0": 1.0},
             "device_ops": [["jit_sort_lex_order(..1)/sort.1", 0.5],
                            ["jit_window_segscan_sum(..2)/fusion.3", 0.2],
                            ["jit_stage_partial_direct_fused_xla(..3)/fusion.1", 0.1]]}
    mods = ["jit_sort_lex_order", "jit_window_segscan_"]
    # the two listed, and the 0.2 s the list leaves unnamed
    assert window_work.device_seconds(trace, mods) == pytest.approx(0.9)
    assert window_work.device_seconds({**trace, "device_ops": trace["device_ops"][2:]}, mods) is None
    from lib.readers import Run
    run = Run(record={"traced": {"rounds": 1}}, trace=trace, round_bytes=rows * 12,
              peaks={"hbm_bytes_per_s": 819e9}, chips=1)
    share = window_work.roofline(run, "x", ["id6", "v3"], 8, "schema_h2o.json", mods)
    assert share == pytest.approx(100 * 2e9 / 819e9 / 0.9)
    assert window_work.roofline(Run(record={}, trace=None, round_bytes=1.0, peaks={}),
                                "x", ["id6", "v3"], 8, "schema_h2o.json", mods) is None


@pytest.fixture(scope="module")
def rehearsal():
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL, "--seed", "2147483734",
         "--seconds", "1", "--trace", "1", "--rehearse", str(SCALE)],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(l) for l in proc.stdout.strip().splitlines() if l.startswith("{")]
    return lines[-1]["rehearsal"], lines


def test_a_rehearsals_line_carries_the_cells_metrics(rehearsal):
    line, lines = rehearsal
    assert line["correct"] and line["failed"] == 0 and line["attempted"] >= 2
    assert {k: v["value"] for k, v in line["checks"].items()} == {
        "rel_err": 0.0, "cells_off": 0, "rows_off": 0, "unanswered": 0}
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    mine = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", [CELL])}
    new = {"h2o_q8_hot_s", "window_lanes_ordered", "window_host_ms", "window_roofline"}
    assert new <= mine
    assert set(line["metrics"]) == mine - NEEDS_A_CHIP
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    assert metrics["h2o_q8_hot_s"] > 0 and metrics["window_host_ms"] > 0
    # every window task's rows, rounded up to a power of two a task
    tasks = metrics["dispatches_per_query"]
    assert tasks >= 1 and N <= metrics["window_lanes_ordered"] < 2 * N + tasks
    assert metrics["off_device_stages"] == 0 and metrics["window_compiles"] == 0
    assert metrics["stage_exec_s"] > 0
    datagen = next(l for l in lines if l.get("phase") == "datagen")
    assert datagen["rows"] == {"x": N} and datagen["reduced"].keys() == {"columns"}


def test_the_cell_and_the_configuration_are_the_issues():
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    w = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (w["config"], w["traffic"], w["chips"]) == ("h2o_g1_1chip", CELL, 1)
    traffic = load_json(os.path.join(BENCH, "workloads", f"{CELL}.json"))
    assert (traffic["queries"], traffic["loop"], traffic["clients"], traffic["warmup_rounds"],
            traffic["trace_rounds"]) == (["h2o_q8"], "closed", 1, 0, 1)
    assert CONFIG["session"] == {"ballista.executor.engine": "tpu"}
    assert CONFIG["scale"] * CONFIG["rows_per_scale"] == 1e8 and CONFIG["k"] == 100
    assert CONFIG["tables"] == {"x": ["id6", "v3"]} and sorted(CONFIG["reduced"]) == ["columns"]
    with open(os.path.join(BENCH, "queries", "h2o_q8.sql")) as f:
        sql = " ".join(f.read().split()).lower()
    assert "row_number() over (partition by id6 order by v3 desc)" in sql
    assert "where v3 is not null" in sql and sql.endswith("where order_v3 <= 2")
