"""Every data file loads, and BENCHMARK.json keeps to the contract's names."""

import glob
import json
import os
import re

import pytest

from run import BENCH, ROOT
from lib import load_attr, reference

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def load(path):
    with open(path) as f:
        return json.load(f)


BENCHMARK = load(os.path.join(ROOT, "BENCHMARK.json"))


@pytest.mark.parametrize("path", sorted(
    p for d in ("workloads", "configs", "metrics", "lib")
    for p in glob.glob(os.path.join(BENCH, d, "*.json"))))
def test_json_file_loads(path):
    assert isinstance(load(path), dict)


def test_names_units_and_lengths():
    b = BENCHMARK
    assert set(b) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    names = ([c["name"] for c in b["configs"]] + [w["name"] for w in b["workloads"]]
             + [w["config"] for w in b["workloads"]] + [w["traffic"] for w in b["workloads"]]
             + [k for c in b["configs"] for k in c["reduced"]]
             + [m["name"] for m in b["end_to_end"] + b["per_layer"]])
    assert all(NAME.match(n) for n in names), [n for n in names if not NAME.match(n)]
    metrics = b["end_to_end"] + b["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES, m
    for m in b["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.25
    for text in ([w["why"] for w in b["workloads"]] + [c["why"] for c in b["configs"]]
                 + [c["source"] for c in b["configs"]] + [m["layer"] for m in b["per_layer"]]):
        assert 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text, text
    assert 1 <= b["run_seconds"] <= 51
    assert len(json.dumps(b)) < 64 * 1024


def test_cells_configs_and_traffic_files_exist():
    configs = {c["name"]: c for c in BENCHMARK["configs"]}
    assert {w["config"] for w in BENCHMARK["workloads"]} == set(configs)
    for c in configs.values():
        assert c["file"].startswith("bench/")
        body = load(os.path.join(ROOT, c["file"]))
        assert body["source"] == c["source"] and set(c["reduced"]) == set(body["reduced"])
        assert os.path.exists(os.path.join(BENCH, "lib", f"generator_{body['generator']}.py"))
        assert os.path.exists(os.path.join(BENCH, "lib", f"topology_{body['topology']}.py"))
        assert set(body["limits"]) == {"rel_err", "cells_off", "rows_off"}
    for w in BENCHMARK["workloads"]:
        traffic = load(os.path.join(BENCH, "workloads", f"{w['traffic']}.json"))
        assert traffic["why"] == w["why"]
        assert w["chips"] == load(os.path.join(ROOT, configs[w["config"]]["file"]))["chips"]
        for q in traffic["queries"]:
            assert os.path.exists(os.path.join(BENCH, "queries", f"{q}.sql"))


def test_every_per_layer_metric_has_a_reader_file():
    """BENCHMARK.json alone says what a metric is and where it is read; its
    file says only how: a reader, `module:function`, and its arguments."""
    cells = {w["name"] for w in BENCHMARK["workloads"]}
    e2e = {m["name"] for m in BENCHMARK["end_to_end"]}
    on_disk = {os.path.basename(p)[:-5] for p in glob.glob(os.path.join(BENCH, "metrics", "*.json"))}
    assert on_disk == {m["name"] for m in BENCHMARK["per_layer"]}
    for m in BENCHMARK["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
        spec = load(os.path.join(BENCH, "metrics", f"{m['name']}.json"))
        assert set(spec) == {"reader", "args", "what"}
        assert callable(load_attr(spec["reader"]))
        assert m["moves"] in e2e and set(m.get("workloads", cells)) <= cells


def test_every_query_has_its_sql_and_its_reference():
    for path in glob.glob(os.path.join(BENCH, "queries", "*.sql")):
        assert callable(reference.query_answer(os.path.basename(path)[:-len(".sql")]))
