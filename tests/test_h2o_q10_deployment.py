"""h2o db-benchmark question 10 as the benchmark deploys it
(`bench/configs/h2o_g1_1e7_1chip.json`, cell `groupby_q10_hot`), at N = 2e5,
K = 100 on the CPU backend: the configuration's table and columns from the
benchmark's generator, the configuration's session keys through
`SessionContext.standalone`, the answer held to the plain reference
(`bench/queries/h2o_q10.py`) by the comparison and the limits that decide the
cell's `correct` — the rows as a set, the SQL promises no order — and the
float32 control refused by them. Beside the answer: six keys (three of them
dictionary strings) and about a group a row go through the sorted path and
the final family's merge with nothing declined, every sorted-path record
carries the capacity it was compiled for, at least its groups, and the final
family's dispatch leaves a record of its own."""

import importlib
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
SCALE, SEED = 0.02, 2**31 + 39
N, K = 200_000, 100


def _bench():
    sys.path.insert(0, BENCH)  # bench/ is no package of the program: its `lib` by path
    try:
        return (importlib.import_module("lib.generator_h2o_groupby"),
                importlib.import_module("lib.topology_standalone_1chip"))
    finally:
        sys.path.remove(BENCH)


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """q10 twice over the configuration's files (the second hot): the answer,
    the reference's and the control's, the hot query's stage records and
    spans, and what STAGE_OUTCOMES counted."""
    import ballista_tpu.ops.tpu.stage_compiler as sc

    generator, topology = _bench()
    with open(os.path.join(BENCH, "configs", "h2o_g1_1e7_1chip.json")) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "queries", "h2o_q10.sql")) as f:
        sql = f.read()
    data_dir = str(tmp_path_factory.mktemp("h2o_g1_1e7"))
    rows = generator.generate(data_dir, config, SCALE, SEED)
    session = topology.open_session(config, data_dir)
    try:
        session.sql(sql).collect()
        sc.RUN_STATS.clear()
        before = sc.STAGE_OUTCOMES.snapshot()
        got = session.sql(sql).collect().to_pandas()
        after = sc.STAGE_OUTCOMES.snapshot()
        stages = sc.RUN_STATS.stages()
    finally:
        topology.close_session(session)
    spans = [s for tag, job in stages.items() if tag.startswith("job_") for s in job["spans"]]
    return {"config": config, "rows": rows, "got": got, "generator": generator,
            "want": generator.answers(data_dir, config, ["h2o_q10"])["h2o_q10"],
            "control": generator.answers(data_dir, config, ["h2o_q10"], "float32")["h2o_q10"],
            "stages": stages, "spans": spans,
            "outcomes": {k: after[k] - before[k] for k in sc.StageOutcomes.KINDS},
            "recent": [str(r) for r in after["recent"]]}


def _within(numbers: dict, limits: dict) -> bool:
    return all(numbers[k] <= limits[k] for k in limits)


def test_the_answer_is_the_references_rows_as_a_set(served):
    want, got = served["want"], served["got"]
    assert served["rows"] == {"x": N}
    assert list(got.columns) == ["id1", "id2", "id3", "id4", "id5", "id6", "v3", "count"]
    # the key space is 1e10 combinations here: nearly every row is its own group
    assert 0.99 * N < len(want) <= N and want["count"].sum() == N
    numbers = served["generator"].compare(got, want)
    assert numbers == {"rows_off": 0, "cells_off": 0, "rel_err": 0.0}
    assert _within(numbers, served["config"]["limits"])


def test_the_float32_control_is_refused_by_rel_err_alone(served):
    numbers = served["generator"].compare(served["control"], served["want"])
    assert numbers["rows_off"] == 0 and numbers["cells_off"] == 0
    assert 1e-9 < numbers["rel_err"] < 1e-6  # six decimals below 100 move by ~4e-8 in float32
    assert not _within(numbers, served["config"]["limits"])


def test_the_comparison_sees_a_changed_cell(served):
    """An altered key, count or sum is caught whatever the order it comes in."""
    compare, want = served["generator"].compare, served["want"]
    for column, value in (("id3", "id9999999999"), ("count", 7), ("v3", -1.0)):
        bad = served["got"].sample(frac=1.0, random_state=3).reset_index(drop=True)
        bad.loc[5, column] = value
        assert not _within(compare(bad, want), served["config"]["limits"]), column
    # a frame compared once and then changed in place is compared again
    again = served["got"].copy()
    assert _within(compare(again, want), served["config"]["limits"])
    assert _within(compare(again.copy(), want), served["config"]["limits"])  # equal: read again
    again.loc[7, "count"] = 9
    assert not _within(compare(again, want), served["config"]["limits"])


def test_every_stage_ran_on_the_device(served):
    outcomes = served["outcomes"]
    assert outcomes["device"] >= 2  # the partial stage and the final family's merge
    assert outcomes["declined"] == outcomes["error"] == 0
    assert not any("group capacity overflow" in r for r in served["recent"])


def test_every_sorted_record_carries_its_capacity(served):
    partial = [r for t, r in served["stages"].items()
               if t.startswith("stage_") and "sorted_groups" in r]
    assert partial, "q10's partial stage takes the sorted path"
    groups = len(served["want"])
    for rec in partial:
        assert rec["sorted_capacity"] >= rec["sorted_groups"]
        assert rec["sorted_capacity"] >= rec["sorted_rows_ordered"] >= rec["sorted_rows_live"]
    assert sum(r["sorted_groups"] for r in partial) == groups
    dispatch = [s[7] for s in served["spans"]
                if s[0] == "bt.stage.dispatch" and s[7].get("family") == "partial"]
    assert sorted(d["sorted_capacity"] for d in dispatch) == sorted(
        r["sorted_capacity"] for r in partial)


def test_the_final_familys_dispatch_leaves_a_record(served):
    final, = (r for t, r in served["stages"].items() if t.startswith("final_"))
    assert final["final_groups"] == len(served["want"]) <= final["sorted_capacity"]
    # none of the keys the partial stages' counters sum: the spans time it
    assert "dispatches" not in final and "exec_s" not in final
    dispatch, = (s[7] for s in served["spans"]
                 if s[0] == "bt.stage.dispatch" and s[7].get("family") == "final")
    assert dispatch["sorted_capacity"] == final["sorted_capacity"]


def test_no_compaction_scatters_a_64_bit_lane(served):
    """The final merge's int64 lanes — `id4`, `id5`, `id6` (widened) and
    `count` — are scattered as 32-bit halves, its float64 `sum(v3)` gathered
    at the segments' ends; the partial stage compacts the same lanes the
    same way. The counts are on the records and the dispatch spans."""
    final, = (r for t, r in served["stages"].items() if t.startswith("final_"))
    assert (final["compact_split_lanes"], final["compact_gathered_lanes"]) == (4, 1)
    partial, = (r for t, r in served["stages"].items()
                if t.startswith("stage_") and "sorted_groups" in r)
    assert (partial["compact_split_lanes"], partial["compact_gathered_lanes"]) == (4, 1)
    dispatch = {s[7]["family"]: s[7] for s in served["spans"] if s[0] == "bt.stage.dispatch"}
    for family, rec in (("final", final), ("partial", partial)):
        for key in ("compact_split_lanes", "compact_gathered_lanes"):
            assert dispatch[family][key] == rec[key], (family, key)
