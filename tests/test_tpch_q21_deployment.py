"""TPC-H Q21 as the benchmark deploys it (`bench/configs/tpch_q21_1chip.json`,
cell `semi_anti_hot`), at SF0.05 on the CPU backend: the configuration's own
tables and columns from the benchmark's generator, its session keys through
`SessionContext.standalone`, the answer held to the benchmark's plain
reference (`bench/queries/q21.py`) and to the program's own oracle
(`testing/reference.q21`), which are derived apart. Beside the answer: the
query is one partial device stage, whose record counts the match lanes the
filtered EXISTS / NOT EXISTS unroll (`match_lanes`, `match_lane_slots`) and
those run at the live rows' tier (`tier_lanes`), and
the first query builds each join's build side under a `bt.join.build` span
while the second finds them all in the build cache (`build_hits`)."""

import importlib
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
SCALE, SEED = 0.05, 41


def _builds(stages: dict) -> list[dict]:
    return [s[7] for tag, job in stages.items() if tag.startswith("job_")
            for s in job["spans"] if s[0] == "bt.join.build"]


def _dispatches(stages: dict) -> list[dict]:
    return [s[7] for tag, job in stages.items() if tag.startswith("job_")
            for s in job["spans"] if s[0] == "bt.stage.dispatch" and "match_lanes" in s[7]]


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Q21 served twice over the configuration's files: each query's answer,
    stage records, spans and `STAGE_OUTCOMES` counts; the two references'
    answers; and the build sides' sizes, counted from the data."""
    import ballista_tpu.ops.tpu.stage_compiler as sc
    from ballista_tpu.testing import reference as oracle

    sys.path.insert(0, BENCH)  # bench/ is no package of the program: its `lib` by path
    try:
        generator = importlib.import_module("lib.generator_tpch")
        topology = importlib.import_module("lib.topology_standalone_1chip")
    finally:
        sys.path.remove(BENCH)
    with open(os.path.join(BENCH, "configs", "tpch_q21_1chip.json")) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "workloads", "semi_anti_hot.json")) as f:
        queries = json.load(f)["queries"]
    with open(os.path.join(BENCH, "queries", "q21.sql")) as f:
        sql = f.read()
    data_dir = str(tmp_path_factory.mktemp("tpch_q21"))
    generator.generate(data_dir, config, SCALE, SEED)
    sc.clear_device_caches()  # the first query must build every side
    # spans an earlier test closed outside any job would join the first
    # query's record: take them
    sc.RUN_STATS.take_job_spans(None)
    runs = []
    session = topology.open_session(config, data_dir)
    try:
        for _ in range(2):
            sc.RUN_STATS.clear()
            before = sc.STAGE_OUTCOMES.snapshot()
            got = session.sql(sql).collect().to_pandas(date_as_object=False)
            after = sc.STAGE_OUTCOMES.snapshot()
            stages = sc.RUN_STATS.stages()
            runs.append({
                "got": got, "stages": stages, "builds": _builds(stages),
                "dispatches": _dispatches(stages),
                "records": [r for t, r in stages.items() if "match_lanes" in r],
                "outcomes": {k: after[k] - before[k] for k in sc.StageOutcomes.KINDS},
            })
    finally:
        topology.close_session(session)
    tables = generator.reference.load_tables(data_dir, config["tables"])
    plain = {t: df.astype({c: str for c in df.columns if df[c].dtype == "category"})
             for t, df in tables.items()}
    l = plain["lineitem"]
    late = l[l.l_receiptdate > l.l_commitdate]
    return {
        "config": config, "generator": generator, "runs": runs,
        "want": generator.answers(data_dir, config, queries)["q21"],
        "oracle": oracle.q21(plain),
        "dup": {"right_semi": int(l.l_orderkey.value_counts().max()),
                "right_anti": int(late.l_orderkey.value_counts().max())},
        "build_rows": {"right_semi": len(l), "right_anti": len(late),
                       "inner": sorted([len(plain["supplier"]),
                                        int((plain["orders"].o_orderstatus == "F").sum()),
                                        int((plain["nation"].n_name == "SAUDI ARABIA").sum())])},
    }


def _within(numbers: dict, limits: dict) -> bool:
    return all(numbers[k] <= limits[k] for k in limits)


@pytest.mark.parametrize("run", [0, 1], ids=["cold", "hot"])
@pytest.mark.parametrize("want", ["want", "oracle"], ids=["bench_reference", "program_oracle"])
def test_the_answer_is_both_references(served, run, want):
    reference = served[want]
    assert 0 < len(reference) <= 100, "a seed at which some suppliers kept orders waiting"
    numbers = served["generator"].compare(served["runs"][run]["got"], reference)
    assert numbers == {"rows_off": 0, "cells_off": 0, "rel_err": 0.0}
    assert _within(numbers, served["config"]["limits"]), "the configuration's own limits"


@pytest.mark.parametrize("run", [0, 1], ids=["cold", "hot"])
def test_one_partial_stage_on_the_device(served, run):
    """The lineitem stage runs on the device, one dispatch; nothing declined or
    raised. (The final stage's few groups stay under the device row floor:
    `below_row_floor`, the policy, not a decline.)"""
    r = served["runs"][run]
    assert r["outcomes"]["device"] == 1 and len(r["records"]) == 1
    assert r["outcomes"]["declined"] == 0 and r["outcomes"]["error"] == 0
    assert r["records"][0]["dispatches"] == 1


@pytest.mark.parametrize("run", [0, 1], ids=["cold", "hot"])
def test_match_lanes_count_the_unrolled_lanes(served, run):
    """Three unique-key joins take a lane each; the filtered semi and anti
    joins one a row of their build key's most: l2's and l3's largest line
    count an order. The unique joins look up every row slot (the all-slot
    mask); the semi and anti lanes run behind the live-row switch
    (`tier_lanes`), at the tier the late lines of 'F' orders of Saudi
    suppliers pick (`probe_rows`), where supplier is looked up again for
    s_name, the group key: the record and its dispatch span say the same."""
    rec = served["runs"][run]["records"][0]
    dup = served["dup"]
    assert rec["match_lanes"] == 3 + dup["right_semi"] + dup["right_anti"]
    assert rec["tier_lanes"] == dup["right_semi"] + dup["right_anti"]
    P, N = rec["table_shape"]
    assert rec["probe_rows"] == P * N // 64
    assert rec["match_lane_slots"] == 3 * P * N + (rec["tier_lanes"] + 1) * rec["probe_rows"]
    assert rec["sorted_rows_live"] < rec["sorted_rows_masked"] <= rec["probe_rows"]
    span, = served["runs"][run]["dispatches"]
    assert (span["match_lanes"], span["tier_lanes"], span["match_lane_slots"]) == (
        rec["match_lanes"], rec["tier_lanes"], rec["match_lane_slots"])


def test_the_first_query_builds_each_side_once(served):
    """One `bt.join.build` span a build side, each with the side's rows and the
    layout its keys chose: the unique-key sides `direct`, l2 and l3 (several
    lines an order) `expansion`; their seconds and rows sum on the record."""
    builds = served["runs"][0]["builds"]
    assert len(builds) == 5
    by_type = {}
    for b in builds:
        by_type.setdefault(b["join_type"], []).append(b)
    assert sorted(by_type) == ["inner", "right_anti", "right_semi"]
    for kind in ("right_semi", "right_anti"):
        b, = by_type[kind]
        assert (b["rows"], b["dup"], b["layout"]) == (
            served["build_rows"][kind], served["dup"][kind], "expansion")
        assert b["bytes"] > 0
    inner = by_type["inner"]
    assert sorted(b["rows"] for b in inner) == served["build_rows"]["inner"]
    assert {(b["dup"], b["layout"]) for b in inner} == {(1, "direct")}
    rec = served["runs"][0]["records"][0]
    assert rec["build_rows"] == sum(b["rows"] for b in builds)
    assert rec["build_s"] > 0 and "build_hits" not in rec


def test_the_second_query_builds_nothing(served):
    """Every build side comes from the build cache: no span, a hit each."""
    assert served["runs"][1]["builds"] == []
    rec = served["runs"][1]["records"][0]
    assert rec["build_hits"] == 5
    assert "build_s" not in rec and "build_rows" not in rec
