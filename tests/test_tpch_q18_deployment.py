"""TPC-H Q18 as the benchmark deploys it (`bench/configs/tpch_sortagg_1chip.json`,
cell `sort_agg_hot`), at SF0.05 on the CPU backend: the configuration's own
tables and columns from the benchmark's generator, the configuration's session
keys through `SessionContext.standalone`, the answer held to the plain
reference (`bench/queries/q18.py`) by the comparison and the limits that decide
the cell's `correct` — and the float32 control refused by them. Beside the
answer: the subquery's stage takes the sorted path's top tier and the outer
stage its compact one, each reports the groups it handed to the host
(`sorted_groups`), and no stage fell back."""

import importlib
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
SCALE, SEED = 0.05, 32


@pytest.fixture(scope="module")
def served(tmp_path_factory):
    """Q18 served once over the configuration's files: the answer, the
    reference's and the control's, the two sorted stages' records (subquery
    first), the `bt.decode` spans' numbers and what `STAGE_OUTCOMES` counted."""
    import ballista_tpu.ops.tpu.stage_compiler as sc

    sys.path.insert(0, BENCH)  # bench/ is no package of the program: its `lib` by path
    try:
        generator = importlib.import_module("lib.generator_tpch")
        topology = importlib.import_module("lib.topology_standalone_1chip")
    finally:
        sys.path.remove(BENCH)
    with open(os.path.join(BENCH, "configs", "tpch_sortagg_1chip.json")) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "workloads", "sort_agg_hot.json")) as f:
        queries = json.load(f)["queries"]
    with open(os.path.join(BENCH, "queries", "q18.sql")) as f:
        sql = f.read()
    data_dir = str(tmp_path_factory.mktemp("tpch_sortagg"))
    rows = generator.generate(data_dir, config, SCALE, SEED)
    session = topology.open_session(config, data_dir)
    try:
        session.sql(sql).collect()  # cold; its record also takes spans closed outside any job
        sc.RUN_STATS.clear()
        before = sc.STAGE_OUTCOMES.snapshot()
        got = session.sql(sql).collect().to_pandas(date_as_object=False)
        after = sc.STAGE_OUTCOMES.snapshot()
        stages = sc.RUN_STATS.stages()
    finally:
        topology.close_session(session)
    records = sorted((r for r in stages.values() if "sorted_rows_ordered" in r),
                     key=lambda r: -r["sorted_rows_live"])
    spans = [s[7] for tag, job in stages.items() if tag.startswith("job_")
             for s in job["spans"] if s[0] == "bt.decode" and "sorted_groups" in s[7]]
    return {
        "config": config, "rows": rows, "got": got, "generator": generator,
        "want": generator.answers(data_dir, config, queries)["q18"],
        "control": generator.answers(data_dir, config, queries, "float32")["q18"],
        "order_keys": generator.reference.load_tables(
            data_dir, {"lineitem": ["l_orderkey"]})["lineitem"].l_orderkey.nunique(),
        "records": records, "spans": sorted(spans, key=lambda n: -n["sorted_groups"]),
        "merges": [r for t, r in stages.items() if t.startswith("final_") and "final_groups" in r],
        "outcomes": {k: after[k] - before[k] for k in sc.StageOutcomes.KINDS},
    }


def _within(numbers: dict, limits: dict) -> bool:
    return all(numbers[k] <= limits[k] for k in limits)


def test_the_answer_is_the_references(served):
    want = served["want"]
    assert 0 < len(want) < 100, "a seed at which HAVING keeps some orders and LIMIT does not bind"
    numbers = served["generator"].compare(served["got"], want)
    assert numbers["rows_off"] == 0 and numbers["cells_off"] == 0 and numbers["rel_err"] <= 1e-10
    assert _within(numbers, served["config"]["limits"]), "the configuration's own limits"


def test_the_float32_control_is_refused(served):
    """The reference computed in float32 is not `correct`: sum(l_quantity) is
    exact there too, `o_totalprice` (cents of hundreds of thousands) is not."""
    numbers = served["generator"].compare(served["control"], served["want"])
    assert not _within(numbers, served["config"]["limits"])
    assert numbers["rel_err"] > 1e-9


@pytest.mark.parametrize("stage,divisor", [(0, 1), (1, 64)], ids=["subquery_top", "outer_compact"])
def test_each_stage_takes_its_tier(served, stage, divisor):
    """Both branches of the one `lax.switch` in one query: every row of the
    subquery's stage is alive (all `M` slots ordered), a handful survive the
    outer stage's joins (`M / 64`)."""
    assert len(served["records"]) == 2, "two sorted-path stages"
    rec = served["records"][stage]
    P, N = rec["table_shape"]
    assert rec["sorted_rows_ordered"] == P * N // divisor
    assert rec["sorted_rows_live"] <= rec["sorted_rows_ordered"]
    if divisor == 1:
        assert rec["sorted_rows_live"] == served["rows"]["lineitem"]


@pytest.mark.parametrize("stage", [0, 1], ids=["subquery", "outer"])
def test_sorted_groups_counts_what_left_the_device(served, stage):
    """`sorted_groups` on the stage's record and, with the capacity, on its
    `bt.decode` span: a group an order for the subquery, a group a row of the
    answer for the outer stage."""
    expected = (served["order_keys"], len(served["want"]))[stage]
    rec, numbers = served["records"][stage], served["spans"][stage]
    assert rec["sorted_groups"] == expected
    assert numbers["sorted_groups"] == expected
    assert expected <= numbers["sorted_capacity"] <= 1 << 22


def test_no_stage_fell_back(served):
    assert served["outcomes"]["device"] >= 2
    assert served["outcomes"]["declined"] == 0 and served["outcomes"]["error"] == 0


def test_no_compaction_scatters_a_64_bit_lane(served):
    """The subquery's merge compacts two int64 lanes — `l_orderkey` and
    `sum(l_quantity)` — each as two 32-bit scatters, and no float64 lane: the
    sorted stages' lanes are integer too, none of them gathered."""
    merge, = served["merges"]
    assert (merge["compact_split_lanes"], merge["compact_gathered_lanes"]) == (2, 0)
    for rec in served["records"]:
        assert rec["compact_split_lanes"] > 0 and rec["compact_gathered_lanes"] == 0
