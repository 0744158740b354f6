"""h2o db-benchmark question 8 as the benchmark deploys it
(`bench/configs/h2o_g1_1chip.json`, cell `window_hot`), at N = 2e5, K = 100 on
the CPU backend: the configuration's table and columns from the benchmark's
generator, the configuration's session keys through
`SessionContext.standalone`, the answer held to the plain reference
(`bench/queries/h2o_q8.py`) by the comparison and the limits that decide the
cell's `correct` — the rows as a set, the SQL promises no order — and the
float32 control refused by them. Beside the answer: every window partition ran
on the device, each task left a record of its own, and peers of equal `v3`
(forced by drawing it from 50 values) cannot fail the comparison."""

import importlib
import json
import os
import sys

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
SCALE, SEED = 0.002, 2**31 + 34
N, K = 200_000, 100


def _bench():
    sys.path.insert(0, BENCH)  # bench/ is no package of the program: its `lib` by path
    try:
        return (importlib.import_module("lib.generator_h2o"),
                importlib.import_module("lib.topology_standalone_1chip"))
    finally:
        sys.path.remove(BENCH)


def _serve(data_dir, config, sql):
    """q8 twice over the files under `data_dir` (the second hot): the answer,
    the stage records and spans it left, and what STAGE_OUTCOMES counted."""
    import ballista_tpu.ops.tpu.stage_compiler as sc

    _, topology = _bench()
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
    return {"got": got, "stages": stages,
            "outcomes": {k: after[k] - before[k] for k in sc.StageOutcomes.KINDS},
            "recent": [r for r in after["recent"] if r[0] == "window"]}


@pytest.fixture(scope="module")
def deployment():
    with open(os.path.join(BENCH, "configs", "h2o_g1_1chip.json")) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "queries", "h2o_q8.sql")) as f:
        sql = f.read()
    return config, sql


@pytest.fixture(scope="module")
def served(deployment, tmp_path_factory):
    config, sql = deployment
    generator, _ = _bench()
    data_dir = str(tmp_path_factory.mktemp("h2o_g1"))
    rows = generator.generate(data_dir, config, SCALE, SEED)
    out = _serve(data_dir, config, sql)
    out.update(rows=rows, generator=generator, config=config,
               want=generator.answers(data_dir, config, ["h2o_q8"])["h2o_q8"],
               control=generator.answers(data_dir, config, ["h2o_q8"], "float32")["h2o_q8"])
    return out


def _within(numbers: dict, limits: dict) -> bool:
    return all(numbers[k] <= limits[k] for k in limits)


def test_the_answer_is_the_references_rows_as_a_set(served):
    want, got = served["want"], served["got"]
    assert served["rows"] == {"x": N}
    assert list(got.columns) == ["id6", "largest2_v3"]
    # N / K groups of about K rows: every group is there, and gives two rows
    assert want["id6"].nunique() == N // K and len(want) == 2 * (N // K) == len(got)
    numbers = served["generator"].compare(got, want)
    assert numbers == {"rows_off": 0, "cells_off": 0, "rel_err": 0.0}
    assert _within(numbers, served["config"]["limits"])
    # the engine's own order is not the reference's: only the comparison's order lines them up
    assert not (got["id6"].to_numpy() == want["id6"].to_numpy()).all()


def test_the_float32_control_is_refused_by_rel_err_alone(served):
    numbers = served["generator"].compare(served["control"], served["want"])
    assert numbers["rows_off"] == 0 and numbers["cells_off"] == 0
    assert 1e-9 < numbers["rel_err"] < 1e-6  # six decimals below 100 move by ~4e-8 in float32
    assert not _within(numbers, served["config"]["limits"])


def test_every_window_partition_ran_on_the_device(served):
    outcomes = served["outcomes"]
    assert outcomes["device"] >= 1
    assert outcomes["declined"] == outcomes["error"] == outcomes["below_row_floor"] == 0
    assert served["recent"] and all(r[1] == "device" for r in served["recent"])
    records = {t: r for t, r in served["stages"].items() if t.startswith("window_")}
    # a record a window task, and together they saw every row once
    assert len(records) == outcomes["device"]
    assert sum(r["window_rows"] for r in records.values()) == N
    assert sum(r["window_segments"] for r in records.values()) == N // K
    for rec in records.values():
        assert rec["dispatches"] == 1 and rec["exec_s"] > 0
        # one device program a task: order, boundaries, scan, scatter; no separate scan
        assert rec["window_frames_fused"] == 1 and rec["window_scans"] == 0
        lanes = rec["window_lanes"]
        assert lanes >= rec["window_rows"] > lanes // 2 and lanes & (lanes - 1) == 0
        assert rec["device_bytes"] > 0 and rec["hbm_plan"] == "run_whole"


def test_peers_of_equal_v3_cannot_fail_the_comparison(deployment, tmp_path):
    """`v3` from 50 values over ~100 rows a group: the top two of most groups
    are tied with rows below them, and `row_number` picks among peers as it
    likes; the rows returned are equal whichever it picked."""
    config, sql = deployment
    generator, _ = _bench()
    rng = np.random.default_rng(SEED)
    os.makedirs(tmp_path / "x")
    table = pa.table({"id6": rng.integers(1, N // K + 1, N, dtype=np.int32),
                      "v3": np.round(rng.integers(0, 50, N) * 1.999999, 6)})
    for i in range(2):
        pq.write_table(table.slice(i * N // 2, N // 2), tmp_path / "x" / f"part-{i:03d}.parquet")
    out = _serve(str(tmp_path), config, sql)
    want = generator.answers(str(tmp_path), config, ["h2o_q8"])["h2o_q8"]
    # the forced ties are there: in most groups the second row's value is shared by a third
    df = table.to_pandas()
    third = df.sort_values("v3", ascending=False).groupby("id6").nth(2)
    second = want.groupby("id6").nth(1)
    assert (third.set_index("id6")["v3"].reindex(second["id6"]).to_numpy()
            == second["largest2_v3"].to_numpy()).mean() > 0.5
    assert generator.compare(out["got"], want) == {"rows_off": 0, "cells_off": 0, "rel_err": 0.0}
    assert out["outcomes"]["declined"] == out["outcomes"]["error"] == 0
