"""The group capacity of the two sort-based device families follows their
rows, on the CPU backend at a small size: a partial sorted-path stage holds
pow2 of its row slots, the final family's merge pow2 of its input stack, so a
stage whose every row is its own group fills its capacity and never overflows
it; what bounds the capacity is HBM admission, which prices the program's
[C] output lanes and ordering scratch and declines a stage past the budget
BEFORE it dispatches. Beside them, dictionary codes leave the device as one
take over the dictionary (`columnar.decode_codes`), equal to the per-group
lookup it replaced."""

import numpy as np
import pyarrow as pa
import pytest

from ballista_tpu.config import (
    BallistaConfig,
    EXECUTOR_ENGINE,
    TPU_HBM_BUDGET_BYTES,
    TPU_MIN_ROWS,
)

N_ROWS = 8000  # two partitions of 4000 rows: 2 x 4096 row slots


def _walk(n):
    yield n
    for c in n.children():
        yield from _walk(c)


def _table(live: int) -> pa.Table:
    """N_ROWS rows of distinct keys, `live` of them passing the filter: every
    live row its own group. In two batches, so two partitions of 4000."""
    alive = np.zeros(N_ROWS, dtype="int64")
    alive[np.random.default_rng(41).choice(N_ROWS, live, replace=False)] = 1
    t = pa.table({"k": np.random.default_rng(43).permutation(N_ROWS) * 5,
                  "s": pa.array([f"id{i % 997:05d}" for i in range(N_ROWS)]),
                  "v": np.arange(N_ROWS) % 13, "alive": alive})
    return pa.Table.from_batches(t.to_batches(max_chunksize=N_ROWS // 2))


def _serve(sql: str, table: pa.Table, extra=None):
    """`sql` on the tpu engine and on the cpu engine; the tpu run's stage
    records and what STAGE_OUTCOMES counted in it."""
    import ballista_tpu.ops.tpu.stage_compiler as sc
    from ballista_tpu.client.context import SessionContext

    out = {}
    for engine in ("tpu", "cpu"):
        ctx = SessionContext(BallistaConfig({EXECUTOR_ENGINE: engine, TPU_MIN_ROWS: 0,
                                             **(extra or {})}))
        ctx.register_arrow_table("t", table, partitions=2)
        if engine == "tpu":
            sc.RUN_STATS.clear()
            before = sc.STAGE_OUTCOMES.snapshot()
        out[engine] = ctx.sql(sql).collect()
        if engine == "tpu":
            after = sc.STAGE_OUTCOMES.snapshot()
            out["stages"] = sc.RUN_STATS.stages()
            out["outcomes"] = {k: after[k] - before[k] for k in sc.StageOutcomes.KINDS}
            out["recent"] = [str(r) for r in after["recent"]]
    return out


def _records(stages: dict, prefix: str) -> list[dict]:
    return [r for t, r in stages.items() if t.startswith(prefix)]


@pytest.mark.parametrize("live,slots", [(N_ROWS, 8192), (100, 128)],
                         ids=["top_tier", "compact_tier"])
def test_a_partial_stage_whose_groups_are_its_rows_fills_its_capacity(live, slots):
    """Groups = live rows, at either tier: the capacity holds them (pow2 of
    the slots the tier ordered), nothing declines, and the answer is the
    CPU engine's."""
    sql = "SELECT k, s, sum(v) AS sv, count(*) AS c FROM t WHERE alive = 1 GROUP BY k, s"
    out = _serve(sql, _table(live))
    rec, = (r for r in _records(out["stages"], "stage_") if "sorted_rows_ordered" in r)
    assert rec["sorted_rows_ordered"] == slots and rec["sorted_groups"] == live
    assert rec["sorted_capacity"] == 8192 >= rec["sorted_groups"]  # the stage's own slots
    assert out["outcomes"]["declined"] == out["outcomes"]["error"] == 0
    assert not any("group capacity overflow" in r for r in out["recent"])
    key = ["k", "s"]
    assert (out["tpu"].to_pandas().sort_values(key).reset_index(drop=True)
            .equals(out["cpu"].to_pandas().sort_values(key).reset_index(drop=True)))


def test_the_final_familys_merge_of_a_group_a_row_leaves_a_record():
    """The final family merges every partial row (a group each) on the
    device and leaves a stage record of its own: its capacity (pow2 of its
    input stack) holds them all."""
    sql = "SELECT k, s, sum(v) AS sv, count(*) AS c FROM t GROUP BY k, s"
    out = _serve(sql, _table(N_ROWS))
    final, = _records(out["stages"], "final_")
    P, N = final["table_shape"]
    assert final["final_groups"] == N_ROWS <= P * N <= final["sorted_capacity"]
    assert final["sorted_capacity"] & (final["sorted_capacity"] - 1) == 0
    # what sized the dispatch, and none of the keys the partial stages'
    # counters sum: `dispatches_per_query` and `stage_exec_s` read the
    # same stages they did before the final family had a record
    assert "dispatches" not in final and "exec_s" not in final
    assert final["hbm_plan"] == "run_whole"
    assert out["outcomes"]["declined"] == out["outcomes"]["error"] == 0
    assert out["tpu"].num_rows == N_ROWS


def test_each_task_of_a_served_final_stage_merges_its_own_partition(tmp_path):
    """A served final stage of several partitions goes out as a task a
    partition, and each task builds its own node over the shuffle's
    hash-placed partitions: each merges ITS partition alone on the device
    (one dispatch a task over a one-partition stack), never the whole
    stage, and the answer is the CPU engine's."""
    import pyarrow.parquet as pq

    import ballista_tpu.ops.tpu.stage_compiler as sc
    from ballista_tpu.client.context import SessionContext
    from ballista_tpu.config import (
        AQE_MIN_PARTITION_BYTES,
        AQE_TARGET_PARTITION_BYTES,
        TPU_SHAPE_BUCKETS,
    )
    from ballista_tpu.plan.provider import ParquetTable

    os_dir = tmp_path / "t"
    os_dir.mkdir()
    table = _table(N_ROWS)
    for i, batch in enumerate(table.to_batches()):
        pq.write_table(pa.Table.from_batches([batch]), os_dir / f"part-{i}.parquet")
    sql = "SELECT k, s, sum(v) AS sv, count(*) AS c FROM t GROUP BY k, s"
    ctx = SessionContext.standalone(BallistaConfig({
        EXECUTOR_ENGINE: "tpu", TPU_MIN_ROWS: 0, TPU_SHAPE_BUCKETS: "256,512,1024,2048,4096,8192",
        AQE_TARGET_PARTITION_BYTES: 16 << 10, AQE_MIN_PARTITION_BYTES: 1}))
    try:
        ctx.register_table("t", ParquetTable(str(os_dir)))
        ctx.sql(sql).collect()
        sc.RUN_STATS.clear()
        got = ctx.sql(sql).collect()
        stages = sc.RUN_STATS.stages()
    finally:
        ctx.shutdown()
    job, = (r for t, r in stages.items() if t.startswith("job_"))
    final_dispatches = [s for s in job["spans"]
                        if s[0] == "bt.stage.dispatch" and s[7].get("family") == "final"]
    final_stage = final_dispatches[0][5]
    final_tasks = {s[6] for s in job["spans"] if s[0] == "bt.task.run" and s[5] == final_stage}
    assert len(final_tasks) > 1, "the final stage goes out as several tasks"
    assert {s[6] for s in final_dispatches} == final_tasks  # one dispatch a task
    assert len(final_dispatches) == len(final_tasks)
    final, = _records(stages, "final_")
    assert final["table_shape"][0] == 1  # a task's own partition, not the stage's
    # one program shape for the stage: every task stacks at the widest
    # input partition's bucket, so the stage compiles once
    # (its partitions of ~1,000 rows straddle the 1024-row bucket here)
    assert {s[7]["sorted_capacity"] for s in final_dispatches} == {final["sorted_capacity"]}
    assert final["table_shape"][1] == final["sorted_capacity"] == 2048
    assert 0 < final["final_groups"] < N_ROWS and "dispatches" not in final

    cpu = SessionContext(BallistaConfig({EXECUTOR_ENGINE: "cpu"}))
    cpu.register_table("t", ParquetTable(str(os_dir)))
    want = cpu.sql(sql).collect()
    key = ["k", "s"]
    assert got.num_rows == want.num_rows == N_ROWS
    assert (got.to_pandas().sort_values(key).reset_index(drop=True)
            .equals(want.to_pandas().sort_values(key).reset_index(drop=True)))


def _final_stage(sql: str, table: pa.Table, budget: int):
    from ballista_tpu.client.context import SessionContext
    from ballista_tpu.engine.tpu_engine import maybe_compile_tpu
    from ballista_tpu.ops.tpu.final_stage import TpuFinalStageExec

    cfg = BallistaConfig({EXECUTOR_ENGINE: "tpu", TPU_MIN_ROWS: 0, TPU_HBM_BUDGET_BYTES: budget})
    ctx = SessionContext(cfg)
    ctx.register_arrow_table("t", table, partitions=2)
    phys = maybe_compile_tpu(ctx.create_physical_plan(ctx.sql(sql).plan), cfg)
    stage, = (nd for nd in _walk(phys) if isinstance(nd, TpuFinalStageExec))
    return cfg, stage


def test_past_the_budget_the_final_family_declines_before_its_dispatch():
    """The final family's admission counts its [C] lanes and its ordering's
    scratch beside its input stacks: with the budget one byte under that, it
    raises Unsupported before any upload or device call — no record of a
    dispatch, no group counted — and the query answers on the CPU."""
    import ballista_tpu.ops.tpu.stage_compiler as sc
    from ballista_tpu.ops.tpu.kernels import Unsupported
    from ballista_tpu.plan.physical import TaskContext

    sql = "SELECT k, s, sum(v) AS sv, count(*) AS c FROM t GROUP BY k, s"
    table = _table(N_ROWS)
    roomy = _serve(sql, table)
    final, = _records(roomy["stages"], "final_")
    need = int(final["hbm_plan_reason"].split("fits: ")[1].split(" B")[0])
    P, N = final["table_shape"]
    assert need > final["sorted_capacity"] * 9 * 4  # the [C] lanes are priced

    cfg, stage = _final_stage(sql, table, need - 1)
    sc.RUN_STATS.clear()
    with sc.RUN_STATS.run("final_probe"), pytest.raises(Unsupported, match="device bytes"):
        stage._tpu_run_all(TaskContext(cfg))
    rec = sc.RUN_STATS.stages()["final_probe"]
    assert rec["hbm_plan"] == "cpu_demote"
    assert "final_groups" not in rec and "exec_s" not in rec and "sorted_capacity" not in rec

    tight = _serve(sql, table, {TPU_HBM_BUDGET_BYTES: need - 1})
    key = ["k", "s"]
    assert (tight["tpu"].to_pandas().sort_values(key).reset_index(drop=True)
            .equals(tight["cpu"].to_pandas().sort_values(key).reset_index(drop=True)))
    assert not any("group capacity overflow" in r for r in tight["recent"])


def _per_group(codes, dictionary, null_mask, type_):
    """The decode `decode_codes` replaced: one Python lookup a group."""
    py = [None if (null_mask is not None and null_mask[j]) else dictionary[int(c)]
          for j, c in enumerate(codes)]
    return pa.array(py, type_)


@pytest.mark.parametrize("type_", [pa.string(), pa.large_string(),
                                   pa.dictionary(pa.int32(), pa.string())],
                         ids=["string", "large_string", "dictionary"])
@pytest.mark.parametrize("nulls", [False, True], ids=["no_nulls", "nulls"])
def test_bulk_decode_is_the_per_group_decode(type_, nulls):
    """Codes into a dictionary with unused entries (and, where asked, null
    keys whose code slot holds a fill value) decode to the same Arrow array,
    type and nulls included, as the per-group lookup."""
    from ballista_tpu.ops.tpu.columnar import decode_codes

    rng = np.random.default_rng(47)
    dictionary = [f"id{i:010d}" for i in range(1000)] + ["", "ü-ß"]
    used = rng.choice(len(dictionary), 300, replace=False)  # 702 entries never used
    codes = rng.choice(used, 5000).astype(np.int64)
    null_mask = (rng.random(5000) < 0.1) if nulls else None
    if nulls:
        codes[null_mask] = 0  # a null key's slot holds the fill value
    got = decode_codes(codes, dictionary, null_mask, type_)
    want = _per_group(codes, dictionary, null_mask, type_)
    assert got.type == want.type == type_
    assert got.null_count == want.null_count == (int(null_mask.sum()) if nulls else 0)
    assert got.equals(want)


def test_bulk_decode_of_no_rows_and_an_empty_dictionary():
    from ballista_tpu.ops.tpu.columnar import decode_codes

    empty = decode_codes(np.zeros(0, np.int64), [], None, pa.string())
    assert empty.equals(pa.array([], pa.string()))
    all_null = decode_codes(np.zeros(3, np.int64), [], np.ones(3, bool), pa.string())
    assert all_null.equals(pa.array([None, None, None], pa.string()))
