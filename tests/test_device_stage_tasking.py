"""One dispatch a device stage (ISSUE 26).

A partial device stage (`TpuStageExec`) computes EVERY partition of its
stage in one dispatch, so the scheduler hands such a stage out as one task
per executor — the slice decided from the stage's plan, not from
`ballista.scheduler.max_partitions_per_task` — and every other stage is
tasked exactly as before. Covered here: the predicate against
`maybe_compile_tpu`, the slices `ExecutionGraph` hands out, the served path
end to end, and the fallback contract of a slice under `emit_pid`."""

import glob
import json
import os

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.ipc as ipc
import pyarrow.parquet as pq
import pytest

from ballista_tpu.config import (
    EXECUTOR_ENGINE,
    MAX_PARTITIONS_PER_TASK,
    TPU_MESH_ENABLED,
    TPU_MIN_ROWS,
    BallistaConfig,
)
from ballista_tpu.tracing import RUN_STATS

from .conftest import iter_plan, tpch_query
from .test_tracing import NAME, NUMBERS, PARENT, ID, by_name, job_records

TPU = {EXECUTOR_ENGINE: "tpu"}


def _stages(tpch_dir, q, cfg, job="jd"):
    from ballista_tpu.client.context import SessionContext
    from ballista_tpu.scheduler.planner import DistributedPlanner
    from ballista_tpu.testing.tpchgen import register_tpch

    ctx = SessionContext(cfg)
    register_tpch(ctx, tpch_dir)
    physical = ctx.create_physical_plan(ctx.sql(tpch_query(q)).plan)
    return DistributedPlanner(job).plan_query_stages(physical)


def _graph(tpch_dir, q, conf):
    from ballista_tpu.scheduler.state.execution_graph import ExecutionGraph

    cfg = BallistaConfig(conf)
    return ExecutionGraph("jd", "", "s1", _stages(tpch_dir, q, cfg), cfg)


def _holds_partial_stage(plan, cfg) -> bool:
    from ballista_tpu.engine.tpu_engine import maybe_compile_tpu
    from ballista_tpu.ops.tpu.stage_compiler import TpuStageExec

    return any(isinstance(n, TpuStageExec) for n in iter_plan(maybe_compile_tpu(plan, cfg)))


def _succeed(graph, task, executor_id="e1", num_bytes=10):
    from ballista_tpu.shuffle.types import PartitionLocation, PartitionStats

    stage = graph.stages[task.stage_id]
    locs = [PartitionLocation(
        map_partition=p, job_id=task.job_id, stage_id=task.stage_id,
        output_partition=o, executor_id=executor_id,
        path=f"/fake/{task.stage_id}/{p}/{o}",
        stats=PartitionStats(num_rows=1, num_batches=1, num_bytes=num_bytes))
        for p in task.partitions
        for o in (range(stage.spec.output_partitions)
                  if stage.spec.plan.output_partitions > 0 else [p])]
    return graph.update_task_status(task.task_id, task.stage_id, task.stage_attempt,
                                    "success", task.partitions, locs)


def _pop_stage(graph, stage_id, executors):
    """Every task the graph hands out for one stage, none completed."""
    tasks = []
    while graph.stages[stage_id].pending:
        t = graph.pop_next_task(f"e{len(tasks) % executors}", executors)
        assert t is not None and t.stage_id == stage_id
        tasks.append(t)
    return tasks


# -- the predicate -------------------------------------------------------------


def test_the_predicate_imports_no_jax():
    import subprocess
    import sys

    code = ("import sys; import ballista_tpu.scheduler.state.execution_graph as g; "
            "import ballista_tpu.engine.tpu_engine as e; "
            "assert 'jax' not in sys.modules, 'jax imported'")
    subprocess.run([sys.executable, "-c", code], check=True, timeout=120)


@pytest.mark.parametrize("q", [1, 3, 5, 6, 10, 12, 14, 18, 19])
def test_predicate_agrees_with_the_compiled_plan(q, tpch_dir):
    """device ⇔ the plan `maybe_compile_tpu` makes holds a TpuStageExec,
    stage by stage, with the scans matching one for one."""
    from ballista_tpu.engine.tpu_engine import (
        is_whole_stage_device,
        maybe_compile_tpu,
        whole_stage_scans,
    )
    from ballista_tpu.ops.tpu.stage_compiler import TpuStageExec

    cfg = BallistaConfig(TPU)
    for s in _stages(tpch_dir, q, cfg):
        compiled = [n for n in iter_plan(maybe_compile_tpu(s.plan, cfg))
                    if isinstance(n, TpuStageExec)]
        assert sorted(map(id, whole_stage_scans(s.plan))) == sorted(id(n.scan) for n in compiled)
        assert is_whole_stage_device(s.plan, cfg) == bool(compiled), s.plan.display()


def test_predicate_follows_the_hoist_and_the_union_push_down():
    from ballista_tpu.client.context import SessionContext
    from ballista_tpu.engine.tpu_engine import is_whole_stage_device
    from ballista_tpu.scheduler.planner import DistributedPlanner

    cfg = BallistaConfig({**TPU, TPU_MIN_ROWS: 0})
    ctx = SessionContext(cfg)
    rng = np.random.default_rng(3)
    for name in ("a", "b"):
        ctx.register_arrow_table(name, pa.table({
            "k": pa.array([f"key{int(i) % 7}-x" for i in rng.integers(0, 100, 500)]),
            "v": rng.integers(1, 9, 500)}))
    for sql in ("select substr(k, 1, 4) s, sum(v) from a group by substr(k, 1, 4)",
                "select k, sum(v) from (select k, v from a union all select k, v from b) u "
                "group by k"):
        stages = DistributedPlanner("jh").plan_query_stages(
            ctx.create_physical_plan(ctx.sql(sql).plan))
        answers = [is_whole_stage_device(s.plan, cfg) for s in stages]
        assert answers == [_holds_partial_stage(s.plan, cfg) for s in stages]
        assert any(answers), sql


def test_a_table_whose_own_rows_are_under_the_floor_is_no_device_stage():
    """From the plan, not from a knob: an in-memory scan states its rows,
    and under ballista.tpu.min.rows the stage would raise BelowRowFloor and
    run its partitions on the CPU engine — better on four threads than one."""
    from ballista_tpu.client.context import SessionContext
    from ballista_tpu.engine.tpu_engine import is_whole_stage_device, whole_stage_scans
    from ballista_tpu.scheduler.planner import DistributedPlanner

    ctx = SessionContext(BallistaConfig(TPU))
    ctx.register_arrow_table("small", pa.table({"k": [1, 2, 3, 1], "v": [1, 2, 3, 4]}))
    stages = DistributedPlanner("js").plan_query_stages(
        ctx.create_physical_plan(ctx.sql("select k, sum(v) from small group by k").plan))
    leaf = stages[0]
    assert whole_stage_scans(leaf.plan), "the shape is a device stage's"
    assert not is_whole_stage_device(leaf.plan, BallistaConfig(TPU))  # 4 rows < 8192
    assert is_whole_stage_device(leaf.plan, BallistaConfig({**TPU, TPU_MIN_ROWS: 0}))


# -- (a) the slices the graph hands out ---------------------------------------


@pytest.mark.parametrize("executors", [1, 2, 3, 4, 16])
def test_a_partial_device_stage_goes_out_one_task_an_executor(executors, tpch_dir):
    g = _graph(tpch_dir, 1, TPU)
    st = g.stages[1]
    assert st.whole_stage_device and _holds_partial_stage(st.resolved_plan, g.config)
    P = st.spec.partitions
    want = -(-P // executors)
    assert g.available_task_count(executors) == -(-P // want)  # tasks, not partitions
    tasks = _pop_stage(g, 1, executors)
    assert [len(t.partitions) for t in tasks][:-1] == [want] * (len(tasks) - 1)
    assert len(tasks) == -(-P // want) <= executors
    assert sorted(p for t in tasks for p in t.partitions) == list(range(P))
    assert g.available_task_count(executors) == 0


@pytest.mark.parametrize("q", [1, 3, 5, 6])
def test_each_benchmark_query_has_one_whole_stage_device_stage(q, tpch_dir):
    g = _graph(tpch_dir, q, TPU)
    flagged = []
    while g.status.value == "running":
        t = g.pop_next_task("e1")
        assert t is not None, g.display()
        st = g.stages[t.stage_id]
        assert st.whole_stage_device == _holds_partial_stage(st.resolved_plan, g.config)
        if st.whole_stage_device:
            flagged.append(t.stage_id)
            assert t.partitions == list(range(st.spec.partitions))
        else:
            assert len(t.partitions) == 1  # final / sort families, CPU stages
        _succeed(g, t)
    assert g.status.value == "successful" and len(flagged) == 1


@pytest.mark.parametrize("per_task", [1, 3])
def test_a_cpu_engine_graph_slices_exactly_as_before(per_task, tpch_dir):
    g = _graph(tpch_dir, 1, {MAX_PARTITIONS_PER_TASK: per_task})
    st = g.stages[1]
    assert not st.whole_stage_device
    P = st.spec.partitions
    assert g.available_task_count(4) == -(-P // per_task)
    sizes = [len(t.partitions) for t in _pop_stage(g, 1, 4)]
    assert sizes == [per_task] * (P // per_task) + ([P % per_task] if P % per_task else [])


@pytest.mark.parametrize("per_task", [1, 2])
def test_a_final_family_stage_keeps_max_partitions_per_task(per_task, tpch_dir):
    from ballista_tpu.engine.tpu_engine import maybe_compile_tpu
    from ballista_tpu.ops.tpu.final_stage import TpuFinalStageExec

    g = _graph(tpch_dir, 1, {**TPU, MAX_PARTITIONS_PER_TASK: per_task})
    for t in _pop_stage(g, 1, 1):
        _succeed(g, t, num_bytes=1 << 28)  # too large for AQE to coalesce the readers
    st = g.stages[2]
    assert st.is_runnable and not st.whole_stage_device and st.effective_partitions > 2
    assert any(isinstance(n, TpuFinalStageExec)
               for n in iter_plan(maybe_compile_tpu(st.resolved_plan, g.config)))
    sizes = {len(t.partitions) for t in _pop_stage(g, 2, 1)}
    assert max(sizes) == per_task


def test_a_mesh_stage_keeps_its_single_task(tpch_dir):
    from ballista_tpu.scheduler.planner import merge_mesh_stages
    from ballista_tpu.scheduler.state.execution_graph import ExecutionGraph

    cfg = BallistaConfig({**TPU, TPU_MIN_ROWS: 0, TPU_MESH_ENABLED: True})
    merged = merge_mesh_stages(_stages(tpch_dir, 1, cfg), cfg)
    g = ExecutionGraph("jm", "", "s1", merged, cfg)
    ms = next(st for st in g.stages.values() if st.spec.mesh)
    assert g.available_task_count(4) == 1
    t = g.pop_next_task("e1", 4)  # four executors: still ONE mesh-wide task
    assert t.stage_id == ms.stage_id and t.partitions == list(range(ms.spec.partitions))


@pytest.mark.parametrize("executors", [1, 2])
def test_a_failed_slice_re_pends_all_its_partitions(executors, tpch_dir):
    g = _graph(tpch_dir, 1, TPU)
    st = g.stages[1]
    first = _pop_stage(g, 1, executors)
    lost = first[0]
    ev = g.update_task_status(lost.task_id, 1, lost.stage_attempt, "failed",
                              lost.partitions, [], "transient io", retryable=True)
    assert "job_failed" not in ev
    assert sorted(st.pending) == lost.partitions
    assert g.available_task_count(executors) == 1
    # the slice is re-run whole, at the size fixed at its first hand-out —
    # also when fewer (or more) executors are alive by then
    again = g.pop_next_task("e9", executors + 3)
    assert again.partitions == lost.partitions and again.task_attempt == 1
    for t in first[1:] + [again]:
        _succeed(g, t)
    assert st.state.value == "successful"


def test_a_lost_executor_re_pends_its_slice_and_a_retry_re_derives(tpch_dir):
    g = _graph(tpch_dir, 1, TPU)
    a, b = _pop_stage(g, 1, 2)
    assert g.reset_stages_on_lost_executor("e1") == 1  # b's executor
    assert sorted(g.stages[1].pending) == b.partitions
    _succeed(g, a, "e0")
    _succeed(g, g.pop_next_task("e0", 1), "e0")
    assert g.stages[1].state.value == "successful"
    # the outputs die with e0: the stage re-runs, flag and slice derived anew
    g.reset_stages_on_lost_executor("e0")
    st = g.stages[1]
    assert st.attempt == 1 and st.whole_stage_device and st.task_slice is None
    assert [len(t.partitions) for t in _pop_stage(g, 1, 1)] == [st.spec.partitions]


@pytest.mark.parametrize("executors", [1, 2])
def test_a_graph_recovered_from_proto_slices_the_same_way(executors, tpch_dir):
    from ballista_tpu.scheduler.state.execution_graph import ExecutionGraph

    g = _graph(tpch_dir, 3, TPU)
    g2 = ExecutionGraph.from_proto(g.to_proto())
    assert ({sid: st.whole_stage_device for sid, st in g2.stages.items() if st.resolved_plan}
            == {sid: st.whole_stage_device for sid, st in g.stages.items() if st.resolved_plan})

    def shape(graph):
        out = []
        while graph.status.value == "running":
            batch = []
            while (t := graph.pop_next_task("e1", executors)) is not None:
                batch.append(t)
            if not batch:
                break
            out.extend((t.stage_id, tuple(t.partitions)) for t in batch)
            for t in batch:
                _succeed(graph, t)
        return out

    a, b = shape(g), shape(g2)
    assert a == b and g2.status.value == "successful"
    assert any(len(parts) > 1 for _, parts in a)


def test_plan_check_holds_the_predicate_to_the_compiled_plan(tpch_dir, monkeypatch):
    from ballista_tpu.analysis.plan_check import verify_graph, verify_stages
    from ballista_tpu.engine import tpu_engine

    cfg = BallistaConfig(TPU)
    stages = _stages(tpch_dir, 5, cfg)
    assert not verify_stages(stages)
    g = _graph(tpch_dir, 1, TPU)
    assert not verify_graph(g)
    # a stage whose flag no longer follows its plan
    st = next(s for s in g.stages.values() if s.whole_stage_device)
    st.whole_stage_device = False
    assert [v.code for v in verify_graph(g)] == ["device-slice"]
    st.whole_stage_device = True
    # a predicate that drifts from maybe_compile_tpu's own matching
    monkeypatch.setattr(tpu_engine, "whole_stage_scans", lambda plan: [])
    codes = {v.code for v in verify_stages(stages)}
    assert codes == {"device-slice"}


# -- (b), (d) the served path -------------------------------------------------


def _sorted_frame(tbl: pa.Table):
    df = tbl.to_pandas()
    return df.sort_values(list(df.columns)).reset_index(drop=True)


@pytest.fixture(scope="module")
def cpu_answers(tpch_dir):
    from ballista_tpu.client.context import SessionContext
    from ballista_tpu.testing.tpchgen import register_tpch

    ctx = SessionContext(BallistaConfig())
    register_tpch(ctx, tpch_dir)
    return {q: _sorted_frame(ctx.sql(tpch_query(q)).collect()) for q in (1, 3, 5, 6)}


@pytest.fixture(scope="module", params=[1, 2])
def served(request, tpch_dir):
    from ballista_tpu.client.context import SessionContext
    from ballista_tpu.testing.tpchgen import register_tpch

    ctx = SessionContext.standalone(BallistaConfig(TPU), num_executors=request.param)
    register_tpch(ctx, tpch_dir)
    yield request.param, ctx
    ctx.shutdown()


@pytest.mark.parametrize("q", [1, 6, 3, 5])
def test_the_served_path_dispatches_a_device_stage_once_an_executor(q, served, cpu_answers):
    import pandas.testing as pdt

    executors, ctx = served
    ctx.sql(tpch_query(q)).collect()  # fill and compile
    RUN_STATS.clear()
    out = ctx.sql(tpch_query(q)).collect()
    pdt.assert_frame_equal(_sorted_frame(out), cpu_answers[q], check_dtype=False, rtol=1e-9)

    (_, rec), = job_records(RUN_STATS).items()
    spans = rec["spans"]
    by_id = {s[ID]: s for s in spans}
    dispatches = [s for s in by_name(spans, "bt.stage.dispatch")
                  if s[NUMBERS].get("family") == "partial"]
    counted = sum(r.get("dispatches", 0) for t, r in RUN_STATS.stages().items()
                  if not t.startswith("job_"))
    # one partial device stage a query, one slice (and one dispatch) an executor
    assert len(dispatches) == counted == executors

    def task_of(span):
        while span[NAME] != "bt.task.run":
            span = by_id[span[PARENT]]
        return span

    slices = [task_of(d)[NUMBERS]["partitions"] for d in dispatches]
    assert slices == [8 // executors] * executors
    # every other stage is tasked a partition at a time, as before
    others = [t for t in by_name(spans, "bt.task.run")
              if t[ID] not in {task_of(d)[ID] for d in dispatches}]
    assert others and {t[NUMBERS]["partitions"] for t in others} == {1}
    # a task commits its shuffle output once: one commit a task, and the
    # device stage's holds its whole slice in one data file + one index
    commits = by_name(spans, "bt.shuffle.commit")
    assert len(commits) == len(by_name(spans, "bt.task.run"))
    device_tasks = {task_of(d)[ID] for d in dispatches}
    sliced = [c[NUMBERS] for c in commits if task_of(c)[ID] in device_tasks]
    assert [c["map_partitions"] for c in sliced] == [8 // executors] * executors
    assert all(c["files"] == 2 for c in sliced)
    # ... and its consumers open at most a location an output partition of a
    # slice (a passthrough: a location a map partition, as before)
    out_parts = max(c["ranges"] for c in sliced)
    opened = [r[NUMBERS]["partitions"] for r in by_name(spans, "bt.shuffle.read")]
    assert max(opened) <= max(out_parts, 8 // executors) * executors


# -- (c) the fallback contract of a slice under emit_pid ----------------------


def _map_outputs(work, job, stage):
    """first map partition of a slice → every row the slice's one commit
    wrote, over all its reduce buckets."""
    out = {}
    for f in glob.glob(f"{work}/{job}/{stage}/*.arrow"):
        from ballista_tpu.shuffle import paths as sp

        mp = int(os.path.basename(f).split("-")[1].split(".")[0])
        with open(sp.index_path(f)) as fh:
            idx = json.load(fh)
        with open(f, "rb") as fh:
            data = fh.read()
        parts = [ipc.open_stream(pa.BufferReader(data[e[0]:e[0] + e[1]])).read_all()
                 for e in idx.values()]
        out[mp] = pa.concat_tables([p for p in parts if p.num_rows] or parts[:1]) if parts else None
    return out


@pytest.mark.parametrize("demoted", ["every slice", "the slice with partition 0",
                                     "the other slice"])
def test_a_demoted_slice_keeps_the_emit_pid_layout(demoted, tmp_path, monkeypatch):
    """Partition 0 carries every group and the others are empty, whichever
    engine produced them: two slices — one on the device, one whose device
    path raises — neither lose a group nor count one twice."""
    import ballista_tpu.ops.tpu.stage_compiler as sc
    from ballista_tpu.client.context import SessionContext
    from ballista_tpu.config import DEFAULT_SHUFFLE_PARTITIONS
    from ballista_tpu.engine.tpu_engine import maybe_compile_tpu
    from ballista_tpu.plan.physical import TaskContext
    from ballista_tpu.scheduler.planner import DistributedPlanner

    rng = np.random.default_rng(5)
    n = 30_000
    tbl = pa.table({"k": rng.integers(0, 5000, n), "v": rng.integers(1, 100, n)})
    os.makedirs(tmp_path / "t")
    for i in range(4):  # four files: a four-partition map stage
        pq.write_table(tbl.slice(i * n // 4, n // 4), str(tmp_path / "t" / f"p{i}.parquet"))
    cfg = BallistaConfig({**TPU, TPU_MIN_ROWS: 0, DEFAULT_SHUFFLE_PARTITIONS: 4})
    ctx = SessionContext(cfg)
    ctx.register_parquet("t", str(tmp_path / "t"))
    phys = ctx.create_physical_plan(
        ctx.sql("select k, sum(v) s, count(*) c from t where v > 10 group by k").plan)
    stage1 = DistributedPlanner("jfb").plan_query_stages(phys)[0]
    P = stage1.partitions
    assert P >= 2
    slices = {"a": list(range(0, P // 2)), "b": list(range(P // 2, P))}
    down = {"every slice": {"a", "b"}, "the slice with partition 0": {"a"},
            "the other slice": {"b"}}[demoted]

    def boom(self, ctx):
        raise RuntimeError("device path down")

    work = str(tmp_path / "work")
    stages = {}
    for name, parts in slices.items():
        # one task = one prepared instance for all of its partitions
        compiled = maybe_compile_tpu(stage1.plan, cfg)
        st, = [nd for nd in iter_plan(compiled) if isinstance(nd, sc.TpuStageExec)]
        assert st.emit_pid is not None
        stages[name] = st
        with monkeypatch.context() as m:
            if name in down:
                m.setattr(sc.TpuStageExec, "_dispatch_all", boom)
            tc = TaskContext(cfg, task_id=name, work_dir=work)
            list(compiled.execute_slice(parts, tc))
    for name, st in stages.items():
        # the instance decided once, for every partition of its slice
        assert (st.tpu_count, st.fallback_count) == (
            (0, len(slices[name])) if name in down else (1, 0))

    # one data file + one index a slice, named after its first partition
    outs = _map_outputs(work, "jfb", 1)
    assert sorted(outs) == [0, P // 2]
    assert sorted(os.listdir(f"{work}/jfb/1")) == sorted(
        f"data-{parts[0]}-{name}.{ext}" for name, parts in slices.items()
        for ext in ("arrow", "idx"))
    assert outs[P // 2] is None or outs[P // 2].num_rows == 0
    got = outs[0].group_by("k").aggregate(
        [("__acc0", "sum"), ("__acc1", "sum"), ("__acc1", "count")])
    assert pc.max(got["__acc1_count"]).as_py() == 1  # no group twice
    want = tbl.filter(pc.greater(tbl["v"], 10)).group_by("k").aggregate(
        [("v", "sum"), ("v", "count")])
    assert got.num_rows == want.num_rows  # no group lost
    a = got.sort_by("k")
    b = want.sort_by("k")
    assert a["__acc0_sum"].to_pylist() == b["v_sum"].to_pylist()
    assert a["__acc1_sum"].to_pylist() == b["v_count"].to_pylist()
