"""A task commits its shuffle output once (ISSUE 33).

`ShuffleWriterExec.execute_slice` takes the map partitions one task holds,
pulls them a partition at a time and commits ONE file set: a hash exchange
buckets by key across the slice (at most K locations), a passthrough keeps a
range a map partition in one data file + one index, and a slice of one writes
exactly what a map task always wrote. The locations are reported under the
slice's first map partition, so lineage is by slice: a duplicate attempt wins
for all of it or none, a corrupt range reruns the stage, and a graph recovered
from its proto resolves consumers from the same locations."""

import json
import os

import numpy as np
import pyarrow as pa
import pyarrow.ipc as ipc
import pyarrow.parquet as pq
import pytest

from ballista_tpu.config import (
    MAX_PARTITIONS_PER_TASK,
    SORT_SHUFFLE_MEMORY_LIMIT,
    TASK_DEADLINE_S,
    BallistaConfig,
)
from ballista_tpu.executor.executor import Executor, ExecutorMetadata
from ballista_tpu.plan.expressions import Column
from ballista_tpu.plan.physical import ExecutionPlan, TaskContext
from ballista_tpu.plan.schema import DFField, DFSchema
from ballista_tpu.scheduler.state.execution_graph import ExecutionGraph, TaskDescription
from ballista_tpu.shuffle.integrity import INTEGRITY, checksum_bytes
from ballista_tpu.shuffle.reader import ShuffleReaderExec
from ballista_tpu.shuffle.writer import ShuffleWriterExec
from ballista_tpu.tracing import RUN_STATS

from .test_tracing import NUMBERS, by_name

SCHEMA = DFSchema([DFField("k", pa.int64(), False), DFField("v", pa.int64(), False)])
P, K = 8, 3


class Parts(ExecutionPlan):
    """A source of P partitions, two batches each; `on_pull(p)` runs ahead of
    a partition's first batch (the tests cancel or stall from there)."""

    def __init__(self, parts: list[list[pa.RecordBatch]], on_pull=None):
        super().__init__(SCHEMA)
        self.parts = parts
        self.on_pull = on_pull

    def output_partition_count(self):
        return len(self.parts)

    def with_children(self, c):
        return self

    def execute(self, partition, ctx):
        if self.on_pull is not None:
            self.on_pull(partition)
        yield from self.parts[partition]


def _parts(sorted_within=False, seed=7, rows=600) -> list[list[pa.RecordBatch]]:
    rng = np.random.default_rng(seed)
    out = []
    for p in range(P):
        k = rng.integers(0, 10_000, rows)
        if sorted_within:
            k = np.sort(k)
        v = rng.integers(0, 100, rows)
        half = rows // 2
        out.append([
            pa.record_batch({"k": pa.array(k[:half]), "v": pa.array(v[:half])},
                            schema=SCHEMA.to_arrow()),
            pa.record_batch({"k": pa.array(k[half:]), "v": pa.array(v[half:])},
                            schema=SCHEMA.to_arrow()),
        ])
    return out


def _writer(parts, mode: str, on_pull=None, job="js", stage=1) -> ShuffleWriterExec:
    src = Parts(parts, on_pull)
    if mode == "passthrough":
        return ShuffleWriterExec(src, job, stage, 0, None, sort_shuffle=False)
    return ShuffleWriterExec(src, job, stage, K, [Column("k")], sort_shuffle=(mode == "sort"))


def _task(plan, partitions, task_id=7, deadline=0.0) -> TaskDescription:
    return TaskDescription(job_id=plan.job_id, stage_id=plan.stage_id, stage_attempt=0,
                           task_id=task_id, partitions=list(partitions), plan=plan,
                           session_id="s", deadline_seconds=deadline)


def _files(work) -> dict[str, bytes]:
    out = {}
    for root, _, names in os.walk(work):
        for n in names:
            full = os.path.join(root, n)
            with open(full, "rb") as f:
                out[os.path.relpath(full, work)] = f.read()
    return out


def _read(locations, n_out) -> list[pa.Table]:
    """What a consumer reads, an output partition at a time (checksums
    verified by the reader on the way)."""
    by_out = [[] for _ in range(n_out)]
    for l in sorted(locations, key=lambda l: (l.output_partition, l.map_partition, l.path)):
        by_out[l.output_partition].append(l)
    reader = ShuffleReaderExec(SCHEMA, by_out)
    ctx = TaskContext(BallistaConfig(), task_id="r", work_dir="")
    return [pa.Table.from_batches(list(reader.execute(o, ctx)), schema=SCHEMA.to_arrow())
            for o in range(n_out)]


def _sorted(t: pa.Table) -> pa.Table:
    return t.sort_by([("k", "ascending"), ("v", "ascending")])


# -- a slice through a hash exchange ------------------------------------------


@pytest.mark.parametrize("layout", ["sort", "hash"])
def test_a_slice_through_a_hash_exchange_gives_at_most_k_locations(layout, tmp_path):
    parts = _parts()
    ex = Executor(str(tmp_path / "slice"), ExecutorMetadata(id="e1"))
    res = ex.execute_task(_task(_writer(parts, layout), range(P)), BallistaConfig())
    assert res.state == "success", res.error
    assert 0 < len(res.locations) <= K
    # reported under the slice's first map partition, one location an output
    assert {l.map_partition for l in res.locations} == {0}
    assert len({l.output_partition for l in res.locations}) == len(res.locations)
    names = sorted(_files(ex.work_dir))
    if layout == "sort":
        assert names == ["js/1/data-0-7.arrow", "js/1/data-0-7.idx"]
    else:
        assert names == sorted(f"js/1/{k}/data-7.arrow{suffix}"
                               for k in range(K) for suffix in ("", ".crc"))

    # the same answer as eight tasks of one partition each
    ex1 = Executor(str(tmp_path / "ones"), ExecutorMetadata(id="e1"))
    single = []
    for p in range(P):
        r = ex1.execute_task(_task(_writer(parts, layout), [p], task_id=100 + p), BallistaConfig())
        assert r.state == "success" and {l.map_partition for l in r.locations} == {p}
        single.extend(r.locations)
    assert len(single) > len(res.locations)
    got, want = _read(res.locations, K), _read(single, K)
    total = 0
    for o in range(K):
        assert _sorted(got[o]).equals(_sorted(want[o]))
        total += got[o].num_rows
    assert total == sum(b.num_rows for part in parts for b in part)


# -- a slice through a passthrough: partition identity ------------------------


def test_a_passthrough_slice_keeps_a_range_a_partition(tmp_path):
    from ballista_tpu.plan.physical import SortKey, SortPreservingMergeExec

    parts = _parts(sorted_within=True)
    ex = Executor(str(tmp_path), ExecutorMetadata(id="e1"))
    res = ex.execute_task(_task(_writer(parts, "passthrough"), range(P)), BallistaConfig())
    assert res.state == "success", res.error
    files = _files(ex.work_dir)
    assert sorted(files) == ["js/1/data-0-7.arrow", "js/1/data-0-7.idx"]
    index = json.loads(files["js/1/data-0-7.idx"])
    assert sorted(index, key=int) == [str(p) for p in range(P)]  # eight ranges
    assert [(l.map_partition, l.output_partition, l.layout) for l in res.locations] == [
        (0, p, "sort") for p in range(P)]
    # range p is map partition p: the same rows in the same order, no more
    got = _read(res.locations, P)
    for p in range(P):
        assert got[p].equals(pa.Table.from_batches(parts[p]))
        start, length, rows, _, crc = index[str(p)]
        assert rows == got[p].num_rows
        assert checksum_bytes(files["js/1/data-0-7.arrow"][start:start + length]) == crc
    # eight individually sorted partitions under a sort-preserving merge
    by_out = [[l] for l in res.locations]
    merge = SortPreservingMergeExec(ShuffleReaderExec(SCHEMA, by_out), [SortKey(Column("k"))])
    merged = pa.Table.from_batches(list(merge.execute(0, TaskContext(BallistaConfig()))))
    ks = merged["k"].to_numpy()
    assert len(ks) == sum(t.num_rows for t in got) and (np.diff(ks) >= 0).all()


# -- a slice of one writes what a map task always wrote -----------------------


def _ipc(batches) -> bytes:
    sink = pa.BufferOutputStream()
    # the codec the configuration defaults to (ballista.shuffle.compression.codec)
    with ipc.new_stream(sink, SCHEMA.to_arrow(),
                        options=ipc.IpcWriteOptions(compression="lz4")) as w:
        for b in batches:
            if b.num_rows:
                w.write_batch(b)
    return sink.getvalue().to_pybytes()


def _buckets(batches) -> dict[int, list[pa.RecordBatch]]:
    from ballista_tpu.ops.hashing import split_batch_by_partition

    out: dict[int, list] = {}
    for b in batches:
        for k, part in split_batch_by_partition(b, [b.column(0)], K):
            out.setdefault(k, []).append(part)
    return out


@pytest.mark.parametrize("mode", ["passthrough", "sort", "hash"])
def test_a_slice_of_one_writes_the_same_paths_and_bytes(mode, tmp_path):
    """The golden listing of map partition 5 written by task 7, its bytes
    rebuilt here from the documented layouts (shuffle/paths.py) with nothing
    of the writer: what every CPU-engine stage's task writes, before and
    after this change."""
    parts = _parts()
    ex = Executor(str(tmp_path), ExecutorMetadata(id="e1"))
    res = ex.execute_task(_task(_writer(parts, mode), [5]), BallistaConfig())
    assert res.state == "success", res.error
    assert {l.map_partition for l in res.locations} == {5}
    want: dict[str, bytes] = {}
    if mode == "passthrough":
        data = _ipc(parts[5])
        want = {"js/1/5/data-7.arrow": data,
                "js/1/5/data-7.arrow.crc": checksum_bytes(data).encode()}
        assert [(l.output_partition, l.layout) for l in res.locations] == [(5, "hash")]
    elif mode == "hash":
        for k, bs in _buckets(parts[5]).items():
            data = _ipc(bs)
            want[f"js/1/{k}/data-7.arrow"] = data
            want[f"js/1/{k}/data-7.arrow.crc"] = checksum_bytes(data).encode()
        assert {l.layout for l in res.locations} == {"hash"}
    else:
        blob, index = b"", {}
        for k, bs in sorted(_buckets(parts[5]).items()):
            data = _ipc(bs)
            index[str(k)] = [len(blob), len(data), sum(b.num_rows for b in bs), len(data),
                             checksum_bytes(data)]
            blob += data
        want = {"js/1/data-5-7.arrow": blob, "js/1/data-5-7.idx": json.dumps(index).encode()}
        assert {l.layout for l in res.locations} == {"sort"}
    got = _files(ex.work_dir)
    assert sorted(got) == sorted(want)
    for name in want:
        assert got[name] == want[name], name
    # and `execute(p)` is that slice of one
    ex2 = Executor(str(tmp_path / "again"), ExecutorMetadata(id="e1"))
    ctx = TaskContext(BallistaConfig(), task_id="7", work_dir=ex2.work_dir)
    list(_writer(parts, mode).execute(5, ctx))
    assert _files(ex2.work_dir) == got


# -- the memory limit spills across a slice -----------------------------------


def test_a_tiny_memory_limit_spills_across_a_slice(tmp_path):
    parts = _parts()
    writer = _writer(parts, "sort")
    cfg = BallistaConfig({SORT_SHUFFLE_MEMORY_LIMIT: 4096})
    ex = Executor(str(tmp_path / "spilled"), ExecutorMetadata(id="e1"))
    res = ex.execute_task(_task(writer, range(P)), cfg)
    assert res.state == "success", res.error
    m = next(m for m in res.metrics if m["name"].startswith("ShuffleWriterExec"))
    # more spills than one partition's rows could force: the budget is the slice's
    assert m["spill_count"] > P and m["spilled_bytes"] > 0
    assert sorted(_files(ex.work_dir)) == ["js/1/data-0-7.arrow", "js/1/data-0-7.idx"]
    ex0 = Executor(str(tmp_path / "roomy"), ExecutorMetadata(id="e1"))
    res0 = ex0.execute_task(_task(_writer(parts, "sort"), range(P)), BallistaConfig())
    got, want = _read(res.locations, K), _read(res0.locations, K)
    for o in range(K):
        assert got[o].num_rows and _sorted(got[o]).equals(_sorted(want[o]))


# -- cancel and deadline between partitions leave nothing behind --------------


@pytest.mark.parametrize("mode,how", [("passthrough", "cancel"), ("sort", "cancel"),
                                      ("passthrough", "deadline"), ("hash", "deadline")])
def test_an_aborted_slice_leaves_no_tmp_and_no_final_name(mode, how, tmp_path):
    import time

    ex = Executor(str(tmp_path), ExecutorMetadata(id="e1"))
    pulled = []

    def on_pull(p):
        pulled.append(p)
        if p == 2 and how == "cancel":
            ex.cancel_task("js", 1, 7)
        if p == 2 and how == "deadline":
            time.sleep(0.3)

    # spills on the way (the exchange), so an abort has them to sweep too
    cfg = BallistaConfig({SORT_SHUFFLE_MEMORY_LIMIT: 4096})
    task = _task(_writer(_parts(), mode, on_pull), range(P),
                 deadline=0.25 if how == "deadline" else 0.0)
    res = ex.execute_task(task, cfg)
    if how == "cancel":
        assert res.state == "cancelled"
    else:
        assert res.state == "failed" and res.retryable and res.timed_out
        assert "deadline" in res.error
    assert pulled == [0, 1, 2]  # checked between partitions: 3 is never pulled
    assert not res.locations
    assert _files(ex.work_dir) == {}  # no .tmp, no spill, nothing under a final name


# -- lineage is by slice -------------------------------------------------------


@pytest.fixture(scope="module")
def table8(tmp_path_factory):
    """Eight parquet files: an eight-partition map stage."""
    d = tmp_path_factory.mktemp("slice-t8")
    rng = np.random.default_rng(21)
    n = 8_000
    tbl = pa.table({"k": rng.integers(0, 300, n), "v": rng.integers(1, 100, n)})
    for i in range(P):
        pq.write_table(tbl.slice(i * n // P, n // P), str(d / f"p{i}.parquet"))
    return str(d), tbl


GROUP_BY = "select k, sum(v) as s, count(*) as c from t group by k"


def _want(tbl: pa.Table) -> pa.Table:
    return tbl.group_by("k").aggregate([("v", "sum"), ("v", "count")]).sort_by("k")


def _check_answer(out: pa.Table, tbl: pa.Table) -> None:
    got, want = out.sort_by("k"), _want(tbl)
    assert got["k"].to_pylist() == want["k"].to_pylist()
    assert got["s"].to_pylist() == want["v_sum"].to_pylist()
    assert got["c"].to_pylist() == want["v_count"].to_pylist()


def _graph(table8, conf=None, job="jl"):
    from ballista_tpu.client.context import SessionContext
    from ballista_tpu.config import DEFAULT_SHUFFLE_PARTITIONS
    from ballista_tpu.scheduler.planner import DistributedPlanner

    cfg = BallistaConfig({MAX_PARTITIONS_PER_TASK: P, DEFAULT_SHUFFLE_PARTITIONS: K,
                          **(conf or {})})
    ctx = SessionContext(cfg)
    ctx.register_parquet("t", table8[0])
    physical = ctx.create_physical_plan(ctx.sql(GROUP_BY).plan)
    stages = DistributedPlanner(job).plan_query_stages(physical)
    assert stages[0].partitions == P and stages[0].plan.output_partitions == K
    return ExecutionGraph(job, "", "s1", stages, cfg), cfg


def _report(graph, res):
    return graph.update_task_status(res.task_id, res.stage_id, res.stage_attempt, res.state,
                                    res.partitions, res.locations, res.error, res.retryable)


def _run_to_end(graph, ex, cfg) -> pa.Table:
    """Every task the graph still hands out, on one real executor; the final
    stage's output read back."""
    guard = 0
    while graph.status.value == "running" and guard < 200:
        guard += 1
        t = graph.pop_next_task(ex.metadata.id)
        assert t is not None, graph.display()
        res = ex.execute_task(t, cfg)
        assert res.state == "success", res.error
        _report(graph, res)
    assert graph.status.value == "successful", graph.display()
    final = graph.stages[graph.final_stage_id]
    locs = final.output_locations()
    reader = ShuffleReaderExec(final.spec.plan.input.df_schema, [locs])
    schema = final.spec.plan.input.schema()
    return pa.Table.from_batches(
        list(reader.execute(0, TaskContext(cfg, task_id="r"))), schema=schema)


def test_a_duplicate_attempt_writes_a_private_file_set_and_readers_see_one(table8, tmp_path):
    graph, cfg = _graph(table8)
    ex = Executor(str(tmp_path), ExecutorMetadata(id="e1"))
    t1 = graph.pop_next_task("e1")
    assert t1.stage_id == 1 and t1.partitions == list(range(P))
    t2 = graph.register_speculative(1, t1.task_id, "e1")
    assert t2 is not None and t2.partitions == t1.partitions
    r1, r2 = ex.execute_task(t1, cfg), ex.execute_task(t2, cfg)
    stage_dir = os.path.join(ex.work_dir, "jl", "1")
    assert sorted(os.listdir(stage_dir)) == sorted(
        f"data-0-{t.task_id}.{ext}" for t in (t1, t2) for ext in ("arrow", "idx"))
    # the duplicate's status arrives first: it wins for the WHOLE slice
    assert "stage_completed" in _report(graph, r2)
    stage = graph.stages[1]
    assert sorted(stage.completed) == list(range(P))
    assert {l.path for l in stage.completed[0]} == {r2.locations[0].path}
    assert all(stage.completed[p] == [] for p in range(1, P))
    _report(graph, r1)  # the loser, late: nothing of it is taken
    assert {l.path for l in stage.output_locations()} == {r2.locations[0].path}
    consumer = graph.stages[graph.output_links[1][0]]
    seen = {l.path for ls in _reader_of(consumer).partition_locations for l in ls}
    assert seen == {r2.locations[0].path}
    _check_answer(_run_to_end(graph, ex, cfg), table8[1])


def _reader_of(stage) -> ShuffleReaderExec:
    from .conftest import iter_plan

    reader, = [n for n in iter_plan(stage.resolved_plan) if isinstance(n, ShuffleReaderExec)]
    return reader


def _fake(graph, task):
    """A success as a task reports it: its locations under its first partition."""
    from ballista_tpu.shuffle.types import PartitionLocation, PartitionStats

    locs = [PartitionLocation(
        map_partition=task.partitions[0], job_id=task.job_id, stage_id=task.stage_id,
        output_partition=o, executor_id="e1", path=f"/fake/task{task.task_id}",
        stats=PartitionStats(num_rows=1, num_batches=1, num_bytes=10)) for o in range(K)]
    return graph.update_task_status(task.task_id, task.stage_id, task.stage_attempt,
                                    "success", task.partitions, locs)


@pytest.mark.parametrize("late_first", [False, True])
def test_a_late_attempt_is_taken_for_its_whole_slice_or_not_at_all(late_first, table8):
    """Slices of three over eight partitions; task A = [0, 1, 2] is swept at
    its deadline and its partitions re-sliced with others ([6, 7, 0], [1, 2]);
    A then reports success after all. Whichever order the statuses come in,
    every partition ends up in exactly ONE accepted slice — the carrier never
    without its companions, nor they without it."""
    import time

    graph, _ = _graph(table8, {MAX_PARTITIONS_PER_TASK: 3, TASK_DEADLINE_S: 1.0})
    a = graph.pop_next_task("e1")
    b = graph.pop_next_task("e1")
    assert (a.partitions, b.partitions) == ([0, 1, 2], [3, 4, 5])
    expired, failed = graph.expire_overdue_tasks(time.time() + 60.0)
    assert not failed and {e[1] for e in expired} == {a.task_id, b.task_id}
    d, e, f = (graph.pop_next_task("e1") for _ in range(3))
    assert (d.partitions, e.partitions, f.partitions) == ([6, 7, 0], [1, 2, 3], [4, 5])
    tasks = {t.task_id: t for t in (a, b, d, e, f)}
    if late_first:
        _fake(graph, a)  # whole: none of [0, 1, 2] was committed yet
        _fake(graph, d)  # [6, 7, 0] overlaps it in part: dropped, 6 and 7 go back
        assert sorted(graph.stages[1].pending) == [6, 7]
    else:
        _fake(graph, d)
        _fake(graph, a)  # 0 is d's now: nothing of a is taken, 1 and 2 stay e's
        assert graph.stages[1].completed.get(1) is None
    guard = 0
    for t in (e, f):
        _fake(graph, t)
    while graph.stages[1].state.value != "successful" and guard < 10:
        guard += 1
        t = graph.pop_next_task("e1")
        assert t is not None and t.stage_id == 1, graph.display()
        tasks[t.task_id] = t
        _fake(graph, t)
    stage = graph.stages[1]
    assert stage.state.value == "successful"
    accepted = {l.path for l in stage.output_locations()}
    covered = sorted(p for t in tasks.values() if f"/fake/task{t.task_id}" in accepted
                     for p in t.partitions)
    assert covered == list(range(P))  # each partition's rows once, none lost
    for p, locs in stage.completed.items():
        assert all(l.map_partition == p for l in locs)


def test_recovery_from_proto_resolves_the_consumer_from_the_slice(table8, tmp_path):
    graph, cfg = _graph(table8, job="jr")
    ex = Executor(str(tmp_path), ExecutorMetadata(id="e1"))
    t1 = graph.pop_next_task("e1")
    r1 = ex.execute_task(t1, cfg)
    assert "stage_completed" in _report(graph, r1)
    assert 0 < len(r1.locations) <= K
    back = ExecutionGraph.from_proto(graph.to_proto())
    stage = back.stages[1]
    assert stage.state.value == "successful"
    assert sorted(stage.completed) == [0]  # the carrier holds the slice's locations
    assert sorted(l.output_partition for l in stage.completed[0]) == sorted(
        l.output_partition for l in r1.locations)
    consumer = back.stages[back.output_links[1][0]]
    assert consumer.state.value == "resolved"
    assert sum(len(ls) for ls in _reader_of(consumer).partition_locations) == len(r1.locations)
    _check_answer(_run_to_end(back, ex, cfg), table8[1])


# -- end to end, over the real scheduler --------------------------------------


@pytest.fixture()
def cluster(table8):
    from ballista_tpu.client.context import SessionContext
    from ballista_tpu.config import DEFAULT_SHUFFLE_PARTITIONS

    ctx = SessionContext.standalone(BallistaConfig(
        {MAX_PARTITIONS_PER_TASK: P, DEFAULT_SHUFFLE_PARTITIONS: K}))
    ctx.register_parquet("t", table8[0])
    yield ctx
    ctx.shutdown()


def _job_spans():
    (_, rec), = {t: r for t, r in RUN_STATS.stages().items() if t.startswith("job_")}.items()
    return rec["spans"]


def test_one_commit_a_task_with_its_numbers(cluster, table8):
    # spans closed outside any job (the writers driven by hand above) hang
    # on the next job's record: let one query take them
    cluster.sql(GROUP_BY).collect()
    RUN_STATS.clear()
    _check_answer(cluster.sql(GROUP_BY).collect(), table8[1])
    spans = _job_spans()
    tasks = by_name(spans, "bt.task.run")
    commits = by_name(spans, "bt.shuffle.commit")
    assert len(commits) == len(tasks)
    first = max(commits, key=lambda s: s[NUMBERS]["map_partitions"])[NUMBERS]
    assert first["map_partitions"] == P and first["files"] == 2
    assert 0 < first["ranges"] <= K and first["bytes"] > 0
    # the consumers open what the slice reported, not a location a map partition
    reads = by_name(spans, "bt.shuffle.read")
    assert sum(r[NUMBERS]["partitions"] for r in reads
               if r[NUMBERS]["partitions"]) <= K + len(tasks)


def test_sorted_partitions_stay_sorted_through_a_slice(cluster, table8):
    out = cluster.sql("select k, v from t order by k, v").collect()
    want = table8[1].sort_by([("k", "ascending"), ("v", "ascending")])
    assert out["k"].to_pylist() == want["k"].to_pylist()
    assert out["v"].to_pylist() == want["v"].to_pylist()


def test_a_flipped_byte_in_a_coalesced_range_is_caught_and_the_rerun_answers_right(
        cluster, table8, monkeypatch):
    import ballista_tpu.shuffle.writer as w

    commit = w._commit_data_and_index
    flipped = []

    def flip_once(data_path, index, what):
        commit(data_path, index, what)
        if not flipped and len(index) > 1:
            # one byte inside the first range of the first coalesced commit
            start, length = next(iter(index.values()))[:2]
            with open(data_path, "r+b") as f:
                f.seek(start + length // 2)
                byte = f.read(1)
                f.seek(start + length // 2)
                f.write(bytes([byte[0] ^ 0x40]))
            flipped.append(data_path)

    cluster.sql("select count(*) from t").collect()  # takes the loose spans
    monkeypatch.setattr(w, "_commit_data_and_index", flip_once)
    before = INTEGRITY.snapshot()["checksum_failures"]
    RUN_STATS.clear()
    _check_answer(cluster.sql(GROUP_BY).collect(), table8[1])
    assert flipped
    assert INTEGRITY.snapshot()["checksum_failures"] > before  # the reader's verify
    # the stage that wrote the range ran again, as one slice
    slices = [s[NUMBERS]["map_partitions"] for s in by_name(_job_spans(), "bt.shuffle.commit")]
    assert slices.count(P) == 2
