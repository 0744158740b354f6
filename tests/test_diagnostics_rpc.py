"""Other processes' spans, counters, memory and profiler: the pull-only
`GetDiagnostics` / `Profile` rpcs and the join of several processes' spans of
a job into one record (tracing.join_job_parts).

The join is tested on plain lists. The rpcs are tested on the deployment
BASELINE.json names, at SF0.01 on the CPU backend: `python -m
ballista_tpu.scheduler` and one, two and four `python -m
ballista_tpu.executor --engine tpu --device-ordinal i` processes, the client
on `SessionContext.remote`. Every test has a time limit of its own."""

import glob
import os
import signal
import socket
import subprocess
import sys
import time

import pytest

from ballista_tpu.testing.reference import compare_results, run_reference
from ballista_tpu.tracing import RunStats, clock_pair, join_job_parts

from .conftest import tpch_query

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LIMIT_S = {"join": 10, "cluster": 240}


@pytest.fixture(autouse=True)
def time_limit(request):
    """A limit a test: the cluster tests wait on other processes."""
    seconds = LIMIT_S["cluster" if "cluster" in request.fixturenames else "join"]

    def expired(signum, frame):
        raise TimeoutError(f"{request.node.name} took more than {seconds} s")

    previous = signal.signal(signal.SIGALRM, expired)
    signal.alarm(seconds)
    yield
    signal.alarm(0)
    signal.signal(signal.SIGALRM, previous)


# ------------------------------------------------------- the join, plain lists

S = 1_000_000_000  # a part's perf_counter may start anywhere: ns per second


def part(process, perf_ns, wall_ns, rows, dropped=0):
    return {"process": process, "clock": [perf_ns, wall_ns], "spans": rows,
            "spans_dropped": dropped}


def client_part():
    # the client's clock: perf 100 s is wall 1000 s
    return part("client", 100 * S, 1000 * S, [
        ["bt.client.collect", 1, None, 10.0, 20.0, None, None, {}],
        ["bt.client.submit", 2, 1, 10.0, 10.5, None, None, {}],
        ["bt.client.wait", 3, 1, 10.5, 19.0, None, None, {}],
        ["bt.client.fetch_results", 4, 1, 19.0, 20.0, None, None, {"rows": 7}]])


def scheduler_part(offset_s=0.0):
    # its perf_counter reads `offset_s` more than the client's at one moment
    o = offset_s
    return part("scheduler:s0", int((100 + o) * S), 1000 * S, [
        ["bt.sched.plan", 1, None, 10.6 + o, 10.9 + o, None, None, {"plan_cache_hit": 0}],
        ["bt.sched.stage", 2, None, 11.0 + o, 15.0 + o, 1, None, {}],
        ["bt.task.launch", 3, None, 11.0 + o, 11.1 + o, 1, 0, {"tasks": 1, "executor": 0}],
        ["bt.sched.stage", 4, None, 15.0 + o, 18.5 + o, 2, None, {}]], dropped=2)


def executor_part(name, stage, offset_s=0.0, lo=11.2, hi=14.0):
    o = offset_s
    return part(name, int((100 + o) * S), 1000 * S, [
        ["bt.task.queued", 1, None, lo - 0.1 + o, lo + o, stage, 0, {}],
        ["bt.task.run", 2, None, lo + o, hi + o, stage, 0, {"partitions": 8}],
        ["bt.shuffle.write", 3, 2, lo + 0.1 + o, hi - 0.1 + o, stage, 0, {}],
        ["bt.stage.dispatch", 4, 3, lo + 0.2 + o, hi - 0.2 + o, stage, 0, {}]], dropped=1)


def by_name(record):
    out: dict = {}
    for row in record["spans"]:
        out.setdefault(row[0], []).append(row)
    return out


def check_tree(record, slack_s=1e-6):
    ids = [row[1] for row in record["spans"]]
    assert len(ids) == len(set(ids)), "ids collide"
    rows = {row[1]: row for row in record["spans"]}
    for row in record["spans"]:
        assert len(row) == 8
        if row[2] is not None:
            parent = rows[row[2]]  # KeyError: a parent that does not exist
            assert parent[3] - slack_s <= row[3] and row[4] <= parent[4] + slack_s, (row, parent)


def test_join_makes_colliding_ids_unique_and_keeps_each_parts_tree():
    record = join_job_parts("j1", [client_part(), scheduler_part(),
                                   executor_part("executor:a", 1),
                                   executor_part("executor:b", 2, lo=15.2, hi=18.0)])
    check_tree(record)
    assert len(record["spans"]) == 16 and record["spans_dropped"] == 4
    assert record["processes"] == ["client", "scheduler:s0", "executor:a", "executor:b"]
    names = by_name(record)
    # a part's own nesting survives the renumbering, in both executors
    for run in names["bt.task.run"]:
        write = next(w for w in names["bt.shuffle.write"] if w[7]["proc"] == run[7]["proc"])
        dispatch = next(d for d in names["bt.stage.dispatch"] if d[7]["proc"] == run[7]["proc"])
        assert write[2] == run[1] and dispatch[2] == write[1]
    # the asking process's rows are as they were; the others say where they came from
    assert [r[1] for r in names["bt.client.collect"] + names["bt.client.wait"]] == [1, 3]
    assert "proc" not in names["bt.client.collect"][0][7]
    assert sorted(r[7]["proc"] for r in names["bt.task.run"]) == [2, 3]
    assert names["bt.client.fetch_results"][0][7] == {"rows": 7}


@pytest.mark.parametrize("sched_off,exec_off", [(0.0, 0.0), (5000.25, -77.5), (-3.0, 1e6)])
def test_join_puts_every_part_on_the_asking_clock(sched_off, exec_off):
    record = join_job_parts("j1", [client_part(), scheduler_part(sched_off),
                                   executor_part("executor:a", 1, exec_off)])
    check_tree(record, slack_s=2e-6)
    names = by_name(record)
    assert names["bt.sched.plan"][0][3:5] == pytest.approx([10.6, 10.9], abs=2e-6)
    assert names["bt.task.run"][0][3:5] == pytest.approx([11.2, 14.0], abs=2e-6)
    assert names["bt.client.collect"][0][3:5] == [10.0, 20.0]  # the record's clock: untouched


def test_join_hangs_orphans_by_the_rule_of_one_process():
    record = join_job_parts("j1", [client_part(), scheduler_part(),
                                   executor_part("executor:a", 1),
                                   executor_part("executor:b", 2, lo=15.2, hi=18.0)])
    names = by_name(record)
    collect, wait = names["bt.client.collect"][0], names["bt.client.wait"][0]
    stage = {s[5]: s for s in names["bt.sched.stage"]}
    # the scheduler's roots under the client's wait, which contains them
    assert names["bt.sched.plan"][0][2] == wait[1]
    assert stage[1][2] == wait[1] and stage[2][2] == wait[1]
    # an executor's task under the scheduler's stage of the same (job, stage)
    for run in names["bt.task.run"]:
        assert run[2] == stage[run[5]][1]
    for queued in names["bt.task.queued"]:
        assert queued[2] == stage[queued[5]][1]
    assert names["bt.task.launch"][0][2] == stage[1][1]
    assert [r for r in record["spans"] if r[2] is None] == [collect]


def test_join_with_a_part_missing_leaves_roots_and_no_dangling_parent():
    # no scheduler part (it was lost): the tasks hang under the client's wait;
    # a span whose parent never arrived is an orphan like any other, not a
    # dangling reference
    lost_parent = executor_part("executor:a", 1)
    lost_parent["spans"].append(["bt.decode", 9, 77, 12.0, 12.5, 1, 0, {}])
    record = join_job_parts("j1", [client_part(), lost_parent])
    check_tree(record)
    names = by_name(record)
    assert names["bt.task.run"][0][2] == names["bt.client.wait"][0][1]
    assert names["bt.decode"][0][2] == names["bt.client.wait"][0][1]
    # the asking process alone: its record as it was
    alone = join_job_parts("j1", [client_part()])
    assert alone["spans"] == client_part()["spans"] and alone["processes"] == ["client"]
    # and a part with nothing in it (a second fetch) adds nothing
    empty = part("scheduler:s0", 5 * S, 1000 * S, [])
    assert join_job_parts("j1", [client_part(), empty])["spans"] == client_part()["spans"]


def test_take_job_spans_hands_a_jobs_closed_spans_out_once():
    stats = RunStats()
    with stats.span("bt.task.run", job="j9", stage=1, task=0):
        with stats.span("bt.shuffle.write"):
            pass
    stats.add_span("bt.task.queued", time.perf_counter_ns() - 1000, job="j9", stage=1, task=0)
    held = stats.add_span("bt.shuffle.read", time.perf_counter_ns() - 500, job="j9")
    stats.add_span("bt.flight.fetch", held.start + 10, end_ns=held.start + 90, parent=held, bytes=5)
    assert stats.stages() == {}  # no root in this process: nothing was published
    first = stats.take_job_spans("j9")
    assert sorted(r[0] for r in first["spans"]) == [
        "bt.flight.fetch", "bt.shuffle.read", "bt.shuffle.write", "bt.task.queued", "bt.task.run"]
    fetch = next(r for r in first["spans"] if r[0] == "bt.flight.fetch")
    assert fetch[2] == held.id and fetch[7] == {"bytes": 5}
    assert fetch[4] - fetch[3] == pytest.approx(80e-9, abs=2e-6)
    assert stats.take_job_spans("j9") == {"spans": [], "spans_dropped": 0}
    assert len(clock_pair()) == 2


@pytest.mark.parametrize("runs_s", [0.05, 0.855, 4.7, 120.0])
def test_the_clients_wait_adds_a_twentieth_at_most(monkeypatch, runs_s):
    """`wait_for_job` polls; what it adds to a query is the interval that was
    running when the job finished. On a clock that only `sleep` moves: a job
    that takes `runs_s` is seen within 5 % of that (10 ms at the least, 2 s
    at the most), and a long job is not polled at 10 Hz."""
    from ballista_tpu.client import remote
    from ballista_tpu.config import BallistaConfig
    from ballista_tpu.proto import pb

    now = [1000.0]
    monkeypatch.setattr(remote.time, "time", lambda: now[0])
    monkeypatch.setattr(remote.time, "sleep", lambda s: now.__setitem__(0, now[0] + s))
    polls = []

    def get_job_status(req, timeout=None):
        polls.append(now[0] - 1000.0)
        done = polls[-1] >= runs_s
        return pb.GetJobStatusResult(status=pb.JobStatusProto(
            job_id=req.job_id, state="successful" if done else "running"))

    client = remote.RemoteSchedulerClient.__new__(remote.RemoteSchedulerClient)
    client.config = BallistaConfig()
    client.stub = type("Stub", (), {"GetJobStatus": staticmethod(get_job_status)})()
    assert client.wait_for_job("j", timeout=600)["state"] == "successful"
    seen = polls[-1]
    assert runs_s <= seen <= runs_s + min(2.0, max(0.021, 0.05 * runs_s * 1.05))
    assert len(polls) <= 40 + 25 * max(1.0, runs_s) ** 0.5  # 120 s: under 320 polls, not 1200


# ------------------------------------------- the deployment, on the CPU backend

def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Cluster:
    """A scheduler process and `n` executor processes, one a device ordinal."""

    def __init__(self, n: int, out_dir: str):
        self.n, self.port, self.procs = n, free_port(), []
        env = {**os.environ, "PYTHONPATH": ROOT + os.pathsep + os.environ.get("PYTHONPATH", "")}

        def spawn(name: str, argv: list[str]) -> None:
            with open(os.path.join(out_dir, f"{name}.log"), "wb") as log:
                self.procs.append(subprocess.Popen(
                    [sys.executable, "-m", *argv], cwd=ROOT, env=env,
                    stdin=subprocess.DEVNULL, stdout=log, stderr=log, start_new_session=True))

        spawn("scheduler", ["ballista_tpu.scheduler", "--bind-host", "127.0.0.1",
                            "--port", str(self.port), "--rest-port", "-1",
                            "--flight-proxy-port", "-1", "--log-level", "WARNING"])
        for i in range(n):
            spawn(f"executor{i}", [
                "ballista_tpu.executor", "--scheduler", f"127.0.0.1:{self.port}",
                "--bind-host", "127.0.0.1", "--external-host", "127.0.0.1",
                "--engine", "tpu", "--device-ordinal", str(i), "--flight-server", "python",
                "--work-dir", os.path.join(out_dir, f"work{i}"), "--log-level", "WARNING"])

    def context(self, tpch_dir: str, **keys):
        from ballista_tpu.client.context import SessionContext
        from ballista_tpu.config import EXECUTOR_ENGINE, BallistaConfig
        from ballista_tpu.testing.tpchgen import register_tpch

        ctx = SessionContext.remote(f"127.0.0.1:{self.port}",
                                    BallistaConfig({EXECUTOR_ENGINE: "tpu", **keys}))
        register_tpch(ctx, tpch_dir)
        return ctx

    def wait_up(self, client) -> list[dict]:
        import grpc

        while True:
            assert all(p.poll() is None for p in self.procs), "a process ended during start-up"
            try:
                executors = client.diagnostics()["executors"]
                if len(executors) == self.n and all(e.get("devices") for e in executors):
                    return executors
            except grpc.RpcError:
                pass
            time.sleep(0.3)

    def stop(self) -> None:
        for p in self.procs:
            try:
                os.killpg(p.pid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass
            p.wait(timeout=10)


@pytest.fixture(scope="module", params=[1, 2, 4])
def cluster(request, tmp_path_factory, tpch_dir):
    c = Cluster(request.param, str(tmp_path_factory.mktemp(f"cluster{request.param}")))
    try:
        ctx = c.context(tpch_dir)
        c.seen = c.wait_up(ctx._ensure_remote())
        yield c, ctx
    finally:
        c.stop()


ONE_EXECUTOR: dict = {}  # query -> the one-executor cluster's answer


@pytest.mark.parametrize("q", [3, 5])
def test_remote_answers_match_the_reference_and_one_executor(cluster, q, tpch_ref_tables):
    c, ctx = cluster
    got = ctx.sql(tpch_query(q)).collect()
    assert not compare_results(got, run_reference(q, tpch_ref_tables), q)
    if c.n == 1:
        ONE_EXECUTOR[q] = got
    elif q in ONE_EXECUTOR:  # parametrised 1 first; alone, a larger cluster has no partner
        assert not compare_results(got, ONE_EXECUTOR[q].to_pandas(date_as_object=False), q)
    assert [e["ordinal"] for e in c.seen] == list(range(c.n))
    assert all(e["devices"]["platform"] == "cpu" for e in c.seen)


def test_joined_record_of_a_remote_query(cluster, tpch_dir):
    c, _ = cluster
    # over Flight also between executors of one host, whose files the reader
    # would otherwise open in place: the layout the rpc exists for
    ctx = c.context(tpch_dir, **{"ballista.shuffle.reader.force_remote_read": True})
    ctx.sql(tpch_query(3)).collect()
    record = ctx.job_diagnostics()
    check_tree(record, slack_s=5e-3)  # children inside their parents to 5 ms
    names = by_name(record)
    for name in ("bt.client.collect", "bt.client.wait", "bt.client.fetch_results",
                 "bt.sched.plan", "bt.sched.stage", "bt.task.launch", "bt.task.queued",
                 "bt.task.run", "bt.shuffle.write", "bt.shuffle.read", "bt.flight.fetch"):
        assert name in names, name
    assert record["processes"][:2] == ["client", "scheduler:scheduler-0"]
    assert len(record["processes"]) == 2 + c.n
    # every executor ran a task of this query (a slot a chip: the tasks spread)
    assert {r[7]["proc"] for r in names["bt.task.run"]} == set(range(2, 2 + c.n))
    assert {r[7]["proc"] for r in names["bt.task.launch"] + names["bt.sched.plan"]} == {1}
    launch = names["bt.task.launch"][0]
    assert launch[7]["tasks"] >= 1 and 0 <= launch[7]["executor"] < c.n
    stages = {s[1]: s for s in names["bt.sched.stage"]}
    assert all(r[2] in stages and stages[r[2]][5] == r[5] for r in names["bt.task.run"])
    # planning starts while the submit rpc is still on its way back
    assert names["bt.sched.plan"][0][2] in {
        r[1] for n in ("bt.client.submit", "bt.client.wait", "bt.client.collect") for r in names[n]}
    # a fetch lies in a read and carries its bytes; the client's own fetch of
    # the result is one too
    reads = {r[1] for r in names["bt.shuffle.read"] + names["bt.client.fetch_results"]}
    assert all(f[2] in reads and f[7]["bytes"] >= 0 for f in names["bt.flight.fetch"])
    assert any(f[7].get("proc", 0) >= 2 for f in names["bt.flight.fetch"])
    assert [r for r in record["spans"] if r[2] is None and r[0] != "bt.diag.fetch"] == \
        names["bt.client.collect"]
    assert len(names["bt.task.run"]) == len(names["bt.task.queued"])
    # the other processes handed their spans out: a second fetch has none of theirs
    again = ctx.job_diagnostics(record["job_id"])
    assert all("proc" not in r[7] for r in again["spans"])
    assert "bt.client.collect" in by_name(again)
    assert all(e["job"] is None or not e["job"]["spans"] for e in
               ctx._ensure_remote().diagnostics(record["job_id"])["executors"])


def test_the_joined_record_carries_its_critical_path(cluster, tpch_dir):
    """`job_diagnostics()` says where the query's wall time went: the path's
    seconds by name sum to the client's `collect()`, equal the sum of its
    segments, and hold what only a deployment of several processes waits for —
    the launches, the scheduler's hand-overs, the client's poll."""
    from ballista_tpu.tracing import critical_path

    c, _ = cluster
    ctx = c.context(tpch_dir)
    ctx.sql(tpch_query(5)).collect()
    record = ctx.job_diagnostics()
    path = record["path"]
    root = next(r for r in record["spans"] if r[0] == "bt.client.collect")
    assert path["root_s"] == pytest.approx(root[4] - root[3], abs=1e-6)
    assert sum(path["seconds"].values()) == pytest.approx(path["root_s"], abs=1e-4)
    full = critical_path(record["spans"])
    by_name_s: dict = {}
    for a, b, _, name in full["segments"]:
        by_name_s[name] = by_name_s.get(name, 0.0) + (b - a)
    assert {n: round(v, 6) for n, v in by_name_s.items()} == path["seconds"]
    assert {"bt.task.launch", "bt.sched.stage", "bt.client.wait", "bt.task.run",
            "bt.shuffle.write"} <= set(path["seconds"]), path["seconds"]
    assert 1 <= len(path["longest"]) <= 10
    stages = {r[5] for r in record["spans"] if r[0] == "bt.task.run"}
    assert any(seg[4] in stages and seg[5] is not None for seg in path["longest"])
    # an executor's write span says what it held, through the rpc and the join
    writes = [r for r in record["spans"] if r[0] == "bt.shuffle.write"]
    assert writes and all(r[7]["proc"] >= 2 and "ops_ms" in r[7] and r[7]["ops"]
                          for r in writes)


def test_job_diagnostics_answers_in_standalone_mode_too(tpch_dir):
    """No rpc to ask: the record `RUN_STATS` published, with its path; for a
    job the recorder no longer has, a record without spans and without one."""
    from ballista_tpu.client.context import SessionContext
    from ballista_tpu.config import EXECUTOR_ENGINE, BallistaConfig
    from ballista_tpu.testing.tpchgen import register_tpch
    from ballista_tpu.tracing import RUN_STATS

    ctx = SessionContext.standalone(BallistaConfig({EXECUTOR_ENGINE: "cpu"}), num_executors=1)
    try:
        register_tpch(ctx, tpch_dir)
        ctx.sql(tpch_query(6)).collect()
        first = ctx.job_diagnostics()
        ctx.sql(tpch_query(1)).collect()
        record = ctx.job_diagnostics()
        assert record["job_id"] and record["job_id"] != first["job_id"]
        assert record["spans"] == RUN_STATS.stages()[f"job_{record['job_id']}"]["spans"]
        path = record["path"]
        root = next(r for r in record["spans"] if r[0] == "bt.client.collect")
        assert path["root_s"] == pytest.approx(root[4] - root[3], abs=1e-6)
        assert sum(path["seconds"].values()) == pytest.approx(path["root_s"], abs=1e-4)
        assert {"bt.shuffle.write", "bt.task.run"} <= set(path["seconds"])
        assert all(len(seg) == 6 for seg in path["longest"])
        assert ctx.job_diagnostics(first["job_id"])["path"]["root_s"] == first["path"]["root_s"]
        gone = ctx.job_diagnostics("no-such-job")
        assert gone["spans"] == [] and gone["path"] is None and gone["job_id"] == "no-such-job"
    finally:
        ctx.shutdown()


def test_counters_outcomes_memory_and_cache_of_every_executor(cluster):
    c, ctx = cluster
    client = ctx._ensure_remote()
    kinds = ("device", "below_row_floor", "declined", "error")

    def ran(answer) -> int:
        return sum(e["outcomes"][k] for e in answer["executors"] for k in kinds)

    client.diagnostics(clear=True)
    before = client.diagnostics()
    assert all(e["stages"] == {} for e in before["executors"])
    ctx.sql(tpch_query(5)).collect()
    after = client.diagnostics()
    assert [e["ordinal"] for e in after["executors"]] == list(range(c.n))
    assert ran(after) > ran(before)  # cumulative, summed over the executors
    records = [rec for e in after["executors"] for rec in e["stages"].values()]
    assert records and sum(rec["dispatches"] for rec in records) >= c.n
    assert all(isinstance(v, (int, float, str, list)) for rec in records for v in rec.values())
    for e in after["executors"]:
        cache = e["compile_cache"]
        assert cache["dir"] and cache["requests"] == cache["hits"] + cache["misses"]
        assert isinstance(e["memory"], dict) and len(e["clock"]) == 2
        assert e["devices"]["count"] >= 1 and e["devices"]["ordinal"] == e["ordinal"]
        assert isinstance(e["outcomes"]["recent"], list)
    sched = after["scheduler"]
    assert sched["process"] == "scheduler:scheduler-0"
    assert sched["devices"] is None and sched["memory"] is None  # it holds no chip
    # the wall clocks of one host's processes agree: the pairs put them on one axis
    assert all(abs(e["clock"][1] - sched["clock"][1]) < 2e9 for e in after["executors"])


def test_profile_leaves_one_complete_file_a_holder(cluster, tmp_path):
    from jax.profiler import ProfileData

    c, ctx = cluster
    client = ctx._ensure_remote()
    started = client.profile(True, str(tmp_path))["executors"]
    assert [e["dir"] for e in started] == [str(tmp_path / f"executor{i}") for i in range(c.n)]
    twice = client.profile(True, str(tmp_path / "again"))["executors"]
    assert all("already running" in e["error"] for e in twice)
    ctx.sql(tpch_query(5)).collect()
    stopped = client.profile(False)["executors"]
    for i, e in enumerate(stopped):
        files = glob.glob(str(tmp_path / f"executor{i}" / "**" / "*.xplane.pb"), recursive=True)
        assert files == e["files"] and len(files) == 1
        planes = {p.name: p for p in ProfileData.from_file(files[0]).planes}  # complete: it parses
        assert "profile_start_time" in dict(planes["Task Environment"].stats)
        names = {ev.name for line in planes["/host:CPU"].lines for ev in line.events}
        assert "bt.task.run" in names  # the program's spans lie in the holder's own file
    assert all("no profiler session" in e["error"] for e in client.profile(False)["executors"])
    assert not os.path.exists(tmp_path / "again")


def test_the_remote_client_stays_free_of_jax(cluster, tpch_dir):
    c, _ = cluster
    code = f"""
import sys
from ballista_tpu.client.context import SessionContext
from ballista_tpu.config import BallistaConfig
from ballista_tpu.testing.tpchgen import register_tpch
ctx = SessionContext.remote("127.0.0.1:{c.port}", BallistaConfig({{"ballista.executor.engine": "tpu"}}))
register_tpch(ctx, {tpch_dir!r})
assert ctx.sql("select count(*) from nation").collect().num_rows == 1
record = ctx.job_diagnostics()
assert any(r[0] == "bt.task.run" for r in record["spans"]), record["processes"]
assert "jax" not in sys.modules and "jaxlib" not in sys.modules
"""
    subprocess.run([sys.executable, "-c", code], cwd=ROOT, check=True, timeout=120)
