"""Real gRPC cluster integration: scheduler daemon + push/pull executor
daemons + remote client, in one process but over real sockets.

Reference analog: the client crate's remote-context tests + tpch.yml's
distributed matrix (scaled down to a handful of representative queries).
"""

import time

import pytest

from ballista_tpu.testing.reference import compare_results, run_reference

from .conftest import tpch_query


@pytest.fixture(scope="module")
def grpc_cluster(tmp_path_factory):
    from ballista_tpu.executor.executor_process import ExecutorProcess
    from ballista_tpu.scheduler.process import SchedulerProcess

    sched = SchedulerProcess(bind_host="127.0.0.1", port=0, rest_port=0)
    sched.start()
    addr = f"127.0.0.1:{sched.port}"
    ex1 = ExecutorProcess(addr, bind_host="127.0.0.1", external_host="127.0.0.1", vcores=4)
    ex2 = ExecutorProcess(addr, bind_host="127.0.0.1", external_host="127.0.0.1",
                          vcores=4, policy="pull")
    ex1.start()
    ex2.start()
    time.sleep(0.3)
    sched.test_executors = [ex1, ex2]  # so tests can reach real work dirs
    yield sched, addr
    ex1.shutdown()
    ex2.shutdown()
    sched.shutdown()


@pytest.fixture()
def remote_ctx(grpc_cluster, tpch_dir):
    from ballista_tpu.client.context import SessionContext
    from ballista_tpu.testing.tpchgen import register_tpch

    _, addr = grpc_cluster
    ctx = SessionContext.remote(addr)
    register_tpch(ctx, tpch_dir)
    return ctx


@pytest.mark.parametrize("q", [1, 3, 13, 22])
def test_tpch_remote_grpc(q, remote_ctx, tpch_ref_tables):
    eng = remote_ctx.sql(tpch_query(q)).collect()
    problems = compare_results(eng, run_reference(q, tpch_ref_tables), q)
    assert not problems, "\n".join(problems)


def test_rest_api(grpc_cluster, remote_ctx):
    import json
    import urllib.request

    sched, _ = grpc_cluster
    port = sched.rest_port
    state = json.load(urllib.request.urlopen(f"http://127.0.0.1:{port}/api/state"))
    assert state["executors"] == 2
    execs = json.load(urllib.request.urlopen(f"http://127.0.0.1:{port}/api/executors"))
    assert len(execs) == 2
    # run a query, then check job endpoints + prometheus + dot
    remote_ctx.sql("select count(*) from nation").collect()
    jobs = json.load(urllib.request.urlopen(f"http://127.0.0.1:{port}/api/jobs"))
    assert jobs
    job_id = jobs[-1]["job_id"]
    stages = json.load(urllib.request.urlopen(f"http://127.0.0.1:{port}/api/job/{job_id}/stages"))
    assert stages and "plan" in stages[0]
    pcts = [p for s in stages for p in s.get("metric_percentiles", [])]
    assert pcts and all("elapsed_ms_p50" in p and "tasks" in p for p in pcts)
    dot = urllib.request.urlopen(f"http://127.0.0.1:{port}/api/job/{job_id}/dot").read().decode()
    assert dot.startswith("digraph")
    metrics = urllib.request.urlopen(f"http://127.0.0.1:{port}/api/metrics").read().decode()
    assert "ballista_scheduler_jobs_completed_total" in metrics
    # web monitor page + its JSON stage-graph endpoint; the page embeds the
    # sparkline/config features backed by /api/config
    page = urllib.request.urlopen(f"http://127.0.0.1:{port}/").read().decode()
    assert "cluster monitor" in page and "/api/jobs" in page
    assert "spark-act" in page and "toggleConfig" in page
    cfg = json.load(urllib.request.urlopen(f"http://127.0.0.1:{port}/api/config"))
    assert cfg["session_config_entries"] and cfg["scheduler_id"]
    graph = json.load(urllib.request.urlopen(f"http://127.0.0.1:{port}/api/job/{job_id}/graph"))
    assert graph["job_id"] == job_id and graph["stages"]
    assert all(len(e) == 2 for e in graph["edges"])
    sids = {s["stage_id"] for s in graph["stages"]}
    assert all(a in sids and b in sids for a, b in graph["edges"])


def test_native_data_plane_forced_remote(grpc_cluster, tpch_dir, tpch_ref_tables):
    """Force every shuffle fetch over Flight (no local fast path): sort-
    layout partition reads go through the executors' native C++ servers."""
    from ballista_tpu.client.context import SessionContext
    from ballista_tpu.config import SHUFFLE_READER_FORCE_REMOTE
    from ballista_tpu.testing.tpchgen import register_tpch

    _, addr = grpc_cluster
    ctx = SessionContext.remote(addr)
    ctx.config.set(SHUFFLE_READER_FORCE_REMOTE, True)
    register_tpch(ctx, tpch_dir)
    eng = ctx.sql(tpch_query(3)).collect()
    problems = compare_results(eng, run_reference(3, tpch_ref_tables), 3)
    assert not problems, "\n".join(problems)


def test_flight_result_proxy(grpc_cluster, tpch_dir):
    """Clients that cannot reach executors fetch results through the
    scheduler's Flight proxy (flight_proxy_service.rs analog)."""
    from ballista_tpu.client.context import SessionContext
    from ballista_tpu.config import FLIGHT_PROXY
    from ballista_tpu.testing.tpchgen import register_tpch

    sched, addr = grpc_cluster
    assert sched.flight_proxy_port > 0
    ctx = SessionContext.remote(addr)
    ctx.config.set(FLIGHT_PROXY, f"127.0.0.1:{sched.flight_proxy_port}")
    register_tpch(ctx, tpch_dir)
    out = ctx.sql(
        "select r_name, count(*) c from nation, region "
        "where n_regionkey = r_regionkey group by r_name order by r_name"
    ).collect()
    assert out.num_rows == 5
    assert out.column("c").to_pylist() == [5, 5, 5, 5, 5]


def test_execute_query_push(grpc_cluster, tpch_dir):
    """Server-streaming status: submit + watch in one rpc, no polling."""
    from ballista_tpu.client.context import SessionContext
    from ballista_tpu.config import PUSH_STATUS
    from ballista_tpu.testing.tpchgen import register_tpch

    _, addr = grpc_cluster
    ctx = SessionContext.remote(addr)
    ctx.config.set(PUSH_STATUS, True)
    register_tpch(ctx, tpch_dir)
    out = ctx.sql("select count(*) n from nation").collect()
    assert out.column("n").to_pylist() == [25]
    # direct stream: terminal event carries the full status
    client = ctx._ensure_remote()
    status = client.execute_sql_push("select count(*) n from region")
    assert status["state"] == "successful"


def test_executor_memory_sizing(grpc_cluster):
    """cgroup/host-aware memory pool drives the per-task spill budget."""
    from ballista_tpu.config import BallistaConfig, SORT_SHUFFLE_MEMORY_LIMIT
    from ballista_tpu.executor.executor_process import detect_memory_limit

    assert detect_memory_limit() > 0
    cfg = BallistaConfig()
    cfg.set_default_if_unset(SORT_SHUFFLE_MEMORY_LIMIT, 123)
    assert cfg.get(SORT_SHUFFLE_MEMORY_LIMIT) == 123
    explicit = BallistaConfig({SORT_SHUFFLE_MEMORY_LIMIT: 999})
    explicit.set_default_if_unset(SORT_SHUFFLE_MEMORY_LIMIT, 123)
    assert explicit.get(SORT_SHUFFLE_MEMORY_LIMIT) == 999


def test_wire_version_gate(grpc_cluster):
    from ballista_tpu.executor.executor import ExecutorMetadata
    from ballista_tpu.proto import pb
    from ballista_tpu.scheduler.grpc_service import scheduler_stub
    from ballista_tpu.serde_control import encode_executor_metadata

    import grpc

    _, addr = grpc_cluster
    stub = scheduler_stub(grpc.insecure_channel(addr))
    bad = ExecutorMetadata(id="bad", wire_version="btpu-OLD")
    resp = stub.RegisterExecutor(
        pb.RegisterExecutorParams(metadata=encode_executor_metadata(bad)), timeout=5
    )
    assert not resp.success
    assert "wire protocol" in resp.error


def test_cancel_job(remote_ctx, grpc_cluster):
    client = remote_ctx._ensure_remote()
    job_id = client.execute_sql(tpch_query(9))
    client.cancel_job(job_id)
    status = client.wait_for_job(job_id, timeout=30)
    assert status["state"] in ("cancelled", "successful")  # may finish first


def test_tui_rest_client_against_live_scheduler(grpc_cluster, remote_ctx):
    from ballista_tpu.cli.tui import RestClient, render_jobs, render_stages

    sched, _ = grpc_cluster
    remote_ctx.sql("select count(*) from region").collect()
    c = RestClient(f"http://127.0.0.1:{sched.rest_port}")
    assert c.state()["executors"] == 2
    jobs = c.jobs()
    assert jobs and jobs[-1]["state"] == "successful"
    assert c.executors()
    st = c.stages(jobs[-1]["job_id"])
    assert st and "metric_percentiles" in st[0]
    # the render layer digests live payloads
    assert len(render_jobs(jobs, 0)) == len(jobs) + 1
    assert len(render_stages(st)) == len(st) + 1


def test_memory_tables_over_remote_cluster(grpc_cluster):
    """In-memory tables work against a REAL cluster: the client plans and
    ships the physical plan with MemoryScanNode IPC bytes (the reference's
    BallistaQueryPlanner flow)."""
    import pyarrow as pa

    from ballista_tpu.client.context import SessionContext

    _, addr = grpc_cluster
    ctx = SessionContext.remote(addr)
    ctx.register_arrow_table("mem", pa.table({"x": [1, 2, 3, 4], "g": ["a", "b", "a", "b"]}),
                             partitions=2)
    out = ctx.sql("select g, sum(x) s, count(*) c from mem group by g order by g").collect()
    assert out.column("s").to_pylist() == [4, 6]
    assert out.column("c").to_pylist() == [2, 2]


def test_remote_explain_analyze(grpc_cluster, remote_ctx):
    """EXPLAIN ANALYZE in remote mode renders per-stage operator metrics
    fetched over GetJobMetrics (DistributedExplainAnalyzeExec analog)."""
    out = remote_ctx.sql(
        "explain analyze select n_regionkey, count(*) from nation group by n_regionkey"
    ).collect()
    plans = dict(zip(out.column("plan_type").to_pylist(), out.column("plan").to_pylist()))
    body = plans.get("analyzed_plan (distributed)", "")
    assert "stage" in body and "elapsed_ms" in body, plans
    # one operator number, one meaning: the inclusive time and the operator's own
    import re

    pairs = re.findall(r"elapsed_ms=([0-9.]+) self_ms=([0-9.]+)", body)
    assert pairs and all(float(own) <= float(whole) + 0.01 for whole, own in pairs), body
    assert any(float(own) > 0 for _, own in pairs), body


def test_concurrent_sessions_and_jobs(grpc_cluster, tpch_dir, tpch_ref_tables):
    """8 clients submit simultaneously: scheduler state (event loop, graph
    registry, session manager, slot accounting) stays consistent and every
    result is correct."""
    import concurrent.futures as fut

    from ballista_tpu.client.context import SessionContext
    from ballista_tpu.testing.tpchgen import register_tpch

    _, addr = grpc_cluster
    queries = [1, 3, 6, 12, 14, 19, 6, 1]

    def run_one(q):
        ctx = SessionContext.remote(addr)
        register_tpch(ctx, tpch_dir)
        out = ctx.sql(tpch_query(q)).collect()
        return q, compare_results(out, run_reference(q, tpch_ref_tables), q)

    with fut.ThreadPoolExecutor(max_workers=8) as pool:
        results = list(pool.map(run_one, queries))
    bad = [(q, p) for q, p in results if p]
    assert not bad, bad


def test_clean_job_data_gc_fans_out(grpc_cluster, remote_ctx):
    """CleanJobData removes the job's shuffle files on EVERY executor
    (reference: ExecutorManager::clean_up_job_data rpc fan-out), not just
    the scheduler's own state."""
    import glob
    import os
    import time as _t

    sched, addr = grpc_cluster
    out = remote_ctx.sql("select count(*) c from lineitem").collect()
    assert out.num_rows == 1
    with sched.scheduler._jobs_lock:
        job_id = list(sched.scheduler.jobs)[-1]
    # the job's shuffle dirs must exist under the real executor work dirs
    # BEFORE cleanup — otherwise this test can pass without testing anything
    work_dirs = [ex.work_dir for ex in sched.test_executors]
    before = [d for wd in work_dirs for d in glob.glob(os.path.join(wd, job_id))]
    assert before, f"no shuffle dirs for {job_id} under {work_dirs}"
    sched.scheduler.clean_job_data(job_id)
    deadline = _t.time() + 10
    remaining = list(before)
    while _t.time() < deadline and remaining:
        remaining = [d for wd in work_dirs for d in glob.glob(os.path.join(wd, job_id))]
        _t.sleep(0.2)
    assert not remaining, remaining


def test_keda_external_scaler(grpc_cluster, remote_ctx):
    """KEDA ExternalScaler rpcs on the scheduler port (external_scaler.rs):
    IsActive true, spec advertises pending_jobs, metrics report queue
    pressure as job counts."""
    import grpc as grpclib

    from ballista_tpu.proto import keda_pb2 as kpb
    from ballista_tpu.scheduler.external_scaler import external_scaler_stub

    from types import SimpleNamespace

    from ballista_tpu.scheduler.state.execution_graph import JobState

    sched, addr = grpc_cluster
    with grpclib.insecure_channel(addr) as ch:
        stub = external_scaler_stub(ch)
        assert stub.IsActive(kpb.ScaledObjectRef(name="x")).result is True
        spec = stub.GetMetricSpec(kpb.ScaledObjectRef(name="x"))
        # executor scaling on pending_jobs, scheduler scaling on the
        # deepest shard event queue
        assert [(m.metricName, m.targetSize) for m in spec.metricSpecs] == [
            ("pending_jobs", 1), ("shard_queue_depth", 1)]
        spec5 = stub.GetMetricSpec(
            kpb.ScaledObjectRef(name="x", scalerMetadata={"targetSize": "5"}))
        assert spec5.metricSpecs[0].targetSize == 5
        remote_ctx.sql("select count(*) from region").collect()
        # observe NONZERO pressure: park fake queued/running jobs in the
        # registry so the count mapping is actually exercised
        s = sched.scheduler
        fakes = {
            "zz_q1": SimpleNamespace(status=JobState.QUEUED),
            "zz_q2": SimpleNamespace(status=JobState.QUEUED),
            "zz_r1": SimpleNamespace(status=JobState.RUNNING),
        }
        with s._jobs_lock:
            s.jobs.update(fakes)
        try:
            vals = {m.metricName: m.metricValue
                    for m in stub.GetMetrics(kpb.GetMetricsRequest()).metricValues}
        finally:
            with s._jobs_lock:
                for k in fakes:
                    s.jobs.pop(k, None)
        assert vals["pending_jobs"] == 2
        assert vals["running_jobs"] == 1
