"""Per-chip executor device pinning (SURVEY §7 step 7: one executor per
chip, scheduler slot = chip; reference analog: the vcore slot model of
executor/src/executor_process.rs:261 + state/executor_manager.rs:62).

Runs on the virtual 8-device CPU mesh from conftest. Three layers pinned:
 * runtime.device_scope commits jax ops to the bound device;
 * an in-process cluster of differently pinned tpu-engine executors keeps
   device placement disjoint (cache keys include the ordinal);
 * real daemon subprocesses accept --device-ordinal and register chip=slot
   metadata with the scheduler.
"""

import json
import os
import subprocess
import sys
import time
import urllib.request

import pytest

from .conftest import tpch_query


def test_bound_device_and_scope():
    import jax

    from ballista_tpu.ops.tpu.runtime import bound_device, device_scope

    devs = jax.devices()
    assert len(devs) == 8, "conftest must force an 8-device CPU mesh"
    assert bound_device(3) is devs[3]
    assert bound_device(-1) is None
    with device_scope(3):
        x = jax.numpy.arange(8) * 2
        assert x.devices() == {devs[3]}
    with device_scope(-1):  # unpinned: no-op scope
        y = jax.numpy.arange(4)
        assert y.devices() == {devs[0]}


def test_platform_questions_are_asked_of_the_bound_device():
    """current_device() follows the thread's device_scope pin, not
    jax.devices()[0] — on a process that sees several devices the HBM
    budget must ask the device the task is bound to."""
    import jax

    from ballista_tpu.ops.tpu import hbm, runtime

    devs = jax.devices()
    assert runtime.current_device() is devs[0]
    with runtime.device_scope(5):
        assert runtime.current_device() is devs[5]
        assert runtime.current_device().platform == "cpu"
        # the CPU backend reports no memory stats: 0 by observation, and
        # the budget falls to the configured ceiling — not by exception
        assert hbm.detect_device_memory_bytes() == 0
    assert runtime.current_device() is devs[0]


def test_bind_process_ordinal_after_backend_init_is_an_error():
    """A process whose jax backend is up already holds every chip it could
    see: pinning it then must fail loudly, never carry on unpinned."""
    import jax

    from ballista_tpu.ops.tpu.runtime import bind_process_ordinal

    jax.devices()
    with pytest.raises(RuntimeError, match="already initialised"):
        bind_process_ordinal(1)
    with pytest.raises(ValueError):
        bind_process_ordinal(-1)


def test_bind_process_ordinal_before_backend_init_pins_one_chip():
    """Before the backend initialises (jax merely imported is fine) the bind
    writes what libtpu reads: one visible chip, one-chip process bounds and
    a slice-builder port of the process's own."""
    code = (
        "import os, jax\n"
        "from ballista_tpu.ops.tpu.runtime import bind_process_ordinal\n"
        "bind_process_ordinal(2)\n"
        "print(os.environ['TPU_VISIBLE_CHIPS'],"
        " os.environ['TPU_CHIPS_PER_PROCESS_BOUNDS'],"
        " os.environ['TPU_PROCESS_BOUNDS'], os.environ['TPU_PROCESS_PORT'],"
        " os.environ['TPU_PROCESS_ADDRESSES'], len(jax.devices()))\n")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run([sys.executable, "-c", code], cwd=root,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    assert r.stdout.split() == ["2", "1,1,1", "1,1,1", "8478",
                                "localhost:8478", "8"]


def test_metadata_serde_roundtrip_ordinal():
    from ballista_tpu.executor.executor import ExecutorMetadata
    from ballista_tpu.serde_control import decode_executor_metadata, encode_executor_metadata

    # ordinal 0 is a valid chip and must survive the wire (explicit presence)
    m0 = ExecutorMetadata(id="e0", device_ordinal=0)
    assert decode_executor_metadata(encode_executor_metadata(m0)).device_ordinal == 0
    # unpinned stays unpinned
    mu = ExecutorMetadata(id="e1")
    assert decode_executor_metadata(encode_executor_metadata(mu)).device_ordinal == -1


@pytest.fixture(scope="module")
def pinned_cluster():
    from ballista_tpu.executor.executor_process import ExecutorProcess
    from ballista_tpu.scheduler.process import SchedulerProcess

    sched = SchedulerProcess(bind_host="127.0.0.1", port=0, rest_port=0)
    sched.start()
    addr = f"127.0.0.1:{sched.port}"
    ex1 = ExecutorProcess(addr, bind_host="127.0.0.1", external_host="127.0.0.1",
                          engine="tpu", device_ordinal=1)
    ex2 = ExecutorProcess(addr, bind_host="127.0.0.1", external_host="127.0.0.1",
                          engine="tpu", device_ordinal=2)
    ex1.start()
    ex2.start()
    time.sleep(0.3)
    yield sched, addr, ex1, ex2
    ex1.shutdown()
    ex2.shutdown()
    sched.shutdown()


def test_pinned_slot_model(pinned_cluster):
    """engine=tpu + pinned chip ⇒ vcores defaults to 1: slots = chips."""
    _, _, ex1, ex2 = pinned_cluster
    assert ex1.metadata.vcores == 1
    assert ex2.metadata.vcores == 1
    assert {ex1.metadata.device_ordinal, ex2.metadata.device_ordinal} == {1, 2}


def test_pinned_cluster_query_and_placement(pinned_cluster, tpch_dir, tpch_ref_tables):
    from ballista_tpu.client.context import SessionContext
    from ballista_tpu.config import EXECUTOR_ENGINE, BallistaConfig
    from ballista_tpu.ops.tpu import stage_compiler
    from ballista_tpu.testing.reference import compare_results, run_reference
    from ballista_tpu.testing.tpchgen import register_tpch

    _, addr, ex1, ex2 = pinned_cluster
    from ballista_tpu.config import TPU_MIN_ROWS

    stage_compiler.DEVICE_CACHE._cache.clear()
    cfg = BallistaConfig({EXECUTOR_ENGINE: "tpu", TPU_MIN_ROWS: 0})
    ctx = SessionContext.remote(addr, cfg)
    register_tpch(ctx, tpch_dir)
    for q in (1, 6):
        got = ctx.sql(tpch_query(q)).collect()
        problems = compare_results(got, run_reference(q, tpch_ref_tables), q)
        assert not problems, "\n".join(problems)

    # every device-resident table must sit on one of the two pinned chips,
    # never the process default (device 0)
    import jax

    devs = jax.devices()
    tables = list(stage_compiler.DEVICE_CACHE._cache.values())
    assert tables, "tpu engine should have cached at least one device table"
    for dt in tables:
        places = set()
        for c in dt.cols:
            places |= c.devices()
        assert places and places <= {devs[1], devs[2]}, places


def test_health_and_rest_report_ordinal(pinned_cluster):
    sched, _, ex1, _ = pinned_cluster
    with urllib.request.urlopen(
            f"http://127.0.0.1:{ex1.health_port}/health", timeout=5) as r:
        assert json.load(r)["device_ordinal"] == 1
    with urllib.request.urlopen(
            f"http://127.0.0.1:{sched.rest_port}/api/executors", timeout=5) as r:
        info = json.load(r)
    assert {e["device_ordinal"] for e in info} == {1, 2}
    assert all(e["total_slots"] == 1 for e in info)


def _spawn_executor_daemon(addr: str, ordinal: int, work_dir: str):
    """Daemon stderr goes to a FILE under its work dir — a PIPE nobody
    drains would wedge a chatty daemon on a full 64 KiB buffer mid-run."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_PLATFORM_NAME"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    os.makedirs(work_dir, exist_ok=True)
    stderr_path = os.path.join(work_dir, "daemon.stderr")
    p = subprocess.Popen(
        [sys.executable, "-m", "ballista_tpu.executor",
         "--scheduler", addr, "--bind-host", "127.0.0.1",
         "--external-host", "127.0.0.1", "--engine", "tpu",
         "--device-ordinal", str(ordinal), "--work-dir", work_dir,
         "--flight-server", "python", "--log-level", "WARNING"],
        env=env, stdout=subprocess.DEVNULL, stderr=open(stderr_path, "wb"),
    )
    p.stderr_path = stderr_path
    return p


def _daemon_stderr_tail(p) -> str:
    try:
        with open(p.stderr_path, "rb") as f:
            return f.read()[-2000:].decode(errors="replace")
    except OSError:
        return "<no stderr captured>"


def test_pinned_daemon_subprocesses(tmp_path, tpch_dir, tpch_ref_tables):
    """Real daemon processes, each pinned via --device-ordinal, serving a
    remote tpu-engine query (the deployment shape: one daemon per chip)."""
    from ballista_tpu.client.context import SessionContext
    from ballista_tpu.config import EXECUTOR_ENGINE, TPU_MIN_ROWS, BallistaConfig
    from ballista_tpu.scheduler.process import SchedulerProcess
    from ballista_tpu.testing.reference import compare_results, run_reference
    from ballista_tpu.testing.tpchgen import register_tpch

    sched = SchedulerProcess(bind_host="127.0.0.1", port=0, rest_port=0)
    sched.start()
    addr = f"127.0.0.1:{sched.port}"
    procs = [
        _spawn_executor_daemon(addr, i, str(tmp_path / f"ex{i}")) for i in (0, 1)
    ]
    try:
        deadline = time.time() + 90
        while time.time() < deadline:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{sched.rest_port}/api/executors", timeout=5) as r:
                info = json.load(r)
            if len(info) == 2:
                break
            for p in procs:
                assert p.poll() is None, _daemon_stderr_tail(p)
            time.sleep(0.5)
        assert len(info) == 2, "daemons did not register in time"
        assert {e["device_ordinal"] for e in info} == {0, 1}
        assert all(e["total_slots"] == 1 for e in info)

        cfg = BallistaConfig({EXECUTOR_ENGINE: "tpu", TPU_MIN_ROWS: 0})
        ctx = SessionContext.remote(addr, cfg)
        register_tpch(ctx, tpch_dir)
        got = ctx.sql(tpch_query(6)).collect()
        problems = compare_results(got, run_reference(6, tpch_ref_tables), 6)
        assert not problems, "\n".join(problems)
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
        sched.shutdown()
