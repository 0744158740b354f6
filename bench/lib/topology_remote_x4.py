"""Topology `remote_x4`: the deployment BASELINE.json names. A scheduler
process (`python -m ballista_tpu.scheduler`) and `num_executors` executor
processes (`python -m ballista_tpu.executor --engine tpu --device-ordinal i`),
each pinned to one chip, all on this host; the process that runs the queries
is a client on `SessionContext.remote` and never imports jax. The contract a
topology keeps is in bench/README.md.

Everything this file knows of the other processes it asks the scheduler by
rpc: `GetDiagnostics` (every executor asked side by side: the devices it
holds, its stage records, its spans of a job, outcomes, compile cache,
memory, one clock pair) and `Profile` (a profiler session in every executor).
It writes nothing beside the trace files: the processes' logs and work
directories live under `<out>/cluster/` and the work directories go when the
cluster does.

The probes are one scheduler call each and run between the queries of the
window, so inside `hot_query_s`; each is a `bt.diag.fetch` span in the next
query's record, which is where PERF.md's cost of them comes from.
"""

from __future__ import annotations

import contextlib
import ctypes
import os
import shutil
import signal
import socket
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
START_TIMEOUT_S = 300.0
KINDS = ("device", "below_row_floor", "declined", "error")

_LIBC = ctypes.CDLL(None)
_cluster: "Cluster | None" = None  # started by devices(), ended by close_session()


def _out_dir() -> str:
    """The run's output directory: bench/cell.py's own --out-dir (the one
    thing this file reads of the process it is imported into)."""
    argv = sys.argv
    if "--out-dir" in argv[:-1]:
        return argv[argv.index("--out-dir") + 1]
    return os.path.join(ROOT, "chiprun_out", "bench", f"remote_x4_{os.getpid()}")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _die_with_parent() -> None:
    """In the child, before exec: the kernel ends it when the process that
    started it ends, however that comes (a killed cell.py leaves no executor
    holding a chip)."""
    _LIBC.prctl(1, signal.SIGKILL)  # PR_SET_PDEATHSIG


class Cluster:
    """The scheduler and the executors, and the one client the probes use."""

    def __init__(self, config: dict):
        self.n = config["num_executors"]
        self.dir = os.path.join(_out_dir(), "cluster")
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir)
        self.port = _free_port()
        self.procs: dict[str, subprocess.Popen] = {}
        self.client = None
        env = {**os.environ, "PYTHONPATH": ROOT + os.pathsep + os.environ.get("PYTHONPATH", "")}
        engine = config["session"].get("ballista.executor.engine", "cpu")

        def spawn(name: str, argv: list[str]) -> None:
            # cell.py's process group (bench/run.py kills what is left of it) and
            # its death signal: nothing here outlives the process that runs the queries
            with open(os.path.join(self.dir, f"{name}.log"), "wb") as log:
                self.procs[name] = subprocess.Popen(
                    [sys.executable, "-m", *argv], cwd=ROOT, env=env, stdin=subprocess.DEVNULL,
                    stdout=log, stderr=log, preexec_fn=_die_with_parent)

        spawn("scheduler", ["ballista_tpu.scheduler", "--bind-host", "127.0.0.1",
                            "--port", str(self.port), "--rest-port", "-1",
                            "--flight-proxy-port", "-1", "--log-level", "WARNING"])
        for i in range(self.n):
            spawn(f"executor{i}", [
                "ballista_tpu.executor", "--scheduler", f"127.0.0.1:{self.port}",
                "--bind-host", "127.0.0.1", "--external-host", "127.0.0.1",
                "--engine", engine, "--device-ordinal", str(i), "--flight-server", "python",
                "--work-dir", os.path.join(self.dir, f"work{i}"), "--log-level", "WARNING"])

    def log_tails(self) -> str:
        out = []
        for name in self.procs:
            with open(os.path.join(self.dir, f"{name}.log"), "rb") as f:
                out.append(f"--- {name}: {f.read()[-1500:].decode(errors='replace')}")
        return "\n".join(out)

    def wait_registered(self) -> list[dict]:
        """Every executor's own answer to `GetDiagnostics`, once all have
        registered and each says what device it holds."""
        import grpc

        from ballista_tpu.client.remote import RemoteSchedulerClient
        from ballista_tpu.config import BallistaConfig

        self.client = RemoteSchedulerClient(f"127.0.0.1:{self.port}", BallistaConfig())
        deadline = time.time() + START_TIMEOUT_S
        while time.time() < deadline:
            dead = [name for name, p in self.procs.items() if p.poll() is not None]
            if dead:
                raise RuntimeError(f"bench: {dead} ended during start-up\n{self.log_tails()}")
            with contextlib.suppress(grpc.RpcError):
                executors = self.client.diagnostics()["executors"]
                if len(executors) == self.n and all(e.get("devices") for e in executors):
                    return executors
            time.sleep(0.25)
        raise RuntimeError(f"bench: {self.n} executors did not register in "
                           f"{START_TIMEOUT_S:.0f} s\n{self.log_tails()}")

    def ask(self, job_id: str = "", clear: bool = False) -> list[dict]:
        """One scheduler call; the executors' parts, each one's own answer."""
        executors = self.client.diagnostics(job_id, clear)["executors"]
        lost = [e for e in executors if "error" in e]
        if lost or len(executors) != self.n:
            raise RuntimeError(f"bench: {self.n} executors asked, {len(executors)} alive, "
                               f"errors {lost}")
        return executors

    def stop(self) -> None:
        """Ends every process and waits for it: the executors first, while
        the scheduler still answers (SIGTERM is their drain, a second one
        the hard stop), then the scheduler, then whatever is left."""
        execs = [p for name, p in self.procs.items() if name != "scheduler"]

        def wait_all(procs, seconds: float) -> None:
            deadline = time.time() + seconds
            for p in procs:
                with contextlib.suppress(subprocess.TimeoutExpired):
                    p.wait(timeout=max(0.05, deadline - time.time()))

        for pause in (0.5, 15.0):
            for p in execs:
                if p.poll() is None:
                    p.send_signal(signal.SIGTERM)
            wait_all(execs, pause)
        sched = self.procs.get("scheduler")
        if sched is not None and sched.poll() is None:
            sched.terminate()
            wait_all([sched], 10.0)
        for p in self.procs.values():
            if p.poll() is None:
                p.kill()
            p.wait()
        for i in range(self.n):  # shuffle files: nothing of them is a result
            shutil.rmtree(os.path.join(self.dir, f"work{i}"), ignore_errors=True)


def devices(config: dict) -> dict:
    """Starts the cluster and reports what its executors say they hold,
    counted over all of them. A program without the diagnostics rpc cannot
    run this configuration: said before any process is started."""
    global _cluster
    from ballista_tpu.proto import pb

    if not hasattr(pb, "DiagnosticsParams"):
        sys.exit("bench: this program has no GetDiagnostics rpc: topology remote_x4 "
                 "cannot read its executors' devices, spans or counters")
    _cluster = Cluster(config)
    try:
        seen = [e["devices"] for e in _cluster.wait_registered()]
    except BaseException:
        close_session(None)  # leave none behind
        raise
    kinds = sorted({(d["platform"], d["kind"]) for d in seen})
    platform, kind = kinds[0] if len(kinds) == 1 else ("mixed", str(kinds))
    return {"platform": platform, "kind": kind, "count": sum(d["count"] for d in seen)}


def open_session(config: dict, data_dir: str):
    from ballista_tpu.client.context import SessionContext
    from ballista_tpu.config import BallistaConfig
    from ballista_tpu.ops import native
    from ballista_tpu.plan.provider import ParquetTable

    # the shuffle's row router builds itself from native/ on first use (~7 s
    # of g++ in a new checkout): here, in set-up, once, not in four first queries
    native.get_lib()
    session = SessionContext.remote(f"127.0.0.1:{_cluster.port}",
                                    BallistaConfig(dict(config["session"])))
    for table in config["tables"]:
        session.register_table(table, ParquetTable(os.path.join(data_dir, table)))
    return session


def close_session(session) -> None:
    global _cluster
    if _cluster is not None:
        cluster, _cluster = _cluster, None
        cluster.stop()
    assert "jax" not in sys.modules, "the process that runs the queries imported jax"


def _profile(start: bool, trace_dir: str = "") -> None:
    answers = _cluster.client.profile(start, trace_dir)["executors"]
    failed = [a for a in answers if "error" in a]
    if failed or len(answers) != _cluster.n:
        raise RuntimeError(f"bench: profiler {'start' if start else 'stop'}: "
                           f"{len(answers)} of {_cluster.n} executors answered, errors {failed}")


def start_trace(session, trace_dir: str):
    """A profiler session in every executor, each into `executor<i>/` under
    `trace_dir`. This process traces nothing: the record's wall-clock marks
    are the window's."""
    _profile(True, trace_dir)
    return lambda name: contextlib.nullcontext()


def stop_trace(session) -> None:
    _profile(False)  # returns when every file is complete


class Probes:
    """The cluster's counters, one scheduler call each."""

    def clear_run_stats(self) -> None:
        from ballista_tpu.tracing import RUN_STATS

        RUN_STATS.clear()
        _cluster.ask(clear=True)

    def run_stats_stages(self) -> dict:
        """Every executor's stage records since the last clear, a record an
        executor (`<stage tag>@executor<i>`: four executors' records of one
        stage stay four, so `dispatches` sums to what the cluster
        dispatched), and the last query's ONE `job_<id>` record joined from
        the client's, the scheduler's and the executors' spans."""
        from ballista_tpu.tracing import RUN_STATS

        jobs = [tag[len("job_"):] for tag in RUN_STATS.stages() if tag.startswith("job_")]
        if not jobs:
            parts, joined = _cluster.ask(), None
        else:
            joined = _cluster.client.job_diagnostics(jobs[-1])
            parts = joined["parts"]["executors"]
        out = {f"{tag}@executor{part['ordinal']}": rec
               for part in parts for tag, rec in (part.get("stages") or {}).items()}
        if joined is not None:
            out[f"job_{jobs[-1]}"] = {k: joined[k] for k in ("spans", "spans_dropped", "processes")}
        return out

    def outcomes(self) -> dict:
        parts = _cluster.ask()
        return {k: sum(p["outcomes"][k] for p in parts) for k in KINDS}

    def outcomes_recent(self) -> list:
        return [list(r) for p in _cluster.ask() for r in p["outcomes"]["recent"]
                if r[1] != "device"]

    def compile_cache(self) -> dict:
        caches = [p["compile_cache"] for p in _cluster.ask()]
        dirs = sorted({str(c["dir"]) for c in caches})
        return {**{k: sum(c[k] for c in caches) for k in ("requests", "hits", "misses")},
                "dir": dirs[0] if len(dirs) == 1 else ",".join(dirs)}

    def memory_stats(self) -> tuple[dict, dict]:
        """The numbers of the fullest chip's `memory_stats()` and every
        chip's peak bytes by `executor<i>`."""
        parts = _cluster.ask()
        peaks = {f"executor{p['ordinal']}": p["memory"].get("peak_bytes_in_use", 0)
                 for p in parts}
        fullest = max(parts, key=lambda p: p["memory"].get("peak_bytes_in_use", 0))
        return fullest["memory"], peaks
