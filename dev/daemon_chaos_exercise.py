"""Exercise the device-runtime daemon's failure domain end-to-end (CPU jax).

    JAX_PLATFORMS=cpu python dev/daemon_chaos_exercise.py [--quick]

Chaos-kills the daemon out from under live TPC-H queries and checks the
one rule of the failure domain (docs/device_daemon.md#failure-domain):
a daemon death costs one retry, never the query, never a crash loop.

Legs (full mode; --quick runs one of each kind for the bench probe):

1. crash  — `daemon_crash` hard-exits the daemon (exit 137) at every
   arming point (pre/mid/post_execute) under q1 AND q3. The once-marker
   limits the fault to the first armed request, so the ladder must
   respawn, retry, and return bytes identical to the in-process
   baseline with daemon_crashes_detected/daemon_restarts nonzero.
2. hang   — `daemon_hang` wedges the execute thread; the per-request
   watchdog (deadline floor ballista.tpu.daemon.execute.timeout.s)
   must convert the hang into a diagnosed death and the ladder must
   recover byte-identically with watchdog_kills nonzero.
3. watchdog post-mortem — a hang with respawn disabled, so the
   <socket>.crash.json artifact survives for inspection: it must name
   the offending request (tag) and carry every thread's stack, and the
   query must still complete in-process, byte-identical.
4. poison — `daemon_crash` WITHOUT the once-marker: every incarnation
   dies on the stage, the second crash per fingerprint quarantines it
   (<socket>.poison.json), the stage demotes in-process
   byte-identically, and a rerun must touch no daemon at all (the
   crash-loop check: zero new crashes).

Exits non-zero on any divergence.
"""

import io
import json
import os
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

ARM_POINTS = ("pre_execute", "mid_execute", "post_execute")
HANG_TIMEOUT_S = 12  # watchdog floor for hang legs: roomy enough that the
                     # retry's recompile+execute fits, short enough to test


def _sql(name: str) -> str:
    with open(os.path.join(ROOT, "benchmarks", "tpch", "queries",
                           f"{name}.sql")) as f:
        return f.read()


def _ipc_bytes(tbl) -> bytes:
    import pyarrow as pa

    sink = io.BytesIO()
    with pa.ipc.new_stream(sink, tbl.schema) as w:
        w.write_table(tbl)
    return sink.getvalue()


def _run(data_dir: str, sql: str, extra_cfg: dict | None = None):
    """One query in THIS process; returns (result bytes, stats snapshot)."""
    from ballista_tpu.client.context import SessionContext
    from ballista_tpu.config import EXECUTOR_ENGINE, BallistaConfig
    from ballista_tpu.ops.tpu import stage_compiler as sc
    from ballista_tpu.testing.tpchgen import register_tpch

    cfg = BallistaConfig({EXECUTOR_ENGINE: "tpu", **(extra_cfg or {})})
    ctx = SessionContext(cfg)
    register_tpch(ctx, data_dir)
    sc.RUN_STATS.clear()
    out = ctx.sql(sql).collect()
    if out.num_rows == 0:
        raise SystemExit("query produced no rows")
    return _ipc_bytes(out), sc.RUN_STATS.snapshot()


def _chaos_cfg(sock: str, mode: str, arm: str, once: bool,
               spawn: bool = True, **extra) -> dict:
    from ballista_tpu.config import (
        CHAOS_DAEMON_ARM,
        CHAOS_DAEMON_ONCE,
        CHAOS_ENABLED,
        CHAOS_MODE,
        TPU_DAEMON_ATTACH_TIMEOUT_MS,
        TPU_DAEMON_ENABLED,
        TPU_DAEMON_SOCKET,
        TPU_DAEMON_SPAWN,
    )

    return {TPU_DAEMON_ENABLED: True, TPU_DAEMON_SOCKET: sock,
            TPU_DAEMON_SPAWN: spawn, TPU_DAEMON_ATTACH_TIMEOUT_MS: 60_000,
            CHAOS_ENABLED: True, CHAOS_MODE: mode,
            CHAOS_DAEMON_ARM: arm, CHAOS_DAEMON_ONCE: once, **extra}


def _shutdown(sock: str) -> None:
    from ballista_tpu.device_daemon import client as dclient

    try:
        dclient.DaemonClient(sock, timeout_s=5.0).shutdown()
    except Exception:  # noqa: BLE001 — a corpse is the expected case here
        pass
    dclient.reset_attach_cache()


def _check(leg: str, cond: bool, msg: str) -> None:
    if not cond:
        raise SystemExit(f"[{leg}] FAILED: {msg}")


def _crash_leg(d: str, data_dir: str, query: str, baseline: bytes,
               mode: str, arm: str) -> None:
    from ballista_tpu.device_daemon import client as dclient

    leg = f"{mode}@{arm}/{query}"
    sock = os.path.join(d, f"{mode}-{arm}-{query}.sock")
    extra = {}
    if mode == "daemon_hang":
        from ballista_tpu.config import TPU_DAEMON_EXECUTE_TIMEOUT_S

        extra[TPU_DAEMON_EXECUTE_TIMEOUT_S] = HANG_TIMEOUT_S
    dclient.reset_failure_counters()
    try:
        blob, stats = _run(data_dir, _sql(query),
                           _chaos_cfg(sock, mode, arm, once=True, **extra))
        c = dclient.failure_counters()
        _check(leg, blob == baseline, "result bytes diverged from baseline")
        _check(leg, c["daemon_crashes_detected"] >= 1,
               f"no crash detected (counters {c})")
        _check(leg, c["daemon_restarts"] >= 1,
               f"crash was not recovered by respawn ({c})")
        _check(leg, c["poisoned_stages"] == 0,
               f"once-armed fault must not quarantine ({c})")
        if mode == "daemon_hang":
            _check(leg, c["watchdog_kills"] >= 1,
                   f"hang was not classified as a watchdog kill ({c})")
        _check(leg, stats.get("daemon_restarts", 0) >= 1,
               "recovery counters did not reach the stats snapshot")
        print(f"[{leg}] ok: byte-identical, counters {c}")
    finally:
        _shutdown(sock)


def _watchdog_postmortem_leg(d: str, data_dir: str, baseline: bytes) -> None:
    from ballista_tpu.device_daemon import client as dclient
    from ballista_tpu.device_daemon import protocol as dproto

    leg = "watchdog-postmortem"
    sock = os.path.join(d, "postmortem.sock")
    from ballista_tpu.config import TPU_DAEMON_EXECUTE_TIMEOUT_S

    dclient.reset_failure_counters()
    proc = dclient.spawn_daemon(sock, parent_pid=os.getpid())
    try:
        dclient.DaemonClient(sock).wait_ready(timeout_s=120)
        # spawn OFF: the corpse stays a corpse, so its crash report does
        # too — and the query must finish in-process anyway
        blob, stats = _run(
            data_dir, _sql("q1"),
            _chaos_cfg(sock, "daemon_hang", "mid_execute", once=True,
                       spawn=False,
                       **{TPU_DAEMON_EXECUTE_TIMEOUT_S: HANG_TIMEOUT_S}))
        _check(leg, blob == baseline, "result bytes diverged from baseline")
        _check(leg, proc.wait(timeout=30) == 4,
               f"daemon exit code {proc.returncode}, expected 4")
        report = dclient.read_crash_report(sock)
        _check(leg, report is not None, "no <socket>.crash.json post-mortem")
        _check(leg, report.get("kind") == "watchdog",
               f"post-mortem kind {report.get('kind')!r}")
        tag = str(report.get("request", {}).get("tag", ""))
        _check(leg, bool(tag), "post-mortem names no offending request tag")
        _check(leg, bool(report.get("stacks")), "post-mortem has no stacks")
        c = dclient.failure_counters()
        _check(leg, c["watchdog_kills"] >= 1, f"no watchdog kill counted ({c})")
        _check(leg, c["daemon_restarts"] == 0,
               f"spawn=off leg must not respawn ({c})")
        print(f"[{leg}] ok: exit 4, post-mortem names {tag!r}, "
              f"{len(report['stacks'])}B of stacks, counters {c}")
    finally:
        _shutdown(sock)
        if proc.poll() is None:
            proc.kill()


def _poison_leg(d: str, data_dir: str, baseline: bytes) -> None:
    from ballista_tpu.device_daemon import client as dclient
    from ballista_tpu.device_daemon import protocol as dproto

    leg = "poison"
    sock = os.path.join(d, "poison.sock")
    dclient.reset_failure_counters()
    try:
        # no once-marker: every incarnation dies until the quarantine bites
        blob, stats = _run(data_dir, _sql("q1"),
                           _chaos_cfg(sock, "daemon_crash", "mid_execute",
                                      once=False))
        c = dclient.failure_counters()
        _check(leg, blob == baseline, "result bytes diverged from baseline")
        _check(leg, c["daemon_crashes_detected"] >= 2,
               f"quarantine needs two crashes ({c})")
        _check(leg, c["poisoned_stages"] >= 1, f"nothing quarantined ({c})")
        _check(leg, stats.get("daemon_failover") == "poisoned",
               f"failover outcome {stats.get('daemon_failover')!r}")
        entries = {}
        try:
            entries = json.load(
                open(dproto.poison_path(sock))).get("entries", {})
        except (OSError, ValueError):
            pass
        _check(leg, bool(entries), "no on-disk quarantine entries")
        # the crash-loop check: a rerun demotes from quarantine WITHOUT
        # touching a daemon — no new crashes, no respawn storm
        crashes_before = c["daemon_crashes_detected"]
        blob2, stats2 = _run(data_dir, _sql("q1"),
                             _chaos_cfg(sock, "daemon_crash", "mid_execute",
                                        once=False))
        c2 = dclient.failure_counters()
        _check(leg, blob2 == baseline, "quarantined rerun diverged")
        _check(leg, stats2.get("daemon_mode") == "in_process",
               f"quarantined rerun mode {stats2.get('daemon_mode')!r}")
        _check(leg, c2["daemon_crashes_detected"] == crashes_before,
               f"quarantined rerun crashed daemons again ({c2})")
        print(f"[{leg}] ok: quarantined {list(entries)}, demoted "
              f"byte-identically, crash loop broken")
    finally:
        _shutdown(sock)
        from ballista_tpu.device_daemon import client as dclient2

        dclient2.clear_poison(sock)


def main(quick: bool = False) -> None:
    from ballista_tpu.testing.tpchgen import generate_tpch

    with tempfile.TemporaryDirectory(prefix="daemon-chaos-") as d:
        data_dir = os.path.join(d, "tpch")
        print(f"generating TPC-H sf0.01 under {data_dir} ...")
        generate_tpch(data_dir, scale=0.01, seed=42, files_per_table=2)

        baselines = {}
        queries = ["q1"] if quick else ["q1", "q3"]
        for q in queries:
            print(f"[baseline] {q} in-process ...")
            baselines[q], _ = _run(data_dir, _sql(q))

        crash_arms = [("mid_execute",)] if quick else [(a,) for a in ARM_POINTS]
        for q in queries:
            for (arm,) in crash_arms:
                _crash_leg(d, data_dir, q, baselines[q], "daemon_crash", arm)
        hang_arms = ["mid_execute"] if quick else list(ARM_POINTS)
        for arm in hang_arms:
            _crash_leg(d, data_dir, "q1", baselines["q1"], "daemon_hang", arm)
        _watchdog_postmortem_leg(d, data_dir, baselines["q1"])
        _poison_leg(d, data_dir, baselines["q1"])

    mode = "quick" if quick else "full"
    print(f"daemon chaos exercise passed ({mode}): every injected daemon "
          "death cost one retry, never the query, never a crash loop")


if __name__ == "__main__":
    main(quick="--quick" in sys.argv[1:])
