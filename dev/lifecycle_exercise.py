"""Exercise the executor lifecycle & storage failure domain end-to-end.

    JAX_PLATFORMS=cpu python dev/lifecycle_exercise.py [--quick]

Drains executors out from under live TPC-H queries and checks the rules
of the lifecycle failure domain (docs/lifecycle.md): a graceful drain
hands shuffle outputs to survivors with ZERO upstream-stage reruns; a
hard kill mid-drain falls back to recompute; injected ENOSPC fails
tasks typed + retryable, never the job.

Legs (full mode; --quick drops the drain_kill leg for the bench probe):

1. drain          — mid-flight drain of a 2-executor per-work-dir fleet
   under q3: the victim's committed map outputs migrate to the survivor
   over the real migrate_pull Flight path, every stage stays at
   attempt 0, and the result matches the pandas reference oracle.
2. drain_kill     — BALLISTA_CHAOS_DRAIN_KILL_AFTER=1 aborts the
   migration after one committed location: the scheduler must fall
   back to the executor-lost recompute path and the job must still
   produce correct results (status "drain-killed" in the ledger).
3. disk_full      — chaos mode=disk_full at p=1.0/once-mode: every
   task's first shuffle write ENOSPCs with a typed retryable
   DiskExhausted, every retry heals, and the query converges — no job
   failure, no quarantine of the only executor.
4. rolling_restart — drain each of a 3-executor fleet's original nodes
   one at a time (adding a replacement after each) while q6 runs in a
   loop: every query must keep succeeding with oracle-correct results
   and the handoffs must migrate real partitions.

Exits non-zero on any divergence.
"""

import os
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _sql(name: str) -> str:
    with open(os.path.join(ROOT, "benchmarks", "tpch", "queries",
                           f"{name}.sql")) as f:
        return f.read()


def _check(leg: str, cond: bool, msg: str) -> None:
    if not cond:
        raise SystemExit(f"[{leg}] FAILED: {msg}")


def _slow_engine():
    """Stretches every task by a few ms so a drain reliably lands while
    the job is mid-flight (upstream outputs committed, consumers pending)."""
    from ballista_tpu.executor.executor import ExecutionEngine

    class SlowEngine(ExecutionEngine):
        def create_query_stage_exec(self, plan, config, stage_attempt=0):
            time.sleep(0.05)
            return super().create_query_stage_exec(plan, config, stage_attempt)

    return SlowEngine


def _drain_cluster(data_dir, cfg, num_executors=2):
    """SessionContext over a per-executor-work-dir standalone fleet: each
    executor owns its work-dir subtree and Flight server, so drain
    migration moves real bytes between data planes."""
    from ballista_tpu.client.context import SessionContext
    from ballista_tpu.executor.standalone import StandaloneCluster
    from ballista_tpu.testing.tpchgen import register_tpch

    ctx = SessionContext.standalone(cfg, num_executors=num_executors)
    ctx._cluster = StandaloneCluster(
        num_executors, 4, config=cfg, per_executor_work_dirs=True,
        engine_factory=_slow_engine())
    register_tpch(ctx, data_dir)
    return ctx


def _drain_midflight(ctx, cfg, sql):
    """Submit sql, wait until some executor holds committed map outputs
    while the job is still running, then drain that executor."""
    cluster = ctx._cluster
    sched = cluster.scheduler
    sid = sched.sessions.create_or_update(cfg.to_key_value_pairs(), "s-lifecycle")
    job_id = sched.submit_sql(sql, sid)
    victim = None
    deadline = time.time() + 60
    while time.time() < deadline and victim is None:
        for eid in list(cluster.executors):
            if sched._locations_on(eid):
                victim = eid
                break
        else:
            time.sleep(0.01)
    _check("drain", victim is not None, "no committed map outputs ever appeared")
    res = sched.drain_executor(victim, timeout_s=60)
    status = sched.wait_for_job(job_id, timeout=120)
    return job_id, res, status


def _drain_leg(data_dir, ref_tables, kill: bool) -> None:
    from ballista_tpu.client.context import fetch_job_results
    from ballista_tpu.config import DEFAULT_SHUFFLE_PARTITIONS, BallistaConfig
    from ballista_tpu.testing.reference import compare_results, run_reference

    leg = "drain_kill" if kill else "drain"
    if kill:
        os.environ["BALLISTA_CHAOS_DRAIN_KILL_AFTER"] = "1"
    cfg = BallistaConfig({DEFAULT_SHUFFLE_PARTITIONS: 4})
    ctx = _drain_cluster(data_dir, cfg)
    sched = ctx._cluster.scheduler
    try:
        job_id, res, status = _drain_midflight(ctx, cfg, _sql("q3"))
        _check(leg, status["state"] == "successful",
               f"job failed: {status.get('error')}")
        want = "drain-killed" if kill else "drained"
        _check(leg, res["status"] == want, f"drain result {res}")
        if not kill:
            _check(leg, res["migrated_partitions"] > 0 and res["migrated_bytes"] > 0,
                   f"nothing migrated: {res}")
            g = sched.jobs.get(job_id)
            attempts = {sid: s.attempt for sid, s in g.stages.items()}
            _check(leg, all(a == 0 for a in attempts.values()),
                   f"stage reruns happened: {attempts}")
        out = fetch_job_results(status, cfg)
        problems = compare_results(out, run_reference(3, ref_tables), 3)
        _check(leg, not problems, "; ".join(problems))
        drained = sched.executors.drained_snapshot()
        _check(leg, drained.get(res["executor_id"], {}).get("reason") == want,
               f"ledger {drained}")
        print(f"[{leg}] ok: {res['migrated_partitions']} partitions "
              f"({res['migrated_bytes']}B) handed off, job successful, "
              "oracle-correct")
    finally:
        if kill:
            del os.environ["BALLISTA_CHAOS_DRAIN_KILL_AFTER"]
        ctx.shutdown()


def _disk_full_leg(data_dir) -> None:
    from ballista_tpu.client.context import SessionContext
    from ballista_tpu.config import (
        CHAOS_ENABLED,
        CHAOS_MODE,
        CHAOS_PROBABILITY,
        CHAOS_SEED,
        DEFAULT_SHUFFLE_PARTITIONS,
        BallistaConfig,
    )
    from ballista_tpu.executor import chaos
    from ballista_tpu.testing.tpchgen import register_tpch

    leg = "disk_full"
    chaos._DISK_FULL_FIRED.clear()
    # p=1.0 + once-mode is DETERMINISTIC: every task's first shuffle write
    # ENOSPCs and every retry heals, with the per-stage task count (2)
    # safely under the stage retry budget
    cfg = BallistaConfig({
        CHAOS_ENABLED: True, CHAOS_MODE: "disk_full",
        CHAOS_PROBABILITY: 1.0, CHAOS_SEED: 11,
        DEFAULT_SHUFFLE_PARTITIONS: 2,
    })
    ctx = SessionContext.standalone(cfg, num_executors=1, vcores=4)
    register_tpch(ctx, data_dir)
    # every task fails exactly once by design; don't let the health ledger
    # quarantine the only executor over the injected faults
    ctx._ensure_cluster().scheduler.executors.quarantine_threshold = 2.0
    try:
        out = ctx.sql(
            "select n_name, count(*) as c from nation group by n_name order by n_name"
        ).collect()
        fired = len(chaos._DISK_FULL_FIRED)
        _check(leg, fired > 0, "no ENOSPC ever injected — leg vacuous")
        _check(leg, out.num_rows == 25, f"{out.num_rows} rows, expected 25")
        _check(leg, all(c == 1 for c in out.column("c").to_pylist()),
               "wrong counts after retry")
        print(f"[{leg}] ok: {fired} injected ENOSPCs, every retry healed, "
              "job never failed")
    finally:
        ctx.shutdown()
        chaos._DISK_FULL_FIRED.clear()


def _rolling_restart_leg(data_dir, ref_tables) -> None:
    from ballista_tpu.config import DEFAULT_SHUFFLE_PARTITIONS, BallistaConfig
    from ballista_tpu.testing.reference import compare_results, run_reference

    leg = "rolling_restart"
    cfg = BallistaConfig({DEFAULT_SHUFFLE_PARTITIONS: 4})
    ctx = _drain_cluster(data_dir, cfg, num_executors=3)
    cluster = ctx._cluster
    sched = cluster.scheduler
    originals = list(cluster.executors)
    results, errors = [], []
    stop = threading.Event()

    def load():
        while not stop.is_set():
            try:
                results.append(ctx.sql(_sql("q6")).collect())
            except Exception as e:  # noqa: BLE001 — surfaced as a leg failure
                errors.append(e)
                return

    t = threading.Thread(target=load, daemon=True, name="query-load")
    t.start()
    try:
        for eid in originals:
            # drain only once this node actually holds shuffle outputs, so
            # every handoff in the rolling restart moves real data
            deadline = time.time() + 30
            while time.time() < deadline and not sched._locations_on(eid):
                time.sleep(0.01)
            res = sched.drain_executor(eid, timeout_s=60)
            _check(leg, res["status"] == "drained", f"drain result {res}")
            cluster.add_executor(vcores=4, config=cfg,
                                 engine_factory=_slow_engine())
        _check(leg, sched.lifecycle_stats["migrated_partitions"] > 0,
               "rolling restart migrated nothing")
        stop.set()
        t.join(timeout=120)
        _check(leg, not errors, f"query load failed: {errors}")
        _check(leg, bool(results), "load thread never completed a query")
        ref = run_reference(6, ref_tables)
        for out in results:
            problems = compare_results(out, ref, 6)
            _check(leg, not problems, "; ".join(problems))
        _check(leg, len(sched.executors.alive_executors()) == 3,
               "fleet size drifted")
        _check(leg, sched.lifecycle_stats["drains"] == 3, "drain count drifted")
        print(f"[{leg}] ok: 3 nodes drained+replaced under load, "
              f"{len(results)} queries all oracle-correct, "
              f"{sched.lifecycle_stats['migrated_partitions']} partitions migrated")
    finally:
        stop.set()
        ctx.shutdown()


def main(quick: bool = False) -> None:
    import tempfile

    from ballista_tpu.testing.reference import load_tables
    from ballista_tpu.testing.tpchgen import generate_tpch

    with tempfile.TemporaryDirectory(prefix="lifecycle-") as d:
        data_dir = os.path.join(d, "tpch")
        print(f"generating TPC-H sf0.01 under {data_dir} ...")
        generate_tpch(data_dir, scale=0.01, seed=42, files_per_table=2)
        ref_tables = load_tables(data_dir)

        _drain_leg(data_dir, ref_tables, kill=False)
        if not quick:
            _drain_leg(data_dir, ref_tables, kill=True)
        _disk_full_leg(data_dir)
        _rolling_restart_leg(data_dir, ref_tables)

    mode = "quick" if quick else "full"
    print(f"lifecycle exercise passed ({mode}): drains cost zero reruns, "
          "ENOSPC cost one retry, the fleet rolled without a wrong answer")


if __name__ == "__main__":
    main(quick="--quick" in sys.argv[1:])
