"""Exercise the high-QPS serving tier end-to-end on a tiny TPC-H dataset.

    JAX_PLATFORMS=cpu python dev/qps_exercise.py

Two identical workloads — N concurrent sessions each firing repeated
short parameterized queries at a 2-executor StandaloneCluster — run
twice: once with the serving tier enabled (plan cache + result cache +
fast lane) and once fully disabled (the legacy queued path). The run
reports sustained QPS and p50/p99 latency for both, then enforces:

1. correctness — every query's result bytes are identical across modes
   and across repeats (zero wrong results);
2. caches engaged — nonzero plan-cache hits and fast-lane executions in
   serving mode, nothing cached in legacy mode;
3. speedup — serving-mode sustained QPS >= 2x legacy and a lower p50;
   warm serving p99 must beat the uncached legacy p50.

A third leg exercises SCHEDULER scale-out instead of the serving tier:
the same executor fleet behind N=1 vs N=4 scheduler event-loop shards
(serving disabled, checkpointing FileJobState, multi-stage aggregation
queries), enforcing that N=4 sustains strictly more QPS than N=1 with
byte-identical results — the sharded loops overlap the GIL-releasing
checkpoint fsyncs a single loop serializes. A direct-dispatch probe then
runs the prepared-statement hot path through an executor lease
(`client/direct.py`), checks byte parity against the scheduler path, and
reports `direct_dispatch_rate`.

A fourth leg exercises incremental maintenance (docs/streaming.md): an
exact-accumulator aggregate is prepared and bootstrapped, rows are
appended between refreshes, and each maintained refresh (delta query
merged into cached state) must be byte-identical to — and in aggregate
faster than — a from-scratch execution in a caches-off session.

Exits non-zero if any check fails. `run_qps_comparison`,
`run_shard_comparison`, and `run_refresh_comparison` are importable.
"""

import os
import statistics
import sys
import tempfile
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

# one query SHAPE, many literals: every distinct literal is a fresh SQL
# text, so legacy mode re-parses and re-plans each one while serving mode
# binds into one cached template
QUERY = ("SELECT l_orderkey, l_partkey, l_quantity FROM lineitem "
         "WHERE l_quantity < {k}")
PARAMS = (2, 3, 4, 5)

SESSIONS = int(os.environ.get("QPS_SESSIONS", "4"))
REPEATS = int(os.environ.get("QPS_REPEATS", "6"))  # per param, per session


def _pct(sorted_vals, p):
    if not sorted_vals:
        return 0.0
    i = min(len(sorted_vals) - 1, int(round(p / 100.0 * (len(sorted_vals) - 1))))
    return sorted_vals[i]


def _fingerprint(tbl) -> bytes:
    """Order-independent byte fingerprint of a result table."""
    import hashlib

    cols = sorted(tbl.column_names)
    rows = sorted(zip(*(tbl.column(c).to_pylist() for c in cols)))
    return hashlib.sha256(repr((cols, rows)).encode()).digest()


def qps_leg(data_dir: str, serving: bool) -> dict:
    """Run the workload against one cluster; returns latencies, QPS, the
    per-param result fingerprints, and the serving-tier snapshot."""
    from ballista_tpu.client.context import SessionContext, fetch_job_results
    from ballista_tpu.config import (
        DEFAULT_SHUFFLE_PARTITIONS,
        SERVING_FAST_LANE,
        SERVING_PLAN_CACHE,
        SERVING_RESULT_CACHE,
        BallistaConfig,
    )
    from ballista_tpu.executor.standalone import StandaloneCluster
    from ballista_tpu.testing.tpchgen import register_tpch

    cfg = BallistaConfig({
        DEFAULT_SHUFFLE_PARTITIONS: 2,
        SERVING_PLAN_CACHE: serving,
        SERVING_FAST_LANE: serving,
        SERVING_RESULT_CACHE: serving,
    })
    ctx = SessionContext(cfg)
    register_tpch(ctx, data_dir)
    cluster = StandaloneCluster(num_executors=2, vcores=4, config=cfg)
    scheduler = cluster.scheduler
    mode = "serving" if serving else "legacy"
    latencies: list[float] = []
    warm_latencies: list[float] = []  # repeats after each shape's first run
    fingerprints: dict[int, set] = {k: set() for k in PARAMS}
    errors: list[str] = []
    lock = threading.Lock()

    def session_worker(n: int) -> None:
        session_id = scheduler.sessions.create_or_update(
            cfg.to_key_value_pairs(), f"qps-{mode}-{n}")
        try:
            for rep in range(REPEATS):
                for k in PARAMS:
                    t0 = time.monotonic()
                    # inline_results: in-process caller, the contract the
                    # result cache requires (tables can't ride the proto)
                    job_id = scheduler.submit_sql(QUERY.format(k=k), session_id,
                                                  inline_results=True)
                    status = scheduler.wait_for_job(job_id, timeout=120)
                    if status["state"] != "successful":
                        raise RuntimeError(
                            f"job {job_id} {status['state']}: {status.get('error')}")
                    tbl = fetch_job_results(status, cfg)
                    dt = time.monotonic() - t0
                    with lock:
                        latencies.append(dt)
                        if rep > 0:
                            warm_latencies.append(dt)
                        fingerprints[k].add(_fingerprint(tbl))
        except Exception as e:  # noqa: BLE001 — collected and reported
            with lock:
                errors.append(f"session {n}: {e}")

    try:
        # warm the cluster once so neither mode pays executor cold-start
        # inside the timed window
        warm_sid = scheduler.sessions.create_or_update(
            cfg.to_key_value_pairs(), f"qps-{mode}-warmup")
        wj = scheduler.submit_sql(QUERY.format(k=PARAMS[0]), warm_sid)
        if scheduler.wait_for_job(wj, timeout=120)["state"] != "successful":
            raise SystemExit(f"[{mode}] warmup query failed")

        threads = [threading.Thread(target=session_worker, args=(i,))
                   for i in range(SESSIONS)]
        t_start = time.monotonic()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.monotonic() - t_start
    finally:
        cluster.shutdown()

    if errors:
        raise SystemExit(f"[{mode}] worker failures: {errors[:3]}")
    lat = sorted(latencies)
    warm = sorted(warm_latencies)
    return {
        "mode": mode,
        "queries": len(latencies),
        "wall_s": round(wall, 3),
        "qps": round(len(latencies) / wall, 2),
        "p50_ms": round(_pct(lat, 50) * 1000, 1),
        "p99_ms": round(_pct(lat, 99) * 1000, 1),
        "warm_p50_ms": round(_pct(warm, 50) * 1000, 1),
        "warm_p99_ms": round(_pct(warm, 99) * 1000, 1),
        "mean_ms": round(statistics.mean(lat) * 1000, 1),
        "fingerprints": fingerprints,
        "serving": scheduler.serving.snapshot(),
    }


def run_qps_comparison(data_dir: str) -> dict:
    """Serving vs legacy on the same data; asserts the acceptance bars and
    returns both legs' stats (without the raw fingerprints)."""
    legacy = qps_leg(data_dir, serving=False)
    served = qps_leg(data_dir, serving=True)

    # 1. zero wrong results: one fingerprint per param, identical across modes
    for k in PARAMS:
        fps = served["fingerprints"][k] | legacy["fingerprints"][k]
        if len(served["fingerprints"][k]) != 1 or len(fps) != 1:
            raise SystemExit(
                f"[qps] param {k}: results diverged across repeats/modes "
                f"(serving={len(served['fingerprints'][k])} distinct, "
                f"combined={len(fps)})")

    # 2. the caches actually engaged
    snap = served["serving"]
    if snap["plan_cache"]["hits"] == 0:
        raise SystemExit("[qps] serving mode recorded zero plan-cache hits — vacuous")
    if snap["fast_lane"]["executed"] == 0:
        raise SystemExit("[qps] fast lane never engaged on a single-stage query")
    if snap["result_cache"]["hits"] == 0:
        raise SystemExit("[qps] result cache recorded zero hits on repeats")
    lsnap = legacy["serving"]
    if lsnap["plan_cache"]["hits"] or lsnap["plan_cache"]["misses"]:
        raise SystemExit("[qps] disabled serving tier still touched the plan cache")

    # 3. the speedup bars
    if served["qps"] < 2.0 * legacy["qps"]:
        raise SystemExit(f"[qps] serving {served['qps']} QPS < 2x legacy "
                         f"{legacy['qps']} QPS")
    if served["p50_ms"] >= legacy["p50_ms"]:
        raise SystemExit(f"[qps] serving p50 {served['p50_ms']}ms not below "
                         f"legacy p50 {legacy['p50_ms']}ms")
    if served["warm_p99_ms"] >= legacy["p50_ms"]:
        raise SystemExit(f"[qps] warm serving p99 {served['warm_p99_ms']}ms not "
                         f"below uncached legacy p50 {legacy['p50_ms']}ms")

    out = {}
    for leg in (legacy, served):
        leg = dict(leg)
        leg.pop("fingerprints")
        out[leg["mode"]] = leg
    out["speedup_qps"] = round(served["qps"] / max(legacy["qps"], 1e-9), 2)
    out["speedup_p50"] = round(legacy["p50_ms"] / max(served["p50_ms"], 1e-9), 2)
    return out


# multi-stage shape for the shard leg: the GROUP BY forces a shuffle
# (partial agg stage -> final agg stage), so every job crosses the event
# loop several times and checkpoints at each stage transition
SHARD_QUERY = ("SELECT l_returnflag, COUNT(*) AS c, SUM(l_quantity) AS q "
               "FROM lineitem WHERE l_quantity < {k} GROUP BY l_returnflag")
SHARD_SESSIONS = int(os.environ.get("QPS_SHARD_SESSIONS", "24"))
SHARD_REPEATS = int(os.environ.get("QPS_SHARD_REPEATS", "4"))
# modeled commit RTT of the shared job-state store (see RemoteStoreJobState)
SHARD_COMMIT_MS = float(os.environ.get("QPS_SHARD_COMMIT_MS", "15"))


def _remote_store_job_state(state_dir: str, commit_latency_s: float):
    """FileJobState plus a modeled commit round trip.

    A multi-scheduler deployment checkpoints through a SHARED remote
    store (etcd/sled behind the reference's JobState trait); every
    `save_graph` pays that store's commit RTT — milliseconds of wall
    time during which the committing event loop holds no CPU. Standalone
    mode's local-file store understates this to microseconds, which
    would let a single loop checkpoint hundreds of jobs a second and
    hide exactly the serialization scheduler sharding removes. The
    sleep (GIL released, like the real socket wait) restores the
    deployment-shaped cost; everything else is the real FileJobState."""
    from ballista_tpu.scheduler.state.job_state import FileJobState

    class RemoteStoreJobState(FileJobState):
        def save_graph(self, graph) -> None:
            time.sleep(commit_latency_s)
            super().save_graph(graph)

    return RemoteStoreJobState(state_dir, fsync=True)


def shard_leg(data_dir: str, shards: int) -> dict:
    """One shard-count leg: concurrent sessions firing multi-stage jobs at
    a StandaloneCluster whose scheduler runs `shards` event loops over a
    checkpointing job-state store with a realistic commit RTT — the
    serialized wait the sharded loops overlap. The plan cache stays ON
    (planning happens once, off the event loop) and the result cache OFF
    (every job really executes), so the leg measures the scheduling path,
    not parse/optimize throughput."""
    from ballista_tpu.client.context import SessionContext, fetch_job_results
    from ballista_tpu.config import (
        DEFAULT_SHUFFLE_PARTITIONS,
        SERVING_FAST_LANE,
        SERVING_PLAN_CACHE,
        SERVING_RESULT_CACHE,
        BallistaConfig,
    )
    from ballista_tpu.executor.standalone import StandaloneCluster
    from ballista_tpu.testing.tpchgen import register_tpch

    cfg = BallistaConfig({
        DEFAULT_SHUFFLE_PARTITIONS: 2,
        SERVING_PLAN_CACHE: True,
        # fast lane can't take a 2-stage plan, but keep it off so a future
        # planner improvement doesn't silently reroute the leg off the loop
        SERVING_FAST_LANE: False,
        SERVING_RESULT_CACHE: False,
    })
    ctx = SessionContext(cfg)
    register_tpch(ctx, data_dir)
    state_dir = tempfile.mkdtemp(prefix=f"qps-shard{shards}-state-")
    cluster = StandaloneCluster(
        num_executors=2, vcores=8, config=cfg, shards=shards,
        job_state=_remote_store_job_state(state_dir, SHARD_COMMIT_MS / 1000.0))
    scheduler = cluster.scheduler
    latencies: list[float] = []
    fingerprints: dict[int, set] = {k: set() for k in PARAMS}
    errors: list[str] = []
    lock = threading.Lock()

    def session_worker(n: int) -> None:
        session_id = scheduler.sessions.create_or_update(
            cfg.to_key_value_pairs(), f"shard{shards}-{n}")
        try:
            for _rep in range(SHARD_REPEATS):
                for k in PARAMS:
                    t0 = time.monotonic()
                    job_id = scheduler.submit_sql(
                        SHARD_QUERY.format(k=k), session_id, inline_results=True)
                    status = scheduler.wait_for_job(job_id, timeout=120)
                    if status["state"] != "successful":
                        raise RuntimeError(
                            f"job {job_id} {status['state']}: {status.get('error')}")
                    tbl = fetch_job_results(status, cfg)
                    dt = time.monotonic() - t0
                    with lock:
                        latencies.append(dt)
                        fingerprints[k].add(_fingerprint(tbl))
        except Exception as e:  # noqa: BLE001 — collected and reported
            with lock:
                errors.append(f"session {n}: {e}")

    try:
        warm_sid = scheduler.sessions.create_or_update(
            cfg.to_key_value_pairs(), f"shard{shards}-warmup")
        wj = scheduler.submit_sql(SHARD_QUERY.format(k=PARAMS[0]), warm_sid)
        if scheduler.wait_for_job(wj, timeout=120)["state"] != "successful":
            raise SystemExit(f"[shards={shards}] warmup query failed")

        threads = [threading.Thread(target=session_worker, args=(i,))
                   for i in range(SHARD_SESSIONS)]
        t_start = time.monotonic()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.monotonic() - t_start
        shard_snap = scheduler.shards_snapshot()
    finally:
        cluster.shutdown()

    if errors:
        raise SystemExit(f"[shards={shards}] worker failures: {errors[:3]}")
    lat = sorted(latencies)
    return {
        "shards": shards,
        "queries": len(latencies),
        "wall_s": round(wall, 3),
        "qps": round(len(latencies) / wall, 2),
        "p50_ms": round(_pct(lat, 50) * 1000, 1),
        "p99_ms": round(_pct(lat, 99) * 1000, 1),
        "fingerprints": fingerprints,
        "shard_snapshot": shard_snap,
    }


def direct_probe(data_dir: str) -> dict:
    """Prepared-statement direct dispatch vs the scheduler path on one
    cluster: byte parity per param, plus the achieved direct rate."""
    from ballista_tpu.client.context import SessionContext, fetch_job_results
    from ballista_tpu.client.direct import DirectDispatcher, LocalLeaseTransport
    from ballista_tpu.config import (
        DEFAULT_SHUFFLE_PARTITIONS,
        BallistaConfig,
    )
    from ballista_tpu.executor.standalone import StandaloneCluster
    from ballista_tpu.testing.tpchgen import register_tpch

    cfg = BallistaConfig({DEFAULT_SHUFFLE_PARTITIONS: 2})
    ctx = SessionContext(cfg)
    register_tpch(ctx, data_dir)
    cluster = StandaloneCluster(num_executors=2, vcores=4, config=cfg)
    scheduler = cluster.scheduler
    try:
        session_id = scheduler.sessions.create_or_update(
            cfg.to_key_value_pairs(), "direct-probe")
        d = DirectDispatcher(scheduler, LocalLeaseTransport(cluster.executors),
                             session_id)
        # prepare takes concrete SQL; literal lifting parameterizes it
        d.prepare(QUERY.format(k=PARAMS[0]))
        for rep in range(3):
            for k in PARAMS:
                st_direct = d.execute((k,))
                direct_fp = _fingerprint(fetch_job_results(st_direct, cfg))
                jid = scheduler.execute_prepared(
                    d.statement_id, (k,), session_id=session_id)
                st_sched = scheduler.wait_for_job(jid, timeout=120)
                if st_sched["state"] != "successful":
                    raise SystemExit(f"[direct] scheduler path failed: {st_sched}")
                sched_fp = _fingerprint(fetch_job_results(st_sched, cfg))
                if direct_fp != sched_fp:
                    raise SystemExit(
                        f"[direct] param {k} rep {rep}: direct-dispatch bytes "
                        f"diverge from the scheduler path")
        rate = d.direct_dispatch_rate()
        if rate <= 0.0:
            raise SystemExit("[direct] every dispatch demoted — the lease "
                             "path never actually ran")
        return {"direct_dispatch_rate": round(rate, 3), "stats": dict(d.stats),
                "leases": scheduler.leases.snapshot()}
    finally:
        cluster.shutdown()


def run_shard_comparison(data_dir: str) -> dict:
    """N=1 vs N=4 scheduler shards over the same fleet, plus the
    direct-dispatch parity probe; asserts the scale-out acceptance bars.

    The shard legs run on their own TINY dataset (sf0.001): the leg
    measures control-plane throughput, and scan-heavy tasks on one core
    would put the ceiling at the data plane for both shard counts."""
    from ballista_tpu.testing.tpchgen import generate_tpch

    with tempfile.TemporaryDirectory(prefix="qps-shard-data-") as tiny:
        generate_tpch(tiny, scale=0.001, seed=42, files_per_table=1)
        n1 = shard_leg(tiny, shards=1)
        n4 = shard_leg(tiny, shards=4)

    # byte-identical results across shard counts and repeats
    for k in PARAMS:
        fps = n1["fingerprints"][k] | n4["fingerprints"][k]
        if len(fps) != 1:
            raise SystemExit(
                f"[shards] param {k}: results diverged across shard counts "
                f"({len(n4['fingerprints'][k])} distinct at N=4, "
                f"{len(fps)} combined)")

    # the loops actually sharded: every shard saw events
    snap = n4["shard_snapshot"]
    if len(snap) != 4 or any(s["handled"] == 0 for s in snap):
        raise SystemExit(f"[shards] N=4 leg left idle shards: {snap}")

    # scale-out bar: more event loops -> strictly more sustained QPS
    if n4["qps"] <= n1["qps"]:
        raise SystemExit(f"[shards] N=4 {n4['qps']} QPS not above N=1 "
                         f"{n1['qps']} QPS")

    direct = direct_probe(data_dir)
    out = {}
    for leg in (n1, n4):
        leg = dict(leg)
        leg.pop("fingerprints")
        out[f"shards_{leg['shards']}"] = leg
    out["scheduler_shards"] = 4
    out["shard_speedup_qps"] = round(n4["qps"] / max(n1["qps"], 1e-9), 2)
    out["direct_dispatch_rate"] = direct["direct_dispatch_rate"]
    out["direct"] = direct
    return out


# incremental-refresh leg: a q1-shaped grouped aggregate whose accumulators
# are all exact (COUNT, int64 SUM, MIN/MAX — the generator's monetary
# columns are float64, and float SUMs are ineligible by design), so the
# serving tier maintains the cached result from retained deltas instead of
# recomputing. The leg appends rows between refreshes and enforces that the
# maintained refresh is BOTH faster than a from-scratch execution and
# byte-identical to it (docs/streaming.md).
REFRESH_QUERY = (
    "SELECT l_returnflag, l_linestatus, COUNT(*) AS cnt, "
    "SUM(l_orderkey) AS sum_ok, MIN(l_quantity) AS min_qty, "
    "MAX(l_quantity) AS max_qty FROM lineitem WHERE l_quantity < 45 "
    "GROUP BY l_returnflag, l_linestatus ORDER BY l_returnflag, l_linestatus")
REFRESH_ROUNDS = int(os.environ.get("QPS_REFRESH_ROUNDS", "5"))
REFRESH_APPEND_ROWS = int(os.environ.get("QPS_REFRESH_APPEND_ROWS", "512"))


def run_refresh_comparison(data_dir: str) -> dict:
    """Append-then-refresh on one cluster: the maintained path (delta query
    merged into cached aggregation state) vs a from-scratch execution of
    the same statement in a caches-off session. Asserts byte identity per
    round, that the maintenance counters actually moved, and that the
    maintained refresh is faster in aggregate."""
    import glob

    import pyarrow.parquet as pq

    from ballista_tpu.client.context import SessionContext, fetch_job_results
    from ballista_tpu.config import (
        DEFAULT_SHUFFLE_PARTITIONS,
        SERVING_FAST_LANE,
        SERVING_PLAN_CACHE,
        SERVING_RESULT_CACHE,
        BallistaConfig,
    )
    from ballista_tpu.testing.tpchgen import register_tpch

    cfg = BallistaConfig({
        DEFAULT_SHUFFLE_PARTITIONS: 2,
        SERVING_PLAN_CACHE: True,
        SERVING_FAST_LANE: False,
        # the result cache (and with it the maintenance ladder) is opt-in
        SERVING_RESULT_CACHE: True,
    })
    ctx = SessionContext.standalone(config=cfg, num_executors=2, vcores=4)
    register_tpch(ctx, data_dir)

    # the appended rows: real lineitem rows re-sent, so every round changes
    # the aggregate and both paths must agree on the new answer
    src = sorted(glob.glob(os.path.join(data_dir, "lineitem", "*.parquet")))[0]
    pool = pq.read_table(src).slice(0, REFRESH_ROUNDS * REFRESH_APPEND_ROWS)
    if pool.num_rows < REFRESH_ROUNDS * REFRESH_APPEND_ROWS:
        raise SystemExit(f"[refresh] delta pool too small: {pool.num_rows} rows")

    maintained_s: list[float] = []
    full_s: list[float] = []
    try:
        stmt = ctx.prepare(REFRESH_QUERY)
        scheduler = ctx._cluster.scheduler

        # from-scratch leg: same scheduler, a session with the result cache
        # off, so every submit re-executes the full plan (appended rows are
        # still visible — the dispatch-time scan graft serves them). Copy
        # the context's config: table registrations ride the session config
        # as ballista.catalog.table.* pairs.
        full_cfg = ctx.config.copy()
        full_cfg.set(SERVING_RESULT_CACHE, "false")
        full_sid = scheduler.sessions.create_or_update(
            full_cfg.to_key_value_pairs(), "refresh-full")

        def full_exec():
            jid = scheduler.submit_sql(REFRESH_QUERY, full_sid,
                                       inline_results=True)
            status = scheduler.wait_for_job(jid, timeout=120)
            if status["state"] != "successful":
                raise SystemExit(f"[refresh] from-scratch execution failed: "
                                 f"{status.get('error')}")
            return fetch_job_results(status, full_cfg)

        # warm both paths outside the timed window: the first prepared
        # execution bootstraps the accumulator state, the first full run
        # pays executor compile
        t0 = time.monotonic()
        boot = stmt.execute()
        bootstrap_ms = round((time.monotonic() - t0) * 1000, 1)
        if _fingerprint(boot) != _fingerprint(full_exec()):
            raise SystemExit("[refresh] bootstrap bytes diverge from scratch")

        for r in range(REFRESH_ROUNDS):
            delta = pool.slice(r * REFRESH_APPEND_ROWS, REFRESH_APPEND_ROWS)
            ctx.append("lineitem", delta)
            t0 = time.monotonic()
            got = stmt.execute()
            maintained_s.append(time.monotonic() - t0)
            t0 = time.monotonic()
            full = full_exec()
            full_s.append(time.monotonic() - t0)
            if _fingerprint(got) != _fingerprint(full):
                raise SystemExit(f"[refresh] round {r}: maintained bytes "
                                 f"diverge from a from-scratch execution")

        snap = scheduler.serving.snapshot()["incremental"]
    finally:
        ctx.shutdown()

    # the cheap path actually ran: every refresh maintained, none recomputed
    if snap["maintained"] < REFRESH_ROUNDS:
        raise SystemExit(f"[refresh] only {snap['maintained']} of "
                         f"{REFRESH_ROUNDS} refreshes maintained: {snap}")
    if snap["bootstraps"] < 1 or snap["appends"] < REFRESH_ROUNDS:
        raise SystemExit(f"[refresh] counters implausible: {snap}")
    modes = {m["mode"] for m in snap["modes"].values()}
    if "aggregate" not in modes:
        raise SystemExit(f"[refresh] no template analyzed as aggregate: {snap}")

    m_total, f_total = sum(maintained_s), sum(full_s)
    if m_total >= f_total:
        raise SystemExit(f"[refresh] maintained refresh {m_total:.3f}s not "
                         f"faster than from-scratch {f_total:.3f}s")
    m_sorted, f_sorted = sorted(maintained_s), sorted(full_s)
    return {
        "rounds": REFRESH_ROUNDS,
        "append_rows": REFRESH_APPEND_ROWS,
        "bootstrap_ms": bootstrap_ms,
        "maintained_total_s": round(m_total, 3),
        "full_total_s": round(f_total, 3),
        "speedup": round(f_total / max(m_total, 1e-9), 2),
        "maintained_p50_ms": round(_pct(m_sorted, 50) * 1000, 1),
        "full_p50_ms": round(_pct(f_sorted, 50) * 1000, 1),
        "incremental": {k: snap[k] for k in
                        ("maintained", "bootstraps", "state_renders",
                         "recomputes", "appends", "appended_rows")},
    }


def main() -> None:
    from ballista_tpu.testing.tpchgen import generate_tpch

    with tempfile.TemporaryDirectory(prefix="qps-tpch-") as d:
        print(f"generating TPC-H sf0.01 under {d} ...")
        generate_tpch(d, scale=0.01, seed=42, files_per_table=2)
        stats = run_qps_comparison(d)
        for mode in ("legacy", "serving"):
            s = stats[mode]
            print(f"[{mode:8s}] {s['queries']} queries in {s['wall_s']}s "
                  f"-> {s['qps']} QPS  p50={s['p50_ms']}ms p99={s['p99_ms']}ms "
                  f"(warm p50={s['warm_p50_ms']}ms p99={s['warm_p99_ms']}ms)")
        srv = stats["serving"]["serving"]
        print(f"[caches  ] plan hits={srv['plan_cache']['hits']} "
              f"misses={srv['plan_cache']['misses']} "
              f"text_hits={srv['plan_cache']['text_hits']} "
              f"fast_lane={srv['fast_lane']}")
        print(f"qps exercise passed: {stats['speedup_qps']}x QPS, "
              f"{stats['speedup_p50']}x p50")

        shard_stats = run_shard_comparison(d)
        for key in ("shards_1", "shards_4"):
            s = shard_stats[key]
            print(f"[shards={s['shards']}] {s['queries']} queries in "
                  f"{s['wall_s']}s -> {s['qps']} QPS  "
                  f"p50={s['p50_ms']}ms p99={s['p99_ms']}ms")
        print(f"[direct  ] rate={shard_stats['direct_dispatch_rate']} "
              f"stats={shard_stats['direct']['stats']}")
        print(f"shard exercise passed: {shard_stats['shard_speedup_qps']}x QPS "
              f"at N=4, direct dispatch byte-identical")

        refresh = run_refresh_comparison(d)
        print(f"[refresh ] {refresh['rounds']} appends x "
              f"{refresh['append_rows']} rows: maintained "
              f"{refresh['maintained_total_s']}s vs from-scratch "
              f"{refresh['full_total_s']}s "
              f"(p50 {refresh['maintained_p50_ms']}ms vs "
              f"{refresh['full_p50_ms']}ms)  counters={refresh['incremental']}")
        print(f"refresh exercise passed: {refresh['speedup']}x, "
              f"maintained results byte-identical")


if __name__ == "__main__":
    main()
