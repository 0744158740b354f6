#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

    python chip_smoke.py [--scale 1] [--seed 1]       one TPU chip, one process
    python chip_smoke.py --chips 4 [--scale 1]        one executor process per chip

Drives the main path once through the entry points a user calls and fails
unless what came out is right AND ran where it claims:

one chip   TPC-H at --scale is generated from --seed, registered on
           `SessionContext.standalone(num_executors=1)` with
           `ballista.executor.engine=tpu` and every other key at its default
           (client → scheduler → executor task runner → in-process TPU engine
           → Flight result), and q1, q6 (scan-aggregate), q3, q5 (join chain),
           q18 (large group domain, sort-based aggregation, ORDER BY ... LIMIT) and a window
           query over lineitem each run cold once and hot twice. Every result is
           compared with the independent pandas oracle
           (ballista_tpu/testing/reference.py; the window query with the same
           query on engine=cpu). A query fails on a mismatch, on any device
           stage that left the device for another reason than the documented
           row floor (ballista.tpu.min.rows — counted and printed apart), and
           on any non-Unsupported exception demoted to the CPU engine.
--chips 4  only this: the parent stays off jax, starts a scheduler and four
           `python -m ballista_tpu.executor --engine tpu --device-ordinal i`
           processes, runs q3 and q5 through `SessionContext.remote`, compares
           with the oracle, and checks from each executor's own answer to the
           `GetDiagnostics` rpc (asked of the scheduler: the device it holds,
           its cumulative stage ledger) that its stages ran on a TPU it alone
           holds, with no fallbacks.

Earlier lines of standard output are one JSON object each (smoke timings, not
benchmark results). The LAST line is
`{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}`;
any failed phase makes it `"ok": false` and the exit code 1. Without a TPU
(or outside the repository) it exits non-zero and prints no result at all:
there is no CPU fallback.
"""

from __future__ import annotations

import argparse
import contextlib
import gc
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
# the scale one chip holds of the SF100 / v5e-8 deployment in BASELINE.json
DEPLOYMENT_SCALE = 10.0
# ...and the scale the whole script fits its 1200 s limit at, compilation
# included (PERF.md, PR 21: 560 s at SF1 on a v5e chip, ~980 s estimated at
# SF2 — timed when each of a stage's 8 map tasks dispatched the whole-stage
# kernel; since PR 26 an executor dispatches a stage once and the script has
# not been re-timed at a larger scale). The engine limit that stays: the
# sorted path holds at most 2^22 groups, so q18's GROUP BY l_orderkey leaves
# the device from SF3 up.
DEFAULT_SCALE = 1.0
REDUCED_WHY = ("whole script must fit 1200 s with compilation (PERF.md has the "
               "timings of every query here) and the sorted path's 2^22 group "
               "capacity overflows on q18 from SF3")
LABEL = "smoke timing, not a benchmark result"

TPCH_QUERIES = (1, 6, 3, 5, 18)
FOUR_CHIP_QUERIES = (3, 5)
# a window stage the device takes: rank + running max over lineitem's last
# quarter of ship dates, partitioned by order (ties broken by the rank itself)
WINDOW_SQL = """
select l_orderkey, l_linenumber, l_extendedprice,
       rank() over (partition by l_orderkey order by l_extendedprice desc) as price_rank,
       max(l_extendedprice) over (partition by l_orderkey order by l_linenumber) as running_max
from lineitem
where l_shipdate >= date '1998-09-01'
"""
# union of the columns the oracle's q1/q3/q5/q6/q18 read: whole SF10 tables
# in pandas would not fit beside the engine on a 40 GiB host
ORACLE_COLUMNS = {
    "lineitem": ["l_orderkey", "l_suppkey", "l_quantity", "l_extendedprice",
                 "l_discount", "l_tax", "l_returnflag", "l_linestatus",
                 "l_shipdate"],
    "orders": ["o_orderkey", "o_custkey", "o_orderdate", "o_shippriority",
               "o_totalprice"],
    "customer": ["c_custkey", "c_name", "c_mktsegment", "c_nationkey"],
    "supplier": ["s_suppkey", "s_nationkey"],
    "nation": ["n_nationkey", "n_name", "n_regionkey"],
    "region": ["r_regionkey", "r_name"],
    "part": ["p_partkey"],
    "partsupp": ["ps_partkey"],
}
STAGE_KEYS = ("dispatches", "table_shape", "fused_spans", "fill_s", "encode_s",
              "upload_s", "trace_s", "xla_compile_s", "compile_overlap_s", "exec_s", "device_bytes",
              "persist_cache_hits", "persist_cache_misses", "hbm_plan",
              "probe_rows_live", "probe_rows")


def emit(obj: dict) -> None:
    print(json.dumps(obj, default=str), flush=True)


def query_sql(q: int) -> str:
    with open(os.path.join(ROOT, "benchmarks", "tpch", "queries", f"q{q}.sql")) as f:
        return f.read()


def require_tpu() -> dict:
    """The device as jax reports it; exits 2 (no result line) when the
    default backend is not a TPU. Only the one-chip path calls this: the
    --chips 4 parent must stay off jax."""
    import jax

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        print(f"chip_smoke: needs a TPU; jax's default backend is "
              f"{d.platform!r} ({len(devs)} device(s)). No CPU fallback.",
              file=sys.stderr)
        sys.exit(2)
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def data_plane() -> dict:
    """Which shuffle data plane this checkout got: the row router is built
    from native/row_router.cpp on first use (numpy twin otherwise); the
    standalone cluster serves shuffle blocks from the Python Flight server."""
    from ballista_tpu.ops import native

    return {"row_router": "native" if native.get_lib() is not None else "python"}


# ------------------------------------------------------------------ set-up

def emit_reduced(scale: float) -> None:
    if scale < DEPLOYMENT_SCALE:
        emit({"reduced": {"scale": {"deployment": DEPLOYMENT_SCALE,
                                    "run": scale, "why": REDUCED_WHY}}})


def generate(scale: float, seed: int) -> tuple[str, dict]:
    from ballista_tpu.testing.tpchgen import generate_tpch

    data_dir = os.path.join(ROOT, "data", "chip_smoke", f"sf{scale:g}_seed{seed}")
    shutil.rmtree(data_dir, ignore_errors=True)
    t0 = time.time()
    generate_tpch(data_dir, scale=scale, seed=seed, files_per_table=2)
    import pyarrow.parquet as pq

    rows = sum(pq.read_metadata(os.path.join(data_dir, "lineitem", f)).num_rows
               for f in os.listdir(os.path.join(data_dir, "lineitem")))
    return data_dir, {"phase": "datagen", "scale": scale, "seed": seed,
                      "lineitem_rows": rows,
                      "seconds": round(time.time() - t0, 1)}


def oracle_results(data_dir: str, queries) -> tuple[dict, dict]:
    """All oracle answers up front, then the pandas tables are freed: the
    oracle and the engine's working set are never resident together."""
    from ballista_tpu.testing.reference import load_tables, run_reference

    t0 = time.time()
    tables = load_tables(data_dir, columns=ORACLE_COLUMNS)
    wants = {q: run_reference(q, tables) for q in queries}
    del tables
    gc.collect()
    return wants, {"phase": "oracle", "queries": [f"q{q}" for q in queries],
                   "seconds": round(time.time() - t0, 1)}


# ---------------------------------------------------------------- one chip

def window_problems(got, want) -> list[str]:
    keys = [("l_orderkey", "ascending"), ("l_linenumber", "ascending")]
    a = got.combine_chunks().sort_by(keys)
    b = want.combine_chunks().sort_by(keys)
    if a.num_rows != b.num_rows:
        return [f"window: row count {a.num_rows} != cpu engine {b.num_rows}"]
    problems = []
    for name in b.column_names:
        ca, cb = a.column(name), b.column(name)
        if not ca.equals(cb):
            import pyarrow.compute as pc

            bad = pc.indices_nonzero(pc.fill_null(pc.not_equal(ca, cb), True))
            i = bad[0].as_py()
            problems.append(
                f"window: column {name} differs from the cpu engine in "
                f"{len(bad)} rows, first at sorted row {i}: "
                f"{ca[i].as_py()!r} != {cb[i].as_py()!r}")
    return problems


def run_cell(name: str, sql: str, ctx, check) -> tuple[dict, bool]:
    """One query cold once and hot twice; returns (its JSON line, passed)."""
    import jax

    import ballista_tpu.ops.tpu.stage_compiler as sc

    line: dict = {"query": name, "label": LABEL}
    problems: list[str] = []
    runs = []
    for i in range(3):
        sc.RUN_STATS.clear()
        before = sc.STAGE_OUTCOMES.snapshot()
        t0 = time.time()
        out = ctx.sql(sql).collect()
        seconds = time.time() - t0
        after = sc.STAGE_OUTCOMES.snapshot()
        led = {k: after[k] - before[k] for k in sc.StageOutcomes.KINDS}
        n_new = sum(led.values())
        off_device = [list(r) for r in after["recent"][-n_new:]
                      if r[1] != "device"] if n_new else []
        stages = {tag: {k: rec[k] for k in STAGE_KEYS if k in rec}
                  for tag, rec in sc.RUN_STATS.stages().items()}
        runs.append(seconds)
        which = "cold" if i == 0 else f"hot{i}"
        if led["device"] == 0:
            problems.append(f"{which}: no stage ran on the device")
        if led["error"] or led["declined"]:
            problems.append(f"{which}: stages left the device: {off_device}")
        problems.extend(f"{which}: {p}" for p in check(out))
        if i == 0:
            merged = sc.RUN_STATS.snapshot()
            line.update({
                "rows": out.num_rows, "cold_s": round(seconds, 3),
                "stages": stages,
                "sort_family": {k: merged[k] for k in (
                    "sort_invocations", "window_invocations", "sort_full_materializations")
                    if k in merged},
                "stage_outcomes": led, "off_device": off_device,
            })
        else:
            line.setdefault("hot_stage_outcomes", []).append(led)
            line.setdefault("hot_exec_s", []).append(
                {tag: rec.get("exec_s") for tag, rec in stages.items()})
    line["hot_s"] = [round(s, 3) for s in runs[1:]]
    mem = jax.devices()[0].memory_stats() or {}
    line["peak_bytes_in_use"] = mem.get("peak_bytes_in_use")
    line["bytes_in_use"] = mem.get("bytes_in_use")
    line["oracle"] = "match" if not problems else "FAILED"
    line["problems"] = problems
    return line, not problems


def one_chip(args, device: dict) -> bool:
    from ballista_tpu.client.context import SessionContext
    from ballista_tpu.config import EXECUTOR_ENGINE, BallistaConfig
    from ballista_tpu.ops.tpu import runtime
    from ballista_tpu.testing.reference import compare_results
    from ballista_tpu.testing.tpchgen import register_tpch

    ok = True
    runtime.ensure_jax()
    emit({"phase": "start", "device": device, "data_plane": data_plane(),
          "compile_cache_dir": runtime.compile_cache_dir()})
    emit_reduced(args.scale)
    data_dir, line = generate(args.scale, args.seed)
    emit(line)
    try:
        wants, line = oracle_results(data_dir, TPCH_QUERIES)
        emit(line)
        t0 = time.time()
        cpu = SessionContext(BallistaConfig({EXECUTOR_ENGINE: "cpu"}))
        register_tpch(cpu, data_dir)
        window_want = cpu.sql(WINDOW_SQL).collect()
        emit({"phase": "window_reference", "engine": "cpu",
              "rows": window_want.num_rows,
              "seconds": round(time.time() - t0, 1)})

        ctx = SessionContext.standalone(
            BallistaConfig({EXECUTOR_ENGINE: "tpu"}), num_executors=1)
        try:
            register_tpch(ctx, data_dir)
            cells = [(f"q{q}", query_sql(q),
                      lambda out, q=q: compare_results(out, wants[q], q))
                     for q in TPCH_QUERIES]
            cells.append(("window", WINDOW_SQL,
                          lambda out: window_problems(out, window_want)))
            for name, sql, check in cells:
                try:
                    line, passed = run_cell(name, sql, ctx, check)
                except Exception:  # noqa: BLE001 — a failed phase, reported
                    line, passed = {"query": name, "oracle": "FAILED",
                                    "problems": [traceback.format_exc(limit=8)]}, False
                emit(line)
                ok &= passed
        finally:
            ctx.shutdown()
        emit({"phase": "compile_cache", **runtime.compile_cache_stats()})
    finally:
        shutil.rmtree(data_dir, ignore_errors=True)
    return ok


# -------------------------------------------------------------- four chips

def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def stop_cluster(procs: dict) -> None:
    """Stop every process this script started, children included.
    Executors go first, while the scheduler still answers: SIGTERM is their
    graceful drain, a second one the hard stop that also ends the native
    Flight server each of them started. Whatever is left in a process group
    afterwards is killed."""
    execs = [p for name, p in procs.items() if name != "scheduler"]

    def wait_all(ps, seconds):
        deadline = time.time() + seconds
        for p in ps:
            with contextlib.suppress(subprocess.TimeoutExpired):
                p.wait(timeout=max(0.1, deadline - time.time()))

    for sig_round in range(2):
        for p in execs:
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        wait_all(execs, 12)
    sched = procs.get("scheduler")
    if sched is not None and sched.poll() is None:
        sched.terminate()
        wait_all([sched], 10)
    for p in procs.values():
        with contextlib.suppress(ProcessLookupError, PermissionError):
            os.killpg(p.pid, signal.SIGKILL)
        with contextlib.suppress(subprocess.TimeoutExpired):
            p.wait(timeout=5)


def holds_one_tpu(executor: dict) -> bool:
    """From an executor's own answer to `GetDiagnostics`: the device it
    claimed at start-up is a TPU and the only one its process sees."""
    return executor["platform"] == "tpu" and executor["local_device_count"] == 1


def four_chips(args) -> tuple[bool, dict]:
    """One executor process per chip behind a scheduler process; this
    parent never imports jax (a parent that has touched jax holds the
    chips its children need)."""
    n = 4
    out_dir = os.path.join(ROOT, "chiprun_out", "chips4")
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    env = dict(os.environ)
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    port = free_port()
    procs: dict[str, subprocess.Popen] = {}
    logs: list[str] = []

    def spawn(name: str, argv: list[str]) -> None:
        path = os.path.join(out_dir, f"{name}.log")
        logs.append(path)
        with open(path, "wb") as f:
            # a process group of its own: the executor's native Flight
            # server child is stopped with it, whatever happens
            procs[name] = subprocess.Popen(
                [sys.executable, "-m", *argv], cwd=ROOT, env=env,
                stdin=subprocess.DEVNULL, stdout=f, stderr=f,
                start_new_session=True)

    def placement() -> tuple[list, bool]:
        """Each executor's own answer, asked of the scheduler in one rpc:
        the device it claimed at start and its cumulative stage ledger. One
        chip each: every process holds exactly one TPU device under its own
        ordinal, and a chip cannot belong to two live processes at once."""
        per = []
        for e in client.diagnostics()["executors"]:
            d, led = e.get("devices") or {}, e.get("outcomes") or {}
            per.append({
                "ordinal": e["ordinal"], "id": e["executor_id"],
                "platform": d.get("platform"), "kind": d.get("kind"),
                "local_device_count": d.get("count"),
                "device_runs": led.get("device", 0),
                "below_row_floor": led.get("below_row_floor", 0),
                "declined": led.get("declined", 0), "errors": led.get("error", 0),
                "stages": sorted(e.get("stages") or ())})
        good = (sorted(r["ordinal"] for r in per) == list(range(n))
                and all(holds_one_tpu(r) for r in per)
                and all(p.poll() is None for p in procs.values()))
        return per, good

    def tails() -> dict:
        out = {}
        for path in logs:
            with open(path, "rb") as f:
                out[os.path.basename(path)] = f.read()[-1500:].decode(errors="replace")
        return out

    ok = True
    device = {"platform": "unknown", "kind": "unknown", "count": 0}
    data_dir = None
    t_start = time.time()
    try:
        spawn("scheduler", ["ballista_tpu.scheduler", "--bind-host", "127.0.0.1",
                            "--port", str(port), "--rest-port", "-1",
                            "--flight-proxy-port", "0", "--log-level", "WARNING"])
        for i in range(n):
            spawn(f"executor{i}", [
                "ballista_tpu.executor", "--scheduler", f"127.0.0.1:{port}",
                "--bind-host", "127.0.0.1", "--external-host", "127.0.0.1",
                "--engine", "tpu", "--device-ordinal", str(i),
                "--work-dir", os.path.join(out_dir, f"work{i}"),
                "--log-level", "INFO"])
        import grpc

        from ballista_tpu.client.remote import RemoteSchedulerClient
        from ballista_tpu.config import BallistaConfig

        client = RemoteSchedulerClient(f"127.0.0.1:{port}", BallistaConfig())
        deadline = time.time() + 240
        per: list = []
        while time.time() < deadline:
            dead = [k for k, p in procs.items() if p.poll() is not None]
            if dead:
                raise RuntimeError(f"exited during start-up: {dead}")
            with contextlib.suppress(grpc.RpcError):
                per, _ = placement()
            # registered, and each says what device it claimed at start-up
            if len(per) == n and all(r["platform"] is not None for r in per):
                break
            time.sleep(1.0)
        per, good = placement()
        emit({"phase": "cluster_up", "executors": per, "ok": good,
              "seconds": round(time.time() - t_start, 1)})
        if not good:
            # before any data is made: four processes that do not each hold
            # one TPU chip of their own are not the deployment under test
            raise RuntimeError("executors are not one per TPU chip")

        emit_reduced(args.scale)
        data_dir, line = generate(args.scale, args.seed)
        emit(line)
        wants, line = oracle_results(data_dir, FOUR_CHIP_QUERIES)
        emit(line)

        from ballista_tpu.client.context import SessionContext
        from ballista_tpu.config import EXECUTOR_ENGINE
        from ballista_tpu.testing.reference import compare_results
        from ballista_tpu.testing.tpchgen import register_tpch

        ctx = SessionContext.remote(f"127.0.0.1:{port}",
                                    BallistaConfig({EXECUTOR_ENGINE: "tpu"}))
        register_tpch(ctx, data_dir)
        for q in FOUR_CHIP_QUERIES:
            line = {"query": f"q{q}", "label": LABEL, "chips": n}
            runs = []
            problems: list[str] = []
            for i in range(2):
                t0 = time.time()
                out = ctx.sql(query_sql(q)).collect()
                runs.append(round(time.time() - t0, 3))
                problems.extend(compare_results(out, wants[q], q))
            line.update({"rows": out.num_rows, "cold_s": runs[0],
                         "hot_s": runs[1:],
                         "oracle": "match" if not problems else "FAILED",
                         "problems": problems})
            emit(line)
            ok &= not problems
        assert "jax" not in sys.modules, "the --chips 4 parent touched jax"

        # the ledgers as the executors hold them now: no heartbeat to wait for
        per, good = placement()
        placed = good and all(r["device_runs"] > 0 and r["errors"] == 0
                              and r["declined"] == 0 for r in per)
        emit({"phase": "placement", "executors": per, "ok": placed})
        ok &= placed
        if placed:
            kinds = sorted({r["kind"] for r in per})
            device = {"platform": "tpu", "kind": kinds[0] if len(kinds) == 1 else str(kinds),
                      "count": n}
    except Exception:  # noqa: BLE001 — a failed phase, reported
        emit({"phase": "four_chips", "ok": False,
              "problems": [traceback.format_exc(limit=8)], "logs": tails()})
        ok = False
    finally:
        stop_cluster(procs)
        if data_dir:
            shutil.rmtree(data_dir, ignore_errors=True)
    return ok, device


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--scale", type=float, default=DEFAULT_SCALE,
                    help="TPC-H scale factor (default %(default)s)")
    ap.add_argument("--seed", type=int, default=1, help="data seed")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = only the one-executor-per-chip phase")
    args = ap.parse_args(argv)
    sys.path.insert(0, ROOT)
    import ballista_tpu  # noqa: F401 — outside the repository: no result

    if args.chips == 4:
        ok, device = four_chips(args)
    else:
        device = require_tpu()
        try:
            ok = one_chip(args, device)
        except Exception:  # noqa: BLE001 — a failed phase, reported
            emit({"phase": "one_chip", "ok": False,
                  "problems": [traceback.format_exc(limit=12)]})
            ok = False
    print(json.dumps({"ok": bool(ok), "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
