#!/usr/bin/env python3
"""bench/cell.py — the child of bench/run.py: the process that runs the queries.

Whether it also holds the chips is the topology's business (lib/topology_*):
this file imports no jax and asks the topology for everything that is bound
to the process that does — the devices, the profiler session, the memory.

Opens the configuration's topology over the data the parent generated, runs
the cell's queries once (the first round: fill, compile or persistent-cache
load, one execution), the cell's untimed warm-up rounds, then the window: one
client, closed loop, the queries round-robin, ending at the first round
boundary at or after --seconds. With --trace 1 the first `trace_rounds` rounds
of the window run under the topology's profiler session(s), every query inside
the context manager the topology hands back and with its start and end on the
wall clock in the record. Everything it saw goes to <out>/cell.json, every
answer to <out>/results/, the trace to <out>/trace/; the parent judges them.

Fails (exit 2, nothing written) unless the topology's devices are TPUs listed
in bench/lib/peaks.json, as many as the cell asks for. --rehearse lifts that
for the CPU rehearsal, whose record is stamped with the platform it ran on and
which the parent never prints as a result.
"""

from __future__ import annotations

import argparse
import contextlib
import importlib
import json
import os
import sys
import time
import traceback

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

from lib import load_json  # noqa: E402
from lib.window import run_window  # noqa: E402


def require_device(device: dict, chips: int, peaks: dict, rehearse: bool) -> None:
    """Exits 2 unless `device`, as the topology reports what its chip-holding
    processes see, is a TPU the table of peaks knows, with `chips` chips: an
    unknown device is an error, not a default."""
    if rehearse:
        return
    if device["platform"] != "tpu" or device["kind"] not in peaks or device["count"] < chips:
        print(f"bench: needs {chips} TPU chip(s) of a kind in lib/peaks.json; jax's "
              f"default backend gives {device}. No CPU fallback.", file=sys.stderr)
        sys.exit(2)


def run_query(session, probes, name: str, sql: str, annotate) -> tuple[dict, object]:
    """One query through the entry a user calls, on the client's clock."""
    probes.clear_run_stats()
    before = probes.outcomes()
    rec: dict = {"query": name, "failed": False}
    out = None
    wall = [time.time_ns()]  # start, end of sql, end: the marks of a trace that has no annotations
    t0 = time.perf_counter()
    try:
        with annotate(name):
            with annotate("sql"):
                frame = session.sql(sql)
            t1 = time.perf_counter()
            wall.append(time.time_ns())
            with annotate("collect"):
                out = frame.collect()
        t2 = time.perf_counter()
        rec.update(sql_s=t1 - t0, collect_s=t2 - t1, rows=out.num_rows)
    except Exception:  # noqa: BLE001 — a query that raises is counted, not fatal
        rec.update(failed=True, error=traceback.format_exc(limit=6)[-1500:])
    rec["t0"], rec["seconds"] = t0, time.perf_counter() - t0
    rec["wall_ns"] = wall + [time.time_ns()]
    after = probes.outcomes()
    rec["outcomes"] = {k: after[k] - before[k] for k in before}
    rec["stages"] = probes.run_stats_stages()
    return rec, out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload-file", required=True)
    ap.add_argument("--config-file", required=True)
    ap.add_argument("--data-dir", required=True)
    ap.add_argument("--out-dir", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--stop-if-cold", action="store_true",
                    help="stop after a first round that missed the compile cache")
    args = ap.parse_args(argv)

    workload, config = load_json(args.workload_file), load_json(args.config_file)
    peaks = load_json(os.path.join(BENCH, "lib", "peaks.json"))
    sys.path.insert(0, ROOT)
    topology = importlib.import_module(f"lib.topology_{config['topology']}")

    queries = [(q, open(os.path.join(BENCH, "queries", f"{q}.sql")).read())
               for q in workload["queries"]]
    results_dir = os.path.join(args.out_dir, "results")
    trace_dir = os.path.join(args.out_dir, "trace")
    record: dict = {"executions": [], "traced": None}
    answers: list = []

    def write_record() -> None:
        with open(os.path.join(args.out_dir, "cell.json"), "w") as f:
            json.dump(record, f, default=str)

    def one_round(phase: str, rnd: int, annotate=contextlib.nullcontext) -> int:
        done = 0
        for name, sql in queries:
            rec, out = run_query(session, probes, name, sql, annotate)
            rec.update(phase=phase, round=rnd, seq=len(record["executions"]))
            record["executions"].append(rec)
            if out is not None:
                answers.append((rec["seq"], name, out))
                done += 1
        return done

    # from here on the topology may have processes of its own running (its
    # chip holders): close_session ends them, with no session where none opened
    record["device"] = topology.devices(config)
    session = None
    try:
        require_device(record["device"], config["chips"], peaks, args.rehearse)
        os.makedirs(results_dir, exist_ok=True)
        probes = topology.Probes()
        session = topology.open_session(config, args.data_dir)
        cache0 = probes.compile_cache()
        t0 = time.perf_counter()
        one_round("first", 0)
        record["first_round_s"] = time.perf_counter() - t0
        cache1 = probes.compile_cache()
        record["first_round_cache"] = {k: cache1[k] - cache0[k]
                                       for k in ("requests", "hits", "misses")}
        record["compile_cache_dir"] = cache1["dir"]
        if args.stop_if_cold and record["first_round_cache"]["misses"]:
            write_record()
            return 0
        for i in range(workload["warmup_rounds"]):
            one_round("warmup", i)

        tracing = {"on": False}

        def window_round(rnd: int) -> int:
            if args.trace and rnd == 0:
                tracing.update(annotate=topology.start_trace(session, trace_dir), on=True,
                               t0=time.perf_counter())
            annotate = tracing["annotate"] if tracing["on"] else contextlib.nullcontext
            done = one_round("window", rnd, annotate)
            if tracing["on"] and rnd + 1 >= workload["trace_rounds"]:
                stop_tracing(rnd + 1)
            return done

        def stop_tracing(rounds: int) -> None:
            traced_s = time.perf_counter() - tracing["t0"]
            topology.stop_trace(session)
            tracing["on"] = False
            record["traced"] = {"rounds": rounds, "seconds": traced_s,
                                "stop_s": time.perf_counter() - tracing["t0"] - traced_s}

        record["window_start"] = time.time()
        cache2 = probes.compile_cache()
        record["window"] = run_window(window_round, args.seconds)
        if tracing["on"]:  # the window closed inside the traced rounds
            stop_tracing(record["window"]["rounds"])
        cache3 = probes.compile_cache()
        record["window_cache"] = {k: cache3[k] - cache2[k]
                                  for k in ("requests", "hits", "misses")}
        record["memory_stats"], record["memory_peaks"] = probes.memory_stats()
        record["outcomes_recent"] = probes.outcomes_recent()
    finally:
        topology.close_session(session)

    import pyarrow as pa

    for seq, name, out in answers:
        with pa.OSFile(os.path.join(results_dir, f"{seq:05d}_{name}.arrow"), "wb") as f:
            with pa.ipc.new_file(f, out.schema) as w:
                w.write_table(out)
    write_record()
    return 0


if __name__ == "__main__":
    sys.exit(main())
