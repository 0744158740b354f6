#!/usr/bin/env python3
"""bench/run.py — one run of one cell of the benchmark (BENCHMARK.json).

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A parent that never touches a device: it generates the configuration's tables
from --seed (only the columns the configuration lists) under
data/bench/<config>_seed<n>/, runs the cell in a child process (bench/cell.py:
topology up, first round, warm-up, window; the chips are held by that child or
by processes its topology starts), then — the child and its processes gone and
the device free — computes the plain reference's answers over the same files,
compares every answer the child got with them, reduces the trace (every file
the run's processes wrote, as one), and prints the contract's one JSON object
as the last line of standard output. Earlier lines (JSON, one object each) are
for people.

A child whose first round missed the persistent compile cache compiled inside
`first_query_s`; its numbers are thrown away and the cell runs once more in a
new child, so `first_query_s` always means "compile cache warm". Only the
first run in a checkout pays this.

Cells, configurations, traffic and per-layer metrics are data files found by
the names in BENCHMARK.json: see bench/README.md.

--rehearse SCALE runs the same control flow at a tiny scale on whatever
backend jax has (JAX_PLATFORMS=cpu here). Its last line holds everything under
the one key "rehearsal", so nothing can read it as a result. --whole-tables
generates every column of the configuration's tables, not only those it lists
(PERF.md: what the cut `columns` changes); its last line sits under
"experiment" for the same reason.
"""

from __future__ import annotations

import argparse
import glob
import importlib
import json
import os
import shutil
import signal
import subprocess
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

from lib import load_attr, load_json, readers, trace_reduce, work  # noqa: E402


def emit(obj: dict) -> None:
    print(json.dumps(obj, default=str), flush=True)


def find_cell(name: str) -> tuple[dict, dict, dict, dict]:
    """BENCHMARK.json, the cell's entry, its configuration (with the path of
    its file under "file") and its traffic."""
    bench = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        sys.exit(f"bench: no cell {name!r} in BENCHMARK.json (has {sorted(cells)})")
    cell = cells[name]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    config = {**load_json(os.path.join(ROOT, entry["file"])), "file": entry["file"]}
    traffic = load_json(os.path.join(BENCH, "workloads", f"{cell['traffic']}.json"))
    return bench, cell, config, traffic


def fresh_dirs(config: dict, seed: int, tag: str) -> tuple[str, str]:
    """The run's data directory (removed on exit) and output directory, both
    emptied, inside the checkout where .gitignore lists them."""
    data_dir = os.path.join(ROOT, "data", "bench", f"{config['name']}_seed{seed}")
    out_dir = os.path.join(ROOT, "chiprun_out", "bench", tag)
    for d in (data_dir, out_dir):
        shutil.rmtree(d, ignore_errors=True)
    os.makedirs(out_dir)
    return data_dir, out_dir


def tree_bytes(path: str) -> int:
    return sum(os.path.getsize(os.path.join(d, f)) for d, _, files in os.walk(path) for f in files)


def child_args(cell: dict, config: dict, data_dir: str, out_dir: str, seconds: float,
               trace: int, rehearse: bool) -> list[str]:
    """bench/cell.py's arguments for one run of `cell`."""
    argv = ["--workload-file", os.path.join(BENCH, "workloads", f"{cell['traffic']}.json"),
            "--config-file", os.path.join(ROOT, config["file"]),
            "--data-dir", data_dir, "--out-dir", out_dir,
            "--seconds", str(seconds), "--trace", str(trace)]
    return argv + ["--rehearse"] * rehearse


def spawn_child(argv: list[str]) -> int:
    """bench/cell.py in a process group of its own, its output on our
    standard error; whatever is left of the group afterwards is killed."""
    proc = subprocess.Popen([sys.executable, os.path.join(BENCH, "cell.py"), *argv],
                            cwd=ROOT, stdin=subprocess.DEVNULL, stdout=sys.stderr,
                            start_new_session=True)
    try:
        return proc.wait()
    finally:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
        proc.wait()


def judge(out_dir: str, wants: dict, limits: dict, generator, attempted: int) -> tuple[dict, bool]:
    """Every answer under <out_dir>/results against the reference's: the
    run's numbers, each beside its limit (an answer that never came counts
    under `unanswered`), and whether all of them hold: `correct`."""
    import pyarrow as pa

    readings = []
    for path in sorted(glob.glob(os.path.join(out_dir, "results", "*.arrow"))):
        query = os.path.basename(path)[:-len(".arrow")].split("_", 1)[1]
        with pa.memory_map(path) as f:
            got = pa.ipc.open_file(f).read_all().to_pandas(date_as_object=False)
        readings.append(generator.compare(got, wants[query]))
    numbers = generator.worst(readings)
    checks = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    checks["unanswered"] = {"value": attempted - len(readings), "limit": 0}
    return checks, all(c["value"] <= c["limit"] for c in checks.values())


def wall_spans(record: dict) -> list:
    """The traced rounds' queries and their parts as the record has them on
    the wall clock: (start_ns, duration_ns, name), named as the annotations
    of a trace are."""
    rounds = (record.get("traced") or {}).get("rounds", 0)
    spans = []
    for e in record["executions"]:
        if e["phase"] == "window" and e["round"] < rounds:
            wall = e["wall_ns"]
            spans.append((wall[0], wall[-1] - wall[0], e["query"]))
            if len(wall) == 3:
                spans += [(wall[0], wall[1] - wall[0], "sql"), (wall[1], wall[2] - wall[1], "collect")]
    return spans


def reduce_traces(out_dir: str, queries: list[str], record: dict, chips: int) -> dict | None:
    """The traced rounds' numbers from the profiler's files, every process's
    as one trace; the files are then removed (tens of MB a run otherwise).
    None when the trace holds nothing."""
    t0 = time.time()
    trace_dir = os.path.join(out_dir, "trace")
    files = trace_reduce.read_files(trace_dir, queries)
    wall = wall_spans(record)
    merged = trace_reduce.merge_files(files, wall, queries)
    trace = trace_reduce.reduce_trace(merged["devices"], merged["spans"], queries, chips)
    skew_ms = None
    if merged["marks"] == "annotations" and merged["base_wall_ns"] is not None and wall:
        # a trace with marks of its own that says when it starts: how far the record's
        # wall-clock marks lie from them — the clock rule, checked in every traced run
        first = [min(s for s, _, n in spans if n in queries) for spans in (wall, merged["spans"])]
        skew_ms = (first[0] - merged["base_wall_ns"] - first[1]) / 1e6
    emit({"phase": "trace", "xplane_bytes": sum(raw["bytes"] for raw in files.values()),
          "files": len(files), "marks": merged["marks"], "record_marks_skew_ms": skew_ms,
          "reduce_s": time.time() - t0, "traced": record["traced"], "reduced": trace})
    shutil.rmtree(trace_dir, ignore_errors=True)
    return trace


def per_layer(bench: dict, cell: dict, run: readers.Run) -> dict:
    metrics = {}
    for m in bench["per_layer"]:
        if "workloads" in m and cell["name"] not in m["workloads"]:
            continue
        spec = load_json(os.path.join(BENCH, "metrics", f"{m['name']}.json"))
        value = load_attr(spec["reader"])(run, **spec["args"])
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    return metrics


def main(argv=None, run_child=spawn_child) -> int:
    t_start = time.time()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", type=float, metavar="SCALE", default=None)
    ap.add_argument("--whole-tables", action="store_true",
                    help="an experiment, never a result: generate every column of the "
                         "configuration's tables, to show what the cut `columns` changes")
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "ballista_tpu")):
        sys.exit("bench: no ballista_tpu/ beside bench/: nothing to measure")
    bench, cell, config, traffic = find_cell(args.workload)
    generator = importlib.import_module(f"lib.generator_{config['generator']}")
    scale = config["scale"] if args.rehearse is None else args.rehearse
    data_dir, out_dir = fresh_dirs(config, args.seed,
                                   f"{cell['name']}_seed{args.seed}_trace{args.trace}")
    try:
        t0 = time.time()
        rows = generator.generate(data_dir, config, scale, args.seed, whole=args.whole_tables)
        emit({"phase": "datagen", "config": config["name"], "scale": scale,
              "seed": args.seed, "rows": rows, "seconds": time.time() - t0,
              "whole_tables": args.whole_tables, "bytes": tree_bytes(data_dir),
              "reduced": config["reduced"]})

        child_argv = child_args(cell, config, data_dir, out_dir, args.seconds, args.trace,
                                args.rehearse is not None)
        for attempt in (1, 2):
            shutil.rmtree(os.path.join(out_dir, "results"), ignore_errors=True)
            shutil.rmtree(os.path.join(out_dir, "trace"), ignore_errors=True)
            rc = run_child(child_argv + ["--stop-if-cold"] * (attempt == 1))
            if rc != 0:
                print(f"bench: the cell's process exited {rc}; no result", file=sys.stderr)
                return rc or 1
            record = load_json(os.path.join(out_dir, "cell.json"))
            cold = "window" not in record
            emit({"phase": "child", "attempt": attempt, "first_round_s": record["first_round_s"],
                  "first_round_cache": record["first_round_cache"],
                  "compile_cache_dir": record["compile_cache_dir"],
                  "kept": not cold})
            if not cold:
                break

        # the window is closed, the child gone and the device free: now the
        # reference, over the same files
        t0 = time.time()
        wants = generator.answers(data_dir, config, traffic["queries"])
        execs = record["executions"]
        attempted = len(execs)
        failed = sum(e["failed"] for e in execs)
        checks, correct = judge(out_dir, wants, config["limits"], generator, attempted)
        reference_s = time.time() - t0

        window = record["window"]
        device = dict(record["device"])
        device["memory_peak_bytes"] = record["memory_stats"].get("peak_bytes_in_use", 0)
        line: dict = {"correct": correct, "attempted": attempted, "failed": failed}
        if args.trace:
            trace = reduce_traces(out_dir, traffic["queries"], record, cell["chips"])
            schema = load_json(os.path.join(BENCH, "lib", generator.SCHEMA_FILE))
            peaks = load_json(os.path.join(BENCH, "lib", "peaks.json"))
            round_bytes = sum(
                work.query_bytes(open(os.path.join(BENCH, "queries", f"{q}.sql")).read(),
                                 config["tables"], rows, schema) for q in traffic["queries"])
            line["metrics"] = per_layer(bench, cell, readers.Run(
                record=record, trace=trace, round_bytes=round_bytes,
                peaks=peaks.get(device["kind"], {}), chips=cell["chips"]))
            if trace:
                device["busy_s"], device["window_s"] = trace["busy_s"], trace["window_s"]
                line["breakdown"] = {"device_ops": trace["device_ops"],
                                     "idle_gaps": trace["idle_gaps"]}
        else:
            values = {"setup_s": record["window_start"] - t_start,
                      "hot_query_s": window["seconds"] / max(window["completed"], 1),
                      "first_query_s": record["first_round_s"] / len(traffic["queries"])}
            line["metrics"] = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                               for m in bench["end_to_end"]
                               if cell["name"] in m.get("workloads", [cell["name"]])}
        line["device"] = device
        line["checks"] = checks

        by_query: dict = {}
        for e in execs:
            if e["phase"] == "window" and not e["failed"]:
                by_query.setdefault(e["query"], []).append(e["seconds"])
        emit({"phase": "window", **window, "reference_s": reference_s,
              "compared": attempted - checks["unanswered"]["value"],
              "query_seconds": {q: sorted(v) for q, v in by_query.items()},
              # a stall names its stage here (one q1 of 4.6 s among 0.31 s: PERF.md, Open questions)
              "slowest": max((e for e in execs if e["phase"] == "window"),
                             key=lambda e: e["seconds"], default=None),
              "below_row_floor": sum(e["outcomes"].get("below_row_floor", 0) for e in execs),
              "off_device_recent": record["outcomes_recent"][-5:],
              "window_cache": record["window_cache"]})
        emit({"phase": "first_round", "executions": [e for e in execs if e["phase"] == "first"]})
        with open(os.path.join(out_dir, "line.json"), "w") as f:
            json.dump(line, f)
        for name, c in checks.items():
            print(f"check {name}: {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
        sys.stderr.flush()
        emit({"rehearsal": line} if args.rehearse is not None else
             {"experiment": line} if args.whole_tables else line)
        return 0
    finally:
        shutil.rmtree(data_dir, ignore_errors=True)
        shutil.rmtree(os.path.join(out_dir, "results"), ignore_errors=True)


def _terminated(signum, frame):
    sys.exit(128 + signum)  # unwinds through the finally blocks: the child is killed


if __name__ == "__main__":
    signal.signal(signal.SIGTERM, _terminated)
    sys.exit(main())
