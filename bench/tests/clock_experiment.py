#!/usr/bin/env python3
"""Do trace files of several processes fit on one clock?

    python3 bench/tests/clock_experiment.py --chips 4 [--seconds 3]

Not a cell and not part of a run. Drives bench/cell.py end to end over the
fake topology `holders` (fake_lib/: one plain python process a chip, each
pinned to its chip, each under its own profiler session, each running a jitted
loop in bursts whose wall-clock start and end it reports; cell.py itself
touches no jax and records the queries' marks), merges the holders' trace files
through run.reduce_traces, and compares what the merged trace says with what
the holders said: where each burst sits on the wall clock, and each plane's
busy time against its holder's bursts. One JSON line; exit 1 where a burst
lies more than --tolerance-ms off or a plane's busy time more than 2 % off
(a burst as its holder times it holds a millisecond or two of the host's own:
make it some hundreds of milliseconds).
bench/tests/test_holders.py runs the same on the CPU backend (--rehearse).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
FAKE_LIB = os.path.join(HERE, "fake_lib")
sys.path.insert(0, BENCH)

import run  # noqa: E402
from lib import trace_reduce  # noqa: E402

QUERIES = ["q1", "q6"]
# cell.py as run.py starts it, with the test's own lib/topology_* on the
# package's path, and the proof that its process never imported jax
CELL = """
import sys
sys.path.insert(0, {bench!r})
import lib
lib.__path__.append({fake_lib!r})
import cell
rc = cell.main(sys.argv[1:])
assert "jax" not in sys.modules, "the process that runs the queries imported jax"
sys.exit(rc)
"""


def drive_cell(out_dir: str, chips: int, bursts: dict, burst: dict, seconds: float,
               rehearse: bool, trace_rounds: int = 2) -> subprocess.CompletedProcess:
    """One traced run of cell.py over `holders`, its files under `out_dir`."""
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    files = {"config.json": {"topology": "holders", "chips": chips, "bursts": bursts,
                             "burst": burst},
             "workload.json": {"queries": QUERIES, "warmup_rounds": 1,
                               "trace_rounds": trace_rounds}}
    for name, body in files.items():
        with open(os.path.join(out_dir, name), "w") as f:
            json.dump(body, f)
    argv = ["--workload-file", os.path.join(out_dir, "workload.json"),
            "--config-file", os.path.join(out_dir, "config.json"),
            "--data-dir", out_dir, "--out-dir", out_dir, "--seconds", str(seconds),
            "--trace", "1"] + ["--rehearse"] * rehearse
    return subprocess.run([sys.executable, "-c", CELL.format(bench=BENCH, fake_lib=FAKE_LIB),
                           *argv], cwd=ROOT, stdin=subprocess.DEVNULL, capture_output=True,
                          text=True, timeout=600)


def known_bursts(out_dir: str, record: dict) -> dict[int, list[tuple[int, int]]]:
    """By holder, the (start, end) wall-clock ns of the bursts it reported for
    the traced rounds' queries."""
    import pyarrow as pa

    rounds = record["traced"]["rounds"]
    traced = {e["seq"] for e in record["executions"]
              if e["phase"] == "window" and e["round"] < rounds}
    bursts: dict[int, list] = {}
    for path in glob.glob(os.path.join(out_dir, "results", "*.arrow")):
        if int(os.path.basename(path).split("_")[0]) in traced:
            with pa.memory_map(path) as f:
                for row in pa.ipc.open_file(f).read_all().to_pylist():
                    bursts.setdefault(row["holder"], []).append((row["start_ns"], row["end_ns"]))
    return {h: sorted(b) for h, b in bursts.items()}


def compare(out_dir: str, chips: int) -> dict:
    """The merged trace against what the holders said."""
    record = run.load_json(os.path.join(out_dir, "cell.json"))
    bursts = known_bursts(out_dir, record)
    trace_dir = os.path.join(out_dir, "trace")
    files = trace_reduce.read_files(trace_dir, QUERIES + ["burst"])
    merged = trace_reduce.merge_files(files, run.wall_spans(record), QUERIES)
    marks = [(s, s + d) for s, d, n in merged["spans"] if n in QUERIES]
    lo, hi = min(a for a, _ in marks), max(b for _, b in marks)
    planes = {}
    for plane, events in merged["devices"].items():
        holder = int(plane.split("/", 1)[0][len("holder"):])
        said = bursts.get(holder, [])
        # each burst is one jitted call: the device's busy stretches inside
        # the window, in order, are the bursts
        stretches = coalesce(trace_reduce.union(trace_reduce.clip(
            [(s, s + d) for s, d, _ in events], lo, hi)), gap_ns=1e6)
        sync = run.load_json(os.path.join(trace_dir, f"holder{holder}", "sync.json"))
        first = min(s for s, _, _ in events)  # the burst the holder began its trace with
        raw = files[plane.rsplit(":/", 1)[0]]
        # the holder's own annotation of each burst, on its file's host plane:
        # the file's clock alone, without the device's
        noted = sorted(raw["start_wall_ns"] + s for s, _, n in raw["spans"] if n == "burst")
        planes[plane] = {
            "host_start_off_ms": [(t - a) / 1e6 for t, (a, _) in
                                  zip(noted, [(sync["start_ns"], 0)] + said)],
            "sync_start_off_ms": (merged["base_wall_ns"] + first - sync["start_ns"]) / 1e6,
            "bursts_said": len(said), "stretches_traced": len(stretches),
            "said_busy_s": sum(b - a for a, b in said) / 1e9,
            "start_off_ms": [(merged["base_wall_ns"] + a - said[i][0]) / 1e6
                             for i, (a, _) in enumerate(stretches[:len(said)])],
            "end_off_ms": [(merged["base_wall_ns"] + b - said[i][1]) / 1e6
                           for i, (_, b) in enumerate(stretches[:len(said)])]}
    reduced = run.reduce_traces(out_dir, QUERIES, record, chips)
    for plane, numbers in planes.items():
        numbers["traced_busy_s"] = reduced["plane_busy_s"][plane]
    return {"device": record["device"], "memory_peaks": record["memory_peaks"],
            "file_starts_wall_ns": {f: raw["start_wall_ns"] for f, raw in files.items()},
            "marks": merged["marks"], "planes": planes, "reduced": reduced}


def coalesce(intervals: list, gap_ns: float) -> list:
    """Intervals closer than `gap_ns` taken as one (the operations of one call)."""
    out: list[list[float]] = []
    for a, b in intervals:
        if out and a - out[-1][1] < gap_ns:
            out[-1][1] = b
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=4)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--size", type=int, default=2048)
    ap.add_argument("--iters", type=int, default=3200)
    ap.add_argument("--tolerance-ms", type=float, default=2.0)
    args = ap.parse_args(argv)
    # holder 0 runs in every query, 1 in q1 only, 2 in q6 only, the last one never
    n = args.chips
    bursts = {"q1": [h for h in (0, 1) if h == 0 or h < n - 1],
              "q6": [h for h in (0, 2) if h == 0 or h < n - 1]}
    out_dir = os.path.join(ROOT, "chiprun_out", "clock_experiment")
    done = drive_cell(out_dir, n, bursts, {"size": args.size, "iters": args.iters},
                      args.seconds, rehearse=False)
    sys.stderr.write(done.stderr[-4000:])
    if done.returncode != 0:
        print(json.dumps({"cell_exit": done.returncode}))
        return done.returncode
    result = {"chips": n, "bursts": bursts, **compare(out_dir, n)}
    ok = True
    for numbers in result["planes"].values():
        ok &= numbers["bursts_said"] == numbers["stretches_traced"]
        ok &= all(abs(ms) <= args.tolerance_ms for ms in numbers["start_off_ms"])
        if numbers["said_busy_s"]:
            ok &= abs(numbers["traced_busy_s"] / numbers["said_busy_s"] - 1) <= 0.02
        else:
            ok &= numbers["traced_busy_s"] == 0
    result["ok"] = bool(ok)
    print(json.dumps(result))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
