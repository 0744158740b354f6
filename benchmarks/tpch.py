"""TPC-H benchmark runner (rebuild of benchmarks/src/bin/tpch.rs).

Modes:
  python benchmarks/tpch.py data --scale 1 --out /tmp/tpch_sf1
  python benchmarks/tpch.py run --data /tmp/tpch_sf1 [--query 1] \
      [--engine cpu|tpu] [--mode local|standalone|remote --scheduler H:P] \
      [--iterations 3] [--verify]

`--verify` checks results against the pandas oracle (the reference's
expected-results verification leg).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def q_path(n: int) -> str:
    return os.path.join(os.path.dirname(os.path.abspath(__file__)), "tpch", "queries", f"q{n}.sql")


def cmd_data(args) -> None:
    from ballista_tpu.testing.tpchgen import generate_tpch

    t0 = time.time()
    generate_tpch(args.out, scale=args.scale, seed=args.seed, files_per_table=args.files_per_table)
    print(f"generated sf={args.scale} at {args.out} in {time.time() - t0:.1f}s")


def cmd_run(args) -> int:
    """Run the queries; returns how many FAILED (raised, or — with --verify —
    disagreed with the oracle), which becomes the exit code."""
    from ballista_tpu.client.context import SessionContext
    from ballista_tpu.config import BallistaConfig, DEFAULT_SHUFFLE_PARTITIONS, EXECUTOR_ENGINE, TARGET_PARTITIONS
    from ballista_tpu.testing.tpchgen import register_tpch

    cfg = BallistaConfig({
        EXECUTOR_ENGINE: args.engine,
        TARGET_PARTITIONS: args.partitions,
        DEFAULT_SHUFFLE_PARTITIONS: args.shuffle_partitions,
    })
    if args.mode == "local":
        ctx = SessionContext(cfg)
    elif args.mode == "standalone":
        ctx = SessionContext.standalone(cfg, num_executors=args.executors, vcores=args.concurrency)
    else:
        ctx = SessionContext.remote(args.scheduler, cfg)
    register_tpch(ctx, args.data)

    queries = [args.query] if args.query else list(range(1, 23))
    ref_tables = None
    if args.verify:
        from ballista_tpu.testing.reference import load_tables

        ref_tables = load_tables(args.data)

    results = {}
    total = 0.0
    failed = 0
    for q in queries:
        sql = open(q_path(q)).read()
        times = []
        out = None
        try:
            for _ in range(args.iterations):
                t0 = time.time()
                out = ctx.sql(sql).collect()
                times.append(time.time() - t0)
            best = min(times)
            total += best
            status = f"{best:8.3f}s  rows={out.num_rows}"
            if ref_tables is not None:
                from ballista_tpu.testing.reference import compare_results, run_reference

                problems = compare_results(out, run_reference(q, ref_tables), q)
                status += "  ✓" if not problems else f"  MISMATCH: {problems[0]}"
                failed += bool(problems)
            results[f"q{q}"] = round(best, 4)
            print(f"q{q:<3} {status}")
        except Exception as e:  # noqa: BLE001 — report, go on, fail at the end
            print(f"q{q:<3} FAILED: {e}")
            results[f"q{q}"] = None
            failed += 1
    print(f"\ntotal (best-of-{args.iterations}): {total:.3f}s  engine={args.engine} mode={args.mode}")
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump({"engine": args.engine, "mode": args.mode, "total_s": round(total, 3),
                       "queries": results}, f, indent=1)
    if failed:
        print(f"{failed} of {len(queries)} queries failed", file=sys.stderr)
    return failed


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description="TPC-H benchmark")
    sub = ap.add_subparsers(dest="cmd", required=True)
    d = sub.add_parser("data")
    d.add_argument("--scale", type=float, default=1.0)
    d.add_argument("--out", required=True)
    d.add_argument("--seed", type=int, default=42)
    d.add_argument("--files-per-table", type=int, default=4)
    r = sub.add_parser("run")
    r.add_argument("--data", required=True)
    r.add_argument("--query", type=int, default=None)
    r.add_argument("--engine", choices=("cpu", "tpu"), default="cpu")
    r.add_argument("--mode", choices=("local", "standalone", "remote"), default="local")
    r.add_argument("--scheduler", default="localhost:50050")
    r.add_argument("--executors", type=int, default=1)
    r.add_argument("--concurrency", type=int, default=8)
    r.add_argument("--partitions", type=int, default=8)
    r.add_argument("--shuffle-partitions", type=int, default=16)
    r.add_argument("--iterations", type=int, default=2)
    r.add_argument("--verify", action="store_true")
    r.add_argument("--json-out", default=None)
    args = ap.parse_args(argv)
    if args.cmd == "data":
        cmd_data(args)
    elif cmd_run(args):
        sys.exit(1)


if __name__ == "__main__":
    main()
