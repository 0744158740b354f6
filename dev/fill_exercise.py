"""Exercise the TPU stage cold path end-to-end (CPU jax).

    JAX_PLATFORMS=cpu python dev/fill_exercise.py

Two legs:

1. overlap — a cold TPC-H q1 with `ballista.tpu.compile.overlap` on must
   start compiling under the device fill: RUN_STATS reports
   `compile_overlap_s > 0` (chunked uploads stretch the fill enough to
   make the overlap deterministic on fast CPU backends).
2. restart — two fresh processes run the same q1 stage sharing one
   persistent compile cache dir (`JAX_COMPILATION_CACHE_DIR`). The warm
   process must fetch its XLA binary from disk: warm `xla_compile_s`
   ≤ 0.1× cold, warm `compile_s` strictly below cold, and the warm run
   reports persistent-cache hits.

Exits non-zero if either leg fails.
"""

import json
import os
import subprocess
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

STATS_MARK = "FILL_EXERCISE_STATS "


def q1_sql() -> str:
    with open(os.path.join(ROOT, "benchmarks", "tpch", "queries", "q1.sql")) as f:
        return f.read()


def run_q1(data_dir: str, extra_cfg: dict | None = None) -> dict:
    from ballista_tpu.client.context import SessionContext
    from ballista_tpu.config import EXECUTOR_ENGINE, BallistaConfig
    from ballista_tpu.ops.tpu import runtime, stage_compiler
    from ballista_tpu.testing.tpchgen import register_tpch

    cfg = BallistaConfig({EXECUTOR_ENGINE: "tpu", **(extra_cfg or {})})
    ctx = SessionContext(cfg)
    register_tpch(ctx, data_dir)
    out = ctx.sql(q1_sql()).collect()
    if out.num_rows == 0:
        raise SystemExit("[q1] produced no rows")
    stats = stage_compiler.RUN_STATS.snapshot()
    stats["_cache"] = runtime.compile_cache_stats()
    return stats


def leg_overlap(data_dir: str) -> None:
    from ballista_tpu.config import TPU_FILL_CHUNK_ROWS

    stats = run_q1(data_dir, {TPU_FILL_CHUNK_ROWS: 4096})
    ov = stats.get("compile_overlap_s", 0.0)
    if ov <= 0:
        raise SystemExit(f"[overlap] no compile/fill overlap recorded: {stats}")
    serial_total = stats["fill_s"] + stats.get("compile_s", 0.0) + stats["exec_s"]
    print(f"[overlap] ok: compile_overlap_s={ov:.3f} hidden under "
          f"fill_s={stats['fill_s']:.3f} (serial total would be "
          f"~{serial_total:.3f}s, compile_s={stats.get('compile_s', 0.0):.3f})")


def child(data_dir: str) -> None:
    stats = run_q1(data_dir)
    print(STATS_MARK + json.dumps(stats))


def spawn(data_dir: str, cache_dir: str) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child", data_dir],
        env=env, capture_output=True, text=True, timeout=600)
    if proc.returncode != 0:
        raise SystemExit(f"[restart] child failed:\n{proc.stdout}\n{proc.stderr}")
    for line in proc.stdout.splitlines():
        if line.startswith(STATS_MARK):
            return json.loads(line[len(STATS_MARK):])
    raise SystemExit(f"[restart] child printed no stats:\n{proc.stdout}")


def leg_restart(data_dir: str) -> None:
    with tempfile.TemporaryDirectory(prefix="fill-xla-cache-") as cache_dir:
        cold = spawn(data_dir, cache_dir)
        if not os.listdir(cache_dir):
            raise SystemExit("[restart] cold run persisted nothing")
        warm = spawn(data_dir, cache_dir)
    cold_x, warm_x = cold.get("xla_compile_s", 0.0), warm.get("xla_compile_s", 0.0)
    if warm_x > 0.1 * cold_x:
        raise SystemExit(f"[restart] warm XLA compile not served from disk: "
                         f"cold={cold_x:.3f}s warm={warm_x:.3f}s")
    if warm.get("compile_s", 0.0) >= cold.get("compile_s", 0.0):
        raise SystemExit(f"[restart] warm compile_s {warm.get('compile_s')} not "
                         f"below cold {cold.get('compile_s')}")
    if warm["_cache"]["hits"] <= cold["_cache"]["hits"]:
        raise SystemExit(f"[restart] warm run reported no persistent-cache hits: "
                         f"cold={cold['_cache']} warm={warm['_cache']}")
    print(f"[restart] ok: xla_compile_s {cold_x:.3f}s cold → {warm_x:.3f}s warm "
          f"({warm['_cache']['hits']} disk hits; compile_s "
          f"{cold.get('compile_s', 0.0):.3f}s → {warm.get('compile_s', 0.0):.3f}s)")


def main() -> None:
    if len(sys.argv) > 1 and sys.argv[1] == "--child":
        child(sys.argv[2])
        return
    from ballista_tpu.testing.tpchgen import generate_tpch

    with tempfile.TemporaryDirectory(prefix="fill-tpch-") as d:
        print(f"generating TPC-H sf0.01 under {d} ...")
        generate_tpch(d, scale=0.01, seed=42, files_per_table=2)
        leg_overlap(d)
        leg_restart(d)
    print("fill exercise passed")


if __name__ == "__main__":
    main()
