"""TpuFinalStageExec: device execution of final-agg / sort / top-K stages.

Reference parity target: the engine owns EVERY stage shape
(ballista/executor/src/execution_engine.rs:51) — round 3 extends device
execution beyond partial-agg chains to the merge/sort stage class.
Each test cross-checks the tpu engine against the cpu engine and asserts
the device path actually ran (no silent fallback)."""

import numpy as np
import pyarrow as pa
import pytest

from ballista_tpu.config import (
    BallistaConfig,
    EXECUTOR_ENGINE,
    TPU_MIN_ROWS,
)


def _walk(n):
    yield n
    for c in n.children():
        yield from _walk(c)


def _run_checked(sql, tables, expect_final=1):
    """Run on both engines; assert `expect_final` device final stages
    compiled AND ran with zero fallbacks; return (tpu, cpu) tables."""
    from ballista_tpu.client.context import SessionContext
    from ballista_tpu.engine.tpu_engine import maybe_compile_tpu
    from ballista_tpu.ops.tpu.final_stage import TpuFinalStageExec
    from ballista_tpu.plan.physical import TaskContext

    results = {}
    for engine in ("tpu", "cpu"):
        cfg = BallistaConfig({EXECUTOR_ENGINE: engine, TPU_MIN_ROWS: 0})
        ctx = SessionContext(cfg)
        for name, tbl in tables.items():
            ctx.register_arrow_table(name, tbl, partitions=2)
        results[engine] = ctx.sql(sql).collect()
        if engine == "tpu":
            phys = maybe_compile_tpu(ctx.create_physical_plan(ctx.sql(sql).plan), cfg)
            stages = [nd for nd in _walk(phys) if isinstance(nd, TpuFinalStageExec)]
            assert len(stages) == expect_final, phys.display()
            tc = TaskContext(cfg)
            for p in range(phys.output_partition_count()):
                list(phys.execute(p, tc))
            assert all(s.tpu_count == 1 for s in stages), "final stage did not run on device"
            assert all(s.fallback_count == 0 for s in stages), "final stage fell back"
    return results["tpu"], results["cpu"]


def test_final_merge_sort_limit_all_agg_kinds():
    """sum/count/min/max/avg merge + two-key ORDER BY (DESC then ASC) +
    LIMIT — the q3/q10 stage class — matches the CPU engine exactly."""
    rng = np.random.default_rng(7)
    n = 20000
    t = pa.table({
        "g": rng.integers(0, 500, n).astype("int64"),
        "s": pa.array([f"name{i % 37}" for i in range(n)]),
        "v": np.round(rng.random(n) * 100, 2),
        "w": rng.integers(0, 1000, n).astype("int64"),
    })
    sql = ("SELECT g, s, sum(v) AS sv, count(*) AS c, min(w) AS mw, "
           "max(w) AS xw, avg(v) AS av "
           "FROM t GROUP BY g, s ORDER BY sv DESC, g ASC LIMIT 25")
    tpu, cpu = _run_checked(sql, {"t": t})
    tp, cp = tpu.to_pandas(), cpu.to_pandas()
    assert tp.g.tolist() == cp.g.tolist()
    assert tp.s.tolist() == cp.s.tolist()
    assert np.allclose(tp.sv.values, cp.sv.values)
    assert tp.c.tolist() == cp.c.tolist()
    assert tp.mw.tolist() == cp.mw.tolist()
    assert tp.xw.tolist() == cp.xw.tolist()
    assert np.allclose(tp.av.values, cp.av.values)


def test_final_stage_nullable_keys_and_accumulators():
    """NULL group keys form their own group; a group whose agg inputs are
    all NULL decodes to NULL after the device merge (not 0 / ±inf)."""
    rng = np.random.default_rng(11)
    n = 8000
    g = rng.integers(0, 50, n).astype("int64")
    null_g = rng.random(n) < 0.1
    v = np.round(rng.random(n) * 10, 2)
    null_v = rng.random(n) < 0.3
    null_v[g == 49] = True  # group 49: all agg inputs NULL
    t = pa.table({
        "g": pa.array(g, pa.int64(), mask=null_g),
        "v": pa.array(v, pa.float64(), mask=null_v),
    })
    sql = ("SELECT g, sum(v) AS s, min(v) AS mn, max(v) AS mx, count(v) AS c "
           "FROM t GROUP BY g ORDER BY g ASC LIMIT 100")
    tpu, cpu = _run_checked(sql, {"t": t})
    tp, cp = tpu.to_pandas(), cpu.to_pandas()
    assert tp.g.fillna(-1).tolist() == cp.g.fillna(-1).tolist()
    assert tp.s.isna().tolist() == cp.s.isna().tolist()
    assert np.allclose(tp.s.fillna(0).values, cp.s.fillna(0).values)
    assert tp.mn.isna().tolist() == cp.mn.isna().tolist()
    assert np.allclose(tp.mn.fillna(0).values, cp.mn.fillna(0).values)
    assert tp.c.tolist() == cp.c.tolist()


def test_final_merge_of_a_group_a_row_with_64_bit_lanes():
    """The h2o q10 shape: groups of about a row over int64 keys (past 2^32,
    negative, one nullable) and a dictionary key, merged with an f64 sum
    whose inputs hold NULLs (one group's all of them), a count, and min /
    max over f64. The answer is the CPU engine's; the merge's record says
    how its lanes were compacted — each int64 lane (three keys, the count,
    each nullable accumulator's valid count) as two 32-bit scatters, each
    f64 accumulator gathered — and no lane was scattered at 64 bits."""
    import ballista_tpu.ops.tpu.stage_compiler as sc

    rng = np.random.default_rng(43)
    n = 6000
    k6 = rng.integers(0, 40, n)
    v = np.round(rng.uniform(0, 100, n), 6)
    null_v = (rng.random(n) < 0.2) | (k6 == 39)  # k6 = 39: every input NULL
    t = pa.table({
        "s": pa.array([f"id{i}" for i in rng.integers(0, 4, n)]),
        "k4": rng.choice(np.array([-(1 << 40), 0, (1 << 33) + 1]), n),
        "k5": pa.array(rng.integers(-5, 5, n), pa.int64(), mask=rng.random(n) < 0.1),
        "k6": (k6 << 32) - 7,
        "v": pa.array(v, pa.float64(), mask=null_v),
    })
    sql = ("SELECT s, k4, k5, k6, sum(v) AS sv, count(*) AS c, min(v) AS mn, "
           "max(v) AS mx FROM t GROUP BY s, k4, k5, k6")
    sc.RUN_STATS.clear()
    tpu, cpu = _run_checked(sql, {"t": t})
    key = ["s", "k4", "k5", "k6"]
    tp = tpu.to_pandas().sort_values(key, na_position="first").reset_index(drop=True)
    cp = cpu.to_pandas().sort_values(key, na_position="first").reset_index(drop=True)
    assert len(tp) == len(cp) > n // 2  # most groups a row or two
    for c in key + ["c"]:
        assert tp[c].fillna(-1).tolist() == cp[c].fillna(-1).tolist(), c
    for c in ("sv", "mn", "mx"):
        assert tp[c].isna().tolist() == cp[c].isna().tolist(), c
        assert tp[c].isna().any(), c
        assert np.allclose(tp[c].fillna(0).values, cp[c].fillna(0).values,
                           rtol=1e-12, atol=0), c
    assert tp.mn.equals(cp.mn) and tp.mx.equals(cp.mx)  # moved, not recomputed
    final, = (r for tag, r in sc.RUN_STATS.stages().items() if tag.startswith("final_"))
    assert final["compact_split_lanes"] == 3 + 1 + 3
    assert final["compact_gathered_lanes"] == 3


def test_final_stage_having_filter():
    """HAVING lowers as a device-side filter over merged groups."""
    rng = np.random.default_rng(13)
    n = 10000
    t = pa.table({
        "g": rng.integers(0, 200, n).astype("int64"),
        "v": rng.integers(1, 10, n).astype("int64"),
    })
    sql = ("SELECT g, sum(v) AS s, count(*) AS c FROM t GROUP BY g "
           "HAVING sum(v) > 250 ORDER BY s DESC, g ASC")
    tpu, cpu = _run_checked(sql, {"t": t})
    tp, cp = tpu.to_pandas(), cpu.to_pandas()
    assert len(tp) == len(cp) and len(tp) > 0
    assert tp.g.tolist() == cp.g.tolist()
    assert tp.s.tolist() == cp.s.tolist()


def test_final_stage_string_sort_key_collation():
    """String ORDER BY keys sort by host-built lexicographic rank LUTs —
    dictionary code order (appearance order) must never leak through."""
    rng = np.random.default_rng(17)
    n = 6000
    # appearance order deliberately differs from lexicographic order
    names = [f"{'zyxwv'[i % 5]}_cat{i % 23:02d}" for i in range(n)]
    t = pa.table({
        "s": pa.array(names),
        "v": rng.integers(0, 100, n).astype("int64"),
    })
    for direction in ("ASC", "DESC"):
        sql = (f"SELECT s, sum(v) AS sv FROM t GROUP BY s "
               f"ORDER BY s {direction} LIMIT 30")
        tpu, cpu = _run_checked(sql, {"t": t})
        tp, cp = tpu.to_pandas(), cpu.to_pandas()
        assert tp.s.tolist() == cp.s.tolist(), direction
        assert tp.sv.tolist() == cp.sv.tolist(), direction


def test_final_stage_money_group_key():
    """Float group keys that refine to fixed-point money (the q10/q18
    c_acctbal / o_totalprice shape) group and sort exactly on device."""
    rng = np.random.default_rng(19)
    n = 9000
    prices = np.round(rng.integers(100, 400, n) + rng.integers(0, 100, n) / 100.0, 2)
    t = pa.table({
        "price": pa.array(prices, pa.float64()),
        "q": rng.integers(1, 50, n).astype("int64"),
    })
    sql = ("SELECT price, sum(q) AS tq, count(*) AS c FROM t GROUP BY price "
           "ORDER BY price DESC LIMIT 50")
    tpu, cpu = _run_checked(sql, {"t": t})
    tp, cp = tpu.to_pandas(), cpu.to_pandas()
    assert np.allclose(tp.price.values, cp.price.values)
    assert tp.tq.tolist() == cp.tq.tolist()
    assert tp.c.tolist() == cp.c.tolist()


def test_final_stage_no_sort_projection_only():
    """Final merge + post-projection without ORDER BY still lowers (the
    writer-rooted merge stage shape); row order is engine-defined so
    compare as sets keyed by the group column."""
    rng = np.random.default_rng(23)
    n = 12000
    t = pa.table({
        "g": rng.integers(0, 300, n).astype("int64"),
        "a": np.round(rng.random(n) * 5, 2),
        "b": rng.integers(0, 7, n).astype("int64"),
    })
    sql = "SELECT g, sum(a) AS sa, avg(a) AS aa, sum(b) AS sb FROM t GROUP BY g"
    tpu, cpu = _run_checked(sql, {"t": t})
    tp = tpu.to_pandas().sort_values("g").reset_index(drop=True)
    cp = cpu.to_pandas().sort_values("g").reset_index(drop=True)
    assert tp.g.tolist() == cp.g.tolist()
    assert np.allclose(tp.sa.values, cp.sa.values)
    assert np.allclose(tp.aa.values, cp.aa.values)
    assert tp.sb.tolist() == cp.sb.tolist()


def test_final_stage_fetch_exceeds_groups():
    """LIMIT larger than the group count returns every group."""
    rng = np.random.default_rng(29)
    n = 5000
    t = pa.table({
        "g": rng.integers(0, 8, n).astype("int64"),
        "v": rng.integers(0, 100, n).astype("int64"),
    })
    sql = "SELECT g, sum(v) AS s FROM t GROUP BY g ORDER BY s DESC LIMIT 1000"
    tpu, cpu = _run_checked(sql, {"t": t})
    tp, cp = tpu.to_pandas(), cpu.to_pandas()
    assert len(tp) == 8
    assert tp.g.tolist() == cp.g.tolist()
    assert tp.s.tolist() == cp.s.tolist()


def test_final_stage_welford_not_matched():
    """Variance queries keep their final merge on CPU (welford triples are
    merged host-side) — the matcher must not wrap them, so the query still
    answers correctly with zero device-final stages."""
    rng = np.random.default_rng(31)
    n = 6000
    t = pa.table({
        "g": rng.integers(0, 20, n).astype("int64"),
        "v": rng.normal(100.0, 10.0, n),
    })
    sql = "SELECT g, stddev(v) AS sd FROM t GROUP BY g ORDER BY g"
    tpu, cpu = _run_checked(sql, {"t": t}, expect_final=0)
    tp, cp = tpu.to_pandas(), cpu.to_pandas()
    assert tp.g.tolist() == cp.g.tolist()
    assert np.allclose(tp.sd.values, cp.sd.values, rtol=1e-9)


def test_final_stage_date_group_and_sort():
    """Date group keys and date sort keys ride the int32 day lanes."""
    import datetime as dt

    rng = np.random.default_rng(37)
    n = 7000
    base = dt.date(1995, 1, 1)
    days = rng.integers(0, 365, n)
    t = pa.table({
        "d": pa.array([base + dt.timedelta(days=int(x)) for x in days], pa.date32()),
        "v": rng.integers(0, 100, n).astype("int64"),
    })
    sql = ("SELECT d, sum(v) AS s, count(*) AS c FROM t GROUP BY d "
           "ORDER BY d DESC LIMIT 40")
    tpu, cpu = _run_checked(sql, {"t": t})
    tp, cp = tpu.to_pandas(), cpu.to_pandas()
    assert tp.d.tolist() == cp.d.tolist()
    assert tp.s.tolist() == cp.s.tolist()
    assert tp.c.tolist() == cp.c.tolist()


def test_final_stage_hbm_cap_enforced():
    """A final stage whose [P, N] stacking exceeds TPU_MAX_DEVICE_BYTES is
    rejected with Unsupported BEFORE dispatch (no device OOM reliance) and
    the query falls back to the CPU subtree with the right answer."""
    from ballista_tpu.client.context import SessionContext
    from ballista_tpu.config import TPU_MAX_DEVICE_BYTES
    from ballista_tpu.engine.tpu_engine import maybe_compile_tpu
    from ballista_tpu.ops.tpu.final_stage import TpuFinalStageExec
    from ballista_tpu.ops.tpu.kernels import Unsupported
    from ballista_tpu.plan.physical import TaskContext

    rng = np.random.default_rng(43)
    n = 4000
    t = pa.table({
        "g": rng.integers(0, 50, n).astype("int64"),
        "v": rng.integers(0, 100, n).astype("int64"),
    })
    sql = "SELECT g, sum(v) AS s FROM t GROUP BY g ORDER BY s DESC LIMIT 5"
    cfg = BallistaConfig({EXECUTOR_ENGINE: "tpu", TPU_MIN_ROWS: 0,
                          TPU_MAX_DEVICE_BYTES: 1024})
    ctx = SessionContext(cfg)
    ctx.register_arrow_table("t", t, partitions=2)
    phys = maybe_compile_tpu(ctx.create_physical_plan(ctx.sql(sql).plan), cfg)
    stages = [nd for nd in _walk(phys) if isinstance(nd, TpuFinalStageExec)]
    assert len(stages) == 1, phys.display()
    # the budget check raises Unsupported cleanly (not a device error)
    with pytest.raises(Unsupported, match="device bytes"):
        stages[0]._tpu_run_all(TaskContext(cfg))
    # ... and the full query answers correctly through the CPU fallback
    out = ctx.sql(sql).collect().to_pandas()
    cfg_cpu = BallistaConfig({EXECUTOR_ENGINE: "cpu"})
    ctx_cpu = SessionContext(cfg_cpu)
    ctx_cpu.register_arrow_table("t", t, partitions=2)
    exp = ctx_cpu.sql(sql).collect().to_pandas()
    assert out.g.tolist() == exp.g.tolist()
    assert out.s.tolist() == exp.s.tolist()
    assert all(s.tpu_count == 0 for s in stages)


def test_bypass_partitioning_contract():
    """Pin the hash-repartition bypass contract (final_stage.py): the exec
    still advertises the repartition's K output partitions, but ALL rows
    come out on partition 0 and partitions 1..K-1 are empty — consumers
    must never trust declared hash placement of this node's output."""
    from ballista_tpu.client.context import SessionContext
    from ballista_tpu.engine.tpu_engine import maybe_compile_tpu
    from ballista_tpu.ops.tpu.final_stage import TpuFinalStageExec
    from ballista_tpu.plan.physical import RepartitionExec, TaskContext

    rng = np.random.default_rng(47)
    n = 6000
    t = pa.table({
        "g": rng.integers(0, 100, n).astype("int64"),
        "v": rng.integers(0, 10, n).astype("int64"),
    })
    sql = "SELECT g, sum(v) AS s FROM t GROUP BY g"
    cfg = BallistaConfig({EXECUTOR_ENGINE: "tpu", TPU_MIN_ROWS: 0})
    ctx = SessionContext(cfg)
    ctx.register_arrow_table("t", t, partitions=2)
    phys = maybe_compile_tpu(ctx.create_physical_plan(ctx.sql(sql).plan), cfg)
    stages = [nd for nd in _walk(phys) if isinstance(nd, TpuFinalStageExec)]
    assert len(stages) == 1, phys.display()
    fs = stages[0]
    if not isinstance(fs.child, RepartitionExec):
        pytest.skip("local plan no longer places a hash repartition here")
    k = fs.output_partition_count()
    assert k > 1  # the advertised partition count is the repartition's K
    tc = TaskContext(cfg)
    rows_by_part = [
        sum(b.num_rows for b in fs.execute(p, tc)) for p in range(k)
    ]
    assert fs.tpu_count == 1 and fs.fallback_count == 0
    assert rows_by_part[0] == 100  # every group lands on partition 0
    assert all(r == 0 for r in rows_by_part[1:])


def test_final_stage_concurrent_contexts_share_cache_entry():
    """Two sessions with identical stage shapes share one compile-cache
    entry; concurrent execution is serialized by the per-entry run lock and
    both answers are exact (pins the retrace/`cell`-race guard)."""
    import threading

    from ballista_tpu.client.context import SessionContext
    from ballista_tpu.engine.tpu_engine import maybe_compile_tpu
    from ballista_tpu.ops.tpu.final_stage import TpuFinalStageExec
    from ballista_tpu.plan.physical import TaskContext

    rng = np.random.default_rng(53)
    n = 8000
    sql = "SELECT g, sum(v) AS s FROM t GROUP BY g ORDER BY s DESC LIMIT 7"

    def make_table(seed):
        r = np.random.default_rng(seed)
        return pa.table({
            "g": r.integers(0, 60, n).astype("int64"),
            "v": r.integers(0, 100, n).astype("int64"),
        })

    tables = [make_table(s) for s in (1, 2)]
    outs: dict = {}
    errs: list = []

    def run(i):
        try:
            cfg = BallistaConfig({EXECUTOR_ENGINE: "tpu", TPU_MIN_ROWS: 0})
            ctx = SessionContext(cfg)
            ctx.register_arrow_table("t", tables[i], partitions=2)
            phys = maybe_compile_tpu(
                ctx.create_physical_plan(ctx.sql(sql).plan), cfg)
            stages = [nd for nd in _walk(phys)
                      if isinstance(nd, TpuFinalStageExec)]
            assert len(stages) == 1
            tc = TaskContext(cfg)
            batches = []
            for p in range(phys.output_partition_count()):
                batches.extend(phys.execute(p, tc))
            assert stages[0].tpu_count == 1 and stages[0].fallback_count == 0
            outs[i] = pa.Table.from_batches(
                [b for b in batches if b.num_rows], phys.schema())
        except Exception as e:  # noqa: BLE001 — surfaced below
            errs.append(e)

    threads = [threading.Thread(target=run, args=(i,)) for i in range(2)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert not errs, errs
    for i in range(2):
        got = outs[i].to_pandas()
        df = tables[i].to_pandas().groupby("g", as_index=False).v.sum()
        exp = df.sort_values(["v", "g"], ascending=[False, True]).head(7)
        assert got.g.tolist() == exp.g.tolist()
        assert got.s.tolist() == exp.v.tolist()


def test_final_stage_distributed_standalone():
    """The staged (distributed) path: a standalone cluster on the tpu
    engine produces the same q3-class answer as the cpu engine."""
    from ballista_tpu.client.context import SessionContext

    rng = np.random.default_rng(41)
    n = 15000
    t = pa.table({
        "g": rng.integers(0, 400, n).astype("int64"),
        "v": np.round(rng.random(n) * 100, 2),
    })
    sql = "SELECT g, sum(v) AS s FROM t GROUP BY g ORDER BY s DESC LIMIT 10"
    results = {}
    for engine in ("tpu", "cpu"):
        cfg = BallistaConfig({EXECUTOR_ENGINE: engine, TPU_MIN_ROWS: 0})
        ctx = SessionContext.standalone(cfg)
        try:
            ctx.register_arrow_table("t", t, partitions=2)
            results[engine] = ctx.sql(sql).collect()
        finally:
            ctx.shutdown()
    tp, cp = results["tpu"].to_pandas(), results["cpu"].to_pandas()
    assert tp.g.tolist() == cp.g.tolist()
    assert np.allclose(tp.s.values, cp.s.values)


def test_declined_final_stage_reuses_materialized_child():
    """When the final stage declines the device (e.g. merged input below
    TPU_MIN_ROWS), its CPU fallback must aggregate the child output the
    device attempt ALREADY materialized — never re-execute the child
    subtree (which would silently re-scan the whole input on the host:
    the 100x-overhead bug the round-5 profile pinned). The child device
    stage must therefore report zero CPU fallbacks."""
    from ballista_tpu.client.context import SessionContext
    from ballista_tpu.engine.tpu_engine import maybe_compile_tpu
    from ballista_tpu.ops.tpu.final_stage import TpuFinalStageExec
    from ballista_tpu.ops.tpu.stage_compiler import TpuStageExec
    from ballista_tpu.plan.physical import TaskContext

    rng = np.random.default_rng(7)
    n = 40000
    t = pa.table({
        "g": rng.integers(0, 3, n).astype("int64"),  # 3 groups << min_rows
        "v": rng.integers(0, 1000, n).astype("int64"),
    })
    sql = "SELECT g, sum(v) AS s, count(*) AS c FROM t GROUP BY g ORDER BY g"
    # min_rows low enough for the 40k-row scan stage to take the device,
    # high enough that the handful of merged partial rows decline it
    cfg = BallistaConfig({EXECUTOR_ENGINE: "tpu", TPU_MIN_ROWS: 100})
    ctx = SessionContext(cfg)
    ctx.register_arrow_table("t", t, partitions=4)
    phys = maybe_compile_tpu(ctx.create_physical_plan(ctx.sql(sql).plan), cfg)
    finals = [nd for nd in _walk(phys) if isinstance(nd, TpuFinalStageExec)]
    stages = [nd for nd in _walk(phys) if isinstance(nd, TpuStageExec)]
    assert finals and stages, phys.display()
    tc = TaskContext(cfg)
    rows = []
    for p in range(phys.output_partition_count()):
        for b in phys.execute(p, tc):
            rows.extend(b.to_pylist())
    # the final stage declined (device roundtrip not worth 3 rows) ...
    assert all(f.tpu_count == 0 and f.fallback_count > 0 for f in finals)
    # ... reused the materialized child output instead of re-scanning (the
    # child executed exactly once, on device, with no host fallback) ...
    assert all(s.fallback_count == 0 for s in stages), \
        "child stage re-executed on the host after its results were consumed"
    assert all(s.tpu_count == 1 for s in stages), \
        "child stage re-dispatched: fallback did not reuse the materialized tables"
    # ... and RELEASED the pinned host copy once the last expected fallback
    # partition was served (it must not stay resident for the plan's lifetime)
    assert all(f._mat_node is None and f._mat_input is None for f in finals), \
        "materialized child copy still pinned after serving"
    # correctness against pandas
    import pandas as pd

    want = (t.to_pandas().groupby("g", as_index=False)
            .agg(s=("v", "sum"), c=("v", "size")).sort_values("g"))
    got = pd.DataFrame(rows).sort_values("g")
    assert got.g.tolist() == want.g.tolist()
    assert got.s.tolist() == want.s.tolist()
    assert got.c.tolist() == want.c.tolist()


def test_consumed_device_results_rerun_not_host_fallback():
    """Re-executing a partition whose device result was already consumed
    re-dispatches the (hot) device path once and serves every partition
    from it — it must not degrade to a host re-scan of the subtree."""
    from ballista_tpu.client.context import SessionContext
    from ballista_tpu.engine.tpu_engine import maybe_compile_tpu
    from ballista_tpu.ops.tpu.stage_compiler import TpuStageExec
    from ballista_tpu.plan.physical import TaskContext

    rng = np.random.default_rng(9)
    n = 30000
    t = pa.table({
        "g": rng.integers(0, 8, n).astype("int64"),
        "v": rng.integers(0, 1000, n).astype("int64"),
    })
    sql = "SELECT g, sum(v) AS s FROM t GROUP BY g"
    cfg = BallistaConfig({EXECUTOR_ENGINE: "tpu", TPU_MIN_ROWS: 0})
    ctx = SessionContext(cfg)
    ctx.register_arrow_table("t", t, partitions=3)
    phys = maybe_compile_tpu(ctx.create_physical_plan(ctx.sql(sql).plan), cfg)
    stages = [nd for nd in _walk(phys) if isinstance(nd, TpuStageExec)]
    assert stages
    st = stages[0]
    tc = TaskContext(cfg)
    first = [[b.to_pydict() for b in st.execute(p, tc)]
             for p in range(st.output_partition_count())]
    runs_after_first = st.tpu_count
    assert runs_after_first >= 1 and st.fallback_count == 0
    # consume AGAIN: one extra device dispatch serves all partitions
    second = [[b.to_pydict() for b in st.execute(p, tc)]
              for p in range(st.output_partition_count())]
    assert st.fallback_count == 0, "consumed re-read degraded to host fallback"
    assert st.tpu_count == runs_after_first + 1, \
        "re-read should cost exactly one re-dispatch"
    assert first == second
