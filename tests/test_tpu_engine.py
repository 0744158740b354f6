"""TPU engine tests (run on jax CPU backend via conftest env).

- hash twin parity: jax hash64 must be bit-identical to the numpy hasher
  (the shuffle wire contract)
- TPC-H correctness with engine=tpu (device stages + per-subtree fallback)
- stage compilation actually happens for q1-shaped pipelines
"""

import numpy as np
import pyarrow as pa
import pytest

from ballista_tpu.config import (
    BallistaConfig,
    EXECUTOR_ENGINE,
    TPU_MIN_ROWS,
)
from ballista_tpu.testing.reference import compare_results, run_reference

from .conftest import tpch_query


@pytest.fixture()
def tpu_ctx(tpch_dir):
    from ballista_tpu.client.context import SessionContext
    from ballista_tpu.testing.tpchgen import register_tpch

    cfg = BallistaConfig({EXECUTOR_ENGINE: "tpu", TPU_MIN_ROWS: 0})
    ctx = SessionContext(cfg)
    register_tpch(ctx, tpch_dir)
    return ctx


def test_hash64_parity_with_numpy():
    from ballista_tpu.ops.hashing import splitmix64, hash_combine
    from ballista_tpu.ops.tpu.kernels import hash64, hash_combine_jax
    from ballista_tpu.ops.tpu.runtime import ensure_jax

    jax = ensure_jax()
    jnp = jax.numpy
    x = np.array([0, 1, 2, 12345678901234, 2**63 - 1], dtype=np.uint64)
    np_h = splitmix64(x)
    jax_h = np.asarray(hash64(jnp.asarray(x)))
    assert (np_h == jax_h).all()
    np_c = hash_combine(np_h, np_h[::-1].copy())
    jax_c = np.asarray(hash_combine_jax(jnp.asarray(np_h), jnp.asarray(np_h[::-1].copy())))
    assert (np_c == jax_c).all()


def test_q1_compiles_to_tpu_stage(tpu_ctx):
    df = tpu_ctx.sql(tpch_query(1))
    phys = tpu_ctx.create_physical_plan(df.plan)
    from ballista_tpu.engine.tpu_engine import maybe_compile_tpu

    compiled = maybe_compile_tpu(phys, tpu_ctx.config)
    assert "TpuStageExec" in compiled.display()


@pytest.mark.parametrize("q", [1, 3, 5, 6, 10, 12, 14, 18, 19])
def test_tpch_tpu_engine(q, tpu_ctx, tpch_ref_tables):
    eng = tpu_ctx.sql(tpch_query(q)).collect()
    ref = run_reference(q, tpch_ref_tables)
    problems = compare_results(eng, ref, q)
    assert not problems, "\n".join(problems)


# -- the sorted path orders its live rows at a tier of the stage's row slots --
# (the share of the table's rows that is alive, the divisor of the ladder the
# stage should take: a sixty-fourth of its row slots where that holds the live
# rows, else all of them). The tier is chosen by the data alone — the same live
# rows among more or fewer dead ones — never by a switch.
LIVE_SHARES = [
    pytest.param(None, 1, id="unfiltered"),
    pytest.param(0.10, 1, id="tenth_alive"),
    pytest.param(0.005, 64, id="half_percent_alive"),
]


def _among_dead_rows(live: pa.Table, share, seed=3) -> pa.Table:
    """`live` with an `alive` column of ones and, given a `share`, among so
    many rows of alive = 0 that the live ones are that share of the table,
    in their own order. A dead row is a copy of a live one: let past the
    filter it would join a real group and move its aggregates."""
    n = live.num_rows
    total = n if share is None else int(round(n / share))
    rng = np.random.default_rng(seed)
    is_live = np.zeros(total, bool)
    is_live[rng.choice(total, n, replace=False)] = True
    take = rng.integers(0, n, total)
    take[is_live] = np.arange(n)
    tbl = live.take(pa.array(take)).append_column(
        "alive", pa.array(is_live.astype("int64")))
    return _in_batches(tbl, 4)


def _in_batches(tbl: pa.Table, k: int) -> pa.Table:
    """`tbl` as k equal batches: a memory table deals its batches out to its
    partitions, and one batch would be one partition's rows."""
    return pa.Table.from_batches(tbl.to_batches(max_chunksize=-(-tbl.num_rows // k)))


def _sorted_stage_counts() -> tuple:
    """(table shape, live rows, row slots ordered) as the ONE stage that took
    the sorted path since the last `RUN_STATS.clear()` recorded them."""
    import ballista_tpu.ops.tpu.stage_compiler as sc

    recs = [r for r in sc.RUN_STATS.stages().values() if "sorted_rows_ordered" in r]
    assert len(recs) == 1, "exactly one stage should have taken the sorted path"
    return (recs[0]["table_shape"], recs[0]["sorted_rows_live"],
            recs[0]["sorted_rows_ordered"])


def _assert_sorted_tier(divisor, live_rows, lanes=1):
    """The sorted-path stage found `live_rows` alive and ordered them at its
    row slots (P x N x `lanes`; None: some number of expansion lanes over
    one) over `divisor`."""
    (P, N), live, ordered = counts = _sorted_stage_counts()
    assert live == live_rows, counts
    got_lanes, rest = divmod(ordered * divisor, P * N)
    assert rest == 0 and (got_lanes > 1 if lanes is None else got_lanes == lanes), counts


def test_large_domain_groupby_on_device(tpu_ctx):
    """q3's group-by (l_orderkey × build-side keys — thousands of groups)
    must take the sort-based segmented-reduction path, not fall back."""
    import ballista_tpu.ops.tpu.stage_compiler as sc
    from ballista_tpu.engine.tpu_engine import maybe_compile_tpu
    from ballista_tpu.plan.physical import TaskContext

    phys = maybe_compile_tpu(
        tpu_ctx.create_physical_plan(tpu_ctx.sql(tpch_query(3)).plan), tpu_ctx.config
    )
    stages = [n for n in _walk(phys) if isinstance(n, sc.TpuStageExec)]
    assert stages
    ctx = TaskContext(tpu_ctx.config)
    for p in range(phys.output_partition_count()):
        list(phys.execute(p, ctx))
    assert sum(s.tpu_count for s in stages) >= 1
    assert sum(s.fallback_count for s in stages) == 0


def _min_max_sum_count_table(share):
    rng = np.random.default_rng(7)
    n = 3_000
    return _among_dead_rows(pa.table({
        "k": rng.integers(0, 3000, n),
        "price": np.round(rng.uniform(1, 100, n), 2),   # money (int64 cents)
        "weight": rng.uniform(0.0, 1.0, n),              # true f64
        "qty": rng.integers(1, 50, n),
    }), share)


MIN_MAX_SUM_COUNT_SQL = (
    "SELECT k, sum(price) AS s, sum(weight) AS w, count(*) AS c, "
    "min(qty) AS mn, max(qty) AS mx FROM t WHERE qty > 5 AND alive = 1 "
    "GROUP BY k ORDER BY k"
)


@pytest.mark.parametrize("share,divisor", LIVE_SHARES)
def test_sorted_path_min_max_sum_count_oracle(share, divisor):
    """Synthetic large-domain aggregation: every agg func through the
    sorted path must match pandas (int money math exact, f64 sums via the
    segmented scan) — and must actually run on the device path, at the tier
    its live rows call for."""
    import ballista_tpu.ops.tpu.stage_compiler as sc
    from ballista_tpu.client.context import SessionContext
    from ballista_tpu.engine.tpu_engine import maybe_compile_tpu
    from ballista_tpu.plan.physical import TaskContext

    tbl = _min_max_sum_count_table(share)
    cfg = BallistaConfig({EXECUTOR_ENGINE: "tpu", TPU_MIN_ROWS: 0})
    ctx = SessionContext(cfg)
    ctx.register_arrow_table("t", tbl, partitions=4)
    sql = MIN_MAX_SUM_COUNT_SQL
    sc.RUN_STATS.clear()
    out = ctx.sql(sql).collect().to_pandas()
    df = tbl.to_pandas()
    df = df[(df.qty > 5) & (df.alive == 1)]
    _assert_sorted_tier(divisor, len(df))
    g = (
        df.groupby("k")
        .agg(s=("price", "sum"), w=("weight", "sum"), c=("price", "size"),
             mn=("qty", "min"), mx=("qty", "max"))
        .reset_index()
        .sort_values("k")
        .reset_index(drop=True)
    )
    assert len(out) == len(g)
    assert (out.k.values == g.k.values).all()
    assert np.allclose(out.s.values, g.s.values, atol=1e-9)
    assert np.allclose(out.w.values, g.w.values, rtol=1e-12)
    assert (out.c.values == g.c.values).all()
    assert (out.mn.values == g.mn.values).all()
    assert (out.mx.values == g.mx.values).all()

    # the oracle match must come from the DEVICE path, not a silent fallback
    phys = maybe_compile_tpu(ctx.create_physical_plan(ctx.sql(sql).plan), cfg)
    stages = [nd for nd in _walk(phys) if isinstance(nd, sc.TpuStageExec)]
    assert stages
    tc = TaskContext(cfg)
    for p in range(phys.output_partition_count()):
        list(phys.execute(p, tc))
    assert sum(s.tpu_count for s in stages) >= 1
    assert sum(s.fallback_count for s in stages) == 0


def test_sorted_path_tiers_agree_bit_for_bit():
    """The same live rows among no, nine times and 199 times as many dead
    ones are ordered at every slot twice and at the lower tier once, and
    every exact kind (keys, money sums in int64 cents, counts, integer min /
    max) comes out the same to the bit; float sums to their rounding (a
    segment's association depends on where it lands)."""
    import ballista_tpu.ops.tpu.stage_compiler as sc
    from ballista_tpu.client.context import SessionContext

    outs = []
    for share, divisor in (case.values for case in LIVE_SHARES):
        ctx = SessionContext(BallistaConfig({EXECUTOR_ENGINE: "tpu", TPU_MIN_ROWS: 0}))
        ctx.register_arrow_table("t", _min_max_sum_count_table(share), partitions=4)
        sc.RUN_STATS.clear()
        outs.append(ctx.sql(MIN_MAX_SUM_COUNT_SQL).collect().to_pandas())
        _assert_sorted_tier(divisor, int(outs[-1].c.sum()))
    for other in outs[1:]:
        for col in ("k", "s", "c", "mn", "mx"):
            assert (other[col].values == outs[0][col].values).all(), col
        assert np.allclose(other.w.values, outs[0].w.values, rtol=1e-12)


# (live rows of the stage's 8192 row slots, the slots they are ordered at): none
# alive; one below the lower tier's capacity, exactly at it and one above it;
# nearly all alive
@pytest.mark.parametrize("live,ordered", [
    (0, 128), (127, 128), (128, 128), (129, 8192), (7999, 8192)])
def test_sorted_path_live_rows_at_a_tier_edge(live, ordered):
    """Every live row its own group, so a tier's group capacity fills with
    its row capacity."""
    rng = np.random.default_rng(23)
    n = 8000  # two partitions of 4000 rows: 2 x 4096 row slots
    alive = np.zeros(n, dtype="int64")
    alive[rng.choice(n, live, replace=False)] = 1
    tbl = _in_batches(pa.table({"k": rng.permutation(n) * 7, "v": rng.integers(1, 100, n),
                                "alive": alive}), 2)
    sql = "SELECT k, sum(v) AS s, count(*) AS c FROM t WHERE alive = 1 GROUP BY k ORDER BY k"
    tpu, cpu = _device_oracle(sql, {"t": tbl})
    assert _sorted_stage_counts() == ([2, 4096], live, ordered)
    want = tbl.to_pandas()
    want = want[want.alive == 1].sort_values("k")
    tp = tpu.to_pandas()
    assert tpu.num_rows == cpu.num_rows == live
    assert tp.k.tolist() == want.k.tolist()
    assert tp.s.tolist() == want.v.tolist()
    assert tp.c.tolist() == [1] * live


def test_sorted_path_group_overflow_reruns_on_cpu_engine():
    """A stage cannot overflow its groups: its capacity is pow2 of its row
    slots. What bounds it is HBM admission, which prices the program's
    [C] output lanes and ordering scratch beside the table: with the budget
    one byte under that working set (and over the table and its LUTs,
    which alone would have been admitted) the stage declines BEFORE it
    dispatches — no count is fetched, no group decoded — and the CPU
    engine answers."""
    import ballista_tpu.ops.tpu.stage_compiler as sc
    from ballista_tpu.config import TPU_HBM_BUDGET_BYTES

    n, live = 8000, 8000
    tbl = _in_batches(
        pa.table({"k": np.arange(n) * 3, "w": np.arange(n) % 11,
                  "alive": np.ones(n, dtype="int64")}), 2)
    sql = "SELECT k, sum(w) AS s FROM t WHERE alive = 1 GROUP BY k ORDER BY k"
    _device_oracle(sql, {"t": tbl})
    rec, = (r for t, r in sc.RUN_STATS.stages().items()
            if t.startswith("stage_") and "sorted_rows_ordered" in r)
    assert rec["sorted_groups"] == live <= rec["sorted_capacity"] == 8192
    working = int(rec["hbm_plan_reason"].split("working set ")[1].split(" B")[0])
    assert working - 1 >= rec["device_bytes"] + 8192 * 9 * 2  # the [C] lanes are in it

    before = sc.STAGE_OUTCOMES.snapshot()
    tpu, cpu = _device_oracle(sql, {"t": tbl}, {TPU_HBM_BUDGET_BYTES: working - 1},
                              expect_device=False)
    after = sc.STAGE_OUTCOMES.snapshot()
    assert after["declined"] > before["declined"]
    assert any("hbm plan: working set" in str(r) for r in after["recent"])
    assert not any("group capacity overflow" in str(r) for r in after["recent"])
    rec, = (r for t, r in sc.RUN_STATS.stages().items() if t.startswith("stage_"))
    assert rec["hbm_plan"] == "cpu_demote" and rec["sorted_capacity"] == 8192
    assert "sorted_groups" not in rec and "exec_s" not in rec  # never dispatched
    assert tpu.num_rows == live and tpu.equals(cpu)


def test_tpu_stage_actually_ran(tpu_ctx):
    """The q1 pipeline must execute on the device path, not fall back."""
    import ballista_tpu.ops.tpu.stage_compiler as sc

    df = tpu_ctx.sql(tpch_query(1))
    phys = tpu_ctx.create_physical_plan(df.plan)
    from ballista_tpu.engine.tpu_engine import maybe_compile_tpu
    from ballista_tpu.plan.physical import TaskContext

    compiled = maybe_compile_tpu(phys, tpu_ctx.config)
    stages = [n for n in _walk(compiled) if isinstance(n, sc.TpuStageExec)]
    assert stages
    ctx = TaskContext(tpu_ctx.config)
    for p in range(compiled.output_partition_count()):
        list(compiled.execute(p, ctx))
    assert stages[0].tpu_count >= 1
    assert stages[0].fallback_count == 0


def test_q5_join_pipeline_on_device(tpu_ctx, tpch_ref_tables):
    """q5's 4-join probe chain must compile and run on the device path."""
    import ballista_tpu.ops.tpu.stage_compiler as sc
    from ballista_tpu.engine.tpu_engine import maybe_compile_tpu
    from ballista_tpu.plan.physical import TaskContext

    phys = maybe_compile_tpu(
        tpu_ctx.create_physical_plan(tpu_ctx.sql(tpch_query(5)).plan), tpu_ctx.config
    )
    stages = [n for n in _walk(phys) if isinstance(n, sc.TpuStageExec)]
    assert stages
    joins = [op for s in stages for op in s.ops if type(op).__name__ == "HashJoinExec"]
    assert len(joins) >= 3
    ctx = TaskContext(tpu_ctx.config)
    for p in range(phys.output_partition_count()):
        list(phys.execute(p, ctx))
    assert sum(s.tpu_count for s in stages) >= 1
    assert sum(s.fallback_count for s in stages) == 0


def test_expansion_join_on_device(tpu_ctx, tpch_ref_tables):
    """q12's build side (filtered lineitem) has duplicate join keys: the
    expansion-join lanes must keep it on the device path, correctly."""
    import ballista_tpu.ops.tpu.stage_compiler as sc
    from ballista_tpu.engine.tpu_engine import maybe_compile_tpu
    from ballista_tpu.plan.physical import TaskContext

    eng = tpu_ctx.sql(tpch_query(12)).collect()
    problems = compare_results(eng, run_reference(12, tpch_ref_tables), 12)
    assert not problems, "\n".join(problems)

    phys = maybe_compile_tpu(
        tpu_ctx.create_physical_plan(tpu_ctx.sql(tpch_query(12)).plan), tpu_ctx.config
    )
    stages = [n for n in _walk(phys) if isinstance(n, sc.TpuStageExec)]
    assert stages
    ctx = TaskContext(tpu_ctx.config)
    for p in range(phys.output_partition_count()):
        list(phys.execute(p, ctx))
    assert sum(s.tpu_count for s in stages) >= 1
    assert sum(s.fallback_count for s in stages) == 0


@pytest.mark.parametrize("share,divisor", LIVE_SHARES)
def test_expansion_join_with_large_domain_groupby(share, divisor):
    """Duplicate build keys AND a large int group domain: expansion lanes
    concatenate into the sorted segmented reduction, whose live rows are the
    matched (row, lane) pairs. Oracle = pandas."""
    import ballista_tpu.ops.tpu.stage_compiler as sc
    from ballista_tpu.client.context import SessionContext
    from ballista_tpu.engine.tpu_engine import maybe_compile_tpu
    from ballista_tpu.plan.physical import TaskContext

    rng = np.random.default_rng(11)
    n_fact = 3_600
    fact = _among_dead_rows(pa.table({
        "fk": rng.integers(0, 500, n_fact),     # join key (dense)
        "gk": rng.integers(0, 4000, n_fact),    # large group domain
        "v": rng.integers(1, 100, n_fact),
    }), share)
    dk = np.repeat(np.arange(500), rng.integers(2, 5, 500))  # 2 to 4 dups per key
    dim = pa.table({"dk": dk, "w": rng.integers(1, 10, len(dk))})
    cfg = BallistaConfig({EXECUTOR_ENGINE: "tpu", TPU_MIN_ROWS: 0})
    ctx = SessionContext(cfg)
    ctx.register_arrow_table("fact", fact, partitions=4)
    ctx.register_arrow_table("dim", dim, partitions=1)
    sql = (
        "SELECT gk, sum(v * w) AS s, count(*) AS c FROM fact, dim "
        "WHERE fk = dk AND alive = 1 GROUP BY gk ORDER BY gk"
    )
    sc.RUN_STATS.clear()
    out = ctx.sql(sql).collect().to_pandas()
    df = fact.to_pandas()
    df = df[df.alive == 1].merge(dim.to_pandas(), left_on="fk", right_on="dk")
    df["p"] = df.v * df.w
    if [r for r in sc.RUN_STATS.stages().values() if "table_shape" in r]:
        _assert_sorted_tier(divisor, len(df), lanes=None)  # collect_left: on the device
    g = (
        df.groupby("gk").agg(s=("p", "sum"), c=("p", "size"))
        .reset_index().sort_values("gk").reset_index(drop=True)
    )
    assert len(out) == len(g)
    assert (out.gk.values == g.gk.values).all()
    assert (out.s.values == g.s.values).all()
    assert (out.c.values == g.c.values).all()

    phys = maybe_compile_tpu(ctx.create_physical_plan(ctx.sql(sql).plan), cfg)
    stages = [nd for nd in _walk(phys) if isinstance(nd, sc.TpuStageExec)]
    if stages:  # planner may pick partitioned mode; if collect_left, no fallback
        tc = TaskContext(cfg)
        for p in range(phys.output_partition_count()):
            list(phys.execute(p, tc))
        assert sum(s.fallback_count for s in stages) == 0


def test_collective_exchange_mesh_execution(tpch_dir, tpch_ref_tables):
    """ballista.tpu.collective.exchange: the stage's device table shards by
    partition across the (virtual 8-device) mesh and GSPMD inserts the
    collectives — results identical to single-device and the CPU oracle."""
    import jax

    import ballista_tpu.ops.tpu.stage_compiler as sc
    from ballista_tpu.client.context import SessionContext
    from ballista_tpu.config import TPU_COLLECTIVE_EXCHANGE
    from ballista_tpu.engine.tpu_engine import maybe_compile_tpu
    from ballista_tpu.plan.physical import TaskContext
    from ballista_tpu.testing.tpchgen import register_tpch

    if len(jax.devices()) < 2:
        pytest.skip("needs a multi-device backend")
    cfg = BallistaConfig({
        EXECUTOR_ENGINE: "tpu", TPU_MIN_ROWS: 0, TPU_COLLECTIVE_EXCHANGE: True,
    })
    ctx = SessionContext(cfg)
    register_tpch(ctx, tpch_dir)
    # q1: unrolled path; q3: sorted path with a join — both through the mesh
    for q in (1, 3):
        eng = ctx.sql(tpch_query(q)).collect()
        problems = compare_results(eng, run_reference(q, tpch_ref_tables), q)
        assert not problems, "\n".join(problems)

    phys = maybe_compile_tpu(ctx.create_physical_plan(ctx.sql(tpch_query(1)).plan), cfg)
    stages = [n for n in _walk(phys) if isinstance(n, sc.TpuStageExec)]
    assert stages
    tc = TaskContext(cfg)
    for p in range(phys.output_partition_count()):
        list(phys.execute(p, tc))
    assert stages[0].tpu_count >= 1 and stages[0].fallback_count == 0
    # the cached device table must actually be sharded across the mesh
    sharded = [
        dt for key, dt in sc.DEVICE_CACHE._cache.items()
        if any(len(c.sharding.device_set) == len(jax.devices()) for c in dt.cols)
    ]
    assert sharded, "no mesh-sharded device table in cache"


def test_money_encoding_exact():
    from ballista_tpu.ops.tpu.columnar import encode_column

    vals = pa.array([1.01, 2.50, 999999.99, 0.0])
    dc = encode_column(vals)
    assert dc.kind == "money"
    assert dc.scale == 2
    assert list(np.asarray(dc.data, dtype=np.int64)) == [101, 250, 99999999, 0]
    # non-fixed-point floats stay f64
    dc2 = encode_column(pa.array([1.001, 2.5]))
    assert dc2.kind == "f64"


def _walk(node):
    yield node
    for c in node.children():
        yield from _walk(c)


@pytest.mark.parametrize("share,divisor", LIVE_SHARES)
def test_device_side_shuffle_routing(tmp_path, share, divisor):
    """ROADMAP device-side shuffle write: the sorted path emits a __pid
    column (bit-exact hash twin, computed at the tier's group capacity), the
    shuffle writer consumes it instead of host hashing, and written buckets
    match host routing exactly."""
    import glob
    import json

    import pyarrow.ipc as ipc
    import pyarrow.parquet as pq

    import ballista_tpu.ops.tpu.stage_compiler as sc
    from ballista_tpu.client.context import SessionContext
    from ballista_tpu.engine.tpu_engine import maybe_compile_tpu
    from ballista_tpu.ops.hashing import partition_indices
    from ballista_tpu.plan.physical import TaskContext
    from ballista_tpu.scheduler.planner import DistributedPlanner
    from ballista_tpu.shuffle import paths as sp

    rng = np.random.default_rng(5)
    n = 3_000
    tbl = _among_dead_rows(pa.table({
        "k": rng.integers(0, 5000, n),
        "v": rng.integers(1, 100, n),
    }), share)
    pq.write_table(tbl, str(tmp_path / "t.parquet"))
    cfg = BallistaConfig({EXECUTOR_ENGINE: "tpu", TPU_MIN_ROWS: 0})
    ctx = SessionContext(cfg)
    ctx.register_parquet("t", str(tmp_path / "t.parquet"))
    sql = "select k, sum(v) s from t where v > 10 and alive = 1 group by k"
    phys = ctx.create_physical_plan(ctx.sql(sql).plan)
    stages = DistributedPlanner("jpid").plan_query_stages(phys)
    stage1 = stages[0]
    compiled = maybe_compile_tpu(stage1.plan, cfg)
    tpu = [nd for nd in _walk(compiled) if isinstance(nd, sc.TpuStageExec)]
    assert tpu and tpu[0].emit_pid is not None

    work = str(tmp_path / "work")
    tc = TaskContext(cfg, task_id="t0", work_dir=work)
    sc.RUN_STATS.clear()
    for p in range(stage1.partitions):
        list(compiled.execute(p, tc))
    assert tpu[0].pid_emitted >= 1
    assert tpu[0].fallback_count == 0
    want = tbl.to_pandas()
    want = want[(want.v > 10) & (want.alive == 1)]
    _assert_sorted_tier(divisor, len(want))

    checked = seen = 0
    for f in glob.glob(f"{work}/jpid/1/*.arrow"):
        idx = json.load(open(sp.index_path(f)))
        for pid_s, entry in idx.items():
            off, length = entry[0], entry[1]
            with open(f, "rb") as fh:
                fh.seek(off)
                buf = fh.read(length)
            tblx = ipc.open_stream(pa.BufferReader(buf)).read_all()
            assert "__pid" not in tblx.column_names
            if tblx.num_rows:
                host = partition_indices(
                    [tblx.column("k").combine_chunks()], stage1.output_partitions
                )
                assert (host == int(pid_s)).all()
                checked += 1
                seen += tblx.num_rows
    assert checked > 0
    assert seen == want.k.nunique()  # every group routed, none twice


def test_q22_string_fn_filter_on_device(tpu_ctx, tpch_ref_tables):
    """substring(c_phone,..) IN (...) composes into the dictionary LUT:
    q22's scalar-subquery stage runs on device with a correct result."""
    import ballista_tpu.ops.tpu.stage_compiler as sc
    from ballista_tpu.engine.tpu_engine import maybe_compile_tpu
    from ballista_tpu.plan.physical import TaskContext

    eng = tpu_ctx.sql(tpch_query(22)).collect()
    problems = compare_results(eng, run_reference(22, tpch_ref_tables), 22)
    assert not problems, "\n".join(problems)

    phys = maybe_compile_tpu(
        tpu_ctx.create_physical_plan(tpu_ctx.sql(tpch_query(22)).plan), tpu_ctx.config
    )
    stages = [n for n in _walk(phys) if isinstance(n, sc.TpuStageExec)]
    assert stages
    ctx = TaskContext(tpu_ctx.config)
    for p in range(phys.output_partition_count()):
        list(phys.execute(p, ctx))
    assert sum(s.tpu_count for s in stages) >= 1
    assert sum(s.fallback_count for s in stages) == 0


def test_semi_and_anti_joins_on_device(tmp_path):
    """IN / NOT IN subqueries (decorrelated to right_semi / right_anti
    collect_left joins) run on device: the probe's match mask is the
    filter — no build gathers, no expansion lanes, duplicate membership
    keys fine."""
    import pyarrow.parquet as pq

    import ballista_tpu.ops.tpu.stage_compiler as sc
    from ballista_tpu.client.context import SessionContext
    from ballista_tpu.engine.tpu_engine import maybe_compile_tpu
    from ballista_tpu.plan.physical import TaskContext

    rng = np.random.default_rng(8)
    n = 30_000
    pq.write_table(pa.table({
        "k": rng.integers(0, 5000, n), "g": rng.choice(["a", "b", "c"], n),
        "v": rng.integers(1, 100, n),
    }), str(tmp_path / "fact.parquet"))
    # duplicate count 20 > MAX_JOIN_DUP: membership joins must not trip the
    # expansion-lane cap (semi/anti never unroll lanes)
    pq.write_table(
        pa.table({"mk": np.repeat(rng.choice(5000, 800, replace=False), 20)}),
        str(tmp_path / "member.parquet"),
    )
    cfg = BallistaConfig({EXECUTOR_ENGINE: "tpu", TPU_MIN_ROWS: 0})
    ctx = SessionContext(cfg)
    ctx.register_parquet("fact", str(tmp_path / "fact.parquet"))
    ctx.register_parquet("member", str(tmp_path / "member.parquet"))
    f = pq.read_table(str(tmp_path / "fact.parquet")).to_pandas()
    m = set(pq.read_table(str(tmp_path / "member.parquet")).to_pandas().mk)
    for sql, sel in [
        ("select g, sum(v) s, count(*) c from fact where k in (select mk from member) "
         "group by g order by g", f[f.k.isin(m)]),
        ("select g, sum(v) s, count(*) c from fact where k not in (select mk from member) "
         "group by g order by g", f[~f.k.isin(m)]),
    ]:
        out = ctx.sql(sql).collect().to_pandas()
        g = sel.groupby("g").agg(s=("v", "sum"), c=("v", "size")).reset_index().sort_values("g")
        assert out.s.tolist() == g.s.tolist()
        assert out.c.tolist() == g.c.tolist()
        phys = maybe_compile_tpu(ctx.create_physical_plan(ctx.sql(sql).plan), cfg)
        stages = [nd for nd in _walk(phys) if isinstance(nd, sc.TpuStageExec)]
        assert stages
        tc = TaskContext(cfg)
        for p in range(phys.output_partition_count()):
            list(phys.execute(p, tc))
        assert sum(s.tpu_count for s in stages) >= 1
        assert sum(s.fallback_count for s in stages) == 0


def test_explain_analyze_shows_device_counters(tpu_ctx):
    """EXPLAIN ANALYZE with engine=tpu analyzes the COMPILED tree: the
    TpuStageExec appears with its device/fallback counters."""
    out = tpu_ctx.sql("explain analyze " + tpch_query(6)).collect().to_pandas()
    body = out[out.plan_type.str.startswith("analyzed")].plan.iloc[0]
    assert "TpuStageExec" in body
    assert "device_runs=1" in body and "cpu_fallbacks=0" in body


# -- NULL-bearing data on the device path (validity planes) -----------------


def _device_oracle(sql: str, tables: dict, cfg_extra=None, expect_device=True):
    """Run `sql` on the tpu engine over `tables`, assert the device path
    actually executed (no silent fallback), and return the result alongside
    the cpu engine's answer for the same query."""
    import ballista_tpu.ops.tpu.stage_compiler as sc
    from ballista_tpu.client.context import SessionContext
    from ballista_tpu.engine.tpu_engine import maybe_compile_tpu
    from ballista_tpu.plan.physical import TaskContext

    results = {}
    sc.RUN_STATS.clear()  # what a caller then reads of the stages is this query's
    for engine in ("tpu", "cpu"):
        cfg = BallistaConfig({EXECUTOR_ENGINE: engine, TPU_MIN_ROWS: 0,
                              **(cfg_extra or {})})
        ctx = SessionContext(cfg)
        for name, tbl in tables.items():
            ctx.register_arrow_table(name, tbl, partitions=2)
        results[engine] = ctx.sql(sql).collect()
        if engine == "tpu" and expect_device:
            phys = maybe_compile_tpu(ctx.create_physical_plan(ctx.sql(sql).plan), cfg)
            stages = [nd for nd in _walk(phys) if isinstance(nd, sc.TpuStageExec)]
            assert stages, "no device stage compiled"
            tc = TaskContext(cfg)
            for p in range(phys.output_partition_count()):
                list(phys.execute(p, tc))
            assert sum(s.tpu_count for s in stages) >= 1
            assert sum(s.fallback_count for s in stages) == 0, "silent cpu fallback"
    return results["tpu"], results["cpu"]


def _null_table(n=8000, seed=11):
    rng = np.random.default_rng(seed)
    k = rng.integers(0, 50, n).astype("int64")
    price = np.round(rng.uniform(1, 100, n), 2)
    qty = rng.integers(1, 50, n).astype("int64")
    flag = rng.integers(0, 2, n).astype(bool)
    null_price = rng.random(n) < 0.3
    null_qty = rng.random(n) < 0.2
    null_k = rng.random(n) < 0.1
    return pa.table({
        "k": pa.array(k, pa.int64()).to_pandas().where(~null_k).astype("Int64").to_numpy(
            dtype=object, na_value=None),
        "price": pa.array(np.where(null_price, np.nan, price)).to_pandas().where(
            ~null_price).to_numpy(dtype=object, na_value=None),
        "qty": pa.array(qty).to_pandas().where(~null_qty).astype("Int64").to_numpy(
            dtype=object, na_value=None),
        "flag": flag,
    })


def test_nullable_filter_and_aggs_on_device():
    """Filters + sum/min/max/count over NULL-bearing columns stay on device
    and agree with the CPU engine (null-strict comparisons, count(x) skips
    nulls, WHERE treats unknown as false)."""
    tbl = _null_table()
    sql = ("SELECT count(*) AS c_all, count(qty) AS c_qty, sum(price) AS s, "
           "min(qty) AS mn, max(qty) AS mx FROM t WHERE price > 10")
    tpu, cpu = _device_oracle(sql, {"t": tbl})
    tp, cp = tpu.to_pandas(), cpu.to_pandas()
    assert tp.c_all[0] == cp.c_all[0]
    assert tp.c_qty[0] == cp.c_qty[0]
    assert abs(tp.s[0] - cp.s[0]) < 1e-6
    assert tp.mn[0] == cp.mn[0] and tp.mx[0] == cp.mx[0]


@pytest.mark.parametrize("share,divisor", LIVE_SHARES)
def test_nullable_group_key_on_device(share, divisor):
    """A nullable GROUP BY key: NULL forms its own group (sorted path's
    null-marker sort operand), matching the CPU engine — the marker lane
    compacted with the value lane at every tier."""
    tbl = _among_dead_rows(_null_table(n=2000), share)
    sql = ("SELECT k, count(*) AS c, sum(price) AS s FROM t "
           "WHERE qty >= 1 AND alive = 1 GROUP BY k ORDER BY k NULLS LAST")
    tpu, cpu = _device_oracle(sql, {"t": tbl})
    tp, cp = tpu.to_pandas(), cpu.to_pandas()
    _assert_sorted_tier(divisor, int(cp.c.sum()))
    assert len(tp) == len(cp)
    # align on key (None sorts last in both by the ORDER BY)
    assert tp.k.isna().tolist() == cp.k.isna().tolist()
    assert tp.k.fillna(-1).tolist() == cp.k.fillna(-1).tolist()
    assert (tp.c.values == cp.c.values).all()
    assert np.allclose(tp.s.fillna(-1).values, cp.s.fillna(-1).values, atol=1e-6)


def test_is_null_predicates_on_device():
    tbl = _null_table()
    sql = ("SELECT count(*) AS c FROM t WHERE qty IS NULL AND price IS NOT NULL")
    tpu, cpu = _device_oracle(sql, {"t": tbl})
    assert tpu.to_pandas().c[0] == cpu.to_pandas().c[0]


@pytest.mark.parametrize("share,divisor", LIVE_SHARES)
def test_all_null_group_aggregates_to_null_on_device(share, divisor):
    """A group whose agg inputs are all NULL yields NULL (not 0 / ±inf) —
    the valid-count companion outputs, compacted with their values."""
    reps = 300  # enough rows for the stage's smallest shape to be mostly alive
    tbl = _among_dead_rows(pa.table({
        "g": pa.array([1, 1, 2, 2, 3] * reps, pa.int64()),
        "v": pa.array([None, None, 5.25, 7.75, None] * reps, pa.float64()),
        "q": pa.array([None, None, 4, 2, 9] * reps, pa.int64()),
    }), share)
    sql = ("SELECT g, sum(v) AS s, min(q) AS mn, max(q) AS mx, count(q) AS c "
           "FROM t WHERE alive = 1 GROUP BY g ORDER BY g")
    tpu, cpu = _device_oracle(sql, {"t": tbl})
    _assert_sorted_tier(divisor, 5 * reps)
    tp, cp = tpu.to_pandas(), cpu.to_pandas()
    assert tp.s.isna().tolist() == cp.s.isna().tolist() == [True, False, True]
    assert tp.mn.isna().tolist() == cp.mn.isna().tolist() == [True, False, False]
    assert float(tp.s[1]) == 13.0 * reps
    assert int(tp.mn[1]) == 2 and int(tp.mx[1]) == 4
    assert int(tp.mn[2]) == 9
    assert tp.c.tolist() == cp.c.tolist() == [0, 2 * reps, reps]


def test_nullable_probe_key_join_on_device():
    """Inner join whose probe key has NULLs: null keys match nothing."""
    rng = np.random.default_rng(5)
    n = 4000
    key = rng.integers(0, 100, n).astype("int64")
    null_key = rng.random(n) < 0.25
    probe = pa.table({
        "fk": pa.array([None if m else int(v) for v, m in zip(key, null_key)], pa.int64()),
        "amt": np.round(rng.uniform(1, 10, n), 2),
    })
    build = pa.table({
        "id": pa.array(np.arange(100), pa.int64()),
        "cat": pa.array([f"c{i % 5}" for i in range(100)]),
    })
    sql = ("SELECT cat, count(*) AS c, sum(amt) AS s FROM probe "
           "JOIN build ON fk = id GROUP BY cat ORDER BY cat")
    tpu, cpu = _device_oracle(sql, {"probe": probe, "build": build})
    tp, cp = tpu.to_pandas(), cpu.to_pandas()
    assert tp.cat.tolist() == cp.cat.tolist()
    assert tp.c.tolist() == cp.c.tolist()
    assert np.allclose(tp.s.values, cp.s.values, atol=1e-6)


def test_right_outer_join_on_device():
    """Right outer join (emit every probe row; NULL build columns on miss)
    through the device chain: unmatched rows ride lane 0 with invalid
    gathers, count(build_col) skips them."""
    rng = np.random.default_rng(3)
    n = 6000
    probe = pa.table({
        "ck": rng.integers(0, 200, n).astype("int64"),   # some keys miss
        "amt": np.round(rng.uniform(1, 10, n), 2),
    })
    build = pa.table({
        "id": pa.array(np.arange(0, 120), pa.int64()),   # ids 120..199 unmatched
        "grp": pa.array([f"g{i % 4}" for i in range(120)]),
        "w": pa.array(np.arange(0, 120).astype("float64") / 2),
    })
    sql = ("SELECT ck, count(w) AS cw, count(*) AS c, sum(amt) AS s "
           "FROM build RIGHT JOIN probe ON id = ck GROUP BY ck ORDER BY ck")
    tpu, cpu = _device_oracle(sql, {"probe": probe, "build": build})
    tp, cp = tpu.to_pandas(), cpu.to_pandas()
    assert tp.ck.tolist() == cp.ck.tolist()
    assert tp.cw.tolist() == cp.cw.tolist()
    assert tp.c.tolist() == cp.c.tolist()
    assert np.allclose(tp.s.values, cp.s.values, atol=1e-6)
    # sanity: the miss range exists, so count(w) < count(*) somewhere
    assert (tp.cw.values < tp.c.values).any()


def test_filtered_semi_anti_join_on_device():
    """EXISTS / NOT EXISTS with a correlated residual predicate (the q21
    shape: l2.l_suppkey <> l1.l_suppkey) lowers to an OR across build match
    lanes on device."""
    rng = np.random.default_rng(9)
    n = 5000
    t1 = pa.table({
        "ok": rng.integers(0, 400, n).astype("int64"),
        "sk": rng.integers(0, 10, n).astype("int64"),
        "v": np.round(rng.uniform(1, 5, n), 2),
    })
    m = 2000
    t2 = pa.table({
        "ok2": rng.integers(0, 400, m).astype("int64"),
        "sk2": rng.integers(0, 10, m).astype("int64"),
    })
    for kw in ("EXISTS", "NOT EXISTS"):
        sql = (f"SELECT sk, count(*) AS c, sum(v) AS s FROM t1 WHERE {kw} "
               f"(SELECT 1 FROM t2 WHERE ok2 = ok AND sk2 <> sk) "
               f"GROUP BY sk ORDER BY sk")
        tpu, cpu = _device_oracle(sql, {"t1": t1, "t2": t2})
        tp, cp = tpu.to_pandas(), cpu.to_pandas()
        assert tp.sk.tolist() == cp.sk.tolist(), kw
        assert tp.c.tolist() == cp.c.tolist(), kw
        assert np.allclose(tp.s.values, cp.s.values, atol=1e-6), kw


def test_aggregate_through_join_multiplicity():
    """count(build_col) through a dup≫16 expansion join uses match-count
    gathers (no lane unrolling, no MAX_JOIN_DUP ceiling) — the q13 shape."""
    rng = np.random.default_rng(21)
    n = 3000
    build = pa.table({
        "fk": rng.integers(0, 60, n).astype("int64"),  # up to ~70 dups per key
        "bid": pa.array(np.arange(n), pa.int64()),
    })
    probe = pa.table({
        "id": pa.array(np.arange(80), pa.int64()),     # ids 60..79 unmatched
        "grp": pa.array([i % 7 for i in range(80)], pa.int64()),
    })
    for jt, sqljoin in (("inner", "JOIN"), ("outer", "RIGHT JOIN")):
        sql = (f"SELECT grp, count(bid) AS cb, count(*) AS c FROM build "
               f"{sqljoin} probe ON fk = id GROUP BY grp ORDER BY grp")
        tpu, cpu = _device_oracle(sql, {"probe": probe, "build": build})
        tp, cp = tpu.to_pandas(), cpu.to_pandas()
        assert tp.grp.tolist() == cp.grp.tolist(), jt
        assert tp.cb.tolist() == cp.cb.tolist(), jt
        assert tp.c.tolist() == cp.c.tolist(), jt


@pytest.fixture(scope="module")
def tpch_mid_dir(tmp_path_factory):
    """SF0.05: large enough that no filtered build side is empty (at SF0.01
    the q16/q18 subquery builds vanish and adaptively fall back — correct,
    but it would mask real device-coverage regressions)."""
    from ballista_tpu.testing.tpchgen import generate_tpch

    d = tmp_path_factory.mktemp("tpch-mid") / "sf005"
    # seed 1: every correlated-subquery build side (q16 complaint suppliers,
    # q18 big-quantity orders) is non-empty at this scale
    generate_tpch(str(d), scale=0.05, seed=1, files_per_table=2)
    return str(d)


# (n partial device stages, n final/sort device stages) per query — exact
# pins so a silent coverage regression in EITHER stage class fails loudly.
# q6/q14/q17/q19 are global (no-GROUP-BY) aggregations: their final merge
# is a handful of rows, left on CPU by design.
TPCH_DEVICE_STAGE_PINS = {
    1: (1, 1), 2: (1, 1), 3: (1, 1), 4: (1, 1), 5: (1, 1), 6: (1, 0),
    7: (1, 1), 8: (1, 1), 9: (1, 1), 10: (1, 1), 11: (2, 1), 12: (1, 1),
    13: (1, 2), 14: (1, 0), 15: (2, 2), 16: (1, 2), 17: (1, 0), 18: (1, 1),
    19: (1, 0), 20: (1, 1), 21: (1, 1), 22: (1, 1),
}


def test_all_22_tpch_queries_run_device_stages(tpch_mid_dir):
    """Coverage pin: every TPC-H query compiles its pinned number of device
    stages (partial-agg chains AND final-agg/sort stages) and runs them all
    with ZERO cpu fallbacks (VERDICT round-2 item #2's done criterion:
    counts must not regress, not just ≥1)."""
    import ballista_tpu.ops.tpu.stage_compiler as sc
    from ballista_tpu.client.context import SessionContext
    from ballista_tpu.engine.tpu_engine import maybe_compile_tpu
    from ballista_tpu.ops.tpu.final_stage import TpuFinalStageExec
    from ballista_tpu.plan.physical import TaskContext
    from ballista_tpu.testing.tpchgen import register_tpch

    cfg = BallistaConfig({EXECUTOR_ENGINE: "tpu", TPU_MIN_ROWS: 0})
    tpu_ctx = SessionContext(cfg)
    register_tpch(tpu_ctx, tpch_mid_dir)
    bad = []
    for q in range(1, 23):
        sql = tpch_query(q)
        phys = maybe_compile_tpu(
            tpu_ctx.create_physical_plan(tpu_ctx.sql(sql).plan), cfg)
        partial = [nd for nd in _walk(phys) if isinstance(nd, sc.TpuStageExec)]
        final = [nd for nd in _walk(phys) if isinstance(nd, TpuFinalStageExec)]
        want = TPCH_DEVICE_STAGE_PINS[q]
        if (len(partial), len(final)) != want:
            bad.append((q, f"stages=({len(partial)},{len(final)}) want {want}"))
            continue
        tc = TaskContext(cfg)
        for p in range(phys.output_partition_count()):
            list(phys.execute(p, tc))
        runs = sum(s.tpu_count for s in partial) + sum(s.tpu_count for s in final)
        fb = sum(s.fallback_count for s in partial) + sum(s.fallback_count for s in final)
        if runs != len(partial) + len(final) or fb:
            bad.append((q, f"runs={runs}/{len(partial) + len(final)} fallbacks={fb}"))
    assert not bad, bad


@pytest.mark.parametrize("share,divisor", LIVE_SHARES)
def test_variance_on_device_sorted_path(share, divisor):
    """var/stddev partials (Welford (cnt, mean, M2) triple) computed on
    device via the sorted segmented two-pass, including an all-NULL group
    and the n<2 sample-variance guard — vs the CPU engine, the segment mean
    gathered back per row inside the tier's own slots."""
    rng = np.random.default_rng(17)
    n = 2000
    g = rng.integers(0, 40, n).astype("int64")
    v = np.round(rng.normal(1000.0, 25.0, n), 4)
    null_v = rng.random(n) < 0.25
    # group 39: all inputs NULL; group 38: exactly one non-null row
    null_v[g == 39] = True
    one = np.nonzero(g == 38)[0]
    null_v[one] = True
    null_v[one[0]] = False
    tbl = _among_dead_rows(pa.table({
        "g": pa.array(g, pa.int64()),
        "v": pa.array(v, pa.float64(), mask=null_v),
    }), share)
    sql = ("SELECT g, var_samp(v) AS vs, var_pop(v) AS vp, "
           "stddev(v) AS sd, count(v) AS c FROM t WHERE alive = 1 GROUP BY g ORDER BY g")
    tpu, cpu = _device_oracle(sql, {"t": tbl})
    _assert_sorted_tier(divisor, n)
    tp, cp = tpu.to_pandas(), cpu.to_pandas()
    assert tp.g.tolist() == cp.g.tolist()
    assert tp.c.tolist() == cp.c.tolist()
    # group 39 (no inputs): NULL everywhere; group 38 (n=1): samp NULL, pop 0
    assert tp.vs.isna().tolist() == cp.vs.isna().tolist()
    assert tp.vp.isna().tolist() == cp.vp.isna().tolist()
    assert np.allclose(tp.vs.fillna(0).values, cp.vs.fillna(0).values,
                       rtol=1e-9, atol=1e-9)
    assert np.allclose(tp.vp.fillna(0).values, cp.vp.fillna(0).values,
                       rtol=1e-9, atol=1e-9)
    assert np.allclose(tp.sd.fillna(0).values, cp.sd.fillna(0).values,
                       rtol=1e-9, atol=1e-9)


def test_variance_on_device_unrolled_path():
    """Variance over a low-cardinality dictionary group key rides the
    unrolled masked-reduction path (two fused passes, no sort)."""
    rng = np.random.default_rng(23)
    n = 8000
    cat = rng.integers(0, 4, n)
    # large offset stresses the centered form: naive sum-of-squares loses
    # all significant digits at 1e8 magnitude with unit variance
    v = 1.0e8 + rng.normal(0.0, 1.0, n)
    tbl = pa.table({
        "cat": pa.array([f"c{i}" for i in cat]),
        "v": pa.array(v, pa.float64()),
    })
    sql = ("SELECT cat, stddev_samp(v) AS sd, var_pop(v) AS vp "
           "FROM t GROUP BY cat ORDER BY cat")
    tpu, cpu = _device_oracle(sql, {"t": tbl})
    tp, cp = tpu.to_pandas(), cpu.to_pandas()
    assert tp.cat.tolist() == cp.cat.tolist()
    assert np.allclose(tp.sd.values, cp.sd.values, rtol=1e-6)
    assert np.allclose(tp.vp.values, cp.vp.values, rtol=1e-6)
    # the data really does have ~unit stddev — catastrophic cancellation
    # would produce 0 or wild values here
    assert (np.abs(tp.sd.values - 1.0) < 0.1).all()


def test_variance_global_no_groups_on_device():
    rng = np.random.default_rng(29)
    v = rng.normal(50.0, 7.0, 5000)
    tbl = pa.table({"v": pa.array(v, pa.float64())})
    sql = "SELECT var_samp(v) AS vs, stddev_pop(v) AS sp, avg(v) AS m FROM t"
    tpu, cpu = _device_oracle(sql, {"t": tbl})
    tp, cp = tpu.to_pandas(), cpu.to_pandas()
    assert np.allclose(tp.vs[0], cp.vs[0], rtol=1e-9)
    assert np.allclose(tp.sp[0], cp.sp[0], rtol=1e-9)
    assert np.allclose(tp.m[0], cp.m[0], rtol=1e-12)


def test_same_shape_stages_with_different_builds_do_not_collide():
    """Two stages identical except for the FILTER on a join's build side
    (TPC-DS q39's d_moy=1 vs d_moy=2 date_dim sides) must not share build
    tables: the stage fingerprint carries the full build subtree."""
    rng = np.random.default_rng(41)
    n = 5000
    fact = pa.table({
        "fk": rng.integers(0, 200, n).astype("int64"),
        "v": rng.integers(0, 100, n).astype("int64"),
    })
    dim = pa.table({
        "id": pa.array(np.arange(200), pa.int64()),
        "moy": pa.array((np.arange(200) % 12) + 1, pa.int64()),
    })
    t1 = "SELECT count(*) c, sum(v) s FROM fact JOIN dim ON fk = id WHERE moy = 1"
    t2 = "SELECT count(*) c, sum(v) s FROM fact JOIN dim ON fk = id WHERE moy = 2"
    tpu1, cpu1 = _device_oracle(t1, {"fact": fact, "dim": dim})
    tpu2, cpu2 = _device_oracle(t2, {"fact": fact, "dim": dim})
    p1, p2 = tpu1.to_pandas(), tpu2.to_pandas()
    assert p1.c[0] == cpu1.to_pandas().c[0]
    assert p2.c[0] == cpu2.to_pandas().c[0]
    assert (p1.c[0], p1.s[0]) != (p2.c[0], p2.s[0])


def test_union_pushdown_device_stages():
    """Partial aggregation over a UNION (TPC-DS cross-channel shapes)
    pushes through the union so each branch runs a device stage; results
    match the CPU engine."""
    rng = np.random.default_rng(43)
    a = pa.table({
        "g": pa.array([f"g{i%5}" for i in rng.integers(0, 5, 4000)]),
        "v": rng.integers(0, 50, 4000).astype("int64"),
    })
    b = pa.table({
        "g": pa.array([f"g{i%5}" for i in rng.integers(0, 5, 3000)]),
        "v": rng.integers(50, 99, 3000).astype("int64"),
    })
    sql = ("SELECT g, count(*) c, sum(v) s FROM "
           "(SELECT g, v FROM a UNION ALL SELECT g, v FROM b) u "
           "GROUP BY g ORDER BY g")
    tpu, cpu = _device_oracle(sql, {"a": a, "b": b})
    tp, cp = tpu.to_pandas(), cpu.to_pandas()
    assert tp.g.tolist() == cp.g.tolist()
    assert tp.c.tolist() == cp.c.tolist()
    assert tp.s.tolist() == cp.s.tolist()


def test_expression_group_key_hoisted_to_device():
    """Group keys that are single-column expressions (q62/q99's substr)
    hoist: the device groups by the raw column, a CPU projection applies
    the expression over the few partial rows, the final agg merges."""
    rng = np.random.default_rng(47)
    n = 6000
    names = [f"warehouse-{i:02d}-site" for i in range(30)]
    tbl = pa.table({
        "w": pa.array([names[i] for i in rng.integers(0, 30, n)]),
        "v": rng.integers(0, 100, n).astype("int64"),
    })
    sql = ("SELECT substr(w, 1, 11) wk, count(*) c, sum(v) s "
           "FROM t GROUP BY substr(w, 1, 11) ORDER BY wk")
    tpu, cpu = _device_oracle(sql, {"t": tbl})
    tp, cp = tpu.to_pandas(), cpu.to_pandas()
    assert tp.wk.tolist() == cp.wk.tolist()
    assert tp.c.tolist() == cp.c.tolist()
    assert tp.s.tolist() == cp.s.tolist()
    # the 11-char prefix folds 30 warehouses into 3 groups — the hoist must
    # actually merge finer device groups downstream
    assert len(tp) == 3


def test_stage_ledger_tells_row_floor_from_declines_and_errors(tpch_dir):
    """STAGE_OUTCOMES is what chip_smoke.py and the heartbeat read instead of
    per-task operator counters: a stage under ballista.tpu.min.rows is a
    `below_row_floor` (policy), any other Unsupported a `declined`, and a
    non-Unsupported exception an `error` — the demotion that would hide a
    broken device path behind right answers."""
    import ballista_tpu.ops.tpu.stage_compiler as sc
    from ballista_tpu.client.context import SessionContext
    from ballista_tpu.ops.tpu.kernels import BelowRowFloor, Unsupported
    from ballista_tpu.testing.tpchgen import register_tpch

    led = sc.StageOutcomes()
    led.note("partial", "device")
    led.note_fallback("final", BelowRowFloor(32))
    led.note_fallback("sort", Unsupported("unencodable column x"))
    led.note_fallback("window", ValueError("lowering refused"))
    snap = led.snapshot()
    assert {k: snap[k] for k in led.KINDS} == {
        "device": 1, "below_row_floor": 1, "declined": 1, "error": 1}
    assert snap["recent"][-1] == ("window", "error", "ValueError: lowering refused")
    assert snap["recent"][1][2] == "BelowRowFloor: only 32 rows (< tpu min)"

    # end to end at the default row floor: q1's partial stage runs on the
    # device, its 4-group final merge stays under the floor, nothing errs
    ctx = SessionContext(BallistaConfig({EXECUTOR_ENGINE: "tpu"}))
    register_tpch(ctx, tpch_dir)
    before = sc.STAGE_OUTCOMES.snapshot()
    ctx.sql(tpch_query(1)).collect()
    after = sc.STAGE_OUTCOMES.snapshot()
    delta = {k: after[k] - before[k] for k in led.KINDS}
    assert delta["device"] >= 1 and delta["below_row_floor"] >= 1
    assert delta["declined"] == 0 and delta["error"] == 0
