"""A join stage probes at its live rows' tier.

A device stage whose chain holds a join evaluates its filters up to the first
join whose match mask filters probe rows over every slot, counts the rows
left alive and runs everything behind — later probes, payload lookups,
predicates, group ids, aggregate inputs — over the scan columns compacted to
the smallest capacity that holds them: a partition's rows into N / 8 slots on
the direct path, all rows into M / 64 on the sorted path, else over the slots
as they are. The capacity follows the data alone; every case here is one
device stage on the CPU backend beside the CPU engine's answer, with the
counts the stage recorded (RunStats `probe_rows_live`, `probe_rows`).
"""

import hashlib

import numpy as np
import pyarrow as pa
import pytest

from .conftest import tpch_query
from .test_tpu_engine import _device_oracle, _in_batches

ROWS, PARTS = 8000, 2   # two partitions of 4000 rows: [2, 4096] row slots
SLOTS = 4096
DIM = 500               # build keys 0 .. 499; probe keys 500 .. 1199 match nothing


def _fact(lives, alive_in_dim=True):
    """The probe table: partition p holds exactly `lives[p]` rows alive behind
    the join with `dim` on `fk` — rows whose key is in `dim` (or, for an anti
    join, is not) — spread among the others in a fixed random order."""
    rng = np.random.default_rng(17)
    per = ROWS // PARTS
    fk = []
    for n_live in lives:
        hit = np.zeros(per, bool)
        hit[rng.choice(per, n_live, replace=False)] = True
        if not alive_in_dim:
            hit = ~hit
        fk.append(np.where(hit, rng.integers(0, DIM, per), rng.integers(DIM, 1200, per)))
    fk = np.concatenate(fk).astype("int64")
    return _in_batches(pa.table({
        "fk": fk,
        "sk": rng.integers(0, 8, ROWS).astype("int64"),
        "g": (rng.permutation(ROWS) % 3000).astype("int64"),
        "tag": pa.array([f"t{i}" for i in rng.integers(0, 4, ROWS)]),
        "amt": np.round(rng.uniform(1, 100, ROWS), 2),
        "qty": rng.integers(1, 50, ROWS).astype("int64"),
    }), PARTS)


def _dims():
    rng = np.random.default_rng(19)
    ids = np.arange(DIM)
    w = rng.uniform(0, 5, DIM)
    xid = np.repeat(ids, rng.integers(1, 4, DIM))     # 1 to 3 rows a key
    return {
        "dim": pa.table({
            "id": pa.array(ids, pa.int64()),
            "cat": pa.array([f"c{i % 5}" for i in ids]),
            "nk": pa.array(ids % 8, pa.int64()),
            "prio": pa.array(ids % 3, pa.int64()),
            "w": pa.array([None if i % 4 == 0 else float(x) for i, x in zip(ids, w)],
                          pa.float64()),
        }),
        # every (sk, nk) pair: the second join of the chain, on two keys, one
        # of them the first join's payload
        "dim2": pa.table({
            "sid": pa.array(np.repeat(np.arange(8), 8), pa.int64()),
            "snk": pa.array(np.tile(np.arange(8), 8), pa.int64()),
            "name": pa.array([f"n{(a * 3 + b) % 6}" for a in range(8) for b in range(8)]),
        }),
        "dimx": pa.table({"xid": pa.array(xid, pa.int64()),
                          "mode": pa.array([f"m{i % 3}" for i in range(len(xid))]),
                          "bid": pa.array(np.arange(len(xid)), pa.int64())}),
        # a residual that never rules a pair out: sq is no fact row's qty;
        # one that does: sk2 is a fact row's sk one time in eight
        "dims": pa.table({"sid2": pa.array(xid, pa.int64()),
                          "sq": pa.array(np.full(len(xid), -1), pa.int64()),
                          "sk2": pa.array(rng.integers(0, 8, len(xid)), pa.int64())}),
    }


# name -> (sql, tables beside fact, path, lanes of the first join (None: by
# the build's duplicates), alive rows are those whose key is in the build)
SHAPES = {
    # q5: a chain, the second join probing on two keys, grouped by a dictionary
    "chain_two_key_probe": (
        "SELECT name, sum(amt) AS s, count(*) AS c FROM fact, dim, dim2 "
        "WHERE fk = id AND sk = sid AND nk = snk GROUP BY name ORDER BY name",
        ("dim", "dim2"), "direct", 1, True),
    # q3: one join, its payloads group keys beside a large int domain
    "sorted_one_join": (
        "SELECT g, prio, sum(amt) AS s, count(*) AS c FROM fact JOIN dim ON fk = id "
        "WHERE qty > 2 GROUP BY g, prio ORDER BY g, prio",
        ("dim",), "sorted", 1, True),
    # q12: duplicate build keys, three match lanes
    "expansion_lanes": (
        "SELECT mode, sum(qty) AS s, count(*) AS c FROM fact JOIN dimx ON fk = xid "
        "GROUP BY mode ORDER BY mode",
        ("dimx",), "direct", None, True),
    "expansion_lanes_sorted": (
        "SELECT g, mode, sum(qty) AS s, count(*) AS c FROM fact JOIN dimx ON fk = xid "
        "GROUP BY g, mode ORDER BY g, mode",
        ("dimx",), "sorted", None, True),
    # q19: a disjunctive residual over both sides behind the join, no group key
    "residual_filter": (
        "SELECT sum(amt) AS s, count(*) AS c FROM fact JOIN dim ON fk = id "
        "WHERE (cat = 'c1' AND qty < 20) OR (cat = 'c3' AND qty >= 20)",
        ("dim",), "direct", 1, True),
    # q13: count(build column) through the join's match count, one lane
    "aggregate_through_join": (
        "SELECT tag, count(bid) AS cb, count(*) AS c FROM dimx JOIN fact ON xid = fk "
        "GROUP BY tag ORDER BY tag",
        ("dimx",), "direct", 1, True),
    # q21: EXISTS / NOT EXISTS with a correlated residual
    "semi_with_residual": (
        "SELECT tag, count(*) AS c, sum(amt) AS s FROM fact WHERE EXISTS "
        "(SELECT 1 FROM dims WHERE sid2 = fk AND sq <> qty) GROUP BY tag ORDER BY tag",
        ("dims",), "direct", 1, True),
    "anti_with_residual": (
        "SELECT tag, count(*) AS c, sum(amt) AS s FROM fact WHERE NOT EXISTS "
        "(SELECT 1 FROM dims WHERE sid2 = fk AND sq <> qty) GROUP BY tag ORDER BY tag",
        ("dims",), "direct", 1, False),
    "nullable_build_payload": (
        "SELECT tag, sum(w) AS s, count(w) AS cw, count(*) AS c FROM fact JOIN dim "
        "ON fk = id GROUP BY tag ORDER BY tag",
        ("dim",), "direct", 1, True),
}

# live rows of partition 0 and of partition 1, of 4096 slots each. The direct
# path probes at 512 slots a partition where the fullest holds that few, the
# sorted path at 128 of the stage's 8192 where all its live rows do
LIVES = [
    pytest.param((0, 0), id="none_alive"),
    pytest.param((40, 60), id="few_alive"),
    pytest.param((64, 64), id="sorted_tier_full"),
    pytest.param((64, 65), id="sorted_tier_one_over"),
    pytest.param((512, 300), id="direct_tier_full"),
    pytest.param((513, 2), id="fullest_partition_one_over"),
    pytest.param((4000, 4000), id="all_alive"),
]


# The sorted path's filters of several match lanes: a filtered EXISTS / NOT
# EXISTS over dims (1 to 3 rows a key, a lane each), behind a filter of one
# lane that decides which rows live — an inner join, a scan filter — grouped
# by a large int domain. name -> (sql, tables beside fact, NOT EXISTS)
TIER_SHAPES = {
    "sorted_semi_behind_join": (
        "SELECT g, count(*) AS c, sum(amt) AS s FROM fact JOIN dim ON fk = id WHERE EXISTS "
        "(SELECT 1 FROM dims WHERE sid2 = fk AND sk2 <> sk) GROUP BY g ORDER BY g",
        ("dim", "dims"), False),
    "sorted_anti_behind_scan_filter": (
        f"SELECT g, count(*) AS c, sum(amt) AS s FROM fact WHERE fk < {DIM} AND NOT EXISTS "
        "(SELECT 1 FROM dims WHERE sid2 = fk AND sk2 <> sk) GROUP BY g ORDER BY g",
        ("dims",), True),
}


def _stage_record():
    """The record of the ONE partial device stage the last query ran."""
    import ballista_tpu.ops.tpu.stage_compiler as sc

    recs = [r for r in sc.RUN_STATS.stages().values() if "probe_rows" in r]
    assert len(recs) == 1, [sorted(r) for r in sc.RUN_STATS.stages().values()]
    return recs[0]


def _assert_same_answer(tpu: pa.Table, cpu: pa.Table):
    assert tpu.schema.names == cpu.schema.names and tpu.num_rows == cpu.num_rows
    for name in tpu.schema.names:
        a, b = tpu.column(name).to_pylist(), cpu.column(name).to_pylist()
        if pa.types.is_floating(tpu.schema.field(name).type):
            assert [x is None for x in a] == [x is None for x in b], name
            assert np.allclose([x or 0.0 for x in a], [x or 0.0 for x in b],
                               rtol=1e-12, atol=1e-9), name
        else:
            assert a == b, name


@pytest.mark.parametrize("lives", LIVES)
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_join_stage_probes_at_its_live_rows_tier(shape, lives):
    sql, names, path, lanes, alive_in_dim = SHAPES[shape]
    dims = _dims()
    fact = _fact(lives, alive_in_dim)
    tpu, cpu = _device_oracle(sql, {"fact": fact, **{n: dims[n] for n in names}})
    _assert_same_answer(tpu, cpu)

    rec = _stage_record()
    assert rec["table_shape"] == [PARTS, SLOTS]
    # matches a probe row finds in the first join's build, lane by lane
    fk = fact.column("fk").to_numpy()
    if lanes is None:
        per_key = np.bincount(dims[names[0]].column(0).to_numpy(), minlength=1200)
        lanes = int(per_key.max())
        matches = per_key[fk]
    else:
        matches = ((fk < DIM) == alive_in_dim).astype(int)
    if path == "sorted" and shape == "sorted_one_join":
        matches = matches * (fact.column("qty").to_numpy() > 2)  # the scan filter
    live = int(np.minimum(matches, lanes).sum())
    assert rec["probe_rows_live"] == live
    if path == "sorted":
        slots = PARTS * SLOTS * lanes
        want = slots // 64 if live <= slots // 64 else slots
        assert rec["sorted_rows_ordered"] == want and rec["sorted_rows_live"] == live
    else:
        fullest = max(int((matches[p * 4000:(p + 1) * 4000] > 0).sum()) for p in range(PARTS))
        want = (SLOTS // 8 if fullest <= SLOTS // 8 else SLOTS) * PARTS * lanes
        assert "sorted_rows_ordered" not in rec
    assert rec["probe_rows"] == want


def test_right_outer_join_compacts_nothing():
    """A right outer join's first lane emits every probe row: its match mask
    filters nothing, the stage has no tier and counts no live rows."""
    sql = ("SELECT tag, count(w) AS cw, count(*) AS c, sum(amt) AS s FROM dim "
           "RIGHT JOIN fact ON id = fk GROUP BY tag ORDER BY tag")
    tpu, cpu = _device_oracle(sql, {"fact": _fact((40, 60)), "dim": _dims()["dim"]})
    _assert_same_answer(tpu, cpu)
    rec = _stage_record()
    assert rec["probe_rows"] == PARTS * SLOTS and "probe_rows_live" not in rec


def test_inner_join_behind_a_right_outer_join_is_the_first_that_filters():
    """The prefix runs up to the first join whose match FILTERS: the outer
    join before it is inside the prefix, and its payloads are probed again
    at the tier, NULL where unmatched."""
    dims = _dims()
    outer = pa.table({"oid": pa.array(np.arange(0, 8, 2), pa.int64()),
                      "ow": pa.array([1.5, 2.5, 3.5, 4.5])})
    sql = ("SELECT tag, count(ow) AS co, sum(ow) AS so, count(*) AS c FROM outerd "
           "RIGHT JOIN fact ON oid = sk JOIN dim ON fk = id GROUP BY tag ORDER BY tag")
    tpu, cpu = _device_oracle(sql, {"fact": _fact((300, 20)), "dim": dims["dim"],
                                    "outerd": outer})
    _assert_same_answer(tpu, cpu)
    rec = _stage_record()
    # the planner may probe dim first or second: either way 320 rows are alive
    assert rec["probe_rows_live"] == 320 and rec["probe_rows"] == PARTS * SLOTS // 8


def test_tiers_agree_bit_for_bit():
    """The same rows alive behind the whole chain, once with few and once with
    many rows alive behind its FIRST join (the others die at the residual
    behind it): probed at the tier and over the slots as they are, every
    exact kind (money in int64 cents, counts) comes out the same to the bit,
    float sums to their rounding (the reduction sees its addends at other
    slots)."""
    sql = ("SELECT sum(amt) AS s, sum(w) AS sw, count(*) AS c FROM fact JOIN dim ON fk = id "
           "WHERE (cat = 'c1' AND qty < 20) OR (cat = 'c3' AND qty >= 20)")
    few = _fact((200, 300)).to_pandas()
    many = few.copy()
    # every dead row now finds a build row of category c0, which the residual drops
    many.loc[many.fk >= DIM, "fk"] = (many.fk[many.fk >= DIM] % 100) * 5
    outs = []
    for fact, want in ((few, PARTS * SLOTS // 8), (many, PARTS * SLOTS)):
        tables = {"fact": _in_batches(pa.Table.from_pandas(fact), PARTS), "dim": _dims()["dim"]}
        tpu, cpu = _device_oracle(sql, tables)
        _assert_same_answer(tpu, cpu)
        assert _stage_record()["probe_rows"] == want
        outs.append(tpu.to_pydict())
    assert outs[0]["s"] == outs[1]["s"] and outs[0]["c"] == outs[1]["c"]
    assert np.allclose(outs[0]["sw"], outs[1]["sw"], rtol=1e-13)


def _exists_in_dims(fact, dims):
    """Per fact row: some dims row of its key has an sk2 other than its sk."""
    fk, sk = (fact.column(c).to_numpy() for c in ("fk", "sk"))
    rows = np.zeros((1200, 8), int)
    np.add.at(rows, tuple(dims.column(c).to_numpy() for c in ("sid2", "sk2")), 1)
    return rows.sum(axis=1)[fk] > rows[fk, sk]


@pytest.mark.parametrize("lives", LIVES)
@pytest.mark.parametrize("shape", sorted(TIER_SHAPES))
def test_multi_lane_filters_run_at_the_live_rows_tier(shape, lives):
    """On the sorted path a filter of several match lanes is left out of the
    count that picks the tier and runs inside it: the rows the one-lane
    filter keeps pick the tier (`sorted_rows_masked`), the lanes decide over
    those alone (`tier_lanes`), and the rows every filter keeps are counted
    after them (`sorted_rows_live`)."""
    sql, names, anti = TIER_SHAPES[shape]
    dims = _dims()
    fact = _fact(lives)
    tpu, cpu = _device_oracle(sql, {"fact": fact, **{n: dims[n] for n in names}})
    _assert_same_answer(tpu, cpu)

    rec = _stage_record()
    assert rec["table_shape"] == [PARTS, SLOTS]
    masked = sum(lives)
    kept = (fact.column("fk").to_numpy() < DIM) & (_exists_in_dims(fact, dims["dims"]) != anti)
    slots = PARTS * SLOTS
    want = slots // 64 if masked <= slots // 64 else slots
    assert (rec["sorted_rows_masked"], rec["sorted_rows_live"], rec["sorted_rows_ordered"]) \
        == (masked, int(kept.sum()), want)
    assert (rec["probe_rows_live"], rec["probe_rows"]) == (int(kept.sum()), want)
    assert rec["tier_lanes"] == 3


def test_multi_lane_tiers_agree_bit_for_bit():
    """The same rows alive behind a filtered EXISTS on the sorted path, once
    with few and once with every row alive before it (the others die in its
    lanes: a key of one dims row whose sk2 is the row's sk): its lanes run at
    the tier and over the slots as they are, and the answer — money sums in
    int64 cents, counts, the group keys — comes out the same to the bit."""
    sql = (f"SELECT g, sum(amt) AS s, sum(qty) AS q, count(*) AS c FROM fact WHERE fk < {DIM} "
           "AND EXISTS (SELECT 1 FROM dims WHERE sid2 = fk AND sk2 <> sk) GROUP BY g ORDER BY g")
    dims = _dims()["dims"]
    sid2, sk2 = (dims.column(c).to_numpy() for c in ("sid2", "sk2"))
    single = np.flatnonzero(np.bincount(sid2) == 1)
    few = _fact((40, 60)).to_pandas()
    many = few.copy()
    dead = many.fk >= DIM
    pick = single[np.arange(int(dead.sum())) % len(single)]
    many.loc[dead, "fk"] = pick
    many.loc[dead, "sk"] = sk2[np.searchsorted(sid2, pick)]  # sid2 is in key order
    outs = []
    for fact, masked, want in ((few, 100, PARTS * SLOTS // 64), (many, ROWS, PARTS * SLOTS)):
        fact = _in_batches(pa.Table.from_pandas(fact, preserve_index=False), PARTS)
        tpu, cpu = _device_oracle(sql, {"fact": fact, "dims": dims})
        _assert_same_answer(tpu, cpu)
        rec = _stage_record()
        assert (rec["sorted_rows_masked"], rec["sorted_rows_ordered"]) == (masked, want)
        outs.append((rec["sorted_rows_live"], tpu.to_pydict()))
    assert outs[0][0] > 0 and outs[0] == outs[1]


def _partial_stage_text(ctx, q):
    import ballista_tpu.ops.tpu.stage_compiler as sc
    from ballista_tpu.engine.tpu_engine import maybe_compile_tpu
    from ballista_tpu.plan.physical import TaskContext

    from .test_tpu_engine import _walk

    phys = maybe_compile_tpu(ctx.create_physical_plan(ctx.sql(tpch_query(q)).plan), ctx.config)
    stage = next(n for n in _walk(phys) if isinstance(n, sc.TpuStageExec))
    dt = sc.DEVICE_CACHE.get(stage.scan, stage.buckets, TaskContext(ctx.config), 1 << 34)
    _, _, meta, lowered = stage._compile(
        dt, list(zip(dt.kinds, dt.scales)), dt.dicts, *dt.shape, [])
    return meta, lowered.as_text()


# sha256 of the lowered text of q1's and q6's partial stage over the session's
# SF0.01 tables, taken from the parent commit of the PR that brought the tiers
# (jax 0.9.0). A jax upgrade changes them: take them again from a tree known
# good; any other change to them is a change to the join-less programs
JOINLESS_PROGRAMS = {
    1: "11a8ebffda24c5d69f5fb987d0b0735e7977572de3a45d9bea37c56d973808de",
    6: "69b551c5e9a638db26f72b6bd2074abd8b6b7f1daa7d9a4a29815d58263bbb6e",
}


@pytest.mark.parametrize("q", sorted(JOINLESS_PROGRAMS))
def test_a_stage_without_a_join_traces_no_switch(q, tpch_dir):
    """q1 and q6 have no join: no prefix, no count, no conditional — the
    program the parent traced, to the byte (so the persistent compile cache
    still holds it)."""
    from ballista_tpu.client.context import SessionContext
    from ballista_tpu.config import EXECUTOR_ENGINE, BallistaConfig
    from ballista_tpu.testing.tpchgen import register_tpch

    ctx = SessionContext(BallistaConfig({EXECUTOR_ENGINE: "tpu"}))
    register_tpch(ctx, tpch_dir)
    meta, text = _partial_stage_text(ctx, q)
    assert meta["mode"] == "unrolled" and not meta["probe_counts"]
    assert "stablehlo.case" not in text and "stablehlo.if" not in text
    assert "stablehlo.sort" not in text
    assert hashlib.sha256(text.encode()).hexdigest() == JOINLESS_PROGRAMS[q]


def test_probe_counts_land_on_the_stage_record_and_its_decode_span(tpch_dir):
    """Served through the standalone cluster: q3 (sorted path) and q5 (direct
    path) leave `probe_rows_live` / `probe_rows` on the device stage's record
    and as numbers of its `bt.decode` span in the job's record."""
    from ballista_tpu.client.context import SessionContext
    from ballista_tpu.config import EXECUTOR_ENGINE, BallistaConfig
    from ballista_tpu.testing.tpchgen import register_tpch
    from ballista_tpu.tracing import RUN_STATS

    ctx = SessionContext.standalone(BallistaConfig({EXECUTOR_ENGINE: "tpu"}), num_executors=1)
    try:
        register_tpch(ctx, tpch_dir)
        for q in (3, 5):
            # the first collect's record also takes the spans earlier tests
            # closed outside any job: read the second
            ctx.sql(tpch_query(q)).collect()
            RUN_STATS.clear()
            ctx.sql(tpch_query(q)).collect()
            rec = _stage_record()
            P, N = rec["table_shape"]
            assert 0 < rec["probe_rows_live"] <= rec["probe_rows"] <= P * N
            numbers = [s[7] for tag, job in RUN_STATS.stages().items() if tag.startswith("job_")
                       for s in job["spans"] if s[0] == "bt.decode" and "probe_rows" in s[7]]
            assert len(numbers) == 1, "one bt.decode span carries the counts"
            assert numbers[0]["probe_rows"] == rec["probe_rows"]
            assert numbers[0]["probe_rows_live"] == rec["probe_rows_live"]
    finally:
        ctx.shutdown()


@pytest.mark.parametrize("lives,divisor", [((40, 60), 64), ((4000, 4000), 1)])
def test_emit_pid_routes_a_join_stage_probed_at_its_tier(tmp_path, lives, divisor):
    """Device-side shuffle routing behind a join: the group keys — one of them
    a payload of the join, looked up at the tier — are hashed over the
    compacted groups, and every written bucket holds the rows the host's
    hash would send there."""
    import glob
    import json

    import pyarrow.ipc as ipc
    import pyarrow.parquet as pq

    import ballista_tpu.ops.tpu.stage_compiler as sc
    from ballista_tpu.client.context import SessionContext
    from ballista_tpu.config import EXECUTOR_ENGINE, TPU_MIN_ROWS, BallistaConfig
    from ballista_tpu.engine.tpu_engine import maybe_compile_tpu
    from ballista_tpu.ops.hashing import partition_indices
    from ballista_tpu.plan.physical import TaskContext
    from ballista_tpu.scheduler.planner import DistributedPlanner
    from ballista_tpu.shuffle import paths as sp

    from .test_tpu_engine import _walk

    fact = _fact(lives)
    pq.write_table(fact, str(tmp_path / "fact.parquet"))
    cfg = BallistaConfig({EXECUTOR_ENGINE: "tpu", TPU_MIN_ROWS: 0})
    ctx = SessionContext(cfg)
    ctx.register_parquet("fact", str(tmp_path / "fact.parquet"))
    ctx.register_arrow_table("dim", _dims()["dim"], partitions=1)
    sql = "SELECT g, prio, sum(amt) AS s FROM fact JOIN dim ON fk = id GROUP BY g, prio"
    stage1 = DistributedPlanner("jprobe").plan_query_stages(
        ctx.create_physical_plan(ctx.sql(sql).plan))[0]
    compiled = maybe_compile_tpu(stage1.plan, cfg)
    tpu = [nd for nd in _walk(compiled) if isinstance(nd, sc.TpuStageExec)]
    assert tpu and tpu[0].emit_pid is not None
    assert any(type(op).__name__ == "HashJoinExec" for op in tpu[0].ops)

    tc = TaskContext(cfg, task_id="t0", work_dir=str(tmp_path / "work"))
    sc.RUN_STATS.clear()
    for p in range(stage1.partitions):
        list(compiled.execute(p, tc))
    assert tpu[0].pid_emitted >= 1 and tpu[0].fallback_count == 0
    rec = _stage_record()
    P, N = rec["table_shape"]  # the parquet file's own partitioning
    assert rec["probe_rows_live"] == sum(lives) and rec["probe_rows"] == P * N // divisor

    want = fact.to_pandas().merge(_dims()["dim"].to_pandas(), left_on="fk", right_on="id")
    seen = 0
    for f in glob.glob(f"{tmp_path}/work/jprobe/1/*.arrow"):
        for pid_s, entry in json.load(open(sp.index_path(f))).items():
            with open(f, "rb") as fh:
                fh.seek(entry[0])
                got = ipc.open_stream(pa.BufferReader(fh.read(entry[1]))).read_all()
            assert "__pid" not in got.column_names
            if got.num_rows:
                host = partition_indices(
                    [got.column(k).combine_chunks() for k in ("g", "prio")],
                    stage1.output_partitions)
                assert (host == int(pid_s)).all()
                seen += got.num_rows
    assert seen == len(want.groupby(["g", "prio"]))  # every group routed, none twice


@pytest.fixture(scope="module")
def tpch_q18_dir(tmp_path_factory):
    """SF0.05, seed 1: q18's HAVING keeps orders, so its outer stage's semi
    join has a build side (at SF0.01 it has none)."""
    from ballista_tpu.testing.tpchgen import generate_tpch

    d = tmp_path_factory.mktemp("tpch-q18") / "sf005"
    generate_tpch(str(d), scale=0.05, seed=1, files_per_table=2)
    return str(d)


def _sorted_stage_texts(q, data_dir):
    """The lowered text of every sorted-path partial stage q runs, outermost
    first, each over its table and its joins' builds as the session fills
    them."""
    import ballista_tpu.ops.tpu.stage_compiler as sc
    from ballista_tpu.client.context import SessionContext
    from ballista_tpu.config import EXECUTOR_ENGINE, BallistaConfig
    from ballista_tpu.engine.tpu_engine import maybe_compile_tpu
    from ballista_tpu.plan.physical import HashJoinExec, TaskContext
    from ballista_tpu.testing.tpchgen import register_tpch

    cfg = BallistaConfig({EXECUTOR_ENGINE: "tpu"})
    ctx = SessionContext(cfg)
    register_tpch(ctx, data_dir)

    def stages(node):
        if isinstance(node, sc.TpuStageExec):
            yield node
            for op in node.ops:
                if isinstance(op, HashJoinExec):
                    yield from stages(maybe_compile_tpu(op.left, cfg))
        for c in node.children():
            yield from stages(c)

    texts = []
    for stage in stages(maybe_compile_tpu(
            ctx.create_physical_plan(ctx.sql(tpch_query(q)).plan), cfg)):
        tc = TaskContext(cfg)
        dt = sc.DEVICE_CACHE.get(stage.scan, stage.buckets, tc, 1 << 34)
        key = sc.DEVICE_CACHE.key_of(stage.scan)
        joins = [o for o in stage.ops if isinstance(o, HashJoinExec)]
        builds = [stage._prepare_build(op, j, tc, key) for j, op in enumerate(joins)]
        _, _, meta, lowered = stage._compile(
            dt, list(zip(dt.kinds, dt.scales)), dt.dicts, *dt.shape, builds)
        if meta["mode"] == "sorted":
            texts.append((meta, lowered.as_text()))
    return texts


# sha256 of the lowered text of the sorted-path partial stages of q3 (SF0.01,
# the session's tables) and q18 (SF0.05: its outer stage, then its subquery's),
# taken from the parent commit of the PR that moved the segment compaction into
# `kernels.SegmentCompaction` (jax 0.9.0). Their compacted lanes are all
# integer, so that move leaves them as they were, to the byte. A jax upgrade
# changes them: take them again from a tree known good
INTEGER_SORTED_PROGRAMS = {
    3: ["dba863e5f64f452a3e5a3db79200437384ed2c28de829ddf9a6a8ad3396fdc20"],
    18: ["60310d06880266693aa2cbfa8ec548d8674a624099f45de51ff2168608b95700",
         "9fb0109fa2299111aa85889c30e4ae2fa2a1598aebad1cd0a1c60665eef51525"],
}


@pytest.mark.parametrize("q", sorted(INTEGER_SORTED_PROGRAMS))
def test_a_sorted_stage_of_integer_lanes_lowers_as_before(q, tpch_dir, tpch_q18_dir):
    """A sorted-path stage that compacts no float64 lane splits its int64
    lanes as it did before the compaction became one function of both
    families: the same program to the byte (so the persistent compile cache
    still holds it), none of its lanes gathered."""
    texts = _sorted_stage_texts(q, tpch_q18_dir if q == 18 else tpch_dir)
    assert [hashlib.sha256(t.encode()).hexdigest() for _, t in texts] == \
        INTEGER_SORTED_PROGRAMS[q]
    for meta, _ in texts:
        assert meta["compact"]["compact_gathered_lanes"] == 0
        assert meta["compact"]["compact_split_lanes"] > 0


# shape -> the match lanes, what its joins look up at (40, 60) and at
# (4000, 4000) rows alive, and the lanes run behind the live-row switch: every
# join's lanes over the 2 x 4096 row slots where its match is in the all-slot
# mask (the prefix on the direct path, every filter of one lane on the sorted
# one), then what the tier looks up over its own rows — again, or for the
# first time where a filter of several lanes runs there; at the slots as they
# are, nothing the mask already found
LANE_SLOTS = {
    # dim in the prefix; at N / 8 = 512 a partition dim again (its nk is
    # dim2's key) and dim2; over the slots as they are dim2 alone
    "chain_two_key_probe": (2, PARTS * SLOTS + 2 * PARTS * 512, 2 * PARTS * SLOTS, 0),
    # dim in the mask; at M / 64 = 128 again for prio, a group key
    "sorted_one_join": (1, PARTS * SLOTS + 128, PARTS * SLOTS, 0),
    # the filtered semi / anti joins: a lane a build row of the key at most
    # (dims holds 1 to 3 rows a key), each a lookup over every slot in the
    # direct path's prefix; nothing behind them looks up again
    "semi_with_residual": (3, 3 * PARTS * SLOTS, 3 * PARTS * SLOTS, 0),
    "anti_with_residual": (3, 3 * PARTS * SLOTS, 3 * PARTS * SLOTS, 0),
    # on the sorted path the same three lanes run at the tier: at M / 64 = 128
    # behind dim in the mask, or behind the scan filter alone
    "sorted_semi_behind_join": (4, PARTS * SLOTS + 3 * 128, 4 * PARTS * SLOTS, 3),
    "sorted_anti_behind_scan_filter": (3, 3 * 128, 3 * PARTS * SLOTS, 3),
}


@pytest.mark.parametrize("lives", [(40, 60), (4000, 4000)], ids=["few_alive", "all_alive"])
@pytest.mark.parametrize("shape", sorted(LANE_SLOTS))
def test_match_lanes_count_the_lookups_of_the_tier_taken(shape, lives):
    """RunStats `match_lanes` (the match lanes the stage's joins unrolled),
    `match_lane_slots` (the rows those lanes looked up, in the tier the
    dispatch took) and `tier_lanes` (those of them run behind the live-row
    switch) on the stage's record."""
    if shape in TIER_SHAPES:
        sql, names, _ = TIER_SHAPES[shape]
        alive_in_dim = True
    else:
        sql, names, _, _, alive_in_dim = SHAPES[shape]
    dims = _dims()
    assert int(np.bincount(dims["dims"].column("sid2").to_numpy()).max()) == 3
    tpu, cpu = _device_oracle(sql, {"fact": _fact(lives, alive_in_dim),
                                    **{n: dims[n] for n in names}})
    _assert_same_answer(tpu, cpu)
    lanes, few, every, tier_lanes = LANE_SLOTS[shape]
    rec = _stage_record()
    assert (rec["match_lanes"], rec["match_lane_slots"], rec["tier_lanes"]) == (
        lanes, few if lives == (40, 60) else every, tier_lanes)
