"""On-device sort / window stages: byte parity with the CPU engine on
adversarial inputs, at row counts on both sides of the lane padding.

Parity is asserted per column over Arrow IPC stream bytes — bitwise
(NaN payloads, ±0.0 signs) without the chunk-slicing layout artifacts a
whole-table stream picks up from `Table.slice`."""

import io

import numpy as np
import pyarrow as pa
import pyarrow.ipc as ipc
import pytest

from ballista_tpu.config import (
    BallistaConfig,
    TPU_MIN_ROWS,
    TPU_SORT_ENABLED,
)
from ballista_tpu.plan.expressions import Column, SortKey, WindowFunction
from ballista_tpu.plan.physical import (
    ExecutionPlan,
    SortExec,
    TaskContext,
    WindowExec,
)
from ballista_tpu.plan.schema import DFSchema

class _Src(ExecutionPlan):
    def __init__(self, tbl, df_schema, chunk=97):
        super().__init__(df_schema)
        self.tbl = tbl
        self.chunk = chunk

    def children(self):
        return []

    def output_partition_count(self):
        return 1

    def execute(self, partition, ctx):
        yield from self.tbl.to_batches(max_chunksize=self.chunk)


def _cfg():
    return BallistaConfig({TPU_MIN_ROWS: 0})


def _collect(plan, cfg):
    ctx = TaskContext(cfg)
    batches = list(plan.execute(0, ctx))
    return pa.Table.from_batches(batches, schema=plan.schema())


def _column_bytes(tbl):
    out = []
    for c in tbl.column_names:
        one = pa.table({c: tbl.column(c).combine_chunks()})
        buf = io.BytesIO()
        with ipc.new_stream(buf, one.schema) as w:
            w.write_table(one)
        out.append(buf.getvalue())
    return out


def _assert_parity(cpu_plan, dev_plan, cfg):
    cpu = _collect(cpu_plan, cfg)
    dev = _collect(dev_plan, cfg)
    assert dev_plan.tpu_count >= 1, "device path did not run"
    assert dev_plan.fallback_count == 0, "device path fell back"
    assert _column_bytes(cpu) == _column_bytes(dev)
    return cpu, dev


def _adversarial_table(n=384):
    rng = np.random.default_rng(11)
    f = rng.integers(-40, 40, n).astype(np.float64)
    f[::7] = np.nan
    f[::11] = 0.0
    f[1::11] = -0.0
    return pa.table({
        "f": pa.array(f),
        "i": pa.array(rng.integers(0, 12, n), pa.int64()),
        "inull": pa.array(
            [None if j % 5 == 0 else int(v)
             for j, v in enumerate(rng.integers(0, 7, n))], pa.int32()),
        "s": pa.array([["aa", "b", "aa", "zz", "m"][j % 5] if j % 13 else None
                       for j in range(n)], pa.string()),
    })


@pytest.mark.parametrize("n", [1, 127, 128, 129, 4097])
def test_sort_parity_adversarial(n):
    """NULLS FIRST/LAST per key, NaN and ±0.0 ordering, string keys, multi
    key DESC — byte-identical to the CPU sort, with and without LIMIT, at
    one row, around a power-of-two lane count (the sentinel padding) and
    past several batches."""
    from ballista_tpu.ops.tpu.sort_window import TpuSortStageExec

    tbl = _adversarial_table(n)
    schema = DFSchema.from_arrow(tbl.schema)
    cfg = _cfg()
    keysets = [
        [SortKey(Column("i")), SortKey(Column("f"), ascending=False,
                                       nulls_first=True)],
        [SortKey(Column("inull"), nulls_first=True)],
        [SortKey(Column("inull"), ascending=False, nulls_first=False)],
        [SortKey(Column("s")), SortKey(Column("i"), ascending=False)],
        [SortKey(Column("f"))],
    ]
    for keys in keysets:
        for fetch in (None, 10):
            _assert_parity(
                SortExec(_Src(tbl, schema), keys, fetch),
                TpuSortStageExec(_Src(tbl, schema), keys, fetch, cfg),
                cfg)


TIES_N = 300


@pytest.mark.parametrize("k", [1, 5, TIES_N - 1, TIES_N, TIES_N + 1])
def test_topk_ties_at_cut_boundary(k):
    """Duplicate key values straddling the LIMIT cut: ORDER BY ... LIMIT k
    keeps exactly the rows the CPU engine's stable sort keeps — a cut inside
    a tie run, at the last row, at the row count and past it."""
    from ballista_tpu.ops.tpu.sort_window import TpuSortStageExec

    # every key value appears 20×, so any small LIMIT cuts inside a tie run
    tbl = pa.table({
        "k": pa.array([j % 15 for j in range(TIES_N)], pa.int64()),
        "payload": pa.array(range(TIES_N), pa.int64()),
    })
    schema = DFSchema.from_arrow(tbl.schema)
    cfg = _cfg()
    keys = [SortKey(Column("k"))]
    cpu, _ = _assert_parity(SortExec(_Src(tbl, schema), keys, k),
                            TpuSortStageExec(_Src(tbl, schema), keys, k, cfg),
                            cfg)
    assert cpu.num_rows == min(k, TIES_N)


def test_sort_dictionary_duplicate_values():
    """A dictionary whose entries contain duplicate strings: equal strings
    must share a rank (ties fall to stability), matching the CPU sort of
    the decoded column. The CPU oracle itself cannot sort dictionary
    columns, so this shape is pure device upside."""
    from ballista_tpu.ops.tpu.sort_window import TpuSortStageExec

    codes = pa.array([0, 1, 2, 3, 4, 0, 2, 1, 3, 0] * 30, pa.int32())
    dup = pa.DictionaryArray.from_arrays(
        codes, pa.array(["b", "aa", "b", "c", "aa"]))
    payload = pa.array(range(300), pa.int64())
    tbl = pa.table({"s": dup, "p": payload})
    schema = DFSchema.from_arrow(tbl.schema)
    dec = pa.table({"s": dup.cast(pa.string()), "p": payload})
    dec_schema = DFSchema.from_arrow(dec.schema)
    keys = [SortKey(Column("s"), ascending=False), SortKey(Column("p"))]
    cfg = _cfg()
    devp = TpuSortStageExec(_Src(tbl, schema), keys, None, cfg)
    dev = _collect(devp, cfg)
    assert devp.tpu_count == 1 and devp.fallback_count == 0
    cpu = _collect(SortExec(_Src(dec, dec_schema), keys, None), cfg)
    assert (dev.column("s").cast(pa.string()).combine_chunks().to_pylist()
            == cpu.column("s").combine_chunks().to_pylist())
    assert dev.column("p").combine_chunks().equals(
        cpu.column("p").combine_chunks())


def _window_schema(tbl, wexprs, schema):
    return DFSchema.from_arrow(pa.schema(
        list(tbl.schema)
        + [pa.field(f"w{j}", w.data_type(schema))
           for j, w in enumerate(wexprs)]))


def _float_extremes(rng, n, domain=4):
    """Floats from a small domain (ties) salted with what an order-preserving
    image must get right: ±0.0, NaNs of several payloads, ±inf, subnormals."""
    f = rng.integers(-domain, domain, n).astype(np.float64)
    salt = np.array([0.0, -0.0, np.inf, -np.inf, 5e-324, -5e-324, 1e-310, -1e-310])
    f[::5] = salt[rng.integers(0, len(salt), len(f[::5]))]
    bits = f.view(np.int64)
    nan_at = np.arange(3, n, 7)
    # quiet and signalling payloads, both signs: NaN never equals NaN
    payloads = np.array([0x7FF8000000000000, 0x7FF0000000000001, 0xFFF8000000000123,
                         0x7FFFFFFFFFFFFFFF], dtype=np.uint64).view(np.int64)
    bits[nan_at] = payloads[np.arange(len(nan_at)) % len(payloads)]
    return f


def _nullable(rng, values, every):
    return pa.array([None if j % every == 0 else v for j, v in enumerate(values.tolist())])


def _window_case(shape, rng):
    """(table, the device's table, PARTITION BY, ORDER BY, aggregate args) of
    one adversarial shape; the device's table differs only where the CPU
    oracle cannot sort the column (a dictionary key: it gets the decoded)."""
    n = {"n_1": 1, "n_2047": 2047, "n_2048": 2048, "n_2049": 2049, "n_3001": 3001}.get(shape, 384)
    vnull = pa.array([None if j % 4 == 0 else int(v)
                      for j, v in enumerate(rng.integers(-50, 50, n))], pa.int64())
    fx = pa.array(_float_extremes(rng, n))
    # the aggregates' float argument: the extremes with one NaN payload and
    # no -0.0. A min / max scan carries the order-preserving image, in which
    # -0.0 < +0.0 and a NaN has no payload, where the oracle's
    # np.minimum.accumulate keeps the first NaN's payload and lets ±0.0 tie
    # (so does the parent: the aggregates' scans are not the frame's)
    fm = pa.array(np.where(np.isnan(fx.to_numpy()), np.nan, fx.to_numpy() + 0.0))
    cols = {"g": pa.array(rng.integers(0, 8, n), pa.int64()),
            "o": pa.array(rng.integers(0, 3, n), pa.int64()),
            "vnull": vnull, "fx": fx, "fm": fm}
    over = ([Column("g")], [SortKey(Column("o"))])
    if shape == "float_keys":
        # a float PARTITION BY and a float ORDER BY, both from the extremes
        cols["pf"] = pa.array(_float_extremes(rng, n, domain=3))
        over = ([Column("pf")], [SortKey(Column("fx"), ascending=False)])
    elif shape.startswith("nulls_"):
        _, direction, placement = shape.split("_")
        cols["pnull"] = _nullable(rng, rng.integers(0, 5, n).astype(np.int32), 6).cast(pa.int32())
        cols["onull"] = _nullable(rng, _float_extremes(rng, n, domain=2), 5).cast(pa.float64())
        over = ([Column("pnull")], [SortKey(Column("onull"), ascending=direction == "asc",
                                            nulls_first=placement == "first")])
    elif shape == "int64_high_tie":
        # every key shares one of three high words; the low words decide
        # (across the low word's sign bit too), so the boundary needs them
        hi = np.array([7, -3, 0], dtype=np.int64)[rng.integers(0, 3, n)] << 32
        lo = np.array([0, 1, 5, 0x7FFFFFFF, 0x80000000, 0xFFFFFFFF])[rng.integers(0, 6, n)]
        cols["p64"] = pa.array(hi + lo, pa.int64())
        over = ([Column("p64")], [SortKey(Column("o"), ascending=False)])
    elif shape == "dict_dup":
        codes = pa.array(rng.integers(0, 5, n), pa.int32())
        cols["sd"] = pa.DictionaryArray.from_arrays(codes, pa.array(["b", "aa", "b", "c", "aa"]))
        over = ([Column("sd")], [SortKey(Column("fx"))])
    elif shape == "two_part":
        cols["a"] = pa.array(rng.integers(0, 3, n), pa.int32())
        cols["pf"] = pa.array(_float_extremes(rng, n, domain=2))
        over = ([Column("a"), Column("pf")], [SortKey(Column("o")), SortKey(Column("g"))])
    dev = pa.table(cols)
    cpu = pa.table({c: (a.cast(pa.string()) if pa.types.is_dictionary(a.type) else a)
                    for c, a in cols.items()})
    return cpu, dev, over


WINDOW_SHAPES = ["base", "float_keys", "nulls_asc_first", "nulls_asc_last", "nulls_desc_first",
                 "nulls_desc_last", "int64_high_tie", "dict_dup", "two_part",
                 "n_1", "n_2047", "n_2048", "n_2049", "n_3001"]


@pytest.mark.parametrize("shape", WINDOW_SHAPES)
@pytest.mark.parametrize(
    "func", ["row_number", "rank", "count", "sum", "min", "max", "all"])
def test_window_parity_adversarial(func, shape):
    """row_number/rank/count/sum/min/max — each alone, and all six over their
    shared frames — through the frame program against the CPU oracle, value
    for value and null for null: ties in both keys; ±0.0, NaN payloads, ±inf
    and subnormals in a float ORDER BY and PARTITION BY; nulls in both keys
    under ASC/DESC × NULLS FIRST/LAST; an int64 PARTITION BY whose high words
    tie; a dictionary key with duplicate entries; two partition keys; peer
    frames whose order values repeat ACROSS partition boundaries (scan resets
    must isolate partitions); one row and the sizes around a block of the
    scans and a power-of-two lane count."""
    from ballista_tpu.ops.tpu.sort_window import TpuWindowStageExec

    rng = np.random.default_rng([23, WINDOW_SHAPES.index(shape)])
    cpu_tbl, dev_tbl, over = _window_case(shape, rng)
    if shape == "base":
        # the first shape: the min and the max over frames of their own
        f = rng.integers(-10, 10, len(cpu_tbl)).astype(np.float64)
        f[::9] = np.nan
        cpu_tbl = dev_tbl = cpu_tbl.append_column("f", pa.array(f))
        mn = WindowFunction("min", [Column("f")], [Column("g")],
                            [SortKey(Column("f"), nulls_first=True)], None)
        mx = WindowFunction("max", [Column("vnull")], [],
                            [SortKey(Column("o"), ascending=False)], None)
    else:
        mn = WindowFunction("min", [Column("fm")], *over, None)
        mx = WindowFunction("max", [Column("vnull")], *over, None)
    wexprs = [
        WindowFunction("row_number", [], *over, None),
        WindowFunction("rank", [], *over, None),
        WindowFunction("count", [Column("vnull")], *over, None),
        WindowFunction("sum", [Column("vnull")], *over, None),
        mn, mx,
    ]
    if func != "all":
        wexprs = [w for w in wexprs if w.func == func]
    cfg = _cfg()
    cpu_schema, dev_schema = (DFSchema.from_arrow(t.schema) for t in (cpu_tbl, dev_tbl))
    cpu_plan = WindowExec(_Src(cpu_tbl, cpu_schema), wexprs,
                          _window_schema(cpu_tbl, wexprs, cpu_schema))
    dev_plan = TpuWindowStageExec(_Src(dev_tbl, dev_schema), wexprs,
                                  _window_schema(dev_tbl, wexprs, dev_schema), cfg)
    if cpu_tbl is dev_tbl:
        _assert_parity(cpu_plan, dev_plan, cfg)
        return
    cpu, dev = _collect(cpu_plan, cfg), _collect(dev_plan, cfg)
    assert dev_plan.tpu_count >= 1 and dev_plan.fallback_count == 0
    wins = [f"w{j}" for j in range(len(wexprs))]
    assert _column_bytes(cpu.select(wins)) == _column_bytes(dev.select(wins))


def test_window_empty_and_all_null_partitions():
    """Partitions of size one and partitions whose aggregate argument is
    entirely NULL (SQL: aggregate over zero valid rows is NULL)."""
    from ballista_tpu.ops.tpu.sort_window import TpuWindowStageExec

    g = pa.array([0] * 50 + [1] + [2] * 49 + [3], pa.int64())
    v = pa.array([None] * 50                       # partition 0: all null
                 + [7]                             # singleton partition
                 + [int(x) for x in range(49)]     # dense partition
                 + [None],                         # singleton, null arg
                 pa.int64())
    tbl = pa.table({"g": g, "v": v})
    schema = DFSchema.from_arrow(tbl.schema)
    over = ([Column("g")], [SortKey(Column("v"), nulls_first=True)])
    wexprs = [
        WindowFunction("sum", [Column("v")], *over, None),
        WindowFunction("min", [Column("v")], *over, None),
        WindowFunction("count", [Column("v")], *over, None),
        WindowFunction("rank", [], *over, None),
    ]
    wschema = _window_schema(tbl, wexprs, schema)
    cfg = _cfg()
    _assert_parity(WindowExec(_Src(tbl, schema), wexprs, wschema),
                   TpuWindowStageExec(_Src(tbl, schema), wexprs, wschema, cfg),
                   cfg)


def test_zero_row_input():
    from ballista_tpu.ops.tpu.sort_window import (
        TpuSortStageExec,
        TpuWindowStageExec,
    )

    tbl = pa.table({"a": pa.array([], pa.int64())})
    schema = DFSchema.from_arrow(tbl.schema)
    cfg = _cfg()
    keys = [SortKey(Column("a"))]
    out = _collect(TpuSortStageExec(_Src(tbl, schema), keys, 5, cfg), cfg)
    assert out.num_rows == 0
    wexprs = [WindowFunction("row_number", [], [], [SortKey(Column("a"))], None)]
    wschema = _window_schema(tbl, wexprs, schema)
    out = _collect(TpuWindowStageExec(_Src(tbl, schema), wexprs, wschema, cfg),
                   cfg)
    assert out.num_rows == 0 and out.num_columns == 2


def test_estimate_covers_device_bytes():
    """Fill test: estimate_sort_stage must price at least the bytes the
    stage actually shipped (RUN_STATS device_bytes) — for a plain sort, a
    sort with LIMIT, and a window stage."""
    from ballista_tpu.ops.tpu import fusion
    from ballista_tpu.ops.tpu.sort_window import (
        TpuSortStageExec,
        TpuWindowStageExec,
        _encode_key_arrays,
    )
    from ballista_tpu.ops.tpu.stage_compiler import RUN_STATS

    tbl = _adversarial_table()
    n = tbl.num_rows
    schema = DFSchema.from_arrow(tbl.schema)
    cfg = _cfg()
    keys = [SortKey(Column("inull"), nulls_first=True),
            SortKey(Column("f"), ascending=False)]
    batch = tbl.combine_chunks().to_batches()[0]
    arrays = [batch.column("inull"), batch.column("f")]
    _, key_meta = _encode_key_arrays(
        arrays, [(k.ascending, k.nulls_first) for k in keys])

    for fetch in (None, 8):
        devp = TpuSortStageExec(_Src(tbl, schema), keys, fetch, cfg)
        _collect(devp, cfg)
        assert devp.tpu_count == 1
        actual = int(RUN_STATS.snapshot()["device_bytes"])
        est = fusion.estimate_sort_stage(n, key_meta)
        assert est.table_bytes >= actual > 0, (est.table_bytes, actual)

    wexprs = [
        WindowFunction("sum", [Column("i")], [Column("i")],
                       [SortKey(Column("f"))], None),
        WindowFunction("rank", [], [Column("i")], [SortKey(Column("f"))],
                       None),
    ]
    wschema = _window_schema(tbl, wexprs, schema)
    devp = TpuWindowStageExec(_Src(tbl, schema), wexprs, wschema, cfg)
    _collect(devp, cfg)
    assert devp.tpu_count == 1
    actual = int(RUN_STATS.snapshot()["device_bytes"])
    warrays = [batch.column("i"), batch.column("f")]
    _, wmeta = _encode_key_arrays(warrays, [(True, False), (True, False)])
    west = fusion.estimate_sort_stage(n, wmeta, window_funcs=len(wexprs))
    assert west.table_bytes >= actual > 0, (west.table_bytes, actual)


def test_counters_flow_to_heartbeat_gauges():
    """RunStats → ExecutorProcess._tpu_metrics: the sort-family gauges are
    exported once the family has run (stats-sync invariant, live)."""
    from ballista_tpu.executor.executor_process import ExecutorProcess
    from ballista_tpu.ops.tpu.sort_window import TpuSortStageExec

    tbl = _adversarial_table()
    schema = DFSchema.from_arrow(tbl.schema)
    cfg = _cfg()
    devp = TpuSortStageExec(_Src(tbl, schema),
                            [SortKey(Column("i"))], 5, cfg)
    _collect(devp, cfg)
    gauges = dict(ExecutorProcess._tpu_metrics())
    for key in ("tpu_sort_kernel_s", "tpu_sort_invocations",
                "tpu_sort_full_materializations"):
        assert key in gauges, key
    assert gauges["tpu_sort_invocations"] >= 1
    assert gauges["tpu_sort_full_materializations"] >= 1


def test_engine_wiring_and_knob_gate():
    """maybe_compile_tpu wraps SortExec/WindowExec when the family knob is
    on, and leaves the plan untouched when it is off."""
    from ballista_tpu.client.context import SessionContext
    from ballista_tpu.config import EXECUTOR_ENGINE
    from ballista_tpu.engine.tpu_engine import maybe_compile_tpu
    from ballista_tpu.ops.tpu.sort_window import (
        TpuSortStageExec,
        TpuWindowStageExec,
    )

    from .conftest import iter_plan

    rng = np.random.default_rng(3)
    t = pa.table({
        "g": pa.array(rng.integers(0, 5, 500), pa.int64()),
        "v": pa.array(rng.integers(0, 99, 500), pa.int64()),
    })
    sql = ("SELECT g, v, rank() OVER (PARTITION BY g ORDER BY v) rk "
           "FROM t ORDER BY v DESC, g LIMIT 20")
    for enabled in (True, False):
        cfg = BallistaConfig({EXECUTOR_ENGINE: "tpu", TPU_MIN_ROWS: 0,
                              TPU_SORT_ENABLED: enabled})
        ctx = SessionContext(cfg)
        ctx.register_arrow_table("t", t, partitions=2)
        phys = maybe_compile_tpu(
            ctx.create_physical_plan(ctx.sql(sql).plan), cfg)
        nodes = [nd for nd in iter_plan(phys)
                 if isinstance(nd, (TpuSortStageExec, TpuWindowStageExec))]
        if enabled:
            assert nodes, phys.display()
        else:
            assert not nodes, phys.display()


def test_limit_is_full_sort_then_slice():
    """ORDER BY ... LIMIT runs on the device as the full order, sliced:
    sort_full_materializations counts it, a sort without LIMIT does not."""
    from ballista_tpu.ops.tpu.sort_window import (
        TpuSortStageExec,
        counters_snapshot,
    )

    tbl = _adversarial_table()
    schema = DFSchema.from_arrow(tbl.schema)
    cfg = _cfg()
    keys = [SortKey(Column("i"))]
    counts = [counters_snapshot()["sort_full_materializations"]]
    for fetch in (5, None):
        _assert_parity(SortExec(_Src(tbl, schema), keys, fetch),
                       TpuSortStageExec(_Src(tbl, schema), keys, fetch, cfg), cfg)
        counts.append(counters_snapshot()["sort_full_materializations"])
    assert counts[1] == counts[0] + 1 and counts[2] == counts[1]


def test_float_extremes_cross_the_device_as_ordered_int64():
    """Window min/max scans never carry floats (a TPU does not return f64
    values bit-identical): the order-preserving int64 image is exactly
    invertible — ±0.0, subnormals, infinities, NaN payloads — and orders
    like the floats."""
    from ballista_tpu.ops.tpu.sort_window import _f64_to_ordered, _ordered_to_f64

    rng = np.random.default_rng(3)
    v = np.concatenate([rng.normal(size=2000) * 1e5,
                        [0.0, -0.0, np.inf, -np.inf, 5e-324, -5e-324,
                         1e-310, -1e-310, np.nan, -np.nan]])
    enc = _f64_to_ordered(v)
    assert enc.dtype == np.int64
    assert np.array_equal(_ordered_to_f64(enc).view(np.int64), v.view(np.int64))
    real = ~np.isnan(v)
    assert np.array_equal(v[real][np.argsort(enc[real], kind="stable")],
                          np.sort(v[real], kind="stable"))
    assert _f64_to_ordered(np.array([-0.0]))[0] < _f64_to_ordered(np.array([0.0]))[0]


@pytest.mark.parametrize("ascending", [True, False])
def test_the_devices_key_lanes_are_the_hosts_bit_for_bit(ascending):
    """The window frame program encodes its keys on the device; the sort
    family keeps the host encoder (`_encode_key_arrays`). On the same
    extremes — ±0.0, subnormals, ±inf, NaNs of several payloads and signs,
    int64's own extremes — both give the same lanes bit for bit, in both
    directions, so the two cannot drift apart."""
    from ballista_tpu.ops.tpu.runtime import ensure_jax
    from ballista_tpu.ops.tpu.sort_window import _device_lane, _encode_key_arrays

    jax = ensure_jax()
    rng = np.random.default_rng(5)
    v = np.concatenate([rng.normal(size=500) * 1e5,
                        [0.0, -0.0, np.inf, -np.inf, 5e-324, -5e-324, 1e-310, -1e-310,
                         np.finfo(np.float64).max, -np.finfo(np.float64).max]])
    nan_bits = np.array([0x7FF8000000000000, 0x7FF0000000000001, 0xFFF8000000000123,
                         0x7FFFFFFFFFFFFFFF, 0xFFFFFFFFFFFFFFFF], dtype=np.uint64).view(np.int64)
    v = np.concatenate([v, nan_bits.view(np.float64)])
    i = np.array([-2**63, -2**63 + 1, -2**32, -1, 0, 1, 2**31, 2**63 - 2, 2**63 - 1],
                 dtype=np.int64)
    for values, src in ((v.view(np.int64), "f64"), (i, "int")):
        arr = pa.array(values.view(np.float64) if src == "f64" else values)
        (key_ops,), _ = _encode_key_arrays([arr], [(ascending, False)])
        host = key_ops[1]
        dev = np.asarray(jax.jit(lambda x, s=src: _device_lane(x, s, ascending))(values))
        assert dev.dtype == np.int64 and np.array_equal(dev, host), src


def test_a_row_number_frame_is_one_device_program():
    """h2o q8's shape (PARTITION BY an int32 key, ORDER BY a float64 one DESC,
    row_number): the task builds its frame in ONE program — one device call
    (`bt.device.exec`, or `bt.compile.xla` on its first), `window_frames_fused`
    1, `window_scans` 0 — whose jitted name is one the benchmark's
    `window_roofline` reads device seconds under."""
    import json
    import pathlib

    from ballista_tpu.ops.tpu import sort_window as sw
    from ballista_tpu.tracing import RUN_STATS

    rng = np.random.default_rng(37)
    n = 3000
    tbl = pa.table({"id6": pa.array(rng.integers(1, 60, n), pa.int32()),
                    "v3": pa.array(np.round(rng.uniform(0, 100, n), 6))})
    schema = DFSchema.from_arrow(tbl.schema)
    wexprs = [WindowFunction("row_number", [], [Column("id6")],
                             [SortKey(Column("v3"), ascending=False)], None)]
    cfg = _cfg()
    plan = sw.TpuWindowStageExec(_Src(tbl, schema), wexprs,
                                 _window_schema(tbl, wexprs, schema), cfg)
    RUN_STATS.clear()
    RUN_STATS.take_job_spans("-")  # what earlier tests left outside any job
    _collect(plan, cfg)
    assert plan.tpu_count == 1 and plan.fallback_count == 0
    spans = RUN_STATS.take_job_spans("-")["spans"]
    (rec,) = [r for t, r in RUN_STATS.stages().items() if t.startswith("window_")]
    assert rec["window_frames_fused"] == 1 and rec["window_scans"] == 0
    assert rec["window_lanes"] == 4096 and rec["dispatches"] == 1
    calls = [s for s in spans if s[0] in ("bt.device.exec", "bt.compile.xla")]
    assert len(calls) == 1
    kernel = calls[0][7]["kernel"]
    assert calls[0][7]["lanes"] == 4096 and calls[0][7]["rows"] == n
    # the program that ran, and the name its operations carry in a trace
    keys = (("int", True, False, False, True), ("f64", False, False, False, False))
    program = sw._frame_jit(keys, ("row_number",), False, 4096)
    text = program.lower(np.int32(n), np.zeros(4096, np.int32),
                         np.zeros(4096, np.int64)).as_text()
    assert f"module @jit_window_{kernel} " in text
    metric = pathlib.Path(__file__).parents[1] / "bench" / "metrics" / "window_roofline.json"
    modules = json.loads(metric.read_text())["args"]["modules"]
    assert any(f"jit_window_{kernel}".startswith(m) for m in modules), (kernel, modules)
    assert sw.counters_snapshot()["window_fused_frames"] >= 1


@pytest.mark.parametrize("dtypes", [("int32",), ("int64",), ("int32", "int32", "int64")])
def test_lex_order_sorted_is_lex_orders_permutation_and_its_leading_lane(dtypes):
    """`kernels.lex_order_sorted` (the window frame program's ordering) gives
    `lex_order`'s permutation, ties and all, and the most significant 32-bit
    lane in that order — one key, one 64-bit key (two lanes), three keys."""
    from ballista_tpu.ops.tpu.kernels import _order_lanes, lex_order, lex_order_sorted
    from ballista_tpu.ops.tpu.runtime import ensure_jax

    jax = ensure_jax()
    rng = np.random.default_rng(len(dtypes))
    n = 3000
    keys = [rng.integers(-3, 3, n).astype(d) << (33 if d == "int64" else 0) for d in dtypes]
    perm, top = jax.jit(lambda *k: lex_order_sorted(list(k)))(*keys)
    want = jax.jit(lambda *k: lex_order(list(k)))(*keys)
    assert np.array_equal(np.asarray(perm), np.asarray(want))
    lead = np.asarray(_order_lanes(jax.numpy.asarray(keys[0]))[0])
    assert np.array_equal(np.asarray(top), lead[np.asarray(want)])


# ---------------------------------------------------------------------------
# kernels.segmented_scan: the blocked scan against the sequential loop


def _sequential_scan(v, b, func):
    """The definition: lane by lane, restarting where a boundary is set."""
    op = {"sum": lambda a, c: a + c, "min": min, "max": max}[func]
    out = [int(v[0])]
    for i in range(1, len(v)):
        out.append(int(v[i]) if b[i] else op(out[-1], int(v[i])))
    # int64 addition wraps: so does the device's
    return np.array([((x + 2**63) % 2**64) - 2**63 for x in out], dtype=np.int64)


def _blocked_scan(v, b, func, block=2048):
    from ballista_tpu.ops.tpu.kernels import segmented_scan
    from ballista_tpu.ops.tpu.runtime import ensure_jax

    jnp = ensure_jax().numpy
    return np.asarray(segmented_scan(jnp.asarray(v), jnp.asarray(b), func, block))


SCAN_LENGTHS = [1, 63, 64, 2047, 2048, 2049, 3 * 2048 + 5, 1 << 17]


@pytest.mark.parametrize("func", ["sum", "min", "max"])
@pytest.mark.parametrize("n", SCAN_LENGTHS)
def test_blocked_scan_is_the_sequential_scan_bit_for_bit(n, func):
    """Seeded int64 lanes, boundaries a lane in about fifty (segments that
    cross blocks, and the blocks of carries at 2^17)."""
    rng = np.random.default_rng([n, len(func)])
    v = rng.integers(-2**62, 2**62, n, dtype=np.int64)
    b = rng.random(n) < 0.02
    assert (_blocked_scan(v, b, func) == _sequential_scan(v, b, func)).all()
    # the jitted program the window family dispatches is the same function
    from ballista_tpu.ops.tpu.sort_window import _segscan_jit

    assert (np.asarray(_segscan_jit(func)(v, b)) == _sequential_scan(v, b, func)).all()


@pytest.mark.parametrize("func", ["sum", "min", "max"])
@pytest.mark.parametrize("where", ["block_first", "block_last", "every_lane", "lane_0_only",
                                   "no_flag_at_all"])
def test_blocked_scan_boundaries_at_the_blocks_edges(where, func):
    n = 3 * 2048 + 5
    rng = np.random.default_rng(7)
    v = rng.integers(-1000, 1000, n, dtype=np.int64)
    b = np.zeros(n, dtype=bool)
    if where == "block_first":
        b[::2048] = True
    elif where == "block_last":
        b[2047::2048] = True
    elif where == "every_lane":
        b[:] = True
    elif where == "lane_0_only":
        b[0] = True
    # "no_flag_at_all": lane 0 starts a segment whatever its flag says
    assert (_blocked_scan(v, b, func) == _sequential_scan(v, b, func)).all()
    # a block that does not divide the length, and one shorter than the carries' count
    assert (_blocked_scan(v, b, func, block=64) == _sequential_scan(v, b, func)).all()


@pytest.mark.parametrize("func", ["min", "max"])
def test_blocked_scan_keeps_int64s_extremes(func):
    """The window family's identities and NaN marks ARE int64's extremes
    (`_emit_scan_agg`): no lane may be combined with a made-up identity."""
    n = 2 * 2048 + 1
    rng = np.random.default_rng(11)
    v = rng.choice(np.array([-2**63, -2**63 + 1, -1, 0, 1, 2**63 - 2, 2**63 - 1],
                            dtype=np.int64), n)
    b = rng.random(n) < 0.01
    assert (_blocked_scan(v, b, func) == _sequential_scan(v, b, func)).all()
