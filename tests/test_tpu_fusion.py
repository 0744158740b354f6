"""The one lowering, end to end: the device stage (`fused_xla`, the only
way a stage reaches the device) against the CPU engine's answer for the same
plan on TPC-H shaped stages, against numpy on the arithmetic and the lookups
the benchmark's limits rest on, and its RunStats / heartbeat surface.

These run the stage compiler end-to-end (jax CPU backend) and are heavier
than tests/test_fusion.py's pure unit tests.
"""

import numpy as np
import pyarrow as pa
import pytest

from ballista_tpu.config import (
    BallistaConfig,
    EXECUTOR_ENGINE,
    TPU_MIN_ROWS,
)

from .conftest import tpch_query


def _ctx(engine, tbl_parts=None, tpch_dir=None):
    from ballista_tpu.client.context import SessionContext

    cfg = BallistaConfig({EXECUTOR_ENGINE: engine, TPU_MIN_ROWS: 0})
    ctx = SessionContext(cfg)
    if tbl_parts:
        for name, (tbl, parts) in tbl_parts.items():
            ctx.register_arrow_table(name, tbl, partitions=parts)
    if tpch_dir is not None:
        from ballista_tpu.testing.tpchgen import register_tpch

        register_tpch(ctx, tpch_dir)
    return ctx


def _on_device(sql, tbl_parts=None, tpch_dir=None):
    """Collect `sql` on the tpu engine; the partial stage must have run on
    the device and nothing may have left it. Returns (table, stats)."""
    import ballista_tpu.ops.tpu.stage_compiler as sc

    sc.RUN_STATS.clear()
    sc.STAGE_OUTCOMES.clear()
    out = _ctx("tpu", tbl_parts, tpch_dir).sql(sql).collect()
    led = sc.STAGE_OUTCOMES.snapshot()
    assert led["error"] == 0 and led["declined"] == 0, led["recent"]
    assert ("partial", "device", "") in [tuple(r) for r in led["recent"]], led
    return out, sc.RUN_STATS.snapshot()


def _assert_same_answer(dev: pa.Table, cpu: pa.Table):
    """The device's rows are the CPU engine's: every column equal, float
    columns to the last few ulps (the two engines add in another order)."""
    assert dev.schema.names == cpu.schema.names
    assert dev.num_rows == cpu.num_rows
    for name in dev.schema.names:
        d, c = dev[name].combine_chunks(), cpu[name].combine_chunks()
        if pa.types.is_floating(d.type):
            np.testing.assert_allclose(
                d.to_numpy(zero_copy_only=False),
                c.to_numpy(zero_copy_only=False), rtol=1e-12, err_msg=name)
        else:
            assert d.equals(c), name


def _synth(n=50_000, seed=5, cats=5):
    rng = np.random.default_rng(seed)
    names = [f"c{i:04d}" for i in range(cats)]
    return pa.table({
        "cat": rng.choice(names, n),
        "price": np.round(rng.uniform(1, 100, n), 2),  # money (int64 cents)
        "w": rng.uniform(0.0, 10.0, n),                # true f64
        "qty": rng.integers(1, 50, n),
    })


# ------------------------------------------- device stage vs the CPU engine


@pytest.mark.parametrize("q", [1, 6, 12, 19])
def test_tpch_parity_device_vs_cpu_engine(q, tpch_dir):
    """What a user compares: the same plan's answer with the partial stage
    on the device and with every operator on the CPU engine."""
    sql = tpch_query(q)
    dev, stats = _on_device(sql, tpch_dir=tpch_dir)
    _assert_same_answer(dev, _ctx("cpu", tpch_dir=tpch_dir).sql(sql).collect())
    assert stats.get("fused_spans", 0) >= 2


def test_parity_with_join_filter_project(tpch_dir):
    """filter→project→join-probe→partial-agg combo (q14 shape): the probe
    gathers too give the CPU engine's answer."""
    sql = tpch_query(14)
    dev, _ = _on_device(sql, tpch_dir=tpch_dir)
    _assert_same_answer(dev, _ctx("cpu", tpch_dir=tpch_dir).sql(sql).collect())


def test_parity_synthetic_all_agg_funcs():
    sql = ("select cat, sum(price) s, sum(w) ws, count(*) c, min(qty) mn, "
           "max(qty) mx from t where qty > 7 group by cat order by cat")
    tables = {"t": (_synth(), 4)}
    dev, _ = _on_device(sql, tables)
    _assert_same_answer(dev, _ctx("cpu", tables).sql(sql).collect())


# ------------------------------------------------ float sums stay float64


@pytest.mark.parametrize("G", [2, 8, 128, 129, 300, 4096, 4097])
def test_grouped_float_sums_are_float64(G):
    """A grouped sum + count of a true-float column (encoded `f64`, not
    fixed-point) is float64 arithmetic on the device: within 1e-12 of
    numpy's float64 — a float32 accumulation sits 1e-7 away, the step the
    benchmark's `rel_err` limit refuses. Over group domains on both sides
    of the direct form's 64-group budget and of every power of two a
    dictionary pads to."""
    from ballista_tpu.ops.tpu.columnar import encode_column

    rng = np.random.default_rng(G)
    n = max(30_000, 8 * G)
    codes = np.r_[np.arange(G), rng.integers(0, G, n - G)]  # every group lives
    w = rng.uniform(0.0, 10.0, n)
    assert encode_column(pa.array(w)).kind == "f64"
    tbl = pa.table({"cat": np.array([f"c{i:05d}" for i in range(G)])[codes],
                    "w": w, "qty": rng.integers(1, 50, n)})
    out, _ = _on_device("select cat, sum(w) s, count(w) c from t where qty > 10 "
                        "group by cat order by cat", {"t": (tbl, 4)})
    keep = np.asarray(tbl["qty"]) > 10
    want_s = np.bincount(codes[keep], weights=w[keep], minlength=G)
    want_c = np.bincount(codes[keep], minlength=G)
    live = want_c > 0
    assert out["cat"].to_pylist() == [f"c{i:05d}" for i in np.flatnonzero(live)]
    assert out["c"].to_pylist() == want_c[live].tolist()
    assert out["s"].type == pa.float64()
    np.testing.assert_allclose(out["s"].to_numpy(), want_s[live], rtol=1e-12)


# ---------------------------------------------------- direct join probe


@pytest.mark.parametrize("build_rows", [1, 1000, 1 << 18, (1 << 18) + 1])
def test_direct_join_probe_matches_numpy(build_rows):
    """The direct (key → row) table lookup: probe keys below, inside and
    past the table, NULL probe keys, hits and misses — against numpy, at
    build sizes around 2^18 entries."""
    import ballista_tpu.ops.tpu.stage_compiler as sc
    from ballista_tpu.engine.tpu_engine import maybe_compile_tpu
    from ballista_tpu.plan.physical import HashJoinExec, TaskContext

    rng = np.random.default_rng(build_rows)
    B = build_rows
    n = max(40_000, 2 * B)  # the larger side is the one the stage scans
    ids = rng.permutation(2 * B)[:B].astype("int64")  # unique, half the range
    grp_of = rng.integers(0, 7, B)
    build = pa.table({"id": ids, "grp": np.array([f"g{i}" for i in range(7)])[grp_of]})
    fk = rng.integers(-3, 2 * B + 3, n).astype("int64")
    null_fk = rng.random(n) < 0.05
    x = rng.integers(1, 1000, n).astype("int64")
    probe = pa.table({"fk": pa.array(fk, mask=null_fk), "x": x})
    sql = ("select grp, count(*) c, sum(x) s from build join probe on fk = id "
           "group by grp order by grp")
    tables = {"probe": (probe, 2), "build": (build, 2)}
    out, _ = _on_device(sql, tables)

    row_of = np.full(2 * B + 6, -1)
    row_of[ids] = np.arange(B)
    hit = ~null_fk & (fk >= 0) & (fk < 2 * B)
    hit[hit] = row_of[fk[hit]] >= 0
    g = grp_of[row_of[fk[hit]]]
    want_c = np.bincount(g, minlength=7)
    want_s = np.bincount(g, weights=x[hit], minlength=7).astype("int64")
    live = want_c > 0
    assert out["grp"].to_pylist() == [f"g{i}" for i in np.flatnonzero(live)]
    assert out["c"].to_pylist() == want_c[live].tolist()
    assert out["s"].to_pylist() == want_s[live].tolist()

    ctx = _ctx("tpu", tables)
    phys = maybe_compile_tpu(ctx.create_physical_plan(ctx.sql(sql).plan), ctx.config)
    stage = next(nd for nd in _walk(phys) if isinstance(nd, sc.TpuStageExec))
    op, = [o for o in stage.ops if isinstance(o, HashJoinExec)]
    bt = stage._prepare_build(op, 0, TaskContext(ctx.config),
                              sc.DEVICE_CACHE.key_of(stage.scan))
    assert bt.mode == "direct" and bt.dup == 1 and bt.keys.shape[0] >= B


# ------------------------------------- string predicates over dictionaries


@pytest.mark.parametrize("T", [100, 70_000])
@pytest.mark.parametrize("kind", ["eq", "prefix", "like_literal"])
def test_string_predicate_over_dictionary_codes(kind, T):
    """A string predicate is a host-built boolean LUT gathered by dictionary
    code on the device — equality, a LIKE prefix and a LIKE without
    wildcards, over a dictionary that fits 16-bit codes and one that does
    not."""
    rng = np.random.default_rng(T)
    n = max(30_000, T + 1000)
    codes = np.r_[np.arange(T), rng.integers(0, T, n - T)]
    words = np.array([f"w{i:06d}" for i in range(T)])
    x = rng.integers(1, 100, n).astype("int64")
    tbl = pa.table({"s": words[codes], "x": x})
    pred, keep = {
        "eq": ("s = 'w000042'", codes == 42),
        "prefix": ("s like 'w00000%'", codes < 10),
        "like_literal": ("s like 'w000077'", codes == 77),
    }[kind]
    for neg in (False, True):
        sql = f"select count(*) c, sum(x) sx from t where {'not ' if neg else ''}{pred}"
        out, _ = _on_device(sql, {"t": (tbl, 4)})
        k = ~keep if neg else keep
        assert out["c"].to_pylist() == [int(k.sum())], sql
        assert out["sx"].to_pylist() == [int(x[k].sum())], sql


# ------------------------------------------------- stats/heartbeat surface


def test_runstats_and_heartbeat_gauges(tpch_dir):
    """The stage lands in RunStats with its span count and the [P, N] stack
    it ran over, and in the heartbeat's gauges."""
    from ballista_tpu.executor.executor_process import ExecutorProcess

    _, stats = _on_device(tpch_query(1), tpch_dir=tpch_dir)
    assert stats.get("fused_spans", 0) >= 2  # filter→project→agg stage
    P, N = stats["table_shape"]
    assert P >= 1 and N & (N - 1) == 0  # a [P, bucket] stack

    gauges = dict(ExecutorProcess._tpu_metrics())
    assert gauges.get("tpu_fused_spans", 0.0) >= 2.0


def _walk(node):
    yield node
    for c in node.children():
        yield from _walk(c)
