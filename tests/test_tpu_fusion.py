"""Whole-stage fusion end-to-end tests: fused-vs-staged parity on TPC-H
shaped stages, the Pallas kernel paths through the full engine, the
fallback ladder, and RunStats/heartbeat visibility.

These run the stage compiler end-to-end (jax CPU backend, Pallas in
interpreter mode) and are heavier than tests/test_fusion.py's pure unit
tests.
"""

import numpy as np
import pyarrow as pa
import pytest

from ballista_tpu.config import (
    BallistaConfig,
    EXECUTOR_ENGINE,
    TPU_FUSION_ENABLED,
    TPU_FUSION_MIN_ROWS,
    TPU_FUSION_MODE,
    TPU_MIN_ROWS,
)

from .conftest import tpch_query


def _ctx(tbl_parts=None, tpch_dir=None, **cfg_extra):
    from ballista_tpu.client.context import SessionContext

    cfg = BallistaConfig({EXECUTOR_ENGINE: "tpu", TPU_MIN_ROWS: 0, **cfg_extra})
    ctx = SessionContext(cfg)
    if tbl_parts:
        for name, (tbl, parts) in tbl_parts.items():
            ctx.register_arrow_table(name, tbl, partitions=parts)
    if tpch_dir is not None:
        from ballista_tpu.testing.tpchgen import register_tpch

        register_tpch(ctx, tpch_dir)
    return ctx


def _run_mode(sql, mode, tbl_parts=None, tpch_dir=None, **cfg_extra):
    """Collect `sql` under a forced fusion mode; return (table, stats)."""
    import ballista_tpu.ops.tpu.stage_compiler as sc

    ctx = _ctx(tbl_parts, tpch_dir, **{TPU_FUSION_MODE: mode, **cfg_extra})
    sc.RUN_STATS.clear()
    out = ctx.sql(sql).collect()
    return out, sc.RUN_STATS.snapshot()


def _synth(n=50_000, seed=5, cats=5):
    rng = np.random.default_rng(seed)
    names = [f"c{i:04d}" for i in range(cats)]
    return pa.table({
        "cat": rng.choice(names, n),
        "price": np.round(rng.uniform(1, 100, n), 2),  # money (int64 cents)
        "w": rng.uniform(0.0, 10.0, n),                # true f64
        "qty": rng.integers(1, 50, n),
    })


# ----------------------------------------------------- staged/fused parity


@pytest.mark.parametrize("q", [1, 6, 12, 19])
def test_tpch_parity_staged_vs_fused(q, tpch_dir):
    """Staged and fused_xla trace the SAME jnp expressions over the same
    inputs — results must be byte-identical, not just allclose. (A stage
    that is staged-ineligible clamps to fused_xla; q1/q6 must genuinely
    run staged.)"""
    sql = tpch_query(q)
    fused, s_f = _run_mode(sql, "fused_xla", tpch_dir=tpch_dir)
    staged, s_s = _run_mode(sql, "staged", tpch_dir=tpch_dir)
    assert s_f.get("fusion_mode") == "fused_xla"
    assert s_s.get("fusion_mode") in ("staged", "fused_xla")
    assert staged.combine_chunks().equals(fused.combine_chunks())
    if q in (1, 6):
        assert s_s.get("fusion_mode") == "staged"
        # staged mode carries the per-span roofline split
        assert set(s_s.get("span_s", {})) == {"predicate", "project", "aggregate"}
        assert s_s.get("fused_spans") == 0
        assert s_f.get("fused_spans", 0) >= 2


def test_parity_with_join_filter_project(tpch_dir):
    """filter→project→join-probe→partial-agg combo (q14 shape): fused and
    staged byte-identical through the probe gathers too."""
    sql = tpch_query(14)
    fused, s_f = _run_mode(sql, "fused_xla", tpch_dir=tpch_dir)
    staged, s_s = _run_mode(sql, "staged", tpch_dir=tpch_dir)
    assert staged.combine_chunks().equals(fused.combine_chunks())
    # q14's stage joins through part (unique direct build): staged-eligible
    assert s_s.get("fusion_mode") == "staged"


def test_parity_synthetic_all_agg_funcs():
    sql = ("select cat, sum(price) s, sum(w) ws, count(*) c, min(qty) mn, "
           "max(qty) mx from t where qty > 7 group by cat order by cat")
    tbl = _synth()
    fused, s_f = _run_mode(sql, "fused_xla", {"t": (tbl, 4)})
    staged, s_s = _run_mode(sql, "staged", {"t": (tbl, 4)})
    assert s_s.get("fusion_mode") == "staged"
    assert staged.combine_chunks().equals(fused.combine_chunks())


# ----------------------------------------------------------- pallas paths


def test_fused_pallas_forced_via_fusion_mode():
    """ballista.tpu.fusion.mode=fused_pallas routes eligible stages through
    the kernels (interpret mode on CPU); f32 sums carry a tolerance, counts
    are exact, and the mode is visible in RunStats."""
    sql = ("select cat, sum(w) s, count(*) c from t where qty > 10 "
           "group by cat order by cat")
    tbl = _synth(n=30_000, seed=21)
    pallas, s_p = _run_mode(sql, "fused_pallas", {"t": (tbl, 4)})
    staged, _ = _run_mode(sql, "staged", {"t": (tbl, 4)})
    assert s_p.get("fusion_mode") == "fused_pallas"
    assert s_p.get("fusion_reason", "").startswith("forced")
    p, s = pallas.to_pandas(), staged.to_pandas()
    assert p.cat.tolist() == s.cat.tolist()
    assert (p.c.values == s.c.values).all()
    np.testing.assert_allclose(p.s.values, s.s.values, rtol=2e-5)


def test_pallas_multi_tile_group_domain():
    """G past the old 128-lane/64-budget ceilings: a ~300-category domain
    (pow2 → 512) runs the multi-tile kernel grid, compared against the
    sorted path which is oracle-exact."""
    import ballista_tpu.ops.tpu.stage_compiler as sc
    from ballista_tpu.engine.tpu_engine import maybe_compile_tpu
    from ballista_tpu.plan.physical import TaskContext

    sql = ("select cat, sum(w) s, count(*) c from t group by cat "
           "order by cat")
    tbl = _synth(n=40_000, seed=13, cats=300)
    pallas, s_p = _run_mode(sql, "fused_pallas", {"t": (tbl, 4)})
    ref, s_r = _run_mode(sql, "fused_xla", {"t": (tbl, 4)})
    assert s_p.get("fusion_mode") == "fused_pallas"
    # fused_xla at G=512 exceeds the unroll budget → sorted path (still
    # one fused kernel, exact math)
    assert s_r.get("fusion_mode") == "fused_xla"
    p, r = pallas.to_pandas(), ref.to_pandas()
    assert p.cat.tolist() == r.cat.tolist()
    assert (p.c.values == r.c.values).all()
    np.testing.assert_allclose(p.s.values, r.s.values, rtol=2e-5)

    # and the stage really ran on device, zero fallbacks
    cfg = BallistaConfig({EXECUTOR_ENGINE: "tpu", TPU_MIN_ROWS: 0,
                          TPU_FUSION_MODE: "fused_pallas"})
    from ballista_tpu.client.context import SessionContext

    ctx = SessionContext(cfg)
    ctx.register_arrow_table("t", tbl, partitions=4)
    phys = maybe_compile_tpu(ctx.create_physical_plan(ctx.sql(sql).plan), cfg)
    stages = [n for n in _walk(phys) if isinstance(n, sc.TpuStageExec)]
    assert stages
    tc = TaskContext(cfg)
    for p_ in range(phys.output_partition_count()):
        list(phys.execute(p_, tc))
    assert sum(s.tpu_count for s in stages) >= 1
    assert sum(s.fallback_count for s in stages) == 0


def test_pallas_fallback_ladder_to_fused_xla():
    """fused_pallas requested for a money-sum stage at large G: the kernel
    family can't carry exact int64 cents, the trace raises Unsupported, and
    the ladder lands on fused_xla (sorted) — NOT the CPU engine."""
    import ballista_tpu.ops.tpu.stage_compiler as sc
    from ballista_tpu.engine.tpu_engine import maybe_compile_tpu
    from ballista_tpu.plan.physical import TaskContext

    sql = ("select cat, sum(price) s, count(*) c from t group by cat "
           "order by cat")
    tbl = _synth(n=30_000, seed=3, cats=300)
    out, stats = _run_mode(sql, "fused_pallas", {"t": (tbl, 4)})
    assert stats.get("fusion_mode") == "fused_xla"  # clamped by the ladder
    df = tbl.to_pandas()
    g = (df.groupby("cat", as_index=False)
         .agg(s=("price", "sum"), c=("price", "size")).sort_values("cat"))
    o = out.to_pandas()
    assert o.cat.tolist() == g.cat.tolist()
    # engine money math is exact int64 cents; pandas' float accumulation
    # is the noisy side of this comparison
    np.testing.assert_allclose(o.s.values.astype(float), g.s.values, rtol=1e-12)
    assert (o.c.values == g.c.values).all()

    cfg = BallistaConfig({EXECUTOR_ENGINE: "tpu", TPU_MIN_ROWS: 0,
                          TPU_FUSION_MODE: "fused_pallas"})
    from ballista_tpu.client.context import SessionContext

    ctx = SessionContext(cfg)
    ctx.register_arrow_table("t", tbl, partitions=4)
    phys = maybe_compile_tpu(ctx.create_physical_plan(ctx.sql(sql).plan), cfg)
    stages = [n for n in _walk(phys) if isinstance(n, sc.TpuStageExec)]
    assert stages
    tc = TaskContext(cfg)
    for p_ in range(phys.output_partition_count()):
        list(phys.execute(p_, tc))
    assert sum(s.fallback_count for s in stages) == 0


# ------------------------------------------------------- cost model in situ


def test_auto_small_input_staged():
    """The cost model's staged fallback, end to end: tiny staged-eligible
    input in auto mode → staged execution, with the reason recorded."""
    sql = "select cat, sum(w) s, count(*) c from t group by cat order by cat"
    tbl = _synth(n=2_000, seed=9)
    out, stats = _run_mode(sql, "auto", {"t": (tbl, 2)})
    assert stats.get("fusion_mode") == "staged"
    assert "fusion.min.rows" in stats.get("fusion_reason", "")
    # and above the threshold the same shape fuses
    big = _synth(n=20_000, seed=9)
    out2, stats2 = _run_mode(sql, "auto", {"t": (big, 2)})
    assert stats2.get("fusion_mode") == "fused_xla"


def test_cost_model_choice_is_the_mode_that_runs():
    """Auto selection (driven here by the legacy pallas knob, the CPU
    backend's stand-in for platform=tpu) knows from the encode metadata
    which value lanes the f32 kernel takes: an f64 sum is chosen AND runs
    fused_pallas, an exact-money sum is chosen AND runs fused_xla — the
    request is never silently clamped to another mode — and both land in
    the process-wide stage ledger as device runs over the [P, N] stack the
    stats name."""
    import ballista_tpu.ops.tpu.stage_compiler as sc
    from ballista_tpu.config import TPU_PALLAS

    tbl = _synth(n=20_000, seed=4)
    sc.STAGE_OUTCOMES.clear()
    for col, want in (("w", "fused_pallas"), ("price", "fused_xla")):
        sql = (f"select cat, sum({col}) s, count(*) c from t group by cat "
               "order by cat")
        _, stats = _run_mode(sql, "auto", {"t": (tbl, 2)}, **{TPU_PALLAS: True})
        assert stats.get("fusion_choice") == want, stats.get("fusion_reason")
        assert stats.get("fusion_mode") == want
    assert "exact int64 or nullable value lanes" in stats["fusion_reason"]
    led = sc.STAGE_OUTCOMES.snapshot()
    assert led["device"] >= 2 and led["error"] == 0 and led["declined"] == 0
    P, N = stats["table_shape"]
    assert P == 2 and N >= 10_000 and N & (N - 1) == 0  # a [P, bucket] stack
    assert {f for f, kind, _ in led["recent"] if kind == "device"} >= {"partial"}


def test_fusion_disabled_lands_staged():
    sql = "select cat, sum(w) s from t group by cat order by cat"
    tbl = _synth(n=20_000, seed=2)
    out, stats = _run_mode(sql, "auto", {"t": (tbl, 2)},
                           **{TPU_FUSION_ENABLED: False})
    assert stats.get("fusion_mode") == "staged"
    assert "disabled" in stats.get("fusion_reason", "")


# ------------------------------------------------- stats/heartbeat surface


def test_runstats_and_heartbeat_gauges(tpch_dir):
    import ballista_tpu.ops.tpu.stage_compiler as sc
    from ballista_tpu.executor.executor_process import ExecutorProcess

    out, stats = _run_mode(tpch_query(1), "fused_xla", tpch_dir=tpch_dir)
    assert stats.get("fusion_mode") == "fused_xla"
    assert stats.get("fused_spans", 0) >= 2  # filter→project→agg stage
    assert stats.get("fused_kernel_s", 0.0) > 0.0
    assert "fusion_reason" in stats

    gauges = dict(ExecutorProcess._tpu_metrics())
    assert gauges.get("tpu_fusion_mode") == 1.0  # fused_xla
    assert gauges.get("tpu_fused_spans", 0.0) >= 2.0
    assert gauges.get("tpu_fused_kernel_s", 0.0) > 0.0


def _walk(node):
    yield node
    for c in node.children():
        yield from _walk(c)
