"""Unit tests for the whole-stage fusion planner, cost model, and the
Pallas kernel family (interpreter mode — pure CPU, no TPC-H data, fast).

Heavier end-to-end parity tests (fused vs staged byte-identical on TPC-H
stages) live in tests/test_tpu_fusion.py.
"""

import numpy as np
import pytest

from ballista_tpu.ops.tpu.fusion import (
    AGGREGATE,
    CostModel,
    PREDICATE,
    PROBE,
    PROJECT,
    StageEstimate,
    plan_spans,
)


def _est(**kw):
    base = dict(
        rows=1_000_000, partitions=8, group_domain=8, n_group_keys=1,
        lanes=1, has_mult=False, n_filters=1, n_projections=1, n_joins=0,
        max_probe_table=0, agg_funcs=("sum", "count"),
    )
    base.update(kw)
    return StageEstimate(**base)


# ---------------------------------------------------------------- cost model


def test_forced_modes_win():
    for mode in ("staged", "fused_xla", "fused_pallas"):
        cm = CostModel(mode=mode)
        assert cm.choose(_est()).mode == mode


def test_disabled_falls_to_staged():
    cm = CostModel(enabled=False)
    dec = cm.choose(_est())
    assert dec.mode == "staged"
    assert "disabled" in dec.reason


def test_small_input_prefers_staged():
    # below min.rows AND staged-eligible: dispatch overhead dominates, the
    # per-span mode gives roofline taps for free
    cm = CostModel(min_fused_rows=4096)
    assert cm.choose(_est(rows=1000)).mode == "staged"
    # exactly at the threshold: fused
    assert cm.choose(_est(rows=4096)).mode == "fused_xla"


def test_small_but_staged_ineligible_fuses():
    cm = CostModel(min_fused_rows=4096)
    # expansion lanes disqualify the staged form
    assert cm.choose(_est(rows=1000, lanes=4)).mode == "fused_xla"
    # so does an unbounded group domain (sorted path)
    assert cm.choose(_est(rows=1000, group_domain=None)).mode == "fused_xla"


def test_tpu_platform_picks_pallas_when_eligible():
    # the group-reduce kernel compiles for the chip (test_tpu_compile.py), so
    # a TPU backend still auto-picks it — but only for stages whose value
    # lanes it really takes: the choice must be the mode that runs
    cm = CostModel(platform="tpu")
    dec = cm.choose(_est(group_domain=256))
    assert dec.mode == "fused_pallas"
    dec = cm.choose(_est(group_domain=256, f32_value_lanes=False))
    assert dec.mode == "fused_xla"
    assert "exact int64 or nullable value lanes" in dec.reason


def test_cpu_platform_never_auto_picks_pallas():
    cm = CostModel(platform="cpu")
    assert cm.choose(_est(group_domain=256)).mode == "fused_xla"


def test_tpu_platform_never_picks_kernels_its_compiler_refuses():
    """hash_probe and the int64 sort/top-k/scan family run only in the CPU
    backend's interpreter: on a TPU the sort family stays on lax.sort even
    when fused_pallas is forced or the legacy knob is on."""
    from ballista_tpu.ops.tpu.fusion import (
        TPU_KERNELS,
        estimate_sort_stage,
        kernel_runs_on,
    )

    assert TPU_KERNELS == {"masked_group_reduce", "dict_filter"}
    for k in ("hash_probe", "segmented_sort", "topk_select", "segmented_scan"):
        assert kernel_runs_on(k, "cpu") and not kernel_runs_on(k, "tpu")
    for k in TPU_KERNELS:
        assert kernel_runs_on(k, "tpu")
    shapes = (estimate_sort_stage(50_000, [("i64", False)]),
              estimate_sort_stage(50_000, [("i64", False)], fetch=10),
              estimate_sort_stage(50_000, [("i64", False)], window_funcs=1))
    for est in shapes:
        for cm in (CostModel(platform="tpu"),
                   CostModel(platform="tpu", mode="fused_pallas"),
                   CostModel(platform="tpu", force_pallas=True)):
            dec = cm.choose_sort(est)
            assert dec.mode == "fused_xla"
            assert "do not lower for platform=tpu" in dec.reason
        # the CPU backend's interpreter still takes a forced request
        assert CostModel(platform="cpu", mode="fused_pallas").choose_sort(
            est).mode == "fused_pallas"


def test_pallas_ineligibility_boundaries():
    cm = CostModel(platform="tpu")
    # G beyond the kernel ceiling
    assert cm.choose(_est(group_domain=1 << 20)).mode == "fused_xla"
    # unbounded group domain (int64 keys → sorted path)
    assert cm.choose(_est(group_domain=None)).mode == "fused_xla"
    # expansion lanes
    assert cm.choose(_est(lanes=4)).mode == "fused_xla"
    # aggregate-through-join weights
    assert cm.choose(_est(has_mult=True)).mode == "fused_xla"
    # min/max not in the kernel family
    assert cm.choose(_est(agg_funcs=("sum", "min"))).mode == "fused_xla"
    # scalar aggregation (G == 1) isn't worth a kernel launch
    assert cm.choose(_est(group_domain=1, n_group_keys=0)).mode == "fused_xla"


def test_legacy_pallas_knob_forces_kernel_path():
    # ballista.tpu.pallas.enabled predates the fusion knobs and must keep
    # working — even on CPU (interpreter mode), which tier-1 relies on
    cm = CostModel(force_pallas=True, platform="cpu")
    dec = cm.choose(_est())
    assert dec.mode == "fused_pallas"
    assert "legacy" in dec.reason


def test_fused_xla_reason_is_explanatory():
    cm = CostModel(platform="cpu")
    dec = cm.choose(_est(lanes=2, group_domain=None))
    assert "unbounded group domain" in dec.reason
    assert "2 expansion lanes" in dec.reason


# ------------------------------------------------------------- span planner


class _Fake:
    pass


def _mk(cls_name):
    from ballista_tpu.plan import physical

    cls = getattr(physical, cls_name)
    return object.__new__(cls)  # structure-only: planner isinstance checks


def test_plan_spans_merges_consecutive_kinds():
    ops = [_mk("FilterExec"), _mk("FilterExec"), _mk("CoalesceBatchesExec"),
           _mk("ProjectionExec"), _mk("HashJoinExec"), _mk("ProjectionExec")]
    spans = plan_spans(1, ops, agg=object())
    assert [(s.kind, s.ops) for s in spans] == [
        (PREDICATE, 3),  # scan filter + 2 FilterExec merge; Coalesce skipped
        (PROJECT, 1),
        (PROBE, 1),
        (PROJECT, 1),
        (AGGREGATE, 1),
    ]


def test_plan_spans_no_agg_no_filters():
    assert plan_spans(0, [], agg=None) == []
    spans = plan_spans(0, [_mk("ProjectionExec")], agg=None)
    assert [(s.kind, s.ops) for s in spans] == [(PROJECT, 1)]


# ------------------------------------------------- pallas kernels (interpret)


def test_masked_group_reduce_matches_numpy():
    from ballista_tpu.ops.tpu.pallas_kernels import masked_group_reduce

    rng = np.random.default_rng(7)
    P, N, G = 3, 512, 11
    vals = rng.uniform(-5, 5, (P, N)).astype(np.float32)
    gid = rng.integers(0, G, (P, N)).astype(np.int32)
    mask = rng.random((P, N)) < 0.7
    sums, cnts = masked_group_reduce(vals, gid, mask, G, block_n=128)
    sums, cnts = np.asarray(sums), np.asarray(cnts)
    assert sums.shape == (P, G) and cnts.shape == (P, G)
    for p in range(P):
        for g in range(G):
            sel = mask[p] & (gid[p] == g)
            assert cnts[p, g] == sel.sum()
            np.testing.assert_allclose(
                sums[p, g], vals[p][sel].astype(np.float64).sum(),
                rtol=1e-4, atol=1e-4)


def test_masked_group_reduce_multi_tile():
    # G = 300 needs 3 lane tiles of 128 — the multi-tile grid axis that
    # replaced the single-tile GROUP_LANES ceiling
    from ballista_tpu.ops.tpu.pallas_kernels import GROUP_LANES, masked_group_reduce

    G = 2 * GROUP_LANES + 44
    rng = np.random.default_rng(11)
    P, N = 2, 256
    vals = rng.uniform(0, 1, (P, N)).astype(np.float32)
    gid = rng.integers(0, G, (P, N)).astype(np.int32)
    mask = np.ones((P, N), dtype=bool)
    sums, cnts = masked_group_reduce(vals, gid, mask, G, block_n=256)
    sums, cnts = np.asarray(sums), np.asarray(cnts)
    assert sums.shape == (P, G)
    assert cnts.sum() == P * N
    ref = np.zeros((P, G))
    for p in range(P):
        np.add.at(ref[p], gid[p], vals[p].astype(np.float64))
    np.testing.assert_allclose(sums, ref, rtol=1e-4, atol=1e-4)


def test_masked_group_reduce_ceiling():
    from ballista_tpu.ops.tpu.pallas_kernels import MAX_GROUPS, masked_group_reduce

    with pytest.raises(ValueError):
        masked_group_reduce(
            np.zeros((1, 8), np.float32), np.zeros((1, 8), np.int32),
            np.ones((1, 8), bool), MAX_GROUPS + 1)


def test_hash_probe_matches_numpy():
    from ballista_tpu.ops.tpu.pallas_kernels import hash_probe

    rng = np.random.default_rng(3)
    T = 64
    table = np.full(T, -1, np.int32)
    present = rng.choice(T, size=40, replace=False)
    table[present] = np.arange(40, dtype=np.int32)
    P, N = 2, 256
    keys = rng.integers(0, T, (P, N)).astype(np.int32)
    mask = rng.random((P, N)) < 0.8
    rows, matched = hash_probe(keys, table, mask, block_n=128)
    rows, matched = np.asarray(rows), np.asarray(matched)
    exp_matched = mask & (table[keys] >= 0)
    np.testing.assert_array_equal(matched, exp_matched)
    np.testing.assert_array_equal(rows, np.where(exp_matched, table[keys], 0))


# ------------------------------------------------ ordering + prefix sum


def test_lex_order_is_lax_sorts_stable_order():
    """kernels.lex_order (LSD radix passes over 32-bit lanes) returns the
    permutation one wide stable `lax.sort` over the same keys returns — for
    every key dtype the stages pass, 64-bit keys split into two lanes, and
    float keys (ranked) with NaN, ±0.0 and ±inf."""
    from ballista_tpu.ops.tpu.runtime import ensure_jax

    jax = ensure_jax()
    jnp = jax.numpy
    from ballista_tpu.ops.tpu.kernels import lex_order

    rng = np.random.default_rng(0)
    M = 5000
    f = rng.normal(size=M)
    f[::17], f[::13], f[::11], f[::7], f[::5] = np.nan, -0.0, 0.0, np.inf, -np.inf
    pool = {
        "i64": jnp.asarray(rng.integers(-2**62, 2**62, M)),
        "i64_ties": jnp.asarray(rng.integers(-5, 5, M)),
        "i32": jnp.asarray(rng.integers(-2**31, 2**31 - 1, M).astype(np.int32)),
        "i16": jnp.asarray(rng.integers(-3, 3, M).astype(np.int16)),
        "bool": jnp.asarray(rng.random(M) < 0.5),
        "f64": jnp.asarray(f),
        "f64_ties": jnp.asarray(np.round(rng.normal(size=M))),
    }
    order = jax.jit(lambda *k: lex_order(list(k)))
    for names in (["i64"], ["i64_ties", "i32"], ["bool", "i16", "i64_ties"],
                  ["f64"], ["i16", "f64"], ["f64_ties", "i64_ties"],
                  ["bool", "i16", "i64_ties", "i32", "f64_ties"]):
        keys = [pool[n] for n in names]
        want = jax.lax.sort(tuple(keys) + (jnp.arange(M, dtype=jnp.int32),),
                            num_keys=len(keys) + 1)[-1]
        assert np.array_equal(np.asarray(order(*keys)), np.asarray(want)), names


@pytest.mark.parametrize("dtype", [np.int32, np.int64])
@pytest.mark.parametrize("M", [7, 2048, 4096, 10_240, 10_241])
def test_int_cumsum_is_exact(dtype, M):
    """The blocked prefix sum equals the flat one in the array's own dtype,
    whether or not the block divides the length."""
    from ballista_tpu.ops.tpu.runtime import ensure_jax

    jnp = ensure_jax().numpy
    from ballista_tpu.ops.tpu.kernels import int_cumsum

    x = np.random.default_rng(M).integers(-1000, 1000, M).astype(dtype)
    got = int_cumsum(jnp.asarray(x))
    assert got.dtype == dtype
    assert np.array_equal(np.asarray(got), np.cumsum(x, dtype=dtype))
