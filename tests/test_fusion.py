"""Unit tests for the stage planner's pure host-side pieces and the ordering
/ prefix-sum primitives (no TPC-H data, fast).

End-to-end tests of the lowering (the device stage against the CPU engine
and numpy) live in tests/test_tpu_fusion.py.
"""

import numpy as np
import pytest

from ballista_tpu.ops.tpu.fusion import (
    AGGREGATE,
    PREDICATE,
    PROBE,
    PROJECT,
    plan_spans,
)


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
