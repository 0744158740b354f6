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


# ------------------------------------------------------- segment compaction


def _segments(layout, M, rng):
    """(end_idx, n_seg) of M sorted rows as `reduce_rows` builds them: the
    valid rows first, split into segments; end_idx holds a segment's index
    on its last row and C (= M) on every other row."""
    if layout == "no_segments":
        n_valid, starts = 0, []
    elif layout == "one_segment":
        n_valid, starts = M, [0]
    elif layout == "a_segment_a_row":  # n_seg == C
        n_valid, starts = M, list(range(M))
    else:  # segments of 1 to 9 rows, a tail of invalid rows
        n_valid = M - 37
        starts = np.flatnonzero(np.r_[True, rng.random(n_valid - 1) < 0.3]).tolist()
    boundary = np.zeros(M, bool)
    boundary[starts] = True
    valid = np.arange(M) < n_valid
    seg = np.cumsum(boundary) - 1
    nxt = np.r_[boundary[1:] | ~valid[1:], True]
    end_idx = np.where(valid & nxt, seg, M).astype(np.int32)
    return end_idx, len(starts)


def _lane(dtype, M, rng):
    if dtype == "bool":
        return rng.random(M) < 0.5
    if dtype == "int32":
        x = rng.integers(-2**31, 2**31, M).astype(np.int32)
        x[:3] = [np.iinfo(np.int32).min, -1, np.iinfo(np.int32).max]
        return x
    if dtype == "int64":
        x = rng.integers(-2**62, 2**62, M)
        x[:6] = [np.iinfo(np.int64).min, np.iinfo(np.int64).max, -1, 2**32, 2**32 - 1, -2**32]
        return x
    x = rng.normal(size=M) * 1e3
    special = np.array([0x7FF0000000000001, 0xFFF8000000000123, 0x7FF8000000000000,
                        0x8000000000000000, 0x0000000000000001, 0x800FFFFFFFFFFFFF],
                       np.uint64).view(np.float64)  # NaNs with payloads, -0.0, subnormals
    x[rng.choice(M, 24, replace=False)] = np.resize(
        np.r_[special, 0.0, np.inf, -np.inf], 24)
    return x


def _eqns(jaxpr):
    """Every equation of a jaxpr, those of the jaxprs inside it included."""
    for e in jaxpr.eqns:
        yield e
        for v in e.params.values():
            inner = getattr(v, "jaxpr", v)
            if hasattr(inner, "eqns"):
                yield from _eqns(inner)


@pytest.mark.parametrize("layout", ["segments_and_invalid_rows", "no_segments",
                                    "one_segment", "a_segment_a_row"])
@pytest.mark.parametrize("dtype", ["int32", "int64", "float64", "bool"])
def test_segment_compaction_is_the_one_scatter_bit_for_bit(dtype, layout):
    """`kernels.SegmentCompaction` gives, bit for bit, what one scatter of the
    lane at its own width gave: an int64 lane through its two 32-bit words
    (values past 2^32, negative ones, the extremes), a float64 lane gathered
    at the segments' ends (NaNs with payloads, ±0.0, ±inf, subnormals), with
    zeros from `n_seg` on — and no scatter in its program is 64 bits wide."""
    from ballista_tpu.ops.tpu.runtime import ensure_jax

    jax = ensure_jax()
    jnp = jax.numpy
    from ballista_tpu.ops.tpu.kernels import SegmentCompaction

    M = C = 512
    rng = np.random.default_rng(list((dtype + layout).encode()))
    end_idx, n_seg = _segments(layout, M, rng)
    lanes = [_lane(dtype, M, rng) for _ in range(2)]

    def compacted(end_idx, n_seg, *lanes):
        compact = SegmentCompaction(end_idx, n_seg, C)
        return [compact(x) for x in lanes], compact.counts()

    def one_scatter(end_idx, *lanes):
        return [jnp.zeros((C,), x.dtype).at[end_idx].set(x, mode="drop", unique_indices=True)
                for x in lanes]

    args = (jnp.asarray(end_idx), jnp.int32(n_seg), *map(jnp.asarray, lanes))
    got, counts = jax.jit(compacted)(*args)
    want = one_scatter(args[0], *args[2:])
    for g, w in zip(got, want):
        assert g.dtype == w.dtype
        g, w = np.asarray(g), np.asarray(w)
        assert np.array_equal(g.view(f"u{g.itemsize}"), w.view(f"u{w.itemsize}"))
    assert counts == {"compact_split_lanes": 2 * (dtype == "int64"),
                      "compact_gathered_lanes": 2 * (dtype == "float64")}

    widths = [e.invars[0].aval.dtype.itemsize
              for e in _eqns(jax.make_jaxpr(compacted)(*args).jaxpr)
              if e.primitive.name == "scatter"]
    assert widths and max(widths) <= 4
    # int64: two halves a lane; float64: ONE position lane for both lanes
    assert len(widths) == {"int64": 4, "float64": 1}.get(dtype, 2)
