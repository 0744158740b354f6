"""JAX kernels: expression lowering, masked segment aggregation, row hash.

Design rules (XLA-friendly):
- no data-dependent shapes: filters produce MASKS, never compaction; the
  aggregation consumes (value, mask) pairs with segment ops
- string work never reaches the device: predicates over dictionary columns
  are host-precomputed boolean LUTs, gathered by code on device
- money arithmetic stays in int64 scaled integers (exact); scale tracking
  happens at lowering time (static), not at runtime
- one jitted function per (stage fingerprint, shape bucket, dict sizes):
  the compile cache is keyed exactly on what changes the traced program

hash64/hash_combine are the bit-exact twins of ops/hashing.py — the wire
contract that lets device-side hash partitioning interoperate with host and
C++ shuffle readers.
"""

from __future__ import annotations

import datetime as _dt
import decimal as _decimal
import fnmatch
from dataclasses import dataclass
from typing import Any, Callable

import numpy as np

from ballista_tpu.plan.expressions import (
    Alias,
    Between,
    BinaryExpr,
    Case,
    Cast,
    Column,
    Expr,
    InList,
    IsNotNull,
    IsNull,
    Like,
    Literal,
    Negative,
    Not,
    ScalarFunction,
)
from ballista_tpu.plan.schema import DFSchema


def _jnp():
    import jax.numpy as jnp

    return jnp


# -- device value with static (lowering-time) type info ---------------------


@dataclass
class DevVal:
    kind: str  # i64 | f64 | money | date | code | bool
    arr: Any  # jnp array
    scale: int = 0
    dictionary: list | None = None
    valid: Any = None  # jnp bool array; None = known non-null everywhere


class Unsupported(Exception):
    """Raised at lowering time → subtree falls back to the CPU engine."""


class BelowRowFloor(Unsupported):
    """The stage's input is under ballista.tpu.min.rows: it stays on the CPU
    engine by policy (compile and dispatch cost dominate), which says
    nothing about the device — counted apart from the other declines."""

    def __init__(self, rows: int):
        super().__init__(f"only {rows} rows (< tpu min)")


def vand(*valids):
    """Null-strict validity combine: result is null if ANY input is null
    (the SQL rule for comparisons, arithmetic, casts, function args)."""
    out = None
    for v in valids:
        if v is None:
            continue
        out = v if out is None else out & v
    return out


def true_mask(v: DevVal):
    """Project three-valued logic onto filtering: rows pass a WHERE clause
    only when the predicate is TRUE — unknown (NULL) behaves as false."""
    if v.valid is None:
        return v.arr
    return v.arr & v.valid


# -- ordering ----------------------------------------------------------------


def _float_rank(k):
    """A float sort key as its dense rank (int32) under `lax.sort`'s own
    float order — NaNs last and equal to each other, -0.0 == +0.0. Floats
    cannot be re-read as order-preserving integers on a TPU (the compiler's
    64-bit rewrite has no f64 <-> s64 bitcast), so a float key costs one
    more two-operand sort."""
    import jax

    jnp = _jnp()
    M = k.shape[0]
    sk, idx = jax.lax.sort((k, jnp.arange(M, dtype=jnp.int32)), num_keys=1)
    same = (sk[1:] == sk[:-1]) | (jnp.isnan(sk[1:]) & jnp.isnan(sk[:-1]))
    steps = jnp.concatenate([jnp.zeros((1,), jnp.int32), (~same).astype(jnp.int32)])
    return jnp.zeros((M,), jnp.int32).at[idx].set(
        int_cumsum(steps), unique_indices=True)


def _order_lanes(k) -> list:
    """Order-preserving int32 lanes of one sort key, most significant first:
    comparing the lanes lexicographically as signed int32 orders exactly as
    `lax.sort` orders the key itself."""
    import jax

    jnp = _jnp()
    dt = k.dtype
    if jnp.issubdtype(dt, jnp.floating):
        return [_float_rank(k)]
    if dt == jnp.bool_ or (dt.itemsize < 4 and jnp.issubdtype(dt, jnp.integer)):
        return [k.astype(jnp.int32)]
    if dt == jnp.int32:
        return [k]
    if dt == jnp.int64:
        # high word signed; low word unsigned, re-read as signed by flipping
        # its top bit
        lo = (k & jnp.int64(0xFFFFFFFF)).astype(jnp.uint32) ^ jnp.uint32(0x80000000)
        return [(k >> 32).astype(jnp.int32),
                jax.lax.bitcast_convert_type(lo, jnp.int32)]
    raise Unsupported(f"sort key dtype {dt}")


def lex_order(keys):
    """Stable ascending lexicographic ordering permutation (int32 [M]) of M
    rows by `keys`, most significant first — the permutation a multi-operand
    `lax.sort(keys + (iota,), num_keys=len(keys) + 1)` returns, in a form the
    TPU's compiler takes quickly.

    The chip compiler's time for a sort grows steeply with its operand count
    (a 64-bit operand counts twice, a stable sort adds one) times log²(M):
    compiling for a v5e, a stable two-operand int32 sort of 2^26 rows takes
    about 40 s, q3's partial-aggregate sort (four keys, two of them 64-bit,
    one 64-bit payload) about 480 s, and every sorted-path stage, final stage
    and window stage emits such a sort. So: ONE stable two-operand sort,
    inside a `lax.scan` over the keys' 32-bit lanes taken least significant
    first (LSD radix passes, 32 bits a digit). A program compiles one small
    sort whatever its integer key list (a float key adds one, _float_rank);
    payloads move afterwards by `x[perm]`."""
    import jax

    jnp = _jnp()
    lanes = [lane for k in keys for lane in _order_lanes(k)]
    M = lanes[0].shape[0]

    def radix_pass(perm, lane):
        _, perm = jax.lax.sort((lane[perm], perm), num_keys=1, is_stable=True)
        return perm, None

    perm, _ = jax.lax.scan(radix_pass, jnp.arange(M, dtype=jnp.int32),
                           jnp.stack(lanes[::-1]))
    return perm


def lex_order_sorted(keys):
    """`lex_order`'s permutation, and the keys' most significant 32-bit lane
    in that order: the same LSD radix passes, the last one (over that lane)
    taken out of the loop so its sorted first output, which `lex_order`
    discards, comes back. A boundary over a one-lane leading key then costs
    no gather. Returns (perm int32 [M], sorted lane int32 [M])."""
    import jax

    jnp = _jnp()
    lanes = [lane for k in keys for lane in _order_lanes(k)]
    M = lanes[0].shape[0]

    def radix_pass(perm, lane):
        _, perm = jax.lax.sort((lane[perm], perm), num_keys=1, is_stable=True)
        return perm, None

    perm = jnp.arange(M, dtype=jnp.int32)
    if len(lanes) > 1:
        perm, _ = jax.lax.scan(radix_pass, perm, jnp.stack(lanes[:0:-1]))
    top, perm = jax.lax.sort((lanes[0][perm], perm), num_keys=1, is_stable=True)
    return perm, top


# rows this short are summed as a masked triangle, not scanned (int_cumsum)
CUMSUM_TRIANGLE = 64


def int_cumsum(x, block: int = 2048):
    """Inclusive cumulative sum of a 1-D INTEGER array in its own dtype, as
    per-block cumsums plus the running block totals. Exact (integer addition
    is associative) and far cheaper for the chip's compiler than one flat
    scan: compiling for a v5e at 2^26 int64 rows, `jnp.cumsum` takes 77 s,
    this form 3 s. A length the block does not divide is padded with zeros
    to the next one it does. A length within one block holds no scan at all:
    inside a conditional's branch or a loop's body the chip's compiler
    cannot place a 64-bit scan of 256 to 1024 elements (it runs out of
    scoped vmem), and the sorted path's tiers are conditionals. Its blocks
    are CUMSUM_TRIANGLE long and each is summed as a masked triangle (64
    additions an element)."""
    jnp = _jnp()
    M = x.shape[0]
    if M == 0:
        return x
    width = block if M > block else min(M, CUMSUM_TRIANGLE)
    if M % width:
        return int_cumsum(jnp.pad(x, (0, -M % width)), block)[:M]
    rows = x.reshape(-1, width)
    if width == block:
        inner = jnp.cumsum(rows, axis=1, dtype=x.dtype)
    else:
        at = jnp.arange(width, dtype=jnp.int32)
        inner = jnp.sum(jnp.where(at[None, :] <= at[:, None], rows[:, None, :], 0),
                        axis=2, dtype=x.dtype)
    if M == width:
        return inner[0]
    totals = inner[:, -1]
    before = int_cumsum(totals, block) - totals
    return (inner + before[:, None]).reshape(-1)


def _segmented_rows(v, f, op):
    """Inclusive segmented scan of every row of `v` ([R, W]) along its lanes,
    resetting where `f` is set: W's log2 steps of "combine with the lane 2^k
    to the left" over the whole array (a lane with no such lane in its row
    keeps its value). Returns the scanned values and, lane for lane, whether
    a set flag lies at or before it in its row."""
    jnp = _jnp()
    lane = jnp.arange(v.shape[1], dtype=jnp.int32)[None, :]
    step = 1
    while step < v.shape[1]:
        left_v = jnp.pad(v[:, :-step], ((0, 0), (step, 0)))
        left_f = jnp.pad(f[:, :-step], ((0, 0), (step, 0)))
        v = jnp.where(f | (lane < step), v, op(left_v, v))
        f = f | left_f
        step *= 2
    return v, f


def segmented_scan(values, boundary, func: str, block: int = 2048):
    """Inclusive segmented `sum` / `min` / `max` of a 1-D array: lane i holds
    the reduction of values[s..i], s the last lane at or before i whose
    `boundary` is set (lane 0 starts a segment whatever its flag says). The
    monoid is the classic one, (value, "a boundary lies in here"), but never
    as one flat `lax.associative_scan`, whose levels the chip's compiler
    places slower than the lanes grow (compiling for a v5e: 5 s at 2^17
    int64 lanes, 121 s at 2^20, over nine minutes at 2^23; a scan over the
    rows of a [R, 2048] array is no better, 111 s at 2^20). As `int_cumsum`,
    in blocks: a segmented scan inside every block of `block` lanes
    (`_segmented_rows`), the same scan over the blocks' carries (a block's
    last value, and whether a boundary lies in it), and the carry combined
    into every lane before its block's first boundary: 2 s to compile at
    2^23 and at 2^24. Exact for integers (addition, min and max are
    associative: every lane is bit for bit the sequential loop's); a float
    sum is bracketed otherwise than the flat scan bracketed it, no worse. A
    length the block does not divide is padded with lanes that start
    segments of their own."""
    jnp = _jnp()
    op = {"sum": jnp.add, "min": jnp.minimum, "max": jnp.maximum}[func]
    M = values.shape[0]
    if M <= block:
        return _segmented_rows(values[None, :], boundary[None, :], op)[0][0]
    if M % block:
        pad = -M % block
        return segmented_scan(jnp.pad(values, (0, pad)),
                              jnp.pad(boundary, (0, pad), constant_values=True),
                              func, block)[:M]
    inner, seen = _segmented_rows(values.reshape(-1, block), boundary.reshape(-1, block), op)
    ends = segmented_scan(inner[:, -1], seen[:, -1], func, block)  # the scan at each block's end
    carry = jnp.concatenate([ends[:1], ends[:-1]])  # what runs into a block; block 0 takes none
    closed = seen | (jnp.arange(inner.shape[0]) == 0)[:, None]
    return jnp.where(closed, inner, op(carry[:, None], inner)).reshape(-1)


def live_slots(valid, cap: int):
    """Slots (int32 [..., cap]) of the first `cap` True entries along the
    last axis of the mask `valid` ([N], or [R, N]: each row for itself), in
    slot order; past a row's live count, its dead slots. One stable
    two-operand sort of (dead, slot) a row: the live slots come first and
    keep their order. On a v5e that is 8-12 ms for 2^23 slots whatever
    `cap` is, where a binary search of the prefix sum (log2(N) steps of
    cap-row gathers) takes 25 ms at N / 64 and 0.2 s at N / 8, and a
    scatter of the slot ids 40-60 ms."""
    import jax

    jnp = _jnp()
    slot = jnp.broadcast_to(jnp.arange(valid.shape[-1], dtype=jnp.int32), valid.shape)
    _, order = jax.lax.sort((~valid, slot), dimension=valid.ndim - 1, num_keys=1,
                            is_stable=True)
    return order[..., :cap]


class SegmentCompaction:
    """Per-row segment results compacted into a static capacity of `C`
    slots: slot s of a lane's output holds the lane at the last row of
    segment s (`end_idx`: s on that row, `C` on every other), slots from
    `n_seg` on hold zero. Both sort-based families (the sorted path's
    `reduce_rows`, the final family's merge) compact through one instance a
    reduction, calling it once a lane in the order they reduce.

    The chip scatters a 64-bit lane as one two-operand scatter that takes
    about 25 times a 32-bit one (0.94 s against 0.041 s for 2^23 updates,
    v5e), so no lane is scattered at 64 bits. An int64 lane is scattered as
    its two 32-bit words and recombined exactly. A float64 lane cannot be
    split (the chip has no f64 <-> s64 bitcast, `_float_rank`): it is
    gathered at the segments' last rows, whose positions are scattered into
    `[C]` once, as int32, for every such lane of the reduction. A lane of 32
    bits or fewer is one scatter. `split_lanes` and `gathered_lanes` count
    the first two kinds as they are traced."""

    def __init__(self, end_idx, n_seg, C: int):
        self.end_idx, self.n_seg, self.C = end_idx, n_seg, C
        self.split_lanes = 0
        self.gathered_lanes = 0
        self._end_row = None  # int32 [C]: the row each segment ends at

    def __call__(self, src):
        jnp = _jnp()
        if src.dtype == jnp.int64:
            self.split_lanes += 1
            lo = self._scatter((src & 0xFFFFFFFF).astype(jnp.uint32))
            hi = self._scatter((src >> 32).astype(jnp.int32))
            return (hi.astype(jnp.int64) << 32) | lo.astype(jnp.int64)
        if src.dtype.itemsize == 8:
            self.gathered_lanes += 1
            if self._end_row is None:
                self._end_row = self._scatter(jnp.arange(src.shape[0], dtype=jnp.int32))
            live = jnp.arange(self.C, dtype=jnp.int32) < self.n_seg
            return jnp.where(live, src[self._end_row], jnp.zeros((), src.dtype))
        return self._scatter(src)

    def counts(self) -> dict:
        """What a stage's record and its dispatch span say of it."""
        return {"compact_split_lanes": self.split_lanes,
                "compact_gathered_lanes": self.gathered_lanes}

    def _scatter(self, src):
        return (
            _jnp().zeros((self.C,), src.dtype)
            .at[self.end_idx]
            .set(src, mode="drop", unique_indices=True)
        )


# -- bit-exact twin of ops/hashing.py ---------------------------------------


def hash64(x):
    """splitmix64 over uint64 lanes (jax)."""
    jnp = _jnp()
    x = x.astype(jnp.uint64)
    x = x + jnp.uint64(0x9E3779B97F4A7C15)
    x = (x ^ (x >> jnp.uint64(30))) * jnp.uint64(0xBF58476D1CE4E5B9)
    x = (x ^ (x >> jnp.uint64(27))) * jnp.uint64(0x94D049BB133111EB)
    return x ^ (x >> jnp.uint64(31))


def hash_combine_jax(h, v):
    jnp = _jnp()
    return h ^ (v + jnp.uint64(0x9E3779B97F4A7C15) + (h << jnp.uint64(6)) + (h >> jnp.uint64(2)))


# -- lowering ----------------------------------------------------------------


class Lowering:
    """Collects host-side LUT constants while lowering expressions into
    closures over (cols, luts). LUTs are padded to pow2 so jit keys are
    stable across partitions with slightly different dictionaries."""

    def __init__(self, schema: DFSchema, kinds: list[tuple[str, int]], dictionaries: list[list | None]):
        self.schema = schema
        self.kinds = kinds  # per-field (kind, scale)
        self.dictionaries = dictionaries
        # LUTs are registered as (source_slot, builder) so they can be
        # REBUILT for each partition's dictionaries without re-tracing: the
        # compiled function takes LUTs as traced args, only their contents
        # change across partitions (padded size is part of the jit key).
        self.lut_builders: list[tuple[int, Any]] = []
        self.slots: list[int] = list(range(len(kinds)))  # field → source slot
        # env indirection (set by the stage compiler): field index → lowered
        # fn, so projections rebind what a Column reference means
        self.env_fns: list | None = None
        self.env_meta: list | None = None

    def add_lut(self, src_slot, builder) -> int:
        """src_slot: scan column index, or ('build', join_idx, col_idx) for
        dictionaries that live in a join's build table."""
        self.lut_builders.append((src_slot, builder))
        return len(self.lut_builders) - 1

    def build_luts(self, dictionaries_by_slot: list[list | None],
                   build_dicts: list[list[list | None]] | None = None) -> list[np.ndarray]:
        out = []
        for slot, builder in self.lut_builders:
            if isinstance(slot, tuple) and slot[0] == "build":
                dic = build_dicts[slot[1]][slot[2]] if build_dicts else None
            else:
                dic = dictionaries_by_slot[slot]
            vals = builder(dic)
            n = 1
            while n < max(len(vals), 1):
                n *= 2
            padded = np.zeros(n, dtype=vals.dtype)
            padded[: len(vals)] = vals
            out.append(padded)
        return out

    def col_index(self, c: Column) -> int:
        return self.schema.index_of(c.name, c.qualifier)


LoweredFn = Callable[[list, list], DevVal]  # (cols, luts) -> DevVal


def _string_expr_fn(e: Expr, ctx: "Lowering"):
    """Recognize pure string-function trees over ONE dictionary column
    (substr/upper/lower/trim with literal args). Returns (base Column,
    col_index, str→str fn) — predicates over such trees compose into the
    column's host-side dictionary LUT, so strings never reach the device
    (the substring(c_phone,..) IN (...) pattern of q22 and TPC-DS)."""
    if isinstance(e, Alias):
        return _string_expr_fn(e.expr, ctx)
    if isinstance(e, Column):
        i = ctx.col_index(e)
        if ctx.kinds[i][0] == "code":
            return e, i, lambda s: s
        return None
    if isinstance(e, ScalarFunction) and e.name in ("substr", "upper", "lower", "trim"):
        inner = _string_expr_fn(e.args[0], ctx) if e.args else None
        if inner is None:
            return None
        col, i, f = inner
        extra = e.args[1:]
        if not all(isinstance(a, Literal) for a in extra):
            return None
        vals = [a.value for a in extra]
        name = e.name
        if name in ("upper", "lower", "trim") and extra:
            # BTRIM(col, chars) etc. — semantics we don't model: stay on cpu
            return None
        if name == "substr":
            if not vals or not all(isinstance(v, int) for v in vals):
                return None
            if vals[0] < 1:
                return None  # SQL start<1 clamps; python would wrap
            start = vals[0] - 1
            end = start + vals[1] if len(vals) > 1 else None

            def g(s, f=f, start=start, end=end):
                t = f(s)
                return t[start:end] if end is not None else t[start:]
        elif name == "upper":
            def g(s, f=f):
                return f(s).upper()
        elif name == "lower":
            def g(s, f=f):
                return f(s).lower()
        else:  # trim
            def g(s, f=f):
                return f(s).strip()
        return col, i, g
    return None


def lower_expr(e: Expr, ctx: Lowering) -> LoweredFn:
    jnp_mod = None  # resolved lazily inside closures

    if isinstance(e, Alias):
        return lower_expr(e.expr, ctx)

    if isinstance(e, Column):
        i = ctx.col_index(e)
        if ctx.env_fns is not None:
            return ctx.env_fns[i]
        kind, scale = ctx.kinds[i]
        dic = ctx.dictionaries[i]
        return lambda cols, luts: DevVal(kind, cols[i], scale, dic)

    if isinstance(e, Literal):
        v = e.value
        if isinstance(v, bool):
            return lambda cols, luts: DevVal("bool", _jnp().asarray(v))
        if isinstance(v, int):
            return lambda cols, luts: DevVal("i64", _jnp().asarray(v, dtype=_jnp().int64))
        if isinstance(v, float):
            cents = v * 100
            if abs(cents - round(cents)) < 1e-9:
                c = int(round(cents))
                return lambda cols, luts: DevVal("money", _jnp().asarray(c, dtype=_jnp().int64), 2)
            return lambda cols, luts: DevVal("f64", _jnp().asarray(v, dtype=_jnp().float64))
        if isinstance(v, _decimal.Decimal):
            # exact-policy literal: the declared scale IS the fixed point
            exp = -v.as_tuple().exponent
            if 0 <= exp <= 4:
                c = int(v.scaleb(exp))
                return lambda cols, luts, c=c, exp=exp: DevVal(
                    "money", _jnp().asarray(c, dtype=_jnp().int64), exp)
            fv = float(v)
            return lambda cols, luts, fv=fv: DevVal(
                "f64", _jnp().asarray(fv, dtype=_jnp().float64))
        if isinstance(v, _dt.date):
            days = (v - _dt.date(1970, 1, 1)).days
            return lambda cols, luts: DevVal("date", _jnp().asarray(days, dtype=_jnp().int32))
        raise Unsupported(f"literal {v!r}")

    if isinstance(e, BinaryExpr):
        # string equality over dictionary columns → host LUT, device gather
        if e.op in ("=", "<>"):
            for a, b in ((e.left, e.right), (e.right, e.left)):
                if isinstance(b, Literal) and isinstance(b.value, str):
                    hit = _string_expr_fn(a, ctx)
                    if hit is not None:
                        col, i, sfn = hit
                        src = lower_expr(col, ctx)
                        val = b.value
                        li = ctx.add_lut(
                            ctx.slots[i],
                            lambda dic, val=val, sfn=sfn: np.array(
                                [sfn(x) == val for x in dic], dtype=bool
                            ),
                        )
                        neg = e.op == "<>"

                        def run(cols, luts, src=src, li=li, neg=neg):
                            v = src(cols, luts)
                            out = luts[li][v.arr]
                            return DevVal("bool", ~out if neg else out, valid=v.valid)

                        return run
        lf = lower_expr(e.left, ctx)
        rf = lower_expr(e.right, ctx)
        op = e.op

        def run(cols, luts):
            return _binop(lf(cols, luts), op, rf(cols, luts))

        return run

    if isinstance(e, Not):
        f = lower_expr(e.expr, ctx)

        def run(cols, luts):
            v = f(cols, luts)
            return DevVal("bool", ~v.arr, valid=v.valid)  # NOT NULL is NULL

        return run

    if isinstance(e, IsNull) or isinstance(e, IsNotNull):
        f = lower_expr(e.expr, ctx)
        want_null = isinstance(e, IsNull)

        def run(cols, luts):
            jnp = _jnp()
            v = f(cols, luts)
            if v.valid is None:
                out = jnp.zeros(jnp.shape(v.arr), bool) if want_null \
                    else jnp.ones(jnp.shape(v.arr), bool)
            else:
                out = ~v.valid if want_null else v.valid
            return DevVal("bool", out)  # IS [NOT] NULL is never null itself

        return run

    if isinstance(e, Negative):
        f = lower_expr(e.expr, ctx)

        def run(cols, luts):
            v = f(cols, luts)
            return DevVal(v.kind, -v.arr, v.scale, valid=v.valid)

        return run

    if isinstance(e, Between):
        vf = lower_expr(e.expr, ctx)
        lof = lower_expr(e.low, ctx)
        hif = lower_expr(e.high, ctx)
        neg = e.negated

        def run(cols, luts):
            v = vf(cols, luts)
            lo = _binop(v, ">=", lof(cols, luts))
            hi = _binop(v, "<=", hif(cols, luts))
            both = _binop(lo, "and", hi)
            return DevVal("bool", ~both.arr if neg else both.arr, valid=both.valid)

        return run

    if isinstance(e, InList):
        # string-fn trees over a code column compose into the dictionary LUT
        hit = _string_expr_fn(e.expr, ctx)
        if hit is not None and all(isinstance(v, str) for v in e.values):
            col, i, sfn = hit
            src = lower_expr(col, ctx)
            values = set(e.values)
            li = ctx.add_lut(
                ctx.slots[i],
                lambda dic, values=values, sfn=sfn: np.array(
                    [sfn(x) in values for x in dic], dtype=bool
                ),
            )
            neg = e.negated

            def run(cols, luts):
                v = src(cols, luts)
                out = luts[li][v.arr]
                return DevVal("bool", ~out if neg else out, valid=v.valid)

            return run
        inner = lower_expr(e.expr, ctx)
        if isinstance(e.expr, (Column, Alias)):
            col = e.expr.expr if isinstance(e.expr, Alias) else e.expr
            i = ctx.col_index(col)
            kind, _ = ctx.kinds[i]
            src = inner
            if kind in ("i64", "date"):
                vals = list(e.values)
                neg = e.negated

                def run(cols, luts):
                    jnp = _jnp()
                    v = src(cols, luts)
                    out = jnp.zeros(v.arr.shape, dtype=bool)
                    for lit in vals:
                        if isinstance(lit, _dt.date):
                            lit = (lit - _dt.date(1970, 1, 1)).days
                        out = out | (v.arr == lit)
                    # NULL IN (...) / NULL NOT IN (...) are both unknown
                    return DevVal("bool", ~out if neg else out, valid=v.valid)

                return run
        raise Unsupported(f"IN over {e.expr}")

    if isinstance(e, Like):
        if not isinstance(e.expr, Column):
            raise Unsupported("LIKE over non-column")
        i = ctx.col_index(e.expr)
        kind, _ = ctx.kinds[i]
        if kind != "code":
            raise Unsupported("LIKE over non-string")
        src = lower_expr(e.expr, ctx)
        pat = _like_to_fnmatch(e.pattern)
        li = ctx.add_lut(
            ctx.slots[i],
            lambda dic, pat=pat: np.array(
                [fnmatch.fnmatchcase(x, pat) for x in dic], dtype=bool
            ),
        )
        neg = e.negated

        def run(cols, luts, src=src, li=li, neg=neg):
            v = src(cols, luts)
            out = luts[li][v.arr]
            return DevVal("bool", ~out if neg else out, valid=v.valid)

        return run

    if isinstance(e, Case):
        branch_fns = [(lower_expr(w, ctx), lower_expr(t, ctx)) for w, t in e.branches]
        else_fn = lower_expr(e.else_expr, ctx) if e.else_expr is not None else None

        has_else = else_fn is not None

        def run(cols, luts):
            jnp = _jnp()
            thens = [tf(cols, luts) for _, tf in branch_fns]
            whens = [wf(cols, luts) for wf, _ in branch_fns]
            # align all branch values to a common kind/scale
            target = thens[0]
            if has_else:
                evd = else_fn(cols, luts)
            else:
                # no ELSE: the fall-through value is NULL
                evd = DevVal(target.kind, jnp.zeros((), dtype=target.arr.dtype),
                             target.scale, valid=jnp.zeros((), dtype=bool))
            allv = thens + [evd]
            kind, scale = _common_kind([(v.kind, v.scale) for v in allv])
            allv = [_coerce(v, kind, scale) for v in allv]
            nullable = any(v.valid is not None for v in whens) or any(
                v.valid is not None for v in allv
            )
            out = allv[-1].arr
            out_valid = None
            if nullable:
                ev = allv[-1].valid
                out_valid = ev if ev is not None else jnp.ones((), dtype=bool)
            decided = jnp.zeros((), dtype=bool)
            for w, t in zip(whens, allv[:-1]):
                taken = true_mask(w)  # a NULL condition skips its branch
                cond = taken & ~decided
                out = jnp.where(cond, t.arr, out)
                if nullable:
                    tv = t.valid if t.valid is not None else True
                    out_valid = jnp.where(cond, tv, out_valid)
                decided = decided | taken
            return DevVal(kind, out, scale, valid=out_valid)

        return run

    if isinstance(e, Cast):
        f = lower_expr(e.expr, ctx)
        import pyarrow as pa

        to = e.to

        def run(cols, luts):
            jnp = _jnp()
            v = f(cols, luts)
            if pa.types.is_floating(to):
                return _coerce(v, "f64", 0)
            if pa.types.is_integer(to):
                if v.kind == "money":
                    return DevVal("i64", v.arr // (10**v.scale), valid=v.valid)
                return DevVal("i64", v.arr.astype(jnp.int64), valid=v.valid)
            raise Unsupported(f"cast to {to}")

        return run

    if isinstance(e, ScalarFunction):
        if e.name in ("extract_year", "extract_month"):
            f = lower_expr(e.args[0], ctx)
            part = e.name

            def run(cols, luts):
                jnp = _jnp()
                v = f(cols, luts)
                if v.kind != "date":
                    raise Unsupported("extract over non-date")
                days = v.arr.astype(jnp.int64)
                # civil-from-days (Howard Hinnant's algorithm, vectorized)
                z = days + 719468
                era = jnp.where(z >= 0, z, z - 146096) // 146097
                doe = z - era * 146097
                yoe = (doe - doe // 1460 + doe // 36524 - doe // 146096) // 365
                y = yoe + era * 400
                doy = doe - (365 * yoe + yoe // 4 - yoe // 100)
                mp = (5 * doy + 2) // 153
                m = jnp.where(mp < 10, mp + 3, mp - 9)
                y = jnp.where(m <= 2, y + 1, y)
                if part == "extract_year":
                    return DevVal("i64", y.astype(jnp.int64), valid=v.valid)
                return DevVal("i64", m.astype(jnp.int64), valid=v.valid)

            return run
        raise Unsupported(f"scalar fn {e.name}")

    raise Unsupported(f"{type(e).__name__}")


def _like_to_fnmatch(pat: str) -> str:
    out = []
    for ch in pat:
        if ch == "%":
            out.append("*")
        elif ch == "_":
            out.append("?")
        elif ch in "*?[]":
            out.append(f"[{ch}]")
        else:
            out.append(ch)
    return "".join(out)


def _common_kind(pairs: list[tuple[str, int]]) -> tuple[str, int]:
    kinds = {k for k, _ in pairs}
    if "f64" in kinds:
        return "f64", 0
    if "money" in kinds:
        scale = max(s for k, s in pairs if k == "money")
        return "money", scale
    if kinds <= {"i64", "bool"}:
        return "i64", 0
    if kinds == {"date"}:
        return "date", 0
    if kinds == {"code"}:
        raise Unsupported("code-valued CASE")
    return "i64", 0


def _coerce(v: DevVal, kind: str, scale: int) -> DevVal:
    jnp = _jnp()
    if v.kind == kind and v.scale == scale:
        return v
    if kind == "f64":
        if v.kind == "money":
            return DevVal("f64", v.arr.astype(jnp.float64) / (10**v.scale), valid=v.valid)
        return DevVal("f64", v.arr.astype(jnp.float64), valid=v.valid)
    if kind == "money":
        if v.kind == "money":
            return DevVal("money", v.arr * (10 ** (scale - v.scale)), scale, valid=v.valid)
        if v.kind in ("i64", "bool"):
            return DevVal("money", v.arr.astype(jnp.int64) * (10**scale), scale, valid=v.valid)
    if kind == "i64":
        return DevVal("i64", v.arr.astype(jnp.int64), valid=v.valid)
    raise Unsupported(f"coerce {v.kind}->{kind}")


_CMP_OPS = {"=", "<>", "<", "<=", ">", ">="}


def _binop(l: DevVal, op: str, r: DevVal) -> DevVal:
    jnp = _jnp()
    if op in ("and", "or"):
        # Kleene three-valued logic. Null value slots are FILLED with False
        # at encode time, so the value lane of AND/OR is simply &/| — the
        # validity lane records where the result is actually known:
        #   x AND y known iff (both known) or (a known-FALSE side exists)
        #   x OR  y known iff (both known) or (a known-TRUE  side exists)
        if l.valid is None and r.valid is None:
            out = l.arr & r.arr if op == "and" else l.arr | r.arr
            return DevVal("bool", out)
        lv = l.valid if l.valid is not None else True
        rv = r.valid if r.valid is not None else True
        if op == "and":
            valid = (lv & rv) | (lv & ~l.arr) | (rv & ~r.arr)
            return DevVal("bool", l.arr & r.arr, valid=valid)
        valid = (lv & rv) | (lv & l.arr) | (rv & r.arr)
        return DevVal("bool", l.arr | r.arr, valid=valid)

    valid = vand(l.valid, r.valid)
    if op in _CMP_OPS:
        if l.kind == "code" or r.kind == "code":
            code, lit = (l, r) if l.kind == "code" else (r, l)
            raise Unsupported("code comparison must be pre-lowered via LUT")
        kind, scale = _common_kind([(l.kind, l.scale), (r.kind, r.scale)])
        a, b = _coerce(l, kind, scale).arr, _coerce(r, kind, scale).arr
        fn = {
            "=": lambda: a == b, "<>": lambda: a != b, "<": lambda: a < b,
            "<=": lambda: a <= b, ">": lambda: a > b, ">=": lambda: a >= b,
        }[op]
        return DevVal("bool", fn(), valid=valid)

    # arithmetic (null-strict: validity is the AND of input validities)
    if op == "/":
        a = _coerce(l, "f64", 0).arr
        b = _coerce(r, "f64", 0).arr
        return DevVal("f64", a / b, valid=valid)
    if op == "*":
        if l.kind == "money" and r.kind == "money":
            return DevVal("money", l.arr * r.arr, l.scale + r.scale, valid=valid)
        if l.kind == "money" and r.kind in ("i64", "bool"):
            return DevVal("money", l.arr * r.arr.astype(jnp.int64), l.scale, valid=valid)
        if r.kind == "money" and l.kind in ("i64", "bool"):
            return DevVal("money", r.arr * l.arr.astype(jnp.int64), r.scale, valid=valid)
        if "f64" in (l.kind, r.kind):
            return DevVal("f64", _coerce(l, "f64", 0).arr * _coerce(r, "f64", 0).arr, valid=valid)
        return DevVal("i64", l.arr.astype(jnp.int64) * r.arr.astype(jnp.int64), valid=valid)
    if op in ("+", "-"):
        if l.kind == "date" and r.kind == "i64":
            arr = l.arr + (r.arr if op == "+" else -r.arr).astype(l.arr.dtype)
            return DevVal("date", arr, valid=valid)
        kind, scale = _common_kind([(l.kind, l.scale), (r.kind, r.scale)])
        a, b = _coerce(l, kind, scale).arr, _coerce(r, kind, scale).arr
        return DevVal(kind, a + b if op == "+" else a - b, scale, valid=valid)
    raise Unsupported(f"binop {op}")


# -- aggregation -------------------------------------------------------------


def segment_aggregate(values: DevVal, mask, gids, num_segments: int, func: str):
    """Masked per-group aggregate; returns jnp array[num_segments]."""
    import jax

    jnp = _jnp()
    if func in ("count", "count_all"):
        return jax.ops.segment_sum(mask.astype(jnp.int64), gids, num_segments=num_segments)
    v = values.arr
    if func == "sum":
        zero = jnp.zeros((), dtype=v.dtype)
        return jax.ops.segment_sum(jnp.where(mask, v, zero), gids, num_segments=num_segments)
    if func == "min":
        big = _max_of(v.dtype)
        return jax.ops.segment_min(jnp.where(mask, v, big), gids, num_segments=num_segments)
    if func == "max":
        small = _min_of(v.dtype)
        return jax.ops.segment_max(jnp.where(mask, v, small), gids, num_segments=num_segments)
    raise Unsupported(f"agg {func}")


def _max_of(dtype):
    jnp = _jnp()
    if jnp.issubdtype(dtype, jnp.integer):
        return jnp.iinfo(dtype).max
    return jnp.inf


def _min_of(dtype):
    jnp = _jnp()
    if jnp.issubdtype(dtype, jnp.integer):
        return jnp.iinfo(dtype).min
    return -jnp.inf
