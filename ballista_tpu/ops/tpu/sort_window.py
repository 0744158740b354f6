"""On-device ORDER BY / window stage family.

`maybe_compile_tpu` wraps eligible SortExec / WindowExec subtrees in
TpuSortStageExec / TpuWindowStageExec (`ballista.tpu.sort.enabled`). The
split of labor is the parity contract:

- ORDER BY: the HOST evaluates the sort-key expressions with the exact
  same `bind_expr`/`evaluate_to_array` calls the CPU oracle sorts, encodes
  them to order-preserving int64 lanes (`_encode_key_arrays`: ints/dates
  widened, floats bit-twiddled, strings as lexicographic-rank dictionary
  codes, NULLS FIRST/LAST as a leading null-rank operand), the DEVICE
  computes the permutation (`kernels.lex_order`: stable LSD radix passes
  over the keys' 32-bit lanes) and the host applies it with
  `pa.Table.take` — payload columns never leave the host, so the output
  bytes are the CPU engine's bytes by construction. ORDER BY ... LIMIT is
  the full order, sliced.
- Windows: a window frame (one PARTITION BY / ORDER BY) is ONE device
  program a task (`_frame_jit`, `window_segscan_<functions>`). The host
  evaluates the key expressions and hands over zero-copy views of their
  values (`_key_operand`: a float's raw bits, an int32 / int64 as stored;
  only dictionary ranks, decimals, dates, bools and narrow ints come as
  `_order_lane`'s host-built lane). The device encodes the lanes by
  `_encode_key_arrays`' rules (`_device_lane`), orders them
  (`kernels.lex_order_sorted`), finds the partition and peer boundaries
  over the sorted lanes, scans `row_number` / `rank`
  (`kernels.segmented_scan`, blocked: seconds to compile at 2^24 lanes)
  and scatters them back to input order: one int32 lane a function comes
  back, and the host builds the Arrow array.

Both are jitted at power-of-two lane counts so one compilation serves
every partition of a bucket; nothing of the family stays resident. An
ineligible shape raises Unsupported and the operator falls back to the
CPU oracle over the SAME materialized input (never re-executing the
child).

Order-preserving int64 encoding per key kind:

  i64 / date / money / bool  value (or unscaled cents) as int64 — exact
  f64                        -0.0 canonicalized to +0.0, NaN to INT64_MAX
                             (pyarrow sorts NaN greatest), then the
                             sign-fold bit twiddle: b >= 0 → b, else
                             ~b | sign bit — total order == float order
  code                       host-ranked dictionary codes; equal strings
                             under duplicate dictionary entries share one
                             rank so ties fall through to stability
  DESC                       bitwise NOT of the ascending lane (no
                             INT64_MIN negation overflow)
  NULLS FIRST/LAST           leading operand: nulls_first → 1 - is_valid
                             complement trick below keeps nulls ahead;
                             always sorted ascending

Window aggregates take their frame from the same program (the
permutation and both boundary planes come back with it), run their
per-segment cumulative state as device segmented scans of their own, and
keep the oracle's `_peer_last`/`_emit_agg`/`_decimal_prepare` — so NULL
masks, decimal reconstruction, and peer sharing are shared code, not
reimplementations.
"""

from __future__ import annotations

import contextlib
import functools
import logging
import threading
import zlib
from dataclasses import dataclass
from typing import Iterator, Optional

import numpy as np
import pyarrow as pa

from ballista_tpu.config import (
    BallistaConfig,
    TPU_MIN_ROWS,
    TPU_SORT_ENABLED,
)
from ballista_tpu.ops.phys_expr import bind_expr, evaluate_to_array
from ballista_tpu.ops.tpu.columnar import encode_column
from ballista_tpu.ops.tpu.kernels import BelowRowFloor, Unsupported
from ballista_tpu.ops.tpu.runtime import device_scope, ensure_jax
from ballista_tpu.plan.expressions import SortKey, WindowFunction
from ballista_tpu.plan.physical import (
    ExecutionPlan,
    TaskContext,
    _concat,
    _empty_batch,
    _sort_table,
)
from ballista_tpu.plan.schema import DFSchema
from ballista_tpu.tracing import RUN_STATS, STAGE_OUTCOMES

log = logging.getLogger(__name__)

_I64_MAX = (1 << 63) - 1
_I64_MIN = -(1 << 63)
_I32_MAX = (1 << 31) - 1
_I32_MIN = -(1 << 31)
_SIGN = 1 << 63

_WINDOW_DEVICE_FUNCS = ("row_number", "rank", "count", "sum", "min", "max")


# ---------------------------------------------------------------------------
# cumulative kernel counters (heartbeat gauges; the hbm spill-counter
# pattern — later clean runs must not erase earlier evidence)

_CTR_LOCK = threading.Lock()
_COUNTERS = {
    "sort_invocations": 0,
    "window_invocations": 0,
    "topk_rows_kept": 0,
    "window_partitions": 0,
    "sort_full_materializations": 0,
    "window_fused_frames": 0,
}
_KERNEL_S = [0.0]


def _count(key: str, delta: int = 1) -> int:
    with _CTR_LOCK:
        _COUNTERS[key] += int(delta)
        val = _COUNTERS[key]
    _publish_counters()
    return val


def _publish_counters() -> None:
    """Mirror the cumulative counters into RUN_STATS (literal keys — the
    stats-sync pass matches emit sites by string constant)."""
    with _CTR_LOCK:
        snap = dict(_COUNTERS)
    RUN_STATS.set("sort_invocations", snap["sort_invocations"])
    RUN_STATS.set("window_invocations", snap["window_invocations"])
    RUN_STATS.set("topk_rows_kept", snap["topk_rows_kept"])
    RUN_STATS.set("window_partitions", snap["window_partitions"])
    RUN_STATS.set("sort_full_materializations",
                  snap["sort_full_materializations"])
    RUN_STATS.set("window_fused_frames", snap["window_fused_frames"])


def _note_kernel_s(dt: float) -> None:
    with _CTR_LOCK:
        _KERNEL_S[0] += dt
        val = round(_KERNEL_S[0], 4)
    RUN_STATS.set("sort_kernel_s", val)


def counters_snapshot() -> dict:
    with _CTR_LOCK:
        return dict(_COUNTERS, sort_kernel_s=round(_KERNEL_S[0], 4))


@contextlib.contextmanager
def _dispatch(family: str, exprs: list, partition: int, ctx: TaskContext):
    """One task's device attempt: the `bt.stage.dispatch` span, and a RunStats
    record of its own (`<family>_<crc of the stage's expressions>_p<partition>`:
    a sort / window stage is a task a partition, so a record a task, each
    with `dispatches` 1) holding what the attempt sets and, if it ends, its
    seconds as `exec_s`."""
    tag = f"{family}_{zlib.crc32(', '.join(map(str, exprs)).encode()):08x}_p{partition}"
    with device_scope(ctx.device_ordinal), RUN_STATS.run(tag), \
            RUN_STATS.span("bt.stage.dispatch", family=family) as span:
        yield
        RUN_STATS.set("exec_s", round(span.seconds, 6))


# ---------------------------------------------------------------------------
# host-side key encoding


def _dict_ranks(dictionary: list) -> np.ndarray:
    """code → lexicographic rank; duplicate dictionary values (legal in
    user-supplied dictionary arrays) share one rank so equal strings tie
    exactly like the CPU comparator and fall through to the next key."""
    if any(v is None for v in dictionary):
        raise Unsupported("null entry in sort-key dictionary")
    ranks = np.zeros(max(len(dictionary), 1), dtype=np.int64)
    order = sorted(range(len(dictionary)), key=lambda j: dictionary[j])
    r = -1
    prev = object()
    for j in order:
        if dictionary[j] != prev:
            r += 1
            prev = dictionary[j]
        ranks[j] = r
    return ranks


def _f64_to_ordered(v: np.ndarray) -> np.ndarray:
    """float64 → int64 with the same order (sign-fold of the raw bits);
    exactly invertible, ±0.0 and NaN payloads included."""
    bits = np.ascontiguousarray(v, dtype=np.float64).view(np.int64)
    return np.where(bits >= 0, bits, (~bits) | np.int64(-_SIGN))


def _ordered_to_f64(lane: np.ndarray) -> np.ndarray:
    lane = np.ascontiguousarray(lane, dtype=np.int64)
    bits = np.where(lane >= 0, lane, ~(lane & np.int64(_I64_MAX)))
    return bits.view(np.float64)


def _order_lane(arr: pa.Array):
    """Encode one evaluated key column as an order-preserving int64 lane.
    Returns (lane i64[n], is_valid bool[n] | None, nan bool[n] | None,
    kind)."""
    dc = encode_column(arr)
    if dc is None:
        raise Unsupported(f"unencodable sort key type {arr.type}")
    nan = None
    if dc.kind in ("i64", "date", "money"):
        lane = dc.data.astype(np.int64, copy=False)
    elif dc.kind == "bool":
        lane = dc.data.astype(np.int64)
    elif dc.kind == "code":
        lane = _dict_ranks(dc.dictionary)[dc.data.astype(np.int64, copy=False)]
    elif dc.kind == "f64":
        v = dc.data + 0.0  # canonicalize -0.0 → +0.0
        lane = _f64_to_ordered(v)
        nan = np.isnan(v)  # placed after the direction flip, see caller
    else:
        raise Unsupported(f"sort key kind {dc.kind}")
    return np.ascontiguousarray(lane), dc.valid, nan, dc.kind


def _encode_key_arrays(arrays: list, orders: list) -> tuple[list, list]:
    """Encode evaluated key arrays into device sort operands.

    `orders` is [(ascending, nulls_first)] per array. Returns
    (key_ops, key_meta): key_ops is [(null_rank i64[n] | None, lane
    i64[n])] to be sorted ASCENDING lexicographically with a trailing
    position tiebreak; key_meta is [(kind, nullable)] for the estimate."""
    key_ops: list = []
    key_meta: list = []
    for arr, (asc, nulls_first) in zip(arrays, orders):
        lane, valid, nan, kind = _order_lane(arr)
        if not asc:
            lane = ~lane
        if nan is not None and nan.any():
            # pyarrow sorts NaN at the END of the non-null block in BOTH
            # directions (placement, not magnitude), so the override goes
            # on top of the flipped lane. I64_MAX-1 needs float bits of a
            # NaN payload to reach → no real value collides, and it stays
            # strictly below the I64_MAX pad sentinel.
            lane = np.where(nan, np.int64(_I64_MAX - 1), lane)
        nrank = None
        if valid is not None:
            is_null = (~valid).astype(np.int64)
            nrank = (1 - is_null) if nulls_first else is_null
            nrank = np.ascontiguousarray(nrank)
        key_ops.append((nrank, lane))
        key_meta.append((kind, valid is not None))
    return key_ops, key_meta


# ---------------------------------------------------------------------------
# device permutation


def _admit(est, config: BallistaConfig) -> None:
    """HBM admission for a sort/window stage: no splittable build side, so
    the ladder is run-whole vs CPU demotion, reason recorded."""
    from ballista_tpu.ops.tpu import hbm
    budget = hbm.resolve_hbm_budget(config)
    plan = hbm.plan_stage(est, budget, grace_eligible=False, grace_fanout=2,
                          grace_max_depth=0)
    RUN_STATS.set("hbm_budget_bytes", budget)
    RUN_STATS.set("hbm_plan", plan.decision)
    RUN_STATS.set("hbm_plan_reason", plan.reason)
    if plan.decision == hbm.CPU_DEMOTE:
        raise Unsupported(f"hbm admission: {plan.reason}")


# analysis: ignore[bounded-cache] one entry a program and shape jax has compiled in this process: lanes are powers of two (under 40), six kernels, a few dtype lists
_CALLED: set = set()  # (kernel, lanes, operand dtypes) dispatched once in this process


@contextlib.contextmanager
def _device_call(kernel: str, n: int, lanes: int, operands: list, spec: tuple = ()):
    """The span of one synced device call of the family — pad, upload, the
    jitted program, the result's fetch: the host blocked on the device.
    `bt.device.exec`; the first call of a program at a shape (and `spec`,
    what else the program was built for) compiles it (or loads the
    persistent cache's binary) inside, and is named `bt.compile.xla` as a
    stage's is, its seconds added to the task's `xla_compile_s`."""
    key = (kernel, lanes, tuple(str(a.dtype) for a in operands), spec)
    with _CTR_LOCK:
        first = key not in _CALLED
        _CALLED.add(key)
    with RUN_STATS.span("bt.compile.xla" if first else "bt.device.exec", rows=n,
                        kernel=kernel, lanes=lanes) as span:
        yield span
    _note_kernel_s(span.seconds)
    if first:
        rec = RUN_STATS.current() or {}
        RUN_STATS.set("xla_compile_s", round(rec.get("xla_compile_s", 0.0) + span.seconds, 6))


class _Uploads:
    """Tracks actual device bytes of every operand shipped for a stage, so
    the fill test can assert estimate >= actual (RUN_STATS device_bytes)."""

    def __init__(self):
        self.bytes = 0
        self.lanes = 0  # padded lanes ordered (`window_lanes`)
        self.frames = 0  # frames built by one program (`window_frames_fused`)
        self.scans = 0  # separate segmented scans dispatched (`window_scans`)
        self.segments = 0  # window partitions found (`window_segments`)

    def put(self, arr: np.ndarray):
        jax = ensure_jax()
        self.bytes += int(arr.nbytes)
        return jax.numpy.asarray(arr)


def _perm_full(key_ops: list, n: int, up: _Uploads) -> np.ndarray:
    """Full ordering permutation of n rows by the encoded key operands:
    pad, upload, `kernels.lex_order` and the permutation's fetch, the host
    blocked on the device throughout (one `bt.device.exec`)."""
    jax = ensure_jax()
    # the stable lexicographic order over every operand, at a power-of-two
    # lane count: max-value sentinels pad the tail and, the order being
    # stable, stay behind every real row — the first n of the permutation
    # are the real rows in order. Lanes whose values fit ship as int32 (one
    # radix pass instead of two).
    L = _pow2(n)
    operands: list = []
    for nrank, lane in key_ops:
        if nrank is not None:
            operands.append(nrank.astype(np.int32))
        if len(lane) and _I32_MIN <= lane.min() and lane.max() <= _I32_MAX:
            lane = lane.astype(np.int32)
        operands.append(lane)
    before = up.bytes
    with _device_call("lex_order", n, L, operands) as span:
        flat = [up.put(_pad_max(lane, L)) for lane in operands]
        up.bytes += L * 4
        perm = np.asarray(jax.device_get(_lex_order_jit()(*flat)))[:n]
        span.set(bytes=up.bytes - before)
    up.lanes += L
    return perm


def _pad_max(a: np.ndarray, L: int) -> np.ndarray:
    """`a` padded to L lanes with its dtype's largest value."""
    if len(a) == L:
        return np.ascontiguousarray(a)
    out = np.full(L, np.iinfo(a.dtype).max, dtype=a.dtype)
    out[: len(a)] = a
    return out


@functools.lru_cache(maxsize=1)
def _lex_order_jit():
    from ballista_tpu.ops.tpu.kernels import lex_order

    def sort_lex_order(*keys):
        return lex_order(list(keys))

    return ensure_jax().jit(sort_lex_order)


@functools.lru_cache(maxsize=8)
def _segscan_jit(func: str):
    from ballista_tpu.ops.tpu.kernels import segmented_scan

    def window_segscan(v, b):
        return segmented_scan(v, b, func)

    window_segscan.__name__ = window_segscan.__qualname__ = f"window_segscan_{func}"
    return ensure_jax().jit(window_segscan)


def _pow2(n: int) -> int:
    p = 1
    while p < max(n, 1):
        p *= 2
    return p


# ---------------------------------------------------------------------------
# static eligibility (plan-time; keeps ineligible stages unwrapped)


def _sortable_type(t: pa.DataType) -> bool:
    if (pa.types.is_integer(t) or pa.types.is_date(t) or pa.types.is_boolean(t)
            or pa.types.is_floating(t) or pa.types.is_string(t)
            or pa.types.is_large_string(t) or pa.types.is_dictionary(t)):
        return True
    if pa.types.is_decimal128(t):
        # the exact money lane; wide decimals would round through f64 and
        # could mis-order near-ties — those stay on the host comparator
        return 0 <= t.scale <= 4 and t.precision - t.scale <= 14
    return False


def sort_static_ok(keys: list, schema: DFSchema) -> bool:
    try:
        return all(_sortable_type(k.expr.data_type(schema)) for k in keys)
    except Exception:  # noqa: BLE001 — unresolvable expr: not ours to run
        return False


def _int_like(t: pa.DataType) -> bool:
    if pa.types.is_integer(t) or pa.types.is_boolean(t):
        return True
    return (pa.types.is_decimal128(t)
            and 0 <= t.scale <= 4 and t.precision - t.scale <= 14)


def window_static_ok(window_exprs: list, schema: DFSchema) -> bool:
    try:
        for w in window_exprs:
            if w.frame is not None or w.func not in _WINDOW_DEVICE_FUNCS:
                return False
            if not sort_static_ok(list(w.order_by), schema):
                return False
            if not all(_sortable_type(e.data_type(schema)) for e in w.partition_by):
                return False
            if w.func == "sum":
                # float sums take the oracle's sequential f64 cumsum; a
                # log-depth device scan would round differently — demote
                if not w.args or not _int_like(w.args[0].data_type(schema)):
                    return False
            elif w.func in ("min", "max"):
                t = w.args[0].data_type(schema) if w.args else None
                if t is None or not (_int_like(t) or pa.types.is_floating(t)):
                    return False
        return True
    except Exception:  # noqa: BLE001
        return False


# ---------------------------------------------------------------------------
# ORDER BY [LIMIT]


def _device_sort(tbl: pa.Table, df_schema: DFSchema, keys: list,
                 fetch: Optional[int], config: BallistaConfig) -> pa.Table:
    from ballista_tpu.ops.tpu import fusion
    n = tbl.num_rows
    if n == 0:
        return tbl
    if n < max(int(config.get(TPU_MIN_ROWS)), 1):
        raise BelowRowFloor(n)
    batch = tbl.combine_chunks().to_batches()[0]
    arrays = [evaluate_to_array(bind_expr(k.expr, df_schema), batch)
              for k in keys]
    orders = [(k.ascending, k.nulls_first) for k in keys]
    key_ops, key_meta = _encode_key_arrays(arrays, orders)

    _admit(fusion.estimate_sort_stage(n, key_meta), config)

    up = _Uploads()
    perm = _perm_full(key_ops, n, up)
    _count("sort_invocations")
    if fetch is not None:
        # ORDER BY ... LIMIT orders every row, then slices
        _count("sort_full_materializations")
    RUN_STATS.set("device_bytes", up.bytes)

    out = tbl.take(pa.array(perm))
    if fetch is not None:
        out = out.slice(0, int(fetch))
    return out


class TpuSortStageExec(ExecutionPlan):
    """SortExec on the device: materialize the child once, compute the
    ordering permutation on device, take on the host. Unsupported shapes
    host-sort the SAME materialized table (no child re-execution)."""

    own_span = True  # `bt.stage.dispatch` and the spans inside it

    def __init__(self, input: ExecutionPlan, keys: list[SortKey],
                 fetch: Optional[int], config: BallistaConfig):
        super().__init__(input.df_schema)
        self.input = input
        self.keys = keys
        self.fetch = fetch
        self.config = config
        self.tpu_count = 0
        self.fallback_count = 0

    def children(self) -> list[ExecutionPlan]:
        return [self.input]

    def with_children(self, c):
        return TpuSortStageExec(c[0], self.keys, self.fetch, self.config)

    def output_partition_count(self) -> int:
        return self.input.output_partition_count()

    def node_str(self) -> str:
        k = ", ".join(str(x) for x in self.keys)
        f = f", fetch={self.fetch}" if self.fetch is not None else ""
        extra = ""
        if self.tpu_count or self.fallback_count:
            extra = (f" device_runs={self.tpu_count}"
                     f" cpu_fallbacks={self.fallback_count}")
        return f"TpuSortStageExec: [{k}]{f}{extra}"

    def execute(self, partition: int, ctx: TaskContext) -> Iterator[pa.RecordBatch]:
        return self._timed(iter(self._run(partition, ctx)))

    def _run(self, partition: int, ctx: TaskContext):
        batches = [b for b in self.input.execute(partition, ctx) if b.num_rows]
        tbl = _concat(batches, self.schema())
        try:
            with _dispatch("sort", self.keys, partition, ctx):
                out = _device_sort(tbl, self.df_schema, self.keys, self.fetch,
                                   self.config)
            if tbl.num_rows:  # an empty partition dispatches nothing
                STAGE_OUTCOMES.note("sort", "device")
            self.tpu_count += 1
        except Unsupported as e:
            log.info("tpu sort fallback (%s)", e)
            STAGE_OUTCOMES.note_fallback("sort", e)
            out = self._host_sort(tbl)
        except Exception as e:  # noqa: BLE001 — device trouble never fails the query
            log.warning("tpu sort raised; falling back to cpu", exc_info=True)
            STAGE_OUTCOMES.note_fallback("sort", e)
            out = self._host_sort(tbl)
        if out.num_rows == 0:
            yield _empty_batch(self.schema())
            return
        for b in out.combine_chunks().to_batches(max_chunksize=ctx.batch_size):
            yield b

    def _host_sort(self, tbl: pa.Table) -> pa.Table:
        self.fallback_count += 1
        with RUN_STATS.span("bt.stage.fallback", family="sort"):
            out = _sort_table(tbl, self.df_schema, self.keys)
            if self.fetch is not None:
                out = out.slice(0, self.fetch)
            return out


# ---------------------------------------------------------------------------
# window aggregates


@dataclass
class _DeviceFrame:
    """What one frame program hands back: each ranking function's values in
    input order (int32), and for the aggregates the sorted row order and
    its two boundary planes (None where no aggregate shares the frame)."""

    ranked: dict
    idx: Optional[np.ndarray] = None
    new_part: Optional[np.ndarray] = None
    new_peer: Optional[np.ndarray] = None


def _values_view(arr: pa.Array, dtype) -> np.ndarray:
    """The values buffer of a fixed-width Arrow array, zero-copy (null slots
    hold whatever the buffer holds there)."""
    buf = arr.buffers()[1]
    return np.frombuffer(buf, dtype=dtype, count=arr.offset + len(arr))[arr.offset:]


def _key_operand(arr) -> tuple:
    """One evaluated window key as the frame program takes it: (src, values,
    valid bool[n] | None, kind for the estimate). `f64`: a float key's raw
    bits, an int64 view of its values; `int`: an int32 / int64 key's own
    values; `host`: the order-preserving lane `_order_lane` builds for the
    kinds whose order needs the host (dictionary ranks, decimals, dates,
    bools, narrow ints), taken as ordered. The plane rides only where the
    column has nulls."""
    if isinstance(arr, pa.ChunkedArray):
        arr = arr.combine_chunks()
    t = arr.type
    if pa.types.is_floating(t) or t in (pa.int32(), pa.int64()):
        valid = arr.is_valid().to_numpy(zero_copy_only=False) if arr.null_count else None
        if pa.types.is_floating(t):
            if not pa.types.is_float64(t):
                arr = arr.cast(pa.float64())
            return "f64", _values_view(arr, np.int64), valid, "f64"
        return "int", _values_view(arr, t.to_pandas_dtype()), valid, "i64"
    lane, valid, _, kind = _order_lane(arr)
    if len(lane) and _I32_MIN <= lane.min() and lane.max() <= _I32_MAX:
        lane = lane.astype(np.int32)
    return "host", lane, valid, kind


def _device_lane(x, src: str, asc: bool):
    """A key's order-preserving lane, on the device, by `_encode_key_arrays`'
    rules in integer operations alone (the chip emulates float64 inexactly):
    a float's bits have -0.0 folded into +0.0 and the sign folded
    (b >= 0 → b, else ~b | sign bit), DESC is the NOT of the lane, and NaN
    takes INT64_MAX - 1 after the flip (pyarrow places NaN last in both
    directions). Integers and host lanes are ordered already."""
    jnp = ensure_jax().numpy
    lane = x
    if src == "f64":
        nan = (x & jnp.int64(_I64_MAX)) > jnp.int64(0x7FF0000000000000)
        b = jnp.where(x == jnp.int64(_I64_MIN), jnp.int64(0), x)
        lane = jnp.where(b >= 0, b, ~b | jnp.int64(_I64_MIN))
    if not asc:
        lane = ~lane
    if src == "f64":
        lane = jnp.where(nan, jnp.int64(_I64_MAX - 1), lane)
    return lane


@functools.lru_cache(maxsize=32)
def _frame_jit(keys: tuple, ranking: tuple, frame: bool, L: int):
    """The one device program of a window frame at L lanes. `keys` is
    ((src, ascending, nulls_first, nullable, partition key)) in sort order;
    the program takes (n, then each key's values and, where nullable, its
    validity plane) and, in order: encodes the lanes (`_device_lane`; a
    null's lane is 0, its null rank a leading operand; lanes past n take
    their dtype's largest value), orders them (`kernels.lex_order_sorted`),
    finds the partition starts over the sorted partition keys and, where
    `rank` or an aggregate needs them, the peer starts (`_changes`' rule:
    nulls equal, NaN never), scans each function in `ranking` in sorted
    order (`kernels.segmented_scan`) and scatters it back to input order.
    Returns (the ranking results, int32 [L] each; the partitions found),
    and with `frame` the permutation and both boundary planes, for the
    aggregates' value scans. Named `window_segscan_<functions>`."""
    jax = ensure_jax()
    jnp = jax.numpy
    from ballista_tpu.ops.tpu.kernels import _order_lanes, lex_order_sorted, segmented_scan

    def window_segscan(n, *cols):
        iota = jnp.arange(L, dtype=jnp.int32)
        live = iota < n
        ops: list = []  # (operand, key index, float lane)
        it = iter(cols)
        for k, (src, asc, nulls_first, nullable, _) in enumerate(keys):
            lane = _device_lane(next(it), src, asc)
            if nullable:
                valid = next(it)
                nrank = (valid if nulls_first else ~valid).astype(jnp.int32)
                ops.append((jnp.where(live, nrank, _I32_MAX), k, False))
                lane = jnp.where(valid, lane, jnp.zeros((), lane.dtype))
            ops.append((jnp.where(live, lane, jnp.iinfo(lane.dtype).max), k, src == "f64"))
        if ops:
            perm, top = lex_order_sorted([op for op, _, _ in ops])
        else:
            perm, top = iota, None
        first = iota == 0

        def starts(which):
            flag = first
            at = 0
            for op, k, is_f in ops:
                lanes = _order_lanes(op)
                if k in which:
                    s = [top if at + j == 0 else lane[perm] for j, lane in enumerate(lanes)]
                    for lane in s:
                        flag = flag | jnp.concatenate([first[:1], lane[1:] != lane[:-1]])
                    if is_f:  # INT64_MAX - 1 as its two lanes: a NaN is a group of its own
                        flag = flag | ((s[0] == _I32_MAX) & (s[1] == _I32_MAX - 1))
                at += len(lanes)
            return flag

        part = {k for k, key in enumerate(keys) if key[4]}
        new_part = starts(part)
        new_peer = None
        if frame or "rank" in ranking:
            new_peer = new_part | starts(set(range(len(keys))) - part)
        out = []
        if ranking:
            rn = segmented_scan(jnp.ones((L,), jnp.int32), new_part, "sum")
        for func in ranking:
            r = rn
            if func == "rank":  # peer start - segment start + 1
                r = segmented_scan(jnp.where(new_peer, iota, _I32_MIN), new_part, "max") - iota + rn
            out.append(jnp.zeros((L,), jnp.int32).at[perm].set(r, unique_indices=True))
        found = jnp.sum(new_part & live, dtype=jnp.int32)
        if frame:
            return tuple(out), found, perm, new_part, new_peer
        return tuple(out), found

    name = "window_segscan_" + "_".join(ranking or ("frame",))
    window_segscan.__name__ = window_segscan.__qualname__ = name
    return jax.jit(window_segscan)


def _pad_zero(a: np.ndarray, L: int) -> np.ndarray:
    """`a` padded to L lanes with zeros (the program masks lanes past n)."""
    if len(a) == L:
        return np.ascontiguousarray(a)
    out = np.empty(L, dtype=a.dtype)
    out[: len(a)] = a
    out[len(a):] = 0
    return out


def _device_frame(batch: pa.RecordBatch, w: WindowFunction, schema: DFSchema,
                  config: BallistaConfig, funcs: list, up: "_Uploads") -> _DeviceFrame:
    """A window frame (one PARTITION BY / ORDER BY) and the ranking functions
    over it, in ONE device call: the host evaluates the keys and hands over
    zero-copy views of their values (`_key_operand`), the frame program
    (`_frame_jit`) does the rest, and one int32 lane of results comes back
    a ranking function; the permutation and the boundary planes only where
    an aggregate shares the frame."""
    from ballista_tpu.ops.tpu import fusion
    jax = ensure_jax()
    n = batch.num_rows
    with RUN_STATS.span("bt.window.keys", rows=n) as span:
        part = [_key_operand(evaluate_to_array(bind_expr(e, schema), batch))
                for e in w.partition_by]
        order = [_key_operand(evaluate_to_array(bind_expr(k.expr, schema), batch))
                 for k in w.order_by]
        span.set(key_lanes=sum(1 + (valid is not None) for _, _, valid, _ in part + order))
    key_meta = [(kind, valid is not None) for _, _, valid, kind in part + order]
    _admit(fusion.estimate_sort_stage(n, key_meta or [("i64", False)],
                                      window_funcs=max(len(funcs), 1)),
           config)

    orders = [(True, False, True)] * len(part) + [
        (k.ascending, k.nulls_first, False) for k in w.order_by]
    keys = tuple((src, asc, nf, valid is not None, is_part)
                 for (src, _, valid, _), (asc, nf, is_part) in zip(part + order, orders))
    ranking = tuple(f for f in ("row_number", "rank") if f in funcs)
    frame = any(f not in ranking for f in funcs)
    operands = [a for _, values, valid, _ in part + order
                for a in ((values,) if valid is None else (values, valid))]
    L = _pow2(n)
    program = _frame_jit(keys, ranking, frame, L)
    before = up.bytes
    with _device_call(program.__name__[len("window_"):], n, L, operands,
                      spec=(keys, ranking, frame)) as span:
        flat = [up.put(_pad_zero(a, L)) for a in operands]
        got = jax.device_get(program(np.int32(n), *flat))
        up.bytes += L * (4 * len(ranking) + (4 + 1 + 1 if frame else 0))
        found = int(got[1])
        span.set(bytes=up.bytes - before, partitions=found)
    if keys:
        up.lanes += L
    up.frames += 1
    up.segments += found
    _count("window_partitions", found)
    _count("window_fused_frames")
    ranked = {f: np.asarray(r)[:n] for f, r in zip(ranking, got[0])}
    if not frame:
        return _DeviceFrame(ranked)
    return _DeviceFrame(ranked, *(np.asarray(a)[:n] for a in got[2:]))


def _seg_scan(vals: np.ndarray, boundary: np.ndarray, func: str,
              up: _Uploads) -> np.ndarray:
    """Device inclusive segmented scan (reset at boundary lanes): pad,
    upload, `window_segscan_<func>` and the result's fetch, the host blocked
    on the device throughout (one `bt.device.exec`)."""
    jax = ensure_jax()
    n = len(vals)
    # power-of-two lanes: one compilation per bucket, not per partition
    # row count
    L = _pow2(n)
    before = up.bytes
    with _device_call(f"segscan_{func}", n, L, [vals]) as span:
        v = np.zeros(L, dtype=vals.dtype)
        v[:n] = vals
        f = np.ones(L, dtype=bool)  # padding lanes self-reset
        f[:n] = boundary
        out = np.asarray(jax.device_get(_segscan_jit(func)(up.put(v), up.put(f))))[:n]
        span.set(bytes=up.bytes - before)
    up.scans += 1
    return out


def _device_compute_one(batch: pa.RecordBatch, w: WindowFunction,
                        schema: DFSchema, fr: _DeviceFrame, up: _Uploads) -> pa.Array:
    """One window expression over its frame: a ranking function's values
    are the frame program's, built into Arrow; an aggregate's value scans
    run on the device inside the oracle's gather/scatter/emit skeleton."""
    from ballista_tpu.ops.cpu.window import _decimal_prepare, _emit_agg, _peer_last
    n = batch.num_rows
    out_type = w.data_type(schema)
    if n == 0:
        return pa.array([], out_type)
    with RUN_STATS.span("bt.window.emit", rows=n, func=w.func):
        if w.func in fr.ranked:
            return pa.array(fr.ranked[w.func]).cast(out_type)
        return _emit_scan_agg(batch, w, schema, fr, fr.new_part, up,
                              out_type, _decimal_prepare, _emit_agg,
                              _peer_last, n)


def _emit_scan_agg(batch, w, schema, fr, boundary, up, out_type,
                   _decimal_prepare, _emit_agg, _peer_last, n):
    import pyarrow.compute as pc  # noqa: F401 — _decimal_prepare path

    dec_scale = None
    if w.args:
        arr = evaluate_to_array(bind_expr(w.args[0], schema),
                                batch).take(pa.array(fr.idx))
        valid = arr.is_valid().to_numpy(zero_copy_only=False).astype(bool)
        if pa.types.is_decimal(arr.type):
            arr, dec_scale = _decimal_prepare(arr, w, out_type)
    else:  # count(*)
        arr = None
        valid = np.ones(n, dtype=bool)
    last = _peer_last(fr.new_peer, n)

    seg_cnt = _seg_scan(valid.astype(np.int64), boundary, "sum", up)
    if w.func == "count":
        out = np.empty(n, dtype=np.int64)
        out[fr.idx] = seg_cnt[last]
        return pa.array(out, out_type)

    vals = arr.to_numpy(zero_copy_only=False)
    if w.func == "sum":
        # nullable ints come back from to_numpy as float64-with-NaN, and
        # the oracle then runs its cumsum in float64 — recover the exact
        # ints via fill_null and bound the magnitude so the float path is
        # exact too (every prefix sum < 2^53 → the two agree bit-for-bit)
        import pyarrow.compute as pc

        if pa.types.is_integer(arr.type) or pa.types.is_boolean(arr.type):
            v = pc.fill_null(arr, 0).cast(pa.int64()).to_numpy(
                zero_copy_only=False).astype(np.int64, copy=False)
        elif np.issubdtype(np.asarray(vals).dtype, np.integer):
            v = np.where(valid, np.asarray(vals, dtype=np.int64), 0)
        else:
            raise Unsupported("float window sum (sequential-cumsum parity)")
        if arr.null_count and n:
            m = int(np.abs(v).max())
            if m and m * n >= (1 << 53):
                raise Unsupported("window sum magnitude beyond exact-f64")
        out_sorted = _seg_scan(v, boundary, "sum", up)[last]
    else:  # min / max
        is_f = (np.issubdtype(np.asarray(vals).dtype, np.floating)
                or pa.types.is_floating(out_type))
        # the running extreme only ever compares: the device scans int64
        # lanes, never floats. A TPU emulates f64 inexactly (a value does
        # not come back bit-identical), so floats ride as their order-
        # preserving int64 image and are decoded here. NaN propagates like
        # np.minimum/np.maximum.accumulate: it takes the extreme the scan
        # is looking for; null slots take the other one (the identity).
        ident, nan_mark = ((_I64_MAX, _I64_MIN) if w.func == "min"
                           else (_I64_MIN, _I64_MAX))
        if is_f:
            fv = np.asarray(vals, dtype=np.float64)
            v = np.where(np.isnan(fv), np.int64(nan_mark), _f64_to_ordered(fv))
        else:
            v = np.asarray(vals, dtype=np.int64)
        v = np.where(valid, v, np.int64(ident))
        out_sorted = _seg_scan(v, boundary, w.func, up)[last]
        if is_f:
            out_sorted = np.where(out_sorted == nan_mark, np.nan,
                                  _ordered_to_f64(out_sorted))
    mask_sorted = seg_cnt[last] == 0  # SQL: aggregate over zero rows is NULL

    out = np.empty(n, dtype=out_sorted.dtype)
    out[fr.idx] = out_sorted
    mask = np.empty(n, dtype=bool)
    mask[fr.idx] = mask_sorted
    return _emit_agg(out, out_type, mask, dec_scale)


def _device_windows(batch: pa.RecordBatch, window_exprs: list,
                    schema: DFSchema, config: BallistaConfig) -> list[pa.Array]:
    n = batch.num_rows
    if n < max(int(config.get(TPU_MIN_ROWS)), 1):
        raise BelowRowFloor(n)
    if not window_static_ok(window_exprs, schema):
        raise Unsupported("window shape not device-eligible")
    groups: dict[tuple, list] = {}
    for w in window_exprs:
        key = (tuple(str(e) for e in w.partition_by),
               tuple(str(k) for k in w.order_by))
        groups.setdefault(key, []).append(w.func)
    frames: dict[tuple, _DeviceFrame] = {}
    out = []
    up = _Uploads()  # stage-total device bytes: frames + scans (fill test)
    for w in window_exprs:
        key = (tuple(str(e) for e in w.partition_by),
               tuple(str(k) for k in w.order_by))
        if key not in frames:
            frames[key] = _device_frame(batch, w, schema, config,
                                        groups[key], up)
        out.append(_device_compute_one(batch, w, schema, frames[key], up))
    RUN_STATS.set("device_bytes", up.bytes)
    RUN_STATS.set("window_rows", n)
    RUN_STATS.set("window_lanes", up.lanes)
    RUN_STATS.set("window_segments", up.segments)
    RUN_STATS.set("window_frames_fused", up.frames)
    RUN_STATS.set("window_scans", up.scans)
    _count("window_invocations")
    return out


class TpuWindowStageExec(ExecutionPlan):
    """WindowExec on the device: a frame program a PARTITION BY / ORDER BY
    (order, boundaries, ranking scans, scatter back), the aggregates'
    value scans beside it, their emit logic shared with the CPU oracle.
    Ineligible shapes run `compute_windows` over the SAME materialized
    batch."""

    own_span = True  # `bt.stage.dispatch` and the spans inside it

    def __init__(self, input: ExecutionPlan, window_exprs: list,
                 df_schema: DFSchema, config: BallistaConfig):
        super().__init__(df_schema)
        self.input = input
        self.window_exprs = window_exprs
        self.config = config
        self.tpu_count = 0
        self.fallback_count = 0

    def children(self) -> list[ExecutionPlan]:
        return [self.input]

    def with_children(self, c):
        return TpuWindowStageExec(c[0], self.window_exprs, self.df_schema,
                                  self.config)

    def output_partition_count(self) -> int:
        return self.input.output_partition_count()

    def node_str(self) -> str:
        extra = ""
        if self.tpu_count or self.fallback_count:
            extra = (f" device_runs={self.tpu_count}"
                     f" cpu_fallbacks={self.fallback_count}")
        return (f"TpuWindowStageExec: "
                f"[{', '.join(map(str, self.window_exprs))}]{extra}")

    def execute(self, partition: int, ctx: TaskContext) -> Iterator[pa.RecordBatch]:
        return self._timed(iter(self._run(partition, ctx)))

    def _run(self, partition: int, ctx: TaskContext):
        batches = [b for b in self.input.execute(partition, ctx) if b.num_rows]
        if not batches:
            yield _empty_batch(self.schema())
            return
        tbl = _concat(batches, self.input.schema())
        batch = tbl.combine_chunks().to_batches()[0] if tbl.num_rows else None
        if batch is None:
            yield _empty_batch(self.schema())
            return
        try:
            with _dispatch("window", self.window_exprs, partition, ctx):
                wins = _device_windows(batch, self.window_exprs,
                                       self.input.df_schema, self.config)
            STAGE_OUTCOMES.note("window", "device")
            self.tpu_count += 1
        except Unsupported as e:
            log.info("tpu window fallback (%s)", e)
            STAGE_OUTCOMES.note_fallback("window", e)
            wins = self._host_windows(batch)
        except Exception as e:  # noqa: BLE001 — device trouble never fails the query
            log.warning("tpu window raised; falling back to cpu", exc_info=True)
            STAGE_OUTCOMES.note_fallback("window", e)
            wins = self._host_windows(batch)
        arrays = [batch.column(i) for i in range(batch.num_columns)] + wins
        out = pa.RecordBatch.from_arrays(arrays, schema=self.schema())
        for off in range(0, out.num_rows, ctx.batch_size):
            yield out.slice(off, min(ctx.batch_size, out.num_rows - off))

    def _host_windows(self, batch: pa.RecordBatch) -> list[pa.Array]:
        from ballista_tpu.ops.cpu.window import compute_windows
        self.fallback_count += 1
        with RUN_STATS.span("bt.stage.fallback", family="window"):
            return compute_windows(batch, self.window_exprs, self.input.df_schema)


def sort_family_enabled(config: BallistaConfig) -> bool:
    return bool(config.get(TPU_SORT_ENABLED))
