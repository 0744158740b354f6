"""Compile physical-plan subtrees to jitted XLA stage functions.

TpuStageExec replaces a `HashAggregateExec(partial)` whose input chain is
Filter*/Projection*/CoalesceBatches* over a scan. The execution model is
built around two facts of TPU systems: HBM is fast, the host↔device link
(PCIe) is not, and XLA loves big static shapes. So:

- the WHOLE table (all scan partitions) is encoded once with UNIFIED
  dictionaries and cached device-resident as [P, N] stacked columns
  (DeviceTableCache; LRU against ballista.tpu.max.device.bytes);
- scan filters and residual operators are lowered into ONE jitted kernel
  that processes all P partitions in a single dispatch: per-partition
  masked segment aggregation with global group ids p*G + g;
- per query the device round trips are O(1): upload LUTs (cached), one
  dispatch, one batched fetch — not O(partitions × outputs).

Output batches match the partial aggregate's schema exactly, so the
downstream repartition/final-aggregate machinery is engine-agnostic —
the per-subtree dispatch pattern of the reference's engine seam
(ballista/executor/src/execution_engine.rs:51,124-147) taken to XLA.

Fallback is runtime-adaptive: unencodable types, NULLs, oversized group
domains, or tiny inputs re-run the original subtree on the CPU engine.
"""

from __future__ import annotations

import functools
import itertools
import logging
import os
import threading
import time
import zlib
from typing import Iterator

import numpy as np
import pyarrow as pa

from ballista_tpu.config import (
    TPU_COMPILE_OVERLAP,
    TPU_FILL_CHUNK_ROWS,
    TPU_FILL_THREADS,
    TPU_HBM_GRACE_BUCKETS,
    TPU_HBM_GRACE_DEPTH,
    TPU_HBM_SPILL_DIR,
    TPU_HBM_SPILL_ENABLED,
    TPU_HBM_SPILL_HOST_BYTES,
    TPU_MAX_DEVICE_BYTES,
    TPU_MIN_ROWS,
    BallistaConfig,
    _env_int,
)
from ballista_tpu.ops.tpu import fusion, hbm, runtime
from ballista_tpu.ops.tpu.columnar import decode_codes, encode_column, encode_stacked, next_bucket
from ballista_tpu.ops.tpu.kernels import (
    BelowRowFloor,
    DevVal,
    Lowering,
    SegmentCompaction,
    Unsupported,
    int_cumsum,
    lex_order,
    live_slots,
    lower_expr,
    segmented_scan,
    true_mask,
)
from ballista_tpu.ops.tpu.runtime import ensure_jax
from ballista_tpu.plan.expressions import Alias, Column, Expr
from ballista_tpu.plan.physical import (
    CoalesceBatchesExec,
    ExecutionPlan,
    FilterExec,
    HashAggregateExec,
    ParquetScanExec,
    ProjectionExec,
    TaskContext,
    _concat,
    _empty_batch,
)
from ballista_tpu.plan.schema import DFSchema

log = logging.getLogger(__name__)

MAX_SEGMENTS = 1 << 16
# A stage works over its LIVE rows: of its row slots it takes the smallest of
# slots / 64, slots / 8 and all of them that holds the rows alive, counted by
# the program itself. The sorted path (_compile_sorted) projects, orders and
# reduces at the first divisor only or at every slot: each of its tiers is an
# ordering of its own to trace, compile and load, and no measured workload
# lands between (q3 keeps 0.4 % of its slots, q18 all or 441 rows). Its count
# leaves out a filter of several match lanes (q21's filtered EXISTS / NOT
# EXISTS), which runs in the tier over the rows the others left. The direct
# path (_compile) probes its join chain at all three, a partition's rows
# compacted within the partition: q5's first join keeps 10.8 % of the slots,
# and a tier there is a handful of gathers, not an ordering.
LIVE_TIERS = (64, 8)

# LruDict moved to utils/lru.py (PR 9) so CPU-side modules can bound their
# caches without importing this module — the executor heartbeat keys TPU
# gauges on `sys.modules` containing this module's name. Re-exported here
# for back-compat.
from ballista_tpu.utils.lru import LruDict  # noqa: E402


# Entry budgets (env-tunable; these are safety rails for long-lived daemons,
# not per-session knobs). Build tables also carry a byte budget: their
# payloads are device-resident and can dwarf the entry count.
_COMPILE_CACHE = LruDict(_env_int("BALLISTA_TPU_COMPILE_CACHE_ENTRIES", 64))
_COMPILE_LOCK = threading.Lock()
# (table_key, fingerprint, mesh, emit, ordinal) → device arrays
_LUT_CACHE = LruDict(_env_int("BALLISTA_TPU_LUT_CACHE_ENTRIES", 256))
# (table_key, fingerprint, join_idx, mesh, ordinal) → BuildTable
_BUILD_CACHE = LruDict(
    _env_int("BALLISTA_TPU_BUILD_CACHE_ENTRIES", 32),
    max_bytes=_env_int("BALLISTA_TPU_BUILD_CACHE_BYTES", 2 * 1024**3),
    sizer=lambda bt: sum(int(getattr(a, "nbytes", 0)) for a in bt.flat_arrays()),
)
# a stage's builds run on helper threads at once: their sums in its record
_BUILD_STATS_LOCK = threading.Lock()


# RunStats / StageOutcomes live in ballista_tpu/tracing.py (jax-free, so the
# client and the scheduler record spans into the same recorder without
# loading this module). Re-exported here: readers import them from
# stage_compiler, and the executor heartbeat keys its TPU gauges on this
# module being loaded.
from ballista_tpu.tracing import (  # noqa: E402,F401
    RUN_STATS,
    STAGE_OUTCOMES,
    RunStats,
    StageOutcomes,
)

KEY_SHIFT = 21  # multi-key combine: k = k1 << 21 | k2 (guarded ranges)


DIRECT_TABLE_MAX = 1 << 27  # 128M entries × int32 = 512 MB HBM ceiling

MAX_JOIN_DUP = 16  # expansion joins unroll this many match lanes at most


class BuildTable:
    """A join's build side, encoded for device probing.

    mode 'direct': keys are dense-enough ints → a [T] int32 lookup table
    (key → build row, -1 absent): ONE gather per probe. mode 'sorted':
    binary search over sorted keys (log B gathers) — the fallback for huge
    key ranges. Non-unique build keys (dup > 1, "expansion joins"): the
    payloads are laid out key-sorted and the lookup yields (first row,
    count); the probe pipeline unrolls dup match lanes (d < count masks)
    so each probe row can emit up to dup joined rows into the agg."""

    def __init__(self, mode, keys, payloads, kinds, scales, dicts, n_rows, device=False,
                 dup=1, cnt=None, pay_valids=None):
        self.mode = mode  # direct | sorted
        self.keys = keys  # direct: int32 [T] row/lo table; sorted: int64 [B] keys
        self.payloads = payloads  # per column, padded (unique direct: original order)
        self.kinds = kinds
        self.scales = scales
        self.dicts = dicts
        self.n_rows = n_rows
        self.device = device
        self.dup = dup  # max duplicates per key (1 = unique fast paths)
        self.cnt = cnt  # direct expansion mode: int32 [T] per-key match count
        self.shifts: list[int] = []  # multi-key combine shifts (per extra key)
        # per payload column: bool [B] validity plane or None; padding slots
        # are invalid, so an outer join's unmatched gathers decode as NULL
        self.pay_valids = pay_valids if pay_valids is not None else [None] * len(payloads)
        # build-schema field index → position in payloads (None = column was
        # not encodable and not uploaded; only legal for semi/anti filters)
        self.pay_pos: list = list(range(len(payloads)))

    def flat_arrays(self):
        """Device-arg layout: keys [, cnt] , payloads..., payload validity
        planes... (offset contract shared with the lowering closures)."""
        out = [self.keys]
        if self.cnt is not None:
            out.append(self.cnt)
        return out + list(self.payloads) + [v for v in self.pay_valids if v is not None]

    def pay_valid_flat_idx(self) -> list:
        """Per payload: index of its validity plane within flat_arrays()
        (relative to this build's block), or None."""
        out = []
        nxt = (2 if self.cnt is not None else 1) + len(self.payloads)
        for v in self.pay_valids:
            if v is None:
                out.append(None)
            else:
                out.append(nxt)
                nxt += 1
        return out

    def shape_key(self):
        return (
            self.mode, len(self.keys), tuple(self.shifts), self.dup,
            self.cnt is not None, self.padded_rows(),
            tuple(str(p.dtype) for p in self.payloads),
            tuple(v is not None for v in self.pay_valids),
            tuple(self.pay_pos),
            tuple(_pow2(len(d)) if d else 0 for d in self.dicts),
        )

    @property
    def layout(self) -> str:
        """`direct` (a key -> row table), `expansion` (key-sorted payloads
        behind lo / cnt tables: keys with several rows) or `sorted` (binary
        search over the sorted keys)."""
        return "expansion" if self.cnt is not None else self.mode

    def padded_rows(self) -> int:
        """Padded payload length B — a compiled fn clips expansion-lane
        indices against it, so it must be part of the compile-cache key."""
        return self.payloads[0].shape[0] if self.payloads else _pow2(max(self.n_rows, 1))


class DeviceTable:
    """All partitions of one scan, device-resident as [P, N] stacks."""

    def __init__(self, kinds, scales, dicts, cols, mask, part_rows, nbytes,
                 valids=None):
        self.kinds = kinds  # per column
        self.scales = scales
        self.dicts = dicts  # unified (global) dictionaries
        self.cols = cols  # list of jnp [P, N]
        self.mask = mask  # jnp bool [P, N]
        self.part_rows = part_rows
        self.nbytes = nbytes
        # per column: jnp bool [P, N] validity plane, or None (no nulls);
        # value slots under an invalid plane hold type-default fills
        self.valids = valids if valids is not None else [None] * len(cols)

    @property
    def shape(self):
        return self.mask.shape

    def flat_cols(self):
        """Device-arg layout: data columns, then the validity planes of the
        nullable columns (offset contract shared with _mk_col_reader)."""
        return list(self.cols) + [v for v in self.valids if v is not None]

    def valid_flat_idx(self) -> list:
        """Per column: index of its validity plane in flat_cols(), or None."""
        out = []
        nxt = len(self.cols)
        for v in self.valids:
            if v is None:
                out.append(None)
            else:
                out.append(nxt)
                nxt += 1
        return out


class DeviceTableCache:
    def __init__(self):
        import collections

        self._cache: "collections.OrderedDict[tuple, DeviceTable]" = collections.OrderedDict()
        self._lock = threading.Lock()
        self._inflight: dict[tuple, threading.Event] = {}

    def table_key(self, scan, ctx, mesh=None) -> tuple:
        # device_ordinal in the key: an in-process cluster of differently
        # pinned executors must not share tables committed to one chip
        return (self.key_of(scan) + ((mesh.devices.size,) if mesh is not None else ())
                + (ctx.device_ordinal,))

    def get(self, scan, buckets: list[int], ctx, max_bytes: int,
            mesh=None, *, fill_threads: int = 0, chunk_rows: int = 0,
            stats: dict | None = None, on_spec=None,
            spill_pool=None) -> DeviceTable:
        key = self.table_key(scan, ctx, mesh)
        with self._lock:
            hit = self._cache.get(key)
            if hit is not None:
                self._cache.move_to_end(key)
                return hit
            ev = self._inflight.get(key)
            owner = ev is None
            if owner:
                ev = threading.Event()
                self._inflight[key] = ev
        if not owner:
            ev.wait()
            with self._lock:
                hit = self._cache.get(key)
            if hit is None:
                raise Unsupported("peer encode failed")
            return hit
        try:
            with RUN_STATS.span("bt.device.fill") as span:
                # spilled-entry fast path: a previously demoted table re-uploads
                # from its host (or disk) copy instead of re-running the whole
                # read+encode fill — the transparent-on-touch half of the spill
                # contract. on_spec still fires so compile/fill overlap holds.
                restored = spill_pool.pop(key) if spill_pool is not None else None
                if restored is not None:
                    dt = _restore_device_table(restored, mesh)
                    if on_spec is not None:
                        on_spec(dt)
                else:
                    dt = self._load(scan, buckets, ctx, mesh, fill_threads=fill_threads,
                                    chunk_rows=chunk_rows, stats=stats, on_spec=on_spec)
                span.set(bytes=dt.nbytes)
            RUN_STATS.set("fill_s", round(span.seconds, 3), rec=stats)
            RUN_STATS.set("device_bytes", dt.nbytes, rec=stats)
            if getattr(scan, "mem_token", None) is not None:
                # memory-backed fill = ingested delta rows riding a grafted
                # scan (serving/incremental.py) — surfaced so operators can
                # watch delta volume reach the device tier
                RUN_STATS.set("delta_fill_rows",
                              int(sum(int(r) for r in dt.part_rows)), rec=stats)
            with self._lock:
                total = sum(v.nbytes for v in self._cache.values())
                while self._cache and total + dt.nbytes > max_bytes:
                    old_key, old = self._cache.popitem(last=False)
                    total -= old.nbytes
                    if spill_pool is not None:
                        _spill_device_table(spill_pool, old_key, old)
                self._cache[key] = dt
            return dt
        finally:
            with self._lock:
                self._inflight.pop(key, None)
            ev.set()

    def resident_bytes(self, exclude_key: tuple | None = None) -> int:
        """Device bytes held by cached tables other than `exclude_key` —
        the admission planner's `resident_other` (cold residency that
        spill_colds can reclaim)."""
        with self._lock:
            return sum(v.nbytes for k, v in self._cache.items() if k != exclude_key)

    def ensure_headroom(self, max_bytes: int, keep_key: tuple | None,
                        spill_pool=None) -> int:
        """Demote cold entries (all but `keep_key`) until residency fits
        `max_bytes`. Returns bytes freed. The spill_colds admission rung."""
        freed = 0
        victims = []
        with self._lock:
            total = sum(v.nbytes for v in self._cache.values())
            for k in list(self._cache):
                if total <= max_bytes:
                    break
                if k == keep_key:
                    continue
                old = self._cache.pop(k)
                total -= old.nbytes
                freed += old.nbytes
                victims.append((k, old))
        for k, old in victims:
            if spill_pool is not None:
                _spill_device_table(spill_pool, k, old)
        return freed

    def spill_all(self, spill_pool=None) -> None:
        """Demote EVERY resident table — the runtime RESOURCE_EXHAUSTED
        rung frees the whole device before the one retry."""
        with self._lock:
            items = list(self._cache.items())
            self._cache.clear()
        for k, old in items:
            if spill_pool is not None:
                _spill_device_table(spill_pool, k, old)

    def clear(self) -> None:
        with self._lock:
            self._cache.clear()

    def key_of(self, scan) -> tuple:
        if isinstance(scan, ParquetScanExec):
            files = tuple(
                tuple((f["file"], tuple(f.get("row_groups") or ())) for f in p.get("files", []))
                for p in scan.partitions
            )
            return (files, tuple(scan.projection))
        token = getattr(scan, "mem_token", None)
        if token is not None:
            return ("mem", token)  # monotonic: never aliases like id() does
        return ("obj", id(scan), id(type(scan)))

    def _load(self, scan, buckets: list[int], ctx, mesh=None, *,
              fill_threads: int = 0, chunk_rows: int = 0,
              stats: dict | None = None, on_spec=None) -> DeviceTable:
        """Read, encode and upload the whole scan as [P, N] stacks.

        Pipelined cold path: columns encode on a small host pool while the
        caller thread streams each finished stack to the device in column
        order, so encode of column k+1 overlaps the upload of column k.
        In-flight encoded stacks are bounded (lazy submission window) and
        every host intermediate — the partition tables, the concatenated
        arrow table, each column's flat encoding and its [P, N] stack — is
        released the moment it has been consumed, instead of all living
        until the end of the fill (~3× table bytes previously).

        `fill_threads` 0 = auto, 1 = strict serial (encode→upload one column
        at a time, the legacy order). `on_spec(spec_table)` fires on the
        encode worker that completes the LAST column: `spec_table` is a
        DeviceTable of ShapeDtypeStructs carrying everything the compile
        key needs (kinds, dtypes, dict sizes, P, N) while uploads are still
        streaming — the compile/fill overlap hook."""
        import concurrent.futures as fut

        jax = ensure_jax()
        if isinstance(scan, ParquetScanExec):
            raw = ParquetScanExec(scan.df_schema, scan.partitions, scan.projection, [], scan.table_name)
        else:
            raw = scan
        P = raw.output_partition_count()

        def read(p):
            return _concat([b for b in raw.execute(p, ctx) if b.num_rows], raw.schema())

        with fut.ThreadPoolExecutor(max_workers=min(P, 8)) as pool:
            tables = list(pool.map(read, range(P)))
        part_rows = [t.num_rows for t in tables]
        full = pa.concat_tables(tables)
        del tables  # concat is zero-copy; the chunks live on via `full`
        N = next_bucket(max(max(part_rows), 1), buckets)

        # multi-chip: shard the partition axis across the mesh — pad P to a
        # multiple of the device count with empty (all-masked) partitions
        if mesh is not None:
            nd = mesh.devices.size
            while len(part_rows) % nd:
                part_rows.append(0)
        P = len(part_rows)

        names = list(full.column_names)
        n_cols = len(names)
        # split the table into per-column references so each column's arrow
        # buffers can be dropped individually once encoded
        col_refs: list = [full.column(name) for name in names]
        del full

        if mesh is not None:
            from jax.sharding import PartitionSpec

            spec = PartitionSpec("part", None)
        else:
            spec = None

        threads = int(fill_threads)
        if threads <= 0:
            threads = min(8, max(2, (os.cpu_count() or 4) // 2), max(n_cols, 1))
        pipelined = threads > 1 and n_cols > 1

        kinds: list = [None] * n_cols
        scales: list = [0] * n_cols
        dicts: list = [None] * n_cols
        dtypes: list = [None] * n_cols
        has_valid = [False] * n_cols
        cols: list = [None] * n_cols
        valids: list = [None] * n_cols
        nbytes = 0
        meta_lock = threading.Lock()
        left = [n_cols]
        t_enc0 = time.perf_counter()

        def spec_table() -> DeviceTable:
            sds = jax.ShapeDtypeStruct
            scols = [sds((P, N), dtypes[i]) for i in range(n_cols)]
            svalids = [sds((P, N), np.bool_) if has_valid[i] else None
                       for i in range(n_cols)]
            return DeviceTable(list(kinds), list(scales), list(dicts), scols,
                               sds((P, N), np.bool_), list(part_rows), 0, svalids)

        def encode_one(i: int):
            dc = encode_stacked(col_refs[i], part_rows, N)
            col_refs[i] = None  # release the arrow buffers
            if dc is None:
                raise Unsupported(f"unencodable column {names[i]}")
            with meta_lock:
                kinds[i] = dc.kind
                scales[i] = dc.scale
                dicts[i] = dc.dictionary
                dtypes[i] = dc.data.dtype
                has_valid[i] = dc.valid is not None
                left[0] -= 1
                done = left[0] == 0
            if done:
                # the compile key (shapes, dtypes, kinds, dict sizes) is now
                # fully determined even though uploads are still streaming
                RUN_STATS.set("encode_s", round(time.perf_counter() - t_enc0, 3), rec=stats)
                if on_spec is not None:
                    on_spec(spec_table())
            return dc

        t_up = 0.0

        def upload(i: int, dc) -> None:
            nonlocal nbytes, t_up
            t0u = time.perf_counter()
            cols[i] = _put_chunked(mesh, dc.data, spec, chunk_rows)
            nbytes += dc.data.nbytes
            if dc.valid is not None:
                valids[i] = _put_chunked(mesh, dc.valid, spec, chunk_rows)
                nbytes += dc.valid.nbytes
            t_up += time.perf_counter() - t0u

        if pipelined:
            # lazy submission window: at most (threads + 2) encoded stacks
            # alive at once — double-buffering generalized, and the host-RSS
            # bound that replaces "hold every stack until the upload loop"
            window = threads + 2
            with fut.ThreadPoolExecutor(
                max_workers=threads, thread_name_prefix="tpu-fill"
            ) as pool:
                pending: dict[int, fut.Future] = {}
                nxt = 0
                for i in range(n_cols):
                    while nxt < n_cols and nxt < i + window:
                        pending[nxt] = pool.submit(encode_one, nxt)
                        nxt += 1
                    try:
                        dc = pending.pop(i).result()
                    except BaseException:
                        for f in pending.values():
                            f.cancel()
                        raise
                    upload(i, dc)
                    del dc  # host stack freed; the device copy is in flight
        else:
            for i in range(n_cols):
                upload(i, encode_one(i))

        mask_np = np.zeros((P, N), dtype=bool)
        for p, r in enumerate(part_rows):
            mask_np[p, :r] = True
        mask = _put(mesh, mask_np, spec)
        nbytes += mask_np.nbytes

        # drain the async transfers before publishing: fill_s must mean
        # "table resident", not "last copy enqueued"
        t0u = time.perf_counter()
        jax.block_until_ready([c for c in cols if c is not None]
                              + [v for v in valids if v is not None] + [mask])
        t_up += time.perf_counter() - t0u
        RUN_STATS.set("upload_s", round(t_up, 3), rec=stats)
        return DeviceTable(kinds, scales, dicts, cols, mask, part_rows, nbytes, valids)


def _record_spill_stats(rec: dict, spill_pool) -> None:
    """Mirror the host spill pool's cumulative counters into the run record
    (the RUN_STATS → heartbeat → /api/executors gauge path)."""
    if spill_pool is None:
        return
    st = spill_pool.stats()
    RUN_STATS.set("hbm_spill_bytes", st["spill_bytes"], rec=rec)
    RUN_STATS.set("hbm_spill_events", st["spill_events"], rec=rec)
    RUN_STATS.set("hbm_reupload_events", st["reupload_events"], rec=rec)
    RUN_STATS.set("hbm_oom_retries", hbm.oom_retry_count(), rec=rec)


def _spill_device_table(pool, key: tuple, dt: DeviceTable) -> None:
    """Demote one cached DeviceTable to the host spill pool: fetch every
    device plane back to numpy and hand the flat list (cols, mask, valids —
    None slots preserved) plus the encode metadata to the pool. The pool
    owns tiering (host buffers vs tmp+rename disk files)."""
    jax = ensure_jax()
    flat = ([np.asarray(jax.device_get(c)) for c in dt.cols]
            + [np.asarray(jax.device_get(dt.mask))]
            + [None if v is None else np.asarray(jax.device_get(v))
               for v in dt.valids])
    meta = (list(dt.kinds), list(dt.scales), list(dt.dicts),
            list(dt.part_rows), int(dt.nbytes))
    pool.put(key, meta, flat, int(dt.nbytes))


def _restore_device_table(restored, mesh) -> DeviceTable:
    """Re-upload a spilled table: the inverse of _spill_device_table, using
    the same placement chokepoint (_put) as the cold fill."""
    meta, flat = restored
    kinds, scales, dicts, part_rows, nbytes = meta
    n = len(kinds)
    if mesh is not None:
        from jax.sharding import PartitionSpec

        spec = PartitionSpec("part", None)
    else:
        spec = None
    cols = [_put(mesh, a, spec) for a in flat[:n]]
    mask = _put(mesh, flat[n], spec)
    valids = [None if a is None else _put(mesh, a, spec) for a in flat[n + 1:]]
    return DeviceTable(kinds, scales, dicts, cols, mask, part_rows, nbytes, valids)


DEVICE_CACHE = DeviceTableCache()


def clear_device_caches() -> None:
    """Release every module-level device cache: resident tables, compiled
    entries, string LUTs, and join build tables. Frees HBM (or host RAM
    under CPU-jax) between unrelated workloads; caches refill on demand.

    When this process is attached to a device daemon, the clear is also
    forwarded there: the state an attached executor actually uses is
    daemon-resident, so a purely local clear would free nothing but this
    process's cold twins while the daemon keeps serving from its caches.
    The forwarding is best-effort (a dead daemon has nothing resident)
    and a no-op inside the daemon itself."""
    DEVICE_CACHE.clear()
    _COMPILE_CACHE.clear()
    _LUT_CACHE.clear()
    _BUILD_CACHE.clear()
    hbm.SPILL_POOL.clear()
    from ballista_tpu.ops.tpu import final_stage

    final_stage.clear_compile_cache()
    from ballista_tpu.device_daemon import client as daemon_client

    daemon_client.clear_attached_caches()


class TpuStageExec(ExecutionPlan):
    own_span = True  # `bt.stage.dispatch` and the spans inside it

    def __init__(self, partial_agg: HashAggregateExec, ops: list, scan: ExecutionPlan,
                 config: BallistaConfig):
        super().__init__(partial_agg.df_schema)
        self.partial_agg = partial_agg
        self.ops = ops  # dataflow-ordered FilterExec/ProjectionExec nodes
        self.scan = scan
        self.config = config
        self.min_rows = int(config.get(TPU_MIN_ROWS))
        self.buckets = config.shape_buckets()
        self.fallback_count = 0
        self.tpu_count = 0
        # device-side shuffle routing: (output-schema key indices, K) set by
        # the engine when the parent shuffle writer hash-partitions on group
        # columns; the sorted path then emits a __pid column
        self.emit_pid: tuple[list[int], int] | None = None
        self.pid_emitted = 0
        self._results: dict[int, list[pa.RecordBatch]] | None = None
        self._results_lock = threading.Lock()
        # partitions served since the last (re-)dispatch: once every resident
        # result has been read at least once, the decoded host batches are
        # evicted instead of staying pinned for the stage's lifetime (a later
        # re-read just costs one more hot re-dispatch)
        self._served_since_dispatch: set[int] = set()
        self._device_ok = False
        # structural fingerprint: identical stages across queries share XLA
        # compilations (plan objects are rebuilt per query, ids are not).
        # Join ops must contribute their FULL build subtree: node_str()
        # alone prints only keys/type, so two joins against differently
        # FILTERED builds (q39's d_moy=1 vs d_moy=2 date_dim sides) would
        # collide in the build/LUT caches and reuse the wrong build table.
        def op_fp(op) -> str:
            from ballista_tpu.plan.physical import HashJoinExec

            if isinstance(op, HashJoinExec):
                return op.node_str() + "«" + op.left.display() + "»"
            return op.node_str()

        self.fingerprint = "|".join(
            [partial_agg.node_str()]
            + [op_fp(op) for op in ops]
            + [scan.node_str(), repr(scan.df_schema)]
        )

    def children(self) -> list[ExecutionPlan]:
        return [self.scan]

    def with_children(self, c):
        return TpuStageExec(self.partial_agg, self.ops, c[0], self.config)

    def output_partition_count(self) -> int:
        return self.scan.output_partition_count()

    def node_str(self) -> str:
        # live counters surface in EXPLAIN ANALYZE / stage metrics so
        # operators can SEE whether the device path ran or fell back
        extra = ""
        if self.tpu_count or self.fallback_count:
            extra = f" device_runs={self.tpu_count} cpu_fallbacks={self.fallback_count}"
        return f"TpuStageExec: [{self.partial_agg.node_str()}] ops={len(self.ops)}{extra}"

    def execute(self, partition: int, ctx: TaskContext) -> Iterator[pa.RecordBatch]:
        return self._timed(iter(self._run(partition, ctx)))

    # ------------------------------------------------------------------

    def _run(self, partition: int, ctx: TaskContext) -> list[pa.RecordBatch]:
        with self._results_lock:
            if self._results is None:
                try:
                    self._results = self._dispatch_all(ctx)
                    self.tpu_count += 1
                    self._device_ok = True
                except Unsupported as e:
                    log.info("tpu fallback (%s): %s", e, self.partial_agg.node_str())
                    STAGE_OUTCOMES.note_fallback("partial", e)
                    self._results = {}
                except Exception as e:  # noqa: BLE001
                    # the device path must never fail a query the CPU engine
                    # can run: adaptive per-subtree dispatch, loudly — and
                    # counted, so a run that must prove the device ran can
                    # tell (STAGE_OUTCOMES "error")
                    log.warning(
                        "tpu stage raised; falling back to cpu for %s",
                        self.partial_agg.node_str(), exc_info=True,
                    )
                    STAGE_OUTCOMES.note_fallback("partial", e)
                    self._results = {}
            if partition not in self._results and self._device_ok:
                # a consumer re-executed a partition whose device result was
                # already popped (e.g. a parent's device attempt that later
                # fell back): the device table cache and compiled entry are
                # hot, so re-dispatching costs ~the exec time — never fall
                # through to a full host re-scan of the subtree
                try:
                    self._results.update(self._dispatch_all(ctx))
                    self.tpu_count += 1
                    self._served_since_dispatch = set()
                    # serve WITHOUT popping: a consumer that re-reads one
                    # partition tends to re-read them all — one re-dispatch
                    # must cover all K re-reads, not K re-dispatches
                    if partition in self._results:
                        out = list(self._results[partition])
                        self._note_served_locked(partition)
                        return out
                except Exception as e:  # noqa: BLE001
                    log.warning("tpu stage re-run failed; cpu fallback for %s",
                                self.partial_agg.node_str(), exc_info=True)
                    STAGE_OUTCOMES.note_fallback("partial", e)
                    self._device_ok = False
            if partition in self._results:
                out = self._results.pop(partition)
                self._note_served_locked(partition)
                return out
        return self._fallback(partition, ctx)

    def _note_served_locked(self, partition: int) -> None:
        """Bound re-run retention (call under _results_lock): when every
        still-resident result has been served at least once since the last
        dispatch, drop them all — they only exist for re-read convenience."""
        self._served_since_dispatch.add(partition)
        if self._results and set(self._results) <= self._served_since_dispatch:
            self._results = {}

    def _dispatch_all(self, ctx: TaskContext) -> dict[int, list[pa.RecordBatch]]:
        """Route one whole-stage dispatch: warm device-runtime daemon first
        when the session opted in (docs/device_daemon.md), else the
        in-process engine pinned to the task's bound device."""
        from ballista_tpu.ops.tpu.runtime import device_scope

        out = self._daemon_run_all(ctx)
        if out is None:
            # per-chip pinning: commit every upload/dispatch in this call
            # tree to the executor's bound device
            with device_scope(ctx.device_ordinal):
                out = self._tpu_run_all(ctx)
        STAGE_OUTCOMES.note("partial", "device")
        return out

    def _daemon_run_all(self, ctx: TaskContext) -> dict[int, list[pa.RecordBatch]] | None:
        """Ship this stage to the device daemon: the RAW rebuilt subtree
        (the same chain _fallback re-executes — this wrapper has no serde
        encoding, that chain round-trips) goes over the socket and the
        daemon runs it through the same maybe_compile_tpu entry, so an
        attached result is byte-identical to an in-process one by
        construction. The whole failure domain — derived execute deadline,
        crash detection, respawn-and-retry, poison quarantine — lives in
        daemon_route.run_via_daemon; None means 'run locally' with the
        reason in RUN_STATS daemon_mode/daemon_mode_reason."""
        from ballista_tpu.ops.tpu import daemon_route

        return daemon_route.run_via_daemon(
            self.config,
            plan_builder=lambda: self.partial_agg.with_children(
                [self._raw_chain()]),
            partitions=list(range(self.scan.output_partition_count())),
            tag=daemon_route.stage_tag("stage", self.fingerprint),
            fingerprint=self.fingerprint,
            emit_pid=self.emit_pid,
            est_bytes=int(getattr(self, "hbm_observed_input_bytes", 0) or 0))

    def _raw_chain(self) -> ExecutionPlan:
        """The original pre-aggregation subtree this wrapper replaced,
        rebuilt from its pieces: what _fallback re-executes on the host and
        what the daemon client serializes over the socket."""
        from ballista_tpu.plan.physical import HashJoinExec

        node: ExecutionPlan = self.scan
        for op in self.ops:
            if isinstance(op, HashJoinExec):
                node = op.with_children([op.left, node])
            else:
                node = op.with_children([node])
        return node

    def _fallback(self, partition: int, ctx: TaskContext) -> list[pa.RecordBatch]:
        """Re-run the original CPU subtree (scan filters applied on host).

        The fallback contract for a slice. A task carries a slice of the
        stage's partitions (all of them with one executor) and runs them on
        this ONE instance, whose first `_run` decides device-or-CPU for the
        whole slice: a dispatch that raises (Unsupported, BelowRowFloor, a
        second OOM, anything else) leaves `_results` empty and `_device_ok`
        false, and every partition of the slice comes here, one after
        another. With `emit_pid` set the layout is the same whichever
        engine produced it: partition 0 carries EVERY group of the stage
        and the other partitions are empty — so the slices of two
        executors, one on the device and one demoted, neither lose a group
        nor count one twice."""
        from ballista_tpu.plan.physical import CoalescePartitionsExec

        self.fallback_count += 1
        node = self._raw_chain()
        if self.emit_pid is not None:
            # the device path ships every group through map partition 0
            # (__pid routing) and empties the others; a classic
            # partition-p partial here would double-count what a device
            # slice on another executor already wrote through partition 0 —
            # or, demoting the slice that holds partition 0, drop every
            # other partition's groups. Keep the shape: partition 0
            # aggregates the WHOLE input; the shuffle writer's host hash is
            # the device routing's bit-exact twin, so each group still
            # meets its partials in the same reduce partition.
            if partition != 0:
                return [_empty_batch(self.schema())]
            node = CoalescePartitionsExec(node)
        agg = self.partial_agg.with_children([node])
        with RUN_STATS.span("bt.stage.fallback", family="partial"):
            return [b for b in agg.execute(partition, ctx)]

    # ------------------------------------------------------------------

    def _prepare_build(self, join, jidx: int, ctx: TaskContext, table_key,
                       mesh=None, grace: tuple[int, int] | None = None,
                       rec: dict | None = None) -> BuildTable:
        """A join's build side for device probing, from `_BUILD_CACHE` or
        built (`_encode_build`) under a `bt.join.build` span — a miss alone
        opens one. `rec` (the stage's run record; helper threads hand it in)
        counts the hits in `build_hits` and sums the misses' seconds and rows
        in `build_s` and `build_rows`.

        `grace=(bucket, n_buckets)`: keep only the build rows whose combined
        key falls in the given secondary-hash sub-bucket (the grace-split
        path). Sub-builds carry their bucket in the cache key — a sub-build
        and the whole build must never alias."""
        jax = ensure_jax()
        cache_key = (table_key, self.fingerprint, jidx, mesh.devices.size if mesh else 0,
                     ctx.device_ordinal, grace)
        hit = _BUILD_CACHE.get(cache_key)
        if hit is not None:
            if rec is not None:
                with _BUILD_STATS_LOCK:
                    rec["build_hits"] = rec.get("build_hits", 0) + 1
            return hit
        with RUN_STATS.span("bt.join.build", join_type=join.join_type) as span:
            bt = self._encode_build(join, ctx, mesh, grace)
            arrays = bt.flat_arrays()
            # resident, not enqueued: the span holds the upload, as fill_s does
            jax.block_until_ready(arrays)
            span.set(rows=bt.n_rows, dup=bt.dup, layout=bt.layout,
                     bytes=sum(int(a.nbytes) for a in arrays))
        if rec is not None:
            with _BUILD_STATS_LOCK:
                rec["build_s"] = round(rec.get("build_s", 0.0) + span.seconds, 6)
                rec["build_rows"] = rec.get("build_rows", 0) + bt.n_rows
        _BUILD_CACHE[cache_key] = bt
        return bt

    def _encode_build(self, join, ctx: TaskContext, mesh,
                      grace: tuple[int, int] | None) -> BuildTable:
        """Collect + encode + sort a join's build side and upload it: the
        work of a `_BUILD_CACHE` miss."""
        import numpy as np

        from ballista_tpu.ops.phys_expr import bind_expr, evaluate_to_array
        from ballista_tpu.ops.tpu.columnar import encode_column

        batches = []
        for p in range(join.left.output_partition_count()):
            batches.extend(b for b in join.left.execute(p, ctx) if b.num_rows)
        tbl = _concat(batches, join.left.schema()).combine_chunks()
        if tbl.num_rows:
            # a build row whose key is NULL can never match any probe row
            # (inner/semi/anti/outer alike): drop it before encoding
            import pyarrow.compute as _pc

            keep = None
            for l_expr, _ in join.on:
                arr = evaluate_to_array(
                    bind_expr(l_expr, join.left.df_schema), tbl.to_batches()[0]
                )
                if arr.null_count:
                    va = arr.is_valid()
                    keep = va if keep is None else _pc.and_(keep, va)
            if keep is not None:
                tbl = tbl.filter(keep).combine_chunks()
        if tbl.num_rows == 0:
            raise Unsupported("empty build side (let CPU/AQE handle it)")
        batch = tbl.to_batches()[0]

        # combined int64 key, verified unique + range-guarded; each extra
        # key gets the smallest shift covering its build-side range (keeps
        # combined keys dense enough for direct addressing)
        key_np = None
        shifts: list[int] = []
        for l_expr, _ in join.on:
            arr = evaluate_to_array(bind_expr(l_expr, join.left.df_schema), batch)
            if arr.null_count:
                raise Unsupported("NULL build keys survived the pre-filter")
            import pyarrow as _pa

            t = arr.type
            if _pa.types.is_date(t):
                vals = arr.cast(_pa.int32()).cast(_pa.int64()).to_numpy(zero_copy_only=False)
            elif _pa.types.is_integer(t):
                vals = arr.cast(_pa.int64(), safe=False).to_numpy(zero_copy_only=False)
            else:
                raise Unsupported(f"non-integer join key {t}")
            vals = vals.astype(np.int64)
            if key_np is None:
                key_np = vals
            else:
                if (vals < 0).any():
                    raise Unsupported("negative secondary join key")
                shift = max(1, int(vals.max()).bit_length())
                if (key_np < 0).any() or (int(key_np.max()) >> (62 - shift)) > 0:
                    raise Unsupported("primary join key out of combine range")
                key_np = (key_np << shift) | vals
                shifts.append(shift)
        if grace is not None:
            bucket, n_buckets = grace
            sel = hbm.grace_bucket_of(key_np, n_buckets) == bucket
            if not sel.any():
                raise Unsupported(
                    f"empty grace sub-bucket {bucket}/{n_buckets}")
            key_np = key_np[sel]
            tbl = tbl.filter(pa.array(sel)).combine_chunks()
            batch = tbl.to_batches()[0]
        uniq, counts = np.unique(key_np, return_counts=True)
        dup = int(counts.max())
        membership_only = join.join_type in ("right_semi", "right_anti") and join.filter is None
        cba = _mult_shape_check(self.partial_agg, self.ops, join)
        # mirror _compile's activation exactly (counted build columns must be
        # non-null): a looser exemption here would pay the full build
        # collect/encode/upload only to fall back at compile time anyway
        mult_shaped = cba is not None and all(
            tbl.column(fi).null_count == 0 for fi in cba.values()
        )
        if dup > MAX_JOIN_DUP and not membership_only and not mult_shaped:
            # filterless semi/anti probes only test membership, and
            # aggregate-through-join stages consume match COUNTS — neither
            # unrolls lanes, so any dup is fine there; inner/outer gathers
            # and semi/anti FILTERS unroll dup lanes and are budgeted
            raise Unsupported(f"build key multiplicity {dup} > {MAX_JOIN_DUP}")

        max_key = int(key_np.max())
        min_key = int(key_np.min())
        direct = min_key >= 0 and max_key + 1 <= DIRECT_TABLE_MAX
        cnt_dev = None
        if dup == 1 and direct:
            T = _pow2(max_key + 1)
            table = np.full(T, -1, dtype=np.int32)
            table[key_np] = np.arange(len(key_np), dtype=np.int32)
            keys_dev = table
            order = np.arange(len(key_np))
            B = _pow2(len(key_np))
            mode = "direct"
        elif direct:
            # expansion layout: payloads key-sorted; lo/cnt tables give each
            # probe its first matching row and its match count
            order = np.argsort(key_np, kind="stable")
            sorted_keys = key_np[order]
            B = _pow2(len(sorted_keys))
            T = _pow2(max_key + 1)
            lo_table = np.zeros(T, dtype=np.int32)
            cnt_table = np.zeros(T, dtype=np.int32)
            firsts = np.searchsorted(sorted_keys, uniq)
            lo_table[uniq] = firsts.astype(np.int32)
            cnt_table[uniq] = counts.astype(np.int32)
            keys_dev = lo_table
            cnt_dev = cnt_table
            mode = "direct"
        else:
            order = np.argsort(key_np, kind="stable")
            sorted_keys = key_np[order]
            B = _pow2(len(sorted_keys))
            keys_dev = np.full(B, np.iinfo(np.int64).max, dtype=np.int64)
            keys_dev[: len(sorted_keys)] = sorted_keys
            mode = "sorted"

        kinds, scales, dicts, payloads, pay_valids, pay_pos = [], [], [], [], [], []
        if membership_only:
            # membership-only joins never gather build columns: skip payload
            # encode/upload entirely (an unencodable non-key column must not
            # knock a semi join off the device)
            pass
        else:
            # semi/anti WITH a join filter only gather the columns the
            # filter touches: tolerate unencodable columns with a None
            # payload slot (lowering raises only if the filter uses one)
            tolerate = join.join_type in ("right_semi", "right_anti")
            for name in batch.schema.names:
                dc = encode_column(batch.column(batch.schema.get_field_index(name)))
                if dc is None:
                    if not tolerate:
                        raise Unsupported(f"unencodable build column {name}")
                    kinds.append("?")
                    scales.append(0)
                    dicts.append(None)
                    pay_pos.append(None)
                    continue
                kinds.append(dc.kind)
                scales.append(dc.scale)
                dicts.append(dc.dictionary)
                padded = np.zeros(B, dtype=dc.data.dtype)
                padded[: len(order)] = dc.data[order]
                pay_pos.append(len(payloads))
                payloads.append(padded)
                if dc.valid is None:
                    pay_valids.append(None)
                else:
                    pv = np.zeros(B, dtype=bool)  # padding slots stay invalid
                    pv[: len(order)] = dc.valid[order]
                    pay_valids.append(pv)

        bt = BuildTable(
            mode, _put(mesh, keys_dev), [_put(mesh, p) for p in payloads],
            kinds, scales, dicts, len(order), device=True, dup=dup,
            cnt=None if cnt_dev is None else _put(mesh, cnt_dev),
            pay_valids=[None if v is None else _put(mesh, v) for v in pay_valids],
        )
        bt.pay_pos = pay_pos
        bt.shifts = shifts
        return bt

    def _tpu_run_all(self, ctx: TaskContext) -> dict[int, list[pa.RecordBatch]]:
        tag = f"stage_{zlib.crc32(self.fingerprint.encode()):08x}"
        # one span per dispatch, never merged: the scheduler hands a stage
        # like this out as one task per executor (pop_next_task), so an
        # executor dispatches it once; RunStats keeps the last record of a
        # tag, which on several executors is one of theirs
        with RUN_STATS.run(tag) as rec, \
                RUN_STATS.span("bt.stage.dispatch", family="partial"):
            try:
                return self._tpu_run_all_inner(ctx, rec)
            except Unsupported:
                raise
            except Exception as e:  # noqa: BLE001 — classified below
                if not hbm.is_resource_exhausted(e):
                    raise
                # runtime OOM rung: the estimate said fit and the device
                # disagreed. Free everything (spilling residents to host so
                # their fills aren't lost), hint the planner to pre-plan
                # grace for this fingerprint, and retry ONCE; a second OOM
                # demotes to the CPU engine via the Unsupported ladder.
                log.warning("device RESOURCE_EXHAUSTED; spilling + retrying "
                            "stage once: %s", e)
                spill_pool = (hbm.SPILL_POOL
                              if bool(self.config.get(TPU_HBM_SPILL_ENABLED))
                              else None)
                DEVICE_CACHE.spill_all(spill_pool)
                _LUT_CACHE.clear()
                _BUILD_CACHE.clear()
                hbm.note_oom(self.fingerprint)
                rec["hbm_oom_retries"] = hbm.oom_retry_count()
                try:
                    return self._tpu_run_all_inner(ctx, rec)
                except Exception as e2:  # noqa: BLE001
                    if hbm.is_resource_exhausted(e2):
                        raise Unsupported(
                            f"device OOM persisted after spill+retry: {e2}"
                        ) from e2
                    raise

    def _compile_key(self, dt: DeviceTable, builds: list[BuildTable]) -> tuple:
        """The compile-cache key. Derivable from a spec DeviceTable (the
        encode metadata alone), which is what makes compile/fill overlap
        possible: tracing starts before the uploads finish."""
        P, N = dt.shape
        emit_key = (tuple(self.emit_pid[0]), self.emit_pid[1]) if self.emit_pid else None
        return (
            self.fingerprint, P, N, tuple(zip(dt.kinds, dt.scales)),
            tuple(str(c.dtype) for c in dt.cols),
            tuple(v is not None for v in dt.valids),
            tuple(_pow2(len(d)) if d else 0 for d in dt.dicts),
            tuple(b.shape_key() for b in builds), emit_key,
        )

    def _compile_locked(self, dt: DeviceTable, builds: list[BuildTable],
                        rec: dict | None):
        """Look up or create the compiled entry. `dt` may be a spec table
        (ShapeDtypeStruct columns): _compile only consults shapes, dtypes,
        kinds and dictionaries. Returns (entry, fresh, lowered) — `lowered`
        (the jax Lowered, pre-backend-compile) only for fresh entries."""
        key = self._compile_key(dt, builds)
        P, N = dt.shape
        kinds = list(zip(dt.kinds, dt.scales))
        with _COMPILE_LOCK:
            cached = _COMPILE_CACHE.get(key)
            if cached is not None:
                return cached, False, None
            with RUN_STATS.span("bt.compile.trace") as span:
                fn, lowering, meta, lowered = self._compile(
                    dt, kinds, dt.dicts, P, N, builds)
            RUN_STATS.set("trace_s", round(span.seconds, 3), rec=rec)
            # the dispatched flag lives with the entry: the FIRST call of a
            # jitted fn runs the backend compile, so the first dispatcher
            # attributes that wall time to xla_compile_s, not exec_s
            cached = (fn, lowering, meta, {"dispatched": False})
            _COMPILE_CACHE[key] = cached
            return cached, True, lowered

    def _tpu_run_all_inner(self, ctx: TaskContext,
                           rec: dict) -> dict[int, list[pa.RecordBatch]]:
        """One dispatch + one fetch for every partition of this stage."""
        from ballista_tpu.plan.physical import HashJoinExec
        from ballista_tpu.ops.tpu.runtime import device_scope

        jax = ensure_jax()
        dispatch_span = RUN_STATS.current_span()  # `bt.stage.dispatch`

        max_bytes = int(self.config.get(TPU_MAX_DEVICE_BYTES))
        budget = hbm.resolve_hbm_budget(self.config)
        if budget > 0:
            # the cache cap never exceeds the admission budget: a chaos- or
            # knob-shrunk budget drives real evictions (and thus spills)
            max_bytes = min(max_bytes, budget)
        spill_pool = None
        if bool(self.config.get(TPU_HBM_SPILL_ENABLED)):
            import tempfile

            from ballista_tpu.executor import disk as _disk

            spill_pool = hbm.SPILL_POOL
            sdir = str(self.config.get(TPU_HBM_SPILL_DIR) or "")
            cfg = self.config
            spill_pool.configure(
                int(self.config.get(TPU_HBM_SPILL_HOST_BYTES)), sdir,
                # low-watermark shed: under disk pressure demotions stay in
                # the host tier (docs/lifecycle.md#watermark-ladder)
                spill_gate=lambda: _disk.spill_allowed(
                    cfg, sdir or tempfile.gettempdir()))
        mesh = _stage_mesh(self.config)
        cc0 = runtime.compile_cache_stats()
        overlap = bool(self.config.get(TPU_COMPILE_OVERLAP))
        fill_threads = int(self.config.get(TPU_FILL_THREADS))
        chunk_rows = int(self.config.get(TPU_FILL_CHUNK_ROWS))

        table_key = DEVICE_CACHE.key_of(self.scan)
        join_ops = [o for o in self.ops if isinstance(o, HashJoinExec)]
        cached = None
        holder: dict = {}

        if overlap:
            # Cold-path pipeline: build sides collect/encode concurrently
            # with the probe fill (independent subtrees), and the compile
            # worker starts tracing the moment the fill's encode phase
            # determines the compile key — all before the uploads drain.
            import concurrent.futures as cf

            spec_ev = threading.Event()

            def on_spec(sdt: DeviceTable) -> None:
                holder.setdefault("spec", sdt)
                spec_ev.set()

            pool = cf.ThreadPoolExecutor(max_workers=1 + len(join_ops),
                                         thread_name_prefix="tpu-cold")
            try:
                def prep(op, jidx):
                    # jax.default_device is thread-local config state: every
                    # helper thread re-enters the executor's chip pin
                    # the helper threads' spans hang under this dispatch
                    with device_scope(ctx.device_ordinal), RUN_STATS.attach(dispatch_span):
                        return self._prepare_build(op, jidx, ctx, table_key, mesh, rec=rec)

                build_futs = [pool.submit(prep, op, jidx)
                              for jidx, op in enumerate(join_ops)]

                def compile_ahead():
                    if not spec_ev.wait(timeout=900):
                        return None
                    sdt = holder.get("spec")
                    if sdt is None:
                        return None  # fill failed; main thread raises
                    bts = [f.result() for f in build_futs]
                    t0 = time.perf_counter()
                    with device_scope(ctx.device_ordinal), RUN_STATS.attach(dispatch_span):
                        entry, fresh, lowered = self._compile_locked(
                            sdt, bts, rec)
                        if fresh and lowered is not None and mesh is None \
                                and runtime.compile_cache_dir():
                            # AOT-compile here: backend_compile writes the
                            # binary into the persistent cache, so the main
                            # thread's dispatch-time compile becomes a disk
                            # fetch — the seconds-long XLA phase overlaps
                            # the fill instead of serializing after it
                            try:
                                with RUN_STATS.span("bt.compile.xla", ahead=1) as span:
                                    lowered.compile()
                                holder["xla_s"] = span.seconds
                            except Exception:  # noqa: BLE001 — warm-up only
                                log.debug("background XLA precompile failed",
                                          exc_info=True)
                    holder["compile_t0"] = t0
                    holder["compile_t1"] = time.perf_counter()
                    return entry

                compile_fut = pool.submit(compile_ahead)
                dt = DEVICE_CACHE.get(
                    self.scan, self.buckets, ctx, max_bytes, mesh,
                    fill_threads=fill_threads, chunk_rows=chunk_rows,
                    stats=rec, on_spec=on_spec, spill_pool=spill_pool)
                fill_end = time.perf_counter()
                if not spec_ev.is_set():
                    # device-cache hit: the fill never ran, so the spec never
                    # fired — the resident table IS the spec
                    on_spec(dt)
                if sum(dt.part_rows) < self.min_rows:
                    raise BelowRowFloor(sum(dt.part_rows))
                builds = [f.result() for f in build_futs]
                cached = compile_fut.result()
                c0, c1 = holder.get("compile_t0"), holder.get("compile_t1")
                if cached is not None and c0 is not None:
                    ov = max(0.0, min(c1, fill_end) - c0)
                    if ov > 0:
                        rec["compile_overlap_s"] = round(ov, 6)
            finally:
                spec_ev.set()  # never strand the compile worker
                pool.shutdown(wait=False)
        else:
            dt = DEVICE_CACHE.get(self.scan, self.buckets, ctx, max_bytes, mesh,
                                  fill_threads=fill_threads,
                                  chunk_rows=chunk_rows, stats=rec,
                                  spill_pool=spill_pool)
            if sum(dt.part_rows) < self.min_rows:
                raise BelowRowFloor(sum(dt.part_rows))
            builds = [self._prepare_build(op, jidx, ctx, table_key, mesh, rec=rec)
                      for jidx, op in enumerate(join_ops)]

        # the program is traced before admission (the overlap worker has
        # traced it already): what it holds beside its inputs is priced
        if cached is None:
            cached, _, _ = self._compile_locked(dt, builds, rec)
        fn, lowering, meta, state = cached
        est = fusion.estimate_stage(self.ops, self.partial_agg, dt, builds,
                                    program_bytes=meta.get("program_bytes", 0))
        if meta["mode"] == "sorted":
            rec["sorted_capacity"] = meta["C"]
            rec.update(meta["compact"])
            if dispatch_span is not None:
                dispatch_span.set(sorted_capacity=meta["C"], **meta["compact"])

        # ---- HBM admission: every stage states its memory plan before the
        # dispatch, each demotion with its reason. Splitting
        # is only sound for an INNER join's build: a probe row's whole match
        # set shares its key's sub-bucket, so wrong-bucket runs mask it like
        # any unmatched probe; outer/anti would re-emit it per bucket.
        grace_fanout = int(self.config.get(TPU_HBM_GRACE_BUCKETS))
        grace_depth_cap = int(self.config.get(TPU_HBM_GRACE_DEPTH))
        grace_eligible = (
            not est.has_mult
            and 0 <= est.max_build_jidx < len(join_ops)
            and join_ops[est.max_build_jidx].join_type == "inner"
        )
        my_key = DEVICE_CACHE.table_key(self.scan, ctx, mesh)
        plan = hbm.plan_stage(
            est, budget,
            grace_eligible=grace_eligible,
            grace_fanout=grace_fanout,
            grace_max_depth=grace_depth_cap,
            resident_other=DEVICE_CACHE.resident_bytes(exclude_key=my_key),
            observed_bytes=int(getattr(self, "hbm_observed_input_bytes", 0) or 0),
            force_grace=hbm.consume_oom_hint(self.fingerprint),
        )
        rec["hbm_budget_bytes"] = budget
        rec["hbm_plan"] = plan.decision
        rec["hbm_plan_reason"] = plan.reason
        mp = getattr(ctx, "memory_pool", None)
        if mp is not None and hasattr(mp, "sync_device_reserved"):
            # device vs host split-accounting: the session pool's device
            # ledger mirrors the cache residency; host `pressure()` (the
            # CPU sort-spill budget) never sees HBM bytes
            mp.set_device_capacity(budget)
            mp.sync_device_reserved(DEVICE_CACHE.resident_bytes())
        if plan.decision == hbm.CPU_DEMOTE:
            _record_spill_stats(rec, spill_pool)
            raise Unsupported(f"hbm plan: {plan.reason}")
        if plan.decision == hbm.SPILL_COLDS:
            DEVICE_CACHE.ensure_headroom(
                max(budget - plan.working_set, 0), my_key, spill_pool)
        if plan.decision == hbm.GRACE_SPLIT:
            try:
                return self._grace_run(ctx, rec, dt, join_ops, builds, plan,
                                       grace_fanout, grace_depth_cap, mesh,
                                       table_key)
            finally:
                _record_spill_stats(rec, spill_pool)

        rec["fused_spans"] = meta.get("fused_spans", 0)
        dicts = dt.dicts
        P, N = dt.shape
        rec["table_shape"] = [P, N]

        emit_key = (tuple(self.emit_pid[0]), self.emit_pid[1]) if self.emit_pid else None
        # device LUTs cached per (table, stage): zero uploads when hot;
        # replicated across the mesh so probe gathers stay local
        lut_key = (table_key, self.fingerprint, mesh.devices.size if mesh else 0, emit_key,
                   ctx.device_ordinal)
        luts = _LUT_CACHE.get(lut_key)
        if luts is None:
            raw_luts = lowering.build_luts(dicts, [b.dicts for b in builds])
            luts = [_put(mesh, l) for l in raw_luts]
            _LUT_CACHE[lut_key] = luts

        build_args = [b.flat_arrays() for b in builds]
        first_dispatch = not state["dispatched"]
        state["dispatched"] = True
        t0 = time.perf_counter()
        # host blocked on the device; a fresh entry's first call compiles
        # (or loads the persistent cache's binary) inside it, and is named so
        with RUN_STATS.span("bt.compile.xla" if first_dispatch else "bt.device.exec") as span:
            outs = fn(dt.flat_cols(), luts, dt.mask, build_args)
            jax.block_until_ready(list(outs))
        t_call = span.seconds  # a cold call folds the backend compile in
        if first_dispatch:
            # jit compiles (or fetches from the persistent cache) inside the
            # first call; when the overlap worker already AOT-compiled, the
            # honest figure is ITS compile time (which ran under the fill)
            rec["xla_compile_s"] = round(holder.get("xla_s", t_call), 6)
        res = self._fetch_decode(outs, meta, P, dicts, [b.dicts for b in builds])
        _note_match_lanes(meta, rec, dispatch_span)
        exec_s = time.perf_counter() - t0
        if first_dispatch and "xla_s" not in holder:
            exec_s = max(0.0, exec_s - t_call)  # compile time isn't exec time
        rec["exec_s"] = round(exec_s, 6)
        if "trace_s" in rec or "xla_compile_s" in rec:
            rec["compile_s"] = round(
                rec.get("trace_s", 0.0) + rec.get("xla_compile_s", 0.0), 6)
        cc1 = runtime.compile_cache_stats()
        if cc1["requests"] > cc0["requests"]:
            rec["persist_cache_hits"] = cc1["hits"] - cc0["hits"]
            rec["persist_cache_misses"] = (
                (cc1["requests"] - cc0["requests"]) - (cc1["hits"] - cc0["hits"]))
        _record_spill_stats(rec, spill_pool)
        return res

    def _grace_run(self, ctx: TaskContext, rec: dict, dt: DeviceTable,
                   join_ops: list, builds: list[BuildTable], plan,
                   fanout: int, depth_cap: int, mesh,
                   table_key) -> dict[int, list[pa.RecordBatch]]:
        """Grace-partitioned execution of a budget-breaking hash-join stage.

        The split join's build side re-splits by a secondary hash of the
        combined int64 key (hbm.grace_bucket_of — the splitmix64 lane
        encoding lineage of the PR 7 exchange, salted so it is independent
        of the routing hash) into `plan.grace_buckets` sub-buckets, each
        executed sequentially on device as the SAME compiled stage shape
        over the full probe table. Probe rows are never re-ordered: a row
        whose key lives in bucket b matches only in run b and is masked (an
        ordinary unmatched probe) in every other run, so concatenating the
        per-partition partial-aggregate batches in bucket order reunifies
        in producer row order and the downstream final aggregate merges
        them exactly as it merges multi-partition partials — byte-identical
        to the unconstrained run. Empty sub-builds are skipped; the
        GraceReport postconditions are checked before results are served."""
        jax = ensure_jax()
        dicts = dt.dicts
        P, _N = dt.shape
        n_buckets = int(plan.grace_buckets)
        jsplit = int(plan.split_jidx)
        merged: dict[int, list[pa.RecordBatch]] = {p: [] for p in range(P)}
        buckets_run: list[int] = []
        buckets_empty: list[int] = []
        for b in range(n_buckets):
            try:
                sub_builds = [
                    self._prepare_build(op, j, ctx, table_key, mesh,
                                        grace=(b, n_buckets), rec=rec)
                    if j == jsplit else builds[j]
                    for j, op in enumerate(join_ops)
                ]
            except Unsupported as e:
                if "empty grace sub-bucket" in str(e):
                    buckets_empty.append(b)
                    continue
                raise
            cached, _, _ = self._compile_locked(dt, sub_builds, rec)
            fn, lowering, meta, state = cached
            state["dispatched"] = True
            # LUT cache bypass: sub-build dictionaries are bucket-dependent,
            # and the (table, stage) LUT key has no bucket component
            luts = [_put(mesh, l)
                    for l in lowering.build_luts(dicts, [sb.dicts for sb in sub_builds])]
            build_args = [sb.flat_arrays() for sb in sub_builds]
            with RUN_STATS.span("bt.device.exec", grace_bucket=b):
                outs = fn(dt.flat_cols(), luts, dt.mask, build_args)
                jax.block_until_ready(list(outs))
            res = self._fetch_decode(outs, meta, P, dicts,
                                     [sb.dicts for sb in sub_builds])
            _note_match_lanes(meta, rec, RUN_STATS.current_span(), add=bool(buckets_run))
            for p, bl in res.items():
                merged[p].extend(x for x in bl if x.num_rows)
            buckets_run.append(b)

        report = hbm.GraceReport(
            stage_tag=f"stage_{zlib.crc32(self.fingerprint.encode()):08x}",
            n_buckets=n_buckets, fanout=max(2, int(fanout)),
            depth=int(plan.grace_depth), max_depth=int(depth_cap),
            buckets_run=buckets_run, buckets_empty=buckets_empty)
        from ballista_tpu.analysis.plan_check import check_grace

        violations = check_grace(report)
        if violations:
            # a postcondition miss means the merged output cannot be trusted:
            # demote to the always-correct CPU rung instead of serving it
            raise Unsupported("grace postcondition violated: "
                              + "; ".join(v.message for v in violations))
        rec["grace_splits"] = len(buckets_run)
        schema = self.schema()
        return {p: (bl if bl else [_empty_batch(schema)])
                for p, bl in merged.items()}

    # ------------------------------------------------------------------

    def _compile(self, dt: DeviceTable, kinds, dicts, P: int, N: int,
                 builds: list[BuildTable] | None = None):
        from ballista_tpu.plan.physical import HashJoinExec

        jax = ensure_jax()
        jnp = jax.numpy
        agg = self.partial_agg
        scan_schema = self.scan.df_schema
        builds = builds or []
        spans = fusion.plan_spans(
            len(getattr(self.scan, "filters", []) or []), self.ops, agg)

        ctx = Lowering(scan_schema, kinds, dicts)
        valid_idx = dt.valid_flat_idx()
        n_flat_cols = len(dt.cols) + sum(1 for v in dt.valids if v is not None)
        env_fns = []
        for i, (kind, scale) in enumerate(kinds):
            env_fns.append(_scoped(
                "scan_decode", _mk_col_reader(i, kind, scale, dicts[i], valid_idx[i])))
        env_meta = [(k, s, d, i) for i, ((k, s), d) in enumerate(zip(kinds, dicts))]
        ctx.env_fns = env_fns
        ctx.env_meta = env_meta
        filter_fns = []

        cur_schema = scan_schema
        _bind_env(ctx, cur_schema)
        # scan-level predicates run ON DEVICE (cache holds raw columns)
        for f in getattr(self.scan, "filters", []):
            filter_fns.append(lower_expr(f, ctx))

        lane_cells = [{"d": 0} for _ in builds]
        lane_dups: list[int] = []  # per build: lanes to unroll (1 for semi/anti)
        join_lanes: list[int] = []  # per join: the match lanes its lowering unrolled
        # filter index -> its match lanes, for a filter that unrolls several
        # (a filtered semi / anti join): the sorted path runs these behind
        # its live-row switch
        tier_filters: dict[int, int] = {}
        lookups = _LaneLog()  # the joins' lookups, noted as the program is traced
        outer_jidx: set[int] = set()  # joins whose build gathers are nullable-by-miss
        # (filters, joins) up to and including the FIRST join whose match mask
        # filters probe rows (inner, semi, anti): the prefix the direct path
        # evaluates over every slot before it probes the rest of the chain at
        # the live rows' tier. None: no such join, no tier
        probe_prefix = None

        def selective_join():
            nonlocal probe_prefix
            if probe_prefix is None:
                probe_prefix = (len(filter_fns), jidx + 1)

        # Aggregate-through-join pre-scan: when the LAST op is an inner/right
        # join whose build columns appear ONLY as count(col) arguments (and
        # group keys are probe-side), the stage aggregates THROUGH the join
        # with per-row match counts — no dup-lane unrolling, no MAX_JOIN_DUP
        # ceiling (the q13 shape: count(o_orderkey) group by c_custkey).
        mult_jidx = None
        mult_outer = False
        count_build_aggs: dict[int, int] = {}  # agg idx → build field idx
        join_ops = [o for o in self.ops if isinstance(o, HashJoinExec)]
        if builds and join_ops:
            jop = join_ops[-1]
            bt_last = builds[-1]
            cba = _mult_shape_check(agg, self.ops, jop)
            if cba is not None and bt_last.dup > 1:
                ok = True
                for fi in cba.values():
                    pp = bt_last.pay_pos[fi] if fi < len(bt_last.pay_pos) else None
                    if pp is None or bt_last.pay_valids[pp] is not None:
                        ok = False  # nullable build col: match count ≠ count(col)
                if ok:
                    mult_jidx = len(builds) - 1
                    mult_outer = jop.join_type == "right"
                    count_build_aggs = cba
        mult_weight_fn = None
        jidx = 0
        for op in self.ops:
            _bind_env(ctx, cur_schema)
            if isinstance(op, FilterExec):
                filter_fns.append(lower_expr(op.predicate, ctx))
            elif isinstance(op, HashJoinExec):
                bt = builds[jidx]
                # build arrays ride at the tail of the flattened cols list
                # (after the scan columns AND their validity planes)
                off = n_flat_cols + sum(len(builds[i].flat_arrays()) for i in range(jidx))
                pay_off = off + (2 if bt.cnt is not None else 1)
                probe_fns = [lower_expr(r, ctx) for (_, r) in op.on]
                probe_scope = f"join_probe_{jidx}"
                finder = _scoped(probe_scope, _mk_join_finder(
                    off, probe_fns, bt, lane_cells[jidx], lookups))
                pv_idx = bt.pay_valid_flat_idx()
                if op.join_type in ("right_semi", "right_anti"):
                    neg = op.join_type == "right_anti"
                    if op.filter is None:
                        # membership only: the match mask filters probe rows
                        # (EXISTS / NOT IN after decorrelation) — no build
                        # columns, no expansion lanes, schema unchanged
                        filter_fns.append(
                            lambda cols, luts, _f=finder, _n=neg:
                            DevVal("bool", ~_f(cols, luts)[1].arr if _n else _f(cols, luts)[1].arr)
                        )
                        selective_join()
                    else:
                        # EXISTS with a correlated residual predicate (q21's
                        # l2.l_suppkey <> l1.l_suppkey): OR the filtered
                        # match across all dup lanes of the build key
                        if bt.dup > MAX_JOIN_DUP:
                            raise Unsupported(
                                f"semi/anti join filter over dup {bt.dup} > {MAX_JOIN_DUP}"
                            )
                        lane_preds = []
                        saved_fns, saved_meta = list(ctx.env_fns), list(ctx.env_meta)
                        combined_schema = op.left.df_schema.merge(cur_schema)
                        for d in range(bt.dup):
                            finder_d = _scoped(probe_scope, _mk_join_finder(
                                off, probe_fns, bt, {"d": d}, lookups))
                            gfns, gmeta = [], []
                            for ci, pp in enumerate(bt.pay_pos):
                                if pp is None:
                                    gfns.append(_mk_raising(
                                        f"unencodable build column {ci} in join filter"))
                                    gmeta.append(None)
                                else:
                                    gfns.append(_mk_build_gather(
                                        pay_off, pp, bt.kinds[ci], bt.scales[ci],
                                        bt.dicts[ci], finder_d,
                                        None if pv_idx[pp] is None else off + pv_idx[pp]))
                                    gmeta.append((bt.kinds[ci], bt.scales[ci],
                                                  bt.dicts[ci], ("build", jidx, ci)))
                            ctx.env_fns = gfns + saved_fns
                            ctx.env_meta = gmeta + saved_meta
                            _bind_env(ctx, combined_schema)
                            lane_preds.append((finder_d, lower_expr(op.filter, ctx)))
                        ctx.env_fns, ctx.env_meta = saved_fns, saved_meta
                        _bind_env(ctx, cur_schema)

                        def run(cols, luts, _lp=lane_preds, _n=neg):
                            any_m = None
                            for fd, pf in _lp:
                                _, matched = fd(cols, luts)
                                md = true_mask(matched) & true_mask(pf(cols, luts))
                                any_m = md if any_m is None else any_m | md
                            return DevVal("bool", ~any_m if _n else any_m)

                        filter_fns.append(run)
                        selective_join()
                        if bt.dup > 1:
                            tier_filters[len(filter_fns) - 1] = bt.dup
                    join_lanes.append(1 if op.filter is None else bt.dup)
                    lane_dups.append(1)
                    jidx += 1
                    continue
                if jidx == mult_jidx:
                    # aggregate-through-join: ONE count gather replaces all
                    # dup match lanes; build columns are never materialized
                    counter = _scoped(probe_scope, _mk_join_counter(
                        off, probe_fns, bt, lane_cells[jidx], lookups))
                    if op.join_type == "inner":
                        filter_fns.append(
                            lambda cols, luts, _c=counter:
                            DevVal("bool", _c(cols, luts) > 0)
                        )
                        selective_join()
                    mult_weight_fn = counter
                    n_bf = len(op.left.df_schema)
                    ctx.env_fns = [
                        _mk_raising("build column consumed as a value in an "
                                    "aggregate-through-join stage")
                    ] * n_bf + list(ctx.env_fns)
                    ctx.env_meta = [None] * n_bf + list(ctx.env_meta)
                    cur_schema = op.df_schema
                    join_lanes.append(1)
                    lane_dups.append(1)
                    jidx += 1
                    continue
                outer = op.join_type == "right"
                if outer:
                    outer_jidx.add(jidx)
                    # right outer: every probe row emits — on lane 0
                    # unconditionally (unmatched rows ride lane 0 with NULL
                    # build gathers), on later lanes only when matched. The
                    # lane is an int, or an int32 a row where the sorted path
                    # has compacted several lanes' rows into one set
                    def emit(cols, luts, _f=finder, _cell=lane_cells[jidx]):
                        _, matched = _f(cols, luts)
                        return DevVal("bool", matched.arr | (_cell["d"] == 0))

                    filter_fns.append(emit)
                else:
                    filter_fns.append(lambda cols, luts, _f=finder: _f(cols, luts)[1])
                    selective_join()
                join_lanes.append(bt.dup)
                lane_dups.append(bt.dup)
                build_fns = [
                    _mk_build_gather(pay_off, ci, bt.kinds[ci], bt.scales[ci], bt.dicts[ci],
                                     finder,
                                     None if pv_idx[ci] is None else off + pv_idx[ci],
                                     outer=outer)
                    for ci in range(len(bt.payloads))
                ]
                build_meta = [
                    (bt.kinds[ci], bt.scales[ci], bt.dicts[ci], ("build", jidx, ci))
                    for ci in range(len(bt.payloads))
                ]
                # exec output order: build fields then probe fields
                ctx.env_fns = build_fns + list(ctx.env_fns)
                ctx.env_meta = build_meta + list(ctx.env_meta)
                cur_schema = op.df_schema
                jidx += 1
            elif isinstance(op, ProjectionExec):
                new_fns, new_meta = [], []
                for e in op.exprs:
                    new_fns.append(lower_expr(e, ctx))
                    new_meta.append(_passthrough_meta(e, ctx, cur_schema))
                ctx.env_fns, ctx.env_meta = new_fns, new_meta
                cur_schema = op.df_schema
            elif isinstance(op, CoalesceBatchesExec):
                pass
            else:
                raise Unsupported(f"op {type(op).__name__}")
        _bind_env(ctx, cur_schema)
        ctx.stage_filter_fns = filter_fns  # shared with the sorted path
        ctx.tier_filters = tier_filters
        lane_sets = list(itertools.product(*[range(d) for d in lane_dups]))
        if len(lane_sets) > MAX_JOIN_DUP:
            raise Unsupported(f"{len(lane_sets)} expansion-join lanes > {MAX_JOIN_DUP}")
        ctx.lane_sets = lane_sets
        ctx.lane_cells = lane_cells
        ctx.lookups = lookups
        ctx.match_lanes = sum(join_lanes)

        # Group-key strategy: small dictionary domains unroll into per-group
        # masked reductions (pure VPU, no scatter/sort). Everything else —
        # int64 keys like l_orderkey, composite keys, big dictionaries —
        # goes through the sort-based segmented reduction below.
        def _slot_nullable(slot) -> bool:
            if isinstance(slot, tuple) and slot[0] == "build":
                if slot[1] in outer_jidx:
                    return True  # unmatched outer gathers are NULL
                pp = builds[slot[1]].pay_pos[slot[2]]
                return pp is None or builds[slot[1]].pay_valids[pp] is not None
            return dt.valids[slot] is not None

        unrolled = True
        group_src_slots: list = []
        group_fns: list = []
        pad_sizes: list = []
        for g in agg.group_exprs:
            gc = g.expr if isinstance(g, Alias) else g
            if not isinstance(gc, Column):
                unrolled = False
                break
            i = cur_schema.index_of(gc.name, gc.qualifier)
            gmeta = ctx.env_meta[i]
            if gmeta is None or gmeta[0] != "code" or gmeta[2] is None:
                unrolled = False
                break
            if _slot_nullable(gmeta[3]):
                # a NULL group key needs its own group: the sorted path
                # carries validity as an extra sort operand; the unrolled
                # code-domain form cannot distinguish null from code 0
                unrolled = False
                break
            group_fns.append(ctx.env_fns[i])
            group_src_slots.append(gmeta[3])
            pad_sizes.append(_pow2(len(gmeta[2])))

        G = 1
        for p in pad_sizes:
            G *= p
        G = max(G, 1)
        n_lanes = len(ctx.lane_sets)
        if unrolled and agg.group_exprs and (
            G * n_lanes > 64 or G * n_lanes * P > MAX_SEGMENTS * 16
        ):
            # the unrolled form materializes G masked reductions PER
            # expansion lane; beyond this budget the sorted form wins (and
            # scatter-free unrolling stops scaling)
            unrolled = False

        agg_fns = []
        agg_modes = []  # "row" | "build_cnt" (count of a mult-join build col)
        for ai, d in enumerate(agg.aggs):
            if d.func in ("welford_mean", "welford_m2"):
                # mean/M2 partials are not additive across expansion lanes and
                # have no weighted form: only plain (single-lane, unweighted)
                # stages carry variance on device; others re-run on cpu
                if mult_weight_fn is not None or len(ctx.lane_sets) != 1:
                    raise Unsupported("welford through expansion join")
            elif d.func not in ("sum", "min", "max", "count", "count_all"):
                raise Unsupported(f"agg {d.func}")
            if ai in count_build_aggs:
                agg_fns.append(None)
                agg_modes.append("build_cnt")
            else:
                agg_fns.append(lower_expr(d.expr, ctx) if d.expr is not None else None)
                agg_modes.append("row")
        mult = (mult_weight_fn, mult_outer) if mult_weight_fn is not None else None

        if not unrolled:
            group_fns = [lower_expr(g, ctx) for g in agg.group_exprs]
            # live-dictionary slots for decode (compilations are shared
            # across tables with equal shapes/dict sizes; dict CONTENTS are
            # resolved at decode time, never baked into the cached meta)
            key_slots: list = []
            key_premeta: list = []  # (kind, scale, dict, slot) | None, PRE-trace
            for g in agg.group_exprs:
                gc = g.expr if isinstance(g, Alias) else g
                slot = None
                gmeta = None
                if isinstance(gc, Column):
                    i = cur_schema.index_of(gc.name, gc.qualifier)
                    gmeta = ctx.env_meta[i]
                    if gmeta is not None:
                        slot = gmeta[3]
                key_slots.append(slot)
                key_premeta.append(gmeta)
            fn_s, ctx_s, meta_s, lowered_s = self._compile_sorted(
                dt, ctx, P, N, builds, group_fns, agg_fns, key_slots, key_premeta,
                agg_modes=agg_modes, mult=mult,
            )
            meta_s["fused_spans"] = len(spans)
            return fn_s, ctx_s, meta_s, lowered_s

        meta_holder: dict = {}
        aggs = agg.aggs

        lane_sets = ctx.lane_sets
        lane_cells = ctx.lane_cells

        # --- span closures, composed into ONE traced function below -------

        def eval_pred(cols, luts, mask, fns):
            """predicate span: scan filters, FilterExec predicates, semi/
            anti membership masks, join match masks — one fused boolean of
            `mask`'s shape."""
            m = mask
            for ff in fns:
                m = m & true_mask(ff(cols, luts))
            return m

        def eval_proj(cols, luts, shape):
            """project/probe span: group-id composition, agg value lanes and
            the aggregate-through-join weight (join-probe gathers ride inside
            the lowered column closures)."""
            if group_fns:
                gid = None
                for gf, psz in zip(group_fns, pad_sizes):
                    codes = gf(cols, luts).arr.astype(jnp.int32)
                    gid = codes if gid is None else gid * psz + codes
            else:
                gid = None
            vs = [af(cols, luts) if af is not None else None for af in agg_fns]
            w = None
            if mult_weight_fn is not None:
                w = jnp.broadcast_to(mult_weight_fn(cols, luts), shape)
            return gid, vs, w

        def aggregate_lane(m, gid, vs, w):
            """aggregate span, one expansion lane: per-group masked
            reductions (the XLA form — pure VPU, no scatter)."""
            m_eff = None
            if w is not None:
                m_eff = jnp.maximum(w, 1) if mult_outer else w
            gmasks = [m & (gid == g) for g in range(G)] if gid is not None else [m]
            outs_lane = []
            out_meta = []
            nullcnt_lane = []
            nullcnt_map: dict[int, int] = {}
            for ai, (d, v) in enumerate(zip(aggs, vs)):
                if v is None:
                    out_meta.append(("i64", 0))
                else:
                    out_meta.append(("i64", 0) if d.func == "count" else (v.kind, v.scale))
                cols_out = []
                for gm in gmasks:
                    if agg_modes[ai] == "build_cnt":
                        cols_out.append(
                            jnp.where(gm, w, 0).astype(jnp.int64).sum(axis=1))
                    elif m_eff is None:
                        cols_out.append(_masked_reduce(jnp, v, gm, d.func))
                    else:
                        cols_out.append(_masked_reduce_w(jnp, v, gm, d.func, m_eff))
                outs_lane.append(jnp.stack(cols_out, axis=1))  # [P, G]
                if (v is not None and v.valid is not None
                        and d.func in ("sum", "min", "max",
                                       "welford_mean", "welford_m2")):
                    # valid-count companion: a group whose inputs are all
                    # NULL must decode to NULL, not 0 / ±inf
                    nullcnt_map[ai] = len(nullcnt_lane)
                    nullcnt_lane.append(jnp.stack(
                        [(gm & v.valid).sum(axis=1) for gm in gmasks], axis=1
                    ))
            presence_lane = jnp.stack([gm.sum(axis=1) for gm in gmasks], axis=1)
            meta_holder["out"] = out_meta
            meta_holder["nullcnt_map"] = nullcnt_map
            return outs_lane, nullcnt_lane, presence_lane

        # each device operation's metadata says which operator span it came from
        eval_pred = _scoped("filter", eval_pred)
        eval_proj = _scoped("project", eval_proj)
        aggregate_lane = _scoped("partial_agg", aggregate_lane)

        prefix_fns, prefix_joins = probe_prefix or (0, 0)
        # the capacities a partition's live rows may be probed at, from N alone
        capacities = _tier_capacities(N, LIVE_TIERS[1:])

        def probe_at(cap, founds):
            """One tier of the probe: everything behind the prefix — the later
            joins' finders and match masks, predicates, payload gathers, group
            ids, aggregate inputs — over each partition's live rows, gathered
            in slot order into `cap` slots of the scan columns. The prefix's
            joins are probed again there for their payloads, `cap` rows and
            not N; at N, over the slots as they are, their payload gathers
            reuse what the prefix found (`founds`, a prefix lane each). Every
            tier hands what the aggregate reads back as [P, N], the slots
            past `cap` dead, so all have the one signature."""

            def compacted(cols, live, count):
                # as ONE flat row set, [P * cap]: a flat gather takes the chip
                # half the time of a batched one, and the chain's lookups are
                # flat gathers already
                with jax.named_scope("compact_live"):
                    src = (live_slots(live, cap) + jnp.arange(
                        0, P * N, N, dtype=jnp.int32)[:, None]).reshape(-1)
                    return ([c.reshape(-1)[src] for c in cols[:n_flat_cols]]
                            + cols[n_flat_cols:],
                            (jnp.arange(cap, dtype=jnp.int32)[None, :]
                             < count[:, None]).reshape(-1))

            def branch(cols, luts, lives, counts):
                lookups.at = cap  # what the joins look up from here on: this tier's
                if cap < N:
                    row_sets = [compacted(cols, *lc) for lc in zip(lives, counts)]
                else:
                    row_sets = [(cols, live) for live in lives]
                outs = []
                for lane, at in zip(lane_sets, lane_prefix):
                    _set_lanes(lane_cells, lane, founds[at] if cap == N else ())
                    at_cols, live = row_sets[at]
                    m = eval_pred(at_cols, luts, live, filter_fns[prefix_fns:])
                    gid, vs, w = eval_proj(at_cols, luts, live.shape)
                    # a switch carries arrays: what a value is (kind, scale)
                    # is the closures' alone, the same in every tier
                    meta_holder["vs"] = [v and (v.kind, v.scale) for v in vs]
                    planes = [v and [None if x is None else jnp.broadcast_to(x, live.shape)
                                     for x in (v.arr, v.valid)] for v in vs]
                    outs.append(jax.tree.map(
                        lambda x: jnp.pad(x.reshape(P, cap), ((0, 0), (0, N - cap))),
                        (m, gid, w, planes)))
                return outs

            return branch

        # lanes that differ only behind the prefix share its mask (and, in a
        # tier, its compaction)
        prefix_lanes = sorted({lane[:prefix_joins] for lane in lane_sets})
        lane_prefix = [prefix_lanes.index(lane[:prefix_joins]) for lane in lane_sets]

        def merge_lane(outs, lane_outs):
            """reductions accumulate across expansion lanes"""
            if outs is None:
                return lane_outs
            merged = []
            for d, prev, cur in zip(aggs, outs[0], lane_outs[0]):
                if d.func == "min":
                    merged.append(jnp.minimum(prev, cur))
                elif d.func == "max":
                    merged.append(jnp.maximum(prev, cur))
                else:  # sum / count: additive across lanes
                    merged.append(prev + cur)
            return (merged, [p_ + c_ for p_, c_ in zip(outs[1], lane_outs[1])],
                    outs[2] + lane_outs[2])

        def raw(cols, luts, mask, build_args):
            # keep [P, N]: partitions are the leading axis, reductions run
            # over axis=1 — XLA fuses the per-group masked sums into single
            # VPU passes, no scatter anywhere. Join-probe gathers hit the
            # build arrays appended after the scan columns. Expansion joins
            # unroll match lanes: the full pipeline is traced once per lane
            # combination (XLA CSEs lane-invariant work) and reductions
            # accumulate across lanes.
            cols = list(cols) + [a for b in build_args for a in b]
            lookups.at = None  # over every slot, up to the tiers' switch
            outs = None
            if probe_prefix is None:
                for lane in lane_sets:
                    _set_lanes(lane_cells, lane)
                    m = eval_pred(cols, luts, mask, filter_fns)
                    outs = merge_lane(outs, aggregate_lane(
                        m, *eval_proj(cols, luts, mask.shape)))
                _set_lanes(lane_cells, lane_sets[0])  # the cells keep no traced value
                return tuple(outs[0]) + tuple(outs[1]) + (outs[2],)
            # a join's match mask filters the rows: the prefix over every
            # slot, then a `lax.switch` on the fullest partition's live count
            # probes the rest of the chain at the smallest capacity that
            # holds every partition's live rows; the aggregate's G masked
            # passes run once, behind the switch
            lives, founds = [], []
            for lane in prefix_lanes:
                _set_lanes(lane_cells, lane + (0,) * (len(lane_cells) - prefix_joins))
                lives.append(eval_pred(cols, luts, mask, filter_fns[:prefix_fns]))
                founds.append([cell["last"] for cell in lane_cells])
            with jax.named_scope("compact_live"):
                counts = [m.sum(axis=1, dtype=jnp.int32) for m in lives]
                fullest = jnp.max(jnp.stack(counts))
                tier = sum((fullest > cap).astype(jnp.int32) for cap in capacities[:-1])
            probed = jax.lax.switch(tier, [probe_at(cap, founds) for cap in capacities],
                                    cols, luts, lives, counts)
            for m, gid, w, vs in probed:
                vs = [v and DevVal(what[0], v[0], what[1], valid=v[1])
                      for v, what in zip(vs, meta_holder["vs"])]
                outs = merge_lane(outs, aggregate_lane(m, gid, vs, w))
            _set_lanes(lane_cells, lane_sets[0])  # the cells keep no traced value
            n_live = sum(counts[at].sum() for at in lane_prefix)
            probe_counts = jnp.stack([
                n_live, jnp.asarray(capacities, jnp.int32)[tier] * (P * len(lane_sets))])
            return tuple(outs[0]) + tuple(outs[1]) + (outs[2], probe_counts)

        # a stable name from what the stage is, never a plan hash: the trace
        # reads jit_stage_partial_direct_fused_xla(..fingerprint)/fusion.N
        raw.__name__ = raw.__qualname__ = "stage_partial_direct_fused_xla"
        jitted = jax.jit(raw)
        cols_spec = [jax.ShapeDtypeStruct(c.shape, c.dtype) for c in dt.flat_cols()]
        luts0 = ctx.build_luts(dicts, [b.dicts for b in builds])
        luts_spec = [jax.ShapeDtypeStruct(l.shape, l.dtype) for l in luts0]
        mask_spec = jax.ShapeDtypeStruct(dt.mask.shape, np.bool_)
        builds_spec = [
            [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in b.flat_arrays()]
            for b in builds
        ]
        # trace → meta; the Lowered also feeds the overlap worker's optional
        # AOT backend compile (which warms the persistent cache)
        lowered = jitted.lower(cols_spec, luts_spec, mask_spec, builds_spec)
        lanes = len(lane_sets)
        meta = {
            "mode": "unrolled",
            "fused_spans": len(spans),
            "out": meta_holder["out"],
            "nullcnt_map": meta_holder.get("nullcnt_map", {}),
            "group_src_slots": group_src_slots,
            "pad_sizes": pad_sizes,
            "G": G,
            # the stage's row slots; where a join's match filters them the
            # last output is int32 (live rows behind the prefix, row slots
            # probed): RunStats `probe_rows_live` / `probe_rows`
            "slots": P * N * len(lane_sets),
            "probe_counts": probe_prefix is not None,
            # RunStats `match_lanes`, and `match_lane_slots` by the
            # `probe_rows` the dispatch reads: the tier it took
            "match_lanes": ctx.match_lanes,
            # RunStats `tier_lanes`: those of the multi-lane filters behind the prefix
            "tier_lanes": sum(n for i, n in tier_filters.items() if i >= prefix_fns),
            "lane_slots": ({cap * P * lanes: lookups.slots(cap) for cap in capacities}
                           if probe_prefix is not None
                           else {P * N * lanes: lookups.slots(None)}),
        }
        return jitted, ctx, meta, lowered

    def _compile_sorted(self, dt: DeviceTable, ctx: Lowering, P: int, N: int,
                        builds: list[BuildTable], group_fns, agg_fns, key_slots,
                        key_premeta, agg_modes=None, mult=None):
        """Sort-based segmented reduction for large/int group domains.

        The TPU has no fast random scatter, so hash aggregation is out; the
        device-native plan for arbitrary group keys is: the lexicographic
        ordering permutation over (validity, key...) (`kernels.lex_order`),
        keys and agg inputs gathered through it, segment boundaries from
        adjacent-key diffs, per-segment totals via
        cumsum-subtract (sum/count: exact int64) or `kernels.segmented_scan`
        (min/max, float sums), then `kernels.SegmentCompaction` moves each
        output column's segment results into a static [C] capacity. Groups never
        outnumber the live rows and the live rows never outnumber the `M`
        row slots, so C = pow2(M) holds every group a dispatch can find: no
        constant caps it and no stage that dispatches can overflow it. What
        the [C] lanes and the ordering's `M`-row scratch cost in HBM is
        priced in `meta["program_bytes"]`, which admission
        (`hbm.plan_stage`) weighs before the dispatch: a stage past the
        budget declines then, never after. The fetch is sliced to
        pow2(actual segment count), so a capacity of millions costs nothing
        to fetch when a query yields 10k groups.

        Gathers and scatters are what the chip does slowly, and all of the
        above — and every join payload lookup of the projection before it —
        is gathers and scatters over every row slot. So the program
        evaluates its filters and join matches over the slots, counts the
        live rows, and a `lax.switch` on the count runs the projection (group
        keys, aggregate inputs: `project`) and all of the above over the
        smaller capacity of `M / 64` and `M` (LIVE_TIERS' first) that holds
        them: below `M`, over the SCAN COLUMNS gathered at the live slots, in
        slot order, through `kernels.live_slots` (the joins are probed again
        there for their payloads, `cap` rows and not `M`); at `M`, over the
        slots as they are. One body (`project`, `reduce_rows`) serves every
        tier, each padding its outputs to [C].

        A filter that unrolls several match lanes — a filtered semi / anti
        join, `dup` lookups and payload gathers OR-ed (`ctx.tier_filters`) —
        is left out of the count and runs in the tier, over the rows the
        other filters left (`kept_behind`): its lanes look up `cap` rows, not
        `M` (q21's fourteen). Every other filter runs over every slot, so a
        stage without such a filter traces what it traced before.

        The last output is int32 (groups, live rows, slots ordered, and
        where a multi-lane filter ran in the tier the rows that picked it):
        RunStats `sorted_groups`, `sorted_rows_live` / `sorted_rows_ordered` /
        `sorted_rows_masked`, and `probe_rows_live` / `probe_rows`, which
        equal `sorted_rows_live` / `sorted_rows_ordered` here.
        """
        jax = ensure_jax()
        jnp = jax.numpy
        agg = self.partial_agg
        aggs = agg.aggs
        filter_fns = ctx.stage_filter_fns
        tier_filters = ctx.tier_filters
        lane_sets = ctx.lane_sets
        lane_cells = ctx.lane_cells
        lookups = ctx.lookups
        M = P * N * len(lane_sets)
        C = _pow2(M)
        # the capacities a dispatch may order its live rows at, from M alone
        # (so the compile key and `meta` stay what they are)
        capacities = _tier_capacities(M, LIVE_TIERS[:1])
        n_scan = len(dt.flat_cols())
        meta_holder: dict = {}
        # device-side shuffle routing: emit a __pid column over the
        # compacted output rows (bit-exact twin of ops/hashing.py — string
        # keys hash via per-dictionary FNV LUTs)
        emit_keys: list[int] | None = None
        emit_k = 0
        emit_luts: dict[int, int] = {}
        if self.emit_pid is not None:
            idxs, emit_k = self.emit_pid
            if all(0 <= i < len(group_fns) for i in idxs) and emit_k > 0:
                emit_keys = list(idxs)
                # LUTs MUST register before tracing: lut specs are frozen
                # when the jitted fn lowers, so trace-time add_lut would
                # index past the traced argument list
                from ballista_tpu.ops.hashing import fnv1a_str

                for ki in emit_keys:
                    pm = key_premeta[ki]
                    # a money key hashes on the host as the IEEE bits of
                    # its float value, which the TPU cannot produce (its
                    # 64-bit rewrite has no f64 -> u64 bitcast): such
                    # stages leave the routing to the writer's host hash
                    if pm is None or pm[0] == "money":
                        emit_keys = None
                        break
                    if pm[0] == "code":
                        emit_luts[ki] = ctx.add_lut(
                            pm[3],
                            lambda dic: np.array(
                                [fnv1a_str(x) for x in (dic or [])], dtype=np.uint64
                            ),
                        )

        def slot_fits_int32(slot) -> bool:
            """A bare-column group key whose STORED lane is 32 bits or less
            (columnar._narrow_int proved the range at encode time; readers
            widen to int64) orders as one int32 radix lane, not two. Stored
            dtypes are part of the compile key."""
            if isinstance(slot, tuple) and slot[0] == "build":
                pp = builds[slot[1]].pay_pos[slot[2]]
                stored = None if pp is None else builds[slot[1]].payloads[pp].dtype
            else:
                stored = dt.cols[slot].dtype if isinstance(slot, int) else None
            return stored is not None and np.dtype(stored).itemsize <= 4

        def raw(cols, luts, mask, build_args):
            cols = list(cols) + [a for b in build_args for a in b]
            lookups.at = None  # over every slot, up to the tiers' switch
            # per expansion-join match lane: valid over every slot (and what
            # its joins' finders found there); lanes concatenate into one row
            # set feeding a single ordering
            lane_valid, founds = [], []
            for lane in lane_sets:
                _set_lanes(lane_cells, lane)
                m = mask
                with jax.named_scope("filter"):
                    for i, ff in enumerate(filter_fns):
                        if i not in tier_filters:
                            m = m & true_mask(ff(cols, luts))
                lane_valid.append(m.reshape(-1))
                founds.append([cell["last"] for cell in lane_cells])

            def kept_behind(cols, shape):
                """What the multi-lane filters keep of one row set of
                `shape`, flat: evaluated behind the switch, over the rows
                the other filters left and the tier holds."""
                with jax.named_scope("filter"):
                    return functools.reduce(jnp.logical_and, [
                        jnp.broadcast_to(true_mask(filter_fns[i](cols, luts)), shape)
                        .reshape(-1) for i in tier_filters])

            def project(cols, shape):
                """(key operands, payloads) of one row set: `cols` of `shape`,
                a lane's [P, N] slots or the live rows of every lane.
                A NULLABLE group key contributes TWO sort operands — a null
                marker then the (filled) value — so NULL forms its own group
                (SQL GROUP BY treats NULLs as equal) without sentinel values."""
                with jax.named_scope("project"):
                    keyops = []  # flat key operand list
                    key_meta = []  # per key: (kind, scale, slot, has_null)
                    key_narrow = []  # per key OPERAND: orders as int32
                    for gf, slot in zip(group_fns, key_slots):
                        v = gf(cols, luts)
                        if v.kind == "f64":
                            raise Unsupported("f64 group key")
                        if v.kind == "code" and slot is None:
                            raise Unsupported("code group key without a dictionary slot")
                        arr = v.arr
                        if arr.dtype == jnp.bool_:
                            arr = arr.astype(jnp.int32)
                        has_null = v.valid is not None
                        if has_null:
                            marker = jnp.broadcast_to(~v.valid, shape).reshape(-1)
                            keyops.append(marker.astype(jnp.int32))
                            key_narrow.append(True)
                        keyops.append(jnp.broadcast_to(arr, shape).reshape(-1))
                        key_narrow.append(slot_fits_int32(slot))
                        key_meta.append((v.kind, v.scale, slot, has_null))
                    meta_holder["key_meta"] = key_meta
                    meta_holder["key_narrow"] = key_narrow
                    w_b = m_eff = None
                    if mult is not None:
                        wfn, mouter = mult
                        w_b = jnp.broadcast_to(wfn(cols, luts), shape)
                        m_eff = jnp.maximum(w_b, 1) if mouter else w_b
                    # payload plan: per agg → (pay_idx|None, ncnt_idx|None)
                    pays = []
                    pay_plan = []
                    out_meta = []
                    # the welford (mean, m2) pair shares one Cast expr object:
                    # ship its value/validity lanes through the sort ONCE
                    welford_pay: dict[int, tuple] = {}
                    for ai, (d, af) in enumerate(zip(aggs, agg_fns)):
                        if agg_modes is not None and agg_modes[ai] == "build_cnt":
                            # count of a mult-join build column == match count
                            out_meta.append(("i64", 0))
                            pays.append(w_b.reshape(-1).astype(jnp.int64))
                            pay_plan.append((len(pays) - 1, None))
                            continue
                        v = af(cols, luts) if af is not None else None
                        if d.func in ("count", "count_all"):
                            out_meta.append(("i64", 0))
                            if v is None or v.valid is None:
                                if m_eff is None:
                                    pay_plan.append((None, None))  # segment length
                                else:
                                    pays.append(m_eff.reshape(-1).astype(jnp.int64))
                                    pay_plan.append((len(pays) - 1, None))
                            else:
                                # count(x): number of non-null x per group (each
                                # probe row weighted by its join multiplicity)
                                vb = jnp.broadcast_to(v.valid, shape)
                                cnt1 = m_eff if m_eff is not None else 1
                                pays.append(jnp.where(vb, cnt1, 0)
                                            .reshape(-1).astype(jnp.int64))
                                pay_plan.append((len(pays) - 1, None))
                            continue
                        if (d.func in ("welford_mean", "welford_m2")
                                and id(d.expr) in welford_pay):
                            out_meta.append(("f64", 0))
                            pay_plan.append(welford_pay[id(d.expr)])
                            continue
                        out_meta.append((v.kind, v.scale))
                        arr = v.arr
                        if m_eff is not None and d.func == "sum":
                            arr = arr * m_eff.astype(arr.dtype)
                        ncnt_idx = None
                        if v.valid is not None:
                            # null-skip: neutralize invalid slots for the reduce,
                            # and carry a valid-count so all-NULL groups decode
                            # to NULL rather than 0 / ±inf
                            if d.func in ("sum", "welford_mean", "welford_m2"):
                                neutral = jnp.zeros((), dtype=arr.dtype)
                            elif d.func == "min":
                                neutral = (jnp.iinfo(arr.dtype).max
                                           if jnp.issubdtype(arr.dtype, jnp.integer) else jnp.inf)
                            else:
                                neutral = (jnp.iinfo(arr.dtype).min
                                           if jnp.issubdtype(arr.dtype, jnp.integer) else -jnp.inf)
                            arr = jnp.where(v.valid, arr, neutral)
                            pays.append(jnp.broadcast_to(
                                v.valid, shape).reshape(-1).astype(jnp.int64))
                            ncnt_idx = len(pays) - 1
                        pays.append(jnp.broadcast_to(arr, shape).reshape(-1))
                        pay_plan.append((len(pays) - 1, ncnt_idx))
                        if d.func in ("welford_mean", "welford_m2"):
                            welford_pay[id(d.expr)] = pay_plan[-1]
                    meta_holder["out"] = out_meta
                    meta_holder["pay_plan"] = pay_plan
                    meta_holder["n_pays"] = len(pays)
                return keyops, pays

            def reduce_rows(valid, keys, pays):
                """The ordering, the segmented reduction and the routing hash
                over ONE row set of any static length: the whole stage's M
                slots, or its live rows compacted into a tier's capacity.
                Outputs are padded to the stage's [C], so every tier has the
                one signature."""
                Mt = valid.shape[0]
                Ct = _pow2(Mt)  # a tier holds no more groups than its rows
                with jax.named_scope("sorted_agg"):
                    perm = lex_order([~valid] + [
                        k.astype(jnp.int32) if fits and k.dtype == jnp.int64 else k
                        for k, fits in zip(keys, meta_holder["key_narrow"])])
                    svalid = valid[perm]
                    skeys = [k[perm] for k in keys]
                    spays = [p[perm] for p in pays]

                    # a row starts a group where a key changes, row 0 always:
                    # every comparison leads with True. (Not from a
                    # `zeros((Mt,)).at[0].set(True)`: XLA folds that into an
                    # Mt-row literal the executable carries — 210 MB at 2^24,
                    # past what the persistent compile cache keeps.)
                    diff = functools.reduce(jnp.logical_or, [
                        jnp.concatenate([jnp.ones((1,), bool), k[1:] != k[:-1]])
                        for k in skeys])
                    boundary = svalid & diff
                    seg = int_cumsum(boundary.astype(jnp.int32)) - 1
                    bor_inv = boundary | ~svalid
                    is_end = svalid & jnp.concatenate([bor_inv[1:], jnp.ones((1,), bool)])
                    n_seg = boundary.sum().astype(jnp.int32)

                    arange = jnp.arange(Mt, dtype=jnp.int32)
                    # segment-start position of each row's segment, via one scatter
                    # + gather (indices unique: one boundary row per segment)
                    spos = (
                        jnp.zeros((Ct,), jnp.int32)
                        .at[jnp.where(boundary, seg, Ct)]
                        .set(arange, mode="drop", unique_indices=True)
                    )
                    start = spos[jnp.clip(seg, 0, Ct - 1)]
                    end_idx = jnp.where(is_end, seg, Ct)
                    compact = SegmentCompaction(end_idx, n_seg, Ct)

                    def int_segsum(sv):
                        # exact int64: global cumsum minus prefix-at-segment-start
                        w = sv.astype(jnp.int64)
                        csum = int_cumsum(w)
                        presum = csum - w  # exclusive
                        return compact(csum - presum[start])

                    key_outs = [compact(k) for k in skeys]
                    agg_outs = []
                    ncnt_outs = []
                    ncnt_map: dict[int, int] = {}
                    welford_stats: dict[int, tuple] = {}  # pay_idx → (c_c, mean_c, ncnt_pos)
                    for ai, (d, (pay_idx, ncnt_idx)) in enumerate(
                        zip(aggs, meta_holder["pay_plan"])
                    ):
                        if pay_idx is None:
                            agg_outs.append(compact((arange - start + 1).astype(jnp.int64)))
                            continue
                        sv = spays[pay_idx]
                        if d.func in ("welford_mean", "welford_m2"):
                            # two-pass variance partial over sorted segments: segment
                            # mean via float segscan, then gather the mean back per
                            # row (seg indexes the compacted [Ct] space) for the
                            # centered square sum — stable, no cancellation. The
                            # (mean, m2) pair shares payload lanes and stats.
                            if pay_idx in welford_stats:
                                c_c, mean_c, ncnt_pos = welford_stats[pay_idx]
                            else:
                                if ncnt_idx is not None:
                                    c_c = int_segsum(spays[ncnt_idx])
                                else:
                                    c_c = compact((arange - start + 1).astype(jnp.int64))
                                s1_c = compact(segmented_scan(sv, boundary, "sum"))
                                mean_c = s1_c / jnp.maximum(c_c, 1).astype(sv.dtype)
                                ncnt_pos = None
                                if ncnt_idx is not None:
                                    ncnt_pos = len(ncnt_outs)
                                    ncnt_outs.append(c_c)
                                welford_stats[pay_idx] = (c_c, mean_c, ncnt_pos)
                            if d.func == "welford_mean":
                                agg_outs.append(mean_c)
                            else:
                                mean_row = mean_c[jnp.clip(seg, 0, Ct - 1)]
                                d2 = (sv - mean_row) ** 2
                                if ncnt_idx is not None:
                                    # null x slots were sum-neutralized to 0; keep
                                    # them out of the square sum too
                                    d2 = jnp.where(spays[ncnt_idx] > 0, d2, 0.0)
                                agg_outs.append(compact(segmented_scan(d2, boundary, "sum")))
                            if ncnt_pos is not None:
                                ncnt_map[ai] = ncnt_pos
                            continue
                        fname = "sum" if d.func in ("count", "count_all") else d.func
                        if fname == "sum" and jnp.issubdtype(sv.dtype, jnp.integer):
                            agg_outs.append(int_segsum(sv))
                        else:
                            # float sums use the segmented scan too: cumsum-subtract
                            # would difference two near-equal whole-table totals
                            # (catastrophic cancellation for small late segments)
                            agg_outs.append(compact(segmented_scan(sv, boundary, fname)))
                        if ncnt_idx is not None:
                            ncnt_map[ai] = len(ncnt_outs)
                            ncnt_outs.append(int_segsum(spays[ncnt_idx]))
                    meta_holder["nullcnt_map"] = ncnt_map
                    meta_holder["compact"] = compact.counts()

                outs = key_outs + agg_outs + ncnt_outs
                if emit_keys is not None:
                    from ballista_tpu.ops.tpu.kernels import hash64, hash_combine_jax

                    with jax.named_scope("emit"):
                        # key_outs layout: optional marker precedes each nullable
                        # key's value — build a key→(marker, value) position map
                        pos = 0
                        key_pos = []
                        for (_k, _s, _slot, hn) in meta_holder["key_meta"]:
                            key_pos.append((pos if hn else None, pos + (1 if hn else 0)))
                            pos += 2 if hn else 1
                        _NULL_TAG = jnp.uint64(0x9E3779B97F4A7C15)
                        h = jnp.zeros((Ct,), jnp.uint64)
                        for ki in emit_keys:
                            kind, scale, slot, _hn = meta_holder["key_meta"][ki]
                            mpos, vpos = key_pos[ki]
                            arr = key_outs[vpos]
                            if kind == "code":
                                enc = luts[emit_luts[ki]][arr]
                            else:  # i64 / date / bool — value-preserving int64 bits
                                enc = arr.astype(jnp.int64).astype(jnp.uint64)
                            hv = hash64(enc)
                            if mpos is not None:
                                hv = jnp.where(key_outs[mpos] != 0, _NULL_TAG, hv)
                            h = hash_combine_jax(h, hv)
                        pid = (h % jnp.uint64(emit_k)).astype(jnp.int32)
                    outs.append(pid)
                return tuple(jnp.pad(o, (0, C - Ct)) for o in outs) + (n_seg,)

            def reduce_kept(valid, keys, pays):
                """`reduce_rows`, and where the multi-lane filters ran in the
                tier the rows every filter keeps"""
                outs = reduce_rows(valid, keys, pays)
                return outs + (valid.sum(dtype=jnp.int32),) if tier_filters else outs

            def at_capacity(cap):
                def branch(cols, luts, valid, n_live):
                    lookups.at = cap  # what the joins look up from here on: this tier's
                    if cap == M:  # mostly alive: over the slots as they are
                        lanes, kept = [], []
                        for lane, found in zip(lane_sets, founds):
                            _set_lanes(lane_cells, lane, found)
                            if tier_filters:
                                kept.append(kept_behind(cols, mask.shape))
                            lanes.append(project(cols, mask.shape))
                        keys, pays = ([jnp.concatenate(xs) for xs in zip(*part)]
                                      for part in zip(*lanes))
                        if tier_filters:
                            valid = valid & jnp.concatenate(kept)
                        return reduce_kept(valid, keys, pays)
                    with jax.named_scope("compact_live"):
                        src = live_slots(valid, cap)
                        # a row of the concatenated lanes: its lane, its slot
                        lane_of, slot = jnp.divmod(src, mask.size)
                        live_cols = [c.reshape(-1)[slot] for c in cols[:n_scan]] + cols[n_scan:]
                        _set_lanes(lane_cells, lane_sets[0] if len(lane_sets) == 1 else
                                   jnp.asarray(lane_sets, jnp.int32)[lane_of].T)
                    keys, pays = project(live_cols, (cap,))
                    valid = jnp.arange(cap, dtype=jnp.int32) < n_live
                    if tier_filters:
                        valid = valid & kept_behind(live_cols, (cap,))
                    return reduce_kept(valid, keys, pays)
                return branch

            with jax.named_scope("sorted_agg"):
                valid = jnp.concatenate(lane_valid)
                # a scalar predicate outside any vmap: only the taken tier runs.
                # The count is of the rows every filter but the multi-lane
                # ones keeps: those run in the tier it picks
                n_live = valid.sum(dtype=jnp.int32)
                tier = sum((n_live > cap).astype(jnp.int32) for cap in capacities[:-1])
            outs = jax.lax.switch(tier, [at_capacity(cap) for cap in capacities],
                                  cols, luts, valid, n_live)
            _set_lanes(lane_cells, lane_sets[0])  # the cells keep no traced value
            # the groups, the rows every filter keeps, the slots ordered; and
            # where the multi-lane filters ran in the tier, the rows that
            # picked it
            if tier_filters:
                *outs, n_seg, n_kept = outs
                more = [n_live]
            else:
                *outs, n_seg = outs
                n_kept, more = n_live, []
            counts = jnp.stack([n_seg, n_kept, jnp.asarray(capacities, jnp.int32)[tier]] + more)
            return tuple(outs) + (counts,)

        raw.__name__ = raw.__qualname__ = "stage_partial_sorted_fused_xla"
        jitted = jax.jit(raw)
        cols_spec = [jax.ShapeDtypeStruct(c.shape, c.dtype) for c in dt.flat_cols()]
        luts0 = ctx.build_luts(dt.dicts, [b.dicts for b in builds])
        luts_spec = [jax.ShapeDtypeStruct(l.shape, l.dtype) for l in luts0]
        mask_spec = jax.ShapeDtypeStruct(dt.mask.shape, np.bool_)
        builds_spec = [
            [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in b.flat_arrays()]
            for b in builds
        ]
        lowered = jitted.lower(cols_spec, luts_spec, mask_spec, builds_spec)  # trace → meta
        # HBM the program holds beside its inputs: its [C] outputs, and at
        # the top tier the ordering's scratch — the permutation and a sorted
        # copy of every key operand and payload, 8 B a row each at most
        out_bytes = sum(int(np.prod(o.shape)) * np.dtype(o.dtype).itemsize
                        for o in jax.tree_util.tree_leaves(lowered.out_info))
        scratch = M * (4 + 8 * (len(meta_holder["key_narrow"]) + meta_holder["n_pays"]))
        meta = {
            "mode": "sorted",
            "out": meta_holder["out"],
            "key_meta": meta_holder["key_meta"],
            "nullcnt_map": meta_holder.get("nullcnt_map", {}),
            "emit_pid": emit_keys is not None,
            "C": C,
            "compact": meta_holder["compact"],
            "program_bytes": out_bytes + scratch,
            # RunStats `match_lanes`, and `match_lane_slots` by the tier the
            # dispatch took (`sorted_rows_ordered`, = `probe_rows`)
            "match_lanes": ctx.match_lanes,
            "tier_lanes": sum(tier_filters.values()),  # RunStats `tier_lanes`
            "lane_slots": {cap: lookups.slots(cap) for cap in capacities},
        }
        return jitted, ctx, meta, lowered

    # ------------------------------------------------------------------

    def _fetch_decode(self, outs, meta: dict, P: int, dicts,
                      build_dicts: list) -> dict[int, list[pa.RecordBatch]]:
        """Device outputs to Arrow batches per partition. The sorted path
        fetches inside its decode (a count first, then a sliced fetch)."""
        if meta["mode"] == "sorted":
            with RUN_STATS.span("bt.decode", mode="sorted") as span:
                return self._decode_sorted(outs, meta, P, dicts, build_dicts, span)
        with RUN_STATS.span("bt.device.fetch"):
            outs = ensure_jax().device_get(list(outs))  # ONE batched fetch
        with RUN_STATS.span("bt.decode") as span:
            n_probed = meta["slots"]  # no join's match filters the rows: all, uncounted
            if meta["probe_counts"]:
                n_live, n_probed = (int(x) for x in outs.pop())
                RUN_STATS.set("probe_rows_live", n_live)
                span.set(probe_rows_live=n_live)
            RUN_STATS.set("probe_rows", n_probed)
            span.set(probe_rows=n_probed)
            return self._decode_all(outs, meta, P, dicts, build_dicts)

    def _decode_sorted(self, outs, meta: dict, P: int, dicts,
                       build_dicts: list, span) -> dict[int, list[pa.RecordBatch]]:
        """Decode the sorted-path compacted outputs. Partial-agg results are
        mergeable, so all segments land in output partition 0 (globally
        deduplicated across input partitions — strictly better reduction
        than per-partition partials); other partitions emit empty. The
        program's three counts come in one fetch: the groups, the live rows
        and the row slots it ordered them at (RunStats, and on `span`)."""
        jax = ensure_jax()
        schema = self.schema()
        key_meta = meta["key_meta"]
        n_keys = len(key_meta)
        n_keyops = sum(2 if km[3] else 1 for km in key_meta)
        C = meta["C"]
        with RUN_STATS.span("bt.device.fetch", what="count"):
            n, n_live, n_ordered, *n_masked = (int(x) for x in jax.device_get(outs[-1]))
        # the rows that picked the tier: where no multi-lane filter ran in
        # it, those every filter keeps
        n_masked = n_masked[0] if n_masked else n_live
        RUN_STATS.set("sorted_rows_live", n_live)
        RUN_STATS.set("sorted_rows_masked", n_masked)
        RUN_STATS.set("sorted_rows_ordered", n_ordered)
        RUN_STATS.set("sorted_groups", n)
        # the projection and its probes run where the ordering runs
        RUN_STATS.set("probe_rows_live", n_live)
        RUN_STATS.set("probe_rows", n_ordered)
        span.set(sorted_rows_live=n_live, sorted_rows_masked=n_masked,
                 sorted_rows_ordered=n_ordered, sorted_groups=n, sorted_capacity=C,
                 probe_rows_live=n_live, probe_rows=n_ordered)
        # n <= n_live <= C by construction (C = pow2 of the row slots)
        results = {p: [_empty_batch(schema)] for p in range(P)}
        if n == 0:
            return results
        pid_out = None
        data_outs = outs[:-1]
        if meta.get("emit_pid"):
            pid_out = data_outs[-1]
            data_outs = data_outs[:-1]
        cp = min(_pow2(n), C)  # sliced fetch: pay for actual groups only
        with RUN_STATS.span("bt.device.fetch", rows=n):
            host = jax.device_get([o[:cp] for o in data_outs])
            pid_host = jax.device_get(pid_out[:cp]) if pid_out is not None else None
        nullcnt_map = meta.get("nullcnt_map", {})
        n_aggs = len(meta["out"])
        ncnt_host = host[n_keyops + n_aggs:]
        arrays: list[pa.Array] = []
        pos = 0
        for (kind, scale, slot, has_null), f in zip(key_meta, schema):
            null_mask = None
            if has_null:
                null_mask = host[pos][:n] != 0
                pos += 1
            kv = host[pos]
            pos += 1
            vals = kv[:n]
            if kind == "code":
                # resolve the LIVE dictionary (compilations are shared across
                # tables with equal shapes; contents are per-table)
                if isinstance(slot, tuple) and slot[0] == "build":
                    dic = build_dicts[slot[1]][slot[2]]
                else:
                    dic = dicts[slot]
                arr = decode_codes(vals, dic, null_mask, f.type)
            elif kind == "date":
                arr = pa.array(vals.astype(np.int32), pa.int32(), mask=null_mask).cast(pa.date32())
            elif kind == "money":
                arr = pa.array(vals.astype(np.float64) / (10**scale), pa.float64(),
                               mask=null_mask)
            else:
                arr = pa.array(vals, mask=null_mask)
            if arr.type != f.type:
                arr = arr.cast(f.type)
            arrays.append(arr)
        for ai, (out, (kind, scale), f) in enumerate(
            zip(host[n_keyops:n_keyops + n_aggs], meta["out"], list(schema)[n_keys:])
        ):
            vals = out[:n]
            null_mask = None
            if ai in nullcnt_map:
                # all of the group's agg inputs were NULL → the agg is NULL
                null_mask = ncnt_host[nullcnt_map[ai]][:n] == 0
            if kind == "money":
                arr = pa.array(vals.astype(np.float64) / (10**scale), pa.float64(),
                               mask=null_mask)
            elif kind == "date":
                arr = pa.array(vals.astype(np.int32), pa.int32(), mask=null_mask).cast(pa.date32())
            else:
                arr = pa.array(vals, mask=null_mask)
            if arr.type != f.type:
                arr = arr.cast(f.type)
            arrays.append(arr)
        if pid_host is not None:
            # device-routed shuffle: ship the partition ids alongside; the
            # shuffle writer consumes and drops the __pid column
            arrays.append(pa.array(pid_host[:n].astype(np.int32), pa.int32()))
            out_schema = pa.schema(list(schema) + [pa.field("__pid", pa.int32())])
            self.pid_emitted += 1
            results[0] = [pa.RecordBatch.from_arrays(arrays, schema=out_schema)]
            return results
        results[0] = [pa.RecordBatch.from_arrays(arrays, schema=schema)]
        return results

    def _decode_all(self, outs: list[np.ndarray], meta: dict, P: int, dicts,
                    build_dicts: list | None = None) -> dict[int, list[pa.RecordBatch]]:
        agg = self.partial_agg
        schema = self.schema()
        group_dicts = []
        for s in meta["group_src_slots"]:
            if isinstance(s, tuple) and s[0] == "build":
                group_dicts.append(build_dicts[s[1]][s[2]])
            else:
                group_dicts.append(dicts[s])
        presence = outs[-1]  # [P, G]
        n_aggs = len(meta["out"])
        nullcnt_map = meta.get("nullcnt_map", {})
        nullcnt_outs = outs[n_aggs:-1]
        results: dict[int, list[pa.RecordBatch]] = {}
        n_group = len(agg.group_exprs)
        for p in range(P):
            sel = np.nonzero(presence[p] > 0)[0]
            if not len(sel):
                results[p] = [_empty_batch(schema)]
                continue
            arrays: list[pa.Array] = []
            gid = sel.astype(np.int64)
            comps = []
            for psz in reversed(meta["pad_sizes"]):
                comps.append(gid % psz)
                gid = gid // psz
            comps = list(reversed(comps))
            for comp, d, f in zip(comps, group_dicts, schema):
                arrays.append(pa.array([d[int(c)] for c in comp], f.type))
            for ai, (out, (kind, scale), f) in enumerate(
                zip(outs[:n_aggs], meta["out"], list(schema)[n_group:])
            ):
                vals = out[p][sel]
                null_mask = None
                if ai in nullcnt_map:
                    # all agg inputs in the group were NULL → the agg is NULL
                    null_mask = nullcnt_outs[nullcnt_map[ai]][p][sel] == 0
                if kind == "money":
                    arr = pa.array(vals.astype(np.float64) / (10**scale), pa.float64(),
                                   mask=null_mask)
                elif kind == "date":
                    arr = pa.array(vals.astype(np.int32), pa.int32(), mask=null_mask).cast(pa.date32())
                else:
                    arr = pa.array(vals, mask=null_mask)
                if arr.type != f.type:
                    arr = arr.cast(f.type)
                arrays.append(arr)
            results[p] = [pa.RecordBatch.from_arrays(arrays, schema=schema)]
        return results


def _put(mesh, arr, spec=None):
    """Place an array for stage execution: mesh-sharded/replicated under a
    mesh, plain device array otherwise. The single place that decides
    placement (memory kind, donation would go here) — which makes it the
    single place chaos hbm_oom can fault an upload."""
    hbm.maybe_chaos_oom()
    jax = ensure_jax()
    if mesh is None:
        return jax.numpy.asarray(arr)
    from jax.sharding import NamedSharding, PartitionSpec

    return jax.device_put(arr, NamedSharding(mesh, spec if spec is not None else PartitionSpec()))


def _put_chunked(mesh, arr, spec=None, chunk_rows: int = 0):
    """Upload a [P, N] stack in row chunks along N. Each device_put is
    async, so chunk k+1's host slice is cut while chunk k streams — the
    double-buffered form of the column upload; the device-side concatenate
    reassembles the full stack in HBM where bandwidth is cheap. Mesh-sharded
    puts stay whole (GSPMD owns their layout), as do 1-D arrays and columns
    smaller than one chunk."""
    if (mesh is not None or chunk_rows <= 0 or getattr(arr, "ndim", 0) != 2
            or arr.shape[1] <= chunk_rows):
        return _put(mesh, arr, spec)
    jax = ensure_jax()
    parts = [
        jax.device_put(np.ascontiguousarray(arr[:, o:o + chunk_rows]))
        for o in range(0, arr.shape[1], chunk_rows)
    ]
    return jax.numpy.concatenate(parts, axis=1)


def _stage_mesh(config: BallistaConfig):
    """1-D mesh over the partition axis when collective exchange is on and
    more than one accelerator is visible: the stage kernel's inputs shard
    by partition and XLA/GSPMD inserts the ICI collectives (psum-style
    merges, gather for the compacted outputs) — the collective form of the
    file shuffle for co-scheduled stages (SURVEY.md §2.5 TPU-native
    equivalent). One executor process drives the whole slice."""
    from ballista_tpu.config import TPU_COLLECTIVE_EXCHANGE

    if not bool(config.get(TPU_COLLECTIVE_EXCHANGE)):
        return None
    jax = ensure_jax()
    devs = jax.devices()
    if len(devs) < 2:
        return None
    from jax.sharding import Mesh

    return Mesh(np.array(devs), ("part",))


def _masked_reduce_w(jnp, v, gm, func: str, m_eff):
    """Weighted reduction for aggregate-through-join: each probe row stands
    in for m_eff joined rows (match count; max(count, 1) under outer)."""
    if func == "count_all":
        return jnp.where(gm, m_eff, 0).astype(jnp.int64).sum(axis=1)
    if func == "count":
        m2 = gm if (v is None or v.valid is None) else gm & v.valid
        return jnp.where(m2, m_eff, 0).astype(jnp.int64).sum(axis=1)
    if func == "sum":
        arr = v.arr
        if v.valid is not None:
            gm = gm & v.valid
        scaled = arr * m_eff.astype(arr.dtype)
        zero = jnp.zeros((), dtype=arr.dtype)
        return jnp.where(gm, scaled, zero).sum(axis=1)
    # min/max are multiplicity-invariant (w==0 rows are filtered for inner
    # joins; under outer every probe row legitimately appears)
    return _masked_reduce(jnp, v, gm, func)


def _masked_reduce(jnp, v, gm, func: str):
    """One group's reduction over axis=1 of [P, N] lanes. SQL null-skipping:
    an agg input's validity plane joins the group mask — count(x) counts
    only non-null x, sum/min/max ignore null slots."""
    if func == "count_all" or (func == "count" and (v is None or v.valid is None)):
        return gm.sum(axis=1).astype(jnp.int64)
    if func == "count":
        return (gm & v.valid).sum(axis=1).astype(jnp.int64)
    arr = v.arr
    if v.valid is not None:
        gm = gm & v.valid
    if func == "sum":
        zero = jnp.zeros((), dtype=arr.dtype)
        return jnp.where(gm, arr, zero).sum(axis=1)
    if func in ("welford_mean", "welford_m2"):
        # variance partials (physical_planner's (cnt, mean, M2) triple): the
        # true two-pass form — group mean first, then the mean-centered
        # square sum — numerically stable at f64 with no Welford recurrence
        # (which would serialize; this stays two fused VPU passes)
        c = gm.sum(axis=1)
        s = jnp.where(gm, arr, 0.0).sum(axis=1)
        mean = s / jnp.maximum(c, 1)
        if func == "welford_mean":
            return mean
        d2 = (arr - mean[:, None]) ** 2
        return jnp.where(gm, d2, 0.0).sum(axis=1)
    if func == "min":
        big = jnp.iinfo(arr.dtype).max if jnp.issubdtype(arr.dtype, jnp.integer) else jnp.inf
        return jnp.where(gm, arr, big).min(axis=1)
    if func == "max":
        small = jnp.iinfo(arr.dtype).min if jnp.issubdtype(arr.dtype, jnp.integer) else -jnp.inf
        return jnp.where(gm, arr, small).max(axis=1)
    raise Unsupported(f"agg {func}")


def _tier_capacities(slots: int, divisors) -> list[int]:
    """The capacities, ascending, a stage's live rows may take of `slots`:
    `slots` over each divisor, then all of them."""
    return sorted({-(-slots // d) for d in divisors} - {slots}) + [slots]


def _pow2(n: int) -> int:
    p = 1
    while p < max(n, 1):
        p *= 2
    return p


def _scoped(name: str, fn):
    """`fn` traced under `jax.named_scope(name)`. Metadata only: every device
    operation then says which operator span of the stage it came from
    (`scan_decode`, `filter`, `project`, `join_probe_<i>`, `partial_agg`,
    `sorted_agg`, `emit`), in the profiler's trace and in HLO dumps."""

    def run(*args):
        with ensure_jax().named_scope(name):
            return fn(*args)

    return run


def _mk_col_reader(i: int, kind: str, scale: int, dictionary, valid_idx=None):
    """Column reader with device-side upcast: columns ship narrow (int16/32)
    to spare the link, then widen in HBM where bandwidth is cheap. Nullable
    columns read their validity plane from the flattened arg tail."""

    def run(cols, luts):
        import jax.numpy as jnp

        arr = cols[i]
        if kind in ("i64", "money") and arr.dtype != jnp.int64:
            arr = arr.astype(jnp.int64)
        elif kind == "code" and arr.dtype != jnp.int32:
            arr = arr.astype(jnp.int32)
        elif kind == "date" and arr.dtype != jnp.int32:
            arr = arr.astype(jnp.int32)
        valid = cols[valid_idx] if valid_idx is not None else None
        return DevVal(kind, arr, scale, dictionary, valid=valid)

    return run


def _note_match_lanes(meta: dict, rec: dict, span, add: bool = False) -> None:
    """RunStats `match_lanes`, `tier_lanes` and `match_lane_slots` of a
    decoded dispatch, on `rec` and on its `bt.stage.dispatch` span: the slots
    by the tier the dispatch took, which its `probe_rows` says; `add` sums
    them over the dispatches of a grace split."""
    slots = meta["lane_slots"].get(rec.get("probe_rows"), 0)
    if add:
        slots += rec.get("match_lane_slots", 0)
    lanes = {"match_lanes": meta["match_lanes"], "tier_lanes": meta["tier_lanes"],
             "match_lane_slots": slots}
    rec.update(lanes)
    if span is not None:
        span.set(**lanes)


def _set_lanes(lane_cells: list, lane, found=()) -> None:
    """Point the joins' closures at a row set, at trace time: each join's
    match lane `d` (an int; an int32 a row where the sorted path has compacted
    several lanes' rows into one set) and, where the row set is one its
    finder has already run over — the slots as they are, at the top tier —
    what it `found` there, so the payload gathers behind reuse the lookup
    instead of issuing an `M`-row gather of their own. Each finder leaves its
    `last` result in its cell."""
    for cell, d_, f in itertools.zip_longest(lane_cells, lane, found):
        cell.update(d=d_, found=f, last=None)


class _LaneLog:
    """The join lookups a stage program issues, noted while it is traced (no
    device work): each one a join's match lane over a row set, under `at` —
    None over every slot, else the capacity of the live-row tier being
    traced. `slots(at)` is RunStats `match_lane_slots` for a dispatch that
    took that tier: the rows of the lookups over every slot, and of those
    the tier issues over its own rows (again, or the first time for a filter
    of several lanes on the sorted path). A lookup traced twice over one
    row set (a match and its payload gather) is one lookup: XLA CSEs it."""

    def __init__(self):
        self.at = None
        self._seen: dict = {}

    def note(self, join: int, lane, rows: int) -> None:
        # a lane that is an int32 a row (the sorted path's compacted lanes)
        # is every lane of the join at once: one lookup over those rows
        self._seen.setdefault(self.at, set()).add(
            (join, lane if isinstance(lane, int) else -1, rows))

    def slots(self, at) -> int:
        seen = self._seen.get(None, set())
        if at is not None:
            seen = seen | self._seen.get(at, set())
        return sum(rows for _, _, rows in seen)


def _mk_join_finder(off: int, probe_fns, bt: BuildTable, cell: dict, lookups: _LaneLog):
    """Closure computing (clamped build index, matched mask) for one join.

    'direct' unique mode: the build shipped a dense key→row int32 table —
    ONE gather per probe (the TPU-friendly hash table: identity hash, no
    collisions by construction). 'direct' expansion mode (dup > 1): lo/cnt
    tables; the probe's match lane d (`cell["d"]`, set by the lane loop at
    trace time) selects row lo+d, matched iff d < cnt. 'sorted' mode:
    binary search over sorted keys with an int64.max tail (two searches
    when expansion). Multi-key probes combine as k1 << shift | k2 with
    device range guards mirroring the host-side guards, so out-of-range
    keys can never alias a real build key. Shape-generic in `cols`: the
    stage calls it over every row slot where its match is the filter, and
    again over the live rows' compacted scan columns for the payload
    gathers behind it (a lookup of its own there, `cap` rows and not `M`;
    a filter of several lanes on the sorted path looks up there alone);
    the lookups the per-column gathers of ONE row set issue are duplicates
    XLA CSEs. The match lane is an int, or an int32 a row where the sorted
    path has compacted several lanes' rows into one set. Each lookup is
    noted in `lookups`.
    """
    mode, shifts, dup = bt.mode, bt.shifts, bt.dup
    has_cnt = bt.cnt is not None
    b_static = bt.padded_rows()  # in shape_key, so cache hits can't go stale

    def find(cols, luts):
        import jax.numpy as jnp

        keys_arr = cols[off]
        valid = None
        k = None
        for i, pf in enumerate(probe_fns):
            v = pf(cols, luts)
            if v.kind not in ("i64", "date"):
                raise Unsupported(f"non-integer probe key kind {v.kind}")
            ki = v.arr.astype(jnp.int64)
            if i == 0:
                k = ki
                valid = ki >= 0
            else:
                shift = shifts[i - 1]
                valid = valid & (ki >= 0) & (ki < (1 << shift))
                k = (k << shift) | ki
            if v.valid is not None:
                valid = valid & v.valid  # a NULL probe key matches nothing
        d = cell["d"]
        lookups.note(off, d, k.size)
        if mode == "direct" and not has_cnt:
            T = keys_arr.shape[0]
            in_range = valid & (k >= 0) & (k < T)
            row = keys_arr[jnp.where(in_range, k, 0)]
            matched = in_range & (row >= 0)
            idxc = jnp.clip(row, 0, None).astype(jnp.int32)
            return idxc, DevVal("bool", matched)
        if mode == "direct":
            T = keys_arr.shape[0]
            in_range = valid & (k >= 0) & (k < T)
            kc = jnp.where(in_range, k, 0)
            lo = keys_arr[kc]
            c = cols[off + 1][kc]
            matched = in_range & (d < c)
            idxc = jnp.clip(lo + d, 0, b_static - 1).astype(jnp.int32)
            return idxc, DevVal("bool", matched)
        if dup == 1:
            idx = jnp.searchsorted(keys_arr, k)
            idxc = jnp.clip(idx, 0, keys_arr.shape[0] - 1)
            matched = (keys_arr[idxc] == k) & valid
            return idxc, DevVal("bool", matched)
        lo = jnp.searchsorted(keys_arr, k, side="left")
        hi = jnp.searchsorted(keys_arr, k, side="right")
        matched = valid & (lo + d < hi)
        idxc = jnp.clip(lo + d, 0, keys_arr.shape[0] - 1).astype(jnp.int32)
        return idxc, DevVal("bool", matched)

    return _found_once(find, cell)


def _found_once(find, cell: dict):
    """`find(cols, luts)` unless the cell already holds what it found over
    this row set (`_set_lanes`)."""

    def run(cols, luts):
        if cell.get("found") is None:
            cell["last"] = find(cols, luts)
            return cell["last"]
        return cell["found"]

    return run


def _mult_shape_check(partial_agg, ops, join) -> dict | None:
    """Structural eligibility for aggregate-through-join: `join` must be the
    stage's LAST join (only pass-through projections may follow), inner or
    right with no residual filter, group keys probe-side, and every
    build-column use a bare count(col). Returns {agg index → build field
    index} (may be empty) or None if ineligible. Shared by _prepare_build
    (to exempt such joins from the dup-lane cap) and _compile (to activate
    the weight path)."""
    from ballista_tpu.plan.physical import HashJoinExec, ProjectionExec

    real_ops = [o for o in ops if not isinstance(o, CoalesceBatchesExec)]
    if join not in real_ops:
        return None
    if join.join_type not in ("inner", "right") or join.filter is not None:
        return None
    k = real_ops.index(join)
    n_build = len(join.left.df_schema)
    schema = join.df_schema
    # per current-schema field: originating build field index, or None
    build_of: list = [i if i < n_build else None for i in range(len(schema))]

    def refs_build(e) -> list[int]:
        refs: list[int] = []

        def walk(x):
            if isinstance(x, Column):
                i = schema.maybe_index_of(x.name, x.qualifier)
                if i is not None and build_of[i] is not None:
                    refs.append(build_of[i])
            for c in x.children():
                walk(c)

        walk(e)
        return refs

    for op in real_ops[k + 1:]:
        if not isinstance(op, ProjectionExec):
            return None  # a later join/filter may consume build values
        new_build: list = []
        for e in op.exprs:
            inner = e.expr if isinstance(e, Alias) else e
            if isinstance(inner, Column):
                i = schema.maybe_index_of(inner.name, inner.qualifier)
                if i is None:
                    return None
                new_build.append(build_of[i])
            else:
                if refs_build(inner):
                    return None  # computed expr over a build column
                new_build.append(None)
        schema = op.df_schema
        build_of = new_build

    for g in partial_agg.group_exprs:
        if refs_build(g.expr if isinstance(g, Alias) else g):
            return None
    out: dict[int, int] = {}
    for ai, d in enumerate(partial_agg.aggs):
        if d.expr is None:
            continue
        brefs = refs_build(d.expr)
        if not brefs:
            continue
        inner_e = d.expr.expr if isinstance(d.expr, Alias) else d.expr
        if d.func == "count" and isinstance(inner_e, Column) and len(brefs) == 1:
            out[ai] = brefs[0]
        else:
            return None
    return out


def _mk_join_counter(off: int, probe_fns, bt: BuildTable, cell: dict, lookups: _LaneLog):
    """Closure computing each probe row's MATCH COUNT against the build —
    the aggregate-through-join weight. Where every build-column use in the
    stage is multiplicity-shaped (count(col), count(*), probe-side sums),
    gathering the count replaces dup-lane unrolling entirely: one gather
    instead of dup traced pipelines, and no MAX_JOIN_DUP ceiling."""
    mode, shifts = bt.mode, bt.shifts
    has_cnt = bt.cnt is not None

    def count(cols, luts):
        import jax.numpy as jnp

        keys_arr = cols[off]
        valid = None
        k = None
        for i, pf in enumerate(probe_fns):
            v = pf(cols, luts)
            if v.kind not in ("i64", "date"):
                raise Unsupported(f"non-integer probe key kind {v.kind}")
            ki = v.arr.astype(jnp.int64)
            if i == 0:
                k = ki
                valid = ki >= 0
            else:
                shift = shifts[i - 1]
                valid = valid & (ki >= 0) & (ki < (1 << shift))
                k = (k << shift) | ki
            if v.valid is not None:
                valid = valid & v.valid
        lookups.note(off, 0, k.size)
        zero = jnp.zeros((), jnp.int32)
        if mode == "direct" and has_cnt:
            T = keys_arr.shape[0]
            in_range = valid & (k >= 0) & (k < T)
            kc = jnp.where(in_range, k, 0)
            return jnp.where(in_range, cols[off + 1][kc], zero)
        if mode == "direct":
            T = keys_arr.shape[0]
            in_range = valid & (k >= 0) & (k < T)
            row = keys_arr[jnp.where(in_range, k, 0)]
            return jnp.where(in_range & (row >= 0), 1, zero).astype(jnp.int32)
        lo = jnp.searchsorted(keys_arr, k, side="left")
        hi = jnp.searchsorted(keys_arr, k, side="right")
        return jnp.where(valid, (hi - lo).astype(jnp.int32), zero)

    return _found_once(count, cell)


def _mk_raising(msg: str):
    def run(cols, luts):
        raise Unsupported(msg)

    return run


def _mk_build_gather(pay_off: int, ci: int, kind: str, scale: int, dictionary, finder,
                     valid_abs_idx=None, outer=False):
    """Gather one build-payload column through the join finder. Nullable
    payloads gather their validity plane too; under an outer join the gather
    of an UNMATCHED probe row is NULL (valid = matched & payload-valid)."""

    def run(cols, luts):
        import jax.numpy as jnp

        idxc, matched = finder(cols, luts)
        arr = cols[pay_off + ci][idxc]
        if kind in ("i64", "money") and arr.dtype != jnp.int64:
            arr = arr.astype(jnp.int64)
        elif kind in ("code", "date") and arr.dtype != jnp.int32:
            arr = arr.astype(jnp.int32)
        valid = cols[valid_abs_idx][idxc] if valid_abs_idx is not None else None
        if outer:
            m = true_mask(matched)
            valid = m if valid is None else valid & m
        return DevVal(kind, arr, scale, dictionary, valid=valid)

    return run


def _bind_env(ctx: Lowering, schema: DFSchema) -> None:
    """Point the Lowering at the current virtual schema: Column exprs now
    resolve through env_fns (projection rebinding) instead of raw columns."""
    ctx.schema = schema
    ctx.kinds = [
        (m[0], m[1]) if m is not None else ("?", 0) for m in ctx.env_meta
    ]
    ctx.dictionaries = [m[2] if m is not None else None for m in ctx.env_meta]
    ctx.slots = [m[3] if m is not None else -1 for m in ctx.env_meta]

    def col_index(c):
        return schema.index_of(c.name, c.qualifier)

    ctx.col_index = col_index  # type: ignore[assignment]


def _passthrough_meta(e: Expr, ctx: Lowering, schema: DFSchema):
    inner = e.expr if isinstance(e, Alias) else e
    if isinstance(inner, Column):
        i = schema.index_of(inner.name, inner.qualifier)
        return ctx.env_meta[i]
    return None
