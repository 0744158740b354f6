"""Device execution for final-aggregation / sort / top-K stage shapes.

The reference engine executes EVERY stage of a query
(ballista/executor/src/execution_engine.rs:51); round 2 of this build
lowered only partial-aggregation chains to the device. This module lowers
the stage class that sits ABOVE the shuffle: merge the hash-partitioned
partial accumulators in HBM, apply the post-aggregation projections and
HAVING filters, and run ORDER BY (+ LIMIT) through one lexicographic
ordering permutation (`kernels.lex_order`) — so a q3-class stage fetches 10
rows back to the host instead of millions.

Stage shape handled (top-down):

    [SortExec(fetch?)]  [ProjectionExec|FilterExec]*  HashAggregateExec(final)
        [CoalesceBatchesExec|CoalescePartitionsExec]*  <child>

Execution model (same contract as TpuStageExec): the whole stage — all
partitions — runs as ONE device dispatch. Input partitions stack to a
[P, N] device layout; partition id rides as the leading sort key so
per-partition grouping and per-partition top-K happen inside a single
compiled program; the fetch returns only surviving rows. The final-mode
merge semantics mirror HashAggregateExec (plan/physical.py:535): sum/count
partials add, min/max partials re-reduce, NULL accumulators are skipped
and an all-NULL group decodes to NULL.

Fallback is runtime-adaptive like the partial path: unencodable inputs,
welford triples, a working set past the HBM budget, or tiny inputs re-run
the original CPU subtree; `match_final_stage` pre-lowers every expression
at plan time with static kinds so stages that CANNOT lower are never
wrapped (the device/fallback counters in EXPLAIN ANALYZE stay honest).
"""

from __future__ import annotations

import functools
import threading
import zlib
from typing import Iterator

import numpy as np
import pyarrow as pa

from ballista_tpu.config import TPU_MAX_DEVICE_BYTES, TPU_MIN_ROWS, BallistaConfig, _env_int
from ballista_tpu.ops.tpu.columnar import decode_codes, encode_column, next_bucket
from ballista_tpu.ops.tpu.stage_compiler import RUN_STATS, STAGE_OUTCOMES, LruDict
from ballista_tpu.ops.tpu.kernels import (
    BelowRowFloor,
    DevVal,
    Lowering,
    SegmentCompaction,
    Unsupported,
    int_cumsum,
    lex_order,
    lower_expr,
    segmented_scan,
    true_mask,
)
from ballista_tpu.ops.tpu.runtime import ensure_jax
from ballista_tpu.plan.expressions import Alias, Column, SortKey
from ballista_tpu.plan.physical import (
    CoalesceBatchesExec,
    CoalescePartitionsExec,
    ExecutionPlan,
    FilterExec,
    HashAggregateExec,
    ProjectionExec,
    SortExec,
    TaskContext,
    _concat,
    _empty_batch,
)

# bounded: long-lived executors see one entry per (stage fingerprint, shape)
# and would otherwise grow without limit (stage_compiler's LruDict is
# import-safe here: stage_compiler only imports this module lazily)
_FINAL_COMPILE_CACHE = LruDict(_env_int("BALLISTA_TPU_FINAL_CACHE_ENTRIES", 64))
_FINAL_COMPILE_LOCK = threading.Lock()


def clear_compile_cache() -> None:
    with _FINAL_COMPILE_LOCK:
        _FINAL_COMPILE_CACHE.clear()


def match_final_stage(node: ExecutionPlan):
    """Match the final-stage shape rooted at `node`; return
    (sort, post_ops top-down, agg, child, coalesce) or None. Conservative:
    only matches when every expression trial-lowers with static kinds, so a
    wrapped stage falls back only on genuinely runtime conditions."""
    sort = None
    cur = node
    if isinstance(cur, SortExec):
        sort = cur
        cur = cur.input
    post_ops: list[ExecutionPlan] = []
    while isinstance(cur, (ProjectionExec, FilterExec, CoalesceBatchesExec)):
        post_ops.append(cur)
        cur = cur.children()[0]
    if not isinstance(cur, HashAggregateExec) or cur.mode != "final":
        return None
    agg = cur
    if not agg.group_exprs:
        # global merges are a handful of rows — nothing for the device
        return None
    child = agg.input
    coalesce = False
    while isinstance(child, (CoalesceBatchesExec, CoalescePartitionsExec)):
        if isinstance(child, CoalescePartitionsExec):
            coalesce = True
        child = child.children()[0]
    if not _trial_lowerable(sort, post_ops, agg):
        return None
    return sort, post_ops, agg, child, coalesce


def _static_kind(t: pa.DataType):
    """Conservative (kind, scale) for trial lowering from an Arrow type.
    float64 is guessed f64 — the money refinement only changes arithmetic
    scales at runtime, never lowerability."""
    if pa.types.is_integer(t):
        return ("i64", 0)
    if pa.types.is_date(t):
        return ("date", 0)
    if pa.types.is_boolean(t):
        return ("bool", 0)
    if pa.types.is_floating(t):
        return ("f64", 0)
    if pa.types.is_string(t) or pa.types.is_large_string(t) or pa.types.is_dictionary(t):
        return ("code", 0)
    return None


def _lower_chain(ctx: Lowering, sort, post_ops):
    """The ONE lowering walk shared by the plan-time matcher and the
    runtime compiler (so they cannot drift): rebinds the env through
    projections, collects filter predicates, and lowers the sort keys with
    their code→lexicographic-rank LUTs. Raises Unsupported when any piece
    cannot lower. Returns (keep_fns, sort_specs)."""
    from ballista_tpu.ops.tpu.stage_compiler import _bind_env, _passthrough_meta

    cur_schema = ctx.schema
    keep_fns: list = []
    for op in reversed(post_ops):
        if isinstance(op, ProjectionExec):
            new_fns, new_meta = [], []
            for e in op.exprs:
                new_fns.append(lower_expr(e, ctx))
                new_meta.append(_passthrough_meta(e, ctx, cur_schema))
            ctx.env_fns, ctx.env_meta = new_fns, new_meta
            cur_schema = op.df_schema
            _bind_env(ctx, cur_schema)
        elif isinstance(op, FilterExec):
            keep_fns.append(lower_expr(op.predicate, ctx))
        # CoalesceBatchesExec: no-op

    sort_specs: list = []  # (fn, ascending, nulls_first, rank_lut_idx|None)
    if sort is not None:
        for k in sort.keys:
            kf = lower_expr(k.expr, ctx)
            m = _passthrough_meta(k.expr, ctx, cur_schema)
            lut_idx = None
            if m is not None and m[0] == "code":
                # dictionary codes are appearance-ordered, not collated:
                # sort through a host-built code→lexicographic-rank LUT
                if m[3] is None or not isinstance(m[3], int) or m[3] < 0:
                    raise Unsupported("string sort key without a slot")

                def rank_builder(dic):
                    ranks = np.zeros(max(len(dic or []), 1), dtype=np.int32)
                    if dic:
                        order = sorted(range(len(dic)), key=lambda j: dic[j])
                        for r, j in enumerate(order):
                            ranks[j] = r
                    return ranks

                lut_idx = ctx.add_lut(m[3], rank_builder)
            sort_specs.append((kf, k.ascending, k.nulls_first, lut_idx))
    return keep_fns, sort_specs


def _trial_lowerable(sort, post_ops, agg) -> bool:
    """Dry-run the shared lowering walk with static kinds. Lowered closures
    are never CALLED, so dummy readers suffice; Unsupported → False."""
    for d in agg.aggs:
        if d.func not in ("sum", "min", "max", "count", "count_all"):
            return False  # welford triples merge on cpu (round-3 scope)
    kinds: list = []
    for f in agg.df_schema:
        k = _static_kind(f.dtype)
        if k is None:
            return False
        # float group keys are allowed statically: TPC money columns refine
        # to exact scaled-int "money" at encode time; a key that stays true
        # f64 is rejected at runtime (falls back, honestly counted)
        kinds.append(k)
    try:
        ctx = Lowering(agg.df_schema, kinds, [[] if k[0] == "code" else None for k in kinds])
        ctx.env_fns = [lambda cols, luts: None] * len(kinds)
        ctx.env_meta = [
            (k[0], k[1], [] if k[0] == "code" else None, i) for i, k in enumerate(kinds)
        ]
        from ballista_tpu.ops.tpu.stage_compiler import _bind_env

        _bind_env(ctx, agg.df_schema)
        _lower_chain(ctx, sort, post_ops)
    except Unsupported:
        return False
    return True


class TpuFinalStageExec(ExecutionPlan):
    """One-dispatch device execution of a final-agg/sort stage (see module
    docstring). Counters (device_runs / cpu_fallbacks) surface in EXPLAIN
    ANALYZE exactly like TpuStageExec's."""

    own_span = True  # `bt.stage.dispatch` and the spans inside it

    def __init__(self, sort, post_ops: list, agg: HashAggregateExec,
                 child: ExecutionPlan, config: BallistaConfig, coalesce: bool = False):
        top = sort if sort is not None else (post_ops[0] if post_ops else agg)
        super().__init__(top.df_schema)
        self.sort = sort
        self.post_ops = post_ops  # top-down Projection/Filter/CoalesceBatches
        self.agg = agg
        self.child = child
        self.config = config
        self.coalesce = coalesce  # True: all input partitions merge into one
        self.min_rows = int(config.get(TPU_MIN_ROWS))
        self.buckets = config.shape_buckets()
        self.tpu_count = 0
        self.fallback_count = 0
        self._results: dict[int, list[pa.RecordBatch]] | None = None
        self._results_lock = threading.Lock()
        self._device_ok = False
        # child output materialized by a device attempt that then declined:
        # (tables, child df_schema, merged?) — the CPU fallback aggregates
        # THESE instead of re-executing the whole child subtree
        self._mat_input: tuple | None = None
        self._mat_node = None
        # fallback partitions already served off the materialized copy; once
        # every expected partition (`_mat_expected`) has been read the copy is
        # dropped (it can pin the stage's whole input on the host otherwise)
        self._mat_served: set[int] = set()
        self._mat_expected: set[int] = set()
        self._mat_released_merged = False
        # partitions served since the last (re-)dispatch — see
        # _note_served_locked for the re-run retention bound
        self._served_since_dispatch: set[int] = set()
        parts = [op.node_str() for op in ([sort] if sort else []) + post_ops]
        self.fingerprint = "|".join(
            parts + [agg.node_str(), repr(agg.input.df_schema), f"coalesce={coalesce}"]
        )

    def children(self) -> list[ExecutionPlan]:
        return [self.child]

    def with_children(self, c):
        return TpuFinalStageExec(self.sort, self.post_ops, self.agg, c[0],
                                 self.config, self.coalesce)

    def output_partition_count(self) -> int:
        return 1 if self.coalesce else self.child.output_partition_count()

    def node_str(self) -> str:
        extra = ""
        if self.tpu_count or self.fallback_count:
            extra = f" device_runs={self.tpu_count} cpu_fallbacks={self.fallback_count}"
        s = f" sort={self.sort.node_str()}" if self.sort is not None else ""
        return (f"TpuFinalStageExec: [{self.agg.node_str()}]"
                f" post_ops={len(self.post_ops)}{s}{extra}")

    def execute(self, partition: int, ctx: TaskContext) -> Iterator[pa.RecordBatch]:
        return self._timed(iter(self._run(partition, ctx)))

    # ------------------------------------------------------------------

    def _run(self, partition: int, ctx: TaskContext) -> list[pa.RecordBatch]:
        import logging

        with self._results_lock:
            if self._results is None:
                # protected-surface routing (docs/device_daemon.md): ship
                # the whole final-merge stage to the warm daemon first; the
                # route's failure domain (crash retry, poison quarantine)
                # demotes to the local attempt below by returning None
                routed = self._daemon_run_all(ctx)
                if routed is not None:
                    self._results = routed
                    STAGE_OUTCOMES.note("final", "device", "daemon-routed")
                    self.tpu_count += 1
                    self._device_ok = True
                    self._mat_input = None
            if self._results is None:
                try:
                    self._results = self._device_run(ctx, partition)
                    self._mat_input = None  # success: release the host copy
                except Unsupported as e:
                    logging.getLogger(__name__).info(
                        "tpu final-stage fallback (%s): %s", e, self.agg.node_str())
                    STAGE_OUTCOMES.note_fallback("final", e)
                    self._results = {}
                except Exception as e:  # noqa: BLE001 — classified below
                    self._results = {}
                    from ballista_tpu.config import TPU_HBM_SPILL_ENABLED
                    from ballista_tpu.ops.tpu import hbm
                    from ballista_tpu.ops.tpu import stage_compiler as _sc

                    if hbm.is_resource_exhausted(e):
                        # runtime OOM rung, final-stage edition: free the
                        # device (spilling residents to host) and retry ONCE
                        # on device before the CPU demotion — the retry
                        # re-reads the child, which the decline contract
                        # already permits (see _fallback's re-read branch)
                        logging.getLogger(__name__).warning(
                            "final stage RESOURCE_EXHAUSTED; spilling + "
                            "retrying once: %s", e)
                        spill_pool = (
                            hbm.SPILL_POOL
                            if bool(self.config.get(TPU_HBM_SPILL_ENABLED))
                            else None)
                        _sc.DEVICE_CACHE.spill_all(spill_pool)
                        hbm.note_oom(self.fingerprint)
                        hbm.consume_oom_hint(self.fingerprint)  # no grace rung here
                        try:
                            self._results = self._device_run(ctx, partition)
                            self._mat_input = None
                            _sc.RUN_STATS.set("hbm_oom_retries",
                                              hbm.oom_retry_count())
                        except Exception as e2:  # noqa: BLE001
                            logging.getLogger(__name__).warning(
                                "final stage OOM persisted after spill+retry; "
                                "falling back to cpu for %s",
                                self.agg.node_str(), exc_info=True)
                            STAGE_OUTCOMES.note_fallback("final", e2)
                            self._results = {}
                    else:
                        logging.getLogger(__name__).warning(
                            "tpu final stage raised; falling back to cpu for %s",
                            self.agg.node_str(), exc_info=True,
                        )
                        STAGE_OUTCOMES.note_fallback("final", e)
            if partition not in self._results and self._device_ok:
                # results were already consumed (a consumer re-executed this
                # partition); caches are hot, so re-running the device path
                # costs ~one dispatch — never a host re-aggregation
                try:
                    self._results.update(self._device_run(ctx, partition))
                    self._mat_input = None
                    self._served_since_dispatch = set()
                    # serve WITHOUT popping: one re-dispatch covers all K
                    # re-reads of an already-consumed result
                    if partition in self._results:
                        out = list(self._results[partition])
                        self._note_served_locked(partition)
                        return out
                except Exception as e:  # noqa: BLE001
                    logging.getLogger(__name__).warning(
                        "tpu final-stage re-run failed; cpu fallback for %s",
                        self.agg.node_str(), exc_info=True)
                    STAGE_OUTCOMES.note_fallback("final", e)
                    self._device_ok = False
            if partition in self._results:
                out = self._results.pop(partition)
                self._note_served_locked(partition)
                return out
        return self._fallback(partition, ctx)

    def _device_run(self, ctx: TaskContext, partition: int) -> dict[int, list[pa.RecordBatch]]:
        """One local device dispatch on the task's bound device, counted
        (call under _results_lock): of the whole stage, or of `partition`
        alone where the input is a shuffle's (`_tpu_run_all`)."""
        from ballista_tpu.ops.tpu.runtime import device_scope

        # a stage record of its own (`final_<crc>`) that holds what the
        # dispatch was sized by, and none of the keys the partial stages'
        # counters sum (`dispatches`, `exec_s`): the spans time it
        tag = f"final_{zlib.crc32(self.fingerprint.encode()):08x}"
        with device_scope(ctx.device_ordinal), RUN_STATS.run(tag, counted=False), \
                RUN_STATS.span("bt.stage.dispatch", family="final"):
            out = self._tpu_run_all(ctx, partition)
        STAGE_OUTCOMES.note("final", "device")
        self.tpu_count += 1
        self._device_ok = True
        return out

    def _note_served_locked(self, partition: int) -> None:
        """Bound re-run retention (call under _results_lock): when every
        still-resident result has been served at least once since the last
        dispatch, drop them all — they only exist for re-read convenience."""
        self._served_since_dispatch.add(partition)
        if self._results and set(self._results) <= self._served_since_dispatch:
            self._results = {}

    def _daemon_run_all(self, ctx: TaskContext):
        """Route the final-merge stage through the device daemon.
        unwrap_device_stages rebuilds the raw sort/post_ops/agg subtree
        from this wrapper (re-adding the CoalescePartitionsExec the
        matcher consumed), so the daemon re-derives the identical stage —
        byte parity and stable compile-cache keys by construction."""
        from ballista_tpu.ops.tpu import daemon_route

        return daemon_route.run_via_daemon(
            self.config,
            plan_builder=lambda: self,
            partitions=list(range(self.output_partition_count())),
            tag=daemon_route.stage_tag("final", self.fingerprint),
            fingerprint=self.fingerprint,
            est_bytes=int(getattr(self, "hbm_observed_input_bytes", 0) or 0))

    def _materialized_scan(self):
        """Build (once) a MemoryScanExec over the child output a declined
        device attempt already read, so the CPU fallback never re-executes
        the child subtree. Returns (scan, merged?) or None."""
        with self._results_lock:
            if self._mat_node is None and self._mat_input is not None:
                from ballista_tpu.plan.physical import MemoryScanExec

                tables, dfs, merged = self._mat_input
                batches = []
                for t in tables:
                    bs = t.combine_chunks().to_batches()
                    batches.append(bs[0] if bs else _empty_batch(t.schema))
                self._mat_node = (
                    MemoryScanExec(dfs, batches, partitions=len(batches)), merged)
                self._mat_input = None  # don't retain a second full copy
            return self._mat_node

    def _note_mat_served(self, partition: int, merged: bool) -> None:
        """Drop the materialized child copy once the LAST expected fallback
        partition has been served: merged/coalesced stages only ever serve
        partition 0; hash-placed stages every output partition the copy
        holds (one, where the task read its own alone)."""
        with self._results_lock:
            if self._mat_node is None:
                return
            self._mat_served.add(partition)
            if self._mat_served >= self._mat_expected:
                self._mat_node = None
                self._mat_served.clear()
                self._mat_released_merged = merged

    def _fallback(self, partition: int, ctx: TaskContext) -> list[pa.RecordBatch]:
        self.fallback_count += 1
        mat = self._materialized_scan()
        merged_mat = False
        if mat is not None:
            node, merged = mat
            merged_mat = merged
            if merged:
                # bypass-read input is NOT hash-placed: merge globally and
                # emit on partition 0 (the device bypass contract)
                if partition != 0 and not self.coalesce:
                    return []
                node = CoalescePartitionsExec(node)
                partition = 0
            elif self.coalesce:
                node = CoalescePartitionsExec(node)
        else:
            node = self.child
            if self._mat_released_merged:
                # the merged host copy was served and released; bypass-read
                # input is not hash-placed, so a late re-read must re-merge
                # the child globally and still emit only on partition 0
                if partition != 0 and not self.coalesce:
                    return []
                node = CoalescePartitionsExec(node)
                partition = 0
            elif self.coalesce:
                node = CoalescePartitionsExec(node)
        node = self.agg.with_children([node])
        for op in reversed(self.post_ops):
            node = op.with_children([node])
        if self.sort is not None:
            node = self.sort.with_children([node])
        with RUN_STATS.span("bt.stage.fallback", family="final"):
            out = [b for b in node.execute(partition, ctx)]
        if mat is not None:
            self._note_mat_served(partition, merged_mat)
        return out

    # ------------------------------------------------------------------

    def _tpu_run_all(self, ctx: TaskContext,
                     partition: int | None = None) -> dict[int, list[pa.RecordBatch]]:
        import concurrent.futures as fut

        from ballista_tpu.ops.tpu.stage_compiler import _pow2, _put
        from ballista_tpu.plan.physical import RepartitionExec
        from ballista_tpu.shuffle.reader import ShuffleReaderExec

        child = self.child
        P_result = self.output_partition_count()
        bypass = False
        if isinstance(child, RepartitionExec) and child.scheme == "hash":
            # the host hash-radix between partial and final agg is pure
            # overhead for this kernel: it re-groups globally anyway. Read
            # the repartition's input directly and emit the merged result
            # on output partition 0 (others empty) — the in-process form of
            # replacing the exchange with a device-side merge.
            #
            # CONTRACT (pinned by test_tpu_final_stage.py::
            # test_bypass_partitioning_contract): output_partition_count()
            # still advertises K, but rows do NOT follow the hash scheme —
            # they all land on partition 0. This is sound because no
            # consumer in this engine trusts declared hash placement:
            # partition-sensitive consumers (partitioned joins, repartition
            # writers) always get a FRESH RepartitionExec inserted above
            # them by the physical planner (physical_planner.py:556-558),
            # and everything else merges/concatenates partitions. A future
            # partitioning-property optimization that elides "redundant"
            # repartitions MUST exclude TpuFinalStageExec outputs.
            child = child.input
            bypass = True
        P_in = child.output_partition_count()
        # a served stage: its input is a shuffle's hash-placed partitions and
        # it goes out as a task a partition, each of which builds its own
        # node; output partition p merges input partition p and nothing else,
        # so a task reads and merges its own alone (the CPU engine's final
        # aggregate does the same) — never the whole stage once a task
        own = (partition is not None and not self.coalesce
               and isinstance(child, ShuffleReaderExec))
        parts = [partition] if own else list(range(P_in))

        # the session quota is thread-local (one-handler-thread-per-request
        # in the daemon); re-scope it on the pool threads or a daemon-routed
        # final stage would run its inner partials with no ceiling
        from ballista_tpu.ops.tpu import hbm
        quota = hbm.active_session_quota()
        dispatch = RUN_STATS.current_span()

        def read(p):
            with hbm.session_quota(quota), RUN_STATS.attach(dispatch):
                return _concat([b for b in child.execute(p, ctx) if b.num_rows],
                               child.schema())

        with fut.ThreadPoolExecutor(max_workers=min(max(len(parts), 1), 8)) as pool:
            tables = list(pool.map(read, parts))
        # from here on the child's output is in hand: any decline below must
        # aggregate THESE tables on the CPU, not re-execute the child (whose
        # device results this read just consumed — re-deriving them on the
        # host is the 100x overhead the profile pinned)
        if own:
            held = [_concat([], child.schema()) for _ in range(P_in)]
            held[partition] = tables[0]
            self._mat_input = (held, child.df_schema, bypass)
            self._mat_expected = {partition}
            P_result = 1
        else:
            self._mat_input = (tables, child.df_schema, bypass)
            self._mat_expected = ({0} if (bypass or self.coalesce)
                                  else set(range(P_result)))
        self._mat_node = None
        part_rows = [t.num_rows for t in tables]
        total = sum(part_rows)
        if total < max(self.min_rows, 1):
            # declined BEFORE ensure_jax(): a daemon-attached client whose
            # final merge is tiny (the common shape — partials did the heavy
            # lifting device-side) never pays a platform init of its own
            raise BelowRowFloor(total)
        jax = ensure_jax()

        full = pa.concat_tables(tables)
        widest = max(part_rows)
        if own:
            # every task of the stage stacks at ONE shape, sized by the
            # stage's widest input partition (the reader's location stats),
            # so the stage compiles one program, as a whole-stage merge did
            widest = max([widest] + [sum(loc.stats.num_rows for loc in locs)
                                     for locs in child.partition_locations])
        N = next_bucket(max(widest, 1), self.buckets)
        P = len(part_rows)

        # encode first (cheap dtype/validity info), then enforce the HBM
        # budget BEFORE any host stacking or device upload: the partial
        # path's discipline (stage_compiler.py:586) — a stage the budget
        # rejects falls back cleanly instead of relying on catching a
        # device OOM that can wedge the client on real runtimes
        encoded = []
        for name in full.column_names:
            dc = encode_column(full.column(name))
            if dc is None:
                raise Unsupported(f"unencodable column {name}")
            encoded.append(dc)
        cell_bytes = P * N
        proj_bytes = cell_bytes  # [P, N] bool row mask
        for dc in encoded:
            proj_bytes += cell_bytes * dc.data.dtype.itemsize
            if dc.valid is not None:
                proj_bytes += cell_bytes  # bool validity plane
        # the program's own: its group capacity follows its rows (groups never
        # outnumber them), C = pow2(P * N); the [C] output lanes (8 B a value,
        # 1 B a validity plane, the partition ids) and the first ordering's
        # scratch (the permutation, a sorted copy of every input lane)
        C = _pow2(cell_bytes)
        proj_bytes += C * (9 * len(self.schema()) + 4 + 4)
        proj_bytes += cell_bytes * (4 + 8 * len(encoded))
        max_bytes = int(self.config.get(TPU_MAX_DEVICE_BYTES))
        # fold the HBM admission budget into the pre-upload cap: the final
        # stage has no build side to grace-split, so the ladder here is just
        # run-whole vs CPU demotion — but the decision still lands in
        # RunStats so /api/executors sees WHY a final stage left the device
        budget = hbm.resolve_hbm_budget(self.config)
        if budget > 0:
            max_bytes = min(max_bytes, budget)
        RUN_STATS.set("hbm_budget_bytes", budget)
        if proj_bytes > max_bytes:
            RUN_STATS.set("hbm_plan", hbm.CPU_DEMOTE)
            RUN_STATS.set(
                "hbm_plan_reason",
                f"final stage needs {proj_bytes} B > budget {max_bytes} B")
            raise Unsupported(
                f"final stage needs {proj_bytes} device bytes (> cap {max_bytes})")
        RUN_STATS.set("hbm_plan", hbm.RUN_WHOLE)
        RUN_STATS.set("hbm_plan_reason",
                      f"final stage fits: {proj_bytes} B <= {max_bytes} B")

        kinds, scales, dicts, cols_np, valids_np = [], [], [], [], []
        for dc in encoded:
            kinds.append(dc.kind)
            scales.append(dc.scale)
            dicts.append(dc.dictionary)
            stack = np.zeros((P, N), dtype=dc.data.dtype)
            off = 0
            for p, r in enumerate(part_rows):
                stack[p, :r] = dc.data[off:off + r]
                off += r
            cols_np.append(stack)
            if dc.valid is None:
                valids_np.append(None)
            else:
                vstack = np.zeros((P, N), dtype=bool)
                off = 0
                for p, r in enumerate(part_rows):
                    vstack[p, :r] = dc.valid[off:off + r]
                    off += r
                valids_np.append(vstack)
        mask_np = np.zeros((P, N), dtype=bool)
        for p, r in enumerate(part_rows):
            mask_np[p, :r] = True

        key = (
            self.fingerprint, P, N, bypass,
            tuple(zip(kinds, scales)),
            tuple(str(c.dtype) for c in cols_np),
            tuple(v is not None for v in valids_np),
            tuple(_pow2(len(d)) if d else 0 for d in dicts),
        )
        with _FINAL_COMPILE_LOCK:
            cached = _FINAL_COMPILE_CACHE.get(key)
            if cached is None:
                with RUN_STATS.span("bt.compile.trace"):
                    fn, lowering, meta = self._compile(
                        kinds, scales, dicts, valids_np, cols_np, P, N,
                        merge_all=bypass)
                # per-entry run lock: the jitted closure mutates its shared
                # trace-time `cell` dict if jax ever retraces it (e.g. jit
                # cache eviction); serializing execution of THIS entry keeps
                # any retrace single-threaded without a global choke point
                cached = (fn, lowering, meta, threading.Lock())
                _FINAL_COMPILE_CACHE[key] = cached
        fn, lowering, meta, run_lock = cached

        luts = [_put(None, l) for l in lowering.build_luts(dicts)]
        flat = [_put(None, c) for c in cols_np] + [
            _put(None, v) for v in valids_np if v is not None
        ]
        mask = _put(None, mask_np)
        # a fresh entry's first call compiles inside it; `_decode` fetches a
        # count first and then a sliced fetch, so its fetches sit inside it
        RUN_STATS.set("table_shape", [P, N])
        RUN_STATS.set("sorted_capacity", meta["C"])
        for k, v in meta["compact"].items():
            RUN_STATS.set(k, v)
        if dispatch is not None:  # `bt.stage.dispatch`
            dispatch.set(sorted_capacity=meta["C"], **meta["compact"])
        with run_lock, RUN_STATS.span("bt.device.exec"):
            outs = fn(flat, luts, mask)
            jax.block_until_ready(list(outs))
        with RUN_STATS.span("bt.decode", family="final"):
            res = self._decode(outs, meta, P_result, dicts)
        return {partition: res[0]} if own else res

    # ------------------------------------------------------------------

    def _compile(self, kinds, scales, dicts, valids_np, cols_np, P: int, N: int,
                 merge_all: bool = False):
        from ballista_tpu.ops.tpu.stage_compiler import _bind_env, _pow2

        jax = ensure_jax()
        jnp = jax.numpy
        agg = self.agg
        n_group = len(agg.group_exprs)
        n_aggs = len(agg.aggs)
        if len(kinds) != n_group + n_aggs:
            raise Unsupported("final input is not [groups..., accumulators...]")
        for d in agg.aggs:
            if d.func not in ("sum", "min", "max", "count", "count_all"):
                raise Unsupported(f"final merge of {d.func}")
        for i in range(n_group):
            if kinds[i] == "f64":
                raise Unsupported("f64 group key")

        # flat-arg layout mirrors DeviceTable.flat_cols(): data cols, then
        # validity planes of nullable cols
        valid_idx: list = []
        nxt = len(cols_np)
        for v in valids_np:
            if v is None:
                valid_idx.append(None)
            else:
                valid_idx.append(nxt)
                nxt += 1

        M = P * N
        C = _pow2(M)  # every group a dispatch can find: no more than its rows

        # ---- compacted-space env: post-op closures read segment results
        # from this cell, populated inside raw before they run
        cell: dict = {}

        def mk_key_reader(i):
            def run(cols, luts):
                return DevVal(kinds[i], cell["keys"][i], scales[i], dicts[i],
                              valid=cell["key_valid"][i])
            return run

        def mk_acc_reader(ai):
            def run(cols, luts):
                return DevVal(cell["acc_kind"][ai], cell["accs"][ai],
                              cell["acc_scale"][ai], None,
                              valid=cell["acc_valid"][ai])
            return run

        ctx = Lowering(agg.df_schema, list(zip(kinds, scales)), dicts)
        env_fns: list = []
        env_meta: list = []
        for i in range(n_group):
            env_fns.append(mk_key_reader(i))
            env_meta.append((kinds[i], scales[i], dicts[i], i))
        for ai, d in enumerate(agg.aggs):
            src = n_group + ai
            if d.func in ("count", "count_all"):
                k, s = "i64", 0
            else:
                k, s = kinds[src], scales[src]
            env_fns.append(mk_acc_reader(ai))
            env_meta.append((k, s, dicts[src], src))
        ctx.env_fns = env_fns
        ctx.env_meta = env_meta
        _bind_env(ctx, agg.df_schema)

        keep_fns, sort_specs = _lower_chain(ctx, self.sort, self.post_ops)
        out_fns = list(ctx.env_fns)
        out_slots = [m[3] if m is not None else None for m in ctx.env_meta]
        fetch = self.sort.fetch if self.sort is not None else None

        agg_descs = list(agg.aggs)
        coalesce = self.coalesce or merge_all
        P_out = 1 if coalesce else P
        meta_holder: dict = {}

        def raw(cols, luts, mask):
            arangeM = jnp.arange(M, dtype=jnp.int32)
            if coalesce:
                pid = jnp.zeros((M,), jnp.int32)
            else:
                pid = jnp.broadcast_to(
                    jnp.arange(P, dtype=jnp.int32)[:, None], (P, N)).reshape(-1)
            valid = mask.reshape(-1)

            def read_col(i):
                arr = cols[i]
                if kinds[i] in ("i64", "money") and arr.dtype != jnp.int64:
                    arr = arr.astype(jnp.int64)
                elif kinds[i] in ("code", "date") and arr.dtype not in (jnp.int32,):
                    arr = arr.astype(jnp.int32)
                vplane = cols[valid_idx[i]] if valid_idx[i] is not None else None
                return arr.reshape(-1), (None if vplane is None else vplane.reshape(-1))

            # ---- phase 1 sort: (invalid, pid, group keys) --------------
            with jax.named_scope("sorted_agg"):
                keyops: list = []
                key_layout: list = []  # per group key: (marker_pos|None, value_pos)
                for i in range(n_group):
                    arr, vplane = read_col(i)
                    mpos = None
                    if vplane is not None:
                        mpos = len(keyops)
                        keyops.append((~vplane).astype(jnp.int32))
                    key_layout.append((mpos, len(keyops)))
                    keyops.append(arr)
                meta_holder["key_layout"] = key_layout

                pays: list = []
                pay_plan: list = []  # per agg: (pay_idx, ncnt_idx|None)
                for ai, d in enumerate(agg_descs):
                    arr, vplane = read_col(n_group + ai)
                    if d.func in ("count", "count_all"):
                        # partial counts are non-null; sum them exactly
                        a = arr.astype(jnp.int64)
                        if vplane is not None:
                            a = jnp.where(vplane, a, 0)
                        pays.append(a)
                        pay_plan.append((len(pays) - 1, None))
                        continue
                    ncnt_idx = None
                    if vplane is not None:
                        if d.func == "sum":
                            neutral = jnp.zeros((), dtype=arr.dtype)
                        elif d.func == "min":
                            neutral = (jnp.iinfo(arr.dtype).max
                                       if jnp.issubdtype(arr.dtype, jnp.integer) else jnp.inf)
                        else:
                            neutral = (jnp.iinfo(arr.dtype).min
                                       if jnp.issubdtype(arr.dtype, jnp.integer) else -jnp.inf)
                        arr = jnp.where(vplane, arr, neutral)
                        pays.append(vplane.astype(jnp.int64))
                        ncnt_idx = len(pays) - 1
                    pays.append(arr)
                    pay_plan.append((len(pays) - 1, ncnt_idx))

                # ordering permutation + gathers, not one wide lax.sort: the
                # chip compiler's time for a sort explodes with its operand
                # count (kernels.lex_order)
                perm1 = lex_order([~valid, pid] + keyops)
                svalid = valid[perm1]
                spid = pid[perm1]
                skeys = [k[perm1] for k in keyops]
                spays = [p[perm1] for p in pays]

                # a row starts a group where its partition or a key changes,
                # row 0 always (no M-row literal: see the sorted path's twin)
                diff = functools.reduce(jnp.logical_or, [
                    jnp.concatenate([jnp.ones((1,), bool), k[1:] != k[:-1]])
                    for k in [spid] + skeys])
                boundary = svalid & diff
                seg = int_cumsum(boundary.astype(jnp.int32)) - 1
                bor_inv = boundary | ~svalid
                is_end = svalid & jnp.concatenate([bor_inv[1:], jnp.ones((1,), bool)])
                n_seg = boundary.sum().astype(jnp.int32)

                spos = (
                    jnp.zeros((C,), jnp.int32)
                    .at[jnp.where(boundary, seg, C)]
                    .set(arangeM, mode="drop", unique_indices=True)
                )
                start = spos[jnp.clip(seg, 0, C - 1)]
                end_idx = jnp.where(is_end, seg, C)
                compact = SegmentCompaction(end_idx, n_seg, C)

                def int_segsum(sv):
                    w = sv.astype(jnp.int64)
                    csum = int_cumsum(w)
                    presum = csum - w
                    return compact(csum - presum[start])

                pid_c = compact(spid)
                key_vals: list = []
                key_valid: list = []
                for (mpos, vpos) in key_layout:
                    key_vals.append(compact(skeys[vpos]))
                    if mpos is None:
                        key_valid.append(None)
                    else:
                        key_valid.append(compact(skeys[mpos]) == 0)

                accs: list = []
                acc_valid: list = []
                acc_kind: list = []
                acc_scale: list = []
                for ai, (d, (pay_idx, ncnt_idx)) in enumerate(zip(agg_descs, pay_plan)):
                    sv = spays[pay_idx]
                    if d.func in ("count", "count_all"):
                        accs.append(int_segsum(sv))
                        acc_valid.append(None)
                        acc_kind.append("i64")
                        acc_scale.append(0)
                        continue
                    src = n_group + ai
                    fname = d.func
                    if fname == "sum" and jnp.issubdtype(sv.dtype, jnp.integer):
                        accs.append(int_segsum(sv))
                    elif fname == "sum":
                        accs.append(compact(segmented_scan(sv, boundary, "sum")))
                    else:
                        out = compact(segmented_scan(sv, boundary, fname))
                        if kinds[src] in ("i64", "money") and out.dtype != jnp.int64:
                            out = out.astype(jnp.int64)
                        accs.append(out)
                    if ncnt_idx is not None:
                        acc_valid.append(int_segsum(spays[ncnt_idx]) > 0)
                    else:
                        acc_valid.append(None)
                    acc_kind.append(kinds[src])
                    acc_scale.append(scales[src])
                cell["keys"] = key_vals
                cell["key_valid"] = key_valid
                cell["accs"] = accs
                cell["acc_valid"] = acc_valid
                cell["acc_kind"] = acc_kind
                cell["acc_scale"] = acc_scale
                meta_holder["compact"] = compact.counts()

            arangeC = jnp.arange(C, dtype=jnp.int32)
            alive = arangeC < n_seg
            with jax.named_scope("filter"):
                for kf in keep_fns:
                    alive = alive & true_mask(kf(cols, luts))

            with jax.named_scope("project"):
                out_vals = [f(cols, luts) for f in out_fns]
                out_meta = []
                for v, slot in zip(out_vals, out_slots):
                    if v.kind == "code" and (slot is None or not isinstance(slot, int)):
                        raise Unsupported("computed string output")
                    out_meta.append((v.kind, v.scale, slot,
                                     v.valid is not None))
                meta_holder["out"] = out_meta

            # ---- phase 2 sort: (dead, pid, user keys...) → perm --------
            with jax.named_scope("topk" if fetch is not None else "sort"):
                ops2: list = [~alive, pid_c]
                for (kf, asc, nf, lut_idx) in sort_specs:
                    v = kf(cols, luts)
                    arr = v.arr
                    if v.kind == "code":
                        if lut_idx is None:
                            raise Unsupported("unranked string sort key")
                        arr = luts[lut_idx][arr]
                    if arr.dtype == jnp.bool_:
                        arr = arr.astype(jnp.int32)
                    arr = jnp.broadcast_to(arr, (C,))
                    if not asc:
                        arr = -arr
                    if v.valid is not None:
                        marker = jnp.broadcast_to(~v.valid, (C,)).astype(jnp.int32)
                        ops2.append(-marker if nf else marker)  # nulls first → ahead
                    ops2.append(arr)
                perm = lex_order(ops2)  # stable: ties keep compacted order
                alive_s = alive[perm]
                spid2 = pid_c[perm]

                b2 = alive_s & jnp.concatenate(
                    [jnp.ones((1,), bool), spid2[1:] != spid2[:-1]])
                spos_pid = (
                    jnp.zeros((P_out,), jnp.int32)
                    .at[jnp.where(b2, spid2, P_out)]
                    .set(arangeC, mode="drop", unique_indices=True)
                )
                rank = arangeC - spos_pid[jnp.clip(spid2, 0, P_out - 1)]
                keep_out = alive_s
                if fetch is not None:
                    keep_out = keep_out & (rank < fetch)
                out_pos = int_cumsum(keep_out.astype(jnp.int32)) - 1
                n_out = keep_out.sum().astype(jnp.int32)
                scatter_idx = jnp.where(keep_out, out_pos, C)
                row_src = (
                    jnp.zeros((C,), jnp.int32)
                    .at[scatter_idx].set(perm, mode="drop", unique_indices=True)
                )
                pid_final = (
                    jnp.zeros((C,), jnp.int32)
                    .at[scatter_idx].set(spid2, mode="drop", unique_indices=True)
                )

            with jax.named_scope("emit"):
                outs: list = []
                for v in out_vals:
                    arr = jnp.broadcast_to(v.arr, (C,))
                    outs.append(arr[row_src])
                for v in out_vals:
                    if v.valid is not None:
                        outs.append(jnp.broadcast_to(v.valid, (C,))[row_src])
            return tuple(outs) + (pid_final, n_seg, n_out)

        raw.__name__ = raw.__qualname__ = "stage_final"
        jitted = jax.jit(raw)
        cols_spec = [jax.ShapeDtypeStruct(c.shape, c.dtype) for c in cols_np] + [
            jax.ShapeDtypeStruct(v.shape, np.bool_) for v in valids_np if v is not None
        ]
        luts0 = ctx.build_luts(dicts)
        luts_spec = [jax.ShapeDtypeStruct(l.shape, l.dtype) for l in luts0]
        mask_spec = jax.ShapeDtypeStruct((P, N), np.bool_)
        jitted.lower(cols_spec, luts_spec, mask_spec)  # trace only → meta
        meta = {
            "out": meta_holder["out"],
            "C": C,
            "P_out": P_out,
            "compact": meta_holder["compact"],
        }
        return jitted, ctx, meta

    # ------------------------------------------------------------------

    def _decode(self, outs, meta: dict, P_result: int, dicts) -> dict[int, list[pa.RecordBatch]]:
        from ballista_tpu.ops.tpu.stage_compiler import _pow2

        jax = ensure_jax()
        schema = self.schema()
        C = meta["C"]
        P_out = meta["P_out"]  # kernel pid space; ≤ P_result under bypass
        with RUN_STATS.span("bt.device.fetch", what="count"):
            n_seg, n_out = (int(x) for x in jax.device_get(outs[-2:]))
        # n_seg <= the rows <= C by construction
        RUN_STATS.set("final_groups", n_seg)
        if self.sort is not None and self.sort.fetch is not None:
            from ballista_tpu.ops.tpu.sort_window import _count

            _count("topk_rows_kept", n_out)
        results = {p: [_empty_batch(schema)] for p in range(P_result)}
        if n_out == 0:
            return results
        cp = min(_pow2(n_out), C)
        with RUN_STATS.span("bt.device.fetch", rows=n_out):
            data = jax.device_get([o[:cp] for o in outs[:-2]])
        out_meta = meta["out"]
        n_cols = len(out_meta)
        vals = data[:n_cols]
        valid_planes = data[n_cols:-1]
        pid = data[-1][:n_out]
        vi = 0
        arrays: list[pa.Array] = []
        for (kind, scale, slot, has_valid), f in zip(out_meta, schema):
            v = vals[len(arrays)][:n_out]
            null_mask = None
            if has_valid:
                null_mask = ~valid_planes[vi][:n_out]
                vi += 1
            if kind == "code":
                arr = decode_codes(v, dicts[slot], null_mask, f.type)
            elif kind == "date":
                arr = pa.array(v.astype(np.int32), pa.int32(),
                               mask=null_mask).cast(pa.date32())
            elif kind == "money":
                arr = pa.array(v.astype(np.float64) / (10 ** scale), pa.float64(),
                               mask=null_mask)
            elif kind == "bool":
                arr = pa.array(v.astype(bool), mask=null_mask)
            else:
                arr = pa.array(v, mask=null_mask)
            if arr.type != f.type:
                arr = arr.cast(f.type)
            arrays.append(arr)
        for p in range(P_out):
            sel = np.nonzero(pid == p)[0]
            if not len(sel):
                continue
            # np.take preserves order: rows are already (pid, sort-key) ordered
            cols_p = [a.take(pa.array(sel, pa.int32())) for a in arrays]
            results[p] = [pa.RecordBatch.from_arrays(cols_p, schema=schema)]
        return results
