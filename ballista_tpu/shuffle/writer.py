"""Shuffle writers: the root operator of every intermediate stage.

ShuffleWriterExec rebuilds the reference's two writers behind one node:

- hash layout (ShuffleWriterExec, shuffle_writer.rs:305): rows routed by
  the engine-wide key hash into K output files per map task — used for
  passthrough/collapse stages (K=0 → one output mirroring the input
  partition) and small fan-outs.
- sort layout (SortShuffleWriterExec, sort_shuffle/writer.rs:179): one
  consolidated data file per map task containing K buckets sorted by
  output partition + an index file; buffered per-bucket batches spill to
  disk when `ballista.shuffle.sort.memory.limit` is exceeded and are
  merged at finish (2×M files instead of N×M).

execute_slice(map_partitions) drives the child over the task's whole slice
and yields ONE metadata batch (output_partition, path, rows, bytes, layout)
— the same results-as-metadata-batches contract the reference uses to
report ShuffleWritePartition summaries (execution_engine.rs:304).

A task commits ONCE, whatever the length of its slice: the slice is pulled
a partition at a time and lands in one file set. A hash exchange buckets
rows by key across the slice (range k = output partition k's rows from
every map partition of the slice, small batches merged up to the session's
batch size); a passthrough keeps partition identity
(range p = map partition p's rows, streamed as they are pulled). A slice of
one map partition writes what a map task always wrote — same layout, paths
and bytes — so the length of the slice is all that decides. The locations
of a longer slice are reported under its FIRST map partition
(docs/tpu_engine.md#how-a-device-stage-is-tasked).

A range is drained inside Arrow: the data file is a native sink (Arrow's
own buffered file stream), never a Python object, so between a range's
first and last byte the IPC writer calls nothing back — in-memory batches
go down in one `write_table`, streamed ones (a spill file, a passthrough's
pull) one `write_batch` each. The checksum is taken afterwards over the
bytes AS STORED: each range of the closed `.tmp` read back once, before
any rename.

On-device partitioning: when the child pipeline ran on the TPU engine the
hash is computed with the jax twin of ops/hashing.py; host and device
partitions are bit-identical so readers never care who wrote a file.
"""

from __future__ import annotations

import contextlib
import os
import json
import time
import uuid
from typing import Iterable, Iterator, Optional

import numpy as np
import pyarrow as pa
import pyarrow.ipc as ipc

from ballista_tpu.config import (
    SHUFFLE_CHECKSUM_ENABLED,
    SHUFFLE_COMPRESSION_CODEC,
    SORT_SHUFFLE_MEMORY_LIMIT,
)
from ballista_tpu.errors import ExecutionError
from ballista_tpu.executor import disk
from ballista_tpu.executor.chaos import maybe_disk_full
from ballista_tpu.shuffle.integrity import Checksum
from ballista_tpu.ops.hashing import partition_indices
from ballista_tpu.ops.phys_expr import bind_expr, evaluate_to_array
from ballista_tpu.plan.expressions import Expr
from ballista_tpu.plan.physical import ExecutionPlan, TaskContext, _empty_batch
from ballista_tpu.plan.schema import DFField, DFSchema
from ballista_tpu.shuffle import paths
from ballista_tpu.shuffle.types import PartitionStats
from ballista_tpu.tracing import RUN_STATS, now_ns


METADATA_SCHEMA = DFSchema(
    [
        DFField("output_partition", pa.int32(), False),
        DFField("path", pa.string(), False),
        DFField("num_rows", pa.int64(), False),
        DFField("num_batches", pa.int64(), False),
        DFField("num_bytes", pa.int64(), False),
        DFField("layout", pa.string(), False),
    ]
)


def _unlink_quiet(*ps: str) -> None:
    for p in ps:
        try:
            os.remove(p)
        except OSError:
            pass


@contextlib.contextmanager
def _sweeping(what: str, *leftovers: str):
    """A write that must leave nothing behind when it fails: an attempt
    killed on the way (cancel, deadline, crash, a full disk) removes its
    `.tmp` and spill files — they will never be renamed — and a full disk
    comes out typed (`DiskExhausted`) with `what` it was writing."""
    try:
        yield
    except OSError as e:
        _unlink_quiet(*leftovers)
        typed = disk.wrap_enospc(e, what)
        if typed is not None:
            raise typed from e
        raise
    except BaseException:
        _unlink_quiet(*leftovers)
        raise


def _checksum_on(ctx: TaskContext) -> bool:
    return bool(ctx.config.get(SHUFFLE_CHECKSUM_ENABLED))


def _write_crc_sidecar(data_path: str, digest: str | None) -> None:
    """Commit a hash-layout file's checksum sidecar (tmp + atomic rename,
    same discipline as the data file it describes). A None digest (knob
    off) writes nothing — absence means 'unchecked' to every reader."""
    if not digest:
        return
    cp = paths.crc_path(data_path)
    try:
        with open(cp + ".tmp", "w") as f:
            f.write(digest)
    except BaseException:
        _unlink_quiet(cp + ".tmp")
        raise
    os.replace(cp + ".tmp", cp)


def _merge_small(batches: list[pa.RecordBatch], target_rows: int) -> list[pa.RecordBatch]:
    """Neighbouring batches merged up to `target_rows` rows each (the
    session's batch size): same rows, same order, fewer IPC messages. A
    batch that is large already is passed on untouched, never copied."""
    out: list[pa.RecordBatch] = []
    run: list[pa.RecordBatch] = []
    rows = 0
    for b in batches + [None]:
        if run and (b is None or rows + b.num_rows > target_rows):
            out.append(run[0] if len(run) == 1 else pa.concat_batches(run))
            run, rows = [], 0
        if b is not None:
            run.append(b)
            rows += b.num_rows
    return out


def _index_entry(start: int, length: int, rows: int, crc: str | None) -> list:
    """One range of a sort-layout index: [offset, length, rows, bytes] + the
    range's checksum string where checksums are on (shuffle/paths.py)."""
    entry: list = [start, length, rows, length]
    if crc:
        entry.append(crc)
    return entry


def _commit_data_and_index(data_path: str, index: dict[str, list], what: str) -> None:
    """Commit a whole `.tmp` data file and its index under their final
    names, data BEFORE index: a reader that finds the index finds every
    range it names."""
    idx_path = paths.index_path(data_path)
    with _sweeping(what, data_path + ".tmp", idx_path + ".tmp"):
        os.replace(data_path + ".tmp", data_path)
        with open(idx_path + ".tmp", "w") as f:
            json.dump(index, f)
        os.replace(idx_path + ".tmp", idx_path)


def _codec(ctx: TaskContext) -> Optional[str]:
    c = str(ctx.config.get(SHUFFLE_COMPRESSION_CODEC))
    return None if c == "none" else c


def _ipc_options(ctx: TaskContext) -> ipc.IpcWriteOptions:
    # the task slots are the parallelism: a drain that borrows the process's
    # CPU pool for 48 KB buffers pays more in hand-offs than the codec costs
    return ipc.IpcWriteOptions(compression=_codec(ctx), use_threads=False)


# Arrow hands a sink every message header, buffer and padding by itself
# (seven a batch: 21,000 for 150 MB of 48 KB batches) and a file system call
# is ~90 us on a sandboxed host, so the sink coalesces them: 1 MiB a write.
_SINK_BUFFER = 1 << 20


def _open_sink(path: str) -> pa.NativeFile:
    """The native sink every data file, `.tmp` and spill is written through."""
    return pa.output_stream(path, compression=None, buffer_size=_SINK_BUFFER)


def _drain_range(sink: pa.NativeFile, schema: pa.Schema, ctx: TaskContext,
                 held: Iterable[pa.RecordBatch] = (),
                 streamed: Iterable[pa.RecordBatch] = ()) -> tuple[int, int, int, int]:
    """Write ONE IPC stream at the sink's position: the batches `held` in
    memory in one call (a table over the batches' own buffers: nothing is
    copied), then the `streamed` ones as they come, never more than one
    alive. Returns (start, length, rows, batches) of the range. `sink` is an
    Arrow native file, so no byte of the range passes through Python."""
    start = sink.tell()
    held = [b for b in held if b.num_rows]
    rows = sum(b.num_rows for b in held)
    n = len(held)
    with ipc.new_stream(sink, schema, options=_ipc_options(ctx)) as w:
        if held:
            w.write_table(pa.Table.from_batches(held, schema=schema))
        for b in streamed:
            if b.num_rows:
                w.write_batch(b)
                rows += b.num_rows
                n += 1
    return start, sink.tell() - start, rows, n


# the most a read-back holds at once: a longer range is checksummed in pieces
_READ_BACK = 16 << 20


def _checksum_ranges(tmp_path: str, ranges: list[tuple[int, int]],
                     ctx: TaskContext) -> list[str | None]:
    """The checksum of each (start, length) range of a closed `.tmp`, over
    the bytes as stored: one open a file, one `pread` a range of up to
    16 MiB (three system calls for a one-range file: a mapping costs three
    times that where system calls are dear). Nothing is read back where
    checksums are off (or there is no range)."""
    if not ranges or not _checksum_on(ctx):
        return [None] * len(ranges)
    digests = []
    fd = os.open(tmp_path, os.O_RDONLY)
    try:
        for start, length in ranges:
            c = Checksum()
            while length:
                part = os.pread(fd, min(length, _READ_BACK), start)
                if not part:
                    raise OSError(f"{tmp_path}: {length} bytes of a written range are missing")
                c.update(part)
                start += len(part)
                length -= len(part)
            digests.append(c.digest())
    finally:
        os.close(fd)
    return digests


class _Pull:
    """The writer's pulls of its input, timed: `ns` is the time spent inside
    the stage's operators — `execute()` (a device stage dispatches in it) and
    every `next()`: two clock reads a batch — and `between_ns` the rest of
    the span up to `done()`: the writer's own work between pulls."""

    __slots__ = ("ns", "between_ns", "_t0")

    def __init__(self):
        self.ns = self.between_ns = 0
        self._t0 = now_ns()

    def of(self, plan: ExecutionPlan, partition: int,
           ctx: TaskContext) -> Iterator[pa.RecordBatch]:
        t0 = now_ns()
        it = iter(plan.execute(partition, ctx))
        while True:
            try:
                b = next(it)
            except StopIteration:
                self.ns += now_ns() - t0
                return
            self.ns += now_ns() - t0
            yield b
            t0 = now_ns()

    def done(self) -> None:
        self.between_ns = now_ns() - self._t0 - self.ns


class ShuffleWriterExec(ExecutionPlan):
    def __init__(self, input: ExecutionPlan, job_id: str, stage_id: int,
                 output_partitions: int, keys: list[Expr] | None,
                 sort_shuffle: bool = True):
        super().__init__(METADATA_SCHEMA)
        self.input = input
        self.job_id = job_id
        self.stage_id = stage_id
        self.output_partitions = output_partitions  # 0 = passthrough
        self.keys = keys or []
        self.sort_shuffle = sort_shuffle and output_partitions > 0

    def children(self):
        return [self.input]

    def with_children(self, c):
        return ShuffleWriterExec(
            c[0], self.job_id, self.stage_id, self.output_partitions, self.keys, self.sort_shuffle
        )

    def output_partition_count(self) -> int:
        return self.input.output_partition_count()

    def input_schema(self) -> pa.Schema:
        return self.input.schema()

    def node_str(self) -> str:
        k = f" keys=[{', '.join(str(e) for e in self.keys)}]" if self.keys else ""
        mode = "sort" if self.sort_shuffle else "hash"
        return (
            f"ShuffleWriterExec: {self.job_id}/{self.stage_id} "
            f"out={self.output_partitions or 'passthrough'} layout={mode}{k}"
        )

    def execute(self, partition: int, ctx: TaskContext) -> Iterator[pa.RecordBatch]:
        return self.execute_slice([partition], ctx)

    def execute_slice(self, partitions: list[int], ctx: TaskContext,
                      before_partition=None) -> Iterator[pa.RecordBatch]:
        """Write the map partitions one task holds and commit them once.
        `before_partition` is called ahead of every partition's pull (the
        task runner's cancel and deadline checks); what it raises aborts
        the write like any other error — nothing under a final name, no
        `.tmp` left."""
        partitions = list(partitions)
        if not partitions:
            return iter(())
        # the whole slice's write: the stage's operators are pulled through
        # it, so a device stage's spans (and the commit) nest inside and its
        # self time is the pull, the partitioning and operators with no span
        with RUN_STATS.span("bt.shuffle.write", map_partitions=len(partitions)) as span:
            if not ctx.work_dir:
                raise ExecutionError("shuffle writer needs a work_dir in TaskContext")
            task_id = ctx.task_id or f"{partitions[0]}-{uuid.uuid4().hex[:6]}"
            write = self._write_passthrough if self.output_partitions <= 0 else self._write_exchange
            pull = _Pull()
            meta = write(partitions, task_id, ctx, before_partition or (lambda: None), pull)
            # `pull_ms`: inside `next()` on the stage's operators (their spans
            # nest in it); `partition_ms`: the writer's own work between
            # pulls, up to the commit (keys, split, reserve, spills; a
            # passthrough's IPC writes)
            span.set(rows=sum(meta.column("num_rows").to_pylist()),
                     bytes=sum(meta.column("num_bytes").to_pylist()),
                     pull_ms=round(pull.ns / 1e6, 3),
                     partition_ms=round(pull.between_ns / 1e6, 3))
        # closed, but its numbers still reach the job's record: the task runner
        # adds the operators' share once it has harvested the plan's metrics
        # (handed over on the task's own context: in-process, tasks share the plan)
        ctx.write_span = span
        return self._timed(iter([meta]))

    # ------------------------------------------------------------------

    def _commit_span(self, partitions: list[int]):
        """One a task: its numbers say what the commit put on disk."""
        return RUN_STATS.span("bt.shuffle.commit", map_partitions=len(partitions))

    @staticmethod
    def _set_commit(span, meta: pa.RecordBatch, files: int, write_s: float,
                    checksum_s: float) -> None:
        """`write_ms`: the drains that ran inside the span (thread-ms; none
        for a passthrough, whose ranges are written during the pull);
        `checksum_ms`: the read-back of the stored ranges."""
        span.set(ranges=meta.num_rows, files=files,
                 bytes=sum(meta.column("num_bytes").to_pylist()),
                 write_ms=round(write_s * 1e3, 3), checksum_ms=round(checksum_s * 1e3, 3))

    def _write_passthrough(self, partitions: list[int], task_id, ctx: TaskContext,
                           before_partition, pull: _Pull) -> pa.RecordBatch:
        """Stage collapse / preserved partitioning: partition identity is
        the contract (a consumer may merge sorted partitions or join
        co-partitioned ones), so every map partition keeps a range of its
        own and its batches go to the sink as they are pulled.

        One map partition: the hash layout's file under the partition's
        directory + its `.crc` sidecar. A longer slice: ONE data file of
        the sort layout, range p = map partition p, + ONE index.

        tmp + atomic rename: a task killed mid-write (deadline, cancel,
        crash) must never leave a truncated file under the final name."""
        first = partitions[0]
        one = len(partitions) == 1
        schema = self.input.schema()
        if one:
            path = paths.hash_data_path(ctx.work_dir, self.job_id, self.stage_id, first, task_id)
        else:
            path = paths.sort_data_path(ctx.work_dir, self.job_id, self.stage_id, first, task_id)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        maybe_disk_full(ctx.config, self.job_id, self.stage_id, first,
                        ctx.task_attempt, "shuffle passthrough write")
        what = f"shuffle write {self.job_id}/{self.stage_id}/{first}"
        ranges = []
        with _sweeping(what, path + ".tmp"), _open_sink(path + ".tmp") as f:
            for p in partitions:
                before_partition()
                ranges.append(_drain_range(f, schema, ctx,
                                           streamed=pull.of(self.input, p, ctx)))
        pull.done()
        layout = "hash" if one else "sort"
        meta = self._meta([(p, path, rows, n, length, layout)
                           for p, (_, length, rows, n) in zip(partitions, ranges)])
        with self._commit_span(partitions) as span:
            # per-RANGE checksum: a partition's byte range is the unit
            # readers fetch and verify (one partition: the file)
            t0 = time.perf_counter()
            with _sweeping(what, path + ".tmp"):
                digests = _checksum_ranges(path + ".tmp", [r[:2] for r in ranges], ctx)
            checksum_s = time.perf_counter() - t0
            if one:
                _write_crc_sidecar(path, digests[0])
                os.replace(path + ".tmp", path)
                files = 2 if digests[0] else 1
            else:
                index = {str(p): _index_entry(start, length, rows, crc)
                         for p, (start, length, rows, _), crc in zip(partitions, ranges, digests)}
                _commit_data_and_index(path, index, what)
                files = 2
            self._set_commit(span, meta, files, 0.0, checksum_s)
        return meta

    def _write_exchange(self, partitions: list[int], task_id, ctx: TaskContext,
                        before_partition, pull: _Pull) -> pa.RecordBatch:
        """Hash exchange: rows are bucketed by key across the WHOLE slice —
        bucket k holds output partition k's rows from every map partition
        the task holds — and drained once. The memory limit, the session
        pool and the spills work over the slice's buckets."""
        first = partitions[0]
        schema = self.input.schema()
        bound = [bind_expr(k, self.input.df_schema) for k in self.keys]
        K = self.output_partitions
        buckets: list[list[pa.RecordBatch]] = [[] for _ in range(K)]
        bucket_rows = [0] * K
        bucket_batches = [0] * K
        buffered = 0
        spills: list[list[str]] = [[] for _ in range(K)]
        limit = int(ctx.config.get(SORT_SHUFFLE_MEMORY_LIMIT)) if self.sort_shuffle else 0
        # session-shared pool (try_grow semantics): when present, buffering
        # reserves against the SESSION's budget — concurrent tasks share it,
        # so idle tasks lend headroom to a heavy sort and a refusal means
        # "spill first" (the reference's per-session RuntimeEnv MemoryPool,
        # runtime_cache.rs:59)
        pool = ctx.memory_pool if self.sort_shuffle else None
        pool_held = 0

        def spill_largest() -> bool:
            nonlocal buffered, pool_held
            # low-watermark shed: spills are the OPTIONAL disk writes, so
            # they stop first under disk pressure. Returning False pushes
            # the caller onto the memory-overcommit ladder (grow_wait)
            # instead of filling the last of the disk.
            if not disk.spill_allowed(ctx.config, ctx.work_dir):
                return False
            k = max(range(K), key=lambda i: sum(b.nbytes for b in buckets[i]))
            if not buckets[k]:
                return False
            maybe_disk_full(ctx.config, self.job_id, self.stage_id, first,
                            ctx.task_attempt, "sort-shuffle spill")
            sp = paths.sort_data_path(ctx.work_dir, self.job_id, self.stage_id, first, task_id) + f".spill{len(spills[k])}.{k}"
            os.makedirs(os.path.dirname(sp), exist_ok=True)
            what = f"sort-shuffle spill {self.job_id}/{self.stage_id}/{first}"
            with _sweeping(what, sp), _open_sink(sp) as f:
                _, sp_bytes, _, _ = _drain_range(f, schema, ctx, buckets[k])
            spills[k].append(sp)
            freed = sum(b.nbytes for b in buckets[k])
            buffered -= freed
            # SpillManager-style accounting (sort_shuffle/spill.rs:46,110):
            # cumulative spilled volume surfaces in EXPLAIN ANALYZE metrics
            self.metrics.extra["spilled_bytes"] = (
                self.metrics.extra.get("spilled_bytes", 0) + sp_bytes)
            self.metrics.extra["spill_count"] = self.metrics.extra.get("spill_count", 0) + 1
            if pool is not None:
                pool.shrink(min(freed, pool_held))
                pool_held -= min(freed, pool_held)
            buckets[k] = []
            return True

        def reserve(nbytes: int) -> None:
            nonlocal pool_held
            if pool is None:
                return
            while not pool.try_grow(nbytes):
                if not spill_largest():
                    # nothing of ours left to spill: BLOCK with a deadline
                    # for peer tasks of this session to shrink (their next
                    # refusal makes them spill); only a deadline pass takes
                    # the headroom unaccounted — bounded liveness instead of
                    # the old unconditional grow()
                    from ballista_tpu.config import SORT_SHUFFLE_POOL_WAIT_S

                    wait_s = float(ctx.config.get(SORT_SHUFFLE_POOL_WAIT_S))
                    if not pool.grow_wait(nbytes, timeout_s=wait_s):
                        import logging

                        logging.getLogger(__name__).warning(
                            "memory pool overcommitted by %d bytes after %.1fs "
                            "wait (session under real pressure)", nbytes, wait_s)
                    break
            pool_held += nbytes

        from ballista_tpu.executor.chaos import skew_params, skew_remap_pids
        from ballista_tpu.ops.hashing import hash_arrays, split_batch_by_partition

        skew = skew_params(ctx.config)
        try:
            for p in partitions:
                before_partition()
                for b in pull.of(self.input, p, ctx):
                    if b.num_rows == 0:
                        continue
                    pids = None
                    if getattr(self, "device_routed", False) and "__pid" in b.schema.names:
                        if skew is not None and bound:
                            # chaos skew reroutes by the row's KEY HASH, but the
                            # device only shipped final partition ids. Recompute
                            # the keys on the host (the jax hash is a bit-exact
                            # twin) so every writer of this exchange — host- or
                            # device-hashed — remaps the same rows.
                            key_arrays = [evaluate_to_array(kb, b) for kb in bound]
                            b = b.select([n for n in b.schema.names if n != "__pid"])
                        else:
                            # device-side routing: the TPU stage already hashed
                            # rows to partitions (bit-exact twin); consume and
                            # drop the column. Gated on the engine-set flag so a
                            # user column named __pid is never misinterpreted.
                            i = b.schema.get_field_index("__pid")
                            pids = b.column(i).to_numpy(zero_copy_only=False).astype(np.uint64)
                            b = b.select([n for n in b.schema.names if n != "__pid"])
                            key_arrays = []
                    else:
                        key_arrays = [evaluate_to_array(kb, b) for kb in bound]
                    if skew is not None and key_arrays:
                        pids = skew_remap_pids(hash_arrays(key_arrays), K, *skew)
                    for k, part in split_batch_by_partition(b, key_arrays, K, precomputed_pids=pids):
                        reserve(part.nbytes)
                        buckets[k].append(part)
                        bucket_rows[k] += part.num_rows
                        bucket_batches[k] += 1
                        buffered += part.nbytes
                    while limit and buffered > limit:
                        if not spill_largest():
                            break

            pull.done()
            # the buckets drained to their files, checksummed and renamed
            with self._commit_span(partitions) as span:
                if len(partitions) > 1:
                    # a bucket of a slice holds a batch a map partition it
                    # pulled: drained as they are, a range would cost its
                    # partitions' count to write and to read, not its rows
                    for k in range(K):
                        merged = _merge_small(buckets[k], ctx.batch_size)
                        bucket_batches[k] -= len(buckets[k]) - len(merged)
                        buckets[k] = merged
                if self.sort_shuffle:
                    done = self._finish_sort(first, task_id, schema, buckets, spills,
                                             bucket_rows, bucket_batches, ctx)
                else:
                    done = self._finish_hash(first, task_id, schema, buckets,
                                             bucket_rows, bucket_batches, ctx)
                meta = done[0]
                self._set_commit(span, *done)
            return meta
        except BaseException:
            # consolidation removes spills as it streams them; an aborted
            # attempt has to sweep up whatever it spilled itself
            _unlink_quiet(*(sp for ks in spills for sp in ks))
            raise
        finally:
            if pool is not None and pool_held:
                pool.shrink(pool_held)

    def _finish_hash(self, map_partition, task_id, schema, buckets, rows, batches, ctx):
        """Drain the K bucket files CONCURRENTLY (the reference's K
        concurrent per-output drain tasks, shuffle_writer.rs:214-303): a
        drain runs in Arrow from its first byte to its last, with the GIL
        released, so the drains genuinely overlap. Returns the metadata,
        the files written, and the drains' and the read-backs' seconds."""
        import concurrent.futures as fut

        live = [k for k in range(len(buckets)) if rows[k]]
        if not live:
            return self._meta([]), 0, 0.0, 0.0
        maybe_disk_full(ctx.config, self.job_id, self.stage_id, map_partition,
                        ctx.task_attempt, "hash-shuffle commit")

        def drain(k: int):
            path = paths.hash_data_path(ctx.work_dir, self.job_id, self.stage_id, k, task_id)
            os.makedirs(os.path.dirname(path), exist_ok=True)
            what = f"shuffle write {self.job_id}/{self.stage_id}/{k}"
            t0 = time.perf_counter()
            with _sweeping(what, path + ".tmp"):
                with _open_sink(path + ".tmp") as f:
                    _, nbytes, _, _ = _drain_range(f, schema, ctx, buckets[k])
                t1 = time.perf_counter()
                (digest,) = _checksum_ranges(path + ".tmp", [(0, nbytes)], ctx)
            t2 = time.perf_counter()
            _write_crc_sidecar(path, digest)
            os.replace(path + ".tmp", path)
            return (k, path, rows[k], batches[k], nbytes, "hash"), t1 - t0, t2 - t1

        files = len(live) * (2 if _checksum_on(ctx) else 1)
        if len(live) == 1:
            out = [drain(live[0])]
        else:
            with fut.ThreadPoolExecutor(max_workers=min(len(live), 8),
                                        thread_name_prefix="shuffle-drain") as pool:
                out = list(pool.map(drain, live))
        return (self._meta([o[0] for o in out]), files,
                sum(o[1] for o in out), sum(o[2] for o in out))

    @staticmethod
    def _iter_bucket_batches(in_memory: list, spill_files: list[str]):
        """Stream a bucket's batches: in-memory first (none from the drain,
        which writes those in one call), then each spill file decoded ONE
        BATCH AT A TIME. Consolidation must never rebuffer what it spilled
        — that would peak at exactly the memory the spill existed to avoid
        (sort_shuffle/spill.rs:46 streams the same way)."""
        for b in in_memory:
            yield b
        for sp in spill_files:
            with open(sp, "rb") as sf:
                yield from ipc.open_stream(sf)
            os.remove(sp)

    def _finish_sort(self, map_partition, task_id, schema, buckets, spills, rows, batches, ctx):
        """Consolidate buckets (memory + spills) into one data file + index.

        The data file name is attempt-unique (task_id baked in) and both
        files commit via tmp + atomic rename, data BEFORE index: duplicate
        attempts of the same slice (speculation) each produce a complete
        private file set, and whichever status reaches the scheduler first
        decides which set readers ever see. `map_partition` names the file:
        the slice's first."""
        data_path = paths.sort_data_path(ctx.work_dir, self.job_id, self.stage_id, map_partition, task_id)
        os.makedirs(os.path.dirname(data_path), exist_ok=True)
        maybe_disk_full(ctx.config, self.job_id, self.stage_id, map_partition,
                        ctx.task_attempt, "sort-shuffle commit")
        what = f"sort-shuffle commit {self.job_id}/{self.stage_id}/{map_partition}"
        live = [k for k in range(len(buckets)) if rows[k]]
        t0 = time.perf_counter()
        with _sweeping(what, data_path + ".tmp"):
            with _open_sink(data_path + ".tmp") as f:
                ranges = [_drain_range(f, schema, ctx, buckets[k],
                                       self._iter_bucket_batches([], spills[k]))
                          for k in live]
            t1 = time.perf_counter()
            # per-RANGE checksum: each bucket's byte range is the unit
            # readers fetch and verify
            digests = _checksum_ranges(data_path + ".tmp", [r[:2] for r in ranges], ctx)
        t2 = time.perf_counter()
        index = {str(k): _index_entry(start, length, nrows, crc)
                 for k, (start, length, nrows, _), crc in zip(live, ranges, digests)}
        _commit_data_and_index(data_path, index, what)
        meta = self._meta([(k, data_path, nrows, batches[k], length, "sort")
                           for k, (_, length, nrows, _) in zip(live, ranges)])
        return meta, 2, t1 - t0, t2 - t1

    def _meta(self, rows: list[tuple]) -> pa.RecordBatch:
        schema = self.schema()
        if not rows:
            return _empty_batch(schema)
        cols = list(zip(*rows))
        arrays = [
            pa.array(cols[0], pa.int32()),
            pa.array(cols[1], pa.string()),
            pa.array([int(x) for x in cols[2]], pa.int64()),
            pa.array([int(x) for x in cols[3]], pa.int64()),
            pa.array([int(x) for x in cols[4]], pa.int64()),
            pa.array(cols[5], pa.string()),
        ]
        return pa.RecordBatch.from_arrays(arrays, schema=schema)


def metadata_to_locations(batch: pa.RecordBatch, job_id: str, stage_id: int,
                          map_partition: int, executor_id: str, host: str, flight_port: int):
    """Convert a writer metadata batch into PartitionLocations
    (reference: drive_shuffle_writer_stage → ShuffleWritePartition,
    execution_engine.rs:304; zero-byte sentinels dropped :336)."""
    from ballista_tpu.shuffle.types import PartitionLocation, PartitionStats

    out = []
    for i in range(batch.num_rows):
        if batch.column(2)[i].as_py() == 0:
            continue
        out.append(
            PartitionLocation(
                map_partition=map_partition,
                job_id=job_id,
                stage_id=stage_id,
                output_partition=batch.column(0)[i].as_py(),
                executor_id=executor_id,
                host=host,
                flight_port=flight_port,
                path=batch.column(1)[i].as_py(),
                layout=batch.column(5)[i].as_py(),
                stats=PartitionStats(
                    num_rows=batch.column(2)[i].as_py(),
                    num_batches=batch.column(3)[i].as_py(),
                    num_bytes=batch.column(4)[i].as_py(),
                ),
            )
        )
    return out
