"""A shuffle range is drained without re-entering Python (ISSUE 35).

The data file of every write — the sort layout's consolidated file, the hash
layout's bucket files, a passthrough's file, a spill — is an Arrow native
sink (its own buffered file stream); a range's in-memory batches go down in one `write_table`, streamed ones
one `write_batch` each, and the checksum is taken once a range over the bytes
as stored, read back from the closed `.tmp` before the rename. What is on
disk is byte for byte what one `write_batch` a batch into a Python sink
wrote: the plain writer below is that, with nothing of the program in it."""

import builtins
import json
import os
import weakref

import numpy as np
import pyarrow as pa
import pyarrow.ipc as ipc
import pytest

from ballista_tpu.config import (
    SHUFFLE_CHECKSUM_ENABLED,
    SHUFFLE_COMPRESSION_CODEC,
    SORT_SHUFFLE_MEMORY_LIMIT,
    BallistaConfig,
)
from ballista_tpu.errors import DiskExhausted
from ballista_tpu.ops.hashing import split_batch_by_partition
from ballista_tpu.plan.expressions import Column
from ballista_tpu.plan.physical import ExecutionPlan, TaskContext
from ballista_tpu.plan.schema import DFField, DFSchema
from ballista_tpu.shuffle import paths
from ballista_tpu.shuffle import writer as writer_mod
from ballista_tpu.shuffle.integrity import checksum_bytes
from ballista_tpu.shuffle.writer import ShuffleWriterExec

SCHEMA = DFSchema([DFField("k", pa.int64(), False), DFField("v", pa.float64(), True),
                   DFField("s", pa.string(), True)])
ARROW = SCHEMA.to_arrow()
P, K = 4, 3
JOB, STAGE, TASK = "jn", 2, "t9"


class Parts(ExecutionPlan):
    """A source of partitions; `boom_at` = (partition, batch) raises there."""

    def __init__(self, parts, boom_at=None):
        super().__init__(SCHEMA)
        self.parts = parts
        self.boom_at = boom_at

    def output_partition_count(self):
        return len(self.parts)

    def with_children(self, c):
        return self

    def execute(self, partition, ctx):
        for i, b in enumerate(self.parts[partition]):
            if self.boom_at == (partition, i):
                raise RuntimeError("the input throws half-way")
            yield b


def _batch(rng, rows: int) -> pa.RecordBatch:
    v = rng.random(rows)
    return pa.record_batch({
        "k": pa.array(rng.integers(0, 10_000, rows)),
        "v": pa.array(v, mask=v < 0.1),
        "s": pa.array([None if x < 0.05 else f"row-{x:.4f}" for x in v], pa.string()),
    }, schema=ARROW)


def _parts(seed=11, partitions=P, batches=5, rows=300):
    """`batches` batches a partition, an EMPTY one among them (never written)."""
    rng = np.random.default_rng(seed)
    return [[_batch(rng, rows) for _ in range(batches - 1)] + [_batch(rng, 0)]
            + [_batch(rng, rows // 3)] for _ in range(partitions)]


def _ctx(work, codec="lz4", checksum=True, **more) -> TaskContext:
    return TaskContext(BallistaConfig({SHUFFLE_COMPRESSION_CODEC: codec,
                                       SHUFFLE_CHECKSUM_ENABLED: checksum, **more}),
                       task_id=TASK, work_dir=str(work))


def _plain_stream(batches, codec) -> bytes:
    """The reference: one IPC stream, one `write_batch` a non-empty batch."""
    sink = pa.BufferOutputStream()
    options = ipc.IpcWriteOptions(compression=None if codec == "none" else codec)
    with ipc.new_stream(sink, ARROW, options=options) as w:
        for b in batches:
            if b.num_rows:
                w.write_batch(b)
    return sink.getvalue().to_pybytes()


def _buckets(batches) -> list[list[pa.RecordBatch]]:
    """Output partition k's sub-batches in arrival order, as the exchange
    buckets them (the engine's router: the routing is not under test)."""
    out = [[] for _ in range(K)]
    for b in batches:
        if b.num_rows:
            for k, part in split_batch_by_partition(b, [b.column(0)], K):
                out[k].append(part)
    return out


def _listing(work) -> list[str]:
    return sorted(os.path.relpath(os.path.join(r, n), work)
                  for r, _, names in os.walk(work) for n in names)


def _bytes(path) -> bytes:
    with open(path, "rb") as f:
        return f.read()


def _check_sort_file(data_path, want_ranges: dict[int, bytes], checksum: bool):
    """One data file = the ranges' streams back to back, one index entry a
    range: [offset, length, rows, bytes] + its stored bytes' digest."""
    data = _bytes(data_path)
    assert data == b"".join(want_ranges[k] for k in sorted(want_ranges))
    with open(paths.index_path(data_path)) as f:
        index = json.load(f)
    assert sorted(index) == sorted(str(k) for k in want_ranges)
    at = 0
    for k in sorted(want_ranges):
        entry = index[str(k)]
        start, length = entry[0], entry[1]
        assert (start, length, entry[3]) == (at, len(want_ranges[k]), len(want_ranges[k]))
        if checksum:
            assert entry[4] == checksum_bytes(data[start:start + length])
        else:
            assert len(entry) == 4
        at += length


def _check_hash_file(path, want: bytes, checksum: bool):
    assert _bytes(path) == want
    if checksum:
        assert _bytes(paths.crc_path(path)).decode() == checksum_bytes(want)
    else:
        assert not os.path.exists(paths.crc_path(path))


# -- (a) the committed bytes ----------------------------------------------------


@pytest.mark.parametrize("checksum", [True, False], ids=["crc", "nocrc"])
@pytest.mark.parametrize("codec", ["none", "lz4", "zstd"])
@pytest.mark.parametrize("layout", ["sort", "hash", "passthrough_one", "passthrough_slice"])
def test_committed_bytes_are_the_plain_writers(tmp_path, layout, codec, checksum):
    parts = _parts()
    ctx = _ctx(tmp_path, codec, checksum)
    if layout in ("sort", "hash"):
        w = ShuffleWriterExec(Parts(parts), JOB, STAGE, K, [Column("k")],
                              sort_shuffle=(layout == "sort"))
        meta = list(w.execute(1, ctx))[0]
        want = {k: _plain_stream(bs, codec) for k, bs in enumerate(_buckets(parts[1])) if bs}
        assert len(want) == K
        if layout == "sort":
            data_path = paths.sort_data_path(str(tmp_path), JOB, STAGE, 1, TASK)
            _check_sort_file(data_path, want, checksum)
            assert len(_listing(tmp_path)) == 2
        else:
            for k in want:
                _check_hash_file(paths.hash_data_path(str(tmp_path), JOB, STAGE, k, TASK),
                                 want[k], checksum)
            assert len(_listing(tmp_path)) == K * (2 if checksum else 1)
        assert meta.column("num_bytes").to_pylist() == [len(want[k]) for k in sorted(want)]
        rows = [sum(b.num_rows for b in bs) for bs in _buckets(parts[1])]
        assert meta.column("num_rows").to_pylist() == rows
    else:
        w = ShuffleWriterExec(Parts(parts), JOB, STAGE, 0, None, sort_shuffle=False)
        if layout == "passthrough_one":
            meta = list(w.execute(2, ctx))[0]
            _check_hash_file(paths.hash_data_path(str(tmp_path), JOB, STAGE, 2, TASK),
                             _plain_stream(parts[2], codec), checksum)
            assert len(_listing(tmp_path)) == (2 if checksum else 1)
            assert meta.column("num_batches").to_pylist() == [5]  # the empty one is not written
        else:
            meta = list(w.execute_slice(list(range(P)), ctx))[0]
            want = {p: _plain_stream(parts[p], codec) for p in range(P)}
            _check_sort_file(paths.sort_data_path(str(tmp_path), JOB, STAGE, 0, TASK),
                             want, checksum)
            assert len(_listing(tmp_path)) == 2
            assert meta.column("num_bytes").to_pylist() == [len(want[p]) for p in range(P)]
    assert not [n for n in _listing(tmp_path) if n.endswith(".tmp")]


# -- (b) a bucket with spills ---------------------------------------------------


def test_spilled_bucket_drains_in_order_one_batch_at_a_time(tmp_path, monkeypatch):
    parts = _parts(seed=3, partitions=1, batches=40, rows=400)
    limit = 3 * parts[0][0].nbytes
    ctx = _ctx(tmp_path, **{SORT_SHUFFLE_MEMORY_LIMIT: limit})
    w = ShuffleWriterExec(Parts(parts), JOB, STAGE, K, [Column("k")], sort_shuffle=True)

    streamed: list[tuple[list[str], list]] = []  # a call: its spill files, the rows it yielded
    orig = ShuffleWriterExec._iter_bucket_batches

    def spy(in_memory, spill_files):
        assert in_memory == [], "in-memory batches go down in one write_table, not through here"
        for sp in spill_files:
            assert os.path.exists(sp)
        rows, alive = [], []
        it = orig(in_memory, spill_files)
        while True:
            # before the next spilled batch is decoded at most the one the
            # drain still writes is alive: consolidation never rebuffers
            assert sum(r() is not None for r in alive) <= 1
            try:
                b = next(it)
            except StopIteration:
                break
            alive.append(weakref.ref(b))
            rows.extend(b.column(0).to_pylist())
            yield b
            del b
        streamed.append((list(spill_files), rows))

    monkeypatch.setattr(ShuffleWriterExec, "_iter_bucket_batches", staticmethod(spy))
    meta = list(w.execute(0, ctx))[0]

    assert w.metrics.extra["spill_count"] >= K, "nothing spilled: the test is vacuous"
    assert len(streamed) == K
    assert _listing(tmp_path) == sorted(
        os.path.relpath(p, tmp_path) for p in
        [paths.sort_data_path(str(tmp_path), JOB, STAGE, 0, TASK),
         paths.index_path(paths.sort_data_path(str(tmp_path), JOB, STAGE, 0, TASK))]), \
        "a spill file or a .tmp outlived the commit"
    data_path = paths.sort_data_path(str(tmp_path), JOB, STAGE, 0, TASK)
    with open(paths.index_path(data_path)) as f:
        index = json.load(f)
    buckets = _buckets(parts[0])
    for k, (spill_files, spilled_rows) in enumerate(streamed):
        # the bucket's spill files in the order they were written ...
        assert spill_files == [f"{data_path}.spill{i}.{k}" for i in range(len(spill_files))]
        start, length, rows, _, crc = index[str(k)]
        stored = _bytes(data_path)[start:start + length]
        assert crc == checksum_bytes(stored)
        got = ipc.open_stream(stored).read_all().column("k").to_pylist()
        # ... behind what was still in memory, every row once
        assert rows == len(got) == sum(b.num_rows for b in buckets[k])
        assert spilled_rows and got[len(got) - len(spilled_rows):] == spilled_rows
        assert sorted(got) == sorted(x for b in buckets[k] for x in b.column(0).to_pylist())
    assert sum(meta.column("num_rows").to_pylist()) == sum(b.num_rows for b in parts[0])


# -- (c) a drain that fails half-way leaves nothing -----------------------------


@pytest.mark.parametrize("layout", ["passthrough_one", "passthrough_slice", "sort_with_spills"])
def test_input_that_throws_leaves_nothing_behind(tmp_path, layout):
    if layout == "sort_with_spills":
        parts = _parts(seed=5, partitions=1, batches=30, rows=400)
        ctx = _ctx(tmp_path, **{SORT_SHUFFLE_MEMORY_LIMIT: 3 * parts[0][0].nbytes})
        w = ShuffleWriterExec(Parts(parts, boom_at=(0, 25)), JOB, STAGE, K, [Column("k")],
                              sort_shuffle=True)
        run = lambda: list(w.execute(0, ctx))
    else:
        parts = _parts()
        ctx = _ctx(tmp_path)
        one = layout == "passthrough_one"
        w = ShuffleWriterExec(Parts(parts, boom_at=(0, 3) if one else (2, 3)), JOB, STAGE, 0,
                              None, sort_shuffle=False)
        run = (lambda: list(w.execute(0, ctx))) if one else \
            (lambda: list(w.execute_slice(list(range(P)), ctx)))
    with pytest.raises(RuntimeError, match="half-way"):
        run()
    if layout == "sort_with_spills":
        assert w.metrics.extra["spill_count"] > 0, "nothing had spilled: the test is vacuous"
    assert _listing(tmp_path) == [], "a .tmp, an index or a spill outlived the failed attempt"


def _full(path):
    """`path` written through to /dev/full: the kernel's own ENOSPC, through
    the writer's own sink."""
    os.makedirs(os.path.dirname(path), exist_ok=True)
    os.symlink("/dev/full", path)


@pytest.mark.skipif(not os.path.exists("/dev/full"), reason="no /dev/full here")
@pytest.mark.parametrize("layout", ["sort", "hash", "passthrough_one", "passthrough_slice",
                                    "spill"])
def test_full_disk_is_disk_exhausted_and_leaves_nothing(tmp_path, layout):
    work = str(tmp_path)
    parts = _parts(seed=9, partitions=P, batches=30, rows=400)
    more = {}
    if layout in ("sort", "hash", "spill"):
        w = ShuffleWriterExec(Parts(parts), JOB, STAGE, K, [Column("k")],
                              sort_shuffle=(layout != "hash"))
        run = lambda: list(w.execute(0, ctx))
        data_path = paths.sort_data_path(work, JOB, STAGE, 0, TASK)
        if layout == "sort":
            _full(data_path + ".tmp")
        elif layout == "hash":
            _full(paths.hash_data_path(work, JOB, STAGE, 1, TASK) + ".tmp")
        else:
            more = {SORT_SHUFFLE_MEMORY_LIMIT: 3 * parts[0][0].nbytes}
            for k in range(K):
                _full(f"{data_path}.spill1.{k}")  # the SECOND spill of whichever bucket
    else:
        w = ShuffleWriterExec(Parts(parts), JOB, STAGE, 0, None, sort_shuffle=False)
        if layout == "passthrough_one":
            run = lambda: list(w.execute(0, ctx))
            _full(paths.hash_data_path(work, JOB, STAGE, 0, TASK) + ".tmp")
        else:
            run = lambda: list(w.execute_slice(list(range(P)), ctx))
            _full(paths.sort_data_path(work, JOB, STAGE, 0, TASK) + ".tmp")
    ctx = _ctx(tmp_path, **more)
    with pytest.raises(DiskExhausted):
        run()
    left = _listing(tmp_path)
    if layout == "hash":
        # the other buckets' drains ran beside the one that failed: their
        # attempt-unique files go with the job's directory, no `.tmp` stays
        assert not [n for n in left if n.endswith(".tmp")]
    elif layout == "spill":
        # the symlinks this test laid for the buckets that never spilled twice
        assert all(os.path.islink(os.path.join(work, n)) for n in left), left
    else:
        assert left == []


# -- (d) no Python between a range's first and last byte -------------------------


class _CountingWriter:
    """What `ipc.new_stream` returned, its calls counted."""

    def __init__(self, inner, calls):
        self.inner, self.calls = inner, calls

    def __enter__(self):
        self.inner.__enter__()
        return self

    def __exit__(self, *exc):
        return self.inner.__exit__(*exc)

    def write_table(self, table, *a, **kw):
        self.calls.append(("write_table", table.num_rows))
        return self.inner.write_table(table, *a, **kw)

    def write_batch(self, batch, *a, **kw):
        self.calls.append(("write_batch", batch.num_rows))
        return self.inner.write_batch(batch, *a, **kw)


@pytest.mark.parametrize("layout", ["sort", "hash"])
def test_in_memory_bucket_goes_down_in_one_call_into_a_native_sink(tmp_path, monkeypatch, layout):
    rng = np.random.default_rng(2)
    parts = [[_batch(rng, 24) for _ in range(2000)]]
    ctx = _ctx(tmp_path)
    w = ShuffleWriterExec(Parts(parts), JOB, STAGE, 1, [Column("k")],
                          sort_shuffle=(layout == "sort"))

    sinks, calls, opened, spans = [], [], [], []
    new_stream = ipc.new_stream

    def counting_new_stream(sink, schema, **kw):
        sinks.append(sink)
        assert kw["options"].use_threads is False
        return _CountingWriter(new_stream(sink, schema, **kw), calls)

    real_open = builtins.open

    def spying_open(file, mode="r", *a, **kw):
        opened.append((str(file), mode))
        return real_open(file, mode, *a, **kw)

    set_commit = ShuffleWriterExec._set_commit

    def spying_set_commit(span, *a):
        set_commit(span, *a)
        spans.append(span)

    monkeypatch.setattr(ipc, "new_stream", counting_new_stream)
    monkeypatch.setattr(builtins, "open", spying_open)
    monkeypatch.setattr(ShuffleWriterExec, "_set_commit", staticmethod(spying_set_commit))
    meta = list(w.execute(0, ctx))[0]
    monkeypatch.undo()

    # one stream, into a file Arrow itself holds: its writer has no Python
    # object to call back into, and the 2,000 batches are ONE call
    assert len(sinks) == 1
    assert isinstance(sinks[0], pa.NativeFile) and not isinstance(sinks[0], pa.PythonFile)
    assert calls == [("write_table", 2000 * 24)]
    # Python's own `open` saw the index or the sidecar (text), never a data file
    assert not [(f, m) for f, m in opened if "b" in m and "w" in m], opened
    assert meta.column("num_rows").to_pylist() == [2000 * 24]
    assert meta.column("num_batches").to_pylist() == [2000]
    (span,) = spans
    n = span.attrs
    assert n["ranges"] == 1 and n["files"] == 2
    assert n["bytes"] == meta.column("num_bytes").to_pylist()[0] > 0
    assert n["write_ms"] > 0 and n["checksum_ms"] > 0
    assert n["write_ms"] + n["checksum_ms"] <= span.seconds * 1e3


def test_streamed_ranges_keep_one_write_a_batch(tmp_path, monkeypatch):
    """A passthrough writes as it pulls: a batch a call, into the native sink,
    and its commit span holds the read-back alone."""
    parts = _parts()
    calls, sinks, spans = [], [], []
    new_stream = ipc.new_stream
    monkeypatch.setattr(ipc, "new_stream", lambda sink, schema, **kw: (
        sinks.append(sink), _CountingWriter(new_stream(sink, schema, **kw), calls))[1])
    set_commit = ShuffleWriterExec._set_commit
    monkeypatch.setattr(ShuffleWriterExec, "_set_commit", staticmethod(
        lambda span, *a: (set_commit(span, *a), spans.append(span))[0]))
    w = ShuffleWriterExec(Parts(parts), JOB, STAGE, 0, None, sort_shuffle=False)
    list(w.execute_slice(list(range(P)), _ctx(tmp_path)))
    assert len(sinks) == P and len({id(s) for s in sinks}) == 1
    assert isinstance(sinks[0], pa.NativeFile) and not isinstance(sinks[0], pa.PythonFile)
    assert [c[0] for c in calls] == ["write_batch"] * (P * 5)
    n = spans[0].attrs
    assert n["ranges"] == P and n["write_ms"] == 0.0 and n["checksum_ms"] > 0


def test_checksums_off_reads_nothing_back(tmp_path, monkeypatch):
    reads = []
    pread = os.pread
    monkeypatch.setattr(os, "pread", lambda fd, n, at: (reads.append((n, at)), pread(fd, n, at))[1])
    w = ShuffleWriterExec(Parts(_parts()), JOB, STAGE, K, [Column("k")], sort_shuffle=True)
    list(w.execute(0, _ctx(tmp_path / "off", checksum=False)))
    assert reads == []
    meta = list(w.execute(0, _ctx(tmp_path / "on", checksum=True)))[0]
    # one read a range, each whole, back to back from the file's first byte
    lengths = meta.column("num_bytes").to_pylist()
    assert reads == [(n, sum(lengths[:i])) for i, n in enumerate(lengths)]


def test_a_long_range_is_read_back_in_pieces(tmp_path, monkeypatch):
    """The read-back holds a bounded piece at a time; the digest is the
    whole range's."""
    reads = []
    pread = os.pread
    monkeypatch.setattr(os, "pread", lambda fd, n, at: (reads.append(n), pread(fd, n, at))[1])
    monkeypatch.setattr(writer_mod, "_READ_BACK", 1000)
    parts = _parts()
    w = ShuffleWriterExec(Parts(parts), JOB, STAGE, K, [Column("k")], sort_shuffle=True)
    meta = list(w.execute(1, _ctx(tmp_path)))[0]
    lengths = meta.column("num_bytes").to_pylist()
    assert min(lengths) > 1000 and max(reads) == 1000
    assert sum(reads) == sum(lengths) and len(reads) == sum(-(-n // 1000) for n in lengths)
    want = {k: _plain_stream(bs, "lz4") for k, bs in enumerate(_buckets(parts[1]))}
    _check_sort_file(paths.sort_data_path(str(tmp_path), JOB, STAGE, 1, TASK), want, True)

