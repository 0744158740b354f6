"""The program's one recorder: per-stage counters and the spans of a query.

`RUN_STATS` (a `RunStats`) holds what the engine counts per stage dispatch
(`run(tag)` scopes, read through `snapshot()` / `stages()`) and the spans of
the served path: client -> scheduler -> task runner -> stage dispatch ->
shuffle -> result fetch, all named `bt.*` (docs/tpu_engine.md#observability
has the table of keys and of span names). `STAGE_OUTCOMES` counts where
device-stage operators ran. jax-free: a scheduler or client process imports
this module and never jax; `ops/tpu/stage_compiler.py` re-exports the four
names it used to define.

Spans are always on: no key, no environment variable. One span is two reads
of `time.perf_counter_ns` and one append under a lock. While a
`jax.profiler` session is active in this process (and only if jax is already
imported) a span also opens a `jax.profiler.TraceAnnotation` of the same
name, so the program's spans lie in the `.xplane.pb` beside the device's
operations.
"""

from __future__ import annotations

import collections
import contextlib
import itertools
import sys
import threading
import time
from collections.abc import Mapping

now_ns = time.perf_counter_ns

MAX_SPANS_PER_JOB = 1024
# jobs whose root span lives in another process (a remote scheduler's or
# executor's view of a client's query) are kept for a later reader, oldest out
MAX_OPEN_JOBS = 64


_TRACE_ANNOTATION = None  # jax.profiler.TraceAnnotation, once jax is imported


def _annotation():
    """`jax.profiler.TraceAnnotation` while a profiler session is tracing
    this process, else None. Never imports jax."""
    global _TRACE_ANNOTATION
    if _TRACE_ANNOTATION is None:
        profiler = getattr(sys.modules.get("jax"), "profiler", None)
        if profiler is None:
            return None
        _TRACE_ANNOTATION = profiler.TraceAnnotation
    return _TRACE_ANNOTATION if _TRACE_ANNOTATION.is_enabled() else None


def _given(job, stage, task) -> dict:
    """The ids that are known, as a trace annotation's metadata."""
    return {k: v for k, v in (("job", job), ("stage", stage), ("task", task)) if v is not None}


class Span:
    """One open or closed span. `with RUN_STATS.span(...) as s:` opens it on
    the calling thread; `s.set(job=..., rows=...)` adds ids and numbers
    while it is open; `s.seconds` is its duration (so far, while open) on the
    span's monotonic clock."""

    __slots__ = ("_stats", "_up", "_ann", "name", "id", "parent", "start", "end",
                 "job", "stage", "task", "attrs", "root")

    def __init__(self, stats: "RunStats", name: str, up: "Span | None", job, stage, task,
                 attrs: dict, root: bool, start: int | None):
        self._stats = stats
        self._up = up  # the enclosing span: the thread's, or the one attached
        self._ann = None
        self.name = name
        self.id = next(stats._ids)
        self.parent = up.id if up is not None else None
        self.job, self.stage, self.task = job, stage, task
        self.attrs = attrs
        self.root = root
        self.end: int | None = None
        self.start = now_ns() if start is None else start

    def set(self, *, job=None, stage=None, task=None, **attrs) -> None:
        if job is not None:
            self.job = job
        if stage is not None:
            self.stage = stage
        if task is not None:
            self.task = task
        self.attrs.update(attrs)
        if self._ann is not None:
            self._ann.set_metadata(**_given(job, stage, task), **attrs)

    @property
    def seconds(self) -> float:
        return ((self.end if self.end is not None else now_ns()) - self.start) / 1e9

    def _inherit(self) -> None:
        """Ids left unset are the enclosing spans': a dispatch under a task
        carries the task's job, stage and task without being handed them."""
        up = self._up
        while up is not None and (self.job is None or self.stage is None or self.task is None):
            if up.job is not None:
                if self.job is None:
                    self.job = up.job
                elif up.job != self.job:
                    break  # another job's span encloses this one: not its ids
            if self.stage is None:
                self.stage = up.stage
            if self.task is None and self.stage == up.stage:
                self.task = up.task
            up = up._up

    def __enter__(self) -> "Span":
        tls = self._stats._tls
        stack = getattr(tls, "spans", None)
        if stack is None:
            stack = tls.spans = []
        stack.append(self)
        annotation = _annotation()
        if annotation is not None:
            self._inherit()
            self._ann = annotation(self.name, **_given(self.job, self.stage, self.task),
                                   **self.attrs)
            self._ann.__enter__()
        return self

    def __exit__(self, *exc) -> None:
        self.end = now_ns()
        if self._ann is not None:
            had_job = self.job is not None
            self._inherit()
            if self.job is not None and not had_job:
                # opened before the job had an id (the client's submit)
                self._ann.set_metadata(job=self.job)
            self._ann.__exit__(*exc)
            self._ann = None
        self._stats._tls.spans.pop()
        self._stats._close(self)


class RunStats(Mapping):
    """Per-stage-run diagnostics and the spans of the served path, for
    chip_smoke.py, the benchmark and the executor heartbeat.

    Counters: every `_tpu_run_all` opens a `run(tag)` scope that collects
    into a private per-run dict (helper threads write through an explicit
    `rec=` handle) and publishes atomically on exit: the merged view
    (`dict(RUN_STATS)`, `snapshot()`) is always a consistent
    most-recent-run-wins snapshot, and `stages()` keeps the last few
    per-stage records (merged over a stage's dispatches since the last
    clear(), most recent value wins, with their number as `dispatches`;
    a `run(tag, counted=False)` record — the final family's — counts none).
    Every key is listed in docs/tpu_engine.md#observability (the
    stats-sync analysis pass holds the table to what the code emits).

    Spans: `span(name, job=, stage=, task=, **numbers)` records name, id,
    parent, start and end (`time.perf_counter_ns`), the ids and the numbers.
    A span's parent is the enclosing open span of its thread (or the one a
    helper thread `attach`ed); the first span of a thread is hung, when its
    job is published, under the span of the same job with the nearest
    matching (job, stage) that contains it. `add_span` records an interval
    that began on another thread (a task's wait in the queue, a stage from
    runnable to done). Spans are kept per job, at most MAX_SPANS_PER_JOB
    (more are counted in `spans_dropped`). When a job's root span closes in
    this process the job's spans become ONE record of `stages()` under the
    tag `job_<job_id>`: {"spans": [[name, id, parent, start_s, end_s, stage,
    task, numbers], ...], "spans_dropped": n}, seconds of `perf_counter`
    rounded to the microsecond. That record never passes through the
    dispatch-counting `_publish` and carries none of the keys the counters
    sum. Spans that closed outside any job (cluster start) ride in the next
    job's record."""

    _MAX_STAGES = 32

    def __init__(self):
        self._lock = threading.Lock()
        self._merged: dict = {}
        self._stages: "collections.OrderedDict[str, dict]" = collections.OrderedDict()
        self._tls = threading.local()
        self._ids = itertools.count(1)
        self._jobs: "collections.OrderedDict[str, list[Span]]" = collections.OrderedDict()
        self._loose: list[Span] = []  # closed with no job: carried to the next record
        self._dropped: dict = {}  # job (None: loose) -> spans over the cap

    # -- counters ----------------------------------------------------------

    @contextlib.contextmanager
    def run(self, tag: str, counted: bool = True):
        rec: dict = {}
        prev = getattr(self._tls, "rec", None)
        self._tls.rec = rec
        try:
            yield rec
        finally:
            self._tls.rec = prev
            self._publish(tag, rec, counted)

    def _publish(self, tag: str, rec: dict, counted: bool = True) -> None:
        if not rec:
            return
        with self._lock:
            self._merged.update(rec)
            # one record per stage, merged over its dispatches (one an
            # executor, and one more for a re-read): only the first carries
            # the cold-path keys (fill_s, xla_compile_s, persist_cache_*) —
            # a later dispatch's record must not erase them
            prev = self._stages.pop(tag, {})
            merged = {**prev, **rec}
            if counted:
                merged["dispatches"] = prev.get("dispatches", 0) + 1
            self._keep_stage(tag, merged)

    def _keep_stage(self, tag: str, record: dict) -> None:
        self._stages[tag] = record
        while len(self._stages) > self._MAX_STAGES:
            self._stages.popitem(last=False)

    def set(self, key: str, value, rec: dict | None = None) -> None:
        """Record one stat. With `rec` (a run's private dict, threadable to
        helper threads) the write lands in that run; otherwise in the
        calling thread's open run scope, else directly in the merged view."""
        if rec is None:
            rec = getattr(self._tls, "rec", None)
        if rec is not None:
            rec[key] = value
        else:
            with self._lock:
                self._merged[key] = value

    def __setitem__(self, key: str, value) -> None:  # legacy write path
        self.set(key, value)

    def current(self) -> dict | None:
        return getattr(self._tls, "rec", None)

    def snapshot(self) -> dict:
        with self._lock:
            return dict(self._merged)

    def stages(self) -> dict:
        with self._lock:
            return {t: dict(r) for t, r in self._stages.items()}

    def clear(self) -> None:
        """Forget the published counters and records. Spans of jobs still in
        flight, and those waiting for the next job's record, stay."""
        with self._lock:
            self._merged.clear()
            self._stages.clear()

    # -- spans -------------------------------------------------------------

    def span(self, name: str, *, job=None, stage=None, task=None, root: bool = False,
             start_ns: int | None = None, **attrs) -> Span:
        """A context manager recording one span on the calling thread.
        `root=True` marks a query's outermost span: its close publishes the
        job. `start_ns` backdates the start to when the work was handed over
        (an event posted to a queue)."""
        stack = getattr(self._tls, "spans", None)
        return Span(self, name, stack[-1] if stack else None, job, stage, task,
                    attrs, root, start_ns)

    def add_span(self, name: str, start_ns: int, *, end_ns: int | None = None,
                 parent: Span | None = None, job=None, stage=None, task=None,
                 **attrs) -> Span:
        """Record an interval that began at `start_ns` elsewhere and ends now
        (or ended at `end_ns`): it never joins a thread's nesting and writes
        no trace annotation. `parent`, a span that contains it, gives it its
        ids. Returns the span, so a later interval can name it as parent."""
        s = Span(self, name, parent, job, stage, task, attrs, False, start_ns)
        s.end = now_ns() if end_ns is None else end_ns
        self._close(s)
        return s

    def current_span(self) -> Span | None:
        stack = getattr(self._tls, "spans", None)
        return stack[-1] if stack else None

    @contextlib.contextmanager
    def attach(self, span: Span | None):
        """Make `span` (open on another thread) the enclosing span of this
        helper thread: what it opens hangs under it and carries its ids."""
        if span is None:
            yield
            return
        stack = getattr(self._tls, "spans", None)
        if stack is None:
            stack = self._tls.spans = []
        stack.append(span)
        try:
            yield
        finally:
            stack.pop()

    def job_spans(self, job: str) -> list[Span]:
        """The closed spans held for a job not yet published: what a process
        that does not hold the job's root (a remote executor) has of it."""
        with self._lock:
            return list(self._jobs.get(job, ()))

    def take_job_spans(self, job: str) -> dict:
        """The closed spans this process holds for `job`, and whatever closed
        outside any job since the last record, as the rows of a `job_<id>`
        record — and forgets them: what a process that does not hold the
        job's root (a remote scheduler or executor) hands to whoever asks
        (`join_job_parts` makes one record of several processes' answers)."""
        with self._lock:
            spans, dropped = self._take(job)
        return {"spans": span_rows(spans), "spans_dropped": dropped}

    def _take(self, job: str) -> tuple[list[Span], int]:
        """Under the lock: the job's spans and whatever closed outside any job
        since the last record, no longer held here, and how many went over
        the cap."""
        spans = self._jobs.pop(job, [])
        dropped = self._dropped.pop(job, 0) + self._dropped.pop(None, 0)
        for s in self._loose:
            s._inherit()  # a child that closed before the root had its id
        spans += self._loose
        self._loose = []
        return spans, dropped

    def _close(self, span: Span) -> None:
        span._inherit()
        with self._lock:
            job = span.job
            if job is None:
                kept = self._loose
            else:
                kept = self._jobs.get(job)
                if kept is None:
                    kept = self._jobs[job] = []
                    while len(self._jobs) > MAX_OPEN_JOBS:
                        old, _ = self._jobs.popitem(last=False)
                        self._dropped.pop(old, None)
            if len(kept) < MAX_SPANS_PER_JOB or span.root:
                kept.append(span)
            else:
                self._dropped[job] = self._dropped.get(job, 0) + 1
            if span.root and job is not None:
                self._publish_job(span)

    def _publish_job(self, root: Span) -> None:
        """Under the lock: the job's spans, and whatever closed outside any
        job since the last record, as one plain record."""
        spans, dropped = self._take(root.job)
        _hang_orphans(spans, root)
        self._keep_stage(f"job_{root.job}", {"spans": span_rows(spans),
                                             "spans_dropped": dropped})

    # Mapping protocol over the merged snapshot (dict(RUN_STATS) works)
    def __getitem__(self, key):
        with self._lock:
            return self._merged[key]

    def __iter__(self):
        with self._lock:
            return iter(list(self._merged))

    def __len__(self) -> int:
        with self._lock:
            return len(self._merged)


def _hang_orphans(spans: list, root) -> None:
    """Give every span of the root's job (with no root: of the spans' job)
    that has no parent (the first of its thread, or an `add_span`; in a
    record joined from several processes, each process's own roots) the
    span that contains it with the nearest
    matching ids: same stage before job only, then the shortest. Only spans
    with no task id (the job's and the stages') can adopt, and only one
    that starts no later and ends no earlier, so no span adopts its parent."""
    def outer(s: Span) -> tuple:
        return (s.start, -s.end, s.id)

    job = root.job if root is not None else (spans[0].job if spans else None)
    holders = [s for s in spans if s.task is None and s.job == job]
    for o in spans:
        if o.parent is not None or o is root or o.job != job:
            continue
        best = None
        for c in holders:
            if (c.start <= o.start and c.end >= o.end and outer(c) < outer(o)
                    and (c.stage is None or c.stage == o.stage)):
                rank = (c.stage is not None, c.start - c.end)
                if best is None or rank > best[0]:
                    best = (rank, c)
        if best is not None:
            o.parent = best[1].id


def span_rows(spans) -> list[list]:
    """Spans as the eight-field rows of a `job_<id>` record: name, id,
    parent, start and end (seconds of `perf_counter`, to the microsecond),
    stage, task, numbers."""
    return [[s.name, s.id, s.parent, round(s.start / 1e9, 6), round(s.end / 1e9, 6),
             s.stage, s.task, s.attrs] for s in spans]


def clock_pair() -> list[int]:
    """This process's span clock and the wall clock, read together (ns):
    what puts another process's spans on the reader's `perf_counter`."""
    return [now_ns(), time.time_ns()]


class _Row:
    """A record's row with the attributes `_hang_orphans` reads."""

    __slots__ = ("row", "job", "id", "parent", "start", "end", "stage", "task")

    def __init__(self, row: list, job: str):
        self.row, self.job = row, job
        _, self.id, self.parent, self.start, self.end, self.stage, self.task = row[:7]


def join_job_parts(job_id: str, parts: list[dict]) -> dict:
    """ONE `job_<id>` record out of several processes' parts of a job, in
    the shape `RunStats._publish_job` writes. A part is {"process": name,
    "clock": `clock_pair()` of that process, "spans": rows, "spans_dropped":
    n}; the first is the asking process's own (the client's published
    record with its clock), whose `perf_counter` the record keeps: every
    other part is shifted onto it through the two clock pairs (on one host
    processes share CLOCK_MONOTONIC, but nothing here depends on that).
    Ids are made unique over the parts (a part's ids are offset past every
    earlier part's, its parents with them). A span without a parent is hung
    by the rule of `_hang_orphans`: under the span of the job with the
    nearest matching stage that contains it — an executor's `bt.task.run`
    under the scheduler's `bt.sched.stage`, the scheduler's own roots under
    the client's `bt.client.wait`; one that nothing contains (its part's
    parent process is missing) stays a root. Every row of a part but the
    first carries the part's index in its numbers as `proc`, and
    "processes" names them in order. Works on plain lists."""
    spans: list[list] = []
    dropped = 0
    base = None
    next_id = 0
    for index, part in enumerate(parts):
        perf_ns, wall_ns = part["clock"]
        if base is None:
            base = wall_ns - perf_ns
        shift = ((wall_ns - perf_ns) - base) / 1e9
        rows = part.get("spans") or []
        offset = next_id
        for row in rows:
            name, sid, parent, start, end, stage, task = row[:7]
            numbers = dict(row[7]) if len(row) > 7 and row[7] else {}
            if index:
                numbers["proc"] = index
            spans.append([name, sid + offset, None if parent is None else parent + offset,
                          round(start + shift, 6), round(end + shift, 6), stage, task, numbers])
            next_id = max(next_id, sid + offset)
        dropped += part.get("spans_dropped", 0)
    held = [_Row(row, job_id) for row in spans]
    ids = {r.id for r in held}
    for r in held:
        if r.parent is not None and r.parent not in ids:
            r.parent = None  # its parent went over the cap, or closed after the fetch
    roots = [r for r in held if r.parent is None and r.row[0] == "bt.client.collect"]
    root = max(roots, key=lambda r: r.end - r.start) if roots else None
    _hang_orphans(held, root)
    for r in held:
        r.row[2] = r.parent
    return {"spans": spans, "spans_dropped": dropped,
            "processes": [part.get("process", str(i)) for i, part in enumerate(parts)]}


# a span that closes on another thread or in another process may end a little
# after the moment its successor says it started (a hand-over between threads,
# a joined part's clock pair, the rounding to the microsecond): that far past
# `t` a span still counts as having ended by `t`. Half a millisecond is fifty
# times the widest such overlap in the recorded jobs (under 10 us) and a
# hundredth of the shortest task: no neighbour's end falls that close by chance
PATH_TOLERANCE_S = 0.0005


def critical_path(rows: list, root: str = "bt.client.collect",
                  waiting: tuple = ("bt.client.wait", "bt.sched.stage"),
                  tolerance_s: float = PATH_TOLERANCE_S) -> dict | None:
    """Which spans the client waited for: the critical path through the rows
    of a `job_<id>` record (one process's or a joined one; plain lists).
    Returns {"root": id, "segments": [[start_s, end_s, span id, name], ...],
    "seconds": {name: seconds}} — the segments, in time order, tile the root
    span's interval exactly — or None where no span is named `root`.

    The walk goes back from the root's end. In span S at time t the next span
    on the path is the candidate with the latest end <= t (+ `tolerance_s`)
    that starts before t: S owns (that end, t], the candidate is walked over
    its own interval (clipped to S's), and S goes on from the candidate's
    start. A candidate still running at t is a neighbour, not a predecessor:
    with four slots the path leaves a task at its start and enters the task
    whose end freed the slot. What no candidate covers is S's own.

    `waiting` spans only wait for others and never hide work: S's candidates
    are its children and, through a waiting child, that child's children
    (recursively), wherever the waiting spans were hung; time no candidate
    covers goes to the innermost (shortest) waiting span open then, else S."""
    tops = [r for r in rows if r[0] == root]
    if not tops:
        return None
    top = max(tops, key=lambda r: r[4] - r[3])
    children: dict = {}
    for r in rows:
        if r[2] is not None and r is not top:
            children.setdefault(r[2], []).append(r)
    waits = set(waiting)
    found: list[list] = []  # latest first

    def own(span, looked: list, lo: float, hi: float) -> None:
        if hi <= lo:
            return
        cuts = sorted({lo, hi, *(x for w in looked for x in (w[3], w[4]) if lo < x < hi)})
        for a, b in zip(cuts[-2::-1], cuts[:0:-1]):
            open_then = [w for w in looked if w[3] <= a and b <= w[4]]
            w = min(open_then, key=lambda w: (w[4] - w[3], w[1])) if open_then else span
            found.append([a, b, w[1], w[0]])

    def walk(span, lo: float, hi: float) -> None:
        candidates, looked = [], []
        stack = list(children.get(span[1], ()))
        while stack:
            c = stack.pop()
            if c[0] in waits:
                looked.append(c)
                stack.extend(children.get(c[1], ()))
            else:
                candidates.append(c)
        candidates.sort(key=lambda c: (c[4], c[1]), reverse=True)
        t = hi
        for c in candidates:
            if t <= lo:
                break
            if c[4] > t + tolerance_s or c[3] >= t or c[4] <= lo:
                continue  # running at t (a neighbour), or outside (lo, t]
            end, start = min(c[4], t), max(c[3], lo)
            own(span, looked, end, t)
            walk(c, start, end)
            t = start
        own(span, looked, lo, t)

    walk(top, top[3], top[4])
    segments: list[list] = []
    for seg in reversed(found):
        if segments and segments[-1][2] == seg[2] and segments[-1][1] == seg[0]:
            segments[-1][1] = seg[1]
        else:
            segments.append(seg)
    seconds: dict = {}
    for a, b, _, name in segments:
        seconds[name] = seconds.get(name, 0.0) + (b - a)
    return {"root": top[1], "segments": segments, "seconds": seconds}


def job_path(rows: list, longest: int = 10) -> dict | None:
    """A record's critical path as `SessionContext.job_diagnostics()` hands it
    out: the root's seconds, the path's seconds by span name (largest first)
    and its `longest` segments as [start_s, end_s, span id, name, stage,
    task]. None where the record has no `bt.client.collect`."""
    path = critical_path(rows)
    if path is None:
        return None
    by_id = {r[1]: r for r in rows}
    top = by_id[path["root"]]
    longest_first = sorted(path["segments"], key=lambda s: s[0] - s[1])[:longest]
    return {"root_s": round(top[4] - top[3], 6),
            "seconds": {name: round(s, 6) for name, s in
                        sorted(path["seconds"].items(), key=lambda kv: -kv[1])},
            "longest": [[a, b, sid, name, by_id[sid][5], by_id[sid][6]]
                        for a, b, sid, name in longest_first]}


RUN_STATS = RunStats()


def _plain_values(rec: dict) -> dict:
    return {k: v for k, v in rec.items() if isinstance(v, (int, float, str, list, tuple))}


def process_diagnostics(job_id: str = "", clear: bool = False,
                        device_ordinal: int = -1) -> dict:
    """What THIS process answers to `GetDiagnostics`: its clock pair; the
    closed spans it holds for `job_id` (handed out once: fetching drops
    them); `RUN_STATS.stages()` as numbers and short strings;
    `STAGE_OUTCOMES.snapshot()`; and, only where the process already runs a
    jax backend (an executor that holds a chip; never a scheduler or a
    client), the devices it sees, its device's `memory_stats()` and
    `runtime.compile_cache_stats()`. `clear` then does `RUN_STATS.clear()`.
    Never imports jax and never initialises a backend."""
    out: dict = {"clock": clock_pair(),
                 "job": RUN_STATS.take_job_spans(job_id) if job_id else None,
                 "stages": {tag: _plain_values(rec) for tag, rec in RUN_STATS.stages().items()},
                 "outcomes": STAGE_OUTCOMES.snapshot(),
                 "devices": None, "memory": None, "compile_cache": None}
    runtime = sys.modules.get("ballista_tpu.ops.tpu.runtime")
    if runtime is not None and runtime.backend_is_up():
        device = runtime.bound_device(device_ordinal) or runtime.current_device()
        out["devices"] = {"platform": device.platform, "kind": device.device_kind,
                          "count": len(device.client.local_devices()),
                          "ordinal": device_ordinal, "id": device.id}
        out["memory"] = _plain_values(device.memory_stats() or {})
        out["compile_cache"] = runtime.compile_cache_stats()
    if clear:
        RUN_STATS.clear()
    return out


class StageOutcomes:
    """Process-wide, cumulative ledger of where device-stage operators ran.

    Every stage family (partial / final / sort / window) notes one outcome
    per dispatch attempt: `device` (ran on the device), `below_row_floor`
    (stayed on the CPU under ballista.tpu.min.rows, by policy), `declined`
    (any other Unsupported — the documented per-subtree fallback) or `error`
    (a non-Unsupported exception demoted to the CPU engine: the query still
    answers, but this is the fallback that would hide a broken device path).
    The operators' own tpu_count / fallback_count live on per-task plan
    objects nobody keeps; this is what chip_smoke.py, tests and the executor
    heartbeat (`tpu_stage_*` gauges) read instead."""

    KINDS = ("device", "below_row_floor", "declined", "error")

    def __init__(self):
        self._lock = threading.Lock()
        self._counts = dict.fromkeys(self.KINDS, 0)
        self._recent: "collections.deque[tuple]" = collections.deque(maxlen=64)

    def note(self, family: str, kind: str, detail: str = "") -> None:
        with self._lock:
            self._counts[kind] += 1
            self._recent.append((family, kind, detail))

    def note_fallback(self, family: str, exc: BaseException) -> None:
        from ballista_tpu.ops.tpu.kernels import BelowRowFloor, Unsupported

        kind = ("below_row_floor" if isinstance(exc, BelowRowFloor)
                else "declined" if isinstance(exc, Unsupported) else "error")
        self.note(family, kind, f"{type(exc).__name__}: {exc}"[:300])

    def snapshot(self) -> dict:
        """Counts per kind and the last 64 (family, kind, detail) notes."""
        with self._lock:
            return {**self._counts, "recent": list(self._recent)}

    def clear(self) -> None:
        with self._lock:
            self._counts = dict.fromkeys(self.KINDS, 0)
            self._recent.clear()


STAGE_OUTCOMES = StageOutcomes()
