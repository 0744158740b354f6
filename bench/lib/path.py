"""Readers over a query's critical path: which spans the client waited for.

The span readers of `lib/spans.py` sum a query's spans over its tasks, and
tasks run side by side: thread-seconds. These readers answer the other
question — of the wall time of a `collect()`, how much was spent where —
by walking the `job_<id>` record (the rows `lib/spans.py` describes) back
from the root span's end. The rule is the standard one for span trees and
is the benchmark's own plain-list copy (the program has one of its own in
`ballista_tpu/tracing.py`; `bench/tests/test_path.py` holds the two equal):

  Standing in span S at time t, the next span on the path is the candidate
  with the latest end <= t (+ TOLERANCE_S: a span that closes on another
  thread or in another process may end a little after its successor says
  it started) that starts before t. S owns (that end, t]; the candidate is
  walked over its own interval, clipped to S's; S goes on from the
  candidate's start. A candidate still running at t is a neighbour, not a
  predecessor: with four task slots the path leaves a task at its start
  and enters the task whose end freed the slot. What no candidate covers
  is S's own.

  WAITING spans (`bt.client.wait`, `bt.sched.stage`) only wait for others
  and never hide work: S's candidates are its children and, through a
  waiting child, that child's children, recursively — wherever the waiting
  spans were hung. Time no candidate covers goes to the innermost
  (shortest) waiting span open then, else to S.

The segments tile the root span's interval, so the seconds by span name sum
to the query's `collect()` wall. A record with no root span has no path and
every reader returns None; once there is a path, a span name the cell never
records counts 0.0.
"""

from __future__ import annotations

from lib.readers import Run
from lib.spans import END, ID, NAME, PARENT, START, _reduce, covered

ROOT = "bt.client.collect"
WAITING = ("bt.client.wait", "bt.sched.stage")
TOLERANCE_S = 0.0005
WRITE = "bt.shuffle.write"
NUMBERS = 7


def critical_path(spans: list, root: str = ROOT, waiting: tuple = WAITING,
                  tolerance_s: float = TOLERANCE_S) -> list[list] | None:
    """The segments [start_s, end_s, span id, name] of the record's critical
    path, in time order; None when the record has no span named `root`."""
    tops = [s for s in spans if s[NAME] == root]
    if not tops:
        return None
    top = max(tops, key=lambda s: s[END] - s[START])
    children: dict = {}
    for s in spans:
        if s[PARENT] is not None and s is not top:
            children.setdefault(s[PARENT], []).append(s)
    waits = set(waiting)
    found: list[list] = []  # latest first

    def own(span, looked, lo, hi):
        if hi <= lo:
            return
        cuts = sorted({lo, hi, *(x for w in looked for x in (w[START], w[END]) if lo < x < hi)})
        for a, b in zip(cuts[-2::-1], cuts[:0:-1]):
            open_then = [w for w in looked if w[START] <= a and b <= w[END]]
            w = (min(open_then, key=lambda w: (w[END] - w[START], w[ID]))
                 if open_then else span)
            found.append([a, b, w[ID], w[NAME]])

    def walk(span, lo, hi):
        candidates, looked = [], []
        stack = list(children.get(span[ID], ()))
        while stack:
            c = stack.pop()
            if c[NAME] in waits:
                looked.append(c)
                stack.extend(children.get(c[ID], ()))
            else:
                candidates.append(c)
        candidates.sort(key=lambda c: (c[END], c[ID]), reverse=True)
        t = hi
        for c in candidates:
            if t <= lo:
                break
            if c[END] > t + tolerance_s or c[START] >= t or c[END] <= lo:
                continue
            end, start = min(c[END], t), max(c[START], lo)
            own(span, looked, end, t)
            walk(c, start, end)
            t = start
        own(span, looked, lo, t)

    walk(top, top[START], top[END])
    segments: list[list] = []
    for seg in reversed(found):
        if segments and segments[-1][2] == seg[2] and segments[-1][1] == seg[0]:
            segments[-1][1] = seg[1]
        else:
            segments.append(seg)
    return segments


def by_name(segments: list[list]) -> dict[str, float]:
    total: dict[str, float] = {}
    for a, b, _, name in segments:
        total[name] = total.get(name, 0.0) + (b - a)
    return total


def write_split(spans: list, segments: list[list]) -> tuple[float, float] | None:
    """The path seconds inside `bt.shuffle.write` spans as (the operators'
    share, the rest). A write span states `ops_ms`, the self time of the
    operators pulled through it that have no span of their own; its share of
    the span's path seconds is `ops_ms` over the span's self milliseconds
    (duration less what its child spans cover), at most all of it. None when
    a write span on the path states no `ops_ms` (a program that does not
    record it)."""
    on_path: dict = {}
    for a, b, sid, name in segments:
        if name == WRITE:
            on_path[sid] = on_path.get(sid, 0.0) + (b - a)
    kids: dict = {}
    for s in spans:
        if s[PARENT] in on_path:
            kids.setdefault(s[PARENT], []).append((s[START], s[END]))
    ops = rest = 0.0
    for s in spans:
        seconds = on_path.get(s[ID])
        if seconds is None:
            continue
        numbers = s[NUMBERS] if len(s) > NUMBERS and s[NUMBERS] else {}
        if "ops_ms" not in numbers:
            return None
        self_s = (s[END] - s[START]) - covered(kids.get(s[ID], []), s[START], s[END])
        share = min(1.0, numbers["ops_ms"] / 1e3 / self_s) if self_s > 0 else 0.0
        ops += seconds * share
        rest += seconds * (1.0 - share)
    return ops, rest


def seconds(run: Run, names: list[str] | None = None, but: list[str] | None = None,
            write: str | None = None, phase: str = "window", per: str = "query",
            scale: float = 1.0) -> float | None:
    """Path seconds of the spans named in `names` — or, with `but`, of every
    span NOT named there (the root's own and whatever is new: so the metrics
    that partition the names sum to the query's wall) — a window query.
    `write` adds a part of the path inside `bt.shuffle.write` (which neither
    list should then name): "ops" the operators' share, "rest" the rest; a
    program that states no `ops_ms` has no "ops" (None) and its "rest" is the
    whole."""
    wanted, left_out = set(names or ()), set(but or ())

    def of(spans):
        segments = critical_path(spans)
        if segments is None:
            return None
        totals = by_name(segments)
        if but is not None:
            value = sum(v for k, v in totals.items() if k not in left_out)
        else:
            value = sum(totals.get(k, 0.0) for k in wanted)
        if write is not None:
            split = write_split(spans, segments)
            if split is None:
                if write == "ops":
                    return None
                split = (0.0, totals.get(WRITE, 0.0))
            value += split[0] if write == "ops" else split[1]
        return value

    return _reduce(run, phase, per, scale, of)


def number_sum(run: Run, name: str, key: str, phase: str = "window", per: str = "query",
               scale: float = 1.0) -> float | None:
    """Sum of the number `key` over the spans named `name`, a window query
    (thread-time where tasks overlap); None where no such span states it."""
    def of(spans):
        values = [s[NUMBERS][key] for s in spans
                  if s[NAME] == name and len(s) > NUMBERS and s[NUMBERS]
                  and isinstance(s[NUMBERS].get(key), (int, float))]
        return float(sum(values)) if values else None

    return _reduce(run, phase, per, scale, of)
