"""Readers over the program's own spans (ballista_tpu/tracing.py).

After every query the topology's `Probes.run_stats_stages()` hands back
`RUN_STATS.stages()`; a program that records spans leaves one record there
under the tag `job_<job id>`:

    {"spans": [[name, id, parent, start_s, end_s, stage, task, numbers], ...],
     "spans_dropped": n}

seconds of one monotonic clock, all threads of the process. These readers
take that list as it is (only the first seven fields are read) and work on
plain lists, so the arithmetic is tested without the program. A program
that records no spans leaves no such record: every reader then returns None
and the metric is left out of the line.

self        a span's duration minus the part of it that its CHILD spans
            (those naming it as parent) cover, children on other threads
            included (`choosing-metrics` section 4). Spans that overlap in
            time on different threads each count: thread-seconds, not wall.
duration    whole durations, summed.
count       how many.
uncovered   the root span's wall time in which no other span of the record
            was open on any thread, the spans in `waiting` left out (they
            only wait for the others): what the tracing cannot see yet.
"""

from __future__ import annotations

from lib.readers import Run, _per

NAME, ID, PARENT, START, END = 0, 1, 2, 3, 4


def covered(intervals: list[tuple[float, float]], lo: float, hi: float) -> float:
    """Length of the union of `intervals` inside [lo, hi]."""
    total = 0.0
    edge = lo
    for a, b in sorted(intervals):
        a, b = max(a, edge), min(b, hi)
        if b > a:
            total += b - a
            edge = b
    return total


def job_records(run: Run, phase: str) -> tuple[list[list], int]:
    """The span lists of the executions in `phase`, one a job, and how many
    executions there were."""
    execs = run.executions(phase)
    records = [rec["spans"] for e in execs for tag, rec in e["stages"].items()
               if tag.startswith("job_") and isinstance(rec.get("spans"), list)]
    return records, len(execs)


def self_of(spans: list, names: set[str]) -> float:
    """Self seconds of the spans of one record whose name is in `names`."""
    children: dict = {}
    for s in spans:
        if s[PARENT] is not None:
            children.setdefault(s[PARENT], []).append((s[START], s[END]))
    return sum((s[END] - s[START]) - covered(children.get(s[ID], []), s[START], s[END])
               for s in spans if s[NAME] in names)


def uncovered_of(spans: list, root: str, waiting: set[str]) -> float | None:
    """Seconds of the record's root span during which no other span, the
    waiting ones left out, was open; None when the record has no such root."""
    roots = [s for s in spans if s[NAME] == root]
    if not roots:
        return None
    top = max(roots, key=lambda s: s[END] - s[START])
    others = [(s[START], s[END]) for s in spans
              if s is not top and s[NAME] != root and s[NAME] not in waiting]
    return (top[END] - top[START]) - covered(others, top[START], top[END])


def _reduce(run: Run, phase: str, per: str, scale: float, of) -> float | None:
    records, n_exec = job_records(run, phase)
    values = [v for v in map(of, records) if v is not None]
    if not values:
        return None
    value = _per(float(sum(values)), run, phase, per, n_exec)
    return None if value is None else scale * value


def self_seconds(run: Run, names: list[str], phase: str = "window", per: str = "query",
                 scale: float = 1.0) -> float | None:
    wanted = set(names)
    return _reduce(run, phase, per, scale, lambda spans: self_of(spans, wanted))


def duration(run: Run, names: list[str], phase: str = "window", per: str = "query",
             scale: float = 1.0) -> float | None:
    wanted = set(names)
    return _reduce(run, phase, per, scale, lambda spans: sum(
        s[END] - s[START] for s in spans if s[NAME] in wanted))


def count(run: Run, names: list[str], phase: str = "window",
          per: str = "query") -> float | None:
    wanted = set(names)
    return _reduce(run, phase, per, 1.0, lambda spans: float(sum(
        1 for s in spans if s[NAME] in wanted)))


def uncovered(run: Run, root: str, phase: str = "window", per: str = "query",
              scale: float = 1.0, waiting: list[str] | None = None) -> float | None:
    idle = set(waiting or ())
    return _reduce(run, phase, per, scale, lambda spans: uncovered_of(spans, root, idle))
