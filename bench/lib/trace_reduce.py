"""From a profiler trace (`.xplane.pb`) to the numbers the benchmark reports.

The yardstick for device time: later PRs cannot change how busy, idle and
per-operation seconds are taken. Works on plain (start, duration, name) tuples
so the arithmetic is tested without a trace; `read_xplane` is the only part
that touches jax, and it only parses a file.

Files    a run's trace is every `.xplane.pb` its processes wrote: one set
         of planes (a plane's key is `<file>:<plane>`, so two processes'
         `/device:TPU:0` stay apart) and one list of annotations, on one
         clock (`merge_files`). A file counts nanoseconds from its own
         profiler session's start and states that start on the wall clock
         (plane `Task Environment`, `profile_start_time`): the common clock
         is nanoseconds since the earliest file's start.
Window   first start to last end of the benchmark's own per-query
         annotations (`q1`, `q6`, ...) on the host planes; where no file
         holds one (the process that runs the queries traced nothing), of
         the same marks as the record has them on the wall clock.
Busy     union of the device-operation intervals on a device plane
         (`/device:TPU:n`, line `XLA Ops`), clipped to the window; summed
         over the planes and divided by the chips the cell asked for (or by
         the planes that ran anything, where those are more): a chip that
         idled through the window is idle, not absent.
Ops      self seconds per operation, named `<module>/<op>` as the trace has
         them today (`jit_raw(..521708)/fusion.30`: XLA's module with the
         tail of its fingerprint, and the instruction — there are no named
         scopes in the program yet): an operation's duration minus the
         operations nested inside it on the same line (a `while` holds its
         body), so the list adds up to the busy time.
Gaps     the idle intervals of the busiest device plane (`gaps_plane`), cut
         where one of the benchmark's annotations starts or ends, each piece
         labelled with the annotations that cover it (`q3.collect`, `q6.sql`,
         `between_queries`), summed by label.
"""

from __future__ import annotations

import bisect
import glob
import os
import re

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
INNER = ("sql", "collect")
SESSION_PLANE = "Task Environment"
SESSION_START = "profile_start_time"


def device_lines(plane) -> tuple | None:
    """The operation and module events of a device plane, None for any other
    plane (the one place that says what a device plane looks like)."""
    if not plane.name.startswith(DEVICE_PLANE):
        return None
    lines = {line.name: line for line in plane.lines}
    if OPS_LINE not in lines:
        return None
    return lines[OPS_LINE].events, lines[MODULES_LINE].events if MODULES_LINE in lines else ()


def read_xplane(path: str, queries: list[str]) -> dict:
    """Device operations per device plane, the benchmark's annotations, and
    the wall-clock time (ns) the file's own clock starts at, None where the
    file does not state it: {"devices": {plane: [(start_ns, dur_ns, name)]},
    "spans": [(start_ns, dur_ns, name)], "start_wall_ns": n}."""
    from jax.profiler import ProfileData

    wanted = set(queries) | set(INNER)
    devices: dict[str, list] = {}
    spans: list = []
    start_wall_ns = None
    for plane in ProfileData.from_file(path).planes:
        device = device_lines(plane)
        if device is not None:
            ops, module_events = device
            modules = sorted((e.start_ns, e.duration_ns, short_module(e.name))
                             for e in module_events)
            starts = [m[0] for m in modules]
            events = devices.setdefault(plane.name, [])
            for e in ops:
                i = bisect.bisect_right(starts, e.start_ns) - 1
                inside = i >= 0 and e.start_ns <= modules[i][0] + modules[i][1]
                op = e.name.split(" = ", 1)[0].lstrip("%")
                events.append((e.start_ns, e.duration_ns,
                               f"{modules[i][2]}/{op}" if inside else op))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.start_ns, e.duration_ns, e.name)
                             for e in line.events if e.name in wanted)
        elif plane.name == SESSION_PLANE:
            start_wall_ns = dict(plane.stats).get(SESSION_START)
    return {"devices": devices, "spans": spans, "start_wall_ns": start_wall_ns}


def read_files(trace_dir: str, queries: list[str]) -> dict[str, dict]:
    """`read_xplane` of every `.xplane.pb` under `trace_dir`, by the file's
    path there less its ending, each with its size under "bytes"."""
    files = {}
    for path in sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)):
        name = os.path.relpath(path, trace_dir)[:-len(".xplane.pb")]
        files[name] = {**read_xplane(path, queries), "bytes": os.path.getsize(path)}
    return files


def merge_files(files: dict[str, dict], wall_spans: list, queries: list[str]) -> dict:
    """One trace from several files' (`read_xplane`'s dictionaries by file
    name): every plane under `<file>:<plane>` and every annotation, on the
    common clock. Where no file holds an annotation of `queries`, the marks
    are `wall_spans`: the same spans as the record has them, wall-clock ns.
    One file that holds its own annotations keeps its own clock (offset 0):
    it needs no wall clock and reduces as it always did."""
    starts = {f: raw["start_wall_ns"] for f, raw in files.items()}
    known = [s for s in starts.values() if s is not None]
    if len(files) > 1 and len(known) < len(files):
        raise ValueError(f"trace files that do not say when they start cannot be merged: "
                         f"{sorted(f for f, s in starts.items() if s is None)}")
    base = min(known, default=None)
    devices: dict[str, list] = {}
    spans: list = []
    for f, raw in files.items():
        shift = starts[f] - base if base is not None else 0
        for plane, events in raw["devices"].items():
            devices[f"{f}:{plane}"] = [(s + shift, d, n) for s, d, n in events]
        spans.extend((s + shift, d, n) for s, d, n in raw["spans"])
    marks = "annotations"
    if not any(n in queries for _, _, n in spans):
        marks = "record"
        spans = [(s - base, d, n) for s, d, n in wall_spans] if base is not None else []
    return {"devices": devices, "spans": spans, "marks": marks, "base_wall_ns": base}


def short_module(name: str) -> str:
    """`jit_raw(1779999857728521708)` -> `jit_raw(..521708)`."""
    return re.sub(r"\((\d+)\)$", lambda m: f"(..{m.group(1)[-6:]})", name)


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Sorted, disjoint intervals covering the same points."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def self_seconds(events: list[tuple[float, float, str]], lo: float, hi: float) -> dict[str, float]:
    """Self nanoseconds by name of the events inside [lo, hi]: nested events
    (fully inside an earlier, longer one) are taken off their parent."""
    total: dict[str, float] = {}
    stack: list[tuple[float, str]] = []  # (end, name)
    for start, dur, name in sorted(events, key=lambda e: (e[0], -e[1])):
        if start < lo or start + dur > hi:
            continue
        while stack and stack[-1][0] <= start:
            stack.pop()
        if stack and start + dur <= stack[-1][0]:
            total[stack[-1][1]] = total.get(stack[-1][1], 0.0) - dur
        total[name] = total.get(name, 0.0) + dur
        stack.append((start + dur, name))
    return total


def label_at(t: float, spans: list[tuple[float, float, str]], queries: set[str]) -> str:
    query = inner = None
    for start, dur, name in spans:
        if start <= t <= start + dur:
            if name in queries:
                query = name
            else:
                inner = name
    if query is None:
        return "between_queries"
    return f"{query}.{inner}" if inner else query


def reduce_trace(devices: dict[str, list], spans: list, queries: list[str],
                 chips: int = 1, top: int = 10) -> dict | None:
    """The traced window's numbers for a cell that asked for `chips`; None
    when the trace holds no annotated query or no device operation."""
    names = set(queries)
    marks = [(s, s + d) for s, d, n in spans if n in names]
    if not marks or not any(devices.values()):
        return None
    lo, hi = min(a for a, _ in marks), max(b for _, b in marks)
    busy: dict[str, list] = {}
    for plane, events in devices.items():
        covered = union(clip([(s, s + d) for s, d, _ in events], lo, hi))
        if covered:
            busy[plane] = covered
    if not busy:
        return None
    busy_ns = {p: sum(b - a for a, b in iv) for p, iv in busy.items()}
    main = max(busy_ns, key=busy_ns.get)
    ops: dict[str, float] = {}
    for plane in busy:
        for name, ns in self_seconds(devices[plane], lo, hi).items():
            ops[name] = ops.get(name, 0.0) + ns
    gaps: dict[str, float] = {}
    edges = sorted({t for s, d, _ in spans for t in (s, s + d)})
    edge = lo
    for a, b in busy[main] + [(hi, hi)]:
        if a > edge:  # an idle interval, cut where an annotation starts or ends
            cuts = [edge, *(t for t in edges if edge < t < a), a]
            for left, right in zip(cuts, cuts[1:]):
                label = label_at((left + right) / 2, spans, names)
                gaps[label] = gaps.get(label, 0.0) + (right - left)
        edge = max(edge, b)

    def ranked(d: dict[str, float]) -> list:
        return [[k, v / 1e9] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {"window_s": (hi - lo) / 1e9,
            "busy_s": sum(busy_ns.values()) / max(chips, len(busy_ns)) / 1e9,
            "device_planes": len(busy_ns),
            "device_events": sum(len(devices[p]) for p in busy),
            "queries_traced": len(marks),
            "device_ops": ranked(ops), "idle_gaps": ranked(gaps),
            "chips": chips, "gaps_plane": main,
            "plane_busy_s": {p: busy_ns.get(p, 0.0) / 1e9 for p in devices}}
