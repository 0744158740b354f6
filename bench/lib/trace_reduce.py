"""From a profiler trace (`.xplane.pb`) to the numbers the benchmark reports.

The yardstick for device time: later PRs cannot change how busy, idle and
per-operation seconds are taken. Works on plain (start, duration, name) tuples
so the arithmetic is tested without a trace; `read_xplane` is the only part
that touches jax, and it only parses a file.

Window   first start to last end of the benchmark's own per-query
         annotations (`q1`, `q6`, ...) on the host planes.
Busy     union of the device-operation intervals on a device plane
         (`/device:TPU:n`, line `XLA Ops`), clipped to the window; averaged
         over the device planes that ran anything.
Ops      self seconds per operation, named `<module>/<op>` as the trace has
         them today (`jit_raw(..521708)/fusion.30`: XLA's module with the
         tail of its fingerprint, and the instruction — there are no named
         scopes in the program yet): an operation's duration minus the
         operations nested inside it on the same line (a `while` holds its
         body), so the list adds up to the busy time.
Gaps     the idle intervals of the busiest device plane, cut where one of the
         benchmark's annotations starts or ends, each piece labelled with the
         annotations that cover it (`q3.collect`, `q6.sql`,
         `between_queries`), summed by label.
"""

from __future__ import annotations

import bisect
import re

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
INNER = ("sql", "collect")


def read_xplane(path: str, queries: list[str]) -> dict:
    """Device operations per device plane and the benchmark's annotations:
    {"devices": {plane: [(start_ns, dur_ns, name)]}, "spans": [(start_ns,
    dur_ns, name)]}."""
    from jax.profiler import ProfileData

    wanted = set(queries) | set(INNER)
    devices: dict[str, list] = {}
    spans: list = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(DEVICE_PLANE):
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE not in lines:
                continue
            module_events = lines[MODULES_LINE].events if MODULES_LINE in lines else ()
            modules = sorted((e.start_ns, e.duration_ns, short_module(e.name))
                             for e in module_events)
            starts = [m[0] for m in modules]
            events = devices.setdefault(plane.name, [])
            for e in lines[OPS_LINE].events:
                i = bisect.bisect_right(starts, e.start_ns) - 1
                inside = i >= 0 and e.start_ns <= modules[i][0] + modules[i][1]
                op = e.name.split(" = ", 1)[0].lstrip("%")
                events.append((e.start_ns, e.duration_ns,
                               f"{modules[i][2]}/{op}" if inside else op))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.start_ns, e.duration_ns, e.name)
                             for e in line.events if e.name in wanted)
    return {"devices": devices, "spans": spans}


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
                 top: int = 10) -> dict | None:
    """The traced window's numbers; None when the trace holds no annotated
    query or no device operation."""
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
            "busy_s": sum(busy_ns.values()) / len(busy_ns) / 1e9,
            "device_planes": len(busy_ns),
            "device_events": sum(len(devices[p]) for p in busy),
            "queries_traced": len(marks),
            "device_ops": ranked(ops), "idle_gaps": ranked(gaps)}
