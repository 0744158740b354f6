"""Readers over the chips of a cell, from the traced window's device planes
(`lib/trace_reduce.reduce_trace`'s `plane_busy_s`: busy seconds a plane, every
file's planes on one clock)."""

from __future__ import annotations

from lib.readers import Run


def balance(run: Run) -> float | None:
    """Least over greatest of the chips' busy seconds in the traced window, in
    %: 100 where every chip of the cell was busy as long as the busiest, 0
    where one ran nothing. A chip the cell asked for whose plane is not in
    the trace (its holder ran no operation) counts as one that ran nothing."""
    busy = list(((run.trace or {}).get("plane_busy_s") or {}).values())
    if not busy or not max(busy):
        return None
    busy += [0.0] * (run.chips - len(busy))
    return 100.0 * min(busy) / max(busy)
