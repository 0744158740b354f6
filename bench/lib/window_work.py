"""The window family's share of a roofline: what `window_roofline` reads.

Bytes: what a window stage must move once, whatever implements it — every row's
PARTITION BY and ORDER BY columns read and the window function's value written.
The rows are the generator's own count, taken through `lib/work.py`'s
`round_bytes` (rows x the widths of the columns the round's SQL names, which
for a round of window queries over one table are the `reads`); the widths come
from the generator's schema file. Nothing asks the program's counters.

Seconds: the device time of the traced rounds' operations whose module is one
of `modules` (`jit_sort_lex_order(..)/...`, `jit_window_segscan_sum(..)/...`):
the reduced trace lists a run's ten longest operations by name
(`trace_reduce.reduce_trace`, `device_ops`) and keeps the rest only in the busy
total, so the operations it does not list cannot be told apart and are counted
in: the share is never overstated by what the list leaves out. A trace that
lists no operation of `modules` gives nothing, and the metric is left out.
"""

from __future__ import annotations

import os

from lib import load_json
from lib.readers import Run

LIB = os.path.dirname(os.path.abspath(__file__))


def stage_bytes(round_bytes: float, schema: dict, table: str, reads: list[str],
                written_bytes: float) -> float:
    """Bytes a round's window stages must move once: `round_bytes` are the
    reads (rows x their widths); each row also has `written_bytes` written."""
    width = sum(schema["tables"][table][c]["bytes"] for c in reads)
    return round_bytes + (round_bytes / width) * written_bytes


def device_seconds(trace: dict, modules: list[str]) -> float | None:
    """Device seconds of the reduced trace's operations under `modules`, the
    unlisted remainder of the busy time counted in; None where the list names
    no such operation."""
    listed = {name: s for name, s in trace.get("device_ops", [])}
    mine = sum(s for name, s in listed.items() if name.startswith(tuple(modules)))
    if not mine:
        return None
    busy = sum(trace.get("plane_busy_s", {}).values()) or trace["busy_s"]
    return mine + max(0.0, busy - sum(listed.values()))


def roofline(run: Run, table: str, reads: list[str], written_bytes: float, schema: str,
             modules: list[str], peak: str = "hbm_bytes_per_s") -> float | None:
    traced = run.record.get("traced")
    if not run.trace or not traced or not run.round_bytes or peak not in run.peaks:
        return None
    seconds = device_seconds(run.trace, modules)
    if not seconds:
        return None
    moved = traced["rounds"] * stage_bytes(run.round_bytes, load_json(os.path.join(LIB, schema)),
                                           table, reads, written_bytes)
    return 100.0 * moved / (run.chips * run.peaks[peak]) / seconds
