"""Readers: each per-layer metric is one file `bench/metrics/<name>.json`
that names its reader as `<module of bench/lib>:<function>` with its
arguments — `readers:runstats_sum` is here; a later reader comes as a module
of its own. A reader gets the run (`Run`) and returns a number, or None when
there is nothing to read — the harness then leaves the metric out of the
line; a share of a roofline is never reported as 0 for lack of a trace.
"""

from __future__ import annotations

import statistics
from dataclasses import dataclass


@dataclass
class Run:
    record: dict          # what bench/cell.py saw (cell.json)
    trace: dict | None    # lib/trace_reduce.reduce_trace's numbers
    round_bytes: float    # lib/work.py: bytes one round's queries must read once
    peaks: dict           # this device's row of lib/peaks.json
    chips: int = 1        # the chips the cell asked for

    def executions(self, phase: str, query: str | None = None) -> list[dict]:
        return [e for e in self.record["executions"]
                if e["phase"] == phase and not e["failed"]
                and (query is None or e["query"] == query)]

    def rounds(self, phase: str) -> int:
        return len({e["round"] for e in self.record["executions"] if e["phase"] == phase})


def _per(total: float, run: Run, phase: str, per: str, n_exec: int) -> float | None:
    count = {"total": 1, "query": n_exec, "round": run.rounds(phase)}[per]
    return total / count if count else None


def runstats_sum(run: Run, keys: list[str], phase: str, per: str) -> float | None:
    """Sum of RunStats `keys` over the stages of each execution in `phase`,
    per query executed, per round, or in total."""
    execs = run.executions(phase)
    values = [rec[k] for e in execs for rec in e["stages"].values()
              for k in keys if isinstance(rec.get(k), (int, float))]
    if not values:
        return None
    return _per(float(sum(values)), run, phase, per, len(execs))


def query_median(run: Run, query: str, field: str = "collect_s",
                 phase: str = "window") -> float | None:
    values = [e[field] for e in run.executions(phase, query)]
    return statistics.median(values) if values else None


def span_mean(run: Run, field: str, phase: str = "window", scale: float = 1.0) -> float | None:
    values = [e[field] for e in run.executions(phase)]
    return scale * statistics.fmean(values) if values else None


def cold_extra(run: Run) -> float | None:
    """What being first adds to a query: the first round's seconds minus the
    median window round's, over the queries of a round."""
    rounds: dict[int, list[float]] = {}
    for e in run.record["executions"]:
        if e["phase"] == "window":
            rounds.setdefault(e["round"], []).append(e["seconds"])
    first = run.executions("first")
    if not rounds or not first or "first_round_s" not in run.record:
        return None
    hot_round = statistics.median(sum(v) for v in rounds.values())
    return (run.record["first_round_s"] - hot_round) / len(first)


def outcomes_delta(run: Run, kinds: list[str], phase: str = "window",
                   per: str = "round") -> float | None:
    execs = [e for e in run.record["executions"] if e["phase"] == phase]
    if not execs:
        return None
    total = float(sum(e["outcomes"].get(k, 0) for e in execs for k in kinds))
    return _per(total, run, phase, per, len(execs))


def cache_delta(run: Run, key: str = "requests", span: str = "window_cache") -> float | None:
    counts = run.record.get(span)
    return float(counts[key]) if counts else None


def memory_stat(run: Run, key: str, scale: float = 1.0) -> float | None:
    value = run.record.get("memory_stats", {}).get(key)
    return scale * value if value is not None else None


def trace_idle(run: Run) -> float | None:
    """Share of the traced window in which no operation ran on the device."""
    if not run.trace:
        return None
    return 100.0 * (1.0 - run.trace["busy_s"] / run.trace["window_s"])


def trace_roofline(run: Run, peak: str = "hbm_bytes_per_s") -> float | None:
    """The least time the cell's chips could take for the traced rounds (their
    bytes read once over the chips' peak together) as a share of the time a
    chip was busy: the same bytes over the chips the cell pays for, whatever
    the program does with them."""
    traced = run.record.get("traced")
    if not run.trace or not traced or not run.trace["busy_s"]:
        return None
    least_s = traced["rounds"] * run.round_bytes / (run.chips * run.peaks[peak])
    return 100.0 * least_s / run.trace["busy_s"]
