"""Compile-time stage facts for the TPU stage compiler.

A stage reaches the device one way: `stage_compiler` traces its whole
operator chain into one jitted function (`stage_partial_{direct,sorted}_
fused_xla`) and XLA fuses the intermediates. What is left here is pure
host-side logic (no jax imports at module scope) that runs BEFORE the
dispatch:

- `plan_spans`: walk a stage's operator chain and group it into SPANS —
  predicate (scan filters + FilterExec + join match masks), project
  (ProjectionExec rebinding), probe (HashJoinExec lookup+gather), aggregate
  (the partial agg). Consecutive ops of the same span kind merge; the span
  list is what RunStats `fused_spans` counts.
- `estimate_stage` / `estimate_sort_stage`: the `StageEstimate` that HBM
  admission (`hbm.plan_stage`) reads — the stage's working-set bytes and
  its grace-split target — from compile-time facts only (DeviceTable encode
  metadata + prepared BuildTables + the plan), so it is computable from a
  spec table during compile/fill overlap.
"""

from __future__ import annotations

from dataclasses import dataclass


def _pow2(n: int) -> int:
    p = 1
    while p < max(n, 1):
        p *= 2
    return p


PREDICATE = "predicate"
PROJECT = "project"
PROBE = "probe"
AGGREGATE = "aggregate"


@dataclass
class Span:
    """One fusible span of the operator chain."""

    kind: str  # predicate | project | probe | aggregate
    ops: int = 1  # plan nodes merged into this span


@dataclass
class StageEstimate:
    """What HBM admission reads of a stage (derivable from a spec
    DeviceTable, so the plan can be made during compile/fill overlap).

    table_bytes reproduces DeviceTable.nbytes exactly — data stacks +
    validity planes + row mask, all [P, N] — so it is computable from a
    spec table before the uploads drain; for an ORDER BY / window stage it
    is the device scratch of the key lanes. dict_bytes prices the string
    LUTs the stage uploads per dictionary column."""

    table_bytes: int = 0
    dict_bytes: int = 0
    build_bytes: int = 0  # all join build sides, device layout
    max_build_bytes: int = 0  # largest single build (the grace-split target)
    max_build_jidx: int = -1  # its join index, -1 when no builds
    has_mult: bool = False  # aggregate-through-join weights: no grace split
    # what the traced program holds beside its inputs (the sorted path's
    # [C] output lanes and its ordering's scratch): fixed, never split
    program_bytes: int = 0


def plan_spans(n_scan_filters: int, ops, agg) -> list[Span]:
    """Group the stage's op chain into fusible spans, dataflow order."""
    from ballista_tpu.plan.physical import (
        CoalesceBatchesExec,
        FilterExec,
        HashJoinExec,
        ProjectionExec,
    )

    spans: list[Span] = []

    def add(kind: str) -> None:
        if spans and spans[-1].kind == kind:
            spans[-1].ops += 1
        else:
            spans.append(Span(kind))

    for _ in range(max(0, int(n_scan_filters))):
        add(PREDICATE)
    for op in ops:
        if isinstance(op, CoalesceBatchesExec):
            continue
        if isinstance(op, FilterExec):
            add(PREDICATE)
        elif isinstance(op, HashJoinExec):
            add(PROBE)
        elif isinstance(op, ProjectionExec):
            add(PROJECT)
        else:
            add(PROJECT)  # unknown residuals lower like projections or raise later
    if agg is not None:
        add(AGGREGATE)
    return spans


def estimate_stage(ops, agg, dt, builds, program_bytes: int = 0) -> StageEstimate:
    """Build a StageEstimate from encode metadata + prepared builds, and
    the traced program's own bytes (`meta["program_bytes"]`)."""
    from ballista_tpu.plan.physical import HashJoinExec

    has_mult = False
    join_ops = [o for o in ops if isinstance(o, HashJoinExec)]
    if builds and join_ops:
        try:
            from ballista_tpu.ops.tpu.stage_compiler import _mult_shape_check

            cba = _mult_shape_check(agg, ops, join_ops[-1])
            has_mult = cba is not None and builds[-1].dup > 1
        except Exception:  # noqa: BLE001 — estimate only, never fail a stage
            has_mult = False

    import numpy as np

    P, N = dt.shape
    # mirror _load's nbytes accumulation term for term: data stacks,
    # validity planes of nullable columns, then the [P, N] row mask
    table_bytes = sum(P * N * np.dtype(c.dtype).itemsize for c in dt.cols)
    table_bytes += sum(P * N for v in dt.valids if v is not None)
    table_bytes += P * N
    # each dictionary column uploads a pow2-padded LUT; 8 B/slot covers the
    # widest remap target (int64 combined keys / i64 decode tables)
    dict_bytes = sum(_pow2(len(d)) * 8 for d in dt.dicts if d)
    build_bytes = 0
    max_build_bytes = 0
    max_build_jidx = -1
    for j, bt in enumerate(builds or []):
        b = sum(int(getattr(a, "nbytes", 0)) for a in bt.flat_arrays())
        dict_bytes += sum(_pow2(len(d)) * 8 for d in bt.dicts if d)
        build_bytes += b
        if b > max_build_bytes:
            max_build_bytes, max_build_jidx = b, j

    return StageEstimate(
        table_bytes=table_bytes,
        dict_bytes=dict_bytes,
        build_bytes=build_bytes,
        max_build_bytes=max_build_bytes,
        max_build_jidx=max_build_jidx,
        has_mult=has_mult,
        program_bytes=int(program_bytes),
    )


def estimate_sort_stage(n_rows: int, key_meta,
                        window_funcs: int = 0) -> StageEstimate:
    """StageEstimate for an ORDER BY / window stage (the device-permutation
    layout: only key lanes upload; payload columns stay host-side and are
    gathered by the returned permutation).

    `key_meta` is a sequence of (kind, nullable) per sort key — kind from
    the lane encoding (i64/date/money/f64/code/bool). Priced per padded
    lane (a power of two, so one compilation serves a bucket):

      per key: 8 B transformed i64 + 8 B null-rank tiebreak operand
               (+ 1 B NaN-disambiguation plane for f64 keys)
      fixed:   4 B position + 4 B permutation output
      scans:   per window function, 8 B value lanes + 8 B scan state
               + 4 B partition-boundary flags + 4 B peer-boundary flags
               (boundary planes ship as int32 lanes)

    The total lands in table_bytes so `hbm.plan_stage` admits the stage
    through the same ladder as aggregate stages (no grace rung: sorts
    have no splittable build side, so over-budget demotes to the CPU
    engine with the reason recorded)."""
    lanes = _pow2(max(int(n_rows), 1))
    per_key = 0
    for kind, nullable in key_meta:
        per_key += 8 + 8  # transformed key + tiebreak operand
        if kind == "f64":
            per_key += 1
        if nullable:
            per_key += 1
    scratch = lanes * (per_key + 4 + 4)
    scratch += int(window_funcs) * lanes * (8 + 8 + 4 + 4)
    return StageEstimate(table_bytes=scratch)
