"""stats-registry sync: RunStats keys emitted vs consumed vs documented.

The executor heartbeat (`_tpu_metrics`) forwards a fixed tuple of RunStats
keys as `tpu_*` gauges. Two drift modes have bitten:

- a consumer key nobody emits (gauge silently always absent — the
  `exchange_bytes_on_device` emission was nearly lost to a refactor and
  is invisible to grep because the `.set(` call spans lines), and
- an emitted key that is neither exported as a gauge nor documented in
  the one table of keys, docs/tpu_engine.md#observability (diagnostics
  nobody can discover).

So: every key `_tpu_metrics` consumes must be emitted somewhere under
`ops/tpu/`, and every emitted key must be consumed by `_tpu_metrics` OR
named (in backticks) in that section. Emission sites are found by AST —
`<anything>.set("key", ...)`-style calls where the receiver smells like a
stats sink (RUN_STATS / rec / stats / run-scope handles) and string
subscript stores on the same receivers.
"""

from __future__ import annotations

import ast
import os
import re

from ballista_tpu.analysis.core import AnalysisPass, Analyzer, Finding

EXEC_REL = "ballista_tpu/executor/executor_process.py"
DOCS_REL = "docs/tpu_engine.md"
DOCS_SECTION = "## Observability"

_SINK_NAMES = {"RUN_STATS", "rec", "stats", "run_stats", "_rec", "srec"}


def _receiver_name(node: ast.expr) -> str:
    if isinstance(node, ast.Name):
        return node.id
    if isinstance(node, ast.Attribute):
        return node.attr
    return ""


def emitted_keys(analyzer: Analyzer) -> dict[str, tuple[str, int]]:
    """key -> (rel, lineno) across ops/tpu/ modules."""
    out: dict[str, tuple[str, int]] = {}
    for src in analyzer.collect():
        if not src.rel.startswith("ballista_tpu/ops/tpu/"):
            continue
        tree = src.tree
        if tree is None:
            continue
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "set" and node.args:
                if _receiver_name(node.func.value) not in _SINK_NAMES:
                    continue
                k = node.args[0]
                if isinstance(k, ast.Constant) and isinstance(k.value, str):
                    out.setdefault(k.value, (src.rel, node.lineno))
            elif isinstance(node, (ast.Assign, ast.AugAssign)):
                targets = node.targets if isinstance(node, ast.Assign) else [node.target]
                for t in targets:
                    if isinstance(t, ast.Subscript) and \
                            _receiver_name(t.value) in _SINK_NAMES and \
                            isinstance(t.slice, ast.Constant) and \
                            isinstance(t.slice.value, str):
                        out.setdefault(t.slice.value, (src.rel, node.lineno))
    return out


def consumed_keys(analyzer: Analyzer) -> dict[str, int]:
    """key -> lineno consumed by _tpu_metrics: the gauge tuple iterated by
    its for-loop plus `"key" in stats` membership checks."""
    src = analyzer.file(EXEC_REL)
    out: dict[str, int] = {}
    if src is None or src.tree is None:
        return out
    fn = None
    for node in ast.walk(src.tree):
        if isinstance(node, ast.FunctionDef) and node.name == "_tpu_metrics":
            fn = node
            break
    if fn is None:
        return out
    for node in ast.walk(fn):
        if isinstance(node, ast.For) and isinstance(node.iter, (ast.Tuple, ast.List)):
            for elt in node.iter.elts:
                if isinstance(elt, ast.Constant) and isinstance(elt.value, str):
                    out.setdefault(elt.value, elt.lineno)
        elif isinstance(node, ast.Compare) and len(node.ops) == 1 \
                and isinstance(node.ops[0], ast.In) \
                and isinstance(node.left, ast.Constant) \
                and isinstance(node.left.value, str) \
                and _receiver_name(node.comparators[0]) in ("stats",):
            out.setdefault(node.left.value, node.lineno)
    return out


def documented_keys(analyzer: Analyzer) -> set[str]:
    """The backticked names in docs/tpu_engine.md's Observability section
    (its table of RunStats keys), up to the next section."""
    try:
        with open(os.path.join(analyzer.root, DOCS_REL)) as f:
            text = f.read()
    except OSError:
        return set()
    _, found, section = text.partition(DOCS_SECTION)
    if not found:
        return set()
    section = section.split("\n## ", 1)[0]
    return set(re.findall(r"`([A-Za-z_][A-Za-z_0-9]*)`", section))


class StatsRegistrySyncPass(AnalysisPass):
    pass_id = "stats-sync"
    doc = "RunStats keys: heartbeat consumers must be emitted; emissions documented"

    def run(self, analyzer: Analyzer) -> list[Finding]:
        findings: list[Finding] = []
        emitted = emitted_keys(analyzer)
        consumed = consumed_keys(analyzer)
        doc = documented_keys(analyzer)

        for key, lineno in sorted(consumed.items()):
            if key not in emitted:
                findings.append(Finding(
                    self.pass_id, EXEC_REL, lineno,
                    f"heartbeat gauge tpu_{key} consumes RunStats key '{key}' "
                    f"but nothing under ops/tpu/ emits it",
                    symbol=f"consumed:{key}",
                ))
        for key, (rel, lineno) in sorted(emitted.items()):
            if key in consumed or key in doc:
                continue
            findings.append(Finding(
                self.pass_id, rel, lineno,
                f"RunStats key '{key}' is emitted but neither exported by the "
                f"heartbeat nor documented in {DOCS_REL}#observability",
                symbol=f"emitted:{key}",
            ))
        return findings
