"""Check ``mesh-axis``: every mesh-parallel call site names its mesh axis
(or carries a rationale).

Migrated from scripts/check_mesh_axis.py (ISSUE 13). Every
``shard_map``/``pjit`` call site names its axis: a literal
``P("dp")``-style spec or an ``axis``/``axis_name`` keyword in the call
text, or a ``# mesh-axis:`` comment within three lines above stating
where the axis lives — so a reader at the call site can always answer
"which leaves live on which axis".
"""
from __future__ import annotations

import ast
import re
from pathlib import Path
from typing import List, Tuple

from dist_dqn_tpu.analysis.core import AnalysisContext, Check, Finding
from dist_dqn_tpu.analysis.registry import register

SCAN_ROOTS = ("dist_dqn_tpu", "benchmarks", "__graft_entry__.py")
#: What names the axis inside the call text.
AXIS_IN_CALL = re.compile(r"""P\(\s*['"]|axis_name|axis\s*=""")
#: Rationale escape hatch for spec-variable call sites.
RATIONALE = re.compile(r"#.*mesh-axis:")


def _call_name(node: ast.Call) -> str:
    f = node.func
    if isinstance(f, ast.Attribute):
        return f.attr
    if isinstance(f, ast.Name):
        return f.id
    return ""


def _has_rationale(lines, lineno: int) -> bool:
    lo = max(lineno - 4, 0)
    return any(RATIONALE.search(ln) for ln in lines[lo:lineno])


def scan(repo_root: Path, ctx: AnalysisContext = None
         ) -> List[Tuple[str, int, str]]:
    """[(relpath, lineno, message), ...] for violating sites.
    Pass the run's shared ``ctx`` to reuse its parse cache."""
    if ctx is None:
        ctx = AnalysisContext(Path(repo_root))
    failures: List[Tuple[str, int, str]] = []
    for rel in ctx.iter_py_files(SCAN_ROOTS):
        if rel.startswith("dist_dqn_tpu/analysis/"):
            continue  # the lint layer DEFINES the patterns it hunts
        src = ctx.source(rel)
        lines = src.splitlines()
        try:
            tree = ctx.tree(rel)
        except SyntaxError as e:
            failures.append((rel, e.lineno or 0, "<unparseable>"))
            continue
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            if _call_name(node) not in ("shard_map", "pjit"):
                continue
            try:
                call_text = ast.get_source_segment(src, node) or ""
            except Exception:
                call_text = ""
            if AXIS_IN_CALL.search(call_text):
                continue
            if _has_rationale(lines, node.lineno):
                continue
            failures.append(
                (rel, node.lineno,
                 f"{_call_name(node)}(...) names no mesh axis — "
                 "put a literal axis spec in the call or a "
                 "'# mesh-axis: <where the specs name it>' comment "
                 "above it"))
    return failures


class MeshAxisCheck(Check):
    name = "mesh-axis"
    description = ("every shard_map/pjit call site names its mesh axis "
                   "or carries a '# mesh-axis:' rationale")
    rationale_tag = "mesh-axis:"

    def run(self, ctx: AnalysisContext) -> List[Finding]:
        findings = []
        for rel, lineno, msg in scan(ctx.root, ctx=ctx):
            # Line-text key: stable across unrelated edits (the
            # baseline contract), distinct per site.
            site = ctx.lines(rel)[lineno - 1].strip()[:80] \
                if lineno else ""
            findings.append(self.finding(rel, lineno, msg,
                                         key=f"mesh:{rel}:{site}"))
        return findings


register(MeshAxisCheck())
