"""Check ``donation``: every jitted train/collect entry point must
declare explicit ``donate_argnums`` — or carry a ``donation:``
rationale comment.

Migrated from scripts/check_donation.py (ISSUE 13). ISSUE 6's aliasing
audit (utils/donation.py) verified the chunk programs donate their
GB-sized carries completely; what the runtime audit cannot do is stop
the NEXT train/collect jit from silently omitting the donation — the
failure mode is an HBM working set doubled on a chip that used to fit,
discovered as an OOM months later. This is the static half of the
guard.

AST-based: any ``jax.jit(...)`` call (or ``partial(jax.jit, ...)``)
whose jitted expression mentions ``train``/``collect``/``chunk``/
``shard`` is a learner/collector entry point and must either pass
``donate_argnums=`` explicitly, or be preceded (within two lines, or on
the same line) by a comment containing ``donation:`` stating why
nothing is donated. Functions named act/eval/sample are out of scope by
construction (their params ARE reused across calls — donating would be
the bug).
"""
from __future__ import annotations

import ast
import re
from pathlib import Path
from typing import List, Tuple

from dist_dqn_tpu.analysis.core import AnalysisContext, Check, Finding
from dist_dqn_tpu.analysis.registry import register

SCAN_ROOTS = ("dist_dqn_tpu", "benchmarks")

#: What makes a jitted expression a train/collect entry point.
#: ``shard`` joined in ISSUE 10: the data-parallel learners wrap their
#: train steps in closures named ``sharded`` (parallel/learner.py
#: make_sharded_train_step), which the train/collect/chunk patterns
#: would silently stop seeing. ``snapshot``/``lane`` joined in
#: ISSUE 15: the sharded-collect runtime's per-chunk param-snapshot
#: program (host_replay_loop.py snapshot_collect_params) and any
#: lane-block split dispatch are collect-side entry points whose
#: buffers are chunk-sized — a rename away from "collect" must not
#: drop them out of scope. ``population`` joined in ISSUE 20: the
#: stacked-member entry points (population.py run_population_chunk /
#: init_population) carry M whole fused carries — the costliest
#: working set in the repo; a rename away from "chunk" must keep them
#: in scope.
TARGET = re.compile(r"train|collect|chunk|shard|snapshot|lane|population")
#: Rationale escape hatch: a nearby comment owning the decision.
RATIONALE = re.compile(r"#.*donation:")


def _is_jit_call(node: ast.Call) -> bool:
    """True for ``jax.jit(...)`` / ``jit(...)`` and the
    ``partial(jax.jit, ...)`` spelling."""
    f = node.func
    if isinstance(f, ast.Attribute) and f.attr == "jit":
        return True
    if isinstance(f, ast.Name) and f.id == "jit":
        return True
    if isinstance(f, ast.Name) and f.id == "partial" and node.args:
        inner = node.args[0]
        return (isinstance(inner, ast.Attribute) and inner.attr == "jit") \
            or (isinstance(inner, ast.Name) and inner.id == "jit")
    return False


def _jitted_expr_text(node: ast.Call) -> str:
    """Source text of what is being jitted (first non-jax.jit arg)."""
    args = node.args
    if args and isinstance(args[0], (ast.Attribute, ast.Name)) \
            and getattr(args[0], "attr", getattr(args[0], "id", "")) \
            == "jit":
        args = args[1:]  # partial(jax.jit, ...) positional tail
    try:
        return " ".join(ast.unparse(a) for a in args)
    except Exception:
        return ""


def _has_rationale(lines, lineno: int) -> bool:
    """A ``donation:`` comment on the call line or the two above it."""
    lo = max(lineno - 3, 0)
    return any(RATIONALE.search(ln) for ln in lines[lo:lineno])


def scan(repo_root: Path, ctx: AnalysisContext = None
         ) -> List[Tuple[str, int, str]]:
    """[(relpath, lineno, jitted expr), ...] for violating sites.
    Pass the run's shared ``ctx`` to reuse its parse cache."""
    if ctx is None:
        ctx = AnalysisContext(Path(repo_root))
    failures: List[Tuple[str, int, str]] = []
    for rel in ctx.iter_py_files(SCAN_ROOTS):
        try:
            tree = ctx.tree(rel)
        except SyntaxError as e:
            failures.append((rel, e.lineno or 0, "<unparseable>"))
            continue
        src = ctx.source(rel)
        lines = src.splitlines()
        decorator_calls = set()
        # Decorator spellings: @jax.jit / @partial(jax.jit, ...) on
        # a def — the jitted expression is the function's own name.
        for node in ast.walk(tree):
            if not isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                continue
            for dec in node.decorator_list:
                is_call = isinstance(dec, ast.Call)
                if is_call and _is_jit_call(dec):
                    decorator_calls.add(id(dec))
                    kw = {k.arg for k in dec.keywords}
                elif isinstance(dec, ast.Attribute) \
                        and dec.attr == "jit":
                    kw = set()
                else:
                    continue
                if not TARGET.search(node.name):
                    continue
                if "donate_argnums" in kw:
                    continue
                if _has_rationale(lines, dec.lineno):
                    continue
                failures.append((rel, dec.lineno, node.name))
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and _is_jit_call(node)) \
                    or id(node) in decorator_calls:
                continue
            expr = _jitted_expr_text(node)
            if not TARGET.search(expr):
                continue
            kw = {k.arg for k in node.keywords}
            if "donate_argnums" in kw:
                continue
            if _has_rationale(lines, node.lineno):
                continue
            failures.append((rel, node.lineno, expr.split("\n")[0]))
    return failures


class DonationCheck(Check):
    name = "donation"
    description = ("every jitted train/collect entry point declares "
                   "donate_argnums or a '# donation:' rationale (HBM "
                   "working-set guard)")
    rationale_tag = "donation:"

    def run(self, ctx: AnalysisContext) -> List[Finding]:
        findings = []
        for rel, lineno, expr in scan(ctx.root, ctx=ctx):
            findings.append(self.finding(
                rel, lineno,
                f"jax.jit({expr!r}) is a train/collect entry point "
                "without explicit donate_argnums — donate the carry/"
                "state (in-place HBM update) or add a '# donation: "
                "<why not>' rationale comment (docs/performance.md, "
                "learner utilization)",
                key=f"jit:{rel}:{expr[:60]}"))
        return findings


register(DonationCheck())
