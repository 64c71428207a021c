"""Check ``metrics``: no NEW JSON-line metric emission bypassing the
telemetry registry, and no ``dqn_*`` family undocumented in
docs/observability.md.

Migrated from scripts/check_metrics.py (ISSUE 13) with the logic and
both allowlists intact; the history and rationale live in the original
docstrings below. ISSUE 1 unified metrics behind ``dist_dqn_tpu/
telemetry`` — new code records through the registry, not more ad-hoc
``print(json.dumps(...))`` / ``log_fn(json.dumps(...))`` call sites
scrapers can't see; ISSUE 5 added the docs-drift half (every registered
``dqn_*`` family must appear in docs/observability.md or carry a
DOCS_ALLOWLIST rationale).
"""
from __future__ import annotations

import re
from pathlib import Path
from typing import Dict, List, Set

from dist_dqn_tpu.analysis.core import (AnalysisContext, Check, Finding,
                                        count_matches)
from dist_dqn_tpu.analysis.registry import register

PATTERN = re.compile(r"(?:print|log_fn)\(json\.dumps")

#: Registry registration with a literal family name. ``\s`` spans
#: newlines, so multi-line calls are covered.
REGISTRATION = re.compile(
    r"\.(?:counter|gauge|histogram)\(\s*[\"'](dqn_[a-z0-9_]+)[\"']")
#: Canonical name constants in telemetry/collectors.py (including the
#: ``NAME = \`` + next-line-string spelling).
CONSTANT = re.compile(
    r"^[A-Z0-9_]+\s*=\s*(?:\\\s*)?[\"'](dqn_[a-z0-9_]+)[\"']", re.M)

#: dqn_* families allowed to be absent from docs/observability.md,
#: each with the reason it stays undocumented.
DOCS_ALLOWLIST = {
    # Internal plumbing of the span tracer: a scratch gauge the
    # MetricLogger uses to mirror counter-style extras; not a scrape
    # surface anyone should alert on (utils/trace.py).
    "dqn_trace_counter",
}

#: file (repo-relative, posix) -> call sites grandfathered at ISSUE 1.
ALLOWLIST = {
    "benchmarks/ale_learning.py": 2,
    "benchmarks/apex_feeder_bench.py": 1,
    "benchmarks/apex_split_bench.py": 2,
    "benchmarks/contract.py": 1,  # ContractEmitter: the contract line
    "benchmarks/host_replay_bench.py": 1,
    "benchmarks/learner_bench.py": 3,
    "benchmarks/pong_learning.py": 2,
    "benchmarks/r2d2_pixel_learning.py": 1,
    # +1 at ISSUE 18: the sharded arm's per-grid BENCH row line — a CLI
    # output contract like the per-impl rows; the device-sampling
    # runtime metrics go through the registry
    # (dqn_replay_device_sample_seconds / _writeback_rows_total).
    "benchmarks/sampler_bench.py": 2,
    # ISSUE 7: the per-arm BENCH row line (the contract line goes
    # through contract.ContractEmitter, counted there) — CLI
    # output contracts; the serving metrics themselves go through the
    # registry (dqn_serving_*).
    "benchmarks/serving_bench.py": 1,
    "dist_dqn_tpu/actors/remote.py": 1,
    # The one device line every entry point logs first (log_device).
    "dist_dqn_tpu/utils/backend.py": 1,
    # +2 at ISSUE 8: the ingest_degraded alarm transitions (one line
    # per episode edge, state changes — the continuous signal is the
    # dqn_ingest_degraded gauge).
    "dist_dqn_tpu/actors/service.py": 5,
    # ISSUE 8: the one-per-episode transport shedding alarm (the
    # per-record stream is dqn_transport_tcp_shed_total).
    "dist_dqn_tpu/actors/transport.py": 1,
    "dist_dqn_tpu/atari57.py": 7,
    # +1 at ISSUE 4: the telemetry_port announcement line (a CLI output
    # contract like train.py's, not a metric — the metrics themselves go
    # through the registry the flag exposes).
    "dist_dqn_tpu/evaluate.py": 2,
    # +2 at ISSUE 8: the resumed_at_frames and per-save checkpoint
    # announcement lines (run-lifecycle output contracts, mirroring
    # train.py's resume line; the chaos/crash metrics go through the
    # registry). +1 at ISSUE 19: the one-shot profile_trace
    # announcement after the --profile-dir first-chunk capture lands
    # (a path, not a metric; chip-time metrics go through the
    # registry's dqn_program_*/dqn_chip_* families).
    "dist_dqn_tpu/host_replay_loop.py": 4,
    # ISSUE 7: the serving CLI's startup announcements (serving_port +
    # optional telemetry_port) — output contracts like train.py's; act
    # metrics go through the registry. +1 at ISSUE 8: the shutdown
    # serving_drained line (graceful-drain outcome contract).
    "dist_dqn_tpu/serving/__main__.py": 3,
    # +1 at ISSUE 4: the one-per-run {"manifest": ...} provenance line
    # (telemetry/manifest.py) — run identity, not a metric stream.
    # +4 at ISSUE 20: the population loop's telemetry_port /
    # resumed_at_frames / profile_trace announcements and its per-chunk
    # metric row — the same output contracts as the solo loop's sites;
    # the population metrics themselves go through the registry
    # (dqn_population_*). +1 at ISSUE 33: the one-per-run ring_boundary
    # set-up line (the two padded byte counts the boundary-layout rule
    # compared — what the run IS, not a stream; whether it engaged goes
    # through the registry: dqn_ring_boundary_row_major).
    "dist_dqn_tpu/train.py": 13,
    "dist_dqn_tpu/utils/metrics.py": 1,  # MetricLogger.flush itself
}

SCAN_ROOTS = ("dist_dqn_tpu", "benchmarks", "__graft_entry__.py")


def scan(repo_root: Path, ctx: AnalysisContext = None) -> Dict[str, int]:
    """{relpath: direct-emission call-site count} over the scan roots
    (the telemetry package itself is the sanctioned emitter). Pass the
    run's shared ``ctx`` to reuse its parse cache."""
    if ctx is None:
        ctx = AnalysisContext(Path(repo_root))
    counts: Dict[str, int] = {}
    for rel in ctx.iter_py_files(SCAN_ROOTS):
        if rel.startswith("dist_dqn_tpu/telemetry/"):
            continue  # the registry itself is the sanctioned emitter
        if rel.startswith("dist_dqn_tpu/analysis/"):
            continue  # the lint layer DEFINES the pattern it hunts
        n = count_matches(PATTERN, ctx.source(rel))
        if n:
            counts[rel] = n
    return counts


def scan_metric_names(repo_root: Path,
                      ctx: AnalysisContext = None) -> Set[str]:
    """Every dqn_* family name the package registers or canonicalizes."""
    if ctx is None:
        ctx = AnalysisContext(Path(repo_root))
    names: Set[str] = set()
    for rel in ctx.iter_py_files(("dist_dqn_tpu",)):
        names.update(REGISTRATION.findall(ctx.source(rel)))
    names.update(CONSTANT.findall(
        ctx.source("dist_dqn_tpu/telemetry/collectors.py")))
    return names


def check_docs(repo_root: Path, ctx: AnalysisContext = None) -> List[str]:
    """Names registered in code but absent from docs/observability.md
    (minus the rationale'd allowlist). Whole-name match: a family that
    is merely a prefix of a documented longer name (dqn_foo vs
    dqn_foo_seconds) still counts as undocumented."""
    doc = (Path(repo_root) / "docs" / "observability.md").read_text()
    return sorted(
        n for n in scan_metric_names(repo_root, ctx=ctx)
        if not re.search(rf"{re.escape(n)}(?![a-z0-9_])", doc)
        and n not in DOCS_ALLOWLIST)


class MetricsCheck(Check):
    name = "metrics"
    description = ("metric emission goes through the telemetry registry "
                   "(no new print(json.dumps) call sites) and every "
                   "registered dqn_* family is documented in "
                   "docs/observability.md")
    rationale_tag = None  # suppression = the in-module allowlists

    def run(self, ctx: AnalysisContext) -> List[Finding]:
        findings = []
        for rel, n in sorted(scan(ctx.root, ctx=ctx).items()):
            allowed = ALLOWLIST.get(rel, 0)
            if n > allowed:
                findings.append(self.finding(
                    rel, 0,
                    f"{n} direct JSON-metric emission call sites "
                    f"(allowlist: {allowed}). New metrics must go "
                    f"through dist_dqn_tpu/telemetry (registry counters/"
                    f"gauges/histograms); see docs/observability.md.",
                    key=f"emission:{rel}"))
        for name in check_docs(ctx.root, ctx=ctx):
            findings.append(self.finding(
                "", 0,
                f"{name}: registered in dist_dqn_tpu/ but missing from "
                f"the docs/observability.md naming table. Document the "
                f"family (or add it to DOCS_ALLOWLIST with a rationale).",
                key=f"undocumented:{name}"))
        return findings


register(MetricsCheck())
