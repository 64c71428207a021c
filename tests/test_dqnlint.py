"""Tier-1 wiring + framework tests for dqnlint (ISSUE 13): the unified
static-analysis framework (``dist_dqn_tpu/analysis/``) behind
``scripts/dqnlint.py``, replacing the seven one-off ``scripts/
check_*.py`` wirings (kept as thin shims for one release).

Four layers:
  * the repo itself passes EVERY registered check, in-process and
    parametrized (one shared AnalysisContext, like the CLI);
  * the CLI contract: ``--all --json`` emits the versioned findings
    artifact with exit 0;
  * the framework: plugin discovery, baseline round-trip (reasonless
    entries rejected, stale entries fail), rationale-comment parsing,
    JSON reporter schema;
  * every check BITES: the migrated lint bite tests (from the seven
    old test files) plus drift-bites for the two new analyzers —
    delete a fire() site -> the seam check fails; drop a ``with
    self._lock`` -> the race check fires.
"""
import json
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from dist_dqn_tpu import analysis  # noqa: E402
from dist_dqn_tpu.analysis import baseline as baseline_mod  # noqa: E402
from dist_dqn_tpu.analysis import core, registry, report  # noqa: E402
from dist_dqn_tpu.analysis.plugins import chaos_seams  # noqa: E402
from dist_dqn_tpu.analysis.plugins import heartbeat_stages  # noqa: E402
from dist_dqn_tpu.analysis.plugins import lock_discipline  # noqa: E402
from dist_dqn_tpu.analysis.plugins import (donation, mesh_axis,  # noqa: E402
                                           metrics, sockets, threads,
                                           wire)

#: The nine checks ISSUE 13's acceptance pins (seven migrated + two
#: new), plus heartbeat-stages (ISSUE 16).
EXPECTED_CHECKS = ("chaos-seams", "ckpt-schema", "donation",
                   "heartbeat-stages", "lock-discipline", "mesh-axis",
                   "metrics", "sockets", "threads", "wire")


# ---------------------------------------------------------------------------
# the repo passes, in-process and via the CLI
# ---------------------------------------------------------------------------

def test_plugin_discovery_finds_all_checks():
    names = registry.check_names()
    assert set(EXPECTED_CHECKS) <= set(names), names
    assert len(names) >= 9


@pytest.mark.parametrize("name", EXPECTED_CHECKS)
def test_repo_passes_check(name):
    """Every registered check is green on the repo (baselined findings
    excepted — and every suppression carries its reason)."""
    results = analysis.run_checks(REPO, names=[name])
    for r in results:
        assert r.ok, "\n".join(f.location() + ": " + f.message
                               for f in r.findings)
        for _f, reason in r.suppressed:
            assert reason.strip()


def test_cli_all_json_artifact():
    """The tier-1 one-shot: scripts/dqnlint.py --all --json runs every
    check in ONE process and emits the machine-readable artifact."""
    proc = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "dqnlint.py"),
         "--all", "--json"],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr or proc.stdout
    payload = json.loads(proc.stdout)
    assert payload["dqnlint"] == report.JSON_SCHEMA_VERSION
    assert payload["ok"] is True
    names = [c["name"] for c in payload["checks"]]
    assert set(EXPECTED_CHECKS) <= set(names)
    assert payload["summary"]["checks_run"] >= 9
    assert payload["summary"]["findings"] == 0
    for c in payload["checks"]:
        assert set(c) >= {"name", "description", "ok", "findings",
                          "suppressed", "rationale_tag"}
        for s in c["suppressed"]:
            assert s["reason"].strip()


def test_shipped_baseline_entries_all_carry_reasons():
    entries = baseline_mod.load_baseline(
        REPO / baseline_mod.DEFAULT_BASELINE)
    assert entries, "the ISSUE 13 triage shipped baseline entries"
    for e in entries:
        assert e["reason"].strip()
        assert e["check"] in EXPECTED_CHECKS


# ---------------------------------------------------------------------------
# framework: discovery context, rationale parsing, baseline, reporter
# ---------------------------------------------------------------------------

def test_context_skips_pycache_and_generated(tmp_path):
    pkg = tmp_path / "dist_dqn_tpu"
    (pkg / "__pycache__").mkdir(parents=True)
    (pkg / "__pycache__" / "sneaky.py").write_text("x = 1\n")
    (pkg / "real.py").write_text("x = 1\n")
    (pkg / "gen_pb2.py").write_text("x = 1\n")
    ctx = core.AnalysisContext(tmp_path)
    assert list(ctx.iter_py_files(("dist_dqn_tpu",))) == [
        "dist_dqn_tpu/real.py"]


def test_context_caches_parses(tmp_path):
    (tmp_path / "m.py").write_text("a = 1\n")
    ctx = core.AnalysisContext(tmp_path)
    assert ctx.tree("m.py") is ctx.tree("m.py")
    assert ctx.source("m.py") is ctx.source("m.py")


def test_rationale_parsing_windows():
    lines = ["x = 1",
             "# lock: probe is read-only",
             "y = self._q",               # line 3: tag 1 above -> hit
             "z = 1", "z = 1", "z = 1",
             "w = self._q"]               # line 7: tag 5 above -> miss
    assert core.has_rationale(lines, 3, "lock:")
    assert not core.has_rationale(lines, 7, "lock:")
    # Method-level: the tag just above the def covers the whole body.
    mlines = ["# lock: always called under the caller's hold",
              "def helper(self):",
              "    pass",
              "    return self._q"]
    assert core.has_rationale(mlines, 4, "lock:", def_lineno=2)
    # A bare tag with no reason is NOT a rationale.
    assert not core.has_rationale(["# lock:", "x = self._q"], 2, "lock:")


def test_baseline_rejects_reasonless_entries(tmp_path):
    path = tmp_path / "baseline.json"
    path.write_text(json.dumps({"version": 1, "entries": [
        {"check": "lock-discipline", "path": "a.py", "key": "K",
         "reason": "   "}]}))
    with pytest.raises(baseline_mod.BaselineError, match="no reason"):
        baseline_mod.load_baseline(path)
    path.write_text(json.dumps({"version": 1, "entries": [
        {"check": "lock-discipline", "path": "a.py", "key": "K"}]}))
    with pytest.raises(baseline_mod.BaselineError, match="missing"):
        baseline_mod.load_baseline(path)


def test_baseline_roundtrip_suppress_and_stale(tmp_path):
    f1 = core.Finding("c1", "a.py", 3, "bad thing", key="A.m:x")
    f2 = core.Finding("c1", "a.py", 9, "other thing", key="A.m:y")
    entries = [
        {"check": "c1", "path": "a.py", "key": "A.m:x", "reason": "ok"},
        {"check": "c1", "path": "a.py", "key": "A.gone:z",
         "reason": "was fixed"},
        {"check": "c2", "path": "b.py", "key": "K",
         "reason": "check did not run"},
    ]
    active, suppressed, stale = baseline_mod.apply_baseline(
        [f1, f2], entries, checks_run=["c1"])
    assert active == [f2]
    assert suppressed == [(f1, "ok")]
    # Stale only for checks that RAN: the c2 entry is untouched.
    assert [s.key for s in stale] == ["stale:c1:A.gone:z"]
    # save/load round-trip preserves entries.
    path = tmp_path / "b.json"
    baseline_mod.save_baseline(path, entries)
    assert baseline_mod.load_baseline(path) == sorted(
        entries, key=lambda e: (e["check"], e["path"], e["key"]))


def test_stale_baseline_entry_fails_the_run(tmp_path):
    """A baseline entry matching nothing is itself a failure — the
    baseline can only shrink toward zero."""
    path = tmp_path / "stale.json"
    path.write_text(json.dumps({"version": 1, "entries": [
        {"check": "threads", "path": "nowhere.py",
         "key": "ghost", "reason": "long fixed"}]}))
    results = analysis.run_checks(REPO, names=["threads"],
                                  baseline_path=path)
    stale = [r for r in results if r.check.name == "baseline"]
    assert stale and not stale[0].ok
    assert "stale baseline entry" in stale[0].findings[0].message


def test_cli_rejects_invalid_baseline(tmp_path):
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"version": 1, "entries": [
        {"check": "threads", "path": "x.py", "key": "k", "reason": ""}]}))
    proc = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "dqnlint.py"),
         "--check", "threads", "--baseline", str(bad)],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 2
    assert "invalid baseline" in proc.stderr


def test_json_reporter_schema_with_findings():
    check = registry.get_checks(["threads"])[0]
    res = report.CheckResult(
        check=check,
        findings=[core.Finding("threads", "a.py", 2, "msg", key="k")],
        suppressed=[(core.Finding("threads", "b.py", 1, "m2", key="k2"),
                     "why")])
    payload = report.render_json([res])
    assert payload["ok"] is False
    assert payload["summary"] == {"checks_run": 1, "findings": 1,
                                  "suppressed": 1, "stale_baseline": 0}
    c = payload["checks"][0]
    assert c["findings"][0] == {"check": "threads", "path": "a.py",
                                "line": 2, "message": "msg", "key": "k"}
    assert c["suppressed"][0]["reason"] == "why"
    text = report.render_text([res])
    assert "threads: FAIL" in text and "a.py:2" in text


def test_unknown_check_name_raises():
    with pytest.raises(KeyError, match="unknown check"):
        analysis.run_checks(REPO, names=["no-such-check"])


# ---------------------------------------------------------------------------
# migrated lints still bite (bodies moved from the seven old test files)
# ---------------------------------------------------------------------------

def test_metrics_bites_on_new_call_site(tmp_path):
    pkg = tmp_path / "dist_dqn_tpu"
    pkg.mkdir()
    (pkg / "rogue.py").write_text("print(json.dumps({'m': 1}))\n")
    counts = metrics.scan(tmp_path)
    assert counts == {"dist_dqn_tpu/rogue.py": 1}
    assert counts["dist_dqn_tpu/rogue.py"] > metrics.ALLOWLIST.get(
        "dist_dqn_tpu/rogue.py", 0)


def test_metrics_docs_drift_bites(tmp_path):
    pkg = tmp_path / "dist_dqn_tpu"
    tele = pkg / "telemetry"
    tele.mkdir(parents=True)
    (tele / "collectors.py").write_text(
        'DOCUMENTED = "dqn_documented_total"\n'
        'WRAPPED = \\\n    "dqn_wrapped_but_undocumented_total"\n')
    (pkg / "loopy.py").write_text(
        'c = reg.counter(\n    "dqn_registered_elsewhere_total",\n'
        '    "help text")\n'
        'g = reg.gauge("dqn_documented", "a PREFIX of the doc name")\n')
    docs = tmp_path / "docs"
    docs.mkdir()
    (docs / "observability.md").write_text(
        "only `dqn_documented_total` is in the table\n")
    assert metrics.scan_metric_names(tmp_path) == {
        "dqn_documented", "dqn_documented_total",
        "dqn_wrapped_but_undocumented_total",
        "dqn_registered_elsewhere_total"}
    # dqn_documented is a substring of the documented name but is NOT
    # itself documented — whole-name matching must still flag it.
    assert metrics.check_docs(tmp_path) == [
        "dqn_documented", "dqn_registered_elsewhere_total",
        "dqn_wrapped_but_undocumented_total"]


def test_metrics_docs_allowlist_entries_are_real():
    names = metrics.scan_metric_names(REPO)
    for allowed in metrics.DOCS_ALLOWLIST:
        assert allowed in names, (
            f"{allowed} is allowlisted but no longer registered — "
            "drop it from DOCS_ALLOWLIST")


def _heartbeat_repo(tmp_path, code: str, table_rows: str):
    pkg = tmp_path / "dist_dqn_tpu"
    pkg.mkdir()
    (pkg / "loopy.py").write_text(code)
    docs = tmp_path / "docs"
    docs.mkdir()
    (docs / "observability.md").write_text(
        "### Heartbeat stage names\n\n"
        "| stage | beaten by | stale means |\n|---|---|---|\n"
        + table_rows + "\n\n# next section\n")
    return core.AnalysisContext(tmp_path)


def test_heartbeat_stages_green_on_consistent_tree(tmp_path):
    """Literals, constants and f-string patterns all line up with the
    table (including a {N}-templated row)."""
    ctx = _heartbeat_repo(
        tmp_path,
        'STAGE = "pump.loop"\n'
        'a = wd.heartbeat("fused.chunk")\n'
        'b = wd.heartbeat(STAGE)\n'
        'c = wd.heartbeat(f"collect.s{shard}")\n',
        "| `fused.chunk` | x | y |\n"
        "| `pump.loop` | x | y |\n"
        "| `collect.s{N}` | x | y |")
    assert heartbeat_stages.HeartbeatStagesCheck().run(ctx) == []


def test_heartbeat_stages_bites_on_undocumented_stage(tmp_path):
    """Drift bites: a stage registered in code but absent from the
    table is a finding naming the stage."""
    ctx = _heartbeat_repo(
        tmp_path,
        'a = wd.heartbeat("fused.chunk")\n'
        'b = wd.heartbeat("rogue.stage")\n',
        "| `fused.chunk` | x | y |")
    findings = heartbeat_stages.HeartbeatStagesCheck().run(ctx)
    assert [f.key for f in findings] == ["undocumented-stage:rogue.stage"]
    assert findings[0].path == "dist_dqn_tpu/loopy.py"


def test_heartbeat_stages_bites_on_ghost_row(tmp_path):
    """The other direction: a table row no registration can produce
    (renamed/removed stage) is a docs finding."""
    ctx = _heartbeat_repo(
        tmp_path,
        'a = wd.heartbeat("fused.chunk")\n',
        "| `fused.chunk` | x | y |\n"
        "| `removed.stage` | x | y |")
    findings = heartbeat_stages.HeartbeatStagesCheck().run(ctx)
    assert [f.key for f in findings] == ["ghost-stage:removed.stage"]
    assert findings[0].path == "docs/observability.md"


def test_heartbeat_stages_real_repo_table_is_live():
    """Every row in the shipped table is producible, and every shipped
    registration is covered (the repo-green assertion, but also pinning
    that the scan actually FINDS the known stages)."""
    stages = heartbeat_stages.scan_stages(REPO)
    texts = {t for t, _, _, _ in stages}
    assert "fused.chunk" in texts
    assert "serving.batcher" in texts  # via the BATCHER_STAGE constant
    assert any("{" in t for t in texts)  # the sharded-collect f-string
    rows = heartbeat_stages.doc_stages(REPO)
    assert "host_replay.collect.s{N}" in rows


def test_threads_bites_on_anonymous_thread(tmp_path):
    pkg = tmp_path / "dist_dqn_tpu"
    pkg.mkdir()
    (pkg / "rogue.py").write_text(
        "import threading\n"
        "t = threading.Thread(target=print, daemon=True)\n"     # no name
        "u = threading.Thread(target=print, name='ok')\n"       # no daemon
        "v = threading.Thread(target=print, name='ok', daemon=True)\n")
    assert threads.scan(tmp_path) == [
        ("dist_dqn_tpu/rogue.py", 2, ["name"]),
        ("dist_dqn_tpu/rogue.py", 3, ["daemon"]),
    ]


def test_threads_bites_on_bare_thread_import(tmp_path):
    pkg = tmp_path / "dist_dqn_tpu"
    pkg.mkdir()
    (pkg / "rogue.py").write_text(
        "from threading import Thread\n"
        "t = Thread(target=print)\n")
    assert threads.scan(tmp_path) == [
        ("dist_dqn_tpu/rogue.py", 2, ["name", "daemon"])]


def test_donation_bites_and_honors_rationale(tmp_path):
    pkg = tmp_path / "dist_dqn_tpu"
    pkg.mkdir()
    (pkg / "rogue.py").write_text(
        "import jax\n"
        "train_step = lambda s, b: s\n"
        "bad = jax.jit(train_step)\n"
        "good = jax.jit(train_step, donate_argnums=0)\n"
        "# donation: nothing donatable, state is reused by the caller\n"
        "excused = jax.jit(train_step)\n"
        "act = jax.jit(lambda p, o: o)\n")
    failures = donation.scan(tmp_path)
    assert [(rel, line) for rel, line, _ in failures] == [
        ("dist_dqn_tpu/rogue.py", 3)]


def test_donation_targets_cover_snapshot_and_lane_sites(tmp_path):
    """ISSUE 15 drift-bites: the sharded-collect era's entry points —
    a jitted param-SNAPSHOT program and any LANE-block split — must
    stay in the donation lint's scope even renamed away from
    'collect'; a rationale comment still excuses them."""
    pkg = tmp_path / "dist_dqn_tpu"
    pkg.mkdir()
    (pkg / "rogue.py").write_text(
        "import jax\n"
        "@jax.jit\n"
        "def snapshot_params(p):\n"
        "    return p\n"
        "def split_lane_blocks(t):\n"
        "    return t\n"
        "bad = jax.jit(split_lane_blocks)\n"
        "# donation: snapshot must copy, the learner owns the params\n"
        "@jax.jit\n"
        "def snapshot_params_ok(p):\n"
        "    return p\n")
    failures = donation.scan(tmp_path)
    assert sorted((rel, line) for rel, line, _ in failures) == [
        ("dist_dqn_tpu/rogue.py", 2), ("dist_dqn_tpu/rogue.py", 7)]


def test_donation_covers_partial_jit_spelling(tmp_path):
    pkg = tmp_path / "dist_dqn_tpu"
    pkg.mkdir()
    (pkg / "rogue.py").write_text(
        "import jax\n"
        "from functools import partial\n"
        "@partial(jax.jit)\n"
        "def run_chunk_train(c):\n"
        "    return c\n")
    failures = donation.scan(tmp_path)
    assert len(failures) == 1 \
        and failures[0][0] == "dist_dqn_tpu/rogue.py"


def test_donation_recognizes_the_real_entry_points():
    """The OK verdict must come from coverage, not blindness: the scan
    has to see the known jitted train/collect sites."""
    import ast

    ctx = core.AnalysisContext(REPO)
    seen = set()
    for rel in ctx.iter_py_files(donation.SCAN_ROOTS):
        try:
            tree = ctx.tree(rel)
        except SyntaxError:
            continue
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) \
                    and donation._is_jit_call(node) \
                    and donation.TARGET.search(
                        donation._jitted_expr_text(node)):
                seen.add(rel)
    for expected in ("dist_dqn_tpu/train.py",
                     "dist_dqn_tpu/host_replay_loop.py",
                     "dist_dqn_tpu/actors/service.py",
                     "benchmarks/learner_bench.py"):
        assert expected in seen, (expected, sorted(seen))


def test_sockets_bites_and_accepts_evidence(tmp_path):
    pkg = tmp_path / "dist_dqn_tpu"
    pkg.mkdir()
    (pkg / "rogue.py").write_text(
        "import socket\n"
        + "\n" * (sockets.CONTEXT_LINES + 1)
        + "s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)\n"
        + "\n" * (sockets.CONTEXT_LINES + 1)
        + "c = socket.create_connection(('h', 1), timeout=2.0)\n"  # ok
        + "conn, _ = s.accept()  # socket: close() shuts the fd down\n")
    failures = sockets.scan(tmp_path)
    assert len(failures) == 1
    assert "rogue.py" in failures[0] and "socket.socket(" in failures[0]
    (pkg / "fine.py").write_text(
        "import socket\n"
        "s = socket.socket(socket.AF_INET, socket.SOCK_STREAM)\n"
        "s.settimeout(0.2)\n")
    assert [f for f in sockets.scan(tmp_path) if "fine.py" in f] == []


def test_mesh_axis_bites_on_axisless_call(tmp_path):
    pkg = tmp_path / "dist_dqn_tpu"
    pkg.mkdir()
    (pkg / "rogue.py").write_text(
        "import jax\n"
        "specs = object()\n"
        "bad = jax.shard_map(lambda x: x, mesh=None,\n"
        "                    in_specs=specs, out_specs=specs)\n"
        "# mesh-axis: specs built by train_step_specs name dp\n"
        "excused = jax.shard_map(lambda x: x, mesh=None,\n"
        "                        in_specs=specs, out_specs=specs)\n"
        "named = jax.shard_map(lambda x: x, mesh=None,\n"
        "                      in_specs=P('dp'), out_specs=P())\n")
    failures = mesh_axis.scan(tmp_path)
    assert [(rel, line) for rel, line, _ in failures] == [
        ("dist_dqn_tpu/rogue.py", 3)], failures


def test_wire_bites_on_header_drift(monkeypatch):
    from dist_dqn_tpu.ingest import codec

    monkeypatch.setattr(codec, "WIRE_HISTORY",
                        {v: "0" * 16 for v in codec.WIRE_HISTORY})
    failures = wire.check()
    assert failures and any("bump PROTOCOL_VERSION" in f
                            for f in failures)


def test_wire_bites_on_missing_version_entry(monkeypatch):
    from dist_dqn_tpu.ingest import codec
    from dist_dqn_tpu.ingest.schema import PROTOCOL_VERSION

    monkeypatch.setattr(
        codec, "WIRE_HISTORY",
        {v: d for v, d in codec.WIRE_HISTORY.items()
         if v != PROTOCOL_VERSION})
    assert any("no WIRE_HISTORY entry" in f for f in wire.check())


def test_wire_digest_covers_header_fields():
    from dist_dqn_tpu.ingest import codec

    base = wire.wire_digest()
    orig = codec.WIRE_HEADER_FIELDS
    try:
        codec.WIRE_HEADER_FIELDS = orig + (("extra", "I"),)
        assert wire.wire_digest() != base
    finally:
        codec.WIRE_HEADER_FIELDS = orig
    assert wire.wire_digest() == base


def test_ckpt_schema_bites_on_drift(monkeypatch):
    from dist_dqn_tpu.analysis.plugins import ckpt_schema
    from dist_dqn_tpu.utils import ckpt_schema as cs

    monkeypatch.setattr(cs, "SIDECAR_HISTORY",
                        {v: "0" * 16 for v in cs.SIDECAR_HISTORY})
    failures = ckpt_schema.check()
    assert failures and any("bump SIDECAR_VERSION" in f
                            for f in failures)


def test_ckpt_schema_bites_on_missing_version_entry(monkeypatch):
    from dist_dqn_tpu.analysis.plugins import ckpt_schema
    from dist_dqn_tpu.utils import ckpt_schema as cs

    monkeypatch.setattr(
        cs, "SIDECAR_HISTORY",
        {v: d for v, d in cs.SIDECAR_HISTORY.items()
         if v != cs.SIDECAR_VERSION})
    assert any("no SIDECAR_HISTORY entry" in f
               for f in ckpt_schema.check())


# ---------------------------------------------------------------------------
# new analyzer: lock-discipline
# ---------------------------------------------------------------------------

_LOCKED_CLASS = """\
import threading

class Tracker:
    def __init__(self):
        self._lock = threading.Lock()
        self._window = []
        self._count = 0

    def observe(self, x):
        with self._lock:
            self._window.append(x)
            self._count += 1

    def snapshot(self):
        {snapshot_body}
"""


def _write_pkg(tmp_path, body):
    pkg = tmp_path / "dist_dqn_tpu"
    pkg.mkdir(exist_ok=True)
    (pkg / "mod.py").write_text(body)
    return tmp_path


def test_lock_discipline_quiet_when_disciplined(tmp_path):
    root = _write_pkg(tmp_path, _LOCKED_CLASS.format(
        snapshot_body="with self._lock:\n            "
                      "return list(self._window), self._count"))
    assert lock_discipline.scan(root) == []


def test_lock_discipline_fires_when_a_hold_is_dropped(tmp_path):
    """The drift-bite the tentpole demands: drop a ``with self._lock``
    and the race check fires, naming class.method:attr."""
    root = _write_pkg(tmp_path, _LOCKED_CLASS.format(
        snapshot_body="return list(self._window), self._count"))
    rows = lock_discipline.scan(root)
    assert {(cls, meth, attr) for _, cls, meth, attr, _, _ in rows} == {
        ("Tracker", "snapshot", "_window"),
        ("Tracker", "snapshot", "_count")}


def test_lock_discipline_honors_site_rationale(tmp_path):
    root = _write_pkg(tmp_path, _LOCKED_CLASS.format(
        snapshot_body="# lock: monitoring read, staleness is fine\n"
                      "        return list(self._window), self._count"))
    assert lock_discipline.scan(root) == []


def test_lock_discipline_honors_method_rationale(tmp_path):
    body = _LOCKED_CLASS.format(
        snapshot_body="return list(self._window), self._count")
    body = body.replace(
        "    def snapshot(self):",
        "    # lock: only called under the caller's hold\n"
        "    def snapshot(self):")
    assert lock_discipline.scan(_write_pkg(tmp_path, body)) == []


def test_lock_discipline_sees_subscript_and_mutator_writes(tmp_path):
    root = _write_pkg(tmp_path, """\
import threading

class Registry:
    def __init__(self):
        self._lock = threading.Lock()
        self._by_id = {}

    def put(self, k, v):
        with self._lock:
            self._by_id[k] = v

    def drop(self, k):
        self._by_id.pop(k, None)
""")
    rows = lock_discipline.scan(root)
    assert {(cls, meth, attr, kind)
            for _, cls, meth, attr, _, kind in rows} == {
        ("Registry", "drop", "_by_id", "write")}


def test_lock_discipline_ignores_lockfree_classes(tmp_path):
    """No lock attribute -> no guarded set -> no findings: the check
    finds INCONSISTENT discipline, not missing discipline (documented
    limit — RateTracker-style lock-free classes are out of scope)."""
    root = _write_pkg(tmp_path, """\
class Free:
    def __init__(self):
        self._events = []

    def update(self, x):
        self._events.append(x)
""")
    assert lock_discipline.scan(root) == []


def test_lock_discipline_nested_defs_are_not_held(tmp_path):
    """A closure defined under a hold usually RUNS after the hold is
    released (thread targets) — its accesses must read as unlocked."""
    root = _write_pkg(tmp_path, """\
import threading

class Worker:
    def __init__(self):
        self._lock = threading.Lock()
        self._jobs = []

    def submit(self, j):
        with self._lock:
            self._jobs.append(j)

            def later():
                return self._jobs.pop()
            return later
""")
    rows = lock_discipline.scan(root)
    assert {(meth, attr) for _, _, meth, attr, _, _ in rows} == {
        ("submit", "_jobs")}


def test_lock_discipline_real_repo_targets_resolved():
    """The ISSUE 13 triage contract over the listed modules: every
    finding is a fix, a '# lock:' rationale, or a reasoned baseline
    entry — nothing unsuppressed, nothing silently dropped."""
    results = analysis.run_checks(REPO, names=["lock-discipline"])
    lock = [r for r in results if r.check.name == "lock-discipline"][0]
    assert lock.ok, [f.message for f in lock.findings]
    # The DivergenceSentinel config reads ride the baseline, each with
    # a reason (the shipped triage).
    assert len(lock.suppressed) >= 1
    for f, reason in lock.suppressed:
        assert reason.strip(), f.key


def test_lock_discipline_missing_target_file_fails(tmp_path):
    """A listed module that disappears must fail the check, not
    silently shrink its coverage."""
    import shutil

    root = tmp_path / "repo"
    for rel in lock_discipline.TARGET_FILES[:2]:
        dst = root / rel
        dst.parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(REPO / rel, dst)
    rows = lock_discipline.scan(root)
    missing = [r for r in rows if r[1] == "<missing>"]
    assert len(missing) == len(lock_discipline.TARGET_FILES) - 2


# ---------------------------------------------------------------------------
# new analyzer: chaos-seam drift
# ---------------------------------------------------------------------------

_PLAN = """\
SEAMS = {
    "a.send": ("drop", "delay"),
    "b.kill": ("crash",),
}
"""

_USER = """\
from dist_dqn_tpu import chaos

def send():
    ev = chaos.fire("a.send")
    if ev is None:
        chaos.mark_recovered("a.send")

def kill():
    cev = chaos.fire("b.kill")
"""


def _chaos_tree(tmp_path, plan=_PLAN, user=_USER):
    pkg = tmp_path / "dist_dqn_tpu"
    (pkg / "chaos").mkdir(parents=True, exist_ok=True)
    (pkg / "chaos" / "plan.py").write_text(plan)
    (pkg / "wire.py").write_text(user)
    return tmp_path


def _run_chaos(root):
    check = registry.get_checks(["chaos-seams"])[0]
    return check.run(core.AnalysisContext(root))


def test_chaos_seams_green_on_consistent_tree(tmp_path):
    assert _run_chaos(_chaos_tree(tmp_path)) == []


def test_chaos_seams_green_on_real_repo():
    assert _run_chaos(REPO) == []


def test_chaos_seam_losing_its_fire_site_fails(tmp_path):
    """THE drift-bite: delete a fire() call site and the registered
    seam fails CI instead of hollowing out the game-day harness."""
    user = _USER.replace('ev = chaos.fire("a.send")\n    ', "ev = None\n    ")
    findings = _run_chaos(_chaos_tree(tmp_path, user=user))
    keys = {f.key for f in findings}
    assert "no-fire:a.send" in keys, keys
    f = [x for x in findings if x.key == "no-fire:a.send"][0]
    assert f.path.endswith("chaos/plan.py") and f.line == 2


def test_chaos_seam_losing_its_recovery_anchor_fails(tmp_path):
    user = _USER.replace('chaos.mark_recovered("a.send")', "pass")
    findings = _run_chaos(_chaos_tree(tmp_path, user=user))
    assert {f.key for f in findings} == {"no-recovery:a.send"}


def test_chaos_crash_only_seam_needs_no_recovery_anchor(tmp_path):
    """b.kill is crash-only: the process dies at the seam, so recovery
    is the next process's resume — no in-process anchor demanded."""
    findings = _run_chaos(_chaos_tree(tmp_path))
    assert not any("b.kill" in f.key for f in findings)


def test_chaos_unregistered_fire_site_fails(tmp_path):
    user = _USER + '\ndef rogue():\n    chaos.fire("c.ghost")\n'
    findings = _run_chaos(_chaos_tree(tmp_path, user=user))
    assert {f.key for f in findings} == {"unregistered-fire:c.ghost"}


def test_chaos_nonliteral_seam_name_fails(tmp_path):
    user = _USER + '\ndef dyn(name):\n    chaos.fire(name)\n'
    findings = _run_chaos(_chaos_tree(tmp_path, user=user))
    assert any(f.key.startswith("nonliteral:") for f in findings)


def test_chaos_docstring_mentions_do_not_count(tmp_path):
    """AST-based scanning: the chaos package's own docstring examples
    (``chaos.fire("transport.recv")``) must never satisfy a seam."""
    user = '"""docs say call chaos.fire("a.send") somewhere."""\n'
    findings = _run_chaos(_chaos_tree(tmp_path, user=user))
    assert "no-fire:a.send" in {f.key for f in findings}


def test_chaos_registry_extraction_matches_live_seams():
    """The static parse of chaos/plan.py agrees with the imported
    registry — the check reads what is committed, so the two must
    never diverge."""
    from dist_dqn_tpu.chaos.plan import SEAMS

    seams, linenos = chaos_seams.extract_seams(
        (REPO / chaos_seams.PLAN_PATH).read_text())
    assert seams == {k: tuple(v) for k, v in SEAMS.items()}
    assert set(linenos) == set(seams)
