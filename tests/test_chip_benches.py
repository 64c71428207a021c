"""Harness smokes for the on-chip benchmark scripts: CPU runs at toy size
pin the harness mechanics (platform gate, probe -> measure sizing,
result-row schema, exit codes) so chip time is never spent on a harness
bug."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.slow  # real multi-process runs: full-suite only


def _run(cmd, timeout=540):
    return subprocess.run(cmd, cwd=REPO, capture_output=True, text=True,
                          timeout=timeout)


def _json_rows(stdout):
    rows = []
    for line in stdout.splitlines():
        try:
            rows.append(json.loads(line))
        except ValueError:
            pass
    return rows


def test_apex_feeder_bench_smoke_vector():
    """The service-ceiling feeder bench (VERDICT round-4 missing #1):
    feeders replace actors, records must flow uncorrupted. ring_dropped
    is NOT asserted zero — ring-full rejections are the feeder's normal
    backpressure (retried, not lost)."""
    proc = _run([sys.executable, "benchmarks/apex_feeder_bench.py",
                 "--allow-cpu", "--variants", "vector",
                 "--measure-seconds", "5"])
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    rows = _json_rows(proc.stdout)
    measure = [r for r in rows if r.get("phase") == "measure"]
    assert len(measure) == 1
    row = measure[0]
    assert row["env_steps"] >= row["total_env_steps"]
    assert row["bad_records"] == 0
    assert row["steady_records_per_sec"] > 0
    assert row["platforms"] == "cpu"


def test_host_replay_bench_smoke():
    """The host-DRAM replay hybrid (VERDICT round-4 next #2): collect ->
    D2H -> host ring -> H2D -> train must cycle with dedup-sized
    streams."""
    proc = _run([sys.executable, "benchmarks/host_replay_bench.py",
                 "--allow-cpu"])
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    rows = _json_rows(proc.stdout)
    bench = [r for r in rows if r.get("bench") == "host_replay"]
    assert len(bench) == 1
    row = bench[0]
    assert row["grad_steps"] > 0
    # Dedup D2H: single frames, not stacks.
    assert row["steady_d2h_bytes_per_chunk"] < \
        row["chunk_iters"] * row["lanes"] * 84 * 84 * 2
    assert row["platforms"] == "cpu"


def test_scaling_bench_smoke():
    """The n-chip scale-out row (ISSUE 10): dp=1 vs dp=N host-replay
    legs with aggregate + per-chip rates, and the honest-contract JSON
    shape the battery stage captures. Apex leg skipped — the fleet
    spread is pinned by test_apex_integration's e2e; this smoke pins
    the harness mechanics."""
    proc = _run([sys.executable, "benchmarks/scaling_bench.py",
                 "--allow-cpu", "--force-host-devices", "4", "--dp", "2",
                 "--chunks", "4", "--skip-apex"])
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    rows = _json_rows(proc.stdout)
    bench = [r for r in rows if r.get("metric") == "dp_scaling"]
    assert len(bench) == 1
    row = bench[0]
    assert row["dp_size"] == 2
    legs = row["host_replay"]
    assert legs["dp1"]["dp_size"] == 1 and legs["dp2"]["dp_size"] == 2
    for leg in legs.values():
        assert leg["grad_steps"] > 0
        assert leg["env_steps_per_sec_per_chip"] == pytest.approx(
            leg["env_steps_per_sec"] / leg["dp_size"], rel=0.01)
    assert row["scaling"]["grad_steps_x"] > 0
    # Collect arm (ISSUE 15): the dpN leg ran the sharded collect and
    # the per-shard byte conservation held (the bench fails otherwise;
    # this pins the row shape the battery captures).
    collect = row["collect"]
    assert collect["sharded"] is True
    assert collect["d2h_bytes_conserved_per_shard"] is True
    assert len(collect["d2h_bytes_by_shard"]) == 2
    assert collect["env_steps_x_vs_dp1"] > 0
    assert legs["dp2"]["collect_lane_block"] * 2 == \
        legs["dp1"]["collect_lane_block"]


def test_apex_split_bench_smoke_vector():
    proc = _run([sys.executable, "benchmarks/apex_split_bench.py",
                 "--allow-cpu", "--variants", "vector",
                 "--measure-seconds", "5"])
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    rows = _json_rows(proc.stdout)
    measure = [r for r in rows if r.get("phase") == "measure"]
    assert len(measure) == 1
    row = measure[0]
    assert row["env_steps"] >= row["total_env_steps"]
    assert row["bad_records"] == 0 and row["ring_dropped"] == 0
    assert row["grad_steps"] > 0
    assert row["platforms"] == "cpu"  # smoke must never record TPU-ish rows


@pytest.mark.parametrize("head", ["dqn", "c51", "rainbow"])
def test_pong_learning_smoke(head):
    """--smoke must exercise the SAME head family as the chip run would
    (a head-specific config bug caught here costs seconds; on the chip
    it costs a window its compile minutes — review round 4)."""
    proc = _run([sys.executable, "benchmarks/pong_learning.py", "--smoke",
                 "--head", head])
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    rows = _json_rows(proc.stdout)
    summary = [r for r in rows if r.get("summary") == "pong_learning"]
    assert len(summary) == 1
    row = summary[0]
    assert row["platform"] == "cpu" and row["smoke"] is True
    assert row["head"] == head
    assert row["frames"] > 0 and row["grad_steps"] > 0
    # The bar is never claimed cleared on a smoke run.
    assert row["cleared_bar"] is False


def test_ale_learning_smoke():
    proc = _run([sys.executable, "benchmarks/ale_learning.py", "--smoke",
                 "--budget-seconds", "20"])
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    rows = _json_rows(proc.stdout)
    summary = [r for r in rows if r.get("summary") == "ale_learning"]
    assert len(summary) == 1
    row = summary[0]
    assert row["fake_ale"] is True and row["platform"] == "cpu"
    assert row["frames"] > 0 and row["grad_steps"] > 0
    assert row["smoke"] is True
