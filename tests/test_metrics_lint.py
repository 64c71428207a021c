"""Thin compatibility shim (ISSUE 13, one release): the metric-emission
lint migrated into ``dist_dqn_tpu/analysis/plugins/metrics.py`` and its
bite tests into tests/test_dqnlint.py. This file keeps the historical
test name + the legacy entry point's verdict pinned so external
references (CI configs, docs) don't break."""
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent


def test_no_new_direct_metric_emission():
    proc = subprocess.run(
        [sys.executable, str(REPO / "scripts" / "check_metrics.py")],
        capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr or proc.stdout


# -- families that left with the host-wall chip-time plane (PR 49) -----------
RETIRED = ("dqn_program_flops", "dqn_program_bytes",
           "dqn_program_dispatches_total",
           "dqn_program_device_seconds_total", "dqn_learner_mfu",
           'dqn_chip_busy_seconds_total{loop="fused"}')


@pytest.fixture(scope="module")
def fused_scrape():
    """``/metrics`` of a toy fused run, fetched over HTTP after its first
    chunk (the server lives as long as ``train.train`` does)."""
    import json
    import urllib.request

    from test_stages import _toy_cfg

    from dist_dqn_tpu.train import train

    seen = {}

    def log_fn(line):
        row = json.loads(line)
        if "telemetry_port" in row:
            seen["port"] = row["telemetry_port"]
        elif "env_frames" in row and "text" not in seen:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{seen['port']}/metrics",
                    timeout=30) as response:
                seen["text"] = response.read().decode()

    train(_toy_cfg(), total_env_steps=8 * 25 * 2, chunk_iters=25,
          log_fn=log_fn, telemetry_port=0)
    return seen["text"]


@pytest.mark.parametrize("name", RETIRED)
def test_retired_family_is_gone(name, fused_scrape):
    """Gone from the package, from the operator's table and from what a
    fused run exports; the chunk's own families are still there."""
    # the fused loops' ledger was the one writer of the last name
    in_code = ('UtilizationLedger("fused"' if "{" in name else name)
    holders = [str(path.relative_to(REPO))
               for path in (REPO / "dist_dqn_tpu").rglob("*.py")
               if in_code in path.read_text()]
    assert holders == []
    doc = (REPO / "docs" / "observability.md").read_text().splitlines()
    assert [n for n, line in enumerate(doc, 1) if name in line] == []
    assert "dqn_chunk_seconds_count" in fused_scrape
    assert name not in fused_scrape
