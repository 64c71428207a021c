"""``perf/run.py --allow-cpu`` end to end at toy size, on one device and on
a 4-device mesh — in a root where the toy configuration, its traffic mixes
and a per-layer metric were ADDED AS FILES plus entries (``toy_root``), with
no harness file touched. Each run is a child process: the harness owns its
process, as on the chip."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from perf.tests import toy_root

CHECKOUT = Path(__file__).resolve().parents[2]
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return toy_root.make(tmp_path_factory.mktemp("perf_toy"))


def _run(root, cell, trace, devices):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}")
    record = root / f"{cell}.{trace}.json"
    proc = subprocess.run(
        [sys.executable, str(CHECKOUT / "perf/run.py"), "--root", str(root),
         "--workload", cell, "--seed", "5", "--trace", str(trace),
         "--allow-cpu", "--record", str(record)],
        capture_output=True, text=True, env=env, timeout=280)
    return proc, record


@pytest.mark.parametrize("cell,trace,devices", [
    ("toy.toy1", 0, 1), ("toy.toy1", 1, 1), ("toy.toy4", 0, 4)])
def test_last_line_has_exactly_the_contracts_keys(root, cell, trace, devices):
    proc, record = _run(root, cell, trace, devices)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert set(line) == LINE_KEYS          # no breakdown from a CPU run
    assert line["correct"] is True and line["failed"] == 0
    # the toy window is 12 chunks by count; a traced run adds its chunks
    assert line["attempted"] == 12 + (2 if trace else 0)
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == devices
    assert set(line["device"]) == {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    # A CPU run prints counts and never a device metric.
    rec = json.loads(record.read_text())
    if trace:
        assert set(line["metrics"]) == {"entry_cache_misses",
                                        "toy_window_chunks"}
        assert line["metrics"]["toy_window_chunks"]["value"] == len(
            rec["series"]["cycle_s"])
    else:
        assert line["metrics"] == {}
    assert rec["correct_parts"] == {"counts_exact": True,
                                    "compiles_in_window": 0,
                                    "reference_ok": True}
    chunk = rec["chunk_iters"] * rec["lanes"]
    assert set(rec["series"]["frames"]) == {chunk}
    assert set(rec["series"]["grad_steps"]) == {rec["grad_steps_per_chunk"]}
    assert rec["lanes"] == (8 if devices == 4 else 4)


def test_too_short_a_window_fails_the_run_and_prints_no_result(root):
    proc, _ = _run(root, "toy.toyshort", 0, 1)      # 5 chunks, 9 needed
    assert proc.returncode != 0
    assert "TooFewChunks" in proc.stderr
    assert not any(l.startswith('{"correct"')
                   for l in proc.stdout.splitlines())


def test_refuses_a_cpu_backend_without_allow_cpu(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(CHECKOUT / "perf/run.py"), "--root", str(root),
         "--workload", "toy.toy1"],
        capture_output=True, text=True, env=env, timeout=120)
    assert proc.returncode != 0 and "no accelerator" in proc.stderr
    assert proc.stdout.strip() == ""


def test_fewer_devices_than_the_cell_asks_for_is_refused(root):
    proc, _ = _run(root, "toy.toy4", 0, 1)
    assert proc.returncode != 0 and proc.stdout.strip() == ""


def test_a_config_file_that_misstates_the_program_is_refused(root):
    path = root / "perf/configs/toy.json"
    good = path.read_text()
    try:
        path.write_text(good.replace('"actor.num_envs": 4',
                                     '"actor.num_envs": 5'))
        proc, _ = _run(root, "toy.toy1", 0, 1)
    finally:
        path.write_text(good)
    assert proc.returncode != 0 and "its file states" in proc.stderr


def test_no_harness_file_was_edited_for_the_toy_cell(root):
    for sub in ("harness", "reduce", "reference", "run.py"):
        ours, theirs = CHECKOUT / "perf" / sub, root / "perf" / sub
        files = [ours] if ours.is_file() else sorted(ours.glob("*.py"))
        for f in files:
            twin = theirs if ours.is_file() else theirs / f.name
            assert f.read_bytes() == twin.read_bytes()
    # and the run used the checkout's harness, not the copy: the copy is
    # data only (configs, traffic, metrics, reference found by name).
