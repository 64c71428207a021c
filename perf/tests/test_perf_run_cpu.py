"""``perf/run.py --allow-cpu`` end to end at toy size, on one device and on
a 4-device mesh — in a root where two toy configurations (a feed-forward and
a sequence learner), their traffic mixes
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
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device",
             "compared"}


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
    ("toy.toy1", 0, 1), ("toy.toy1", 1, 1), ("toy.toy4", 0, 4),
    # the sequence learner (preset r2d2, reference r2d2_float32): another
    # learner kind, another loop, the same harness
    ("toyseq.toy1", 1, 1)])
def test_last_line_has_exactly_the_contracts_keys(root, cell, trace, devices):
    proc, record = _run(root, cell, trace, devices)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert set(line) == LINE_KEYS          # no breakdown from a CPU run
    # each number compared beside its limit: last in the line, and the last
    # lines of standard error
    assert list(line)[-1] == "compared"
    assert all(value <= limit for value, limit in line["compared"].values())
    assert proc.stderr.strip().splitlines()[-1].startswith(
        "compared compiles_in_window: 0 (limit 0)")
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


BROKEN_STEP = '''"""perf/run.py with the timed path broken underneath: the learner's step
returns its state unchanged (the chunk program scans this very function,
and the check in set-up drives it too)."""
import sys

sys.path.insert(0, {checkout!r})
from dist_dqn_tpu.agents import dqn

real = dqn.make_learner


def make_learner(*args, **kwargs):
    init, train_step = real(*args, **kwargs)
    return init, lambda state, *batch: (state, train_step(state, *batch)[1])


dqn.make_learner = make_learner
from perf import run  # noqa: E402  (after the patch: the loops bind it)

sys.exit(run.main())
'''


def test_a_step_that_leaves_its_state_unchanged_is_not_correct(root):
    """The rest of a run, driven past the look for a chip, on a learner that
    learns nothing: every chunk still counts its frames and grad steps and
    reports a finite loss, and ``correct`` comes out false: the gradient
    read back from Adam's moments, which never moved, is zero where the
    reference's is the batch's. (The optimizer's step, compared GIVEN that
    gradient, is zero on both sides: ``grad`` is the number that catches
    it.)"""
    script = root / "broken_step.py"
    script.write_text(BROKEN_STEP.format(checkout=str(CHECKOUT)))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    proc = subprocess.run(
        [sys.executable, str(script), "--root", str(root), "--workload",
         "toy.toy1", "--seed", "6", "--trace", "0", "--allow-cpu"],
        capture_output=True, text=True, env=env, timeout=280)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is False
    assert line["failed"] == 0 and line["attempted"] == 12
    value, limit = line["compared"]["grad"]
    assert value > 5 * limit
    assert f"compared grad: {value!r} (limit {limit!r})" in proc.stderr


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
