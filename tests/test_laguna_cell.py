"""``perf/run.py --allow-cpu`` on the ``laguna_q`` core at toy widths: the
preset with its reference module ``laguna_float32``, as a configuration and a
cell ADDED to ``toy_root``'s root (files and entries, no harness file
touched). The toy configuration is ``perf/tests/test_perf_laguna.py``'s; the
run lives here, in a file of its own, because tier-1 runs all of
``perf/tests`` on one worker."""
import json
import os
import subprocess
import sys
from pathlib import Path

from perf.tests import toy_root
from perf.tests.test_perf_laguna import CELL, TOY_LAGUNA_CONFIG

CHECKOUT = Path(__file__).resolve().parents[1]


def run_toy_cell(tmp_path, config: dict, like_cell: str):
    """``perf/run.py --allow-cpu`` on ``config`` as a configuration and a
    cell ``<name>.toy1`` added to ``toy_root``'s root, listed wherever
    ``like_cell`` is: ``(the printed line, the kept record)``."""
    name = config["name"]
    root = toy_root.make(tmp_path)
    (root / f"perf/configs/{name}.json").write_text(json.dumps(config))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": name, "source": "tests only",
                             "file": f"perf/configs/{name}.json",
                             "reduced": [], "why": "toy"})
    bench["workloads"].append({"name": f"{name}.toy1", "config": name,
                               "traffic": "toy1", "chips": 1, "why": "toy"})
    for metric in bench["per_layer"]:
        if like_cell in metric.get("workloads", ()):
            metric["workloads"].append(f"{name}.toy1")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    record = root / f"{name}.json"
    proc = subprocess.run(
        [sys.executable, str(CHECKOUT / "perf/run.py"), "--root", str(root),
         "--workload", f"{name}.toy1", "--seed", str(2 ** 31 + 7),
         "--trace", "0", "--allow-cpu", "--record", str(record)],
        capture_output=True, text=True, timeout=280,
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 XLA_FLAGS="--xla_force_host_platform_device_count=1"))
    assert proc.returncode == 0, proc.stderr[-2000:]
    return (json.loads(proc.stdout.strip().splitlines()[-1]),
            json.loads(record.read_text()))


def assert_a_sound_toy_run(line: dict, kept: dict) -> None:
    """Correct, 12 chunks attempted, the step's and the ring's numbers
    compared and inside their limits, 4 grad steps a chunk."""
    assert line["correct"] is True and line["failed"] == 0, line
    assert line["attempted"] == 12
    assert {"q", "grad", "ring_windows", "ring_weights"} <= set(
        line["compared"])
    assert all(value <= limit for value, limit in line["compared"].values())
    assert kept["grad_steps_per_chunk"] == 4
    assert kept["grad_step_flops"] > 0


def test_the_laguna_cell_runs_through_the_harness(tmp_path):
    """The whole command on a toy ``laguna_q`` cell: the reference
    check (the step's five numbers and the ring's five) comes out ok, every
    chunk holds its counts at a grad step every second iteration, nothing
    compiles in the window, the routing counters ride the chunk row, and
    the line has the contract's keys — what the chip run of
    ``laguna_q.preset`` does at the published widths."""
    assert_a_sound_toy_run(*run_toy_cell(tmp_path, TOY_LAGUNA_CONFIG, CELL))
