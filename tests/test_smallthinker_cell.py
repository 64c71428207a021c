"""``perf/run.py --allow-cpu`` on the ``smallthinker_q`` core at toy widths:
the preset with its reference module ``smallthinker_float32``, as a
configuration and a cell ADDED to ``toy_root``'s root (files and entries, no
harness file touched; ``tests/test_laguna_cell.py`` has the driver). The toy
configuration is ``perf/tests/test_perf_smallthinker.py``'s; the run lives
here, in a file of its own, because tier-1 runs all of ``perf/tests`` on one
worker."""
import json
import os
import subprocess
import sys

from perf.reference import smallthinker_float32
from perf.tests.test_perf_smallthinker import (CELL,
                                               TOY_SMALLTHINKER_CONFIG)
from tests.test_laguna_cell import (CHECKOUT, assert_a_sound_toy_run,
                                    run_toy_cell)


def test_the_smallthinker_cell_runs_through_the_harness(tmp_path):
    """The whole command on a toy ``smallthinker_q`` cell: the reference
    check (the step's five numbers and the ring's five) comes out ok, every
    chunk holds its counts at a grad step every second iteration, nothing
    compiles in the window, and the line has the contract's keys — what the
    chip run of ``smallthinker_q.preset`` does at the published widths."""
    assert_a_sound_toy_run(*run_toy_cell(tmp_path, TOY_SMALLTHINKER_CONFIG,
                                         CELL))


def test_the_wrong_formula_study_reads_a_wrong_formula(tmp_path):
    """``perf/tools/wrong_formula_study.py`` on the cell at toy widths: the
    sound program against the reference with ``silu`` in its experts comes
    out NOT ok, its Q-values far outside the float32 bound — the reading
    the tool takes at the published widths on the chip, from which
    ``TOLERANCES``' ``q`` is set."""
    out = tmp_path / "wrong.json"
    proc = subprocess.run(
        [sys.executable, str(CHECKOUT / "perf/tools/wrong_formula_study.py"),
         "--cell", CELL, "--formulas", "silu_for_relu", "--seed-base",
         str(2 ** 31 + 9), "--allow-cpu", "--out", str(out), "--set",
         *TOY_SMALLTHINKER_CONFIG["overrides"]],
        capture_output=True, text=True, timeout=280,
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 XLA_FLAGS="--xla_force_host_platform_device_count=1"))
    assert proc.returncode == 0, proc.stderr[-2000:]
    read = json.loads(out.read_text())["formulas"]["silu_for_relu"]
    assert read["replaced"] == "expert_mlp" and not read["ok"], read
    assert read["errors"]["q"] > 100 * smallthinker_float32.TOLERANCES[
        "float32"]["q"], read
