"""``perf/run.py --allow-cpu`` on the ``ouro_q`` core at toy widths: the
preset with its reference module ``ouro_float32``, as a configuration and a
cell ADDED to ``toy_root``'s root (files and entries, no harness file
touched; ``tests/test_laguna_cell.py`` has the driver); the wrong-formula
tool on it; and the cell's two new readers on the toy program itself. The toy
configuration is ``perf/tests/test_perf_ouro.py``'s; the runs live here, in a
file of its own, because tier-1 runs all of ``perf/tests`` on one worker."""
import functools
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from dist_dqn_tpu.config import CONFIGS, apply_overrides
from dist_dqn_tpu.telemetry import stages
from perf.harness.manifest import Manifest
from perf.metrics import _children, _stages
from perf.reduce import trace_reduce
from perf.reference import ouro_float32
from perf.tests.test_perf_laguna import TOY_LAGUNA_CONFIG
from perf.tests.test_perf_ouro import CELL, NEW_METRICS, TOY_OURO_CONFIG
from tests.test_laguna_cell import (CHECKOUT, assert_a_sound_toy_run,
                                    run_toy_cell)


def test_the_ouro_cell_runs_through_the_harness(tmp_path):
    """The whole command on a toy ``ouro_q`` cell — two layers run three
    times: the reference check (the step's five numbers and the ring's
    five) comes out ok, every chunk holds its counts at a grad step every
    second iteration, nothing compiles in the window, and the line has the
    contract's keys — what the chip run of ``ouro_q.preset`` does at the
    published widths."""
    assert_a_sound_toy_run(*run_toy_cell(tmp_path, TOY_OURO_CONFIG, CELL))


@pytest.mark.parametrize("formula,replaced,number,times", [
    ("pre_norm_only", "norm_output", "q", 100),
    ("bfloat16_residual_stream", "stream", "stream", 30)])
def test_the_wrong_formula_study_reads_a_wrong_ouro_formula(
        tmp_path, formula, replaced, number, times):
    """``perf/tools/wrong_formula_study.py`` on the cell at toy widths: the
    sound program against the reference that leaves a sublayer's output
    un-normed comes out NOT ok, its Q-values far outside the float32 bound,
    and against the one whose residual stream is rounded to bfloat16 NOT ok
    by the check's own number for it, ``stream`` — the readings the tool
    takes at the published widths on the chip."""
    out = tmp_path / "wrong.json"
    proc = subprocess.run(
        [sys.executable, str(CHECKOUT / "perf/tools/wrong_formula_study.py"),
         "--cell", CELL, "--formulas", formula, "--seed-base",
         str(2 ** 31 + 9), "--allow-cpu", "--out", str(out), "--set",
         *TOY_OURO_CONFIG["overrides"]],
        capture_output=True, text=True, timeout=280,
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 XLA_FLAGS="--xla_force_host_platform_device_count=1"))
    assert proc.returncode == 0, proc.stderr[-2000:]
    read = json.loads(out.read_text())["formulas"][formula]
    assert read["replaced"] == replaced and not read["ok"], read
    assert read["errors"][number] > times * read["tolerances"][number], read


# -- the two readers on the toy program -----------------------------------------
ON_CARTPOLE = ["env_name=cartpole", "network.torso=mlp",
               "network.mlp_features=(16,)", "replay.frame_dedup=false"]


@functools.lru_cache(maxsize=None)
def _program(preset: str):
    """The toy chunk program of a preset, compiled here: ``(the stage
    table, {group: children}, a trace)`` — the trace made from the
    program's own instructions, every one of them a device event of 1 us
    inside one ``while`` (a few kilobytes; what the readers join is the
    instruction's name, which a TPU trace carries the same way)."""
    from dist_dqn_tpu.envs import make_jax_env
    from dist_dqn_tpu.models import build_network
    from dist_dqn_tpu.train_loop import make_fused_train

    toy = {"ouro_q": TOY_OURO_CONFIG, "laguna_q": TOY_LAGUNA_CONFIG}[preset]
    cfg = apply_overrides(CONFIGS[preset], toy["overrides"] + ON_CARTPOLE)
    env = make_jax_env(cfg.env_name)
    net = build_network(cfg.network, env.num_actions)
    init, run_chunk = make_fused_train(cfg, env, net)
    text = jax.jit(run_chunk, static_argnums=1, donate_argnums=0).lower(
        init(np.asarray(jax.random.PRNGKey(0))), 8).compile().as_text()
    table = stages.table_from_text(text)
    children = {group: stages.children_from_text(text, getattr(stages, group))
                for group in ("CORE_PARTS", "LOOPS")}
    events = [("while.0", 0.0, 1e3 * (len(table) + 2))] + [
        (inst, 1e3 * (i + 1), 1e3) for i, inst in enumerate(sorted(table))]
    trace = trace_reduce.reduce(
        [{"name": "/device:TPU:0",
          "lines": [{"name": trace_reduce.OPS_LINE, "events": events}]}],
        chips=1)
    return table, children, trace


@pytest.fixture()
def on_program(monkeypatch):
    def use(preset):
        table, children, trace = _program(preset)
        monkeypatch.setattr(_stages, "table", lambda run=None: table)
        monkeypatch.setattr(_children, "children",
                            lambda run, group: children.get(group))
        trace.__dict__.pop("_child_seconds", None)
        return children, trace
    return use


def test_the_new_readers_read_the_looped_toy_program(on_program):
    """On the toy ``ouro_q`` program both readers return numbers: at 1 us an
    instruction, ``mlp_dense_ms_per_grad_step`` counts the instructions of
    stage ``loss_grad`` under the MLP's scope and
    ``loop_overhead_ms_per_grad_step`` those under ``loops`` that
    ``CORE_PARTS`` leaves under no name — norms, residual adds, the turn's
    norm; every mixer's instruction lies under
    ``loops`` too and is counted once, by its mixer's reader."""
    children, trace = on_program("ouro_q")
    manifest = Manifest(CHECKOUT)
    run = {"traced_chunks": 1, "grad_steps_per_chunk": 4}
    loops, core = children["LOOPS"], children["CORE_PARTS"]
    for name in ("mlp_dense", "attention_full"):
        held = [inst for inst, child in core.items() if child == name]
        assert held and all(loops[inst] == "loops" for inst in held)
    # the trace's leaves: control flow holds other events and is no leaf
    leaves = {op.inst for op in trace.devices[0].leaves}
    overhead = sum(1 for inst, child in loops.items() if inst in leaves
                   and child == "loops" and core[inst] in (None, stages.MIXED))
    dense = sum(1 for inst, child in core.items()
                if inst in leaves and child == "mlp_dense")
    assert overhead > 0
    for metric, count in zip(NEW_METRICS, (dense, overhead)):
        assert manifest.metric_reader(metric)(run, trace) == pytest.approx(
            1e3 * count * 1e-6 / 4)
    assert manifest.metric_reader("attention_full_ms_per_grad_step")(
        run, trace) > 0


def test_the_loop_reader_is_silent_on_an_unlooped_program(on_program):
    """The toy ``laguna_q`` program runs its stack once and enters no
    ``loops``: the overhead reader returns None, the MLP's reads the dense
    sublayer as ``dense_mlp_ms_per_grad_step`` does."""
    children, trace = on_program("laguna_q")
    manifest = Manifest(CHECKOUT)
    run = {"traced_chunks": 1, "grad_steps_per_chunk": 4}
    assert "loops" not in set(children["LOOPS"].values())
    assert manifest.metric_reader("loop_overhead_ms_per_grad_step")(
        run, trace) is None
    assert manifest.metric_reader("mlp_dense_ms_per_grad_step")(
        run, trace) == manifest.metric_reader("dense_mlp_ms_per_grad_step")(
            run, trace) > 0
