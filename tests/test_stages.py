"""Stage names in the fused chunk program, the table built from them on
demand, the compile-cache flag that keeps them true, and the host spans of
``train.train`` (telemetry/stages.py, utils/trace.py, utils/backend.py)."""
import dataclasses
import functools
import gzip
import hashlib
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from dist_dqn_tpu.config import CONFIGS, apply_overrides
from dist_dqn_tpu.envs import make_jax_env
from dist_dqn_tpu.models import build_network
from dist_dqn_tpu.telemetry import flight as tm_flight
from dist_dqn_tpu.telemetry import stages
from dist_dqn_tpu.train import train
from dist_dqn_tpu.train_loop import make_fused_train

CHECKOUT = Path(__file__).resolve().parents[1]
TOY = ["network.mlp_features=(32,)", "replay.capacity=4096",
       "replay.min_fill=64", "learner.batch_size=16", "actor.num_envs=8"]
# Stages every fused program holds; a prioritized one adds the write-back.
# ``allreduce`` exists only under a mesh (its case is below).
COMMON = {"act", "env", "insert", "sample", "gather", "loss_grad",
          "optimizer", "target_sync"}


def _toy_cfg(*extra):
    cfg = apply_overrides(CONFIGS["cartpole"], TOY + list(extra))
    return dataclasses.replace(cfg, eval_every_steps=0)


def _toy_recurrent_cfg(*extra):
    """The recurrent learner over the sequence ring at toy size; one LSTM
    step a trip of the cell's scan, so that the cell's ``while`` is on the
    op paths as at the preset's 125 steps, and a float32 cell (the CPU
    backend widens a bf16 one with converts that carry no name)."""
    return apply_overrides(CONFIGS["r2d2"], [
        "env_name=cartpole", "network.torso=mlp",
        "network.mlp_features=(16,)", "network.hidden=0",
        "network.lstm_size=8", "network.lstm_unroll=1",
        "network.lstm_dtype=float32",
        "network.compute_dtype=float32",
        "replay.capacity=512", "replay.min_fill=64", "replay.burn_in=2",
        "replay.unroll_length=4", "replay.sequence_stride=2",
        "learner.n_step=2", "learner.batch_size=16", "actor.num_envs=4"]
        + list(extra))


@functools.lru_cache(maxsize=None)
def _recurrent_text():
    return _chunk_text(_toy_recurrent_cfg())


def _chunk_text(cfg, num_devices=1):
    env = make_jax_env(cfg.env_name)
    net = build_network(cfg.network, env.num_actions)
    key = np.asarray(jax.random.PRNGKey(0))
    if num_devices == 1:
        init, run_chunk = make_fused_train(cfg, env, net)
        run = jax.jit(run_chunk, static_argnums=1, donate_argnums=0)
    else:
        from dist_dqn_tpu.parallel import make_mesh, make_mesh_fused_train
        mesh = make_mesh(devices=jax.devices()[:num_devices])
        init, run = make_mesh_fused_train(cfg, env, net, mesh)
    return run.lower(init(key), 10).compile().as_text()


# -- the vocabulary --------------------------------------------------------
def test_every_scope_in_the_package_is_a_stage_and_every_stage_is_entered():
    """... or a pass of ``loss_grad``: the two vocabularies that ARE
    entered. The parts are read, not entered (the next test)."""
    entered = set()
    for path in (CHECKOUT / "dist_dqn_tpu").rglob("*.py"):
        entered.update(re.findall(r'named_scope\("([^"]+)"\)',
                                  path.read_text()))
    # ... and the hybrid core's mixers enter their part names (``torso`` is
    # a module name there too), a looped core ``loops`` around its turns
    assert entered == (set(stages.STAGES) | set(stages.PASSES)
                       | set(stages.LOOPS)
                       | set(stages.CORE_PARTS) - {"torso"})
    assert not set(stages.STAGES) & set(
        stages.PASSES + stages.PARTS + stages.CORE_PARTS + stages.LOOPS)


def test_the_parts_are_the_recurrent_networks_module_names():
    """``torso`` and ``core`` are parameter keys of the recurrent network
    (so a rename breaks every checkpoint before it breaks a metric), and
    whole parts of the op paths of all three passes, forward and
    backward."""
    cfg = _toy_recurrent_cfg()
    env = make_jax_env(cfg.env_name)
    net = build_network(cfg.network, env.num_actions)
    params = net.init(jax.random.PRNGKey(0), net.initial_state(1),
                      np.zeros((1, 1) + env.observation_shape, np.float32),
                      method=net.unroll)
    assert set(stages.PARTS) <= set(params["params"])
    paths = set(re.findall(r'op_name="([^"]*)"', _recurrent_text()))
    for wrapper in ("jvp(burn_in)", "jvp(online_unroll)",
                    "jvp(target_unroll)", "transpose(jvp(online_unroll))"):
        for part in stages.PARTS:
            assert any(f"/loss_grad/{wrapper}/" in p
                       and part in p.split("/") for p in paths), (wrapper,
                                                                  part)


TWOTOWER_PARTS = ("torso", "ssm", "attention", "moe_router", "moe_routed",
                  "moe_shared")
LAGUNA_PARTS = ("torso", "attention_window", "attention_full", "mlp_dense",
                "moe_router", "moe_routed", "moe_shared")


@pytest.mark.parametrize("preset,toy,parts", [
    ("twotower_q", "test_perf_run_twotower.TOY_CORE_CONFIG", TWOTOWER_PARTS),
    ("laguna_q", "test_perf_laguna.TOY_LAGUNA_CONFIG", LAGUNA_PARTS)])
def test_the_hybrid_cores_parts_split_loss_grad(preset, toy, parts):
    """The chunk program over the hybrid sequence core at toy widths, once
    with each preset's kinds of sublayer: every name of ``CORE_PARTS`` that
    the pattern has is on the op paths of the online pass, forward and
    backward (through the layers' rematerialisation), and the children
    table puts instructions of stage ``loss_grad`` under each of them —
    while ``PARTS`` still reads the whole stack as ``core``. Between them
    the two patterns enter every name."""
    import importlib

    module, name = toy.split(".")
    config = getattr(importlib.import_module(f"perf.tests.{module}"), name)
    assert set(TWOTOWER_PARTS + LAGUNA_PARTS) == set(stages.CORE_PARTS)
    # the benchmark's toy hybrid cell, on cartpole's four numbers
    cfg = apply_overrides(CONFIGS[preset], config[
        "overrides"] + ["env_name=cartpole", "network.torso=mlp",
                        "network.mlp_features=(16,)",
                        "replay.frame_dedup=false"])
    text = _chunk_text(cfg)
    paths = set(re.findall(r'op_name="([^"]*)"', text))
    for wrapper in ("jvp(online_unroll)", "transpose(jvp(online_unroll))",
                    "jvp(target_unroll)"):
        for part in parts:
            assert any(f"/loss_grad/{wrapper}/" in p
                       and stages.child_of(p, stages.CORE_PARTS) == part
                       for p in paths), (wrapper, part)
    children = stages.children_from_text(text, stages.CORE_PARTS)
    assert set(parts) <= set(children.values()) <= set(parts) | {
        None, stages.MIXED}
    whole = stages.children_from_text(text, stages.PARTS)
    assert {"torso", "core"} <= set(whole.values())


@pytest.mark.parametrize("op_name,stage", [
    ("jit(run_chunk)/while/body/closed_call/act/dot_general", "act"),
    ("jit(run_chunk)/while/body/cond/branch_1_fun/while/body/loss_grad/"
     "transpose(jvp(QNetwork))/Dense_0/dot_general", "loss_grad"),
    ("jit(run_chunk)/while/body/insert/gather/x", "gather"),  # innermost
    ("jit(run_chunk)/while/body/closed_call/add", None),
    ("jit(run_chunk)/while/body/actor/transact", None),  # whole parts only
])
def test_stage_of_reads_the_innermost_whole_path_part(op_name, stage):
    assert stages.stage_of(op_name) == stage


# Op paths copied from the lowering of ``_toy_recurrent_cfg()``'s chunk
# program (``_recurrent_text()``; the live text is held to them below).
_TRAIN = ("jit(run_chunk)/while/body/closed_call/cond/branch_1_fun/while/body/"
          "closed_call/loss_grad/")
_UNROLL = "RecurrentQNetwork.unroll/"
_CELL = _UNROLL + "while/body/closed_call/core/lstm/dot_general"
_TORSO = _UNROLL + "RecurrentQNetwork._embed/torso/MLPTorso_0/"
REAL_PATHS = [
    # forward only, outside any transform: the acting path
    ("jit(run_chunk)/while/body/closed_call/act/RecurrentQNetwork/" + _TORSO
     + "Dense_0/dot_general", None, "torso"),
    ("jit(run_chunk)/while/body/closed_call/act/RecurrentQNetwork/" + _CELL,
     None, "core"),
    # inside value_and_grad every pass reads jvp(..), the target's too;
    # the cell's own scan is a while under it
    (_TRAIN + "jvp(burn_in)/" + _CELL, "burn_in", "core"),
    (_TRAIN + "jvp(online_unroll)/" + _TORSO + "Dense_0/dot_general",
     "online_unroll", "torso"),
    (_TRAIN + "jvp(target_unroll)/" + _UNROLL
     + "RecurrentQNetwork._q_head/advantage/dot_general", "target_unroll",
     None),
    # the backward ops
    (_TRAIN + "transpose(jvp(online_unroll))/" + _CELL, "online_unroll",
     "core"),
    (_TRAIN + "transpose(jvp(online_unroll))/" + _TORSO
     + "Dense_0/reduce_sum", "online_unroll", "torso"),
    (_TRAIN + "transpose(loss_grad)/jvp(online_unroll)/" + _TORSO
     + "select_n", "online_unroll", "torso"),
    # loss_fn's own ops: under no child
    (_TRAIN + "jvp()/abs", None, None),
    (_TRAIN + "transpose(jvp())/add_any", None, None),
    (_TRAIN + "jvp(jit(take_along_axis))/gather", None, None),
]


@pytest.mark.parametrize("op_name,a_pass,part", REAL_PATHS)
def test_child_of_reads_through_the_transforms_wrappers(op_name, a_pass,
                                                        part):
    assert stages.child_of(op_name, stages.PASSES) == a_pass
    assert stages.child_of(op_name, stages.PARTS) == part
    # the stage reading takes whole parts only, as before
    assert stages.stage_of(op_name) in ("act", "loss_grad", "gather")


def test_the_pinned_paths_are_the_live_lowerings():
    paths = set(re.findall(r'op_name="([^"]*)"', _recurrent_text()))
    assert {p for p, _, _ in REAL_PATHS} <= paths


def test_a_child_counts_only_under_loss_grad():
    """``torso`` and ``core`` are on the acting path too: the parts' table
    names those instructions, ``children`` leaves them out, and every
    instruction of stage ``loss_grad`` is in it — under a child, ``mixed``
    or None (the stage's own: the loss, the heads)."""
    text = _recurrent_text()
    stage = stages.table_from_text(text)
    parts = stages.table_from_text(text, stages.PARTS)
    acting = {i for i, s in stage.items() if s == "act" and i in parts}
    assert {parts[i] for i in acting} >= set(stages.PARTS)
    for group in (stages.PASSES, stages.PARTS):
        children = stages.children_from_text(text, group)
        assert set(children) == {i for i, s in stage.items()
                                 if s == stages.PARENT}
        assert not acting & set(children)
        assert set(group) <= set(children.values()) <= set(group) | {
            None, stages.MIXED}
    # the stage table never sees a child
    assert set(stage.values()) <= set(stages.STAGES) | {stages.MIXED}


# -- the table, from the executable's text -----------------------------------
_SKIP = {"parameter", "get-tuple-element", "tuple", "constant", "bitcast"}


def _loop_body_instructions(text):
    """The instructions of the iteration loop: those of the outermost
    ``while``'s body and of every computation it reaches — conditions,
    branches, inner loops and the insides of fusions."""
    comps = {}
    for computation, m, line in stages.instructions(text):
        comps.setdefault(computation, []).append((m, line))
    (entry_name,) = re.findall(r"^ENTRY %?([\w.\-]+) ", text, re.M)
    control = re.compile(r"\b(?:body|condition|true_computation|"
                         r"false_computation|calls)=%?([\w.\-]+)")
    branches = re.compile(r"branch_computations=\{([^}]*)\}")

    def reached(line):
        found = control.findall(line)
        for group in branches.findall(line):
            found += re.findall(r"%?([\w.\-]+)", group)
        return found

    todo = [c for m, line in comps[entry_name] if m.group("op") == "while"
            for c in reached(line)]
    seen, out = set(), []
    while todo:
        comp = todo.pop()
        if comp in seen:
            continue
        seen.add(comp)
        for m, line in comps.get(comp, ()):
            if m.group("op") not in _SKIP:
                out.append(m.group("inst"))
            todo += reached(line)
    return out


@pytest.mark.parametrize("text_of,expected", [
    (lambda: _chunk_text(_toy_cfg()), COMMON),
    (lambda: _chunk_text(_toy_cfg("replay.prioritized=true")),
     COMMON | {"writeback"}),
    (lambda: _chunk_text(_toy_cfg("replay.prioritized=true",
                                  "replay.updates_per_chunk=2")),
     COMMON | {"writeback"}),
    # the recurrent learner over the sequence ring (its write-back has no
    # batched flush)
    (_recurrent_text, COMMON | {"writeback"}),
], ids=["uniform", "prioritized", "prioritized_ratio2",
        "the_recurrent_program_enters_the_loop_level_names"])
def test_table_holds_every_stage_and_covers_the_loop(text_of, expected):
    text = text_of()
    table = stages.table_from_text(text)
    assert expected <= set(table.values()) <= set(stages.STAGES) | {
        stages.MIXED}
    # By count, on the CPU backend: 85-87% here. The rest is the loop's own
    # bookkeeping (episode statistics, counters, the train predicate), the
    # backend's carry copies, and the key splits — JAX lowers
    # ``_threefry_split`` once as a shared sub-computation, which keeps no
    # caller's scope. The chip's measure is by time
    # (``stage_unattributed_share``, perf/tests/test_perf_stage_metrics.py).
    body = _loop_body_instructions(text)
    assert len(body) > 1000
    staged = [i for i in body if table.get(i) in stages.STAGES]
    assert len(staged) >= 0.8 * len(body), (len(staged), len(body))


@pytest.mark.parametrize("cfg,expected", [
    (_toy_cfg("actor.num_envs=16", "learner.batch_size=32"), COMMON),
    (_toy_recurrent_cfg(), COMMON | {"writeback"}),
], ids=["dqn", "recurrent"])
def test_the_mesh_program_names_its_allreduce(cfg, expected):
    table = stages.table_from_text(_chunk_text(cfg, num_devices=2))
    assert expected | {"allreduce"} <= set(table.values())


# ``table_from_text`` with no vocabulary named, on the three programs
# recorded under perf/testdata: (entries, sha256 of the sorted items) as
# the walk gave them before it took a vocabulary (PR 41's tree).
RECORDED = {
    "atari_preset_v5e": (5684, "e566fdcfcb0b87fc"),
    "apex_preset_v5e": (5243, "e460484c1c26d3b1"),
    "r2d2_preset_v5e": (405, "478d1dc41706d6cc"),
}


@pytest.mark.parametrize("program", sorted(RECORDED))
def test_the_stage_table_of_a_recorded_program_is_what_it_was(program):
    with gzip.open(CHECKOUT / "perf" / "testdata"
                   / f"{program}.hlo.txt.gz", "rt") as f:
        table = stages.table_from_text(f.read())
    digest = hashlib.sha256(
        json.dumps(sorted(table.items())).encode()).hexdigest()
    assert (len(table), digest[:16]) == RECORDED[program]


HLO = """HloModule toy

%fused_computation (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %c = f32[] constant(1), metadata={op_name="jit(f)/while/body/env/mul"}
  ROOT %t = f32[8]{0} tanh(%p), metadata={op_name="jit(f)/while/body/act/tanh"}
}

%fused_computation.1 (p.1: f32[8]) -> (f32[], f32[4,4]) {
  %p.1 = f32[8]{0} parameter(0)
  %conv = f32[4,4]{1,0} convolution(%p.1, %p.1), metadata={op_name="jit(f)/while/body/loss_grad/transpose(jvp(Net))/conv"}
  %sq = f32[4,4]{1,0} multiply(%conv, %conv), metadata={op_name="jit(f)/while/body/optimizer/mul"}
  %n = f32[] reduce(%sq), metadata={op_name="jit(f)/while/body/optimizer/reduce"}
  ROOT %out = (f32[], f32[4,4]{1,0}) tuple(%n, %conv)
}

%fused_computation.2 (p.2: f32[8]) -> f32[8] {
  %p.2 = f32[8]{0} parameter(0)
  %a = f32[8]{0} add(%p.2, %p.2), metadata={op_name="jit(f)/while/body/act/add"}
  ROOT %b = f32[8]{0} multiply(%a, %a), metadata={op_name="jit(f)/while/body/env/mul"}
}

%body (arg: (f32[8], f32[8])) -> (f32[8], f32[8]) {
  %arg = (f32[8]{0}, f32[8]{0}) parameter(0)
  %gte = f32[8]{0} get-tuple-element(%arg), index=0
  %copy.1 = f32[8]{0} copy(%gte)
  %bitcast.1 = f32[8]{0} bitcast(%copy.1)
  %fusion.1 = f32[8]{0} fusion(%bitcast.1), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/while/body/sub"}
  %fusion.2 = (f32[], f32[4,4]{1,0}) fusion(%fusion.1), kind=kOutput, calls=%fused_computation.1
  %fusion.3 = f32[8]{0} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.2
  %copy.2 = f32[8]{0} copy(%fusion.3)
  %add.9 = f32[8]{0} add(%gte, %gte), metadata={op_name="jit(f)/while/body/add"}
  ROOT %tuple.1 = (f32[8]{0}, f32[8]{0}) tuple(%copy.2, %add.9)
}

ENTRY %main (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0)
  ROOT %while.1 = (f32[8]{0}, f32[8]{0}) while(%x), condition=%cond, body=%body
}
"""


def test_table_rules_on_a_hand_written_module():
    table = stages.table_from_text(HLO)
    # a fusion takes its working instructions' stage (the constant's
    # op_name does not vote), whatever its own op_name says
    assert table["fusion.1"] == "act"
    # a fused-in convolution decides alone over the epilogue's stage
    assert table["fusion.2"] == "loss_grad"
    # no hero and no 3/4 majority: mixed
    assert table["fusion.3"] == stages.MIXED
    # compiler-inserted movement: its consumers' stage, through a bitcast;
    # with no staged consumer, its producer's
    assert table["copy.1"] == table["bitcast.1"] == "act"
    assert table["copy.2"] == stages.MIXED
    # under no stage: left out
    assert "add.9" not in table and "while.1" not in table


# -- nobody asks: no table; the host spans ------------------------------------
@pytest.fixture()
def fresh_flight():
    tm_flight._reset_for_tests()
    yield
    tm_flight._reset_for_tests()


def test_train_builds_no_table_until_asked_and_spans_every_chunk(
        fresh_flight, monkeypatch):
    calls = []
    for cls in (jax.stages.Lowered, jax.stages.Compiled):
        monkeypatch.setattr(
            cls, "as_text",
            lambda self, *a, _orig=cls.as_text, **k:
            calls.append(type(self).__name__) or _orig(self, *a, **k))
    stages.keep(None)
    _, history = train(_toy_cfg(), total_env_steps=8 * 25 * 4, chunk_iters=25,
                       log_fn=lambda _line: None)
    assert len(history) == 4
    assert not stages.table_built() and calls == []
    spans = [e["name"] for e in tm_flight.get_flight().tail()
             if e["kind"] == "span"]
    assert spans == ["fused.dispatch", "fused.fence",
                     "fused.bookkeeping"] * len(history)
    # asked: built once, from the program train kept
    table = stages.table()
    assert COMMON <= set(table.values()) and stages.table_built()
    assert stages.table() is table and calls == ["Compiled"]
    assert stages.table_seconds() > 0


def test_trace_path_writes_the_spans_and_feeds_the_histogram(fresh_flight,
                                                             tmp_path):
    import json

    from dist_dqn_tpu import telemetry

    path = tmp_path / "host.json"
    _, history = train(_toy_cfg(), total_env_steps=8 * 25 * 2, chunk_iters=25,
                       log_fn=lambda _line: None, trace_path=str(path))
    spans = [e["name"] for e in json.loads(path.read_text())
             if e.get("ph") == "X"]
    assert spans == ["fused.dispatch", "fused.fence",
                     "fused.bookkeeping"] * len(history)
    text = telemetry.render_prometheus(telemetry.get_registry())
    assert 'dqn_host_span_seconds_count{span="fused.dispatch"}' in text


def test_no_flight_recorder_leaves_no_span(fresh_flight):
    tm_flight.configure(enabled=False)
    _, history = train(_toy_cfg(), total_env_steps=8 * 25 * 2, chunk_iters=25,
                       log_fn=lambda _line: None)
    assert len(history) == 2
    assert tm_flight.get_flight().tail() == []


def test_spans_are_profiler_annotations_beside_the_device_ops(
        fresh_flight, tmp_path):
    """``profile_dir`` traces a STEADY chunk — the first after one with the
    full cadence's grad steps — and the trace holds the step and the three
    spans on the profiler's own clock."""
    lines = []
    _, history = train(_toy_cfg(), total_env_steps=8 * 25 * 4, chunk_iters=25,
                       log_fn=lines.append, profile_dir=str(tmp_path))
    assert [r["grad_steps_in_chunk"] for r in history] == [18.0, 25, 25, 25]
    # chunk 0 is not full (min_fill), chunk 1 is: chunk 2 is traced
    assert lines.index('{"profile_trace": "%s"}' % tmp_path) == 3
    (pb,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    names = {e.name for plane in jax.profiler.ProfileData.from_file(
        str(pb)).planes for line in plane.lines for e in line.events}
    assert {"fused.chunk", "fused.dispatch", "fused.fence",
            "fused.bookkeeping"} <= names


def test_a_run_too_short_for_a_steady_chunk_writes_no_trace(tmp_path):
    lines = []
    train(_toy_cfg(), total_env_steps=8 * 25, chunk_iters=25,
          log_fn=lines.append, profile_dir=str(tmp_path / "p"))
    assert len(lines) == 1 and "profile_trace" not in lines[0]
    assert not (tmp_path / "p").exists()


# -- names survive the compile cache ------------------------------------------
_STALE = '''
import contextlib, sys
import jax, jax.numpy as jnp
from dist_dqn_tpu.telemetry import stages
from dist_dqn_tpu.utils import backend
backend.enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
key = (backend.names_in_cache_key if sys.argv[1] == "1"
       else contextlib.nullcontext)
f = lambda x: jnp.tanh(x @ x)
for name in ("act", "env"):            # the scope is renamed, the cache kept
    jax.clear_caches()
    def g(x, name=name):
        with jax.named_scope(name):
            return f(x)
    lowered = jax.jit(g).lower(jnp.ones((64, 64)))
    with key():
        text = lowered.compile().as_text()
    assert not jax.config.jax_compilation_cache_include_metadata_in_key
    print(name, sorted(set(stages.table_from_text(text).values())))
'''


@pytest.mark.parametrize("metadata_in_key,after_rename", [
    (True, "env"),      # compiled as train.train compiles its chunk
    (False, "act"),     # JAX's own key: the OLD executable, the OLD name
])
def test_a_renamed_scope_with_the_cache_kept(tmp_path, metadata_in_key,
                                             after_rename):
    script = tmp_path / "stale.py"
    script.write_text(_STALE)
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               JAX_ENABLE_COMPILATION_CACHE="true", JAX_PLATFORMS="cpu",
               PYTHONPATH=str(CHECKOUT))
    out = subprocess.run(
        [sys.executable, str(script), str(int(metadata_in_key))], env=env,
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.splitlines()[-2:] == ["act ['act']",
                                            f"env ['{after_rename}']"]
