"""The ``ouro_q`` configuration in the benchmark: its file against the
program's preset, the catalog row's numbers and the contract's keys, its
cell's two readers on hand-written tables, and the toy configuration of the
cell that ``tests/test_ouro_cell.py`` drives through ``perf/run.py
--allow-cpu`` (a minute of compiling on the CPU: kept out of this directory,
whose tests tier-1 runs on one worker). The mathematics is held in
``tests/test_ouro_core.py``."""
from pathlib import Path

import pytest

from perf.harness.manifest import Manifest, resolve_cell

CHECKOUT = Path(__file__).resolve().parents[2]
CELL = "ouro_q.preset"
NEW_METRICS = ("mlp_dense_ms_per_grad_step",
               "loop_overhead_ms_per_grad_step")
# what the catalog row's ``config`` states (``architectures.jsonl``,
# Ouro-2.6B), but the three keys under ``reduced``
PUBLISHED = {
    "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 5632, "max_position_embeddings": 65536,
    "max_window_layers": 48, "model_type": "ouro",
    "num_attention_heads": 16, "num_key_value_heads": 16,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
    "sliding_window": None, "tie_word_embeddings": False,
    "total_ut_steps": 4, "early_exit_threshold": 1,
    "use_sliding_window": False}
# two published layers at toy widths, run three times
TOY_OURO_CONFIG = {
    "name": "toyouro", "source": "tests only", "preset": "ouro_q",
    "overrides": [
        "network.torso=small", "network.hidden=32", "network.remat_torso=false",
        "network.compute_dtype=float32", "network.core.pattern=FDFD",
        "network.core.loops=3",
        "network.core.attention_heads_per_layer=4,4",
        "network.core.num_key_value_heads=4", "network.core.head_dim=8",
        "network.core.intermediate_size=24",
        "network.core.attention_window=16",
        "replay.burn_in=6", "replay.unroll_length=5",
        "replay.sequence_stride=6", "replay.capacity=512",
        "replay.min_fill=64",
        "learner.n_step=3", "learner.batch_size=4", "actor.num_envs=4",
        "train_every=2"],
    "reference": "ouro_float32", "chunk_iters": 8,
    "warmup": {"full_train_chunks": 2, "ring": "min_fill"},
    "trace_chunks": 2,
    "sizes": {"network.core.kind": "hybrid", "network.core.pattern": "FDFD",
              "network.core.loops": 3, "network.core.sandwich_norm": True,
              "network.lstm_size": 0, "train_every": 2},
}


def test_the_ouro_cell_is_in_the_benchmark_with_its_metrics():
    """The cell resolves to its files; its configuration is used by it and
    lists what it cut; the two per-layer metrics it brings list it alone,
    move a metric it reports, and each has a reader; the full attention's
    reader, the unsplit share, the ten stage readers and the four host and
    loop readers of every sequence cell report it too — and the lists the
    benchmark's own tests hold to one cell do not."""
    manifest = Manifest(CHECKOUT)
    plan = resolve_cell(manifest, CELL)
    assert (plan["preset"], plan["reference"], plan["chips"]) == (
        "ouro_q", "ouro_float32", 1)
    assert plan["chunk_iters"] == 16
    entry = manifest._entry("configs", "ouro_q")
    stated = manifest.config("ouro_q")
    assert entry["reduced"] == list(stated["reduced"]) == [
        "num_hidden_layers", "layer_types", "vocab_size"]
    assert ("(model_type ouro; total_ut_steps 4; layer_types[:4] = "
            "full_attention x4)") in entry["source"]
    assert entry["source"].startswith(stated["source"])
    assert len(entry["source"]) <= 200
    assert [w["name"] for w in manifest.data["workloads"]
            if w["config"] == "ouro_q"] == [CELL]
    reported = [m["name"] for m in manifest.metrics_of("end_to_end", CELL)]
    for name in NEW_METRICS:
        metric = manifest._entry("per_layer", name)
        assert metric["workloads"] == [CELL]
        assert metric["moves"] == "grad_steps_per_s" in reported
        assert callable(manifest.metric_reader(name))
    listed = {m["name"] for m in manifest.metrics_of("per_layer", CELL)}
    assert {"attention_full_ms_per_grad_step", "core_unsplit_share",
            "loop_gap_share", "chunk_dispatch_ms", "chunk_dispatch_worst_ms",
            "chunk_bookkeeping_worst_ms", "learn_ms_per_grad_step",
            "train_mfu", "device_idle_share"} <= listed
    assert {"act_ms_per_iter", "env_ms_per_iter", "insert_ms_per_iter",
            "sample_ms_per_grad_step", "gather_ms_per_grad_step",
            "loss_grad_ms_per_grad_step", "optimizer_ms_per_grad_step",
            "writeback_ms_per_grad_step", "loss_grad_mfu",
            "stage_unattributed_share"} <= listed
    # no experts, no window layer; and the lists a test of the benchmark
    # holds to ``laguna_q.preset`` / ``r2d2.preset`` / the flat cells
    assert not {"moe_router_ms_per_grad_step", "moe_routed_ms_per_grad_step",
                "attention_window_ms_per_grad_step",
                "dense_mlp_ms_per_grad_step",
                "full_attention_ms_per_grad_step", "core_ms_per_grad_step",
                "chunk_temp_reserved_gb"} & listed


def test_the_ouro_file_holds_the_published_widths_and_its_cut():
    """Every number of the catalog row's ``config`` stands in the file under
    its own key, but the three under ``reduced``, each of which has its
    published value beside it; every width the file states is the program's
    preset (``build_config`` refuses a difference under ``sizes``); every
    reading under ``assumed`` has its ground, the exit gate's absence among
    them; the deployment states the stages and no share."""
    from perf.harness.run_cell import build_config

    manifest = Manifest(CHECKOUT)
    stated = manifest.config("ouro_q")
    for key, value in PUBLISHED.items():
        assert stated[key] == value, key
    assert set(stated["published"]) == set(stated["reduced"]) == {
        "num_hidden_layers", "layer_types", "vocab_size"}
    assert "vocab_size" not in stated
    assert stated["published"]["num_hidden_layers"] == 48
    assert stated["num_hidden_layers"] == len(stated["layer_types"]) == 4
    assert set(stated["layer_types"]) == {"full_attention"}
    cfg = build_config(resolve_cell(manifest, CELL))
    core = cfg.network.core
    assert (stated["hidden_size"], stated["head_dim"],
            stated["num_key_value_heads"], stated["intermediate_size"],
            stated["rms_norm_eps"], stated["rope_theta"],
            stated["total_ut_steps"]) == (
        cfg.network.hidden, core.head_dim, core.num_key_value_heads,
        core.intermediate_size, core.norm_eps, core.rope_full.theta,
        core.loops)
    assert set(core.attention_heads_per_layer) == {
        stated["num_attention_heads"]}
    assert core.pattern == "FD" * stated["num_hidden_layers"]
    assert (core.rope_full.rotary_factor, core.rope_full.yarn_factor,
            core.rope_full.attention_factor) == (1.0, 0.0, 1.0)
    assert (core.sandwich_norm, core.attention_gate) == (True, False)
    assert {"loop", "sandwich_norm", "cache_a_turn", "exit_gate",
            "attention", "precision", "replay"} <= set(stated["assumed"])
    assert "NOT BUILT" in stated["assumed"]["exit_gate"]
    assert "12 pipeline stages of 4 layers" in stated["deployment"]
    assert "turn closes over the stage held" in stated["deployment"]
    # every size ``sizes`` states is the preset's: build_config passed; and
    # the cell's traffic is what the issue reckoned
    window = (cfg.replay.burn_in + cfg.replay.unroll_length
              + cfg.learner.n_step)
    assert (window, cfg.learner.batch_size * window) == (2048, 4096)
    assert cfg.actor.num_envs == 8 and cfg.replay.capacity == 131_072
    assert cfg.train_every == resolve_cell(manifest, CELL)["chunk_iters"]
    assert cfg.replay.min_fill // cfg.actor.num_envs >= window


def test_the_ouro_lanes_carry_sixteen_rings():
    """``state_bytes_a_lane`` of the cell's network: sixteen rings (a turn a
    layer) of 2,048 slots x 16,384 B, with a step counter each."""
    from dist_dqn_tpu.envs import make_jax_env
    from dist_dqn_tpu.models import build_network
    from perf.harness.run_cell import build_config

    cfg = build_config(resolve_cell(Manifest(CHECKOUT), CELL))
    net = build_network(cfg.network, make_jax_env(cfg.env_name).num_actions)
    assert net.state_bytes_a_lane() == {
        "attention_full": 16 * (2048 * 16_384 + 4)}


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_an_ouro_reader_is_silent_on_a_program_without_its_names(
        metric, monkeypatch):
    """On a program with no ``loops`` (or ``mlp_dense``) among its names, or
    no such group at all — the parent's programs, whose ``stages`` has no
    ``LOOPS`` — each new reader returns None and does not raise: the
    line leaves the metric out."""
    from perf.metrics import _children

    read = Manifest(CHECKOUT).metric_reader(metric)
    for split in (None, {"fusion.1": "ssm", "fusion.2": None}):
        monkeypatch.setattr(_children, "children",
                            lambda run, group, split=split: split)

        class Trace:
            devices = ()

        assert read({"traced_chunks": 2, "grad_steps_per_chunk": 1},
                    Trace()) is None


def test_the_loop_reader_asks_for_a_group_the_parent_lacks(monkeypatch):
    """``loop_overhead_ms_per_grad_step`` reads the group ``LOOPS``; where
    ``telemetry/stages.py`` has none (the parent's), ``_children`` finds
    nothing and the reader returns None."""
    from dist_dqn_tpu.telemetry import stages
    from perf.metrics import _children

    assert stages.LOOPS == ("loops",)
    read = Manifest(CHECKOUT).metric_reader("loop_overhead_ms_per_grad_step")
    monkeypatch.delattr(stages, "LOOPS")
    run = {"traced_chunks": 2, "grad_steps_per_chunk": 1}

    class Trace:
        devices = ()

    assert _children.children(run, "LOOPS") is None
    assert read(run, Trace()) is None


def test_the_mlp_reader_reads_its_scope(monkeypatch):
    """``mlp_dense_ms_per_grad_step`` returns the op time under the MLP's
    scope per grad step."""
    from perf.metrics import _children

    read = Manifest(CHECKOUT).metric_reader("mlp_dense_ms_per_grad_step")
    monkeypatch.setattr(
        _children, "child_seconds",
        lambda run, trace, group: [{"mlp_dense": 0.5, "attention_full": 0.25,
                                    None: 0.1}])
    assert read({"traced_chunks": 2, "grad_steps_per_chunk": 1},
                object()) == pytest.approx(250.0)


def test_the_loop_reader_reads_what_no_mixer_holds(monkeypatch):
    """``loop_overhead_ms_per_grad_step`` sums the ops inside the iteration
    loop that lie under ``loops`` and under no mixer (none, or ``mixed``):
    not a mixer's ops, not what lies outside the scope."""
    from perf.metrics import _children
    from perf.reduce import trace_reduce

    tables = {
        "LOOPS": {"fusion.1": "loops", "fusion.2": "loops",
                  "fusion.3": "loops", "fusion.4": None},
        "CORE_PARTS": {"fusion.1": "mlp_dense", "fusion.2": None,
                       "fusion.3": "mixed", "fusion.4": None}}
    monkeypatch.setattr(_children, "children",
                        lambda run, group: tables[group])
    events = [("while.0", 0.0, 100e6), ("fusion.1", 1e6, 1e6),
              ("fusion.2", 3e6, 2e6), ("fusion.3", 6e6, 4e6),
              ("fusion.4", 11e6, 8e6)]
    trace = trace_reduce.reduce(
        [{"name": "/device:TPU:0",
          "lines": [{"name": trace_reduce.OPS_LINE, "events": events}]}],
        chips=1)
    read = Manifest(CHECKOUT).metric_reader("loop_overhead_ms_per_grad_step")
    assert read({"traced_chunks": 2, "grad_steps_per_chunk": 1},
                trace) == pytest.approx((2.0 + 4.0) / 2)
