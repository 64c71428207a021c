"""The ``laguna_q`` configuration in the benchmark: its file against the
program's preset and the contract's keys, its cell's readers on a program
without their names, and the toy configuration of the cell that
``tests/test_laguna_cell.py`` drives through ``perf/run.py --allow-cpu`` (a
minute of compiling on the CPU: kept out of this directory, whose tests
tier-1 runs on one worker). The mathematics is held in
``tests/test_laguna_core.py``."""
from pathlib import Path

import pytest

from perf.harness.manifest import Manifest, resolve_cell

CHECKOUT = Path(__file__).resolve().parents[2]
CELL = "laguna_q.preset"
NEW_METRICS = ("window_attention_ms_per_grad_step",
               "full_attention_ms_per_grad_step",
               "dense_mlp_ms_per_grad_step", "experts_ms_per_grad_step",
               "laguna_core_unsplit_share")
# one published layer of each kind: full attention + dense MLP, window
# attention + experts
TOY_LAGUNA_CONFIG = {
    "name": "toylaguna", "source": "tests only", "preset": "laguna_q",
    "overrides": [
        "network.torso=small", "network.hidden=32", "network.remat_torso=false",
        "network.compute_dtype=float32", "network.core.pattern=FDWE",
        "network.core.attention_heads_per_layer=4,6",
        "network.core.num_key_value_heads=2", "network.core.head_dim=8",
        "network.core.sliding_window=4", "network.core.attention_window=16",
        "network.core.intermediate_size=48",
        "network.core.n_routed_experts=8", "network.core.experts_held=0,1",
        "network.core.num_experts_per_tok=3",
        "network.core.moe_intermediate_size=16",
        "network.core.moe_shared_expert_intermediate_size=24",
        "replay.burn_in=4", "replay.unroll_length=5",
        "replay.sequence_stride=4", "replay.capacity=512",
        "replay.min_fill=64",
        "learner.n_step=3", "learner.batch_size=4", "actor.num_envs=4",
        "train_every=2"],
    "reference": "laguna_float32", "chunk_iters": 8,
    "warmup": {"full_train_chunks": 2, "ring": "min_fill"},
    "trace_chunks": 2,
    "sizes": {"network.core.kind": "hybrid", "network.core.pattern": "FDWE",
              "network.core.experts_held": [0, 1], "network.lstm_size": 0,
              "train_every": 2},
}


def test_the_laguna_cell_is_in_the_benchmark_with_its_five_metrics():
    """The cell resolves to its files; its configuration is used by it and
    lists what it cut; its five per-layer metrics are listed in it alone,
    move a metric it reports, and each has a reader."""
    manifest = Manifest(CHECKOUT)
    plan = resolve_cell(manifest, CELL)
    assert (plan["preset"], plan["reference"], plan["chips"]) == (
        "laguna_q", "laguna_float32", 1)
    assert plan["chunk_iters"] == 8
    entry = manifest._entry("configs", "laguna_q")
    stated = manifest.config("laguna_q")
    assert set(entry["reduced"]) == set(stated["reduced"]) == {
        "num_hidden_layers", "layer_types", "mlp_layer_types",
        "num_attention_heads_per_layer", "num_experts", "vocab_size"}
    assert "layer_types[:5]" in entry["source"]
    reported = [m["name"] for m in manifest.metrics_of("end_to_end", CELL)]
    for name in NEW_METRICS:
        metric = manifest._entry("per_layer", name)
        assert metric["workloads"] == [CELL] and metric["moves"] in reported
        assert callable(manifest.metric_reader(name))


def test_the_laguna_file_holds_the_published_widths_and_its_cut():
    """Every width the file states is the program's preset (``build_config``
    refuses a difference under ``sizes``) and the published one; the keys
    under ``reduced`` are the only ones cut, each with its published value
    beside it; every reading under ``assumed`` has its ground."""
    from perf.harness.run_cell import build_config

    manifest = Manifest(CHECKOUT)
    stated = manifest.config("laguna_q")
    cfg = build_config(resolve_cell(manifest, CELL))
    core = cfg.network.core
    assert (stated["hidden_size"], stated["head_dim"],
            stated["num_key_value_heads"], stated["intermediate_size"],
            stated["moe_intermediate_size"],
            stated["shared_expert_intermediate_size"],
            stated["num_experts_per_tok"], stated["sliding_window"],
            stated["rms_norm_eps"], stated["moe_routed_scaling_factor"]) == (
        cfg.network.hidden, core.head_dim, core.num_key_value_heads,
        core.intermediate_size, core.moe_intermediate_size,
        core.moe_shared_expert_intermediate_size, core.num_experts_per_tok,
        core.sliding_window, core.norm_eps, core.routed_scaling_factor)
    assert stated["num_attention_heads_per_layer"] == list(
        core.attention_heads_per_layer)
    assert stated["num_experts"] == len(core.experts_held) == 8
    assert stated["published"]["num_experts"] == core.n_routed_experts == 256
    assert stated["num_hidden_layers"] == len(stated["layer_types"]) == len(
        stated["mlp_layer_types"]) == 5
    letters = {"full_attention": "F", "sliding_attention": "W",
               "dense": "D", "sparse": "E"}
    assert core.pattern == "".join(
        letters[a] + letters[m] for a, m in zip(stated["layer_types"],
                                                stated["mlp_layer_types"]))
    yarn = stated["rope_parameters"]["full_attention"]
    assert (yarn["rope_theta"], yarn["factor"], yarn["beta_fast"],
            yarn["beta_slow"], yarn["attention_factor"],
            yarn["partial_rotary_factor"],
            yarn["original_max_position_embeddings"]) == (
        core.rope_full.theta, core.rope_full.yarn_factor,
        core.rope_full.beta_fast, core.rope_full.beta_slow,
        core.rope_full.attention_factor, core.rope_full.rotary_factor,
        core.rope_full.original_positions)
    assert stated["rope_parameters"]["sliding_attention"]["rope_theta"] == (
        core.rope_window.theta)
    assert "vocab_size" not in stated
    assert set(stated["published"]) == set(stated["reduced"])
    assert {"gating", "mlp_form", "router", "qk_norm"} <= set(
        stated["assumed"])
    assert "32 chips share each layer" in stated["deployment"]


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_a_laguna_reader_is_silent_on_a_program_without_its_names(
        metric, monkeypatch):
    """On the parent's program — no ``attention_window`` among its names,
    or no group at all — each new reader returns None and does not raise:
    the line leaves the metric out."""
    from perf.metrics import _children

    read = Manifest(CHECKOUT).metric_reader(metric)
    for split in (None, {"fusion.1": "ssm", "fusion.2": None}):
        monkeypatch.setattr(_children, "children",
                            lambda run, group, split=split: split)

        class Trace:
            devices = ()

        assert read({"traced_chunks": 2, "grad_steps_per_chunk": 1},
                    Trace()) is None
