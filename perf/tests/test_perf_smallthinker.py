"""The ``smallthinker_q`` configuration in the benchmark: its file against
the program's preset, the catalog row's numbers and the contract's keys, its
cell's readers on a program without their names, and the toy configuration
of the cell that ``tests/test_smallthinker_cell.py`` drives through
``perf/run.py --allow-cpu`` (a minute of compiling on the CPU: kept out of
this directory, whose tests tier-1 runs on one worker). The mathematics is
held in ``tests/test_smallthinker_core.py``."""
from pathlib import Path

import pytest

from perf.harness.manifest import Manifest, resolve_cell

CHECKOUT = Path(__file__).resolve().parents[2]
CELL = "smallthinker_q.preset"
NEW_METRICS = ("attention_window_ms_per_grad_step",
               "attention_full_ms_per_grad_step")
# what the catalog row's ``config`` states (``architectures.jsonl``,
# SmallThinker-21BA3B-Instruct), but the five keys under ``reduced``
PUBLISHED = {
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 28, "num_key_value_heads": 4,
    "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1500000,
    "sliding_window_size": 4096, "tie_word_embeddings": False}
# one published layer of each kind at toy widths
TOY_SMALLTHINKER_CONFIG = {
    "name": "toysmallthinker", "source": "tests only",
    "preset": "smallthinker_q",
    "overrides": [
        "network.torso=small", "network.hidden=32", "network.remat_torso=false",
        "network.compute_dtype=float32", "network.core.pattern=FEWE",
        "network.core.attention_heads_per_layer=14,14",
        "network.core.num_key_value_heads=2", "network.core.head_dim=8",
        "network.core.sliding_window=6", "network.core.attention_window=16",
        "network.core.n_routed_experts=8", "network.core.experts_held=0,1",
        "network.core.num_experts_per_tok=3",
        "network.core.moe_intermediate_size=16",
        "replay.burn_in=6", "replay.unroll_length=5",
        "replay.sequence_stride=6", "replay.capacity=512",
        "replay.min_fill=64",
        "learner.n_step=3", "learner.batch_size=4", "actor.num_envs=4",
        "train_every=2"],
    "reference": "smallthinker_float32", "chunk_iters": 8,
    "warmup": {"full_train_chunks": 2, "ring": "min_fill"},
    "trace_chunks": 2,
    "sizes": {"network.core.kind": "hybrid", "network.core.pattern": "FEWE",
              "network.core.experts_held": [0, 1],
              "network.core.router_ahead": True, "network.lstm_size": 0,
              "train_every": 2},
}


def test_the_smallthinker_cell_is_in_the_benchmark_with_its_metrics():
    """The cell resolves to its files; its configuration is used by it and
    lists what it cut; the two per-layer metrics it brings — the attention
    scopes' readers under the scopes' names, to whose lists a later cell
    appends itself — list it, move a metric it reports, and each has a
    reader; the router's
    and the routed experts' readers, the unsplit share and the four host
    and loop readers of every sequence cell report it too."""
    manifest = Manifest(CHECKOUT)
    plan = resolve_cell(manifest, CELL)
    assert (plan["preset"], plan["reference"], plan["chips"]) == (
        "smallthinker_q", "smallthinker_float32", 1)
    assert plan["chunk_iters"] == 32
    entry = manifest._entry("configs", "smallthinker_q")
    stated = manifest.config("smallthinker_q")
    assert set(entry["reduced"]) == set(stated["reduced"]) == {
        "num_hidden_layers", "sliding_window_layout", "rope_layout",
        "moe_num_primary_experts", "vocab_size"}
    assert "layout[:4] = 0, 1, 1, 1" in entry["source"]
    assert entry["source"].startswith(stated["source"])
    assert len(entry["source"]) <= 200
    reported = [m["name"] for m in manifest.metrics_of("end_to_end", CELL)]
    for name in NEW_METRICS:
        metric = manifest._entry("per_layer", name)
        assert CELL in metric["workloads"]
        assert metric["moves"] in reported
        assert callable(manifest.metric_reader(name))
    listed = {m["name"] for m in manifest.metrics_of("per_layer", CELL)}
    assert {"moe_router_ms_per_grad_step", "moe_routed_ms_per_grad_step",
            "core_unsplit_share", "loop_gap_share", "chunk_dispatch_ms",
            "chunk_dispatch_worst_ms", "chunk_bookkeeping_worst_ms",
            "learn_ms_per_grad_step", "train_mfu"} <= listed
    # every stage is entered in this program and its traced run on the chip
    # filled all ten stage readers (PERF.md §5, PR 50)
    assert {"act_ms_per_iter", "env_ms_per_iter", "insert_ms_per_iter",
            "sample_ms_per_grad_step", "gather_ms_per_grad_step",
            "loss_grad_ms_per_grad_step", "optimizer_ms_per_grad_step",
            "writeback_ms_per_grad_step", "loss_grad_mfu",
            "stage_unattributed_share"} <= listed
    # no shared expert and no dense MLP: their readers have nothing to read
    assert not {"moe_shared_ms_per_grad_step",
                "dense_mlp_ms_per_grad_step"} & listed


def test_the_smallthinker_file_holds_the_published_widths_and_its_cut():
    """Every number of the catalog row's ``config`` stands in the file under
    its own key, but the five under ``reduced``, each of which has its
    published value beside it; every width the file states is the
    program's preset (``build_config`` refuses a difference under
    ``sizes``); every reading under ``assumed`` has its ground."""
    from perf.harness.run_cell import build_config

    manifest = Manifest(CHECKOUT)
    stated = manifest.config("smallthinker_q")
    for key, value in PUBLISHED.items():
        assert stated[key] == value, key
    assert set(stated["published"]) == set(stated["reduced"])
    assert "vocab_size" not in stated
    cfg = build_config(resolve_cell(manifest, CELL))
    core = cfg.network.core
    assert (stated["hidden_size"], stated["head_dim"],
            stated["num_key_value_heads"], stated["moe_ffn_hidden_size"],
            stated["moe_num_active_primary_experts"],
            stated["sliding_window_size"], stated["rms_norm_eps"],
            stated["rope_theta"]) == (
        cfg.network.hidden, core.head_dim, core.num_key_value_heads,
        core.moe_intermediate_size, core.num_experts_per_tok,
        core.sliding_window, core.norm_eps, core.rope_window.theta)
    assert set(core.attention_heads_per_layer) == {
        stated["num_attention_heads"]}
    assert stated["moe_num_primary_experts"] == len(core.experts_held) == 8
    assert stated["published"]["moe_num_primary_experts"] == (
        core.n_routed_experts) == 64
    assert stated["num_hidden_layers"] == len(
        stated["sliding_window_layout"]) == len(stated["rope_layout"]) == 4
    assert stated["sliding_window_layout"] == stated["rope_layout"]
    assert core.pattern == "".join(
        "WE" if windowed else "FE"
        for windowed in stated["sliding_window_layout"])
    # rope_layout 0 is no position embedding; 1 the plain one over all dims
    assert core.rope_full.rotary_factor == 0.0
    assert (core.rope_window.rotary_factor, core.rope_window.yarn_factor,
            core.rope_window.attention_factor) == (1.0, 0.0, 1.0)
    assert (core.router_ahead, core.router_scores, core.expert_act,
            core.moe_shared_expert_intermediate_size, core.attention_gate,
            core.router_bias) == (True, "softmax", "relu", 0, False, False)
    assert {"router_placement", "router", "mlp_form", "attention",
            "layout"} <= set(stated["assumed"])
    assert "8 chips share each layer" in stated["deployment"]
    assert "13 stages" in stated["deployment"]
    window = (cfg.replay.burn_in + cfg.replay.unroll_length
              + cfg.learner.n_step)
    assert (window, cfg.learner.batch_size * window) == (8192, 16384)
    assert cfg.replay.burn_in == core.sliding_window
    assert cfg.train_every == resolve_cell(manifest, CELL)["chunk_iters"]


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_a_smallthinker_reader_is_silent_on_a_program_without_its_names(
        metric, monkeypatch):
    """On a program with no ``attention_window`` among its names, or no
    group at all — the parent's programs in their cells — each new reader
    returns None and does not raise: the line leaves the metric out."""
    from perf.metrics import _children

    read = Manifest(CHECKOUT).metric_reader(metric)
    for split in (None, {"fusion.1": "ssm", "fusion.2": None}):
        monkeypatch.setattr(_children, "children",
                            lambda run, group, split=split: split)

        class Trace:
            devices = ()

        assert read({"traced_chunks": 2, "grad_steps_per_chunk": 1},
                    Trace()) is None


@pytest.mark.parametrize("metric,child", zip(
    NEW_METRICS, ("attention_window", "attention_full")))
def test_a_smallthinker_reader_reads_its_scope(metric, child, monkeypatch):
    """Each reader returns the op time under its own scope per grad step."""
    from perf.metrics import _children

    read = Manifest(CHECKOUT).metric_reader(metric)
    monkeypatch.setattr(
        _children, "child_seconds",
        lambda run, trace, group: [{"attention_window": 0.5,
                                    "attention_full": 0.25, None: 0.1}])
    assert read({"traced_chunks": 2, "grad_steps_per_chunk": 1},
                object()) == pytest.approx(
        {"attention_window": 250.0, "attention_full": 125.0}[child])
