"""``perf/run.py --allow-cpu`` on the hybrid core at toy widths: the
``twotower_q`` preset with its reference module ``twotower_float32`` and the
per-layer readers of ``stages.CORE_PARTS``, as a configuration and a cell
ADDED to ``toy_root``'s root (files and entries, no harness file touched)."""
import json
import os
import subprocess
import sys
from pathlib import Path

from perf.tests import toy_root

CHECKOUT = Path(__file__).resolve().parents[2]
TOY_CORE_CONFIG = {
    "name": "toycore", "source": "tests only", "preset": "twotower_q",
    "overrides": [
        "network.torso=small", "network.hidden=32",
        "network.remat_torso=false", "network.compute_dtype=float32",
        "network.core.pattern=ME*M", "network.core.mamba_num_heads=4",
        "network.core.mamba_head_dim=8", "network.core.ssm_state_size=8",
        "network.core.n_groups=2", "network.core.chunk_size=4",
        "network.core.n_routed_experts=8", "network.core.experts_held=0,1",
        "network.core.num_experts_per_tok=3",
        "network.core.moe_intermediate_size=16",
        "network.core.moe_shared_expert_intermediate_size=24",
        "network.core.num_attention_heads=4",
        "network.core.num_key_value_heads=2", "network.core.head_dim=8",
        "network.core.attention_window=12",
        "replay.burn_in=4", "replay.unroll_length=5",
        "replay.sequence_stride=4", "replay.capacity=512",
        "replay.min_fill=64", "learner.n_step=3", "learner.batch_size=4",
        "actor.num_envs=4"],
    "reference": "twotower_float32", "chunk_iters": 8,
    "warmup": {"full_train_chunks": 2, "ring": "min_fill"},
    "trace_chunks": 2,
    "sizes": {"network.core.kind": "hybrid", "network.core.pattern": "ME*M",
              "network.core.experts_held": [0, 1], "network.lstm_size": 0},
}


def test_the_hybrid_cell_runs_through_the_harness(tmp_path):
    """The whole command on the toy hybrid cell, traced: the reference check
    (the step's five numbers and the ring's five) comes out ok, every chunk
    holds its counts, nothing compiles in the window, and the line has the
    contract's keys — what the chip run of ``twotower_q.preset`` does at the
    published widths."""
    root = toy_root.make(tmp_path)
    (root / "perf/configs/toycore.json").write_text(
        json.dumps(TOY_CORE_CONFIG))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "toycore", "source": "tests only",
                             "file": "perf/configs/toycore.json",
                             "reduced": [], "why": "toy"})
    bench["workloads"].append({"name": "toycore.toy1", "config": "toycore",
                               "traffic": "toy1", "chips": 1, "why": "toy"})
    for metric in bench["per_layer"]:
        if "twotower_q.preset" in metric.get("workloads", ()):
            metric["workloads"].append("toycore.toy1")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    record = root / "toycore.json"
    proc = subprocess.run(
        [sys.executable, str(CHECKOUT / "perf/run.py"), "--root", str(root),
         "--workload", "toycore.toy1", "--seed", str(2 ** 31 + 5),
         "--trace", "1", "--allow-cpu", "--record", str(record)],
        capture_output=True, text=True, timeout=280,
        env=dict(os.environ, JAX_PLATFORMS="cpu",
                 XLA_FLAGS="--xla_force_host_platform_device_count=1"))
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0, line
    assert line["attempted"] == 12 + 2
    assert {"q", "grad", "ring_windows", "ring_weights"} <= set(
        line["compared"])
    assert all(value <= limit for value, limit in line["compared"].values())
    kept = json.loads(record.read_text())
    assert kept["grad_steps_per_chunk"] == 8
    assert kept["grad_step_flops"] > 0
