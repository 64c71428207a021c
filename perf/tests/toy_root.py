"""A throwaway benchmark root for the CPU tests: the real ``BENCHMARK.json``
plus a toy configuration, three toy traffic mixes (one chip, a 4-device mesh, a window too short)
and a toy per-layer metric — ADDED AS FILES AND ENTRIES ONLY. Nothing of the
harness is edited or patched: this is the proof that a later PR can add a
cell the same way."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

CHECKOUT = Path(__file__).resolve().parents[2]

TOY_CONFIG = {
    "name": "toy", "source": "tests only", "preset": "atari",
    "overrides": ["network.torso=small", "network.hidden=32",
                  "replay.capacity=512", "replay.min_fill=64",
                  "learner.batch_size=8", "actor.num_envs=4"],
    "reference": "dqn_float32", "chunk_iters": 8,
    "warmup": {"full_train_chunks": 2, "ring": "full"}, "trace_chunks": 2,
    "sizes": {"network.torso": "small", "actor.num_envs": 4},
}
# A second learner kind, again as files and entries: the sequence learner
# (preset ``r2d2``) with its own reference module, at toy widths.
TOY_SEQ_CONFIG = {
    "name": "toyseq", "source": "tests only", "preset": "r2d2",
    "overrides": ["network.torso=small", "network.hidden=32",
                  "network.lstm_size=16", "network.lstm_unroll=1",
                  "replay.burn_in=4",
                  "replay.unroll_length=8", "replay.sequence_stride=4",
                  "replay.capacity=512", "replay.min_fill=64",
                  "replay.frame_dedup=true", "learner.n_step=3",
                  "learner.batch_size=4", "actor.num_envs=4"],
    "reference": "r2d2_float32", "chunk_iters": 8,
    "warmup": {"full_train_chunks": 2, "ring": "min_fill"},
    "trace_chunks": 2,
    "sizes": {"network.lstm_size": 16, "replay.unroll_length": 8},
}
# ``test_window_chunks``: the window is that many chunks whatever the clock
# says (tests only, refused on a chip), so no test times the CPU.
TOY_TRAFFIC = {
    "toy1": {"overrides": [], "num_devices": 1, "test_window_chunks": 12},
    "toy4": {"overrides": ["actor.num_envs=8", "learner.batch_size=16",
                           "replay.capacity=1024"],
             "num_devices": 4, "trace_chunks": 1, "test_window_chunks": 12},
    "toyshort": {"overrides": [], "num_devices": 1, "test_window_chunks": 5},
}
TOY_METRIC = '''"""A toy program counter: chunks the window held."""


def read(run, trace):
    return float(len(run["series"]["cycle_s"]))
'''


def make(tmp: Path) -> Path:
    """Build the root under ``tmp``; returns it (pass as ``--root``)."""
    root = Path(tmp) / "root"
    shutil.copytree(CHECKOUT / "perf", root / "perf",
                    ignore=shutil.ignore_patterns(
                        "records", "__pycache__", "tests"))
    bench = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
    (root / "perf/configs/toy.json").write_text(json.dumps(TOY_CONFIG))
    (root / "perf/configs/toyseq.json").write_text(
        json.dumps(TOY_SEQ_CONFIG))
    for name, traffic in TOY_TRAFFIC.items():
        (root / f"perf/traffic/{name}.json").write_text(json.dumps(traffic))
    (root / "perf/metrics/toy_window_chunks.py").write_text(TOY_METRIC)
    bench["configs"].append({"name": "toy", "source": "tests only",
                             "file": "perf/configs/toy.json", "reduced": [],
                             "why": "toy"})
    bench["configs"].append({"name": "toyseq", "source": "tests only",
                             "file": "perf/configs/toyseq.json",
                             "reduced": [], "why": "toy"})
    bench["workloads"] += [
        {"name": "toyseq.toy1", "config": "toyseq", "traffic": "toy1",
         "chips": 1, "why": "toy"},
        {"name": "toy.toy1", "config": "toy", "traffic": "toy1", "chips": 1,
         "why": "toy"},
        {"name": "toy.toy4", "config": "toy", "traffic": "toy4", "chips": 4,
         "why": "toy"},
        {"name": "toy.toyshort", "config": "toy", "traffic": "toyshort",
         "chips": 1, "why": "toy"}]
    bench["per_layer"].append(
        {"name": "toy_window_chunks", "unit": "count", "better": "higher",
         "source": "program_counter", "layer": "Host loop (train.train)",
         "moves": "env_steps_per_s_chip",
         "workloads": ["toy.toy1", "toyseq.toy1"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root
