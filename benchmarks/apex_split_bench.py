"""End-to-end Ape-X split benchmark: learner on the chip, real actors.

bench.py measures the FUSED on-device loop with a synthetic on-device
env, but the 50k/chip target (BASELINE.json:5,9) is stated for config 3
— CPU rollout actors streaming trajectories to a chip-side learner service. This stage times
that actual program: ``actors/service.py`` with the learner on the TPU,
fed by real shm actor processes stepping the fake-ALE Atari path
(``ale:Pong`` — raw 210x160 frames through the REAL AtariPreprocessing
stack), reporting steady-state env-steps/s/chip and grad-steps/s.

Honesty note (goes with the number): the HOST side of the split shares
one machine's CPU cores between the whole actor fleet + env stepping +
assembly, so the env-steps/s number here can be host-core-bound, not
chip-bound — production Ape-X gives actors their own host pools. The
chip-side service rate (grad-steps/s with batches sampled from the live
host shard) is the part the chip controls, and the vector variant shows
the transport/learner pipeline at a cheaper env to separate env cost
from transport cost.

Sizing: both variants run TWO phases in one process: a small fixed-size
PROBE run (pays all compiles, measures the achievable rate on this
host), then a MEASURE run whose frame budget is DERIVED from the probe's
measured rate to fit ``--measure-seconds`` of steady state. Compiles are
paid once (same process, in-memory jit cache).

Usage:  python benchmarks/apex_split_bench.py [--allow-cpu]
            [--variants pixel vector] [--measure-seconds 120]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from dist_dqn_tpu.utils.backend import select_platform  # noqa: E402

# ale-py is structurally absent from this offline image (SURVEY.md §7);
# the pixel variant routes ale:Pong through the in-repo fake emulator —
# raw 210x160 RGB frames through the REAL AtariPreprocessing stack
# (envs/gym_adapter.py). Actor subprocesses inherit the env var. The
# result rows carry fake_ale so a real-ALE install is distinguishable.
import os  # noqa: E402

os.environ.setdefault("DQN_FAKE_ALE", "1")
FAKE_ALE = os.environ["DQN_FAKE_ALE"] == "1"


def _configs(variant: str, smoke: bool):
    """(cfg, rt_kwargs, probe_total) for a variant. Sizes are the probe
    phase only — the measure phase is sized from the probe's rate."""
    from dist_dqn_tpu.config import CONFIGS

    if variant == "pixel":
        cfg = CONFIGS["apex"]
        cfg = dataclasses.replace(
            cfg,
            # Host-DRAM shard sized for the bench box, not the 1M-slot
            # pod shard (28 GB of frames): 60k slots ~ 1.7 GB.
            replay=dataclasses.replace(cfg.replay, capacity=60_000,
                                       min_fill=2_000 if not smoke else 200),
            learner=dataclasses.replace(
                cfg.learner, batch_size=256 if not smoke else 32),
        )
        rt_kwargs = dict(host_env="ale:Pong", num_actors=4,
                         envs_per_actor=8)
        probe_total = 4_000 if not smoke else 600
    elif variant == "vector":
        cfg = CONFIGS["apex"]
        cfg = dataclasses.replace(
            cfg,
            network=dataclasses.replace(cfg.network, torso="mlp",
                                        mlp_features=(256, 256), hidden=0,
                                        compute_dtype="float32"),
            replay=dataclasses.replace(cfg.replay, capacity=200_000,
                                       min_fill=2_000 if not smoke else 200),
            learner=dataclasses.replace(
                cfg.learner, batch_size=256 if not smoke else 32),
        )
        rt_kwargs = dict(host_env="CartPole-v1", num_actors=8,
                         envs_per_actor=16)
        probe_total = 20_000 if not smoke else 1_500
    else:
        raise ValueError(f"unknown variant {variant!r}")
    return cfg, rt_kwargs, probe_total


def _run(cfg, rt_kwargs, total: int):
    """One service run; returns (summary, wall_s, steady_rates) where
    steady_rates comes from the LAST windowed-rate log row (the service
    logs env/grad rates over a 30s window every log_every_s)."""
    from dist_dqn_tpu.actors.service import ApexRuntimeConfig, run_apex

    rows = []

    def capture(line):
        try:
            rows.append(json.loads(line))
        except (TypeError, ValueError):
            pass

    rt = ApexRuntimeConfig(total_env_steps=total, log_every_s=5.0,
                           **rt_kwargs)
    t0 = time.perf_counter()
    summary = run_apex(cfg, rt, log_fn=capture)
    wall = time.perf_counter() - t0
    rate_rows = [r for r in rows
                 if r.get("env_steps_per_sec_per_chip", 0) > 0]
    steady = rate_rows[-1] if rate_rows else {}
    return summary, wall, steady


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--allow-cpu", action="store_true",
                   help="smoke the harness on CPU (tiny sizes; NOT for "
                        "BASELINE numbers)")
    p.add_argument("--variants", nargs="*", default=["pixel", "vector"])
    p.add_argument("--measure-seconds", type=float, default=120.0)
    args = p.parse_args()

    platforms = select_platform(args.allow_cpu)

    ok = True
    for variant in args.variants:
        cfg, rt_kwargs, probe_total = _configs(variant, args.allow_cpu)

        # Phase 1 — fixed small probe: pays every compile, measures the
        # end-to-end rate this host can actually sustain.
        summary, wall, steady = _run(cfg, rt_kwargs, probe_total)
        probe_rate = summary["env_steps"] / max(wall, 1e-9)
        print(json.dumps({"bench": "apex_split", "variant": variant,
                          "phase": "probe", "wall_s": round(wall, 1),
                          "avg_env_steps_per_sec": round(probe_rate, 1),
                          **{k: summary[k] for k in
                             ("env_steps", "grad_steps", "ring_dropped",
                              "bad_records")}}), flush=True)

        # Phase 2 — measure run sized FROM the probe rate (compiles are
        # already cached in-process): ~measure-seconds of steady state.
        # The probe's steady-window rate (if a row landed) beats its
        # compile-depressed average; even a 2x over-estimate only
        # doubles the measure wall time.
        best_rate = max(probe_rate,
                        steady.get("env_steps_per_sec_per_chip") or 0.0)
        measure_total = max(int(best_rate * args.measure_seconds),
                            2 * probe_total)
        summary, wall, steady = _run(cfg, rt_kwargs, measure_total)
        row = {
            "bench": "apex_split", "variant": variant, "phase": "measure",
            "platforms": platforms, "fake_ale": FAKE_ALE,
            "host_env": rt_kwargs["host_env"],
            "actors": rt_kwargs["num_actors"],
            "lanes": rt_kwargs["num_actors"] * rt_kwargs["envs_per_actor"],
            "batch_size": cfg.learner.batch_size,
            "total_env_steps": measure_total,
            "wall_s": round(wall, 1),
            "avg_env_steps_per_sec":
                round(summary["env_steps"] / max(wall, 1e-9), 1),
            "steady_env_steps_per_sec_per_chip":
                steady.get("env_steps_per_sec_per_chip"),
            "steady_grad_steps_per_sec":
                steady.get("grad_steps_per_sec"),
            "note": "host side is 1-core-bound on this dev box; see "
                    "module docstring",
            **{k: summary[k] for k in
               ("env_steps", "grad_steps", "replay_size", "ring_dropped",
                "tcp_backpressure", "bad_records", "actor_restarts")},
        }
        print(json.dumps(row), flush=True)
        ok = ok and summary["bad_records"] == 0
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
