"""Full-game learning proof through the real Atari path (fake ALE).

The other pixel-learning proofs run the PixelCatch toy through the
FUSED loop; this one shows learning on the Atari-shaped games through
the REAL ``ale:`` adapter stack —
AtariPreprocessing's frame-skip, max-pool, grayscale-resize, reward
clipping, episodic-life — which is what the driver's Atari configs
actually exercise. This script is that run: the apex split (config-3
shape: real actor processes, learner service on the accelerator)
training fake-ALE Pong or Breakout (envs/fake_ale.py: raw 210x160 RGB,
sticky-able, lives/fire-to-serve on Breakout) with the production
Nature-CNN torso, judged on TRAINING episode returns (the service's
episode_return metric — host-eval stepping costs one device call per
step; the training returns come free with ingestion).

Bar: the FIRST logged episode-return window (epsilon ~1: the de-facto
random baseline) vs the BEST window; cleared iff best >= first +
--margin (Pong: +2.0 game points of the 5-point fake game; Breakout:
+5 clipped brick rewards). Exit 0 iff cleared, r2d2_pixel_learning
style.

Sizing: same scheme as apex_split_bench — a small probe run pays all
compiles and measures the end-to-end rate, then the learning run's frame
budget is derived from that rate to fit --budget-seconds.

Usage:  python benchmarks/ale_learning.py [--game Pong|Breakout]
            [--budget-seconds 360] [--smoke]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

os.environ.setdefault("DQN_FAKE_ALE", "1")

from dist_dqn_tpu.utils.backend import select_platform  # noqa: E402

MARGINS = {"Pong": 2.0, "Breakout": 5.0}


def _cfg(args):
    from dist_dqn_tpu.config import CONFIGS

    small = args.smoke or args.torso == "small"
    cfg = CONFIGS["apex"]
    return dataclasses.replace(
        cfg,
        network=dataclasses.replace(
            cfg.network,
            torso="small" if args.smoke else args.torso,
            hidden=128 if small else cfg.network.hidden),
        replay=dataclasses.replace(
            cfg.replay, capacity=60_000,
            min_fill=300 if args.smoke else 2_000),
        learner=dataclasses.replace(
            cfg.learner,
            batch_size=args.batch_size,
            # The small torso takes the pixel-test lr (1e-3, proven on
            # PixelCatch); the Nature CNN stays at the conservative 3e-4.
            learning_rate=1e-3 if small else 3e-4, n_step=3,
            target_update_period=500),
        actor=dataclasses.replace(
            cfg.actor, epsilon_decay_steps=2_000 if args.smoke else 30_000),
    )


def _run(cfg, args, total):
    from dist_dqn_tpu.actors.service import ApexRuntimeConfig, run_apex

    rows = []

    def capture(line):
        print(line, flush=True)
        try:
            rows.append(json.loads(line))
        except (TypeError, ValueError):
            pass

    rt = ApexRuntimeConfig(
        host_env=f"ale:{args.game}", num_actors=args.actors,
        envs_per_actor=args.lanes_per_actor,
        total_env_steps=total, log_every_s=5.0,
        inserts_per_grad_step=args.inserts_per_grad_step)
    t0 = time.perf_counter()
    summary = run_apex(cfg, rt, log_fn=capture)
    return summary, time.perf_counter() - t0, rows


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--game", choices=sorted(MARGINS), default="Pong")
    p.add_argument("--torso", default="nature",
                   help="production default: the atari config's Nature CNN")
    p.add_argument("--margin", type=float, default=None,
                   help="improvement over the first (epsilon~1) episode-"
                        "return window that counts as learning "
                        f"(defaults per game: {MARGINS})")
    p.add_argument("--budget-seconds", type=float, default=600.0,
                   help="learning-run wall budget; the frame total is "
                        "derived from the probe phase's measured rate")
    p.add_argument("--total-env-steps", type=int, default=2_000_000,
                   help="frame-budget CAP (the rate-derived total never "
                        "exceeds it)")
    p.add_argument("--smoke", action="store_true",
                   help="CPU harness smoke: tiny sizes, bar not enforced "
                        "(1-core boxes cannot learn a game in minutes)")
    p.add_argument("--seed", type=int, default=None,
                   help="experiment seed (default: the apex preset's)")
    p.add_argument("--actors", type=int, default=None,
                   help="default: 4 (chip/smoke), 2 (--calibrate-cpu)")
    p.add_argument("--lanes-per-actor", type=int, default=8)
    p.add_argument("--batch-size", type=int, default=None,
                   help="default: 128 (chip), 64 (--calibrate-cpu), "
                        "32 (--smoke)")
    p.add_argument("--inserts-per-grad-step", type=int, default=None,
                   help="replay ratio knob. "
                        "Default: 16 (chip/smoke), 64 (--calibrate-cpu "
                        "— 16 monopolizes a shared core, measured "
                        "ingest stalls)")
    p.add_argument("--calibrate-cpu", action="store_true",
                   help="CPU calibration run: full-size protocol with the "
                        "'small' torso and the bar ENFORCED — validates "
                        "that the game/knobs/bar are learnable before "
                        "spending chip time on the real run")
    args = p.parse_args()
    if args.smoke and args.calibrate_cpu:
        p.error("--smoke and --calibrate-cpu are mutually exclusive: "
                "smoke checks pipeline health at tiny sizes, calibrate "
                "enforces the learning bar at full protocol sizes")
    margin = args.margin if args.margin is not None else MARGINS[args.game]

    # Per-mode defaults; explicit flags always win (None = unset).
    if args.calibrate_cpu:
        # Gentler shared-core settings — the first calibration attempt
        # at the chip settings starved ingestion on 1 core.
        mode_defaults = dict(actors=2, batch_size=64,
                             inserts_per_grad_step=64)
    elif args.smoke:
        mode_defaults = dict(actors=4, batch_size=32,
                             inserts_per_grad_step=16)
    else:
        mode_defaults = dict(actors=4, batch_size=128,
                             inserts_per_grad_step=16)
    for name, value in mode_defaults.items():
        if getattr(args, name) is None:
            setattr(args, name, value)

    platform = select_platform(allow_cpu=args.smoke or args.calibrate_cpu)
    if args.calibrate_cpu and args.torso == "nature":
        args.torso = "small"  # the CNN a CPU can train

    cfg = _cfg(args)
    if args.seed is not None:
        cfg = dataclasses.replace(cfg, seed=args.seed)
    t0 = time.time()

    # Probe phase: all compiles + the sustainable end-to-end rate.
    probe_total = 600 if args.smoke else 4_000
    summary, wall, _ = _run(cfg, args, probe_total)
    rate = summary["env_steps"] / max(wall, 1e-9)
    print(json.dumps({"phase": "probe", "wall_s": round(wall, 1),
                      "env_steps_per_sec": round(rate, 1)}), flush=True)

    total = min(args.total_env_steps,
                max(int(rate * args.budget_seconds), 2 * probe_total))
    summary, wall, rows = _run(cfg, args, total)

    curve = [r for r in rows if r.get("episodes_completed", 0) > 0
             and "episode_return" in r]
    first = curve[0]["episode_return"] if curve else None
    best = max(r["episode_return"] for r in curve) if curve else None
    ok = (first is not None and best is not None
          and best >= first + margin)
    print(json.dumps({
        "summary": "ale_learning", "game": args.game,
        "fake_ale": os.environ.get("DQN_FAKE_ALE") == "1",
        "platform": platform, "torso": cfg.network.torso,
        "first_return": first, "best_return": best,
        "episodes": summary["episodes_completed"],
        "frames": summary["env_steps"],
        "grad_steps": summary["grad_steps"],
        "wall_s": round(time.time() - t0, 1),
        "cleared_bar": bool(ok), "margin": margin,
        "smoke": args.smoke, "calibrate_cpu": args.calibrate_cpu,
    }), flush=True)
    if args.smoke:
        # Harness smoke: pipeline health only — frames flowed and the
        # learner trained. Episodes need thousands of decisions each
        # (5-point games), far past a tiny smoke budget.
        return 0 if (summary["env_steps"] >= total
                     and summary["grad_steps"] > 0) else 1
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
