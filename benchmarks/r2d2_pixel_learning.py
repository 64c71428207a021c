"""R2D2 pixel-path LEARNING run — the on-chip leg of the evidence.

The recurrent pixel path's frame budget exceeds what a CPU reaches in
minutes, so its learning evidence on CPU stands on the CartPole SOLVE +
pixel smoke only. This script is the run for real hardware: the
tests/test_pixel_learning.py protocol (PixelCatch,
random baseline ~-0.6, clear-margin bar +0.5) through the FULL R2D2
machinery — sequence replay with burn-in, stored recurrent state, LSTM
Q-net, value rescale.

Prints one JSON row per chunk and a final summary row; exits 0 iff the
run clears the +0.5 bar.

Usage:  python benchmarks/r2d2_pixel_learning.py [--platform cpu]
                                                 [--total-env-steps N]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

RANDOM_BASELINE = -0.6
TARGET = 0.5


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--platform", default=None)
    p.add_argument("--total-env-steps", type=int, default=200_000)
    p.add_argument("--chunk-iters", type=int, default=250)
    args = p.parse_args()

    import jax

    if args.platform:
        jax.config.update("jax_platforms", args.platform)
    else:
        # No platform named: an accelerator, never a silent CPU run.
        from dist_dqn_tpu.utils.backend import require_accelerator
        require_accelerator()
    from dist_dqn_tpu.config import CONFIGS
    from dist_dqn_tpu.train import train

    cfg = CONFIGS["r2d2"]
    cfg = dataclasses.replace(
        cfg,
        env_name="pixel_catch",
        network=dataclasses.replace(cfg.network, torso="small", hidden=128,
                                    lstm_size=32),
        actor=dataclasses.replace(cfg.actor, num_envs=32,
                                  epsilon_decay_steps=10_000),
        replay=dataclasses.replace(cfg.replay, capacity=16_384, min_fill=1_500,
                                   burn_in=4, unroll_length=8,
                                   sequence_stride=4),
        learner=dataclasses.replace(cfg.learner, batch_size=32,
                                    learning_rate=1e-3, n_step=3,
                                    target_update_period=250),
        train_every=2,
        eval_every_steps=0,
    )

    t0 = time.time()

    stop = lambda row: row["episode_return"] >= TARGET  # noqa: E731
    _, history = train(cfg, total_env_steps=args.total_env_steps,
                       chunk_iters=args.chunk_iters,
                       log_fn=lambda s: print(s, flush=True), stop_fn=stop)
    returns = [r["episode_return"] for r in history]
    # Skip leading 0.0 rows (chunks before any episode completed); the
    # first real return must sit at the random baseline for the bar to
    # mean anything.
    real = [r for r in returns if r != 0.0]
    ok = (real and real[0] < RANDOM_BASELINE + 0.3
          and max(real) >= TARGET)
    print(json.dumps({
        "summary": "r2d2_pixel_learning",
        "platform": jax.devices()[0].platform,
        "first_return": real[0] if real else None,
        "best_return": max(real) if real else None,
        "frames": history[-1]["env_frames"] if history else 0,
        "wall_s": round(time.time() - t0, 1),
        "cleared_bar": bool(ok), "bar": TARGET,
        "random_baseline": RANDOM_BASELINE,
    }), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
