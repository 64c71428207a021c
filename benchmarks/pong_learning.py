"""Full-game Pong learning AT CHIP RATE through the fused on-device loop.

The two existing full-game proofs split along the dev box's constraint:
the CPU leg learns fake-ALE Pong end-to-end through the REAL
AtariPreprocessing path (``ale_learning.py --calibrate-cpu``), and the
chip leg of that same harness is host-bound (emulator + actors + service
share the host's cores). This script closes the remaining gap from the
other side: the FUSED on-device loop on this exact env, trained until it
is WINNING whole games of the device-native Pong (envs/pixel_pong.py: ±1 per point, first-to-5
episodes, tracking opponent, spin). Same production stack as the atari
config: Nature CNN bf16, uint8 84x84x4 frame stacks, n-step TD, uniform
replay ring (the atari preset is plain Nature DQN; --head rainbow adds
PER + dueling + noisy), epsilon-greedy per lane.

Bar (ale_learning convention): FIRST chunk's training episode-return
window (epsilon ~1 -> the de-facto random baseline, ~-5 of the 5-point
game) vs the BEST window; cleared iff best >= first + --margin
(default +2.0 game points). Exit 0 iff cleared.

The run is WALL-bounded: a wall-clock stop_fn ends it at the chunk
boundary that crosses the post-compile budget, so the worst case is
compile + budget + one chunk, whatever the frame cap.

Usage:  python benchmarks/pong_learning.py [--budget-seconds 300]
            [--smoke] [--seed N]
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


def _apply_head(cfg, head: str):
    """Head surgery mirroring tests/test_pixel_learning.py, with C51's
    support sized per game (cfg.env_name). dqn = the atari config as-is."""
    import dataclasses as dc

    if head == "dqn":
        return cfg
    if head in ("c51", "rainbow"):
        # Support sized to the game's return range: Pong is a ±5 rally
        # game; Breakout returns count bricks (0..72).
        v_min, v_max = {"pixel_breakout": (-1.0, 80.0)}.get(
            cfg.env_name, (-6.0, 6.0))
        net = dc.replace(cfg.network, num_atoms=51, v_min=v_min,
                         v_max=v_max, noisy=(head == "rainbow"),
                         dueling=(head == "rainbow" or cfg.network.dueling))
        cfg = dc.replace(cfg, network=net)
        if head == "rainbow":
            # The FULL Rainbow combination on the atari torso: the
            # base preset is plain Nature DQN (uniform replay, no
            # dueling), so add PER + dueling here, and NoisyNet
            # exploration replaces the epsilon ladder (rainbow preset
            # convention, config.py).
            cfg = dc.replace(
                cfg,
                actor=dc.replace(cfg.actor, epsilon_start=0.0,
                                 epsilon_end=0.0),
                replay=dc.replace(cfg.replay, prioritized=True,
                                  priority_exponent=0.5,
                                  importance_exponent=0.4))
        return cfg
    if head == "qrdqn":
        return dc.replace(cfg, network=dc.replace(cfg.network,
                                                  num_atoms=64,
                                                  quantile=True))
    if head == "iqn":
        return dc.replace(cfg, network=dc.replace(
            cfg.network, iqn=True, iqn_embed_dim=32, iqn_tau_samples=16,
            iqn_tau_target_samples=16, iqn_tau_act=16))
    if head == "mdqn":
        # Munchausen requires n_step=1 (LearnerConfig.munchausen);
        # train_every=1 compensates the slower credit propagation.
        return dc.replace(
            cfg, learner=dc.replace(cfg.learner, munchausen=True,
                                    double_dqn=False, n_step=1),
            train_every=1)
    raise ValueError(head)


def _r2d2_cfg(args):
    """Recurrent variant: its own sizing (the feedforward lane/batch
    defaults do not transfer to sequence replay). Scaled between the
    r2d2 preset and the PixelCatch run (32 lanes, small torso): more
    lanes for frame rate, unroll 20 to span a few
    ball crossings, small torso to keep the 20-step BPTT affordable."""
    import dataclasses as dc

    from dist_dqn_tpu.config import CONFIGS

    cfg = CONFIGS["r2d2"]
    return dc.replace(
        cfg,
        env_name=args.env,
        network=dc.replace(cfg.network, torso="small", hidden=256,
                           lstm_size=64),
        actor=dc.replace(cfg.actor, num_envs=256,
                         epsilon_decay_steps=args.eps_decay_frames),
        # frame_dedup propagates: the sequence ring supports dedup too.
        replay=dc.replace(cfg.replay, capacity=131_072, min_fill=16_384,
                          burn_in=5, unroll_length=20,
                          sequence_stride=10,
                          frame_dedup=args.frame_dedup),
        learner=dc.replace(cfg.learner, batch_size=64,
                           learning_rate=5e-4, n_step=3,
                           target_update_period=500),
        train_every=2,
        eval_every_steps=0,
    )


def _cfg(args):
    """Full run config: base per head/env/smoke, then the optional lr
    anneal applied uniformly — r2d2 and smoke builds included, so a
    scheduled chip run's config bugs fail in the CPU smoke first."""
    cfg = _base_cfg(args)
    if args.lr_anneal_frames:
        # The schedule counts GRAD steps (agents/dqn.py:make_optimizer);
        # convert the frame horizon at the FINAL config's cadence
        # (mdqn overrides train_every to 1, r2d2 sizes its own lanes).
        # frames-per-grad-step = num_envs * train_every / updates_per_train
        # (each train event runs updates_per_train grad steps).
        grad_per_iter = max(
            1, cfg.actor.num_envs * cfg.train_every // cfg.updates_per_train)
        lr0 = cfg.learner.learning_rate
        cfg = dataclasses.replace(cfg, learner=dataclasses.replace(
            cfg.learner,
            lr_schedule="cosine",
            lr_decay_steps=max(1, args.lr_anneal_frames // grad_per_iter),
            lr_end_value=args.lr_end if args.lr_end is not None
            else lr0 / 10.0))
    return cfg


def _base_cfg(args):
    from dist_dqn_tpu.config import CONFIGS

    if args.head == "r2d2":
        cfg = _r2d2_cfg(args)
        if not args.smoke:
            return cfg
        # Tiny recurrent smoke: same runtime, CPU-compilable sizes.
        return dataclasses.replace(
            cfg,
            network=dataclasses.replace(cfg.network, torso="small",
                                        hidden=32, lstm_size=8),
            actor=dataclasses.replace(cfg.actor, num_envs=8,
                                      epsilon_decay_steps=2_000),
            replay=dataclasses.replace(cfg.replay, capacity=2_048,
                                       min_fill=256, burn_in=2,
                                       unroll_length=4,
                                       sequence_stride=2),
            learner=dataclasses.replace(cfg.learner, batch_size=4))
    cfg = CONFIGS["atari"]
    if args.smoke:
        # CPU harness check: tiny everything, bar not enforced — but the
        # SAME head family AND env as the chip run, so a head- or
        # env-specific config bug (e.g. the per-game C51 support) fails
        # here instead of costing a chip run its compile time.
        cfg = dataclasses.replace(
            cfg,
            env_name=args.env,
            network=dataclasses.replace(cfg.network, torso="small",
                                        hidden=32),
            actor=dataclasses.replace(cfg.actor, num_envs=8,
                                      epsilon_decay_steps=2_000),
            replay=dataclasses.replace(cfg.replay, capacity=2_048,
                                       min_fill=256,
                                       frame_dedup=args.frame_dedup),
            learner=dataclasses.replace(cfg.learner, batch_size=16),
            train_every=2, eval_every_steps=0)
        return _apply_head(cfg, args.head)
    actor_kw = dict(num_envs=args.lanes,
                    epsilon_decay_steps=args.eps_decay_frames)
    if args.eps_end is not None:
        actor_kw["epsilon_end"] = args.eps_end
    cfg = dataclasses.replace(
        cfg,
        env_name=args.env,
        actor=dataclasses.replace(cfg.actor, **actor_kw),
        replay=dataclasses.replace(
            cfg.replay, capacity=args.ring, min_fill=args.min_fill,
            frame_dedup=args.frame_dedup,
            flat_storage=args.flat_storage),
        learner=dataclasses.replace(
            cfg.learner, batch_size=args.batch_size,
            learning_rate=args.lr,
            target_update_period=args.target_update),
        train_every=args.train_every,
        eval_every_steps=0,   # training returns are the signal; greedy
                              # eval would add per-period device programs
    )
    return _apply_head(cfg, args.head)


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--budget-seconds", type=float, default=300.0,
                   help="post-compile wall budget for the learning loop; "
                        "a stop_fn ends the run at the first chunk "
                        "boundary past it")
    p.add_argument("--env", default="pixel_pong",
                   choices=["pixel_pong", "pixel_breakout"],
                   help="device-native game (envs/pixel_pong.py ±5 "
                        "rally game; envs/pixel_breakout.py 72-brick "
                        "wall with fire-to-serve and 5 lives)")
    p.add_argument("--margin", type=float, default=None,
                   help="improvement over the first (epsilon~1) chunk's "
                        "episode-return that counts as learning "
                        "(default per env: pong +2.0 of the ±5 game, "
                        "breakout +15 bricks over random's ~6)")
    p.add_argument("--total-env-steps", type=int, default=120_000_000,
                   help="frame-budget CAP; the wall-clock stop usually "
                        "fires first")
    p.add_argument("--lanes", type=int, default=1024)
    p.add_argument("--batch-size", type=int, default=512)
    p.add_argument("--flat-storage", action="store_true", default=None,
                   help="force replay.flat_storage=True (default: the "
                        "auto rule — flat above 2GB logical)")
    p.add_argument("--frame-dedup", action="store_true",
                   help="replay.frame_dedup: store single frames, "
                        "rebuild stacks at sample time — 4x the "
                        "affordable window")
    p.add_argument("--ring", type=int, default=131_072,
               help="4x the bench ring: at 1024 lanes the ring "
                    "holds 128 iterations of history — replay "
                    "diversity matters here, throughput does not")
    p.add_argument("--min-fill", type=int, default=32_768)
    p.add_argument("--train-every", type=int, default=2,
                   help="2 -> 0.25 examples/frame: twice the bench "
                        "cadence's learning signal, still learner-"
                        "underutilized at batch 512")
    p.add_argument("--lr", type=float, default=2.5e-4)
    p.add_argument("--lr-anneal-frames", type=int, default=None,
                   help="cosine-anneal the lr over this many env frames "
                        "(converted to grad steps at the run's cadence); "
                        "Breakout's late-run 40-53-brick oscillation is "
                        "the target")
    p.add_argument("--lr-end", type=float, default=None,
                   help="anneal floor (default lr/10)")
    p.add_argument("--target-update", type=int, default=500)
    p.add_argument("--eps-decay-frames", type=int, default=8_000_000)
    p.add_argument("--eps-end", type=float, default=None,
                   help="final exploration epsilon (default: the "
                        "preset's 0.05; Breakout's late-game oscillation "
                        "softens at 0.01)")
    p.add_argument("--chunk-iters", type=int, default=250,
                   help="250 x 1024 lanes = 256k frames per logged chunk")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--head", default="dqn",
                   choices=["dqn", "c51", "rainbow", "qrdqn", "iqn",
                            "mdqn", "r2d2"],
                   help="algorithm family on the same torso/replay stack "
                        "(surgery mirrors tests/test_pixel_learning.py; "
                        "r2d2 instead swaps in the recurrent runtime with "
                        "its own sizing — see _r2d2_cfg)")
    p.add_argument("--smoke", action="store_true",
                   help="CPU harness smoke: tiny sizes, bar not enforced")
    args = p.parse_args()
    if args.margin is None:
        args.margin = {"pixel_pong": 2.0, "pixel_breakout": 15.0}[args.env]
    if args.head == "rainbow" and args.eps_end is not None:
        print(json.dumps({"warning": "--head rainbow uses NoisyNet "
                          "exploration with epsilon pinned to 0; "
                          "--eps-end is ignored"}), flush=True)

    if args.smoke:
        args.total_env_steps = 16_000
        args.chunk_iters = 100
        args.budget_seconds = 120.0
    platforms = select_platform(allow_cpu=args.smoke)

    cfg = _cfg(args)

    from dist_dqn_tpu.train import train

    rows = []
    t_start = time.perf_counter()

    def log(line):
        print(line, flush=True)
        try:
            rows.append(json.loads(line))
        except (TypeError, ValueError):
            pass

    state = {"first": None, "deadline": None}

    def stop(row):
        # The clock starts at the FIRST chunk boundary (compile +
        # warmup excluded), so the budget buys measured learning time.
        if state["deadline"] is None:
            state["deadline"] = time.perf_counter() + args.budget_seconds
        # Baseline = the first chunk that actually finished episodes
        # (episode_return is a 0.0 sentinel when episodes == 0).
        if state["first"] is None and row["episodes"] > 0:
            state["first"] = row["episode_return"]
        cleared = (state["first"] is not None
                   and row["episodes"] > 0
                   and row["episode_return"]
                   >= state["first"] + args.margin)
        return cleared or time.perf_counter() >= state["deadline"]

    carry, history = train(cfg, total_env_steps=args.total_env_steps,
                           seed=args.seed, chunk_iters=args.chunk_iters,
                           log_fn=log, stop_fn=stop)
    wall = time.perf_counter() - t_start

    returns = [r["episode_return"] for r in history if r["episodes"] > 0]
    if not returns:          # smoke runs can end before any episode does
        returns = [0.0]
    first, best = returns[0], max(returns)
    frames = history[-1]["env_frames"]
    grad_steps = sum(r["grad_steps_in_chunk"] for r in history)
    cleared = best >= first + args.margin and not args.smoke
    summary = {
        "summary": "pong_learning", "env": cfg.env_name,
        "head": args.head,
        "platform": platforms, "torso": cfg.network.torso,
        "lanes": cfg.actor.num_envs, "batch_size": cfg.learner.batch_size,
        "train_every": cfg.train_every,
        "ring": cfg.replay.capacity,
        "frame_dedup": cfg.replay.frame_dedup,
        "first_return": round(float(first), 3),
        "best_return": round(float(best), 3),
        "final_return": round(float(returns[-1]), 3),
        "frames": int(frames), "grad_steps": int(grad_steps),
        "wall_s": round(wall, 1),
        "env_steps_per_sec": round(frames / wall, 1),
        "cleared_bar": bool(cleared), "margin": args.margin,
        "smoke": args.smoke,
    }
    print(json.dumps(summary), flush=True)
    if args.smoke:
        return 0
    return 0 if cleared else 1


if __name__ == "__main__":
    sys.exit(main())
