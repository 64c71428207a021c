"""The learner step's cost IN-SCAN, against its HBM roofline.

The standalone learner bench times each config's donated-state train
step with one dispatch per step; the cost of the SAME learner step
running inside the fused ``lax.scan`` (where there is no per-step
dispatch at all) is what the fused loop actually pays.

This bench isolates it by DIFFERENCING fused-loop chunks at
``train_every`` in {1, 2, never}: the train branch lives under a
``lax.cond`` (train_loop.py one_iteration), so a never-training chunk
executes the identical act/env/replay-insert program with zero train
cost, and

    inscan_step_s = (T(train_every=k) - T(never)) / grad_steps(k)

is the marginal in-scan cost of one sample+train+target-sync iteration
(uniform ring sample included — it is part of the branch; the replay
mode is forced uniform for comparability across configs). k=1 and k=2
must agree — that consistency check rides along in the row.

Each config row also re-times the STANDALONE step (the learner_bench
program) in the same process and carries the roofline census, so the
output is one table: per config, standalone gap vs in-scan gap.

Usage: python benchmarks/roofline_inscan.py [--configs atari qrdqn ...]
           [--allow-cpu] [--chunks 6] [--chunk-iters 200]
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

FEEDFORWARD = ["atari", "apex", "rainbow", "qrdqn", "iqn", "mdqn"]
NEVER = 1 << 30  # iteration % NEVER == 0 only at iter 0, where min_fill gates


def _fused_cfg(name: str, num_envs: int, ring: int):
    from dist_dqn_tpu.config import CONFIGS

    cfg = CONFIGS[name]
    return dataclasses.replace(
        cfg,
        env_name="pixel_pong",  # same Atari-shaped env for every head
        actor=dataclasses.replace(cfg.actor, num_envs=num_envs),
        # Uniform ring for every config: the differenced branch then
        # contains gather-sample + train + (no) priority ops identically
        # across heads, and matches the standalone step's uniform batch.
        replay=dataclasses.replace(cfg.replay, capacity=ring,
                                   prioritized=False,
                                   pallas_sampler=False,
                                   min_fill=4_096),
        updates_per_train=1,
    )


def _measure_fused(cfg, train_every: int, chunk_iters: int, chunks: int):
    """(steps_per_sec, grad_steps_per_chunk, chunk_seconds)."""
    import jax

    from dist_dqn_tpu.envs import make_jax_env
    from dist_dqn_tpu.models import build_network
    from dist_dqn_tpu.train_loop import make_fused_train

    cfg = dataclasses.replace(cfg, train_every=train_every)
    env = make_jax_env(cfg.env_name)
    net = build_network(cfg.network, env.num_actions)
    init, run_chunk = make_fused_train(cfg, env, net)
    run = jax.jit(run_chunk, static_argnums=1, donate_argnums=0)
    carry = init(jax.random.PRNGKey(0))
    compiled = run.lower(carry, chunk_iters).compile()
    # Chip-time attribution (ISSUE 19): this tool reports its own
    # roofline columns, so the registry entry is provenance only (no
    # per-row `programs` block).
    from dist_dqn_tpu.telemetry import devtime as devtime_mod
    devtime_mod.register_program(  # census of `run`'s fused chunk
        "roofline.chunk", loop="roofline", role="chunk", cost=compiled)

    def fence(metrics):
        return float(jax.device_get(metrics["loss"]))

    for _ in range(2):  # warmup + fill past min_fill
        carry, metrics = compiled(carry)
        fence(metrics)
    t0 = time.perf_counter()
    for _ in range(chunks):
        carry, metrics = compiled(carry)
    fence(metrics)
    dt = time.perf_counter() - t0
    grads = float(jax.device_get(metrics["grad_steps_in_chunk"]))
    steps_per_sec = chunks * chunk_iters * cfg.actor.num_envs / dt
    return steps_per_sec, grads, dt / chunks


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--configs", nargs="*", default=FEEDFORWARD)
    p.add_argument("--allow-cpu", action="store_true")
    p.add_argument("--chunks", type=int, default=6)
    p.add_argument("--chunk-iters", type=int, default=200)
    p.add_argument("--num-envs", type=int, default=1024)
    p.add_argument("--ring", type=int, default=16_384)
    p.add_argument("--standalone-iters", type=int, default=200)
    args = p.parse_args()

    select_platform(args.allow_cpu)
    if args.allow_cpu:
        # CPU smoke: shrink to harness-validation sizes.
        args.num_envs = min(args.num_envs, 8)
        args.chunk_iters = min(args.chunk_iters, 20)
        args.chunks = min(args.chunks, 2)
        args.ring = min(args.ring, 2_048)
        args.standalone_iters = min(args.standalone_iters, 3)

    from learner_bench import bench_config

    for name in args.configs:
        cfg = _fused_cfg(name, args.num_envs, args.ring)
        if args.allow_cpu:
            cfg = dataclasses.replace(
                cfg,
                network=dataclasses.replace(cfg.network,
                                            compute_dtype="float32"),
                replay=dataclasses.replace(cfg.replay, min_fill=64),
                learner=dataclasses.replace(cfg.learner, batch_size=32))

        # Order: never-train first (cheapest compile), then te=2, te=1.
        base_sps, g0, t_never = _measure_fused(
            cfg, NEVER, args.chunk_iters, args.chunks)
        assert g0 == 0.0, f"never-train variant trained ({g0} steps)"
        rows = {}
        for te in (2, 1):
            sps, grads, t_chunk = _measure_fused(
                cfg, te, args.chunk_iters, args.chunks)
            assert grads > 0, (
                f"train_every={te} chunk measured zero grad steps "
                f"(chunk_iters={args.chunk_iters} too small for the "
                f"cadence/min_fill?) — the marginal would be garbage")
            rows[te] = {
                "steps_per_sec": sps, "grads_per_chunk": grads,
                "chunk_s": t_chunk,
                "inscan_step_s": (t_chunk - t_never) / grads,
            }

        standalone = bench_config(name, args.standalone_iters, cfg=cfg)
        out = {
            "bench": "roofline_inscan", "config": name,
            "num_envs": cfg.actor.num_envs, "ring": args.ring,
            "batch_size": cfg.learner.batch_size,
            "chunk_iters": args.chunk_iters, "chunks": args.chunks,
            "never_steps_per_sec": round(base_sps, 1),
            "never_chunk_s": round(t_never, 4),
            "te1_steps_per_sec": round(rows[1]["steps_per_sec"], 1),
            "te2_steps_per_sec": round(rows[2]["steps_per_sec"], 1),
            "inscan_step_s_te1": round(rows[1]["inscan_step_s"], 6),
            "inscan_step_s_te2": round(rows[2]["inscan_step_s"], 6),
            "standalone_step_s": standalone.get("measured_step_s"),
            "roofline_s": standalone.get("roofline_s"),
            "roofline_bound": standalone.get("roofline_bound"),
            "standalone_gap_x": standalone.get("roofline_gap_x"),
        }
        if standalone.get("roofline_s"):
            out["inscan_gap_x_te1"] = round(
                rows[1]["inscan_step_s"] / standalone["roofline_s"], 2)
            out["inscan_gap_x_te2"] = round(
                rows[2]["inscan_step_s"] / standalone["roofline_s"], 2)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
