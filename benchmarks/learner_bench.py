"""Learner grad-steps/sec microbenchmark (north-star metric #2).

BASELINE.json:2 names learner grad-steps/sec alongside env-steps/sec/chip as
the throughput metrics this framework is judged on. The benchmark
(perf/run.py) covers the fused actor+learner loop; this script isolates the *learner* train step —
what the Ape-X service spends its device time on — for each driver config's
network/batch shape, on the accelerator (every row names its platform;
an explicit --platform cpu runs the harness on the CPU instead).

Per config: build the configured Q-net, jit the train step with donated
state (exactly how both runtimes call it), run a timed chain of steps, and
fence with a device_get. Prints one JSON line per config.

Usage: python benchmarks/learner_bench.py [--configs atari apex ...]
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax
import jax.numpy as jnp
import numpy as np

OBS_SHAPE = (84, 84, 4)
NUM_ACTIONS = 6


def _feedforward_case(cfg):
    """(state, jitted step, args) for the DQN/Rainbow-style learners.
    The batch width resolves through the ISSUE 6 pow2 bucket rule
    (loop_common.resolve_train_batch) — identical to learner.batch_size
    unless replay.train_batch widens it."""
    from dist_dqn_tpu import loop_common
    from dist_dqn_tpu.agents.dqn import make_learner
    from dist_dqn_tpu.models.qnets import build_network
    from dist_dqn_tpu.types import Transition

    net = build_network(cfg.network, NUM_ACTIONS)
    init, train_step = make_learner(net, cfg.learner)
    rng = jax.random.PRNGKey(0)
    state = init(rng, jnp.zeros(OBS_SHAPE, jnp.uint8))
    B = loop_common.resolve_train_batch(cfg)
    r = np.random.default_rng(0)
    batch = Transition(
        obs=jnp.asarray(r.integers(0, 255, (B,) + OBS_SHAPE, np.uint8)),
        action=jnp.asarray(r.integers(0, NUM_ACTIONS, B, np.int32)),
        reward=jnp.asarray(r.normal(size=B).astype(np.float32)),
        discount=jnp.full(B, cfg.learner.gamma ** cfg.learner.n_step,
                          jnp.float32),
        next_obs=jnp.asarray(r.integers(0, 255, (B,) + OBS_SHAPE, np.uint8)),
    )
    weights = jnp.ones(B, jnp.float32)
    step = jax.jit(train_step, donate_argnums=0)
    return state, step, (batch, weights)


def _r2d2_case(cfg):
    """(state, jitted step, args) for the recurrent sequence learner.
    Sequence-batch width resolves through the same bucket rule as the
    loops (replay.train_batch widens sequences there too)."""
    from dist_dqn_tpu import loop_common
    from dist_dqn_tpu.agents.r2d2 import make_r2d2_learner
    from dist_dqn_tpu.models.qnets import build_network
    from dist_dqn_tpu.types import SequenceSample

    net = build_network(cfg.network, NUM_ACTIONS)
    init, train_step = make_r2d2_learner(net, cfg.learner, cfg.replay)
    state = init(jax.random.PRNGKey(0), jnp.zeros(OBS_SHAPE, jnp.uint8))
    S = loop_common.resolve_train_batch(cfg)
    T = cfg.replay.burn_in + cfg.replay.unroll_length + cfg.learner.n_step
    r = np.random.default_rng(0)
    sample = SequenceSample(
        obs=jnp.asarray(r.integers(0, 255, (T, S) + OBS_SHAPE, np.uint8)),
        action=jnp.asarray(r.integers(0, NUM_ACTIONS, (T, S), np.int32)),
        reward=jnp.asarray(r.normal(size=(T, S)).astype(np.float32)),
        done=jnp.zeros((T, S), bool),
        reset=jnp.zeros((T, S), bool),
        start_state=net.initial_state(S),
        weights=jnp.ones(S, jnp.float32),
        t_idx=jnp.zeros(S, jnp.int32),
        b_idx=jnp.zeros(S, jnp.int32),
    )
    step = jax.jit(train_step, donate_argnums=0)
    return state, step, (sample,)


def bench_config(name: str, iters: int, cfg=None) -> dict:
    from dist_dqn_tpu.config import CONFIGS

    if cfg is None:
        cfg = CONFIGS[name]
    if cfg.network.lstm_size:
        state, step, args = _r2d2_case(cfg)
    else:
        state, step, args = _feedforward_case(cfg)
    # AOT-compile so the timed loop holds no compile.
    compiled = step.lower(state, *args).compile()
    state, _ = compiled(state, *args)  # one cached-dispatch warmup
    jax.device_get(state.steps)    # fence before timing
    t0 = time.perf_counter()
    for _ in range(iters):
        state, metrics = compiled(state, *args)
    jax.device_get(state.steps)    # fence: steps depends on every iteration
    dt = time.perf_counter() - t0
    device = jax.devices()[0]
    from dist_dqn_tpu import loop_common
    train_batch = loop_common.resolve_train_batch(cfg)
    return {
        "config": name,
        "grad_steps_per_sec": round(iters / dt, 2),
        "batch_size": cfg.learner.batch_size,
        "examples_per_sec": round(iters * train_batch / dt, 1),
        "platform": device.platform,
        # Learner-utilization config provenance (ISSUE 6): every row
        # names the knobs that shaped it.
        "replay_ratio": loop_common.resolve_replay_ratio(cfg),
        "train_batch": train_batch,
        "actor_dtype": cfg.network.actor_dtype or "float32",
    }


def r2d2_sweep(iters: int):
    """R2D2 learner-throughput sweep: remat
    on/off x LSTM gate dtype f32/bf16 x scan-unroll 1/8 on the full r2d2
    config. Numerics of every knob are pinned by tests/test_recurrent_knobs
    — this sweep is pure throughput. One JSON line per point; run on the
    real chip to pick the winner (CPU ordering does not transfer)."""
    import dataclasses

    from dist_dqn_tpu.config import CONFIGS

    base = CONFIGS["r2d2"]
    for remat in (True, False):
        for lstm_dtype in ("float32", "bfloat16"):
            for unroll in (1, 8):
                net = dataclasses.replace(
                    base.network, remat_torso=remat, lstm_dtype=lstm_dtype,
                    lstm_unroll=unroll)
                cfg = dataclasses.replace(base, network=net)
                out = bench_config("r2d2", iters, cfg=cfg)
                out.update(remat_torso=remat, lstm_dtype=lstm_dtype,
                           lstm_unroll=unroll)
                print(json.dumps(out), flush=True)


def batch_sweep(iters: int, config_name: str = "apex"):
    """Learner batch-size scaling (next perf lever after the lane sweep):
    the feed-forward heads are latency/bandwidth-bound, not MXU-bound, at
    their config batch sizes — so grad-steps/s should fall sublinearly
    while examples/s climbs as B doubles. Sizes up to
    2048 = 4x the proven B=512 chip run, stepped through 1024 first, so
    each point is <=2x the previously measured size (verify-skill
    incident-#3 rule; run order is smallest-first)."""
    import dataclasses

    from dist_dqn_tpu.config import CONFIGS

    base = CONFIGS[config_name]
    for batch in (256, 512, 1024, 2048):
        cfg = dataclasses.replace(
            base, learner=dataclasses.replace(base.learner,
                                              batch_size=batch))
        out = bench_config(config_name, iters, cfg=cfg)
        out.update(batch_sweep_point=batch)
        print(json.dumps(out), flush=True)


def replay_ratio_sweep(iters: int, ratios=(1, 2, 4, 8),
                       chunk_iters: int = 200, emit=print):
    """Fused-chunk replay-ratio sweep (ISSUE 6): grad-steps/sec of the
    WHOLE fused program — collect + N scanned grad sub-steps per train
    event — at each ratio, plus the donation audit of the chunk carry.

    The standalone-step rows above price one dispatch, but the replay ratio
    only pays off inside the chunk scan where the extra sub-steps share
    the collect. ``scaling_vs_ratio1`` is the acceptance column (the
    ISSUE 6 bar: >= 3x from ratio 1 -> 8 on the fused CPU path). On the
    chip the sweep runs a 1024-lane atari program; on CPU a
    cartpole-MLP shrink of the same structure (the pixel program would
    take minutes per point without measuring anything different about
    the scaling).
    """
    import dataclasses

    from dist_dqn_tpu import loop_common
    from dist_dqn_tpu.config import CONFIGS
    from dist_dqn_tpu.envs import make_jax_env
    from dist_dqn_tpu.models import build_network
    from dist_dqn_tpu.train_loop import make_fused_train
    from dist_dqn_tpu.utils import donation as donation_util

    on_cpu = jax.default_backend() == "cpu"
    if on_cpu:
        # Shape chosen so collect vs train mirrors the CHIP's balance
        # (collect-heavy at ratio 1): 64 lanes of cartpole against a
        # one-layer MLP step at B=16 measures 4.1x scaling at ratio 8
        # on this box — above the >= 3x acceptance bar; a heavier step
        # (B=32, two layers) is learner-bound by ratio 4 and caps at
        # ~2x, which is the chip's problem statement, not a CPU
        # measurement of the engine.
        base = CONFIGS["cartpole"]
        cfg0 = dataclasses.replace(
            base,
            actor=dataclasses.replace(base.actor, num_envs=64),
            network=dataclasses.replace(base.network, torso="mlp",
                                        mlp_features=(32,), hidden=0),
            replay=dataclasses.replace(base.replay, capacity=8192,
                                       min_fill=256),
            learner=dataclasses.replace(base.learner, batch_size=16),
            train_every=4)
    else:
        base = CONFIGS["atari"]
        cfg0 = dataclasses.replace(
            base,
            actor=dataclasses.replace(base.actor, num_envs=1024),
            replay=dataclasses.replace(base.replay, capacity=65_536,
                                       frame_dedup=True, min_fill=4_096),
            learner=dataclasses.replace(base.learner, batch_size=512))

    base_rate = None
    for ratio in ratios:
        cfg = dataclasses.replace(
            cfg0, replay=dataclasses.replace(cfg0.replay,
                                             updates_per_chunk=ratio))
        env = make_jax_env(cfg.env_name)
        net = build_network(cfg.network, env.num_actions)
        init, run_chunk = make_fused_train(cfg, env, net)
        carry = init(jax.random.PRNGKey(0))
        compiled = jax.jit(run_chunk, static_argnums=1,
                           donate_argnums=0).lower(carry,
                                                   chunk_iters).compile()
        # Aliasing audit (ISSUE 6): the scan carry must keep updating
        # in place at every ratio — an unintended copy would show here
        # before it shows as an OOM on the chip.
        audit = donation_util.donation_report(compiled)
        for _ in range(2):  # warmup + fill past min_fill
            carry, metrics = compiled(carry)
            jax.device_get(metrics["loss"])
        t0 = time.perf_counter()
        for _ in range(iters):
            carry, metrics = compiled(carry)
        g = float(jax.device_get(metrics["grad_steps_in_chunk"]))
        dt = time.perf_counter() - t0
        rate = g * iters / dt
        row = {
            "replay_ratio": ratio,
            "grad_steps_per_sec": round(rate, 2),
            "env_steps_per_sec": round(
                iters * chunk_iters * cfg.actor.num_envs / dt, 1),
            "grad_steps_per_chunk": g,
            "train_batch": loop_common.resolve_train_batch(cfg),
            "actor_dtype": cfg.network.actor_dtype or "float32",
            "platform": jax.devices()[0].platform,
            "aliased_pairs": audit.get("aliased_pairs"),
            "alias_bytes": audit.get("alias_bytes"),
        }
        if base_rate is None:
            base_rate = rate
        row["scaling_vs_ratio1"] = round(rate / base_rate, 2)
        emit(json.dumps(row))
    return base_rate


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--configs", nargs="*",
                   default=["atari", "apex", "r2d2", "rainbow", "qrdqn",
                            "iqn", "mdqn"])
    p.add_argument("--iters", type=int, default=50)
    p.add_argument("--platform", default=None)
    p.add_argument("--r2d2-sweep", action="store_true",
                   help="sweep the R2D2 throughput knobs (remat, LSTM "
                        "dtype, scan unroll) instead of --configs")
    p.add_argument("--batch-sweep", action="store_true",
                   help="sweep learner batch size 256..2048 on the apex "
                        "config instead of --configs")
    p.add_argument("--replay-ratio-sweep", action="store_true",
                   help="sweep the fused chunk's on-device replay "
                        "ratio (replay.updates_per_chunk) 1..8 — "
                        "whole-program grad-steps/sec + the chunk-"
                        "carry donation audit (ISSUE 6)")
    p.add_argument("--population-sweep", action="store_true",
                   help="sweep the member-axis width M 1..8 — solo vs "
                        "vmap-stacked population chunk, aggregate + "
                        "per-member grad-steps/sec (ISSUE 20; same "
                        "sweep as benchmarks/population_bench.py)")
    p.add_argument("--chunk-iters", type=int, default=200,
                   help="replay-ratio sweep: fused chunk length")
    args = p.parse_args()
    if args.platform:
        jax.config.update("jax_platforms", args.platform)
    else:
        # No platform named: an accelerator, never a silent CPU run.
        from dist_dqn_tpu.utils.backend import require_accelerator
        require_accelerator()
    if args.r2d2_sweep:
        r2d2_sweep(args.iters)
        return
    if args.batch_sweep:
        batch_sweep(args.iters)
        return
    if args.replay_ratio_sweep:
        replay_ratio_sweep(args.iters, chunk_iters=args.chunk_iters)
        return
    if args.population_sweep:
        from benchmarks.population_bench import population_sweep
        population_sweep(args.iters, chunk_iters=args.chunk_iters)
        return
    for name in args.configs:
        print(json.dumps(bench_config(name, args.iters)), flush=True)


if __name__ == "__main__":
    main()
