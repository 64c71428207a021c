"""Scaffolding of the fused chunk program (train_loop.py) and of the device
ring it runs over (replay/device_ring.py): the schedules, the per-device rng
handling, the chunk-metric reduction, and the rules that size and lay out a
ring (batch bucket, frame dedup, flat storage, sampler routing). The
host-replay runtime and the benchmark's reference checks call the same
rules, so each is written once, here.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax

from dist_dqn_tpu.config import ExperimentConfig

Array = jnp.ndarray


def resolve_train_batch(cfg: ExperimentConfig) -> int:
    """Effective train-event batch width (ISSUE 6).

    ``replay.train_batch == 0`` keeps ``learner.batch_size`` EXACTLY
    (the bit-identity contract for existing configs); > 0 widens the
    train batch to that many rows — sequences, for a recurrent agent —
    rounded up to the next power of two by the SAME ``pad_pow2`` the
    ingest act bucketing uses (replay/host.py), so the two bucket
    policies cannot drift apart. Every runtime's learner resolves
    through here.
    """
    from dist_dqn_tpu.replay.host import pad_pow2

    if cfg.replay.train_batch <= 0:
        return cfg.learner.batch_size
    return pad_pow2(cfg.replay.train_batch)


def resolve_replay_ratio(cfg: ExperimentConfig) -> int:
    """Validated on-device replay ratio (``replay.updates_per_chunk``):
    grad sub-steps per train event, >= 1."""
    r = cfg.replay.updates_per_chunk
    if r < 1:
        raise ValueError(
            f"replay.updates_per_chunk must be >= 1, got {r}")
    return r


def make_actor_param_cast(actor_dtype: str):
    """(cast_fn, active) for the actor/learner dtype split (ISSUE 6).

    ``actor_dtype="float32"`` (default) returns an identity and
    ``active=False`` — acting reads the live learner params, exactly
    the pre-split program. "bfloat16" returns a tree-cast of float
    leaves (params only; integer leaves untouched) the loops apply ONCE
    per chunk, keeping the learner's fp32 masters untouched.
    """
    if actor_dtype in ("", "float32"):
        return (lambda params: params), False
    if actor_dtype != "bfloat16":
        raise ValueError(
            f"network.actor_dtype must be 'float32' or 'bfloat16', got "
            f"{actor_dtype!r}")
    dt = jnp.bfloat16

    def cast(params):
        return jax.tree.map(
            lambda x: x.astype(dt)
            if jnp.issubdtype(x.dtype, jnp.floating) else x, params)

    return cast, True


def shard_sizes(cfg: ExperimentConfig, num_shards: int) -> Tuple[int, int]:
    """Validate divisibility and return per-shard (num_envs,
    train_batch) — the batch side resolved through the ISSUE 6 bucket
    rule (``resolve_train_batch``; identical to learner.batch_size
    unless replay.train_batch widens it)."""
    train_batch = resolve_train_batch(cfg)
    for name, total in (("num_envs", cfg.actor.num_envs),
                        ("train_batch", train_batch)):
        if total % num_shards:
            raise ValueError(f"{name}={total} not divisible by "
                             f"num_shards={num_shards}")
    return (cfg.actor.num_envs // num_shards,
            train_batch // num_shards)


FLAT_AUTO_BYTES = 2 << 30


def resolve_flat_storage(rcfg, obs_shape, obs_dtype, num_slots: int, B: int,
                         store_final: bool = False,
                         prefer_flat: bool = False) -> bool:
    """Decide merged-row ("flat") obs storage for a device ring.

    XLA lays out multi-dim u8 ring buffers with (8,128) tiling on
    whichever dims it puts minormost, padding 84x84 to ~1.6x its logical
    bytes — and a [slots, B, flat] 3-D form to 2.0x (lanes transposed
    minormost and padded 64->128). A 2-D merged-row buffer pads 0.2%
    (28,224-B rows) to 1.6% (7,056-B rows) row-major, and less in the
    order the device picks when nothing is stated: slots minormost for
    all three rings of the benchmark, which costs a copy of the whole
    ring into and out of every chunk program (45 ms of a 0.72 s `atari`
    chunk, and a second ring in HBM). Where row-major pads at most 1%
    more, replay/device_ring.py ``merged_row_boundary`` stores a row at
    a whole number of 128-lane tiles, which makes row-major the order
    that pads least (PERF.md §5, §7.1).
    Auto rule (``replay.flat_storage=None``): flat only when the ring's
    logical bytes exceed FLAT_AUTO_BYTES, where memory dominates (a
    four-way mesh shard of the `atari` preset, 1.4 GB, falls under it
    and is stored tiled). Measured on the chip (PERF.md §5, §7.6): a row
    gather from the merged-row ring runs at 82-83 GB/s, a u8 row sharing
    every 32-bit word with three neighbours; the tiled form's gather is
    not measured.
    """
    if rcfg.flat_storage is None:
        if prefer_flat and len(obs_shape) >= 2:
            # Frame-dedup rings store [.., H, W, 1] slices whose TILED
            # layout pads the size-1 minor dim to a whole 128-lane tile.
            # Flat is the dedup default at any size.
            return True
        obs_bytes = num_slots * B * int(jnp.dtype(obs_dtype).itemsize)
        for d in obs_shape:
            obs_bytes *= d
        return (len(obs_shape) >= 2
                and obs_bytes * (2 if store_final else 1) > FLAT_AUTO_BYTES)
    return bool(rcfg.flat_storage) and len(obs_shape) >= 2


def flat_obs_codecs(flat_storage: bool, obs_shape):
    """Reshape helpers for merged-row ("flat") ring storage.

    ``flatten_batched``: [B, *obs_shape] leaves -> [B, prod] at the
    insert boundary (identity when tiled). ``unflatten_rows``:
    [..., width] leaves -> [..., *obs_shape] after a gather —
    rank-agnostic, so the feed-forward [N, width] batch and the R2D2
    [L, S, width] sequence gather share it; a ring may store a row wider
    than ``prod`` (replay/device_ring.py ``merged_row_boundary``: the tail
    is never written), so it takes the row's head. Every ring uses these
    (not local reshapes) so the layout boundary cannot diverge.
    """
    from dist_dqn_tpu.replay.device import row_head

    obs_shape = tuple(obs_shape)

    def flatten_batched(tree):
        if not flat_storage:
            return tree
        return jax.tree.map(
            lambda x: x.reshape(x.shape[0], -1) if x.ndim >= 3 else x,
            tree)

    def unflatten_rows(tree):
        if not flat_storage:
            return tree
        return jax.tree.map(
            lambda x: row_head(x, obs_shape), tree)

    return flatten_batched, unflatten_rows


def ring_obs_example(obs_example, flat_storage: bool, row_width: int = None):
    """Per-env obs example as the ring will store it (flattened rows,
    ``row_width`` wide where the ring stores them wider, when flat). The
    unflatten codec reshapes every leaf to the env's single
    observation_shape; a multi-leaf obs tree would need per-leaf
    bookkeeping it doesn't do — no current env emits one, so fail
    loudly rather than mis-shape a future one."""
    if not flat_storage:
        return obs_example
    if len(jax.tree.leaves(obs_example)) != 1:
        raise ValueError(
            "replay.flat_storage supports single-array observations "
            f"only; this env's obs is a {type(obs_example).__name__} "
            "tree — set replay.flat_storage=False")

    def row(x):
        if x.ndim < 2:
            return x
        x = x.reshape(-1)
        return x if row_width in (None, x.size) else jnp.pad(
            x, (0, row_width - x.size))

    return jax.tree.map(row, obs_example)


def resolve_frame_dedup(rcfg, env, obs_shape,
                        store_final: bool = False):
    """Validate + resolve ``replay.frame_dedup`` for a device ring.

    Returns (stack, stored_shape, frame_shape, slice_newest): the
    declared rolling-stack depth (0 = dedup off), the per-step shape as
    STORED in the ring (single frame under dedup), the static frame
    shape the merge-rows gather reshapes to (None when off), and the
    insert-side obs slicer."""
    obs_shape = tuple(obs_shape)
    stack = rcfg.frame_dedup and getattr(env, "frame_stack", 0) or 0
    if rcfg.frame_dedup:
        if stack < 2:
            raise ValueError(
                "replay.frame_dedup=True but this env does not declare a "
                "rolling frame stack (JaxEnv.frame_stack is "
                f"{getattr(env, 'frame_stack', 0)}); dedup storage "
                "cannot rebuild its observations")
        if stack != obs_shape[-1]:
            raise ValueError(
                f"env.frame_stack={stack} does not match the obs last "
                f"axis {obs_shape[-1]}")
        if store_final:
            raise ValueError(
                "replay.frame_dedup needs store_final_obs off (the "
                "final-obs buffer is not a rolling frame stream)")
    stored_shape = obs_shape[:-1] + (1,) if stack else obs_shape
    frame_shape = stored_shape if stack else None
    slice_newest = ((lambda o: o[..., -1:]) if stack else (lambda o: o))
    return stack, stored_shape, frame_shape, slice_newest


def make_schedules(cfg: ExperimentConfig, B: int, num_shards: int
                   ) -> Tuple[Callable, Callable]:
    """(epsilon(iteration), beta(iteration)): exploration decay and the PER
    importance exponent annealing beta0 -> 1 over the configured run, both
    in per-shard iteration units."""
    epsilon = optax.linear_schedule(
        cfg.actor.epsilon_start, cfg.actor.epsilon_end,
        max(cfg.actor.epsilon_decay_steps // (B * num_shards), 1))
    total_iters = max(cfg.total_env_steps // (B * num_shards), 1)
    beta0 = cfg.replay.importance_exponent

    def beta_at(iteration: Array) -> Array:
        frac = jnp.minimum(iteration.astype(jnp.float32) / total_iters, 1.0)
        return beta0 + (1.0 - beta0) * frac

    return epsilon, beta_at


def make_member_epsilon(cfg: ExperimentConfig, B: int, num_shards: int
                        ) -> Callable:
    """Per-member exploration decay for the population plane (ISSUE 20):
    ``eps_at(iteration, delta, end)`` with TRACED ``delta`` / ``end``
    scalars (member k's ``epsilon_start - epsilon_end`` and
    ``epsilon_end`` under ``jax.vmap``).

    Op-for-op the body of ``make_schedules``'s
    ``optax.linear_schedule`` (polynomial power=1): same int32 clip,
    same ``1 - count/steps`` promotion, same multiply-add — with the
    constants arriving as [M]-array lanes instead of trace-time
    literals, so member k's epsilon is bit-identical to a solo run
    configured with member k's ``epsilon_end`` (the member-independence
    pin). ``delta`` must be folded on the HOST in float64 then cast to
    f32, exactly as the schedule's Python-literal subtraction is
    (population.member_hp does this).
    """
    steps = max(cfg.actor.epsilon_decay_steps // (B * num_shards), 1)

    def eps_at(iteration: Array, delta: Array, end: Array) -> Array:
        count = jnp.clip(iteration, 0, steps)
        frac = 1 - count / steps
        return delta * frac + end

    return eps_at


def pallas_routing(enabled: bool) -> Tuple[bool, bool]:
    """(use_pallas, pallas_interpret) for the priority-sampling kernel.

    Pallas kernels compile only on real TPU backends; anywhere else the
    config flag falls back to the equivalent XLA sampler — the Python-level
    interpreter inside a scanned hot loop would look like a hang at real
    buffer sizes. DIST_DQN_PALLAS_INTERPRET=1 opts back in for tiny-size
    integration tests of the kernel routing.
    """
    import os

    import jax

    on_tpu = jax.default_backend() == "tpu"
    interpret = (not on_tpu
                 and os.environ.get("DIST_DQN_PALLAS_INTERPRET") == "1")
    return enabled and (on_tpu or interpret), interpret


def make_rng_splitter(spmd: bool) -> Callable:
    """split(carry_rng, n) -> (new_carry_rng, [n] keys); in SPMD mode the
    carry rng is a [1] key array (per-device stream) and stays that shape."""

    def split(carry_rng: Array, n: int):
        base = carry_rng[0] if spmd else carry_rng
        keys = jax.random.split(base, n + 1)
        new = keys[:1] if spmd else keys[0]
        return new, keys[1:]

    return split


def reduce_chunk_metrics(carry, axis_name: Optional[str], B: int,
                         num_shards: int) -> Tuple[Dict, Dict]:
    """Reduce the chunk accumulators of the carry into the global
    metrics dict; returns (metrics, zeroed accumulator replacements).

    In SPMD mode episode stats are psum-ed (global counts), loss/train
    counters pmean-ed (identical across devices anyway), and the returned
    replacements keep every accumulator leaf replicated for the next chunk.
    """
    completed_return = carry.completed_return
    completed_count = carry.completed_count
    loss_sum = carry.loss_sum
    train_count = carry.train_count
    # the agent's further per-grad-step metrics (TrainCarry.agent_sums),
    # where the carry has any
    agent_sums = dict(getattr(carry, "agent_sums", {}))
    zero = jnp.float32(0.0)
    replace = {}
    if axis_name is not None:
        completed_return = jax.lax.psum(completed_return, axis_name)
        completed_count = jax.lax.psum(completed_count, axis_name)
        loss_sum = jax.lax.pmean(loss_sum, axis_name)
        train_count = jax.lax.pmean(train_count, axis_name)
        replace = dict(completed_return=zero, completed_count=zero,
                       loss_sum=zero, train_count=zero)
        if agent_sums:
            agent_sums = jax.lax.pmean(agent_sums, axis_name)
            replace["agent_sums"] = {k: zero for k in agent_sums}
    metrics = {
        "env_frames": carry.iteration * B * num_shards,
        "episode_return":
            completed_return / jnp.maximum(completed_count, 1.0),
        "episodes": completed_count,
        "loss": loss_sum / jnp.maximum(train_count, 1.0),
        "grad_steps_in_chunk": train_count,
        **{k: v / jnp.maximum(train_count, 1.0)
           for k, v in agent_sums.items()},
    }
    return metrics, replace


def episode_stats_update(carry, reward: Array, done: Array):
    """Fold one step's rewards/dones into the per-env episode trackers.

    Returns (ep_return, completed_return, completed_count) updates.
    """
    ep_return = carry.ep_return + reward
    completed_return = carry.completed_return + jnp.sum(
        jnp.where(done, ep_return, 0.0))
    completed_count = carry.completed_count + jnp.sum(
        done.astype(jnp.float32))
    ep_return = jnp.where(done, 0.0, ep_return)
    return ep_return, completed_return, completed_count
