"""Fused on-device R2D2 training loop (BASELINE.json:10).

Same Anakin-style shape as the feed-forward loop (train_loop.py): act ->
env.step -> sequence-replay add -> sample -> sequence train step, all one
``lax.scan`` body in a single XLA program. The differences are the threaded
actor LSTM carry (zeroed on episode ends, stored into the ring alongside
each step so learner burn-in starts from the exact acting state) and the
sequence sampler/learner pair (replay/sequence_device.py, agents/r2d2.py).

SPMD-parameterizable like the feed-forward loop: with ``axis_name`` /
``num_shards`` set it is the per-device body for ``shard_map`` over the dp
mesh axis — env lanes and the sequence-replay shard are device-local, the
learner pmean-allreduces gradients over ICI (BASELINE.json:5).
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from dist_dqn_tpu import loop_common
from dist_dqn_tpu.agents.dqn import LearnerState
from dist_dqn_tpu.agents.r2d2 import make_r2d2_learner, \
    make_recurrent_actor_step
from dist_dqn_tpu.config import ExperimentConfig
from dist_dqn_tpu.envs.base import JaxEnv
from dist_dqn_tpu.replay import device as ring
from dist_dqn_tpu.replay import sequence_device as sring
from dist_dqn_tpu.types import PyTree

Array = jnp.ndarray


class R2D2Carry(NamedTuple):
    env_state: PyTree
    obs: PyTree
    actor_carry: Tuple[Array, Array]   # LSTM (c, h), each [B, lstm]
    replay: sring.SequenceRingState
    learner: LearnerState
    rng: Array                         # [1] key array in SPMD mode
    iteration: Array
    ep_return: Array
    completed_return: Array
    completed_count: Array
    loss_sum: Array
    train_count: Array


def make_r2d2_train(cfg: ExperimentConfig, env: JaxEnv, net,
                    axis_name: Optional[str] = None, num_shards: int = 1):
    """Returns (init, run_chunk) — same contract as train_loop.make_fused_train."""
    spmd = axis_name is not None
    rcfg = cfg.replay
    # Honest-unsupported-surface gate (the host_replay lstm_size
    # pattern): the ISSUE 6 replay-ratio scan exists only in the
    # feed-forward loops — a recurrent config setting the knob must
    # fail loudly, not silently train at ratio 1 (the --replay-ratio
    # CLI flag is warned-and-stripped by train.py before it gets here;
    # this catches the --set/config path). replay.train_batch IS
    # honored: it widens the sequence batch through shard_sizes below.
    if rcfg.updates_per_chunk != 1:
        raise ValueError(
            "replay.updates_per_chunk (the replay-ratio scan) is not "
            "supported by the recurrent R2D2 loop yet; leave it at 1 "
            "or use a feed-forward config")
    seq_len = rcfg.burn_in + rcfg.unroll_length + cfg.learner.n_step
    stride = rcfg.sequence_stride or rcfg.unroll_length
    init_learner, train_step = make_r2d2_learner(net, cfg.learner, rcfg,
                                                 axis_name=axis_name)
    act = make_recurrent_actor_step(net)

    B, batch_size = loop_common.shard_sizes(cfg, num_shards)
    min_fill = max(rcfg.min_fill // num_shards, 1)
    num_slots = max(cfg.replay.capacity // (B * num_shards), seq_len + 2)
    if num_slots < seq_len + stride:
        # A seeded start lives num_slots - seq_len + 1 writes and seeds come
        # every `stride` writes; a smaller ring can transiently hold zero
        # valid starts and the sampler would train on garbage windows.
        raise ValueError(
            f"sequence ring too small: num_slots={num_slots} < "
            f"seq_len+stride={seq_len + stride}; raise replay.capacity")

    # Frame-dedup (replay.frame_dedup): the sequence ring stores single
    # frames and the sampler rebuilds [L, B, H, W, stack] stacks — same
    # 4x HBM saving and exactness contract as the feedforward ring. Each
    # frame is gathered once and the stack assembled batch-minor, as the
    # learner's first convolution reads it (replay/sequence_device.py
    # _rebuild_seq_stacks; agents/r2d2.py slices its regions off that).
    _obs_shape = tuple(env.observation_shape)
    stack, _stored_shape, _frame_shape, _slice_newest = \
        loop_common.resolve_frame_dedup(rcfg, env, _obs_shape)
    # Context slots for the oldest start's rebuild, and headroom so a
    # seeded start is never ONLY transiently inside the masked oldest
    # region between two stride seeds (the static side of the can_train
    # guard below).
    num_slots = max(num_slots, seq_len + stride + max(stack - 1, 0))

    # Pixel sequence rings take the same merged-row flat storage as the
    # feedforward ring (loop_common.resolve_flat_storage): obs rows are
    # flattened at insert and reshaped back after the window gather.
    flat_storage = loop_common.resolve_flat_storage(
        rcfg, _stored_shape, env.observation_dtype, num_slots, B,
        prefer_flat=bool(stack))

    _flatten_batched, _unflatten_seq_codec = loop_common.flat_obs_codecs(
        flat_storage, _stored_shape)
    # Dedup sampling returns rebuilt (unflattened) stacks already.
    _unflatten_seq = ((lambda x: x) if stack else _unflatten_seq_codec)

    epsilon, beta_at = loop_common.make_schedules(cfg, B, num_shards)
    _split_rng = loop_common.make_rng_splitter(spmd)
    use_pallas, pallas_interpret = loop_common.pallas_routing(
        rcfg.pallas_sampler)

    def can_train(replay: sring.SequenceRingState, iteration: Array) -> Array:
        filled = replay.ring.size * B >= min_fill
        # The dynamic any() guard backs up the static ring-size check above:
        # never sample when no seeded window start is currently alive —
        # counting only starts the dedup sampler would actually draw
        # (the oldest stack-1 are masked: replay/device.py
        # contextful_start_mask), so a transiently all-masked plane
        # cannot produce zero-weight garbage batches.
        alive = replay.priorities > 0.0
        if stack:
            alive = jnp.logical_and(
                alive,
                ring.contextful_start_mask(replay.ring, stack)[:, None])
        has_starts = jnp.any(alive)
        return jnp.logical_and(
            jnp.logical_and(jnp.logical_and(filled, has_starts),
                            sring.sequence_ring_can_sample(replay, seq_len)),
            iteration % cfg.train_every == 0)

    def init(rng: Array) -> R2D2Carry:
        base = rng
        if spmd:
            rng = jax.random.fold_in(rng, jax.lax.axis_index(axis_name))
        k_env, k_learn, k_run = jax.random.split(rng, 3)
        if spmd:
            k_learn = jax.random.fold_in(base, 7)
        env_state, obs = env.v_reset(k_env, B)
        obs = jax.tree.map(jnp.copy, obs)
        obs_example = jax.tree.map(lambda x: x[0], obs)
        stored_example = jax.tree.map(lambda x: _slice_newest(x)[0], obs)
        ring_example = loop_common.ring_obs_example(stored_example,
                                                    flat_storage)
        replay = sring.sequence_ring_init(num_slots, B, ring_example,
                                          net.lstm_size,
                                          merge_obs_rows=flat_storage)
        learner = init_learner(k_learn, obs_example)
        zero = jnp.float32(0.0)
        return R2D2Carry(
            env_state=env_state, obs=obs,
            actor_carry=net.initial_state(B), replay=replay, learner=learner,
            rng=k_run[None] if spmd else k_run, iteration=jnp.int32(0),
            ep_return=jnp.zeros((B,), jnp.float32),
            completed_return=zero, completed_count=zero,
            loss_sum=zero, train_count=zero)

    def one_iteration(carry: R2D2Carry, _) -> Tuple[R2D2Carry, None]:
        rng, (k_act, k_sample) = _split_rng(carry.rng, 2)
        eps = epsilon(carry.iteration)
        new_actor_carry, actions = act(carry.learner.params,
                                       carry.actor_carry, carry.obs, k_act,
                                       eps)
        env_state, out = env.v_step(carry.env_state, actions)
        # Store the *pre-step* carry: the state the actor held entering obs.
        replay = sring.sequence_ring_add(
            carry.replay,
            _flatten_batched(jax.tree.map(_slice_newest, carry.obs)),
            actions, out.reward,
            out.terminated, out.truncated, carry.actor_carry, seq_len,
            stride, merge_obs_rows=flat_storage)
        # Zero the carry for envs that just finished an episode so the next
        # act (and the state stored with it) starts the new episode fresh.
        done = jnp.logical_or(out.terminated, out.truncated)
        keep = (~done).astype(jnp.float32)[:, None]
        new_actor_carry = (new_actor_carry[0] * keep,
                           new_actor_carry[1] * keep)
        beta = beta_at(carry.iteration)

        def do_train(operand):
            learner, rep = operand

            def one_update(c, key):
                l, rep = c
                s = sring.sequence_ring_sample(
                    rep, key, batch_size, seq_len,
                    rcfg.priority_exponent, beta, use_pallas=use_pallas,
                    pallas_interpret=pallas_interpret,
                    merge_obs_rows=flat_storage,
                    frame_stack=stack, frame_shape=_frame_shape)
                s = s._replace(obs=_unflatten_seq(s.obs))
                l, metrics = train_step(l, s)
                rep = sring.sequence_ring_update(
                    rep, s.t_idx, s.b_idx, metrics["priorities"],
                    eps=rcfg.priority_eps)
                return (l, rep), metrics["loss"]

            keys = jax.random.split(k_sample, cfg.updates_per_train)
            (learner, rep), losses_u = jax.lax.scan(one_update,
                                                    (learner, rep), keys)
            return (learner, rep, jnp.sum(losses_u),
                    jnp.float32(cfg.updates_per_train))

        def no_train(operand):
            learner, rep = operand
            return learner, rep, jnp.float32(0.0), jnp.float32(0.0)

        learner, replay, loss, trained = jax.lax.cond(
            can_train(replay, carry.iteration), do_train, no_train,
            (carry.learner, replay))

        ep_return, completed_return, completed_count = \
            loop_common.episode_stats_update(carry, out.reward, done)

        return R2D2Carry(
            env_state=env_state, obs=out.obs, actor_carry=new_actor_carry,
            replay=replay, learner=learner, rng=rng,
            iteration=carry.iteration + 1, ep_return=ep_return,
            completed_return=completed_return,
            completed_count=completed_count,
            loss_sum=carry.loss_sum + loss,
            train_count=carry.train_count + trained), None

    def run_chunk(carry: R2D2Carry, num_iters: int):
        zero = jnp.float32(0.0)
        carry = carry._replace(completed_return=zero, completed_count=zero,
                               loss_sum=zero, train_count=zero)
        carry, _ = jax.lax.scan(one_iteration, carry, None, length=num_iters)
        metrics, replace = loop_common.reduce_chunk_metrics(
            carry, axis_name, B, num_shards)
        if spmd:
            # Keep the new-window priority seed replicated (global max).
            replace["replay"] = carry.replay._replace(
                max_priority=jax.lax.pmax(carry.replay.max_priority,
                                          axis_name))
        if replace:
            carry = carry._replace(**replace)
        return carry, metrics

    return init, run_chunk


def make_r2d2_evaluator(cfg: ExperimentConfig, env: JaxEnv, net,
                        num_episodes: int = 10, epsilon: float = 0.001):
    """Greedy eval with the LSTM carry threaded (and zeroed on done)."""
    act = make_recurrent_actor_step(net)

    def evaluate(params: PyTree, rng: Array) -> Array:
        k_reset, k_run = jax.random.split(rng)
        env_state, obs = env.v_reset(k_reset, num_episodes)
        carry0 = net.initial_state(num_episodes)

        def step(c, _):
            env_state, obs, carry, ret, alive, rng = c
            rng, k = jax.random.split(rng)
            carry, a = act(params, carry, obs, k, jnp.float32(epsilon))
            env_state, out = env.v_step(env_state, a)
            ret = ret + out.reward * alive
            done = jnp.logical_or(out.terminated, out.truncated)
            keep = (~done).astype(jnp.float32)[:, None]
            carry = (carry[0] * keep, carry[1] * keep)
            alive = jnp.logical_and(alive > 0, ~done).astype(jnp.float32)
            return (env_state, out.obs, carry, ret, alive, rng), None

        init = (env_state, obs, carry0,
                jnp.zeros((num_episodes,), jnp.float32),
                jnp.ones((num_episodes,), jnp.float32), k_run)
        carry, _ = jax.lax.scan(step, init, None, length=env.max_steps)
        return jnp.mean(carry[3])

    return evaluate
