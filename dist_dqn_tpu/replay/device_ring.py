"""The device ring a fused chunk program (train_loop.py) was configured
with — uniform, prioritized or sequence — behind one set of closures.

The ring's geometry is computed HERE and nowhere else: slots from
``replay.capacity``, the window (``n_step`` or burn-in + unroll + n-step
and its stride), the frame-dedup context, ``store_final_obs`` and the
merged-row ("flat") layout. Which ring: one that stores an actor state
with every step (``actor_state`` has leaves) is the sequence ring;
otherwise ``replay.prioritized`` chooses. What a sample is stays between
the ring and the agent's ``train_step`` (agents/agent.py): a
``PrioritizedSample`` (``weights=None`` from the uniform ring) or a
``SequenceSample``; the loop passes it through.
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from dist_dqn_tpu import loop_common
from dist_dqn_tpu.config import ExperimentConfig
from dist_dqn_tpu.replay import device as ring
from dist_dqn_tpu.replay import prioritized_device as pring
from dist_dqn_tpu.replay import sequence_device as sring


class DeviceRing(NamedTuple):
    init: Callable        # obs [B, ...] -> state
    # (state, obs, actions, StepOut, the actor state held entering obs)
    add: Callable
    can_sample: Callable  # state -> bool: past min_fill, a whole window held
    sample: Callable      # (state, key, gamma, beta) -> sample
    update: Callable      # (state, sample, priorities) -> state
    # (state, t_idx [N, S], b_idx, priorities) -> state: one last-wins flush
    # of N sub-steps' write-backs; None where the ring has none to defer.
    update_batched: Optional[Callable]
    specs: Callable       # mesh axis -> PartitionSpecs of the state
    prioritized: bool     # carries max_priority (pmax-ed over a mesh)
    sequence: bool


def make_device_ring(cfg: ExperimentConfig, env, num_shards: int = 1,
                     actor_state=()) -> DeviceRing:
    """``actor_state``: the per-lane state (or its shapes) the agent threads
    through acting; all sizes are per-shard sizes."""
    rcfg = cfg.replay
    n_step = cfg.learner.n_step
    sequence = bool(jax.tree.leaves(actor_state))
    prioritized = sequence or rcfg.prioritized
    B, batch_size = loop_common.shard_sizes(cfg, num_shards)
    min_fill = max(rcfg.min_fill // num_shards, 1)
    # Exact truncation bootstrap for cheap (non-pixel) observations; pixel
    # rings skip final_obs to halve HBM use (truncation treated as terminal).
    # cfg.replay.store_final_obs overrides the heuristic either way. The
    # sequence ring stores none.
    store_final = not sequence and (
        env.observation_dtype != jnp.uint8 if rcfg.store_final_obs is None
        else rcfg.store_final_obs)
    # Frame-dedup (replay.frame_dedup): store each step's NEWEST frame
    # only and rebuild stacks at sample time — a 4x HBM saving that
    # lifts the v5e pixel window cap from ~200k to ~1M transitions.
    # Exactness relies on the env's declared rolling-stack contract.
    stack, stored_shape, frame_shape, slice_newest = \
        loop_common.resolve_frame_dedup(rcfg, env,
                                        tuple(env.observation_shape),
                                        store_final=store_final)
    context = max(stack - 1, 0)
    if sequence:
        lstm_size = jax.tree.leaves(actor_state)[0].shape[-1]
        seq_len = rcfg.burn_in + rcfg.unroll_length + n_step
        stride = rcfg.sequence_stride or rcfg.unroll_length
        num_slots = max(rcfg.capacity // (B * num_shards), seq_len + 2)
        if num_slots < seq_len + stride:
            # A seeded start lives num_slots - seq_len + 1 writes and seeds
            # come every `stride` writes; a smaller ring can transiently
            # hold zero valid starts and the sampler would train on garbage
            # windows.
            raise ValueError(
                f"sequence ring too small: num_slots={num_slots} < "
                f"seq_len+stride={seq_len + stride}; raise replay.capacity")
        # Context slots for the oldest start's rebuild, and headroom so a
        # seeded start is never ONLY transiently inside the masked oldest
        # region between two stride seeds (the static side of can_sample).
        num_slots = max(num_slots, seq_len + stride + context)
    else:
        # Dedup rebuild needs frame_stack-1 context slots beyond the n-step
        # window; a ring under that floor would be permanently unsampleable.
        num_slots = max(rcfg.capacity // (B * num_shards), n_step + 2,
                        n_step + context + 2)
    # Multi-dim obs can be STORED FLAT in the ring — [slots*B, 28224]
    # for 84x84x4, via replay/device.py merge_obs_rows — with reshapes
    # at the insert/sample boundary (rationale + measured padding
    # factors: loop_common.resolve_flat_storage).
    flat = loop_common.resolve_flat_storage(
        rcfg, stored_shape, env.observation_dtype, num_slots, B,
        store_final=store_final, prefer_flat=bool(stack))
    flatten, unflatten = loop_common.flat_obs_codecs(flat, stored_shape)
    # Dedup gathers return UNFLATTENED rebuilt stacks (gather owns the
    # reshape via frame_shape); without dedup the flat codec decodes.
    decode = (lambda x: x) if stack else unflatten
    use_pallas, pallas_interpret = loop_common.pallas_routing(
        prioritized and rcfg.pallas_sampler)
    layout = dict(merge_obs_rows=flat, frame_stack=stack,
                  frame_shape=frame_shape)

    def init(obs):
        # The ring stores single frames under dedup.
        example = loop_common.ring_obs_example(
            jax.tree.map(lambda x: slice_newest(x)[0], obs), flat)
        if sequence:
            return sring.sequence_ring_init(num_slots, B, example, lstm_size,
                                            merge_obs_rows=flat)
        make = (pring.prioritized_ring_init if prioritized
                else ring.time_ring_init)
        return make(num_slots, B, example, store_final_obs=store_final,
                    merge_obs_rows=flat)

    def add(state, obs, actions, out, held):
        stored = flatten(jax.tree.map(slice_newest, obs))
        if sequence:
            # The *pre-step* state: what the actor held entering obs.
            return sring.sequence_ring_add(
                state, stored, actions, out.reward, out.terminated,
                out.truncated, held, seq_len, stride,
                merge_obs_rows=flat)
        add_ = pring.prioritized_ring_add if prioritized else \
            ring.time_ring_add
        return add_(state, stored, actions, out.reward, out.terminated,
                    out.truncated,
                    final_obs=flatten(out.next_obs) if store_final else None,
                    merge_obs_rows=flat)

    def can_sample(state):
        r = state.ring if prioritized else state
        filled = r.size * B >= min_fill
        if not sequence:
            return jnp.logical_and(
                filled, ring.time_ring_can_sample(r, n_step,
                                                  frame_stack=stack))
        # The dynamic any() guard backs up the static ring-size check above:
        # never sample when no seeded window start is currently alive —
        # counting only starts the dedup sampler would actually draw
        # (the oldest stack-1 are masked: replay/device.py
        # contextful_start_mask), so a transiently all-masked plane
        # cannot produce zero-weight garbage batches.
        alive = state.priorities > 0.0
        if stack:
            alive = jnp.logical_and(
                alive, ring.contextful_start_mask(r, stack)[:, None])
        return jnp.logical_and(
            jnp.logical_and(filled, jnp.any(alive)),
            sring.sequence_ring_can_sample(state, seq_len))

    def sample(state, key, gamma, beta):
        if sequence:
            s = sring.sequence_ring_sample(
                state, key, batch_size, seq_len, rcfg.priority_exponent,
                beta, use_pallas=use_pallas,
                pallas_interpret=pallas_interpret, **layout)
            with jax.named_scope("gather"):
                return s._replace(obs=decode(s.obs))
        if prioritized:
            s = pring.prioritized_ring_sample(
                state, key, batch_size, n_step, gamma,
                rcfg.priority_exponent, beta, use_pallas=use_pallas,
                pallas_interpret=pallas_interpret, **layout)
        else:
            s = pring.PrioritizedSample(
                ring.time_ring_sample(state, key, batch_size, n_step, gamma,
                                      **layout), None, None, None)
        with jax.named_scope("gather"):
            return s._replace(batch=s.batch._replace(
                obs=decode(s.batch.obs), next_obs=decode(s.batch.next_obs)))

    def update(state, s, priorities):
        if not prioritized:
            return state
        write = (sring.sequence_ring_update if sequence
                 else pring.prioritized_ring_update)
        return write(state, s.t_idx, s.b_idx, priorities,
                     eps=rcfg.priority_eps)

    def update_batched(state, t_idx, b_idx, priorities):
        return pring.prioritized_ring_update_batched(
            state, t_idx, b_idx, priorities, eps=rcfg.priority_eps)

    def specs(axis: str):
        """Leaves are [slots, lanes, ...] (or merged rows of both): the lane
        axis is sharded, cursors and the priority seed replicated."""
        lanes, repl = P(None, axis), P()
        r = ring.TimeRingState(
            obs=lanes, action=lanes, reward=lanes, terminated=lanes,
            truncated=lanes, final_obs=lanes, pos=repl, size=repl)
        if sequence:
            return sring.SequenceRingState(
                ring=r, state_c=lanes, state_h=lanes, priorities=lanes,
                max_priority=repl, writes=repl)
        if prioritized:
            return pring.PrioritizedRingState(ring=r, priorities=lanes,
                                              max_priority=repl)
        return r

    return DeviceRing(
        init, add, can_sample, sample, update,
        update_batched if prioritized and not sequence else None, specs,
        prioritized, sequence)
