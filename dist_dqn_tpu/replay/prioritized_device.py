"""On-device prioritized replay over the time-ring (Ape-X, BASELINE.json:5,9).

The reference keeps a host/GPU sum-tree; a sum-tree's sequential root-to-leaf
descent is hostile to a TPU's vector units, so the TPU-native design samples
by *stratified inverse-CDF*: mask invalid slots, cumsum the priority mass
(one memory-bound pass XLA vectorizes well), and binary-search stratified
uniforms into the CDF. O(N) per sample batch, but N floats of cumsum is
microseconds in HBM at our sizes, it lives entirely on device, and the same
pass yields the total mass needed for importance weights for free.

Priorities are stored raw (|TD|); the alpha exponent is applied at sample
time so alpha can anneal without rewriting the buffer.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from dist_dqn_tpu.replay import device as ring
from dist_dqn_tpu.types import PyTree, Transition

Array = jnp.ndarray


class PrioritizedRingState(NamedTuple):
    ring: ring.TimeRingState
    priorities: Array    # [T * B] float32 cells (replay/device.py), raw
    #   |TD| (+eps), 0 = never written
    max_priority: Array  # scalar float32 running max — seed for new items


class PrioritizedSample(NamedTuple):
    batch: Transition
    weights: Array  # [S] importance-sampling weights, batch-max normalized
    t_idx: Array    # [S] ring slot of each sampled transition
    b_idx: Array    # [S] env lane of each sampled transition


def prioritized_ring_init(num_slots: int, num_envs: int, obs_example: PyTree,
                          store_final_obs: bool = False,
                          merge_obs_rows: bool = False
                          ) -> PrioritizedRingState:
    return PrioritizedRingState(
        ring=ring.time_ring_init(num_slots, num_envs, obs_example,
                                 store_final_obs=store_final_obs,
                                 merge_obs_rows=merge_obs_rows),
        priorities=jnp.zeros((num_slots * num_envs,), jnp.float32),
        max_priority=jnp.float32(1.0),
    )


def prioritized_ring_add(state: PrioritizedRingState, obs: PyTree,
                         action: Array, reward: Array, terminated: Array,
                         truncated: Array, final_obs: PyTree = None,
                         merge_obs_rows: bool = False
                         ) -> PrioritizedRingState:
    """Append a time slice; fresh transitions get the running max priority
    so every new experience is sampled at least once with high probability
    (standard Ape-X seeding)."""
    p = state.ring.pos
    new_ring = ring.time_ring_add(state.ring, obs, action, reward,
                                  terminated, truncated, final_obs=final_obs,
                                  merge_obs_rows=merge_obs_rows)
    num_envs = action.shape[0]
    priorities = jax.lax.dynamic_update_slice(
        state.priorities, jnp.full((num_envs,), state.max_priority),
        (p * num_envs,))
    return PrioritizedRingState(ring=new_ring, priorities=priorities,
                                max_priority=state.max_priority)


def _valid_start_mask(state: ring.TimeRingState, n_step: int,
                      frame_stack: int, num_slots: int,
                      t: Array = None) -> Array:
    """bool over slots ``t`` (default ``[T]``) — valid n-step window starts
    (same region the uniform sampler draws from: the oldest size - n_step
    slots; frame-dedup rings also exclude the oldest frame_stack - 1, whose
    stack-rebuild context is not stored — ring.contextful_start_mask)."""
    return jnp.logical_and(
        ring.contextful_start_mask(state, frame_stack, num_slots, t),
        ring.stored_offset(state, num_slots, t) < (state.size - n_step))


def prioritized_ring_sample(state: PrioritizedRingState, rng: Array,
                            batch_size: int, n_step: int, gamma: float,
                            alpha: float, beta: Array, num_envs: int,
                            use_pallas: bool = False,
                            pallas_interpret: bool = False,
                            merge_obs_rows: bool = False,
                            frame_stack: int = 0,
                            frame_shape=None) -> PrioritizedSample:
    """Stratified sample ~ P(i) = p_i^alpha / sum p^alpha over valid slots.

    ``use_pallas`` routes the cumsum+search through the Pallas TPU kernel
    (ops/pallas_sampler.py, BASELINE.json:5) — same stratified inverse-CDF
    math, VMEM-resident; the XLA path below is the portable fallback.
    """
    from dist_dqn_tpu.ops.pallas_sampler import (importance_weights,
                                                 stratified_sample)

    cells = state.priorities.shape[0]
    num_slots = cells // num_envs
    with jax.named_scope("sample"):
        mask = _valid_start_mask(state.ring, n_step, frame_stack, num_slots)
        n_valid = (jnp.sum(mask.astype(jnp.float32)) * num_envs)
        # The same mask per cell, in the plane's own order: one elementwise
        # pass over the flat cells is all the draw costs outside the kernel.
        slot_of_cell = jnp.arange(cells, dtype=jnp.int32) // num_envs
        w = jnp.where(
            _valid_start_mask(state.ring, n_step, frame_stack, num_slots,
                              slot_of_cell),
            state.priorities ** alpha, 0.0)                      # [T * B]
        t_idx, b_idx, mass_sel, total = stratified_sample(
            w, rng, batch_size, num_envs, use_pallas=use_pallas,
            interpret=pallas_interpret)
        weights = importance_weights(mass_sel, total, n_valid, beta)

    batch = ring.gather_transitions(state.ring, t_idx, b_idx, n_step, gamma,
                                    num_envs, merge_obs_rows=merge_obs_rows,
                                    frame_stack=frame_stack,
                                    frame_shape=frame_shape)
    return PrioritizedSample(batch=batch, weights=weights, t_idx=t_idx,
                             b_idx=b_idx)


def _write_back(state: PrioritizedRingState, t_idx: Array, b_idx: Array,
                new_priorities: Array, eps: float, num_envs: int,
                scatter) -> PrioritizedRingState:
    """|TD| + eps into cells ``t_idx * B + b_idx`` through ``scatter(plane,
    cells, values)``: the one indexing of both write-backs below."""
    with jax.named_scope("writeback"):
        p = jnp.abs(new_priorities.reshape(-1)) + eps
        cells = t_idx.reshape(-1) * num_envs + b_idx.reshape(-1)
        return PrioritizedRingState(
            ring=state.ring, priorities=scatter(state.priorities, cells, p),
            max_priority=jnp.maximum(state.max_priority, jnp.max(p)))


def prioritized_ring_update(state: PrioritizedRingState, t_idx: Array,
                            b_idx: Array, new_priorities: Array,
                            num_envs: int, eps: float = 1e-6
                            ) -> PrioritizedRingState:
    """Write back learner TD magnitudes for the sampled transitions."""
    return _write_back(state, t_idx, b_idx, new_priorities, eps, num_envs,
                       lambda plane, cells, p: plane.at[cells].set(p))


def prioritized_ring_update_batched(state: PrioritizedRingState,
                                    t_idx: Array, b_idx: Array,
                                    new_priorities: Array, num_envs: int,
                                    eps: float = 1e-6
                                    ) -> PrioritizedRingState:
    """One flush for N sub-steps' write-backs (ISSUE 6 replay ratio).

    The replay-ratio scan defers each sub-step's |TD| plane and lands
    them all HERE, once per train event, with chronological
    last-write-wins on slots several sub-steps sampled — the on-device
    twin of the host loops' ``prio_writeback_batch`` semantics (PR 2/
    PR 5: vectorized update, later step wins). Inputs are [N, S] (or
    already flat [M]) in sub-step order; flattening row-major keeps
    chronology, so ``last_write_wins_scatter``'s election is exact.
    """
    return _write_back(state, t_idx, b_idx, new_priorities, eps, num_envs,
                       ring.last_write_wins_scatter)
