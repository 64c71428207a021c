"""On-device R2D2 sequence replay over the time-ring (BASELINE.json:10).

The reference's sequence replay stores fixed-length (burn-in + unroll)
trajectory slices with the recurrent state at the slice start. The TPU-native
layout reuses the time-ring (replay/device.py): every step is stored exactly
once, as one slice of all B lanes, together with what the network keeps of
the actor's state *entering* that step (an opaque pytree: the LSTM's pair,
nothing for a core whose learner windows start from an empty state), and a
"sequence" is just a length-L window gather at sample time —
overlapping sequences (stride < L) therefore cost zero extra HBM, where the
reference's per-sequence storage pays length/stride x duplication.

Window starts are seeded into the priority plane only every
``sequence_stride`` writes (classic R2D2 overlap control): a slot's row gets
the running max priority the moment its full window lands in the ring, and
is cleared when the ring overwrites it — so ``priorities > 0`` is exactly
the valid-start set, and the same stratified inverse-CDF sampler as the
transition path (replay/prioritized_device.py) draws from it.

Priorities are per-sequence (eta-mix of max/mean |TD| is computed by the
learner, agents/r2d2.py); stored raw with alpha applied at sample time.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from dist_dqn_tpu.replay import device as ring
from dist_dqn_tpu.types import PyTree, SequenceSample

Array = jnp.ndarray


class SequenceRingState(NamedTuple):
    ring: ring.TimeRingState
    # what the network stores of the state entering each step: one
    # [T, B, ...] float32 plane a leaf; () where it stores nothing
    start_state: PyTree
    priorities: Array    # [T, B] float32; >0 exactly at valid window starts
    #   (the one plane still [T, B]: the benchmark's reference check reads
    #   it so — perf/reference/r2d2_float32.py, PERF.md §7)
    max_priority: Array  # scalar float32 — seed for fresh windows
    writes: Array        # scalar int32 — total time slices ever written


def sequence_ring_init(num_slots: int, num_envs: int, obs_example: PyTree,
                       stored_state,
                       merge_obs_rows: bool = False) -> SequenceRingState:
    """``stored_state``: one lane's stored state (a pytree whose leaves, or
    their shapes, are ``[...]`` a lane), or an int: an LSTM pair of that
    width. ``merge_obs_rows`` stores obs as flat ``[T*B, ...]`` rows (same
    records, same order — see replay/device.py:time_ring_init); callers
    pass the same flag to add/sample. The inner ring's scalar planes are
    flat cells like every ring's; the state planes are ``[T, B, ...]``,
    lane-dense as they are; the ring's slots and lanes are the priority
    plane's."""
    if isinstance(stored_state, int):
        pair = jax.ShapeDtypeStruct((stored_state,), jnp.float32)
        stored_state = (pair, pair)
    return SequenceRingState(
        ring=ring.time_ring_init(num_slots, num_envs, obs_example,
                                 store_final_obs=False,
                                 merge_obs_rows=merge_obs_rows),
        start_state=jax.tree.map(
            lambda x: jnp.zeros((num_slots, num_envs) + tuple(x.shape),
                                jnp.float32), stored_state),
        priorities=jnp.zeros((num_slots, num_envs), jnp.float32),
        max_priority=jnp.float32(1.0),
        writes=jnp.int32(0),
    )


def sequence_ring_add(state: SequenceRingState, obs: PyTree, action: Array,
                      reward: Array, terminated: Array, truncated: Array,
                      carry: PyTree, seq_len: int,
                      stride: int,
                      merge_obs_rows: bool = False) -> SequenceRingState:
    """Append one time slice plus what is stored of the actor state that
    produced ``action`` (``carry``: leaves ``[B, ...]``).

    ``seq_len`` (L) and ``stride`` are static. Overwriting slot ``p``
    invalidates the window starting at ``p`` (it is the oldest slot of any
    window containing it), so its priority row is cleared; the newest slot
    whose full window just completed — write index ``writes + 1 - L`` — is
    seeded with the running max priority when stride-aligned.
    """
    num_slots = state.priorities.shape[0]
    p = state.ring.pos
    new_ring = ring.time_ring_add(state.ring, obs, action, reward,
                                  terminated, truncated,
                                  merge_obs_rows=merge_obs_rows)
    writes = state.writes + 1

    priorities = state.priorities.at[p].set(0.0)
    start_write = writes - seq_len                 # write index of new start
    s = (p - (seq_len - 1)) % num_slots
    seed = jnp.logical_and(start_write >= 0, (start_write % stride) == 0)
    row = jnp.where(seed, state.max_priority, priorities[s])
    priorities = priorities.at[s].set(row)

    return SequenceRingState(
        ring=new_ring,
        start_state=jax.tree.map(
            lambda plane, x: plane.at[p].set(x.astype(jnp.float32)),
            state.start_state, carry),
        priorities=priorities,
        max_priority=state.max_priority,
        writes=writes,
    )


def sequence_ring_can_sample(state: SequenceRingState, seq_len: int) -> Array:
    """True once the first full window has been seeded."""
    return state.writes >= seq_len


def _window_slots(t_idx: Array, L: int, num_slots: int) -> Array:
    """[L, S] ring slots of the length-``L`` windows from ``t_idx`` [S]
    (time-major)."""
    offs = jnp.arange(L, dtype=jnp.int32)
    return (t_idx[None, :] + offs[:, None]) % num_slots


def _rebuild_seq_stacks(r: ring.TimeRingState, t_idx: Array, b_idx: Array,
                        seq_len: int, frame_stack: int,
                        merge_obs_rows: bool, frame_shape,
                        num_slots: int, num_envs: int) -> PyTree:
    """[L, B, ..., frame_stack] stacks for every window position, from a
    dedup ring (single stored ``[..., 1]`` frames — replay/device.py
    semantics).

    Every frame is read from the ring ONCE: one extended gather of
    ``E = seq_len + frame_stack - 1`` frames a window (offsets
    -(S-1)..L-1) as flat time-major ``[E*B, H*W]`` rows. Position ``i``'s
    channel with lookback ``d`` is ext frame ``i + (S-1) - min(d, age_i)``
    — the ``min(d, age)`` clamp of ``device.stack_rebuild_indices`` (reset
    re-tiling). Unclamped that is a ROW SLICE, ``L*B`` rows from
    ``(S-1-d)*B``; and since ``min(d, age)`` is ``d`` where ``age >= d``
    and ``min(d-1, age)`` elsewhere, each channel is the previous one with
    its own slice selected in where ``age >= d``: S-1 selects, no second
    gather.

    Four uint8 channels of a pixel are then packed into one uint32 word,
    the words transposed to ``[H*W, L*B]`` and split back into bytes:
    byte for byte that IS the batch-minor ``[L*B, H, W, S]`` array the
    first convolution reads on TPU (its tile holds the S channels of 128
    frames, four bytes a word), so the stack costs three 32-bit passes at
    the HBM's speed and the learner slices its burn-in and unroll regions
    off the flat batch (agents/r2d2.py) — where per-lookback
    ``take_along_axis`` passes over ``[E, B, H, W, 1]`` and a concatenate
    on the size-1 minor axis cost four frame gathers, four byte-wise frame
    copies, two casts and two relayouts a network pass (PERF.md, PR 31).
    Any other depth or dtype stacks the channels as they are: exact, and
    as slow as a byte-wise relayout is. Callers mask out window starts
    whose context predates the ring (sequence_ring_sample).
    """
    S = frame_stack
    L = seq_len
    batch = t_idx.shape[0]
    ext_offs = jnp.arange(-(S - 1), L, dtype=jnp.int32)        # [E]
    tt = (t_idx[None, :] + ext_offs[:, None]) % num_slots      # [E, B]
    cells = tt * num_envs + b_idx[None, :]
    done_ext = jnp.logical_or(r.terminated, r.truncated)[cells]  # [E, B]
    # age[i] = distance-1 to the nearest done among positions i-1..i-(S-1)
    # (window position i lives at ext index i + S - 1).
    age = jnp.full((L, batch), S - 1, jnp.int32)
    for j in range(S - 1, 0, -1):   # descending: the nearest done wins
        # done at position i-j = ext index i + S - 1 - j.
        age = jnp.where(done_ext[S - 1 - j:S - 1 - j + L], j - 1, age)
    age = age.reshape(L * batch, 1)

    def rebuild(x):
        if merge_obs_rows:
            shape = tuple(frame_shape)
            rows = ring.row_head(x[cells.reshape(-1)],
                                 (math.prod(shape),))
        else:
            shape = x.shape[2:]
            rows = x[tt, b_idx[None, :]].reshape(tt.size, -1)
        chans = []                                             # newest first
        for d in range(S):
            lo = (S - 1 - d) * batch
            shift = rows[lo:lo + L * batch]
            chans.append(shift if d == 0
                         else jnp.where(age >= d, shift, chans[-1]))
        chans.reverse()                                        # oldest first
        if S == 4 and rows.dtype == jnp.uint8 and shape[-1] == 1:
            word = functools.reduce(jnp.bitwise_or, (
                c.astype(jnp.uint32) << (8 * k)    # channel k = byte k
                for k, c in enumerate(chans)))                 # [L*B, H*W]
            stack = jax.lax.bitcast_convert_type(word.T, jnp.uint8)
            stack = jnp.moveaxis(
                stack.reshape(shape[:-1] + (L * batch, S)), -2, 0)
        else:
            stack = jnp.concatenate(
                [c.reshape((L * batch,) + shape) for c in chans], axis=-1)
        return stack.reshape((L, batch) + stack.shape[1:])

    return jax.tree.map(rebuild, r.obs)


def sequence_ring_sample(state: SequenceRingState, rng: Array,
                         batch_size: int, seq_len: int, alpha: float,
                         beta: Array, use_pallas: bool = False,
                         pallas_interpret: bool = False,
                         merge_obs_rows: bool = False,
                         frame_stack: int = 0,
                         frame_shape=None) -> SequenceSample:
    """Stratified-CDF sample of ``batch_size`` length-``seq_len`` sequences.

    Same inverse-CDF machinery as the transition sampler — the priority
    plane is already masked (zero = invalid start) — including the same
    Pallas kernel routing (ops/pallas_sampler.py) for large planes on TPU.

    ``frame_stack=S > 0``: the ring stores single frames (dedup) and the
    returned obs are rebuilt stacks — the logical ``[L, B, ..., S]`` array
    of the stacked ring, byte for byte, assembled from ONE gather of each
    frame in the batch-minor layout the learner's first convolution reads
    (``_rebuild_seq_stacks``, looked up at call time so that a check can
    put a broken one in its place); starts whose rebuild context predates
    the stored region (the oldest S-1 slots) are masked out of the draw.
    """
    from dist_dqn_tpu.ops.pallas_sampler import (importance_weights,
                                                 stratified_sample)

    num_slots, num_envs = state.priorities.shape
    # Stage names (telemetry/stages.py STAGES): trace metadata only.
    with jax.named_scope("sample"):
        w = jnp.where(state.priorities > 0.0, state.priorities ** alpha,
                      0.0)
        if frame_stack:
            # Exclude the oldest frame_stack-1 starts: their context slots
            # hold the other lap's frames (or nothing, first lap). Shared
            # region logic: replay/device.py contextful_start_mask.
            w = jnp.where(
                ring.contextful_start_mask(state.ring, frame_stack,
                                           num_slots)[:, None],
                w, 0.0)
        t_idx, b_idx, mass_sel, total = stratified_sample(
            w.reshape(-1), rng, batch_size, num_envs, use_pallas=use_pallas,
            interpret=pallas_interpret)
        n_valid = jnp.sum((w > 0.0).astype(jnp.float32))
        weights = importance_weights(mass_sel, total, n_valid, beta)

    r = state.ring
    with jax.named_scope("gather"):
        tt = _window_slots(t_idx, seq_len, num_slots)          # [L, S]
        # Slot t of env b lives at cell (and merged row) t*B + b.
        cells = tt * num_envs + b_idx[None, :]
        if frame_stack:
            obs = _rebuild_seq_stacks(r, t_idx, b_idx, seq_len, frame_stack,
                                      merge_obs_rows, frame_shape,
                                      num_slots, num_envs)
        elif merge_obs_rows:
            obs = jax.tree.map(lambda x: x[cells], r.obs)
        else:
            obs = jax.tree.map(lambda x: x[tt, b_idx[None, :]], r.obs)
        action, reward = r.action[cells], r.reward[cells]
        # (one dense pass and one look-up, as replay/device.py's ``done``)
        done = jnp.logical_or(r.terminated, r.truncated)[cells]
        # obs[t] opens a new episode iff the previous stored step ended one.
        # The first step never resets: its stored carry is already
        # episode-correct.
        reset = jnp.concatenate(
            [jnp.zeros((1, batch_size), jnp.bool_), done[:-1]], axis=0)
        start_state = jax.tree.map(lambda plane: plane[t_idx, b_idx],
                                   state.start_state)
    return SequenceSample(obs=obs, action=action, reward=reward, done=done,
                          reset=reset, start_state=start_state,
                          weights=weights, t_idx=t_idx, b_idx=b_idx)


def sequence_ring_update(state: SequenceRingState, t_idx: Array,
                         b_idx: Array, new_priorities: Array,
                         eps: float = 1e-6) -> SequenceRingState:
    """Write back learner per-sequence priorities for the sampled windows.

    Guarded by ``priorities > 0`` at the written cell so a start that was
    overwritten (cleared) between sample and update cannot be resurrected.
    """
    with jax.named_scope("writeback"):
        p = jnp.abs(new_priorities) + eps
        still_valid = state.priorities[t_idx, b_idx] > 0.0
        p = jnp.where(still_valid, p, 0.0)
        priorities = state.priorities.at[t_idx, b_idx].set(p)
        return state._replace(
            priorities=priorities,
            max_priority=jnp.maximum(state.max_priority, jnp.max(p)))
