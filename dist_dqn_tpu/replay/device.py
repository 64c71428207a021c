"""On-device replay: a time-major ring buffer living in TPU HBM.

Replaces the reference's host/GPU replay store (BASELINE.json:5) with a
TPU-native layout: one ring of ``T`` time slots, each holding one step from
all ``B`` parallel envs. Observations are ``[T, B, ...]`` (or merged rows,
``[T * B, width]``); every scalar-per-step plane — action, reward,
terminated, truncated, and the priorities of the prioritized ring — is
stored ONCE, flat ``[T * B]``, slot ``t`` of env ``b`` at cell
``t * B + b``: the order of the merged rows, of the sampler's draw and of
the priority write-back, so every reader indexes it in place. The fused
(Anakin) training loop appends one time slice per env step, entirely inside
jit.

n-step returns are computed *at sample time* from the stored per-step
(reward, terminated, truncated) fields, which

  * stores every frame exactly once (no n-step precomputation, no per-
    transition copies of overlapping windows),
  * handles episode boundaries exactly (rewards stop at the first done in
    the window; bootstrap is taken at the first done or at horizon n), and
  * bootstraps correctly through *truncation* (time-limit cuts) because the
    window's successor observation is the stored next time slot.

The same window-gather machinery is reused by the prioritized sampler
(replay/prioritized_device.py) and the R2D2 sequence sampler.
"""
from __future__ import annotations

import math
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from dist_dqn_tpu.types import PyTree, Transition

Array = jnp.ndarray


class TimeRingState(NamedTuple):
    obs: PyTree        # [T, B, ...] observation at each step (post auto-reset)
    action: Array      # [T * B] int32, cell t * B + b
    reward: Array      # [T * B] float32
    terminated: Array  # [T * B] bool
    truncated: Array   # [T * B] bool
    final_obs: PyTree  # [T, B, ...] pre-reset successor obs, or None.
    #   Only differs from the next slot's ``obs`` at episode ends; storing it
    #   buys exact bootstrapping through *truncation*. When None (memory-
    #   tight pixel configs), truncation is treated as terminal instead.
    pos: Array         # scalar int32 — next slot to write
    size: Array        # scalar int32 — slots filled (<= T)


def time_ring_init(num_slots: int, num_envs: int, obs_example: PyTree,
                   store_final_obs: bool = False,
                   merge_obs_rows: bool = False) -> TimeRingState:
    """Allocate a zeroed ring; ``obs_example`` fixes per-env obs shape/dtype.

    ``merge_obs_rows`` stores obs leaves as ``[num_slots * num_envs, ...]``
    instead of ``[num_slots, num_envs, ...]``. Same records, same order —
    slot ``t`` of env ``b`` lives at row ``t * num_envs + b`` — but a 2-D
    buffer is immune to XLA layout assignment putting a small dim (the
    lanes) minormost and tile-padding it: the atari config's 200k-slot
    ring as ``[3125, 64, 28224]`` has its lanes padded 64->128 (2.0x)
    against its 5.3 GB logical size as ``[200000, 28224]``. Callers pass
    the same flag to add/gather/sample. The scalar-per-step planes are
    flat ``[num_slots * num_envs]`` in that same order whatever the flag:
    as ``[T, B]`` a plane of few lanes is either padded to 128 of them
    (8x its bytes at ``B = 16``) or stored time-minor, and its readers —
    a row write, a flattened draw, single-cell gathers and scatters — made
    the loop convert the whole plane between the two every iteration
    (PERF.md §6, PR 37). The planes do not say how they divide into slots
    and lanes: readers are handed ``num_envs``.
    """
    def zeros(x):
        if merge_obs_rows:
            return jnp.zeros((num_slots * num_envs,) + x.shape, x.dtype)
        return jnp.zeros((num_slots, num_envs) + x.shape, x.dtype)

    obs = jax.tree.map(zeros, obs_example)
    return TimeRingState(
        obs=obs,
        action=jnp.zeros((num_slots * num_envs,), jnp.int32),
        reward=jnp.zeros((num_slots * num_envs,), jnp.float32),
        terminated=jnp.zeros((num_slots * num_envs,), jnp.bool_),
        truncated=jnp.zeros((num_slots * num_envs,), jnp.bool_),
        final_obs=jax.tree.map(zeros, obs_example) if store_final_obs
        else None,
        pos=jnp.int32(0),
        size=jnp.int32(0),
    )


def row_head(rows: Array, shape) -> Array:
    """The logical head of gathered merged rows — a ring may store a row
    wider than ``prod(shape)`` (replay/device_ring.py
    ``merged_row_boundary``; the tail is never written) — reshaped to
    ``[..., *shape]``. The gather itself takes whole rows: asked for the
    head alone, the v5e compiler leaves its one-kernel row gather for a
    loop of dynamic-slices or fourteen column blocks (PERF.md §6, PR 33);
    the head of a whole-row gather is a bitcast."""
    width = math.prod(shape)
    if rows.shape[-1] != width:
        rows = rows[..., :width]
    return rows.reshape(rows.shape[:-1] + tuple(shape))


def time_ring_add(state: TimeRingState, obs: PyTree, action: Array,
                  reward: Array, terminated: Array, truncated: Array,
                  final_obs: PyTree = None,
                  merge_obs_rows: bool = False) -> TimeRingState:
    """Append one time slice (all envs) at ``pos``; wraps around. The
    slice's own length is the ring's lane count."""
    num_envs = action.shape[0]
    num_slots = state.action.shape[0] // num_envs
    p = state.pos

    def write(buf, x):
        # Cells (or merged rows) [p*B, (p+1)*B) — x is the [B, ...] slice.
        start = (p * num_envs,) + (0,) * (buf.ndim - 1)
        return jax.lax.dynamic_update_slice(buf, x.astype(buf.dtype), start)

    def write_obs(buf, x):
        return write(buf, x) if merge_obs_rows else buf.at[p].set(x)

    return TimeRingState(
        obs=jax.tree.map(write_obs, state.obs, obs),
        action=write(state.action, action),
        reward=write(state.reward, reward),
        terminated=write(state.terminated, terminated),
        truncated=write(state.truncated, truncated),
        final_obs=jax.tree.map(write_obs, state.final_obs, final_obs)
        if state.final_obs is not None else None,
        pos=(p + 1) % num_slots,
        size=jnp.minimum(state.size + 1, num_slots),
    )


def time_ring_can_sample(state: TimeRingState, n_step: int,
                         frame_stack: int = 0) -> Array:
    """True once windows of length ``n_step`` (plus bootstrap slot) exist.

    With frame-dedup storage (``frame_stack`` > 0) a sampled start also
    needs ``frame_stack - 1`` PRIOR slots stored to rebuild its stack."""
    return state.size > n_step + max(frame_stack - 1, 0)


def _gather_window(field: Array, t_idx: Array, b_idx: Array, n: int,
                   num_slots: int, num_envs: int) -> Array:
    """Gather [..., n] windows starting at ring slot ``t_idx`` for env
    ``b_idx``. field: [T * B] cells; t_idx/b_idx: [S]. Returns [S, n]."""
    offs = jnp.arange(n, dtype=jnp.int32)
    tt = (t_idx[:, None] + offs[None, :]) % num_slots  # [S, n]
    return field[tt * num_envs + b_idx[:, None]]


def compute_n_step(reward_w: Array, term_w: Array, trunc_w: Array,
                   gamma: float) -> Tuple[Array, Array, Array]:
    """Exact n-step return over a window with episode-boundary masking.

    Args: [S, n] windows of per-step reward / terminated / truncated.
    Returns:
      returns:  [S] — sum_{k<=k*} gamma^k r_k, where k* is the first done in
                the window (or n-1 if none).
      discount: [S] — gamma^(k*+1) * (1 - terminated[k*]); zero on terminal,
                a live bootstrap through truncation or a full window.
      kstar:    [S] int32 — index of the last step inside the transition,
                i.e. bootstrap observation lives at slot t + k* + 1.
    """
    n = reward_w.shape[-1]
    done_w = jnp.logical_or(term_w, trunc_w)
    # prefix_cont[k] = prod_{j<k} (1 - done_j): 1 until just after first done.
    cont = 1.0 - done_w.astype(jnp.float32)
    prefix = jnp.concatenate(
        [jnp.ones_like(cont[:, :1]), jnp.cumprod(cont[:, :-1], axis=-1)],
        axis=-1)
    gammas = gamma ** jnp.arange(n, dtype=jnp.float32)
    returns = jnp.sum(prefix * gammas[None, :] * reward_w, axis=-1)

    any_done = jnp.any(done_w, axis=-1)
    first_done = jnp.argmax(done_w, axis=-1).astype(jnp.int32)
    kstar = jnp.where(any_done, first_done, n - 1)
    term_at_k = jnp.take_along_axis(term_w, kstar[:, None], axis=-1)[:, 0]
    discount = (gamma ** (kstar + 1).astype(jnp.float32)) * \
        (1.0 - term_at_k.astype(jnp.float32))
    return returns, discount, kstar


def stored_offset(state: TimeRingState, num_slots: int,
                  t: Array = None) -> Array:
    """Age rank of ring slots ``t`` (default every slot, ``[T]``): 0 for
    the oldest stored, ``size`` and above for slots not stored. With
    ``t = arange(T * B) // B`` it ranks the flat planes' cells in place."""
    if t is None:
        t = jnp.arange(num_slots, dtype=jnp.int32)
    return (t - (state.pos - state.size)) % num_slots


def contextful_start_mask(state: TimeRingState, frame_stack: int,
                          num_slots: int, t: Array = None) -> Array:
    """bool over slots ``t`` (default ``[T]``) — slots whose frame-dedup
    rebuild context is stored: the oldest ``frame_stack - 1`` stored slots
    are excluded (their context holds the other lap's frames, or nothing
    on the first lap). All-true over the stored slots when ``frame_stack``
    is 0/1. Shared by the prioritized transition sampler, the sequence
    sampler, and the loops' can_train gates so the exclusion region cannot
    diverge."""
    offset = stored_offset(state, num_slots, t)
    return jnp.logical_and(offset >= max(frame_stack - 1, 0),
                           offset < state.size)


def last_write_wins_scatter(plane: Array, flat_idx: Array, values: Array
                            ) -> Array:
    """Scatter ``values`` into flat ``plane`` with DETERMINISTIC
    chronological last-write-wins on duplicate indices (ISSUE 6).

    XLA scatter leaves the application order of duplicate indices
    implementation-defined, so a plain ``.at[idx].set(v)`` cannot
    promise which of N replay-ratio sub-steps' |TD| values a
    twice-sampled slot ends up with. This routes every non-final
    writer of a slot out of bounds (``mode='drop'``) after electing
    the chronologically LAST writer with a scatter-max over write
    positions — one vectorized pass, no host round trip, and the same
    last-wins contract the host-side batched write-backs keep
    (host_ring.RingPrioritySampler / actors/service.py).

    Args: plane [S] flat target; flat_idx [M] int32 write positions in
    chronological order; values [M]. Returns the updated [S] plane.
    """
    order = jnp.arange(1, flat_idx.shape[0] + 1, dtype=jnp.int32)
    # Last writer per slot: max write position landing on it (0 = none).
    winner = jnp.zeros(plane.shape[0], jnp.int32).at[flat_idx].max(order)
    keep = winner[flat_idx] == order
    safe_idx = jnp.where(keep, flat_idx, plane.shape[0])  # OOB -> dropped
    return plane.at[safe_idx].set(values, mode="drop")


def stack_rebuild_indices(done_at, t_idx: Array, frame_stack: int,
                          num_slots: int):
    """Per-channel ring slots that rebuild a frame stack stored deduped.

    The rolling-stack contract (envs/base.py ``frame_stack``): within an
    episode ``obs_t`` channel with lookback ``d`` (d=0 newest) is the
    single frame from step ``t-d``; a reset at boundary ``done[t-1-j]``
    re-tiled the stack, so frames older than the episode start are the
    episode's FIRST frame repeated. Hence channel ``d`` comes from slot
    ``t - min(d, age_t)`` where ``age_t`` = j-1 for the nearest j in
    [1, S-1] with ``done[t-j]`` (S-1 when none — unconstrained).

    ``done_at(slots) -> [len(t_idx)] bool`` abstracts the done-flag
    lookup so callers own the (merge-rows vs tiled) indexing. Returns
    slot indices per lookback, NEWEST-first: [(d, [S] slots), ...].
    ``gather_transitions`` stacks them oldest-first into ONE [S, N] index
    so the whole rebuild is a single row gather (see there).
    """
    S = frame_stack
    age = jnp.full_like(t_idx, S - 1)
    for j in range(S - 1, 0, -1):  # descending: the NEAREST done wins
        age = jnp.where(done_at((t_idx - j) % num_slots), j - 1, age)
    return [(d, (t_idx - jnp.minimum(d, age)) % num_slots)
            for d in range(S)]


def gather_transitions(state: TimeRingState, t_idx: Array, b_idx: Array,
                       n_step: int, gamma: float, num_envs: int,
                       merge_obs_rows: bool = False,
                       frame_stack: int = 0,
                       frame_shape=None) -> Transition:
    """Window-gather + n-step fold for explicit (t_idx, b_idx) pairs of a
    ring of ``num_envs`` lanes.

    Shared by the uniform and prioritized samplers so the episode-boundary
    semantics live in exactly one place.

    ``frame_stack=S > 0``: the ring stores only each step's NEWEST frame
    (obs leaves [..., H, W, 1] — a 4x HBM saving for Atari stacks) and
    this gather rebuilds the full [N, H, W, S] stacks exactly, including
    the reset-boundary re-tiling (see ``stack_rebuild_indices``). In
    merge_obs_rows mode the stored rows are flat; ``frame_shape`` (e.g.
    (84, 84, 1)) is then required to reshape gathered rows — gathered
    stacks come back UNFLATTENED either way. The slot index is [S, N]
    (stack-major), one row gather per leaf: the learner's input is
    batch-minor on TPU, and [S*N, H*W] rows ARE [N, H, W, S] in that
    layout once transposed — one 2-D relayout instead of S size-1-minor
    frame copies and a concatenate (PERF.md, PR 28).
    """
    if frame_stack and state.final_obs is not None:
        raise ValueError(
            "frame_stack rebuild is undefined for rings with final_obs "
            "(the final-obs buffer is not a rolling frame stream) — "
            "build the ring with store_final_obs=False for frame dedup")
    with jax.named_scope("gather"):
        return _gather_transitions(state, t_idx, b_idx, n_step, gamma,
                                   num_envs, merge_obs_rows, frame_stack,
                                   frame_shape)


def _gather_transitions(state: TimeRingState, t_idx: Array, b_idx: Array,
                        n_step: int, gamma: float, num_envs: int,
                        merge_obs_rows: bool, frame_stack: int,
                        frame_shape) -> Transition:
    num_slots = state.action.shape[0] // num_envs
    reward_w, term_w, trunc_w = (
        _gather_window(field, t_idx, b_idx, n_step, num_slots, num_envs)
        for field in (state.reward, state.terminated, state.truncated))
    returns, discount, kstar = compute_n_step(reward_w, term_w, trunc_w,
                                              gamma)

    # One dense pass over the flat cells, then ONE look-up a lookback: at
    # 1M cells the pass is 9 us and a 512-cell look-up 5 (PERF.md §6, PR 37).
    done = jnp.logical_or(state.terminated, state.truncated)

    def done_at(tt):
        return done[tt * num_envs + b_idx]

    def take_one(x, t):
        if merge_obs_rows:
            out = x[t * num_envs + b_idx]
            if frame_stack and frame_shape is not None:
                out = row_head(out, frame_shape)
            return out
        return x[t, b_idx]

    def take(tree, t):
        if not frame_stack:
            return jax.tree.map(lambda x: take_one(x, t), tree)
        slots = stack_rebuild_indices(done_at, t, frame_stack, num_slots)
        # [S, N] slot index, channel order oldest -> newest = lookback
        # S-1 -> 0: ONE row gather fetches every frame of every sample.
        ts = jnp.stack([s for _, s in reversed(slots)])

        def rebuild(x):
            # [S, N, H, W, 1] -> [N, H, W, S]: stack-major rows make the
            # stack a plain 2-D transpose of [S*N, H*W].
            frames = jnp.moveaxis(take_one(x, ts), 0, -2)
            return frames.reshape(frames.shape[:-2] + (-1,))

        return jax.tree.map(rebuild, tree)

    obs = take(state.obs, t_idx)
    action = state.action[t_idx * num_envs + b_idx]
    if state.final_obs is not None:
        # Exact path: the stored pre-reset successor of step k*.
        boot_t = (t_idx + kstar) % num_slots
        next_obs = take(state.final_obs, boot_t)
    else:
        # The next slot's obs is post-reset at episode ends, so it is only a
        # valid bootstrap within an episode: zero the discount at truncation
        # (termination already zeroes it in compute_n_step).
        trunc_at_k = jnp.take_along_axis(trunc_w, kstar[:, None],
                                         axis=-1)[:, 0]
        discount = discount * (1.0 - trunc_at_k.astype(jnp.float32))
        boot_t = (t_idx + kstar + 1) % num_slots
        next_obs = take(state.obs, boot_t)
    return Transition(obs=obs, action=action, reward=returns,
                      discount=discount, next_obs=next_obs)


def time_ring_sample(state: TimeRingState, rng: Array, batch_size: int,
                     n_step: int, gamma: float, num_envs: int,
                     merge_obs_rows: bool = False,
                     frame_stack: int = 0, frame_shape=None) -> Transition:
    """Uniformly sample ``batch_size`` n-step transitions from a ring of
    ``num_envs`` lanes.

    Valid window starts are the oldest ``size - n_step`` slots, so the
    bootstrap slot (start + k* + 1 <= start + n_step) is always a stored,
    in-order step of the same env. Frame-dedup rings additionally skip
    the oldest ``frame_stack - 1`` starts (their rebuild context is not
    stored — time_ring_can_sample gates the same way).
    """
    num_slots = state.action.shape[0] // num_envs
    extra = max(frame_stack - 1, 0)
    with jax.named_scope("sample"):
        k_t, k_b = jax.random.split(rng)
        num_valid = state.size - n_step - extra  # traced; gated by can_sample
        u = jax.random.randint(k_t, (batch_size,), 0,
                               jnp.maximum(num_valid, 1))
        t_idx = (state.pos - state.size + extra + u) % num_slots
        b_idx = jax.random.randint(k_b, (batch_size,), 0, num_envs)
    return gather_transitions(state, t_idx, b_idx, n_step, gamma, num_envs,
                              merge_obs_rows=merge_obs_rows,
                              frame_stack=frame_stack,
                              frame_shape=frame_shape)
