"""Base class for JAX-native environments.

Envs implement single-instance ``reset`` / ``env_step`` as pure functions over
a state pytree; the base class derives an auto-resetting ``step`` and
vectorized ``v_reset`` / ``v_step`` via ``vmap``. Everything is jittable, so
rollouts can live entirely on the TPU (Anakin-style) or be traced into the
fused training loop.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from dist_dqn_tpu.types import PyTree, StepOut

Array = jnp.ndarray

# A rolling stack of four uint8 frames is HELD as 32-bit words: one word a
# pixel, byte k of the word = frame k of the stack (little-endian — frame 0,
# the oldest, in the low byte; the order replay/sequence_device.py
# _rebuild_seq_stacks packs). The roll is then a shift, the re-tiling a
# multiply, and the readers share ONE relayout (``words_split``): the
# device gives a u8[B, H, W, 4] array three physical orders (the loop's
# carry, the convolution's batch-minor input, the ring's rows) and relays
# bytes between them at a quarter of the HBM's speed, where u32[B, H*W]
# and its transpose say the order by shape (PERF.md §6, PR 41).
_WORD_DEPTH = 4


def pixel_grid(height: int, width: int) -> Tuple[Array, Array]:
    """Row and column of every pixel, float32 ``[height * width]`` each, on
    the flat index ``h * width + w``. A pixel env rasterizes on it, so its
    frame is ``[H * W]`` — what ``stack_reset`` / ``stack_roll`` take — and
    no ``[H, W] -> [H * W]`` reshape stands between the rendering and the
    words: on the TPU that reshape is a retile, which the compiler spreads
    over every mask of the rendering, where rendering, roll and the
    auto-reset select otherwise fuse into one pass (PERF.md §6, PR 41)."""
    i = np.arange(height * width)
    return (jnp.asarray(i // width, jnp.float32),
            jnp.asarray(i % width, jnp.float32))


def held_in_words(env) -> bool:
    """Whether ``env`` holds its observation as words: decided from what
    the env declares — a stack of four uint8 frames kept in its state —
    and nothing else; any other depth or dtype keeps the bytes,
    ``[..., depth]``."""
    return (env.obs_field is not None and env.frame_stack == _WORD_DEPTH
            and env.observation_dtype == jnp.uint8)


def words_to_stack(words: Array, frame_shape) -> Array:
    """u32[..., P] words -> the logical u8[..., *frame_shape, 4] stack they
    are (a bitcast and a reshape: no byte moves)."""
    return jax.lax.bitcast_convert_type(words, jnp.uint8).reshape(
        words.shape[:-1] + tuple(frame_shape) + (_WORD_DEPTH,))


def stack_to_words(stack: Array) -> Array:
    """u8[..., H, W, 4] -> u32[..., H*W]; ``words_to_stack``'s inverse."""
    lead = stack.shape[:-3]
    return jax.lax.bitcast_convert_type(
        stack.reshape(lead + (-1, _WORD_DEPTH)), jnp.uint32)


class StackWords(NamedTuple):
    """A batch of held words as a loop hands it to its readers: the words
    and the ONE relayout of them an iteration pays for (``words_split``)."""

    words: Array    # u32[B, P]
    split: Array    # u8[P, B, 4]


def words_split(words: Array) -> Array:
    """u32[B, P] words -> u8[P, B, 4]: the words transposed, each split
    into its bytes. On the TPU that is the order a convolution reads its
    input in (the batch in the lanes, one word a pixel and lane) and one
    u8 transpose away from a ring's rows, so it is made once an iteration
    and both read it (``split_stack``, ``split_rows``). The barrier keeps
    it ONE array: without it the compiler derives each reader's view from
    the words again — a retile in front of the transpose, the byte split
    done twice (once through a 7 MB broadcast), and the first convolution
    nested in the second's fusion at twice their time (PERF.md §6, PR 41)."""
    return jax.lax.optimization_barrier(
        jax.lax.bitcast_convert_type(words.T, jnp.uint8))


def split_stack(split: Array, frame_shape) -> Array:
    """u8[P, B, 4] -> the logical u8[B, *frame_shape, 4] stack (what
    ``words_to_stack`` gives), batch-minor as it lies."""
    return jnp.moveaxis(
        split.reshape(tuple(frame_shape) + split.shape[1:]), -2, 0)


def split_rows(split: Array) -> Array:
    """u8[P, B, 4] -> u8[B, 4 * P]: every frame of every pixel, a lane a
    row, in the logical stack's own (row-major) order."""
    return jnp.moveaxis(split, 1, 0).reshape(split.shape[1], -1)


def words_newest(words: Array) -> Array:
    """u32[..., P] -> u8[..., P]: the newest frame, byte 3 of each word."""
    return (words >> 24).astype(jnp.uint8)


class JaxEnv:
    """Interface: subclasses define ``num_actions`` / observation specs and
    single-instance ``reset(rng) -> (state, obs)`` and ``env_step(state,
    action) -> (state, next_obs, reward, terminated, truncated)``; the state
    pytree must carry a per-env rng exposed via ``_reset_rng``.
    """

    num_actions: int
    observation_shape: Tuple[int, ...]
    observation_dtype = jnp.float32
    # Rolling frame-stack depth of the observation's LAST axis, or 0 when
    # obs is not a rolling stack. Non-zero promises the Atari contract:
    # obs_t[..., 1:] == obs_{t-1}[..., :-1] within an episode, and reset
    # re-tiles the first frame across the stack — exactly what
    # ``replay.frame_dedup`` (replay/device.py) relies on to rebuild
    # stacks from single stored frames. ``stack_reset`` / ``stack_roll``
    # below implement both halves (the re-tiling and the roll) for an env
    # that keeps its stack in its state: a pixel env calls them and spells
    # neither the ``tile`` nor the ``concatenate`` itself.
    frame_stack: int = 0
    # The field of the state pytree that holds the observation, in the
    # form ``stack_reset`` made it, for an env that keeps it there (the
    # pixel envs' ``frames``); None: the observation is computed from the
    # state, and a loop that needs it across steps carries it beside.
    obs_field: Optional[str] = None

    def reset(self, rng: Array) -> Tuple[PyTree, Array]:
        raise NotImplementedError

    def env_step(self, state: PyTree, action: Array):
        raise NotImplementedError

    def _reset_rng(self, state: PyTree) -> Array:
        raise NotImplementedError

    # -- the rolling frame stack, as the state holds it ---------------------
    def stack_reset(self, frame: Array) -> Array:
        """The held stack of a fresh episode: ``frame`` ``[H * W]`` in
        every slot (``frame * 0x01010101`` as words)."""
        if held_in_words(self):
            return frame.astype(jnp.uint32) * jnp.uint32(0x01010101)
        frame = frame.reshape(self.observation_shape[:-1])
        return jnp.tile(frame[..., None],
                        (1,) * frame.ndim + (self.frame_stack,))

    def stack_roll(self, held: Array, frame: Array) -> Array:
        """Drop the oldest frame of the held stack, append ``frame``."""
        if held_in_words(self):
            return (held >> 8) | (frame.astype(jnp.uint32) << 24)
        frame = frame.reshape(self.observation_shape[:-1])
        return jnp.concatenate([held[..., 1:], frame[..., None]], axis=-1)

    def stack_obs(self, held: Array) -> Array:
        """The observation a held stack is: ``[..., *observation_shape]``
        (leading axes kept; a bitcast of the words)."""
        if held_in_words(self):
            return words_to_stack(held, self.observation_shape[:-1])
        return held

    def stack_held(self, obs: Array) -> Array:
        """``stack_obs``'s inverse: the held form of an observation."""
        return stack_to_words(obs) if held_in_words(self) else obs

    def observe(self, state: PyTree) -> Optional[Array]:
        """What the state holds of the observation (``obs_field``), in its
        held form, or None. Where it is not None the observation that
        ``reset`` / ``step`` return is ``stack_obs`` of it — the same
        bytes — so a loop carries the state alone and reads this."""
        return getattr(state, self.obs_field) if self.obs_field else None

    # -- auto-reset single-instance step (scalar `done` broadcasts) ---------
    def step(self, state: PyTree, action: Array) -> Tuple[PyTree, StepOut]:
        new_state, next_obs, reward, terminated, truncated = self.env_step(
            state, action)
        done = jnp.logical_or(terminated, truncated)
        reset_state, reset_obs = self.reset(self._reset_rng(new_state))
        state_out = jax.tree.map(lambda r, c: jnp.where(done, r, c),
                                 reset_state, new_state)
        held = self.observe(state_out)
        # A held observation is selected once, with the state.
        obs_out = (jnp.where(done, reset_obs, next_obs) if held is None
                   else self.stack_obs(held))
        return state_out, StepOut(obs=obs_out, next_obs=next_obs,
                                  reward=reward, terminated=terminated,
                                  truncated=truncated)

    # -- vectorized forms ---------------------------------------------------
    def v_reset(self, rng: Array, num_envs: int):
        return jax.vmap(self.reset)(jax.random.split(rng, num_envs))

    def v_step(self, state: PyTree, action: Array):
        return jax.vmap(self.step)(state, action)
