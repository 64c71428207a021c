"""PixelCatch — the fast-learning pixel control task, Atari-shaped.

Purpose (VERDICT round 2, next #4): the pixel configs need evidence of
LEARNING, not just loss-finiteness — but this 1-core dev box cannot train
pixel Pong far enough to beat random inside a test budget (measured: 48k
frames in ~500s with returns still at the random baseline). Catch is the
standard cheap pixel task (bsuite / DeepMind's haiku examples use it for
exactly this reason): a ball falls from a random column, the agent slides
a paddle along the bottom row; ±1 on catch/miss. A random policy catches
rarely (the paddle covers ~1/8 of the width); a working DQN approaches
+1 within tens of thousands of frames — a margin no smoke test can fake.

The observation keeps the full Atari shape — [84, 84, 4] uint8 frame
stack — so a learning run exercises the SAME pipeline as the atari/apex
configs: uint8 pixel replay rings, CNN torso, n-step TD, PER. Actions
follow the minimal-ALE convention (NOOP, LEFT, RIGHT = 3 actions, like
real Catch implementations).
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from dist_dqn_tpu.envs.base import JaxEnv, pixel_grid

Array = jnp.ndarray

_H = _W = 84
_PAD_HALF = 5          # paddle half-width (10 px wide)
_PAD_Y = 80.0          # paddle row
_BALL_SPEED = 3.0      # rows per step: ~26-step episodes
_PAD_SPEED = 3.0


class PixelCatchState(NamedTuple):
    ball_x: Array     # scalar float32
    ball_y: Array
    pad_x: Array
    t: Array          # scalar int32
    frames: Array     # the frame stack as held (envs/base.py stack_reset)
    rng: Array


def _render(ball_x: Array, ball_y: Array, pad_x: Array) -> Array:
    r, c = pixel_grid(_H, _W)
    ball_m = (jnp.abs(r - ball_y) <= 1.5) & (jnp.abs(c - ball_x) <= 1.5)
    pad_m = (jnp.abs(r - _PAD_Y) <= 1.5) & (jnp.abs(c - pad_x) <= _PAD_HALF)
    return (ball_m.astype(jnp.uint8) * 255 | pad_m.astype(jnp.uint8) * 200)


class PixelCatch(JaxEnv):
    num_actions = 3    # NOOP, LEFT, RIGHT (minimal-set convention)
    observation_shape = (_H, _W, 4)
    frame_stack = 4  # rolling stack (envs/base.py contract; replay.frame_dedup)
    obs_field = "frames"
    observation_dtype = jnp.uint8

    def __init__(self, max_steps: int = 200):
        self.max_steps = max_steps

    def reset(self, rng: Array) -> Tuple[PixelCatchState, Array]:
        rng, k_ball, k_pad = jax.random.split(rng, 3)
        ball_x = jax.random.uniform(k_ball, (), jnp.float32, 4.0, _W - 5.0)
        pad_x = jax.random.uniform(k_pad, (), jnp.float32, _PAD_HALF,
                                   _W - 1.0 - _PAD_HALF)
        ball_y = jnp.float32(4.0)
        frame = _render(ball_x, ball_y, pad_x)
        frames = self.stack_reset(frame)
        state = PixelCatchState(ball_x=ball_x, ball_y=ball_y, pad_x=pad_x,
                                t=jnp.int32(0), frames=frames, rng=rng)
        return state, self.stack_obs(frames)

    def _reset_rng(self, state: PixelCatchState) -> Array:
        return state.rng

    def env_step(self, state: PixelCatchState, action: Array):
        dx = jnp.where(action == 1, -_PAD_SPEED,
                       jnp.where(action == 2, _PAD_SPEED, 0.0))
        pad_x = jnp.clip(state.pad_x + dx, _PAD_HALF, _W - 1.0 - _PAD_HALF)
        ball_y = state.ball_y + _BALL_SPEED
        reached = ball_y >= _PAD_Y
        caught = reached & (jnp.abs(state.ball_x - pad_x) <= _PAD_HALF + 1.5)
        reward = jnp.where(caught, 1.0,
                           jnp.where(reached, -1.0, 0.0)).astype(jnp.float32)
        t = state.t + 1
        terminated = reached
        truncated = jnp.logical_and(t >= self.max_steps, ~terminated)
        frame = _render(state.ball_x, ball_y, pad_x)
        frames = self.stack_roll(state.frames, frame)
        new_state = PixelCatchState(ball_x=state.ball_x, ball_y=ball_y,
                                    pad_x=pad_x, t=t, frames=frames,
                                    rng=state.rng)
        return (new_state, self.stack_obs(frames), reward, terminated,
                truncated)
