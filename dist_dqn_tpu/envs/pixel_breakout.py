"""PixelBreakout — a pure-JAX, Atari-shaped 84x84 Breakout.

Second device-native full game beside PixelPong (envs/pixel_pong.py),
with the structure that makes real Breakout interesting and that Pong
lacks: a destructible brick wall (6 rows x 12 columns), FIRE-to-serve,
a lives counter, and dense-but-earned rewards (+1 per brick, 72 max).
The driver's Atari configs name Pong AND Breakout (BASELINE.json:8-9);
the host-side fake ALE models Breakout's raw-frame protocol
(envs/fake_ale.py), and this env is its fused-loop counterpart: the
whole game — physics, brick collisions, rasterization, frame stacking —
is branch-free JAX, so a thousand lanes step in parallel on a TPU core
inside the fused train loop at the same rates as the headline bench.

Action semantics follow the minimal-ALE Breakout set: NOOP, FIRE,
RIGHT, LEFT (4 actions, same order as ale-py's minimal action set).
While the ball is not in play only FIRE serves it (real-Breakout
fire-to-serve, the semantics ALE's episodic-life wrappers care about);
losing the ball costs one of 5 lives, and the episode ends when lives
run out or the wall is cleared.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from dist_dqn_tpu.envs.base import JaxEnv, pixel_grid

Array = jnp.ndarray

_H = _W = 84
_ROWS, _COLS = 6, 12
_BRICK_H, _BRICK_W = 3, 7      # 6x3 rows of 12x7 bricks = rows 18..35
_WALL_TOP = 18.0
_WALL_BOT = _WALL_TOP + _ROWS * _BRICK_H
_PAD_Y = 78.0
_PAD_HALF = 4.0                # 8 px paddle
_PAD_SPEED = 3.0
_BALL_SPEED_Y = 2.0
_LIVES = 5


class PixelBreakoutState(NamedTuple):
    ball: Array       # [4] = (x, y, vx, vy) float32
    pad_x: Array      # paddle center column
    bricks: Array     # [6, 12] float32 (1 = alive)
    lives: Array      # scalar int32
    in_play: Array    # scalar bool — False until FIRE serves
    t: Array          # scalar int32
    frames: Array     # the frame stack as held (envs/base.py stack_reset)
    rng: Array


def _render(ball: Array, pad_x: Array, bricks: Array,
            in_play: Array) -> Array:
    r, c = pixel_grid(_H, _W)
    # Brick wall: map each pixel to its brick cell and gather liveness.
    cell_r = jnp.clip(((r - _WALL_TOP) // _BRICK_H).astype(jnp.int32),
                      0, _ROWS - 1)
    cell_c = jnp.clip((c // _BRICK_W).astype(jnp.int32), 0, _COLS - 1)
    in_wall = (r >= _WALL_TOP) & (r < _WALL_BOT)
    brick_m = in_wall & (bricks[cell_r, cell_c] > 0.5) \
        & (c < _COLS * _BRICK_W)
    ball_m = in_play & (jnp.abs(r - ball[1]) <= 1.0) \
        & (jnp.abs(c - ball[0]) <= 1.0)
    pad_m = (jnp.abs(r - _PAD_Y) <= 1.0) & (jnp.abs(c - pad_x) <= _PAD_HALF)
    frame = (ball_m.astype(jnp.uint8) * 255
             | pad_m.astype(jnp.uint8) * 200
             | brick_m.astype(jnp.uint8) * 120)
    return frame


def _serve(rng: Array, pad_x: Array) -> Array:
    """Ball starts just above the paddle, heading up at a random angle."""
    vx = jax.random.uniform(rng, (), jnp.float32, -1.2, 1.2)
    return jnp.stack([pad_x, _PAD_Y - 3.0, vx, -_BALL_SPEED_Y])


class PixelBreakout(JaxEnv):
    num_actions = 4    # NOOP, FIRE, RIGHT, LEFT (ale-py minimal order)
    observation_shape = (_H, _W, 4)
    frame_stack = 4  # rolling stack (envs/base.py contract; replay.frame_dedup)
    obs_field = "frames"
    observation_dtype = jnp.uint8

    def __init__(self, max_steps: int = 2000):
        self.max_steps = max_steps

    def reset(self, rng: Array) -> Tuple[PixelBreakoutState, Array]:
        rng, _ = jax.random.split(rng)
        pad_x = jnp.float32(_W / 2.0)
        bricks = jnp.ones((_ROWS, _COLS), jnp.float32)
        ball = jnp.stack([pad_x, _PAD_Y - 3.0, jnp.float32(0.0),
                          jnp.float32(0.0)])
        frame = _render(ball, pad_x, bricks, jnp.bool_(False))
        frames = self.stack_reset(frame)
        state = PixelBreakoutState(
            ball=ball, pad_x=pad_x, bricks=bricks,
            lives=jnp.int32(_LIVES), in_play=jnp.bool_(False),
            t=jnp.int32(0), frames=frames, rng=rng)
        return state, self.stack_obs(frames)

    def _reset_rng(self, state: PixelBreakoutState) -> Array:
        return state.rng

    def env_step(self, state: PixelBreakoutState, action: Array):
        rng, k_serve = jax.random.split(state.rng)

        dx = jnp.where(action == 2, _PAD_SPEED,
                       jnp.where(action == 3, -_PAD_SPEED, 0.0))
        pad_x = jnp.clip(state.pad_x + dx, _PAD_HALF,
                         _W - 1.0 - _PAD_HALF)

        # FIRE serves when the ball is dead; otherwise it is a NOOP.
        serve = (~state.in_play) & (action == 1)
        served = _serve(k_serve, pad_x)
        ball = jnp.where(serve, served, state.ball)
        in_play = state.in_play | serve

        # Ball motion (frozen while not in play) with wall bounces.
        bx = ball[0] + jnp.where(in_play, ball[2], 0.0)
        by = ball[1] + jnp.where(in_play, ball[3], 0.0)
        vx = jnp.where((bx <= 1.0) | (bx >= _W - 2.0), -ball[2], ball[2])
        bx = jnp.clip(bx, 1.0, _W - 2.0)
        vy = jnp.where(by <= 1.0, -ball[3], ball[3])
        by = jnp.maximum(by, 1.0)

        # Brick collision: the cell under the new ball position.
        cell_r = jnp.clip(((by - _WALL_TOP) // _BRICK_H).astype(jnp.int32),
                          0, _ROWS - 1)
        cell_c = jnp.clip((bx // _BRICK_W).astype(jnp.int32), 0, _COLS - 1)
        in_wall = in_play & (by >= _WALL_TOP) & (by < _WALL_BOT) \
            & (bx < _COLS * _BRICK_W)
        hit_brick = in_wall & (state.bricks[cell_r, cell_c] > 0.5)
        bricks = state.bricks.at[cell_r, cell_c].set(
            jnp.where(hit_brick, 0.0, state.bricks[cell_r, cell_c]))
        vy = jnp.where(hit_brick, -vy, vy)
        reward = hit_brick.astype(jnp.float32)

        # Paddle bounce with spin from the hit offset.
        hit_pad = in_play & (by >= _PAD_Y - 1.0) & (vy > 0) \
            & (jnp.abs(bx - pad_x) <= _PAD_HALF + 1.0)
        spin = jnp.where(hit_pad, (bx - pad_x) / _PAD_HALF * 0.8, 0.0)
        vy = jnp.where(hit_pad, -vy, vy)
        vx = jnp.clip(vx + spin, -1.8, 1.8)
        by = jnp.where(hit_pad, _PAD_Y - 1.0, by)

        # Ball lost below the paddle: lose a life, back to serve state.
        lost = in_play & (by >= _H - 2.0)
        lives = state.lives - lost.astype(jnp.int32)
        in_play = in_play & ~lost
        ball = jnp.stack([bx, by, vx, vy])
        dead_ball = jnp.stack([pad_x, _PAD_Y - 3.0, jnp.float32(0.0),
                               jnp.float32(0.0)])
        ball = jnp.where(lost, dead_ball, ball)

        cleared = jnp.sum(bricks) <= 0.0
        t = state.t + 1
        terminated = (lives <= 0) | cleared
        truncated = jnp.logical_and(t >= self.max_steps, ~terminated)

        frame = _render(ball, pad_x, bricks, in_play)
        frames = self.stack_roll(state.frames, frame)
        new_state = PixelBreakoutState(
            ball=ball, pad_x=pad_x, bricks=bricks, lives=lives,
            in_play=in_play, t=t, frames=frames, rng=rng)
        return (new_state, self.stack_obs(frames), reward, terminated,
                truncated)
