"""PixelPong — a pure-JAX, Atari-shaped 84x84 pixel environment.

The driver's Atari configs (BASELINE.json:8-9) target ALE Pong/Breakout, but
this image has no ``ale-py`` and no network (SURVEY.md §7 [ENV]), so the
Atari-shaped perf and training paths run offline on this synthetic Pong: 84x84
grayscale frames, 4-frame stacking, 6 Atari-style actions, ±1 point rewards,
first-to-5 episodes. Real ALE plugs in through the host-env adapter
(``envs/gym_adapter.py``) when available — the learner/replay stack is
identical, only the env behind the actor changes.

Everything (physics + rasterization + framestack) is branch-free JAX, so
thousands of envs step in parallel on a TPU core inside the fused loop.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from dist_dqn_tpu.envs.base import JaxEnv, pixel_grid

Array = jnp.ndarray

_H = _W = 84
_PAD_HALF = 4          # paddle half-height (8 px tall)
_AGENT_X = 78.0        # agent paddle column (2 px wide)
_OPP_X = 4.0
_BALL_SPEED_X = 1.6
_PAD_SPEED = 2.0
_OPP_SPEED = 1.0
_WIN_SCORE = 5

# Atari Pong action semantics: NOOP, FIRE, UP, DOWN, UPFIRE, DOWNFIRE.
# (numpy, not jnp: module import must not trigger JAX backend init.)
import numpy as _np

_ACTION_DY = _np.array([0.0, 0.0, -_PAD_SPEED, _PAD_SPEED,
                        -_PAD_SPEED, _PAD_SPEED], _np.float32)


class PixelPongState(NamedTuple):
    ball: Array       # [4] = (x, y, vx, vy) float32
    pad_y: Array      # agent paddle center
    opp_y: Array      # opponent paddle center
    score: Array      # [2] int32 = (agent, opponent)
    t: Array          # scalar int32
    frames: Array     # the frame stack as held (envs/base.py stack_reset)
    rng: Array


def _render(ball: Array, pad_y: Array, opp_y: Array) -> Array:
    """Rasterize one uint8 frame, [84 * 84] on the flat pixel index
    (envs/base.py pixel_grid), elementwise."""
    r, c = pixel_grid(_H, _W)
    ball_m = (jnp.abs(r - ball[1]) <= 1.0) & (jnp.abs(c - ball[0]) <= 1.0)
    pad_m = (jnp.abs(r - pad_y) <= _PAD_HALF) & (jnp.abs(c - _AGENT_X) <= 1.0)
    opp_m = (jnp.abs(r - opp_y) <= _PAD_HALF) & (jnp.abs(c - _OPP_X) <= 1.0)
    frame = (ball_m.astype(jnp.uint8) * 255
             | pad_m.astype(jnp.uint8) * 200
             | opp_m.astype(jnp.uint8) * 200)
    return frame


def _serve(rng: Array, toward_agent: Array) -> Array:
    """New ball at center; vx toward the given side, vy random."""
    vy = jax.random.uniform(rng, (), jnp.float32, -1.0, 1.0)
    vx = jnp.where(toward_agent, _BALL_SPEED_X, -_BALL_SPEED_X)
    return jnp.stack([_W / 2.0, _H / 2.0, vx, vy])


class PixelPong(JaxEnv):
    num_actions = 6
    observation_shape = (_H, _W, 4)
    frame_stack = 4  # rolling stack (envs/base.py contract; replay.frame_dedup)
    obs_field = "frames"
    observation_dtype = jnp.uint8

    def __init__(self, max_steps: int = 2000):
        self.max_steps = max_steps

    def reset(self, rng: Array) -> Tuple[PixelPongState, Array]:
        rng, k_serve, k_side = jax.random.split(rng, 3)
        toward_agent = jax.random.bernoulli(k_side)
        ball = _serve(k_serve, toward_agent)
        pad_y = jnp.float32(_H / 2.0)
        opp_y = jnp.float32(_H / 2.0)
        frame = _render(ball, pad_y, opp_y)
        frames = self.stack_reset(frame)
        state = PixelPongState(ball=ball, pad_y=pad_y, opp_y=opp_y,
                               score=jnp.zeros((2,), jnp.int32),
                               t=jnp.int32(0), frames=frames, rng=rng)
        return state, self.stack_obs(frames)

    def _reset_rng(self, state: PixelPongState) -> Array:
        return state.rng

    def env_step(self, state: PixelPongState, action: Array):
        rng, k_serve = jax.random.split(state.rng)

        # Paddles.
        dy = jnp.asarray(_ACTION_DY)[jnp.clip(action, 0, 5)]
        pad_y = jnp.clip(state.pad_y + dy, _PAD_HALF, _H - 1 - _PAD_HALF)
        opp_dy = jnp.clip(state.ball[1] - state.opp_y, -_OPP_SPEED, _OPP_SPEED)
        opp_y = jnp.clip(state.opp_y + opp_dy, _PAD_HALF, _H - 1 - _PAD_HALF)

        # Ball motion with top/bottom bounce.
        bx = state.ball[0] + state.ball[2]
        by = state.ball[1] + state.ball[3]
        vy = jnp.where((by <= 1.0) | (by >= _H - 2.0), -state.ball[3],
                       state.ball[3])
        by = jnp.clip(by, 1.0, _H - 2.0)
        vx = state.ball[2]

        # Paddle collisions: reflect and add spin from the hit offset.
        hit_agent = (bx >= _AGENT_X - 1.0) & (vx > 0) & \
                    (jnp.abs(by - pad_y) <= _PAD_HALF + 1.0)
        hit_opp = (bx <= _OPP_X + 1.0) & (vx < 0) & \
                  (jnp.abs(by - opp_y) <= _PAD_HALF + 1.0)
        spin = jnp.where(hit_agent, (by - pad_y) / _PAD_HALF * 0.8,
                         jnp.where(hit_opp, (by - opp_y) / _PAD_HALF * 0.8,
                                   0.0))
        vx = jnp.where(hit_agent, -vx, jnp.where(hit_opp, -vx, vx))
        vy = jnp.clip(vy + spin, -1.8, 1.8)
        bx = jnp.where(hit_agent, _AGENT_X - 1.0,
                       jnp.where(hit_opp, _OPP_X + 1.0, bx))

        # Scoring: ball past a paddle column.
        agent_point = bx <= 1.0     # opponent missed
        opp_point = bx >= _W - 2.0  # agent missed
        point = agent_point | opp_point
        reward = jnp.where(agent_point, 1.0,
                           jnp.where(opp_point, -1.0, 0.0)).astype(jnp.float32)
        score = state.score + jnp.stack(
            [agent_point.astype(jnp.int32), opp_point.astype(jnp.int32)])

        served = _serve(k_serve, toward_agent=opp_point)
        ball = jnp.where(point, served, jnp.stack([bx, by, vx, vy]))

        frame = _render(ball, pad_y, opp_y)
        frames = self.stack_roll(state.frames, frame)
        t = state.t + 1
        terminated = jnp.max(score) >= _WIN_SCORE
        truncated = jnp.logical_and(t >= self.max_steps, ~terminated)
        new_state = PixelPongState(ball=ball, pad_y=pad_y, opp_y=opp_y,
                                   score=score, t=t, frames=frames, rng=rng)
        return (new_state, self.stack_obs(frames), reward, terminated,
                truncated)
