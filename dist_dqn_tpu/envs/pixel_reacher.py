"""PixelReacher — a pure-JAX, DM-Control-shaped 84x84 pixel environment.

The driver's Rainbow config targets DM-Control pixel observations
(BASELINE.json:11). Real ``dm_control`` is available in this image (EGL
rendering; see envs/dmc_adapter.py for the host adapter the Ape-X actors
step), but host MuJoCo cannot live inside the fused on-device loop — so this
synthetic reacher mirrors the DMC ``reacher`` task in branch-free JAX:
a 2-link arm, random target, sparse in-target reward, fixed-length episodes
(DMC semantics: time-limit truncation, never termination), rasterized to
84x84 grayscale with 4-frame stacking.

Actions are the 3x3 torque grid {-1, 0, +1}^2 (9 discrete actions) — the
same discretization the host DMC adapter applies, so policies and configs
transfer between the synthetic and real env.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from dist_dqn_tpu.envs.base import JaxEnv, pixel_grid

Array = jnp.ndarray

_H = _W = 84
_CX = _CY = 42.0       # arm anchor (arena center)
_L1, _L2 = 18.0, 14.0  # link lengths (px)
_DT = 0.25
_TORQUE = 2.0
_DAMPING = 0.12
_MAX_VEL = 6.0
_TARGET_R = 5.0        # in-target radius (px)
_TARGET_DIST_MAX = _L1 + _L2 - 3.0
_TARGET_DIST_MIN = 8.0

import numpy as _np

# 9 actions = {-1, 0, +1} torque per joint (numpy: import must not init JAX).
_ACTION_TORQUE = _np.array([[i - 1, j - 1] for i in range(3)
                            for j in range(3)], _np.float32)


class PixelReacherState(NamedTuple):
    theta: Array    # [2] joint angles
    theta_dot: Array  # [2] joint velocities
    target: Array   # [2] (x, y) px
    t: Array        # scalar int32
    frames: Array     # the frame stack as held (envs/base.py stack_reset)
    rng: Array


def _tip_positions(theta: Array) -> Tuple[Array, Array]:
    """Elbow and fingertip pixel coordinates for joint angles [2]."""
    a1 = theta[0]
    a2 = theta[0] + theta[1]
    elbow = jnp.stack([_CX + _L1 * jnp.cos(a1), _CY + _L1 * jnp.sin(a1)])
    tip = elbow + jnp.stack([_L2 * jnp.cos(a2), _L2 * jnp.sin(a2)])
    return elbow, tip


def _segment_mask(a: Array, b: Array, half_width: float) -> Array:
    """[84 * 84] bool: pixels within ``half_width`` of segment a->b."""
    r, c = pixel_grid(_H, _W)
    ab = b - a
    denom = jnp.maximum(jnp.sum(ab * ab), 1e-6)
    # Project each pixel onto the segment, clamp to [0, 1].
    tproj = ((c - a[0]) * ab[0] + (r - a[1]) * ab[1]) / denom
    tproj = jnp.clip(tproj, 0.0, 1.0)
    dx = c - (a[0] + tproj * ab[0])
    dy = r - (a[1] + tproj * ab[1])
    return dx * dx + dy * dy <= half_width * half_width


def _render(theta: Array, target: Array) -> Array:
    elbow, tip = _tip_positions(theta)
    anchor = jnp.stack([jnp.float32(_CX), jnp.float32(_CY)])
    link1 = _segment_mask(anchor, elbow, 1.5)
    link2 = _segment_mask(elbow, tip, 1.5)
    r, c = pixel_grid(_H, _W)
    d2_target = (c - target[0]) ** 2 + (r - target[1]) ** 2
    ring = (d2_target <= _TARGET_R ** 2) & (d2_target >= (_TARGET_R - 2.0) ** 2)
    d2_tip = (c - tip[0]) ** 2 + (r - tip[1]) ** 2
    tip_m = d2_tip <= 4.0
    frame = jnp.maximum(
        jnp.maximum(link1.astype(jnp.uint8) * 150,
                    link2.astype(jnp.uint8) * 150),
        jnp.maximum(ring.astype(jnp.uint8) * 255,
                    tip_m.astype(jnp.uint8) * 230))
    return frame


def _sample_target(rng: Array) -> Array:
    k_r, k_a = jax.random.split(rng)
    dist = jax.random.uniform(k_r, (), jnp.float32, _TARGET_DIST_MIN,
                              _TARGET_DIST_MAX)
    ang = jax.random.uniform(k_a, (), jnp.float32, 0.0, 2.0 * jnp.pi)
    return jnp.stack([_CX + dist * jnp.cos(ang), _CY + dist * jnp.sin(ang)])


class PixelReacher(JaxEnv):
    """DMC-reacher-shaped synthetic pixel env.

    ``shaping > 0`` adds a dense -shaping * (dist / arena) term to the DMC
    sparse reward — off by default (DMC parity), used by smoke tests that
    need measurable learning in few steps.
    """

    num_actions = 9
    observation_shape = (_H, _W, 4)
    frame_stack = 4  # rolling stack (envs/base.py contract; replay.frame_dedup)
    obs_field = "frames"
    observation_dtype = jnp.uint8

    def __init__(self, max_steps: int = 1000, shaping: float = 0.0):
        self.max_steps = max_steps
        self.shaping = shaping

    def reset(self, rng: Array) -> Tuple[PixelReacherState, Array]:
        rng, k_theta, k_target = jax.random.split(rng, 3)
        theta = jax.random.uniform(k_theta, (2,), jnp.float32, -jnp.pi,
                                   jnp.pi)
        target = _sample_target(k_target)
        frame = _render(theta, target)
        frames = self.stack_reset(frame)
        state = PixelReacherState(theta=theta,
                                  theta_dot=jnp.zeros((2,), jnp.float32),
                                  target=target, t=jnp.int32(0),
                                  frames=frames, rng=rng)
        return state, self.stack_obs(frames)

    def _reset_rng(self, state: PixelReacherState) -> Array:
        return state.rng

    def env_step(self, state: PixelReacherState, action: Array):
        torque = jnp.asarray(_ACTION_TORQUE)[jnp.clip(action, 0, 8)]
        theta_dot = state.theta_dot * (1.0 - _DAMPING) \
            + torque * _TORQUE * _DT
        theta_dot = jnp.clip(theta_dot, -_MAX_VEL, _MAX_VEL)
        theta = state.theta + theta_dot * _DT

        _, tip = _tip_positions(theta)
        dist = jnp.sqrt(jnp.sum((tip - state.target) ** 2))
        reward = (dist <= _TARGET_R).astype(jnp.float32)
        if self.shaping:
            reward = reward - self.shaping * dist / (_L1 + _L2)

        frame = _render(theta, state.target)
        frames = self.stack_roll(state.frames, frame)
        t = state.t + 1
        terminated = jnp.zeros((), jnp.bool_)      # DMC: time limits only
        truncated = t >= self.max_steps
        new_state = PixelReacherState(theta=theta, theta_dot=theta_dot,
                                      target=state.target, t=t,
                                      frames=frames, rng=state.rng)
        return (new_state, self.stack_obs(frames), reward, terminated,
                truncated)
