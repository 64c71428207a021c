"""Greedy-episode rollouts on HOST envs — the one eval protocol shared by
in-training eval (actors/service.py) and standalone checkpoint eval
(evaluate.py), so the two surfaces cannot drift on carry-reset or
truncation accounting.
"""
from __future__ import annotations

from typing import Optional, Tuple

import numpy as np


def run_greedy_episodes(env, act, params, rng, *, episodes: int,
                        recurrent_carry=None, epsilon: float = 0.001,
                        max_steps: int = 10_000
                        ) -> Tuple[np.ndarray, int, "object"]:
    """Play one episode per vectorized env lane with a (near-)greedy
    policy; returns (per-episode returns [episodes], episodes still
    alive at the step cap, advanced rng).

    ``act`` is the jitted actor step: ``act(params, obs, k, eps) ->
    actions`` for feed-forward nets, or — when ``recurrent_carry`` is
    given — ``act(params, carry, obs, k, eps) -> (carry, actions, ...)``
    (extra outputs such as Q planes are ignored; ``agents/agent.py``'s
    ``act`` has this form for any network, its carry ``()`` for a
    feed-forward one). The carry's leaves, ``[B, n]``, are zeroed on each
    lane's episode end, matching training-side acting.
    """
    import jax
    import jax.numpy as jnp

    carry = recurrent_carry
    obs = env.reset()
    returns = np.zeros((episodes,), np.float64)
    alive = np.ones((episodes,), bool)
    eps = jnp.float32(epsilon)
    for _ in range(max_steps):
        rng, k = jax.random.split(rng)
        if carry is not None:
            out = act(params, carry, jnp.asarray(obs), k, eps)
            carry, actions = out[0], out[1]
        else:
            actions = act(params, jnp.asarray(obs), k, eps)
        obs, _, reward, term, trunc = env.step(np.asarray(actions))
        returns += np.asarray(reward, np.float64) * alive
        done = np.logical_or(term, trunc)
        if carry is not None and done.any():
            keep = jnp.asarray(~done, jnp.float32)[:, None]
            carry = jax.tree.map(lambda x: x * keep, carry)
        alive &= ~done
        if not alive.any():
            break
    return returns, int(alive.sum()), rng
