"""What the fused chunk program (train_loop.py) asks of an agent.

One place decides, from the network, whether acting carries a state
between steps: a network with ``initial_state`` (models/recurrent.py)
gets the sequence learner and a threaded actor state, any other the
feed-forward learner and the EMPTY state ``()`` — no leaf, so no
operation and no buffer in the compiled program. The loop, the evaluator
and the mesh specs treat the state as an opaque pytree whose leaves are
``[B, ...]``, so a state that is not an LSTM pair changes ``models/`` and
this package only. What of it a sequence ring stores with each step is the
network's word too (``stored_state``: the LSTM pair whole; nothing of a
core whose state is megabytes a lane, models/sequence_core.py).
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import optax

from dist_dqn_tpu.agents.dqn import make_actor_step, make_learner
from dist_dqn_tpu.agents.r2d2 import ROUTING_COUNTERS, \
    make_r2d2_learner, make_recurrent_actor_step
from dist_dqn_tpu.config import ExperimentConfig


class Agent(NamedTuple):
    init_learner: Callable   # (key, obs_example) -> LearnerState
    # (learner, sample) -> (learner, metrics); ``sample`` is whatever the
    # replay built for this agent draws (replay/device_ring.py).
    train_step: Callable
    # (params, actor_state, obs, key, eps) -> (actor_state, actions [B])
    act: Callable
    initial_state: Callable  # B -> actor state for B lanes; () = none
    reset_state: Callable    # (actor_state, done [B]) -> actor_state
    # actor_state -> what a sequence ring keeps of it with each step, for
    # the learner's windows to start from; () = nothing (the learner
    # starts from an empty state and burns in)
    stored_state: Callable
    # names of scalar ``train_step`` metrics beside the loss that the chunk
    # program averages over a chunk's grad steps into its row
    chunk_metrics: tuple


def make_agent(net, cfg: ExperimentConfig, axis_name: Optional[str] = None,
               tx: Optional[optax.GradientTransformation] = None) -> Agent:
    """The agent for ``net``. ``axis_name`` makes the train step the
    per-device body of a data-parallel learner (gradients pmean-ed);
    ``tx`` overrides the feed-forward optimizer (population.py)."""
    if hasattr(net, "initial_state"):
        init_learner, train_step = make_r2d2_learner(
            net, cfg.learner, cfg.replay, axis_name=axis_name)
        act = make_recurrent_actor_step(net)
        initial_state = net.initial_state
        stored_state = net.stored_state
        chunk_metrics = (ROUTING_COUNTERS if getattr(net, "sows_routing",
                                                     False) else ())
    else:
        init_learner, train = make_learner(net, cfg.learner,
                                           axis_name=axis_name, tx=tx)
        step = make_actor_step(net)

        def train_step(learner, sample):
            return train(learner, sample.batch, sample.weights)

        def act(params, actor_state, obs, key, eps):
            return actor_state, step(params, obs, key, eps)

        def initial_state(num_lanes: int):
            return ()

        def stored_state(actor_state):
            return ()

        chunk_metrics = ()

    def reset_state(actor_state, done):
        # Zero the state of lanes that just finished an episode so the
        # next act (and the state stored with it) starts the new one fresh.
        keep = (~done).astype(jnp.float32)
        return jax.tree.map(
            lambda x: x * jax.lax.expand_dims(keep, range(1, x.ndim)),
            actor_state)

    # a network whose state is not emptied by zeroing all of it says how
    # (models/sequence_core.py: a ring of keys is emptied by its counter)
    reset_state = getattr(net, "reset_state", reset_state)
    return Agent(init_learner, train_step, act, initial_state, reset_state,
                 stored_state, chunk_metrics)
