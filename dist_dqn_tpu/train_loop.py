"""Fused on-device training loop (Anakin-style, after Podracer/PAPERS.md:5).

For JAX-native envs the entire act -> env.step -> replay.add -> sample ->
train iteration is one ``lax.scan`` body compiled into a single XLA program:
zero host round-trips in steady state, which is what a TPU needs to hit the
driver's env-steps/sec/chip north star (BASELINE.json:2). Host envs (real
Atari / DM-Control) instead use the Ape-X actor/learner split in
``actors/`` — same learner, different feeding mechanism.

The loop is SPMD-parameterizable: with ``axis_name``/``num_shards`` set it
becomes the *per-device* body of the multi-chip program (see
``parallel/learner.py``): envs, replay shard and sampling are local to each
device, and only the learner's gradients cross the ICI via ``pmean``
(BASELINE.json:5 — sharded replay, allreduced learners, replicated params).
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from dist_dqn_tpu import loop_common
from dist_dqn_tpu.agents.agent import make_agent
from dist_dqn_tpu.agents.dqn import LearnerState, \
    make_population_optimizer, set_member_lr
from dist_dqn_tpu.config import ExperimentConfig
from dist_dqn_tpu.envs.base import JaxEnv, StackWords, held_in_words, \
    split_stack, words_split
from dist_dqn_tpu.replay.device_ring import make_device_ring
from dist_dqn_tpu.types import PyTree

Array = jnp.ndarray


class MemberHP(NamedTuple):
    """Per-member hyperparameters of the population plane (ISSUE 20).

    Scalar f32 leaves under the vmapped member axis — [M] arrays at the
    stacked entry points, member k's scalars inside the per-member body.
    ``eps_delta`` is ``epsilon_start - epsilon_end`` folded on the host
    in float64 then cast to f32 (the exact constant
    ``optax.linear_schedule`` embeds — loop_common.make_member_epsilon).
    ``lr`` is consumed only when the member optimizer is the injected
    one (``member_lr=True``); it rides along untouched otherwise.
    """

    eps_delta: Array
    eps_end: Array
    gamma: Array
    lr: Array


class TrainCarry(NamedTuple):
    env_state: PyTree
    # The observation the next act reads, carried beside the env state
    # only where the state does not hold it: () for an env that does
    # (envs/base.py ``observe``) — no second buffer of the same bytes.
    obs: PyTree
    # What the actor holds between steps, leaves [B, ...] (an LSTM's
    # (c, h)); () for a feed-forward network (agents/agent.py).
    actor_carry: PyTree
    replay: PyTree         # the ring's state (replay/device_ring.py)
    learner: LearnerState
    rng: Array             # single key; shape [1] key array in SPMD mode
    iteration: Array       # scalar int32 — env vector steps taken
    # Per-env episode trackers and chunk-level accumulators.
    ep_return: Array       # [B]
    completed_return: Array  # scalar float32 — sum of finished-episode returns
    completed_count: Array   # scalar float32
    loss_sum: Array
    train_count: Array
    # {name: sum over the chunk's grad steps} of the agent's further
    # train-step metrics (agents/agent.py ``chunk_metrics``); {} for none
    agent_sums: PyTree = {}


def twin_obs_checkpoint(env: JaxEnv, tree):
    """For a whole-carry checkpoint (``--checkpoint-replay``) written while
    the carry held the observation twice, ``obs`` beside the env state's
    own copy, both as the observation itself (before PERF.md §6, PR 41):
    ``(example, adopt)`` for ``TrainCheckpointer.restore_latest`` — what
    that program saved, given ``tree``, what this one saves; and how its
    tree restored becomes this one's: the twin is dropped, the state's
    copy put in the form the env holds it in now. None where the carry
    still holds ``obs``."""
    if "obs" in tree:
        return None
    field = env.obs_field
    held = getattr(tree["env_state"], field)
    obs = jax.eval_shape(env.stack_obs, held)
    saved = jax.ShapeDtypeStruct(obs.shape, obs.dtype,
                                 sharding=getattr(held, "sharding", None))
    example = dict(tree, obs=saved,
                   env_state=tree["env_state"]._replace(**{field: saved}))

    def adopt(restored):
        state = restored["env_state"]
        return dict(
            {k: v for k, v in restored.items() if k != "obs"},
            env_state=state._replace(
                **{field: env.stack_held(getattr(state, field))}))

    return example, adopt


def fused_parts(cfg: ExperimentConfig, env: JaxEnv, net,
                axis_name: Optional[str] = None, num_shards: int = 1,
                member_hp: bool = False, member_lr: bool = False):
    """(agent, replay) the chunk program is built over — agents/agent.py,
    replay/device_ring.py — and the one place a combination that a part
    cannot serve is refused (honest-unsupported-surface gates: fail
    loudly, never train silently at other settings)."""
    agent = make_agent(
        net, cfg, axis_name=axis_name,
        tx=make_population_optimizer(cfg.learner) if member_lr else None)
    lane_state = jax.eval_shape(lambda: agent.initial_state(1))
    replay = make_device_ring(
        cfg, env, num_shards, lane_state,
        jax.eval_shape(agent.stored_state, lane_state))
    # The ISSUE 6 replay-ratio scan and the population's member axis exist
    # for the transition rings only (the --replay-ratio CLI flag is
    # warned-and-stripped by train.py before it gets here; this catches the
    # --set/config path). replay.train_batch IS honored: it widens the
    # sequence batch through shard_sizes.
    if replay.sequence and cfg.replay.updates_per_chunk != 1:
        raise ValueError(
            "replay.updates_per_chunk (the replay-ratio scan) is not "
            "supported by the recurrent R2D2 loop yet; leave it at 1 "
            "or use a feed-forward config")
    if replay.sequence and member_hp:
        raise ValueError(
            "--population is not supported by the recurrent (R2D2) fused "
            "loop yet (its sequence learner has no member axis)")
    return agent, replay


def make_fused_train(cfg: ExperimentConfig, env: JaxEnv, net,
                     axis_name: Optional[str] = None, num_shards: int = 1,
                     member_hp: bool = False, member_lr: bool = False):
    """Returns (init, run_chunk): ``run_chunk(carry, num_iters)`` executes
    ``num_iters`` fused iterations and reports aggregated metrics. The ONE
    chunk program of the fused runtime: feed-forward or recurrent agent,
    uniform, prioritized or sequence ring (``fused_parts``).

    With ``axis_name`` set the returned functions are per-device bodies to be
    wrapped in ``shard_map`` (parallel/learner.py); all sizes below become
    per-shard sizes and chunk metrics are psum-reduced to global values.

    With ``member_hp`` set (the population plane, ISSUE 20) the returned
    functions become the PER-MEMBER bodies population.py vmaps over the
    member axis: ``init(rng, hp)`` / ``run_chunk(carry, hp, num_iters)``
    take a :class:`MemberHP` of traced scalars, epsilon decays through
    ``loop_common.make_member_epsilon`` (bit-identical to the solo
    schedule per member) and ``hp.gamma`` threads into the n-step fold
    at sample time. ``member_lr`` additionally swaps the optimizer for
    :func:`make_population_optimizer` and seeds each member's
    ``hp.lr`` into its opt_state. ``member_hp=False`` (every existing
    caller) compiles the EXACT pre-knob program.
    """
    spmd = axis_name is not None
    agent, replay = fused_parts(cfg, env, net, axis_name, num_shards,
                                member_hp, member_lr)
    # Replay-ratio engine (ISSUE 6): each train event scans
    # updates_per_train * updates_per_chunk grad sub-steps over
    # independently-drawn batches. At ratio 1 the scan length and the
    # key stream are exactly the pre-knob program's — bit-identical,
    # pinned by tests/test_replay_ratio.py.
    replay_ratio = loop_common.resolve_replay_ratio(cfg)
    updates = cfg.updates_per_train * replay_ratio
    # PER write-backs defer to ONE last-wins flush per event when the
    # ratio engine is on (sub-steps sample event-entry priorities; the
    # host loops' prio_writeback_batch lag contract). Ratio 1 keeps the
    # in-scan sequential updates — the bit-identity contract.
    defer_writeback = replay.update_batched is not None and replay_ratio > 1
    _cast_actor, _actor_split = loop_common.make_actor_param_cast(
        cfg.network.actor_dtype)
    B, _ = loop_common.shard_sizes(cfg, num_shards)
    epsilon, beta_at = loop_common.make_schedules(cfg, B, num_shards)
    eps_member = (loop_common.make_member_epsilon(cfg, B, num_shards)
                  if member_hp else None)
    _split_rng = loop_common.make_rng_splitter(spmd)
    # Acting and the insert read the observation AS HELD: words for four
    # uint8 frames, with the one relayout both share (envs/base.py
    # StackWords) — the network its batch-minor stack, the ring its rows
    # or the newest frame (replay/device_ring.py).
    obs_words = held_in_words(env)

    def init(rng: Array, hp: Optional[MemberHP] = None) -> TrainCarry:
        base = rng
        if spmd:
            # Per-device rng stream for envs/exploration; the learner init
            # below must stay identical across devices, so its key comes
            # from the unfolded base key.
            rng = jax.random.fold_in(rng, jax.lax.axis_index(axis_name))
        k_env, k_learn, k_run = jax.random.split(rng, 3)
        if spmd:
            k_learn = jax.random.fold_in(base, 7)
        env_state, obs = env.v_reset(k_env, B)
        replay_state = replay.init(obs)
        # The learner inits on the full stacked obs of one lane.
        learner = agent.init_learner(k_learn,
                                     jax.tree.map(lambda x: x[0], obs))
        if member_lr:
            learner = set_member_lr(learner, hp.lr)
        zero = jnp.float32(0.0)
        # Envs may return obs aliasing their own state (e.g. CartPole's
        # phys vector); the carry is donated, so every leaf must be distinct.
        obs = (jax.tree.map(jnp.copy, obs)
               if env.observe(env_state) is None else ())
        return TrainCarry(env_state=env_state, obs=obs,
                          actor_carry=agent.initial_state(B),
                          replay=replay_state, learner=learner,
                          rng=k_run[None] if spmd else k_run,
                          iteration=jnp.int32(0),
                          ep_return=jnp.zeros((B,), jnp.float32),
                          completed_return=zero, completed_count=zero,
                          loss_sum=zero, train_count=zero,
                          agent_sums={k: zero for k in agent.chunk_metrics})

    def one_iteration(actor_params, hp, carry: TrainCarry, _
                      ) -> Tuple[TrainCarry, None]:
        rng, (k_act, k_sample) = _split_rng(carry.rng, 2)
        # Population members decay epsilon through the traced-constant
        # twin of the same schedule (bit-identical per member).
        eps = (eps_member(carry.iteration, hp.eps_delta, hp.eps_end)
               if member_hp else epsilon(carry.iteration))
        gamma = hp.gamma if member_hp else cfg.learner.gamma
        # Dtype split (ISSUE 6): with actor_dtype="bfloat16" the actor
        # reads the bf16 snapshot cast once at chunk entry; otherwise
        # the live fp32 learner params, exactly the pre-split program.
        acting_params = (actor_params if actor_params is not None
                         else carry.learner.params)
        held = env.observe(carry.env_state)
        obs = carry.obs if held is None else held
        # Stage names (telemetry/stages.py STAGES): trace metadata only.
        with jax.named_scope("act"):
            if obs_words:
                obs = StackWords(held, words_split(held))
            acting_obs = (split_stack(obs.split, env.observation_shape[:-1])
                          if obs_words else obs)
            actor_carry, actions = agent.act(
                acting_params, carry.actor_carry, acting_obs, k_act, eps)
        with jax.named_scope("env"):
            env_state, out = env.v_step(carry.env_state, actions)
        with jax.named_scope("insert"):
            replay_state = replay.add(
                carry.replay, obs, actions, out,
                agent.stored_state(carry.actor_carry))
        beta = beta_at(carry.iteration)

        def do_train(operand):
            learner, rep = operand

            def one_update(c, key):
                l, rep = c
                s = replay.sample(rep, key, gamma, beta)
                l, metrics = agent.train_step(l, s)
                if defer_writeback:
                    # Replay-ratio scan: stack this sub-step's draw
                    # + |TD| plane as scan outputs; ONE last-wins
                    # flush lands them after the scan.
                    return (l, rep), (metrics["loss"], s.t_idx,
                                      s.b_idx, metrics["priorities"])
                rep = replay.update(rep, s, metrics["priorities"])
                return (l, rep), (metrics["loss"],
                                  {k: metrics[k]
                                   for k in agent.chunk_metrics})

            with jax.named_scope("sample"):
                keys = jax.random.split(k_sample, updates)
            (learner, rep), ys = jax.lax.scan(one_update,
                                              (learner, rep), keys)
            if defer_writeback:
                losses_u, t_i, b_i, prios = ys
                rep = replay.update_batched(rep, t_i, b_i, prios)
                further = {}
            else:
                losses_u, further = ys
            return (learner, rep, jnp.sum(losses_u), jnp.float32(updates),
                    jax.tree.map(jnp.sum, further))

        def no_train(operand):
            learner, rep = operand
            return (learner, rep, jnp.float32(0.0), jnp.float32(0.0),
                    {k: jnp.float32(0.0) for k in carry.agent_sums})

        learner, replay_state, loss, trained, further = jax.lax.cond(
            jnp.logical_and(replay.can_sample(replay_state),
                            carry.iteration % cfg.train_every == 0),
            do_train, no_train, (carry.learner, replay_state))

        done = jnp.logical_or(out.terminated, out.truncated)
        ep_return, completed_return, completed_count = \
            loop_common.episode_stats_update(carry, out.reward, done)

        return TrainCarry(
            env_state=env_state, obs=out.obs if held is None else (),
            actor_carry=agent.reset_state(actor_carry, done),
            replay=replay_state, learner=learner,
            rng=rng, iteration=carry.iteration + 1, ep_return=ep_return,
            completed_return=completed_return,
            completed_count=completed_count,
            loss_sum=carry.loss_sum + loss,
            train_count=carry.train_count + trained,
            agent_sums={k: v + further[k]
                        for k, v in carry.agent_sums.items()}), None

    def _run_chunk(carry: TrainCarry, hp, num_iters: int):
        zero = jnp.float32(0.0)
        carry = carry._replace(completed_return=zero, completed_count=zero,
                               loss_sum=zero, train_count=zero,
                               agent_sums={k: zero
                                           for k in carry.agent_sums})
        # Actor-dtype split: cast the chunk-entry params ONCE; the cast
        # tree is scan-invariant (closed over), so XLA keeps a single
        # bf16 copy for the whole chunk instead of re-casting per step.
        actor_params = (_cast_actor(carry.learner.params)
                        if _actor_split else None)
        carry, _ = jax.lax.scan(
            lambda c, x: one_iteration(actor_params, hp, c, x),
            carry, None, length=num_iters)
        metrics, replace = loop_common.reduce_chunk_metrics(
            carry, axis_name, B, num_shards)
        if spmd and replay.prioritized:
            # Keep the new-item priority seed replicated (global max).
            replace["replay"] = carry.replay._replace(
                max_priority=jax.lax.pmax(carry.replay.max_priority,
                                          axis_name))
        if replace:
            carry = carry._replace(**replace)
        return carry, metrics

    def run_chunk(carry: TrainCarry, num_iters: int):
        """Run ``num_iters`` iterations; returns (carry, summary metrics).

        Chunk accumulators are zeroed on entry and (in SPMD mode) psum-
        reduced into the reported metrics, then zeroed in the returned carry
        so every accumulator leaf stays replicated across devices.
        """
        return _run_chunk(carry, None, num_iters)

    def run_member_chunk(carry: TrainCarry, hp: MemberHP, num_iters: int):
        """Per-member chunk body for the population vmap: identical to
        ``run_chunk`` with member hyperparameters threaded through."""
        return _run_chunk(carry, hp, num_iters)

    if member_hp:
        return init, run_member_chunk
    return init, run_chunk


def make_evaluator(cfg: ExperimentConfig, env: JaxEnv, net,
                   num_episodes: int = 10, epsilon: float = 0.001):
    """Greedy-policy evaluation: one episode per vmapped env instance,
    the agent's actor state threaded (and reset on done) as in training.

    Runs ``env.max_steps`` steps under a mask that freezes each env at its
    first episode end; returns mean undiscounted return.
    """
    agent = make_agent(net, cfg)

    def evaluate(params: PyTree, rng: Array) -> Array:
        k_reset, k_run = jax.random.split(rng)
        env_state, obs = env.v_reset(k_reset, num_episodes)

        def step(carry, _):
            env_state, obs, state, ret, alive, rng = carry
            rng, k = jax.random.split(rng)
            state, a = agent.act(params, state, obs, k, jnp.float32(epsilon))
            env_state, out = env.v_step(env_state, a)
            ret = ret + out.reward * alive
            done = jnp.logical_or(out.terminated, out.truncated)
            state = agent.reset_state(state, done)
            alive = jnp.logical_and(alive > 0, ~done).astype(jnp.float32)
            return (env_state, out.obs, state, ret, alive, rng), None

        init = (env_state, obs, agent.initial_state(num_episodes),
                jnp.zeros((num_episodes,), jnp.float32),
                jnp.ones((num_episodes,), jnp.float32), k_run)
        carry, _ = jax.lax.scan(step, init, None, length=env.max_steps)
        return jnp.mean(carry[3])

    return evaluate
