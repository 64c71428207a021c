"""The DQN-family learner: one jit-compiled train step for every head type.

Covers the driver's single-jit requirement (BASELINE.json:5): Q-net forward,
TD loss (scalar or C51), backward, optimizer update and target-network Polyak
sync are all traced into one XLA program; ``donate_argnums`` lets XLA update
parameters and optimizer state in place on device.

The same ``train_step`` serves vanilla DQN, double-DQN, dueling, NoisyNet,
C51, QR-DQN and IQN (BASELINE.json:7-9,11) — the variant is fixed at trace
time by the
network module and ``LearnerConfig``, so there is zero runtime dispatch in the
compiled program. Per-example TD magnitudes are always returned as
``priorities`` for the prioritized replay path (Ape-X, BASELINE.json:9).
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax

from dist_dqn_tpu.config import LearnerConfig
from dist_dqn_tpu.ops import losses
from dist_dqn_tpu.types import PyTree, Transition

Array = jnp.ndarray


class LearnerState(NamedTuple):
    params: PyTree
    target_params: PyTree
    opt_state: PyTree
    steps: Array  # scalar int32 — completed gradient steps
    rng: Array    # for NoisyNet noise draws inside the train step


def _apply(net: nn.Module, params: PyTree, obs: Array, rng: Optional[Array],
           add_noise: bool) -> Array:
    rngs = {"noise": rng} if (add_noise and rng is not None) else None
    return net.apply(params, obs, add_noise=add_noise, rngs=rngs)


def make_optimizer(cfg: LearnerConfig) -> optax.GradientTransformation:
    """Shared optimizer factory for the feed-forward and R2D2 learners.

    Builds clip-by-global-norm + Adam, with the learning rate either
    constant or annealed per ``cfg.lr_schedule`` over grad steps. The
    schedule rides optax's own step counter inside the optimizer state,
    so it checkpoints/resumes with the rest of the learner state.
    """
    if cfg.lr_schedule == "constant":
        lr = cfg.learning_rate
    elif cfg.lr_schedule in ("linear", "cosine"):
        if cfg.lr_decay_steps <= 0:
            raise ValueError(
                f"lr_schedule={cfg.lr_schedule!r} needs lr_decay_steps > 0 "
                "(the grad-step horizon the anneal spans)")
        if cfg.lr_schedule == "linear":
            lr = optax.linear_schedule(
                init_value=cfg.learning_rate, end_value=cfg.lr_end_value,
                transition_steps=cfg.lr_decay_steps)
        else:
            if cfg.learning_rate <= 0:
                raise ValueError(
                    "lr_schedule='cosine' needs learning_rate > 0 (the "
                    "decay floor is expressed as the ratio "
                    "lr_end_value / learning_rate)")
            lr = optax.cosine_decay_schedule(
                init_value=cfg.learning_rate, decay_steps=cfg.lr_decay_steps,
                alpha=cfg.lr_end_value / cfg.learning_rate)
    else:
        raise ValueError(
            f"unknown lr_schedule {cfg.lr_schedule!r}; "
            "expected one of: constant, linear, cosine")
    tx_parts = []
    if cfg.max_grad_norm:
        tx_parts.append(optax.clip_by_global_norm(cfg.max_grad_norm))
    tx_parts.append(optax.adam(lr, eps=cfg.adam_eps))
    return optax.chain(*tx_parts)


def make_population_optimizer(cfg: LearnerConfig
                              ) -> optax.GradientTransformation:
    """Optimizer for the vmap-stacked population learner (ISSUE 20).

    Same clip+Adam chain as :func:`make_optimizer`, but built through
    ``optax.inject_hyperparams`` so the learning rate lives in the
    optimizer STATE — a per-member [M] leaf under ``jax.vmap`` instead
    of a trace-time constant. :func:`set_member_lr` writes member k's
    rate into a freshly-initialized state; every subsequent update reads
    it back as a traced scalar. The injected Adam applies bit-identically
    to ``make_optimizer``'s at the same rate (the member-independence
    pin, tests/test_population.py), so a population member matches a
    solo run exactly.

    Per-member rates compose with ``lr_schedule="constant"`` only: the
    annealed schedules close over their horizon at trace time, and a
    per-member horizon is a different axis than a per-member rate.
    """
    if cfg.lr_schedule != "constant":
        raise ValueError(
            f"population per-member learning rates require "
            f"lr_schedule='constant', got {cfg.lr_schedule!r} (the "
            "anneal horizon is a trace-time constant, not a stackable "
            "member axis)")

    def _build(learning_rate):
        tx_parts = []
        if cfg.max_grad_norm:
            tx_parts.append(optax.clip_by_global_norm(cfg.max_grad_norm))
        tx_parts.append(optax.adam(learning_rate, eps=cfg.adam_eps))
        return optax.chain(*tx_parts)

    return optax.inject_hyperparams(_build)(
        learning_rate=cfg.learning_rate)


def set_member_lr(state: LearnerState, lr: Array) -> LearnerState:
    """Write a (traced) per-member learning rate into an opt_state built
    by :func:`make_population_optimizer` — called inside the vmapped
    population init, where ``lr`` is member k's scalar."""
    opt = state.opt_state
    hyper = dict(opt.hyperparams)
    hyper["learning_rate"] = jnp.asarray(lr, jnp.float32)
    return state._replace(opt_state=opt._replace(hyperparams=hyper))


def make_learner(net: nn.Module, cfg: LearnerConfig,
                 axis_name: Optional[str] = None,
                 tx: Optional[optax.GradientTransformation] = None):
    """Build (init, train_step) for a feed-forward Q-network.

    train_step(state, batch, weights) -> (state, metrics); metrics includes
    ``priorities`` [B] for replay priority updates.

    With ``axis_name`` set, the step is a *distributed data-parallel learner*
    meant to run under ``shard_map`` over that mesh axis: gradients (and the
    scalar loss) are ``pmean``-ed across learners — the TPU-native
    equivalent of the reference's multi-learner NCCL allreduce
    (BASELINE.json:5) — so every learner applies the same averaged
    gradient (replicas stay consistent) while each consumes its own
    replay shard's batch. The sharded step is numerically equivalent to
    the single-device full-batch step (rtol 2e-5 — cross-shard pmean
    reorders the reduction, so exact bit-equality is not expected;
    tests/test_distributed.py).

    ``tx`` overrides the optimizer (default :func:`make_optimizer`) —
    the population path passes :func:`make_population_optimizer` so the
    learning rate is a per-member state leaf.
    """
    if tx is None:
        tx = make_optimizer(cfg)

    num_atoms = getattr(net, "num_atoms", 1)
    quantile = num_atoms > 1 and getattr(net, "quantile", False)
    distributional = num_atoms > 1 and not quantile
    noisy = getattr(net, "noisy", False)
    iqn = getattr(net, "iqn", False)
    if cfg.munchausen and (distributional or quantile or iqn):
        raise ValueError(
            "munchausen targets are scalar-head only; unset munchausen "
            "or use a non-distributional network")
    if cfg.munchausen and cfg.value_rescale:
        raise ValueError(
            "munchausen and value_rescale both transform the target; "
            "set only one")
    if cfg.munchausen and cfg.n_step != 1:
        raise ValueError(
            "munchausen requires n_step=1: replay folds n-step rewards "
            "at sample time, so the per-step log-policy bonuses the "
            "soft recursion needs cannot be applied for n_step > 1")
    if cfg.munchausen and cfg.double_dqn:
        raise ValueError(
            "munchausen replaces the max/double-Q bootstrap with the "
            "tau-logsumexp soft bootstrap, so double_dqn has no effect; "
            "set double_dqn=False (the mdqn preset does)")

    def init(rng: Array, obs_example: Array) -> LearnerState:
        rng, k_param, k_noise = jax.random.split(rng, 3)
        obs_b = jnp.expand_dims(obs_example, 0)
        params = net.init({"params": k_param, "noise": k_noise}, obs_b,
                          add_noise=noisy)
        return LearnerState(
            params=params,
            # Distinct buffers: params and target_params are donated together
            # by the fused loop, and XLA rejects aliased donated inputs.
            target_params=jax.tree.map(jnp.copy, params),
            opt_state=tx.init(params),
            steps=jnp.int32(0),
            rng=rng,
        )

    def loss_fn(params: PyTree, target_params: PyTree, batch: Transition,
                weights: Array, rng: Array) -> Tuple[Array, Tuple]:
        k_online, k_next, k_target = jax.random.split(rng, 3)
        if distributional:
            logits = _apply(net, params, batch.obs, k_online, noisy)
            logits_next_online = _apply(net, params, batch.next_obs, k_next,
                                        noisy)
            logits_next_target = _apply(net, target_params, batch.next_obs,
                                        k_target, noisy)
            atoms = net.atoms()
            # Non-double = the same selection with the target net picking
            # its own greedy action.
            selector = (logits_next_online if cfg.double_dqn
                        else logits_next_target)
            next_probs = losses.categorical_double_q_probs(
                selector, logits_next_target, atoms)
            target_probs = losses.categorical_projection(
                atoms, next_probs, batch.reward, batch.discount)
            per_example = losses.categorical_td_loss(
                logits, batch.action, target_probs)
            priorities = per_example
        elif quantile:
            # QR-DQN (the second distributional family): quantile-Huber
            # regression against Bellman-mapped target quantile samples.
            theta = _apply(net, params, batch.obs, k_online, noisy)
            theta_next_target = _apply(net, target_params, batch.next_obs,
                                       k_target, noisy)
            if cfg.double_dqn:
                theta_next_online = _apply(net, params, batch.next_obs,
                                           k_next, noisy)
                selector = theta_next_online
            else:
                selector = theta_next_target
            next_theta = losses.quantile_double_q_select(
                selector, theta_next_target)                    # [B, N]
            target_theta = (batch.reward[:, None]
                            + batch.discount[:, None] * next_theta)
            theta_a = jnp.take_along_axis(
                theta, batch.action[:, None, None].astype(jnp.int32),
                axis=1)[:, 0]                                   # [B, N]
            per_example = losses.quantile_huber_td(
                theta_a, target_theta, cfg.huber_delta)
            priorities = per_example
        elif iqn:
            # IQN: quantile-Huber regression at SAMPLED fractions — N
            # online draws conditioned into the net, N' independent
            # target draws as Bellman samples (Dabney et al., 2018b).
            # Tau keys fold in each example's GLOBAL batch position so
            # the draws are bit-identical whether the batch is whole on
            # one device or row-sharded over the dp mesh — that lets the
            # sharded IQN step join the same numerical-equivalence test
            # (rtol 2e-5) as the deterministic heads (VERDICT round-3
            # ask #8; exact bit-equality is not expected — pmean
            # reorders the cross-shard reduction).
            local_b = batch.obs.shape[0]
            ids = jnp.arange(local_b, dtype=jnp.uint32)
            if axis_name is not None:
                ids = ids + (jax.lax.axis_index(axis_name)
                             .astype(jnp.uint32) * local_b)
            theta, taus = net.apply(
                params, batch.obs, net.num_tau, example_ids=ids,
                method=net.sample_quantiles, rngs={"tau": k_online})
            theta_next_target, _ = net.apply(
                target_params, batch.next_obs, net.num_tau_target,
                example_ids=ids,
                method=net.sample_quantiles, rngs={"tau": k_target})
            if cfg.double_dqn:
                # Greedy selection by the online net's deterministic
                # acting fractions (risk-neutral mean at eta=1).
                q_sel = net.apply(params, batch.next_obs,
                                  method=net.q_values)
            else:
                q_sel = jnp.mean(theta_next_target, axis=-1)
            a_star = jnp.argmax(q_sel, axis=-1)
            next_theta = jnp.take_along_axis(
                theta_next_target, a_star[:, None, None], axis=1)[:, 0]
            target_theta = (batch.reward[:, None]
                            + batch.discount[:, None] * next_theta)
            theta_a = jnp.take_along_axis(
                theta, batch.action[:, None, None].astype(jnp.int32),
                axis=1)[:, 0]                                   # [B, N]
            per_example = losses.iqn_quantile_huber_td(
                theta_a, taus, target_theta, cfg.huber_delta)
            priorities = per_example
        else:
            q = _apply(net, params, batch.obs, k_online, noisy)
            q_next_target = _apply(net, target_params, batch.next_obs,
                                   k_target, noisy)
            if cfg.munchausen:
                # M-DQN (Vieillard et al., 2020): soft bootstrap replaces
                # the max/double-Q bootstrap, and the clipped scaled
                # log-policy of the taken action (target net at the
                # STORED obs) is added to the reward.
                boot = losses.munchausen_soft_bootstrap(
                    q_next_target, cfg.munchausen_tau)
                q_obs_target = _apply(net, target_params, batch.obs,
                                      k_next, noisy)
                bonus = losses.munchausen_bonus(
                    q_obs_target, batch.action, cfg.munchausen_alpha,
                    cfg.munchausen_tau, cfg.munchausen_clip)
                target = batch.reward + bonus + batch.discount * boot
            else:
                if cfg.double_dqn:
                    q_next_online = _apply(net, params, batch.next_obs,
                                           k_next, noisy)
                    boot = losses.double_q_bootstrap(q_next_online,
                                                     q_next_target)
                else:
                    boot = jnp.max(q_next_target, axis=-1)
                if cfg.value_rescale:
                    boot = losses.inv_value_rescale(boot)
                target = batch.reward + batch.discount * boot
                if cfg.value_rescale:
                    target = losses.value_rescale(target)
            qa = jnp.take_along_axis(
                q, batch.action[:, None].astype(jnp.int32), axis=-1)[:, 0]
            td = qa - jax.lax.stop_gradient(target)
            per_example = losses.huber(td, cfg.huber_delta)
            priorities = jnp.abs(td)
        loss = jnp.mean(weights * per_example)
        aux = (jax.lax.stop_gradient(priorities),
               jax.lax.stop_gradient(jnp.mean(per_example)))
        return loss, aux

    def train_step(state: LearnerState, batch: Transition,
                   weights: Optional[Array] = None
                   ) -> Tuple[LearnerState, dict]:
        if weights is None:
            weights = jnp.ones_like(batch.reward)
        # Stage names (telemetry/stages.py STAGES): trace metadata only;
        # the backward ops keep theirs through transpose(jvp(...)).
        with jax.named_scope("loss_grad"):
            rng, k_loss = jax.random.split(state.rng)
            (loss, (priorities, raw_loss)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(state.params, state.target_params,
                                       batch, weights, k_loss)
        if axis_name is not None:
            # Gradient allreduce over the learner mesh axis (ICI collective).
            with jax.named_scope("allreduce"):
                grads = jax.lax.pmean(grads, axis_name)
                loss = jax.lax.pmean(loss, axis_name)
                raw_loss = jax.lax.pmean(raw_loss, axis_name)
                mean_gap = jax.lax.pmean(jnp.mean(priorities), axis_name)
        else:
            mean_gap = jnp.mean(priorities)
        with jax.named_scope("optimizer"):
            updates, opt_state = tx.update(grads, state.opt_state,
                                           state.params)
            params = optax.apply_updates(state.params, updates)
        steps = state.steps + 1

        with jax.named_scope("target_sync"):
            if cfg.target_tau > 0.0:
                # Soft Polyak sync every step (BASELINE.json:5).
                target_params = jax.tree.map(
                    lambda t, p: t + cfg.target_tau * (p - t),
                    state.target_params, params)
            else:
                # Periodic hard copy, branch-free under jit.
                do_sync = (steps % cfg.target_update_period) == 0
                target_params = jax.tree.map(
                    lambda t, p: jnp.where(do_sync, p, t),
                    state.target_params, params)

        new_state = LearnerState(params=params, target_params=target_params,
                                 opt_state=opt_state, steps=steps, rng=rng)
        metrics = {
            "loss": loss,
            "raw_loss": raw_loss,
            "priorities": priorities,
            "grad_norm": optax.global_norm(grads),
            "mean_q_target_gap": mean_gap,
        }
        return new_state, metrics

    return init, train_step


def make_scan_train(train_step: Callable, flatten: bool = True) -> Callable:
    """Fold N train sub-steps into ONE dispatched program (ISSUE 6).

    ``scan_train(state, batches, weights)`` scans ``train_step`` over a
    stacked batch pytree with leading sub-step axis N — the apex
    service's replay-ratio path: on a round-trip-priced device link one
    dispatch buys N grad steps, the same lever the fused loop gets from
    its in-chunk scan. Scanning the SAME train_step the serial path
    jits keeps the math identical (pinned by tests/test_replay_ratio
    .py: scan over N == N serial steps, bit-for-bit).

    Returned metrics keep the serial step's contract where the host
    consumes them: ``priorities`` flatten to [N*B] in sub-step order
    (chronological — what the batched last-wins write-back expects),
    ``loss``/``raw_loss``/``mean_q_target_gap`` are sub-step means, and
    ``grad_norm`` is the LAST sub-step's (the freshest divergence
    signal for the sentinel).

    ``flatten=False`` keeps priorities [N, B] instead: required when the
    scan runs data-parallel under ``shard_map`` (batch rows sharded on
    axis 1) — a per-shard flatten would concatenate device blocks, not
    sub-steps, so the HOST reshapes the global [N, B] to [N*B] instead
    (parallel/learner.py scan_train_step_specs).
    """

    def scan_train(state: LearnerState, batches: Transition,
                   weights: Array) -> Tuple[LearnerState, dict]:
        def body(s, xs):
            batch, w = xs
            s, m = train_step(s, batch, w)
            return s, (m["loss"], m["raw_loss"], m["priorities"],
                       m["grad_norm"], m["mean_q_target_gap"])

        state, (loss, raw, prios, gnorm, gap) = jax.lax.scan(
            body, state, (batches, weights))
        metrics = {
            "loss": jnp.mean(loss),
            "raw_loss": jnp.mean(raw),
            "priorities": prios.reshape(-1) if flatten else prios,
            "grad_norm": gnorm[-1],
            "mean_q_target_gap": jnp.mean(gap),
        }
        return state, metrics

    return scan_train


def make_actor_step(net: nn.Module, return_q: bool = False) -> Callable:
    """Epsilon-greedy acting on scalar Q-values (any head type).

    act(params, obs, rng, epsilon) -> actions [B]. With a NoisyNet head,
    exploration comes from parameter noise: pass epsilon=0 and noise is drawn
    per call from ``rng``.

    ``return_q=True`` also returns the inference-time Q planes —
    ``(actions, q_sel, q_max)`` with ``q_sel = Q(obs, action_taken)``
    (the TAKEN action, exploratory or greedy) and ``q_max = max_a Q`` —
    both f32. The zero-copy ingest path (ISSUE 9) ships these planes in
    the act reply so actors can echo them on their step frames and the
    learner seeds insertion priorities with zero extra dispatches (the
    feed-forward twin of the R2D2 ``return_q`` acting path).
    """
    noisy = getattr(net, "noisy", False)

    def act(params: PyTree, obs: Array, rng: Array, epsilon: Array):
        k_noise, k_eps, k_rand = jax.random.split(rng, 3)
        rngs = {"noise": k_noise} if noisy else None
        q = net.apply(params, obs, add_noise=noisy, rngs=rngs,
                      method=net.q_values)
        greedy = jnp.argmax(q, axis=-1).astype(jnp.int32)
        random_a = jax.random.randint(k_rand, greedy.shape, 0,
                                      net.num_actions)
        explore = jax.random.uniform(k_eps, greedy.shape) < epsilon
        actions = jnp.where(explore, random_a, greedy)
        if not return_q:
            return actions
        q_sel = jnp.take_along_axis(q, actions[:, None], axis=-1)[:, 0]
        return (actions, q_sel.astype(jnp.float32),
                jnp.max(q, axis=-1).astype(jnp.float32))

    return act
