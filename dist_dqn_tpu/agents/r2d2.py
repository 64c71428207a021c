"""R2D2 sequence learner: LSTM unroll with burn-in, one jit program.

The recurrent half of the driver's capability list (BASELINE.json:10):
sequence replay batches flow through stored-state burn-in, an unrolled
double-Q n-step loss with value rescaling, and the eta-mixed per-sequence
priorities of Kapturowski et al. (2019) — all traced, with the optimizer
update and target sync, into one XLA program like the feed-forward learner
(agents/dqn.py, BASELINE.json:5).

Burn-in: the first ``burn_in`` steps are unrolled from the stored actor
carry purely to refresh the hidden state (stop-gradient, online and target
nets each with their own parameters); the loss covers the next
``unroll_length`` steps; the final ``n_step`` steps exist only as the
within-window bootstrap region. Episode boundaries inside a window are
handled exactly: the cell re-zeroes its carry on the stored reset flags and
n-step returns stop at dones (truncation treated as terminal, matching the
pixel ring's bootstrap semantics — replay/device.py).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import optax

from dist_dqn_tpu.agents.dqn import LearnerState, make_optimizer
from dist_dqn_tpu.config import LearnerConfig, ReplayConfig
from dist_dqn_tpu.ops import losses
from dist_dqn_tpu.types import PyTree, SequenceSample

Array = jnp.ndarray


def make_r2d2_learner(net, cfg: LearnerConfig, rcfg: ReplayConfig,
                      axis_name: Optional[str] = None):
    """Build (init, train_step) for a RecurrentQNetwork over sequences.

    train_step(state, sample: SequenceSample) -> (state, metrics); metrics
    includes per-sequence ``priorities`` [S]. With ``axis_name`` set,
    gradients are pmean-ed across the learner mesh axis (the NCCL-allreduce
    replacement, BASELINE.json:5).
    """
    burn = rcfg.burn_in
    unroll = rcfg.unroll_length
    n = cfg.n_step
    eta = rcfg.priority_mix
    if unroll <= 0:
        raise ValueError("R2D2 learner needs replay.unroll_length > 0")
    if cfg.munchausen:
        raise ValueError(
            "munchausen targets are implemented on the feed-forward "
            "scalar head only (agents/dqn.py); unset munchausen or "
            "lstm_size")

    tx = make_optimizer(cfg)

    def init(rng: Array, obs_example: Array) -> LearnerState:
        rng, k_param = jax.random.split(rng)
        carry = net.initial_state(1)
        obs_tb = obs_example[None, None]            # [T=1, B=1, ...]
        params = net.init(k_param, carry, obs_tb, method=net.unroll)
        return LearnerState(
            params=params,
            target_params=jax.tree.map(jnp.copy, params),
            opt_state=tx.init(params),
            steps=jnp.int32(0),
            rng=rng,
        )

    def _unrolled_q(params: PyTree, sample: SequenceSample,
                    unroll_pass) -> Tuple[Array, dict]:
        """Burn in (stop-grad) then unroll the loss+bootstrap region.

        Returns q over steps [burn, burn+unroll+n): [unroll+n, S, A], and
        what the network's layers sowed into the ``routing`` collection in
        that region (models/sequence_core.py: an expert layer's counters;
        empty for a network that sows none).

        Each region is entered under its pass name (telemetry/stages.py
        PASSES, children of stage ``loss_grad``): ``burn_in`` for either
        network, then ``unroll_pass`` — the caller's ``named_scope`` for
        this network's loss+bootstrap region.

        The two regions are row ranges of the FLAT ``[L*B, ...]`` batch,
        handed on in the ``[T, B, ...]`` shape ``net.unroll`` flattens
        again: the same values as ``obs[:burn]`` / ``obs[burn:]``, but the
        torso then reads the sampler's batch-minor stacks where they were
        written — a slice on the time axis of ``[L, B, ...]`` put a cast
        and two relayouts of every frame between them (PERF.md, PR 31).

        Three of the four regions keep nothing for a backward (either
        network's burn-in, the target's unroll); the torso holds its
        convolutions' outputs in every one (models/recurrent.py
        ``_HeldCNNTorso``), or the compiler nests conv1 in conv2's kernel
        there, a third slower than apart (PERF.md §7.8).
        """
        obs = sample.obs
        B = obs.shape[1]
        flat = obs.reshape((-1,) + obs.shape[2:])

        def steps(lo, hi):
            return flat[lo * B:hi * B].reshape((hi - lo,) + obs.shape[1:])

        # the stored pair, or an empty state for a core that stores none
        carry = net.window_state(sample.start_state, B, burn)
        if burn:
            with jax.named_scope("burn_in"):
                carry, _ = net.apply(params, carry, steps(0, burn),
                                     sample.reset[:burn], method=net.unroll)
                carry = jax.lax.stop_gradient(carry)
        with unroll_pass:
            (_, q), sown = net.apply(
                params, carry, steps(burn, obs.shape[0]),
                sample.reset[burn:], method=net.unroll, mutable=["routing"])
        return q, sown.get("routing", {})

    def loss_fn(params: PyTree, target_params: PyTree,
                sample: SequenceSample) -> Tuple[Array, Tuple]:
        # [unroll+n, S, A] each
        q_online, routing = _unrolled_q(params, sample,
                                        jax.named_scope("online_unroll"))
        q_target, _ = _unrolled_q(target_params, sample,
                                  jax.named_scope("target_unroll"))

        # Per-step n-step returns inside the window; d_t = gamma*(1 - done_t)
        # zeroes everything past an episode end (and the bootstrap with it).
        r = sample.reward[burn:]                        # [unroll+n, S]
        d = cfg.gamma * (1.0 - sample.done[burn:].astype(jnp.float32))
        acc_r = jnp.zeros_like(r[:unroll])
        acc_d = jnp.ones_like(acc_r)
        for j in range(n):
            acc_r = acc_r + acc_d * r[j:j + unroll]
            acc_d = acc_d * d[j:j + unroll]

        boot_online = q_online[n:n + unroll]            # q at step k+n
        boot_target = q_target[n:n + unroll]
        selector = boot_online if cfg.double_dqn else boot_target
        a_star = jnp.argmax(selector, axis=-1)
        boot = jnp.take_along_axis(boot_target, a_star[..., None],
                                   axis=-1)[..., 0]
        if cfg.value_rescale:
            boot = losses.inv_value_rescale(boot)
        target = acc_r + acc_d * boot
        if cfg.value_rescale:
            target = losses.value_rescale(target)

        qa = jnp.take_along_axis(
            q_online[:unroll],
            sample.action[burn:burn + unroll, :, None].astype(jnp.int32),
            axis=-1)[..., 0]
        td = qa - jax.lax.stop_gradient(target)         # [unroll, S]
        per_step = losses.huber(td, cfg.huber_delta)
        per_seq = jnp.mean(per_step, axis=0)            # [S]
        loss = jnp.mean(sample.weights * per_seq)

        abs_td = jnp.abs(td)
        priorities = (eta * jnp.max(abs_td, axis=0)
                      + (1.0 - eta) * jnp.mean(abs_td, axis=0))
        aux = (jax.lax.stop_gradient(priorities),
               jax.lax.stop_gradient(jnp.mean(per_seq)),
               jax.lax.stop_gradient(_routing_counters(routing)))
        return loss, aux

    def train_step(state: LearnerState, sample: SequenceSample
                   ) -> Tuple[LearnerState, dict]:
        # Stage names (telemetry/stages.py STAGES), as agents/dqn.py
        # enters them: trace metadata only.
        with jax.named_scope("loss_grad"):
            rng, _ = jax.random.split(state.rng)
            (loss, (priorities, raw_loss, routing)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(state.params, state.target_params,
                                       sample)
        if axis_name is not None:
            with jax.named_scope("allreduce"):
                grads = jax.lax.pmean(grads, axis_name)
                loss = jax.lax.pmean(loss, axis_name)
                raw_loss = jax.lax.pmean(raw_loss, axis_name)
        with jax.named_scope("optimizer"):
            updates, opt_state = tx.update(grads, state.opt_state,
                                           state.params)
            params = optax.apply_updates(state.params, updates)
        steps = state.steps + 1

        with jax.named_scope("target_sync"):
            if cfg.target_tau > 0.0:
                target_params = jax.tree.map(
                    lambda t, p: t + cfg.target_tau * (p - t),
                    state.target_params, params)
            else:
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
            **routing,
        }
        return new_state, metrics

    return init, train_step


#: The train step's routing counters (a network with expert layers only),
#: over the online network's loss + bootstrap region: the share of the
#: tokens' expert choices that fall on experts this chip holds (mean over
#: the expert layers), and the busiest held expert's load over the held
#: experts' mean (the worst layer).
ROUTING_COUNTERS = ("routing_held_share", "routing_busiest_over_mean")


def _routing_counters(sown: dict) -> dict:
    """``ROUTING_COUNTERS`` from what the expert layers sowed; {} where
    no layer sowed anything."""
    by_name = {}
    for path, value in jax.tree_util.tree_leaves_with_path(sown):
        by_name.setdefault(path[-2].key, []).append(value)
    if not by_name:
        return {}
    return {
        "routing_held_share": jnp.mean(jnp.stack(by_name["held_share"])),
        "routing_busiest_over_mean": jnp.max(
            jnp.stack(by_name["busiest_over_mean"]))}


def make_recurrent_actor_step(net, return_q: bool = False):
    """Epsilon-greedy acting for the recurrent net, carry threaded by caller.

    act(params, carry, obs, rng, epsilon) -> (new_carry, actions [B]).
    The caller zeroes the carry on episode ends before the next call (the
    fused loop does this right after env.step), so no reset flags here.

    With ``return_q`` the step also yields (q_sel, q_max) [B] float32 — the
    Q-value of the action actually taken and the greedy value. The Ape-X
    service records these per step so freshly assembled sequences enter
    replay with real inference-time TD priorities (the R2D2 actor-side
    seeding rule) instead of the running max, at zero extra device passes.
    """

    def act(params: PyTree, carry, obs: Array, rng: Array, epsilon: Array):
        k_eps, k_rand = jax.random.split(rng)
        carry, q = net.apply(params, carry, obs)
        greedy = jnp.argmax(q, axis=-1).astype(jnp.int32)
        random_a = jax.random.randint(k_rand, greedy.shape, 0,
                                      net.num_actions)
        explore = jax.random.uniform(k_eps, greedy.shape) < epsilon
        actions = jnp.where(explore, random_a, greedy)
        if not return_q:
            return carry, actions
        q32 = q.astype(jnp.float32)
        q_sel = jnp.take_along_axis(q32, actions[:, None].astype(jnp.int32),
                                    axis=-1)[:, 0]
        return carry, actions, q_sel, jnp.max(q32, axis=-1)

    return act
