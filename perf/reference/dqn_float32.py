"""Plain float32 reference of one DQN learner step.

The Nature-DQN family as published (Mnih et al. 2015; dueling streams of
Wang et al. 2016; double-Q of van Hasselt et al. 2016; importance weights of
Schaul et al. 2016; Adam with global-norm clipping as the presets state),
written in straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``. It imports nothing from
``dist_dqn_tpu``: the parameter tree is read by its key names only.

The layers come from ``plain.py``, where a convolution is written as what it
is: the windows of the input laid side by side, times the kernel as one
matrix. The batch is walked in blocks of ``ROW_BLOCK`` rows and the blocks'
losses and gradients summed, which is exact because every term of the loss
belongs to one row.
Both are for the compiler, not for the mathematics: XLA's float32
convolution backward at "highest" precision takes two minutes to compile
for a TPU, these matmuls seconds, and the window matrix of a whole batch
would not fit beside a cell's ring (PERF.md section 7).

Departures from the papers, all taken from the configuration as it is run:
the batch already holds the n-step return and ``gamma**n * (1 - done)`` as
``reward`` and ``discount`` (the program folds n steps when it samples), and
the optimizer is Adam, not RMSProp.

Besides the step it holds what ``perf/harness/reference_check.py`` asks of
every reference module (``perf/README.md``): which learner of the program
this reference stands beside (``make_program`` — the one place that names
it; the arithmetic above it imports nothing), a seeded batch in that
learner's layout, the tolerances, and the FLOPs of a grad step.
"""
from __future__ import annotations

from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from perf.reference.plain import (ADAM_B1, CONV_STRIDES, CONVS,  # noqa: F401
                                  adam_delta, clip_by_global_norm, cnn_torso,
                                  dense, global_norm, mlp_torso)

ROW_BLOCK = 32

# Largest error allowed for each quantity ``reference_check`` compares (its
# docstring defines them), by the dtype the configuration computes in.
#
# bfloat16 keeps 8 significant bits (2^-8 = 0.4% per rounding); through
# five layers, the loss and the backward pass the roundings add up. The
# bf16 bounds are at least three times the largest error over the seeded
# states of the study on the chip (PERF.md section 6, PR 23: 64 seeds for
# each configuration at its own widths; largest readings Q 0.94%, |TD|
# 0.94%, loss 1.2%, gradient 1.6%), rounded up; the optimizer's, float32
# against float32, read 1.8e-5 at most. A type with fewer bits fails them:
# with the program's weights rounded through float8 (e4m3, 4 significant
# bits) the Q-values are 4-7% off (perf/tests pins it). A wrong formula — no
# importance weights, a dropped dueling mean, another learning rate — moves
# loss, gradient or optimizer by tens of percent; double-Q against the
# plain maximum, at a state this close to initialisation, moves |TD| and
# loss by 1-9% depending on the seed, so that one is caught in most seeds
# and not in all. float32 configurations differ from the reference only by
# summation order.
TOLERANCES = {
    "bfloat16": {"q": 0.03, "priorities": 0.03, "loss": 0.04, "grad": 0.05,
                 "optimizer": 1e-3},
    "float32": {"q": 1e-4, "priorities": 1e-4, "loss": 1e-4, "grad": 1e-3,
                "optimizer": 1e-3},
}


class Hyper(NamedTuple):
    """What the step needs from the configuration (hashable: jit-static)."""

    torso: str
    dueling: bool
    double_dqn: bool
    huber_delta: float
    learning_rate: float
    adam_eps: float
    max_grad_norm: float


def q_values(params: Dict, obs, hp: Hyper):
    """[B, A] Q-values of the (dueling) Nature network in float32."""
    p = params["params"]
    x = obs.astype(jnp.float32)
    if obs.dtype == jnp.uint8:
        x = x / 255.0
    if hp.torso == "mlp":
        x = mlp_torso(p["MLPTorso_0"], x)
    else:
        x = cnn_torso(p["CNNTorso_0"], x, CONV_STRIDES[hp.torso])
    if "Dense_0" in p:
        x = jax.nn.relu(dense(p["Dense_0"], x))
    adv = dense(p["advantage"], x)
    if not hp.dueling:
        return adv
    val = dense(p["value"], x)
    return val + adv - jnp.mean(adv, axis=1, keepdims=True)


def _loss_sum(params, target_params, batch: Dict, weights, hp: Hyper):
    """Sum over the rows of ``weights * huber(TD)``; aux: |TD| and Q."""
    q = q_values(params, batch["obs"], hp)
    q_next_target = q_values(target_params, batch["next_obs"], hp)
    if hp.double_dqn:
        a_star = jnp.argmax(q_values(params, batch["next_obs"], hp), axis=-1)
        boot = jnp.take_along_axis(q_next_target, a_star[:, None],
                                   axis=-1)[:, 0]
    else:
        boot = jnp.max(q_next_target, axis=-1)
    target = jax.lax.stop_gradient(
        batch["reward"] + batch["discount"] * boot)
    qa = jnp.take_along_axis(q, batch["action"][:, None].astype(jnp.int32),
                             axis=-1)[:, 0]
    td = qa - target
    quad = jnp.minimum(jnp.abs(td), hp.huber_delta)
    huber = 0.5 * quad * quad + hp.huber_delta * (jnp.abs(td) - quad)
    return jnp.sum(weights * huber), (jnp.abs(td), q)


def _pull_sum(params, batch: Dict, pull, hp: Hyper):
    """Sum over the rows of ``pull * Q(obs, action)``: with ``pull`` the
    size of each row's ``d loss / d Q``, its gradient is what the loss's
    gradient would be if every row's TD error had the same sign."""
    q = q_values(params, batch["obs"], hp)
    qa = jnp.take_along_axis(q, batch["action"][:, None].astype(jnp.int32),
                             axis=-1)[:, 0]
    return jnp.sum(pull * qa)


def step(params, target_params, batch: Dict, hp: Hyper) -> Dict:
    """Loss and gradient of one learner step on a batch as ``seeded_batch``
    lays it out (its ``weights`` among the rows): Q-values of ``obs``, the mean
    weighted Huber loss, per-row |TD| (the priorities), the gradient's
    global norm, the gradient as the optimizer takes it (clipped to
    ``max_grad_norm``), and ``grad_scale``: the norm that gradient would
    have if no two rows' TD errors cancelled — the yardstick for an error of
    the gradient, which its own norm is not where the rows cancel."""
    weights = batch["weights"]
    batch = {k: v for k, v in batch.items() if k != "weights"}
    rows = batch["action"].shape[0]
    block = max(b for b in range(1, ROW_BLOCK + 1) if rows % b == 0)
    blocks = jax.tree.map(
        lambda x: x.reshape((rows // block, block) + x.shape[1:]),
        (batch, weights))

    def one_block(total, block_rows):
        rows_batch, rows_weights = block_rows
        (loss, (abs_td, q)), grads = jax.value_and_grad(
            _loss_sum, has_aux=True)(params, target_params, rows_batch,
                                     rows_weights, hp)
        pull = rows_weights * jnp.minimum(abs_td, hp.huber_delta)
        one_way = jax.grad(_pull_sum)(params, rows_batch, pull, hp)
        return (jax.tree.map(jnp.add, total, (loss, grads, one_way)),
                (abs_td, q))

    with jax.default_matmul_precision("highest"):
        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                             params)
        (loss, grads, one_way), (abs_td, q) = jax.lax.scan(
            one_block, (jnp.float32(0.0), zeros, zeros), blocks)
        loss = loss / rows
        grads = jax.tree.map(lambda g: g / rows, grads)
        grads, norm, scale = clip_by_global_norm(grads, hp.max_grad_norm)
    return {"q": q.reshape((rows,) + q.shape[2:]), "loss": loss,
            "priorities": abs_td.reshape(rows), "grad_norm": norm,
            "grads": grads,
            "grad_scale": scale * global_norm(one_way) / rows}


def hyper_from_config(cfg) -> Hyper:
    """Read the program's ``ExperimentConfig`` by attribute; refuse what this
    reference does not compute rather than compare against something else."""
    net, learner = cfg.network, cfg.learner
    unsupported = [name for name, on in (
        ("network.noisy", net.noisy), ("network.num_atoms", net.num_atoms > 1),
        ("network.iqn", net.iqn), ("network.lstm_size", net.lstm_size),
        ("learner.munchausen", learner.munchausen),
        ("learner.value_rescale", learner.value_rescale),
        ("learner.lr_schedule", learner.lr_schedule != "constant")) if on]
    if unsupported or net.torso not in ("mlp", *CONV_STRIDES):
        raise NotImplementedError(
            f"dqn_float32 does not cover {unsupported or net.torso}")
    return Hyper(torso=net.torso, dueling=bool(net.dueling),
                 double_dqn=bool(learner.double_dqn),
                 huber_delta=float(learner.huber_delta),
                 learning_rate=float(learner.learning_rate),
                 adam_eps=float(learner.adam_eps),
                 max_grad_norm=float(learner.max_grad_norm))


# -- what the harness asks of a reference module, beside the step -----------

def make_program(cfg, env, net):
    """The program's side of the comparison: ``init(key)`` and
    ``train_step(state, batch)`` of the learner ``train.train`` builds for
    this configuration (``agents/dqn.py make_learner``), and ``q_of(params,
    batch)``, the program's Q-values of ``obs``. ``batch`` is a
    ``seeded_batch``."""
    from dist_dqn_tpu.agents.dqn import make_learner
    from dist_dqn_tpu.types import Transition

    init, train_step = make_learner(net, cfg.learner)
    # made inside the caller's trace: a constant of its program, not a
    # buffer that stays on the device
    def example():
        return jnp.zeros(tuple(env.observation_shape),
                         np.dtype(env.observation_dtype))

    def program_step(state, batch):
        rows = {k: v for k, v in batch.items() if k != "weights"}
        return train_step(state, Transition(**rows), batch["weights"])

    return (lambda key: init(key, example()), program_step,
            lambda params, batch: net.apply(params, batch["obs"]))


def seeded_batch(seed: int, index: int, batch_size: int, cfg, env
                 ) -> Dict[str, np.ndarray]:
    """Batch ``index`` of ``seed`` in the learner's own layout: n-step
    ``reward``, ``discount = gamma**n * (1 - done)``, importance ``weights``
    in (0, 1] where the configuration samples by priority, ones elsewhere."""
    rng = np.random.default_rng([seed, index])
    obs_shape = tuple(env.observation_shape)
    gamma_n = cfg.learner.gamma ** cfg.learner.n_step

    def frames():
        if np.dtype(env.observation_dtype) == np.uint8:
            return rng.integers(0, 256, (batch_size, *obs_shape),
                                dtype=np.uint8)
        return rng.standard_normal((batch_size, *obs_shape)).astype(
            np.float32)

    return {
        "obs": frames(),
        "next_obs": frames(),
        "action": rng.integers(0, env.num_actions, batch_size).astype(
            np.int32),
        # One sign and larger than a fresh network's Q-values, so that the
        # rows' TD errors share a sign and their gradients add up: a sum
        # that cancels is small against its own rounding noise, and its
        # relative error says little. On both sides of huber_delta = 1.
        "reward": rng.choice([0.5, 1.0, 1.5, 2.0],
                             batch_size).astype(np.float32),
        "discount": (gamma_n * (rng.random(batch_size) > 0.05)).astype(
            np.float32),
        "weights": (rng.uniform(0.2, 1.0, batch_size)
                    if cfg.replay.prioritized
                    else np.ones(batch_size)).astype(np.float32),
    }


def grad_step_flops(cfg, env) -> float:
    """FLOPs one grad step of this configuration requires, from its shapes
    (``perf/reduce/flops.py``: the Nature CNN on ``batch_size`` transitions,
    whole mesh)."""
    from perf.reduce import flops

    if cfg.network.torso not in CONVS:
        raise NotImplementedError(
            f"dqn_float32 counts {sorted(CONVS)} torsos, not "
            f"{cfg.network.torso!r}")
    return flops.grad_step_flops(
        cfg.learner.batch_size, obs_shape=tuple(env.observation_shape),
        convs=CONVS[cfg.network.torso], hidden=cfg.network.hidden,
        num_actions=env.num_actions,
        dueling=bool(cfg.network.dueling),
        double_dqn=bool(cfg.learner.double_dqn))
