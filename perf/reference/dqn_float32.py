"""Plain float32 reference of one DQN learner step.

The Nature-DQN family as published (Mnih et al. 2015; dueling streams of
Wang et al. 2016; double-Q of van Hasselt et al. 2016; importance weights of
Schaul et al. 2016; Adam with global-norm clipping as the presets state),
written in straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``. It imports nothing from
``dist_dqn_tpu``: the parameter tree is read by its key names only.

A convolution is written as what it is: the windows of the input laid side
by side (strided slices), times the kernel as one matrix. The batch is
walked in blocks of ``ROW_BLOCK`` rows and the blocks' losses and gradients
summed, which is exact because every term of the loss belongs to one row.
Both are for the compiler, not for the mathematics: XLA's float32
convolution backward at "highest" precision takes two minutes to compile
for a TPU, these matmuls seconds, and the window matrix of a whole batch
would not fit beside a cell's ring (PERF.md section 7).

Departures from the papers, all taken from the configuration as it is run:
the batch already holds the n-step return and ``gamma**n * (1 - done)`` as
``reward`` and ``discount`` (the program folds n steps when it samples), and
the optimizer is Adam, not RMSProp.
"""
from __future__ import annotations

from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp

# (features, kernel, stride) per VALID conv; the kernels' own shapes come
# from the parameter tree, only the strides are not stored there.
CONV_STRIDES = {"nature": (4, 2, 1), "small": (4, 2)}
ADAM_B1, ADAM_B2 = 0.9, 0.999
ROW_BLOCK = 32


class Hyper(NamedTuple):
    """What the step needs from the configuration (hashable: jit-static)."""

    torso: str
    dueling: bool
    double_dqn: bool
    huber_delta: float
    learning_rate: float
    adam_eps: float
    max_grad_norm: float


def _dense(p: Dict, x):
    return x @ p["kernel"].astype(jnp.float32) + p["bias"].astype(
        jnp.float32)


def _conv_valid(x, kernel, stride: int):
    """VALID convolution of NHWC ``x`` with an HWIO ``kernel``: the
    ``kh * kw`` strided window slices side by side in the kernel's own
    (row, column, channel) order, times the kernel as a matrix."""
    kh, kw, cin, cout = kernel.shape
    ho = (x.shape[1] - kh) // stride + 1
    wo = (x.shape[2] - kw) // stride + 1
    windows = jnp.concatenate(
        [x[:, i:i + stride * (ho - 1) + 1:stride,
           j:j + stride * (wo - 1) + 1:stride, :]
         for i in range(kh) for j in range(kw)], axis=-1)
    return windows @ kernel.reshape(kh * kw * cin, cout)


def q_values(params: Dict, obs, hp: Hyper):
    """[B, A] Q-values of the (dueling) Nature network in float32."""
    p = params["params"]
    x = obs.astype(jnp.float32)
    if obs.dtype == jnp.uint8:
        x = x / 255.0
    if hp.torso == "mlp":
        x = x.reshape((x.shape[0], -1))
        torso = p["MLPTorso_0"]
        for i in range(len(torso)):
            x = jax.nn.relu(_dense(torso[f"Dense_{i}"], x))
    else:
        torso = p["CNNTorso_0"]
        for i, stride in enumerate(CONV_STRIDES[hp.torso]):
            conv = torso[f"Conv_{i}"]
            x = _conv_valid(x, conv["kernel"].astype(jnp.float32), stride)
            x = jax.nn.relu(x + conv["bias"].astype(jnp.float32))
        x = x.reshape((x.shape[0], -1))
    if "Dense_0" in p:
        x = jax.nn.relu(_dense(p["Dense_0"], x))
    adv = _dense(p["advantage"], x)
    if not hp.dueling:
        return adv
    val = _dense(p["value"], x)
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


def _global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(tree)))


def _pull_sum(params, batch: Dict, pull, hp: Hyper):
    """Sum over the rows of ``pull * Q(obs, action)``: with ``pull`` the
    size of each row's ``d loss / d Q``, its gradient is what the loss's
    gradient would be if every row's TD error had the same sign."""
    q = q_values(params, batch["obs"], hp)
    qa = jnp.take_along_axis(q, batch["action"][:, None].astype(jnp.int32),
                             axis=-1)[:, 0]
    return jnp.sum(pull * qa)


def step(params, target_params, batch: Dict, weights, hp: Hyper) -> Dict:
    """Loss and gradient of one learner step: Q-values of ``obs``, the mean
    weighted Huber loss, per-row |TD| (the priorities), the gradient's
    global norm, the gradient as the optimizer takes it (clipped to
    ``max_grad_norm``), and ``grad_scale``: the norm that gradient would
    have if no two rows' TD errors cancelled — the yardstick for an error of
    the gradient, which its own norm is not where the rows cancel."""
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
        norm = _global_norm(grads)
        scale = 1.0
        if hp.max_grad_norm:
            scale = jnp.where(norm < hp.max_grad_norm, 1.0,
                              hp.max_grad_norm / norm)
            grads = jax.tree.map(lambda g: g * scale, grads)
    return {"q": q.reshape((rows,) + q.shape[2:]), "loss": loss,
            "priorities": abs_td.reshape(rows), "grad_norm": norm,
            "grads": grads,
            "grad_scale": scale * _global_norm(one_way) / rows}


def adam_delta(grads, adam_mu, adam_nu, adam_count, hp: Hyper):
    """The parameter change Adam makes from moments ``(mu, nu)`` after
    ``count`` steps when handed ``grads`` (already clipped)."""
    count = adam_count.astype(jnp.float32) + 1.0
    mu = jax.tree.map(lambda m, g: ADAM_B1 * m + (1 - ADAM_B1) * g,
                      adam_mu, grads)
    nu = jax.tree.map(lambda v, g: ADAM_B2 * v + (1 - ADAM_B2) * g * g,
                      adam_nu, grads)
    return jax.tree.map(
        lambda m, v: -hp.learning_rate * (m / (1 - ADAM_B1 ** count))
        / (jnp.sqrt(v / (1 - ADAM_B2 ** count)) + hp.adam_eps),
        mu, nu)


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
