"""Plain float32 reference of one sequence-learner step of the hybrid core.

Recurrent replay as in ``r2d2_float32.py`` (Kapturowski et al. 2019: windows
with a burn-in prefix that only refreshes the state, n-step double-Q targets
under the invertible value rescaling, importance weights and the eta-mixed
priority a window) around a recurrent Q-network whose core is the first
nine layers of ``nemotron_h`` (``perf/configs/twotower_q.json``): convolutions
and a dense layer in front, ``x + mixer(RMSNorm(x))`` nine times, a final
RMSNorm and linear dueling heads. One letter of the pattern a layer:

``M``  Mamba-2 as the PLAIN PER-STEP RECURRENCE (not the chunked form the
       program computes): ``[z | xBC | dt] = u W_in``; ``xBC = silu(conv_K
       (xBC) + b)`` over the last K steps of the episode; ``dt = softplus(dt
       + dt_bias)``, ``A = -exp(A_log)``; ``h_t = exp(dt_t A) h_{t-1} + dt_t
       x_t (x) B_t`` (head h reads group ``h // (H / G)`` of B and C); ``y_t
       = C_t . h_t + D x_t``; ``y = RMSNorm_grouped(y silu(z)) w``, G groups,
       the gate before the norm; ``out = y W_out``.
``E``  ``s = sigmoid(u W_r)`` in float32, chosen = the top k of ``s +
       bias``, ``w = s[chosen] / sum(s[chosen]) * scale``; ``expert_e(u) =
       W_down,e relu(W_up,e u)^2``; the layer's output is the published sum
       over the chosen experts THAT ARE HELD (``experts_held``: expert
       parallelism's share) plus the shared expert; what the absent experts
       would add is left out, as in the program.
``*``  Grouped-query attention without a position embedding, causal within
       the episode.

A window starts from the EMPTY state (the program's ring stores none for
this core); ``reset[t]`` (``obs[t]`` opens an episode) empties every layer's
memory before step t: the recurrence's ``h``, the convolution's look-back,
the keys an attention query may see. What leaves the burn-in prefix — each
``M`` layer's ``h`` and look-back, each ``*`` layer's keys and values of the
prefix — is a constant to the gradient.

Float32 ``jax.numpy`` under ``default_matmul_precision("highest")``, one
window a block (exact: every term of the loss belongs to one window), each
layer's activations recomputed in its backward (memory, not mathematics);
the parameter tree is read by key names only and nothing is shared with
``models/sequence_core.py`` or ``agents/r2d2.py``. The n-step targets with
their value rescaling are ``r2d2_float32.py``'s (imported), the shared
layers ``plain.py``'s.

Beside the step: what ``perf/harness/reference_check.py`` asks of every
reference module (``perf/README.md``), and the sequence ring's own check,
which is ``r2d2_float32.make_further_check`` (imported) on this
configuration's windows (512 steps every 192; the pair it stores is zero
wide here).
"""
from __future__ import annotations

import functools
from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from perf.reference.plain import (ADAM_B1, CONV_STRIDES, CONVS,  # noqa: F401
                                  adam_delta, clip_by_global_norm, cnn_torso,
                                  dense, global_norm, mlp_torso)
# the loss's targets (n-step, double-Q, value rescaling) and the sequence
# ring's check are the sequence learner's own, whatever its core
from perf.reference.r2d2_float32 import (make_further_check,  # noqa: F401
                                         n_step_targets)

# Largest error allowed for each quantity ``reference_check`` compares (its
# docstring defines them; ``q`` is the online network's Q-values at the
# unroll + n_step positions of every window), by the dtype the configuration
# computes in.
#
# bfloat16: set from a study on the chip at the cell's own widths (PR 44,
# ``perf/tools/reference_study.py --seeds 12 --control 4 --cells
# twotower_q.preset``: 8 windows x 512 steps, 587.4 M parameters; 12 seeded
# states and not 64, a check being 123 s at this size) and from the cell's
# own 13 runs on 13 further seeds (perf/records/pr44: each a whole check),
# each bound three times the largest of the 25 readings or more:
#   grad        0.65-0.87% in the study (median 0.75%), 0.62-1.04% in the
#               runs                                               -> 3.5%
#   priorities  0.17-1.94% (median 0.41%) in the study, 0.20-4.32% in the
#               runs: the eta-mix is nine tenths the LARGEST |TD| of a
#               window, so it sees the same tokens as ``q`` below  -> 13%
#   loss        0.007-0.15% (median 0.08%); r2d2_float32's bound   -> 7.5%
#   optimizer   0.97e-4 - 1.09e-4, float32 against float32         -> 3.3e-4
#   q           5.4-19.6% (median 11.1%): NOT rounding of the usual kind.
#               A token's top-6 experts are a discrete choice: where its
#               6th and 7th scores lie closer than bf16's noise in the
#               residual stream (about one token-layer in a hundred; one in
#               eight of those involves an expert held here) the program
#               and the reference compute that token with a held expert
#               more or less, and its Q-values, and through the state those
#               after it, move by tens of per cent. ``q`` is the LARGEST
#               gap over 3,072 positions x 6 actions, so it reads the worst
#               such token; the window means (loss, priorities, gradient)
#               do not see it                                       -> 60%
# The control, the program's network on float8-rounded weights (e4m3; 4
# seeds): gradient 17.1-20.2% in every seed — 4.9 times the bound, 16 times
# the sound runs' largest: ``grad`` is the number that tells bf16 from a
# coarser type here; |TD| 1.5-3.4%, loss 0.05-1.2%, ``q`` 8-19% (the same
# flips: it tells nothing apart in this cell and is held only against a
# step that is not this network's). A wrong formula — gates not normalised,
# a plain relu, a norm over all channels, attention across an episode's end,
# a gradient through the burn-in — fails in float32 at toy size
# (perf/tests/test_perf_reference_twotower.py). float32 configurations
# differ from the reference by summation order only; no cell runs one, so
# these are the toy tests' bounds, not read on a chip.
TOLERANCES = {
    "bfloat16": {"q": 0.6, "priorities": 0.13, "loss": 0.075, "grad": 0.035,
                 "optimizer": 3.3e-4},
    "float32": {"q": 1e-4, "priorities": 1e-4, "loss": 1e-4, "grad": 1e-3,
                "optimizer": 1e-3},
}


class Core(NamedTuple):
    """The core's shape, as the configuration states it."""

    pattern: str
    norm_eps: float
    heads: int          # M: heads x head_dim channels
    head_dim: int
    state: int          # N
    groups: int         # G: B and C a group
    conv_kernel: int
    routed: int         # E: experts the router scores
    held: Tuple[int, ...]
    per_token: int
    scale: float
    attention_heads: int
    kv_heads: int
    attention_dim: int


class Hyper(NamedTuple):
    """What the step needs from the configuration (hashable: jit-static)."""

    torso: str
    core: Core
    dueling: bool
    double_dqn: bool
    value_rescale: bool
    burn_in: int
    unroll: int
    n_step: int
    gamma: float
    eta: float
    huber_delta: float
    learning_rate: float
    adam_eps: float
    max_grad_norm: float


# -- the layers, one window [T, ...] at a time -------------------------------

def rms_norm(x, weight, eps: float, groups: int = 1):
    """``x / sqrt(mean(x^2) + eps) * weight``, the mean over each of
    ``groups`` equal slices of the last axis."""
    parts = x.reshape(x.shape[:-1] + (groups, -1))
    parts = parts / jnp.sqrt(jnp.mean(parts ** 2, axis=-1, keepdims=True)
                             + eps)
    return parts.reshape(x.shape) * weight


def relu2_mlp(u, up, down):
    """``relu(u W_up)^2 W_down``: ``nemotron_h``'s expert and shared expert
    (``mlp_hidden_act`` relu2, no gate projection)."""
    return jnp.maximum(u @ up, 0.0) ** 2 @ down


def mamba2(p: Dict, u, reset, memory, core: Core):
    """``u [T, hidden]`` -> ``[T, hidden]`` by the per-step recurrence.
    ``memory`` is ``(the last K-1 steps' xBC [K-1, channels], h [H, P, N])``
    entering step 0; returned as it stands after step T-1."""
    H, P, G, N, K = (core.heads, core.head_dim, core.groups, core.state,
                     core.conv_kernel)
    inner = H * P
    proj = u @ p["in_proj"]
    z, xbc, dt = (proj[:, :inner], proj[:, inner:-H], proj[:, -H:])
    dt = jax.nn.softplus(dt + p["dt_bias"])
    a = -jnp.exp(p["A_log"])

    def one_step(memory, inputs):
        lookback, h = memory
        xbc_t, dt_t, reset_t = inputs
        keep = 1.0 - reset_t
        lookback, h = lookback * keep, h * keep
        taps = jnp.concatenate([lookback, xbc_t[None]])         # [K, C]
        conv = jax.nn.silu(jnp.sum(taps * p["conv_kernel"], axis=0)
                           + p["conv_bias"])
        x = conv[:inner].reshape(H, P)
        # head h reads group h // (H / G) of B and C
        b = jnp.repeat(conv[inner:inner + G * N].reshape(G, N), H // G, 0)
        c = jnp.repeat(conv[inner + G * N:].reshape(G, N), H // G, 0)
        h = (jnp.exp(dt_t * a)[:, None, None] * h
             + (dt_t[:, None] * x)[:, :, None] * b[:, None, :])
        y = jnp.sum(h * c[:, None, :], axis=-1) + p["D"][:, None] * x
        return (taps[1:], h), y.reshape(inner)

    memory, y = jax.lax.scan(one_step, memory,
                             (xbc, dt, reset.astype(jnp.float32)))
    y = rms_norm(y * jax.nn.silu(z), p["norm"], core.norm_eps, groups=G)
    return y @ p["out_proj"], memory


def gates(picked, core: Core):
    """The chosen experts' weights from their scores ``[T, k]``: normalised
    to sum to one (``norm_topk_prob``), times ``routed_scaling_factor``."""
    return picked / jnp.sum(picked, axis=-1, keepdims=True) * core.scale


def experts(p: Dict, u, reset, memory, core: Core):
    """``u [T, hidden]`` -> the held experts' part of the routed sum plus
    the shared expert."""
    scores = jax.nn.sigmoid(u @ p["router"])                    # [T, routed]
    _, chosen = jax.lax.top_k(scores + p["e_score_correction_bias"],
                              core.per_token)
    weight = gates(jnp.take_along_axis(scores, chosen, axis=-1), core)
    out = relu2_mlp(u, p["shared_up"], p["shared_down"])
    for local, expert in enumerate(core.held):
        # this expert's weight for each token: its gate where it was chosen
        gate = jnp.sum(jnp.where(chosen == expert, weight, 0.0), axis=-1)
        out = out + gate[:, None] * relu2_mlp(
            u, p["experts_up"][:, local], p["experts_down"][local])
    return out, memory


def visible(key_position, key_episode, position, episode):
    """``[T, S]``: query t sees key s where s is not after it and lies in
    its episode."""
    return jnp.logical_and(key_position[None, :] <= position[:, None],
                           key_episode[None, :] == episode[:, None])


def attention(p: Dict, u, reset, memory, core: Core):
    """``u [T, hidden]`` -> ``[T, hidden]``. ``memory`` is ``(keys, values
    [S0, KV, D], the episode count at each of those steps [S0])`` of the
    window's earlier steps; step t sees the steps up to itself that lie in
    its own episode."""
    heads, kv, D = core.attention_heads, core.kv_heads, core.attention_dim
    old_k, old_v, old_episode = memory
    before = old_episode[-1] if old_episode.shape[0] else 0
    episode = before + jnp.cumsum(reset.astype(jnp.int32))
    T = u.shape[0]
    q = (u @ p["q_proj"]).reshape(T, heads, D)
    keys = jnp.concatenate([old_k, (u @ p["k_proj"]).reshape(T, kv, D)])
    values = jnp.concatenate([old_v, (u @ p["v_proj"]).reshape(T, kv, D)])
    episodes = jnp.concatenate([old_episode, episode])
    position = old_k.shape[0] + jnp.arange(T)
    see = visible(jnp.arange(keys.shape[0]), episodes, position, episode)
    # query head i reads KV head i // (heads / kv)
    k, v = (jnp.repeat(x, heads // kv, axis=1) for x in (keys, values))
    scores = jnp.einsum("thd,shd->hts", q, k) / np.sqrt(D)
    weights = jax.nn.softmax(jnp.where(see[None], scores, -jnp.inf), axis=-1)
    out = jnp.einsum("hts,shd->thd", weights, v).reshape(T, heads * D)
    return out @ p["o_proj"], (keys, values, episodes)


MIXERS = {"M": mamba2, "E": experts, "*": attention}


def empty_memory(core: Core):
    """What every layer remembers before a window's first step."""
    inner = core.heads * core.head_dim
    channels = inner + 2 * core.groups * core.state
    kv = (0, core.kv_heads, core.attention_dim)
    return tuple({
        "M": (jnp.zeros((core.conv_kernel - 1, channels)),
              jnp.zeros((core.heads, core.head_dim, core.state))),
        "E": (),
        "*": (jnp.zeros(kv), jnp.zeros(kv), jnp.zeros((0,), jnp.int32)),
    }[kind] for kind in core.pattern)


def core_forward(p: Dict, x, reset, memory, core: Core):
    """The nine layers and the final norm over one window's steps ``x [T,
    hidden]``; each layer's activations are recomputed in its backward."""
    new_memory = []
    for i, kind in enumerate(core.pattern):
        layer = p[f"layer_{i}"]

        @jax.checkpoint
        def block(layer, x, memory_i, kind=kind):
            out, memory_i = MIXERS[kind](
                layer["mixer"], rms_norm(x, layer["norm"], core.norm_eps),
                reset, memory_i, core)
            return x + out, memory_i

        x, memory_i = block(layer, x, memory[i])
        new_memory.append(memory_i)
    return rms_norm(x, p["norm_f"], core.norm_eps), tuple(new_memory)


def _embed(torso: Dict, frames, hp: Hyper):
    """[N, H, W, C] frames -> [N, hidden]: the convolutions and the dense
    layer in front of the core."""
    x = frames.astype(jnp.float32)
    if frames.dtype == jnp.uint8:
        x = x / 255.0
    if hp.torso == "mlp":
        x = mlp_torso(torso["MLPTorso_0"], x)
    else:
        x = cnn_torso(torso["CNNTorso_0"], x, CONV_STRIDES[hp.torso])
    return jax.nn.relu(dense(torso["embed"], x))


def leave_burn_in(memory):
    """What the burn-in prefix leaves in the layers' memories is a constant
    to the gradient: the prefix refreshes the state and learns nothing."""
    return jax.lax.stop_gradient(memory)


def q_window(params: Dict, obs, reset, hp: Hyper):
    """Q-values ``[unroll + n_step, A]`` of ONE window ``obs [T, ...]`` at
    the positions after the burn-in, from the empty state; what the burn-in
    prefix leaves in the layers' memories is a constant to the gradient."""
    p = params["params"]
    x = _embed(p["torso"], obs, hp)
    memory = empty_memory(hp.core)
    if hp.burn_in:
        _, memory = core_forward(p["core"], x[:hp.burn_in],
                                 reset[:hp.burn_in], memory, hp.core)
        memory = leave_burn_in(memory)
    hidden, _ = core_forward(p["core"], x[hp.burn_in:], reset[hp.burn_in:],
                             memory, hp.core)
    adv = dense(p["advantage"], hidden)
    if not hp.dueling:
        return adv
    return (dense(p["value"], hidden) + adv
            - jnp.mean(adv, axis=-1, keepdims=True))


# -- the loss: r2d2_float32's, on one window ---------------------------------

def _q_taken(params, window: Dict, hp: Hyper):
    """The online network's Q-values after the burn-in, and ``[unroll]``
    those of the actions taken at the loss positions."""
    q_online = q_window(params, window["obs"], window["reset"], hp)
    taken = window["action"][hp.burn_in:hp.burn_in + hp.unroll]
    return q_online, jnp.take_along_axis(
        q_online[:hp.unroll], taken[:, None].astype(jnp.int32),
        axis=-1)[:, 0]


def _loss(params, target_params, window: Dict, hp: Hyper):
    """``weight * mean over the unroll of huber(TD)`` of one window; aux:
    |TD| ``[unroll]`` and the online Q-values."""
    q_online, qa = _q_taken(params, window, hp)
    q_target = q_window(target_params, window["obs"], window["reset"], hp)
    # r2d2_float32's targets over [T, S]: this window is its one sequence
    td = qa - jax.lax.stop_gradient(n_step_targets(
        q_online[:, None], q_target[:, None],
        window["reward"][hp.burn_in:, None],
        window["done"][hp.burn_in:, None], hp)[:, 0])
    quad = jnp.minimum(jnp.abs(td), hp.huber_delta)
    huber = 0.5 * quad * quad + hp.huber_delta * (jnp.abs(td) - quad)
    return window["weights"] * jnp.mean(huber), (jnp.abs(td), q_online)


def _pull_sum(params, window: Dict, pull, hp: Hyper):
    """Sum of ``pull * Q(obs, action)`` over the loss positions: with
    ``pull`` the size of each position's ``d loss / d Q``, its gradient is
    what the loss's gradient would be if every TD error had the same
    sign."""
    return jnp.sum(pull * _q_taken(params, window, hp)[1])


def step(params, target_params, batch: Dict, hp: Hyper) -> Dict:
    """Loss and gradient of one learner step on a batch as ``seeded_batch``
    lays it out (time-major ``[T, S, ...]``), one window at a time: the
    online Q-values at the training positions, the mean over windows of the
    weighted mean Huber loss, the window priorities, the gradient's global
    norm, the gradient as the optimizer takes it (clipped), and
    ``grad_scale``: the norm that gradient would have if no two TD errors
    cancelled."""
    seqs = batch["weights"].shape[0]
    windows = {k: jnp.moveaxis(batch[k], 1, 0)
               for k in ("obs", "action", "reward", "done", "reset")}
    windows["weights"] = batch["weights"]

    def one_window(total, window):
        (loss, (abs_td, q)), grads = jax.value_and_grad(
            _loss, has_aux=True)(params, target_params, window, hp)
        pull = (window["weights"] * jnp.minimum(abs_td, hp.huber_delta)
                / hp.unroll)
        one_way = jax.grad(_pull_sum)(params, window, pull, hp)
        return (jax.tree.map(jnp.add, total, (loss, grads, one_way)),
                (abs_td, q))

    with jax.default_matmul_precision("highest"):
        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                             params)
        (loss, grads, one_way), (abs_td, q) = jax.lax.scan(
            one_window, (jnp.float32(0.0), zeros, zeros), windows)
        abs_td, q = jnp.moveaxis(abs_td, 0, 1), jnp.moveaxis(q, 0, 1)
        loss = loss / seqs
        grads = jax.tree.map(lambda g: g / seqs, grads)
        grads, norm, scale = clip_by_global_norm(grads, hp.max_grad_norm)
        priorities = (hp.eta * jnp.max(abs_td, axis=0)
                      + (1.0 - hp.eta) * jnp.mean(abs_td, axis=0))
    return {"q": q, "loss": loss, "priorities": priorities,
            "grad_norm": norm, "grads": grads,
            "grad_scale": scale * global_norm(one_way) / seqs}


def hyper_from_config(cfg) -> Hyper:
    """Read the program's ``ExperimentConfig`` by attribute; refuse what this
    reference does not compute rather than compare against something else."""
    net, learner, replay = cfg.network, cfg.learner, cfg.replay
    core = getattr(net, "core", None)
    unsupported = [name for name, on in (
        ("network.noisy", net.noisy), ("network.num_atoms", net.num_atoms > 1),
        ("network.iqn", net.iqn), ("network.lstm_size", net.lstm_size),
        ("network.core.kind", getattr(core, "kind", None) != "hybrid"),
        ("no network.hidden", not net.hidden),
        ("no replay.unroll_length", replay.unroll_length <= 0),
        ("learner.munchausen", learner.munchausen),
        ("learner.target_tau", learner.target_tau > 0),
        ("learner.lr_schedule", learner.lr_schedule != "constant")) if on]
    if unsupported or net.torso not in ("mlp", *CONV_STRIDES):
        raise NotImplementedError(
            f"twotower_float32 does not cover {unsupported or net.torso}")
    return Hyper(
        torso=net.torso,
        core=Core(pattern=core.pattern, norm_eps=float(core.norm_eps),
                  heads=core.mamba_num_heads, head_dim=core.mamba_head_dim,
                  state=core.ssm_state_size, groups=core.n_groups,
                  conv_kernel=core.conv_kernel,
                  routed=core.n_routed_experts,
                  held=tuple(core.experts_held),
                  per_token=core.num_experts_per_tok,
                  scale=float(core.routed_scaling_factor),
                  attention_heads=core.num_attention_heads,
                  kv_heads=core.num_key_value_heads,
                  attention_dim=core.head_dim),
        dueling=bool(net.dueling), double_dqn=bool(learner.double_dqn),
        value_rescale=bool(learner.value_rescale),
        burn_in=int(replay.burn_in), unroll=int(replay.unroll_length),
        n_step=int(learner.n_step), gamma=float(learner.gamma),
        eta=float(replay.priority_mix),
        huber_delta=float(learner.huber_delta),
        learning_rate=float(learner.learning_rate),
        adam_eps=float(learner.adam_eps),
        max_grad_norm=float(learner.max_grad_norm))


# -- what the harness asks of a reference module, beside the step -----------

def make_program(cfg, env, net):
    """The program's side of the comparison: ``init(key)`` and
    ``train_step(state, batch)`` of the learner ``train.train`` builds for a
    recurrent configuration (``agents/r2d2.py make_r2d2_learner``), and
    ``q_of(params, batch)``: the program's network over the whole window as
    the learner runs it — the burn-in from the empty state, then the
    training positions from what the burn-in left. ``batch`` is a
    ``seeded_batch``."""
    from dist_dqn_tpu.agents.r2d2 import make_r2d2_learner
    from dist_dqn_tpu.types import SequenceSample

    init, train_step = make_r2d2_learner(net, cfg.learner, cfg.replay)
    burn = cfg.replay.burn_in

    def example():
        return jnp.zeros(tuple(env.observation_shape),
                         np.dtype(env.observation_dtype))

    def q_of(params, batch):
        obs, reset = batch["obs"], batch["reset"]
        carry = net.window_state(batch["start_state"], obs.shape[1], burn)
        if burn:
            carry, _ = net.apply(params, carry, obs[:burn], reset[:burn],
                                 method=net.unroll)
        return net.apply(params, carry, obs[burn:], reset[burn:],
                         method=net.unroll)[1]

    return (lambda key: init(key, example()),
            lambda state, batch: train_step(state, SequenceSample(**batch)),
            q_of)


def _noise_key(seed):
    """XLA's own bit generator: threefry takes seconds to compile and is no
    better noise for a comparison."""
    return jax.random.key(seed, impl="rbg")


@functools.partial(jax.jit, static_argnums=(2, 3))
def _frames(seed, index, shape, dtype: str):
    """Frames of noise, made where they are used (a window batch of the
    cell's shape is 116 MB)."""
    key = jax.random.fold_in(_noise_key(seed), index)
    if dtype == "uint8":
        return jax.random.bits(key, shape, jnp.uint8)
    return jax.random.normal(key, shape, jnp.float32)


def seeded_batch(seed: int, index: int, batch_size: int, cfg, env) -> Dict:
    """Batch ``index`` of ``seed``: ``batch_size`` windows in the sequence
    learner's time-major layout, from the seed alone. Frames are noise (no
    two alike; drawn on the device), the stored start state is EMPTY (the
    ring stores none for this core), half of the windows hold an episode's
    end somewhere (``done`` at step t, ``reset`` at t+1: in the burn-in a
    reset only, among the loss positions a return cut short as well), and
    importance ``weights`` lie in (0, 1]."""
    rng = np.random.default_rng([seed, index])
    steps = (cfg.replay.burn_in + cfg.replay.unroll_length
             + cfg.learner.n_step)
    shape = (steps, batch_size)
    obs = _frames(np.uint32(seed % 2 ** 32), np.uint32(index),
                  shape + tuple(env.observation_shape),
                  np.dtype(env.observation_dtype).name)
    done = np.zeros(shape, bool)
    ends = rng.random(batch_size) < 0.5
    done[rng.integers(0, steps - 1, batch_size)[ends],
         np.flatnonzero(ends)] = True
    reset = np.concatenate([np.zeros((1, batch_size), bool), done[:-1]])
    return {
        "obs": obs,
        "action": rng.integers(0, env.num_actions, shape).astype(np.int32),
        # One sign, and n-step sums that rescale to both sides of
        # huber_delta = 1 and above a fresh network's Q-values: the TD
        # errors then share a sign and their gradients add up.
        "reward": rng.choice([0.2, 0.4, 0.6, 0.8], shape).astype(np.float32),
        "done": done,
        "reset": reset,
        "start_state": (),
        "weights": (rng.uniform(0.2, 1.0, batch_size)
                    if cfg.replay.prioritized
                    else np.ones(batch_size)).astype(np.float32),
        "t_idx": np.arange(batch_size, dtype=np.int32),
        "b_idx": np.zeros(batch_size, np.int32),
    }


def forward_flops_per_step(cfg, env) -> Dict[str, float]:
    """Multiply-accumulates x 2 one step of one window REQUIRES in a forward
    pass, by part. The routed experts count the rows the routing sends to
    the held experts at balance (``per_token * held / routed`` expert
    evaluations a token), not the dense product the program computes them
    by; the attention counts the mean number of keys a causal query of the
    window sees; elementwise work is left out."""
    from perf.reduce import flops

    net, core = cfg.network, cfg.network.core
    if net.torso not in CONVS:
        raise NotImplementedError(
            f"twotower_float32 counts {sorted(CONVS)} torsos, not "
            f"{net.torso!r}")
    hidden = net.hidden
    window = (cfg.replay.burn_in + cfg.replay.unroll_length
              + cfg.learner.n_step)
    torso = flops.cnn_layer_macs(tuple(env.observation_shape),
                                 CONVS[net.torso], hidden,
                                 env.num_actions, False)[:-1]
    H, P, G, N = (core.mamba_num_heads, core.mamba_head_dim, core.n_groups,
                  core.ssm_state_size)
    inner = H * P
    ssm = (hidden * (2 * inner + 2 * G * N + H) + inner * hidden
           + core.conv_kernel * (inner + 2 * G * N)
           + 3 * H * P * N)              # decay+add, and the read by C
    heads, kv, D = (core.num_attention_heads, core.num_key_value_heads,
                    core.head_dim)
    attention = (hidden * (heads + 2 * kv) * D + heads * D * hidden
                 + 2 * heads * D * (window + 1) / 2)
    expert = 2 * hidden * core.moe_intermediate_size
    macs = {
        "torso": float(sum(torso)),
        "ssm": float(ssm),
        "attention": float(attention),
        "moe_router": float(hidden * core.n_routed_experts),
        "moe_routed": (core.num_experts_per_tok * len(core.experts_held)
                       / core.n_routed_experts * expert),
        "moe_shared": float(
            2 * hidden * core.moe_shared_expert_intermediate_size),
        "heads": float(hidden * (env.num_actions + (1 if net.dueling else 0))),
    }
    count = {"M": "ssm", "*": "attention"}
    per_step = {"torso": 2.0 * macs["torso"], "heads": 2.0 * macs["heads"]}
    for kind in core.pattern:
        for part in ((count[kind],) if kind in count
                     else ("moe_router", "moe_routed", "moe_shared")):
            per_step[part] = per_step.get(part, 0.0) + 2.0 * macs[part]
    return per_step


def grad_step_flops(cfg, env) -> float:
    """FLOPs one grad step requires (``forward_flops_per_step``): both
    networks forward over the whole window (the heads at the training
    positions only), the online network backward — two products a forward
    product — over its ``unroll + n_step`` positions after the burn-in,
    whose state is a constant. Recomputed forwards, elementwise work and
    the optimizer are left out."""
    per_step = forward_flops_per_step(cfg, env)
    train = cfg.replay.unroll_length + cfg.learner.n_step
    window = cfg.replay.burn_in + train
    body = sum(v for k, v in per_step.items() if k != "heads")
    forward = 2 * (window * body + train * per_step["heads"])
    backward = 2 * train * (body + per_step["heads"])
    return float(cfg.learner.batch_size * (forward + backward))
